"""Host loop: mean wall milliseconds per round that the host spends
outside the round jit's execution: the program's ``round`` span less the
``execute`` span inside it (matched on the round), on the host clock.
Sampling, gathering, dispatch and the reads of the round's results; the
device waits for most of it.  Moves ``round_s``."""


def read(ctx):
    execute = {e["round"]: e["dur_s"] for e in ctx.spans
               if e["name"] == "execute" and e.get("dur_s") is not None}
    outside = [e["dur_s"] - execute[e["round"]] for e in ctx.spans
               if e["name"] == "round" and e["round"] in execute]
    return 1e3 * sum(outside) / len(outside) if outside else None
