"""Host loop: mean wall time per round of the program's ``sample_gather``
telemetry span (cohort sampling and the stacking of the cohort's data), on
the host clock.  Moves ``round_s``."""


def read(ctx):
    durs = [e["dur_s"] for e in ctx.spans
            if e["name"] == "sample_gather" and e.get("dur_s") is not None]
    return 1e3 * sum(durs) / len(durs) if durs else None
