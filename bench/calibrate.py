"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 [--control]
        [--highest] [--fault half_batch]

Runs in one process on the chip.  For each seed it drives the program's
set-up rounds (the very rounds a benchmark run compares), runs the
reference over them at the configuration's stated precision, and prints
one JSON line of the compared numbers for each count of compared rounds up
to the cell's ``check_rounds``; the line of all of them carries the verdict
that the cell's limits give (``correct``).  Further lines, each against
the same reference:

* ``--control``: the control, the reference one precision step down
  (``bench/reference/``) put in the program's place;
* ``--fault half_batch``: the program with every client loss seeing only
  the first half of its batch, the mean taken over the rest;
* ``--highest``: the program, and the reference at its stated precision,
  each against the reference at ``highest``, which shows how far the
  stated precision lies from exact float32.

The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import run  # noqa: E402


class HalfBatch:
    """An adapter whose client losses see half of each batch."""

    def __init__(self, inner):
        self._inner = inner

    def _half(self, fn):
        import jax
        return lambda p, b: fn(p, jax.tree.map(
            lambda x: x[: x.shape[0] // 2], b))

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if name in ("loss_simple", "loss_side", "loss_complex"):
            return self._half(attr)
        return attr


def plant(trainer, fault: str):
    """Plant ``fault`` in a freshly built trainer: ``half_batch`` (see the
    module doc) or ``state_unchanged`` (each round returns the server model
    it was given)."""
    import jax
    import jax.numpy as jnp
    if fault == "half_batch":
        trainer.adapter = HalfBatch(trainer.adapter)
        donate = (0, 1) if jax.default_backend() != "cpu" else ()
        trainer._round_fn = jax.jit(trainer._make_round_fn(),
                                    donate_argnums=donate)
        trainer._dispatch.jit_fn = trainer._round_fn
    elif fault == "state_unchanged":
        inner = trainer.run_round

        def run_round():
            before = jax.tree.map(jnp.copy, trainer.server.complex)
            metrics = inner()
            trainer.server.complex = before
            return metrics

        trainer.run_round = run_round
    elif fault != "none":
        raise ValueError(f"unknown fault {fault!r}")
    return trainer


def _program(cell: dict, seed: int, fault: str) -> dict:
    from bench import families
    traffic = cell["traffic_data"]
    t = time.perf_counter()
    trainer = plant(families.build_trainer(cell["cfg"], traffic, seed), fault)
    program = run.drive_setup(trainer, traffic["check_rounds"])
    program["seconds"] = time.perf_counter() - t
    del trainer
    gc.collect()
    return program


def _lines(cell, seed, kind, seconds, w0, got, want, rounds) -> list:
    """The numbers of ``got`` against ``want`` for 1..``rounds`` compared
    rounds; the last line with the cell's verdict."""
    from bench import check
    out = []
    for n in range(1, rounds + 1):
        nums = check.numbers(w0, got[0], got[1][:n], want[0], want[1][:n], n)
        line = {"seed": seed, "kind": kind, "rounds": n, "seconds": seconds,
                **nums}
        if n == rounds:
            line["correct"] = check.correct(check.judged(cell, nums))
        out.append(line)
    return out


def readings(cell: dict, seed: int, *, control: bool = False,
             highest: bool = False, fault: str = "none") -> list:
    """The compared numbers of one seed (see the module doc)."""
    from bench import check
    rounds = cell["traffic_data"]["check_rounds"]
    programs = {"program": _program(cell, seed, "none")}
    if fault != "none":
        programs[fault] = _program(cell, seed, fault)
    t = time.perf_counter()
    w0, ref_models, ref_losses = check.reference_rounds(cell, seed, rounds)
    ref = (ref_models, ref_losses)
    t_ref = time.perf_counter() - t
    out = [{"seed": seed, "kind": "reference", "seconds": t_ref,
            "losses": ref_losses}]
    for kind, p in programs.items():
        out += _lines(cell, seed, kind, p["seconds"], w0,
                      (p["models"], p["losses"]), ref, rounds)
    others = (["control"] if control else []) + (["highest"] if highest
                                                 else [])
    for precision in others:
        t = time.perf_counter()
        _, models, losses = check.reference_rounds(cell, seed, rounds,
                                                   precision)
        secs = time.perf_counter() - t
        if precision == "control":
            out += _lines(cell, seed, "control", secs, w0, (models, losses),
                          ref, rounds)
        else:
            p = programs["program"]
            out += _lines(cell, seed, "program_vs_highest", secs, w0,
                          (p["models"], p["losses"]), (models, losses),
                          rounds)
            out += _lines(cell, seed, "stated_vs_highest", secs, w0, ref,
                          (models, losses), rounds)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--highest", action="store_true")
    ap.add_argument("--fault", default="none", choices=("none", "half_batch"))
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run._setup_jax()
    run.require_chips(cell["chips"])
    for seed in (int(s) for s in args.seeds.split(",")):
        for line in readings(cell, seed, control=args.control,
                             highest=args.highest, fault=args.fault):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
