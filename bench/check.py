"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, the plain
reference (``bench/reference/``), at the precision the configuration
states, runs the cell's set-up rounds from the same seed, weights, data,
cohorts and keys, and three numbers compare what the program produced in
those rounds with it:

* ``loss_gap``: the largest relative gap between the program's and the
  reference's mean client loss, over the rounds and both populations;
* ``update_gap``: the server's first update ``w1 - w0`` (the pseudo-
  gradient the server step applies), leaf by leaf: the gap between the
  program's and the reference's norm of the leaf, over the reference's norm
  of that leaf or of the median leaf, whichever is larger; the worst leaf;
* ``change_gap``: the same of the change ``wR - w0`` after the last set-up
  round, as the window starts from it.

Leaves whose reference update is under a thousandth of the median leaf's
move by round-off alone and are left out of both.  Each number that
``bench/limits/<cell>.json`` gives a limit must be at most that limit; a
number it leaves out is not compared in that cell (``PERF.md`` says why).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import numpy as np

from bench import families, reference


def _leaf_norms(a, b) -> List[float]:
    return [float(np.linalg.norm(np.asarray(x, np.float64)
                                 - np.asarray(y, np.float64)))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def _norm_gap(got: List[float], want: List[float], keep) -> float:
    floor = float(np.median(want))
    return max((abs(g - w) / max(w, floor)
                for g, w, k in zip(got, want, keep) if k), default=0.0)


def numbers(w0, got_models: Dict[int, object],
            got_losses: List[Tuple[float, float]], ref_models: Dict[int, object],
            ref_losses: List[Tuple[float, float]], rounds: int
            ) -> Dict[str, float]:
    """The three compared numbers of one run (see the module docstring)."""
    loss_gap = max(abs(g - w) / max(abs(w), 1e-30)
                   for gr, wr in zip(got_losses, ref_losses)
                   for g, w in zip(gr, wr))
    ref1 = _leaf_norms(ref_models[1], w0)
    keep = [n >= 1e-3 * float(np.median(ref1)) for n in ref1]
    update_gap = _norm_gap(_leaf_norms(got_models[1], w0), ref1, keep)
    change_gap = _norm_gap(_leaf_norms(got_models[rounds], w0),
                           _leaf_norms(ref_models[rounds], w0), keep)
    out = {"loss_gap": loss_gap, "update_gap": update_gap,
           "change_gap": change_gap}
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}


def reference_rounds(cell: dict, seed: int, rounds: int,
                     precision: str = "stated"):
    """(initial weights, models by round, losses) of the reference (or, by
    ``precision``, of its control)."""
    cfg, traffic = cell["cfg"], cell["traffic_data"]
    _, w0 = families.weights(cfg, seed)
    client_data = families.data(cfg, traffic, seed)
    models, losses = reference.run_rounds(
        cfg, traffic, seed, w0, lambda ids: families.stack(client_data, ids),
        rounds, precision)
    w0 = jax.tree.map(np.asarray, w0)
    return w0, {r + 1: m for r, m in enumerate(models)}, losses


def judged(cell: dict, got: Dict[str, float]) -> dict:
    """``{name: {"value", "limit"}}`` of each number the cell compares."""
    return {k: {"value": got[k], "limit": limit}
            for k, limit in cell["limits"].items()}


def correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def check(cell: dict, seed: int, program: dict, rounds: int) -> dict:
    """``{name: {"value", "limit"}}`` of the program's run."""
    w0, ref_models, ref_losses = reference_rounds(cell, seed, rounds)
    return judged(cell, numbers(w0, program["models"], program["losses"],
                                ref_models, ref_losses, rounds))
