"""Fold kernels: the bytes a round's fold calls must move
(``bench/flops.py``) over their device time and the chip's HBM bandwidth.
Moves ``round_s``."""

from bench import trace
from bench.metrics._folds import is_fold


def read(ctx):
    s = trace.kernel_seconds(ctx.trace["ops"], is_fold) / ctx.rounds
    if s <= 0:
        return None
    return 100.0 * sum(ctx.fold_bytes) / s / ctx.peaks["hbm_bytes_per_s"]
