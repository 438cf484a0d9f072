"""Fold kernels (``kernels/masked_agg``): device milliseconds per round of
the accumulating fold kernels, summed from the trace.  Moves ``round_s``."""

from bench import trace
from bench.metrics._folds import is_fold


def read(ctx):
    s = trace.kernel_seconds(ctx.trace["ops"], is_fold)
    return 1e3 * s / ctx.rounds if s > 0 else None
