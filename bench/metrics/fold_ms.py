"""Fold: device milliseconds per round of the ops the program tags
``fold`` (the ``masked_agg`` kernels with the packing and casts around
them).  Moves ``round_s``."""

from bench.metrics._scopes import stage_ms


def read(ctx):
    return stage_ms(ctx, "fold")
