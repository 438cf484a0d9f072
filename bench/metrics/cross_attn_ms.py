"""Client model, cross-attention: device milliseconds per round of the
ops the program tags ``cross_attn`` (the conditioning's projection, each
layer's cross norm, q/k/v/o projections and attention to the
conditioning, forward and backward).  Moves ``round_s``."""

from bench.metrics._parts import part_ms


def read(ctx):
    return part_ms(ctx, "cross_attn")
