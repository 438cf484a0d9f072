"""Client model, self-attention: device milliseconds per round of the ops
the program tags ``self_attn`` (each layer's pre-norm, q/k/v/o
projections and causal attention, forward and backward).  Moves
``round_s``."""

from bench.metrics._parts import part_ms


def read(ctx):
    return part_ms(ctx, "self_attn")
