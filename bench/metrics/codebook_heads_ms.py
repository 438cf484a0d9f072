"""Client model, codebook heads: device milliseconds per round of the ops
the program tags ``heads`` (the final and exit norms, the per-codebook
output heads and the delay-pattern cross-entropy over them, forward and
backward).  Moves ``round_s``."""

from bench.metrics._parts import part_ms


def read(ctx):
    return part_ms(ctx, "heads")
