"""Round jit, local SGD: the model FLOPs of a round's local training
(``bench/flops.py``) over the traced run's round time and the chip's bf16
peak.  The whole round's share of the peak, so it bounds every kernel's
share on the path.  Moves ``round_s``."""


def read(ctx):
    return 100.0 * ctx.flops["total"] / ctx.round_s / ctx.peaks["flops_bf16"]
