"""Device: the share of the traced window in which no op ran on the chip
(1 - union of op intervals / window).  Moves ``round_s``."""


def read(ctx):
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
