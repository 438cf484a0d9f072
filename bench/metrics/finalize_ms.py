"""Finalize: device milliseconds per round of the ops the program tags
``finalize`` (the sums normalized and unpacked into the new server
model, SCAFFOLD's server control variate).  Moves ``round_s``."""

from bench.metrics._scopes import stage_ms


def read(ctx):
    return stage_ms(ctx, "finalize")
