"""Round jit, local SGD: device milliseconds per round of the ops the
program tags ``local_sgd`` (the vmapped client trainers' forward,
backward and optimizer step, SCAFFOLD's correction, the NaN-device
test).  Moves ``round_s``."""

from bench.metrics._scopes import stage_ms


def read(ctx):
    return stage_ms(ctx, "local_sgd")
