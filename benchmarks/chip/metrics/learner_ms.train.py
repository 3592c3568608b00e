"""Device milliseconds per IMPALA learner step (the jitted train step)."""

from chip.devtrace import per_call_ms

PROGRAM = "jit_train_step"


def read(ctx):
    return per_call_ms(ctx["trace"], PROGRAM)
