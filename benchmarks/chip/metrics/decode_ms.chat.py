"""Device milliseconds per call of the decode session's step (one token
for every slot: ``core.generate``'s jitted ``step``), in a serving cell."""

from chip.devtrace import per_call_ms

PROGRAM = "jit_step"


def read(ctx):
    return per_call_ms(ctx["trace"], PROGRAM)
