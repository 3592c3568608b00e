"""Device milliseconds per actor unroll (T steps of B environments and the
policy forward, one compiled scan)."""

from chip.devtrace import per_call_ms

PROGRAM = "jit_unroll"


def read(ctx):
    return per_call_ms(ctx["trace"], PROGRAM)
