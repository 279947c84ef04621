"""Serving engine: device idle time, per tick of the traced span, while
the engine thread was in ``serving.phase.readback`` — the device done,
the host not yet woken with the tokens."""
from harness.hostspans import idle_ms_per_tick


def read(ctx):
    return idle_ms_per_tick(ctx, "readback")
