"""Serving engine: device idle time, per tick of the traced span, while
the engine thread was in ``serving.phase.admit``, ``.build`` or
``.dispatch`` — the chip waiting for the next program to reach it."""
from harness.hostspans import idle_ms_per_tick


def read(ctx):
    return idle_ms_per_tick(ctx, "admit", "build", "dispatch")
