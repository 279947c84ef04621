"""Serving engine: device idle time, per tick of the traced span, while
the engine thread was in ``serving.phase.emit`` — tokens streamed,
requests retired and the tick recorded before the next one is built."""
from harness.hostspans import idle_ms_per_tick


def read(ctx):
    return idle_ms_per_tick(ctx, "emit")
