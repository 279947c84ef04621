"""Model step: device busy time inside the traced span over the ticks
counted there (``decode_steps`` counter delta)."""
from harness.readers import per_tick_ms


def read(ctx):
    tr = ctx.get("trace")
    return per_tick_ms(ctx, tr["busy_s"] if tr else None)
