"""Kernels: summed device time of the ragged paged-attention kernel
over the ticks counted in the traced span. A time, not a roofline
share: the bytes a tick attends are not counted anywhere yet."""
from harness.readers import op_seconds, per_tick_ms

PATTERN = r"^ragged_paged_attention"


def read(ctx):
    return per_tick_ms(ctx, op_seconds(ctx, PATTERN))
