"""Model step: device self time, per tick of the traced span, under the
scope ``attn.full`` of ``models/mimo_v2_flash.py``: a FULL attention
layer's norm, q / k / v projections, partial rotary, the kernel (label
``attn.full.kernel``) and the output projection, over the full layers.
The K and V rows' scatter into the paged pool is ``kv_pool.write``
(``kv_pool_move_ms_per_tick``). None where the program has no such scope
(a program from before PR 47, another family)."""
from harness.hostspans import load
from harness.readers import per_tick_ms

LABELS = ("attn.full", "attn.full.kernel")


def read(ctx):
    hs = load(ctx)
    if not hs or not any(k in hs["by_label"] for k in LABELS):
        return None
    return per_tick_ms(
        ctx, sum(hs["by_label"].get(k, 0) for k in LABELS) / 1e9)
