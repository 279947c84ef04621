"""Model step: device self time, per tick of the traced span, under the
scope ``attn.window`` of ``models/mimo_v2_flash.py``: a WINDOW attention
layer's norm, q / k / v projections, partial rotary, the kernel (label
``attn.window.kernel``: the ragged walk with a window and a sink over
the slots' rings) and the output projection, over the window layers. The
ring's writes are ``window_pool.write``
(``window_pool_write_ms_per_tick``). None where the program has no such
scope."""
from harness.hostspans import load
from harness.readers import per_tick_ms

LABELS = ("attn.window", "attn.window.kernel")


def read(ctx):
    hs = load(ctx)
    if not hs or not any(k in hs["by_label"] for k in LABELS):
        return None
    return per_tick_ms(
        ctx, sum(hs["by_label"].get(k, 0) for k in LABELS) / 1e9)
