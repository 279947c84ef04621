"""Model step: device self time, per tick of the traced span, under the
scope ``window_pool.write`` of ``models/mimo_v2_flash.py``: the scatter
of the tick's K and V rows into the window layers' rings (a pool of its
own beside the paged one, so these writes do not vanish into
``kv_pool.write``). None where the program has no such scope."""
from harness.hostspans import load
from harness.readers import per_tick_ms

LABEL = "window_pool.write"


def read(ctx):
    hs = load(ctx)
    if not hs or LABEL not in hs["by_label"]:
        return None
    return per_tick_ms(ctx, hs["by_label"][LABEL] / 1e9)
