"""Model step: device self time, per tick of the traced span, of moving
the KV pool rather than attending it — operations under the scope
``kv_pool.write`` (the span's keys and values scattered into the pages),
under bare ``layers`` (the layer scan's own: a layer's pages sliced out
of the pool, re-laid-out and written back), under ``ragged_attn`` other
than the kernel (the relayout around it), and the copies the compiler
adds with no scope at all (``xla:copy``: in the tick programs, the result
pools copied whole into the donated buffers as the program ends)."""
from harness.hostspans import load
from harness.readers import per_tick_ms

LABELS = ("kv_pool.write", "layers", "ragged_attn", "xla:copy")


def read(ctx):
    hs = load(ctx)
    # a program without the scopes has every operation under ``xla:``
    if not hs or not any(k in hs["by_label"] for k in LABELS[:3]):
        return None
    return per_tick_ms(
        ctx, sum(hs["by_label"].get(k, 0) for k in LABELS) / 1e9)
