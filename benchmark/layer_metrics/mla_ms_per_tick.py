"""Model step: device self time, per tick of the traced span, under the
latent-attention scopes of ``models/longcat_flash.py``: ``attn.mla.q``
(the query's down-projection, its norm, the up-projection, rotary),
``attn.mla.kv`` (the latent's down-projection, norm and scale, the
shared rotary key), ``attn.mla.core`` (the absorb and up-project einsums
around the kernel) and the kernel (``attn.mla.core.kernel``). The latent
rows' scatter is ``kv_pool.write`` (``kv_pool_move_ms_per_tick``), the
output projection ``attn.out``."""
from harness.hostspans import load
from harness.readers import per_tick_ms

LABELS = ("attn.mla.q", "attn.mla.kv", "attn.mla.core",
          "attn.mla.core.kernel")


def read(ctx):
    hs = load(ctx)
    if not hs or not any(k in hs["by_label"] for k in LABELS):
        return None
    return per_tick_ms(
        ctx, sum(hs["by_label"].get(k, 0) for k in LABELS) / 1e9)
