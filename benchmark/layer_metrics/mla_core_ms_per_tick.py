"""Kernels: device self time of ``mla_paged_attention*`` (label
``attn.mla.core.kernel``: attention over the latent pages, every
sublayer's launch), per tick of the traced span."""
from harness.hostspans import load
from harness.readers import per_tick_ms

LABEL = "attn.mla.core.kernel"


def read(ctx):
    hs = load(ctx)
    if not hs or LABEL not in hs["by_label"]:
        return None
    return per_tick_ms(ctx, hs["by_label"][LABEL] / 1e9)
