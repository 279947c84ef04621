"""Kernels (rehearsal): device self time, per tick of the traced span,
under the scope and the kernel label that only the family ``tiny_split``
declares; nothing to read in a trace of any other family."""
from harness.hostspans import load
from harness.readers import per_tick_ms

LABELS = ("attn.split_latent", "attn.split_latent.kernel")


def read(ctx):
    hs = load(ctx)
    if not hs or not all(k in hs["by_label"] for k in LABELS):
        return None
    return per_tick_ms(ctx, sum(hs["by_label"][k] for k in LABELS) / 1e9)
