"""Model step, training: device self time under the latent attention's
scopes (``attn.mla.q``, ``attn.mla.kv``, ``attn.mla.expand``: the
projections, their norms, the rotary and ``kv_b`` applied to every
token; ``attn.core``: the splash kernels and the layout changes around
them; ``attn.out``), forward, rematerialised forward and backward, per
step of the traced span."""
from harness.hostspans import load

SCOPES = ("attn.mla.q", "attn.mla.kv", "attn.mla.expand", "attn.core",
          "attn.core.kernel", "attn.out")


def read(ctx):
    hs, tr = load(ctx), ctx.get("train")
    if not hs or not tr or not tr.get("trace_steps"):
        return None
    if not hs["by_label"].get("attn.mla.expand"):
        return None          # a step without latent attention
    spent = sum(hs["by_label"].get(s, 0) for s in SCOPES)
    return spent / 1e6 / tr["trace_steps"]
