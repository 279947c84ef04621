"""Model step: device self time, per tick of the traced span, under the
Mamba-2 mixer's four scopes (``models/granite_hybrid.py``): ``ssm.in``
(norm and the three input projections), ``ssm.conv`` (the taps, the
bias, SiLU, the gathers from stream and state, the ``conv_state``
write), ``ssm.scan`` (the tick's chunk algebra and the state pass, whose
kernel is filed apart as ``ssm.scan.kernel``) and ``ssm.out`` (the gated
norm and ``out_proj``). A time; the state pass's share of its roofline is
``ssm_scan_roofline_pct``."""
from harness.hostspans import load
from harness.readers import per_tick_ms

LABELS = ("ssm.in", "ssm.conv", "ssm.scan", "ssm.scan.kernel", "ssm.out")


def read(ctx):
    hs = load(ctx)
    if not hs or not any(k in hs["by_label"] for k in LABELS):
        return None
    return per_tick_ms(
        ctx, sum(hs["by_label"].get(k, 0) for k in LABELS) / 1e9)
