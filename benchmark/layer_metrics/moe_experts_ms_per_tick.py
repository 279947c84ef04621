"""Model step: device self time, per tick of the traced span, under the
scope ``moe.experts`` (``incubate/moe/functional.py moe_ffn``): the
dispatch einsum, the three expert einsums at a capacity equal to the
cohort (C = N) and the combine einsum, over every expert layer. The
largest scope of both MoE cells; a time (its share of a roofline is
``moe_experts_roofline_pct``)."""
from harness.hostspans import load
from harness.readers import per_tick_ms

LABEL = "moe.experts"


def read(ctx):
    hs = load(ctx)
    if not hs or LABEL not in hs["by_label"]:
        return None
    return per_tick_ms(ctx, hs["by_label"][LABEL] / 1e9)
