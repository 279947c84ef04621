"""Model step: the least time the chip could take for the expert layers
of a cell that holds ONE CHIP'S SHARE of the experts, over the device's
self time under ``moe.experts`` in the traced span: ``moe_share_
roofline_pct``'s arithmetic for a configuration whose expert width is
``moe_intermediate_size`` (that reader returns None without
``expert_ffn_hidden_size``).

The least time is the longer of two: to read once the held experts that
took at least one row (the engine's counter ``moe_experts_touched``, one
a launch a layer, x 3 matrices x ``hidden_size`` x
``moe_intermediate_size`` x 2 B = 50.3 MB an expert), or to do the FLOPs
of the (token, choice) pairs that landed on a held expert
(``moe_pairs_held`` x 3 matrices x 2 FLOP a multiply-add). Both counters
come back from the tick program beside its tokens and cover the whole
window; they are scaled to the traced span by ticks (``trace_ticks``
over the window's ``decode_steps``). Returns None where the window has
no such counters or the configuration no ``moe_intermediate_size``."""
from harness.hostspans import load
from harness.readers import peaks

LABEL = "moe.experts"


def expert_params(model: dict) -> int:
    """One expert's three matrices."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def least_seconds(model: dict, touched: float, pairs: float,
                  peak: dict, itemsize: int = 2) -> float:
    return max(touched * expert_params(model) * itemsize
               / peak["hbm_bytes_per_s"],
               pairs * 2 * expert_params(model) / peak["bf16_flops"])


def read(ctx):
    hs = load(ctx)
    win = ctx.get("window") or {}
    counters = win.get("counters") or {}
    ticks, steps = win.get("trace_ticks"), counters.get("decode_steps")
    if (not hs or not ticks or not steps
            or "moe_experts_touched" not in counters
            or "moe_intermediate_size" not in ctx["model"]
            or "router_experts" not in ctx["model"]):
        return None
    spent = hs["by_label"].get(LABEL, 0) / 1e9
    if not spent:
        return None
    share = ticks / steps
    peak = peaks(ctx["devices"][0].device_kind)
    return 100.0 * least_seconds(
        ctx["model"], counters["moe_experts_touched"] * share,
        counters["moe_pairs_held"] * share, peak) / spent
