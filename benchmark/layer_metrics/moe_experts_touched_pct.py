"""Model step: of the experts a window's launches HELD (one launch an
expert layer a tick or fused step, every one of the layer's experts in
it), the share that took at least one row: 100 x ``moe_experts_touched``
/ ``moe_experts_held``, the engine's counters, which the tick program
hands back beside its tokens. A launch through the held-experts grouped
matmul reads the weights of the touched experts and of no other, so
this is the share of the expert bytes the window read; the capacity
einsum at C = N reads every expert whatever the rows chose. Lower is
fewer bytes for the same tokens (a tick of few rows over many experts);
100 where every expert is chosen anyway. Returns None where the window
has no such counters (a program from before PR 43)."""


def read(ctx):
    counters = (ctx.get("window") or {}).get("counters") or {}
    held = counters.get("moe_experts_held")
    if not held or "moe_experts_touched" not in counters:
        return None
    return 100.0 * counters["moe_experts_touched"] / held
