"""Model step, training: device self time under the scope
``moe.experts`` (the sort of the held pairs, the gather into the sorted
buffer, the grouped matmuls each way and the weighted scatter-add), per
step of the traced span."""
from harness.hostspans import load

SCOPES = ("moe.experts", "moe.experts.kernel")


def read(ctx):
    hs, tr = load(ctx), ctx.get("train")
    if not hs or not tr or not tr.get("trace_steps"):
        return None
    spent = sum(hs["by_label"].get(s, 0) for s in SCOPES)
    return spent / 1e6 / tr["trace_steps"] if spent else None
