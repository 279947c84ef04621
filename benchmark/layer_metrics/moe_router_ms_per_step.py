"""Model step, training: device self time under the scope
``moe.router`` (the float32 router matmul, the sigmoid, the choice of
the top k under the selection bias, the renormalised weights and the
counts), per step of the traced span."""
from harness.hostspans import load


def read(ctx):
    hs, tr = load(ctx), ctx.get("train")
    if not hs or not tr or not tr.get("trace_steps"):
        return None
    spent = hs["by_label"].get("moe.router")
    return spent / 1e6 / tr["trace_steps"] if spent else None
