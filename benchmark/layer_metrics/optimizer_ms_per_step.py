"""Trainer: device self time under the scope ``optimizer`` (the AdamW
update and its application to the parameters), per step of the traced
span."""
from harness.hostspans import load


def read(ctx):
    hs, tr = load(ctx), ctx.get("train")
    if not hs or not tr or not tr.get("trace_steps"):
        return None
    spent = hs["by_label"].get("optimizer")
    if not spent:
        return None
    return spent / 1e6 / tr["trace_steps"]
