"""Parallel: per step, on the first device of the mesh, the time in
all-reduce / all-gather / reduce-scatter / collective-permute /
all-to-all / async-collective-start/done operations during which no
other operation runs there. It counts the collectives that stand as
operations of their own on the device's operation line; one the compiler
fused into a compute fusion lengthens that fusion and is not in this
number (harness/trace.py: COLLECTIVE), so it is a floor on what the mesh
costs a step."""


def read(ctx):
    tr, win = ctx.get("trace"), ctx.get("train")
    if not tr or not win or not win.get("trace_steps"):
        return None
    if not tr["collective_s"]:
        return None
    return tr["collective_exposed_s"] / win["trace_steps"] * 1e3
