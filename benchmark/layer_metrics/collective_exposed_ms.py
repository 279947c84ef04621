"""Parallel: per step, on the first device of the mesh, the time in
all-reduce / all-gather / reduce-scatter / collective-permute /
all-to-all operations during which no other operation runs there."""


def read(ctx):
    tr, win = ctx.get("trace"), ctx.get("train")
    if not tr or not win or not win.get("trace_steps"):
        return None
    if not tr["collective_s"]:
        return None
    return tr["collective_exposed_s"] / win["trace_steps"] * 1e3
