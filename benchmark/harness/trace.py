"""From a profiler trace to numbers: one pure reducer over
``(name, start_ns, duration_ns)`` events of a device's operation line,
and a thin adapter from ``jax.profiler.ProfileData``.

What is sound today: busy/idle by the union of operation intervals,
time by operation name, exposed collective time by overlap, the top
operations and the longest gaps. A gap carries the name of the host
phase (``harness/hostspans.py``: the engine thread's
``serving.phase.*`` annotations, on the same clock) that covers most of
it, and ``unattributed`` where none does (training has no phases).
"""
from __future__ import annotations

import glob
import os
import re

# a collective the TPU compiler leaves standing as an operation of its
# own; ``async-collective-start/done`` bracket one it overlaps. One that
# it fuses into a compute fusion (``async_collective_fusion``,
# ``all-reduce-scatter``: on a 2 x 2 mesh the tp weight gathers inside
# the MLP matmuls) is a ``fusion.N`` like any other and cannot be told
# apart here.
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|async-collective)")


def union(intervals):
    """Merged, sorted ``[(start, end)]`` of possibly overlapping ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _overlap(a, b) -> int:
    """Total length of the intersection of two merged, sorted interval
    lists (one linear sweep)."""
    tot = i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def short_name(name: str) -> str:
    """The trace names an operation by its whole HLO line
    (``%fusion.12 = bf16[...] fusion(...)``): keep the operation's own
    name."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def self_times(evs):
    """Time by name with nesting taken out: the operations line holds a
    ``while`` (the layer scan) AND the operations inside it, so a plain
    sum by name counts the loop body twice. An event's self time is its
    length less what the events nested inside it cover. ``evs``:
    ``[(name, start, end)]``; returns ``{name: self time}``."""
    out, stack = {}, []          # stack of [name, end, covered_until]

    def close(item):
        name, start, end, child = item
        out[name] = out.get(name, 0) + (end - start) - child

    for n, s, e in sorted(evs, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:       # nested (or, by hand, overlapping) in the top
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([n, s, e, 0])
    while stack:
        close(stack.pop())
    return out


def gap_label(gap, phases) -> str:
    """The phase that covers most of ``gap`` ``(start, end)``;
    ``phases``: ``[(name, start, end)]``."""
    by = {}
    for name, s, e in phases or ():
        both = min(e, gap[1]) - max(s, gap[0])
        if both > 0:
            by[name] = by.get(name, 0) + both
    return max(by, key=by.get) if by else "unattributed"


def reduce_events(events, window=None, top=10, gaps=5, phases=None):
    """``events``: ``[(name, start_ns, dur_ns)]`` of ONE device's
    operation line. ``window``: ``(start_ns, end_ns)`` or None for the
    span of the events. ``phases``: the host's, to name the gaps by.
    Returns seconds throughout; ``by_name_s`` is self time (see
    ``self_times``)."""
    evs = [(short_name(n), s, s + d) for n, s, d in events if d > 0]
    if window is None:
        window = (min(s for _, s, _ in evs), max(e for _, _, e in evs))
    w0, w1 = window
    evs = [(n, max(s, w0), min(e, w1)) for n, s, e in evs
           if e > w0 and s < w1]
    merged = union((s, e) for _, s, e in evs)
    busy = sum(e - s for s, e in merged)
    by_name = self_times(evs)
    # a container (the ``while`` of the layer scan) spans the
    # collectives inside it and hides nothing: only leaves can overlap
    order = sorted(evs, key=lambda x: (x[1], -x[2]))
    leaves = [ev for ev, nxt in zip(order, order[1:] + [None])
              if nxt is None or nxt[1] >= ev[2]]
    coll = union((s, e) for n, s, e in evs if COLLECTIVE.match(n))
    other = union((s, e) for n, s, e in leaves if not COLLECTIVE.match(n))
    coll_s = sum(e - s for s, e in coll)
    exposed = coll_s - _overlap(coll, other)
    edges = [w0] + [x for se in merged for x in se] + [w1]
    idle = sorted(((edges[i + 1] - edges[i], edges[i])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)
    ns = 1e-9
    return {
        "window_s": (w1 - w0) * ns, "busy_s": busy * ns,
        "by_name_s": {n: t * ns for n, t in by_name.items()},
        "collective_s": coll_s * ns,
        "collective_exposed_s": exposed * ns,
        "top_ops": [[n, t * ns] for n, t in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        "longest_gaps": [[gap_label((at, at + g), phases), g * ns]
                         for g, at in idle[:gaps]],
        "n_events": len(evs),
    }


def name_sum(reduced: dict, pattern: str) -> float:
    """Seconds of every operation whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(t for n, t in reduced["by_name_s"].items() if rx.search(n))


# ------------------------------------------------------------ adapter ----

def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


OPS_LINE = "XLA Ops"


def device_events(path: str, ops_line: str = OPS_LINE) -> dict:
    """``{device plane name: [(name, start_ns, dur_ns)]}`` from the
    planes named ``/device:TPU:<n>``, line ``XLA Ops``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != ops_line:
                continue
            out[plane.name] = [(ev.name, ev.start_ns, ev.duration_ns)
                               for ev in line.events]
    return out


def describe(path: str, limit: int = 12) -> list:
    """A trace by hand: every plane, its lines, a few event names."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    rows = []
    for plane in data.planes:
        for line in plane.lines:
            evs = list(line.events)
            names = {}
            for ev in evs:
                names[ev.name] = names.get(ev.name, 0) + ev.duration_ns
            top = sorted(names.items(), key=lambda kv: -kv[1])[:limit]
            rows.append({"plane": plane.name, "line": line.name,
                         "events": len(evs),
                         "top": [[n, t / 1e9] for n, t in top]})
    return rows


def reduce_trace(trace_dir: str, n_devices: int, phases=None) -> dict:
    """Reduced trace of a run: per-device reductions over one common
    window (first operation start to last operation end on any
    device), ``busy_s`` averaged over the ``n_devices`` used."""
    per_dev = device_events(find_xplane(trace_dir))
    per_dev = {k: v for k, v in sorted(per_dev.items()) if v}
    if not per_dev:
        raise RuntimeError("the trace holds no device operation")
    w0 = min(s for evs in per_dev.values() for _, s, _ in evs)
    w1 = max(s + d for evs in per_dev.values() for _, s, d in evs)
    devs = [reduce_events(evs, (w0, w1), phases=phases)
            for evs in per_dev.values()]
    first = devs[0]
    return {**first, "devices": list(per_dev),
            "busy_s": sum(d["busy_s"] for d in devs) / n_devices,
            "per_device_busy_s": [d["busy_s"] for d in devs]}
