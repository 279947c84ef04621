"""The host's side of a profiler trace, joined to the device's.

The program writes each span of its tick path to the profiler
(``jax.profiler.TraceAnnotation``): they land on plane ``/host:CPU`` of
the same ``.xplane.pb`` that holds the device's operations, on the same
``start_ns`` axis, with the span's args as the event's stats. So three
joins need no second clock:

* the device's idle intervals against the engine thread's phases
  (``serving.phase.*``): what the host was doing while the chip waited;
* a stat of an annotation summed over the traced ticks
  (``serving.tick``'s ``rows``, ``rows_real``, ``kv_tokens``): what the
  ticks in the trace launched and needed;
* the device's self time by the ``jax.named_scope`` an operation was
  traced under, which says what line of the model an operation is
  whatever serial number the compiler gave it.

Pure functions over plain tuples first, then the adapter that reads
the ``.xplane.pb``; ``harness/trace.py`` is used as it is. Every reader
over this returns None, and never raises, where the trace lacks what it
reads (a program without the annotations or the scopes, no device
plane): ``load`` returns None then, or the table is empty.
"""
from __future__ import annotations

import re
import struct

from . import trace as T
from .common import log, trace_dir
from .readers import per_tick_ms

HOST_PLANE = "/host:CPU"
PHASE = "serving.phase."
TICK = "serving.tick"
# the scopes the program names (models/llama.py, qwen2_moe.py,
# incubate/moe/functional.py); an operation belongs to the innermost
# one on its path. A family whose model step enters scopes of its own
# declares them (``SCOPES``), and its kernels (``KERNELS``), in its file:
# ``tables(family)`` adds them to these.
SCOPES = ("embed", "layers", "attn.qkv_rope", "kv_pool.write",
          "ragged_attn", "attn.out", "mlp", "moe.router", "moe.experts",
          "moe.shared", "lm_head", "sampler", "loss", "optimizer")
# ``{"<scope>.<word>": pattern}``: an operation under ``<scope>`` whose
# name matches the pattern is the kernel, filed apart from the
# operations around it
KERNELS = {"ragged_attn.kernel": r"^ragged_paged_attention"}
# the stat of a device operation's event metadata that holds its scope
# path (``jit(serving_tick)/layers/while/body/closed_call/kv_pool.write/
# scatter:``, the HLO ``op_name``), on a TPU v5e with jax 0.9.0
SCOPE_STAT = "tf_op"


# ------------------------------------------------------------- pure ----

def idle_intervals(events, window):
    """``events``: ``[(name, start, end)]`` of one device's operation
    line. The intervals of ``window`` in which no operation runs."""
    w0, w1 = window
    out, at = [], w0
    for s, e in T.union((s, e) for _, s, e in events):
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        if s > at:
            out.append((at, s))
        at = e
    if w1 > at:
        out.append((at, w1))
    return out


def ticked_phases(annotations):
    """``annotations``: ``[(name, start, end, stats)]``. The phase
    annotations of iterations that ticked (an idle iteration polls
    admission and builds nothing: its ``admit`` and ``build`` carry a
    tick number no ``dispatch``, ``readback`` or ``emit`` shares), as
    ``[(phase, start, end, tick)]`` sorted by start, each phase
    stretched to the start of the next one of its tick — the program
    reads ONE clock at a boundary, the profiler stamps the two
    annotations a microsecond apart."""
    ph = sorted((s, e, n[len(PHASE):], st.get("tick"))
                for n, s, e, st in annotations if n.startswith(PHASE))
    ticked = {t for _, _, n, t in ph
              if n in ("dispatch", "readback", "emit")}
    ph = [p for p in ph if p[3] in ticked]
    out = []
    for i, (s, e, n, t) in enumerate(ph):
        if i + 1 < len(ph) and ph[i + 1][3] == t:
            e = max(e, ph[i + 1][0])
        out.append((n, s, e, t))
    return out


def idle_by_phase(idle, phases):
    """Length of ``idle`` (merged, sorted) inside each phase:
    ``{phase: ns}``, with what no phase covers under ``"none"``."""
    out = {}
    for name in {p[0] for p in phases}:
        spans = T.union((s, e) for n, s, e, _ in phases if n == name)
        out[name] = T._overlap(idle, spans)
    covered = T.union((s, e) for _, s, e, _ in phases)
    out["none"] = sum(e - s for s, e in idle) - T._overlap(idle, covered)
    return out


def whole_ticks(annotations, window):
    """The ``serving.tick`` annotations that lie inside ``window``:
    ``[(start, end, stats)]``. The tick is synchronous, so the device's
    work for one lies inside its annotation."""
    w0, w1 = window
    return sorted((s, e, st) for n, s, e, st in annotations
                  if n == TICK and s >= w0 and e <= w1)


def stat_sum(ticks, stat: str):
    """Sum of a stat over ``whole_ticks``; None if a tick lacks it."""
    vals = [st.get(stat) for _, _, st in ticks]
    if not vals or any(v is None for v in vals):
        return None
    return sum(int(v) for v in vals)


def scope_of(path: str, scopes=SCOPES):
    """The innermost of ``scopes`` among the ``/``-separated segments
    of an operation's scope path; None if it has none."""
    for seg in reversed(path.split("/")):
        if seg in scopes:
            return seg
    return None


def tables(family=None):
    """``(scopes, kernels)``: the harness's own and, added to them,
    those the family declares."""
    return (SCOPES + tuple(getattr(family, "SCOPES", ())),
            {**KERNELS, **getattr(family, "KERNELS", {})})


def label(name: str, path: str, scopes=SCOPES, kernels=KERNELS) -> str:
    """What the busy-by-scope table files an operation under: its
    scope, with a kernel apart from the operations around it. An
    operation without a scope is one the compiler added and gave no
    ``op_name`` (on the chip: the result pools copied whole into the
    donated buffers as the tick program ends, ``copy.141``): it is filed
    under its opcode, ``xla:copy``."""
    scope = scope_of(path, scopes)
    if scope is None:
        return "xla:" + name.split(".", 1)[0]
    for kernel, pattern in kernels.items():
        if kernel.rsplit(".", 1)[0] == scope and re.match(pattern, name):
            return kernel
    return scope


def self_time_by_label(events, window=None, scopes=SCOPES, kernels=KERNELS):
    """``events``: ``[(name, start, end, scope path)]`` of one device's
    operation line. Self time (``trace.self_times``: a ``while`` does not
    count its body again) by ``label``, clipped to ``window``."""
    evs = [(label(n, p, scopes, kernels), s, e) for n, s, e, p in events]
    if window is not None:
        w0, w1 = window
        evs = [(n, max(s, w0), min(e, w1)) for n, s, e in evs
               if e > w0 and s < w1]
    return T.self_times(evs)


# ---------------------------------------------------------- adapter ----
# ``jax.profiler.ProfileData`` shows an event's own stats but not those
# of its metadata, and on a TPU the scope path of an operation (``tf_op``:
# the HLO ``op_name`` and the operation's type) is a stat of the event
# METADATA. So the file is read as what it is, a protobuf (tsl's
# xplane.proto), with the few field numbers needed.

def _varint(buf, i):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint or a fixed field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield num, val


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _stat(buf, stat_names):
    """One XStat -> ``(name, value)``."""
    name = val = None
    for num, v in _fields(buf):
        if num == 1:
            name = stat_names.get(v)
        elif num == 2:
            val = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif num == 3:
            val = v
        elif num == 4:
            val = v - (1 << 64) if v >> 63 else v
        elif num in (5, 6):
            val = _text(v)
        elif num == 7:
            val = stat_names.get(v)
    return name, val


def _plane(buf):
    """One XPlane -> ``(name, {line name: [(event name, start_ns,
    end_ns, stats)]})``; an event's stats are its metadata's and then
    its own."""
    name, lines, metas, stat_names = "", [], {}, {}
    for num, v in _fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 3:
            lines.append(v)
        elif num in (4, 5):             # map<int64, X*Metadata> entries
            entry = dict(_fields(v))
            (metas if num == 4 else stat_names)[entry.get(1, 0)] = entry[2]
    stat_names = {k: _text(dict(_fields(v)).get(2, b""))
                  for k, v in stat_names.items()}
    for k, v in metas.items():
        m_name, m_stats = "", {}
        for num, x in _fields(v):
            if num == 2:
                m_name = _text(x)
            elif num == 5:
                m_stats.update([_stat(x, stat_names)])
        metas[k] = (m_name, m_stats)
    out = {}
    for line in lines:
        l_name, t0_ns, events = "", 0, []
        for num, v in _fields(line):
            if num == 2:
                l_name = _text(v)
            elif num == 3:
                t0_ns = v
            elif num == 4:
                events.append(v)
        rows = out.setdefault(l_name, [])
        for ev in events:
            meta, off_ps, dur_ps, stats = ("", {}), 0, 0, {}
            for num, v in _fields(ev):
                if num == 1:
                    meta = metas.get(v, meta)
                elif num == 2:
                    off_ps = v
                elif num == 3:
                    dur_ps = v
                elif num == 4:
                    stats.update([_stat(v, stat_names)])
            start = t0_ns + off_ps / 1e3
            rows.append((meta[0], start, start + dur_ps / 1e3,
                         {**meta[1], **stats}))
    return name, out


def read_xplane(path: str):
    """``(annotations, device, modules)``: the host plane's events named
    ``serving.*`` as ``[(name, start, end, stats)]``; the first
    ``/device:TPU:<n>`` plane's ``XLA Ops`` line as ``[(short name,
    start, end, scope path)]``; the names on its ``XLA Modules`` line."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = dict(_plane(v) for num, v in _fields(space) if num == 1)
    annotations = [ev for rows in planes.get(HOST_PLANE, {}).values()
                   for ev in rows if ev[0].startswith("serving.")]
    device, modules = [], []
    for name in sorted(planes):
        if name.startswith("/device:TPU:"):
            device = [(T.short_name(n), s, e, str(st.get(SCOPE_STAT, "")))
                      for n, s, e, st in planes[name].get(T.OPS_LINE, [])
                      if e > s]
            modules = sorted({n.split("(")[0] for n, _, _, _ in
                              planes[name].get("XLA Modules", [])})
            break
    return annotations, device, modules


def _table(title: str, rows: dict, total: float) -> None:
    """Logged largest first; rows under 0.1 % are summed into one."""
    log(f"[hostspans] {title}")
    rest = 0.0
    for name, ns in sorted(rows.items(), key=lambda kv: -kv[1]):
        share = 100.0 * ns / total if total else 0.0
        if share < 0.1:
            rest += ns
            continue
        log(f"[hostspans]   {name:<20} {ns / 1e6:10.3f} ms  {share:5.1f} %")
    if rest:
        log(f"[hostspans]   {'(rows under 0.1 %)':<20} {rest / 1e6:10.3f} ms")


def load(ctx):
    """The run's xplane, reduced once for all readers of the run (kept
    in ``ctx``; its two tables are logged once): ``idle_by_phase`` over
    the device's window, self time ``by_label`` there and
    ``tick_by_label`` over the whole ticks inside it (by the harness's
    scopes and kernels and those of the cell's family), those ticks'
    summed ``tick_stats``, all in ns, and the ticked ``phases``. None
    where the trace has no device operation."""
    if "hostspans" in ctx:
        return ctx["hostspans"]
    try:
        annotations, device, modules = read_xplane(
            T.find_xplane(trace_dir()))
    except FileNotFoundError:
        annotations = device = modules = None
    out = None
    if device:
        scopes, kernels = tables(ctx["cell"].family)
        window = (min(s for _, s, _, _ in device),
                  max(e for _, _, e, _ in device))
        idle = idle_intervals([(n, s, e) for n, s, e, _ in device], window)
        phases = ticked_phases(annotations)
        ticks = whole_ticks(annotations, window)
        out = {
            "idle_by_phase": idle_by_phase(idle, phases) if phases else {},
            "phases": phases,
            "by_label": self_time_by_label(device, None, scopes, kernels),
            "tick_by_label": (self_time_by_label(
                device, (ticks[0][0], ticks[-1][1]), scopes, kernels)
                if ticks else {}),
            "tick_stats": {k: stat_sum(ticks, k)
                           for k in ("rows", "rows_real", "kv_tokens")},
        }
        log(f"[hostspans] modules on the device: {modules}; "
            f"{len(annotations)} host annotations, {len(phases)} phases of "
            f"ticked iterations, {len(ticks)} whole ticks in the window, "
            f"their stats {out['tick_stats']}")
        if phases:
            _table("device idle by the engine thread's phase",
                   out["idle_by_phase"], sum(e - s for s, e in idle))
        _table("device busy (self time) by scope", out["by_label"],
               sum(out["by_label"].values()))
    ctx["hostspans"] = out
    return out


def idle_ms_per_tick(ctx, *phases):
    """Device idle time inside the named phases, per tick of the traced
    span (the harness's own count, as ``tick_device_ms`` uses)."""
    hs = load(ctx)
    if not hs or not hs["idle_by_phase"]:
        return None
    return per_tick_ms(ctx, sum(hs["idle_by_phase"].get(p, 0)
                                for p in phases) / 1e9)
