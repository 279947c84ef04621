"""Live recompile sentinel: the static ≤2-programs proof as an alarm.

The recompile-hazard pass (analysis/recompile.py) *proves* at engine
construction that the ragged serving dispatch reaches 1-2 programs per
packed-width bucket. That proof is about reachable dispatch — it cannot
see a mis-sized warmup, a config drift between blue/green restarts, or
a jax upgrade quietly changing a trace key. Those failures all present
the same way in production: an XLA compile *inside a serving tick*, a
multi-second stall the p99 histogram only reports after the fact.

The sentinel watches the real thing: ``jax.monitoring``'s
``/jax/core/compile/backend_compile_duration`` event fires on every
executable materialization in the process (including persistent-cache
hits — a cache hit is still a program this process had not warmed, so
it still counts; measured on jax 0.4.37). One module-level listener is
registered once and dispatches to every live sentinel:

* before ``arm()`` (warmup), compiles are counted but expected;
* after ``arm()``, every compile is an alarm: a labeled WARN metric
  (``recompiles{during=...}``), a span on the ``sentinel`` track named
  after the innermost open span it interrupted ("compile during
  serving.tick"), and a ``RecompileWarning``.

``report()`` carries the engine's *expected* program inventory
(``analysis.recompile.program_inventory`` — the same schema
``graph_lint --json`` emits in its ``observability`` block), so the
static and runtime views of "what should ever compile here" are one
diffable document.
"""
from __future__ import annotations

import threading
import time
import warnings
import weakref
from collections import deque
from typing import List, Optional

from .tracer import current_span

__all__ = ["RecompileSentinel", "RecompileWarning", "COMPILE_EVENT",
           "RECOMPILES_METRIC", "compile_ledger", "compile_totals",
           "ledger_health", "LEDGER_CAPACITY"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_CACHE = "/jax/compilation_cache/"
CACHE_REQUEST_EVENT = _CACHE + "compile_requests_use_cache"
CACHE_HIT_EVENT = _CACHE + "cache_hits"
CACHE_RETRIEVAL_EVENT = _CACHE + "cache_retrieval_time_sec"
CACHE_SAVED_EVENT = _CACHE + "compile_time_saved_sec"
# the prometheus series the sentinel's alarms land in
# (ServingMetrics.expose: <prefix>_<counter>_total); graph_lint --json
# names the same string in its observability block so CI consumers and
# scrape configs share one source of truth
RECOMPILES_METRIC = "paddle_serving_recompiles_total"


class RecompileWarning(UserWarning):
    """A post-warmup XLA compile was observed by a RecompileSentinel."""


_installed = False
_install_lock = threading.Lock()
# live sentinels; weak so an abandoned engine cannot leak through the
# process-wide listener
_active: "weakref.WeakSet" = weakref.WeakSet()

# ------------------------------------------------------ compile ledger ----
# One record per program MATERIALISED in this process (a backend event:
# an XLA compile or a read from the persistent cache), oldest first:
#   fun_name   the Python function's name ("jit(f)" reads "f")
#   t0, t_end  time.monotonic() seconds: the first event's start, the
#              backend event's end
#   trace_s, lower_s, backend_s   seconds tracing to a jaxpr, lowering
#              to StableHLO, and in compile_or_get_cached (the compile,
#              or on a hit the read and the executable's load)
#   cache      "hit" / "miss" (the persistent cache was asked and did
#              not hold it; a program quicker than
#              jax_persistent_cache_min_compile_time_secs is never
#              written, so it misses in every run) / "uncached" (no
#              compile_requests_use_cache event came: no cache is set)
#   retrieval_s, saved_s   on a hit: the read, and the compile time the
#              entry says it saved
#   during     current_span() when the backend event fired, else None
#   thread     the thread's name
# A program's events arrive in order on ONE thread (trace, lower, cache
# events, backend), so they are paired per thread. A jit called while
# another is traced is traced INTO it and has no lowering of its own;
# a trace or a lowering that never reaches the backend (eval_shape, a
# lowering read as text) leaves no record.
LEDGER_CAPACITY = 1024
_ledger: "deque[dict]" = deque(maxlen=LEDGER_CAPACITY)
_ledger_lock = threading.Lock()
_ledger_dropped = [0]
# what the listener itself costs: events it handled, ns spent in them
_listener_cost = [0, 0]
# per thread: ``traces``, the trace events no lowering has claimed yet
# (name -> start, seconds; the last few names), ``rec``, the record a lowering
# opened, and ``cache``, what the persistent cache said of the program
# now in the backend; the backend event closes the record
_pending = threading.local()
_UNCLAIMED = 64


def _program(fun_name) -> str:
    name = str(fun_name) if fun_name is not None else "?"
    return name[4:-1] if name.startswith("jit(") and name.endswith(")") \
        else name


def _open_record(name: str, t0: float) -> dict:
    rec = {"fun_name": name, "t0": t0, "t_end": t0, "trace_s": 0.0,
           "lower_s": 0.0, "backend_s": 0.0, "cache": "uncached",
           "retrieval_s": 0.0, "saved_s": 0.0, "during": None,
           "thread": None}
    _pending.rec = rec
    return rec


def _on_event(event: str, **_kw) -> None:
    # the cache's events fire inside the backend event they belong to;
    # asked and not held is a miss whether or not ``cache_misses`` (the
    # entry's WRITE: only for a program slow enough to keep) follows
    if event == CACHE_REQUEST_EVENT:
        _pending.cache = {"cache": "miss"}
    elif event == CACHE_HIT_EVENT:
        _pending.cache = {"cache": "hit"}


def _on_event_duration(event: str, duration: float, **kw) -> None:
    field = _DURATION_FIELDS.get(event)
    if field is None:
        return
    t_in = time.monotonic_ns()
    try:
        _pair(field, float(duration), t_in / 1e9, kw.get("fun_name"))
    finally:
        _listener_cost[0] += 1
        _listener_cost[1] += time.monotonic_ns() - t_in


def _pair(field: str, duration: float, now: float, fun_name) -> None:
    if field == "retrieval_s" or field == "saved_s":
        cache = getattr(_pending, "cache", None)
        if cache is not None:
            cache[field] = duration
        return
    rec = getattr(_pending, "rec", None)
    name = _program(fun_name)
    if field == "trace_s":
        traces = getattr(_pending, "traces", None)
        if traces is None:
            traces = _pending.traces = {}
        traces.pop(name, None)
        traces[name] = (now - duration, duration)
        if len(traces) > _UNCLAIMED:
            del traces[next(iter(traces))]
        return
    if field == "lower_s":
        # the lowering claims the last trace of its name; the jits
        # traced INTO it fired before it, those its lowering rules
        # trace after it, and both go with it, as does a trace that
        # nothing lowered (eval_shape)
        traces = getattr(_pending, "traces", None) or {}
        mine = traces.pop(name, None)
        if mine is None:        # traced earlier, lowered again
            rec = _open_record(name, now - duration)
        else:
            rec = _open_record(name, mine[0])
            rec["trace_s"] = mine[1]
        traces.clear()
        rec["lower_s"] = duration
        return
    # the backend event: the program is materialised
    if rec is None or rec["fun_name"] != name:
        # an AOT compile of a program lowered some time ago
        rec = _open_record(name, now - duration)
    rec.update(getattr(_pending, "cache", None) or {},
               backend_s=duration, t_end=now, during=current_span(),
               thread=threading.current_thread().name)
    _pending.rec = _pending.cache = None
    with _ledger_lock:
        if len(_ledger) == _ledger.maxlen:
            _ledger_dropped[0] += 1
        _ledger.append(rec)
    for s in list(_active):
        s._on_compile(duration, rec)


_DURATION_FIELDS = {TRACE_EVENT: "trace_s", LOWER_EVENT: "lower_s",
                    COMPILE_EVENT: "backend_s",
                    CACHE_RETRIEVAL_EVENT: "retrieval_s",
                    CACHE_SAVED_EVENT: "saved_s"}


def _install_listener() -> None:
    global _installed
    with _install_lock:
        if _installed:
            return
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(
            _on_event_duration)
        monitoring.register_event_listener(_on_event)
        _installed = True


def compile_ledger() -> List[dict]:
    """The process's compile ledger, oldest first: one dict a program
    materialised (the fields are listed above ``LEDGER_CAPACITY``). The
    last ``LEDGER_CAPACITY`` records; ``ledger_health()["dropped"]``
    counts what fell off."""
    with _ledger_lock:
        return [dict(r) for r in _ledger]


def ledger_health() -> dict:
    """The ledger's own state, process-wide: ``held`` and ``dropped``
    records, and what its listener costs: ``listener_events`` it
    handled and the ``listener_s`` it spent in them."""
    with _ledger_lock:
        held = len(_ledger)
    return {"held": held, "dropped": _ledger_dropped[0],
            "listener_events": _listener_cost[0],
            "listener_s": _listener_cost[1] / 1e9}


def compile_totals(since: Optional[float] = None,
                   until: Optional[float] = None,
                   records: Optional[List[dict]] = None) -> dict:
    """Sums over the ledger's records that ENDED in ``(since, until]``
    (``time.monotonic()`` seconds; None: unbounded), or over
    ``records``: ``programs``, ``hits``, ``misses``, ``uncached``,
    ``trace_s``, ``lower_s``, ``compile_s`` (backend seconds of misses
    and uncached), ``hit_s`` (backend seconds of hits: the read and the
    executable's load), ``cache_read_s`` (retrieval seconds of hits),
    ``saved_s``."""
    if records is None:
        records = [r for r in compile_ledger()
                   if (since is None or r["t_end"] > since)
                   and (until is None or r["t_end"] <= until)]
    out = {"programs": len(records), "hits": 0, "misses": 0, "uncached": 0,
           "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0, "hit_s": 0.0,
           "cache_read_s": 0.0, "saved_s": 0.0}
    for r in records:
        out[{"hit": "hits", "miss": "misses"}.get(r["cache"],
                                                  "uncached")] += 1
        out["trace_s"] += r["trace_s"]
        out["lower_s"] += r["lower_s"]
        if r["cache"] == "hit":
            out["hit_s"] += r["backend_s"]
            out["cache_read_s"] += r["retrieval_s"]
            out["saved_s"] += r["saved_s"]
        else:
            out["compile_s"] += r["backend_s"]
    return out


class RecompileSentinel:
    """Count and name every XLA compile; alarm on any after ``arm()``.

        s = RecompileSentinel(expected=program_inventory(geom),
                              tracer=tr, metrics=m, label="serving")
        ... warmup traffic ...
        s.arm()                      # warmup done: compiles now WARN
        ... serve ...
        s.report()["post_warmup_compiles"]   # 0 when clean

    The listener fires on whichever thread ran the jit call, so the
    event is named after that thread's innermost open tracer span —
    for the serving engine that is the tick span that stalled.
    ``close()`` detaches the sentinel (the process-wide listener stays,
    dispatching to whoever remains).

    Scope note: compile events are PROCESS-wide. A sentinel on an
    otherwise-idle serving process attributes every post-warmup compile
    to serving (the intent); co-resident non-serving jax work shows up
    too and is distinguishable by its ``during`` span name.
    """

    def __init__(self, *, expected: Optional[dict] = None,
                 tracer=None, metrics=None, label: str = "serving",
                 max_events: int = 256):
        self.expected = expected
        self.label = label
        self._tracer = tracer
        self._metrics = metrics
        self._lock = threading.Lock()
        self._armed_at: Optional[float] = None
        self.warmup_compiles = 0
        self.post_warmup_compiles = 0
        self.events: "deque[dict]" = deque(maxlen=int(max_events))
        self._closed = False
        _install_listener()
        _active.add(self)

    # ------------------------------------------------------------ state ----
    @property
    def armed(self) -> bool:
        return self._armed_at is not None

    @property
    def clean(self) -> bool:
        """True when no compile has been seen since ``arm()``."""
        return self.post_warmup_compiles == 0

    def arm(self) -> None:
        """Declare warmup complete: every later compile is an alarm.
        Idempotent (re-arming does not forgive earlier alarms)."""
        with self._lock:
            if self._armed_at is None:
                self._armed_at = time.monotonic()

    def close(self) -> None:
        """Stop observing (engine shutdown)."""
        self._closed = True
        _active.discard(self)

    # --------------------------------------------------------- listener ----
    def _on_compile(self, duration: float, rec: dict) -> None:
        """``rec`` is the ledger's record of the program (``fun_name``,
        ``cache``, ``during``, ``t_end``)."""
        if self._closed:
            return
        during, now = rec["during"], rec["t_end"]
        program, cache = rec["fun_name"], rec["cache"]
        with self._lock:
            armed = self._armed_at is not None
            ev = {"t_s": now, "compile_s": float(duration),
                  "during": during, "program": program, "cache": cache,
                  "phase": "post_warmup" if armed else "warmup"}
            self.events.append(ev)
            if not armed:
                self.warmup_compiles += 1
                return
            self.post_warmup_compiles += 1
        name = (f"compile of {program} ({cache}) during {during}" if during
                else f"compile of {program} ({cache}) (no active span)")
        if self._metrics is not None:
            try:
                self._metrics.inc("recompiles")
                self._metrics.inc_labeled(
                    "recompiles", during=during or "idle",
                    program=program, cache=cache)
            except Exception:
                pass
        if self._tracer is not None:
            self._tracer.add(name, "sentinel", now - duration, now,
                             compile_s=round(float(duration), 6),
                             program=program, cache=cache)
        warnings.warn(
            f"[{self.label}] post-warmup XLA compile "
            f"({duration * 1e3:.1f} ms) — {name}; the one-program-tick "
            f"warmup did not cover this program (see "
            f"docs/OBSERVABILITY.md recompile sentinel)",
            RecompileWarning, stacklevel=2)

    # ------------------------------------------------------------ export ----
    def report(self) -> dict:
        """Plain-dict sentinel state: counts, recent events, the
        expected static program inventory, and ``clean``."""
        with self._lock:
            return {
                "label": self.label,
                "armed": self._armed_at is not None,
                "warmup_compiles": self.warmup_compiles,
                "post_warmup_compiles": self.post_warmup_compiles,
                "clean": self.post_warmup_compiles == 0,
                "expected_programs": self.expected,
                "events": list(self.events),
            }


# the ledger is the process's: set-up begins before any engine exists
_install_listener()
