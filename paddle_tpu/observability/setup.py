"""Time to ready: the process's set-up spans and the compile ledger,
as ONE report.

Set-up is measured where the work happens: the serving engine and the
trainer open spans on the process tracer (``process_tracer()``, track
``setup``), each closed where its work ends (blocking on what it made,
so the span holds the device's part too):

    serving.setup.init              ServingEngine.__init__
    serving.setup.init.inventory      the static program inventory, the
                                      geometry checks
    serving.setup.init.cache          the family's ``init_pages``: pools and
                                      slot state allocated on the device
    serving.setup.init.relay          the family's ``params``: the weights
                                      it re-lays for serving
    serving.setup.warm              warm_programs()
    serving.setup.warm.program        one call of a tick program (args
                                      tq, decode_tail, spec_k; the fused
                                      block: block)
    serving.setup.warm.sync           the wait for the last of them
    train.setup.build               make_train_step's body
    train.setup.init                the init(key) it returns, until the
                                    state it made is ready

and every program materialised meanwhile is a record of the compile
ledger (observability/sentinel.py) carrying the innermost open span in
``during``. ``setup_report()`` joins the two so that no second is
counted twice: a span's row is its time LESS the ledger seconds
recorded during it.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax

from .sentinel import compile_ledger, compile_totals, ledger_health
from .tracer import Span, process_tracer

__all__ = ["SETUP_TRACK", "setup_span", "in_setup_span", "setup_report"]

SETUP_TRACK = "setup"
# a row of the report: the top-level spans under it, by prefix
_ROWS = {"engine_init_s": "serving.setup.init",
         "warm_s": "serving.setup.warm",
         "train_init_s": "train.setup."}


def setup_span(name: str, **args):
    """A set-up span on the process tracer."""
    return process_tracer().span(name, track=SETUP_TRACK, **args)


def in_setup_span(name: str, ready: bool = False):
    """Decorator: the whole call is one set-up span; with ``ready`` it
    closes once the arrays the call returned are ready on the device."""
    def deco(fn):
        @functools.wraps(fn)
        def spanned(*args, **kw):
            with setup_span(name):
                out = fn(*args, **kw)
                if ready:
                    jax.block_until_ready(out)  # noqa: PT002 — a set-up span closes where its work ends, once a process
                return out
        return spanned
    return deco


def _ledger_s(rec: dict) -> float:
    return rec["trace_s"] + rec["lower_s"] + rec["backend_s"]


def _nest(spans: List[Span]) -> List[Optional[int]]:
    """Each span's parent (an index into ``spans``, None at the top):
    the innermost span of the same thread that contains it."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i].tid, spans[i].t0, -spans[i].t1))
    parent: List[Optional[int]] = [None] * len(spans)
    stack: List[int] = []
    for i in order:
        s = spans[i]
        while stack and not (spans[stack[-1]].tid == s.tid
                             and spans[stack[-1]].t1 >= s.t1):
            stack.pop()
        parent[i] = stack[-1] if stack else None
        stack.append(i)
    return parent


def _union_s(spans: List[Span]) -> float:
    total, end = 0, None
    for s in sorted(spans, key=lambda s: s.t0):
        if end is None or s.t0 > end:
            total, end = total + (s.t1 - s.t0), s.t1
        elif s.t1 > end:
            total, end = total + (s.t1 - end), s.t1
    return total / 1e9


def setup_report(since: Optional[float] = None,
                 until: Optional[float] = None) -> dict:
    """What set-up spent and where, over ``(since, until]``
    (``time.monotonic()`` seconds; None: unbounded):

    ``spans``    the set-up spans that closed in the interval, each with
                 ``self_s`` (its duration less what its children cover)
                 and ``parent``;
    ``totals``   ``compile_totals`` of the interval, ``by_during`` the
                 same split by the records' ``during``;
    ``slowest``  the ten ledger records with the most seconds;
    ``ledger``   ``ledger_health()``: records dropped, the listener's cost;
    ``rows``     a partition of the program's part of set-up:
                 ``programs_missed`` (records not read from the cache
                 though slow enough to be kept there: a warm cache
                 should have held them), ``compile_s``,
                 ``cache_read_s``, ``trace_lower_s`` (the ledger's
                 seconds, every record), ``engine_init_s``, ``warm_s``,
                 ``train_init_s`` (those spans LESS the ledger seconds
                 recorded during them), and ``in_program_s``: the union
                 of the top-level spans plus the ledger seconds recorded
                 outside them = the sum of the other rows.
    """
    lo = None if since is None else int(since * 1e9)
    hi = None if until is None else int(until * 1e9)
    spans = [s for s in process_tracer().spans()
             if s.track == SETUP_TRACK
             and (lo is None or s.t1 > lo) and (hi is None or s.t1 <= hi)]
    parent = _nest(spans)
    child_ns = [0] * len(spans)
    for i, p in enumerate(parent):
        if p is not None:
            child_ns[p] += spans[i].t1 - spans[i].t0
    span_rows = []
    for i, s in enumerate(spans):
        d = s.to_dict()
        d["self_s"] = (s.t1 - s.t0 - child_ns[i]) / 1e9
        d["parent"] = None if parent[i] is None else spans[parent[i]].name
        span_rows.append(d)
    top = [s for s, p in zip(spans, parent) if p is None]

    records = [r for r in compile_ledger()
               if (since is None or r["t_end"] > since)
               and (until is None or r["t_end"] <= until)]
    by_during: Dict[Optional[str], list] = {}
    for r in records:
        by_during.setdefault(r["during"], []).append(r)
    totals = compile_totals(records=records)

    keep_s = jax.config.jax_persistent_cache_min_compile_time_secs
    rows = {
        "programs_missed": sum(1 for r in records if r["cache"] != "hit"
                               and r["backend_s"] >= keep_s),
        "compile_s": totals["compile_s"],
        "cache_read_s": totals["hit_s"],
        "trace_lower_s": totals["trace_s"] + totals["lower_s"]}
    outside = sum(_ledger_s(r) for r in records)
    for row, prefix in _ROWS.items():
        inside = sum(_ledger_s(r) for r in records
                     if (r["during"] or "").startswith(prefix))
        outside -= inside
        rows[row] = sum(s.dur_s for s in top
                        if s.name.startswith(prefix)) - inside
    rows["in_program_s"] = _union_s(top) + outside
    return {
        "spans": span_rows, "totals": totals,
        "by_during": {k: compile_totals(records=v)
                      for k, v in by_during.items()},
        "ledger": ledger_health(),
        "slowest": sorted(records, key=_ledger_s, reverse=True)[:10],
        "rows": rows}
