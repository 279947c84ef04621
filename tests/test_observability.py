"""Runtime observability layer (ISSUE r13): span tracer round-trip,
flight-recorder postmortems, the live recompile sentinel, Prometheus
exposition, thread-safe snapshots, the profiler RecordEvent /
host_statistics coverage the module never had, and (PR 26) the tick
named from the inside: spans as profiler annotations, the engine
thread's five contiguous phases, the per-tick counts, the prefill
queue's wait, and the named scopes on the device programs.

Acceptance pins exercised here:
  * exported Perfetto JSON re-parses, spans nest, no negative
    durations, and per-request TTFT spans reconcile EXACTLY with the
    ``ttft_s`` histogram observations (same monotonic clock);
  * a seeded ``KVInvariantError`` writes a JSON postmortem carrying
    the violation list, recent tick ring, state snapshots and spans;
  * a seeded geometry change after warmup trips the recompile
    sentinel (WARN metric + RecompileWarning + named event);
  * measured tracing overhead ≤ 3% of tick wall (slow test, via
    ``serving_bench --modes trace_overhead``).
"""
import json
import os
import re
import threading
import time
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import llama as L
from paddle_tpu.observability import (FlightRecorder, RecompileWarning,
                                      SpanTracer, compile_ledger,
                                      compile_totals, current_span,
                                      process_tracer, setup_report,
                                      setup_span)
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.metrics import Histogram, ServingMetrics

CFG = L.LlamaConfig.tiny(dtype=jnp.float32, use_flash_attention=False,
                         remat=False)


@pytest.fixture(scope="module")
def params():
    return L.init_params(CFG, jax.random.PRNGKey(0))


def _engine(params, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_prompt_len", 16)
    kw.setdefault("max_new_tokens_cap", 16)
    return ServingEngine(params, CFG, **kw)


# ---------------------------------------------------------------------------
# metrics satellites: histogram window semantics + prometheus text
# ---------------------------------------------------------------------------

def test_histogram_reports_lifetime_and_window_separately():
    """Once the window wraps, lifetime mean and windowed stats describe
    different populations — summary() must report BOTH, not mix them
    (the pre-r13 bug: lifetime mean next to windowed percentiles)."""
    h = Histogram(cap=4)
    for v in range(1, 9):           # 1..8; window keeps 5,6,7,8
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 8
    assert s["mean"] == pytest.approx(4.5)          # lifetime
    assert s["window_count"] == 4
    assert s["window_mean"] == pytest.approx(6.5)   # last 4 only
    assert s["p50"] == pytest.approx(6.5)           # windowed
    assert s["max"] == 8.0
    # before the wrap the two means agree
    h2 = Histogram(cap=16)
    for v in (1.0, 3.0):
        h2.observe(v)
    s2 = h2.summary()
    assert s2["mean"] == s2["window_mean"] == pytest.approx(2.0)


def test_metrics_expose_prometheus_text():
    m = ServingMetrics()
    m.inc("submitted", 3)
    m.inc("recompiles")
    m.inc_labeled("recompiles", during='serving.tick "w=16"\n')
    for v in (0.1, 0.2, 0.3):
        m.observe("ttft_s", v)
    text = m.expose(gauges={"free_pages": 31, "occupancy": 0.25})
    lines = text.splitlines()
    assert "paddle_serving_submitted_total 3" in lines
    assert "paddle_serving_recompiles_total 1" in lines
    # labeled series live in their OWN family (a label-sliced sample of
    # the flat family would make sum(rate(...)) double-count)
    lab = [ln for ln in lines if ln.startswith(
        "paddle_serving_recompiles_breakdown_total{")]
    assert len(lab) == 1 and r'\"w=16\"' in lab[0] and "\n" not in lab[0]
    assert not any(ln.startswith("paddle_serving_recompiles_total{")
                   for ln in lines)
    # summary: windowed quantiles + LIFETIME _sum/_count
    assert 'paddle_serving_ttft_s{quantile="0.5"} 0.2' in lines
    assert "paddle_serving_ttft_s_count 3" in lines
    assert any(ln.startswith("paddle_serving_ttft_s_sum 0.6")
               for ln in lines)
    assert "paddle_serving_free_pages 31" in lines
    # every sample line parses as <name>{labels}? <float>
    import re
    pat = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*(\{.*\})? [-+0-9.eE]+$")
    for ln in lines:
        if ln and not ln.startswith("#"):
            assert pat.match(ln), ln
    # labeled counters survive snapshot() too
    snap = m.snapshot()
    assert snap["labeled"]["recompiles"][0]["value"] == 1


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_tracer_roundtrip_nesting_and_threads(tmp_path):
    tr = SpanTracer(capacity=128)
    with tr.span("outer", track="engine.decode", tick=1):
        assert current_span() == "outer"
        time.sleep(0.002)
        with tr.span("inner", track="engine.decode"):
            assert current_span() == "inner"
            time.sleep(0.002)
        assert current_span() == "outer"
    assert current_span() is None

    def worker():
        with tr.span("w", track="slot1"):
            time.sleep(0.001)
    t = threading.Thread(target=worker)
    t.start()
    t.join()
    tr.add("retro", "slot0", 1.0, 2.5, req=7)

    path = tr.export(str(tmp_path / "t.json"))
    doc = json.load(open(path))           # re-parses
    evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    byname = {e["name"]: e for e in evs}
    assert set(byname) == {"outer", "inner", "w", "retro"}
    for e in evs:
        assert e["dur"] >= 0              # no negative durations
    # nesting: inner fully inside outer, same track (tid)
    o, i = byname["outer"], byname["inner"]
    assert i["tid"] == o["tid"]
    assert i["ts"] >= o["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-3
    # retroactive spans keep their explicit stamps + args
    assert byname["retro"]["dur"] == pytest.approx(1.5e6)  # us
    assert byname["retro"]["args"]["req"] == 7
    # per-track thread metadata present (Perfetto track names)
    names = [e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"]
    assert {"engine.decode", "slot0", "slot1"} <= set(names)


def test_tracer_ring_bound_and_disable():
    tr = SpanTracer(capacity=8)
    for i in range(20):
        tr.instant("e", "t", i=i)
    assert len(tr.spans()) == 8
    assert tr.dropped == 12
    assert [s.args["i"] for s in tr.spans()] == list(range(12, 20))
    off = SpanTracer(enabled=False)
    with off.span("x"):
        # disabled tracers record nothing but STILL publish the span
        # name — the sentinel's "compile during <span>" attribution
        # must survive tracing being off
        assert current_span() == "x"
    assert current_span() is None
    off.add("y", "t", 0.0, 1.0)
    assert off.spans() == []


# ---------------------------------------------------------------------------
# profiler satellites: host_statistics / RecordEvent nesting; spans and
# phases as profiler annotations
# ---------------------------------------------------------------------------

def test_record_event_nesting_host_statistics():
    from paddle_tpu import profiler as prof
    prof.reset_host_statistics()
    for _ in range(3):
        with prof.RecordEvent("outer"):
            time.sleep(0.002)
            with prof.RecordEvent("inner"):
                time.sleep(0.002)
    st = prof.host_statistics()
    assert st["outer"]["calls"] == 3 and st["inner"]["calls"] == 3
    # nested spans accumulate independently; inner time is contained
    assert 0 < st["inner"]["total_ms"] <= st["outer"]["total_ms"]
    assert st["outer"]["avg_ms"] == pytest.approx(
        st["outer"]["total_ms"] / 3)
    # manual begin/end (the non-context API) + reset
    ev = prof.RecordEvent("manual")
    ev.begin()
    ev.end()
    ev.end()                              # idempotent, not double-counted
    assert prof.host_statistics()["manual"]["calls"] == 1
    prof.reset_host_statistics()
    assert prof.host_statistics() == {}


def _host_annotations(trace_dir):
    """``{name: [stats dict]}`` of plane ``/host:CPU`` of the newest
    xplane under ``trace_dir``."""
    import glob
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    {"start_ns": ev.start_ns, "dur_ns": ev.duration_ns,
                     **{k: v for k, v in ev.stats}})
    return out


@pytest.mark.parametrize("enabled", [True, False])
def test_span_is_a_profiler_annotation_with_its_args(tmp_path, enabled):
    """One call site writes ring and profiler: a span (and a phase)
    lands on plane /host:CPU with its args as the event's stats,
    whether or not the ring is enabled; a disabled ring still appends
    nothing."""
    tr = SpanTracer(enabled=enabled)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tr.span("obs.test.span", track="t", tick=7, kv_tokens=2 ** 40):
            ph = tr.phases("t.phase", tick=7)
            ph.enter("obs.test.phase.a")
            ph.enter("obs.test.phase.b")
            durs = ph.end()
    finally:
        jax.profiler.stop_trace()
    ann = _host_annotations(tmp_path)
    (span,) = ann["obs.test.span"]
    assert span["tick"] == 7 and span["kv_tokens"] == 2 ** 40
    (a,), (b,) = ann["obs.test.phase.a"], ann["obs.test.phase.b"]
    assert a["tick"] == b["tick"] == 7
    # the phases sit inside the span on the profiler's clock, in order
    assert span["start_ns"] <= a["start_ns"] <= b["start_ns"]
    assert (b["start_ns"] + b["dur_ns"]
            <= span["start_ns"] + span["dur_ns"])
    assert list(durs) == ["obs.test.phase.a", "obs.test.phase.b"]
    names = [s.name for s in tr.spans()]
    if enabled:
        assert names == ["obs.test.phase.a", "obs.test.phase.b",
                         "obs.test.span"]
    else:
        assert names == []


def test_phases_are_contiguous_and_idle_iterations_leave_nothing():
    tr = SpanTracer()
    ph = tr.phases("engine.phase", tick=0)
    ph.enter("a")
    assert current_span() is None       # phases stay off the stack
    t = ph.stop()
    ph.enter("b", at=t)
    ph.enter("c")
    durs = ph.end()
    a, b, c = tr.spans()
    assert (a.t1, b.t1) == (b.t0, c.t0) and a.t1 == t
    assert sum(durs.values()) == pytest.approx((c.t1 - a.t0) / 1e9)
    assert all(s.track == "engine.phase" and s.args == {"tick": 0}
               for s in (a, b, c))
    idle = tr.phases("engine.phase", tick=1)
    idle.enter("a")
    assert list(idle.end(keep=False)) == ["a"]
    assert len(tr.spans()) == 3


# ---------------------------------------------------------------------------
# engine wiring: trace export reconciles with metrics
# ---------------------------------------------------------------------------

def test_engine_trace_reconciles_with_metrics(params, tmp_path):
    """serving_bench --trace acceptance, at test scale: the exported
    timeline is valid Chrome-trace JSON, spans nest on slot tracks, and
    each request's TTFT span equals its ttft_s observation (same
    clock, same stamps — sub-microsecond agreement)."""
    rng = np.random.RandomState(0)
    specs = [(rng.randint(0, 256, (n,)).astype(np.int32), m)
             for n, m in ((3, 4), (7, 3), (12, 5), (5, 6))]
    with _engine(params, trace=True) as eng:
        handles = [eng.submit(p, m) for p, m in specs]
        outs = [h.result(timeout=300) for h in handles]
        path = eng.export_trace(str(tmp_path / "serve.json"))
    doc = json.load(open(path))
    evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert all(e["dur"] >= 0 for e in evs)
    by_req = {}
    for e in evs:
        if "args" in e and "req" in e.get("args", {}):
            by_req.setdefault(e["args"]["req"], {}) \
                  .setdefault(e["name"], []).append(e)
    for h, out in zip(handles, outs):
        spans = by_req[h.id]
        # lifecycle: queue -> (prefill.chunk) -> decode* -> request
        assert {"queue", "ttft", "request"} <= set(spans)
        ttft_us = spans["ttft"][0]["dur"]
        assert ttft_us == pytest.approx(h.ttft_s * 1e6, abs=2.0)
        req_span = spans["request"][0]
        assert req_span["args"]["state"] == "completed"
        assert req_span["args"]["tokens"] == len(out)
        # queue/ttft nest exactly inside the request span; tick-shaped
        # spans (prefill.chunk, decode) START inside it but the FINAL
        # tick's span legitimately outlives finish_t (retirement
        # happens inside the tick, the span covers the whole tick)
        for name in ("queue", "ttft"):
            for e in spans.get(name, []):
                assert e["ts"] >= req_span["ts"] - 2.0
                assert (e["ts"] + e["dur"]
                        <= req_span["ts"] + req_span["dur"] + 2.0)
        for name in ("prefill.chunk", "decode"):
            for e in spans.get(name, []):
                assert e["ts"] >= req_span["ts"] - 2.0
    # engine-phase tracks exist alongside slot tracks
    tracks = {e["cat"] for e in evs}
    assert "engine.decode" in tracks
    assert any(t.startswith("slot") for t in tracks)
    # the ttft histogram saw exactly these observations
    snap = eng.snapshot()
    assert snap["histograms"]["ttft_s"]["count"] == len(specs)


def test_engine_snapshot_concurrent_with_loop(params):
    """Satellite: snapshot()/expose() from a second thread during a
    live run — gauges are read under the tick lock, so slot/pool/trie
    walks cannot race the loop's mutations."""
    rng = np.random.RandomState(1)
    stop = threading.Event()
    errs = []

    def hammer(eng):
        while not stop.is_set():
            try:
                snap = eng.snapshot()
                assert set(snap) == {"counters", "labeled",
                                     "histograms", "gauges"}
                assert "free_pages" in snap["gauges"]
                text = eng.expose()
                assert "paddle_serving_submitted_total" in text
            except Exception as e:      # surfaced after join
                errs.append(e)
                return
    with _engine(params, prefill_chunk=4) as eng:
        threads = [threading.Thread(target=hammer, args=(eng,))
                   for _ in range(2)]
        for t in threads:
            t.start()
        handles = [eng.submit(
            rng.randint(0, 256, (rng.randint(2, 16),)).astype(np.int32),
            int(rng.randint(2, 10))) for _ in range(12)]
        for h in handles:
            h.result(timeout=300)
        stop.set()
        for t in threads:
            t.join()
    assert not errs
    assert eng.snapshot()["counters"]["completed"] == 12


PHASES = ["serving.phase." + p
          for p in ("admit", "build", "dispatch", "readback", "emit")]


def _scripted_run(params, **kw):
    """Two requests queued under the tick lock, so that both are
    admitted in one iteration and every tick's make-up is fixed:
    A = 6 prompt tokens + 3 new, B = 3 + 2, 4 prompt tokens a tick.
    Tick 0 carries A[0:4]; tick 1 A[4:6] + B[0:2]; tick 2 A's decode
    token + B[2:3]; tick 3 is the fused block over A and B."""
    rng = np.random.RandomState(7)
    a = rng.randint(0, 256, (6,)).astype(np.int32)
    b = rng.randint(0, 256, (3,)).astype(np.int32)
    depth = kw.pop("_depth", None)
    with _engine(params, prefill_chunk=4, prefix_cache=False,
                 **kw) as eng:
        with eng._tick_lock:
            if depth is not None:
                eng._depth = depth      # private: no public knob
            ha, hb = eng.submit(a, 3), eng.submit(b, 2)
        outs = [ha.result(timeout=300), hb.result(timeout=300)]
    return eng, (ha, hb), outs


@pytest.fixture(scope="module")
def scripted(params):
    return _scripted_run(params)


def _phases_by_tick(eng):
    by_tick = {}
    for s in eng.tracer.spans():
        if s.track == "engine.phase":
            by_tick.setdefault(s.args["tick"], []).append(s)
    return by_tick


@pytest.mark.parametrize("kind", ["ragged", "block"])
def test_five_phases_partition_a_ticked_iteration(scripted, kind):
    """The engine thread's time in an iteration that ticked is cut into
    admit, build, dispatch, readback, emit: each ends where the next
    starts and they sum to the iteration. With one tick in flight the
    iteration that DISPATCHES tick N reads back and emits tick N-1
    (tick 0's iteration has nothing to read back, and one more
    iteration, carrying the number of a tick it does not launch,
    completes the last), and the ``serving.tick`` span covers its
    tick's dispatch — for the ragged tick and for the fused block."""
    eng, _, _ = scripted
    ticks = {s.args["tick"]: s for s in eng.tracer.spans()
             if s.name == "serving.tick"}
    assert sorted(ticks) == [0, 1, 2, 3]
    want = {"ragged": [0, 1, 2], "block": [3]}[kind]
    assert [t for t, s in sorted(ticks.items())
            if (s.args.get("kind") == "block") == (kind == "block")] == want
    by_tick = _phases_by_tick(eng)
    assert sorted(by_tick) == [0, 1, 2, 3, 4]   # idle polls left nothing
    names = {0: PHASES[:3], 4: PHASES[:2] + PHASES[3:]}
    for t in want + [4]:
        ph = by_tick[t]
        assert [s.name for s in ph] == names.get(t, PHASES)
        for s0, s1 in zip(ph, ph[1:]):
            assert s0.t1 == s1.t0
        assert sum(s.t1 - s.t0 for s in ph) == ph[-1].t1 - ph[0].t0
    for t in want:
        # opens as the dispatch starts, closes as it ends: before the
        # read-back of the tick before it returns
        tick, dispatch = ticks[t], by_tick[t][2]
        assert dispatch.t0 <= tick.t0 <= dispatch.t1 <= tick.t1
        if t:
            assert tick.t1 <= by_tick[t][3].t1


def test_tick_host_is_the_iteration_less_its_readback(scripted):
    eng, _, _ = scripted
    h = eng.metrics.histograms
    by_tick = _phases_by_tick(eng)
    seen = dict.fromkeys(PHASES, 0)
    for i, (t, ph) in enumerate(sorted(by_tick.items())):
        whole = (ph[-1].t1 - ph[0].t0) / 1e9
        blocked = sum(s.dur_s for s in ph if s.name == PHASES[3])
        assert h["tick_host_s"]._vals[i] + blocked == pytest.approx(
            whole, abs=1e-9)
        for s in ph:
            short = s.name.rsplit(".", 1)[1]
            assert h[f"phase_{short}_s"]._vals[seen[s.name]] == \
                pytest.approx(s.dur_s, abs=1e-9)
            seen[s.name] += 1
    assert h["tick_host_s"]._count == len(by_tick) == 5
    assert [seen[p] for p in PHASES] == [5, 5, 4, 4, 4]


def test_tick_in_flight_counters_and_completed_steps(params, scripted):
    """``decode_steps`` counts at COMPLETION (the scripted run: tick 1
    completes no decode row... ticks 2 and 3 carry decode rows), one
    ``decode_step_s`` observation a completed decode tick, and the
    three counters the mechanism brings are in ``snapshot()``: ticks
    1..3 were dispatched with the tick before in flight, the loop
    completed early once (the engine ran empty), and no row outlived
    its request."""
    eng, _, _ = scripted
    snap = eng.snapshot()
    c = snap["counters"]
    assert c["decode_steps"] == 2
    assert eng.metrics.histograms["decode_step_s"]._count == 2
    assert (c["ticks_ahead"], c["inflight_drains"],
            c["overrun_slot_ticks"]) == (3, 1, 0)
    assert snap["labeled"]["inflight_drains"] == [
        {"labels": {"reason": "empty"}, "value": 1}]
    drains = [s for s in eng.tracer.spans() if s.name == "serving.drain"]
    assert [(s.args["reason"], s.args["tick"]) for s in drains] == [
        ("empty", 3)]
    # the slot spans of a tick run from its dispatch to its completion
    tick3 = next(s for s in eng.tracer.spans()
                 if s.name == "serving.tick" and s.args["tick"] == 3)
    decode3 = [s for s in eng.tracer.spans()
               if s.name == "decode" and s.args["tick"] == 3]
    assert len(decode3) == 2
    for s in decode3:
        assert s.t0 <= tick3.t1 and s.t1 >= drains[0].t0
    # an engine held in step (what a drafter's engine observes) keeps
    # the lock-step order through the same dispatch/complete pair
    eng2, _, outs2 = _scripted_run(params, _depth=0)
    c2 = eng2.snapshot()["counters"]
    assert (c2["ticks_ahead"], c2["inflight_drains"],
            c2["decode_steps"]) == (0, 4, 2)
    by_tick = _phases_by_tick(eng2)
    assert sorted(by_tick) == [0, 1, 2, 3]
    for ph in by_tick.values():
        assert [s.name for s in ph] == PHASES


def test_tick_counts_match_the_hand_computed_run(params, scripted):
    """rows launched, rows real and cache tokens attended, by hand for
    the scripted run (S = 4 slots, the one packed width is 4):
    tick 0: 8 rows, 4 real (A[0:4]), A attends 4;
    tick 1: 8 rows, 4 real, A attends 6 + B 2;
    tick 2: 8 rows, 2 real (A decodes, B[2:3]), A attends 7 + B 3;
    tick 3 (block): 4 rows, 2 real, A attends 8 + B 4.
    The span args carry each tick's share; a second run repeats them
    exactly (they are counts, not times). And what a static walk over
    slots x table would have cost against what the live slots hold
    (pages of 4 tokens, 8 a slot, one attention launch a tick): tick 0
    one live slot of 1 page; tick 1 A's 6 tokens (2 pages) + B's 2
    (1); tick 2 7 (2) + 3 (1); tick 3 8 (2) + 4 (1); a table of
    4 x 8 = 32 pages each time."""
    eng, _, outs = scripted
    c = eng.metrics.snapshot()["counters"]
    assert (c["tick_rows"], c["tick_rows_real"],
            c["kv_tokens_attended"]) == (28, 12, 34)
    assert (c["tick_live_slots"], c["kv_pages_walked"],
            c["kv_pages_table"]) == (7, 10, 128)
    ticks = [s.args for s in eng.tracer.spans() if s.name == "serving.tick"]
    per_tick = [(a["rows"], a["rows_real"], a["kv_tokens"]) for a in ticks]
    assert per_tick == [(8, 4, 4), (8, 4, 8), (8, 2, 10), (4, 2, 12)]
    walked = [(a["live_slots"], a["kv_pages"], a["kv_pages_table"])
              for a in ticks]
    assert walked == [(1, 1, 32), (2, 3, 32), (2, 3, 32), (2, 3, 32)]
    # the copies the kernel starts to walk them: a grid step holds a
    # slot's KV heads, so ONE a pool a live page a layer
    layers = eng._cache["k_pages"].shape[0]
    assert [a["kv_page_copies"] for a in ticks] == [
        2 * layers * a["kv_pages"] for a in ticks]
    assert c["kv_page_copies"] == 2 * layers * c["kv_pages_walked"]
    eng2, _, outs2 = _scripted_run(params, trace=False)
    c2 = eng2.metrics.snapshot()["counters"]
    for k in ("tick_rows", "tick_rows_real", "kv_tokens_attended",
              "tick_live_slots", "kv_pages_walked", "kv_pages_table",
              "kv_page_copies", "decode_steps", "tokens_out"):
        assert c2[k] == c[k]
    # a disabled ring changes nothing served and records nothing
    assert eng2.tracer.spans() == []
    for o, o2 in zip(outs, outs2):
        np.testing.assert_array_equal(o, o2)


def test_prefill_wait_span_equals_its_observation(scripted):
    """admission -> the tick that carries the request's first chunk:
    one observation and one ``prefill.wait`` span a request, on the
    same stamps; B waits a tick behind A."""
    eng, (ha, hb), _ = scripted
    waits = {s.args["req"]: s for s in eng.tracer.spans()
             if s.name == "prefill.wait"}
    assert sorted(waits) == sorted([ha.id, hb.id])
    vals = list(eng.metrics.histograms["prefill_wait_s"]._vals)
    assert len(vals) == 2
    for v, req in zip(vals, (ha.id, hb.id)):
        assert waits[req].dur_s == pytest.approx(v, abs=2e-9)
        assert waits[req].track.startswith("slot")
    assert vals[1] > vals[0]
    first_tick = {s.args["req"]: s for s in reversed(eng.tracer.spans())
                  if s.name == "prefill.chunk"}
    for req, w in waits.items():
        assert w.t1 == pytest.approx(first_tick[req].t0, abs=2)


def _train_step_text(debug_info):
    from paddle_tpu.parallel.mesh import init_hybrid_mesh
    hm = init_hybrid_mesh(dp=1, pp=1, tp=1, set_global=False)
    with hm.mesh:
        step, init = L.make_train_step(CFG, hm.mesh)
        state = init(jax.random.PRNGKey(0))
        batch = L.make_batch(CFG, batch_size=2, seq_len=8, mesh=hm.mesh)
        return {"step": step.lower(state, batch).as_text(
            debug_info=debug_info)}


def _tick_texts(model, debug_info):
    from paddle_tpu.serving import engine as E
    E._JIT_CACHE.clear()    # trace anew: the scopes may be patched out
    if model == "moe":
        from paddle_tpu.models import qwen2_moe as Q
        cfg = Q.Qwen2MoeConfig.tiny(dtype=jnp.float32,
                                    use_flash_attention=False, remat=False)
        prm = Q.init_params(cfg, jax.random.PRNGKey(1))
    else:
        cfg, prm = CFG, L.init_params(CFG, jax.random.PRNGKey(0))
    with ServingEngine(prm, cfg, max_batch=2, page_size=4,
                       max_prompt_len=8, max_new_tokens_cap=8,
                       prefill_chunk=4) as eng:
        return eng.program_texts(debug_info=debug_info)


BLOCK_SCOPES = ["layers", "kv_pool.write", "ragged_attn", "attn.qkv_rope",
                "attn.out", "lm_head", "sampler", "embed"]


@pytest.mark.parametrize("program, scopes, module", [
    ("dense", BLOCK_SCOPES + ["mlp"], "jit_serving_tick"),
    ("moe", BLOCK_SCOPES + ["moe.router", "moe.experts", "moe.shared"],
     "jit_serving_tick"),
    ("train", ["loss", "optimizer", "attn.qkv_rope", "attn.out", "mlp"],
     "jit_step_fn"),
])
def test_named_scopes_are_metadata_only(monkeypatch, program, scopes,
                                        module):
    """The device side has names — every scope is on the lowered
    program's operations and the module is named after its function
    (not ``jit__unknown``) — and they are metadata only: with the
    scopes patched out the lowered programs are the same text, so the
    tokens they compute are the same."""
    import contextlib
    texts = (lambda dbg: _train_step_text(dbg) if program == "train"
             else _tick_texts(program, dbg))
    named = texts(True)
    for name, text in named.items():
        want = module + ("_block" if name == "block" else "")
        assert f"module @{want} " in text, (name, text[:200])
        for scope in scopes:
            # a path segment of some operation's location (paths inside
            # a scan body are relative to it in the lowered text)
            assert re.search(rf'["/]{re.escape(scope)}[/"]', text), \
                (name, scope)
    plain = texts(False)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = texts(False)
    assert bare == plain
    assert all("kv_pool.write" not in t for t in texts(True).values())


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_and_dump(tmp_path):
    fr = FlightRecorder(capacity=3)
    for i in range(5):
        fr.record_tick(tick=i, dur_s=0.001 * i)
    assert [t["tick"] for t in fr.ticks()] == [2, 3, 4]
    p = fr.dump(str(tmp_path / "pm.json"),
                error=ValueError("boom"),
                geometry="engine geometry: page_size=4",
                state={"slots": [], "rows": np.arange(3)})
    doc = json.load(open(p))
    assert doc["schema"] == "paddle_tpu.flight_recorder/1"
    assert doc["error"]["type"] == "ValueError"
    assert doc["state"]["rows"] == [0, 1, 2]     # numpy coerced
    assert len(doc["ticks"]) == 3


def test_postmortem_written_on_seeded_invariant_error(params, tmp_path):
    """Acceptance: a seeded KVInvariantError kills the engine AND
    ships a postmortem — violations, geometry, program inventory,
    recent tick ring, span window, state snapshot."""
    from paddle_tpu.analysis.kv_invariants import KVInvariantError
    fdir = str(tmp_path / "flight")
    eng = _engine(params, check_invariants=True, flight_dir=fdir,
                  tick_interval_s=0.005)
    try:
        rng = np.random.RandomState(3)
        eng.submit(rng.randint(0, 256, (9,)).astype(np.int32), 4) \
           .result(timeout=300)
        h = eng.submit(rng.randint(0, 256, (9,)).astype(np.int32), 24)
        it = iter(h)
        next(it)
        with eng._tick_lock:
            nodes = eng.prefix_cache.nodes()
            assert nodes
            nodes[0].refs += 3          # the corruption the audit sees
        with pytest.raises(KVInvariantError):
            h.result(timeout=300)
        for _ in range(200):            # dump happens on the dying worker
            if eng.postmortem_path is not None:
                break
            time.sleep(0.02)
        assert eng.postmortem_path is not None
        assert os.path.dirname(eng.postmortem_path) == fdir
        doc = json.load(open(eng.postmortem_path))
        assert doc["error"]["type"] == "KVInvariantError"
        codes = [v["code"] for v in doc["error"]["violations"]]
        assert "refcount-drift" in codes
        assert "engine geometry:" in doc["geometry"]
        assert doc["expected_programs"]["programs_per_bucket"] <= 2
        assert doc["ticks"] and doc["ticks"][-1]["live"] >= 0
        assert any(s["name"] == "serving.tick" for s in doc["spans"])
        assert doc["state"]["slots"]        # the offending occupancy
        assert doc["metrics"]["counters"]["invariant_violations"] >= 1
    finally:
        eng.close(drain=False)


# ---------------------------------------------------------------------------
# recompile sentinel
# ---------------------------------------------------------------------------

def test_sentinel_trips_on_post_warmup_geometry_change(params):
    """Acceptance: warm one width, arm, then submit a prompt whose
    packed width was never compiled — the sentinel must name the
    compile (WARN metric + RecompileWarning + event tied to the tick
    span), while already-warmed traffic stays clean."""
    from paddle_tpu.serving import engine as _em
    _em._JIT_CACHE.clear()      # force fresh jit objects: compiles fire
    #                             even when XLA's persistent cache hits
    rng = np.random.RandomState(5)
    eng = _engine(params, recompile_sentinel=True)
    try:
        # warmup: width-8 mixed tick + decode programs compile here
        eng.submit(rng.randint(0, 256, (5,)).astype(np.int32), 3) \
           .result(timeout=300)
        rep0 = eng.sentinel.report()
        assert rep0["warmup_compiles"] >= 1 and rep0["clean"]
        eng.arm_sentinel()
        # same geometry again: warmed — must stay clean
        eng.submit(rng.randint(0, 256, (4,)).astype(np.int32), 3) \
           .result(timeout=300)
        assert eng.sentinel.report()["clean"]
        # seeded geometry change: a max-length prompt packs at width
        # 16 — a program warmup never touched
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            eng.submit(rng.randint(0, 256, (16,)).astype(np.int32), 3) \
               .result(timeout=300)
            time.sleep(0.05)
        rep = eng.sentinel.report()
        assert rep["post_warmup_compiles"] >= 1 and not rep["clean"]
        post = [e for e in rep["events"] if e["phase"] == "post_warmup"]
        assert any(e["during"] == "serving.tick" for e in post)
        assert any(isinstance(w.message, RecompileWarning)
                   for w in caught)
        snap = eng.snapshot()
        assert snap["counters"]["recompiles"] >= 1
        labels = {lbl["labels"]["during"]
                  for lbl in snap["labeled"]["recompiles"]}
        assert "serving.tick" in labels
        # the sentinel span landed on its own track
        assert any(s.track == "sentinel" for s in eng.tracer.spans())
    finally:
        eng.close()


def test_sentinel_expected_inventory_matches_static_proof(params):
    """The sentinel's expected-programs document IS the static
    recompile proof's inventory — the same schema graph_lint --json
    emits in its observability block."""
    from paddle_tpu.analysis.recompile import (ServingGeometry,
                                               program_inventory)
    with _engine(params) as eng:
        assert eng.sentinel is not None
        rep = eng.sentinel.report()
        inv = program_inventory(ServingGeometry.of_engine(eng))
        assert rep["expected_programs"] == inv == eng.program_inventory
        assert set(inv) == {"programs_per_bucket", "total", "widths"}
        assert inv["programs_per_bucket"] <= 2
    # closed engine: sentinel detached from the process listener
    assert eng.sentinel._closed


# ---------------------------------------------------------------------------
# the compile ledger and the set-up spans (ISSUE 42)
# ---------------------------------------------------------------------------

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CC = "/jax/compilation_cache/"


def _feed(kind, name=None, seconds=0.0):
    """One monitoring event handed straight to the ledger's listener
    (no program compiles, the shared ``.jax_cache`` is never touched)."""
    from paddle_tpu.observability import sentinel as S
    if kind == "event":
        S._on_event(CC + name)
    elif kind in (TRACE, LOWER, BACKEND):
        fun = name if kind == TRACE else f"jit({name})"
        S._on_event_duration(kind, seconds, fun_name=fun)
    else:
        S._on_event_duration(CC + kind, seconds)


def _program_events(name, cache, trace=0.5, lower=0.25, backend=2.0):
    """The stream JAX emits for one program, in its order."""
    evs = [(TRACE, "inner_of_" + name, 0.125),   # a jit traced INTO it
           (TRACE, name, trace), (LOWER, name, lower)]
    if cache != "uncached":
        evs.append(("event", "compile_requests_use_cache", 0.0))
    if cache == "hit":
        evs += [("event", "cache_hits", 0.0),
                ("compile_time_saved_sec", None, 7.5),
                ("cache_retrieval_time_sec", None, 0.0625)]
    elif cache == "miss":
        evs.append(("event", "cache_misses", 0.0))
    return evs + [(BACKEND, name, backend)]


def _mine(prefix):
    return [r for r in compile_ledger() if r["fun_name"].startswith(prefix)]


def test_ledger_pairs_a_synthetic_event_stream():
    """A hit, a miss, an uncached program, and what is no program: the
    records carry the right seconds, cache state and open span."""
    with process_tracer().span("t42.outer", track="setup"):
        for ev in _program_events("t42a_hit", "hit", backend=0.75):
            _feed(*ev)
    for ev in _program_events("t42a_miss", "miss", backend=3.0):
        _feed(*ev)
    for ev in _program_events("t42a_quick", "miss", backend=0.01)[:-2] \
            + [(BACKEND, "t42a_quick", 0.01)]:
        _feed(*ev)      # asked, neither held nor written: still a miss
    for ev in _program_events("t42a_plain", "uncached", backend=1.0):
        _feed(*ev)
    _feed(TRACE, "t42a_shape_only", 4.0)        # eval_shape: no record
    _feed(BACKEND, "t42a_aot", 0.5)             # compiled, traced long ago
    _feed(LOWER, "t42a_relowered", 0.25)        # lowered again, no trace
    _feed(BACKEND, "t42a_relowered", 0.5)
    recs = {r["fun_name"]: r for r in _mine("t42a_")}
    assert set(recs) == {"t42a_hit", "t42a_miss", "t42a_quick",
                         "t42a_plain", "t42a_aot", "t42a_relowered"}
    hit = recs["t42a_hit"]
    assert (hit["cache"], hit["trace_s"], hit["lower_s"], hit["backend_s"],
            hit["retrieval_s"], hit["saved_s"], hit["during"]) == (
        "hit", 0.5, 0.25, 0.75, 0.0625, 7.5, "t42.outer")
    assert hit["thread"] == threading.current_thread().name
    assert hit["t0"] <= hit["t_end"] <= time.monotonic()
    assert recs["t42a_miss"]["cache"] == "miss"
    assert recs["t42a_miss"]["during"] is None
    assert recs["t42a_quick"]["cache"] == "miss"
    assert recs["t42a_plain"]["cache"] == "uncached"
    assert recs["t42a_plain"]["trace_s"] == 0.5     # not the inner jit's
    aot = recs["t42a_aot"]
    assert (aot["trace_s"], aot["lower_s"], aot["cache"]) == (
        0.0, 0.0, "uncached")       # the orphan trace is not its own
    assert recs["t42a_relowered"]["trace_s"] == 0.0
    assert recs["t42a_relowered"]["lower_s"] == 0.25
    tot = compile_totals(records=list(recs.values()))
    assert (tot["programs"], tot["hits"], tot["misses"], tot["uncached"]) \
        == (6, 1, 2, 3)
    assert tot["compile_s"] == 3.0 + 0.01 + 1.0 + 0.5 + 0.5
    assert tot["hit_s"] == 0.75 and tot["cache_read_s"] == 0.0625
    assert tot["saved_s"] == 7.5
    assert tot["trace_s"] == 2.0 and tot["lower_s"] == 1.25
    # since / until bound the records by their end
    assert compile_totals(since=time.monotonic())["programs"] == 0
    assert compile_totals(until=hit["t_end"])["programs"] \
        < compile_totals()["programs"]


def test_ledger_pairs_per_thread_when_two_threads_interleave():
    import queue
    streams = {"A": _program_events("t42b_A", "hit", trace=1.0),
               "B": _program_events("t42b_B", "uncached", trace=2.0)}
    qs = {k: queue.Queue() for k in streams}
    done = queue.Queue()

    def worker(k):
        for ev in iter(qs[k].get, None):
            _feed(*ev)
            done.put(k)
    threads = [threading.Thread(target=worker, args=(k,), name=f"t42b-{k}")
               for k in streams]
    for th in threads:
        th.start()
    for a, b in zip(streams["A"], streams["B"] + [None] * 4):
        for k, ev in (("A", a), ("B", b)):      # A, B, A, B, ...
            if ev is not None:
                qs[k].put(ev)
                done.get(timeout=10)
    for k, th in zip(streams, threads):
        qs[k].put(None)
        th.join(timeout=10)
    recs = {r["fun_name"]: r for r in _mine("t42b_")}
    assert recs["t42b_A"]["cache"] == "hit"
    assert recs["t42b_A"]["trace_s"] == 1.0
    assert recs["t42b_A"]["thread"] == "t42b-A"
    assert recs["t42b_B"]["cache"] == "uncached"    # A's hit is not B's
    assert recs["t42b_B"]["trace_s"] == 2.0
    assert recs["t42b_B"]["retrieval_s"] == 0.0
    assert recs["t42b_B"]["thread"] == "t42b-B"


def test_a_real_jit_is_one_record_named_after_the_open_span():
    def t42c_probe(x):
        return jnp.tanh(x) * jnp.arange(5.0) + jnp.where(x > 0, x, 0.0)
    x = jnp.ones((5,), jnp.float32)
    jax.block_until_ready(x)
    since = time.monotonic()
    with process_tracer().span("t42.real_jit", track="setup"):
        jax.block_until_ready(jax.jit(t42c_probe)(x))
    recs = [r for r in compile_ledger() if r["t_end"] > since]
    assert [r["fun_name"] for r in recs] == ["t42c_probe"]
    rec = recs[0]
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0 and rec["backend_s"] > 0
    assert rec["during"] == "t42.real_jit"
    assert rec["cache"] in ("hit", "miss", "uncached")
    assert rec["t0"] >= since - 1e-3
    # the three phases lie inside the record's own interval
    assert rec["trace_s"] + rec["lower_s"] + rec["backend_s"] \
        <= rec["t_end"] - rec["t0"] + 5e-3


def _by_name(report):
    out = {}
    for s in report["spans"]:
        out.setdefault(s["name"], []).append(s)
    return out


def test_engine_setup_spans_and_stats_block(params):
    from paddle_tpu.serving import engine as _em
    _em._JIT_CACHE.clear()      # fresh jit objects: the programs compile
    eng = _engine(params)
    try:
        n = eng.warm_programs()
        before = eng.stats()["setup"]
        assert before["ready"] is False and before["time_to_ready_s"] is None
        eng.submit(np.arange(5, dtype=np.int32), 3).result(timeout=300)
        setup = eng.stats()["setup"]
    finally:
        eng.close()
    assert setup["ready"] is True
    spans = _by_name(setup)
    (init,), (warm,) = spans["serving.setup.init"], spans["serving.setup.warm"]
    assert init["parent"] is None and warm["parent"] is None
    kids = spans["serving.setup.init.inventory"] \
        + spans["serving.setup.init.cache"] \
        + spans["serving.setup.init.relay"]     # llama's serving tree
    assert len(kids) == 3
    assert all(k["parent"] == "serving.setup.init" for k in kids)
    assert sum(k["dur_s"] for k in kids) <= init["dur_s"]
    assert init["self_s"] == pytest.approx(
        init["dur_s"] - sum(k["dur_s"] for k in kids), abs=1e-9)
    programs = spans["serving.setup.warm.program"]
    assert len(programs) == n
    assert {"tq", "decode_tail", "spec_k"} <= set(programs[0]["args"])
    assert "block" in programs[-1]["args"]
    (sync,) = spans["serving.setup.warm.sync"]
    inside = programs + [sync]
    assert all(k["parent"] == "serving.setup.warm" for k in inside)
    assert sum(k["dur_s"] for k in inside) <= warm["dur_s"]
    assert all(warm["t0_s"] <= k["t0_s"] and k["t0_s"] + k["dur_s"]
               <= warm["t0_s"] + warm["dur_s"] + 1e-9 for k in inside)
    # every program the warm-up materialised is named after its span
    warmed = [r for r in compile_ledger()
              if warm["t0_s"] < r["t_end"] <= warm["t0_s"] + warm["dur_s"]]
    assert len(warmed) == n
    assert {r["during"] for r in warmed} == {"serving.setup.warm.program"}
    assert {r["fun_name"] for r in warmed} == {"serving_tick",
                                               "serving_tick_block"}
    # the rows partition the program's part of time to ready
    rows = setup["rows"]
    assert rows["train_init_s"] == 0
    parts = ("compile_s", "cache_read_s", "trace_lower_s",
             "engine_init_s", "warm_s")
    assert sum(rows[k] for k in parts) == pytest.approx(
        rows["in_program_s"], abs=1e-6)
    assert rows["in_program_s"] <= setup["time_to_ready_s"]
    assert setup["by_during"]["serving.setup.warm.program"]["programs"] == n
    assert setup["slowest"][0]["fun_name"].startswith("serving_tick")


def test_train_setup_spans_close_when_the_state_is_ready(monkeypatch):
    from paddle_tpu.parallel.mesh import init_hybrid_mesh
    hm = init_hybrid_mesh(dp=2, pp=1, tp=2, set_global=False)
    blocked_in = []
    real = jax.block_until_ready
    monkeypatch.setattr(
        jax, "block_until_ready",
        lambda x: (blocked_in.append(current_span()), real(x))[1])
    key = jax.block_until_ready(jax.random.PRNGKey(0))
    since = time.monotonic()
    with hm.mesh:
        step, init = L.make_train_step(CFG, hm.mesh)
        state = init(key)
        t_back = time.monotonic()
    assert "train.setup.init" in blocked_in
    assert all(leaf.is_ready() for leaf in jax.tree_util.tree_leaves(state))
    rep = setup_report(since=since)
    spans = _by_name(rep)
    (build,), (ini,) = spans["train.setup.build"], spans["train.setup.init"]
    assert build["parent"] is None and ini["parent"] is None
    assert build["t0_s"] + build["dur_s"] <= ini["t0_s"]
    assert ini["t0_s"] + ini["dur_s"] <= t_back
    made = [r for r in compile_ledger() if r["t_end"] > since]
    assert made and {r["during"] for r in made} <= {"train.setup.build",
                                                    "train.setup.init"}
    rows = rep["rows"]
    assert rows["train_init_s"] > 0 and rows["engine_init_s"] == 0
    assert rows["train_init_s"] + rows["compile_s"] + rows["cache_read_s"] \
        + rows["trace_lower_s"] == pytest.approx(rows["in_program_s"],
                                                 abs=1e-6)
    # the MoE family's trainer opens the same spans
    from paddle_tpu.models import qwen2_moe as Q
    qcfg = Q.Qwen2MoeConfig.tiny(dtype=jnp.float32)
    since = time.monotonic()
    with hm.mesh:
        _, qinit = Q.make_train_step(qcfg, hm.mesh)
        qinit(key)
    assert {"train.setup.build", "train.setup.init"} <= set(
        _by_name(setup_report(since=since)))


def test_armed_sentinel_names_the_program_and_the_cache():
    from paddle_tpu.observability import RecompileSentinel
    m, tr = ServingMetrics(), SpanTracer()
    s = RecompileSentinel(tracer=tr, metrics=m, label="t42")
    try:
        for ev in _program_events("t42e_warm", "miss"):
            _feed(*ev)
        assert s.report()["warmup_compiles"] == 1 and s.clean
        s.arm()
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            with tr.span("serving.tick"):
                for ev in _program_events("t42e_late", "hit", backend=0.5):
                    _feed(*ev)
            for ev in _program_events("t42e_idle", "miss"):
                _feed(*ev)
    finally:
        s.close()
    msgs = [str(w.message) for w in caught
            if isinstance(w.message, RecompileWarning)]
    assert len(msgs) == 2
    assert "t42e_late" in msgs[0] and "(hit)" in msgs[0]
    assert "during serving.tick" in msgs[0]
    assert "t42e_idle" in msgs[1] and "(miss)" in msgs[1]
    rep = s.report()
    assert rep["post_warmup_compiles"] == 2 and not rep["clean"]
    late = rep["events"][-2]
    assert (late["program"], late["cache"], late["during"], late["phase"]) \
        == ("t42e_late", "hit", "serving.tick", "post_warmup")
    assert late["compile_s"] == 0.5
    labels = [lbl["labels"] for lbl in m.snapshot()["labeled"]["recompiles"]]
    assert {"during": "serving.tick", "program": "t42e_late",
            "cache": "hit"} in labels
    assert {"during": "idle", "program": "t42e_idle",
            "cache": "miss"} in labels
    sent = [sp for sp in tr.spans() if sp.track == "sentinel"]
    assert [sp.args["program"] for sp in sent] == ["t42e_late", "t42e_idle"]
    assert "t42e_late" in sent[0].name and "hit" in sent[0].name
    # detached: a later compile reaches the ledger, not the sentinel
    _feed(BACKEND, "t42e_after_close", 0.25)
    assert s.report()["post_warmup_compiles"] == 2
    assert _mine("t42e_after_close")


def test_perf_counter_and_monotonic_are_one_clock_here():
    """The benchmark stamps ``time.perf_counter()``, the program
    ``time.monotonic()``; its set-up readers convert through one offset,
    which on Linux is 0 (both read CLOCK_MONOTONIC)."""
    offs = [time.monotonic() - time.perf_counter() for _ in range(5)]
    assert max(abs(o) for o in offs) < 1e-3


def test_ledger_and_process_ring_stay_bounded():
    """Last of the set-up tests: it pushes everything older out."""
    from paddle_tpu.observability import sentinel as S
    from paddle_tpu.observability import ledger_health
    held, dropped = len(compile_ledger()), ledger_health()["dropped"]
    extra = S.LEDGER_CAPACITY + 10
    for i in range(extra):
        _feed(BACKEND, f"t42f_{i}", 0.001)
    recs = compile_ledger()
    assert len(recs) == S.LEDGER_CAPACITY
    assert recs[-1]["fun_name"] == f"t42f_{extra - 1}"
    tot = ledger_health()
    assert tot["held"] == S.LEDGER_CAPACITY
    assert tot["dropped"] - dropped == held + extra - S.LEDGER_CAPACITY
    assert tot["listener_events"] >= extra and tot["listener_s"] > 0
    assert tot["listener_s"] / tot["listener_events"] < 1e-3
    tr = process_tracer()
    cap = tr._ring.maxlen
    assert cap == 256
    d0, n0 = tr.dropped, len(tr.spans())
    for i in range(cap + 5):
        with setup_span("t42f.span", i=i):
            pass
    assert len(tr.spans()) == cap
    assert tr.dropped - d0 == n0 + 5
    assert all(s.track == "setup" for s in tr.spans())
    assert setup_report()["rows"]["in_program_s"] >= 0


# ---------------------------------------------------------------------------
# measured overhead (slow): the ≤3% pin
# ---------------------------------------------------------------------------

def _load_bench():
    import importlib.util
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "serving_bench.py")
    spec = importlib.util.spec_from_file_location("serving_bench", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.slow
def test_tracing_overhead_within_3pct():
    """ISSUE r13 acceptance: instrumented tick wall ≤ 3% over untraced
    on the serving_bench default preset. The true cost is sub-1% (a
    dozen ring appends against a multi-ms tick); co-tenant CPU noise
    swings ±4%, so best-of-3 bench invocations (each itself interleaved
    best-of-6 per arm)."""
    sb = _load_bench()
    ratios = []
    for attempt in range(3):
        res = sb.main(["--requests", "64", "--seed", str(attempt),
                       "--modes", "trace_overhead"])
        r = res["trace_overhead"]["overhead_ratio"]
        ratios.append(r)
        if r <= 1.03:
            break
    assert min(ratios) <= 1.03, f"tracing overhead ratios: {ratios}"
