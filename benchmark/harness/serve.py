"""Modes ``serve_open`` and ``serve_closed``: the program's
``ServingEngine`` under a load the harness generates and times.

The harness measures from the client's side with the host's clock: one
client thread per request reads the handle's iterator and stamps every
token. Nothing end to end is read from the program.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import jax
import numpy as np

from . import reference, traffic
from .common import CompileCounter, check, log, pctl, start_trace

# geometry a workload file may set; every policy knob of the engine
# (decode_block_size, admission_window, prefix_cache, attn_impl,
# speculation, quantisation) stays at the program's default
GEOMETRY_KEYS = {"max_batch", "page_size", "total_pages", "max_prompt_len",
                 "max_new_tokens_cap", "prompt_buckets", "prefill_chunk"}


@dataclasses.dataclass
class Record:
    req: traffic.Req
    due_t: float = 0.0
    send_t: float = 0.0
    token_t: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    error: str | None = None
    done_t: float | None = None

    @property
    def finished(self) -> bool:
        return (self.error is None and self.done_t is not None
                and len(self.tokens) == self.req.max_new_tokens)


def build_engine(cell, params, cfg, mod):
    from paddle_tpu.serving import ServingEngine
    geo = dict(cell.workload["engine"])
    bad = set(geo) - GEOMETRY_KEYS
    if bad:
        raise SystemExit(f"workload file pins engine knobs {sorted(bad)}; "
                         f"only geometry {sorted(GEOMETRY_KEYS)} may be set")
    if "prompt_buckets" in geo:
        geo["prompt_buckets"] = tuple(geo["prompt_buckets"])
    return ServingEngine(params, cfg, model=mod, **geo)


def warm_engine(eng, vocab: int, prompt_len: int) -> int:
    """Every tick program, then one prompt end to end, twice (admission,
    chunked prefill, retirement, then a prefix-cache hit), as
    ``chip_smoke.py`` does."""
    n = eng.warm_programs()
    warm = np.random.default_rng(1).integers(
        0, vocab, (prompt_len,), dtype=np.int32)
    for _ in range(2):
        eng.submit(warm, 2).result(timeout=600)
    return n


def _client(eng, rec: Record):
    """One request as its client lives it: submit, then stamp every
    token as the handle's iterator yields it."""
    try:
        h = eng.submit(rec.req.prompt, rec.req.max_new_tokens)
        rec.send_t = time.perf_counter()
        for tok in h:
            rec.token_t.append(time.perf_counter())
            rec.tokens.append(int(tok))
        if len(rec.tokens) != rec.req.max_new_tokens:
            rec.error = f"ended {h.status} after {len(rec.tokens)} tokens"
        rec.done_t = time.perf_counter()
    except Exception as e:  # rejected or engine error: a failed request
        rec.error = f"{type(e).__name__}: {e}"


def drive_open(eng, reqs, t0: float, mark):
    """Sends each request when it is due (offset from ``t0``; lead-in
    requests have negative offsets), one client thread each; calls
    ``mark()`` as the window opens."""
    recs, threads = [], []
    marked = False
    for r in reqs:
        rec = Record(r, due_t=t0 + r.due_s)
        if not marked and r.due_s >= 0:
            time.sleep(max(t0 - time.perf_counter(), 0.0))
            mark()
            marked = True
        wait = rec.due_t - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(target=_client, args=(eng, rec), daemon=True)
        th.start()
        recs.append(rec)
        threads.append(th)
    return recs, threads


def drive_closed(eng, reqs, clients: int, t0: float, seconds: float,
                 lead_s: float, mark):
    """``clients`` threads, each sending the next unissued request when
    its last one ended, from ``lead_s`` before the window opens (those
    requests load the system and are not counted) until it closes."""
    recs, lock = [], threading.Lock()
    nxt = [0]
    t_end = t0 + seconds

    def loop():
        while time.perf_counter() < t_end:
            with lock:
                i = nxt[0]
                nxt[0] += 1
            now = time.perf_counter()
            req = dataclasses.replace(reqs[i % len(reqs)],
                                      counted=now >= t0)
            rec = Record(req, due_t=now)
            with lock:
                recs.append(rec)
            _client(eng, rec)

    threads = [threading.Thread(target=loop, daemon=True)
               for _ in range(clients)]
    for th in threads:
        th.start()
    time.sleep(max(t0 - time.perf_counter(), 0.0))
    mark()
    return recs, threads


def join_all(threads, deadline: float) -> None:
    for th in threads:
        th.join(max(deadline - time.perf_counter(), 0.0))


class TraceSpan:
    """Profiler trace of a few seconds inside the window, on a thread
    of its own, with the engine's tick counter read at both ends."""

    def __init__(self, eng, t0: float, after_s: float, for_s: float):
        self.eng, self.ticks, self.error = eng, None, None
        self.th = threading.Thread(
            target=self._run, args=(t0 + after_s, for_s), daemon=True)
        self.th.start()

    def _ticks(self) -> int:
        return int(self.eng.metrics.snapshot()["counters"]["decode_steps"])

    def _run(self, start_t: float, for_s: float):
        try:
            time.sleep(max(start_t - time.perf_counter(), 0.0))
            start_trace()
            n0, t_a = self._ticks(), time.perf_counter()
            time.sleep(for_s)
            n1, t_b = self._ticks(), time.perf_counter()
            jax.profiler.stop_trace()
            self.ticks, self.host_span_s = n1 - n0, t_b - t_a
        except Exception as e:
            self.error = f"{type(e).__name__}: {e}"

    def join(self):
        self.th.join()
        if self.error:
            raise RuntimeError(f"tracing failed: {self.error}")


def hist_window(eng, name: str, start_count: int) -> list:
    """The observations a histogram of the engine took since its count
    was ``start_count``."""
    h = eng.metrics.histograms[name]
    with eng.metrics._lock:
        n_new = h._count - start_count
        vals = list(h._vals)
    return vals[-n_new:] if n_new > 0 else []


def longest_sequence(cell) -> int:
    geo = cell.workload["engine"]
    return reference.pad_to(geo["max_prompt_len"]
                            + geo["max_new_tokens_cap"])


def sample_for_check(recs, seed: int, extra: int):
    """The finished request with the most tokens, and ``extra`` more
    drawn from the seed."""
    done = [r for r in recs if r.finished and r.req.counted]
    if not done:
        return []
    longest = max(done, key=lambda r: r.req.prompt.size + len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = traffic.seed_rng(seed, 3)
    pick = rng.permutation(len(rest))[:extra]
    return [longest] + [rest[i] for i in pick]


def compare_with_reference(params, sample, model, family, limits,
                           checks, control: bool = False,
                           pad_len: int | None = None):
    """Teacher-forced float32 reference over each sampled request: the
    widest and the mean gap of a served token's logit below the
    reference's best. With ``control`` also the gaps of the token a
    3-mantissa-bit (float8) reference puts first (read by ``control.py`` only)."""
    gaps, low = [], []
    for rec in sample:
        g, c = reference.served_gaps(
            params, rec.req.prompt, rec.tokens, model, family,
            family.CONTROL_ROUND_TO if control else None, pad_len)
        gaps.append(g)
        if c is not None:
            low.append(c)
    out = {"served_tokens": int(sum(g.size for g in gaps)),
           "requests": len(sample)}
    if gaps:
        allg = np.concatenate(gaps)
        out.update(gap_max=float(allg.max()), gap_mean=float(allg.mean()))
        log(f"[correct] reference over {len(sample)} requests, "
            f"{allg.size} served tokens (longest sequence "
            f"{max(r.req.prompt.size + len(r.tokens) for r in sample)})")
        check("served_logit_gap_max", out["gap_max"],
              limits["served_logit_gap_max"], checks)
        check("served_logit_gap_mean", out["gap_mean"],
              limits["served_logit_gap_mean"], checks)
    else:
        log("[correct] no finished request to compare")
        checks.note("requests_compared", 0, 1, False)
    if low:
        allc = np.concatenate(low)
        out.update(control_gap_max=float(allc.max()),
                   control_gap_mean=float(allc.mean()))
        log(f"[control] 3-mantissa-bit (float8) reference: gap_max "
            f"{out['control_gap_max']:.6g} gap_mean "
            f"{out['control_gap_mean']:.6g}")
    return out


def run_window(cell, eng, model, seed: int, seconds: float,
               trace: bool, rate: float | None = None):
    """One measured window on a warmed engine. Returns the records and
    what the window's readers need."""
    tr = cell.traffic
    wl = cell.workload
    slots = eng.scheduler.max_batch
    if tr["loop"] == "open":
        rate = float(rate if rate is not None else wl["rate_rps"])
        n = traffic.open_loop_count(rate, seconds)
    else:
        n = int(wl["request_pool"])
    reqs = traffic.build_requests(tr, seed, n, model["vocab_size"], rate)
    lead_s = float(wl.get("lead_in_s", 0.0))
    if tr["loop"] == "open" and lead_s:
        reqs = traffic.lead_in(reqs, tr, seed, model["vocab_size"],
                               n / rate, lead_s) + reqs
    compiles = CompileCounter()
    compiles.arm()
    t0 = time.perf_counter() + 0.05 + lead_s
    marks = {}

    def mark():     # the engine's counts as the window opens
        marks["hist"] = {k: h._count
                         for k, h in eng.metrics.histograms.items()}
        marks["counters"] = dict(eng.metrics.snapshot()["counters"])
    span = (TraceSpan(eng, t0, wl.get("trace_after_s", 3.0),
                      wl.get("trace_seconds", 3.0)) if trace else None)
    t_close = t0 + seconds
    if tr["loop"] == "open":
        recs, threads = drive_open(eng, reqs, t0, mark)
    else:
        clients = int(tr["clients_per_slot"] * slots)
        recs, threads = drive_closed(eng, reqs, clients, t0, seconds,
                                     lead_s, mark)
    time.sleep(max(t_close - time.perf_counter(), 0.0))
    queued_at_close = eng.scheduler.queued()
    deadline = t_close + wl["drain_s"]
    join_all(threads, deadline)
    if span is not None:
        span.join()
    n_compiles = compiles.disarm()
    stuck = sum(th.is_alive() for th in threads)
    hists = {k: hist_window(eng, k, c0) for k, c0 in marks["hist"].items()}
    counters0 = marks["counters"]
    counters1 = eng.metrics.snapshot()["counters"]
    return {
        "records": recs, "t0": t0, "t_close": t_close, "seconds": seconds,
        "deadline": deadline,
        "rate": rate, "compiles_in_window": n_compiles, "stuck": stuck,
        "queued_at_close": queued_at_close, "hists": hists,
        "counters": {k: counters1[k] - counters0.get(k, 0)
                     for k in counters1},
        "trace_ticks": span.ticks if span else None,
        "loop": tr["loop"], "slots": slots,
    }


def end_to_end(win: dict) -> tuple:
    """``(metrics, attempted, failed, info)`` from the window's records,
    by the host's clock at the client."""
    every, t0, t_close = win["records"], win["t0"], win["t_close"]
    recs = [r for r in every if r.req.counted]
    ok = [r for r in recs if r.finished and r.done_t <= win["deadline"]]
    attempted, failed = len(recs), len(recs) - len(ok)
    metrics, info = {}, {}
    if win["loop"] == "open":
        ttft = [(r.token_t[0] - r.due_t) * 1e3 for r in ok]
        itl = [(b - a) * 1e3 for r in ok
               for a, b in zip(r.token_t, r.token_t[1:])]
        late = [(r.send_t - r.due_t) * 1e3 for r in recs if r.send_t]
        if ttft and itl:
            metrics["ttft_p95_ms"] = {"value": pctl(ttft, 95), "unit": "ms"}
            metrics["itl_p95_ms"] = {"value": pctl(itl, 95), "unit": "ms"}
            info = {"ttft_n": len(ttft), "ttft_p50_ms": pctl(ttft, 50),
                    "ttft_mean_ms": float(np.mean(ttft)),
                    "itl_n": len(itl), "itl_p50_ms": pctl(itl, 50),
                    "gen_late_ms": late}
    else:
        inside = sum(1 for r in every for t in r.token_t
                     if t0 <= t <= t_close)
        metrics["serve_tokens_per_s"] = {
            "value": inside / win["seconds"], "unit": "tokens/s"}
        info = {"tokens_in_window": inside,
                "requests_finished": len(ok)}
    return metrics, attempted, failed, info


def setup(cell, seed: int, devs):
    """Weights on the device from the seed, the engine, its programs
    warmed. Returns ``(engine, params, model, family)``."""
    family = cell.family
    cfg, mod = family.program_config(cell.model)
    with jax.default_device(devs[0]):
        params = family.make_params(cell.model, seed)
        jax.block_until_ready(params)
        eng = build_engine(cell, params, cfg, mod)
        n = warm_engine(eng, cell.model["vocab_size"],
                        int(cell.workload["warm_prompt_tokens"]))
    log(f"[setup] {n} tick programs warmed; slots "
        f"{eng.scheduler.max_batch} pages/slot "
        f"{eng.scheduler.pages_per_slot} total pages "
        f"{eng.pool.total_pages} width grid {eng._w_grid}")
    return eng, params, cell.model, family
