"""Traffic-replay serving benchmark: sequential vs DynamicBatcher vs
the continuous-batching ServingEngine.

Replays one synthetic mixed-length request trace (Poisson arrivals,
mixed prompt lengths, mixed max_new_tokens) through three serving
strategies over the SAME model params:

  (a) sequential    — one `generate_paged` per request, in arrival
                      order (no batching at all);
  (b) batcher       — `inference.DynamicBatcher` whole-request ragged
                      batching: mixed-length prompts coalesce into one
                      paged decode, but every batch runs the GLOBAL
                      max_new_tokens and a request's tokens only
                      surface when the whole batch finishes;
  (c) engine        — `serving.ServingEngine` continuous batching:
                      per-step admission/retirement over the shared
                      page pool, tokens streamed as decoded (the model
                      family's `SERVING` record under
                      `models/serving_tick.py`).

Reported per mode: wall_s, useful tok/s (only each request's OWN
requested tokens count), time-to-first-token p50/p99 (ms), and mean
batch occupancy where defined. Acceptance (ISSUE r6): (c) beats (b) on
aggregate tok/s AND p99 TTFT on the CPU mesh.

``--shared-prefix N`` prepends one fixed N-token header to every prompt
(the common-system-prompt workload the r8 prefix cache targets) and adds
prefix-cache counters to the engine row. The ``prefix_ab`` mode emits
the ISSUE r8 acceptance numbers directly: cold-vs-warm TTFT on one
shared prefix, pages saved, and the max decode stall an in-flight stream
feels while a max-length prompt is admitted — chunked vs unchunked
prefill.

``--speculative`` serves the engine mode with self-drafting (n-gram)
speculative decoding (``--spec-k`` caps drafts); the ``spec_ab`` mode
emits the ISSUE r15 acceptance numbers: target-model launches per
emitted token, speculative vs plain greedy, on a repetitive
single-stream workload — with outputs asserted bitwise-equal across
the arms.

``--replicas N`` (the ``fleet`` mode) drives the serving FLEET
(paddle_tpu/serving/fleet/): N engine replicas behind the
prefix-affinity router, a multi-turn multi-session shared-prefix
workload A/B'd against forced round-robin (the hit-rate claim), a
flood 1-vs-N scaling arm, and a kill-one-replica scenario
(drain-on-failure: queued hand-back + re-dispatch, zero drops, clean
survivor sentinels). ``--arrival seed:K`` pins a replayable arrival
schedule (inter-arrival + length draws) independent of content.

    JAX_PLATFORMS=cpu python tools/serving_bench.py --requests 32
    JAX_PLATFORMS=cpu python tools/serving_bench.py \
        --shared-prefix 24 --modes engine prefix_ab
    JAX_PLATFORMS=cpu python tools/serving_bench.py \
        --replicas 4 --arrival seed:1
"""
import argparse
import json
import os
import sys
import threading
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


class ArrivalSpec:
    """Replayable HEAVY-TAILED schedule (``--arrival lognormal:K[:s]``
    / ``pareto:K[:a]``): inter-arrival gaps, prompt lengths and output
    lengths all draw from the heavy-tailed law instead of the uniform/
    exponential defaults — the production traffic shape (a few huge
    prompts/outputs among many small ones) that convoy/admission
    policies must be measured under. Same replay contract as
    ``seed:K``: the spec string alone reproduces the schedule bitwise,
    whatever ``--seed`` says about content.

    Gaps keep MEAN ``1/rate`` so ``--rate`` means the same offered
    load across laws: lognormal uses ``mu = ln(1/rate) - sigma^2/2``;
    Pareto (Lomax) scales by ``(alpha-1)/rate`` and needs
    ``alpha > 1`` for the mean to exist. Lengths map a mean-1 draw of
    the same law onto ``[lo, hi]`` (mass near ``lo``, rare spikes
    capped at ``hi``); output lengths pick from the sorted
    ``--mnt-choices`` by the same draw (small outputs common, the big
    choice rare)."""

    def __init__(self, kind, seed, param=None):
        if kind not in ("lognormal", "pareto"):
            raise ValueError(f"unknown arrival law {kind!r}")
        self.kind = kind
        self.seed = int(seed)
        self.param = 1.5 if param is None else float(param)
        if kind == "pareto" and self.param <= 1.0:
            raise ValueError("pareto alpha must be > 1 (finite mean), "
                             f"got {self.param}")
        if kind == "lognormal" and self.param <= 0.0:
            raise ValueError("lognormal sigma must be > 0, "
                             f"got {self.param}")

    def __repr__(self):
        return f"ArrivalSpec({self.kind}:{self.seed}:{self.param})"

    def gaps(self, sched, rate, n):
        """n inter-arrival gaps with mean 1/rate."""
        if self.kind == "lognormal":
            s = self.param
            mu = np.log(1.0 / rate) - 0.5 * s * s
            return sched.lognormal(mu, s, n)
        a = self.param
        return sched.pareto(a, n) * (a - 1.0) / rate

    def _unit(self, sched):
        """One mean-1 draw of the law (shared by lengths + mnt)."""
        return float(self.gaps(sched, 1.0, 1)[0])

    def length(self, sched, lo, hi):
        """Heavy-tailed int length in [lo, hi]."""
        lo, hi = int(lo), int(hi)
        if hi <= lo:
            return lo
        # mean-1 draw scaled so the typical draw sits in the lower
        # third of the span; the tail hits hi and is capped there
        d = self._unit(sched) * (hi - lo) / 3.0
        return lo + min(int(d), hi - lo)

    def pick(self, sched, choices):
        """Heavy-tailed pick over sorted choices (small ones common)."""
        cs = sorted(int(c) for c in choices)
        i = int(self._unit(sched) * len(cs) / 2.0)
        return cs[min(i, len(cs) - 1)]


def parse_arrival(spec):
    """``--arrival`` spec -> schedule-RNG seed, :class:`ArrivalSpec`,
    or None (legacy: the schedule rides the content seed).

    * ``seed:K`` — dedicated, replayable arrival schedule (ROADMAP
      item 5's first slice): the SAME ``seed:K`` reproduces identical
      inter-arrival gaps, prompt lengths and mnt draws whatever
      ``--seed`` says, so fleet A/Bs and the kill-replica scenario
      replay bit-identical schedules while varying content.
    * ``lognormal:K[:sigma]`` / ``pareto:K[:alpha]`` — same replay
      contract with HEAVY-TAILED gaps + lengths (:class:`ArrivalSpec`;
      defaults sigma=1.5, alpha=1.5)."""
    if spec is None:
        return None
    if isinstance(spec, str):
        if spec.startswith("seed:"):
            return int(spec.split(":", 1)[1])
        parts = spec.split(":")
        if parts[0] in ("lognormal", "pareto") and len(parts) in (2, 3):
            return ArrivalSpec(parts[0], int(parts[1]),
                               float(parts[2]) if len(parts) == 3
                               else None)
    raise ValueError(f"--arrival must be 'seed:K', 'lognormal:K[:s]' "
                     f"or 'pareto:K[:a]', got {spec!r}")


def build_trace(n, rate, max_prompt, mnt_choices, seed, shared_prefix=0,
                arrival=None):
    """[(arrival_s, prompt int32[?], max_new_tokens)] sorted by arrival.
    mnt_choices is a SMALL set so every mode compiles a bounded number
    of programs. shared_prefix > 0 prepends one fixed token header to
    EVERY prompt (the common-system-prompt serving shape the prefix
    cache exists for). ``arrival`` (see :func:`parse_arrival`) splits
    the SCHEDULE draws (inter-arrival gaps, prompt lengths, mnt
    choices) onto their own seeded RNG, leaving ``seed`` to govern
    content only."""
    rng = np.random.RandomState(seed)
    heavy = isinstance(arrival, ArrivalSpec)
    sched = rng if arrival is None else np.random.RandomState(
        arrival.seed if heavy else arrival)
    arrivals = np.cumsum(arrival.gaps(sched, rate, n) if heavy
                         else sched.exponential(1.0 / rate, n))
    header = (rng.randint(0, 256, (shared_prefix,)).astype(np.int32)
              if shared_prefix else None)
    lo = min(shared_prefix + 2, max_prompt)
    trace = []
    for t in arrivals:
        plen = (arrival.length(sched, max(lo, 2), max_prompt) if heavy
                else int(sched.randint(max(lo, 2), max_prompt + 1)))
        prompt = rng.randint(0, 256, (plen,)).astype(np.int32)
        if header is not None:
            prompt[:shared_prefix] = header
        mnt = (arrival.pick(sched, mnt_choices) if heavy
               else int(sched.choice(mnt_choices)))
        trace.append((float(t), prompt, mnt))
    return trace


def build_session_trace(groups, group_size, rate, header_tokens,
                        tail_lo, tail_hi, mnt_choices, seed,
                        arrival=None):
    """Multi-session shared-prefix workload for the FLEET modes: ``groups``
    sessions, each with its own fixed ``header_tokens``-token header
    (system prompt), ``group_size`` requests per session with random
    tails, arrival order interleaved across sessions by the schedule
    RNG. Returns ``[(arrival_s, group_id, prompt, mnt)]``. This is the
    workload where routing decides the hit rate: affinity keeps each
    session's header on ONE replica (~1 cold prefill per session);
    round-robin scatters it over N cold tries."""
    rng = np.random.RandomState(seed)
    heavy = isinstance(arrival, ArrivalSpec)
    sched = rng if arrival is None else np.random.RandomState(
        arrival.seed if heavy else arrival)
    headers = [rng.randint(0, 256, (header_tokens,)).astype(np.int32)
               for _ in range(groups)]
    order = np.repeat(np.arange(groups), group_size)
    sched.shuffle(order)
    arrivals = np.cumsum(arrival.gaps(sched, rate, order.size) if heavy
                         else sched.exponential(1.0 / rate, order.size))
    trace = []
    for t, g in zip(arrivals, order):
        tlen = (arrival.length(sched, tail_lo, tail_hi) if heavy
                else int(sched.randint(tail_lo, tail_hi + 1)))
        tail = rng.randint(0, 256, (tlen,)).astype(np.int32)
        prompt = np.concatenate([headers[int(g)], tail])
        mnt = (arrival.pick(sched, mnt_choices) if heavy
               else int(sched.choice(mnt_choices)))
        trace.append((float(t), int(g), prompt, mnt))
    return trace


def _bucket(n, buckets):
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def _pctl(xs, q):
    return float(np.percentile(np.asarray(xs), q)) if xs else float("nan")


def _report(name, wall, useful, ttfts, occupancy=None):
    out = {"mode": name, "wall_s": round(wall, 3),
           "useful_tokens": int(useful),
           "tok_s": round(useful / wall, 1),
           "ttft_p50_ms": round(_pctl(ttfts, 50) * 1e3, 1),
           "ttft_p99_ms": round(_pctl(ttfts, 99) * 1e3, 1)}
    if occupancy is not None:
        out["occupancy_mean"] = round(occupancy, 3)
    return out


class Bench:
    def __init__(self, args):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.models import llama as L
        self.jnp = jnp
        self.L = L
        self.args = args
        self.cfg = L.LlamaConfig(
            vocab_size=256, hidden_size=args.hidden,
            intermediate_size=2 * args.hidden,
            num_hidden_layers=args.layers,
            num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=args.max_prompt + max(args.mnt_choices),
            dtype=jnp.float32, use_flash_attention=False, remat=False)
        self.params = L.init_params(self.cfg, jax.random.PRNGKey(0))
        # the ENGINE's bucket policy, so every mode pads to the same
        # shapes as the engine under test (no silent drift)
        from paddle_tpu.serving.engine import _default_buckets
        self.buckets = _default_buckets(args.max_prompt)
        self.mnt_cap = max(args.mnt_choices)
        # one jitted ragged generate per (B, Tb, mnt): shared by (a)/(b)
        self._gen = jax.jit(
            partial(L.generate_paged, cfg=self.cfg, page_size=args.page_size),
            static_argnames=("max_new_tokens",))

    def _pad(self, prompts):
        lens = [len(p) for p in prompts]
        tb = _bucket(max(lens), self.buckets)
        out = np.zeros((len(prompts), tb), np.int32)
        for i, p in enumerate(prompts):
            out[i, :len(p)] = p
        return out, np.asarray(lens, np.int32)

    # ------------------------------------------------------------ modes ----
    def run_sequential(self, trace):
        jnp = self.jnp
        t0 = time.perf_counter()
        useful, ttfts = 0, []
        for arrival, prompt, mnt in trace:
            now = time.perf_counter() - t0
            if now < arrival:
                time.sleep(arrival - now)
            padded, lens = self._pad([prompt])
            out = self._gen(self.params, jnp.asarray(padded),
                            jnp.asarray(lens), max_new_tokens=mnt)
            np.asarray(out)  # block
            ttfts.append(time.perf_counter() - t0 - arrival)
            useful += mnt
        return _report("sequential", time.perf_counter() - t0, useful,
                       ttfts)

    def run_batcher(self, trace):
        """Whole-request ragged batching: the r5 serving shape. Every
        batch decodes the GLOBAL mnt cap (the batcher cannot retire rows
        early), and a request's TTFT is its whole batch's completion."""
        from paddle_tpu.inference import DynamicBatcher
        jnp = self.jnp
        cap = self.mnt_cap

        def fn(batch, lengths):
            out = self._gen(self.params, jnp.asarray(batch),
                            jnp.asarray(lengths), max_new_tokens=cap)
            return np.asarray(out)

        bat = DynamicBatcher(fn, max_batch_size=self.args.max_batch,
                             max_delay_ms=self.args.batch_delay_ms,
                             seq_buckets=self.buckets)
        t0 = time.perf_counter()
        done_t, lock = {}, threading.Lock()
        futs = []
        for i, (arrival, prompt, mnt) in enumerate(trace):
            now = time.perf_counter() - t0
            if now < arrival:
                time.sleep(arrival - now)
            fut = bat.submit(prompt)

            def _mark(f, i=i):
                with lock:
                    done_t[i] = time.perf_counter()
            fut.add_done_callback(_mark)
            futs.append(fut)
        for f in futs:
            f.result()
        wall = time.perf_counter() - t0
        bat.close()
        useful = sum(mnt for _, _, mnt in trace)
        ttfts = [done_t[i] - t0 - trace[i][0] for i in range(len(trace))]
        return _report("batcher", wall, useful, ttfts)

    def _mk_engine(self, **over):
        from paddle_tpu.serving import ServingEngine
        a = self.args
        kw = dict(max_batch=a.max_batch, page_size=a.page_size,
                  max_prompt_len=a.max_prompt,
                  max_new_tokens_cap=self.mnt_cap,
                  prompt_buckets=self.buckets,
                  decode_block_size=a.decode_block,
                  prefix_cache=not a.no_prefix_cache,
                  prefill_chunk=a.prefill_chunk or None,
                  admission_window=a.admission_window,
                  cold_tier_bytes=getattr(a, "cold_tier", 0),
                  rewrites=getattr(a, "rewrites", False),
                  # None = env default; True = per-tick paged-KV
                  # invariant checking (violations raise inside the
                  # tick -> every handle errors -> main exits non-zero)
                  check_invariants=a.check_invariants or None)
        if a.speculative:
            kw.update(speculative="ngram", spec_k=a.spec_k)
        kw.update(over)
        return ServingEngine(self.params, self.cfg, **kw)

    def run_engine(self, trace):
        a = self.args
        # explicit flags must win over fleet-wide env defaults: --trace
        # with PADDLE_TPU_SERVING_TRACE=0 would export zero spans, and
        # --check-invariants with PADDLE_TPU_SERVING_SENTINEL=0 would
        # silently skip the sentinel gate it documents
        over = {}
        if a.trace:
            over["trace"] = True
        if a.check_invariants:
            over["recompile_sentinel"] = True
        eng = self._mk_engine(**over)
        if a.speculative:
            # the verify program's reachable widths depend on per-tick
            # draft counts — traffic cannot be trusted to cover them,
            # so compile the whole static inventory deterministically
            eng.warm_programs()
        # warmup (bench.warmup) already compiled every width-grid entry
        # and the fused block; from here any compile is a warmed-run
        # regression the sentinel must name
        eng.arm_sentinel()
        # --sample-frac: that fraction of requests submit with
        # temperature/top-p sampling (fused in-graph sampler, r16) —
        # deterministic per bench seed. Sampling is DATA to the tick,
        # so the armed sentinel doubles as the proof that sampled
        # traffic compiles NOTHING beyond the warmed inventory.
        sampled = (np.random.RandomState(a.seed).rand(len(trace))
                   < a.sample_frac)
        t0 = time.perf_counter()
        handles = []
        for i, (arrival, prompt, mnt) in enumerate(trace):
            now = time.perf_counter() - t0
            if now < arrival:
                time.sleep(arrival - now)
            kw = (dict(temperature=a.temperature, top_p=0.95, seed=i)
                  if sampled[i] else {})
            handles.append(eng.submit(prompt, mnt, **kw))
        outs = [h.result(timeout=600) for h in handles]
        wall = time.perf_counter() - t0
        snap = eng.stats()
        sentinel = (eng.sentinel.report() if eng.sentinel is not None
                    else None)
        if a.trace:
            eng.export_trace(a.trace)
        if a.check_invariants:
            # final standalone audit on top of the per-tick checks —
            # the post-drain state (page leaks) is only visible here
            violations = eng.audit()
            if violations:
                eng.close()
                raise SystemExit(
                    "serving_bench --check-invariants: "
                    + "; ".join(str(v) for v in violations))
            # --check-invariants also gates on a CLEAN recompile
            # sentinel: a post-warmup compile means the static
            # program-set proof and the runtime program set diverged
            if sentinel is not None and not sentinel["clean"]:
                eng.close()
                raise SystemExit(
                    "serving_bench --check-invariants: recompile "
                    f"sentinel tripped — "
                    f"{sentinel['post_warmup_compiles']} post-warmup "
                    f"XLA compile(s): "
                    + "; ".join(
                        f"during={e['during']} "
                        f"({e['compile_s'] * 1e3:.0f} ms)"
                        for e in sentinel["events"]
                        if e["phase"] == "post_warmup"))
        eng.close()
        useful = sum(len(o) for o in outs)
        ttfts = [h.ttft_s for h in handles]
        occ = snap["histograms"]["batch_occupancy"]["mean"]
        out = _report("engine", wall, useful, ttfts, occupancy=occ)
        c = snap["counters"]
        if a.shared_prefix and not a.no_prefix_cache:
            denom = max(c["prefix_hits"] + c["prefix_misses"], 1)
            out["prefix_hit_rate"] = round(c["prefix_hits"] / denom, 3)
            out["prefix_hit_tokens"] = int(c["prefix_hit_tokens"])
            out["prefix_pages_saved"] = int(c["prefix_pages_saved"])
            out["prefix_hit_tokens_per_sec"] = round(
                c["prefix_hit_tokens"] / wall, 1)
        st = snap["histograms"]["decode_stall_s"]
        if st["count"]:
            out["decode_stall_max_ms"] = round(st["max"] * 1e3, 1)
        if a.speculative:
            out["spec"] = {
                "spec_ticks": int(c["spec_ticks"]),
                "draft_tokens": int(c["draft_tokens"]),
                "draft_accepted": int(c["draft_accepted"]),
                "acceptance": round(
                    c["draft_accepted"] / max(c["draft_tokens"], 1), 3),
                "launches_per_token": round(
                    c["decode_steps"] / max(c["tokens_out"], 1), 3)}
        if sentinel is not None:
            out["sentinel"] = {
                "clean": sentinel["clean"],
                "post_warmup_compiles":
                    sentinel["post_warmup_compiles"]}
        if a.trace:
            out["trace"] = a.trace
        return out

    def run_trace_overhead(self, trace, reps=6):
        """Measured cost of span tracing (ISSUE r13 acceptance): the
        same unpaced flood replayed through engines that differ ONLY
        in ``trace=`` — interleaved traced/untraced repeats so
        co-tenant CPU drift hits both arms, best-of-``reps`` per arm,
        per-tick wall = replay wall / engine ticks. The slow test pins
        ``overhead_ratio`` ≤ 1.03 (docs/OBSERVABILITY.md). Invariant
        checking and the sentinel are OFF in both arms (their host
        work would mask the tracer's)."""
        kw = dict(check_invariants=False, recompile_sentinel=False)
        # pay every compile before either timed arm
        eng = self._mk_engine(trace=False, **kw)
        rng = np.random.RandomState(self.args.seed + 4)
        for b in self.buckets:
            p = rng.randint(0, 256, (b,)).astype(np.int32)
            eng.submit(p, self.mnt_cap).result(timeout=600)
        eng.close()

        def replay_once(traced):
            eng = self._mk_engine(trace=traced, **kw)
            t0 = time.perf_counter()
            handles = [eng.submit(prompt, mnt)
                       for _, prompt, mnt in trace]
            for h in handles:
                h.result(timeout=600)
            wall = time.perf_counter() - t0
            ticks = eng._tick_no
            spans = len(eng.tracer.spans()) + eng.tracer.dropped
            eng.close()
            return wall / max(ticks, 1), spans

        per_tick = {True: [], False: []}
        spans_traced = 0
        for _ in range(reps):
            for traced in (True, False):
                t, n = replay_once(traced)
                per_tick[traced].append(t)
                if traced:
                    spans_traced = max(spans_traced, n)
        t_on, t_off = min(per_tick[True]), min(per_tick[False])
        return {"mode": "trace_overhead",
                "tick_ms_traced": round(t_on * 1e3, 4),
                "tick_ms_untraced": round(t_off * 1e3, 4),
                "overhead_ratio": round(t_on / t_off, 4),
                "spans_recorded": int(spans_traced),
                "reps": reps,
                "within_3pct": bool(t_on / t_off <= 1.03)}

    # -------------------------------------------- prefix / chunk A-Bs ----
    def _ab_geometry(self):
        """The A-B runs at prompt lengths where prefill COST (not fixed
        dispatch overhead) dominates — at the default tiny trace shapes
        a whole prefill costs ~2 ms against ~1 ms of per-call overhead
        and both effects drown. 128+ tokens puts prefill well clear of
        the noise floor on the CPU mesh."""
        from paddle_tpu.serving.engine import _default_buckets
        a = self.args
        ab_len = max(a.max_prompt, 256)
        if a.shared_prefix:
            # honor the user's shared FRACTION (their --shared-prefix is
            # sized for the --max-prompt trace), rescaled to ab_len — a
            # 24-of-256-token share would measure nothing
            shared = int(ab_len * a.shared_prefix / a.max_prompt)
        else:
            shared = 7 * ab_len // 8
        shared = min(shared, ab_len - 4)
        chunk = a.prefill_chunk or max(
            (ab_len // 8) // a.page_size, 1) * a.page_size
        return ab_len, shared, chunk, _default_buckets(ab_len)

    def run_prefix_ab(self, trace=None):
        """Controlled cold-vs-warm TTFT on one shared prefix, plus the
        max decode stall an in-flight stream feels while a max-length
        prompt is admitted — chunked vs unchunked. Emitted as one JSON
        row; the ISSUE r8 acceptance numbers."""
        a = self.args
        rng = np.random.RandomState(a.seed + 1)
        ab_len, shared, chunk, buckets = self._ab_geometry()
        header = rng.randint(0, 256, (shared,)).astype(np.int32)
        tail = ab_len - shared

        def mk_prompt():
            return np.concatenate(
                [header, rng.randint(0, 256, (tail,)).astype(np.int32)])

        mnt = min(self.mnt_cap, 8)
        eng = self._mk_engine(max_prompt_len=ab_len,
                              prompt_buckets=buckets)
        # compile the COLD-path shapes outside the timed submissions,
        # with token values that cannot seed the measured prefix chain
        warm_p = (mk_prompt() + 1) % 256
        eng.submit(warm_p, mnt).result(timeout=600)
        # compile the WARM-path shape (suffix bucket x attached-page
        # count) too: a second throwaway-header request hits the first
        # one's chain with exactly the measured geometry
        eng.submit(((mk_prompt() + 1) % 256), mnt).result(timeout=600)
        # median of 3 cold/warm PAIRS, each on a fresh header (cold
        # prefill time swings 2x with co-tenant CPU load; one sample
        # proves nothing)
        colds, warms = [], []
        for i in range(3):
            header[:] = rng.randint(0, 256, (shared,))
            h_cold = eng.submit(mk_prompt(), mnt)
            h_cold.result(timeout=600)
            h_warm = eng.submit(mk_prompt(), mnt)
            h_warm.result(timeout=600)
            colds.append(h_cold.ttft_s)
            warms.append(h_warm.ttft_s)
        snap = eng.stats()
        eng.close()
        c = snap["counters"]
        cold_s = float(np.median(colds))
        warm_s = float(np.median(warms))

        out = {
            "mode": "prefix_ab",
            "shared_prefix_tokens": int(shared),
            "ttft_cold_ms": round(cold_s * 1e3, 1),
            "ttft_warm_ms": round(warm_s * 1e3, 1),
            "warm_ttft_speedup": round(cold_s / max(warm_s, 1e-9), 2),
            "prefix_hit_tokens": int(c["prefix_hit_tokens"]),
            "prefix_pages_saved": int(c["prefix_pages_saved"]),
            "stall_unchunked_ms": self._admission_stall(None),
            "stall_chunked_ms": self._admission_stall(chunk),
        }
        out["prefill_chunk_tokens"] = int(chunk)
        out["stall_reduced"] = (out["stall_chunked_ms"]
                                < out["stall_unchunked_ms"])
        return out

    def _admission_stall(self, chunk):
        """Max inter-token gap (ms) an in-flight VICTIM stream feels
        while a max-length intruder is admitted mid-stream — the
        latency a user actually observes. The ragged one-program
        tick folds prefill INTO the tick, so the between-tick gap is
        structurally ~0 and the felt latency is the tick's own
        duration: chunking bounds it by capping the per-tick prefill
        token budget (= the packed program width).
        Median of 3 fresh-engine repeats (any single gap swings with
        co-tenant CPU load)."""
        rng = np.random.RandomState(self.args.seed + 2)
        ab_len, _, _, buckets = self._ab_geometry()
        mnt = min(self.mnt_cap, 24)
        victim_p = rng.randint(0, 256, (2,)).astype(np.int32)
        intruder_p = rng.randint(0, 256, (ab_len,)).astype(np.int32)
        stalls = []
        for _ in range(3):
            eng = self._mk_engine(prefill_chunk=chunk,
                                  prefix_cache=False, max_batch=2,
                                  max_prompt_len=ab_len,
                                  prompt_buckets=buckets,
                                  decode_block_size=1)
            # compile victim decode + intruder prefill shapes (the jit
            # cache is shared across engines, so only the first repeat
            # can ever pay a compile)
            eng.submit(intruder_p, 2).result(timeout=600)
            h = eng.submit(victim_p, mnt)
            it = iter(h)
            next(it)
            next(it)                   # victim is mid-decode
            h2 = eng.submit(intruder_p, 2)
            gap, last = 0.0, time.perf_counter()
            for _tok in it:            # live timestamps: tick + stall
                now = time.perf_counter()
                gap = max(gap, now - last)
                last = now
            h.result(timeout=600)
            h2.result(timeout=600)
            eng.close()
            stalls.append(gap)
        return round(float(np.median(stalls)) * 1e3, 1)

    # ------------------------------------------------- speculative A/B ----
    def run_spec_ab(self, trace=None):
        """ISSUE r15 acceptance A/B: speculative vs plain greedy decode
        on a self-drafting repetitive workload, single stream (the
        motivating perf number — docs/PERF.md decode section). The
        MEASURED win is structural and CPU-visible: target-model
        LAUNCHES per emitted token (``decode_steps / tokens_out`` —
        each launch streams every weight once, so on-chip this ratio
        IS the bandwidth-ceiling uplift; the wall-time A/B rides the
        next TPU round). Both arms replay the same requests; spec
        outputs are asserted bitwise-equal to the plain arm's.

        The workload: tiled 4-token-pattern prompts (fixed seeds —
        greedy decode of the bench model settles into repetitive
        attractors the n-gram drafter locks onto; deterministic, so
        the slow test pins the measured ratio and acceptance)."""
        a = self.args
        k = a.spec_k
        mnt = a.spec_mnt
        pats = []
        for s in (2, 5, 2, 5):
            rng = np.random.RandomState(s)
            pats.append(np.tile(
                rng.randint(0, 256, (4,)).astype(np.int32), 6)[:24])
        kw = dict(max_batch=1, page_size=8, max_prompt_len=32,
                  max_new_tokens_cap=mnt, prompt_buckets=[32],
                  decode_block_size=1, prefix_cache=False,
                  prefill_chunk=None, admission_window=0,
                  check_invariants=a.check_invariants or False)

        def run(spec):
            over = dict(kw)
            if spec:
                over.update(speculative="ngram", spec_k=k)
            else:
                over.update(speculative=None)
            eng = self._mk_engine(**over)
            eng.warm_programs()
            # one throwaway request pays any remaining host-side cache
            # warmup outside the measured pass
            eng.submit((pats[0] + 1) % 256, 4).result(timeout=600)
            if a.check_invariants:
                eng.arm_sentinel()
            base = eng.stats()["counters"]
            t0 = time.perf_counter()
            outs = [eng.submit(p, mnt).result(timeout=600)
                    for p in pats]
            wall = time.perf_counter() - t0
            c = eng.stats()["counters"]
            sentinel = (eng.sentinel.report()
                        if a.check_invariants and eng.sentinel is not None
                        else None)
            if a.check_invariants:
                violations = eng.audit()
                if violations:
                    eng.close()
                    raise SystemExit("spec_ab --check-invariants: "
                                     + "; ".join(map(str, violations)))
            eng.close()
            launches = c["decode_steps"] - base["decode_steps"]
            tokens = c["tokens_out"] - base["tokens_out"]
            row = {"wall_s": round(wall, 3),
                   "tok_s": round(tokens / wall, 1),
                   "target_launches": int(launches),
                   "tokens": int(tokens),
                   "launches_per_token": round(launches / tokens, 4)}
            if spec:
                dt = c["draft_tokens"] - base["draft_tokens"]
                da = c["draft_accepted"] - base["draft_accepted"]
                row.update(
                    spec_ticks=int(c["spec_ticks"] - base["spec_ticks"]),
                    draft_tokens=int(dt), draft_accepted=int(da),
                    acceptance=round(da / max(dt, 1), 4))
            if sentinel is not None:
                row["sentinel_clean"] = bool(sentinel["clean"])
                if not sentinel["clean"]:
                    raise SystemExit(
                        "spec_ab --check-invariants: recompile sentinel "
                        f"tripped — {sentinel['post_warmup_compiles']} "
                        "post-warmup compile(s)")
            return row, outs

        plain, outs_p = run(False)
        spec, outs_s = run(True)
        exact = all(np.array_equal(x, y)
                    for x, y in zip(outs_p, outs_s))
        ratio = (plain["launches_per_token"]
                 / max(spec["launches_per_token"], 1e-9))
        return {
            "mode": "spec_ab", "spec_k": int(k),
            "requests": len(pats), "mnt": int(mnt),
            "plain": plain, "spec": spec,
            "acceptance": spec["acceptance"],
            "launch_reduction": round(ratio, 3),
            "bitwise_equal": bool(exact),
            # the ISSUE r15 acceptance bar, pinned by the slow test
            "meets_bar": bool(ratio >= 1.8
                              and spec["acceptance"] >= 0.7
                              and exact),
        }

    # ------------------------------------------------------- fleet mode ----
    def _session_trace(self):
        a = self.args
        header = a.fleet_header or max(2 * a.page_size, 16)
        header = min(header, a.max_prompt - 6)
        tail_lo, tail_hi = 4, max(5, a.max_prompt - header)
        mnts = [m for m in a.mnt_choices if m <= 16] or \
            [min(a.mnt_choices)]
        return build_session_trace(
            a.fleet_groups, a.fleet_group_size, a.rate, header,
            tail_lo, tail_hi, mnts, a.seed,
            arrival=parse_arrival(a.arrival)), header

    def _proc_spec(self):
        """WorkerSpec mirroring this bench's cfg + engine geometry —
        every spawned worker re-derives the SAME weights
        (params_seed=0 == the parent's PRNGKey(0)), so proc and
        in-process arms decode identical streams and A/B cleanly."""
        from paddle_tpu.serving.engine import _default_buckets
        from paddle_tpu.serving.fleet.proc import WorkerSpec
        a = self.args
        cfg_kw = dict(
            vocab_size=256, hidden_size=a.hidden,
            intermediate_size=2 * a.hidden,
            num_hidden_layers=a.layers,
            num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=a.max_prompt + max(a.mnt_choices),
            dtype="float32", use_flash_attention=False, remat=False)
        engine_kw = dict(max_batch=a.max_batch, page_size=a.page_size,
                         max_prompt_len=a.max_prompt,
                         max_new_tokens_cap=self.mnt_cap,
                         prompt_buckets=_default_buckets(a.max_prompt),
                         decode_block_size=a.decode_block,
                         prefix_cache=not a.no_prefix_cache,
                         prefill_chunk=a.prefill_chunk or None,
                         admission_window=a.admission_window,
                         check_invariants=a.check_invariants or None)
        # workers on the CPU, said out loud: this parent has touched
        # JAX, so on a chip host it HOLDS the chip and a worker that
        # needed it would fail or hang. --proc measures the process
        # boundary (IPC, GIL), never the device.
        return WorkerSpec("cpu", cfg_kw=cfg_kw, params_seed=0,
                          engine_kw=engine_kw, warm=True)

    def _fleet_run(self, n, policy, strace, *, paced=True,
                   sequential=True, kill_at=None, proc=False):
        """One fleet arm over ``[(arrival, group, prompt, mnt)]``.

        ``sequential=True`` replays each group as a MULTI-TURN session
        (one thread per session; turn k+1 submits only after turn k's
        reply completed — the traffic shape whose prefix re-hits the
        router must keep warm). ``sequential=False, paced=False`` is
        the flood: every request submitted up front, wall = pure
        service time (the tok/s scaling arm). ``kill_at=i`` runs the
        kill-one-replica scenario: after the i-th accepted submission
        the first serving replica is killed (drain-on-failure:
        admission stops, in-flight finish, queued hand back +
        re-dispatch) while submission continues — the zero-drop claim
        is checked on EVERY handle, the killed replica's accepted
        requests included."""
        from collections import defaultdict

        from paddle_tpu.serving.fleet import SERVING, ServingFleet
        if proc:
            from paddle_tpu.serving.fleet.proc import ProcServingFleet
            fleet = ProcServingFleet(self._proc_spec(), replicas=n,
                                     policy=policy)
        else:
            fleet = ServingFleet(lambda: self._mk_engine(), replicas=n,
                                 policy=policy)
        fleet.arm_sentinels()
        nreq = len(strace)
        handles = [None] * nreq
        state = {"submitted": 0, "kill_started": False, "kill": None}
        klock = threading.Lock()
        t0 = time.perf_counter()

        def _maybe_kill():
            with klock:
                if (kill_at is None or state["kill_started"]
                        or state["submitted"] < kill_at):
                    return
                state["kill_started"] = True
            victim = min(fleet.replicas(SERVING), key=lambda r: r.name)
            handed = fleet.kill(victim.name)
            with klock:
                state["kill"] = {"killed": victim.name,
                                 "at_request": int(kill_at),
                                 "handed_back": len(handed)}

        def _one(idx, arrival, prompt, mnt, wait_done):
            if paced:
                now = time.perf_counter() - t0
                if now < arrival:
                    time.sleep(arrival - now)
            try:
                handles[idx] = fleet.submit(prompt, mnt)
            except BaseException:
                return                  # counted as a drop below
            with klock:
                state["submitted"] += 1
            _maybe_kill()
            if wait_done:
                try:
                    handles[idx].result(timeout=600)
                except BaseException:
                    pass                # judged in the collect pass

        if sequential:
            sessions = defaultdict(list)
            for idx, (arr, g, prompt, mnt) in enumerate(strace):
                sessions[g].append((idx, arr, prompt, mnt))

            def _run_session(items):
                for idx, arr, prompt, mnt in items:
                    _one(idx, arr, prompt, mnt, wait_done=True)

            threads = [threading.Thread(target=_run_session,
                                        args=(items,), daemon=True)
                       for items in sessions.values()]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
        else:
            for idx, (arr, g, prompt, mnt) in enumerate(strace):
                _one(idx, arr, prompt, mnt, wait_done=False)
        drops, useful, ttfts = 0, 0, []
        for h in handles:
            if h is None:
                drops += 1
                continue
            try:
                out = h.result(timeout=600)
            except BaseException:
                drops += 1
                continue
            if h.status != "completed":
                drops += 1
                continue
            useful += len(out)
            if h.ttft_s is not None:
                ttfts.append(h.ttft_s)
        wall = time.perf_counter() - t0
        kill_info = state["kill"]
        snap = fleet.snapshot()
        sentinels = {rep.name: rep.sentinel_report()
                     for rep in fleet.replicas()}
        fleet.close()
        agg = {k: 0 for k in ("completed", "tokens_out", "prefix_hits",
                              "prefix_misses", "handed_back")}
        per_replica = {}
        for name, rh in snap["replicas"].items():
            c = rh.get("counters")
            if not c:
                continue
            for k in agg:
                agg[k] += c.get(k, 0)
            denom = max(c["prefix_hits"] + c["prefix_misses"], 1)
            per_replica[name] = {
                "state": rh["state"], "role": rh["role"],
                "completed": int(c["completed"]),
                "tokens_out": int(c["tokens_out"]),
                "prefix_hit_rate": round(c["prefix_hits"] / denom, 3)}
        denom = max(agg["prefix_hits"] + agg["prefix_misses"], 1)
        row = _report(f"fleet[{policy}]x{n}", wall, useful, ttfts)
        row.update(
            replicas=n, policy=policy,
            prefix_hit_rate=round(agg["prefix_hits"] / denom, 3),
            drops=int(drops), completed=int(agg["completed"]),
            per_replica=per_replica,
            router=dict(snap["router"]), generation=snap["generation"])
        if kill_info is not None:
            survivors_clean = all(
                s is None or s["clean"] for name, s in sentinels.items()
                if name != kill_info["killed"])
            kill_info.update(
                redispatched=snap["router"]["redispatched"],
                redispatch_failed=snap["router"]["redispatch_failed"],
                drops=int(drops),
                zero_drops=bool(drops == 0),
                sentinel_clean_survivors=bool(survivors_clean))
            row["kill"] = kill_info
        return row

    def run_fleet(self, trace):
        """ISSUE r18 acceptance mode (``--replicas N``). Arms, one
        JSON row:

        * **sessions** — the multi-session shared-prefix workload
          (multi-turn: turn k+1 follows turn k's reply) under
          prefix-affinity routing vs forced round-robin, plus a
          single-replica baseline. This is where the hit rate lives:
          affinity keeps each session's header chain on one replica
          (~1 cold prefill per session); round-robin scatters it cold.
        * **flood** — the plain mixed trace, all requests submitted up
          front, 1 vs N replicas: aggregate tok/s scaling
          (``speedup_vs_single``). On the shared-CPU mesh this
          measures in-process contention more than fleet capacity
          (docs/SERVING.md "Fleet" discusses the measured ceiling);
          the N-process multi-host number is the real target.
        * **kill** (unless ``--no-kill``) — kill-one-replica during
          the flood: drain-on-failure, queued hand-back +
          re-dispatch, submission continuing throughout; reports
          zero-drop status and survivor sentinel cleanliness.
        """
        a = self.args
        n = max(a.replicas, 2)
        proc = bool(getattr(a, "proc", False))
        strace, header = self._session_trace()
        single_s = self._fleet_run(1, "affinity", strace, proc=proc)
        aff = self._fleet_run(n, "affinity", strace, proc=proc)
        rr = self._fleet_run(n, "round_robin", strace, proc=proc)
        ftrace = [(arr, 0, p, mnt) for arr, p, mnt in trace]
        flood_1 = self._fleet_run(1, "affinity", ftrace, paced=False,
                                  sequential=False, proc=proc)
        flood_n = self._fleet_run(n, "affinity", ftrace, paced=False,
                                  sequential=False, proc=proc)
        out = {
            "mode": "fleet", "proc": proc,
            "worker_platform": "cpu" if proc else None, "replicas": n,
            "workload": {
                "groups": a.fleet_groups,
                "group_size": a.fleet_group_size,
                "header_tokens": int(header),
                "session_requests": len(strace),
                "flood_requests": len(ftrace),
                "arrival": a.arrival or f"seed:{a.seed} (legacy)"},
            "sessions": {"single": single_s, "affinity": aff,
                         "round_robin": rr},
            "flood": {"single": flood_1, "fleet": flood_n},
            "speedup_vs_single": round(
                flood_n["tok_s"] / max(flood_1["tok_s"], 1e-9), 2),
            "hit_rate_affinity": aff["prefix_hit_rate"],
            "hit_rate_round_robin": rr["prefix_hit_rate"],
            "affinity_beats_round_robin": bool(
                aff["prefix_hit_rate"] > rr["prefix_hit_rate"]),
            "hit_rate_target_met": bool(
                aff["prefix_hit_rate"] >= 0.90),
        }
        if not a.no_kill:
            kill_at = max(1, int(0.4 * len(ftrace)))
            kill_row = self._fleet_run(n, "affinity", ftrace,
                                       paced=False, sequential=False,
                                       kill_at=kill_at, proc=proc)
            out["kill"] = kill_row["kill"]
            out["kill"]["completed"] = kill_row["completed"]
        return out

    def run_migration_ab(self, trace=None):
        """Router-driven KV-migration A/B (ISSUE r17): the SAME
        multi-turn session workload (heavy-tailed lognormal arrivals
        by default) served by

        * **disaggregated_migrate** — a 3-proc fleet split 1 prefill
          + 2 decode with the automatic handoff policy ON: a
          session's header chain prefills on the prefill worker, the
          chain-completion event triggers a chunked transfer to the
          rendezvous-chosen decode worker, and the session's
          decode-heavy turns route there warm
          (``router.routed_migrated``);
        * **monolithic** — the same 3 workers untagged (no pools, no
          migration): the control arm.

        Reports per-arm tok/s + TTFT, follow-up-turn (turn >= 2) TTFT,
        migration/router counters, decode-side prefix hit rate, and
        each worker's max inter-tick stall from its flight recorder —
        the overlap evidence: chunked transfer must not open tick gaps
        beyond one chunk's gather/scatter."""
        from collections import defaultdict

        from paddle_tpu.serving.fleet.proc import ProcServingFleet
        a = self.args
        arrival = parse_arrival(a.arrival or f"lognormal:{a.seed}")
        header = a.fleet_header or max(2 * a.page_size, 16)
        header = min(header, a.max_prompt - 6)
        mnt_lo, mnt_hi = min(a.mnt_choices), max(a.mnt_choices)
        strace = build_session_trace(
            a.fleet_groups, a.fleet_group_size, a.rate, header,
            4, max(5, a.max_prompt - header), [mnt_lo], a.seed,
            arrival=arrival)
        # the handoff workload: each session opens with one expensive
        # header prefill (small mnt -> prefill-classed on the split
        # fleet), then decode-heavy follow-up turns (large mnt ->
        # decode-classed). prefill_len_ratio is computed from the
        # trace so the split is exact for any geometry: turn-0
        # requests satisfy plen >= r*mnt_lo, follow-ups plen < r*mnt_hi
        turns = defaultdict(int)
        shaped = []
        for t, g, p, _ in strace:
            k = turns[g]
            turns[g] += 1
            shaped.append((t, g, p, mnt_lo if k == 0 else mnt_hi))
        strace = shaped
        plens = [len(p) for _, _, p, _ in strace]
        ratio = (max(plens) + 1) / mnt_hi
        if ratio > min(plens) / mnt_lo:
            ratio = 1.0             # degenerate mnt choices: best effort

        def run(roles, label):
            fleet = ProcServingFleet(
                self._proc_spec(), replicas=3, roles=roles,
                prefill_len_ratio=ratio)
            sessions = defaultdict(list)
            for idx, (arr, g, prompt, mnt) in enumerate(strace):
                sessions[g].append((idx, arr, prompt, mnt))
            results = [None] * len(strace)
            t0 = time.perf_counter()

            def _session(items):
                for turn, (idx, arr, prompt, mnt) in enumerate(items):
                    now = time.perf_counter() - t0
                    if now < arr:
                        time.sleep(arr - now)
                    try:
                        h = fleet.submit(prompt, mnt)
                        out = h.result(timeout=600)
                    except BaseException:
                        continue
                    results[idx] = (turn, h.ttft_s, len(out))
            ths = [threading.Thread(target=_session, args=(items,),
                                    daemon=True)
                   for items in sessions.values()]
            for th in ths:
                th.start()
            for th in ths:
                th.join()
            wall = time.perf_counter() - t0
            # max inter-tick stall per worker: gap between one tick's
            # end (t_mono_s + dur_s) and the next tick's start — what
            # a chunked transfer must keep bounded
            stalls = {}
            for rep in fleet.replicas():
                try:
                    ticks = rep.flight_ticks()
                except Exception:
                    continue
                gaps = [ticks[i + 1]["t_mono_s"]
                        - (ticks[i]["t_mono_s"] + ticks[i]["dur_s"])
                        for i in range(len(ticks) - 1)]
                stalls[rep.name] = round(max(gaps), 4) if gaps else 0.0
            snap = fleet.snapshot()
            fleet.close()
            done = [r for r in results if r is not None]
            useful = sum(r[2] for r in done)
            ttfts = [r[1] for r in done if r[1] is not None]
            follow = [r[1] for r in done
                      if r[0] >= 1 and r[1] is not None]
            decode_hits = decode_total = 0
            for name, rh in snap["replicas"].items():
                c = rh.get("counters")
                if c and rh.get("role") == "decode":
                    decode_hits += c.get("prefix_hits", 0)
                    decode_total += (c.get("prefix_hits", 0)
                                     + c.get("prefix_misses", 0))
            row = _report(f"migration[{label}]", wall, useful, ttfts)
            row.update(
                arm=label, drops=int(len(strace) - len(done)),
                followup_ttft_p50_ms=round(_pctl(follow, 50) * 1e3, 1),
                followup_ttft_p99_ms=round(_pctl(follow, 99) * 1e3, 1),
                migrations=snap["fleet"]["migrations"],
                migration_failed=snap["fleet"]["migration_failed"],
                routed_migrated=snap["router"].get("routed_migrated", 0),
                decode_prefix_hit_rate=round(
                    decode_hits / max(decode_total, 1), 3),
                max_tick_stall_s=stalls)
            return row

        dis = run(["prefill", "decode", "decode"],
                  "disaggregated_migrate")
        mono = run(None, "monolithic")
        return {
            "mode": "migration_ab",
            "workload": {"groups": a.fleet_groups,
                         "group_size": a.fleet_group_size,
                         "header_tokens": int(header),
                         "requests": len(strace),
                         "arrival": a.arrival or f"lognormal:{a.seed}"},
            "disaggregated_migrate": dis, "monolithic": mono,
            "migrations_happened": bool(dis["migrations"] > 0),
            "zero_drops_both": bool(dis["drops"] == 0
                                    and mono["drops"] == 0),
        }

    def run_cold_tier(self, trace=None):
        """Host-memory cold-tier A/B (ISSUE r17): one engine, device
        page budget deliberately too small for the working set of
        session header chains, revisited over two rounds:

        * **cold_tier on** — evicted chains spill to host RAM; a
          round-2 revisit re-adopts the pages (``cold_hits``) instead
          of recomputing prefill;
        * **cold_tier off** — the control: a round-2 revisit
          re-prefills from scratch.

        Outputs must be BITWISE identical between arms (the cold tier
        stores the bytes the device computed); the win is round-2
        TTFT. Reports per-arm revisit TTFT, cold counters and the
        cold-tier gauges."""
        a = self.args
        groups = max(4, a.fleet_groups)
        header = a.fleet_header or max(2 * a.page_size, 16)
        header = min(header, a.max_prompt - 6)
        mnt = min(m for m in a.mnt_choices)
        rng = np.random.RandomState(a.seed)
        headers = [rng.randint(0, 256, (header,)).astype(np.int32)
                   for _ in range(groups)]
        tails = [rng.randint(0, 256, (4,)).astype(np.int32)
                 for _ in range(groups)]
        prompts = [np.concatenate([h, t])
                   for h, t in zip(headers, tails)]
        # pool sized for ONE in-flight request + ~1 cached chain: by
        # the time a session's header is revisited its chain has been
        # evicted (admission matches the trie BEFORE evicting, so a
        # roomier pool would let revisits stay warm and the control
        # arm would never re-prefill)
        pages_per_slot = -(-(_bucket(a.max_prompt, self.buckets)
                             + self.mnt_cap - 1) // a.page_size)
        chain_pages = header // a.page_size
        total_pages = pages_per_slot + chain_pages + 2
        cold_bytes = int(getattr(a, "cold_tier", 0)) or (64 << 20)
        wrng = np.random.RandomState(a.seed + 17)
        warm_prompts = [wrng.randint(0, 256, (header + 4,))
                        .astype(np.int32) for _ in range(3)]

        def run(tier_bytes):
            eng = self._mk_engine(max_batch=1,
                                  total_pages=total_pages,
                                  cold_tier_bytes=tier_bytes)
            # unmeasured warm lap: compile prefill/decode (+ the
            # rewarm gather/scatter when the tier is on — submit A,
            # evict it via B, revisit A) so the measured revisits
            # compare steady-state costs, not XLA compiles
            for p in (*warm_prompts, warm_prompts[0]):
                eng.submit(p, mnt).result(timeout=600)
            c0 = eng.snapshot()["counters"]
            outs, ttfts = {}, []
            t0 = time.perf_counter()
            for rnd in range(2):
                for g in range(groups):
                    h = eng.submit(prompts[g], mnt)
                    outs[(rnd, g)] = list(h.result(timeout=600))
                    if rnd == 1 and h.ttft_s is not None:
                        ttfts.append(h.ttft_s)
            wall = time.perf_counter() - t0
            snap = eng.snapshot()
            eng.close()
            c = {k: int(v - c0.get(k, 0))
                 for k, v in snap["counters"].items()}
            row = {
                "wall_s": round(wall, 3),
                "revisit_ttft_p50_ms": round(
                    _pctl(ttfts, 50) * 1e3, 2),
                "revisit_ttft_mean_ms": round(
                    float(np.mean(ttfts)) * 1e3, 2),
                "cold_hits": c.get("cold_hits", 0),
                "cold_hit_pages": c.get("cold_hit_pages", 0),
                "cold_spills": c.get("cold_spills", 0),
                "prefix_hits": c.get("prefix_hits", 0),
                "cold_tier": snap["gauges"].get("cold_tier"),
            }
            hist = snap.get("histograms", {}).get("cold_adopt_s")
            if hist:
                row["cold_adopt_s"] = hist
            return row, outs

        off, outs_off = run(0)
        on, outs_on = run(cold_bytes)
        bitwise = all(outs_on[k] == outs_off[k] for k in outs_on)
        return {
            "mode": "cold_tier",
            "workload": {"groups": groups, "header_tokens": int(header),
                         "mnt": int(mnt), "rounds": 2,
                         "total_pages": int(total_pages),
                         "cold_tier_bytes": int(cold_bytes)},
            "cold_tier_on": on, "cold_tier_off": off,
            "bitwise_equal": bool(bitwise),
            "rehit_beats_cold_prefill": bool(
                on["revisit_ttft_p50_ms"] < off["revisit_ttft_p50_ms"]),
        }

    def warmup(self, modes):
        """Compile the selected modes' program shapes outside the timed
        runs (only theirs — the full grid is seconds of XLA compiles)."""
        warm = [(0.0, np.arange(1, 1 + ln, dtype=np.int32) % 200, mnt)
                for ln in self.buckets for mnt in self.args.mnt_choices]
        if "sequential" in modes:
            self.run_sequential(warm)
        if "batcher" in modes:
            # warm the (batch-bucket, seq-bucket) grid at the cap
            jnp = self.jnp
            bb = 1
            while True:
                for tb in self.buckets:
                    padded = np.ones((bb, tb), np.int32)
                    lens = np.full((bb,), tb, np.int32)
                    np.asarray(self._gen(self.params, jnp.asarray(padded),
                                         jnp.asarray(lens),
                                         max_new_tokens=self.mnt_cap))
                if bb >= self.args.max_batch:
                    break
                bb = min(bb * 2, self.args.max_batch)
        if "engine" in modes:
            # one request per prompt bucket at the mnt cap, submitted
            # SEQUENTIALLY so each runs alone: covers every mixed tick
            # width AND the pure-decode fused block (an mnt below the
            # fused tail never reaches pure decode, leaving the block
            # program to compile inside the measured run). Distinct
            # random prompts — shared prefixes would attach and shrink
            # the span below the width being warmed.
            rng = np.random.RandomState(self.args.seed + 3)
            eng = self._mk_engine()
            for b in self.buckets:
                p = rng.randint(0, 256, (b,)).astype(np.int32)
                eng.submit(p, self.mnt_cap).result(timeout=600)
            eng.close()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=96)
    ap.add_argument("--rate", type=float, default=500.0,
                    help="arrival rate, requests/sec (keep the system "
                         "LOADED: an underloaded trace measures the "
                         "arrival window, not serving capacity)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--max-prompt", type=int, default=32)
    ap.add_argument("--mnt-choices", type=int, nargs="+",
                    default=[4, 8, 16, 48])
    ap.add_argument("--batch-delay-ms", type=float, default=4.0)
    ap.add_argument("--decode-block", type=int, default=4,
                    help="fused greedy decode steps per engine tick")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend one fixed N-token header to every "
                         "prompt (the common-system-prompt workload); "
                         "also enables the prefix_ab mode's default "
                         "prefix length and the engine row's "
                         "prefix-cache counters")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="engine prefill chunk tokens (multiple of "
                         "--page-size; 0 = whole-suffix prefill)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable cross-request KV prefix reuse")
    ap.add_argument("--admission-window", type=int, default=0,
                    help="queued requests allowed to overtake a "
                         "non-fitting head (0 = strict FIFO)")
    ap.add_argument("--sample-frac", type=float, default=0.0,
                    help="fraction of engine-mode requests submitted "
                         "with temperature/top-p sampling (r16 fused "
                         "sampler: rides the same programs — the "
                         "sentinel gate proves it)")
    ap.add_argument("--temperature", type=float, default=0.8,
                    help="temperature for --sample-frac requests")
    ap.add_argument("--speculative", action="store_true",
                    help="serve the engine mode with self-drafting "
                         "(n-gram) speculative decoding")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculative draft-length cap (the one "
                         "static knob; per-tick k is adaptive)")
    ap.add_argument("--spec-mnt", type=int, default=160,
                    help="spec_ab mode: tokens generated per request "
                         "(long enough that the repetitive attractor "
                         "dominates)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serving-fleet size for the fleet mode; "
                         "passing N>1 selects the fleet mode when "
                         "--modes was not given")
    ap.add_argument("--arrival", default=None,
                    help="seeded replayable arrival schedule. "
                         "'seed:K': gaps/lengths/mnt from "
                         "RandomState(K), independent of --seed "
                         "(content) — the same spec replays the "
                         "identical schedule. 'lognormal:K[:sigma]' / "
                         "'pareto:K[:alpha]': same replay contract "
                         "with HEAVY-TAILED gaps + prompt/output "
                         "lengths (defaults sigma=1.5, alpha=1.5)")
    ap.add_argument("--proc", action="store_true",
                    help="fleet mode: run replicas as worker "
                         "PROCESSES (serving.fleet.proc) instead of "
                         "in-process engines — same JSON schema, so "
                         "the two are directly A/B-able. Workers run "
                         "on the CPU (this parent holds whatever chip "
                         "the host has): --proc prices the process "
                         "boundary, not the device")
    ap.add_argument("--fleet-groups", type=int, default=8,
                    help="fleet mode: distinct shared-prefix sessions "
                         "(each gets its own system-prompt header)")
    ap.add_argument("--fleet-group-size", type=int, default=12,
                    help="fleet mode: requests per session")
    ap.add_argument("--fleet-header", type=int, default=0,
                    help="fleet mode: session header tokens "
                         "(0 = max(2 pages, 16))")
    ap.add_argument("--no-kill", action="store_true",
                    help="fleet mode: skip the kill-one-replica "
                         "scenario")
    ap.add_argument("--rewrites", action="store_true",
                    help="route engine step functions through the "
                         "verified rewrite passes (decode-tail fuse + "
                         "fused rmsnorm); greedy outputs are pinned "
                         "bitwise-identical, so --check-invariants "
                         "and the recompile sentinel apply unchanged")
    ap.add_argument("--check-invariants", action="store_true",
                    help="run the paged-KV invariant checker "
                         "(analysis/kv_invariants.py) after every "
                         "engine tick + a final audit, require a "
                         "clean recompile sentinel (any post-warmup "
                         "XLA compile exits non-zero), AND enable the "
                         "runtime LockTracer (serving/locktrace.py): "
                         "an observed lock-order inversion also exits "
                         "non-zero; the acquisition graph + wait/hold "
                         "stats land in the results as `lock_trace`")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="export the engine run's span timeline as "
                         "Perfetto-loadable Chrome-trace JSON (one "
                         "track per engine phase + per slot)")
    ap.add_argument("--cold-tier", type=int, default=0,
                    help="host-memory cold-chain tier byte budget "
                         "(engine mode: passed straight to the "
                         "engine's cold_tier_bytes=; cold_tier mode: "
                         "the ON arm's budget, 0 = 64 MiB default)")
    ap.add_argument("--modes", nargs="+", default=None,
                    help="any of: sequential batcher engine prefix_ab "
                         "trace_overhead spec_ab fleet "
                         "migration_ab cold_tier "
                         "(default: sequential batcher engine, or "
                         "fleet when --replicas > 1)")
    args = ap.parse_args(argv)
    if args.modes is None:
        args.modes = (["fleet"] if args.replicas > 1
                      else ["sequential", "batcher", "engine"])
    if (args.shared_prefix and args.shared_prefix >= args.max_prompt
            and any(m != "prefix_ab" for m in args.modes)):
        # trace prompts are capped at --max-prompt; prefix_ab picks its
        # own (longer) geometry and clamps the share itself
        ap.error(f"--shared-prefix ({args.shared_prefix}) must be < "
                 f"--max-prompt ({args.max_prompt}): every prompt needs "
                 f"at least one non-shared token")
    if args.prefill_chunk and args.prefill_chunk % args.page_size:
        ap.error(f"--prefill-chunk ({args.prefill_chunk}) must be a "
                 f"multiple of --page-size ({args.page_size})")

    lt_tracer = None
    if args.check_invariants:
        # --check-invariants also turns on the runtime lock tracer
        # (analysis/concurrency.py's dynamic half): every serving lock
        # built from here on records acquisition order, and an
        # observed order inversion — two locks taken in both orders,
        # i.e. a latent deadlock the static cycle check may not see
        # across dynamic call paths — fails the bench after the modes
        # run. Enable BEFORE Bench construction: wrapping is decided
        # at lock construction time.
        from paddle_tpu.serving import locktrace
        lt_tracer = locktrace.enable()

    bench = Bench(args)
    trace = build_trace(args.requests, args.rate, args.max_prompt,
                        args.mnt_choices, args.seed,
                        shared_prefix=args.shared_prefix,
                        arrival=parse_arrival(args.arrival))
    bench.warmup([m for m in args.modes
                  if m not in ("prefix_ab", "spec_ab", "fleet",
                               "migration_ab", "cold_tier")])
    results = {}
    for mode in args.modes:
        results[mode] = getattr(bench, f"run_{mode}")(list(trace))
        print(json.dumps(results[mode]), flush=True)
    if "engine" in results and "batcher" in results:
        verdict = {
            "engine_beats_batcher_tok_s":
                results["engine"]["tok_s"] > results["batcher"]["tok_s"],
            "engine_beats_batcher_ttft_p99":
                results["engine"]["ttft_p99_ms"]
                < results["batcher"]["ttft_p99_ms"],
        }
        print(json.dumps(verdict), flush=True)
        results["verdict"] = verdict
    if lt_tracer is not None:
        rep = lt_tracer.report()
        results["lock_trace"] = rep
        print(json.dumps({"lock_trace": {
            "edges": rep["edges"], "inversions": rep["inversions"],
            "host_sync_held": rep["host_sync_held"]}}), flush=True)
        if rep["inversions"]:
            raise SystemExit(
                "serving_bench --check-invariants: lock-order "
                f"inversion(s) observed at runtime: {rep['inversions']}")
    return results


if __name__ == "__main__":
    from paddle_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(sys.argv[1:])
