"""Continuous-batching generation engine over the paged KV cache.

Reference capability: the inference product's serving stack —
AnalysisPredictor wrapped by frontends that coalesce MANY concurrent
generation streams per device over block_multihead_attention's paged
cache. ``inference.DynamicBatcher`` batches whole requests (a long
generation holds its batch slot until EOS while short requests queue
behind it); this engine batches per STEP:

  - requests are admitted mid-flight into free slots of a fixed
    ``max_batch``-wide decode batch (admission is page-budget-aware —
    see serving/scheduler.py);
  - admission first attaches the longest PREFIX-CACHED page-aligned
    span of the prompt EXACTLY — any page count (serving/
    prefix_cache.py — refcounted KV page reuse across requests:
    system prompts and few-shot headers are computed once) — and only
    the uncached suffix is ever computed;
  - every engine tick is ONE jitted ragged program (``models/
    serving_tick.py`` around the model family's layer walk, over the
    ragged-paged-attention Pallas kernel): each live slot's decode
    token AND up to a per-tick token budget of pending prompt spans
    run in the same launch, with sequence geometry (span lengths,
    cache lengths, page tables) carried as device arrays. Prompt length, chunk position and
    attached-prefix size are DATA, not compile shapes, and the
    recompile-hazard pass proves the whole engine compiles 1-2
    programs per packed width;
  - ``prefill_chunk=N`` caps the per-tick prefill token budget (its
    scheduling role — bounded inter-token stall for in-flight streams
    while long prompts are absorbed); it no longer affects what
    compiles;
  - sequences retire at EOS / max_new_tokens / deadline / cancel and
    their pages return to the pool the same tick, so the next queued
    request starts without waiting for the rest of the batch;
  - the engine keeps ONE TICK IN FLIGHT ahead of the host: a slot's
    current token lives on the device (``_cur_tok_d``, beside the
    cache), so tick N+1 is built from the PREDICTED state and launched
    before tick N's tokens are read back, and the device never waits
    for the engine thread (see ``_loop``; docs/SERVING.md "One tick in
    flight").

Correctness bar (tests/test_serving.py): with greedy sampling every
request's tokens equal a standalone ``generate()`` run token-for-token,
regardless of what else shares the batch — slots are mathematically
independent (row-wise model math + per-slot page tables).

Tokens stream to callers through per-request iterators
(``RequestHandle``); ``close()`` drains gracefully. Counters and
latency histograms live in serving/metrics.py; every span of the tick
path is also a ``jax.profiler.TraceAnnotation`` (observability/
tracer.py), so ticks and their phases land in device traces.

Runtime observability (ISSUE r13, paddle_tpu/observability/): every
tick records engine-phase and per-slot lifecycle spans into a bounded
ring (``export_trace(path)`` -> Perfetto), the flight recorder keeps
the last N ticks + state snapshots and dumps a JSON postmortem
automatically when a ``KVInvariantError`` or engine-loop crash kills
the worker, and the recompile sentinel turns any post-warmup XLA
compile into a named WARN metric + ``RecompileWarning`` — the runtime
alarm form of the static ≤2-programs-per-bucket recompile proof. See
docs/OBSERVABILITY.md.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional

import numpy as np

from collections import deque

import itertools

from ..inference.paged_kv import PagePool, defrag_pools
from ..observability import (FlightRecorder, RecompileSentinel, SpanTracer,
                             in_setup_span, setup_report, setup_span)
from .locktrace import get_tracer, host_sync, wrap_lock
from .metrics import ServingMetrics
from .prefix_cache import ColdTier, PrefixCache, _fp_extend
from .scheduler import (CANCELLED, COMPLETED, REJECTED, TIMED_OUT,
                        Request, RequestHandle, Scheduler)

__all__ = ["ServingEngine"]


def _env_flag(name: str, default: bool) -> bool:
    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw.strip().lower() in ("1", "true", "yes", "on")


def _family(mod):
    """The module's ``SERVING`` record: the one place the engine learns
    a family (``models/layer_walk.py: ServingFamily``)."""
    try:
        return mod.SERVING
    except AttributeError:
        raise TypeError(
            f"{mod!r} is no serving family: it exposes no SERVING (a "
            f"models.layer_walk.ServingFamily)") from None


from collections import OrderedDict

# LRU-bounded: each entry pins a config + three jitted fns (and their
# XLA executables); a per-tenant-config service must not grow this
# forever. 8 distinct live (model, config, impl) triples is plenty for
# blue/green reuse.
_JIT_CACHE: "OrderedDict" = OrderedDict()
_JIT_CACHE_MAX = 8


def _jit_step_fns(mod, cfg, attn_impl: str, rewrites: bool = False):
    """Shared jitted tick/block per (model, config, impl): several
    engines over one config (tests, blue/green restarts) reuse the same
    jit objects, so XLA's executable cache carries across instances.

    Exactly TWO step functions serve everything, one over each of the
    shared tick's two entry points (``models/serving_tick.py``, around
    the walk of ``mod.SERVING``, whose ``init_pages`` built the cache
    they take): ``serving_tick`` — any mix of decode tokens and prompt
    spans as one ragged program (one compile per packed width; widths
    come from the engine's small width grid — see
    ``ServingEngine._w_grid``) — and ``serving_tick_block`` — the fused
    multi-step decode path.

    ``rewrites=True`` routes every step function through the analysis
    subsystem's verified rewrite passes (analysis/rewrite.py) before
    jit: each jit trace pattern-matches the step's jaxpr and substitutes
    the registered fused kernels (compile-time cost only; the exactness
    pin in tests/test_rewrite.py proves greedy outputs stay
    byte-identical to the unrewritten engine)."""
    import jax
    from ..models import serving_tick as shared
    family = _family(mod)
    # content key (repr of a dataclass config is deterministic and
    # covers every field): benches and tests that rebuild an identical
    # config per run — the common restart shape — reuse the traced jit
    # objects instead of paying a full re-trace + lowering per engine
    key = (mod.__name__, type(cfg).__name__, repr(cfg), attn_impl,
           bool(rewrites))
    hit = _JIT_CACHE.get(key)
    if hit is not None:
        _JIT_CACHE.move_to_end(key)
        return hit[1:]
    if rewrites:
        from ..analysis.rewrite import rewrite_callable as _rw
    else:
        def _rw(fn):
            return fn
    # the step functions take the model's whole cache pytree (the two
    # page pools, and whatever else its layer kinds keep) as ONE donated
    # argument: the engine rebinds the returned cache immediately, and
    # without donation every tick pays a full pool copy — measured 2-3x the whole step time on the CPU mesh at
    # bench shapes
    # named wrappers, not bare partials: the function's name is the
    # HLO module's (``jit_serving_tick``), which is how a profiler
    # trace tells the tick programs from everything else on the chip
    def serving_tick(params, tokens, meta, cache, tq=1, decode_tail=0,
                     spec_k=0):
        return shared.serving_tick(params, tokens, meta, cache, cfg, family,
                                   tq=tq, decode_tail=decode_tail,
                                   spec_k=spec_k, attn_impl=attn_impl)

    def serving_tick_block(params, tok, lengths, tables, cache, num_steps,
                           sampling=None):
        return shared.serving_tick_block(params, tok, lengths, tables, cache,
                                         cfg, family, num_steps,
                                         attn_impl=attn_impl,
                                         sampling=sampling)

    tick = jax.jit(_rw(serving_tick), donate_argnums=(3,),
                   static_argnames=("tq", "decode_tail", "spec_k"))
    blk = jax.jit(_rw(serving_tick_block), donate_argnums=(4,),
                  static_argnames=("num_steps",))
    _JIT_CACHE[key] = (cfg, tick, blk)
    if len(_JIT_CACHE) > _JIT_CACHE_MAX:
        _JIT_CACHE.popitem(last=False)
    return tick, blk


class _Tick:
    """One dispatched tick whose tokens the host has not read back: the
    device handles, and per row the ``(slot, req)`` it was launched for.
    Completion emits a row only if its slot still holds that request."""

    __slots__ = ("no", "outs", "counts", "live", "spans", "drafts", "tail",
                 "admitted", "ahead", "t0", "m0")

    def __init__(self, no, live, spans, drafts, tail, ahead):
        self.no = no                # the tick's number
        self.outs = ()              # (toks_d,) or (toks_d, accept_d)
        self.counts = None          # the family's counters, [n] i32
        self.live = live            # decode rows [(slot, req)]
        self.spans = spans          # [(slot, req, start, take)]
        self.drafts = drafts        # {slot: draft tokens} (verify tick)
        self.tail = tail            # fused steps after the first
        self.admitted = 0           # requests its iteration admitted
        self.ahead = ahead          # launched while another was in flight
        self.t0 = time.perf_counter()
        self.m0 = time.monotonic()


def _default_buckets(max_prompt_len: int):
    buckets, b = [], 8
    while b < max_prompt_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_prompt_len)
    return sorted(set(buckets))


class ServingEngine:
    """Continuous-batching serving engine.

        eng = ServingEngine(params, cfg, max_batch=8, page_size=8,
                            max_prompt_len=32, max_new_tokens_cap=32)
        h = eng.submit([1, 2, 3], max_new_tokens=16, eos_token_id=7)
        for tok in h:          # streams as decoded
            ...
        toks = h.result()      # or block for the full continuation
        eng.close()            # graceful drain

    params/cfg: a Llama- or Qwen2Moe-family params pytree + config
    (model resolved from the config type; pass ``model=`` to override).
    max_batch: decode slots (the one compiled decode shape).
    page_size/total_pages: the shared KV pool geometry. The default
    total_pages funds every slot's worst case; pass something smaller to
    get real admission backpressure.
    max_prompt_len / prompt_buckets: prompts are right-padded to the
    smallest bucket (one prefill compile per bucket).
    max_new_tokens_cap: per-request max_new_tokens ceiling (sizes the
    fixed page-table width).
    quantization: None/"none" (serve the params as given) or "int8" —
    weight-only int8 PTQ applied at engine construction
    (quantization/decode.py quantize_for_decode: per-channel int8
    projections + f32 scales, halving decode's weight stream) with NO
    caller-side changes; already-quantized params pass through. Greedy
    tokens then match ``generate()`` run on the SAME quantized params
    (weight-only quant is a params transform, not a decode-path fork).
    prefix_cache: True (default) keeps full prompt-KV pages registered
    across requests (refcounted; LRU-evicted under page pressure) so a
    shared prompt prefix is prefilled once — and attached EXACTLY: any
    cached page count (prefix size is data to the ragged tick, not a
    compile shape). Greedy outputs stay
    byte-identical to ``generate()`` whether a prefix was cached,
    partially cached, or cold (tests/test_prefix_cache.py).
    prefill_chunk: per-tick prefill token budget. None (default)
    absorbs a whole suffix in its admission tick; N caps per-tick
    prefill work at N prompt tokens, interleaved with decode in the
    SAME ragged program (bounded inter-token stall for in-flight
    streams while long prompts are absorbed). Purely a scheduling
    knob — any positive value compiles the same two programs.
    admission_window: 0 (default) = strict-FIFO admission; N lets up to
    N queued requests overtake a head whose page budget does not fit.
    check_invariants: True runs the paged-KV invariant checker
    (analysis/kv_invariants.py) after every tick and around every
    defrag — the race-detector-style debug mode: any page-ownership /
    refcount / dead-slot-row violation raises ``KVInvariantError``
    instead of silently cross-contaminating KV. Default comes from the
    ``PADDLE_TPU_SERVING_CHECK_INVARIANTS`` env var (the test suite
    turns it on); cost is host-side only (<10% of a CPU-mesh tick,
    measured in docs/ANALYSIS.md).
    rewrites: True routes every step function through the verified
    jaxpr rewrite passes (analysis/rewrite.py — fused-kernel
    substitution at jit-trace time, compile-time cost only). Greedy
    outputs remain byte-identical to the unrewritten engine
    (tests/test_rewrite.py exactness pin).
    trace: span tracing (observability/tracer.py): per-tick engine
    phase spans (admission / prefill+decode tick / defrag / invariant
    audit) and per-request lifecycle spans (queue -> prefill chunks ->
    decode ticks -> retire) on one track per slot, ring-bounded,
    exportable as Perfetto JSON via ``export_trace(path)``. Default
    from ``PADDLE_TPU_SERVING_TRACE`` (on when unset); measured
    overhead ≤3% of tick wall (docs/OBSERVABILITY.md), so it stays on
    in production.
    flight_ticks / flight_dir: the flight recorder keeps the last N
    tick records + state snapshots; on ``KVInvariantError`` or any
    unhandled engine-loop exception a JSON postmortem (recent ticks,
    span window, metrics, scheduler/pool/prefix state, the violation
    list, expected program inventory) is written under ``flight_dir``
    (default ``PADDLE_TPU_FLIGHT_DIR`` or ``<tmp>/paddle_tpu_flight``)
    and the path lands in ``self.postmortem_path``.
    recompile_sentinel: watch ``jax.monitoring`` compile events at
    runtime (observability/sentinel.py): after ``arm_sentinel()``
    declares warmup done, ANY XLA compile raises a named
    ``RecompileWarning``, increments the labeled ``recompiles`` metric
    and records a sentinel span — the runtime alarm form of the static
    ≤2-programs-per-bucket proof. Default from
    ``PADDLE_TPU_SERVING_SENTINEL`` (on when unset).
    speculative: None (default, off); True/"ngram" = self-drafting
    speculative decoding (serving/speculative.py NGramDrafter — prompt
    lookup over the request's own history, zero model cost); or any
    object with ``propose(history, k) -> tokens`` / bare callable (the
    pluggable draft-model hook). Each tick, every live slot — greedy
    AND sampling since r16 — may submit its current token plus up to
    ``spec_k`` draft tokens as an ordinary ragged span of the
    one-program tick; the target model verifies the whole span in ONE
    launch (in-graph longest-prefix acceptance against its own token
    pick: argmax for greedy slots, the fused sampler's draw for
    sampling ones) and the slot emits ``1 + accepted`` tokens.
    Outputs stay bitwise-equal to the non-speculative engine — and,
    for greedy requests, to ``generate()`` — whatever the drafter
    proposes (tests/test_speculative.py pins every cache state);
    rejected draft KV needs no rollback — the stale rows sit past the
    slot's length, masked until real tokens overwrite them (the same
    trash-row discipline as retiring overruns). Scheduling is
    acceptance-aware: a per-request acceptance EWMA adapts each slot's
    draft budget, degrading low-acceptance slots to plain one-token
    decode (with periodic probes). Speculation replaces the fused
    greedy tail on mixed ticks (``decode_tail`` and ``spec_k`` are
    mutually exclusive programs); pure-decode ticks with no drafts
    still run the fused block, so the program set stays ≤2 per width
    bucket — statically proven via the spec-aware
    ``enumerate_tick_programs``.
    spec_k: draft-length CAP (static — the one extra compile knob; a
    slot's actual per-tick draft count is device data).
    cold_tier_bytes: 0 (default, off) or a host-RAM byte budget for
    the COLD TIER (prefix_cache.ColdTier): refcount-0 chains evicted
    under page pressure page out to host memory (keyed by the same
    chain fingerprints migration and the fleet router use) instead of
    being discarded, and a queued prompt whose warm trie match ends
    where a spilled chain begins re-adopts the pages (alloc + scatter
    + graft, one rewarm pass before each admission) instead of
    recomputing prefill. Outputs stay bitwise-equal to a warm hit —
    the stored bytes ARE the bytes the device computed — and a
    fingerprint collision is detected by exact token-tuple comparison
    before anything is adopted. Metrics: cold_hits / cold_hit_pages /
    cold_spills counters, cold_adopt_s histogram, cold_tier_* gauges.
    on_chain_complete: optional callback ``fn(req, info)`` fired (tick
    lock held — keep it cheap/non-blocking, e.g. enqueue an event)
    when a request's prefill completes having registered/extended a
    prefix chain; ``info`` carries ``{"fp", "fps", "pages",
    "prompt_tokens"}`` with ``fp`` the deepest chain fingerprint and
    ``fps`` the cumulative per-page fingerprints. This is the
    chain-completion EVENT the fleet's migration policy rides: a
    prefill-pool worker surfaces it to the router, which picks a
    decode-pool target and drives the chunked transfer with no caller
    involvement (serving/fleet/proc/fleet.py).
    """

    # Sanctioned lock-free READS (analysis/concurrency.py guarded-by
    # pass; writes still flag). These engine-private objects are
    # mutated only on the worker tick thread under the tick lock;
    # cross-thread readers either call internally-synchronized
    # methods or take the tick lock themselves right after the
    # None/flag check, and tolerate one-tick staleness.
    _CC_LOCK_FREE_READS = {
        "scheduler": "queue methods serialize on Scheduler._lock; "
                     "slot/table state is read only under the tick "
                     "lock or after worker join",
        "prefix_cache": "is-enabled None-check only; every trie "
                        "touch below it runs under the tick lock",
        "tracer": "SpanTracer serializes on its own internal lock",
        "_closing": "handshake flag written under the _cond mutex; "
                    "the tick loop re-reads it each iteration "
                    "(worst case: one extra idle tick)",
    }
    # Caller-must-hold contracts the entry-point detector cannot see.
    _CC_REQUIRES = {
        "_spill_node": ["_tick_lock", "trie spill hook: PrefixCache "
                        "only evicts under the engine tick lock"],
    }

    @in_setup_span("serving.setup.init")
    def __init__(self, params, cfg, *, model=None, max_batch: int = 8,
                 page_size: int = 16, total_pages: Optional[int] = None,
                 max_prompt_len: int = 64, max_new_tokens_cap: int = 64,
                 prompt_buckets=None, attn_impl: str = "auto",
                 max_queue: Optional[int] = None,
                 tick_interval_s: float = 0.0,
                 decode_block_size: int = 1,
                 quantization: Optional[str] = None,
                 prefix_cache: bool = True,
                 prefill_chunk: Optional[int] = None,
                 admission_window: int = 0,
                 check_invariants: Optional[bool] = None,
                 rewrites: bool = False,
                 trace: Optional[bool] = None,
                 trace_capacity: int = 65536,
                 flight_ticks: int = 64,
                 flight_dir: Optional[str] = None,
                 recompile_sentinel: Optional[bool] = None,
                 speculative=None,
                 spec_k: int = 3,
                 cold_tier_bytes: int = 0,
                 on_chain_complete=None):
        # time to ready (``stats()["setup"]``): from here to the first
        # tick that carries a request
        self._setup_t0 = time.monotonic()
        self._ready_t: Optional[float] = None
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if prefill_chunk is not None:
            prefill_chunk = int(prefill_chunk)
            if prefill_chunk < 1:
                raise ValueError(f"prefill_chunk must be >= 1, got "
                                 f"{prefill_chunk}")
        if quantization not in (None, "none", "int8"):
            raise ValueError(f"quantization must be None/'none'/'int8', "
                             f"got {quantization!r}")
        if quantization == "int8":
            from ..quantization.decode import (is_quantized_params,
                                               quantize_for_decode)
            if not is_quantized_params(params):
                params = quantize_for_decode(params, cfg)
        # optional pacing between decode ticks (tests / co-tenant CPU
        # politeness); 0 = run ticks back to back
        self._tick_interval = float(tick_interval_s)
        # >1: fuse this many decode steps per tick (multi-step
        # scheduling — per-tick dispatch/host work amortizes over the
        # block at the cost of admission/retirement granularity;
        # sampling slots ride the block through the fused in-graph
        # sampler since r16, so nobody forces single steps)
        if decode_block_size < 1:
            raise ValueError("decode_block_size must be >= 1")
        self._decode_block = int(decode_block_size)
        self._cfg = cfg
        from ..models import resolve_family
        self._mod = resolve_family(model, cfg)
        self._family = _family(self._mod)
        self._params = self._serving_tree(params)
        # a layer kind that keeps a fixed row a slot (not pages) holds
        # state that a prefix's pages cannot rebuild: no snapshots exist
        # yet, so what attaches, moves or rolls back pages is off
        kinds = tuple(self._family.kinds(cfg))
        self._stateful = [k.name for k in kinds if k.cache == "slot_rows"]
        # a kind that keeps the last tokens of a WINDOW in a ring of
        # pages a slot (sized by the config's window and the chunk, not
        # by the context): a prefix's pages cannot rebuild it either
        self._windowed = [k.name for k in kinds
                          if k.cache == "window_pages"]
        if self._stateful or self._windowed:
            if speculative is not None:
                raise ValueError(
                    f"speculative decoding is not available for a model "
                    f"with per-slot state ({self._stateful + self._windowed}"
                    f" layers): a rejected draft's state cannot be rolled "
                    f"back")
            prefix_cache = False    # so: no chains, no cold tier
        self._attn_impl = attn_impl
        self._max_new_cap = int(max_new_tokens_cap)
        self._buckets = sorted(set(int(b) for b in (
            prompt_buckets or _default_buckets(max_prompt_len))))
        max_bucket = self._buckets[-1]
        pages_per_slot = -(-(max_bucket + self._max_new_cap - 1)
                           // page_size)
        if total_pages is None:
            total_pages = max_batch * pages_per_slot + 1
        self.pool = PagePool(total_pages=total_pages, page_size=page_size)
        # EXACT prefix attach: cached-prefix size is carried to the
        # ragged tick as data, so any page count costs zero extra
        # compiles
        self.prefix_cache = PrefixCache(self.pool) if prefix_cache \
            else None
        self._chunk = prefill_chunk
        # per-tick prefill token budget: prefill_chunk's surviving
        # (scheduling) role. None = absorb a whole suffix in one tick.
        self._budget = int(prefill_chunk) if prefill_chunk is not None \
            else max_bucket
        # speculative decoding (serving/speculative.py): drafter +
        # per-request adaptive-k policy; None = off (spec_k then plays
        # no role and compiles nothing)
        from .speculative import AcceptancePolicy, resolve_drafter
        self._drafter = resolve_drafter(speculative)
        if self._drafter is not None and int(spec_k) < 1:
            raise ValueError(f"spec_k must be >= 1 when speculative "
                             f"decoding is on, got {spec_k}")
        self._spec_k = int(spec_k) if self._drafter is not None else 0
        self._spec_policy = (AcceptancePolicy(self._spec_k)
                             if self._drafter is not None else None)
        # packed-width grid: a spans tick runs at the smallest width
        # covering its ACTUAL span tokens (a warm attach whose suffix
        # is 40 tokens must not pay the 256-wide cold program). This
        # pads the program like any jit bucket pad — geometry stays
        # data (span offsets, prefix sizes, cache lengths), so it has
        # no exactness role.
        # With speculation on, spec spans add up to S*(1+spec_k)
        # tokens on top of the prefill budget: the grid grows two
        # entries (the all-slots-drafting width and the combined
        # worst case) so every reachable span-token total still snaps
        # to a small static set — mirrored EXACTLY by
        # analysis/recompile.tick_width_grid (pinned by test).
        grid = {min(b, self._budget) for b in self._buckets} \
            | {self._budget}
        if self._spec_k:
            spec_max = max_batch * (1 + self._spec_k)
            grid |= {spec_max, self._budget + spec_max}
        self._w_grid = sorted(grid)
        # statically prove the one-program-tick invariant for THIS
        # geometry (the recompile-hazard pass, analysis/recompile.py):
        # the ragged engine reaches exactly {serving_tick@S+w (w in the
        # width grid)} and {serving_tick@S, serving_tick_block[k]} —
        # 1-2 programs per packed-width bucket. The enumeration runs
        # here so any future
        # dispatch change that silently multiplies the program set
        # warns at construction instead of stalling under traffic; the
        # warning names the offending program set.
        with setup_span("serving.setup.init.inventory"):
            from ..analysis.recompile import (ServingGeometry,
                                              program_inventory)
            geom = ServingGeometry(
                page_size=page_size, pages_per_slot=pages_per_slot,
                buckets=list(self._buckets), prefill_chunk=prefill_chunk,
                max_batch=max_batch, decode_block=self._decode_block,
                spec_k=self._spec_k)
            # the static proof's inventory, kept on the engine: the
            # recompile sentinel reports it as "expected", the flight
            # recorder ships it with every postmortem, and graph_lint
            # --json emits the identical schema — one diffable document
            self.program_inventory = program_inventory(geom)
            worst = self.program_inventory["programs_per_bucket"]
            if worst > 2:
                import warnings
                warnings.warn(
                    f"serving geometry (page_size={page_size}, "
                    f"buckets={self._buckets}, "
                    f"prefill_chunk={prefill_chunk}, "
                    f"decode_block={self._decode_block}) reaches {worst} "
                    f"distinct tick programs in one width bucket (> 2): "
                    f"{self.program_inventory['widths']}"
                    f" — each is an XLA compile inside a serving tick; see "
                    f"docs/ANALYSIS.md recompile-hazard.", stacklevel=3)
        if check_invariants is None:
            check_invariants = _env_flag(
                "PADDLE_TPU_SERVING_CHECK_INVARIANTS", False)
        self._check_invariants = bool(check_invariants)
        self.scheduler = Scheduler(
            max_batch=max_batch, pages_per_slot=pages_per_slot,
            pool=self.pool, max_queue=max_queue,
            max_prompt_len=max_bucket, prefix_cache=self.prefix_cache,
            admission_window=admission_window)
        self.metrics = ServingMetrics()
        # ------------------------------------------- observability ----
        if trace is None:
            trace = _env_flag("PADDLE_TPU_SERVING_TRACE", True)
        self.tracer = SpanTracer(capacity=trace_capacity,
                                 enabled=bool(trace))
        self.flight = FlightRecorder(capacity=flight_ticks)
        self._flight_dir = flight_dir
        self.postmortem_path: Optional[str] = None
        if recompile_sentinel is None:
            recompile_sentinel = _env_flag("PADDLE_TPU_SERVING_SENTINEL",
                                           True)
        self.sentinel = RecompileSentinel(
            expected=self.program_inventory, tracer=self.tracer,
            metrics=self.metrics, label="serving-engine") \
            if recompile_sentinel else None
        self._tick_no = 0

        # the cache pytree the model made: the two page pools, and what
        # its other layer kinds keep (sized by the slots)
        import jax
        with setup_span("serving.setup.init.cache"):
            self._cache = jax.block_until_ready(dict(  # noqa: PT002 — the set-up span holds the allocation, once an engine
                self._family.init_pages(cfg, total_pages, page_size,
                                        max_batch, self._budget)))
        # its page pools by name, and whether they are the K and V pools
        # that chain export / adopt and the cold tier carry
        self._pools = tuple(self._family.page_pools(cfg))
        self._kv_pools = tuple(p.name for p in self._pools) == (
            "k_pages", "v_pages")
        # counts a family's tick programs return beside their tokens
        # (the record's ``counters``): added when the tick completes
        self._tick_counters = tuple(self._family.counters)
        self._page_copies: Dict[int, int] = {}   # by query rows a slot
        self._tick_layers = {}
        if kinds:
            self._tick_layers = dict(
                state_layers=len(self._stateful),
                attn_layers=sum(k.cache == "pages" for k in kinds))
            pool_names = {p.name for p in self._pools}
            if self._windowed:
                self._window = int(cfg.sliding_window)
                self._tick_layers["window_layers"] = len(self._windowed)
                # the rings, by the names the family gives them: they
                # come with the slots and no allocator counts them
                rings = set(self._family.window_pools(cfg))
                self._window_pool_bytes = sum(
                    int(self._cache[name].nbytes) for name in rings)
                pool_names |= rings
            self._slot_state_bytes = sum(
                int(a.nbytes) for name, a in self._cache.items()
                if name not in pool_names)
            # what ONE slot holds of it (the trash row is one more)
            self._state_bytes_per_slot = (
                self._slot_state_bytes // (max_batch + 1))
        self._jnp = jax.numpy
        self._tick_jit, self._block_jit = _jit_step_fns(
            self._mod, cfg, attn_impl, rewrites=rewrites)
        self._jax = jax
        # requests parked mid chunked-prefill, FIFO: one chunk advances
        # per tick so in-flight decode streams keep a bounded stall
        self._prefill_q: "deque" = deque()
        self._last_decode_t: Optional[float] = None

        # each slot's current token ON THE DEVICE, like its KV: both
        # tick programs take it and return its successor, so the next
        # tick's decode rows never wait for a read-back
        self._cur_tok_d = self._jnp.zeros((max_batch,), self._jnp.int32)
        # tokens DISPATCHED per slot (emitted + in flight): the index
        # the fused sampler draws the next token at, and what says a
        # request reaches max_new_tokens with the tick in flight;
        # ``_emitted`` is what its client has seen
        self._produced = np.zeros((max_batch,), np.int64)
        self._emitted = np.zeros((max_batch,), np.int64)
        # the tick in flight (None: the host has read everything back)
        # and how many the loop keeps ahead of its read-back: one,
        # unless a drafter reads ``req.tokens`` on the host to build
        # the next tick
        self._inflight: Optional[_Tick] = None
        self._depth = 0 if self._drafter is not None else 1
        self._last_done_t: Optional[float] = None
        # per-slot raw PRNG key data (fused in-graph sampling, r16):
        # PRNGKey(seed) at admission, CONSTANT for the request's whole
        # life — the tick folds the token's continuation index in
        # (fold_in(key, produced)), so no host-side split chain exists
        # to drift with batch composition
        self._key_data = np.zeros((max_batch, 2), np.uint32)
        # device-side cache of the composition-dependent sampling
        # arrays (see _sampling_arrays); None = rebuild next tick
        self._samp_cache = None

        # ------------------------------------- migration + cold tier ----
        # chain-completion hook (fired by _register_prompt, tick lock
        # held) — the fleet wires this to surface events to the router
        self.on_chain_complete = on_chain_complete
        # in-flight chunked transfers, both directions. Exports pin
        # their chain nodes (refs+1, released at export_chain_end);
        # adopts own freshly-allocated pages that no scheduler row or
        # trie node references yet, plus pins on the matched warm
        # prefix. Both are declared to the KV auditor via
        # _audit_extras() so CHECK_INVARIANTS stays clean mid-transfer.
        self._exports: Dict[int, dict] = {}
        self._adopts: Dict[int, dict] = {}
        self._xfer_ids = itertools.count(1)
        # host-RAM cold tier: refcount-0 chains evicted under pressure
        # spill here (PrefixCache.spill hook) and rewarm on a prefix
        # match instead of recomputing prefill — see class docstring
        self._cold = (ColdTier(int(cold_tier_bytes))
                      if int(cold_tier_bytes) > 0
                      and self.prefix_cache is not None
                      and self._kv_pools else None)
        if int(cold_tier_bytes) > 0 and not self._kv_pools:
            # the cold tier's entries are K and V pages: another pool
            # is not spilled, and says so
            self.metrics.inc_labeled(
                "cold_tier_refused",
                pool=",".join(p.name for p in self._pools))
        if self._cold is not None:
            self.prefix_cache.spill = self._spill_node

        # _cond stays a RAW Condition (its internal mutex cannot be
        # traced without modelling wait()'s release semantics); the
        # tick lock goes through wrap_lock so the LockTracer / fuzzer
        # see every acquisition when enabled (zero cost otherwise)
        self._cond = threading.Condition()
        self._tick_lock = wrap_lock(threading.Lock(),
                                    "ServingEngine._tick_lock")
        self._closing = False
        self._drain = True
        # hand-back drain (the fleet drain protocol): when set, the
        # drain stops admission and returns queued-but-unadmitted
        # requests through close() instead of serving them
        self._hand_back = False
        self._returned: list = []
        self._dead: Optional[BaseException] = None
        self._worker = threading.Thread(target=self._loop, daemon=True,
                                        name="serving-engine")
        self._worker.start()

    # ------------------------------------------------------------- cache ----
    # the two page pools are two leaves of the cache pytree: what moves
    # pages (defrag, migration, the cold tier) reads and rebinds them
    def _step_tick(self, tok_d, meta, **static):
        """One call of the jitted ragged tick over the slots' current
        tokens and the donated cache; rebinds both successors and hands
        back the rest of the results."""
        *out, self._cur_tok_d, self._cache = self._tick_jit(
            self._params, tok_d, dict(meta, cur_tok=self._cur_tok_d),
            self._cache, **static)
        return self._split_counts(out)

    def _step_block(self, lengths_d, tables_d, sampling):
        """Likewise the jitted fused decode block; returns its tokens
        (and the family's counts, or None)."""
        *out, self._cur_tok_d, self._cache = self._block_jit(
            self._params, self._cur_tok_d, lengths_d, tables_d,
            self._cache, num_steps=self._decode_block, sampling=sampling)
        (toks_d,), counts_d = self._split_counts(out)
        return toks_d, counts_d

    def _split_counts(self, out):
        """``(results, counts)``: a family with ``counters`` hands
        its counts back as the last result before the slots' tokens."""
        if self._tick_counters:
            return out[:-1], out[-1]
        return out, None

    def _pull_pages(self, idx):
        """Pages ``idx`` of every pool on the host, in the pools' order
        (K and V: ``[L, Hkv, n, ps, Dh]`` each); caller holds the tick
        lock."""
        jnp = self._jnp
        return tuple(
            np.asarray(jnp.take(self._cache[p.name], idx, axis=p.page_axis))  # noqa: PT005 — migration export and cold-tier spill are sanctioned one-shot device pulls
            for p in self._pools)

    def _write_pages(self, idx, *rows) -> None:
        """Host pages written back at ``idx``, one array a pool in the
        pools' order (caller holds the tick lock)."""
        jnp = self._jnp
        for p, r in zip(self._pools, rows):
            at = (slice(None),) * p.page_axis + (idx,)
            self._cache[p.name] = self._cache[p.name].at[at].set(
                jnp.asarray(r))

    def _refuse_stateful(self, what: str) -> None:
        if self._stateful:
            raise RuntimeError(
                f"{what} is not available for a model with per-slot "
                f"state ({self._stateful} layers): a chain of pages does "
                f"not carry the state its prefix left behind")
        if self._windowed:
            self.metrics.inc_labeled("chain_refused", pool="window_pages")
            raise RuntimeError(
                f"{what} is not available for a model with window rings "
                f"({len(self._windowed)} window layers): a chain of the "
                f"full layers' pages does not carry the window layers' "
                f"last tokens")
        if not self._kv_pools:
            # the chain blob and the cold tier's entries are K and V
            # pages; a family with another pool is refused by name
            pools = ",".join(p.name for p in self._pools)
            self.metrics.inc_labeled("chain_refused", pool=pools)
            raise RuntimeError(
                f"{what} is not available for a cache whose page pools "
                f"are ({pools}): the chain wire format carries k_pages / "
                f"v_pages")

    # --------------------------------------------------------------- API ----
    def submit(self, prompt, max_new_tokens: int, *,
               eos_token_id: Optional[int] = None,
               timeout: Optional[float] = None,
               temperature: float = 0.0, top_p: float = 1.0,
               top_k: int = 0, seed: int = 0) -> RequestHandle:
        """Queue one request; returns a streaming handle. Raises
        RuntimeError when the request is REJECTED (queue full, or its
        prompt/page budget can never fit this engine).
        ``temperature``/``top_p``/``top_k``/``seed`` are per-request
        sampling state carried to the fused in-graph sampler as DATA
        (r16): a sampling request rides the same tick programs as its
        greedy neighbours, and a fixed seed reproduces its token
        stream exactly whatever else shares the batch."""
        if self._dead is not None:
            raise RuntimeError("engine worker died") from self._dead
        deadline = None if timeout is None else time.monotonic() + timeout
        req = Request(prompt, max_new_tokens, eos_token_id=eos_token_id,
                      deadline_s=deadline, temperature=temperature,
                      top_p=top_p, top_k=top_k, seed=seed)
        self.metrics.inc("submitted")
        with self._cond:
            if self._closing:
                raise RuntimeError("ServingEngine is closed")
            ok = self.scheduler.submit(req)
            if ok:
                self._cond.notify_all()
        if ok and self._dead is not None and not req.done.is_set():
            # the worker died between our liveness check and the
            # enqueue: _fail_all may have drained the queue already, so
            # nothing would ever resolve this handle — fail it here.
            # (done.is_set() guards the other interleaving: the worker
            # served this request COMPLETELY and died later — that
            # success must not be clobbered to CANCELLED)
            req.error = self._dead
            req.finish(CANCELLED)
            raise RuntimeError("engine worker died") from self._dead
        if not ok:
            req.state = REJECTED
            self.metrics.inc("rejected")
            raise RuntimeError(
                f"request rejected: prompt {req.prompt.size} tokens + "
                f"{req.max_new_tokens} new needs "
                f"{self.scheduler.pages_needed(req)} pages "
                f"(slot budget {self.scheduler.pages_per_slot}, max "
                f"prompt {self.scheduler.max_prompt_len}) or queue full")
        return RequestHandle(req)

    def generate(self, prompt, max_new_tokens: int, **kw) -> np.ndarray:
        """Blocking convenience: submit + wait; returns the generated
        tokens (no prompt prefix, same contract as generate_paged)."""
        return self.submit(prompt, max_new_tokens, **kw).result()

    @property
    def alive(self) -> bool:
        """Worker thread running with no recorded death — the public
        liveness surface fleet replicas (and any future RPC health
        endpoint) key routing eligibility on."""
        return self._dead is None and self._worker.is_alive()

    def inject(self, req: Request) -> bool:
        """Enqueue an EXISTING :class:`Request` object (the fleet
        router's dispatch/re-dispatch path — serving/fleet/router.py):
        same admission checks as :meth:`submit`, but non-raising, so a
        router can try the next replica. The request object carries
        its own stream/done machinery, so a caller's
        ``RequestHandle`` keeps working across re-dispatch to a
        different engine — tokens simply start arriving from the new
        replica. Returns False (and finalizes NOTHING) when this
        engine cannot take it: closed/closing, dead worker, queue
        full, or a prompt/page budget that can never fit this
        geometry. Counter contract: ``submitted`` counts only ACCEPTED
        injections (a router's dispatch walk trying several replicas
        must not inflate fleet-aggregated submit totals); a refusal
        counts ``rejected`` on the refusing replica."""
        if self._dead is not None:
            self.metrics.inc("rejected")
            return False
        with self._cond:
            if self._closing:
                self.metrics.inc("rejected")
                return False
            ok = self.scheduler.submit(req)
            if ok:
                self._cond.notify_all()
        if not ok:
            self.metrics.inc("rejected")
            return False
        if self._dead is not None and not req.done.is_set():
            # worker died between the liveness check and the enqueue.
            # Safe to hand back ONLY if we can pull the request out of
            # the queue untouched — if it is not there, the worker
            # already moved it to a slot (or _fail_all is finalizing
            # it): the engine owns it, so report accepted and let the
            # fail-fast contract resolve the handle; returning False
            # here would let the router dispatch the SAME object into
            # a second engine while this one still mutates it.
            if self.scheduler.drop_queued(lambda r: r is req):
                # counter contract: every refusal counts as rejected
                self.metrics.inc("rejected")
                return False
        self.metrics.inc("submitted")
        return True

    def close(self, drain: bool = True,
              hand_back: bool = False) -> "list[Request]":
        """Stop admission and shut down; returns the requests handed
        back for re-dispatch (empty unless ``hand_back``).

        drain=True (default) finishes every queued + running request
        first; drain=False cancels them all. ``hand_back=True`` is the
        fleet drain protocol (serving/fleet/): admission stops
        IMMEDIATELY, in-flight slots (decoding or parked mid-prefill)
        run to completion, and queued-but-unadmitted requests are
        returned — still QUEUED, never finalized as failed — so a
        router can re-dispatch them to another replica and the
        caller's handles resolve there. Without hand-back a drain
        serves its whole queue, so nothing is ever silently dropped
        either way; hand-back just trades queue latency on a dying
        replica for a re-dispatch.

        The hand-back list is returned ONCE: each request appears in
        exactly one close() return (a second close on a drained
        engine returns ``[]``), so a caller can never re-dispatch a
        request that an earlier close already surfaced."""
        if hand_back and not drain:
            raise ValueError("hand_back requires drain=True (a cancel "
                             "close finalizes, it cannot hand back)")
        with self._cond:
            if self._dead is not None and not self._worker.is_alive():
                if self.sentinel is not None:
                    self.sentinel.close()
                return self._take_returned()
            self._closing = True     # noqa: CC001(handshake flags are written under the _cond mutex; the tick loop re-reads them under the tick lock every iteration)
            self._drain = drain      # noqa: CC001(same _cond handshake as _closing above)
            self._hand_back = bool(hand_back)  # noqa: CC001(same _cond handshake as _closing above)
            self._cond.notify_all()
        self._worker.join()
        if self.sentinel is not None:
            self.sentinel.close()
        return self._take_returned()

    def _take_returned(self) -> "list[Request]":
        """Drain the hand-back list atomically (worker is not running
        when this is called; the cond lock guards racing closers)."""
        with self._cond:
            out, self._returned = self._returned, []  # noqa: CC001(worker has exited by the time any closer gets here; the _cond mutex serializes racing closers)
        return out

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def _gauges(self) -> dict:
        """Live pool/queue gauges. Caller must hold ``_tick_lock``:
        occupancy / utilization / prefix stats walk structures the
        engine loop mutates mid-tick (slot list, free list, trie), so
        an unlocked read can see a torn view or a dict resized under
        iteration. The metrics lock alone is NOT enough — the loop
        only holds it inside inc()/observe(), not while it mutates the
        scheduler."""
        g = {
            "queued": self.scheduler.queued(),
            "occupancy": self.scheduler.occupancy,
            "page_utilization": self.pool.utilization,
            "free_pages": self.pool.free_pages,
        }
        if self.prefix_cache is not None:
            g["prefix_cache"] = self.prefix_cache.stats()
        if self._cold is not None:
            g["cold_tier"] = self._cold.stats()
        if self._tick_layers:
            g["slot_state_bytes"] = self._slot_state_bytes
        if self._windowed:
            g["window_pool_bytes"] = self._window_pool_bytes
        return g

    def snapshot(self) -> dict:
        """Plain-dict metrics snapshot (+ live pool/queue gauges).
        Safe to call from any thread concurrently with the engine
        loop: counters/histograms are copied under the metrics lock
        and gauges are read under the tick lock (serialized against
        the loop's scheduler/pool mutations — see ``_gauges``)."""
        snap = self.metrics.snapshot()
        with self._tick_lock:
            snap["gauges"] = self._gauges()
        return snap

    def stats(self) -> dict:
        """:meth:`snapshot` plus ``setup``: where the time to ready
        went (``observability.setup_report``: spans, the compile
        ledger's totals and slowest programs, the rows that partition
        it) as of the first tick that carried a request (until then,
        ``ready`` False, as of now), and ``_serving_tree``'s cost."""
        snap = self.snapshot()
        with self._tick_lock:
            ready_t = self._ready_t
        snap["setup"] = dict(
            setup_report(since=self._setup_t0, until=ready_t),
            ready=ready_t is not None, **self._relaid,
            time_to_ready_s=(None if ready_t is None
                             else ready_t - self._setup_t0))
        return snap

    def gauges(self) -> dict:
        """Flat ``{name: number}`` view of the live pool/queue gauges
        (nested dicts like the prefix-cache stats flattened to
        ``prefix_cache_<k>``). Thread-safe like :meth:`snapshot` —
        this is the health feed a fleet replica polls
        (serving/fleet/replica.py) and what :meth:`expose` renders."""
        with self._tick_lock:
            g = self._gauges()
        flat = {}
        for k, v in g.items():
            if isinstance(v, dict):
                flat.update({f"{k}_{kk}": vv for kk, vv in v.items()
                             if isinstance(vv, (int, float))})
            elif isinstance(v, (int, float)):
                flat[k] = v
        return flat

    def expose(self, labels: Optional[dict] = None) -> str:
        """Prometheus text exposition of counters + histograms + live
        gauges (``ServingMetrics.expose`` — dependency-free; serve it
        from any HTTP handler). Thread-safe like :meth:`snapshot`.
        ``labels`` (raw, unescaped) are stamped on every sample — the
        fleet aggregator passes ``{"replica": ...}`` and relies on
        escape-once at render time."""
        return self.metrics.expose(gauges=self.gauges(), labels=labels)

    def affinity_summary(self, max_depth: int = 2) -> dict:
        """The prefix cache's hot-chain fingerprint summary
        (``PrefixCache.affinity_summary``) read under the tick lock —
        safe from any thread; ``{}`` when the prefix cache is off.
        This is the warmth signal the fleet router matches prompts
        against."""
        if self.prefix_cache is None:
            return {}
        with self._tick_lock:
            return self.prefix_cache.affinity_summary(max_depth)

    # ------------------------------------------------- KV-page migration ----
    def export_chain(self, fp: int,
                     max_depth: int = 64) -> Optional[dict]:
        """Export a cached prefix chain's tokens + KV pages, keyed by
        the affinity FINGERPRINT the fleet router matches on
        (``prefix_cache.prefix_fingerprints`` / ``affinity_summary``).
        Returns a plain-data blob —
        ``{fp, page_size, tokens: [page token tuples], k, v}`` with
        ``k``/``v`` numpy arrays of shape ``[L, Hkv, n_pages,
        page_size, Dh]`` gathered from the live pools — or ``None``
        when no cached chain hashes to ``fp``. The blob is what
        crosses the process boundary in disaggregated serving
        (fleet/proc/): a prefill worker exports, a decode worker
        :meth:`adopt_chain`\\ s. Runs under the tick lock, so the
        gather can never race a tick's pool donation or a defrag's
        page moves — and post-defrag ``node.page`` ids are already
        the live ids (``PrefixCache.remap``), so a scattered-then-
        compacted source exports correctly by construction."""
        self._refuse_stateful("export_chain")
        if self.prefix_cache is None:
            return None
        jnp = self._jnp
        with self._tick_lock:
            self._drain_inflight("migrate")
            nodes = self.prefix_cache.chain_by_fingerprint(fp, max_depth)
            if not nodes:
                return None
            pages = [nd.page for nd in nodes]
            tokens = [tuple(int(t) for t in nd.toks) for nd in nodes]
            idx = jnp.asarray(pages, jnp.int32)
            # gather along the page axis (pools are [L, Hkv, P, ps, Dh]);
            # the pull to host is the POINT: the blob must be plain
            # numpy to pickle across the fleet/proc worker boundary
            k, v = self._pull_pages(idx)
            host_sync("serving.migrate_export")
        return {"fp": int(fp), "page_size": int(self.pool.page_size),
                "tokens": tokens, "k": k, "v": v}

    def adopt_chain(self, blob: dict) -> dict:
        """Adopt an exported chain (:meth:`export_chain` blob) into
        THIS engine's pool + trie: allocate pages for the un-cached
        suffix of the chain (evicting cold refcount-0 pages under
        pressure, same policy as admission), scatter the exported KV
        into the live pools, and graft the trie nodes at refs=0 —
        after which a submit sharing that prefix attaches it through
        the normal exact-token-tuple path and decodes BITWISE equal
        to a single-engine ``generate()`` (the KV bytes are the
        source's; attachment never trusts the fingerprint). Returns
        ``{"matched_pages", "adopted_pages"}``; raises ValueError on
        a page-size mismatch and RuntimeError when the pool cannot
        hold the suffix even after eviction."""
        self._refuse_stateful("adopt_chain")
        if self.prefix_cache is None:
            raise RuntimeError("adopt_chain needs prefix_cache=True")
        if int(blob["page_size"]) != int(self.pool.page_size):
            raise ValueError(
                f"page-size mismatch: exported {blob['page_size']}, "
                f"this engine serves {self.pool.page_size}")
        tokens = [tuple(int(t) for t in tt) for tt in blob["tokens"]]
        jnp = self._jnp
        with self._tick_lock:
            self._drain_inflight("migrate")
            pc = self.prefix_cache
            have = pc.match_chain(tokens)
            need = len(tokens) - have
            if need == 0:
                return {"matched_pages": have, "adopted_pages": 0}
            if not self.pool.can_alloc(need):
                pc.evict(need - self.pool.free_pages)
            if not self.pool.can_alloc(need):
                raise RuntimeError(
                    f"cannot adopt chain: {need} pages needed, "
                    f"{self.pool.free_pages} free after eviction")
            pages = self.pool.alloc(need)
            idx = jnp.asarray(pages, jnp.int32)
            self._write_pages(idx, blob["k"][:, :, have:],
                              blob["v"][:, :, have:])
            pc.adopt_chain(tokens, pages, start=have)
        return {"matched_pages": have, "adopted_pages": need}

    # ------------------------------------- chunked (overlapped) transfer ----
    # The whole-blob export/adopt above stalls BOTH tick loops for the
    # full gather/scatter. The chunked protocol splits the transfer so
    # neither worker's tick loop ever holds the tick lock longer than
    # ONE bounded chunk: begin snapshots/pins under the lock, chunks
    # stream between ticks, and the trie graft happens only at commit —
    # exactly-once, with abort/end making any partial transfer
    # invisible. The fleet drives this (fleet/proc/fleet.py
    # ``migrate_chain``); in-flight state is declared to the KV auditor
    # via ``_audit_extras`` so CHECK_INVARIANTS stays clean mid-flight.

    def export_chain_begin(self, fp: int,
                           max_depth: int = 64) -> Optional[dict]:
        """Open a chunked export: resolve the chain for ``fp``, PIN its
        nodes (refs+1 — eviction and defrag-freeing cannot touch them
        while the transfer streams), and return the transfer header
        ``{"xid", "fp", "page_size", "tokens"}`` (no KV bytes yet) —
        or ``None`` when nothing hashes to ``fp``. Pins release at
        :meth:`export_chain_end` (also call it on failure paths)."""
        self._refuse_stateful("export_chain_begin")
        if self.prefix_cache is None:
            return None
        with self._tick_lock:
            self._drain_inflight("migrate")
            nodes = self.prefix_cache.chain_by_fingerprint(fp, max_depth)
            if not nodes:
                return None
            for nd in nodes:
                nd.refs += 1
            xid = next(self._xfer_ids)
            self._exports[xid] = {"nodes": nodes}
            tokens = [tuple(int(t) for t in nd.toks) for nd in nodes]
        return {"xid": xid, "fp": int(fp),
                "page_size": int(self.pool.page_size), "tokens": tokens}

    def export_chain_chunk(self, xid: int, start: int,
                           count: int) -> dict:
        """Gather one bounded chunk of the pinned export: pages
        ``[start, start+count)`` of the chain, returned as
        ``{"start", "count", "k", "v"}`` numpy blobs. Page ids are
        re-read from the live nodes at gather time, so a defrag that
        ran between chunks (``PrefixCache.remap``) is harmless — the
        pins only stop the pages being FREED, not moved."""
        jnp = self._jnp
        with self._tick_lock:
            self._drain_inflight("migrate")
            ent = self._exports[xid]
            nodes = ent["nodes"][start:start + count]
            idx = jnp.asarray([nd.page for nd in nodes], jnp.int32)
            k, v = self._pull_pages(idx)
            host_sync("serving.migrate_export")
        return {"start": int(start), "count": len(nodes), "k": k, "v": v}

    def export_chain_end(self, xid: int) -> None:
        """Close a chunked export and release its pins. Idempotent —
        an unknown/already-closed ``xid`` is a no-op, so failure paths
        can call it unconditionally."""
        with self._tick_lock:
            ent = self._exports.pop(xid, None)
            if ent is None:
                return
            for nd in ent["nodes"]:
                nd.refs -= 1

    def adopt_chain_begin(self, header: dict) -> dict:
        """Open a chunked adopt from an :meth:`export_chain_begin`
        header: match the warm prefix, PIN the matched nodes, allocate
        pages for the uncached suffix (evicting under pressure, same
        policy as admission) and return ``{"aid", "matched_pages",
        "need"}``. When the whole chain is already cached, ``aid`` is
        None and no state is held. The allocated pages belong to the
        transfer (not the trie) until :meth:`adopt_chain_commit`;
        :meth:`adopt_chain_abort` frees them. Raises ValueError on a
        page-size mismatch, RuntimeError when the suffix cannot fit."""
        self._refuse_stateful("adopt_chain_begin")
        if self.prefix_cache is None:
            raise RuntimeError("adopt_chain needs prefix_cache=True")
        if int(header["page_size"]) != int(self.pool.page_size):
            raise ValueError(
                f"page-size mismatch: exported {header['page_size']}, "
                f"this engine serves {self.pool.page_size}")
        tokens = [tuple(int(t) for t in tt) for tt in header["tokens"]]
        with self._tick_lock:
            self._drain_inflight("migrate")
            pc = self.prefix_cache
            pinned = pc.chain_nodes(tokens)
            have = len(pinned)
            need = len(tokens) - have
            if need == 0:
                return {"aid": None, "matched_pages": have, "need": 0}
            if not self.pool.can_alloc(need):
                pc.evict(need - self.pool.free_pages)
            if not self.pool.can_alloc(need):
                raise RuntimeError(
                    f"cannot adopt chain: {need} pages needed, "
                    f"{self.pool.free_pages} free after eviction")
            for nd in pinned:
                nd.refs += 1
            pages = self.pool.alloc(need)
            aid = next(self._xfer_ids)
            self._adopts[aid] = {"tokens": tokens, "have": have,
                                 "pages": pages, "pinned": pinned,
                                 "filled": 0}
        return {"aid": aid, "matched_pages": have, "need": need}

    def adopt_chain_chunk(self, aid: int, start: int, k, v) -> None:
        """Scatter one exported chunk (chain-page index ``start``,
        blobs from :meth:`export_chain_chunk`) into this transfer's
        pre-allocated pages. Chunks may arrive in any order; commit
        checks completeness."""
        jnp = self._jnp
        with self._tick_lock:
            self._drain_inflight("migrate")
            ent = self._adopts[aid]
            off = int(start) - ent["have"]
            count = int(k.shape[2])
            idx = jnp.asarray(ent["pages"][off:off + count], jnp.int32)
            self._write_pages(idx, k, v)
            ent["filled"] += count

    def adopt_chain_commit(self, aid: int) -> dict:
        """Finalize a chunked adopt: verify every suffix page arrived,
        re-check the warm match (a LOCAL prefill may have inserted the
        same chain while chunks streamed — the duplicated leading
        pages are freed instead of grafted, exactly-once by token
        equality), graft the remainder into the trie at refs=0, and
        release the prefix pins. Returns ``{"matched_pages",
        "adopted_pages"}`` mirroring :meth:`adopt_chain`."""
        with self._tick_lock:
            self._drain_inflight("migrate")
            ent = self._adopts.pop(aid)
            pc = self.prefix_cache
            dup = 0
            try:
                need = len(ent["tokens"]) - ent["have"]
                if ent["filled"] != need:
                    raise RuntimeError(
                        f"adopt_chain_commit: {ent['filled']} of "
                        f"{need} suffix pages arrived")
                now_have = pc.match_chain(ent["tokens"])
                dup = max(0, now_have - ent["have"])
                if dup > 0:
                    self.pool.free(ent["pages"][:dup])
                pc.adopt_chain(ent["tokens"], ent["pages"][dup:],
                               start=now_have)
            except BaseException:
                self.pool.free(ent["pages"][dup:])
                raise
            finally:
                for nd in ent["pinned"]:
                    nd.refs -= 1
        return {"matched_pages": ent["have"],
                "adopted_pages": len(ent["pages"]) - dup}

    def adopt_chain_abort(self, aid: int) -> None:
        """Abandon a chunked adopt: free its pages, release its pins.
        Idempotent on unknown ``aid`` — safe from any failure path."""
        with self._tick_lock:
            ent = self._adopts.pop(aid, None)
            if ent is None:
                return
            self.pool.free(ent["pages"])
            for nd in ent["pinned"]:
                nd.refs -= 1

    def _audit_extras(self):
        """(extra_refs, extra_pages) describing in-flight chunked
        transfers for ``audit_serving_state`` — export/adopt pins as
        per-node refcount credits, adopt-owned pages as expected
        allocations. Caller holds the tick lock."""
        extra_refs: Dict[int, int] = {}
        extra_pages: Dict[int, str] = {}
        for xid, ent in self._exports.items():
            for nd in ent["nodes"]:
                extra_refs[id(nd)] = extra_refs.get(id(nd), 0) + 1
        for aid, ent in self._adopts.items():
            for nd in ent["pinned"]:
                extra_refs[id(nd)] = extra_refs.get(id(nd), 0) + 1
            for p in ent["pages"]:
                extra_pages[int(p)] = f"adopt-{aid}"
        return extra_refs, extra_pages

    # -------------------------------------------- host-memory cold tier ----
    def _spill_node(self, nd) -> None:
        """``PrefixCache.spill`` hook: page one evicted refcount-0
        chain node's KV out to the host-RAM cold tier before its
        device page is freed. Runs inside ``PrefixCache.evict`` —
        tick lock already held, and no tick in flight: whoever evicts
        with a cold tier on (admission in ``_loop``, ``adopt_chain*``)
        completed it first; failures are swallowed by the caller
        (spill is an optimization, eviction must always succeed)."""
        if self._cold is None:
            return
        fp = self.prefix_cache.node_fingerprint(nd)
        jnp = self._jnp
        idx = jnp.asarray([nd.page], jnp.int32)
        k, v = self._pull_pages(idx)
        host_sync("serving.cold_spill")
        if self._cold.put(fp, nd.toks, k, v):
            self.metrics.inc("cold_spills")

    def _rewarm_cold(self) -> None:
        """Cold-tier rewarm (engine loop, tick lock held, right before
        admission): for each prompt at the admission frontier, if its
        warm trie match ends where a spilled chain begins, re-adopt
        the contiguous cold run — alloc + scatter + graft — so
        ``_try_reserve`` attaches it as an ordinary warm hit and the
        suffix prefill never recomputes those pages. Every adopted
        page is verified by exact token-tuple equality (the
        fingerprint only indexes); decode over re-adopted pages is
        bitwise-equal to never having evicted. Best-effort: any
        failure skips the request, never the loop."""
        pc = self.prefix_cache
        ps = self.pool.page_size
        jnp = self._jnp
        for req in self.scheduler.peek_queued(4):
            try:
                max_pages = (int(req.prompt.size) - 1) // ps
                if max_pages <= 0:
                    continue
                tuples = [tuple(int(t) for t in
                                req.prompt[i * ps:(i + 1) * ps])
                          for i in range(max_pages)]
                warm = pc.match_chain(tuples)
                fp, fps = 0, []
                for tt in tuples:
                    fp = _fp_extend(fp, tt)
                    fps.append(fp)
                run = []
                for i in range(warm, max_pages):
                    ent = self._cold.get(fps[i])
                    if ent is None or ent["toks"] != tuples[i]:
                        break       # fp collision or gap: stop the run
                    run.append(ent)
                if not run:
                    continue
                t0 = time.monotonic()
                n = len(run)
                if not self.pool.can_alloc(n):
                    # evict under pressure — with the warm prefix
                    # PINNED: its leaf may be refs-0/childless (prime
                    # eviction food) and the graft below walks it
                    pinned = pc.chain_nodes(tuples[:warm])
                    for nd in pinned:
                        nd.refs += 1
                    try:
                        pc.evict(n - self.pool.free_pages)
                    finally:
                        for nd in pinned:
                            nd.refs -= 1
                if not self.pool.can_alloc(n):
                    continue        # no room: leave it cold
                pages = self.pool.alloc(n)
                idx = jnp.asarray(pages, jnp.int32)
                k = np.concatenate([e["k"] for e in run], axis=2)
                v = np.concatenate([e["v"] for e in run], axis=2)
                self._write_pages(idx, k, v)
                pc.adopt_chain(tuples[:warm + n], pages, start=warm)
                for i in range(warm, warm + n):
                    self._cold.pop(fps[i])
                self.metrics.inc("cold_hits")
                self.metrics.inc("cold_hit_pages", n)
                self.metrics.observe("cold_adopt_s",
                                     time.monotonic() - t0)
            except Exception:
                continue    # rewarm is opportunistic, never fatal

    def export_trace(self, path: str) -> str:
        """Write the span tracer's ring as Perfetto-loadable
        Chrome-trace JSON (one track per engine phase + per slot);
        returns ``path``."""
        return self.tracer.export(path)

    def arm_sentinel(self) -> None:
        """Declare warmup complete: from now on, ANY XLA compile in
        this process raises ``RecompileWarning`` and increments the
        labeled ``recompiles`` counter (no-op when the sentinel is
        disabled). Call after traffic has touched every width-grid
        entry — ``tools/serving_bench.py`` does this after its warmup
        pass."""
        if self.sentinel is not None:
            self.sentinel.arm()

    def _pad_tick_args(self):
        """The all-padding tick arguments ``warm_programs`` and
        ``program_texts`` share: ``(pad_meta(T), tabs, zs, samp)``
        — every packed token is the padding sentinel, every KV write
        lands on the trash page."""
        jnp = self._jnp
        S = self.scheduler.max_batch
        pps = self.scheduler.pages_per_slot
        tabs = np.full((S, pps), PagePool.TRASH, np.int32)
        zs = np.zeros((S,), np.int32)
        samp = dict(temp=jnp.asarray(np.zeros((S,), np.float32)),
                    top_p=jnp.asarray(np.ones((S,), np.float32)),
                    top_k=jnp.asarray(zs),
                    key=jnp.asarray(np.zeros((S, 2), np.uint32)),
                    produced=jnp.asarray(zs))

        def pad_meta(T):
            return dict(
                tok_slot=jnp.asarray(np.full((T,), S, np.int32)),
                tok_pos=jnp.asarray(np.zeros((T,), np.int32)),
                tok_page=jnp.asarray(
                    np.full((T,), PagePool.TRASH, np.int32)),
                tok_off=jnp.asarray(np.zeros((T,), np.int32)),
                tok_qoff=jnp.asarray(np.zeros((T,), np.int32)),
                q_len=jnp.asarray(zs), kv_len=jnp.asarray(zs),
                last=jnp.asarray(zs), tables=jnp.asarray(tabs),
                tail_live=jnp.asarray(np.zeros((S,), bool)),
                **samp)

        return pad_meta, tabs, zs, samp

    def program_texts(self, debug_info: bool = False) -> Dict[str, str]:
        """Lowered (StableHLO) text of every program ``warm_programs``
        compiles on a non-speculative engine, traced at the same
        padding arguments: ``{"tick@<w>": ..., "block": ...}``. Lets a
        caller check what the programs ARE on this backend (a Pallas
        kernel shows as a ``tpu_custom_call``) without reaching into
        the jit objects; with ``debug_info`` every operation carries
        its ``jax.named_scope`` path. Nothing executes and no pool is
        donated."""
        jnp = self._jnp
        S = self.scheduler.max_batch
        pad_meta, tabs, zs, samp = self._pad_tick_args()
        out = {}
        with self._tick_lock:
            for w in self._w_grid:
                T = S + w
                out[f"tick@{w}"] = self._tick_jit.lower(
                    self._params, jnp.asarray(np.zeros((T,), np.int32)),
                    dict(pad_meta(T), cur_tok=self._cur_tok_d),
                    self._cache, tq=w,
                    decode_tail=0).as_text(debug_info=debug_info)
            out["block"] = self._block_jit.lower(
                self._params, self._cur_tok_d, jnp.asarray(zs),
                jnp.asarray(tabs), self._cache,
                num_steps=self._decode_block,
                sampling=samp).as_text(debug_info=debug_info)
        return out

    def warm_programs(self) -> int:
        """Eagerly compile every tick program the static inventory
        enumerates, via all-padding no-op ticks (every packed token is
        the padding sentinel, every KV write lands on the trash page,
        every output row is junk the caller discards) — so the compile
        set is covered DETERMINISTICALLY instead of depending on which
        widths traffic happens to hit. This is what lets the recompile
        sentinel be armed right after construction and stay clean: on a
        speculative engine the reachable verify widths depend on
        per-tick draft counts, which a traffic-shaped warmup cannot
        guarantee to cover. Safe any time (serialized against ticks;
        real pages are never read into outputs that matter nor
        written; no slot is tail-live, so every slot's current token
        passes through unchanged — a tick in flight is not disturbed).
        Returns the number of jit invocations made."""
        from ..core.stack_anchor import above_stack_anchor
        # every call in there traces and lowers a program: seconds of
        # deeply nested Python, whose speed would otherwise depend on
        # the depth of whoever called us (core/stack_anchor.py)
        return above_stack_anchor(self._warm_programs)

    @in_setup_span("serving.setup.warm")
    def _warm_programs(self) -> int:
        import jax
        jnp = self._jnp
        S = self.scheduler.max_batch
        n = 0
        pad_meta, tabs, zs, samp = self._pad_tick_args()
        # jit keys its programs on the calling thread's config context:
        # under a caller's ``jax.default_device(...)`` (thread-local;
        # the engine thread has none) these calls would warm programs
        # the engine thread never runs, and its first real ticks would
        # load every tick program a second time
        with self._tick_lock, jax.default_device(None):
            def spec_meta(T):
                m = pad_meta(T)
                k = self._spec_k
                m.update(ver_idx=jnp.asarray(
                             np.zeros((S, 1 + k), np.int32)),
                         draft_tok=jnp.asarray(np.zeros((S, k),
                                                        np.int32)),
                         draft_len=jnp.asarray(zs))
                return m

            # mixed widths (the spans tick — verify program on a
            # speculative engine, tail/no-tail variants otherwise;
            # sampling state is part of EVERY program, so no per-
            # temperature variant exists to warm)
            for w in self._w_grid:
                T = S + w
                tok = jnp.asarray(np.zeros((T,), np.int32))
                if self._spec_k:
                    with setup_span("serving.setup.warm.program", tq=w,
                                    decode_tail=0, spec_k=self._spec_k):
                        self._step_tick(tok, spec_meta(T), tq=w,
                                        decode_tail=0, spec_k=self._spec_k)
                    n += 1
                else:
                    tails = {self._decode_block - 1, 0}
                    for tail in sorted(tails, reverse=True):
                        with setup_span("serving.setup.warm.program",
                                        tq=w, decode_tail=tail, spec_k=0):
                            self._step_tick(tok, pad_meta(T), tq=w,
                                            decode_tail=tail)
                        n += 1
            # width S: the fused block — the ONLY pure-decode program
            # since r16 (the single-step sampling tick is gone: its
            # traffic rides the block through the in-graph sampler)
            with setup_span("serving.setup.warm.program",
                            block=self._decode_block):
                self._step_block(jnp.asarray(zs), jnp.asarray(tabs), samp)
            n += 1
            # the calls above return at dispatch: the programs' first
            # runs end here
            with setup_span("serving.setup.warm.sync"):
                jax.block_until_ready((self._cur_tok_d, self._cache))  # noqa: PT002 — warm-up ends when the programs' first runs do
        return n

    def audit(self):
        """Standalone paged-KV invariant audit (serialized against
        ticks): returns the violation list — empty when healthy."""
        from ..analysis.kv_invariants import audit_serving_state
        with self._tick_lock:
            extra_refs, extra_pages = self._audit_extras()
            return audit_serving_state(
                self.pool, self.scheduler, self.prefix_cache,
                prefill_queue=tuple(self._prefill_q),
                extra_refs=extra_refs, extra_pages=extra_pages)

    def _geometry_desc(self) -> str:
        """One-line engine geometry for diagnostics: every raise and
        warning that names a violation also names the geometry that
        produced it, so reports from dead engines are actionable."""
        return (f"engine geometry: page_size={self.pool.page_size} "
                f"pages_per_slot={self.scheduler.pages_per_slot} "
                f"max_batch={self.scheduler.max_batch} "
                f"buckets={self._buckets} width_grid={self._w_grid} "
                f"prefill_chunk={self._chunk} "
                f"decode_block={self._decode_block} "
                f"spec_k={self._spec_k}")

    def _audit_or_raise(self) -> None:
        """Per-tick debug-mode check (caller holds the tick lock)."""
        from ..analysis.kv_invariants import (KVInvariantError,
                                              audit_serving_state)
        with self.tracer.span("serving.audit", track="engine.audit"):
            extra_refs, extra_pages = self._audit_extras()
            violations = audit_serving_state(
                self.pool, self.scheduler, self.prefix_cache,
                prefill_queue=tuple(self._prefill_q),
                extra_refs=extra_refs, extra_pages=extra_pages)
        if violations:
            self.metrics.inc("invariant_violations", len(violations))
            raise KVInvariantError(violations,
                                   context=self._geometry_desc())

    def defragment(self) -> int:
        """Compact live pages to the pool's low indices (the paged-KV
        defrag hook): rewrites the pool arrays + every live slot's table
        row, then commits the plan to the allocator. Returns the number
        of pages moved. Safe mid-generation (serialized against ticks)."""
        with self._tick_lock, \
                self.tracer.span("serving.defrag", track="engine.defrag"):
            self._drain_inflight("defragment")
            plan = self.pool.defrag_plan()
            if not plan:
                return 0
            if self._check_invariants:
                # closure check BEFORE anything is rewritten: the plan
                # must cover every live reference source (rows, page
                # lists, parked stashed rows, cached trie pages)
                from ..analysis.kv_invariants import (KVInvariantError,
                                                      audit_defrag_plan)
                bad = audit_defrag_plan(plan, self.pool, self.scheduler,
                                        self.prefix_cache)
                if bad:
                    raise KVInvariantError(
                        bad, context=self._geometry_desc())
            # every pool's pages move by the same plan, each along its
            # own page axis
            moved, tables = defrag_pools(
                plan, [(self._cache[p.name], p.page_axis)
                       for p in self._pools], self.scheduler.tables)
            self._cache.update(zip((p.name for p in self._pools), moved))
            # np.array (not asarray): the jnp result is a zero-copy
            # READ-ONLY view, and retire()/admit() write tables in place
            self.scheduler.tables = np.array(tables, np.int32)
            self.scheduler.remap_pages(plan)  # per-request page LISTS
            if self.prefix_cache is not None:
                self.prefix_cache.remap(plan)  # cached-node page ids
            # pending chunked-adopt pages are allocated (so the plan
            # covers them) but live only in the transfer entries —
            # remap those lists too or the eventual graft/scatter
            # would target stale ids
            for ent in self._adopts.values():
                ent["pages"] = [plan.get(p, p) for p in ent["pages"]]
            self.pool.commit_defrag(plan)
            if self._check_invariants:
                try:
                    self._audit_or_raise()
                except BaseException as e:
                    # defrag corrupted state: the caller gets the
                    # raise, the postmortem gets the geometry + plan
                    try:
                        self._write_postmortem(e)
                    except Exception:
                        pass    # a failing dump must not mask the error
                    raise
            return len(plan)

    # ----------------------------------------------------- observability ----
    def _copies_a_page(self, tq: int) -> int:
        """Copies a tick's attention launches of ``tq`` query rows a
        slot start for ONE live page, over the attention layers: from
        the geometry the kernel is launched with (the pool as it
        stands, lane-packed or not)."""
        if tq not in self._page_copies and self._family.page_copies:
            # another pool's kernel: the family says what it starts
            self._page_copies[tq] = int(self._family.page_copies(
                self._cfg, self._cache, self.scheduler.pages_per_slot, tq))
        if tq not in self._page_copies:
            from ..ops.pallas.ragged_paged_attention import page_copies
            pool = self._cache["k_pages"]
            layers, kv_heads, _, ps, dh = pool.shape
            self._page_copies[tq] = layers * page_copies(
                kv_heads, self.scheduler.pages_per_slot, ps, dh, pool.dtype,
                rows=tq * (self._cfg.num_attention_heads // kv_heads))
        return self._page_copies[tq]

    def _window_counts(self, launches) -> dict:
        """What the WINDOW layers' launches read and score, one layer's
        worth (a reader multiplies by ``window_layers``): over
        ``launches`` (``[(q_len, kv_len)]`` arrays of the slots with a
        query row), ``window_kv_tokens`` the keys a launch must read,
        ``min(kv_len, W - 1 + q_len)`` a slot, and ``window_attn_pairs``
        the (query token, key) pairs it scores, ``min(position + 1, W)``
        a row."""
        W = self._window
        tokens = pairs = 0
        for q, kv in launches:
            q, kv = q.astype(np.int64), kv.astype(np.int64)
            tokens += int(np.minimum(kv, W - 1 + q).sum())
            # rows whose position lies under the window see it all
            short = np.clip(W - 1 - (kv - q), 0, q)
            pairs += int((short * (kv - q + 1) + short * (short - 1) // 2
                          + (q - short) * W).sum())
        self.metrics.inc("window_kv_tokens", tokens)
        self.metrics.inc("window_attn_pairs", pairs)
        return dict(window_kv_tokens=tokens, window_attn_pairs=pairs)

    def _count_tick(self, rows: int, rows_real: int, kv_tokens: int,
                    walks, tq: int = 1, attn_pairs: int = 0,
                    q_lens=None) -> dict:
        """What a tick launches against what it needs, counted where
        the tick's arrays are built: into the counters (operators) and,
        returned, into the ``serving.tick`` span's args (the profiler's
        annotation then carries them for exactly the ticks traced).
        ``walks``: for each attention launch of the tick (the main
        step of ``tq`` query rows a slot, each fused step of one after
        it), the host array of the cache tokens its live slots attend.
        ``kv_pages / kv_pages_table`` is the share of a static walk
        over slots x table the launches needed; ``kv_page_copies`` the
        copies the kernel starts to walk them, all attention layers
        counted (over ``kv_pages`` x those layers: the copies a
        page). ``attn_pairs``: the (query token, key) pairs the
        launches score, ``q_len x (kv_len - (q_len - 1) / 2)`` a slot:
        what attention costs where it is bound by arithmetic, as
        ``kv_tokens`` is what it costs where it is bound by bytes."""
        ps = self.pool.page_size
        live_slots = sum(len(w) for w in walks)
        pages = [int((-(-w // ps)).sum()) for w in walks]
        kv_pages = sum(pages)
        copies = sum(n * self._copies_a_page(tq if i == 0 else 1)
                     for i, n in enumerate(pages))
        table = (len(walks) * self.scheduler.max_batch
                 * self.scheduler.pages_per_slot)
        self.metrics.inc("tick_rows", rows)
        self.metrics.inc("tick_rows_real", rows_real)
        self.metrics.inc("kv_tokens_attended", kv_tokens)
        self.metrics.inc("attn_score_pairs", attn_pairs)
        self.metrics.inc("tick_live_slots", live_slots)
        self.metrics.inc("kv_pages_walked", kv_pages)
        self.metrics.inc("kv_pages_table", table)
        self.metrics.inc("kv_page_copies", copies)
        if self._stateful:
            self.metrics.inc("slot_state_bytes_moved",
                             2 * live_slots * self._state_bytes_per_slot)
        window = {}
        if self._windowed:
            # ``q_lens``: the main launch's query rows a live slot; a
            # fused step after it brings one
            window = self._window_counts(
                [(np.ones_like(w) if i or q_lens is None else q_lens, w)
                 for i, w in enumerate(walks)])
        return dict(rows=rows, rows_real=rows_real, kv_tokens=kv_tokens,
                    attn_pairs=attn_pairs,
                    live_slots=live_slots, kv_pages=kv_pages,
                    kv_pages_table=table, kv_page_copies=copies,
                    **window, **self._tick_layers)

    def _record_tick(self, tk: _Tick, t1: float) -> None:
        """Per-tick evidence, at the tick's completion (caller holds the
        tick lock): slot-track spans for each live decoder and prefill
        span, from the tick's dispatch to its completion, plus one
        compact flight-recorder record with the tick's geometry and the
        live pool/queue gauges. Requests may have retired since the
        dispatch — only ids are used, never slot re-reads."""
        tick, t0, live, spans = tk.no, tk.m0, tk.live, tk.spans
        for slot, req, start, _ in spans:
            if start == req.cached_len:
                # the request's first chunk rode this tick: its wait
                # in the prefill queue, retroactive on the stamps the
                # observation uses (the span EQUALS it)
                self.metrics.observe("prefill_wait_s", t0 - req.admit_t)
                self.tracer.add("prefill.wait", f"slot{slot}",
                                req.admit_t, t0, req=req.id)
        if self.tracer.enabled:
            for slot, req in live:
                self.tracer.add("decode", f"slot{slot}", t0, t1,
                                req=req.id, tick=tick)
            for slot, req, start, take in spans:
                self.tracer.add("prefill.chunk", f"slot{slot}", t0, t1,
                                req=req.id, tick=tick, start=int(start),
                                tokens=int(take))
        self.flight.record_tick(
            tick=tick, t_mono_s=round(t0, 6), dur_s=round(t1 - t0, 6),
            live=len(live), prefill_spans=len(spans),
            span_tokens=int(sum(t for _, _, _, t in spans)),
            admitted=int(tk.admitted), queued=self.scheduler.queued(),
            occupancy=self.scheduler.occupancy,
            free_pages=self.pool.free_pages,
            prefill_queue_depth=len(self._prefill_q))

    def _write_postmortem(self, e: BaseException) -> str:
        """Dump the flight-recorder postmortem: the error (with the
        KV-invariant violation list when that is the killer), engine
        geometry + expected program inventory, the last-N tick records,
        the span-tracer window, a metrics snapshot, and the scheduler/
        PagePool/PrefixCache state at death. Returns the path written
        (also kept in ``self.postmortem_path``)."""
        slots = []
        for slot, req in enumerate(self.scheduler.slots):
            if req is None:
                continue
            slots.append({
                "slot": slot, "req": req.id, "state": req.state,
                "length": int(self.scheduler.lengths[slot]),
                "prefilling": bool(req.prefilling),
                "chunk_done": int(req.chunk_done),
                "cached_len": int(req.cached_len),
                "private_pages": len(req.pages),
                "prefix_pages": len(req.prefix_nodes),
                "row": self.scheduler.effective_row(slot).tolist(),
            })
        state = {
            "slots": slots,
            "queued": self.scheduler.queued(),
            "prefill_queue": [req.id for _, req in self._prefill_q],
            "pool": {"total_pages": self.pool.total_pages,
                     "page_size": self.pool.page_size,
                     "free_pages": self.pool.free_pages},
        }
        if self.prefix_cache is not None:
            state["prefix_cache"] = self.prefix_cache.stats()
        lt = get_tracer()
        if lt is not None:
            # runtime acquisition graph + wait/hold aggregates: which
            # lock the dying engine was living under (locktrace.py)
            state["lock_trace"] = lt.report()
        spans = [s.to_dict() for s in self.tracer.spans()] \
            if self.tracer.enabled else None
        self.postmortem_path = self.flight.dump(
            dir=self._flight_dir, error=e,
            geometry=self._geometry_desc(),
            programs=self.program_inventory, state=state, spans=spans,
            metrics=self.metrics.snapshot(),
            sentinel=(self.sentinel.report()
                      if self.sentinel is not None else None))
        return self.postmortem_path

    # ------------------------------------------------------------ worker ----
    def _sampling_arrays(self):
        """The fused sampler's per-slot DATA (r16): temperature /
        top_p / top_k from each occupied slot's request, the constant
        per-slot PRNG key, and the produced-token count that keys each
        draw (the DISPATCHED count, emitted + in flight: the index the
        token this launch emits has in its stream). Passed with EVERY
        tick (greedy slots carry temp 0 and take the bitwise argmax
        path in-graph), so sampling is never a different program. The composition-dependent arrays
        (params + keys) change only at admission/retirement, so they
        are cached on-device and rebuilt on invalidation (``_park`` /
        ``_retire``); only ``produced`` uploads per tick — the hot
        path pays one tiny transfer, not five."""
        jnp = self._jnp
        if self._samp_cache is None:
            S = self.scheduler.max_batch
            temp = np.zeros((S,), np.float32)
            top_p = np.ones((S,), np.float32)
            top_k = np.zeros((S,), np.int32)
            for slot, req in enumerate(self.scheduler.slots):
                if req is None:
                    continue
                temp[slot] = req.temperature
                top_p[slot] = req.top_p
                top_k[slot] = req.top_k
            self._samp_cache = dict(
                temp=jnp.asarray(temp), top_p=jnp.asarray(top_p),
                top_k=jnp.asarray(top_k),
                key=jnp.asarray(self._key_data))
        return dict(self._samp_cache,
                    produced=jnp.asarray(
                        self._produced.astype(np.int32)))

    def _emit(self, slot: int, req: Request, tok: int) -> bool:
        """Stream one token; returns True when the request just
        finished (EOS or max_new_tokens)."""
        now = time.monotonic()
        if req.first_token_t is None:
            req.first_token_t = now
            self.metrics.observe("ttft_s", now - req.submit_t)
            # retroactive span on the SAME timestamps as the metric
            # observation: the exported TTFT span and the ttft_s
            # histogram reconcile exactly (same monotonic clock)
            self.tracer.add("ttft", f"slot{slot}", req.submit_t, now,
                            req=req.id)
        req.tokens.append(tok)
        req.stream.put(tok)
        self._emitted[slot] += 1
        self.metrics.inc("tokens_out")
        done = (self._emitted[slot] >= req.max_new_tokens
                or (req.eos_token_id is not None
                    and tok == req.eos_token_id))
        return bool(done)

    def _retire(self, slot: int, state: str) -> None:
        def record(req):
            # before the handle completes: a caller that exports the
            # trace (or reads the counters) right after result()
            # returns must find this request in both
            self.metrics.inc({COMPLETED: "completed",
                              CANCELLED: "cancelled",
                              TIMED_OUT: "timed_out"}[state])
            # whole-lifecycle span, submit -> retirement, on the slot
            # track
            self.tracer.add("request", f"slot{slot}", req.submit_t,
                            req.finish_t, req=req.id, state=state,
                            tokens=len(req.tokens))

        # the slot's token on the device stays as it is: the next
        # occupant prefills first, and completing its prompt overwrites
        # it before any decode row reads it
        self.scheduler.retire(slot, state, before_finish=record)
        self._produced[slot] = 0
        self._emitted[slot] = 0
        self._key_data[slot] = 0
        self._samp_cache = None

    def _emit_toks(self, slot: int, req: Request, toks_row,
                   j0: int, j1: int) -> None:
        """Emit ``toks_row[j0:j1]`` (fused block/tail/verify tokens —
        greedy or in-graph-sampled) for (slot, req), retiring at the
        first completion — remaining tokens are discarded (their KV
        landed on the trash page or past the length, and discarded
        sampled tokens burn no key state: draws are keyed by
        continuation index, so the next launch re-draws them
        identically)."""
        for j in range(j0, j1):
            if self._emit(slot, req, int(toks_row[j])):
                self._retire(slot, COMPLETED)
                break

    # ----------------------------------------------------------- prefill ----
    def _park(self, slot: int, req: Request) -> None:
        """Admission: every request's uncached suffix is absorbed by the
        per-tick ragged program — park the slot until its prompt is
        fully cached. The real table row moves onto the request and the
        scheduler row goes all-TRASH (length stays 0): the parked slot
        is DEAD to the fused block program (its writes land on the
        trash page) while each tick's ragged metadata addresses the
        stashed real row directly."""
        if req.cached_len:
            self.metrics.inc("prefix_hits")
            self.metrics.inc("prefix_hit_tokens", req.cached_len)
            self.metrics.inc("prefix_pages_saved", len(req.prefix_nodes))
        elif self.prefix_cache is not None:
            self.metrics.inc("prefix_misses")
        elif self._stateful:
            self.metrics.inc("prefix_bypassed_stateful")
        elif self._windowed:
            self.metrics.inc("prefix_bypassed_window")
        req.prefilling = True
        req.chunk_done = 0
        req.table_row = self.scheduler.tables[slot].copy()
        self.scheduler.tables[slot, :] = PagePool.TRASH
        # the slot's constant sampling key (fused sampler, r16); the
        # tick folds each token's continuation index in, so this never
        # advances host-side. Built as raw threefry key DATA —
        # [0, seed & 0xffffffff], bit-identical to
        # jax.random.PRNGKey(seed) under the default (x64-off) config
        # for negative and >32-bit seeds too (pinned by test; the mask
        # runs on the PYTHON int — np.uint64(-1) raises on NumPy 2) —
        # because a jax call here would put a jit dispatch + device
        # sync on the admission path (measured as a real
        # engine-throughput hit on admission-heavy traffic)
        self._key_data[slot] = (0, req.seed & 0xffffffff)
        self._samp_cache = None
        self._prefill_q.append((slot, req))

    def _collect_spans(self):
        """The tick's prefill work: FIFO over parked requests, capped at
        the per-tick token budget. Returns [(slot, req, start, take)];
        advances no state (the tick's dispatch does: ``chunk_done``
        says what is computed or in flight). A later request only gets
        budget once every earlier one's span completed its prompt, so
        finishing spans are always a prefix of the queue."""
        while self._prefill_q:          # drop entries retired by sweeps
            slot, req = self._prefill_q[0]
            if self.scheduler.slots[slot] is req and req.prefilling:
                break
            self._prefill_q.popleft()
        spans, left = [], self._budget
        for slot, req in self._prefill_q:
            if left <= 0:
                break
            if self.scheduler.slots[slot] is not req or not req.prefilling:
                continue
            remaining = req.prompt.size - req.cached_len - req.chunk_done
            take = min(remaining, left)
            if take <= 0:
                continue
            spans.append((slot, req, req.cached_len + req.chunk_done,
                          take))
            left -= take
            if take < remaining:
                break                   # budget exhausted mid-prompt
        return spans

    def _join_decode(self, slot: int, req: Request, tail: int) -> None:
        """A prompt's completion, the half taken at the DISPATCH of the
        tick that computes its last span: the real row re-installed and
        the slot in the decode batch, so the next tick carries its
        decode row (whose token the device holds: this tick's
        ``cur_tok`` successor)."""
        if self._prefill_q and self._prefill_q[0][1] is req:
            self._prefill_q.popleft()
        req.prefilling = False
        self.scheduler.tables[slot, :] = req.table_row
        req.table_row = None
        self.scheduler.lengths[slot] = req.prompt.size - 1
        self._advance(slot, req, 1 + tail)

    def _advance(self, slot: int, req: Request, steps: int) -> None:
        """``steps`` decode steps of ``slot`` dispatched: the tokens
        they produce and the KV they land, both counted only as far as
        the request goes (a fused block runs on past ``max_new_tokens``
        onto the trash page; the length stays inside the funded row)."""
        n = min(steps, req.max_new_tokens - int(self._produced[slot]))
        self.scheduler.lengths[slot] += n
        self._produced[slot] += n

    def _register_prompt(self, req: Request) -> None:
        """A prompt's completion, the half taken at that tick's
        COMPLETION (its slot still holding ``req``), before the first
        token is emitted: the prompt's full pages registered in the
        prefix cache and the chain-completion event."""
        n = req.prompt.size
        if self.prefix_cache is not None:
            new_full = n // self.pool.page_size - len(req.prefix_nodes)
            if new_full > 0:
                adopted, dup = self.prefix_cache.insert(
                    req.prompt, req.prefix_nodes, req.pages[:new_full])
                req.prefix_nodes = req.prefix_nodes + adopted
                req.pages = dup + req.pages[new_full:]
            # chain-completion event: this prefill just registered /
            # extended a prefix chain — surface its cumulative page
            # fingerprints so a fleet policy can hand the chain to a
            # decode-pool worker. Fingerprints are recomputed from the
            # PROMPT (not req.prefix_nodes — dedup can make the node
            # list skip chain nodes). Tick lock is held: the hook must
            # stay cheap (the fleet worker just enqueues an event).
            if self.on_chain_complete is not None:
                ps = self.pool.page_size
                n_pages = n // ps
                if n_pages > 0:
                    fp, fps = 0, []
                    for i in range(n_pages):
                        fp = _fp_extend(
                            fp, req.prompt[i * ps:(i + 1) * ps])
                        fps.append(fp)
                    try:
                        self.on_chain_complete(req, {
                            "fp": fps[-1], "fps": fps,
                            "pages": n_pages, "prompt_tokens": int(n)})
                    except Exception:
                        pass    # policy failure must not kill the tick

    # ------------------------------------------------------- speculation ----
    def _collect_drafts(self, live):
        """The tick's draft side (host, model-free by default): ask the
        drafter for up to ``policy.budget(...)`` next tokens per live
        slot — SAMPLING slots included since r16: the verify pass
        draws the target's own sampled token at every span position
        (same fold_in key a plain tick would use), accepts while the
        draft matches it, and the emitted stream stays bitwise the
        non-speculative engine's; low acceptance on an unpredictable
        sampled stream just degrades the slot to plain decode through
        the ordinary acceptance EWMA. Returns ``{slot: int32[k_s]}``
        with ``1 <= k_s <= spec_k``; slots with no entry decode
        plainly this tick. Drafting never blocks correctness — an
        arbitrarily wrong draft only costs the wasted span rows
        (verification emits the target's own tokens)."""
        drafts = {}
        t0 = time.monotonic()
        # a drafter that declares its history window (NGramDrafter
        # does) gets only that tail — rebuilding the FULL
        # prompt+generated array per slot per tick would be O(produced)
        # host work on the hot path for long generations; drafters
        # without the attribute keep the whole-history contract
        window = getattr(self._drafter, "max_history", None)
        for slot, req in live:
            remaining = req.max_new_tokens - int(self._produced[slot]) - 1
            k = self._spec_policy.budget(req, remaining)
            if k <= 0:
                continue
            toks = req.tokens if window is None else req.tokens[-window:]
            parts = [np.asarray(toks, np.int32)]
            if window is None or len(toks) < window:
                need = None if window is None else window - len(toks)
                parts.insert(0, req.prompt if need is None
                             else req.prompt[-need:])
            hist = np.concatenate(parts)
            d = np.asarray(self._drafter.propose(hist, k),
                           np.int32).reshape(-1)[:k]
            if d.size:
                drafts[slot] = d
        if drafts and self.tracer.enabled:
            self.tracer.add(
                "serving.draft", "engine.draft", t0, time.monotonic(),
                slots=len(drafts),
                tokens=int(sum(d.size for d in drafts.values())))
        return drafts

    # -------------------------------------------------------------- tick ----
    def _ragged_tick(self, ph, live, spans, tail: int = 0,
                     drafts=None) -> _Tick:
        """DISPATCH one serving_tick call covering every live slot's
        decode token plus the collected prompt spans; returns the
        pending ``_Tick`` (``_complete`` reads it back). Geometry is
        data: the program compiles once per packed width (S when no
        prefill work is pending, S + the smallest width-grid entry
        covering the span tokens otherwise). ``tail`` fuses that many
        extra decode steps into the same program for tail-live slots —
        decoding slots plus spans COMPLETING their prompt this tick —
        so an admission tick still produces a full decode block for
        in-flight streams (mid-prefill slots sit the tail out on the
        trash page). Since r16 sampling slots ride the tail too: token
        selection is the in-graph fused sampler, per-slot params and
        keys are meta DATA.

        A decode row's input token is a sentinel (-1): the program takes
        it from the slots' current tokens on the device, where the tick
        before left it — the host has not read that tick back yet.

        What the next tick's build needs is advanced HERE, before the
        launch, from what the tick will do whatever its tokens are: a
        decode row's ``lengths`` and ``_produced`` by ``1 + tail``, a
        span's ``chunk_done``, a completing prompt's place in the decode
        batch (``_join_decode``). A drafted slot advances by what the
        verify pass accepts, which only completion knows (a drafter
        engine completes each tick before it builds the next).

        ``drafts`` (``{slot: draft tokens}``, speculative engines only)
        turns drafted slots into ordinary ragged SPANS: current token
        plus the drafts, written-then-attended exactly like a prefill
        chunk, with the verify/acceptance outputs computed in-graph
        (``spec_k`` mode of ``serving_tick``). Any tick carrying spans
        or drafts on a speculative engine runs the ONE verify program
        for its width — prefill-only ticks included — which is what
        keeps the per-bucket program count at 1 there.

        ``ph``: the iteration's ``Phases`` (``_loop``), in ``build`` on
        entry; the tick moves it through ``dispatch`` and stops it
        there (the next phase entered starts at that boundary)."""
        jnp = self._jnp
        S = self.scheduler.max_batch
        ps = self.pool.page_size
        pps = self.scheduler.pages_per_slot
        drafts = drafts or {}
        # speculative engines route every span-carrying tick through
        # the verify program (one program per mixed width); draft-less
        # pure-decode ticks run the fused block instead (_dispatch_tick)
        spec = self._spec_k if (drafts or spans) else 0
        if spec:
            tail = 0    # speculation replaces the fused greedy tail
        span_tok = (sum(take for _, _, _, take in spans)
                    + sum(1 + d.size for d in drafts.values()))
        width = next((w for w in self._w_grid if w >= span_tok),
                     self._w_grid[-1]) if span_tok else 0
        T = S + width
        tq = max(width, 1)
        tok = np.zeros((T,), np.int32)
        tok_slot = np.full((T,), S, np.int32)   # S = padding sentinel
        tok_pos = np.zeros((T,), np.int32)
        tok_qoff = np.zeros((T,), np.int32)
        q_len = np.zeros((S,), np.int32)
        kv_len = np.zeros((S,), np.int32)
        last = np.zeros((S,), np.int32)
        tail_live = np.zeros((S,), bool)
        tabs = np.stack([self.scheduler.effective_row(s)
                         for s in range(S)]).astype(np.int32)
        for slot, req in live:
            if slot in drafts:
                continue    # rides the span region below
            tok[slot] = -1                  # the device's cur_tok[slot]
            tok_slot[slot] = slot
            tok_pos[slot] = self.scheduler.lengths[slot]
            q_len[slot] = 1
            kv_len[slot] = self.scheduler.lengths[slot] + 1
            last[slot] = slot
            tail_live[slot] = True
        idx = S
        spec_rows = []                      # (slot, idx0, k_s)
        for slot, req in live:
            d = drafts.get(slot)
            if d is None:
                continue
            k_s = int(d.size)
            p0 = int(self.scheduler.lengths[slot])
            tok[idx] = -1
            tok[idx + 1: idx + 1 + k_s] = d
            tok_slot[idx: idx + 1 + k_s] = slot
            tok_pos[idx: idx + 1 + k_s] = np.arange(p0, p0 + 1 + k_s)
            tok_qoff[idx: idx + 1 + k_s] = np.arange(1 + k_s)
            q_len[slot] = 1 + k_s
            kv_len[slot] = p0 + 1 + k_s
            last[slot] = idx + k_s
            tail_live[slot] = True
            spec_rows.append((slot, idx, k_s))
            idx += 1 + k_s
        for slot, req, start, take in spans:
            tok[idx:idx + take] = req.prompt[start:start + take]
            tok_slot[idx:idx + take] = slot
            tok_pos[idx:idx + take] = np.arange(start, start + take)
            tok_qoff[idx:idx + take] = np.arange(take)
            q_len[slot] = take
            kv_len[slot] = start + take
            last[slot] = idx + take - 1
            tail_live[slot] = start + take >= req.prompt.size
            idx += take
        if not tail_live.any():
            tail = 0    # nobody would advance — skip the tail variant
        # page/offset per packed token (padding -> trash page)
        real = tok_slot < S
        page_i = np.minimum(tok_pos // ps, pps - 1)
        tok_page = np.where(
            real & (tok_pos // ps < pps),
            tabs[np.minimum(tok_slot, S - 1), page_i], PagePool.TRASH)
        tok_off = np.where(real, tok_pos % ps, 0).astype(np.int32)
        meta = dict(tok_slot=jnp.asarray(tok_slot),
                    tok_pos=jnp.asarray(tok_pos),
                    tok_page=jnp.asarray(tok_page.astype(np.int32)),
                    tok_off=jnp.asarray(tok_off),
                    tok_qoff=jnp.asarray(tok_qoff),
                    q_len=jnp.asarray(q_len), kv_len=jnp.asarray(kv_len),
                    last=jnp.asarray(last), tables=jnp.asarray(tabs),
                    tail_live=jnp.asarray(tail_live),
                    **self._sampling_arrays())
        if spec:
            # verify geometry: per-slot span-position indices + drafts
            # (all DATA — non-speculating slots point at `last`, so
            # their row 0 is the plain tick's logits/argmax)
            ver_idx = np.tile(last[:, None], (1, 1 + spec)).astype(
                np.int32)
            draft_tok = np.zeros((S, spec), np.int32)
            draft_len = np.zeros((S,), np.int32)
            for slot, idx0, k_s in spec_rows:
                ver_idx[slot, :1 + k_s] = np.arange(idx0, idx0 + 1 + k_s)
                ver_idx[slot, 1 + k_s:] = idx0 + k_s
                draft_tok[slot, :k_s] = drafts[slot]
                draft_len[slot] = k_s
            meta.update(ver_idx=jnp.asarray(ver_idx),
                        draft_tok=jnp.asarray(draft_tok),
                        draft_len=jnp.asarray(draft_len))
        # what the launch carries against what it needs: T rows and,
        # per fused tail step, S more; of them the live decoders',
        # drafts' and spans' tokens; the cache tokens those attend
        # (tail step j attends j more per tail-live slot)
        n_tail = int(tail_live.sum())
        # a fused step is one row a tail-live slot: its cache tokens
        # are its (query token, key) pairs too
        tail_tokens = (tail * int(kv_len[tail_live].sum())
                       + n_tail * tail * (tail + 1) // 2)
        counts = self._count_tick(
            T + S * tail, int(real.sum()) + n_tail * tail,
            int(kv_len[q_len > 0].sum()) + tail_tokens,
            [kv_len[q_len > 0]] + [kv_len[tail_live] + j
                                   for j in range(1, tail + 1)], tq=tq,
            attn_pairs=int((q_len.astype(np.int64) * (
                2 * kv_len.astype(np.int64) - q_len + 1) // 2).sum())
            + tail_tokens, q_lens=q_len[q_len > 0])
        # the state the NEXT build reads, advanced before the launch
        for slot, req in live:
            if slot not in drafts:
                self._advance(slot, req, 1 + tail)
        for slot, req, start, take in spans:
            req.chunk_done += take
            if tail_live[slot]:
                self._join_decode(slot, req, tail)
        tk = self._new_tick(live, spans, drafts, tail)
        tok_d = jnp.asarray(tok)
        t_build = ph.stop()
        with self.tracer.span("serving.tick", track="engine.decode",
                              tick=tk.no, width=int(width),
                              live=len(live), span_tokens=int(span_tok),
                              tail=int(tail), spec=len(spec_rows),
                              **counts):
            ph.enter("serving.phase.dispatch", at=t_build)
            if spec:
                # toks [S, 1+spec_k] i32 + accept [S] i32
                (toks_d, accept_d, _logits_d), tk.counts = self._step_tick(
                    tok_d, meta, tq=tq, decode_tail=0, spec_k=spec)
                tk.outs = (toks_d, accept_d)
            else:
                # toks [S] (tail=0) or [S, 1+tail] i32: sampling
                # happens IN-GRAPH (r16), so no [S, V] logits row ever
                # crosses to the host
                (toks_d, _logits_d), tk.counts = self._step_tick(
                    tok_d, meta, tq=tq, decode_tail=tail)
                tk.outs = (toks_d,)
            ph.stop()
        return tk

    def _block_tick(self, ph, live) -> _Tick:
        """DISPATCH the fast path when no prefill work is pending:
        ``num_steps`` fused decode ticks in one program — token
        selection is in-graph (argmax for greedy slots, the fused
        temperature/top-k/top-p sampler for sampling ones, r16), so
        the device→host pull is [S, k] i32 tokens and NO [S, V] f32
        logits row ever crosses, whoever is sampling. Fused ticks
        always run the FULL block — capping at the remaining tokens
        would compile one program per distinct cap; at worst K-1 cheap
        steps run past the last retirement and their tokens are
        discarded (budget overruns land on the trash page, and
        discarded sampled tokens burn no key state). The slots' input
        tokens are the device's own (``_cur_tok_d``); a slot that is
        not in ``live`` (free, or ending by count with the tick in
        flight) enters with length 0 and is dead to the block."""
        jnp = self._jnp
        k = self._decode_block
        # S rows a step, the live slots' real; step j attends the
        # slot's length + j cache tokens
        rows = [slot for slot, _ in live]
        lens = self.scheduler.lengths[rows]
        kv_tokens = k * int(lens.sum()) + len(live) * k * (k + 1) // 2
        counts = self._count_tick(
            self.scheduler.max_batch * k, len(live) * k, kv_tokens,
            [lens + j for j in range(1, k + 1)],
            attn_pairs=kv_tokens)   # one query row a step: a pair a token
        lengths = np.zeros_like(self.scheduler.lengths)
        lengths[rows] = lens
        lengths_d = jnp.asarray(lengths)
        tables_d = jnp.asarray(self.scheduler.tables)
        sampling = self._sampling_arrays()
        for slot, req in live:              # the block's KV, as launched
            self._advance(slot, req, k)
        tk = self._new_tick(live, [], {}, k - 1)
        t_build = ph.stop()
        with self.tracer.span("serving.tick", track="engine.decode",
                              tick=tk.no, kind="block",
                              live=len(live), steps=k, **counts):
            ph.enter("serving.phase.dispatch", at=t_build)
            toks_d, tk.counts = self._step_block(lengths_d, tables_d,
                                                  sampling)
            tk.outs = (toks_d,)
            ph.stop()
        return tk

    def _new_tick(self, live, spans, drafts, tail: int) -> _Tick:
        """The pending record of the tick being dispatched, numbered."""
        ahead = self._inflight is not None
        if ahead:
            self.metrics.inc("ticks_ahead")
        tk = _Tick(self._tick_no, live, spans, drafts, tail, ahead)
        self._tick_no += 1
        return tk

    def _dispatch_tick(self, ph, live, spans) -> _Tick:
        """Tick dispatch (r16 — sampling is DATA, so temperature never
        picks a program): the fused block when the tick is pure
        decode, else the ragged one-program tick with the fused decode
        tail. Only live decoders and spans COMPLETING their prompt
        this tick gate the tail — mid-prefill spans sit it out on the
        trash page (``tail_live``). The pre-r16 width-S single-step
        sampling program and the sampling-disables-the-tail rule are
        both gone: SAMPLING slots ride the block/tail through the
        in-graph fused sampler.

        Speculative engines add one branch on top: any tick with
        drafts or prefill spans runs the verify program (drafted slots
        as ragged spans, everything else riding along); a tick with
        neither falls through to the plain paths — live slots whose
        acceptance degraded them to k=0 still get the fused block, so
        'speculation off' is a per-slot data state, not a different
        program set."""
        if self._drafter is not None:
            drafts = self._collect_drafts(live)
            if drafts or spans:
                return self._ragged_tick(ph, live, spans, 0, drafts)
        if not spans:
            return self._block_tick(ph, live)
        return self._ragged_tick(ph, live, spans, self._decode_block - 1)

    def _complete(self, tk: _Tick, ph=None) -> None:
        """COMPLETE a dispatched tick (caller holds the tick lock):
        read its tokens back — the one blocking pull; with another tick
        in flight the device is already working on that one — then do
        what clients see, for every row whose slot still holds the
        request the row was launched for: ``req.tokens``, the stream,
        ``first_token_t``, retirement at EOS / ``max_new_tokens``, a
        completed prompt's registration. A row whose request has ended
        since the dispatch (an EOS the tick before found, a cancel, a
        deadline) is dropped: its tokens are discarded, its KV writes
        landed in pages that were the request's own at dispatch, and
        the device ran them before anything launched later. ``ph``:
        the engine thread's ``Phases``, stopped after ``dispatch``;
        moved through ``readback`` and left in ``emit`` (None: another
        thread completes the tick under the tick lock)."""
        if ph is not None:
            ph.enter("serving.phase.readback")
        toks = np.asarray(tk.outs[0])  # noqa: PT005 - THE sanctioned per-tick token read-back ([S], [S, 1+tail] or [S, k] i32)
        accept = (np.asarray(tk.outs[1])  # noqa: PT005 - rides the same sync (verify tick: [S] i32)
                  if len(tk.outs) > 1 else None)
        if tk.counts is not None:
            # the family's counts ride the same sync: no pull of their own
            for name, n in zip(self._tick_counters,
                               np.asarray(tk.counts)):  # noqa: PT005 - rides the token read-back ([n] i32)
                self.metrics.inc(name, int(n))
        host_sync("serving.tick.readback")
        if ph is not None:
            ph.enter("serving.phase.emit")
        now = time.perf_counter()
        m1 = time.monotonic()
        if toks.ndim == 1:
            toks = toks[:, None]
        tail, drafts = tk.tail, tk.drafts
        if tk.live:
            # the pace a stream feels: from the completion before this
            # one — or, for a tick launched with nothing in flight,
            # from its own dispatch — a decode step
            start = (self._last_done_t
                     if tk.ahead and self._last_done_t is not None
                     else tk.t0)
            self.metrics.inc("decode_steps", 1 + tail)
            self.metrics.observe("decode_step_s",
                                 (now - start) / (1 + tail))
        self._last_done_t = now
        if drafts:
            self.metrics.inc("spec_ticks")
        overrun = 0
        for slot, req in tk.live:
            if self.scheduler.slots[slot] is not req:
                overrun += 1
                continue
            d = drafts.get(slot)
            if d is None:
                # token 0: in-graph argmax OR fused sample; 1..tail
                # the fused steps'
                self._emit_toks(slot, req, toks[slot], 0, 1 + tail)
                continue
            # speculative slot: 1 + accept tokens from this ONE
            # launch (verified prefix + the bonus/correction
            # token); rejected draft KV stays past the advanced
            # length — masked by kv_len until real tokens
            # positionally overwrite it (no device-side rollback)
            k_s = int(d.size)
            a = int(accept[slot])
            self._advance(slot, req, 1 + a)
            self.metrics.inc("draft_tokens", k_s)
            self.metrics.inc("draft_accepted", a)
            self.metrics.inc("draft_rejected", k_s - a)
            self.metrics.observe("spec_accept_rate", a / k_s)
            self._spec_policy.update(req, k_s, a)
            if self.tracer.enabled:
                self.tracer.add("spec.verify", f"slot{slot}", tk.m0, m1,
                                req=req.id, drafted=k_s, accepted=a)
                if k_s > a:
                    self.tracer.add("spec.rollback", f"slot{slot}",
                                    m1, m1, req=req.id,
                                    rejected=k_s - a)
            self._emit_toks(slot, req, toks[slot], 0, a + 1)
        for slot, req, start, take in tk.spans:
            self.metrics.inc("prefill_chunks")
            if start + take < req.prompt.size:
                continue
            self.metrics.inc("prefills")
            if self.scheduler.slots[slot] is not req:
                overrun += 1
                continue
            self._register_prompt(req)
            # the completing slot rode the tail too: its first 1+tail
            # tokens landed in this same program
            self._emit_toks(slot, req, toks[slot], 0, 1 + tail)
        if overrun:
            self.metrics.inc("overrun_slot_ticks", overrun)
        self._record_tick(tk, m1)

    def _drain_inflight(self, reason: str, ph=None) -> None:
        """Complete the tick in flight, if any, before its time (caller
        holds the tick lock): what rewrites pool, tables or trie, a
        drafter that reads ``req.tokens``, an engine with nothing more
        to launch and ``close`` all want the host's state and the
        device's in step."""
        tk, self._inflight = self._inflight, None
        if tk is None:
            return
        self.metrics.inc("inflight_drains")
        self.metrics.inc_labeled("inflight_drains", reason=reason)
        t0 = time.monotonic()
        self._complete(tk, ph)
        self.tracer.add("serving.drain", "engine.drain", t0,
                        time.monotonic(), reason=reason, tick=tk.no)

    def _sweep(self, now: float) -> None:
        """Apply cancellations + deadlines to queued and occupied
        (decoding OR mid-prefill) requests."""
        for r in self.scheduler.drop_queued(
                lambda r: r.cancel_flag or r.expired(now)):
            state = CANCELLED if r.cancel_flag else TIMED_OUT
            r.finish(state)
            self.metrics.inc("cancelled" if r.cancel_flag else "timed_out")
        for slot, req in self.scheduler.occupied():
            if req.cancel_flag:
                self._retire(slot, CANCELLED)
            elif req.expired(now):
                self._retire(slot, TIMED_OUT)

    def _loop(self) -> None:
        try:
            with self._tick_lock:
                tick_no = self._tick_no
            while True:
                # the engine thread's time, cut into five contiguous
                # phases (track engine.phase; kept only if the
                # iteration ticks). Waiting for the tick lock is
                # admission's: it is host time before the next launch.
                ph = self.tracer.phases("engine.phase", tick=tick_no)
                ph.enter("serving.phase.admit")
                with self._tick_lock:
                    now = time.monotonic()
                    self._sweep(now)
                    if self._closing and not self._drain:
                        ph.end(keep=False)
                        break
                    if self._closing and self._hand_back:
                        # hand-back drain (fleet protocol): admission
                        # stops NOW — queued requests go back to the
                        # caller un-finalized for re-dispatch, while
                        # in-flight slots below run to completion
                        handed = self.scheduler.drop_queued(
                            lambda r: True)
                        if handed:
                            self._returned.extend(handed)
                            self.metrics.inc("handed_back", len(handed))
                    if self._cold is not None \
                            and self.scheduler.queued():
                        # admission may spill an evicted chain, the
                        # rewarm scatters one: pages move, so the host
                        # and the device first come in step
                        self._drain_inflight("migrate")
                        if len(self._cold):
                            # cold-tier rewarm BEFORE admission: a
                            # queued prompt whose warm match ends where
                            # a spilled chain begins re-adopts those
                            # pages now, so _try_reserve sees them as a
                            # warm hit
                            self._rewarm_cold()
                    t_adm = time.monotonic()
                    admitted = self.scheduler.admit()
                    if admitted:
                        # recorded only when work happened: an idle
                        # engine polls admission every 50ms and must
                        # not slowly flush real spans out of the ring
                        self.tracer.add("serving.admission",
                                        "engine.admission", t_adm,
                                        time.monotonic(),
                                        admitted=len(admitted))
                    for slot, req in admitted:
                        self.metrics.inc("admitted")
                        self.metrics.observe("queue_wait_s",
                                             req.admit_t - req.submit_t)
                        # queue-wait span, retroactive on the request's
                        # own submit/admit stamps (== the observation)
                        self.tracer.add("queue", f"slot{slot}",
                                        req.submit_t, req.admit_t,
                                        req=req.id,
                                        prompt=int(req.prompt.size),
                                        cached=int(req.cached_len))
                        self._park(slot, req)
                    ph.enter("serving.phase.build")
                    spans = self._collect_spans()
                    # built from the PREDICTED state: a request that
                    # reaches max_new_tokens with the tick in flight
                    # gets no row (known by counting; only an EOS is
                    # found a tick late)
                    live = [(slot, req)
                            for slot, req in self.scheduler.live()
                            if self._produced[slot] < req.max_new_tokens]
                    self.metrics.observe("batch_occupancy",
                                         self.scheduler.occupancy)
                    self.metrics.observe("page_utilization",
                                         self.pool.utilization)
                    self.metrics.observe("chunk_queue_depth",
                                         len(self._prefill_q))
                    prev = self._inflight
                    ticked = prev is not None or bool(live) or bool(spans)
                    if live or spans:
                        if self._ready_t is None:
                            self._ready_t = now
                        # inter-decode-tick stall: everything since the
                        # last tick's completion (host work, metadata
                        # builds) shows up as this gap; with a tick in
                        # flight the device works through it. Prefill
                        # spans ride INSIDE the tick, budget-bounded,
                        # instead of stalling between ticks.
                        t = time.perf_counter()
                        if live and self._last_decode_t is not None:
                            self.metrics.observe(
                                "decode_stall_s",
                                t - self._last_decode_t)
                        # dispatch tick N+1, THEN complete tick N: its
                        # read-back returns while N+1 runs
                        self._inflight = self._dispatch_tick(
                            ph, live, spans)
                        self._inflight.admitted = len(admitted)
                        if prev is not None:
                            self._complete(prev, ph)
                        if self._depth == 0:
                            # in step: the next build reads what this
                            # tick emits (a drafter: ``req.tokens``)
                            self._drain_inflight(
                                "drafter" if self._drafter is not None
                                else "step", ph)
                        self._last_decode_t = (time.perf_counter()
                                               if live else None)
                    else:
                        # nothing to launch: the engine runs empty
                        self._drain_inflight("empty", ph)
                        self._last_decode_t = None
                    if ticked and self._check_invariants:
                        self._audit_or_raise()
                    phases = ph.end(keep=ticked)
                    tick_no = self._tick_no     # for the next iteration
                if ticked:
                    self._observe_phases(phases)
                    # pace OUTSIDE the tick lock: sleeping inside it
                    # starves defragment() (python locks are unfair)
                    if self._tick_interval:
                        time.sleep(self._tick_interval)
                    continue
                # idle: nothing live — wait for work or shutdown
                with self._cond:
                    if self.scheduler.queued():
                        continue
                    if self._closing:
                        break
                    self._cond.wait(timeout=0.05)
        except BaseException as e:  # fail every caller, then surface
            self._dead = e
            try:
                # the postmortem snapshots PRE-failure state, so it
                # must be written before _fail_all retires everything —
                # and under the tick lock (released when the raise
                # unwound the with-block): a caller blocked in
                # defragment() must not rewrite pool/rows/trie while
                # the dump walks them
                with self._tick_lock:
                    self._settle_inflight()
                    self._write_postmortem(e)
            except Exception:
                pass        # a failing dump must not mask the error
            with self._tick_lock:
                self._fail_all(e)
            raise
        finally:
            # post-drain (or cancel-close): flush whatever remains —
            # under the tick lock: snapshot()/gauges()/defragment()
            # callers may still be mid-read, and the teardown rewrites
            # the very slot/table/trie state they walk
            with self._tick_lock:
                self._settle_inflight()     # a cancel-close mid-tick
                for r in self.scheduler.drop_queued(lambda r: True):
                    r.finish(CANCELLED)
                    self.metrics.inc("cancelled")
                for slot, req in self.scheduler.occupied():
                    self._retire(slot, CANCELLED)
                self._prefill_q.clear()
                if self.prefix_cache is not None:
                    # teardown hygiene: every request is retired, so
                    # all cached pages are refcount-0 — return them so
                    # the pool ends balanced (used_pages == 0 after
                    # close). Detach the cold-tier spill hook first:
                    # teardown eviction is disposal, not pressure —
                    # spilling the whole trie to host RAM on close
                    # would be pure waste.
                    self.prefix_cache.spill = None
                    self.prefix_cache.evict(
                        self.prefix_cache.cached_pages)

    def _settle_inflight(self) -> None:
        """The worker is leaving (close, or dying): complete the tick
        in flight so the state torn down or dumped is one the device
        agrees with; a tick that cannot be completed (the failure was
        its own) is dropped. Caller holds the tick lock."""
        try:
            self._drain_inflight("close")
        except Exception:
            pass            # ``_inflight`` is already cleared

    def _observe_phases(self, phases: Dict[str, float]) -> None:
        """One ticked iteration's phases into their histograms, with
        ``tick_host_s``: the iteration less its read-back."""
        host = 0.0
        for name, dur in phases.items():
            short = name.rsplit(".", 1)[1]
            self.metrics.observe(f"phase_{short}_s", dur)
            if short != "readback":
                host += dur
        self.metrics.observe("tick_host_s", host)

    def _fail_all(self, e: BaseException) -> None:
        for r in self.scheduler.drop_queued(lambda r: True):
            r.error = e
            r.finish(CANCELLED)
        for slot, req in self.scheduler.occupied():
            req.error = e
            self.scheduler.retire(slot, CANCELLED)

    def _serving_tree(self, params):
        """The tree this engine's programs read. A family may bring a
        layout of its own for serving (its record's ``params(params,
        cfg)``: a NEW tree that shares with the caller's every leaf it
        does not re-lay); a family without one serves the tree it was
        given. Made once, here, under a set-up span of its own; the
        caller's tree is not touched. ``stats()["setup"]`` says what it cost:
        ``weights_relaid_bytes`` (the leaves of the engine's tree that
        are not the caller's own: 0 where nothing was re-laid) and
        ``weights_relay_s``. (At the end of the class: a line added
        above ``warm_programs`` would shift the frames a Mosaic
        kernel's compile-cache key holds.)"""
        import jax
        relay = self._family.params
        if relay is None:
            self._relaid = dict(weights_relaid_bytes=0, weights_relay_s=0.0)
            return params
        t0 = time.monotonic()
        with setup_span("serving.setup.init.relay"):
            tree = relay(params, self._cfg)
            given = {id(x) for x in jax.tree_util.tree_leaves(params)}
            new = jax.block_until_ready(  # noqa: PT002 — the set-up span holds the transposes, once an engine
                [x for x in jax.tree_util.tree_leaves(tree)
                 if id(x) not in given])
        self._relaid = dict(
            weights_relaid_bytes=sum(int(x.nbytes) for x in new),
            weights_relay_s=time.monotonic() - t0)
        return tree
