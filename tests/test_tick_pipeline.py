"""One tick in flight (``serving/engine.py: _loop``): the engine launches
tick N+1 from the PREDICTED state before it reads tick N back, a slot's
current token staying on the device.

The bar is exactness: whatever the timing, the token streams are the
in-step engine's bit for bit, greedy and sampled, for every family —
and the one thing that is a tick stale (an EOS, a cancel, a deadline:
the request has ended, its row is already in flight) costs a discarded
row and nothing else. The in-step arm is the same engine with its
private ``_depth`` at 0, which is what an engine with a drafter
observes of itself; there is no public knob.
"""
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import lfm2_moe as M, llama as L, qwen2_moe as Q
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.prefix_cache import prefix_fingerprints


def _llama():
    cfg = L.LlamaConfig.tiny()
    return cfg, L.init_params(cfg, jax.random.PRNGKey(0))


def _qwen():
    cfg = Q.Qwen2MoeConfig.tiny(dtype=jnp.float32,
                                use_flash_attention=False, remat=False)
    return cfg, Q.init_params(cfg, jax.random.PRNGKey(1))


def _lfm2():
    cfg = M.Lfm2MoeConfig.tiny()
    return cfg, M.init_params(cfg, jax.random.PRNGKey(2))


FAMILIES = {"llama": _llama, "qwen2_moe": _qwen, "lfm2_moe": _lfm2}
_BUILT = {}


def family(name):
    if name not in _BUILT:
        _BUILT[name] = FAMILIES[name]()
    return _BUILT[name]


def engine(name, depth=None, **kw):
    cfg, params = family(name)
    kw.setdefault("max_batch", 3)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_prompt_len", 24)
    kw.setdefault("max_new_tokens_cap", 12)
    kw.setdefault("check_invariants", True)
    eng = ServingEngine(params, cfg, **kw)
    if depth is not None:
        with eng._tick_lock:
            eng._depth = depth      # private: what a drafter's engine sees
    return eng


def counters(eng):
    return eng.snapshot()["counters"]


def drains(eng):
    return {e["labels"]["reason"]: e["value"]
            for e in eng.snapshot()["labeled"].get("inflight_drains", [])}


def mixed_traffic(vocab, seed=0, n=9):
    """Prompts from 2 to 24 tokens (so spans sit mid-prefill over
    several ticks at 5 tokens a tick, and complete beside decoding
    slots), 1 to 12 new tokens (1: the request ends with the tick that
    completes its prompt), every other one sampled with a fixed seed."""
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        prompt = rng.randint(1, vocab, (rng.randint(2, 25),)).astype(
            np.int32)
        kw = (dict(temperature=0.9, top_k=8, top_p=0.9, seed=100 + i)
              if i % 2 else {})
        out.append((prompt, 1 if i == 4 else int(rng.randint(2, 13)), kw))
    return out


def serve(eng, traffic):
    hs = [eng.submit(p, m, **kw) for p, m, kw in traffic]
    return [h.result(timeout=300).tolist() for h in hs]


# ---------------------------------------------------------------------------
# the token streams are the in-step engine's, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block", [1, 4])
@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_streams_equal_the_in_step_engine(name, block):
    """Greedy and sampled requests, spans mid-prefill, prompts
    completing beside decoding slots, slots reused, ``decode_block_size``
    1 and 4 (the fused block and the fused tail): the engine with a
    tick in flight emits what the engine in step emits."""
    cfg, _ = family(name)
    traffic = mixed_traffic(cfg.vocab_size)
    out = {}
    for depth in (0, 1):
        with engine(name, depth, decode_block_size=block,
                    prefill_chunk=5) as eng:
            out[depth] = serve(eng, traffic)
            c = counters(eng)
            assert eng.audit() == []
        assert c["overrun_slot_ticks"] == 0     # no EOS, nothing cancelled
        assert c["tokens_out"] == sum(m for _, m, _ in traffic)
        if depth:
            # nearly every tick was launched with one in flight; the
            # loop completed early only when it ran empty
            assert c["ticks_ahead"] > 0
            assert set(drains(eng)) <= {"empty", "close"}
        else:
            assert c["ticks_ahead"] == 0
            assert set(drains(eng)) == {"step"}
    assert out[1] == out[0]
    assert [len(t) for t in out[1]] == [m for _, m, _ in traffic]


# ---------------------------------------------------------------------------
# the stale tick
# ---------------------------------------------------------------------------

def _held_with_a_tick_in_flight(eng, want=lambda: True, timeout=60.0):
    """Takes the tick lock at a moment when a tick is in flight (and
    ``want()`` holds); returns with the lock HELD. The engine paces
    itself outside the lock (``tick_interval_s``), which is when this
    gets in."""
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end:
        eng._tick_lock.acquire()
        if eng._inflight is not None and want():
            return
        eng._tick_lock.release()
        time.sleep(0.001)
    raise AssertionError("no tick in flight seen")


def _stream_of(name, prompt, n, **kw):
    with engine(name, **kw) as eng:
        return eng.submit(prompt, n).result(timeout=300).tolist()


def test_eos_found_a_tick_late_costs_one_discarded_row():
    """The EOS is in tick N's tokens, read after tick N+1 was launched
    with the slot's row: that row's token is discarded, counted
    (``overrun_slot_ticks``), its KV write landed past the prompt in
    the request's own tail page (the shared prefix pages hold what the
    in-step engine's hold, bit for bit), the slot serves the next
    request, and the audit is clean."""
    cfg, _ = family("llama")
    rng = np.random.RandomState(5)
    prompt = rng.randint(1, cfg.vocab_size, (9,)).astype(np.int32)
    geo = dict(max_batch=1, max_new_tokens_cap=12)
    free = _stream_of("llama", prompt, 10, **geo)
    j = next(i for i in range(1, 8) if free[i] not in free[:i])
    eos = free[j]
    fp = prefix_fingerprints(prompt, 4)[-1]     # 2 full prompt pages
    arms = {}
    for depth in (0, 1):
        with engine("llama", depth, **geo) as eng:
            a = eng.submit(prompt, 10, eos_token_id=eos).result(timeout=300)
            # the next request shares the prefix and takes the SAME slot
            b = eng.submit(prompt, 6).result(timeout=300)
            assert eng.audit() == []
            chain = eng.export_chain(fp)
            c = counters(eng)
        arms[depth] = (a.tolist(), b.tolist(), chain["k"], chain["v"],
                       c["overrun_slot_ticks"], c["prefix_hits"])
    for a, b, _, _, _, hits in arms.values():
        assert a == free[:j + 1] and b == free[:6]
        assert hits == 1
    assert arms[0][4] == 0 and arms[1][4] == 1
    np.testing.assert_array_equal(arms[1][2], arms[0][2])
    np.testing.assert_array_equal(arms[1][3], arms[0][3])


@pytest.mark.parametrize("how", ["cancelled", "timed_out"])
def test_request_ended_while_its_tick_is_in_flight(how):
    """A cancel or a deadline lands between a tick's dispatch and its
    completion: the sweep retires the request, the row in flight is
    dropped at completion (nothing more reaches the handle), the
    neighbour's stream is untouched."""
    cfg, _ = family("llama")
    rng = np.random.RandomState(6)
    pa, pb = (rng.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
              for n in (7, 5))
    want_b = _stream_of("llama", pb, 12)
    with engine("llama", tick_interval_s=0.002) as eng:
        ha, hb = eng.submit(pa, 12), eng.submit(pb, 12)
        req = ha._req
        _held_with_a_tick_in_flight(
            eng, lambda: 1 <= len(req.tokens) < 8 and any(
                r is req for _, r in eng._inflight.live))
        try:
            seen = list(req.tokens)
            if how == "cancelled":
                ha.cancel()
            else:
                req.deadline_s = time.monotonic() - 1.0
        finally:
            eng._tick_lock.release()
        got_a = ha.result(timeout=300).tolist()
        assert hb.result(timeout=300).tolist() == want_b
        assert eng.audit() == []
        c = counters(eng)
    assert ha.status == how and got_a == seen
    assert c[how] == 1 and c["overrun_slot_ticks"] == 1


def test_slot_readmitted_under_its_old_occupants_tick():
    """One slot: A is cancelled and B queued while A's tick is in
    flight, so B is admitted to the slot and its first span launched
    BEFORE A's tick is read back. A's row is dropped by the ``(slot,
    req)`` it was launched for: no token reaches the wrong handle."""
    cfg, _ = family("llama")
    rng = np.random.RandomState(7)
    pa, pb = (rng.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
              for n in (6, 10))
    want_a = _stream_of("llama", pa, 12, max_batch=1)
    want_b = _stream_of("llama", pb, 8, max_batch=1)
    with engine("llama", max_batch=1, tick_interval_s=0.002) as eng:
        ha = eng.submit(pa, 12)
        req = ha._req
        _held_with_a_tick_in_flight(
            eng, lambda: 2 <= len(req.tokens) < 8 and any(
                r is req for _, r in eng._inflight.live))
        try:
            seen = list(req.tokens)
            ha.cancel()
            hb = eng.submit(pb, 8)
        finally:
            eng._tick_lock.release()
        got_b = hb.result(timeout=300).tolist()
        got_a = ha.result(timeout=300).tolist()
        assert eng.audit() == []
        c = counters(eng)
    assert got_a == seen == want_a[:len(seen)]
    assert got_b == want_b
    assert ha._req.slot == hb._req.slot == 0
    assert c["overrun_slot_ticks"] == 1 and c["cancelled"] == 1


def test_defragment_completes_the_tick_in_flight():
    """``defragment()`` rewrites pool and tables under the tick lock: it
    first completes the tick in flight (counted, with its reason), and
    the streams are what they are without it."""
    cfg, _ = family("llama")
    traffic = mixed_traffic(cfg.vocab_size, seed=3, n=7)
    with engine("llama", prefill_chunk=5) as eng:
        want = serve(eng, traffic)
    moved = 0
    with engine("llama", prefill_chunk=5, tick_interval_s=0.002) as eng:
        hs = [eng.submit(p, m, **kw) for p, m, kw in traffic]
        while not all(h._req.done.is_set() for h in hs):
            moved += eng.defragment()
            time.sleep(0.002)
        got = [h.result(timeout=300).tolist() for h in hs]
        assert eng.audit() == []
        by_reason = drains(eng)
    assert got == want
    assert by_reason.get("defragment", 0) >= 1 and moved > 0


@pytest.mark.parametrize("drain", [True, False])
def test_close_with_a_tick_in_flight(drain):
    """``close(drain=True)`` serves everything out, the last tick
    completed like any other; ``close(drain=False)`` completes the tick
    in flight (reason ``close``) and cancels what is left: every handle
    resolves, the pool ends empty."""
    cfg, _ = family("llama")
    traffic = mixed_traffic(cfg.vocab_size, seed=4, n=6)
    with engine("llama", prefill_chunk=5) as eng:
        want = serve(eng, traffic)
    eng = engine("llama", prefill_chunk=5, tick_interval_s=0.002)
    hs = [eng.submit(p, 12 if not drain else m, **kw)
          for p, m, kw in traffic]
    _held_with_a_tick_in_flight(eng)
    threading.Timer(0.05, eng._tick_lock.release).start()
    eng.close(drain=drain)      # waits for the lock's release, then ends
    got = [h.result(timeout=300).tolist() for h in hs]
    assert eng._inflight is None and eng.pool.used_pages == 0
    if drain:
        assert got == want
        assert all(h.status == "completed" for h in hs)
    else:
        assert any(h.status == "cancelled" for h in hs)
        assert drains(eng).get("close") == 1


def test_a_drafter_engine_stays_in_step():
    """N-gram drafting reads ``req.tokens`` on the host to build the
    next tick, so that engine completes every tick before the next
    build — through the same dispatch/complete pair — and its streams
    are the plain engine's."""
    cfg, _ = family("llama")
    rng = np.random.RandomState(8)
    traffic = [(np.tile(rng.randint(1, cfg.vocab_size, (4,)), 3).astype(
        np.int32), 10, {}) for _ in range(4)]
    with engine("llama") as eng:
        want = serve(eng, traffic)
    with engine("llama", speculative="ngram") as eng:
        assert eng._depth == 0
        got = serve(eng, traffic)
        c = counters(eng)
        by_reason = drains(eng)
    assert got == want
    assert c["ticks_ahead"] == 0 and c["spec_ticks"] > 0
    assert set(by_reason) == {"drafter"}
    assert by_reason["drafter"] == c["inflight_drains"]


@pytest.mark.parametrize("block", [1, 4])
def test_warm_programs_warms_the_signatures_the_ticks_run(block):
    """``warm_programs()`` loads as many programs as before the slots'
    tokens moved to the device (a tick and, with a fused tail, a tick +
    tail a width, and the block), with the SAME signatures the live
    ticks use: armed right after it, the sentinel sees no compile under
    mixed traffic, tick in flight and all."""
    cfg, _ = family("llama")
    with engine("llama", decode_block_size=block, prefill_chunk=5) as eng:
        n = eng.warm_programs()
        assert n == len(eng._w_grid) * (2 if block > 1 else 1) + 1
        eng.arm_sentinel()
        serve(eng, mixed_traffic(cfg.vocab_size, seed=9, n=6))
        c = counters(eng)
    assert c["recompiles"] == 0 and c["ticks_ahead"] > 0
