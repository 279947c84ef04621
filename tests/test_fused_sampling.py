"""Fused top-k/top-p sampling on the serving tick (ISSUE r16).

What this module pins, bottom up:

* the in-graph ``_fused_sample`` head degrades EXACTLY to greedy at
  temp=0 / top_k=1 / top_p→0 (argmax-equivalent masks), so every
  greedy bitwise pin in the suite survives by construction;
* SAMPLING requests ride the same fused programs as greedy ones —
  the fused block, the mixed tick's decode tail, the speculative
  verify — and the pre-r16 width-S single-step sampling program is
  GONE from the statically proven inventory;
* DETERMINISM: a fixed-seed sampled request emits one token stream
  whether it runs alone, packed with any neighbours, submitted in any
  order, under any decode_block size, or on a speculative engine
  (sampled acceptance) — the fold_in-by-token-index key discipline,
  the r16 determinism fix;
* ``warm_programs()`` still covers the whole (smaller) inventory, so
  the recompile sentinel stays clean under mixed sampled traffic.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import llama as L
from paddle_tpu.models.serving_tick import _fused_sample
from paddle_tpu.serving import ServingEngine

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


def _ref(params, prompt, n):
    out = jax.jit(lambda p, t: L.generate(p, t, CFG, max_new_tokens=n)
                  )(params, jnp.asarray(prompt)[None])
    return np.asarray(out)[0, len(prompt):]


RNG = np.random.RandomState(3)
PROMPT = RNG.randint(0, CFG.vocab_size, (11,)).astype(np.int32)


def _sampled(params, *, neighbors=0, block=1, spec=False, order=0,
             n=8, **samp):
    """One fixed-seed sampled request's stream under a given batch
    composition; greedy neighbours verified exact on the side."""
    samp.setdefault("temperature", 0.9)
    samp.setdefault("top_p", 0.95)
    samp.setdefault("seed", 42)
    kw = dict(decode_block_size=block)
    if spec:
        kw.update(speculative=True, spec_k=3)
    nb_prompts = [RNG.randint(0, CFG.vocab_size, (7,)).astype(np.int32)
                  for _ in range(neighbors)]
    with _engine(params, **kw) as eng:
        handles, h_s = [], None
        for i in range(neighbors + 1):
            if i == order:
                h_s = eng.submit(PROMPT, n, **samp)
            else:
                p = nb_prompts[i if i < order else i - 1]
                handles.append((p, eng.submit(p, 6)))
        out = h_s.result(timeout=300)
        nb = [(p, h.result(timeout=300)) for p, h in handles]
    for p, o in nb:
        np.testing.assert_array_equal(o, _ref(params, p, 6))
    return out


def test_sampled_stream_is_batch_composition_invariant(params):
    """THE determinism pin (r16 fix): same seed -> same stream alone,
    packed with greedy neighbours, submitted first or last (slot
    permutation), and under either decode_block size — while every
    greedy neighbour stays bitwise-equal to generate()."""
    base = _sampled(params)
    assert len(base) == 8
    for kw in (dict(neighbors=3), dict(neighbors=3, order=2),
               dict(block=4), dict(neighbors=2, block=4, order=1)):
        np.testing.assert_array_equal(base, _sampled(params, **kw))


def test_sampled_stream_invariant_under_speculation(params):
    """Speculative engines verify drafts against the target's own
    SAMPLED token (spec_k no longer greedy-only): the emitted stream
    equals the plain engine's bitwise, whatever the drafter proposed
    and wherever acceptance landed."""
    base = _sampled(params)
    np.testing.assert_array_equal(base, _sampled(params, spec=True))
    np.testing.assert_array_equal(
        base, _sampled(params, spec=True, neighbors=2, order=1))


def test_top_k_one_and_top_p_zero_degrade_to_greedy(params):
    """Exactness hooks into the reference: top_k=1 (and top_p→0)
    force the fused sampler's mask down to the argmax token, so the
    sampled stream equals the GREEDY stream equals generate() —
    pinning the mask semantics, not just determinism."""
    greedy = _ref(params, PROMPT, 8)
    np.testing.assert_array_equal(
        greedy, _sampled(params, temperature=0.8, top_k=1, top_p=1.0))
    np.testing.assert_array_equal(
        greedy, _sampled(params, temperature=0.8, top_p=1e-9))
    # and through the fused block with greedy neighbours
    np.testing.assert_array_equal(
        greedy, _sampled(params, temperature=0.8, top_k=1,
                         neighbors=2, block=4))


def test_sampling_rides_the_fused_block(params):
    """A pure-decode tick mixing greedy and sampling slots runs the
    fused block (steps > ticks), not single steps — the program the
    width-S single-step tick used to own."""
    with _engine(params, decode_block_size=4,
                 prefix_cache=False) as eng:
        h_g = eng.submit(PROMPT, 12)
        h_s = eng.submit(PROMPT[:7], 12, temperature=0.7, seed=1)
        out_g = h_g.result(timeout=300)
        out_s = h_s.result(timeout=300)
        snap = eng.stats()
    np.testing.assert_array_equal(out_g, _ref(params, PROMPT, 12))
    assert len(out_s) == 12
    steps = snap["counters"]["decode_steps"]
    ticks = snap["histograms"]["decode_step_s"]["count"]
    assert steps > ticks, (
        f"sampling forced single steps: {steps} steps / {ticks} ticks")


def test_single_step_program_gone_from_inventory(params):
    """The static half of the acceptance: the engine's proven
    inventory (== analysis/recompile.py's enumeration) no longer
    contains the width-S single-step tick; width S is the fused block
    alone, and the per-bucket bound holds with sampling as data."""
    from paddle_tpu.analysis.recompile import (ServingGeometry,
                                               program_inventory)
    with _engine(params, decode_block_size=4) as eng:
        inv = eng.program_inventory
        S = eng.scheduler.max_batch
        assert inv == program_inventory(ServingGeometry.of_engine(eng))
    assert inv["programs_per_bucket"] <= 2
    progs = [p for ps in inv["widths"].values() for p in ps]
    assert "serving_tick[decode]" not in progs
    assert inv["widths"][str(S)] == ["serving_tick_block[k=4]"]


@pytest.mark.parametrize("ambient_device", [False, True])
def test_warm_programs_sentinel_clean_under_sampled_traffic(
        params, ambient_device):
    """warm_programs() covers the whole r16 inventory (one compile per
    mixed-width tail variant + the block), and an armed sentinel stays
    clean through mixed greedy+sampled+chunked traffic — the runtime
    proof that sampling really is data. Also when the caller warms
    inside ``jax.default_device(...)``, as a deployment that names its
    chip does: that context is the calling thread's, the engine thread
    has none, and the programs warmed must be the ones IT runs."""
    import contextlib
    from paddle_tpu.serving import engine as _em
    _em._JIT_CACHE.clear()
    ambient = (jax.default_device(jax.devices()[0]) if ambient_device
               else contextlib.nullcontext())
    with ambient, _engine(params, recompile_sentinel=True, prefill_chunk=4,
                          max_batch=2, decode_block_size=2) as eng:
        n = eng.warm_programs()
        # two tail variants per mixed width (decode_block=2) + block
        assert n == 2 * len(eng._w_grid) + 1
        eng.arm_sentinel()
        hs = [eng.submit(PROMPT, 6),
              eng.submit(PROMPT[:9], 6, temperature=0.9, seed=5),
              eng.submit(PROMPT[:5], 4, temperature=0.5, top_k=3,
                         seed=6)]
        for h in hs:
            h.result(timeout=300)
        rep = eng.sentinel.report()
    assert rep["clean"], rep["events"]


def test_host_key_data_matches_prngkey():
    """The engine builds each slot's raw threefry key HOST-side
    ([0, seed & 0xffffffff] on the Python int) to keep a jit dispatch
    + device sync off the admission path — pin it bit-identical to
    jax.random.PRNGKey under the default (x64-off) config, including
    PRNGKey's >32-bit truncation AND negative seeds (np.uint64(-1)
    would raise on NumPy 2 — the mask must run on the Python int)."""
    for s in (0, 7, 42, 2**31 - 1, 2**33 + 5, -1, -42):
        host = np.array([0, s & 0xffffffff], np.uint32)
        np.testing.assert_array_equal(
            host, np.asarray(jax.random.PRNGKey(s), np.uint32))


def test_negative_seed_serves(params):
    """A negative seed must not kill the engine worker (regression:
    the first r16 cut crashed in _park on NumPy 2)."""
    with _engine(params) as eng:
        out = eng.submit(PROMPT, 4, temperature=0.8,
                         seed=-1).result(timeout=300)
    assert len(out) == 4


def test_fused_sample_unit_masks():
    """Unit pins on ``_fused_sample``: greedy rows bitwise argmax;
    top_k=1 rows equal argmax regardless of temperature; top_p=1 /
    top_k=0 leave the distribution intact (every token reachable);
    draws depend only on (key, idx), not on neighbouring rows."""
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(4, 64).astype(np.float32))
    keys = jnp.asarray(
        np.stack([np.asarray(jax.random.PRNGKey(s)) for s in
                  (1, 2, 3, 4)]).astype(np.uint32))
    idx = jnp.asarray([0, 5, 9, 2], jnp.int32)
    zeros = jnp.zeros((4,), jnp.float32)
    ones = jnp.ones((4,), jnp.float32)
    zi = jnp.zeros((4,), jnp.int32)
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    out = np.asarray(_fused_sample(logits, zeros, ones, zi, keys, idx))
    np.testing.assert_array_equal(out, greedy)
    out = np.asarray(_fused_sample(logits, ones, ones,
                                   jnp.full((4,), 1, jnp.int32), keys, idx))
    np.testing.assert_array_equal(out, greedy)       # top_k=1
    # row independence: permuting OTHER rows does not change row 0
    a = np.asarray(_fused_sample(logits, ones, ones, zi, keys, idx))
    perm = jnp.asarray([0, 3, 2, 1])
    b = np.asarray(_fused_sample(logits[perm], ones, ones, zi, keys[perm],
                                 idx[perm]))
    assert a[0] == b[0]
    assert a[3] == b[1]
