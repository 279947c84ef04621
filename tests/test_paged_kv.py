"""Paged (block-table) KV cache decode (inference/paged_kv.py +
models/llama.py generate_paged).

Reference capability:
python/paddle/incubate/nn/functional/block_multihead_attention.py —
fixed-size KV blocks, per-sequence block tables, decode attention over
valid blocks only. These tests pin the TPU-native redesign's semantics
to the dense-cache path on the CPU mesh.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.inference.paged_kv import PagePool
from paddle_tpu.models import llama as L


def _cfg(**kw):
    return L.LlamaConfig.tiny(dtype=jnp.float32, use_flash_attention=False,
                              remat=False, **kw)


# ---------------------------------------------------------------------------
# pool
# ---------------------------------------------------------------------------

def test_page_pool_alloc_free_exhaust():
    pool = PagePool(total_pages=5, page_size=4)
    assert pool.free_pages == 4               # page 0 reserved (trash)
    a = pool.alloc_for_len(9)                 # ceil(9/4) = 3 pages
    assert len(a) == 3 and PagePool.TRASH not in a
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc(2)
    pool.free(a)
    assert pool.free_pages == 4


# ---------------------------------------------------------------------------
# end-to-end generate: paged == dense cache
# ---------------------------------------------------------------------------

def test_generate_paged_matches_dense_equal_lengths():
    cfg = _cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    B, T0, N = 2, 12, 8
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, T0), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    dense = L.generate(params, prompt, cfg, N, temperature=0.0)
    paged = L.generate_paged(params, prompt,
                             jnp.full((B,), T0, jnp.int32), cfg, N,
                             page_size=4, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(dense[:, T0:]),
                                  np.asarray(paged))


def test_generate_paged_ragged_matches_per_sequence_dense():
    """The point of paging: mixed-length prompts in ONE batch, each
    matching its own unpadded dense decode."""
    cfg = _cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    lens = [5, 9, 12]
    T0, N = 12, 6
    rows = [jax.random.randint(jax.random.PRNGKey(10 + i), (1, l), 0,
                               cfg.vocab_size, dtype=jnp.int32)
            for i, l in enumerate(lens)]
    prompt = jnp.concatenate(
        [jnp.pad(r, ((0, 0), (0, T0 - r.shape[1]))) for r in rows])
    paged = L.generate_paged(params, prompt,
                             jnp.asarray(lens, jnp.int32), cfg, N,
                             page_size=4, temperature=0.0)
    for i, r in enumerate(rows):
        dense = L.generate(params, r, cfg, N, temperature=0.0)
        np.testing.assert_array_equal(np.asarray(dense[0, lens[i]:]),
                                      np.asarray(paged[i]),
                                      err_msg=f"row {i} len {lens[i]}")


def test_generate_paged_eos_latches():
    cfg = _cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    lens = jnp.asarray([8, 5], jnp.int32)
    out = L.generate_paged(params, prompt, lens, cfg, 10, page_size=4,
                           temperature=0.0, eos_token_id=3)
    a = np.asarray(out)
    for row in a:
        hits = np.where(row == 3)[0]
        if hits.size:
            assert np.all(row[hits[0]:] == 3), row


def test_dynamic_batcher_ragged_paged_composition():
    """Serving composition: mixed-length requests coalesce into ONE
    paged decode batch (DynamicBatcher seq_buckets mode); every caller
    gets exactly its per-sequence dense-decode continuation."""
    from paddle_tpu.inference.serving import DynamicBatcher
    cfg = _cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    N = 5

    def fn(batch, lens):
        return L.generate_paged(params, jnp.asarray(batch),
                                jnp.asarray(lens), cfg, N, page_size=4,
                                temperature=0.0)

    lens = [5, 9, 12]
    rows = [np.asarray(jax.random.randint(jax.random.PRNGKey(20 + i),
                                          (l,), 0, cfg.vocab_size,
                                          dtype=jnp.int32))
            for i, l in enumerate(lens)]
    with DynamicBatcher(fn, max_batch_size=4, max_delay_ms=200,
                        seq_buckets=[16]) as db:
        futs = [db.submit(r) for r in rows]
        outs = [f.result(timeout=120) for f in futs]
    assert db.stats["batches"] == 1, db.stats  # ONE coalesced batch
    for i, r in enumerate(rows):
        dense = L.generate(params, jnp.asarray(r)[None], cfg, N,
                           temperature=0.0)
        np.testing.assert_array_equal(np.asarray(dense[0, lens[i]:]),
                                      outs[i], err_msg=f"row {i}")


def test_generation_predictor_generate_ragged():
    from paddle_tpu.inference import GenerationPredictor
    cfg = _cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    pred = GenerationPredictor(params, cfg, max_len=64)
    prompts = [np.arange(5) % cfg.vocab_size,
               np.arange(11) % cfg.vocab_size]
    outs = pred.generate_ragged(prompts, 4, page_size=4)
    assert len(outs) == 2 and all(o.shape == (4,) for o in outs)
    dense = pred.generate(np.asarray(prompts[0])[None], 4)
    np.testing.assert_array_equal(dense[0, 5:], outs[0])
