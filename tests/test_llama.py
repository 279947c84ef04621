"""Flagship model + hybrid parallelism tests on the 8-device CPU mesh.

Mirrors the reference's hybrid-strategy integration tests
(test/auto_parallel/hybrid_strategy/semi_auto_llama.py — dp/mp/pp Llama on
multi-GPU): here the mesh is virtual, the parallelism is real.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.parallel import init_hybrid_mesh
from paddle_tpu.models import llama as L


def _cfg(**kw):
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("use_flash_attention", False)
    kw.setdefault("remat", False)
    return L.LlamaConfig.tiny(**kw)


def test_forward_shapes():
    cfg = _cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    logits = L.forward(params, toks, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)


def test_fused_norm_rope_path_matches_unfused():
    # the bench path runs the pallas fused rmsnorm/rope between GEMMs
    # (interpret mode here); it must agree with the jnp formulation
    cfg_f = _cfg(use_fused_norm_rope=True)
    cfg_u = _cfg(use_fused_norm_rope=False)
    params = L.init_params(cfg_f, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg_f.vocab_size)

    def loss(p, cfg):
        lg = L.forward(p, toks, cfg)
        return (lg.astype(jnp.float32) ** 2).mean()

    lf, gf = jax.value_and_grad(loss)(params, cfg_f)
    lu, gu = jax.value_and_grad(loss)(params, cfg_u)
    np.testing.assert_allclose(float(lf), float(lu), rtol=2e-5)
    flat_f = jax.tree_util.tree_leaves(gf)
    flat_u = jax.tree_util.tree_leaves(gu)
    for a, b in zip(flat_f, flat_u):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-5)


@pytest.mark.parametrize("against", ["plain_remat", "no_remat"])
@pytest.mark.parametrize("attn", ["pallas", False], ids=["pallas", "dense"])
def test_keeping_splash_s_residuals_changes_no_gradient(
        attn, against, splash_interpreted, monkeypatch):
    """Two layers rematerialised but for splash's ``out`` and
    ``logsumexp`` (``remat_layer``), against plain ``jax.checkpoint`` and
    against no remat: the loss and every leaf's gradient EXACTLY, through
    the kernel (interpret mode) and on the dense path, where nothing
    carries the name and the policy is plain remat."""
    def value_and_grads(remat):
        cfg = dataclasses.replace(
            _cfg(use_flash_attention=attn, use_fused_norm_rope=False,
                 remat=remat), num_hidden_layers=2)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        return jax.jit(jax.value_and_grad(
            lambda p: L.loss_fn(p, batch, cfg)))(
                L.init_params(cfg, jax.random.PRNGKey(0)))

    kept = value_and_grads(True)
    if against == "plain_remat":
        monkeypatch.setattr(L, "remat_layer", jax.checkpoint)
    other = value_and_grads(against == "plain_remat")
    assert np.abs(np.asarray(kept[1]["layers"]["wq"])).max() > 0
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(other)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pipeline_matches_single_stage():
    """forward_pipelined (pp=2, 2 microbatches) == forward (pp=1)."""
    hm = init_hybrid_mesh(dp=2, pp=2, tp=2, set_global=False)
    cfg1 = _cfg()
    cfg2 = _cfg(pp_stages=2, num_microbatches=2)
    params = L.init_params(cfg1, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg1.vocab_size)
    ref = L.forward(params, toks, cfg1)
    with hm.mesh:
        sharded = L.shard_params(params, cfg2, hm.mesh)
        out = jax.jit(lambda p, t: L.forward_pipelined(p, t, cfg2, hm.mesh))(
            sharded, toks)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-4, atol=2e-4)


def test_train_step_4d_loss_decreases():
    hm = init_hybrid_mesh(dp=2, pp=2, tp=2, set_global=False)
    cfg = _cfg(pp_stages=2, num_microbatches=2)
    with hm.mesh:
        step, init = L.make_train_step(cfg, hm.mesh)
        state = init(jax.random.PRNGKey(0))
        batch = L.make_batch(cfg, batch_size=4, seq_len=16, mesh=hm.mesh)
        losses = []
        for _ in range(5):
            state, loss = step(state, batch)
            losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert int(state["step"]) == 5


def test_gqa_attention_matches_mha_expansion():
    cfg = _cfg()
    B, T, H, Dh = 2, 8, cfg.num_attention_heads, cfg.head_dim
    Hkv = cfg.num_key_value_heads
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (B, T, H, Dh))
    k = jax.random.normal(k2, (B, T, Hkv, Dh))
    v = jax.random.normal(k3, (B, T, Hkv, Dh))
    out = L.attention(q, k, v, cfg)
    # manual expansion
    kk = jnp.repeat(k, H // Hkv, axis=2)
    vv = jnp.repeat(v, H // Hkv, axis=2)
    ref = L.attention(q, kk, vv, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_flash_attention_fallback_matches_dense():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    cfg = _cfg()
    B, T, H, Dh = 2, 16, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, T, H, Dh)) for kk in ks)
    out = flash_attention(q, k, v, causal=True)
    ref = L.attention(q, k, v, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_lenet_train_step():
    import paddle_tpu as pt
    from paddle_tpu.models import LeNet
    m = LeNet()
    opt = pt.optimizer.Adam(learning_rate=1e-3, parameters=m.parameters())
    x = pt.to_tensor(np.random.randn(8, 1, 28, 28).astype(np.float32))
    y = pt.to_tensor(np.random.randint(0, 10, (8,)))
    losses = []
    for _ in range(5):
        logits = m(x)
        loss = pt.nn.functional.cross_entropy(logits, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    assert losses[-1] < losses[0]
