"""Flash attention wrapper (ops/pallas/flash_attention.py).

The pallas splash kernel itself only runs on TPU; these CPU tests pin the
wrapper's semantics — dense-path numerics, GQA handling, impl validation,
and that the splash mask construction is bottom-right aligned exactly like
the dense path (the silent-disagreement bug class when t_q != t_kv).
In interpret mode (conftest's ``splash_interpreted``) they also count
the kernel's launches in a rematerialised layer's gradient program: the
forward rule's residuals carry ``SPLASH_RESIDUALS`` and ``remat_layer``
keeps them, so a backward pass holds no second forward.
"""
import contextlib
import dataclasses
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import flash_attention as fa_mod
from paddle_tpu.ops.pallas.flash_attention import flash_attention


def naive(q, k, v, causal):
    B, T, H, Dh = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(Dh)
    if causal:
        mask = np.tril(np.ones((T, S), bool), k=S - T)
        s = jnp.where(mask, s, -np.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hkv", [4, 2, 1])
def test_dense_path_matches_naive_gqa(causal, hkv):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (2, 16, 4, 8))
    k = jax.random.normal(k2, (2, 16, hkv, 8))
    v = jax.random.normal(k3, (2, 16, hkv, 8))
    out = flash_attention(q, k, v, causal=causal, impl="dense")
    np.testing.assert_allclose(out, naive(q, k, v, causal),
                               rtol=1e-5, atol=1e-5)


def test_dense_path_kv_longer_than_q_is_bottom_right_aligned():
    """S > T (chunked decode with a cached prefix): every query sees the
    full prefix plus its causal window."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k1, (1, 4, 2, 8))
    k = jax.random.normal(k2, (1, 12, 2, 8))
    v = jax.random.normal(k3, (1, 12, 2, 8))
    out = flash_attention(q, k, v, causal=True, impl="dense")
    np.testing.assert_allclose(out, naive(q, k, v, True),
                               rtol=1e-5, atol=1e-5)


def test_splash_mask_matches_dense_alignment():
    """The mask fed to the splash kernel must equal the dense path's
    tril(k=S-T) for rectangular shapes."""
    sm = pytest.importorskip(
        "jax.experimental.pallas.ops.tpu.splash_attention"
        ".splash_attention_mask")
    for T, S in [(4, 4), (4, 12), (8, 8), (2, 6)]:
        m = sm.CausalMask((T, S), offset=S - T)
        got = np.array(m[0:T, 0:S]).astype(bool)
        want = np.tril(np.ones((T, S), bool), k=S - T)
        np.testing.assert_array_equal(got, want, err_msg=f"T={T} S={S}")


def test_invalid_impl_raises():
    q = jnp.zeros((1, 8, 2, 8))
    with pytest.raises(ValueError, match="impl"):
        flash_attention(q, q, q, impl="splash")


def test_pallas_strict_raises_off_tpu():
    """The splash kernel has no interpret mode: strict pallas off the
    chip raises the lowering's own error — nothing catches it and
    nothing falls back to the dense path."""
    if jax.default_backend() == "tpu":
        pytest.skip("strict mode succeeds on TPU")
    q = jnp.zeros((1, 128, 2, 128))
    with pytest.raises(ValueError, match="[Oo]nly interpret mode"):
        flash_attention(q, q, q, impl="pallas")


# ------------------------------------------- the residuals under remat ----

T_SPLASH = 128


def _splash_launches(fn, *args):
    """The splash kernels in ``fn``'s program by kind (fwd, dkv, dq):
    the ``pallas_call`` equations of its jaxpr, loops and rules walked
    (the printed jaxpr shows a sub-jaxpr that occurs twice once)."""
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    kinds = [re.fullmatch(r"splash_mha_(fwd|dkv|dq)_\w+", n).group(1)
             for n in names if n.startswith("splash")]
    return {k: kinds.count(k) for k in ("fwd", "dkv", "dq")}


def _tokens(vocab, t=T_SPLASH):
    return jax.random.randint(jax.random.PRNGKey(1), (2, t), 0, vocab)


def _joyai(mtp=0):
    """(loss, params, scanned layer bodies, mesh): a group each for the
    dense and the expert layers, and the module's one layer."""
    from paddle_tpu.models import joyai_flash as M
    cfg = M.JoyAIFlashConfig.tiny(use_flash_attention="pallas",
                                  num_nextn_predict_layers=mtp)
    toks = _tokens(cfg.vocab_size, T_SPLASH + 1)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    return (lambda p: M.loss_fn(p, batch, cfg),
            M.init_params(cfg, jax.random.PRNGKey(0)), 2 + mtp, None)


def _llama(**mesh_kw):
    from paddle_tpu.models import llama as L
    from paddle_tpu.parallel import init_hybrid_mesh
    cfg = L.LlamaConfig.tiny(dtype=jnp.float32, use_flash_attention="pallas",
                             use_fused_norm_rope=False)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    toks = _tokens(cfg.vocab_size)
    mesh = None
    if mesh_kw:
        mesh = init_hybrid_mesh(set_global=False, **mesh_kw).mesh
        assert L._tp_heads_shardable(cfg, mesh)     # splash under shard_map
        with mesh:
            params = L.shard_params(params, cfg, mesh)
    return (lambda p: (L.forward(p, toks, cfg, mesh) ** 2).mean(), params,
            1, mesh)


def _qwen2_moe():
    from paddle_tpu.models import qwen2_moe as Q
    # this family asks for "auto", the kernel where the shapes allow: a
    # head size of 128
    cfg = dataclasses.replace(
        Q.Qwen2MoeConfig.tiny(), hidden_size=256, num_attention_heads=2,
        num_key_value_heads=1, dtype=jnp.float32)
    toks = _tokens(cfg.vocab_size)
    return (lambda p: (Q.forward(p, toks, cfg)[0] ** 2).mean(),
            Q.init_params(cfg, jax.random.PRNGKey(0)), 1, None)


REMAT_CASES = {
    "joyai_flash": (_joyai, "joyai_flash"),
    "joyai_flash_mtp": (functools.partial(_joyai, mtp=1), "joyai_flash"),
    "llama": (_llama, "llama"),
    "llama_tp2_shard_map": (
        functools.partial(_llama, dp=1, pp=1, tp=2), "llama"),
    "llama_dp2_tp2_shard_map": (
        functools.partial(_llama, dp=2, pp=1, tp=2), "llama"),
    "qwen2_moe": (_qwen2_moe, "qwen2_moe"),
}


@pytest.mark.parametrize("case", list(REMAT_CASES))
def test_a_rematerialised_layer_runs_the_splash_forward_once(
        case, splash_interpreted, monkeypatch):
    """The gradient program of each family that trains through
    ``flash_attention``, its layers rematerialised (every config's
    default): ONE ``splash_mha_fwd*`` a scanned layer body beside one
    ``dkv`` and one ``dq``, under ``shard_map`` too. Plain
    ``jax.checkpoint`` has two forwards: the second, in the backward
    loop, rebuilds ``out`` and ``logsumexp``."""
    monkeypatch.setattr(fa_mod, "_on_tpu", lambda: True)    # qwen's "auto"
    build, module = REMAT_CASES[case]
    loss, params, bodies, mesh = build()
    with mesh if mesh is not None else contextlib.nullcontext():
        assert _splash_launches(jax.grad(loss), params) == {
            "fwd": bodies, "dkv": bodies, "dq": bodies}
        monkeypatch.setattr(f"paddle_tpu.models.{module}.remat_layer",
                            jax.checkpoint)
        assert _splash_launches(jax.grad(loss), params) == {
            "fwd": 2 * bodies, "dkv": bodies, "dq": bodies}


def test_outside_a_checkpoint_the_name_lowers_to_nothing(
        splash_interpreted, monkeypatch):
    """``flash_attention`` with no policy around it (serving, whole
    sequences, training without remat): value and gradient lower to the
    same text with the residuals named and unnamed (but for the running
    number the lowering gives its private functions: ``@closed_call_50``
    against ``_49``)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    q = jnp.zeros((1, T_SPLASH, 2, 128), jnp.float32)
    fn = lambda q, k, v: flash_attention(q, k, v, impl="pallas").sum()

    def lowered():
        fa_mod._splash_kernel.cache_clear()
        return [re.sub(r"(@[A-Za-z_]+)_\d+\b", r"\1",
                       jax.jit(f).lower(q, q, q).as_text())
                for f in (fn, jax.grad(fn, (0, 1, 2)))]

    named, make, seen = lowered(), sk.make_splash_mha, []

    def unnamed_make(**kw):
        seen.append(kw.pop("residual_checkpoint_name"))
        return make(residual_checkpoint_name=None, **kw)

    monkeypatch.setattr(sk, "make_splash_mha", unnamed_make)
    assert lowered() == named
    assert seen == [fa_mod.SPLASH_RESIDUALS]
