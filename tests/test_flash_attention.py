"""Flash attention wrapper (ops/pallas/flash_attention.py).

The pallas splash kernel itself only runs on TPU; these CPU tests pin the
wrapper's semantics — dense-path numerics, GQA handling, impl validation,
and that the splash mask construction is bottom-right aligned exactly like
the dense path (the silent-disagreement bug class when t_q != t_kv).
"""
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
