"""Flash attention for TPU.

Counterpart of the reference's flash_attn kernels
(paddle/phi/kernels/gpu/flash_attn_kernel.cu, exposed at
python/paddle/nn/functional/flash_attention.py:242): tiled
online-softmax attention that never materialises the [T, T] score matrix.

TPU path: the Pallas *splash* attention kernel
(jax.experimental.pallas.ops.tpu.splash_attention) — block-sparse
flash attention with native GQA (grouped KV heads are consumed directly,
no [B, T, H, Dh] repeat materialisation the way a plain MHA kernel would
need) and causal block skipping (the upper-triangular blocks are never
scheduled, not just masked). Block sizes are fixed at 512 after an
on-chip sweep: at B=4 H=32 T=2048 Dh=128 the default-blocked legacy
flash kernel runs ~10.8 ms fwd, 512-blocked 3.0 ms, splash 2.3 ms
(fwd+bwd 9.6 ms vs 7.2 ms — see docs/PERF.md).

Elsewhere (the 8-device CPU test mesh) a dense XLA path with identical
semantics runs instead.

Layout contract: q/k/v are [B, T, H, Dh] (time-major like the reference's
python API); GQA passes k/v as [B, T, Hkv, Dh] with H % Hkv == 0. ``v``
may have a head size of its own (``head_dim_v``: latent attention's
expanded form has q / k at 192 and v at 128); the result has v's.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from . import on_tpu as _on_tpu

# The name the splash forward rule gives its residuals ``out`` and
# ``logsumexp``. It has to come from INSIDE the kernel's ``custom_vjp``
# forward rule: the backward kernels read that rule's own residuals, so
# an output named outside the call is another variable, brings no
# log-sum-exp and saves nothing. Under no policy the name is an identity.
SPLASH_RESIDUALS = "splash_residuals"


def remat_layer(layer):
    """``jax.checkpoint(layer)`` that keeps splash's two residuals (128
    + 2 MiB a layer at 2 x 32 heads x 8192 x 128) and rebuilds everything
    else: the backward pass then runs no second forward of the kernel.
    The dense path carries no name, so this is plain remat there."""
    return jax.checkpoint(
        layer, policy=jax.checkpoint_policies.save_only_these_names(
            SPLASH_RESIDUALS))


def _dense_reference(q, k, v, causal, sm_scale):
    B, T, H, Dh = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q, k) * sm_scale
    if causal:
        mask = jnp.tril(jnp.ones((T, S), bool), k=S - T)
        scores = jnp.where(mask, scores.astype(jnp.float32), -1e30)
    else:
        scores = scores.astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


@functools.lru_cache(maxsize=32)
def _splash_kernel(n_heads: int, t_q: int, t_kv: int, causal: bool,
                   block: int):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    # bottom-right-aligned causal (offset = S-T), matching _dense_reference's
    # tril(k=S-T): with a cached prefix (S > T) every query attends to the
    # whole prefix plus its own causal window
    mk = (sm.CausalMask((t_q, t_kv), offset=t_kv - t_q) if causal
          else sm.FullMask((t_q, t_kv)))
    mask = sm.MultiHeadMask([mk for _ in range(n_heads)])
    bs = sk.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block)
    # the kernel object precomputes mask-info arrays; force those to be
    # concrete even when first built inside a jit trace (the object is
    # cached and reused across traces — a tracer leaking into it would
    # poison later calls)
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mha(
            mask=mask, head_shards=1, q_seq_shards=1, block_sizes=bs,
            residual_checkpoint_name=SPLASH_RESIDUALS)


def _block_for(t_q: int, t_kv: int) -> int:
    """The splash block: 512 (the sweep in the module docstring, 2048
    tokens), 1024 from 8192 tokens on (forward + backward of 2 x 32
    heads x 8192 at head sizes 192 / 128 on a v5e: 67.3 ms at 512, 59.3
    at 1024, out of VMEM at 2048: PERF.md §6, PR 44)."""
    return min(1024 if min(t_q, t_kv) >= 8192 else 512, t_q, t_kv)


def _splash(q, k, v, causal, sm_scale):
    """[B, T, H, Dh] x [B, S, Hkv, Dh] (v: [B, S, Hkv, Dv]) -> [B, T, H,
    Dv] via splash."""
    H, T, S = q.shape[2], q.shape[1], k.shape[1]
    kernel = _splash_kernel(H, T, S, causal, _block_for(T, S))
    qt = (q * sm_scale).astype(q.dtype).transpose(0, 2, 1, 3)  # [B,H,T,Dh]
    kt = k.transpose(0, 2, 1, 3)                               # [B,Hkv,S,Dh]
    vt = v.transpose(0, 2, 1, 3)
    out = jax.vmap(kernel)(qt, kt, vt)                         # [B,H,T,Dh]
    return out.transpose(0, 2, 1, 3)


def flash_attention(q, k, v, *, causal: bool = True, sm_scale=None,
                    impl: str = "auto"):
    """[B, T, H, Dh] attention; returns [B, T, H, Dv] (``Dv`` is v's
    head size, ``Dh`` unless v brings its own).

    impl: "auto" (pallas splash on TPU when shapes allow, dense
    otherwise), "pallas" (splash whatever the shapes or backend —
    interpret mode off-TPU), or "dense". A splash kernel that fails to
    build raises under both "auto" and "pallas": the dense O(T^2) path
    is chosen by shape and backend, never by a caught exception.
    """
    if impl not in ("auto", "pallas", "dense"):
        raise ValueError(
            f"impl must be 'auto', 'pallas', or 'dense', got {impl!r}")
    H, Dh = q.shape[2], q.shape[3]
    Hkv = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(Dh)

    # q / k at a whole number of lane tiles, or at one and a half (192:
    # the chip's compiler takes it as it is, tests/test_chip_compile.py)
    pallas_ok = (_on_tpu() and (Dh % 128 == 0 or Dh == 192)
                 and v.shape[3] % 128 == 0 and q.shape[1] % 128 == 0
                 and k.shape[1] % 128 == 0 and H % Hkv == 0)
    if impl == "pallas" or (impl == "auto" and pallas_ok):
        return _splash(q, k, v, causal, sm_scale)
    return _dense_reference(q, k, v, causal, sm_scale)


# ---------------------------------------------------------------------------
# kernel-audit registration (analysis/kernel_audit.py)
# ---------------------------------------------------------------------------
# No autotune kind (block sizes are pinned at 512 by the on-chip
# sweep). The splash kernel's three stats outputs (running max /
# denominator / logsumexp) are revisited across the kv grid axis, but
# kv is innermost so the revisits are consecutive runs — KA002's
# sequential-accumulation allowance covers them with no waiver.

AUDIT_KIND = None
AUDIT_CONFIG_KEYS = ()
AUDIT_GEOMETRIES = (
    {"batch": 2, "seq": 1024, "heads": 8, "kv_heads": 8,
     "head_dim": 128, "causal": True, "dtype": "bfloat16"},
)


def audit_launches(geom, config=None):
    B, T = int(geom["batch"]), int(geom["seq"])
    H, Hkv = int(geom["heads"]), int(geom["kv_heads"])
    dh = int(geom["head_dim"])
    dt = jnp.dtype(geom["dtype"])
    causal = bool(geom["causal"])
    sm_scale = float(dh) ** -0.5
    q = jax.ShapeDtypeStruct((B, T, H, dh), dt)
    k = jax.ShapeDtypeStruct((B, T, Hkv, dh), dt)
    v = jax.ShapeDtypeStruct((B, T, Hkv, dh), dt)

    def fn(q, k, v):
        return _splash(q, k, v, causal, sm_scale)

    return [("splash_fwd", fn, (q, k, v))]
