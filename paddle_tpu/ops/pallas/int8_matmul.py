"""Authored int8×bf16 weight-only matmul Pallas kernel.

Counterpart of the reference's cutlass int8 weight-only GEMMs
(paddle/phi/kernels/fusion/cutlass/...): the weight tile streams from
HBM as int8 (half the bytes of bf16 — decode's dominant traffic), is
widened to the activation dtype in VMEM, hits the MXU, and the
per-output-channel f32 scale is applied once to the f32 accumulator on
the final K step — the scale multiply is O(tm·tn) per output tile, not
O(K·tn) per weight tile.

Grid ``(M/tm, N/tn, K/tk)`` with K innermost: the f32 accumulator lives
in VMEM scratch across the sequential K steps (TPU grids execute in
order), exactly the pattern of ops/pallas/grouped_matmul.py.

Off-TPU the kernel runs in interpreter mode so CPU tests exercise the
same code. Shapes that violate the tiling constraints (K or N not
divisible by a supported tile) fall back to the jnp formulation —
callers get correctness everywhere, the kernel where it pays.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from . import on_tpu as _on_tpu


def _pick_tile(dim: int, cap: int, step: int) -> int:
    """Largest multiple of ``step`` that divides ``dim``, capped at
    ``cap``; falls back to ``dim`` itself (single tile) when none."""
    t = cap
    while t >= step:
        if dim % t == 0:
            return t
        t -= step
    return dim


def _kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], q_ref[...].astype(x_ref.dtype),
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _store():
        o_ref[...] = (acc_ref[...]
                      * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "tk", "interpret"))
def _call(x, q, scale2d, tm, tn, tk, interpret):
    M, K = x.shape
    N = q.shape[1]
    grid = (M // tm, N // tn, K // tk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, tk), lambda m, n, k: (m, k)),
            pl.BlockSpec((tk, tn), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, tn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        interpret=interpret,
    )(x, q, scale2d)


def int8_matmul_pallas(x, q, scale):
    """``x [..., K] @ (q [K, N] int8 * scale [N]) -> [..., N]`` in
    ``x.dtype``. Leading x dims are flattened into M and zero-padded to
    the sublane tile (decode steps carry M = B·T of just a few rows)."""
    K, N = q.shape
    lead = x.shape[:-1]
    M = 1
    for d in lead:
        M *= d
    x2 = x.reshape(M, K)

    sub = 16 if x.dtype == jnp.bfloat16 else 8
    tk = _pick_tile(K, 512, sub)
    tn = _pick_tile(N, 512, 128)
    if K % tk or N % tn or N % 128 or K % sub or tk % sub:
        # un-tileable shape: jnp dequant-in-matmul (never wrong, just
        # not the authored kernel)
        out = (jnp.matmul(x2, q.astype(x.dtype))
               * scale.astype(jnp.float32)).astype(x.dtype)
        return out.reshape(*lead, N)

    Mp = -(-M // sub) * sub
    if Mp != M:
        x2 = jnp.pad(x2, ((0, Mp - M), (0, 0)))
    tm = _pick_tile(Mp, 128, sub)
    out = _call(x2, q, scale.reshape(1, N).astype(jnp.float32),
                tm, tn, tk, interpret=not _on_tpu())
    return out[:M].reshape(*lead, N)


# ---------------------------------------------------------------------------
# kernel-audit registration (analysis/kernel_audit.py)
# ---------------------------------------------------------------------------
# No autotune kind: the entry derives its tiles statically
# (_pick_tile), so the audit pins the derived tiling at the decode
# flagship shape (and the int8 weight operand arms KA004).

AUDIT_KIND = None
AUDIT_CONFIG_KEYS = ()
AUDIT_GEOMETRIES = (
    {"M": 128, "K": 4096, "N": 4096, "dtype": "bfloat16"},
)


def audit_launches(geom, config=None):
    M, K, N = int(geom["M"]), int(geom["K"]), int(geom["N"])
    dt = jnp.dtype(geom["dtype"])
    sub = 16 if dt == jnp.bfloat16 else 8
    tk = _pick_tile(K, 512, sub)
    tn = _pick_tile(N, 512, 128)
    tm = _pick_tile(-(-M // sub) * sub, 128, sub)
    x = jax.ShapeDtypeStruct((-(-M // sub) * sub, K), dt)
    q = jax.ShapeDtypeStruct((K, N), jnp.int8)
    s = jax.ShapeDtypeStruct((1, N), jnp.float32)
    fn = functools.partial(_call, tm=tm, tn=tn, tk=tk, interpret=False)
    return [(f"int8_matmul[{tm}x{tn}x{tk}]", fn, (x, q, s))]
