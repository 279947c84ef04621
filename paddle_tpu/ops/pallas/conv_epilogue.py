"""Authored matmul+bias(+relu) epilogue Pallas kernel — the conv
epilogue for 1x1 convolutions.

A stride-1 1x1 NHWC convolution IS a matmul over rows = B*H*W — the
shape 36 of ResNet-50's 53 convs take after the conv-bn-fold rewrite
(analysis/rewrite_conv.py). On TPU the win is one kernel: the f32
accumulator picks up the folded-BN bias and the relu before the output
tile ever leaves VMEM, so the conv output crosses HBM exactly once
(the XLA baseline materialises the conv result, then a separate fusion
re-reads it for the epilogue).

Grid ``(M/tm, N/tn, K/tk)`` with K innermost and a VMEM f32 accumulator
across the sequential K steps — the ops/pallas/int8_matmul.py pattern.
Tile shapes come from the persistent autotune winner store when
``tools/kernel_bench.py --block-sweep`` has swept this geometry
(KForge flywheel, ops/autotune.py), else the static defaults below.
Off-TPU the kernel runs in interpreter mode; shapes that violate the
tiling constraints fall back to the jnp formulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from . import on_tpu as _on_tpu


def _pick_tile(dim: int, cap: int, step: int) -> int:
    t = cap
    while t >= step:
        if dim % t == 0:
            return t
        t -= step
    return dim


def _kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, nk, relu):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _store():
        acc = acc_ref[...] + b_ref[...].astype(jnp.float32)
        if relu:
            acc = jnp.maximum(acc, 0.0)
        o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("tm", "tn", "tk", "relu", "interpret"))
def _call(x, w, bias2d, tm, tn, tk, relu, interpret):
    M, K = x.shape
    N = w.shape[1]
    grid = (M // tm, N // tn, K // tk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=grid[2], relu=relu),
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
    )(x, w, bias2d)


def default_tiles(M: int, K: int, N: int, dtype) -> tuple:
    """The static tiling an unswept geometry gets (the pre-KForge
    guess): as large as divides, lane-aligned."""
    sub = 16 if jnp.dtype(dtype) == jnp.bfloat16 else 8
    tm = _pick_tile(M, 256, sub)
    tn = _pick_tile(N, 256, 128)
    tk = _pick_tile(K, 512, sub)
    return tm, tn, tk


def matmul_bias_act(x2, w, bias, relu: bool = True,
                    tiles: tuple | None = None):
    """``relu?(x2 [M,K] @ w [K,N] + bias [N])`` in ``x2.dtype`` through
    the epilogue kernel. ``tiles=None`` consults the persistent
    autotune winner store for this geometry, falling back to
    :func:`default_tiles`; untileable shapes fall back to jnp (never
    wrong, just not the authored kernel)."""
    M, K = x2.shape
    N = w.shape[1]
    dt = str(jnp.dtype(x2.dtype))
    if tiles is None:
        from .. import autotune as at
        win = at.lookup("conv_epilogue", M=M, K=K, N=N, dtype=dt)
        if win is not None:
            tiles = (int(win["tm"]), int(win["tn"]), int(win["tk"]))
        else:
            tiles = default_tiles(M, K, N, x2.dtype)
    tm, tn, tk = tiles
    sub = 16 if x2.dtype == jnp.bfloat16 else 8
    if (M % tm or N % tn or K % tk or N % 128 or K % sub
            or tk % sub or tm % sub):
        out = jnp.matmul(x2, w.astype(x2.dtype)) + bias.astype(x2.dtype)
        if relu:
            out = jnp.maximum(out, 0.0)
        return out
    return _call(x2, w.astype(x2.dtype),
                 bias.reshape(1, N).astype(jnp.float32),
                 tm, tn, tk, relu, interpret=not _on_tpu())


# ---------------------------------------------------------------------------
# kernel-audit registration (analysis/kernel_audit.py)
# ---------------------------------------------------------------------------
# Geometry keys match matmul_bias_act's autotune lookup kwargs, so
# block-sweep winners.json entries audit directly (audit-at-record /
# audit-at-load in ops/autotune.py ride this registration).

AUDIT_KIND = "conv_epilogue"
AUDIT_GEOM_KEYS = ("M", "K", "N", "dtype")
AUDIT_CONFIG_KEYS = ("tm", "tn", "tk")
AUDIT_GEOMETRIES = (
    # ResNet-50 B=8 stage-3 1x1 (M = 8*28*28) — the profiled rewrite's
    # hottest epilogue shape class
    {"M": 6272, "K": 512, "N": 512, "dtype": "bfloat16"},
    {"M": 512, "K": 2048, "N": 512, "dtype": "float32"},
)


def audit_launches(geom, config=None):
    M, K, N = int(geom["M"]), int(geom["K"]), int(geom["N"])
    dt = jnp.dtype(geom["dtype"])
    if config is not None and {"tm", "tn", "tk"} <= set(config):
        tm, tn, tk = int(config["tm"]), int(config["tn"]), int(config["tk"])
    else:
        tm, tn, tk = default_tiles(M, K, N, dt)
    x = jax.ShapeDtypeStruct((M, K), dt)
    w = jax.ShapeDtypeStruct((K, N), dt)
    b = jax.ShapeDtypeStruct((1, N), jnp.float32)
    fn = functools.partial(_call, tm=tm, tn=tn, tk=tk, relu=True,
                           interpret=False)
    return [(f"matmul_bias_act[{tm}x{tn}x{tk}]", fn, (x, w, b))]
