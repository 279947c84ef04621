"""Fused RMSNorm and rotary-embedding Pallas kernels.

Counterparts of the reference's fused epilogue kernels
(paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu and
fused_rms_norm_kernel): one pass over HBM instead of the several
materialised intermediates the unfused formulation costs (cos/sin tables,
half-splits, concats).

TPU-shape notes:
  * rope is computed roll-based: ``out = x*cos' + roll(x, Dh/2)*sign*sin'``
    where cos'/sin' repeat over both halves and ``sign`` is -1 on the first
    half. This keeps every op full-lane (no Dh/2 slicing, which would
    break the 128-lane tiling).
  * the backward of a rotation is the rotation by the negated angle, so
    the same kernel serves the VJP with ``positions`` negated.
  * rms_norm's dw is accumulated across row tiles directly in the f32
    output window (the TPU grid is sequential).

Both kernels run in interpreter mode off-TPU so CPU tests exercise the
same code (tests/test_fused_norm_rope.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P
from . import on_tpu as _on_tpu


# ---------------------------------------------------------------------------
# fused rope (q and k in one pass)
# ---------------------------------------------------------------------------

def _rope_kernel(pos_ref, q_ref, k_ref, oq_ref, ok_ref, *, theta):
    TT = q_ref.shape[1]
    Dh = q_ref.shape[-1]
    half = Dh // 2
    b, t = pl.program_id(0), pl.program_id(1)
    # positions ref is the whole [B, T] array (tiny; a (1, TT) block
    # would violate Mosaic's (8, 128) block-divisibility rule)
    pos = pos_ref[b, pl.ds(t * TT, TT)].astype(jnp.float32)   # [TT]
    j = jax.lax.broadcasted_iota(jnp.int32, (TT, Dh), 1)
    exponent = (j % half).astype(jnp.float32) / half
    inv_freq = jnp.exp(-jnp.log(theta) * exponent)            # [TT, Dh]
    ang = pos[:, None] * inv_freq
    cos = jnp.cos(ang)[None, :, None, :]                      # [1,TT,1,Dh]
    sin = jnp.sin(ang)[None, :, None, :]
    sign = jnp.where(j < half, -1.0, 1.0)[None, :, None, :]

    def rot(x):
        xf = x.astype(jnp.float32)
        rolled = pltpu.roll(xf, half, axis=3)
        return (xf * cos + rolled * sign * sin).astype(x.dtype)

    oq_ref[...] = rot(q_ref[...])
    ok_ref[...] = rot(k_ref[...])


@functools.partial(jax.jit, static_argnames=("theta", "tile_t",
                                             "interpret"))
def _rope_call(q, k, positions, theta, tile_t, interpret):
    B, T, H, Dh = q.shape
    Hkv = k.shape[2]
    assert T % tile_t == 0 and Dh % 2 == 0
    grid = (B, T // tile_t)
    kern = functools.partial(_rope_kernel, theta=theta)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, T), lambda b, t: (0, 0)),
            pl.BlockSpec((1, tile_t, H, Dh), lambda b, t: (b, t, 0, 0)),
            pl.BlockSpec((1, tile_t, Hkv, Dh), lambda b, t: (b, t, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tile_t, H, Dh), lambda b, t: (b, t, 0, 0)),
            pl.BlockSpec((1, tile_t, Hkv, Dh), lambda b, t: (b, t, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
        ],
        interpret=interpret,
    )(positions, q, k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_rope(q, k, positions, theta: float = 10000.0, tile_t: int = 256):
    """Rotary embedding applied to q ``[B,T,H,Dh]`` and k ``[B,T,Hkv,Dh]``
    in one fused pass. positions: int ``[B, T]``."""
    tt = tile_t if q.shape[1] % tile_t == 0 else q.shape[1]
    return tuple(_rope_call(q, k, positions, float(theta), tt,
                            interpret=not _on_tpu()))


def _rope_fwd(q, k, positions, theta, tile_t):
    return fused_rope(q, k, positions, theta, tile_t), positions


def _rope_bwd(theta, tile_t, positions, g):
    gq, gk = g
    # rotation transpose == rotation by -angle
    tt = tile_t if gq.shape[1] % tile_t == 0 else gq.shape[1]
    dq, dk = _rope_call(gq, gk, -positions, float(theta), tt,
                        interpret=not _on_tpu())
    return dq, dk, None


fused_rope.defvjp(_rope_fwd, _rope_bwd)


# ---------------------------------------------------------------------------
# fused rms_norm
# ---------------------------------------------------------------------------

def _rms_fwd_kernel(x_ref, w_ref, o_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    rstd_ref[...] = rstd  # [tile_n, 1] — 1-D outputs trip XLA's f32
    #                        1024-element tiling, so rstd stays 2-D
    o_ref[...] = (x * rstd * w_ref[...].astype(jnp.float32)).astype(
        o_ref.dtype)


def _rms_bwd_kernel(x_ref, w_ref, rstd_ref, g_ref, dx_ref, dw_ref, *, eps):
    del eps
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    rstd = rstd_ref[...]  # [tile_n, 1]
    xhat = x * rstd
    gw = g * w
    dx = (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True)) * rstd

    @pl.when(i == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dw_ref[...] += jnp.sum(g * xhat, axis=0)
    dx_ref[...] = dx.astype(dx_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "tile_n", "interpret"))
def _rms_fwd_call(x, w, eps, tile_n, interpret):
    N, D = x.shape
    kern = functools.partial(_rms_fwd_kernel, eps=eps)
    return pl.pallas_call(
        kern,
        grid=(N // tile_n,),
        in_specs=[
            pl.BlockSpec((tile_n, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((tile_n, D), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, D), x.dtype),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, w)


@functools.partial(jax.jit, static_argnames=("eps", "tile_n", "interpret"))
def _rms_bwd_call(x, w, rstd, g, eps, tile_n, interpret):
    N, D = x.shape
    kern = functools.partial(_rms_bwd_kernel, eps=eps)
    return pl.pallas_call(
        kern,
        grid=(N // tile_n,),
        in_specs=[
            pl.BlockSpec((tile_n, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
            pl.BlockSpec((tile_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, D), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_n, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, D), x.dtype),
            jax.ShapeDtypeStruct((D,), jnp.float32),
        ],
        interpret=interpret,
    )(x, w, rstd, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_rms_norm(x, weight, eps: float = 1e-5, tile_n=None):
    """RMSNorm over the last dim of ``x [..., D]``, fused fwd+bwd.
    ``tile_n=None`` resolves the row tile from the persistent autotune
    winner store (swept geometries) else the static budget walk; an
    explicit int keeps the legacy cap semantics (the sweep harness
    forces tiles this way)."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    tn = _pick_row_tile(x2.shape[0], x2.shape[1], x2.dtype, tile_n)
    out, _ = _rms_fwd_call(x2, weight, float(eps), tn,
                           interpret=not _on_tpu())
    return out.reshape(shape)


def _row_tile(n: int, d: int, cap: int = 256) -> int:
    """Largest row tile that divides ``n`` AND keeps the kernel's live
    f32 [tile, d] windows inside scoped vmem. The bwd kernel holds ~6 of
    them; at 3 MB/window (tile*d*4B) the measured peak stays under the
    16 MB scope (tile 256 at D=4096 = 4 MB/window blows it)."""
    budget = max(3_000_000 // (4 * d), 8)
    for t in (256, 128, 64, 32, 16, 8, 4, 2):
        if t <= cap and t <= budget and n % t == 0:
            return t
    return 1


def _pick_row_tile(n: int, d: int, dtype, cap) -> int:
    """Resolve the row tile. ``cap=None`` (the entry-point default)
    consults the persistent autotune winner store for this geometry
    first — the KForge flywheel: ``kernel_bench --block-sweep`` records
    the winner, every later call picks it up — falling back to the
    static :func:`_row_tile` walk for unswept geometries (bitwise the
    same math either way; tiles only reschedule it). An explicit int
    cap skips the store."""
    if cap is not None:
        return _row_tile(n, d, cap)
    from .. import autotune as at
    win = at.lookup("fused_rms_norm", rows=n, d=d,
                    dtype=str(jnp.dtype(dtype)))
    if win is not None:
        t = int(win.get("tile_n", 0))
        if t > 0 and n % t == 0:
            return t
    return _row_tile(n, d)


def _rms_fwd(x, weight, eps, tile_n):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    tn = _pick_row_tile(x2.shape[0], x2.shape[1], x2.dtype, tile_n)
    out, rstd = _rms_fwd_call(x2, weight, float(eps), tn,
                              interpret=not _on_tpu())
    return out.reshape(shape), (x2, weight, rstd, shape)


def _rms_bwd(eps, tile_n, res, g):
    x2, weight, rstd, shape = res
    g2 = g.reshape(-1, shape[-1])
    tn = _pick_row_tile(x2.shape[0], x2.shape[1], x2.dtype, tile_n)
    dx, dw = _rms_bwd_call(x2, weight, rstd, g2, float(eps), tn,
                           interpret=not _on_tpu())
    return dx.reshape(shape), dw.astype(weight.dtype)


fused_rms_norm.defvjp(_rms_fwd, _rms_bwd)


# ---------------------------------------------------------------------------
# GSPMD-sharded entries
# ---------------------------------------------------------------------------
# A pallas_call is an opaque custom call to GSPMD: feeding it a sharded
# operand makes the partitioner all-gather the input and replicate the
# kernel. But rmsnorm and rope are token/head-local — exactly like the
# reference's per-rank fused kernels that TP runs unchanged on each shard
# (paddle/phi/kernels/fusion/gpu/rms_norm_kernel.cu, fused_rope_kernel.cu)
# — so the *_sharded entries below run the SAME kernel bodies per shard
# under shard_map (the technique parallel/context_parallel.py uses for the
# ring). Gradients are explicit custom_vjps whose backwards also run per
# shard; the only cross-shard communication in either direction is the
# psum of the (replicated) rmsnorm weight gradient.


# trace-time activity counters: tests (and doubtful users) assert the
# sharded fused path was actually taken — r4's gap was exactly a silent
# fallback to the jnp formulation under tp/cp
sharded_call_stats = {"rms": 0, "rope": 0}


def _axes_of(spec) -> tuple:
    """Flatten a PartitionSpec into the tuple of mesh axis names it uses."""
    axes = []
    for e in spec:
        if e is None:
            continue
        if isinstance(e, (tuple, list)):
            axes.extend(e)
        else:
            axes.append(e)
    return tuple(axes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def fused_rms_norm_sharded(x, weight, mesh, spec, eps: float = 1e-5,
                           tile_n=None):
    """``fused_rms_norm`` over a sharded ``x [..., D]``.

    ``spec`` is x's PartitionSpec on ``mesh``; the normalised (last) dim
    must be unsharded — every other dim may shard freely (dp on batch,
    tp/cp on sequence). ``weight`` is replicated; its gradient is psum'd
    over spec's axes.
    """
    if len(spec) == x.ndim and spec[-1] is not None:
        # (a spec shorter than x.ndim leaves trailing dims unsharded)
        raise ValueError(
            f"rms_norm reduces over the last dim but spec {spec} shards it")
    sharded_call_stats["rms"] += 1

    def body(xl, wl):
        return fused_rms_norm(xl, wl, eps, tile_n)

    return shard_map(body, mesh=mesh, in_specs=(spec, P(None)),
                     out_specs=spec, check_vma=False)(x, weight)


def _rms_sharded_fwd(x, weight, mesh, spec, eps, tile_n):
    return (fused_rms_norm_sharded(x, weight, mesh, spec, eps, tile_n),
            (x, weight))


def _rms_sharded_bwd(mesh, spec, eps, tile_n, res, g):
    x, weight = res
    axes = _axes_of(spec)

    def body(xl, wl, gl):
        x2 = xl.reshape(-1, xl.shape[-1])
        g2 = gl.reshape(-1, gl.shape[-1])
        xf = x2.astype(jnp.float32)
        # rstd recomputed per shard (one elementwise pass) rather than
        # carried across the shard_map boundary as a residual
        rstd = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        tn = _pick_row_tile(x2.shape[0], x2.shape[1], x2.dtype, tile_n)
        dx, dw = _rms_bwd_call(x2, wl, rstd, g2, float(eps), tn,
                               interpret=not _on_tpu())
        if axes:
            dw = jax.lax.psum(dw, axes)
        return dx.reshape(xl.shape), dw

    dx, dw = shard_map(body, mesh=mesh, in_specs=(spec, P(None), spec),
                       out_specs=(spec, P(None)),
                       check_vma=False)(x, weight, g)
    return dx, dw.astype(weight.dtype)


fused_rms_norm_sharded.defvjp(_rms_sharded_fwd, _rms_sharded_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def fused_rope_sharded(q, k, positions, mesh, q_spec, k_spec, pos_spec,
                       theta: float = 10000.0):
    """``fused_rope`` over sharded ``q [B,T,H,Dh]`` / ``k [B,T,Hkv,Dh]``.

    Rope is token- and head-local, so any sharding of the B/T/H dims works
    as long as ``positions [B, T]`` is sharded consistently with q/k's
    B/T dims (``pos_spec``); the Dh dim must be unsharded.
    """
    if any(len(s) == 4 and s[-1] is not None for s in (q_spec, k_spec)):
        raise ValueError("rope rotates within Dh; the last dim of "
                         f"q_spec/k_spec must be unsharded (got {q_spec}, "
                         f"{k_spec})")
    sharded_call_stats["rope"] += 1

    def body(ql, kl, posl):
        return fused_rope(ql, kl, posl, theta)

    return tuple(shard_map(
        body, mesh=mesh, in_specs=(q_spec, k_spec, pos_spec),
        out_specs=(q_spec, k_spec), check_vma=False)(q, k, positions))


def _rope_sharded_fwd(q, k, positions, mesh, q_spec, k_spec, pos_spec,
                      theta):
    out = fused_rope_sharded(q, k, positions, mesh, q_spec, k_spec,
                             pos_spec, theta)
    return out, positions


def _rope_sharded_bwd(mesh, q_spec, k_spec, pos_spec, theta, positions, g):
    gq, gk = g

    def body(gql, gkl, posl):
        # rotation transpose == rotation by the negated angle
        tt = 256 if gql.shape[1] % 256 == 0 else gql.shape[1]
        return _rope_call(gql, gkl, -posl, float(theta), tt,
                          interpret=not _on_tpu())

    dq, dk = shard_map(body, mesh=mesh, in_specs=(q_spec, k_spec, pos_spec),
                       out_specs=(q_spec, k_spec),
                       check_vma=False)(gq, gk, positions)
    return dq, dk, None


fused_rope_sharded.defvjp(_rope_sharded_fwd, _rope_sharded_bwd)


# ---------------------------------------------------------------------------
# kernel-audit registration (analysis/kernel_audit.py)
# ---------------------------------------------------------------------------
# Two geometry shapes under one registration: rms geometries use the
# autotune lookup kwargs (rows/d/dtype — winners.json entries audit
# directly, fwd AND bwd kernels), rope geometries carry rope_* keys and
# audit the rotation kernel.

AUDIT_KIND = "fused_rms_norm"
AUDIT_GEOM_KEYS = ("rows", "d", "dtype")
AUDIT_CONFIG_KEYS = ("tile_n",)
AUDIT_GEOMETRIES = (
    # 7B-class train step: [B*T, D] rows into the norm
    {"rows": 2048, "d": 4096, "dtype": "bfloat16"},
    {"rope_batch": 2, "rope_seq": 512, "rope_heads": 8,
     "rope_kv_heads": 4, "rope_head_dim": 128, "dtype": "bfloat16"},
)


def audit_launches(geom, config=None):
    dt = jnp.dtype(geom["dtype"])
    if "rope_batch" in geom:
        B, T = int(geom["rope_batch"]), int(geom["rope_seq"])
        H, Hkv = int(geom["rope_heads"]), int(geom["rope_kv_heads"])
        dh = int(geom["rope_head_dim"])
        tt = 256 if T % 256 == 0 else T
        q = jax.ShapeDtypeStruct((B, T, H, dh), dt)
        k = jax.ShapeDtypeStruct((B, T, Hkv, dh), dt)
        pos = jax.ShapeDtypeStruct((B, T), jnp.int32)
        fn = functools.partial(_rope_call, theta=10000.0, tile_t=tt,
                               interpret=False)
        return [(f"rope[tile_t={tt}]", fn, (q, k, pos))]
    n, d = int(geom["rows"]), int(geom["d"])
    if config is not None and "tile_n" in config:
        tn = int(config["tile_n"])
    else:
        tn = _row_tile(n, d)
    x = jax.ShapeDtypeStruct((n, d), dt)
    w = jax.ShapeDtypeStruct((d,), dt)
    rstd = jax.ShapeDtypeStruct((n, 1), jnp.float32)
    g = jax.ShapeDtypeStruct((n, d), dt)
    fwd = functools.partial(_rms_fwd_call, eps=1e-5, tile_n=tn,
                            interpret=False)
    bwd = functools.partial(_rms_bwd_call, eps=1e-5, tile_n=tn,
                            interpret=False)
    return [(f"rms_fwd[tile_n={tn}]", fwd, (x, w)),
            (f"rms_bwd[tile_n={tn}]", bwd, (x, w, rstd, g))]
