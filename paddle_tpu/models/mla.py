"""Multi-head LATENT attention's projections, shared by the families
that hold them (``models/longcat_flash.py`` serves the absorbed form
over latent pages; ``models/joyai_flash.py`` trains the expanded form
through splash): a low-rank query and a low-rank key/value latent, each
behind a norm of its own, one rotary key for all heads, rotary on
INTERLEAVED pairs.

A sublayer's leaves (``H`` heads, ranks ``Rq`` / ``Rkv``, head parts
``nope`` / ``rope`` / ``v``)::

    input_norm [D]  wq_a [D, Rq]  q_a_norm [Rq]
    wq_nope [H*nope, Rq]  wq_rope [H*rope, Rq]      (output-major)
    wkv_a [D, Rkv]  wk_rope [D, rope]  kv_a_norm [Rkv]
    w_uk [H, nope, Rkv]  w_uv [H, Rkv, v]  wo [H*v, D]

and what a config gives: ``num_attention_heads``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``rms_norm_eps``, ``rope_theta``, ``q_scale`` /
``kv_scale`` (1.0 where the published model scales neither latent).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .llama import _mm, rms_norm


def rope_interleaved(x, positions, theta: float):
    """Rotary embedding on INTERLEAVED pairs ``(x[2i], x[2i+1])`` of the
    last axis; ``x [..., T, (heads,) R]`` with ``positions`` broadcast
    over the heads."""
    R = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, R, 2, dtype=jnp.float32) / R))
    ang = positions.astype(jnp.float32)[..., None] * inv        # [..., T, R/2]
    if x.ndim == ang.ndim + 1:                                  # a head axis
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], R // 2, 2)
    x0, x1 = xf[..., 0], xf[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def mla_qkv(lp, h, positions, cfg, norm=None):
    """The sublayer's projections: ``(q_n [.., T, H, nope], q_r [.., T,
    H, rope], c_kv [.., T, Rkv], k_r [.., T, rope])``. ``norm(x, w)``:
    the rms-norm to use (a trainer's fused kernel); the plain one
    where none is given."""
    H, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    if norm is None:
        norm = lambda x, w: rms_norm(x, w, cfg.rms_norm_eps)
    a = norm(h, lp["input_norm"])
    with jax.named_scope("attn.mla.q"):
        c_q = norm(_mm(a, lp["wq_a"]), lp["q_a_norm"])
        q_n = jnp.einsum("...r,nr->...n", c_q, lp["wq_nope"]).reshape(
            *h.shape[:-1], H, nope)
        q_r = jnp.einsum("...r,nr->...n", c_q, lp["wq_rope"]).reshape(
            *h.shape[:-1], H, cfg.qk_rope_head_dim)
        if cfg.q_scale != 1.0:
            q_n = q_n * jnp.asarray(cfg.q_scale, q_n.dtype)
            q_r = q_r * jnp.asarray(cfg.q_scale, q_r.dtype)
        q_r = rope_interleaved(q_r, positions, cfg.rope_theta)
    with jax.named_scope("attn.mla.kv"):
        c_kv = norm(_mm(a, lp["wkv_a"]), lp["kv_a_norm"])
        if cfg.kv_scale != 1.0:
            c_kv = c_kv * jnp.asarray(cfg.kv_scale, c_kv.dtype)
        k_r = rope_interleaved(_mm(a, lp["wk_rope"]), positions,
                               cfg.rope_theta)
    return q_n, q_r, c_kv, k_r
