"""MiMo-V2-Flash-family decoder (Xiaomi MiMo-V2-Flash): WINDOW attention
layers with a learned sink beside FULL attention layers, query / key
heads of one size and value heads of another, rotary on part of a head
at two bases, and a sigmoid-and-bias router over experts of which this
chip holds a share.

A layer (``D`` = ``hidden_size``, RMS norms, no biases)::

    a = rms_norm(h, attn.norm);   h = h + attention(a, positions)
    m = rms_norm(h, ffn.norm);    h = h + ffn(m)

``hybrid_layer_pattern[l]`` says which attention layer ``l`` has (0 =
full, 1 = window), ``moe_layer_freq[l]`` which feed-forward (0 = a dense
SwiGLU of ``intermediate_size``, 1 = routed experts).

ATTENTION, both kinds: ``H`` query heads, q / k head size ``head_dim``
(192), v head size ``v_head_dim`` (128)::

    q = (a @ wq.T).reshape(T, H, 192);  k = (a @ wk.T).reshape(T, Hkv, 192)
    v = (a @ wv.T).reshape(T, Hkv, 128)
    q, k = rotary on their FIRST ``rotary_dim`` = int(192 x
           partial_rotary_factor) = 64 dimensions (half-split pairs
           ``(x[i], x[i + 32])``), the other 128 as they are
    z_ij = q_i . k_j / sqrt(192)
    out = concat_heads(softmax(z) @ v) * attention_value_scale @ wo

* FULL: ``num_key_value_heads`` (4) KV heads, causal over the whole
  context, rotary base ``rope_theta``.
* WINDOW: ``swa_num_key_value_heads`` (8) KV heads, query ``i`` sees
  keys ``i - sliding_window + 1 .. i``, rotary base ``swa_rope_theta``,
  and a learned SINK a head: ``p_ij = exp(z_ij - m) / (exp(s_h - m) +
  sum_j exp(z_ij - m))``: a logit that joins the denominator and takes
  no value.

EXPERTS: ``n_routed_experts`` router outputs, ``num_experts_per_tok`` a
token, scores ``sigmoid`` in float32, the choice by score +
``router_bias`` (``e_score_correction_bias``), weights the chosen scores
renormalised, no shared expert. This chip holds ``experts_held =
(first, count)`` of them (``incubate/moe/functional.py:
moe_ffn_share``): what the absent experts would add is left out, and
that partial sum goes on.

Parameters are stacked BY KIND, each kind's layers in model order::

    embed [V, D]   final_norm [D]   lm_head [D, V]
    full    norm [Lf, D]  wq [Lf, H*192, D]  wk [Lf, 4*192, D]
            wv [Lf, 4*128, D]  wo [Lf, H*128, D]
    window  the same at 8 KV heads, and sinks [Lw, H] f32
    dense   norm [Ld, D]  w_gate, w_up [Ld, D, F]  w_down [Ld, F, D]
    moe     norm [Lm, D]  router [Lm, D, E] f32  router_bias [Lm, E] f32
            experts.w_gate, .w_up [Lm, n, D, Fm]  .w_down [Lm, n, Fm, D]

(``wq`` / ``wk`` / ``wv`` are held OUTPUT-MAJOR, ``q = a @ wq.T``: the
layout the chip's compiler gives a product whose result is split into
heads of 192, 1.5 lane tiles; held input-major, a decode tick copied
every layer's three matrices, 120 MB a layer, in front of their
products: described-chip compile, PR 47.)

THE CACHE is one pytree with TWO KINDS of pool:

* the FULL layers' K and V in the PAGED pool the engine's allocator
  hands out, ``k_full [Lf, 4, P, ps, 256]`` / ``v_full [Lf, 4, P, ps,
  128]`` (``cache_page_pools``; admission, ``total_pages`` and
  ``page_utilization`` count these pages and no other). A key row is its
  192 values and 64 zeros: 192 is 1.5 of the chip's 128-lane tiles, and
  a row of 256 is what the chip's layout would pad it to anyway; bytes
  are COUNTED at the published 192.
* the WINDOW layers' K and V in a RING of their own, ``k_window [Lw, 8,
  S*R + 1, ps, 256]`` / ``v_window [..., 128]``: ``R`` pages a slot that
  come with the slot and that its context never grows. A token at
  position ``p`` of slot ``s`` lives on page ``s*R + (p // ps) % R`` at
  offset ``p % ps``: the ring is addressed IN THE PROGRAM from the
  token's position, so the window launch walks the same kernel with a
  page table built inside the tick (``j -> s*R + j % R``) and nothing on
  the host allocates. ``R = ceil((sliding_window - 1 + max_span) / ps) +
  1``: a span of ``max_span`` rows (the engine's ``prefill_chunk``)
  writes its keys BEFORE it attends, its first row still sees the 127
  keys before the span, and one more page covers a span that starts
  mid-page; so nothing a row of the tick sees is overwritten by a later
  row of the same tick. The last page is the trash page padding tokens
  write.

A prefix's full-layer pages cannot rebuild the window layers' last 128
tokens, so prefix reuse, chain export / adopt and the cold tier are off
for this family and counted (``serving/engine.py``); speculation too (a
rejected draft would have overwritten ring rows).

ATTENTION LAUNCHES go through ``ops/pallas/ragged_paged_attention.py``
(``window=``, ``sinks=``, a ``v`` pool of another head size), a span cut
into virtual slots of ``BLOCK_TOKENS`` tokens (``_span_blocks`` there:
the kernel's VMEM does not grow with the chunk).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..incubate.moe.functional import moe_ffn_share
from ..ops.pallas import ragged_paged_attention as _rpa
from . import layer_walk as _lw
from .layer_walk import COUNTS, LayerKind, PagePoolSpec, _layer_params
from .llama import _mm, rms_norm

FULL, WINDOW = "full", "window"
DENSE, MOE = "dense", "moe"
K_FULL, V_FULL = "k_full", "v_full"
K_WINDOW, V_WINDOW = "k_window", "v_window"
TICK_COUNTERS = ("moe_pairs_held", "moe_pairs_zero", "moe_pairs_absent",
                 "moe_experts_touched")
# tokens a virtual slot of a span (x G query rows a KV head: 256 rows a
# full layer's head, 128 a window layer's)
BLOCK_TOKENS = 16
# cache tokens a tile of the full layers' walk
FULL_TILE_TOKENS = 512


@dataclasses.dataclass
class MimoV2FlashConfig:
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    swa_num_key_value_heads: int = 8
    head_dim: int = 192
    v_head_dim: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 5000000.0
    swa_rope_theta: float = 10000.0
    sliding_window: int = 128
    attention_value_scale: float = 0.707
    # 0 = full, 1 = window; None: layer 0 and every sixth from 5 full
    hybrid_layer_pattern: Optional[Tuple[int, ...]] = None
    # 0 = dense SwiGLU, 1 = routed experts; None: layer 0 alone dense
    moe_layer_freq: Optional[Tuple[int, ...]] = None
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    # the routed experts this chip holds: (first, count); None = all
    experts_held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        L = self.num_hidden_layers
        if self.hybrid_layer_pattern is None:
            self.hybrid_layer_pattern = tuple(
                0 if l == 0 or l % 6 == 5 else 1 for l in range(L))
        if self.moe_layer_freq is None:
            self.moe_layer_freq = tuple(int(l > 0) for l in range(L))
        self.hybrid_layer_pattern = tuple(
            int(x) for x in self.hybrid_layer_pattern)
        self.moe_layer_freq = tuple(int(x) for x in self.moe_layer_freq)
        if (len(self.hybrid_layer_pattern) != L
                or len(self.moe_layer_freq) != L):
            raise ValueError(
                f"hybrid_layer_pattern / moe_layer_freq must name each of "
                f"the {L} layers")
        held = self.experts_held or (0, self.n_routed_experts)
        self.experts_held = (int(held[0]), int(held[1]))
        lo, n = self.experts_held
        if lo < 0 or n < 1 or lo + n > self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {self.n_routed_experts} routed "
                             f"experts")

    @property
    def rotary_dim(self) -> int:
        """Dimensions of a q / k head that turn (published: 64)."""
        return int(self.head_dim * self.partial_rotary_factor) // 2 * 2

    @property
    def key_row_width(self) -> int:
        """Lanes a key row takes in a pool (192 -> 256)."""
        return -(-self.head_dim // _rpa.LANES) * _rpa.LANES

    @property
    def sm_scale(self) -> float:
        return 1.0 / float(np.sqrt(self.head_dim))

    def kv_heads(self, kind: str) -> int:
        return (self.swa_num_key_value_heads if kind == WINDOW
                else self.num_key_value_heads)

    def theta(self, kind: str) -> float:
        return self.swa_rope_theta if kind == WINDOW else self.rope_theta

    @staticmethod
    def tiny(**kw) -> "MimoV2FlashConfig":
        """Tiny widths that keep the ratios: q / k heads 1.5 x the v
        heads, twice the KV heads in the window layers, a third of a
        head turning, a window far under the prompts."""
        return MimoV2FlashConfig(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=7,
            num_attention_heads=8, num_key_value_heads=2,
            swa_num_key_value_heads=4, head_dim=24, v_head_dim=16,
            sliding_window=8, n_routed_experts=16, num_experts_per_tok=4,
            max_position_embeddings=512, experts_held=(4, 8),
            dtype=jnp.float32), **kw})


# ------------------------------------------------------------ the stack ----

def layer_kinds(cfg: MimoV2FlashConfig):
    """``[(operator, feed-forward, operator's ordinal, feed-forward's
    ordinal)]`` for every layer."""
    ops = [WINDOW if w else FULL for w in cfg.hybrid_layer_pattern]
    return _lw.layer_kinds(
        ops, lambda i: MOE if cfg.moe_layer_freq[i] else DENSE)


def layer_groups(cfg: MimoV2FlashConfig):
    """The stack as the walk takes it (``layer_walk.layer_groups``): the
    leading dense layers, the whole periods of the pattern behind them
    (scanned where there is more than one), the rest of a period."""
    leading = next((i for i, m in enumerate(cfg.moe_layer_freq) if m),
                   cfg.num_hidden_layers)
    return _lw.layer_groups(layer_kinds(cfg), leading)


def _count(cfg, kind: str) -> int:
    return sum(kind in layer[:2] for layer in layer_kinds(cfg))


def init_params(cfg: MimoV2FlashConfig, key: jax.Array) -> Dict[str, Any]:
    D, V, H = cfg.hidden_size, cfg.vocab_size, cfg.num_attention_heads
    Dk, Dv = cfg.head_dim, cfg.v_head_dim
    F, Fm = cfg.intermediate_size, cfg.moe_intermediate_size
    E, n = cfg.n_routed_experts, cfg.experts_held[1]
    Ld, Lm = _count(cfg, DENSE), _count(cfg, MOE)
    ks = iter(jax.random.split(key, 24))

    def init(shape, fan_in, dtype=cfg.dtype):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * (1.0 / np.sqrt(fan_in))).astype(dtype)

    def attn(kind):
        L, Hkv = _count(cfg, kind), cfg.kv_heads(kind)
        p = {"norm": jnp.ones((L, D), cfg.dtype),
             "wq": init((L, H * Dk, D), D), "wk": init((L, Hkv * Dk, D), D),
             "wv": init((L, Hkv * Dv, D), D), "wo": init((L, H * Dv, D),
                                                          H * Dv)}
        if kind == WINDOW:
            p["sinks"] = init((L, H), 1.0, jnp.float32)
        return p

    return {
        "embed": init((V, D), D), "lm_head": init((D, V), D),
        "final_norm": jnp.ones((D,), cfg.dtype),
        FULL: attn(FULL), WINDOW: attn(WINDOW),
        DENSE: {"norm": jnp.ones((Ld, D), cfg.dtype),
                "w_gate": init((Ld, D, F), D), "w_up": init((Ld, D, F), D),
                "w_down": init((Ld, F, D), F)},
        MOE: {"norm": jnp.ones((Lm, D), cfg.dtype),
              "router": init((Lm, D, E), D, jnp.float32),
              "router_bias": init((Lm, E), 1e4, jnp.float32),
              "experts": {"w_gate": init((Lm, n, D, Fm), D),
                          "w_up": init((Lm, n, D, Fm), D),
                          "w_down": init((Lm, n, Fm, D), Fm)}},
    }


def abstract_params(cfg: MimoV2FlashConfig):
    """ShapeDtypeStruct pytree of ``init_params`` (tracing-only
    tooling; see models/llama.py abstract_params)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


# ------------------------------------------------------------ the layers ----

def partial_rotary(x, positions, theta: float, rotary_dim: int,
                   pad: int = 0):
    """Rotary embedding on the FIRST ``rotary_dim`` dimensions of ``x
    [..., T, heads, Dh]`` (half-split pairs ``(x[i], x[i + rotary_dim /
    2])``), the rest as they are; ``positions [..., T]``. ``pad`` zeros
    follow each head (the lanes a pool's key row is padded by: written
    by the one concatenate, no pass of their own)."""
    half = rotary_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    parts = [(x1 * cos - x2 * sin).astype(x.dtype),
             (x2 * cos + x1 * sin).astype(x.dtype), x[..., rotary_dim:]]
    if pad:
        parts.append(jnp.zeros((*x.shape[:-1], pad), x.dtype))
    return jnp.concatenate(parts, -1)


def _qkv(lp, h, positions, cfg: MimoV2FlashConfig, kind: str,
         pad: int = 0):
    """``(a's q [B, T, H, Dk + pad], k [B, T, Hkv, Dk + pad], v [B, T,
    Hkv, Dv])`` of the normed rows, q and k turned (and followed by
    ``pad`` zeros a head)."""
    B, T, _ = h.shape
    H, Hkv = cfg.num_attention_heads, cfg.kv_heads(kind)
    a = rms_norm(h, lp["norm"], cfg.rms_norm_eps)

    def heads(w, n, size):         # w output-major: a @ w.T
        return jnp.einsum("btd,od->bto", a, w).reshape(B, T, n, size)

    q = heads(lp["wq"], H, cfg.head_dim)
    k = heads(lp["wk"], Hkv, cfg.head_dim)
    v = heads(lp["wv"], Hkv, cfg.v_head_dim)
    rot = (positions, cfg.theta(kind), cfg.rotary_dim, pad)
    return partial_rotary(q, *rot), partial_rotary(k, *rot), v


def _attn_out(lp, h, o, cfg: MimoV2FlashConfig):
    """``h + (o * attention_value_scale) @ wo`` (the scale on the
    attention output: equal to scaling the values)."""
    o = o.reshape(*h.shape[:-1], -1) * jnp.asarray(
        cfg.attention_value_scale, o.dtype)
    return h + _mm(o.astype(h.dtype), lp["wo"])


def _ffn(params, kind: str, at, h, cfg: MimoV2FlashConfig, row_mask=None):
    """Layer's feed-forward at ordinal ``at`` of its kind's stack:
    ``(h', counts [4] | None)``."""
    lp = _layer_params({k: v for k, v in params[kind].items()
                        if k != "experts"}, at)
    m = rms_norm(h, lp["norm"], cfg.rms_norm_eps)
    if kind == DENSE:
        with jax.named_scope("mlp"):
            y = _mm(jax.nn.silu(_mm(m, lp["w_gate"])) * _mm(m, lp["w_up"]),
                    lp["w_down"])
        return h + y, None
    y, counts = moe_ffn_share(
        m.reshape(-1, m.shape[-1]), lp["router"], lp["router_bias"],
        params[kind]["experts"], held=cfg.experts_held,
        num_routed=cfg.n_routed_experts, top_k=cfg.num_experts_per_tok,
        scale=cfg.routed_scaling_factor, score_fn="sigmoid",
        normalize_topk=cfg.norm_topk_prob,
        layer=jnp.asarray(at, jnp.int32), row_mask=row_mask)
    return h + y.reshape(h.shape), counts


# ----------------------------------------------------- whole sequences ----

def init_kv_cache(cfg: MimoV2FlashConfig, batch_size: int, max_len: int):
    """The dense cache: every position's K and V of every layer, by
    kind (``[L_kind, B, S, Hkv, Dk | Dv]``); the window is a mask."""
    out = {}
    for kind in (FULL, WINDOW):
        L, Hkv = _count(cfg, kind), cfg.kv_heads(kind)
        out[f"k_{kind}"] = jnp.zeros(
            (L, batch_size, max_len, Hkv, cfg.head_dim), cfg.dtype)
        out[f"v_{kind}"] = jnp.zeros(
            (L, batch_size, max_len, Hkv, cfg.v_head_dim), cfg.dtype)
    return out


def _dense_attention(q, k, v, pos0, cfg: MimoV2FlashConfig, window: int,
                     sinks):
    """Attention of queries at ``pos0 ..`` over a dense ``k / v [B, S,
    Hkv, D]``, scores and softmax in float32; the sink written out."""
    B, T, H, _ = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    qg = q.reshape(B, T, Hkv, H // Hkv, -1)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k).astype(jnp.float32)
    s = s * cfg.sm_scale
    pos = (pos0 + jnp.arange(T))[:, None]
    keys = jnp.arange(S)[None, :]
    mask = keys <= pos
    if window:
        mask = mask & (keys > pos - window)
    s = jnp.where(mask, s, -1e30)
    m = s.max(-1, keepdims=True)
    if sinks is not None:
        sink = sinks.astype(jnp.float32).reshape(1, Hkv, H // Hkv, 1, 1)
        m = jnp.maximum(m, sink)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = p.sum(-1, keepdims=True)
    if sinks is not None:
        l = l + jnp.exp(sink - m)
    o = jnp.einsum("bkgts,bskd->btkgd", (p / l).astype(v.dtype), v)
    return o.reshape(B, T, H, -1)


def forward_with_cache(params, tokens, cache, pos0, cfg: MimoV2FlashConfig,
                       every_position: bool = False):
    """tokens ``[B, T]`` at positions ``pos0..`` -> (last-position
    logits ``[B, V]``, updated cache): the dense-cache counterpart of
    the serving tick. ``cache=None`` is a whole sequence from 0."""
    B, T = tokens.shape
    h = params["embed"].astype(cfg.dtype)[tokens]
    positions = pos0 + jnp.broadcast_to(jnp.arange(T), (B, T))
    new = None if cache is None else dict(cache)
    for op, ffn, op_at, ffn_at in layer_kinds(cfg):
        lp = _layer_params(params[op], op_at)
        q, k, v = _qkv(lp, h, positions, cfg, op)
        if new is not None:
            kk, vv = f"k_{op}", f"v_{op}"
            k = jax.lax.dynamic_update_slice(
                new[kk][op_at], k.astype(cfg.dtype), (0, pos0, 0, 0))
            v = jax.lax.dynamic_update_slice(
                new[vv][op_at], v.astype(cfg.dtype), (0, pos0, 0, 0))
            new[kk] = new[kk].at[op_at].set(k)
            new[vv] = new[vv].at[op_at].set(v)
        with jax.named_scope(f"attn.{op}"):
            o = _dense_attention(
                q, k, v, pos0, cfg,
                cfg.sliding_window if op == WINDOW else 0, lp.get("sinks"))
            h = _attn_out(lp, h, o.astype(h.dtype), cfg)
        h, _ = _ffn(params, ffn, ffn_at, h, cfg)
    if not every_position:
        h = h[:, -1]
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return _mm(h, params["lm_head"]).astype(jnp.float32), new


def forward(params, tokens, cfg: MimoV2FlashConfig):
    """tokens ``[B, T]`` -> logits ``[B, T, V]``: the whole sequence, no
    cache."""
    logits, _ = forward_with_cache(params, tokens, None, 0, cfg,
                                   every_position=True)
    return logits


def generate(params, prompt, cfg: MimoV2FlashConfig, max_new_tokens: int, *,
             temperature: float = 0.0, top_p: float = 1.0, top_k: int = 0,
             key=None, eos_token_id: Optional[int] = None):
    """Autoregressive decode with the dense cache (same contract as
    ``models/llama.py generate``: returns prompt + continuation)."""
    from .llama import _decode_loop
    return _decode_loop(
        lambda p, t, c, pos: forward_with_cache(p, t, c, pos, cfg),
        lambda B, L: init_kv_cache(cfg, B, L),
        params, prompt, max_new_tokens, temperature, top_p, top_k, key,
        eos_token_id)


# ---------------------------------------------------------------- serving ----

def serving_cache_kinds(cfg: MimoV2FlashConfig):
    """Every layer's attention kind, in order: a full layer keeps pages
    of the allocator's pool, a window layer a ring of its own a slot."""
    return tuple(LayerKind(op, "window_pages" if op == WINDOW else "pages")
                 for op, *_ in layer_kinds(cfg))


def cache_page_pools(cfg: MimoV2FlashConfig):
    """The pools whose pages the engine's allocator hands out: the FULL
    layers' two (page axis 2). The window pools are addressed by slot,
    not by page id, so nothing that moves pages touches them."""
    return (PagePoolSpec(K_FULL, 2), PagePoolSpec(V_FULL, 2))


def cache_window_pools(cfg: MimoV2FlashConfig):
    """The window layers' rings by name (the gauge
    ``window_pool_bytes`` is their bytes)."""
    return (K_WINDOW, V_WINDOW)


def window_ring_pages(cfg: MimoV2FlashConfig, page_size: int,
                      max_span: int) -> int:
    """Pages a slot's ring holds (module docstring)."""
    return -(-(cfg.sliding_window - 1 + int(max_span)) // page_size) + 1


def init_serving_pages(cfg: MimoV2FlashConfig, total_pages: int,
                       page_size: int, max_batch: int, max_span: int = 1):
    """The model's cache: the full layers' paged pools (page 0 = trash)
    and the window layers' rings (``max_batch`` slots of
    ``window_ring_pages`` pages, the last page trash). ``max_span``: the
    most query rows a slot brings to one tick (the engine's
    ``prefill_chunk``)."""
    Lf, Lw = _count(cfg, FULL), _count(cfg, WINDOW)
    Kw, Dv = cfg.key_row_width, cfg.v_head_dim
    Pw = max_batch * window_ring_pages(cfg, page_size, max_span) + 1

    def pool(L, heads, pages, width):
        return jnp.zeros((L, heads, pages, page_size, width), cfg.dtype)

    return {K_FULL: pool(Lf, cfg.num_key_value_heads, total_pages, Kw),
            V_FULL: pool(Lf, cfg.num_key_value_heads, total_pages, Dv),
            K_WINDOW: pool(Lw, cfg.swa_num_key_value_heads, Pw, Kw),
            V_WINDOW: pool(Lw, cfg.swa_num_key_value_heads, Pw, Dv)}


def _tiles(cfg: MimoV2FlashConfig, page_size: int, bt: int):
    """``(full, window)`` KV tiles of the walk, in pages: 512 tokens of
    a full layer's context; of a window layer's what a block of ``bt``
    tokens can see, and a page for where it starts."""
    return (max(1, FULL_TILE_TOKENS // page_size),
            -(-(cfg.sliding_window - 1 + bt) // page_size) + 1)


def cache_page_copies(cfg: MimoV2FlashConfig, cache, pages_per_slot: int,
                      tq: int) -> int:
    """Copies a tick's FULL-layer launches start for ONE live page, over
    those layers (a span's blocks after its first re-walk its pages;
    those are not counted here)."""
    Lf, Hkv, _, ps, Kw = cache[K_FULL].shape
    bt = min(int(tq), BLOCK_TOKENS)
    return Lf * _rpa.page_copies(
        Hkv, pages_per_slot, ps, Kw, cache[K_FULL].dtype,
        rows=bt * (cfg.num_attention_heads // Hkv),
        kv_tile_pages=_tiles(cfg, ps, bt)[0], v_head_dim=cfg.v_head_dim)


def _walk(params, h, cache, meta, cfg: MimoV2FlashConfig, tq, attn_impl):
    """The tick's layer walk (``models/llama.py _walk_one_kind``'s
    contract) over ``layer_groups``, both kinds of pool and the tick's
    counts in its carry. A slot's rows are CONTIGUOUS in the packed
    stream: they are ``last - q_len + 1 .. last``."""
    S = meta["q_len"].shape[0]
    tok_slot, tok_qoff = meta["tok_slot"], meta["tok_qoff"]
    tok_pos = meta["tok_pos"]
    positions = tok_pos[None]
    q_len, kv_len = meta["q_len"], meta["kv_len"]
    real = tok_slot < S
    ps = cache[K_FULL].shape[-2]
    pps = meta["tables"].shape[1]
    W = cfg.sliding_window
    ring = (cache[K_WINDOW].shape[2] - 1) // S
    if (ring - 1) * ps < W - 1 + int(tq):
        raise ValueError(
            f"a span of {tq} rows needs a ring of "
            f"{window_ring_pages(cfg, ps, tq)} pages a slot; the window "
            f"pool holds {ring} (init_serving_pages' max_span)")
    pad = cfg.key_row_width - cfg.head_dim
    bt = min(int(tq), BLOCK_TOKENS)
    tiles = dict(zip((FULL, WINDOW), _tiles(cfg, ps, bt)))
    # where a token's K and V land: the allocator's page for a full
    # layer; for a window layer its slot's ring, by position
    at = {FULL: (meta["tok_page"][:, None], meta["tok_off"][:, None]),
          WINDOW: (jnp.where(real, tok_slot * ring + (tok_pos // ps) % ring,
                             S * ring)[:, None],
                   jnp.where(real, tok_pos % ps, 0)[:, None])}
    # the window launch's page table: logical page j of slot s is the
    # ring's page j mod R
    tables = {FULL: meta["tables"],
              WINDOW: (jnp.arange(S, dtype=jnp.int32)[:, None] * ring
                       + jnp.arange(pps, dtype=jnp.int32)[None] % ring)}

    # what the kernel's path needs of the packing, once a kind for all
    # its layers (a span enters as virtual slots of BLOCK_TOKENS tokens)
    plans = {kind: _lw.tick_plan(
        meta, tq, cfg.num_attention_heads, cache[pool], tables[kind],
        BLOCK_TOKENS) for kind, pool in ((FULL, K_FULL), (WINDOW, K_WINDOW))}

    def attention(kind, lp, h, kp, vp, layer):
        heads = jnp.arange(cfg.kv_heads(kind), dtype=jnp.int32)[None]
        q, k, v = _qkv(lp, h, positions, cfg, kind, pad)
        page, off = at[kind]
        with jax.named_scope("window_pool.write" if kind == WINDOW
                             else "kv_pool.write"):
            kp = kp.at[layer, heads, page, off].set(k[0].astype(kp.dtype))
            vp = vp.at[layer, heads, page, off].set(v[0].astype(vp.dtype))
        o = _rpa.ragged_paged_attention_packed(
            q[0], kp, vp, tok_slot,
            tok_qoff, q_len, kv_len, tables[kind], tq=tq,
            sm_scale=cfg.sm_scale, impl=attn_impl,
            kv_tile_pages=tiles[kind], layer=layer,
            window=W if kind == WINDOW else 0, sinks=lp.get("sinks"),
            plan=plans[kind])
        return _attn_out(lp, h, o[None].astype(h.dtype), cfg), kp, vp

    def run(group, carry, i):
        """The group's pattern once: its ``i``-th repeat."""
        h, pools, counts = carry
        pools = dict(pools)
        for op, ffn, op_at, ffn_at in group.layers:
            i_op = op_at + i * group.stride[op]
            kk, vv = f"k_{op}", f"v_{op}"
            with jax.named_scope(f"attn.{op}"):
                h, pools[kk], pools[vv] = attention(
                    op, _layer_params(params[op], i_op), h, pools[kk],
                    pools[vv], jnp.asarray(i_op, jnp.int32))
            h, c = _ffn(params, ffn, ffn_at + i * group.stride[ffn], h, cfg,
                        row_mask=real)
            if c is not None:
                counts = counts + c
        return h, pools, counts

    counts = cache.get(COUNTS, jnp.zeros((len(TICK_COUNTERS),), jnp.int32))
    pools = {k: cache[k] for k in (K_FULL, V_FULL, K_WINDOW, V_WINDOW)}
    with jax.named_scope("layers"):
        h, pools, counts = _lw.walk_groups(layer_groups(cfg),
                                           (h, pools, counts), run)
    new = dict(pools)
    if COUNTS in cache:
        new[COUNTS] = counts
    return h, new


SERVING = _lw.ServingFamily(
    walk=_walk, init_pages=init_serving_pages, kinds=serving_cache_kinds,
    page_pools=cache_page_pools, window_pools=cache_window_pools,
    tick_pool=K_FULL, counters=TICK_COUNTERS, page_copies=cache_page_copies)
