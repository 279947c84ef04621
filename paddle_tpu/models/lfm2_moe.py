"""LFM2-MoE-family decoder (``model_type`` ``lfm2_moe``): a stack of
layers of THREE kinds that follows a published pattern.

Every layer is an OPERATOR and a FEED-FORWARD, both pre-norm and
residual:

* operator ``full_attention``: GQA with an RMSNorm over each head of q
  and k (one weight vector for all heads) before half-split rotary
  embedding; K and V live in the paged pool;
* operator ``conv``: the gated short convolution — ``[B, C, x] =
  split3(a W_in)``, ``u = B * x``, a causal depthwise convolution of
  ``conv_L_cache`` taps over ``u``, ``o = (C * c) W_out``. Its state is
  the last ``conv_L_cache - 1`` values of ``u``: a fixed row a slot,
  not pages;
* feed-forward: a dense SwiGLU in the first ``num_dense_layers``
  layers, then routed experts behind a SIGMOID router whose per-expert
  bias enters the CHOICE of experts only; the weights are the unbiased
  sigmoids of the chosen experts, renormalised. No shared expert.

Parameters are stacked BY KIND, each kind's layers in model order (a
layer is its operator's ordinal and its feed-forward's ordinal)::

    embed [V, D]   final_norm [D]   lm_head [D, V]
    attn   operator_norm [La, D]  wq [La, D, H*Dh]  wk, wv [La, D, Hkv*Dh]
           wo [La, H*Dh, D]  q_norm, k_norm [La, Dh]
    conv   operator_norm [Lc, D]  in_proj [Lc, D, 3D]  conv_w [Lc, D, K]
           out_proj [Lc, D, D]          (conv_w[:, K-1] meets the current token)
    dense  ffn_norm [Ld, D]  w_gate, w_up [Ld, D, F]  w_down [Ld, F, D]
    moe    ffn_norm [Lm, D]  router [Lm, D, E] f32  expert_bias [Lm, E] f32
           experts.w_gate, .w_up [Lm, E, D, Fm]  .w_down [Lm, E, Fm, D]

THE WALK PIECES that do not know this model's kinds — ``LayerKind``,
``Group``, ``layer_groups``, ``_layer_params``, the loop over the groups
(``walk_groups``) and the convolution's window a slot (``earlier_rows``,
``window_rows``) — live in ``models/layer_walk.py`` since PR 38, shared
with ``models/granite_hybrid.py``; the names this module always had
(``LayerKind``, ``Group``, ``_layer_params``, ``layer_kinds``,
``layer_groups``) stay importable from here.

LAYER KINDS AND THEIR CACHE STATE (ROADMAP D1, begun here): a kind says
what it keeps between ticks (``KINDS``): pages of K and V
(``full_attention``) or a fixed row a slot (``conv``).
``init_serving_pages`` builds ONE pytree from the model's kinds —
``k_pages`` / ``v_pages`` over the attention layers only, ``conv_state``
``[Lc, S + 1, K - 1, D]`` over the conv layers (row ``S`` is the trash
row padding tokens read) — and the serving entry points
(``models/serving_tick.py``, every model's) take and return that pytree
whole; ``serving_cache_kinds`` (the record's ``kinds``) tells the engine
what the kinds keep. A kind whose state is a row a slot cannot be
rebuilt from a prefix's pages, so the engine serves such a model without
the prefix cache, chain migration, the cold tier and speculation
(``serving/engine.py``).

THE TICK: embedding, final norm, head, fused sampler and the fused
decode tail are ``models/serving_tick.py``'s; this module
brings the layer WALK (``_walk``): the leading dense layers, then ONE
scan over the periods of the layer pattern whose body is the period's
layers, then the trailing part of a period (``layer_groups``) — a
handful of loops whatever the depth.

The MoE feed-forward goes through ``incubate.moe.functional.moe_ffn``
at a capacity equal to the cohort (C = N, nothing dropped), as
``models/qwen2_moe.py``'s serving block does: the same expert einsums.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..incubate.moe.functional import moe_ffn
from ..ops.pallas.ragged_paged_attention import lane_pack_factor
from . import layer_walk as _lw
from .layer_walk import (Group, LayerKind,  # noqa: F401  (this module's names)
                         _layer_params)
from .llama import _mm, rms_norm, rope

ATTN, CONV = "full_attention", "conv"
KINDS = {ATTN: LayerKind(ATTN, "pages"), CONV: LayerKind(CONV, "slot_rows")}


def _published_layer_types(n: int) -> Tuple[str, ...]:
    """One attention layer in four, the third of each period."""
    return tuple(ATTN if i % 4 == 2 else CONV for i in range(n))


@dataclasses.dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776          # the dense layers' SwiGLU
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_dense_layers: int = 2
    num_experts: int = 64
    num_experts_per_tok: int = 4
    layer_types: Optional[Tuple[str, ...]] = None   # None: the published
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    max_position_embeddings: int = 128000
    dtype: Any = jnp.bfloat16
    use_flash_attention: bool = True

    def __post_init__(self):
        types = (self.layer_types if self.layer_types is not None
                 else _published_layer_types(self.num_hidden_layers))
        self.layer_types = tuple(types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        bad = set(self.layer_types) - set(KINDS)
        if bad:
            raise ValueError(f"unknown layer kinds {sorted(bad)}; known: "
                             f"{sorted(KINDS)}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def rms_norm_eps(self) -> float:     # the shared tick's name for it
        return self.norm_eps

    @staticmethod
    def tiny(**kw) -> "Lfm2MoeConfig":
        kw.setdefault("num_hidden_layers", 6)
        kw.setdefault("layer_types", (CONV, ATTN, CONV, CONV, ATTN, CONV)[
            :kw["num_hidden_layers"]])
        return Lfm2MoeConfig(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_attention_heads=4,
            num_key_value_heads=2, num_dense_layers=1, num_experts=8,
            num_experts_per_tok=2, max_position_embeddings=256), **kw})


# ------------------------------------------------------------ the stack ----

def layer_kinds(cfg: Lfm2MoeConfig):
    """``[(operator, feed-forward, operator's ordinal, feed-forward's
    ordinal)]`` for every layer, in order."""
    return _lw.layer_kinds(
        cfg.layer_types,
        lambda i: "dense" if i < cfg.num_dense_layers else "moe")


def layer_groups(cfg: Lfm2MoeConfig):
    """The stack as the walk takes it (``layer_walk.layer_groups``): the
    leading dense layers (one group, walked once), the whole periods of
    the expert layers' pattern (one group, SCANNED), the trailing part
    of a period (one group, walked once)."""
    return _lw.layer_groups(layer_kinds(cfg), cfg.num_dense_layers)


def init_params(cfg: Lfm2MoeConfig, key: jax.Array) -> Dict[str, Any]:
    D, V, K = cfg.hidden_size, cfg.vocab_size, cfg.conv_L_cache
    H, Hkv, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    E, F, Fm = cfg.num_experts, cfg.intermediate_size, cfg.moe_intermediate_size
    kinds = layer_kinds(cfg)
    La = sum(op == ATTN for op, *_ in kinds)
    Lc = len(kinds) - La
    Ld = sum(ffn == "dense" for _, ffn, *_ in kinds)
    Lm = len(kinds) - Ld
    ks = iter(jax.random.split(key, 20))

    def init(shape, fan_in, dtype=cfg.dtype):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * (1.0 / np.sqrt(fan_in))).astype(dtype)

    return {
        "embed": init((V, D), D), "lm_head": init((D, V), D),
        "final_norm": jnp.ones((D,), cfg.dtype),
        "attn": {
            "operator_norm": jnp.ones((La, D), cfg.dtype),
            "wq": init((La, D, H * Dh), D), "wk": init((La, D, Hkv * Dh), D),
            "wv": init((La, D, Hkv * Dh), D), "wo": init((La, H * Dh, D),
                                                         H * Dh),
            "q_norm": jnp.ones((La, Dh), cfg.dtype),
            "k_norm": jnp.ones((La, Dh), cfg.dtype)},
        "conv": {
            "operator_norm": jnp.ones((Lc, D), cfg.dtype),
            "in_proj": init((Lc, D, 3 * D), D),
            "conv_w": init((Lc, D, K), K),
            "out_proj": init((Lc, D, D), D)},
        "dense": {
            "ffn_norm": jnp.ones((Ld, D), cfg.dtype),
            "w_gate": init((Ld, D, F), D), "w_up": init((Ld, D, F), D),
            "w_down": init((Ld, F, D), F)},
        "moe": {
            "ffn_norm": jnp.ones((Lm, D), cfg.dtype),
            # float32, as models/qwen2_moe.py keeps its router
            "router": init((Lm, D, E), 2500.0, jnp.float32),
            "expert_bias": init((Lm, E), 100.0, jnp.float32),
            "experts": {"w_gate": init((Lm, E, D, Fm), D),
                        "w_up": init((Lm, E, D, Fm), D),
                        "w_down": init((Lm, E, Fm, D), Fm)}},
    }


def abstract_params(cfg: Lfm2MoeConfig):
    """ShapeDtypeStruct pytree of ``init_params`` (tracing-only
    tooling; see models/llama.py abstract_params)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


# ------------------------------------------------------------ the layers ----

def _attn_op(lp, h, positions, cfg: Lfm2MoeConfig, attn_fn):
    """``h [B, T, D]``; ``attn_fn(q, k, v) -> o`` owns the cache."""
    B, T, _ = h.shape
    H, Hkv, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    with jax.named_scope("attn.qkv_rope"):
        x = rms_norm(h, lp["operator_norm"], cfg.norm_eps)
        q = _mm(x, lp["wq"]).reshape(B, T, H, Dh)
        k = _mm(x, lp["wk"]).reshape(B, T, Hkv, Dh)
        v = _mm(x, lp["wv"]).reshape(B, T, Hkv, Dh)
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
        q, k = rope(q, k, positions, cfg.rope_theta, Dh)
    o = attn_fn(q, k, v)
    with jax.named_scope("attn.out"):
        return h + _mm(o.reshape(B, T, H * Dh), lp["wo"])


def _conv_op(lp, h, cfg: Lfm2MoeConfig, earlier_fn):
    """``h [..., T, D]``. ``earlier_fn(u) -> [u_{t-1}, ..., u_{t-K+1}]``
    owns the state: ``u`` shifted by 1 .. K-1 positions, zeros (or the
    cached state) where the shift leaves the span. ``u`` is rounded to
    the model's dtype before the taps, so that a value read back from
    the state equals the value the stream held; the taps accumulate in
    float32."""
    K = cfg.conv_L_cache
    with jax.named_scope("shortconv.in"):
        x = rms_norm(h, lp["operator_norm"], cfg.norm_eps)
        b, c, xg = jnp.split(_mm(x, lp["in_proj"]), 3, axis=-1)
    with jax.named_scope("shortconv.mix"):
        u = b * xg
        w = lp["conv_w"].astype(jnp.float32)                    # [D, K]
        acc = w[:, K - 1] * u.astype(jnp.float32)
        for d, prev in enumerate(earlier_fn(u), start=1):
            acc = acc + w[:, K - 1 - d] * prev.astype(jnp.float32)
        y = (c.astype(jnp.float32) * acc).astype(h.dtype)
    with jax.named_scope("shortconv.out"):
        return h + _mm(y, lp["out_proj"])


def _ffn(lp, h, cfg: Lfm2MoeConfig, kind: str):
    if kind == "dense":
        with jax.named_scope("mlp"):
            x = rms_norm(h, lp["ffn_norm"], cfg.norm_eps)
            return h + _mm(jax.nn.silu(_mm(x, lp["w_gate"]))
                           * _mm(x, lp["w_up"]), lp["w_down"])
    with jax.named_scope("moe.router"):     # the norm that feeds it
        x = rms_norm(h, lp["ffn_norm"], cfg.norm_eps)
    ex = lp["experts"]
    # C = N: every expert's capacity is the whole cohort, nothing drops
    routed, _ = moe_ffn(
        x, lp["router"], ex["w_gate"], ex["w_up"], ex["w_down"],
        top_k=cfg.num_experts_per_tok,
        capacity_factor=cfg.num_experts / cfg.num_experts_per_tok,
        score_fn="sigmoid",
        select_bias=lp["expert_bias"] if cfg.use_expert_bias else None,
        normalize_topk=cfg.norm_topk_prob)
    if cfg.routed_scaling_factor != 1.0:
        routed = routed * cfg.routed_scaling_factor
    return h + routed


def _shifted(u, earlier=None):
    """``u [B, T, D]`` shifted by 1 .. K-1 positions along ``T``, the
    ``K - 1`` positions before the span taken from ``earlier [B, K-1,
    D]`` (oldest first)."""
    T = u.shape[1]
    n = earlier.shape[1]
    ext = jnp.concatenate([earlier.astype(u.dtype), u], axis=1)
    return [ext[:, n - d:n - d + T] for d in range(1, n + 1)], ext[:, -n:]


# ----------------------------------------------------- whole sequences ----

def forward(params, tokens, cfg: Lfm2MoeConfig):
    """tokens ``[B, T]`` -> logits ``[B, T, V]``: the whole sequence, no
    cache, layer by layer (the tests' and tools' path; serving walks
    ``layer_groups``)."""
    logits, _ = forward_with_cache(params, tokens, None, 0, cfg,
                                   every_position=True)
    return logits


def init_kv_cache(cfg: Lfm2MoeConfig, batch_size: int, max_len: int):
    kinds = layer_kinds(cfg)
    La = sum(op == ATTN for op, *_ in kinds)
    shape = (La, batch_size, max_len, cfg.num_key_value_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype),
            "conv": jnp.zeros((len(kinds) - La, batch_size,
                               cfg.conv_L_cache - 1, cfg.hidden_size),
                              cfg.dtype)}


def forward_with_cache(params, tokens, cache, pos0, cfg: Lfm2MoeConfig,
                       every_position: bool = False):
    """tokens ``[B, T]`` at positions ``pos0..`` -> (last-position
    logits ``[B, V]``, updated cache): the dense-cache counterpart of
    the serving tick (``models/llama.py forward_with_cache``'s
    contract). ``cache=None`` is a whole sequence from position 0."""
    from .llama import _cached_attention
    from ..ops.pallas.flash_attention import flash_attention as _fa
    B, T = tokens.shape
    h = params["embed"].astype(cfg.dtype)[tokens]
    positions = pos0 + jnp.broadcast_to(jnp.arange(T), (B, T))
    fresh = cache is None or (isinstance(pos0, int) and pos0 == 0)
    new = None if cache is None else dict(cache)
    zeros = jnp.zeros((B, cfg.conv_L_cache - 1, cfg.hidden_size), cfg.dtype)
    for op, ffn, i_op, i_ffn in layer_kinds(cfg):
        lp = _layer_params(params[{ATTN: "attn", CONV: "conv"}[op]], i_op)
        if op == ATTN:
            def attn_fn(q, k, v, i=i_op):
                if new is not None:
                    new["k"] = new["k"].at[i].set(lax.dynamic_update_slice(
                        new["k"][i], k.astype(cfg.dtype), (0, pos0, 0, 0)))
                    new["v"] = new["v"].at[i].set(lax.dynamic_update_slice(
                        new["v"][i], v.astype(cfg.dtype), (0, pos0, 0, 0)))
                if fresh:
                    return _fa(q, k, v, causal=True,
                               impl="auto" if cfg.use_flash_attention
                               else "dense")
                return _cached_attention(q, new["k"][i], new["v"][i], pos0,
                                         cfg)
            h = _attn_op(lp, h, positions, cfg, attn_fn)
        else:
            def earlier_fn(u, i=i_op):
                prevs, last = _shifted(u, zeros if fresh else new["conv"][i])
                if new is not None:
                    new["conv"] = new["conv"].at[i].set(last)
                return prevs
            h = _conv_op(lp, h, cfg, earlier_fn)
        h = _ffn(_layer_params(params[ffn], i_ffn), h, cfg, ffn)
    if not every_position:
        h = h[:, -1]
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return _mm(h, params["lm_head"]).astype(jnp.float32), new


def generate(params, prompt, cfg: Lfm2MoeConfig, max_new_tokens: int, *,
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

def serving_cache_kinds(cfg: Lfm2MoeConfig):
    """Every layer's kind, in order: what the engine reads to know that
    this model's step functions take the whole cache pytree, and which
    of its layers keep state that pages cannot rebuild."""
    return tuple(KINDS[t] for t in cfg.layer_types)


def init_serving_pages(cfg: Lfm2MoeConfig, total_pages: int, page_size: int,
                       max_batch: int, max_span: int = 1):
    """The model's cache, ONE pytree built from its kinds: ``k_pages`` /
    ``v_pages`` over the attention layers only (page 0 = trash), lane-
    packed where the head size is under the chip's 128 lanes (``[La,
    Hkv/f, P, ps, f*Dh]``: ``ops/pallas/ragged_paged_attention.py
    lane_pack_factor``), and ``conv_state [Lc, S + 1, K - 1, D]``, the
    conv layers' last ``K - 1`` values of ``u`` a slot, oldest first
    (row ``S`` = trash: padding tokens read it, nothing writes it)."""
    kinds = layer_kinds(cfg)
    La = sum(op == ATTN for op, *_ in kinds)
    Hkv, Dh = cfg.num_key_value_heads, cfg.head_dim
    f = lane_pack_factor(Dh, Hkv)
    shape = (La, Hkv // f, total_pages, page_size, f * Dh)
    return {"k_pages": jnp.zeros(shape, cfg.dtype),
            "v_pages": jnp.zeros(shape, cfg.dtype),
            "conv_state": jnp.zeros(
                (len(kinds) - La, max_batch + 1, cfg.conv_L_cache - 1,
                 cfg.hidden_size), cfg.dtype)}


def _walk(params, h, cache, meta, cfg: Lfm2MoeConfig, tq, attn_impl):
    """The tick's layer walk (``models/llama.py _walk_one_kind``'s
    contract) over ``layer_groups``: one loop a group, the pools and the
    conv state in its carry.

    CONV STATE IN A RAGGED TICK. A slot's span is contiguous in the
    packed stream, so for a token at span offset ``j`` the value ``d``
    positions back is the stream's ``d`` rows up when ``j >= d`` and
    else row ``K - 1 - d + j`` of the slot's state; whatever lies before
    position 0 is zero BY POSITION (``tok_pos``), so a slot needs no
    reset when it changes hands. After the layer the row of every slot
    with ``q_len > 0`` holds its span's last values (the old row's tail
    in front of them when the span is shorter than the row); idle
    slots, padding tokens and slots dead in a fused tail step (``q_len``
    0 there: mid-prefill) leave every row as it was."""
    K = cfg.conv_L_cache
    tok_slot, tok_qoff = meta["tok_slot"], meta["tok_qoff"]
    positions = meta["tok_pos"][None]
    at = _lw.kv_rows(meta, cache["k_pages"])
    q_len, last = meta["q_len"], meta["last"]
    plan = _lw.tick_plan(meta, tq, cfg.num_attention_heads,
                         cache["k_pages"])

    def attn_layer(lp, h, kp, vp, layer):
        cell = {}

        def attn_fn(q, k, v):
            o, cell["kp"], cell["vp"] = _lw.paged_kv_attend(
                q, k, v, kp, vp, layer, meta, at, plan, tq, attn_impl)
            return o

        h = _attn_op(lp, h, positions, cfg, attn_fn)
        return h, cell["kp"], cell["vp"]

    def conv_layer(lp, h, cs, layer):
        rows = lax.dynamic_index_in_dim(cs, layer, 0, keepdims=False)
        cell = {}

        def earlier_fn(u):                                      # [1, T, D]
            u = cell["u"] = u[0]
            return _lw.earlier_rows(u, rows, tok_slot, tok_qoff,
                                    meta["tok_pos"], K)

        h = _conv_op(lp, h, cfg, earlier_fn)
        with jax.named_scope("conv_state.write"):
            new = _lw.window_rows(cell["u"], rows, q_len, last, K,
                                  cs.dtype)
            cs = lax.dynamic_update_slice(cs, new[None], (layer, 0, 0, 0))
        return h, cs

    def run(group, carry, i):
        """The group's pattern once: its ``i``-th repeat."""
        h, kp, vp, cs = carry
        for op, ffn, op_at, ffn_at in group.layers:
            i_op = op_at + i * group.stride[op]
            if op == ATTN:
                h, kp, vp = attn_layer(
                    _layer_params(params["attn"], i_op), h, kp, vp,
                    jnp.asarray(i_op, jnp.int32))
            else:
                h, cs = conv_layer(_layer_params(params["conv"], i_op), h,
                                   cs, jnp.asarray(i_op, jnp.int32))
            h = _ffn(_layer_params(params[ffn],
                                   ffn_at + i * group.stride[ffn]),
                     h, cfg, ffn)
        return h, kp, vp, cs

    carry = (h, cache["k_pages"], cache["v_pages"], cache["conv_state"])
    # an operation under bare ``layers`` is a loop's own: the slicing
    # of a layer's weights out of its kind's stack
    with jax.named_scope("layers"):
        carry = _lw.walk_groups(layer_groups(cfg), carry, run)
    h, kp, vp, cs = carry
    return h, {"k_pages": kp, "v_pages": vp, "conv_state": cs}


SERVING = _lw.ServingFamily(walk=_walk, init_pages=init_serving_pages,
                            kinds=serving_cache_kinds)
