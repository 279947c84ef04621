"""LongCat-Flash-family decoder (Meituan LongCat-Flash-Chat): multi-head
LATENT attention (MLA), a SHORTCUT-CONNECTED mixture of experts and
IDENTITY ("zero-computation") experts, served as ONE CHIP'S SHARE of an
expert-parallel deployment.

A layer holds TWO attention sublayers, TWO dense SwiGLUs and ONE routed
block whose input is taken after the first attention and whose result is
added at the END of the layer (the shortcut: in a deployment the
experts' exchange overlaps the second attention and SwiGLU)::

    for i in (0, 1):
        a = rms_norm(h, input_norm[2l+i]);   h = h + MLA[2l+i](a, positions)
        m = rms_norm(h, post_norm[2l+i])
        if i == 0: s = routed[l](m)
        h = h + swiglu(m, dense[2l+i])
    h = h + s

``MLA(a)``, ``H`` heads, ranks ``q_lora_rank`` / ``kv_lora_rank``, head
parts ``nope`` / ``rope`` / ``v``::

    c_q  = rms_norm(a @ wq_a, q_a_norm)
    q    = (c_q @ wq_b).reshape(T, H, nope + rope) * sqrt(D / q_lora_rank)
           # wq_b a head = [wq_nope | wq_rope]
    c_kv = rms_norm(a @ wkv_a, kv_a_norm) * sqrt(D / kv_lora_rank)
    k_r  = rope(a @ wk_rope);  q_r = rope(q[..., nope:])      # interleaved pairs
    k_n[h] = c_kv @ w_uk[h].T;  v[h] = c_kv @ w_uv[h]           # kv_b_proj a head
    p = causal_softmax_f32((q_n . k_n + q_r . k_r) / sqrt(nope + rope))
    out = concat_heads(p @ v) @ wo

What a token leaves in the cache is ``(c_kv, k_r)``: ``kv_lora_rank +
rope`` values a sublayer (576: 1152 B against the 4096 B of an 8-KV-head
GQA layer). Serving computes the ABSORBED form: ``q_abs[h] = q_n[h] @
w_uk[h]``, scores ``q_abs . c_kv + q_r . k_r``, ``o_lat = p @ c_kv``,
``o[h] = o_lat[h] @ w_uv[h]`` — attention
over the latent pages themselves (``ops/pallas/mla_paged_attention.py``),
no per-head K or V ever materialised. ``forward_with_cache`` computes the
EXPANDED form over a dense latent cache (the tests hold the two equal).

``routed(m)``: ``n_routed_experts + zero_expert_num`` router outputs,
``moe_topk`` a token, float32 softmax over all of them, a bias that
enters the choice only, weights ``routed_scaling_factor x p`` NOT
renormalised; an identity expert adds its weight times ``m``. This chip
holds ``experts_held = (first, count)`` of the routed experts
(``incubate/moe/functional.py: moe_ffn_share``): what the absent
experts would add is left out, and that partial sum goes on.

Parameters are stacked BY KIND (a layer is sublayers ``2l``, ``2l + 1``
of ``mla`` and ``dense`` and entry ``l`` of ``moe``)::

    embed [V, D]   final_norm [D]   lm_head [D, V]
    mla    input_norm [2L, D]  wq_a [2L, D, Rq]  q_a_norm [2L, Rq]
           wq_nope [2L, H*nope, Rq]  wq_rope [2L, H*rope, Rq]   (output-major)
           wkv_a [2L, D, Rkv]
           wk_rope [2L, D, rope]  kv_a_norm [2L, Rkv]
           w_uk [2L, H, nope, Rkv]  w_uv [2L, H, Rkv, v]  wo [2L, H*v, D]
    dense  post_norm [2L, D]  w_gate, w_up [2L, D, F]  w_down [2L, F, D]
    moe    router [L, D, E+Z] f32  router_bias [L, E+Z] f32
           experts.w_gate, .w_up [L, n, D, Fm]  .w_down [L, n, Fm, D]

(the published ``kv_a_proj_with_mqa`` ``[D, Rkv + rope]`` is held as its
two column blocks: 576 columns are no multiple of the chip's 128 lanes;
the published ``kv_b_proj`` ``[Rkv, H*(nope+v)]`` as its K and V blocks a
head, head-major, the layout the absorbed form's per-head products read:
held as one matrix the compiler re-laid the whole stack out in every
tick, described-chip compile, PR 40; the published ``q_b_proj`` ``[Rq,
H*(nope+rope)]`` as each head's nope and rope column blocks, for the
same reason, each block OUTPUT-MAJOR (``q = c_q @ w.T``): the layout the
chip's compiler gives that product's weight, so none is re-laid out.)

THE CACHE is one pytree with ONE page pool, ``latent_pages [2L, P, ps,
W]``: a token's row of a sublayer is ``[c_kv | k_r | 0]``, ``W`` = 576
padded to 640 lanes (``cache_page_pools`` tells the engine the pool's
name and page axis; a page's bytes are counted at the published 1152 B a
token a sublayer). Pages are rebuildable from a prefix, so prefix cache,
defrag and speculation's length roll-back stay on.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..incubate.moe.functional import moe_ffn_share
from ..ops.pallas.mla_paged_attention import (latent_row_width,
                                              mla_paged_attention)
from . import layer_walk as _lw
from .layer_walk import (COUNTS, Group, LayerKind, PagePoolSpec,
                         _layer_params)
from .llama import _mm, rms_norm
from .mla import mla_qkv as _mla_qkv, rope_interleaved  # noqa: F401

MLA = "mla"
POOL = "latent_pages"
# the tick's per-launch counts, carried through the walk beside the pool
# (``layer_walk.COUNTS``) and handed back BESIDE the tokens
# (the record's ``counters`` names them for the engine, which adds them
# when the tick completes)
TICK_COUNTERS = ("moe_pairs_held", "moe_pairs_zero", "moe_pairs_absent",
                 "moe_experts_touched")


@dataclasses.dataclass
class LongcatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000000.0
    max_position_embeddings: int = 131072
    # the routed experts this chip holds: (first, count); None = all
    experts_held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        held = self.experts_held or (0, self.n_routed_experts)
        self.experts_held = (int(held[0]), int(held[1]))
        lo, n = self.experts_held
        if lo < 0 or n < 1 or lo + n > self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {self.n_routed_experts} routed "
                             f"experts")

    @property
    def num_hidden_layers(self) -> int:      # the engine's name for it
        return self.num_layers

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Values a token leaves a sublayer (published: 576)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_width(self) -> int:
        """Lanes a pool row takes (576 -> 640)."""
        return latent_row_width(self.kv_lora_rank, self.qk_rope_head_dim)

    @property
    def q_scale(self) -> float:
        return (float(np.sqrt(self.hidden_size / self.q_lora_rank))
                if self.mla_scale_q_lora else 1.0)

    @property
    def kv_scale(self) -> float:
        return (float(np.sqrt(self.hidden_size / self.kv_lora_rank))
                if self.mla_scale_kv_lora else 1.0)

    @property
    def sm_scale(self) -> float:
        return 1.0 / float(np.sqrt(self.qk_head_dim))

    @staticmethod
    def tiny(**kw) -> "LongcatFlashConfig":
        return LongcatFlashConfig(**{**dict(
            vocab_size=256, hidden_size=64, ffn_hidden_size=96,
            expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
            q_lora_rank=32, kv_lora_rank=48, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16,
            zero_expert_num=8, moe_topk=4, routed_scaling_factor=2.0,
            max_position_embeddings=256, experts_held=(4, 8),
            dtype=jnp.float32), **kw})


# ------------------------------------------------------------ the stack ----

def layer_groups(cfg: LongcatFlashConfig):
    """Every layer is the same pattern (two ``mla`` + ``dense``
    sublayers around one ``moe``), so the walk is ONE scanned group."""
    return [Group(layers=((MLA, "dense", 0, 0), (MLA, "dense", 1, 1)),
                  repeats=cfg.num_layers,
                  stride={MLA: 2, "dense": 2, "moe": 1})]


def init_params(cfg: LongcatFlashConfig, key: jax.Array) -> Dict[str, Any]:
    D, V, L = cfg.hidden_size, cfg.vocab_size, cfg.num_layers
    H, Rq, Rkv = cfg.num_attention_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rp, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    F, Fm = cfg.ffn_hidden_size, cfg.expert_ffn_hidden_size
    E = cfg.n_routed_experts + cfg.zero_expert_num
    n = cfg.experts_held[1]
    ks = iter(jax.random.split(key, 20))

    def init(shape, fan_in, dtype=cfg.dtype):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * (1.0 / np.sqrt(fan_in))).astype(dtype)

    return {
        "embed": init((V, D), D), "lm_head": init((D, V), D),
        "final_norm": jnp.ones((D,), cfg.dtype),
        "mla": {
            "input_norm": jnp.ones((2 * L, D), cfg.dtype),
            "wq_a": init((2 * L, D, Rq), D),
            "q_a_norm": jnp.ones((2 * L, Rq), cfg.dtype),
            # the up-projections behind a scaled latent at
            # 1/sqrt(hidden_size): what the sqrt(D / rank) scales
            # turn into unit q, k and v
            "wq_nope": init((2 * L, H * nope, Rq), D),
            "wq_rope": init((2 * L, H * rp, Rq), D),
            "wkv_a": init((2 * L, D, Rkv), D),
            "wk_rope": init((2 * L, D, rp), D),
            "kv_a_norm": jnp.ones((2 * L, Rkv), cfg.dtype),
            "w_uk": init((2 * L, H, nope, Rkv), D),
            "w_uv": init((2 * L, H, Rkv, dv), D),
            "wo": init((2 * L, H * dv, D), H * dv)},
        "dense": {
            "post_norm": jnp.ones((2 * L, D), cfg.dtype),
            "w_gate": init((2 * L, D, F), D), "w_up": init((2 * L, D, F), D),
            "w_down": init((2 * L, F, D), F)},
        "moe": {
            "router": init((L, D, E), D, jnp.float32),
            "router_bias": init((L, E), 1e4, jnp.float32),
            "experts": {"w_gate": init((L, n, D, Fm), D),
                        "w_up": init((L, n, D, Fm), D),
                        "w_down": init((L, n, Fm, D), Fm)}},
    }


def abstract_params(cfg: LongcatFlashConfig):
    """ShapeDtypeStruct pytree of ``init_params`` (tracing-only
    tooling; see models/llama.py abstract_params)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


# ------------------------------------------------------------ the layers ----

def _attn_out(lp, h, o):
    with jax.named_scope("attn.out"):
        return h + _mm(o.reshape(*h.shape[:-1], -1), lp["wo"])


def _dense(lp, h, m):
    with jax.named_scope("mlp"):
        return h + _mm(jax.nn.silu(_mm(m, lp["w_gate"])) * _mm(m, lp["w_up"]),
                       lp["w_down"])


def _routed(mp, m, cfg: LongcatFlashConfig, layer=None, row_mask=None,
            impl: str = "auto"):
    """The routed block over rows ``m [N, D]``; ``mp`` one layer's
    ``moe`` parameters, or with ``layer`` the stacks. ``(s, counts)``."""
    if layer is None:
        router, bias = mp["router"], mp["router_bias"]
    else:
        router = lax.dynamic_index_in_dim(mp["router"], layer, 0, False)
        bias = lax.dynamic_index_in_dim(mp["router_bias"], layer, 0, False)
    return moe_ffn_share(
        m, router, bias, mp["experts"], held=cfg.experts_held,
        num_routed=cfg.n_routed_experts, zero_experts=cfg.zero_expert_num,
        top_k=cfg.moe_topk, scale=cfg.routed_scaling_factor, layer=layer,
        row_mask=row_mask, impl=impl)


# ----------------------------------------------------- whole sequences ----

def init_kv_cache(cfg: LongcatFlashConfig, batch_size: int, max_len: int):
    """The dense latent cache: ``[2L, B, S, Rkv + rope]``."""
    return {"lat": jnp.zeros((2 * cfg.num_layers, batch_size, max_len,
                              cfg.latent_width), cfg.dtype)}


def _expanded_attention(lp, q_n, q_r, lat, pos0, cfg: LongcatFlashConfig):
    """The EXPANDED form over a dense latent cache ``lat [B, S, Rkv +
    rope]``: ``kv_b`` applied to the context, scores and softmax in
    float32; queries at positions ``pos0 ..``."""
    B, T, H, _ = q_n.shape
    S = lat.shape[1]
    c, k_r = lat[..., :cfg.kv_lora_rank], lat[..., cfg.kv_lora_rank:]
    k_n = jnp.einsum("bsc,hnc->bshn", c, lp["w_uk"])
    v = jnp.einsum("bsc,hcv->bshv", c, lp["w_uv"])
    s = (jnp.einsum("bthn,bshn->bhts", q_n, k_n)
         + jnp.einsum("bthr,bsr->bhts", q_r, k_r)).astype(jnp.float32)
    s = s * cfg.sm_scale
    mask = jnp.arange(S)[None, :] <= (pos0 + jnp.arange(T))[:, None]
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1).astype(v.dtype)
    return jnp.einsum("bhts,bshv->bthv", p, v)


def forward_with_cache(params, tokens, cache, pos0, cfg: LongcatFlashConfig,
                       every_position: bool = False):
    """tokens ``[B, T]`` at positions ``pos0..`` -> (last-position
    logits ``[B, V]``, updated cache): the dense-cache counterpart of
    the serving tick, attention in the EXPANDED form. ``cache=None`` is
    a whole sequence from position 0."""
    B, T = tokens.shape
    h = params["embed"].astype(cfg.dtype)[tokens]
    positions = pos0 + jnp.broadcast_to(jnp.arange(T), (B, T))
    new = None if cache is None else dict(cache)
    for l in range(cfg.num_layers):
        s = None
        for i in (0, 1):
            lp = _layer_params(params[MLA], 2 * l + i)
            q_n, q_r, c_kv, k_r = _mla_qkv(lp, h, positions, cfg)
            rows = jnp.concatenate([c_kv, k_r], -1).astype(cfg.dtype)
            if new is None:
                lat = rows
            else:
                lat = lax.dynamic_update_slice(new["lat"][2 * l + i], rows,
                                               (0, pos0, 0))
                new["lat"] = new["lat"].at[2 * l + i].set(lat)
            with jax.named_scope("attn.mla.core"):
                o = _expanded_attention(lp, q_n, q_r, lat, pos0, cfg)
            h = _attn_out(lp, h, o.astype(h.dtype))
            dp = _layer_params(params["dense"], 2 * l + i)
            m = rms_norm(h, dp["post_norm"], cfg.rms_norm_eps)
            if i == 0:
                s, _ = _routed(_layer_params(params["moe"], l),
                               m.reshape(B * T, -1), cfg)
            h = _dense(dp, h, m)
        h = h + s.reshape(h.shape)
    if not every_position:
        h = h[:, -1]
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return _mm(h, params["lm_head"]).astype(jnp.float32), new


def forward(params, tokens, cfg: LongcatFlashConfig):
    """tokens ``[B, T]`` -> logits ``[B, T, V]``: the whole sequence, no
    cache."""
    logits, _ = forward_with_cache(params, tokens, None, 0, cfg,
                                   every_position=True)
    return logits


def generate(params, prompt, cfg: LongcatFlashConfig, max_new_tokens: int, *,
             temperature: float = 0.0, top_p: float = 1.0, top_k: int = 0,
             key=None, eos_token_id: Optional[int] = None):
    """Autoregressive decode with the dense latent cache (same contract
    as ``models/llama.py generate``: returns prompt + continuation)."""
    from .llama import _decode_loop
    return _decode_loop(
        lambda p, t, c, pos: forward_with_cache(p, t, c, pos, cfg),
        lambda B, L: init_kv_cache(cfg, B, L),
        params, prompt, max_new_tokens, temperature, top_p, top_k, key,
        eos_token_id)


# ---------------------------------------------------------------- serving ----

def serving_cache_kinds(cfg: LongcatFlashConfig):
    """Every attention SUBLAYER's kind, in order (two a layer): each
    keeps pages, so nothing the engine does with pages is off."""
    return (LayerKind(MLA, "pages"),) * (2 * cfg.num_layers)


def cache_page_pools(cfg: LongcatFlashConfig):
    """The cache's page pools by name: ONE, ``latent_pages [2L, P, ps,
    W]`` (page axis 1), counted at the published ``latent_width`` values
    a token a sublayer."""
    return (PagePoolSpec(POOL, 1),)


def init_serving_pages(cfg: LongcatFlashConfig, total_pages: int,
                       page_size: int, max_batch: int, max_span: int = 1):
    """The model's cache: one latent pool over the ``2L`` attention
    sublayers (page 0 = trash), a row ``[c_kv | k_r | 0]``."""
    del max_batch, max_span
    return {POOL: jnp.zeros((2 * cfg.num_layers, total_pages, page_size,
                             cfg.row_width), cfg.dtype)}


def cache_page_copies(cfg: LongcatFlashConfig, cache, pages_per_slot: int,
                        tq: int) -> int:
    """Copies a tick's attention launches start for ONE live page, over
    the sublayers: one a walk (a span's row blocks after its first
    re-walk its pages; those are not counted here)."""
    del cache, pages_per_slot, tq
    return 2 * cfg.num_layers


def _walk(params, h, cache, meta, cfg: LongcatFlashConfig, tq, attn_impl):
    """The tick's layer walk (``models/llama.py _walk_one_kind``'s
    contract): one scan over the layers, the latent pool (and the
    tick's counts, where the caller carries them) in its carry.

    A slot's rows are CONTIGUOUS in the packed stream (the engine builds
    it so, as the conv window of ``layer_walk.py`` assumes too): they
    are ``last - q_len + 1 .. last``."""
    del tq
    S = meta["q_len"].shape[0]
    H, Rkv = cfg.num_attention_heads, cfg.kv_lora_rank
    tok_slot, tok_qoff = meta["tok_slot"], meta["tok_qoff"]
    positions = meta["tok_pos"][None]
    tok_page, tok_off = meta["tok_page"], meta["tok_off"]
    q_len, kv_len = meta["q_len"], meta["kv_len"]
    start = meta["last"] - q_len + 1
    real = tok_slot < S
    pad = cfg.row_width - cfg.latent_width

    def mla_sublayer(lp, h, lat, sub):
        q_n, q_r, c_kv, k_r = _mla_qkv(lp, h, positions, cfg)
        T = h.shape[1]
        with jax.named_scope("kv_pool.write"):
            row = jnp.concatenate(
                [c_kv[0], k_r[0], jnp.zeros((T, pad), c_kv.dtype)], -1)
            lat = lat.at[sub, tok_page, tok_off].set(row.astype(lat.dtype))
        with jax.named_scope("attn.mla.core"):
            q_abs = jnp.einsum("thn,hnc->thc", q_n[0], lp["w_uk"])
            q = jnp.concatenate(
                [q_abs, q_r[0], jnp.zeros((T, H, pad), q_abs.dtype)], -1)
            o_lat = mla_paged_attention(
                q, lat, start, q_len, kv_len, meta["tables"], dv=Rkv,
                sm_scale=cfg.sm_scale, tok_slot=tok_slot, tok_qoff=tok_qoff,
                impl=attn_impl, layer=sub)
            o = jnp.einsum("thc,hcv->thv", o_lat, lp["w_uv"])
        return _attn_out(lp, h, o[None].astype(h.dtype)), lat

    def run(group, carry, l):
        h, lat, counts = carry
        s = None
        for i in (0, 1):
            sub = 2 * l + i
            lp = _layer_params(params[MLA], sub)
            h, lat = mla_sublayer(lp, h, lat, jnp.asarray(sub, jnp.int32))
            dp = _layer_params(params["dense"], sub)
            m = rms_norm(h, dp["post_norm"], cfg.rms_norm_eps)
            if i == 0:
                s, c = _routed(params["moe"], m[0], cfg,
                               layer=jnp.asarray(l, jnp.int32),
                               row_mask=real, impl="auto")
                counts = counts + c
            h = _dense(dp, h, m)
        return h + s[None], lat, counts

    counts = cache.get(COUNTS, jnp.zeros((len(TICK_COUNTERS),), jnp.int32))
    with jax.named_scope("layers"):
        h, lat, counts = _lw.walk_groups(layer_groups(cfg),
                                         (h, cache[POOL], counts), run)
    new = {POOL: lat}
    if COUNTS in cache:
        new[COUNTS] = counts
    return h, new


SERVING = _lw.ServingFamily(
    walk=_walk, init_pages=init_serving_pages, kinds=serving_cache_kinds,
    page_pools=cache_page_pools, tick_pool=POOL, counters=TICK_COUNTERS,
    page_copies=cache_page_copies)
