"""Qwen2-MoE-family decoder: Llama attention + MoE FFN with shared expert.

Capability target: the reference ecosystem's MoE pretrain path —
python/paddle/incubate/distributed/models/moe/moe_layer.py (dispatch) +
fused cutlass MoE kernels — redesigned as one jitted SPMD program.

Parallelism (on top of models/llama.py's tp/sp/dp):
  - EP: expert weights carry a leading E axis sharded over the mesh ``ep``
    axis; the dense dispatch einsums (incubate.moe.functional) compile to
    the expert all_to_all under GSPMD.
  - The router and shared expert stay tp-sharded like llama's MLP.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..incubate.moe.functional import moe_ffn, moe_ffn_share
from ..ops.pallas.flash_attention import remat_layer
from .layer_walk import (COUNTS, EXPERT_COUNTERS, ServingFamily,
                         expert_counts, kv_rows, paged_kv_attend, tick_plan)
from .llama import (_mm, _proj, init_serving_pages, rms_norm, rope,
                    serving_params)


def _dense_w(w, dtype):
    """Dense view of a weight that may be an Int8Weight: the einsum-
    dispatched MoE FFN consumes full expert tensors, so quantized
    experts are dequantized here and XLA fuses the int8→dtype cast +
    per-channel scale into the dispatch einsums (the HBM read — the
    thing int8 halves — is still of the int8 buffer)."""
    return w.dequant(dtype) if hasattr(w, "dequant") else w


@dataclasses.dataclass
class Qwen2MoeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 24
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    max_position_embeddings: int = 8192
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    # MoE
    num_experts: int = 60
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1408
    shared_expert_intermediate_size: int = 5632
    capacity_factor: float = 2.0
    router_aux_loss_coef: float = 0.001
    # "einsum": GShard capacity dispatch (drops overflow tokens; the
    # all_to_all EP path). "dropless": the authored grouped-GEMM Pallas
    # kernel (ops/pallas/grouped_matmul.py) — no capacity, no drops;
    # engages only when expert weights are unsharded (no ep/tp axis —
    # the kernel has no shard_map partitioning rule yet); other layouts
    # fall back to the einsum path automatically.
    moe_impl: str = "einsum"
    dtype: Any = jnp.bfloat16
    remat: bool = True
    use_flash_attention: bool = True

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @staticmethod
    def tiny(**kw) -> "Qwen2MoeConfig":
        return Qwen2MoeConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=128, num_experts=4,
            num_experts_per_tok=2, moe_intermediate_size=32,
            shared_expert_intermediate_size=64, **kw)


def init_params(cfg: Qwen2MoeConfig, key: jax.Array) -> Dict[str, Any]:
    D, V = cfg.hidden_size, cfg.vocab_size
    H, Hkv, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    L, E = cfg.num_hidden_layers, cfg.num_experts
    Fm, Fs = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    ks = jax.random.split(key, 16)

    def init(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) *
                (1.0 / np.sqrt(fan_in))).astype(cfg.dtype)

    layers = {
        "wq": init(ks[0], (L, D, H * Dh), D),
        "wk": init(ks[1], (L, D, Hkv * Dh), D),
        "wv": init(ks[2], (L, D, Hkv * Dh), D),
        "wo": init(ks[3], (L, H * Dh, D), H * Dh),
        "attn_norm": jnp.ones((L, D), cfg.dtype),
        "mlp_norm": jnp.ones((L, D), cfg.dtype),
        # router stays fp32 for stable softmax
        "router": jax.random.normal(ks[4], (L, D, E), jnp.float32) * 0.02,
        "experts": {
            "w_gate": init(ks[5], (L, E, D, Fm), D),
            "w_up": init(ks[6], (L, E, D, Fm), D),
            "w_down": init(ks[7], (L, E, Fm, D), Fm),
        },
        "shared": {
            "w_gate": init(ks[8], (L, D, Fs), D),
            "w_up": init(ks[9], (L, D, Fs), D),
            "w_down": init(ks[10], (L, Fs, D), Fs),
            "gate": init(ks[11], (L, D, 1), D),  # shared-expert gate proj
        },
    }
    return {
        "embed": init(ks[12], (V, D), D),
        "layers": layers,
        "final_norm": jnp.ones((D,), cfg.dtype),
        "lm_head": init(ks[13], (D, V), D),
    }


def param_specs(cfg: Qwen2MoeConfig) -> Dict[str, Any]:
    """TP shards attention + shared expert like llama; EP shards the E axis
    of routed experts; expert matrices additionally tp-shard their F dim."""
    layers = {
        "wq": P(None, None, "tp"),
        "wk": P(None, None, "tp"),
        "wv": P(None, None, "tp"),
        "wo": P(None, "tp", None),
        "attn_norm": P(None, None),
        "mlp_norm": P(None, None),
        "router": P(None, None, None),
        "experts": {
            "w_gate": P(None, "ep", None, "tp"),
            "w_up": P(None, "ep", None, "tp"),
            "w_down": P(None, "ep", "tp", None),
        },
        "shared": {
            "w_gate": P(None, None, "tp"),
            "w_up": P(None, None, "tp"),
            "w_down": P(None, "tp", None),
            "gate": P(None, None, None),
        },
    }
    return {
        "embed": P("tp", None),
        "layers": layers,
        "final_norm": P(None),
        "lm_head": P(None, "tp"),
    }


def shard_params(params, cfg: Qwen2MoeConfig, mesh: Mesh):
    specs = param_specs(cfg)

    def put(x, s):
        # drop only the axes absent from this mesh (e.g. no 'ep' axis when
        # ep=1), keeping the rest of the spec intact
        pruned = P(*(n if (n is not None and n in mesh.shape) else None
                     for n in s))
        return jax.device_put(x, NamedSharding(mesh, pruned))

    return jax.tree_util.tree_map(
        put, params, specs, is_leaf=lambda x: isinstance(x, P))


def decoder_layer(lp, h, cfg: Qwen2MoeConfig, ep_axis: Optional[str],
                  use_dropless: bool = False):
    B, T, D = h.shape
    H, Hkv, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))

    x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
    q = (x @ lp["wq"]).reshape(B, T, H, Dh)
    k = (x @ lp["wk"]).reshape(B, T, Hkv, Dh)
    v = (x @ lp["wv"]).reshape(B, T, Hkv, Dh)
    q, k = rope(q, k, positions, cfg.rope_theta, Dh)
    from ..ops.pallas.flash_attention import flash_attention as _fa
    o = _fa(q, k, v, causal=True,
            impl="auto" if cfg.use_flash_attention else "dense")
    h = h + o.reshape(B, T, H * Dh) @ lp["wo"]

    x = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
    if use_dropless:
        from ..incubate.moe.functional import moe_ffn_dropless
        routed, aux = moe_ffn_dropless(
            x, lp["router"],
            lp["experts"]["w_gate"], lp["experts"]["w_up"],
            lp["experts"]["w_down"],
            top_k=cfg.num_experts_per_tok)
    else:
        routed, aux = moe_ffn(
            x, lp["router"],
            lp["experts"]["w_gate"], lp["experts"]["w_up"],
            lp["experts"]["w_down"],
            top_k=cfg.num_experts_per_tok,
            capacity_factor=cfg.capacity_factor,
            ep_axis=ep_axis)
    sh = lp["shared"]
    shared = (jax.nn.silu(x @ sh["w_gate"]) * (x @ sh["w_up"])) @ sh["w_down"]
    shared = jax.nn.sigmoid(x @ sh["gate"]) * shared
    return h + routed + shared, aux


def forward(params, tokens, cfg: Qwen2MoeConfig,
            mesh: Optional[Mesh] = None):
    """tokens [B, T] -> (logits [B, T, V], total_aux_loss)."""
    if cfg.moe_impl not in ("einsum", "dropless"):
        raise ValueError(f"moe_impl must be 'einsum' or 'dropless', "
                         f"got {cfg.moe_impl!r}")
    ep_axis = ("ep" if mesh is not None and mesh.shape.get("ep", 1) > 1
               else None)
    # the grouped-GEMM kernel has no GSPMD partitioning rule yet, so
    # dropless only engages on layouts where nothing it touches is
    # sharded: not the expert weights (ep/tp) and not the token
    # activations either (dp — an un-partitionable pallas_call would
    # make XLA replicate the full activation on every dp rank per step)
    use_dropless = (cfg.moe_impl == "dropless" and ep_axis is None
                    and (mesh is None or (mesh.shape.get("tp", 1) == 1
                                          and mesh.shape.get("dp", 1) == 1)))
    h = params["embed"].astype(cfg.dtype)[tokens]

    fn = partial(decoder_layer, cfg=cfg, ep_axis=ep_axis,
                 use_dropless=use_dropless)
    if cfg.remat:
        fn = remat_layer(fn)

    def body(carry, lp):
        h, aux = carry
        h, a = fn(lp, h)
        return (h, aux + a), None

    (h, aux), _ = lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                           params["layers"])
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return h @ params["lm_head"], aux


def loss_fn(params, batch, cfg: Qwen2MoeConfig, mesh=None):
    tokens, labels = batch["tokens"], batch["labels"]
    logits, aux = forward(params, tokens, cfg, mesh)
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return nll.mean() + cfg.router_aux_loss_coef * aux


def make_train_step(cfg: Qwen2MoeConfig, mesh: Mesh, optimizer=None):
    """Jitted SPMD train step; optimizer state inherits param sharding
    (ZeRO-style, like models/llama.py make_train_step)."""
    from .train_step import make_family_train_step
    return make_family_train_step(
        lambda key: shard_params(init_params(cfg, key), cfg, mesh),
        lambda params, batch: (loss_fn(params, batch, cfg, mesh), None),
        optimizer)


# ---------------------------------------------------------------------------
# decode: KV cache + generate
# ---------------------------------------------------------------------------
# Reference capability: MoE decode serving (the fused cutlass MoE kernels
# run at inference too). Same cache design as models/llama.py: [L, B, S,
# Hkv, Dh] pytree updated with dynamic_update_slice inside one jitted
# step; the MoE FFN (einsum routing) runs unchanged on T=1 tokens.


def init_kv_cache(cfg: Qwen2MoeConfig, batch_size: int, max_len: int):
    L, Hkv, Dh = (cfg.num_hidden_layers, cfg.num_key_value_heads,
                  cfg.head_dim)
    shape = (L, batch_size, max_len, Hkv, Dh)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype)}


def _routed_capacity(lp, x, cfg: Qwen2MoeConfig):
    """The routed experts through the capacity einsum, DROP-FREE:
    capacity cf = E/top_k makes expert capacity == cohort size (C = N),
    so no token is ever dropped. Training capacity drops are a
    throughput regularizer; at inference a dropped token silently loses
    its FFN contribution — and the drop pattern depends on cohort size,
    which would make cached decode diverge from a full forward. It
    reads EVERY expert of the layer whatever the rows chose: the
    dense-cache decode's path, and a serving tick's where the expert
    leaves are weight-only int8 (the grouped matmul reads bfloat16
    stacks)."""
    nodrop_cf = cfg.num_experts / cfg.num_experts_per_tok
    routed, _ = moe_ffn(
        x, lp["router"], _dense_w(lp["experts"]["w_gate"], cfg.dtype),
        _dense_w(lp["experts"]["w_up"], cfg.dtype),
        _dense_w(lp["experts"]["w_down"], cfg.dtype),
        top_k=cfg.num_experts_per_tok,
        capacity_factor=nodrop_cf, ep_axis=None)
    return routed


def _decode_block(lp, h, positions, cfg: Qwen2MoeConfig, attn_fn,
                  routed_fn=_routed_capacity):
    """Qwen block math shared by every cached-decode consumer (dense
    cache forward_with_cache AND the serving engine's paged step fns):
    norm -> QKV -> rope -> attn_fn -> o-proj+residual -> norm -> MoE FFN
    (``routed_fn(lp, x, cfg)``: dropless either way, see
    ``_routed_capacity`` and the serving ``_walk``) + shared expert +
    residual."""
    B, T, D = h.shape
    H, Hkv, Dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    # the scopes of models/llama.py _block (metadata only); the MoE FFN
    # brings ``moe.router`` and ``moe.experts``
    with jax.named_scope("attn.qkv_rope"):
        x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
        q = _proj(x, lp, "wq").reshape(B, T, H, Dh)
        k = _proj(x, lp, "wk").reshape(B, T, Hkv, Dh)
        v = _proj(x, lp, "wv").reshape(B, T, Hkv, Dh)
        q, k = rope(q, k, positions, cfg.rope_theta, Dh)
    o = attn_fn(q, k, v)
    with jax.named_scope("attn.out"):
        h = h + _mm(o.reshape(B, T, H * Dh), lp["wo"])

    with jax.named_scope("moe.router"):     # the norm that feeds it
        x = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
    routed = routed_fn(lp, x, cfg)
    with jax.named_scope("moe.shared"):
        sh = lp["shared"]
        shared = _mm(jax.nn.silu(_mm(x, sh["w_gate"]))
                     * _mm(x, sh["w_up"]), sh["w_down"])
        shared = jax.nn.sigmoid(x @ sh["gate"]) * shared
        return h + routed + shared


def forward_with_cache(params, tokens, cache, pos0, cfg: Qwen2MoeConfig):
    """tokens [B, T] at positions pos0.. -> (last-position logits
    [B, V], updated cache). T = prompt length for prefill (pos0 = 0),
    T = 1 for decode steps."""
    from .llama import _cached_attention
    from ..ops.pallas.flash_attention import flash_attention as _fa
    B, T = tokens.shape
    h = params["embed"].astype(cfg.dtype)[tokens]
    positions = pos0 + jnp.broadcast_to(jnp.arange(T), (B, T))
    is_prefill = isinstance(pos0, int) and pos0 == 0

    def body(h, xs):
        lp, ck, cv = xs
        cell = {}

        def attn_fn(q, k, v):
            ck2 = lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                           (0, pos0, 0, 0))
            cv2 = lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                           (0, pos0, 0, 0))
            cell["ck"], cell["cv"] = ck2, cv2
            if is_prefill:
                return _fa(q, k, v, causal=True,
                           impl="auto" if cfg.use_flash_attention
                           else "dense")
            return _cached_attention(q, ck2, cv2, pos0, cfg)

        h = _decode_block(lp, h, positions, cfg, attn_fn)
        return h, (cell["ck"], cell["cv"])

    h, (ck_new, cv_new) = lax.scan(
        body, h, (params["layers"], cache["k"], cache["v"]))
    h = rms_norm(h[:, -1], params["final_norm"], cfg.rms_norm_eps)
    logits = _mm(h, params["lm_head"])
    return logits.astype(jnp.float32), {"k": ck_new, "v": cv_new}


def generate(params, prompt, cfg: Qwen2MoeConfig, max_new_tokens: int,
             *, temperature: float = 0.0, top_p: float = 1.0,
             top_k: int = 0, key=None, eos_token_id: Optional[int] = None):
    """Autoregressive MoE decode with a KV cache (same contract as
    models/llama.py generate: returns prompt + continuation). Routing
    is DROP-FREE at decode (see forward_with_cache)."""
    from .llama import _decode_loop
    return _decode_loop(
        lambda p, t, c, pos: forward_with_cache(p, t, c, pos, cfg),
        lambda B, L: init_kv_cache(cfg, B, L),
        params, prompt, max_new_tokens, temperature, top_p, top_k, key,
        eos_token_id)


def make_batch(cfg: Qwen2MoeConfig, batch_size: int, seq_len: int,
               mesh: Mesh, key=None):
    from .llama import make_batch as _llama_make_batch
    return _llama_make_batch(cfg, batch_size, seq_len, mesh, key=key)


# ---------------------------------------------------------------------------
# serving: the tick over a shared page pool
# ---------------------------------------------------------------------------
# The family's record (``SERVING``, at the end: ``models/layer_walk.py:
# ServingFamily``): llama's cache and serving tree, this model's walk. A
# tick's routed experts go through the held-experts grouped matmul at
# the share ``(0, E)`` (``incubate/moe/functional.py: moe_ffn_share``):
# it fetches the weights of the experts that took a row and of no other.


def abstract_params(cfg: Qwen2MoeConfig):
    """ShapeDtypeStruct pytree of ``init_params`` (tracing-only
    tooling; see models/llama.py abstract_params)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


def _walk(params, h, cache, meta, cfg: Qwen2MoeConfig, tq, attn_impl):
    """The tick's layer walk (``models/llama.py _walk_one_kind``'s
    contract and its scan: the stacked pools in the carry, a layer's
    KV through ``paged_kv_attend``), with the
    expert STACKS kept out of the scanned ``xs``: a Pallas call cannot
    take a scanned slice without the compiler copying the layer's
    experts (1.04 GB) in front of it, so the block gets the stacks and
    the layer index. Rows that are no token (``tok_slot == S``: padding
    and dead slots) route nowhere. The tick's counts (``EXPERT_COUNTERS``)
    ride the carry where the caller carries them (``cache[COUNTS]``)."""
    k_pages, v_pages = cache["k_pages"], cache["v_pages"]
    E, top_k = cfg.num_experts, cfg.num_experts_per_tok
    plan = tick_plan(meta, tq, cfg.num_attention_heads, k_pages)
    positions = meta["tok_pos"][None]
    real = meta["tok_slot"] < meta["q_len"].shape[0]
    at = kv_rows(meta, k_pages)
    layers = dict(params["layers"])
    experts = layers["experts"]
    # what the code observes in its input: bfloat16 stacks go to the
    # grouped matmul whole, weight-only int8 leaves stay scanned and
    # keep the einsum
    held = not any(hasattr(w, "dequant") for w in experts.values())
    if held:
        del layers["experts"]

    def body(carry, xs):
        h, kp, vp, counts = carry
        lp, layer = xs
        cell = {}

        def attn_fn(q, k, v):
            o, cell["kp"], cell["vp"] = paged_kv_attend(
                q, k, v, kp, vp, layer, meta, at, plan, tq, attn_impl)
            return o

        def routed_fn(lp, x, cfg):
            if not held:
                cell["counts"] = jnp.stack(
                    [top_k * real.sum(), E, E]).astype(jnp.int32)
                return _routed_capacity(lp, x, cfg)
            y, c = moe_ffn_share(
                x[0], lp["router"], None, experts, held=(0, E),
                num_routed=E, top_k=top_k, layer=layer, row_mask=real)
            cell["counts"] = expert_counts(c, E)
            return y[None]

        h = _decode_block(lp, h, positions, cfg, attn_fn, routed_fn)
        return (h, cell["kp"], cell["vp"], counts + cell["counts"]), None

    counts = cache.get(COUNTS, jnp.zeros((len(EXPERT_COUNTERS),), jnp.int32))
    # an operation under bare ``layers`` is the scan's own: the slicing
    # of a layer's weights
    with jax.named_scope("layers"):
        (h, kp_new, vp_new, counts), _ = lax.scan(
            body, (h, k_pages, v_pages, counts),
            (layers, jnp.arange(k_pages.shape[0], dtype=jnp.int32)))
    new = {"k_pages": kp_new, "v_pages": vp_new}
    if COUNTS in cache:
        new[COUNTS] = counts
    return h, new


# what a serving tick hands back beside its tokens: ``counts [3]`` i32,
# ``EXPERT_COUNTERS`` over the tick's launches
SERVING = ServingFamily(walk=_walk, init_pages=init_serving_pages,
                        counters=EXPERT_COUNTERS, params=serving_params)
