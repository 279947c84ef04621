"""JoyAI-LLM-Flash-family decoder (jdopensource JoyAI-LLM-Flash: the
DeepSeek-V3 layer at its own numbers), TRAINED as ONE CHIP'S SHARE of
an expert-parallel job: multi-head LATENT attention in its EXPANDED form
through splash (q / k head size ``nope + rope`` = 192, v head size 128),
a leading DENSE layer and EXPERT layers in one step, a sigmoid router
with a selection bias over all routed experts of which this chip holds
``experts_held``, a shared expert, and one multi-token-prediction
module.

A layer, for hidden states ``h [B, T, D]`` at positions ``p``::

    a = rms(h, input_norm)
    c_q = rms(a @ wq_a, q_a_norm);   [q_n | q_r] = c_q @ wq_b  a head
    c_kv = rms(a @ wkv_a, kv_a_norm);  k_r = rope(a @ wk_rope)  (ONE for all heads)
    q_r = rope(q_r)                                  # interleaved pairs
    k_n[h] = c_kv @ w_uk[h].T;  v[h] = c_kv @ w_uv[h]           # kv_b applied to every token
    o = causal_softmax_f32(([q_n | q_r] . [k_n | k_r]) / sqrt(nope + rope)) v
    h = h + concat_heads(o) @ wo
    m = rms(h, post_norm)
    dense layer:   h = h + swiglu(m, w_gate, w_up, w_down)
    expert layer:  s = sigmoid(f32(m) @ router);  S = top_k(s + router_bias)
                   w_e = scale * s_e / sum_{e in S} s_e
                   h = h + sum_{e in S, e held} w_e swiglu_e(m) + swiglu_shared(m)

(``incubate/moe/functional.py: moe_ffn_share``: what the experts this
chip does not hold would add is left out, and that partial sum goes on;
nothing is dropped; the router's gradient comes through ``w_e``, none
through the choice and none to the bias.) The multi-token-prediction
module, depth 1, where ``num_nextn_predict_layers`` > 0::

    h'_i = [rms(embed[t_{i+1}], embed_norm) | rms(h_i, hidden_norm)] @ proj
    h''  = expert_layer(h');   logits' = rms(h'', final_norm) @ lm_head
    loss = CE(logits, t_{i+1}) + mtp_loss_weight * CE(logits'_i, t_{i+2})

with ``h_i`` the trunk's last layer output before the final norm, the
embedding and the head the trunk's own, over the positions that have a
``t_{i+2}``.

Parameters (the pytree the benchmark's reference names its leaves by)::

    embed [V, D]   final_norm [D]   lm_head [D, V]
    dense_layers   the attention leaves of models/mla.py [Ld, ...],
                   post_norm [Ld, D], w_gate / w_up [Ld, D, F], w_down [Ld, F, D]
    layers         the attention leaves [Le, ...], post_norm [Le, D],
                   router [Le, D, E] f32, router_bias [Le, E] f32,
                   experts.w_gate / .w_up [Le, n, D, Fm], .w_down [Le, n, Fm, D],
                   shared.w_gate / .w_up [Le, D, Fs], .w_down [Le, Fs, D]
    mtp            (only where num_nextn_predict_layers > 0) embed_norm,
                   hidden_norm, final_norm [D], proj [2D, D], layer: one
                   expert layer's leaves, unstacked

The walk goes by ``models/layer_walk.py``'s groups (a leading dense
group, an expert group), each ``lax.scan``-ned over its stack and
rematerialised a layer. One chip: the exchange that would bring the
other chips' rows to this chip's experts is not in the program
(ROADMAP); ``param_specs`` replicates every leaf.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..incubate.moe.functional import moe_ffn_share
from ..ops.pallas.flash_attention import flash_attention, remat_layer
from .layer_walk import layer_groups as _groups, layer_kinds
from .llama import _fused_nr_on, _norm_fn
from .mla import mla_qkv

# a step's sums over its expert layers (``observability.step_counters``,
# group ``train``): the (row, choice) pairs this chip's experts took and
# those that went to experts it does not hold, the dead rows of the
# sorted buffers' live tiles, the launches that passed the buffer's
# static bound and took the exact fall-back
TRAIN_COUNTERS = ("train_moe_pairs_held", "train_moe_pairs_absent",
                  "train_moe_rows_padded", "train_moe_bound_fallbacks")


@dataclasses.dataclass
class JoyAIFlashConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    num_nextn_predict_layers: int = 1
    mtp_loss_weight: float = 0.3
    rms_norm_eps: float = 1e-6
    rope_theta: float = 32000000.0
    max_position_embeddings: int = 131072
    # the routed experts this chip holds: (first, count); None = all
    experts_held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # as models/llama.py reads them: True / "auto" (the kernel on a TPU
    # where the shapes allow), "pallas" (the kernel or an error), False
    use_flash_attention: Any = True
    use_fused_norm_rope: Any = "auto"
    # the published model scales neither latent (models/mla.py)
    q_scale = 1.0
    kv_scale = 1.0

    def __post_init__(self):
        held = self.experts_held or (0, self.n_routed_experts)
        self.experts_held = (int(held[0]), int(held[1]))
        lo, n = self.experts_held
        if lo < 0 or n < 1 or lo + n > self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {self.n_routed_experts} routed "
                             f"experts")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace lies outside the stack")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("one multi-token-prediction module or none")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def sm_scale(self) -> float:
        return 1.0 / float(np.sqrt(self.qk_head_dim))

    @property
    def num_dense_layers(self) -> int:
        return self.first_k_dense_replace

    @property
    def num_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @staticmethod
    def tiny(**kw) -> "JoyAIFlashConfig":
        return JoyAIFlashConfig(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=3,
            first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=32,
            kv_lora_rank=48, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, n_routed_experts=16, num_experts_per_tok=4,
            max_position_embeddings=256, experts_held=(4, 8),
            dtype=jnp.float32), **kw})


# ------------------------------------------------------------ the stack ----

DENSE, MOE = "dense_layers", "layers"


def layer_groups(cfg: JoyAIFlashConfig):
    """The leading dense layers (one group) and the expert layers (one
    group), as ``models/layer_walk.py`` cuts a stack; a layer's
    feed-forward kind names the stack its parameters lie in."""
    nd = cfg.num_dense_layers
    return _groups(layer_kinds(["mla"] * cfg.num_hidden_layers,
                               lambda i: DENSE if i < nd else MOE), nd)


def _attention_shapes(cfg: JoyAIFlashConfig) -> Dict[str, tuple]:
    """``{leaf: (shape, fan_in or None for a norm)}``."""
    D, H = cfg.hidden_size, cfg.num_attention_heads
    Rq, Rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rp, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {"input_norm": ((D,), None), "wq_a": ((D, Rq), D),
            "q_a_norm": ((Rq,), None), "wq_nope": ((H * nope, Rq), Rq),
            "wq_rope": ((H * rp, Rq), Rq), "wkv_a": ((D, Rkv), D),
            "wk_rope": ((D, rp), D), "kv_a_norm": ((Rkv,), None),
            "w_uk": ((H, nope, Rkv), Rkv), "w_uv": ((H, Rkv, dv), Rkv),
            "wo": ((H * dv, D), H * dv), "post_norm": ((D,), None)}


def init_params(cfg: JoyAIFlashConfig, key: jax.Array) -> Dict[str, Any]:
    D, V = cfg.hidden_size, cfg.vocab_size
    F, Fm = cfg.intermediate_size, cfg.moe_intermediate_size
    Fs, E, n = Fm * cfg.n_shared_experts, cfg.n_routed_experts, \
        cfg.experts_held[1]
    ks = iter(jax.random.split(key, 64))

    def init(shape, fan_in, dtype=cfg.dtype):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * (1.0 / np.sqrt(fan_in))).astype(dtype)

    def attention(lead):
        return {k: (jnp.ones(lead + s, cfg.dtype) if fan is None
                    else init(lead + s, fan))
                for k, (s, fan) in _attention_shapes(cfg).items()}

    def expert_layer(lead):
        return {**attention(lead),
                "router": init(lead + (D, E), D, jnp.float32),
                "router_bias": jnp.zeros(lead + (E,), jnp.float32),
                "experts": {"w_gate": init(lead + (n, D, Fm), D),
                            "w_up": init(lead + (n, D, Fm), D),
                            "w_down": init(lead + (n, Fm, D), Fm)},
                "shared": {"w_gate": init(lead + (D, Fs), D),
                           "w_up": init(lead + (D, Fs), D),
                           "w_down": init(lead + (Fs, D), Fs)}}

    Ld = (cfg.num_dense_layers,)
    params = {
        "embed": init((V, D), D), "lm_head": init((D, V), D),
        "final_norm": jnp.ones((D,), cfg.dtype),
        DENSE: {**attention(Ld), "w_gate": init(Ld + (D, F), D),
                "w_up": init(Ld + (D, F), D),
                "w_down": init(Ld + (F, D), F)},
        MOE: expert_layer((cfg.num_expert_layers,)),
    }
    if cfg.num_nextn_predict_layers:
        params["mtp"] = {"embed_norm": jnp.ones((D,), cfg.dtype),
                         "hidden_norm": jnp.ones((D,), cfg.dtype),
                         "final_norm": jnp.ones((D,), cfg.dtype),
                         "proj": init((2 * D, D), 2 * D),
                         "layer": expert_layer(())}
    return params


def abstract_params(cfg: JoyAIFlashConfig):
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


def param_specs(cfg: JoyAIFlashConfig) -> Dict[str, Any]:
    """Every leaf replicated: the step is ONE chip's (the module
    docstring)."""
    return jax.tree_util.tree_map(lambda _: P(), abstract_params(cfg))


def shard_params(params, cfg: JoyAIFlashConfig, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params,
        param_specs(cfg))


# ------------------------------------------------------------ the layers ----

def _attention(lp, h, positions, cfg: JoyAIFlashConfig, norm):
    """``h + MLA(rms(h))`` in the expanded form."""
    B, T, _ = h.shape
    H = cfg.num_attention_heads
    q_n, q_r, c_kv, k_r = mla_qkv(lp, h, positions, cfg, norm)
    with jax.named_scope("attn.mla.expand"):
        k_n = jnp.einsum("btc,hnc->bthn", c_kv, lp["w_uk"])
        v = jnp.einsum("btc,hcv->bthv", c_kv, lp["w_uv"])
        q = jnp.concatenate([q_n, q_r], axis=-1)
        k = jnp.concatenate(
            [k_n, jnp.broadcast_to(k_r[:, :, None], (B, T, H, k_r.shape[-1]))],
            axis=-1)
    fa = cfg.use_flash_attention
    with jax.named_scope("attn.core"):
        o = flash_attention(
            q, k, v, causal=True, sm_scale=cfg.sm_scale,
            impl=fa if isinstance(fa, str) else ("auto" if fa else "dense"))
    with jax.named_scope("attn.out"):
        return h + o.reshape(B, T, -1) @ lp["wo"]


def _swiglu(x, w):
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def dense_layer(lp, h, positions, cfg: JoyAIFlashConfig, norm):
    h = _attention(lp, h, positions, cfg, norm)
    m = norm(h, lp["post_norm"])
    with jax.named_scope("mlp"):
        return h + _swiglu(m, lp)


def expert_layer(lp, h, positions, cfg: JoyAIFlashConfig, norm):
    """``(h', counts [6])``: ``moe_ffn_share``'s counts with its row
    statistics."""
    B, T, D = h.shape
    h = _attention(lp, h, positions, cfg, norm)
    m = norm(h, lp["post_norm"])
    routed, counts = moe_ffn_share(
        m.reshape(B * T, D), lp["router"], lp["router_bias"], lp["experts"],
        held=cfg.experts_held, num_routed=cfg.n_routed_experts,
        top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
        score_fn="sigmoid", normalize_topk=cfg.norm_topk_prob,
        row_stats=True)
    with jax.named_scope("moe.shared"):
        shared = _swiglu(m, lp["shared"])
    return h + routed.reshape(B, T, D) + shared, counts


def _step_counts(counts):
    """``TRAIN_COUNTERS`` from ``moe_ffn_share``'s ``[held, zero,
    absent, touched, rows_padded, fallbacks]``."""
    return jnp.stack([counts[0], counts[2], counts[4], counts[5]])


def _trunk(params, tokens, cfg: JoyAIFlashConfig, mesh):
    """``(h [B, T, D] before the final norm, counts [4], norm)``."""
    B, T = tokens.shape
    norm = _norm_fn(cfg, None, _fused_nr_on(cfg, mesh))
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    with jax.named_scope("embed"):
        h = params["embed"].astype(cfg.dtype)[tokens]
    counts = jnp.zeros((len(TRAIN_COUNTERS),), jnp.int32)

    def dense(h, lp):
        return dense_layer(lp, h, positions, cfg, norm), None

    def moe(h, lp):
        h, c = expert_layer(lp, h, positions, cfg, norm)
        return h, _step_counts(c)

    for group in layer_groups(cfg):
        kind = group.layers[0][1]
        body = dense if kind == DENSE else moe
        if cfg.remat:
            body = remat_layer(body)
        with jax.named_scope("layers"):
            h, per_layer = lax.scan(body, h, params[kind])
        if per_layer is not None:
            counts = counts + per_layer.sum(0)
    return h, counts, norm


def _head(h, final_norm, lm_head, norm):
    with jax.named_scope("lm_head"):
        return norm(h, final_norm) @ lm_head


def forward(params, tokens, cfg: JoyAIFlashConfig,
            mesh: Optional[Mesh] = None):
    """tokens ``[B, T]`` -> logits ``[B, T, V]`` (the trunk's)."""
    h, _, norm = _trunk(params, tokens, cfg, mesh)
    return _head(h, params["final_norm"], params["lm_head"], norm)


def _mtp_logits(params, h, next_tokens, cfg: JoyAIFlashConfig, norm):
    """The module's logits ``[B, T, V]`` (position ``i`` predicts
    ``t_{i+2}``) and its expert layer's counts."""
    mp = params["mtp"]
    B, T, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    with jax.named_scope("mtp"):
        e = params["embed"].astype(cfg.dtype)[next_tokens]
        x = jnp.concatenate([norm(e, mp["embed_norm"]),
                             norm(h, mp["hidden_norm"])], axis=-1)
        layer = lambda x: expert_layer(mp["layer"], x @ mp["proj"],
                                       positions, cfg, norm)
        x, counts = (remat_layer(layer) if cfg.remat else layer)(x)
        return _head(x, mp["final_norm"], params["lm_head"], norm), counts


def loss_and_counts(params, batch, cfg: JoyAIFlashConfig, mesh=None):
    """``(loss, counts [4])``: next-token cross entropy over every
    position, plus ``mtp_loss_weight`` times the module's over the
    positions that have a token two ahead; ``TRAIN_COUNTERS``."""
    from ..ops.fused import fused_softmax_cross_entropy
    tokens, labels = batch["tokens"], batch["labels"]
    h, counts, norm = _trunk(params, tokens, cfg, mesh)
    logits = _head(h, params["final_norm"], params["lm_head"], norm)
    loss = fused_softmax_cross_entropy(logits, labels).mean()
    if cfg.num_nextn_predict_layers:
        # labels[i] = t_{i+1}: the module's input at i; its target
        # t_{i+2} = labels[i+1], which the last position lacks
        logits2, c = _mtp_logits(params, h, labels, cfg, norm)
        nll = fused_softmax_cross_entropy(logits2[:, :-1], labels[:, 1:])
        loss = loss + cfg.mtp_loss_weight * nll.mean()
        counts = counts + _step_counts(c)
    return loss, counts


def loss_fn(params, batch, cfg: JoyAIFlashConfig, mesh=None):
    return loss_and_counts(params, batch, cfg, mesh)[0]


def train_state_specs(cfg: JoyAIFlashConfig, mesh: Mesh, optimizer=None):
    """PartitionSpecs of ``make_train_step``'s state: all replicated."""
    from .llama import default_train_optimizer
    optimizer = optimizer or default_train_optimizer()
    params = abstract_params(cfg)
    state = {"params": params, "opt": jax.eval_shape(optimizer.init, params),
             "step": jax.ShapeDtypeStruct((), jnp.int32)}
    return jax.tree_util.tree_map(lambda _: P(), state)


def make_train_step(cfg: JoyAIFlashConfig, mesh: Mesh, optimizer=None):
    """``(step_fn, init_fn)`` as ``models/llama.py``'s: ``init_fn(key)``
    the state ``{"params", "opt", "step"}`` on the mesh,
    ``step_fn(state, batch) -> (state', loss)`` with the state donated.
    Each step sends ``TRAIN_COUNTERS`` to ``observability.
    step_counters()`` (group ``train``)."""
    from .train_step import make_family_train_step
    if mesh.size > 1:
        raise NotImplementedError(
            "models/joyai_flash.py trains one chip's share: the grouped "
            "matmul and splash have no partitioning rule here, and the "
            "experts' exchange across chips is not in the program")
    return make_family_train_step(
        lambda key: shard_params(init_params(cfg, key), cfg, mesh),
        lambda params, batch: loss_and_counts(params, batch, cfg, mesh),
        optimizer, counters=TRAIN_COUNTERS)

