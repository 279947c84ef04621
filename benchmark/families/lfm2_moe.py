"""Family ``lfm2_moe``: LFM2-MoE-shaped decoders, served through the
program's ``models/lfm2_moe.py``. A stack of layers of three kinds that
follows the configuration's ``layer_types``: every layer is an operator
(``full_attention``: GQA with a norm over each head of q and k, rotary;
``conv``: the gated short convolution) and a feed-forward (a dense
SwiGLU in the first ``num_dense_layers`` layers, then routed experts
behind a sigmoid router with a selection bias).

THE PATTERN AND A CUT IN DEPTH. The kinds of an ``L``-layer model are
the FIRST ``L`` entries of ``layer_types`` and its dense layers the
first ``min(num_dense_layers, L)``: a cut is a slice of the published
stack from its start, never a re-patterning. A family with a layer
pattern has to read its kinds this way, because the harness's own test
(``tests/test_harness.py``) sets ``num_hidden_layers`` to 2 on every
tiny configuration and nothing else; a tiny configuration therefore
begins with layers of different kinds (``tests/tiny/configs/
tiny-lfm2.json``: conv-dense, then attention-MoE).

HOW THE CELL'S 9 LAYERS MAP TO THE PUBLISHED MODEL. The published stack
(0-based) is conv-dense, conv-dense, then ten periods of (attention,
conv, conv, conv) with experts, the last one cut short. The
configuration file keeps published layers 1-9: its ``layer_types`` is
entries 1..9 of the published list and its ``num_dense_layers`` is 1.
So layer 0 here is published layer 1 (conv, dense), layers 1-4 are
published 2-5 (the first whole period) and layers 5-8 published 6-9 (the
second). ``tests/test_lfm2_moe.py`` checks at a tiny size that such a
slice run alone agrees with the reference's.

``make_params`` is the benchmark's own recipe, in the pytree
``models/lfm2_moe.py`` documents (parameters stacked BY KIND: ``attn``,
``conv``, ``dense``, ``moe``): normal(0, 1/sqrt(fan_in)) matrices in the
served dtype, conv taps normal(0, 1/sqrt(taps)), the router float32
normal x 0.02 (as the program keeps it), ``expert_bias`` float32 normal
x 0.1 — non-zero, so that it changes the chosen experts for a good share
of tokens (sigmoid scores of a x 0.02 router lie within a few hundredths
of one half) and a bias that leaked into the weights would show.

THE REFERENCE is ``layer`` below: plain float32 ``jax.numpy`` from the
published equations, one sequence, no cache, no state, nothing of
``paddle_tpu``. ``reference_layers`` hands the harness the layers IN
ORDER as groups of equal kind, each a VIEW (``_Rows``) of the rows of
the kinds' stacks it needs: slicing would copy gigabytes of experts
beside the engine that still holds them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench_family_dense_decoder import (CONTROL_ROUND_TO,  # noqa: F401
                                        _make, dtype_of, seed_key)
from harness.reference import (F32, _a, _w, causal_attention, rms_norm,
                               rotary, swiglu)

# the scopes models/lfm2_moe.py enters beyond the harness's own
SCOPES = ("shortconv.in", "shortconv.mix", "conv_state.write",
          "shortconv.out")
KERNELS: dict = {}
ATTN, CONV = "full_attention", "conv"
OP_KEY = {ATTN: "attn", CONV: "conv"}
ROUTER_EPS = 1e-6       # p_e = s_e / (sum of the chosen s + 1e-6)


def layer_kinds(m: dict) -> list:
    """``[(operator, feed-forward)]`` of the model's layers: the first
    ``num_hidden_layers`` entries of ``layer_types``, dense in the first
    ``min(num_dense_layers, L)``."""
    L = m["num_hidden_layers"]
    types = list(m["layer_types"])[:L]
    if len(types) < L:
        raise SystemExit(f"layer_types names {len(types)} layers, "
                         f"num_hidden_layers is {L}")
    nd = min(m["num_dense_layers"], L)
    return [(t, "dense" if i < nd else "moe") for i, t in enumerate(types)]


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def rope_theta(m: dict) -> float:
    return float(m["rope_theta"] if "rope_theta" in m
                 else m["rope_parameters"]["rope_theta"])


def param_shapes(m: dict) -> dict:
    """``{leaf path: (shape, fan)}`` of every matrix (std = 1/sqrt(fan));
    the two float32 leaves are ``moe.router`` and ``moe.expert_bias``."""
    D, V, K = m["hidden_size"], m["vocab_size"], m["conv_L_cache"]
    H, Hkv, Dh = m["num_attention_heads"], m["num_key_value_heads"], head_dim(m)
    E, F, Fm = (m["num_experts"], m["intermediate_size"],
                m["moe_intermediate_size"])
    kinds = layer_kinds(m)
    La = sum(op == ATTN for op, _ in kinds)
    Lc = len(kinds) - La
    Ld = sum(ffn == "dense" for _, ffn in kinds)
    Lm = len(kinds) - Ld
    return {
        "embed": ((V, D), D), "lm_head": ((D, V), D),
        "attn.wq": ((La, D, H * Dh), D), "attn.wk": ((La, D, Hkv * Dh), D),
        "attn.wv": ((La, D, Hkv * Dh), D),
        "attn.wo": ((La, H * Dh, D), H * Dh),
        "conv.in_proj": ((Lc, D, 3 * D), D),
        "conv.conv_w": ((Lc, D, K), K),
        "conv.out_proj": ((Lc, D, D), D),
        "dense.w_gate": ((Ld, D, F), D), "dense.w_up": ((Ld, D, F), D),
        "dense.w_down": ((Ld, F, D), F),
        "moe.experts.w_gate": ((Lm, E, D, Fm), D),
        "moe.experts.w_up": ((Lm, E, D, Fm), D),
        "moe.experts.w_down": ((Lm, E, Fm, D), Fm),
        # float32: std 0.02 and 0.1 (fan = 1 / std^2)
        "moe.router": ((Lm, D, E), 2500.0),
        "moe.expert_bias": ((Lm, E), 100.0),
    }


def norm_shapes(m: dict) -> dict:
    sh = param_shapes(m)
    D, Dh = m["hidden_size"], head_dim(m)
    La, Lc = sh["attn.wq"][0][0], sh["conv.in_proj"][0][0]
    Ld, Lm = sh["dense.w_gate"][0][0], sh["moe.router"][0][0]
    return {"final_norm": (D,), "attn.operator_norm": (La, D),
            "attn.q_norm": (La, Dh), "attn.k_norm": (La, Dh),
            "conv.operator_norm": (Lc, D), "dense.ffn_norm": (Ld, D),
            "moe.ffn_norm": (Lm, D)}


def param_count(m: dict) -> int:
    return int(sum(np.prod(s) for s, _ in param_shapes(m).values())
               + sum(np.prod(s) for s in norm_shapes(m).values()))


def make_params(model: dict, seed: int) -> dict:
    sh, dt = param_shapes(model), dtype_of(model)
    f32 = {k: sh.pop(k) for k in ("moe.router", "moe.expert_bias")}
    made = _make(seed_key(seed), shapes=tuple(sh.items()), dtype=dt)
    made.update(_make(jax.random.fold_in(seed_key(seed), 1),
                      shapes=tuple(f32.items()), dtype=jnp.float32))
    made.update({k: jnp.ones(s, dt) for k, s in norm_shapes(model).items()})
    out: dict = {}
    for name, arr in made.items():
        node = out
        *path, leaf = name.split(".")
        for k in path:
            node = node.setdefault(k, {})
        node[leaf] = arr
    return out


def program_config(model: dict, **kw):
    from paddle_tpu.models import lfm2_moe as M
    L = model["num_hidden_layers"]
    if head_dim(model) * model["num_attention_heads"] != model["hidden_size"]:
        raise SystemExit("models/lfm2_moe.py derives head_dim as "
                         "hidden_size / num_attention_heads")
    if model.get("conv_bias"):
        raise SystemExit("models/lfm2_moe.py has no bias on the short "
                         "convolution")
    cfg = M.Lfm2MoeConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        moe_intermediate_size=model["moe_intermediate_size"],
        num_hidden_layers=L,
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        num_dense_layers=min(model["num_dense_layers"], L),
        num_experts=model["num_experts"],
        num_experts_per_tok=model["num_experts_per_tok"],
        layer_types=tuple(model["layer_types"][:L]),
        conv_L_cache=model["conv_L_cache"], norm_eps=model["norm_eps"],
        rope_theta=rope_theta(model),
        norm_topk_prob=bool(model["norm_topk_prob"]),
        use_expert_bias=bool(model["use_expert_bias"]),
        routed_scaling_factor=float(model["routed_scaling_factor"]),
        max_position_embeddings=model["max_position_embeddings"],
        dtype=dtype_of(model), **kw)
    return cfg, M


# ------------------------------------------------------- the reference ----

def attention_operator(lp, h, positions, m, round_to):
    """``a = n(h)``; q as H heads, k and v as Hkv heads of Dh; a norm
    over the Dh of each head of q and of k (one weight vector for all
    heads); half-split rotary on q and k; causal softmax attention at
    scale 1/sqrt(Dh), a KV head serving H/Hkv query heads; ``Wo``."""
    T = h.shape[0]
    H, Hkv, Dh = m["num_attention_heads"], m["num_key_value_heads"], head_dim(m)
    a = _a(rms_norm(h, _w(lp["operator_norm"]), m["norm_eps"]), round_to)
    q = (a @ _w(lp["wq"], round_to)).reshape(T, H, Dh)
    k = (a @ _w(lp["wk"], round_to)).reshape(T, Hkv, Dh)
    v = (a @ _w(lp["wv"], round_to)).reshape(T, Hkv, Dh)
    q = rms_norm(q, _w(lp["q_norm"]), m["norm_eps"])
    k = rms_norm(k, _w(lp["k_norm"]), m["norm_eps"])
    q = rotary(q, positions, m["rope_theta"])
    k = rotary(k, positions, m["rope_theta"])
    return _a(causal_attention(q, k, v), round_to) @ _w(lp["wo"], round_to)


def conv_operator(lp, h, positions, m, round_to):
    """``[B, C, x] = split3(a W_in)``; ``u = B * x``; ``c_t = sum_j
    w[:, j] * u_{t-(K-1)+j}`` with ``u_s = 0`` for ``s < 0`` (depthwise,
    causal, ``w[:, K-1]`` meets the current token, no bias); ``o = (C *
    c) W_out``."""
    T, K = h.shape[0], m["conv_L_cache"]
    a = _a(rms_norm(h, _w(lp["operator_norm"]), m["norm_eps"]), round_to)
    b, c, x = jnp.split(a @ _w(lp["in_proj"], round_to), 3, axis=-1)
    u = jnp.concatenate([jnp.zeros((K - 1, b.shape[-1]), F32), b * x], axis=0)
    w = lp["conv_w"].astype(F32)                                # [D, K]
    conv = sum(w[:, j] * u[j:j + T] for j in range(K))
    return _a(c * conv, round_to) @ _w(lp["out_proj"], round_to)


def dense_ffn(lp, h, m, round_to):
    f = rms_norm(h, _w(lp["ffn_norm"]), m["norm_eps"])
    return swiglu(f, _w(lp["w_gate"], round_to), _w(lp["w_up"], round_to),
                  _w(lp["w_down"], round_to), round_to)


def router_weights(f, router, bias, m, round_to=None):
    """``[T, E]``: ``s = sigmoid(f W_g)``; the chosen set is the top-k of
    ``s + b`` (the bias enters the CHOICE only); ``p_e = s_e / (sum of
    the chosen s + 1e-6)`` where ``norm_topk_prob``, times
    ``routed_scaling_factor``; zero for the experts not chosen."""
    s = jax.nn.sigmoid(_a(f, round_to) @ _w(router, round_to))
    choose = s + bias.astype(F32) if m["use_expert_bias"] else s
    _, top_i = jax.lax.top_k(choose, m["num_experts_per_tok"])
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    if m["norm_topk_prob"]:
        top_s = top_s / (top_s.sum(-1, keepdims=True) + ROUTER_EPS)
    top_s = top_s * m["routed_scaling_factor"]
    return jnp.zeros_like(s).at[
        jnp.arange(f.shape[0])[:, None], top_i].set(top_s)


def moe_ffn(lp, h, m, round_to):
    """Dropless: every token reaches its k experts. One expert at a time
    over all rows, weighted: slow and plain. No shared expert."""
    f = rms_norm(h, _w(lp["ffn_norm"]), m["norm_eps"])
    weight = router_weights(f, lp["router"], lp["expert_bias"], m, round_to)
    ex = lp["experts"]

    def one(acc, xs):
        g, u, d, w_e = xs
        y = swiglu(f, _w(g, round_to), _w(u, round_to), _w(d, round_to),
                   round_to)
        return acc + y * w_e[:, None], None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(f),
        (ex["w_gate"], ex["w_up"], ex["w_down"], weight.T))
    return routed


def layer(lp, h, positions, m, round_to=None):
    """One layer of whatever kind its parameters are: ``h + operator(n(h))``
    then ``h + feed_forward(n(h))``."""
    if "attn" in lp:
        h = h + attention_operator(lp["attn"], h, positions, m, round_to)
    else:
        h = h + conv_operator(lp["conv"], h, positions, m, round_to)
    if "dense" in lp:
        return h + dense_ffn(lp["dense"], h, m, round_to)
    return h + moe_ffn(lp["moe"], h, m, round_to)


class _Rows:
    """Rows ``lo:hi`` of a stacked array, as the harness reads a group's
    stack (``.shape[0]``, ``[i]``): a view, so that a group's gigabytes
    are not copied beside the program's."""

    def __init__(self, base, lo: int, hi: int):
        self.base, self.lo = base, lo
        self.shape = (hi - lo,) + tuple(base.shape[1:])

    def __getitem__(self, i: int):
        return self.base[self.lo + i]


def reference_layers(params, model):
    """The layers IN ORDER as runs of equal kind: ``[(layer, {operator
    kind: rows, feed-forward kind: rows})]`` — at the cell's cut
    conv-dense; attention-MoE; conv-MoE x 3; attention-MoE; conv-MoE x
    3."""
    groups, at = [], {}
    kinds = layer_kinds(model)
    i = 0
    while i < len(kinds):
        j = i
        while j < len(kinds) and kinds[j] == kinds[i]:
            j += 1
        stack = {}
        for key in (OP_KEY[kinds[i][0]], kinds[i][1]):
            lo = at.get(key, 0)
            stack[key] = jax.tree_util.tree_map(
                lambda a, lo=lo: _Rows(a, lo, lo + j - i), params[key])
            at[key] = lo + j - i
        groups.append((layer, stack))
        i = j
    return groups
