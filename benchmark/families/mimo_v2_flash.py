"""Family ``mimo_v2_flash``: MiMo-V2-Flash-shaped decoders (Xiaomi
MiMo-V2-Flash), served through the program's ``models/mimo_v2_flash.py``
as ONE CHIP'S SHARE of an expert-parallel deployment.

THE EQUATIONS (D = ``hidden_size``, eps ``layernorm_epsilon``). Layer
``l``, hidden ``h [T, D]``::

    a = rms_norm(h, attn.norm);   h = h + attention_l(a, positions)
    x = rms_norm(h, ffn.norm);    h = h + ffn_l(x)

``hybrid_layer_pattern[l]``: 0 a FULL attention layer, 1 a WINDOW one;
``moe_layer_freq[l]``: 0 a dense SwiGLU of ``intermediate_size``, 1
routed experts.

``attention(a)``, ``H`` query heads, q / k head size ``head_dim`` (192),
v head size ``v_head_dim`` (128), ``Hkv`` KV heads (full:
``num_key_value_heads`` 4; window: ``swa_num_key_value_heads`` 8)::

    q = (a @ wq.T).reshape(T, H, 192);   k = (a @ wk.T).reshape(T, Hkv, 192)
    v = (a @ wv.T).reshape(T, Hkv, 128) * attention_value_scale
          (the three projections are held output-major, [out, D])
    q, k: rotary on the FIRST int(192 x partial_rotary_factor) = 64
          dimensions, half-split pairs (x[i], x[i + 32]); base
          rope_theta (full) / swa_rope_theta (window); the other 128 as
          they are
    z_ij = q_i . k_j / sqrt(192)
    full:    j <= i;                       p = softmax_j(z)
    window:  i - sliding_window < j <= i;  with the head's sink s_h
             m = max(max_j z_ij, s_h)
             p_ij = exp(z_ij - m) / (exp(s_h - m) + sum_j exp(z_ij - m))
    out = concat_heads(p @ v) @ wo

(a sink joins the denominator and takes no value; only window layers
have one: ``add_swa_attention_sink_bias`` true,
``add_full_attention_sink_bias`` false).

``routed(x)``: ``R`` = ``router_experts`` router outputs, ``k`` =
``num_experts_per_tok`` a token, ``scoring_func`` sigmoid, ``noaux_tc``
with one group::

    s = sigmoid(f32(x) @ f32(router));   S = top_k(s + router_bias)
    w_e = s_e / sum_{e' in S} s_e'          (norm_topk_prob; no scaling
                                             factor, no shared expert)
    y = sum_{e in S, e held} w_e swiglu(x, expert_e)

THE CUT: this chip holds ``n_routed_experts`` (as the cell runs it: 16)
of the ``router_experts`` (256) experts the router scores; a choice of
an expert another chip holds adds NOTHING here, in the program and in
this reference alike, and that partial sum goes on to the next layer.

``make_params`` is the benchmark's own recipe in the pytree ``models/
mimo_v2_flash.py`` documents (stacked BY KIND: ``full``, ``window``,
``dense``, ``moe``): matrices normal(0, 1/sqrt(fan_in)) in the served
dtype (normed rows times such a ``wq`` / ``wk`` give unit q and k, so
the scores have deviation 1.0 over their 192 dimensions); the router
float32 normal(0, 1/sqrt(D)) (logits of unit deviation: the chosen
eight of 256 score 0.85-0.95 each); ``router_bias`` float32 normal x
0.01 (the gap between the 8th and the 9th score is about 0.01, so the
bias changes the choice for a good share of tokens); the sinks float32
normal(2, 1): a sink of 2 weighs as much as seven average keys of a
window of 128 (about 3 % of a row's mass, up to a third for a head that
draws 4), so that a program that drops the sink is far outside the
limits; norms ones.

THE REFERENCE is ``_layer`` below: plain float32 ``jax.numpy`` from the
equations, one sequence, no cache, nothing of ``paddle_tpu``. Attention
one KV head's group of query heads at a time and ``QUERY_BLOCK`` query
rows at a time (a full layer over every key, a window layer over the
``QUERY_BLOCK + sliding_window`` keys a block can see), the dense SwiGLU
``FFN_BLOCK`` of its columns at a time, a python loop over the ``k``
choices, the held experts one at a time over all rows, masked, so that a
17 408-token sequence fits beside the weights. ``reference_layers``
hands the harness one group a RUN of layers of one (attention, feed-
forward) pattern, each a view of its kinds' stacks (``_Rows``: nothing
is copied).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench_family_dense_decoder import (CONTROL_ROUND_TO,  # noqa: F401
                                        _make, dtype_of, seed_key)
from harness.reference import F32, _a, _w, rms_norm, swiglu

FULL, WINDOW, DENSE, MOE = "full", "window", "dense", "moe"
# the scopes models/mimo_v2_flash.py enters beyond the harness's own
SCOPES = ("attn.full", "attn.window", "window_pool.write")
KERNELS = {"attn.full.kernel": r"^ragged_paged_attention",
           "attn.window.kernel": r"^ragged_paged_attention"}
ROUTER_BIAS_STD = 0.01
SINK_MEAN, SINK_STD = 2.0, 1.0
QUERY_BLOCK, FFN_BLOCK = 256, 2048


def routed_experts(m: dict) -> int:
    """Experts the ROUTER scores (``router_experts``, a key of its own
    beside the published ``n_routed_experts``: a cell overrides that one
    to what THIS CHIP holds, and the router keeps its width)."""
    return int(m.get("router_experts", m["n_routed_experts"]))


def deployment(m: dict) -> tuple:
    """``(chips, this chip)`` of the expert-parallel deployment whose
    share this configuration is."""
    chips, rest = divmod(routed_experts(m), m["n_routed_experts"])
    if rest or not 0 <= int(m.get("ep_this_chip", 0)) < chips:
        raise SystemExit("router_experts is not a whole number of shares "
                         "of n_routed_experts, or ep_this_chip is none")
    return chips, int(m.get("ep_this_chip", 0))


def held(m: dict) -> tuple:
    """``(first, count)`` of the routed experts this chip holds."""
    n = m["n_routed_experts"]
    return deployment(m)[1] * n, n


def layer_kinds(m: dict) -> list:
    """``[(attention kind, feed-forward kind)]`` of every layer."""
    L = m["num_hidden_layers"]
    pat, freq = m["hybrid_layer_pattern"], m["moe_layer_freq"]
    if len(pat) < L or len(freq) < L:
        raise SystemExit(f"hybrid_layer_pattern / moe_layer_freq must name "
                         f"each of the {L} layers")
    # a model cut in depth alone keeps the lists' first entries
    return [(WINDOW if w else FULL, MOE if e else DENSE)
            for w, e in zip(pat[:L], freq[:L])]


def counts(m: dict) -> dict:
    kinds = layer_kinds(m)
    return {k: sum(k in layer for layer in kinds)
            for k in (FULL, WINDOW, DENSE, MOE)}


def kv_heads(m: dict, kind: str) -> int:
    return m["swa_num_key_value_heads" if kind == WINDOW
             else "num_key_value_heads"]


def rotary_dim(m: dict) -> int:
    return int(m["head_dim"] * m["partial_rotary_factor"]) // 2 * 2


def param_shapes(m: dict) -> dict:
    """``{leaf path: (shape, fan)}`` of every leaf drawn normal(0,
    1/sqrt(fan)) in the served dtype."""
    D, V, H = m["hidden_size"], m["vocab_size"], m["num_attention_heads"]
    Dk, Dv = m["head_dim"], m["v_head_dim"]
    F, Fm, n = m["intermediate_size"], m["moe_intermediate_size"], held(m)[1]
    L = counts(m)
    out = {"embed": ((V, D), D), "lm_head": ((D, V), D)}
    for kind in (FULL, WINDOW):
        Hkv = kv_heads(m, kind)
        out.update({
            # q / k / v projections OUTPUT-MAJOR (q = a @ wq.T), as the
            # program holds them
            f"{kind}.wq": ((L[kind], H * Dk, D), D),
            f"{kind}.wk": ((L[kind], Hkv * Dk, D), D),
            f"{kind}.wv": ((L[kind], Hkv * Dv, D), D),
            f"{kind}.wo": ((L[kind], H * Dv, D), H * Dv)})
    out.update({
        "dense.w_gate": ((L[DENSE], D, F), D),
        "dense.w_up": ((L[DENSE], D, F), D),
        "dense.w_down": ((L[DENSE], F, D), F),
        "moe.experts.w_gate": ((L[MOE], n, D, Fm), D),
        "moe.experts.w_up": ((L[MOE], n, D, Fm), D),
        "moe.experts.w_down": ((L[MOE], n, Fm, D), Fm)})
    return out


def f32_shapes(m: dict) -> dict:
    D, E, L = m["hidden_size"], routed_experts(m), counts(m)
    return {"moe.router": ((L[MOE], D, E), D),
            "moe.router_bias": ((L[MOE], E), 1.0 / ROUTER_BIAS_STD ** 2),
            "window.sinks": ((L[WINDOW], m["num_attention_heads"]),
                             1.0 / SINK_STD ** 2)}


def norm_shapes(m: dict) -> dict:
    D, L = m["hidden_size"], counts(m)
    return {"final_norm": (D,), **{f"{k}.norm": (L[k], D)
                                   for k in (FULL, WINDOW, DENSE, MOE)}}


def param_count(m: dict) -> int:
    """Parameters of the configuration AS RUN (the experts this chip
    holds, the rows of the vocabulary it holds)."""
    return int(sum(np.prod(s) for s, _ in param_shapes(m).values())
               + sum(np.prod(s) for s, _ in f32_shapes(m).values())
               + sum(np.prod(s) for s in norm_shapes(m).values()))


def make_params(model: dict, seed: int) -> dict:
    dt = dtype_of(model)
    key = seed_key(seed)
    made = _make(key, shapes=tuple(param_shapes(model).items()), dtype=dt)
    made.update(_make(jax.random.fold_in(key, 1),
                      shapes=tuple(f32_shapes(model).items()), dtype=F32))
    made["window.sinks"] = made["window.sinks"] + SINK_MEAN
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
    from paddle_tpu.models import mimo_v2_flash as M
    refused = {
        "attention_bias": bool(model.get("attention_bias")),
        "scoring_func": model.get("scoring_func", "sigmoid") != "sigmoid",
        "n_group": model.get("n_group", 1) != 1,
        "n_shared_experts": bool(model.get("n_shared_experts")),
        "routed_scaling_factor": model.get("routed_scaling_factor")
        not in (None, 1, 1.0),
        "add_full_attention_sink_bias": bool(
            model.get("add_full_attention_sink_bias")),
        "add_swa_attention_sink_bias": not model.get(
            "add_swa_attention_sink_bias", True),
        "tie_word_embeddings": bool(model.get("tie_word_embeddings")),
    }
    if any(refused.values()):
        raise SystemExit(f"models/mimo_v2_flash.py does not serve "
                         f"{sorted(k for k, v in refused.items() if v)} as "
                         f"this configuration sets them")
    layer_kinds(model)      # the two lists name every layer
    cfg = M.MimoV2FlashConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        moe_intermediate_size=model["moe_intermediate_size"],
        num_hidden_layers=model["num_hidden_layers"],
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        swa_num_key_value_heads=model["swa_num_key_value_heads"],
        head_dim=model["head_dim"], v_head_dim=model["v_head_dim"],
        partial_rotary_factor=float(model["partial_rotary_factor"]),
        rope_theta=float(model["rope_theta"]),
        swa_rope_theta=float(model["swa_rope_theta"]),
        sliding_window=model["sliding_window"],
        attention_value_scale=float(model["attention_value_scale"]),
        hybrid_layer_pattern=tuple(
            model["hybrid_layer_pattern"][:model["num_hidden_layers"]]),
        moe_layer_freq=tuple(
            model["moe_layer_freq"][:model["num_hidden_layers"]]),
        n_routed_experts=routed_experts(model),
        num_experts_per_tok=model["num_experts_per_tok"],
        norm_topk_prob=bool(model["norm_topk_prob"]),
        rms_norm_eps=model["layernorm_epsilon"],
        max_position_embeddings=model["max_position_embeddings"],
        experts_held=held(model), dtype=dtype_of(model), **kw)
    return cfg, M


# ------------------------------------------------------- the reference ----

def partial_rotary(x, positions, theta, rot: int):
    """Rotary embedding on the first ``rot`` dimensions of ``x [T, heads,
    Dh]``, half-split pairs ``(x[i], x[i + rot / 2])``."""
    half = rot // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = positions.astype(F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


def attention(lp, h, positions, m, round_to, kind: str):
    """The attention sublayer's update over ``h [T, D]`` (``positions``
    ``0 .. T-1``): one KV head's group of query heads at a time,
    ``QUERY_BLOCK`` query rows at a time; a window layer's block over
    the ``QUERY_BLOCK + sliding_window`` keys it can see."""
    T, _ = h.shape
    H, Hkv = m["num_attention_heads"], kv_heads(m, kind)
    Dk, Dv, g = m["head_dim"], m["v_head_dim"], H // kv_heads(m, kind)
    W = m["sliding_window"] if kind == WINDOW else 0
    theta = m["swa_rope_theta" if kind == WINDOW else "rope_theta"]
    x = _a(rms_norm(h, _w(lp["norm"]), m["layernorm_epsilon"]), round_to)
    q = (x @ _w(lp["wq"], round_to).T).reshape(T, H, Dk)
    k = (x @ _w(lp["wk"], round_to).T).reshape(T, Hkv, Dk)
    v = (x @ _w(lp["wv"], round_to).T).reshape(T, Hkv, Dv)
    v = v * m["attention_value_scale"]
    q = partial_rotary(q, positions, theta, rotary_dim(m))
    k = partial_rotary(k, positions, theta, rotary_dim(m))
    sinks = (lp["sinks"].astype(F32).reshape(Hkv, g) if kind == WINDOW
             else jnp.zeros((Hkv, g), F32))
    qb = min(QUERY_BLOCK, T)
    assert T % qb == 0, (T, qb)
    scale = 1.0 / np.sqrt(Dk)
    # a window block's keys: positions b*qb - W .. b*qb + qb - 1 (W
    # zero rows in front stand for the positions before 0: masked)
    span = qb + W if W else T

    def head(args):
        qh, kh, vh, sh = args           # [T, g, Dk] [T, Dk] [T, Dv] [g]
        if W:
            kh = jnp.pad(kh, ((W, 0), (0, 0)))
            vh = jnp.pad(vh, ((W, 0), (0, 0)))

        def block(b):
            qblk = jax.lax.dynamic_slice_in_dim(qh, b * qb, qb)
            q_pos = b * qb + jnp.arange(qb)
            if W:
                ks = jax.lax.dynamic_slice_in_dim(kh, b * qb, span)
                vs = jax.lax.dynamic_slice_in_dim(vh, b * qb, span)
                k_pos = b * qb - W + jnp.arange(span)
            else:
                ks, vs, k_pos = kh, vh, jnp.arange(T)
            z = jnp.einsum("tgd,sd->gts", qblk, ks) * scale
            mask = (k_pos[None, :] <= q_pos[:, None]) & (k_pos[None, :] >= 0)
            if W:
                mask = mask & (k_pos[None, :] > q_pos[:, None] - W)
            z = jnp.where(mask[None], z, -jnp.inf)
            if kind == WINDOW:
                s = sh[:, None, None]
                mx = jnp.maximum(z.max(-1, keepdims=True), s)
                e = jnp.exp(z - mx)
                p = e / (jnp.exp(s - mx) + e.sum(-1, keepdims=True))
            else:
                p = jax.nn.softmax(z, axis=-1)
            return jnp.einsum("gts,sd->tgd", p, vs)

        return jax.lax.map(block, jnp.arange(T // qb)).reshape(T, g, Dv)

    o = jax.lax.map(head, (q.reshape(T, Hkv, g, Dk).transpose(1, 0, 2, 3),
                           k.transpose(1, 0, 2), v.transpose(1, 0, 2),
                           sinks))
    o = o.transpose(1, 0, 2, 3).reshape(T, H * Dv)
    return _a(o, round_to) @ _w(lp["wo"], round_to)


def dense_swiglu(lp, x, round_to):
    """The dense SwiGLU, ``FFN_BLOCK`` of its columns at a time (a
    block's weights are cast to float32 inside the loop)."""
    D, F = lp["w_gate"].shape
    fb = min(FFN_BLOCK, F)
    x = _a(x, round_to)

    def block(acc, j):
        g = _w(jax.lax.dynamic_slice(lp["w_gate"], (0, j * fb), (D, fb)),
               round_to)
        u = _w(jax.lax.dynamic_slice(lp["w_up"], (0, j * fb), (D, fb)),
               round_to)
        d = _w(jax.lax.dynamic_slice(lp["w_down"], (j * fb, 0), (fb, D)),
               round_to)
        return acc + _a(jax.nn.silu(x @ g) * (x @ u), round_to) @ d, None

    y, _ = jax.lax.scan(block, jnp.zeros_like(x), jnp.arange(F // fb))
    return y


def routed(lp, x, m, round_to):
    """The routed block over ``x [T, D]``: a loop over the ``k`` choices
    gives every token its weight on each held expert; the held experts
    run one at a time over all rows."""
    k = m["num_experts_per_tok"]
    lo, n = held(m)
    s = jax.nn.sigmoid(x @ lp["router"].astype(F32))
    _, top = jax.lax.top_k(s + lp["router_bias"].astype(F32), k)
    rows = jnp.arange(x.shape[0])
    chosen = s[rows[:, None], top]                              # [T, k]
    if m.get("norm_topk_prob", True):
        chosen = chosen / chosen.sum(-1, keepdims=True)
    on_held = jnp.zeros((x.shape[0], n), F32)
    for j in range(k):
        e = top[:, j]
        here = (e >= lo) & (e < lo + n)
        on_held = on_held.at[rows, jnp.clip(e - lo, 0, n - 1)].add(
            jnp.where(here, chosen[:, j], 0.0))
    ex = lp["experts"]

    def one(acc, xs):
        g, u, d, w_e = xs
        y = swiglu(x, _w(g, round_to), _w(u, round_to), _w(d, round_to),
                   round_to)
        return acc + y * w_e[:, None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (ex["w_gate"], ex["w_up"], ex["w_down"], on_held.T))
    return y


def _layer(lp, h, positions, m, round_to, attn_kind: str, ffn_kind: str):
    T = h.shape[0]
    pad = -T % min(QUERY_BLOCK, -(-T // 8) * 8)
    if pad:
        # padding rows sit at later positions than every real one:
        # causal, so no real row sees them
        h = jnp.pad(h, ((0, pad), (0, 0)))
        positions = jnp.concatenate(
            [positions, positions[-1] + 1
             + jnp.arange(pad, dtype=positions.dtype)])
    h = h + attention(lp["attn"], h, positions, m, round_to, attn_kind)
    x = rms_norm(h, _w(lp["ffn"]["norm"]), m["layernorm_epsilon"])
    y = (dense_swiglu(lp["ffn"], x, round_to) if ffn_kind == DENSE
         else routed(lp["ffn"], x, m, round_to))
    return (h + y)[:T]


def full_dense_layer(lp, h, positions, m, round_to=None):
    return _layer(lp, h, positions, m, round_to, FULL, DENSE)


def full_moe_layer(lp, h, positions, m, round_to=None):
    return _layer(lp, h, positions, m, round_to, FULL, MOE)


def window_dense_layer(lp, h, positions, m, round_to=None):
    return _layer(lp, h, positions, m, round_to, WINDOW, DENSE)


def window_moe_layer(lp, h, positions, m, round_to=None):
    return _layer(lp, h, positions, m, round_to, WINDOW, MOE)


LAYER_FNS = {(FULL, DENSE): full_dense_layer, (FULL, MOE): full_moe_layer,
             (WINDOW, DENSE): window_dense_layer,
             (WINDOW, MOE): window_moe_layer}


class _Rows:
    """Rows ``lo .. lo + n - 1`` of a kind's stack as a stack of their
    own, as the harness reads a group's (``.shape[0]``, ``[i]``): a
    view, so nothing is copied until one layer's row is."""

    def __init__(self, base, lo: int, n: int):
        self.base, self.lo = base, lo
        self.shape = (n,) + tuple(base.shape[1:])

    def __getitem__(self, i: int):
        return self.base[self.lo + i]


def reference_layers(params, model):
    """One group a RUN of layers of one (attention, feed-forward)
    pattern, in model order; a group's stack is ``{"attn": ..., "ffn":
    ...}``, views of the rows of its two kinds' stacks."""
    groups, at = [], {FULL: 0, WINDOW: 0, DENSE: 0, MOE: 0}
    kinds = layer_kinds(model)
    i = 0
    while i < len(kinds):
        n = 1
        while i + n < len(kinds) and kinds[i + n] == kinds[i]:
            n += 1
        op, ffn = kinds[i]
        stack = {
            "attn": jax.tree_util.tree_map(
                lambda a, lo=at[op]: _Rows(a, lo, n), params[op]),
            "ffn": jax.tree_util.tree_map(
                lambda a, lo=at[ffn]: _Rows(a, lo, n), params[ffn])}
        groups.append((LAYER_FNS[kinds[i]], stack))
        at[op] += n
        at[ffn] += n
        i += n
    return groups
