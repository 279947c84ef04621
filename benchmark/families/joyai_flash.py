"""Family ``joyai_flash``: JoyAI-LLM-Flash-shaped decoders (jdopensource
JoyAI-LLM-Flash, the DeepSeek-V3 layer at its own numbers), TRAINED
through the program's ``models/joyai_flash.py`` as ONE CHIP'S SHARE of
an expert-parallel job.

THE EQUATIONS (D = ``hidden_size``, eps ``rms_norm_eps``). A layer, for
one sequence ``h [T, D]`` at positions ``p``; ``H`` heads,
``q_lora_rank`` Rq, ``kv_lora_rank`` Rkv, head parts nope / rope / v::

    a    = rms_norm(h, input_norm)
    c_q  = rms_norm(a @ wq_a, q_a_norm)
    q    = (c_q @ wq_b).reshape(T, H, nope + rope)          # a head: [wq_nope | wq_rope]
    c_kv = rms_norm(a @ wkv_a, kv_a_norm)
    k_r  = rope(a @ wk_rope);  q_r = rope(q[..., nope:])   # interleaved pairs, ONE k_r for all heads
    k_n[h] = c_kv @ w_uk[h].T;  v[h] = c_kv @ w_uv[h]       # kv_b_proj a head, applied to EVERY token
    p    = causal_softmax_f32((q_n . k_n + q_r . k_r) / sqrt(nope + rope))
    h    = h + concat_heads(p @ v) @ wo
    m    = rms_norm(h, post_norm)
    layers < first_k_dense_replace:  h = h + swiglu(m, w_gate, w_up, w_down)
    the others:   s = sigmoid(f32(m) @ f32(router));  S = top_k(s + router_bias)
                  w_e = routed_scaling_factor * s_e / (sum_{e in S} s_e + 1e-20)
                  h = h + sum_{e in S, e held} w_e swiglu(m, expert_e) + swiglu(m, shared)

(the bias enters the choice only; ``n_group`` = ``topk_group`` = 1: no
group limit; nothing is dropped). THE CUT: this chip holds
``n_routed_experts`` (as the cell runs it: 32) of the ``router_experts``
(256) the router scores; a choice of an expert another chip holds adds
NOTHING here, in the program and in this reference alike, and that
partial sum goes on to the next layer.

``make_params`` is the benchmark's own recipe in the pytree ``models/
joyai_flash.py`` documents (``dense_layers`` and ``layers`` stacks):
matrices normal(0, 1/sqrt(fan_in)) in the served dtype. After the two
latent norms that gives q, k and v of unit deviation and scores of
deviation 1.0 (``tests/test_joyai_flash.py`` holds it within 15 %: PR
40's lesson, there the published latent scales broke it); the router
float32 normal(0, 1/sqrt(D)) (logits of unit deviation), ``router_bias``
float32 normal x 0.002 (small and non-zero: the gap between the 8th and
the 9th score of 256 is about 0.003, so the bias changes the choice for
a good share of tokens), norms ones.

THE REFERENCE: ``dense_layer`` and ``expert_layer`` below, plain float32
``jax.numpy``, nothing of ``paddle_tpu``; attention EXPANDED, one head
and ``QUERY_BLOCK`` query rows at a time (rematerialised under a
gradient), so that an 8192-square of float32 scores (8.6 GB for 32
heads) never exists; the held experts one at a time over ``ROW_BLOCK``
rows, masked. ``mtp_loss`` is the multi-token-prediction term in the
same kind, for the tests only: the harness's training reference has one
loss (``harness/reference.py: TrainReference``).

``train_flops_per_token`` / ``splash_least_seconds`` /
``gmm_least_seconds``: the arithmetic the cell's readers divide by.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench_family_dense_decoder import (CONTROL_ROUND_TO,  # noqa: F401
                                        _make, dtype_of, seed_key)
from harness.reference import F32, _a, _w, rms_norm, swiglu

# the scopes models/joyai_flash.py enters beyond the harness's own
SCOPES = ("attn.mla.q", "attn.mla.kv", "attn.mla.expand", "attn.core",
          "mtp")
KERNELS = {"attn.core.kernel": r"^splash_mha",
           "moe.experts.kernel": r"^grouped_matmul"}
ROUTER_BIAS_STD = 0.002
QUERY_BLOCK, ROW_BLOCK = 2048, 1024
MTP_LOSS_WEIGHT = 0.3


def routed_experts(m: dict) -> int:
    """Routed experts the ROUTER scores (``router_experts``, a key of
    its own beside the published ``n_routed_experts``: a cell overrides
    that one to what THIS CHIP holds, and the router keeps its width)."""
    return int(m.get("router_experts", m["n_routed_experts"]))


def held(m: dict) -> tuple:
    """``(first, count)`` of the routed experts this chip holds."""
    n = int(m["n_routed_experts"])
    chips, rest = divmod(routed_experts(m), n)
    k = int(m.get("ep_this_chip", 0))
    if rest or not 0 <= k < chips:
        raise SystemExit("router_experts is not a whole number of shares "
                         "of n_routed_experts, or ep_this_chip is none")
    return k * n, n


def _layers(m: dict) -> tuple:
    nd = int(m["first_k_dense_replace"])
    return nd, int(m["num_hidden_layers"]) - nd


def _attention_shapes(m: dict, lead: tuple) -> tuple:
    """``({leaf: (shape, fan)}, {norm leaf: shape})`` of one stack's
    attention with its two layer norms."""
    D, H = m["hidden_size"], m["num_attention_heads"]
    Rq, Rkv = m["q_lora_rank"], m["kv_lora_rank"]
    nope, rp, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    mats = {"wq_a": ((D, Rq), D),
            # the published q_b_proj [Rq, H*(nope+rope)] as each head's
            # nope and rope column blocks, output-major (q = c_q @ w.T)
            "wq_nope": ((H * nope, Rq), Rq), "wq_rope": ((H * rp, Rq), Rq),
            # the published kv_a_proj_with_mqa [D, Rkv + rope] as its two
            # column blocks (576 columns are no multiple of 128 lanes)
            "wkv_a": ((D, Rkv), D), "wk_rope": ((D, rp), D),
            # the published kv_b_proj [Rkv, H*(nope+v)] as its K and V
            # blocks a head, head-major
            "w_uk": ((H, nope, Rkv), Rkv), "w_uv": ((H, Rkv, dv), Rkv),
            "wo": ((H * dv, D), H * dv)}
    norms = {"input_norm": (D,), "q_a_norm": (Rq,), "kv_a_norm": (Rkv,),
             "post_norm": (D,)}
    return ({k: (lead + s, fan) for k, (s, fan) in mats.items()},
            {k: lead + s for k, s in norms.items()})


def _expert_layer_shapes(m: dict, lead: tuple) -> tuple:
    """``(bf16 matrices, float32 matrices, norms)`` of expert layers."""
    D, Fm = m["hidden_size"], m["moe_intermediate_size"]
    Fs, n, E = Fm * m["n_shared_experts"], held(m)[1], routed_experts(m)
    mats, norms = _attention_shapes(m, lead)
    mats.update({
        "experts.w_gate": (lead + (n, D, Fm), D),
        "experts.w_up": (lead + (n, D, Fm), D),
        "experts.w_down": (lead + (n, Fm, D), Fm),
        "shared.w_gate": (lead + (D, Fs), D),
        "shared.w_up": (lead + (D, Fs), D),
        "shared.w_down": (lead + (Fs, D), Fs)})
    f32 = {"router": (lead + (D, E), D),
           "router_bias": (lead + (E,), 1.0 / ROUTER_BIAS_STD ** 2)}
    return mats, f32, norms


def param_shapes(m: dict) -> tuple:
    """``(bf16 matrices, float32 matrices, norms)``, each ``{dotted leaf
    path: (shape, fan) or shape}``, of the configuration AS RUN."""
    D, V, F = m["hidden_size"], m["vocab_size"], m["intermediate_size"]
    nd, ne = _layers(m)
    mats = {"embed": ((V, D), D), "lm_head": ((D, V), D)}
    f32, norms = {}, {"final_norm": (D,)}
    dm, dn = _attention_shapes(m, (nd,))
    dm.update({"w_gate": ((nd, D, F), D), "w_up": ((nd, D, F), D),
               "w_down": ((nd, F, D), F)})
    em, ef, en = _expert_layer_shapes(m, (ne,))
    groups = [("dense_layers.", dm, {}, dn), ("layers.", em, ef, en)]
    if m.get("num_nextn_predict_layers", 0):
        mm, mf, mn = _expert_layer_shapes(m, ())
        groups.append(("mtp.layer.", mm, mf, mn))
        mats["mtp.proj"] = ((2 * D, D), 2 * D)
        norms.update({"mtp.embed_norm": (D,), "mtp.hidden_norm": (D,),
                      "mtp.final_norm": (D,)})
    for prefix, gm, gf, gn in groups:
        mats.update({prefix + k: v for k, v in gm.items()})
        f32.update({prefix + k: v for k, v in gf.items()})
        norms.update({prefix + k: v for k, v in gn.items()})
    return mats, f32, norms


def param_count(m: dict) -> int:
    mats, f32, norms = param_shapes(m)
    return int(sum(np.prod(s) for s, _ in mats.values())
               + sum(np.prod(s) for s, _ in f32.values())
               + sum(np.prod(s) for s in norms.values()))


def make_params(model: dict, seed: int) -> dict:
    dt = dtype_of(model)
    key = seed_key(seed)
    mats, f32, norms = param_shapes(model)
    made = _make(key, shapes=tuple(mats.items()), dtype=dt)
    made.update(_make(jax.random.fold_in(key, 1), shapes=tuple(f32.items()),
                      dtype=F32))
    made.update({k: jnp.ones(s, dt) for k, s in norms.items()})
    out: dict = {}
    for name, arr in made.items():
        node = out
        *path, leaf = name.split(".")
        for k in path:
            node = node.setdefault(k, {})
        node[leaf] = arr
    return out


def program_config(model: dict, **kw):
    from paddle_tpu.models import joyai_flash as M
    refused = {
        "attention_bias": bool(model.get("attention_bias")),
        "scoring_func": model.get("scoring_func", "sigmoid") != "sigmoid",
        "topk_method": model.get("topk_method", "noaux_tc") != "noaux_tc",
        "n_group": model.get("n_group", 1) != 1,
        "topk_group": model.get("topk_group", 1) != 1,
        "rope_scaling": model.get("rope_scaling") is not None,
        "rope_interleave": not model.get("rope_interleave", True),
        "hidden_act": model.get("hidden_act", "silu") != "silu",
        "tie_word_embeddings": bool(model.get("tie_word_embeddings")),
        "moe_layer_freq": model.get("moe_layer_freq", 1) != 1,
    }
    if any(refused.values()):
        raise SystemExit(f"models/joyai_flash.py does not run "
                         f"{sorted(k for k, v in refused.items() if v)} as "
                         f"this configuration sets them")
    cfg = M.JoyAIFlashConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        moe_intermediate_size=model["moe_intermediate_size"],
        num_hidden_layers=model["num_hidden_layers"],
        first_k_dense_replace=model["first_k_dense_replace"],
        num_attention_heads=model["num_attention_heads"],
        q_lora_rank=model["q_lora_rank"], kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        n_routed_experts=routed_experts(model),
        num_experts_per_tok=model["num_experts_per_tok"],
        n_shared_experts=model["n_shared_experts"],
        routed_scaling_factor=float(model["routed_scaling_factor"]),
        norm_topk_prob=bool(model["norm_topk_prob"]),
        num_nextn_predict_layers=model.get("num_nextn_predict_layers", 0),
        mtp_loss_weight=MTP_LOSS_WEIGHT,
        rms_norm_eps=model["rms_norm_eps"],
        rope_theta=float(model["rope_theta"]),
        experts_held=held(model), dtype=dtype_of(model), **kw)
    return cfg, M


# ------------------------------------------------------- the reference ----

def rope_pairs(x, positions, theta):
    """Rotary embedding on INTERLEAVED pairs ``(x[2i], x[2i+1])`` of the
    last axis of ``x [T, R]``."""
    R = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, R, 2, dtype=F32) / R))
    ang = positions.astype(F32)[:, None] * inv[None]             # [T, R/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.reshape(x.shape[0], R // 2, 2)
    x0, x1 = x[..., 0], x[..., 1]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     -1).reshape(x.shape[0], R)


def attention(lp, h, positions, m, round_to):
    """``h + MLA(rms_norm(h))``, EXPANDED: ``kv_b`` applied to every
    token; one head at a time, its scores ``QUERY_BLOCK`` query rows at
    a time, both rematerialised under a gradient; the heads' outputs
    are kept (``[H, T, v]``) and projected by ``wo`` at the end."""
    T, D = h.shape
    H, Rq, Rkv = m["num_attention_heads"], m["q_lora_rank"], m["kv_lora_rank"]
    nope, rp, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    a = _a(rms_norm(h, _w(lp["input_norm"]), eps), round_to)
    c_q = _a(rms_norm(a @ _w(lp["wq_a"], round_to), _w(lp["q_a_norm"]), eps),
             round_to)
    c_kv = _a(rms_norm(a @ _w(lp["wkv_a"], round_to), _w(lp["kv_a_norm"]),
                       eps), round_to)
    k_r = rope_pairs(a @ _w(lp["wk_rope"], round_to), positions, theta)
    qb = min(QUERY_BLOCK, T)
    pad = -T % qb
    scale = 1.0 / np.sqrt(nope + rp)
    k_pos = positions

    @jax.checkpoint
    def head(ws):
        wqn, wqr, uk, uv = ws          # [nope, Rq], [rp, Rq], [nope, Rkv], [Rkv, dv]
        q_n = c_q @ _w(wqn, round_to).T
        q_r = rope_pairs(c_q @ _w(wqr, round_to).T, positions, theta)
        k_n = c_kv @ _w(uk, round_to).T
        v = c_kv @ _w(uv, round_to)

        @jax.checkpoint
        def block(args):
            qn_b, qr_b, pos_b = args
            sc = (qn_b @ k_n.T + qr_b @ k_r.T) * scale
            mask = k_pos[None, :] <= pos_b[:, None]
            p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
            return p @ v

        # padding queries sit past every key: they see all and are cut
        blocks = tuple(
            jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                    constant_values=(T if x.ndim == 1 else 0)).reshape(
                (T + pad) // qb, qb, *x.shape[1:])
            for x in (q_n, q_r, positions))
        return jax.lax.map(block, blocks).reshape(T + pad, dv)[:T]

    o = jax.lax.map(head, (lp["wq_nope"].reshape(H, nope, Rq),
                           lp["wq_rope"].reshape(H, rp, Rq),
                           lp["w_uk"], lp["w_uv"]))            # [H, T, dv]
    o = _a(o.transpose(1, 0, 2).reshape(T, H * dv), round_to)
    return h + o @ _w(lp["wo"], round_to)


def dense_layer(lp, h, positions, m, round_to=None):
    h = attention(lp, h, positions, m, round_to)
    x = rms_norm(h, _w(lp["post_norm"]), m["rms_norm_eps"])
    return h + swiglu(x, _w(lp["w_gate"], round_to), _w(lp["w_up"], round_to),
                      _w(lp["w_down"], round_to), round_to)


def router_weights(lp, x, m):
    """``[T, n]``: every token's combine weight on each HELD expert
    (zero where it did not choose it): sigmoid scores in float32, the
    ``k`` largest of score + bias, the chosen scores renormalised and
    scaled. The router's matmul is float32 on both sides and takes no
    part in the control's rounding."""
    k = m["num_experts_per_tok"]
    lo, n = held(m)
    s = jax.nn.sigmoid(x @ lp["router"].astype(F32))
    _, top = jax.lax.top_k(s + lp["router_bias"].astype(F32), k)
    w = jnp.take_along_axis(s, top, axis=-1)
    if m.get("norm_topk_prob"):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * m["routed_scaling_factor"]
    local = top - lo
    here = (local >= 0) & (local < n)
    onehot = jax.nn.one_hot(jnp.where(here, local, n), n + 1, dtype=F32)
    return jnp.einsum("tk,tkn->tn", w, onehot)[:, :n]


def routed(lp, x, m, round_to):
    """The held experts' part over ``x [T, D]``: one expert at a time
    over ``ROW_BLOCK`` rows, masked by the token's weight on it."""
    T, D = x.shape
    on_held = router_weights(lp, x, m)
    ex = lp["experts"]
    rb = min(ROW_BLOCK, T)
    pad = -T % rb

    @jax.checkpoint
    def rows(args):
        xb, wb = args

        def one(acc, xs):
            g, u, d, w_e = xs
            y = swiglu(xb, _w(g, round_to), _w(u, round_to), _w(d, round_to),
                       round_to)
            return acc + y * w_e[:, None], None

        return jax.lax.scan(one, jnp.zeros_like(xb),
                            (ex["w_gate"], ex["w_up"], ex["w_down"], wb.T))[0]

    blocks = tuple(jnp.pad(a, ((0, pad), (0, 0))).reshape(-1, rb, a.shape[1])
                   for a in (x, on_held))
    return jax.lax.map(rows, blocks).reshape(T + pad, D)[:T]


def expert_layer(lp, h, positions, m, round_to=None):
    h = attention(lp, h, positions, m, round_to)
    x = rms_norm(h, _w(lp["post_norm"]), m["rms_norm_eps"])
    sh = lp["shared"]
    return (h + routed(lp, x, m, round_to)
            + swiglu(x, _w(sh["w_gate"], round_to), _w(sh["w_up"], round_to),
                     _w(sh["w_down"], round_to), round_to))


def reference_layers(params, model):
    """Two groups: the leading dense layers, the expert layers."""
    groups = []
    if _layers(model)[0]:
        groups.append((dense_layer, params["dense_layers"], "dense_layers"))
    if _layers(model)[1]:
        groups.append((expert_layer, params["layers"], "layers"))
    return groups


def _head_nll(x, final_norm, lm_head, targets, eps, round_to):
    logits = (_a(rms_norm(x, _w(final_norm), eps), round_to)
              @ _w(lm_head, round_to))
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]


def loss_with_mtp(params, tokens, labels, model, round_to=None,
                  weight: float = MTP_LOSS_WEIGHT):
    """FOR THE TESTS (the harness never calls it): the mean next-token
    cross entropy of ``tokens [B, T]`` / ``labels [B, T]`` plus, where
    the parameters hold a multi-token-prediction module, ``weight``
    times its term: ``h'_i = [rms(embed[t_{i+1}]) | rms(h_i)] @ proj``
    with ``h_i`` the trunk's last hidden state before the final norm,
    one expert layer, a norm of its own, the trunk's head, against
    ``t_{i+2}`` over the positions that have one. Plain float32, one
    sequence at a time."""
    m, eps = dict(model), model["rms_norm_eps"]
    stacks = [(fn, stack) for fn, stack, _ in reference_layers(params, m)]

    def one(args):
        toks, labs = args
        pos = jnp.arange(toks.shape[0], dtype=jnp.int32)
        h = params["embed"][toks].astype(F32)
        for fn, stack in stacks:
            for i in range(jax.tree_util.tree_leaves(stack)[0].shape[0]):
                lp = jax.tree_util.tree_map(lambda a, i=i: a[i], stack)
                h = fn(lp, h, pos, m, round_to)
        nll = _head_nll(h, params["final_norm"], params["lm_head"], labs,
                        eps, round_to).mean()
        if "mtp" not in params:
            return nll
        mp = params["mtp"]
        e = params["embed"][labs].astype(F32)
        x = jnp.concatenate([rms_norm(e, _w(mp["embed_norm"]), eps),
                             rms_norm(h, _w(mp["hidden_norm"]), eps)], -1)
        x = expert_layer(mp["layer"], _a(x, round_to)
                         @ _w(mp["proj"], round_to), pos, m, round_to)
        nll2 = _head_nll(x[:-1], mp["final_norm"], params["lm_head"],
                         labs[1:], eps, round_to).mean()
        return nll + weight * nll2

    with jax.default_matmul_precision("highest"):
        return jnp.stack([one((t, y)) for t, y in zip(tokens, labels)]).mean()


# ------------------------------------------------ operations and bytes ----

def attention_params(m: dict) -> int:
    """Matmul parameters of one layer's attention."""
    D, H = m["hidden_size"], m["num_attention_heads"]
    Rq, Rkv = m["q_lora_rank"], m["kv_lora_rank"]
    nope, rp, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return (D * Rq + Rq * H * (nope + rp) + D * (Rkv + rp)
            + Rkv * H * (nope + dv) + H * dv * D)


def train_flops_per_token(m: dict, seq_len: int,
                          held_pairs_per_token: float) -> float:
    """Model FLOP a token, forward + backward, NO recompute: ``6 x`` the
    matmul parameters a token meets outside the routed experts and over
    the vocabulary AS HELD (the embedding lookup is no matmul), causal
    attention at the PUBLISHED head sizes (QK^T over ``nope + rope``, PV
    over ``v``, half the square, x 3 for forward + backward), and the
    routed experts by the pairs that REACHED the experts held here:
    ``held_pairs_per_token x 3 matrices x 6 x D x Fm``."""
    D, V = m["hidden_size"], m["vocab_size"]
    F, Fm = m["intermediate_size"], m["moe_intermediate_size"]
    H = m["num_attention_heads"]
    nd, ne = _layers(m)
    dense = (nd + ne) * attention_params(m) + nd * 3 * D * F + ne * (
        D * routed_experts(m) + 3 * D * Fm * m["n_shared_experts"]) + D * V
    qk, dv = m["qk_nope_head_dim"] + m["qk_rope_head_dim"], m["v_head_dim"]
    attn = 3.0 * (nd + ne) * 2.0 * seq_len * H * (qk + dv) / 2.0
    return 6.0 * dense + attn + held_pairs_per_token * 18.0 * D * Fm


def splash_least_seconds(m: dict, batch: int, seq_len: int, peak: dict,
                         itemsize: int = 2) -> float:
    """The least time for ONE layer's causal attention, forward and
    backward, at the PUBLISHED head sizes (a padded head shows as a
    lower share): forward QK^T and PV, backward QK^T again, dP, dV, dQ,
    dK (flash recomputes the scores by design), over the causal half;
    bytes q, k, v, o once forward and q, k, v, o, dO, dq, dk, dv
    backward. The larger of FLOP / peak and bytes / bandwidth, each
    pass on its own."""
    H = m["num_attention_heads"]
    qk, dv = m["qk_nope_head_dim"] + m["qk_rope_head_dim"], m["v_head_dim"]
    sq = 2.0 * batch * H * seq_len * seq_len / 2.0          # a causal matmul a unit of depth
    fwd_flops = sq * (qk + dv)
    bwd_flops = sq * (2 * qk + dv + dv + qk)     # S again, dQ, dK | dP, dV
    rows = batch * seq_len * H * itemsize
    fwd_bytes = rows * (2 * qk + 2 * dv)
    bwd_bytes = rows * (4 * qk + 4 * dv)
    least = lambda f, b: max(f / peak["bf16_flops"],
                             b / peak["hbm_bytes_per_s"])
    return least(fwd_flops, fwd_bytes) + least(bwd_flops, bwd_bytes)


def gmm_least_seconds(m: dict, held_pairs: float, steps: int,
                      peak: dict, itemsize: int = 2) -> float:
    """The least time for the held experts' grouped matmuls of ``steps``
    steps that computed ``held_pairs`` (row, choice) pairs in all: the
    longer of the arithmetic (a pair: 3 matrices, forward + dX + dW = 18
    x D x Fm) and the traffic the weights alone force (each held
    expert's three matrices read forward and backward, their gradient
    written once: x 3)."""
    D, Fm = m["hidden_size"], m["moe_intermediate_size"]
    flops = held_pairs * 18.0 * D * Fm
    nbytes = (steps * _layers(m)[1] * held(m)[1] * 3 * D * Fm * itemsize
              * 3.0)
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
