"""Family ``longcat_flash``: LongCat-Flash-shaped decoders (Meituan
LongCat-Flash-Chat), served through the program's ``models/
longcat_flash.py`` as ONE CHIP'S SHARE of an expert-parallel deployment.

THE EQUATIONS (D = ``hidden_size``, eps ``rms_norm_eps``). Layer ``l``,
hidden ``h [T, D]``::

    for i in (0, 1):
        a = rms_norm(h, input_norm[2l+i]);    h = h + MLA[2l+i](a, positions)
        m = rms_norm(h, post_norm[2l+i])
        if i == 0: s = routed(m)              # the shortcut: taken here ...
        h = h + swiglu(m, dense[2l+i])
    h = h + s                                 # ... added at the end

``MLA(a)``, ``H`` heads, ``q_lora_rank`` Rq, ``kv_lora_rank`` Rkv, head
parts nope / rope / v::

    c_q  = rms_norm(a @ wq_a, q_a_norm)
    q    = (c_q @ wq_b).reshape(T, H, nope + rope) * sqrt(D / Rq)   # a head: [wq_nope | wq_rope]
    c_kv = rms_norm(a @ wkv_a, kv_a_norm) * sqrt(D / Rkv)
    k_r  = rope(a @ wk_rope);  q_r = rope(q[..., nope:])   # interleaved pairs, ONE k_r for all heads
    k_n[h] = c_kv @ w_uk[h].T;  v[h] = c_kv @ w_uv[h]       # kv_b_proj a head
    p    = causal_softmax_f32((q_n . k_n + q_r . k_r) / sqrt(nope + rope))
    out  = concat_heads(p @ v) @ wo

``routed(m)``: ``R`` routed experts + ``Z`` identity experts, ``k`` a
token, ``routed_scaling_factor`` c::

    p = softmax_{R+Z}(f32(m) @ f32(router));   S = top_k(p + router_bias)
    s = sum_{e in S, e held} c p_e swiglu(m, expert_e) + (sum_{e in S, e >= R} c p_e) m

(the bias enters the choice only; the weights are not renormalised). THE
CUT: this chip holds ``n_routed_experts`` (as the cell runs it: 16) of
the ``R = router_experts`` routed experts the
router scores (512); a choice of a routed expert another chip holds adds
NOTHING here, in the program and in this reference alike, and that
partial sum goes on to the next layer.

``make_params`` is the benchmark's own recipe in the pytree ``models/
longcat_flash.py`` documents (stacked BY KIND: ``mla`` and ``dense``
``[2L, ...]``, ``moe`` ``[L, ...]``): matrices normal(0, 1/sqrt(fan_in))
in the served dtype, BUT the two up-projections behind a scaled latent
(``q_b_proj``, ``kv_b_proj``) normal(0, 1/sqrt(hidden_size)): the
published scales ``sqrt(hidden_size / rank)`` (x 2 on q, x 3.46 on the KV
latent) exist to make exactly that initialisation unit-variance. Drawn at
1/sqrt(rank) instead, q and k came out at 2 and 3.46, the scores at a
deviation of 5.7 over thousands of keys, every softmax all but an argmax
and every bfloat16 rounding amplified into another key: the served-logit
gap read 0.4-1.0 in the MEAN on prompts of 600 to 9000 tokens with both
kernels equal to their references to a bfloat16 ulp (my chip runs, PR
40). With unit q and k the scores have deviation 1.0. The router float32 normal(0, 1/sqrt(D)) (logits of
unit deviation over 768 outputs: the chosen twelve carry p of 0.005-0.03
each, times 6 about 1 in all), ``router_bias`` float32 normal x 0.002
(the gap between the 12th and 13th p is about 0.0005, so the bias
changes the choice for a good share of tokens), norms ones.

THE REFERENCE is ``layer`` below: plain float32 ``jax.numpy`` from the
equations, one sequence, no cache, nothing of ``paddle_tpu``, attention
in the EXPANDED form (``kv_b`` applied to every token), eight heads and
512 query rows at a time and the dense SwiGLU 2048 of its columns at a
time so
that a 17 408-token sequence fits beside the weights, a python loop over the ``k`` choices, the held experts one at a
time over all rows, masked. ``reference_layers`` hands the harness ONE
group: a layer is rows ``2l, 2l + 1`` of ``mla`` and ``dense`` and row
``l`` of ``moe`` (``_Pairs`` / the arrays themselves: views, nothing is
copied).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench_family_dense_decoder import (CONTROL_ROUND_TO,  # noqa: F401
                                        _make, dtype_of, seed_key)
from harness.reference import F32, _a, _w, rms_norm, swiglu

# the scopes models/longcat_flash.py enters beyond the harness's own
SCOPES = ("attn.mla.q", "attn.mla.kv", "attn.mla.core", "moe.zero")
KERNELS = {"attn.mla.core.kernel": r"^mla_paged_attention"}
ROUTER_BIAS_STD = 0.002
HEAD_GROUP, ROW_BLOCK, QUERY_BLOCK, FFN_BLOCK = 8, 1024, 256, 2048


def routed_experts(m: dict) -> int:
    """Routed experts the ROUTER scores (``router_experts``, a key of
    its own beside the published ``n_routed_experts``: a cell overrides
    that one to what THIS CHIP holds, and the router keeps its width).
    Top-level numbers: the harness hands a layer function those only."""
    return int(m.get("router_experts", m["n_routed_experts"]))


def deployment(m: dict) -> tuple:
    """``(chips, this chip)`` of the expert-parallel deployment whose
    share this configuration is: the chips that share a layer's experts
    (the router's experts over those held here) and this chip's place."""
    chips, rest = divmod(routed_experts(m), m["n_routed_experts"])
    if rest or not 0 <= int(m.get("ep_this_chip", 0)) < chips:
        raise SystemExit("router_experts is not a whole number of shares "
                         "of n_routed_experts, or ep_this_chip is none")
    return chips, int(m.get("ep_this_chip", 0))


def held(m: dict) -> tuple:
    """``(first, count)`` of the routed experts this chip holds."""
    n = m["n_routed_experts"]
    return deployment(m)[1] * n, n


def param_shapes(m: dict) -> dict:
    """``{leaf path: (shape, fan)}`` of every leaf drawn normal(0,
    1/sqrt(fan)) in the served dtype."""
    D, V, L = m["hidden_size"], m["vocab_size"], m["num_layers"]
    H, Rq, Rkv = m["num_attention_heads"], m["q_lora_rank"], m["kv_lora_rank"]
    nope, rp, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    F, Fm, n = m["ffn_hidden_size"], m["expert_ffn_hidden_size"], held(m)[1]
    return {
        "embed": ((V, D), D), "lm_head": ((D, V), D),
        "mla.wq_a": ((2 * L, D, Rq), D),
        # the published q_b_proj [Rq, H*(nope+rope)] as each head's nope
        # and rope column blocks, each output-major (q = c_q @ w.T)
        # (fan hidden_size, not the rank: see the module docstring)
        "mla.wq_nope": ((2 * L, H * nope, Rq), D),
        "mla.wq_rope": ((2 * L, H * rp, Rq), D),
        # the published kv_a_proj_with_mqa [D, Rkv + rope] as its two
        # column blocks (576 columns are no multiple of 128 lanes)
        "mla.wkv_a": ((2 * L, D, Rkv), D),
        "mla.wk_rope": ((2 * L, D, rp), D),
        # the published kv_b_proj [Rkv, H*(nope+v)] as its K and V
        # blocks a head, head-major
        "mla.w_uk": ((2 * L, H, nope, Rkv), D),
        "mla.w_uv": ((2 * L, H, Rkv, dv), D),
        "mla.wo": ((2 * L, H * dv, D), H * dv),
        "dense.w_gate": ((2 * L, D, F), D), "dense.w_up": ((2 * L, D, F), D),
        "dense.w_down": ((2 * L, F, D), F),
        "moe.experts.w_gate": ((L, n, D, Fm), D),
        "moe.experts.w_up": ((L, n, D, Fm), D),
        "moe.experts.w_down": ((L, n, Fm, D), Fm),
    }


def f32_shapes(m: dict) -> dict:
    D, L = m["hidden_size"], m["num_layers"]
    E = routed_experts(m) + m["zero_expert_num"]
    return {"moe.router": ((L, D, E), D),
            "moe.router_bias": ((L, E), 1.0 / ROUTER_BIAS_STD ** 2)}


def norm_shapes(m: dict) -> dict:
    D, L = m["hidden_size"], m["num_layers"]
    return {"final_norm": (D,), "mla.input_norm": (2 * L, D),
            "mla.q_a_norm": (2 * L, m["q_lora_rank"]),
            "mla.kv_a_norm": (2 * L, m["kv_lora_rank"]),
            "dense.post_norm": (2 * L, D)}


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
    from paddle_tpu.models import longcat_flash as M
    refused = {
        "attention_bias": bool(model.get("attention_bias")),
        "attention_method": model.get("attention_method", "MLA") != "MLA",
        "zero_expert_type": model.get("zero_expert_type",
                                      "identity") != "identity",
    }
    if any(refused.values()):
        raise SystemExit(f"models/longcat_flash.py does not serve "
                         f"{sorted(k for k, v in refused.items() if v)} as "
                         f"this configuration sets them")
    cfg = M.LongcatFlashConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        ffn_hidden_size=model["ffn_hidden_size"],
        expert_ffn_hidden_size=model["expert_ffn_hidden_size"],
        num_layers=model["num_layers"],
        num_attention_heads=model["num_attention_heads"],
        q_lora_rank=model["q_lora_rank"], kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        n_routed_experts=routed_experts(model),
        zero_expert_num=model["zero_expert_num"],
        moe_topk=model["moe_topk"],
        routed_scaling_factor=float(model["routed_scaling_factor"]),
        mla_scale_q_lora=bool(model["mla_scale_q_lora"]),
        mla_scale_kv_lora=bool(model["mla_scale_kv_lora"]),
        rms_norm_eps=model["rms_norm_eps"],
        rope_theta=float(model["rope_theta"]),
        max_position_embeddings=model["max_position_embeddings"],
        experts_held=held(model), dtype=dtype_of(model), **kw)
    return cfg, M


# ------------------------------------------------------- the reference ----

def rope_pairs(x, positions, theta):
    """Rotary embedding on INTERLEAVED pairs ``(x[2i], x[2i+1])`` of the
    last axis of ``x [T, (H,) R]``."""
    R = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, R, 2, dtype=F32) / R))
    ang = positions.astype(F32)[:, None] * inv[None]             # [T, R/2]
    if x.ndim == 3:
        ang = ang[:, None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.reshape(*x.shape[:-1], R // 2, 2)
    x0, x1 = x[..., 0], x[..., 1]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     -1).reshape(*x.shape[:-2], R)


def latents(lp, h, positions, m, round_to):
    """What every token leaves for the others: ``(c_kv [T, Rkv], k_r [T,
    rope])``, the normed and scaled KV latent (as the operand of
    ``kv_b``) and the rotary key all heads share; ``ROW_BLOCK`` rows at
    a time."""
    eps = m["rms_norm_eps"]
    scale = (np.sqrt(m["hidden_size"] / m["kv_lora_rank"])
             if m["mla_scale_kv_lora"] else 1.0)

    def rows(args):
        hb, pos = args
        a = _a(rms_norm(hb, _w(lp["input_norm"]), eps), round_to)
        c_kv = rms_norm(a @ _w(lp["wkv_a"], round_to), _w(lp["kv_a_norm"]),
                        eps) * scale
        return _a(c_kv, round_to), rope_pairs(
            a @ _w(lp["wk_rope"], round_to), pos, m["rope_theta"])

    return jax.lax.map(rows, (h, positions))


def mla(lp, hb, pos, c_kv, k_r, m, round_to):
    """The sublayer's attention for the query rows ``hb [R, D]`` at
    positions ``pos`` over every token's latents, EXPANDED: ``kv_b``
    applied to the context, ``HEAD_GROUP`` heads at a time (a group's
    queries, keys and values are made, attended and projected by its
    rows of ``wo`` before the next group's exist); scores and softmax in
    float32."""
    R, D = hb.shape
    T = c_kv.shape[0]
    H, Rq, Rkv = m["num_attention_heads"], m["q_lora_rank"], m["kv_lora_rank"]
    nope, rp, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    a = _a(rms_norm(hb, _w(lp["input_norm"]), eps), round_to)
    c_q = _a(rms_norm(a @ _w(lp["wq_a"], round_to), _w(lp["q_a_norm"]), eps),
             round_to)
    q_scale = np.sqrt(D / Rq) if m["mla_scale_q_lora"] else 1.0
    g = min(HEAD_GROUP, H)
    qb = min(QUERY_BLOCK, R)
    assert R % qb == 0, (R, qb)
    scale = 1.0 / np.sqrt(nope + rp)
    k_pos = jnp.arange(T)

    def group(out, ws):
        wqn, wqr, uk_g, uv_g, wo_g = ws
        # [g*nope, Rq], [g*rp, Rq], [g, nope, Rkv], [g, Rkv, dv], [g*dv, D]
        q_n = (c_q @ _w(wqn, round_to).T).reshape(R, g, nope) * q_scale
        q_r = rope_pairs((c_q @ _w(wqr, round_to).T).reshape(R, g, rp)
                         * q_scale, pos, theta)
        k_n = jnp.einsum("tc,gnc->tgn", c_kv, _w(uk_g, round_to))
        v = jnp.einsum("tc,gcv->tgv", c_kv, _w(uv_g, round_to))

        def block(args):                # QUERY_BLOCK rows' scores at a time
            qn_b, qr_b, pos_b = args
            sc = (jnp.einsum("tgn,sgn->gts", qn_b, k_n)
                  + jnp.einsum("tgr,sr->gts", qr_b, k_r)) * scale
            mask = k_pos[None, :] <= pos_b[:, None]
            p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("gts,sgv->tgv", p, v)

        o = jax.lax.map(block, tuple(
            x.reshape(R // qb, qb, *x.shape[1:]) for x in (q_n, q_r, pos)))
        o = o.reshape(R, g * dv)
        return out + _a(o, round_to) @ _w(wo_g, round_to), None

    n = H // g
    out, _ = jax.lax.scan(group, jnp.zeros((R, D), F32), (
        lp["wq_nope"].reshape(n, g * nope, Rq),
        lp["wq_rope"].reshape(n, g * rp, Rq),
        lp["w_uk"].reshape(n, g, nope, Rkv), lp["w_uv"].reshape(n, g, Rkv, dv),
        lp["wo"].reshape(n, g * dv, D)))
    return out


def dense_swiglu(pair, i: int, x, round_to):
    """Sublayer ``i``'s dense SwiGLU of the layer's ``pair [2, ...]``,
    ``FFN_BLOCK`` of its ``ffn_hidden_size`` columns at a time; a
    block's weights are sliced out of the pair and cast to float32
    INSIDE the loop (whole, a sublayer's are 0.45 GB in bfloat16 and 0.9
    in float32, and a slice in front of the loop is a copy)."""
    _, D, F = pair["w_gate"].shape
    fb = min(FFN_BLOCK, F)
    x = _a(x, round_to)

    def cut(w, at, size):
        return _w(jax.lax.dynamic_slice(w, (i,) + at, (1,) + size)[0],
                  round_to)

    def block(acc, j):
        g = cut(pair["w_gate"], (0, j * fb), (D, fb))
        u = cut(pair["w_up"], (0, j * fb), (D, fb))
        d = cut(pair["w_down"], (j * fb, 0), (fb, D))
        hid = jax.nn.silu(x @ g) * (x @ u)
        return acc + _a(hid, round_to) @ d, None

    y, _ = jax.lax.scan(block, jnp.zeros_like(x), jnp.arange(F // fb))
    return y


def routed(lp, x, m, round_to):
    """The routed block over ``x [T, D]``: a loop over the ``k``
    choices gives every token its weight on each held expert and on the
    identity experts; the held experts run one at a time over all rows."""
    R, k = routed_experts(m), m["moe_topk"]
    lo, n = held(m)
    p = jax.nn.softmax(x @ lp["router"].astype(F32), axis=-1)
    _, top = jax.lax.top_k(p + lp["router_bias"].astype(F32), k)
    on_held = jnp.zeros((x.shape[0], n), F32)
    on_zero = jnp.zeros((x.shape[0],), F32)
    rows = jnp.arange(x.shape[0])
    for j in range(k):
        e = top[:, j]
        w = m["routed_scaling_factor"] * p[rows, e]
        here = (e >= lo) & (e < lo + n)
        on_held = on_held.at[rows, jnp.clip(e - lo, 0, n - 1)].add(
            jnp.where(here, w, 0.0))
        on_zero = on_zero + jnp.where(e >= R, w, 0.0)
    ex = lp["experts"]

    def one(acc, xs):
        g, u, d, w_e = xs
        y = swiglu(x, _w(g, round_to), _w(u, round_to), _w(d, round_to),
                   round_to)
        return acc + y * w_e[:, None], None

    s, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (ex["w_gate"], ex["w_up"], ex["w_down"], on_held.T))
    return s + on_zero[:, None] * x


def layer(lp, h, positions, m, round_to=None):
    """One layer: two attention sublayers and two dense SwiGLUs around
    the shortcut-connected routed block. ``ROW_BLOCK`` rows at a time:
    a sublayer first takes every token's latents (``latents``), then
    each block of rows attends them and goes through the rest of the
    sublayer, which is row by row."""
    T, D = h.shape
    rb = min(ROW_BLOCK, -(-T // QUERY_BLOCK) * QUERY_BLOCK)
    pad = -T % rb
    # padding rows sit at later positions than every real one: causal,
    # so no real row sees them
    h = jnp.pad(h, ((0, pad), (0, 0))).reshape((T + pad) // rb, rb, D)
    pos = jnp.concatenate(
        [positions, positions[-1] + 1 + jnp.arange(pad, dtype=positions.dtype)]
    ).reshape(-1, rb)
    s = None
    for i in (0, 1):
        at = jax.tree_util.tree_map(lambda a, i=i: a[i], lp["mla"])
        post_norm = _w(lp["dense"]["post_norm"][i])
        c_kv, k_r = latents(at, h, pos, m, round_to)
        c_kv, k_r = (x.reshape(T + pad, -1) for x in (c_kv, k_r))

        def rows(args, at=at, i=i, post_norm=post_norm, c_kv=c_kv, k_r=k_r):
            hb, pb = args
            hb = hb + mla(at, hb, pb, c_kv, k_r, m, round_to)
            x = rms_norm(hb, post_norm, m["rms_norm_eps"])
            sb = routed(lp["moe"], x, m, round_to) if i == 0 else None
            return hb + dense_swiglu(lp["dense"], i, x, round_to), sb

        h, sb = jax.lax.map(rows, (h, pos))
        s = sb if i == 0 else s
    return (h + s).reshape(T + pad, D)[:T]


class _Pairs:
    """Rows ``2i, 2i + 1`` of a ``[2L, ...]`` stack as entry ``i`` of an
    ``[L, 2, ...]`` one, as the harness reads a group's stack
    (``.shape[0]``, ``[i]``): a view, so nothing is copied until one
    layer's pair is."""

    def __init__(self, base):
        self.base = base
        self.shape = (base.shape[0] // 2, 2) + tuple(base.shape[1:])

    def __getitem__(self, i: int):
        return self.base[2 * i:2 * i + 2]


def reference_layers(params, model):
    """ONE group: every layer is the same pattern."""
    pairs = {k: jax.tree_util.tree_map(_Pairs, params[k])
             for k in ("mla", "dense")}
    return [(layer, {**pairs, "moe": params["moe"]})]
