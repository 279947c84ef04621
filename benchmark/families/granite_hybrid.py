"""Family ``granite_hybrid``: Granite-4.0-H-shaped dense hybrids
(``model_type`` ``granitemoehybrid`` with ``num_local_experts`` 0), served
through the program's ``models/granite_hybrid.py``. A stack that follows
the configuration's ``layer_types``: every layer is an operator
(``mamba``: the Mamba-2 mixer; ``attention``: GQA without rotary, scores
times ``attention_multiplier``) and a dense SwiGLU of width
``shared_intermediate_size``, each added to the residual times
``residual_multiplier``; the embedding enters times
``embedding_multiplier``.

THE PATTERN AND A CUT IN DEPTH: the kinds of an ``L``-layer model are
the FIRST ``L`` entries of ``layer_types`` (``families/lfm2_moe.py`` says
why); the tiny configuration begins ``mamba, attention``.

``make_params`` is the benchmark's own recipe, in the pytree ``models/
granite_hybrid.py`` documents (parameters stacked BY KIND: ``attn``,
``mamba``, ``mlp``): matrices normal(0, 1/sqrt(fan_in)) in the served
dtype; conv taps normal(0, 1/sqrt(K)), conv bias normal x 0.1; ``A_log =
log(uniform(1, 16))``, ``dt_bias`` the inverse softplus of
log-uniform(0.001, 0.1), ``D_skip`` ones, those three float32; ``W_in``
as its three column blocks (``in_z``, ``in_xbc``, ``in_dt``), the
``mamba_n_heads`` ``dt`` columns at a tenth of the other columns' scale, so that ``dt`` stays near its bias and a step's decay
``exp(dt A)`` runs from 0.2 (fast heads) to 0.999 (heads that remember
hundreds of tokens): a state that leaked between slots, was reset or was
rounded has to show in the logits. THE HEAD IS DRAWN ON ITS OWN, not
tied: with random weights a head equal to the embedding's transpose
makes every position predict ITS OWN INPUT TOKEN (``h`` keeps 12 x that
token's embedding, whose logit then stands 4-9 above every other), by a
margin no rounding reaches: on the chip the served-logit gap read 0.0 on
7 seeds for the program AND for the 3-bit control (PR 38), a comparison
that could fail nothing. The program holds embedding and head as two
arrays either way, so sizes and speed are the tied model's with its head
materialised.

THE REFERENCE is ``layer`` below: plain float32 ``jax.numpy`` from the
published equations, one sequence, no cache, nothing of ``paddle_tpu``,
the recurrence as a SEQUENTIAL ``lax.scan`` over the tokens (the program
computes a chunked form). ``reference_layers`` hands the harness the
layers in order as runs of equal kind, led by a one-layer group whose
function multiplies the embedding on entry (the harness's own embedding
has no multiplier). DEPARTURE: the harness's head has no divisor, so
the reference's logits are ``logits_scaling`` (8) times the published
scale; greedy tokens are the same, and the cell's limits are read in
that unit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench_family_dense_decoder import (CONTROL_ROUND_TO,  # noqa: F401
                                        _make, dtype_of, seed_key)
from harness.manifest import load_family
from harness.reference import F32, _a, _w, rms_norm, swiglu

# a view of rows lo:hi of a stacked array (not a copy of gigabytes)
_Rows = load_family("lfm2_moe")._Rows

# the scopes models/granite_hybrid.py enters beyond the harness's own
SCOPES = ("ssm.in", "ssm.conv", "ssm.scan", "ssm.out")
KERNELS = {"ssm.scan.kernel": r"^ssd_update"}
ATTN, MAMBA = "attention", "mamba"
OP_KEY = {ATTN: "attn", MAMBA: "mamba"}
DT_COLUMN_SCALE = 0.1


def layer_types(m: dict) -> list:
    L = m["num_hidden_layers"]
    types = list(m["layer_types"])[:L]
    if len(types) < L:
        raise SystemExit(f"layer_types names {len(types)} layers, "
                         f"num_hidden_layers is {L}")
    return types


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def dims(m: dict) -> dict:
    Hm, P, N = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
    Di = Hm * P
    if Di != m["mamba_expand"] * m["hidden_size"]:
        raise SystemExit("mamba_n_heads x mamba_d_head is not mamba_expand "
                         "x hidden_size")
    return dict(Hm=Hm, P=P, N=N, Di=Di, K=m["mamba_d_conv"],
                Dc=Di + 2 * m["mamba_n_groups"] * N)


def param_shapes(m: dict) -> dict:
    """``{leaf path: (shape, fan)}`` of every leaf drawn normal(0,
    1/sqrt(fan)) in the served dtype."""
    D, V, F = m["hidden_size"], m["vocab_size"], m["shared_intermediate_size"]
    H, Hkv, Dh = m["num_attention_heads"], m["num_key_value_heads"], head_dim(m)
    d = dims(m)
    types = layer_types(m)
    L, La = len(types), types.count(ATTN)
    Lm = L - La
    return {
        "embed": ((V, D), D), "lm_head": ((D, V), D),
        "attn.wq": ((La, D, H * Dh), D), "attn.wk": ((La, D, Hkv * Dh), D),
        "attn.wv": ((La, D, Hkv * Dh), D),
        "attn.wo": ((La, H * Dh, D), H * Dh),
        # W_in's three column blocks [z | xBC | dt], each a matrix of its
        # own (8512 columns are not a multiple of the chip's 128 lanes),
        # the dt columns at a tenth of the others' scale
        "mamba.in_z": ((Lm, D, d["Di"]), D),
        "mamba.in_xbc": ((Lm, D, d["Dc"]), D),
        "mamba.in_dt": ((Lm, D, d["Hm"]), D / DT_COLUMN_SCALE ** 2),
        "mamba.conv_w": ((Lm, d["K"], d["Dc"]), d["K"]),    # taps-major
        "mamba.conv_b": ((Lm, d["Dc"]), 100.0),             # std 0.1
        "mamba.out_proj": ((Lm, d["Di"], D), d["Di"]),
        "mlp.w_in": ((L, D, 2 * F), D), "mlp.w_out": ((L, F, D), F),
    }


def norm_shapes(m: dict) -> dict:
    types = layer_types(m)
    L, La = len(types), types.count(ATTN)
    D = m["hidden_size"]
    return {"final_norm": (D,), "attn.norm": (La, D),
            "mamba.norm": (L - La, D), "mamba.gate_norm": (L - La, dims(m)["Di"]),
            "mlp.norm": (L, D)}


def param_count(m: dict, tied: bool = True) -> int:
    """Parameters of the published (tied) model; ``tied=False`` counts
    the head as the array of its own that the program holds."""
    Lm, Hm = norm_shapes(m)["mamba.norm"][0], dims(m)["Hm"]
    n = int(sum(np.prod(s) for s, _ in param_shapes(m).values())
            + sum(np.prod(s) for s in norm_shapes(m).values())
            + 3 * Lm * Hm)
    return n - m["vocab_size"] * m["hidden_size"] if tied else n


@jax.jit
def _ssm_scalars(key, like):
    """``(dt_bias, A_log)`` float32, shaped like ``like``."""
    k1, k2 = jax.random.split(key)
    step = jnp.exp(np.log(1e-3) + jax.random.uniform(k1, like.shape, F32)
                   * (np.log(1e-1) - np.log(1e-3)))
    a = 1.0 + 15.0 * jax.random.uniform(k2, like.shape, F32)
    # softplus(dt_bias) = step
    return step + jnp.log(-jnp.expm1(-step)), jnp.log(a)


def make_params(model: dict, seed: int) -> dict:
    sh, dt = param_shapes(model), dtype_of(model)
    made = _make(seed_key(seed), shapes=tuple(sh.items()), dtype=dt)
    made.update({k: jnp.ones(s, dt) for k, s in norm_shapes(model).items()})
    like = jnp.zeros((made["mamba.norm"].shape[0], dims(model)["Hm"]), F32)
    made["mamba.dt_bias"], made["mamba.A_log"] = _ssm_scalars(
        jax.random.fold_in(seed_key(seed), 1), like)
    made["mamba.D_skip"] = jnp.ones_like(like)
    out: dict = {}
    for name, arr in made.items():
        node = out
        *path, leaf = name.split(".")
        for k in path:
            node = node.setdefault(k, {})
        node[leaf] = arr
    return out


def program_config(model: dict, **kw):
    from paddle_tpu.models import granite_hybrid as M
    L = model["num_hidden_layers"]
    if head_dim(model) * model["num_attention_heads"] != model["hidden_size"]:
        raise SystemExit("models/granite_hybrid.py derives head_dim as "
                         "hidden_size / num_attention_heads")
    refused = {
        "num_local_experts": model.get("num_local_experts", 0) != 0,
        "position_embedding_type": model.get(
            "position_embedding_type", "nope") != "nope",
        "attention_bias": bool(model.get("attention_bias")),
        "mamba_proj_bias": bool(model.get("mamba_proj_bias")),
        "mamba_conv_bias": not model.get("mamba_conv_bias", True),
        "intermediate_size": model["shared_intermediate_size"] != model.get(
            "intermediate_size", model["shared_intermediate_size"]),
    }
    if any(refused.values()):
        raise SystemExit(f"models/granite_hybrid.py does not serve "
                         f"{sorted(k for k, v in refused.items() if v)} as "
                         f"this configuration sets them")
    dims(model)
    cfg = M.GraniteHybridConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["shared_intermediate_size"],
        num_hidden_layers=L,
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        layer_types=tuple(layer_types(model)),
        mamba_n_heads=model["mamba_n_heads"],
        mamba_d_head=model["mamba_d_head"],
        mamba_d_state=model["mamba_d_state"],
        mamba_d_conv=model["mamba_d_conv"],
        mamba_n_groups=model["mamba_n_groups"],
        mamba_chunk_size=model["mamba_chunk_size"],
        embedding_multiplier=float(model["embedding_multiplier"]),
        attention_multiplier=float(model["attention_multiplier"]),
        residual_multiplier=float(model["residual_multiplier"]),
        logits_scaling=float(model["logits_scaling"]),
        rms_norm_eps=model["rms_norm_eps"],
        max_position_embeddings=model["max_position_embeddings"],
        dtype=dtype_of(model),
        ssm_state_dtype=dtype_of({"torch_dtype": model.get(
            "ssm_state_dtype", "float32")}), **kw)
    return cfg, M


# ------------------------------------------------------- the reference ----

def attention_operator(lp, h, m, round_to):
    """``a = n(h)``; q as H heads, k and v as Hkv heads of Dh, no bias,
    NO rotary, no norm on q or k; causal softmax attention with the
    scores times ``attention_multiplier``, a KV head serving H/Hkv query
    heads; ``Wo``."""
    T = h.shape[0]
    H, Hkv, Dh = m["num_attention_heads"], m["num_key_value_heads"], head_dim(m)
    a = _a(rms_norm(h, _w(lp["norm"]), m["rms_norm_eps"]), round_to)
    q = (a @ _w(lp["wq"], round_to)).reshape(T, Hkv, H // Hkv, Dh)
    k = (a @ _w(lp["wk"], round_to)).reshape(T, Hkv, Dh)
    v = (a @ _w(lp["wv"], round_to)).reshape(T, Hkv, Dh)
    mask = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def one(args):                  # one KV head at a time
        qh, kh, vh = args           # [T, g, Dh], [T, Dh], [T, Dh]
        s = jnp.einsum("tgd,sd->gts", qh, kh) * m["attention_multiplier"]
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sd->tgd", p, vh)

    o = jax.lax.map(one, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
                          v.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2, 3).reshape(T, H * Dh)
    return _a(o, round_to) @ _w(lp["wo"], round_to)


def mamba_operator(lp, h, m, round_to):
    """The Mamba-2 mixer, token by token. ``[z, xBC, dt] = a [W_z | W_xBC
    | W_dt]``; ``xBC_t <- silu(sum_k w[:, k] xBC_{t-K+1+k} + b)`` (zero
    before position 0); ``[x, B, C] = split(xBC)``; ``dt = softplus(dt +
    dt_bias)``, ``A = -exp(A_log)``; ``S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h]
    + dt_t[h] x_t[h] (x) B_t`` from ``S = 0``; ``y_t[h] = S_t[h] C_t +
    D_skip[h] x_t[h]``; ``y <- RMSNorm(y * silu(z)) * w``; ``y W_out``."""
    T = h.shape[0]
    Hm, P, N, Di, K = (m["mamba_n_heads"], m["mamba_d_head"],
                       m["mamba_d_state"],
                       m["mamba_n_heads"] * m["mamba_d_head"],
                       m["mamba_d_conv"])
    a = _a(rms_norm(h, _w(lp["norm"]), m["rms_norm_eps"]), round_to)
    z, xbc, dt = (a @ _w(lp[k], round_to)
                  for k in ("in_z", "in_xbc", "in_dt"))
    pad = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[-1]), F32), xbc], 0)
    w = lp["conv_w"].astype(F32)                                # [K, Dc]
    xbc = jax.nn.silu(sum(w[k] * pad[k:k + T] for k in range(K))
                      + lp["conv_b"].astype(F32))
    x, bm, cm = jnp.split(xbc, [Di, Di + N], axis=-1)
    x = x.reshape(T, Hm, P)
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(F32))        # [T, Hm]
    a_neg = -jnp.exp(lp["A_log"].astype(F32))                   # [Hm]

    def step(s, xs):                # s [Hm, P, N]
        x_t, b_t, c_t, dt_t = xs
        s = (jnp.exp(dt_t * a_neg)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        s = _a(s, round_to)         # the control also STORES it narrower
        return s, jnp.einsum("hpn,n->hp", s, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((Hm, P, N), F32), (x, bm, cm, dt))
    y = y + lp["D_skip"].astype(F32)[None, :, None] * x
    y = y.reshape(T, Di) * jax.nn.silu(z)
    y = rms_norm(y, _w(lp["gate_norm"]), m["rms_norm_eps"])
    return _a(y, round_to) @ _w(lp["out_proj"], round_to)


def mlp(lp, h, m, round_to):
    f = rms_norm(h, _w(lp["norm"]), m["rms_norm_eps"])
    gate, up = jnp.split(_w(lp["w_in"], round_to), 2, axis=-1)
    return swiglu(f, gate, up, _w(lp["w_out"], round_to), round_to)


def layer(lp, h, positions, m, round_to=None):
    """One layer of whatever kind its parameters are: ``h + r
    operator(n(h))`` then ``h + r mlp(n(h))``, ``r`` the
    ``residual_multiplier``. No layer reads ``positions``: the model has
    no positional embedding."""
    r = m["residual_multiplier"]
    if "attn" in lp:
        h = h + r * attention_operator(lp["attn"], h, m, round_to)
    else:
        h = h + r * mamba_operator(lp["mamba"], h, m, round_to)
    return h + r * mlp(lp["mlp"], h, m, round_to)


def first_layer(lp, h, positions, m, round_to=None):
    """The model's first layer: the harness's embedding lookup enters
    times ``embedding_multiplier``."""
    return layer(lp, h * m["embedding_multiplier"], positions, m, round_to)


def reference_layers(params, model):
    """The layers IN ORDER as runs of equal kind, the first layer a
    group of its own (``first_layer``): at the published depth mamba;
    mamba x 4; attention; mamba x 9; attention; ..."""
    types = layer_types(model)
    groups, at = [], {}
    i = 0
    while i < len(types):
        j = i + 1
        while i and j < len(types) and types[j] == types[i]:
            j += 1
        stack = {}
        for key in (OP_KEY[types[i]], "mlp"):
            lo = at.get(key, 0)
            stack[key] = jax.tree_util.tree_map(
                lambda a, lo=lo: _Rows(a, lo, lo + j - i), params[key])
            at[key] = lo + j - i
        groups.append((layer if i else first_layer, stack))
        i = j
    return groups
