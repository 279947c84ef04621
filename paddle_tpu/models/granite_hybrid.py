"""Granite-4.0-H-family decoder (``model_type`` ``granitemoehybrid``,
dense: no routed experts): a stack whose layers follow a published
pattern of TWO operator kinds, every one followed by a dense SwiGLU,
with four scalar multipliers and no positional embedding at all.

Every layer: ``h = h + residual_multiplier * Op(RMSNorm(h))``, then ``h =
h + residual_multiplier * MLP(RMSNorm(h))``.

* operator ``attention``: GQA, q / k / v / o without bias, causal, the
  scores times ``attention_multiplier`` (not ``1/sqrt(head size)``), NO
  rotary embedding and no norm on q or k; K and V live in the paged
  pool;
* operator ``mamba``: the Mamba-2 mixer. ``[z, xBC, dt] = split(a
  W_in)``; ``xBC`` through a causal depthwise convolution of
  ``mamba_d_conv`` taps with a bias, then SiLU; ``[x, B, C] =
  split(xBC)``, ``x`` as ``H`` heads of ``P``, ``B`` and ``C`` (``N``
  each) shared by all heads; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; the recurrence ``S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] +
  dt_t[h] x_t[h] (x) B_t``, ``y_t[h] = S_t[h] C_t + D_skip[h] x_t[h]``; ``y
  <- RMSNorm(y * silu(z)) * w`` (gate first); ``o = y W_out``. Its state
  a slot: ``S`` (``H x P x N`` float32: 2 MiB a layer at the published
  widths) and the last ``K - 1`` rows of ``xBC`` before the
  convolution;
* feed-forward: ``[g, u] = split2(a W_in)``, ``(silu(g) * u) W_out``;
* model: ``h_0 = embedding_multiplier * E[token]``; final RMSNorm;
  ``logits = (h W_head) / logits_scaling`` (both applied by
  ``models/serving_tick.py`` from this config's fields).

Parameters are stacked BY KIND, each kind's layers in model order::

    embed [V, D]   final_norm [D]   lm_head [D, V]
    attn   norm [La, D]  wq [La, D, H*Dh]  wk, wv [La, D, Hkv*Dh]
           wo [La, H*Dh, D]
    mamba  norm [Lm, D]  in_z [Lm, D, Di]  in_xbc [Lm, D, Di + 2*N]
           in_dt [Lm, D, Hm]  conv_w [Lm, K, Di + 2*N]  conv_b [Lm, Di + 2*N]
           dt_bias, A_log, D_skip [Lm, Hm] f32
           gate_norm [Lm, Di]  out_proj [Lm, Di, D]
           (Di = Hm * P; ``W_in``'s three column blocks are three
           matrices: a stack 2*Di + 2*N + Hm = 8512 columns wide is not a
           multiple of the chip's 128 lanes, and the chip's compiler then
           re-lays the WHOLE stack out, 1.2 GiB, in every tick; the taps
           are stored taps-major for the same reason, conv_w[K-1] meets
           the current token)
    mlp    norm [L, D]  w_in [L, D, 2*F]  w_out [L, F, D]

THE CACHE (``init_serving_pages``; kinds in ``serving_cache_kinds``:
``attention`` keeps ``pages``, ``mamba`` ``slot_rows``): ``k_pages`` /
``v_pages`` over the attention layers (lane-packed where the head size
is under 128), ``conv_state [Lm, S + 1, K - 1, Di + 2*N]`` in the
model's dtype and ``ssm_state [Lm, S + 1, N, Hm * P]`` in
``cfg.ssm_state_dtype`` (float32), row ``S`` the trash row. The state is
held STATE-MAJOR (``[N, Hm * P]``, the transpose of the equations' ``[Hm,
P, N]``): see ``ops/pallas/ssd_update.py``. At the published widths and
64 slots it is 4.57 GiB: it is the layer loops' carry and the tick's
donated argument, updated in place by the kernel; nothing may copy it.

ONE RAGGED FORMULATION (``ssd_rows``): a tick's packed rows — decode
rows of one token, prompt spans — are ONE chunk of the chunked (SSD)
form whose segments are the slots. With ``a_t`` the running sum of ``dt
A`` over the row's own span::

    y_t = exp(a_t) (C_t . S_prev[slot_t])
          + sum_{r <= t, same slot} exp(a_t - a_r) dt_r (C_t . B_r) x_r
          + D_skip x_t
    S_new[s] = exp(a_last) S_prev[s]
               + sum_r exp(a_last - a_r) dt_r x_r (x) B_r

``S_prev`` counts as ZERO where the span starts at position 0 (by
``tok_pos``, as the conv state does): a slot needs no reset when it
changes hands, and a preempted request that prefills again rebuilds its
state. Slots without a row (idle, mid-prefill in a fused tail step,
padding) keep their state bitwise. The sums over the tick's rows are
small dense algebra (``[T, T]`` masks); whatever touches ``S_prev`` is
the kernel's. More than ``mamba_chunk_size`` packed rows are taken as
several chunks in turn. The whole-sequence paths (``forward``,
``forward_with_cache``, ``generate``) pack ``[B, T]`` the same way and
run the same function: there is no second formulation to drift.

The walk pieces (``LayerKind``, ``Group``, ``layer_groups``,
``_layer_params``, ``walk_groups``, the convolution's window a slot)
are ``models/layer_walk.py``'s, shared with ``models/lfm2_moe.py``. The
period here is 10 layers (5 mamba, attention, 4 mamba): its body is
walked as LOOPS over the runs of equal kind, so a tick program holds
two Mamba bodies and one attention body whatever the depth.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..ops.pallas.ragged_paged_attention import lane_pack_factor
from ..ops.pallas.ssd_update import Walk, live_walk, ssd_update
from . import layer_walk as _lw
from .layer_walk import LayerKind, _layer_params
from .llama import _mm, rms_norm

ATTN, MAMBA = "attention", "mamba"
KINDS = {ATTN: LayerKind(ATTN, "pages"), MAMBA: LayerKind(MAMBA, "slot_rows")}
_HI = lax.Precision.HIGHEST


def _published_layer_types(n: int) -> Tuple[str, ...]:
    """One attention layer in ten, the sixth of each period."""
    return tuple(ATTN if i % 10 == 5 else MAMBA for i in range(n))


@dataclasses.dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192           # shared_intermediate_size
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    layer_types: Optional[Tuple[str, ...]] = None   # None: the published
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    dtype: Any = jnp.bfloat16
    # what a slot's state S is STORED in between ticks (the update is
    # float32 whatever this says): a field of the model, not an engine
    # option, because a narrower one is a change of precision
    ssm_state_dtype: Any = jnp.float32
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
        if self.mamba_n_groups != 1:
            raise ValueError("one group of B and C only (mamba_n_groups 1)")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @staticmethod
    def tiny(**kw) -> "GraniteHybridConfig":
        kw.setdefault("num_hidden_layers", 6)
        kw.setdefault("layer_types", (MAMBA, ATTN, MAMBA, MAMBA, ATTN, MAMBA)[
            :kw["num_hidden_layers"]])
        return GraniteHybridConfig(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=4,
            mamba_d_head=32, mamba_d_state=16, mamba_chunk_size=16,
            max_position_embeddings=256), **kw})


# ------------------------------------------------------------ the stack ----

def layer_kinds(cfg: GraniteHybridConfig):
    """``[(operator, "mlp", operator's ordinal, layer index)]``."""
    return _lw.layer_kinds(cfg.layer_types, lambda i: "mlp")


def layer_groups(cfg: GraniteHybridConfig):
    """The whole periods of the pattern (one group, SCANNED), then what
    is left of one (walked once): ``layer_walk.layer_groups``."""
    return _lw.layer_groups(layer_kinds(cfg))


def init_params(cfg: GraniteHybridConfig, key: jax.Array) -> Dict[str, Any]:
    D, V, F = cfg.hidden_size, cfg.vocab_size, cfg.intermediate_size
    H, Hkv, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    Hm, Di, Dc, K = (cfg.mamba_n_heads, cfg.d_inner, cfg.conv_dim,
                     cfg.mamba_d_conv)
    L = cfg.num_hidden_layers
    La = sum(t == ATTN for t in cfg.layer_types)
    Lm = L - La
    ks = iter(jax.random.split(key, 20))

    def init(shape, fan_in, dtype=cfg.dtype):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * (1.0 / np.sqrt(fan_in))).astype(dtype)

    def uniform(shape, lo, hi, log=False):
        u = jax.random.uniform(next(ks), shape, jnp.float32)
        if log:
            return jnp.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))
        return lo + u * (hi - lo)

    # dt_bias: the inverse softplus of a step log-uniform in [1e-3, 1e-1]
    step = uniform((Lm, Hm), 1e-3, 1e-1, log=True)
    return {
        "embed": init((V, D), D), "lm_head": init((D, V), D),
        "final_norm": jnp.ones((D,), cfg.dtype),
        "attn": {
            "norm": jnp.ones((La, D), cfg.dtype),
            "wq": init((La, D, H * Dh), D), "wk": init((La, D, Hkv * Dh), D),
            "wv": init((La, D, Hkv * Dh), D),
            "wo": init((La, H * Dh, D), H * Dh)},
        "mamba": {
            "norm": jnp.ones((Lm, D), cfg.dtype),
            "in_z": init((Lm, D, Di), D), "in_xbc": init((Lm, D, Dc), D),
            "in_dt": init((Lm, D, Hm), 100.0 * D),  # dt stays near its bias
            "conv_w": init((Lm, K, Dc), K),
            "conv_b": init((Lm, Dc), 100.0),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(uniform((Lm, Hm), 1.0, 16.0)),
            "D_skip": jnp.ones((Lm, Hm), jnp.float32),
            "gate_norm": jnp.ones((Lm, Di), cfg.dtype),
            "out_proj": init((Lm, Di, D), Di)},
        "mlp": {
            "norm": jnp.ones((L, D), cfg.dtype),
            "w_in": init((L, D, 2 * F), D), "w_out": init((L, F, D), F)},
    }


def abstract_params(cfg: GraniteHybridConfig):
    """ShapeDtypeStruct pytree of ``init_params`` (tracing-only
    tooling; see models/llama.py abstract_params)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


# ------------------------------------------------------------ the layers ----

def _residual(h, out, cfg):
    return h + (out * jnp.asarray(cfg.residual_multiplier, out.dtype)
                ).astype(h.dtype)


def _attn_op(lp, h, cfg: GraniteHybridConfig, attn_fn):
    """``h [B, T, D]``; ``attn_fn(q, k, v) -> o`` owns the cache and the
    scale (``attention_multiplier``). No rotary, no norm on q or k."""
    B, T, _ = h.shape
    H, Hkv, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    with jax.named_scope("attn.qkv_rope"):
        x = rms_norm(h, lp["norm"], cfg.rms_norm_eps)
        q = _mm(x, lp["wq"]).reshape(B, T, H, Dh)
        k = _mm(x, lp["wk"]).reshape(B, T, Hkv, Dh)
        v = _mm(x, lp["wv"]).reshape(B, T, Hkv, Dh)
    o = attn_fn(q, k, v)
    with jax.named_scope("attn.out"):
        return _residual(h, _mm(o.reshape(B, T, H * Dh), lp["wo"]), cfg)


def _mlp(lp, h, cfg: GraniteHybridConfig):
    with jax.named_scope("mlp"):
        x = rms_norm(h, lp["norm"], cfg.rms_norm_eps)
        g, u = jnp.split(_mm(x, lp["w_in"]), 2, axis=-1)
        return _residual(h, _mm(jax.nn.silu(g) * u, lp["w_out"]), cfg)


class SsdPlan(NamedTuple):
    """What ``ssd_rows`` needs of a chunk's rows that depends on
    ``tok_slot`` / ``tok_pos`` alone, made ONCE a tick for all layers
    (``ssd_plan``): the rows of the chunk, the masks (``own [T, T]``:
    same slot; ``upto``: and not later; ``mine [S, T]``), which slots
    count their state as zero (``fresh [S]``: a row at position 0),
    which rows read it (``keep [T]``), and the kernel's walk."""
    rows: slice
    tok_slot: jax.Array
    own: jax.Array
    upto: jax.Array
    mine: jax.Array
    fresh: jax.Array
    keep: jax.Array
    walk: Walk


def ssd_plan(tok_slot, tok_pos, slots: int, chunk: int):
    """One ``SsdPlan`` a chunk of at most ``chunk`` packed rows, in
    order: a tick within ``mamba_chunk_size`` is one."""
    T = tok_slot.shape[0]
    plans = []
    for lo in range(0, T, chunk):
        rows = slice(lo, min(lo + chunk, T))
        ts, tp = tok_slot[rows], tok_pos[rows]
        real = ts < slots
        own = (ts[:, None] == ts[None, :]) & real[:, None]
        at = jnp.arange(ts.shape[0])
        mine = jnp.arange(slots, dtype=ts.dtype)[:, None] == ts[None, :]
        fresh = jnp.any(mine & (tp == 0)[None], axis=1)
        plans.append(SsdPlan(
            rows, ts, own, own & (at[None, :] <= at[:, None]), mine, fresh,
            real & ~jnp.append(fresh, True)[ts], live_walk(ts, slots)))
    return tuple(plans)


def ssd_rows(x, dt, a_neg, bm, cm, plans, state, layer, impl: str = "auto"):
    """The recurrence over a tick's packed rows in the chunked (SSD)
    form (module docstring), the slots as its segments. ``x [T, H, P]``,
    ``dt [T, H]`` f32 (after the softplus), ``a_neg [H]`` f32 (``-exp(
    A_log)``), ``bm`` / ``cm`` ``[T, N]``, ``plans``: ``ssd_plan`` of
    the rows' ``tok_slot`` / ``tok_pos`` (slot ``S``: a padding row),
    ``state [L, S + 1, N, H * P]`` with ``layer`` this layer's ordinal.
    Returns ``(y [T, H, P] f32 without the skip term, state')``. A
    slot's rows are contiguous and in position order; the chunks are
    taken in turn, the state threading through them."""
    f32 = jnp.float32
    if state.dtype != f32:
        # a state STORED narrower (``cfg.ssm_state_dtype``; the tests'
        # measurement of what that costs): widened whole, updated,
        # re-rounded whole. The float32 default never takes this path
        y, new = ssd_rows(x, dt, a_neg, bm, cm, plans, state.astype(f32),
                          layer, impl)
        return y, new.astype(state.dtype)
    if len(plans) > 1:
        ys = []
        for p in plans:
            y, state = ssd_rows(x[p.rows], dt[p.rows], a_neg, bm[p.rows],
                                cm[p.rows], (p,), state, layer, impl)
            ys.append(y)
        return jnp.concatenate(ys, axis=0), state
    (p,) = plans
    T, H, P = x.shape
    bm, cm = bm.astype(f32), cm.astype(f32)
    da = dt * a_neg                                           # [T, H] <= 0
    a = jnp.dot(p.upto.astype(f32), da, precision=_HI)        # running sum
    a_all = jnp.dot(p.own.astype(f32), da, precision=_HI)     # the span's
    # within the tick: every row against the earlier rows of its span
    g = jnp.dot(cm, bm.T, precision=_HI)                      # C_t . B_r
    # head-major ([H, T, T]): the batched matmul's own layout, so the
    # one large intermediate is never transposed
    decay = jnp.exp(jnp.where(p.upto[None],
                              a.T[:, :, None] - a.T[:, None, :], -jnp.inf))
    xdt = x.astype(f32) * dt[..., None]                       # [T, H, P]
    y = jnp.einsum("htr,rhp->thp", g[None] * decay, xdt, precision=_HI)
    # against the state the tick found: the state pass
    a_slot = jnp.dot(p.mine.astype(f32), da, precision=_HI)   # [S, H]
    dec = jnp.where(p.fresh[:, None], 0.0, jnp.exp(a_slot))
    w = jnp.exp(a_all - a)[..., None] * xdt
    ys, state = ssd_update(
        state, layer, cm, bm, w.reshape(T, H * P),
        jnp.repeat(dec, P, axis=1), p.tok_slot, walk=p.walk, impl=impl)
    y = y + jnp.where(p.keep[:, None, None],
                      jnp.exp(a)[..., None] * ys.reshape(T, H, P), 0.0)
    return y, state


def _mamba_op(lp, h, cfg: GraniteHybridConfig, earlier_fn, scan_fn):
    """``h [1, T, D]`` (the packed rows). ``earlier_fn(u) -> [u_{t-1},
    ..., u_{t-K+1}]`` owns the convolution's state (``models/
    layer_walk.py``); ``scan_fn(x [T, H, P], dt [T, H], a_neg [H], B, C
    [T, N]) -> y [T, H, P] f32`` owns the recurrence's. ``xBC`` is
    rounded to the model's dtype before the taps, so that a value read
    back from the state equals the value the stream held; taps and bias
    accumulate in float32."""
    K, Di, N = cfg.mamba_d_conv, cfg.d_inner, cfg.mamba_d_state
    Hm, P = cfg.mamba_n_heads, cfg.mamba_d_head
    T = h.shape[1]
    f32 = jnp.float32
    with jax.named_scope("ssm.in"):
        a = rms_norm(h, lp["norm"], cfg.rms_norm_eps)
        z, u, dt = (_mm(a, lp[k]) for k in ("in_z", "in_xbc", "in_dt"))
    with jax.named_scope("ssm.conv"):
        wc = lp["conv_w"].astype(f32)                           # [K, Dc]
        acc = lp["conv_b"].astype(f32) + wc[K - 1] * u.astype(f32)
        for d, prev in enumerate(earlier_fn(u), start=1):
            acc = acc + wc[K - 1 - d] * prev.astype(f32)
        xbc = jax.nn.silu(acc).astype(h.dtype)[0]               # [T, Dc]
    with jax.named_scope("ssm.scan"):
        x, bm, cm = jnp.split(xbc, [Di, Di + N], axis=-1)
        x = x.reshape(T, Hm, P)
        step = jax.nn.softplus(dt[0].astype(f32) + lp["dt_bias"])
        y = scan_fn(x, step, -jnp.exp(lp["A_log"]), bm, cm)
        y = y + lp["D_skip"][None, :, None] * x.astype(f32)
    with jax.named_scope("ssm.out"):
        y = y.reshape(1, T, Di) * jax.nn.silu(z.astype(f32))
        y = rms_norm(y, lp["gate_norm"], cfg.rms_norm_eps).astype(h.dtype)
        return _residual(h, _mm(y, lp["out_proj"]), cfg)


# ----------------------------------------------------- whole sequences ----

def init_kv_cache(cfg: GraniteHybridConfig, batch_size: int, max_len: int):
    """The dense cache: K and V ``[La, B, max_len, Hkv, Dh]``, and the
    Mamba layers' state a SEQUENCE as the serving cache keeps it a slot
    (row ``B`` the trash row)."""
    La = sum(t == ATTN for t in cfg.layer_types)
    Lm = cfg.num_hidden_layers - La
    shape = (La, batch_size, max_len, cfg.num_key_value_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype),
            "conv": jnp.zeros((Lm, batch_size + 1, cfg.mamba_d_conv - 1,
                               cfg.conv_dim), cfg.dtype),
            "ssm": jnp.zeros((Lm, batch_size + 1, cfg.mamba_d_state,
                              cfg.d_inner), cfg.ssm_state_dtype)}


def _cached_attention(q, ck, cv, pos0, scale):
    """``models/llama.py _cached_attention`` at this model's scale."""
    B, T, H, Dh = q.shape
    S, Hkv = ck.shape[1], ck.shape[2]
    qg = q.reshape(B, T, Hkv, H // Hkv, Dh)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, ck) * scale
    mask = jnp.arange(S)[None, :] <= pos0 + jnp.arange(T)[:, None]
    scores = jnp.where(mask, scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgts,bskd->btkgd", probs, cv).reshape(B, T, H, Dh)


def forward(params, tokens, cfg: GraniteHybridConfig):
    """tokens ``[B, T]`` -> logits ``[B, T, V]``: the whole sequence, no
    cache kept (the tests' and tools' path)."""
    logits, _ = forward_with_cache(params, tokens, None, 0, cfg,
                                   every_position=True)
    return logits


def forward_with_cache(params, tokens, cache, pos0, cfg: GraniteHybridConfig,
                       every_position: bool = False):
    """tokens ``[B, T]`` at positions ``pos0..`` -> (last-position
    logits ``[B, V]``, updated cache): the dense-cache counterpart of
    the serving tick (``models/llama.py forward_with_cache``'s
    contract). ``cache=None`` is a whole sequence from position 0. The
    ``B`` sequences are the SLOTS of one packed stream of ``B * T``
    rows, and the Mamba layers run the tick's own ``ssd_rows``; more
    than ``mamba_chunk_size`` tokens are taken as several passes in
    turn."""
    B, T = tokens.shape
    C = cfg.mamba_chunk_size
    if T > C:
        if cache is None:
            cache = init_kv_cache(cfg, B, T)
        outs = []
        for lo in range(0, T, C):
            out, cache = forward_with_cache(
                params, tokens[:, lo:lo + C], cache, pos0 + lo, cfg,
                every_position)
            outs.append(out)
        return (jnp.concatenate(outs, axis=1) if every_position
                else outs[-1]), cache
    from ..ops.pallas.flash_attention import flash_attention as _fa
    h = params["embed"].astype(cfg.dtype)[tokens]
    h = h * jnp.asarray(cfg.embedding_multiplier, h.dtype)
    fresh = cache is None or (isinstance(pos0, int) and pos0 == 0)
    keep = cache is not None
    new = dict(cache) if keep else init_kv_cache(cfg, B, 0)
    K = cfg.mamba_d_conv
    # the packed stream: sequence b's rows are b*T .. b*T + T - 1
    tok_slot = jnp.repeat(jnp.arange(B, dtype=jnp.int32), T)
    tok_qoff = jnp.tile(jnp.arange(T, dtype=jnp.int32), B)
    tok_pos = (pos0 + tok_qoff).astype(jnp.int32)
    q_len = jnp.full((B,), T, jnp.int32)
    last = jnp.arange(B, dtype=jnp.int32) * T + T - 1
    plans = ssd_plan(tok_slot, tok_pos, B, B * T)
    for op, _, i_op, i_mlp in layer_kinds(cfg):
        lp = _layer_params(params[{ATTN: "attn", MAMBA: "mamba"}[op]], i_op)
        if op == ATTN:
            def attn_fn(q, k, v, i=i_op):
                if keep:
                    new["k"] = new["k"].at[i].set(lax.dynamic_update_slice(
                        new["k"][i], k.astype(cfg.dtype), (0, pos0, 0, 0)))
                    new["v"] = new["v"].at[i].set(lax.dynamic_update_slice(
                        new["v"][i], v.astype(cfg.dtype), (0, pos0, 0, 0)))
                if fresh:
                    return _fa(q, k, v, causal=True,
                               sm_scale=cfg.attention_multiplier,
                               impl="auto" if cfg.use_flash_attention
                               else "dense")
                return _cached_attention(q, new["k"][i], new["v"][i], pos0,
                                         cfg.attention_multiplier)
            h = _attn_op(lp, h, cfg, attn_fn)
        else:
            cell = {}

            def earlier_fn(u, i=i_op):                  # [1, B*T, Dc]
                cell["u"] = u[0]
                return _lw.earlier_rows(u[0], new["conv"][i], tok_slot,
                                        tok_qoff, tok_pos, K)

            def scan_fn(x, dt, a_neg, bm, cm, i=i_op):
                y, new["ssm"] = ssd_rows(x, dt, a_neg, bm, cm, plans,
                                         new["ssm"], i, impl="dense")
                return y

            # the operator sees one packed stream
            h = _mamba_op(lp, h.reshape(1, B * T, -1), cfg, earlier_fn,
                          scan_fn).reshape(B, T, -1)
            rows = _lw.window_rows(cell["u"], new["conv"][i_op], q_len, last,
                                   K, cfg.dtype)
            new["conv"] = new["conv"].at[i_op, :B].set(rows)
        h = _mlp(_layer_params(params["mlp"], i_mlp), h, cfg)
    if not every_position:
        h = h[:, -1]
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    logits = _mm(h, params["lm_head"]).astype(jnp.float32)
    return logits / cfg.logits_scaling, (new if keep else None)


def generate(params, prompt, cfg: GraniteHybridConfig, max_new_tokens: int, *,
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

def serving_cache_kinds(cfg: GraniteHybridConfig):
    """Every layer's kind, in order: what the engine reads to know that
    this model's step functions take the whole cache pytree, and which
    of its layers keep state that pages cannot rebuild."""
    return tuple(KINDS[t] for t in cfg.layer_types)


def init_serving_pages(cfg: GraniteHybridConfig, total_pages: int,
                       page_size: int, max_batch: int, max_span: int = 1):
    """The model's cache, ONE pytree built from its kinds: ``k_pages`` /
    ``v_pages`` over the attention layers only (page 0 = trash; lane-
    packed where the head size is under the chip's 128 lanes), and the
    Mamba layers' two states a slot: ``conv_state [Lm, S + 1, K - 1, Di
    + 2 N]`` (the last rows of ``xBC``, oldest first) and ``ssm_state
    [Lm, S + 1, N, Hm * P]`` (row ``S`` = trash: padding tokens read it,
    nothing writes it)."""
    La = sum(t == ATTN for t in cfg.layer_types)
    Lm = cfg.num_hidden_layers - La
    Hkv, Dh = cfg.num_key_value_heads, cfg.head_dim
    f = lane_pack_factor(Dh, Hkv)
    shape = (La, Hkv // f, total_pages, page_size, f * Dh)
    return {"k_pages": jnp.zeros(shape, cfg.dtype),
            "v_pages": jnp.zeros(shape, cfg.dtype),
            "conv_state": jnp.zeros(
                (Lm, max_batch + 1, cfg.mamba_d_conv - 1, cfg.conv_dim),
                cfg.dtype),
            "ssm_state": jnp.zeros(
                (Lm, max_batch + 1, cfg.mamba_d_state, cfg.d_inner),
                cfg.ssm_state_dtype)}


def _walk(params, h, cache, meta, cfg: GraniteHybridConfig, tq, attn_impl):
    """The tick's layer walk (``models/llama.py _walk_one_kind``'s
    contract) over ``layer_groups``: one scan over the periods, inside
    it one loop a run of Mamba layers, the pools and both states in
    every loop's carry (the state is never copied: module docstring)."""
    K = cfg.mamba_d_conv
    tok_slot, tok_qoff, tok_pos = (meta["tok_slot"], meta["tok_qoff"],
                                   meta["tok_pos"])
    at = _lw.kv_rows(meta, cache["k_pages"])
    q_len, last = meta["q_len"], meta["last"]
    plan = _lw.tick_plan(meta, tq, cfg.num_attention_heads,
                         cache["k_pages"])
    ssd_impl = attn_impl if attn_impl in ("pallas", "dense") else "auto"
    # what the Mamba layers need of the packing, once for all of them
    plans = ssd_plan(tok_slot, tok_pos, q_len.shape[0], cfg.mamba_chunk_size)

    def attn_layer(lp, h, kp, vp, layer):
        cell = {}

        def attn_fn(q, k, v):
            o, cell["kp"], cell["vp"] = _lw.paged_kv_attend(
                q, k, v, kp, vp, layer, meta, at, plan, tq, attn_impl,
                sm_scale=cfg.attention_multiplier)
            return o

        h = _attn_op(lp, h, cfg, attn_fn)
        return h, cell["kp"], cell["vp"]

    def mamba_layer(lp, h, cs, ss, layer):
        rows = lax.dynamic_index_in_dim(cs, layer, 0, keepdims=False)
        cell = {}

        def earlier_fn(u):                                      # [1, T, Dc]
            u = cell["u"] = u[0]
            return _lw.earlier_rows(u, rows, tok_slot, tok_qoff, tok_pos, K)

        def scan_fn(x, dt, a_neg, bm, cm):
            y, cell["ss"] = ssd_rows(x, dt, a_neg, bm, cm, plans, ss, layer,
                                     impl=ssd_impl)
            return y

        h = _mamba_op(lp, h, cfg, earlier_fn, scan_fn)
        with jax.named_scope("ssm.conv"):
            new = _lw.window_rows(cell["u"], rows, q_len, last, K, cs.dtype)
            cs = lax.dynamic_update_slice(cs, new[None], (layer, 0, 0, 0))
        return h, cs, cell["ss"]

    def one(carry, op, i_op, i_mlp):
        h, kp, vp, cs, ss = carry
        i_op = jnp.asarray(i_op, jnp.int32)
        if op == ATTN:
            h, kp, vp = attn_layer(_layer_params(params["attn"], i_op), h,
                                   kp, vp, i_op)
        else:
            h, cs, ss = mamba_layer(_layer_params(params["mamba"], i_op), h,
                                    cs, ss, i_op)
        h = _mlp(_layer_params(params["mlp"], i_mlp), h, cfg)
        return h, kp, vp, cs, ss

    def run(group, carry, i):
        """The group's pattern once (its ``i``-th repeat): a loop a run
        of equal layers."""
        for (op, ffn, op_at, mlp_at), n in _lw.runs(group.layers):
            i_op = op_at + i * group.stride[op]
            i_mlp = mlp_at + i * group.stride[ffn]
            if n == 1:
                carry = one(carry, op, i_op, i_mlp)
            else:
                carry = lax.fori_loop(
                    0, n, lambda j, c, op=op, a=i_op, b=i_mlp: one(
                        c, op, a + j, b + j), carry)
        return carry

    carry = (h, cache["k_pages"], cache["v_pages"], cache["conv_state"],
             cache["ssm_state"])
    # an operation under bare ``layers`` is a loop's own: the slicing
    # of a layer's weights out of its kind's stack
    with jax.named_scope("layers"):
        carry = _lw.walk_groups(layer_groups(cfg), carry, run)
    h, kp, vp, cs, ss = carry
    return h, {"k_pages": kp, "v_pages": vp, "conv_state": cs,
               "ssm_state": ss}


SERVING = _lw.ServingFamily(walk=_walk, init_pages=init_serving_pages,
                            kinds=serving_cache_kinds)
