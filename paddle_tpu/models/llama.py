"""Llama-family decoder, functional and TPU-first.

This is the flagship pretrain path: the capability target is the reference's
hybrid-parallel Llama training stack (fleet TP layers mp_layers.py, pipeline
schedules pipeline_parallel.py, sharding optimizer, sequence-parallel utils —
see SURVEY.md §2.8/§3.4), redesigned as ONE jitted SPMD program:

  - params are a plain pytree; per-layer weights are stacked on a leading
    layer axis and consumed by ``lax.scan`` (fast compiles, XLA-friendly);
  - TP  = GSPMD sharding annotations on weights (column/row parallel exactly
    where fleet's ColumnParallelLinear/RowParallelLinear shard);
  - SP  = sequence-sharded residual stream between blocks over the tp axis
    (megatron sequence parallel, sequence_parallel_utils.py:427);
  - PP  = microbatch pipeline via parallel.pipeline_spmd (collective-permute
    ring instead of NCCL isend/irecv);
  - DP/ZeRO = batch sharded over dp; optimizer state sharded like params.

XLA inserts every collective (all-gather / reduce-scatter / ppermute) from
the sharding annotations — there is no hand-written communication here.
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

from ..observability import in_setup_span, setup_span
from ..ops.pallas.flash_attention import remat_layer
from ..parallel.pipeline_spmd import pipeline_spmd, microbatch
from .layer_walk import ServingFamily, kv_rows, paged_kv_attend, tick_plan


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    # parallelism
    pp_stages: int = 1
    num_microbatches: int = 1
    # "gpipe": autodiff through the SPMD pipeline (pipeline_spmd) — all
    # forwards then all backwards, O(M) live microbatch activations.
    # "1f1b": explicit fused fwd+bwd LOCKSTEP schedule (pipeline_1f1b)
    # — O(S) live activations, matching pipeline_parallel.py:565, but
    # every tick runs every slot (fill/drain = masked work).
    # "1f1b_async": rank-asymmetric 1F1B (pipeline_async) — shard_map
    # body branching on stage index, reference per-rank bubble
    # 1-(S-1)/(VM+S-1); composes dp (row-sharded microbatches, grad
    # psum folded into the f32 accumulation carry) and tp (manual
    # megatron f/g collectives in the stage body, vocab-parallel CE
    # in the head) since r19.
    # "zb": ZB-H1-style W-deferral on top of 1f1b_async
    # (pipeline_zero_bubble.py counterpart); V=1, W consumes
    # ring-saved residuals (~4.5 work units vs the fused 4).
    pp_schedule: str = "gpipe"
    # interleaved VPP: chunks per device under the 1f1b schedule
    # (pipeline_parallel.py:1372 round-robin model partition)
    vpp_chunks: int = 1
    remat: bool = True
    # kernels: True/"auto" (pallas when shapes allow), "pallas" (strict:
    # error instead of silently falling back to dense — the bench runs
    # this), False/"dense"
    use_flash_attention: Any = True
    # fused rmsnorm/rope pallas kernels between the GEMMs
    # (ops/pallas/fused_norm_rope; counterpart of the reference's
    # fused_rms_norm/fused_rope fusion kernels). "auto": on when running
    # on TPU. Under a tp/cp-sharded residual stream the kernels run per
    # shard via the *_sharded shard_map entries (norm/rope are token- and
    # head-local, like the reference's per-rank fused kernels under TP);
    # in the pp>1 stage loop — where stages run under vmap, which does
    # not compose with shard_map — the jnp formulation runs instead.
    # True/"pallas": always (interpret mode off-TPU). False: never.
    use_fused_norm_rope: Any = "auto"
    # context parallelism: "none" | "ring" | "ulysses" | "zigzag" —
    # shards the sequence dim over the mesh cp axis
    # (parallel/context_parallel.py). "zigzag" is the causal-balanced
    # ring: tokens are laid out so every rank owns one head + one tail
    # cell and each ring hop carries equal unmasked work.
    context_parallel: str = "none"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @staticmethod
    def llama3_8b(**kw) -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32,
            num_key_value_heads=8, rope_theta=500000.0, **kw)

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        """Test/dryrun config."""
        return LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128, **kw)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_params(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """Init a params pytree; per-layer tensors stacked on a leading L axis."""
    D, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    H, Hkv, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    L = cfg.num_hidden_layers
    ks = jax.random.split(key, 10)

    def init(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) *
                (1.0 / np.sqrt(fan_in))).astype(cfg.dtype)

    layers = {
        "wq": init(ks[0], (L, D, H * Dh), D),
        "wk": init(ks[1], (L, D, Hkv * Dh), D),
        "wv": init(ks[2], (L, D, Hkv * Dh), D),
        "wo": init(ks[3], (L, H * Dh, D), H * Dh),
        "w_gate": init(ks[4], (L, D, F), D),
        "w_up": init(ks[5], (L, D, F), D),
        "w_down": init(ks[6], (L, F, D), F),
        "attn_norm": jnp.ones((L, D), cfg.dtype),
        "mlp_norm": jnp.ones((L, D), cfg.dtype),
    }
    return {
        "embed": init(ks[7], (V, D), D),
        "layers": layers,
        "final_norm": jnp.ones((D,), cfg.dtype),
        "lm_head": init(ks[8], (D, V), D),
    }


def abstract_params(cfg: LlamaConfig):
    """ShapeDtypeStruct pytree of ``init_params`` output without
    computing (or allocating) anything — what tracing-only tooling
    (analysis/serving_graphs.py graph lint, cost models) feeds to
    ``jax.make_jaxpr`` so a lint run costs milliseconds, not an init."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


def param_specs(cfg: LlamaConfig) -> Dict[str, Any]:
    """PartitionSpecs: where fleet's TP layers shard, we annotate.

    Column-parallel (out-dim on tp): wq/wk/wv, w_gate/w_up — fleet's
    ColumnParallelLinear (mp_layers.py). Row-parallel (in-dim on tp):
    wo, w_down — RowParallelLinear. Vocab-parallel embedding shards the
    vocab dim; lm_head is column-parallel over vocab (ParallelCrossEntropy
    consumes vocab-sharded logits). Leading axis of layer weights is the
    layer/stage axis: sharded over pp when pipelining.
    """
    pp = "pp" if cfg.pp_stages > 1 else None
    layers = {
        "wq": P(pp, None, "tp"),
        "wk": P(pp, None, "tp"),
        "wv": P(pp, None, "tp"),
        "wo": P(pp, "tp", None),
        "w_gate": P(pp, None, "tp"),
        "w_up": P(pp, None, "tp"),
        "w_down": P(pp, "tp", None),
        "attn_norm": P(pp, None),
        "mlp_norm": P(pp, None),
    }
    return {
        "embed": P("tp", None),
        "layers": layers,
        "final_norm": P(None),
        "lm_head": P(None, "tp"),
    }


def shard_params(params, cfg: LlamaConfig, mesh: Mesh):
    specs = param_specs(cfg)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs, is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# model math
# ---------------------------------------------------------------------------

def _mm(x, w):
    """``x @ w`` for a dense weight or an ``Int8Weight`` (the weight-only
    int8 decode path, quantization/decode.py): the per-channel dequant is
    fused into the matmul (ops/fused/int8_matmul). Dense weights — the
    training path — take the plain-``@`` branch, so nothing changes for
    them."""
    dm = getattr(w, "dequant_matmul", None)
    return x @ w if dm is None else dm(x)


def rms_norm(x, weight, eps):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * weight.astype(jnp.float32)).astype(dt)


def rope(q, k, positions, theta, head_dim):
    """Rotary embedding applied to [B, T, H, Dh] q/k."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,T,half]
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]

    def rot(x):
        x1, x2 = x[..., :half], x[..., half:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1)
        return out.astype(x.dtype)

    return rot(q), rot(k)


def attention(q, k, v, cfg: LlamaConfig):
    """Causal GQA attention, dense path (single implementation lives in
    ops/pallas/flash_attention; this forces impl='dense')."""
    from ..ops.pallas.flash_attention import flash_attention as _fa
    return _fa(q, k, v, causal=True, impl="dense")


def _fused_nr_on(cfg: LlamaConfig, mesh) -> bool:
    """Whether the fused pallas rmsnorm/rope kernels replace the jnp
    formulations in the layer body (see LlamaConfig.use_fused_norm_rope)."""
    v = getattr(cfg, "use_fused_norm_rope", "auto")
    if v in (False, "off", "dense"):
        return False
    if v in (True, "pallas"):
        return True
    return jax.default_backend() == "tpu"


def _spec_divides(mesh, spec, shape) -> bool:
    """Whether every sharded dim of ``shape`` divides its mesh axis size
    (shard_map requires even splits; GSPMD would pad, shard_map raises)."""
    for dim, ax in zip(shape, tuple(spec)):
        if ax is None:
            continue
        axes = ax if isinstance(ax, (tuple, list)) else (ax,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if dim % size:
            return False
    return True


def _tp_heads_shardable(cfg: LlamaConfig, mesh) -> bool:
    """Whether q/k/v head dims can shard over tp: the GQA group structure
    survives a head split iff BOTH head counts divide the tp degree."""
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    return (tp > 1 and cfg.num_attention_heads % tp == 0
            and cfg.num_key_value_heads % tp == 0)


def _norm_fn(cfg: LlamaConfig, mesh, fused: bool, h_spec=None):
    """The rms_norm callable: fused pallas kernel (per-shard via shard_map
    when ``h_spec`` gives the stream's PartitionSpec) or the jnp path."""
    if fused and h_spec is not None:
        from ..ops.pallas.fused_norm_rope import fused_rms_norm_sharded
        return lambda x, w: fused_rms_norm_sharded(x, w, mesh, h_spec,
                                                   cfg.rms_norm_eps)
    if fused:
        from ..ops.pallas.fused_norm_rope import fused_rms_norm
        return lambda x, w: fused_rms_norm(x, w, cfg.rms_norm_eps)
    return lambda x, w: rms_norm(x, w, cfg.rms_norm_eps)


def _fused_shard_specs(cfg: LlamaConfig, mesh, sp_spec):
    """PartitionSpecs for running the fused norm/rope kernels per shard
    when the residual stream is sequence-sharded (megatron SP over tp, or
    context parallel over cp).

    Returns ``(h_spec, rope_specs)`` where ``rope_specs`` is
    ``(q_spec, k_spec, pos_spec)`` or None (rope then runs the jnp path —
    e.g. GQA head counts not divisible by the tp degree). Returns None
    outright when there is no mesh context to shard_map over.
    """
    if mesh is None or sp_spec is None:
        return None
    h_spec = sp_spec.spec if hasattr(sp_spec, "spec") else sp_spec
    dp_ax, seq_ax = h_spec[0], h_spec[1]
    tp = mesh.shape.get("tp", 1)
    # q/k leave the column-parallel QKV matmul head-sharded over tp
    head_ax = "tp" if _tp_heads_shardable(cfg, mesh) else None
    if seq_ax == "tp":
        # megatron SP: the matmul all-gathers the seq dim; heads carry tp
        if head_ax is None:
            rope_specs = None
        else:
            qk = P(dp_ax, None, "tp", None)
            rope_specs = (qk, qk, P(dp_ax, None))
    elif seq_ax is not None:
        # context parallel: seq stays sharded through rope (positions are
        # per-token, so any layout — zigzag included — shards with it)
        if tp > 1 and head_ax is None:
            rope_specs = None  # heads carry tp but do not divide it
        else:
            qk = P(dp_ax, seq_ax, head_ax, None)
            rope_specs = (qk, qk, P(dp_ax, seq_ax))
    else:
        rope_specs = None
    return h_spec, rope_specs


def _block(lp, h, positions, cfg: LlamaConfig, attn_fn, sp_spec=None,
           fused_nr=False, mesh=None):
    """The transformer block math shared by the training path
    (decoder_layer) and the KV-cache decode path (forward_with_cache):
    rms_norm -> QKV -> rope -> ``attn_fn(q, k, v)`` -> o-proj+residual ->
    rms_norm -> SwiGLU+residual. One source of truth — attention strategy
    is the only thing the two paths vary.

    With ``fused_nr`` and a sequence-sharded residual stream (sp_spec),
    the fused pallas kernels run per shard via the *_sharded shard_map
    entries (fused_norm_rope.py) — norm and rope are token/head-local, so
    the sharded stream no longer forces the slow jnp path."""
    B, T, D = h.shape
    H, Hkv, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    sharded = None
    if fused_nr and sp_spec is not None:
        sharded = _fused_shard_specs(cfg, mesh, sp_spec)
        if sharded is not None and not _spec_divides(mesh, sharded[0],
                                                    h.shape):
            sharded = None  # uneven split: shard_map would raise
        if sharded is None:
            fused_nr = False  # sharded stream, no mesh context: jnp
        elif sharded[1] is not None and not _spec_divides(
                mesh, sharded[1][0], (B, T, H, Dh)):
            sharded = (sharded[0], None)  # rope alone falls back to jnp
    norm = _norm_fn(cfg, mesh, fused_nr, sharded[0] if sharded else None)
    if fused_nr and sharded is not None and sharded[1] is not None:
        from ..ops.pallas.fused_norm_rope import fused_rope_sharded
        q_spec, k_spec, pos_spec = sharded[1]
        rope_fn = lambda q, k: fused_rope_sharded(
            q, k, positions, mesh, q_spec, k_spec, pos_spec, cfg.rope_theta)
    elif fused_nr and sharded is None:
        from ..ops.pallas.fused_norm_rope import fused_rope
        rope_fn = lambda q, k: fused_rope(q, k, positions, cfg.rope_theta)
    else:
        rope_fn = lambda q, k: rope(q, k, positions, cfg.rope_theta, Dh)
    # named scopes are metadata on the operations (the program is the
    # same): a device trace then says which line of the model an
    # operation is
    with jax.named_scope("attn.qkv_rope"):
        x = norm(h, lp["attn_norm"])
        q = _proj(x, lp, "wq").reshape(B, T, H, Dh)
        k = _proj(x, lp, "wk").reshape(B, T, Hkv, Dh)
        v = _proj(x, lp, "wv").reshape(B, T, Hkv, Dh)
        q, k = rope_fn(q, k)
    o = attn_fn(q, k, v)
    with jax.named_scope("attn.out"):
        h = h + _mm(o.reshape(B, T, H * Dh), lp["wo"])
        if sp_spec is not None:
            # sequence-parallel residual stream: reduce-scatter the
            # row-parallel output over tp along the seq dim
            # (sequence_parallel_utils.py:427)
            h = lax.with_sharding_constraint(h, sp_spec)

    with jax.named_scope("mlp"):
        x = norm(h, lp["mlp_norm"])
        h = h + _mm(jax.nn.silu(_mm(x, lp["w_gate"]))
                    * _mm(x, lp["w_up"]), lp["w_down"])
        if sp_spec is not None:
            h = lax.with_sharding_constraint(h, sp_spec)
    return h


def _train_attn_fn(cfg: LlamaConfig, mesh):
    """Attention callable for the training path: context-parallel when a
    cp axis is live, otherwise the flash kernel per cfg — run per tp
    shard over the head dim when tp shards the stream (attention is
    head-local; GQA grouping survives because Hkv % tp == 0), so the
    opaque pallas call never makes GSPMD all-gather the activations."""
    cp_on = (cfg.context_parallel != "none" and mesh is not None
             and mesh.shape.get("cp", 1) > 1)
    if cp_on:
        from ..parallel.context_parallel import context_parallel_attention
        return lambda q, k, v: context_parallel_attention(
            q, k, v, mesh, impl=cfg.context_parallel)
    from ..ops.pallas.flash_attention import flash_attention as _fa
    fa = cfg.use_flash_attention
    impl = fa if isinstance(fa, str) else ("auto" if fa else "dense")
    if _tp_heads_shardable(cfg, mesh):
        from jax import shard_map
        dp_ax = "dp" if "dp" in mesh.shape else None
        spec = P(dp_ax, None, "tp", None)
        body = lambda ql, kl, vl: _fa(ql, kl, vl, causal=True, impl=impl)

        def attn(q, k, v):
            if not _spec_divides(mesh, spec, q.shape):
                # uneven batch split: plain GSPMD call instead of a
                # shard_map trace error
                return _fa(q, k, v, causal=True, impl=impl)
            return shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)(q, k, v)
        return attn
    return lambda q, k, v: _fa(q, k, v, causal=True, impl=impl)


def decoder_layer(lp, h, cfg: LlamaConfig, sp_spec=None, mesh=None,
                  positions=None):
    """One transformer block on [B, T, D]. ``lp`` holds this layer's
    (unstacked) weights. ``positions``: global token positions [B, T]
    (defaults to arange — zigzag CP passes its permuted layout)."""
    B, T, _ = h.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    return _block(lp, h, positions, cfg, _train_attn_fn(cfg, mesh),
                  sp_spec=sp_spec, fused_nr=_fused_nr_on(cfg, mesh),
                  mesh=mesh)


def _grads_leave_sharded(tree, specs, grad_specs, mesh):
    """Identity on a subtree of the parameters whose backward lays each
    cotangent out in ``grad_specs`` (the leaf's dp-sharded ZeRO layout)
    by way of ``specs`` (the leaf's own tp layout). The forward and an
    undifferentiated program are untouched.

    Two constraints, in that order, are what makes the compiler reduce a
    weight gradient by reduce-scatters fused onto the matmul that made
    it: the first (left out where the leaf's own spec shards nothing on
    this mesh: it would then ask for the whole all-reduce) keeps the tp
    reduce-scatter it emits today, the second turns the dp all-reduce
    into a second one. Constrained straight to the dp layout, a
    gradient that is partial over tp AND dp (the MLP's, computed on
    each chip's half of the sequence) falls back to one synchronous
    four-way all-reduce of the full matrix (described v5e:2x2 compile,
    PR 41)."""

    @jax.custom_vjp
    def ident(t):
        return t

    def lay(x, own, sharded):
        if any(mesh.shape.get(a, 1) > 1 for a in own if a is not None):
            x = lax.with_sharding_constraint(x, NamedSharding(mesh, own))
        return lax.with_sharding_constraint(x, NamedSharding(mesh, sharded))

    def bwd(_, ct):
        return (jax.tree_util.tree_map(lay, ct, specs, grad_specs),)

    ident.defvjp(lambda t: (t, None), bwd)
    return ident(tree)


def _layer_specs(stacked_specs):
    """One layer's specs from the stacked leaves': the layer axis off."""
    return jax.tree_util.tree_map(lambda s: P(*tuple(s)[1:]), stacked_specs,
                                  is_leaf=lambda s: isinstance(s, P))


def _scan_layers(layer_params, h, cfg: LlamaConfig, sp_spec=None, remat=False,
                 mesh=None, positions=None, grad_specs=None):
    """``grad_specs``: the stacked leaves' dp-sharded layout, when the
    caller's update runs on dp shards (``_loss_with_sharded_grads``):
    each layer's weight gradients then leave the backward loop
    reduce-scattered over dp instead of all-reduced at its tail."""
    fn = partial(decoder_layer, cfg=cfg, sp_spec=sp_spec, mesh=mesh,
                 positions=positions)
    if remat:
        # all of the layer is rebuilt in the backward pass but splash's
        # out and logsumexp, named inside the kernel's own forward rule
        # (the backward kernels read THAT rule's residuals: a name put
        # on the layer's attention output saves nothing they read)
        fn = remat_layer(fn)
    if grad_specs is not None:
        own, sharded = (_layer_specs(param_specs(cfg)["layers"]),
                        _layer_specs(grad_specs))

    def body(carry, lp):
        if grad_specs is not None:
            lp = _grads_leave_sharded(lp, own, sharded, mesh)
        return fn(lp, carry), None

    h, _ = lax.scan(body, h, layer_params)
    return h


def _zigzag_on(cfg: LlamaConfig, mesh) -> bool:
    return (cfg.context_parallel == "zigzag" and mesh is not None
            and mesh.shape.get("cp", 1) > 1)


def forward(params, tokens, cfg: LlamaConfig, mesh: Optional[Mesh] = None):
    """tokens [B, T] -> logits [B, T, V]. Single pipeline stage (pp=1).

    Under zigzag CP the sequence is internally re-laid-out (one head +
    one tail cell per cp rank, parallel/context_parallel.py
    zigzag_global_perm) — logits come back in that order; loss_fn
    permutes the labels identically, so training is order-consistent.
    """
    return _forward(params, tokens, cfg, mesh)


def _forward(params, tokens, cfg: LlamaConfig, mesh, layer_grad_specs=None):
    """``forward``; ``layer_grad_specs`` is ``_scan_layers``'
    ``grad_specs``."""
    sp_spec = None
    positions = None
    if mesh is not None and mesh.shape.get("cp", 1) > 1:
        # context parallel: residual stream sequence-sharded over cp
        sp_spec = NamedSharding(mesh, P("dp", "cp", None))
        if _zigzag_on(cfg, mesh):
            from ..parallel.context_parallel import zigzag_global_perm
            perm = zigzag_global_perm(tokens.shape[1], mesh.shape["cp"])
            tokens = tokens[:, perm]
            positions = jnp.broadcast_to(jnp.asarray(perm), tokens.shape)
    elif mesh is not None and mesh.shape.get("tp", 1) > 1:
        sp_spec = NamedSharding(mesh, P("dp", "tp", None))
    h = params["embed"].astype(cfg.dtype)[tokens]
    if sp_spec is not None:
        h = lax.with_sharding_constraint(h, sp_spec)
    h = _scan_layers(params["layers"], h, cfg, sp_spec, remat=cfg.remat,
                     mesh=mesh, positions=positions,
                     grad_specs=layer_grad_specs)
    fin_spec = sp_spec.spec if sp_spec is not None else None
    if fin_spec is not None and not _spec_divides(mesh, fin_spec, h.shape):
        fin_spec = None  # uneven split: run the jnp norm instead
        fused_fin = False
    else:
        fused_fin = _fused_nr_on(cfg, mesh)
    h = _norm_fn(cfg, mesh, fused_fin, fin_spec)(h, params["final_norm"])
    return _mm(h, params["lm_head"])


def _split_stages(layer_params, cfg: LlamaConfig):
    """[L, ...] stacked layers -> [S, L/S, ...] (stage axis leading)."""
    S = cfg.pp_stages
    L = cfg.num_hidden_layers
    assert L % S == 0, f"layers {L} not divisible by pp_stages {S}"
    return jax.tree_util.tree_map(
        lambda x: x.reshape((S, L // S) + x.shape[1:]), layer_params)


def forward_pipelined(params, tokens, cfg: LlamaConfig, mesh: Mesh):
    """Full pp×tp×sp×dp forward: embed → pipeline over stages → head."""
    if cfg.context_parallel != "none":
        raise NotImplementedError(
            "context_parallel with pp_stages > 1 is not supported yet: the "
            "pipeline stage loop would need the cp shard_map nested inside "
            "it; use cp with pp=1 (ring attention already gives the "
            "long-sequence memory scaling pipelining would)")
    sp_spec = (NamedSharding(mesh, P(None, "dp", "tp", None))
               if mesh.shape.get("tp", 1) > 1 else None)
    h = params["embed"].astype(cfg.dtype)[tokens]          # [B, T, D]
    h = microbatch(h, cfg.num_microbatches)                # [M, mb, T, D]
    h = lax.with_sharding_constraint(
        h, NamedSharding(mesh, P(None, "dp", "tp" if sp_spec is not None else None, None)))

    stage_params = _split_stages(params["layers"], cfg)

    def stage_fn(sp, x):
        inner_sp = sp_spec.spec if sp_spec is not None else None
        inner = NamedSharding(mesh, P(*inner_sp[1:])) if sp_spec is not None else None
        # per-layer remat inside the stage (same recompute FLOPs as
        # checkpointing the whole stage, but backward peak memory is one
        # layer's internals, not one stage's)
        return _scan_layers(sp, x, cfg, inner, remat=cfg.remat)

    h = pipeline_spmd(stage_fn, stage_params, h,
                      num_stages=cfg.pp_stages, remat=False)
    h = h.reshape((-1,) + h.shape[2:])                     # [B, T, D]
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return _mm(h, params["lm_head"])


# ---------------------------------------------------------------------------
# loss / train step
# ---------------------------------------------------------------------------

def loss_fn(params, batch, cfg: LlamaConfig, mesh: Optional[Mesh] = None):
    """Next-token cross entropy via the fused op (ops/fused/cross_entropy):
    logits stay in model dtype and vocab-sharded (tp) end to end — no f32
    [B, T, V] log-softmax is materialised, and under GSPMD the reductions
    lower to the reference's _c_softmax_with_cross_entropy collective
    pattern (mp_ops.py:414), never a logits all-gather
    (tests/test_fused_ce.py checks the HLO)."""
    return _loss(params, batch, cfg, mesh)


def _loss(params, batch, cfg: LlamaConfig, mesh, layer_grad_specs=None):
    from ..ops.fused import fused_softmax_cross_entropy
    tokens, labels = batch["tokens"], batch["labels"]
    if mesh is not None and cfg.pp_stages > 1:
        logits = forward_pipelined(params, tokens, cfg, mesh)
    else:
        logits = _forward(params, tokens, cfg, mesh, layer_grad_specs)
        if _zigzag_on(cfg, mesh):
            # logits are in the zigzag layout; pair labels the same way
            from ..parallel.context_parallel import zigzag_global_perm
            labels = labels[:, zigzag_global_perm(labels.shape[1],
                                                  mesh.shape["cp"])]
    return fused_softmax_cross_entropy(logits, labels).mean()


def _loss_with_sharded_grads(params, batch, cfg: LlamaConfig, mesh: Mesh,
                             grad_specs):
    """``loss_fn`` for a trainer whose update runs on dp shards
    (``grad_specs``: ``zero_param_specs``): every parameter's gradient
    comes out of the backward pass in that layout, reduce-scattered
    where it is made — the layers' inside their loop, the rest here."""
    outer = lambda t: {k: v for k, v in t.items() if k != "layers"}
    params = {**params, **_grads_leave_sharded(
        outer(params), outer(param_specs(cfg)), outer(grad_specs), mesh)}
    return _loss(params, batch, cfg, mesh, grad_specs["layers"])


from ..parallel.pipeline_async import PP_SCHEDULES

#: cfg.pp_schedule -> pipeline_async executor variant
ASYNC_PP_SCHEDULES = {k: var for k, (_, var) in PP_SCHEDULES.items()
                      if var is not None}


def _tp_local_block(lp, h, positions, cfg: LlamaConfig, attn_fn):
    """One transformer block on tp-LOCAL weight shards inside a
    ``shard_map`` body — the manual-collective mirror of ``_block``
    for the rank-asymmetric pipeline schedules, where GSPMD cannot
    insert the tp collectives (and a raw in-body ``lax.psum`` would
    transpose wrong under ``jax.vjp`` — parallel/mp_ops.py).

    Megatron placement: the "f" op (identity fwd, psum bwd) sits on
    each norm's OUTPUT, between the replicated math and the
    column-parallel weights — downstream of every replicated weight,
    so the backward psum completes the cotangent BEFORE it reaches the
    norm and its gradient arrives COMPLETE on each tp rank; the "g" op
    (psum fwd, identity bwd) completes the row-parallel outputs (wo,
    w_down) — two activation all-reduces per block forward and two
    backward, exactly the pattern the planner's analytic tp term
    priced. Local head/ffn widths are derived from the SHARD shapes
    (``wq.shape[-1] // head_dim``), so the same code runs at tp=1
    unsharded."""
    from ..parallel.mp_ops import (identity_fwd_psum_bwd,
                                   psum_fwd_identity_bwd)
    B, T, D = h.shape
    Dh = cfg.head_dim
    Hl = lp["wq"].shape[-1] // Dh
    Hkvl = lp["wk"].shape[-1] // Dh
    x = identity_fwd_psum_bwd(
        rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps), "tp")
    q = (x @ lp["wq"]).reshape(B, T, Hl, Dh)
    k = (x @ lp["wk"]).reshape(B, T, Hkvl, Dh)
    v = (x @ lp["wv"]).reshape(B, T, Hkvl, Dh)
    q, k = rope(q, k, positions, cfg.rope_theta, Dh)
    o = attn_fn(q, k, v)
    h = h + psum_fwd_identity_bwd(
        o.reshape(B, T, Hl * Dh) @ lp["wo"], "tp")
    x = identity_fwd_psum_bwd(
        rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps), "tp")
    h = h + psum_fwd_identity_bwd(
        (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"]))
        @ lp["w_down"], "tp")
    return h


def _async_stage_head_fns(cfg: LlamaConfig, mesh: Mesh):
    """(stage_fn, head_fn) for ``pipeline_train_async``'s shard_map
    body. tp=1 keeps the exact pre-r19 callables (GSPMD-free local
    math, fused dense CE) so those traced programs are unchanged;
    tp>1 switches to the manual-collective forms: ``_tp_local_block``
    per layer and a vocab-parallel head (``final_norm`` replicated,
    ``lm_head`` vocab-sharded, CE via the explicit-psum
    ``vocab_parallel_cross_entropy``)."""
    from ..ops.fused import (fused_softmax_cross_entropy,
                             vocab_parallel_cross_entropy)
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    if tp <= 1:
        def stage_fn(chunk_params, xm):
            return _scan_layers(chunk_params, xm, cfg, None,
                                remat=cfg.remat)

        def head_fn(hp, y, y_labels):
            h = rms_norm(y, hp["final_norm"], cfg.rms_norm_eps)
            return fused_softmax_cross_entropy(
                h @ hp["lm_head"], y_labels).mean()
        return stage_fn, head_fn

    H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    F, V = cfg.intermediate_size, cfg.vocab_size
    bad = {k: n for k, n in
           dict(heads=H, kv_heads=Hkv, ffn=F, vocab=V).items()
           if n % tp}
    if bad:
        raise ValueError(
            f"tp={tp} does not divide {bad} — the async schedules "
            f"shard heads/ffn/vocab over tp inside the stage body")
    from ..ops.pallas.flash_attention import flash_attention as _fa
    fa = cfg.use_flash_attention
    impl = fa if isinstance(fa, str) else ("auto" if fa else "dense")
    attn_fn = lambda q, k, v: _fa(q, k, v, causal=True, impl=impl)
    from ..parallel.mp_ops import identity_fwd_psum_bwd

    def stage_fn(chunk_params, xm):
        B, T, _ = xm.shape
        positions = jnp.broadcast_to(jnp.arange(T), (B, T))
        fn = lambda lp, hh: _tp_local_block(lp, hh, positions, cfg,
                                            attn_fn)
        if cfg.remat:
            fn = remat_layer(fn)

        def body(carry, lp):
            return fn(lp, carry), None

        h, _ = lax.scan(body, xm, chunk_params)
        return h

    def head_fn(hp, y, y_labels):
        h = identity_fwd_psum_bwd(
            rms_norm(y, hp["final_norm"], cfg.rms_norm_eps), "tp")
        return vocab_parallel_cross_entropy(
            h @ hp["lm_head"], y_labels, "tp").mean()
    return stage_fn, head_fn


def _async_shard_specs(cfg: LlamaConfig, mesh: Mesh):
    """(stage_specs, head_specs, x_spec, aux_specs) for the composed
    async executor: per-leaf chunk-dim specs derived from the ONE
    declared layout (``param_specs``), rows sharded over dp. The tail
    of each layer spec (everything after the stacked-layer axis) IS
    the chunk tail — the executor prepends its (V, pp) axes."""
    dp_on = mesh.shape.get("dp", 1) > 1
    tp_on = mesh.shape.get("tp", 1) > 1
    pspecs = param_specs(cfg)
    stage_specs = jax.tree_util.tree_map(
        lambda s: P(None, *(tuple(s)[1:] if tp_on else ())),
        pspecs["layers"], is_leaf=lambda v: isinstance(v, P))
    head_specs = {"final_norm": P(),
                  "lm_head": P(None, "tp") if tp_on else P()}
    dp_ax = "dp" if dp_on else None
    x_spec = P(None, dp_ax, None, None)
    aux_specs = P(None, dp_ax, None)
    return stage_specs, head_specs, x_spec, aux_specs


def grads_1f1b(params, batch, cfg: LlamaConfig, mesh: Mesh):
    """(loss, grads) via an explicit fused fwd+bwd pipeline schedule:
    the lockstep 1F1B / interleaved-VPP scan (parallel/pipeline_1f1b.py,
    ``pp_schedule="1f1b"``) or a rank-asymmetric schedule
    (parallel/pipeline_async.py, ``"1f1b_async"`` / ``"zb"`` — same
    numerics, reference per-rank bubble). Embedding forward+pullback
    bracket the pipeline; the loss head (final norm + lm_head + fused
    CE) runs per-microbatch as each one exits the last stage."""
    from ..ops.fused import fused_softmax_cross_entropy
    from ..parallel.pipeline_1f1b import (pipeline_train_1f1b,
                                          split_chunks_round_robin)
    from ..parallel.pipeline_async import pipeline_train_async
    S, V, M = cfg.pp_stages, cfg.vpp_chunks, cfg.num_microbatches
    tokens, labels = batch["tokens"], batch["labels"]
    tp_on = mesh is not None and mesh.shape.get("tp", 1) > 1
    inner_sp = (NamedSharding(mesh, P("dp", "tp", None)) if tp_on else None)
    mb_spec = P("dp", "tp" if tp_on else None, None)

    def stage_fn(chunk_params, xm):
        return _scan_layers(chunk_params, xm, cfg, inner_sp,
                            remat=cfg.remat)

    def head_fn(hp, y, y_labels):
        h = rms_norm(y, hp["final_norm"], cfg.rms_norm_eps)
        logits = h @ hp["lm_head"]
        return fused_softmax_cross_entropy(logits, y_labels).mean()

    def embed_fwd(emb):
        h = emb.astype(cfg.dtype)[tokens]
        return microbatch(h, M)

    x_mb, embed_pull = jax.vjp(embed_fwd, params["embed"])
    labels_mb = microbatch(labels, M)
    chunks = split_chunks_round_robin(
        params["layers"], cfg.num_hidden_layers, S, V)
    head_params = {"final_norm": params["final_norm"],
                   "lm_head": params["lm_head"]}
    if cfg.pp_schedule in ASYNC_PP_SCHEDULES:
        a_stage, a_head = _async_stage_head_fns(cfg, mesh)
        spec_kw = {}
        if (mesh.shape.get("dp", 1) > 1 or mesh.shape.get("tp", 1) > 1):
            sspecs, hspecs, xspec, aspecs = _async_shard_specs(cfg, mesh)
            spec_kw = dict(stage_specs=sspecs, head_specs=hspecs,
                           x_spec=xspec, aux_specs=aspecs)
        loss, gchunks, ghead, dx = pipeline_train_async(
            a_stage, a_head, chunks, head_params, x_mb, labels_mb,
            num_stages=S, virtual_chunks=V,
            variant=ASYNC_PP_SCHEDULES[cfg.pp_schedule], mesh=mesh,
            **spec_kw)
    else:
        loss, gchunks, ghead, dx = pipeline_train_1f1b(
            stage_fn, head_fn, chunks, head_params, x_mb, labels_mb,
            num_stages=S, virtual_chunks=V, mesh=mesh, mb_spec=mb_spec)
    glayers = jax.tree_util.tree_map(
        lambda g, p: g.reshape(p.shape), gchunks, params["layers"])
    (dembed,) = embed_pull(dx)
    grads = {"embed": dembed, "layers": glayers,
             "final_norm": ghead["final_norm"],
             "lm_head": ghead["lm_head"]}
    return loss, grads


def default_train_optimizer():
    """The optimizer ``make_train_step`` builds when none is given —
    one definition so the analysis targets (analysis/training_graphs.py)
    derive specs for the exact optimizer the step runs."""
    import optax
    return optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1)


def zero_param_specs(cfg: LlamaConfig, dp: int):
    """``param_specs`` with one more dim of every leaf sharded over dp
    (``zero_spec``: the first free dim that dp divides) — the layout of
    a ZeRO shard: the moments' from stage 1, the gradients' as they
    leave the backward pass, the stored parameters' at stage 3.

    A stacked layer leaf takes dp on a dim WITHIN the layer, never on
    the layer axis, however many layers dp divides: a gradient sharded
    by layers cannot be reduce-scattered inside the loop over layers,
    and moments laid out otherwise than their gradients would pay a
    reshard every step. A leaf no dim of which dp divides keeps its
    spec (its gradient is all-reduced, its update replicated)."""
    from ..distributed.sharding import zero_spec

    def place(sp, a, layer_axes=0):
        rest = tuple(sp)[layer_axes:]
        zs = zero_spec(P(*rest), a.shape[layer_axes:], dp)
        return sp if zs is None else P(*tuple(sp)[:layer_axes], *zs)

    pspecs, abs_params = param_specs(cfg), abstract_params(cfg)
    is_spec = lambda x: isinstance(x, P)
    out = jax.tree_util.tree_map(place, pspecs, abs_params, is_leaf=is_spec)
    out["layers"] = jax.tree_util.tree_map(
        partial(place, layer_axes=1), pspecs["layers"],
        abs_params["layers"], is_leaf=is_spec)
    return out


def _zero_stage_of(cfg: LlamaConfig, mesh: Mesh, zero_stage) -> int:
    """``zero_stage=None`` is "by the mesh": where the batch is split
    over dp > 1 and the step is the plain ``value_and_grad`` (no
    pipeline schedule), stage 1; else 0 (nothing to shard over, or a
    pipelined gradient path, which keeps its all-reduce)."""
    if zero_stage is None:
        return int(mesh.shape.get("dp", 1) > 1 and cfg.pp_stages == 1)
    if zero_stage not in (0, 1, 2, 3):
        raise ValueError(f"zero_stage must be 0..3, got {zero_stage}")
    return zero_stage


def train_state_specs(cfg: LlamaConfig, mesh: Mesh, optimizer=None,
                      zero_stage: Optional[int] = None):
    """PartitionSpec pytree matching ``make_train_step``'s state
    ``{"params", "opt", "step"}`` — the declared layout, computed
    without allocating anything. ``init_fn`` places by these specs and
    the static sharding lint reads the same tree, so the two cannot
    drift.

    Optimizer-state leaves inherit the owning param's (tp/pp) spec
    (every params-shaped subtree of the optax state maps one-to-one);
    zero_stage >= 1 (``None``: by the mesh, as ``make_train_step``)
    gives them ``zero_param_specs``' dp dim on top; zero_stage >= 3
    does the same to the params.
    """
    if optimizer is None:
        optimizer = default_train_optimizer()
    zero_stage = _zero_stage_of(cfg, mesh, zero_stage)
    dp = mesh.shape.get("dp", 1)
    pspecs = param_specs(cfg)
    abs_params = abstract_params(cfg)
    moment_specs = (zero_param_specs(cfg, dp)
                    if zero_stage >= 1 and dp > 1 else pspecs)

    # opt-state leaves mirror params subtree-by-subtree (adamw mu/nu);
    # anything not params-shaped (count scalars) replicates
    p_def = jax.tree_util.tree_structure(abs_params)
    abs_opt = jax.eval_shape(optimizer.init, abs_params)

    def params_like(node):
        try:
            return jax.tree_util.tree_structure(node) == p_def
        except Exception:
            return False

    opt_specs = jax.tree_util.tree_map(
        lambda node: moment_specs if params_like(node) else P(),
        abs_opt, is_leaf=params_like)
    if zero_stage >= 3:
        pspecs = moment_specs
    return {"params": pspecs, "opt": opt_specs, "step": P()}


def make_train_step(cfg: LlamaConfig, mesh: Mesh, optimizer=None,
                    zero_stage: Optional[int] = None):
    """Build the jitted SPMD train step (fwd+bwd+adamw) over ``mesh``.

    Returns (step_fn, init_fn). ``init_fn(key)`` places params and
    optimizer state sharded on the mesh per ``train_state_specs``;
    ``step_fn(state, batch)`` is one update (state donated — params and
    optimizer buffers are updated in place, never doubly resident).

    zero_stage (reference: fleet group-sharded stages,
    dygraph_sharding_optimizer.py:48 / group_sharded_stage3.py). A
    caller need set nothing: the default, ``None``, goes by the mesh —
    stage 1 where its dp degree is above 1 (and the step is not
    pipelined), stage 0 where there is nothing to shard over (dp 1:
    the program is the one stage 0 always gave).
      0 — optimizer state inherits the param (tp/pp) sharding only;
          every weight gradient is all-reduced over dp where the
          backward pass makes it (for a layer: synchronously, at the
          tail of each iteration of the backward loop) and every
          replica runs the whole update. Set explicitly on a dp > 1
          mesh it is the REFERENCE path the sharded update is tested
          against, not a mode to train in.
      1 — gradients, moments and update sharded over dp
          (``zero_param_specs``; ZeRO-1/2). INSIDE the backward loop
          each of a layer's weight gradients is reduce-scattered over
          dp at the matmul that makes it (an all-reduce is a
          reduce-scatter plus an all-gather; only the first half stays
          in the loop, in the form the TPU compiler fuses onto the
          matmul); the stacked gradients leave the loop dp-sharded, the
          optimizer updates each replica's shard against moments laid
          out the same way, and the updated parameters are all-gathered
          once, after the update. Same sums, same dtype, same AdamW as
          stage 0. The pipelined steps (``pp_stages > 1``) keep their
          own gradient path: there the moments' layout alone asks for
          the scatter.
      2 — the same program as 1 (the gradients of stage 1 already
          arrive reduce-scattered; asserted on HLO in tests).
      3 — parameters themselves stored dp-sharded too, gathered for
          the forward and backward passes (ZeRO-3); the update as in 1.
    """
    # a ``with``, not a decorator: no frame is added beneath the build
    # (what it imports and traces is sensitive to its stack depth:
    # core/stack_anchor.py)
    with setup_span("train.setup.build"):
        import optax
        if optimizer is None:
            optimizer = default_train_optimizer()
        zero_stage = _zero_stage_of(cfg, mesh, zero_stage)

        use_1f1b = cfg.pp_stages > 1 and cfg.pp_schedule in PP_SCHEDULES
        if cfg.pp_schedule not in ("gpipe",) + tuple(PP_SCHEDULES):
            raise ValueError(
                f"pp_schedule must be one of "
                f"{('gpipe',) + tuple(PP_SCHEDULES)}, got "
                f"{cfg.pp_schedule!r}")

        @in_setup_span("train.setup.init", ready=True)
        def init_fn(key):
            specs = train_state_specs(cfg, mesh, optimizer, zero_stage)
            params = jax.tree_util.tree_map(
                lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
                init_params(cfg, key), specs["params"])
            # moments are born directly in their declared (possibly
            # dp-sharded) layout: optimizer.init on unsharded params would
            # transiently hold 2x full param bytes replicated per device —
            # the exact peak ZeRO stages exist to avoid
            opt_shardings = jax.tree_util.tree_map(
                lambda sp: NamedSharding(mesh, sp), specs["opt"],
                is_leaf=lambda x: isinstance(x, P))
            opt_state = jax.jit(optimizer.init,
                                out_shardings=opt_shardings)(params)
            return {"params": params, "opt": opt_state,
                    "step": jax.device_put(
                        jnp.zeros((), jnp.int32),
                        NamedSharding(mesh, specs["step"]))}

        # ZeRO-3 rebuild-on-forward (group_sharded_stage3.py): compute runs
        # on params gathered back to their tp/pp-only layout; only STORAGE
        # (the state between steps) is dp-sharded. Besides being the
        # reference semantics, this keeps dp-sharded weights out of the
        # differentiated layer scan, which the CPU SPMD partitioner
        # miscompiles (fwd+bwd loss drifts 3e-3 from the f64 reference —
        # pinned by tests/test_zero_sharding.py numerics tests).
        fwd_pspecs = param_specs(cfg)
        # the dp shards the update runs on (None: every replica updates the
        # whole of its tp shard, as stage 0 and dp 1 do)
        shard_specs = (zero_param_specs(cfg, mesh.shape["dp"])
                       if zero_stage >= 1 and mesh.shape.get("dp", 1) > 1
                       else None)
        stored_pspecs = shard_specs if zero_stage >= 3 else fwd_pspecs
        # the pipelined gradient paths (grads_1f1b, forward_pipelined) are
        # left as they are: their reductions stand where GSPMD puts them
        grad_specs = shard_specs if cfg.pp_stages == 1 else None

        def _constrain(params, specs):
            return jax.tree_util.tree_map(
                lambda x, sp: lax.with_sharding_constraint(
                    x, NamedSharding(mesh, sp)), params, specs)

        @partial(jax.jit, donate_argnums=(0,))
        def step_fn(state, batch):
            params = state["params"]
            if zero_stage >= 3:
                params = _constrain(params, fwd_pspecs)
            with jax.named_scope("loss"):
                if use_1f1b:
                    loss, grads = grads_1f1b(params, batch, cfg, mesh)
                elif grad_specs is not None:
                    loss, grads = jax.value_and_grad(_loss_with_sharded_grads)(
                        params, batch, cfg, mesh, grad_specs)
                else:
                    loss, grads = jax.value_and_grad(loss_fn)(
                        params, batch, cfg, mesh)
            with jax.named_scope("optimizer"):
                if shard_specs is not None:
                    # each replica's slice of what it already holds
                    params = _constrain(params, shard_specs)
                updates, opt = optimizer.update(grads, state["opt"], params)
                params = optax.apply_updates(params, updates)
            if shard_specs is not None:
                # stage 1/2: one all-gather a leaf, behind its update
                params = _constrain(params, stored_pspecs)
            return {"params": params, "opt": opt,
                    "step": state["step"] + 1}, loss

    return step_fn, init_fn


# ---------------------------------------------------------------------------
# decode: KV cache + generate
# ---------------------------------------------------------------------------
# Reference capability: the fused decode attention + cache machinery
# (paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu,
# masked_multihead_attention_kernel.cu) behind paddle.incubate fused
# generation. TPU-native shape: the cache is a [L, B, S_max, Hkv, Dh]
# pytree updated with lax.dynamic_update_slice inside one jitted step;
# prefill reuses the flash kernel on the un-padded prompt, decode steps
# run a masked dense attention over the cache (T=1 queries cannot fill
# the MXU; the op is bandwidth-bound either way).


def init_kv_cache(cfg: LlamaConfig, batch_size: int, max_len: int):
    """Empty per-layer K/V cache, layers stacked on a leading axis."""
    L, Hkv, Dh = cfg.num_hidden_layers, cfg.num_key_value_heads, cfg.head_dim
    shape = (L, batch_size, max_len, Hkv, Dh)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype)}


def _cached_attention(q, ck, cv, pos0, cfg: LlamaConfig):
    """q [B,T,H,Dh] against the full cache [B,S,Hkv,Dh]; query at
    position pos0+t attends to keys at positions <= pos0+t.

    GQA is a grouped einsum against the UN-repeated cache — decode is
    bandwidth-bound, so materialising an H-head copy of the cache would
    amplify its traffic H/Hkv-fold per step."""
    B, T, H, Dh = q.shape
    S, Hkv = ck.shape[1], ck.shape[2]
    G = H // Hkv
    qg = q.reshape(B, T, Hkv, G, Dh)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, ck) / np.sqrt(Dh)
    key_pos = jnp.arange(S)[None, :]                       # [1, S]
    q_pos = pos0 + jnp.arange(T)[:, None]                  # [T, 1]
    mask = key_pos <= q_pos                                # [T, S]
    scores = jnp.where(mask, scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgts,bskd->btkgd", probs, cv)
    return o.reshape(B, T, H, Dh)


def forward_with_cache(params, tokens, cache, pos0, cfg: LlamaConfig):
    """tokens [B, T] at absolute positions pos0..pos0+T-1 -> (logits of
    the LAST position [B, V], updated cache). Used for both prefill
    (T = prompt length, pos0 = 0) and decode steps (T = 1)."""
    B, T = tokens.shape
    h = params["embed"].astype(cfg.dtype)[tokens]
    positions = pos0 + jnp.broadcast_to(jnp.arange(T), (B, T))
    is_prefill = isinstance(pos0, int) and pos0 == 0

    def body(h, xs):
        lp, ck, cv = xs
        cell = {}

        def attn_fn(q, k, v):
            ck2 = lax.dynamic_update_slice(
                ck, k.astype(ck.dtype), (0, pos0, 0, 0))
            cv2 = lax.dynamic_update_slice(
                cv, v.astype(cv.dtype), (0, pos0, 0, 0))
            cell["ck"], cell["cv"] = ck2, cv2
            if is_prefill:
                # prompt: plain causal attention over the fresh keys —
                # the flash kernel path, no cache-length masking needed
                from ..ops.pallas.flash_attention import (
                    flash_attention as _fa)
                fa = cfg.use_flash_attention
                impl = (fa if isinstance(fa, str)
                        else ("auto" if fa else "dense"))
                return _fa(q, k, v, causal=True, impl=impl)
            return _cached_attention(q, ck2, cv2, pos0, cfg)

        h = _block(lp, h, positions, cfg, attn_fn)
        return h, (cell["ck"], cell["cv"])

    h, (ck_new, cv_new) = lax.scan(
        body, h, (params["layers"], cache["k"], cache["v"]))
    h = rms_norm(h[:, -1], params["final_norm"], cfg.rms_norm_eps)
    logits = _mm(h, params["lm_head"])
    return logits.astype(jnp.float32), {"k": ck_new, "v": cv_new}


def sample_logits(logits, key, temperature: float = 1.0,
                  top_p: float = 1.0, top_k: int = 0):
    """[B, V] logits -> [B] token ids (greedy when temperature == 0)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with mass >= top_p; the top-1 token is
        # always kept (top_p=0.0 must degrade to greedy, not to
        # full-distribution sampling)
        keep = (cum - probs) < top_p
        keep = keep.at[:, 0].set(True)
        # cutoff = SMALLEST kept logit (min, not max — the max would mask
        # everything below the argmax and silently degenerate to greedy)
        cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1)
        logits = jnp.where(logits < cutoff[:, None], -1e30, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def _decode_loop(fwd_cache_fn, init_cache_fn, params, prompt,
                 max_new_tokens: int, temperature, top_p, top_k, key,
                 eos_token_id):
    """Shared autoregressive decode driver (llama + qwen2_moe): prefill
    via ``fwd_cache_fn(params, tokens, cache, pos0)``, then a scan of
    single-token steps with EOS latching. Returns prompt+continuation."""
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, "
                         f"got {max_new_tokens}")
    B, T0 = prompt.shape
    key = key if key is not None else jax.random.PRNGKey(0)
    cache = init_cache_fn(B, T0 + max_new_tokens)
    logits, cache = fwd_cache_fn(params, prompt, cache, 0)
    key, sub = jax.random.split(key)
    tok = sample_logits(logits, sub, temperature, top_p, top_k)
    done = (jnp.zeros((B,), bool) if eos_token_id is None
            else tok == eos_token_id)

    def step(carry, _):
        tok, cache, pos, key, done = carry
        logits, cache = fwd_cache_fn(params, tok[:, None], cache, pos)
        key, sub = jax.random.split(key)
        nxt = sample_logits(logits, sub, temperature, top_p, top_k)
        if eos_token_id is not None:
            nxt = jnp.where(done, eos_token_id, nxt)
            done = done | (nxt == eos_token_id)
        return (nxt, cache, pos + 1, key, done), tok

    (last, _, _, _, _), toks = lax.scan(
        step, (tok, cache, jnp.int32(T0), key, done),
        None, length=max_new_tokens - 1)
    return jnp.concatenate(
        [prompt, jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1)


def generate(params, prompt, cfg: LlamaConfig, max_new_tokens: int,
             *, temperature: float = 0.0, top_p: float = 1.0,
             top_k: int = 0, key=None, eos_token_id: Optional[int] = None):
    """Autoregressive decode with a KV cache.

    prompt: int32 [B, T0]. Returns [B, T0 + max_new_tokens] (prompt +
    continuation; positions after EOS repeat EOS when eos_token_id set).
    """
    return _decode_loop(
        lambda p, t, c, pos: forward_with_cache(p, t, c, pos, cfg),
        lambda B, L: init_kv_cache(cfg, B, L),
        params, prompt, max_new_tokens, temperature, top_p, top_k, key,
        eos_token_id)


# ---------------------------------------------------------------------------
# paged decode: block-table KV cache
# ---------------------------------------------------------------------------
# Reference: block_multi_head_attention (paged KV decode,
# paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu +
# python/paddle/incubate/nn/functional/block_multihead_attention.py).
# TPU shape: per-layer page pools [L, Hkv, P, ps, Dh] + shared tables,
# written with masked scatters; attention reads only each sequence's
# valid pages (inference/paged_kv.py — pallas kernel on TPU). Mixed-
# length batches stop paying the dense cache's B*max_len traffic.


def prefill_paged(params, tokens, lengths, cfg: LlamaConfig,
                  max_new_tokens: int, page_size: int = 16,
                  attn_impl: str = "auto"):
    """Ragged prefill: ``tokens [B, T0]`` right-padded, ``lengths [B]``
    valid counts. Builds the paged cache (prompt pages by PURE RESHAPE —
    measured: per-sequence page scatters cost ~14 ms/step on TPU — plus
    an empty dense tail for generated tokens) and returns (logits at
    each sequence's LAST valid position ``[B, V]``, cache)."""
    from ..inference.paged_kv import prompt_pages_from_dense
    from ..ops.pallas.flash_attention import flash_attention as _fa
    B, T0 = tokens.shape
    Hkv, Dh = cfg.num_key_value_heads, cfg.head_dim
    lengths = jnp.asarray(lengths, jnp.int32)
    h = params["embed"].astype(cfg.dtype)[tokens]
    positions = jnp.broadcast_to(jnp.arange(T0), (B, T0))
    if attn_impl != "auto":
        impl = attn_impl  # explicit override wins (decode honors it too)
    else:
        fa = cfg.use_flash_attention
        impl = fa if isinstance(fa, str) else ("auto" if fa else "dense")

    def body(h, lp):
        cell = {}

        def attn_fn(q, k, v):
            kp, vp, tables = prompt_pages_from_dense(
                k.astype(cfg.dtype), v.astype(cfg.dtype), page_size)
            cell["kp"], cell["vp"], cell["tables"] = kp, vp, tables
            # causal flash over the fresh prompt keys; pad positions
            # compute garbage that is never read (beyond-length pages
            # are masked by the kernel's length mask, their logits are
            # discarded)
            return _fa(q, k, v, causal=True, impl=impl)

        h = _block(lp, h, positions, cfg, attn_fn)
        return h, (cell["kp"], cell["vp"], cell["tables"])

    h, (k_pages, v_pages, tables) = lax.scan(body, h, params["layers"])
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    idx = jnp.maximum(lengths - 1, 0)[:, None, None]
    h_last = jnp.take_along_axis(h, idx, axis=1)[:, 0]     # [B, D]
    logits = _mm(h_last, params["lm_head"])
    L = cfg.num_hidden_layers
    nt = max(max_new_tokens, 1)
    cache = {"k_pages": k_pages, "v_pages": v_pages,
             "tables": tables[0],        # identical across layers
             "prompt_lens": lengths,
             "k_tail": jnp.zeros((L, B, nt, Hkv, Dh), cfg.dtype),
             "v_tail": jnp.zeros((L, B, nt, Hkv, Dh), cfg.dtype),
             "n_tail": jnp.zeros((), jnp.int32)}
    return logits.astype(jnp.float32), cache


def _decode_paged_step(params, tok, cache, cfg: LlamaConfig,
                       attn_impl: str = "auto"):
    """One paged decode step: ``tok [B]`` -> (logits ``[B, V]``, cache).

    The token is appended to the dense TAIL (one lockstep
    dynamic_update_slice — no page scatter); attention merges the
    paged prompt with the live tail (paged_attention_with_tail)."""
    from ..inference.paged_kv import paged_attention_with_tail
    lens0 = cache["prompt_lens"]
    n = cache["n_tail"]
    h = params["embed"].astype(cfg.dtype)[tok[:, None]]     # [B, 1, D]
    positions = (lens0 + n)[:, None]

    def body(h, xs):
        lp, kp, vp, kt, vt = xs
        cell = {}

        def attn_fn(q, k, v):
            kt2 = lax.dynamic_update_slice(
                kt, k.astype(kt.dtype), (0, n, 0, 0))
            vt2 = lax.dynamic_update_slice(
                vt, v.astype(vt.dtype), (0, n, 0, 0))
            cell["kt"], cell["vt"] = kt2, vt2
            o = paged_attention_with_tail(
                q[:, 0], kp, vp, lens0, cache["tables"], kt2, vt2,
                n + 1, impl=attn_impl)
            return o[:, None].astype(q.dtype)

        h = _block(lp, h, positions, cfg, attn_fn)
        return h, (cell["kt"], cell["vt"])

    h, (kt_new, vt_new) = lax.scan(
        body, h, (params["layers"], cache["k_pages"], cache["v_pages"],
                  cache["k_tail"], cache["v_tail"]))
    h = rms_norm(h[:, 0], params["final_norm"], cfg.rms_norm_eps)
    logits = _mm(h, params["lm_head"])
    cache = dict(cache, k_tail=kt_new, v_tail=vt_new, n_tail=n + 1)
    return logits.astype(jnp.float32), cache


def generate_paged(params, prompt, lengths, cfg: LlamaConfig,
                   max_new_tokens: int, *, page_size: int = 16,
                   temperature: float = 0.0, top_p: float = 1.0,
                   top_k: int = 0, key=None,
                   eos_token_id: Optional[int] = None,
                   attn_impl: str = "auto"):
    """Batched autoregressive decode over the paged KV cache.

    prompt: int32 ``[B, T0]`` right-padded; lengths: valid counts
    ``[B]``. Returns the ``[B, max_new_tokens]`` continuations (ragged
    prompts make a concatenated return ill-defined; callers splice at
    ``lengths[b]``).
    """
    B, T0 = prompt.shape
    lengths = jnp.asarray(lengths, jnp.int32)
    key = key if key is not None else jax.random.PRNGKey(0)
    logits, cache = prefill_paged(params, prompt, lengths, cfg,
                                  max_new_tokens, page_size, attn_impl)
    key, sub = jax.random.split(key)
    tok = sample_logits(logits, sub, temperature, top_p, top_k)
    done = (jnp.zeros((B,), bool) if eos_token_id is None
            else tok == eos_token_id)

    def step(carry, _):
        tok, cache, key, done = carry
        logits, cache = _decode_paged_step(params, tok, cache, cfg,
                                           attn_impl)
        key, sub = jax.random.split(key)
        nxt = sample_logits(logits, sub, temperature, top_p, top_k)
        if eos_token_id is not None:
            nxt = jnp.where(done, eos_token_id, nxt)
            done = done | (nxt == eos_token_id)
        return (nxt, cache, key, done), tok

    (last, _, _, _), toks = lax.scan(step, (tok, cache, key, done),
                                     None, length=max_new_tokens - 1)
    return jnp.concatenate([jnp.moveaxis(toks, 0, 1), last[:, None]],
                           axis=1)


# ---------------------------------------------------------------------------
# serving: this family's cache and layer walk
# ---------------------------------------------------------------------------
# What the engine and the shared tick (``models/serving_tick.py``) ask of
# a family is ONE record, ``SERVING`` (``models/layer_walk.py:
# ServingFamily``; this module's stands at its end). The block math is
# _block — the same single source of truth the training and fused-scan
# decode paths use.


def init_serving_pages(cfg, total_pages: int, page_size: int,
                       max_batch: int = 0, max_span: int = 1):
    """The serving cache pytree: layer-stacked page pools ``[L, Hkv, P,
    ps, Dh]`` (page 0 = trash). ``max_batch`` (the slots) sizes what a
    layer kind keeps a slot and ``max_span`` a window layer's ring; a
    model of pages alone has no use for either."""
    L, Hkv, Dh = (cfg.num_hidden_layers, cfg.num_key_value_heads,
                  cfg.head_dim)
    shape = (L, Hkv, total_pages, page_size, Dh)
    return {"k_pages": jnp.zeros(shape, cfg.dtype),
            "v_pages": jnp.zeros(shape, cfg.dtype)}


def _walk_one_kind(params, h, cache, meta, cfg, tq, attn_impl):
    """The layer walk of a model whose layers are ONE kind, each holding
    K and V: ``params['layers']`` scanned, the two stacked pools in the
    carry. A walk is ``walk(params, h [1, T, D], cache, meta, cfg, tq,
    attn_impl) -> (h, cache)`` over the model's WHOLE cache pytree;
    ``models/serving_tick.py`` owns everything around it. A model whose
    layers follow a pattern of kinds brings its own
    (``models/lfm2_moe.py``)."""
    k_pages, v_pages = cache["k_pages"], cache["v_pages"]
    plan = tick_plan(meta, tq, cfg.num_attention_heads, k_pages)
    positions = meta["tok_pos"][None]
    at = kv_rows(meta, k_pages)

    def body(carry, xs):
        h, kp, vp = carry
        lp, layer = xs
        cell = {}

        def attn_fn(q, k, v):
            o, cell["kp"], cell["vp"] = paged_kv_attend(
                q, k, v, kp, vp, layer, meta, at, plan, tq, attn_impl)
            return o

        h = _block(lp, h, positions, cfg, attn_fn)
        return (h, cell["kp"], cell["vp"]), None

    # the scan CARRIES the stacked pools: the donated parameter is the
    # loop's initial carry and its final carry is the result, so the
    # pool is one buffer for the whole tick and a layer touches only
    # the pages it writes and reads. An operation under bare ``layers``
    # is the scan's own: the slicing of a layer's weights
    with jax.named_scope("layers"):
        (h, kp_new, vp_new), _ = lax.scan(
            body, (h, k_pages, v_pages),
            (params["layers"],
             jnp.arange(k_pages.shape[0], dtype=jnp.int32)))
    return h, {"k_pages": kp_new, "v_pages": vp_new}


def make_batch(cfg: LlamaConfig, batch_size: int, seq_len: int, mesh: Mesh,
               key=None):
    """Synthetic next-token batch, dp-sharded."""
    key = key if key is not None else jax.random.PRNGKey(0)
    toks = jax.random.randint(key, (batch_size, seq_len + 1), 0,
                              cfg.vocab_size, dtype=jnp.int32)
    cp = "cp" if mesh.shape.get("cp", 1) > 1 else None
    sh = NamedSharding(mesh, P("dp", cp))
    return {"tokens": jax.device_put(toks[:, :-1], sh),
            "labels": jax.device_put(toks[:, 1:], sh)}


# ---------------------------------------------------------------------------
# the tree a serving engine holds
# ---------------------------------------------------------------------------
# Kept at the END of the file: a Mosaic kernel's compile-cache key holds
# its call site's line numbers, so a line added above a call site costs
# every cell that runs it one cold start.

# a layer's q / k / v projection held OUTPUT-MAJOR ``[L, O, D]`` stands
# under its name with this suffix (``wq_om``), in a serving tree only
OUTPUT_MAJOR = "_om"
_RELAID = ("wq", "wk", "wv")


def _proj(x, lp, name):
    """``x @ W`` of the layer's projection ``name``, as the tree holds
    it: output-major (``lp[name + OUTPUT_MAJOR]``, ``[O, D]``) it is
    contracted over its LAST axis, where the chip's compiler reads the
    layer's slice of the stack as it lies; input-major or int8
    (``lp[name]``: ``init_params``' tree, the trainers', an ``Int8Weight``)
    it is ``_mm``. In front of a product of a tick's handful of rows the
    compiler wants the weight the other way round: held ``[D, O]`` it
    copied every layer's three slices in every tick (``PERF.md`` §6,
    PR 49)."""
    w = lp.get(name + OUTPUT_MAJOR)
    if w is None:
        return _mm(x, lp[name])
    return jnp.einsum("btd,od->bto", x, w)


@jax.jit
def _output_major(w):
    return jnp.swapaxes(w, -1, -2)


def serving_params(params, cfg):
    """The tree a serving engine holds for ``params`` (the record's
    ``params``; ``ServingEngine`` calls it once, at construction): a
    NEW tree that shares every leaf with the caller's but the dense
    ``layers.wq`` / ``.wk`` / ``.wv`` stacks, which it holds
    output-major ``[L, O, D]`` under ``wq_om`` / ``wk_om`` /
    ``wv_om`` (one transpose a stack, made here). ``_proj`` reads
    either. The caller's tree is not touched; a stack that is no dense
    array (``Int8Weight``) stays as and where it is."""
    layers = dict(params["layers"])
    for name in _RELAID:
        if not hasattr(layers[name], "dequant_matmul"):
            layers[name + OUTPUT_MAJOR] = _output_major(layers.pop(name))
    return dict(params, layers=layers)


SERVING = ServingFamily(walk=_walk_one_kind, init_pages=init_serving_pages,
                        params=serving_params)
