"""Flagship serving-graph targets for the lint passes.

One place that knows how to hand each flagship program to the
analysers: abstract-trace (``jax.make_jaxpr`` over ShapeDtypeStructs —
nothing allocates, nothing compiles) the serving step functions of a
model family exactly as the engine jits them (the cache pytree of the
family's ``SERVING.init_pages`` through ``models/serving_tick.py``'s
``serving_tick`` / ``serving_tick_block``), tagged with the call-site
facts the passes need (compute dtype, donated pool outputs,
slot/step counts, engine geometry for the recompile pass, pp stage
grouping for the collective pass).

The geometries here are the FLAGSHIP shapes — the ones the engine
tests and serving_bench drive on the CPU mesh — shrunk to tiny model
dims (linting is structural; hidden size changes nothing a pass looks
at, while tracing a 4-layer model keeps the CLI under a second).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from .framework import GraphTarget, trace_graph
from .recompile import ServingGeometry, tick_budget

__all__ = ["engine_geometry", "serving_targets", "pp_stage_targets",
           "rewrite_targets", "ragged_walk_model", "FLAGSHIP_MODELS"]

FLAGSHIP_MODELS = ("llama", "qwen2_moe")


def ragged_walk_model(*, kv_len: int, page_size: int, head_dim: int,
                      num_kv_heads: int, num_heads: int,
                      num_layers: int, dtype_bytes: int = 2,
                      kv_tile_pages=None) -> Dict[str, Any]:
    """Analytic flops/bytes of ONE slot's decode-step KV walk through
    the ragged kernel (ops/pallas/ragged_paged_attention.py) — the
    model decode_profile's long-context ceiling prices the walk with.

    The walk streams each live page exactly once a slot (one strided
    copy a pool moves its KV heads), so HBM bytes are
    ``2 · L · ceil(kv_len/ps) · ps · Dh`` per kv head; its VMEM
    residency is O(heads · tile) (``vmem_scratch_bytes`` of a grid
    step holding the slot's heads), whatever the table's width — which is why context length is capped by
    bandwidth, not by on-chip memory. ``kv_tile_pages`` None: the
    tile the kernel's geometry selection picks."""
    from ..ops.pallas.ragged_paged_attention import (
        default_kv_tile_pages, vmem_scratch_bytes)
    pages = -(-int(kv_len) // int(page_size))
    kv_bytes = (2 * num_layers * num_kv_heads * pages * page_size
                * head_dim * dtype_bytes)
    # decode q_len=1: scores + weighted sum, 2 dots of [1, Dh] x
    # [Dh/., kv] per head
    flops = 2 * 2 * num_layers * num_heads * int(kv_len) * head_dim
    dtype = jnp_dtype_of(dtype_bytes)
    if kv_tile_pages is None:
        kv_tile_pages = default_kv_tile_pages(pages, page_size, head_dim,
                                              dtype)
    return {
        "kv_len": int(kv_len), "pages": pages,
        "kv_bytes_per_step": kv_bytes, "attn_flops_per_step": flops,
        "kv_tile_pages": int(kv_tile_pages),
        "kv_tiles": -(-pages // max(int(kv_tile_pages), 1)),
        "vmem_scratch_bytes": vmem_scratch_bytes(
            pages, page_size, head_dim, dtype,
            kv_tile_pages=kv_tile_pages,
            rows=num_heads // num_kv_heads, kv_heads=num_kv_heads),
    }


def jnp_dtype_of(dtype_bytes: int):
    """bytes-per-element -> the matching pool dtype (the walk model's
    inputs are geometry numbers, not arrays)."""
    import jax.numpy as jnp
    return {1: jnp.int8, 2: jnp.bfloat16, 4: jnp.float32}[int(dtype_bytes)]


def engine_geometry(*, page_size: int, max_prompt_len: int,
                    max_new_tokens_cap: int,
                    prefill_chunk: Optional[int] = None,
                    prompt_buckets=None,
                    max_batch: int = 8,
                    decode_block: int = 1,
                    spec_k: int = 0) -> ServingGeometry:
    """The ``ServingGeometry`` a ``ServingEngine(**same_kwargs)`` would
    run — the same arithmetic as the engine ctor, computable without
    building pools or starting workers (tests pin the two against each
    other so this cannot drift). Prefix attach is exact (attach size
    is device data, not a compile shape), so the prefix cache plays no
    part in it, and the program set is keyed by packed token width
    (``enumerate_tick_programs``)."""
    from ..serving.engine import _default_buckets
    buckets = sorted(set(int(b) for b in (
        prompt_buckets or _default_buckets(max_prompt_len))))
    pages_per_slot = -(-(buckets[-1] + max_new_tokens_cap - 1)
                       // page_size)
    return ServingGeometry(
        page_size=page_size, pages_per_slot=pages_per_slot,
        buckets=buckets, prefill_chunk=prefill_chunk,
        max_batch=int(max_batch),
        decode_block=int(decode_block), spec_k=int(spec_k))


def _get_model(name: str):
    """``(module, tiny config)`` of the serving family ``name``."""
    import dataclasses

    from ..models import SERVING_FAMILIES, resolve_family
    mod = resolve_family(name)
    cfg_cls = getattr(mod, SERVING_FAMILIES[name])
    off = {"use_flash_attention": False, "remat": False}
    fields = {f.name for f in dataclasses.fields(cfg_cls)}
    return mod, cfg_cls.tiny(**{k: v for k, v in off.items()
                                if k in fields})


def _abstract_cache(mod, cfg, slots: int, pps: int, page_size: int,
                    max_span: int = 1):
    """The family's cache pytree as the engine has its record's
    ``init_pages`` build it (``slots * pps`` pages and the trash page;
    ``max_span`` rows a slot a tick, which sizes a window ring),
    abstractly."""
    import jax
    return jax.eval_shape(lambda: mod.SERVING.init_pages(
        cfg, slots * pps + 1, page_size, slots, max_span))


def _donated(cache, first: int):
    """Result positions of the slots' current tokens (at ``first``) and
    of the cache's leaves after them: they are the LAST results of both
    step functions, and the engine rebinds them (the cache donated,
    whole), so they never cross to the host."""
    import jax
    return tuple(range(
        first, first + 1 + len(jax.tree_util.tree_leaves(cache))))


def _sampling_meta(slots: int) -> Dict[str, Any]:
    """The fused in-graph sampler's per-slot DATA: the engine passes
    these with every tick, so the linted graphs carry the sampling head
    exactly as production compiles it."""
    import jax
    import jax.numpy as jnp
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    return {"temp": sds((slots,), jnp.float32),
            "top_p": sds((slots,), jnp.float32),
            "top_k": sds((slots,), i32),
            "key": sds((slots, 2), jnp.uint32),
            "produced": sds((slots,), i32)}


def _tick_meta(T: int, slots: int, pps: int) -> Dict[str, Any]:
    """``serving_tick``'s ``meta`` at packed width ``T``, abstractly."""
    import jax
    import jax.numpy as jnp
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    return {"tok_slot": sds((T,), i32), "tok_pos": sds((T,), i32),
            "tok_page": sds((T,), i32), "tok_off": sds((T,), i32),
            "tok_qoff": sds((T,), i32), "q_len": sds((slots,), i32),
            "kv_len": sds((slots,), i32), "last": sds((slots,), i32),
            "tables": sds((slots, pps), i32),
            "tail_live": sds((slots,), jnp.bool_),
            "cur_tok": sds((slots,), i32), **_sampling_meta(slots)}


def serving_targets(model: str = "llama", *, slots: int = 4,
                    page_size: int = 4, max_prompt_len: int = 16,
                    max_new_tokens_cap: int = 16,
                    prefill_chunk: int = 8,
                    decode_block: int = 4,
                    spec_k: int = 3) -> List[GraphTarget]:
    """GraphTargets for one family's flagship serving programs, traced
    through what the engine calls (the cache pytree of the family's
    ``SERVING.init_pages`` abstractly, then ``models/serving_tick.py``'s
    ``serving_tick`` / ``serving_tick_block`` over the family's
    record): the tick at the mixed packed width,
    the fused decode block (the ONLY pure-decode program: sampling
    slots ride it through the fused in-graph sampler, whose per-slot
    temperature/top-k/top-p/key/produced state is traced here exactly
    as the engine passes it) and ``generate_paged`` (the offline
    batched decode, where the family has one), plus the engine geometry
    riding the block target for the recompile-hazard pass — and, where
    the family can verify (no layer kind keeps per-slot rows a rejected
    draft could not roll back), the speculative VERIFY tick
    (``serving_tick[verify]`` at the all-slots-drafting width, spec_k
    static, draft/acceptance geometry as device data) carrying the
    SPECULATIVE engine geometry, so the recompile pass statically
    proves the draft/verify program set keeps the
    ≤2-programs-per-width-bucket invariant too."""
    import jax
    import jax.numpy as jnp

    from ..models.serving_tick import serving_tick, serving_tick_block

    mod, cfg = _get_model(model)
    family = mod.SERVING
    geom = engine_geometry(
        page_size=page_size, max_prompt_len=max_prompt_len,
        max_new_tokens_cap=max_new_tokens_cap,
        prefill_chunk=prefill_chunk, max_batch=slots,
        decode_block=decode_block)
    pps = geom.pages_per_slot
    meta: Dict[str, Any] = {}
    if model == "qwen2_moe":
        # the router GEMM is fp32 BY DESIGN (stable softmax over expert
        # logits — see qwen2_moe.init_params): declare the island so
        # the dtype-drift pass pins every OTHER wide dot. The predicate
        # is shape-tight: only a projection onto the expert dim passes.
        n_e = cfg.num_experts
        meta["wide_dot_ok"] = (
            lambda lhs, rhs: rhs.shape and rhs.shape[-1] == n_e)

    given = mod.abstract_params(cfg)        # generate_paged's tree
    # the tree an ENGINE holds: the family's own where it brings one
    params = given if family.params is None else jax.eval_shape(
        lambda p: family.params(p, cfg), given)
    cache = _abstract_cache(mod, cfg, slots, pps, page_size,
                            tick_budget(geom))
    # a family that hands counts back beside its tokens (``counters``:
    # one more small result in front of the slots' tokens)
    counts = 1 if family.counters else 0

    sds = jax.ShapeDtypeStruct
    i32 = jnp.int32

    targets: List[GraphTarget] = []

    # --- the ragged tick at its mixed width ---------------------------
    # widths mirror enumerate_tick_programs: S+budget (mixed ticks).
    # The mixed tick carries prefill, which legitimately returns one
    # [S, V] logits row set per prompt completion — in_decode_loop
    # stays False so the host-pull budget (whose hot-path guard is the
    # block program below) does not charge it per step; the engine
    # pulls only the [S(,1+tail)] i32 token block whoever samples.
    budget = tick_budget(geom)
    T = slots + budget
    targets.append(trace_graph(
        f"{model}.serving_tick[mixed]",
        serving_tick,
        (params, sds((T,), i32), _tick_meta(T, slots, pps), cache),
        static_kwargs=dict(cfg=cfg, family=family, tq=budget,
                           attn_impl="dense"),
        compute_dtype=cfg.dtype, slots=slots,
        donated_outputs=_donated(cache, 2 + counts), meta=dict(meta)))

    # --- the speculative verify tick: drafted slots as ragged spans +
    # in-graph longest-prefix acceptance. Traced at the
    # all-slots-drafting width; the SPECULATIVE engine geometry rides
    # this target, so graph_lint proves the draft/verify program set
    # stays within the per-bucket bound (emitted as
    # serving_programs_spec in --json)
    if all(k.cache == "pages" for k in family.kinds(cfg)):
        spec_geom = engine_geometry(
            page_size=page_size, max_prompt_len=max_prompt_len,
            max_new_tokens_cap=max_new_tokens_cap,
            prefill_chunk=prefill_chunk, max_batch=slots,
            decode_block=decode_block, spec_k=spec_k)
        Tv = slots + slots * (1 + spec_k)
        ver_meta = dict(
            _tick_meta(Tv, slots, pps),
            ver_idx=sds((slots, 1 + spec_k), i32),
            draft_tok=sds((slots, spec_k), i32),
            draft_len=sds((slots,), i32))
        targets.append(trace_graph(
            f"{model}.serving_tick[verify,spec_k={spec_k}]",
            serving_tick,
            (params, sds((Tv,), i32), ver_meta, cache),
            static_kwargs=dict(cfg=cfg, family=family,
                               tq=slots * (1 + spec_k), spec_k=spec_k,
                               attn_impl="dense"),
            compute_dtype=cfg.dtype, slots=slots,
            donated_outputs=_donated(cache, 3 + counts),
            meta=dict(meta, geometry=spec_geom)))

    # --- fused decode block: the per-tick hot program (greedy AND
    # sampling slots — the sampling state is a traced arg, exactly as
    # the engine passes it) --------------------------------------------
    def _block_with_sampling(p, tok, lens, tabs, cache_, samp):
        return serving_tick_block(
            p, tok, lens, tabs, cache_, cfg, family, decode_block,
            attn_impl="dense", sampling=samp)

    targets.append(trace_graph(
        f"{model}.serving_tick_block[k={decode_block}]",
        _block_with_sampling,
        (params, sds((slots,), i32), sds((slots,), i32),
         sds((slots, pps), i32), cache, _sampling_meta(slots)),
        compute_dtype=cfg.dtype, slots=slots,
        steps_per_call=decode_block, in_decode_loop=True,
        # outputs (toks, tok', cache'): only toks crosses to the host
        donated_outputs=_donated(cache, 1 + counts),
        meta=dict(meta, geometry=geom)))

    # --- offline batched decode: generate_paged ----------------------
    if hasattr(mod, "generate_paged"):
        B, T0, mnt = slots, max_prompt_len, max_new_tokens_cap
        targets.append(trace_graph(
            f"{model}.generate_paged[B={B}]",
            mod.generate_paged,
            (given, sds((B, T0), i32), sds((B,), i32)),
            static_kwargs=dict(cfg=cfg, max_new_tokens=mnt,
                               page_size=page_size, attn_impl="dense"),
            compute_dtype=cfg.dtype, slots=B, steps_per_call=mnt,
            in_decode_loop=True, meta=dict(meta)))
    return targets


def rewrite_targets(models=("llama",), *, slots: int = 4,
                    page_size: int = 4, max_prompt_len: int = 16,
                    max_new_tokens_cap: int = 16, decode_block: int = 4,
                    serving_pool: Optional[List[GraphTarget]] = None
                    ) -> List[GraphTarget]:
    """Flagship targets for the REWRITE suite (graph_lint --suite
    rewrite): per model, the fused decode block and the mixed tick
    — both traced with the fused norm/rope kernels OFF (the
    default off-TPU), so the jnp rmsnorm formulation the
    ``fused-rmsnorm`` substitution targets is really present — plus,
    for llama, the decode-width tick over ``quantize_for_decode``
    params traced with the UNFUSED dequantize-then-matmul idiom
    (``PADDLE_TPU_INT8_IMPL=unfused``), the seeded graph the
    ``int8-epilogue-fuse`` pass must fire on.

    Each target's ``meta['expect_rewrites']`` names the rewrites that
    MUST fire there — the suite errors if one does not, so the
    patterns cannot silently rot as the model code evolves.

    ``serving_pool``: already-traced serving targets (the lint suite's
    — same default geometry) to select from instead of re-tracing
    them, so ``graph_lint --suite all`` traces each flagship program
    once."""
    import os

    import jax
    import jax.numpy as jnp

    targets: List[GraphTarget] = []
    for m in models:
        pool = (serving_pool if serving_pool is not None
                else serving_targets(
                    m, slots=slots, page_size=page_size,
                    max_prompt_len=max_prompt_len,
                    max_new_tokens_cap=max_new_tokens_cap,
                    decode_block=decode_block))
        for t in pool:
            if not t.name.startswith(m + "."):
                continue
            if ("serving_tick_block" in t.name
                    or "serving_tick[mixed]" in t.name):
                # the tail (final norm → last-row gather → lm_head →
                # f32 cast) belongs to decode-tail-fuse; the per-layer
                # norms still fall through to the plain substitution
                t.meta["expect_rewrites"] = ("fused-rmsnorm",
                                             "decode-tail-fuse")
                targets.append(t)

    # --- int8: the decode-width tick over weight-only-quantized params,
    # traced with the un-fused dequant-matmul idiom (llama is the int8
    # flagship — skipped when the caller excluded llama) --------------
    if "llama" not in models:
        return targets
    from ..models.serving_tick import serving_tick
    from ..quantization.decode import quantize_for_decode
    mod, cfg = _get_model("llama")
    geom = engine_geometry(
        page_size=page_size, max_prompt_len=max_prompt_len,
        max_new_tokens_cap=max_new_tokens_cap)
    pps = geom.pages_per_slot
    qparams = jax.eval_shape(lambda: quantize_for_decode(
        mod.init_params(cfg, jax.random.PRNGKey(0)), cfg))
    cache = _abstract_cache(mod, cfg, slots, pps, page_size)
    prev = os.environ.get("PADDLE_TPU_INT8_IMPL")
    os.environ["PADDLE_TPU_INT8_IMPL"] = "unfused"
    try:
        t = trace_graph(
            "llama.serving_tick[int8-unfused]",
            serving_tick,
            (qparams, jax.ShapeDtypeStruct((slots,), jnp.int32),
             _tick_meta(slots, slots, pps), cache),
            static_kwargs=dict(cfg=cfg, family=mod.SERVING, tq=1,
                               attn_impl="dense"),
            compute_dtype=cfg.dtype, slots=slots, in_decode_loop=True,
            donated_outputs=_donated(cache, 2))
    finally:
        if prev is None:
            os.environ.pop("PADDLE_TPU_INT8_IMPL", None)
        else:
            os.environ["PADDLE_TPU_INT8_IMPL"] = prev
    t.meta["expect_rewrites"] = ("int8-epilogue-fuse", "fused-rmsnorm")
    targets.append(t)
    return targets


def pp_stage_targets(num_stages: int = 2, virtual_chunks: int = 2,
                     seq_len: int = 8, batch: int = 2
                     ) -> List[GraphTarget]:
    """One GraphTarget per pipeline stage chunk of the flagship llama
    pp path (the round-robin VPP partition feeding
    ``pipeline_train_1f1b``), grouped for the collective-consistency
    pass: every chunk program must issue the identical collective
    sequence or the lockstep schedule deadlocks/corrupts."""
    import jax
    import jax.numpy as jnp

    from ..models import llama as L
    from ..parallel.pipeline_1f1b import split_chunks_round_robin

    cfg = L.LlamaConfig.tiny(use_flash_attention=False, remat=False,
                             pp_stages=num_stages,
                             vpp_chunks=virtual_chunks)
    params = L.abstract_params(cfg)
    VS = num_stages * virtual_chunks
    x = jax.ShapeDtypeStruct((batch, seq_len, cfg.hidden_size),
                             cfg.dtype)

    def stage_fn(chunk_params, xm):
        return L._scan_layers(chunk_params, xm, cfg, None,
                              remat=False)

    targets = []
    for k in range(VS):
        # each stage traces ITS OWN chunk slice (abstract-indexed out
        # of the real round-robin split) — so a future heterogeneous
        # partition, or any chunk-dependent program difference, shows
        # up as a genuinely different jaxpr rather than the check
        # comparing VS copies of one trace against itself
        chunk_k = jax.eval_shape(
            lambda p, k=k: jax.tree_util.tree_map(
                lambda c: c[k],
                split_chunks_round_robin(
                    p, cfg.num_hidden_layers, num_stages,
                    virtual_chunks)),
            params["layers"])
        targets.append(trace_graph(
            f"llama.pp_stage_chunk[{k}/{VS}]", stage_fn, (chunk_k, x),
            compute_dtype=cfg.dtype,
            meta={"stage_group": f"llama.pp[{num_stages}x"
                                 f"{virtual_chunks}]",
                  "stage_count": VS}))
    return targets
