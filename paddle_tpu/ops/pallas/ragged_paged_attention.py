"""Authored ragged paged-attention Pallas kernel (one-launch serving tick).

Counterpart of the TPU serving kernel described in "Ragged Paged
Attention: A High-Performance and Flexible LLM Inference Kernel for
TPU" (PAPERS.md, arxiv 2604.15464) and of the reference's fused
block_multihead_attention path: ONE kernel launch computes attention
for a mixed batch of variable-length sequences — ragged prefill spans
(bottom-right causal within each sequence) and decode steps (q_len=1)
in the same grid — over per-slot page tables. Sequence geometry is
DATA, not shape: ``(q_len, kv_len, page_table)`` ride in as device
arrays (scalar-prefetched into SMEM), so any mix of chunked prefills,
warm-prefix attaches and decodes is one static XLA program. This is
what lets the serving engine drop its compile-geometry quantization
(chunk-width buckets, attach quanta) at the root.

Layout contract:

* ``q``: ``[S, Tq, H, Dh]`` — slot-major padded query spans. Slot
  ``s`` owns rows ``0..q_len[s]-1``; rows past ``q_len[s]`` (and whole
  slots with ``q_len[s] == 0``) are padding the kernel never reads
  into real outputs.
* ``k_pages``/``v_pages``: ``[Hkv, total_pages, page_size, Dh]`` — the
  shared serving pools. The span's OWN fresh KV must already be
  written into the pages (the step fn scatters before attending, like
  ``serving_decode_step``), so the kernel is purely paged: no separate
  current-chunk operand, no gathered-prefix concat.
* ``layer`` (optional): with it the pools are the serving tick's
  STACKED ones, ``[L, Hkv, total_pages, page_size, Dh]``, and the
  kernel reads that layer's pages where they lie (its page DMAs start
  from ``pages[layer, h, page]``; the index is one more
  scalar-prefetch operand). That is what lets the tick's layer scan
  CARRY the pools — one buffer from the program's parameter to its
  result — where slicing a layer out in front of the kernel copied a
  layer's pages per layer. There is one kernel body per walk: a 4-D
  pool enters as a one-layer stack read at layer 0. Off the kernel
  (the dense and packed formulations) the layer is sliced out in
  front of the same code as ever.
* ``kv_len[s]`` counts every key visible at the END of slot ``s``'s
  span (context + the span itself); query row ``t`` attends key
  positions ``0 .. kv_len[s]-q_len[s]+t`` — the bottom-right causal
  mask that makes a chunked prefill bitwise-equal to a whole-prompt
  one.
* ``tables``: ``[S, pages_per_slot]`` int32; entries past the covered
  range may be TRASH (0) — the kernel walks only
  ``ceil(kv_len/page_size)`` entries, so HBM traffic scales with the
  tokens actually cached, not the table width.

Grid ``(S, Hkv)``: each program DMAs its slot's valid pages into VMEM
scratch (all copies started, then awaited — pages overlap in flight),
computes the full masked score block ``[G·Tq, KV_max]`` in f32 and a
ONE-SHOT softmax. The one-shot formulation (not an online-softmax
accumulator) is deliberate: it makes the kernel bitwise-equal to the
dense-gather reference below, which is the verification story the
engine's exactness bar rests on (tests/test_ragged_attention.py). At
serving shapes ``KV_max = pages_per_slot · page_size`` fits VMEM
comfortably.

**Tiled flash combine (r16 — the long-context walk).** The one-shot
scratch is ``O(pages_per_slot · page_size)``, so max context is capped
by VMEM. Past that knee the kernel switches to a TILED walk (the
Ragged Paged Attention paper's formulation, arxiv 2604.15464): the
slot's live pages are walked in fixed ``kv_tile_pages``-sized tiles
with double-buffered DMA (tile ``t+1``'s copies start while tile ``t``
computes), carrying running max / denominator / accumulator in f32 —
VMEM scratch becomes ``O(tile)``, independent of ``pages_per_slot``,
so a 100k-token page table costs the same on-chip bytes as a 2k one.
Exactness discipline: the tiled KERNEL is bitwise-equal to the tiled
dense reference (the same ``_flash_tile`` math at two call sites —
the one-shot kernel's own pin, replayed), and tiled-vs-one-shot is
held to a measured ulp-at-row-scale bound (``TILED_ULP_BOUND`` /
``tiled_ulp_error``, the fused-rmsnorm measured-sweep contract style
from analysis/rewrite.py) — the flash combine reassociates the
softmax reductions, so bitwise is off the table by construction, and
the bound is what the tests enforce across the geometry grid. Selection is by geometry (``default_kv_tile_pages``):
one-shot stays the bitwise-pinned fast path while its K+V scratch
fits ``ONE_SHOT_VMEM_BUDGET``; the tiled walk takes over past the
knee. ``kv_tile_pages=`` overrides (0 forces one-shot).

Off-TPU the kernel runs in interpreter mode (CPU-testable, like the
int8/flash kernels); ``impl="dense"`` selects the reference gather
formulation with identical semantics — ``impl="auto"`` uses the kernel
on TPU and the reference elsewhere.
"""
from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from . import on_tpu as _on_tpu

__all__ = ["ragged_paged_attention", "ragged_paged_attention_reference",
           "ragged_paged_attention_packed", "default_kv_tile_pages",
           "vmem_scratch_bytes", "ONE_SHOT_VMEM_BUDGET",
           "TILED_ULP_BOUND", "tiled_ulp_error"]

_MASK = -1e30  # matches the repo's dense-attention mask value

# K+V VMEM scratch budget of the ONE-SHOT walk: past this the kernel
# auto-selects the tiled flash combine. 4 MiB leaves headroom for the
# q/out blocks and the compiler's own allocations inside ~16 MiB/core;
# at Dh=128/bf16 the knee sits at 8k KV tokens.
ONE_SHOT_VMEM_BUDGET = 4 * 2 ** 20
# default tile of the flash walk, in KV TOKENS (converted to pages by
# default_kv_tile_pages): big enough that the per-tile dot amortizes
# the DMA turnaround, small enough that double-buffered K+V scratch
# stays ~512 KiB at Dh=128/bf16. The kernel_bench ragged sweep is the
# measured A/B over this choice (the first entry of the KForge-style
# autotune loop, PAPERS.md 2606.02963).
DEFAULT_TILE_KV_TOKENS = 512
# tiled-vs-one-shot exactness contract (the fused-rmsnorm measured-
# sweep style, analysis/rewrite.py): the flash combine reassociates
# the softmax sum and rescales the accumulator per tile, so bitwise
# equality is structurally off the table. A PER-ELEMENT ulp bound is
# the wrong metric here and provably cannot hold: attention output
# components are weighted averages whose terms CANCEL, so a component
# can be 1e-4 of its slot's scale while both formulations carry
# O(scale) rounding — measured 35k "ulp" at such elements with the
# absolute error still ~1 ulp of the row scale. The contract is
# therefore ulp AT THE SLOT'S OUTPUT SCALE:
#
#     |tiled - oneshot|  <=  TILED_ULP_BOUND · eps(dtype) · linf(slot)
#
# (``tiled_ulp_error`` computes the left side in those units).
# Measured worst case across the tests/test_ragged_attention.py
# geometry grid — f32, both matmul precisions, mixed prefill+decode
# spans, non-dividing tiles, empty slots, input scales 0.01-10 —
# is 6.5; the contract pins <= 16 for headroom on untested shapes.
TILED_ULP_BOUND = 16


def tiled_ulp_error(got, ref) -> float:
    """Max error of ``got`` vs ``ref`` in units-in-the-last-place of
    each leading-axis row's (slot's) largest reference component —
    the tiled walk's contract metric (see TILED_ULP_BOUND). Inputs
    are same-shape float arrays, slot-major on axis 0."""
    got = np.asarray(got)
    ref = np.asarray(ref)
    axes = tuple(range(1, ref.ndim))
    linf = np.maximum(
        np.max(np.abs(ref), axis=axes, keepdims=True), 1e-30)
    eps = np.finfo(ref.dtype).eps
    return float((np.abs(got.astype(np.float64)
                         - ref.astype(np.float64))
                  / (eps * linf)).max())


def vmem_scratch_bytes(pages_per_slot: int, page_size: int,
                       head_dim: int, dtype=jnp.bfloat16,
                       kv_tile_pages: int = 0) -> int:
    """K+V VMEM scratch one grid program pins, straight from the
    kernels' ``scratch_shapes``: the one-shot walk holds the whole
    table (``2 · pps · ps · Dh``), the tiled walk two double-buffer
    tiles (``2 · 2 · tile · ps · Dh``) — independent of
    ``pages_per_slot``, which is the whole point. Shared by the
    kernel_bench sweep's ``vmem_scratch_bytes`` column and the
    decode_profile long-context ceiling."""
    item = jnp.dtype(dtype).itemsize
    if kv_tile_pages:
        return 2 * 2 * int(kv_tile_pages) * page_size * head_dim * item
    return 2 * int(pages_per_slot) * page_size * head_dim * item


def default_kv_tile_pages(pages_per_slot: int, page_size: int,
                          head_dim: int, dtype=jnp.bfloat16,
                          budget_bytes: int = ONE_SHOT_VMEM_BUDGET
                          ) -> int:
    """Geometry selection of the KV walk: 0 (one-shot — the
    bitwise-pinned fast path) while the one-shot K+V scratch fits the
    VMEM budget, else the default flash-combine tile in pages. The
    engine never chooses: ``serving_tick`` passes geometry through and
    this picks per (pages_per_slot, page_size, Dh, dtype)."""
    if vmem_scratch_bytes(pages_per_slot, page_size, head_dim,
                          dtype) <= budget_bytes:
        return 0
    return min(int(pages_per_slot),
               max(1, DEFAULT_TILE_KV_TOKENS // int(page_size)))


def _mxu_dot(a, b, dims):
    """Operand-dtype ``dot_general`` the way the MXU runs it: narrow
    operands, f32 accumulator, result rounded ONCE to the operand
    dtype. Mosaic refuses a 16-bit accumulator (``Expected matmul acc
    to be 32-bit``); rounding the f32 accumulator back keeps the
    arithmetic of ``_packed_impl``'s operand-dtype dots. The precision
    is pinned because under a process-wide "highest" Mosaic refuses a
    bf16 matmul (``Bad lhs type``) and narrow products are exact in
    f32 anyway. f32 operands keep the plain dot."""
    if a.dtype == jnp.float32:
        return jax.lax.dot_general(a, b, dims)
    return jax.lax.dot_general(
        a, b, dims, precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32).astype(a.dtype)


def _attend(qs, ks, vs, q_len, kv_len, tq: int):
    """One (slot, kv-head) attention block — the single source of the
    math, shared verbatim by the kernel body and the reference (the
    bitwise-equality pin compares two call sites of THIS function, not
    two formulations).

    qs ``[G*Tq, Dh]`` (pre-scaled, rows ordered (g, t)); ks/vs
    ``[KV_max, Dh]`` — positions >= kv_len may hold garbage (stale
    kernel scratch / trash-page contents) and are zeroed here so a NaN
    in dead space can never leak through a 0-weight product.
    Returns ``[G*Tq, Dh]`` in vs.dtype.
    """
    kv_max = ks.shape[0]
    kmask = jax.lax.broadcasted_iota(jnp.int32, (kv_max, 1), 0) < kv_len
    ks = jnp.where(kmask, ks, 0)
    vs = jnp.where(kmask, vs, 0)
    s = _mxu_dot(qs, ks, (((1,), (1,)), ((), ()))).astype(jnp.float32)
    t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % tq
    k_idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # bottom-right causal: row t sees keys 0 .. (kv_len - q_len) + t;
    # rows past q_len (span padding) are fully masked
    mask = (t < q_len) & (k_idx <= (kv_len - q_len) + t)
    s = jnp.where(mask, s, _MASK)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = _mxu_dot(p.astype(vs.dtype), vs, (((1,), (0,)), ((), ())))
    # fully-masked rows (padding, empty slots): l == 0 -> emit 0, not NaN
    return (o / jnp.where(l > 0, l, 1.0).astype(o.dtype)).astype(vs.dtype)


def _flash_tile(qs, ks_t, vs_t, k0, q_len, kv_len, tq: int, m, l, acc):
    """One TILE of the online-softmax (flash-combine) KV walk — the
    single source of the tiled math, shared verbatim by the tiled
    kernel body and the tiled dense reference (the bitwise pin
    compares two call sites of THIS function, exactly like
    ``_attend``'s).

    qs ``[G*Tq, Dh]`` pre-scaled; ks_t/vs_t ``[tile_kv, Dh]`` — the
    tile's keys/values, covering global KV positions
    ``k0 .. k0+tile_kv-1`` (positions >= kv_len may hold garbage —
    stale double-buffer contents, un-DMA'd pages — and are masked /
    zeroed here exactly as ``_attend`` does for its dead span).
    m/l ``[G*Tq, 1]`` f32 running max / denominator, acc
    ``[G*Tq, Dh]`` f32 running accumulator. A tile fully past
    ``kv_len`` is an exact no-op (alpha == 1, p == 0), which is why
    the reference may walk a static tile count while the kernel walks
    only live tiles and the two stay bitwise-equal."""
    gt = qs.shape[0]
    tile_kv = ks_t.shape[0]
    k_idx = k0 + jax.lax.broadcasted_iota(jnp.int32, (gt, tile_kv), 1)
    vmask = (k0 + jax.lax.broadcasted_iota(jnp.int32, (tile_kv, 1), 0)
             < kv_len)
    vs_t = jnp.where(vmask, vs_t, 0)
    s = _mxu_dot(qs, ks_t, (((1,), (1,)), ((), ()))).astype(jnp.float32)
    t = jax.lax.broadcasted_iota(jnp.int32, (gt, tile_kv), 0) % tq
    mask = (t < q_len) & (k_idx <= (kv_len - q_len) + t)
    s = jnp.where(mask, s, _MASK)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + _mxu_dot(
        p.astype(vs_t.dtype), vs_t,
        (((1,), (0,)), ((), ()))).astype(jnp.float32)
    return m_new, l_new, acc_new


def _flash_init(gt: int, dh: int):
    """Flash-combine carry init: running max starts at the MASK value
    (not -inf — ``exp(_MASK - _MASK)`` must be a defined 1.0 for rows
    that never see a live key, so fully-masked rows emit 0, not NaN —
    the same dead-row contract as ``_attend``)."""
    return (jnp.full((gt, 1), _MASK, jnp.float32),
            jnp.zeros((gt, 1), jnp.float32),
            jnp.zeros((gt, dh), jnp.float32))


def _flash_final(m, l, acc, dtype):
    del m  # fully-masked rows: l == 0 -> emit 0, not NaN
    return (acc / jnp.where(l > 0, l, 1.0)).astype(dtype)


def _attend_tiled(qs, ks, vs, q_len, kv_len, tq: int, tile_kv: int):
    """Tiled (flash-combine) counterpart of ``_attend``: the SAME per
    (slot, kv-head) block, but the KV axis walked in ``tile_kv``-sized
    tiles through ``_flash_tile``. This is the tiled DENSE REFERENCE —
    the Pallas tiled kernel is proven bitwise-equal to it, and IT is
    held to the ulp contract vs ``_attend`` (one-shot). Walks every
    tile of the padded KV_max statically; tiles past ``kv_len`` are
    exact no-ops (see ``_flash_tile``)."""
    kv_max, dh = ks.shape
    n_tiles = -(-kv_max // tile_kv)
    pad = n_tiles * tile_kv - kv_max
    if pad:
        ks = jnp.concatenate(
            [ks, jnp.zeros((pad, dh), ks.dtype)], axis=0)
        vs = jnp.concatenate(
            [vs, jnp.zeros((pad, dh), vs.dtype)], axis=0)

    def body(t, carry):
        k0 = t * tile_kv
        ks_t = jax.lax.dynamic_slice_in_dim(ks, k0, tile_kv)
        vs_t = jax.lax.dynamic_slice_in_dim(vs, k0, tile_kv)
        return _flash_tile(qs, ks_t, vs_t, k0, q_len, kv_len, tq,
                           *carry)

    m, l, acc = jax.lax.fori_loop(0, n_tiles, body,
                                  _flash_init(qs.shape[0], dh))
    return _flash_final(m, l, acc, vs.dtype)


def _kernel(layer_ref, qlen_ref, kvlen_ref, tab_ref, q_ref, kp_ref, vp_ref,
            o_ref, k_scr, v_scr, sems, *, pps: int, page_size: int,
            tq: int):
    s = pl.program_id(0)
    h = pl.program_id(1)
    layer = layer_ref[0]
    qn = qlen_ref[s]
    kn = kvlen_ref[s]
    n_pages = pl.cdiv(kn, page_size)

    def dma(p, pages_ref, scr, lane):
        page = tab_ref[s * pps + p]
        return pltpu.make_async_copy(pages_ref.at[layer, h, page],
                                     scr.at[p], sems.at[lane, p])

    # start every valid page's K and V copy, then await them — the
    # copies overlap in flight; a dead slot (qn == 0) moves no bytes
    for p in range(pps):
        @pl.when((qn > 0) & (p < n_pages))
        def _(p=p):
            dma(p, kp_ref, k_scr, 0).start()
            dma(p, vp_ref, v_scr, 1).start()
    for p in range(pps):
        @pl.when((qn > 0) & (p < n_pages))
        def _(p=p):
            dma(p, kp_ref, k_scr, 0).wait()
            dma(p, vp_ref, v_scr, 1).wait()

    @pl.when(qn > 0)
    def _():
        kv_max = pps * page_size
        dh = k_scr.shape[-1]
        ks = k_scr[...].reshape(kv_max, dh)
        vs = v_scr[...].reshape(kv_max, dh)
        o_ref[...] = _attend(q_ref[...], ks, vs, qn, kn, tq)

    @pl.when(qn == 0)
    def _():
        # dead slot: emit defined zeros (the reference's fully-masked
        # rows), not stale output-buffer contents — the bitwise pin
        # covers empty slots too
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit,
                   static_argnames=("tq", "g", "interpret"))
def _pallas_impl(qs, k_pages, v_pages, layer, q_len, kv_len, tables, tq,
                 g, interpret):
    """qs ``[S, Hkv, G*Tq, Dh]`` pre-scaled; returns the same shape.
    k_pages/v_pages are the STACKED pools ``[L, Hkv, P, ps, Dh]`` and
    ``layer`` ``[1]`` i32 picks the layer whose pages the DMAs read:
    the pools stay in HBM (``pl.ANY``) whole, nothing is sliced out."""
    S, Hkv, GT, Dh = qs.shape
    pps = tables.shape[1]
    page_size = k_pages.shape[3]
    kernel = functools.partial(_kernel, pps=pps, page_size=page_size,
                               tq=tq)
    block = pl.BlockSpec((None, None, GT, Dh),
                         lambda s, h, *_: (s, h, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(S, Hkv),
            in_specs=[
                block,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=block,
            scratch_shapes=[
                # the explicitly ONE-SHOT path: scratch deliberately
                # scales with the table width to keep the bitwise pin;
                # every other walk must be O(tile) (PT004). The growth
                # is bounded, not trusted: the kernel auditor's KA001
                # proves this footprint against the 14 MiB per-core
                # budget for every registered/swept geometry, and the
                # autotune gate refuses any winner past it — by the
                # knee (ONE_SHOT_VMEM_BUDGET) the default walk is
                # tiled anyway
                pltpu.VMEM((pps, page_size, Dh), k_pages.dtype),  # noqa: PT004 — one-shot by design, KA001-audited
                pltpu.VMEM((pps, page_size, Dh), v_pages.dtype),  # noqa: PT004 — one-shot by design, KA001-audited
                pltpu.SemaphoreType.DMA((2, pps)),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        out_shape=jax.ShapeDtypeStruct(qs.shape, k_pages.dtype),
        interpret=interpret,
        # a stable name: how the kernel shows in lowered and compiled
        # program text (chip_smoke.py) and, later, in a device trace
        name="ragged_paged_attention",
    )(layer, q_len, kv_len, tables.reshape(-1), qs, k_pages, v_pages)


def _tiled_kernel(layer_ref, qlen_ref, kvlen_ref, tab_ref, q_ref, kp_ref,
                  vp_ref, o_ref, k_scr, v_scr, sems, *, pps: int,
                  page_size: int, tq: int, tile_pages: int):
    """Flash-combine walk: live pages in ``tile_pages``-sized tiles,
    DOUBLE-BUFFERED — tile ``t+1``'s K/V page copies start while tile
    ``t`` computes, so past the first tile the DMA hides under the
    dots. Scratch is ``(2, tile_pages, page_size, Dh)`` per pool —
    O(tile), independent of ``pps`` — plus the f32 (m, l, acc) carry
    in registers/VMEM via the fori_loop."""
    s = pl.program_id(0)
    h = pl.program_id(1)
    layer = layer_ref[0]
    qn = qlen_ref[s]
    kn = kvlen_ref[s]
    n_pages = pl.cdiv(kn, page_size)
    tile_kv = tile_pages * page_size
    n_tiles = pl.cdiv(kn, tile_kv)

    def tile_dma(t, buf, p, pages_ref, scr, lane):
        page = tab_ref[s * pps + t * tile_pages + p]
        return pltpu.make_async_copy(pages_ref.at[layer, h, page],
                                     scr.at[buf, p],
                                     sems.at[lane, buf, p])

    def start_tile(t, buf):
        # static unroll over the tile's page slots; a slot past the
        # live range moves no bytes (its stale scratch is masked by
        # kv_len in _flash_tile)
        for p in range(tile_pages):
            @pl.when((t * tile_pages + p) < n_pages)
            def _(p=p):
                tile_dma(t, buf, p, kp_ref, k_scr, 0).start()
                tile_dma(t, buf, p, vp_ref, v_scr, 1).start()

    def wait_tile(t, buf):
        for p in range(tile_pages):
            @pl.when((t * tile_pages + p) < n_pages)
            def _(p=p):
                tile_dma(t, buf, p, kp_ref, k_scr, 0).wait()
                tile_dma(t, buf, p, vp_ref, v_scr, 1).wait()

    @pl.when(qn > 0)
    def _():
        dh = k_scr.shape[-1]
        qs = q_ref[...]
        start_tile(0, 0)

        def body(t, carry):
            buf = jax.lax.rem(t, 2)

            @pl.when(t + 1 < n_tiles)
            def _():
                start_tile(t + 1, jax.lax.rem(t + 1, 2))

            wait_tile(t, buf)
            ks_t = k_scr[buf].reshape(tile_kv, dh)
            vs_t = v_scr[buf].reshape(tile_kv, dh)
            return _flash_tile(qs, ks_t, vs_t, t * tile_kv, qn, kn,
                               tq, *carry)

        m, l, acc = jax.lax.fori_loop(
            0, n_tiles, body, _flash_init(qs.shape[0], dh))
        o_ref[...] = _flash_final(m, l, acc, o_ref.dtype)

    @pl.when(qn == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit,
                   static_argnames=("tq", "g", "tile_pages", "interpret"))
def _pallas_tiled_impl(qs, k_pages, v_pages, layer, q_len, kv_len, tables,
                       tq, g, tile_pages, interpret):
    """The tiled walk behind the same slot-major entry contract as
    ``_pallas_impl`` (stacked pools + layer index); scratch shapes are
    the whole VMEM story — O(tile), never O(pps)."""
    S, Hkv, GT, Dh = qs.shape
    pps = tables.shape[1]
    page_size = k_pages.shape[3]
    tile_pages = min(int(tile_pages), pps)
    kernel = functools.partial(_tiled_kernel, pps=pps,
                               page_size=page_size, tq=tq,
                               tile_pages=tile_pages)
    block = pl.BlockSpec((None, None, GT, Dh),
                         lambda s, h, *_: (s, h, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(S, Hkv),
            in_specs=[
                block,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((2, tile_pages, page_size, Dh), k_pages.dtype),
                pltpu.VMEM((2, tile_pages, page_size, Dh), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2, tile_pages)),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        out_shape=jax.ShapeDtypeStruct(qs.shape, k_pages.dtype),
        interpret=interpret,
        name="ragged_paged_attention_tiled",
    )(layer, q_len, kv_len, tables.reshape(-1), qs, k_pages, v_pages)


def _reference_impl(qs, k_pages, v_pages, q_len, kv_len, tables, tq, g,
                    tile_pages: int = 0):
    """Dense-gather reference with identical semantics: per slot,
    gather the table's pages and run the SAME ``_attend`` block per kv
    head. vmapped over (slot, head) — proven bitwise-equal to the
    kernel's sequential grid by tests/test_ragged_attention.py.
    ``tile_pages > 0`` selects the TILED dense reference (the same
    gather, attended through ``_attend_tiled``'s flash combine) — the
    off-chip twin of the tiled kernel."""
    S, Hkv, GT, Dh = qs.shape
    pps = tables.shape[1]
    ps = k_pages.shape[2]
    if tile_pages:
        tile_kv = min(int(tile_pages), pps) * ps
        attend = lambda qh, kh, vh, qn, kn: _attend_tiled(  # noqa: E731
            qh, kh, vh, qn, kn, tq, tile_kv)
    else:
        attend = lambda qh, kh, vh, qn, kn: _attend(  # noqa: E731
            qh, kh, vh, qn, kn, tq)

    def per_slot(q_s, qn, kn, tab):
        ks = k_pages[:, tab].reshape(Hkv, pps * ps, Dh)
        vs = v_pages[:, tab].reshape(Hkv, pps * ps, Dh)
        return jax.vmap(
            lambda qh, kh, vh: attend(qh, kh, vh, qn, kn)
        )(q_s, ks, vs)

    return jax.vmap(per_slot)(qs, q_len, kv_len, tables)


def _layer_pages(pages, layer):
    """One layer's ``[Hkv, P, ps, Dh]`` pages of a stacked pool, for the
    formulations that gather from them (the kernel never slices: it
    DMAs from ``pages_ref.at[layer, h, page]``)."""
    if layer is None:
        return pages
    return jax.lax.dynamic_index_in_dim(pages, layer, 0, keepdims=False)


def ragged_paged_attention(q, k_pages, v_pages, q_len, kv_len, tables,
                           sm_scale=None, impl: str = "auto",
                           kv_tile_pages=None, layer=None):
    """One-launch attention for a mixed ragged batch over paged KV.

    q: ``[S, Tq, H, Dh]`` slot-major query spans (see module
    docstring); k_pages/v_pages: ``[Hkv, P, page_size, Dh]``;
    q_len/kv_len: i32 ``[S]``; tables: i32 ``[S, pages_per_slot]``.
    Returns ``[S, Tq, H, Dh]`` in q.dtype.

    layer: None, or an i32 scalar with k_pages/v_pages the STACKED
    pools ``[L, Hkv, P, page_size, Dh]`` — the serving tick's way in:
    the kernel reads that layer's pages where they lie, so a layer
    scan that carries the pools never slices a layer out of them.

    impl: "auto" (pallas kernel on TPU, dense-gather reference
    elsewhere), "pallas" (strict — interpreter mode off-TPU), "dense".

    kv_tile_pages: the KV walk. None (default) = geometry AUTO on the
    pallas path — a persistent autotune winner for this geometry if
    ``kernel_bench --ragged-sweep`` recorded one, else one-shot while
    its scratch fits the VMEM budget and the tiled flash combine past
    the knee (``default_kv_tile_pages``; the dense path stays
    one-shot, it has no VMEM to protect);
    0 forces one-shot; N > 0 forces the tiled walk at an N-page tile
    (dense included — the tiled dense reference the kernel's bitwise
    pin runs against).
    """
    if impl not in ("auto", "pallas", "dense"):
        raise ValueError(f"impl must be auto|pallas|dense, got {impl!r}")
    S, Tq, H, Dh = q.shape
    Hkv, _, page_size, _ = k_pages.shape[-4:]
    if H % Hkv:
        raise ValueError(f"H={H} not a multiple of Hkv={Hkv}")
    G = H // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(Dh))
    q_len = jnp.asarray(q_len, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    # [S, Tq, H, Dh] -> [S, Hkv, G*Tq, Dh], rows (g, t)-ordered — the
    # head axis is kv-head-major (H = Hkv*G), matching the GQA reshape
    # every other kernel in the repo uses
    qs = (q * sm_scale).astype(q.dtype)
    qs = qs.reshape(S, Tq, Hkv, G, Dh).transpose(0, 2, 3, 1, 4)
    qs = qs.reshape(S, Hkv, G * Tq, Dh)
    use_pallas = impl == "pallas" or (impl == "auto" and _on_tpu())
    tile = kv_tile_pages
    if tile is None:
        if use_pallas:
            # KForge flywheel: a ragged-sweep winner recorded for this
            # geometry overrides the static VMEM-budget selection; an
            # unswept geometry (or unset store) keeps the default —
            # either way the same flash-combine math, only retiled.
            from .. import autotune as at
            win = at.lookup("ragged_paged_attention",
                            pages_per_slot=int(tables.shape[1]),
                            page_size=int(page_size),
                            head_dim=int(Dh),
                            dtype=str(jnp.dtype(k_pages.dtype)))
            if win is not None and "kv_tile_pages" in win:
                tile = int(win["kv_tile_pages"])
            else:
                tile = default_kv_tile_pages(tables.shape[1], page_size,
                                             Dh, k_pages.dtype)
        else:
            tile = 0
    tile = int(tile)
    if use_pallas:
        # one kernel body: a single layer's 4-D pool enters as a
        # one-layer stack (a bitcast) read at layer 0
        if layer is None:
            k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
        layer = jnp.asarray(layer, jnp.int32).reshape(1)
        if tile:
            out = _pallas_tiled_impl(qs, k_pages, v_pages, layer, q_len,
                                     kv_len, tables, tq=Tq, g=G,
                                     tile_pages=tile,
                                     interpret=not _on_tpu())
        else:
            out = _pallas_impl(qs, k_pages, v_pages, layer, q_len, kv_len,
                               tables, tq=Tq, g=G,
                               interpret=not _on_tpu())
    else:
        out = _reference_impl(qs, _layer_pages(k_pages, layer),
                              _layer_pages(v_pages, layer), q_len, kv_len,
                              tables, tq=Tq, g=G, tile_pages=tile)
    out = out.reshape(S, Hkv, G, Tq, Dh).transpose(0, 3, 1, 2, 4)
    return out.reshape(S, Tq, H, Dh).astype(q.dtype)


def ragged_paged_attention_reference(q, k_pages, v_pages, q_len, kv_len,
                                     tables, sm_scale=None):
    """The dense-gather formulation, directly (tests reach it via
    ``impl="dense"`` too)."""
    return ragged_paged_attention(q, k_pages, v_pages, q_len, kv_len,
                                  tables, sm_scale=sm_scale, impl="dense")


def _packed_impl(q, k_pages, v_pages, tok_slot, tok_qoff, q_len, kv_len,
                 tables, sm_scale):
    """Work-proportional PACKED formulation: attention computed
    directly on the tick's token stream — score work scales with the
    ``T`` real rows, not the ``S × Tq`` slot-major padding the kernel's
    block layout needs (6-7x less at serving shapes, which is why the
    engine's CPU ticks route here). Same math, same masks, same
    reduction axes/order as ``_attend`` — proven bitwise-equal to the
    slot-major reference by tests/test_ragged_attention.py."""
    T, H, Dh = q.shape
    S, pps = tables.shape
    Hkv, _, ps, _ = k_pages.shape
    G = H // Hkv
    KV = pps * ps
    qs = (q * sm_scale).astype(q.dtype).reshape(T, Hkv, G, Dh)
    # ONE per-token page gather, via the (tiny) [T, pps] table-row
    # gather — gathering [Hkv, S, KV, Dh] per slot and then re-indexing
    # [:, tok_slot] would copy the gathered block a second time
    # (padding rows — slot sentinel S — clamp to slot 0 and are fully
    # masked below)
    sl = jnp.minimum(tok_slot, S - 1)
    tabs_t = tables[sl]                                     # [T, pps]
    ks = k_pages[:, tabs_t].reshape(Hkv, T, KV, Dh)
    vs = v_pages[:, tabs_t].reshape(Hkv, T, KV, Dh)
    kmask = (jax.lax.broadcasted_iota(jnp.int32, (T, KV), 1)
             < kv_len[sl][:, None])                         # [T, KV]
    # K needs no pre-zeroing: every garbage position's score is
    # REPLACED by _MASK below (jnp.where takes the other branch even
    # for NaN), and live positions only dot rows < kv_len. V keeps the
    # zeroing — it is the NaN barrier for garbage rows (p is exactly 0
    # there, but 0 * NaN would still poison the weighted sum)
    vs = jnp.where(kmask[None, :, :, None], vs, 0)
    s = jnp.einsum("tkgd,ktsd->tkgs", qs, ks).astype(jnp.float32)
    # bottom-right causal per token: row qoff sees keys
    # 0 .. (kv_len - q_len) + qoff of ITS slot; padding rows (slot
    # sentinel, or qoff >= q_len) are fully masked
    k_idx = jax.lax.broadcasted_iota(jnp.int32, (T, KV), 1)
    hi = (kv_len[sl] - q_len[sl] + tok_qoff)[:, None]
    mask = ((tok_slot < S)[:, None] & (tok_qoff < q_len[sl])[:, None]
            & (k_idx <= hi))                                # [T, KV]
    m4 = mask[:, None, None, :]
    s = jnp.where(m4, s, _MASK)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(m4, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("tkgs,ktsd->tkgd", p.astype(vs.dtype), vs)
    o = o / jnp.where(l > 0, l, 1.0).astype(o.dtype)
    return o.reshape(T, H, Dh).astype(q.dtype)


# A pool whose rows are narrower than the chip's 128 lanes cannot be
# read by the kernel: Mosaic refuses the page DMA ("Slice shape along
# dimension 4 must be aligned to tiling (128), but is 64", described
# v5e compile at head size 64). Such a model keeps its pool LANE-PACKED:
# ``f`` neighbouring KV heads share one row, ``[L, Hkv/f, P, ps, f*Dh]``
# (the row-major reshape of ``[..., Hkv, Dh]``), and the packed entry
# below hands the kernel queries widened to the row with zeros on the
# other heads' lanes. To the kernel that is the 128-wide geometry with
# ``Hkv/f`` heads of ``f*G`` query rows: no second kernel, no new
# autotune key, every byte of the pool read once, and the MXU passes a
# 64-deep contraction would be padded to anyway.
LANES = 128


def lane_pack_factor(head_dim: int, num_kv_heads: int) -> int:
    """How many KV heads share one row of a lane-packed pool (1: the
    pool is not packed)."""
    if head_dim >= LANES or LANES % head_dim:
        return 1
    return math.gcd(LANES // head_dim, num_kv_heads)


def lane_pack_heads(x, f: int):
    """``[..., Hkv, Dh] -> [..., Hkv/f, f*Dh]``: a span's K or V rows
    as a lane-packed pool stores them."""
    *lead, hkv, dh = x.shape
    return x.reshape(*lead, hkv // f, f * dh)


def _lane_member(q, k_pages):
    """``[H]``: which of its row's ``f`` KV heads each query head of
    ``q [T, H, Dh]`` reads (head ``h`` reads KV head ``h // G``, member
    ``(h // G) % f`` of its row) over a lane-packed pool."""
    H, Dh = q.shape[1:]
    f = k_pages.shape[-1] // Dh
    G = H // (k_pages.shape[-4] * f)
    return (jnp.arange(H, dtype=jnp.int32) // G) % f, f


def _lane_widen(q, member, f):
    """``[T, H, Dh] -> [T, H, f*Dh]``: each head's values on its
    member's lanes and zeros elsewhere, so that its scores over the row
    see its own KV head alone."""
    T, H, Dh = q.shape
    lanes = jax.nn.one_hot(member, f, dtype=q.dtype)            # [H, f]
    return (q[:, :, None, :] * lanes[None, :, :, None]).reshape(T, H, f * Dh)


def _lane_narrow(o, member, f):
    """``[T, H, f*Dh] -> [T, H, Dh]``: its member's lanes of each
    head's row-wide result."""
    T, H, wide = o.shape
    return jnp.take_along_axis(o.reshape(T, H, f, wide // f),
                               member[None, :, None, None], axis=2)[:, :, 0]


def ragged_paged_attention_packed(q, k_pages, v_pages, tok_slot, tok_qoff,
                                  q_len, kv_len, tables, tq: int,
                                  sm_scale=None, impl: str = "auto",
                                  kv_tile_pages=None, layer=None):
    """Packed-layout entry for the serving tick: ``q [T, H, Dh]`` is
    the tick's token stream with per-token owner/offset metadata
    (``tok_slot [T]`` — ``S`` = padding sentinel; ``tok_qoff [T]``).
    Returns ``[T, H, Dh]`` (padding rows zero).

    impl: "auto" — the work-proportional packed formulation off-TPU,
    the Pallas kernel (scatter to the slot-major layout at the
    boundary) on TPU; "pallas"/"dense" force the slot-major kernel /
    reference; "packed" forces the packed formulation.
    ``kv_tile_pages`` rides through to the slot-major walk selection
    (None = geometry auto — the serving tick passes nothing and a
    100k-token table picks the tiled walk by itself on TPU).
    ``layer`` as in ``ragged_paged_attention``: with it the pools are
    the stacked ``[L, Hkv, P, page_size, Dh]``. Pools with rows wider
    than ``Dh`` are lane-packed (``lane_pack_factor``).
    """
    if impl not in ("auto", "pallas", "dense", "packed"):
        raise ValueError(
            f"impl must be auto|pallas|dense|packed, got {impl!r}")
    T, H, Dh = q.shape
    if k_pages.shape[-1] != Dh:
        member, f = _lane_member(q, k_pages)
        o = ragged_paged_attention_packed(
            _lane_widen(q, member, f), k_pages, v_pages, tok_slot, tok_qoff,
            q_len, kv_len, tables, tq,
            sm_scale=sm_scale or 1.0 / float(np.sqrt(Dh)), impl=impl,
            kv_tile_pages=kv_tile_pages, layer=layer)
        return _lane_narrow(o, member, f)
    S = tables.shape[0]
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(Dh))
    tok_slot = jnp.asarray(tok_slot, jnp.int32)
    tok_qoff = jnp.asarray(tok_qoff, jnp.int32)
    q_len = jnp.asarray(q_len, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    if impl == "packed" or (impl == "auto" and not _on_tpu()):
        return _packed_impl(q, _layer_pages(k_pages, layer),
                            _layer_pages(v_pages, layer), tok_slot,
                            tok_qoff, q_len, kv_len, tables, sm_scale)
    # slot-major boundary: scatter the stream into the kernel's
    # [S, Tq] layout (row S+1 absorbs padding tokens), run the kernel,
    # gather back (padding reads the zero row)
    qs = jnp.zeros((S + 1, int(tq), H, Dh), q.dtype)
    qs = qs.at[tok_slot, tok_qoff].set(q)
    o = ragged_paged_attention(qs[:S], k_pages, v_pages, q_len, kv_len,
                               tables, sm_scale=sm_scale, impl=impl,
                               kv_tile_pages=kv_tile_pages, layer=layer)
    o = jnp.concatenate([o, jnp.zeros((1,) + o.shape[1:], o.dtype)],
                        axis=0)
    return o[tok_slot, tok_qoff].astype(q.dtype)


# ---------------------------------------------------------------------------
# kernel-audit registration (analysis/kernel_audit.py)
# ---------------------------------------------------------------------------
# Geometry keys are EXACTLY the autotune lookup kwargs above, so every
# winners.json entry for this kind audits directly. The one-shot
# flagship geometry pins the deliberate O(pps) scratch (KA001's number
# is the waived PT004 lines' justification); the long-context geometry
# sits past the ONE_SHOT_VMEM_BUDGET knee so the default walk under
# audit is the tiled double-buffered kernel.

AUDIT_KIND = "ragged_paged_attention"
AUDIT_GEOM_KEYS = ("pages_per_slot", "page_size", "head_dim", "dtype")
AUDIT_CONFIG_KEYS = ("kv_tile_pages",)
AUDIT_GEOMETRIES = (
    # serving flagship: 4k-token table, one-shot walk
    {"pages_per_slot": 16, "page_size": 16, "head_dim": 128,
     "dtype": "bfloat16"},
    # long context: 16k tokens — 8 MiB one-shot scratch is past the
    # 4 MiB knee, so the default walk here is the tiled double-buffered
    # kernel (KA003 proves its start/wait pairing)
    {"pages_per_slot": 1024, "page_size": 16, "head_dim": 128,
     "dtype": "bfloat16"},
    # head size 64, 2k-token table: a lane-packed pool, so the launch
    # under audit is the 128-wide one with half the KV heads and twice
    # the query rows a head (see ``lane_pack_factor``)
    {"pages_per_slot": 128, "page_size": 16, "head_dim": 64,
     "dtype": "bfloat16"},
)


def audit_launches(geom, config=None):
    """Zero-execution traceable launches for the kernel auditor: big
    tensors as ShapeDtypeStructs, scalar-prefetch metadata (layer,
    q_len, kv_len, tables) concrete so KA002 can evaluate the index
    maps."""
    pps = int(geom["pages_per_slot"])
    ps = int(geom["page_size"])
    dh = int(geom["head_dim"])
    dt = jnp.dtype(geom["dtype"])
    S, Hkv, G, Tq = 4, 2, 2, 8
    f = lane_pack_factor(dh, Hkv)
    Hkv, G, dh = Hkv // f, G * f, dh * f
    qs = jax.ShapeDtypeStruct((S, Hkv, G * Tq, dh), dt)
    pages = jax.ShapeDtypeStruct((1, Hkv, S * pps, ps, dh), dt)
    layer = np.zeros((1,), np.int32)
    q_len = np.full((S,), Tq, np.int32)
    kv_len = np.full((S,), pps * ps, np.int32)
    tables = np.arange(S * pps, dtype=np.int32).reshape(S, pps)
    args = (qs, pages, pages, layer, q_len, kv_len, tables)
    if config is not None and "kv_tile_pages" in config:
        tile = int(config["kv_tile_pages"])
    else:
        tile = default_kv_tile_pages(pps, ps, dh, dt)
    if tile:
        tile = min(tile, pps)
        fn = functools.partial(_pallas_tiled_impl, tq=Tq, g=G,
                               tile_pages=tile, interpret=False)
        return [(f"tiled[kv_tile_pages={tile}]", fn, args)]
    fn = functools.partial(_pallas_impl, tq=Tq, g=G, interpret=False)
    return [("one_shot", fn, args)]
