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
  written into the pages (``models/layer_walk.py: paged_kv_attend``
  scatters before attending), so the kernel is purely paged: no separate current-chunk
  operand, no gathered-prefix concat.
* ``layer`` (optional): with it the pools are the serving tick's
  STACKED ones, ``[L, Hkv, total_pages, page_size, Dh]``, and the
  kernel reads that layer's pages where they lie (its page DMAs start
  from ``pages[layer, h0:h0+heads, page]``; the index is one more
  scalar-prefetch operand). That is what lets the tick's layer scan
  CARRY the pools — one buffer from the program's parameter to its
  result — where slicing a layer out in front of the kernel copied a
  layer's pages per layer. There is one kernel body: a 4-D pool
  enters as a one-layer stack read at layer 0. Off the kernel (the
  dense and packed formulations) the layer is sliced out in front of
  the same code as ever.
* ``kv_len[s]`` counts every key visible at the END of slot ``s``'s
  span (context + the span itself); query row ``t`` attends key
  positions ``0 .. kv_len[s]-q_len[s]+t`` — the bottom-right causal
  mask that makes a chunked prefill bitwise-equal to a whole-prompt
  one. A ``kv_len[s]`` past the table (``pages_per_slot·page_size``:
  the fused decode tail steps a retiring slot past its last page) is
  read as the table's width, in the kernel and in both references.
* ``tables``: ``[S, pages_per_slot]`` int32; entries past the covered
  range may be TRASH (0) — the kernel walks only
  ``ceil(kv_len/page_size)`` entries, so HBM traffic scales with the
  tokens actually cached, not the table width.

**One walk, three trip counts read from the tick's data.** Grid
``(S, Hkv / heads)``: a grid step is a SLOT and ``heads`` of its KV
heads — all of them, a grid over slots alone, wherever VMEM holds
them (``heads_per_step``; every launch of the benchmark's cells but
the 16-head cell's 256-row span launch, which takes two). A step's
cost follows what its slot holds, not the launch's static
extents (slots, table width, query rows a slot):

* *slots*: a slot with ``q_len == 0`` writes its zeros and does
  nothing else — no predicate, no copy, no dot;
* *pages*: the slot's live pages are walked in fixed
  ``kv_tile_pages``-sized tiles with the flash combine (the Ragged
  Paged Attention paper's formulation: float32 running max /
  denominator / accumulator), ``cdiv(kv_len, tile)`` trips, each
  tile's page copies issued and awaited by loops of the tile's live
  page count, DOUBLE-BUFFERED (tile ``t+1``'s copies start while tile
  ``t`` computes). A live page moves with ONE copy a pool: the
  descriptor ``pages[layer, h0:h0+heads, page] -> scratch[buf, p]``
  is strided over the pool's head axis (the pool's layout is not
  touched) and lands the step's heads side by side — a copy started
  costs several times its 4 KiB of bytes, so a slot starts ``2`` a
  page, not ``2 · Hkv``. VMEM is ``O(heads · tile)``, independent of
  ``pages_per_slot``: a 100k-token table costs the on-chip bytes of a
  2k one, and every live page is read once a slot;
* *heads and query rows*: inside a tile, a loop over the step's heads,
  and inside it over the head's query rows. Rows enter ordered
  (token, group), so a slot's real rows are its first ``G·q_len``;
  they are walked in blocks of ``ROW_BLOCK`` rows,
  ``cdiv(G·q_len, ROW_BLOCK)`` trips, the flash state of every (head,
  row block) kept in VMEM scratch. A decoding slot in a launch that
  carries a prefill span costs one row block a head; the score block
  in VMEM is ``ROW_BLOCK × tile`` whatever ``Tq`` and
  ``pages_per_slot`` are.

Decode against prefill, short against long, dead against live are
trip counts of this one loop nest, not paths. A table no wider than
one tile is walked in one trip: the one-shot walk, by the same code.
The tile is chosen by geometry alone (``default_kv_tile_pages``, or a
``kernel_bench --ragged-sweep`` winner in the autotune store);
``kv_tile_pages=`` overrides. The heads a step holds follow from the
tile, the launch's query rows and ``STEP_VMEM_BUDGET``
(``heads_per_step``: the heads give way, never the tile — a flash step
costs more than a copy). ``page_copies`` says what a launch starts for
a live page (the engine's ``kv_page_copies``). A step's last tile
starts the NEXT live step's first tile into the buffer it leaves free
(``nxt_ref``), so only a launch's first live slot starts cold; a decode
launch (one row block a head) takes its heads ``HEAD_UNROLL`` a trip.

Exactness discipline: the kernel is BITWISE-equal to its dense twin
(``impl="dense"`` at the same ``kv_tile_pages``): the same
``_flash_tile`` math at two call sites, the twin walking every tile
and row block statically — a tile past ``kv_len`` is an exact no-op
and a row block past ``G·q_len`` comes out zero, which is what the
kernel's skipped trips leave. Against the ONE-SHOT dense reference
(``_attend``: the whole context in one softmax, ``kv_tile_pages=0``
off the kernel) the walk is held to a measured ulp-at-row-scale bound
(``TILED_ULP_BOUND`` / ``tiled_ulp_error``, the fused-rmsnorm
measured-sweep contract style from analysis/rewrite.py): the flash
combine reassociates the softmax reductions, so bitwise is off the
table by construction, and the bound is what
tests/test_ragged_attention.py enforces across the geometry grid.

Off-TPU the kernel runs in interpreter mode (CPU-testable, like the
int8/flash kernels); ``impl="dense"`` selects the reference gather
formulation with identical semantics — ``impl="auto"`` uses the kernel
on TPU and the reference elsewhere.

**The serving tick's way in** (``ragged_paged_attention_packed``): the
tick's queries arrive PACKED, ``[T, H, Dh]``, one row a token, a slot's
rows contiguous. On the kernel's path ONE function lays them out
(``_stream_launch``), for whole slots and for spans cut into virtual
slots (``_span_blocks``) alike, from a plan of the packing that a walk
of many layers makes once a tick (``stream_plan``: index arrays from
the tick's metadata and the pool's KV heads alone; ``models/
layer_walk.py: tick_plan`` is the one place the families' walks make it; where a
slot's rows start and whether spans are cut are the PLAN's to say, not
the entry's). Every (virtual) slot's FIRST token goes
into its block of the kernel's input ``[S, Hkv, R, Dh]`` by one row
gather, padded out to the block's ``R`` rows in the same write: all a
decode slot needs. A slot that holds a SPAN gets its block as one
window of the stream, re-laid at ITS size a KV head at a time with rows
(token, group)-ordered, copied in place from the slot's first row on:
a loop over the spans alone: one or two a tick, or every drafting slot
of a speculative tick (rows past a slot's own are its neighbours':
finite, masked, never read back). Behind the kernel the stream's rows
are gathered straight out of its result by the inverse map (``(slot ·
Hkv + head) · R + offset · G + g``), a row a (token, head): nothing of
the buffer's size stands between. A decode launch's block is one
stream row: no pad, no loop. A span CUT into virtual slots (a buffer
about the stream's size) is one token gather a KV head into head-major
blocks and one back, as it was before PR 48: alone the loop and the
row gather are faster there too, in MiMo's whole tick 1.5 ms a span
tick slower (PERF.md section 6). The buffer of whole slots is ``S · tq``
tokens for a stream of ``S + tq``: 26 times the data in the chat
cell's span tick, where the slot-major scatter, the scale, four
transposing copies and a pad that stood here until PR 48 were eight
passes over it a layer, 8 of a span tick's 21 ms (PERF.md section 6).
The slot-major API (``ragged_paged_attention``) keeps its own layout
for its callers and tests.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from . import on_tpu as _on_tpu

__all__ = ["ragged_paged_attention", "ragged_paged_attention_reference",
           "ragged_paged_attention_packed", "stream_plan", "StreamPlan",
           "default_kv_tile_pages",
           "vmem_scratch_bytes", "ROW_BLOCK", "TILED_ULP_BOUND",
           "tiled_ulp_error"]

_MASK = -1e30  # matches the repo's dense-attention mask value

# bytes of ONE buffer of one pool's tile A HEAD (the walk holds two
# buffers of K and two of V for each head of the step): big enough that
# the tile's dots amortize the flash step's fixed cost, small enough
# that the double-buffered K+V scratch stays 512 KiB a head. At
# Dh=128/bf16 that is 512 KV tokens a tile. The kernel_bench ragged
# sweep is the measured A/B over this choice (the first entry of the
# KForge-style autotune loop, PAPERS.md 2606.02963): on a v5e a tile
# half as wide costs a decode launch a third more and a span launch
# half more, whatever it saves in heads a step; a tile twice as wide
# gains at long contexts and loses at short ones (PERF.md, PR 39).
DEFAULT_TILE_BYTES = 128 * 2 ** 10
# what a grid step's scratch and blocks may pin of the chip's 16 MiB of
# scoped VMEM; the rest is the compiler's (the score blocks: 0.8 MiB at
# a 128-row block and a 512-token tile). The heads a step holds are
# chosen under it.
STEP_VMEM_BUDGET = 14 * 2 ** 20
# the chip's lane count: the minor dimension a VMEM array is padded to
LANES = 128
# query rows a block (rows are (token, group)-ordered; a launch with
# fewer rows a slot has one block of them all): the score block in
# VMEM is ROW_BLOCK x tile float32 whatever the launch's Tq is
ROW_BLOCK = 128
# page copies started a trip of the copy loop (the last trips take one)
PAGE_UNROLL = 8
# heads a trip of a decode launch's head loop (fewer where the step's
# heads do not divide by it): a flash step of a few query rows is a
# chain of latencies, and neighbouring heads' chains are independent
HEAD_UNROLL = 4
# flash-vs-one-shot exactness contract (the fused-rmsnorm measured-
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


def _step_vmem_bytes(heads: int, tile_pages: int, page_size: int,
                     head_dim: int, rows: int, itemsize: int,
                     v_head_dim=None) -> int:
    """What one grid step of ``heads`` KV heads pins of VMEM as the
    chip's compiler counts it: the double-buffered K and V tiles, the
    float32 flash state (each ``(rows, 1)`` column of running max and
    denominator fills whole 128-lane rows there), and the q and o
    blocks, which the pipeline double-buffers. ``v_head_dim``: the
    values' (and the result's) head size where it is not the keys'."""
    dv = head_dim if v_head_dim is None else int(v_head_dim)
    rows = -(-rows // ROW_BLOCK) * ROW_BLOCK if rows > ROW_BLOCK else rows
    kv = 2 * heads * tile_pages * page_size * (head_dim + dv) * itemsize
    state = heads * rows * (dv + 2 * LANES) * 4
    blocks = 2 * heads * rows * (head_dim + dv) * itemsize
    return kv + state + blocks


def heads_per_step(kv_heads: int, tile_pages: int, page_size: int,
                   head_dim: int, dtype=jnp.bfloat16, rows: int = 0,
                   v_head_dim=None) -> int:
    """KV heads one grid step holds at a ``tile_pages`` tile and
    ``rows`` query rows a head: all of them where ``STEP_VMEM_BUDGET``
    allows (a page then moves with one copy a pool), else the largest
    divisor of ``kv_heads`` it does. The heads give way, not the tile:
    a narrower tile costs more flash steps than a second copy a page
    (module constants above)."""
    item = jnp.dtype(dtype).itemsize
    for heads in range(int(kv_heads), 1, -1):
        if kv_heads % heads == 0 and _step_vmem_bytes(
                heads, tile_pages, page_size, head_dim, int(rows),
                item, v_head_dim) <= STEP_VMEM_BUDGET:
            return heads
    return 1


def default_kv_tile_pages(pages_per_slot: int, page_size: int,
                          head_dim: int, dtype=jnp.bfloat16) -> int:
    """Geometry selection of the KV walk's tile, in pages: what
    ``DEFAULT_TILE_BYTES`` holds of this geometry's rows a head, and
    never more than the table (a table that fits one tile is walked in
    one trip). The engine never chooses: the serving tick passes
    geometry through and this picks per (pages_per_slot, page_size,
    Dh, dtype); ``heads_per_step`` then fits the step to VMEM."""
    tokens = DEFAULT_TILE_BYTES // (int(head_dim)
                                    * jnp.dtype(dtype).itemsize)
    return min(int(pages_per_slot), max(1, tokens // int(page_size)))


def page_copies(kv_heads: int, pages_per_slot: int, page_size: int,
                head_dim: int, dtype=jnp.bfloat16, rows: int = 0,
                kv_tile_pages=None, v_head_dim=None) -> int:
    """Copies a launch of ``rows`` query rows a (slot, kv head) starts
    for ONE live page of a layer: one a pool a grid step that walks it
    (2 where a step holds every head)."""
    tile = _tile_pages(pages_per_slot, page_size, head_dim, dtype,
                       kv_tile_pages)
    return 2 * (int(kv_heads) // heads_per_step(
        kv_heads, tile, page_size, head_dim, dtype, rows, v_head_dim))


def _tile_pages(pages_per_slot, page_size, head_dim, dtype,
                kv_tile_pages) -> int:
    """``kv_tile_pages`` as the kernel takes it: None the geometry's
    AUTO, 0 the whole table in one tile. AUTO is the KForge flywheel: a
    ragged-sweep winner recorded for this geometry overrides the static
    selection; an unswept geometry (or unset store) keeps the default
    — either way the same flash-combine math, only retiled."""
    if kv_tile_pages is None:
        from .. import autotune as at
        win = at.lookup("ragged_paged_attention",
                        pages_per_slot=int(pages_per_slot),
                        page_size=int(page_size), head_dim=int(head_dim),
                        dtype=str(jnp.dtype(dtype)))
        if win is not None and "kv_tile_pages" in win:
            kv_tile_pages = int(win["kv_tile_pages"])
        else:
            return default_kv_tile_pages(pages_per_slot, page_size,
                                         head_dim, dtype)
    return min(int(kv_tile_pages) or int(pages_per_slot),
               int(pages_per_slot))


def _row_block(rows: int) -> int:
    """Query rows a block for a launch of ``rows`` rows a (slot, kv
    head): ``ROW_BLOCK``, or all of them where there are fewer."""
    return min(ROW_BLOCK, int(rows))


def vmem_scratch_bytes(pages_per_slot: int, page_size: int,
                       head_dim: int, dtype=jnp.bfloat16,
                       kv_tile_pages=None, rows: int = 0,
                       kv_heads: int = 1) -> int:
    """VMEM scratch one grid program pins, straight from the kernel's
    ``scratch_shapes``: for each of the KV heads a step holds
    (``heads_per_step`` of the launch's ``kv_heads``), two
    double-buffer tiles of K and of V (``2 · 2 · tile · ps · Dh`` —
    independent of ``pages_per_slot`` past one tile, which is the whole
    point) plus the float32 flash state (running max, denominator,
    accumulator) of the launch's ``rows`` query rows a (slot, kv
    head). ``kv_tile_pages`` as the kernel takes it: None the
    geometry's default, 0 the whole table in one tile. Shared by the
    kernel_bench sweep, the decode_profile long-context ceiling and the
    kernel auditor's KA001 pin."""
    pps = int(pages_per_slot)
    tile = (default_kv_tile_pages(pps, page_size, head_dim, dtype)
            if kv_tile_pages is None else min(int(kv_tile_pages) or pps, pps))
    heads = heads_per_step(kv_heads, tile, page_size, head_dim, dtype, rows)
    item = jnp.dtype(dtype).itemsize
    return heads * (2 * 2 * tile * page_size * head_dim * item
                    + int(rows) * (head_dim + 2) * 4)


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


def _row_mask(r0, shape, k0, q_len, kv_len, g: int, window: int = 0):
    """Bottom-right causal mask ``[rows, keys]`` (broadcast from a
    column of tokens and a row of key positions) of (token, group)-
    ordered query rows ``r0 ..`` against key positions ``k0 ..``: row
    ``r`` is token ``t = r // g`` and sees keys
    ``0 .. (kv_len - q_len) + t``; rows past ``g·q_len`` (span padding)
    are fully masked. With ``window`` a row sees the LAST ``window`` of
    those keys only (its own position and the ``window - 1`` before)."""
    t = jax.lax.div(
        r0 + jax.lax.broadcasted_iota(jnp.int32, (shape[0], 1), 0),
        jnp.int32(g))
    k_idx = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, shape[1]), 1)
    if not window:
        return (t < q_len) & (k_idx <= (kv_len - q_len) + t)
    hi = (kv_len - q_len) + t
    return (t < q_len) & (k_idx <= hi) & (k_idx > hi - window)


def _first_key(q_len, kv_len, window: int):
    """The first key position ANY row of a slot sees under ``window``
    (its first row's): what lies before it is skipped by the walk."""
    return jnp.maximum(kv_len - q_len - (window - 1), 0)


def _sink_rows(sinks, r0, rows: int, g: int):
    """``[rows, 1]`` float32: the sink logit of each of the (token,
    group)-ordered query rows ``r0 ..`` of one KV head, ``sinks [G]``
    its query heads' (row ``r`` is query head ``r % g`` of the group)."""
    gi = jax.lax.rem(
        r0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), jnp.int32(g))
    col = jnp.zeros((rows, 1), jnp.float32)
    for j in range(g):
        col = jnp.where(gi == j, sinks[j], col)
    return col


def _attend(qs, ks, vs, q_len, kv_len, g: int, window: int = 0,
            sink=None):
    """One (slot, kv-head) attention block in ONE softmax over the
    whole padded context: the one-shot dense reference the flash walk
    is held to under ``TILED_ULP_BOUND``.

    qs ``[Tq*G, Dh]`` (pre-scaled, rows ordered (t, g)); ks/vs
    ``[KV_max, Dh]`` — positions >= kv_len may hold garbage (trash-page
    contents) and are zeroed here so a NaN in dead space can never
    leak through a 0-weight product. Returns ``[Tq*G, Dh]`` in
    vs.dtype.
    """
    kv_max = ks.shape[0]
    kmask = jax.lax.broadcasted_iota(jnp.int32, (kv_max, 1), 0) < kv_len
    ks = jnp.where(kmask, ks, 0)
    vs = jnp.where(kmask, vs, 0)
    s = _mxu_dot(qs, ks, (((1,), (1,)), ((), ()))).astype(jnp.float32)
    mask = _row_mask(0, s.shape, 0, q_len, kv_len, g, window)
    s = jnp.where(mask, s, _MASK)
    m = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        # the sink ``[rows, 1]``: a logit in the denominator, no value
        m = jnp.maximum(m, sink)
    p = jnp.exp(s - m)
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if sink is not None:
        l = l + jnp.exp(sink - m)
    o = _mxu_dot(p.astype(vs.dtype), vs, (((1,), (0,)), ((), ())))
    # fully-masked rows (padding, empty slots): l == 0 -> emit 0, not NaN
    return (o / jnp.where(l > 0, l, 1.0).astype(o.dtype)).astype(vs.dtype)


def _flash_tile(qs, ks_t, vs_t, k0, r0, q_len, kv_len, g: int, m, l, acc,
                window: int = 0, k_lo=None):
    """One (row block, KV tile) step of the online-softmax (flash-
    combine) walk — the single source of the walk's math, shared
    verbatim by the kernel body and its dense twin (the bitwise pin
    compares two call sites of THIS function).

    qs ``[rows, Dh]`` pre-scaled: the (t, g)-ordered query rows
    ``r0 .. r0+rows-1`` of the slot; ks_t/vs_t ``[tile_kv, Dh]`` — the
    tile's keys/values, covering global KV positions
    ``k0 .. k0+tile_kv-1`` (positions >= kv_len may hold garbage —
    stale double-buffer contents, un-DMA'd pages: their scores are
    REPLACED by the mask, a NaN's too, and their values zeroed).
    m/l ``[rows, 1]`` f32 running max / denominator, acc
    ``[rows, Dh]`` f32 running accumulator. A tile fully past
    ``kv_len`` is an exact no-op (alpha == 1, p == 0), which is why
    the twin may walk a static tile count while the kernel walks only
    live tiles and the two stay bitwise-equal."""
    tile_kv = ks_t.shape[0]
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (tile_kv, 1), 0)
    vmask = k_pos < kv_len
    if k_lo is not None:
        # keys before the slot's first visible one: their pages were
        # not copied (the windowed walk starts at ``k_lo``'s page)
        vmask = vmask & (k_pos >= k_lo)
    vs_t = jnp.where(vmask, vs_t, 0)
    s = _mxu_dot(qs, ks_t, (((1,), (1,)), ((), ()))).astype(jnp.float32)
    mask = _row_mask(r0, s.shape, k0, q_len, kv_len, g, window)
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


def _flash_init(rows: int, dh: int, sink=None):
    """Flash-combine state init: running max starts at the MASK value
    (not -inf — ``exp(_MASK - _MASK)`` must be a defined 1.0 for rows
    that never see a live key, so fully-masked rows emit 0, not NaN —
    the same dead-row contract as ``_attend``). With a SINK (``[rows,
    1]`` float32 logits in the kernel's own scaling) the state starts
    as if one key of that score and no value had been seen: ``m =
    sink``, ``l = 1``, ``acc = 0``."""
    if sink is not None:
        return (sink, jnp.ones((rows, 1), jnp.float32),
                jnp.zeros((rows, dh), jnp.float32))
    return (jnp.full((rows, 1), _MASK, jnp.float32),
            jnp.zeros((rows, 1), jnp.float32),
            jnp.zeros((rows, dh), jnp.float32))


def _flash_final(l, acc, dtype):
    # fully-masked rows: l == 0 -> emit 0, not NaN
    return (acc / jnp.where(l > 0, l, 1.0)).astype(dtype)


def _attend_tiled(qs, ks, vs, r0, q_len, kv_len, g: int, tile_kv: int,
                  window: int = 0, sink=None):
    """One row block of the kernel's DENSE TWIN: query rows
    ``r0 .. r0+rows-1`` over the KV axis walked in ``tile_kv``-sized
    tiles through ``_flash_tile``. Walks every tile of the padded
    KV_max statically; tiles past ``kv_len`` are exact no-ops (see
    ``_flash_tile``), and a row block past the slot's real rows comes
    out zero."""
    kv_max, dh = ks.shape
    dv = vs.shape[1]
    n_tiles = -(-kv_max // tile_kv)
    pad = n_tiles * tile_kv - kv_max
    if pad:
        ks = jnp.concatenate(
            [ks, jnp.zeros((pad, dh), ks.dtype)], axis=0)
        vs = jnp.concatenate(
            [vs, jnp.zeros((pad, dv), vs.dtype)], axis=0)
    k_lo = _first_key(q_len, kv_len, window) if window else None

    def body(t, carry):
        k0 = t * tile_kv
        ks_t = jax.lax.dynamic_slice_in_dim(ks, k0, tile_kv)
        vs_t = jax.lax.dynamic_slice_in_dim(vs, k0, tile_kv)
        return _flash_tile(qs, ks_t, vs_t, k0, r0, q_len, kv_len, g,
                           *carry, window=window, k_lo=k_lo)

    _, l, acc = jax.lax.fori_loop(0, n_tiles, body,
                                  _flash_init(qs.shape[0], dv, sink))
    return _flash_final(l, acc, vs.dtype)


def _kernel(layer_ref, qlen_ref, kvlen_ref, tab_ref, *refs,
            pps: int, page_size: int, g: int, tile_pages: int, rb: int,
            window: int = 0, sinks: bool = False):
    """One slot, and the step's KV heads of it (all of them on a grid
    over slots alone): see the module docstring's loop nest. The K/V
    scratch is ``(2, tile_pages, heads, page_size, Dh)`` a pool — two
    buffers of one tile, a page's heads side by side as ONE copy lands
    them — and the flash state ``(heads, rows, 1 | Dh)`` float32, a
    (head, row block)'s slice loaded and stored around each
    ``_flash_tile``. ``nxt_ref`` (SMEM) hands the next live step the
    first tile this one started for it: the step's number + 1 (0:
    none), and the buffer it lands in.

    ``window`` (static): a row sees its last ``window`` keys only; the
    walk starts at the tile, and the copies at the page, of the slot's
    first visible key (``_first_key``), so what lies behind the window
    costs nothing. ``sinks``: one more scalar-prefetch operand, ``[H]``
    float32 sink logits (``_flash_init``). K and V may differ in head
    size (``q_ref`` the keys', ``o_ref`` the values')."""
    sink_ref, refs = (refs[0], refs[1:]) if sinks else (None, refs)
    (q_ref, kp_ref, vp_ref, o_ref, k_scr, v_scr, m_scr, l_scr, acc_scr,
     sems, nxt_ref) = refs
    s, j = pl.program_id(0), pl.program_id(1)
    n_slots, head_steps = pl.num_programs(0), pl.num_programs(1)
    step = s * head_steps + j
    heads, n_rows, dh = o_ref.shape
    dk = q_ref.shape[-1]
    qn = qlen_ref[s]
    tile_kv = tile_pages * page_size

    def live_pages(slot):
        # the table bounds the walk: a kv_len past it (a retiring
        # slot's overrun in the fused decode tail) is the table's
        # width, so no tile, page-table entry or key past it is read
        kn = jnp.minimum(kvlen_ref[slot], pps * page_size)
        return kn, pl.cdiv(kn, page_size)

    def tile_live_pages(n_pages, t):
        return jnp.minimum(tile_pages, n_pages - t * tile_pages)

    def first_key(slot):
        # the first key the slot's rows see under the window
        return _first_key(qlen_ref[slot],
                          jnp.minimum(kvlen_ref[slot], pps * page_size),
                          window)

    def tile_first_page(slot, t, n):
        # pages of tile t in front of the slot's first visible key:
        # never copied (0 without a window)
        if not window:
            return 0
        return jnp.clip(first_key(slot) // page_size - t * tile_pages, 0, n)

    def start_tile(slot, jh, n_pages, t, buf):
        # start the K and V copies of the live pages of tile t of
        # (slot, head step jh): loops of their count, so a slot's
        # copies cost what it holds and a page past the live range
        # moves no bytes (its stale scratch is masked by kv_len in
        # _flash_tile). ONE copy a pool moves a page's heads, strided
        # over the pool's head axis; a pool's copies into one buffer
        # share one semaphore.
        layer = layer_ref[0]

        def start_page(p):
            page = tab_ref[slot * pps + t * tile_pages + p]
            pltpu.make_async_copy(
                kp_ref.at[layer, pl.ds(jh * heads, heads), page],
                k_scr.at[buf, p], sems.at[0, buf]).start()
            pltpu.make_async_copy(
                vp_ref.at[layer, pl.ds(jh * heads, heads), page],
                v_scr.at[buf, p], sems.at[1, buf]).start()

        n = tile_live_pages(n_pages, t)
        lo = tile_first_page(slot, t, n)

        def chunk(c, carry):
            for i in range(PAGE_UNROLL):
                start_page(c * PAGE_UNROLL + i if not window
                           else lo + c * PAGE_UNROLL + i)
            return carry

        def rest(p, carry):
            start_page(p)
            return carry

        if not window:
            whole = n // PAGE_UNROLL
            jax.lax.fori_loop(0, whole, chunk, 0)
            jax.lax.fori_loop(whole * PAGE_UNROLL, n, rest, 0)
        else:
            whole = (n - lo) // PAGE_UNROLL
            jax.lax.fori_loop(0, whole, chunk, 0)
            jax.lax.fori_loop(lo + whole * PAGE_UNROLL, n, rest, 0)

    def start_next_step(buf):
        # the slot's last tile computes: start the first tile of the
        # next live step (this slot's next heads, else the next slot
        # with a query row) into the buffer that tile leaves free, so
        # that step does not start cold
        def dead(c):
            return (c < n_slots) & (
                qlen_ref[jnp.minimum(c, n_slots - 1)] == 0)

        more = j + 1 < head_steps
        s2 = jnp.where(more, s, jax.lax.while_loop(
            dead, lambda c: c + 1, s + 1))
        j2 = jnp.where(more, j + 1, 0)

        @pl.when(s2 < n_slots)
        def _():
            start_tile(s2, j2, live_pages(s2)[1],
                       first_key(s2) // tile_kv if window else 0, buf)
            nxt_ref[0] = s2 * head_steps + j2 + 1
            nxt_ref[1] = buf

    @pl.when(step == 0)
    def _():
        nxt_ref[0] = 0

    @pl.when(qn == 0)
    def _():
        # dead slot: emit defined zeros (the twin's fully-masked rows),
        # not stale output-buffer contents, and nothing else
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(qn > 0)
    def _():
        kn, n_pages = live_pages(s)
        n_tiles = pl.cdiv(kn, tile_kv)
        n_blocks = pl.cdiv(g * qn, rb)
        # the walk's first tile: the slot's first visible key's
        k_lo = first_key(s) if window else None
        t_lo = k_lo // tile_kv if window else 0

        def wait_tile(t, buf):
            # a DMA semaphore counts bytes, so ONE wait a pool awaits a
            # whole tile's copies: a descriptor over the whole buffer
            # (it is only its size: nothing is copied). The slot's last
            # tile, where it holds fewer pages, awaits them one by one.
            def wait(scr, lane, *at):
                dst = scr.at[(buf, *at)]
                pltpu.make_async_copy(dst, dst, sems.at[lane, buf]).wait()

            n = tile_live_pages(n_pages, t)
            lo = tile_first_page(s, t, n)
            copied = n - lo if window else n

            @pl.when(copied == tile_pages)
            def _():
                wait(k_scr, 0)
                wait(v_scr, 1)

            @pl.when(copied < tile_pages)
            def _():
                def wait_page(p, carry):
                    wait(k_scr, 0, p)
                    wait(v_scr, 1, p)
                    return carry

                jax.lax.fori_loop(lo, n, wait_page, 0)

        def rows(b):
            # a launch of one block (fewer rows than ROW_BLOCK, which
            # need not sit on the dtype's sublane tiling) reads it at
            # a static offset
            if n_rows == rb:
                return pl.ds(0, rb)
            return pl.ds(pl.multiple_of(b * rb, rb), rb)

        # a launch of one row block a head (a decode tick) takes no
        # loop over them, and its heads HEAD_UNROLL a trip
        one_block = n_rows == rb
        unroll = math.gcd(HEAD_UNROLL, heads) if one_block else 1

        def each_head(fn):
            def trip(i, carry):
                for u in range(unroll):
                    fn(i * unroll + u)
                return carry

            jax.lax.fori_loop(0, heads // unroll, trip, 0)

        def each_block(lo, hi, fn):
            # fn(head, rows) over the step's heads and row blocks lo..hi
            def head(h):
                if one_block:
                    return fn(h, 0, rows(0))

                def block_body(b, carry):
                    fn(h, b, rows(b))
                    return carry

                jax.lax.fori_loop(lo, hi, block_body, 0)

            each_head(head)

        def init_block(h, b, r):
            sink = None
            if sinks:
                h0 = (j * heads + h) * g
                sink = _sink_rows([sink_ref[h0 + i] for i in range(g)],
                                  b * rb, rb, g)
            m_scr[h, r], l_scr[h, r], acc_scr[h, r] = _flash_init(
                rb, dh, sink)

        each_block(0, n_blocks, init_block)
        # the step before may have started this one's first tile
        started = nxt_ref[0] == step + 1
        buf0 = jnp.where(started, nxt_ref[1], 0)

        @pl.when(jnp.logical_not(started))
        def _():
            start_tile(s, j, n_pages, t_lo, buf0)

        def tile_body(t, carry):
            buf = jax.lax.rem(buf0 + t if not window else buf0 + t - t_lo,
                              2)

            @pl.when(t + 1 < n_tiles)
            def _():
                start_tile(s, j, n_pages, t + 1, 1 - buf)

            @pl.when(t + 1 == n_tiles)
            def _():
                start_next_step(1 - buf)

            wait_tile(t, buf)

            def flash_block(h, b, r):
                # the head's tile is loaded inside the row-block loop:
                # a span's row blocks are few and the load is VMEM's
                m_scr[h, r], l_scr[h, r], acc_scr[h, r] = _flash_tile(
                    q_ref[h, r], k_scr[buf, :, h].reshape(tile_kv, dk),
                    v_scr[buf, :, h].reshape(tile_kv, dh), t * tile_kv,
                    b * rb, qn, kn, g, m_scr[h, r], l_scr[h, r],
                    acc_scr[h, r], window=window, k_lo=k_lo)

            each_block(0, n_blocks, flash_block)
            return carry

        jax.lax.fori_loop(t_lo, n_tiles, tile_body, 0)

        def final_block(h, b, r):
            o_ref[h, r] = _flash_final(l_scr[h, r], acc_scr[h, r],
                                       o_ref.dtype)

        each_block(0, n_blocks, final_block)

        if not one_block:
            def zero_block(h, b, r):
                o_ref[h, r] = jnp.zeros((rb, dh), o_ref.dtype)

            each_block(n_blocks, n_rows // rb, zero_block)


@functools.partial(jax.jit,
                   static_argnames=("g", "tile_pages", "interpret",
                                    "window", "head_major"))
def _pallas_impl(qs, k_pages, v_pages, layer, q_len, kv_len, tables, g,
                 tile_pages, interpret, window=0, sinks=None,
                 head_major=False):
    """qs ``[S, Hkv, R, Dh]`` pre-scaled, rows (t, g)-ordered, ``R`` a
    multiple of its row block; returns the same shape. k_pages/v_pages
    are the STACKED pools ``[L, Hkv, P, ps, Dh]`` and ``layer`` ``[1]``
    i32 picks the layer whose pages the DMAs read: the pools stay in
    HBM (``pl.ANY``) whole, nothing is sliced out. The grid is the
    slots, times the steps a slot's heads take (one wherever
    ``heads_per_step`` holds them all). The scratch shapes are the
    whole VMEM story — O(tile) and O(rows) a head, never O(pps).
    ``v_pages`` may hold another head size than ``k_pages`` (the
    result's); ``window`` / ``sinks [H]`` f32 as ``_kernel`` takes them
    (the sinks are one more scalar-prefetch operand). ``head_major``:
    ``qs`` and the result are ``[Hkv, S, R, D]`` (what a gather of the
    packed stream's rows a KV head gives: a span cut into virtual
    slots, ``_stream_launch``); a grid step's blocks are the same."""
    if head_major:
        Hkv, S, R, Dh = qs.shape
    else:
        S, Hkv, R, Dh = qs.shape
    Dv = v_pages.shape[-1]
    pps = tables.shape[1]
    page_size = k_pages.shape[3]
    tile_pages = min(int(tile_pages), pps)
    heads = heads_per_step(Hkv, tile_pages, page_size, Dh, k_pages.dtype, R,
                           None if Dv == Dh else Dv)
    kernel = functools.partial(_kernel, pps=pps, page_size=page_size, g=g,
                               tile_pages=tile_pages, rb=_row_block(R),
                               window=int(window), sinks=sinks is not None)
    block = pl.BlockSpec((None, heads, R, Dh),
                         lambda s, h, *_: (s, h, 0, 0))
    out_block = block if Dv == Dh else pl.BlockSpec(
        (None, heads, R, Dv), lambda s, h, *_: (s, h, 0, 0))
    out_shape = (S, Hkv, R, Dv)
    if head_major:
        block, out_block = (pl.BlockSpec(
            (heads, None, R, d), lambda s, h, *_: (h, s, 0, 0))
            for d in (Dh, Dv))
        out_shape = (Hkv, S, R, Dv)
    prefetch = (layer, q_len, kv_len, tables.reshape(-1))
    if sinks is not None:
        prefetch += (sinks.astype(jnp.float32),)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(S, Hkv // heads),
            in_specs=[
                block,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=out_block,
            scratch_shapes=[
                pltpu.VMEM((2, tile_pages, heads, page_size, Dh),
                           k_pages.dtype),
                pltpu.VMEM((2, tile_pages, heads, page_size, Dv),
                           v_pages.dtype),
                pltpu.VMEM((heads, R, 1), jnp.float32),
                pltpu.VMEM((heads, R, 1), jnp.float32),
                pltpu.VMEM((heads, R, Dv), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        out_shape=jax.ShapeDtypeStruct(out_shape, k_pages.dtype),
        interpret=interpret,
        # a stable name: how the kernel shows in lowered and compiled
        # program text (chip_smoke.py) and in a device trace (the
        # benchmark's reducer reads ``^ragged_paged_attention``)
        name="ragged_paged_attention",
    )(*prefetch, qs, k_pages, v_pages)


def _reference_impl(qs, k_pages, v_pages, q_len, kv_len, tables, g,
                    tile_pages: int = 0, window: int = 0, sinks=None):
    """Dense-gather reference with identical semantics: per slot,
    gather the table's pages and attend per kv head.

    ``tile_pages == 0`` is the ONE-SHOT reference (``_attend``),
    vmapped over (slot, head). ``tile_pages > 0`` is the kernel's
    DENSE TWIN: the same gather attended in the kernel's row blocks
    and KV tiles through ``_attend_tiled`` (the same ``_flash_tile`` as
    the kernel, every trip taken statically), proven bitwise-equal to
    the kernel by tests/test_ragged_attention.py. It takes its (slot,
    head, row block) steps one after another like the kernel's grid,
    not batched: a batched dot is another XLA program than the
    kernel's, and at some shapes rounds differently in the last
    place. ``window`` / ``sinks [H]`` as the kernel takes them; V may
    hold another head size than K (the result's)."""
    S, Hkv, R, Dh = qs.shape
    Dv = v_pages.shape[-1]
    pps = tables.shape[1]
    ps = k_pages.shape[2]
    kv_len = jnp.minimum(kv_len, pps * ps)     # the kernel's clamp

    def gathered(pages, h, tab):
        return pages[h][tab].reshape(pps * ps, pages.shape[-1])

    def sink_rows(h, r0, rows):
        if sinks is None:
            return None
        return _sink_rows(sinks.astype(jnp.float32).reshape(Hkv, g)[h],
                          r0, rows, g)

    if not tile_pages:
        def per_slot(q_s, qn, kn, tab):
            return jax.vmap(lambda qh, h: _attend(
                qh, gathered(k_pages, h, tab), gathered(v_pages, h, tab),
                qn, kn, g, window, sink_rows(h, 0, R)))(
                    q_s, jnp.arange(Hkv))

        return jax.vmap(per_slot)(qs, q_len, kv_len, tables)

    tile_kv = min(int(tile_pages), pps) * ps
    rb = _row_block(R)

    def step(shb):
        s, h, b = shb
        qb = jax.lax.dynamic_slice_in_dim(qs[s, h], b * rb, rb)
        return _attend_tiled(qb, gathered(k_pages, h, tables[s]),
                             gathered(v_pages, h, tables[s]), b * rb,
                             q_len[s], kv_len[s], g, tile_kv, window,
                             sink_rows(h, b * rb, rb))

    steps = jnp.stack(jnp.meshgrid(
        jnp.arange(S), jnp.arange(Hkv), jnp.arange(R // rb),
        indexing="ij"), -1).reshape(-1, 3)
    return jax.lax.map(step, steps).reshape(S, Hkv, R, Dv)


def _layer_pages(pages, layer):
    """One layer's ``[Hkv, P, ps, Dh]`` pages of a stacked pool, for the
    formulations that gather from them (the kernel never slices: it
    DMAs from ``pages_ref.at[layer, heads, page]``)."""
    if layer is None:
        return pages
    return jax.lax.dynamic_index_in_dim(pages, layer, 0, keepdims=False)


def ragged_paged_attention(q, k_pages, v_pages, q_len, kv_len, tables,
                           sm_scale=None, impl: str = "auto",
                           kv_tile_pages=None, layer=None, window: int = 0,
                           sinks=None):
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

    kv_tile_pages: the KV walk's tile. None (default) = geometry AUTO
    on the pallas path — a persistent autotune winner for this
    geometry if ``kernel_bench --ragged-sweep`` recorded one, else
    ``default_kv_tile_pages`` — and the one-shot reference on the
    dense path (it has no VMEM to protect); N > 0 = an N-page tile
    (dense included — the kernel's bitwise twin); 0 = one softmax
    over the whole context: the one-shot reference on the dense path,
    the whole table in one tile on the kernel's.

    Three arguments that default to "none" (a launch without them is
    the program it always was): ``v_pages`` of ANOTHER HEAD SIZE than
    ``k_pages`` (``q`` carries the keys', the result the values');
    ``window``: a query sees its own position and the ``window - 1``
    before it, and the walk skips the tiles (and the copies the pages)
    wholly behind a slot's first visible key; ``sinks [H]``: a learned
    logit a head (in the scaled scores' units) that joins the softmax's
    denominator and takes no value.
    """
    if impl not in ("auto", "pallas", "dense"):
        raise ValueError(f"impl must be auto|pallas|dense, got {impl!r}")
    S, Tq, H, Dh = q.shape
    Hkv, _, page_size, _ = k_pages.shape[-4:]
    Dv = v_pages.shape[-1]
    extra = dict(window=int(window), sinks=sinks)
    if H % Hkv:
        raise ValueError(f"H={H} not a multiple of Hkv={Hkv}")
    G = H // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(Dh))
    q_len = jnp.asarray(q_len, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    pps = int(tables.shape[1])
    # [S, Tq, H, Dh] -> [S, Hkv, Tq*G, Dh], rows (t, g)-ordered: a
    # slot's real rows are its first G*q_len, which is what lets the
    # walk stop at them — the head axis is kv-head-major (H = Hkv*G),
    # matching the GQA reshape every other kernel in the repo uses
    qs = (q * sm_scale).astype(q.dtype)
    qs = qs.reshape(S, Tq, Hkv, G, Dh).transpose(0, 2, 1, 3, 4)
    qs = qs.reshape(S, Hkv, Tq * G, Dh)
    use_pallas = impl == "pallas" or (impl == "auto" and _on_tpu())
    rows = Tq * G
    if use_pallas:
        tile = _tile_pages(pps, page_size, Dh, k_pages.dtype, kv_tile_pages)
    else:
        tile = int(kv_tile_pages or 0)
    if tile:
        # whole row blocks (the twin walks the kernel's)
        pad = -rows % _row_block(rows)
        if pad:
            qs = jnp.pad(qs, ((0, 0), (0, 0), (0, pad), (0, 0)))
    if use_pallas:
        # one kernel body: a single layer's 4-D pool enters as a
        # one-layer stack (a bitcast) read at layer 0
        if layer is None:
            k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
        layer = jnp.asarray(layer, jnp.int32).reshape(1)
        out = _pallas_impl(qs, k_pages, v_pages, layer, q_len, kv_len,
                           tables, g=G, tile_pages=tile,
                           interpret=not _on_tpu(), **extra)
    else:
        out = _reference_impl(qs, _layer_pages(k_pages, layer),
                              _layer_pages(v_pages, layer), q_len, kv_len,
                              tables, g=G, tile_pages=tile, **extra)
    out = out[:, :, :rows].reshape(S, Hkv, Tq, G, Dv)
    return out.transpose(0, 2, 1, 3, 4).reshape(S, Tq, H, Dv).astype(q.dtype)


def ragged_paged_attention_reference(q, k_pages, v_pages, q_len, kv_len,
                                     tables, sm_scale=None, window: int = 0,
                                     sinks=None):
    """The dense-gather formulation, directly (tests reach it via
    ``impl="dense"`` too)."""
    return ragged_paged_attention(q, k_pages, v_pages, q_len, kv_len,
                                  tables, sm_scale=sm_scale, impl="dense",
                                  window=window, sinks=sinks)


def _packed_impl(q, k_pages, v_pages, tok_slot, tok_qoff, q_len, kv_len,
                 tables, sm_scale, window: int = 0, sinks=None):
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
    Dv = v_pages.shape[-1]
    sl = jnp.minimum(tok_slot, S - 1)
    tabs_t = tables[sl]                                     # [T, pps]
    ks = k_pages[:, tabs_t].reshape(Hkv, T, KV, Dh)
    vs = v_pages[:, tabs_t].reshape(Hkv, T, KV, Dv)
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
    if window:
        mask = mask & (k_idx > hi - window)
    m4 = mask[:, None, None, :]
    s = jnp.where(m4, s, _MASK)
    m = jnp.max(s, axis=-1, keepdims=True)
    if sinks is not None:
        sink = sinks.astype(jnp.float32).reshape(1, Hkv, G, 1)
        m = jnp.maximum(m, sink)
    p = jnp.exp(s - m)
    p = jnp.where(m4, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if sinks is not None:
        l = l + jnp.exp(sink - m)
    o = jnp.einsum("tkgs,ktsd->tkgd", p.astype(vs.dtype), vs)
    o = o / jnp.where(l > 0, l, 1.0).astype(o.dtype)
    return o.reshape(T, H, Dv).astype(q.dtype)


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


def _span_blocks(start, q_len, kv_len, tables, tok_slot, tok_qoff, T: int,
                 bt: int, tq: int):
    """A tick's spans as the VIRTUAL SLOTS the kernel is launched over,
    at most ``bt`` tokens each: ``(q_len_v, kv_len_v, tables_v, start_v
    [NV], slot_v [T], off_v [T])``. Where ``bt >= tq`` nothing is cut:
    the virtual slots ARE the slots. Else slot ``s`` (rows ``start[s] ..
    start[s] + q_len[s] - 1`` of the packed stream of ``T`` rows)
    becomes ``ceil(q_len[s] / bt)`` of them, in order; block ``b`` of it
    holds its tokens ``b·bt ..`` and sees the keys up to its own last
    token (``kv_len_v``: bottom-right causal makes a block of a span
    exactly a span of its own), over the slot's table row. ``NV = S +
    ceil(T / bt)`` bounds their number; the rest are dead (``q_len_v``
    0). ``start_v`` is the stream row of each virtual slot's first token
    (``T``: none), ``slot_v`` / ``off_v`` each stream row's virtual slot
    (``NV``: a padding row) and its place in it.

    WHY: the kernel holds a slot's rows whole in one VMEM block, which a
    512-token span of 64 heads is 32 times too large for
    (``mla_paged_attention.py``'s docstring has the arithmetic); ``bt``
    tokens are not, and a span re-walks its pages once a block as that
    kernel's does."""
    S = q_len.shape[0]
    if bt >= tq:
        return (q_len, kv_len, tables, jnp.where(q_len > 0, start, T),
                tok_slot, tok_qoff)
    nv = S + -(-T // bt)
    nb = (q_len + bt - 1) // bt                         # blocks a slot
    ends = jnp.cumsum(nb)
    first = ends - nb                                   # a slot's first
    v = jnp.arange(nv, dtype=jnp.int32)
    owner = jnp.searchsorted(ends, v, side="right").astype(jnp.int32)
    o = jnp.minimum(owner, S - 1)
    sub = (v - first[o]) * bt                           # tokens before it
    qv = jnp.where(owner < S, jnp.clip(q_len[o] - sub, 0, bt), 0)
    kvv = kv_len[o] - q_len[o] + sub + qv
    sl = jnp.minimum(tok_slot, S - 1)
    return (qv.astype(jnp.int32), kvv.astype(jnp.int32), tables[o],
            jnp.where(qv > 0, start[o] + sub, T).astype(jnp.int32),
            jnp.where(tok_slot < S, first[sl] + tok_qoff // bt,
                      nv).astype(jnp.int32),
            (tok_qoff % bt).astype(jnp.int32))


class StreamPlan(NamedTuple):
    """What the kernel's path needs of a tick's packing, from its
    metadata and the launch's head counts alone, so a walk of many
    layers makes it ONCE a tick (``stream_plan``): the (virtual) slots
    the kernel is launched over (``_span_blocks``) and where their rows
    lie in the stream and in the kernel's result. WHOLE slots:
    ``rows_in [NV]`` the stream row of each slot's first token, ``spans
    [NV]`` the slots that hold MORE than one token, compacted in front,
    ``n_spans`` their count, ``rows_out`` each stream row's slot
    (``[T]``: a decode launch) or its rows of the flat result (``[T,
    Hkv, G]``). A span CUT into virtual slots (``cut``): ``rows_in [NV,
    bt]`` every virtual row's stream row (``T``: none), ``rows_out [T]``
    each stream row's place among the ``NV·bt`` virtual rows.
    ``real [T, 1, 1]`` the rows that are tokens, ``bt`` the tokens a
    slot's block holds, ``heads`` the ``(kv heads, query heads a kv
    head)`` it was made for."""
    q_len: jax.Array
    kv_len: jax.Array
    tables: jax.Array
    rows_in: jax.Array
    spans: Optional[jax.Array]
    n_spans: Optional[jax.Array]
    rows_out: jax.Array
    real: jax.Array
    bt: int
    heads: tuple
    cut: bool


def stream_plan(tok_slot, tok_qoff, q_len, kv_len, tables, tq: int,
                heads: int, pool, start=None,
                block_tokens: int = 0) -> StreamPlan:
    """The ``StreamPlan`` of a tick (metadata as
    ``ragged_paged_attention_packed`` takes it) for launches of
    ``heads`` query heads over ``pool``, the K pool as the kernel reads
    it (``[..., Hkv, P, page_size, D]``; lane-packed or not: its own KV
    heads are the kernel's). ``start [S]``: each slot's first row in
    the stream (a slot without a row: anything); without it, read from
    ``tok_slot``. ``block_tokens``: a launch of more query rows a slot
    is cut into virtual slots of that many (``_span_blocks``), so the
    kernel's VMEM does not grow with the chunk."""
    tok_slot = jnp.asarray(tok_slot, jnp.int32)
    tok_qoff = jnp.asarray(tok_qoff, jnp.int32)
    q_len = jnp.asarray(q_len, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    T, S = tok_slot.shape[0], tables.shape[0]
    Hkv = int(pool.shape[-4])
    G = int(heads) // Hkv
    if start is None:
        start = jnp.full((S + 1,), T, jnp.int32).at[tok_slot].min(
            jnp.arange(T, dtype=jnp.int32))[:S]
    start = jnp.clip(jnp.asarray(start, jnp.int32), 0, T)
    bt = min(int(block_tokens) or int(tq), int(tq))
    qv, kvv, tabs, start_v, slot_v, off_v = _span_blocks(
        start, q_len, kv_len, tables, tok_slot, tok_qoff, T, bt, int(tq))
    nv, R = qv.shape[0], _block_rows(bt * G)
    real = (slot_v < nv)[:, None, None]                 # over [T, H, Dv]
    slot_c = jnp.minimum(slot_v, nv - 1)
    if bt < int(tq):
        j = jnp.arange(bt, dtype=jnp.int32)[None]
        rows_in = jnp.where(j < qv[:, None], start_v[:, None] + j, T)
        return StreamPlan(qv, kvv, tabs, rows_in, None, None,
                          slot_c * bt + off_v, real, bt, (Hkv, G), True)
    if bt == 1:
        rows_out = slot_c
    else:
        rows_out = ((slot_c * (Hkv * R) + off_v * G)[:, None, None]
                    + jnp.arange(Hkv, dtype=jnp.int32)[None, :, None] * R
                    + jnp.arange(G, dtype=jnp.int32)[None, None, :])
    span = qv > 1
    at = jnp.where(span, jnp.cumsum(span) - 1, nv)
    spans = jnp.zeros((nv,), jnp.int32).at[at].set(
        jnp.arange(nv, dtype=jnp.int32), mode="drop")
    return StreamPlan(qv, kvv, tabs, jnp.minimum(start_v, T - 1), spans,
                      jnp.sum(span).astype(jnp.int32), rows_out, real, bt,
                      (Hkv, G), False)


def _rows(x, at):
    """``x[at]`` along axis 0 for indices that ARE in bounds: the bare
    gather, none of the wrap-around and clamp arithmetic indexing puts
    in front of it (a layer loop would repeat it a layer)."""
    return jax.lax.gather(
        x, at[..., None], jax.lax.GatherDimensionNumbers(
            offset_dims=tuple(range(at.ndim, at.ndim + x.ndim - 1)),
            collapsed_slice_dims=(0,), start_index_map=(0,)),
        (1,) + x.shape[1:], mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def _block_rows(rows: int) -> int:
    """``rows`` query rows a (slot, kv head) as whole row blocks."""
    return rows + -rows % _row_block(rows)


def _stream_launch(q, k_pages, v_pages, plan: StreamPlan, sm_scale, impl,
                   kv_tile_pages, layer, extra):
    """``q [T, H, Dk]`` (the packed stream) through the kernel, or its
    dense twin, over the plan's (virtual) slots: ``[T, H, Dv]``, padding
    rows zero. WHOLE slots, where the buffer is many times the stream
    (``S · tq`` tokens for ``S + tq``): ONE write of the kernel's query
    buffer in front of it (``[NV, Hkv, R, Dk]``): every slot's first
    token, a row gather of the stream, padded out to the block's ``R``
    rows; then the slots that hold a SPAN, and they alone, get their
    block (``[Hkv, R, Dk]``) as ONE window of the stream re-laid at ITS
    size, a KV head at a time with rows (token, group)-ordered (``[Hkv,
    T·G, Dk]``), from the slot's first row on (a slot's rows are
    contiguous in the stream), copied in place. What a window holds past
    its slot's rows are the next slots' (finite, masked by the kernel,
    never read back). Behind the kernel the stream's rows are gathered
    straight out of its result by the inverse map, a row a (token,
    head): nothing of the buffer's size stands between. A decode
    launch's block IS a stream row: no pad, no span, and its results are
    put back by slot. A span CUT into virtual slots, where the buffer
    is about the stream's size: one token gather a KV head out of
    ``[Hkv, T + 1, G·Dk]`` straight into head-major blocks and one back
    (in MiMo's whole tick that launch is 1.5 ms a span tick FASTER than
    the loop and the row gather, which win when timed alone: PERF.md
    section 6, PR 48)."""
    T, H, Dk = q.shape
    Hkv, _, page_size, _ = k_pages.shape[-4:]
    G, Dv = H // Hkv, v_pages.shape[-1]
    nv, bt = plan.q_len.shape[0], plan.bt
    R = _block_rows(bt * G)
    if plan.heads != (Hkv, G) or plan.rows_out.shape[0] != T:
        raise ValueError(
            f"a plan for {plan.rows_out.shape[0]} rows of {plan.heads} "
            f"(kv heads, group) cannot place a stream of {T} rows of "
            f"{(Hkv, G)}")
    if plan.cut:
        qh = (q * sm_scale).astype(q.dtype).reshape(T, Hkv, G * Dk)
        qh = jnp.concatenate(
            [qh.transpose(1, 0, 2), jnp.zeros((Hkv, 1, G * Dk), q.dtype)], 1)
        qs = qh[:, plan.rows_in].reshape(Hkv, nv, bt * G, Dk)
        qs = jnp.pad(qs, ((0, 0), (0, 0), (0, R - bt * G), (0, 0)))
    else:
        # every slot's FIRST token, one row gather; the kernel's block
        # holds it in front of R - G rows that only a span fills
        qs = (_rows(q, plan.rows_in) * sm_scale).astype(q.dtype)
        qs = qs.reshape(nv, Hkv, G, Dk)
        if bt > 1:
            qs = jnp.pad(qs, ((0, 0), (0, 0), (0, R - G), (0, 0)))
            qh = (q * sm_scale).astype(q.dtype).reshape(T, Hkv, G, Dk)
            qh = qh.transpose(1, 0, 2, 3).reshape(Hkv, T * G, Dk)
            qh = jnp.pad(qh, ((0, 0), (0, R), (0, 0)))

            def copy_span(i, qs):
                # a span's block is ONE window of the head-major stream
                v = plan.spans[i]
                rows = jax.lax.dynamic_slice(
                    qh, (0, plan.rows_in[v] * G, 0), (Hkv, R, Dk))
                return jax.lax.dynamic_update_slice(qs, rows[None],
                                                    (v, 0, 0, 0))

            qs = jax.lax.fori_loop(0, plan.n_spans, copy_span, qs)
    use_pallas = impl == "pallas" or (impl == "auto" and _on_tpu())
    if use_pallas:
        tile = _tile_pages(plan.tables.shape[1], page_size, Dk,
                           k_pages.dtype, kv_tile_pages)
        # one kernel body: a single layer's 4-D pool enters as a
        # one-layer stack (a bitcast) read at layer 0
        if layer is None:
            k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
        out = _pallas_impl(
            qs, k_pages, v_pages, jnp.asarray(layer, jnp.int32).reshape(1),
            plan.q_len, plan.kv_len, plan.tables, g=G, tile_pages=tile,
            interpret=not _on_tpu(), head_major=plan.cut, **extra)
    else:
        major = (1, 0, 2, 3) if plan.cut else (0, 1, 2, 3)
        out = _reference_impl(
            qs.transpose(major), _layer_pages(k_pages, layer),
            _layer_pages(v_pages, layer), plan.q_len, plan.kv_len,
            plan.tables, g=G, tile_pages=int(kv_tile_pages or 0),
            **extra).transpose(major)
    # the way back is the plan's: a cut span's virtual rows a KV head, a
    # decode launch's slots, else the flat result's rows
    if plan.cut:
        o = out[:, :, :bt * G].reshape(Hkv, nv * bt, G * Dv)
        o = o[:, plan.rows_out].transpose(1, 0, 2)
    else:
        o = _rows(
            out.reshape((nv, H, Dv) if bt == 1 else (nv * Hkv * R, Dv)),
            plan.rows_out)
    return jnp.where(plan.real, o.reshape(T, H, Dv), 0).astype(q.dtype)


def ragged_paged_attention_packed(q, k_pages, v_pages, tok_slot, tok_qoff,
                                  q_len, kv_len, tables, tq: int,
                                  sm_scale=None, impl: str = "auto",
                                  kv_tile_pages=None, layer=None,
                                  window: int = 0, sinks=None, plan=None):
    """Packed-layout entry for the serving tick: ``q [T, H, Dh]`` is
    the tick's token stream with per-token owner/offset metadata
    (``tok_slot [T]`` — ``S`` = padding sentinel; ``tok_qoff [T]``); a
    slot's rows are CONTIGUOUS in it, in ``tok_qoff`` order, as the
    engine packs them (padding rows anywhere between slots). Returns
    ``[T, H, Dh]`` (padding rows zero).

    impl: "auto" — the work-proportional packed formulation off-TPU,
    the Pallas kernel on TPU (``_stream_launch``: each slot's rows
    copied straight into the kernel's block, its results gathered
    straight back); "pallas"/"dense" force the kernel / its
    dense reference over that same boundary; "packed" forces the packed
    formulation.
    ``kv_tile_pages`` rides through to the slot-major walk selection
    (None = geometry auto — the serving tick passes nothing and a
    100k-token table picks the tiled walk by itself on TPU).
    ``layer`` as in ``ragged_paged_attention``: with it the pools are
    the stacked ``[L, Hkv, P, page_size, Dh]``. Pools with rows wider
    than ``Dh`` are lane-packed (``lane_pack_factor``).

    ``window`` / ``sinks`` / a ``v_pages`` of another head size as
    ``ragged_paged_attention`` takes them (with any of them the pools
    are never lane-packed: ``q`` brings the keys' head size).
    ``plan``: the kernel's path reads the packing from it alone
    (``stream_plan`` of the same metadata and ``tq`` over ``k_pages``,
    which also says where a slot's rows start and whether a span is cut
    into virtual slots): a walk of many layers makes it once a tick
    outside them, where the index arithmetic is then not repeated a
    layer. Without one it is made here, from the metadata, un-cut.
    """
    if impl not in ("auto", "pallas", "dense", "packed"):
        raise ValueError(
            f"impl must be auto|pallas|dense|packed, got {impl!r}")
    T, H, Dh = q.shape
    extra = dict(window=int(window), sinks=sinks)
    if k_pages.shape[-1] != Dh:
        member, f = _lane_member(q, k_pages)
        o = ragged_paged_attention_packed(
            _lane_widen(q, member, f), k_pages, v_pages, tok_slot, tok_qoff,
            q_len, kv_len, tables, tq,
            sm_scale=sm_scale or 1.0 / float(np.sqrt(Dh)), impl=impl,
            kv_tile_pages=kv_tile_pages, layer=layer, plan=plan)
        return _lane_narrow(o, member, f)
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(Dh))
    if impl == "packed" or (impl == "auto" and not _on_tpu()):
        return _packed_impl(
            q, _layer_pages(k_pages, layer), _layer_pages(v_pages, layer),
            jnp.asarray(tok_slot, jnp.int32), jnp.asarray(tok_qoff, jnp.int32),
            jnp.asarray(q_len, jnp.int32), jnp.asarray(kv_len, jnp.int32),
            jnp.asarray(tables, jnp.int32), sm_scale, **extra)
    if plan is None:
        plan = stream_plan(tok_slot, tok_qoff, q_len, kv_len, tables, tq, H,
                           k_pages)
    return _stream_launch(q, k_pages, v_pages, plan, sm_scale, impl,
                          kv_tile_pages, layer, extra)


# ---------------------------------------------------------------------------
# kernel-audit registration (analysis/kernel_audit.py)
# ---------------------------------------------------------------------------
# Geometry keys are EXACTLY the autotune lookup kwargs above, so every
# winners.json entry for this kind audits directly. The flagship
# geometry's table fits one tile (the walk's one-trip case); the
# long-context and head-size-64 geometries walk several, double-
# buffered. KA001 proves the scratch O(tile) + O(rows), KA003 the
# start / wait pairing of the page-copy loops.

AUDIT_KIND = "ragged_paged_attention"
AUDIT_GEOM_KEYS = ("pages_per_slot", "page_size", "head_dim", "dtype")
AUDIT_CONFIG_KEYS = ("kv_tile_pages",)
AUDIT_GEOMETRIES = (
    # serving flagship: 256-token table, one tile
    {"pages_per_slot": 16, "page_size": 16, "head_dim": 128,
     "dtype": "bfloat16"},
    # long context: 16k tokens in 32 tiles of 512
    {"pages_per_slot": 1024, "page_size": 16, "head_dim": 128,
     "dtype": "bfloat16"},
    # head size 64, 2k-token table: a lane-packed pool, so the launch
    # under audit is the 128-wide one with half the KV heads and twice
    # the query rows a head (see ``lane_pack_factor``)
    {"pages_per_slot": 128, "page_size": 16, "head_dim": 64,
     "dtype": "bfloat16"},
)
# the audited launch: 4 slots, 2 KV heads of 2 query heads, 8-row spans
AUDIT_SHAPE = dict(slots=4, kv_heads=2, group=2, tq=8)


def audit_launches(geom, config=None):
    """Zero-execution traceable launches for the kernel auditor: big
    tensors as ShapeDtypeStructs, scalar-prefetch metadata (layer,
    q_len, kv_len, tables) concrete so KA002 can evaluate the index
    maps."""
    pps = int(geom["pages_per_slot"])
    ps = int(geom["page_size"])
    dh = int(geom["head_dim"])
    dt = jnp.dtype(geom["dtype"])
    S, Hkv, G, Tq = (AUDIT_SHAPE[k]
                     for k in ("slots", "kv_heads", "group", "tq"))
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
        tile = int(config["kv_tile_pages"]) or pps
    else:
        tile = default_kv_tile_pages(pps, ps, dh, dt)
    tile = min(tile, pps)
    fn = functools.partial(_pallas_impl, g=G, tile_pages=tile,
                           interpret=False)
    return [(f"walk[kv_tile_pages={tile}]", fn, args)]
