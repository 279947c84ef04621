"""Attention over a LATENT paged cache (multi-head latent attention, the
absorbed form): the sibling of ``ragged_paged_attention.py`` for a model
whose cache row is one compressed vector a token, shared by every head.

What a token leaves in the pool is ``[c_kv | k_r | 0]``: the normed,
scaled KV latent (``dv`` values: 512), the rotary key every head shares
(64) and zeros up to a multiple of the chip's 128 lanes (``dk``: 640).
There is ONE "KV head"; its KEY is the whole row, its VALUE the row's
first ``dv`` lanes — V is a lane-prefix of K, one pool, every live page
read once a walk. A query row is a (token, head) pair: ``[q_abs | q_r |
0]`` with ``q_abs[h] = q_nope[h] @ W_kvb_K[h].T`` (the key up-projection
absorbed into the query), so a decode slot brings ``H`` rows (64) and a
512-token span 32 768. The result is ``p @ c_kv`` a row (``dv`` wide);
the caller applies ``W_kvb_V`` a head.

WHY A SIBLING, NOT THE RAGGED KERNEL GENERALISED (PERF.md, PR 40): that
kernel takes its queries SLOT-MAJOR (``[S, Hkv, Tq·G, Dh]``, a slot's
rows whole in one VMEM block). At this model's 48 slots x 512 rows x 64
heads x 640 lanes that layout is 2.0 GB a launch and one slot's block
42 MB of a 16 MiB VMEM. Here the queries stay PACKED as the tick's token
stream is (``[T·H, dk]`` in HBM), a slot's rows are found by ``start``
and ``q_len``, and the kernel moves its own row blocks.

Loop nest (grid: a step is a SLOT, PR 39's lesson; trip counts are the
tick's data, PR 31's):

* a slot with ``q_len == 0`` does nothing;
* a slot with ``q_len == 1`` (a decode row) is ONE block of ``H`` rows;
* any other span is walked in blocks of ``block_tokens`` tokens
  (``block_tokens · H`` rows: 1024 at 16), the last one partial;
* a block walks the pages its rows can see — keys ``0 .. (kv_len −
  q_len) + its last token`` (bottom-right causal), so a chunk's early
  blocks skip the chunk's late keys — in tiles of ``kv_tile_pages``
  pages with the flash combine (float32 running max, denominator,
  accumulator), double-buffered: tile ``t + 1``'s page copies start
  while tile ``t`` computes, and a block's last tile starts the NEXT
  block's first tile (this slot's next block, else the next live
  slot's first), so only a launch's first block starts cold.

A span re-walks its pages once a block. That is affordable where the
ragged kernel's 128-row blocks would not be: a block of 1024 rows does
1024 x tile x (dk + dv) x 2 FLOP a tile of ``tile x dk x 2`` bytes, 1840
FLOP a byte against the chip's ridge of 240, so the re-read hides behind
the block's own matmuls.

A ``kv_len`` past the table is read as the table's width (the fused
decode tail steps a retiring slot past its last page), as in the ragged
kernel. Rows the launch does not own (padding tokens, dead slots) come
out ZERO.

Off-TPU the kernel runs in interpreter mode; ``impl="auto"`` takes the
plain ``jax.numpy`` formulation there (``impl="dense"``: one softmax a
packed row over its slot's gathered pages), which is also the reference
the tests hold the kernel to.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import on_tpu as _on_tpu
from .ragged_paged_attention import LANES, PAGE_UNROLL, _MASK

__all__ = ["mla_paged_attention", "latent_row_width", "BLOCK_TOKENS",
           "default_kv_tile_pages"]

# tokens a span's row block (x H rows): the score block in VMEM is
# BLOCK_TOKENS·H x tile float32. 16, not 8 or 4: a launch of 44 decode
# rows and a 512-row span over 4096 tokens read 2.39 / 2.56 / 2.77 ms
# (kernel_bench --mla-sweep on a v5e, PR 40)
BLOCK_TOKENS = 16
# cache tokens a KV tile
TILE_TOKENS = 512
# what the kernel may pin of VMEM (the chip's default scoped limit is
# 16 MiB; a 1024-row block with its score temporaries needs ~14)
VMEM_LIMIT_BYTES = 40 * 2 ** 20


def latent_row_width(kv_lora_rank: int, rope_dim: int) -> int:
    """Lanes a pool row takes: ``[c_kv | k_r]`` padded with zeros to a
    multiple of the chip's 128 lanes (576 -> 640)."""
    return -(-(int(kv_lora_rank) + int(rope_dim)) // LANES) * LANES


def default_kv_tile_pages(pages_per_slot: int, page_size: int) -> int:
    return min(int(pages_per_slot), max(1, TILE_TOKENS // int(page_size)))


def _dot(a, b, dims):
    """MXU dot with a float32 result (scores and the accumulator stay
    float32: a 576-deep score rounded to bfloat16 would carry 0.4 % of
    its size into the softmax)."""
    if a.dtype == jnp.float32:
        return jax.lax.dot_general(a, b, dims)
    return jax.lax.dot_general(
        a, b, dims, precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)


def _flash_tile(q, k, k0, t0, base, qn, n_keys, heads: int, dv: int, m,
                l, acc):
    """One (row block, KV tile) step of the online softmax. ``q [R,
    dk]`` rows ordered (token, head), the block's first token ``t0`` of
    the span; ``k [tile, dk]`` the tile's rows at key positions ``k0
    ..``; row of token ``t`` sees keys ``<= base + t`` (``base = kn -
    qn``); tokens past ``qn`` are masked whole. Positions ``>= n_keys``
    (the block's horizon: no page past it was copied) may hold stale
    scratch: their scores are REPLACED and their values zeroed."""
    tile = k.shape[0]
    live = (k0 + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)) < n_keys
    v = jnp.where(live, k[:, :dv], 0)
    s = _dot(q, k, (((1,), (1,)), ((), ())))
    t = t0 + jax.lax.div(
        jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], 1), 0),
        jnp.int32(heads))
    k_idx = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    mask = (t < qn) & (k_idx <= base + t) & (k_idx < n_keys)
    s = jnp.where(mask, s, _MASK)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + _dot(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())))
    return m_new, l_new, acc_new


def _kernel(layer_ref, start_ref, qlen_ref, kvlen_ref, tab_ref, q_hbm,
            lat_hbm, o_hbm, q_scr, o_scr, k_scr, m_scr, l_scr, acc_scr,
            sems, nxt_ref, *, pps: int, page_size: int, heads: int,
            tile_pages: int, tb: int, dv: int):
    """One slot: its blocks, each walking the pages it can see. ``sems``:
    ``[0, buf]`` a K buffer's page copies, ``[1, 0]`` the query rows,
    ``[1, 1]`` the result rows. ``nxt_ref`` (SMEM): the block whose
    first tile the block before started (its number + 1; 0 none), and
    the buffer it lands in."""
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    tile_kv = tile_pages * page_size
    cap = pps * page_size
    layer = layer_ref[0]

    def geometry(slot):
        qn = qlen_ref[slot]
        kn = jnp.minimum(kvlen_ref[slot], cap)
        return qn, kn

    def block_tokens(qn):
        # a decode row is a block of one token; any other span goes in
        # blocks of tb
        return jnp.where(qn == 1, 1, tb)

    def block_keys(qn, kn, b):
        """Keys block ``b`` of a span can see: up to its last token's
        horizon, never past ``kn``."""
        return jnp.minimum(kn, (kn - qn) + (b + 1) * block_tokens(qn))

    def start_tile(slot, n_keys, t, buf):
        n_pages = pl.cdiv(n_keys, page_size)
        n = jnp.minimum(tile_pages, n_pages - t * tile_pages)

        def start_page(p):
            page = tab_ref[slot * pps + t * tile_pages + p]
            pltpu.make_async_copy(lat_hbm.at[layer, page],
                                  k_scr.at[buf, p], sems.at[0, buf]).start()

        def chunk(c, carry):
            for i in range(PAGE_UNROLL):
                start_page(c * PAGE_UNROLL + i)
            return carry

        def rest(p, carry):
            start_page(p)
            return carry

        whole = n // PAGE_UNROLL
        jax.lax.fori_loop(0, whole, chunk, 0)
        jax.lax.fori_loop(whole * PAGE_UNROLL, n, rest, 0)

    def wait_tile(n_keys, t, buf):
        n_pages = pl.cdiv(n_keys, page_size)
        n = jnp.minimum(tile_pages, n_pages - t * tile_pages)

        def wait(*at):
            dst = k_scr.at[(buf, *at)]
            pltpu.make_async_copy(dst, dst, sems.at[0, buf]).wait()

        @pl.when(n == tile_pages)
        def _():
            wait()

        @pl.when(n < tile_pages)
        def _():
            def wait_page(p, carry):
                wait(p)
                return carry

            jax.lax.fori_loop(0, n, wait_page, 0)

    def unit_no(slot, b):
        # a block's number in the launch: slots x (blocks a slot could
        # have) + 1 (0 = none)
        return slot * 65536 + b + 1

    def start_next(slot, qn, kn, b, n_blocks, buf):
        """The block after ``(slot, b)``: this slot's next, else the
        next live slot's first; start its first tile into ``buf``."""
        def dead(c):
            return (c < n_slots) & (
                qlen_ref[jnp.minimum(c, n_slots - 1)] == 0)

        more = b + 1 < n_blocks
        s2 = jnp.where(more, slot, jax.lax.while_loop(
            dead, lambda c: c + 1, slot + 1))
        b2 = jnp.where(more, b + 1, 0)

        @pl.when(s2 < n_slots)
        def _():
            qn2, kn2 = geometry(jnp.minimum(s2, n_slots - 1))
            start_tile(s2, block_keys(qn2, kn2, b2), 0, buf)
            nxt_ref[0] = unit_no(s2, b2)
            nxt_ref[1] = buf

    @pl.when(s == 0)
    def _():
        nxt_ref[0] = 0

    qn, kn = geometry(s)
    start = start_ref[s]

    def run_block(b, n_tok: int, n_blocks):
        """Block ``b`` of ``n_tok`` (static) tokens of this slot."""
        rows = n_tok * heads
        t0 = b * n_tok
        row0 = (start + t0) * heads
        n_keys = block_keys(qn, kn, b)
        n_tiles = pl.cdiv(n_keys, tile_kv)
        q_copy = pltpu.make_async_copy(
            q_hbm.at[pl.ds(row0, rows)], q_scr.at[pl.ds(0, rows)],
            sems.at[1, 0])
        q_copy.start()
        started = nxt_ref[0] == unit_no(s, b)
        buf0 = jnp.where(started, nxt_ref[1], 0)

        @pl.when(jnp.logical_not(started))
        def _():
            start_tile(s, n_keys, 0, buf0)

        m_scr[pl.ds(0, rows)] = jnp.full((rows, 1), _MASK, jnp.float32)
        l_scr[pl.ds(0, rows)] = jnp.zeros((rows, 1), jnp.float32)
        acc_scr[pl.ds(0, rows)] = jnp.zeros((rows, dv), jnp.float32)
        q_copy.wait()

        def tile_body(t, carry):
            buf = jax.lax.rem(buf0 + t, 2)

            @pl.when(t + 1 < n_tiles)
            def _():
                start_tile(s, n_keys, t + 1, 1 - buf)

            @pl.when(t + 1 == n_tiles)
            def _():
                start_next(s, qn, kn, b, n_blocks, 1 - buf)

            wait_tile(n_keys, t, buf)
            r = pl.ds(0, rows)
            m_scr[r], l_scr[r], acc_scr[r] = _flash_tile(
                q_scr[r], k_scr[buf].reshape(tile_kv, k_scr.shape[-1]),
                t * tile_kv, t0, kn - qn, qn, n_keys, heads, dv, m_scr[r],
                l_scr[r], acc_scr[r])
            return carry

        jax.lax.fori_loop(0, n_tiles, tile_body, 0)
        r = pl.ds(0, rows)
        l = l_scr[r]
        o_scr[r] = (acc_scr[r] / jnp.where(l > 0, l, 1.0)).astype(
            o_scr.dtype)
        # the block's live tokens: all of them but in a span's last block
        n_live = jnp.minimum(n_tok, qn - t0)

        def put(src, dst):
            cp = pltpu.make_async_copy(src, dst, sems.at[1, 1])
            cp.start()
            cp.wait()

        @pl.when(n_live == n_tok)
        def _():
            put(o_scr.at[r], o_hbm.at[pl.ds(row0, rows)])

        if n_tok > 1:
            @pl.when(n_live < n_tok)
            def _():
                def one(i, carry):
                    put(o_scr.at[pl.ds(i * heads, heads)],
                        o_hbm.at[pl.ds(row0 + i * heads, heads)])
                    return carry

                jax.lax.fori_loop(0, n_live, one, 0)

    @pl.when(qn == 1)
    def _():
        run_block(0, 1, 1)

    @pl.when(qn > 1)
    def _():
        n_blocks = pl.cdiv(qn, tb)

        def body(b, carry):
            run_block(b, tb, n_blocks)
            return carry

        jax.lax.fori_loop(0, n_blocks, body, 0)


@functools.partial(jax.jit, static_argnames=(
    "heads", "dv", "tile_pages", "tb", "interpret"))
def _pallas_impl(q2, lat_pages, layer, start, q_len, kv_len, tables, *,
                 heads, dv, tile_pages, tb, interpret):
    """``q2 [(T + tb)·H, dk]`` pre-scaled packed rows (the ``tb`` tokens
    of padding let a span's last block read whole); ``lat_pages [L, P,
    ps, dk]``. Returns ``[(T + tb)·H, dv]``; rows no live slot owns are
    NOT written (the caller zeroes them)."""
    rows_all, dk = q2.shape
    S, pps = tables.shape
    page_size = lat_pages.shape[2]
    tile_pages = min(int(tile_pages), pps)
    kernel = functools.partial(_kernel, pps=pps, page_size=page_size,
                               heads=heads, tile_pages=tile_pages, tb=tb,
                               dv=dv)
    R = tb * heads
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(S,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((R, dk), q2.dtype),
                pltpu.VMEM((R, dv), q2.dtype),
                pltpu.VMEM((2, tile_pages, page_size, dk), lat_pages.dtype),
                pltpu.VMEM((R, 1), jnp.float32),
                pltpu.VMEM((R, 1), jnp.float32),
                pltpu.VMEM((R, dv), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        out_shape=jax.ShapeDtypeStruct((rows_all, dv), q2.dtype),
        interpret=interpret,
        # a stable name: the benchmark's reducer reads
        # ``^mla_paged_attention`` in a device trace
        name="mla_paged_attention",
    )(layer, start, q_len, kv_len, tables.reshape(-1), q2, lat_pages)


def _owned_rows(T: int, start, q_len):
    """``[T]`` bool: the packed rows some live slot owns."""
    t = jnp.arange(T, dtype=jnp.int32)[:, None]
    return jnp.any((t >= start[None]) & (t < (start + q_len)[None]), axis=1)


def _packed_impl(q, pages, tok_slot, tok_qoff, q_len, kv_len, tables,
                 dv: int):
    """The plain formulation: every packed row gathers its slot's pages
    and takes ONE float32 softmax over the whole context. What the
    engine's ticks run off-TPU (work proportional to the tick's rows)
    and the reference the tests hold the kernel to."""
    T, H, dk = q.shape
    S, pps = tables.shape
    ps = pages.shape[1]
    KV = pps * ps
    sl = jnp.minimum(tok_slot, S - 1)
    kn = jnp.minimum(kv_len, KV)[sl]
    k = pages[tables[sl]].reshape(T, KV, dk)
    k_idx = jax.lax.broadcasted_iota(jnp.int32, (T, KV), 1)
    live = k_idx < kn[:, None]
    v = jnp.where(live[..., None], k[..., :dv], 0)
    s = jnp.einsum("thd,tkd->thk", q, k,
                   preferred_element_type=jnp.float32)
    hi = (kn - q_len[sl] + tok_qoff)[:, None]
    mask = ((tok_slot < S)[:, None] & (tok_qoff < q_len[sl])[:, None]
            & (k_idx <= hi) & live)[:, None, :]
    s = jnp.where(mask, s, _MASK)
    p = jnp.where(mask, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    l = p.sum(-1, keepdims=True)
    o = jnp.einsum("thk,tkd->thd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return (o / jnp.where(l > 0, l, 1.0)).astype(q.dtype)


def mla_paged_attention(q, lat_pages, start, q_len, kv_len, tables, *,
                        dv: int, sm_scale: float, tok_slot=None,
                        tok_qoff=None, impl: str = "auto", layer=None,
                        kv_tile_pages=None, block_tokens=None):
    """Absorbed-form latent attention over the tick's packed rows.

    q ``[T, H, dk]``: ``[q_abs | q_r | 0]`` a (token, head), UNscaled;
    lat_pages ``[P, ps, dk]``, or with ``layer`` (i32 scalar) the
    stacked pool ``[L, P, ps, dk]`` read where it lies; ``start [S]``
    the packed index of each slot's first row this launch (its rows are
    contiguous: ``start .. start + q_len - 1``), ``q_len`` / ``kv_len``
    ``[S]``, ``tables [S, pps]``. ``tok_slot`` / ``tok_qoff [T]`` (the
    tick's own metadata) are what the packed formulation reads; without
    them it is derived from ``start`` / ``q_len``. Returns ``p @ c_kv``
    ``[T, H, dv]``; rows no live slot owns are zero.

    impl: ``auto`` (the kernel on TPU, the plain formulation
    elsewhere), ``pallas`` (strict; interpreter mode off-TPU), ``dense``
    / ``packed`` (the plain formulation: the engine's ``attn_impl``
    names, which the ragged kernel's entry tells apart)."""
    if impl not in ("auto", "pallas", "dense", "packed"):
        raise ValueError(f"impl must be auto|pallas|dense|packed, got "
                         f"{impl!r}")
    T, H, dk = q.shape
    start = jnp.asarray(start, jnp.int32)
    q_len = jnp.asarray(q_len, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    qs = (q * sm_scale).astype(q.dtype)
    use_pallas = impl == "pallas" or (impl == "auto" and _on_tpu())
    if not use_pallas:
        pages = (lat_pages if layer is None else
                 jax.lax.dynamic_index_in_dim(lat_pages, layer, 0,
                                              keepdims=False))
        if tok_slot is None:
            S = tables.shape[0]
            t = jnp.arange(T, dtype=jnp.int32)[:, None]
            own = (t >= start[None]) & (t < (start + q_len)[None])
            tok_slot = jnp.where(own.any(1), jnp.argmax(own, 1), S)
            tok_qoff = t[:, 0] - start[jnp.minimum(tok_slot, S - 1)]
        return _packed_impl(qs, pages, jnp.asarray(tok_slot, jnp.int32),
                            jnp.asarray(tok_qoff, jnp.int32), q_len, kv_len,
                            tables, dv)
    if layer is None:
        lat_pages, layer = lat_pages[None], 0
    pps, ps = tables.shape[1], lat_pages.shape[2]
    tile = (default_kv_tile_pages(pps, ps) if kv_tile_pages is None
            else min(int(kv_tile_pages) or pps, pps))
    tb = int(block_tokens or BLOCK_TOKENS)
    q2 = jnp.concatenate(
        [qs, jnp.zeros((tb, H, dk), qs.dtype)], 0).reshape((T + tb) * H, dk)
    out = _pallas_impl(q2, lat_pages, jnp.asarray(layer, jnp.int32).reshape(1),
                       start, q_len, kv_len, tables, heads=H, dv=dv,
                       tile_pages=tile, tb=tb, interpret=not _on_tpu())
    out = out.reshape(T + tb, H, dv)[:T]
    return jnp.where(_owned_rows(T, start, q_len)[:, None, None], out,
                     0).astype(q.dtype)


# ---------------------------------------------------------------------------
# kernel-audit registration (analysis/kernel_audit.py)
# ---------------------------------------------------------------------------

AUDIT_KIND = "mla_paged_attention"
AUDIT_GEOM_KEYS = ("pages_per_slot", "page_size", "row_width", "heads",
                   "dtype")
AUDIT_CONFIG_KEYS = ("kv_tile_pages", "block_tokens")
AUDIT_GEOMETRIES = (
    # the long-prompt cell's: 17 408-token tables in pages of 64, the
    # published 64 heads over 640-lane rows
    {"pages_per_slot": 272, "page_size": 64, "row_width": 640, "heads": 64,
     "dtype": "bfloat16"},
)
AUDIT_SHAPE = dict(slots=4, span=24, dv=512)


def audit_launches(geom, config=None):
    """Zero-execution traceable launches for the kernel auditor: one
    launch of three decode rows and a 24-token span."""
    pps, ps = int(geom["pages_per_slot"]), int(geom["page_size"])
    dk, H = int(geom["row_width"]), int(geom["heads"])
    dt = jnp.dtype(geom["dtype"])
    cfg = config or {}
    S, span, dv = (AUDIT_SHAPE[k] for k in ("slots", "span", "dv"))
    tb = int(cfg.get("block_tokens", BLOCK_TOKENS))
    tile = min(int(cfg.get("kv_tile_pages",
                           default_kv_tile_pages(pps, ps))) or pps, pps)
    T = S + span
    q2 = jax.ShapeDtypeStruct(((T + tb) * H, dk), dt)
    pages = jax.ShapeDtypeStruct((1, S * pps, ps, dk), dt)
    layer = np.zeros((1,), np.int32)
    q_len = np.array([1] * (S - 1) + [span], np.int32)
    start = np.array(list(range(S - 1)) + [S], np.int32)
    kv_len = np.full((S,), pps * ps, np.int32)
    tables = np.arange(S * pps, dtype=np.int32).reshape(S, pps)
    fn = functools.partial(_pallas_impl, heads=H, dv=dv, tile_pages=tile,
                           tb=tb, interpret=False)
    return [(f"walk[kv_tile_pages={tile},block_tokens={tb}]", fn,
             (q2, pages, layer, start, q_len, kv_len, tables))]
