"""Paged (block-table) KV cache for batched decode serving.

Reference capability: the paged KV cache behind the reference's serving
decode — paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu
exposed at python/paddle/incubate/nn/functional/block_multihead_attention.py
(fixed-size KV blocks, per-sequence block tables, attention over valid
blocks only).

TPU-native shape: one KV page pool array per layer
(``[Hkv, total_pages, page_size, Dh]``), int32 per-sequence page tables,
and the Pallas ``paged_attention`` kernel
(jax.experimental.pallas.ops.tpu.paged_attention) whose grid walks only
each sequence's VALID pages — decode HBM traffic scales with
``sum(len_b)`` instead of the ``B * max_len`` a dense
``[B, max_len, Hkv, Dh]`` cache pays on every step. Off-TPU a gathered
dense formulation with identical semantics runs instead (tests compare
the two).

Page allocation is host-side (`PagePool`, a free list): serving code
allocates pages as sequences grow and frees them when streams finish —
the jitted decode step only ever sees the pool arrays + tables.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp
from ..ops.pallas import on_tpu as _on_tpu

__all__ = ["PagePool", "paged_attention_with_tail",
           "prompt_pages_from_dense", "apply_defrag", "defrag_pools"]


class PagePool:
    """Host-side free-list allocator over ``total_pages`` KV pages.

    The reference's block manager role (block_multihead_attention's
    block tables are produced by the serving layer's block allocator);
    here it hands out page indices for the pool arrays the jitted step
    consumes. Page 0 is reserved as the trash page masked writes land
    on, so valid tables never contain 0.
    """

    TRASH = 0

    def __init__(self, total_pages: int, page_size: int):
        if total_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the trash page)")
        self.page_size = int(page_size)
        self.total_pages = int(total_pages)
        self._free: List[int] = list(range(total_pages - 1, 0, -1))
        # membership mirror of the free list: free() validates against it
        # so a double-free or out-of-range id raises instead of silently
        # aliasing two sequences onto one page later (the refcounting
        # prefix cache makes that failure mode reachable from more call
        # sites than the pre-r8 retire path)
        self._free_set = set(self._free)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def alloc(self, n: int = 1) -> List[int]:
        if n > len(self._free):
            raise RuntimeError(
                f"KV page pool exhausted: need {n}, have {len(self._free)} "
                f"of {self.total_pages}")
        out = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(out)
        return out

    def alloc_for_len(self, length: int) -> List[int]:
        """Pages covering ``length`` tokens."""
        return self.alloc(self.pages_for_len(length))

    def free(self, pages) -> None:
        """Return pages to the free list. Rejects out-of-range ids,
        pages that are already free, and duplicates within one call —
        all-or-nothing: a rejected call frees NOTHING, so the pool state
        stays consistent for the error handler."""
        ids = [int(p) for p in pages]
        ids = [p for p in ids if p != self.TRASH]
        for p in ids:
            if not 0 < p < self.total_pages:
                raise ValueError(
                    f"free(): page id {p} out of range (valid ids are "
                    f"1..{self.total_pages - 1}; 0 is the trash page)")
            if p in self._free_set:
                raise ValueError(
                    f"free(): double free of page {p} (already on the "
                    f"free list)")
        if len(set(ids)) != len(ids):
            dup = sorted(p for p in set(ids) if ids.count(p) > 1)
            raise ValueError(f"free(): duplicate page ids in one call: "
                             f"{dup}")
        self._free.extend(ids)
        self._free_set.update(ids)

    # ------------------------------------------------- serving helpers ----
    @property
    def free_page_ids(self) -> frozenset:
        """Snapshot of the free ids (audit/debug introspection — the
        invariant checker reads this instead of the mutable internals)."""
        return frozenset(self._free_set)

    @property
    def used_pages(self) -> int:
        """Pages currently handed out (trash page excluded)."""
        return self.total_pages - 1 - len(self._free)

    @property
    def utilization(self) -> float:
        """Fraction of allocatable pages currently in use."""
        return self.used_pages / max(self.total_pages - 1, 1)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def pages_for_len(self, length: int) -> int:
        """How many pages ``length`` tokens need (>= 1)."""
        return max(1, -(-int(length) // self.page_size))

    def defrag_plan(self) -> Dict[int, int]:
        """Compaction plan ``{old_page: new_page}`` moving every USED page
        down to the lowest free indices (1..used). Empty dict when already
        compact. The pool's free list is NOT mutated here — call
        ``commit_defrag`` after the pool arrays/tables have been rewritten
        (``apply_defrag``), so a failed rewrite cannot desync the
        allocator from the arrays."""
        used = sorted(set(range(1, self.total_pages)) - set(self._free))
        plan = {old: new for new, old in enumerate(used, start=1)
                if old != new}
        return plan

    def commit_defrag(self, plan: Dict[int, int]) -> None:
        """Point the free list at the pages vacated by ``plan``.

        Derived from the plan against the CURRENT used set (not a blind
        "first n pages are used" rewrite), and raises if the pool
        changed incompatibly between ``defrag_plan()`` and here — an
        interleaved alloc/free would otherwise silently alias two
        sequences onto one page. Callers serialize the
        plan -> apply_defrag -> commit_defrag window (the serving
        engine holds its tick lock across it)."""
        if not plan:
            return
        used_now = set(range(1, self.total_pages)) - set(self._free)
        if not set(plan).issubset(used_now):
            raise RuntimeError(
                "commit_defrag: plan references pages freed since "
                "defrag_plan() — recompute the plan")
        if set(plan.values()) & (used_now - set(plan)):
            raise RuntimeError(
                "commit_defrag: plan destinations were allocated since "
                "defrag_plan() — recompute the plan")
        used_after = (used_now - set(plan)) | set(plan.values())
        self._free = sorted(set(range(1, self.total_pages)) - used_after,
                            reverse=True)
        self._free_set = set(self._free)


# ---------------------------------------------------------------------------
# split decode: paged prompt + dense tail, merged by online-softmax stats
# ---------------------------------------------------------------------------
# Per-sequence page SCATTERS are pathologically slow on TPU (measured
# ~14 ms/step inside a scan at B=32 — XLA lowers the batched scatter to
# full-pool traffic), so the decode hot path never writes pages at all:
# prompt KV lands in pages ONCE (a pure reshape for contiguous tables),
# generated tokens append to a small dense tail buffer with a
# lockstep dynamic_update_slice (one shared scalar index), and each
# step merges  attention-over-pages  with  attention-over-tail  using
# the numerically exact flash combine
#     m = max(m_p, m_t);  out = (e^{m_p-m} l_p o_p + e^{m_t-m} l_t o_t)
#                               / (e^{m_p-m} l_p + e^{m_t-m} l_t).
# The pallas kernel already computes (m, l) and its stock wrapper
# discards them; _stats_call below re-plumbs the same kernel body with
# the stats returned.


def _stats_call(q, k_pages, v_pages, lengths, page_indices,
                pages_per_compute_block: int):
    """The upstream paged_attention pallas kernel, returning
    (out_normalized, m, l). Plumbing mirrors the stock wrapper's
    unquantized single-core path (jax.experimental.pallas.ops.tpu.
    paged_attention.paged_attention_kernel.paged_attention), which
    computes these stats and throws them away."""
    import functools
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental.pallas.ops.tpu.paged_attention import (
        paged_attention_kernel as pk)

    batch_size, num_q_heads, head_dim = q.shape
    num_kv_heads, _, page_size, _ = k_pages.shape
    _, pages_per_sequence = page_indices.shape
    num_groups = num_q_heads // num_kv_heads

    if num_groups % 8 != 0:
        q = q.reshape(batch_size, num_q_heads, 1, head_dim)
        q_block_spec = pl.BlockSpec(
            (None, num_groups, None, head_dim),
            lambda core_index, b, h, *_: (b, h, 0, 0))
        q_dtype = jnp.float32
    else:
        q_block_spec = pl.BlockSpec(
            (None, num_groups, head_dim),
            lambda core_index, b, h, *_: (b, h, 0))
        q_dtype = q.dtype

    kernel = pk.paged_flash_attention_kernel_inline_seq_dim
    # the inline-seq-dim kernel folds the page loop inside: 3-D grid
    grid = (1, batch_size, num_kv_heads)
    dimension_semantics = ("parallel", "arbitrary", "arbitrary")
    in_specs = [
        q_block_spec,
        pl.BlockSpec(memory_space=pl.ANY),
        None,
        pl.BlockSpec(memory_space=pl.ANY),
        None,
    ]
    scratch_shapes = (
        pltpu.VMEM((2, pages_per_compute_block, page_size, head_dim),
                   k_pages.dtype),
        None,
        pltpu.VMEM((2, pages_per_compute_block, page_size, head_dim),
                   v_pages.dtype),
        None,
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
    )
    out, m, l = pl.pallas_call(
        functools.partial(
            kernel,
            pages_per_sequence=pages_per_sequence,
            batch_size=batch_size,
            pages_per_compute_block=pages_per_compute_block,
            mask_value=-2.3819763e38,
            attn_logits_soft_cap=None,
            megacore_mode=None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=in_specs,
            out_specs=[q_block_spec, q_block_spec, q_block_spec],
            grid=grid,
            scratch_shapes=scratch_shapes),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=dimension_semantics),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q_dtype),
            jax.ShapeDtypeStruct((*q.shape[:-1], 1), jnp.float32),
            jax.ShapeDtypeStruct((*q.shape[:-1], 1), jnp.float32),
        ],
    )(lengths, page_indices.reshape(-1), jnp.zeros((1,), jnp.int32),
      jnp.ones((1,), jnp.int32), q.astype(q_dtype), k_pages, None,
      v_pages, None)
    B, H = batch_size, num_q_heads
    return (out.reshape(B, H, head_dim).astype(k_pages.dtype),
            m.reshape(B, H), l.reshape(B, H))


def _ref_paged_attention_stats(q, k_pages, v_pages, lengths, page_indices):
    """Reference (out_normalized, m, l) with paged semantics; q must
    already carry the softmax scale (like the kernel's contract)."""
    B, H, Dh = q.shape
    Hkv, _, ps, _ = k_pages.shape
    G = H // Hkv

    def per_seq(qb, tab, ln):
        S = tab.shape[0] * ps
        k = k_pages[:, tab].reshape(Hkv, S, Dh)
        v = v_pages[:, tab].reshape(Hkv, S, Dh)
        qg = qb.reshape(Hkv, G, Dh)
        s = jnp.einsum("kgd,ksd->kgs", qg, k).astype(jnp.float32)
        mask = jnp.arange(S) < ln
        s = jnp.where(mask[None, None, :], s, -1e30)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("kgs,ksd->kgd", p.astype(v.dtype), v)
        o = o / l[..., None].astype(v.dtype)
        return o.reshape(H, Dh), m.reshape(H), l.reshape(H)

    return jax.vmap(per_seq)(q, page_indices, lengths)


def paged_attention_with_tail(q, k_pages, v_pages, prompt_lens,
                              page_indices, k_tail, v_tail, n_valid,
                              sm_scale: Optional[float] = None,
                              pages_per_compute_block: int = 4,
                              impl: str = "auto"):
    """Decode attention over paged PROMPT KV merged with a dense TAIL of
    generated tokens.

    q ``[B, H, Dh]``; k_tail/v_tail ``[B, Nt, Hkv, Dh]`` with the first
    ``n_valid`` slots live (lockstep across the batch — slot j holds the
    j-th GENERATED token of each sequence, at absolute position
    ``prompt_lens[b] + j``).
    """
    B, H, Dh = q.shape
    Hkv = k_pages.shape[0]
    G = H // Hkv
    if impl not in ("auto", "pallas", "dense"):
        raise ValueError(f"impl must be auto|pallas|dense, got {impl!r}")
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(Dh))
    qs = (q * sm_scale).astype(q.dtype)
    use_pallas = impl == "pallas" or (impl == "auto" and _on_tpu())
    if use_pallas:
        pps = page_indices.shape[1]
        blk = pages_per_compute_block
        while pps % blk:
            blk -= 1
        o_p, m_p, l_p = _stats_call(qs, k_pages, v_pages, prompt_lens,
                                    page_indices, blk)
    else:
        o_p, m_p, l_p = _ref_paged_attention_stats(
            qs, k_pages, v_pages, prompt_lens, page_indices)

    # tail part (dense, tiny): same scaled-q contract
    Nt = k_tail.shape[1]
    qg = qs.reshape(B, Hkv, G, Dh)
    s_t = jnp.einsum("bkgd,bjkd->bkgj", qg, k_tail).astype(jnp.float32)
    live = jnp.arange(Nt)[None, None, None, :] < n_valid
    s_t = jnp.where(live, s_t, -1e30)
    m_t = jnp.max(s_t, axis=-1).reshape(B, H)
    p_t = jnp.exp(s_t - m_t.reshape(B, Hkv, G)[..., None])
    p_t = jnp.where(live, p_t, 0.0)  # dead slots: exp(-1e30+1e30)=1
    l_t = jnp.sum(p_t, axis=-1).reshape(B, H)
    o_t = jnp.einsum("bkgj,bjkd->bkgd", p_t.astype(v_tail.dtype),
                     v_tail).reshape(B, H, Dh)  # UNnormalized

    m = jnp.maximum(m_p, m_t)
    a_p = (jnp.exp(m_p - m) * l_p)[..., None]
    a_t = jnp.exp(m_t - m)[..., None]
    num = a_p.astype(o_p.dtype) * o_p + a_t.astype(o_t.dtype) * o_t
    den = a_p[..., 0] * 1.0 + a_t[..., 0] * l_t
    return (num / den[..., None].astype(num.dtype)).astype(q.dtype)


def prompt_pages_from_dense(k, v, page_size: int):
    """Build (k_pages, v_pages, tables) from right-padded prompt KV
    ``[B, T0, Hkv, Dh]`` by pure reshape — no scatter. Page 0 is the
    (zeroed) trash page; seq b owns pages ``1 + b*pps .. 1 + (b+1)*pps``.
    Positions beyond each length hold padding the kernel's length mask
    never reads."""
    B, T0, Hkv, Dh = k.shape
    ps = page_size
    pps = -(-T0 // ps)
    pad = pps * ps - T0
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # [B, pps, ps, Hkv, Dh] -> [Hkv, B*pps, ps, Dh]
    def to_pages(x):
        x = x.reshape(B * pps, ps, Hkv, Dh).transpose(2, 0, 1, 3)
        trash = jnp.zeros((Hkv, 1, ps, Dh), x.dtype)
        return jnp.concatenate([trash, x], axis=1)
    tables = (1 + np.arange(B * pps, dtype=np.int32)).reshape(B, pps)
    return to_pages(k), to_pages(v), jnp.asarray(tables)


def defrag_pools(plan: Dict[int, int], pools, tables):
    """Rewrite any number of page pools + tables per a
    ``PagePool.defrag_plan()``: ``pools`` is ``[(array, page_axis)]``
    (K and V a head, a latent pool, ...: every pool's pages move by the
    same plan, each along its own axis). Returns ``(arrays, tables)``."""
    if not plan:
        return [a for a, _ in pools], tables
    P_total = pools[0][0].shape[pools[0][1]]
    src = np.arange(P_total, dtype=np.int32)
    dst_map = np.arange(P_total, dtype=np.int32)
    for old, new in plan.items():
        src[new] = old          # gather: new slot <- old page's contents
        dst_map[old] = new      # remap: table entries old -> new
    gather = jnp.asarray(src)
    moved = [jnp.take(a, gather, axis=axis) for a, axis in pools]
    return moved, jnp.asarray(dst_map)[jnp.asarray(tables)]


def apply_defrag(plan: Dict[int, int], k_pages, v_pages, tables,
                 page_axis: int = -3):
    """Rewrite pool arrays + tables per a ``PagePool.defrag_plan()``.

    k_pages/v_pages carry the page dim at ``page_axis`` (default -3:
    ``[..., P, ps, Dh]`` — works for per-layer ``[Hkv, P, ps, Dh]`` and
    layer-stacked ``[L, Hkv, P, ps, Dh]`` pools alike). ``tables`` is any
    int array of page indices. Returns ``(k_pages, v_pages, tables)``;
    callers then ``commit_defrag(plan)`` on the pool."""
    (k_pages, v_pages), tables = defrag_pools(
        plan, [(k_pages, page_axis), (v_pages, page_axis)], tables)
    return k_pages, v_pages, tables
