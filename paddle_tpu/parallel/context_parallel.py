"""Context parallelism: ring attention + Ulysses over the ``cp`` mesh axis.

The reference has no ring attention / context parallel of its own
(SURVEY.md §2.8 CP row: absent; its long-context story is the SEP topology
axis topology.py:204 + sequence-parallel utils + flash-attn varlen
kernels, with the attention alltoall delegated to the model library).
Here long context is first-class:

  - **Ring attention**: each cp rank holds a sequence chunk of q/k/v;
    k/v chunks rotate around the cp ring via ``lax.ppermute`` while each
    hop's partial attention folds into a running online-softmax
    accumulator (m, l, o) — the flash-attention recurrence across
    devices, so the full [T, T] score matrix never exists and sequence
    length scales linearly with cp degree. ppermute rides ICI neighbours.
  - **Ulysses**: ``lax.all_to_all`` re-partitions seq->heads, runs dense/
    pallas flash attention on full sequences for H/cp local heads, and
    all_to_alls back (the alltoall the reference leaves to PaddleNLP).

Both run inside ``shard_map`` and compose with the GSPMD llama forward:
q/k/v arrive [B, T, H, Dh] sharded (dp, cp, tp, -) and the ring runs over
cp only, per tp-local head group.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import numpy as np
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

_NEG_INF = -1e30


def _repeat_kv(q, k, v):
    H, Hkv = q.shape[2], k.shape[2]
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def _block_accum(q, k, v, qpos, kpos, causal, sm_scale, m, l, o):
    """Fold one k/v block into the online-softmax state.

    q [B,Tq,H,D]; k/v [B,Tk,Hkv,D] (GQA heads broadcast here, locally,
    so the ring only ever carries the small Hkv chunks);
    m,l [B,H,Tq]; o [B,Tq,H,D] (fp32). qpos/kpos are the GLOBAL token
    positions of the blocks' rows ([Tq]/[Tk] int vectors — arbitrary
    layouts like zigzag welcome).
    """
    k, v = _repeat_kv(q, k, v)
    s = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) * sm_scale
    if causal:
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask, s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # guard fully-masked rows: exp(-inf - -inf) -> exp(0)=1 would poison l
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
    corr = jnp.exp(m - m_new)
    corr = jnp.where(m <= _NEG_INF / 2, 0.0, corr)
    l_new = corr * l + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhts,bshd->bthd", p.astype(v.dtype), v).astype(jnp.float32)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def chunk_positions(r, R: int, Tl: int, layout: str = "contiguous"):
    """Global positions of rank ``r``'s local sequence slots.

    contiguous: rank r holds tokens [r*Tl, (r+1)*Tl).
    zigzag: the sequence is cut into 2R cells; rank r holds cell r and
    cell 2R-1-r (one early + one late) — the llama-3 style causal load
    balance: every (rank, hop) pair then carries the same unmasked area
    (tests/test_context_parallel.py proves the count).
    """
    if layout == "zigzag":
        if Tl % 2:
            raise ValueError(
                f"zigzag needs an even per-rank chunk (got {Tl} slots): "
                "the global seq len must be divisible by 2*cp")
        C = Tl // 2
        a = jnp.arange(C)
        return jnp.concatenate([r * C + a, (2 * R - 1 - r) * C + a])
    return r * Tl + jnp.arange(Tl)


def zigzag_global_perm(T: int, R: int) -> np.ndarray:
    """Permutation placing tokens into the zigzag layout: position j of
    the permuted sequence holds original token perm[j]; cp-sharding the
    permuted sequence contiguously gives every rank cell r + cell
    2R-1-r. Host-side (numpy) — it is a static data layout."""
    if T % (2 * R):
        raise ValueError(f"seq len {T} not divisible by 2*cp ({2 * R})")
    C = T // (2 * R)
    out = []
    for r in range(R):
        out.append(np.arange(r * C, (r + 1) * C))
        out.append(np.arange((2 * R - 1 - r) * C, (2 * R - r) * C))
    return np.concatenate(out)


def ring_attention(q, k, v, *, axis_name: str = "cp", causal: bool = True,
                   sm_scale: Optional[float] = None,
                   layout: str = "contiguous"):
    """Blockwise ring attention on per-device chunks (use inside shard_map).

    q/k/v are the LOCAL sequence chunks [B, T/cp, H|Hkv, Dh]; returns the
    local output chunk [B, T/cp, H, Dh]. The ring rotates the UNREPEATED
    Hkv-head k/v chunks (GQA broadcast happens per-hop inside
    _block_accum), so ppermute bandwidth is Hkv/H of the naive version.

    ``layout``: how local slots map to global positions (chunk_positions).
    contiguous causal rings are imbalanced — late ranks own almost-fully
    unmasked hops while early ranks mask almost everything; "zigzag"
    gives every rank one head + one tail cell so each hop's unmasked
    area is equal across ranks (the reference has no CP at all; this is
    the standard fix from ring-flash-attention / llama-3 training).
    """
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    R = lax.psum(1, axis_name)
    r = lax.axis_index(axis_name)
    B, Tl, H, D = q.shape
    qpos = chunk_positions(r, R, Tl, layout)

    m0 = jnp.full((B, H, Tl), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tl), jnp.float32)
    o0 = jnp.zeros((B, Tl, H, D), jnp.float32)
    fwd = [(i, (i + 1) % R) for i in range(R)]

    def step(carry, s):
        k_c, v_c, m, l, o = carry
        src = (r - s) % R                     # origin rank of this kv chunk
        m, l, o = _block_accum(q, k_c, v_c, qpos,
                               chunk_positions(src, R, Tl, layout),
                               causal, sm_scale, m, l, o)
        k_c = lax.ppermute(k_c, axis_name, fwd)
        v_c = lax.ppermute(v_c, axis_name, fwd)
        return (k_c, v_c, m, l, o), None

    # R-1 hops rotate; the final block needs no further ppermute
    (k_c, v_c, m, l, o), _ = lax.scan(step, (k, v, m0, l0, o0),
                                      jnp.arange(R - 1))
    src_last = (r - (R - 1)) % R
    m, l, o = _block_accum(q, k_c, v_c, qpos,
                           chunk_positions(src_last, R, Tl, layout),
                           causal, sm_scale, m, l, o)
    l = jnp.where(l == 0.0, 1.0, l)           # rows with nothing to attend
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(q, k, v, *, axis_name: str = "cp",
                      causal: bool = True, sm_scale: Optional[float] = None,
                      impl: str = "auto"):
    """Ulysses sequence parallelism (use inside shard_map): all_to_all
    seq<->heads so each cp rank attends the FULL sequence for H/cp heads,
    then redistributes. The cp degree must divide the (local) head counts,
    both H and Hkv — GQA k/v stay unrepeated through the all_to_all
    (flash_attention broadcasts them natively)."""
    R = lax.psum(1, axis_name)
    if q.shape[2] % R or k.shape[2] % R:
        raise ValueError(
            f"ulysses needs cp degree {R} to divide local head counts "
            f"H={q.shape[2]}, Hkv={k.shape[2]}")
    # [B, T/cp, H, D] -> [B, T, H/cp, D]
    a2a = partial(lax.all_to_all, axis_name=axis_name, split_axis=2,
                  concat_axis=1, tiled=True)
    qg, kg, vg = a2a(q), a2a(k), a2a(v)
    from ..ops.pallas.flash_attention import flash_attention
    out = flash_attention(qg, kg, vg, causal=causal, sm_scale=sm_scale,
                          impl=impl)
    # back: [B, T, H/cp, D] -> [B, T/cp, H, D]
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def context_parallel_attention(q, k, v, mesh: Mesh, *, impl: str = "ring",
                               causal: bool = True,
                               sm_scale: Optional[float] = None):
    """Global-array entry: q/k/v [B, T, H, Dh] with T sharded over ``cp``
    (and optionally B over dp, H over tp); returns same layout.

    Wraps ring/ulysses in shard_map over every mesh axis that shards an
    input dim, so it drops into a GSPMD forward (models/llama.py).
    """
    fns = {"ring": ring_attention, "ulysses": ulysses_attention,
           "zigzag": partial(ring_attention, layout="zigzag")}
    fn = fns[impl]
    dp = "dp" if "dp" in mesh.shape else None
    tp = "tp" if "tp" in mesh.shape else None
    spec = P(dp, "cp", tp, None)

    inner = partial(fn, axis_name="cp", causal=causal, sm_scale=sm_scale)
    return shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)
