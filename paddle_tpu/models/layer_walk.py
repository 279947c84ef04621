"""What the serving families share below the tick (ROADMAP D1): the ONE
record a family's module hands the engine and the shared tick
(``ServingFamily``, as ``SERVING``), what a kind keeps between ticks
(``LayerKind``), a cache's page pools by name (``PagePoolSpec``), how a
tick's K and V land in a stacked pool and are attended
(``tick_plan`` / ``paged_kv_attend``), and for the families whose
layers follow a PATTERN OF KINDS the stack cut into a handful of groups
(``layer_groups``), one layer's parameters out of a kind's stack
(``_layer_params``), the loop over the groups (``walk_groups``) and the
per-slot window of a causal depthwise convolution in a ragged tick
(``earlier_rows`` / ``window_rows``).

A layer is ``(operator, feed-forward, operator's ordinal, feed-
forward's ordinal)``: parameters are stacked BY KIND, each kind's layers
in model order, so a layer is found by its ordinals.
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.pallas.ragged_paged_attention import (
    lane_pack_heads, ragged_paged_attention_packed, stream_plan)


class LayerKind(NamedTuple):
    """An operator kind and what it keeps between ticks: ``pages`` (K
    and V in the paged pool, rebuilt from a prefix's pages),
    ``slot_rows`` (a fixed row a slot, which nothing but the tokens
    themselves can rebuild) or ``window_pages`` (K and V of the last
    tokens of a window in a ring of pages a slot, in a pool of its own
    that the allocator does not count and the context never grows:
    ``models/mimo_v2_flash.py``; a prefix's pages do not rebuild it
    either)."""
    name: str
    cache: str


class PagePoolSpec(NamedTuple):
    """One page pool of a family's cache pytree, as its record's
    ``page_pools(cfg)`` declares it: the leaf's ``name`` and the
    axis its pages lie on. What moves pages (defrag, the KV auditor's
    gathers, chain export and the cold tier where they carry the pool)
    goes by these, not by the names ``k_pages`` / ``v_pages``."""
    name: str
    page_axis: int


# what a family that declares nothing holds: K and V a head in two pools
# ``[L, Hkv, P, ps, Dh]``
KV_POOLS = (PagePoolSpec("k_pages", 2), PagePoolSpec("v_pages", 2))


def _one_kind(cfg):
    return ()


def _kv_pools(cfg):
    return KV_POOLS


def _no_rings(cfg):
    return ()


class ServingFamily(NamedTuple):
    """Everything the engine (``serving/engine.py``) and the shared tick
    (``models/serving_tick.py``) ask of a serving family: its module
    exposes exactly one, as ``SERVING``. The record points at the
    module's plain functions; every field but the first two has the
    default a model of one kind, each layer holding K and V, needs.

    * ``walk(params, h [1, T, D], cache, meta, cfg, tq, attn_impl) ->
      (h, cache')``: the layers, over the model's WHOLE cache pytree;
      the tick owns everything around them.
    * ``init_pages(cfg, total_pages, page_size, max_batch, max_span) ->
      cache``: the cache pytree (page 0 of a pool = trash).
      ``max_batch`` (the slots) sizes what a kind keeps a slot,
      ``max_span`` (the most query rows a slot brings to one tick: the
      engine's prefill budget) a window layer's ring; a family with no
      use for either ignores it.
    * ``kinds(cfg)``: a ``LayerKind`` a layer, in order (``()``: one
      kind, pages of K and V). A kind whose cache is not ``"pages"``
      keeps what a prefix's pages cannot rebuild: the engine then
      attaches, moves and rolls back nothing, and the tick refuses
      ``spec_k``.
    * ``page_pools(cfg)``: the pools whose pages the engine's allocator
      hands out (``PagePoolSpec``s; ``KV_POOLS``).
    * ``window_pools(cfg)``: the leaves that are window rings, by name
      (``()``): they come with the slots and no allocator counts them.
    * ``tick_pool``: a pool whose second-to-last axis is a page's
      tokens; all the tick reads of it is that size.
    * ``counters``: names of the ``[n]`` i32 counts a tick hands back
      beside its tokens (``()``: none, and no such result); the walk
      adds to ``cache[COUNTS]``.
    * ``page_copies(cfg, cache, pages_per_slot, tq) -> int``: copies a
      tick's launches of ``tq`` rows a slot start for ONE live page, for
      a kernel other than the K / V one (None: the engine reckons from
      ``k_pages``).
    * ``params(params, cfg)``: the tree a serving engine holds, made
      once an engine: a NEW tree sharing every leaf it does not re-lay
      (None: the engine serves the tree it was given)."""
    walk: Callable
    init_pages: Callable
    kinds: Callable = _one_kind
    page_pools: Callable = _kv_pools
    window_pools: Callable = _no_rings
    tick_pool: str = "k_pages"
    counters: tuple = ()
    page_copies: Optional[Callable] = None
    params: Optional[Callable] = None


# a tick's per-launch counts (a family's ``counters``), carried through
# its walk beside the pools under this key of the cache pytree: the
# shared tick puts the zeros there and hands the sums back beside the
# tokens; a walk adds to ``cache[COUNTS]`` where it finds it
COUNTS = "tick_counts"


# what a family that holds EVERY routed expert counts over a tick's
# expert launches (one an expert layer): the (row, choice) pairs it
# computed, the experts that took a row (whose weights it read) and the
# experts those launches held (touched / held: the share of the expert
# bytes a window read)
EXPERT_COUNTERS = ("moe_pairs_held", "moe_experts_touched",
                   "moe_experts_held")


def expert_counts(share_counts, num_experts: int):
    """``EXPERT_COUNTERS`` of one launch from ``moe_ffn_share``'s
    ``[pairs_held, pairs_zero, pairs_absent, experts_touched]`` at the
    share ``(0, num_experts)``."""
    return jnp.stack([share_counts[0], share_counts[3],
                      num_experts]).astype(jnp.int32)


def tick_plan(meta, tq, heads: int, pool, tables=None, block_tokens: int = 0):
    """What the ragged kernel's path needs of a tick's packing
    (``ops/pallas/ragged_paged_attention.py: stream_plan``) for launches
    of ``heads`` query heads over ``pool``: every walk makes it ONCE a
    tick outside its layers and hands it to each launch. A slot's rows
    are contiguous in the stream, up to ``meta['last']``. ``tables``: a
    launch's own page table (None: ``meta['tables']``)."""
    return stream_plan(
        meta["tok_slot"], meta["tok_qoff"], meta["q_len"], meta["kv_len"],
        meta["tables"] if tables is None else tables, tq, heads, pool,
        start=meta["last"] - meta["q_len"] + 1, block_tokens=block_tokens)


def kv_rows(meta, pool):
    """Where a tick's K and V rows land in a stacked pool ``[L, Hkv, P,
    ps, Dh]``: ``(heads [1, Hkv], page [T, 1], offset [T, 1])``. Like
    the plan, made once a tick OUTSIDE the layers (the compiler does
    not hoist index arithmetic out of a loop)."""
    heads = jnp.arange(pool.shape[1], dtype=jnp.int32)[None, :]
    return heads, meta["tok_page"][:, None], meta["tok_off"][:, None]


def paged_kv_attend(q, k, v, kp, vp, layer, meta, at, plan, tq, attn_impl,
                    sm_scale=None):
    """One attention layer of a tick over the stacked K / V pools: ``q
    [1, T, H, Dh]`` and the span's ``k`` / ``v [1, T, Hkv, Dh]`` ->
    ``(o [1, T, H, Dh], kp', vp')``. The pools are ``[L, Hkv, P, ps,
    Dh]``, or lane-packed ``[L, Hkv / f, P, ps, f * Dh]``
    (``lane_pack_factor``), which their row width says; ``at`` /
    ``plan``: ``kv_rows`` / ``tick_plan`` over ``kp``."""
    heads, tok_page, tok_off = at
    pack = kp.shape[-1] // k.shape[-1]

    def landed(pool, x):
        x = x[0] if pack == 1 else lane_pack_heads(x[0], pack)
        return pool.at[layer, heads, tok_page, tok_off].set(
            x.astype(pool.dtype))

    # 1) land the span's KV in the layer's pages, in place on the
    # carried pool (padding -> trash page). One scattered row per
    # (token, kv head), the window the head size alone: a window over
    # the heads (``kp.at[layer, :, page, off]``) makes the chip's
    # compiler re-lay the WHOLE pool out, heads next to the head size,
    # around every use of it
    with jax.named_scope("kv_pool.write"):
        kp, vp = landed(kp, k), landed(vp, v)
    # 2) one ragged launch over the pages (span KV included): the packed
    # entry keeps score work proportional to the T real rows off-TPU and
    # copies each slot's rows straight into the kernel's blocks on TPU;
    # the kernel reads the layer's pages where they lie in the stacked
    # pool
    with jax.named_scope("ragged_attn"):
        o = ragged_paged_attention_packed(
            q[0], kp, vp, meta["tok_slot"], meta["tok_qoff"], meta["q_len"],
            meta["kv_len"], meta["tables"], tq=tq, sm_scale=sm_scale,
            impl=attn_impl, layer=layer, plan=plan)
    return o[None].astype(q.dtype), kp, vp


class Group(NamedTuple):
    """``repeats`` times the layers of one pattern: ``layers`` holds
    ``(operator, feed-forward, operator's ordinal, feed-forward's
    ordinal)`` for the FIRST repeat, and a kind's ordinal grows by
    ``stride[kind]`` (its layers in the pattern) with every repeat."""
    layers: tuple
    repeats: int
    stride: dict


def layer_kinds(operators, ffn_of):
    """``[(operator, feed-forward, operator's ordinal, feed-forward's
    ordinal)]`` for every layer, in order; ``ffn_of(i)`` names layer
    ``i``'s feed-forward kind."""
    seen: Dict[str, int] = {}
    out = []
    for i, op in enumerate(operators):
        ffn = ffn_of(i)
        out.append((op, ffn, seen.get(op, 0), seen.get(ffn, 0)))
        seen[op] = seen.get(op, 0) + 1
        seen[ffn] = seen.get(ffn, 0) + 1
    return out


def layer_groups(kinds, leading: int = 0):
    """The stack as a walk takes it: the ``leading`` layers (one group,
    walked once), the whole periods of the remaining layers' pattern
    (one group, SCANNED), the trailing part of a period (one group,
    walked once). The period is the shortest that the remaining layers
    repeat with."""
    nd = min(leading, len(kinds))
    rest = [k[:2] for k in kinds[nd:]]
    period = next((p for p in range(1, len(rest) + 1)
                   if all(rest[i] == rest[i % p]
                          for i in range(len(rest)))), 0)
    n = len(rest) // period if period else 0
    groups = []
    for lo, size, repeats in ((0, nd, 1), (nd, period, n),
                              (nd + n * period, len(rest) - n * period, 1)):
        first = tuple(kinds[lo:lo + size])
        if first and repeats:
            names = [k for layer in first for k in layer[:2]]
            groups.append(Group(first, repeats,
                                {k: names.count(k) for k in set(names)}))
    return groups


def runs(layers):
    """A group's layers as runs of equal ``(operator, feed-forward)``:
    ``[(first layer of the run, how many)]``. A walk may take a run as
    an inner loop, so that its program holds one body a run."""
    out = []
    for layer in layers:
        if out and out[-1][0][:2] == layer[:2]:
            out[-1][1] += 1
        else:
            out.append([layer, 1])
    return [(layer, n) for layer, n in out]


def _layer_params(stack, i):
    """One layer's parameters out of a kind's stack: a static ordinal
    inside a group walked once, a traced one inside the scanned group
    (the dynamic slice a ``lax.scan`` over ``xs`` would make)."""
    if isinstance(i, int):
        return jax.tree_util.tree_map(lambda a: a[i], stack)
    return jax.tree_util.tree_map(
        lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), stack)


def walk_groups(groups, carry, run):
    """``run(group, carry, i) -> carry`` over every group: once where
    the group is walked once, else one ``lax.scan`` over its repeats
    with the carry (hidden states, pools, per-slot state) as the
    loop's."""
    for group in groups:
        if group.repeats == 1:
            carry = run(group, carry, 0)
        else:
            carry, _ = lax.scan(
                lambda c, i, g=group: (run(g, c, i), None), carry,
                jnp.arange(group.repeats, dtype=jnp.int32))
    return carry


# ------------------------------------- a convolution's window a slot ----
# A slot's span is contiguous in the packed stream, so for a token at
# span offset ``j`` the value ``d`` positions back is the stream's ``d``
# rows up when ``j >= d`` and else row ``K - 1 - d + j`` of the slot's
# state (its last ``K - 1`` values, oldest first); whatever lies before
# position 0 is zero BY POSITION (``tok_pos``), so a slot needs no reset
# when it changes hands.

def earlier_rows(u, rows, tok_slot, tok_qoff, tok_pos, K: int):
    """``[u_{t-1}, ..., u_{t-K+1}]`` of the packed stream ``u [T, C]``,
    each ``[1, T, C]``; ``rows [S + 1, K - 1, C]`` is the layer's state
    (row ``S`` the trash row padding tokens read)."""
    slot_rows = rows[tok_slot]                          # [T, K-1, C]
    prevs = []
    for d in range(1, K):
        stream = jnp.concatenate(
            [jnp.zeros_like(u[:d]), u[:-d]], axis=0)
        state = jnp.take_along_axis(
            slot_rows, jnp.clip(K - 1 - d + tok_qoff, 0, K - 2)[
                :, None, None], axis=1)[:, 0]
        prev = jnp.where((tok_qoff >= d)[:, None], stream, state)
        prevs.append(jnp.where((tok_pos >= d)[:, None],
                               prev, 0)[None])
    return prevs


def window_rows(u, rows, q_len, last, K: int, dtype):
    """The state after the tick, ``[S, K - 1, C]``: the row of every
    slot with ``q_len > 0`` holds its span's last values (the old row's
    tail in front of them when the span is shorter than the row); idle
    slots, padding tokens and slots dead in a fused tail step (``q_len``
    0 there: mid-prefill) keep every row as it was."""
    S = q_len.shape[0]
    new = []
    for r in range(K - 1):
        back = K - 2 - r            # rows up from the span's last
        kept = jnp.take_along_axis(
            rows[:S], jnp.clip(r + q_len, 0, K - 2)[:, None, None],
            axis=1)[:, 0]
        new.append(jnp.where(
            (q_len > back)[:, None],
            u[jnp.maximum(last - back, 0)].astype(dtype), kept))
    return jnp.where((q_len > 0)[:, None, None],
                     jnp.stack(new, axis=1), rows[:S])
