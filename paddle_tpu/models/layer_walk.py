"""What the families whose layers follow a PATTERN OF KINDS share
(ROADMAP D1; ``models/lfm2_moe.py`` began these, ``models/
granite_hybrid.py`` is their second user): what a kind keeps between
ticks (``LayerKind``), the stack cut into a handful of groups
(``layer_groups``), one layer's parameters out of a kind's stack
(``_layer_params``), the loop over the groups (``walk_groups``), the
counts a tick hands back beside its tokens (``with_tick_counts``) and
the per-slot window of a causal depthwise convolution in a ragged tick
(``earlier_rows`` / ``window_rows``).

A layer is ``(operator, feed-forward, operator's ordinal, feed-
forward's ordinal)``: parameters are stacked BY KIND, each kind's layers
in model order, so a layer is found by its ordinals.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


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
    """One page pool of a family's cache pytree, as its
    ``cache_page_pools(cfg)`` declares it: the leaf's ``name`` and the
    axis its pages lie on. What moves pages (defrag, the KV auditor's
    gathers, chain export and the cold tier where they carry the pool)
    goes by these, not by the names ``k_pages`` / ``v_pages``."""
    name: str
    page_axis: int


# what a family that declares nothing holds: K and V a head in two pools
# ``[L, Hkv, P, ps, Dh]``
KV_POOLS = (PagePoolSpec("k_pages", 2), PagePoolSpec("v_pages", 2))


# a tick's per-launch counts (a family's ``TICK_COUNTERS``), carried
# through its walk beside the pools under this key of the cache pytree
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


def with_tick_counts(fn, cache, n: int, has_cur: bool):
    """Run a tick entry point (``fn(cache) -> (..., [cur_tok',]
    cache')``) with ``n`` counts carried in the cache under ``COUNTS``,
    and hand them back BESIDE the tokens: ``(..., counts [n] i32,
    [cur_tok',] cache')``. The family's walk adds to ``cache[COUNTS]``
    where it finds it; the engine adds the counts to its counters when
    the tick completes (no pull of their own)."""
    *out, new = fn({**cache, COUNTS: jnp.zeros((n,), jnp.int32)})
    new = dict(new)
    counts = new.pop(COUNTS)
    if has_cur:
        *out, nxt = out
        return (*out, counts, nxt, new)
    return (*out, counts, new)


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
