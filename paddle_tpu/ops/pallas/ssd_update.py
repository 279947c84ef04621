"""The STATE PASS of a Mamba-2 (SSD) layer in one ragged serving tick,
in place on the per-slot state.

A Mamba-2 layer keeps, a slot, the state ``S [H, P, N]`` of the
recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t
C_t``. A tick's packed rows (decode rows of one token, prompt spans of
up to a chunk) are ONE chunk of the chunked (SSD) form whose segments
are the slots; with ``a_t`` the running sum of ``dt A`` over a row's own
span, everything that does NOT touch a slot's stored state is small
dense algebra over the tick's rows (``models/granite_hybrid.py``), and
what does is this pass, over every slot that has a row:

    ys[t]       = C_t . S_prev[slot_t]                    (the read-out)
    S_new[s]    = dec[s] * S_prev[s] + sum_{t in s} B_t (x) w_t

with ``w_t = exp(a_last - a_t) dt_t x_t`` and ``dec[s] = exp(a_last)``
(0 where the span starts at position 0: the state then counts as zero,
whoever held the slot before) prepared by the caller.

THE STATE IS STORED STATE-MAJOR: ``[L, S + 1, N, H * P]`` float32 (row
``S`` the trash row), the transpose of the equations' ``[H, P, N]``. The
decay is then a vector along the lanes, the read-out ``C [rows, N] @ S
[N, H P]`` and the update ``B^T [N, rows] @ w [rows, H P]`` are lane-
dense matmuls with the state as their wide operand, and nothing is
transposed.

THE KERNEL (``impl="pallas"``; ``"auto"`` on a TPU): grid ``(head
blocks, slots)``. The slot axis walks the LIVE slots only: their ids
are compacted in front (scalar-prefetched with their count, their first
row and their row count), and a step past the count keeps the block
index of the step before it, so it moves nothing (the pipeline fetches
and writes back a block when its index CHANGES). A live step brings one
``[N, HPb]`` block of the slot's state to VMEM, reads it out against the
span's rows, decays it, adds the rows' outer products, and the block is
written back into the SAME buffer (``input_output_aliases``): the state
is the layer loops' carry and the tick's donated argument, one buffer
for the whole tick, and a slot without a row is neither read nor
written. A span's rows are taken in aligned blocks of 8 with the rows
of other slots masked, trip count from the slot's row count. Float32
throughout (PR 22): the state, the accumulators, both matmuls' results.
The two matmuls take their float32 operands at the chip's DEFAULT matmul
precision (one bfloat16 pass, as every projection of a bfloat16 model:
against the float32 twin at the cell's size, 0.2 % of the read-out's
scale and 0.3 % of a step's contribution; narrowing the operands to
bfloat16 by hand times the same, 0.414 ms a launch of 64 live slots
either way: my chip run, PR 38), the decay and the sums are float32.

THE XLA TWIN (``impl="dense"``; ``"auto"`` elsewhere) takes the same
steps slot by slot in a ``fori_loop`` with the state as its carry; the
CPU tests run it and hold the kernel (interpret mode) to it.

A slot's rows must be contiguous in the packed stream (the engine packs
them so).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8                        # a float32 sublane tile: one row block
VMEM_LIMIT_BYTES = 32 * 2 ** 20
# blocks held a grid step, double-buffered: what ``default_head_blocks``
# keeps under this
VMEM_BLOCK_BUDGET = 12 * 2 ** 20
_HI = lax.Precision.HIGHEST


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def state_bytes(slots: int, lanes: int, n: int) -> int:
    """Bytes of one layer's state that ``slots`` live slots hold: what
    a tick reads once and writes once for them."""
    return slots * n * lanes * 4


def block_bytes(rows: int, slots: int, lanes: int, n: int) -> int:
    """VMEM the pipeline holds a grid step at ``lanes`` state columns a
    block: state in and out, the rows' ``w`` and ``ys``, ``dec``, ``C``
    and ``B``, each double-buffered."""
    s8 = -(-slots // ROWS) * ROWS
    return 2 * 4 * (2 * n * lanes + 2 * rows * lanes + s8 * lanes
                    + 2 * rows * n)


def default_head_blocks(rows: int, slots: int, lanes: int, n: int) -> int:
    """The fewest blocks of the ``H * P`` axis (each a multiple of 128
    lanes) whose step fits ``VMEM_BLOCK_BUDGET``."""
    hb = 1
    while (block_bytes(rows, slots, lanes // hb, n) > VMEM_BLOCK_BUDGET
           and lanes % (2 * hb) == 0 and lanes // (2 * hb) % 128 == 0):
        hb *= 2
    return hb


class Walk(NamedTuple):
    """A tick's rows by slot, from ``tok_slot`` alone (so a caller with
    many layers makes it ONCE a tick: ``live_walk``): ``ids [S]`` the
    slots that have a row, compacted in front (the rest names the trash
    row ``S``), ``n_live [1]`` their count, and every slot's first row
    and row count, ``start`` / ``q_len`` ``[S]``."""
    ids: jax.Array
    n_live: jax.Array
    start: jax.Array
    q_len: jax.Array


def live_walk(tok_slot, slots: int) -> Walk:
    T = tok_slot.shape[0]
    q_len = jnp.zeros((slots + 1,), jnp.int32).at[tok_slot].add(1)[:slots]
    start = jnp.full((slots + 1,), T, jnp.int32).at[tok_slot].min(
        jnp.arange(T, dtype=jnp.int32))[:slots]
    live = q_len > 0
    at = jnp.where(live, jnp.cumsum(live) - 1, slots)
    ids = jnp.full((slots,), slots, jnp.int32).at[at].set(
        jnp.arange(slots, dtype=jnp.int32), mode="drop")
    return Walk(ids, jnp.sum(live).astype(jnp.int32)[None], start, q_len)


# ------------------------------------------------------------ the twin ----

def _dense_impl(state, layer, c, b, w, dec, tok_slot, q_len):
    S = state.shape[1] - 1

    def step(s, carry):
        ys, st = carry
        mine = (tok_slot == s)[:, None]
        prev = lax.dynamic_slice(
            st, (layer, s, 0, 0), (1, 1) + st.shape[2:])[0, 0]
        ys = ys + jnp.where(mine, jnp.dot(c, prev, precision=_HI), 0.0)
        new = dec[s][None] * prev + jnp.dot(
            jnp.where(mine, b, 0.0).T, w, precision=_HI)
        new = jnp.where(q_len[s] > 0, new, prev)      # bitwise untouched
        return ys, lax.dynamic_update_slice(st, new[None, None],
                                            (layer, s, 0, 0))

    return lax.fori_loop(0, S, step, (jnp.zeros_like(w), state))


# ---------------------------------------------------------- the kernel ----

def _kernel(layer_ref, ids_ref, n_ref, start_ref, qlen_ref,
            state_ref, c_ref, b_ref, w_ref, dec_ref, ys_ref, out_ref):
    del layer_ref

    i = pl.program_id(1)
    n = n_ref[0]

    @pl.when(i == 0)
    def _():
        ys_ref[...] = jnp.zeros_like(ys_ref)

    # no live slot: the one block the walk visits is the trash row's
    @pl.when(jnp.logical_and(i == 0, n == 0))
    def _():
        out_ref[...] = state_ref[...]

    @pl.when(i < n)
    def _():
        s = ids_ref[i]
        start, q = start_ref[s], qlen_ref[s]
        g = pl.multiple_of((s // ROWS) * ROWS, ROWS)
        pick = lax.broadcasted_iota(jnp.int32, (ROWS, 1), 0) == s - g
        d = jnp.sum(jnp.where(pick, dec_ref[pl.ds(g, ROWS), :], 0.0),
                    axis=0, keepdims=True)                  # [1, HPb]
        out_ref[...] = state_ref[...] * d

        def block(k, carry):
            r0 = pl.multiple_of(k * ROWS, ROWS)
            rows = r0 + lax.broadcasted_iota(jnp.int32, (ROWS, 1), 0)
            mine = jnp.logical_and(rows >= start, rows < start + q)
            y = jnp.dot(c_ref[pl.ds(r0, ROWS), :], state_ref[...],
                        preferred_element_type=jnp.float32)
            ys_ref[pl.ds(r0, ROWS), :] += jnp.where(mine, y, 0.0)
            bb = jnp.where(mine, b_ref[pl.ds(r0, ROWS), :], 0.0)
            out_ref[...] += lax.dot_general(
                bb, w_ref[pl.ds(r0, ROWS), :], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return carry

        lax.fori_loop(start // ROWS, (start + q + ROWS - 1) // ROWS, block, 0)


@functools.partial(jax.jit, static_argnames=("head_blocks", "interpret"))
def _pallas_impl(state, layer, ids, n_live, start, q_len, c, b, w, dec, *,
                 head_blocks, interpret):
    L, S1, N, HP = state.shape
    S = S1 - 1
    T = c.shape[0]
    lanes = HP // head_blocks

    def slot_of(i, ids, n):
        return ids[jnp.minimum(i, jnp.maximum(n[0] - 1, 0))]

    def state_map(j, i, layer, ids, n, start, q_len):
        # a step past the live slots keeps the block of the step before
        return layer[0], slot_of(i, ids, n), 0, j

    def lanes_map(j, i, *_):
        return 0, j

    def whole_map(j, i, *_):
        return 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(head_blocks, S),
        in_specs=[
            pl.BlockSpec((None, None, N, lanes), state_map),
            pl.BlockSpec((T, N), whole_map),
            pl.BlockSpec((T, N), whole_map),
            pl.BlockSpec((T, lanes), lanes_map),
            pl.BlockSpec((dec.shape[0], lanes), lanes_map),
        ],
        out_specs=[
            pl.BlockSpec((T, lanes), lanes_map),
            pl.BlockSpec((None, None, N, lanes), state_map),
        ])
    ys, new = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((T, HP), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={5: 1},        # the state, past 5 scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ssd_update",
    )(layer, ids, n_live, start, q_len, state, c, b, w, dec)
    return ys, new


def ssd_update(state, layer, c, b, w, dec, tok_slot, *, walk: Walk = None,
               impl: str = "auto", head_blocks: int | None = None):
    """``state [L, S + 1, N, H P]`` f32 (donate it); ``layer`` i32
    scalar; ``c``, ``b`` ``[T, N]`` and ``w [T, H P]`` f32, the tick's
    rows; ``dec [S, H P]`` f32; ``tok_slot [T]`` i32 (``S``: a row of no
    slot); ``walk``: ``live_walk(tok_slot, S)`` where the caller made it
    already. Returns ``(ys [T, H P] f32, state')``: see the module
    docstring. ``impl``: ``"auto"`` (the kernel on a TPU, the XLA twin
    elsewhere), ``"pallas"`` (the kernel; interpret mode off a TPU),
    ``"dense"``."""
    if impl not in ("auto", "pallas", "dense"):
        raise ValueError(f"unknown ssd_update impl {impl!r}")
    S = state.shape[1] - 1
    T = tok_slot.shape[0]
    layer = jnp.asarray(layer, jnp.int32)
    c, b, w, dec = (a.astype(jnp.float32) for a in (c, b, w, dec))
    if walk is None:
        walk = live_walk(tok_slot, S)
    if impl == "dense" or (impl == "auto" and not _on_tpu()):
        return _dense_impl(state, layer, c, b, w, dec, tok_slot, walk.q_len)
    pad = -T % ROWS
    if pad:
        c, b, w = (jnp.pad(a, ((0, pad), (0, 0))) for a in (c, b, w))
    dec = jnp.pad(dec, ((0, -S % ROWS), (0, 0)))
    if head_blocks is None:
        head_blocks = default_head_blocks(T + pad, S, state.shape[3],
                                          state.shape[2])
    ys, new = _pallas_impl(state, layer.reshape(1), walk.ids, walk.n_live,
                           walk.start, walk.q_len, c, b, w, dec,
                           head_blocks=head_blocks,
                           interpret=not _on_tpu())
    return ys[:T], new


# ---------------------------------------------------------------------------
# kernel-auditor registration (paddle_tpu/analysis/kernel_audit.py). The
# geometry is one layer's launch: the state's two axes, the slots and
# the packed rows of the tick.

AUDIT_KIND = "ssd_update"
AUDIT_GEOM_KEYS = ("slots", "rows", "state", "lanes")
AUDIT_CONFIG_KEYS = ("head_blocks",)
AUDIT_GEOMETRIES = (
    # granite-4.0-h-micro's serving cell: 64 decode rows + a 128-row span
    {"slots": 64, "rows": 192, "state": 128, "lanes": 4096},
    # its decode-only block
    {"slots": 64, "rows": 64, "state": 128, "lanes": 4096},
)
AUDIT_WAIVERS = (
    ("KA002", "ssd_update",
     "the state output is ALIASED to the state input and the walk visits "
     "the live slots' blocks only: a block it does not write keeps what "
     "the buffer held, which is the point (a slot without a row costs no "
     "DMA)"),
)


def audit_launches(geom, config=None):
    """One traceable launch: the state and the rows as
    ShapeDtypeStructs, the scalar-prefetched walk concrete (every second
    slot live with one row, the last live slot a span of the rest)."""
    S, T, N, HP = (int(geom[k]) for k in ("slots", "rows", "state", "lanes"))
    hb = int((config or {}).get("head_blocks")
             or default_head_blocks(T, S, HP, N))
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    live = np.arange(0, S, 2, dtype=np.int32)
    ids = np.full((S,), S, np.int32)
    ids[:live.size] = live
    q_len = np.zeros((S,), np.int32)
    q_len[live] = 1
    q_len[live[-1]] = T - (live.size - 1)
    start = np.full((S,), T, np.int32)
    start[live] = np.arange(live.size)
    args = (f32((2, S + 1, N, HP)), np.ones((1,), np.int32), ids,
            np.asarray([live.size], np.int32), start, q_len,
            f32((T, N)), f32((T, N)), f32((T, HP)),
            f32((-(-S // ROWS) * ROWS, HP)))
    fn = functools.partial(_pallas_impl, head_blocks=hb, interpret=False)
    return [(f"state_pass[head_blocks={hb}]", fn, args)]
