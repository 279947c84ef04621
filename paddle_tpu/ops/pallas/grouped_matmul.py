"""Grouped (per-expert) matmul Pallas kernels + dropless MoE glue.

Counterpart of the reference's fused MoE GEMM
(paddle/phi/kernels/fusion/cutlass/fused_moe_kernel.cu and the dispatch in
python/paddle/incubate/distributed/models/moe/moe_layer.py:119-190): there,
tokens are scattered to experts and each expert runs a CUTLASS grouped GEMM.

TPU-native version: ``gmm`` — one Pallas kernel over row tiles of the
token-sorted activation matrix, where each 128-row tile belongs to exactly
one expert (callers pad each expert's rows to the tile size). The expert id
per tile is a *scalar-prefetched* array, so the weight block for the right
expert is DMA'd from HBM before each tile's compute — the kernel reads
``lhs[tile] @ rhs[expert_of_tile]`` with zero gather/scatter inside.

This is the *dropless* MoE formulation (no capacity factor, no dropped
tokens): the fixed-capacity einsum path in incubate/moe stays as the
GShard-style alternative; ``moe_mlp_dropless`` below is the glue that
sorts/pads tokens by expert, runs the three FFN gmms, and combines with
router weights. Also used as the building block for grad-of-weights via
``tgmm`` (per-expert X^T G accumulation).

All kernels run in interpreter mode off-TPU so the CPU test mesh exercises
identical semantics (tests/test_pallas_kernels.py, tests/test_moe.py).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from . import on_tpu as _on_tpu


# ---------------------------------------------------------------------------
# gmm: out[i*TM:(i+1)*TM] = lhs[i*TM:(i+1)*TM] @ rhs[tile_expert[i]]
# ---------------------------------------------------------------------------

def _fit_tile_n(K: int, tile_m: int, tile_n: int, N: int,
                itemsize: int = 2, budget: int = 10 << 20) -> int:
    """Shrink tile_n until the kernel's VMEM working set (double-buffered
    lhs tile + weight block + out tile) fits the ~16MB/core VMEM."""
    tn = min(tile_n, N)
    while tn > 128:
        need = 2 * itemsize * (tile_m * K + K * tn + tile_m * tn)
        if need <= budget and N % tn == 0:
            return tn
        tn //= 2
    return tn if N % tn == 0 else N


# what the compiler keeps beside a call's own blocks, and the most a
# call asks for (a v5e core has 128 MiB of VMEM, the compiler's default
# scope is 16)
GMM_VMEM_SLACK = 8 << 20
GMM_VMEM_MOST = 96 << 20


def _vmem_limit(*block_bytes: int) -> int:
    """Two buffers a block plus slack, stated: a whole expert's weights
    a step pass the default scope."""
    return min(2 * sum(block_bytes) + GMM_VMEM_SLACK, GMM_VMEM_MOST)


def _live_row_tile(i, live):
    """The row tile grid step ``i`` names: its own while it is live,
    the last live one after (so a dead step fetches nothing)."""
    return jnp.minimum(i, jnp.maximum(live[0] - 1, 0))


def _gmm_kernel(tile_expert_ref, live_ref, lhs_ref, rhs_ref, out_ref):
    del tile_expert_ref  # consumed by the index maps
    i = pl.program_id(0)

    @pl.when(i < live_ref[0])
    def _():
        out_ref[...] = jnp.dot(
            lhs_ref[...], rhs_ref[0],
            preferred_element_type=jnp.float32).astype(out_ref.dtype)

    @pl.when(i >= live_ref[0])
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_n",
                                             "interpret"))
def _gmm_call(lhs, rhs, tile_expert, live, tile_m, tile_n, interpret):
    """``live [1]`` i32: row tiles ``>= live[0]`` hold no row (the
    static buffer's tail): they cost no matmul and no fetch, and their
    rows of the result are zeros."""
    M, K = lhs.shape
    E, K2, N = rhs.shape
    assert K == K2 and M % tile_m == 0 and N % tile_n == 0
    grid = (M // tile_m, N // tile_n)
    item = lhs.dtype.itemsize
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tile_m, K),
                             lambda i, j, te, lv: (_live_row_tile(i, lv), 0)),
                pl.BlockSpec((1, K, tile_n),
                             lambda i, j, te, lv: (te[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((tile_m, tile_n),
                                   lambda i, j, te, lv: (i, j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(tile_m * K * item,
                                         K * tile_n * item,
                                         tile_m * tile_n * item)),
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        interpret=interpret,
        name="grouped_matmul",
    )(tile_expert, live, lhs, rhs)


# ---------------------------------------------------------------------------
# tgmm: drhs[e] = sum over expert-e row tiles of lhs_tile^T @ g_tile
# (accumulates directly in the f32 output VMEM window; for a fixed n-tile
# the expert index is non-decreasing over the sequential TPU grid, so each
# output block is visited in one contiguous run)
# ---------------------------------------------------------------------------

def _tgmm_kernel(tile_expert_ref, live_ref, lhs_ref, g_ref, out_ref):
    i = pl.program_id(1)  # m tile (inner, sequential over experts)
    e = tile_expert_ref[i]
    first_of_expert = jnp.logical_or(
        i == 0, tile_expert_ref[jnp.maximum(i - 1, 0)] != e)

    @pl.when(first_of_expert)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i < live_ref[0])
    def _():
        out_ref[...] += jax.lax.dot_general(
            lhs_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[None]


@functools.partial(jax.jit, static_argnames=("num_experts", "tile_m",
                                             "tile_n", "interpret"))
def _tgmm_call(lhs, g, tile_expert, live, num_experts, tile_m, tile_n,
               interpret):
    M, K = lhs.shape
    M2, N = g.shape
    assert M == M2 and M % tile_m == 0 and N % tile_n == 0
    grid = (N // tile_n, M // tile_m)
    item = lhs.dtype.itemsize
    out = pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tile_m, K),
                             lambda j, i, te, lv: (_live_row_tile(i, lv), 0)),
                pl.BlockSpec((tile_m, tile_n),
                             lambda j, i, te, lv: (_live_row_tile(i, lv), j)),
            ],
            out_specs=pl.BlockSpec((1, K, tile_n),
                                   lambda j, i, te, lv: (te[i], 0, j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(tile_m * K * item,
                                         tile_m * tile_n * item,
                                         K * tile_n * 4)),
        out_shape=jax.ShapeDtypeStruct((num_experts, K, N), jnp.float32),
        interpret=interpret,
        name="grouped_matmul_dw",
    )(tile_expert, live, lhs, g)
    # experts owning no row tile never have their output block written —
    # zero them instead of returning uninitialised memory
    present = jnp.zeros((num_experts,), jnp.bool_).at[tile_expert].set(True)
    return jnp.where(present[:, None, None], out, 0.0)


# ---------------------------------------------------------------------------
# public op with custom VJP
# ---------------------------------------------------------------------------

def gmm(lhs, rhs, tile_expert, tile_m: int = 128, tile_n: int = 128,
        live_tiles=None):
    """Grouped matmul: rows are token tiles, each tile owned by one expert.

    lhs: ``[M, K]`` token-sorted activations, M % tile_m == 0; every row
      tile must belong to a single expert (pad groups to tile_m — see
      ``sort_and_pad_by_expert``).
    rhs: ``[E, K, N]`` per-expert weights.
    tile_expert: int32 ``[M // tile_m]`` expert id per row tile.
      PRECONDITION for gradients: must be NON-DECREASING (sorted by
      expert). The forward pass is correct for any order, but the
      weight-gradient kernel accumulates each expert's output block in
      one contiguous run of tiles — an out-of-order tile_expert (e.g.
      [0, 1, 0]) silently drops earlier contributions.
      ``sort_and_pad_by_expert`` always produces a sorted layout; the
      precondition is checked here when the value is concrete.
    live_tiles: i32 scalar, the row tiles that hold a row (the first
      ``live_tiles`` of them; default: all). The rest of a static
      worst-case buffer costs no matmul, and its rows of the result and
      of ``dlhs`` are zeros.

    Returns ``[M, N]`` in lhs dtype.
    """
    _check_sorted_tiles(tile_expert)
    if live_tiles is None:
        live_tiles = lhs.shape[0] // tile_m
    live = jnp.asarray(live_tiles, jnp.int32).reshape(1)
    return _gmm(lhs, rhs, tile_expert, live, tile_m, tile_n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _gmm(lhs, rhs, tile_expert, live, tile_m, tile_n):
    tn = _fit_tile_n(lhs.shape[1], tile_m, tile_n, rhs.shape[2],
                     lhs.dtype.itemsize)
    return _gmm_call(lhs, rhs, tile_expert, live, tile_m, tn,
                     interpret=not _on_tpu())


def _check_sorted_tiles(tile_expert):
    """Best-effort static check of the non-decreasing precondition (only
    possible when the value is concrete, i.e. outside jit)."""
    try:
        import numpy as _np
        te = _np.asarray(tile_expert)
    except Exception:
        return  # traced — caller guarantees (sort_and_pad_by_expert does)
    if te.size > 1 and _np.any(_np.diff(te) < 0):
        raise ValueError(
            "gmm: tile_expert must be non-decreasing (sorted by expert) "
            "for correct weight gradients; use sort_and_pad_by_expert")


def _gmm_fwd(lhs, rhs, tile_expert, live, tile_m, tile_n):
    return (_gmm(lhs, rhs, tile_expert, live, tile_m, tile_n),
            (lhs, rhs, tile_expert, live))


def _gmm_bwd(tile_m, tile_n, res, g):
    lhs, rhs, tile_expert, live = res
    interp = not _on_tpu()
    g = g.astype(lhs.dtype)
    # dlhs = g @ rhs[e]^T — same kernel with swapped weight dims (the
    # output dim is K here, re-fitted to VMEM by _fit_tile_n)
    tn_k = _fit_tile_n(rhs.shape[2], tile_m, tile_n, rhs.shape[1],
                       g.dtype.itemsize)
    dlhs = _gmm_call(g, jnp.swapaxes(rhs, 1, 2), tile_expert, live, tile_m,
                     tn_k, interpret=interp)
    tn_d = _fit_tile_n(rhs.shape[1], tile_m, tile_n, rhs.shape[2],
                       g.dtype.itemsize)
    drhs = _tgmm_call(lhs, g, tile_expert, live, rhs.shape[0], tile_m, tn_d,
                      interpret=interp).astype(rhs.dtype)
    return dlhs, drhs, None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


# ---------------------------------------------------------------------------
# dropless MoE glue
# ---------------------------------------------------------------------------

def sort_and_pad_by_expert(expert_ids: jax.Array, num_experts: int,
                           tile_m: int) -> Tuple[jax.Array, jax.Array,
                                                 jax.Array, int]:
    """Stable-sort assignment indices by expert and compute tile-aligned
    destination slots.

    expert_ids: int32 ``[A]`` expert per (token, k) assignment.
    Returns ``(order, dest, tile_expert, m_pad)``:
      order: ``[A]`` identity permutation (see note below);
      dest: ``[A]`` destination row of assignment ``order[i]`` in the
        padded ``[m_pad, ...]`` buffer (each expert's rows start at a
        tile_m-aligned offset; padding rows stay zero);
      tile_expert: ``[m_pad // tile_m]`` owning expert per row tile;
      m_pad: static padded row count = A rounded up + worst-case per-expert
        padding (shape must be static under jit).
    Implementation note: this is a counting sort, not ``argsort`` —
    sorting networks are slow on TPU, and with tiny E the stable sort is
    one cumsum over the one-hot assignment matrix. ``order`` is the
    identity (``dest[i]`` is where assignment ``i`` lands).
    """
    A = expert_ids.shape[0]
    m_pad = ((A + tile_m - 1) // tile_m + (num_experts - 1)) * tile_m
    order = jnp.arange(A, dtype=jnp.int32)
    onehot = (expert_ids[:, None]
              == jnp.arange(num_experts, dtype=expert_ids.dtype))
    incl = jnp.cumsum(onehot.astype(jnp.int32), axis=0)       # [A, E]
    counts = incl[-1]                                         # [E]
    # stable rank of assignment i within its expert group
    rank = jnp.take_along_axis(
        incl, expert_ids[:, None].astype(jnp.int32), axis=1)[:, 0] - 1
    padded_counts = ((counts + tile_m - 1) // tile_m) * tile_m
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(padded_counts)[:-1].astype(jnp.int32)])
    dest = starts[expert_ids] + rank
    tile_starts = jnp.arange(m_pad // tile_m, dtype=jnp.int32) * tile_m
    tile_expert = (jnp.searchsorted(
        jnp.cumsum(padded_counts), tile_starts, side="right")
        .astype(jnp.int32))
    # trailing all-padding tiles (rows past the last expert's block) get
    # clipped to a valid expert id; their lhs rows are zero so they only
    # produce zeros
    tile_expert = jnp.minimum(tile_expert, num_experts - 1)
    return order, dest, tile_expert, m_pad


def moe_mlp_dropless(x, expert_ids, combine_weights, w_gate, w_up, w_down,
                     *, tile_m: int = None, tile_n: int = None):
    """Dropless token-choice MoE FFN (SwiGLU experts) via grouped matmul.

    x: ``[S, D]`` tokens; expert_ids/combine_weights: ``[S, k]`` top-k
    routing (no capacity, nothing dropped); w_gate/w_up: ``[E, D, F]``;
    w_down: ``[E, F, D]``. Returns ``[S, D]``.

    ``tile_m``/``tile_n`` default to the persistent autotune winner for
    this routing geometry when ``kernel_bench --block-sweep`` has swept
    it (the KForge flywheel), else the static 128/128; explicit ints
    always win.
    """
    S, D = x.shape
    k = expert_ids.shape[1]
    E = w_gate.shape[0]
    if tile_m is None or tile_n is None:
        from .. import autotune as at
        win = at.lookup("grouped_matmul", S=S, D=D, F=int(w_gate.shape[2]),
                        E=E, k=k, dtype=str(jnp.dtype(x.dtype))) or {}
        tile_m = int(win.get("tile_m", 128)) if tile_m is None else tile_m
        tile_n = int(win.get("tile_n", 128)) if tile_n is None else tile_n
    flat_e = expert_ids.reshape(-1).astype(jnp.int32)
    order, dest, tile_expert, m_pad = sort_and_pad_by_expert(
        flat_e, E, tile_m)
    token_of = order // k  # source token for each sorted assignment
    xs = jnp.zeros((m_pad, D), x.dtype).at[dest].set(x[token_of])

    h = jax.nn.silu(gmm(xs, w_gate, tile_expert, tile_m, tile_n)) * \
        gmm(xs, w_up, tile_expert, tile_m, tile_n)
    ys = gmm(h.astype(x.dtype), w_down, tile_expert, tile_m,
             tile_n if D % tile_n == 0 else D)

    w = combine_weights.reshape(-1)[order].astype(ys.dtype)
    return (jnp.zeros((S, D), ys.dtype)
            .at[token_of].add(ys[dest] * w[:, None]))


# ---------------------------------------------------------------------------
# held_gmm: the serving block's grouped matmul over the experts a chip HOLDS
# ---------------------------------------------------------------------------
# ``gmm`` above walks ROW TILES and fetches the tile's expert: with a
# static worst-case row count (every pair could land on this chip) nearly
# all of its grid steps are padding, each re-fetching a weight block.
# Here a grid step is an EXPERT (and a column block of its weights), each
# weight block is fetched once whatever the rows, and the expert's row
# tiles are a loop whose trip count is data: an expert nobody chose costs
# its empty steps and, through the index map (``_held_w_index``), no
# fetch at any number of column blocks. Rows are sorted by
# expert, each expert's first row on a ``tile_m`` boundary
# (``sort_rows_by_held_expert``); weights enter as the model's STACKS
# ``[L, E, K, N]`` with the layer as a scalar, so no layer is sliced out
# in front of the call.

def _held_gmm_kernel(layer_ref, blk_ref, first_ref, tiles_ref, lhs_hbm,
                     *refs, tile_m: int, tile_n: int, gated: bool):
    del layer_ref, blk_ref                   # the index maps read them
    rhs = refs[:2] if gated else refs[:1]
    out_hbm, x_scr, o_scr, sems = refs[len(rhs):]
    e, j = pl.program_id(0), pl.program_id(1)
    col = pl.ds(pl.multiple_of(j * tile_n, tile_n), tile_n)

    def tile(t, carry):
        rows = pl.ds(pl.multiple_of((first_ref[e] + t) * tile_m, tile_m),
                     tile_m)
        cp = pltpu.make_async_copy(lhs_hbm.at[rows], x_scr, sems.at[0])
        cp.start()
        cp.wait()
        x = x_scr[...]
        y = jnp.dot(x, rhs[0][...], preferred_element_type=jnp.float32)
        if gated:
            y = jax.nn.silu(y) * jnp.dot(
                x, rhs[1][...], preferred_element_type=jnp.float32)
        o_scr[...] = y.astype(o_scr.dtype)
        cp = pltpu.make_async_copy(o_scr, out_hbm.at[rows, col], sems.at[1])
        cp.start()
        cp.wait()
        return carry

    jax.lax.fori_loop(0, tiles_ref[e], tile, 0)


def _held_w_index(e, j, layer, blk, first, tiles, *, last_j: int):
    """The weight block of grid step ``(e, j)``. An expert nobody chose
    names the block of the step before it in BOTH coordinates, so the
    pipeline fetches nothing for it: the last chosen expert (``blk``)
    at its LAST column block."""
    del first
    return layer[0], blk[e], 0, jnp.where(tiles[e] > 0, j, last_j)


# what the compiler keeps beside the call's own blocks (its internal
# scratch for the matmul's operands and result)
HELD_VMEM_SLACK = 16 << 20


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_n",
                                             "interpret"))
def _held_gmm_call(lhs, rhs, layer, blk, first, tiles, tile_m, tile_n,
                   interpret):
    """``lhs [M, K]`` rows sorted by expert; ``rhs``: one stack ``[L, E,
    K, N]`` (``lhs @ W``) or two (``silu(lhs @ W0) * (lhs @ W1)``).
    Rows outside the experts' live tiles are NOT written."""
    M, K = lhs.shape
    _, E, K2, N = rhs[0].shape
    assert K == K2 and M % tile_m == 0 and N % tile_n == 0
    w_spec = pl.BlockSpec(
        (None, None, K, tile_n),
        functools.partial(_held_w_index, last_j=N // tile_n - 1))
    kernel = functools.partial(_held_gmm_kernel, tile_m=tile_m,
                               tile_n=tile_n, gated=len(rhs) == 2)
    item = lhs.dtype.itemsize
    # two buffers a stack's block, the row tile and its result; stated,
    # because a whole expert a step (5.8 MB a stack at 2048 x 1408)
    # passes the compiler's default scope of 16 MiB
    vmem = (2 * len(rhs) * K * tile_n + tile_m * (K + tile_n)) * item
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(E, N // tile_n),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)]
            + [w_spec] * len(rhs),
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((tile_m, K), lhs.dtype),
                            pltpu.VMEM((tile_m, tile_n), lhs.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + HELD_VMEM_SLACK),
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        interpret=interpret,
        name="held_experts_matmul",
    )(layer, blk, first, tiles, lhs, *rhs)


def held_tile_n(K: int, N: int, itemsize: int = 2, whole: int = 8 << 20,
                budget: int = 4 << 20) -> int:
    """Columns a weight block: ``N`` or a multiple of 128 lanes that
    divides it (Mosaic refuses any other block). A WHOLE expert a step
    where ``K x N`` stays under ``whole`` bytes (two stacks, two buffers
    each: a quarter of the chip's VMEM), else the widest block under
    ``budget``. From the chip (``tools/kernel_bench.py --held-sweep``,
    PERF.md §6, PR 43): at 2048 x 1408 every legal block reads within
    1 % of the others, at 1536 x 2048 the whole expert is 2.3 % ahead
    of two halves."""
    if K * N * itemsize <= whole:
        return N
    legal = [tn for tn in range(128, N, 128) if N % tn == 0]
    under = [tn for tn in legal if K * tn * itemsize <= budget]
    return under[-1] if under else (legal[0] if legal else N)


def sort_rows_by_held_expert(local_ids, num_held: int, tile_m: int,
                             max_pairs: int = None):
    """``local_ids [A]``: each assignment's expert among the ``num_held``
    held ones, or ``num_held`` for an assignment that lands elsewhere.
    Returns ``(dest [A], first [E], tiles [E], counts [E], m_pad)``:
    ``dest`` the row of an assignment in the sorted buffer (``m_pad``
    for one that lands elsewhere: past the buffer), each expert's rows
    from ``first[e] * tile_m`` on, in ``tiles[e]`` tiles. A counting
    sort, as ``sort_and_pad_by_expert``. The buffer holds every
    assignment (``A`` of them could be held) unless the caller KNOWS
    that at most ``max_pairs`` are."""
    A = local_ids.shape[0]
    E = int(num_held)
    m_pad = (-(-min(A, max_pairs or A) // tile_m) + E) * tile_m
    onehot = local_ids[:, None] == jnp.arange(E, dtype=local_ids.dtype)
    incl = jnp.cumsum(onehot.astype(jnp.int32), axis=0)            # [A, E]
    counts = incl[-1]
    here = local_ids < E
    rank = jnp.take_along_axis(
        incl, jnp.minimum(local_ids, E - 1)[:, None], axis=1)[:, 0] - 1
    tiles = -(-counts // tile_m)
    first = jnp.cumsum(tiles) - tiles
    dest = jnp.where(
        here, first[jnp.minimum(local_ids, E - 1)] * tile_m + rank, m_pad)
    return (dest.astype(jnp.int32), first.astype(jnp.int32),
            tiles.astype(jnp.int32), counts, m_pad)


def held_experts_swiglu(x, local_ids, weights, w_gate, w_up, w_down, *,
                        layer=None, tile_m: int = 16):
    """Dropless SwiGLU over the experts this chip holds. ``x [N, D]``;
    ``local_ids`` / ``weights [N, k]``: every token's ``k`` choices as
    held-expert ordinals (``E`` = the choice lands elsewhere: it costs
    nothing here and adds nothing) and their combine weights; ``w_gate``
    / ``w_up [E, D, F]``, ``w_down [E, F, D]``, or with ``layer`` (i32
    scalar) the stacks ``[L, E, ...]``. Returns ``(y [N, D] float32,
    counts [E])``: ``y[n] = sum_j weights[n, j] swiglu(x[n], expert
    local_ids[n, j])`` over the held choices, and the rows each expert
    took."""
    N, D = x.shape
    k = local_ids.shape[1]
    if layer is None:
        w_gate, w_up, w_down, layer = w_gate[None], w_up[None], w_down[None], 0
    E, F = w_gate.shape[1], w_gate.shape[3]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    flat = local_ids.reshape(-1).astype(jnp.int32)
    dest, first, tiles, counts, m_pad = sort_rows_by_held_expert(
        flat, E, tile_m)
    # the sorted buffer's rows as tokens (a row nothing lands on reads
    # token 0: finite, and nothing gathers its result)
    row_token = jnp.zeros((m_pad,), jnp.int32).at[dest].set(
        jnp.arange(N * k, dtype=jnp.int32) // k, mode="drop")
    xs = x[row_token]
    # an expert nobody chose fetches nothing: its steps name the block
    # of the last expert before it that somebody chose (the FIRST one
    # chosen, where none comes before it)
    e_idx = jnp.arange(E, dtype=jnp.int32)
    chosen = counts > 0
    blk = jax.lax.cummax(jnp.where(chosen, e_idx, -1))
    blk = jnp.where(blk < 0, jnp.argmax(chosen).astype(jnp.int32), blk)
    interp = not _on_tpu()
    item = x.dtype.itemsize
    h = _held_gmm_call(xs, (w_gate, w_up), layer, blk, first, tiles,
                       tile_m=tile_m, tile_n=held_tile_n(D, F, item),
                       interpret=interp)
    ys = _held_gmm_call(h, (w_down,), layer, blk, first, tiles,
                        tile_m=tile_m, tile_n=held_tile_n(F, D, item),
                        interpret=interp)
    # pairs are token-major, so the combine is a gather and a sum over
    # a token's k; a row outside the live tiles was never written
    here = (flat < E)[:, None]
    rows = jnp.where(here, ys[jnp.minimum(dest, m_pad - 1)], 0)
    y = (rows.astype(jnp.float32)
         * weights.reshape(-1, 1).astype(jnp.float32))
    return y.reshape(N, k, D).sum(axis=1), counts


# ---------------------------------------------------------------------------
# the same share over MANY rows an expert, differentiable
# ---------------------------------------------------------------------------
# ``held_experts_swiglu`` walks EXPERTS and loops a data-dependent number
# of 16-row tiles inside a step, one synchronous copy a tile: right for a
# serving tick's handful of rows an expert, and forward only. With
# hundreds of rows an expert (a training step) the ROW-TILE walk ``gmm``
# is the one to use: its tiles are pipelined, an expert's weights stay in
# VMEM across its consecutive tiles (a whole expert a block), it has a
# backward (``dX`` the same kernel over the transposed weights, ``dW``
# ``_tgmm_call``), and the static buffer's dead tail costs neither a
# matmul nor a fetch (``live_tiles``).

def _share_rows(x, local_ids, weights, w_gate, w_up, w_down, *, tile_m,
                max_pairs):
    """One pass over rows ``x [N, D]`` whose held pairs number at
    most ``max_pairs`` (the caller's promise). ``(y [N, D] f32, counts
    [E], rows_padded)``."""
    N, D = x.shape
    k = local_ids.shape[1]
    E, F = w_gate.shape[0], w_gate.shape[2]
    flat = local_ids.reshape(-1).astype(jnp.int32)
    dest, first, tiles, counts, m_pad = sort_rows_by_held_expert(
        flat, E, tile_m, max_pairs)
    n_tiles = m_pad // tile_m
    live = tiles.sum()
    tile_expert = jnp.minimum(
        jnp.searchsorted(jnp.cumsum(tiles), jnp.arange(n_tiles),
                         side="right"), E - 1).astype(jnp.int32)
    # the sorted buffer's rows as pairs (-1: a row nothing lands on,
    # which is ZERO, gives zeros all the way and takes no gradient)
    row_pair = jnp.full((m_pad,), -1, jnp.int32).at[dest].set(
        jnp.arange(N * k, dtype=jnp.int32), mode="drop")
    row_live = row_pair >= 0
    row_token = jnp.maximum(row_pair, 0) // k
    xs = jnp.where(row_live[:, None], x[row_token], 0)
    grouped = functools.partial(gmm, tile_expert=tile_expert, tile_m=tile_m,
                                live_tiles=live)
    h = (jax.nn.silu(grouped(xs, w_gate, tile_n=F))
         * grouped(xs, w_up, tile_n=F))
    ys = grouped(h.astype(x.dtype), w_down, tile_n=D)
    # the combine over the SORTED rows (the bound's many, not the
    # pairs' N x k): each row's weight gathered, the weighted rows
    # scatter-added onto their tokens in float32 (a dead row past them)
    row_w = weights.reshape(-1).astype(jnp.float32)[jnp.maximum(row_pair, 0)]
    y = jnp.zeros((N, D), jnp.float32).at[
        jnp.where(row_live, row_token, N)].add(
        ys.astype(jnp.float32) * row_w[:, None], mode="drop")
    return y, counts, live * tile_m - counts.sum()


def _pass_ids(local_ids, num_held: int, max_pairs: int):
    """``(ids_of(j), needed)``: the held pairs ``max_pairs`` at a time,
    in pair order. ``ids_of(j)`` is ``local_ids`` with every pair that
    is not pass ``j``'s sent elsewhere; ``needed`` the passes that hold
    a pair."""
    held = local_ids < num_held
    place = (jnp.cumsum(held.reshape(-1)) - 1).reshape(held.shape)
    needed = -(-held.sum() // max_pairs)
    return (lambda j: jnp.where(held & (place // max_pairs == j),
                                local_ids, num_held)), needed


def _later_passes(first, one, needed, most: int):
    """``first`` (pass 0's results) plus those of the passes ``1 ..
    needed - 1`` (``one(j)``, the same pytree), at most ``most``
    passes: nothing runs where pass 0 held every pair."""
    def rest(acc):
        def body(acc, j):
            return jax.lax.cond(
                j < needed,
                lambda a: jax.tree_util.tree_map(jnp.add, a, one(j)),
                lambda a: a, acc), None
        return jax.lax.scan(body, acc,
                            jnp.arange(1, most, dtype=jnp.int32))[0]
    return jax.lax.cond(needed > 1, rest, lambda a: a, first)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _share_in_passes(x, local_ids, weights, w_gate, w_up, w_down, tile_m,
                     max_pairs):
    ids_of, needed = _pass_ids(local_ids, w_gate.shape[0], max_pairs)
    one = lambda j: _share_rows(x, ids_of(j), weights, w_gate, w_up, w_down,
                                tile_m=tile_m, max_pairs=max_pairs)
    y, counts, padded = _later_passes(
        one(0), one, needed, -(-local_ids.size // max_pairs))
    return y, counts, jnp.stack([padded, needed > 1]).astype(jnp.int32)


def _share_in_passes_fwd(x, local_ids, weights, w_gate, w_up, w_down, tile_m,
                         max_pairs):
    return (_share_in_passes(x, local_ids, weights, w_gate, w_up, w_down,
                             tile_m, max_pairs),
            (x, local_ids, weights, w_gate, w_up, w_down))


def _share_in_passes_bwd(tile_m, max_pairs, res, g):
    """A pass's gradients are made where its rows are sorted again
    (nothing of a pass is kept from the forward), and summed over the
    passes."""
    x, local_ids, weights, w_gate, w_up, w_down = res
    dy = g[0]
    ids_of, needed = _pass_ids(local_ids, w_gate.shape[0], max_pairs)

    def one(j):
        _, vjp = jax.vjp(
            lambda *a: _share_rows(a[0], ids_of(j), *a[1:], tile_m=tile_m,
                                   max_pairs=max_pairs)[0],
            x, weights, w_gate, w_up, w_down)
        return vjp(dy)

    dx, dw, dg, du, dd = _later_passes(
        one(0), one, needed, -(-local_ids.size // max_pairs))
    return dx, None, dw, dg, du, dd


_share_in_passes.defvjp(_share_in_passes_fwd, _share_in_passes_bwd)


def grouped_experts_swiglu(x, local_ids, weights, w_gate, w_up, w_down, *,
                           tile_m: int = 128, max_pairs: int = None):
    """``held_experts_swiglu``'s contract (``w_*`` one layer's ``[E,
    ...]``) by the row-tile walk, DIFFERENTIABLE in ``x``, ``weights``
    and the three stacks. ``max_pairs``: a static bound on the held
    (row, choice) pairs that sizes the sorted buffer (default: every
    pair could be held). Held pairs past the bound are NOT dropped: the
    held pairs go through the one buffer ``max_pairs`` at a time, in as
    many passes as they need, so a call within the bound runs one pass
    and a call past it is exact at the cost of sorting again and
    reading the weights once a pass.

    Returns ``(y [N, D] float32, counts [E], stats [2] int32)``:
    ``stats = [rows_padded, fell_back]``, the dead rows of the live
    tiles and whether the call needed more than one pass."""
    A = local_ids.size
    if max_pairs is None or max_pairs >= A:
        y, counts, padded = _share_rows(
            x, local_ids, weights, w_gate, w_up, w_down, tile_m=tile_m,
            max_pairs=A)
        return y, counts, jnp.stack([padded, 0]).astype(jnp.int32)
    return _share_in_passes(x, local_ids, weights, w_gate, w_up, w_down,
                            tile_m, max_pairs)


# ---------------------------------------------------------------------------
# kernel-audit registration (analysis/kernel_audit.py)
# ---------------------------------------------------------------------------
# Geometry keys match moe_mlp_dropless's autotune lookup kwargs, so
# block-sweep winners audit directly. The launches mirror the dropless
# MoE call sites: the gate/down gmms and the weight-gradient tgmm, with
# a sorted tile_expert covering every expert (the layout
# sort_and_pad_by_expert always produces).

AUDIT_KIND = "grouped_matmul"
AUDIT_GEOM_KEYS = ("S", "D", "F", "E", "k", "dtype")
AUDIT_CONFIG_KEYS = ("tile_m", "tile_n")
AUDIT_GEOMETRIES = (
    {"S": 256, "D": 512, "F": 1024, "E": 4, "k": 2, "dtype": "bfloat16"},
)


def audit_launches(geom, config=None):
    import numpy as np
    S, D, F, E = (int(geom[k]) for k in ("S", "D", "F", "E"))
    k = int(geom["k"])
    dt = jnp.dtype(geom["dtype"])
    cfg = config or {}
    tile_m = int(cfg.get("tile_m", 128))
    tile_n = int(cfg.get("tile_n", 128))
    A = S * k
    m_pad = ((A + tile_m - 1) // tile_m + (E - 1)) * tile_m
    n_tiles = m_pad // tile_m
    # sorted, all experts owning at least one tile — the layout the
    # sorted-precondition check and tgmm's contiguous-run accumulation
    # rely on
    te = np.sort(np.arange(n_tiles, dtype=np.int32) % E)
    live = np.asarray([n_tiles], np.int32)
    xs = jax.ShapeDtypeStruct((m_pad, D), dt)
    hs = jax.ShapeDtypeStruct((m_pad, F), dt)
    w_gate = jax.ShapeDtypeStruct((E, D, F), dt)
    w_down = jax.ShapeDtypeStruct((E, F, D), dt)
    item = dt.itemsize
    tn_gate = _fit_tile_n(D, tile_m, tile_n, F, item)
    tn_down = _fit_tile_n(F, tile_m, tile_n, D, item)
    tn_grad = _fit_tile_n(D, tile_m, tile_n, F, item)
    # the serving block's walk over held experts: every expert two row
    # tiles of 16, the stacks two layers deep, read at layer 1
    held_m = 16
    i32 = functools.partial(np.asarray, dtype=np.int32)
    held = (i32([1]), i32(np.arange(E)), i32(2 * np.arange(E)),
            i32(np.full((E,), 2)))
    hx = jax.ShapeDtypeStruct((2 * E * held_m, D), dt)
    stack = jax.ShapeDtypeStruct((2, E, D, F), dt)
    held_gate_up = (
        f"held_gmm_gate_up[{held_m}x{held_tile_n(D, F, item)}]",
        lambda x, wg, wu, *pre: _held_gmm_call(
            x, (wg, wu), *pre, tile_m=held_m,
            tile_n=held_tile_n(D, F, item), interpret=False),
        (hx, stack, stack, *held))
    return [held_gate_up,
        (f"gmm_gate[{tile_m}x{tn_gate}]",
         functools.partial(_gmm_call, tile_m=tile_m, tile_n=tn_gate,
                           interpret=False),
         (xs, w_gate, te, live)),
        (f"gmm_down[{tile_m}x{tn_down}]",
         functools.partial(_gmm_call, tile_m=tile_m, tile_n=tn_down,
                           interpret=False),
         (hs, w_down, te, live)),
        (f"tgmm_dw[{tile_m}x{tn_grad}]",
         functools.partial(_tgmm_call, num_experts=E, tile_m=tile_m,
                           tile_n=tn_grad, interpret=False),
         (xs, hs, te, live)),
    ]
