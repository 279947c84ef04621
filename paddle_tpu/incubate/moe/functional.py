"""Functional MoE: gating + expert dispatch, TPU-first.

Reference capability: python/paddle/incubate/distributed/models/moe/
(moe_layer.py:119-190,263 — gates + global_scatter/global_gather alltoall
dispatch; gshard_gate.py, switch_gate.py, naive_gate.py) and the fused
cutlass MoE kernel (paddle/phi/kernels/fusion/cutlass/fused_moe_kernel.cu).

TPU-native redesign: instead of per-rank index scatter + NCCL alltoall, the
whole dispatch is expressed as dense one-hot einsums over static shapes
(the GShard formulation). Expert weights carry a leading E axis sharded over
the mesh's ``ep`` axis; when dispatch/combine einsums contract against
ep-sharded operands, XLA GSPMD emits exactly the all_to_all the reference
hand-codes — and the expert FFN itself is one big grouped batched matmul
on the MXU.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def default_capacity(num_tokens: int, num_experts: int, top_k: int,
                     capacity_factor: float) -> int:
    """Per-expert token slots C (gshard_gate.py capacity computation)."""
    cap = int(capacity_factor * top_k * num_tokens / num_experts)
    return max(cap, top_k)


def _renormalize(gate_vals):
    """The chosen experts' scores over their sum (mixtral-style), one
    ``[S]`` array a choice."""
    denom = sum(gate_vals)
    denom = jnp.where(denom > 0, denom, 1.0)
    return [gv / denom for gv in gate_vals]


def top_k_choice(
    logits: jax.Array,
    top_k: int,
    *,
    score_fn: str = "softmax",
    select_bias: Optional[jax.Array] = None,
    normalize_topk: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The router's choice: ``logits [S, E]`` -> ``(idx [S, k] int32,
    w [S, k] float32, raw_gates [S, E])``, a token's ``top_k`` experts
    in the order it prefers them and their combine weights. The ONE
    place the choice is made: :func:`top_k_gating` builds its capacity
    dispatch from it and the serving block (:func:`moe_ffn_share`) its
    sorted rows, so the two cannot drift.

    ``score_fn``: ``"softmax"`` over the experts or ``"sigmoid"``, each
    expert scored on its own. ``select_bias [E]`` is added to the scores
    for the CHOICE only; the weights stay the unbiased scores.
    ``normalize_topk`` divides them by their sum over the choice."""
    E = logits.shape[-1]
    compute_dtype = jnp.float32
    if score_fn == "softmax":
        raw_gates = jax.nn.softmax(logits.astype(compute_dtype), axis=-1)
    elif score_fn == "sigmoid":
        raw_gates = jax.nn.sigmoid(logits.astype(compute_dtype))
    else:
        raise ValueError(f"score_fn must be 'softmax' or 'sigmoid', got "
                         f"{score_fn!r}")
    # iteratively peel off the top-k experts per token
    idxs, gate_vals = [], []
    g = raw_gates
    if select_bias is not None:
        g = g + select_bias.astype(compute_dtype)[None]
    for _ in range(top_k):
        idx = jnp.argmax(g, axis=-1)
        m = jax.nn.one_hot(idx, E, dtype=compute_dtype)      # [S, E]
        # a chosen expert is never picked again (a biased score may be
        # negative: zero would not keep it out)
        g = (g * (1.0 - m) if select_bias is None
             else jnp.where(m > 0, -jnp.inf, g))
        idxs.append(idx.astype(jnp.int32))
        gate_vals.append(jnp.sum(raw_gates * m, axis=-1))    # [S]
    if normalize_topk:
        gate_vals = _renormalize(gate_vals)
    return jnp.stack(idxs, axis=-1), jnp.stack(gate_vals, axis=-1), raw_gates


def top_k_gating(
    logits: jax.Array,
    top_k: int,
    capacity: int,
    *,
    key: Optional[jax.Array] = None,
    second_policy: str = "all",
    normalize_topk: bool = False,
    score_fn: str = "softmax",
    select_bias: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Dense top-k gating (GShard).

    Args:
      logits: ``[S, E]`` router logits for S tokens over E experts.
      top_k: experts per token (1 = switch, 2 = gshard).
      capacity: per-expert slot count C; overflow tokens are dropped.
      key: optional PRNG key; with ``second_policy='random'`` the 2nd+
        expert is kept with probability proportional to its gate value
        (gshard_gate.py random routing).
      score_fn, select_bias, normalize_topk: the router's, as
        :func:`top_k_choice` takes them.

    Returns:
      (dispatch, combine, aux_loss) with dispatch ``[S, E, C]`` one-hot,
      combine ``[S, E, C]`` float weights, and the load-balance aux loss
      (switch/gshard l_aux: E * mean_e(importance_e * load_e)).
    """
    S, E = logits.shape
    compute_dtype = jnp.float32
    random = second_policy == "random" and key is not None
    # with random routing the weights are renormalised over what is KEPT
    idx, w, raw_gates = top_k_choice(
        logits, top_k, score_fn=score_fn, select_bias=select_bias,
        normalize_topk=normalize_topk and not random)
    masks = [jax.nn.one_hot(idx[:, i], E, dtype=compute_dtype)
             for i in range(top_k)]
    gate_vals = [w[:, i] for i in range(top_k)]
    if random:
        for i in range(1, top_k):
            # keep the i-th expert with prob 2*gate (gshard random routing)
            key, sub = jax.random.split(key)
            keep = jax.random.uniform(sub, (S,)) < (2.0 * gate_vals[i])
            masks[i] = masks[i] * keep[:, None].astype(compute_dtype)
            gate_vals[i] = gate_vals[i] * keep.astype(compute_dtype)
        if normalize_topk:
            gate_vals = _renormalize(gate_vals)

    # aux load-balance loss uses the top-1 assignment (switch_gate.py)
    density = jnp.mean(masks[0], axis=0)                     # fraction routed
    density_proxy = jnp.mean(raw_gates, axis=0)              # mean gate prob
    aux_loss = jnp.mean(density * density_proxy) * (E * E)

    # position of each token in its expert's queue; earlier k-slots and
    # earlier tokens win capacity (cumsum ordering == reference prioritizing)
    dispatch = jnp.zeros((S, E, capacity), compute_dtype)
    combine = jnp.zeros((S, E, capacity), compute_dtype)
    running = jnp.zeros((E,), compute_dtype)
    for m, gv in zip(masks, gate_vals):
        pos_all = jnp.cumsum(m, axis=0) - m + running        # [S, E]
        pos = jnp.sum(pos_all * m, axis=-1).astype(jnp.int32)  # [S]
        running = running + jnp.sum(m, axis=0)
        within = (pos < capacity).astype(compute_dtype)
        oh_pos = jax.nn.one_hot(pos, capacity, dtype=compute_dtype)  # [S, C]
        d = (m * within[:, None])[:, :, None] * oh_pos[:, None, :]   # [S,E,C]
        dispatch = dispatch + d
        combine = combine + gv[:, None, None] * d
    return dispatch, combine, aux_loss


def moe_ffn_dropless(
    x: jax.Array,
    gate_w: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    top_k: int = 2,
):
    """Dropless token-choice MoE FFN (same contract as :func:`moe_ffn`,
    returns ``(y, aux_loss)``): routes through the authored grouped-GEMM
    Pallas kernel (ops/pallas/grouped_matmul.py) — no capacity factor,
    nothing dropped. Single-device/dp layouts; EP all_to_all dispatch
    stays on :func:`moe_ffn`. The load-balance aux loss uses the SAME
    switch-gate spelling as :func:`top_k_gating` so the two paths cannot
    drift."""
    from ...ops.pallas.grouped_matmul import moe_mlp_dropless

    orig_shape = x.shape
    D = orig_shape[-1]
    E = w_gate.shape[0]
    xs = x.reshape(-1, D)
    logits = xs.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    raw_gates = jax.nn.softmax(logits, axis=-1)
    cw, eids = jax.lax.top_k(raw_gates, top_k)
    # aux: identical formula to top_k_gating (top-1 density x mean prob)
    density = jnp.mean(jax.nn.one_hot(eids[:, 0], E, dtype=jnp.float32),
                       axis=0)
    density_proxy = jnp.mean(raw_gates, axis=0)
    aux = jnp.mean(density * density_proxy) * (E * E)
    y = moe_mlp_dropless(xs, eids, cw.astype(x.dtype), w_gate, w_up,
                         w_down)
    return y.reshape(orig_shape), aux


def moe_expert_compute(
    xs: jax.Array,
    dispatch: jax.Array,
    combine: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    ep_axis: Optional[str] = None,
    activation=jax.nn.silu,
) -> jax.Array:
    """Dispatch -> grouped expert SwiGLU -> combine, on tokens ``[S, D]``
    with gating tensors ``[S, E, C]`` (shared by moe_ffn and MoELayer)."""
    dispatch = dispatch.astype(xs.dtype)
    combine = combine.astype(xs.dtype)
    expert_in = jnp.einsum("sec,sd->ecd", dispatch, xs)      # [E, C, D]
    if ep_axis is not None:
        expert_in = lax.with_sharding_constraint(
            expert_in, jax.sharding.PartitionSpec(ep_axis, None, None))
    h = activation(jnp.einsum("ecd,edf->ecf", expert_in, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", expert_in, w_up)
    expert_out = jnp.einsum("ecf,efd->ecd", h, w_down)       # [E, C, D]
    if ep_axis is not None:
        expert_out = lax.with_sharding_constraint(
            expert_out, jax.sharding.PartitionSpec(ep_axis, None, None))
    return jnp.einsum("sec,ecd->sd", combine, expert_out)    # [S, D]


def moe_ffn(
    x: jax.Array,
    gate_w: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    top_k: int = 2,
    capacity_factor: float = 2.0,
    key: Optional[jax.Array] = None,
    ep_axis: Optional[str] = None,
    activation=jax.nn.silu,
    score_fn: str = "softmax",
    select_bias: Optional[jax.Array] = None,
    normalize_topk: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Mixture-of-experts SwiGLU FFN over tokens ``x`` ``[..., D]``.

    Expert weights are stacked on a leading E axis: ``w_gate/w_up [E, D, F]``,
    ``w_down [E, F, D]``. With ``ep_axis`` set and the weights ep-sharded,
    the dispatch/combine einsums below compile to the expert-parallel
    all_to_all (moe_layer.py global_scatter/global_gather equivalent).
    ``score_fn``, ``select_bias`` and ``normalize_topk`` are the
    router's, as :func:`top_k_gating` takes them; the defaults give the
    softmax router with unnormalised weights.

    Returns (y, aux_loss) with y shaped like x.
    """
    orig_shape = x.shape
    D = orig_shape[-1]
    E = w_gate.shape[0]
    xs = x.reshape(-1, D)                                    # [S, D]
    S = xs.shape[0]
    capacity = default_capacity(S, E, top_k, capacity_factor)

    # scopes: metadata that names these operations in a device trace
    with jax.named_scope("moe.router"):
        logits = xs.astype(jnp.float32) @ gate_w.astype(jnp.float32)  # [S, E]
        dispatch, combine, aux = top_k_gating(
            logits, top_k, capacity, key=key, score_fn=score_fn,
            select_bias=select_bias, normalize_topk=normalize_topk)
    with jax.named_scope("moe.experts"):
        y = moe_expert_compute(xs, dispatch, combine, w_gate, w_up, w_down,
                               ep_axis=ep_axis, activation=activation)
    return y.reshape(orig_shape), aux.astype(jnp.float32)


# rows an expert (the call's rows x top_k over the router's outputs)
# from which the grouped matmul walks row tiles of ROW_TILE_M rows: a
# full MXU pass of rows an expert, where a pipelined tile pays and the
# expert walk's one synchronous copy a 16-row tile no longer does
ROW_TILE_WALK_FROM = 128
ROW_TILE_M = 128
# pairs under which the sorted buffer is sized for the worst case (every
# pair held): at 2048 columns 16 384 rows are 64 MiB, and a bound would
# buy nothing
HELD_PAIRS_UNBOUNDED_TO = 16384


def held_pairs_bound(rows: int, top_k: int, held: int, outputs: int) -> int:
    """The static bound on the (row, choice) pairs that land on the
    ``held`` experts of a router with ``outputs`` outputs, which sizes
    the row-tile walk's sorted buffer: TWICE the expectation under a
    balanced router (``rows x top_k x held / outputs``), never under
    ``HELD_PAIRS_UNBOUNDED_TO`` and never over the worst case (every
    pair: ``rows x top_k``). A step whose pairs exceed it is computed
    exactly all the same (``grouped_experts_swiglu``: further passes
    over the same buffer), at the cost of sorting again and reading the
    weights once a pass."""
    worst = rows * top_k
    return min(worst, max(2 * worst * held // outputs,
                          HELD_PAIRS_UNBOUNDED_TO))


def moe_ffn_share(
    x: jax.Array,
    router_w: jax.Array,
    select_bias: Optional[jax.Array],
    experts: dict,
    *,
    held: Tuple[int, int],
    num_routed: int,
    zero_experts: int = 0,
    top_k: int = 2,
    scale: float = 1.0,
    layer=None,
    row_mask: Optional[jax.Array] = None,
    score_fn: str = "softmax",
    normalize_topk: bool = False,
    impl: str = "auto",
    tile_m: int = 16,
    row_stats: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """The expert layer of a chip that HOLDS a share of an expert-
    parallel deployment's experts (``held = (0, num_routed)``: all of
    them, a chip that has the whole layer), dropless, for a serving
    tick and a training step alike. It routes over every
    router output, computes the part of the experts it holds and the
    identity experts' part, and reads the weights of the held experts
    that took a row and of no other.

    DIFFERENTIABLE in ``x``, ``router_w`` and the experts' stacks: the
    router's gradient comes through the combine weights (the scores of
    the chosen, renormalised where asked), none through the choice and
    none to ``select_bias``. On the TPU the grouped matmul goes by what
    the call observes: a handful of rows an expert
    (a serving tick) walks the EXPERTS, forward only; from
    ``ROW_TILE_WALK_FROM`` rows an expert on (a training step) it walks
    ROW TILES, which has a backward, over a sorted buffer sized by
    ``held_pairs_bound``, exact past it too (further passes).

    ``x [N, D]``; ``router_w [D, num_routed + zero_experts]`` (float32
    router; ``score_fn``, ``select_bias`` and ``normalize_topk`` as
    :func:`top_k_choice` takes them, ``scale`` times the weights after
    it). ``experts``:
    ``{"w_gate", "w_up": [n, D, F], "w_down": [n, F, D]}``, the ``n =
    held[1]`` experts ``held[0] .. held[0] + n - 1`` of the
    ``num_routed`` — or, with ``layer`` (i32 scalar), the model's stacks
    ``[L, n, ...]`` read at that layer. Outputs ``>= num_routed`` are
    IDENTITY experts: a choice of one adds ``scale * p * x``, on every
    chip for its own tokens. A choice of a routed expert this chip does
    not hold adds nothing HERE (its chip adds it, in a deployment that
    exchanges tokens); the sum over all shares' held parts plus the
    identity part once is the uncut layer (``tests/
    test_longcat_flash.py``).

    ``row_mask [N]`` bool: rows that are no token (a tick's padding)
    route nowhere and count nowhere. ``impl``: ``auto`` (the grouped
    matmul of ``ops/pallas/grouped_matmul.py: held_experts_swiglu`` on
    TPU, a masked einsum over the held experts elsewhere), ``pallas``,
    ``dense``.

    Returns ``(y [N, D] in x.dtype, counts [4] int32)``: ``counts =
    [pairs_held, pairs_zero, pairs_absent, experts_touched]``, with
    ``pairs_held + pairs_zero + pairs_absent = top_k x rows``. With
    ``row_stats`` two more follow: ``[rows_padded, bound_fallbacks]``,
    the dead rows of the sorted buffer's live tiles and whether this
    call took the fall-back (both 0 where no buffer is sorted).
    """
    from ...ops.pallas import grouped_matmul as _gmm
    lo, n = int(held[0]), int(held[1])
    N, D = x.shape
    with jax.named_scope("moe.router"):
        logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
        idx, w, _ = top_k_choice(
            logits, top_k, score_fn=score_fn, select_bias=select_bias,
            normalize_topk=normalize_topk)                          # [N, k]
        w = w * scale
        if row_mask is not None:
            w = jnp.where(row_mask[:, None], w, 0.0)
            # a masked row's choices land nowhere
            idx = jnp.where(row_mask[:, None], idx, -1)
        local = idx - lo
        here = (local >= 0) & (local < n)
        zero = idx >= num_routed
    with jax.named_scope("moe.experts"):
        local = jnp.where(here, local, n).astype(jnp.int32)
        w_here = jnp.where(here, w, 0.0)
        grouped = impl == "pallas" or (impl == "auto" and _gmm._on_tpu())
        by_row_tiles = grouped and (
            N * top_k // (num_routed + zero_experts) >= ROW_TILE_WALK_FROM)
        stats = None
        if not grouped or by_row_tiles:
            ex = experts if layer is None else {
                k: lax.dynamic_index_in_dim(v, layer, 0, keepdims=False)
                for k, v in experts.items()}
        if by_row_tiles:
            y, rows, stats = _gmm.grouped_experts_swiglu(
                x, local, w_here, ex["w_gate"], ex["w_up"], ex["w_down"],
                tile_m=ROW_TILE_M, max_pairs=held_pairs_bound(
                    N, top_k, n, num_routed + zero_experts))
        elif grouped:
            y, rows = _gmm.held_experts_swiglu(
                x, local, w_here, experts["w_gate"], experts["w_up"],
                experts["w_down"], layer=layer, tile_m=tile_m)
        else:
            onehot = jax.nn.one_hot(local, n + 1, dtype=jnp.float32)[..., :n]
            per = jnp.einsum("nk,nke->ne", w_here, onehot)          # [N, n]
            rows = (onehot.sum((0, 1))).astype(jnp.int32)
            h = jax.nn.silu(jnp.einsum("nd,edf->enf", x, ex["w_gate"]))
            h = h * jnp.einsum("nd,edf->enf", x, ex["w_up"])
            y = jnp.einsum("enf,efd,ne->nd", h, ex["w_down"],
                           per.astype(x.dtype)).astype(jnp.float32)
    if zero_experts:
        with jax.named_scope("moe.zero"):
            z = jnp.sum(jnp.where(zero, w, 0.0), axis=-1)           # [N]
            y = y + z[:, None] * x.astype(jnp.float32)
    y = y.astype(x.dtype)
    with jax.named_scope("moe.router"):
        pairs_held = here.sum()
        pairs_zero = zero.sum()
        real = (N if row_mask is None else row_mask.sum()) * top_k
        counts = jnp.stack([pairs_held, pairs_zero,
                            real - pairs_held - pairs_zero,
                            (rows > 0).sum()]).astype(jnp.int32)
        if row_stats:
            counts = jnp.concatenate(
                [counts, jnp.zeros((2,), jnp.int32) if stats is None
                 else stats])
    return y, counts
