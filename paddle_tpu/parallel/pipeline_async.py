"""Rank-asymmetric 1F1B / zero-bubble pipeline schedules.

The lockstep traced schedule (``pipeline_1f1b.py``) runs every slot on
every tick — fill/drain manifests as masked work, a (2S-1)/(M+2S-1)
tick fraction that lags the reference's per-rank 1F1B by 10-20
efficiency points at pp>=4 (tools/pipeline_ceiling.py, docs/PERF.md).
The reference kills that bubble with PER-RANK schedules
(pipeline_parallel.py:565 forward_backward_pipeline,
pipeline_zero_bubble.py): each rank runs warmup forwards, a steady
1F1B interleave, and a drain tail — DIFFERENT code per rank. This
module expresses that under XLA as one SPMD program:

  * a HOST-side schedule builder computes, for every ``(tick, rank)``,
    which op runs — forward (F), input-grad backward (B), deferred
    weight-grad (W), forward+loss-head (FH on the last rank), or idle —
    via a greedy list scheduler over the true data dependencies
    (1-tick neighbour latency), then register-allocates every saved
    activation/cotangent into a bounded ring (the O(S)-not-O(M)
    1F1B memory property, now proven per schedule by interval
    allocation instead of asserted);
  * a TRACED executor (`pipeline_train_async`) wraps one
    ``lax.scan`` over ticks in a ``shard_map`` over the ``pp`` axis.
    The scan body branches on the prefetched op code with
    ``lax.switch`` — ``lax.axis_index("pp")`` picks each rank's column
    of the op table, so every device executes ONLY its own rank's op
    for the tick (a real branch at runtime, not masked lockstep work).
    Neighbour exchange is one up- and one down-``ppermute`` per tick,
    unconditional, so the collective signature is identical on every
    rank by construction.

Variants (``schedule_ticks`` / ``schedule_efficiency`` model both):

  * ``"1f1b"`` — classic rank-asymmetric 1F1B: ticks are half-steps
    (one F or one full backward per rank). Span = 2(VM + S - 1) ticks,
    efficiency VM/(VM + S - 1) — the reference 1F1B bubble exactly
    (0.889 at pp=2/M=8, 0.970 at M=32), including interleaved V>1
    (efficiency 1 - (S-1)/(VM + S - 1), the VPP fill-shrink the
    lockstep form could not express).
  * ``"zb"`` — ZB-H1-style W-deferral (pipeline_zero_bubble.py): the
    backward splits into B (input grads, critical path) and W (weight
    grads, deferred into bubble slots; backlog bounded by S so the
    saved-tensor ring stays O(S)). Span = 3VM + fill/drain remainder —
    strictly above the 1F1B bound at every geometry. Honest cost: B's
    ``jax.vjp`` re-runs the stage forward (a pullback cannot cross
    scan ticks), but its RESIDUALS — the pullback's own pytree leaves
    — are ring-saved (interval-colored like the sx/sc rings, depths
    still exactly M-independent), so W restores the saved pullback and
    computes weight grads with NO second forward replay: ~4.5 work
    units per microbatch-stage vs the fused backward's 4 (the dW pass
    still re-walks the cotangent chain — docs/PERF.md r19 quantifies
    the cut from the r14 5/4).

Numerics are IDENTICAL to the lockstep schedule by construction: the
same per-microbatch stage/head functions, f32 grad accumulation in the
same per-stage microbatch order, mean over M — every existing pipeline
exactness test doubles as a correctness pin for this module
(tests/test_pipeline_async.py asserts loss+grads match lockstep and
plain single-stage autodiff).

Mesh composition (r19, ROADMAP item 4's roll-forward): the shard_map
now spans the FULL ``(dp, tp, pp)`` mesh. The op-table scan and the
up/down ppermute pair run along ``pp`` exactly as before; ``dp``
shards the microbatch rows (the caller's ``x_spec``), with the dp
gradient psum folded into the f32 accumulation carry AFTER the scan —
one psum per accumulator leaf, not per microbatch — and loss/ghead
psum'd over dp×pp; ``tp`` shards the stage weights per the caller's
``stage_specs``, with the stage/head bodies doing their own in-body
collectives (models/llama.py `_tp_local_block`: megatron f/g custom
ops from parallel/mp_ops.py + vocab-parallel CE). Axes other than
dp/tp/pp (cp, ep) must still be size 1.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

# op codes — ALSO the lax.switch branch order in pipeline_train_async
IDLE, OP_F, OP_B, OP_FH, OP_W = 0, 1, 2, 3, 4
KIND_NAMES = {IDLE: "idle", OP_F: "F", OP_B: "B", OP_FH: "F+head",
              OP_W: "W"}
VARIANTS = ("1f1b", "zb")

#: the ONE statement of what each pp_schedule config value means:
#: LlamaConfig.pp_schedule -> (schedule-model name spoken by
#: schedule_ticks/schedule_efficiency, executor variant — None = the
#: lockstep pipeline_1f1b executor). llama, analysis/training_graphs
#: and tools/pipeline_ceiling all derive from this so a new schedule
#: cannot desynchronize them.
PP_SCHEDULES = {
    "1f1b": ("lockstep", None),
    "1f1b_async": ("1f1b", "1f1b"),
    "zb": ("zb", "zb"),
}


@dataclasses.dataclass(frozen=True)
class ScheduleInfo:
    """Queryable metadata for one ``pp_schedule`` value — the legality
    constraints and cost facts that used to live as raise-sites inside
    the builders and prose inside docstrings. The auto-parallel planner
    (analysis/planner.py) enumerates its search space from this table;
    ``schedule_legality`` below is derived from the same fields the
    executors enforce, so a constraint added to one cannot silently
    miss the other.

    ``work_units_per_mb_stage``: relative compute units one microbatch
    costs one stage (F=1, fused backward=3). The zb variant's B
    re-runs the stage forward inside its ``jax.vjp`` and W re-walks
    the cotangent chain from the ring-saved residuals (no second
    forward replay — r19's residual-ring cut from the r14 5/4) —
    ~4.5 units vs 4 (docs/PERF.md r19) — which the planner prices as
    a flop multiplier.
    ``lockstep_masked_work``: the schedule executes every slot every
    tick, so (1 - efficiency) is REAL extra compute, not idle time.
    """
    name: str                   # LlamaConfig.pp_schedule value
    model: str                  # schedule_ticks/schedule_efficiency name
    executor: Optional[str]     # pipeline_async variant; None = lockstep
    requires_dp1_tp1: bool      # True only for a schedule whose stage
    #                             body cannot compose dp/tp (none today)
    supports_vpp: bool          # virtual_chunks > 1 allowed
    vpp_needs_divisible_M: bool  # V>1 requires M % S == 0
    min_stages: int
    work_units_per_mb_stage: float
    lockstep_masked_work: bool

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


#: pp_schedule name -> ScheduleInfo. Consistent with PP_SCHEDULES by
#: construction (asserted at import below).
SCHEDULE_INFO: Dict[str, ScheduleInfo] = {
    "1f1b": ScheduleInfo(
        name="1f1b", model="lockstep", executor=None,
        requires_dp1_tp1=False, supports_vpp=True,
        vpp_needs_divisible_M=False, min_stages=1,
        work_units_per_mb_stage=4, lockstep_masked_work=True),
    "1f1b_async": ScheduleInfo(
        name="1f1b_async", model="1f1b", executor="1f1b",
        requires_dp1_tp1=False, supports_vpp=True,
        vpp_needs_divisible_M=True, min_stages=2,
        work_units_per_mb_stage=4, lockstep_masked_work=False),
    "zb": ScheduleInfo(
        name="zb", model="zb", executor="zb",
        requires_dp1_tp1=False, supports_vpp=False,
        vpp_needs_divisible_M=True, min_stages=2,
        work_units_per_mb_stage=4.5, lockstep_masked_work=False),
}
assert set(SCHEDULE_INFO) == set(PP_SCHEDULES) and all(
    (i.model, i.executor) == PP_SCHEDULES[n]
    for n, i in SCHEDULE_INFO.items())

#: executor variant -> pp_schedule name (build_schedule speaks variant)
_VARIANT_TO_SCHEDULE = {v: n for n, (_, v) in PP_SCHEDULES.items()
                        if v is not None}


def schedule_legality(name: str, *, num_stages: int,
                      num_microbatches: int, virtual_chunks: int = 1,
                      dp: int = 1, tp: int = 1) -> Optional[str]:
    """None when ``(schedule, geometry)`` is legal, else the reason it
    is not — the ONE statement of schedule legality. ``build_schedule``
    raises exactly these reasons for its subset (asserted by the
    rejection tests), ``pipeline_train_async`` enforces the mesh-axis
    restriction at run time, and the planner prunes its search space
    with the same answers, so legality cannot drift between the three.

    ``dp``/``tp`` are accepted for any schedule since r19 (the
    executor composes both into the shard_map — model-level
    divisibility like heads-per-tp-shard is the planner's/caller's
    mesh-level check, not a schedule property); the parameters remain
    so a future schedule that genuinely cannot compose can gate on
    them via ``requires_dp1_tp1``.
    """
    info = SCHEDULE_INFO.get(name)
    if info is None:
        return (f"variant must be one of {tuple(SCHEDULE_INFO)}, "
                f"got {name!r}")
    S, M, V = int(num_stages), int(num_microbatches), int(virtual_chunks)
    if M < 1 or V < 1:
        return "need num_microbatches >= 1, virtual_chunks >= 1"
    if S < info.min_stages:
        if info.min_stages >= 2:
            return ("rank-asymmetric schedules need num_stages >= 2 "
                    "(pp=1 has no pipeline bubble — use the plain or "
                    "lockstep path)")
        return f"need num_stages >= {info.min_stages}"
    if V > 1 and not info.supports_vpp:
        return ("zb W-deferral with virtual_chunks > 1 (ZB-V-style "
                "schedules) is not supported — the reference's "
                "pipeline_zero_bubble.py ZB-H1 is V=1 too; use "
                "variant='1f1b' for interleaved VPP")
    if V > 1 and info.vpp_needs_divisible_M and M % S:
        return (f"interleaved V>1 needs num_microbatches divisible by "
                f"num_stages (the reference's VPP constraint), got "
                f"M={M} S={S}")
    if info.requires_dp1_tp1 and (int(dp) > 1 or int(tp) > 1):
        return (f"schedule {name!r} currently requires every non-pp "
                f"mesh axis to be size 1 (the shard_map stage body is "
                f"a single-device program); got dp={dp} tp={tp}")
    return None


@dataclasses.dataclass(frozen=True)
class Schedule:
    """One built rank-asymmetric schedule: the static op/routing tables
    the traced executor consumes, plus the bookkeeping tests pin.

    All tables are int32 ``[ticks, S]`` (tick-major so ``lax.scan`` can
    slice per-tick rows): ``kind`` (op codes above), ``chunk``/``mb``
    (which (virtual chunk, microbatch) the op touches), ``slot_x`` /
    ``slot_c`` (saved-activation / saved-cotangent ring slots the op
    reads — for F with ``inject`` set, the slot it WRITES the injected
    input to), ``slot_r`` (zb only: the residual-ring slot B WRITES its
    pullback's residual leaves to and W READS them from — what lets W
    skip the stage-forward replay), ``inject`` (F consumes ``x[mb]``
    instead of an arrival), ``emit`` (B's dx is the stage-0 embedding
    cotangent), ``store_up`` / ``store_dn`` (ring slot where this rank
    stores the value arriving on the up/down ppermute at the END of
    the tick; -1 = none/discard).
    """
    num_stages: int
    num_microbatches: int
    virtual_chunks: int
    variant: str
    ticks: int
    depth_x: int          # saved-activation ring depth (max over ranks)
    depth_c: int          # saved-cotangent ring depth
    depth_r: int          # saved-residual ring depth (zb; 0 otherwise)
    kind: np.ndarray
    chunk: np.ndarray
    mb: np.ndarray
    slot_x: np.ndarray
    slot_c: np.ndarray
    slot_r: np.ndarray
    inject: np.ndarray
    emit: np.ndarray
    store_up: np.ndarray
    store_dn: np.ndarray

    @property
    def useful_ticks_per_rank(self) -> int:
        per_mb = 3 if self.variant == "zb" else 2
        return per_mb * self.virtual_chunks * self.num_microbatches

    @property
    def efficiency(self) -> float:
        """Non-idle fraction of each rank's ticks — the schedule-bubble
        measure the reference's 1F1B/ZB numbers are quoted in."""
        return self.useful_ticks_per_rank / self.ticks

    def op_counts(self) -> Dict[str, int]:
        """rank-tick counts per op kind over the whole schedule."""
        out = {}
        for code, name in KIND_NAMES.items():
            out[name] = int((self.kind == code).sum())
        return out


def _f_dest(S: int, V: int, v: int, s: int, m: int
            ) -> Optional[Tuple[int, int, int]]:
    """Where chunk (v, s)'s F output lands: (v, s+1) one rank up, or
    the ring wrap (v+1, 0) from the last rank to rank 0. None for the
    last chunk's F (= FH — the loss head consumes it locally).

    The ONE statement of the forward routing: both schedule builders
    AND the store_up table construction use it (``_validate``
    re-states it independently, on purpose — it is the check)."""
    if v == V - 1 and s == S - 1:
        return None
    if s == S - 1:
        return (v + 1, 0, m)
    return (v, s + 1, m)


def _b_dest(S: int, V: int, v: int, s: int, m: int
            ) -> Optional[Tuple[int, int, int]]:
    """Where B's dx cotangent lands: (v, s-1) one rank down, or the
    wrap (v-1, S-1) from rank 0 back to the last rank. None at chunk
    (0, 0) — that dx is the embedding cotangent (emitted)."""
    if v == 0 and s == 0:
        return None
    if s == 0:
        return (v - 1, S - 1, m)
    return (v, s - 1, m)


def _interleaved_order(S: int, s: int, M: int, V: int
                       ) -> List[Tuple[str, int, int]]:
    """Rank ``s``'s fixed op order for interleaved V>1 — the
    reference's VPP pattern (pipeline_parallel.py:1372, same shape as
    Megatron's interleaved 1F1B): microbatches run in groups of S;
    forwards cycle chunks 0..V-1 per group, backwards cycle V-1..0;
    warmup = 2(S-s-1) + (V-1)S + 1 forwards (the Megatron count, +1
    because the steady-state pair here is F-then-B against our 1-tick
    arrival latency), then strict F,B pairs, then the backward drain.
    Greedy choice cannot reproduce this pattern (it deadlocks against
    the wrap dependencies), so V>1 uses the fixed order and — like
    the reference — requires M % S == 0."""
    total = V * M

    def f_op(k):
        return (k // S) % V, (k // (S * V)) * S + k % S

    def b_op(k):
        return V - 1 - ((k // S) % V), (k // (S * V)) * S + k % S

    warmup = min(2 * (S - s - 1) + (V - 1) * S + 1, total)
    ops: List[Tuple[str, int, int]] = [
        ("F",) + f_op(k) for k in range(warmup)]
    for k in range(total - warmup):
        ops.append(("F",) + f_op(warmup + k))
        ops.append(("B",) + b_op(k))
    for k in range(total - warmup, total):
        ops.append(("B",) + b_op(k))
    return ops


def _fixed_order_schedule(S: int, M: int, V: int
                          ) -> List[List[Tuple[int, int, int]]]:
    """Earliest-feasible tick assignment of the fixed interleaved op
    order: each rank executes its list strictly in order, idling while
    the next op's input has not arrived (1-tick neighbour latency)."""
    orders = {s: _interleaved_order(S, s, M, V) for s in range(S)}
    ptr = {s: 0 for s in range(S)}
    act_arr: Dict[Tuple[int, int, int], int] = {}
    ct_arr: Dict[Tuple[int, int, int], int] = {}
    grid: List[List[Tuple[int, int, int]]] = []
    limit = 8 * (2 * V * M + 2 * S * V) + 64
    t = 0
    while any(ptr[s] < len(orders[s]) for s in range(S)):
        if t >= limit:
            raise AssertionError(
                f"fixed-order schedule stalled for S={S} M={M} V={V}")
        row: List[Tuple[int, int, int]] = []
        for s in range(S):
            op = (IDLE, 0, 0)
            if ptr[s] < len(orders[s]):
                what, v, m = orders[s][ptr[s]]
                if what == "F":
                    ready = (v == 0 and s == 0) or \
                        act_arr.get((v, s, m), t) <= t - 1
                    if ready:
                        kind = (OP_FH if (v == V - 1 and s == S - 1)
                                else OP_F)
                        op = (kind, v, m)
                else:
                    if ct_arr.get((v, s, m), t) <= t - 1:
                        op = (OP_B, v, m)
            row.append(op)
        for s, (kind, v, m) in enumerate(row):
            if kind == IDLE:
                continue
            ptr[s] += 1
            if kind == OP_FH:
                ct_arr[(v, s, m)] = t          # head ct, local
            elif kind == OP_F:
                act_arr[_f_dest(S, V, v, s, m)] = t
            elif kind == OP_B:
                dst = _b_dest(S, V, v, s, m)
                if dst is not None:            # (0,0): dx -> embedding
                    ct_arr[dst] = t
        grid.append(row)
        t += 1
    return grid


def _greedy_schedule(S: int, M: int, variant: str
                     ) -> List[List[Tuple[int, int, int]]]:
    """Greedy list scheduler for V=1 -> grid[t][s] = (kind, 0, m)
    (interleaved V>1 goes through ``_fixed_order_schedule`` instead —
    greedy choice deadlocks against the ring-wrap dependencies there).

    Per tick, per rank, priority order:
      1. B, microbatch FIFO (the critical path);
      2. forced W when the deferred-W backlog hits S (bounds the
         saved-tensor ring at O(S) — the ZB-H1 memory discipline);
      3. F in microbatch order (injected at rank 0, arrival-gated
         elsewhere), capped at S - s in-flight microbatches per rank
         (the classic 1F1B warmup depth — what bounds activation
         memory independent of M);
      4. any W (bubble filler — the entire point of ZB);
      5. idle.
    """
    zb = variant == "zb"
    fdone: Dict[Tuple[int, int, int], int] = {}
    bdone: Dict[Tuple[int, int, int], int] = {}
    wdone: Dict[Tuple[int, int, int], int] = {}
    act_arr: Dict[Tuple[int, int, int], int] = {}
    ct_arr: Dict[Tuple[int, int, int], int] = {}
    total = S * M * (3 if zb else 2)
    done = 0
    grid: List[List[Tuple[int, int, int]]] = []
    limit = 6 * (3 * M + 2 * S) + 64
    t = 0

    def w_backlog(s, t):
        return sorted(
            (bdone[k], k) for k in bdone
            if k[1] == s and k not in wdone and bdone[k] <= t - 1)

    while done < total:
        if t >= limit:
            raise AssertionError(
                f"schedule builder did not converge for S={S} M={M} "
                f"variant={variant!r} after {limit} ticks")
        row: List[Tuple[int, int, int]] = []
        for s in range(S):
            op = (IDLE, 0, 0)
            # -- 1. B -------------------------------------------------
            cand_b = [
                m for m in range(M)
                if (0, s, m) in fdone and (0, s, m) not in bdone
                and ct_arr.get((0, s, m), t) <= t - 1]
            if cand_b:
                op = (OP_B, 0, min(cand_b))
            elif zb and len(w_backlog(s, t)) >= S:
                _, (v, _s, m) = w_backlog(s, t)[0]
                op = (OP_W, v, m)
            if op[0] == IDLE:
                # -- 3. F ---------------------------------------------
                inflight = sum(
                    1 for m in range(M)
                    if (0, s, m) in fdone and (0, s, m) not in bdone)
                if inflight < S - s:
                    m = next((m for m in range(M)
                              if (0, s, m) not in fdone), None)
                    if m is not None and (
                            s == 0
                            or act_arr.get((0, s, m), t) <= t - 1):
                        op = (OP_FH if s == S - 1 else OP_F, 0, m)
            if op[0] == IDLE and zb and w_backlog(s, t):
                # -- 4. W filler --------------------------------------
                _, (v, _s, m) = w_backlog(s, t)[0]
                op = (OP_W, v, m)
            row.append(op)
        # apply the whole tick's decisions, then record arrivals (end
        # of tick t -> usable from t + 1)
        for s, (kind, v, m) in enumerate(row):
            if kind in (OP_F, OP_FH):
                fdone[(v, s, m)] = t
                if kind == OP_FH:
                    ct_arr[(v, s, m)] = t      # head ct, local
                else:
                    act_arr[_f_dest(S, 1, v, s, m)] = t
                done += 1
            elif kind == OP_B:
                bdone[(v, s, m)] = t
                dst = _b_dest(S, 1, v, s, m)
                if dst is not None:            # (0,0): dx -> embedding
                    ct_arr[dst] = t
                done += 1
            elif kind == OP_W:
                wdone[(v, s, m)] = t
                done += 1
        grid.append(row)
        t += 1
    return grid


def _validate(grid, S: int, M: int, V: int, variant: str) -> None:
    """Replay the grid asserting every dependency with 1-tick latency.
    Independent of the greedy builder: a scheduling bug fails HERE, at
    build time, not as silently-wrong gradients."""
    zb = variant == "zb"
    fdone, bdone, wdone, act_arr, ct_arr = {}, {}, {}, {}, {}
    for t, row in enumerate(grid):
        assert len(row) == S
        for s, (kind, v, m) in enumerate(row):
            key = (v, s, m)
            if kind in (OP_F, OP_FH):
                assert key not in fdone, f"double F {key}"
                if v == 0 and s == 0:
                    for mp in range(m):   # injects strictly in order
                        assert (0, 0, mp) in fdone, (t, key)
                else:
                    assert act_arr.get(key, t) <= t - 1, \
                        f"F{key} @t{t}: input not arrived"
                assert (kind == OP_FH) == (v == V - 1 and s == S - 1)
            elif kind == OP_B:
                assert key in fdone and fdone[key] < t, (t, key)
                assert ct_arr.get(key, t) <= t - 1, \
                    f"B{key} @t{t}: cotangent not arrived"
                assert key not in bdone
            elif kind == OP_W:
                assert zb and key in bdone and bdone[key] < t, (t, key)
                assert key not in wdone
            else:
                assert kind == IDLE
            # arrivals (same bookkeeping as the builder)
            if kind in (OP_F, OP_FH):
                fdone[key] = t
                if kind == OP_FH:
                    ct_arr[key] = t
                elif s == S - 1:
                    act_arr[(v + 1, 0, m)] = t
                else:
                    act_arr[(v, s + 1, m)] = t
            elif kind == OP_B:
                bdone[key] = t
                if s == 0 and v > 0:
                    ct_arr[(v - 1, S - 1, m)] = t
                elif s > 0:
                    ct_arr[(v, s - 1, m)] = t
            elif kind == OP_W:
                wdone[key] = t
    want = {(v, s, m) for v in range(V) for s in range(S)
            for m in range(M)}
    assert set(fdone) == want, "missing forwards"
    assert set(bdone) == want, "missing backwards"
    if zb:
        assert set(wdone) == want, "missing deferred weight grads"


def _alloc_slots(intervals: List[Tuple[int, int, Any]]
                 ) -> Tuple[Dict[Any, int], int]:
    """Greedy interval-graph coloring: values -> ring slots. A slot
    whose value was last READ at tick e is reusable by a value STORED
    at the end of tick e or later (stores happen end-of-tick, reads
    during the following ticks). Returns (value -> slot, depth)."""
    slots_free_at: List[int] = []
    assign: Dict[Any, int] = {}
    for store, last_read, key in sorted(intervals):
        for i, free_at in enumerate(slots_free_at):
            if free_at <= store:
                assign[key] = i
                slots_free_at[i] = last_read
                break
        else:
            assign[key] = len(slots_free_at)
            slots_free_at.append(last_read)
    return assign, len(slots_free_at)


@lru_cache(maxsize=None)
def build_schedule(num_stages: int, num_microbatches: int,
                   virtual_chunks: int = 1,
                   variant: str = "1f1b") -> Schedule:
    """Build + validate + register-allocate one schedule (cached)."""
    S, M, V = int(num_stages), int(num_microbatches), int(virtual_chunks)
    if variant not in VARIANTS:
        raise ValueError(f"variant must be one of {VARIANTS}, "
                         f"got {variant!r}")
    # legality lives in ONE queryable table (schedule_legality /
    # SCHEDULE_INFO) shared with the planner's search-space pruning;
    # the builder raises exactly its reasons
    reason = schedule_legality(
        _VARIANT_TO_SCHEDULE[variant], num_stages=S,
        num_microbatches=M, virtual_chunks=V)
    if reason is not None:
        raise ValueError(reason)
    zb = variant == "zb"
    if V > 1:
        grid = _fixed_order_schedule(S, M, V)
    else:
        grid = _greedy_schedule(S, M, variant)
    _validate(grid, S, M, V, variant)
    T = len(grid)

    # -- op-time lookup ----------------------------------------------
    ftick, btick, wtick = {}, {}, {}
    for t, row in enumerate(grid):
        for s, (kind, v, m) in enumerate(row):
            if kind in (OP_F, OP_FH):
                ftick[(v, s, m)] = t
            elif kind == OP_B:
                btick[(v, s, m)] = t
            elif kind == OP_W:
                wtick[(v, s, m)] = t

    # -- saved-value intervals per rank ------------------------------
    # ACT(v,s,m): stage input. Stored at arrival (end of the sender's F
    # tick) or, for stage-0 chunk-0 injects, during its own F tick;
    # read by F (non-inject) and B (W consumes the residual ring, not
    # the input — it never replays the stage forward).
    # CT(v,s,m): incoming cotangent. Stored at arrival / the FH tick;
    # read by B and (zb) W.
    # RES(v,s,m) (zb): B's pullback residual leaves. Stored during the
    # B tick, read once by W — the interval that prices the W-replay
    # cut's memory.
    x_assign: Dict[int, Dict[Tuple[int, int], int]] = {}
    c_assign: Dict[int, Dict[Tuple[int, int], int]] = {}
    r_assign: Dict[int, Dict[Tuple[int, int], int]] = {}
    depth_x = depth_c = 1
    depth_r = 0
    for s in range(S):
        xiv, civ, riv = [], [], []
        for v in range(V):
            for m in range(M):
                f_t = ftick[(v, s, m)]
                last = wtick[(v, s, m)] if zb else btick[(v, s, m)]
                if v == 0 and s == 0:
                    store = f_t
                else:
                    if s == 0:
                        store = ftick[(v - 1, S - 1, m)]
                    else:
                        store = ftick[(v, s - 1, m)]
                xiv.append((store, btick[(v, s, m)], (v, m)))
                if v == V - 1 and s == S - 1:
                    c_store = f_t  # head ct, written during FH
                else:
                    if s == S - 1:
                        c_store = btick[(v + 1, 0, m)]
                    else:
                        c_store = btick[(v, s + 1, m)]
                civ.append((c_store, last, (v, m)))
                if zb:
                    riv.append((btick[(v, s, m)], wtick[(v, s, m)],
                                (v, m)))
        xa, dx = _alloc_slots(xiv)
        ca, dc = _alloc_slots(civ)
        x_assign[s], c_assign[s] = xa, ca
        depth_x, depth_c = max(depth_x, dx), max(depth_c, dc)
        if zb:
            ra, dr = _alloc_slots(riv)
            r_assign[s] = ra
            depth_r = max(depth_r, dr)

    # -- tables ------------------------------------------------------
    kind = np.zeros((T, S), np.int32)
    chunk = np.zeros((T, S), np.int32)
    mb = np.zeros((T, S), np.int32)
    slot_x = np.zeros((T, S), np.int32)
    slot_c = np.zeros((T, S), np.int32)
    slot_r = np.zeros((T, S), np.int32)
    inject = np.zeros((T, S), np.int32)
    emit = np.zeros((T, S), np.int32)
    store_up = np.full((T, S), -1, np.int32)
    store_dn = np.full((T, S), -1, np.int32)
    for t, row in enumerate(grid):
        for s, (k, v, m) in enumerate(row):
            kind[t, s], chunk[t, s], mb[t, s] = k, v, m
            if k == IDLE:
                continue
            slot_x[t, s] = x_assign[s][(v, m)]
            if k in (OP_B, OP_W) or (k == OP_FH):
                slot_c[t, s] = c_assign[s][(v, m)]
            if zb and k in (OP_B, OP_W):
                slot_r[t, s] = r_assign[s][(v, m)]
            if k in (OP_F, OP_FH) and v == 0 and s == 0:
                inject[t, s] = 1
            if k == OP_B and v == 0 and s == 0:
                emit[t, s] = 1
        # arrival routing (the same _f_dest/_b_dest the builders
        # scheduled with): rank r receives the up value from rank
        # (r-1)%S and the down value from rank (r+1)%S, end of tick t
        for r in range(S):
            k, v, m = row[(r - 1) % S]
            if k == OP_F:  # FH is consumed locally by the head
                tgt = _f_dest(S, V, v, (r - 1) % S, m)
                assert tgt is not None and tgt[1] == r
                store_up[t, r] = x_assign[r][(tgt[0], tgt[2])]
            k, v, m = row[(r + 1) % S]
            if k == OP_B:
                tgt = _b_dest(S, V, v, (r + 1) % S, m)
                if tgt is not None:  # None: (0,0) dx -> embedding
                    assert tgt[1] == r
                    store_dn[t, r] = c_assign[r][(tgt[0], tgt[2])]
    return Schedule(
        num_stages=S, num_microbatches=M, virtual_chunks=V,
        variant=variant, ticks=T, depth_x=depth_x, depth_c=depth_c,
        depth_r=depth_r, kind=kind, chunk=chunk, mb=mb, slot_x=slot_x,
        slot_c=slot_c, slot_r=slot_r, inject=inject, emit=emit,
        store_up=store_up, store_dn=store_dn)


# ---------------------------------------------------------------------------
# traced executor
# ---------------------------------------------------------------------------

def _spec_names(spec) -> set:
    """Flat set of mesh-axis names a PartitionSpec mentions."""
    out = set()
    for entry in tuple(spec) if spec is not None else ():
        if entry is None:
            continue
        for ax in (entry if isinstance(entry, (tuple, list))
                   else (entry,)):
            out.add(ax)
    return out


def pipeline_train_async(
    stage_fn: Callable[[Any, Any], Any],
    head_fn: Callable[[Any, Any, Any], Any],
    stage_params: Any,
    head_params: Any,
    x: Any,
    aux: Any,
    *,
    num_stages: int,
    virtual_chunks: int = 1,
    variant: str = "1f1b",
    mesh: Any,
    stage_specs: Any = None,
    head_specs: Any = None,
    x_spec: Any = None,
    aux_specs: Any = None,
    _schedule: Optional[Schedule] = None,
    _drop_dp_grad_psum: bool = False,
):
    """One fused forward+backward pass under a rank-asymmetric schedule.

    Same contract as ``pipeline_1f1b.pipeline_train_1f1b`` (and the
    same return tuple ``(loss, grads_stage, grads_head, dx)``), but the
    schedule is per-rank: the scan body ``lax.switch``-es on the op
    table column selected by ``lax.axis_index("pp")`` inside a
    ``shard_map``, so warmup/steady/drain differ per rank and idle
    ticks execute a trivial branch instead of a masked full fwd+bwd.

    ``stage_params`` leaves are ``[V*S, ...]`` chunk-major (``v*S+s``,
    the ``split_chunks_round_robin`` layout); ``x`` is ``[M, mb, ...]``
    stage-0 microbatch inputs; ``aux`` leaves ``[M, ...]``. Grads are
    accumulated in f32 in per-stage microbatch order — the SAME order
    as the lockstep schedule, so loss and grads match it (pinned by
    tests/test_pipeline_async.py).

    Mesh composition (r19): the shard_map spans the FULL mesh, not a
    pp-only one. ``dp`` shards the microbatch rows — ``x_spec`` /
    ``aux_specs`` must name it when dp > 1 (each dp rank then runs the
    schedule on its row shard; the gradient psum over dp is folded
    into the f32 accumulation carry ONCE per accumulator leaf after
    the scan, and loss/ghead are psum'd over dp×pp). ``tp`` shards the
    stage weights per ``stage_specs`` (per-leaf PartitionSpecs over
    the dims AFTER the leading ``V*S`` chunk axis) and the head per
    ``head_specs`` — the stage/head callables are then responsible for
    their own in-body tp collectives (``parallel.mp_ops`` f/g custom
    ops; see models/llama.py ``_tp_local_block``) and must return
    tp-COMPLETE cotangents and gradients (replicated leaves complete
    on every tp rank, sharded leaves shard-local), which the megatron
    f-op placement guarantees. All spec arguments default to the
    pp-only behavior (everything else replicated).

    zb's W ticks consume RING-SAVED residuals: B runs the one
    forward+input-grad backward of its ``jax.vjp`` and stores the
    pullback's own leaves into the residual ring (``slot_r``,
    interval-colored, M-independent depth); W restores the pullback
    and computes weight grads with no second forward replay (~4.5
    work units per microbatch-stage vs the r14 replay's 5 — the
    unused co-outputs of each pullback call are dead code XLA
    eliminates per switch branch).

    ``_schedule`` overrides the built schedule and
    ``_drop_dp_grad_psum`` drops the folded dp gradient psum (tests
    use both to prove mutations trip the analysis passes); everyone
    else leaves them alone.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    S, V = int(num_stages), int(virtual_chunks)
    M = x.shape[0]
    if mesh is None or "pp" not in getattr(mesh, "shape", {}):
        raise ValueError("pipeline_train_async needs a mesh with a "
                         "'pp' axis (it is a shard_map program)")
    if mesh.shape["pp"] != S:
        raise ValueError(f"mesh pp axis is {mesh.shape['pp']} but "
                         f"num_stages={S}")
    dp_deg = int(mesh.shape.get("dp", 1))
    busy = {k: int(n) for k, n in mesh.shape.items()
            if k not in ("dp", "tp", "pp") and int(n) > 1}
    if busy:
        raise NotImplementedError(
            f"rank-asymmetric schedules compose dp/tp/pp only; mesh "
            f"axes {busy} must be size 1 (cp/ep inside the per-rank "
            f"op-table scan is future work)")
    if dp_deg > 1:
        aux_leaves = jax.tree_util.tree_leaves(
            aux_specs, is_leaf=lambda v: isinstance(v, P))
        if ("dp" not in _spec_names(x_spec)
                or not aux_leaves
                or not all("dp" in _spec_names(sp)
                           for sp in aux_leaves)):
            raise ValueError(
                "dp > 1 needs x_spec AND aux_specs sharding the "
                "microbatch rows over 'dp' — with replicated inputs "
                "the folded dp gradient psum would over-count by the "
                "dp degree (and global-shaped labels would silently "
                "broadcast against local rows in the head)")
    sched = _schedule if _schedule is not None else build_schedule(
        S, M, V, variant)
    zb = sched.variant == "zb"

    chunks_vs = jax.tree_util.tree_map(
        lambda p: p.reshape((V, S) + p.shape[1:]), stage_params)
    rows_np = dict(
        kind=sched.kind, chunk=sched.chunk, mb=sched.mb,
        slot_x=sched.slot_x, slot_c=sched.slot_c, slot_r=sched.slot_r,
        inject=sched.inject, emit=sched.emit,
        store_up=sched.store_up, store_dn=sched.store_dn)

    is_p = lambda v: isinstance(v, P)
    if stage_specs is None:
        chunk_in_specs: Any = P(None, "pp")
    else:
        chunk_in_specs = jax.tree_util.tree_map(
            lambda sp: P(None, "pp", *tuple(sp)), stage_specs,
            is_leaf=is_p)
    head_in_specs = P() if head_specs is None else head_specs
    x_in_spec = P() if x_spec is None else x_spec
    aux_in_specs = P() if aux_specs is None else aux_specs
    dx_out_spec = P("pp", *tuple(x_in_spec))

    def body(chunks, x_all, aux_all, hp):
        r = lax.axis_index("pp")
        chunks_loc = jax.tree_util.tree_map(
            lambda c: c.reshape((V,) + c.shape[2:]), chunks)
        mb_shape = x_all.shape[1:]
        dt = x_all.dtype
        zero_mb = jnp.zeros(mb_shape, dt)
        rows_all = {k: jnp.asarray(v) for k, v in rows_np.items()}

        # zb residual rings: the pullback of ONE stage vjp is a pytree
        # whose leaves are exactly the residuals W needs — get their
        # avals + treedef abstractly (zero equations traced) so the
        # rings can live in the scan carry and W can rebuild the
        # pullback from a ring slot instead of replaying the forward
        if zb:
            p_abs = jax.tree_util.tree_map(
                lambda c: jax.ShapeDtypeStruct(c.shape[1:], c.dtype),
                chunks_loc)
            pull_abs = jax.eval_shape(
                lambda pp_, xx: jax.vjp(stage_fn, pp_, xx)[1],
                p_abs, jax.ShapeDtypeStruct(mb_shape, dt))
            res_abs, res_tree = jax.tree_util.tree_flatten(pull_abs)
            depth_r = max(int(sched.depth_r), 1)
            sr0 = [jnp.zeros((depth_r,) + l.shape, l.dtype)
                   for l in res_abs]
        else:
            res_tree, sr0 = None, []

        def pick(tree, v):
            return jax.tree_util.tree_map(
                lambda a: lax.dynamic_index_in_dim(a, v, 0,
                                                   keepdims=False), tree)

        def store_if(buf, val, slot):
            idx = jnp.clip(slot, 0, buf.shape[0] - 1)
            cur = lax.dynamic_index_in_dim(buf, idx, 0, keepdims=False)
            return lax.dynamic_update_index_in_dim(
                buf, jnp.where(slot >= 0, val, cur), idx, 0)

        def tick(carry, row):
            sx, sc, sr, gacc, ghead, loss, dxbuf = carry
            kind = row["kind"][r]
            v = row["chunk"][r]
            m = jnp.clip(row["mb"][r], 0, M - 1)
            sl_x = row["slot_x"][r]
            sl_c = row["slot_c"][r]
            sl_r = row["slot_r"][r]
            inject = row["inject"][r]
            emit = row["emit"][r]
            p_v = pick(chunks_loc, v)
            x_m = lax.dynamic_index_in_dim(x_all, m, 0, keepdims=False)
            aux_m = jax.tree_util.tree_map(
                lambda a: lax.dynamic_index_in_dim(a, m, 0,
                                                   keepdims=False),
                aux_all)
            x_sl = lax.dynamic_index_in_dim(sx, sl_x, 0, keepdims=False)
            ct_sl = lax.dynamic_index_in_dim(sc, sl_c, 0, keepdims=False)
            x_in = jnp.where(inject == 1, x_m, x_sl)

            def _idle():
                return (sx, sc, sr, zero_mb, zero_mb, gacc, ghead,
                        loss, dxbuf)

            def _f():
                sx2 = lax.dynamic_update_index_in_dim(sx, x_in, sl_x, 0)
                y = stage_fn(p_v, x_in).astype(dt)
                return (sx2, sc, sr, y, zero_mb, gacc, ghead, loss,
                        dxbuf)

            def _b():
                # ONE forward inside the vjp either way; zb ring-saves
                # the pullback's residual leaves so W never replays it
                # (the dp co-output is dead here and DCE'd by XLA)
                _, pull = jax.vjp(stage_fn, p_v, x_in)
                dp, dx = pull(ct_sl)
                if zb:
                    leaves = jax.tree_util.tree_leaves(pull)
                    assert len(leaves) == len(sr), (
                        f"pullback residual structure changed between "
                        f"eval_shape ({len(sr)} leaves) and the B "
                        f"trace ({len(leaves)})")
                    sr2 = [lax.dynamic_update_index_in_dim(rb, l,
                                                           sl_r, 0)
                           for rb, l in zip(sr, leaves)]
                    gacc2 = gacc
                else:
                    sr2 = sr
                    gacc2 = jax.tree_util.tree_map(
                        lambda g, d: g.at[v].add(d.astype(jnp.float32)),
                        gacc, dp)
                dx = dx.astype(dt)
                old = lax.dynamic_index_in_dim(dxbuf, m, 0,
                                               keepdims=False)
                dxbuf2 = lax.dynamic_update_index_in_dim(
                    dxbuf, jnp.where(emit == 1, dx, old), m, 0)
                return (sx, sc, sr2, zero_mb, dx, gacc2, ghead, loss,
                        dxbuf2)

            def _fh():
                sx2 = lax.dynamic_update_index_in_dim(sx, x_in, sl_x, 0)
                y = stage_fn(p_v, x_in).astype(dt)
                loss_m, pull = jax.vjp(
                    lambda hpp, yy: head_fn(hpp, yy, aux_m), hp, y)
                dhead, dout = pull(jnp.ones((), loss_m.dtype))
                sc2 = lax.dynamic_update_index_in_dim(
                    sc, dout.astype(dt), sl_c, 0)
                ghead2 = jax.tree_util.tree_map(
                    lambda g, d: g + d.astype(jnp.float32), ghead, dhead)
                return (sx2, sc2, sr, zero_mb, zero_mb, gacc, ghead2,
                        loss + loss_m.astype(jnp.float32), dxbuf)

            def _w():
                # restore B's pullback from the residual ring: weight
                # grads with NO stage-forward replay (the dx co-output
                # is dead here and DCE'd by XLA)
                leaves = [lax.dynamic_index_in_dim(rb, sl_r, 0,
                                                   keepdims=False)
                          for rb in sr]
                pull = jax.tree_util.tree_unflatten(res_tree, leaves)
                dp, _dx = pull(ct_sl)
                gacc2 = jax.tree_util.tree_map(
                    lambda g, d: g.at[v].add(d.astype(jnp.float32)),
                    gacc, dp)
                return (sx, sc, sr, zero_mb, zero_mb, gacc2, ghead,
                        loss, dxbuf)

            branches = [_idle, _f, _b, _fh] + ([_w] if zb else [])
            (sx, sc, sr, up, dn, gacc, ghead, loss, dxbuf) = lax.switch(
                kind, branches)

            # unconditional neighbour exchange: identical collective
            # signature on every rank, every tick
            up_in = lax.ppermute(
                up, "pp", [(i, (i + 1) % S) for i in range(S)])
            dn_in = lax.ppermute(
                dn, "pp", [(i, (i - 1) % S) for i in range(S)])
            sx = store_if(sx, up_in, row["store_up"][r])
            sc = store_if(sc, dn_in, row["store_dn"][r])
            return (sx, sc, sr, gacc, ghead, loss, dxbuf), None

        carry0 = (
            jnp.zeros((sched.depth_x,) + mb_shape, dt),
            jnp.zeros((sched.depth_c,) + mb_shape, dt),
            sr0,
            jax.tree_util.tree_map(
                lambda c: jnp.zeros(c.shape, jnp.float32), chunks_loc),
            jax.tree_util.tree_map(
                lambda h: jnp.zeros(h.shape, jnp.float32), hp),
            jnp.zeros((), jnp.float32),
            jnp.zeros((M,) + mb_shape, dt),
        )
        (sx, sc, sr, gacc, ghead, loss, dxbuf), _ = lax.scan(
            tick, carry0, rows_all)
        # dp composition: each dp rank accumulated grads for ITS row
        # shard of every microbatch — fold the dp reduction into the
        # f32 accumulators, ONE psum per accumulator leaf (not per
        # microbatch); loss/ghead reduce over dp x pp (pp because only
        # the last rank's head ops are nonzero, as before)
        red_axes = ("pp", "dp") if dp_deg > 1 else ("pp",)
        if dp_deg > 1 and not _drop_dp_grad_psum:
            gacc = jax.tree_util.tree_map(
                lambda g: lax.psum(g, "dp"), gacc)
        loss = lax.psum(loss, red_axes)
        ghead = jax.tree_util.tree_map(
            lambda g: lax.psum(g, red_axes), ghead)
        gacc_out = jax.tree_util.tree_map(
            lambda g: g.reshape((V, 1) + g.shape[1:]), gacc)
        return loss, gacc_out, ghead, dxbuf[None]

    if stage_specs is None:
        gacc_out_specs: Any = P(None, "pp")
    else:
        gacc_out_specs = chunk_in_specs
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(chunk_in_specs, x_in_spec, aux_in_specs,
                  head_in_specs),
        out_specs=(P(), gacc_out_specs, head_in_specs, dx_out_spec),
        check_vma=False)
    loss, gchunks, ghead, dxs = fn(chunks_vs, x, aux, head_params)
    # mean over the M microbatches AND the dp row shards: each dp rank
    # computed per-microbatch means over its rows/dp rows, so the
    # dp-psum'd sums divide by M*dp
    inv_m = 1.0 / (M * dp_deg)
    gchunks = jax.tree_util.tree_map(
        lambda g, p: (g.reshape((V * S,) + g.shape[2:]) * inv_m
                      ).astype(p.dtype),
        gchunks, stage_params)
    ghead = jax.tree_util.tree_map(
        lambda g, p: (g * inv_m).astype(p.dtype), ghead, head_params)
    return loss * inv_m, gchunks, ghead, dxs[0] * inv_m
