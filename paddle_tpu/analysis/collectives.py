"""Collective-consistency pass: pipeline stages must issue identical
collective sequences.

Generalizes ``Engine._verify_pp_forward_order`` (the ADVICE r5 guard):
that check proves the pp stage list matches the model's forward
*dataflow*; this one proves the stage *programs* agree on the one
thing that deadlocks or silently corrupts a pipeline — the ordered
sequence of collectives each stage issues. Two stages that disagree
(one psum where another ppermutes, different axes, different scan trip
counts around a collective) hang the mesh at best; at worst a
reordered pair of reductions completes with transposed data.

The signature of a program is the depth-first ordered list of its
collective equations with their semantics-bearing params (axis names,
permutation, tiling), each tagged with the loop structure that repeats
it (a ppermute inside a length-8 scan is eight issues, not one — two
stages with different trip counts are NOT consistent). Everything
shape-local is deliberately excluded: stages hold different weight
chunks and may differ freely in local math.

Use :func:`collective_signature` directly, or the pass over a group of
:class:`GraphTarget`\\ s that carry ``meta['stage_group']`` — targets
in one group must agree pairwise.
"""
from __future__ import annotations

from typing import Any, List, Sequence, Tuple

from .framework import (Finding, GraphTarget, LintPass, Severity,
                        register_pass)

__all__ = ["COLLECTIVE_PRIMS", "collective_signature",
           "CollectiveConsistencyPass", "check_stage_consistency",
           "collective_cost_bytes", "scan_trip_counts"]

# ``psum_invariant``/``all_gather_invariant`` are what ``lax.psum``/
# ``lax.all_gather`` bind inside a ``check_vma=True`` shard_map body
COLLECTIVE_PRIMS = {
    "psum", "psum2", "psum_invariant", "pmax", "pmin", "pmean",
    "ppermute", "pbroadcast", "all_gather", "all_gather_invariant",
    "all_to_all", "reduce_scatter", "psum_scatter", "pgather",
    "pshuffle",
}

# eqn params that carry collective SEMANTICS (vs. local tiling detail)
_SIG_PARAMS = ("axes", "axis_name", "axis_index_groups", "perm",
               "all_gather_dimension", "scatter_dimension",
               "split_axis", "concat_axis", "tiled")


def _freeze(v: Any):
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    return v


def collective_signature(jaxpr, include_loops: bool = False
                         ) -> List[Tuple]:
    """Ordered (prim, loop_nest, params) for every collective in the
    program, depth-first — the stage's communication contract.
    ``loop_nest`` records the loop frames that repeat the collective,
    with scan trip counts: a ppermute inside a length-8 scan is eight
    issues, and a stage scanning 4 layers differs from one scanning 8
    even when the body matches.

    ``include_loops=True`` additionally records every loop frame itself
    as a ``("__loop__", nest, (("length", n),))`` entry — the mode the
    TRAINING stage check runs in: pipeline stage chunks under GSPMD
    carry no explicit collectives (XLA inserts them at compile), but
    their layer-scan trip counts ARE the per-stage work contract, and a
    chunk scanning a different layer count desynchronizes the lockstep
    schedule exactly like a diverging collective would."""
    from ..core.graph_trace import sub_jaxprs
    from jax.extend import core as jax_core

    sig: List[Tuple] = []

    def walk(j, loops: Tuple):
        if isinstance(j, jax_core.ClosedJaxpr):
            j = j.jaxpr
        for eqn in j.eqns:
            name = eqn.primitive.name
            if name in COLLECTIVE_PRIMS:
                params = tuple(
                    (k, _freeze(eqn.params[k])) for k in _SIG_PARAMS
                    if k in eqn.params)
                sig.append((name, loops, params))
            for label, sub in sub_jaxprs(eqn):
                if name in ("scan", "while", "fori_loop"):
                    frame = (name, eqn.params.get("length"))
                    if include_loops:
                        sig.append(("__loop__", loops,
                                    (("length",
                                      eqn.params.get("length")),)))
                    walk(sub, loops + (frame,))
                else:
                    walk(sub, loops)
        return sig

    return walk(jaxpr, ())


#: wire-traffic weight per collective primitive: how many times the
#: payload crosses a link relative to its size (ring all-reduce moves
#: ~2(n-1)/n ≈ 2 payloads, a permute moves 1, gather/scatter families
#: ~1). Deliberately topology-free — the planner's comms term is a
#: RANKING proxy, not a wall-clock model.
_COLLECTIVE_WIRE_FACTOR = {
    "psum": 2.0, "psum2": 2.0, "psum_invariant": 2.0, "pmax": 2.0,
    "pmin": 2.0, "pmean": 2.0, "ppermute": 1.0, "pbroadcast": 1.0,
    "all_gather": 1.0, "all_gather_invariant": 1.0,
    "all_to_all": 1.0, "reduce_scatter": 1.0, "psum_scatter": 1.0,
    "pgather": 1.0, "pshuffle": 1.0,
}


def collective_cost_bytes(jaxpr) -> int:
    """Wire bytes the program's EXPLICIT collectives move, scan trip
    counts included: each collective contributes (output bytes) x
    (enclosing scan trips) x (per-prim wire factor). This prices what
    the trace can see — shard_map programs (the async pipeline
    schedules' per-tick ppermute pair) and manual psums; collectives
    GSPMD inserts at compile time are invisible here and the planner
    adds them analytically from the declared specs. A ``while`` body
    has no static trip count, so its collectives count once (a lower
    bound, stated rather than guessed). One number per graph so the
    planner's comms term and a test can pin it."""
    from ..core.graph_trace import sub_jaxprs
    from jax.extend import core as jax_core
    from .framework import aval_nbytes

    total = 0.0

    def walk(j, mult: int):
        nonlocal total
        if isinstance(j, jax_core.ClosedJaxpr):
            j = j.jaxpr
        for eqn in j.eqns:
            name = eqn.primitive.name
            if name in COLLECTIVE_PRIMS:
                out_b = sum(aval_nbytes(o.aval) for o in eqn.outvars)
                total += (out_b * mult
                          * _COLLECTIVE_WIRE_FACTOR.get(name, 1.0))
            for _label, sub in sub_jaxprs(eqn):
                trips = (eqn.params.get("length") if name == "scan"
                         else None)
                walk(sub, mult * int(trips) if trips is not None
                     else mult)

    walk(jaxpr, 1)
    return int(total)


def scan_trip_counts(jaxpr) -> List[int]:
    """Every ``lax.scan`` trip count in the program, depth-first."""
    from ..core.graph_trace import iter_jaxpr_eqns
    out = []
    for _path, eqn in iter_jaxpr_eqns(jaxpr):
        if (eqn.primitive.name == "scan"
                and eqn.params.get("length") is not None):
            out.append(int(eqn.params["length"]))
    return out


def check_stage_consistency(
        stages: Sequence[Tuple[str, Any]],
        include_loops: bool = False) -> List[Tuple[str, str]]:
    """Compare collective signatures across ``(name, jaxpr)`` stages.
    Returns [(stage_name, description)] for every stage diverging from
    the first one (the reference stage)."""
    if len(stages) < 2:
        return []
    ref_name, ref_jaxpr = stages[0]
    ref_sig = collective_signature(ref_jaxpr, include_loops)
    out = []
    for name, jaxpr in stages[1:]:
        sig = collective_signature(jaxpr, include_loops)
        if sig == ref_sig:
            continue
        # locate the first divergence for an actionable message
        i = 0
        while i < min(len(sig), len(ref_sig)) and sig[i] == ref_sig[i]:
            i += 1
        ours = sig[i] if i < len(sig) else "<end>"
        theirs = ref_sig[i] if i < len(ref_sig) else "<end>"
        out.append((name,
                    f"collective #{i} is {ours} but stage "
                    f"'{ref_name}' issues {theirs} "
                    f"({len(sig)} vs {len(ref_sig)} collectives total)"))
    return out


@register_pass
class CollectiveConsistencyPass(LintPass):
    """Group targets by ``meta['stage_group']`` and require identical
    collective signatures inside each group (loop trip counts included
    when any member sets ``meta['signature_include_loops']`` — the
    training stage-chunk mode). Run via :func:`framework.run_passes`
    this fires once per target but keeps state, reporting each group
    exactly once (on its last member).

    Per-target rule: a target carrying ``meta['expected_scan_trips']``
    (the 1F1B train step: ``pipeline_1f1b.schedule_ticks(S, M, V)``)
    must contain a scan with exactly that trip count — the schedule's
    fill + steady + drain tick arithmetic. A schedule edit that changes
    the tick count without updating ``schedule_ticks`` (or vice versa)
    is a lockstep desync and fails here before it ever runs."""

    name = "collective-consistency"

    def __init__(self):
        self._groups = {}

    def run(self, target: GraphTarget) -> List[Finding]:
        findings: List[Finding] = []
        expected = target.meta.get("expected_scan_trips")
        if expected is not None:
            trips = scan_trip_counts(target.jaxpr)
            if int(expected) not in trips:
                findings.append(self.finding(
                    target,
                    f"no scan with the schedule's expected trip count "
                    f"{expected} (traced scan lengths: {sorted(set(trips))})"
                    f" — the 1F1B tick arithmetic and the traced "
                    f"schedule disagree"))

        group = target.meta.get("stage_group")
        if group is None:
            return findings
        members = self._groups.setdefault(group, [])
        members.append((target.name, target.jaxpr,
                        bool(target.meta.get("signature_include_loops"))))
        total = target.meta.get("stage_count")
        if total is None or len(members) < total:
            return findings
        include_loops = any(m[2] for m in members)
        for name, desc in check_stage_consistency(
                [(n, j) for n, j, _ in members], include_loops):
            findings.append(Finding(
                pass_name=self.name, severity=Severity.ERROR,
                graph=name,
                message=f"pipeline stage group '{group}': {desc}"))
        return findings
