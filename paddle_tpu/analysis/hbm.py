"""Static HBM peak estimator: a liveness walk over the traced step.

The question every geometry decision ultimately asks — "does this step
fit?" — is answerable before any compile: the jaxpr is a schedule of
buffer births (equation outputs) and deaths (last uses), so walking it
in order while summing live bytes gives the per-program-point resident
set, and its maximum is the static peak. The model mirrors how XLA's
buffer assignment actually behaves:

* non-donated inputs stay resident for the whole program (argument
  buffers are caller-owned and never freed);
* donated inputs die at their last use (XLA reuses them as outputs —
  the donation audit proves the aliasing is real);
* equation outputs live from their defining equation to their last
  consumer; program outputs live to the end;
* control-flow bodies (scan/while/pjit/remat/custom_vjp) contribute
  their own INTERNAL peak on top of the operands live outside — a
  scan's stacked residuals are its equation outputs, its body
  intermediates are transient inside one trip;
* per-device bytes divide by the declared PartitionSpec's shard factor
  where one is known (program inputs from ``meta['in_specs']``,
  ``with_sharding_constraint`` sites in the graph); unannotated
  intermediates inherit the factor of their largest input — GSPMD may
  shard them further, so the estimate is an upper bound, which is the
  safe direction for a fits-in-HBM question.

Accuracy is pinned by test against the compiled module's own
accounting (``compiled.memory_analysis()`` / ``cost_analysis()``):
within ±10% on the flagship llama train step (f32 on the CPU mesh —
bf16 graphs compiled ON CPU get f32-normalized buffers XLA itself
inflates ~2x, a backend artifact, not an estimator one; see
docs/ANALYSIS.md for the measured table).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from ..core.graph_trace import sub_jaxprs as _sub_jaxprs
from .framework import (GraphTarget, LintPass, Severity,
                        aval_nbytes as _nbytes, register_pass)
from .sharding_lint import spec_shard_factor

__all__ = ["HbmEstimate", "estimate_hbm_peak", "HbmPeakPass",
           "xla_cost_analysis", "xla_peak_bytes"]


@dataclass
class HbmEstimate:
    """Per-device peak estimate + the live set at the peak instant."""
    peak_bytes: int
    #: (bytes, label) largest-first at the peak program point
    top: List[Tuple[int, str]] = field(default_factory=list)
    args_bytes: int = 0          # resident non-donated + donated inputs
    graph: str = ""

    def __str__(self) -> str:
        lines = [f"{self.graph}: est. peak {self.peak_bytes / 2**20:.2f}"
                 f" MiB/device (inputs {self.args_bytes / 2**20:.2f}"
                 f" MiB)"]
        for b, label in self.top:
            lines.append(f"  {b / 2**20:8.2f} MiB  {label}")
        return "\n".join(lines)


def _internal_peak(jaxpr) -> int:
    """Peak bytes of values CREATED inside ``jaxpr`` (its invars alias
    buffers that the caller already accounts for)."""
    from jax.extend import core as jax_core
    last: Dict[Any, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for a in eqn.invars:
            if not isinstance(a, jax_core.Literal):
                last[a] = i
    outset = {o for o in jaxpr.outvars
              if not isinstance(o, jax_core.Literal)}
    n_eqns = len(jaxpr.eqns)
    for o in outset:
        last[o] = n_eqns
    live = peak = 0
    created: Dict[Any, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        out_b = sum(_nbytes(o.aval) for o in eqn.outvars)
        sub_pk = max([_internal_peak(sj) for _, sj in _sub_jaxprs(eqn)]
                     + [0])
        peak = max(peak, live + out_b + sub_pk)
        for o in eqn.outvars:
            created[o] = _nbytes(o.aval)
        live += out_b
        for v in list(created):
            if last.get(v, -1) <= i and v not in outset:
                live -= created.pop(v)
    return peak


def estimate_hbm_peak(target: GraphTarget, top_k: int = 8
                      ) -> HbmEstimate:
    """Liveness-walk ``target.jaxpr`` and return the per-device peak
    estimate with its top-k live contributors."""
    from jax.extend import core as jax_core
    closed = target.jaxpr
    jaxpr = closed.jaxpr
    # make_jaxpr over a jitted fn wraps everything in one pjit: inline
    # through single-equation wrappers whose arity matches
    while (len(jaxpr.eqns) == 1 and _sub_jaxprs(jaxpr.eqns[0])
           and len(_sub_jaxprs(jaxpr.eqns[0])[0][1].invars)
           == len(jaxpr.invars)):
        jaxpr = _sub_jaxprs(jaxpr.eqns[0])[0][1]

    mesh_axes = dict(target.meta.get("mesh_axes", {}))
    specs = target.meta.get("in_specs")
    labels = target.meta.get("invar_labels",
                             [f"arg{i}" for i in range(len(jaxpr.invars))])
    donated = target.meta.get("donated_invars",
                              [False] * len(jaxpr.invars))

    factor: Dict[Any, int] = {}
    bytes_of: Dict[Any, int] = {}
    label_of: Dict[Any, str] = {}

    for i, v in enumerate(jaxpr.invars):
        f = (spec_shard_factor(specs[i], mesh_axes)
             if specs is not None and i < len(specs) else 1)
        factor[v] = max(f, 1)
        bytes_of[v] = _nbytes(v.aval) // factor[v]
        label_of[v] = labels[i] if i < len(labels) else f"arg{i}"
    for v in jaxpr.constvars:
        factor[v] = 1
        bytes_of[v] = _nbytes(v.aval)
        label_of[v] = "const"

    last: Dict[Any, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for a in eqn.invars:
            if not isinstance(a, jax_core.Literal):
                last[a] = i
    outset = {o for o in jaxpr.outvars
              if not isinstance(o, jax_core.Literal)}
    n_eqns = len(jaxpr.eqns)
    for o in outset:
        last[o] = n_eqns

    args_bytes = sum(bytes_of[v] for v in jaxpr.invars)
    live: Dict[Any, int] = {v: bytes_of[v]
                            for v in (*jaxpr.invars, *jaxpr.constvars)}
    live_total = sum(live.values())
    peak, peak_live, peak_extra = live_total, dict(live), 0
    don = {v for v, d in zip(jaxpr.invars, donated) if d}

    for i, eqn in enumerate(jaxpr.eqns):
        prim = eqn.primitive.name
        # propagate shard factors: constraint sites are exact, other
        # outputs inherit the largest input's factor (upper bound)
        if prim == "sharding_constraint":
            sh = eqn.params.get("sharding")
            f_out = (spec_shard_factor(sh.spec, mesh_axes)
                     if getattr(sh, "spec", None) is not None else 1)
        else:
            in_fs = [factor.get(a, 1) for a in eqn.invars
                     if not isinstance(a, jax_core.Literal)]
            big = max(((_nbytes(a.aval), factor.get(a, 1))
                       for a in eqn.invars
                       if not isinstance(a, jax_core.Literal)),
                      default=(0, 1))
            f_out = big[1] if big[0] else (min(in_fs) if in_fs else 1)
        out_b = 0
        for o in eqn.outvars:
            factor[o] = max(f_out, 1)
            bytes_of[o] = _nbytes(o.aval) // factor[o]
            label_of[o] = f"{prim} -> {getattr(o, 'aval', '?')}"
            out_b += bytes_of[o]
        sub_pk = max([_internal_peak(sj) for _, sj in _sub_jaxprs(eqn)]
                     + [0]) // max(f_out, 1)
        if live_total + out_b + sub_pk > peak:
            peak = live_total + out_b + sub_pk
            peak_live = dict(live)
            for o in eqn.outvars:
                peak_live[o] = bytes_of[o]
            peak_extra = sub_pk
        for o in eqn.outvars:
            live[o] = bytes_of[o]
            live_total += bytes_of[o]
        for v in list(live):
            if last.get(v, -1) > i or v in outset:
                continue
            if v in jaxpr.invars and v not in don:
                continue  # caller-owned buffer: resident to the end
            live_total -= live.pop(v)

    top = sorted(((b, label_of.get(v, "?")) for v, b in
                  peak_live.items()), key=lambda t: -t[0])[:top_k]
    if peak_extra:
        top = [(peak_extra, "loop-body transient peak")] + top
        top = top[:top_k]
    return HbmEstimate(peak_bytes=peak, top=top, args_bytes=args_bytes,
                       graph=target.name)


@register_pass
class HbmPeakPass(LintPass):
    """Report the per-device static peak for every target that declares
    input specs, and fail targets that declare a byte budget
    (``meta['hbm_budget_bytes']``) the estimate exceeds. The estimate
    is also collected on the pass instance (``self.reports``) so the
    CLI can emit the full table in ``--json``."""

    name = "hbm-peak"

    def __init__(self, top_k: int = 6):
        self.top_k = int(top_k)
        self.reports: Dict[str, HbmEstimate] = {}

    def run(self, target: GraphTarget):
        if target.meta.get("in_specs") is None:
            return []
        est = estimate_hbm_peak(target, top_k=self.top_k)
        self.reports[target.name] = est
        findings = [self.finding(
            target,
            f"estimated per-device peak {est.peak_bytes / 2**20:.2f} "
            f"MiB (top: "
            + "; ".join(f"{b / 2**20:.2f} MiB {lbl}"
                        for b, lbl in est.top[:3]) + ")",
            severity=Severity.INFO)]
        budget = target.meta.get("hbm_budget_bytes")
        if budget is not None and est.peak_bytes > int(budget):
            findings.append(self.finding(
                target,
                f"estimated peak {est.peak_bytes / 2**20:.2f} MiB "
                f"exceeds the declared per-device budget "
                f"{int(budget) / 2**20:.2f} MiB — the step does not "
                f"fit the geometry it claims to run on"))
        return findings


def xla_cost_analysis(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` normalized across jax versions: the
    current one returns a LIST with one properties-dict per partition,
    older ones return the dict directly. Always returns a (possibly
    empty) plain dict for the addressable partition, so callers can
    ``.get("flops")`` without version branches — the one shared helper
    for every cost_analysis consumer (this module's accuracy pin,
    tools/resnet_bench.py, tools/decode_profile.py, the 1F1B
    schedule-efficiency test)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca) if ca else {}


def xla_peak_bytes(compiled) -> Optional[int]:
    """XLA's own per-device peak for a compiled step: argument buffers
    + temp heap + non-aliased outputs (``memory_analysis()``, the same
    introspection family as ``cost_analysis()``). None when the backend
    does not expose it."""
    try:
        ma = compiled.memory_analysis()
        return int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                   + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    except Exception:
        return None
