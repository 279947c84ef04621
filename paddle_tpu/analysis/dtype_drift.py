"""Dtype-drift lint pass: silent bf16→f32 upcasts in compute.

The silent-wrongness class this hunts: a weight or constant left in
f32 while the model's declared compute dtype is bf16. JAX's type
promotion then silently upcasts the bf16 side and the whole downstream
chain — matmuls included — runs in f32: numerically *different* from
the bf16 program that was benchmarked (and 2x the weight stream the
int8/bf16 decode budgets assume), with no error anywhere. At the jaxpr
level promotion is explicit (``convert_element_type`` equations), so
the drift is statically visible.

Three rules, each anchored to a concrete failure:

* **wide-dot** (error): a ``dot_general``/``conv`` with an f32+
  OPERAND whose value *originates* from the declared narrow dtype
  (reached the dot through casts/elementwise ops). Deliberate f32
  islands — softmax stats, rms-norm accumulation, rope angles — are
  elementwise/reduction math and never trip this; only a GEMM pulled
  up to f32 does. That is exactly the f32-weight-in-bf16-model bug.
  Narrow operands with an f32 ACCUMULATOR
  (``preferred_element_type=f32``) are not drift: that is how the MXU
  multiplies, and the only accumulator the chip's kernel compiler
  accepts — the weight stream and the multiply width are unchanged.
* **const-pollution** (error): a non-scalar f32 constant (a baked-in
  table or weight captured by closure) forcing a bf16 operand's upcast
  in a binary op. Scalar literals (eps, mask values) are exempt — f32
  scalars against bf16 arrays are JAX's weak-type norm.
* **f64-anywhere** (error): any float64 value in the graph. On TPU
  f64 is always an accident (x64 leaks through np arithmetic).

Origin tracking is per-jaxpr and flows through ``convert_element_type``
and elementwise ops: ``origin(v)`` is the set of float dtypes the value
passed through. Sub-jaxprs (scan bodies — the serving hot loops) are
analysed with origins seeded from their invars' own dtypes, which is
where the weights enter; this keeps the analysis linear and local
while still catching every in-loop drift.
"""
from __future__ import annotations

from typing import Dict, List, Set

import numpy as np

from ..core.graph_trace import sub_jaxprs
from .framework import (Finding, GraphTarget, LintPass, Severity,
                        register_pass)

__all__ = ["DtypeDriftPass"]

# GEMM-class primitives: where an upcast changes the compute budget
_DOT_PRIMS = {"dot_general", "conv_general_dilated"}

# primitives that PRODUCE a value of a new dtype by design: their
# output's origin is reset to its own dtype (an f32 iota is a genuine
# f32 source, not drift from some narrow input)
_SOURCE_PRIMS = {"iota", "rng_bit_generator", "random_seed",
                 "random_bits"}


def _is_float(dt) -> bool:
    import jax.numpy as jnp
    try:
        # jnp.issubdtype, not np: the extended float dtypes (bfloat16,
        # f8 variants) register as numpy kind 'V' and np.issubdtype
        # calls them non-floating
        # issubdtype is a metadata predicate (already a Python bool) —
        # no bool() wrapper, which source_lint PT003 would read as a
        # device-array coercion
        return jnp.issubdtype(np.dtype(dt), jnp.floating)
    except TypeError:
        return False


def _width(dt) -> int:
    return np.dtype(dt).itemsize


@register_pass
class DtypeDriftPass(LintPass):
    name = "dtype-drift"

    def __init__(self, max_const_elems_exempt: int = 1):
        # constants with <= this many elements never count as pollution
        # (scalar eps / mask literals are idiomatic f32 weak types)
        self.max_const_elems_exempt = int(max_const_elems_exempt)

    # ------------------------------------------------------------------
    def run(self, target: GraphTarget) -> List[Finding]:
        narrow = target.compute_dtype
        if narrow is None or not _is_float(narrow) or _width(narrow) >= 4:
            # f32 models have no narrower dtype to drift FROM; only the
            # f64 rule applies
            narrow = None
        closed = target.jaxpr
        findings: List[Finding] = []
        self._walk(target, closed.jaxpr, narrow, (), findings)
        return findings

    # ------------------------------------------------------------------
    def _walk(self, target, jaxpr, narrow, path, findings):
        # origin[id(var)] = set of float dtype names the value has
        # lived in; const_ids = vars that ARE baked-in constants (or
        # pure elementwise functions of one)
        origin: Dict[int, Set[str]] = {}
        const_ids: Set[int] = set()

        def seed(v, is_const=False):
            dt = getattr(v.aval, "dtype", None)
            if dt is not None and _is_float(dt):
                origin[id(v)] = {np.dtype(dt).name}
            if is_const:
                const_ids.add(id(v))

        for v in jaxpr.invars:
            seed(v)
        for v in jaxpr.constvars:
            seed(v, is_const=True)

        narrow_name = np.dtype(narrow).name if narrow is not None else None

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            in_orig: Set[str] = set()
            any_const_in = False
            for a in eqn.invars:
                if hasattr(a, "aval") and not hasattr(a, "val"):
                    in_orig |= origin.get(id(a), set())
                    if id(a) in const_ids:
                        any_const_in = True

            # ---- f64 rule -------------------------------------------
            for o in eqn.outvars:
                dt = getattr(o.aval, "dtype", None)
                if (dt is not None and _is_float(dt)
                        and np.dtype(dt) == np.float64):
                    findings.append(self.finding(
                        target,
                        f"float64 value produced by `{prim}` — f64 on "
                        f"TPU is always drift (np x64 leak)",
                        path=path))
                    break

            # ---- wide-dot rule --------------------------------------
            if (narrow_name is not None and prim in _DOT_PRIMS
                    and eqn.outvars):
                out_dt = getattr(eqn.outvars[0].aval, "dtype", None)
                wide_operand = any(
                    _is_float(a.aval.dtype)
                    and _width(a.aval.dtype) > _width(narrow)
                    for a in eqn.invars[:2]
                    if getattr(getattr(a, "aval", None), "dtype", None)
                    is not None)
                if (out_dt is not None and _is_float(out_dt)
                        and _width(out_dt) > _width(narrow)
                        and wide_operand
                        and narrow_name in in_orig):
                    # declared f32 islands (e.g. the MoE router GEMM,
                    # fp32-by-design for stable softmax) are suppressed
                    # via target.meta['wide_dot_ok'](lhs_aval, rhs_aval)
                    # — suppression is per-shape and auditable, never a
                    # blanket rule relaxation
                    avals = [a.aval for a in eqn.invars
                             if hasattr(a, "aval")]
                    allow = target.meta.get("wide_dot_ok")
                    shapes = " x ".join(
                        str(list(a.shape)) for a in avals[:2])
                    if (allow is not None and len(avals) >= 2
                            and allow(avals[0], avals[1])):
                        findings.append(self.finding(
                            target,
                            f"declared f32 island: `{prim}` ({shapes}) "
                            f"runs in {np.dtype(out_dt).name} by "
                            f"design", severity=Severity.INFO,
                            path=path))
                    else:
                        findings.append(self.finding(
                            target,
                            f"`{prim}` ({shapes}) computes in "
                            f"{np.dtype(out_dt).name} on "
                            f"{narrow_name}-origin data — a silent "
                            f"upcast widened GEMM compute (check for "
                            f"f32 weights/constants in the "
                            f"{narrow_name} model)", path=path))

            # ---- const-pollution rule -------------------------------
            if (narrow_name is not None and len(eqn.invars) >= 2
                    and prim not in _DOT_PRIMS and any_const_in
                    and narrow_name in in_orig):
                for a in eqn.invars:
                    if id(a) not in const_ids:
                        continue
                    dt = getattr(a.aval, "dtype", None)
                    if (dt is None or not _is_float(dt)
                            or _width(dt) <= _width(narrow)):
                        continue
                    size = int(np.prod(getattr(a.aval, "shape", ()) or
                                       (1,)))
                    if size <= self.max_const_elems_exempt:
                        continue
                    findings.append(self.finding(
                        target,
                        f"{np.dtype(dt).name} constant "
                        f"({size} elems) meets {narrow_name} compute "
                        f"in `{prim}` — the constant should be cast "
                        f"to {narrow_name} at build time",
                        path=path))

            # ---- propagate origins ----------------------------------
            if prim in _SOURCE_PRIMS:
                out_orig: Set[str] = set()
            elif prim == "convert_element_type":
                out_orig = set(in_orig)     # casts carry provenance
            else:
                out_orig = set(in_orig)
            for o in eqn.outvars:
                dt = getattr(o.aval, "dtype", None)
                if dt is not None and _is_float(dt):
                    cur = set(out_orig)
                    cur.add(np.dtype(dt).name)
                    origin[id(o)] = cur
                    if any_const_in and all(
                            (id(a) in const_ids or hasattr(a, "val"))
                            for a in eqn.invars):
                        # pure function of constants stays a constant
                        const_ids.add(id(o))

            # ---- recurse into sub-jaxprs ----------------------------
            for label, sub in sub_jaxprs(eqn):
                self._walk(target, sub, narrow,
                           path + ((prim, label),), findings)
