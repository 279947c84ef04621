"""Verified jaxpr rewrite passes: the analysis subsystem as optimizer.

PRs 4-5 taught the passes to *see* every flagship graph; this module
lets them *rewrite*. The shape of the thing:

* a :class:`~paddle_tpu.analysis.framework.RewritePass` declares a
  subgraph pattern (``analysis/patterns.py`` DSL), a replacement
  callable (a real Python function — a Pallas kernel entry point, a
  fused op), and an :class:`ExactnessContract`;
* :func:`rewrite_jaxpr` matches every registered pattern across a
  traced ``ClosedJaxpr`` — including inside ``lax.scan`` / ``pjit`` /
  ``cond`` / ``while`` bodies, rebuilt 1:1 via
  ``core.graph_trace.bind_rewritten`` — and returns a **re-jittable,
  re-differentiable callable**: a custom interpreter that executes the
  original equations except where a match fires, where it calls the
  replacement instead (CODA-style epilogue fusion / KForge-style
  kernel substitution, PAPERS.md arxiv 2605.19269 / 2606.02963);
* :func:`verify_rewrite` runs original-vs-rewritten on concrete seeded
  inputs and enforces the contract — bitwise for reassociation-free
  kernel substitutions, pinned tolerance otherwise — before a rewrite
  is allowed to ship (``tools/graph_lint.py --suite rewrite`` is the
  gate).

Concrete rewrites registered here:

* ``int8-epilogue-fuse`` — the dequantize-then-matmul idiom
  (``convert(int8 q) * scale -> dot_general``) becomes the fused
  dequant-in-matmul (``ops/fused/int8_matmul.int8_weight_matmul``:
  scale applied post-matmul, O(out) not O(in*out); routes to the
  authored Pallas int8*bf16 kernel when ``PADDLE_TPU_INT8_IMPL=pallas``).
* ``fused-rmsnorm`` — the jnp rms_norm formulation becomes the
  ``ops/pallas/fused_norm_rope.fused_rms_norm`` kernel (one HBM pass;
  same reductions in the same association, so nothing reassociates —
  but compiler clustering (FMA contraction and reduction tiling inside
  the compiled kernel body vs the eager eqn chain) rounds each of the
  square-sum/rsqrt/mul steps slightly differently. Measured worst case
  across a 420-config sweep (bf16+f32, widths 16-1024, input scales
  0.01-100): 4 units in the last place, so the contract is ``ulp<=4``).
"""
from __future__ import annotations

import functools
from collections import Counter
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.graph_trace import (bind_rewritten, eval_eqn, iter_jaxpr_eqns,
                                sub_jaxprs)
from .framework import (ExactnessContract, Finding, GraphTarget,
                        RewritePass, Severity, default_rewrites,
                        register_rewrite)
from .patterns import In, Lit, Match, Op, Opt, Via, match_jaxpr

__all__ = ["RewriteResult", "VerifyOutcome", "rewrite_jaxpr",
           "rewrite_target", "rewrite_callable", "verify_rewrite",
           "count_matches", "run_rewrite_suite",
           "Int8EpilogueFusePass", "FusedRmsNormPass",
           "DecodeTailFusePass"]

_CONVERT = "convert_element_type"
#: jaxpr-carrying primitives whose bodies the rewriter can rebuild;
#: anything else (custom_vjp bodies, shard_map, pallas_call) is opaque
#: — matches inside it neither fire nor count.
_REBUILDABLE = frozenset({"scan", "pjit", "closed_call", "core_call",
                          "cond", "while", "remat2", "checkpoint"})


def _closed(jaxpr):
    from jax.extend import core as jax_core
    if isinstance(jaxpr, jax_core.ClosedJaxpr):
        return jaxpr
    return jax_core.ClosedJaxpr(jaxpr, ())


# ---------------------------------------------------------------------------
# the rewriting interpreter
# ---------------------------------------------------------------------------

class _Rewriter:
    """Matches per jaxpr level (cached) + the evaluating interpreter."""

    def __init__(self, rules: Sequence[RewritePass]):
        self.rules = list(rules)
        self._matches: Dict[int, List[Tuple[RewritePass, Match]]] = {}
        self._deep: Dict[int, bool] = {}
        self._keep: List[Any] = []   # id()-stability for cached jaxprs

    # -- matching ----------------------------------------------------
    def matches_for(self, jaxpr) -> List[Tuple[RewritePass, Match]]:
        key = id(jaxpr)
        hit = self._matches.get(key)
        if hit is not None:
            return hit
        self._keep.append(jaxpr)
        out: List[Tuple[RewritePass, Match]] = []
        taken: set = set()
        for rule in self.rules:
            ms = match_jaxpr(
                jaxpr, rule.patterns(),
                validate=lambda m, j, r=rule: (
                    r.validate(m, j) and _replacement_fits(r, m)))
            for m in ms:
                if m.eqn_idxs & taken:
                    continue
                taken |= m.eqn_idxs
                out.append((rule, m))
        self._matches[key] = out
        return out

    def deep(self, jaxpr) -> bool:
        """Any match at this level or inside a rebuildable body?"""
        key = id(jaxpr)
        hit = self._deep.get(key)
        if hit is not None:
            return hit
        self._deep[key] = False   # cycle guard (jaxprs are acyclic)
        found = bool(self.matches_for(jaxpr))
        if not found:
            for eqn in jaxpr.eqns:
                if eqn.primitive.name not in _REBUILDABLE:
                    continue
                for _, sub in sub_jaxprs(eqn):
                    if self.deep(sub):
                        found = True
                        break
                if found:
                    break
        self._deep[key] = found
        return found

    def count(self, jaxpr) -> Counter:
        """Static fire counts: matched sites at this level plus inside
        every rebuildable body (each textual site counts once, however
        many loop trips execute it)."""
        c: Counter = Counter()
        for rule, _ in self.matches_for(jaxpr):
            c[rule.name] += 1
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in _REBUILDABLE:
                for _, sub in sub_jaxprs(eqn):
                    c.update(self.count(sub))
        return c

    def sites(self, jaxpr):
        """Yield ``(level_jaxpr, rule, match)`` for every matched site
        at every rebuildable level — the unit local verification runs
        on."""
        for rule, m in self.matches_for(jaxpr):
            yield jaxpr, rule, m
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in _REBUILDABLE:
                for _, sub in sub_jaxprs(eqn):
                    yield from self.sites(sub)

    # -- evaluation --------------------------------------------------
    def run(self, closed, *args) -> List[Any]:
        from jax.extend import core as jax_core
        closed = _closed(closed)
        jaxpr = closed.jaxpr
        if len(args) != len(jaxpr.invars):
            raise TypeError(
                f"rewritten program takes {len(jaxpr.invars)} flat "
                f"args, got {len(args)}")
        env: Dict[Any, Any] = {}

        def read(a):
            return a.val if isinstance(a, jax_core.Literal) else env[a]

        for v, c in zip(jaxpr.constvars, closed.consts):
            env[v] = c
        for v, a in zip(jaxpr.invars, args):
            env[v] = a

        level = self.matches_for(jaxpr)
        anchors = {m.anchor_idx: (rule, m) for rule, m in level}
        skip: set = set()
        for _, m in level:
            skip |= m.eqn_idxs - {m.anchor_idx}

        for i, eqn in enumerate(jaxpr.eqns):
            if i in skip:
                continue
            if i in anchors:
                rule, m = anchors[i]
                fn = rule.build(m.statics)
                vals = [read(m.bindings[n]) for n in rule.arg_names]
                out = fn(*vals)
                outs = (list(out) if isinstance(out, (tuple, list))
                        else [out])
                for v, val in zip(m.out_vars, outs):
                    env[v] = val
                continue
            invals = [read(a) for a in eqn.invars]
            subs = sub_jaxprs(eqn)
            if subs and any(self.deep(s) for _, s in subs):
                try:
                    outs = bind_rewritten(eqn, self.run, invals)
                except NotImplementedError:
                    outs = eval_eqn(eqn, invals)   # opaque body
            else:
                outs = eval_eqn(eqn, invals)
            for v, val in zip(eqn.outvars, outs):
                env[v] = val
        return [read(v) for v in jaxpr.outvars]


def _replacement_fits(rule: RewritePass, m: Match) -> bool:
    """The replacement must produce exactly the anchor's aval (shape
    AND dtype) — a match whose substitute would change the graph's
    types is not a match."""
    import jax
    from jax.extend import core as jax_core
    try:
        args = []
        for n in rule.arg_names:
            atom = m.bindings[n]
            if isinstance(atom, jax_core.Literal):
                args.append(atom.val)
            else:
                args.append(jax.ShapeDtypeStruct(atom.aval.shape,
                                                 atom.aval.dtype))
        out = jax.eval_shape(rule.build(m.statics), *args)
        outs = jax.tree_util.tree_leaves(out)
        if len(outs) != len(m.out_vars):
            return False
        for o, v in zip(outs, m.out_vars):
            if (tuple(o.shape) != tuple(v.aval.shape)
                    or np.dtype(o.dtype) != np.dtype(v.aval.dtype)):
                return False
        return True
    except Exception:
        return False


def count_matches(jaxpr, rules: Optional[Sequence[RewritePass]] = None
                  ) -> Dict[str, int]:
    """Static per-rule match counts over ``jaxpr`` (rebuildable bodies
    included) — the idempotence probe: re-counting on a rewritten
    retrace must give zero."""
    rules = list(rules) if rules is not None else default_rewrites()
    rw = _Rewriter(rules)
    return dict(rw.count(_closed(jaxpr).jaxpr))


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

@dataclass
class RewriteResult:
    """A rewritten program plus everything the suite reports on it."""
    name: str
    closed: Any                           # the original ClosedJaxpr
    fn_flat: Callable                     # flat-args -> flat-outputs
    fired: Dict[str, int]                 # rule name -> matched sites
    eqns_before: int
    eqns_after: Optional[int] = None      # after retrace (None if skipped)
    residual: Optional[Dict[str, int]] = None   # matches on the retrace
    rewritten_closed: Any = None

    @property
    def idempotent(self) -> Optional[bool]:
        if self.residual is None:
            return None
        return not any(self.residual.values())


def rewrite_jaxpr(closed, rules: Optional[Sequence[RewritePass]] = None,
                  name: str = "graph", retrace: bool = False
                  ) -> RewriteResult:
    """Apply ``rules`` (default: every registered rewrite) to a traced
    ``ClosedJaxpr``. The result's ``fn_flat`` takes the jaxpr's flat
    invars and is re-jittable and re-differentiable — replacements are
    real Python functions (custom_vjp kernels keep their gradients).

    ``retrace=True`` re-traces the rewritten callable abstractly to
    report after-rewrite equation counts and the idempotence residual
    (matches still present — must be zero).
    """
    import jax
    closed = _closed(closed)
    rules = list(rules) if rules is not None else default_rewrites()
    rw = _Rewriter(rules)
    fired = dict(rw.count(closed.jaxpr))
    fn_flat = functools.partial(rw.run, closed)
    res = RewriteResult(
        name=name, closed=closed, fn_flat=fn_flat, fired=fired,
        eqns_before=sum(1 for _ in iter_jaxpr_eqns(closed)))
    if retrace:
        if any(fired.values()):
            avals = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                     for v in closed.jaxpr.invars]
            new_closed = jax.make_jaxpr(fn_flat)(*avals)
            res.rewritten_closed = new_closed
            res.eqns_after = sum(1 for _ in iter_jaxpr_eqns(new_closed))
            res.residual = count_matches(new_closed, rules)
        else:
            res.rewritten_closed = closed
            res.eqns_after = res.eqns_before
            res.residual = {}
    return res


def rewrite_target(target: GraphTarget,
                   rules: Optional[Sequence[RewritePass]] = None,
                   retrace: bool = True) -> RewriteResult:
    """:func:`rewrite_jaxpr` over a lint :class:`GraphTarget`."""
    return rewrite_jaxpr(target.jaxpr, rules, name=target.name,
                         retrace=retrace)


def rewrite_callable(fn: Callable,
                     rules: Optional[Sequence[str]] = None) -> Callable:
    """Wrap ``fn`` so every call traces it, applies the rewrites, and
    runs the rewritten program. Composes with ``jax.jit`` (the wrapper
    re-traces per jit trace — compile-time cost only) and with
    ``jax.grad`` (replacements carry their own VJPs). Keyword args are
    treated as static (closed over at trace time), matching how the
    serving engine partials its step functions."""
    rule_objs = None if rules is None else default_rewrites(tuple(rules))

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        import jax
        closed, out_shape = jax.make_jaxpr(
            lambda *a: fn(*a, **kwargs), return_shape=True)(*args)
        res = rewrite_jaxpr(closed, rule_objs)
        leaves = jax.tree_util.tree_leaves(args)
        out_flat = res.fn_flat(*leaves)
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(out_shape), out_flat)

    return wrapped


# ---------------------------------------------------------------------------
# verification: the exactness gate
# ---------------------------------------------------------------------------

@dataclass
class VerifyOutcome:
    ok: bool
    mode: str                   # "bitwise" | "rtol=.. atol=.." | "no-op"
    max_abs: float = 0.0
    max_rel: float = 0.0
    sites: int = 0              # locally verified match sites
    detail: str = ""


def _seed_value(aval, rng):
    """One seeded concrete value for an abstract value: small ints for
    integer avals (valid as tokens/lengths/page ids — XLA clamps
    gathers, and both sides see identical inputs), scaled normals for
    floats."""
    import jax.numpy as jnp
    sh = tuple(aval.shape)
    dt = aval.dtype
    if jnp.issubdtype(dt, jnp.integer):
        lo, hi = (-3, 4) if np.dtype(dt).itemsize == 1 else (0, 4)
        return jnp.asarray(rng.randint(lo, hi, size=sh), dt)
    if jnp.issubdtype(dt, jnp.bool_):
        return jnp.zeros(sh, bool)
    return jnp.asarray(
        rng.standard_normal(sh) * 0.5, jnp.float32).astype(dt)


def concrete_inputs(closed, seed: int = 0) -> List[Any]:
    """Seeded concrete values for a jaxpr's flat invars."""
    rng = np.random.RandomState(seed)
    return [_seed_value(v.aval, rng)
            for v in _closed(closed).jaxpr.invars]


def _ulp_distance(an: np.ndarray, bn: np.ndarray) -> int:
    """Max units-in-last-place distance between two same-dtype float
    arrays (IEEE lexicographic-ordering trick: bit patterns map to a
    monotonic integer line; +0 and -0 coincide). NaNs must coincide
    positionally; any mismatched NaN is an infinite distance."""
    nan_a, nan_b = np.isnan(an), np.isnan(bn)
    if (nan_a != nan_b).any():
        return np.iinfo(np.int64).max
    # all arithmetic stays in the UNSIGNED view dtype (modular), so the
    # mapping is exact for 8-byte floats too — int64 intermediates
    # would wrap at `1 << 63` and scramble the float64 ordering
    u = np.dtype(f"u{an.dtype.itemsize}")
    ai, bi = an.view(u), bn.view(u)
    sign = np.array(1, u) << np.array(8 * an.dtype.itemsize - 1, u)
    zero = np.array(0, u)
    ao = np.where(ai < sign, sign + ai, zero - ai)
    bo = np.where(bi < sign, sign + bi, zero - bi)
    d = np.where(ao >= bo, ao - bo, bo - ao)
    d = np.where(nan_a, zero, d)
    return int(d.max()) if d.size else 0


def _compare(contract: ExactnessContract, ref, got, label: str
             ) -> VerifyOutcome:
    """Compare two flat output lists under a contract."""
    if len(ref) != len(got):
        return VerifyOutcome(False, contract.describe(),
                             detail=f"{label}: output arity changed")
    max_abs = max_rel = 0.0
    for k, (a, b) in enumerate(zip(ref, got)):
        an, bn = np.asarray(a), np.asarray(b)
        if an.shape != bn.shape or an.dtype != bn.dtype:
            return VerifyOutcome(
                False, contract.describe(),
                detail=f"{label}: output {k} aval changed: "
                       f"{an.dtype}{an.shape} vs {bn.dtype}{bn.shape}")
        exact_kind = an.dtype.kind in "iub"
        if contract.bitwise or exact_kind:
            if an.tobytes() != bn.tobytes():
                af = an.astype(np.float64) if not exact_kind else an
                bf = bn.astype(np.float64) if not exact_kind else bn
                d = float(np.max(np.abs(af - bf)))
                return VerifyOutcome(
                    False, contract.describe(), max_abs=d,
                    detail=f"{label}: output {k} not bitwise-equal "
                           f"(max abs diff {d:.3e})")
        elif contract.ulp:
            d = _ulp_distance(an, bn)
            if d > contract.ulp:
                return VerifyOutcome(
                    False, contract.describe(),
                    max_abs=float(np.max(np.abs(
                        an.astype(np.float64) - bn.astype(np.float64)))),
                    detail=f"{label}: output {k} is {d} ulp from the "
                           f"original (contract allows {contract.ulp})")
        else:
            af = an.astype(np.float64)
            bf = bn.astype(np.float64)
            # Diffs over the jointly-finite positions only: a NaN (from
            # e.g. rsqrt of an adversarially-seeded negative variance)
            # would poison max() and report 0.0 for a failing site.
            fin = np.isfinite(af) & np.isfinite(bf)
            diff = np.abs(af[fin] - bf[fin])
            denom = np.maximum(np.abs(af[fin]), 1e-30)
            max_abs = max(max_abs, float(diff.max()) if diff.size
                          else 0.0)
            max_rel = max(max_rel, float((diff / denom).max())
                          if diff.size else 0.0)
            if not np.allclose(af, bf, rtol=contract.rtol,
                               atol=contract.atol, equal_nan=True):
                why = ("NaN/inf positions diverge"
                       if bool((np.isnan(af) != np.isnan(bf)).any()
                               or (np.isinf(af) != np.isinf(bf)).any())
                       else "outside tolerance")
                return VerifyOutcome(
                    False, contract.describe(), max_abs=max_abs,
                    max_rel=max_rel,
                    detail=f"{label}: output {k} {why}")
    return VerifyOutcome(True, contract.describe(), max_abs=max_abs,
                         max_rel=max_rel)


def verify_site(jaxpr, rule: RewritePass, m: Match,
                seeds: Sequence[int] = (0, 1)) -> VerifyOutcome:
    """Verify ONE matched site locally: evaluate the matched subgraph
    (original equations) vs the rule's replacement on seeded concrete
    values of the subgraph's own inputs, under the rule's contract.

    This is where a tolerance contract is *meaningful*: it bounds the
    error of the replaced computation itself. (A whole-graph tolerance
    check would instead measure how a downstream transformer amplifies
    a one-ulp weight difference — unbounded and graph-dependent, so the
    suite never does that; whole-graph equivalence is only asserted
    bitwise, when every firing rule is bitwise.)"""
    from jax.extend import core as jax_core
    if isinstance(jaxpr, jax_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    idxs = sorted(m.eqn_idxs)
    produced = {o for i in idxs for o in jaxpr.eqns[i].outvars}
    # external inputs of the subgraph = vars read by matched eqns but
    # produced outside the match (named In captures among them)
    external: List[Any] = []
    for i in idxs:
        for a in jaxpr.eqns[i].invars:
            if (not isinstance(a, jax_core.Literal)
                    and a not in produced and a not in external):
                external.append(a)
    outcome = None
    for seed in seeds:
        rng = np.random.RandomState(seed)
        env: Dict[Any, Any] = {v: _seed_value(v.aval, rng)
                               for v in external}

        def read(a):
            return (a.val if isinstance(a, jax_core.Literal)
                    else env[a])

        for i in idxs:
            eqn = jaxpr.eqns[i]
            outs = eval_eqn(eqn, [read(a) for a in eqn.invars])
            for v, val in zip(eqn.outvars, outs):
                env[v] = val
        ref = [env[v] for v in m.out_vars]
        args = [read(m.bindings[n]) for n in rule.arg_names]
        got = rule.build(m.statics)(*args)
        got = list(got) if isinstance(got, (tuple, list)) else [got]
        outcome = _compare(rule.contract, ref, got,
                           f"{rule.name}@eqn{m.anchor_idx} seed {seed}")
        if not outcome.ok:
            return outcome
    return outcome if outcome is not None else VerifyOutcome(
        True, rule.contract.describe())


def _effective_contract(fired: Dict[str, int],
                        rules: Sequence[RewritePass]) -> ExactnessContract:
    """The loosest contract among the rules that fired: outputs are
    bitwise only if EVERY firing rewrite is bitwise; a tolerance
    (rtol/atol) rule dominates a ulp rule dominates bitwise."""
    by_name = {r.name: r for r in rules}
    rtol = atol = 0.0
    ulp = 0
    bitwise = True
    for name, n in fired.items():
        if not n:
            continue
        c = by_name[name].contract
        if not c.bitwise:
            bitwise = False
            ulp = max(ulp, c.ulp)
            rtol = max(rtol, c.rtol)
            atol = max(atol, c.atol)
    if rtol or atol:
        ulp = 0
    return ExactnessContract(bitwise=bitwise, ulp=ulp, rtol=rtol,
                             atol=atol)


def verify_rewrite(res: RewriteResult,
                   rules: Optional[Sequence[RewritePass]] = None,
                   seeds: Sequence[int] = (0, 1),
                   jit: bool = True) -> VerifyOutcome:
    """Enforce the exactness contracts of every rewrite that fired:

    1. **Per-site, always** — every matched subgraph is evaluated
       original-vs-replacement in isolation on seeded concrete values
       of its own inputs (:func:`verify_site`), under the owning rule's
       contract. A tolerance contract bounds THIS — the error of the
       replaced computation — not the whole program, through which a
       downstream transformer amplifies one-ulp differences without
       bound.
    2. **Whole-graph, when every firing rule is bitwise** — original vs
       rewritten program on seeded whole-graph inputs, byte-identical
       outputs required. ``jit=True`` compiles both sides, which also
       proves the rewritten callable is re-jittable.
    """
    import jax
    from jax.extend import core as jax_core
    rules = list(rules) if rules is not None else default_rewrites()
    if not any(res.fired.values()):
        return VerifyOutcome(ok=True, mode="no-op",
                             detail="no rewrite fired")
    contract = _effective_contract(res.fired, rules)
    # 1. local: every matched site, under its own rule's contract
    rw = _Rewriter(rules)
    n_sites = 0
    max_abs = max_rel = 0.0
    for level, rule, m in rw.sites(res.closed.jaxpr):
        out = verify_site(level, rule, m, seeds)
        n_sites += 1
        max_abs = max(max_abs, out.max_abs)
        max_rel = max(max_rel, out.max_rel)
        if not out.ok:
            out.sites = n_sites
            return out
    # 2. global: only meaningful when the composition is bitwise
    if contract.bitwise:
        base = jax_core.jaxpr_as_fun(res.closed)
        new = res.fn_flat
        if jit:
            base, new = jax.jit(base), jax.jit(new)
        for seed in seeds:
            ins = concrete_inputs(res.closed, seed)
            out = _compare(contract, base(*ins), new(*ins),
                           f"whole-graph seed {seed}")
            if not out.ok:
                out.sites = n_sites
                return out
    return VerifyOutcome(True, contract.describe(), max_abs=max_abs,
                         max_rel=max_rel, sites=n_sites,
                         detail=f"{n_sites} sites verified locally"
                                + (", whole graph bitwise"
                                   if contract.bitwise else ""))


# ---------------------------------------------------------------------------
# concrete rewrites
# ---------------------------------------------------------------------------

def _is_matmul_dims(dn, eqn) -> bool:
    """dot_general contracting (last lhs dim, first rhs dim), no batch
    dims — the ``x @ w`` shape every projection in the repo uses."""
    try:
        (lc, rc), (lb, rb) = dn
        lhs_ndim = len(eqn.invars[0].aval.shape)
        return (tuple(lb) == () and tuple(rb) == ()
                and tuple(rc) == (0,) and tuple(lc) == (lhs_ndim - 1,))
    except Exception:
        return False


@register_rewrite
class Int8EpilogueFusePass(RewritePass):
    """Fuse dequantize-then-matmul into dequant-IN-matmul.

    The unfused idiom materialises the dense weight —
    ``w = (q.astype(f32) * scale).astype(dtype); x @ w`` — paying
    O(in*out) dequant traffic per call. The fused form computes
    ``(x @ q.astype(dtype)) * scale``: int8 values are exact in bf16,
    the per-output-channel scale moves across the contraction, and the
    epilogue costs O(out). Moving the scale reassociates the rounding,
    so the contract is a pinned tolerance, not bitwise."""

    name = "int8-epilogue-fuse"
    contract = ExactnessContract(bitwise=False, rtol=0.05, atol=0.1)
    arg_names = ("x", "q", "scale")
    # outranks decode-tail-fuse (10): that pattern takes the head as an
    # opaque input, so on an un-fused int8 head it would claim the dot
    # and leave the dequantized weight materialised outside it
    priority = 5

    def patterns(self):
        qf = Op(_CONVERT, In("q", dtype=np.int8))
        sb = Via((_CONVERT, "broadcast_in_dim", "reshape"),
                 In("scale", ndim=1), capture="scale_b")
        w = Via((_CONVERT,), Op("mul", qf, sb, commute=True))
        return [Op("dot_general", In("x"), w,
                   params={"dimension_numbers": _is_matmul_dims})]

    def validate(self, match, jaxpr) -> bool:
        q = match.bindings["q"]
        scale = match.bindings["scale"]
        qsh = tuple(q.aval.shape)
        if len(qsh) != 2:
            return False
        if tuple(scale.aval.shape) != (qsh[1],):
            return False
        # the scale must broadcast over the INPUT dim (per-output-
        # channel): the mul's scale-side operand (``scale_b`` — the
        # broadcast/reshape chain's outer value) has `out` as its
        # trailing dim and only 1s before it. A per-input-channel
        # scale ([in, 1]) is a different quantization scheme — the
        # epilogue cannot represent it, so it must NOT fire.
        sb = match.bindings.get("scale_b")
        if sb is not None and hasattr(sb, "aval"):
            sh = tuple(sb.aval.shape)
            if sh and (sh[-1] != qsh[1]
                       or any(d != 1 for d in sh[:-1])):
                return False
        return True

    def build(self, statics):
        from ..ops.fused.int8_matmul import fused_impl, int8_weight_matmul
        impl = fused_impl()
        return lambda x, q, scale: int8_weight_matmul(x, q, scale,
                                                      impl=impl)


def _last_axis(axes, eqn) -> bool:
    ndim = len(eqn.invars[0].aval.shape)
    return tuple(axes) == (ndim - 1,)


def _rms_core_pattern():
    """The jnp rms_norm idiom (models.llama.rms_norm and the
    functional layer path trace to the same eqn chain), ending at the
    pre-output-convert weight multiply. Shared by ``fused-rmsnorm``
    (which anchors here / on the trailing convert) and by
    ``decode-tail-fuse`` (which swallows it inside the serving tail)."""
    xf = Opt(_CONVERT, In("x"))
    mean = Op("div",
              Via(("broadcast_in_dim", "reshape"),
                  Op("reduce_sum", Op("mul", xf, xf),
                     params={"axes": _last_axis})),
              Lit("denom"))
    rstd = Op("rsqrt", Op("add", mean, Lit("eps")))
    y = Op("mul", xf, Via(("broadcast_in_dim", "reshape"), rstd),
           commute=True)
    wb = Via((_CONVERT, "broadcast_in_dim", "reshape"), In("w", ndim=1))
    return Op("mul", y, wb, commute=True)


@register_rewrite
class FusedRmsNormPass(RewritePass):
    """Substitute the fused Pallas rms_norm kernel for the jnp
    formulation (KForge-style kernel substitution against a kernel the
    repo already trusts — tests/test_pallas_kernels.py). The kernel
    performs the same reductions in the same association in f32; only
    compiler clustering (FMA contraction, reduction tiling across the
    fused kernel body vs the eager eqn chain) can round differently.
    The compounded drift through the square-sum -> rsqrt -> two-mul
    chain measures at most 4 units in the last place of the output
    dtype (420-config sweep: bf16+f32, widths 16-1024, input scales
    0.01-100; flagship shapes measure 2), so the contract pins
    ``ulp<=4``."""

    name = "fused-rmsnorm"
    contract = ExactnessContract(ulp=4)
    arg_names = ("x", "w")

    def patterns(self):
        core = _rms_core_pattern()
        return [Op(_CONVERT, core), core]

    def validate(self, match, jaxpr) -> bool:
        x = match.bindings["x"]
        w = match.bindings["w"]
        xsh = tuple(x.aval.shape)
        if not xsh or tuple(w.aval.shape) != (xsh[-1],):
            return False
        # the mean's denominator must be the normalised axis size —
        # a mean over anything else is not an rmsnorm
        if match.statics.get("denom") != xsh[-1]:
            return False
        # the kernel tiles rows in VMEM: rows must exist
        return int(np.prod(xsh[:-1], dtype=np.int64)) >= 1

    def build(self, statics):
        from ..ops.pallas.fused_norm_rope import fused_rms_norm
        eps = float(statics["eps"])
        return lambda x, w: fused_rms_norm(x, w, eps)


def _is_row_gather(dn, eqn) -> bool:
    """``x[idx]`` on a 2-D operand: one whole row per index."""
    return (tuple(dn.offset_dims) == (1,)
            and tuple(dn.collapsed_slice_dims) == (0,)
            and tuple(dn.start_index_map) == (0,))


@register_rewrite
class DecodeTailFusePass(RewritePass):
    """Fuse the serving decode tail — final rms_norm over the packed
    ``[T, D]`` stream, negative-wrapping last-row gather, lm_head
    matmul, f32 cast — into ``ops/fused/decode_tail.fused_decode_tail``,
    which hoists the gather ABOVE the norm (rms is row-local, so the
    reorder is exact per surviving row and the ``T−S`` dead rows are
    never normalised or written back) and runs the norm through the
    Pallas ``fused_rms_norm`` kernel.

    The pattern swallows the whole fused-rmsnorm core, so this pass
    must outrank it (priority 10 < 100): the tail's norm belongs to
    this match, while every per-layer norm still falls through to the
    plain substitution.

    Contract: the gather reorder is exact, and the substitution
    mirrors the matched dot's compute dtype (the AMP graphs cast the
    normed f32 rows DOWN to ``head.dtype`` before the matmul — an
    early version computed the dot in f32 and measured 2e-2 of
    phantom "drift" that was really extra precision). With dtypes
    mirrored the serving suite's seeded sites measure 0.0 drift; the
    rtol 1e-3 / atol 1e-3 pin is headroom for the kernel-vs-eager
    norm difference (≤4 ulp) amplified through the [D]-long dot.
    """

    name = "decode-tail-fuse"
    contract = ExactnessContract(rtol=1e-3, atol=1e-3)
    arg_names = ("x", "w", "idx", "head")
    priority = 10

    def patterns(self):
        normed = Opt(_CONVERT, _rms_core_pattern())
        idx = In("idx")
        wrapped = Op("select_n",
                     Op("lt", idx, Lit(value=0)),
                     idx,
                     Op("add", idx, Lit("nrows")))
        bidx = Via(("broadcast_in_dim", "reshape", _CONVERT), wrapped)
        rows = Op("gather", normed, bidx,
                  params={"dimension_numbers": _is_row_gather})
        mm = Op("dot_general", rows, In("head"),
                params={"dimension_numbers": _is_matmul_dims})
        return [Op(_CONVERT, mm), mm]

    def validate(self, match, jaxpr) -> bool:
        x = match.bindings["x"].aval
        w = match.bindings["w"].aval
        idx = match.bindings["idx"].aval
        head = match.bindings["head"].aval
        if len(x.shape) != 2 or tuple(w.shape) != (x.shape[-1],):
            return False
        if match.statics.get("denom") != x.shape[-1]:
            return False
        # the wrap's added constant must be THIS stream's row count
        if match.statics.get("nrows") != x.shape[0]:
            return False
        if len(idx.shape) != 1 or not np.issubdtype(idx.dtype,
                                                    np.integer):
            return False
        if len(head.shape) != 2 or head.shape[0] != x.shape[-1]:
            return False
        gather = next(jaxpr.eqns[i] for i in sorted(match.eqn_idxs)
                      if jaxpr.eqns[i].primitive.name == "gather")
        if tuple(gather.params["slice_sizes"]) != (1, x.shape[-1]):
            return False
        # the anchor may or may not carry the final f32 convert; the
        # replacement must reproduce the matched output dtype exactly
        match.statics["out_dtype"] = str(match.out_vars[0].aval.dtype)
        return True

    def build(self, statics):
        import jax.numpy as jnp
        from ..ops.fused.decode_tail import fused_decode_tail
        eps = float(statics["eps"])
        out_dtype = jnp.dtype(statics["out_dtype"])
        return lambda x, w, idx, head: fused_decode_tail(
            x, w, idx, head, eps=eps, out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# the rewrite suite (graph_lint --suite rewrite)
# ---------------------------------------------------------------------------

def run_rewrite_suite(models=("llama",), verify: bool = True,
                      rules: Optional[Sequence[RewritePass]] = None,
                      targets: Optional[Sequence[GraphTarget]] = None,
                      serving_pool: Optional[Sequence[GraphTarget]] = None):
    """Rewrite + verify every flagship rewrite target (or explicit
    ``targets``). Returns ``(findings, table)`` where ``findings`` are
    framework Findings (ERROR when an expected rewrite did not fire,
    the rewriter is not idempotent, or a contract is violated) and
    ``table`` is the ``--json`` payload: per graph, which rewrites
    fired with before/after eqn counts and the verifier verdict."""
    rules = list(rules) if rules is not None else default_rewrites()
    if targets is None:
        from .rewrite_conv import resnet_rewrite_targets
        from .serving_graphs import rewrite_targets
        targets = rewrite_targets(models, serving_pool=(
            list(serving_pool) if serving_pool is not None else None))
        targets = list(targets) + resnet_rewrite_targets()
    findings: List[Finding] = []
    table: List[Dict[str, Any]] = []
    for target in targets:
        res = rewrite_target(target, rules)
        expect = set(target.meta.get("expect_rewrites", ()))
        fired = {k for k, v in res.fired.items() if v}
        row: Dict[str, Any] = {
            "graph": target.name, "fired": dict(res.fired),
            "eqns_before": res.eqns_before, "eqns_after": res.eqns_after,
            "idempotent": res.idempotent,
        }
        for missing in sorted(expect - fired):
            findings.append(Finding(
                pass_name="rewrite-suite", severity=Severity.ERROR,
                graph=target.name,
                message=f"expected rewrite {missing!r} did not fire "
                        f"(fired: {sorted(fired) or 'none'})"))
        if res.idempotent is False:
            findings.append(Finding(
                pass_name="rewrite-suite", severity=Severity.ERROR,
                graph=target.name,
                message=f"rewriter is not idempotent: re-running on the "
                        f"rewritten graph still matches {res.residual}"))
        if verify:
            out = verify_rewrite(res, rules)
            row["verify"] = {"ok": out.ok, "contract": out.mode,
                             "max_abs": out.max_abs,
                             "max_rel": out.max_rel}
            if not out.ok:
                findings.append(Finding(
                    pass_name="rewrite-suite", severity=Severity.ERROR,
                    graph=target.name,
                    message=f"exactness contract ({out.mode}) violated: "
                            f"{out.detail}"))
        findings.append(Finding(
            pass_name="rewrite-suite", severity=Severity.INFO,
            graph=target.name,
            message=f"fired {dict(res.fired)}, eqns "
                    f"{res.eqns_before}->{res.eqns_after}"
                    + (f", verified {row['verify']['contract']}"
                       if verify and "verify" in row else "")))
        table.append(row)
    return findings, table


# registers the ResNet conv passes (conv-bn-fold, stem-space-to-depth,
# conv-nhwc-layout) alongside the passes defined above — one import
# site, so building rules from REWRITE_REGISTRY always sees all of them
from . import rewrite_conv as _rewrite_conv  # noqa: E402,F401
