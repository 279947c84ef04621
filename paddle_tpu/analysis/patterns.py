"""Subgraph pattern DSL for the jaxpr rewrite passes.

A pattern is a small dataflow tree written from the anchor (the last
equation of the idiom — the one whose output the rest of the graph
consumes) back toward its inputs:

    ``Op("mul", In("x"), Op("rsqrt", ...), commute=True)``

Matching walks BACKWARD from candidate anchor equations through the
producing equations at the *same jaxpr level* (``lax.scan`` bodies are
their own level — the rewriter recurses into control flow separately),
binding:

* ``In("name")``  — a pattern input: any value (var or literal) feeding
  the idiom from outside. Re-using a name (or the same node instance)
  at two operand positions requires the SAME value at both — how
  ``mul(x, x)`` expresses "the square of one thing".
* ``Lit("name")`` — a scalar ``jax.extend.core.Literal`` operand, captured as
  a Python number (static to the replacement: eps, axis sizes).
* ``Op(prims, *operands, params=..., commute=...)`` — an equation whose
  primitive is in ``prims``; ``params`` entries are exact values or
  ``callable(value, eqn) -> bool`` predicates.
* ``Opt(prims, inner)`` / ``Via(prims, inner)`` — zero-or-one / zero-or-
  more single-input pass-through equations (convert/broadcast/reshape
  wrappers), so one pattern covers the f32 and bf16 spellings of an
  idiom.

A successful match yields the bound values plus the full matched
equation set; the matcher then enforces **exclusivity** — every matched
intermediate is consumed only inside the match — because the rewrite
deletes those equations, and a value someone else reads must keep its
producer. Overlapping candidates resolve largest-first (the bf16
variant of an idiom strictly contains its f32 core).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
from jax.extend import core as jax_core

from ..core.graph_trace import producer_map, var_use_sites

__all__ = ["In", "Lit", "Op", "Opt", "Via", "Match", "match_jaxpr"]


def _prims(p) -> Tuple[str, ...]:
    return (p,) if isinstance(p, str) else tuple(p)


class Pat:
    """Base pattern node."""
    capture: Optional[str] = None


@dataclass
class In(Pat):
    """A value feeding the pattern from outside (captured by name)."""
    name: str
    dtype: Any = None          # required numpy dtype kind/name, if any
    ndim: Optional[int] = None

    def ok(self, aval) -> bool:
        import numpy as np
        if self.dtype is not None:
            dt = getattr(aval, "dtype", None)
            if dt is None or np.dtype(dt) != np.dtype(self.dtype):
                return False
        if self.ndim is not None:
            if len(getattr(aval, "shape", ())) != self.ndim:
                return False
        return True


@dataclass
class Lit(Pat):
    """A scalar literal operand, captured as a Python number."""
    name: Optional[str] = None
    value: Any = None           # required exact value, if given


@dataclass
class Op(Pat):
    prims: Any
    operands: Tuple[Pat, ...]
    params: Optional[Dict[str, Any]] = None
    commute: bool = False
    capture: Optional[str] = None

    def __init__(self, prims, *operands, params=None, commute=False,
                 capture=None):
        self.prims = _prims(prims)
        self.operands = tuple(operands)
        self.params = params
        self.commute = commute
        self.capture = capture


@dataclass
class Opt(Pat):
    """Zero-or-ONE single-input wrapper equation around ``inner``."""
    prims: Any
    inner: Pat
    capture: Optional[str] = None

    def __post_init__(self):
        self.prims = _prims(self.prims)


@dataclass
class Via(Pat):
    """Zero-or-MORE single-input wrapper equations around ``inner``."""
    prims: Any
    inner: Pat
    capture: Optional[str] = None

    def __post_init__(self):
        self.prims = _prims(self.prims)


@dataclass
class Match:
    """One accepted occurrence of a pattern inside one jaxpr level."""
    anchor_idx: int
    eqn_idxs: frozenset               # all matched equations (anchor incl.)
    bindings: Dict[str, Any]          # In/Op captures -> Var | Literal
    statics: Dict[str, Any]           # Lit captures -> Python number
    out_vars: Tuple                   # the anchor equation's outvars
    pattern: Pat = None
    meta: Dict[str, Any] = field(default_factory=dict)

    @property
    def n_eqns(self) -> int:
        return len(self.eqn_idxs)


class _State:
    """Copy-on-branch match state (patterns are tiny; copies are cheap)."""

    __slots__ = ("bindings", "statics", "eqns", "nodes")

    def __init__(self, bindings=None, statics=None, eqns=None, nodes=None):
        self.bindings = dict(bindings or {})
        self.statics = dict(statics or {})
        self.eqns = set(eqns or ())
        self.nodes = dict(nodes or {})   # id(Pat) -> atom (instance reuse)

    def fork(self) -> "_State":
        return _State(self.bindings, self.statics, self.eqns, self.nodes)


def _same_atom(a, b) -> bool:
    if isinstance(a, jax_core.Literal) or isinstance(b, jax_core.Literal):
        return (isinstance(a, jax_core.Literal)
                and isinstance(b, jax_core.Literal)
                and type(a.val) is type(b.val) and bool(a.val == b.val))
    return a is b


def _bind(st: _State, name: Optional[str], atom) -> bool:
    if name is None:
        return True
    if name in st.bindings:
        return _same_atom(st.bindings[name], atom)
    st.bindings[name] = atom
    return True


def _params_ok(pat: Op, eqn) -> bool:
    if not pat.params:
        return True
    for k, want in pat.params.items():
        if k not in eqn.params:
            return False
        got = eqn.params[k]
        if callable(want):
            try:
                if not want(got, eqn):
                    return False
            except Exception:
                return False
        elif got != want:
            return False
    return True


def _match_node(pat: Pat, atom, producers, st: _State) -> Optional[_State]:
    """Try to match ``pat`` against ``atom`` (Var or Literal); returns
    the extended state or None."""
    prev = st.nodes.get(id(pat))
    if prev is not None:
        return st if _same_atom(prev, atom) else None

    if isinstance(pat, In):
        aval = getattr(atom, "aval", None)
        if isinstance(atom, jax_core.Literal):
            aval = jax.typeof(atom.val)
        if not pat.ok(aval):
            return None
        if not _bind(st, pat.name, atom):
            return None
        st.nodes[id(pat)] = atom
        return st

    if isinstance(pat, Lit):
        if not isinstance(atom, jax_core.Literal):
            return None
        import numpy as np
        val = atom.val
        if np.ndim(val) != 0:
            return None
        val = val.item() if hasattr(val, "item") else val
        if pat.value is not None and val != pat.value:
            return None
        if pat.name is not None:
            if pat.name in st.statics and st.statics[pat.name] != val:
                return None
            st.statics[pat.name] = val
        st.nodes[id(pat)] = atom
        return st

    if isinstance(pat, (Opt, Via)):
        cur, walk = atom, st.fork()
        hops = 0
        max_hops = 1 if isinstance(pat, Opt) else 16
        while True:
            got = _match_node(pat.inner, cur, producers, walk.fork())
            if got is not None:
                if not _bind(got, pat.capture, atom):
                    return None
                got.nodes[id(pat)] = atom
                return got
            if hops >= max_hops:
                return None
            if isinstance(cur, jax_core.Literal):
                return None       # literals have no producer to walk
            prod = producers.get(cur)
            if prod is None:
                return None
            i, eqn = prod
            if (eqn.primitive.name not in pat.prims
                    or len(eqn.invars) != 1 or len(eqn.outvars) != 1):
                return None
            walk.eqns.add(i)
            cur = eqn.invars[0]
            hops += 1

    if isinstance(pat, Op):
        if isinstance(atom, jax_core.Literal):
            return None           # an Op's output is never a literal
        prod = producers.get(atom)
        if prod is None:
            return None
        i, eqn = prod
        if eqn.primitive.name not in pat.prims:
            return None
        if len(eqn.invars) != len(pat.operands):
            return None
        if not _params_ok(pat, eqn):
            return None
        orders = [pat.operands]
        if pat.commute and len(pat.operands) == 2:
            orders.append((pat.operands[1], pat.operands[0]))
        for order in orders:
            nxt = st.fork()
            nxt.eqns.add(i)
            ok = True
            for sub, arg in zip(order, eqn.invars):
                got = _match_node(sub, arg, producers, nxt)
                if got is None:
                    ok = False
                    break
                nxt = got
            if ok:
                if not _bind(nxt, pat.capture, atom):
                    continue
                nxt.nodes[id(pat)] = atom
                return nxt
        return None

    raise TypeError(f"unknown pattern node {type(pat).__name__}")


def _exclusive(m: Match, jaxpr, producers, uses) -> bool:
    """Every matched intermediate (output of a matched non-anchor eqn)
    must be consumed ONLY by matched eqns and must not be a jaxpr
    output — the rewrite deletes its producer."""
    for idx in m.eqn_idxs:
        if idx == m.anchor_idx:
            continue
        eqn = jaxpr.eqns[idx]
        for o in eqn.outvars:
            for site in uses.get(o, ()):
                if site == -1 or site not in m.eqn_idxs:
                    return False
    return True


def match_jaxpr(jaxpr, patterns: Sequence[Pat],
                validate: Optional[Callable[[Match, Any], bool]] = None
                ) -> List[Match]:
    """All non-overlapping, exclusive occurrences of ``patterns``
    (anchor variants of ONE idiom) at the top level of ``jaxpr``.
    Candidates are resolved largest-first so a wrapper variant beats
    its own core; ``validate(match, jaxpr)`` is the rule's cross-
    binding check (shape arithmetic the DSL cannot express)."""
    if isinstance(jaxpr, jax_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    producers = producer_map(jaxpr)
    uses = var_use_sites(jaxpr)
    candidates: List[Match] = []
    anchor_prims = set()
    for p in patterns:
        if not isinstance(p, Op):
            raise TypeError("a pattern's anchor must be an Op")
        anchor_prims |= set(p.prims)
    for i, eqn in enumerate(jaxpr.eqns):
        if eqn.primitive.name not in anchor_prims:
            continue
        if len(eqn.outvars) != 1:
            continue
        for p in patterns:
            st = _match_node(p, eqn.outvars[0], producers, _State())
            if st is None:
                continue
            m = Match(anchor_idx=i, eqn_idxs=frozenset(st.eqns),
                      bindings=st.bindings, statics=st.statics,
                      out_vars=tuple(eqn.outvars), pattern=p)
            if not _exclusive(m, jaxpr, producers, uses):
                continue
            if validate is not None and not validate(m, jaxpr):
                continue
            candidates.append(m)
            break   # first variant that fully matches this anchor wins
    # overlap resolution: larger matches first, then program order
    candidates.sort(key=lambda m: (-m.n_eqns, m.anchor_idx))
    taken: set = set()
    out: List[Match] = []
    for m in candidates:
        if m.eqn_idxs & taken:
            continue
        taken |= m.eqn_idxs
        out.append(m)
    out.sort(key=lambda m: m.anchor_idx)
    return out
