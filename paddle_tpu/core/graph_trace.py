"""Shared layer-graph tracer + jaxpr walking utilities.

One tracing forward that records, at TOP level (outside any leaf
layer), both leaf-layer calls and functional registry ops — the
machinery behind `onnx/export.py` (graph emission) and
`inference/passes.py` (dataflow-verified folds). Keeping it in one
place means tuple outputs, kwargs tensors and consumer accounting
behave identically for every consumer of the trace.

The jaxpr side (``iter_jaxpr_eqns`` / ``sub_jaxprs``) is the shared
walk every jaxpr-level analysis uses (``paddle_tpu/analysis``): one
recursive traversal that sees through scan/while/cond/pjit/remat/
shard_map bodies, yielding each equation with the control-flow path
that reaches it — so a pass written against flat equations works
unchanged on the serving graphs, whose hot loops all live inside
``lax.scan``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Set, Tuple

import jax
from jax.extend import core as jax_core

from .tensor import Tensor


# ---------------------------------------------------------------------------
# jaxpr traversal
# ---------------------------------------------------------------------------

def sub_jaxprs(eqn) -> List[Tuple[str, "jax_core.Jaxpr"]]:
    """The (label, jaxpr) bodies nested inside one equation.

    Covers every closed-jaxpr-carrying param jax uses across versions
    (scan/while/cond/pjit/custom_vjp/remat/shard_map/...) by TYPE, not
    by a primitive-name allowlist — a new primitive with a jaxpr param
    is walked automatically instead of silently skipped."""
    out = []
    for name, val in eqn.params.items():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for i, v in enumerate(vals):
            label = name if len(vals) == 1 else f"{name}[{i}]"
            if isinstance(v, jax_core.ClosedJaxpr):
                out.append((label, v.jaxpr))
            elif isinstance(v, jax_core.Jaxpr):
                out.append((label, v))
    return out


def iter_jaxpr_eqns(jaxpr, path: Tuple = ()) -> Iterator[Tuple[Tuple,
                                                               Any]]:
    """Yield ``(path, eqn)`` for every equation, depth-first, where
    ``path`` is the chain of ``(primitive_name, param_label)`` frames
    that reaches the equation (empty for top level). ``jaxpr`` may be a
    ``ClosedJaxpr`` or a raw ``Jaxpr``."""
    if isinstance(jaxpr, jax_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield path, eqn
        for label, sub in sub_jaxprs(eqn):
            yield from iter_jaxpr_eqns(
                sub, path + ((eqn.primitive.name, label),))


# ---------------------------------------------------------------------------
# jaxpr rewriting support (analysis/rewrite.py builds on these)
# ---------------------------------------------------------------------------

def producer_map(jaxpr) -> Dict[Any, Tuple[int, Any]]:
    """var -> (eqn_index, eqn) for every var DEFINED at this level of
    ``jaxpr`` (sub-jaxpr internals excluded: a pattern is a same-level
    dataflow chain; values crossing a control-flow boundary are inputs,
    not intermediates)."""
    if isinstance(jaxpr, jax_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    out: Dict[Any, Tuple[int, Any]] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for o in eqn.outvars:
            out[o] = (i, eqn)
    return out


def var_use_sites(jaxpr) -> Dict[Any, List[int]]:
    """var -> list of eqn indices consuming it at this level; an
    appearance in ``jaxpr.outvars`` adds the sentinel ``-1``. The
    exclusivity test rewrites need: a matched intermediate whose uses
    are not all inside the match cannot be deleted with it."""
    if isinstance(jaxpr, jax_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    uses: Dict[Any, List[int]] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for a in eqn.invars:
            if not isinstance(a, jax_core.Literal):
                uses.setdefault(a, []).append(i)
    for o in jaxpr.outvars:
        if not isinstance(o, jax_core.Literal):
            uses.setdefault(o, []).append(-1)
    return uses


def eval_eqn(eqn, invals: List[Any]):
    """Re-issue one equation on concrete/traced values exactly as
    ``jax.core.eval_jaxpr`` would (same primitive, same params).
    Returns the flat list of outputs."""
    subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
    ans = eqn.primitive.bind(*subfuns, *invals, **bind_params)
    return list(ans) if eqn.primitive.multiple_results else [ans]


def bind_rewritten(eqn, run_body, invals: List[Any]) -> List[Any]:
    """Re-issue a jaxpr-carrying equation with every body evaluated by
    ``run_body(closed_jaxpr, *flat_args) -> flat_outs`` — the hook a
    rewriter uses to splice replacements into scan/while/cond/pjit
    bodies while the surrounding control flow is rebuilt 1:1 (same trip
    counts, same carry structure, so numerics outside the rewritten
    subgraphs are untouched). Raises ``NotImplementedError`` for
    jaxpr-carrying primitives without a rebuild recipe (custom_vjp
    bodies, shard_map, ...): the caller falls back to binding the eqn
    unchanged, i.e. those bodies are opaque to rewriting."""
    import jax
    from jax import lax
    prim = eqn.primitive.name
    p = eqn.params
    if prim == "scan":
        nc, ncar = p["num_consts"], p["num_carry"]
        body = p["jaxpr"]
        consts = tuple(invals[:nc])
        carry = tuple(invals[nc:nc + ncar])
        xs = tuple(invals[nc + ncar:])

        def f(c, x):
            outs = run_body(body, *consts, *c, *(x or ()))
            return tuple(outs[:ncar]), tuple(outs[ncar:])

        carry_out, ys = lax.scan(
            f, carry, xs if xs else None, length=p["length"],
            reverse=p["reverse"], unroll=p.get("unroll", 1))
        return list(carry_out) + list(ys)
    if prim in ("pjit", "closed_call", "core_call"):
        # inline: the rewritten whole-program is re-jitted by its
        # caller anyway, so the inner jit boundary carries no value
        return list(run_body(p["jaxpr"], *invals))
    if prim == "cond":
        branches = p["branches"]
        idx, *ops = invals
        fns = [(lambda b: lambda *a: tuple(run_body(b, *a)))(b)
               for b in branches]
        out = lax.switch(idx, fns, *ops)
        return list(out)
    if prim == "while":
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        cconsts = tuple(invals[:cn])
        bconsts = tuple(invals[cn:cn + bn])
        init = tuple(invals[cn + bn:])
        out = lax.while_loop(
            lambda c: run_body(p["cond_jaxpr"], *cconsts, *c)[0],
            lambda c: tuple(run_body(p["body_jaxpr"], *bconsts, *c)),
            init)
        return list(out)
    if prim in ("remat2", "checkpoint"):
        body = p["jaxpr"]
        closed = (body if isinstance(body, jax_core.ClosedJaxpr)
                  else jax_core.ClosedJaxpr(body, ()))
        fn = jax.checkpoint(lambda *a: tuple(run_body(closed, *a)),
                            policy=p.get("policy"),
                            prevent_cse=p.get("prevent_cse", True))
        return list(fn(*invals))
    raise NotImplementedError(
        f"no rebuild recipe for jaxpr-carrying primitive {prim!r}")


@dataclass
class TraceResult:
    #: ordered top-level events:
    #:   ("layer", layer, inputs, output) | ("op", name, args, kwargs, out)
    events: List[Tuple] = field(default_factory=list)
    #: id(tensor) -> number of top-level consumptions (leaf-layer inputs
    #: + depth-0 op args + model outputs)
    consumers: Dict[int, int] = field(default_factory=dict)
    #: ids of every tensor PRODUCED during the trace
    traced_ids: Set[int] = field(default_factory=set)
    #: per-layer top-level call counts (object identity)
    layer_calls: Dict[int, int] = field(default_factory=dict)
    #: the model's return value
    y: Any = None
    #: strong refs — a GC'd tensor's id would be recycled mid-trace
    keep: List[Any] = field(default_factory=list)

    def consumed(self, v):
        if isinstance(v, Tensor):
            self.keep.append(v)
            self.consumers[id(v)] = self.consumers.get(id(v), 0) + 1

    def produced(self, out):
        for t in (out if isinstance(out, (tuple, list)) else (out,)):
            if isinstance(t, Tensor):
                self.keep.append(t)
                self.traced_ids.add(id(t))


def trace_layer_graph(model, x: Tensor, leaves=None) -> TraceResult:
    """Run ``model(x)`` in eval/no-grad with recording hooks installed;
    restores training mode and hooks afterwards.

    ``leaves`` sets the trace granularity: the layers treated as
    ATOMIC (one "layer" event each; anything inside them — sublayer
    calls, functional ops — is masked by the depth counter). Default
    None = the model's leaf sublayers (the ONNX-export shape). The
    auto-parallel Engine's pp forward-order check passes its top-level
    UNITS here, so "op" events then mean exactly "functional math
    between units" — glue a stage loop cannot reproduce."""
    from ..autograd import tape as _tape
    from ..ops import registry as _registry

    res = TraceResult()
    depth = [0]
    hooks = []

    def pre(l, inputs):
        if depth[0] == 0:
            for v in (inputs if isinstance(inputs, tuple) else (inputs,)):
                res.consumed(v)
        depth[0] += 1

    def post(l, inputs, output):
        depth[0] -= 1
        res.produced(output)
        if depth[0] == 0:
            res.events.append(("layer", l, inputs, output))
            res.layer_calls[id(l)] = res.layer_calls.get(id(l), 0) + 1
            src = inputs[0] if isinstance(inputs, tuple) else inputs
            res.keep.append(src)

    if leaves is None:
        leaves = [s for _, s in model.named_sublayers(include_self=True)
                  if not list(s.sublayers())]
    else:
        leaves = list(leaves)
    for s in leaves:
        hooks.append(s.register_forward_pre_hook(pre))
        hooks.append(s.register_forward_post_hook(post))

    # pre-hooks receive only POSITIONAL inputs (Layer.__call__, paddle
    # hook parity) — wrap each leaf's forward so tensors passed as
    # kwargs count as consumers too (depth == 1 inside a top-level
    # call: the pre-hook already incremented)
    wrapped_leaves = []

    def _wrap_forward(orig):
        def wrapped(*a, **kw):
            if depth[0] == 1 and kw:
                for v in kw.values():
                    jax.tree_util.tree_map(
                        res.consumed, v,
                        is_leaf=lambda t: isinstance(t, Tensor))
            return orig(*a, **kw)
        return wrapped

    for s in leaves:
        wrapped_leaves.append((s, s.__dict__.get("forward")))
        object.__setattr__(s, "forward", _wrap_forward(s.forward))

    def op_rec(name, args, kwargs, out):
        res.produced(out)
        if depth[0] == 0:
            for a in list(args) + list(kwargs.values()):
                jax.tree_util.tree_map(
                    res.consumed, a,
                    is_leaf=lambda v: isinstance(v, Tensor))
            res.events.append(("op", name, args, kwargs, out))

    was_training = model.training
    model.eval()
    prev_hook = _registry._ONNX_TRACE
    _registry._ONNX_TRACE = op_rec
    try:
        with _tape.no_grad():
            res.y = model(x)
    finally:
        _registry._ONNX_TRACE = prev_hook
        if was_training:
            model.train()
        for h in hooks:
            h.remove()
        for s, saved in wrapped_leaves:
            if saved is None:
                s.__dict__.pop("forward", None)
            else:
                object.__setattr__(s, "forward", saved)
    # the model's outputs are consumers too: a tensor that is RETURNED
    # must not be treated as exclusively feeding its one layer consumer
    # (walk the FULL structure — dicts/nested containers included)
    jax.tree_util.tree_map(res.consumed, res.y,
                           is_leaf=lambda t: isinstance(t, Tensor))
    return res
