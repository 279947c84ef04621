"""Pass framework for the static-analysis subsystem.

Reference capability: the reference ships IR-level passes and runtime
enforcement (``paddle/pir`` pass infrastructure, ``phi/core/enforce.h``
check macros). The JAX-native counterpart analyses **jaxprs** — the
one IR every flagship program already lowers through — plus host-side
serving state (``kv_invariants.py``). This module is the shared
plumbing: a finding record, a pass protocol, and a report that the
``tools/graph_lint.py`` CLI and the tests consume identically.

A pass is a callable object with ``name`` / ``run(target) ->
List[Finding]``. Targets are :class:`GraphTarget` records (a traced
jaxpr plus the metadata passes need: declared compute dtype, which
outputs the caller donates/rebinds, how many batch slots the program
serves). Passes never run the program — everything here is tracing
plus host-side walks, so linting the flagship serving graphs costs
milliseconds, not XLA compiles.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

__all__ = ["Severity", "Finding", "GraphTarget", "LintPass",
           "LintReport", "PASS_REGISTRY", "register_pass",
           "default_passes", "run_passes", "trace_graph",
           "ExactnessContract", "RewritePass", "REWRITE_REGISTRY",
           "register_rewrite", "default_rewrites", "aval_nbytes"]


def aval_nbytes(aval) -> int:
    """Bytes of one abstract value (0 for token/effect avals without a
    dtype) — the ONE byte-accounting helper every pass uses (hbm peak,
    donation audit, sharding lint, planner cost model), so the passes
    cannot disagree on what a buffer weighs."""
    import numpy as np
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return 0
    n = int(np.prod(shape)) if shape else 1
    return n * np.dtype(dtype).itemsize

#: name -> LintPass subclass; every pass registers itself here so the
#: CLI (tools/graph_lint.py) and the tests build the same pass set —
#: a pass that exists but is wired nowhere is the vacuous-pass
#: anti-pattern in a new costume.
PASS_REGISTRY: Dict[str, type] = {}

#: name -> RewritePass subclass. Same contract as PASS_REGISTRY: the
#: rewrite suite (tools/graph_lint.py --suite rewrite), the rewriting
#: engine wrapper (serving) and the tests all build from this one
#: registry, so a rewrite that exists but is wired nowhere cannot
#: happen.
REWRITE_REGISTRY: Dict[str, type] = {}


def register_pass(cls):
    """Class decorator: add a LintPass subclass to ``PASS_REGISTRY``
    under its ``name``."""
    PASS_REGISTRY[cls.name] = cls
    return cls


def register_rewrite(cls):
    """Class decorator: add a RewritePass subclass to
    ``REWRITE_REGISTRY`` under its ``name``."""
    REWRITE_REGISTRY[cls.name] = cls
    return cls


def default_rewrites(names=None) -> List["RewritePass"]:
    """One instance of every registered rewrite (or of ``names``),
    ordered by ``priority`` (stable: registration order breaks ties).
    The rewriter hands each anchor to the FIRST rule that matches it,
    so bigger-subgraph passes (the decode tail swallows an rms-norm;
    the conv epilogue swallows a layout-normalizable conv) must sort
    ahead of the smaller passes they contain."""
    if names is None:
        rules = [cls() for cls in REWRITE_REGISTRY.values()]
    else:
        rules = [REWRITE_REGISTRY[n]() for n in names]
    return sorted(rules, key=lambda r: r.priority)


def default_passes(**ctor_kwargs) -> List["LintPass"]:
    """One instance of every registered pass, in registration order.
    ``ctor_kwargs[name]`` supplies per-pass constructor kwargs (e.g.
    ``{"recompile-hazard": {"ragged_limit": 2}})``."""
    return [cls(**ctor_kwargs.get(name, {}))
            for name, cls in PASS_REGISTRY.items()]


class Severity:
    ERROR = "error"      # invariant violated / silent-wrongness class
    WARNING = "warning"  # perf hazard, suspicious but not provably wrong
    INFO = "info"        # informational (counts, program inventories)

    ORDER = {ERROR: 0, WARNING: 1, INFO: 2}


@dataclass
class Finding:
    """One lint result: which pass, on which graph, what and where."""
    pass_name: str
    severity: str
    graph: str                 # target name (e.g. "llama.serving_tick[mixed]")
    message: str
    #: control-flow path to the offending eqn, e.g. (("scan","jaxpr"),)
    path: Tuple = ()

    def __str__(self) -> str:
        loc = "/".join(p[0] for p in self.path) or "top"
        return (f"[{self.severity}] {self.pass_name} @ {self.graph} "
                f"({loc}): {self.message}")


@dataclass
class GraphTarget:
    """A traced program plus the call-site facts passes need.

    ``donated_outputs``: indices into the jaxpr's flat outputs that the
    caller donates back in (pool arrays the engine rebinds) — they
    never cross to the host, so host-sync accounting excludes them.
    ``slots``: batch width of the program (decode-batch S), for
    per-slot byte budgets. ``steps_per_call``: decode steps one call
    advances (the fused block's k); host-pull budgets are per step.
    ``in_decode_loop``: the program IS a per-tick decode body — the
    host-sync pass applies its output-size budget only there.
    """
    name: str
    jaxpr: Any                              # jax.extend.core.ClosedJaxpr
    compute_dtype: Any = None               # declared model dtype
    donated_outputs: Tuple[int, ...] = ()
    slots: int = 1
    steps_per_call: int = 1
    in_decode_loop: bool = False
    meta: Dict[str, Any] = field(default_factory=dict)


def trace_graph(name: str, fn: Callable, args: Sequence,
                static_kwargs: Optional[Dict[str, Any]] = None,
                **target_kw) -> GraphTarget:
    """Trace ``fn(*args, **static_kwargs)`` to a :class:`GraphTarget`.
    ``args`` may be ShapeDtypeStructs — tracing is abstract, nothing
    executes."""
    import jax
    closed = jax.make_jaxpr(
        lambda *a: fn(*a, **(static_kwargs or {})))(*args)
    return GraphTarget(name=name, jaxpr=closed, **target_kw)


class LintPass:
    """Base class: subclasses set ``name`` and implement ``run``."""

    name: str = "pass"

    def run(self, target: GraphTarget) -> List[Finding]:
        raise NotImplementedError

    def finding(self, target: GraphTarget, message: str,
                severity: str = Severity.ERROR,
                path: Tuple = ()) -> Finding:
        return Finding(pass_name=self.name, severity=severity,
                       graph=target.name, message=message, path=path)


@dataclass
class ExactnessContract:
    """What a rewrite is allowed to change about the numbers.

    ``bitwise=True`` — the replacement is byte-identical (integer
    outputs, or a substitution proven to round identically).
    ``ulp=N`` — the replacement performs the same operations in the
    same association, but compiler clustering (FMA contraction, fusion
    boundaries) may round differently: outputs must be within N units-
    in-last-place of the OUTPUT dtype (the kernel-substitution
    contract). Otherwise the rewrite genuinely reassociates (e.g.
    moving a dequant scale across a matmul) and must pin
    ``rtol``/``atol``: close-enough-by-accident is not a contract.
    """
    bitwise: bool = False
    ulp: int = 0
    rtol: float = 0.0
    atol: float = 0.0

    def describe(self) -> str:
        if self.bitwise:
            return "bitwise"
        if self.ulp:
            return f"ulp<={self.ulp}"
        return f"rtol={self.rtol:g} atol={self.atol:g}"


class RewritePass:
    """Base class for graph rewrites (the optimizer counterpart of
    :class:`LintPass`). Subclasses declare:

    * ``name`` — registry key;
    * ``contract`` — the :class:`ExactnessContract` the verifier
      enforces before the rewrite is allowed to ship;
    * ``patterns()`` — anchor-variant list of :mod:`patterns` trees
      describing the subgraph to replace;
    * ``arg_names`` — which pattern captures feed the replacement, in
      call order;
    * ``build(statics)`` — the replacement callable taking the captured
      values; ``statics`` holds the ``Lit`` captures (Python numbers).
    * ``validate(match, jaxpr)`` — optional cross-binding check.

    The machinery that applies these lives in ``analysis/rewrite.py``;
    passes themselves stay declarative.
    """

    name: str = "rewrite"
    contract: ExactnessContract = ExactnessContract(bitwise=True)
    arg_names: Tuple[str, ...] = ()
    #: rule order handed to the rewriter — lower runs first; passes
    #: whose pattern CONTAINS another pass's pattern must sort lower
    #: (see :func:`default_rewrites`)
    priority: int = 100

    def patterns(self):
        raise NotImplementedError

    def build(self, statics: Dict[str, Any]) -> Callable:
        raise NotImplementedError

    def validate(self, match, jaxpr) -> bool:
        return True


@dataclass
class LintReport:
    findings: List[Finding] = field(default_factory=list)
    #: (pass, graph) pairs that ran — a pass that never ran is not a
    #: clean pass (the vacuous-pass lesson, ADVICE r5)
    ran: List[Tuple[str, str]] = field(default_factory=list)

    @property
    def errors(self) -> List[Finding]:
        return [f for f in self.findings
                if f.severity == Severity.ERROR]

    @property
    def ok(self) -> bool:
        return not self.errors

    def extend(self, other: "LintReport") -> None:
        self.findings.extend(other.findings)
        self.ran.extend(other.ran)

    def summary(self) -> str:
        n_err = len(self.errors)
        n_warn = sum(f.severity == Severity.WARNING for f in self.findings)
        return (f"{len(self.ran)} pass runs, {n_err} errors, "
                f"{n_warn} warnings")

    def to_dict(self) -> Dict[str, Any]:
        return {
            "ok": self.ok,
            "runs": len(self.ran),
            "findings": [
                {"pass": f.pass_name, "severity": f.severity,
                 "graph": f.graph, "message": f.message,
                 "path": ["/".join(p) for p in f.path]}
                for f in sorted(
                    self.findings,
                    key=lambda f: Severity.ORDER.get(f.severity, 9))],
        }


def run_passes(passes: Sequence[LintPass],
               targets: Sequence[GraphTarget]) -> LintReport:
    """Run every pass over every target; findings are accumulated, a
    pass raising is converted into an ERROR finding (a crashed linter
    must never read as a clean one)."""
    report = LintReport()
    for target in targets:
        for p in passes:
            try:
                found = p.run(target)
            except Exception as e:  # noqa: BLE001 - surfaced as finding
                found = [Finding(
                    pass_name=p.name, severity=Severity.ERROR,
                    graph=target.name,
                    message=f"pass crashed: {type(e).__name__}: {e}")]
            report.findings.extend(found)
            report.ran.append((p.name, target.name))
    return report
