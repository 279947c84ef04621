"""Static Pallas kernel auditor: VMEM / grid / DMA / accumulator proofs.

The "static proof first, runtime check second" discipline (recompile
enumeration, guarded-by lint) extended to the kernel tree: every Pallas
kernel in ``paddle_tpu/ops/pallas/`` registers its entry points and
representative geometries (module attributes ``AUDIT_KIND``,
``AUDIT_CONFIG_KEYS``, ``AUDIT_GEOMETRIES``, ``AUDIT_WAIVERS`` and the
``audit_launches(geom, config)`` hook), and the auditor proves four
admissibility rules per (kernel, geometry, config) WITHOUT executing or
compiling anything — it traces the launch with ``jax.make_jaxpr`` and
reads the actual ``pallas_call`` equation (grid, BlockSpecs, index-map
jaxprs, scratch avals, kernel jaxpr), so the audited facts are the
kernel's own, not a hand-maintained mirror:

  KA001  VMEM footprint — pipelined BlockSpec blocks (x2 for Mosaic's
         double buffering) + VMEM ``scratch_shapes`` summed per grid
         step against the per-core budget (16 MiB hardware minus a
         2 MiB compiler reserve).
  KA002  grid coverage & index-map bounds — every index map evaluated
         over the FULL grid (scalar-prefetch operands included, via
         state-discharge of the index-map jaxpr): block starts must
         stay in bounds, and every output tile must be written with
         exact coverage — no unwritten tile, and revisits of an output
         block only in consecutive grid steps (the sequential-
         accumulation pattern; an interleaved revisit is a silent
         overwrite under Mosaic's change-triggered writeback).
  KA003  DMA discipline — walk of the kernel jaxpr (through cond /
         while / scan / pjit) proving every ``dma_start`` has a
         matching ``dma_wait`` keyed on (destination ref, semaphore
         ref) root identity — slot indices deliberately excluded so a
         double-buffered walk that starts slot (t+1)%2 while waiting
         slot t%2 keys correctly — and that no read of a DMA
         destination buffer precedes the first wait on it in program
         order.
  KA004  accumulator dtype — when a kernel takes bf16/f16/int8
         operands, its reduction carries must be f32: scratch
         accumulators (refs both read and compute-written), loop
         carries, sum-reductions, and int8 dots must not accumulate
         in a narrower type.

Findings ride the shared :class:`~paddle_tpu.analysis.framework.Finding`
schema. Waivers mirror the concurrency lint's noqa discipline: a
kernel module declares ``AUDIT_WAIVERS = ((rule, match, reason), ...)``
— a reasonless waiver is rejected at registration, suppressions are
inventoried in the report, and a waiver that suppresses nothing is
itself an error (stale waiver), so the clean-tree pin re-audits the
waiver set every run.

The autotune flywheel gates on this module: ``ops/autotune.record``
refuses an audit-failing winner (KA001/KA002), ``ops/autotune.lookup``
skips a stored winner whose geometry no longer passes, and
``tools/kernel_bench.py`` stamps every sweep row with its verdict.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.extend import core as jax_core

from .framework import Finding, Severity

# per-core VMEM: 16 MiB on every deployed TPU generation (v4/v5e/v5p),
# minus a reserve for Mosaic's own spills/stack — the audit budget a
# kernel's steady-state footprint must fit
VMEM_BYTES_PER_CORE = 16 * 2 ** 20
VMEM_COMPILER_RESERVE = 2 * 2 ** 20
VMEM_AUDIT_BUDGET = VMEM_BYTES_PER_CORE - VMEM_COMPILER_RESERVE

#: refuse to enumerate absurd grids rather than hang the lint
MAX_GRID_POINTS = 1 << 18

RULES = {
    "KA001": "VMEM footprint exceeds the per-core budget",
    "KA002": "index map out of bounds / output coverage not exact",
    "KA003": "DMA start without matching wait, or read before wait",
    "KA004": "low-precision reduction carry (accumulator must be f32)",
}

#: dtypes whose presence as kernel operands arms KA004
_LOW_PRECISION = {"bfloat16", "float16", "int8"}

#: the registered kernel modules (paddle_tpu.ops.pallas.<name>)
_KERNEL_MODULES = (
    "ragged_paged_attention",
    "flash_attention",
    "grouped_matmul",
    "int8_matmul",
    "conv_epilogue",
    "fused_norm_rope",
    "ssd_update",
    "mla_paged_attention",
)

ALL_RULES = ("KA001", "KA002", "KA003", "KA004")


class KernelAuditError(Exception):
    """The audit itself could not run (trace failure, bad registration,
    unprovable scalar operand) — reported as an error, never silently
    passed."""


@dataclass(frozen=True)
class Waiver:
    rule: str
    match: str        # substring of the finding message (incl. kernel name)
    reason: str

    def __post_init__(self):
        if self.rule not in RULES:
            raise KernelAuditError(f"waiver for unknown rule {self.rule!r}")
        if not str(self.reason).strip():
            raise KernelAuditError(
                f"waiver {self.rule}({self.match!r}) needs a justification "
                f"reason, like every noqa in this tree")


@dataclass
class KernelSpec:
    """One registered kernel: its launch hook + audit metadata."""
    name: str
    kind: Optional[str]              # autotune store kind, or None
    config_keys: Tuple[str, ...]     # winner-dict keys record/lookup use
    geometries: Tuple[Dict[str, Any], ...]
    launches: Callable[..., Sequence]  # (geom, config) -> [(label, fn, args)]
    rules: Tuple[str, ...] = ALL_RULES
    waivers: Tuple[Waiver, ...] = ()
    geom_keys: Tuple[str, ...] = ()  # autotune geometry kwargs (sorted)


_REGISTRY: Optional[Dict[str, KernelSpec]] = None


def _build_registry() -> Dict[str, KernelSpec]:
    reg: Dict[str, KernelSpec] = {}
    for modname in _KERNEL_MODULES:
        mod = importlib.import_module(f"paddle_tpu.ops.pallas.{modname}")
        launches = getattr(mod, "audit_launches", None)
        geoms = getattr(mod, "AUDIT_GEOMETRIES", None)
        if launches is None or geoms is None:
            raise KernelAuditError(
                f"kernel module {modname} is not audit-registered: needs "
                f"AUDIT_GEOMETRIES + audit_launches(geom, config)")
        kind = getattr(mod, "AUDIT_KIND", None)
        waivers = tuple(Waiver(*w) for w in
                        getattr(mod, "AUDIT_WAIVERS", ()))
        geom_keys: Tuple[str, ...] = ()
        if kind is not None:
            geom_keys = tuple(sorted(getattr(mod, "AUDIT_GEOM_KEYS", ())))
            if not geom_keys:
                raise KernelAuditError(
                    f"{modname}: AUDIT_KIND={kind!r} needs AUDIT_GEOM_KEYS")
        reg[modname] = KernelSpec(
            name=modname, kind=kind,
            config_keys=tuple(getattr(mod, "AUDIT_CONFIG_KEYS", ())),
            geometries=tuple(dict(g) for g in geoms),
            launches=launches,
            rules=tuple(getattr(mod, "AUDIT_RULES", ALL_RULES)),
            waivers=waivers, geom_keys=geom_keys)
    return reg


def registry(refresh: bool = False) -> Dict[str, KernelSpec]:
    global _REGISTRY
    if _REGISTRY is None or refresh:
        _REGISTRY = _build_registry()
    return _REGISTRY


def kernel_signatures() -> Dict[str, Dict[str, Tuple[str, ...]]]:
    """``{autotune kind: {"geom_keys": (...), "config_keys": (...)}}``
    for every registered kernel with a persistent-store kind — the
    schema ``ops/autotune.py`` validates winners.json entries against."""
    out = {}
    for spec in registry().values():
        if spec.kind is not None:
            out[spec.kind] = {"geom_keys": spec.geom_keys,
                              "config_keys": spec.config_keys}
    return out


# ---------------------------------------------------------------------------
# trace extraction: find pallas_call eqns with concrete scalar operands
# ---------------------------------------------------------------------------

class _Unknown:
    __slots__ = ()

    def __repr__(self):
        return "<unknown>"


_UNKNOWN = _Unknown()

#: never eagerly materialize anything bigger than this during the
#: partial evaluation (scalar-prefetch metadata is tiny; tensors that
#: large are abstract by construction)
_MAX_EAGER_BYTES = 16 * 2 ** 20

#: higher-order primitives we recurse into rather than execute
_CALL_PRIMS = {"jit", "closed_call", "core_call", "remat", "remat2",
               "custom_jvp_call", "custom_vjp_call",
               "custom_vjp_call_jaxpr"}


@dataclass
class ExtractedCall:
    eqn: Any                     # the pallas_call JaxprEqn
    scalar_values: List[Any]     # concrete scalar-prefetch operands (or
    #                            # _UNKNOWN where the trace lost them)


def _closed_jaxpr_param(eqn):
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        cj = eqn.params.get(key)
        if cj is not None and hasattr(cj, "jaxpr"):
            return cj
    return None


def _partial_eval(closed, in_vals, calls):
    """Evaluate a jaxpr with a mix of concrete and _UNKNOWN inputs,
    executing only cheap known-input equations, recursing into call
    primitives, and recording every ``pallas_call`` with the concrete
    values of its invars (the scalar-prefetch operands are what KA002
    needs)."""
    jaxpr, consts = closed.jaxpr, closed.consts
    env: Dict[Any, Any] = {}

    def read(v):
        if isinstance(v, jax_core.Literal):
            return v.val
        return env.get(v, _UNKNOWN)

    def write(v, val):
        env[v] = val

    for cv, c in zip(jaxpr.constvars, consts):
        write(cv, c)
    for iv, val in zip(jaxpr.invars, in_vals):
        write(iv, val)

    for eqn in jaxpr.eqns:
        vals = [read(v) for v in eqn.invars]
        name = eqn.primitive.name
        if name == "pallas_call":
            calls.append(ExtractedCall(eqn=eqn, scalar_values=vals))
            outs = [_UNKNOWN] * len(eqn.outvars)
        elif name in _CALL_PRIMS:
            sub = _closed_jaxpr_param(eqn)
            if sub is not None and len(sub.jaxpr.invars) <= len(vals):
                # custom_* calls pass (consts..., args...); trailing
                # invars line up with trailing eqn invars
                outs = _partial_eval(
                    sub, vals[len(vals) - len(sub.jaxpr.invars):], calls)
            else:
                outs = [_UNKNOWN] * len(eqn.outvars)
        elif (all(v is not _UNKNOWN for v in vals)
              and name not in ("cond", "while", "scan")
              and all(_aval_bytes(ov.aval) <= _MAX_EAGER_BYTES
                      for ov in eqn.outvars)):
            try:
                res = eqn.primitive.bind(*vals, **eqn.params)
            except Exception:
                outs = [_UNKNOWN] * len(eqn.outvars)
            else:
                outs = list(res) if eqn.primitive.multiple_results else [res]
        else:
            outs = [_UNKNOWN] * len(eqn.outvars)
        for ov, val in zip(eqn.outvars, outs):
            write(ov, val)
    return [read(v) for v in jaxpr.outvars]


def _aval_bytes(aval) -> int:
    try:
        return int(np.prod(aval.shape)) * np.dtype(aval.dtype).itemsize
    except Exception:
        return 0


def extract_pallas_calls(fn, args) -> List[ExtractedCall]:
    """Trace ``fn(*args)`` (args may mix concrete arrays with
    ShapeDtypeStructs) and return every pallas_call equation with the
    concrete values reaching its invars.

    The partial evaluation runs under ``ensure_compile_time_eval`` so
    its eager binds stay concrete even when the audit is triggered
    inside an outer jit trace (autotune.lookup audits winners at trace
    time). The ``make_jaxpr`` trace itself must NOT — inside that
    context scalar closures materialise as captured-constant arrays,
    which pallas_call rejects."""
    closed = jax.make_jaxpr(fn)(*args)
    in_vals = []
    for a in jax.tree_util.tree_leaves(args):
        if isinstance(a, jax.ShapeDtypeStruct):
            in_vals.append(_UNKNOWN)
        else:
            in_vals.append(a)
    calls: List[ExtractedCall] = []
    with jax.ensure_compile_time_eval():
        _partial_eval(closed, in_vals, calls)
    if not calls:
        raise KernelAuditError("trace contains no pallas_call")
    return calls


# ---------------------------------------------------------------------------
# KA001 — VMEM footprint
# ---------------------------------------------------------------------------

def _block_dims(bm) -> Tuple[int, ...]:
    """Block shape in elements, squeezed dims as 1 (``block_shape``
    entries are ``pl.Blocked``/``pl.Element`` carrying ``block_size``,
    or ``pl.Squeezed``)."""
    return tuple(int(getattr(d, "block_size", 1)) for d in bm.block_shape)


def _block_memory_space(bm):
    return getattr(bm.transformed_block_aval, "memory_space", None)


def _is_pipelined_vmem(bm) -> bool:
    """True when the operand is windowed into VMEM by the pipeline (the
    default); ANY/SMEM operands stay in HBM/SMEM and cost no VMEM."""
    ms = _block_memory_space(bm)
    return ms is None or str(ms).lower() in ("vmem", "tpumemoryspace.vmem")


def vmem_footprint(call: ExtractedCall) -> Dict[str, Any]:
    """The per-grid-step VMEM bytes of one pallas_call: pipelined
    blocks (x2 — Mosaic double-buffers every windowed operand so the
    next block's copy overlaps compute) plus VMEM scratch (allocated
    once, not double-buffered)."""
    gm = call.eqn.params["grid_mapping"]
    blocks = []
    blocks_bytes = 0
    for bm in gm.block_mappings:
        nbytes = int(np.prod(_block_dims(bm))) * np.dtype(
            bm.array_aval.dtype).itemsize
        pipelined = _is_pipelined_vmem(bm)
        contrib = 2 * nbytes if pipelined else 0
        blocks_bytes += contrib
        blocks.append({"origin": str(bm.origin),
                       "block": list(_block_dims(bm)),
                       "dtype": str(bm.array_aval.dtype),
                       "bytes": nbytes, "pipelined": pipelined,
                       "vmem_bytes": contrib})
    scratch_bytes = 0
    sem_slots = 0
    kjaxpr = call.eqn.params["jaxpr"]
    n_lead = gm.num_index_operands + gm.num_inputs + gm.num_outputs
    for v in kjaxpr.invars[n_lead:]:
        aval = v.aval
        ms = str(getattr(aval, "memory_space", "")).lower()
        if "sem" in ms or "sem" in str(getattr(aval, "dtype", "")):
            sem_slots += int(np.prod(aval.shape)) if aval.shape else 1
        elif "smem" in ms:
            pass  # scalar scratch: SMEM, not VMEM
        else:
            scratch_bytes += (int(np.prod(aval.shape))
                              * np.dtype(aval.dtype).itemsize)
    return {"grid": [int(g) for g in gm.grid],
            "blocks": blocks,
            "blocks_bytes": int(blocks_bytes),
            "scratch_bytes": int(scratch_bytes),
            "sem_slots": int(sem_slots),
            "total_bytes": int(blocks_bytes + scratch_bytes),
            "budget_bytes": VMEM_AUDIT_BUDGET}


def _check_ka001(call: ExtractedCall, ctx: str, emit) -> Dict[str, Any]:
    fp = vmem_footprint(call)
    fp["ok"] = fp["total_bytes"] <= fp["budget_bytes"]
    if not fp["ok"]:
        emit("KA001",
             f"{ctx}: VMEM footprint {fp['total_bytes']} B "
             f"(blocks x2 {fp['blocks_bytes']} + scratch "
             f"{fp['scratch_bytes']}) exceeds budget "
             f"{fp['budget_bytes']} B")
    return fp


# ---------------------------------------------------------------------------
# KA002 — grid coverage & index-map bounds
# ---------------------------------------------------------------------------

def _grid_index_arrays(grid) -> List[np.ndarray]:
    """Flat row-major enumeration of the grid (last dim innermost —
    Pallas's iteration order), one int32 array per grid dim."""
    mesh = np.meshgrid(*[np.arange(g, dtype=np.int32) for g in grid],
                       indexing="ij")
    return [m.reshape(-1) for m in mesh]


def _discharged_index_map(bm):
    from jax._src.state.discharge import discharge_state
    cj = bm.index_map_jaxpr
    return discharge_state(cj.jaxpr, cj.consts)


def _eval_index_map(bm, grid, scalar_values, ctx: str) -> np.ndarray:
    """Evaluate one block's index map over the full grid. Returns
    ``[n_steps, n_block_dims]`` int64 block indices."""
    n_steps = int(np.prod(grid)) if grid else 1
    idx_arrays = _grid_index_arrays(grid)
    dj, consts = _discharged_index_map(bm)
    n_grid = len(grid)
    n_out = len(bm.block_shape)
    scalar_args = []
    for k, aval in enumerate(dj.invars[n_grid:]):
        val = (scalar_values[k] if k < len(scalar_values) else _UNKNOWN)
        if val is _UNKNOWN:
            # the map may not actually read this operand; zeros are
            # fine then — but if it does, the result would be wrong,
            # so require concreteness when the operand is used
            used = any(v is dj.invars[n_grid + k]
                       for eqn in dj.eqns for v in eqn.invars)
            if used:
                raise KernelAuditError(
                    f"{ctx}: index map reads scalar-prefetch operand "
                    f"#{k} but its value was not concrete at trace "
                    f"time — pass it as a concrete array in "
                    f"audit_launches")
            val = np.zeros(aval.aval.shape, np.dtype(aval.aval.dtype))
        scalar_args.append(np.asarray(val))
    if not dj.eqns:
        # fast path: pure pass-through maps (the common case) — outputs
        # are grid indices or literals, no tracing needed
        outs = []
        for ov in dj.outvars[:n_out]:
            if isinstance(ov, jax_core.Literal):
                outs.append(np.full(n_steps, int(ov.val), np.int64))
            else:
                pos = dj.invars.index(ov)
                outs.append(idx_arrays[pos].astype(np.int64))
        return np.stack(outs, axis=-1)

    def one(ij):
        res = jax.core.eval_jaxpr(dj, consts, *ij, *scalar_args)
        return [jnp.asarray(r, jnp.int32) for r in res[:n_out]]

    with jax.ensure_compile_time_eval():
        stacked = jax.vmap(one)(tuple(jnp.asarray(a) for a in idx_arrays))
    return np.stack([np.asarray(s, np.int64) for s in stacked], axis=-1)


def _check_ka002(call: ExtractedCall, ctx: str, emit) -> int:
    gm = call.eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    n_steps = int(np.prod(grid)) if grid else 1
    if n_steps > MAX_GRID_POINTS:
        raise KernelAuditError(
            f"{ctx}: grid {grid} has {n_steps} steps > "
            f"{MAX_GRID_POINTS}; register a smaller representative "
            f"geometry")
    ndg = int(getattr(gm, "num_dynamic_grid_bounds", 0))
    scalars = call.scalar_values[ndg:ndg + gm.num_index_operands]
    checked = 0
    for bm in gm.block_mappings:
        origin = str(bm.origin)
        is_output = origin.startswith("output")
        if not _is_pipelined_vmem(bm) and not is_output:
            continue  # ANY-space: the kernel indexes it manually (DMA)
        bctx = f"{ctx} {origin}"
        idx = _eval_index_map(bm, grid, scalars, bctx)
        checked += 1
        bdims = np.array(_block_dims(bm), np.int64)
        adims = np.array(bm.array_aval.shape, np.int64)
        starts = idx * bdims
        bad_lo = starts < 0
        bad_hi = starts + bdims > adims
        if bad_lo.any() or bad_hi.any():
            step = int(np.argwhere((bad_lo | bad_hi).any(axis=1))[0][0])
            emit("KA002",
                 f"{bctx}: index map leaves bounds at grid step {step} "
                 f"(block index {idx[step].tolist()}, block "
                 f"{bdims.tolist()}, array {adims.tolist()})")
            continue
        if is_output:
            n_tiles_dim = -(-adims // bdims)  # ceil
            want = int(np.prod(n_tiles_dim))
            flat = np.ravel_multi_index(idx.T, n_tiles_dim)
            seen = np.unique(flat)
            if len(seen) != want:
                emit("KA002",
                     f"{bctx}: output coverage not exact — "
                     f"{len(seen)}/{want} tiles written (unwritten "
                     f"tiles would hold garbage)")
                continue
            # revisits must be consecutive in grid order: under the
            # change-triggered writeback, block (m,n) revisited at
            # non-adjacent steps is flushed then silently overwritten
            change = np.flatnonzero(np.diff(flat) != 0)
            n_runs = len(change) + 1
            if n_runs != want:
                first_bad = int(change[np.argmax(
                    np.diff(np.concatenate([[0], change])) >= 0)])
                emit("KA002",
                     f"{bctx}: output block revisited in non-"
                     f"consecutive grid steps ({n_runs} write runs for "
                     f"{want} tiles, e.g. around step {first_bad}) — "
                     f"interleaved revisits silently overwrite")
    return checked


# ---------------------------------------------------------------------------
# kernel-jaxpr walk shared by KA003 / KA004
# ---------------------------------------------------------------------------

@dataclass
class _KernelEvent:
    kind: str                    # dma_start | dma_wait | get | put | loop
    roots: Tuple[int, ...] = ()  # kernel invar indices of the ref args
    lits: Tuple = ()             # static literal operands (slot indices)
    aval: Any = None


def _walk_kernel(jaxpr, env, events: List[_KernelEvent]):
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        roots = tuple(env[v] for v in eqn.invars
                      if not isinstance(v, jax_core.Literal) and v in env)
        lits = tuple(v.val for v in eqn.invars
                     if isinstance(v, jax_core.Literal)
                     and np.ndim(v.val) == 0)
        if name in ("dma_start", "dma_wait"):
            events.append(_KernelEvent(name, roots, lits))
        elif name == "get":
            events.append(_KernelEvent("get", roots, lits))
        elif name in ("swap", "addupdate", "masked_swap"):
            events.append(_KernelEvent("put", roots, lits))
        elif name in ("reduce_sum", "cumsum", "cumlogsumexp"):
            events.append(_KernelEvent(
                "reduce", (), (), eqn.invars[0].aval))
        elif name == "dot_general":
            events.append(_KernelEvent(
                "dot", (),
                (str(eqn.invars[0].aval.dtype),
                 str(eqn.invars[1].aval.dtype)),
                eqn.outvars[0].aval))
        subs = []
        if name == "cond":
            for br in eqn.params["branches"]:
                subs.append((br.jaxpr, list(eqn.invars[1:])))
        elif name == "while":
            cn = eqn.params["cond_nconsts"]
            bn = eqn.params["body_nconsts"]
            carry = list(eqn.invars[cn + bn:])
            events.append(_KernelEvent(
                "carry", (), (), [v.aval for v in carry]))
            cj, bj = eqn.params["cond_jaxpr"], eqn.params["body_jaxpr"]
            subs.append((cj.jaxpr, list(eqn.invars[:cn]) + carry))
            subs.append((bj.jaxpr, list(eqn.invars[cn:cn + bn]) + carry))
        elif name == "scan":
            nc = eqn.params["num_consts"]
            ncar = eqn.params["num_carry"]
            events.append(_KernelEvent(
                "carry", (), (),
                [v.aval for v in eqn.invars[nc:nc + ncar]]))
            subs.append((eqn.params["jaxpr"].jaxpr, list(eqn.invars)))
        else:
            sub = _closed_jaxpr_param(eqn)
            if sub is not None and len(sub.jaxpr.invars) <= len(eqn.invars):
                subs.append((sub.jaxpr,
                             list(eqn.invars)[-len(sub.jaxpr.invars):]))
        for sjaxpr, outer in subs:
            senv = {}
            for iv, ov in zip(sjaxpr.invars, outer):
                if (not isinstance(ov, jax_core.Literal)) and ov in env:
                    senv[iv] = env[ov]
            _walk_kernel(sjaxpr, senv, events)


def _kernel_events(call: ExtractedCall) -> List[_KernelEvent]:
    kjaxpr = call.eqn.params["jaxpr"]
    env = {v: i for i, v in enumerate(kjaxpr.invars)}
    events: List[_KernelEvent] = []
    _walk_kernel(kjaxpr, env, events)
    return events


def _ref_ranges(call: ExtractedCall):
    gm = call.eqn.params["grid_mapping"]
    n_idx = gm.num_index_operands
    n_in = gm.num_inputs
    n_out = gm.num_outputs
    n_total = len(call.eqn.params["jaxpr"].invars)
    return {"scalar": range(0, n_idx),
            "input": range(n_idx, n_idx + n_in),
            "output": range(n_idx + n_in, n_idx + n_in + n_out),
            "scratch": range(n_idx + n_in + n_out, n_total)}


# ---------------------------------------------------------------------------
# KA003 — DMA discipline
# ---------------------------------------------------------------------------

def _check_ka003(call: ExtractedCall, ctx: str, emit) -> int:
    events = _kernel_events(call)
    kjaxpr = call.eqn.params["jaxpr"]

    def dma_key(ev):
        # (dst root, sem root): a start and its wait bind the same
        # destination buffer and semaphore. Pairing is at buffer
        # granularity, not unrolled-slot granularity — double-buffered
        # kernels start slot (t+1)%2 and wait slot t%2 with traced
        # indices, which slot-exact keys would falsely flag.
        refs = [r for r in ev.roots
                if hasattr(kjaxpr.invars[r].aval, "memory_space")]
        return tuple(refs[1:]) if len(refs) >= 2 else tuple(refs)

    starts: Dict[Tuple, int] = {}
    waited: Dict[Tuple, int] = {}
    dst_roots = set()
    first_wait_pos: Dict[int, int] = {}
    n_pairs = 0
    for pos, ev in enumerate(events):
        key = dma_key(ev) if ev.kind in ("dma_start", "dma_wait") else None
        if ev.kind == "dma_start":
            n_pairs += 1
            starts[key] = starts.get(key, 0) + 1
            if key:
                dst_roots.add(key[0])
        elif ev.kind == "dma_wait":
            waited[key] = waited.get(key, 0) + 1
            if key:
                first_wait_pos.setdefault(key[0], pos)
    for key, n in starts.items():
        if waited.get(key, 0) == 0:
            emit("KA003",
                 f"{ctx}: dma_start on destination/semaphore "
                 f"{key} has no matching dma_wait — the copy may "
                 f"still be in flight when its buffer is read")
    # read-before-wait: the first get on a DMA destination must come
    # after some wait on that destination in program order
    for pos, ev in enumerate(events):
        if ev.kind == "get" and ev.roots and ev.roots[0] in dst_roots:
            root = ev.roots[0]
            w = first_wait_pos.get(root)
            if w is None or w > pos:
                aval = kjaxpr.invars[root].aval
                emit("KA003",
                     f"{ctx}: read of DMA destination buffer "
                     f"{aval} precedes any dma_wait on it")
            break
    return n_pairs


# ---------------------------------------------------------------------------
# KA004 — accumulator dtype
# ---------------------------------------------------------------------------

def _is_low_precision(dtype) -> bool:
    return str(np.dtype(dtype)) in _LOW_PRECISION


def _np_dtype(aval):
    """The aval's numpy dtype, or None for non-data types (semaphores
    carry a 'dma_sem' pseudo-dtype numpy cannot interpret)."""
    try:
        return np.dtype(getattr(aval, "dtype", None))
    except TypeError:
        return None


def _is_float(dt) -> bool:
    # jnp.issubdtype, not np: bf16 is an ml_dtypes extension type that
    # numpy does not classify under np.floating (operates on dtypes,
    # never on traced values)
    return jnp.issubdtype(dt, jnp.floating)


def _check_ka004(call: ExtractedCall, ctx: str, emit) -> int:
    gm = call.eqn.params["grid_mapping"]
    kjaxpr = call.eqn.params["jaxpr"]
    low = any(_is_low_precision(bm.array_aval.dtype)
              for bm in gm.block_mappings)
    if not low:
        return 0
    events = _kernel_events(call)
    ranges = _ref_ranges(call)
    got_get, got_put = set(), set()
    checks = 0
    for ev in events:
        if ev.kind == "get" and ev.roots:
            got_get.add(ev.roots[0])
        elif ev.kind == "put" and ev.roots:
            got_put.add(ev.roots[0])
    for root in ranges["scratch"]:
        aval = kjaxpr.invars[root].aval
        dt = _np_dtype(aval)
        if dt is None or not _is_float(dt):
            continue
        checks += 1
        if (root in got_get and root in got_put
                and dt.itemsize < 4):
            emit("KA004",
                 f"{ctx}: scratch accumulator {aval} is read-modify-"
                 f"written in {dt} — reduction carries must be f32 "
                 f"when kernel operands are bf16/int8")
    for ev in events:
        if ev.kind == "carry":
            for aval in ev.aval:
                dt = _np_dtype(aval)
                if dt is not None and _is_float(dt):
                    checks += 1
                    if dt.itemsize < 4:
                        emit("KA004",
                             f"{ctx}: loop carry {aval} accumulates in "
                             f"{dt} — flash/matmul carries must be f32")
        elif ev.kind == "reduce":
            dt = np.dtype(ev.aval.dtype)
            if _is_float(dt):
                checks += 1
                if dt.itemsize < 4:
                    emit("KA004",
                         f"{ctx}: sum-reduction over {dt} operand — "
                         f"softmax/reduction sums must run in f32")
        elif ev.kind == "dot":
            in_dts = ev.lits
            out_dt = np.dtype(ev.aval.dtype)
            if all(d == "int8" for d in in_dts):
                checks += 1
                if out_dt.itemsize < 4:
                    emit("KA004",
                         f"{ctx}: int8xint8 dot accumulates in "
                         f"{out_dt} — needs "
                         f"preferred_element_type=f32/int32")
    return checks


# ---------------------------------------------------------------------------
# per-launch / per-kernel drivers
# ---------------------------------------------------------------------------

_RULE_FNS = {"KA001": _check_ka001, "KA002": _check_ka002,
             "KA003": _check_ka003, "KA004": _check_ka004}


def audit_callable(kernel: str, label: str, fn, args,
                   rules: Sequence[str] = ALL_RULES,
                   waivers: Sequence[Waiver] = ()):
    """Audit one traceable launch. Returns ``(findings, suppressed,
    vmem_rows, rule_evals)`` — findings as :class:`Finding`, one vmem
    table row per pallas_call."""
    findings: List[Finding] = []
    suppressed: List[Dict[str, str]] = []
    vmem_rows: List[Dict[str, Any]] = []
    rule_evals = {r: 0 for r in ALL_RULES}

    def emitter(rule):
        def emit(r, message):
            for w in waivers:
                if w.rule == r and w.match in message:
                    suppressed.append({"rule": r, "message": message,
                                       "match": w.match,
                                       "reason": w.reason})
                    return
            findings.append(Finding(
                pass_name=f"kernel-audit/{r}", severity=Severity.ERROR,
                graph=f"{kernel}:{label}", message=message))
        return emit

    calls = extract_pallas_calls(fn, args)
    for ci, call in enumerate(calls):
        ctx = f"{kernel}:{label}" + (f"#call{ci}" if len(calls) > 1 else "")
        for rule in rules:
            res = _RULE_FNS[rule](call, ctx, emitter(rule))
            if rule == "KA001":
                row = dict(res)
                row.update({"kernel": kernel, "launch": label})
                vmem_rows.append(row)
                rule_evals[rule] += 1
            else:
                rule_evals[rule] += int(res)
    return findings, suppressed, vmem_rows, rule_evals


def _spec_launches(spec: KernelSpec, geom: Dict[str, Any],
                   config: Optional[Dict[str, Any]]):
    launches = spec.launches(dict(geom), dict(config) if config else None)
    if not launches:
        raise KernelAuditError(
            f"{spec.name}: audit_launches returned no launches for "
            f"{geom}")
    return launches


def audit_kernel(name: str, geom: Dict[str, Any],
                 config: Optional[Dict[str, Any]] = None,
                 rules: Optional[Sequence[str]] = None):
    """Audit one registered kernel at one geometry (and optional
    explicit winner config). Returns the same tuple as
    :func:`audit_callable`, aggregated over the geometry's launches."""
    spec = registry()[name]
    use_rules = tuple(rules) if rules is not None else spec.rules
    findings, suppressed, vmem, evals = [], [], [], \
        {r: 0 for r in ALL_RULES}
    for label, fn, args in _spec_launches(spec, geom, config):
        f, s, v, e = audit_callable(name, label, fn, args,
                                    rules=use_rules,
                                    waivers=spec.waivers)
        findings += f
        suppressed += s
        for row in v:
            row["geometry"] = dict(geom)
            if config:
                row["config"] = dict(config)
        vmem += v
        for r, n in e.items():
            evals[r] += n
    return findings, suppressed, vmem, evals


# the flywheel gate caches verdicts: autotune.lookup audits at most
# once per (kind, geometry, config) per process
_VERDICT_CACHE: Dict[Tuple, Dict[str, Any]] = {}

#: the admission rules a winner config must pass to be recorded or
#: applied — KA001/KA002 are config-dependent; KA003/KA004 are
#: properties of the kernel body, covered by the clean-tree pin
GATE_RULES = ("KA001", "KA002")


def audit_config(kind: str, geom: Dict[str, Any],
                 config: Optional[Dict[str, Any]],
                 use_cache: bool = True) -> Dict[str, Any]:
    """The flywheel admission verdict for one autotune winner:
    ``{"ok": bool, "rules": [rule, ...], "detail": str}``. Unknown
    kinds fail closed with rule ``unregistered``; a launch the auditor
    cannot trace (``KernelAuditError``) fails with rule ``build``. Any
    other exception is a fault in the auditor or the kernel and
    propagates — it must not read as "this winner is inadmissible"."""
    key = (kind, tuple(sorted((k, str(v)) for k, v in geom.items())),
           tuple(sorted((k, str(v)) for k, v in (config or {}).items())))
    if use_cache and key in _VERDICT_CACHE:
        return dict(_VERDICT_CACHE[key])
    spec = next((s for s in registry().values() if s.kind == kind), None)
    if spec is None:
        verdict = {"ok": False, "rules": ["unregistered"],
                   "detail": f"kind {kind!r} has no registered kernel"}
    else:
        try:
            findings, _, _, _ = audit_kernel(
                spec.name, geom, config, rules=GATE_RULES)
        except KernelAuditError as e:
            verdict = {"ok": False, "rules": ["build"],
                       "detail": f"{type(e).__name__}: {e}"}
        else:
            rules = sorted({f.pass_name.split("/")[-1] for f in findings})
            verdict = {"ok": not findings, "rules": rules,
                       "detail": "; ".join(f.message for f in findings[:2])}
    _VERDICT_CACHE[key] = dict(verdict)
    return verdict


def clear_verdict_cache():
    _VERDICT_CACHE.clear()


def _store_geometries(spec: KernelSpec):
    """Every geometry recorded for this kernel in the persistent
    autotune store (with its winner config) — the swept configs the
    flywheel would actually apply."""
    if spec.kind is None:
        return []
    import json

    from paddle_tpu.ops import autotune as at
    raw = at.raw_store()
    out = []
    for gkey, win in raw.get(spec.kind, {}).items():
        try:
            geom = json.loads(gkey)
        except ValueError:
            continue
        if isinstance(geom, dict) and isinstance(win, dict):
            out.append((geom, win))
    return out


def run_kernel_audit(include_store: bool = True) -> Dict[str, Any]:
    """The ``graph_lint --suite kernels`` entry: audit every registered
    kernel over its registered geometries (plus, when a persistent
    autotune store is configured, every swept geometry/winner in it).
    """
    findings: List[Finding] = []
    suppressed: List[Dict[str, str]] = []
    vmem: List[Dict[str, Any]] = []
    errors: List[str] = []
    rule_evals = {r: 0 for r in ALL_RULES}
    n_launches = 0
    try:
        reg = registry()
    except Exception as e:
        return {"ok": False, "kernels": [], "launches": 0, "vmem": [],
                "by_rule": {}, "rule_evals": rule_evals, "findings": [],
                "suppressed": [], "stale_waivers": [],
                "errors": [f"registry: {type(e).__name__}: {e}"]}
    for name, spec in reg.items():
        jobs = [(g, None) for g in spec.geometries]
        if include_store:
            try:
                jobs += _store_geometries(spec)
            except Exception as e:
                errors.append(f"{name}: store geometries unreadable: "
                              f"{type(e).__name__}: {e}")
        for geom, config in jobs:
            n_launches += 1
            try:
                f, s, v, e = audit_kernel(name, geom, config)
            except Exception as exc:
                errors.append(f"{name} @ {geom}: "
                              f"{type(exc).__name__}: {exc}")
                continue
            findings += f
            suppressed += s
            vmem += v
            for r, n in e.items():
                rule_evals[r] += n
    # stale-waiver discipline: a waiver that suppressed nothing across
    # the whole run is dead weight hiding a future regression
    stale = []
    used = {(s["rule"], s["match"]) for s in suppressed}
    for name, spec in reg.items():
        for w in spec.waivers:
            if (w.rule, w.match) not in used:
                stale.append({"kernel": name, "rule": w.rule,
                              "match": w.match, "reason": w.reason})
    by_rule = {r: 0 for r in ALL_RULES}
    for f in findings:
        by_rule[f.pass_name.split("/")[-1]] += 1
    return {
        "ok": not findings and not errors and not stale,
        "kernels": sorted(reg),
        "launches": n_launches,
        "vmem": vmem,
        "by_rule": by_rule,
        "rule_evals": rule_evals,
        "findings": [{"pass": f.pass_name, "severity": f.severity,
                      "graph": f.graph, "message": f.message}
                     for f in findings],
        "suppressed": suppressed,
        "stale_waivers": stale,
        "errors": errors,
    }
