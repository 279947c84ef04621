"""Runtime kernel autotune cache + the persistent KForge winner store.

Reference: paddle/phi/kernels/autotune/ — algorithm selection by timing
(cuDNN algo search, transpose/layout autotune) with a per-process cache
keyed by op + shapes.

TPU-native shape: candidates are jax-traceable callables (different
Pallas block sizes, layouts, algorithm variants); the first call for a
given key times each candidate with a warm-up plus chained timed
iterations and caches the winner. All later calls dispatch straight to
the cached choice.

The KForge flywheel (PAPERS.md 2606.02963) rides a second, PERSISTENT
tier: ``tools/kernel_bench.py`` sweeps *record* the winning block
shapes per geometry (``record(kind, winner, **geom)``) into a JSON file
under ``$PADDLE_TPU_AUTOTUNE_DIR``, and the Pallas entry points
(``fused_rms_norm``, ``ragged_paged_attention``, the conv-epilogue
matmul) *look up* that store at call time (``lookup(kind, **geom)``).
A swept geometry therefore picks its searched tiling automatically; an
unswept one (or an unset env var, or a corrupt store) falls back to the
entry point's static default — never a crash, never a numerics change
(tilings partition the same arithmetic).

Timing: each candidate's window ends in ``block_until_ready``; use
``iters`` high enough that the deltas dominate the per-sync cost
(not measured on the chip). Tests exercise the machinery on the CPU.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax

_CACHE: Dict[Any, int] = {}
_STATS: Dict[Any, Tuple[float, ...]] = {}

#: in-memory mirror of the on-disk winner store, keyed by the dir it
#: was loaded from so tests (and long-lived processes pointed at a new
#: dir) reload instead of serving a stale mirror
_DISK: Optional[Dict[str, Dict[str, Any]]] = None
_DISK_FROM: Optional[str] = None

_ENV_DIR = "PADDLE_TPU_AUTOTUNE_DIR"
_STORE_FILE = "winners.json"
#: set to "0" to disable the audit-at-load gate (debugging escape
#: hatch; the default ON is what keeps a stale store from silently
#: applying an inadmissible tiling)
_ENV_AUDIT = "PADDLE_TPU_AUTOTUNE_AUDIT"


class AutotuneAuditError(RuntimeError):
    """``record(..., audit=True)`` refused a winner whose config fails
    the static kernel audit (KA001 VMEM / KA002 coverage) — the sweep
    measured something the kernel cannot actually serve."""


def _audit_on() -> bool:
    return os.environ.get(_ENV_AUDIT, "1").lower() not in ("0", "false",
                                                           "off")


def _audit_verdict(kind: str, geom: Dict[str, Any],
                   winner: Dict[str, Any]) -> Dict[str, Any]:
    """KA001/KA002 admission verdict from the kernel auditor. A fault
    INSIDE the auditor raises: swallowed, it reads as "no winner" and
    the store silently stops applying."""
    from ..analysis import kernel_audit as ka
    return ka.audit_config(kind, geom, winner)


def _kernel_signatures() -> Dict[str, Dict[str, Any]]:
    from ..analysis import kernel_audit as ka
    return ka.kernel_signatures()


def clear():
    """Drop BOTH tiers' in-process state (the on-disk store survives —
    the next ``lookup`` reloads it, which is what the fresh-process
    round-trip test exercises)."""
    global _DISK, _DISK_FROM
    _CACHE.clear()
    _STATS.clear()
    _DISK = None
    _DISK_FROM = None


def cache_info():
    return dict(_CACHE), dict(_STATS)


def make_key(op: str, args: Sequence[Any] = (),
             blocks: Tuple = (), extra: Tuple = ()) -> tuple:
    """Canonical in-process cache key: op name + every arg's shape AND
    dtype + the candidate block-shape tuple. Shape-only keys collide
    across bf16/int8 callers of the same geometry (and across candidate
    sets of different block shapes) — this helper is the one place the
    key schema lives so callers cannot under-key."""
    sig = tuple((tuple(getattr(a, "shape", ())),
                 str(getattr(a, "dtype", type(a).__name__)))
                for a in args)
    return (op, sig, tuple(blocks), tuple(extra))


# ---------------------------------------------------------------------------
# persistent winner store (the KForge flywheel)
# ---------------------------------------------------------------------------

def store_dir() -> Optional[str]:
    """The env-pointed winner-store directory, or None (persistence
    off, entry points use their static defaults)."""
    d = os.environ.get(_ENV_DIR)
    return d or None


def store_path() -> Optional[str]:
    d = store_dir()
    return os.path.join(d, _STORE_FILE) if d else None


def geometry_key(**geom) -> str:
    """Canonical string key for one kernel geometry: sorted fields,
    JSON-encoded, so writers and readers agree byte-for-byte. Dtypes
    must be passed as strings (``str(jnp.dtype(dt))``)."""
    return json.dumps({k: geom[k] for k in sorted(geom)},
                      separators=(",", ":"))


def _load_store() -> Dict[str, Dict[str, Any]]:
    """Lazy-load (and cache) the winner store. A missing or corrupt
    file degrades to an empty store — unswept behavior, not a crash."""
    global _DISK, _DISK_FROM
    path = store_path()
    if path is None:
        return {}
    if _DISK is not None and _DISK_FROM == path:
        return _DISK
    store: Dict[str, Dict[str, Any]] = {}
    try:
        with open(path) as f:
            raw = json.load(f)
        if isinstance(raw, dict):
            store = {str(k): dict(v) for k, v in raw.items()
                     if isinstance(v, dict)}
    except FileNotFoundError:
        pass
    except (OSError, ValueError, TypeError) as e:
        import warnings
        warnings.warn(f"autotune winner store {path} unreadable "
                      f"({type(e).__name__}: {e}); using defaults",
                      stacklevel=2)
    store = _validate_store(store, path)
    _DISK, _DISK_FROM = store, path
    return store


def _validate_store(store: Dict[str, Dict[str, Any]],
                    path: str) -> Dict[str, Dict[str, Any]]:
    """Schema-check loaded entries against the registered kernel
    signatures: an entry whose kind is no longer registered, whose
    geometry keys don't match the kernel's lookup kwargs, or whose
    winner carries unknown config keys is warned about and SKIPPED —
    a renamed kernel must not silently orphan (or worse, misapply) its
    winners."""
    if not store:
        return store
    sigs = _kernel_signatures()
    import warnings
    out: Dict[str, Dict[str, Any]] = {}
    for kind, per_kind in store.items():
        sig = sigs.get(kind)
        if sig is None:
            warnings.warn(
                f"autotune store {path}: kind {kind!r} matches no "
                f"registered kernel signature; skipping its "
                f"{len(per_kind)} entries", stacklevel=3)
            continue
        kept: Dict[str, Any] = {}
        for gkey, winner in per_kind.items():
            try:
                geom = json.loads(gkey)
            except ValueError:
                geom = None
            if (not isinstance(geom, dict)
                    or tuple(sorted(geom)) != tuple(sig["geom_keys"])):
                warnings.warn(
                    f"autotune store {path}: {kind} entry {gkey!r} "
                    f"does not match geometry keys "
                    f"{list(sig['geom_keys'])}; skipping", stacklevel=3)
                continue
            if (not isinstance(winner, dict) or not winner
                    or not set(winner) <= set(sig["config_keys"])):
                warnings.warn(
                    f"autotune store {path}: {kind} winner {winner!r} "
                    f"does not match config keys "
                    f"{list(sig['config_keys'])}; skipping",
                    stacklevel=3)
                continue
            kept[gkey] = winner
        if kept:
            out[kind] = kept
    return out


def raw_store() -> Dict[str, Dict[str, Any]]:
    """A copy of the loaded winner store, ``{kind: {geom_key:
    winner}}`` — the kernel auditor sweeps this to audit every stored
    geometry, and tests inspect it directly."""
    return {k: dict(v) for k, v in _load_store().items()}


def lookup(kind: str, **geom) -> Optional[Dict[str, Any]]:
    """The swept winner for ``kind`` at ``geom``, or None (caller falls
    back to its default tiling — the unswept path is bitwise-unchanged
    because block shape never changes the math, only the schedule).

    Audit-at-load: a stored winner whose geometry no longer passes the
    static kernel audit (KA001 VMEM / KA002 coverage) is ignored with a
    warning instead of silently applied — the flywheel's admission gate
    on the read side. Verdicts are cached per (kind, geom, config), so
    a hot entry audits once per process; set
    ``PADDLE_TPU_AUTOTUNE_AUDIT=0`` to disable."""
    entry = _load_store().get(kind)
    if not entry:
        return None
    win = entry.get(geometry_key(**geom))
    if not isinstance(win, dict):
        return None
    if _audit_on():
        v = _audit_verdict(kind, dict(geom), dict(win))
        if not v["ok"]:
            import warnings
            warnings.warn(
                f"autotune winner {win} for {kind} @ "
                f"{geometry_key(**geom)} fails the kernel audit "
                f"({','.join(v.get('rules', []))}: "
                f"{v.get('detail', '')}); ignoring it", stacklevel=2)
            return None
    return dict(win)


def record(kind: str, winner: Dict[str, Any], *, audit: bool = False,
           **geom) -> str:
    """Persist one sweep winner (``{"tile_n": 128, ...}``) for
    ``kind``/``geom``. Requires ``$PADDLE_TPU_AUTOTUNE_DIR``. Writes
    atomically (tmp + rename) so a concurrent reader never sees a torn
    file. Returns the store path.

    ``audit=True`` (what ``kernel_bench`` passes) runs the static
    kernel audit's admission rules (KA001/KA002) first and raises
    :class:`AutotuneAuditError` instead of writing a winner the kernel
    cannot serve — the flywheel's write-side gate."""
    path = store_path()
    if path is None:
        raise RuntimeError(
            f"set ${_ENV_DIR} to record autotune winners")
    if audit and _audit_on():
        v = _audit_verdict(kind, dict(geom), dict(winner))
        if not v["ok"]:
            raise AutotuneAuditError(
                f"refusing to record {winner} for {kind} @ "
                f"{geometry_key(**geom)}: fails kernel audit "
                f"({','.join(v.get('rules', []))}: "
                f"{v.get('detail', '')})")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    store = dict(_load_store())
    per_kind = dict(store.get(kind, {}))
    per_kind[geometry_key(**geom)] = dict(winner)
    store[kind] = per_kind
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(store, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    global _DISK, _DISK_FROM
    _DISK, _DISK_FROM = store, path
    return path


# ---------------------------------------------------------------------------
# in-process candidate timing
# ---------------------------------------------------------------------------

def _time_once(fn, args, iters: int) -> float:
    out = fn(*args)
    jax.block_until_ready(out)  # noqa: PT002 — timing harness
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)  # noqa: PT002 — timing harness
    return (time.perf_counter() - t0) / iters


def autotune(key, candidates: Sequence[Callable], args: tuple,
             iters: int = 10):
    """Run the fastest of ``candidates`` for ``args``; first call per
    ``key`` measures, later calls hit the cache.

    key: hashable — build it with :func:`make_key` so shapes, dtypes
    and block tuples are all in it. candidates: callables with
    identical semantics. Returns the chosen candidate's output.
    """
    if not candidates:
        raise ValueError("need at least one candidate")
    idx = _CACHE.get(key)
    if idx is None:
        times = []
        for fn in candidates:
            try:
                times.append(_time_once(fn, args, iters))
            except Exception:
                times.append(float("inf"))
        idx = int(min(range(len(times)), key=times.__getitem__))
        if times[idx] == float("inf"):
            raise RuntimeError(f"all autotune candidates failed for {key}")
        _CACHE[key] = idx
        _STATS[key] = tuple(times)
    return candidates[idx](*args)


def choose(key, candidates: Sequence[Callable], args: tuple,
           iters: int = 10) -> int:
    """Return the winning index for callers that bind the winner
    themselves; on a warm cache this is a pure lookup (no execution)."""
    idx = _CACHE.get(key)
    if idx is not None:
        return idx
    autotune(key, candidates, args, iters)
    return _CACHE[key]
