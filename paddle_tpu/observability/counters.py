"""Process-wide step counters: what a COMPILED program counts on the
device and nobody holds a handle to.

A serving engine hands its per-tick counts back beside the tokens and
adds them to its own metrics. A train step has no such owner: a caller
may compile it, call the executable and keep nothing else (the
benchmark's harness does). So a step that counts something sends the
counts out by ONE unordered host callback a step (``emit``: a handful
of scalars), and they land here, stamped with the host's monotonic
clock as they arrive — which is when the device reached that point of
the step, not when the host dispatched it.

    emit("train", names, values)          inside a jitted function
    step_counters().totals("train")       {name: sum}, {"steps": n}
    step_counters().since("train", t)     the same over records from t on
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Dict, Optional, Sequence

import jax
import numpy as np

__all__ = ["StepCounters", "step_counters", "emit"]


class StepCounters:
    """A bounded ring of ``(arrival time, {name: value})`` a group."""

    def __init__(self, capacity: int = 65536):
        self._lock = threading.Lock()
        self._records: Dict[str, collections.deque] = {}
        self._capacity = capacity

    def add(self, group: str, values: Dict[str, int],
            at: Optional[float] = None) -> None:
        rec = (time.monotonic() if at is None else at, dict(values))
        with self._lock:
            self._records.setdefault(
                group, collections.deque(maxlen=self._capacity)).append(rec)

    def since(self, group: str, t0: float = float("-inf")) -> Dict[str, int]:
        """Sums over the records that arrived from ``t0`` (monotonic
        seconds) on, and how many they were under ``steps``."""
        with self._lock:
            recs = [v for t, v in self._records.get(group, ()) if t >= t0]
        out: Dict[str, int] = {"steps": len(recs)}
        for v in recs:
            for k, n in v.items():
                out[k] = out.get(k, 0) + n
        return out

    def totals(self, group: str) -> Dict[str, int]:
        return self.since(group)

    def clear(self) -> None:
        with self._lock:
            self._records.clear()


_PROCESS = StepCounters()


def step_counters() -> StepCounters:
    """The process's registry."""
    return _PROCESS


def emit(group: str, names: Sequence[str], values) -> None:
    """Inside a traced function: send ``values [len(names)]`` (an int
    array) to the process's registry by one UNORDERED host callback:
    nothing in the program waits for it."""
    names = tuple(names)

    def land(vals):
        vals = np.asarray(vals).reshape(-1)
        _PROCESS.add(group, {n: int(v) for n, v in zip(names, vals)})

    jax.debug.callback(land, values, ordered=False)
