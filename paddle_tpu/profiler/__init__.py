"""paddle_tpu.profiler — host+device tracing.

Reference: python/paddle/profiler/profiler.py:358 (Profiler with scheduler
windows, export:853) over the C++ RecordEvent/HostTracer/CudaTracer stack
(paddle/fluid/platform/profiler/).

TPU-native: device-side tracing is jax.profiler (XPlane -> TensorBoard /
Perfetto); the RecordEvent python annotation API is kept and forwards to
jax.profiler.TraceAnnotation so user marks appear inside the device trace.
Host-side spans are also timed in-process for the summary table.
"""
from __future__ import annotations

import contextlib
import enum
import os
import threading
import time
from collections import defaultdict
from typing import Callable, Iterable, Optional

import jax


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    CUSTOM_DEVICE = 2
    TPU = 3


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Reference make_scheduler: step -> state windows."""
    period = closed + ready + record

    def fn(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return fn


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """Returns an on_trace_ready callback exporting to ``dir_name``
    (jax writes xplane/trace-viewer files there)."""
    def handler(prof):
        prof._export_dir = dir_name
    return handler


_records = threading.local()
_stats_lock = threading.Lock()
_host_stats = defaultdict(lambda: [0, 0.0])  # name -> [count, total_s]


class RecordEvent:
    """User annotation span (reference: paddle.profiler.RecordEvent /
    C++ platform::RecordEvent). Times the host span and nests a
    jax.profiler.TraceAnnotation so the mark shows up on device traces."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._ann = None
        self._t0 = None

    def begin(self):
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()

    def end(self):
        if self._ann is not None:
            dt = time.perf_counter() - self._t0
            with _stats_lock:
                st = _host_stats[self.name]
                st[0] += 1
                st[1] += dt
            self._ann.__exit__(None, None, None)
            self._ann = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def host_statistics():
    """name -> {calls, total_ms, avg_ms} for RecordEvent spans."""
    with _stats_lock:
        return {k: {"calls": v[0], "total_ms": v[1] * 1e3,
                    "avg_ms": v[1] * 1e3 / max(v[0], 1)}
                for k, v in _host_stats.items()}


def reset_host_statistics():
    with _stats_lock:
        _host_stats.clear()


class Profiler:
    """paddle.profiler.Profiler-compatible facade over jax.profiler.

    with Profiler(scheduler=(2, 5)) as p:
        for batch in loader:
            step(batch)
            p.step()
    """

    def __init__(self, *, targets: Optional[Iterable] = None,
                 scheduler=None, on_trace_ready=None, record_shapes=False,
                 profile_memory=False, timer_only: bool = False,
                 emit_nvtx: bool = False, with_flops: bool = False):
        if isinstance(scheduler, tuple):
            start, end = scheduler
            scheduler = make_scheduler(closed=start, ready=0,
                                       record=end - start, repeat=1)
        self._scheduler = scheduler
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._export_dir = os.environ.get(
            "PADDLE_TPU_PROFILE_DIR", "/tmp/paddle_tpu_profile")
        self._step = 0
        self._state = ProfilerState.CLOSED
        self._tracing = False
        self._step_times = []
        self._last_step_t = None

    # -- lifecycle ----------------------------------------------------------
    def start(self):
        self._last_step_t = time.perf_counter()
        self._transition()
        return self

    def stop(self):
        if self._tracing:
            self._stop_trace()
        if self._on_trace_ready:
            self._on_trace_ready(self)

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        if self._last_step_t is not None:
            self._step_times.append(now - self._last_step_t)
        self._last_step_t = now
        self._step += 1
        self._transition()

    def _transition(self):
        state = (self._scheduler(self._step) if self._scheduler
                 else ProfilerState.RECORD)
        if self._timer_only:
            return
        should_trace = state in (ProfilerState.RECORD,
                                 ProfilerState.RECORD_AND_RETURN)
        if should_trace and not self._tracing:
            self._start_trace()
        elif not should_trace and self._tracing:
            self._stop_trace()
        self._state = state

    def _start_trace(self):
        try:
            jax.profiler.start_trace(self._export_dir)
            self._tracing = True
        except Exception:
            self._tracing = False  # e.g. trace already active

    def _stop_trace(self):
        try:
            jax.profiler.stop_trace()
        finally:
            self._tracing = False

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- reports ------------------------------------------------------------
    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        lines = ["Profiler summary", "-" * 60]
        if self._step_times:
            ts = self._step_times
            lines.append(
                f"steps: {len(ts)}  avg {1e3 * sum(ts) / len(ts):.2f} ms  "
                f"min {1e3 * min(ts):.2f}  max {1e3 * max(ts):.2f}")
        for name, st in sorted(host_statistics().items(),
                               key=lambda kv: -kv[1]["total_ms"]):
            lines.append(f"{name:<40} x{st['calls']:<6} "
                         f"total {st['total_ms']:.2f} ms  "
                         f"avg {st['avg_ms']:.3f} ms")
        out = "\n".join(lines)
        print(out)
        return out

    def export(self, path: str, format: str = "json"):
        """Traces are written by stop_trace to the profile dir; this
        records the requested destination for tooling parity."""
        self._export_dir = path


@contextlib.contextmanager
def profile(**kw):
    p = Profiler(**kw)
    p.start()
    try:
        yield p
    finally:
        p.stop()
