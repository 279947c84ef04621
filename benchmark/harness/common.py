"""What every mode shares: the device check, the compile cache, the
compile counter, percentiles, the result line."""
from __future__ import annotations

import json
import os
import shutil
import sys

import jax
import jax.monitoring
import numpy as np

from .manifest import BENCH_DIR, ROOT

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(*a):
    print(*a, flush=True)


def require_devices(chips: int, platform: str = "tpu"):
    """The first touch of JAX. Exits non-zero unless ``jax.devices()``
    holds at least ``chips`` devices of ``platform``. Never sets a
    platform."""
    devs = jax.devices()
    if devs[0].platform != platform or len(devs) < chips:
        raise SystemExit(
            f"the benchmark needs {chips} {platform} device(s); "
            f"jax.devices() reports {len(devs)} x {devs[0].platform!r} "
            f"({devs[0].device_kind!r})")
    return devs[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout
    (``<checkout>/.jax_cache``), unless the environment names one."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


_compiles = [0]


def _on_event(event, duration, **_kw):
    if event == COMPILE_EVENT:
        _compiles[0] += 1


jax.monitoring.register_event_duration_secs_listener(_on_event)


class CompileCounter:
    """XLA compiles between ``arm()`` and ``disarm()`` (persistent-cache
    hits included: a hit is still a program the process had not
    warmed)."""

    def arm(self):
        self._start = _compiles[0]

    def disarm(self) -> int:
        return _compiles[0] - self._start


def start_trace() -> None:
    """The profiler, into a fresh ``trace_dir()``, without Python
    frames: the device lines are what is read, and the Python tracer
    slows the host that feeds the device."""
    shutil.rmtree(trace_dir(), ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir(), profiler_options=opts)


def pctl(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


def peak_memory_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def device_block(devs, **extra) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak_memory_bytes(devs),
            **extra}


def result_line(*, correct, attempted, failed, metrics, device,
                breakdown=None, compared=None) -> str:
    """The last line of stdout; ``compared`` (each number compared,
    beside its limit) comes last in it."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if compared is not None:
        out["compared"] = compared
    return json.dumps(out)


class Checks(list):
    """A run's comparisons: the list of passes (``all(checks)``), and in
    ``compared`` each number beside its limit, ``{name: [value,
    limit]}``, for the result line and the last lines of stderr."""

    def __init__(self):
        super().__init__()
        self.compared = {}

    def note(self, name: str, value: float, limit: float, ok: bool):
        self.append(bool(ok))
        self.compared[name] = [float(value), float(limit)]

    def to_stderr(self) -> None:
        for name, (value, limit) in self.compared.items():
            print(f"[compared] {name} = {value:.6g}  limit {limit:.6g}",
                  file=sys.stderr)
        sys.stderr.flush()


def check(name: str, value: float, limit: float, checks: Checks) -> None:
    """One number compared, printed beside its limit."""
    ok = bool(np.isfinite(value)) and value <= limit
    checks.note(name, value, limit, ok)
    log(f"[correct] {name} = {value:.6g}  limit {limit:.6g}  "
        f"{'ok' if ok else 'FAIL'}")


def trace_dir() -> str:
    return os.path.join(BENCH_DIR, ".trace")
