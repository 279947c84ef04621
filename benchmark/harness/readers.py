"""What the per-layer readers share. A reader is
``layer_metrics/<name>.py`` with ``read(ctx) -> float | None``; ``ctx``
holds ``cell``, ``model``, ``window`` (the harness's own samples, the
engine's histogram observations and counter deltas of the window),
``trace`` (the reduced device trace), ``stats``, ``late_ms``,
``devices``, ``end_to_end``."""
from __future__ import annotations

import json
import os

import numpy as np

from . import trace as T


def hist_pctl(ctx, name: str, q: float, scale: float = 1.0):
    vals = (ctx.get("window") or {}).get("hists", {}).get(name)
    if not vals:
        return None
    return float(np.percentile(np.asarray(vals, np.float64), q)) * scale


def per_tick_ms(ctx, seconds):
    ticks = (ctx.get("window") or {}).get("trace_ticks")
    if not ticks or seconds is None:
        return None
    return seconds / ticks * 1e3


def op_seconds(ctx, pattern: str):
    tr = ctx.get("trace")
    if not tr:
        return None
    s = T.name_sum(tr, pattern)
    return s if s > 0 else None


def idle_pct(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise SystemExit(f"no peak listed for device_kind {device_kind!r}; "
                         f"known: {sorted(k for k in table if k != 'source')}")
    return table[device_kind]
