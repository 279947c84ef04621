"""Host-sync lint pass: device→host traffic in tick/decode loops.

The bug class PR 2's in-graph sampling fixed: an all-greedy decode
tick used to pull ``[S, V]`` f32 logits to the host every step (V·4
bytes per slot per step over the host link) when the step
only needed ``[S, 1]`` i32 tokens — a 1000x host-transfer tax that no
test catches because the tokens are still correct. Two statically
checkable symptoms:

* **callbacks** (error): ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` equations anywhere in a decode-loop graph. A
  callback inside the per-tick program is a host round-trip per step
  (and under ``lax.scan`` it serializes the whole loop on the host).
  Outside decode loops callbacks are reported as warnings — legal, but
  worth eyes.
* **oversized host pull** (error): the program's non-donated outputs —
  what the host actually fetches per call — exceed a per-slot,
  per-step byte budget. The engine donates and rebinds the KV pools,
  so the real pull is everything else; a ``[S, V]`` f32 logits output
  blows the default 64-byte budget ~1000x while the fused block's
  ``[S, k]`` i32 tokens cost 4.

The output-size rule only applies to targets marked
``in_decode_loop`` — prefill programs legitimately return logits once
per prompt, and charging them a per-step budget would be noise.
"""
from __future__ import annotations

from typing import List

import numpy as np

from ..core.graph_trace import iter_jaxpr_eqns
from .framework import (Finding, GraphTarget, LintPass, Severity,
                        register_pass)

__all__ = ["HostSyncPass"]

_CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                   "callback")
_LOOP_PRIMS = {"scan", "while", "fori_loop"}


def _in_loop(path) -> bool:
    return any(frame[0] in _LOOP_PRIMS for frame in path)


@register_pass
class HostSyncPass(LintPass):
    name = "host-sync"

    def __init__(self, max_bytes_per_slot_step: int = 64):
        self.max_bytes = int(max_bytes_per_slot_step)

    def run(self, target: GraphTarget) -> List[Finding]:
        findings: List[Finding] = []
        closed = target.jaxpr

        # ---- callback scan ------------------------------------------
        for path, eqn in iter_jaxpr_eqns(closed):
            prim = eqn.primitive.name
            if not any(prim == c or prim.endswith("_callback")
                       for c in _CALLBACK_PRIMS):
                continue
            in_loop = _in_loop(path)
            hot = target.in_decode_loop or in_loop
            where = "inside a traced loop body" if in_loop \
                else "in the program"
            findings.append(self.finding(
                target,
                f"host callback `{prim}` {where} — every execution is "
                f"a device→host round-trip"
                + (" serializing the decode loop" if hot else ""),
                severity=Severity.ERROR if hot else Severity.WARNING,
                path=path))

        # ---- host-pull budget (decode-loop programs only) -----------
        if target.in_decode_loop:
            pulled = 0
            shapes = []
            for i, v in enumerate(closed.jaxpr.outvars):
                if i in target.donated_outputs:
                    continue  # donated & rebound: never crosses to host
                aval = v.aval
                n = int(np.prod(aval.shape)) if aval.shape else 1
                pulled += n * np.dtype(aval.dtype).itemsize
                shapes.append(f"{aval.dtype}{list(aval.shape)}")
            slots = max(target.slots, 1)
            steps = max(target.steps_per_call, 1)
            per = pulled / (slots * steps)
            if per > self.max_bytes:
                findings.append(self.finding(
                    target,
                    f"decode tick pulls {per:.0f} bytes/slot/step to "
                    f"the host (outputs {', '.join(shapes)}; budget "
                    f"{self.max_bytes}) — move the reduction (sampling/"
                    f"argmax) in-graph so only tokens cross"))
        return findings
