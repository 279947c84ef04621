"""What a compiled train step's text says of the reductions inside its
backward loop: the static counter of whether ``make_train_step``'s
dp-sharded update engages.

Test support: it parses compiler text, so it lives beside the test that
reads it (``test_chip_compile.py``), not in the package.

The TPU compiler gives a collective a form of its own in two cases
only: an asynchronous ``all-gather`` and a reduce-scatter FUSED onto
the matmul that produces the partial result (a ``kCustom`` fusion that
calls ``%all-reduce-scatter.N``, whose own text holds the group's
``all-reduce``; on the chip it stands for its transfer's time all the
same: PERF.md, PR 41). A bare ``all-reduce`` instruction in the loop's body is
synchronous: nothing runs beside it. ``backward_loop_collectives`` reads
both kinds out of the body of the backward ``while`` and says which of
them reduce across a mesh axis (``dp``).
"""
from __future__ import annotations

import re
from typing import Dict, List, Mapping, NamedTuple, Optional

import numpy as np

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                "u64": 8}
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{$")
_SHAPE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")
_BACKWARD = "transpose(jvp"


class Reduction(NamedTuple):
    """One reduction in the backward loop's body. ``shapes``: what it
    yields (``"bf16[4096,7168]"``); ``bytes``: their sum; ``crosses``:
    whether a replica group holds devices that differ along the axis."""
    name: str
    shapes: tuple
    bytes: int
    crosses: bool


class LoopCollectives(NamedTuple):
    body: Optional[str]              # the backward while body's name
    all_reduces: List[Reduction]     # standing, synchronous
    reduce_scatters: List[Reduction]     # fused onto their producers


def _computations(text: str) -> Dict[str, List[str]]:
    out, name = {}, None
    for line in text.split("\n"):
        m = _COMPUTATION.match(line)
        if m:
            name = m.group(1)
            out[name] = []
        elif name is not None:
            if line == "}":     # a kernel's attributes may span lines
                name = None         # and start one with "}}"
            else:
                out[name].append(line)
    return out


def replica_groups(line: str) -> Optional[np.ndarray]:
    """``replica_groups=`` of one instruction as an array [groups,
    members] of logical device ids: the listed form ``{{0,2},{1,3}}``
    or the iota form ``[2,2]<=[2,2]T(1,0)``."""
    m = re.search(r"replica_groups=\{(\{[0-9,{} ]*\})\}", line)
    if m:
        return np.array([[int(i) for i in g.split(",")]
                         for g in re.findall(r"\{([0-9, ]+)\}", m.group(1))])
    m = re.search(r"replica_groups=\[([0-9,]+)\]<=\[([0-9,]+)\]"
                  r"(?:T\(([0-9,]+)\))?", line)
    if not m:
        return None
    ints = lambda s: [int(i) for i in s.split(",")]
    dims = ints(m.group(2))
    ids = np.arange(int(np.prod(dims))).reshape(dims)
    if m.group(3):
        ids = ids.transpose(ints(m.group(3)))
    return ids.reshape(ints(m.group(1)))


def _reduction(line: str, op: str, group_line: str, coord) -> Reduction:
    name = line.split("=", 1)[0].strip().lstrip("%")
    shapes = _SHAPE.findall(line.split("=", 1)[1].split(f" {op}(", 1)[0])
    nbytes = sum(_DTYPE_BYTES[dt] * int(np.prod([int(d) for d in
                                                 dims.split(",") if d]))
                 for dt, dims in shapes)
    groups = replica_groups(group_line)
    crosses = groups is not None and any(
        len({coord(i) for i in g}) > 1 for g in groups)
    return Reduction(name, tuple(f"{dt}[{dims}]" for dt, dims in shapes),
                     int(nbytes), bool(crosses))


def backward_loop_collectives(text: str, mesh_shape: Mapping[str, int],
                              axis: str = "dp") -> LoopCollectives:
    """The reductions in the body of ``text``'s backward layer loop.

    ``text``: ``compiled.as_text()`` of a jitted train step whose layers
    are a differentiated ``lax.scan``; ``mesh_shape``: ``mesh.shape`` of
    the mesh it was compiled for (axis name -> size, in the mesh's own
    order: a replica group's ids count positions in ``mesh.devices``).
    The backward body is the ``while`` body most of whose instructions
    come from the transposed scan. No such loop: ``body`` is None and
    both lists are empty."""
    sizes = list(mesh_shape.values())
    at = list(mesh_shape).index(axis)
    coord = lambda i: int(np.unravel_index(int(i), sizes)[at])
    comps = _computations(text)
    bodies = set(re.findall(r"body=%([^\s,)]+)", text))
    score = {b: sum(_BACKWARD in ln and "/while/body" in ln
                    for ln in comps.get(b, ())) for b in bodies}
    body = max(score, key=score.get, default=None)
    if body is None or not score[body]:
        return LoopCollectives(None, [], [])
    standing, fused = [], []
    for line in comps[body]:
        if " all-reduce(" in line:
            standing.append(_reduction(line, "all-reduce", line, coord))
            continue
        m = re.search(r" fusion\(.*calls=%(all-reduce-scatter[^\s,)]*)",
                      line)
        if m:
            inner = [ln for ln in comps.get(m.group(1), ())
                     if " all-reduce(" in ln]
            fused.append(_reduction(line, "fusion",
                                    inner[0] if inner else "", coord))
    return LoopCollectives(body, standing, fused)
