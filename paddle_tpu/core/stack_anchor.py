"""Run deeply nested Python work above a frame that owns its own chunk
of the interpreter's frame stack.

CPython (3.11 and later) keeps a thread's frames in chunks of 16 KiB
and frees a chunk the moment its first frame returns. A function that
happens to be the first frame of a chunk, called in a loop, maps and
unmaps a chunk on every call. Tracing a serving tick program is such
work: a jit over a layer scan over a jit over a Pallas kernel whose
body is ~180 ``pl.when`` branches, each traced through a few hundred
frames of JAX. How long it takes then depends on how many BYTES of
frames lie below it: the same trace of the Qwen1.5-MoE tick at 256
rows read 0.77 s to 1.5 s on one machine as the caller's depth went
from 0 to 90 frames, with a period of one chunk; two frames more in
the model's call path moved ``warm_programs()`` by 7 to 10 s on the
chip's host (PERF.md §6, PR 30).

``above_stack_anchor(fn, ...)`` calls ``fn`` from a frame of ~320 KiB:
the interpreter gives that frame a chunk of 512 KiB, and everything
``fn`` calls runs in the ~190 KiB left above it without meeting a
chunk's end, whatever lay below. One allocation a call (~140 us): for
work that takes seconds, not for a tick.
"""
from __future__ import annotations

_SLOTS = 40_000     # 8 B each; the chunk is the next 16 KiB * 2**k above
_anchor = None


def _build():
    # locals that are never bound still have a slot in the frame: the
    # assignment below the return is unreachable and costs nothing
    names = " = ".join(f"_{i}" for i in range(_SLOTS))
    scope: dict = {}
    exec(compile("def anchor(fn, *args, **kw):\n"
                 "    return fn(*args, **kw)\n"
                 f"    {names} = None\n", "<stack_anchor>", "exec"), scope)
    return scope["anchor"]


def above_stack_anchor(fn, *args, **kw):
    """``fn(*args, **kw)``, called from a frame large enough to begin a
    frame-stack chunk of its own (see the module's docstring)."""
    global _anchor
    if _anchor is None:
        _anchor = _build()
    return _anchor(fn, *args, **kw)
