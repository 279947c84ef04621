"""Serving engine: the host's time in one ticked iteration of the engine
thread — admission, the tick's arrays packed and sent, the dispatch of
the program, tokens emitted and the tick's records; the iteration less
its blocking read-back (the engine's ``tick_host_s`` histogram, the sum
of ``phase_admit_s``, ``_build_s``, ``_dispatch_s``, ``_emit_s``), median
over the window."""
from harness.readers import hist_pctl


def read(ctx):
    return hist_pctl(ctx, "tick_host_s", 50, 1e3)
