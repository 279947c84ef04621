"""Serving engine: one whole tick (device step + host scheduling +
token read-back; the engine's ``decode_step_s`` histogram), median over
the window."""
from harness.readers import hist_pctl


def read(ctx):
    return hist_pctl(ctx, "decode_step_s", 50, 1e3)
