"""Serving engine: submit -> admission (``queue_wait_s`` histogram),
95th percentile over the window."""
from harness.readers import hist_pctl


def read(ctx):
    return hist_pctl(ctx, "queue_wait_s", 95, 1e3)
