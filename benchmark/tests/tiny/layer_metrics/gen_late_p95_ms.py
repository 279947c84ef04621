"""Load generator: how late a request was sent, actual send - due."""
import numpy as np


def read(ctx):
    late = ctx.get("late_ms")
    return float(np.percentile(late, 95)) if late else None
