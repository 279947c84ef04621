"""Device: 1 - union of operation intervals over the traced window."""
from harness.readers import idle_pct


def read(ctx):
    return idle_pct(ctx)
