"""Kernels: device self time, per tick of the traced span, of the
Mamba-2 state pass (``ops/pallas/ssd_update.py``, label
``ssm.scan.kernel``: operations named ``ssd_update*`` under the scope
``ssm.scan``), over every Mamba layer and every launch of a tick."""
from harness.hostspans import load
from harness.readers import per_tick_ms

LABEL = "ssm.scan.kernel"


def read(ctx):
    hs = load(ctx)
    if not hs or LABEL not in hs["by_label"]:
        return None
    return per_tick_ms(ctx, hs["by_label"][LABEL] / 1e9)
