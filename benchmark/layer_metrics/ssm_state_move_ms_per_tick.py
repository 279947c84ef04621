"""Model step: device self time, per tick of the traced span, of MOVING
the state-space state rather than updating it: the copies under the
scope ``ssm.scan`` (an operation the compiler names ``copy*``,
``dynamic-update-slice*``, ``dynamic-slice*`` or ``transpose*`` there:
the chunk algebra's fusions and the kernel are not moves) and the copies
the compiler adds with no scope at all (``xla:copy``: in a tick program,
a result buffer copied whole into a donated one). The state is 4.57 GiB
at 64 slots: one copy of it is 11 ms, so a lost alias reads here as
tens of milliseconds a tick, and a program that updates in place as the
few small copies around the kernel."""
from harness import hostspans as H
from harness.manifest import load_reader
from harness.readers import per_tick_ms

MOVES = r"^(copy|dynamic-update-slice|dynamic_update_slice|dynamic-slice|transpose)"


def read(ctx):
    hs = H.load(ctx)
    if not hs or not any(k.startswith("ssm.") for k in hs["by_label"]):
        return None
    device, _ = load_reader("ssm_scan_roofline_pct").device_and_ticks(ctx)
    if not device:
        return None
    scopes, kernels = H.tables(ctx["cell"].family)
    by = H.self_time_by_label(device, None, scopes,
                              {**kernels, "ssm.scan.move": MOVES})
    return per_tick_ms(
        ctx, (by.get("ssm.scan.move", 0) + by.get("xla:copy", 0)) / 1e9)
