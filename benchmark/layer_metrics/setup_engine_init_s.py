"""Set-up, serving: ``ServingEngine.__init__`` (span
``serving.setup.init``: the scheduler, the static program inventory
under ``.inventory``, the pools and slot state allocated on the device
under ``.cache``) less the compile ledger's seconds recorded during
it."""
import sys
import time


def read(ctx):
    from paddle_tpu import observability
    report = getattr(observability, "setup_report", None)
    t0 = (ctx.get("window") or ctx.get("train") or {}).get("t0")
    if report is None or t0 is None:
        return None     # a program without the ledger: nothing to read
    # the harness stamps time.perf_counter(), the program
    # time.monotonic(): one offset (0 on Linux: the same clock)
    rows = report(until=t0 + time.monotonic() - time.perf_counter())["rows"]
    val = rows["engine_init_s"]
    print(f"[setup] setup_engine_init_s {val:.3f} s",
          file=sys.stderr, flush=True)
    return val
