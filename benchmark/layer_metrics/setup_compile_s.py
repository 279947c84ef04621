"""Set-up: seconds in the backend (XLA, Mosaic) for every program
materialised before the window opened that the persistent cache did
not hold, the quick ones included (compile ledger: sum of ``backend_s``
where ``cache`` != ``hit``)."""
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
    val = rows["compile_s"]
    print(f"[setup] setup_compile_s {val:.3f} s",
          file=sys.stderr, flush=True)
    return val
