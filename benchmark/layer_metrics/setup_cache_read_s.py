"""Set-up: seconds reading programs from the persistent compile cache
before the window opened: the whole backend event of every hit (the
read, ``retrieval_s``, and the load of the executable onto the device),
so that the rows add up (compile ledger: sum of ``backend_s`` where
``cache`` == ``hit``)."""
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
    val = rows["cache_read_s"]
    print(f"[setup] setup_cache_read_s {val:.3f} s",
          file=sys.stderr, flush=True)
    return val
