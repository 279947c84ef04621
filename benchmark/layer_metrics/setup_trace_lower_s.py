"""Set-up: seconds of Python tracing programs to jaxprs and lowering them
to StableHLO before the window opened, cached or not (compile ledger:
sum of ``trace_s`` + ``lower_s`` of every record). This is the part that
depends on the depth of the calling stack wherever nothing anchors it
(PERF.md, PR 30)."""
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
    rep = report(until=t0 + time.monotonic() - time.perf_counter())
    val = rep["rows"]["trace_lower_s"]
    print(f"[setup] setup_trace_lower_s {val:.3f} s",
          file=sys.stderr, flush=True)
    # the programs that took the most seconds, by name
    print("[setup] slowest programs (trace + lower + backend s): "
          + "; ".join(f"{r['fun_name']} {r['trace_s']:.2f} + "
                      f"{r['lower_s']:.2f} + {r['backend_s']:.2f} "
                      f"{r['cache']} during {r['during']}"
                      for r in rep["slowest"][:6]),
          file=sys.stderr, flush=True)
    return val
