"""Set-up: programs materialised before the window opened that were NOT
read from the persistent compile cache although slow enough to be kept
there (compile ledger: ``cache`` != ``hit`` and ``backend_s`` >=
``jax_persistent_cache_min_compile_time_secs``). A program quicker than
that is never written, misses in every run and is left out here (its
seconds are in ``setup_compile_s``). 0 is a warm run; more is a cold
cache, a lost entry, or a program whose key changed."""
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
    val = rows["programs_missed"]
    print(f"[setup] setup_programs_missed {val} programs",
          file=sys.stderr, flush=True)
    return val
