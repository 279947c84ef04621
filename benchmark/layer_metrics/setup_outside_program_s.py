"""Set-up: what is left of ``setup_s`` after the workload's lead-in, the
program's top-level set-up spans and the compile ledger's seconds
recorded outside them: the interpreter's imports, device discovery,
the harness's own seeded weights and feed, the warm requests. With
this row the cell's ``setup_*`` rows and its lead-in add up to
``setup_s``."""
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
    rows, totals, ledger = rep["rows"], rep["totals"], rep["ledger"]
    setup = (ctx.get("end_to_end") or {}).get("setup_s")
    if setup is None:
        return None
    lead = float(ctx["cell"].workload.get("lead_in_s", 0.0))
    val = setup["value"] - lead - rows["in_program_s"]
    print(f"[setup] setup_outside_program_s {val:.3f} s = setup_s "
          f"{setup['value']:.3f} - lead-in {lead:.3f} - in the program "
          f"{rows['in_program_s']:.3f}",
          file=sys.stderr, flush=True)
    # what the instrumentation itself cost, process-wide
    print(f"[setup] the ledger's listener took "
          f"{ledger['listener_s'] * 1e3:.3f} ms over "
          f"{ledger['listener_events']} events; {totals['programs']} "
          f"programs ({totals['hits']} read from the cache) and "
          f"{len(rep['spans'])} set-up spans before the window",
          file=sys.stderr, flush=True)
    return val
