"""Set-up, training: ``make_train_step`` (span ``train.setup.build``) and
the ``init(key)`` it returns, until the state it made is ready on the
device (span ``train.setup.init``), less the compile ledger's seconds
recorded during them: the random draw of weights the harness then
replaces, their placement, the optimizer's state."""
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
    val = rows["train_init_s"]
    print(f"[setup] setup_train_init_s {val:.3f} s",
          file=sys.stderr, flush=True)
    return val
