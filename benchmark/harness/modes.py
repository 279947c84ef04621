"""A cell's mode -> the function that runs it and returns the last line."""
from __future__ import annotations

import time

import jax

from . import hostspans, serve, train
from . import trace as T
from .common import (Checks, check, device_block, log, pctl, result_line,
                     trace_dir)


def read_layers(cell, ctx: dict) -> dict:
    """Every per-layer reader of the cell; one that finds nothing to
    read returns None and is left out."""
    out = {}
    for m in cell.per_layer:
        val = cell.readers[m["name"]].read(ctx)
        if val is not None:
            out[m["name"]] = {"value": float(val), "unit": m["unit"]}
    return out


def finish(cell, devs, peak: dict, checks: Checks, attempted: int,
           failed: int, metrics: dict, ctx: dict | None) -> str:
    """The last line. Untraced (``ctx`` None): the end-to-end metrics.
    Traced: the trace is reduced, every per-layer reader reads ``ctx``
    and the line carries the per-layer metrics and the breakdown. The
    numbers compared go to stderr as its last lines, and last into the
    line."""
    device, breakdown = peak, None
    # the harness measures what the mode can; the manifest says which
    # of it is this cell's end-to-end metrics
    names = {m["name"] for m in cell.end_to_end}
    metrics = {k: v for k, v in metrics.items() if k in names}
    if ctx is not None:
        hs = hostspans.load(ctx)
        # the gaps of the breakdown go by the engine thread's phases
        red = T.reduce_trace(
            trace_dir(), len(devs),
            phases=[(hostspans.PHASE + n, s, e)
                    for n, s, e, _ in hs["phases"]] if hs else None)
        ctx["trace"] = red
        device = {**peak, "busy_s": red["busy_s"],
                  "window_s": red["window_s"]}
        breakdown = {"device_ops": red["top_ops"],
                     "idle_gaps": red["longest_gaps"]}
        log(f"[trace] window {red['window_s']:.3f} s busy "
            f"{red['busy_s']:.3f} s events {red['n_events']}")
        metrics = read_layers(cell, ctx)
    checks.to_stderr()
    return result_line(correct=all(checks), attempted=attempted,
                       failed=failed, metrics=metrics, device=device,
                       breakdown=breakdown, compared=checks.compared)


def run_serve(cell, args, devs, t_process: float) -> str:
    eng, params, model, family = serve.setup(cell, args.seed, devs)
    try:
        win = serve.run_window(cell, eng, model, args.seed, args.seconds,
                               bool(args.trace))
        stats = eng.stats()
    finally:
        eng.close(drain=False)   # what missed the drain limit is failed
    peak = device_block(devs)
    metrics, attempted, failed, info = serve.end_to_end(win)
    # process start -> the window opens (the lead-in is set-up)
    metrics["setup_s"] = {"value": win["t0"] - t_process, "unit": "s"}
    late = info.pop("gen_late_ms", None)
    log(f"[window] {win['loop']} loop, {win['seconds']} s, rate "
        f"{win['rate']}, attempted {attempted} failed {failed} "
        f"queued at close {win['queued_at_close']} stuck clients "
        f"{win['stuck']}")
    log(f"[window] engine counters {win['counters']}")
    for k in ("decode_step_s", "queue_wait_s", "batch_occupancy",
              "page_utilization"):
        v = win["hists"].get(k)
        if v:
            log(f"[window] {k}: n {len(v)} p50 {pctl(v, 50):.6g} p95 "
                f"{pctl(v, 95):.6g} max {max(v):.6g}")
    if late:
        log(f"[window] generator lateness ms: p50 {pctl(late, 50):.4f} "
            f"p95 {pctl(late, 95):.4f} max {max(late):.4f}")
    # ------------------------------------------------- correctness ----
    checks = Checks()
    checks.note("failed_requests", failed, 0, failed == 0)
    log(f"[correct] failed requests = {failed}  limit 0  "
        f"{'ok' if failed == 0 else 'FAIL'}")
    check("compiles_in_window", win["compiles_in_window"], 0, checks)
    del eng                      # the pools go; the weights stay
    sample = serve.sample_for_check(
        win["records"], args.seed, int(cell.workload["check_requests"]) - 1)
    t_ref = time.perf_counter()
    with jax.default_device(devs[0]):
        serve.compare_with_reference(
            params, sample, model, family, cell.workload["limits"], checks,
            pad_len=serve.longest_sequence(cell))
    log(f"[correct] reference took {time.perf_counter() - t_ref:.2f} s "
        f"(outside the window, not in setup_s)")
    if args.trace:
        ctx = {"cell": cell, "model": model, "window": win, "stats": stats,
               "late_ms": late, "devices": devs, "end_to_end": metrics}
        log(f"[trace] ticks counted in the traced span: "
            f"{win['trace_ticks']}")
    else:
        ctx = None
    log(f"[metrics] {info}; measured { {k: round(v['value'], 3) for k, v in metrics.items()} }")
    return finish(cell, devs, peak, checks, attempted, failed, metrics, ctx)


MODES = {"serve_open": run_serve, "serve_closed": run_serve}


def run_train(cell, args, devs, t_process: float) -> str:
    model, family = cell.model, cell.family
    wl = cell.workload
    trainer = train.Trainer(cell, model, family, args.seed, devs)
    kern = train.kernels_in(trainer.text)
    first = trainer.first_steps(wl["optimizer"])
    step_s = min(first["step_s"][1:])
    log(f"[setup] mesh {dict(trainer.mesh.shape)}; seconds: "
        f"{ {k: round(v, 2) for k, v in trainer.timing.items()} }; first "
        f"steps {[round(s * 1e3, 1) for s in first['step_s']]} ms; "
        f"kernels {kern}")
    win = train.run_window(trainer, args.seconds, step_s, bool(args.trace),
                           wl)
    setup_s = win["t0"] - t_process
    peak = device_block(devs)
    log(f"[window] {win['steps']} steps of {win['tokens_per_step']} tokens "
        f"in {win['window_s']:.3f} s; loss first {win['losses'][0]:.4f} "
        f"last {win['losses'][-1]:.4f}")
    checks = Checks()
    check("non_finite_losses", win["failed"], 0, checks)
    check("compiles_in_window", win["compiles_in_window"], 0, checks)
    need = wl.get("kernels", [])
    missing = [k for k in need if not kern.get(k)]
    log(f"[correct] kernels missing from the compiled step: {missing}  "
        f"limit none  {'ok' if not missing else 'FAIL'}")
    checks.note("kernels_missing", len(missing), 0, not missing)
    del trainer                 # the program's state goes; then the reference
    t_ref = time.perf_counter()
    with jax.default_device(devs[0]):
        ref = train.run_reference(cell, model, family, args.seed)
    log(f"[correct] reference (float32, {train.REF_STEPS} steps + a loss) "
        f"took {time.perf_counter() - t_ref:.2f} s, after the program's "
        f"state was freed; outside the window, not in setup_s")
    train.compare_training(first, ref, wl["limits"], checks)
    metrics = {"train_tokens_per_s": {"value": win["tokens_per_s"],
                                      "unit": "tokens/s"},
               "setup_s": {"value": setup_s, "unit": "s"}}
    ctx = None
    if args.trace:
        ctx = {"cell": cell, "model": model, "train": win, "devices": devs,
               "end_to_end": metrics}
        log(f"[trace] steps in the traced span: {win['trace_steps']}; rate "
            f"before the trace {win['tokens_per_s']:.1f} tokens/s")
    return finish(cell, devs, peak, checks, win["steps"], win["failed"],
                  metrics, ctx)


MODES["train"] = run_train
