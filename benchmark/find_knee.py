"""Find a serving cell's knee, once: one process, one set-up, offered
rate in steps of x1.25, each step a window of its own with a drain
between.

    python benchmark/find_knee.py --workload <name> --seed <n> \
        --start 2.0 --steps 9 --seconds 20

The rule (PERF.md section 4): a step SUSTAINS its rate when (a) no
request failed, (b) the queue when the step closed was no longer than
the slot count, and (c) the median time from due to first token of the
step's second half was under twice that of its first half plus 50 ms.
(As first fixed, (c) read the engine's ``queue_wait_s``; this engine
admits at once and queues inside, on its prefill queue, so the backlog
shows at the client and not there. Both are in the rows.) The knee is
the highest sustained step below the first step that is not. Rows go to stdout and to ``chiprun_out/knee_<workload>.json``; the
rate and the rows are then written by hand into the workload file.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def sustained(row: dict, slots: int) -> bool:
    return (row["failed"] == 0 and row["queued_at_close"] <= slots
            and row["ttft_p50_2nd_ms"]
            <= 2 * row["ttft_p50_1st_ms"] + 50.0)


def main(argv=None, platform: str = "tpu") -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--start", type=float, default=2.0)
    ap.add_argument("--factor", type=float, default=1.25)
    ap.add_argument("--steps", type=int, default=9)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    from harness import serve
    from harness.common import (enable_compile_cache, log, pctl,
                                require_devices)
    from harness.manifest import ROOT, Cell, load_manifest
    cell = Cell(load_manifest(), args.workload)
    devs = require_devices(cell.chips, platform)
    enable_compile_cache()
    eng, params, model, family = serve.setup(cell, args.seed, devs)
    log(f"[knee] set-up {time.perf_counter() - T_PROCESS:.1f} s")
    rows, rate = [], args.start
    try:
        for step in range(args.steps):
            win = serve.run_window(cell, eng, model, args.seed + step,
                                   args.seconds, False, rate=rate)
            metrics, attempted, failed, info = serve.end_to_end(win)
            recs = sorted((r for r in win["records"] if r.finished),
                          key=lambda r: r.due_t)
            half = len(recs) // 2
            # the backlog as the client sees it: due -> first token
            w1 = [(r.token_t[0] - r.due_t) * 1e3 for r in recs[:half]]
            w2 = [(r.token_t[0] - r.due_t) * 1e3 for r in recs[half:]]
            qw = win["hists"].get("queue_wait_s") or [0.0]
            qh = len(qw) // 2
            row = {
                "rate_rps": rate, "attempted": attempted, "failed": failed,
                "queued_at_close": win["queued_at_close"],
                "ttft_p50_ms": info.get("ttft_p50_ms"),
                "ttft_p95_ms": metrics.get("ttft_p95_ms", {}).get("value"),
                "itl_p50_ms": info.get("itl_p50_ms"),
                "itl_p95_ms": metrics.get("itl_p95_ms", {}).get("value"),
                "ttft_p50_1st_ms": pctl(w1, 50) if w1 else None,
                "ttft_p50_2nd_ms": pctl(w2, 50) if w2 else None,
                "queue_wait_p50_1st_ms": pctl(qw[:qh] or [0.0], 50) * 1e3,
                "queue_wait_p50_2nd_ms": pctl(qw[qh:] or [0.0], 50) * 1e3,
                "tick_p50_ms": pctl(win["hists"]["decode_step_s"], 50) * 1e3,
                "occupancy_p50": pctl(win["hists"]["batch_occupancy"], 50),
                "tokens_out_per_s": win["counters"]["tokens_out"]
                / args.seconds,
            }
            row["sustained"] = sustained(row, win["slots"])
            rows.append(row)
            log("[knee] " + json.dumps(row))
            if not row["sustained"] and step and not rows[-2]["sustained"]:
                break           # two steps past the knee are enough
            rate *= args.factor
    finally:
        eng.close()
    good = []
    for r in rows:
        if not r["sustained"]:
            break
        good.append(r["rate_rps"])
    knee = max(good) if good else None
    out = {"workload": args.workload, "knee_rps": knee,
           "device": devs[0].device_kind, "rows": rows}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"knee_{args.workload}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"knee_rps": knee,
                      "rate_at_0.8": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
