"""Read the numbers a cell's limits are set from: the program's, over
many seeds, and the CONTROL's beside them, in one process.

    python benchmark/control.py --workload <name> --seeds 11,12,13 --seconds 15

The control is the plain reference put in the program's place and
computed in the nearest precision below the configuration's (both
matmul operands at float8 e4m3's 3 mantissa bits for a bfloat16
configuration). Serving: at each position
of the same prompts and served tokens, the gap of the token the control
puts first. Training: the control's losses, first-gradient norms and
parameter-change norms held against the float32 reference's by the
run's own comparison. A benchmark run never runs the control. Rows go
to stdout and ``chiprun_out/control_<workload>.json``.

``--control-only`` (training) leaves the program out: the float32
reference and the control, both one-device programs, on ONE chip
whatever the cell asks for. A four-chip cell's control readings then
cost a quarter; the program's own numbers come from its runs' last
lines (``compared``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def serve_seed(cell, seed, seconds, devs):
    import jax
    from harness import serve
    from harness.common import Checks
    eng, params, model, family = serve.setup(cell, seed, devs)
    try:
        win = serve.run_window(cell, eng, model, seed, seconds, False)
    finally:
        eng.close(drain=False)
    del eng
    _, attempted, failed, _ = serve.end_to_end(win)
    sample = serve.sample_for_check(
        win["records"], seed, int(cell.workload["check_requests"]) - 1)
    checks = Checks()
    t0 = time.perf_counter()
    with jax.default_device(devs[0]):
        out = serve.compare_with_reference(
            params, sample, model, family, cell.workload["limits"], checks,
            control=True, pad_len=serve.longest_sequence(cell))
    out.update(seed=seed, attempted=attempted, failed=failed,
               correct=all(checks) and failed == 0,
               compiles=win["compiles_in_window"],
               reference_s=time.perf_counter() - t0)
    return out


def train_seed(cell, seed, devs, control_only=False):
    import jax
    from harness import train
    from harness.common import Checks
    model, family = cell.model, cell.family
    first = None
    if not control_only:
        trainer = train.Trainer(cell, model, family, seed, devs)
        first = trainer.first_steps(cell.workload["optimizer"])
        del trainer
    with jax.default_device(devs[0]):
        t0 = time.perf_counter()
        ref = train.run_reference(cell, model, family, seed)
        ref_s = time.perf_counter() - t0
        low = train.run_reference(cell, model, family, seed,
                                  round_to=family.CONTROL_ROUND_TO)
    checks_low, checks = Checks(), Checks()
    out = {"seed": seed, "reference_s": ref_s,
           "control": train.compare_training(
               low, ref, cell.workload["limits"], checks_low,
               tag=" control:")}
    out["control_correct"] = all(checks_low)
    if first is not None:
        out["step_ms"] = [s * 1e3 for s in first["step_s"]]
        out["program"] = train.compare_training(
            first, ref, cell.workload["limits"], checks)
        out["correct"] = all(checks)
    return out


def main(argv=None, platform: str = "tpu") -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--control-only", action="store_true")
    args = ap.parse_args(argv)
    from harness.common import enable_compile_cache, log, require_devices
    from harness.manifest import ROOT, Cell, load_manifest
    cell = Cell(load_manifest(), args.workload)
    if args.control_only and cell.mode != "train":
        raise SystemExit("--control-only is for training cells: a served "
                         "model's control reads the program's own tokens")
    devs = require_devices(1 if args.control_only else cell.chips, platform)
    enable_compile_cache()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if cell.mode == "train":
            row = train_seed(cell, seed, devs, args.control_only)
        else:
            row = serve_seed(cell, seed, args.seconds, devs)
        row["took_s"] = time.perf_counter() - t0
        rows.append(row)
        log("[control] " + json.dumps(row))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", f"control_{args.workload}.json")
    old = json.load(open(path)) if os.path.exists(path) else []
    with open(path, "w") as f:
        json.dump(old + rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
