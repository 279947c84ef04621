"""Auto-parallel planner CLI: search, rank, and trace-verify
(dp, tp, pp, V, M, schedule, zero, dtype) plans for a model + mesh +
HBM budget (analysis/planner.py — ROADMAP item 4).

    JAX_PLATFORMS=cpu python tools/auto_parallel.py \\
        --devices 4 --batch 64 --seq-len 64 --hbm-gb 0.25

enumerates the legal configuration space (illegal points pruned by the
same divisibility/schedule/zero rules the executors enforce, each
counted by reason), prices every point with the composed static cost
model (traced HBM peak, xla-cost-analysis step-time proxy normalized
by schedule efficiency, traced + analytic comms terms), prints the
ranked plan, and VERIFIES the winner: traces it at the full requested
batch and runs the complete registered pass stack plus the planner
contract (prediction-vs-trace deltas in the shared Finding schema;
non-zero exit when any pass errors or the prediction misses its
tolerance).

``--smoke`` is the CI entry (tests/test_auto_parallel_planner.py):
tiny config, 2x2 mesh, narrowed space — asserts a non-empty ranked
plan whose winner trace-verifies, in well under a minute.

Everything runs on virtual CPU devices — tracing is abstract and the
one reference compile per dtype is a tiny single-device step, so
planning a 4-device space costs ~20s and zero TPU time.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

DTYPE_ALIASES = {"bf16": "bfloat16", "bfloat16": "bfloat16",
                 "f32": "float32", "float32": "float32"}


def build_config(args):
    import dataclasses
    from paddle_tpu.models import llama as L
    cfg = (L.LlamaConfig.llama3_8b() if args.model == "llama3_8b"
           else L.LlamaConfig.tiny())
    over = {}
    if args.layers:
        over["num_hidden_layers"] = args.layers
    if args.hidden:
        over["hidden_size"] = args.hidden
    return dataclasses.replace(cfg, **over) if over else cfg


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", choices=["tiny", "llama3_8b"],
                    default="tiny")
    ap.add_argument("--layers", type=int, default=0,
                    help="override the model's layer count (e.g. to "
                         "open deeper pp factorizations)")
    ap.add_argument("--hidden", type=int, default=0)
    ap.add_argument("--devices", type=int, default=4,
                    help="mesh size the plan must fill (dp*tp*pp)")
    ap.add_argument("--batch", type=int, default=64,
                    help="global batch size the step must take")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--hbm-gb", type=float, default=None,
                    help="per-device HBM budget; plans exceeding it "
                         "are excluded from the ranking (counted in "
                         "over_budget)")
    ap.add_argument("--dtypes", nargs="+", default=["bf16", "f32"],
                    choices=sorted(DTYPE_ALIASES))
    ap.add_argument("--zero", nargs="+", type=int, default=[0, 1, 3])
    ap.add_argument("--schedules", nargs="+", default=None,
                    help="pp schedules to search (default: every "
                         "entry of SCHEDULE_INFO)")
    ap.add_argument("--vpp", nargs="+", type=int, default=[1, 2])
    ap.add_argument("--microbatches", nargs="+", type=int, default=None)
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--tolerance", type=float, default=0.15,
                    help="planner-contract HBM tolerance")
    ap.add_argument("--no-verify", action="store_true")
    ap.add_argument("--json", action="store_true",
                    help="emit the full plan JSON on stdout")
    ap.add_argument("--out", default=None,
                    help="also write the plan JSON to this path")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tiny config, 2x2 mesh, narrowed "
                         "space; non-zero exit unless a non-empty "
                         "ranked plan verifies")
    args = ap.parse_args(argv)

    if args.smoke:
        # the CI space is defined ONCE (planner.SMOKE_KNOBS) and
        # shared with graph_lint --planner, so the two gates cannot
        # drift onto different spaces
        from paddle_tpu.analysis.planner import SMOKE_KNOBS
        args.model, args.layers, args.hidden = "tiny", 0, 0
        args.devices = SMOKE_KNOBS["devices"]
        args.batch = SMOKE_KNOBS["batch_size"]
        args.seq_len = SMOKE_KNOBS["seq_len"]
        args.dtypes = list(SMOKE_KNOBS["dtypes"])  # full names alias
        args.zero = list(SMOKE_KNOBS["zero_stages"])
        args.vpp = list(SMOKE_KNOBS["vpp_choices"])
        args.hbm_gb = (args.hbm_gb
                       or SMOKE_KNOBS["hbm_budget_bytes"] / 2**30)
        args.top = SMOKE_KNOBS["top"]

    # planning runs on virtual CPU devices — must happen before any
    # jax operation (tools/graph_lint.py does the same)
    from paddle_tpu.testing import force_host_cpu_devices
    force_host_cpu_devices(max(args.devices, 1))

    from paddle_tpu.analysis.planner import plan_auto_parallel

    cfg = build_config(args)
    budget = (int(args.hbm_gb * 2**30)
              if args.hbm_gb is not None else None)
    say = (lambda *_: None) if args.json else print
    t0 = time.time()
    out = plan_auto_parallel(
        cfg, args.devices, batch_size=args.batch,
        seq_len=args.seq_len, hbm_budget_bytes=budget, top=args.top,
        verify=not args.no_verify, tolerance=args.tolerance,
        dtypes=tuple(DTYPE_ALIASES[d] for d in args.dtypes),
        zero_stages=tuple(args.zero),
        schedules=(tuple(args.schedules) if args.schedules else None),
        vpp_choices=tuple(args.vpp),
        microbatch_choices=(tuple(args.microbatches)
                            if args.microbatches else None),
        progress=say)
    out["seconds"] = round(time.time() - t0, 2)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
    if args.json:
        print(json.dumps(out, indent=2))
    else:
        print(f"\n{out['legal']} legal / {out['enumerated']} "
              f"enumerated points "
              f"({out['over_budget']} over budget) in "
              f"{out['seconds']}s")
        for reason, n in out["pruned"].items():
            print(f"  pruned {n:4d}  {reason}")
        print(f"\n rank  {'plan':34s} {'step*':>9s} {'peak MiB':>9s} "
              f"{'eff':>6s}  fits")
        for p in out["plans"]:
            c = p["cost"]
            print(f"  {p['rank']:3d}  {p['label']:34s} "
                  f"{c['step_time_proxy_s'] * 1e6:8.1f}u "
                  f"{c['hbm_peak_bytes'] / 2**20:9.2f} "
                  f"{c['efficiency']:6.3f}  {c['fits']}")
        ver = out.get("verification")
        if ver is not None:
            print(f"\nwinner verification: "
                  f"{'OK' if ver['ok'] else 'FAILED'}")
            for k, v in ver.get("deltas", {}).items():
                print(f"  {k}: {v}")
            for f_ in ver.get("report", {}).get("findings", []):
                if f_["severity"] != "info":
                    print(f"  [{f_['severity']}] {f_['pass']}: "
                          f"{f_['message']}")

    ok = bool(out["plans"])
    if not args.no_verify:
        ok = ok and bool(out.get("verification", {}).get("ok"))
    if args.smoke and not args.json:
        print(f"auto_parallel --smoke: "
              f"{'OK' if ok else 'FAIL'} "
              f"({len(out['plans'])} ranked plans)")
    return 0 if ok else 1


if __name__ == "__main__":
    from paddle_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main(sys.argv[1:]))
