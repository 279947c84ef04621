"""Graph lint CLI: run the static-analysis passes over the flagship
serving AND training graphs.

The pre-merge check (with ruff — see pyproject.toml):

    JAX_PLATFORMS=cpu python tools/graph_lint.py --ci

runs, in seconds and with zero XLA compiles:

  * the jaxpr lint passes (dtype-drift, host-sync,
    collective-consistency) over the flagship llama + qwen2_moe
    serving programs (the r12 one-program tick as r16 reshaped it:
    `serving_tick` at the mixed width, the fused `serving_tick_block`
    with the in-graph sampling state traced as data — the width-S
    single-step sampling program no longer exists — and
    `generate_paged`) and the llama pp stage chunks;
  * the recompile-hazard pass over the flagship engine geometry —
    statically proving the ≤2-programs-per-packed-width one-program-
    tick invariant (`--json` carries the inventory as
    `serving_programs`);
  * the TRAINING passes (sharding-lint, donation-audit, hbm-peak,
    collective-consistency trip counts) over the llama auto-parallel
    train step at the dp / dp×mp / pp-1F1B / zero1 geometries, the
    rank-asymmetric pipeline schedules (pp2_zb W-deferral, pp4_async
    per-rank 1F1B — `--json` carries their trip/phase inventory as
    `pipeline_schedules`), plus the 1F1B stage-chunk group
    (analysis/training_graphs.py);
  * the REWRITE suite (analysis/rewrite.py): every registered rewrite
    pass applied to its flagship targets — the jnp-rmsnorm serving
    graphs and the unfused-int8 decode tick — with each expected
    rewrite required to fire, the rewriter required to be idempotent,
    and every fired site verified against its exactness contract
    (bitwise / pinned tolerance) on concrete seeded inputs;
  * the CONCURRENCY suite (analysis/concurrency.py, also under
    --ci): the static guarded-by lint + lock-order cycle analysis
    over every threading.Lock/RLock in paddle_tpu/serving/ — `--json`
    carries the lock inventory, the acquisition-order graph
    (`concurrency.lock_order.edges`), per-rule counts and the
    suppression/annotation inventories; any unsuppressed finding or
    order cycle fails the run (static passes only here — the runtime
    LockTracer and the schedule fuzzer run in the test suite and
    under `serving_bench --check-invariants`);
  * the KERNELS suite (analysis/kernel_audit.py, also under --ci):
    the static Pallas kernel auditor — per registered kernel geometry
    (plus every swept winner in the autotune store) it proves the
    VMEM footprint fits the per-core budget (KA001), every index_map
    stays in bounds and the output tiling covers exactly (KA002),
    every async-copy start has a matching wait ordered before any
    read (KA003), and reduction carries over bf16/int8 inputs are f32
    (KA004); `--json` carries the per-launch VMEM table
    (`kernels.vmem`), per-rule finding counts and per-rule evaluation
    counts (the non-vacuity proof), the suppression inventory, and
    stale-waiver list — any finding, error, or stale waiver fails the
    run;
  * (--ci) the AST source lint over paddle_tpu/ + tools/
    (analysis/source_lint.py), plus `ruff check` when the binary is
    installed (the container image does not ship it; the AST subset
    always runs so the gate can never silently no-op);
  * (--planner) the auto-parallel planner smoke (analysis/planner.py:
    tiny config, 2x2 mesh): a non-empty ranked plan whose winner
    passes trace-verification under the planner contract, emitted as
    the `planner` section of `--json`.

Exit status: non-zero on any ERROR finding. `--json` emits a
machine-readable report including the per-geometry HBM peak estimates;
`--verbose` includes INFO findings (program inventories, declared f32
islands, donation inventories, HBM tops).
"""
import argparse
import json
import os
import shutil
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def run_graph_passes(models, suite="all"):
    from paddle_tpu.analysis import (default_passes, pp_stage_targets,
                                     run_passes, serving_targets,
                                     training_targets)
    targets = []
    serving_pool = []
    if suite in ("all", "serving"):
        for m in models:
            serving_pool += serving_targets(m)
        targets += serving_pool
        targets += pp_stage_targets()
    if suite in ("all", "training"):
        targets += training_targets()
    passes = default_passes()
    report = run_passes(passes, targets)
    hbm = next((p for p in passes if p.name == "hbm-peak"), None)
    return report, (hbm.reports if hbm is not None else {}), serving_pool


def run_ruff(root):
    """ruff check, when available. Returns (ran, ok, output)."""
    exe = shutil.which("ruff")
    if exe is None:
        return False, True, "ruff not installed (AST lint still ran)"
    proc = subprocess.run([exe, "check", "."], cwd=root,
                          capture_output=True, text=True)
    return True, proc.returncode == 0, proc.stdout + proc.stderr


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--models", nargs="+",
                    default=["llama", "qwen2_moe"],
                    help="flagship models to lint (serving suite)")
    ap.add_argument("--suite",
                    choices=["all", "serving", "training", "rewrite",
                             "concurrency", "kernels"],
                    default="all")
    ap.add_argument("--ci", action="store_true",
                    help="also run the source lint (+ruff if installed)"
                         " — the pre-merge configuration")
    ap.add_argument("--planner", action="store_true",
                    help="also run the auto-parallel planner smoke "
                         "(tiny config, 2x2 mesh) and emit the ranked "
                         "plan + winner verification as a `planner` "
                         "section (~20s)")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--verbose", action="store_true",
                    help="include INFO findings")
    args = ap.parse_args(argv)

    # lint runs never touch the chip, and the training
    # geometries need the virtual 8-device CPU mesh (tracing only —
    # nothing executes on the fake devices)
    from paddle_tpu.testing import force_host_cpu_devices
    force_host_cpu_devices(8)

    t0 = time.time()
    report, hbm, serving_pool = run_graph_passes(args.models, args.suite)
    rw_table = None
    if args.suite in ("all", "rewrite"):
        from paddle_tpu.analysis.rewrite import run_rewrite_suite
        # reuse the lint suite's already-traced serving targets (same
        # geometry) so --suite all traces each flagship program once
        rw_findings, rw_table = run_rewrite_suite(
            models=args.models,
            serving_pool=serving_pool or None)
        report.findings.extend(rw_findings)
        report.ran.extend(
            ("rewrite-suite", row["graph"]) for row in rw_table)
    ok = report.ok
    out = {"graph": report.to_dict()}
    if args.suite in ("all", "serving"):
        # the serving-suite program-set proof, machine-readable: the
        # exact tick-program inventory the recompile-hazard pass
        # enumerated for the flagship engine geometry (--ci consumers
        # gate on programs_per_bucket <= 2)
        from paddle_tpu.analysis.recompile import program_inventory
        geoms = [t.meta["geometry"] for t in serving_pool
                 if t.meta.get("geometry") is not None]
        geom = next((g for g in geoms if not g.spec_k), None)
        if geom is not None:
            inventory = program_inventory(geom)
            out["serving_programs"] = inventory
            # the runtime-observability contract: the recompile
            # sentinel (observability/sentinel.py) reports this SAME
            # inventory dict as `expected_programs` at runtime, so the
            # static (CI) and runtime (postmortem / sentinel report)
            # views of "what may ever compile" are one schema a
            # consumer can diff field for field
            from paddle_tpu.observability import (COMPILE_EVENT,
                                                  RECOMPILES_METRIC)
            out["observability"] = {
                "sentinel": {
                    "expected_programs": inventory,
                    "compile_event": COMPILE_EVENT,
                    "metric": RECOMPILES_METRIC,
                    "schema": "paddle_tpu.program_inventory/1",
                }}
        # the speculative engine's inventory (ISSUE r15): the same
        # schema over the draft/verify tick programs — the static
        # proof that speculation keeps ≤2 programs per width bucket
        spec_geom = next((g for g in geoms if g.spec_k), None)
        if spec_geom is not None:
            out["serving_programs_spec"] = program_inventory(spec_geom)
    if args.suite in ("all", "training"):
        # the training-schedule counterpart of serving_programs: the
        # pipeline schedules' expected trip/phase inventory (tick
        # counts, per-op-kind rank-ticks, modeled efficiency) — one
        # diffable schema next to the serving program inventory, and
        # the same numbers the collective-consistency pass pins via
        # expected_scan_trips on the traced train steps
        from paddle_tpu.analysis.training_graphs import (
            schedule_inventory)
        out["pipeline_schedules"] = schedule_inventory()
    if rw_table is not None:
        out["rewrite"] = rw_table
    if args.planner:
        # the auto-parallel planner as a CI section: the ONE shared
        # smoke space (planner.SMOKE_KNOBS — the same knobs
        # `tools/auto_parallel.py --smoke` plans) must produce a
        # non-empty ranked plan whose winner trace-verifies under the
        # planner contract — prediction-vs-trace deltas ride the same
        # Finding JSON schema as every other pass
        from paddle_tpu.analysis.planner import (SMOKE_KNOBS,
                                                 plan_auto_parallel)
        from paddle_tpu.models import llama as L
        kn = dict(SMOKE_KNOBS)
        plan = plan_auto_parallel(
            L.LlamaConfig.tiny(), kn.pop("devices"), **kn)
        out["planner"] = plan
        ok = ok and bool(plan["plans"]) and bool(
            plan.get("verification", {}).get("ok"))
    out["hbm"] = [
        {"graph": name, "peak_bytes": est.peak_bytes,
         "input_bytes": est.args_bytes,
         "top": [{"bytes": b, "value": lbl} for b, lbl in est.top]}
        for name, est in sorted(hbm.items())]

    if args.suite in ("all", "concurrency") or args.ci:
        # the static half of the concurrency analysis (guarded-by,
        # lock-order cycles, noqa discipline) over paddle_tpu/serving/
        # — pure AST, no tracing, well under the --ci 10s budget
        from paddle_tpu.analysis.concurrency import check_tree
        cres = check_tree()
        out["concurrency"] = {
            "by_rule": cres["by_rule"],
            "findings": cres["findings"],
            "suppressed": cres["suppressed"],
            "lock_free_reads": cres["lock_free_reads"],
            "requires": cres["requires"],
            "locks": cres["locks"],
            "lock_order": cres["lock_order"],
            "errors": cres["errors"],
        }
        ok = ok and not cres["findings"] and not cres["errors"]

    if args.suite in ("all", "kernels") or args.ci:
        # the Pallas kernel auditor (analysis/kernel_audit.py): static
        # VMEM/grid/DMA/accumulator proofs over every registered kernel
        # geometry plus every swept winner in the autotune store — jaxpr
        # inspection only, no Mosaic compiles, well inside the --ci
        # budget. `--json` carries the per-launch VMEM table and the
        # per-rule finding/evaluation counts; rule_evals being all
        # non-zero is the non-vacuity proof (a rule that evaluated
        # nothing proves nothing)
        from paddle_tpu.analysis.kernel_audit import run_kernel_audit
        kres = run_kernel_audit()
        out["kernels"] = kres
        ok = ok and kres["ok"]

    if args.ci:
        from paddle_tpu.analysis.source_lint import lint_tree
        root = os.path.join(os.path.dirname(__file__), "..")
        src = lint_tree(root)
        out["source"] = [
            {"file": p, "rule": r, "line": ln, "message": m}
            for p, r, ln, m in src]
        ok = ok and not src
        ruff_ran, ruff_ok, ruff_out = run_ruff(root)
        out["ruff"] = {"ran": ruff_ran, "ok": ruff_ok}
        if not ruff_ok:
            out["ruff"]["output"] = ruff_out[-4000:]
        ok = ok and ruff_ok
    out["seconds"] = round(time.time() - t0, 2)

    if args.json:
        print(json.dumps(out, indent=2))
    else:
        from paddle_tpu.analysis import Severity
        for f in report.findings:
            if f.severity == Severity.INFO and not args.verbose:
                continue
            print(f)
        if args.verbose:
            for name, est in sorted(hbm.items()):
                print(est)
        if "concurrency" in out:
            c = out["concurrency"]
            for item in c["findings"]:
                print(f"[error] {item['rule']} @ {item['path']}:"
                      f"{item['line']}: {item['message']}")
            lo = c["lock_order"]
            print(f"concurrency: {len(c['locks'])} locks, "
                  f"{len(lo['edges'])} order edges, "
                  f"{len(lo['cycles'])} cycles, "
                  f"{sum(c['by_rule'].values())} findings "
                  f"({len(c['suppressed'])} suppressed)")
        if "kernels" in out:
            k = out["kernels"]
            for item in k["findings"]:
                print(f"[error] {item['pass']} @ {item['graph']}: "
                      f"{item['message']}")
            for msg in k["errors"]:
                print(f"[error] kernel-audit: {msg}")
            for w in k["stale_waivers"]:
                print(f"[error] kernel-audit stale waiver: "
                      f"{w['kernel']} {w['rule']} {w['match']!r}")
            peak = max((row["total_bytes"] for row in k["vmem"]),
                       default=0)
            print(f"kernel audit: {len(k['kernels'])} kernels, "
                  f"{k['launches']} launches, peak VMEM "
                  f"{peak / 2**20:.2f} MiB, "
                  f"{sum(k['by_rule'].values())} findings "
                  f"({len(k['suppressed'])} suppressed)")
        if args.ci:
            for item in out.get("source", []):
                print(f"[error] source-lint @ {item['file']}:"
                      f"{item['line']}: {item['rule']} "
                      f"{item['message']}")
            r = out["ruff"]
            print(f"ruff: {'ok' if r['ok'] else 'FAILED'}"
                  f"{'' if r['ran'] else ' (not installed)'}")
            if not r["ok"]:
                print(out["ruff"].get("output", ""))
        if args.planner:
            pl = out["planner"]
            win = pl["winner"]["label"] if pl["winner"] else "<none>"
            ver = pl.get("verification", {}).get("ok")
            print(f"planner: {pl['legal']} legal plans, winner {win} "
                  f"verification {'OK' if ver else 'FAIL'}")
        print(f"graph lint: {report.summary()} in {out['seconds']}s -> "
              f"{'OK' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    from paddle_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main(sys.argv[1:]))
