"""Static-analysis subsystem: jaxpr lint passes + paged-KV invariant
checker for the serving AND training stacks.

The JAX-native counterpart of the reference's IR pass infrastructure
and runtime enforcement (``paddle/pir``, ``phi/core/enforce.h``):
analysis over **jaxprs** (the IR every program here already lowers
through) and over the serving stack's host-side state. Entry points:

* ``tools/graph_lint.py`` — CLI running every pass over the flagship
  llama + qwen2_moe serving graphs and the llama train-step graphs at
  the dp / dp×mp / pp(1F1B) / zero-sharded geometries (the pre-merge
  check).
* ``tools/auto_parallel.py`` — the auto-parallel planner
  (``analysis/planner.py``): search + rank the legal
  (dp, tp, pp, V, M, schedule, zero, dtype) space with a composed
  static cost model, then trace-verify the winner through the full
  pass stack under the ``planner-contract`` tolerance.
* ``ServingEngine(check_invariants=True)`` — per-tick paged-KV
  invariant checking (race-detector-style debug mode).
* ``graph_lint --suite concurrency`` — the host-side concurrency
  analysis (``analysis/concurrency.py``): static guarded-by lint +
  lock-order cycle detection over every lock in
  ``paddle_tpu/serving/``, paired with the runtime ``LockTracer`` and
  seeded schedule fuzzer (``serving/locktrace.py``).
* ``graph_lint --suite kernels`` — the Pallas kernel auditor
  (``analysis/kernel_audit.py``): static VMEM-footprint, grid/index-
  map, DMA-discipline, and accumulator-dtype proofs (KA001–KA004)
  over every registered kernel geometry plus every swept winner in
  the autotune store; the same verdict gates autotune admission
  (``ops.autotune.record(audit=True)``, audited ``lookup``).
* ``audit_engine(engine)`` — standalone audit of a live engine;
  ``audit_engine_plan(engine)`` — mpu-hint audit of an auto-parallel
  Engine's plan; ``Engine.donation_audit()`` — donation audit of the
  live jitted train step.

See docs/ANALYSIS.md for each pass's invariant and how to add one.
"""
from .concurrency import (analyze_source, analyze_tree, check_tree,
                          fuzz_fleet_scenario, mutate_remove_with)
from .collectives import (CollectiveConsistencyPass,
                          check_stage_consistency,
                          collective_cost_bytes, collective_signature,
                          scan_trip_counts)
from .donation import DonationAuditPass, jit_donation_flags
from .dtype_drift import DtypeDriftPass
from .framework import (ExactnessContract, Finding, GraphTarget,
                        LintPass, LintReport, PASS_REGISTRY,
                        REWRITE_REGISTRY, RewritePass, Severity,
                        default_passes, default_rewrites,
                        register_pass, register_rewrite, run_passes,
                        trace_graph)
from .hbm import (HbmEstimate, HbmPeakPass, estimate_hbm_peak,
                  xla_cost_analysis, xla_peak_bytes)
from .host_sync import HostSyncPass
from .kernel_audit import (ALL_RULES as KERNEL_AUDIT_RULES,
                           GATE_RULES as KERNEL_AUDIT_GATE_RULES,
                           KernelAuditError, KernelSpec,
                           VMEM_AUDIT_BUDGET, Waiver, audit_callable,
                           audit_config, audit_kernel,
                           kernel_signatures, run_kernel_audit)
from .kv_invariants import (KVInvariantError, Violation,
                            audit_defrag_plan, audit_engine,
                            audit_serving_state)
from .planner import (CostModel, PlanCost, PlanPoint,
                      PlannerContractPass, enumerate_plan_points,
                      plan_auto_parallel, price_plan_point,
                      verify_plan)
from .recompile import (RecompileHazardPass, ServingGeometry,
                        enumerate_tick_programs)
from .rewrite import (FusedRmsNormPass, Int8EpilogueFusePass,
                      RewriteResult, VerifyOutcome, count_matches,
                      rewrite_callable, rewrite_jaxpr, rewrite_target,
                      run_rewrite_suite, verify_rewrite, verify_site)
from .serving_graphs import (engine_geometry, pp_stage_targets,
                             rewrite_targets, serving_targets)
from .sharding_lint import (ShardingLintPass, audit_engine_plan,
                            spec_shard_factor)
from .training_graphs import (TRAIN_GEOMETRIES, build_train_target,
                              flagship_train_objects,
                              train_stage_targets, train_step_target,
                              training_targets)

__all__ = [
    "CollectiveConsistencyPass", "CostModel", "DonationAuditPass",
    "DtypeDriftPass",
    "ExactnessContract", "Finding", "FusedRmsNormPass", "GraphTarget",
    "HbmEstimate", "HbmPeakPass", "HostSyncPass",
    "Int8EpilogueFusePass", "KERNEL_AUDIT_GATE_RULES",
    "KERNEL_AUDIT_RULES", "KVInvariantError", "KernelAuditError",
    "KernelSpec", "LintPass",
    "LintReport", "PASS_REGISTRY", "PlanCost", "PlanPoint",
    "PlannerContractPass", "REWRITE_REGISTRY",
    "RecompileHazardPass", "RewritePass", "RewriteResult",
    "ServingGeometry", "Severity", "ShardingLintPass",
    "TRAIN_GEOMETRIES", "VMEM_AUDIT_BUDGET", "VerifyOutcome",
    "Violation", "Waiver",
    "analyze_source", "analyze_tree", "audit_callable",
    "audit_config", "audit_defrag_plan", "audit_engine",
    "audit_engine_plan", "audit_kernel",
    "audit_serving_state", "build_train_target", "check_tree",
    "check_stage_consistency", "collective_cost_bytes",
    "collective_signature", "count_matches", "default_passes",
    "default_rewrites", "engine_geometry",
    "enumerate_plan_points", "enumerate_tick_programs",
    "estimate_hbm_peak", "flagship_train_objects",
    "fuzz_fleet_scenario", "jit_donation_flags", "kernel_signatures",
    "mutate_remove_with", "plan_auto_parallel", "pp_stage_targets",
    "price_plan_point", "register_pass",
    "register_rewrite", "rewrite_callable", "rewrite_jaxpr",
    "rewrite_target", "rewrite_targets", "run_passes",
    "run_kernel_audit",
    "run_rewrite_suite", "scan_trip_counts", "serving_targets",
    "spec_shard_factor", "trace_graph", "train_stage_targets",
    "train_step_target", "training_targets", "verify_plan",
    "verify_rewrite", "verify_site", "xla_cost_analysis",
    "xla_peak_bytes",
]
