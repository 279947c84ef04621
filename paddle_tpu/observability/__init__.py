"""paddle_tpu.observability — runtime evidence for the serving stack.

The static-analysis subsystem (paddle_tpu/analysis/) *proves* serving
invariants offline: the recompile pass enumerates the reachable tick
program set, the KV checker audits page ownership, the HBM estimator
bounds peaks. This package is the runtime half (ISSUE r13): the same
invariants *watched while serving*, and the evidence shipped with every
anomaly instead of reconstructed after it.

    SpanTracer       — thread-safe bounded-ring span tracer; Chrome-
                       trace/Perfetto export, one track per engine
                       phase + one per serving slot; every span is
                       also a jax.profiler.TraceAnnotation, so a
                       profiler session sees it beside the device's
                       operations (tracer.py)
    FlightRecorder   — last-N-ticks ring + JSON postmortem dumped
                       automatically on KVInvariantError / engine-loop
                       crash (flight.py)
    RecompileSentinel— jax.monitoring compile listener: any XLA compile
                       after warmup becomes a labeled WARN metric, a
                       named span and a RecompileWarning, cross-checked
                       against the static program inventory
                       (sentinel.py)
    compile_ledger   — the same listener's record of every program the
                       process materialised: traced, lowered, compiled
                       or read from the persistent cache, by name
                       (sentinel.py: compile_ledger, compile_totals,
                       ledger_health)
    step_counters    — what a compiled step counts on the device and
                       sends out by one unordered host callback (a
                       train step's expert pairs): the process's
                       registry (counters.py: emit, step_counters)
    setup_report     — time to ready: the set-up spans of the engine
                       and the trainer on the process tracer, joined
                       to the ledger (setup.py)

Wired through ``serving.ServingEngine`` (``trace=``, ``flight_ticks=``,
``recompile_sentinel=`` ctor knobs; on by default — measured overhead
≤3% of tick wall, pinned by test) and surfaced by
``tools/serving_bench.py --trace`` / ``--check-invariants`` and
``graph_lint --json``'s ``observability`` block. See
docs/OBSERVABILITY.md.
"""
from .counters import StepCounters, emit, step_counters  # noqa: F401
from .flight import FlightRecorder, default_flight_dir  # noqa: F401
from .sentinel import (COMPILE_EVENT, RECOMPILES_METRIC,  # noqa: F401
                       RecompileSentinel, RecompileWarning,
                       compile_ledger, compile_totals, ledger_health)
from .setup import in_setup_span, setup_report, setup_span  # noqa: F401
from .tracer import (Span, SpanTracer, current_span,  # noqa: F401
                     process_tracer)

__all__ = ["SpanTracer", "Span", "current_span", "process_tracer",
           "FlightRecorder", "default_flight_dir", "RecompileSentinel",
           "RecompileWarning", "COMPILE_EVENT", "RECOMPILES_METRIC",
           "compile_ledger", "compile_totals", "ledger_health", "setup_span",
           "in_setup_span", "setup_report", "StepCounters", "step_counters",
           "emit"]
