"""Measure pipeline schedule efficiency at north-star scale — A/B over
the lockstep scan, rank-asymmetric 1F1B, and ZB-style W-deferral.

The measurement is structural (the r4-established method): trace the
ACTUAL train step on the CPU mesh and read the schedule scan's trip
count out of the jaxpr — for the rank-asymmetric schedules the scan
lives inside the shard_map body, which the shared jaxpr walker
(analysis/collectives.scan_trip_counts) sees through. Per schedule the
efficiency those ticks imply:

  * lockstep  — every tick runs all S slots fwd+bwd (masked fill/drain
                included), so efficiency = M / ticks;
  * 1f1b      — rank-asymmetric half-step ticks (one F or one full
                backward per rank), useful = 2·V·M per rank, so
                efficiency = 2·V·M / ticks (= the reference per-rank
                1F1B bubble 1 - (S-1)/(VM+S-1) when the builder hits
                its bound — asserted);
  * zb        — F / input-grad B / deferred weight-grad W unit ticks,
                useful = 3·M per rank, efficiency = 3·M / ticks.

Reference comparison columns: the interleaved-1F1B analytic bubble
(pipeline_parallel.py forward_backward_pipeline, VPP chunks V).

Composed geometries (r19): ``--dp``/``--tp`` run the SAME tick-count
A/B with data/tensor parallelism composed into the async schedules'
shard_map (the op-table scan is along pp only, so tick counts — and
therefore the efficiency columns — must be IDENTICAL to the dp=tp=1
run at every geometry; the measured table in docs/PERF.md r19 pins
that parity). dp·tp·pp must fit the 8 virtual host devices.

Run:  python tools/pipeline_ceiling.py
      python tools/pipeline_ceiling.py --schedule lockstep 1f1b zb \
          --json out.json
      python tools/pipeline_ceiling.py --schedule zb --pp 2 --dp 2
"""
import argparse
import json
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402

#: CLI schedule name -> (cfg.pp_schedule, useful rank-ticks factor x M
#: — 1 lockstep fwd+bwd tick, 2 half-step F/B ticks, 3 F/B/W unit
#: ticks). The model name comes from the one exported
#: parallel.pipeline_async.PP_SCHEDULES mapping, so this tool cannot
#: drift from the executor dispatch.
SCHEDULES = {
    "lockstep": ("1f1b", 1),
    "1f1b": ("1f1b_async", 2),
    "zb": ("zb", 3),
}


def measure(S, M, schedule, dp=1, tp=1):
    """Trace the real train step, return (ticks, efficiency)."""
    from paddle_tpu.analysis.collectives import scan_trip_counts
    from paddle_tpu.models import llama as L
    from paddle_tpu.parallel import init_hybrid_mesh
    from paddle_tpu.parallel.pipeline_1f1b import schedule_ticks
    from paddle_tpu.parallel.pipeline_async import PP_SCHEDULES

    pp_schedule, factor = SCHEDULES[schedule]
    model = PP_SCHEDULES[pp_schedule][0]
    cfg = L.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=8, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=128,
        dtype=jnp.float32, use_flash_attention=False, remat=False,
        pp_stages=S, pp_schedule=pp_schedule, num_microbatches=M)
    hm = init_hybrid_mesh(dp=dp, pp=S, tp=tp, set_global=False)
    with hm.mesh:
        step, init = L.make_train_step(cfg, hm.mesh)
        state = init(jax.random.PRNGKey(0))
        batch = L.make_batch(cfg, batch_size=M * 2 * dp, seq_len=16,
                             mesh=hm.mesh)
        jaxpr = jax.make_jaxpr(step.__wrapped__)(state, batch)
    # exclude the per-stage layer scans (trip count <= layers) so an
    # analytic tick count that happens to collide with one can never
    # mask a schedule/model desync; at tiny M the schedule scan itself
    # can run <= layers ticks, so fall back to the unfiltered set
    # rather than measuring nothing
    all_lengths = set(scan_trip_counts(jaxpr))
    lengths = {n for n in all_lengths if n > cfg.num_hidden_layers}
    if not lengths:
        lengths = all_lengths
    expect = schedule_ticks(S, M, 1, schedule=model)
    if expect in lengths:
        ticks = expect
    else:
        # report what is actually traced, flagging divergence from the
        # analytic count rather than refusing to measure it
        ticks = max(lengths)
        print(f"NOTE: pp={S} M={M} {schedule}: traced schedule runs "
              f"{ticks} ticks, analytic model says {expect}",
              flush=True)
    return ticks, factor * M / ticks


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--schedule", nargs="+",
                    choices=sorted(SCHEDULES), default=["lockstep",
                                                        "1f1b", "zb"])
    ap.add_argument("--pp", nargs="+", type=int, default=[2, 4, 8])
    ap.add_argument("--mb", nargs="+", type=int, default=[8, 16, 32])
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel degree composed into the "
                         "schedules (r19); batch rows shard over it")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree composed into the "
                         "stage bodies (r19, manual collectives)")
    ap.add_argument("--json", metavar="PATH",
                    help="write the efficiency table as JSON")
    args = ap.parse_args(argv)

    rows = []
    cols = " | ".join(f"{s} eff" for s in args.schedule)
    geo = (f" (dp={args.dp} tp={args.tp})"
           if args.dp > 1 or args.tp > 1 else "")
    print(f"| pp | M | {cols} | ref 1F1B eff (V=1) | "
          f"ref interleaved eff (V=2) |{geo}")
    print("|---|---|" + "---|" * (len(args.schedule) + 2))
    for S in args.pp:
        for M in args.mb:
            effs = {}
            for sched in args.schedule:
                ticks, eff = measure(S, M, sched, dp=args.dp,
                                     tp=args.tp)
                effs[sched] = {"ticks": ticks, "efficiency": round(eff,
                                                                   4)}
            ref1 = 1 - (S - 1) / (M + S - 1)
            refv = 1 - (S - 1) / (2 * M + S - 1)
            rows.append({"pp": S, "microbatches": M,
                         "dp": args.dp, "tp": args.tp,
                         "schedules": effs,
                         "ref_1f1b_eff": round(ref1, 4),
                         "ref_interleaved_v2_eff": round(refv, 4)})
            cells = " | ".join(
                f"{effs[s]['efficiency']:.3f} ({effs[s]['ticks']}t)"
                for s in args.schedule)
            print(f"| {S} | {M} | {cells} | {ref1:.3f} | {refv:.3f} |")
    out = {"schema": "paddle_tpu.pipeline_ceiling/2", "rows": rows}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    from paddle_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main(sys.argv[1:]))
