"""ResNet-50 training-step MFU on one chip — north-star metric #2.

BASELINE.md: "CINN-replacement (XLA) ResNet-50 MFU". The conv stack is
the real user Layer (models/resnet.py resnet50) traced into ONE jitted
XLA step via the same bind-params capture to_static/Engine use, with
AMP O1 auto_cast putting the convs on the MXU in bf16 and an SGD
momentum update fused into the step. (The Engine path compiles the
identical program; its slot-materialising first step runs EAGERLY,
op by op — the functional form here skips that, nothing else
differs.)

FLOP accounting: the compiled program's own XLA cost_analysis (no
remat, so HFU == MFU); falls back to the 2*4.09 GMAC torchvision
convention * 3 (fwd+bwd) if the backend hides cost analysis.

Run (TPU): python tools/resnet_bench.py

Profile mode — the measurement behind the conv rewrite passes
(analysis/rewrite_conv.py):

    python tools/resnet_bench.py --profile out.json [--mode infer]
        [--depth 50] [--image 224]

emits the per-region table (analysis/resnet_profile.py): every site
the rewrite passes match, slope-timed and XLA-cost-analyzed baseline
vs rewritten, plus the full-graph A/B. Batch comes from
RESNET_BENCH_B (keep it small on CPU).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from bench import peak_flops as _peak_flops  # the one peaks table


def peak_flops() -> float:
    return _peak_flops(jax.devices()[0])


def run_profile(path: str, mode: str, depth: int, image: int) -> None:
    from paddle_tpu.analysis.resnet_profile import profile_resnet

    B = int(os.environ.get("RESNET_BENCH_B", "8"))
    prof = profile_resnet(depth=depth, image=image, batch=B, mode=mode)
    with open(path, "w") as f:
        json.dump(prof, f, indent=1)
    hdr = (f"{'region':<34} {'rule':<20} {'n':>2} {'GF':>7} "
           f"{'MB/op':>8} {'MB/fus':>8} {'ms':>8} {'%step':>6} "
           f"{'MB(rw)':>8} {'ms(rw)':>8}")
    print(hdr)
    print("-" * len(hdr))
    for r in prof["regions"]:
        rw = r["rewritten"]
        print(f"{r['name']:<34} {r['rule']:<20} {r['count']:>2} "
              f"{r['flops'] / 1e9:>7.2f} {r['bytes'] / 1e6:>8.2f} "
              f"{r['fused']['bytes'] / 1e6:>8.2f} "
              f"{r['ms']:>8.3f} {str(r['pct_of_step']):>6} "
              f"{rw['bytes'] / 1e6:>8.2f} {rw['ms']:>8.3f}")
    t = prof["totals"]
    print(f"totals: per-op {t['baseline_per_op']['bytes'] / 1e6:.1f} MB, "
          f"region-fused {t['baseline_fused']['bytes'] / 1e6:.1f} MB, "
          f"rewritten {t['rewritten']['bytes'] / 1e6:.1f} MB -> "
          f"bytes_ratio per-op {t['bytes_ratio_per_op']}, fused "
          f"{t['bytes_ratio_fused']}; ms_ratio {t['ms_ratio']}")
    fg = prof["full_graph"]
    print(f"full-graph: {prof['step_ms']:.2f} -> "
          f"{prof['step_ms_rewritten']:.2f} ms, bytes_ratio "
          f"{fg['bytes_ratio']} ({fg['note']})")
    print(f"wrote {path}")


def main():
    import optax
    import paddle_tpu as pt
    from paddle_tpu.autograd import tape as _tape
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.resnet import resnet50
    from paddle_tpu.static.nn import _bind

    B = int(os.environ.get("RESNET_BENCH_B", "128"))
    pt.seed(0)
    model = resnet50(num_classes=1000)
    params = model.parameters()
    bufs = list(model.buffers())            # BN running stats

    def loss_arrays(parrs, barrs, x, y):
        with _bind(params, parrs), _bind(bufs, barrs), _tape.no_grad(), \
                pt.amp.auto_cast(True):
            out = model(Tensor(x))
            l = pt.nn.functional.cross_entropy(
                out.astype("float32"), Tensor(y)).mean()
            new_b = [b._data for b in bufs]
        return l.data, new_b

    opt = optax.sgd(0.1, momentum=0.9)

    def step(parrs, barrs, opt_state, x, y):
        (loss, new_b), grads = jax.value_and_grad(
            loss_arrays, has_aux=True)(parrs, barrs, x, y)
        updates, opt_state = opt.update(grads, opt_state, parrs)
        parrs = optax.apply_updates(parrs, updates)
        return parrs, new_b, opt_state, loss

    parrs = [p._data for p in params]
    barrs = [b._data for b in bufs]
    opt_state = opt.init(parrs)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, 3, 224, 224).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 1000, (B,)).astype(np.int32))
    # compile ONCE ahead-of-time; the same executable serves warmup,
    # timing, and cost_analysis (calling the jit-wrapped fn AND
    # lower().compile() would build the program twice)
    comp = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
        parrs, barrs, opt_state, x, y).compile()
    jstep = comp

    def run_n(n, parrs, barrs, opt_state):
        loss = None
        for _ in range(n):
            parrs, barrs, opt_state, loss = jstep(parrs, barrs,
                                                  opt_state, x, y)
        return parrs, barrs, opt_state, float(loss)  # one host sync

    parrs, barrs, opt_state, _ = run_n(2, parrs, barrs, opt_state)
    n0, n1 = 2, 10
    t = {}
    for n in (n0, n1):
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            parrs, barrs, opt_state, loss = run_n(n, parrs, barrs,
                                                  opt_state)
            best = min(best, time.perf_counter() - t0)
        t[n] = best
    dt = (t[n1] - t[n0]) / (n1 - n0)

    try:
        from paddle_tpu.analysis.hbm import xla_cost_analysis
        flops = float(xla_cost_analysis(comp)["flops"])
        source = "xla_cost_analysis"
    except Exception:
        flops = 3 * 2 * 4.089e9 * B
        source = "analytic_4.09GMAC"
    mfu = flops / dt / peak_flops()
    print(json.dumps({
        "metric": "resnet50_train_mfu_1chip",
        "value": round(mfu, 4),
        "unit": "fraction_of_peak_bf16",
        "batch": B,
        "step_ms": round(dt * 1e3, 2),
        "images_per_sec": round(B / dt, 1),
        "flops_per_step": flops,
        "flop_source": source,
        "loss": loss,
        "backend": jax.default_backend(),
    }))


if __name__ == "__main__":
    from paddle_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", metavar="OUT_JSON", default=None,
                    help="write the per-region rewrite profile and exit")
    ap.add_argument("--mode", choices=("infer", "train"),
                    default="infer")
    ap.add_argument("--depth", type=int, default=50)
    ap.add_argument("--image", type=int, default=224)
    args = ap.parse_args()
    if args.profile:
        run_profile(args.profile, args.mode, args.depth, args.image)
    else:
        main()
