"""Eager-dispatch microbenchmark: µs per op with and without the vjp
cache (FLAGS_eager_vjp_cache). Run on any backend; numbers in
docs/PERF.md come from the CPU host (the overhead being measured is
host-side Python/tracing, not device compute).
"""
import time

import numpy as np


def bench(label, n=300):
    import paddle_tpu as pt

    x = pt.to_tensor(np.random.randn(64, 64).astype("float32"),
                     stop_gradient=False)
    w = pt.to_tensor(np.random.randn(64, 64).astype("float32"),
                     stop_gradient=False)

    def chain():
        y = pt.matmul(x, w)
        y = pt.nn.functional.relu(y)
        y = y + x
        y = y * 0.5
        return y.sum()

    chain()  # warm caches (1st occurrence registers keys,
    chain()  # 2nd occurrence builds the jitted entries)
    t0 = time.perf_counter()
    for _ in range(n):
        chain()
    fwd_us = (time.perf_counter() - t0) / n / 5 * 1e6

    loss = chain()
    loss.backward()
    t0 = time.perf_counter()
    for _ in range(n):
        x.clear_grad()
        w.clear_grad()
        loss = chain()
        loss.backward()
    fb_us = (time.perf_counter() - t0) / n / 5 * 1e6
    print(f"{label}: fwd {fwd_us:7.1f} us/op   fwd+bwd {fb_us:7.1f} us/op")
    return fwd_us, fb_us


def main():
    import paddle_tpu as pt

    pt.set_flags({"FLAGS_eager_vjp_cache": False})
    off = bench("vjp cache OFF")
    pt.set_flags({"FLAGS_eager_vjp_cache": True})
    on = bench("vjp cache ON ")
    print(f"speedup: fwd {off[0]/on[0]:.2f}x   fwd+bwd {off[1]/on[1]:.2f}x")


if __name__ == "__main__":
    from paddle_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
