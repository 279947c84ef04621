"""One-variant-at-a-time perf probe for the llama bench config.

Slope-method timing: run N chained device-side iterations with a single
host sync, for two values of N; per-iter time = slope. This cancels the
per-sync host<->device cost out of the estimate.

Usage: python tools/perf_probe.py <mode> [D L H KV B T F [remat]]
modes: step | fwd | grad | grad_dense | grad_nosm
"""
import sys
import time

import jax
import jax.numpy as jnp

from paddle_tpu.models import llama as L
from paddle_tpu.parallel import init_hybrid_mesh


def slope_time(run_n, ns=(4, 12)):
    """run_n(n) must execute n chained iterations then sync once."""
    run_n(2)  # warmup/compile
    times = []
    for n in ns:
        t0 = time.perf_counter()
        run_n(n)
        times.append(time.perf_counter() - t0)
    return (times[1] - times[0]) / (ns[1] - ns[0]) * 1e3


def main():
    mode = sys.argv[1] if len(sys.argv) > 1 else "step"
    hidden = int(sys.argv[2]) if len(sys.argv) > 2 else 1024
    layers = int(sys.argv[3]) if len(sys.argv) > 3 else 16
    heads = int(sys.argv[4]) if len(sys.argv) > 4 else 8
    kv = int(sys.argv[5]) if len(sys.argv) > 5 else 8
    B = int(sys.argv[6]) if len(sys.argv) > 6 else 4
    T = int(sys.argv[7]) if len(sys.argv) > 7 else 2048
    ffn = int(sys.argv[8]) if len(sys.argv) > 8 else 4 * hidden
    flags = set(sys.argv[9:])
    remat = "remat" in flags

    cfg = L.LlamaConfig(
        vocab_size=32000, hidden_size=hidden, intermediate_size=ffn,
        num_hidden_layers=layers, num_attention_heads=heads,
        num_key_value_heads=kv, max_position_embeddings=T,
        dtype=jnp.bfloat16, remat=remat, use_flash_attention=True,
        use_fused_norm_rope=False if "nofuse" in flags else "auto")
    hm = init_hybrid_mesh(dp=1, pp=1, tp=1, set_global=False)
    with hm.mesh:
        batch = L.make_batch(cfg, batch_size=B, seq_len=T, mesh=hm.mesh)
        if mode == "step":
            step, init = L.make_train_step(cfg, hm.mesh)
            state = init(jax.random.PRNGKey(0))
            st = [state]

            def run_n(n):
                l = None
                for _ in range(n):
                    s, l = step(st[0], batch)
                    st[0] = s
                float(l)
        else:
            params = L.init_params(cfg, jax.random.PRNGKey(0))
            params = L.shard_params(params, cfg, hm.mesh)
            if mode == "fwd":
                @jax.jit
                def g(p, t):
                    lg = L.forward(p, t, cfg, hm.mesh)
                    # full-reduction feedback so successive calls chain
                    # device-side AND nothing can be dead-code-eliminated
                    # or narrowed (a single-element chain lets XLA slice
                    # the whole lm_head matmul down to one element)
                    s = lg.astype(jnp.float32).sum()
                    return (s * 0).astype(jnp.int32) + (s > 1e30).astype(
                        jnp.int32)

                def run_n(n):
                    d = jnp.int32(0)
                    for _ in range(n):
                        d = g(params, batch["tokens"] + d)
                    int(d)
            else:
                if mode == "grad":
                    lf = lambda p, b: L.loss_fn(p, b, cfg, hm.mesh)
                elif mode == "grad_dense":
                    cfg2 = L.LlamaConfig(
                        **{**cfg.__dict__, "use_flash_attention": False})
                    lf = lambda p, b: L.loss_fn(p, b, cfg2, hm.mesh)
                elif mode == "grad_nosm":
                    def lf(p, b):
                        lg = L.forward(p, b["tokens"], cfg, hm.mesh)
                        return (lg * lg).astype(jnp.float32).mean()
                else:
                    raise SystemExit(f"unknown mode {mode}")

                @jax.jit
                def g(p, b):
                    l, grads = jax.value_and_grad(lf)(p, b)
                    # fold every grad leaf into the chained scalar so the
                    # backward pass cannot be dead-code-eliminated
                    gs = sum(x.astype(jnp.float32).sum()
                             for x in jax.tree_util.tree_leaves(grads))
                    s = l + gs
                    return (s * 0).astype(jnp.int32) + (s > 1e30).astype(
                        jnp.int32)

                def run_n(n):
                    d = jnp.int32(0)
                    for _ in range(n):
                        d = g(params, {"tokens": batch["tokens"] + d,
                                       "labels": batch["labels"]})
                    int(d)
        ms = slope_time(run_n)

    D, L_, V = cfg.hidden_size, cfg.num_hidden_layers, cfg.vocab_size
    H, Hkv, Dh, F = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim, cfg.intermediate_size)
    n_params = (V * D * 2
                + L_ * (D * H * Dh + 2 * D * Hkv * Dh + H * Dh * D + 3 * D * F))
    tokens = B * T
    mult = 2 if mode == "fwd" else 6
    flops = (mult * n_params + mult * L_ * D * T) * tokens
    mfu = flops / (ms / 1e3) / 197e12
    print(f"mode={mode} D={hidden} L={layers} B={B} T={T} F={ffn} "
          f"remat={remat} params={n_params/1e9:.3f}B ms={ms:.2f} MFU={mfu:.4f}")


if __name__ == "__main__":
    from paddle_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
