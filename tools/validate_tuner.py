"""Validate the auto-tuner's memory/cost models against reality.

Reference capability: the auto-tuner prunes candidate configs by a
memory model before measuring survivors
(python/paddle/distributed/auto_tuner/memory_cost_model.py); a model
that is badly wrong prunes good configs or launches OOM ones. This tool
scores OUR models (distributed/auto_tuner.py estimate_memory /
estimate_step_cost) against the compiler's memory analysis and measured
step time for single-chip llama configs, and prints one JSON line per
config. Results are recorded in docs/PERF.md.

Run on the real chip: python tools/validate_tuner.py
"""
import gc
import json
import time

import jax
import jax.numpy as jnp

from paddle_tpu.models import llama as L
from paddle_tpu.parallel import init_hybrid_mesh
from paddle_tpu.distributed.auto_tuner import (Candidate, ModelDesc,
                                               estimate_memory,
                                               estimate_step_cost)

CONFIGS = [
    # D, L, F, H, KV, B
    (4096, 6, 16384, 32, 8, 5),
    (2560, 16, 10240, 20, 4, 8),
    (2048, 24, 8192, 16, 4, 8),
    (1024, 8, 4096, 8, 8, 8),
]


def slope_ms(step, state, batch, ns=(2, 6)):
    def run_n(n, st):
        l = None
        for _ in range(n):
            st, l = step(st, batch)
        return st, float(l)

    state, _ = run_n(2, state)
    t = []
    for n in ns:
        t0 = time.perf_counter()
        state, _ = run_n(n, state)
        t.append(time.perf_counter() - t0)
    return (t[1] - t[0]) / (ns[1] - ns[0]) * 1e3


def main():
    for D, Ln, F, H, KV, B in CONFIGS:
        cfg = L.LlamaConfig(
            vocab_size=32000, hidden_size=D, intermediate_size=F,
            num_hidden_layers=Ln, num_attention_heads=H,
            num_key_value_heads=KV, max_position_embeddings=2048,
            dtype=jnp.bfloat16, remat=True, use_flash_attention=True)
        m = ModelDesc(hidden=D, layers=Ln, ffn=F, vocab=32000, heads=H,
                      kv_heads=KV, seq_len=2048, global_batch=B)
        c = Candidate(dp=1, tp=1, pp=1, zero=1, microbatches=1)
        est_mem = estimate_memory(m, c)
        est_ms = estimate_step_cost(m, c) * 1e3

        hm = init_hybrid_mesh(dp=1, pp=1, tp=1, set_global=False)
        with hm.mesh:
            step, init = L.make_train_step(cfg, hm.mesh)
            state = init(jax.random.PRNGKey(0))
            batch = L.make_batch(cfg, batch_size=B, seq_len=2048,
                                 mesh=hm.mesh)
            compiled = jax.jit(step.__wrapped__, donate_argnums=(0,)
                               ).lower(state, batch).compile()
            ma = compiled.memory_analysis()
            # peak live HBM ~ resident args + XLA temp (outputs alias
            # the donated args)
            real_mem = (ma.argument_size_in_bytes + ma.temp_size_in_bytes)
            ms = slope_ms(step, state, batch)
            del state, compiled, step
        gc.collect()
        print(json.dumps({
            "config": f"D{D} L{Ln} F{F} B{B}",
            "est_mem_gb": round(est_mem / 1e9, 2),
            "real_mem_gb": round(real_mem / 1e9, 2),
            "mem_err_pct": round(100 * (est_mem - real_mem) / real_mem, 1),
            "est_step_ms": round(est_ms, 1),
            "real_step_ms": round(ms, 1),
            "cost_err_pct": round(100 * (est_ms - ms) / ms, 1),
        }), flush=True)


if __name__ == "__main__":
    from paddle_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
