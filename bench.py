"""Benchmark: flagship Llama pretrain step MFU on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Baseline (BASELINE.md north star): 40% MFU for Llama pretrain. vs_baseline
is measured MFU / 0.40.

Two configs are measured:
  * flagship — a 1.72B wide decoder (D=4096, L=6, F=16384, GQA 32/8)
    sized to fill one v5e chip; the headline ``value``.
  * deep — a reference-shaped 16-layer model (D=2560, L=16, F=10240),
    reported as ``deep_model_*``: proof the MFU survives depth, i.e.
    the per-layer rmsnorm/rope/scan overheads between GEMMs are paid
    down (fused pallas kernels), not hidden by a shallow-wide shape.

Flash attention runs the Pallas kernel in strict mode — a silent dense
fallback fails the bench instead of polluting the number. Timing uses
chained steps with a single final sync: each step's donated state feeds
the next, so device execution serializes, and the per-sync host cost
is cancelled by differencing a short and a long chain rather than
miscounted per-step.

Runs on a TPU only: without one it exits non-zero (a CPU timing is
never printed under a device metric's name). No number from this
script has been recorded on the current machine — see PERF.md.
"""
import json
import time

import jax
import jax.numpy as jnp

from paddle_tpu.compile_cache import enable_compile_cache

# bf16 peak FLOP/s by ``device_kind`` (Google Cloud TPU documentation,
# per-chip figures). A device that is not listed is an error, never a
# default: an MFU against the wrong peak is a wrong number.
PEAK_BF16 = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
}


def peak_flops(dev) -> float:
    try:
        return PEAK_BF16[dev.device_kind]
    except KeyError:
        raise SystemExit(
            f"no bf16 peak listed for device_kind {dev.device_kind!r} "
            f"(platform {dev.platform!r}); known: {sorted(PEAK_BF16)}")


def count_params(cfg) -> int:
    D, L_, V = cfg.hidden_size, cfg.num_hidden_layers, cfg.vocab_size
    H, Hkv, Dh, F = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim, cfg.intermediate_size)
    return (V * D * 2  # embed + lm_head
            + L_ * (D * H * Dh + 2 * D * Hkv * Dh + H * Dh * D + 3 * D * F))


def measure_step(cfg, B, T, iters, mesh, L):
    """Slope-timed train-step seconds + final loss for one config."""
    step, init = L.make_train_step(cfg, mesh)
    state = init(jax.random.PRNGKey(0))
    batch = L.make_batch(cfg, batch_size=B, seq_len=T, mesh=mesh)

    def run_n(n, state):
        loss = None
        for _ in range(n):
            state, loss = step(state, batch)
        return state, float(loss)  # single host sync for the chain

    state, _ = run_n(2, state)  # compile + warmup
    n0, n1 = max(iters // 4, 1), iters
    # repeat and take min of EACH chain time separately before
    # differencing: min-of-the-difference would prefer a repeat
    # whose short chain got slowed by a time-share neighbour
    # (inflated subtrahend -> understated dt -> overstated MFU)
    t_short = t_long = float("inf")
    loss = None
    for _ in range(2):
        t0 = time.perf_counter()
        state, _ = run_n(n0, state)
        t_short = min(t_short, time.perf_counter() - t0)
        t0 = time.perf_counter()
        state, loss = run_n(n1, state)
        t_long = min(t_long, time.perf_counter() - t0)
    dt = (t_long - t_short) / (n1 - n0)
    return dt, loss, state


def mfu_of(cfg, B, T, dt) -> float:
    # PaLM-style MFU accounting: per-token train FLOPs = 6N + 6*L*D*T
    # (causal attention term); remat recompute NOT credited (MFU, not HFU)
    flops = (6 * count_params(cfg)
             + 6 * cfg.num_hidden_layers * cfg.hidden_size * T) * (B * T)
    return flops / dt / peak_flops(jax.devices()[0])


def main():
    from paddle_tpu.models import llama as L
    from paddle_tpu.parallel import init_hybrid_mesh

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures the chip and found platform "
            f"{dev.platform!r}; there is no CPU mode")
    peak_flops(dev)  # an unlisted device_kind fails before any compile
    enable_compile_cache()
    cfg = L.LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=16384,
        num_hidden_layers=6, num_attention_heads=32,
        num_key_value_heads=8, max_position_embeddings=2048,
        dtype=jnp.bfloat16, remat=True, use_flash_attention="pallas")
    # B=5 came from a sweep on a machine that is gone (removed
    # records); not re-measured here
    B, T, iters = 5, 2048, 24
    deep_cfg = L.LlamaConfig(
        vocab_size=32000, hidden_size=2560, intermediate_size=10240,
        num_hidden_layers=16, num_attention_heads=20,
        num_key_value_heads=4, max_position_embeddings=2048,
        dtype=jnp.bfloat16, remat=True, use_flash_attention="pallas")
    deep_B, deep_iters = 8, 8

    hm = init_hybrid_mesh(dp=1, pp=1, tp=1, set_global=False)
    with hm.mesh:
        dt, loss, state = measure_step(cfg, B, T, iters, hm.mesh, L)

        # decode throughput on the same model (KV-cache generate path)
        from functools import partial
        gen_new = 64
        prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 128),
                                    0, cfg.vocab_size, dtype=jnp.int32)
        gen = jax.jit(partial(L.generate, cfg=cfg,
                              max_new_tokens=gen_new))
        out = gen(state["params"], prompt)
        int(out[0, -1])  # host read = sync
        t0 = time.perf_counter()
        out = gen(state["params"], prompt)
        int(out[0, -1])  # host sync
        decode_tok_s = gen_new / (time.perf_counter() - t0)

        # weight-only int8 decode (quantization/decode.py): same
        # model, projections+lm_head stored int8 + per-channel f32
        # scales — decode is weight-bandwidth-bound, so this halves
        # the dominant byte stream (docs/PERF.md decode section)
        from paddle_tpu.quantization.decode import quantize_for_decode
        qparams = quantize_for_decode(state["params"], cfg)
        out = gen(qparams, prompt)
        int(out[0, -1])
        t0 = time.perf_counter()
        out = gen(qparams, prompt)
        int(out[0, -1])
        decode_int8_tok_s = gen_new / (time.perf_counter() - t0)

        # batched MIXED-LENGTH decode: paged KV (block tables, pallas
        # paged_attention) vs the dense cache padded to max length.
        # 32 concurrent streams, prompts 64..2016 tokens; decode time
        # isolated by differencing a long and a short generation
        # (identical prefill cancels).
        Bs = 32
        lens_mix = [64 + (2016 - 64) * i // (Bs - 1) for i in range(Bs)]
        t0max = 2048  # splash prefill needs T % 512 == 0
        pad_prompt = jax.random.randint(
            jax.random.PRNGKey(3), (Bs, t0max), 0, cfg.vocab_size,
            dtype=jnp.int32)
        lens_arr = jnp.asarray(lens_mix, jnp.int32)
        n_long, n_short = 40, 8

        def timed(fn, *args):
            out = fn(*args)          # compile + warmup
            int(out[0, -1])
            best = float("inf")
            for _ in range(2):
                t0 = time.perf_counter()
                out = fn(*args)
                int(out[0, -1])
                best = min(best, time.perf_counter() - t0)
            return best

        def paged_for(n):
            fn = jax.jit(partial(L.generate_paged, cfg=cfg,
                                 max_new_tokens=n, page_size=32,
                                 attn_impl="pallas"))
            return lambda: fn(state["params"], pad_prompt, lens_arr)

        def dense_for(n):
            fn = jax.jit(partial(L.generate, cfg=cfg,
                                 max_new_tokens=n))
            return lambda: fn(state["params"], pad_prompt)

        def rate2(mk):
            return Bs * (n_long - n_short) / (
                timed(mk(n_long)) - timed(mk(n_short)))

        paged_tok_s = rate2(paged_for)
        dense_batch_tok_s = rate2(dense_for)

        def paged_int8_for(n):
            fn = jax.jit(partial(L.generate_paged, cfg=cfg,
                                 max_new_tokens=n, page_size=32,
                                 attn_impl="pallas"))
            return lambda: fn(qparams, pad_prompt, lens_arr)

        paged_int8_tok_s = rate2(paged_int8_for)

        # serving prefix cache (r8): warm-shared-prefix TTFT and
        # hit-token throughput through the continuous-batching
        # engine. Geometry keeps every flash shape % 128 == 0 so
        # the strict splash prefill path runs: shared header 128
        # tokens (4 pages), suffix bucket 128 -> chunk program sees
        # S = 256. Methodology: docs/PERF.md serving note.
        import numpy as onp
        from paddle_tpu.serving import ServingEngine
        shared_n, tail_n, s_mnt = 128, 128, 8
        rng_s = onp.random.RandomState(7)
        header = rng_s.randint(0, cfg.vocab_size,
                               (shared_n,)).astype(onp.int32)

        def s_prompt():
            t = rng_s.randint(0, cfg.vocab_size,
                              (tail_n,)).astype(onp.int32)
            return onp.concatenate([header, t])

        eng = ServingEngine(
            state["params"], cfg, max_batch=4, page_size=32,
            max_prompt_len=shared_n + tail_n,
            prompt_buckets=[128, 256], max_new_tokens_cap=s_mnt)
        # seed the header chain (compiles the cold whole-prompt
        # shape), then one warm request to compile the suffix-chunk
        # shape (suffix bucket 128 x 4 attached header pages) —
        # only the SECOND warm request is measured
        eng.submit(s_prompt(), s_mnt).result(timeout=600)
        eng.submit(s_prompt(), s_mnt).result(timeout=600)
        h_warm = eng.submit(s_prompt(), s_mnt)
        h_warm.result(timeout=600)
        serving_prefix_ttft_ms = h_warm.ttft_s * 1e3
        c0 = eng.stats()["counters"]["prefix_hit_tokens"]
        t0 = time.perf_counter()
        hs = [eng.submit(s_prompt(), s_mnt) for _ in range(8)]
        for h in hs:
            h.result(timeout=600)
        wall_s = time.perf_counter() - t0
        c1 = eng.stats()["counters"]["prefix_hit_tokens"]
        serving_prefix_tok_s = (c1 - c0) / wall_s
        eng.close()

        # free the flagship's HBM (and its ~1.7 GB int8 copy) before
        # the deep model's compile/steps
        del state, qparams, paged_int8_for
        d_dt, d_loss, d_state = measure_step(
            deep_cfg, deep_B, T, deep_iters, hm.mesh, L)
        del d_state
        deep = {
            "deep_model_mfu": round(mfu_of(deep_cfg, deep_B, T, d_dt), 4),
            "deep_model_layers": deep_cfg.num_hidden_layers,
            "deep_model_params_b": round(count_params(deep_cfg) / 1e9, 3),
            "deep_model_step_ms": round(d_dt * 1e3, 2),
        }

    mfu = mfu_of(cfg, B, T, dt)
    print(json.dumps({
        "metric": "llama_pretrain_mfu_1chip",
        "value": round(mfu, 4),
        "unit": "fraction_of_peak_bf16",
        "vs_baseline": round(mfu / 0.40, 4),
        "tokens_per_sec": round(B * T / dt, 1),
        "decode_tokens_per_sec": round(decode_tok_s, 1),
        "decode_int8_tokens_per_sec": round(decode_int8_tok_s, 1),
        "paged_decode_tokens_per_sec": round(paged_tok_s, 1),
        "paged_decode_int8_tokens_per_sec": round(paged_int8_tok_s, 1),
        "dense_batch_decode_tokens_per_sec": round(dense_batch_tok_s, 1),
        "serving_prefix_hit_tokens_per_sec": round(serving_prefix_tok_s, 1),
        "serving_prefix_ttft_ms": round(serving_prefix_ttft_ms, 2),
        "step_ms": round(dt * 1e3, 2),
        "params_b": round(count_params(cfg) / 1e9, 3),
        "loss": float(loss),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        **deep,
    }))


if __name__ == "__main__":
    main()
