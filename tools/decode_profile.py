"""Per-op profile of the single-stream decode step + int8 A/B.

Decode is weight-bandwidth-bound: one greedy step must stream every
projection weight once, so the hard ceiling is

    steps/s <= HBM_bandwidth / bytes_per_step

(bytes_per_step = quantization.decode.decode_weight_bytes + the KV
cache read + the activation noise). This tool measures where the step's
time actually goes — the PERF.md decode counterpart of the train-side
device-op breakdown:

  * whole-step rate by slope timing (chained generate of N0 vs N1
    tokens, prefill and sync cancel in the difference);
  * the step TAIL in isolation — final_norm + lm_head + argmax sample
    on a captured hidden state (jitted alone);
  * embed lookup in isolation;
  * layer body = step − tail − embed (the scan over blocks, including
    the per-layer KV append + cached attention);
  * compiled-program cost_analysis (XLA's own flops / bytes-accessed
    estimate) for the f32-accounting cross-check;
  * the analytic bytes/step + ceiling at a given HBM bandwidth, and the
    fraction of that ceiling the measured rate achieves.

Runs the bf16/f32 params and (``int8`` flag) the weight-only-quantized
params through the SAME harness, printing both and the uplift.

``rewrites`` adds the verified-rewrite A/B (analysis/rewrite.py): the
single int8 decode step traced with the naive dequantize-then-matmul
idiom (``PADDLE_TPU_INT8_IMPL=unfused``) is measured three ways —
as-is, through the ``int8-epilogue-fuse`` rewrite (fires at jit-trace
time; the fused-rmsnorm substitution is excluded off-TPU, where its
Pallas kernel would run in interpret mode and the emulation cost would
swamp the signal), and against the hand-fused path — emitting per
variant the XLA bytes/flops per step and measured step time, plus the
rewrite deltas. This is the acceptance A/B for the optimizer passes:
the rewritten graph must beat the unfused baseline and land at (or
within noise of) the hand fusion it reproduces.

``trace=out.json`` records one observability span per measured section
(per-variant whole-step / tail / embed slope chains, the rewrite
A/B arms) and exports them as Perfetto-loadable Chrome-trace
JSON — the same exporter ``serving_bench --trace`` uses, so a profile
session and a serving run read in the same UI.

Every variant's JSON additionally carries ``spec_ceiling`` — the
acceptance-rate-parameterized SPECULATIVE decode ceiling (expected
tok/s as a function of draft length k, per-token acceptance alpha and
relative draft cost — ``spec_draft_cost=``, default 0 for the
host-side n-gram self-drafter): decode's bandwidth ceiling is per
target LAUNCH, and a verify span emits ``1 + E[accepted]`` tokens per
launch, so the PERF.md speculative projections are computed here, not
hand-derived. The measured counterpart is ``serving_bench --modes
spec_ab``.

Usage:
  python tools/decode_profile.py [flagship|deep|mid|tiny] [int8] [json]
      [rewrites] [trace=out.json] [bw=819e9] [steps=64]
      [spec_draft_cost=0.0]

``flagship`` is the 1.72B bench model (TPU-sized; expect minutes per
chain on CPU); ``mid`` (0.17B) profiles the same shape story at
CPU-friendly cost. Default: mid off-TPU, flagship on TPU.
"""
import contextlib
import json
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.models import llama as L
from paddle_tpu.quantization.decode import (decode_weight_bytes,
                                            quantize_for_decode)

# module-level so the measured helpers can annotate their sections
# without threading a tracer through every signature; None = no-op
_TRACER = None


def _span(name, **args):
    if _TRACER is None:
        return contextlib.nullcontext()
    return _TRACER.span(name, track="decode_profile", **args)


PRESETS = {
    # bench.py flagship: the 1.72B decode whose 176.7 tok/s (a removed
    # record of a machine that is gone; see ROADMAP.md) this tool was
    # written to explain
    "flagship": dict(vocab_size=32000, hidden_size=4096,
                     intermediate_size=16384, num_hidden_layers=6,
                     num_attention_heads=32, num_key_value_heads=8),
    "deep": dict(vocab_size=32000, hidden_size=2560,
                 intermediate_size=10240, num_hidden_layers=16,
                 num_attention_heads=20, num_key_value_heads=4),
    "mid": dict(vocab_size=8192, hidden_size=1024,
                intermediate_size=4096, num_hidden_layers=8,
                num_attention_heads=8, num_key_value_heads=4),
    "tiny": dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                 num_hidden_layers=4, num_attention_heads=4,
                 num_key_value_heads=2),
}


def slope(run_n, n0, n1, repeats=2):
    """Per-iteration seconds: min-per-chain, then difference (the bench.py
    convention — min of the difference would pair a slowed short chain
    with a fast long one and understate dt)."""
    run_n(2)  # compile + warmup
    t_short = t_long = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        run_n(n0)
        t_short = min(t_short, time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_n(n1)
        t_long = min(t_long, time.perf_counter() - t0)
    return (t_long - t_short) / (n1 - n0)


def speculative_ceiling(ceiling_tok_s, ks=(1, 2, 3, 4, 6, 8),
                        alphas=(0.3, 0.5, 0.7, 0.8, 0.9),
                        draft_cost: float = 0.0):
    """Acceptance-rate-parameterized speculative decode ceiling.

    Decode is weight-bandwidth-bound: the ceiling is per target-model
    LAUNCH (one launch streams every weight once, whether it scores 1
    token or a k+1-token verify span — the extra span rows are compute,
    which decode has slack of). Speculation therefore multiplies the
    per-launch ceiling by expected emitted tokens per launch:

        E[accepted | k, alpha] = alpha (1 - alpha^k) / (1 - alpha)
        tok/s(k, alpha)       = ceiling * (1 + E) / (1 + k*draft_cost)

    with iid per-token draft acceptance probability ``alpha`` and
    ``draft_cost`` = the cost of ONE draft token relative to a target
    launch (0 for the host-side n-gram self-drafter; a draft MODEL
    pays roughly its size ratio). Emitted in the JSON output so the
    PERF.md projections are computed, not hand-derived; the measured
    counterpart of (1 + E) is serving_bench spec_ab's
    ``launch_reduction``."""
    table = {}
    for k in ks:
        row = {}
        for a in alphas:
            e = float(k) if a >= 1.0 else a * (1 - a ** k) / (1 - a)
            row[f"alpha={a}"] = {
                "tok_s": round(ceiling_tok_s * (1 + e)
                               / (1 + k * draft_cost), 1),
                "launches_per_token": round(1 / (1 + e), 4),
                "expected_accepted": round(e, 3)}
        table[f"k={k}"] = row
    return {"draft_cost_per_token": draft_cost,
            "model": "iid per-token acceptance; E[acc]="
                     "a(1-a^k)/(1-a); verify span streams the same "
                     "weights as one decode step",
            "table": table}


def long_context_ceiling(cfg, bw, weight_bytes,
                         kv_lens=(4096, 16384, 65536, 102400),
                         page_size=16):
    """The long-context extension of the same bandwidth ceiling: price
    the decode step at long contexts. The ragged kernel walks a slot's
    live pages in tiles, each page once a slot (its KV heads in one
    strided copy a pool; analysis/serving_graphs.ragged_walk_model),
    with O(heads x tile) VMEM scratch whatever the table's width: the table shows the ceiling
    the bytes alone set, the tiles a step walks and the scratch it
    pins. The measured counterpart is the kernel_bench
    ``--ragged-sweep`` on the chip."""
    from paddle_tpu.analysis.serving_graphs import ragged_walk_model
    dtype_bytes = jnp.dtype(cfg.dtype).itemsize
    rows = {}
    for n in kv_lens:
        m = ragged_walk_model(
            kv_len=n, page_size=page_size, head_dim=cfg.head_dim,
            num_kv_heads=cfg.num_key_value_heads,
            num_heads=cfg.num_attention_heads,
            num_layers=cfg.num_hidden_layers,
            dtype_bytes=dtype_bytes)
        total = weight_bytes + m["kv_bytes_per_step"]
        rows[f"kv={n}"] = {
            "kv_bytes_per_step": m["kv_bytes_per_step"],
            "bw_ceiling_tok_per_s": round(bw / total, 1),
            "kv_tile_pages": m["kv_tile_pages"],
            "kv_tiles": m["kv_tiles"],
            "vmem_scratch_bytes": m["vmem_scratch_bytes"],
        }
    return {"page_size": page_size,
            "model": "ceiling = bw / (weight_bytes + kv_bytes); the "
                     "walk streams each live page once and pins "
                     "O(tile) VMEM scratch, so the bytes term alone "
                     "caps long contexts",
            "table": rows}


def kv_bytes_per_step(cfg, seq_len, dtype_bytes=None):
    """K+V read traffic of one cached-attention step at cache length
    ``seq_len`` (the write is one token — noise)."""
    if dtype_bytes is None:
        dtype_bytes = jnp.dtype(cfg.dtype).itemsize
    return (2 * cfg.num_hidden_layers * seq_len * cfg.num_key_value_heads
            * cfg.head_dim * dtype_bytes)


def profile(params, cfg, steps, prompt_len=32):
    """Measured seconds per decode step, split step/tail/embed."""
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, prompt_len), 0,
                                cfg.vocab_size, dtype=jnp.int32)

    n0 = max(steps // 4, 2)
    n1 = max(steps, n0 + 4)  # slope needs n1 > n0 (steps<=2 otherwise
    #                          divides by zero in the difference)
    gens = {n: jax.jit(lambda p, t, n=n: L.generate(p, t, cfg,
                                                    max_new_tokens=n))
            for n in (2, n0, n1)}

    def run_gen(n):
        out = gens[n](params, prompt)
        int(out[0, -1])  # host read: the only reliable sync everywhere

    with _span("step_slope"):
        step_s = slope(run_gen, n0, n1)

    # tail: final_norm + lm_head + greedy sample, jitted alone on a
    # captured hidden state (chained via a data dependency so the chain
    # cannot be executed in parallel)
    h = jnp.zeros((1, cfg.hidden_size), cfg.dtype) + 0.1

    def tail_n(p, h, n):
        def body(carry, _):
            hh = L.rms_norm(carry, p["final_norm"], cfg.rms_norm_eps)
            logits = L._mm(hh, p["lm_head"]).astype(jnp.float32)
            tok = jnp.argmax(logits, axis=-1)
            # feed the token back so steps serialize
            return carry + tok.astype(carry.dtype)[:, None] * 1e-9, tok
        _, toks = jax.lax.scan(body, h, None, length=n)
        return toks

    # scan length must be static: one jit per chain length
    tails = {n: jax.jit(lambda p, h, n=n: tail_n(p, h, n))
             for n in (2, n0, n1)}

    def run_tail(n):
        int(np.asarray(tails[n](params, h))[-1, 0])

    with _span("tail_slope"):
        tail_s = slope(run_tail, n0, n1)

    # embed lookup in isolation (chained through an index dependency)
    def embed_n(p, n):
        def body(tok, _):
            row = p["embed"][tok]
            nxt = (tok + jnp.int32(1) +
                   (row.sum() * 0).astype(jnp.int32)) % cfg.vocab_size
            return nxt, row.sum()
        _, s = jax.lax.scan(body, jnp.int32(0), None, length=n)
        return s

    embeds = {n: jax.jit(lambda p, n=n: embed_n(p, n))
              for n in (2, n0, n1)}

    def run_embed(n):
        float(np.asarray(embeds[n](params))[-1])

    with _span("embed_slope"):
        embed_s = slope(run_embed, n0, n1)

    # XLA's own accounting of ONE decode step (prefilled cache, T=1)
    cost = {}
    try:
        cache = L.init_kv_cache(cfg, 1, prompt_len + steps)
        _, cache = jax.jit(
            lambda p, t, c: L.forward_with_cache(p, t, c, 0, cfg)
        )(params, prompt, cache)
        tok = jnp.zeros((1, 1), jnp.int32)
        lowered = jax.jit(
            lambda p, t, c: L.forward_with_cache(p, t, c,
                                                 jnp.int32(prompt_len),
                                                 cfg)
        ).lower(params, tok, cache)
        from paddle_tpu.analysis.hbm import xla_cost_analysis
        ca = xla_cost_analysis(lowered.compile())
        if ca:
            cost = {"xla_flops": float(ca.get("flops", -1)),
                    "xla_bytes_accessed": float(ca.get("bytes accessed",
                                                       -1))}
    except Exception as e:  # cost_analysis is best-effort per backend
        cost = {"xla_cost_error": str(e)[:120]}

    return {
        "step_ms": step_s * 1e3,
        "tail_ms": tail_s * 1e3,          # final_norm + lm_head + sample
        "embed_ms": embed_s * 1e3,
        "layers_ms": max(step_s - tail_s - embed_s, 0.0) * 1e3,
        "tok_per_s": 1.0 / step_s,
        **cost,
    }


def rewrite_ab(params, cfg, steps, prompt_len=32):
    """The verified-rewrite A/B (docstring above): one int8 decode step
    (``forward_with_cache`` at T=1 on a prefilled cache) traced with the
    naive dequantize-then-matmul idiom, measured three ways — as-is,
    through the rewrite passes, and against the hand-fused path. Each
    variant reports XLA bytes-accessed of the compiled step and the
    slope-timed ms/step; the deltas at the end are the acceptance
    numbers for the optimizer passes."""
    from paddle_tpu.analysis.hbm import xla_cost_analysis
    from paddle_tpu.analysis.rewrite import count_matches, rewrite_callable

    qparams = quantize_for_decode(params, cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, prompt_len), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    cache0 = L.init_kv_cache(cfg, 1, prompt_len + 2)
    _, cache0 = jax.jit(
        lambda p, t, c: L.forward_with_cache(p, t, c, 0, cfg)
    )(qparams, prompt, cache0)
    pos = jnp.int32(prompt_len)
    tok0 = jnp.zeros((1, 1), jnp.int32)

    def make_step():
        # a FRESH function object per variant: jax caches traces keyed
        # on the function's identity, so reusing one `step` across
        # variants would hand every impl the first variant's jaxpr and
        # the PADDLE_TPU_INT8_IMPL switch would silently not happen
        # (measured: identical flops across impls without this)
        def step(p, tok, c):
            logits, c2 = L.forward_with_cache(p, tok, c, pos, cfg)
            # greedy sample in-graph so chained calls serialize on data
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None],
                    c2)
        return step

    n0 = max(steps // 4, 2)
    n1 = max(steps, n0 + 4)

    # the A/B isolates the int8-epilogue rewrite: off-TPU the
    # fused-rmsnorm substitution would route through the Pallas kernel
    # in INTERPRET mode, polluting the step time with emulation cost
    # that says nothing about the rewrite (the rmsnorm contract is
    # verified separately by graph_lint --suite rewrite)
    rules = ("int8-epilogue-fuse",)

    def measure(impl, wrap=None):
        prev = os.environ.get("PADDLE_TPU_INT8_IMPL")
        os.environ["PADDLE_TPU_INT8_IMPL"] = impl
        try:
            step = make_step()
            fn = wrap(step, rules=rules) if wrap is not None else step
            jitted = jax.jit(fn)
            # compile (and, for the rewritten variant, pattern-match)
            # while the impl env var is in force — the idiom is chosen
            # at trace time
            lowered = jitted.lower(qparams, tok0, cache0)
            ca = xla_cost_analysis(lowered.compile())
            fired = None
            if wrap is not None:
                from paddle_tpu.analysis.framework import default_rewrites
                fired = dict(count_matches(
                    jax.make_jaxpr(step)(qparams, tok0, cache0),
                    rules=default_rewrites(rules)))

            def run(n):
                t, c = tok0, cache0
                for _ in range(n):
                    t, c = jitted(qparams, t, c)
                int(np.asarray(t)[0, 0])

            with _span(f"rewrite_ab.{impl}" + (
                    ".rewritten" if wrap is not None else "")):
                ms = slope(run, n0, n1) * 1e3
        finally:
            if prev is None:
                os.environ.pop("PADDLE_TPU_INT8_IMPL", None)
            else:
                os.environ["PADDLE_TPU_INT8_IMPL"] = prev
        row = {"step_ms": round(ms, 4),
               "xla_bytes_accessed": float(ca.get("bytes accessed", -1)),
               "xla_flops": float(ca.get("flops", -1))}
        if fired is not None:
            row["fired"] = fired
        return row

    ab = {
        "unfused": measure("unfused"),
        "rewritten": measure("unfused", wrap=rewrite_callable),
        "hand_fused": measure("jnp"),
    }
    ub, rb = (ab["unfused"]["xla_bytes_accessed"],
              ab["rewritten"]["xla_bytes_accessed"])
    hb = ab["hand_fused"]["xla_bytes_accessed"]
    if ub > 0 and rb > 0:
        ab["bytes_cut_vs_unfused"] = round(ub / rb, 4)
        ab["bytes_vs_hand_fused"] = round(rb / hb, 4) if hb > 0 else None
    uf, rf = ab["unfused"]["xla_flops"], ab["rewritten"]["xla_flops"]
    if uf > 0 and rf > 0:
        ab["flops_cut_vs_unfused"] = round(uf / rf, 4)
    ab["speedup_vs_unfused"] = round(
        ab["unfused"]["step_ms"] / ab["rewritten"]["step_ms"], 4)
    ab["time_vs_hand_fused"] = round(
        ab["rewritten"]["step_ms"] / ab["hand_fused"]["step_ms"], 4)
    return ab


def main():
    flags = set(sys.argv[1:])
    preset = next((f for f in flags if f in PRESETS), None)
    if preset is None:
        preset = "flagship" if jax.default_backend() == "tpu" else "mid"
    bw = next((float(f.split("=")[1]) for f in flags
               if f.startswith("bw=")), 819e9)  # v5e HBM
    steps = next((int(f.split("=")[1]) for f in flags
                  if f.startswith("steps=")), 64)
    spec_draft_cost = next((float(f.split("=")[1]) for f in flags
                            if f.startswith("spec_draft_cost=")), 0.0)
    trace_path = next((f.split("=", 1)[1] for f in flags
                       if f.startswith("trace=")), None)
    if trace_path:
        global _TRACER
        from paddle_tpu.observability import SpanTracer
        _TRACER = SpanTracer()
    on_tpu = jax.default_backend() == "tpu"
    cfg = L.LlamaConfig(
        max_position_embeddings=4096,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
        remat=False, use_flash_attention="pallas" if on_tpu else False,
        **PRESETS[preset])

    params = L.init_params(cfg, jax.random.PRNGKey(0))
    variants = [("fp", params)]
    if "noint8" not in flags:
        variants.append(("int8", quantize_for_decode(params, cfg)))

    out = {"preset": preset, "backend": jax.default_backend(),
           "hbm_bw_gbs": bw / 1e9, "steps": steps}
    seq = 32 + steps // 2  # mean cache length over the run
    for tag, p in variants:
        with _span(f"profile.{tag}"):
            prof = profile(p, cfg, steps)
        wbytes = decode_weight_bytes(p)
        tbytes = wbytes + kv_bytes_per_step(cfg, seq)
        ceiling = bw / tbytes
        prof.update({
            "weight_bytes_per_step": wbytes,
            "kv_bytes_per_step": kv_bytes_per_step(cfg, seq),
            "bw_ceiling_tok_per_s": ceiling,
            "ceiling_fraction": prof["tok_per_s"] / ceiling,
        })
        out[tag] = {k: (round(v, 4) if isinstance(v, float) else v)
                    for k, v in prof.items()}
        # the speculative extension of the same ceiling: per-LAUNCH
        # bandwidth bound x expected emitted tokens per verify launch
        out[tag]["spec_ceiling"] = speculative_ceiling(
            ceiling, draft_cost=spec_draft_cost)
        # the long-context extension (r16): the ceiling at 4k..100k
        # context, with the walk's tiles and its O(tile) VMEM scratch
        out[tag]["long_context_ceiling"] = long_context_ceiling(
            cfg, bw, wbytes)
    if "fp" in out and "int8" in out:
        out["int8_speedup"] = round(
            out["int8"]["tok_per_s"] / out["fp"]["tok_per_s"], 4)
    if "rewrites" in flags:
        with _span("rewrite_ab"):
            out["rewrite_ab"] = rewrite_ab(params, cfg, steps)
    if trace_path:
        out["trace"] = _TRACER.export(trace_path)

    if "json" in flags:
        print(json.dumps(out))
        return
    print(f"# decode profile — {preset} ({out['backend']}), "
          f"bw={bw/1e9:.0f} GB/s")
    hdr = ("variant | step ms | layers | tail(norm+head+sample) | embed "
           "| tok/s | bytes/step | ceiling tok/s | achieved")
    print(hdr)
    for tag, _ in variants:
        r = out[tag]
        print(f"{tag:5s} | {r['step_ms']:8.3f} | {r['layers_ms']:7.3f} | "
              f"{r['tail_ms']:7.3f} | {r['embed_ms']:6.3f} | "
              f"{r['tok_per_s']:8.1f} | {r['weight_bytes_per_step']:>11,} |"
              f" {r['bw_ceiling_tok_per_s']:8.1f} | "
              f"{r['ceiling_fraction']:.3f}")
    if "int8_speedup" in out:
        print(f"int8 speedup: {out['int8_speedup']}x")
    sc = out[variants[0][0]]["spec_ceiling"]
    print(f"\n# speculative ceiling ({variants[0][0]}, draft cost "
          f"{sc['draft_cost_per_token']}/token): expected tok/s at "
          f"acceptance alpha")
    alphas = list(next(iter(sc["table"].values())).keys())
    print("k | " + " | ".join(a.split("=")[1] for a in alphas))
    for krow, row in sc["table"].items():
        print(krow.split("=")[1] + " | "
              + " | ".join(f"{row[a]['tok_s']:.0f}" for a in alphas))
    lc = out[variants[0][0]]["long_context_ceiling"]
    print(f"\n# long-context ceiling ({variants[0][0]}, page_size "
          f"{lc['page_size']}): what the bytes allow, and the walk")
    print("kv_len | ceiling tok/s | tile pages | tiles | scratch bytes")
    for krow, row in lc["table"].items():
        print(f"{krow.split('=')[1]:>6s} | "
              f"{row['bw_ceiling_tok_per_s']:13.1f} | "
              f"{row['kv_tile_pages']:>10d} | {row['kv_tiles']:>5d} | "
              f"{row['vmem_scratch_bytes']:>12,}")
    if "rewrite_ab" in out:
        ab = out["rewrite_ab"]
        print("\n# rewrite A/B (int8 decode step, unfused idiom)")
        print("variant    | step ms  | XLA bytes/step | rewrites fired")
        for tag in ("unfused", "rewritten", "hand_fused"):
            r = ab[tag]
            print(f"{tag:10s} | {r['step_ms']:8.3f} | "
                  f"{r['xla_bytes_accessed']:>14,.0f} | "
                  f"{r.get('fired', '')}")
        print(f"bytes cut vs unfused: {ab.get('bytes_cut_vs_unfused')}x; "
              f"flops cut vs unfused: {ab.get('flops_cut_vs_unfused')}x; "
              f"bytes vs hand-fused: {ab.get('bytes_vs_hand_fused')}x; "
              f"speedup vs unfused: {ab['speedup_vs_unfused']}x; "
              f"time vs hand-fused: {ab['time_vs_hand_fused']}x")


if __name__ == "__main__":
    from paddle_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
