"""chip_smoke.py — the two main paths on the chip, through their entry points.

    python chip_smoke.py            # one TPU chip: train phase, serve phase
    python chip_smoke.py --chips 4  # four chips: dp2 x tp2 train step only

One process, one model configuration: ``LlamaConfig.llama3_8b`` with
every width as published (D=4096, F=14336, 32 query / 8 KV heads,
Dh=128, vocab 128256) in bf16; only ``num_hidden_layers`` is cut
(``LAYERS``) so that parameters + AdamW state + one step fit 16 GB —
depth and batch were chosen from ``compiled.memory_analysis()`` of a
described-chip compile (CHANGES.md, PR 22), not by trial on the chip.
Weights are random, from ``--seed``.

* train: ``init_hybrid_mesh`` -> ``L.make_train_step`` ->
  ``L.make_batch``, T=2048, remat, strict Pallas splash attention and
  fused rmsnorm/rope. A few steps on one fixed batch. Fails unless
  every loss is finite, the last is below the first, and the compiled
  step holds the three kernels' ``tpu_custom_call``s.
* serve: ``ServingEngine`` with its default ``attn_impl`` and 16-token
  pages; mixed prompts through ``submit()``/``result()``. Fails unless
  every request returns the tokens it asked for, every tick program
  holds the ragged kernel's custom call, the recompile sentinel saw no
  compile after warm-up, and the ragged kernel agrees with its
  dense-gather reference at the tick's shapes. Whether greedy tokens
  equal ``L.generate()`` is printed, not judged.
* ``--chips 4``: the same config and batch on ``dp=2 x tp=2``; first-
  step loss against the ``dp=tp=1`` step; fails unless all four
  devices hold a share of the state and the program has collectives.

With no TPU the script exits non-zero before any phase. Any phase that
raises makes the exit code non-zero. The last line of stdout is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import numpy as np

# depth/batch of the smoke, from the described-chip compile's
# memory_analysis (L=3, B=2, T=2048: 13.78 GiB of the 15.75 GiB a v5e
# chip offers; L=3 B=1 13.09, L=2 B=4 13.84)
LAYERS, BATCH, SEQ = 3, 2, 2048
TRAIN_STEPS = 4
# widest packed width of a tick = the prefill chunk. The ragged kernel
# compiles for the chip up to 256 query rows per slot at this geometry
# (G*Tq = 1024 score rows); at 512 Mosaic refuses it (26.2 MiB of
# scoped VMEM against 16 MiB) — ROADMAP.md S2
PREFILL_CHUNK = 256
# first-step loss, dp2 x tp2 against one chip: bf16 matmuls whose
# partial sums are reduced across tp in another order (loss ~ 12)
SHARDED_LOSS_TOL = 0.05
# ragged kernel vs dense-gather reference, in bf16 eps at each slot's
# output scale (ragged_paged_attention.tiled_ulp_error's unit): the
# two share their arithmetic, so only accumulation order may differ
KERNEL_ULP_TOL = 4.0

# how a kernel shows in program text: a line naming tpu_custom_call
# plus one of these. The splash and ragged kernels carry a name of
# their own, which both the compiled (HLO) and the lowered (StableHLO,
# ``kernel_name = "..."``) text show; the fused norm/rope kernels are
# read off the jitted wrapper in the compiled text's op_name metadata.
KERNEL_MARKS = {
    "splash_attention": ("splash_mha",),
    "fused_rms_norm": ("_rms_fwd_call", "_rms_bwd_call"),
    "fused_rope": ("_rope_call",),
    "ragged_paged_attention": ("ragged_paged_attention",),
}
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


class SmokeFailure(AssertionError):
    pass


def require_tpu():
    """First thing the script does. Never sets a platform."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke.py needs a TPU; jax.devices() reports platform "
            f"{devs[0].platform!r} ({devs[0].device_kind!r})")
    return devs


def kernels_in(text: str) -> dict:
    """``{kernel: number of tpu_custom_call lines that name it}``."""
    calls = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
    return {k: sum(any(m in ln for m in marks) for ln in calls)
            for k, marks in KERNEL_MARKS.items()}


def smoke_config(layers: int = LAYERS, seq: int = SEQ):
    import jax.numpy as jnp
    from paddle_tpu.models import llama as L
    return dataclasses.replace(
        L.LlamaConfig.llama3_8b(
            dtype=jnp.bfloat16, remat=True, max_position_embeddings=seq,
            use_flash_attention="pallas", use_fused_norm_rope="pallas"),
        num_hidden_layers=layers)


def shard_bytes_per_device(tree) -> dict:
    """``{device id: bytes of the tree's shards it holds}``."""
    import jax
    held: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for sh in leaf.addressable_shards:
            held[sh.device.id] = held.get(sh.device.id, 0) + sh.data.nbytes
    return held


# ---------------------------------------------------------------- train ----

def train_phase(cfg, *, devices, dp: int = 1, tp: int = 1,
                batch: int = BATCH, seq: int = SEQ,
                steps: int = TRAIN_STEPS, seed: int = 0) -> dict:
    """The calls bench.py makes, compiled once ahead of time so the
    same executable gives the text, the memory analysis and the steps."""
    import jax
    from paddle_tpu.models import llama as L
    from paddle_tpu.parallel import init_hybrid_mesh

    hm = init_hybrid_mesh(dp=dp, pp=1, tp=tp, devices=devices,
                          set_global=False)
    with hm.mesh:
        step, init = L.make_train_step(cfg, hm.mesh)
        state = init(jax.random.PRNGKey(seed))
        data = L.make_batch(cfg, batch_size=batch, seq_len=seq,
                            mesh=hm.mesh)
        held = {"state": shard_bytes_per_device(state),
                "batch": shard_bytes_per_device(data)}
        t0 = time.perf_counter()
        compiled = step.lower(state, data).compile()
        compile_s = time.perf_counter() - t0
        text = compiled.as_text()
        losses, t_first = [], None
        t0 = time.perf_counter()
        for _ in range(steps):
            state, loss = compiled(state, data)
            losses.append(loss)
            if t_first is None:
                jax.block_until_ready(loss)
                t_first = time.perf_counter()
        jax.block_until_ready((state, losses))
        t_end = time.perf_counter()
    return {
        "mesh": dict(hm.mesh.shape),
        "losses": [float(x) for x in losses],
        "compile_s": compile_s,
        "first_step_ms": (t_first - t0) * 1e3,
        "step_ms": ((t_end - t_first) / (steps - 1) * 1e3
                    if steps > 1 else None),
        "kernels": kernels_in(text),
        "collectives": {c: text.count(c) for c in COLLECTIVES},
        "held": held,
        "program_bytes": _program_bytes(compiled),
        "state": state,
    }


def _program_bytes(compiled):
    ma = compiled.memory_analysis()
    if ma is None:
        return None
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def check_train(rep: dict, kernels=("splash_attention", "fused_rms_norm",
                                    "fused_rope")) -> None:
    losses = rep["losses"]
    if not all(np.isfinite(losses)):
        raise SmokeFailure(f"non-finite loss: {losses}")
    if len(losses) > 1 and not losses[-1] < losses[0]:
        raise SmokeFailure(f"loss did not fall on a fixed batch: {losses}")
    missing = [k for k in kernels if not rep["kernels"][k]]
    if missing:
        raise SmokeFailure(
            f"compiled train step holds no tpu_custom_call for {missing} "
            f"(found {rep['kernels']}): a kernel silently gave way")


# ---------------------------------------------------------------- serve ----

def smoke_requests(vocab: int, seed: int, lens=(12, 300, 1100),
                   shared=(160, 40), new=(16, 24, 32, 16, 16)):
    """Mixed traffic: a short prompt, a few hundred tokens, one past
    1k, and two that share a prefix (page-aligned, so the prefix cache
    can serve the second from the first's pages)."""
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, vocab, (n,)).astype(np.int32) for n in lens]
    head = rng.randint(0, vocab, (shared[0],)).astype(np.int32)
    for _ in range(2):
        tail = rng.randint(0, vocab, (shared[1],)).astype(np.int32)
        prompts.append(np.concatenate([head, tail]))
    return list(zip(prompts, new))


def serve_phase(params, cfg, requests, **engine_kw) -> dict:
    from paddle_tpu.serving import ServingEngine

    t0 = time.perf_counter()
    eng = ServingEngine(params, cfg, **engine_kw)
    try:
        n_programs = eng.warm_programs()
        # before arming, one prompt of its own end to end, twice:
        # admission, a chunked prefill, retirement, then a prefix-cache
        # hit — whatever small programs those paths compile
        warm = np.random.RandomState(1).randint(
            0, cfg.vocab_size, (len(requests[1][0]),)).astype(np.int32)
        for _ in range(2):
            eng.submit(warm, 2).result(timeout=600)
        warm_s = time.perf_counter() - t0
        eng.arm_sentinel()
        t0 = time.perf_counter()
        handles = [eng.submit(p, n) for p, n in requests]
        outs = [np.asarray(h.result(timeout=600)) for h in handles]
        serve_s = time.perf_counter() - t0
        sentinel = eng.sentinel.report()
        snap = eng.stats()
        program_kernels = {
            name: kernels_in(text)["ragged_paged_attention"]
            for name, text in eng.program_texts().items()}
        geometry = {"slots": eng.scheduler.max_batch,
                    "pages_per_slot": eng.scheduler.pages_per_slot,
                    "page_size": eng.pool.page_size,
                    "total_pages": eng.pool.total_pages}
    finally:
        eng.close()
    return {"outs": outs, "asked": [n for _, n in requests],
            "n_programs": n_programs,
            "warm_s": warm_s, "serve_s": serve_s, "sentinel": sentinel,
            "counters": snap["counters"],
            "decode_step_s": snap["histograms"].get("decode_step_s"),
            "ttft_s": snap["histograms"].get("ttft_s"),
            "program_kernels": program_kernels, "geometry": geometry}


def check_serve(rep: dict, need_kernel: bool = True) -> None:
    got = [len(o) for o in rep["outs"]]
    if got != rep["asked"]:
        raise SmokeFailure(
            f"requests returned {got} tokens, asked {rep['asked']}")
    if not rep["sentinel"]["clean"]:
        raise SmokeFailure(
            f"compile after warm-up: {rep['sentinel']['events']}")
    if need_kernel:
        bare = [name for name, n in rep["program_kernels"].items()
                if not n]
        if bare:
            raise SmokeFailure(
                f"serving programs {bare} hold no tpu_custom_call for "
                f"the ragged kernel")


def compare_ragged_kernel(cfg, geometry: dict, tq: int, seed: int) -> float:
    """The ragged kernel against ``impl="dense"`` at the tick's shapes
    (slots, heads, page geometry, widest packed width), on a seeded
    mixed batch: full-width prefill spans, decode rows, an idle slot, a
    span on a warm prefix. Returns the worst error in eps of the pool
    dtype at each slot's output scale."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention, tiled_ulp_error)

    S, pps, ps = (geometry["slots"], geometry["pages_per_slot"],
                  geometry["page_size"])
    H, Hkv, Dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    kv_max = pps * ps
    rng = np.random.RandomState(seed)
    # (q_len, kv_len) per slot, cycled over S slots
    mix = [(tq, kv_max), (1, kv_max // 2), (0, 0), (tq // 2, tq // 2),
           (1, kv_max), (tq, tq), (1, ps), (max(tq // 8, 1), kv_max // 3)]
    q_len = np.array([mix[i % len(mix)][0] for i in range(S)], np.int32)
    kv_len = np.array([mix[i % len(mix)][1] for i in range(S)], np.int32)
    P = S * pps + 1
    tables = (1 + rng.permutation(S * pps)).astype(np.int32).reshape(S, pps)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (S, tq, H, Dh), jnp.float32).astype(cfg.dtype)
    kp = jax.random.normal(k2, (Hkv, P, ps, Dh), jnp.float32).astype(cfg.dtype)
    vp = jax.random.normal(k3, (Hkv, P, ps, Dh), jnp.float32).astype(cfg.dtype)
    got = ragged_paged_attention(q, kp, vp, q_len, kv_len, tables,
                                 impl="pallas")
    ref = ragged_paged_attention(q, kp, vp, q_len, kv_len, tables,
                                 impl="dense")
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    if not np.isfinite(got).all():
        raise SmokeFailure("ragged kernel produced non-finite values")
    # rows past q_len are padding both sides leave undefined-but-equal
    # zeros; the metric is per slot over the whole span
    eps = float(jnp.finfo(cfg.dtype).eps)
    return tiled_ulp_error(got, ref) * float(np.finfo(np.float32).eps) / eps


def tokens_equal_generate(params, cfg, requests, outs, which=(0, 1)):
    """Greedy engine tokens against ``L.generate()`` for a few requests
    (each prompt length is its own compile). Information, not a gate."""
    import jax
    import jax.numpy as jnp
    from functools import partial
    from paddle_tpu.models import llama as L

    # generate() prefills at arbitrary prompt lengths: "auto" takes the
    # splash kernel when the shapes allow and the dense path otherwise
    ref_cfg = dataclasses.replace(cfg, use_flash_attention=True)
    same = {}
    for i in which:
        prompt, n = requests[i]
        gen = jax.jit(partial(L.generate, cfg=ref_cfg, max_new_tokens=n))
        ref = np.asarray(gen(params, jnp.asarray(prompt)[None]))[0, -n:]
        same[i] = bool(np.array_equal(ref, outs[i]))
    return same


# -------------------------------------------------------------- 4 chips ----

def sharded_phase(cfg, devices, *, batch: int = BATCH, seq: int = SEQ,
                  seed: int = 0, tol: float = SHARDED_LOSS_TOL) -> dict:
    """dp=2 x tp=2 over four devices against the dp=tp=1 step: same
    seed, same batch, first-step loss."""
    if len(devices) < 4:
        raise SmokeFailure(f"--chips 4 found {len(devices)} device(s)")
    one = train_phase(cfg, devices=devices[:1], batch=batch, seq=seq,
                      steps=1, seed=seed)
    del one["state"]  # free chip 0 before the sharded state lands
    four = train_phase(cfg, devices=devices[:4], dp=2, tp=2, batch=batch,
                       seq=seq, steps=1, seed=seed)
    del four["state"]
    for rep in (one, four):
        check_train(rep, kernels=())
    diff = abs(four["losses"][0] - one["losses"][0])
    if not diff <= tol:
        raise SmokeFailure(
            f"first-step loss dp2xtp2 {four['losses'][0]} vs one chip "
            f"{one['losses'][0]}: |diff| {diff} > {tol}")
    ids = [d.id for d in devices[:4]]
    held = four["held"]
    for what in ("state", "batch"):
        empty = [i for i in ids if not held[what].get(i)]
        if empty:
            raise SmokeFailure(f"devices {empty} hold none of the {what}")
    # tp=2 halves every sharded weight: a device holding the whole
    # parameter set means the mesh did not spread anything
    per_dev_params = max(held["state"].values())
    if not per_dev_params < one["held"]["state"][ids[0]]:
        raise SmokeFailure(
            f"state is not spread: a device of the dp2xtp2 mesh holds "
            f"{per_dev_params} B, the one-chip state is "
            f"{one['held']['state'][ids[0]]} B")
    if not any(four["collectives"].values()):
        raise SmokeFailure("dp2xtp2 program has no collective")
    return {"one": one, "four": four, "loss_diff": diff}


# ----------------------------------------------------------------- main ----

def _peak_bytes(dev):
    ms = dev.memory_stats()
    return ms.get("peak_bytes_in_use") if ms else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = require_tpu()
    import jax
    from paddle_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    dev = devs[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devs)} jax={jax.__version__}", flush=True)
    cfg = smoke_config()
    print(f"[config] llama3_8b widths D={cfg.hidden_size} "
          f"F={cfg.intermediate_size} H={cfg.num_attention_heads}/"
          f"{cfg.num_key_value_heads} Dh={cfg.head_dim} "
          f"V={cfg.vocab_size} bf16; cut: layers={LAYERS} (of 32); "
          f"batch={BATCH} seq={SEQ}", flush=True)

    if args.chips == 4:
        rep = sharded_phase(cfg, devs, seed=args.seed)
        one, four = rep["one"], rep["four"]
        print(f"[sharded] one chip: loss {one['losses'][0]:.6f} "
              f"compile {one['compile_s']:.1f}s "
              f"first step {one['first_step_ms']:.1f} ms", flush=True)
        print(f"[sharded] dp2xtp2 mesh={four['mesh']}: loss "
              f"{four['losses'][0]:.6f} compile {four['compile_s']:.1f}s "
              f"first step {four['first_step_ms']:.1f} ms; |loss diff| "
              f"{rep['loss_diff']:.6f} (tol {SHARDED_LOSS_TOL})",
              flush=True)
        print(f"[sharded] state bytes per device {four['held']['state']} "
              f"(one chip: {one['held']['state']}); batch bytes per "
              f"device {four['held']['batch']}; collectives "
              f"{four['collectives']}", flush=True)
        print(f"[sharded] bytes_in_use per device "
              f"{[(d.memory_stats() or {}).get('bytes_in_use') for d in devs]}"
              f" peak {[_peak_bytes(d) for d in devs]}", flush=True)
    else:
        tr = train_phase(cfg, devices=devs[:1], seed=args.seed)
        print(f"[train] losses {['%.4f' % x for x in tr['losses']]} "
              f"compile {tr['compile_s']:.1f}s first step "
              f"{tr['first_step_ms']:.1f} ms, then {tr['step_ms']:.1f} "
              f"ms/step; kernels {tr['kernels']}; program bytes "
              f"{tr['program_bytes']}; peak_bytes_in_use "
              f"{_peak_bytes(dev)}", flush=True)
        check_train(tr)
        params = tr.pop("state")["params"]  # drops the AdamW state
        requests = smoke_requests(cfg.vocab_size, args.seed)
        sv = serve_phase(params, cfg, requests, max_batch=8, page_size=16,
                         max_prompt_len=1280, max_new_tokens_cap=32,
                         prompt_buckets=(32, PREFILL_CHUNK, 1280),
                         prefill_chunk=PREFILL_CHUNK)
        print(f"[serve] {len(sv['outs'])} requests, prompt lengths "
              f"{[len(p) for p, _ in requests]}, new tokens "
              f"{[len(o) for o in sv['outs']]} in {sv['serve_s']:.2f}s; "
              f"warm-up {sv['warm_s']:.1f}s for {sv['n_programs']} "
              f"programs; geometry {sv['geometry']}", flush=True)
        print(f"[serve] decode_step_s {sv['decode_step_s']} ttft_s "
              f"{sv['ttft_s']}", flush=True)
        print(f"[serve] prefix_hit_tokens "
              f"{sv['counters'].get('prefix_hit_tokens', 0)}; sentinel "
              f"warm-up compiles {sv['sentinel']['warmup_compiles']}, "
              f"after arming {sv['sentinel']['post_warmup_compiles']}; "
              f"ragged custom calls per program "
              f"{sv['program_kernels']}", flush=True)
        check_serve(sv)
        err = compare_ragged_kernel(cfg, sv["geometry"], tq=PREFILL_CHUNK,
                                    seed=args.seed)
        print(f"[serve] ragged kernel vs dense reference at "
              f"Tq={PREFILL_CHUNK}: {err:.3f} bf16-eps at slot scale "
              f"(tol {KERNEL_ULP_TOL})", flush=True)
        if not err <= KERNEL_ULP_TOL:
            raise SmokeFailure(
                f"ragged kernel off its dense reference by {err} "
                f"bf16-eps at slot scale (tol {KERNEL_ULP_TOL})")
        same = tokens_equal_generate(params, cfg, requests, sv["outs"])
        print(f"[serve] greedy tokens equal L.generate(): "
              f"{ {f'req{i}': v for i, v in same.items()} }", flush=True)
        print(f"[memory] peak_bytes_in_use {_peak_bytes(dev)}", flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
