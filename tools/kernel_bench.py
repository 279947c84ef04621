"""Microbench: authored Pallas kernels vs XLA-fused baselines, on TPU.

Run: python tools/kernel_bench.py   (needs the real chip)

Methodology: per-call DEVICE time from a jax.profiler trace (sum of
jit_* device events / iterations): host wall-clock carries per-call
dispatch cost that can swamp a sub-millisecond kernel, device time is
what the hardware actually spends. The kernel table in docs/PERF.md is
from a machine that is gone; nothing is re-measured yet (PERF.md).

``--ragged-sweep`` (r16) runs the tiled-vs-one-shot ragged
paged-attention A/B instead: a sweep over (pages_per_slot, page_size,
kv_tile_pages) geometries, ONE JSON LINE PER CONFIG on stdout (and
``--out=path`` as JSONL), each carrying a ``vmem_scratch_bytes``
column computed from the kernels' actual scratch shapes — the
evidence that tiled scratch is O(tile) while one-shot scratch grows
with the table. Per geometry the fastest variant is then recorded
through ``ops.autotune`` (key ``("ragged_kv_walk", ...)``) — the
first entry of the KForge-style autotune loop (PAPERS.md
2606.02963): block shapes searched against the bench harness, cache
picks the winner per geometry. On TPU it times device events; off
TPU it still runs end-to-end in interpreter mode (wall-clock,
``timing_honest: false`` — the smoke path; the overdue on-chip round,
ROADMAP item 3, reruns it unmodified for real numbers).

``--block-sweep`` (r23) is the flywheel's write side for the other
swept kernels: per geometry it times every candidate block shape for
``fused_rms_norm`` (row tile), the conv-epilogue matmul (tm/tn/tk),
and the dropless-MoE grouped matmul (tile_m/tile_n), one JSON row per
candidate (``tiling_source: "explicit"``), records the fastest into
the persistent winner store when ``$PADDLE_TPU_AUTOTUNE_DIR`` is set
(``ops.autotune.record`` — the geometry kwargs here match each entry
point's ``lookup`` byte-for-byte), then emits a resolution row showing
what a default call now resolves to (``tiling_source: "swept"`` vs
``"default"``). The ragged sweep records its winner the same way.

Every sweep row additionally carries the static kernel-audit verdict
(``audit: "ok" | "failed:<rule>"`` — analysis/kernel_audit.py run on
that exact geometry+tiling, no compile), and the record path runs
with ``audit=True``: a measured winner that fails KA001/KA002 is
REFUSED admission to the store — the row keeps its timing but gains
an ``audit_failed`` marker and the resolution row shows what actually
resolves without it. Fast-but-unsound never enters the flywheel.
"""
import functools
import glob
import gzip
import json
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _audit_verdict(kind, geom, config):
    """Static kernel-audit verdict for one sweep row: ``"ok"`` or
    ``"failed:<rule>"`` (KA001/KA002 gate rules), ``None`` when the
    auditor cannot run here. Pure jaxpr inspection — no compile, so
    annotating every candidate costs milliseconds."""
    try:
        from paddle_tpu.analysis import kernel_audit as ka
        v = ka.audit_config(kind, geom, config)
    except Exception:
        return None
    return "ok" if v["ok"] else "failed:" + ",".join(v["rules"])


def devtime(f, args, tag, n=5):
    y = f(*args)
    jax.block_until_ready(y)
    with jax.profiler.trace(f"/tmp/kb_{tag}"):
        for _ in range(n):
            y = f(*args)
        np.asarray(jax.tree_util.tree_leaves(y)[0].ravel()[0])
    tr = json.load(gzip.open(sorted(glob.glob(
        f"/tmp/kb_{tag}/plugins/profile/*/vm.trace.json.gz"))[-1]))
    pids = {e["pid"]: e["args"].get("name", "")
            for e in tr["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "process_name"}
    tot = sum(e.get("dur", 0) for e in tr["traceEvents"]
              if e.get("ph") == "X"
              and "tpu" in pids.get(e.get("pid"), "").lower()
              and e["name"].startswith("jit_"))
    return tot / n / 1e3


def bench_moe():
    from paddle_tpu.ops.pallas.grouped_matmul import moe_mlp_dropless
    S, D, F, E, topk = 8192, 2048, 5632, 8, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    dt = jnp.bfloat16
    x = jax.random.normal(ks[0], (S, D), dt)
    wg = jax.random.normal(ks[1], (E, D, F), dt) * 0.02
    wu = jax.random.normal(ks[2], (E, D, F), dt) * 0.02
    wd = jax.random.normal(ks[3], (E, F, D), dt) * 0.02
    logits = jax.random.normal(ks[4], (S, E), jnp.float32)
    cw, eids = jax.lax.top_k(jax.nn.softmax(logits), topk)
    cw = cw.astype(dt)
    C = topk * S // E

    # NOTE: everything is a jit ARGUMENT — closed-over device arrays
    # become compile-time constants and XLA's constant folding of the
    # routing cumsums hangs the compile for minutes
    fd = jax.jit(lambda x, eids, cw, wg, wu, wd: moe_mlp_dropless(
        x, eids, cw, wg, wu, wd, tile_m=256, tile_n=512))

    def einsum_moe(x, eids, cw, wg, wu, wd):
        # GShard capacity-1.0 dense dispatch (the incubate/moe
        # formulation): drops overflow tokens; dispatch/combine einsums
        # cost 2*S*E*C*D extra FLOPs and an [S*k, E, C] slot one-hot
        disp = jax.nn.one_hot(eids, E, dtype=dt)
        pos = jnp.cumsum(disp.reshape(S * topk, E), axis=0) - 1.0
        slot_id = jnp.where(disp.reshape(S * topk, E) > 0, pos, -1.0)
        slot = (jax.nn.one_hot(slot_id.astype(jnp.int32), C, dtype=dt)
                * disp.reshape(S * topk, E)[..., None])
        slc = (slot.reshape(S, topk, E, C) * cw[:, :, None, None]).sum(1)
        sl = slot.reshape(S, topk, E, C).sum(1)
        xe = jnp.einsum("sec,sd->ecd", sl, x)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg)) * \
            jnp.einsum("ecd,edf->ecf", xe, wu)
        ye = jnp.einsum("ecf,efd->ecd", h, wd)
        return jnp.einsum("sec,ecd->sd", slc, ye)

    fe = jax.jit(einsum_moe)
    args = (x, eids, cw, wg, wu, wd)
    td = devtime(fd, args, "moe_drop")
    te = devtime(fe, args, "moe_ein")
    fl = 2 * 3 * S * topk * D * F
    print(f"moe S={S} D={D} F={F} E={E} top{topk} (device time):")
    print(f"  dropless gmm : {td:7.2f} ms  {fl/td/1e9:6.0f} TFLOP/s  "
          f"(0 tokens dropped)")
    print(f"  einsum (XLA) : {te:7.2f} ms  (capacity 1.0: overflow "
          f"tokens dropped; slot one-hot is 2*(S*k)^2 bytes = "
          f"{2*(S*topk)**2/2**30:.1f} GiB here, 8.6 GiB at top-8 — "
          f"the dropless glue stays O(S*k*E) int32)")
    print(f"  ratio        : {te/td:.2f}x")


def bench_rope():
    from paddle_tpu.ops.pallas.fused_norm_rope import fused_rope
    from paddle_tpu.models.llama import rope as xla_rope
    B, T, H, Hkv, Dh = 4, 2048, 32, 8, 128
    q = jax.random.normal(jax.random.PRNGKey(0), (B, T, H, Dh),
                          jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, T, Hkv, Dh),
                          jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    tf = devtime(jax.jit(
        lambda q, k: fused_rope(q, k, pos, 500000.0, 256)), (q, k), "ropef")
    tx = devtime(jax.jit(
        lambda q, k: xla_rope(q, k, pos, 500000.0, Dh)), (q, k), "ropex")
    by = (q.size + k.size) * 2 * 2 / 1e9
    print(f"rope B={B} T={T} H={H}/{Hkv} Dh={Dh} (device time):")
    print(f"  fused pallas : {tf:7.3f} ms  {by/tf*1e3:6.0f} GB/s")
    print(f"  xla          : {tx:7.3f} ms  {by/tx*1e3:6.0f} GB/s")
    print(f"  speedup      : {tx/tf:.2f}x")


def bench_rms():
    from paddle_tpu.ops.pallas.fused_norm_rope import fused_rms_norm
    from paddle_tpu.models.llama import rms_norm as xla_rms
    N, D = 16384, 4096
    x = jax.random.normal(jax.random.PRNGKey(0), (N, D), jnp.bfloat16)
    w = jnp.ones((D,), jnp.bfloat16)
    tf = devtime(jax.jit(lambda x: fused_rms_norm(x, w, 1e-5)), (x,),
                 "rmsf")
    tx = devtime(jax.jit(lambda x: xla_rms(x, w, 1e-5)), (x,), "rmsx")
    by = x.size * 2 * 2 / 1e9
    print(f"rms_norm N={N} D={D} (device time):")
    print(f"  fused pallas : {tf:7.3f} ms  {by/tf*1e3:6.0f} GB/s")
    print(f"  xla          : {tx:7.3f} ms  {by/tx*1e3:6.0f} GB/s")
    print(f"  speedup      : {tx/tf:.2f}x")


def _walltime(f, args, n=3):
    """best-of wall-clock ms/call (the off-TPU fallback — honest
    enough for interpret-mode smoke, not for perf claims)."""
    y = f(*args)
    jax.block_until_ready(y)
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def ragged_tiling_sweep(out=None, iters=3):
    """Tiled-vs-one-shot ragged paged-attention A/B (module
    docstring). Returns the list of per-config result dicts."""
    from paddle_tpu.ops import autotune as at
    from paddle_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention, vmem_scratch_bytes)
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        dt = jnp.bfloat16
        S, H, Hkv, Dh = 8, 32, 8, 128
        # pps x page_size spans the knee: 2k tokens (one-shot
        # territory) to 100k (tiled-only)
        geoms = [(128, 16), (512, 16), (2048, 16), (6250, 16)]
        tiles = (0, 8, 16, 32, 64)
    else:
        dt = jnp.float32
        S, H, Hkv, Dh = 2, 4, 2, 8
        geoms = [(8, 4), (32, 4)]
        tiles = (0, 2, 4, 8)
    rng = np.random.RandomState(0)
    results = []
    for pps, ps in geoms:
        P = S * pps + 1
        kv_len = pps * ps
        q = jnp.asarray(rng.randn(S, 1, H, Dh), dt)       # decode spans
        kp = jnp.asarray(rng.randn(Hkv, P, ps, Dh), dt)
        vp = jnp.asarray(rng.randn(Hkv, P, ps, Dh), dt)
        ql = jnp.ones((S,), jnp.int32)
        kl = jnp.full((S,), kv_len, jnp.int32)
        tabs = jnp.asarray(
            1 + np.arange(S * pps, dtype=np.int32).reshape(S, pps))
        args = (q, kp, vp, ql, kl, tabs)

        def make(tile):
            return jax.jit(functools.partial(
                ragged_paged_attention, impl="pallas",
                kv_tile_pages=tile))

        ageom = dict(pages_per_slot=pps, page_size=ps, head_dim=Dh,
                     dtype=str(jnp.dtype(dt)))
        cands, rows = [], []
        for tile in tiles:
            if tile > pps:
                continue
            scratch = vmem_scratch_bytes(pps, ps, Dh, dt,
                                         kv_tile_pages=tile)
            row = {
                "bench": "ragged_kv_walk", "pps": pps, "page_size": ps,
                "kv_len": kv_len, "slots": S, "heads": H,
                "kv_heads": Hkv, "head_dim": Dh, "dtype": str(jnp.dtype(dt)),
                "kv_tile_pages": tile,
                "walk": "tiled" if tile else "oneshot",
                "vmem_scratch_bytes": scratch,
                "timing_honest": on_tpu,
                "audit": _audit_verdict("ragged_paged_attention", ageom,
                                        {"kv_tile_pages": tile}),
            }
            # the one-shot variant past the VMEM knee cannot even
            # compile on the chip — that IS the result (the row the
            # tiled walk exists for), not a reason to abort the sweep
            if on_tpu and tile == 0 and scratch > 12 * 2 ** 20:
                rows.append(dict(row, ms=None,
                                 skipped="oneshot scratch exceeds VMEM"))
                continue
            fn = make(tile)
            try:
                if on_tpu:
                    ms = devtime(fn, args, f"rg_{pps}_{ps}_{tile}",
                                 n=iters)
                else:
                    ms = _walltime(fn, args, n=iters)
            except Exception as e:   # compile/scratch failure = a row
                rows.append(dict(row, ms=None, error=str(e)[:200]))
                continue
            rows.append(dict(row, ms=round(ms, 4)))
            cands.append((len(rows) - 1, fn))
        # the KForge-style loop's first entry: cache the measured
        # winner per geometry so a runtime dispatcher can pick it
        # (skipped/failed variants never become candidates)
        if cands:
            key = ("ragged_kv_walk", pps, ps, Dh, Hkv,
                   str(jnp.dtype(dt)))
            at.autotune(key, [f for _, f in cands], args,
                        iters=max(iters, 2))
            win_row = cands[at.cache_info()[0][key]][0]
            for i, row in enumerate(rows):
                row["autotune_winner"] = bool(i == win_row)
                row["tiling_source"] = "explicit"
            # persist the winner under the EXACT geometry key the
            # entry point's lookup uses — audit-gated: a measured
            # winner failing KA001/KA002 is refused and emits an
            # audit_failed row instead — then report what a
            # kv_tile_pages=None call now resolves to
            winner_cfg = {"kv_tile_pages":
                          rows[win_row]["kv_tile_pages"]}
            if at.store_dir():
                try:
                    at.record("ragged_paged_attention", winner_cfg,
                              audit=True, **ageom)
                except at.AutotuneAuditError as e:
                    rows.append({"bench": "ragged_kv_walk", **ageom,
                                 **winner_cfg,
                                 "audit_failed": str(e)[:200]})
            win = at.lookup("ragged_paged_attention", **ageom)
            rows.append({"bench": "ragged_kv_walk", "resolution": True,
                         **ageom, **(win or {}),
                         "tiling_source": "swept" if win else "default"})
        results.extend(rows)
    for row in results:
        print(json.dumps(row))
    if out:
        with open(out, "w") as f:
            for row in results:
                f.write(json.dumps(row) + "\n")
    return results


def block_sweep(out=None, iters=3):
    """Block-shape sweeps for the swept Pallas entry points (module
    docstring): time every candidate, record the winner per geometry
    into the persistent store, emit a resolution row. Returns the list
    of result dicts."""
    from paddle_tpu.ops import autotune as at
    from paddle_tpu.ops.pallas.conv_epilogue import matmul_bias_act
    from paddle_tpu.ops.pallas.fused_norm_rope import fused_rms_norm
    from paddle_tpu.ops.pallas.grouped_matmul import moe_mlp_dropless

    on_tpu = jax.default_backend() == "tpu"
    persist = at.store_dir() is not None
    rng = np.random.RandomState(0)
    results = []

    def timed(fn, args, tag):
        try:
            if on_tpu:
                return devtime(fn, args, tag, n=iters), None
            return _walltime(fn, args, n=max(iters, 2)), None
        except Exception as e:     # a failing candidate is a row, not an abort
            return None, str(e)[:200]

    def finish(kind, geom, cand_rows, winner_blocks):
        """Mark the winner among ``cand_rows``, persist it, then report
        what a tiles-unspecified call now resolves to. The resolution
        row is the flywheel's read-side receipt: ``swept`` only if the
        store actually answers for this geometry."""
        timed_rows = [r for r in cand_rows if r.get("ms") is not None]
        best = min(timed_rows, key=lambda r: r["ms"]) if timed_rows \
            else None
        for r in cand_rows:
            r["tiling_source"] = "explicit"
            r["timing_honest"] = on_tpu
            r["autotune_winner"] = r is best
            r["audit"] = _audit_verdict(kind, geom, winner_blocks(r))
        results.extend(cand_rows)
        if best is not None and persist:
            # audit-gated admission: fastest-but-unsound is refused
            # (the flywheel would otherwise replay the violation on
            # every future default call at this geometry)
            try:
                at.record(kind, winner_blocks(best), audit=True, **geom)
            except at.AutotuneAuditError as e:
                results.append({"bench": kind, **geom,
                                **winner_blocks(best),
                                "audit_failed": str(e)[:200]})
        win = at.lookup(kind, **geom)
        results.append({"bench": kind, "resolution": True, **geom,
                        **(win or {}),
                        "tiling_source": "swept" if win else "default"})

    # --- fused_rms_norm: row-tile sweep --------------------------------
    if on_tpu:
        rms_geoms = [(16384, 4096, jnp.bfloat16)]
        rms_tiles = (32, 64, 128, 256)
    else:
        rms_geoms = [(64, 32, jnp.float32)]
        rms_tiles = (2, 4, 8, 16)
    for n, d, dt in rms_geoms:
        x = jnp.asarray(rng.randn(n, d), dt)
        w = jnp.asarray(1.0 + 0.1 * rng.randn(d), dt)
        geom = dict(rows=n, d=d, dtype=str(jnp.dtype(dt)))
        cand = []
        for t in rms_tiles:
            if n % t:
                continue
            fn = jax.jit(functools.partial(fused_rms_norm, eps=1e-5,
                                           tile_n=t))
            ms, err = timed(fn, (x, w), f"rms_{n}_{d}_{t}")
            cand.append({"bench": "fused_rms_norm", **geom, "tile_n": t,
                         "ms": None if ms is None else round(ms, 4),
                         **({"error": err} if err else {})})
        finish("fused_rms_norm", geom, cand,
               lambda best: {"tile_n": best["tile_n"]})

    # --- conv-epilogue matmul: tm/tn/tk sweep --------------------------
    if on_tpu:
        ce_geoms = [(12544, 256, 512, jnp.bfloat16)]
        ce_tiles = [(128, 128, 256), (128, 256, 256), (256, 128, 512),
                    (256, 256, 512)]
    else:
        ce_geoms = [(64, 32, 128, jnp.float32)]
        ce_tiles = [(8, 128, 8), (16, 128, 16), (32, 128, 32),
                    (64, 128, 32)]
    for M, K, N, dt in ce_geoms:
        x2 = jnp.asarray(rng.randn(M, K), dt)
        wmat = jnp.asarray(0.05 * rng.randn(K, N), dt)
        bias = jnp.asarray(rng.randn(N), jnp.float32)
        geom = dict(M=M, K=K, N=N, dtype=str(jnp.dtype(dt)))
        sub = 16 if jnp.dtype(dt) == jnp.bfloat16 else 8
        cand = []
        for tm, tn, tk in ce_tiles:
            # a tiling the kernel would reject silently falls back to
            # jnp — that's not a candidate, it's a measurement of the
            # wrong thing
            if (M % tm or N % tn or K % tk or N % 128 or tk % sub
                    or tm % sub):
                continue
            fn = jax.jit(functools.partial(matmul_bias_act, relu=True,
                                           tiles=(tm, tn, tk)))
            ms, err = timed(fn, (x2, wmat, bias),
                            f"ce_{M}_{tm}_{tn}_{tk}")
            cand.append({"bench": "conv_epilogue", **geom,
                         "tm": tm, "tn": tn, "tk": tk,
                         "ms": None if ms is None else round(ms, 4),
                         **({"error": err} if err else {})})
        finish("conv_epilogue", geom, cand,
               lambda best: {"tm": best["tm"], "tn": best["tn"],
                             "tk": best["tk"]})

    # --- dropless-MoE grouped matmul: tile_m/tile_n sweep --------------
    if on_tpu:
        gm_geoms = [(8192, 2048, 5632, 8, 2, jnp.bfloat16)]
        gm_tiles = [(128, 128), (256, 256), (256, 512), (512, 256)]
    else:
        gm_geoms = [(32, 16, 32, 4, 2, jnp.float32)]
        gm_tiles = [(8, 16), (16, 16), (16, 32)]
    for S, D, F, E, k, dt in gm_geoms:
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        x = jax.random.normal(ks[0], (S, D), dt)
        wg = jax.random.normal(ks[1], (E, D, F), dt) * 0.02
        wu = jax.random.normal(ks[2], (E, D, F), dt) * 0.02
        wd = jax.random.normal(ks[3], (E, F, D), dt) * 0.02
        logits = jax.random.normal(ks[4], (S, E), jnp.float32)
        cw, eids = jax.lax.top_k(jax.nn.softmax(logits), k)
        cw = cw.astype(dt)
        args = (x, eids, cw, wg, wu, wd)
        geom = dict(S=S, D=D, F=F, E=E, k=k, dtype=str(jnp.dtype(dt)))
        cand = []
        for tm, tn in gm_tiles:
            # everything a jit ARGUMENT (see bench_moe) but the tiles
            # partial-bound so they stay concrete Python ints
            fn = jax.jit(functools.partial(
                lambda x, e, c, g, u, d2, tm, tn: moe_mlp_dropless(
                    x, e, c, g, u, d2, tile_m=tm, tile_n=tn),
                tm=tm, tn=tn))
            ms, err = timed(fn, args, f"gm_{S}_{tm}_{tn}")
            cand.append({"bench": "grouped_matmul", **geom,
                         "tile_m": tm, "tile_n": tn,
                         "ms": None if ms is None else round(ms, 4),
                         **({"error": err} if err else {})})
        finish("grouped_matmul", geom, cand,
               lambda best: {"tile_m": best["tile_m"],
                             "tile_n": best["tile_n"]})

    for row in results:
        print(json.dumps(row))
    if out:
        with open(out, "w") as f:
            for row in results:
                f.write(json.dumps(row) + "\n")
    return results


if __name__ == "__main__":
    from paddle_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    if "--block-sweep" in sys.argv or "--ragged-sweep" in sys.argv:
        path = next((a.split("=", 1)[1] for a in sys.argv
                     if a.startswith("--out=")), None)
        if "--block-sweep" in sys.argv:
            block_sweep(out=path)
        else:
            ragged_tiling_sweep(out=path)
    else:
        assert jax.default_backend() == "tpu", "run on the TPU chip"
        bench_moe()
        bench_rope()
        bench_rms()
