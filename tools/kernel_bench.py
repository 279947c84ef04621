"""Microbench: authored Pallas kernels vs XLA-fused baselines, on TPU.

Run: python tools/kernel_bench.py   (needs the real chip)

Methodology: per-call DEVICE time from a jax.profiler trace (sum of
jit_* device events / iterations): host wall-clock carries per-call
dispatch cost that can swamp a sub-millisecond kernel, device time is
what the hardware actually spends. The kernel table in docs/PERF.md is
from a machine that is gone; nothing is re-measured yet (PERF.md).

``--ragged-sweep`` times the ragged paged-attention kernel ALONE at
the serving cells' geometries (``ragged_cells()``, read from the
benchmark's own files): one JSON line for each (cell, decode or span
tick, share of the slots live, share of the table live,
``kv_tile_pages``) on stdout (and ``--out=path`` as JSONL), so one
reads whether the kernel's time follows the data or the launch's
static extents. ``--cells=chat,batch`` picks cells,
``--tiles=auto,16,64`` the tiles, ``--label=`` names the walk in the
rows. Where several tiles are given and ``$PADDLE_TPU_AUTOTUNE_DIR``
is set, the fastest is recorded through ``ops.autotune`` — the first
entry of the KForge-style autotune loop (PAPERS.md 2606.02963): block
shapes searched against the bench harness, cache picks the winner per
geometry. On TPU it reads the kernel's device events from a profiler
trace; off TPU it still runs end-to-end in interpreter mode at a tiny
size (wall-clock, ``timing_honest: false`` — the smoke path).

``--packed-sweep`` times the PACKED entry (``ragged_paged_attention_
packed``: each slot's rows copied into the kernel's blocks, the
kernel, its results gathered back) at the same geometries and at the
two launches of a cell whose spans enter as virtual slots
(``blocked_cells()``): a decode, a span and a verify tick a cell, a
quarter of the slots and all of them live; ``busy_ms`` a call, the
kernel's own ``kernel_ms``, what lies around it (``boundary_ms``) and
the packing plan's own ``plan_ms``. A boundary that wins here has still
to win in its tick (PERF.md section 6, PR 48).

``--mla-sweep`` times attention over latent pages (``ops/pallas/
mla_paged_attention.py``) ALONE at the geometry of every cell with a
latent cache (``mla_cells()``), and the expanded form of a span as plain
XLA beside it.

``--ssd-sweep`` times the Mamba-2 state pass (``ops/pallas/
ssd_update.py``) ALONE at the geometry of the serving cells with
state-space layers: decode rows only and with the cell's span, 0 / 25 /
100 % of the slots live, ``ms`` and the GB/s of the live slots' state.

``--held-sweep`` times ONE layer's routed experts through the
held-experts grouped matmul (``ops/pallas/grouped_matmul.py:
held_experts_swiglu``) ALONE at the geometry of the serving cells with
routed experts (``moe_cells()``), by share of the held experts that no
row chose, ``--tile-ms=`` and ``--tile-ns=<gate/up>x<down>``, and the
capacity einsum at C = N over the same rows beside it.

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


def _cells():
    """``(workloads entry, workload file, model)`` of every cell of
    ``BENCHMARK.json``, READ from the files the cell runs from: its
    workload file and its configuration with the workload's overrides."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def read(*path):
        with open(os.path.join(root, *path)) as f:
            return json.load(f)

    bench = read("BENCHMARK.json")
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for w in bench["workloads"]:
        work = read("benchmark", "workloads", w["name"] + ".json")
        yield w, work, {**read(files[w["config"]]),
                        **work.get("overrides", {})}


def _serving_cells():
    """``(workloads entry, model, engine geometry, slots x table)`` of
    every serving cell."""
    for w, work, model in _cells():
        if not work.get("mode", "").startswith("serve"):
            continue
        eng = work["engine"]
        ps, slots = eng["page_size"], eng["max_batch"]
        longest = max(eng.get("prompt_buckets") or [eng["max_prompt_len"]])
        pps = -(-(longest + eng["max_new_tokens_cap"] - 1) // ps)
        yield w, model, dict(
            slots=slots, page_size=ps, pps=pps,
            pages=eng.get("total_pages") or slots * pps + 1,
            span=eng["prefill_chunk"])


def ragged_cells():
    """What ONE layer's launch of the ragged kernel looks like in each
    serving cell of the benchmark that launches it, keyed by the cell's
    traffic name (chat, batch, generate), READ from the files the cell
    runs from (``_serving_cells``: slots, page size, the table's width
    as the engine sizes it, the pool, ``span``: the prefill chunk, the
    query rows a slot of a span tick gets; heads, head size, how many
    layers attend). ``kv_heads`` / ``group`` / ``head_dim`` are what
    the KERNEL sees: a head size under the chip's 128 lanes is served
    from a lane-packed pool (the generate cell: 4 KV heads of 8 query
    heads at width 128); ``model`` keeps the configuration's own three.
    A second cell of a traffic name is keyed by its CELL's name
    (``granite4h-serve-generate``; the first keeps the traffic name). A
    configuration with state-space layers adds ``ssm``: how many such
    layers, their heads, head size and state size (what ``ssd_sweep``
    and the described-chip compile of ``ssd_update`` read). A cell
    whose cache is latent pages is ``mla_cells()``'s. The one copy of
    these numbers: the sweeps below and tests/test_chip_compile.py both
    read it."""
    from paddle_tpu.ops.pallas.ragged_paged_attention import lane_pack_factor

    cells = {}
    for w, model, geo in _serving_cells():
        if "kv_lora_rank" in model or "hybrid_layer_pattern" in model:
            # latent pages are ``mla_cells()``'s; a cell whose window
            # and full layers launch the kernel at two geometries (k / v
            # head sizes 192 / 128, 8 and 4 KV heads, a span in virtual
            # slots) has its described-chip compile of the whole tick
            # (tests/test_chip_compile.py) and no sweep here yet
            continue
        heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
        dh = model.get("head_dim") or model["hidden_size"] // heads
        kinds = model.get("layer_types")
        f = lane_pack_factor(dh, kv)
        kinds = kinds and kinds[:model["num_hidden_layers"]]
        cell = dict(
            geo, kv_heads=kv // f, group=heads // kv * f, head_dim=dh * f,
            layers=(sum(k in ("full_attention", "attention") for k in kinds)
                    if kinds else model["num_hidden_layers"]),
            model=dict(heads=heads, kv_heads=kv, head_dim=dh))
        if kinds and "mamba" in kinds:
            cell["ssm"] = dict(
                layers=kinds.count("mamba"), heads=model["mamba_n_heads"],
                head_dim=model["mamba_d_head"], state=model["mamba_d_state"])
        cells[w["name"] if w["traffic"] in cells else w["traffic"]] = cell
    return cells


def mla_cells():
    """``ragged_cells()`` for the cells whose cache is LATENT pages
    (``ops/pallas/mla_paged_attention.py``), keyed by the cell's traffic
    name: the same geometry, the heads, the pool row's lanes
    (``row_width``), the value's lane prefix (``dv``) and the attention
    SUBLAYERS (two a layer)."""
    from paddle_tpu.ops.pallas.mla_paged_attention import latent_row_width

    return {w["traffic"]: dict(
        geo, heads=model["num_attention_heads"],
        row_width=latent_row_width(model["kv_lora_rank"],
                                   model["qk_rope_head_dim"]),
        dv=model["kv_lora_rank"], layers=2 * model["num_layers"])
        for w, model, geo in _serving_cells() if "kv_lora_rank" in model}


def moe_cells():
    """The routed-expert layer of every serving cell whose configuration
    has one, keyed by the cell's traffic name, READ from the files the
    cell runs from: the tick's rows (``slots``, and ``span`` more on a
    tick that carries a chunk), ``hidden`` x ``width`` an expert,
    ``held`` experts on this chip of ``routed`` router outputs, ``top_k``
    choices a row, ``layers`` such layers. The one copy of these
    numbers: ``held_sweep`` and tests/test_chip_compile.py read it."""
    cells = {}
    for w, model, geo in _serving_cells():
        if "n_routed_experts" in model:             # a share of the experts
            held = model["n_routed_experts"]
            # (the two families that hold a share name these three
            # differently: LongCat's keys, then MiMo-V2's)
            freq = model.get("moe_layer_freq")
            moe = dict(held=held,
                       top_k=model.get("moe_topk")
                       or model["num_experts_per_tok"],
                       routed=(model.get("router_experts", held)
                               + model.get("zero_expert_num", 0)),
                       width=model.get("expert_ffn_hidden_size")
                       or model["moe_intermediate_size"],
                       layers=sum(freq) if freq else model["num_layers"])
        elif model.get("num_experts"):
            held = model["num_experts"]
            moe = dict(held=held, routed=held,
                       top_k=model["num_experts_per_tok"],
                       width=model["moe_intermediate_size"],
                       layers=(model["num_hidden_layers"]
                               - model.get("num_dense_layers", 0)))
        else:
            continue
        cells[w["traffic"]] = dict(slots=geo["slots"], span=geo["span"],
                                   hidden=model["hidden_size"], **moe)
    return cells


# off the chip: the same shape of sweep at a size interpret mode can run
RAGGED_CELLS_TINY = {
    "tiny": dict(slots=4, kv_heads=2, group=2, head_dim=8, page_size=4,
                 pps=8, pages=33, span=4),
}


def _kernel_ms(trace_dir, prefix="ragged_paged_attention"):
    """Device time, in ms, of every event of the traced device's
    operation line whose name starts with ``prefix``, in the order
    they ran."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    evs = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                evs += [(ev.start_ns, ev.duration_ns) for ev in line.events
                        if ev.name.split(" = ", 1)[0].lstrip("%")
                        .startswith(prefix)]
    return [d / 1e6 for _, d in sorted(evs)]


def _emit(results, out=None):
    """A sweep's rows: printed one JSON object a line, and written to
    ``out`` where one is named."""
    for row in results:
        print(json.dumps(row))
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            for row in results:
                f.write(json.dumps(row) + "\n")
    return results


def ragged_sweep(out=None, iters=5, cells=None, tiles=(None,), label=""):
    """The ragged kernel alone at the serving cells' geometries
    (``ragged_cells()``), through the slot-major entry over a stacked
    pool with a layer index, as the serving tick launches it: one row
    for each (cell, tick kind, share of the slots live, share of the
    table live, ``kv_tile_pages``), with the ``copies`` the launch
    starts beside its ``ms`` (live pages x the copies a page). A
    ``decode`` tick gives every live slot one query row; a ``span`` tick gives the launch the cell's
    prefill chunk of rows a slot, one live slot a whole chunk and the
    others one token. Only the public entry is called, so the same
    file times another checkout's kernel (copy it into that tree):
    ``label`` names the walk in the rows. On the chip a row's ``ms`` is
    the kernel's own device time from a profiler trace; off it the
    interpreter's wall clock (``timing_honest: false``). Where several
    ``tiles`` are given and ``$PADDLE_TPU_AUTOTUNE_DIR`` is set, the
    tile with the least summed time is recorded for the cell's
    geometry (audit-gated), and a resolution row says what a default
    call then resolves to."""
    import tempfile
    from paddle_tpu.ops import autotune as at
    from paddle_tpu.ops.pallas import ragged_paged_attention as R
    on_tpu = jax.default_backend() == "tpu"
    table = ragged_cells() if on_tpu else RAGGED_CELLS_TINY
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    layers = 2
    results = []
    for cell in cells or table:
        c = table[cell]
        S, Hkv, G, Dh = c["slots"], c["kv_heads"], c["group"], c["head_dim"]
        ps, pps, P = c["page_size"], c["pps"], c["pages"]
        rng = np.random.RandomState(0)
        kk, kv_ = jax.random.split(jax.random.PRNGKey(0))
        kp = jax.random.normal(kk, (layers, Hkv, P, ps, Dh), dt)
        vp = jax.random.normal(kv_, (layers, Hkv, P, ps, Dh), dt)
        # scattered page lists, as a pool in service has them
        tabs = jnp.asarray(1 + rng.randint(0, P - 1, (S, pps)), jnp.int32)
        ageom = dict(pages_per_slot=pps, page_size=ps, head_dim=Dh,
                     dtype=str(jnp.dtype(dt)))
        runs = []                       # (row, jitted fn, args)

        def copies_a_page(tile, tq):
            # copies the walk starts for one live page: the kernel's
            # own count where it has one (a grid step of several heads
            # moves them with one copy a pool), else one a (pool, head)
            count = getattr(R, "page_copies", None)
            if count is None:
                return 2 * Hkv
            return count(Hkv, pps, ps, Dh, dt, rows=tq * G,
                         kv_tile_pages=tile)

        for tile in tiles:
            audit = _audit_verdict(
                "ragged_paged_attention", ageom,
                None if tile is None else {"kv_tile_pages": tile})
            for kind, tq in (("decode", 1), ("span", c["span"])):
                fn = jax.jit(functools.partial(
                    R.ragged_paged_attention, impl="pallas",
                    kv_tile_pages=tile, layer=1))
                q = jnp.asarray(rng.randn(S, tq, Hkv * G, Dh), dt)
                for slots_live, table_live in (
                        [(0.0, 0.0)] + [(a, b) for a in (0.25, 1.0)
                                        for b in (0.25, 0.4, 1.0)]):
                    n_live = int(round(slots_live * S))
                    kv = int(round(table_live * pps * ps))
                    ql = np.zeros((S,), np.int32)
                    # live slots spread over the table's rows, the
                    # span (if any) in the first of them
                    live = (np.arange(n_live) * S) // max(n_live, 1)
                    ql[live] = 1
                    if kind == "span" and n_live:
                        ql[live[0]] = min(tq, kv)
                    kl = np.where(ql > 0, kv, 0).astype(np.int32)
                    row = {
                        "bench": "ragged_sweep", "label": label,
                        "cell": cell, "tick": kind, "tq": tq,
                        "slots": S, "kv_heads": Hkv, "group": G,
                        "head_dim": Dh, "pps": pps, "page_size": ps,
                        "slots_live": slots_live, "table_live": table_live,
                        "live_slots": n_live, "kv_len": kv,
                        "kv_pages": int(n_live * -(-kv // ps)),
                        "kv_pages_table": S * pps,
                        "copies": int(n_live * -(-kv // ps)
                                      * copies_a_page(tile, tq)),
                        "kv_tile_pages": tile, "audit": audit,
                        "timing_honest": on_tpu}
                    runs.append((row, fn, (q, kp, vp, jnp.asarray(ql),
                                           jnp.asarray(kl), tabs)))
        ran = []
        for row, fn, args in runs:      # compile outside the trace
            try:
                jax.block_until_ready(fn(*args))
                ran.append((row, fn, args))
            except Exception as e:      # a refused compile IS the row
                results.append(dict(row, ms=None, error=str(e)[-300:]))
        if on_tpu:
            tdir = tempfile.mkdtemp(prefix=f"kb_ragged_{cell}_")
            with jax.profiler.trace(tdir):
                for _, fn, args in ran:
                    for _ in range(iters):
                        y = fn(*args)
                    jax.block_until_ready(y)
            ms = _kernel_ms(tdir)
            assert len(ms) == iters * len(ran), (len(ms), iters, len(ran))
            for i, (row, _, _) in enumerate(ran):
                mine = sorted(ms[i * iters:(i + 1) * iters])
                results.append(dict(row, ms=round(mine[len(mine) // 2], 5)))
        else:
            for row, fn, args in ran:
                results.append(dict(
                    row, ms=round(_walltime(fn, args, n=iters), 4)))
        # the flywheel's write side: the tile with the least summed
        # time over the cell's rows, audit-gated, then what a default
        # call resolves to
        mine = [r for r in results if r["cell"] == cell]
        totals = {t: sum(r["ms"] for r in mine if r["kv_tile_pages"] == t)
                  for t in tiles if t is not None
                  and all(r.get("ms") is not None for r in mine
                          if r["kv_tile_pages"] == t)}
        if len(totals) > 1 and at.store_dir():
            winner = {"kv_tile_pages": min(totals, key=totals.get)}
            try:
                at.record("ragged_paged_attention", winner, audit=True,
                          **ageom)
            except at.AutotuneAuditError as e:
                results.append({"bench": "ragged_sweep", "cell": cell,
                                **ageom, **winner,
                                "audit_failed": str(e)[:200]})
        win = at.lookup("ragged_paged_attention", **ageom)
        results.append({"bench": "ragged_sweep", "cell": cell,
                        "resolution": True, **ageom, **(win or {}),
                        "tiling_source": "swept" if win else "default"})
    return _emit(results, out)


def blocked_cells():
    """The ragged kernel's launches in a serving cell whose full and
    window layers take it at two geometries (``models/mimo_v2_flash.py``),
    keyed ``<traffic>.full`` / ``<traffic>.window``: ``ragged_cells()``'s
    fields, READ from the same files, and what such a launch adds: a key
    row as the pool holds it (192 -> 256 lanes, the queries padded
    alike), ``v_dim``, ``window`` and ``sinks``, a window layer's ring
    of pages a slot, and ``block_tokens``: a span enters as virtual
    slots of that many tokens."""
    from paddle_tpu.models.mimo_v2_flash import BLOCK_TOKENS

    cells = {}
    for w, model, geo in _serving_cells():
        if "hybrid_layer_pattern" not in model:
            continue
        heads, ps = model["num_attention_heads"], geo["page_size"]
        dk = -(-model["head_dim"] // 128) * 128
        W = model["sliding_window"]
        ring = -(-(W - 1 + geo["span"]) // ps) + 1
        for kind, kv, win in (
                ("full", model["num_key_value_heads"], 0),
                ("window", model["swa_num_key_value_heads"], W)):
            cells[f"{w['traffic']}.{kind}"] = dict(
                geo, kv_heads=kv, group=heads // kv, head_dim=dk,
                v_dim=model["v_head_dim"], window=win, sinks=bool(win),
                ring=ring if win else 0, block_tokens=BLOCK_TOKENS,
                pages=geo["slots"] * ring + 1 if win else geo["pages"],
                layers=sum(bool(x) == bool(win)
                           for x in model["hybrid_layer_pattern"]))
    return cells


BLOCKED_CELLS_TINY = {
    "tiny.blocked": dict(slots=4, kv_heads=2, group=2, head_dim=8, v_dim=4,
                         page_size=4, pps=8, pages=33, span=8, window=5,
                         sinks=True, ring=0, block_tokens=2),
}


def _packed_stream(S, tq, n_live, ctx, pps, ps, spans=1):
    """A tick's metadata as the engine packs it: ``n_live`` slots spread
    over the table's rows hold one decode row each over ``ctx`` tokens
    of context, the first ``spans`` of them (None: every one, as a
    speculative tick's verify rows are) a span of ``tq`` rows where
    ``tq > 1``; ``T = S + tq`` stream rows (``S`` at ``tq`` 1, ``S * tq``
    where every slot may hold a span), the padding behind the slots.
    ``(tok_slot, tok_qoff, q_len, kv_len, start)``."""
    T = S * tq if spans is None else S + (tq if tq > 1 else 0)
    ql, kl = np.zeros((S,), np.int32), np.zeros((S,), np.int32)
    live = (np.arange(n_live) * S) // max(n_live, 1)
    ql[live], kl[live] = 1, ctx
    if tq > 1 and n_live:
        wide = live[:spans]
        ql[wide], kl[wide] = tq, min(ctx + tq, pps * ps)
    start = np.concatenate([[0], np.cumsum(ql)[:-1]]).astype(np.int32)
    tok_slot = np.full((T,), S, np.int32)
    tok_qoff = np.zeros((T,), np.int32)
    for s in np.flatnonzero(ql):
        tok_slot[start[s]:start[s] + ql[s]] = s
        tok_qoff[start[s]:start[s] + ql[s]] = np.arange(ql[s])
    return tok_slot, tok_qoff, ql, kl, start


VERIFY_ROWS = 5     # a speculative tick's rows a drafting slot (1 + k)


def packed_sweep(out=None, iters=5, cells=None, label=""):
    """The packed entry ALONE at the serving cells' geometries
    (``ragged_cells()`` and ``blocked_cells()``), as a layer of the tick
    calls it (stacked pools, a layer index, the packing's plan made
    outside it): one row for each (cell, tick kind, share of the slots
    live), with the device's ``busy_ms`` a call, the kernel's own
    ``kernel_ms``, ``boundary_ms`` = what the entry spends around the
    kernel (laying the stream into the kernel's blocks, gathering the
    results back) and ``plan_ms``, the plan's own time, once a tick. A
    tick kind is ``decode`` (one row a live slot), ``span`` (the cell's
    prefill chunk in the first live slot beside the decode rows) or
    ``verify`` (``VERIFY_ROWS`` rows in EVERY live slot, as a
    speculative tick packs its drafts). Only the public entry is
    called, so the same file times another checkout's boundary (copy it
    into that tree; a checkout from before ``stream_plan`` takes the
    metadata alone, and its ``plan_ms`` is inside ``boundary_ms``). Off
    the chip: the interpreter's wall clock at a tiny size
    (``timing_honest: false``)."""
    import tempfile
    from paddle_tpu.ops.pallas import ragged_paged_attention as R
    on_tpu = jax.default_backend() == "tpu"
    table = ({**ragged_cells(), **blocked_cells()} if on_tpu
             else {**RAGGED_CELLS_TINY, **BLOCKED_CELLS_TINY})
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    make_plan = getattr(R, "stream_plan", None)

    def device_ms(fn, args):
        """(busy, the kernel's own) ms a call of ``fn``."""
        tdir = tempfile.mkdtemp(prefix="kb_packed_")
        with jax.profiler.trace(tdir):
            for _ in range(iters):
                y = fn(*args)
            jax.block_until_ready(y)
        return _busy_ms(tdir) / iters, sum(_kernel_ms(tdir)) / iters

    results = []
    for cell in cells or table:
        c = table[cell]
        S, ps, pps, P = c["slots"], c["page_size"], c["pps"], c["pages"]
        bt = c.get("block_tokens", 0)
        # the stream's heads are the MODEL's; a lane-packed pool holds
        # the kernel's (fewer, wider)
        m = c.get("model") or dict(heads=c["kv_heads"] * c["group"],
                                   head_dim=c["head_dim"])
        rng = np.random.RandomState(0)
        kk, kv_ = jax.random.split(jax.random.PRNGKey(0))
        pool = (2, c["kv_heads"], P, ps)
        kp = jax.random.normal(kk, pool + (c["head_dim"],), dt)
        vp = jax.random.normal(kv_, pool + (c.get("v_dim", c["head_dim"]),),
                               dt)
        if c.get("ring"):       # a window layer's ring of pages a slot
            tabs = jnp.asarray(np.arange(S)[:, None] * c["ring"]
                               + np.arange(pps)[None] % c["ring"], jnp.int32)
        else:
            tabs = jnp.asarray(1 + rng.randint(0, P - 1, (S, pps)),
                               jnp.int32)
        opts = dict(window=c["window"]) if c.get("window") else {}
        if c.get("sinks"):
            opts["sinks"] = jnp.asarray(rng.randn(m["heads"]), jnp.float32)
        runs = []
        for kind, tq, spans in (("decode", 1, 1), ("span", c["span"], 1),
                                ("verify", VERIFY_ROWS, None)):
            for slots_live in (0.25, 1.0):
                n_live = max(1, int(round(slots_live * S)))
                ctx = min(pps * ps // 4, pps * ps - tq)
                *meta, start = map(jnp.asarray, _packed_stream(
                    S, tq, n_live, ctx, pps, ps, spans))
                T = meta[0].shape[0]
                q = jnp.asarray(rng.randn(T, m["heads"], m["head_dim"]), dt)
                plan_fn = None
                if make_plan:
                    # a walk makes the plan once a tick, outside its
                    # layers: here outside the timed call
                    mk = functools.partial(
                        make_plan, tq=tq, heads=m["heads"],
                        pool=jax.ShapeDtypeStruct(kp.shape, kp.dtype),
                        block_tokens=bt)
                    kw = dict(plan=mk(*meta, tabs, start=start))
                    plan_fn = jax.jit(lambda *a, mk=mk: [
                        x for x in mk(*a[:-1], start=a[-1])
                        if isinstance(x, jax.Array)])
                else:
                    kw = dict(block_tokens=bt, start=start) if bt else {}
                fn = jax.jit(functools.partial(
                    R.ragged_paged_attention_packed, tq=tq, layer=1,
                    impl="pallas", **opts, **kw))
                row = {"bench": "packed_sweep", "label": label,
                       "cell": cell, "tick": kind, "tq": tq, "slots": S,
                       "stream_rows": T, "heads": m["heads"],
                       "kv_heads": c["kv_heads"], "head_dim": m["head_dim"],
                       "block_tokens": bt,
                       "slots_live": slots_live, "live_slots": n_live,
                       "kv_len": ctx, "timing_honest": on_tpu}
                runs.append((row, fn, (q, kp, vp, *meta, tabs),
                             plan_fn, (*meta, tabs, start)))
        for row, fn, args, plan_fn, plan_args in runs:
            jax.block_until_ready(fn(*args))        # compile outside it
            if not on_tpu:
                results.append(dict(row, busy_ms=round(
                    _walltime(fn, args, n=iters), 4)))
                continue
            busy, kern = device_ms(fn, args)
            row = dict(row, busy_ms=round(busy, 5), kernel_ms=round(kern, 5),
                       boundary_ms=round(busy - kern, 5))
            if plan_fn:
                jax.block_until_ready(plan_fn(*plan_args))
                row["plan_ms"] = round(device_ms(plan_fn, plan_args)[0], 5)
            results.append(row)
    return _emit(results, out)


def mla_sweep(out=None, iters=5, cells=None, label="", blocks=(None,)):
    """Attention over latent pages (``ops/pallas/mla_paged_attention.py``)
    ALONE at the geometry of every cell with a latent cache
    (``mla_cells()``), through the public entry over a stacked pool with
    a layer index, as the tick launches it: one row for each (cell, tick
    kind, share of the slots live, context, ``block_tokens``). A
    ``decode`` launch gives every live slot one token (``heads`` rows);
    a ``span`` launch adds the cell's prefill chunk on one more slot,
    whose context is the row's ``kv_len`` at the span's END. ``pct`` is
    the share of the roofline the benchmark's reader would give the
    launch (the longer of the published latent bytes over the peak
    bandwidth and the absorbed form's FLOPs over the peak rate; v5e
    peaks). ``expanded`` rows time the OTHER form of a span as plain
    XLA: ``kv_b`` applied to the span's context and a dense causal
    softmax over it (what a program that expands would add to a span;
    its decode rows would stay absorbed). Off the chip: a tiny size in
    interpret mode, the wall clock (``timing_honest: false``)."""
    import tempfile
    from paddle_tpu.ops.pallas import mla_paged_attention as K
    on_tpu = jax.default_backend() == "tpu"
    table = mla_cells() if on_tpu else {"tiny": dict(
        slots=4, page_size=16, pps=6, pages=25, span=16, heads=4,
        row_width=128, dv=64, layers=2)}
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    results = []
    for cell in cells or table:
        c = table[cell]
        S, ps, pps, P = c["slots"], c["page_size"], c["pps"], c["pages"]
        H, dk, dv, span = c["heads"], c["row_width"], c["dv"], c["span"]
        rng = np.random.RandomState(0)
        pool = jax.random.normal(jax.random.PRNGKey(0), (2, P, ps, dk), dt)
        tabs = jnp.asarray(1 + rng.randint(0, P - 1, (S, pps)), jnp.int32)
        cap = pps * ps
        contexts = sorted({min(cap, x) for x in
                           ((span, 2 * span, cap) if not on_tpu else
                            (512, 2048, 4096, 8192, cap))})
        runs = []
        for tb in blocks:
            fn = jax.jit(functools.partial(
                K.mla_paged_attention, dv=dv, sm_scale=0.07, impl="pallas",
                layer=1, block_tokens=tb))
            for kind in ("decode", "span"):
                T = S + (span if kind == "span" else 0)
                q = jnp.asarray(rng.randn(T, H, dk), dt)
                for share in ((0.0, 0.9375) if kind == "decode" else (0.9375,)):
                    for kv in contexts:
                        n_live = int(round(share * (S - 1)))
                        ql = np.zeros((S,), np.int32)
                        ql[:n_live] = 1
                        kl = np.where(ql > 0, kv, 0).astype(np.int32)
                        start = np.arange(S, dtype=np.int32)
                        pairs = float(n_live * kv)
                        if kind == "span":      # the last slot prefills
                            ql[S - 1], kl[S - 1], start[S - 1] = (
                                min(span, kv), kv, S)
                            pairs += ql[S - 1] * (kv - (ql[S - 1] - 1) / 2)
                        kv_tokens = float(kl.sum())
                        # the published row: dv + 64 values of 2 B
                        least = max(kv_tokens * 2 * (dv + 64) / 819e9,
                                    pairs * H * (2 * dv + 64) * 2 / 197e12)
                        runs.append(({
                            "bench": "mla_sweep", "label": label,
                            "cell": cell, "tick": kind, "slots": S,
                            "heads": H, "pps": pps, "page_size": ps,
                            "slots_live": n_live + (kind == "span"),
                            "kv_len": kv, "kv_tokens": kv_tokens,
                            "attn_pairs": pairs, "block_tokens": tb,
                            "least_ms": round(least * 1e3, 5),
                            "timing_honest": on_tpu}, fn,
                            (q, pool, jnp.asarray(start), jnp.asarray(ql),
                             jnp.asarray(kl), tabs)))
        ran = []
        for row, fn, args in runs:      # compile outside the trace
            try:
                jax.block_until_ready(fn(*args))
                ran.append((row, fn, args))
            except Exception as e:      # a refused compile IS the row
                results.append(dict(row, ms=None, error=str(e)[-300:]))
        if on_tpu:
            tdir = tempfile.mkdtemp(prefix=f"kb_mla_{cell}_")
            with jax.profiler.trace(tdir):
                for _, fn, args in ran:
                    for _ in range(iters):
                        y = fn(*args)
                    jax.block_until_ready(y)
            ms = _kernel_ms(tdir, prefix="mla_paged_attention")
            assert len(ms) == iters * len(ran), (len(ms), iters, len(ran))
            for i, (row, _, _) in enumerate(ran):
                mid = sorted(ms[i * iters:(i + 1) * iters])[iters // 2]
                results.append(dict(row, ms=round(mid, 5), pct=round(
                    100 * row["least_ms"] / mid, 2) if mid else None))
        else:
            for row, fn, args in ran:
                results.append(dict(
                    row, ms=round(_walltime(fn, args, n=iters), 4)))
        # the expanded form of a span, as plain XLA, by the wall clock
        # around block_until_ready (no kernel of its own to find in a
        # trace): kv_b on the context, then dense causal attention
        nope, rp, vd = (128, 64, 128) if on_tpu else (16, 8, 16)

        def expanded(qn, qr, lat, w_k, w_v):
            c, kr = lat[:, :dv], lat[:, dv:dv + rp]
            k_n = jnp.einsum("sc,hnc->shn", c, w_k)
            v = jnp.einsum("sc,hcv->shv", c, w_v)
            sc = (jnp.einsum("thn,shn->hts", qn, k_n)
                  + jnp.einsum("thr,sr->hts", qr, kr)).astype(jnp.float32)
            n = lat.shape[0]
            mask = (jnp.arange(n)[None] <= (n - qn.shape[0]
                                            + jnp.arange(qn.shape[0]))[:, None])
            p = jax.nn.softmax(jnp.where(mask[None], sc, -1e30), -1)
            return jnp.einsum("hts,shv->thv", p.astype(v.dtype), v)

        for kv in contexts:
            n = min(span, kv)
            args = (jnp.asarray(rng.randn(n, H, nope), dt),
                    jnp.asarray(rng.randn(n, H, rp), dt),
                    jnp.asarray(rng.randn(kv, dk), dt),
                    jnp.asarray(rng.randn(H, nope, dv), dt),
                    jnp.asarray(rng.randn(H, dv, vd), dt))
            try:
                ms = _walltime(jax.jit(expanded), args, n=iters)
            except Exception as e:
                results.append({"bench": "mla_sweep", "cell": cell,
                                "tick": "span-expanded-xla", "kv_len": kv,
                                "ms": None, "error": str(e)[-200:]})
                continue
            results.append({"bench": "mla_sweep", "label": label,
                            "cell": cell, "tick": "span-expanded-xla",
                            "kv_len": kv, "span": n, "ms": round(ms, 4),
                            "timing_honest": on_tpu})
    return _emit(results, out)


def ssd_sweep(out=None, iters=5, cells=None, label=""):
    """The state pass of a Mamba-2 layer (``ops/pallas/ssd_update.py``)
    ALONE at the geometry of every serving cell whose configuration
    has state-space layers (``ragged_cells()[...]["ssm"]``), through the
    public entry over a stacked state with a layer index, as the tick
    launches it: one row for each (cell, decode rows only or with the
    cell's span, share of the slots live). ``gbps`` is the live slots'
    state read once and written once over the kernel's device time.
    Off the chip: a tiny size in interpret mode, the wall clock
    (``timing_honest: false``)."""
    import tempfile
    from paddle_tpu.ops.pallas import ssd_update as K
    on_tpu = jax.default_backend() == "tpu"
    table = ({k: v for k, v in ragged_cells().items() if "ssm" in v}
             if on_tpu else
             {"tiny": dict(slots=4, span=8,
                           ssm=dict(heads=2, head_dim=64, state=16))})
    results = []
    for cell in cells or table:
        c = table[cell]
        S, span, m = c["slots"], c["span"], c["ssm"]
        N, HP = m["state"], m["heads"] * m["head_dim"]
        rng = np.random.RandomState(0)
        state = jnp.asarray(rng.randn(2, S + 1, N, HP), jnp.float32)
        fn = jax.jit(functools.partial(K.ssd_update, impl="pallas"),
                     donate_argnums=(0,))
        runs = []
        for kind, extra in (("decode", 0), ("span", span)):
            for share in (0.0, 0.25, 1.0):
                n_live = int(round(share * S))
                live = (np.arange(n_live) * S) // max(n_live, 1)
                # one row a live slot, then the span on the first of
                # them; the launch's width is the tick's: slots + span
                T = S + extra
                tok = np.full((T,), S, np.int32)
                rows = list(live[:1]) * extra + list(live)
                tok[:len(rows)] = rows
                args = (jnp.asarray(1, jnp.int32),
                        jnp.asarray(rng.randn(T, N), jnp.float32),
                        jnp.asarray(rng.randn(T, N), jnp.float32),
                        jnp.asarray(rng.randn(T, HP), jnp.float32),
                        jnp.asarray(rng.rand(S, HP), jnp.float32),
                        jnp.asarray(tok))
                geom = dict(slots=S, rows=T, state=N, lanes=HP)
                runs.append(({
                    "bench": "ssd_sweep", "label": label, "cell": cell,
                    "tick": kind, **geom, "slots_live": share,
                    "live_slots": n_live,
                    "state_bytes": 2 * K.state_bytes(n_live, HP, N),
                    "head_blocks": K.default_head_blocks(T, S, HP, N),
                    "audit": _audit_verdict("ssd_update", geom, None),
                    "timing_honest": on_tpu}, fn, args))
        for _, fn, args in runs:        # compile outside the trace
            _, state = fn(state, *args)
        jax.block_until_ready(state)
        if on_tpu:
            tdir = tempfile.mkdtemp(prefix=f"kb_ssd_{cell}_")
            with jax.profiler.trace(tdir):
                for _, fn, args in runs:
                    for _ in range(iters):
                        _, state = fn(state, *args)
                jax.block_until_ready(state)
            ms = _kernel_ms(tdir, prefix="ssd_update")
            assert len(ms) == iters * len(runs), (len(ms), iters, len(runs))
            for i, (row, _, _) in enumerate(runs):
                mid = sorted(ms[i * iters:(i + 1) * iters])[iters // 2]
                results.append(dict(
                    row, ms=round(mid, 5),
                    gbps=round(row["state_bytes"] / mid / 1e6, 1)))
        else:
            for row, fn, args in runs:
                t0 = time.perf_counter()
                for _ in range(iters):
                    _, state = fn(state, *args)
                jax.block_until_ready(state)
                results.append(dict(row, ms=round(
                    (time.perf_counter() - t0) / iters * 1e3, 4)))
    return _emit(results, out)


def _busy_ms(trace_dir):
    """The traced device's busy time in ms: the union of its operations'
    intervals (the line holds a loop AND the operations inside it)."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    evs = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs += [(ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in line.events]
    busy, end = 0, 0
    for a, b in sorted(evs):
        busy += max(0, b - max(a, end))
        end = max(end, b)
    return busy / 1e6


def held_sweep(out=None, iters=5, cells=None, label="", tile_ms=(None,),
               tile_ns=(None,)):
    """The routed experts of ONE layer (``ops/pallas/grouped_matmul.py:
    held_experts_swiglu`` over the model's stacks at a layer index)
    ALONE at the geometry of every serving cell with routed experts
    (``moe_cells()``): one row for each (cell, decode rows only or with
    the cell's span, share of the held experts that NO row chose,
    ``tile_m``, the two matmuls' ``tile_n``). ``ms`` is the device's
    busy time a call (sort, gather, both grouped matmuls, combine),
    ``kernel_ms`` the two matmuls', ``gbps`` the touched experts' bytes
    over ``ms``. Beside them ``impl: einsum``: the capacity einsum at C
    = N over the same rows (``incubate/moe/functional.py:
    moe_expert_compute``), which reads every expert. ``--tile-ms=16,32``
    and ``--tile-ns=auto,1408x2048`` (gate/up x down) pick the blocks.
    Off the chip: a tiny size in interpret mode, the wall clock
    (``timing_honest: false``)."""
    import tempfile
    from paddle_tpu.incubate.moe.functional import moe_expert_compute
    from paddle_tpu.ops.pallas import grouped_matmul as G
    on_tpu = jax.default_backend() == "tpu"
    table = moe_cells() if on_tpu else {
        "tiny": dict(slots=4, span=8, hidden=128, width=256, held=6,
                     routed=6, top_k=2, layers=2)}
    auto_tile_n = G.held_tile_n
    results = []
    for cell in cells or table:
        c = table[cell]
        D, F, E, k = c["hidden"], c["width"], c["held"], c["top_k"]
        rng = np.random.RandomState(0)
        draw = jax.jit(lambda key, shape: (jax.random.normal(
            key, shape, jnp.float32) * 0.02).astype(jnp.bfloat16),
            static_argnums=1)
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        wg, wu = draw(keys[0], (2, E, D, F)), draw(keys[1], (2, E, D, F))
        wd = draw(keys[2], (2, E, F, D))
        layer = jnp.asarray(1, jnp.int32)
        runs = []
        for kind, rows in (("decode", c["slots"]),
                           ("span", c["slots"] + c["span"])):
            x = jnp.asarray(rng.randn(rows, D), jnp.bfloat16)
            # the pairs this chip holds: every pair where it holds every
            # expert, the deployment's share of them otherwise
            pairs = rows * k * E // c["routed"]
            for idle in (0.0, 1 / 3, 0.5):
                n_idle = int(round(idle * E))
                live = np.sort(rng.permutation(E)[:E - n_idle])
                ids = np.full((rows * k,), E, np.int32)
                # round-robin: every live expert takes a row where the
                # pairs reach that far
                ids[rng.permutation(rows * k)[:pairs]] = live[
                    np.arange(pairs) % len(live)]
                touched = len(set(ids.tolist()) - {E})
                ids = jnp.asarray(ids.reshape(rows, k))
                wts = jnp.asarray(rng.rand(rows, k), jnp.float32)
                for tm in tile_ms:
                    for tn in tile_ns:
                        def fn(x, ids, wts, wg, wu, wd, layer, tm=tm):
                            return G.held_experts_swiglu(
                                x, ids, wts, wg, wu, wd, layer=layer,
                                **({} if tm is None else {"tile_m": tm}))[0]

                        blocks = (None if tn is None else
                                  {(D, F): tn[0], (F, D): tn[1]})
                        row = {"bench": "held_sweep", "label": label,
                               "cell": cell, "tick": kind, "rows": rows,
                               "impl": "held", "experts": E,
                               "experts_idle": n_idle,
                               "experts_touched": touched, "pairs": pairs,
                               "tile_m": tm or 16,
                               "tile_n": [(blocks or {}).get(s) or
                                          auto_tile_n(*s)
                                          for s in ((D, F), (F, D))],
                               "bytes": touched * 3 * D * F * 2,
                               "timing_honest": on_tpu}
                        runs.append((row, jax.jit(fn), blocks,
                                     (x, ids, wts, wg, wu, wd, layer)))
            # the capacity einsum at C = N over the same rows
            if E == c["routed"]:
                disp = jnp.asarray(rng.rand(rows, E, rows) < k / E / rows,
                                   jnp.bfloat16)

                def ein(x, disp, wg, wu, wd, layer):
                    at = lambda a: jax.lax.dynamic_index_in_dim(
                        a, layer, 0, keepdims=False)
                    return moe_expert_compute(x, disp, disp, at(wg), at(wu),
                                              at(wd))

                runs.append(({"bench": "held_sweep", "label": label,
                              "cell": cell, "tick": kind, "rows": rows,
                              "impl": "einsum", "experts": E,
                              "experts_touched": E,
                              "bytes": E * 3 * D * F * 2,
                              "timing_honest": on_tpu},
                             jax.jit(ein), None,
                             (x, disp, wg, wu, wd, layer)))
        for row, fn, blocks, args in runs:
            G.held_tile_n = (auto_tile_n if blocks is None else
                             lambda K, N, *a, b=blocks: b[(K, N)])
            try:
                jax.block_until_ready(fn(*args))    # compile outside
                if on_tpu:
                    tdir = tempfile.mkdtemp(prefix=f"kb_held_{cell}_")
                    with jax.profiler.trace(tdir):
                        for _ in range(iters):
                            y = fn(*args)
                        jax.block_until_ready(y)
                    ms = _busy_ms(tdir) / iters
                    kern = _kernel_ms(tdir, prefix="held_experts_matmul")
                    row = dict(row, kernel_ms=round(sum(kern) / iters, 5))
                else:
                    ms = _walltime(fn, args, n=iters)
                results.append(dict(
                    row, ms=round(ms, 5),
                    gbps=round(row["bytes"] / ms / 1e6, 1)))
            except Exception as e:   # a block the compiler refuses is a row
                results.append(dict(row, error=f"{type(e).__name__}: "
                                    f"{str(e)[:300]}"))
            finally:
                G.held_tile_n = auto_tile_n
    return _emit(results, out)


def train_gmm_cells():
    """The held experts' grouped matmul of every TRAINING cell whose
    configuration holds a share of routed experts, keyed by the cell's
    name, READ from the files the cell runs from: ``rows`` a step,
    ``hidden`` x ``width`` an expert, ``held`` experts of ``routed``
    router outputs, ``top_k`` choices a row. The one copy of these
    numbers: ``train_gmm_sweep`` and tests/test_chip_compile.py read
    it."""
    cells = {}
    for w, work, m in _cells():
        if work.get("mode") != "train" or "moe_intermediate_size" not in m:
            continue
        t = work["trainer"]
        cells[w["name"]] = dict(
            rows=t["batch"] * t["seq_len"], batch=t["batch"],
            seq_len=t["seq_len"], hidden=m["hidden_size"],
            width=m["moe_intermediate_size"], held=m["n_routed_experts"],
            routed=m.get("router_experts", m["n_routed_experts"]),
            top_k=m["num_experts_per_tok"],
            heads=m["num_attention_heads"],
            qk=m["qk_nope_head_dim"] + m["qk_rope_head_dim"],
            dv=m["v_head_dim"])
    return cells


def train_gmm_sweep(out=None, iters=5, cells=None, label="",
                    tile_ms=(128, 256, 512)):
    """ONE grouped matmul of a training cell's held experts, ``[rows,
    hidden] @ [held, hidden, width]``, alone, each way: ``forward``
    (``_gmm_call``), ``dX`` (the same kernel over the transposed
    stack; ``dX+T`` with the transpose in front of it) and ``dW``
    (``_tgmm_call``), by rows an expert (256 / 512 / 1024), uniform and
    skewed counts (loads from a quarter to seven quarters of the mean)
    and ``tile_m``; the sorted buffer as the step sizes it (twice the
    rows, the tail dead). Beside them the EXPERT walk's forward
    (``_held_gmm_call``, one stack) at the same rows, and splash's
    forward + backward at the cell's q / k head size as it is and
    padded to the next lane tile. ``ms`` is the device's busy time a
    call, ``tflops`` the real rows' arithmetic over it."""
    import tempfile
    from paddle_tpu.ops.pallas import flash_attention as FA
    from paddle_tpu.ops.pallas import grouped_matmul as G
    on_tpu = jax.default_backend() == "tpu"
    table = train_gmm_cells() if on_tpu else {
        "tiny": dict(rows=256, batch=1, seq_len=256, hidden=128, width=128,
                     held=4, routed=16, top_k=4, heads=2, qk=192, dv=128)}
    if not on_tpu:
        tile_ms = (16, 32)
    results = []

    def timed(row, fn, args, flops):
        try:
            jax.block_until_ready(fn(*args))
            if on_tpu:
                tdir = tempfile.mkdtemp(prefix="kb_tgmm_")
                with jax.profiler.trace(tdir):
                    for _ in range(iters):
                        y = fn(*args)
                    jax.block_until_ready(y)
                ms = _busy_ms(tdir) / iters
            else:
                ms = _walltime(fn, args, n=iters)
            results.append(dict(row, ms=round(ms, 5),
                                tflops=round(flops / ms / 1e9, 2),
                                timing_honest=on_tpu))
        except Exception as e:      # a block the compiler refuses is a row
            results.append(dict(row, error=f"{type(e).__name__}: "
                                f"{str(e)[:300]}"))

    for name in cells or table:
        c = table[name]
        D, F, E = c["hidden"], c["width"], c["held"]
        rng = np.random.RandomState(0)
        keys = jax.random.split(jax.random.PRNGKey(0), 2)
        w = (jax.random.normal(keys[0], (E, D, F), jnp.float32)
             * 0.02).astype(jnp.bfloat16)
        mean_rows = c["rows"] * c["top_k"] // c["routed"]
        for per in (mean_rows // 2, mean_rows, mean_rows * 2):
            for load in ("uniform", "skewed"):
                share = (np.ones(E) if load == "uniform"
                         else np.linspace(0.25, 1.75, E))
                counts = np.maximum((share * per).astype(np.int64), 1)
                real = int(counts.sum())
                for tm in tile_ms:
                    tiles = -(-counts // tm)
                    n_tiles = -(-2 * real // tm) + E
                    M = n_tiles * tm
                    te = np.minimum(np.searchsorted(
                        np.cumsum(tiles), np.arange(n_tiles), side="right"),
                        E - 1).astype(np.int32)
                    live = np.asarray([tiles.sum()], np.int32)
                    x = jnp.asarray(rng.randn(M, D) * 0.1, jnp.bfloat16)
                    g = jnp.asarray(rng.randn(M, F) * 0.1, jnp.bfloat16)
                    row = {"bench": "train_gmm_sweep", "label": label,
                           "cell": name, "rows_an_expert": int(per),
                           "load": load, "rows": real, "buffer_rows": M,
                           "tile_m": tm}
                    flops = 2.0 * real * D * F
                    interp = not on_tpu
                    timed(dict(row, op="forward", impl="row_tiles"),
                          jax.jit(lambda x, w, te, live, tm=tm: G._gmm_call(
                              x, w, te, live, tm, F, interpret=interp)),
                          (x, w, te, live), flops)
                    wt = jnp.swapaxes(w, 1, 2)
                    timed(dict(row, op="dX", impl="row_tiles"),
                          jax.jit(lambda g, wt, te, live, tm=tm: G._gmm_call(
                              g, wt, te, live, tm, D, interpret=interp)),
                          (g, wt, te, live), flops)
                    timed(dict(row, op="dX+T", impl="row_tiles"),
                          jax.jit(lambda g, w, te, live, tm=tm: G._gmm_call(
                              g, jnp.swapaxes(w, 1, 2), te, live, tm, D,
                              interpret=interp)),
                          (g, w, te, live), flops)
                    timed(dict(row, op="dW", impl="row_tiles"),
                          jax.jit(lambda x, g, te, live, tm=tm: G._tgmm_call(
                              x, g, te, live, E, tm, F, interpret=interp)),
                          (x, g, te, live), flops)
                for tm in ((16, 128, 256) if on_tpu else (8,)):
                    tiles = (-(-counts // tm)).astype(np.int32)
                    first = (np.cumsum(tiles) - tiles).astype(np.int32)
                    M = (int(tiles.sum()) + E) * tm
                    x = jnp.asarray(rng.randn(M, D) * 0.1, jnp.bfloat16)
                    blk = np.arange(E, dtype=np.int32)
                    timed({"bench": "train_gmm_sweep", "label": label,
                           "cell": name, "rows_an_expert": int(per),
                           "load": load, "rows": real, "buffer_rows": M,
                           "tile_m": tm, "op": "forward",
                           "impl": "expert_walk"},
                          jax.jit(lambda x, w, blk, first, tiles, tm=tm:
                                  G._held_gmm_call(
                                      x, (w[None],), jnp.zeros((1,), jnp.int32),
                                      blk, first, tiles, tile_m=tm, tile_n=F,
                                      interpret=not on_tpu)),
                          (x, w, blk, first, tiles), 2.0 * real * D * F)
        # splash at the cell's q / k head size, as it is and padded to
        # the next lane tile, by block size (the program's is 512)
        B, T, H = c["batch"], c["seq_len"], c["heads"]
        for dq in sorted({c["qk"], -(-c["qk"] // 128) * 128}):
            for block in ((512, 1024, 2048) if on_tpu else ()):
                q = jnp.asarray(rng.randn(B, T, H, dq) * 0.1, jnp.bfloat16)
                v = jnp.asarray(rng.randn(B, T, H, c["dv"]) * 0.1,
                                jnp.bfloat16)
                kernel = FA._splash_kernel(H, T, T, True, block)

                def loss(q, k, v, kernel=kernel):
                    t = lambda a: a.transpose(0, 2, 1, 3)
                    o = jax.vmap(kernel)(
                        t((q * c["qk"] ** -0.5).astype(q.dtype)), t(k), t(v))
                    return (o.astype(jnp.float32) ** 2).sum()

                sq = 2.0 * B * H * T * T / 2.0
                timed({"bench": "train_gmm_sweep", "label": label,
                       "cell": name, "op": "splash fwd+bwd", "head_qk": dq,
                       "head_v": c["dv"], "block": block},
                      jax.jit(jax.grad(loss, argnums=(0, 1, 2))), (q, q, v),
                      sq * (c["qk"] + c["dv"])
                      + sq * (3 * c["qk"] + 2 * c["dv"]))
    return _emit(results, out)


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
    if {"--block-sweep", "--ragged-sweep", "--ssd-sweep", "--packed-sweep",
            "--mla-sweep", "--held-sweep", "--train-gmm-sweep"} & set(
                sys.argv):
        opt = {a.split("=", 1)[0]: a.split("=", 1)[1] for a in sys.argv
               if a.startswith("--") and "=" in a}
        path = opt.get("--out")
        if "--block-sweep" in sys.argv:
            block_sweep(out=path)
        elif "--packed-sweep" in sys.argv:
            packed_sweep(out=path, label=opt.get("--label", ""),
                         cells=(opt["--cells"].split(",") if "--cells" in opt
                                else None))
        elif "--mla-sweep" in sys.argv:
            mla_sweep(out=path, label=opt.get("--label", ""),
                      cells=(opt["--cells"].split(",") if "--cells" in opt
                             else None),
                      blocks=tuple(None if b == "auto" else int(b) for b in
                                   opt.get("--blocks", "auto").split(",")))
        elif "--ssd-sweep" in sys.argv:
            ssd_sweep(out=path, label=opt.get("--label", ""),
                      cells=(opt["--cells"].split(",") if "--cells" in opt
                             else None))
        elif "--train-gmm-sweep" in sys.argv:
            train_gmm_sweep(
                out=path, label=opt.get("--label", ""),
                cells=(opt["--cells"].split(",") if "--cells" in opt
                       else None),
                tile_ms=tuple(int(t) for t in
                              opt.get("--tile-ms", "128,256,512").split(",")))
        elif "--held-sweep" in sys.argv:
            held_sweep(
                out=path, label=opt.get("--label", ""),
                cells=(opt["--cells"].split(",") if "--cells" in opt
                       else None),
                tile_ms=tuple(None if t == "auto" else int(t) for t in
                              opt.get("--tile-ms", "auto").split(",")),
                tile_ns=tuple(
                    None if t == "auto" else tuple(map(int, t.split("x")))
                    for t in opt.get("--tile-ns", "auto").split(",")))
        else:
            ragged_sweep(
                out=path, label=opt.get("--label", ""),
                cells=(opt["--cells"].split(",") if "--cells" in opt
                       else None),
                tiles=tuple(None if t == "auto" else int(t) for t in
                            opt.get("--tiles", "auto").split(",")))
    else:
        assert jax.default_backend() == "tpu", "run on the TPU chip"
        bench_moe()
        bench_rope()
        bench_rms()
