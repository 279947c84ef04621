"""Static-analysis subsystem (ISSUE r9).

Detection is PROVEN, not assumed (the vacuous-pass lesson, ADVICE r5's
`test_export_int_scalar_const_dtype`): every lint pass and the paged-KV
invariant checker must (a) run clean on healthy flagship state and (b)
catch a deliberately seeded bug of the exact class it exists for —
f32-weight drift, host callbacks in decode loops, oversized host
pulls, diverging pipeline collectives, unbounded chunk-program sets,
corrupted refcounts, double-attached pages, stale defrag mappings,
non-TRASH dead-slot rows.
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from paddle_tpu.analysis import (TRAIN_GEOMETRIES,
                                 CollectiveConsistencyPass,
                                 DonationAuditPass, DtypeDriftPass,
                                 GraphTarget, HbmPeakPass, HostSyncPass,
                                 KVInvariantError, RecompileHazardPass,
                                 ServingGeometry, Severity,
                                 ShardingLintPass, audit_defrag_plan,
                                 audit_serving_state,
                                 check_stage_consistency,
                                 collective_signature, engine_geometry,
                                 estimate_hbm_peak,
                                 flagship_train_objects,
                                 jit_donation_flags, pp_stage_targets,
                                 run_passes, scan_trip_counts,
                                 serving_targets, trace_graph,
                                 train_stage_targets, train_step_target,
                                 training_targets, xla_peak_bytes)
from paddle_tpu.inference.paged_kv import PagePool, apply_defrag
from paddle_tpu.models import (SERVING_FAMILIES, llama as L,
                               resolve_family)
from paddle_tpu.serving import PrefixCache, ServingEngine

sds = jax.ShapeDtypeStruct
CFG = L.LlamaConfig.tiny(dtype=jnp.float32, use_flash_attention=False,
                         remat=False)


@pytest.fixture(scope="module")
def params():
    return L.init_params(CFG, jax.random.PRNGKey(0))


def _errors(findings):
    return [f for f in findings if f.severity == Severity.ERROR]


# ---------------------------------------------------------------------------
# flagship graphs lint clean (the CLI's acceptance bar)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model", ["llama", "qwen2_moe"])
def test_flagship_serving_graphs_lint_clean(model):
    targets = serving_targets(model)
    report = run_passes(
        [DtypeDriftPass(), HostSyncPass(), RecompileHazardPass()],
        targets)
    assert report.ran, "passes must actually run"
    assert report.ok, "\n".join(str(f) for f in report.errors)
    # the recompile pass PROVED a bound (info finding present), it did
    # not just fail to run
    assert any(f.pass_name == "recompile-hazard"
               and "proven bound" in f.message
               for f in report.findings)


@pytest.mark.parametrize("program", ["serving_tick[mixed]",
                                     "serving_tick_block[k=4]"])
@pytest.mark.parametrize("model", sorted(SERVING_FAMILIES))
def test_serving_targets_trace_a_family_through_its_record(
        model, program, monkeypatch):
    """Analysis reaches a family as the engine does: the cache pytree of
    its record's ``init_pages`` through the shared tick around its
    record's ``walk``, abstractly (zero compiles), with the whole cache
    donated back."""
    from paddle_tpu.observability import RecompileSentinel
    mod = resolve_family(model)
    calls = dict(walk=0, init_pages=0)
    caches = []

    def spy(name):
        fn = getattr(mod.SERVING, name)

        def wrapped(*a, **kw):
            calls[name] += 1
            out = fn(*a, **kw)
            if name == "init_pages":
                caches.append(out)
            return out
        return wrapped

    monkeypatch.setattr(mod, "SERVING", mod.SERVING._replace(
        **{name: spy(name) for name in calls}))
    sentinel = RecompileSentinel()
    try:
        targets = {t.name: t for t in serving_targets(model)}
    finally:
        sentinel.close()
    assert sentinel.warmup_compiles == 0
    assert all(calls.values()), calls
    target = targets[f"{model}.{program}"]
    # the results end with the slots' next current tokens ([S] i32,
    # kept on the device like the cache) and the family's whole cache,
    # donated: neither crosses to the host
    leaves = jax.tree_util.tree_leaves(caches[0])
    outs = target.jaxpr.jaxpr.outvars
    assert target.donated_outputs == tuple(
        range(len(outs) - len(leaves) - 1, len(outs)))
    assert [(o.aval.shape, o.aval.dtype) for o in outs[-len(leaves):]] \
        == [(x.shape, x.dtype) for x in leaves]
    cur = outs[-len(leaves) - 1].aval
    assert (cur.shape, cur.dtype) == ((target.slots,), jnp.int32)
    # a verify target only where the family can verify: what its layer
    # kinds keep tells, not its name
    cfg_cls = getattr(mod, SERVING_FAMILIES[model])
    pages_only = all(k.cache == "pages"
                     for k in mod.SERVING.kinds(cfg_cls.tiny()))
    assert any("[verify" in n for n in targets) == pages_only


@pytest.mark.parametrize("model", sorted(SERVING_FAMILIES))
def test_a_serving_family_is_one_record(model):
    """Everything the engine and the shared tick ask of a family stands
    in its module's ``SERVING``: every field of its type, ``init_pages``
    of the ONE signature, the kinds and pools it declares true of the
    cache it builds, and as many counts out of a tick as it names
    counters (abstractly: zero compiles)."""
    import inspect

    from paddle_tpu.analysis.serving_graphs import _tick_meta
    from paddle_tpu.models.layer_walk import (LayerKind, PagePoolSpec,
                                              ServingFamily)
    from paddle_tpu.models.serving_tick import (serving_tick,
                                                serving_tick_block)
    from paddle_tpu.observability import RecompileSentinel
    mod = resolve_family(model)
    family = mod.SERVING
    assert isinstance(family, ServingFamily)
    for name in ("walk", "init_pages", "kinds", "page_pools",
                 "window_pools"):
        assert callable(getattr(family, name)), name
    for name in ("page_copies", "params"):
        assert getattr(family, name) is None \
            or callable(getattr(family, name)), name
    assert isinstance(family.tick_pool, str)
    assert isinstance(family.counters, tuple) \
        and all(isinstance(c, str) for c in family.counters)
    assert list(inspect.signature(family.init_pages).parameters) == [
        "cfg", "total_pages", "page_size", "max_batch", "max_span"]
    cfg = getattr(mod, SERVING_FAMILIES[model]).tiny()
    S, ps, pps, T = 2, 4, 3, 6
    sentinel = RecompileSentinel()
    try:
        cache = jax.eval_shape(
            lambda: family.init_pages(cfg, 1 + S * pps, ps, S, T))
        kinds, pools = family.kinds(cfg), family.page_pools(cfg)
        rings = family.window_pools(cfg)
        assert all(isinstance(k, LayerKind) for k in kinds)
        assert all(isinstance(p, PagePoolSpec) for p in pools)
        # the allocator's pools hold its pages on the axis they say; a
        # ring is a leaf no allocator counts; the tick's pool has a
        # page's tokens second to last
        for p in pools:
            assert cache[p.name].shape[p.page_axis] == 1 + S * pps
        assert set(rings) <= set(cache) - {p.name for p in pools}
        assert bool(rings) == any(k.cache == "window_pages" for k in kinds)
        assert cache[family.tick_pool].shape[-2] == ps
        # a kind that keeps rows a slot has a leaf that is no pool
        assert (set(cache) > {p.name for p in pools} | set(rings)) == any(
            k.cache == "slot_rows" for k in kinds)
        params = mod.abstract_params(cfg)
        if family.params is not None:
            params = jax.eval_shape(lambda p: family.params(p, cfg), params)
        sds, i32 = jax.ShapeDtypeStruct, jnp.int32
        meta = _tick_meta(T, S, pps)
        tick = jax.eval_shape(
            lambda p, t, m, c: serving_tick(p, t, m, c, cfg, family, tq=T,
                                            decode_tail=1,
                                            attn_impl="dense"),
            params, sds((T,), i32), meta, cache)
        block = jax.eval_shape(
            lambda p, t, n, tab, c: serving_tick_block(
                p, t, n, tab, c, cfg, family, 2, attn_impl="dense"),
            params, sds((S,), i32), sds((S,), i32), sds((S, pps), i32),
            cache)
    finally:
        sentinel.close()
    assert sentinel.warmup_compiles == 0
    # (toks, logits, [counts,] cur_tok', cache') / (toks, [counts,]
    # tok', cache'): the cache comes back as it went in
    n = len(family.counters)
    assert len(tick) == 4 + bool(n) and len(block) == 3 + bool(n)
    for out in (tick, block):
        assert jax.tree.map(lambda a: (a.shape, a.dtype), out[-1]) == \
            jax.tree.map(lambda a: (a.shape, a.dtype), cache)
        if n:
            assert (out[-3].shape, out[-3].dtype) == ((n,), jnp.int32)


def test_a_module_without_the_record_is_no_serving_family():
    """The engine reads ``SERVING`` and nothing else of a module: one
    that lacks it is refused by name, not served with defaults."""
    import types
    mod = types.SimpleNamespace(
        __name__="no_record", init_serving_pages=L.init_serving_pages,
        serving_params=L.serving_params)
    cfg = L.LlamaConfig.tiny()
    with pytest.raises(TypeError, match="exposes no SERVING"):
        ServingEngine(L.abstract_params(cfg), cfg, model=mod, max_batch=2,
                      page_size=4, max_prompt_len=8, max_new_tokens_cap=4)


def test_pp_stage_chunks_consistent():
    targets = pp_stage_targets()
    report = run_passes([CollectiveConsistencyPass()], targets)
    assert len(report.ran) == len(targets)
    assert report.ok


# ---------------------------------------------------------------------------
# dtype-drift: seeded mutations
# ---------------------------------------------------------------------------

def test_dtype_drift_catches_f32_weight_in_bf16_model():
    def bad(x, w):
        return (x @ w).astype(jnp.bfloat16)

    t = trace_graph("bad", bad,
                    (sds((4, 8), jnp.bfloat16), sds((8, 8), jnp.float32)),
                    compute_dtype=jnp.bfloat16)
    errs = _errors(DtypeDriftPass().run(t))
    assert errs and "dot_general" in errs[0].message

    def good(x, w):
        return x @ w

    t2 = trace_graph("good", good,
                     (sds((4, 8), jnp.bfloat16),
                      sds((8, 8), jnp.bfloat16)),
                     compute_dtype=jnp.bfloat16)
    assert not DtypeDriftPass().run(t2)


def test_dtype_drift_f32_accumulator_is_not_a_widened_gemm():
    """bf16 operands with an f32 accumulator is how the MXU multiplies
    — and the only accumulator Mosaic accepts (the ragged kernel's
    ``_mxu_dot``); the drift is an f32 OPERAND of bf16 origin, also
    when it arrives through an explicit upcast."""
    def mxu(x, w):
        return lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x.dtype)

    def upcast(x, w):
        return lax.dot_general(x.astype(jnp.float32), w.astype(jnp.float32),
                               (((1,), (0,)), ((), ()))).astype(x.dtype)

    args = (sds((4, 8), jnp.bfloat16), sds((8, 8), jnp.bfloat16))
    ok = trace_graph("mxu", mxu, args, compute_dtype=jnp.bfloat16)
    assert not DtypeDriftPass().run(ok)
    bad = trace_graph("upcast", upcast, args, compute_dtype=jnp.bfloat16)
    errs = _errors(DtypeDriftPass().run(bad))
    assert errs and "dot_general" in errs[0].message


def test_dtype_drift_catches_f32_const_pollution():
    table = jnp.asarray(np.linspace(0, 1, 16, dtype=np.float32))

    def bad(x):
        return x * table      # f32 closure const forces the upcast

    t = trace_graph("bad", bad, (sds((4, 16), jnp.bfloat16),),
                    compute_dtype=jnp.bfloat16)
    errs = _errors(DtypeDriftPass().run(t))
    assert errs and "constant" in errs[0].message
    # the bf16-cast version of the same constant is clean
    table16 = table.astype(jnp.bfloat16)

    def good(x):
        return x * table16

    t2 = trace_graph("good", good, (sds((4, 16), jnp.bfloat16),),
                     compute_dtype=jnp.bfloat16)
    assert not DtypeDriftPass().run(t2)


def test_dtype_drift_scalar_eps_exempt_and_f64_flagged():
    def norm(x):
        # the idiomatic f32 island: explicit upcast, reduce, downcast
        xf = x.astype(jnp.float32)
        return (xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                               + 1e-5)).astype(x.dtype)

    t = trace_graph("norm", norm, (sds((4, 8), jnp.bfloat16),),
                    compute_dtype=jnp.bfloat16)
    assert not DtypeDriftPass().run(t)

    from jax import enable_x64
    with enable_x64():
        def f64fn(x):
            return x.astype(jnp.float64) * 2.0

        t2 = trace_graph("f64", f64fn, (sds((4,), jnp.float32),),
                         compute_dtype=jnp.bfloat16)
    errs = _errors(DtypeDriftPass().run(t2))
    assert errs and "float64" in errs[0].message


# ---------------------------------------------------------------------------
# host-sync: seeded mutations
# ---------------------------------------------------------------------------

def test_host_sync_catches_callback_in_decode_loop():
    def bad(x):
        def body(c, _):
            jax.debug.callback(lambda v: None, c)
            return c + 1, c

        return lax.scan(body, x, None, length=3)

    t = trace_graph("bad", bad, (sds((4,), jnp.float32),),
                    in_decode_loop=True)
    errs = _errors(HostSyncPass().run(t))
    assert errs and "callback" in errs[0].message
    assert errs[0].path and errs[0].path[0][0] == "scan"


def test_host_sync_catches_oversized_logits_pull():
    V = 256

    def bad_tick(x, w):
        return x @ w           # [S, V] f32 logits cross to the host

    t = trace_graph("bad", bad_tick,
                    (sds((4, 64), jnp.float32), sds((64, V), jnp.float32)),
                    slots=4, steps_per_call=1, in_decode_loop=True)
    errs = _errors(HostSyncPass().run(t))
    assert errs and "bytes/slot/step" in errs[0].message

    def good_tick(x, w):
        return jnp.argmax(x @ w, -1).astype(jnp.int32)  # [S] tokens

    t2 = trace_graph("good", good_tick,
                     (sds((4, 64), jnp.float32),
                      sds((64, V), jnp.float32)),
                     slots=4, steps_per_call=1, in_decode_loop=True)
    assert not HostSyncPass().run(t2)


def test_host_sync_prefill_exempt_from_pull_budget():
    """Prefill programs legitimately return logits once per prompt."""
    def prefill(x, w):
        return x @ w

    t = trace_graph("prefill", prefill,
                    (sds((1, 64), jnp.float32),
                     sds((64, 256), jnp.float32)),
                    slots=1, in_decode_loop=False)
    assert not HostSyncPass().run(t)


# ---------------------------------------------------------------------------
# collective-consistency: seeded mutations
# ---------------------------------------------------------------------------

def _two_device_mesh():
    devs = np.array(jax.devices()[:2])
    return Mesh(devs, ("x",))


def test_collective_divergence_caught():
    mesh = _two_device_mesh()

    def stage_a(x):
        return shard_map(lambda v: lax.psum(v, "x"), mesh=mesh,
                         in_specs=P("x"), out_specs=P())(x)

    def stage_b(x):
        return shard_map(
            lambda v: lax.ppermute(v, "x", [(0, 1), (1, 0)]),
            mesh=mesh, in_specs=P("x"), out_specs=P("x"))(x)

    x = jnp.ones((2, 4))
    ja = jax.make_jaxpr(stage_a)(x)
    jb = jax.make_jaxpr(stage_b)(x)
    assert collective_signature(ja) != collective_signature(jb)
    bad = check_stage_consistency([("s0", ja), ("s1", jb)])
    assert bad and bad[0][0] == "s1"
    assert not check_stage_consistency([("s0", ja), ("s1", ja)])


def test_collective_signature_counts_scan_trips():
    """Stages whose ring loops run different trip counts are NOT
    consistent even though the loop bodies match."""
    mesh = _two_device_mesh()

    def ring(x, hops):
        def inner(v):
            def body(c, _):
                return lax.ppermute(c, "x", [(0, 1), (1, 0)]), None

            out, _ = lax.scan(body, v, None, length=hops)
            return out

        return shard_map(inner, mesh=mesh, in_specs=P("x"),
                         out_specs=P("x"))(x)

    x = jnp.ones((2, 4))
    j3 = jax.make_jaxpr(lambda v: ring(v, 3))(x)
    j5 = jax.make_jaxpr(lambda v: ring(v, 5))(x)
    assert check_stage_consistency([("s0", j3), ("s1", j5)])


# ---------------------------------------------------------------------------
# recompile-hazard: proof + seeded hazard
# ---------------------------------------------------------------------------

def test_recompile_enumeration_matches_live_engine_geometry(params):
    """engine_geometry() (the static mirror) must agree with a real
    engine's extracted geometry — the proof is about the engine that
    actually runs, not a lookalike."""
    kw = dict(page_size=4, max_prompt_len=16, max_new_tokens_cap=16,
              prefill_chunk=8)
    with ServingEngine(params, CFG, max_batch=2, **kw) as eng:
        live = ServingGeometry.of_engine(eng)
    assert engine_geometry(max_batch=2, **kw) == live


def test_recompile_pass_proves_flagship_bound_and_flags_hazard():
    """The engine's program set is 1-2 per packed-width bucket BY
    CONSTRUCTION; a bound the dispatch does not keep is an ERROR that
    spells the offending program set out."""
    from paddle_tpu.analysis import enumerate_tick_programs
    good = engine_geometry(page_size=4, max_prompt_len=16,
                           max_new_tokens_cap=16, prefill_chunk=8,
                           max_batch=4, decode_block=4)
    progs = enumerate_tick_programs(good)
    assert progs and all(len(v) <= 2 for v in progs.values())
    # both reachable widths are enumerated: S and S+budget
    assert set(progs) == {4, 12}
    t_good = trace_graph("geom", lambda x: x, (sds((1,), jnp.float32),),
                         meta={"geometry": good})
    found = RecompileHazardPass().run(t_good)
    assert not _errors(found)
    assert any("proven bound" in f.message for f in found)

    # seeded hazard: held to ONE program a bucket, the mixed width's
    # tail / no-tail pair is over the bound; the error names the set
    errs = _errors(RecompileHazardPass(ragged_limit=1).run(t_good))
    assert len(errs) == 1 and "tick width 12" in errs[0].message
    assert str(sorted(progs[12])) in errs[0].message


def test_engine_geometry_hazard_died_with_quantization(params):
    """A tiny chunk against a big prompt budget (once a compile storm:
    one program per static prefix size) compiles the SAME two programs
    as any other geometry: the ctor enumeration stays silent because
    prefix size and chunk position are data, not because the check was
    dropped."""
    import warnings
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        eng = ServingEngine(params, CFG, max_batch=1, page_size=4,
                            max_prompt_len=128, max_new_tokens_cap=4,
                            prefill_chunk=4, check_invariants=False)
        geom = ServingGeometry.of_engine(eng)
        eng.close()
    assert not [x for x in w if "tick programs" in str(x.message)]
    from paddle_tpu.analysis import enumerate_tick_programs
    progs = enumerate_tick_programs(geom)
    assert all(len(v) <= 2 for v in progs.values())


def test_graph_lint_json_reports_serving_program_set(capsys):
    """graph_lint --json (and therefore --ci --json) carries the
    serving-suite program-set proof: per-width inventory plus the
    programs-per-bucket bound CI consumers gate on."""
    import importlib.util
    import json as _json
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "graph_lint.py")
    spec = importlib.util.spec_from_file_location("graph_lint", path)
    gl = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gl)
    rc = gl.main(["--suite", "serving", "--json"])
    out = _json.loads(capsys.readouterr().out)
    assert rc == 0
    sp = out["serving_programs"]
    assert sp["programs_per_bucket"] <= 2
    assert sp["total"] >= 2
    assert all(len(progs) <= 2 for progs in sp["widths"].values())
    # r13: the observability block carries the SAME inventory dict the
    # runtime recompile sentinel reports as expected_programs — static
    # and runtime views share one schema
    sent = out["observability"]["sentinel"]
    assert sent["expected_programs"] == sp
    assert sent["metric"] == "paddle_serving_recompiles_total"
    # r15: the SPECULATIVE engine's inventory rides the same schema —
    # the static proof that the draft/verify tick programs keep the
    # per-bucket bound (exactly one verify program per mixed width)
    sps = out["serving_programs_spec"]
    assert sps["programs_per_bucket"] <= 2
    verify = [p for progs in sps["widths"].values() for p in progs
              if p.startswith("serving_tick[verify")]
    assert verify and all(len(progs) <= 2
                          for progs in sps["widths"].values())


def test_prefix_attach_is_exact(params):
    """The engine attaches EVERY cached full page (cap floor((n-1)/ps)
    only), whatever the chunk size."""
    with ServingEngine(params, CFG, max_batch=2, page_size=4,
                       max_prompt_len=16, max_new_tokens_cap=16,
                       prefill_chunk=8) as eng:
        prompt = np.arange(1, 16, dtype=np.int32)      # 15 tokens
        eng.submit(prompt, 4).result(timeout=300)
        eng.submit(prompt, 4).result(timeout=300)
        c = eng.stats()["counters"]
    # floor(14/4) = 3 pages = 12 tokens attach, past the chunk (2 pages)
    assert c["prefix_pages_saved"] == 3
    assert c["prefix_hit_tokens"] == 12


# ---------------------------------------------------------------------------
# paged-KV invariant checker: healthy engine clean, mutations caught
# ---------------------------------------------------------------------------

def _eng(params, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_prompt_len", 16)
    kw.setdefault("max_new_tokens_cap", 16)
    kw.setdefault("check_invariants", True)
    return ServingEngine(params, CFG, **kw)


def _ref(params, prompt, n):
    out = L.generate(params, jnp.asarray(prompt)[None], CFG,
                     max_new_tokens=n)
    return np.asarray(out)[0, len(prompt):]


def test_checker_clean_through_mixed_workload(params):
    rng = np.random.RandomState(0)
    with _eng(params, prefill_chunk=4) as eng:
        hs = [eng.submit(rng.randint(0, 256, (n,)).astype(np.int32), 5)
              for n in (12, 3, 15, 12, 7)]
        for h in hs:
            h.result(timeout=300)
        assert eng.audit() == []
    assert eng.pool.used_pages == 0


def test_checker_catches_refcount_corruption(params):
    prompt = np.arange(1, 13, dtype=np.int32)
    with _eng(params) as eng:
        eng.submit(prompt, 4).result(timeout=300)
        nodes = eng.prefix_cache.nodes()
        assert nodes
        nodes[0].refs += 1          # seeded bug: leaked reference
        bad = eng.audit()
        assert any(v.code == "refcount-drift" for v in bad)
        nodes[0].refs -= 1
        assert eng.audit() == []


def test_checker_catches_double_attached_page(params):
    """The page-aliasing bug class: one physical page in two live
    slots' rows without a backing trie refcount."""
    rng = np.random.RandomState(1)
    p1 = rng.randint(0, 256, (6,)).astype(np.int32)
    p2 = rng.randint(0, 256, (6,)).astype(np.int32)
    eng = _eng(params, check_invariants=False, tick_interval_s=0.01)
    try:
        h1 = eng.submit(p1, 12)
        h2 = eng.submit(p2, 12)
        it = iter(h1)
        next(it)                    # both slots live
        with eng._tick_lock:
            occ = eng.scheduler.occupied()
            if len(occ) == 2:
                (s1, r1), (s2, r2) = occ
                # double-attach: slot 2's first page aliased into
                # slot 1's row (classic mis-maintained page table)
                eng.scheduler.tables[s1, -1] = r2.pages[0]
                bad = audit_serving_state(eng.pool, eng.scheduler,
                                          eng.prefix_cache)
                assert any(v.code in ("share-uncached", "row-mismatch")
                           for v in bad)
                eng.scheduler.tables[s1, -1] = PagePool.TRASH
    finally:
        eng.close(drain=False)


def test_checker_catches_freelist_aliasing(params):
    prompt = np.arange(1, 9, dtype=np.int32)
    eng = _eng(params, check_invariants=False, tick_interval_s=0.01)
    try:
        h = eng.submit(prompt, 12)
        it = iter(h)
        next(it)
        with eng._tick_lock:
            occ = eng.scheduler.occupied()
            if occ:
                _, req = occ[0]
                page = req.pages[0]
                # seeded bug: a live page pushed back to the free list
                eng.pool._free.append(page)
                eng.pool._free_set.add(page)
                bad = audit_serving_state(eng.pool, eng.scheduler,
                                          eng.prefix_cache)
                assert any(v.code == "page-free-owned" for v in bad)
                eng.pool._free.remove(page)
                eng.pool._free_set.discard(page)
    finally:
        eng.close(drain=False)


def test_checker_catches_parked_row_leak(params):
    """A parked (mid chunked-prefill) slot whose scheduler row is not
    all-TRASH: the dead-slot contract the TPU pallas page loop depends
    on."""
    rng = np.random.RandomState(2)
    long_p = rng.randint(0, 256, (16,)).astype(np.int32)
    short_p = rng.randint(0, 256, (2,)).astype(np.int32)
    eng = _eng(params, prefill_chunk=4, max_batch=2,
               check_invariants=False, tick_interval_s=0.02)
    try:
        h_short = eng.submit(short_p, 24)
        it = iter(h_short)
        next(it)
        h_long = eng.submit(long_p, 4)
        seen = False
        for _ in range(400):
            time.sleep(0.002)
            with eng._tick_lock:
                parked = [(s, r) for s, r in eng.scheduler.occupied()
                          if r.table_row is not None]
                if parked:
                    seen = True
                    slot, req = parked[0]
                    # healthy parked state passes
                    assert audit_serving_state(
                        eng.pool, eng.scheduler,
                        eng.prefix_cache) == []
                    # seeded bug: one real entry leaks into the row
                    eng.scheduler.tables[slot, 0] = req.table_row[0]
                    bad = audit_serving_state(eng.pool, eng.scheduler,
                                              eng.prefix_cache)
                    assert any(v.code == "parked-row-live"
                               for v in bad)
                    eng.scheduler.tables[slot, 0] = PagePool.TRASH
                    break
            if h_long._req.done.is_set():
                break
        assert seen, "no parked slot observed — chunk too large?"
        h_long.result(timeout=300)
        h_short.result(timeout=300)
    finally:
        eng.close()


def test_defrag_plan_audit_catches_stale_mapping(params):
    prompt = np.arange(1, 13, dtype=np.int32)
    with _eng(params) as eng:
        eng.submit(prompt, 4).result(timeout=300)
        with eng._tick_lock:
            plan = eng.pool.defrag_plan()
            assert audit_defrag_plan(plan, eng.pool, eng.scheduler,
                                     eng.prefix_cache) == []
            # stale mapping: pretend a freed page is still being moved
            free_page = max(eng.pool.free_page_ids)
            stale = dict(plan)
            stale[free_page] = 1
            bad = audit_defrag_plan(stale, eng.pool, eng.scheduler,
                                    eng.prefix_cache)
            assert any(v.code == "defrag-stale-src" for v in bad)


def test_per_tick_checker_fails_engine_on_live_corruption(params):
    """Detection through the LIVE path: corrupt state under the tick
    lock and the next tick's audit kills the engine, surfacing
    KVInvariantError to every caller."""
    rng = np.random.RandomState(3)
    eng = _eng(params, tick_interval_s=0.01)
    try:
        eng.submit(rng.randint(0, 256, (9,)).astype(np.int32), 4) \
           .result(timeout=300)
        h = eng.submit(rng.randint(0, 256, (9,)).astype(np.int32), 24)
        it = iter(h)
        next(it)
        with eng._tick_lock:
            nodes = eng.prefix_cache.nodes()
            assert nodes
            nodes[0].refs += 3      # corruption the next tick must see
        with pytest.raises(KVInvariantError) as exc:
            h.result(timeout=300)
        # the raise names the engine geometry that produced it, so a
        # report from a dead engine is actionable without a repro
        assert "engine geometry:" in str(exc.value)
        assert "page_size=" in str(exc.value)
    finally:
        eng.close(drain=False)


# ---------------------------------------------------------------------------
# defrag while a chunk-prefill slot is parked (satellite)
# ---------------------------------------------------------------------------

def test_defrag_while_chunk_prefill_parked(params):
    """Defrag running while a slot is parked mid chunked-prefill must
    remap the dead-slot scheduler row (all-TRASH, trivially), the
    STASHED real row, and the prefix-cached pages consistently — the
    parked request then completes byte-exact and the checker stays
    green throughout."""
    rng = np.random.RandomState(4)
    churn = rng.randint(0, 256, (10,)).astype(np.int32)
    long_p = rng.randint(0, 256, (16,)).astype(np.int32)
    short_p = rng.randint(0, 256, (2,)).astype(np.int32)
    eng = _eng(params, prefill_chunk=4, max_batch=3,
               tick_interval_s=0.02)
    try:
        # all three admit together (3 free slots): churn takes the LOW
        # pages and retires after 2 tokens — while the long prompt is
        # still parked mid chunked-prefill — leaving a low hole that
        # gives defrag real work across: a live decode row (short), a
        # parked slot's STASHED row (long), and churn's now-cached
        # prefix pages in the trie
        h_churn = eng.submit(churn, 2)
        h_short = eng.submit(short_p, 30)
        h_long = eng.submit(long_p, 6)
        moved = None
        for _ in range(800):
            time.sleep(0.002)
            with eng._tick_lock:
                parked = [r for _, r in eng.scheduler.occupied()
                          if r.table_row is not None]
                fragmented = (h_churn._req.done.is_set()
                              and bool(eng.pool.defrag_plan()))
            if parked and fragmented:
                moved = eng.defragment()   # audits plan + result
                break
            if h_long._req.done.is_set():
                break
        assert moved is not None, \
            "never saw a parked slot + fragmentation window"
        assert moved > 0
        out_long = h_long.result(timeout=300)
        out_short = h_short.result(timeout=300)
        assert eng.audit() == []
    finally:
        eng.close()
    np.testing.assert_array_equal(out_long, _ref(params, long_p, 6))
    np.testing.assert_array_equal(out_short, _ref(params, short_p, 30))


# ---------------------------------------------------------------------------
# training-graph lint (ISSUE 5 tentpole): clean flagships + seeded defects
# ---------------------------------------------------------------------------

def _train_passes():
    return [ShardingLintPass(), DonationAuditPass(), HbmPeakPass(),
            CollectiveConsistencyPass()]


@pytest.fixture(scope="module")
def train_targets():
    """One traced target per geometry, shared across the mutation
    tests — tracing is the expensive part; each test gets a fresh META
    copy via _fresh() so seeded mutations cannot leak between tests."""
    return {g: train_step_target(g) for g in TRAIN_GEOMETRIES}


def _fresh(t):
    meta = {k: (list(v) if isinstance(v, list) else v)
            for k, v in t.meta.items()}
    return GraphTarget(name=t.name, jaxpr=t.jaxpr,
                       compute_dtype=t.compute_dtype, meta=meta)


def test_training_targets_cover_required_geometries_and_lint_clean():
    assert {"dp", "dp_mp", "pp_1f1b", "zero1"} <= set(TRAIN_GEOMETRIES)
    targets = training_targets()
    report = run_passes(_train_passes(), targets)
    assert len(report.ran) == 4 * len(targets)
    assert report.ok, "\n".join(str(f) for f in report.errors)
    # non-vacuous: the estimator actually reported, the donation audit
    # actually inventoried, on every train-step target
    steps = [t.name for t in targets if "train_step" in t.name]
    assert len(steps) == len(TRAIN_GEOMETRIES)
    for name in steps:
        assert any(f.pass_name == "hbm-peak" and f.graph == name
                   for f in report.findings)
        assert any(f.pass_name == "donation-audit" and f.graph == name
                   for f in report.findings)


def test_sharding_lint_catches_replicated_large_weight(train_targets):
    t = _fresh(train_targets["dp_mp"])
    i = t.meta["invar_labels"].index("[0]['params']['embed']")
    t.meta["in_specs"][i] = P()           # seeded: spec quietly lost
    errs = _errors(ShardingLintPass(replicated_bytes=16 * 1024).run(t))
    assert errs and "replicated" in errs[0].message
    # clean at the same threshold with the real spec
    assert not _errors(ShardingLintPass(replicated_bytes=16 * 1024)
                       .run(_fresh(train_targets["dp_mp"])))


def test_sharding_lint_catches_unknown_mesh_axis(train_targets):
    """The Engine-vs-llama axis-name class: 'mp' on a 'tp' mesh shards
    nothing while reading as if it did."""
    t = _fresh(train_targets["dp_mp"])
    i = t.meta["invar_labels"].index("[0]['params']['lm_head']")
    t.meta["in_specs"][i] = P(None, "mp")
    errs = _errors(ShardingLintPass().run(t))
    assert errs and "mp" in errs[0].message


def test_sharding_lint_catches_uncovered_opt_state(train_targets):
    t = _fresh(train_targets["zero1"])
    i = next(i for i, (c, sp) in enumerate(
        zip(t.meta["invar_classes"], t.meta["in_specs"]))
        if c == "opt" and "dp" in str(sp))
    t.meta["in_specs"][i] = P()           # seeded: ZeRO dim dropped
    errs = _errors(ShardingLintPass().run(t))
    assert errs and "zero_spec" in errs[0].message
    assert not _errors(ShardingLintPass().run(_fresh(train_targets["zero1"])))


def test_donation_audit_catches_undonated_opt_state(train_targets):
    t = _fresh(train_targets["dp"])
    i = next(i for i, (c, v) in enumerate(
        zip(t.meta["invar_classes"], t.jaxpr.jaxpr.invars))
        if c == "opt" and np.prod(v.aval.shape or (1,)) > 64)
    t.meta["donated_invars"][i] = False   # seeded: donation dropped
    errs = _errors(DonationAuditPass().run(t))
    assert errs and "NON-donated" in errs[0].message


def test_donation_audit_warns_on_unaliasable_donation():
    def f(a):
        return a.astype(jnp.bfloat16)     # no f32 output to alias onto

    t = trace_graph("bad", f, (sds((64, 64), jnp.float32),),
                    meta={"donated_invars": [True],
                          "invar_labels": ["a"],
                          "invar_classes": ["param"]})
    warns = [x for x in DonationAuditPass().run(t)
             if x.severity == Severity.WARNING]
    assert warns and "alias" in warns[0].message


def test_train_donation_flags_match_live_lowering():
    """The declared donation meta must equal what jax actually stamps
    into the step's lowering (tf.aliasing_output) — the
    engine_geometry-vs-live-engine lesson applied to donation."""
    target, step_fn, state, batch = flagship_train_objects()
    flags = jit_donation_flags(step_fn, state, batch)
    assert list(flags) == list(target.meta["donated_invars"])
    n_state = len(jax.tree_util.tree_leaves(state))
    assert sum(flags) == n_state          # whole state donated, batch not


def test_donation_flags_survive_unused_arg_pruning():
    """jit's default keep_unused=False drops unused flat args from the
    lowered @main; the parsed flags must still align with the CALLER's
    flat signature (a step with one dead state leaf used to shift every
    flag after it)."""
    def f(a, b, c):                       # b is dead
        return a * 2.0 + c

    j = jax.jit(f, donate_argnums=(0, 2))
    x = jax.ShapeDtypeStruct((4,), jnp.float32)
    import warnings
    with warnings.catch_warnings():
        # one output can alias only one donor; jax warns about the other
        warnings.simplefilter("ignore")
        flags = jit_donation_flags(j, x, x, x)
    assert len(flags) == 3                # full signature, not kept args
    assert flags[1] is False              # the dead arg is not donated
    assert flags[0] or flags[2]           # a real donor kept its flag
    # misaligned meta must be a loud lint error, not an IndexError
    closed = jax.make_jaxpr(f)(x, x, x)
    t = GraphTarget(name="pruned", jaxpr=closed,
                    meta={"donated_invars": [True]})
    errs = _errors(DonationAuditPass().run(t))
    assert errs and "misaligned" in errs[0].message


def test_collective_pass_catches_dropped_psum_in_dp_variant():
    mesh = _two_device_mesh()

    def with_psum(x):
        return shard_map(lambda v: lax.psum(v * 2, "x"), mesh=mesh,
                         in_specs=P("x"), out_specs=P())(x)

    def without_psum(x):                  # seeded: grad psum dropped
        return shard_map(lambda v: v * 2, mesh=mesh,
                         in_specs=P("x"), out_specs=P("x"))(x)

    x = jnp.ones((2, 4))
    group = {"stage_group": "llama.dp_grads", "stage_count": 2}
    ta = GraphTarget(name="dp0", jaxpr=jax.make_jaxpr(with_psum)(x),
                     meta=dict(group))
    tb = GraphTarget(name="dp1", jaxpr=jax.make_jaxpr(without_psum)(x),
                     meta=dict(group))
    report = run_passes([CollectiveConsistencyPass()], [ta, tb])
    assert not report.ok
    assert "psum" in str(report.errors[0])


def test_train_stage_chunks_consistent_and_trip_mismatch_caught():
    targets = train_stage_targets()
    report = run_passes([CollectiveConsistencyPass()], targets)
    assert len(report.ran) == len(targets) and report.ok
    # seeded: one chunk scans a different layer count (bad partition)
    cfg1 = L.LlamaConfig.tiny(use_flash_attention=False, remat=False)

    def chunk(n_layers):
        p = jax.eval_shape(lambda: jax.tree_util.tree_map(
            lambda a: jnp.zeros((n_layers,) + a.shape[1:], a.dtype),
            L.abstract_params(cfg1)["layers"]))
        x = sds((2, 8, cfg1.hidden_size), cfg1.dtype)
        return jax.make_jaxpr(
            lambda pp, h: L._scan_layers(pp, h, cfg1, None,
                                         remat=False))(p, x)

    group = {"stage_group": "bad.pp", "stage_count": 2,
             "signature_include_loops": True}
    ta = GraphTarget(name="c0", jaxpr=chunk(1), meta=dict(group))
    tb = GraphTarget(name="c1", jaxpr=chunk(2), meta=dict(group))
    report2 = run_passes([CollectiveConsistencyPass()], [ta, tb])
    assert not report2.ok


def test_1f1b_schedule_trip_count_checked_and_mutation_caught(train_targets):
    from paddle_tpu.parallel.pipeline_1f1b import schedule_ticks
    assert schedule_ticks(2, 4, 2) == 11
    t = _fresh(train_targets["pp_1f1b"])
    assert t.meta["expected_scan_trips"] == 11
    assert 11 in scan_trip_counts(t.jaxpr)   # the check is non-vacuous
    assert not _errors(CollectiveConsistencyPass().run(t))
    t.meta["expected_scan_trips"] = 13       # seeded: schedule desync
    errs = _errors(CollectiveConsistencyPass().run(t))
    assert errs and "trip count" in errs[0].message


@pytest.mark.parametrize("geom,model", [("pp2_zb", "zb"),
                                        ("pp4_async", "1f1b"),
                                        ("pp2_dp2_zb", "zb"),
                                        ("pp2_tp2_async", "1f1b")])
def test_async_schedule_trip_count_checked_and_mutation_caught(
        train_targets, geom, model):
    """The rank-asymmetric schedules are traced targets too: the
    schedule scan lives INSIDE the shard_map body and the trip-count
    rule still sees it (type-based jaxpr walk); a tick-arithmetic
    desync is caught exactly like the lockstep one."""
    from paddle_tpu.parallel.pipeline_1f1b import schedule_ticks
    g = TRAIN_GEOMETRIES[geom]
    T = schedule_ticks(g["pp"], g["microbatches"], g["vpp"],
                       schedule=model)
    t = _fresh(train_targets[geom])
    assert t.meta["expected_scan_trips"] == T
    assert T in scan_trip_counts(t.jaxpr)
    assert not _errors(CollectiveConsistencyPass().run(t))
    t.meta["expected_scan_trips"] = T + 1    # seeded: schedule desync
    errs = _errors(CollectiveConsistencyPass().run(t))
    assert errs and "trip count" in errs[0].message


def test_async_targets_per_pass_mutations(train_targets):
    """One seeded mutation per training pass on the rank-asymmetric
    targets — the shard_map program form must not blind any of them."""
    # sharding-lint: decorative axis name on a param spec
    t = _fresh(train_targets["pp4_async"])
    i = t.meta["invar_labels"].index("[0]['params']['lm_head']")
    t.meta["in_specs"][i] = P(None, "mp")
    errs = _errors(ShardingLintPass().run(t))
    assert errs and "mp" in errs[0].message
    # donation-audit: dropped donation on a large opt leaf
    t = _fresh(train_targets["pp2_zb"])
    i = next(i for i, (c, v) in enumerate(
        zip(t.meta["invar_classes"], t.jaxpr.jaxpr.invars))
        if c == "opt" and np.prod(v.aval.shape or (1,)) > 64)
    t.meta["donated_invars"][i] = False
    errs = _errors(DonationAuditPass().run(t))
    assert errs and "NON-donated" in errs[0].message
    # hbm-peak: the estimator walks the shard_map program and a budget
    # breach still fires
    t = _fresh(train_targets["pp4_async"])
    t.meta["hbm_budget_bytes"] = 1024
    errs = _errors(HbmPeakPass().run(t))
    assert errs and "budget" in errs[0].message
    # all three clean un-mutated
    for geom in ("pp2_zb", "pp4_async"):
        for p in (ShardingLintPass(), DonationAuditPass(),
                  CollectiveConsistencyPass()):
            assert not _errors(p.run(_fresh(train_targets[geom]))), \
                (geom, p.name)


def test_graph_lint_json_reports_schedule_inventory(capsys):
    """graph_lint --json carries the pipeline-schedule trip/phase
    inventory next to the serving program inventory — one diffable
    schema — and it agrees with the schedule builder's own counts."""
    import importlib.util
    import json as _json
    import os
    from paddle_tpu.analysis.training_graphs import schedule_inventory
    from paddle_tpu.parallel.pipeline_async import build_schedule
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "graph_lint.py")
    spec = importlib.util.spec_from_file_location("graph_lint", path)
    gl = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gl)
    rc = gl.main(["--suite", "training", "--json"])
    out = _json.loads(capsys.readouterr().out)
    assert rc == 0
    inv = out["pipeline_schedules"]
    assert inv == schedule_inventory()
    assert inv["schema"] == "paddle_tpu.schedule_inventory/1"
    assert {"pp_1f1b", "pp2_zb", "pp4_async"} <= set(inv["geometries"])
    zb = inv["geometries"]["pp2_zb"]
    sched = build_schedule(2, 5, 1, "zb")
    assert zb["ticks"] == sched.ticks
    assert zb["phases"] == sched.op_counts()
    assert zb["phases"]["W"] == 2 * 5          # one W per stage per mb
    assert zb["efficiency"] == pytest.approx(sched.efficiency, abs=1e-6)


# ---------------------------------------------------------------------------
# HBM peak estimator: XLA accuracy pin + drift + budget mutations
# ---------------------------------------------------------------------------

def test_hbm_estimator_within_10pct_of_xla(tmp_path):
    """The acceptance pin: static estimate vs the compiled flagship
    llama train step's own accounting (memory_analysis — the
    cost_analysis introspection family), within ±10%."""
    target, step_fn, state, batch = flagship_train_objects()
    est = estimate_hbm_peak(target)
    # compile under the ambient matmul precision the conftest pins for
    # the whole suite ("highest") — the setting every numeric test
    # actually runs this step under; overriding to "default" here makes
    # the CPU backend pick a dot lowering with ~2MiB of extra temp
    # scratch the estimator (rightly) doesn't model. The compile goes
    # through a private EMPTY persistent-cache dir: the shared cache's
    # key ignores the matmul-precision context, so a stale entry
    # lowered under a different precision would silently substitute its
    # own buffer assignment for the fresh one this test measures
    # (disabling jax_enable_compilation_cache mid-process does not
    # reliably stop reads — measured).
    cache_dir = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        compiled = step_fn.lower(state, batch).compile()
    finally:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    xla = xla_peak_bytes(compiled)
    if xla is None:
        pytest.skip("backend exposes no memory_analysis")
    rel = abs(est.peak_bytes - xla) / xla
    assert rel <= 0.10, (est.peak_bytes, xla, rel)
    # the estimate is not a coincidence of ignoring donation: dropping
    # the donation model (old state held to the end) must visibly
    # drift the estimate out of tolerance
    target.meta["donated_invars"] = [False] * len(
        target.meta["donated_invars"])
    est_bad = estimate_hbm_peak(target)
    assert abs(est_bad.peak_bytes - xla) / xla > 0.10, \
        (est_bad.peak_bytes, xla)
    # top contributors are real values with real sizes
    assert est.top and all(b > 0 for b, _ in est.top)


def test_hbm_budget_breach_flagged(train_targets):
    t = _fresh(train_targets["dp"])
    t.meta["hbm_budget_bytes"] = 1 << 40
    assert not _errors(HbmPeakPass().run(t))
    t2 = _fresh(train_targets["dp"])
    t2.meta["hbm_budget_bytes"] = 1024
    errs = _errors(HbmPeakPass().run(t2))
    assert errs and "budget" in errs[0].message


# ---------------------------------------------------------------------------
# fixes the training lint surfaced
# ---------------------------------------------------------------------------

def test_gradscaler_unscale_is_one_host_sync_and_still_detects_inf():
    """amp.GradScaler.unscale_ used to pull one bool per PARAMETER per
    step (the host-sync pass's bug class); it now reduces once. The
    semantics must survive the rewrite: finite grads pass, a single inf
    grad flips found_inf and skips the optimizer step."""
    import paddle_tpu as pt
    from paddle_tpu.amp import GradScaler

    lin = pt.nn.Linear(4, 4)
    opt = pt.optimizer.SGD(learning_rate=0.1,
                           parameters=lin.parameters())
    scaler = GradScaler(init_loss_scaling=8.0)
    x = pt.to_tensor(np.ones((2, 4), np.float32))
    scaler.scale((lin(x) ** 2).mean()).backward()
    scaler.unscale_(opt)
    assert scaler._found_inf is False
    grads = [p._grad for p in opt._param_list if p._grad is not None]
    assert grads
    grads[0]._data = jnp.full_like(grads[0]._data, np.inf)
    scaler.unscale_(opt)
    assert scaler._found_inf is True
    w_before = np.asarray(lin.weight.data).copy()
    scaler.step(opt)                       # must SKIP the update
    np.testing.assert_array_equal(np.asarray(lin.weight.data), w_before)


def test_zero_spec_never_duplicates_axis():
    """Regression for the zero3-then-zero1 double placement: a spec
    already carrying the dp axis must not get it again on another dim
    (P('dp', 'dp') is not a valid sharding)."""
    from paddle_tpu.distributed.sharding import zero_spec
    assert zero_spec(P("dp", None), (32, 64), 2) is None
    assert zero_spec(P(None, "dp"), (32, 64), 2) is None
    assert tuple(zero_spec(P(None, "tp"), (32, 64), 2)) == ("dp", "tp")


def test_group_sharded_parallel_unknown_level_lists_valid_levels():
    import paddle_tpu as pt
    from paddle_tpu import distributed as dist
    m = pt.nn.Linear(4, 4)
    opt = pt.optimizer.SGD(learning_rate=0.1, parameters=m.parameters())
    with pytest.raises(ValueError, match="p_g_os"):
        dist.group_sharded_parallel(m, opt, level="stage2")


# ---------------------------------------------------------------------------
# source lint
# ---------------------------------------------------------------------------

def test_source_lint_rules_and_noqa(tmp_path):
    from paddle_tpu.analysis.source_lint import lint_file
    f = tmp_path / "m.py"
    f.write_text(
        "import os\n"
        "import sys  # noqa: F401\n"
        "from typing import Optional\n"
        "x = None\n"
        "ok = x == None\n"
        "def g(a=[]):\n"
        "    try:\n"
        "        pass\n"
        "    except:\n"
        "        pass\n"
        "    return os.sep\n")
    rules = sorted(r for r, _, _ in lint_file(f))
    assert rules == ["B006", "E711", "E722", "F401"]  # sys suppressed


def test_source_lint_unused_local_rule(tmp_path):
    """F841: plain never-read locals flag; closures, underscores,
    tuple unpacking, class attributes and noqa lines do not."""
    from paddle_tpu.analysis.source_lint import lint_file
    f = tmp_path / "m.py"
    f.write_text(
        "def f():\n"
        "    dead = 1\n"
        "    sup = 2  # noqa: F841\n"
        "    _scratch = 3\n"
        "    a, b = 4, 5\n"
        "    kept = 6\n"
        "    class C:\n"
        "        attr = 7\n"
        "    def inner():\n"
        "        return kept + C.attr\n"
        "    return inner()\n")
    hits = [(r, ln) for r, ln, _ in lint_file(f) if r == "F841"]
    assert hits == [("F841", 2)], hits


def test_repo_source_lint_clean():
    from paddle_tpu.analysis.source_lint import lint_tree
    import pathlib
    root = pathlib.Path(__file__).resolve().parent.parent
    findings = lint_tree(root)
    assert findings == [], "\n".join(map(str, findings))
