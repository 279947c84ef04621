"""Kernel auditor (analysis/kernel_audit.py): mutation-tested rules,
clean-tree pin, and the autotune flywheel's admission gates.

The mutation discipline mirrors test_concurrency's: each probe kernel
carries exactly one seeded violation and must trip exactly its rule —
a rule that also fires on the clean probes is over-broad, one that
misses its seeded violation proves nothing. The clean-tree pin then
locks the real kernel tree at zero findings with every rule
non-vacuously evaluated.
"""
import json
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.analysis import kernel_audit as ka
from paddle_tpu.ops import autotune as at


def _rules(findings):
    return sorted({f.pass_name.split("/")[-1] for f in findings})


def _audit(fn, args, label="probe", **kw):
    return ka.audit_callable("probe", label, fn, args, **kw)


# --------------------------------------------------- mutation probes ----

def _copy_probe(in_map=None, out_map=None, scratch=(), grid=(2,),
                dtype=jnp.float32, body=None):
    """A 128x128 -> 128x128 tiled copy, one seam per rule mutation:
    the index maps, the scratch list, and the kernel body are the
    injection points."""
    in_map = in_map or (lambda i: (i, 0))
    out_map = out_map or (lambda i: (i, 0))
    tile = 128 // grid[0]

    def kern(x_ref, o_ref, *scr):
        if body is not None:
            body(x_ref, o_ref, *scr)
        else:
            o_ref[...] = x_ref[...]

    def fn(x):
        return pl.pallas_call(
            kern,
            grid=grid,
            in_specs=[pl.BlockSpec((tile, 128), in_map)],
            out_specs=pl.BlockSpec((tile, 128), out_map),
            scratch_shapes=list(scratch),
            out_shape=jax.ShapeDtypeStruct((128, 128), dtype),
        )(x)

    return fn, (jax.ShapeDtypeStruct((128, 128), dtype),)


def test_clean_probe_passes_every_rule():
    fn, args = _copy_probe()
    findings, suppressed, vmem, evals = _audit(fn, args)
    assert not findings and not suppressed
    assert vmem and vmem[0]["ok"]
    assert evals["KA001"] == 1 and evals["KA002"] >= 2


def test_ka001_trips_on_vmem_busting_scratch():
    # 2048x2048 f32 scratch = 16 MiB alone: past the 14 MiB budget
    fn, args = _copy_probe(
        scratch=(pltpu.VMEM((2048, 2048), jnp.float32),))
    findings, _, vmem, _ = _audit(fn, args)
    assert _rules(findings) == ["KA001"]
    assert not vmem[0]["ok"]
    assert vmem[0]["total_bytes"] > ka.VMEM_AUDIT_BUDGET
    assert "exceeds budget" in findings[0].message


def test_ka002_trips_on_out_of_bounds_index_map():
    # input map shifted one tile right: off the array at the last step
    fn, args = _copy_probe(grid=(4,), in_map=lambda i: (i + 1, 0))
    findings, _, _, _ = _audit(fn, args)
    assert _rules(findings) == ["KA002"]
    assert "bounds" in findings[0].message


def test_ka002_trips_on_uncovered_output_tiles():
    # every grid step writes output tile 0: tiles 1..3 never written
    fn, args = _copy_probe(grid=(4,), out_map=lambda i: (0, 0))
    findings, _, _, _ = _audit(fn, args)
    assert _rules(findings) == ["KA002"]
    assert "tiles" in findings[0].message


def test_ka003_trips_on_dropped_dma_wait():
    def body(x_hbm, o_ref, scr, sem):
        pltpu.make_async_copy(x_hbm.at[0:64], scr.at[0],
                              sem.at[0]).start()
        o_ref[...] = scr[0]  # read of the DMA destination, no wait

    def fn(x):
        return pl.pallas_call(
            body,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((64, 128), lambda i: (0, 0)),
            scratch_shapes=[pltpu.VMEM((2, 64, 128), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))],
            out_shape=jax.ShapeDtypeStruct((64, 128), jnp.float32),
        )(x)

    findings, _, _, _ = _audit(
        fn, (jax.ShapeDtypeStruct((128, 128), jnp.float32),))
    assert _rules(findings) == ["KA003"]
    msgs = " | ".join(f.message for f in findings)
    assert "dma_wait" in msgs


def test_ka003_clean_when_wait_present():
    def body(x_hbm, o_ref, scr, sem):
        cp = pltpu.make_async_copy(x_hbm.at[0:64], scr.at[0], sem.at[0])
        cp.start()
        cp.wait()
        o_ref[...] = scr[0]

    def fn(x):
        return pl.pallas_call(
            body,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((64, 128), lambda i: (0, 0)),
            scratch_shapes=[pltpu.VMEM((2, 64, 128), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))],
            out_shape=jax.ShapeDtypeStruct((64, 128), jnp.float32),
        )(x)

    findings, _, _, _ = _audit(
        fn, (jax.ShapeDtypeStruct((128, 128), jnp.float32),))
    assert not findings


def test_ka004_trips_on_bf16_accumulator():
    def body(x_ref, o_ref, acc):
        acc[...] += x_ref[...]     # reduction carry in bf16
        o_ref[...] = acc[...]

    fn, args = _copy_probe(
        dtype=jnp.bfloat16, body=body,
        scratch=(pltpu.VMEM((64, 128), jnp.bfloat16),))
    findings, _, _, _ = _audit(fn, args)
    assert _rules(findings) == ["KA004"]

    # the correct form — f32 carry over bf16 inputs — is clean
    def good(x_ref, o_ref, acc):
        acc[...] += x_ref[...].astype(jnp.float32)
        o_ref[...] = acc[...].astype(jnp.bfloat16)

    fn, args = _copy_probe(
        dtype=jnp.bfloat16, body=good,
        scratch=(pltpu.VMEM((64, 128), jnp.float32),))
    findings, _, _, _ = _audit(fn, args)
    assert not findings


# ---------------------------------------------------------- waivers ----

def test_waiver_suppresses_and_is_inventoried():
    fn, args = _copy_probe(
        scratch=(pltpu.VMEM((2048, 2048), jnp.float32),))
    w = ka.Waiver("KA001", "probe", "seeded probe, budget waived")
    findings, suppressed, _, _ = _audit(fn, args, waivers=(w,))
    assert not findings
    assert suppressed and suppressed[0]["rule"] == "KA001"
    assert suppressed[0]["reason"] == "seeded probe, budget waived"
    # a waiver only mutes its own rule
    fn2, args2 = _copy_probe(grid=(4,), out_map=lambda i: (0, 0))
    findings, suppressed, _, _ = _audit(fn2, args2, waivers=(w,))
    assert _rules(findings) == ["KA002"] and not suppressed


def test_reasonless_waiver_rejected():
    with pytest.raises(ka.KernelAuditError, match="justification"):
        ka.Waiver("KA001", "probe", "   ")
    with pytest.raises(ka.KernelAuditError, match="unknown rule"):
        ka.Waiver("KA999", "probe", "nope")


# ---------------------------------------------------- clean-tree pin ----

def test_clean_tree_pin(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_AUTOTUNE_DIR", raising=False)
    rep = ka.run_kernel_audit()
    assert rep["ok"], (rep["findings"], rep["errors"],
                       rep["stale_waivers"])
    assert sorted(rep["kernels"]) == [
        "conv_epilogue", "flash_attention", "fused_norm_rope",
        "grouped_matmul", "int8_matmul", "mla_paged_attention",
        "ragged_paged_attention", "ssd_update"]
    # non-vacuity: every rule actually evaluated something
    assert all(rep["rule_evals"][r] > 0 for r in ka.ALL_RULES), \
        rep["rule_evals"]
    # the per-kernel VMEM table is the --json payload: every launch
    # priced, every row under budget
    assert len(rep["vmem"]) >= rep["launches"]
    assert all(row["ok"] for row in rep["vmem"])
    assert {row["kernel"] for row in rep["vmem"]} == set(rep["kernels"])


def test_kernel_signatures_cover_autotuned_kinds():
    sigs = ka.kernel_signatures()
    assert set(sigs) == {"ragged_paged_attention", "fused_rms_norm",
                         "conv_epilogue", "grouped_matmul", "ssd_update",
                         "mla_paged_attention"}
    assert tuple(sigs["fused_rms_norm"]["config_keys"]) == ("tile_n",)
    # geom_keys are kept sorted — the store validator compares them
    # against sorted(loaded geometry) keys
    assert tuple(sigs["ragged_paged_attention"]["geom_keys"]) == (
        "dtype", "head_dim", "page_size", "pages_per_slot")


# ------------------------------------------- vmem_scratch_bytes pin ----

def test_vmem_scratch_bytes_agrees_with_ka001():
    """The bench column and the auditor's KA001 accounting are the
    same number, byte for byte, across the sweep grid: the K+V tiles
    (the whole table where it fits one tile, O(tile) past that) plus
    the float32 flash state of the audited launch's query rows, for
    the KV heads a grid step holds (both of the audited launch's)."""
    from paddle_tpu.ops.pallas import ragged_paged_attention as rpa
    shape = rpa.AUDIT_SHAPE
    rows = shape["group"] * shape["tq"]
    grid = [(16, 16, None), (64, 16, 0), (128, 32, None),
            (256, 16, 8), (512, 16, 16), (1024, 16, 32)]
    for pps, ps, tile in grid:
        geom = {"pages_per_slot": pps, "page_size": ps,
                "head_dim": 128, "dtype": "bfloat16"}
        config = None if tile is None else {"kv_tile_pages": tile}
        for label, fn, args in rpa.audit_launches(geom, config):
            _, _, vmem, _ = ka.audit_callable(
                "ragged_paged_attention", label, fn, args,
                rules=("KA001",))
            got = sum(row["scratch_bytes"] for row in vmem)
            want = rpa.vmem_scratch_bytes(
                pps, ps, 128, jnp.bfloat16, kv_tile_pages=tile, rows=rows,
                kv_heads=shape["kv_heads"])
            assert got == want, (pps, ps, tile, got, want)


# ------------------------------------------------ the flywheel gates ----

@pytest.fixture
def store(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_DIR", str(tmp_path))
    at.clear()
    yield tmp_path
    at.clear()


def test_record_gate_refuses_audit_failing_winner(store):
    # rows=64 with tile_n=5: 5 does not tile 64 -> KA002 coverage
    with pytest.raises(at.AutotuneAuditError, match="KA002"):
        at.record("fused_rms_norm", {"tile_n": 5}, audit=True,
                  rows=64, d=32, dtype="float32")
    assert at.raw_store() == {}          # nothing written
    # the sound winner IS admitted through the same gate
    at.record("fused_rms_norm", {"tile_n": 4}, audit=True,
              rows=64, d=32, dtype="float32")
    assert at.lookup("fused_rms_norm", rows=64, d=32,
                     dtype="float32") == {"tile_n": 4}


def test_load_gate_skips_stale_winner(store):
    # recorded un-audited (yesterday's store, or audit=False sweep):
    # the read side still refuses to serve it
    at.record("fused_rms_norm", {"tile_n": 5},
              rows=64, d=32, dtype="float32")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        got = at.lookup("fused_rms_norm", rows=64, d=32,
                        dtype="float32")
    assert got is None
    assert any("kernel audit" in str(x.message)
               and "KA002" in str(x.message) for x in w)


def test_auditor_fault_is_not_read_as_no_winner(store, monkeypatch):
    """A fault INSIDE the auditor (an AttributeError after a jax
    upgrade was the real one) propagates from both gates; swallowed, it
    made ``lookup`` quietly drop every stored winner."""
    at.record("fused_rms_norm", {"tile_n": 4},
              rows=64, d=32, dtype="float32")
    ka.clear_verdict_cache()

    def broken(*a, **k):
        raise AttributeError("module 'jax.core' has no attribute 'Literal'")

    monkeypatch.setattr(ka, "audit_kernel", broken)
    with pytest.raises(AttributeError, match="Literal"):
        at.lookup("fused_rms_norm", rows=64, d=32, dtype="float32")
    with pytest.raises(AttributeError, match="Literal"):
        at.record("fused_rms_norm", {"tile_n": 8}, audit=True,
                  rows=64, d=32, dtype="float32")
    # a launch the auditor cannot trace is still a verdict, not a fault
    monkeypatch.setattr(
        ka, "audit_kernel",
        lambda *a, **k: (_ for _ in ()).throw(ka.KernelAuditError("x")))
    assert ka.audit_config("fused_rms_norm",
                           {"rows": 64, "d": 32, "dtype": "float32"},
                           {"tile_n": 4}, use_cache=False)["rules"] == ["build"]
    ka.clear_verdict_cache()    # later tests audit this geometry for real


def test_load_gate_env_escape_hatch(store, monkeypatch):
    at.record("fused_rms_norm", {"tile_n": 5},
              rows=64, d=32, dtype="float32")
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_AUDIT", "0")
    assert at.lookup("fused_rms_norm", rows=64, d=32,
                     dtype="float32") == {"tile_n": 5}


def test_store_schema_validation_drops_stale_entries(store):
    bad = {
        # kind renamed since the sweep: no registered signature
        "renamed_kernel": {json.dumps({"rows": 64}): {"tile_n": 4}},
        # geometry keys from an older schema revision
        "conv_epilogue": {json.dumps({"m": 64}): {"tm": 8}},
        # healthy entry rides along untouched
        "fused_rms_norm": {
            at.geometry_key(rows=64, d=32, dtype="float32"):
            {"tile_n": 4}},
    }
    (store / "winners.json").write_text(json.dumps(bad))
    at.clear()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        loaded = at.raw_store()
    assert set(loaded) == {"fused_rms_norm"}
    assert len([x for x in w if "skipping" in str(x.message)]) == 2
    assert at.lookup("fused_rms_norm", rows=64, d=32,
                     dtype="float32") == {"tile_n": 4}


def test_store_audit_runs_inside_outer_jit(store):
    # autotune.lookup audits at trace time — the entry point must
    # still resolve its swept winner from inside jit
    at.record("fused_rms_norm", {"tile_n": 4}, audit=True,
              rows=64, d=32, dtype="float32")
    from paddle_tpu.ops.pallas.fused_norm_rope import fused_rms_norm
    x = jnp.ones((64, 32), jnp.float32)
    wt = jnp.ones((32,), jnp.float32)
    y = jax.jit(fused_rms_norm)(x, wt)
    np.testing.assert_allclose(np.asarray(y), 1.0, rtol=1e-5)


def test_kernel_bench_rows_carry_audit_verdict():
    from tools.kernel_bench import _audit_verdict
    geom = dict(rows=64, d=32, dtype="float32")
    assert _audit_verdict("fused_rms_norm", geom, {"tile_n": 4}) == "ok"
    bad = _audit_verdict("fused_rms_norm", geom, {"tile_n": 5})
    assert bad.startswith("failed:") and "KA002" in bad
    assert _audit_verdict("no_such_kernel", geom, {}) == \
        "failed:unregistered"


def test_audit_config_verdict_shapes():
    v = ka.audit_config("fused_rms_norm",
                        {"rows": 64, "d": 32, "dtype": "float32"},
                        {"tile_n": 4})
    assert v["ok"] and v["rules"] == []
    v = ka.audit_config("fused_rms_norm",
                        {"rows": 64, "d": 32, "dtype": "float32"},
                        {"tile_n": 5})
    assert not v["ok"] and v["rules"] == ["KA002"]
    v = ka.audit_config("ghost", {}, None)
    assert not v["ok"] and v["rules"] == ["unregistered"]
