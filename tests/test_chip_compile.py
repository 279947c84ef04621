"""Described-chip compiles: the kernels of chip_smoke.py's two phases,
at the smoke's widths, handed to the TPU's own compiler for a v5e that
is described, not attached (the ``on-chip-measurement`` guide, section
2). Nothing runs; what the chip's compiler would refuse — a 16-bit
matmul accumulator, a slice off the tiling, too much VMEM — fails here,
at no chip time. Interpret mode never objects to any of those.

This is the ONE file of such compiles in tier-1. The topology is
described inside the module-scoped fixture below, never at import, in
a ``skipif``, a ``parametrize`` argument or ``conftest.py``: only one
process may load the TPU library, and every xdist worker imports every
test file. Compiles happen in the test's own process for the same
reason.
"""
import functools
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# Llama-3-8B widths (chip_smoke.smoke_config) and the smoke's geometry
H, HKV, DH, D = 32, 8, 128, 4096
G = H // HKV
BATCH, SEQ = 2, 2048
SLOTS, PAGE, PPS = 8, 16, 82           # serve: 8 slots, 82 pages/slot


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """Compile ``fn`` for one described chip at the given shapes. The
    persistent cache is off around it (an entry written for a described
    device cannot be read back without a chip and only warns), and so
    is conftest's ``jax_default_matmul_precision="highest"``: programs
    on the chip run at the default precision, and under "highest"
    Mosaic refuses every bf16 matmul, upstream kernels included."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    one = SingleDeviceSharding(topo.devices[0])
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_default_matmul_precision)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    cc.reset_cache()

    def compile_for_chip(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)
                for s in shapes]
        lowered = jax.jit(fn).lower(*args)
        text = types.SimpleNamespace(lowered=lowered.as_text(),
                                     compiled=lowered.compile().as_text())
        assert "tpu_custom_call" in text.compiled, "no Mosaic kernel"
        return text

    yield compile_for_chip
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_default_matmul_precision", was[1])
    cc.reset_cache()


def sds(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _ragged_args(tq, pps):
    pages = sds((HKV, SLOTS * pps + 1, PAGE, DH))
    i32 = functools.partial(sds, dtype=jnp.int32)
    return (sds((SLOTS, HKV, G * tq, DH)), pages, pages, i32((SLOTS,)),
            i32((SLOTS,)), i32((SLOTS, pps)))


# the engine's packed widths in the smoke: the fused block (tq=1), a
# decode-heavy tick (32) and a full prefill chunk (256)
@pytest.mark.parametrize("tq", [1, 32, 256])
def test_ragged_one_shot(chip, tq):
    from paddle_tpu.ops.pallas import ragged_paged_attention as R
    fn = functools.partial(R._pallas_impl, tq=tq, g=G, interpret=False)
    text = chip(fn, *_ragged_args(tq, PPS))
    # chip_smoke.py finds the kernel by name in the train step's
    # compiled text and in the serving programs' lowered text
    from chip_smoke import kernels_in
    assert kernels_in(text.compiled)["ragged_paged_attention"] == 1
    assert kernels_in(text.lowered)["ragged_paged_attention"] == 1


@pytest.mark.parametrize("tq", [1, 256])
def test_ragged_tiled(chip, tq):
    """The long-context walk: a 16k-token table is past the one-shot
    VMEM knee, so this is what ``kv_tile_pages=None`` picks there."""
    from paddle_tpu.ops.pallas import ragged_paged_attention as R
    pps = 1024
    tile = R.default_kv_tile_pages(pps, PAGE, DH)
    assert tile > 0
    fn = functools.partial(R._pallas_tiled_impl, tq=tq, g=G,
                           tile_pages=tile, interpret=False)
    chip(fn, *_ragged_args(tq, pps))


def test_splash_fwd(chip):
    from paddle_tpu.ops.pallas.flash_attention import _splash
    fn = functools.partial(_splash, causal=True, sm_scale=DH ** -0.5)
    text = chip(fn, sds((BATCH, SEQ, H, DH)), sds((BATCH, SEQ, HKV, DH)),
                sds((BATCH, SEQ, HKV, DH)))
    assert "splash_mha" in text.compiled


def test_fused_rms_fwd_bwd(chip):
    from paddle_tpu.ops.pallas import fused_norm_rope as F
    geom = {"rows": BATCH * SEQ, "d": D, "dtype": "bfloat16"}
    labels = []
    for label, fn, args in F.audit_launches(geom):
        chip(fn, *args)
        labels.append(label.split("[")[0])
    assert labels == ["rms_fwd", "rms_bwd"]


def test_fused_rope(chip):
    from paddle_tpu.ops.pallas import fused_norm_rope as F
    geom = {"rope_batch": BATCH, "rope_seq": SEQ, "rope_heads": H,
            "rope_kv_heads": HKV, "rope_head_dim": DH, "dtype": "bfloat16"}
    (_, fn, args), = F.audit_launches(geom)
    chip(fn, *args)


# bench.py's batched mixed-length decode: 32 streams, 32-token pages,
# prompts to 2048 tokens
_PK = dict(B=32, page=32, pps=64)


def _paged_kv_args():
    B, page, pps = _PK["B"], _PK["page"], _PK["pps"]
    pages = sds((HKV, B * pps, page, DH))
    return (sds((B, H, DH)), pages, pages, sds((B,), jnp.int32),
            sds((B, pps), jnp.int32))


def test_paged_kv_paged_attention(chip):
    from paddle_tpu.inference.paged_kv import paged_attention
    chip(functools.partial(paged_attention, impl="pallas"),
         *_paged_kv_args())


def test_paged_kv_stats_call(chip):
    """``_stats_call`` re-plumbs a private upstream kernel body; this is
    the compile test that says the plumbing still fits the installed
    JAX."""
    from paddle_tpu.inference.paged_kv import _stats_call
    chip(functools.partial(_stats_call, pages_per_compute_block=4),
         *_paged_kv_args())


def test_int8_matmul(chip):
    """Weight-only int8 decode GEMMs at 8B widths: the MLP up-projection
    for a block of 8 slots."""
    from paddle_tpu.ops.pallas import int8_matmul as I
    (_, fn, args), = I.audit_launches(
        {"M": SLOTS, "K": D, "N": 14336, "dtype": "bfloat16"})
    chip(fn, *args)
