"""chip_smoke.py's phases at ``LlamaConfig.tiny`` on the CPU — by
calling its functions, the script has no CPU mode — and its refusal to
run without a TPU. What only the chip can show (Mosaic kernels in the
compiled programs, agreement at bf16 and real widths) is what the
script itself checks there; here the same functions must run, judge
right answers right, and judge an absent kernel a failure."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke as cs
from paddle_tpu.models import llama as L

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cfg():
    # splash has no interpret mode: dense attention here, the fused
    # norm/rope kernels interpreted
    return L.LlamaConfig.tiny(dtype=jnp.float32, remat=True,
                              use_flash_attention=False,
                              use_fused_norm_rope="pallas")


@pytest.fixture(scope="module")
def trained(cfg):
    return cs.train_phase(cfg, devices=jax.devices()[:1], batch=2, seq=128,
                          steps=3)


def test_train_phase_runs_and_is_judged(trained):
    assert trained["mesh"] == {"dp": 1, "pp": 1, "tp": 1}
    assert len(trained["losses"]) == 3 and trained["program_bytes"] > 0
    cs.check_train(trained, kernels=())
    # off the chip the kernels are interpreted, so no tpu_custom_call:
    # exactly the "kernel silently gave way" the script must refuse
    with pytest.raises(cs.SmokeFailure, match="tpu_custom_call"):
        cs.check_train(trained)
    with pytest.raises(cs.SmokeFailure, match="did not fall"):
        cs.check_train({**trained, "losses": [1.0, 2.0]}, kernels=())
    with pytest.raises(cs.SmokeFailure, match="non-finite"):
        cs.check_train({**trained, "losses": [1.0, float("nan")]},
                       kernels=())


def test_serve_phase_runs_and_is_judged(cfg, trained):
    params = trained["state"]["params"]
    reqs = cs.smoke_requests(cfg.vocab_size, 0, lens=(5, 20, 70),
                             shared=(16, 6), new=(4, 6, 8, 4, 4))
    sv = cs.serve_phase(params, cfg, reqs, max_batch=4, page_size=4,
                        max_prompt_len=80, max_new_tokens_cap=8,
                        prompt_buckets=(8, 16, 80), prefill_chunk=16)
    assert sorted(sv["program_kernels"]) == ["block", "tick@16", "tick@8"]
    assert sv["n_programs"] == 3
    cs.check_serve(sv, need_kernel=False)
    with pytest.raises(cs.SmokeFailure, match="ragged kernel"):
        cs.check_serve(sv)
    short = {**sv, "outs": [o[:-1] for o in sv["outs"]]}
    with pytest.raises(cs.SmokeFailure, match="asked"):
        cs.check_serve(short, need_kernel=False)
    dirty = {**sv, "sentinel": {**sv["sentinel"], "clean": False}}
    with pytest.raises(cs.SmokeFailure, match="after warm-up"):
        cs.check_serve(dirty, need_kernel=False)
    # f32 on the CPU: the engine's greedy tokens ARE generate()'s
    same = cs.tokens_equal_generate(params, cfg, reqs, sv["outs"],
                                    which=range(len(reqs)))
    assert all(same.values()), same
    # the kernel (interpreted) and its dense reference share _attend
    assert cs.compare_ragged_kernel(cfg, sv["geometry"], tq=16, seed=0) == 0


def test_sharded_phase_on_virtual_devices(cfg):
    rep = cs.sharded_phase(cfg, jax.devices(), batch=2, seq=128, tol=1e-3)
    assert rep["four"]["mesh"] == {"dp": 2, "pp": 1, "tp": 2}
    assert len(rep["four"]["held"]["state"]) == 4
    assert rep["four"]["collectives"]["all-reduce"] > 0
    with pytest.raises(cs.SmokeFailure, match="found 1 device"):
        cs.sharded_phase(cfg, jax.devices()[:1], batch=2, seq=128)


def test_kernel_marks_read_a_compiled_program():
    text = ('%a = custom-call(), custom_call_target="tpu_custom_call", '
            'metadata={op_name="jit(step)/jit(_rope_call)/pallas_call"}\n'
            '%splash_mha_fwd.1 = custom-call(), '
            'custom_call_target="tpu_custom_call"\n'
            '%b = fusion(), metadata={op_name="jit(_rms_fwd_call)"}\n'
            # lowered (StableHLO) form, as ServingEngine.program_texts()
            # gives it: the kernel's own name, no op_name metadata
            '%1 = stablehlo.custom_call @tpu_custom_call(%arg3) '
            '{kernel_name = "ragged_paged_attention"}\n')
    assert cs.kernels_in(text) == {
        "splash_attention": 1, "fused_rms_norm": 0, "fused_rope": 1,
        "ragged_paged_attention": 1}


@pytest.mark.parametrize("script,says", [
    ("chip_smoke.py", "needs a TPU"),
    ("bench.py", "there is no CPU mode"),
])
def test_script_exits_nonzero_without_tpu(script, says):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, script], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert says in r.stderr and "'cpu'" in r.stderr
    # no result line: nothing on stdout may read as a verdict or a metric
    assert '"ok"' not in r.stdout and '"metric"' not in r.stdout


def test_peaks_table_has_no_default():
    """One table (tools/resnet_bench.py reads bench's), keyed by
    device_kind; a device it does not list is an error."""
    import types

    import bench
    v5e = types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
    assert bench.peak_flops(v5e) == 197e12
    with pytest.raises(SystemExit, match="no bf16 peak listed"):
        bench.peak_flops(jax.devices()[0])
    with open(os.path.join(REPO, "tools", "resnet_bench.py")) as f:
        src = f.read()
    assert "from bench import peak_flops" in src and "197e12" not in src


def test_smoke_config_is_llama3_8b_with_only_depth_cut():
    c, full = cs.smoke_config(), L.LlamaConfig.llama3_8b()
    cut = {"num_hidden_layers", "dtype", "max_position_embeddings",
           "use_flash_attention", "use_fused_norm_rope", "remat"}
    for f in dataclasses.fields(c):
        if f.name not in cut:
            assert getattr(c, f.name) == getattr(full, f.name), f.name
    assert (c.num_hidden_layers, c.dtype) == (cs.LAYERS, jnp.bfloat16)
    assert c.use_flash_attention == c.use_fused_norm_rope == "pallas"
    assert np.isclose(c.head_dim, 128)
