"""End-to-end rehearsals on the CPU at a tiny size: each mode is driven
through the same functions a chip run uses (only the harness's look for
a TPU is skipped), the last line's keys are checked, the control is
shown to fail and a broken timed path is shown to come out not correct.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import argparse
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import manifest, modes, reference  # noqa: E402
from harness.common import require_devices  # noqa: E402

TINY = os.path.join(HERE, "tiny")
KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def run_cell(name, seed=7, seconds=1.5, trace=0):
    cell = manifest.Cell(manifest.load_manifest(TINY), name, TINY)
    devs = require_devices(1, "cpu")
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace)
    line = modes.MODES[cell.mode](cell, args, devs, time.perf_counter())
    return cell, json.loads(line)


def test_run_py_refuses_to_run_without_a_tpu():
    import subprocess
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "mistral7b-serve-chat", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    assert "needs 1 tpu" in r.stderr


def test_serve_open_last_line():
    cell, out = run_cell("tiny-serve-open", seed=2**31 + 5)
    assert set(out) == KEYS
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 12
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}


def test_serve_closed_last_line():
    cell, out = run_cell("tiny-serve-closed")
    assert out["correct"] is True
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("name", ["tiny-serve-open", "tiny-split-closed"])
def test_altered_token_comes_out_not_correct(monkeypatch, name):
    """The timed path broken underneath: every 5th token the engine
    emits is altered where it is produced."""
    from paddle_tpu.serving import engine as E
    real = E.ServingEngine._emit
    n = [0]

    def emit(self, slot, req, tok):
        n[0] += 1
        if n[0] % 5 == 0:
            tok = (int(tok) + 1) % 256
        return real(self, slot, req, tok)

    monkeypatch.setattr(E.ServingEngine, "_emit", emit)
    _, out = run_cell(name, seed=11)
    assert out["correct"] is False and out["failed"] == 0
    gap, limit = out["compared"]["served_logit_gap_max"]
    assert gap > limit and list(out)[-1] == "compared"


@pytest.mark.parametrize("name", ["tiny-serve-open", "tiny-split-closed"])
def test_control_fails_the_limits(name):
    """The reference with float8-rounded weights in the program's
    place: the token it puts first lies further below the float32
    reference's best than the cell's limit allows."""
    cell = manifest.Cell(manifest.load_manifest(TINY), name, TINY)
    family = cell.family
    params = family.make_params(cell.model, 5)
    rng = np.random.default_rng(5)
    worst = 0.0
    for _ in range(3):
        prompt = rng.integers(0, 256, (40,), dtype=np.int32)
        served = rng.integers(0, 256, (16,), dtype=np.int32)
        _, low = reference.served_gaps(
            params, prompt, served, cell.model, family,
            family.CONTROL_ROUND_TO)
        worst = max(worst, float(low.max()))
    assert worst > 3 * cell.workload["limits"]["served_logit_gap_max"]


def test_reference_matches_program_forward():
    """The plain reference against ``models/llama.py``'s own forward at
    the tiny size, float32: they describe the same model."""
    import jax
    cell = manifest.Cell(manifest.load_manifest(TINY), "tiny-serve-open",
                         TINY)
    family = cell.family
    params = family.make_params(cell.model, 9)
    cfg, L = family.program_config(cell.model, use_flash_attention=False,
                                   use_fused_norm_rope=False, remat=False)
    toks = np.arange(32, dtype=np.int32) * 7 % 256
    with jax.default_matmul_precision("highest"):
        prog = np.asarray(L.forward(params, toks[None], cfg))[0]
    h = reference.hidden_states(params, toks, cell.model, family)
    ref = np.asarray(reference.logits_at(params, h, np.arange(32),
                                         cell.model))
    assert np.abs(prog - ref).max() < 1e-4


def test_train_last_line():
    cell, out = run_cell("tiny-train", seed=2**31 + 9, seconds=0.5)
    assert set(out) == KEYS
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["attempted"] >= 1


def test_train_step_that_returns_its_state_comes_out_not_correct(
        monkeypatch):
    """The timed path broken underneath: a step that returns its state
    unchanged (and the loss of the unchanged weights)."""
    from harness import train

    real = train.Trainer.step

    def stuck(self):
        before = self.state
        import jax
        keep = jax.tree_util.tree_map(lambda a: a.copy(), before)
        loss = real(self)
        self.state = keep
        return loss

    monkeypatch.setattr(train.Trainer, "step", stuck)
    _, out = run_cell("tiny-train", seed=3, seconds=0.3)
    assert out["correct"] is False


def test_train_control_fails_a_limit():
    """The reference at float8 weights in the program's place: one of
    the compared numbers leaves its limit."""
    from harness import train
    from harness.common import Checks
    cell = manifest.Cell(manifest.load_manifest(TINY), "tiny-train", TINY)
    family = cell.family
    ref = train.run_reference(cell, cell.model, family, 4)
    low = train.run_reference(cell, cell.model, family, 4,
                              round_to=family.CONTROL_ROUND_TO)
    checks = Checks()
    train.compare_training(low, ref, cell.workload["limits"], checks)
    assert not all(checks)


def test_control_rows_at_tiny_size():
    """control.py's per-seed functions: the program's numbers and the
    control's side by side, as they are read on the chip."""
    import control
    devs = require_devices(1, "cpu")
    m = manifest.load_manifest(TINY)
    row = control.serve_seed(manifest.Cell(m, "tiny-serve-open", TINY), 21,
                             1.0, devs)
    assert row["correct"] and row["control_gap_max"] > row["gap_max"]
    row = control.train_seed(manifest.Cell(m, "tiny-train", TINY), 21, devs)
    assert row["correct"] and not row["control_correct"]


def test_moe_family_serve_closed_last_line():
    """The MoE family through the same engine and the MoE reference."""
    cell, out = run_cell("tiny-moe-closed", seed=13)
    assert out["correct"] is True and out["failed"] == 0
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_added_family_serve_closed_last_line():
    """A family made only of added files (``tiny/families/tiny_split.py``):
    its own layer function over a two-group stack decides ``correct``."""
    cell, out = run_cell("tiny-split-closed", seed=19)
    assert cell.family.__file__.startswith(TINY)
    assert not hasattr(cell.family, "REFERENCE_KIND")
    groups = reference.layer_groups(cell.family.make_params(cell.model, 1),
                                    cell.model, cell.family)
    assert [g[1]["wq"].shape[0] for g in groups] == [1, 2]
    assert out["correct"] is True and out["failed"] == 0
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert set(out["compared"]) >= {"served_logit_gap_max",
                                    "served_logit_gap_mean"}


def test_added_family_reference_equals_the_built_in_dense_path():
    """Same weights, serving and training: the family's own layers in
    two groups against ``LAYER_FNS["dense"]`` over one stack."""
    from harness import train
    m = manifest.load_manifest(TINY)
    split = manifest.Cell(m, "tiny-split-closed", TINY)
    dense = manifest.load_family("dense_decoder")
    params = split.family.make_params(split.model, 23)
    toks = np.arange(48, dtype=np.int32) * 11 % 256
    for round_to in (None, 3):
        a = reference.hidden_states(params, toks, split.model, split.family,
                                    round_to)
        b = reference.hidden_states(params, toks, split.model, dense,
                                    round_to)
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-6
    cell = manifest.Cell(m, "tiny-train", TINY)
    model = {**cell.model, "num_hidden_layers": 3}
    a = train.run_reference(cell, model, split.family, 23)
    b = train.run_reference(cell, model, dense, 23)
    assert a["grad"].keys() == b["grad"].keys() >= {"layers.wq", "embed"}
    for what in ("grad", "change"):
        for k in b[what]:
            assert a[what][k] == pytest.approx(b[what][k], rel=1e-6), k
    assert a["losses"] == pytest.approx(b["losses"], abs=1e-6)


def _fake_reduced(monkeypatch):
    """The CPU's profile has no TPU plane: stand in for the adapter with
    a hand-made reduction (the reducer itself is tested on events)."""
    from harness import trace
    events = [("%while.1 = while()", 0, 900_000_000),
              ("%ragged_paged_attention.2 = custom-call()", 100, 200_000_000),
              ("%splash_mha_fwd.3 = custom-call()", 300_000_000, 100_000_000),
              ("%all-reduce.4 = all-reduce()", 950_000_000, 20_000_000)]
    red = trace.reduce_events(events, window=(0, 1_000_000_000))
    monkeypatch.setattr(trace, "reduce_trace",
                        lambda d, n, phases=None: {**red,
                                                   "devices": ["fake"]})
    # an unknown device kind is an error, as it must be; the stand-in
    # trace gets a stand-in peak
    from harness import readers
    real = readers.peaks
    monkeypatch.setattr(readers, "peaks", lambda kind: real("TPU v5 lite"))


@pytest.mark.parametrize("name", ["tiny-serve-open", "tiny-train"])
def test_traced_run_reports_the_per_layer_metrics(monkeypatch, name):
    _fake_reduced(monkeypatch)
    cell, out = run_cell(name, seed=17, seconds=1.5, trace=1)
    assert set(out) == KEYS | {"breakdown"}
    assert out["device"]["busy_s"] > 0
    assert out["device"]["window_s"] >= out["device"]["busy_s"]
    assert len(out["breakdown"]["device_ops"]) <= 10
    want = {m["name"] for m in cell.per_layer}
    assert set(out["metrics"]) <= want and out["metrics"]
    assert not set(out["metrics"]) & {m["name"] for m in cell.end_to_end}


def test_traced_run_files_operations_under_the_family_s_own_labels(
        monkeypatch):
    """A scope and a kernel that only ``tiny_split`` declares: the traced
    rehearsal (the device's operation line hand-made, the CPU's profile
    has none) files an operation under each, the added reader reads
    them, and another family's cell finds nothing there."""
    from harness import hostspans
    _fake_reduced(monkeypatch)
    body = "jit(serving_tick)/layers/while/body/"
    device = [
        ("while.1", 0, 1000, "jit(serving_tick)/layers/while"),
        ("fusion.2", 0, 100, body + "attn.split_latent/dot_general"),
        ("split_latent_attention.3", 100, 400,
         body + "attn.split_latent/pallas_call"),
        ("ragged_paged_attention.4", 400, 600, body + "ragged_attn/call"),
        ("fusion.5", 600, 900, body + "mlp/dot_general")]
    monkeypatch.setattr(hostspans, "read_xplane",
                        lambda path: ([], device, ["jit_serving_tick"]))
    seen = {}
    real = hostspans.load

    def load(ctx):
        seen[ctx["cell"].name] = real(ctx)
        return seen[ctx["cell"].name]

    monkeypatch.setattr(hostspans, "load", load)
    cell, out = run_cell("tiny-split-closed", seed=29, trace=1)
    by = seen["tiny-split-closed"]["by_label"]
    assert by == {"attn.split_latent": 100, "attn.split_latent.kernel": 300,
                  "ragged_attn.kernel": 200, "mlp": 300, "layers": 100}
    assert out["metrics"]["split_latent_ms_per_tick"]["value"] > 0
    # the same trace under a family that declares neither
    run_cell("tiny-serve-closed", seed=29, trace=1)
    assert seen["tiny-serve-closed"]["by_label"] == {
        "layers": 500, "ragged_attn.kernel": 200, "mlp": 300}
