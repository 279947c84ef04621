"""The cell ``lfm2moe-serve-generate`` and what it adds: its manifest
entries resolved through the loader, the new readers on hand-made
``ctx``s, their bytes and FLOP functions against numbers worked by hand
here, and a tiny CPU rehearsal of the family through ``serve_closed``.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_lfm2_cell.py -q -p no:cacheprovider
"""
import argparse
import json
import os
import shutil
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import manifest  # noqa: E402

CELL = "lfm2moe-serve-generate"
NEW = ["shortconv_ms_per_tick", "moe_experts_ms_per_tick",
       "moe_experts_roofline_pct", "attn_layers_roofline_pct"]


class Device:
    device_kind = "TPU v5 lite"


def cell():
    return manifest.Cell(manifest.load_manifest(), CELL)


def ctx_with(hostspans, model=None):
    return {"hostspans": hostspans, "model": model or cell().model,
            "devices": [Device()], "window": {"trace_ticks": 4, "hists": {}}}


def test_the_cell_resolves_through_the_loader():
    c = cell()
    assert c.mode == "serve_closed" and c.chips == 1
    assert {m["name"] for m in c.end_to_end} == {"serve_tokens_per_s",
                                                 "setup_s"}
    assert len(c.readers) == 14
    assert {k.rsplit(".", 1)[0] for k in c.readers} >= set(NEW)
    assert not any(k.startswith("ragged_attn_roofline_pct")
                   for k in c.readers)
    m = c.model
    assert (m["num_hidden_layers"], m["num_dense_layers"]) == (9, 1)
    # the cut is the published list's entries 1-9, every width published
    assert m["layer_types"] == c.config["layer_types"][1:10]
    assert (m["hidden_size"], m["num_experts"], m["num_experts_per_tok"],
            m["moe_intermediate_size"], m["intermediate_size"],
            m["vocab_size"]) == (2048, 64, 4, 1536, 11776, 65536)
    assert set(c.config["reduced"]) == {"num_hidden_layers",
                                        "num_dense_layers", "layer_types"}
    geo = c.workload["engine"]
    assert geo["max_batch"] == 64 and geo["prompt_buckets"] == [32, 128, 1024]
    batch = manifest.Cell(manifest.load_manifest(), "qwen15moe-serve-batch")
    assert "moe_experts_ms_per_tick.batch" in batch.readers


def test_the_cells_sizes_by_hand():
    """The configuration file's ``sizes`` against the family's count."""
    c = cell()
    fam = manifest.load_family("lfm2_moe")
    sizes = c.config["sizes"]
    D = 2048
    assert sizes["params_one_expert"] == 3 * D * 1536 == 9437184
    assert sizes["params_conv_operator"] == D * 3 * D + D * D + 3 * D + D
    assert sizes["params_attention_operator"] == (
        2 * D * D + 2 * D * 512 + 2 * 64 + D)
    assert fam.param_count(c.model) == sizes["params_cell_9_layers"]
    assert fam.param_count(c.config) == sizes["params_whole_model_own_head"]
    kinds = fam.layer_kinds(c.model)
    assert kinds[0] == ("conv", "dense")
    assert [op for op, _ in kinds].count("full_attention") == 2


def test_bytes_and_flops_by_hand():
    c = cell()
    m = c.model
    rd = {k.rsplit(".", 1)[0]: v for k, v in c.readers.items()}
    moe = rd["moe_experts_roofline_pct"]
    # 8 expert layers x 64 experts x 9 437 184 parameters x 2 bytes
    assert moe.expert_layers(m) == 8
    assert moe.held_bytes(m) == 8 * 64 * 9437184 * 2 == 9663676416
    # a row reaches 4 experts: 2 FLOP a multiply-add over an expert
    assert moe.dropless_flops(m, 64) == 8 * 4 * 64 * 2 * 9437184
    peak = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    # 64 decode rows: reading the experts (11.8 ms) is the longer
    assert moe.least_seconds(m, 1, 64, peak) == pytest.approx(
        9663676416 / 819e9)
    # 100 000 rows in a tick: the FLOPs are
    assert moe.least_seconds(m, 1, 100000, peak) == pytest.approx(
        8 * 4 * 100000 * 2 * 9437184 / 197e12)
    attn = rd["attn_layers_roofline_pct"]
    # 2 attention layers x K and V x 8 heads x 64 x 2 bytes = 4 KiB a token
    assert attn.attention_layers(m) == 2
    assert attn.kv_bytes(m, 1000) == 1000 * 4096
    conv = rd["shortconv_ms_per_tick"]
    assert conv.conv_layers(m) == 7
    # in_proj 2048 x 6144 and out_proj 2048 x 2048 at 2 FLOP, 3 taps and
    # 2 gates: (2 x 3 + 2) x 2048 elementwise
    assert conv.shortconv_flops(m, 64) == 64 * (
        2 * 2048 * 6144 + 2 * 2048 * 2048 + 8 * 2048)
    assert conv.shortconv_bytes(m, 64, 64) == 2 * (
        2048 * 6144 + 2048 * 2048 + 2048 * 3 + 2048
        + 2 * 64 * 2048 + 2 * 64 * 2 * 2048)


def test_the_new_readers_on_a_reduced_trace():
    hs = {"idle_by_phase": {}, "phases": [],
          "by_label": {"shortconv.in": 3e6, "shortconv.mix": 2e6,
                       "conv_state.write": 1e6, "shortconv.out": 2e6,
                       "moe.experts": 64e6, "moe.router": 5e6},
          "tick_by_label": {"ragged_attn.kernel": 2e6},
          "tick_stats": {"rows": 300, "rows_real": 256, "kv_tokens": 100000}}
    c = cell()
    got = {k.rsplit(".", 1)[0]: r.read(ctx_with(hs, c.model))
           for k, r in c.readers.items() if k.rsplit(".", 1)[0] in NEW}
    assert got["shortconv_ms_per_tick"] == pytest.approx(8.0 / 4)
    assert got["moe_experts_ms_per_tick"] == pytest.approx(64.0 / 4)
    # 4 ticks x 9 663 676 416 B at 819e9 B/s = 47.2 ms of the 64 ms
    assert got["moe_experts_roofline_pct"] == pytest.approx(
        100 * 4 * 9663676416 / 819e9 / 64e-3)
    # 100 000 tokens x 4096 B at 819e9 B/s = 0.5 ms of the kernel's 2 ms
    assert got["attn_layers_roofline_pct"] == pytest.approx(
        100 * 100000 * 4096 / 819e9 / 2e-3)
    assert all(v < 100 for k, v in got.items() if k.endswith("_pct"))


@pytest.mark.parametrize("hostspans", [
    None,
    {"idle_by_phase": {}, "by_label": {"xla:copy": 5, "moe.router": 9},
     "tick_by_label": {},
     "tick_stats": {"rows": None, "rows_real": None, "kv_tokens": None}},
], ids=["no-device-plane", "no-scope-no-annotation"])
def test_a_new_reader_returns_none_where_there_is_nothing_to_read(hostspans):
    """As a program without the scopes gives: the parent commit."""
    c = cell()
    for name, reader in c.readers.items():
        if name.rsplit(".", 1)[0] in NEW:
            assert reader.read(ctx_with(hostspans, c.model)) is None, name
    # and on a model without experts or a layer pattern
    dense = {"num_hidden_layers": 16, "num_key_value_heads": 8,
             "head_dim": 128}
    hs = {"by_label": {"moe.experts": 5e6}, "tick_by_label": {
        "ragged_attn.kernel": 2e6}, "tick_stats": {
            "rows": 3, "rows_real": 2, "kv_tokens": 10}}
    for name in ("moe_experts_roofline_pct", "attn_layers_roofline_pct"):
        assert manifest.load_reader(name).read(ctx_with(hs, dense)) is None


def test_tiny_rehearsal_of_the_family_through_serve_closed(tmp_path):
    """A manifest of its own in a temporary directory (the tiny
    configuration with all three kinds of layer over six layers), run on
    the CPU through the functions a chip run uses: correct against the
    family's reference, two bypasses counted by the warm-up's prompt
    sent twice, no prefix hit."""
    from harness import modes
    from harness.common import require_devices
    bench = tmp_path / "bench"
    for d in ("configs", "workloads", "traffic"):
        (bench / d).mkdir(parents=True)
    shutil.copy(os.path.join(HERE, "tiny", "configs", "tiny-lfm2.json"),
                bench / "configs" / "tiny-lfm2.json")
    (bench / "traffic" / "tiny-generate.json").write_text(json.dumps({
        "loop": "closed", "clients_per_slot": 2, "shared_prefix": None,
        "prompt_tokens": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                          "min": 4, "max": 60},
        "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.4,
                          "min": 3, "max": 16},
        "greedy": True, "order_seed": 0}))
    (bench / "workloads" / "tiny-lfm2-closed.json").write_text(json.dumps({
        "mode": "serve_closed",
        "overrides": {"num_hidden_layers": 6},
        "engine": {"max_batch": 4, "page_size": 8, "max_prompt_len": 64,
                   "max_new_tokens_cap": 16, "prompt_buckets": [16, 64],
                   "prefill_chunk": 16},
        "request_pool": 64, "drain_s": 30.0, "warm_prompt_tokens": 24,
        "check_requests": 3, "lead_in_s": 0.4,
        "limits": {"served_logit_gap_max": 0.001,
                   "served_logit_gap_mean": 0.0001}}))
    real = manifest.load_manifest()
    man = {**real,
           "paths": ["bench"],
           "configs": [{"name": "tiny-lfm2", "source": "none",
                        "file": "bench/configs/tiny-lfm2.json",
                        "reduced": ["num_hidden_layers", "num_dense_layers",
                                    "layer_types"], "why": "rehearsal"}],
           "workloads": [{"name": "tiny-lfm2-closed", "config": "tiny-lfm2",
                          "traffic": "tiny-generate", "chips": 1,
                          "why": "rehearsal"}],
           "end_to_end": [
               {**m, "workloads": ["tiny-lfm2-closed"]} if "workloads" in m
               else m for m in real["end_to_end"]
               if m["name"] in ("serve_tokens_per_s", "setup_s")],
           "per_layer": [{**m, "workloads": ["tiny-lfm2-closed"]}
                         for m in real["per_layer"]
                         if CELL in m.get("workloads", [])]}
    c = manifest.Cell(man, "tiny-lfm2-closed", str(tmp_path))
    assert len(c.readers) == 14
    devs = require_devices(1, "cpu")
    args = argparse.Namespace(seed=2**31 + 9, seconds=1.5, trace=0)
    out = json.loads(modes.MODES[c.mode](c, args, devs, time.perf_counter()))
    assert out["correct"] is True and out["failed"] == 0
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert out["compared"]["compiles_in_window"][0] == 0
