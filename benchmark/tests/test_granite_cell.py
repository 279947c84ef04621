"""The cell ``granite4h-serve-generate`` and what it adds: its manifest
entries resolved through the loader, the configuration's sizes and the
new readers' byte functions against numbers worked by hand here, the
readers on hand-made ``ctx``s, and a tiny CPU rehearsal of the family
through ``serve_closed``.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_granite_cell.py -q -p no:cacheprovider
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

CELL = "granite4h-serve-generate"
NEW = ["ssm_ms_per_tick", "ssm_scan_ms_per_tick", "ssm_scan_roofline_pct",
       "ssm_state_move_ms_per_tick", "hybrid_attn_roofline_pct"]


class Device:
    device_kind = "TPU v5 lite"


def cell():
    return manifest.Cell(manifest.load_manifest(), CELL)


def ctx_with(hostspans, c, **kw):
    return {"hostspans": hostspans, "model": c.model, "cell": c,
            "devices": [Device()], "window": {"trace_ticks": 4, "hists": {}},
            **kw}


def test_the_cell_resolves_through_the_loader():
    c = cell()
    assert c.mode == "serve_closed" and c.chips == 1
    assert c.entry["traffic"] == "generate"
    assert {m["name"] for m in c.end_to_end} == {"serve_tokens_per_s",
                                                 "setup_s"}
    assert len(c.readers) == 15
    assert {k.rsplit(".", 1)[0] for k in c.readers} >= set(NEW)
    assert not any(k.split("_")[0] in ("shortconv", "moe")
                   or k.startswith("attn_layers") for k in c.readers)
    # nothing cut: the model IS the configuration
    assert c.workload["overrides"] == {} and c.config["reduced"] == {}
    assert c.model == c.config
    m = c.model
    assert (m["num_hidden_layers"], m["hidden_size"], m["vocab_size"],
            m["shared_intermediate_size"]) == (40, 2048, 100352, 8192)
    assert [i for i, t in enumerate(m["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]
    geo = c.workload["engine"]
    assert geo == {"max_batch": 64, "page_size": 16, "max_prompt_len": 1024,
                   "max_new_tokens_cap": 1024,
                   "prompt_buckets": [32, 128, 1024], "prefill_chunk": 128}
    # the SAME traffic file and window keys as the LFM2 cell's
    other = manifest.Cell(manifest.load_manifest(), "lfm2moe-serve-generate")
    assert other.traffic == c.traffic
    for k in ("lead_in_s", "drain_s", "warm_prompt_tokens",
              "check_requests", "trace_after_s", "trace_seconds"):
        assert other.workload[k] == c.workload[k], k
    # but a pool of ONE cycle a run (what a run issues whose tokens fall
    # in the window), so that every seed's window holds the same lengths
    assert c.workload["request_pool"] == 256


def test_the_published_keys_are_verbatim():
    """Every number of the catalog's entry, under the same key."""
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(rows):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(rows))
               if r["name"] == "granite-4.0-h-micro")
    c = cell()
    assert c.config["source"] == row["source_url"]
    for k, v in row["config"].items():
        assert c.config[k] == v, k


def test_the_cells_sizes_by_hand():
    c = cell()
    fam = manifest.load_family("granite_hybrid")
    s = c.config["sizes"]
    D = 2048
    assert s["params_mamba_mixer"] == (
        D * (4096 + 4352 + 64) + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * D
    ) == 25847232
    assert s["params_swiglu"] == D * 16384 + 8192 * D == 50331648
    assert s["params_attention_layer"] == 60821504
    assert s["params_mamba_layer"] == 76182976
    assert s["params_embedding_tied"] == 100352 * D == 205520896
    assert fam.param_count(c.model) == s["params_whole_model_tied"] \
        == 3191396096
    assert s["bytes_whole_model_own_head_bf16"] == 2 * (
        3191396096 + 205520896)
    assert s["ssm_state_bytes_per_slot_per_mamba_layer"] == 2 * 2 ** 20


def test_bytes_by_hand():
    c = cell()
    rd = {k.rsplit(".", 1)[0]: v for k, v in c.readers.items()}
    scan = rd["ssm_scan_roofline_pct"]
    assert scan.mamba_layers(c.model) == 36
    # 64 live slots x 36 layers x 2 MiB, read once and written once
    assert scan.state_bytes(c.model, 64) == 64 * 36 * 2 * 2 * 2 ** 20 \
        == 9663676416
    attn = rd["hybrid_attn_roofline_pct"]
    # 4 attention layers x K and V x 8 heads x 64 x 2 bytes = 8 KiB a token
    assert attn.attention_layers(c.model) == 4
    assert attn.kv_bytes(c.model, 1000) == 1000 * 8192
    # the file that is there counts another type's name: 0 of this model
    assert manifest.load_reader("attn_layers_roofline_pct").attention_layers(
        c.model) == 0


def test_the_new_readers_on_a_reduced_trace():
    hs = {"idle_by_phase": {}, "phases": [],
          "by_label": {"ssm.in": 4e6, "ssm.conv": 2e6, "ssm.scan": 3e6,
                       "ssm.scan.kernel": 48e6, "ssm.out": 3e6, "mlp": 30e6},
          "tick_by_label": {"ragged_attn.kernel": 2e6,
                            "ssm.scan.kernel": 48e6},
          "tick_stats": {"rows": 300, "rows_real": 256, "kv_tokens": 100000}}
    c = cell()
    tick = ("serving.tick", 0, 10, {"live_slots": 64})
    # the device's operations of the span: the kernel, the algebra's
    # fusion and a copy under ssm.scan, a scope-less copy, a matmul
    scan = "jit(serving_tick)/layers/while/body/ssm.scan/"
    device = [("ssd_update.3", 0, 6e6, scan + "custom_call"),
              ("fusion.7", 6e6, 7e6, scan + "mul"),
              ("copy.9", 7e6, 9e6, scan + "copy"),
              ("copy.141", 9e6, 10e6, ""),
              ("fusion.1", 10e6, 12e6, "jit(serving_tick)/layers/mlp/dot")]
    ticks = [(t[1], t[2], t[3]) for t in [tick] * 4]
    ctx = ctx_with(hs, c, ssm_trace=(device, ticks))
    got = {k.rsplit(".", 1)[0]: r.read(ctx)
           for k, r in c.readers.items() if k.rsplit(".", 1)[0] in NEW}
    assert got["ssm_ms_per_tick"] == pytest.approx(60.0 / 4)
    assert got["ssm_scan_ms_per_tick"] == pytest.approx(48.0 / 4)
    # 4 ticks x 9 663 676 416 B at 819e9 B/s = 47.2 ms of the 48 ms
    assert got["ssm_scan_roofline_pct"] == pytest.approx(
        100 * 4 * 9663676416 / 819e9 / 48e-3)
    # the copy under ssm.scan (2 ms) and the scope-less one (1 ms)
    assert got["ssm_state_move_ms_per_tick"] == pytest.approx(3.0 / 4)
    # 100 000 tokens x 8192 B at 819e9 B/s = 1 ms of the kernel's 2 ms
    assert got["hybrid_attn_roofline_pct"] == pytest.approx(
        100 * 100000 * 8192 / 819e9 / 2e-3)
    assert all(v < 100 for k, v in got.items() if k.endswith("_pct"))


@pytest.mark.parametrize("hostspans", [
    None,
    {"idle_by_phase": {}, "by_label": {"xla:copy": 5, "mlp": 9},
     "tick_by_label": {},
     "tick_stats": {"rows": None, "rows_real": None, "kv_tokens": None}},
], ids=["no-device-plane", "no-scope-no-annotation"])
def test_a_new_reader_returns_none_where_there_is_nothing_to_read(hostspans):
    """As a program without the scopes gives; and where no trace file
    exists at all the readers that open it find none and do not raise."""
    c = cell()
    for name, reader in c.readers.items():
        if name.rsplit(".", 1)[0] in NEW:
            assert reader.read(ctx_with(hostspans, c)) is None, name
    hs = {"by_label": {"ssm.scan.kernel": 5e6}, "tick_by_label": {
        "ssm.scan.kernel": 5e6, "ragged_attn.kernel": 2e6}, "tick_stats": {
            "rows": 3, "rows_real": 2, "kv_tokens": 10}}
    for name in ("ssm_scan_roofline_pct", "ssm_state_move_ms_per_tick"):
        assert c.readers[name + ".generate"].read(
            ctx_with(hs, c, ssm_trace=(None, None))) is None
    dense = {"num_hidden_layers": 16, "num_key_value_heads": 8,
             "head_dim": 128}
    for name in ("ssm_scan_roofline_pct", "hybrid_attn_roofline_pct"):
        assert manifest.load_reader(name).read(
            {**ctx_with(hs, c), "model": dense}) is None


def test_tiny_rehearsal_of_the_family_through_serve_closed(tmp_path):
    """A manifest of its own in a temporary directory (the tiny
    configuration: 12 layers, three periods of its pattern), run on the
    CPU through the functions a chip run uses: correct against the
    family's sequential reference (whose logits are 8 x the published
    scale: the limits are in that unit), two bypasses counted by the
    warm-up's prompt sent twice, the state's traffic counted."""
    from harness import modes
    from harness.common import require_devices
    bench = tmp_path / "bench"
    for d in ("configs", "workloads", "traffic"):
        (bench / d).mkdir(parents=True)
    shutil.copy(os.path.join(HERE, "tiny", "configs", "tiny-granite.json"),
                bench / "configs" / "tiny-granite.json")
    (bench / "traffic" / "tiny-generate.json").write_text(json.dumps({
        "loop": "closed", "clients_per_slot": 2, "shared_prefix": None,
        "prompt_tokens": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                          "min": 4, "max": 60},
        "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.4,
                          "min": 3, "max": 16},
        "greedy": True, "order_seed": 0}))
    (bench / "workloads" / "tiny-granite-closed.json").write_text(json.dumps({
        "mode": "serve_closed", "overrides": {},
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
           "configs": [{"name": "tiny-granite", "source": "none",
                        "file": "bench/configs/tiny-granite.json",
                        "reduced": ["num_hidden_layers"],
                        "why": "rehearsal"}],
           "workloads": [{"name": "tiny-granite-closed",
                          "config": "tiny-granite",
                          "traffic": "tiny-generate", "chips": 1,
                          "why": "rehearsal"}],
           "end_to_end": [
               {**m, "workloads": ["tiny-granite-closed"]}
               if "workloads" in m else m for m in real["end_to_end"]
               if m["name"] in ("serve_tokens_per_s", "setup_s")],
           "per_layer": [{**m, "workloads": ["tiny-granite-closed"]}
                         for m in real["per_layer"]
                         if CELL in m.get("workloads", [])]}
    c = manifest.Cell(man, "tiny-granite-closed", str(tmp_path))
    assert len(c.readers) == 15
    devs = require_devices(1, "cpu")
    args = argparse.Namespace(seed=2**31 + 9, seconds=1.5, trace=0)
    out = json.loads(modes.MODES[c.mode](c, args, devs, time.perf_counter()))
    assert out["correct"] is True and out["failed"] == 0
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert out["compared"]["compiles_in_window"][0] == 0
