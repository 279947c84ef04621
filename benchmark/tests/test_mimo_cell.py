"""The cell ``mimov2-serve-mixedlen`` and what it adds: its manifest
entries resolved through the loader, the published keys verbatim, the
configuration's sizes and the new readers' byte and FLOP functions
against numbers worked by hand here, the readers on hand-made ``ctx``s,
the reference with ``paddle_tpu`` made unimportable, the control shown to
fail, and a tiny CPU rehearsal of the family through ``serve_closed``.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_mimo_cell.py -q -p no:cacheprovider
"""
import argparse
import json
import os
import shutil
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

from harness import manifest  # noqa: E402

CELL = "mimov2-serve-mixedlen"
NEW = ["full_attn_ms_per_tick", "window_attn_ms_per_tick",
       "window_pool_write_ms_per_tick", "full_attn_roofline_pct",
       "window_attn_roofline_pct", "held_experts_roofline_pct"]
REDUCED = {"num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
           "n_routed_experts", "vocab_size"}


class Device:
    device_kind = "TPU v5 lite"


def cell():
    return manifest.Cell(manifest.load_manifest(), CELL)


def ctx_with(hostspans, c, **kw):
    return {"hostspans": hostspans, "model": c.model, "cell": c,
            "devices": [Device()],
            "window": {"trace_ticks": 4, "hists": {}, "counters": {}}, **kw}


def test_the_cell_resolves_through_the_loader():
    c = cell()
    assert c.mode == "serve_closed" and c.chips == 1
    assert c.entry["traffic"] == "mixedlen"
    assert {m["name"] for m in c.end_to_end} == {"serve_tokens_per_s",
                                                 "setup_s"}
    names = {k.rsplit(".mixedlen", 1)[0] for k in c.readers}
    assert set(NEW) <= names and len(c.readers) == 16 + 7
    # not LongCat's readers: its expert width has another key
    assert "moe_share_roofline_pct.longprompt" not in c.readers
    m = c.model
    assert (m["num_hidden_layers"], m["n_routed_experts"], m["vocab_size"],
            m["router_experts"], m["num_experts_per_tok"]) == (
        7, 16, 19072, 256, 8)
    assert m["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1]
    assert m["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    assert set(c.workload["overrides"]) == set(c.config["reduced"]) == REDUCED
    fam = manifest.load_family("mimo_v2_flash")
    assert fam.deployment(m) == (16, 0) and fam.held(m) == (0, 16)
    assert fam.counts(m) == {"full": 2, "window": 5, "dense": 1, "moe": 6}
    # un-overridden the file states the uncut model
    assert fam.deployment(c.config) == (1, 0)
    assert fam.counts(c.config) == {"full": 9, "window": 39, "dense": 1,
                                    "moe": 47}
    geo = c.workload["engine"]
    from harness.serve import GEOMETRY_KEYS
    assert set(geo) <= GEOMETRY_KEYS
    assert (geo["max_batch"], geo["page_size"], geo["total_pages"],
            geo["max_prompt_len"], geo["max_new_tokens_cap"],
            geo["prefill_chunk"]) == (48, 64, 48 * 272 + 1, 16384, 1024, 512)
    tr = c.traffic
    assert tr["loop"] == "closed" and tr["clients_per_slot"] == 2
    assert tr["prompt_tokens"]["median"] == 2048
    assert tr["prompt_tokens"]["sigma"] == 1.2
    assert tr["prompt_tokens"]["min"] == 128
    # the issue's fallback maximum (traffic file, prompt_max_note)
    assert tr["prompt_tokens"]["max"] == 8192
    assert tr["output_tokens"] == {"dist": "lognormal", "median": 512,
                                   "sigma": 0.5, "min": 128, "max": 1024}
    for k in ("reduced", "assumed", "departures", "deployment", "sizes"):
        assert c.config[k], k


def test_the_published_keys_are_verbatim():
    """Every key of the catalog's entry, under the same key."""
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(rows):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(rows))
               if r["name"] == "MiMo-V2-Flash")
    c = cell()
    assert c.config["source"] == row["source_url"]
    for k, v in row["config"].items():
        assert c.config[k] == v, k
    # no width is cut
    for k in ("hidden_size", "num_attention_heads", "head_dim", "v_head_dim",
              "num_key_value_heads", "swa_num_key_value_heads",
              "sliding_window", "moe_intermediate_size", "intermediate_size",
              "num_experts_per_tok"):
        assert c.model[k] == row["config"][k], k


def test_the_cells_sizes_by_hand():
    c = cell()
    fam = manifest.load_family("mimo_v2_flash")
    s, D = c.config["sizes"], 4096
    assert s["params_full_attention"] == (
        D * 64 * 192 + D * 4 * 192 + D * 4 * 128 + 64 * 128 * D) == 89128960
    assert s["params_window_attention_with_sinks"] == (
        D * 64 * 192 + D * 8 * 192 + D * 8 * 128 + 64 * 128 * D + 64
    ) == 94371904
    assert s["params_router_and_bias"] == D * 256 + 256
    assert s["params_one_expert"] == 3 * D * 2048 == 25165824
    assert s["params_16_experts"] == 402653184
    assert s["params_dense_swiglu"] == 3 * D * 16384
    assert s["params_embed_plus_head_eighth"] == 2 * 19072 * D
    n = fam.param_count(c.model)
    assert n == s["params_cell_7_layers"] == (
        2 * (89128960 + D) + 5 * (94371904 + D) + 201326592 + D
        + 6 * (1048832 + 402653184 + D) + 156237824 + D) == 3429955392
    # bfloat16 but for the routers, their biases and the sinks
    f32 = 6 * 1048832 + 5 * 64
    assert s["bytes_cell_7_layers"] == 2 * (n - f32) + 4 * f32
    assert 6.3 < s["bytes_cell_7_layers"] / 2 ** 30 < 6.5
    # the two caches: published bytes, and as the pools hold a key row
    assert s["kv_bytes_per_token_full_layer_published"] == 4 * 320 * 2
    assert s["kv_bytes_per_token_window_layer_published"] == 8 * 320 * 2
    assert s["window_ring_pages_per_slot"] == -(-(127 + 512) // 64) + 1 == 11
    assert s["window_pool_bytes_48_slots_published"] == (
        5 * 8 * 320 * 2 * 704 * 48)
    assert s["full_pool_bytes_13057_pages_published"] == (
        2 * 4 * 320 * 2 * 64 * 13057)
    # held as full layers the window layers would need 21.4 GB more
    assert 5 * 8 * 320 * 2 * 48 * 17408 == pytest.approx(21.4e9, rel=0.01)


def test_bytes_and_flops_by_hand():
    c = cell()
    rd = {k.rsplit(".", 1)[0] if k.endswith(".mixedlen") else k: v
          for k, v in c.readers.items()}
    full = rd["full_attn_roofline_pct"]
    assert full.kind_layers(c.model, False) == 2
    assert full.kind_layers(c.model, True) == 5
    assert full.token_bytes(c.model, False) == 4 * (192 + 128) * 2 == 2560
    assert full.token_bytes(c.model, True) == 8 * (192 + 128) * 2 == 5120
    assert full.pair_flops(c.model) == 64 * 320 * 2
    peak = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    # a decode tick: 48 rows over 4000 tokens each: bytes lead
    t = [(0, 1, {"kv_tokens": 48 * 4000, "attn_pairs": 48 * 4000,
                 "window_kv_tokens": 48 * 128,
                 "window_attn_pairs": 48 * 128})]
    assert full.least_seconds(c.model, t, peak, False) == pytest.approx(
        2 * 48 * 4000 * 2560 / 819e9)
    assert full.least_seconds(c.model, t, peak, True) == pytest.approx(
        5 * 48 * 128 * 5120 / 819e9)
    # a 512-row span over 4096 keys: arithmetic leads
    pairs = 512 * (4096 - 255.5)
    t = [(0, 1, {"kv_tokens": 4096, "attn_pairs": pairs,
                 "window_kv_tokens": 639, "window_attn_pairs": 512 * 128})]
    assert full.least_seconds(c.model, t, peak, False) == pytest.approx(
        2 * pairs * 64 * 320 * 2 / 197e12)
    assert full.least_seconds(c.model, t, peak, True) == pytest.approx(
        5 * 512 * 128 * 64 * 320 * 2 / 197e12)
    held = rd["held_experts_roofline_pct"]
    assert held.expert_params(c.model) * 2 == 50331648


def test_the_new_readers_on_a_reduced_trace():
    hs = {"idle_by_phase": {}, "phases": [],
          "by_label": {"attn.full": 6e6, "attn.full.kernel": 10e6,
                       "attn.window": 12e6, "attn.window.kernel": 8e6,
                       "window_pool.write": 2e6, "moe.experts": 40e6,
                       "mlp": 9e6},
          "tick_by_label": {"attn.full.kernel": 10e6,
                            "attn.window.kernel": 8e6},
          "tick_stats": {"rows": 2240, "rows_real": 2200,
                         "kv_tokens": 800000}}
    c = cell()
    tick = {"kv_tokens": 45 * 4000 + 4096,
            "attn_pairs": 45 * 4000 + 512 * (4096 - 255.5),
            "window_kv_tokens": 45 * 128 + 639,
            "window_attn_pairs": 45 * 128 + 512 * 128}
    ctx = ctx_with(hs, c, attn_ticks=[(0, 10, tick)] * 4)
    ctx["window"]["counters"] = {"decode_steps": 400,
                                 "moe_experts_touched": 400 * 90,
                                 "moe_pairs_held": 400 * 280}
    got = {k.rsplit(".mixedlen", 1)[0]: r.read(ctx)
           for k, r in c.readers.items()
           if k.rsplit(".mixedlen", 1)[0] in NEW}
    assert got["full_attn_ms_per_tick"] == pytest.approx(16.0 / 4)
    assert got["window_attn_ms_per_tick"] == pytest.approx(20.0 / 4)
    assert got["window_pool_write_ms_per_tick"] == pytest.approx(2.0 / 4)
    # 45 decode rows over 4000 tokens beside one span: the bytes lead
    least = 2 * tick["kv_tokens"] * 2560 / 819e9
    assert least > 2 * tick["attn_pairs"] * 64 * 320 * 2 / 197e12
    assert got["full_attn_roofline_pct"] == pytest.approx(
        100 * 4 * least / 10e-3)
    # the window layers read 6399 keys of 5120 B a layer: bytes again
    assert got["window_attn_roofline_pct"] == pytest.approx(
        100 * 4 * 5 * tick["window_kv_tokens"] * 5120 / 819e9 / 8e-3)
    # 4 traced ticks of 400: 90 experts a tick x 50.3 MB at 819 GB/s
    assert got["held_experts_roofline_pct"] == pytest.approx(
        100 * 4 * 90 * 50331648 / 819e9 / 40e-3)
    assert all(0 < v < 100 for k, v in got.items() if k.endswith("_pct"))


@pytest.mark.parametrize("hostspans", [
    None,
    {"idle_by_phase": {}, "by_label": {"xla:copy": 5, "mlp": 9},
     "tick_by_label": {},
     "tick_stats": {"rows": None, "rows_real": None, "kv_tokens": None}},
], ids=["no-device-plane", "no-scope-no-annotation"])
def test_a_new_reader_returns_none_where_there_is_nothing_to_read(hostspans):
    """As a program from before PR 47 gives (no scope, no window counts
    on its annotations, no counters): None, never a raise."""
    c = cell()
    for name, reader in c.readers.items():
        if name.rsplit(".mixedlen", 1)[0] in NEW:
            assert reader.read(ctx_with(hostspans, c, attn_ticks=None)) \
                is None, name
    hs = {"by_label": {"attn.window.kernel": 5e6, "moe.experts": 5e6},
          "tick_by_label": {"attn.window.kernel": 5e6,
                            "attn.full.kernel": 5e6},
          "tick_stats": {"rows": 3, "rows_real": 2, "kv_tokens": 10}}
    old_ticks = [(0, 10, {"kv_tokens": 10, "attn_pairs": 10, "rows": 3})]
    assert c.readers["window_attn_roofline_pct"].read(
        ctx_with(hs, c, attn_ticks=old_ticks)) is None
    assert c.readers["held_experts_roofline_pct"].read(
        ctx_with(hs, c)) is None
    dense = {"num_hidden_layers": 16, "num_key_value_heads": 8,
             "head_dim": 128}
    for name in ("full_attn_roofline_pct", "window_attn_roofline_pct",
                 "held_experts_roofline_pct"):
        assert manifest.load_reader(name).read(
            {**ctx_with(hs, c, attn_ticks=old_ticks), "model": dense}) is None


def tiny_model():
    return json.load(open(os.path.join(HERE, "tiny", "configs",
                                       "tiny-mimo.json")))


def test_the_reference_runs_without_the_program(monkeypatch):
    import importlib
    for k in [k for k in sys.modules if k.startswith("bench_family_")]:
        monkeypatch.delitem(sys.modules, k)
    for k in [k for k in sys.modules if k.split(".")[0] == "paddle_tpu"]:
        monkeypatch.delitem(sys.modules, k)
    monkeypatch.setitem(sys.modules, "paddle_tpu", None)
    with pytest.raises(ImportError):
        importlib.import_module("paddle_tpu.models")
    from harness import reference
    model = tiny_model()
    fam = manifest.load_family("mimo_v2_flash")
    params = fam.make_params(model, 3)
    groups = fam.reference_layers(params, model)
    # layer 0; window x 4; the full expert layer; the last window layer
    assert [g[0].__name__ for g in groups] == [
        "full_dense_layer", "window_moe_layer", "full_moe_layer",
        "window_moe_layer"]
    assert [g[1]["attn"]["wq"].shape[0] for g in groups] == [1, 4, 1, 1]
    assert "sinks" in groups[1][1]["attn"] and "sinks" not in groups[0][1][
        "attn"]
    toks = np.arange(40, dtype=np.int32) * 5 % model["vocab_size"]
    h = reference.hidden_states(params, toks, model, fam)
    logits = np.asarray(reference.logits_at(params, h, [39], model))
    assert logits.shape == (1, model["vocab_size"])
    assert np.isfinite(logits).all()


def test_the_control_fails_where_the_reference_passes():
    """The 3-bit control's first choice lies far below the reference's
    best where the reference's own is at zero: the comparison separates
    a lower precision at the tiny size too."""
    from harness import reference
    model = tiny_model()
    fam = manifest.load_family("mimo_v2_flash")
    params = fam.make_params(model, 3)
    toks = np.arange(64, dtype=np.int32) * 5 % model["vocab_size"]
    rows = np.arange(64)
    ref = np.asarray(reference.logits_at(
        params, reference.hidden_states(params, toks, model, fam), rows,
        model))
    low = np.asarray(reference.logits_at(
        params, reference.hidden_states(params, toks, model, fam,
                                        fam.CONTROL_ROUND_TO),
        rows, model, fam.CONTROL_ROUND_TO))
    gap = ref.max(-1) - ref[rows, low.argmax(-1)]
    assert gap.max() > 0.05 and gap.mean() > 0.005
    assert (ref.max(-1) - ref[rows, ref.argmax(-1)]).max() == 0.0


def test_tiny_rehearsal_of_the_family_through_serve_closed(tmp_path):
    """A manifest of its own in a temporary directory (the tiny
    configuration: 7 layers, chip 1 of 4 holding 8 of 32 experts, a
    window of 8 under prompts of 8-100), run on the CPU through the
    functions a chip run uses: correct against the family's reference,
    the window counts on the window, no prefix reuse."""
    from harness import modes
    from harness.common import require_devices
    bench = tmp_path / "bench"
    for d in ("configs", "workloads", "traffic"):
        (bench / d).mkdir(parents=True)
    shutil.copy(os.path.join(HERE, "tiny", "configs", "tiny-mimo.json"),
                bench / "configs" / "tiny-mimo.json")
    (bench / "traffic" / "tiny-mixedlen.json").write_text(json.dumps({
        "loop": "closed", "clients_per_slot": 2, "shared_prefix": None,
        "prompt_tokens": {"dist": "lognormal", "median": 30, "sigma": 1.0,
                          "min": 8, "max": 100},
        "output_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.4,
                          "min": 3, "max": 12},
        "greedy": True, "order_seed": 0}))
    (bench / "workloads" / "tiny-mimo-closed.json").write_text(json.dumps({
        "mode": "serve_closed", "overrides": {},
        "engine": {"max_batch": 4, "page_size": 8, "max_prompt_len": 104,
                   "max_new_tokens_cap": 12, "prompt_buckets": [16, 104],
                   "prefill_chunk": 16},
        "request_pool": 64, "drain_s": 30.0, "warm_prompt_tokens": 24,
        "check_requests": 3, "lead_in_s": 0.4,
        "limits": {"served_logit_gap_max": 0.001,
                   "served_logit_gap_mean": 0.0001}}))
    real = manifest.load_manifest()
    man = {**real,
           "paths": ["bench"],
           "configs": [{"name": "tiny-mimo", "source": "none",
                        "file": "bench/configs/tiny-mimo.json",
                        "reduced": ["num_hidden_layers"],
                        "why": "rehearsal"}],
           "workloads": [{"name": "tiny-mimo-closed", "config": "tiny-mimo",
                          "traffic": "tiny-mixedlen", "chips": 1,
                          "why": "rehearsal"}],
           "end_to_end": [
               {**m, "workloads": ["tiny-mimo-closed"]}
               if "workloads" in m else m for m in real["end_to_end"]
               if m["name"] in ("serve_tokens_per_s", "setup_s")],
           "per_layer": [{**m, "workloads": ["tiny-mimo-closed"]}
                         for m in real["per_layer"]
                         if CELL in m.get("workloads", [])]}
    c = manifest.Cell(man, "tiny-mimo-closed", str(tmp_path))
    assert len(c.readers) == 16 + 7
    devs = require_devices(1, "cpu")
    args = argparse.Namespace(seed=2**31 + 9, seconds=1.5, trace=0)
    out = json.loads(modes.MODES[c.mode](c, args, devs, time.perf_counter()))
    assert out["correct"] is True and out["failed"] == 0
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert out["compared"]["compiles_in_window"][0] == 0
