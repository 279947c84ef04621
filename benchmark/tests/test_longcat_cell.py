"""The cell ``longcat-serve-longprompt`` and what it adds: its manifest
entries resolved through the loader, the published keys verbatim, the
configuration's sizes and the new readers' byte and FLOP functions
against numbers worked by hand here, the readers on hand-made ``ctx``s,
and a tiny CPU rehearsal of the family through ``serve_closed``.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_longcat_cell.py -q -p no:cacheprovider
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

CELL = "longcat-serve-longprompt"
NEW = ["mla_ms_per_tick", "mla_core_ms_per_tick", "mla_core_roofline_pct",
       "moe_share_roofline_pct"]


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
    assert c.entry["traffic"] == "longprompt"
    assert {m["name"] for m in c.end_to_end} == {"serve_tokens_per_s",
                                                 "setup_s"}
    assert len(c.readers) == 14
    assert all(k.endswith(".longprompt") for k in c.readers)
    assert {k.rsplit(".", 1)[0] for k in c.readers} >= set(NEW)
    # not the C = N cells' expert roofline: it counts k x rows pairs
    assert "moe_experts_roofline_pct.longprompt" not in c.readers
    m = c.model
    assert (m["num_layers"], m["n_routed_experts"], m["vocab_size"],
            m["router_experts"], m["zero_expert_num"], m["moe_topk"]) == (
        4, 16, 16384, 512, 256, 12)
    assert set(c.workload["overrides"]) == set(c.config["reduced"]) == {
        "num_layers", "n_routed_experts", "vocab_size"}
    fam = manifest.load_family("longcat_flash")
    assert fam.deployment(m) == (32, 0) and fam.held(m) == (0, 16)
    assert fam.routed_experts(m) + m["zero_expert_num"] == 768
    # un-overridden the file states the uncut model
    assert fam.deployment(c.config) == (1, 0)
    geo = c.workload["engine"]
    assert (geo["max_batch"], geo["max_prompt_len"],
            geo["max_new_tokens_cap"], geo["prefill_chunk"]) == (
        48, 16384, 1024, 512)
    tr = c.traffic
    assert tr["loop"] == "closed" and tr["clients_per_slot"] == 2
    # the issue's fallback maximum (traffic file, prompt_max_note)
    assert tr["prompt_tokens"] == {"dist": "lognormal", "median": 4096,
                                   "sigma": 0.6, "min": 512, "max": 8192}
    assert tr["output_tokens"] == {"dist": "lognormal", "median": 384,
                                   "sigma": 0.5, "min": 64, "max": 1024}
    for k in ("reduced", "assumed", "departures", "deployment"):
        assert c.config[k], k


def test_the_published_keys_are_verbatim():
    """Every number of the catalog's entry, under the same key."""
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(rows):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(rows))
               if r["name"] == "LongCat-Flash-Chat")
    c = cell()
    assert c.config["source"] == row["source_url"]
    for k, v in row["config"].items():
        assert c.config[k] == v, k


def test_the_cells_sizes_by_hand():
    c = cell()
    fam = manifest.load_family("longcat_flash")
    s, D = c.config["sizes"], 6144
    # matrices 90 570 752 and the sublayer's three norms
    assert s["params_mla_sublayer"] == (
        D * 1536 + 1536 * 64 * 192 + D * 576 + 512 * 64 * 256 + 64 * 128 * D
        + D + 1536 + 512) == 90578944
    assert s["params_dense_swiglu"] == 3 * D * 12288 + D == 226498560
    assert s["params_router_and_bias"] == D * 768 + 768 == 4719360
    assert s["params_one_expert"] == 3 * D * 2048 == 37748736
    assert s["params_16_experts"] == 16 * 37748736 == 603979776
    assert s["params_layer_outside_experts"] == 638874368
    assert s["params_embed_plus_head_eighth"] == 2 * 16384 * D
    assert fam.param_count(c.model) == s["params_cell_4_layers"] \
        == 5172749312
    # as run: 4 layers, 16 experts a layer, an eighth of the vocabulary
    n = fam.param_count(c.model)
    assert 5.17e9 < n < 5.18e9 and 9.6 < 2 * n / 2 ** 30 < 9.7
    assert s["latent_bytes_per_token_per_sublayer_published"] == 2 * 576


def test_bytes_and_flops_by_hand():
    c = cell()
    rd = {k.rsplit(".", 1)[0]: v for k, v in c.readers.items()}
    core = rd["mla_core_roofline_pct"]
    assert core.sublayers(c.model) == 8 and core.row_bytes(c.model) == 1152
    # a pair a head: a 576-deep score and a 512-deep value dot
    assert core.absorbed_flops(c.model, 1) == 64 * (576 + 512) * 2
    # kv_b on a context token: 512 x 64 x 256 multiply-adds
    assert core.expanded_flops(c.model, 0, 1) == 512 * 64 * 256 * 2
    assert core.expanded_flops(c.model, 1, 0) == 64 * 320 * 2
    # the issue's two numbers a context token a 512-row span
    assert core.expanded_flops(c.model, 512, 1) == pytest.approx(37.7e6,
                                                                 rel=0.01)
    assert core.absorbed_flops(c.model, 512) == pytest.approx(71.3e6,
                                                              rel=0.01)
    share = rd["moe_share_roofline_pct"]
    assert share.expert_params(c.model) * 2 == 75497472


def test_the_new_readers_on_a_reduced_trace():
    hs = {"idle_by_phase": {}, "phases": [],
          "by_label": {"attn.mla.q": 3e6, "attn.mla.kv": 1e6,
                       "attn.mla.core": 4e6, "attn.mla.core.kernel": 80e6,
                       "moe.experts": 24e6, "mlp": 60e6},
          "tick_by_label": {"attn.mla.core": 4e6,
                            "attn.mla.core.kernel": 80e6},
          "tick_stats": {"rows": 2240, "rows_real": 2200,
                         "kv_tokens": 400000}}
    c = cell()
    # a tick: 45 decode rows over 2300 tokens each + a 512-row span
    # whose last row sees 4096: bytes 105 596 tokens, pairs 45 x 2300 +
    # 512 x (4096 - 255.5)
    pairs = 45 * 2300 + 512 * (4096 - 255.5)
    kv = 45 * 2300 + 4096
    ticks = [(0, 10, {"kv_tokens": kv, "attn_pairs": pairs})] * 4
    ctx = ctx_with(hs, c, mla_ticks=ticks)
    ctx["window"]["counters"] = {"decode_steps": 400,
                                 "moe_experts_touched": 400 * 64,
                                 "moe_pairs_held": 400 * 560}
    got = {k.rsplit(".", 1)[0]: r.read(ctx)
           for k, r in c.readers.items() if k.rsplit(".", 1)[0] in NEW}
    assert got["mla_ms_per_tick"] == pytest.approx(88.0 / 4)
    assert got["mla_core_ms_per_tick"] == pytest.approx(80.0 / 4)
    core = c.readers["mla_core_roofline_pct.longprompt"]
    flops = min(core.absorbed_flops(c.model, pairs),
                core.expanded_flops(c.model, pairs, kv))
    # arithmetic leads: 8 sublayers x the lesser form over 197 TFLOP/s
    assert 8 * flops / 197e12 > 8 * kv * 1152 / 819e9
    assert got["mla_core_roofline_pct"] == pytest.approx(
        100 * 4 * 8 * flops / 197e12 / 84e-3)
    # 4 traced ticks of 400: 64 experts a tick x 75.5 MB at 819 GB/s =
    # 5.9 ms a tick of the 6 ms under moe.experts
    assert got["moe_share_roofline_pct"] == pytest.approx(
        100 * 4 * 64 * 75497472 / 819e9 / 24e-3)
    assert all(0 < v < 100 for k, v in got.items() if k.endswith("_pct"))


@pytest.mark.parametrize("hostspans", [
    None,
    {"idle_by_phase": {}, "by_label": {"xla:copy": 5, "mlp": 9},
     "tick_by_label": {},
     "tick_stats": {"rows": None, "rows_real": None, "kv_tokens": None}},
], ids=["no-device-plane", "no-scope-no-annotation"])
def test_a_new_reader_returns_none_where_there_is_nothing_to_read(hostspans):
    """As a program from before PR 40 gives (no scope, no ``attn_pairs``
    on its annotations, no counters): None, never a raise."""
    c = cell()
    for name, reader in c.readers.items():
        if name.rsplit(".", 1)[0] in NEW:
            assert reader.read(ctx_with(hostspans, c, mla_ticks=None)) \
                is None, name
    hs = {"by_label": {"attn.mla.core.kernel": 5e6, "moe.experts": 5e6},
          "tick_by_label": {"attn.mla.core.kernel": 5e6},
          "tick_stats": {"rows": 3, "rows_real": 2, "kv_tokens": 10}}
    old_ticks = [(0, 10, {"kv_tokens": 10, "rows": 3})]
    assert c.readers["mla_core_roofline_pct.longprompt"].read(
        ctx_with(hs, c, mla_ticks=old_ticks)) is None
    assert c.readers["moe_share_roofline_pct.longprompt"].read(
        ctx_with(hs, c)) is None
    dense = {"num_hidden_layers": 16, "num_key_value_heads": 8,
             "head_dim": 128}
    for name in ("mla_core_roofline_pct", "moe_share_roofline_pct"):
        assert manifest.load_reader(name).read(
            {**ctx_with(hs, c, mla_ticks=old_ticks), "model": dense}) is None


def test_tiny_rehearsal_of_the_family_through_serve_closed(tmp_path):
    """A manifest of its own in a temporary directory (the tiny
    configuration: 2 layers, chip 1 of 4 holding 8 of 32 routed experts
    beside 8 identity experts), run on the CPU through the functions a
    chip run uses: correct against the family's expanded reference, a
    prefix hit by the warm-up's prompt sent twice, the share's pairs
    counted and adding up."""
    from harness import modes
    from harness.common import require_devices
    bench = tmp_path / "bench"
    for d in ("configs", "workloads", "traffic"):
        (bench / d).mkdir(parents=True)
    shutil.copy(os.path.join(HERE, "tiny", "configs", "tiny-longcat.json"),
                bench / "configs" / "tiny-longcat.json")
    (bench / "traffic" / "tiny-longprompt.json").write_text(json.dumps({
        "loop": "closed", "clients_per_slot": 2, "shared_prefix": None,
        "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                          "min": 8, "max": 100},
        "output_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.4,
                          "min": 3, "max": 12},
        "greedy": True, "order_seed": 0}))
    (bench / "workloads" / "tiny-longcat-closed.json").write_text(json.dumps({
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
           "configs": [{"name": "tiny-longcat", "source": "none",
                        "file": "bench/configs/tiny-longcat.json",
                        "reduced": ["num_layers"], "why": "rehearsal"}],
           "workloads": [{"name": "tiny-longcat-closed",
                          "config": "tiny-longcat",
                          "traffic": "tiny-longprompt", "chips": 1,
                          "why": "rehearsal"}],
           "end_to_end": [
               {**m, "workloads": ["tiny-longcat-closed"]}
               if "workloads" in m else m for m in real["end_to_end"]
               if m["name"] in ("serve_tokens_per_s", "setup_s")],
           "per_layer": [{**m, "workloads": ["tiny-longcat-closed"]}
                         for m in real["per_layer"]
                         if CELL in m.get("workloads", [])]}
    c = manifest.Cell(man, "tiny-longcat-closed", str(tmp_path))
    assert len(c.readers) == 14
    devs = require_devices(1, "cpu")
    args = argparse.Namespace(seed=2**31 + 9, seconds=1.5, trace=0)
    out = json.loads(modes.MODES[c.mode](c, args, devs, time.perf_counter()))
    assert out["correct"] is True and out["failed"] == 0
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert out["compared"]["compiles_in_window"][0] == 0
