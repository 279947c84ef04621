"""The cell ``joyai-train-8k-ep8`` and what it adds: its manifest
entries resolved through the loader, the published keys verbatim, the
configuration's sizes and the new readers' FLOP and byte functions
against numbers worked by hand here, the family's reference with
``paddle_tpu`` made unimportable, and the six readers on hand-made
``ctx``s (none reads over 100 %).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_joyai_cell.py -q -p no:cacheprovider
"""
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

from harness import manifest  # noqa: E402

CELL = "joyai-train-8k-ep8"
NEW = ["share_train_mfu_pct", "mla_train_ms_per_step",
       "mla_splash_roofline_pct", "moe_experts_ms_per_step",
       "moe_router_ms_per_step", "moe_train_gmm_roofline_pct"]
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
# the published config, as the catalog row has it
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280}


class Device:
    device_kind = "TPU v5 lite"


def cell():
    return manifest.Cell(manifest.load_manifest(), CELL)


def test_the_cell_resolves_through_the_loader():
    c = cell()
    assert c.mode == "train" and c.chips == 1
    assert c.entry["traffic"] == "train-2x8192"
    assert {m["name"] for m in c.end_to_end} == {"train_tokens_per_s",
                                                 "setup_s"}
    assert set(NEW) <= set(c.readers)
    # the dense decoder's two shares count a GQA decoder from head_dim
    # and intermediate_size: not this cell's
    assert not {"train_mfu_pct", "splash_roofline_pct"} & set(c.readers)
    assert {"device_idle_pct.train", "optimizer_ms_per_step",
            "setup_train_init_s"} <= set(c.readers)
    for k, v in PUBLISHED.items():
        assert c.config[k] == v, k
    m = c.model
    assert (m["num_hidden_layers"], m["n_routed_experts"], m["vocab_size"],
            m["num_nextn_predict_layers"], m["router_experts"]) == (
        6, 32, 16160, 0, 256)
    assert set(c.workload["overrides"]) == set(c.config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"}
    assert c.workload["trainer"] == {"dp": 1, "tp": 1, "batch": 2,
                                     "seq_len": 8192, "strict_kernels": True}
    assert c.workload["kernels"] == ["splash_attention", "fused_rms_norm"]
    mistral = manifest.Cell(manifest.load_manifest(), "mistral7b-train-2k")
    assert c.workload["optimizer"] == mistral.workload["optimizer"]
    for k in ("reduced", "assumed", "departures", "deployment"):
        assert c.config[k], k
    fam = c.family
    assert fam.held(m) == (0, 32) and fam.held(c.config) == (0, 256)


def test_the_configuration_s_sizes():
    c = cell()
    fam, m, sizes = c.family, c.model, c.config["sizes"]
    assert fam.attention_params(m) == sizes["params_attention_layer"] == (
        2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
        + 32 * 128 * 2048)
    assert sizes["params_one_expert"] == 3 * 2048 * 768
    assert fam.param_count(m) == sizes["params_cell_6_layers"] == (
        sizes["params_dense_layer"] + 5 * sizes["params_expert_layer_32_held"]
        + sizes["params_embed_plus_head_eighth"] + 2048)
    assert sizes["params_expert_layer_whole"] == (
        sizes["params_expert_layer_outside_experts"]
        + 256 * sizes["params_one_expert"])
    # one held pair a token a layer: 3.22 GFLOP, by hand
    dense = (6 * fam.attention_params(m) + 3 * 2048 * 7168
             + 5 * (2048 * 256 + 3 * 2048 * 768) + 2048 * 16160)
    attn = 3 * 6 * 2 * 8192 * 32 * (192 + 128) / 2
    want = 6 * dense + attn + 5 * 18 * 2048 * 768
    assert fam.train_flops_per_token(m, 8192, 5.0) == pytest.approx(want)
    assert want / 1e9 == pytest.approx(
        sizes["model_gflop_per_token_at_one_held_pair_a_layer"])


def test_the_least_times_by_hand():
    c = cell()
    fam, m = c.family, c.model
    # splash, one layer, 2 x 8192: compute-bound both ways
    sq = 2 * 2 * 32 * 8192 * 8192 / 2
    fwd, bwd = sq * 320 / 197e12, sq * (3 * 192 + 2 * 128) / 197e12
    assert fam.splash_least_seconds(m, 2, 8192, PEAK) == pytest.approx(
        fwd + bwd)
    assert fwd + bwd == pytest.approx(0.0251, rel=0.01)
    # the grouped matmuls of 3 steps at 16 384 pairs a layer (512 rows
    # an expert, above the ridge of 240): the arithmetic is the longer;
    # at an eighth of the pairs the weights' traffic (5 x 32 experts x 3
    # matrices x 3 passes) is
    pairs = 3 * 5 * 16384.0
    flops_s = pairs * 18 * 2048 * 768 / 197e12
    bytes_s = 3 * 5 * 32 * 3 * 2048 * 768 * 2 * 3 / 819e9
    assert flops_s > bytes_s > flops_s / 8
    assert fam.gmm_least_seconds(m, pairs, 3, PEAK) == pytest.approx(flops_s)
    assert fam.gmm_least_seconds(m, pairs / 8, 3, PEAK) == pytest.approx(
        bytes_s)


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
    model = json.load(open(os.path.join(
        HERE, "tiny", "configs", "tiny-joyai.json")))
    fam = manifest.load_family("joyai_flash")
    assert [g[2] for g in fam.reference_layers(
        fam.make_params(model, 3), model)] == ["dense_layers", "layers"]
    toks = np.arange(40, dtype=np.int32) * 5 % model["vocab_size"]
    ref = reference.TrainReference(
        fam.make_params(model, 3), model, fam,
        {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
         "weight_decay": 0.1})
    loss = ref.step(toks[None, :-1], toks[None, 1:])
    assert np.isfinite(loss)
    names = set(ref.grad_norms())
    assert {"dense_layers.w_gate", "layers.router", "layers.router_bias",
            "layers.experts.w_down", "layers.shared.w_up", "embed"} <= names
    assert ref.grad_norms()["layers.router_bias"] == 0.0
    with_mtp = {**model, "num_nextn_predict_layers": 1}
    p = fam.make_params(with_mtp, 3)
    both = fam.loss_with_mtp(p, toks[None, :-1], toks[None, 1:], with_mtp)
    assert float(both) > loss + 1.0


# ------------------------------------------------------ the readers ----

def ctx_with(by_label, c, monkeypatch, pairs_a_step=5 * 16384.0, steps=4,
             by_name=None, tokens_per_s=25000.0):
    """A traced training run's ``ctx``: ``by_label`` ns of device self
    time over ``steps`` traced steps, the program's step counters holding
    ``pairs_a_step`` held pairs for each of 10 window steps."""
    from paddle_tpu.observability import step_counters
    reg = step_counters()
    reg.clear()
    t0 = time.perf_counter()
    reg.add("train", {"train_moe_pairs_held": 10**9}, at=time.monotonic() - 50)
    for _ in range(10):
        reg.add("train", {"train_moe_pairs_held": int(pairs_a_step),
                          "train_moe_pairs_absent": 0,
                          "train_moe_rows_padded": 0,
                          "train_moe_bound_fallbacks": 0})
    return {"hostspans": {"by_label": by_label}, "model": c.model, "cell": c,
            "devices": [Device()],
            "trace": {"by_name_s": by_name or {}, "busy_s": 1.0,
                      "window_s": 1.0},
            "train": {"trace_steps": steps, "tokens_per_s": tokens_per_s,
                      "tokens_per_step": 16384, "t0": t0 - 1.0, "steps": 10}}


def test_the_step_s_share_of_the_peak(monkeypatch):
    c = cell()
    ctx = ctx_with({}, c, monkeypatch)
    got = c.readers["share_train_mfu_pct"].read(ctx)
    want = 100 * 25000.0 * c.family.train_flops_per_token(
        c.model, 8192, 5.0) / 197e12
    assert got == pytest.approx(want) and 35 < got < 45
    # the window's records only: the one from before it is not counted
    ctx = ctx_with({}, c, monkeypatch, pairs_a_step=0.0)
    less = c.readers["share_train_mfu_pct"].read(ctx)
    assert less == pytest.approx(want * (3.22 - 0.1416) / 3.22, rel=1e-3)
    # a program that counts nothing: nothing to read
    from paddle_tpu.observability import step_counters
    step_counters().clear()
    assert c.readers["share_train_mfu_pct"].read(ctx) is None
    assert c.readers["moe_train_gmm_roofline_pct"].read(ctx) is None


def test_the_scope_readers(monkeypatch):
    c = cell()
    ms = 1e6
    by_label = {"attn.mla.q": 30 * ms, "attn.mla.kv": 10 * ms,
                "attn.mla.expand": 20 * ms, "attn.core": 8 * ms,
                "attn.core.kernel": 400 * ms, "attn.out": 12 * ms,
                "moe.experts": 40 * ms, "moe.experts.kernel": 60 * ms,
                "moe.router": 16 * ms, "mlp": 99 * ms}
    ctx = ctx_with(by_label, c, monkeypatch,
                   by_name={"splash_mha_fwd.1": 0.9, "splash_mha_dq.3": 0.5,
                            "fusion.7": 0.3})
    assert c.readers["mla_train_ms_per_step"].read(ctx) == pytest.approx(120)
    assert c.readers["moe_experts_ms_per_step"].read(ctx) == pytest.approx(25)
    assert c.readers["moe_router_ms_per_step"].read(ctx) == pytest.approx(4)
    # six layers x four steps x 25.1 ms least over 1.4 s of splash
    got = c.readers["mla_splash_roofline_pct"].read(ctx)
    assert got == pytest.approx(
        100 * 6 * 4 * c.family.splash_least_seconds(c.model, 2, 8192, PEAK)
        / 1.4)
    assert 40 < got < 46
    got = c.readers["moe_train_gmm_roofline_pct"].read(ctx)
    want = 100 * c.family.gmm_least_seconds(
        c.model, 4 * 5 * 16384.0, 4, PEAK) / 0.060
    assert got == pytest.approx(want) and got < 100
    # a step without the scopes: nothing to read, nothing raised
    empty = ctx_with({"mlp": 5 * ms}, c, monkeypatch)
    for name in ("mla_train_ms_per_step", "moe_experts_ms_per_step",
                 "moe_router_ms_per_step", "mla_splash_roofline_pct",
                 "moe_train_gmm_roofline_pct"):
        assert c.readers[name].read(empty) is None, name
