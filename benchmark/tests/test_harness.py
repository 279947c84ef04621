"""The harness's own tests. Run by hand and in the CPU rehearsal:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

(the repo's tier-1 command collects ``tests/`` only).
"""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import flops, manifest, trace, traffic  # noqa: E402

TINY = os.path.join(HERE, "tiny")


# ------------------------------------------------------------ reducer ----

EVENTS = [("fusion.1", 0, 100), ("fusion.2", 50, 100),      # overlap
          ("all-reduce.3", 200, 100), ("fusion.4", 250, 100),
          ("ragged_paged_attention.5", 500, 50),
          ("all-gather.6", 700, 100)]


def test_union_and_busy():
    assert trace.union([(0, 100), (50, 150), (200, 300)]) == [
        (0, 150), (200, 300)]
    r = trace.reduce_events(EVENTS, window=(0, 1000))
    assert r["busy_s"] == pytest.approx(450e-9)   # 0-150,200-350,500-550,700-800
    assert r["window_s"] == pytest.approx(1000e-9)


def test_gaps_longest_first():
    r = trace.reduce_events(EVENTS, window=(0, 1000), gaps=3)
    assert [g for _, g in r["longest_gaps"]] == pytest.approx(
        [200e-9, 150e-9, 150e-9])
    assert all(n == "unattributed" for n, _ in r["longest_gaps"])


def test_a_gap_takes_the_name_of_the_host_phase_that_covers_most_of_it():
    """``breakdown.idle_gaps``: gaps 350-500, 550-700, 800-1000 against
    hand-made phases."""
    phases = [("serving.phase." + n, s, e) for n, s, e in (
        ("readback", 340, 420), ("emit", 420, 440), ("admit", 440, 450),
        ("build", 450, 520), ("dispatch", 600, 640))]
    r = trace.reduce_events(EVENTS, window=(0, 1000), gaps=3, phases=phases)
    assert r["longest_gaps"] == [
        ["unattributed", pytest.approx(200e-9)],        # 800-1000: no phase
        ["serving.phase.dispatch", pytest.approx(150e-9)],  # 550-700
        # 350-500: readback 70, emit 20, admit 10, build 50
        ["serving.phase.readback", pytest.approx(150e-9)]]
    assert trace.gap_label((0, 10), None) == "unattributed"


def test_name_sums_and_top():
    r = trace.reduce_events(EVENTS, window=(0, 1000))
    assert trace.name_sum(r, r"ragged_paged_attention") == pytest.approx(50e-9)
    assert trace.name_sum(r, r"^fusion") == pytest.approx(250e-9)
    assert r["top_ops"][0][1] == pytest.approx(100e-9)


def test_exposed_collectives():
    r = trace.reduce_events(EVENTS, window=(0, 1000))
    # all-reduce 200-300 overlaps fusion.4 on 250-300: 50 exposed;
    # all-gather 700-800 is all exposed
    assert r["collective_s"] == pytest.approx(200e-9)
    assert r["collective_exposed_s"] == pytest.approx(150e-9)


def test_a_container_does_not_hide_the_collective_inside_it():
    evs = [("%while.1 = while()", 0, 1000),
           ("%fusion.2 = fusion()", 0, 400),
           ("%all-reduce.3 = all-reduce()", 400, 200),
           ("%fusion.4 = fusion()", 500, 500)]
    r = trace.reduce_events(evs, window=(0, 1000))
    assert r["collective_exposed_s"] == pytest.approx(100e-9)


def test_async_collective_brackets_count_as_collectives():
    evs = [("%fusion.1 = fusion()", 0, 100),
           ("%async-collective-start.2 = ()", 100, 30),
           ("%fusion.340 = fusion()", 130, 300),     # the gather fused in
           ("%async-collective-done.2 = ()", 430, 20)]
    r = trace.reduce_events(evs, window=(0, 500))
    assert r["collective_exposed_s"] == pytest.approx(50e-9)


def test_window_clips_events():
    r = trace.reduce_events(EVENTS, window=(100, 600))
    assert r["busy_s"] == pytest.approx((50 + 150 + 50) * 1e-9)


# ------------------------------------------------------------ traffic ----

CHAT = json.load(open(os.path.join(BENCH, "traffic", "chat.json")))


def test_traffic_same_seed_same_requests():
    a = traffic.build_requests(CHAT, 2**31 + 11, 50, 32768, rate=5.0)
    b = traffic.build_requests(CHAT, 2**31 + 11, 50, 32768, rate=5.0)
    assert all(np.array_equal(x.prompt, y.prompt) and x.due_s == y.due_s
               and x.max_new_tokens == y.max_new_tokens
               for x, y in zip(a, b))


def test_traffic_seeds_share_the_multiset_of_work():
    a = traffic.build_requests(CHAT, 1, 80, 32768, rate=5.0)
    b = traffic.build_requests(CHAT, 2, 80, 32768, rate=5.0)
    assert sorted(r.prompt.size for r in a) == sorted(
        r.prompt.size for r in b)
    assert sorted(r.max_new_tokens for r in a) == sorted(
        r.max_new_tokens for r in b)
    assert [r.prompt.size for r in a] != [r.prompt.size for r in b]
    pool = traffic.quantile_gaps(5.0, 80)
    assert pool.sum() == pytest.approx(80 / 5.0)
    for rs in (a, b):       # every gap is one of the fixed multiset
        d = np.diff([r.due_s for r in rs])
        assert np.abs(d[:, None] - pool[None]).min(1).max() < 1e-9


def test_traffic_due_times_fit_the_window():
    n = traffic.open_loop_count(5.0, 30.0)
    reqs = traffic.build_requests(CHAT, 3, n, 32768, rate=5.0)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 30.0
    assert np.mean(np.diff(due)) == pytest.approx(1 / 5.0, rel=0.05)
    lens = [r.prompt.size for r in reqs]
    assert min(lens) >= 32 and max(lens) <= 2048
    assert 450 < np.median(lens) < 580


# ----------------------------------------------------------- manifest ----

def test_manifest_finds_every_file_it_names():
    m = manifest.load_manifest()
    for w in m["workloads"]:
        cell = manifest.Cell(m, w["name"])
        assert cell.mode in ("serve_open", "serve_closed", "train")
        assert set(cell.readers) == {x["name"] for x in cell.per_layer}
        assert all(hasattr(r, "read") for r in cell.readers.values())
        assert cell.model["hidden_size"] == cell.config["hidden_size"]
    for c in m["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])


def test_added_files_are_found_without_an_edit():
    """A manifest of its own with one added reader, configuration, cell
    and traffic mix: files plus entries, nothing edited."""
    m = manifest.load_manifest(TINY)
    cell = manifest.Cell(m, "tiny-serve-open", TINY)
    assert "tiny_added_metric" in cell.readers
    assert cell.readers["tiny_added_metric"].read({"late_ms": [1, 3]}) == 3
    assert cell.model["num_hidden_layers"] == 2       # the override


def test_added_family_is_found_without_an_edit():
    """A family that only the test manifest holds, found through
    ``find_file`` like every other file; the benchmark's own families
    are still found from there, and its tables reach the trace's."""
    from harness import hostspans
    m = manifest.load_manifest(TINY)
    cell = manifest.Cell(m, "tiny-split-closed", TINY)
    assert cell.family.__file__ == os.path.join(TINY, "families",
                                                "tiny_split.py")
    assert callable(cell.family.reference_layers)
    dense = manifest.Cell(m, "tiny-serve-closed", TINY).family
    assert dense.__file__ == os.path.join(BENCH, "families",
                                          "dense_decoder.py")
    with pytest.raises(SystemExit):
        manifest.load_family("no_such_family", TINY)
    scopes, kernels = hostspans.tables(cell.family)
    assert scopes[:len(hostspans.SCOPES)] == hostspans.SCOPES
    assert scopes[-1] == "attn.split_latent"
    assert kernels == {**hostspans.KERNELS,
                       "attn.split_latent.kernel": "^split_latent_attention"}
    assert hostspans.tables(dense) == (hostspans.SCOPES, hostspans.KERNELS)


def _tiny_configs():
    import glob
    return sorted(glob.glob(os.path.join(TINY, "configs", "*.json")))


def test_every_family_has_a_tiny_configuration():
    """So that the test below runs every family's reference: a PR that
    adds ``families/<x>.py`` adds ``tests/tiny/configs/<y>.json`` of it."""
    import glob
    have = {os.path.basename(p)[:-3] for d in (BENCH, TINY)
            for p in glob.glob(os.path.join(d, "families", "*.py"))}
    assert have == {json.load(open(p))["family"] for p in _tiny_configs()}


@pytest.mark.parametrize("path", _tiny_configs(),
                         ids=lambda p: os.path.basename(p)[:-5])
def test_a_family_s_reference_runs_without_the_program(monkeypatch, path):
    """No reference code imports ``paddle_tpu``: with the package made
    unimportable, the family and the reference load anew and run, a
    forward pass and a training step, at the tiny size."""
    import importlib
    for k in [k for k in sys.modules if k.startswith("bench_family_")]:
        monkeypatch.delitem(sys.modules, k)
    for k in [k for k in sys.modules if k.split(".")[0] == "paddle_tpu"]:
        monkeypatch.delitem(sys.modules, k)
    monkeypatch.setitem(sys.modules, "paddle_tpu", None)
    with pytest.raises(ImportError):
        importlib.import_module("paddle_tpu.models")
    from harness import reference
    model = json.load(open(path))
    model["num_hidden_layers"] = 2
    family = manifest.load_family(model["family"], TINY)
    toks = np.arange(24, dtype=np.int32) * 5 % model["vocab_size"]
    h = reference.hidden_states(family.make_params(model, 3), toks, model,
                                family)
    logits = np.asarray(reference.logits_at(
        family.make_params(model, 3), h, np.arange(24), model))
    assert logits.shape == (24, model["vocab_size"])
    assert np.isfinite(logits).all()
    ref = reference.TrainReference(
        family.make_params(model, 3), model, family,
        {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
         "weight_decay": 0.1})
    loss = ref.step(toks[None, :-1], toks[None, 1:])
    assert np.isfinite(loss)
    change = ref.change_norms(family.make_params(model, 3))
    assert change.keys() == ref.grad_norms().keys()
    assert all(np.isfinite(v) and v > 0 for v in change.values())
    with pytest.raises(ImportError):
        family.program_config(model)


def test_override_outside_reduced_is_refused(tmp_path):
    m = manifest.load_manifest(TINY)
    import shutil
    root = tmp_path / "tiny"
    shutil.copytree(TINY, root)
    p = root / "workloads" / "tiny-serve-open.json"
    w = json.loads(p.read_text())
    w["overrides"]["hidden_size"] = 32
    p.write_text(json.dumps(w))
    with pytest.raises(SystemExit):
        manifest.Cell(m, "tiny-serve-open", str(root))


def test_benchmark_json_form():
    m = manifest.load_manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= 1
    names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(names) == len(set(names)) and "setup_s" in names
    e2e = {x["name"] for x in m["end_to_end"]}
    for x in m["per_layer"]:
        assert x["moves"] in e2e and set(x) <= {
            "name", "unit", "better", "source", "layer", "moves",
            "workloads"}
    for w in m["workloads"]:
        assert len(w["why"]) <= 200


# -------------------------------------------------------------- flops ----

def test_flops_agree_with_bench_count_params():
    import bench
    from types import SimpleNamespace
    model = json.load(open(os.path.join(BENCH, "configs",
                                        "mistral-7b-v0.3.json")))
    cfg = SimpleNamespace(
        hidden_size=model["hidden_size"], num_hidden_layers=5,
        vocab_size=model["vocab_size"],
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"],
        intermediate_size=model["intermediate_size"])
    model["num_hidden_layers"] = 5
    assert flops.matmul_params(model) == bench.count_params(cfg)


def test_self_time_takes_nesting_out():
    """A ``while`` holding the layer scan and the operations inside it
    sit on one line: by-name time must not count the body twice."""
    evs = [("%while.5 = (s32[]) while(...)", 0, 1000),
           ("%fusion.1 = bf16[8] fusion(...)", 100, 300),
           ("%ragged_paged_attention.6 = bf16[8] custom-call(...)", 500, 200),
           ("%fusion.1 = bf16[8] fusion(...)", 1200, 100)]
    r = trace.reduce_events(evs, window=(0, 1500))
    assert r["busy_s"] == pytest.approx(1100e-9)
    assert r["by_name_s"]["while.5"] == pytest.approx(500e-9)
    assert r["by_name_s"]["fusion.1"] == pytest.approx(400e-9)
    assert trace.name_sum(r, r"^ragged_paged_attention") == pytest.approx(
        200e-9)
    assert sum(r["by_name_s"].values()) == pytest.approx(r["busy_s"])
