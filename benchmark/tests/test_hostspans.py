"""``harness/hostspans.py`` on hand-made events, the new per-layer
entries resolved through the manifest loader, and the readers on traces
that lack what they read.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_hostspans.py -q -p no:cacheprovider
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import hostspans as H  # noqa: E402
from harness import manifest  # noqa: E402

NEW = ["tick_host_ms", "idle_launch_ms_per_tick",
       "idle_readback_ms_per_tick", "idle_emit_ms_per_tick",
       "kv_pool_move_ms_per_tick", "ragged_attn_roofline_pct",
       "tick_row_fill_pct"]


def phase(name, start, end, tick):
    return (H.PHASE + name, start, end, {"tick": tick})


# two ticked iterations (tick 7: 0-100, tick 8: 100-200) and an idle poll
# between whose admit/build share tick number 9 with nothing that ran
ANNOTATIONS = [
    phase("admit", 0, 9, 7), phase("build", 10, 29, 7),
    phase("dispatch", 30, 39, 7), phase("readback", 40, 89, 7),
    phase("emit", 90, 100, 7),
    (H.TICK, 31, 90, {"tick": 7, "rows": 40, "rows_real": 10,
                      "kv_tokens": 1000}),
    phase("admit", 100, 110, 8), phase("build", 110, 130, 8),
    phase("dispatch", 130, 140, 8), phase("readback", 140, 190, 8),
    phase("emit", 190, 200, 8),
    (H.TICK, 131, 191, {"tick": 8, "rows": 40, "rows_real": 30,
                        "kv_tokens": 3000}),
    phase("admit", 200, 205, 9), phase("build", 205, 210, 9),
    (H.TICK, 231, 400, {"tick": 9, "rows": 8, "rows_real": 8,
                        "kv_tokens": 5}),
]


def test_idle_is_split_over_the_phases_of_ticked_iterations():
    device = [("fusion.1", 35, 80), ("copy.2", 80, 84), ("fusion.1", 138, 185)]
    idle = H.idle_intervals(device, (35, 215))
    assert idle == [(84, 138), (185, 215)]
    phases = H.ticked_phases(ANNOTATIONS)
    assert [p[0] for p in phases] == [
        "admit", "build", "dispatch", "readback", "emit"] * 2
    # contiguous inside a tick: admit 0-9 is stretched to build's start
    assert phases[0][1:3] == (0, 10) and phases[3][1:3] == (40, 90)
    by = H.idle_by_phase(idle, phases)
    # 84-90 readback, 90-100 emit | 100-110 admit, 110-130 build,
    # 130-138 dispatch | 185-190 readback, 190-200 emit | 200-215 none
    assert by == {"readback": 6 + 5, "emit": 10 + 10, "admit": 10,
                  "build": 20, "dispatch": 8, "none": 15}
    assert sum(by.values()) == sum(e - s for s, e in idle)


def test_a_stat_is_summed_over_the_whole_ticks_in_the_window():
    ticks = H.whole_ticks(ANNOTATIONS, (20, 215))
    assert [st["tick"] for _, _, st in ticks] == [7, 8]    # 9 is cut off
    assert H.stat_sum(ticks, "kv_tokens") == 4000
    assert H.stat_sum(ticks, "rows_real") == 40
    assert H.stat_sum(ticks, "absent") is None
    assert H.stat_sum([], "rows") is None


def test_self_time_by_scope_with_a_nested_while():
    top = "jit(serving_tick)/"
    body = top + "layers/while/body/closed_call/"
    device = [
        ("fusion.9", 0, 10, top + "embed/gather"),
        ("while.5", 10, 110, top + "layers/while"),
        ("fusion.1", 12, 30, body + "attn.qkv_rope/dot_general"),
        ("scatter.3", 30, 40, body + "kv_pool.write/scatter"),
        ("copy.7", 40, 55, body + "ragged_attn/transpose"),
        ("ragged_paged_attention.6", 55, 75, body + "ragged_attn/pallas_call"),
        ("dynamic-update-slice.2", 80, 100, top + "layers/while/body/"
         "dynamic_update_slice"),
        ("fusion.4", 110, 120, ""),
    ]
    by = H.self_time_by_label(device)
    assert by == {"embed": 10, "attn.qkv_rope": 18, "kv_pool.write": 10,
                  "ragged_attn": 15, "ragged_attn.kernel": 20,
                  # the while's own 100 less the 83 its body covers, and
                  # the scan's write-back under bare ``layers``
                  "layers": 17 + 20, "xla:fusion": 10}
    # 50-90: the while's 40 less 5 + 20 + 10 of its body, and the
    # write-back's 10
    clipped = H.self_time_by_label(device, (50, 90))
    assert clipped["ragged_attn"] == 5 and clipped["layers"] == 5 + 10
    assert H.scope_of("attn.qkv_rope/mul") == "attn.qkv_rope"
    assert H.scope_of("jit(step_fn)/loss/transpose(jvp(mlp))/dot") == "loss"
    assert H.scope_of("") is None


def test_the_xplane_is_read_as_the_profiler_reads_it(tmp_path):
    """A real (CPU) trace: the annotations, their stats and their times
    come out as ``jax.profiler.ProfileData`` gives them; a CPU trace has
    no ``/device:TPU:`` plane, so no device operation and no module."""
    import glob
    import jax
    from jax.profiler import ProfileData, TraceAnnotation
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for tick in range(3):
            with TraceAnnotation(H.TICK, tick=tick, kv_tokens=2 ** 40 + tick,
                                 kind="block"):
                with TraceAnnotation(H.PHASE + "dispatch", tick=tick):
                    jax.numpy.ones((8, 8)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    annotations, device, modules = H.read_xplane(path)
    assert (device, modules) == ([], [])
    want = sorted(
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name == H.HOST_PLANE
        for line in plane.lines for ev in line.events
        if ev.name.startswith("serving."))
    assert len(want) == 6
    got = sorted(annotations, key=lambda a: (a[0], a[1]))
    for (n, s, e, st), (wn, ws, we, wst) in zip(got, want):
        assert (n, st) == (wn, wst)
        assert s == pytest.approx(ws, abs=1) and e == pytest.approx(we, abs=1)
    ticks = H.whole_ticks(annotations, (0, float("inf")))
    assert H.stat_sum(ticks, "kv_tokens") == 3 * 2 ** 40 + 3
    assert [p[0] for p in H.ticked_phases(annotations)] == ["dispatch"] * 3


def test_protobuf_fields_by_hand():
    # field 1 varint 300, field 2 bytes "ab", field 3 fixed64 1, field 4
    # varint -2 (ten bytes, two's complement)
    msg = (bytes([0x08, 0xAC, 0x02, 0x12, 0x02]) + b"ab"
           + bytes([0x19]) + (1).to_bytes(8, "little")
           + bytes([0x20]) + bytes([0xFE] + [0xFF] * 8 + [0x01]))
    got = [(n, bytes(v) if isinstance(v, memoryview) else v)
           for n, v in H._fields(memoryview(msg))]
    assert got == [(1, 300), (2, b"ab"), (3, 1), (4, 2 ** 64 - 2)]
    # an XStat: metadata_id 7, int64_value -2
    stat = bytes([0x08, 0x07, 0x20]) + bytes([0xFE] + [0xFF] * 8 + [0x01])
    assert H._stat(memoryview(stat), {7: "tick"}) == ("tick", -2)


def test_every_new_entry_resolves_to_its_reader():
    m = manifest.load_manifest()
    by_name = {x["name"]: x for x in m["per_layer"]}
    e2e = {"chat": "itl_p95_ms", "batch": "serve_tokens_per_s"}
    cells = {"chat": "mistral7b-serve-chat", "batch": "qwen15moe-serve-batch"}
    for sfx, cell_name in cells.items():
        cell = manifest.Cell(m, cell_name)
        for stem in NEW:
            entry = by_name[f"{stem}.{sfx}"]
            assert entry["workloads"] == [cell_name]
            assert entry["moves"] == e2e[sfx]
            reader = cell.readers[entry["name"]]
            assert reader.__file__.endswith(
                os.path.join("layer_metrics", stem + ".py"))
    train = manifest.Cell(m, "mistral7b-train-2k")
    assert train.readers["optimizer_ms_per_step"].__file__.endswith(
        "optimizer_ms_per_step.py")
    assert by_name["optimizer_ms_per_step"]["moves"] == "train_tokens_per_s"
    # what was there is still there, ahead of what was added
    assert [x["name"] for x in m["per_layer"]][:11] == [
        "tick_ms.chat", "tick_device_ms.chat", "ragged_attn_ms_per_tick.chat",
        "device_idle_pct.chat", "train_mfu_pct", "splash_roofline_pct",
        "device_idle_pct.train", "tick_ms.batch", "tick_device_ms.batch",
        "ragged_attn_ms_per_tick.batch", "device_idle_pct.batch"]


class Device:
    device_kind = "TPU v5 lite"


def ctx_with(hostspans):
    model = {"num_hidden_layers": 16, "num_key_value_heads": 8,
             "head_dim": 128}
    return {"hostspans": hostspans, "model": model, "devices": [Device()],
            "window": {"trace_ticks": 2, "hists": {}},
            "train": {"trace_steps": 2}}


def readers():
    m = manifest.load_manifest()
    out = dict(manifest.Cell(m, "mistral7b-serve-chat").readers)
    out.update(manifest.Cell(m, "mistral7b-train-2k").readers)
    return {k: v for k, v in out.items()
            if k.rsplit(".", 1)[0] in NEW + ["optimizer_ms_per_step"]}


@pytest.mark.parametrize("hostspans", [
    None,                                   # no device plane
    {"idle_by_phase": {}, "by_label": {"xla:copy": 5, "xla:fusion": 9},
     "tick_by_label": {},
     "tick_stats": {"rows": None, "rows_real": None, "kv_tokens": None}},
    # ^ no annotation, no scope
], ids=["no-device-plane", "no-annotation-no-scope"])
def test_a_reader_returns_none_where_there_is_nothing_to_read(hostspans):
    ctx = ctx_with(hostspans)
    rd = readers()
    assert len(rd) == 8
    for name, reader in rd.items():
        assert reader.read(ctx) is None, name


def test_load_finds_no_trace_and_says_none(monkeypatch, tmp_path):
    monkeypatch.setattr(H, "trace_dir", lambda: str(tmp_path))
    ctx = {}
    assert H.load(ctx) is None and ctx["hostspans"] is None


def test_the_readers_on_a_reduced_trace():
    hs = {"idle_by_phase": {"admit": 1e6, "build": 2e6, "dispatch": 1e6,
                            "readback": 0.5e6, "emit": 3e6, "none": 1e6},
          "by_label": {"kv_pool.write": 4e6, "layers": 7e6, "xla:copy": 3e6,
                       "ragged_attn": 6e6, "ragged_attn.kernel": 8e6,
                       "optimizer": 50e6},
          "tick_by_label": {"ragged_attn.kernel": 8e6},
          "tick_stats": {"rows": 80, "rows_real": 20, "kv_tokens": 4000}}
    ctx = ctx_with(hs)
    ctx["window"]["hists"]["tick_host_s"] = [0.004, 0.006, 0.005]
    got = {k.rsplit(".", 1)[0] if k.endswith(".chat") else k: r.read(ctx)
           for k, r in readers().items()}
    assert got["tick_host_ms"] == pytest.approx(5.0)
    assert got["idle_launch_ms_per_tick"] == pytest.approx(2.0)
    assert got["idle_readback_ms_per_tick"] == pytest.approx(0.25)
    assert got["idle_emit_ms_per_tick"] == pytest.approx(1.5)
    assert got["kv_pool_move_ms_per_tick"] == pytest.approx(10.0)
    assert got["tick_row_fill_pct"] == pytest.approx(25.0)
    assert got["optimizer_ms_per_step"] == pytest.approx(25.0)
    # 4000 tokens x 16 layers x 2 x 8 heads x 128 x 2 B = 262 144 000 B
    # at 819e9 B/s = 0.32 ms of the kernel's 8 ms
    assert got["ragged_attn_roofline_pct"] == pytest.approx(
        100 * 262144000 / 819e9 / 8e-3)
