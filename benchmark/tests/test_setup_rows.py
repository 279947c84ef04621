"""The eight ``setup_*`` readers over a hand-made compile ledger and
hand-made set-up spans, without a chip: each row reads what its
docstring says, the rows of a cell and its lead-in add up to its
``setup_s``, a reader is listed only for the cells it has something to
read in, and a program without the ledger leaves every row out.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_setup_rows.py -q
"""
import os
import sys
import time
from collections import deque

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import manifest, modes  # noqa: E402

ALL = ("setup_programs_missed", "setup_compile_s", "setup_cache_read_s",
       "setup_trace_lower_s", "setup_outside_program_s")
SERVE = ("setup_engine_init_s", "setup_warm_s")
TRAIN = ("setup_train_init_s",)
BENCHMARK = manifest.load_manifest()
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def rec(name, t_end, trace, lower, backend, cache, during):
    return {"fun_name": name, "t0": t_end - trace - lower - backend,
            "t_end": t_end, "trace_s": trace, "lower_s": lower,
            "backend_s": backend, "cache": cache,
            "retrieval_s": backend / 2 if cache == "hit" else 0.0,
            "saved_s": 0.0, "during": during, "thread": "MainThread"}


@pytest.fixture
def by_hand(monkeypatch):
    """An empty ledger and an empty process ring; returns
    ``(base, add_span, add_record)``: times are seconds after ``base``,
    the instant the process started."""
    import jax
    from paddle_tpu.observability import sentinel, tracer
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    ledger = deque(maxlen=sentinel.LEDGER_CAPACITY)
    ring = tracer.SpanTracer(capacity=256)
    monkeypatch.setattr(sentinel, "_ledger", ledger)
    monkeypatch.setattr(tracer, "_PROCESS_TRACER", ring)
    base = time.monotonic() - 1000.0

    def add_span(name, t0, t1, **args):
        ring.add(name, "setup", base + t0, base + t1, **args)

    def add_record(name, t_end, trace, lower, backend, cache, during=None):
        ledger.append(rec(name, base + t_end, trace, lower, backend, cache,
                          during))
    return base, add_span, add_record


def read_rows(cell_name, base, t0, setup_s, key="window"):
    cell = manifest.Cell(BENCHMARK, cell_name)
    # the harness's clock is time.perf_counter()
    offset = time.perf_counter() - time.monotonic()
    ctx = {"cell": cell, key: {"t0": base + t0 + offset},
           "end_to_end": {"setup_s": {"value": setup_s, "unit": "s"}}}
    out = modes.read_layers(cell, ctx)
    return cell, {k: v["value"] for k, v in out.items()
                  if k.startswith("setup_")}


def test_serving_rows_partition_setup_s(by_hand, capsys):
    base, span, record = by_hand
    # process start 0; weights until 4; the engine 4 -> 6; warm-up
    # 6.5 -> 21.5; warm requests; the lead-in 30.05 -> 40.05
    record("_make", 3.5, 0.25, 0.5, 1.25, "hit")                # outside
    span("serving.setup.init.inventory", 4.25, 4.75)
    span("serving.setup.init.cache", 5.0, 5.875)
    record("broadcast_in_dim", 5.5, 0.0, 0.015625, 0.046875, "miss",
           "serving.setup.init.cache")                  # too quick to keep
    span("serving.setup.init", 4.0, 6.0)
    for i, (tq, tail) in enumerate(((128, 3), (128, 0), (2048, 3))):
        t = 6.75 + 4.5 * i
        span("serving.setup.warm.program", t, t + 4.25, tq=tq,
             decode_tail=tail, spec_k=0)
        record("serving_tick", t + 4.0, 2.0, 0.5, 1.0, "hit",
               "serving.setup.warm.program")
    span("serving.setup.warm.program", 20.25, 20.75, block=4)
    record("serving_tick_block", 20.625, 0.125, 0.0625, 0.125, "hit",
           "serving.setup.warm.program")
    span("serving.setup.warm.sync", 20.75, 21.25)
    span("serving.setup.warm", 6.5, 21.5)
    record("serving_tick", 25.0, 0.0, 0.0, 5.0, "miss", "serving.tick")
    record("late", 41.0, 1.0, 1.0, 1.0, "miss")     # inside the window
    cell, rows = read_rows("mistral7b-serve-chat", base, 40.05, 40.05)
    assert set(rows) == set(ALL + SERVE)
    assert rows["setup_programs_missed"] == 1       # not the quick one
    assert rows["setup_compile_s"] == pytest.approx(5.046875)
    assert rows["setup_cache_read_s"] == pytest.approx(1.25 + 3.0 + 0.125)
    assert rows["setup_trace_lower_s"] == pytest.approx(
        0.75 + 0.015625 + 7.5 + 0.1875)
    assert rows["setup_engine_init_s"] == pytest.approx(2.0 - 0.0625)
    assert rows["setup_warm_s"] == pytest.approx(15.0 - 10.5 - 0.3125)
    # setup_s - lead-in - (the spans' union + the seconds outside them)
    assert rows["setup_outside_program_s"] == pytest.approx(
        40.05 - 10.0 - (2.0 + 15.0 + 2.0 + 5.0))
    lead = cell.workload["lead_in_s"]
    assert lead + sum(v for k, v in rows.items()
                      if k != "setup_programs_missed") \
        == pytest.approx(40.05, abs=1e-3)
    err = capsys.readouterr().err
    assert err.count("[setup] setup_") == len(rows)
    assert "[setup] setup_programs_missed 1 programs" in err


def test_training_rows_partition_setup_s(by_hand):
    base, span, record = by_hand
    span("train.setup.build", 2.0, 3.0)
    record("_normal", 10.0, 0.125, 0.25, 0.5, "hit", "train.setup.init")
    span("train.setup.init", 3.0, 25.0)
    record("_make", 27.0, 0.25, 0.25, 0.5, "hit")
    record("step_fn", 36.0, 3.0, 1.0, 4.0, "hit")
    cell, rows = read_rows("mistral7b-train-2k", base, 41.0, 41.0,
                           key="train")
    assert set(rows) == set(ALL + TRAIN)
    assert rows["setup_programs_missed"] == 0
    assert rows["setup_compile_s"] == 0
    assert rows["setup_train_init_s"] == pytest.approx(23.0 - 0.875)
    assert rows["setup_outside_program_s"] == pytest.approx(
        41.0 - 23.0 - 1.0 - 8.0)
    assert sum(v for k, v in rows.items() if k != "setup_programs_missed") \
        == pytest.approx(41.0, abs=1e-3)       # no lead-in in this cell


@pytest.mark.parametrize("cell_name", CELLS)
def test_a_reader_is_listed_only_where_it_is_due(cell_name):
    cell = manifest.Cell(BENCHMARK, cell_name)
    listed = {m["name"] for m in cell.per_layer
              if m["name"].startswith("setup_")}
    due = set(ALL) | set(TRAIN if cell.mode == "train" else SERVE)
    assert listed == due
    assert "setup_s" in {m["name"] for m in cell.end_to_end}


def test_the_eight_entries_name_accepted_cells_only():
    mine = [m for m in BENCHMARK["per_layer"] if m["moves"] == "setup_s"]
    assert sorted(m["name"] for m in mine) == sorted(ALL + SERVE + TRAIN)
    assert BENCHMARK["per_layer"][-8:] == mine       # appended, at the end
    for m in mine:
        assert set(m["workloads"]) <= set(CELLS)
        assert (m["source"], m["better"]) == ("program_counter", "lower")
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py"))


def test_a_program_without_the_ledger_leaves_the_rows_out(monkeypatch):
    """The parent of the PR that brought the readers has no
    ``setup_report``: every reader returns None and raises nothing."""
    from paddle_tpu import observability
    monkeypatch.delattr(observability, "setup_report")
    _, rows = read_rows("mistral7b-serve-chat", time.monotonic(), 0.0, 30.0)
    assert rows == {}
    _, rows = read_rows("mistral7b-train-dp2tp2", time.monotonic(), 0.0,
                        30.0, key="train")
    assert rows == {}
