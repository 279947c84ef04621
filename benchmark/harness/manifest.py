"""Finds by name what ``BENCHMARK.json`` lists: a cell's workload file,
its configuration file, its traffic file and its per-layer readers.

A later PR adds files and entries and edits nothing here: a cell is
``workloads/<name>.json``, a configuration the ``file`` its entry
gives, a traffic mix ``traffic/<traffic>.json``, a per-layer metric
``layer_metrics/<name>.py`` with a ``read(ctx)``, a model family
``families/<family>.py``. Each is looked for under the manifest's own
first path, then under this benchmark's directory.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_file(bench_dir: str, *parts: str) -> str:
    """A file of the benchmark by its name: under ``bench_dir`` (the
    manifest's own first path), else under this benchmark's directory
    (a test manifest with a few files of its own reuses the rest)."""
    for base in (bench_dir, BENCH_DIR):
        path = os.path.join(base, *parts)
        if os.path.exists(path):
            return path
    raise SystemExit(f"BENCHMARK.json names {os.path.join(*parts)!r}, "
                     f"which is not under {bench_dir}")


def load_family(name: str, bench_dir: str = BENCH_DIR):
    """``families/<name>.py``, registered so that one family can build
    on another (``from bench_family_dense_decoder import ...``)."""
    import sys
    key = f"bench_family_{name}"
    if key not in sys.modules:
        if name != "dense_decoder":
            load_family("dense_decoder")
        sys.modules[key] = load_module(
            find_file(bench_dir, "families", f"{name}.py"), key)
    return sys.modules[key]


def load_reader(name: str, bench_dir: str = BENCH_DIR):
    """``layer_metrics/<name>.py``. A quantity split by suffix because
    its cells report different end-to-end metrics (``tick_ms.chat``,
    ``tick_ms.batch``) is read by one file, ``layer_metrics/tick_ms.py``,
    unless a file of the full name exists."""
    for stem in (name, name.rsplit(".", 1)[0]):
        for base in (bench_dir, BENCH_DIR):
            path = os.path.join(base, "layer_metrics", f"{stem}.py")
            if os.path.exists(path):
                return load_module(path, "bench_reader_" + re.sub(
                    r"\W", "_", stem))
    raise SystemExit(f"BENCHMARK.json names the per-layer metric {name!r}; "
                     f"no layer_metrics/{name}.py under {bench_dir}")


class Cell:
    """One entry of ``workloads`` with every file it names, loaded."""

    def __init__(self, manifest: dict, name: str, root: str = ROOT):
        bench_dir = os.path.normpath(
            os.path.join(root, manifest["paths"][0]))
        entry = next((w for w in manifest["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise SystemExit(
                f"no workload {name!r} in BENCHMARK.json; known: "
                f"{[w['name'] for w in manifest['workloads']]}")
        cfg_entry = next(c for c in manifest["configs"]
                         if c["name"] == entry["config"])
        self.name = name
        self.bench_dir = bench_dir
        self.chips = int(entry["chips"])
        self.entry = entry
        self.config = _load_json(os.path.join(root, cfg_entry["file"]))
        self.workload = _load_json(
            find_file(bench_dir, "workloads", f"{name}.json"))
        self.traffic = _load_json(
            find_file(bench_dir, "traffic", f"{entry['traffic']}.json"))
        self.mode = self.workload["mode"]
        over = self.workload.get("overrides", {})
        allowed = set(self.config.get("reduced", {})) & set(
            cfg_entry["reduced"])
        if set(over) - allowed:
            raise SystemExit(
                f"workload {name!r} overrides {sorted(set(over) - allowed)}"
                f": only keys under the configuration's `reduced` "
                f"({sorted(allowed)}) may change")
        self.model = {**self.config, **over}

        self.end_to_end = [m for m in manifest["end_to_end"]
                           if name in m.get("workloads", [name])]
        e2e = {m["name"] for m in self.end_to_end}
        # a per-layer metric without `workloads` is due in every cell
        # that reports the end-to-end metric it moves
        self.per_layer = [m for m in manifest["per_layer"]
                          if (name in m["workloads"] if "workloads" in m
                              else m["moves"] in e2e)]
        self.readers = {m["name"]: load_reader(m["name"], bench_dir)
                        for m in self.per_layer}

    @property
    def family(self):
        """The configuration's family module, loaded at first use (it
        imports JAX; building a cell does not)."""
        return load_family(self.model["family"], self.bench_dir)
