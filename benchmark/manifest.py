"""`BENCHMARK.json` and the data files it names.

The harness knows no cell, configuration, shape or metric by name: a cell
is found in the manifest, its configuration at the manifest's `file`, its
traffic at `traffic/<traffic>.json`, a shape at `shapes/<shape>.py`, a
dataset generator at `datasets/<dataset>.py`, a per-layer metric at
`layer_metrics/<metric>.json`, an end-to-end metric at
`end_to_end/<metric>.json`, and the reader either names at
`readers/<reader>.py`.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def read_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """The module at `benchmark/<kind>/<name>.py` (names may hold `-` and
    `.`, so they are loaded by path, not imported by name)."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def manifest() -> dict:
    return read_json(ROOT, "BENCHMARK.json")


class Cell:
    """One entry of `workloads`, with everything it names loaded."""

    def __init__(self, name: str, scale: dict | None = None):
        m = manifest()
        entries = [w for w in m["workloads"] if w["name"] == name]
        if not entries:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.name, self.entry = name, entries[0]
        self.chips = self.entry["chips"]
        config = [c for c in m["configs"] if c["name"] == self.entry["config"]][0]
        self.config = read_json(ROOT, config["file"])
        self.config.update(scale or {})
        self.traffic = read_json(HERE, "traffic", self.entry["traffic"] + ".json")
        self.shapes = {
            s["shape"]: load_module("shapes", s["shape"]) for s in self.traffic["shapes"]
        }
        here = lambda metric: name in metric.get("workloads", [name])  # noqa: E731
        self.end_to_end = [e for e in m["end_to_end"] if here(e)]
        self.per_layer = [p for p in m["per_layer"] if here(p)]

    def dataset(self, seed: int):
        return load_module("datasets", self.config["dataset"]).Dataset(self.config, seed)

    def read_metric(self, kind: str, name: str, run: dict):
        """`kind` is `end_to_end` or `layer_metrics`: the directory that
        holds the metric's file, which names its reader."""
        spec = read_json(HERE, kind, name + ".json")
        return load_module("readers", spec["reader"]).read(run, **spec["args"])
