"""`window_counted_per_query` (PR 32) reads the counter the tile executor's
window probe moves, per request of the window, in `tsbs-heavy` alone; a
program without the counter (the parent) leaves the metric out."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402


def test_window_counted_reads_its_counter_per_request():
    from greptimedb_tpu.utils import metrics

    assert type(metrics.TILE_WINDOW_COUNTED).__name__ == "Counter"  # program.counters() finds it
    spec = manifest.read_json(ROOT, "benchmark", "layer_metrics", "window_counted_per_query.json")
    assert spec["reader"] == "counter_delta"
    assert spec["args"] == {"counter": "TILE_WINDOW_COUNTED", "per_request": True}
    assert (spec["layer"], spec["source"], spec["moves"]) == ("tile executor", "program_counter", "sql_qps")
    cell = manifest.Cell("tsbs-heavy")
    assert "window_counted_per_query" in {p["name"] for p in cell.per_layer}
    assert "window_counted_per_query" not in {p["name"] for p in manifest.Cell("prom-rate-range").per_layer}
    # a window of 210 requests in turn holds 70 `double-groupby-1`, each probed once
    run = {"requests": 210, "counters": {"TILE_WINDOW_COUNTED": 70.0}}
    assert cell.read_metric("layer_metrics", "window_counted_per_query", run) == pytest.approx(1 / 3)
    assert cell.read_metric("layer_metrics", "window_counted_per_query", {"requests": 210, "counters": {}}) is None
    assert cell.read_metric("layer_metrics", "window_counted_per_query", {**run, "requests": 0}) is None
