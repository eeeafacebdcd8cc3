"""`ordinal_gid_dispatches_per_query` (PR 34) reads the counter a tile
dispatch moves when its plan groups by the source's own series ordinals, per
request of the window, in the two SQL cells; a program without the counter
(the parent) leaves the metric out."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402

NAME = "ordinal_gid_dispatches_per_query"


def test_ordinal_gid_dispatches_reads_its_counter_per_request():
    from greptimedb_tpu.utils import metrics

    assert type(metrics.TILE_ORDINAL_GIDS).__name__ == "Counter"  # program.counters() finds it
    spec = manifest.read_json(ROOT, "benchmark", "layer_metrics", NAME + ".json")
    assert spec["reader"] == "counter_delta"
    assert spec["args"] == {"counter": "TILE_ORDINAL_GIDS", "per_request": True}
    entry = [p for p in manifest.manifest()["per_layer"] if p["name"] == NAME]
    assert len(entry) == 1
    for key in ("name", "layer", "unit", "better", "source", "moves"):
        assert spec[key] == entry[0][key], key
    assert (spec["layer"], spec["better"], spec["moves"]) == (
        "kernels (ops/aggregate.py tile program)", "higher", "sql_qps",
    )
    # the two SQL cells; a later SQL cell may append itself
    assert {"tsbs-heavy", "tsbs-mesh4-heavy"} <= set(entry[0]["workloads"])
    mesh = manifest.Cell("tsbs-mesh4-heavy")
    assert NAME in {p["name"] for p in mesh.per_layer}
    assert NAME in {p["name"] for p in manifest.Cell("tsbs-heavy").per_layer}
    assert NAME not in {p["name"] for p in manifest.Cell("prom-rate-range").per_layer}
    # a window of 135 requests in turn: 45 `double-groupby-1` and 45 `lastpoint`
    # group by ordinals, 45 `groupby-orderby-limit` have no tag in their gid
    run = {"requests": 135, "counters": {"TILE_ORDINAL_GIDS": 90.0}}
    assert mesh.read_metric("layer_metrics", NAME, run) == pytest.approx(2 / 3)
    # one region holds the whole dictionary: the counter is there and does not move
    assert manifest.Cell("tsbs-heavy").read_metric(
        "layer_metrics", NAME, {"requests": 520, "counters": {"TILE_ORDINAL_GIDS": 0.0}}
    ) == 0.0


@pytest.mark.parametrize("cell", ["tsbs-heavy", "tsbs-mesh4-heavy"])
def test_the_parents_program_leaves_the_metric_out(cell):
    """The parent has no such counter: its run holds no such key, the reader
    returns nothing and does not raise, and the line leaves the metric out."""
    c = manifest.Cell(cell)
    parents = {"requests": 64, "counters": {"TPU_DEVICE_DISPATCHES": 64.0, "TILE_MESH_DISPATCHES": 64.0}}
    assert c.read_metric("layer_metrics", NAME, parents) is None
    assert c.read_metric("layer_metrics", NAME, {**parents, "requests": 0}) is None
