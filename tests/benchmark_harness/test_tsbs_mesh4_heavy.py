"""The benchmark's tests of the cell `tsbs-mesh4-heavy`: its files
cross-refer and keep every shape of `tsbs-cpu-only-4000`, the generator
writes the four-region table, the per-device roofline reader divides by the
device planes, and the cell runs end to end at a tiny size on four virtual
CPU devices with every request one mesh dispatch, the float32 control and
the planted faults coming out as not correct.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import manifest, traffic  # noqa: E402

CELL, CONFIG, PARENT = "tsbs-mesh4-heavy", "tsbs-cpu-only-4000-mesh4", "tsbs-cpu-only-4000"
TINY = "hosts=12,hours=24"
NEW_METRICS = {
    "mesh_dispatches_per_query": "tile executor", "mesh_degraded_per_query": "tile executor",
    "mesh_ineligible_per_query": "tile executor", "mesh_stack_ms": "tile executor",
    "window_build_ms": "tile executor", "window_tile_builds_per_query": "tile executor",
    "mesh_scan_roofline": "kernels (ops/aggregate.py tile program)",
}


def _run(script: str, *args: str):
    """As the other harness tests run a cell, but on four host devices:
    the cell's `tile.mesh_devices = 4` needs them."""
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    proc = subprocess.run(
        [sys.executable, script, *args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600,
    )
    lines = proc.stdout.strip().splitlines()
    return proc, (json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None)


def _fails(numbers: dict) -> list:
    return [name for name, (number, limit) in numbers.items() if number > limit]


def test_the_new_files_cross_refer_and_keep_the_source_shapes():
    m = manifest.manifest()
    entry = [w for w in m["workloads"] if w["name"] == CELL]
    assert entry == [{
        "name": CELL, "config": CONFIG, "traffic": CELL, "chips": 4, "why": entry[0]["why"],
    }] and len(entry[0]["why"]) <= 200
    config = [c for c in m["configs"] if c["name"] == CONFIG][0]
    for text in (config["why"], config["source"]):
        assert 1 <= len(text) <= 200 and text.isascii() and text.isprintable()
    assert config["reduced"] == ["hours", "primary_key"]
    body = manifest.read_json(ROOT, config["file"])
    parent = manifest.read_json(ROOT, "benchmark", "configs", PARENT + ".json")
    for key in ("dataset", "table", "hosts", "scrape_s", "start_ms", "append_mode", "tags",
                "primary_key", "fields", "stored_bytes"):
        assert body[key] == parent[key], key
    assert body["hosts"] == 4000 and body["scrape_s"] == 10
    assert len(body["tags"]) == 10 and len(body["fields"]) == 10
    assert body["regions"] == 4 and body["hours"] == 24 and body["deployment"]["chips"] == 4
    for key in config["reduced"]:
        assert set(body["reduced"][key]) == {"source", "run", "why"}, key
    assert body["reduced"]["hours"]["source"] == 72 and body["reduced"]["hours"]["run"] == 24
    assert "window-tile path" in body["reduced"]["hours"]["why"]  # what the hours keep in the cell
    assert body["reduced"]["primary_key"]["run"] == body["primary_key"] == ["hostname"]
    assert len(body["reduced"]["primary_key"]["source"]) == 10
    assert {k: v for k, v in body["assumed"].items() if k != "partition_rule"} == parent["assumed"]
    assert "HASH (hostname)" in body["assumed"]["partition_rule"]
    assert {k: v for k, v in body["guarantees"].items() if k != "partitioning"} == parent["guarantees"]
    assert "row order included" in body["guarantees"]["partitioning"]
    assert body["database"] == {"query.fallback_to_cpu": False, "tile.mesh_devices": 4}
    # the three TSBS queries, letter for letter as tsbs-heavy sends them
    mix = manifest.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    assert mix == manifest.read_json(ROOT, "benchmark", "traffic", "tsbs-heavy.json")
    per_layer = {p["name"]: p for p in m["per_layer"]}
    for name, layer in NEW_METRICS.items():
        assert per_layer[name]["layer"] == layer and per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "sql_qps"
    reports = {p["name"] for p in m["per_layer"] if CELL in p.get("workloads", [CELL])}
    heavy = {p["name"] for p in m["per_layer"] if "tsbs-heavy" in p.get("workloads", ["tsbs-heavy"])}
    # one chip's least time over the mean busy time would read four times high
    assert reports == (heavy - {"scan_roofline"}) | set(NEW_METRICS)
    reported = {e["name"] for e in m["end_to_end"] if CELL in e.get("workloads", [CELL])}
    # under 100 requests a window: its p95 is one of a handful of double-groupby-1
    assert reported == {"sql_qps", "setup_s"}


def test_the_generator_writes_the_four_region_table_and_draws_inside_it():
    cell = manifest.Cell(CELL, {"hosts": 12})
    ds = cell.dataset(2**31 + 33)
    assert ds.ticks == 8640 and ds.rows == 8640 * 12
    create, = ds.create_statements()
    assert "PRIMARY KEY (hostname)) PARTITION BY HASH (hostname) PARTITIONS 4 " in create
    assert "append_mode = 'true'" in create
    stream = traffic.requests(cell.traffic, ds, 2**31 + 5, 1)
    drawn = [
        lit for shape, lit in (next(stream) for _ in range(300)) if shape == "double-groupby-1"
    ]
    starts = {lit["start"] for lit in drawn}
    # a 12 h window drawn over the 12 h at which it fits the 24 h
    assert len(starts) == 100 and all(ds.t0 <= s <= ds.end - 43_200_000 for s in starts)
    assert max(starts) - min(starts) > 36_000_000
    # exactly half of a region's rows wherever it starts: the cover at which a tile is still built
    spans = [cell.shapes["double-groupby-1"].ticks(ds, lit) for lit in drawn]
    assert {i1 - i0 for i0, i1 in spans} == {4320} and 4320 == ds.ticks // 2


def test_the_per_device_roofline_divides_by_the_device_planes():
    read = manifest.load_module("readers", "trace_roofline_per_device").read
    run = {"trace": {"busy_s": 0.5, "devices": 4, "window_s": 1.0}, "traced_least_s": 0.02}
    assert read(run) == pytest.approx(100.0 * 0.02 / 4 / 0.5)
    assert read({"trace": None, "traced_least_s": 0.0}) is None
    assert read({"trace": {"busy_s": 0.0, "devices": 4}, "traced_least_s": 0.02}) is None
    # a program that reports no device count (an older trace_reduce): nothing, never a guess
    assert read({"trace": {"busy_s": 0.5}, "traced_least_s": 0.02}) is None


@pytest.mark.parametrize("trace", ["0", "1"])
def test_cell_rehearses_on_four_host_devices_and_the_float32_control_fails(trace):
    m = manifest.manifest()
    proc, result = _run(
        "benchmark/run.py", "--workload", CELL, "--seed", "4000000133",
        "--seconds", "1", "--trace", trace, "--rehearse", TINY, "--control",
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] is True and result["failed"] == 0 < result["attempted"]
    assert result["device"]["platform"] != "tpu" and result["device"]["count"] == 4
    assert _fails(result["control"]) == ["gap.value_rtol_avg_f32", "gap.value_rtol_f64"]
    kind = "per_layer" if trace == "1" else "end_to_end"
    listed = {e["name"] for e in m[kind] if CELL in e.get("workloads", [CELL])}
    moved, metrics = result["run"]["moved"], result["metrics"]
    # every request of the window crossed the mesh, whatever the line reports
    assert moved["TILE_MESH_DISPATCHES"] == moved["TPU_DEVICE_DISPATCHES"] == result["attempted"]
    assert "TILE_MESH_INELIGIBLE" not in moved and "TILE_MESH_DEGRADED" not in moved
    if trace == "0":
        assert set(metrics) == listed
        return
    # no device plane on the CPU: the trace's readers report nothing, never 0
    assert set(metrics) == listed - {"mesh_scan_roofline", "device_idle_pct.sql"}
    assert metrics["mesh_dispatches_per_query"]["value"] == 1.0
    assert metrics["mesh_degraded_per_query"]["value"] == 0.0
    assert metrics["mesh_ineligible_per_query"]["value"] == 0.0
    assert metrics["compiles_in_window.sql"]["value"] == 0.0
    assert metrics["host_fast_path_pct"]["value"] == 0.0
    assert metrics["mesh_stack_ms"]["value"] > 0
    # a region of three hosts is far under 2^22 rows: no window is probed here
    # (at the real size every double-groupby-1 builds a tile a region; tests/test_mesh_cell.py
    # has that path at a small size)
    assert metrics["window_tile_builds_per_query"]["value"] == 0.0
    assert metrics["window_build_ms"]["value"] == 0.0
    # the stages telescope with the two new ones among them (one request's
    # seconds may fall on the other side of the reading at either end)
    stages = sum(v for k, v in moved.items() if k.startswith("STAGE_SELF_S_"))
    root = moved["HTTP_REQUEST_S"]
    assert abs(stages - root) <= 2.0 * root / result["attempted"]
    assert moved["STAGE_SELF_S_TILE_MESH_STACK"] < moved["STAGE_SELF_S_TILE_MESH_STACK"] + \
        moved["STAGE_SELF_S_TILE_DISPATCH"] < root


@pytest.mark.parametrize("fault,caught_by", [
    ("sql-value", "gap.value_rtol_f64"),
    ("sql-row", "answers_wrong"),
    ("sql-order", "answers_wrong"),
])
def test_an_answer_altered_where_it_is_produced_is_not_correct(fault, caught_by):
    proc, result = _run(
        os.path.join(HERE, "fault_run.py"), fault, "--workload", CELL, "--seed", "7",
        "--seconds", "1", "--trace", "0", "--rehearse", TINY,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] is False and result["failed"] > 0
    assert caught_by in _fails(result["compared"])
