"""The benchmark's tests of the cell `tsbs-tags10-double-groupby`: its files
cross-refer, the configuration keeps every shape of `tsbs-cpu-only-4000`
but the primary key, the generator keeps every field as ground truth, and
the cell runs end to end at a tiny size on the CPU with every request one
series-grouped tile dispatch, nothing declined, and the float32 control
and the planted faults coming out as not correct.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import manifest, traffic  # noqa: E402

CELL, CONFIG, PARENT = "tsbs-tags10-double-groupby", "tsbs-cpu-only-4000-tags10", "tsbs-cpu-only-4000"
TINY = "hosts=10,hours=13"
NEW_METRICS = {
    "series_gid_dispatches_per_query": "kernels (ops/aggregate.py tile program)",
    "tile_series_per_query": "tile executor",
    "tile_declined_per_query": "tile executor",
}


def _run(script: str, *args: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
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
        "name": CELL, "config": CONFIG, "traffic": CELL, "chips": 1, "why": entry[0]["why"],
    }] and len(entry[0]["why"]) <= 200
    config = [c for c in m["configs"] if c["name"] == CONFIG][0]
    for text in (config["why"], config["source"]):
        assert 1 <= len(text) <= 200 and text.isascii() and text.isprintable()
    assert config["reduced"] == ["hours"]
    body = manifest.read_json(ROOT, config["file"])
    parent = manifest.read_json(ROOT, "benchmark", "configs", PARENT + ".json")
    # every shape of the source configuration but the key, which is its ten tags
    for key in ("table", "hosts", "scrape_s", "hours", "start_ms", "regions", "append_mode",
                "tags", "fields", "stored_bytes", "assumed", "guarantees"):
        assert body[key] == parent[key], key
    assert body["primary_key"] == parent["tags"] == parent["reduced"]["primary_key"]["source"]
    assert body["reduced"] == {"hours": parent["reduced"]["hours"]}
    assert body["dataset"] == "tsbs_cpu_fields"
    assert body["database"] == {"query.fallback_to_cpu": False, "query.legacy_fallback": False}
    mix = manifest.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    heavy = manifest.read_json(ROOT, "benchmark", "traffic", "tsbs-heavy.json")
    assert [s["shape"] for s in mix["shapes"]] == ["double-groupby-1", "double-groupby-all"]
    assert all(s["weight"] == 1 for s in mix["shapes"])
    assert mix["shapes"][0] == heavy["shapes"][0]  # the window drawn as tsbs-heavy draws it
    assert mix["shapes"][1]["literals"] == heavy["shapes"][0]["literals"]
    assert mix["trace"] == heavy["trace"] and mix["clients"] == 1
    per_layer = {p["name"]: p for p in m["per_layer"]}
    for name, layer in NEW_METRICS.items():
        assert per_layer[name]["layer"] == layer and CELL in per_layer[name]["workloads"]
        assert per_layer[name]["moves"] == "sql_qps" and per_layer[name]["source"] == "program_counter"
    assert per_layer["tile_declined_per_query"]["workloads"] == [
        "tsbs-heavy", "tsbs-mesh4-heavy", CELL,
    ]
    reports = {p["name"] for p in m["per_layer"] if CELL in p.get("workloads", [CELL])}
    assert {"scan_roofline", "dispatches_per_query.sql", "readback_bytes_per_query",
            "device_idle_pct.sql", "compile_s", "prewarm_s", "ingest_krows_per_s",
            "flush_sort_s", "sst_index_s"} <= reports
    reported = {e["name"] for e in m["end_to_end"] if CELL in e.get("workloads", [CELL])}
    assert reported == {"sql_qps", "setup_s"}


def test_the_generator_keeps_every_field_and_draws_inside_the_data():
    cell = manifest.Cell(CELL, {"hosts": 6})
    ds = cell.dataset(2**31 + 77)
    assert ds.ticks == 4680 and ds.rows == 4680 * 6
    create, = ds.create_statements()
    assert "PRIMARY KEY (hostname, region, datacenter, rack, os, arch, team, service, " \
           "service_version, service_environment)) WITH (append_mode = 'true')" in create
    batches = list(ds.batches())
    for field in ds.fields:
        got = np.concatenate([b.column(field).to_numpy() for _t, b in batches])
        assert np.array_equal(ds.values[field].reshape(-1), got), field
    # the base generator's own ground truth is the same numbers
    assert np.array_equal(ds.values["usage_user"], ds.usage_user)
    stream = traffic.requests(cell.traffic, ds, 2**31 + 5, 1)
    drawn = [next(stream) for _ in range(40)]
    assert [s for s, _lit in drawn[:4]] == ["double-groupby-1", "double-groupby-all"] * 2
    for shape, lit in drawn:
        i0, i1 = cell.shapes[shape].ticks(ds, lit)
        assert i1 - i0 == 4320 and ds.t0 <= lit["start"] <= ds.end - 43_200_000
    # the least bytes of a request: every window row's ts, key and ten fields
    lit = drawn[1][1]
    from benchmark import roofline

    assert roofline.shape_bytes(cell.shapes["double-groupby-all"], ds, lit) == 4320 * 6 * (8 + 4 + 80)


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_the_double_groupby_all_reference_is_the_plain_fold(dtype):
    """Whole hours summed once and kept, the window's cut hours summed each
    time: the same numbers as one fold of the window's rows, in either
    precision, wherever the window starts."""
    cell = manifest.Cell(CELL, {"hosts": 5})
    ds = cell.dataset(2**31 + 9)
    for _ in ds.batches():
        pass
    shape = cell.shapes["double-groupby-all"]
    from benchmark.folds import bucket_starts

    for start in (ds.t0, ds.t0 + 1, ds.t0 + 3_600_000, ds.t0 + 1_234_567, ds.end - 43_200_000):
        lit = {"start": start}
        i0, i1 = shape.ticks(ds, lit)
        starts, tb = bucket_starts(ds, i0, i1, 3_600_000)
        counts = np.diff(np.append(starts, i1 - i0)).astype(dtype)
        got = shape.reference(ds, lit, dtype)
        assert np.array_equal(got[1], np.tile(tb, ds.hosts))
        for field, col in zip(ds.fields, got[2:]):
            block = ds.values[field][i0:i1].astype(dtype)
            avg = np.add.reduceat(block, starts, axis=0, dtype=dtype) / counts[:, None]
            want = avg[:, ds.host_order].T.reshape(-1).astype(np.float64)
            np.testing.assert_allclose(col, want, rtol=1e-6 if dtype == np.float32 else 1e-13)


@pytest.mark.parametrize("trace", ["0", "1"])
def test_cell_rehearses_on_the_series_path_and_the_float32_control_fails(trace):
    m = manifest.manifest()
    proc, result = _run(
        "benchmark/run.py", "--workload", CELL, "--seed", "4000000133",
        "--seconds", "1", "--trace", trace, "--rehearse", TINY, "--control",
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] is True and result["failed"] == 0 < result["attempted"]
    assert result["device"]["platform"] != "tpu"
    assert _fails(result["control"]) == ["gap.value_rtol_avg_f32"]
    moved, metrics = result["run"]["moved"], result["metrics"]
    # every request of the window is one dispatch grouped by series, none declined
    assert moved["TILE_SERIES_GIDS"] == moved["TPU_DEVICE_DISPATCHES"] == result["attempted"]
    assert moved["TILE_STAGE1_SERIES"] == 10 * result["attempted"]
    assert "TILE_DECLINED" not in moved and "TPU_LOWERED_TOTAL" not in moved
    kind = "per_layer" if trace == "1" else "end_to_end"
    listed = {e["name"] for e in m[kind] if CELL in e.get("workloads", [CELL])}
    if trace == "0":
        assert set(metrics) == listed
        return
    # no device plane on the CPU: the trace's readers report nothing, never 0
    assert set(metrics) == listed - {"scan_roofline", "device_idle_pct.sql"}
    assert metrics["series_gid_dispatches_per_query"]["value"] == 1.0
    assert metrics["tile_series_per_query"]["value"] == 10.0
    assert metrics["tile_declined_per_query"]["value"] == 0.0
    assert metrics["dispatches_per_query.sql"]["value"] == 1.0
    assert metrics["compiles_in_window.sql"]["value"] == 0.0


@pytest.mark.parametrize("fault,caught_by", [
    ("sql-value", "gap.value_rtol_avg_f32"),
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
