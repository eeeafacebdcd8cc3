"""The benchmark's tests of the cell `prom-metric-engine-range`: its files
cross-refer, the generator makes the seven metrics and twelve labels the
configuration describes, the requests are the text the issue gives, the
reference folds and orders as Prometheus does, the cell runs end to end at a
small size on the CPU with the new counters in the traced line, and
the float32 control and three planted faults come out as not correct.
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

from benchmark import manifest, promql_ref, roofline, traffic  # noqa: E402

CELL, CONFIG = "prom-metric-engine-range", "prom-nginx-metric-engine-4000"
SMALL = "hosts=100,hours=1"
LABELS = sorted([
    "hostname", "region", "datacenter", "rack", "os", "arch", "team", "service",
    "service_version", "service_environment", "port", "server",
])
# the per-layer metrics this PR wrote.  A new `per_layer` entry goes at the
# END of the list, and `test_ordinal_gid_dispatches.py` (PR 34) holds the list's
# last entry to its own; only a `benchmark` PR may edit that file.  So their
# files are here, tested, and nothing below keeps a later PR from listing them
# with these `workloads`; until then the counters they read are in every
# result line's `run.moved`
WRITTEN = {
    "tql_logical_dispatches_per_query": ("TQL_TILE_LOGICAL_DISPATCHES", [CELL]),
    "tql_plane_rows_per_query": ("TQL_TILE_PLANE_ROWS", ["prom-rate-range", CELL]),
}
# not `tql_plan_ms`, `tql_assemble_ms`, `tql_legacy_per_query`,
# `tql_dispatches_per_query`, `rate_roofline`: `test_prom_rate_range.py` holds
# their lists to `prom-rate-range` alone, and only a `benchmark` PR may edit it
JOINED = {
    "tql_segment_stats_per_query", "dispatches_per_query.sql",
    "readback_bytes_per_query", "device_idle_pct.sql", "compile_s", "compiles_in_window.sql",
    "prewarm_s", "ingest_krows_per_s", "http_self_ms", "http_render_ms", "http_write_ms",
    "parse_ms", "dispatch_ms", "readback_wait_ms", "client_side_ms",
    "render_columnar_cells_per_query",
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


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(CELL, {"hosts": 400, "hours": 1})


@pytest.fixture(scope="module")
def ds(cell):
    return cell.dataset(2**31 + 35)


def test_the_new_files_cross_refer():
    m = manifest.manifest()
    entry = [w for w in m["workloads"] if w["name"] == CELL]
    assert entry == [{
        "name": CELL, "config": CONFIG, "traffic": CELL, "chips": 1, "why": entry[0]["why"],
    }] and len(entry[0]["why"]) <= 200
    config = [c for c in m["configs"] if c["name"] == CONFIG][0]
    assert config["reduced"] == ["hours"]
    assert 1 <= len(config["why"]) <= 200 and config["why"].isascii()
    body = manifest.read_json(ROOT, config["file"])
    assert config["source"] == body["source"] and len(body["source"]) <= 200
    assert set(body["reduced"]) == {"hours"}
    assert set(body["reduced"]["hours"]) == {"source", "run", "why"}
    assert body["reduced"]["hours"]["source"] == 72
    assert body["reduced"]["hours"]["run"] == body["hours"] == 1
    assert body["hosts"] == 4000 and body["scrape_s"] == 10 and body["regions"] == 1
    assert body["engine"] == "metric" and body["append_mode"] is False
    assert body["physical_table"] == "greptime_physical_table"
    assert body["metrics"] == [
        "accepts", "active", "handled", "reading", "requests", "waiting", "writing",
    ]
    assert sorted(body["labels"]) == LABELS
    assert body["assumed"]["restart_share"] == 0.05
    assert body["guarantees"]["value_rtol_f64"] == 1e-9
    assert body["database"] == {"query.fallback_to_cpu": False, "tql.legacy_fallback": False}
    mix = manifest.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    assert mix == {
        "clients": 1, "loop": "closed", "order": "round_robin",
        "shapes": [
            {"shape": "me-sum-by-region", "weight": 1,
             "literals": {"start": {"uniform_window_s": 1800}}},
            {"shape": "me-increase-host", "weight": 1,
             "literals": {"host": "uniform_host", "start": {"uniform_window_s": 1800}}},
        ],
        "trace": {"after_s": 2, "cycles": 2},
    }
    per_layer = {p["name"]: p for p in m["per_layer"]}
    for name, (counter, cells) in WRITTEN.items():
        spec = manifest.read_json(ROOT, "benchmark", "layer_metrics", name + ".json")
        assert spec["reader"] == "counter_delta" and spec["moves"] == "sql_qps"
        assert spec["args"] == {"counter": counter, "per_request": True}
        assert spec["layer"] == "PromQL tile path" and spec["source"] == "program_counter"
        if name in per_layer:  # listed by a later PR: as its file says
            entry = per_layer[name]
            assert entry["workloads"] == cells
            for key in ("layer", "unit", "better", "source", "moves"):
                assert entry[key] == spec[key], key
    reports = {p["name"] for p in m["per_layer"] if CELL in p.get("workloads", [CELL])}
    assert reports >= JOINED
    for name in JOINED:
        assert CELL in per_layer[name]["workloads"], name
    # 460 requests a window, two shapes in turn: the rate; the tail waits for
    # a PR that shows its spread (the cell's `why` says so)
    reported = {e["name"] for e in m["end_to_end"] if CELL in e.get("workloads", [CELL])}
    assert reported >= {"sql_qps", "setup_s"}


def test_the_generator_makes_the_metrics_and_labels_the_configuration_describes(cell, ds):
    assert ds.rows == 7 * 360 * 400 and len(ds.tables) == 7
    assert ds.tables[0] == "nginx_accepts" and ds.tables[-1] == "nginx_writing"
    again = cell.dataset(2**31 + 35)
    other = cell.dataset(36)
    for metric in ds.metrics:
        assert ds.samples[metric].shape == (360, 400)
        assert (again.samples[metric] == ds.samples[metric]).all()
        assert (other.samples[metric] != ds.samples[metric]).any()
    restarted = ds.restart_tick >= 0
    assert 8 <= restarted.sum() <= 35  # 5 % of 400 hosts
    for metric in ("accepts", "handled", "requests"):
        v = ds.samples[metric]
        assert (v == np.floor(v)).all() and (v >= 0).all()
        drops = np.diff(v, axis=0) < 0
        assert (drops.sum(axis=0) == restarted).all()  # monotonic but for the one restart
        for h in np.nonzero(restarted)[0]:
            assert v[ds.restart_tick[h], h] == 0  # all three counters of the host, one scrape
        assert 124_000 < np.median(v[0]) < 132_000  # the level 71 h would have left
        grows = np.diff(v[:, ~restarted], axis=0)
        assert 4.9 < grows.mean() < 5.1
    for metric in ("active", "reading", "waiting", "writing"):
        v = ds.samples[metric]
        assert v.min() >= 0 and v.max() <= 100 and 0.7 < np.abs(np.diff(v, axis=0)).mean() < 0.9
    assert sorted(ds.label_values) == ds.labels == LABELS
    assert len(set(ds.label_values["hostname"])) == 400
    assert set(ds.label_values["region"]) <= {f"region-{i}" for i in range(9)}
    # a host's datacenter lies in its region, as tsbs_cpu.py draws it, from the same stream
    assert all(d.startswith(r) for r, d in zip(ds.label_values["region"], ds.label_values["datacenter"]))
    cpu = manifest.load_module("datasets", "tsbs_cpu")
    cpu_cfg = manifest.read_json(ROOT, "benchmark", "configs", "tsbs-cpu-only-4000.json")
    twin = cpu.Dataset({**cpu_cfg, "hosts": 400, "hours": 1}, 2**31 + 35)
    assert (np.array(twin.domains["rack"])[twin.tag_codes["rack"]] == ds.label_values["rack"]).all()
    assert all(1024 <= int(p) <= 65535 for p in ds.label_values["port"])
    assert all(s.startswith("nginx_") for s in ds.label_values["server"])
    statements = ds.create_statements()
    assert len(statements) == 8 and "'physical_metric_table' = ''" in statements[0]
    assert all(
        "ENGINE = metric WITH ('on_physical_table' = 'greptime_physical_table')" in s
        and s.count(" STRING") == 12 for s in statements[1:]
    )
    batches = list(ds.batches())
    assert [t for t, _ in batches] == ds.tables  # 144,000 rows a table: one chunk
    table, batch = batches[4]
    assert batch.num_rows == 360 * 400 and set(batch.column_names) == {
        *LABELS, "greptime_timestamp", "greptime_value",
    }
    assert batch["greptime_value"].to_numpy()[:400].tolist() == ds.samples["requests"][0].tolist()
    assert batch["hostname"].to_pylist()[:3] == ["host_0", "host_1", "host_2"]


def test_requests_are_the_issues_text(cell, ds):
    lit = {"host": 7, "start": ds.t0 + 122_999}
    assert cell.shapes["me-sum-by-region"].request(ds, lit) == {"sql": (
        "TQL EVAL (1767225722.999, 1767227522.999, '60s') "
        "sum by (region) (rate(nginx_requests[5m]))"
    )}
    assert cell.shapes["me-increase-host"].request(ds, lit) == {"sql": (
        "TQL EVAL (1767225722.999, 1767227522.999, '60s') "
        'increase(nginx_handled{hostname="host_7"}[5m])'
    )}
    for shape in cell.shapes.values():
        assert shape.KIND == "sql" and shape.BAR == "value_rtol_f64"
    stream = traffic.requests(cell.traffic, ds, 2**31 + 5, 1)
    drawn = [next(stream) for _ in range(200)]
    assert [shape for shape, _ in drawn[:4]] == 2 * ["me-sum-by-region", "me-increase-host"]
    starts = {lit["start"] for _, lit in drawn}
    assert len(starts) > 190 and all(ds.t0 <= s <= ds.end - 1800_000 for s in starts)
    assert len({lit["host"] for shape, lit in drawn if shape == "me-increase-host"}) > 60


def test_the_reference_folds_by_region_and_orders_by_label(cell, ds):
    lit = {"host": 3, "start": ds.t0 + 600_000}
    region, ts, value = cell.shapes["me-sum-by-region"].reference(ds, lit)
    regions = sorted(set(ds.label_values["region"]))
    assert list(region[::31]) == regions and len(value) == 31 * len(regions)
    assert (ts[:31] == ds.t0 + 600_000 + np.arange(31) * 60_000).all()
    steps = lit["start"] + np.arange(31, dtype=np.int64) * 60_000
    rates = promql_ref.extrapolated(ds, ds.samples["requests"], steps, 300_000, True)
    for k, name in enumerate(regions):
        mine = rates[ds.label_values["region"] == name].sum(axis=0)
        np.testing.assert_allclose(value[31 * k:31 * k + 31], mine, rtol=1e-13)
    # about 0.5 requests a second a host
    assert 0.4 * 400 < value.reshape(len(regions), 31).sum(axis=0).mean() < 0.6 * 400
    # the float32 control differs from it by more than the bar
    low = cell.shapes["me-sum-by-region"].reference(ds, lit, np.float32)[2]
    assert np.abs(low / value - 1).max() > 1e-9
    columns = cell.shapes["me-increase-host"].reference(ds, {"host": 3, "start": ds.t0 + 1})
    assert len(columns) == 14 and [len(c) for c in columns] == [30] * 14
    assert [c[0] for c in columns[:12]] == [ds.label_values[l][3] for l in LABELS]
    assert columns[12][0] == ds.t0 + 60_001 and columns[13].dtype == np.float64
    # label order: column by column in ascending label-name order
    hosts = ds.label_order(np.arange(400))
    keys = [tuple(ds.label_values[l][h] for l in LABELS) for h in hosts]
    assert keys == sorted(keys) and sorted(hosts) == list(range(400))


def test_roofline_bytes_are_the_samples_the_request_must_read(cell, ds):
    lit = {"host": 3, "start": ds.t0 + 600_000}
    assert cell.shapes["me-sum-by-region"].ticks(ds, lit) == (31, 241)
    assert roofline.shape_bytes(cell.shapes["me-sum-by-region"], ds, lit) == 210 * 400 * 20
    assert roofline.shape_bytes(cell.shapes["me-increase-host"], ds, lit) == 210 * 20


@pytest.mark.parametrize("trace", ["0", "1"])
def test_cell_rehearses_on_the_cpu_and_the_float32_control_fails(trace):
    m = manifest.manifest()
    proc, result = _run(
        "benchmark/run.py", "--workload", CELL, "--seed", "4000000135",
        "--seconds", "1", "--trace", trace, "--rehearse", SMALL, "--control",
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] is True and result["failed"] == 0 < result["attempted"]
    assert result["device"]["platform"] != "tpu"
    assert result["compared"]["gap.value_rtol_f64"][0] < 1e-12
    assert _fails(result["control"]) == ["gap.value_rtol_f64"]
    assert result["run"]["clock"]["rows"] == 7 * 360 * 100
    kind = "per_layer" if trace == "1" else "end_to_end"
    listed = {e["name"] for e in m[kind] if CELL in e.get("workloads", [CELL])}
    moved, metrics = result["run"]["moved"], result["metrics"]
    if trace == "0":
        assert set(metrics) == listed >= {"sql_qps", "setup_s"}
        return
    # no device plane on the CPU: the trace's readers report nothing, never 0
    assert set(metrics) == listed - {"device_idle_pct.sql"}
    assert metrics["dispatches_per_query.sql"]["value"] == 1.0
    assert metrics["compiles_in_window.sql"]["value"] == 0.0
    # the counters of the metrics whose lists this PR may not join, and of
    # the two it wrote, read through the harness as a listed metric is
    assert moved["TQL_TILE_DISPATCHES"] == moved["TPU_DEVICE_DISPATCHES"] == result["attempted"]
    run = {"requests": result["attempted"], "counters": moved}
    here = manifest.Cell(CELL)
    assert here.read_metric("layer_metrics", "tql_logical_dispatches_per_query", run) == 1.0
    # 36,000 rows a logical table of a 252,000-row region: a 2^16-row slice
    # of planes padded to 2^18 rows
    assert here.read_metric("layer_metrics", "tql_plane_rows_per_query", run) == 1 << 16
    # a program without the counters (the parent): nothing, and no raise
    assert here.read_metric(
        "layer_metrics", "tql_plane_rows_per_query", {"requests": 8, "counters": {}}
    ) is None
    assert "TQL_TILE_COLD_SERVES" not in moved and "TQL_TILE_INELIGIBLE" not in moved
    stages = sum(v for k, v in moved.items() if k.startswith("STAGE_SELF_S_"))
    root = moved["HTTP_REQUEST_S"]
    assert abs(stages - root) <= 2.0 * root / result["attempted"]


@pytest.mark.parametrize("fault,caught_by", [
    ("tql-value", "gap.value_rtol_f64"),
    ("tql-point", "answers_wrong"),
    ("tql-series", "answers_wrong"),
])
def test_a_tql_answer_altered_where_it_is_produced_is_not_correct(fault, caught_by):
    proc, result = _run(
        os.path.join(HERE, "fault_run_me.py"), fault, "--workload", CELL, "--seed", "7",
        "--seconds", "1", "--trace", "0", "--rehearse", SMALL,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] is False and result["failed"] > 0
    assert caught_by in _fails(result["compared"])


def test_the_parents_program_cannot_open_the_configuration():
    """What ends a run of the new files on a program without the setting:
    `program.open_database` refuses a key the program's config lacks, before
    anything is ingested."""
    import tempfile

    from benchmark import program

    with pytest.raises(KeyError, match="no setting tql.no_such_setting"):
        program.open_database(tempfile.mkdtemp(), {"tql.no_such_setting": False})
    body = manifest.read_json(ROOT, "benchmark", "configs", CONFIG + ".json")
    db = program.open_database(tempfile.mkdtemp(), body["database"])
    try:
        assert db.config.tql.legacy_fallback is False
        assert db.config.query.fallback_to_cpu is False
    finally:
        db.close()
