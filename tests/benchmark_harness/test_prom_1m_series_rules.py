"""The benchmark's tests of the cell `prom-1m-series-rules`: its entries are
found by name, the generator makes the million-series deployment the
configuration describes (here at a reduced scale), the two shapes send the
text of a recording rule and of a dashboard panel and fold their reference
as Prometheus does, and the cell runs end to end on the CPU with the
float32 control failing and the two new per-layer metrics in the traced
line.
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

CELL, CONFIG = "prom-1m-series-rules", "prom-nginx-metric-engine-1m"
PROMQL_CELLS = ["prom-rate-range", "prom-metric-engine-range", CELL]
SMALL = "hosts=4096"
LABELS = sorted([
    "hostname", "region", "datacenter", "rack", "os", "arch", "team", "service",
    "service_version", "service_environment", "port", "server",
])
# the metrics this cell joins, the fifteen of the write path among them
JOINED = {
    "dispatches_per_query.sql", "readback_bytes_per_query", "device_idle_pct.sql",
    "compile_s", "compiles_in_window.sql", "prewarm_s", "ingest_krows_per_s",
    "http_self_ms", "http_render_ms", "http_write_ms", "parse_ms", "dispatch_ms",
    "readback_wait_ms", "client_side_ms", "render_columnar_cells_per_query",
    "tql_plan_ms", "tql_assemble_ms", "tql_legacy_per_query", "tql_dispatches_per_query",
    "rate_roofline", "tql_segment_stats_per_query", "tql_logical_dispatches_per_query",
    "tql_plane_rows_per_query", "write_batch_s", "write_logical_s", "write_split_s",
    "write_wal_s", "write_memtable_s", "flush_region_s", "flush_sort_s", "sst_encode_s",
    "sst_index_s", "compact_region_s", "compact_read_s", "compact_merge_s",
    "write_stall_s", "wal_bytes_per_row", "compaction_rewritten_mb",
}
NEW = {
    "tql_series_per_query": ("program_counter", "series/query", "TQL_TILE_SERIES", {}),
    "tql_order_ms": ("program_span", "ms/query", "STAGE_SELF_S_TQL_ORDER", {"times": 1000.0}),
}


def _run(*args: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", *args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600,
    )
    lines = proc.stdout.strip().splitlines()
    return proc, (json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None)


def _fails(numbers: dict) -> list:
    return [name for name, (number, limit) in numbers.items() if number > limit]


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(CELL, {"hosts": 1000})


@pytest.fixture(scope="module")
def ds(cell):
    return cell.dataset(2**31 + 40)


def test_the_entries_are_found_by_name():
    m = manifest.manifest()
    entry = [w for w in m["workloads"] if w["name"] == CELL]
    assert entry == [{
        "name": CELL, "config": CONFIG, "traffic": CELL, "chips": 1, "why": entry[0]["why"],
    }] and len(entry[0]["why"]) <= 200
    config = [c for c in m["configs"] if c["name"] == CONFIG][0]
    assert config["reduced"] == ["minutes", "metrics", "batch_scrapes"]
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    body = manifest.read_json(ROOT, config["file"])
    assert config["source"] == body["source"] and len(body["source"]) <= 200
    assert set(body["reduced"]) == {"minutes", "metrics", "batch_scrapes"}
    assert body["reduced"]["minutes"]["source"] == 72 * 60
    assert body["reduced"]["minutes"]["run"] == body["minutes"]
    assert body["reduced"]["metrics"] == {**body["reduced"]["metrics"], "source": 7, "run": 1}
    # a bulk load where remote write delivers a scrape at a time
    assert body["reduced"]["batch_scrapes"]["source"] == 1
    assert body["reduced"]["batch_scrapes"]["run"] == body["batch_scrapes"] == body["minutes"]
    # the series are never cut, nor the labels, the scrape or the guarantees
    assert body["hosts"] == 1_000_000 and body["metrics"] == ["requests"]
    assert body["scrape_s"] == 60 and body["regions"] == 1
    assert body["engine"] == "metric" and body["append_mode"] is False
    assert sorted(body["labels"]) == LABELS
    assert body["assumed"]["restart_share"] == 0.05
    assert body["assumed"]["minutes_before"] == 72 * 60 - body["minutes"]
    assert body["guarantees"]["value_rtol_f64"] == 1e-9
    # one SST a flush: no compaction of a flush's own key-disjoint slices
    assert body["database"] == {
        "query.fallback_to_cpu": False, "tql.legacy_fallback": False,
        "storage.ingest_flush_workers": 1,
    }
    assert manifest.read_json(ROOT, "benchmark", "traffic", CELL + ".json") == {
        "clients": 1, "loop": "closed", "order": "round_robin",
        "shapes": [
            {"shape": "rule-sum-by-service", "weight": 1,
             "literals": {"start": {"uniform_window_s": 300}}},
            {"shape": "panel-sum-by-region", "weight": 1,
             "literals": {"start": {"uniform_window_s": 600}}},
        ],
        "trace": {"after_s": 2, "cycles": 2},
    }
    per_layer = {p["name"]: p for p in m["per_layer"]}
    for name in JOINED:
        assert CELL in per_layer[name]["workloads"], name
    for name, (source, unit, counter, extra) in NEW.items():
        spec = manifest.read_json(ROOT, "benchmark", "layer_metrics", name + ".json")
        assert spec["reader"] == "counter_delta" and spec["moves"] == "sql_qps"
        assert spec["args"] == {"counter": counter, "per_request": True, **extra}
        assert spec["layer"] == "PromQL tile path" and spec["source"] == source
        assert spec["unit"] == unit
        entry = per_layer[name]
        assert set(PROMQL_CELLS) <= set(entry["workloads"])
        for key in ("layer", "unit", "better", "source", "moves"):
            assert entry[key] == spec[key], key
    reported = {e["name"] for e in m["end_to_end"] if CELL in e.get("workloads", [CELL])}
    # too few requests a window for a tail: the rate and the set-up
    assert reported == {"sql_qps", "setup_s"}


def test_the_generator_makes_a_span_of_minutes(cell, ds):
    assert ds.ticks == cell.config["minutes"] and isinstance(ds.ticks, int)
    assert ds.end == ds.t0 + cell.config["minutes"] * 60_000 and isinstance(ds.end, int)
    assert ds.rows == 1000 * cell.config["minutes"] and ds.tables == ["nginx_requests"]
    assert (np.diff(ds.tick_ts()) == 60_000).all()
    v = ds.samples["requests"]
    assert v.shape == (cell.config["minutes"], 1000) and (v == np.floor(v)).all()
    restarted = ds.restart_tick >= 0
    assert 25 <= restarted.sum() <= 80  # 5 % of 1000 hosts
    assert ((np.diff(v, axis=0) < 0).sum(axis=0) == restarted).all()
    # the level the 72 h cut away would have left at one step a scrape
    level = 5 * cell.config["assumed"]["minutes_before"]
    assert 0.97 * level < np.median(v[0][~restarted]) < 1.03 * level
    assert sorted(ds.label_values) == ds.labels == LABELS
    assert len(set(ds.label_values["hostname"])) == 1000
    again = cell.dataset(2**31 + 40)
    assert (again.samples["requests"] == v).all()
    # `batch_scrapes` scrapes of every series a batch
    per = cell.config["batch_scrapes"]
    batches = list(ds.batches())
    assert [b.num_rows for _t, b in batches] == [1000 * per] * (cell.config["minutes"] // per)
    assert batches[0][1]["hostname"].to_pylist()[:2] == ["host_0", "host_1"]


def test_requests_are_a_rule_and_a_panel(cell, ds):
    lit = {"start": ds.t0 + 122_999}
    assert cell.shapes["rule-sum-by-service"].request(ds, lit) == {"sql": (
        "TQL EVAL (1767226022.999, 1767226022.999, '1m') "
        "sum by (service) (rate(nginx_requests[5m]))"
    )}
    assert cell.shapes["panel-sum-by-region"].request(ds, lit) == {"sql": (
        "TQL EVAL (1767226022.999, 1767226322.999, '60s') "
        "sum by (region) (rate(nginx_requests[5m]))"
    )}
    for shape in cell.shapes.values():
        assert shape.KIND == "sql" and shape.BAR == "value_rtol_f64" and shape.SERIES == "all"
    stream = traffic.requests(cell.traffic, ds, 2**31 + 5, 1)
    drawn = [next(stream) for _ in range(200)]
    assert [shape for shape, _ in drawn[:4]] == 2 * ["rule-sum-by-service", "panel-sum-by-region"]
    for shape, lit in drawn:
        # the rule's (t - 5m, t] and the panel's (s - 5m, s + 5m] lie in the data
        reach = 300_000 if shape == "rule-sum-by-service" else 600_000
        assert ds.t0 <= lit["start"] and lit["start"] + reach <= ds.end
    starts = {shape: {lit["start"] for s, lit in drawn if s == shape} for shape in cell.shapes}
    assert len(starts["rule-sum-by-service"]) > 95
    # ten minutes of data leave the panel one start: s = 5 min
    assert ds.end - ds.t0 == 600_000 and starts["panel-sum-by-region"] == {ds.t0}


@pytest.mark.parametrize("shape,by,steps,start,window_ticks", [
    # the rule at t = 7.5 min: (2.5, 7.5] holds the scrapes of minutes 3..7
    ("rule-sum-by-service", "service", 1, 150_000, (3, 8)),
    # the panel from s = 5 min, the one start 10 minutes of data leave it:
    # (0, 10] holds the scrapes of minutes 1..9
    ("panel-sum-by-region", "region", 6, 0, (1, 10)),
])
def test_the_reference_folds_by_the_label_in_its_order(
    cell, ds, shape, by, steps, start, window_ticks,
):
    lit = {"start": ds.t0 + start}
    names, ts, value = cell.shapes[shape].reference(ds, lit)
    groups = sorted(set(ds.label_values[by]))
    assert list(names[::steps]) == groups and len(value) == steps * len(groups)
    first = lit["start"] + 300_000
    assert (ts[:steps] == first + np.arange(steps) * 60_000).all()
    rates = promql_ref.extrapolated(
        ds, ds.samples["requests"], first + np.arange(steps) * 60_000, 300_000, True
    )
    for k, g in enumerate(groups):
        mine = rates[ds.label_values[by] == g].sum(axis=0)
        np.testing.assert_allclose(value[steps * k:steps * (k + 1)], mine, rtol=1e-13)
    # about 5 requests a scrape a host: 1/12 a second, 1000 hosts
    assert 0.07 * 1000 < value.reshape(len(groups), steps).sum(axis=0).mean() < 0.1 * 1000
    low = cell.shapes[shape].reference(ds, lit, np.float32)[2]
    assert np.abs(low / value - 1).max() > 1e-9
    # the bytes the request must read: the samples of its windows, every series
    i0, i1 = cell.shapes[shape].ticks(ds, lit)
    assert (i0, i1) == window_ticks
    assert roofline.shape_bytes(cell.shapes[shape], ds, lit) == (i1 - i0) * 1000 * 20


@pytest.mark.parametrize("trace", ["0", "1"])
def test_cell_rehearses_on_the_cpu_and_the_float32_control_fails(trace):
    m = manifest.manifest()
    proc, result = _run(
        "--workload", CELL, "--seed", "4000000140", "--seconds", "4", "--trace", trace,
        "--rehearse", SMALL, "--control",
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] is True and result["failed"] == 0 < result["attempted"]
    assert result["device"]["platform"] != "tpu"
    assert result["compared"]["gap.value_rtol_f64"][0] < 1e-12
    assert _fails(result["control"]) == ["gap.value_rtol_f64"]
    config = manifest.read_json(ROOT, "benchmark", "configs", CONFIG + ".json")
    assert result["run"]["clock"]["rows"] == 4096 * config["minutes"]
    kind = "per_layer" if trace == "1" else "end_to_end"
    listed = {e["name"] for e in m[kind] if CELL in e.get("workloads", [CELL])}
    moved, metrics = result["run"]["moved"], result["metrics"]
    if trace == "0":
        assert set(metrics) == listed == {"sql_qps", "setup_s"}
        return
    # no device plane on the CPU: the trace's readers report nothing, never 0
    assert set(metrics) == listed - {"device_idle_pct.sql", "rate_roofline"}
    assert metrics["tql_dispatches_per_query"]["value"] == 1.0
    assert metrics["tql_legacy_per_query"]["value"] == 0.0
    assert metrics["tql_series_per_query"]["value"] == 4096
    assert moved["TQL_TILE_SERIES"] == 4096 * result["attempted"]
    assert 0 < metrics["tql_order_ms"]["value"] < 1000
    assert metrics["compiles_in_window.sql"]["value"] == 0.0
    # a program without the counter and the stage: nothing, and no raise
    here = manifest.Cell(CELL)
    for name in NEW:
        assert here.read_metric(
            "layer_metrics", name, {"requests": 8, "counters": {}}
        ) is None
