"""The benchmark's tests of the cell `prom-rate-range`: its files
cross-refer, the generator makes the counters the configuration describes,
the requests are the text the issue gives, the cell runs end to end at a tiny
size on the CPU with every new per-layer metric in the traced line, and the
float32 control and a planted fault both come out as not correct.
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

from benchmark import manifest, roofline, traffic  # noqa: E402

CELL, CONFIG = "prom-rate-range", "prom-nginx-counters-4000"
TINY = "hosts=10,hours=1"
NEW_METRICS = {
    "tql_plan_ms": "PromQL tile path", "tql_assemble_ms": "PromQL tile path",
    "tql_legacy_per_query": "PromQL tile path", "tql_dispatches_per_query": "PromQL tile path",
    "rate_roofline": "kernels (ops/rate.py)",
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
    return manifest.Cell(CELL, {"hosts": 400})


@pytest.fixture(scope="module")
def ds(cell):
    return cell.dataset(2**31 + 29)


def test_the_new_files_cross_refer():
    m = manifest.manifest()
    entry = [w for w in m["workloads"] if w["name"] == CELL]
    assert entry == [{
        "name": CELL, "config": CONFIG, "traffic": CELL, "chips": 1, "why": entry[0]["why"],
    }] and len(entry[0]["why"]) <= 200
    config = [c for c in m["configs"] if c["name"] == CONFIG][0]
    assert 1 <= len(config["why"]) <= 200 and config["why"].isascii() and config["why"].isprintable()
    body = manifest.read_json(ROOT, config["file"])
    assert config["source"] == body["source"] and len(body["source"]) <= 200
    assert config["reduced"] == ["hours", "metrics", "engine", "series_key"]
    for key in config["reduced"]:
        assert set(body["reduced"][key]) == {"source", "run", "why"}, key
    assert body["reduced"]["hours"]["run"] == body["hours"] == 1
    assert body["reduced"]["series_key"]["run"] == body["series_key"] == ["hostname"]
    assert body["metrics"] == ["requests"] and body["reduced"]["metrics"]["run"] == 1
    assert body["engine"] == "mito"
    assert len(body["reduced"]["series_key"]["source"]) == 12
    assert body["assumed"]["restart_share"] == 0.05 and body["guarantees"]["value_rtol_f64"] == 1e-9
    assert body["database"] == {"query.fallback_to_cpu": False}
    mix = manifest.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    assert [(s["shape"], s["weight"]) for s in mix["shapes"]] == [("rate-all", 1), ("increase-1", 1)]
    assert mix["clients"] == 1 and mix["trace"] == {"after_s": 2, "cycles": 1}
    per_layer = {p["name"]: p for p in m["per_layer"]}
    for name, layer in NEW_METRICS.items():
        assert per_layer[name]["layer"] == layer and CELL in per_layer[name]["workloads"]
        assert per_layer[name]["moves"] == "sql_qps"
    reports = {p["name"] for p in m["per_layer"] if CELL in p.get("workloads", [CELL])}
    assert reports >= set(NEW_METRICS) | {"compile_s", "prewarm_s", "ingest_krows_per_s"}
    assert not reports & {"scan_roofline", "window_probe_ms", "host_fast_path_pct"}
    # six requests a window (PR 29): their 95th percentile was the window's
    # maximum, so the cell reports the rate (a closed loop runs at capacity)
    reported = {e["name"] for e in m["end_to_end"] if CELL in e.get("workloads", [CELL])}
    assert reported >= {"sql_qps", "setup_s"}


def test_the_generator_makes_the_counters_the_configuration_describes(cell, ds):
    assert ds.requests.shape == (360, 400) and ds.rows == 144_000
    again = cell.dataset(2**31 + 29)
    assert (again.requests == ds.requests).all() and (again.restart_tick == ds.restart_tick).all()
    assert (cell.dataset(30).requests != ds.requests).any()
    restarted = ds.restart_tick >= 0
    assert 8 <= restarted.sum() <= 35  # 5 % of 400
    assert (ds.requests == np.floor(ds.requests)).all() and (ds.requests >= 0).all()
    drops = np.diff(ds.requests, axis=0) < 0
    assert (drops.sum(axis=0) == restarted).all()  # monotonic but for the one restart
    for h in np.nonzero(restarted)[0]:
        r = ds.restart_tick[h]
        assert ds.requests[r, h] == 0 and drops[r - 1, h]
    # the level 71 h before the data left, and |normal(5, 1)| a scrape since
    assert 125_000 < np.median(ds.requests[0]) < 131_000
    grows = np.diff(ds.requests[:, ~restarted], axis=0)
    assert 4.9 < grows.mean() < 5.1 and grows.max() <= 12
    (table, batch), = list(ds.batches())
    assert table == "nginx_requests" and batch.num_rows == ds.rows
    assert batch.column_names == ["hostname", "greptime_timestamp", "greptime_value"]
    assert "append_mode = 'false'" in ds.create_statements()[0]


def test_requests_are_tql_eval_with_seconds_to_three_decimals(cell, ds):
    lit = {"host": 7, "start": ds.t0 + 122_999}
    assert cell.shapes["rate-all"].request(ds, lit) == {"sql": (
        "TQL EVAL (1767225722.999, 1767227522.999, '60s') rate(nginx_requests[5m])"
    )}
    assert cell.shapes["increase-1"].request(ds, lit) == {"sql": (
        "TQL EVAL (1767225722.999, 1767227522.999, '60s') "
        'increase(nginx_requests{hostname="host_7"}[5m])'
    )}
    stream = traffic.requests(cell.traffic, ds, 2**31 + 5, 1)
    drawn = [next(stream) for _ in range(200)]
    assert [shape for shape, _ in drawn[:4]] == 2 * ["rate-all", "increase-1"]
    starts = {lit["start"] for _, lit in drawn}
    assert len(starts) > 190 and all(ds.t0 <= s <= ds.end - 1800_000 for s in starts)
    assert len({lit["host"] for shape, lit in drawn if shape == "increase-1"}) > 60
    assert traffic.edges(cell.traffic, ds)[:2] == [
        ("rate-all", {"start": ds.t0 + 1}), ("rate-all", {"start": ds.end - 1800_000 - 1}),
    ]


def test_reference_has_a_point_exactly_where_prometheus_gives_one(cell, ds):
    # a window that starts 1 ms into the data: its first step sees one sample
    hostname, ts, value = cell.shapes["increase-1"].reference(ds, {"host": 3, "start": ds.t0 + 1})
    assert list(hostname) == ["host_3"] * 30 and ts[0] == ds.t0 + 60_001 and len(value) == 30
    hostname, ts, value = cell.shapes["rate-all"].reference(ds, {"start": ds.t0 + 600_000})
    assert len(value) == 31 * ds.hosts and value.dtype == np.float64
    assert list(hostname[::31]) == sorted(ds.host_names)  # dictionary order: host_10 < host_2
    assert (ts[:31] == ds.t0 + 600_000 + np.arange(31) * 60_000).all()
    assert 0.4 < np.median(value) < 0.6  # about 5 requests a 10 s scrape


def test_roofline_bytes_are_the_samples_the_request_must_read(cell, ds):
    lit = {"host": 3, "start": ds.t0 + 600_000}
    # (start - 5 min, start + 30 min] at a 10 s scrape: 210 samples a series
    assert cell.shapes["rate-all"].ticks(ds, lit) == (31, 241)
    assert roofline.shape_bytes(cell.shapes["rate-all"], ds, lit) == 210 * 400 * 20
    assert roofline.shape_bytes(cell.shapes["increase-1"], ds, lit) == 210 * 20
    early = {"host": 3, "start": ds.t0 + 1}
    assert cell.shapes["rate-all"].ticks(ds, early) == (0, 181)


@pytest.mark.parametrize("trace", ["0", "1"])
def test_cell_rehearses_on_the_cpu_and_the_float32_control_fails(trace):
    m = manifest.manifest()
    proc, result = _run(
        "benchmark/run.py", "--workload", CELL, "--seed", "4000000129",
        "--seconds", "1", "--trace", trace, "--rehearse", TINY, "--control",
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] is True and result["failed"] == 0 < result["attempted"]
    assert result["device"]["platform"] != "tpu"
    assert result["compared"]["gap.value_rtol_f64"][0] < 1e-12
    assert _fails(result["control"]) == ["gap.value_rtol_f64"]
    kind = "per_layer" if trace == "1" else "end_to_end"
    listed = {e["name"] for e in m[kind] if CELL in e.get("workloads", [CELL])}
    moved, metrics = result["run"]["moved"], result["metrics"]
    if trace == "0":
        assert set(metrics) == listed >= {"sql_qps", "setup_s"}
        return
    # no device plane on the CPU: the trace's readers report nothing, never 0
    assert set(metrics) == listed - {"rate_roofline", "device_idle_pct.sql"}
    assert metrics["tql_dispatches_per_query"]["value"] == 1.0
    assert metrics["tql_legacy_per_query"]["value"] == 0.0
    assert metrics["compiles_in_window.sql"]["value"] == 0.0
    assert metrics["tql_plan_ms"]["value"] > 0 and metrics["tql_assemble_ms"]["value"] > 0
    assert "TQL_TILE_COLD_SERVES" not in moved and "TQL_TILE_INELIGIBLE" not in moved
    # the stages telescope: their self seconds sum to the requests' inclusive seconds.
    # The harness reads the counters once its client has the answer, and the server's
    # thread may close that request's http.request only afterwards, so at either end of
    # the window one request's seconds can land on the other side of the reading
    stages = sum(v for k, v in moved.items() if k.startswith("STAGE_SELF_S_"))
    root = moved["HTTP_REQUEST_S"]
    assert abs(stages - root) <= 2.0 * root / result["attempted"]


@pytest.mark.parametrize("fault,caught_by", [
    ("tql-value", "gap.value_rtol_f64"),
    ("tql-point", "answers_wrong"),
])
def test_a_tql_answer_altered_where_it_is_produced_is_not_correct(fault, caught_by):
    proc, result = _run(
        os.path.join(HERE, "fault_run_tql.py"), fault, "--workload", CELL, "--seed", "7",
        "--seconds", "1", "--trace", "0", "--rehearse", TINY,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] is False and result["failed"] > 0
    assert caught_by in _fails(result["compared"])
