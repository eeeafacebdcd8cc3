"""The readers of the program's stage clocks: `span_gaps.py` on a
hand-made trace whose answer is known and on a cut of a trace kept from
the chip (`recorded_stages.json`, made by `span_gaps.py --record`), and
`readers/outside_server.py` on a hand-made run record."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import manifest, span_gaps, trace_reduce  # noqa: E402

MS = 1e6


def _planes():
    return {
        "/host:CPU": {
            "client": [
                ["bench.slice", 0, 100 * MS],
                ["bench.request a", 10 * MS, 50 * MS], ["bench.request b", 70 * MS, 25 * MS],
            ],
            "handler": [
                ["http.request", 12 * MS, 46 * MS], ["http.render", 42 * MS, 8 * MS],
                ["http.write", 50 * MS, 6 * MS], ["PjitFunction(f)", 15 * MS, 1 * MS],
            ],
            "gt-kernel": [
                ["query.tpu", 14 * MS, 26 * MS], ["tile.readback", 30 * MS, 8 * MS],
                ["tile.decode", 36 * MS, 2 * MS],
            ],
        },
        "/device:TPU:0": {"XLA Ops": [
            ["%while", 20 * MS, 10 * MS], ["%fusion", 22 * MS, 3 * MS], ["%copy", 33 * MS, 2 * MS],
        ]},
    }


def test_span_gaps_arithmetic():
    scopes = {"/device:TPU:0": ["jit(p)/partial/blocked", "jit(p)/partial/blocked/x", ""]}
    got = span_gaps.attribute(_planes(), scopes)
    assert got["window_s"] == pytest.approx(0.1) and got["busy_s"] == pytest.approx(0.012)
    assert got["idle_s"] == pytest.approx(0.088) and got["named_share"] == pytest.approx(1.0)
    assert got["requests"] == {"a": 1, "b": 1}
    idle = {tuple(row[:3]): row[3] for row in got["idle"]}
    want = {
        ("a", "before first op", "client"): 2, ("a", "before first op", "http.request"): 2,
        ("a", "before first op", "query.tpu"): 6, ("a", "between ops", "tile.readback"): 3,
        ("a", "after last op", "tile.readback"): 1, ("a", "after last op", "tile.decode"): 2,
        ("a", "after last op", "query.tpu"): 2, ("a", "after last op", "http.request"): 4,
        ("a", "after last op", "http.render"): 8, ("a", "after last op", "http.write"): 6,
        ("a", "after last op", "client"): 2, ("b", "no device op", "client"): 25,
        ("", "", "between requests"): 25,
    }
    assert {k: pytest.approx(v * 1e-3) for k, v in want.items()} == idle
    assert got["last_op"] == {"a": [{"ends_in": "tile.readback", "ms_before_decode": pytest.approx(1.0)}]}
    assert dict(got["busy_by_scope"]) == {
        "jit(p)/partial/blocked": pytest.approx(0.007),
        "jit(p)/partial/blocked/x": pytest.approx(0.003), "(no scope)": pytest.approx(0.002),
    }
    assert "tile.readback" in span_gaps.render(got)
    # the runtime's own host events are no stages; a trace of the parent program has none
    assert not span_gaps.is_stage("PjitFunction(f)") and not span_gaps.is_stage("bench.slice")
    bare = _planes()
    bare["/host:CPU"] = {"client": bare["/host:CPU"]["client"]}
    got = span_gaps.attribute(bare)
    assert got["named_share"] == pytest.approx(25 / 88) and not got["busy_by_scope"]
    assert span_gaps.attribute({"/host:CPU": bare["/host:CPU"]}) is None


def test_span_gaps_on_the_recorded_stages():
    with open(os.path.join(HERE, "recorded_stages.json")) as f:
        recorded = json.load(f)
    got = span_gaps.attribute(recorded["planes"])
    for key, want in recorded["expect"].items():
        assert got[key] == pytest.approx(want, rel=1e-9), key
    reduced = trace_reduce.reduce(recorded["planes"])
    assert got["busy_s"] == pytest.approx(reduced["busy_s"], rel=1e-9)
    assert got["idle_s"] == pytest.approx(got["window_s"] - got["busy_s"], rel=1e-9)
    assert got["named_share"] >= 0.9
    stages = {row[2] for row in got["idle"]}
    assert {"client", "http.render", "tile.decode", "tile.readback", "query.tpu"} <= stages
    # the check the flight recorder's split rests on: the device is done
    # before the decode opens
    for ends in got["last_op"].values():
        assert all(e["ends_in"] == "tile.readback" and e["ms_before_decode"] >= 0 for e in ends)


def test_outside_server_reader():
    reader = manifest.load_module("readers", "outside_server")
    run = {"requests": 4, "latencies_ms": [10.0, 20.0, 30.0, 40.0],
           "counters": {"HTTP_REQUEST_S": 0.08}}
    assert reader.read(run, counter="HTTP_REQUEST_S") == pytest.approx(5.0)
    assert reader.read({**run, "counters": {}}, counter="HTTP_REQUEST_S") is None  # the parent
    assert reader.read({**run, "requests": 0}, counter="HTTP_REQUEST_S") is None
    spec = manifest.read_json(ROOT, "benchmark", "layer_metrics", "client_side_ms.json")
    assert spec["reader"] == "outside_server" and spec["args"] == {"counter": "HTTP_REQUEST_S"}


@pytest.mark.parametrize("metric,counter", [
    ("http_self_ms", "STAGE_SELF_S_HTTP_REQUEST"), ("http_render_ms", "STAGE_SELF_S_HTTP_RENDER"),
    ("http_write_ms", "STAGE_SELF_S_HTTP_WRITE"), ("parse_ms", "STAGE_SELF_S_QUERY_PARSE"),
    ("plan_ms", "STAGE_SELF_S_QUERY_PLAN"), ("tile_host_ms", "STAGE_SELF_S_QUERY_TPU"),
    ("dispatch_ms", "STAGE_SELF_S_TILE_DISPATCH"), ("window_probe_ms", "STAGE_SELF_S_TILE_WINDOW"),
    ("readback_wait_ms", "STAGE_SELF_S_TILE_READBACK"), ("decode_ms", "STAGE_SELF_S_TILE_DECODE"),
])
def test_a_stage_metric_reads_its_counter_per_request(metric, counter):
    from greptimedb_tpu.utils import metrics

    assert type(getattr(metrics, counter)).__name__ == "Counter"  # program.counters() finds it
    cell = manifest.Cell("tsbs-heavy")
    assert metric in {p["name"] for p in cell.per_layer}
    run = {"requests": 8, "counters": {counter: 0.4}}
    assert cell.read_metric("layer_metrics", metric, run) == pytest.approx(50.0)  # ms/query
    assert cell.read_metric("layer_metrics", metric, {"requests": 8, "counters": {}}) is None
