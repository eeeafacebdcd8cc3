"""The benchmark's own tests: the manifest and its data files cross-refer,
every cell runs end to end at a tiny size on the CPU (never naming a TPU),
the float32 control and a planted fault both come out as not correct, and
the trace reduction and the roofline's byte functions give known numbers.
"""

import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import client, manifest, roofline, trace_reduce, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TINY = {"tsbs-heavy": "hosts=10,hours=13"}


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


def test_manifest_and_its_files_cross_refer():
    m = manifest.manifest()
    assert m["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= m["run_seconds"] <= 51
    cells = {w["name"]: w for w in m["workloads"]}
    configs = {c["name"]: c for c in m["configs"]}
    end_to_end = {e["name"]: e for e in m["end_to_end"]}
    assert len(cells) == len(m["workloads"]) and "setup_s" in end_to_end
    for name in [*cells, *configs, *end_to_end, *(p["name"] for p in m["per_layer"])]:
        assert NAME.match(name), name
    assert {w["config"] for w in cells.values()} == set(configs)
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 2)
    for config in configs.values():
        body = manifest.read_json(ROOT, config["file"])
        assert body["name"] == config["name"]
        assert sorted(body["reduced"]) == sorted(config["reduced"])
        assert all(key in body for key in config["reduced"])
    reports = {}  # cell -> the end-to-end metrics it reports
    for name in cells:
        cell = manifest.Cell(name)
        reports[name] = {e["name"] for e in cell.end_to_end}
        assert "setup_s" in reports[name] and len(reports[name]) >= 2
        assert cell.per_layer, name
        for shape in cell.shapes.values():
            assert shape.BAR in cell.config["guarantees"]
            assert shape.KIND in ("sql", "promql") and shape.SERIES in ("all", "one")
    for kind, entries in (("end_to_end", m["end_to_end"]), ("layer_metrics", m["per_layer"])):
        for entry in entries:
            spec = manifest.read_json(ROOT, "benchmark", kind, entry["name"] + ".json")
            for key in ("name", "unit", "better", "source", "layer", "moves"):
                assert spec.get(key) == entry.get(key), (entry["name"], key)
            assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
            assert entry["source"] in SOURCES
            assert os.path.exists(os.path.join(ROOT, "benchmark", "readers", spec["reader"] + ".py"))
            for cell in entry.get("workloads", cells):
                assert cell in cells
                if kind == "layer_metrics":
                    assert entry["moves"] in reports[cell], (entry["name"], cell)
    for entry in m["end_to_end"]:
        assert 0 < entry["bound"] <= 0.25 and entry["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", sorted(TINY))
def test_cell_rehearses_on_the_cpu_and_the_float32_control_fails(cell):
    m = manifest.manifest()
    for trace in ("0", "1"):
        proc, result = _run(
            "benchmark/run.py", "--workload", cell, "--seed", "4000000123",
            "--seconds", "1", "--trace", trace, "--rehearse", TINY[cell], "--control",
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert result["correct"] is True and result["failed"] == 0 < result["attempted"]
        assert result["device"]["platform"] != "tpu"
        kind = "per_layer" if trace == "1" else "end_to_end"
        listed = {e["name"] for e in m[kind] if cell in e.get("workloads", [cell])}
        assert set(result["metrics"]) <= listed
        if trace == "0":
            assert set(result["metrics"]) == listed
            assert all(v["value"] > 0 for v in result["metrics"].values())
        else:  # no device plane on the CPU: a trace's readers report nothing, never 0
            assert not {n for n in result["metrics"] if "roofline" in n or "idle" in n}
        # the float32 control has to fail every float bar of the cell, the
        # float32-shipped avg's (double-groupby-1) as well as the f64 one
        assert _fails(result["control"]) == ["gap.value_rtol_avg_f32", "gap.value_rtol_f64"]
        assert list(result)[-1] == "compared"
        for name, (number, limit) in result["compared"].items():
            assert f"{name} = {number!r} (limit {limit!r})" in proc.stderr


def test_without_a_tpu_a_run_fails_and_prints_no_result():
    proc, result = _run(
        "benchmark/run.py", "--workload", "tsbs-heavy", "--seed", "1",
        "--seconds", "1", "--trace", "0",
    )
    assert proc.returncode != 0 and result is None
    assert "no accelerator" in proc.stderr


@pytest.mark.parametrize("cell,fault,caught_by", [
    ("tsbs-heavy", "sql-value", "gap.value_rtol_f64"),
    ("tsbs-heavy", "sql-row", "answers_wrong"),
    ("tsbs-heavy", "sql-order", "answers_wrong"),
])
def test_an_answer_altered_where_it_is_produced_is_not_correct(cell, fault, caught_by):
    proc, result = _run(
        os.path.join(HERE, "fault_run.py"), fault, "--workload", cell, "--seed", "7",
        "--seconds", "1", "--trace", "0", "--rehearse", TINY[cell],
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] is False and result["failed"] > 0
    assert caught_by in _fails(result["compared"])


def test_traffic_draws_from_the_seed_and_inside_the_data():
    cell = manifest.Cell("tsbs-heavy")

    class Fleet:  # the configuration's scale, nothing generated
        hosts, t0 = cell.config["hosts"], cell.config["start_ms"]
        end = t0 + cell.config["hours"] * 3600_000

    def draws(seed, stream, n=300):
        requests = traffic.requests(cell.traffic, Fleet, seed, stream)
        return [next(requests) for _ in range(n)]

    assert draws(2**31 + 5, 1) == draws(2**31 + 5, 1)
    assert draws(2**31 + 5, 1) != draws(2**31 + 5, 0) != draws(6, 0)
    assert [shape for shape, _ in draws(6, 1, 6)] == 2 * [
        "double-groupby-1", "lastpoint", "groupby-orderby-limit"
    ]
    starts = {lit["start"] for shape, lit in draws(6, 1) if shape == "double-groupby-1"}
    assert len(starts) > 90  # a start drawn per request: no literal repeated
    assert all(Fleet.t0 <= s and s + 12 * 3600_000 <= Fleet.end for s in starts)
    ends = {lit["end"] for shape, lit in draws(6, 1) if shape == "groupby-orderby-limit"}
    assert len(ends) > 80 and all(Fleet.t0 + 3600_000 <= e <= Fleet.end for e in ends)
    # the warm-up's edges: each shape with literals, at both ends of what is drawn
    assert traffic.edges(cell.traffic, Fleet) == [
        ("double-groupby-1", {"start": Fleet.t0 + 1}),
        ("double-groupby-1", {"start": Fleet.end - 12 * 3600_000 - 1}),
        ("groupby-orderby-limit", {"end": Fleet.t0 + 3600_000}),
        ("groupby-orderby-limit", {"end": Fleet.end}),
    ]


def test_client_parses_both_protocols_into_rows():
    sql = b'{"output": [{"records": {"rows": [["host_1", 1.5], ["host_2", null]]}}]}'
    assert client.parse({"sql": "SELECT 1"}, sql) == [["host_1", 1.5], ["host_2", None]]
    prom = (b'{"status": "success", "data": {"resultType": "matrix", "result": ['
            b'{"metric": {"hostname": "host_1"}, "values": [[60, "0.5"], [120, "0.25"]]}]}}')
    assert client.parse({"query": "m", "start": 60, "end": 120, "step": 60}, prom) == [
        ("host_1", 60_000, 0.5), ("host_1", 120_000, 0.25),
    ]
    with pytest.raises(RuntimeError):
        client.parse({"query": "m"}, b'{"status": "error", "error": "bad"}')


def test_trace_reduce_on_the_recorded_trace():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        recorded = json.load(f)
    got = trace_reduce.reduce(recorded["planes"])
    for key, want in recorded["expect"].items():
        assert got[key] == pytest.approx(want, rel=1e-9), key
    assert 0 < got["busy_s"] < got["window_s"]
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10


def test_trace_reduce_arithmetic():
    ms = 1e6
    planes = {
        "/host:CPU": {"python": [
            ["bench.slice", 10 * ms, 100 * ms],
            ["bench.request a", 10 * ms, 30 * ms], ["bench.request b", 41 * ms, 9 * ms],
            ["bench.request c", 51 * ms, 9 * ms], ["bench.request d", 90 * ms, 20 * ms],
        ]},
        "/device:TPU:0": {"XLA Ops": [
            ["early", 0 * ms, 15 * ms],  # clipped to the slice: 5 ms
            ["while", 20 * ms, 20 * ms], ["fusion", 25 * ms, 5 * ms],  # nested: 20 ms
            ["late", 100 * ms, 30 * ms],  # clipped: 10 ms
        ]},
    }
    got = trace_reduce.reduce(planes)
    assert got["busy_s"] == pytest.approx(0.035) and got["window_s"] == pytest.approx(0.1)
    assert got["device_ops"][0] == ["while", pytest.approx(0.02)]
    assert got["idle_gaps"] == [
        ["across 3 requests with no device op", pytest.approx(0.06)],
        ["in request a, before its first device op", pytest.approx(0.005)],
    ]
    assert trace_reduce.short_name(
        "%fusion.71 = (f32[131073]{0:T(1024)S(1)}, f32[8]{0}) fusion(f32[8]{0} %p), kind=kCustom"
    ) == "%fusion.71 f32[131073]"
    assert trace_reduce.reduce({"/host:CPU": planes["/host:CPU"]}) is None


def test_roofline_bytes_on_known_shapes():
    cell = manifest.Cell("tsbs-heavy")
    stored = cell.config["stored_bytes"]
    assert roofline.row_bytes(("ts", "tag", "field"), stored) == 20
    assert roofline.request_bytes(17_280_000, ("ts", "field"), stored) == 276_480_000

    class Fleet:  # 12 h of 4000 hosts, nothing generated
        cfg, hosts, ticks, scrape_s, t0 = cell.config, 4000, 4320, 10, cell.config["start_ms"]

    all_rows = roofline.shape_bytes(cell.shapes["double-groupby-1"], Fleet, {"start": Fleet.t0})
    assert all_rows == 17_280_000 * 20
    first_hour = roofline.shape_bytes(
        cell.shapes["groupby-orderby-limit"], Fleet, {"end": Fleet.t0 + 3600_000}
    )
    assert first_hour == 360 * 4000 * 16
    assert roofline.least_seconds(819_000_000, "TPU v5 lite") == pytest.approx(1e-3)
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
