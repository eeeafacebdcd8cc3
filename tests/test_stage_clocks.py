"""Stage clocks (utils/tracing.py `stage`): one monotonic clock pair per
stage, a `jax.profiler.TraceAnnotation` of the same name, and SELF time
into the stage's counter of `metrics.STAGE_SELF_S`.

The contracts:
  * the self times of one tree of stages sum to its root's inclusive
    time, on one thread and across the hop to the kernel thread;
  * a stage without a counter is transparent; `suppressed()` and the
    plane builder's mute scope move no counter;
  * one `/v1/sql` request through a real `HttpServer` moves every stage
    counter of its path, and their sum is `HTTP_REQUEST_S`'s move;
  * inside a `jax.profiler` session the stages lie on the host plane,
    nested by time as they are nested in the code;
  * the flight recorder's dispatch / readback split IS the stages'
    durations (one interval, one measurement).
"""

import glob
import json
import os
import time
import urllib.parse
import urllib.request

import pytest

from greptimedb_tpu.database import Database
from greptimedb_tpu.utils import flight_recorder as fr
from greptimedb_tpu.utils import kernel_executor, metrics, tracing

Q = (
    "SELECT host, time_bucket('30s', ts) AS tb, avg(usage_user) AS au,"
    " max(usage_system) AS ms, count(*) AS c FROM cpu GROUP BY host, tb"
)
REQUEST_PATH = (
    "http.request", "http.render", "http.write", "query.parse", "query.plan",
    "query.tpu", "tile.compile", "tile.dispatch", "tile.readback", "tile.decode",
)


def _self_seconds() -> dict:
    """Every stage counter's total, by counter name (two stage names may
    share one counter)."""
    return {c.name: c.total() for c in metrics.STAGE_SELF_S.values()}


def _moved(before: dict) -> dict:
    return {k: v - before[k] for k, v in _self_seconds().items()}


def _counter(stage: str) -> str:
    return metrics.STAGE_SELF_S[stage].name


def _spin(seconds: float):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


@pytest.fixture()
def warm_db(tmp_path):
    """A flushed table whose grouped query has reached the warm device
    dispatch (cold serve and the background build are behind it)."""
    db = Database(data_home=str(tmp_path / "db"))
    db.sql(
        "CREATE TABLE cpu (host STRING, region STRING, ts TIMESTAMP TIME INDEX,"
        " usage_user DOUBLE, usage_system DOUBLE, PRIMARY KEY (host, region))"
    )
    rows = [
        f"('host_{h}', 'r{h % 2}', {t * 1000}, {t % 13 + h}, {(t + h) % 7})"
        for t in range(120) for h in range(6)
    ]
    db.sql("INSERT INTO cpu VALUES " + ",".join(rows))
    db.sql("ADMIN flush_table('cpu')")
    for _ in range(3):
        db.sql_one(Q)
    yield db
    db.close()


@pytest.fixture()
def server(warm_db):
    from greptimedb_tpu.servers.http import HttpServer

    srv = HttpServer(warm_db, "127.0.0.1:0").start()
    yield srv
    srv.stop()


def _post_sql(server, sql: str) -> dict:
    """One request, returned once the server has closed its `http.request`
    stage too (the client holds the answer a moment before that)."""
    body = urllib.parse.urlencode({"sql": sql}).encode()
    root_before = metrics.HTTP_REQUEST_S.total()
    with urllib.request.urlopen(f"http://{server.address}/v1/sql", data=body, timeout=120) as r:
        assert r.status == 200
        doc = json.loads(r.read())
    deadline = time.monotonic() + 10.0
    while metrics.HTTP_REQUEST_S.total() == root_before and time.monotonic() < deadline:
        time.sleep(0.001)
    assert metrics.HTTP_REQUEST_S.total() > root_before
    return doc


# ---- self time -------------------------------------------------------------

@pytest.mark.parametrize("hop", [False, True], ids=["one_thread", "kernel_thread_hop"])
def test_self_times_sum_to_the_roots_inclusive_time(hop):
    """root(http.request) > query.tpu > tile.readback > tile.decode, with
    the inner three run on the `gt-kernel` thread when `hop`."""
    if hop:
        kernel_executor._ensure_executor()

    def inner():
        with tracing.stage("query.tpu"):
            _spin(0.004)
            with tracing.stage("tile.readback"):
                _spin(0.003)
                with tracing.stage("tile.decode") as dec:
                    _spin(0.002)
            return dec

    before = _self_seconds()
    with tracing.stage("http.request") as root:
        _spin(0.001)
        dec = kernel_executor.run(inner) if hop else inner()
    moved = _moved(before)
    assert sum(moved.values()) == pytest.approx(root.duration_s, rel=1e-9)
    assert moved[_counter("tile.decode")] == pytest.approx(dec.duration_s, rel=1e-9)
    for name, least in (("http.request", 0.001), ("query.tpu", 0.004),
                        ("tile.readback", 0.003), ("tile.decode", 0.002)):
        assert least <= moved[_counter(name)] < least + 0.05, name
    assert root.child_s == pytest.approx(root.duration_s - moved[_counter("http.request")])


def test_a_stage_without_a_counter_is_transparent():
    assert "admission.wait" not in metrics.STAGE_SELF_S
    before = _self_seconds()
    with tracing.stage("query.tpu") as outer:
        with tracing.stage("admission.wait") as through:  # no counter
            _spin(0.002)
            with tracing.stage("tile.decode") as dec:
                _spin(0.002)
    moved = _moved(before)
    assert through.child_s == pytest.approx(dec.duration_s)
    # the transparent stage's own 2 ms stay with query.tpu; only the
    # counted child's time is taken out of it
    assert moved[_counter("query.tpu")] == pytest.approx(outer.duration_s - dec.duration_s)
    assert moved[_counter("query.tpu")] >= 0.002
    assert sum(moved.values()) == pytest.approx(outer.duration_s, rel=1e-9)


@pytest.mark.parametrize("scope", ["suppressed", "counters_muted", "fused_build_scope"])
def test_a_muting_scope_moves_no_counter(scope):
    from greptimedb_tpu.parallel.tile_cache import fused_build_scope

    scopes = {
        "suppressed": tracing.suppressed, "counters_muted": tracing.counters_muted,
        "fused_build_scope": fused_build_scope,
    }
    before = _self_seconds()
    tracing.EXPORTER.clear()
    with scopes[scope]():
        with tracing.span("query.tpu") as sp, tracing.stage("tile.decode") as st:
            _spin(0.001)
    assert not any(_moved(before).values())
    assert st.duration_s >= 0.001  # the clock is still read: call sites feed on it
    assert sp.duration() >= 0.001
    # suppressed records nothing anywhere; a muted span is still a span
    assert len(tracing.EXPORTER.spans()) == (0 if scope == "suppressed" else 1)


def test_span_duration_is_monotonic_and_end_is_start_plus_it():
    with tracing.span("tile.build") as sp:
        _spin(0.001)
        assert 0 < sp.duration() < 1.0  # readable while open
    assert sp.stage.duration_s == sp.duration() >= 0.001
    assert sp.end == pytest.approx(sp.start + sp.duration())


# ---- one request through the real server -----------------------------------

def test_one_sql_request_moves_every_stage_of_its_path(warm_db, server):
    _post_sql(server, Q)  # the server's own first request
    warm_db._plan_cache.clear()  # so that this request plans
    before, root_before = _self_seconds(), metrics.HTTP_REQUEST_S.total()
    doc = _post_sql(server, Q)
    assert len(doc["output"][0]["records"]["rows"]) > 0
    moved = _moved(before)
    root = metrics.HTTP_REQUEST_S.total() - root_before
    for name in REQUEST_PATH:
        assert moved[_counter(name)] > 0, name
    assert moved[_counter("query.cpu")] == 0
    assert root > 0 and sum(moved.values()) == pytest.approx(root, rel=0.01)


def test_a_profiler_session_holds_the_stages_nested_by_time(warm_db, server, tmp_path):
    import jax
    from jax.profiler import ProfileData

    _post_sql(server, Q)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _post_sql(server, Q)
    finally:
        jax.profiler.stop_trace()
    (xplane,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    found: dict = {}
    for plane in ProfileData.from_file(xplane).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in REQUEST_PATH:
                    found.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                    )
    assert set(found) >= set(REQUEST_PATH) - {"query.plan"}, sorted(found)
    chain = [found[n][0] for n in ("http.request", "query.tpu", "tile.readback", "tile.decode")]
    for outer, inner in zip(chain, chain[1:]):
        assert outer[0] <= inner[0] and inner[1] <= outer[1]
    assert found["http.request"][0][2].get("route") == "/v1/sql"
    # render and write follow the engine's work, inside the request
    assert chain[1][1] <= found["http.render"][0][0]
    assert found["http.render"][0][1] <= found["http.write"][0][0]
    assert found["http.write"][0][1] <= chain[0][1]


# ---- one interval, one measurement -----------------------------------------

def test_the_flight_recorders_split_is_the_stages_durations(warm_db, monkeypatch):
    closed = []
    plain_exit = tracing.stage.__exit__

    def exit_and_note(self, *exc):
        out = plain_exit(self, *exc)
        closed.append((self.name, self.duration_s))
        return out

    monkeypatch.setattr(tracing.stage, "__exit__", exit_and_note)
    cursor = fr.RECORDER.cursor()
    warm_db.sql_one(Q)
    rec = [r for r in fr.RECORDER.since(cursor) if r.table == "public.cpu" and not r.ghost][-1]
    took = {name: seconds * 1000.0 for name, seconds in closed}
    assert [n for n, _s in closed].count("tile.readback") == 1
    assert rec.stage_ms("dispatch") == pytest.approx(took["tile.dispatch"], rel=1e-9)
    assert rec.stage_ms("readback_decode") == pytest.approx(took["tile.decode"], rel=1e-9)
    assert rec.stage_ms("readback_transfer") + rec.stage_ms("readback_decode") == pytest.approx(
        took["tile.readback"], rel=1e-9
    )
    assert "greptime_tile_readback_ms" not in metrics.REGISTRY.render()  # the third histogram of one number
