"""Device flight recorder: the glass-box introspection of the TPU hot
path (utils/flight_recorder.py) and its three surfaces —
information_schema.{device_dispatches, tile_cache_entries,
device_memory}, the EXPLAIN ANALYZE device-stage split, and
/debug/tile.

The hard contracts:
  * a warm tile dispatch lands ONE record whose trace_id matches the
    statement's root span (self-trace on) — the e2e acceptance check;
  * EXPLAIN ANALYZE renders the real per-stage device split with
    nonzero dispatch + readback;
  * a recorder failure (fault point `recorder.emit`) never fails the
    recorded query — the trace.self_write pattern;
  * the ring is bounded drop-oldest; recorder.enabled=false is a no-op.
"""

import json
import math
import time

import pyarrow as pa
import pytest

from greptimedb_tpu.database import Database
from greptimedb_tpu.utils import flight_recorder as fr
from greptimedb_tpu.utils import metrics
from greptimedb_tpu.utils.config import Config


@pytest.fixture()
def db(tmp_path):
    d = Database(data_home=str(tmp_path / "db"))
    yield d
    d.close()


def _mk_cpu(db, name="cpu"):
    db.sql(
        f"CREATE TABLE {name} (host STRING, region STRING, ts TIMESTAMP TIME"
        f" INDEX, usage_user DOUBLE, usage_system DOUBLE,"
        f" PRIMARY KEY (host, region))"
    )


def _load(db, name="cpu", hosts=6, ticks=120, t0=0):
    rows = []
    for t in range(ticks):
        for h in range(hosts):
            rows.append(
                f"('host_{h}', 'r{h % 2}', {t0 + t * 1000},"
                f" {t % 13 + h}, {(t + h) % 7})"
            )
    db.sql(f"INSERT INTO {name} VALUES " + ",".join(rows))


Q = (
    "SELECT host, time_bucket('30s', ts) AS tb, avg(usage_user) AS au,"
    " max(usage_system) AS ms, count(*) AS c FROM cpu GROUP BY host, tb"
)


def _warm(db, q=Q, reps=3):
    """Cold + enough reps to get past cold-serve/build onto the warm
    device dispatch; returns the last result."""
    out = None
    for _ in range(reps):
        out = db.sql_one(q)
    return out


def _dispatch_rows(db, table_key="public.cpu"):
    t = db.sql_one(
        "SELECT * FROM information_schema.device_dispatches"
    )
    rows = t.to_pylist()
    return [r for r in rows if r["table_name"] == table_key]


# ---- ring unit behavior ----------------------------------------------------

def test_ring_bounded_drop_oldest():
    rec = fr.FlightRecorder(ring_size=4)
    for i in range(10):
        rec.emit(fr.DispatchRecord(table=f"t{i}"))
    snap = rec.snapshot()
    assert len(snap) == 4
    assert [r.table for r in snap] == ["t6", "t7", "t8", "t9"]
    assert rec.dropped == 6
    # seq is monotonic and survives eviction
    assert [r.seq for r in snap] == [7, 8, 9, 10]
    assert rec.since(8) == snap[2:]


def test_configure_resize_preserves_newest():
    rec = fr.FlightRecorder(ring_size=8)
    for i in range(8):
        rec.emit(fr.DispatchRecord(table=f"t{i}"))

    class _Cfg:
        enabled = True
        ring_size = 3

    rec.configure(_Cfg())
    assert [r.table for r in rec.snapshot()] == ["t5", "t6", "t7"]


# ---- e2e: warm dispatch recorded, trace-linked, EXPLAIN split --------------

def test_warm_dispatch_recorded_and_trace_linked(tmp_path):
    """Acceptance: a warm tile query's dispatch appears in
    information_schema.device_dispatches with nonzero dispatch+readback
    stage ms, and its trace_id is the SQL statement's root span's."""
    from greptimedb_tpu.utils import tracing

    cfg = Config()
    cfg.trace.enabled = True
    cfg.trace.sample_ratio = 1.0
    # keep kept spans in the ring long enough to inspect (the writer
    # would otherwise drain them into the trace table mid-assert)
    cfg.trace.export_interval_s = 3600.0
    db = Database(data_home=str(tmp_path / "db"), config=cfg)
    try:
        _mk_cpu(db)
        _load(db)
        db.sql("ADMIN flush_table('cpu')")
        _warm(db)
        tracing.EXPORTER.clear()
        cursor = fr.RECORDER.cursor()
        table = db.sql_one(Q)
        assert table.num_rows > 0
        new = [
            r for r in fr.RECORDER.since(cursor)
            if r.table == "public.cpu" and not r.ghost
        ]
        assert new, "warm tile query did not land a dispatch record"
        rec = new[-1]
        assert rec.strategy in ("sort", "hash"), rec.strategy
        assert rec.stage_ms("dispatch") > 0.0
        assert (
            rec.stage_ms("readback_transfer") > 0.0
            or rec.stage_ms("readback_decode") > 0.0
        )
        assert rec.bytes_down > 0
        assert rec.hbm_budget > 0
        assert rec.plan_fp
        # the same record through the SQL surface
        rows = _dispatch_rows(db)
        mine = [r for r in rows if r["seq"] == rec.seq]
        assert mine, "record not visible via information_schema"
        row = mine[0]
        assert row["dispatch_ms"] > 0.0
        assert row["readback_transfer_ms"] + row["readback_decode_ms"] > 0.0
        assert row["ghost"] == "false"
        # trace link: the statement's ROOT span owns the trace id the
        # recorder captured at dispatch time
        roots = [
            s for s in tracing.EXPORTER.spans()
            if s.name == "statement.sql" and s.parent_id is None
            and s.trace_id == rec.trace_id
        ]
        assert roots, (
            "device_dispatches trace_id does not match any statement.sql "
            f"root span (trace_id={rec.trace_id!r})"
        )
        assert Q[:40] in roots[0].attributes.get("statement", "")
    finally:
        db.close()


def test_explain_analyze_device_stage_split(db):
    """EXPLAIN ANALYZE on a warm tile query renders the per-stage device
    split — upload/compile/dispatch/readback-transfer/readback-decode —
    with nonzero dispatch + readback, pulled from the recorder."""
    _mk_cpu(db)
    _load(db)
    db.sql("ADMIN flush_table('cpu')")
    _warm(db)
    out = db.sql_one("EXPLAIN ANALYZE " + Q)
    stages = [s.strip() for s in out["stage"].to_pylist()]
    mets = out["metrics"].to_pylist()
    for want in (
        "device.upload", "device.compile", "device.dispatch",
        "device.readback_transfer", "device.readback_decode",
    ):
        assert want in stages, f"missing {want} in: {stages}"

    def ms_of(name):
        m = mets[stages.index(name)]
        return float(m.split("ms")[0]) if m and m[0].isdigit() else 0.0

    assert ms_of("device.dispatch") > 0.0
    assert ms_of("device.readback_transfer") + ms_of("device.readback_decode") > 0.0
    # per-region build legs render too (mode=warm on a resident entry)
    assert any(s == "device.region" for s in stages)


# ---- fault point: recording never fails the query --------------------------

def test_recorder_emit_fault_harmless(db):
    """The trace.self_write pattern: an injected recorder.emit failure
    must neither fail nor corrupt the recorded query — it lands in
    greptime_recorder_errors_total and the query result is unchanged."""
    from greptimedb_tpu.utils import fault_injection as fi

    _mk_cpu(db)
    _load(db)
    db.sql("ADMIN flush_table('cpu')")
    want = _warm(db)
    errs0 = metrics.RECORDER_ERRORS.get()
    with fi.REGISTRY.armed(
        "recorder.emit", fail_times=100, error=RuntimeError("boom")
    ):
        got = db.sql_one(Q)
    assert metrics.RECORDER_ERRORS.get() > errs0
    assert got.num_rows == want.num_rows
    s1 = want.sort_by([("host", "ascending"), ("tb", "ascending")]).to_pydict()
    s2 = got.sort_by([("host", "ascending"), ("tb", "ascending")]).to_pydict()
    for c in s1:
        for x, y in zip(s1[c], s2[c]):
            if isinstance(x, float):
                assert math.isclose(x, y, rel_tol=1e-12) or (
                    math.isnan(x) and math.isnan(y)
                )
            else:
                assert x == y
    # healed: the next query records again
    c0 = fr.RECORDER.cursor()
    db.sql_one(Q)
    assert any(
        r.table == "public.cpu" for r in fr.RECORDER.since(c0)
    ), "recorder did not heal after the fault cleared"


# ---- off-switch ------------------------------------------------------------

def test_recorder_disabled_off_safe(tmp_path):
    cfg = Config()
    cfg.recorder.enabled = False
    db = Database(data_home=str(tmp_path / "db"), config=cfg)
    try:
        _mk_cpu(db)
        _load(db)
        db.sql("ADMIN flush_table('cpu')")
        fr.RECORDER.clear()
        c0 = fr.RECORDER.cursor()
        out = _warm(db)
        assert out.num_rows > 0
        assert fr.RECORDER.since(c0) == []
        t = db.sql_one("SELECT * FROM information_schema.device_dispatches")
        assert t.num_rows == 0
    finally:
        db.close()
        # restore the process-wide default for later tests
        fr.RECORDER.configure(Config().recorder)


def test_recorder_work_per_warm_query_bounded(tmp_path, monkeypatch):
    """The always-on recorder must not slow the warm tile dispatch: held
    by what it DOES per warm query, not by a stopwatch.  On: one draft,
    one record appended, a bounded and constant number of field writes,
    nothing dropped or failed.  Off: no draft, no record, no recorder
    counter moved, and every write site finds no draft to write to."""
    cfg = Config()
    cfg.storage.compaction_background_enable = False
    db = Database(data_home=str(tmp_path / "db"), config=cfg)
    try:
        _mk_cpu(db)
        _load(db)
        db.sql("ADMIN flush_table('cpu')")
        _warm(db, reps=4)  # cold + build + settle onto the warm path

        drafts: list = []
        writes: list = []  # (site, a draft was open)
        real_record = fr.DispatchRecord

        def counted_record(*a, **kw):
            rec = real_record(*a, **kw)
            drafts.append(rec)
            return rec

        monkeypatch.setattr(fr, "DispatchRecord", counted_record)
        for site in ("stage_add", "note", "flag", "mark", "add_bytes",
                     "region_build"):
            real = getattr(fr, site)

            def counted(*a, _real=real, _site=site, **kw):
                writes.append((_site, fr._draft() is not None))
                return _real(*a, **kw)

            monkeypatch.setattr(fr, site, counted)

        n = 5
        counters = (
            metrics.RECORDER_RECORDS, metrics.RECORDER_DROPPED,
            metrics.RECORDER_ERRORS,
        )
        # ---- on: one record a query, a constant handful of writes ----
        per_query = []
        recs0, dropped0, errs0 = (c.get() for c in counters)
        ring0 = len(fr.RECORDER.snapshot())
        c0 = fr.RECORDER.cursor()
        for _ in range(n):
            w0 = len(writes)
            db.sql_one(Q)
            per_query.append(len(writes) - w0)
        got = [
            r for r in fr.RECORDER.since(c0)
            if r.table == "public.cpu" and not r.ghost
        ]
        assert len(got) == n and len(drafts) == n
        assert all(r.stage_ms("dispatch") > 0.0 for r in got)
        assert all(set(r.stages_ms) <= set(fr.STAGES) for r in got)
        # every warm query leaves a record of the same shape: nothing in it
        # grows with the queries served
        assert len({
            (tuple(sorted(r.stages_ms)), len(r.regions), r.flags) for r in got
        }) == 1
        assert metrics.RECORDER_RECORDS.get() == recs0 + n
        assert metrics.RECORDER_DROPPED.get() == dropped0
        assert metrics.RECORDER_ERRORS.get() == errs0
        assert len(fr.RECORDER.snapshot()) == ring0 + n
        assert len(set(per_query)) == 1, per_query  # no growth by query
        assert 0 < per_query[0] <= 24, per_query
        assert all(open_ for _, open_ in writes)

        # ---- off: the same queries leave no trace of the recorder ----
        fr.RECORDER.enabled = False
        drafts.clear()
        writes.clear()
        c0 = fr.RECORDER.cursor()
        for _ in range(n):
            assert db.sql_one(Q).num_rows > 0
        assert fr.RECORDER.since(c0) == [] and fr.RECORDER.cursor() == c0
        assert drafts == []
        assert not any(open_ for _, open_ in writes)
        assert [c.get() for c in counters] == [recs0 + n, dropped0, errs0]
        assert len(fr.RECORDER.snapshot()) == ring0 + n
    finally:
        fr.RECORDER.enabled = True
        db.close()


def test_recorder_config_validation():
    from greptimedb_tpu.utils.errors import ConfigError

    cfg = Config()
    cfg.recorder.ring_size = 4
    with pytest.raises(ConfigError, match="recorder.ring_size"):
        cfg.validate()
    cfg = Config()
    cfg.recorder.enabled = "yes"
    with pytest.raises(ConfigError, match="recorder.enabled"):
        cfg.validate()


# ---- ghost labeling --------------------------------------------------------

def test_ghost_dispatches_labeled(db):
    """Dispatches run under the fused-build scope are recorded but
    labeled ghost, so per-query views can exclude the builder."""
    from greptimedb_tpu.parallel.tile_cache import fused_build_scope

    _mk_cpu(db)
    _load(db)
    db.sql("ADMIN flush_table('cpu')")
    _warm(db)
    c0 = fr.RECORDER.cursor()
    with fused_build_scope():
        db.sql_one(Q)
    ghosts = [
        r for r in fr.RECORDER.since(c0)
        if r.table == "public.cpu" and r.ghost
    ]
    assert ghosts, "builder-scope dispatch was not recorded as ghost"
    rows = _dispatch_rows(db)
    assert any(r["ghost"] == "true" for r in rows)


# ---- cache + memory introspection tables -----------------------------------

def test_tile_cache_entries_table(db):
    _mk_cpu(db)
    _load(db)
    db.sql("ADMIN flush_table('cpu')")
    _warm(db)
    t = db.sql_one(
        "SELECT * FROM information_schema.tile_cache_entries"
    )
    rows = [r for r in t.to_pylist() if r["table_name"] == "cpu"]
    assert rows, "no tile_cache_entries rows for the warmed table"
    kinds = {r["kind"] for r in rows}
    assert "column" in kinds
    cols = [r for r in rows if r["kind"] == "column"]
    assert all(r["device_bytes"] > 0 for r in cols)
    assert all(r["rows"] == 720 for r in cols)
    assert all(r["padded_rows"] >= r["rows"] for r in cols)
    assert all(r["last_hit_ms"] > 0 for r in cols)
    assert all(r["table_schema"] == "public" for r in rows)


def test_tile_cache_entries_delta_extend_count(tmp_path):
    cfg = Config()
    # a background compaction would merge the appended file into the
    # cached prefix, which takes the full rebuild by design
    cfg.storage.compaction_background_enable = False
    db = Database(data_home=str(tmp_path / "db"), config=cfg)
    try:
        _mk_cpu(db)
        _load(db)
        db.sql("ADMIN flush_table('cpu')")
        # prewarm consolidates the entry (and its sort order) on this
        # thread, so the append below finds it whatever the background
        # builder's timing
        db.prewarm(tables=["cpu"])
        _warm(db, reps=2)
        # append + flush: the entry delta-extends in place and the
        # counter surfaces through the introspection table
        merges0 = metrics.TILE_DELTA_MERGES.get()
        _load(db, ticks=6, t0=120 * 1000)
        db.sql("ADMIN flush_table('cpu')")
        _warm(db, reps=2)
        assert metrics.TILE_DELTA_MERGES.get() == merges0 + 1
        t = db.sql_one(
            "SELECT max(delta_extends) AS de FROM"
            " information_schema.tile_cache_entries WHERE table_name = 'cpu'"
        )
        assert t["de"][0].as_py() == 1
    finally:
        db.close()


def test_device_memory_table(db):
    _mk_cpu(db)
    _load(db)
    db.sql("ADMIN flush_table('cpu')")
    _warm(db)
    t = db.sql_one("SELECT * FROM information_schema.device_memory")
    rows = t.to_pylist()
    assert len(rows) == len(db.query_engine.tile_cache.devices)
    assert all(r["tile_budget"] > 0 for r in rows)
    assert all(r["tile_headroom"] == r["tile_budget"] - r["tile_in_use"]
               for r in rows)
    assert all(r["chunk_rows"] > 0 for r in rows)
    assert all(r["degrade_rounds"] >= 0 for r in rows)


# ---- /debug/tile -----------------------------------------------------------

def test_debug_tile_endpoint(db):
    import urllib.request

    from greptimedb_tpu.servers.http import HttpServer

    _mk_cpu(db)
    _load(db)
    db.sql("ADMIN flush_table('cpu')")
    _warm(db)
    server = HttpServer(db, "127.0.0.1:0").start()
    try:
        with urllib.request.urlopen(
            f"http://{server.address}/debug/tile?n=5&table=public.cpu",
            timeout=10,
        ) as resp:
            doc = json.loads(resp.read())
        assert doc["recorder"]["enabled"] is True
        assert doc["recorder"]["ring_size"] >= 16
        assert doc["dispatches"], "no dispatch tail in /debug/tile"
        assert len(doc["dispatches"]) <= 5
        last = doc["dispatches"][-1]
        assert last["table"] == "public.cpu"
        assert set(last["stages_ms"]) <= set(fr.STAGES)
        assert doc["entries"], "no tile-cache entries in /debug/tile"
        e = doc["entries"][0]
        assert e["rows"] == 720 and e["device_bytes"] > 0
        assert doc["memory"] and "bytes_in_use" in doc["memory"][0]
        assert doc["tile_cache"]["budget"] > 0
    finally:
        server.stop()


# ---- TQL strategy ----------------------------------------------------------

def test_tql_dispatch_recorded(db):
    """A warm TQL tile evaluation lands a strategy='tql' record."""
    db.sql(
        "CREATE TABLE reqs (host STRING, ts TIMESTAMP TIME INDEX,"
        " val DOUBLE, PRIMARY KEY (host))"
    )
    rows = []
    for t in range(240):
        for h in range(3):
            rows.append(f"('h{h}', {t * 1000}, {t * 2 + h})")
    db.sql("INSERT INTO reqs VALUES " + ",".join(rows))
    db.sql("ADMIN flush_table('reqs')")
    tql = "TQL EVAL (60, 230, '10s') rate(reqs[30s])"
    c0 = fr.RECORDER.cursor()
    for _ in range(3):
        out = db.sql_one(tql)
    assert out is not None and out.num_rows > 0
    recs = [
        r for r in fr.RECORDER.since(c0)
        if r.table == "public.reqs" and not r.ghost
    ]
    assert recs, "TQL tile path landed no recorder records"
    warm = [r for r in recs if r.stage_ms("dispatch") > 0]
    if not warm:
        pytest.skip("TQL tile path did not reach a warm dispatch")
    assert warm[-1].strategy == "tql"
    assert warm[-1].stage_ms("readback_transfer") > 0.0
