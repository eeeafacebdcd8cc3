"""Stages on the write path (insert -> logical -> split -> WAL -> memtable ->
flush -> SST encode / index -> compaction), each on the one stage clock.

The contracts:
  * every interval is one `tracing.stage`: its twelve counters of
    `metrics.STAGE_SELF_S` move on insert -> flush -> compaction, and on the
    direct path the five `write.*` self seconds sum to what the batch added
    to `WRITE_BATCH_S`;
  * a counter holds seconds on the thread that ran the stage: the region
    worker's stages are roots on its thread, and hand their durations back
    in the request's own dict (the `write.region` span's attributes);
  * the five histograms operators have documented observe the stages'
    durations (one interval, one measurement);
  * `flush.region` says who asked (`cause`), `WRITE_STALL_S` is the stall
    flushes' seconds, and the byte counters are the files' own sizes;
  * `suppressed()` and `counters_muted()` move no stage counter.
"""

import glob
import os
import time
from concurrent.futures import Future

import numpy as np
import pyarrow as pa
import pytest
from test_stage_clocks import _counter, _moved, _self_seconds

from greptimedb_tpu.database import Database
from greptimedb_tpu.storage.worker import _WriteRequest
from greptimedb_tpu.utils import metrics, tracing
from greptimedb_tpu.utils.config import Config

T0, HOSTS = 1_767_225_600_000, 8
WRITE_STAGES = ("write.batch", "write.logical", "write.split", "write.wal", "write.memtable")
FLUSH_STAGES = ("flush.region", "flush.sort", "sst.encode", "sst.index")
COMPACT_STAGES = ("compact.region", "compact.read", "compact.merge")
TRANSPARENT = "flush.windows"


def _database(tmp_path, partitions: int = 1, **storage) -> Database:
    cfg = Config()
    for key, value in storage.items():
        setattr(cfg.storage, key, value)
    db = Database(data_home=str(tmp_path / "db"), config=cfg)
    # compaction when the test asks: the scheduler's thread (a flush wakes
    # it) is stopped, its `run_once` stays
    db.storage.compactor.stop()
    db.sql(
        "CREATE TABLE cpu (hostname STRING, ts TIMESTAMP(3) TIME INDEX,"
        " usage_user DOUBLE, PRIMARY KEY (hostname))"
        + (f" PARTITION BY HASH (hostname) PARTITIONS {partitions}" if partitions > 1 else "")
    )
    return db


@pytest.fixture()
def db(tmp_path):
    db = _database(tmp_path)
    yield db
    db.close()


@pytest.fixture()
def sharded_db(tmp_path):
    db = _database(tmp_path, partitions=2)
    yield db
    db.close()


def _rows(tick_lo: int, tick_hi: int) -> pa.RecordBatch:
    """`HOSTS` series, one row a tick each: a batch of one time range."""
    ticks = np.arange(tick_lo, tick_hi, dtype=np.int64)
    n = len(ticks) * HOSTS
    return pa.record_batch({
        "hostname": pa.array(np.tile([f"host_{i}" for i in range(HOSTS)], len(ticks))),
        "ts": pa.array(T0 + np.repeat(ticks, HOSTS) * 10_000, pa.timestamp("ms")),
        "usage_user": pa.array(np.random.default_rng(tick_lo).uniform(0, 100, n)),
    })


def _logical(db: Database) -> str:
    db.sql("CREATE TABLE phy (ts TIMESTAMP TIME INDEX, val DOUBLE) WITH ('physical_metric_table' = '')")
    db.sql(
        "CREATE TABLE reqs (ts TIMESTAMP TIME INDEX, val DOUBLE, host STRING, job STRING,"
        " PRIMARY KEY (host, job)) WITH ('on_physical_table' = 'phy')"
    )
    return "reqs"


def _logical_rows(n_hosts: int = 6, ticks: int = 20) -> pa.Table:
    n = n_hosts * ticks
    return pa.table({
        "ts": pa.array(T0 + np.repeat(np.arange(ticks, dtype=np.int64), n_hosts) * 1000,
                       pa.timestamp("ms")),
        "val": pa.array(np.arange(n, dtype=np.float64)),
        "host": pa.array(np.tile([f"h{i}" for i in range(n_hosts)], ticks)),
        "job": pa.array(["nginx"] * n),
    })


def _files(db: Database, table: str = "cpu") -> list:
    return [
        f for rid in db.catalog.table(table, "public").region_ids
        for f in db.storage.region(rid).files()
    ]


def _size(files: list) -> int:
    return sum(f.file_size + f.index_file_size for f in files)  # by hand, not `stored_bytes`


@pytest.fixture()
def closed(monkeypatch):
    """Every stage that closes, from whatever thread: (name, seconds, attrs)."""
    seen: list = []
    plain_exit = tracing.stage.__exit__

    def exit_and_note(self, *exc):
        out = plain_exit(self, *exc)
        seen.append((self.name, self.duration_s, dict(self.attrs)))
        return out

    monkeypatch.setattr(tracing.stage, "__exit__", exit_and_note)
    return seen


def _took(closed: list, name: str) -> float:
    return sum(seconds for n, seconds, _a in closed if n == name)


# ---- every interval is a stage -----------------------------------------------

def test_the_stages_have_one_counter_each_and_the_window_stage_none():
    names = WRITE_STAGES + FLUSH_STAGES + COMPACT_STAGES
    counters = [metrics.STAGE_SELF_S[name] for name in names]
    assert len({c.name for c in counters}) == 12
    for name, counter in zip(names, counters):
        assert counter.name == f"greptime_stage_self_seconds_{name.replace('.', '_')}_total"
        module_name = "STAGE_SELF_S_" + name.replace(".", "_").upper()
        assert getattr(metrics, module_name) is counter  # benchmark readers find it by this name
    assert TRANSPARENT not in metrics.STAGE_SELF_S and "write.region" not in metrics.STAGE_SELF_S
    for name in ("WRITE_BATCH_S", "WRITE_STALL_S", "FLUSH_SST_BYTES", "COMPACTION_INPUT_BYTES",
                 "COMPACTION_OUTPUT_BYTES", "COMPACTION_DISCARDED_BYTES"):
        assert type(getattr(metrics, name)).__name__ == "Counter", name


def test_insert_flush_compact_moves_every_counter_and_the_bytes_are_the_files(db):
    before = _self_seconds()
    counts = {
        name: getattr(metrics, name).total()
        for name in ("FLUSH_SST_BYTES", "COMPACTION_INPUT_BYTES", "COMPACTION_OUTPUT_BYTES",
                     "COMPACTION_DISCARDED_BYTES", "COMPACTION_TOTAL", "FLUSH_TOTAL")
    }
    for lo in (0, 100):  # two flushes of one run: small neighbours, which a round merges
        assert db.insert_rows("cpu", _rows(lo, lo + 100)) == 100 * HOSTS
        db.storage.flush_all()
    level0 = _files(db)
    assert len(level0) == 2 and {f.level for f in level0} == {0}
    assert db.storage.compactor.run_once() == 1
    (merged,) = _files(db)
    assert merged.level == 1 and merged.num_rows == 200 * HOSTS
    moved = _moved(before)
    for name in WRITE_STAGES[:1] + WRITE_STAGES[2:] + FLUSH_STAGES + COMPACT_STAGES:
        assert moved[_counter(name)] > 0, name
    assert moved[_counter("write.logical")] == 0  # a mito table

    def delta(name):
        return getattr(metrics, name).total() - counts[name]

    assert delta("FLUSH_TOTAL") == 2 and delta("COMPACTION_TOTAL") == 1
    assert delta("FLUSH_SST_BYTES") == _size(level0)
    assert delta("COMPACTION_INPUT_BYTES") == _size(level0)
    assert delta("COMPACTION_OUTPUT_BYTES") == _size([merged])
    assert delta("COMPACTION_DISCARDED_BYTES") == 0
    assert all(f.index_file_size > 0 for f in level0 + [merged])  # the index bytes are in them


def test_a_refused_commit_counts_its_output_as_discarded(db, monkeypatch):
    for lo in (0, 100):
        db.insert_rows("cpu", _rows(lo, lo + 100))
        db.storage.flush_all()
    region = db.storage.region(db.catalog.table("cpu", "public").region_ids[0])
    monkeypatch.setattr(region, "apply_compaction", lambda adds, removes: False)
    before = {n: getattr(metrics, n).total() for n in
              ("COMPACTION_INPUT_BYTES", "COMPACTION_OUTPUT_BYTES", "COMPACTION_DISCARDED_BYTES")}
    assert db.storage.compactor.run_once() == 0
    assert metrics.COMPACTION_DISCARDED_BYTES.total() > before["COMPACTION_DISCARDED_BYTES"]
    assert metrics.COMPACTION_INPUT_BYTES.total() == before["COMPACTION_INPUT_BYTES"]
    assert metrics.COMPACTION_OUTPUT_BYTES.total() == before["COMPACTION_OUTPUT_BYTES"]
    assert len(_files(db)) == 2  # and the inputs stay


def test_a_round_with_nothing_to_merge_opens_no_stage(db, closed):
    db.insert_rows("cpu", _rows(0, 50))
    db.storage.flush_all()
    del closed[:]
    before = _self_seconds()
    assert db.storage.compactor.run_once() == 0
    db.storage.flush_all()  # nothing in any memtable
    assert closed == [] and not any(_moved(before).values())


def test_a_logical_table_moves_write_logical_and_hashes_its_label_sets(db, closed):
    table = _logical(db)
    before, hashes = _self_seconds(), metrics.METRIC_TSID_HASHES.total()
    assert db.insert_rows(table, _logical_rows(n_hosts=6)) == 120
    moved = _moved(before)
    for name in WRITE_STAGES:
        assert moved[_counter(name)] > 0, name
    assert metrics.METRIC_TSID_HASHES.total() - hashes == 6
    (attrs,) = [a for n, _s, a in closed if n == "write.logical"]
    assert (attrs["table"], attrs["rows"], attrs["labels"], attrs["tsids"]) == ("reqs", 120, 2, 6)
    # the stage ends where the physical table's write begins
    names = [n for n, _s, _a in closed]
    assert names.index("write.logical") < names.index("write.split") < names.index("write.batch")


# ---- per thread, the self times close ----------------------------------------

@pytest.mark.parametrize("logical", [False, True], ids=["mito", "logical"])
def test_on_the_direct_path_the_write_stages_sum_to_write_batch_s(db, closed, logical):
    table = _logical(db) if logical else "cpu"
    rows = _logical_rows() if logical else _rows(0, 100)
    db.insert_rows(table, rows)  # schemas cached, the region's worker never started
    del closed[:]
    before, root = _self_seconds(), metrics.WRITE_BATCH_S.total()
    db.insert_rows(table, rows)
    moved = _moved(before)
    inclusive = metrics.WRITE_BATCH_S.total() - root
    assert inclusive > 0
    assert sum(moved[_counter(name)] for name in WRITE_STAGES) == pytest.approx(inclusive, rel=1e-6)
    assert sum(moved.values()) == pytest.approx(inclusive, rel=1e-6)  # and nothing else moved
    assert inclusive == pytest.approx(_took(closed, "write.batch"), rel=1e-9)
    (split,) = [a for n, _s, a in closed if n == "write.split"]
    assert split["regions"] == 1
    (wal,) = [a for n, _s, a in closed if n == "write.wal"]
    assert wal["group"] == 1 and wal["bytes"] > 0
    assert "write.region" not in [n for n, _s, _a in closed]  # direct: no future to wait on


def test_the_region_workers_stages_are_roots_on_its_thread(sharded_db, closed):
    """Two regions: the batch is pipelined, WAL and memtable run on the
    workers, and the caller's `write.batch` keeps the wait as self time."""
    before, root = _self_seconds(), metrics.WRITE_BATCH_S.total()
    sharded_db.insert_rows("cpu", _rows(0, 100))
    moved = _moved(before)
    inclusive = metrics.WRITE_BATCH_S.total() - root
    on_caller = moved[_counter("write.batch")] + moved[_counter("write.split")]
    assert on_caller == pytest.approx(inclusive, rel=1e-6)
    assert moved[_counter("write.wal")] > 0 and moved[_counter("write.memtable")] > 0
    assert len([n for n, _s, _a in closed if n == "write.wal"]) == 2


def test_the_frontends_insert_rows_opens_the_same_stages(tmp_path):
    """The distributed twin: `write.batch` and `write.split` on the frontend's
    thread; the datanodes (Flight handlers of this process) run WAL and
    memtable as roots of their own threads."""
    import time as _time

    from greptimedb_tpu.distributed.flight import FlightDatanode
    from greptimedb_tpu.distributed.frontend import Frontend
    from greptimedb_tpu.distributed.kv import MemoryKvBackend
    from greptimedb_tpu.distributed.meta_service import MetasrvServer
    from greptimedb_tpu.distributed.metasrv import Metasrv

    home = str(tmp_path / "shared")
    datanodes = {i: FlightDatanode(i, home) for i in range(2)}
    metasrv = Metasrv(MemoryKvBackend(), None)
    for i, dn in datanodes.items():
        metasrv.register_datanode(i, dn.location.removeprefix("grpc://"))
        metasrv.handle_heartbeat(i, [], _time.time() * 1000)
    server = MetasrvServer(metasrv).start()
    fe = Frontend(home, [server.address])
    try:
        fe.sql(
            "CREATE TABLE cpu (hostname STRING, ts TIMESTAMP(3) TIME INDEX,"
            " usage_user DOUBLE, PRIMARY KEY (hostname)) PARTITION BY HASH (hostname) PARTITIONS 2"
        )
        before, root = _self_seconds(), metrics.WRITE_BATCH_S.total()
        split = metrics.INGEST_SPLIT_MS.total()
        assert fe.insert_rows("cpu", _rows(0, 20)) == 20 * HOSTS
        moved = _moved(before)
        inclusive = metrics.WRITE_BATCH_S.total() - root
        assert inclusive > 0 and metrics.INGEST_SPLIT_MS.total() - split == 1
        on_frontend = moved[_counter("write.batch")] + moved[_counter("write.split")]
        assert on_frontend == pytest.approx(inclusive, rel=1e-6)
        assert moved[_counter("write.wal")] > 0 and moved[_counter("write.memtable")] > 0
    finally:
        fe.close()
        server.stop()
        for dn in datanodes.values():
            dn.shutdown()


def test_no_by_hand_clock_is_left_on_the_write_path():
    import inspect

    from greptimedb_tpu.storage import compaction, engine, region, sst, worker

    for module in (compaction, engine, region, sst, worker):
        assert "perf_counter" not in inspect.getsource(module), module.__name__
    assert "perf_counter" not in inspect.getsource(Database._write_batch_admitted)
    assert not hasattr(worker.RegionWorkerLoop, "_stamp_stages")


# ---- one interval, one measurement -------------------------------------------

def test_each_histogram_observes_its_stages_duration(sharded_db, closed):
    hists = {
        "write.split": (metrics.INGEST_SPLIT_MS, 1000.0),
        "write.wal": (metrics.INGEST_WAL_MS, 1000.0),
        "write.memtable": (metrics.INGEST_MEMTABLE_MS, 1000.0),
        "flush.windows": (metrics.INGEST_FLUSH_ENCODE_MS, 1000.0),
        "flush.region": (metrics.FLUSH_ELAPSED, 1.0),
    }
    before = {name: (h.sum(), h.total()) for name, (h, _x) in hists.items()}
    sharded_db.insert_rows("cpu", _rows(0, 100))  # pipelined: two workers
    rid = sharded_db.catalog.table("cpu", "public").region_ids[0]
    sharded_db.storage.write(rid, _rows(100, 110))  # and a direct write
    sharded_db.storage.flush_all()
    for name, (hist, times) in hists.items():
        stages = [seconds for n, seconds, _a in closed if n == name]
        assert hist.total() - before[name][1] == len(stages) > 0, name
        assert hist.sum() - before[name][0] == pytest.approx(sum(stages) * times, rel=1e-9), name
    # sort + encode + index of one flush, as before: the window stage holds them
    for name in ("flush.sort", "sst.encode", "sst.index"):
        assert 0 < _took(closed, name) < _took(closed, "flush.windows"), name
    assert _took(closed, "flush.windows") < _took(closed, "flush.region")


def test_write_region_carries_the_workers_stage_durations_with_no_parent_span(sharded_db, closed):
    assert tracing.current_span() is None
    tracing.EXPORTER.clear()
    sharded_db.insert_rows("cpu", _rows(0, 100))
    spans = [s for s in tracing.EXPORTER.spans() if s.name == "write.region"]
    assert len(spans) == 2 and all(s.parent_id is None for s in spans)
    rids = sharded_db.catalog.table("cpu", "public").region_ids
    assert sorted(s.attributes["region"] for s in spans) == sorted(rids)
    wal_ms = sorted(round(s * 1000, 3) for n, s, _a in closed if n == "write.wal")
    mem_ms = sorted(round(s * 1000, 3) for n, s, _a in closed if n == "write.memtable")
    assert sorted(s.attributes["wal_ms"] for s in spans) == wal_ms
    assert sorted(s.attributes["memtable_ms"] for s in spans) == mem_ms
    assert sum(s.attributes["rows"] for s in spans) == 100 * HOSTS
    assert not hasattr(sharded_db.storage.region(rids[0]), "last_write_stage_ms")


def test_a_merged_frame_hands_each_request_its_group_writes(sharded_db, closed):
    """A drained group through the worker: one frame, one pair of stages,
    and every request's own dict holds what they measured."""
    rid = sharded_db.catalog.table("cpu", "public").region_ids[0]
    reqs = [
        _WriteRequest(rid, pa.record_batch({
            "hostname": pa.array([f"gh_{i}"]),
            "ts": pa.array([T0 + i], pa.timestamp("ms")),
            "usage_user": pa.array([1.0]),
        }), Future(), {})
        for i in range(3)
    ]
    sharded_db.storage.workers._worker_for(rid)._handle(reqs)
    assert [r.future.result(timeout=30) for r in reqs] == [1, 1, 1]
    ((_n, wal_s, wal),) = [c for c in closed if c[0] == "write.wal"]
    assert wal["group"] == 3
    for r in reqs:
        assert r.stages["group_writes"] == 3
        assert r.stages["wal_ms"] == pytest.approx(wal_s * 1000, rel=1e-9)
        assert r.stages["memtable_ms"] == pytest.approx(_took(closed, "write.memtable") * 1000)
        assert not hasattr(r.future, "stage_ms")
    solo = _WriteRequest(rid, reqs[0].batch, Future(), {})
    sharded_db.storage.workers._worker_for(rid)._handle([solo])
    assert sorted(solo.stages) == ["memtable_ms", "wal_ms"]  # no group: no `group_writes`


# ---- who asked for the flush --------------------------------------------------

def _causes(closed: list) -> list:
    return [a["cause"] for n, _s, a in closed if n == "flush.region"]


def test_flush_cause_manual(db, closed):
    db.insert_rows("cpu", _rows(0, 10))
    db.sql("ADMIN flush_table('cpu')")
    db.insert_rows("cpu", _rows(10, 20))
    db.storage.flush_all()
    assert _causes(closed) == ["manual", "manual"]
    (_n, _s, attrs) = [c for c in closed if c[0] == "flush.region"][-1]
    assert (attrs["rows"], attrs["files"]) == (10 * HOSTS, 1)


@pytest.mark.parametrize("background", [True, False], ids=["flush_scheduler", "inline"])
def test_flush_cause_threshold(tmp_path, closed, background):
    db = _database(tmp_path, write_buffer_size_mb=0, async_flush_enable=background)
    stalled = metrics.WRITE_STALL_S.total()
    try:
        db.insert_rows("cpu", _rows(0, 10))
        if background:
            db.storage.flusher.wait_idle()
        assert _causes(closed) == ["threshold"]
        assert metrics.WRITE_STALL_S.total() == stalled  # nobody waited for it
        assert (db.storage.flusher is not None) == background
    finally:
        db.close()


def test_flush_cause_stall_and_its_seconds(db, closed, monkeypatch):
    db.insert_rows("cpu", _rows(0, 10))
    stalls, seconds = metrics.WRITE_STALL_TOTAL.total(), metrics.WRITE_STALL_S.total()
    monkeypatch.setattr(db.storage.buffer_mgr, "should_stall", lambda: True)
    before, root = _self_seconds(), metrics.WRITE_BATCH_S.total()
    db.insert_rows("cpu", _rows(10, 20))
    monkeypatch.undo()
    assert _causes(closed) == ["stall"]
    assert metrics.WRITE_STALL_TOTAL.total() - stalls == 1
    assert metrics.WRITE_STALL_S.total() - seconds == pytest.approx(_took(closed, "flush.region"))
    # the foreground write waited for it: the flush's stages are inside its batch
    assert sum(_moved(before).values()) == pytest.approx(
        metrics.WRITE_BATCH_S.total() - root, rel=1e-6
    )
    assert len(_files(db)) == 1


# ---- scopes that move nothing -------------------------------------------------

@pytest.mark.parametrize("scope", ["suppressed", "counters_muted"])
def test_a_muting_scope_moves_no_write_path_counter(db, scope):
    scopes = {"suppressed": tracing.suppressed, "counters_muted": tracing.counters_muted}
    before, root = _self_seconds(), metrics.WRITE_BATCH_S.total()
    rows, wal = metrics.WRITE_ROWS_TOTAL.total(), metrics.INGEST_WAL_MS.total()
    tracing.EXPORTER.clear()
    with scopes[scope]():
        for lo in (0, 100):
            db.insert_rows("cpu", _rows(lo, lo + 100))
            db.storage.flush_all()
        assert db.storage.compactor.run_once() == 1
    assert not any(_moved(before).values())
    assert metrics.WRITE_BATCH_S.total() == root
    assert tracing.EXPORTER.spans() == []
    # the work was done and counted; only the clocks' counters stood still
    assert metrics.WRITE_ROWS_TOTAL.total() - rows == 200 * HOSTS
    assert metrics.INGEST_WAL_MS.total() - wal == 2


# ---- on the device trace's clock ----------------------------------------------

def test_a_profiler_session_holds_a_background_flush_and_a_compaction_by_name(tmp_path):
    import jax
    from jax.profiler import ProfileData

    db = _database(tmp_path, write_buffer_size_mb=0)  # every write schedules a flush
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=options)
        try:
            for lo in (0, 100):
                db.insert_rows("cpu", _rows(lo, lo + 100))
                db.storage.flusher.wait_idle()
            deadline = time.monotonic() + 30
            while len(_files(db)) < 2 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert db.storage.compactor.run_once() == 1
        finally:
            jax.profiler.stop_trace()
    finally:
        db.close()
    (xplane,) = glob.glob(
        os.path.join(str(tmp_path / "trace"), "plugins", "profile", "*", "*.xplane.pb")
    )
    found: dict = {}
    for plane in ProfileData.from_file(xplane).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.split(".")[0] in ("write", "flush", "sst", "compact"):
                    found.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats), line.name)
                    )
    wanted = set(WRITE_STAGES + FLUSH_STAGES + COMPACT_STAGES + (TRANSPARENT,)) - {"write.logical"}
    assert set(found) >= wanted, sorted(wanted - set(found))
    flush = found["flush.region"][0]
    assert flush[2].get("cause") == "threshold" and flush[2].get("files") == 1
    inside = [found[n][0] for n in ("flush.windows", "flush.sort")]
    for start, end, _stats, _line in inside:
        assert flush[0] <= start and end <= flush[1]
    levels = sorted(int(e[2]["level"]) for e in found["sst.encode"])
    assert levels == [0, 0, 1]
    merge = found["compact.region"][0]
    assert int(merge[2]["picks"]) == 1 and int(merge[2]["merges"]) == 1
    assert merge[0] <= found["compact.read"][0][0] and found["compact.merge"][0][1] <= merge[1]
