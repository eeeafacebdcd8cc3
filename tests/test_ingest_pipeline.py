"""Pipelined columnar ingest: WAL group commit, vectorized routing,
flush-overlapped writes (ISSUE 15).

Contracts pinned here:
  * group frames preserve per-write entry-id semantics — replay after a
    crash (torn tail included) is row-for-row equal to the frame-per-write
    ladder, and follower lag counts per-write entries under merged frames;
  * `ingest.group_commit = false` restores the legacy worker merge path
    (today's WAL bytes bit-for-bit);
  * the vectorized partition split / hash routing is bit-identical to the
    per-partition-mask legacy implementation;
  * flush overlap admits writes while an encode is in flight, bounded at
    2x the global write buffer;
  * the `ingest.group_commit` fault point fails the whole group atomically
    and the write path heals.
"""

from __future__ import annotations

import os
import zlib

import numpy as np
import pyarrow as pa
import pytest

from greptimedb_tpu.datatypes.data_type import ConcreteDataType
from greptimedb_tpu.datatypes.schema import ColumnSchema, Schema, SemanticType
from greptimedb_tpu.storage.engine import TimeSeriesEngine
from greptimedb_tpu.storage.wal import GROUP_FLAG, RegionWal, _HEADER
from greptimedb_tpu.utils import fault_injection as fi
from greptimedb_tpu.utils import metrics as m
from greptimedb_tpu.utils.config import Config, StorageConfig
from greptimedb_tpu.utils.errors import ConfigError


def _schema() -> Schema:
    return Schema(
        columns=[
            ColumnSchema("host", ConcreteDataType.STRING, SemanticType.TAG),
            ColumnSchema(
                "ts", ConcreteDataType.TIMESTAMP_MILLISECOND, SemanticType.TIMESTAMP
            ),
            ColumnSchema("val", ConcreteDataType.FLOAT64, SemanticType.FIELD),
        ]
    )


def _batch(hosts, ts, vals) -> pa.RecordBatch:
    return pa.RecordBatch.from_arrays(
        [
            pa.array(hosts, pa.string()),
            pa.array(ts, pa.timestamp("ms")),
            pa.array(vals, pa.float64()),
        ],
        schema=_schema().to_arrow(),
    )


def _mk_engine(tmp_path, name, **cfg) -> TimeSeriesEngine:
    sc = StorageConfig(data_home=str(tmp_path / name), **cfg)
    return TimeSeriesEngine(sc)


def _rows(table: pa.Table) -> list[tuple]:
    cols = [table[c].to_pylist() for c in table.column_names]
    return sorted(zip(*cols)) if cols else []


# ---- WAL group frames -------------------------------------------------------


def test_wal_group_frame_roundtrip(tmp_path):
    """append_group yields the SAME replay entries (ids + rows) as
    individual appends, from one frame."""
    solo = RegionWal(str(tmp_path / "solo.wal"))
    grouped = RegionWal(str(tmp_path / "group.wal"))
    batches = [
        _batch([f"h{i}"], [1000 + i], [float(i)]) for i in range(4)
    ]
    frames0 = m.INGEST_WAL_FRAMES.get()
    gw0 = m.INGEST_GROUP_WRITES.get()
    ids = grouped.append_group(batches)
    assert ids == [1, 2, 3, 4]
    assert grouped.last_entry_id == 4
    assert m.INGEST_WAL_FRAMES.get() - frames0 == 1
    assert m.INGEST_GROUP_WRITES.get() - gw0 == 4
    for b in batches:
        solo.append(b)
    got = [(e.entry_id, e.batch.to_pydict()) for e in grouped.replay(0)]
    want = [(e.entry_id, e.batch.to_pydict()) for e in solo.replay(0)]
    assert got == want
    # filtered replay starts mid-group
    assert [e.entry_id for e in grouped.replay(2)] == [3, 4]
    # a reopened wal recovers last_entry_id from the flagged header
    grouped.close()
    reopened = RegionWal(str(tmp_path / "group.wal"))
    assert reopened.last_entry_id == 4
    reopened.close()
    solo.close()


def test_wal_group_torn_tail_drops_whole_group(tmp_path):
    """A torn group frame drops the WHOLE group (all-or-nothing), earlier
    frames survive — the same recovery contract as torn solo frames."""
    path = str(tmp_path / "torn.wal")
    wal = RegionWal(path)
    wal.append_group([_batch(["a"], [1], [1.0]), _batch(["b"], [2], [2.0])])
    wal.append_group([_batch(["c"], [3], [3.0]), _batch(["d"], [4], [4.0])])
    wal.close()
    # tear into the LAST frame's payload
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size - 7)
    recovered = RegionWal(path)
    assert [e.entry_id for e in recovered.replay(0)] == [1, 2]
    assert recovered.last_entry_id == 2
    # and the next group reuses ids above the surviving tail
    ids = recovered.append_group(
        [_batch(["e"], [5], [5.0]), _batch(["f"], [6], [6.0])]
    )
    assert ids == [3, 4]
    recovered.close()


def test_wal_group_obsolete_mid_group(tmp_path):
    """obsolete() at a watermark INSIDE a group keeps exactly the
    sub-entries above it."""
    wal = RegionWal(str(tmp_path / "obs.wal"))
    wal.append_group([_batch([f"h{i}"], [i], [float(i)]) for i in range(4)])
    wal.obsolete(2)
    assert [e.entry_id for e in wal.replay(0)] == [3, 4]
    wal.close()


def test_group_commit_crash_parity(tmp_path):
    """Kill mid-ingest with group commit ON: replay equals the
    frame-per-write ladder row for row, torn-tail drop included."""
    from greptimedb_tpu.storage.region import Region

    def build(name, grouped: bool):
        wal = RegionWal(str(tmp_path / f"{name}.wal"))
        region = Region(1, str(tmp_path / name), _schema(), wal)
        writes = [
            _batch([f"h{i % 3}"], [100 + i], [float(i)]) for i in range(6)
        ]
        if grouped:
            region.write_group(writes[:3])
            region.write_group(writes[3:])
        else:
            for b in writes:
                region.write(b)
        wal.close()
        return str(tmp_path / f"{name}.wal")

    on_path = build("gc_on", True)
    off_path = build("gc_off", False)
    # crash: tear into the second group frame / the 4th solo frame, so the
    # survivors are writes 1-3 in BOTH ladders
    with open(on_path, "r+b") as f:
        f.truncate(os.path.getsize(on_path) - 5)
    # drop the last three solo frames byte-exactly: replay offsets differ,
    # so recompute the keep-prefix from frame headers
    import struct

    with open(off_path, "rb") as f:
        buf = f.read()
    pos, frames = 0, []
    while pos + _HEADER.size <= len(buf):
        length, _crc, _eid = _HEADER.unpack_from(buf, pos)
        frames.append((pos, _HEADER.size + length))
        pos += _HEADER.size + length
    keep = frames[2][0] + frames[2][1]  # first three frames
    with open(off_path, "r+b") as f:
        f.truncate(keep)

    from greptimedb_tpu.storage.region import Region as R2

    r_on = R2(1, str(tmp_path / "gc_on"), _schema(), RegionWal(on_path))
    r_off = R2(1, str(tmp_path / "gc_off"), _schema(), RegionWal(off_path))
    t_on, t_off = r_on.scan(), r_off.scan()
    assert _rows(t_on) == _rows(t_off)
    assert t_on.num_rows == 3  # the torn group vanished atomically
    assert r_on.applied_entry_id == r_off.applied_entry_id == 3


def test_follower_lag_entries_under_group_frames(tmp_path):
    """greptime_follower_lag_entries counts per-WRITE entries even when
    the leader committed them as merged frames."""
    from greptimedb_tpu.storage.region import Region
    from greptimedb_tpu.storage.remote_wal import RemoteWalManager

    wal_dir = str(tmp_path / "shared_wal")
    leader_mgr = RemoteWalManager(wal_dir)
    follower_mgr = RemoteWalManager(wal_dir)
    leader = Region(7, str(tmp_path / "leader"), _schema(), leader_mgr.region_wal(7))
    follower = Region(
        7, str(tmp_path / "leader"), _schema(),
        follower_mgr.region_wal(7), writable=False,
    )
    assert follower.stat().follower_lag_entries == 0
    # two merged groups of three writes = SIX entries of lag
    leader.write_group([_batch([f"a{i}"], [i], [1.0]) for i in range(3)])
    leader.write_group([_batch([f"b{i}"], [10 + i], [2.0]) for i in range(3)])
    # the follower's view of the shared log head advances on sync/stat
    follower.wal.advance_to(leader_mgr.store.last_entry_id("topic_3", 7))
    stat = follower.stat()
    assert stat.follower_lag_entries == 6
    assert m.FOLLOWER_LAG_ENTRIES.get(region="7") == 6
    applied, _refreshed = follower.follower_sync()
    assert applied == 6
    assert follower.stat().follower_lag_entries == 0
    assert _rows(follower.scan()) == _rows(leader.scan())
    leader_mgr.close()
    follower_mgr.close()


def test_group_commit_fault_point_atomic_and_heals(tmp_path):
    """An armed ingest.group_commit fault fails EVERY write of the group
    (no partial WAL/memtable state) and the write path heals."""
    engine = _mk_engine(tmp_path, "fault")
    engine.create_region(1, _schema())
    try:
        rows = engine.write_group(
            1, [_batch(["x"], [100], [1.0]), _batch(["y"], [101], [2.0])]
        )
        assert rows == [1, 1]
        plan = fi.REGISTRY.arm(
            "ingest.group_commit", fail_times=1, error=TimeoutError
        )
        region = engine.region(1)
        before = region.scan().num_rows
        wal_before = region.wal.last_entry_id
        with pytest.raises(TimeoutError):
            engine.write_group(
                1, [_batch(["p"], [200], [1.0]), _batch(["q"], [201], [2.0])]
            )
        # atomicity: no partial WAL append, no partial memtable rows
        assert plan.trips == 1
        assert region.scan().num_rows == before
        assert region.wal.last_entry_id == wal_before
        fi.REGISTRY.disarm()
        # heals: the next group commits, ids resume contiguously
        assert engine.write_group(1, [_batch(["r"], [300], [3.0])]) == [1]
        assert region.wal.last_entry_id == wal_before + 1
    finally:
        fi.REGISTRY.disarm()
        engine.close()


def test_group_commit_off_restores_legacy_merge_bytes(tmp_path):
    """ingest.group_commit=false: the worker's drain group goes through
    the legacy merge — WAL bytes bit-for-bit today's frame-per-merged-
    batch encoding."""
    engine = _mk_engine(tmp_path, "legacy", ingest_group_commit=False)
    engine.create_region(1, _schema())
    batches = [_batch([f"h{i}"], [i], [float(i)]) for i in range(3)]
    # drive the worker _handle directly with one drained group so the
    # merge is deterministic (no queue-timing dependence)
    from concurrent.futures import Future

    from greptimedb_tpu.storage.worker import _WriteRequest

    worker = engine.workers.workers[0]
    reqs = [_WriteRequest(1, b, Future()) for b in batches]
    worker._handle(reqs)
    for r in reqs:
        assert r.future.result(timeout=10) == 1
    wal_path = engine.region(1).wal.path
    engine.close()

    # expected legacy bytes: ONE solo frame of the merged batch
    merged = pa.Table.from_batches(batches).combine_chunks().to_batches()[0]
    expect = RegionWal(str(tmp_path / "expect.wal"))
    expect.append(engine.region(1)._conform(merged))
    expect.close()
    with open(wal_path, "rb") as f, open(expect.path, "rb") as g:
        assert f.read() == g.read()


def test_worker_group_commit_merges_frames(tmp_path):
    """With group commit ON, a drained group commits as ONE frame carrying
    one entry id per request: frames < writes by the counters."""
    engine = _mk_engine(tmp_path, "merge")
    engine.create_region(1, _schema())
    from concurrent.futures import Future

    from greptimedb_tpu.storage.worker import _WriteRequest

    frames0 = m.INGEST_WAL_FRAMES.get()
    writes0 = m.INGEST_WRITES_TOTAL.get()
    worker = engine.workers.workers[0]
    reqs = [
        _WriteRequest(1, _batch([f"h{i}"], [i], [float(i)]), Future())
        for i in range(5)
    ]
    worker._handle(reqs)
    assert [r.future.result(timeout=10) for r in reqs] == [1] * 5
    assert m.INGEST_WAL_FRAMES.get() - frames0 == 1
    assert m.INGEST_WRITES_TOTAL.get() - writes0 == 5
    region = engine.region(1)
    assert region.wal.last_entry_id == 5
    assert region.scan().num_rows == 5
    # replay of the merged frame yields the five per-write entries
    wal_path = region.wal.path
    engine.close()
    entries = list(RegionWal(wal_path).replay(0))
    assert [e.entry_id for e in entries] == [1, 2, 3, 4, 5]
    assert all(e.batch.num_rows == 1 for e in entries)


# ---- vectorized routing -----------------------------------------------------


def _legacy_split(rule, table: pa.Table) -> list[pa.Table]:
    """The pre-vectorization reference implementation: one filter mask per
    partition."""
    n = rule.num_partitions()
    if n == 1 or table.num_rows == 0:
        return [table] + [table.schema.empty_table() for _ in range(n - 1)]
    idx = rule.partition_indices(table)
    return [table.filter(pa.array(idx == p)) for p in range(n)]


def _legacy_hash_indices(rule, table: pa.Table) -> np.ndarray:
    h = np.zeros(table.num_rows, dtype=np.uint64)
    import pyarrow.compute as pc

    for c in rule.columns:
        col = table[c]
        if pa.types.is_dictionary(col.type):
            col = pc.cast(col, col.type.value_type)
        vals = col.to_pylist()
        cache: dict = {}
        hc = np.empty(table.num_rows, dtype=np.uint64)
        for i, v in enumerate(vals):
            if v not in cache:
                cache[v] = zlib.crc32(repr(v).encode())
            hc[i] = cache[v]
        h = h * np.uint64(1000003) + hc
    return (h % np.uint64(rule.n)).astype(np.int32)


def test_partition_split_parity_and_order():
    from greptimedb_tpu.models.partition import (
        HashPartitionRule,
        MultiDimPartitionRule,
        RangePartitionRule,
    )

    rng = np.random.default_rng(11)
    n = 2000
    hosts = [
        None if rng.random() < 0.05 else f"host_{int(rng.integers(0, 37))}"
        for _ in range(n)
    ]
    ts = rng.integers(0, 10_000, n)
    vals = rng.uniform(0, 1, n)
    table = pa.table(
        {"host": pa.array(hosts), "ts": pa.array(ts), "val": pa.array(vals)}
    )
    rules = [
        HashPartitionRule(["host"], 8),
        HashPartitionRule(["host", "ts"], 3),
        RangePartitionRule("ts", [1000, 5000, 9000]),
        MultiDimPartitionRule(
            ["ts"], ["ts < 3000", "ts >= 3000 AND ts < 7000", "ts >= 7000"]
        ),
    ]
    for rule in rules:
        parts = rule.split(table)
        legacy = _legacy_split(rule, table)
        assert len(parts) == len(legacy)
        for got, want in zip(parts, legacy):
            # bit-identical content AND row order within each partition
            assert got.to_pydict() == want.to_pydict()
    # hash indices themselves must match the per-row crc loop (routing
    # stability: existing partitioned tables must keep their layout)
    for rule in rules[:2]:
        np.testing.assert_array_equal(
            rule.partition_indices(table), _legacy_hash_indices(rule, table)
        )


def test_range_rule_nulls_and_unsorted_bounds():
    from greptimedb_tpu.models.partition import RangePartitionRule

    t = pa.table({"x": pa.array([None, 1, 5, 10, None, 7])})
    rule = RangePartitionRule("x", [3, 8])
    idx = rule.partition_indices(t)
    np.testing.assert_array_equal(idx, [0, 0, 1, 2, 0, 1])
    # unsorted bounds keep legacy break-at-first-fail semantics
    odd = RangePartitionRule("x", [8, 3])
    np.testing.assert_array_equal(
        odd.partition_indices(t), [0, 0, 0, 2, 0, 0]
    )


def test_insert_zip_transpose_and_coerce(tmp_path):
    from greptimedb_tpu.database import Database

    db = Database(data_home=str(tmp_path / "db"))
    try:
        db.sql(
            "CREATE TABLE t (host STRING, ts TIMESTAMP(3) TIME INDEX, "
            "v DOUBLE, PRIMARY KEY (host))"
        )
        db.sql(
            "INSERT INTO t VALUES ('a', 1000, 1.5), ('b', 2000, 2.5), "
            "('c', '1970-01-01 00:00:03', 3.5)"
        )
        out = db.sql_one("SELECT host, ts, v FROM t ORDER BY host")
        assert out["v"].to_pylist() == [1.5, 2.5, 3.5]
        ts = [int(x.timestamp() * 1000) for x in out["ts"].to_pylist()]
        assert ts == [1000, 2000, 3000]
    finally:
        db.close()


def test_sort_dedup_fast_path_parity_nulls():
    """The lexsort fast path in memtable._sort_and_dedup is bit-identical
    to the arrow sort path — incl. the all-null tag column that ships an
    EMPTY dictionary (a live regression: empty rank table), null ints,
    and duplicate keys resolved by sequence."""
    from greptimedb_tpu.storage import memtable as mt

    rng = np.random.default_rng(5)
    n = 3000
    hosts = [
        None if rng.random() < 0.2 else f"h{int(rng.integers(0, 9))}"
        for _ in range(n)
    ]
    ts = rng.integers(0, 50, n)  # dense: plenty of (pk, ts) duplicates
    tables = {
        "mixed": pa.table({
            "host": pa.array(hosts, pa.string()),
            "ts": pa.array(ts, pa.timestamp("ms")),
            "val": pa.array(rng.uniform(0, 1, n)),
            "__seq": pa.array(np.arange(n, dtype=np.int64)),
        }),
        "all_null_tag": pa.table({
            "host": pa.array([None] * 64, pa.string()),
            "ts": pa.array(np.arange(64) % 8, pa.timestamp("ms")),
            "val": pa.array(np.arange(64, dtype=np.float64)),
            "__seq": pa.array(np.arange(64, dtype=np.int64)),
        }),
    }
    schema = _schema_named("host", "ts", "val")
    orig = mt._key_codes
    for name, t in tables.items():
        for dedup in (False, True):
            fast = mt._sort_and_dedup(t, schema, dedup=dedup)
            assert orig(t, ["host", "ts"]) is not None  # fast path taken
            mt._key_codes = lambda *a: None
            try:
                legacy = mt._sort_and_dedup(t, schema, dedup=dedup)
            finally:
                mt._key_codes = orig
            assert fast.to_pydict() == legacy.to_pydict(), (name, dedup)
    # uint64 keys past 2^63 don't fit the int64 code space: the fast
    # path must decline (arrow sort handles them), not raise
    big = pa.table({"k": pa.array([(1 << 63) + 5, 1], pa.uint64())})
    assert mt._key_codes(big, ["k"]) is None


def _schema_named(tag, ts, field) -> Schema:
    return Schema(
        columns=[
            ColumnSchema(tag, ConcreteDataType.STRING, SemanticType.TAG),
            ColumnSchema(
                ts, ConcreteDataType.TIMESTAMP_MILLISECOND, SemanticType.TIMESTAMP
            ),
            ColumnSchema(field, ConcreteDataType.FLOAT64, SemanticType.FIELD),
        ]
    )


def test_influx_columnar_python_fallback_parity():
    """The pure-Python batch-split columnar parser produces the same
    (ts, fields, tag spans) as the native homogeneous parser, and the
    assembled table matches the per-line Point parser row for row."""
    from greptimedb_tpu import native
    from greptimedb_tpu.servers.influx import (
        _parse_homogeneous_py,
        parse_line_protocol,
        parse_line_protocol_columnar,
    )

    rng = np.random.default_rng(3)
    n = 400
    vals = rng.uniform(0, 100, n)
    body = "\n".join(
        f"cpu,hostname=host_{h % 7},dc=dc_{h % 3} "
        f"usage_user={vals[h]:.3f},usage_sys={vals[h] / 2:.4f} "
        f"{(1000 + h) * 1_000_000}"
        for h in range(n)
    ).encode()
    py = _parse_homogeneous_py(body, 1, 1_000_000)
    assert py is not None
    meas, tag_keys, field_keys, ts, fields, spans = py
    assert (meas, tag_keys, field_keys) == (
        "cpu", ["hostname", "dc"], ["usage_user", "usage_sys"]
    )
    nat = native.lp_parse_homogeneous(body, 1, 1_000_000)
    if nat is not None:  # native lib present: bit-identical outputs
        np.testing.assert_array_equal(nat[3], ts)
        np.testing.assert_array_equal(nat[4], fields)
        np.testing.assert_array_equal(nat[5], spans)
    # assembled table matches the exact Point parser
    out = parse_line_protocol_columnar(body, "ns")
    assert out is not None
    _meas, table, _tags = out
    pts = parse_line_protocol(body.decode(), "ns")
    assert table.num_rows == len(pts) == n
    hostnames = table["hostname"].to_pylist()
    tvals = table["usage_user"].to_pylist()
    tss = table["ts"].to_pylist()
    for i in (0, 1, 137, n - 1):
        assert hostnames[i] == pts[i].tags["hostname"]
        assert abs(tvals[i] - pts[i].fields["usage_user"]) < 1e-12
        assert round(tss[i].timestamp() * 1000) == pts[i].ts_ms
    # heterogeneous / escaped / string-field bodies bail to the Point path
    for bad in (
        b'cpu,hostname=a usage="str" 1000000\n',
        b"cpu,hostname=a usage=1i 1000000\n",
        b"cpu,hostname=a usage=1.0\n",  # no timestamp
        b"cpu,hostname=a usage=1.0 1000000\nmem,hostname=a usage=2.0 2000000\n",
        b"cpu,hostname=a\\ b usage=1.0 1000000\n",
    ):
        assert _parse_homogeneous_py(bad, 1, 1_000_000) is None


# ---- flush overlap ----------------------------------------------------------


def test_buffer_manager_freeze_accounting():
    from greptimedb_tpu.storage.flush import WriteBufferManager

    mgr = WriteBufferManager(global_limit_bytes=100, region_limit_bytes=50)
    mgr.set_region_usage(1, 120)
    assert mgr.should_stall()
    # freezing for flush moves the bytes out of the mutable budget:
    # writes are admitted again while the encode is in flight
    mgr.freeze_region(1, 120)
    assert mgr.mutable_usage() == 0
    assert mgr.flushing_usage() == 120
    assert not mgr.should_stall()
    # but the 2x hard bound still stalls a runaway backlog
    mgr.set_region_usage(1, 90)
    assert mgr.mutable_usage() == 90
    assert mgr.should_stall()  # 90 + 120 >= 200
    mgr.unfreeze_region(1, 120)
    assert mgr.flushing_usage() == 0
    assert not mgr.should_stall()
    mgr.remove_region(1)
    assert mgr.mutable_usage() == 0


def test_flush_parallel_encode_parity(tmp_path):
    """flush_workers > 1 produces the same committed rows/windows as the
    serial loop."""
    from greptimedb_tpu.storage.region import Region

    day = 86_400_000

    def build(name, workers):
        wal = RegionWal(str(tmp_path / f"{name}.wal"))
        region = Region(
            1, str(tmp_path / name), _schema(), wal,
            flush_workers=workers,
        )
        # force the pool path even on a 1-core CI box (construction
        # clamps to real cores)
        region.flush_workers = workers
        # rows across 5 distinct time windows -> 5 SSTs per flush
        for w in range(5):
            region.write(
                _batch(
                    [f"h{i}" for i in range(20)],
                    [w * day + i for i in range(20)],
                    [float(i) for i in range(20)],
                )
            )
        added = region.flush()
        return region, added

    r_ser, a_ser = build("ser", 1)
    r_par, a_par = build("par", 4)
    assert len(a_ser) == len(a_par) == 5
    assert sorted(fm.time_range for fm in a_ser) == sorted(
        fm.time_range for fm in a_par
    )
    assert _rows(r_ser.scan()) == _rows(r_par.scan())


def test_flush_overlap_admits_writes_mid_encode(tmp_path):
    """While a flush encode is in flight, the engine admits new writes
    instead of stalling (the frozen bytes left the mutable budget)."""
    engine = _mk_engine(
        tmp_path, "overlap",
        write_buffer_size_mb=1, global_write_buffer_size_mb=1,
    )
    engine.create_region(1, _schema())
    region = engine.region(1)
    n = 4000
    big = _batch(
        [f"h{i % 50}" for i in range(n)],
        list(range(n)),
        [float(i) for i in range(n)],
    )
    engine.write(1, big)
    # simulate mid-encode: freeze has happened, encode not finished
    frozen = (3 << 20) // 2  # over the 1 MB mutable limit, under the 2x bound
    engine.buffer_mgr.set_region_usage(1, frozen)
    assert engine.buffer_mgr.should_stall()
    engine.buffer_mgr.freeze_region(1, frozen)
    assert not engine.buffer_mgr.should_stall()
    stalls0 = m.WRITE_STALL_TOTAL.get()
    engine.write(1, _batch(["x"], [999_999], [1.0]))
    assert m.WRITE_STALL_TOTAL.get() == stalls0  # admitted, no stall
    engine.buffer_mgr.unfreeze_region(1, frozen)
    engine.close()


# ---- through the Database: pipelined vs legacy ladder --------------------------


def test_database_pipelined_vs_legacy_ladder(tmp_path):
    """Pipelined (group commit + vectorized routing + flush overlap, the
    defaults) vs legacy ingest through `Database` on a small dataset —
    bit-identical query results, the greptime_ingest_* stage metrics
    present, and merged-frame evidence (WAL frames < writes) asserted
    via counters."""
    from concurrent.futures import Future

    from greptimedb_tpu.database import Database
    from greptimedb_tpu.storage.worker import _WriteRequest

    t0, n_hosts = 1_767_225_600_000, 8

    def mk_db(name, pipelined: bool) -> Database:
        cfg = Config()
        cfg.storage.compaction_background_enable = False
        if not pipelined:
            cfg.storage.ingest_group_commit = False
            cfg.storage.ingest_flush_workers = 1
            cfg.storage.ingest_flush_overlap = False
        db = Database(data_home=str(tmp_path / name), config=cfg)
        db.sql(
            "CREATE TABLE cpu (hostname STRING, ts TIMESTAMP(3) TIME INDEX,"
            " usage_user DOUBLE, usage_system DOUBLE, PRIMARY KEY (hostname))"
            " PARTITION BY HASH (hostname) PARTITIONS 2"
        )
        return db

    def ingest(db, tick_lo, tick_hi, seed):
        rng = np.random.default_rng(seed)
        n = (tick_hi - tick_lo) * n_hosts
        ts = t0 + np.repeat(np.arange(tick_lo, tick_hi, dtype=np.int64), n_hosts) * 10_000
        db.insert_rows("cpu", pa.table({
            "hostname": pa.array(
                np.tile([f"host_{i}" for i in range(n_hosts)], tick_hi - tick_lo)
            ),
            "ts": pa.array(ts, pa.timestamp("ms")),
            "usage_user": pa.array(rng.uniform(0, 100, n)),
            "usage_system": pa.array(rng.uniform(0, 100, n)),
        }))

    db_new = mk_db("pipelined", True)
    db_old = mk_db("legacy", False)
    try:
        w0 = m.INGEST_WRITES_TOTAL.get()
        f0 = m.INGEST_WAL_FRAMES.get()
        split0 = m.INGEST_SPLIT_MS.total()
        wal0 = m.INGEST_WAL_MS.total()
        mem0 = m.INGEST_MEMTABLE_MS.total()
        enc0 = m.INGEST_FLUSH_ENCODE_MS.total()
        for db in (db_new, db_old):
            for lo in range(0, 300, 100):
                ingest(db, lo, lo + 100, seed=lo)
            # the multi-row VALUES path (zip transpose + coercion)
            db.sql(
                "INSERT INTO cpu VALUES"
                " ('host_0', 1767225600001, 1.5, 2.5),"
                " ('host_1', 1767225600002, 3.5, 4.5)"
            )
        # a deterministic drained group through the pipelined worker:
        # five requests commit as ONE merged WAL frame, five entry ids
        engine = db_new.storage
        frames1 = m.INGEST_WAL_FRAMES.get()
        writes1 = m.INGEST_WRITES_TOTAL.get()
        rid = db_new.catalog.table("cpu", "public").region_ids[0]
        reqs = [
            _WriteRequest(rid, pa.record_batch(
                {"hostname": pa.array([f"gh_{i}"]),
                 "ts": pa.array([t0 + 10_000_000 + i], pa.timestamp("ms")),
                 "usage_user": pa.array([1.0]),
                 "usage_system": pa.array([2.0])},
            ), Future())
            for i in range(5)
        ]
        engine.workers._worker_for(rid)._handle(reqs)
        assert [r.future.result(timeout=30) for r in reqs] == [1] * 5
        assert m.INGEST_WAL_FRAMES.get() - frames1 == 1
        assert m.INGEST_WRITES_TOTAL.get() - writes1 == 5
        db_old.sql(
            "INSERT INTO cpu VALUES"
            + ", ".join(
                f"('gh_{i}', {t0 + 10_000_000 + i}, 1.0, 2.0)"
                for i in range(5)
            )
        )
        # merged-frame evidence overall: fewer frames than write requests
        writes_d = m.INGEST_WRITES_TOTAL.get() - w0
        frames_d = m.INGEST_WAL_FRAMES.get() - f0
        assert writes_d > 0 and frames_d < writes_d, (frames_d, writes_d)
        # every ingest stage metric observed something
        assert m.INGEST_SPLIT_MS.total() > split0
        assert m.INGEST_WAL_MS.total() > wal0
        assert m.INGEST_MEMTABLE_MS.total() > mem0
        db_new.storage.flush_all()
        db_old.storage.flush_all()
        assert m.INGEST_FLUSH_ENCODE_MS.total() > enc0
        # bit-identical query results across the two ladders
        for q in (
            "SELECT hostname, ts, usage_user, usage_system FROM cpu"
            " ORDER BY hostname, ts",
            "SELECT hostname, avg(usage_user), count(usage_system) FROM cpu"
            " GROUP BY hostname ORDER BY hostname",
        ):
            t_new, t_old = db_new.sql_one(q), db_old.sql_one(q)
            assert t_new.to_pydict() == t_old.to_pydict(), q
    finally:
        db_new.close()
        db_old.close()


# ---- config -----------------------------------------------------------------


def test_ingest_config_validation_and_copydown():
    cfg = Config()
    assert cfg.storage.ingest_group_commit is True
    assert cfg.storage.ingest_flush_workers == 2
    assert cfg.storage.ingest_flush_overlap is True

    cfg = Config._from_dict({"ingest": {"group_commit": "false",
                                        "flush_workers": "5",
                                        "flush_overlap": "false"}})
    assert cfg.ingest.group_commit is False
    assert cfg.storage.ingest_group_commit is False
    assert cfg.storage.ingest_flush_workers == 5
    assert cfg.storage.ingest_flush_overlap is False

    with pytest.raises(ConfigError, match="ingest.flush_workers"):
        Config._from_dict({"ingest": {"flush_workers": 0}})
    with pytest.raises(ConfigError, match="ingest.flush_workers"):
        Config._from_dict({"ingest": {"flush_workers": 65}})
    with pytest.raises(ConfigError, match="ingest.group_commit"):
        Config._from_dict({"ingest": {"group_commit": 3}})
    with pytest.raises(ConfigError, match="ingest.flush_overlap"):
        Config._from_dict({"ingest": {"flush_overlap": 2}})
