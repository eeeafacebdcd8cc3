"""Region-streamed execution for working sets larger than the HBM budget
(parallel/tile_cache.py _streamed_execute): build -> dispatch -> merge ->
release per region, peak HBM bounded by one region's planes.

Reference parity: MergeScan consumes per-region streams without
materializing the table (reference query/src/dist_plan/merge_scan.rs:
250-330); here the same contract bounds HBM so retention can exceed the
chip (the reference's 1B-row JSONBench runs bound server RAM the same
way)."""

import numpy as np
import pyarrow as pa
import pytest

from greptimedb_tpu.database import Database
from greptimedb_tpu.parallel import tile_cache as tc
from greptimedb_tpu.utils import metrics


@pytest.fixture()
def db(tmp_path):
    d = Database(data_home=str(tmp_path / "db"))
    yield d
    d.close()


def _load_partitioned(db, n=1 << 16, parts=4, metrics_n=2):
    cols = ", ".join(f"m{i} DOUBLE" for i in range(metrics_n))
    db.sql(
        f"CREATE TABLE spill (host STRING, ts TIMESTAMP TIME INDEX, {cols},"
        f" PRIMARY KEY (host)) PARTITION BY HASH (host) PARTITIONS {parts}"
        f" WITH (append_mode = 'true')"
    )
    rng = np.random.default_rng(21)
    hosts = np.array([f"h{i % 16}" for i in range(n)])
    ts = np.arange(n, dtype=np.int64) * 100
    data = {"host": pa.array(hosts), "ts": pa.array(ts, pa.timestamp("ms"))}
    vals = {}
    for i in range(metrics_n):
        vals[f"m{i}"] = rng.uniform(0, 100, n)
        data[f"m{i}"] = pa.array(vals[f"m{i}"])
    db.insert_rows("spill", pa.table(data))
    db.storage.flush_all()
    return hosts, ts, vals


def _force_stream(db, budget_mb=2):
    cache = db.query_engine.tile_cache
    cache.budget = budget_mb << 20
    db.config.query.tile_cache_mb = budget_mb


Q = (
    "SELECT host, count(*) AS c, sum(m0) AS s0, avg(m1) AS a1,"
    " max(m0) AS x0 FROM spill GROUP BY host ORDER BY host"
)


def test_streamed_matches_cpu_and_bounds_hbm(db):
    hosts, ts, vals = _load_partitioned(db)
    _force_stream(db)
    n_stream0 = metrics.TILE_STREAM_QUERIES.get()
    series0 = metrics.TILE_STAGE1_SERIES.get()
    t1 = db.sql_one(Q)
    assert metrics.TILE_STREAM_QUERIES.get() == n_stream0 + 1, (
        "working set above budget must take the streamed path"
    )
    # partitioned on `host`: each region's stage 1 groups by its own hosts
    assert metrics.TILE_STAGE1_SERIES.get() == series0 + 16
    # per-region latency samples were recorded (one per region with files)
    assert len(tc.LAST_STREAM_CHUNK_MS) == 4
    # after the query every region's planes were released: resident device
    # bytes stay a small fraction of even this tiny budget
    cache = db.query_engine.tile_cache
    assert cache._used < (1 << 20), f"{cache._used} bytes still resident"

    db.config.query.backend = "cpu"
    t2 = db.sql_one(Q)
    db.config.query.backend = "tpu"
    assert t1["host"].to_pylist() == t2["host"].to_pylist()
    assert t1["c"].to_pylist() == t2["c"].to_pylist()
    np.testing.assert_allclose(
        t1["s0"].to_pylist(), t2["s0"].to_pylist(), rtol=1e-7
    )
    np.testing.assert_allclose(
        t1["a1"].to_pylist(), t2["a1"].to_pylist(), rtol=1e-7
    )
    np.testing.assert_allclose(
        t1["x0"].to_pylist(), t2["x0"].to_pylist(), rtol=1e-12
    )


def test_streamed_windowed_query_matches(db):
    hosts, ts, vals = _load_partitioned(db)
    _force_stream(db)
    lo, hi = 1_000_000, 4_000_000
    q = (
        f"SELECT host, sum(m0) AS s FROM spill"
        f" WHERE ts >= {lo} AND ts < {hi} GROUP BY host ORDER BY host"
    )
    t1 = db.sql_one(q)
    db.config.query.backend = "cpu"
    t2 = db.sql_one(q)
    db.config.query.backend = "tpu"
    assert t1["host"].to_pylist() == t2["host"].to_pylist()
    np.testing.assert_allclose(
        t1["s"].to_pylist(), t2["s"].to_pylist(), rtol=1e-7
    )


def test_streamed_disabled_pass_falls_back_correct(db):
    _load_partitioned(db)
    _force_stream(db)
    db.config.query.disabled_passes = ("stream_spill",)
    n0 = metrics.TILE_STREAM_QUERIES.get()
    t1 = db.sql_one(Q)  # all-at-once tile path (may thrash) or scan path
    assert metrics.TILE_STREAM_QUERIES.get() == n0
    db.config.query.disabled_passes = ()
    t2 = db.sql_one(Q)
    assert t1["c"].to_pylist() == t2["c"].to_pylist()
    np.testing.assert_allclose(
        t1["s0"].to_pylist(), t2["s0"].to_pylist(), rtol=1e-7
    )


def test_streamed_explain_analyze_shows_pass(db):
    _load_partitioned(db)
    _force_stream(db)
    out = db.sql_one("EXPLAIN ANALYZE " + Q)
    stages = out["stage"].to_pylist()
    mets = out["metrics"].to_pylist()
    i = stages.index("── optimizer passes ──")
    d = {s.strip(): m for s, m in zip(stages[i + 1:], mets[i + 1:])}
    assert d.get("stream_spill", "").startswith("fired"), d


def test_streamed_with_memtable_tail(db):
    """Unflushed rows ride as memtable sources in the same streamed
    dispatch; results stay exact."""
    hosts, ts, vals = _load_partitioned(db)
    _force_stream(db)
    db.sql("INSERT INTO spill VALUES ('h3', 99999000, 50.0, 60.0)")
    t1 = db.sql_one(Q)
    db.config.query.backend = "cpu"
    t2 = db.sql_one(Q)
    db.config.query.backend = "tpu"
    assert t1["c"].to_pylist() == t2["c"].to_pylist()
    np.testing.assert_allclose(
        t1["s0"].to_pylist(), t2["s0"].to_pylist(), rtol=1e-7
    )
