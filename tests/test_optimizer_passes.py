"""Optimizer-pass framework: named, ordered, individually switchable
strategies with EXPLAIN visibility.

Reference parity: the extension physical optimizer rules
(reference query/src/optimizer/parallelize_scan.rs:29, windowed_sort.rs:47,
remove_duplicate.rs) are composable passes the planner runs in order and
tests disable one at a time; EXPLAIN ANALYZE (analyze.rs:49) shows their
effect per query.
"""

import pyarrow as pa
import pytest

from greptimedb_tpu.database import Database
from greptimedb_tpu.query import passes


@pytest.fixture()
def db(tmp_path, monkeypatch):
    from greptimedb_tpu.parallel.tile_cache import TileCacheManager

    # window tiles only pay off at scale; shrink the floor so the 64k-row
    # fixture exercises the same decision points the TSBS run does
    monkeypatch.setattr(TileCacheManager, "_WINDOW_TILE_MIN_ROWS", 1 << 14)
    d = Database(data_home=str(tmp_path / "db"))
    # device-path pass visibility is under test; cold-serve routing would
    # answer the first (EXPLAIN ANALYZE) query from host instead
    d.config.query.disabled_passes = ("cold_host_serve",)
    yield d
    d.close()


def _setup(db, n=1 << 16):
    import numpy as np

    db.sql(
        "CREATE TABLE cpu (host STRING, ts TIMESTAMP TIME INDEX,"
        " usage_user DOUBLE, PRIMARY KEY (host))"
    )
    hosts = np.repeat([f"h{i}" for i in range(8)], n // 8)
    ts = np.tile(np.arange(n // 8, dtype=np.int64) * 1000, 8)
    rng = np.random.default_rng(11)
    db.insert_rows("cpu", pa.table({
        "host": pa.array(hosts),
        "ts": pa.array(ts, pa.timestamp("ms")),
        "usage_user": pa.array(rng.uniform(0, 100, n)),
    }))
    db.storage.flush_all()


WINDOWED = (
    "SELECT host, time_bucket('30s', ts) AS tb, avg(usage_user) AS au"
    " FROM cpu WHERE ts >= 1000000 AND ts < 2000000 GROUP BY host, tb"
)


def _pass_lines(table: pa.Table) -> dict[str, str]:
    stages = table["stage"].to_pylist()
    mets = table["metrics"].to_pylist()
    if "── optimizer passes ──" not in stages:
        return {}
    i = stages.index("── optimizer passes ──")
    return {s.strip(): m for s, m in zip(stages[i + 1:], mets[i + 1:])}


def test_registry_is_ordered_and_described():
    names = [p.name for p in passes.registry()]
    # routing before layout before distributed — the run order contract
    assert names.index("cost_route") < names.index("window_tile")
    assert names.index("window_tile") < names.index("state_ship")
    for p in passes.registry():
        assert p.description and p.kind in ("routing", "layout", "distributed")


def test_explain_lists_static_pass_pipeline(db):
    _setup(db)
    out = db.sql_one("EXPLAIN " + WINDOWED)
    lines = out["plan"].to_pylist()
    assert "── optimizer passes ──" in lines
    joined = "\n".join(lines)
    for name in ("window_tile", "host_fast_path", "limb_quantize"):
        assert name in joined


def test_explain_analyze_shows_fired_passes(db):
    _setup(db)
    out = db.sql_one("EXPLAIN ANALYZE " + WINDOWED)
    decisions = _pass_lines(out)
    # the windowed group-by over flushed SSTs must take the window-tile
    # strategy and record WHY
    assert decisions, f"no pass section in: {out['stage'].to_pylist()}"
    assert "window_tile" in decisions
    assert decisions["window_tile"].startswith("fired")
    assert "chunk_placement" in decisions
    # the decision trace is per-query: a selective pk-equality query takes
    # the host fast path instead
    out2 = db.sql_one(
        "EXPLAIN ANALYZE SELECT max(usage_user) AS m FROM cpu"
        " WHERE host = 'h1' AND ts >= 1000000 AND ts < 2000000"
    )
    d2 = _pass_lines(out2)
    assert d2.get("host_fast_path", "").startswith("fired")


def test_disabling_window_tile_composes(db):
    _setup(db)
    db.config.query.disabled_passes = ("window_tile",)
    out = db.sql_one("EXPLAIN ANALYZE " + WINDOWED)
    decisions = _pass_lines(out)
    assert not decisions.get("window_tile", "").startswith("fired")
    # result stays correct through the full-tile masked path
    t = db.sql_one(WINDOWED)
    db.config.query.disabled_passes = ()
    t2 = db.sql_one(WINDOWED)
    assert t.sort_by([("host", "ascending"), ("tb", "ascending")]).equals(
        t2.sort_by([("host", "ascending"), ("tb", "ascending")])
    )


def test_disabling_limb_quantize_switches_accumulator(db):
    _setup(db)
    db.config.query.disabled_passes = ("limb_quantize",)
    out = db.sql_one("EXPLAIN ANALYZE " + WINDOWED)
    decisions = _pass_lines(out)
    lq = decisions.get("limb_quantize", "")
    assert lq.startswith("skipped"), lq
    # exact float accumulation must produce the same aggregates
    t = db.sql_one(WINDOWED)
    db.config.query.disabled_passes = ()
    t2 = db.sql_one(WINDOWED)
    a1 = sorted(zip(t["host"].to_pylist(), t["au"].to_pylist()))
    a2 = sorted(zip(t2["host"].to_pylist(), t2["au"].to_pylist()))
    for (h1, v1), (h2, v2) in zip(a1, a2):
        assert h1 == h2 and abs(v1 - v2) < 1e-6


def test_disabling_host_fast_path_still_serves(db):
    _setup(db)
    q = (
        "SELECT max(usage_user) AS m FROM cpu"
        " WHERE host = 'h1' AND ts >= 1000000 AND ts < 2000000"
    )
    ref = db.sql_one(q)["m"].to_pylist()
    db.config.query.disabled_passes = ("host_fast_path",)
    out = db.sql_one("EXPLAIN ANALYZE " + q)
    decisions = _pass_lines(out)
    assert not decisions.get("host_fast_path", "").startswith("fired")
    assert db.sql_one(q)["m"].to_pylist() == ref


def test_explain_analyze_says_why_a_window_declined_over_resident_planes(db):
    """Once a full scan has put the region's planes on the device, a window
    whose tile would cost more to build than its masked scan declines, and
    the pass says so, not "covers most of retention" (its cover is 12 %)."""
    from greptimedb_tpu.utils import metrics

    _setup(db)
    db.sql_one("SELECT host, avg(usage_user) AS au FROM cpu GROUP BY host")
    scans = metrics.TILE_WINDOW_RESIDENT_SCANS.get()
    decisions = _pass_lines(db.sql_one("EXPLAIN ANALYZE " + WINDOWED))
    assert metrics.TILE_WINDOW_RESIDENT_SCANS.get() == scans + 1
    assert not decisions["window_tile"].startswith("fired"), decisions
    assert "planes resident" in decisions["window_tile"] and "declined=resident" in decisions["window_tile"]
    entry = next(iter(db.query_engine.tile_cache._super.values()))
    assert not entry.window_tiles
