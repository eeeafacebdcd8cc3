"""HBM tile cache: correctness vs the authoritative CPU path, cache
lifecycle (hits, invalidation, dictionary-growth repair, eviction), and
the dedup-safety gate (reference parity: mito2 write cache serves reads
from cached media, mito-codec pre-encodes keys at write time)."""

import math

import pyarrow as pa
import pytest

from greptimedb_tpu.database import Database
from greptimedb_tpu.utils import metrics


@pytest.fixture()
def db(tmp_path):
    d = Database(data_home=str(tmp_path / "db"))
    yield d
    d.close()


def _mk_cpu_table(db, name="cpu", append=""):
    with_clause = f" WITH (append_mode = 'true')" if append else ""
    db.sql(
        f"CREATE TABLE {name} (host STRING, region STRING, ts TIMESTAMP TIME INDEX,"
        f" usage_user DOUBLE, usage_system DOUBLE, PRIMARY KEY (host, region))"
        + with_clause
    )


def _load(db, name="cpu", hosts=6, ticks=120, t0=0):
    rows = []
    for t in range(ticks):
        for h in range(hosts):
            rows.append(
                f"('host_{h}', 'r{h % 2}', {t0 + t * 1000}, {t % 13 + h}, {(t + h) % 7})"
            )
    db.sql(f"INSERT INTO {name} VALUES " + ",".join(rows))


Q = (
    "SELECT host, time_bucket('30s', ts) AS tb, avg(usage_user) AS au,"
    " max(usage_system) AS ms, count(*) AS c FROM cpu GROUP BY host, tb"
)


def _both(db, q):
    """Run on the TPU (tile) path and the CPU path; return both tables."""
    db.config.query.backend = "tpu"
    t1 = db.sql_one(q)
    db.config.query.backend = "cpu"
    t2 = db.sql_one(q)
    db.config.query.backend = "tpu"
    return t1, t2


def _assert_equal(t1: pa.Table, t2: pa.Table, keys):
    assert t1.num_rows == t2.num_rows
    s1 = t1.sort_by([(k, "ascending") for k in keys]).to_pydict()
    s2 = t2.sort_by([(k, "ascending") for k in keys]).to_pydict()
    assert len(s1) == len(s2)
    for c1, c2 in zip(list(s1), list(s2)):
        for x, y in zip(s1[c1], s2[c2]):
            if isinstance(x, float) and isinstance(y, float):
                assert math.isclose(x, y, rel_tol=1e-9) or (
                    math.isnan(x) and math.isnan(y)
                ), (c1, x, y)
            else:
                assert x == y, (c1, x, y)


def _tile_count():
    return metrics.TILE_LOWERED_TOTAL.get()


def test_tile_path_engages_and_matches_cpu(db):
    _mk_cpu_table(db)
    _load(db)
    db.sql("ADMIN flush_table('cpu')")
    before = _tile_count()
    t1, t2 = _both(db, Q)
    assert _tile_count() == before + 1, "tile path did not engage"
    _assert_equal(t1, t2, ["host", "tb"])


def test_warm_query_hits_cache(db):
    db.config.query.disabled_passes = ("cold_host_serve",)  # device-path mechanics under test
    _mk_cpu_table(db)
    _load(db)
    db.sql("ADMIN flush_table('cpu')")
    db.sql_one(Q)  # cold: builds tiles
    h0 = metrics.TILE_CACHE_HITS.get()
    m0 = metrics.TILE_CACHE_MISSES.get()
    db.sql_one(Q)  # warm
    assert metrics.TILE_CACHE_HITS.get() > h0
    assert metrics.TILE_CACHE_MISSES.get() == m0


def test_memtable_tail_included(db):
    _mk_cpu_table(db)
    _load(db, ticks=60)
    db.sql("ADMIN flush_table('cpu')")
    db.sql_one(Q)
    # fresh rows in a later, disjoint time window stay in the memtable
    _load(db, ticks=30, t0=600_000)
    before = _tile_count()
    t1, t2 = _both(db, Q)
    assert _tile_count() == before + 1
    _assert_equal(t1, t2, ["host", "tb"])


def test_persisted_tiles_skip_reconsolidation(tmp_path):
    """Cold-start: a SECOND Database over the same data dir loads the
    persisted consolidation (order + sorted planes + column buffers)
    instead of re-reading Parquet — and serves identical results, on the
    device path AND the selective host fast path."""
    import time as _time

    import numpy as np

    home = str(tmp_path / "db")
    db = Database(data_home=home)
    _mk_cpu_table(db)
    n = 4096 * 4
    hosts = np.repeat([f"host_{i}" for i in range(8)], n // 8)
    ts = np.tile(np.arange(n // 8, dtype=np.int64) * 1000, 8)
    rng = np.random.default_rng(31)
    vals = rng.uniform(0, 100, n)
    db.insert_rows("cpu", pa.table({
        "host": pa.array(hosts),
        "region": pa.array(np.repeat("r0", n)),
        "ts": pa.array(ts, pa.timestamp("ms")),
        "usage_user": pa.array(vals),
        "usage_system": pa.array(vals * 2),
    }))
    db.sql("ADMIN flush_table('cpu')")
    q = "SELECT host, avg(usage_user) AS a FROM cpu GROUP BY host ORDER BY host"
    want = db.sql_one(q).to_pydict()
    # wait for the background persist writer
    deadline = _time.time() + 30
    import os as _os

    pdir = _os.path.join(home, "tile_cache")
    while _time.time() < deadline:
        metas = [
            f
            for root, _d, files in _os.walk(pdir)
            for f in files
            if f == "meta.json"
        ]
        if metas:
            break
        _time.sleep(0.2)
    assert metas, "persist writer did not commit"
    db.close()

    db2 = Database(data_home=home)
    before_hits = metrics.TILE_PERSIST_HITS.get()
    got = db2.sql_one(q).to_pydict()
    assert got == want
    assert metrics.TILE_PERSIST_HITS.get() == before_hits + 1, (
        "fresh process did not load the persisted consolidation"
    )
    # host fast path over persisted planes (selective pk query)
    t = db2.sql_one(
        "SELECT count(*) AS c, max(usage_system) AS m FROM cpu"
        " WHERE host = 'host_3'"
    )
    assert t["c"].to_pylist() == [n // 8]
    g = vals[np.asarray(hosts) == "host_3"] * 2
    np.testing.assert_allclose(t["m"].to_pylist()[0], g.max(), rtol=1e-12)
    db2.close()


def test_persisted_tiles_with_stale_tag_codes_rebuild(tmp_path):
    """A hash-partitioned table: each region's consolidation is persisted
    at the dictionary epoch it was built at, and later regions add hosts
    that sort in between ("h10" < "h2"), shifting the earlier codes.  The
    permutation history lives in the process, so a SECOND Database cannot
    repair those codes forward: it must drop the stale store and rebuild
    from Parquet — not merge different hosts into one group."""
    import os as _os
    import time as _time

    import numpy as np

    home = str(tmp_path / "db")
    db = Database(data_home=home)
    db.config.query.disabled_passes = ("cold_host_serve", "host_fast_path")
    db.sql(
        "CREATE TABLE p (host STRING, ts TIMESTAMP TIME INDEX, v DOUBLE,"
        " PRIMARY KEY (host)) PARTITION BY HASH (host) PARTITIONS 4"
    )
    n_hosts, ticks = 40, 64
    hosts = np.tile(np.array([f"h{i}" for i in range(n_hosts)]), ticks)
    ts = np.repeat(np.arange(ticks, dtype=np.int64) * 1000, n_hosts)
    db.insert_rows("p", pa.table({
        "host": pa.array(hosts),
        "ts": pa.array(ts, pa.timestamp("ms")),
        "v": pa.array(np.random.default_rng(5).uniform(0, 100, len(ts))),
    }))
    db.sql("ADMIN flush_table('p')")
    q = "SELECT host, max(v) AS m, count(*) AS c FROM p GROUP BY host"
    want = db.sql_one(q).to_pydict()
    assert len(want["host"]) == n_hosts
    pdir = _os.path.join(home, "tile_cache")
    deadline = _time.time() + 30
    while _time.time() < deadline:
        n_meta = sum(
            f == "meta.json" for _r, _d, files in _os.walk(pdir) for f in files
        )
        if n_meta == 4:
            break
        _time.sleep(0.2)
    assert n_meta == 4, "persist writer did not commit every region"
    db.close()

    db2 = Database(data_home=home)
    db2.config.query.disabled_passes = ("cold_host_serve", "host_fast_path")
    lowered0 = metrics.TILE_LOWERED_TOTAL.get()
    got = db2.sql_one(q).to_pydict()
    assert metrics.TILE_LOWERED_TOTAL.get() > lowered0
    assert got == want
    db2.close()


def test_window_tile_engages_and_matches(db, monkeypatch):
    """Windowed query over deep retention gathers a compact window tile
    (kernel scans the window, not the retention) — results must equal the
    CPU path, including combined with overwrite dedup."""
    db.config.query.disabled_passes = ("cold_host_serve",)  # device-path mechanics under test
    import numpy as np

    from greptimedb_tpu.parallel.tile_cache import TileCacheManager

    monkeypatch.setattr(TileCacheManager, "_WINDOW_TILE_MIN_ROWS", 1 << 14)
    _mk_cpu_table(db)
    n = 1 << 16
    hosts = np.repeat([f"h{i}" for i in range(8)], n // 8)
    ts = np.tile(np.arange(n // 8, dtype=np.int64) * 1000, 8)
    rng = np.random.default_rng(77)
    vals = rng.uniform(0, 100, n)
    db.insert_rows("cpu", pa.table({
        "host": pa.array(hosts),
        "region": pa.array(np.repeat("r0", n)),
        "ts": pa.array(ts, pa.timestamp("ms")),
        "usage_user": pa.array(vals),
        "usage_system": pa.array(vals),
    }))
    db.sql("ADMIN flush_table('cpu')")
    # overwrite a slice inside the window in a second flush -> dedup+window
    sel = (ts >= 1_000_000) & (ts < 1_200_000) & (np.arange(n) % 2 == 0)
    db.insert_rows("cpu", pa.table({
        "host": pa.array(hosts[sel]),
        "region": pa.array(np.repeat("r0", int(sel.sum()))),
        "ts": pa.array(ts[sel], pa.timestamp("ms")),
        "usage_user": pa.array(np.full(int(sel.sum()), 500.0)),
        "usage_system": pa.array(np.zeros(int(sel.sum()))),
    }))
    db.sql("ADMIN flush_table('cpu')")

    builds = metrics.TILE_WINDOW_BUILDS.get()
    q = ("SELECT host, count(*) AS c, avg(usage_user) AS a FROM cpu"
         " WHERE ts >= 1000000 AND ts < 2000000 GROUP BY host ORDER BY host")
    t1, t2 = _both(db, q)
    assert metrics.TILE_WINDOW_BUILDS.get() == builds + 1, "window tile not built"
    s1, s2 = t1.to_pydict(), t2.to_pydict()
    assert s1["host"] == s2["host"] and s1["c"] == s2["c"]
    import numpy as _np

    _np.testing.assert_allclose(s1["a"], s2["a"], rtol=1e-7)
    # warm rep reuses the cached window tile (no second build)
    db.sql_one(q)
    assert metrics.TILE_WINDOW_BUILDS.get() == builds + 1


def test_window_tile_extends_with_new_columns(db, monkeypatch):
    """A wider query over the SAME window must EXTEND the cached window
    tile with the new columns and stay on the tile path.  Round 4 rebuilt
    the tile, then DISCARDED the rebuild in its race branch — the returned
    sources lacked the new columns, so every multi-column query after a
    narrower one over the same window fell back to the CPU scan (the
    round-4 driver-bench timeout: TSBS double-groupby-5 'warm' at 55 s)."""
    db.config.query.disabled_passes = ("cold_host_serve",)  # device-path mechanics under test
    import numpy as np

    from greptimedb_tpu.parallel.tile_cache import TileCacheManager

    monkeypatch.setattr(TileCacheManager, "_WINDOW_TILE_MIN_ROWS", 1 << 14)
    _mk_cpu_table(db)
    n = 1 << 16
    hosts = np.repeat([f"h{i}" for i in range(8)], n // 8)
    ts = np.tile(np.arange(n // 8, dtype=np.int64) * 1000, 8)
    rng = np.random.default_rng(5)
    db.insert_rows("cpu", pa.table({
        "host": pa.array(hosts),
        "region": pa.array(np.repeat("r0", n)),
        "ts": pa.array(ts, pa.timestamp("ms")),
        "usage_user": pa.array(rng.uniform(0, 100, n)),
        "usage_system": pa.array(rng.uniform(0, 100, n)),
    }))
    db.sql("ADMIN flush_table('cpu')")
    w = " WHERE ts >= 1000000 AND ts < 2000000"
    q1 = f"SELECT host, avg(usage_user) AS a FROM cpu{w} GROUP BY host"
    q2 = (f"SELECT host, avg(usage_user) AS a, avg(usage_system) AS b,"
          f" count(*) AS c FROM cpu{w} GROUP BY host")
    builds = metrics.TILE_WINDOW_BUILDS.get()
    db.sql_one(q1)  # builds the narrow window tile
    assert metrics.TILE_WINDOW_BUILDS.get() == builds + 1
    # the wider query must NOT fall back: surface any tile-path error
    db.config.query.fallback_to_cpu = False
    before = _tile_count()
    try:
        t1 = db.sql_one(q2)
    finally:
        db.config.query.fallback_to_cpu = True
    assert _tile_count() == before + 1, "wider query left the tile path"
    try:
        db.config.query.backend = "cpu"
        t2 = db.sql_one(q2)
    finally:
        db.config.query.backend = "tpu"
    s1 = t1.sort_by("host").to_pydict()
    s2 = t2.sort_by("host").to_pydict()
    assert s1["host"] == s2["host"] and s1["c"] == s2["c"]
    import numpy as _np

    _np.testing.assert_allclose(s1["a"], s2["a"], rtol=1e-7)
    _np.testing.assert_allclose(s1["b"], s2["b"], rtol=1e-7)
    # and the now-complete tile serves the narrow query without a rebuild
    builds2 = metrics.TILE_WINDOW_BUILDS.get()
    db.sql_one(q1)
    db.sql_one(q2)
    assert metrics.TILE_WINDOW_BUILDS.get() == builds2


def _host_entry(codes, ts):
    """A super-tile entry of host planes alone, its rows in the (pk, ts)
    order the consolidation's stable lexsort leaves (equal keys in flush
    order, so the newest version of a key sits last)."""
    import numpy as np

    from greptimedb_tpu.ops.tiles import padded_size
    from greptimedb_tpu.parallel.tile_cache import _SuperTiles

    order = np.lexsort([ts, codes]).astype(np.int32)
    return _SuperTiles(
        region_id=7, file_ids=("f0",), num_rows=len(ts),
        pad=padded_size(len(ts)), order=order,
        sorted_host={"host": codes[order], "ts": ts[order]},
    )


def _probe_plane(kind, rng):
    """(series codes, ts) in flush order, made from the seed."""
    import numpy as np

    if kind == "regular":  # 6 series x 40 ticks, every series its own run
        return np.repeat(np.arange(6), 40), np.tile(np.arange(40) * 10 + 100, 6)
    if kind == "duplicate_ts":  # append mode: a timestamp recurs within a series
        codes = np.sort(rng.integers(0, 5, 600))
        return codes, rng.integers(0, 40, 600) * 10 + 100
    if kind == "one_row_runs":  # one row a series, each older than the last
        return np.arange(50), 1000 - np.arange(50) * 10
    if kind == "merged_series":
        # series i ends where i + 1 begins (equal ts at the seam for odd i):
        # the whole plane ascends and is ONE run
        starts = np.arange(8) * 100 - (np.arange(8) % 2) * 10
        return np.repeat(np.arange(8), 10), (starts[:, None] + np.arange(10) * 10).ravel()
    if kind == "ragged":  # runs of 1, 2 and 900 rows side by side
        lens = rng.permutation(np.r_[np.ones(20, int), np.full(20, 2), [900, 700, 3]])
        codes = np.repeat(np.arange(len(lens)), lens)
        return codes, np.concatenate([np.sort(rng.integers(0, 500, n)) * 10 for n in lens])
    if kind == "overlapping_files":
        # a second flush overwrites a third of the keys: under dedup the older
        # versions are losers, spread over every window's inside, edges and outside
        codes, ts = np.repeat(np.arange(6), 80), np.tile(np.arange(80) * 10 + 100, 6)
        again = rng.random(len(ts)) < 0.35
        return np.r_[codes, codes[again]], np.r_[ts, ts[again]]
    assert kind == "random"
    return rng.integers(0, 30, 3000), rng.integers(-200, 200, 3000) * 5


def _probe_windows(ts, rng):
    """Windows that start or end exactly on a sample (lo inclusive, hi
    exclusive) or beside one, before the first and after the last sample,
    empty and inverted ones, and the whole plane."""
    import numpy as np

    lo, hi = int(ts.min()), int(ts.max())
    out = [(lo - 50, lo), (lo - 50, lo + 1), (hi, hi + 1), (hi + 1, hi + 50),
           (lo - 5, hi + 5), (lo, hi), (lo + 1, hi + 1)]
    for _ in range(12):
        s1, s2 = sorted(int(x) for x in rng.choice(ts, 2))
        out += [(s1, s2), (s1, s2 + 1), (s1 + 1, s2), (s1 - 1, s2 - 1),
                (s1, s1), (s1, s1 + 1), (s2, s1)]
    return out


@pytest.mark.parametrize("kind,dedup", [
    ("regular", False), ("duplicate_ts", False), ("duplicate_ts", True),
    ("one_row_runs", False), ("merged_series", False), ("merged_series", True),
    ("ragged", False), ("overlapping_files", True), ("overlapping_files", False),
    ("random", False), ("random", True),
])
def test_window_rows_from_run_bounds_equal_the_mask(kind, dedup):
    """The rows of a window found by two searches per ascending run of the
    sorted ts plane are, in count and element for element, those of the
    mask over the whole plane that `ensure_window_tile` used to build."""
    import numpy as np

    from greptimedb_tpu.parallel.tile_cache import TileCacheManager, _range_rows

    rng = np.random.default_rng(32)
    codes, ts = _probe_plane(kind, rng)
    entry = _host_entry(codes.astype(np.int32), ts.astype(np.int64))
    cache = TileCacheManager(budget_bytes=1 << 28)
    ts_sorted = entry.sorted_host["ts"]
    if kind == "merged_series":
        assert len(cache._ts_runs(entry, "ts")) == 1
    if kind == "one_row_runs":
        assert len(cache._ts_runs(entry, "ts")) == entry.num_rows
    if dedup:
        assert cache.ensure_dedup_keep(entry)
        if kind == "overlapping_files":
            assert 0 < np.count_nonzero(~entry.keep_host) < len(ts) // 3
    counted = metrics.TILE_WINDOW_COUNTED.get()
    for lo, hi in _probe_windows(ts_sorted, rng):
        mask = (ts_sorted >= lo) & (ts_sorted < hi)
        if dedup:
            mask &= entry.keep_host
        first, end, n = cache._window_ranges(entry, (lo, hi), "ts", dedup)
        assert n == np.count_nonzero(mask), (lo, hi)
        rows = _range_rows(first, end, entry.keep_host if dedup else None)
        assert rows.dtype == np.int32
        np.testing.assert_array_equal(rows, np.flatnonzero(mask), err_msg=str((lo, hi)))
    assert metrics.TILE_WINDOW_COUNTED.get() == counted  # a probe's, not a search's


@pytest.mark.parametrize("floor", ["below", "above"])
def test_window_tile_min_rows_floor(floor, monkeypatch):
    """A plane below `_WINDOW_TILE_MIN_ROWS` is not probed at all; one at
    the floor is counted, and the tile built from the ranges holds the
    rows `flatnonzero` of the mask gave, in their order."""
    import numpy as np

    from greptimedb_tpu.parallel.tile_cache import TileCacheManager

    codes = np.repeat(np.arange(6, dtype=np.int32), 4000)
    ts = np.tile(np.arange(4000, dtype=np.int64) * 10, 6)
    entry = _host_entry(codes, ts)
    monkeypatch.setattr(
        TileCacheManager, "_WINDOW_TILE_MIN_ROWS",
        len(ts) + (1 if floor == "below" else 0),
    )
    cache = TileCacheManager(budget_bytes=1 << 30)
    counted, builds = metrics.TILE_WINDOW_COUNTED.get(), metrics.TILE_WINDOW_BUILDS.get()
    src, declined = cache.ensure_window_tile(entry, (5000, 15000), "ts", {"ts"}, set(), False, 0)
    if floor == "below":
        assert src is None and declined == "unprobed"
        assert not entry.window_tiles and entry.ts_run_starts is None
        assert metrics.TILE_WINDOW_COUNTED.get() == counted
        return
    assert declined is None
    assert metrics.TILE_WINDOW_COUNTED.get() == counted + 1
    assert metrics.TILE_WINDOW_BUILDS.get() == builds + 1
    ts_sorted = entry.sorted_host["ts"]
    want = ts_sorted[np.flatnonzero((ts_sorted >= 5000) & (ts_sorted < 15000))]
    wt = entry.window_tiles[(5000, 15000, False)]
    assert wt["rows"] == len(want) == 6000 and len(src) == len(wt["valid"])
    got = np.concatenate([np.asarray(c) for c in wt["cols"]["ts"]])
    np.testing.assert_array_equal(got[: len(want)], want)
    valid = np.concatenate([np.asarray(c) for c in wt["valid"]])
    assert valid[: len(want)].all() and not valid[len(want):].any()
    # the same window again is a lookup: neither counted nor built
    again, declined = cache.ensure_window_tile(entry, (5000, 15000), "ts", {"ts"}, set(), False, 0)
    assert again and declined is None
    assert metrics.TILE_WINDOW_COUNTED.get() == counted + 1
    assert metrics.TILE_WINDOW_BUILDS.get() == builds + 1


# ticks of 4000 a series in the window: a cover of the plane's 24,000 rows
_DECISION_TICKS = {"half": 2000, "under_break_even": 100, "most": 3600, "none": 0}


@pytest.mark.parametrize("resident,cover,declined", [
    (True, "half", "resident"), (True, "under_break_even", None),
    (True, "most", "cover"), (True, "none", "empty"),
    (False, "half", None), (False, "under_break_even", None),
    (False, "most", "cover"), (False, "none", "empty"),
])
def test_window_tile_is_built_where_it_is_cheaper_than_the_scan_it_saves(
    resident, cover, declined, monkeypatch
):
    """The decision of `ensure_window_tile` after the count: over planes
    that are not on the device a tile is built up to a cover of one half
    (it uploads the window's rows instead of the plane); over resident
    planes only where the host's build costs less than the device's masked
    scan of the padded rows the tile would spare; a window over most of
    the rows, or over none, declines wherever the planes are."""
    import jax.numpy as jnp
    import numpy as np

    from greptimedb_tpu.parallel.tile_cache import TileCacheManager

    codes = np.repeat(np.arange(6, dtype=np.int32), 4000)
    ts = np.tile(np.arange(4000, dtype=np.int64) * 10, 6)
    entry = _host_entry(codes, ts)
    monkeypatch.setattr(TileCacheManager, "_WINDOW_TILE_MIN_ROWS", len(ts))
    monkeypatch.setattr(TileCacheManager, "_WINDOW_TILE_GRID", 1 << 10)
    cache = TileCacheManager(budget_bytes=1 << 30)
    if resident:
        pad = np.zeros(entry.pad - len(ts), np.int64)
        entry.valid = [jnp.asarray(np.arange(entry.pad) < len(ts))]
        entry.cols = {
            name: [jnp.asarray(np.r_[plane, pad.astype(plane.dtype)])]
            for name, plane in entry.sorted_host.items()
        }
    n = 6 * _DECISION_TICKS[cover]
    # what the rule compares, at its constants: 24,000 rows pad to 2^15
    build_ns = n * TileCacheManager._WINDOW_BUILD_NS_PER_ROW
    scan_ns = (entry.pad - cache._window_pad(n)) * TileCacheManager._WINDOW_SCAN_NS_PER_ROW
    assert (build_ns < scan_ns) == (cover in ("under_break_even", "none"))
    before = {
        k: getattr(metrics, k).get()
        for k in ("TILE_WINDOW_COUNTED", "TILE_WINDOW_BUILDS", "TILE_WINDOW_RESIDENT_SCANS")
    }
    window = (5000, 5000 + 10 * _DECISION_TICKS[cover])
    src, why = cache.ensure_window_tile(entry, window, "ts", {"host", "ts"}, set(), False, 0)
    moved = {k: getattr(metrics, k).get() - v for k, v in before.items()}
    assert why == declined and (src is None) == (declined is not None)
    assert moved == {
        "TILE_WINDOW_COUNTED": 1,
        "TILE_WINDOW_BUILDS": int(declined is None),
        "TILE_WINDOW_RESIDENT_SCANS": int(declined == "resident"),
    }
    if declined is None:
        wt = entry.window_tiles[(*window, False)]
        assert wt["rows"] == n and set(wt["cols"]) == {"host", "ts"}
    else:
        assert not entry.window_tiles


def test_window_tile_declines_by_count_without_a_pass_over_the_plane(db, monkeypatch):
    """A window over most of the entry declines on the count the run
    bounds give: the probe is counted, nothing is built, the pass notes
    the decline as before, and `np.flatnonzero` is never reached inside
    the probe (the run bounds were built by the first probe, as a run's
    warm-up builds them)."""
    import numpy as np

    from greptimedb_tpu.parallel.tile_cache import TileCacheManager
    from greptimedb_tpu.query import passes

    db.config.query.disabled_passes = ("cold_host_serve",)  # device-path mechanics under test
    db.config.query.fallback_to_cpu = False  # a raise inside the probe must surface
    monkeypatch.setattr(TileCacheManager, "_WINDOW_TILE_MIN_ROWS", 1 << 14)
    _mk_cpu_table(db)
    n = 1 << 16
    db.insert_rows("cpu", pa.table({
        "host": pa.array(np.repeat([f"h{i}" for i in range(8)], n // 8)),
        "region": pa.array(np.repeat("r0", n)),
        "ts": pa.array(np.tile(np.arange(n // 8, dtype=np.int64) * 1000, 8), pa.timestamp("ms")),
        "usage_user": pa.array(np.random.default_rng(9).uniform(0, 100, n)),
        "usage_system": pa.array(np.zeros(n)),
    }))
    db.sql("ADMIN flush_table('cpu')")
    q = ("SELECT host, count(*) AS c, avg(usage_user) AS a FROM cpu"
         " WHERE ts >= {} AND ts < 8000000 GROUP BY host ORDER BY host")
    db.sql_one(q.format(100000))  # builds the entry and, in its probe, the run bounds
    entry = next(iter(db.query_engine.tile_cache._super.values()))
    assert len(entry.ts_run_starts) == 8

    probe = TileCacheManager.ensure_window_tile

    def probe_without_flatnonzero(self, *args, **kwargs):
        def reached(*_a, **_k):
            raise AssertionError("np.flatnonzero reached inside the window probe")

        with monkeypatch.context() as m:
            m.setattr(np, "flatnonzero", reached)
            return probe(self, *args, **kwargs)

    monkeypatch.setattr(TileCacheManager, "ensure_window_tile", probe_without_flatnonzero)
    counted, builds = metrics.TILE_WINDOW_COUNTED.get(), metrics.TILE_WINDOW_BUILDS.get()
    lowered = _tile_count()
    with passes.use_trace(passes.PassTrace()) as trace:
        t1 = db.sql_one(q.format(200000))
    assert metrics.TILE_WINDOW_COUNTED.get() == counted + 1
    assert metrics.TILE_WINDOW_BUILDS.get() == builds and not entry.window_tiles
    assert _tile_count() == lowered + 1  # answered by the full-tile scan on the device
    notes = [d for d in trace.decisions if d.name == "window_tile"]
    assert [(d.fired, d.why, d.attrs["declined"]) for d in notes] == [(
        False, "window covers most of retention: full-tile scan with device masking", "cover",
    )]
    assert t1.to_pydict()["c"] == [7800] * 8


def test_limb_verdict_keeps_a_few_row_group_inside_the_float32_bar(db):
    """A group of one small row beside blocks of large values: its limb bound
    (half a quantization step, 2^-23 at a block maximum under 128) is 5.7e-8
    of its sum, which with the float32 rounding of a shipped avg (5.96e-8)
    would pass the 1.2e-7 the deployments guarantee.  The verdict holds the
    bound to `_LIMB_VERDICT_RTOL` and the query reruns in exact f64."""
    import numpy as np

    from greptimedb_tpu.parallel import tile_cache

    db.config.query.disabled_passes = ("cold_host_serve",)  # device-path mechanics under test
    _mk_cpu_table(db)
    n = 65536
    # "a" sorts first: its row opens the first block, beside 4095 rows of "h0"
    hosts = np.append(np.repeat([f"h{i}" for i in range(8)], n // 8), "a")
    ts = np.append(np.tile(np.arange(n // 8, dtype=np.int64) * 1000, 8), 0)
    vals = np.append(np.random.default_rng(36).uniform(90, 100, n), 2.1)
    half_step = 2.0 ** -23
    assert tile_cache._LIMB_VERDICT_RTOL < half_step / 2.1 < 1e-7
    assert tile_cache._LIMB_VERDICT_RTOL + 2.0 ** -24 < 1.2e-7
    db.insert_rows("cpu", pa.table({
        "host": pa.array(hosts),
        "region": pa.array(np.repeat("r0", n + 1)),
        "ts": pa.array(ts, pa.timestamp("ms")),
        "usage_user": pa.array(vals),
        "usage_system": pa.array(vals),
    }))
    db.sql("ADMIN flush_table('cpu')")
    q = "SELECT host, avg(usage_user) AS au, count(*) AS c FROM cpu GROUP BY host ORDER BY host"
    reruns, lowered = metrics.TILE_LIMB_RERUNS.get(), _tile_count()
    got = db.sql_one(q).to_pydict()
    assert _tile_count() == lowered + 1, "tile path did not engage"
    assert metrics.TILE_LIMB_RERUNS.get() == reruns + 1, "verdict did not fire"
    assert got["host"][0] == "a" and got["c"][0] == 1 and got["au"][0] == 2.1
    for i in range(8):
        np.testing.assert_allclose(got["au"][i + 1], vals[i * (n // 8):(i + 1) * (n // 8)].mean(), rtol=1e-12)


def test_query_deadline_aborts_cpu_scan(db):
    """query.timeout_s bounds a statement cooperatively: a CPU-path scan
    past its deadline raises QueryTimeoutError instead of grinding (the
    round-4 driver bench died in an unbounded Python parquet scan)."""
    from greptimedb_tpu.utils.errors import QueryTimeoutError

    _mk_cpu_table(db)
    _load(db, ticks=30)
    db.sql("ADMIN flush_table('cpu')")
    db.config.query.backend = "cpu"
    db.config.query.timeout_s = 1e-9
    try:
        with pytest.raises(QueryTimeoutError):
            db.sql_one("SELECT host, count(*) AS c FROM cpu GROUP BY host")
    finally:
        db.config.query.timeout_s = 0.0
        db.config.query.backend = "tpu"
    # disabled again: the same query serves fine
    assert db.sql_one(
        "SELECT host, count(*) AS c FROM cpu GROUP BY host"
    ).num_rows > 0


def test_limb_kernel_with_mixed_source_sizes(db):
    """A flushed chunk large enough for the MXU limb kernel merged with a
    tiny memtable tail: both sources must emit structurally identical
    AggStates (limb trio vs exact scatter trio) and match the CPU path."""
    import numpy as np

    _mk_cpu_table(db)
    hosts, ticks = 8, 8192  # 65536 rows -> meets the limb fast-path floor
    h = np.repeat([f"host_{i}" for i in range(hosts)], ticks)
    r = np.repeat([f"r{i % 2}" for i in range(hosts)], ticks)
    ts = np.tile(np.arange(ticks, dtype=np.int64) * 1000, hosts)
    rng = np.random.default_rng(3)
    tbl = pa.table({
        "host": pa.array(h), "region": pa.array(r),
        "ts": pa.array(ts, pa.timestamp("ms")),
        "usage_user": pa.array(rng.uniform(0, 100, hosts * ticks)),
        "usage_system": pa.array(rng.uniform(0, 100, hosts * ticks)),
    })
    db.insert_rows("cpu", tbl)
    db.sql("ADMIN flush_table('cpu')")
    # memtable tail AFTER the flushed range (disjoint -> tile path stays on)
    db.sql(
        "INSERT INTO cpu VALUES "
        + ",".join(
            f"('host_{i}', 'r{i % 2}', {ticks * 1000 + j * 1000}, {i + j}, {j})"
            for i in range(hosts)
            for j in range(3)
        )
    )
    q = (
        "SELECT host, avg(usage_user) AS au, sum(usage_system) AS ss,"
        " count(*) AS c FROM cpu GROUP BY host"
    )
    before = _tile_count()
    t1, t2 = _both(db, q)
    assert _tile_count() == before + 1, "tile path did not engage"
    # limb quantization bound is ~1e-9 relative; compare at 1e-7
    s1 = t1.sort_by("host").to_pydict()
    s2 = t2.sort_by("host").to_pydict()
    assert s1["host"] == s2["host"]
    assert s1["c"] == s2["c"]
    np.testing.assert_allclose(s1["au"], s2["au"], rtol=1e-7)
    np.testing.assert_allclose(s1["ss"], s2["ss"], rtol=1e-7)


def test_limb_mixed_magnitude_reruns_exact(db):
    """Groups of tiny values co-blocked with huge values break the limb
    kernel's shared per-block scale; the per-group error-bound verdict
    must detect it and transparently rerun in exact f64."""
    db.config.query.disabled_passes = ("cold_host_serve",)  # device-path mechanics under test
    import numpy as np

    _mk_cpu_table(db)
    n = 65536
    ts = np.arange(n, dtype=np.int64) * 1000
    # alternate magnitude per 600s bucket: 1e9-buckets share blocks with
    # 1.0-buckets, so the small buckets' sums quantize to ~0 in limb mode
    bucket = ts // 600_000
    vals = np.where(bucket % 2 == 0, 1e9, 1.0).astype(np.float64)
    db.insert_rows("cpu", pa.table({
        "host": pa.array(np.repeat("h0", n)),
        "region": pa.array(np.repeat("r0", n)),
        "ts": pa.array(ts, pa.timestamp("ms")),
        "usage_user": pa.array(vals),
        "usage_system": pa.array(vals),
    }))
    db.sql("ADMIN flush_table('cpu')")
    q = ("SELECT time_bucket('600s', ts) AS tb, sum(usage_user) AS su"
         " FROM cpu GROUP BY tb")
    rerun_before = metrics.TILE_LIMB_RERUNS.get()
    before = _tile_count()
    t1, t2 = _both(db, q)
    assert _tile_count() == before + 1, "tile path did not engage"
    assert metrics.TILE_LIMB_RERUNS.get() > rerun_before, "verdict did not fire"
    s1 = t1.sort_by("tb").to_pydict()
    s2 = t2.sort_by("tb").to_pydict()
    assert s1["tb"] == s2["tb"]
    np.testing.assert_allclose(s1["su"], s2["su"], rtol=1e-9)


def test_packed_readback_large_group_space(db):
    """>= 2^14 groups engages the byte-packed result buffer: bit-packed
    uint8 gating rows + f32 avg rows + hand-computed host offsets (and,
    with a count(*) output, the exact-int32 variant).  Round-trips must
    match the CPU path."""
    import numpy as np

    _mk_cpu_table(db)
    hosts, ticks = 32, 2048  # 65536 rows; 32 hosts x 512 buckets = 16384 groups
    h = np.repeat([f"host_{i:02d}" for i in range(hosts)], ticks)
    r = np.repeat([f"r{i % 2}" for i in range(hosts)], ticks)
    ts = np.tile(np.arange(ticks, dtype=np.int64) * 1000, hosts)
    rng = np.random.default_rng(17)
    tbl = pa.table({
        "host": pa.array(h), "region": pa.array(r),
        "ts": pa.array(ts, pa.timestamp("ms")),
        "usage_user": pa.array(rng.uniform(0, 100, hosts * ticks)),
        "usage_system": pa.array(rng.uniform(0, 100, hosts * ticks)),
    })
    db.insert_rows("cpu", tbl)
    db.sql("ADMIN flush_table('cpu')")
    # avg-only -> uint8 bit-packed gating rows + f32 avg rows
    q1 = ("SELECT host, time_bucket('4s', ts) AS tb, avg(usage_user) AS au"
          " FROM cpu GROUP BY host, tb")
    # count(*) -> exact int32 rows alongside the f32 avg rows
    q2 = ("SELECT host, time_bucket('4s', ts) AS tb, avg(usage_user) AS au,"
          " count(*) AS c FROM cpu GROUP BY host, tb")
    for q in (q1, q2):
        before = _tile_count()
        t1, t2 = _both(db, q)
        assert _tile_count() == before + 1, "tile path did not engage"
        s1 = t1.sort_by([("host", "ascending"), ("tb", "ascending")]).to_pydict()
        s2 = t2.sort_by([("host", "ascending"), ("tb", "ascending")]).to_pydict()
        assert s1["host"] == s2["host"] and s1["tb"] == s2["tb"]
        # f32-packed avg: 6e-8 relative
        np.testing.assert_allclose(s1["au"], s2["au"], rtol=1e-6)
        if "c" in s1:
            assert s1["c"] == s2["c"]


def test_overlapping_flushes_dedup_on_tile_path(db):
    """Same keys written twice across flushes -> the tile path ENGAGES
    with the last-write-wins keep plane (round 3 silently lost the TPU
    path to any overwrite workload) and matches the scan path."""
    _mk_cpu_table(db)
    _load(db, ticks=50)
    db.sql("ADMIN flush_table('cpu')")
    _load(db, ticks=50)  # identical (host, ts) keys again
    db.sql("ADMIN flush_table('cpu')")
    before = _tile_count()
    t1, t2 = _both(db, Q)
    assert _tile_count() == before + 1, "tile path did not engage on overlap"
    _assert_equal(t1, t2, ["host", "tb"])
    # last-write-wins: counts match the single-write load
    assert sum(t1["c"].to_pylist()) == 50 * 6


def test_overwrite_changes_values_last_write_wins(db):
    """Overwriting flushes with DIFFERENT values: the keep plane must
    select the newer file's rows, not just collapse counts."""
    import numpy as np

    _mk_cpu_table(db)
    n = 512
    ts = np.arange(n, dtype=np.int64) * 1000
    base = {
        "host": pa.array(["h0"] * n),
        "region": pa.array(["r0"] * n),
        "ts": pa.array(ts, pa.timestamp("ms")),
        "usage_system": pa.array(np.zeros(n)),
    }
    db.insert_rows("cpu", pa.table({**base, "usage_user": pa.array(np.full(n, 1.0))}))
    db.sql("ADMIN flush_table('cpu')")
    # overwrite the middle half with value 5.0
    mid = slice(n // 4, 3 * n // 4)
    db.insert_rows("cpu", pa.table({
        "host": pa.array(["h0"] * (n // 2)),
        "region": pa.array(["r0"] * (n // 2)),
        "ts": pa.array(ts[mid], pa.timestamp("ms")),
        "usage_user": pa.array(np.full(n // 2, 5.0)),
        "usage_system": pa.array(np.zeros(n // 2)),
    }))
    db.sql("ADMIN flush_table('cpu')")
    q = ("SELECT host, count(*) AS c, sum(usage_user) AS s, max(usage_user) AS m"
         " FROM cpu GROUP BY host")
    t1, t2 = _both(db, q)
    _assert_equal(t1, t2, ["host"])
    assert t1["c"].to_pylist() == [n]
    assert t1["s"].to_pylist() == [float(n // 2) * 1.0 + float(n // 2) * 5.0]
    assert t1["m"].to_pylist() == [5.0]


def test_append_mode_keeps_duplicates_and_tiles(db):
    _mk_cpu_table(db, append=True)
    _load(db, ticks=50)
    db.sql("ADMIN flush_table('cpu')")
    _load(db, ticks=50)  # duplicates are KEPT in append mode
    db.sql("ADMIN flush_table('cpu')")
    before = _tile_count()
    t1, t2 = _both(db, Q)
    assert _tile_count() == before + 1, "append_mode table should tile"
    _assert_equal(t1, t2, ["host", "tb"])
    assert sum(t1["c"].to_pylist()) == 2 * 50 * 6


def test_append_mode_rejects_delete(db):
    _mk_cpu_table(db, append=True)
    _load(db, ticks=5)
    with pytest.raises(Exception, match="append_mode"):
        db.sql("DELETE FROM cpu WHERE host = 'host_0'")


def test_deleted_rows_fall_back(db):
    _mk_cpu_table(db)
    _load(db, ticks=30)
    db.sql("DELETE FROM cpu WHERE host = 'host_3' AND ts < 10000")
    db.sql("ADMIN flush_table('cpu')")
    before = _tile_count()
    t1, t2 = _both(db, Q)
    assert _tile_count() == before, "tombstoned file must not tile"
    _assert_equal(t1, t2, ["host", "tb"])


def test_dictionary_growth_repairs_cached_tiles(db):
    """New tag values that sort BEFORE existing ones shift codes; cached
    tiles must be remapped (not re-read) and results stay correct."""
    _mk_cpu_table(db)
    _load(db, hosts=4, ticks=40)
    db.sql("ADMIN flush_table('cpu')")
    db.sql_one(Q)  # tiles built with codes for host_0..host_3
    d = db.dicts.get("public.cpu")
    epoch0 = d.epoch
    # 'aaa_host' sorts before every existing value -> all codes shift
    rows = [f"('aaa_host', 'r0', {1_000_000 + t * 1000}, 1.5, 2.5)" for t in range(20)]
    db.sql("INSERT INTO cpu VALUES " + ",".join(rows))
    db.sql("ADMIN flush_table('cpu')")
    before = _tile_count()
    t1, t2 = _both(db, Q)
    assert _tile_count() == before + 1
    assert d.epoch > epoch0
    _assert_equal(t1, t2, ["host", "tb"])
    assert "aaa_host" in set(t1["host"].to_pylist())


def test_filters_on_tags_and_values(db):
    _mk_cpu_table(db)
    _load(db)
    db.sql("ADMIN flush_table('cpu')")
    for q in [
        "SELECT host, count(*) AS c FROM cpu WHERE region = 'r0' GROUP BY host",
        "SELECT host, count(*) AS c FROM cpu WHERE host > 'host_2' GROUP BY host",
        "SELECT host, count(*) AS c FROM cpu WHERE host <= 'host_3' GROUP BY host",
        "SELECT host, count(*) AS c FROM cpu WHERE host IN ('host_1','host_4') GROUP BY host",
        "SELECT host, sum(usage_user) AS s FROM cpu WHERE usage_system > 3 GROUP BY host",
        "SELECT host, max(usage_user) AS m FROM cpu WHERE ts >= 30000 AND ts < 90000 GROUP BY host",
    ]:
        t1, t2 = _both(db, q)
        _assert_equal(t1, t2, [t1.column_names[0]])


def test_string_inequality_filter_is_exact(db):
    """Sorted dictionary codes make host > 'host_2' exact on codes."""
    _mk_cpu_table(db)
    _load(db, hosts=6, ticks=10)
    db.sql("ADMIN flush_table('cpu')")
    t = db.sql_one("SELECT host, count(*) AS c FROM cpu WHERE host > 'host_2' GROUP BY host")
    hosts = sorted(set(t["host"].to_pylist()))
    assert hosts == ["host_3", "host_4", "host_5"]


def test_null_tags_and_values(db):
    db.sql(
        "CREATE TABLE n (host STRING, ts TIMESTAMP TIME INDEX, v DOUBLE,"
        " PRIMARY KEY (host))"
    )
    db.sql(
        "INSERT INTO n VALUES ('a', 1000, 1.0), (NULL, 2000, 2.0),"
        " ('b', 3000, NULL), (NULL, 4000, NULL), ('a', 5000, 5.0)"
    )
    db.sql("ADMIN flush_table('n')")
    q = "SELECT host, sum(v) AS s, count(v) AS cv, count(*) AS c FROM n GROUP BY host"
    before = _tile_count()
    t1, t2 = _both(db, q)
    assert _tile_count() == before + 1
    assert t1.num_rows == 3  # 'a', 'b', NULL groups
    _assert_equal(t1, t2, ["host"])


def test_dictionary_persists_across_restart(db, tmp_path):
    _mk_cpu_table(db)
    _load(db, hosts=3, ticks=20)
    db.sql("ADMIN flush_table('cpu')")
    db.sql_one(Q)
    vals = db.dicts.get("public.cpu").values("host")
    db.close()
    db2 = Database(data_home=str(tmp_path / "db"))
    try:
        assert db2.dicts.get("public.cpu").values("host") == vals
        t1, t2 = _both(db2, Q)
        _assert_equal(t1, t2, ["host", "tb"])
    finally:
        db2.close()


def test_eviction_under_tiny_budget(db):
    db.query_engine.tile_cache.budget = 1  # evict everything not pinned
    _mk_cpu_table(db)
    _load(db)
    db.sql("ADMIN flush_table('cpu')")
    t1a = db.sql_one(Q)
    e0 = metrics.TILE_CACHE_EVICTIONS.get()
    t1b = db.sql_one(Q)  # rebuilt after eviction, still correct
    assert metrics.TILE_CACHE_EVICTIONS.get() >= e0
    _assert_equal(t1a, t1b, ["host", "tb"])


def test_compaction_invalidates_tiles(db):
    _mk_cpu_table(db)
    _load(db, ticks=40)
    db.sql("ADMIN flush_table('cpu')")
    db.sql_one(Q)
    _load(db, ticks=40, t0=200_000)
    db.sql("ADMIN flush_table('cpu')")
    db.sql("ADMIN compact_table('cpu')")
    t1, t2 = _both(db, Q)
    _assert_equal(t1, t2, ["host", "tb"])


def test_ungrouped_aggregate_tiles(db):
    _mk_cpu_table(db)
    _load(db, ticks=30)
    db.sql("ADMIN flush_table('cpu')")
    before = _tile_count()
    t1, t2 = _both(db, "SELECT max(usage_user) AS m, count(*) AS c FROM cpu")
    assert _tile_count() == before + 1
    assert t1["m"].to_pylist() == t2["m"].to_pylist()
    assert t1["c"].to_pylist() == t2["c"].to_pylist()


def test_bucket_only_groupby_time_major(db):
    """Bucket-only GROUP BY (TSBS single-groupby / groupby-orderby-limit
    shape) rides the time-major permutation and must match CPU."""
    _mk_cpu_table(db)
    _load(db)
    db.sql("ADMIN flush_table('cpu')")
    before = _tile_count()
    q = (
        "SELECT time_bucket('10s', ts) AS tb, max(usage_user) AS mu,"
        " count(*) AS c FROM cpu GROUP BY tb"
    )
    t1, t2 = _both(db, q)
    assert _tile_count() == before + 1, "bucket-only query did not tile"
    _assert_equal(t1, t2, ["tb"])


def test_non_prefix_group_hierarchical(db):
    """GROUP BY the second pk column (region) forces the hierarchical
    (pk x bucket) layout with an on-device fold; results must match CPU."""
    _mk_cpu_table(db)
    _load(db)
    db.sql("ADMIN flush_table('cpu')")
    before = _tile_count()
    q = (
        "SELECT region, time_bucket('30s', ts) AS tb, avg(usage_user) AS au,"
        " min(usage_system) AS ms FROM cpu GROUP BY region, tb"
    )
    t1, t2 = _both(db, q)
    assert _tile_count() == before + 1, "hierarchical layout did not tile"
    _assert_equal(t1, t2, ["region", "tb"])
    # and without a bucket: non-prefix tag subset alone
    q2 = "SELECT region, sum(usage_user) AS s FROM cpu GROUP BY region"
    t1, t2 = _both(db, q2)
    _assert_equal(t1, t2, ["region"])


def test_windowed_query_tiles_despite_out_of_window_overlap(db):
    """Overlap confined to OLD files must not disqualify a windowed query
    whose in-window sources are disjoint (round-3 gate: eligibility is
    judged per query window, not whole-table)."""
    _mk_cpu_table(db)
    _load(db, ticks=50)
    db.sql("ADMIN flush_table('cpu')")
    _load(db, ticks=50)  # same (host, ts) keys -> overlapping history
    db.sql("ADMIN flush_table('cpu')")
    _load(db, ticks=50, t0=1_000_000)  # disjoint recent window
    db.sql("ADMIN flush_table('cpu')")
    before = _tile_count()
    q = (
        "SELECT host, count(*) AS c FROM cpu"
        " WHERE ts >= 1000000 AND ts < 2000000 GROUP BY host"
    )
    t1, t2 = _both(db, q)
    assert _tile_count() == before + 1, "windowed query should tile"
    _assert_equal(t1, t2, ["host"])
    assert sum(t1["c"].to_pylist()) == 50 * 6
    # whole-table query now tiles TOO: in-window overlap engages the
    # last-write-wins keep plane instead of bailing (round 4 dedup kernel)
    before = _tile_count()
    t1, t2 = _both(db, Q)
    assert _tile_count() == before + 1, "overlapping whole-table query should tile"
    _assert_equal(t1, t2, ["host", "tb"])


def test_last_value_tiles_on_pk_group(db):
    """lastpoint shape: last_value grouped by the pk prefix tiles; grouped
    by a non-prefix tag it must bail (no hierarchical LAST fold)."""
    _mk_cpu_table(db)
    _load(db)
    db.sql("ADMIN flush_table('cpu')")
    before = _tile_count()
    q = (
        "SELECT host, region, last_value(usage_user ORDER BY ts) AS lu"
        " FROM cpu GROUP BY host, region"
    )
    t1, t2 = _both(db, q)
    assert _tile_count() == before + 1, "pk-group last_value should tile"
    _assert_equal(t1, t2, ["host", "region"])
    q2 = "SELECT region, last_value(usage_user ORDER BY ts) AS lu FROM cpu GROUP BY region"
    before = _tile_count()
    t1, t2 = _both(db, q2)
    assert _tile_count() == before, "non-prefix last_value must not tile"
    _assert_equal(t1, t2, ["region"])


def test_alter_added_column_null_fills_old_files(db):
    """Files predating an ALTER ADD COLUMN contribute NULL for that column
    (reference read-compat semantics) instead of disabling the tile path."""
    _mk_cpu_table(db)
    _load(db, ticks=30)
    db.sql("ADMIN flush_table('cpu')")
    db.sql("ALTER TABLE cpu ADD COLUMN extra DOUBLE")
    rows = [
        f"('host_0', 'r0', {500_000 + t * 1000}, 1.0, 2.0, {t * 1.5})"
        for t in range(20)
    ]
    db.sql("INSERT INTO cpu (host, region, ts, usage_user, usage_system, extra) VALUES "
           + ",".join(rows))
    db.sql("ADMIN flush_table('cpu')")
    before = _tile_count()
    q = "SELECT host, avg(extra) AS ae, count(extra) AS ce, count(*) AS c FROM cpu GROUP BY host"
    t1, t2 = _both(db, q)
    assert _tile_count() == before + 1, "post-ALTER table should still tile"
    _assert_equal(t1, t2, ["host"])


def test_host_fast_path_selective_queries(db):
    """pk-equality + bucket/scalar queries are answered from the sorted
    host encode cache (no device dispatch) and must match CPU exactly."""
    _mk_cpu_table(db)
    _load(db)
    db.sql("ADMIN flush_table('cpu')")
    # warm the super-tile/order with a broad query first
    db.sql_one(Q)
    h0 = metrics.TILE_HOST_FAST_PATH.get()
    for q in [
        "SELECT time_bucket('30s', ts) AS tb, avg(usage_user) AS au,"
        " count(*) AS c FROM cpu WHERE host = 'host_2' GROUP BY tb",
        "SELECT time_bucket('30s', ts) AS tb, max(usage_user) AS mu"
        " FROM cpu WHERE host IN ('host_1','host_4') GROUP BY tb",
        "SELECT count(*) AS n, max(usage_user) AS m FROM cpu"
        " WHERE host = 'host_3' AND usage_system > 2 AND ts >= 10000 AND ts < 60000",
        "SELECT min(usage_user) AS mn, sum(usage_system) AS s FROM cpu"
        " WHERE host = 'host_0' AND region = 'r0'",
    ]:
        t1, t2 = _both(db, q)
        keys = [c for c in t1.column_names if c == "tb"]
        _assert_equal(t1, t2, keys or [t1.column_names[0]])
    assert metrics.TILE_HOST_FAST_PATH.get() >= h0 + 4, "host fast path did not engage"


def test_host_fast_path_includes_memtable(db):
    _mk_cpu_table(db)
    _load(db, ticks=40)
    db.sql("ADMIN flush_table('cpu')")
    db.sql_one(Q)
    _load(db, ticks=20, t0=600_000)  # unflushed tail in a disjoint window
    q = ("SELECT count(*) AS c, avg(usage_user) AS au FROM cpu"
         " WHERE host = 'host_1'")
    t1, t2 = _both(db, q)
    _assert_equal(t1, t2, ["c"])
    assert t1["c"].to_pylist()[0] == 60


def test_cold_host_serve_then_device_build(db):
    """A cold grouped aggregate answers from the host consolidation with
    ZERO device plane uploads (uploads dominate a first touch); the
    next touch builds the HBM tiles so warm reps keep
    the one-dispatch path.  Results match the CPU path in both phases.
    Pinned to the LEGACY ladder (tile.fused_build=false) — under the fused
    planner the second touch joins a background build instead
    (tests/test_fused_build.py covers that contract)."""
    db.config.tile.fused_build = False
    _mk_cpu_table(db)
    _load(db, hosts=8, ticks=400)
    db.sql("ADMIN flush_table('cpu')")
    q = ("SELECT host, time_bucket('30s', ts) AS tb, avg(usage_user) AS a,"
         " max(usage_system) AS m, count(*) AS c FROM cpu GROUP BY host, tb")
    served0 = None
    cache = db.query_engine.tile_cache
    t1 = db.sql_one(q)
    entries = list(cache._super.values())
    assert entries, "super-tile entry should exist after the cold query"
    assert all(getattr(e, "cold_served", False) for e in entries), (
        "cold query must be host-served once"
    )
    assert all(not e.cols for e in entries), (
        f"cold serve must not upload planes: {[list(e.cols) for e in entries]}"
    )
    # second touch builds the device planes
    t2 = db.sql_one(q)
    assert any(e.cols for e in cache._super.values()), (
        "second touch must build device tiles"
    )
    db.config.query.backend = "cpu"
    t3 = db.sql_one(q)
    db.config.query.backend = "tpu"
    for t in (t1, t2):
        s1 = t.sort_by([("host", "ascending"), ("tb", "ascending")]).to_pydict()
        s3 = t3.sort_by([("host", "ascending"), ("tb", "ascending")]).to_pydict()
        assert s1["host"] == s3["host"] and s1["c"] == s3["c"]
        import numpy as _np

        _np.testing.assert_allclose(s1["a"], s3["a"], rtol=1e-9)
        _np.testing.assert_allclose(s1["m"], s3["m"], rtol=1e-12)
