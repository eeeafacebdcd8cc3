"""Incremental (delta) super-tile maintenance + pipelined cold path.

Contracts under test (ISSUE 4 acceptance):
  * an incrementally-maintained super-tile (N flush deltas, interleaved
    plane evictions and emergency_release) is BIT-IDENTICAL to a
    from-scratch rebuild — order, sorted host planes, dedup keep mask and
    query results — across null tags/values, duplicate timestamps
    (last-write-wins dedup-keep) and sum/avg (limb-plane) columns;
  * post-flush cost is O(delta): the delta merge re-encodes ONLY the new
    file(s) (greptime_tile_cache_misses_total counts per-file encodes)
    and extends the SAME entry object (no invalidate-and-rebuild);
  * `tile.incremental = false` restores the drop-and-rebuild path
    bit-for-bit; `query.streamed_readback = false` restores the single
    batched device_get bit-for-bit;
  * last_value group-bys (TSBS lastpoint) ship through the compact
    device-finalize path (O(rows_out) readback).
"""

import math

import numpy as np
import pyarrow as pa
import pytest

from greptimedb_tpu.database import Database
from greptimedb_tpu.utils import metrics
from greptimedb_tpu.utils.config import Config


def _mk_db(tmp_path, name="db", **tile_kw):
    cfg = Config()
    # background compaction would merge the delta files mid-test and make
    # the file-set/order comparison ambiguous — the delta path itself is
    # what's under test (compaction-changed filesets take the full
    # rebuild by design)
    cfg.storage.compaction_background_enable = False
    for k, v in tile_kw.items():
        setattr(cfg.tile, k, v)
    return Database(data_home=str(tmp_path / name), config=cfg)


def _batch(rng, n, t_lo, t_hi, null_tags=True, null_vals=True):
    """Random rows with null tags/values and duplicate timestamps (the
    same (pk, ts) key recurs across batches -> last-write-wins dedup)."""
    hosts = rng.choice([f"h{i}" for i in range(4)], n)
    regions = rng.choice(["r0", "r1", None] if null_tags else ["r0", "r1"], n)
    ts = rng.integers(t_lo, t_hi, n) * 1000
    v = rng.uniform(0, 100, n)
    w = rng.uniform(0, 100, n)
    w_mask = rng.random(n) < 0.2 if null_vals else np.zeros(n, bool)
    return pa.table({
        "host": pa.array(hosts),
        "region": pa.array(regions),
        "ts": pa.array(ts, pa.timestamp("ms")),
        "v": pa.array(v),
        "w": pa.array(np.where(w_mask, np.nan, w), pa.float64(),
                      mask=w_mask),
    })


Q = (
    "SELECT host, region, time_bucket('60s', ts) AS tb, avg(v) AS av,"
    " max(v) AS mv, sum(v) AS sv, count(*) AS c, count(w) AS cw,"
    " avg(w) AS aw FROM t GROUP BY host, region, tb"
)
KEYS = [("host", "ascending"), ("region", "ascending"), ("tb", "ascending")]


def _entry(db):
    return next(iter(db.query_engine.tile_cache._super.values()))


def _pydict(t):
    return t.sort_by(KEYS).to_pydict()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_incremental_bit_identical_to_rebuild_randomized(tmp_path, seed):
    rng = np.random.default_rng(seed)
    db = _mk_db(tmp_path, f"s{seed}")
    try:
        db.sql(
            "CREATE TABLE t (host STRING, region STRING,"
            " ts TIMESTAMP(3) TIME INDEX, v DOUBLE, w DOUBLE,"
            " PRIMARY KEY (host, region))"
        )
        tc = db.query_engine.tile_cache
        merges0 = metrics.TILE_DELTA_MERGES.get()
        n_flushes = 4
        for i in range(n_flushes):
            # overlapping ts ranges across flushes: duplicate (pk, ts)
            # keys force the dedup-keep plane on the tile path
            db.insert_rows("t", _batch(rng, 300, 0, 600))
            db.sql("ADMIN flush_table('t')")
            db.sql_one(Q)  # touch: cold-serve then device build / delta
            db.sql_one(Q)
            if i == 1:
                # strip every re-derivable plane mid-sequence: the next
                # delta must survive an emergency-released entry
                tc.emergency_release(set())
            if i == 2:
                tc.release_unneeded(_entry(db), set())
        assert metrics.TILE_DELTA_MERGES.get() - merges0 == n_flushes - 1, (
            "every appended flush after the first must delta-merge"
        )
        t_inc = db.sql_one(Q)
        entry = _entry(db)
        assert len(entry.file_ids) == n_flushes
        assert tc.ensure_dedup_keep(entry)
        order_inc = np.array(entry.order)
        sh_inc = {k: np.array(v) for k, v in entry.sorted_host.items()}
        keep_inc = np.array(entry.keep_host)

        # from-scratch rebuild over the SAME files (compaction disabled)
        tc.invalidate_region(entry.region_id)
        db.sql_one(Q)
        t_rb = db.sql_one(Q)
        rebuilt = _entry(db)
        assert rebuilt is not entry
        assert tc.ensure_dedup_keep(rebuilt)
        assert np.array_equal(order_inc, np.array(rebuilt.order))
        for k, arr in sh_inc.items():
            assert np.array_equal(arr, np.array(rebuilt.sorted_host[k])), k
        assert np.array_equal(keep_inc, np.array(rebuilt.keep_host))
        assert _pydict(t_inc) == _pydict(t_rb)

        # CPU path is the independent ground truth
        db.config.query.backend = "cpu"
        t_cpu = db.sql_one(Q)
        db.config.query.backend = "tpu"
        a, b = _pydict(t_inc), _pydict(t_cpu)
        assert set(a) == set(b) and len(a["host"]) == len(b["host"])
        for col in a:
            for x, y in zip(a[col], b[col]):
                if isinstance(x, float) and isinstance(y, float):
                    assert (
                        math.isclose(x, y, rel_tol=1e-9)
                        or (math.isnan(x) and math.isnan(y))
                    ), (col, x, y)
                else:
                    assert x == y, (col, x, y)
    finally:
        db.close()


def test_incremental_off_restores_rebuild_path(tmp_path):
    rng = np.random.default_rng(7)
    batches = [_batch(rng, 200, 0, 400) for _ in range(3)]
    results = {}
    for mode in (True, False):
        db = _mk_db(tmp_path, f"inc_{mode}", incremental=mode)
        try:
            db.sql(
                "CREATE TABLE t (host STRING, region STRING,"
                " ts TIMESTAMP(3) TIME INDEX, v DOUBLE, w DOUBLE,"
                " PRIMARY KEY (host, region))"
            )
            merges0 = metrics.TILE_DELTA_MERGES.get()
            first_entry = None
            for b in batches:
                db.insert_rows("t", b)
                db.sql("ADMIN flush_table('t')")
                db.sql_one(Q)
                db.sql_one(Q)
                if first_entry is None:
                    first_entry = _entry(db)
            if mode:
                assert metrics.TILE_DELTA_MERGES.get() - merges0 == 2
                assert _entry(db) is first_entry, (
                    "incremental path must extend the entry in place"
                )
            else:
                assert metrics.TILE_DELTA_MERGES.get() == merges0, (
                    "tile.incremental=false must never delta-merge"
                )
                assert _entry(db) is not first_entry
            results[mode] = _pydict(db.sql_one(Q))
        finally:
            db.close()
    assert results[True] == results[False], (
        "incremental on/off must be bit-identical"
    )


def test_delta_flush_is_o_delta_not_o_total(tmp_path):
    """Acceptance: after an initial build, a <=5% flush reaches
    warm-equivalent service without a full rebuild — the delta merge
    re-encodes ONLY the new file and extends the live entry, and prewarm
    drives it off the query path (prewarm_builds + tile_delta_merges)."""
    db = _mk_db(tmp_path, "odelta")
    try:
        db.sql(
            "CREATE TABLE t (host STRING, region STRING,"
            " ts TIMESTAMP(3) TIME INDEX, v DOUBLE, w DOUBLE,"
            " PRIMARY KEY (host, region))"
        )
        rng = np.random.default_rng(11)
        db.insert_rows("t", _batch(rng, 4000, 0, 4000, null_tags=False,
                                   null_vals=False))
        db.sql("ADMIN flush_table('t')")
        db.prewarm(tables=["t"])
        db.sql_one(Q)
        db.sql_one(Q)  # device planes warm
        entry = _entry(db)
        misses0 = metrics.TILE_CACHE_MISSES.get()
        merges0 = metrics.TILE_DELTA_MERGES.get()
        drows0 = metrics.TILE_DELTA_ROWS.get()
        pw0 = metrics.PREWARM_BUILDS.get()
        # <= 5% delta, disjoint ts range (no dedup churn)
        db.insert_rows("t", _batch(rng, 200, 5000, 5400, null_tags=False,
                                   null_vals=False))
        db.sql("ADMIN flush_table('t')")
        db.prewarm(tables=["t"])  # the flush-listener path calls this
        assert metrics.PREWARM_BUILDS.get() > pw0
        assert metrics.TILE_DELTA_MERGES.get() == merges0 + 1
        # duplicate keys WITHIN the batch dedup at flush, so the delta
        # file holds at most the inserted row count
        assert drows0 < metrics.TILE_DELTA_ROWS.get() <= drows0 + 200
        # O(delta): exactly ONE new per-file host encode (the delta file);
        # the old file's rows were never re-read or re-encoded
        assert metrics.TILE_CACHE_MISSES.get() == misses0 + 1
        assert _entry(db) is entry, "full rebuild ran despite the delta path"
        t1 = db.sql_one(Q)
        db.config.query.backend = "cpu"
        t2 = db.sql_one(Q)
        db.config.query.backend = "tpu"
        assert t1.num_rows == t2.num_rows
    finally:
        db.close()


N_HOSTS = 8
TICKS = 720  # 2 h at 10 s scrape
T0 = 1_767_225_600_000


def _ingest_cpu(db, tick_lo, tick_hi, seed):
    """Every host at every 10 s tick of [tick_lo, tick_hi); returns rows."""
    rng = np.random.default_rng(seed)
    ticks = tick_hi - tick_lo
    n = ticks * N_HOSTS
    ts = T0 + np.repeat(np.arange(tick_lo, tick_hi, dtype=np.int64), N_HOSTS) * 10_000
    db.insert_rows("cpu", pa.table({
        "hostname": pa.array(np.tile([f"host_{i}" for i in range(N_HOSTS)], ticks)),
        "ts": pa.array(ts, pa.timestamp("ms")),
        "usage_user": pa.array(rng.uniform(0, 100, n)),
        "usage_system": pa.array(rng.uniform(0, 100, n)),
    }))
    return n


def test_prewarm_delta_query(tmp_path):
    """One query family through the full engine path on a TSBS-shaped
    append-mode table: prewarm builds the tiles off the query path, the
    post-flush delta (~5 % new rows) merges into the SAME entry instead
    of rebuilding, and the result matches the authoritative CPU path."""
    db = _mk_db(tmp_path, "prewarm_delta")
    try:
        db.sql(
            "CREATE TABLE cpu (hostname STRING, ts TIMESTAMP(3) TIME INDEX,"
            " usage_user DOUBLE, usage_system DOUBLE,"
            " PRIMARY KEY (hostname)) WITH (append_mode = 'true')"
        )
        n = _ingest_cpu(db, 0, TICKS, seed=1)
        db.storage.flush_all()

        # prewarm: the cold consolidation runs OFF the query path
        pw0 = metrics.PREWARM_BUILDS.get()
        db.prewarm(tables=["cpu"])
        assert metrics.PREWARM_BUILDS.get() > pw0

        q = (
            "SELECT hostname, time_bucket('1m', ts) AS tb,"
            " avg(usage_user) AS au FROM cpu GROUP BY hostname, tb"
        )
        lowered0 = metrics.TILE_LOWERED_TOTAL.get()
        db.sql_one(q)
        db.sql_one(q)  # device planes warm (cold-serve answered once)
        assert metrics.TILE_LOWERED_TOTAL.get() > lowered0, (
            "query did not take the tile path"
        )

        # delta flush (~5% new rows) + re-query: must delta-merge, not
        # rebuild
        merges0 = metrics.TILE_DELTA_MERGES.get()
        entry = _entry(db)
        _ingest_cpu(db, TICKS, TICKS + TICKS // 20, seed=2)
        db.storage.flush_all()
        t_delta = db.sql_one(q)
        assert metrics.TILE_DELTA_MERGES.get() == merges0 + 1, (
            "post-flush query rebuilt the super-tile instead of delta-merging"
        )
        assert _entry(db) is entry

        # correctness vs the authoritative CPU path
        db.config.query.backend = "cpu"
        t_cpu = db.sql_one(q)
        db.config.query.backend = "tpu"
        k = [("hostname", "ascending"), ("tb", "ascending")]
        got = t_delta.sort_by(k).to_pydict()
        want = t_cpu.sort_by(k).to_pydict()
        assert got["hostname"] == want["hostname"]
        for x, y in zip(got["au"], want["au"]):
            assert math.isclose(x, y, rel_tol=1e-9), (x, y)
        assert n == TICKS * N_HOSTS
    finally:
        db.close()


def test_window_tiles_survive_disjoint_delta(tmp_path, monkeypatch):
    """A cached window tile whose window cannot contain a delta row stays
    resident (bit-identical data); one the delta intersects is dropped
    and rebuilds on next touch."""
    from greptimedb_tpu.parallel.tile_cache import TileCacheManager

    monkeypatch.setattr(TileCacheManager, "_WINDOW_TILE_MIN_ROWS", 0)
    db = _mk_db(tmp_path, "wt")
    try:
        db.sql(
            "CREATE TABLE t (host STRING, region STRING,"
            " ts TIMESTAMP(3) TIME INDEX, v DOUBLE, w DOUBLE,"
            " PRIMARY KEY (host, region))"
        )
        rng = np.random.default_rng(3)
        db.insert_rows("t", _batch(rng, 3000, 0, 3000, null_tags=False,
                                   null_vals=False))
        db.sql("ADMIN flush_table('t')")
        wq = (
            "SELECT host, time_bucket('60s', ts) AS tb, avg(v) AS av"
            " FROM t WHERE ts >= 0 AND ts < 600000 GROUP BY host, tb"
        )
        db.sql_one(wq)
        db.sql_one(wq)
        db.sql_one(wq)  # ensure the window tile materialized
        entry = _entry(db)
        # the family's build uploads no full plane for a windowed shape, so
        # the tile is built over planes that are not resident (cover 0.2)
        assert entry.window_tiles and "v" not in entry.cols
        # delta strictly ABOVE the window: the tile must survive the merge
        db.insert_rows("t", _batch(rng, 150, 4000, 4400, null_tags=False,
                                   null_vals=False))
        db.sql("ADMIN flush_table('t')")
        t1 = db.sql_one(wq)
        assert _entry(db) is entry
        assert entry.window_tiles, (
            "disjoint delta must not drop the cached window tile"
        )
        # delta INSIDE the window: the stale tile must be dropped (serving
        # it would miss the new rows)
        db.insert_rows("t", _batch(rng, 150, 100, 500, null_tags=False,
                                   null_vals=False))
        db.sql("ADMIN flush_table('t')")
        t2 = db.sql_one(wq)
        db.config.query.backend = "cpu"
        t_cpu = db.sql_one(wq)
        db.config.query.backend = "tpu"
        k = [("host", "ascending"), ("tb", "ascending")]
        got = t2.sort_by(k).to_pydict()
        want = t_cpu.sort_by(k).to_pydict()
        assert got["host"] == want["host"]
        for x, y in zip(got["av"], want["av"]):
            assert math.isclose(x, y, rel_tol=1e-9), (x, y)
        assert t1.num_rows <= t2.num_rows
    finally:
        db.close()


def test_window_probe_counts_the_rows_a_delta_added(tmp_path, monkeypatch):
    """The run bounds a window probe counts from belong to ONE ts plane: a
    delta extend that lengthens the plane drops them, and the next probe
    rebuilds them and counts the new rows too."""
    from greptimedb_tpu.parallel.tile_cache import TileCacheManager

    monkeypatch.setattr(TileCacheManager, "_WINDOW_TILE_MIN_ROWS", 0)
    probes = []
    ranges = TileCacheManager._window_ranges

    def recorded(self, entry, window, ts_name, dedup):
        got = ranges(self, entry, window, ts_name, dedup)
        ts = np.asarray(entry.sorted_host[ts_name])
        mask = (ts >= window[0]) & (ts < window[1])
        if dedup:
            mask &= entry.keep_host
        probes.append((got[2], int(np.count_nonzero(mask)), entry.num_rows))
        return got

    monkeypatch.setattr(TileCacheManager, "_window_ranges", recorded)
    db = _mk_db(tmp_path, "wp")
    db.config.query.disabled_passes = ("cold_host_serve",)  # device-path mechanics under test
    try:
        db.sql(
            "CREATE TABLE t (host STRING, region STRING,"
            " ts TIMESTAMP(3) TIME INDEX, v DOUBLE, w DOUBLE,"
            " PRIMARY KEY (host, region))"
        )
        rng = np.random.default_rng(32)
        db.insert_rows("t", _batch(rng, 3000, 0, 3000, null_tags=False,
                                   null_vals=False))
        db.sql("ADMIN flush_table('t')")
        # the window holds every row: the probe counts and declines each time
        wq = (
            "SELECT host, time_bucket('60s', ts) AS tb, count(*) AS c"
            " FROM t WHERE ts >= 0 AND ts < 9000000 GROUP BY host, tb"
        )
        counted = metrics.TILE_WINDOW_COUNTED.get()
        db.sql_one(wq)
        entry = _entry(db)
        runs = entry.ts_run_starts
        assert runs is not None and probes[-1][0] == probes[-1][1] == entry.num_rows
        db.insert_rows("t", _batch(rng, 150, 4000, 4400, null_tags=False,
                                   null_vals=False))
        db.sql("ADMIN flush_table('t')")
        merges = metrics.TILE_DELTA_MERGES.get()
        t = db.sql_one(wq)
        assert _entry(db) is entry and metrics.TILE_DELTA_MERGES.get() == merges + 1
        assert entry.num_rows > probes[0][2], "the delta added no row"
        assert entry.ts_run_starts is not runs, "run bounds of the old plane reused"
        assert probes[-1][0] == probes[-1][1] == entry.num_rows
        assert sum(t["c"].to_pylist()) == entry.num_rows
        # (after a delta the family's fused build probes the window too)
        assert metrics.TILE_WINDOW_COUNTED.get() == counted + len(probes) >= counted + 2
        assert not entry.window_tiles
        # (GROUP BY host over the key (host, region) groups by series: the
        # region's series table is charged beside the planes it is read from)
        assert entry.host_nbytes == (
            entry.order.nbytes + entry.file_row_offsets.nbytes
            + sum(a.nbytes for a in entry.sorted_host.values())
            + entry.ts_run_starts.nbytes
            + (entry.series_table.nbytes if entry.series_table is not None else 0)
        )
    finally:
        db.close()


def test_lex_merge_positions_matches_stable_lexsort():
    """Property check of the sorted-run merge against numpy's stable
    lexsort over the concatenation — including heavy duplicate keys,
    where stability (old run first) is what keeps last-write-wins dedup
    correct."""
    from greptimedb_tpu.parallel.tile_cache import _lex_merge_positions

    rng = np.random.default_rng(42)
    for _ in range(25):
        n_old = int(rng.integers(0, 200))
        n_new = int(rng.integers(1, 200))
        kspace = int(rng.integers(2, 8))  # tiny key space -> many ties
        old = [
            np.sort(rng.integers(0, kspace, n_old).astype(np.int32)),
            np.zeros(n_old, np.int64),
        ]
        # second key sorted WITHIN runs of the first (lexicographic)
        old[1] = np.sort(rng.integers(0, kspace, n_old).astype(np.int64))
        idx = np.lexsort([old[1], old[0]])
        old = [old[0][idx], old[1][idx]]
        new = [
            rng.integers(0, kspace, n_new).astype(np.int32),
            rng.integers(0, kspace, n_new).astype(np.int64),
        ]
        nidx = np.lexsort([new[1], new[0]])
        new = [new[0][nidx], new[1][nidx]]
        pos = _lex_merge_positions(old, new)
        # reference: stable lexsort of the concat, old rows first
        cat0 = np.concatenate([old[0], new[0]])
        cat1 = np.concatenate([old[1], new[1]])
        ref = np.lexsort([cat1, cat0])
        merged0 = np.empty(n_old + n_new, np.int64)
        shift = np.searchsorted(pos, np.arange(n_old), side="right")
        merged0[np.arange(n_old) + shift] = np.arange(n_old)
        merged0[pos + np.arange(n_new)] = n_old + np.arange(n_new)
        assert np.array_equal(merged0, ref), (n_old, n_new, kspace)


def test_streamed_device_get_bit_identical():
    import jax
    import jax.numpy as jnp

    from greptimedb_tpu.parallel.executor import streamed_device_get

    rng = np.random.default_rng(5)
    buf = jnp.asarray(rng.integers(0, 255, 300_000).astype(np.uint8))
    accs = jnp.asarray(rng.uniform(-1, 1, (3, 20_000)))
    plain = jax.device_get((buf, accs))
    streamed = streamed_device_get([buf, accs], chunk_bytes=64 << 10)
    assert np.array_equal(np.asarray(plain[0]), streamed[0])
    assert np.array_equal(np.asarray(plain[1]), streamed[1])
    assert streamed[1].dtype == np.asarray(plain[1]).dtype


def test_streamed_readback_query_parity(tmp_path):
    """A query whose packed result exceeds 2 chunks streams its readback
    (greptime_tpu_readback_streamed_total) and is bit-identical to the
    query.streamed_readback=false path."""
    db = _mk_db(tmp_path, "srb")
    try:
        db.sql(
            "CREATE TABLE t (host STRING, region STRING,"
            " ts TIMESTAMP(3) TIME INDEX, v DOUBLE, w DOUBLE,"
            " PRIMARY KEY (host, region))"
        )
        rng = np.random.default_rng(9)
        db.insert_rows("t", _batch(rng, 6000, 0, 40_000, null_tags=False,
                                   null_vals=False))
        db.sql("ADMIN flush_table('t')")
        # 1s buckets over 40k seconds: a big group space -> a packed
        # result comfortably past 2 x 64 KiB
        bigq = (
            "SELECT host, region, time_bucket('1s', ts) AS tb,"
            " avg(v) AS av, avg(w) AS aw FROM t GROUP BY host, region, tb"
        )
        db.config.query.readback_chunk_kb = 64
        db.sql_one(bigq)  # build planes
        s0 = metrics.TPU_READBACK_STREAMED.get()
        t_on = db.sql_one(bigq)
        assert metrics.TPU_READBACK_STREAMED.get() > s0, (
            "large fetch did not stream"
        )
        db.config.query.streamed_readback = False
        t_off = db.sql_one(bigq)
        db.config.query.streamed_readback = True
        k = [("host", "ascending"), ("region", "ascending"),
             ("tb", "ascending")]
        assert t_on.sort_by(k).to_pydict() == t_off.sort_by(k).to_pydict()
        # the transfer/decode split landed for attribution
        assert metrics.TPU_READBACK_TRANSFER_MS.total() > 0
        assert metrics.TPU_READBACK_DECODE_MS.total() > 0
    finally:
        db.close()


def test_lastpoint_ships_compact(tmp_path):
    """last_value group-bys ride the compact device-finalize path
    (O(rows_out) fetch) and match the CPU path; query.device_topk=false
    restores the full-buffer path bit-for-bit."""
    db = _mk_db(tmp_path, "lp")
    try:
        db.sql(
            "CREATE TABLE t (host STRING, region STRING,"
            " ts TIMESTAMP(3) TIME INDEX, v DOUBLE, w DOUBLE,"
            " PRIMARY KEY (host, region))"
        )
        rng = np.random.default_rng(13)
        db.insert_rows("t", _batch(rng, 2000, 0, 2000, null_tags=False,
                                   null_vals=False))
        db.sql("ADMIN flush_table('t')")
        lq = (
            "SELECT host, region, last_value(v) AS lv FROM t"
            " GROUP BY host, region"
        )
        db.sql_one(lq)
        df0 = metrics.TPU_DEVICE_FINALIZE.get()
        t_on = db.sql_one(lq)
        assert metrics.TPU_DEVICE_FINALIZE.get() > df0, (
            "lastpoint did not take the compact device-finalize path"
        )
        db.config.query.device_topk = False
        t_off = db.sql_one(lq)
        db.config.query.device_topk = True
        db.config.query.backend = "cpu"
        t_cpu = db.sql_one(lq)
        db.config.query.backend = "tpu"
        k = [("host", "ascending"), ("region", "ascending")]
        assert t_on.sort_by(k).to_pydict() == t_off.sort_by(k).to_pydict()
        assert t_on.sort_by(k).to_pydict() == t_cpu.sort_by(k).to_pydict()
    finally:
        db.close()
