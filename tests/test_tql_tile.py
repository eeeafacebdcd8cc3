"""Warm TQL hot path (query/promql/tile_exec.py, the `tql_tile` pass).

Contracts under test:
  * parity: tile-path TQL results vs the legacy upload-per-query path
    (`tql.tile = false`) — BIT-identical for *_over_time / delta /
    instant vectors / matchers / by-label folds on single-region tables,
    and bit-identical to an independent numpy twin for rate/increase
    (last-ulp tolerance vs legacy only where the reset strip's scan tree
    shape differs — see the tile_exec module docstring);
  * warm contract: a repeated warm TQL rate performs ZERO host->device
    plane builds and exactly ONE device dispatch;
  * cold contract: a family's first query answers from the legacy scan
    (zero tile dispatches) and schedules the background fused build;
  * mesh: 1-device and N-device (tile.mesh_devices) results are
    bit-identical on a hash-partitioned multi-region table;
  * fault `tql.tile`: an injected tile failure degrades to the legacy
    path with the result unchanged
    (`greptime_tql_tile_degraded_total`);
  * label churn: dictionary growth between flushes (new hosts) keeps
    warm results correct through plane repair;
  * large-int64 timestamps: ns-scale inputs through range_windows /
    extrapolated_rate (the utils/jax_env.py x64 note) stay exact;
  * the `rate(val, ts)` SQL scalar computes real delta/elapsed-time.
"""

import tempfile
import time

import numpy as np
import pytest

from greptimedb_tpu.utils import fault_injection as fi
from greptimedb_tpu.utils import metrics as m


@pytest.fixture(autouse=True)
def _clean_faults():
    fi.REGISTRY.disarm()
    yield
    fi.REGISTRY.disarm()


def _db(**tql_overrides):
    from greptimedb_tpu.database import Database
    from greptimedb_tpu.utils.config import Config

    cfg = Config()
    cfg.storage.data_home = tempfile.mkdtemp()
    for k, v in tql_overrides.items():
        setattr(cfg.tql, k, v)
    return Database(config=cfg)


def _load_counter(db, rng, hosts=4, ticks=48, resets=True, nulls=False,
                  table="tq", extra_tag=False, t0=0):
    tag2 = ", dc STRING" if extra_tag else ""
    pk = "host, dc" if extra_tag else "host"
    db.sql(
        f"CREATE TABLE IF NOT EXISTS {table} (host STRING{tag2}, "
        "greptime_value DOUBLE, ts TIMESTAMP(3) TIME INDEX, "
        f"PRIMARY KEY ({pk}))"
    )
    rows = []
    for h in range(hosts):
        v = 0.0
        for t in range(ticks):
            v += rng.uniform(0, 5)
            if resets and rng.random() < 0.06:
                v = rng.uniform(0, 1)  # counter reset
            val = "NULL" if (nulls and rng.random() < 0.08) else f"{v:.6f}"
            dc = f", 'dc{h % 2}'" if extra_tag else ""
            rows.append(f"('h{h}'{dc}, {val}, {t0 + t * 15000})")
    db.sql(f"INSERT INTO {table} VALUES " + ",".join(rows))
    db.sql(f"ADMIN flush_table('{table}')")


def _drain_fused(db, timeout=60.0):
    te = db.query_engine._tile_executor
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        with te._fused_lock:
            if not te._fused_builds and not te._fused_queue:
                return
        time.sleep(0.05)
    raise AssertionError("fused builder did not drain")


def _rows(t):
    return list(zip(*[t[c].to_pylist() for c in t.column_names]))


def _assert_rows_close(got, want, msg=""):
    """Tile vs legacy: labels, timestamps, row count and row order exact;
    the value (last column) within 1e-12 relative — the two paths sum
    f64 samples in different orders (padded plane vs dense scan), so the
    last bit may differ; invisible at the renderer's 6 sig digits."""
    assert len(got) == len(want), msg
    for a, b in zip(got, want):
        assert a[:-1] == b[:-1], msg
        np.testing.assert_allclose(a[-1], b[-1], rtol=1e-12, err_msg=msg)


def _legacy(db, q):
    db.config.tql.tile = False
    try:
        return db.sql_one(q)
    finally:
        db.config.tql.tile = True


def _warm(db, q):
    """Run once (may be cold), drain the background build, run again."""
    db.sql_one(q)
    _drain_fused(db)
    return db.sql_one(q)


# ---- parity ----------------------------------------------------------------

EXACT_QUERIES = [
    "TQL EVAL (60, 540, '25s') avg_over_time(tq[2m])",
    "TQL EVAL (60, 540, '25s') sum_over_time(tq[90s])",
    "TQL EVAL (60, 540, '25s') min_over_time(tq[2m])",
    "TQL EVAL (60, 540, '25s') max_over_time(tq[2m])",
    "TQL EVAL (60, 540, '25s') count_over_time(tq[2m])",
    "TQL EVAL (60, 540, '25s') last_over_time(tq[2m])",
    "TQL EVAL (60, 540, '25s') delta(tq[2m])",
    "TQL EVAL (60, 540, '25s') tq",
    "TQL EVAL (60, 540, '25s') timestamp(tq)",
    "TQL EVAL (60, 540, '25s') tq{host='h1'}",
    "TQL EVAL (60, 540, '25s') tq{host!='h1'}",
    "TQL EVAL (60, 540, '25s') tq{host=~'h[12]'}",
    "TQL EVAL (60, 540, '25s') tq{host!~'h1'}",
    "TQL EVAL (60, 540, '25s') sum by (host) (avg_over_time(tq[2m]))",
    "TQL EVAL (60, 540, '25s') avg by (host) (delta(tq[2m]))",
    "TQL EVAL (60, 540, '25s') min by (host) (tq)",
    "TQL EVAL (60, 540, '25s') max(tq)",
    "TQL EVAL (60, 540, '25s') count(tq)",
    "TQL EVAL (60, 540, '25s') sum(sum_over_time(tq[2m]))",
    "TQL EVAL (60, 540, '25s') sum_over_time(tq[2m] offset 1m)",
    "TQL EVAL (60, 540, '25s') avg_over_time(tq[2m] @ 300)",
    "TQL EVAL (60, 540, '25s') last_over_time(tq[2m] @ end())",
]

ULP_QUERIES = [
    # counter resets: the strip's prefix-scan tree shape differs between
    # the padded tile plane and the legacy dense array — last-ulp only
    "TQL EVAL (60, 540, '25s') rate(tq[2m])",
    "TQL EVAL (60, 540, '25s') increase(tq[2m])",
    "TQL EVAL (60, 540, '25s') sum by (host) (rate(tq[2m]))",
]


def test_tile_parity_seeded():
    """Seeded randomized parity across functions, matchers, NaN gaps
    (NULL values), by-label folds and @/offset modifiers: tile results
    match the legacy path — labels, timestamps and order exactly, values
    within 1e-12 relative (f64 summation order differs)."""
    db = _db()
    try:
        _load_counter(db, np.random.default_rng(11), nulls=True)
        for q in EXACT_QUERIES:
            _warm(db, q)
        for q in EXACT_QUERIES:
            w = db.sql_one(q)
            l = _legacy(db, q)
            _assert_rows_close(_rows(w), _rows(l), f"diverged: {q}")
        for q in ULP_QUERIES:
            w = _warm(db, q)
            l = _legacy(db, q)
            _assert_rows_close(_rows(w), _rows(l), q)
        # tile-path determinism: same query, same bytes
        q = ULP_QUERIES[0]
        assert _rows(db.sql_one(q)) == _rows(db.sql_one(q))
        assert m.TQL_TILE_DEGRADED.get() == 0
        assert m.TQL_TILE_DISPATCHES.get() > 0
    finally:
        db.close()


def test_tile_matches_numpy_twin():
    """rate() vs an independent numpy reimplementation of Prometheus'
    extrapolatedRate over the same flat samples (resets stripped with a
    sequential cumsum): tight-tolerance agreement on every defined
    cell, identical defined-cell sets."""
    db = _db()
    try:
        rng = np.random.default_rng(23)
        _load_counter(db, rng, hosts=3, ticks=40)
        start, end, step, rng_ms = 60_000, 540_000, 30_000, 120_000
        q = "TQL EVAL (60, 540, '30s') rate(tq[2m])"
        w = _warm(db, q)
        # ground truth from the raw samples
        raw = db.sql_one(
            "SELECT host, ts, greptime_value AS v FROM tq ORDER BY host, ts"
        )
        hosts = raw["host"].to_pylist()
        import pyarrow as pa

        ts = np.asarray(raw["ts"].cast(pa.int64()).to_pylist(), np.int64)
        vals = np.asarray(raw["v"].to_pylist(), np.float64)
        twin: dict = {}
        steps = np.arange(start, end + 1, step, dtype=np.int64)
        for h in sorted(set(hosts)):
            sel = np.asarray([x == h for x in hosts])
            hts, hv = ts[sel], vals[sel]
            keep = (hts >= start - rng_ms) & (hts <= end)
            hts, hv = hts[keep], hv[keep]
            # sequential reset strip
            adj = hv.copy()
            acc = 0.0
            for i in range(1, len(adj)):
                if hv[i] < hv[i - 1]:
                    acc += hv[i - 1]
                adj[i] = hv[i] + acc
            for t1 in steps:
                wmask = (hts > t1 - rng_ms) & (hts <= t1)
                if wmask.sum() < 2:
                    continue
                wts, wv = hts[wmask], adj[wmask]
                si = float(wts[-1] - wts[0])
                avg = si / (len(wts) - 1)
                d_start, d_end = float(wts[0] - (t1 - rng_ms)), float(t1 - wts[-1])
                thr = avg * 1.1
                ext_s = d_start if d_start < thr else avg / 2.0
                ext_e = d_end if d_end < thr else avg / 2.0
                result = wv[-1] - wv[0]
                raw_first = hv[wmask][0]  # Prometheus clamps with the RAW sample
                if result > 0 and raw_first >= 0:
                    zero_dur = si * (raw_first / result)
                    if zero_dur < ext_s:
                        ext_s = zero_dur
                twin[(h, int(t1))] = (
                    result * ((si + ext_s + ext_e) / si) / (rng_ms / 1000.0)
                )
        got = {}
        for h, t1, v in zip(
            w["host"].to_pylist(),
            w["ts"].cast(pa.int64()).to_pylist(),
            w["value"].to_pylist(),
        ):
            got[(h, int(t1))] = v
        assert set(got) == set(twin)
        for k in twin:
            np.testing.assert_allclose(got[k], twin[k], rtol=1e-9, err_msg=k)
    finally:
        db.close()


# ---- warm / cold contracts -------------------------------------------------


def test_warm_zero_uploads_one_dispatch():
    """THE warm contract: a repeated warm TQL rate performs zero
    host->device plane builds (no tile-cache misses, planes untouched)
    and exactly one device dispatch per query."""
    db = _db()
    try:
        _load_counter(db, np.random.default_rng(3))
        q = "TQL EVAL (60, 540, '30s') rate(tq[2m])"
        _warm(db, q)
        entry = next(iter(db.query_engine.tile_cache._super.values()))
        plane_ids = {
            name: [id(c) for c in chunks] for name, chunks in entry.cols.items()
        }
        for _ in range(3):
            misses0 = m.TILE_CACHE_MISSES.get()
            disp0 = m.TPU_DEVICE_DISPATCHES.get()
            tql0 = m.TQL_TILE_DISPATCHES.get()
            out = db.sql_one(q)
            assert out.num_rows > 0
            assert m.TILE_CACHE_MISSES.get() == misses0, "warm rep rebuilt"
            assert m.TPU_DEVICE_DISPATCHES.get() - disp0 == 1
            assert m.TQL_TILE_DISPATCHES.get() - tql0 == 1
        # the resident planes are the SAME device buffers (zero uploads)
        entry2 = next(iter(db.query_engine.tile_cache._super.values()))
        for name, ids in plane_ids.items():
            assert [id(c) for c in entry2.cols[name]] == ids
        # sliding the window re-hits the compile cache (same shape bucket)
        from greptimedb_tpu.query.promql import tile_exec

        progs0 = len(tile_exec._PROGRAMS)
        db.sql_one("TQL EVAL (90, 570, '30s') rate(tq[2m])")
        assert len(tile_exec._PROGRAMS) == progs0
    finally:
        db.close()


def test_prewarm_of_a_one_field_table_makes_the_first_query_warm():
    """A metric table has one field, so one family of planes: `prewarm`
    uploads them (a wider table's ride its families' background builds),
    and the table's FIRST TQL query dispatches instead of being served
    cold from the legacy scan."""
    db = _db()
    try:
        _load_counter(db, np.random.default_rng(7))
        db.prewarm(tables=["tq"])
        entry = next(iter(db.query_engine.tile_cache._super.values()))
        assert {"host", "ts", "greptime_value"} <= set(entry.cols)
        d0, c0 = m.TQL_TILE_DISPATCHES.get(), m.TQL_TILE_COLD_SERVES.get()
        q = "TQL EVAL (60, 540, '30s') rate(tq[2m])"
        first = db.sql_one(q)
        assert m.TQL_TILE_DISPATCHES.get() == d0 + 1
        assert m.TQL_TILE_COLD_SERVES.get() == c0
        _assert_rows_close(_rows(first), _rows(_legacy(db, q)))
    finally:
        db.close()


def test_cold_serves_legacy_and_schedules_build():
    db = _db()
    try:
        _load_counter(db, np.random.default_rng(5))
        q = "TQL EVAL (60, 540, '30s') rate(tq[2m])"
        d0 = m.TQL_TILE_DISPATCHES.get()
        c0 = m.TQL_TILE_COLD_SERVES.get()
        cold = db.sql_one(q)
        assert cold.num_rows > 0
        assert m.TQL_TILE_DISPATCHES.get() == d0, "cold must not dispatch"
        assert m.TQL_TILE_COLD_SERVES.get() == c0 + 1
        _drain_fused(db)
        d1 = m.TQL_TILE_DISPATCHES.get()
        warm = db.sql_one(q)
        assert m.TQL_TILE_DISPATCHES.get() == d1 + 1
        assert _rows(warm) and len(_rows(warm)) == len(_rows(cold))
    finally:
        db.close()


@pytest.mark.parametrize(
    "func", ["rate", "increase", "count_over_time", "last_over_time", "sum_over_time"]
)
def test_legacy_scan_with_host_made_bounds_equals_the_tile_path(func, monkeypatch):
    """The legacy scan (`engine._range_from_samples`) hands the window
    kernel run bounds made on the host from its dense (sid, ts)-sorted
    samples, the tile path reads them off the series table: over the same
    samples (counters without resets, where the strip cannot differ, and a
    matcher that drops a series between the others) what is found by
    position answers bit for bit.  `sum_over_time` adds by segment, over
    the padded plane in another order than over the dense samples (the
    module docstring's ulp-level exception): within 1e-12."""
    from greptimedb_tpu.ops import rate as rate_ops

    made = []

    def spy(series, num_series):
        made.append(num_series)
        return real(series, num_series)

    real = rate_ops.sorted_run_bounds
    monkeypatch.setattr(rate_ops, "sorted_run_bounds", spy)
    db = _db()
    try:
        _load_counter(db, np.random.default_rng(41), hosts=5, ticks=40, resets=False)
        q = f"TQL EVAL (60, 540, '30s') {func}(tq{{host!='h2'}}[2m])"
        _warm(db, q)
        d0, b0 = m.TQL_TILE_DISPATCHES.get(), m.TQL_TILE_RUN_BOUNDS.get()
        made.clear()
        w = db.sql_one(q)
        assert m.TQL_TILE_DISPATCHES.get() == d0 + 1
        assert m.TQL_TILE_RUN_BOUNDS.get() == b0 + 1 and made == []
        l = _legacy(db, q)
        assert made == [4]  # the legacy scan's four series
        assert m.TQL_TILE_DISPATCHES.get() == d0 + 1
        assert len(_rows(w)) > 0
        if func == "sum_over_time":
            _assert_rows_close(_rows(w), _rows(l))
        else:
            assert _rows(w) == _rows(l)
    finally:
        db.close()


def test_tile_off_is_legacy_bit_for_bit():
    db = _db(tile=False)
    try:
        _load_counter(db, np.random.default_rng(7))
        q = "TQL EVAL (60, 540, '30s') rate(tq[2m])"
        d0 = m.TQL_TILE_DISPATCHES.get()
        c0 = m.TQL_TILE_COLD_SERVES.get()
        a = db.sql_one(q)
        b = db.sql_one(q)
        # the tile engine never engages: no dispatches, no cold serves,
        # no background builds scheduled
        assert m.TQL_TILE_DISPATCHES.get() == d0
        assert m.TQL_TILE_COLD_SERVES.get() == c0
        te = db.query_engine._tile_executor
        with te._fused_lock:
            assert not te._fused_builds and not te._fused_queue
        assert _rows(a) == _rows(b)
    finally:
        db.close()


def test_fault_tql_tile_degrades_to_legacy():
    """Fault point `tql.tile`: an injected tile failure never fails (or
    changes) the query — it degrades to the legacy path and counts."""
    db = _db()
    try:
        _load_counter(db, np.random.default_rng(9))
        q = "TQL EVAL (60, 540, '30s') avg_over_time(tq[2m])"
        want = _rows(_warm(db, q))
        deg0 = m.TQL_TILE_DEGRADED.get()
        fi.REGISTRY.arm("tql.tile", fail_times=1, error=RuntimeError)
        got = db.sql_one(q)
        _assert_rows_close(_rows(got), want, "degraded != tile")
        assert m.TQL_TILE_DEGRADED.get() == deg0 + 1
        # healed: next query takes the tile path again
        d0 = m.TQL_TILE_DISPATCHES.get()
        assert _rows(db.sql_one(q)) == want
        assert m.TQL_TILE_DISPATCHES.get() == d0 + 1
    finally:
        db.close()


def test_memtable_rows_route_to_legacy():
    """Unflushed rows inside the fetch window: the tile path must bail
    (planes cover flushed files only) and results must include them."""
    db = _db()
    try:
        _load_counter(db, np.random.default_rng(13), hosts=2, ticks=30)
        q = "TQL EVAL (60, 540, '30s') sum_over_time(tq[2m])"
        _warm(db, q)
        db.sql("INSERT INTO tq VALUES ('h0', 123.5, 301000)")
        d0 = m.TQL_TILE_DISPATCHES.get()
        got = db.sql_one(q)
        assert m.TQL_TILE_DISPATCHES.get() == d0, "memtable rows must bail"
        _assert_rows_close(_rows(got), _rows(_legacy(db, q)))
        # after flush the delta lands in the planes and the path re-warms
        db.sql("ADMIN flush_table('tq')")
        _warm(db, q)
        d1 = m.TQL_TILE_DISPATCHES.get()
        warm = db.sql_one(q)
        assert m.TQL_TILE_DISPATCHES.get() == d1 + 1
        _assert_rows_close(_rows(warm), _rows(_legacy(db, q)))
    finally:
        db.close()


def test_label_churn_repair():
    """New hosts between flushes grow the dictionary (codes shift):
    plane repair must keep warm tile results identical to legacy."""
    db = _db()
    try:
        rng = np.random.default_rng(17)
        _load_counter(db, rng, hosts=3, ticks=24)
        q = "TQL EVAL (60, 540, '30s') sum by (host) (avg_over_time(tq[2m]))"
        _warm(db, q)
        # 'aa' sorts BEFORE h0..h2: every existing code shifts by one
        db.sql(
            "INSERT INTO tq VALUES " + ",".join(
                f"('aa', {rng.uniform(0, 9):.4f}, {t * 15000})"
                for t in range(24)
            )
        )
        db.sql("ADMIN flush_table('tq')")
        w = _warm(db, q)
        _assert_rows_close(_rows(w), _rows(_legacy(db, q)))
        assert {r[0] for r in _rows(w)} == {"aa", "h0", "h1", "h2"}
    finally:
        db.close()


# ---- mesh ------------------------------------------------------------------


def test_mesh_1_vs_n_bit_identical():
    """Hash-partitioned multi-region table: results under
    tile.mesh_devices in {0, 1, 4} are byte-identical (regions are
    series-disjoint, the stats merge is selection)."""
    db = _db()
    try:
        db.sql(
            "CREATE TABLE mq (host STRING, greptime_value DOUBLE, "
            "ts TIMESTAMP(3) TIME INDEX, PRIMARY KEY (host)) "
            "PARTITION BY HASH (host) PARTITIONS 3"
        )
        rng = np.random.default_rng(29)
        rows = []
        for h in range(6):
            v = 0.0
            for t in range(30):
                v += rng.uniform(0, 4)
                rows.append(f"('h{h}', {v:.5f}, {t * 15000})")
        db.sql("INSERT INTO mq VALUES " + ",".join(rows))
        db.sql("ADMIN flush_table('mq')")
        queries = [
            "TQL EVAL (60, 420, '30s') rate(mq[2m])",
            "TQL EVAL (60, 420, '30s') sum by (host) (rate(mq[2m]))",
            "TQL EVAL (60, 420, '30s') max(avg_over_time(mq[2m]))",
        ]
        for q in queries:
            _warm(db, q)
        base = {}
        for q in queries:
            base[q] = _rows(db.sql_one(q))
            # legacy agreement (order-insensitive on multi-region; float
            # sums may differ in the last ulp — see module docstring)
            lr = _rows(_legacy(db, q))
            assert len(base[q]) == len(lr)
            for a, b in zip(sorted(base[q]), sorted(lr)):
                assert a[:-1] == b[:-1]
                np.testing.assert_allclose(a[-1], b[-1], rtol=1e-12)
        for n in (1, 4):
            db.config.tile.mesh_devices = n
            try:
                for q in queries:
                    md0 = m.TILE_MESH_DISPATCHES.get()
                    got = _rows(db.sql_one(q))
                    assert got == base[q], f"mesh={n} diverged: {q}"
                    if n > 1:
                        assert m.TILE_MESH_DISPATCHES.get() > md0
            finally:
                db.config.tile.mesh_devices = 0
    finally:
        db.close()


# ---- kernels: large-int64 timestamps ---------------------------------------


def test_range_windows_ns_scale_timestamps():
    """Seeded ns-scale int64 timestamps through range_windows /
    extrapolated_rate (the utils/jax_env.py OverflowError note): x64
    must hold end to end — results match a from-scratch numpy replay."""
    import jax.numpy as jnp

    from greptimedb_tpu.ops.rate import (
        RangeSpec,
        extrapolated_rate,
        extrapolated_rate_dyn,
        range_windows,
        range_windows_dyn,
        sorted_run_bounds,
    )

    rng = np.random.default_rng(41)
    base = 1_700_000_000_000_000_000 // 1_000_000  # ns epoch in ms scale
    n_series, n_samples = 3, 60
    sid = np.repeat(np.arange(n_series, dtype=np.int32), n_samples)
    ts = np.tile(base + np.arange(n_samples, dtype=np.int64) * 15_000, n_series)
    vals = np.cumsum(rng.uniform(0, 5, n_series * n_samples))
    spec = RangeSpec(
        start=base + 120_000, end=base + 600_000, step=30_000, range_=120_000
    )
    valid = jnp.ones(len(vals), bool)
    bounds = sorted_run_bounds(sid, n_series)
    stats = range_windows(
        jnp.asarray(sid), jnp.asarray(ts), jnp.asarray(vals), valid, spec, bounds,
    )
    rate_v, defined = extrapolated_rate(stats, spec, "rate")
    rate_v = np.asarray(rate_v)
    defined = np.asarray(defined)
    assert defined.any()
    # timestamps must survive exactly (no f32/i32 truncation)
    first_ts = np.asarray(stats.first_ts).reshape(n_series, -1)
    assert first_ts[defined.reshape(n_series, -1)].min() >= base
    # numpy replay of one defined window
    w = spec.num_steps - 1
    t1 = spec.start + w * spec.step
    mask = (sid == 0) & (ts > t1 - spec.range_) & (ts <= t1)
    wts, wv = ts[mask], vals[mask]
    si = float(wts[-1] - wts[0])
    avg = si / (len(wts) - 1)
    d_s, d_e = float(wts[0] - (t1 - spec.range_)), float(t1 - wts[-1])
    ext_s = d_s if d_s < avg * 1.1 else avg / 2
    ext_e = d_e if d_e < avg * 1.1 else avg / 2
    want = (wv[-1] - wv[0]) * ((si + ext_s + ext_e) / si) / (
        spec.range_ / 1000.0
    )
    got = rate_v.reshape(n_series, -1)[0, w]
    np.testing.assert_allclose(got, want, rtol=1e-12)
    # the dynamic-spec form (the tile program's path) is bit-identical
    stats_d, _present = range_windows_dyn(
        jnp.asarray(sid), jnp.asarray(ts), jnp.asarray(vals), valid, bounds,
        start=np.int64(spec.start), step=np.int64(spec.step),
        range_=np.int64(spec.range_), n_steps=spec.num_steps,
        k=spec.windows_per_sample,
    )
    rate_d, defined_d = extrapolated_rate_dyn(
        stats_d, np.int64(spec.start), np.int64(spec.step),
        np.int64(spec.range_), spec.num_steps, "rate",
    )
    assert np.array_equal(np.asarray(defined_d), defined)
    assert np.array_equal(
        np.asarray(rate_d)[defined], rate_v[defined]
    )


def test_strip_segmented_matches_dense():
    """strip_counter_resets_segmented on a padded array with interspersed
    invalid rows == strip_counter_resets on the compacted dense array
    (same scan length => bit-identical is not required across lengths,
    so compare at matching length with zero-padding only)."""
    import jax.numpy as jnp

    from greptimedb_tpu.ops.rate import (
        strip_counter_resets,
        strip_counter_resets_segmented,
    )

    rng = np.random.default_rng(43)
    sid = np.sort(rng.integers(0, 5, 200).astype(np.int32))
    vals = rng.uniform(0, 100, 200)
    valid = rng.random(200) < 0.8
    seg = np.asarray(strip_counter_resets_segmented(
        jnp.asarray(sid), jnp.asarray(vals), jnp.asarray(valid)
    ))
    # reference: python replay per series over valid rows
    want = vals.copy()
    for s in np.unique(sid):
        idxs = np.nonzero((sid == s) & valid)[0]
        acc = 0.0
        prev = None
        for i in idxs:
            if prev is not None and vals[i] < prev:
                acc += prev
            prev = vals[i]
            want[i] = vals[i] + acc
    np.testing.assert_allclose(seg[valid], want[valid], rtol=1e-12)


# ---- spans -----------------------------------------------------------------


def test_tql_tile_spans_dispatch_and_build():
    """TQL rides the tile span taxonomy: a warm query emits ONE
    `tile.dispatch` span with strategy=tql, and the cold build emitted
    `tile.build` spans — the same stable stage names the SQL path uses
    (asserted against the README block by the conftest taxonomy gate)."""
    from greptimedb_tpu.utils.tracing import EXPORTER

    db = _db()
    try:
        _load_counter(db, np.random.default_rng(19), hosts=2, ticks=24)
        q = "TQL EVAL (60, 300, '30s') rate(tq[2m])"
        EXPORTER.drain()
        _warm(db, q)
        names = [s.name for s in EXPORTER.drain()]
        assert "tile.build" in names
        EXPORTER.drain()
        db.sql_one(q)
        spans = [s for s in EXPORTER.drain() if s.name == "tile.dispatch"]
        assert len(spans) == 1
        assert spans[0].attributes.get("strategy") == "tql"
        assert spans[0].attributes.get("func") == "rate"
    finally:
        db.close()


# ---- SQL scalar rate -------------------------------------------------------


def test_sql_scalar_rate_delta_over_elapsed():
    db = _db()
    try:
        db.sql(
            "CREATE TABLE r (host STRING, ts TIMESTAMP(3) TIME INDEX, "
            "v DOUBLE, PRIMARY KEY (host))"
        )
        db.sql(
            "INSERT INTO r VALUES ('a', 0, 10.0), ('a', 2000, 14.0), "
            "('a', 3000, 20.0)"
        )
        t = db.sql_one("SELECT ts, rate(v, ts) AS r FROM r")
        got = t["r"].to_pylist()
        # per-row delta / elapsed ms (reference RateFunction: raw deltas
        # in the ts argument's own unit): first row NULL
        assert got[0] is None
        np.testing.assert_allclose(got[1], 4.0 / 2000.0)
        np.testing.assert_allclose(got[2], 6.0 / 1000.0)
        from greptimedb_tpu.utils.errors import PlanError

        with pytest.raises(PlanError):
            db.sql_one("SELECT rate(v) FROM r")
        # non-advancing time -> NULL, never a divide (append_mode keeps
        # the duplicate-ts row the LWW table would collapse)
        db.sql(
            "CREATE TABLE r2 (host STRING, ts TIMESTAMP(3) TIME INDEX, "
            "v DOUBLE, PRIMARY KEY (host)) WITH (append_mode = 'true')"
        )
        db.sql(
            "INSERT INTO r2 VALUES ('a', 0, 1.0), ('a', 1000, 3.0), "
            "('a', 1000, 9.0), ('a', 2000, 10.0)"
        )
        t2 = db.sql_one("SELECT ts, rate(v, ts) AS r FROM r2")
        got2 = t2["r"].to_pylist()
        assert got2[0] is None
        np.testing.assert_allclose(got2[1], 2.0 / 1000.0)
        assert got2[2] is None  # dt == 0
        np.testing.assert_allclose(got2[3], 1.0 / 1000.0)
    finally:
        db.close()


@pytest.mark.parametrize("range_ms", [2646, 2647])
def test_a_grid_past_31_bits_reads_64_bit_timestamps(range_ms):
    """`TQL_TILE_WIDE_GRIDS` counts a dispatch whose grid, (padded steps
    - 1) x step + range, spans 2^31 - 1 ms or more: there the window
    kernel searches and reads 64-bit timestamps, below it 32-bit keys
    (the `span_at_the_32_bit_stop` grid of `tests/test_rate_windows.py`,
    samples on and beside every window's edges).  Both answer as the
    legacy scan; the grids of `prom-1m-series-rules` (a rule's instant
    and a 6-step panel of rate[5m]) never move it."""
    step = 715_827_000
    start = 1_700_000_000_000
    edges = [start + w * step for w in range(4)]
    ts = sorted({
        t + d for t in edges for d in (-range_ms - 1, -range_ms, -range_ms + 1, -1, 0, 1)
    })
    db = _db()
    try:
        db.sql(
            "CREATE TABLE tq (host STRING, greptime_value DOUBLE,"
            " ts TIMESTAMP(3) TIME INDEX, PRIMARY KEY (host))"
        )
        db.sql("INSERT INTO tq VALUES " + ",".join(
            f"('h{h}', {float(i * (h + 1))}, {t})" for h in range(3) for i, t in enumerate(ts)
        ))
        db.sql("ADMIN flush_table('tq')")
        end_s = (edges[-1]) // 1000
        q = (f"TQL EVAL ({start // 1000}, {end_s}, '{step // 1000}s') "
             f"rate(tq[{range_ms}ms])")
        _warm(db, q)
        d0, w0 = m.TQL_TILE_DISPATCHES.get(), m.TQL_TILE_WIDE_GRIDS.get()
        got = db.sql_one(q)
        assert m.TQL_TILE_DISPATCHES.get() == d0 + 1
        assert m.TQL_TILE_WIDE_GRIDS.get() == w0 + (range_ms == 2647)
        assert len(_rows(got)) == 12
        _assert_rows_close(_rows(got), _rows(_legacy(db, q)))

        t = start // 1000 + 300
        for grid in (f"({t}, {t}, '1m')", f"({t}, {t + 300}, '60s')"):
            cell = f"TQL EVAL {grid} rate(tq[5m])"
            _warm(db, cell)
            d0, w0 = m.TQL_TILE_DISPATCHES.get(), m.TQL_TILE_WIDE_GRIDS.get()
            db.sql_one(cell)
            assert m.TQL_TILE_DISPATCHES.get() == d0 + 1
            assert m.TQL_TILE_WIDE_GRIDS.get() == w0
    finally:
        db.close()
