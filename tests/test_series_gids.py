"""A primary key of all ten TSBS tags (as InfluxDB line protocol writes
the table) on the SQL tile path: stage 1 groups by the sorted rows' series
(`DistGroupByPlan.series_slots`) and folds to the requested groups on the
device.  Each shape is checked against a numpy fold of the generated rows
and against the CPU executor; the data holds what TSBS lacks: a host whose
`service_version` changes mid-span (one host, two series, a group folding
both) and a series with a NULL tag.
"""

import time

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from greptimedb_tpu.database import Database
from greptimedb_tpu.utils import metrics

TAGS = (
    "hostname", "region", "datacenter", "rack", "os", "arch", "team",
    "service", "service_version", "service_environment",
)
FIELDS = (
    "usage_user", "usage_system", "usage_idle", "usage_nice", "usage_iowait",
    "usage_irq", "usage_softirq", "usage_steal", "usage_guest", "usage_guest_nice",
)
HOSTS, TICKS, STEP_MS, HOUR_MS = 64, 720, 10_000, 3_600_000
T0 = 1_767_225_600_000
SPLIT_HOST, SPLIT_TICK, NULL_HOST = 5, 333, 9
# the configuration's bars: an avg shipped as float32 past 2^14 groups, and f64
RTOL_AVG, RTOL_F64 = 1.2e-7, 1e-9


def _data():
    rng = np.random.default_rng(42)
    domains = {
        "region": [f"region-{i}" for i in range(9)], "datacenter": [f"dc-{i}" for i in range(27)],
        "rack": [str(i) for i in range(100)], "os": ["Ubuntu15.10", "Ubuntu16.04LTS", "Ubuntu16.10"],
        "arch": ["x64", "x86"], "team": ["CHI", "LON", "NYC", "SF"],
        "service": [str(i) for i in range(20)], "service_version": ["0", "1"],
        "service_environment": ["production", "staging", "test"],
    }
    per_host = {"hostname": [f"host_{i}" for i in range(HOSTS)]}
    for tag, values in domains.items():
        per_host[tag] = [values[k] for k in rng.integers(0, len(values), HOSTS)]
    per_host["team"][NULL_HOST] = None
    per_host["service_version"][SPLIT_HOST] = "0"
    cols = {tag: np.array(per_host[tag] * TICKS, dtype=object) for tag in TAGS}
    tick = np.repeat(np.arange(TICKS), HOSTS)
    host = np.tile(np.arange(HOSTS), TICKS)
    cols["service_version"][(host == SPLIT_HOST) & (tick >= SPLIT_TICK)] = "1"
    cols["ts"] = T0 + tick.astype(np.int64) * STEP_MS
    for f in FIELDS:
        cols[f] = rng.uniform(0.0, 100.0, HOSTS * TICKS)
    return cols


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    cols = _data()
    db = Database(data_home=str(tmp_path_factory.mktemp("tags10") / "db"))
    db.config.query.fallback_to_cpu = False
    db.sql(
        "CREATE TABLE cpu (" + ", ".join(f"{t} STRING" for t in TAGS)
        + ", ts TIMESTAMP(3) TIME INDEX, " + ", ".join(f"{f} DOUBLE" for f in FIELDS)
        + f", PRIMARY KEY ({', '.join(TAGS)})) WITH (append_mode = 'true')"
    )
    table = {t: pa.array(list(cols[t]), pa.string()) for t in TAGS}
    table["ts"] = pa.array(cols["ts"], pa.timestamp("ms"))
    table.update({f: pa.array(cols[f]) for f in FIELDS})
    db.insert_rows("cpu", pa.table(table))
    db.storage.flush_all()
    db.prewarm(tables=["cpu"])
    yield db, cols
    db.close()


def _codes(values):
    """Dictionary-code order: values sorted as strings, NULL last."""
    uniq = sorted({v for v in values if v is not None}) + [None]
    rank = {v: i for i, v in enumerate(uniq)}
    return np.array([rank[v] for v in values], np.int64)


def reference(cols, tags, bucket, aggs, lo=T0, hi=T0 + TICKS * STEP_MS):
    """Rows of the answer in the engine's order: a numpy fold over the
    generated rows, grouped by `tags` (+ the hour when `bucket`)."""
    sel = (cols["ts"] >= lo) & (cols["ts"] < hi)
    keys = [_codes(cols[t])[sel] for t in tags]
    if bucket:
        keys.append(cols["ts"][sel] // HOUR_MS)
    uniq, inv = np.unique(np.stack(keys, axis=1), axis=0, return_inverse=True)
    inv = inv.reshape(-1)
    first = np.zeros(len(uniq), np.int64)
    first[inv[::-1]] = np.flatnonzero(sel)[::-1]
    out = [cols[t][first].tolist() for t in tags]
    if bucket:
        out.append((uniq[:, -1] * HOUR_MS).tolist())
    count = np.bincount(inv, minlength=len(uniq))
    for func, col in aggs:
        v = cols[col][sel]
        if func == "avg":
            out.append(np.bincount(inv, v, len(uniq)) / count)
        elif func == "count":
            out.append(count.astype(np.float64))
        elif func in ("min", "max"):
            acc = np.full(len(uniq), np.inf if func == "min" else -np.inf)
            (np.minimum if func == "min" else np.maximum).at(acc, inv, v)
            out.append(acc)
        else:  # last_value: the row of the newest ts
            ts = cols["ts"][sel]
            best = np.full(len(uniq), -1, np.int64)
            np.maximum.at(best, inv, ts)
            last = np.zeros(len(uniq))
            hit = ts == best[inv]
            last[inv[hit]] = v[hit]
            out.append(last)
    return out


def _sql(tags, bucket, aggs):
    sel = list(tags) + (["time_bucket('1h', ts) AS tb"] if bucket else [])
    sel += [f"{f}({c}) AS {f}_{c}" for f, c in aggs]
    group = list(tags) + (["tb"] if bucket else [])
    return f"SELECT {', '.join(sel)} FROM cpu GROUP BY {', '.join(group)}"


def _columns(table):
    out = []
    for name in table.column_names:
        col = table[name]
        if pa.types.is_timestamp(col.type):
            col = pc.cast(col, pa.int64())
        out.append(col.to_pylist())
    return out


def _wait_builds(db, timeout_s=120.0):
    te = db.query_engine._tile_executor
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        with te._fused_lock:
            if not te._fused_builds and not te._fused_queue:
                return
        time.sleep(0.05)
    raise AssertionError("background plane build did not finish")


COUNTERS = ("TILE_SERIES_GIDS", "TPU_DEVICE_DISPATCHES", "TPU_LOWERED_TOTAL", "TILE_DECLINED",
            "TILE_STAGE1_SERIES", "TPU_FALLBACK_TOTAL")


def _counters():
    return {k: getattr(metrics, k).total() for k in COUNTERS}


def _warm(db, sql):
    for _ in range(3):
        db.sql_one(sql)
        _wait_builds(db)


# (group tags, bucketed, aggregates, stage 1 by series)
SHAPES = {
    "double-groupby-1": (("hostname",), True, (("avg", "usage_user"),), True),
    "double-groupby-all": (("hostname",), True, tuple(("avg", f) for f in FIELDS), True),
    "region-hour": (("region",), True, (("avg", "usage_user"),), True),
    "region-service-hour": (("region", "service"), True, (("avg", "usage_system"),), True),
    # all ten: a sparse product of cardinalities, which the hash strategy takes
    "all-ten-hour": (TAGS, True, (("avg", "usage_user"),), False),
    "lastpoint": (("hostname",), False, (("last_value", "usage_user"),), False),
    "max-min-count": (
        ("team", "region"), True,
        (("max", "usage_user"), ("min", "usage_idle"), ("count", "usage_nice")), True,
    ),
    "service-unbucketed": (("service",), False, (("max", "usage_steal"), ("avg", "usage_irq")), True),
}


@pytest.mark.parametrize("shape", list(SHAPES))
def test_ten_tag_key_answers_on_the_tile_path_as_the_reference(fleet, shape):
    db, cols = fleet
    tags, bucket, aggs, by_series = SHAPES[shape]
    sql = _sql(tags, bucket, aggs)
    _warm(db, sql)
    before = _counters()
    got = db.sql_one(sql)
    moved = {k: v - before[k] for k, v in _counters().items()}
    # one dispatch a request, and never the legacy scan
    assert moved["TPU_DEVICE_DISPATCHES"] == 1, moved
    assert moved["TPU_LOWERED_TOTAL"] == 0 and moved["TILE_DECLINED"] == 0, moved
    assert moved["TPU_FALLBACK_TOTAL"] == 0, moved
    assert moved["TILE_SERIES_GIDS"] == (1 if by_series else 0), moved
    # 64 hosts, the split one in two series of the whole key; the key's
    # first eight tags (the unbucketed `service`'s series) hold 64
    series = (HOSTS + 1 if bucket else HOSTS) if by_series else 0
    assert moved["TILE_STAGE1_SERIES"] == series, moved
    want = reference(cols, tags, bucket, aggs)
    have = _columns(got)
    n_keys = len(tags) + bool(bucket)
    assert len(have) == len(want) and len(have[0]) == len(want[0])
    for h, w in zip(have[:n_keys], want[:n_keys]):
        assert h == w  # keys and row order exact
    for (func, _c), h, w in zip(aggs, have[n_keys:], want[n_keys:]):
        rtol = RTOL_AVG if func == "avg" else RTOL_F64
        np.testing.assert_allclose(np.asarray(h, np.float64), w, rtol=rtol)
    # and the CPU executor agrees, group for group
    db.config.query.backend = "cpu"
    try:
        cpu = db.sql_one(sql)
    finally:
        db.config.query.backend = "tpu"
    keys = [(name, "ascending") for name in got.column_names[:n_keys]]
    for h, c in zip(_columns(got.sort_by(keys)), _columns(cpu.sort_by(keys))):
        if isinstance(c[0], float):
            np.testing.assert_allclose(h, c, rtol=RTOL_AVG)
        else:
            assert h == c


def test_a_group_of_two_series_folds_both(fleet):
    """The split host is one group of double-groupby-1: its hour across the
    split averages the rows of both series."""
    db, cols = fleet
    sql = _sql(("hostname",), True, (("avg", "usage_user"),))
    got = db.sql_one(sql).to_pydict()
    split_hour = (T0 + SPLIT_TICK * STEP_MS) // HOUR_MS * HOUR_MS
    host = f"host_{SPLIT_HOST}"
    rows = [i for i, h in enumerate(got["hostname"]) if h == host]
    at = [i for i in rows if got["tb"][i].timestamp() * 1000 == split_hour]
    assert len(rows) == 2 and len(at) == 1
    sel = (cols["hostname"] == host) & (cols["ts"] // HOUR_MS * HOUR_MS == split_hour)
    assert len(set(cols["service_version"][sel])) == 2
    np.testing.assert_allclose(got["avg_usage_user"][at[0]], cols["usage_user"][sel].mean(), rtol=RTOL_AVG)


@pytest.mark.parametrize("sql,reason", [
    # LAST states of several series cannot fold into one group
    ("SELECT region, last_value(usage_user) AS l FROM cpu GROUP BY region",
     "last_value over groups of several series"),
    # the direct plan over all ten tags' codes: 2^35 x buckets groups
    (_sql(TAGS, True, (("max", "usage_user"),)), "max_groups"),
])
def test_a_declined_statement_fails_with_its_reason_without_legacy_fallback(fleet, sql, reason):
    db, _cols = fleet
    db.config.query.agg_strategy = "sort"
    try:
        db.config.query.legacy_fallback = False
        before = _counters()
        with pytest.raises(Exception, match=reason):
            db.sql_one(sql)
        moved = {k: v - before[k] for k, v in _counters().items()}
        assert moved["TILE_DECLINED"] == 1 and moved["TPU_LOWERED_TOTAL"] == 0, moved
    finally:
        db.config.query.legacy_fallback = True
        db.config.query.agg_strategy = "auto"


def test_a_window_tile_holds_the_series_of_its_rows(tmp_path, monkeypatch):
    """Over a window tile (the window's rows gathered off the sorted planes)
    a series opens where its first row in the window lies; the answer is the
    reference's, the split host's two series included."""
    from greptimedb_tpu.parallel.tile_cache import TileCacheManager

    monkeypatch.setattr(TileCacheManager, "_WINDOW_TILE_MIN_ROWS", 0)
    cols = _data()
    db = Database(data_home=str(tmp_path / "db"))
    db.config.query.fallback_to_cpu = False
    try:
        db.sql(
            "CREATE TABLE cpu (" + ", ".join(f"{t} STRING" for t in TAGS)
            + ", ts TIMESTAMP(3) TIME INDEX, usage_user DOUBLE"
            + f", PRIMARY KEY ({', '.join(TAGS)})) WITH (append_mode = 'true')"
        )
        table = {t: pa.array(list(cols[t]), pa.string()) for t in TAGS}
        table["ts"] = pa.array(cols["ts"], pa.timestamp("ms"))
        table["usage_user"] = pa.array(cols["usage_user"])
        db.insert_rows("cpu", pa.table(table))
        db.storage.flush_all()
        # 40 minutes across the split: a quarter of the rows
        lo = T0 + (SPLIT_TICK - 120) * STEP_MS
        hi = lo + 240 * STEP_MS
        sql = (
            "SELECT hostname, time_bucket('1h', ts) AS tb, avg(usage_user) AS a FROM cpu "
            f"WHERE ts >= {lo} AND ts < {hi} GROUP BY hostname, tb"
        )
        builds = metrics.TILE_WINDOW_BUILDS.total()
        _warm(db, sql)
        assert metrics.TILE_WINDOW_BUILDS.total() > builds
        before = _counters()
        got = db.sql_one(sql)
        moved = {k: v - before[k] for k, v in _counters().items()}
        assert moved["TILE_SERIES_GIDS"] == moved["TPU_DEVICE_DISPATCHES"] == 1, moved
        want = reference(cols, ("hostname",), True, (("avg", "usage_user"),), lo, hi)
        have = _columns(got)
        assert have[:2] == [list(w) for w in want[:2]]
        np.testing.assert_allclose(have[2], want[2], rtol=RTOL_AVG)
    finally:
        db.close()


def test_a_decline_is_named_on_the_query_tpu_span(tmp_path):
    """With the legacy scan allowed, a declined statement is still answered,
    and the `query.tpu` span that tried the tile path names the check."""
    from greptimedb_tpu.utils import tracing
    from greptimedb_tpu.utils.config import Config

    cfg = Config()
    cfg.trace.enabled, cfg.trace.sample_ratio, cfg.trace.export_interval_s = True, 1.0, 60.0
    db = Database(cfg, data_home=str(tmp_path / "db"))
    try:
        db.sql("CREATE TABLE t (a STRING, b STRING, ts TIMESTAMP TIME INDEX, v DOUBLE, "
               "PRIMARY KEY (a, b))")
        db.sql("INSERT INTO t VALUES ('x','p',1000,1.0),('y','q',2000,2.0),('x','q',3000,3.0)")
        db.storage.flush_all()
        before = _counters()
        got = db.sql_one("SELECT b, last_value(v) AS l FROM t GROUP BY b")
        assert got.to_pydict() == {"b": ["p", "q"], "l": [1.0, 3.0]}
        moved = {k: v - before[k] for k, v in _counters().items()}
        assert moved["TILE_DECLINED"] == 1 and moved["TPU_LOWERED_TOTAL"] == 1, moved
        declined = [
            s.attributes["declined"] for s in tracing.EXPORTER.spans()
            if s.name == "query.tpu" and "declined" in s.attributes
        ]
        assert declined[-1] == "last_value over groups of several series"
    finally:
        db.close()


def test_a_failed_limb_verdict_is_answered_exactly(fleet):
    """A window whose last hour holds one row a host: the limb sums'
    verdict fails and the exact-f64 program answers, its first dispatch
    outside the device supervisor (compiling), the next inside it."""
    db, cols = fleet
    lo, hi = T0 + 1, T0 + HOUR_MS + 1
    aggs = tuple(("avg", f) for f in FIELDS)
    sql = _sql(("hostname",), True, aggs).replace(
        "FROM cpu", f"FROM cpu WHERE ts >= {lo} AND ts < {hi}"
    )
    _warm(db, sql)
    reruns = metrics.TILE_LIMB_RERUNS.total()
    before = _counters()
    got = db.sql_one(sql)
    moved = {k: v - before[k] for k, v in _counters().items()}
    assert metrics.TILE_LIMB_RERUNS.total() == reruns + 1
    assert moved["TPU_DEVICE_DISPATCHES"] == 2, moved  # the limb one, then the f64 one
    want = reference(cols, ("hostname",), True, aggs, lo, hi)
    have = _columns(got)
    assert have[:2] == [list(w) for w in want[:2]]
    for h, w in zip(have[2:], want[2:]):
        np.testing.assert_allclose(np.asarray(h, np.float64), w, rtol=RTOL_F64)
