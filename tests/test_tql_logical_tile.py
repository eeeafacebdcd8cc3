"""TQL over metric-engine logical tables on the device tile path.

A logical table is a row range of its physical region's planes
(`__table_id` leads the key), its series the runs of `__tsid` inside that
range, its labels a per-series table made once per plane build.  Contracts:
  * tile path = legacy path = the plain reference (`benchmark/promql_ref.py`,
    Prometheus' extrapolatedRate over the generator's own samples), for
    rate / increase, matchers on any label, and fused by-label folds;
  * the answer's series in label order on both paths;
  * another logical table's rows never enter; the planes are built once;
  * what makes a mito table ineligible makes a logical table ineligible;
  * `tql.legacy_fallback = false` fails where the legacy scan would answer;
  * `write_logical` hashes a tsid per distinct label set, bit-equal to
    `tsid_hash` row by row.
"""

import os
import sys
import tempfile
import time
import types

import numpy as np
import pyarrow as pa
import pytest

from greptimedb_tpu.utils import metrics as m

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark import promql_ref  # noqa: E402

T0 = 1_767_225_600_000
SCRAPE_S = 10
LABELS = sorted([
    "hostname", "region", "datacenter", "rack", "os", "arch", "team", "service",
    "service_version", "service_environment", "port", "server",
])
METRICS = ("accepts", "handled", "requests")


def _label(name, h):
    if name == "hostname":
        return f"host_{h}"
    if name == "region":
        return f"region-{h % 3}"
    if name == "datacenter":
        return f"region-{h % 3}{'ab'[h % 2]}"
    if name == "rack":
        return str(h % 4)
    if name == "port":
        return str(8000 + (h * 7) % 5)
    return f"{name}-{(h * 5 + len(name)) % 3}"


class Fleet:
    """The ground truth: per metric a [ticks, hosts] array, per label the
    hosts' values; shaped like a benchmark dataset for `promql_ref`."""

    def __init__(self, hosts, ticks, seed=5, restart=None, restarts=()):
        self.hosts, self.ticks, self.scrape_s, self.t0 = hosts, ticks, SCRAPE_S, T0
        rng = np.random.default_rng(seed)
        self.samples = {}
        for metric in METRICS:
            state = np.cumsum(np.abs(rng.normal(5, 1, (ticks, hosts))), axis=0) + 1000.0
            for h, r in ([restart] if restart is not None else []) + list(restarts):
                state[r:, h] -= state[r, h]
            self.samples[metric] = np.floor(state)
        self.label_values = {
            l: np.array([_label(l, h) for h in range(hosts)]) for l in LABELS
        }

    def tick_ts(self):
        return self.t0 + np.arange(self.ticks, dtype=np.int64) * (self.scrape_s * 1000)

    def label_order(self, hosts):
        keys = [self.label_values[l][hosts] for l in reversed(LABELS)]
        return hosts[np.lexsort(keys)]

    def table(self, metric, ticks=slice(None), extra=None):
        idx = np.arange(self.ticks)[ticks]
        codes = np.tile(np.arange(self.hosts, dtype=np.int32), len(idx))
        cols = {
            l: pa.DictionaryArray.from_arrays(pa.array(codes), pa.array(list(v)))
            for l, v in self.label_values.items()
        }
        cols.update(extra or {})
        cols["greptime_timestamp"] = pa.array(
            np.repeat(self.tick_ts()[idx], self.hosts), pa.timestamp("ms")
        )
        cols["greptime_value"] = pa.array(self.samples[metric][idx].reshape(-1))
        return pa.table(cols)


def _db(**tql):
    from greptimedb_tpu.database import Database
    from greptimedb_tpu.utils.config import Config

    cfg = Config()
    cfg.storage.data_home = tempfile.mkdtemp()
    cfg.query.fallback_to_cpu = False
    for k, v in tql.items():
        setattr(cfg.tql, k, v)
    db = Database(config=cfg)
    db.sql(
        "CREATE TABLE phy (greptime_timestamp TIMESTAMP(3) TIME INDEX, "
        "greptime_value DOUBLE) WITH ('physical_metric_table' = '')"
    )
    cols = ", ".join(f"{l} STRING" for l in LABELS)
    for metric in METRICS:
        db.sql(
            f"CREATE TABLE nginx_{metric} (greptime_timestamp TIMESTAMP(3) TIME INDEX, "
            f"greptime_value DOUBLE, {cols}, PRIMARY KEY ({', '.join(LABELS)})) "
            "ENGINE = metric WITH ('on_physical_table' = 'phy')"
        )
    return db


def _load(db, fleet, ticks=slice(None)):
    for metric in METRICS:
        db.insert_rows(f"nginx_{metric}", fleet.table(metric, ticks))
    db.storage.flush_all()


def _drain(db, timeout=60.0):
    te = db.query_engine._tile_executor
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        with te._fused_lock:
            if not te._fused_builds and not te._fused_queue:
                return
        time.sleep(0.02)
    raise AssertionError("fused builder did not drain")


def _rows(t):
    return list(zip(*[t[c].to_pylist() for c in t.column_names]))


def _legacy(db, q):
    db.config.tql.tile = False
    try:
        return db.sql_one(q)
    finally:
        db.config.tql.tile = True


def _warm(db, q):
    db.sql_one(q)
    _drain(db)
    return db.sql_one(q)


def _close(got, want, rtol, msg=""):
    assert len(got) == len(want), (len(got), len(want), msg)
    for a, b in zip(got, want):
        assert a[:-1] == b[:-1], (a, b, msg)
        np.testing.assert_allclose(a[-1], b[-1], rtol=rtol, err_msg=msg)


def _tql(start_s, promql, span_s=1800, step_s=60):
    return f"TQL EVAL ({start_s}, {start_s + span_s}, '{step_s}s') {promql}"


def _reference(fleet, metric, hosts, start_ms, per_second, by=None, op="sum",
               span_s=1800, step_s=60, range_s=300):
    """Rows as TQL gives them, from `promql_ref.extrapolated`: the series
    (or the `by` label's groups) in label order, steps ascending."""
    import datetime

    steps = start_ms + np.arange(span_s // step_s + 1, dtype=np.int64) * step_s * 1000
    hosts = fleet.label_order(np.asarray(hosts))
    mat = promql_ref.extrapolated(
        fleet, fleet.samples[metric][:, hosts], steps, range_s * 1000, per_second
    )
    if by is None:
        keys = [tuple(fleet.label_values[l][h] for l in LABELS) for h in hosts]
    else:
        of = fleet.label_values[by][hosts]
        keys, folded = [], []
        for g in np.unique(of):
            rows = mat[of == g]
            with np.errstate(all="ignore"):
                val = np.nansum(rows, axis=0) if op == "sum" else np.nanmean(rows, axis=0)
            folded.append(np.where(np.isnan(rows).all(axis=0), np.nan, val))
            keys.append((g,))
        mat = np.array(folded)
    out = []
    for key, row in zip(keys, mat):
        for t, v in zip(steps, row):
            if not np.isnan(v):
                ts = datetime.datetime.fromtimestamp(t / 1000, datetime.UTC).replace(tzinfo=None)
                out.append((*key, ts, float(v)))
    return out


@pytest.fixture(scope="module")
def fleet_db():
    # host 3 restarts at tick 100: a window's edge for the starts below
    fleet = Fleet(hosts=24, ticks=360, restart=(3, 100))
    db = _db()
    _load(db, fleet)
    stats = db.prewarm(tables=[f"nginx_{x}" for x in METRICS])
    yield db, fleet, stats
    db.close()


@pytest.fixture(scope="module")
def wide_db():
    # 2^14 series of twelve labels in one logical table, 5 % of them
    # restarting at a drawn scrape; 12 minutes of 10 s scrapes
    rng = np.random.default_rng(14)
    hosts = 1 << 14
    restarts = [
        (int(h), int(rng.integers(1, 72)))
        for h in rng.choice(hosts, hosts // 20, replace=False)
    ]
    fleet = Fleet(hosts=hosts, ticks=72, restarts=restarts)
    db = _db()
    db.insert_rows("nginx_requests", fleet.table("requests"))
    db.storage.flush_all()
    stats = db.prewarm(tables=["nginx_requests"])
    yield db, fleet, stats
    db.close()


S0 = T0 // 1000 + 600  # the half hour from minute 10: every window whole
# where each fleet's requests start, their span and step, and the padded
# rows of the slice the program is handed: 24 hosts x 360 scrapes = 8,640
# rows a table, a 2^14-row slice of the region's 2^15-row planes; 2^14
# hosts x 72 scrapes, the region's one table, all of its 2^21-row planes
FLEETS = {"small": ("fleet_db", S0, 1800, 60, 1 << 14), "wide": ("wide_db", T0 // 1000 + 330, None, 60, 1 << 21)}

# (expression, metric, hosts, per second, by, op[, fleet, span s])
CASES = {
    "rate-all": ("rate(nginx_requests[5m])", "requests", range(24), True, None, "sum"),
    "increase-hostname": (
        'increase(nginx_handled{hostname="host_7"}[5m])', "handled", [7], False, None, "sum",
    ),
    "rate-rack": (
        'rate(nginx_requests{rack="2"}[5m])', "requests", range(2, 24, 4), True, None, "sum",
    ),
    "rate-regex-not": (
        'rate(nginx_accepts{region!~"region-[01]"}[5m])', "accepts", range(2, 24, 3), True,
        None, "sum",
    ),
    "sum-by-region": (
        "sum by (region) (rate(nginx_requests[5m]))", "requests", range(24), True, "region", "sum",
    ),
    "avg-by-datacenter": (
        "avg by (datacenter) (rate(nginx_handled[5m]))", "handled", range(24), True,
        "datacenter", "avg",
    ),
    # a recording rule's instant and a panel's six steps over 2^14 series
    "wide-instant-sum-by-service": (
        "sum by (service) (rate(nginx_requests[5m]))", "requests", None, True,
        "service", "sum", "wide", 0,
    ),
    "wide-6-steps-sum-by-region": (
        "sum by (region) (rate(nginx_requests[5m]))", "requests", None, True,
        "region", "sum", "wide", 300,
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_tile_is_legacy_is_reference(request, case):
    promql, metric, hosts, per_second, by, op, *where = CASES[case]
    which, span_s = where or ("small", None)
    fixture, start_s, span, step_s, plane_rows = FLEETS[which]
    span_s = span if span_s is None else span_s
    db, fleet, _ = request.getfixturevalue(fixture)
    hosts = range(fleet.hosts) if hosts is None else hosts
    q = _tql(start_s, promql, span_s=span_s, step_s=step_s)
    _warm(db, q)
    before = {k: getattr(m, k).total() for k in (
        "TQL_TILE_DISPATCHES", "TQL_TILE_LOGICAL_DISPATCHES", "TQL_TILE_INELIGIBLE",
        "TPU_DEVICE_DISPATCHES", "TQL_TILE_PLANE_ROWS", "TQL_TILE_SERIES",
    )}
    got = db.sql_one(q)
    moved = {k: getattr(m, k).total() - v for k, v in before.items()}
    assert moved["TQL_TILE_DISPATCHES"] == moved["TQL_TILE_LOGICAL_DISPATCHES"] == 1
    assert moved["TPU_DEVICE_DISPATCHES"] == 1 and moved["TQL_TILE_INELIGIBLE"] == 0
    assert moved["TQL_TILE_PLANE_ROWS"] == plane_rows
    # every series of the table is evaluated, whatever the matchers keep
    assert moved["TQL_TILE_SERIES"] == fleet.hosts
    want_names = [by] if by else LABELS
    assert got.column_names == [*want_names, "ts", "value"]
    if which == "small":  # the legacy scan takes tens of seconds at 2^14 series
        legacy = _legacy(db, q)
        assert legacy.column_names == got.column_names
        _close(_rows(got), _rows(legacy), 1e-12, case + ": tile vs legacy")
    want = _reference(
        fleet, metric, list(hosts), start_s * 1000, per_second, by, op,
        span_s=span_s, step_s=step_s,
    )
    assert len(want) == (span_s // step_s + 1) * len({_label(by, h) for h in hosts}) if by \
        else len(want)
    _close(_rows(got), want, 1e-9, case + ": tile vs reference")


def _loop_order(np_pres):
    """The series order as a loop over the regions' presence made it."""
    order, seen = [], set()
    for p in np_pres:
        for sid in np.nonzero(p)[0]:
            if int(sid) not in seen:
                seen.add(int(sid))
                order.append(int(sid))
    return order


@pytest.mark.parametrize("regions,seed", [(1, 0), (2, 1), (3, 2), (5, 3)])
def test_the_series_and_group_order_are_the_loops(regions, seed):
    """Shuffled presence over several regions, which may overlap: the
    series at first appearance, regions in scan order, and the groups at
    their first present series, as `dict.fromkeys` over that order."""
    from greptimedb_tpu.query.promql import tile_exec as te

    rng = np.random.default_rng(seed)
    s, groups = 4096, 37
    pres = [rng.random(s) < rng.uniform(0.05, 0.9) for _ in range(regions)]
    order = te._legacy_order(pres)
    assert order.tolist() == _loop_order(pres)
    gid = rng.permutation(np.arange(s) % groups).astype(np.int32)
    got = te._first_appearance(gid[order], groups)
    assert got.tolist() == list(dict.fromkeys(gid[order].tolist()))
    every = te._first_appearance(gid, groups)
    assert every.tolist() == list(dict.fromkeys(gid.tolist()))


def test_an_evaluation_past_the_free_device_memory_declines_naming_its_bytes(
    fleet_db, monkeypatch,
):
    from greptimedb_tpu.query.promql import tile_exec as te
    from greptimedb_tpu.utils import tracing

    db, fleet, _ = fleet_db
    q = _tql(S0, "sum by (region) (rate(nginx_requests[5m]))")
    want = _rows(_warm(db, q))
    seen = []
    plain = tracing.stage.set
    monkeypatch.setattr(
        tracing.stage, "set", lambda self, **a: (seen.append(a), plain(self, **a))[1]
    )
    _close(_rows(db.sql_one(q)), want, 1e-12)
    # the compiler's count of what the fused program holds beside the planes
    [need] = [a["device_bytes"] for a in seen if "device_bytes" in a]
    assert need > 32 * 32 * 8  # at least the [series, steps] matrix
    cache = db.query_engine.tile_cache
    device = cache.devices[0]
    saved = te._DEVICE_LIMIT.get(device, "absent")
    try:
        # tql.legacy_fallback = false: the statement fails, naming the bytes
        te._DEVICE_LIMIT[device] = cache.device_used()[0] + need - 1
        db.config.tql.legacy_fallback = False
        with pytest.raises(Exception, match=(
            f"the evaluation holds {need} bytes on the device \\(32 series x 32 "
            f"steps, 4 groups\\); the plane cache leaves {need - 1} bytes free"
        )):
            db.sql_one(q)
        db.config.tql.legacy_fallback = True
        # no room for the fold nor for the per-series evaluation the engine
        # asks for next: the legacy scan answers
        te._DEVICE_LIMIT[device] = cache.device_used()[0] + 1
        before = m.TQL_TILE_INELIGIBLE.total(), m.TQL_TILE_DISPATCHES.total()
        _close(_rows(db.sql_one(q)), want, 1e-12)
        assert m.TQL_TILE_INELIGIBLE.total() - before[0] == 2
        assert m.TQL_TILE_DISPATCHES.total() == before[1]
        # the byte it lacked, and it is the device's again
        te._DEVICE_LIMIT[device] = cache.device_used()[0] + need
        before = m.TQL_TILE_DISPATCHES.total()
        _close(_rows(db.sql_one(q)), want, 1e-12)
        assert m.TQL_TILE_DISPATCHES.total() - before == 1
    finally:
        db.config.tql.legacy_fallback = True
        if saved == "absent":
            te._DEVICE_LIMIT.pop(device, None)
        else:
            te._DEVICE_LIMIT[device] = saved


def test_series_come_in_label_order_on_both_paths(fleet_db):
    db, fleet, _ = fleet_db
    q = _tql(S0, "rate(nginx_requests[5m])", span_s=60)
    for table in (_warm(db, q), _legacy(db, q)):
        keys = [r[:-2] for r in _rows(table)]
        series = list(dict.fromkeys(keys))
        assert len(series) == 24
        assert series == sorted(series)
        # and not the order of the planes: a hash's
        assert [k[LABELS.index("hostname")] for k in series] != [
            f"host_{h}" for h in range(24)
        ]


def test_label_order_is_decided_once_from_the_tables_the_expression_reads():
    """`query_range` asks the catalog once: an answer is put in label order
    where every metric of the expression is a logical table; one that reads
    a mito table keeps the order its key gives, the same on both paths."""
    fleet = Fleet(hosts=6, ticks=120)
    db = _db()
    try:
        _load(db, fleet)
        _label_order_cases(db, fleet)
    finally:
        db.close()


def _label_order_cases(db, fleet):
    from greptimedb_tpu.query.promql.engine import PromqlEngine
    from greptimedb_tpu.query.promql.parser import parse_promql

    db.sql(
        "CREATE TABLE IF NOT EXISTS mito_requests (hostname STRING, greptime_timestamp "
        "TIMESTAMP(3) TIME INDEX, greptime_value DOUBLE, PRIMARY KEY (hostname))"
    )
    hosts = ["host_5", "host_1", "host_2"]
    ts = np.repeat(fleet.tick_ts()[:120], 3)
    db.insert_rows("mito_requests", pa.table({
        "hostname": hosts * 120,
        "greptime_timestamp": pa.array(ts, pa.timestamp("ms")),
        "greptime_value": np.arange(360, dtype=np.float64),
    }))
    db.storage.flush_all()
    engine = PromqlEngine(db)
    reads = {
        "rate(nginx_requests[5m])": True,
        "sum by (region) (rate(nginx_requests[5m])) / 2": True,
        "nginx_requests - on (hostname) nginx_handled": True,
        "rate(mito_requests[5m])": False,
        "nginx_requests * on (hostname) group_left mito_requests": False,
        "rate(no_such_table[5m])": False,
        "1 + 1": False,
    }
    for promql, logical in reads.items():
        assert engine._reads_logical_tables(parse_promql(promql)) is logical, promql
    assert not hasattr(engine, "_label_sort")
    # the mito table answers in its key's order from the tiles and from the
    # legacy scan, after logical tables were read too
    q = _tql(T0 // 1000 + 300, "rate(mito_requests[5m])", span_s=300)
    got = _rows(_warm(db, q))
    assert list(dict.fromkeys(r[0] for r in got)) == sorted(hosts)
    _close(got, _rows(_legacy(db, q)), 1e-12, "mito: tile vs legacy")
    # a mixed expression: the same rows in the same order on both paths
    mixed = _tql(
        T0 // 1000 + 300,
        "rate(nginx_requests[5m]) * on (hostname) group_left rate(mito_requests[5m])",
        span_s=300,
    )
    got = _rows(_warm(db, mixed))
    assert len({r[:-2] for r in got}) == 3
    _close(got, _rows(_legacy(db, mixed)), 1e-12, "mixed: tile vs legacy")


def test_the_series_table_is_charged_to_the_host_budget_and_its_memo_bounded(fleet_db):
    from greptimedb_tpu.parallel.tile_cache import SeriesTable

    db, _, _ = fleet_db
    _warm(db, _tql(S0, "rate(nginx_requests[5m])"))
    cache = db.query_engine.tile_cache
    # the physical region's entry: one table of all its series
    (entry,) = [e for e in cache._super.values() if e.num_rows == 3 * 24 * 360]
    table = entry.series_table
    assert len(table) == 3 * 24 and table.nbytes == table.starts.nbytes + table.codes.nbytes
    parts = [entry.order, entry.file_row_offsets, *entry.sorted_host.values(),
             entry.ts_run_starts, entry.keep_host, entry.keep_prefix]
    assert entry.host_nbytes == table.nbytes + sum(a.nbytes for a in parts if a is not None)
    # asked again at the same file set and epoch: the same table, charged once
    charged = entry.host_nbytes, cache._host_used
    _warm(db, _tql(S0, "rate(nginx_handled[5m])"))
    assert entry.series_table is table
    assert (entry.host_nbytes, cache._host_used) == charged
    # the memo keeps the newest MEMO_MAX of what requests derived
    small = SeriesTable(("k",), ("a",), np.zeros(2, np.int64), np.zeros((1, 1), np.int32))
    made = []
    for i in range(3 * SeriesTable.MEMO_MAX):
        assert small.remember(("gids", i), lambda i=i: made.append(i) or i) == i
    assert len(small.memo) == SeriesTable.MEMO_MAX and len(made) == 3 * SeriesTable.MEMO_MAX
    newest = ("gids", 3 * SeriesTable.MEMO_MAX - 1)
    assert small.remember(newest, lambda: made.append("again")) == newest[1]
    assert "again" not in made and ("gids", 0) not in small.memo


def test_a_neighbour_tables_rows_never_enter(fleet_db):
    """The slice is 2^14 rows, the table 8,640: it reaches into the next
    logical table's rows, which the row bounds keep out."""
    db, fleet, _ = fleet_db
    for metric in METRICS:
        q = _tql(S0, f"count_over_time(nginx_{metric}[10m])", span_s=600)
        got = _rows(_warm(db, q))
        assert len(got) == 24 * 11
        assert {r[-1] for r in got} == {60.0}
        _close(got, _rows(_legacy(db, q)), 0, metric)
    # each table answers with its own samples
    a = _rows(db.sql_one(_tql(S0, "last_over_time(nginx_accepts[1m])", span_s=60)))
    b = _rows(db.sql_one(_tql(S0, "last_over_time(nginx_handled[1m])", span_s=60)))
    assert [r[:-1] for r in a] == [r[:-1] for r in b]
    assert [r[-1] for r in a] != [r[-1] for r in b]


@pytest.mark.parametrize("shift_s", [-10, 0, 10, 290, 300, 310])
def test_a_counter_that_restarts_at_a_windows_edge(fleet_db, shift_s):
    """Host 3's counters read 0 at tick 100 (T0 + 1000 s): windows that
    begin or end on, just before and just after that sample."""
    db, fleet, _ = fleet_db
    start = T0 // 1000 + 1000 + shift_s
    q = _tql(start, 'increase(nginx_requests{hostname="host_3"}[5m])', span_s=600)
    got = _rows(_warm(db, q))
    _close(got, _rows(_legacy(db, q)), 1e-12, "tile vs legacy")
    _close(got, _reference(fleet, "requests", [3], start * 1000, False, span_s=600), 1e-9)
    assert all(r[-1] >= 0 for r in got)


def test_two_tables_of_one_padded_size_share_a_program(fleet_db):
    from greptimedb_tpu.query.promql import tile_exec

    db, _, _ = fleet_db
    _warm(db, _tql(S0, "delta(nginx_requests[5m])"))
    programs = len(tile_exec._PROGRAMS)
    for metric in ("accepts", "handled"):
        db.sql_one(_tql(S0 + 60, f"delta(nginx_{metric}[5m])"))
    assert len(tile_exec._PROGRAMS) == programs


def test_prewarm_builds_the_region_once_and_reports_each_table(fleet_db):
    db, _, stats = fleet_db
    assert sorted(stats) == [f"public.nginx_{x}" for x in METRICS]
    for s in stats.values():
        assert s["physical"] == "public.phy" and s["regions_built"] == 1
        assert "error" not in s
    phys = db.catalog.table("phy", "public")
    cache = db.query_engine.tile_cache
    assert list(cache._super) == list(phys.region_ids)
    entry = cache._super[phys.region_ids[0]]
    # each sample on the device once: the region's planes, no copy a table
    assert entry.num_rows == 3 * 24 * 360
    assert {"__table_id", "__tsid", "greptime_value"} <= set(entry.cols)


def test_a_build_that_raises_is_an_error_in_the_stats(monkeypatch):
    from greptimedb_tpu.parallel import tile_cache

    fleet = Fleet(hosts=4, ticks=30)
    db = _db()
    try:
        _load(db, fleet)

        def boom(*_a, **_k):
            raise RuntimeError("encode failed")

        monkeypatch.setattr(tile_cache, "_encode_host_tiles", boom)
        stats = db.prewarm(tables=["nginx_requests"])
        assert "encode failed" in stats["public.nginx_requests"]["error"]
        assert stats["public.nginx_requests"]["regions_built"] == 0
    finally:
        db.close()


def test_memtable_rows_make_it_ineligible_with_the_reason(monkeypatch):
    from greptimedb_tpu.utils import tracing

    fleet = Fleet(hosts=6, ticks=120)
    db = _db()
    try:
        _load(db, fleet, slice(0, 100))
        q = _tql(T0 // 1000 + 300, "rate(nginx_requests[5m])", span_s=600)
        want = _rows(_warm(db, q))
        # rows of ANOTHER logical table, unflushed, inside the fetch window
        # (scraped again: the same samples): the region's memtable is every
        # table's
        db.insert_rows("nginx_accepts", fleet.table("accepts", slice(50, 60)))
        seen = []
        plain = tracing.stage.set
        monkeypatch.setattr(
            tracing.stage, "set", lambda self, **a: (seen.append(a), plain(self, **a))[1]
        )
        before = m.TQL_TILE_INELIGIBLE.total(), m.TQL_TILE_DISPATCHES.total()
        got = _rows(db.sql_one(q))
        assert m.TQL_TILE_INELIGIBLE.total() - before[0] == 1
        assert m.TQL_TILE_DISPATCHES.total() == before[1]
        assert {"ineligible": "memtable rows in the fetch window"} in seen
        assert any(a.get("logical_table") == "nginx_requests" for a in seen)
        _close(got, want, 1e-12)
        # tql.legacy_fallback = false: the same statement fails, naming why
        db.config.tql.legacy_fallback = False
        with pytest.raises(Exception, match="memtable rows in the fetch window"):
            db.sql_one(q)
        db.config.tql.legacy_fallback = True
        # flushed: eligible again (a delta merge into the planes)
        db.storage.flush_all()
        before = m.TQL_TILE_LOGICAL_DISPATCHES.total()
        _close(_rows(_warm(db, q)), want, 1e-12)
        assert m.TQL_TILE_LOGICAL_DISPATCHES.total() > before
    finally:
        db.close()


def test_legacy_fallback_off_still_serves_the_first_touch_and_the_warm_path():
    fleet = Fleet(hosts=6, ticks=120)
    db = _db(legacy_fallback=False)
    try:
        _load(db, fleet)
        q = _tql(T0 // 1000 + 300, "sum by (region) (rate(nginx_requests[5m]))", span_s=600)
        cold = m.TQL_TILE_COLD_SERVES.total()
        first = _rows(db.sql_one(q))  # no prewarm: the designed first touch
        assert m.TQL_TILE_COLD_SERVES.total() > cold
        _drain(db)
        before = m.TQL_TILE_LOGICAL_DISPATCHES.total()
        _close(_rows(db.sql_one(q)), first, 1e-12)
        assert m.TQL_TILE_LOGICAL_DISPATCHES.total() - before == 1
        # an expression with no tile form has no answer either
        with pytest.raises(Exception, match="tql.legacy_fallback = false"):
            db.sql_one(_tql(T0 // 1000 + 300, "quantile_over_time(0.5, nginx_requests[5m])"))
        db.config.tql.legacy_fallback = True
        assert db.sql_one(
            _tql(T0 // 1000 + 300, "quantile_over_time(0.5, nginx_requests[5m])")
        ).num_rows
    finally:
        db.close()


def test_a_second_flush_after_the_prewarm_gives_the_same_answers():
    fleet = Fleet(hosts=6, ticks=240)
    db = _db()
    try:
        _load(db, fleet, slice(0, 120))
        db.prewarm(tables=["nginx_requests"])
        q = _tql(T0 // 1000 + 300, "rate(nginx_handled[5m])", span_s=600)
        first = _rows(_warm(db, q))
        _load(db, fleet, slice(120, 240))
        again = _rows(_warm(db, q))
        _close(again, first, 1e-12)
        late = _tql(T0 // 1000 + 1500, "rate(nginx_handled[5m])", span_s=600)
        got = _rows(_warm(db, late))
        _close(got, _rows(_legacy(db, late)), 1e-12)
        _close(got, _reference(fleet, "handled", range(6), (T0 // 1000 + 1500) * 1000, True,
                               span_s=600), 1e-9)
    finally:
        db.close()


def test_a_label_added_later_is_a_value_like_any_other():
    """`zone` joins nginx_accepts after its first rows: those keep NULL
    there and stay their own series; no row is dropped for it."""
    fleet = Fleet(hosts=4, ticks=120)
    db = _db()
    try:
        _load(db, fleet, slice(0, 60))
        db.metric.ensure_logical_table("nginx_accepts", [*LABELS, "zone"], "phy", "public")
        zone = pa.array([f"z{h % 2}" for h in range(4)] * 60)
        db.insert_rows("nginx_accepts", fleet.table("accepts", slice(60, 120), {"zone": zone}))
        db.storage.flush_all()
        q = _tql(T0 // 1000 + 300, "count_over_time(nginx_accepts[5m])", span_s=720, step_s=60)
        got = _warm(db, q)
        assert got.column_names == [*LABELS, "zone", "ts", "value"]
        rows = _rows(got)
        _close(rows, _rows(_legacy(db, q)), 0)
        zones = {r[-3] for r in rows}
        assert zones == {None, "z0", "z1"}
        assert len({r[:-2] for r in rows}) == 8  # 4 old series, 4 new
        # every sample of the window is in some series' count
        at = [r for r in rows if r[-2] == rows[0][-2]]
        assert sum(r[-1] for r in at) == 4 * 30
        # a matcher on the new label, and a fold by it
        only = _rows(_warm(db, _tql(T0 // 1000 + 900, 'delta(nginx_accepts{zone="z1"}[5m])')))
        assert {r[-3] for r in only} == {"z1"} and len({r[0:-2] for r in only}) == 2
        by = _warm(db, _tql(T0 // 1000 + 900, "sum by (zone) (delta(nginx_accepts[5m]))"))
        _close(_rows(by), _rows(_legacy(
            db, _tql(T0 // 1000 + 900, "sum by (zone) (delta(nginx_accepts[5m]))"))), 1e-12)
    finally:
        db.close()


def test_a_table_across_chunks_of_the_planes():
    """Small chunks: the region's planes are eight, each logical table
    lies across several, and the cut enters the first and the last."""
    fleet = Fleet(hosts=24, ticks=360)
    db = _db()
    try:
        db.query_engine.tile_cache.chunk_rows = 4096
        _load(db, fleet)
        db.prewarm(tables=["nginx_handled"])
        phys = db.catalog.table("phy", "public")
        entry = db.query_engine.tile_cache._super[phys.region_ids[0]]
        assert len(entry.cols["__tsid"]) == 8
        for metric in METRICS:
            q = _tql(S0, f"rate(nginx_{metric}[5m])", span_s=600)
            before = m.TQL_TILE_LOGICAL_DISPATCHES.total()
            got = _rows(_warm(db, q))
            assert m.TQL_TILE_LOGICAL_DISPATCHES.total() > before
            _close(got, _rows(_legacy(db, q)), 1e-12, metric)
            _close(got, _reference(fleet, metric, range(24), S0 * 1000, True, span_s=600), 1e-9)
    finally:
        db.close()


def test_a_logical_table_under_the_mesh_keeps_the_legacy_path():
    fleet = Fleet(hosts=4, ticks=60)
    db = _db()
    try:
        _load(db, fleet)
        db.config.tile.mesh_devices = 2
        q = _tql(T0 // 1000 + 300, "rate(nginx_requests[2m])", span_s=120)
        before = m.TQL_TILE_INELIGIBLE.total(), m.TQL_TILE_DISPATCHES.total()
        got = db.sql_one(q)
        assert m.TQL_TILE_INELIGIBLE.total() - before[0] == 1
        assert m.TQL_TILE_DISPATCHES.total() == before[1]
        assert got.num_rows == 4 * 3
    finally:
        db.close()


def test_sql_over_a_logical_table_keeps_todays_path(fleet_db):
    db, fleet, _ = fleet_db
    before = m.TQL_TILE_LOGICAL_DISPATCHES.total()
    t = db.sql_one(
        "SELECT hostname, max(greptime_value) AS v FROM nginx_requests "
        "WHERE hostname = 'host_5' GROUP BY hostname"
    )
    assert t["v"].to_pylist() == [fleet.samples["requests"][:, 5].max()]
    assert m.TQL_TILE_LOGICAL_DISPATCHES.total() == before
    assert db._tile_context(
        types.SimpleNamespace(table="nginx_requests", database="public")
    ) is None


# ---- write path --------------------------------------------------------------


def test_tsids_are_bit_equal_to_tsid_hash_row_by_row():
    from greptimedb_tpu.metric.engine import _batch_tsids, tsid_hash

    n = 3000
    labels = {
        "a": pa.array([None if i % 7 == 0 else f"x{i % 13}" for i in range(n)]),
        "b": pa.array([f"y{i % 5}" if i % 3 else None for i in range(n)]),
        "c": pa.array([None] * n, pa.string()),
        "d": pa.DictionaryArray.from_arrays(
            pa.array([i % 4 if i % 11 else None for i in range(n)], pa.int32()),
            pa.array(["p", "q", "p", None]),  # a repeated and a null dictionary value
        ),
    }
    before = m.METRIC_TSID_HASHES.total()
    got = _batch_tsids("metric", labels, n)
    hashed = m.METRIC_TSID_HASHES.total() - before
    plain = {k: v.cast(pa.string()).to_pylist() for k, v in labels.items()}
    want = [
        tsid_hash(
            [(k, v[i]) for k, v in plain.items() if v[i] is not None]
            + [("__name__", "metric")]
        )
        for i in range(n)
    ]
    assert got.tolist() == want
    assert len(set(want)) <= hashed < n // 4  # one hash a distinct label set


def test_a_key_space_past_int64_is_made_dense_again():
    from greptimedb_tpu.metric.engine import _batch_tsids, tsid_hash

    n = 400
    rng = np.random.default_rng(3)
    cols = {
        f"l{j:02d}": [f"v{j}-{x}" for x in rng.integers(0, 60, n)] for j in range(14)
    }  # 61^14 > 2^62
    got = _batch_tsids("wide", {k: pa.array(v) for k, v in cols.items()}, n)
    want = [
        tsid_hash([(k, v[i]) for k, v in cols.items()] + [("__name__", "wide")])
        for i in range(n)
    ]
    assert got.tolist() == want


def test_write_logical_stores_those_tsids(fleet_db):
    from greptimedb_tpu.metric.engine import tsid_hash

    db, fleet, _ = fleet_db
    t = db.sql_one("SELECT DISTINCT __tsid, hostname FROM phy WHERE __table_id = "
                   f"{db.catalog.table('nginx_handled', 'public').table_id}")
    assert t.num_rows == 24
    for tsid, host in zip(t["__tsid"].to_pylist(), t["hostname"].to_pylist()):
        h = int(host.split("_")[1])
        pairs = [(l, _label(l, h)) for l in LABELS] + [("__name__", "nginx_handled")]
        assert tsid == tsid_hash(pairs)


# ---- the slice geometry --------------------------------------------------------


@pytest.mark.parametrize("lengths,r_lo,r_hi", [
    ([1 << 14], 0, 1 << 14),                 # the whole single chunk
    ([1 << 14], 5000, 9000),                 # inside one chunk
    ([4096] * 8, 8640, 17280),               # across three chunks
    ([4096] * 8, 4000, 4200),                # across one boundary, L < chunk
    ([4096] * 8, 25920, 25921),              # one row near the end
    ([4096] * 8, 0, 1),                      # one row at the start
    ([1 << 12, 1 << 12], 4095, 4097),        # two rows, one each side
    ([1 << 13] * 4, 100, 30000),             # nearly everything: L = total
    ([1024], 3, 9),                          # a plane smaller than the least slice
])
def test_slice_plan_cuts_the_tables_rows(lengths, r_lo, r_hi):
    from greptimedb_tpu.query.promql.tile_exec import _slice_plan

    total = sum(lengths)
    plane = np.arange(total)
    chunks, at = [], 0
    for n in lengths:
        chunks.append(plane[at:at + n])
        at += n
    first, last, (size, head, tail), off, base = _slice_plan(lengths, r_lo, r_hi)
    touched = chunks[first:last + 1]
    if len(touched) == 1:
        flat = touched[0]
    else:
        flat = np.concatenate([touched[0][head:], *touched[1:-1], touched[-1][:tail]])
    assert 0 <= off and off + size <= len(flat)  # dynamic_slice would not clamp
    cut = flat[off:off + size]
    assert cut.tolist() == list(range(base, base + size))
    assert base <= r_lo and r_hi <= base + size
    assert size & (size - 1) == 0 or size == total
    if len(touched) > 1:  # what is copied: never the region
        assert len(flat) <= 2 * size + sum(lengths[first + 1:last])
