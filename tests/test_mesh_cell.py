"""The deployment of the cell `tsbs-mesh4-heavy` at a small size, on the
conftest's virtual CPU devices: TSBS `cpu` in four regions by HASH
(hostname) with `tile.mesh_devices = 4`.  Every shape of the cell's mix is
ONE shard_map dispatch, equal to the numpy folds and to the same table with
`tile.mesh_devices = 0`.  A `double-groupby-1` scans its regions' resident
planes with the window as a mask, whether the window declines as dearer to
build than to scan (12 h over 24 h: cover 0.5) or by its cover (over 13 h:
12/13); where the planes are NOT on the device at the decision, the 24 h
table gets its window tiles.  A dispatch handed to the single chip moves
`TILE_MESH_INELIGIBLE` and says why on its `tile.dispatch`.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import compare, manifest, program, traffic  # noqa: E402

from greptimedb_tpu.parallel import tile_cache  # noqa: E402
from greptimedb_tpu.parallel.mesh import region_device_index  # noqa: E402
from greptimedb_tpu.parallel.tile_cache import TileCacheManager  # noqa: E402
from greptimedb_tpu.utils import metrics, tracing  # noqa: E402

CELL, HOSTS, CHIPS = "tsbs-mesh4-heavy", 40, 4
SHAPES = ("double-groupby-1", "lastpoint", "groupby-orderby-limit")
MESH_COUNTERS = (
    "TILE_MESH_DISPATCHES", "TILE_MESH_INELIGIBLE", "TILE_MESH_DEGRADED",
    "TPU_DEVICE_DISPATCHES", "TILE_WINDOW_BUILDS", "TILE_WINDOW_COUNTED", "TILE_WINDOW_RESIDENT_SCANS",
    "TPU_FALLBACK_TOTAL", "TPU_ROUTED_TO_CPU", "TILE_SERIES_GIDS", "TILE_ORDINAL_GIDS",
    "TPU_COMPILE_CACHE_HITS", "TPU_COMPILE_CACHE_MISSES",
)


class Fleet:
    """The cell's table at `hours` hours, loaded as the harness loads it."""

    def __init__(self, home: str, hours: int, **scale):
        self.cell = manifest.Cell(CELL, {"hosts": HOSTS, "hours": hours, **scale})
        self.ds = self.cell.dataset(2**31 + 33)
        self.db = program.open_database(home, self.cell.config["database"])
        program.load(self.db, self.ds)
        program.prewarm(self.db, self.ds.tables)
        self.literals = {}
        for shape, lit in traffic.requests(self.cell.traffic, self.ds, 33, 1):
            if shape in self.literals:
                break
            self.literals[shape] = lit

    def sql(self, shape: str) -> str:
        if shape == "high-cpu-1":  # TSBS's, as `chip_smoke.py` asks it: no group key
            return (
                f"SELECT count(*) AS n, max(usage_user) AS m FROM {self.ds.table} WHERE usage_user > 90.0 "
                f"AND hostname = 'host_3' AND ts >= {self.ds.t0} AND ts < {self.ds.end}"
            )
        return self.cell.shapes[shape].request(self.ds, self.literals[shape])["sql"]

    def ask(self, shape: str):
        """(table, counter moves) of one request of `shape`."""
        before = {k: getattr(metrics, k).total() for k in MESH_COUNTERS}
        table = self.db.sql_one(self.sql(shape))
        return table, {k: getattr(metrics, k).total() - before[k] for k in MESH_COUNTERS}

    def warm(self, shape: str):
        """The first touch is served from the host while the background
        builder makes the family's planes and programs."""
        self.ask(shape)
        program.wait_builds(self.db)

    def gap(self, shape: str, table) -> tuple:
        rows = list(zip(*[
            [int(v.timestamp() * 1000) if hasattr(v, "timestamp") else v
             for v in table[c].to_pylist()]
            for c in table.column_names
        ]))
        want = self.cell.shapes[shape].reference(self.ds, self.literals[shape])
        return compare.compare(rows, want)


@pytest.fixture(scope="module", params=[24, 13], ids=["window-resident-scan", "window-cover-declines"])
def fleet(request, tmp_path_factory):
    # a region holds 40 / 4 hosts x 8640 ticks: far under 2^22 rows, where a
    # window is not probed at all
    patch = pytest.MonkeyPatch()
    patch.setattr(TileCacheManager, "_WINDOW_TILE_MIN_ROWS", 1 << 12)
    patch.setattr(TileCacheManager, "_WINDOW_TILE_GRID", 1 << 14)
    f = Fleet(str(tmp_path_factory.mktemp(f"mesh{request.param}")), request.param)
    f.half = request.param == 24  # the window covers half a region, not 12/13
    yield f
    f.db.close()
    patch.undo()


@pytest.mark.parametrize("shape", SHAPES)
def test_each_shape_is_one_mesh_dispatch_equal_to_the_fold_and_to_one_chip(fleet, shape):
    bar = fleet.cell.config["guarantees"][fleet.cell.shapes[shape].BAR]
    fleet.warm(shape)
    for _ in range(2):
        meshed, moved = fleet.ask(shape)
        assert moved["TILE_MESH_DISPATCHES"] == 1 == moved["TPU_DEVICE_DISPATCHES"], moved
        assert not moved["TILE_MESH_INELIGIBLE"] and not moved["TILE_MESH_DEGRADED"], moved
        assert not moved["TPU_FALLBACK_TOTAL"] and not moved["TPU_ROUTED_TO_CPU"], moved
        keys_ok, gap = fleet.gap(shape, meshed)
        assert keys_ok and gap <= bar, (shape, gap)
    if shape == "double-groupby-1":
        regions = fleet.cell.config["regions"]
        # the planes are resident, so no tile is built, in the family's build
        # or by a request: a declined window is counted anew by every request,
        # and says whether the scan's price or the cover declined it
        assert moved["TILE_WINDOW_BUILDS"] == 0
        assert moved["TILE_WINDOW_COUNTED"] == regions
        assert moved["TILE_WINDOW_RESIDENT_SCANS"] == (regions if fleet.half else 0)
        entries = fleet.db.query_engine.tile_cache._super.values()
        assert [len(e.window_tiles) for e in entries] == [0] * regions
    fleet.db.config.tile.mesh_devices = 0
    try:
        single, moved = fleet.ask(shape)
    finally:
        fleet.db.config.tile.mesh_devices = CHIPS
    assert moved["TILE_MESH_DISPATCHES"] == 0 and moved["TPU_DEVICE_DISPATCHES"] == 1, moved
    assert not moved["TILE_MESH_INELIGIBLE"], "the mesh path is off, not declined"
    assert single.to_pydict() == meshed.to_pydict()


def test_planes_not_on_the_device_at_the_decision_still_get_their_window_tile(fleet):
    """Deep retention's path: with a region's planes released, a window of
    half its rows is gathered into a tile (it uploads the window's rows, not
    the plane), whose chunks go round robin from the region's chip; the
    answer is the resident scan's and the fold's.  A cover of 12/13 declines
    there too, and the planes come back."""
    shape = "double-groupby-1"
    fleet.warm(shape)
    scanned, moved = fleet.ask(shape)
    assert moved["TILE_WINDOW_BUILDS"] == 0 and moved["TILE_MESH_DISPATCHES"] == 1, moved
    cache = fleet.db.query_engine.tile_cache
    devices = cache.placement_devices()[:CHIPS]
    regions = fleet.cell.config["regions"]
    try:
        for entry in list(cache._super.values()):
            cache.release_unneeded(entry, set())
            assert not entry.cols and not entry.limb_cols
        tiled, moved = fleet.ask(shape)
        assert moved["TILE_MESH_DISPATCHES"] == 1 == moved["TPU_DEVICE_DISPATCHES"], moved
        assert not moved["TILE_MESH_INELIGIBLE"] and not moved["TILE_MESH_DEGRADED"], moved
        assert moved["TILE_WINDOW_COUNTED"] == regions and not moved["TILE_WINDOW_RESIDENT_SCANS"], moved
        assert moved["TILE_WINDOW_BUILDS"] == (regions if fleet.half else 0), moved
        for rid, entry in cache._super.items():
            base = region_device_index(rid, CHIPS)
            assert len(entry.window_tiles) == (1 if fleet.half else 0)
            # a region served by its tile never uploads its planes (the
            # groups come off its series table: `ts` is a plane it reads)
            assert ("ts" in entry.cols) == (not fleet.half)
            for wt in entry.window_tiles.values():
                assert wt["rows"] == entry.num_rows // 2 and len(wt["valid"]) == 3
                for chunks in (wt["valid"], *wt["cols"].values()):
                    assert [next(iter(x.devices())) for x in chunks] == [
                        devices[(base + i) % CHIPS] for i in range(len(chunks))
                    ]
        keys_ok, gap = fleet.gap(shape, tiled)
        assert keys_ok and gap <= fleet.cell.config["guarantees"][fleet.cell.shapes[shape].BAR]
        # a group's limb sums quantize by the blocks its rows share with
        # their neighbours, which the tile's own rows change
        rows_close(tiled, scanned, rtol=2e-7)
        again, moved = fleet.ask(shape)  # the window's tiles are found, not counted
        assert moved["TILE_WINDOW_COUNTED"] == (0 if fleet.half else regions), moved
        assert moved["TILE_WINDOW_BUILDS"] == 0 and again.to_pydict() == tiled.to_pydict()
    finally:
        # leave the module's fleet as the other tests expect it: no tile, and
        # the planes back on the device (a `lastpoint` reads every one)
        for entry in list(cache._super.values()):
            cache.release_unneeded(entry, {"no such column"})
            assert not entry.window_tiles
        fleet.warm("lastpoint")


def test_planes_lie_where_chunk_device_places_them(fleet):
    """A region's planes, the time-major copies of `groupby-orderby-limit`
    included, lie whole on device `region_device_index(r, 4)`, so every
    device holds a quarter of the rows and none another's."""
    for shape in SHAPES:
        fleet.warm(shape)
    cache = fleet.db.query_engine.tile_cache
    devices = cache.placement_devices()[:CHIPS]
    assert len(cache._super) == CHIPS
    held = []
    for rid, entry in cache._super.items():
        base = region_device_index(rid, CHIPS)
        assert entry.tm_valid is not None and entry.perm is not None
        planes = [entry.valid, entry.tm_valid, *entry.cols.values(), *entry.tm_cols.values()]
        arrays = [x for chunks in planes for x in chunks] + [entry.perm]
        assert {d for x in arrays for d in x.devices()} == {devices[base]}, rid
        assert not entry.window_tiles
        held.append((base, entry.num_rows))
    assert sorted(base for base, _ in held) == list(range(CHIPS))
    assert {rows for _, rows in held} == {fleet.ds.rows // CHIPS}


@pytest.mark.parametrize("planted", ["source", "pass"])
def test_a_dispatch_handed_to_the_single_chip_is_counted_and_says_why(fleet, planted, monkeypatch):
    shape = "lastpoint"
    fleet.warm(shape)
    if planted == "source":
        def runs(_sources):
            raise tile_cache._MeshIneligible("planted: no stacked mesh form")
        monkeypatch.setattr(tile_cache, "_mesh_runs", runs)
        why = "planted: no stacked mesh form"
    else:
        monkeypatch.setattr(fleet.db.query_engine.config, "disabled_passes", ("mesh_dispatch",))
        why = "mesh_dispatch pass disabled"
    tracing.EXPORTER.clear()
    table, moved = fleet.ask(shape)
    assert moved["TILE_MESH_INELIGIBLE"] == 1 == moved["TPU_DEVICE_DISPATCHES"], moved
    assert moved["TILE_MESH_DISPATCHES"] == 0 == moved["TILE_MESH_DEGRADED"], moved
    answered = [
        s for s in tracing.EXPORTER.spans()
        if s.name == "tile.dispatch" and s.attributes.get("mesh_devices") == 0
    ]
    assert [s.attributes.get("mesh_ineligible") for s in answered] == [why]
    keys_ok, gap = fleet.gap(shape, table)
    assert keys_ok and gap <= fleet.cell.config["guarantees"]["value_rtol_f64"]


def test_every_chip_is_held_to_its_own_share_of_the_budget(fleet):
    """`budget` is one chip's share: four regions on a chip each may hold
    four shares between them, and none is evicted for the others' bytes."""
    for shape in SHAPES:
        fleet.warm(shape)
    cache = fleet.db.query_engine.tile_cache
    sizes = {region_device_index(rid, CHIPS): e.nbytes for rid, e in cache._super.items()}
    assert cache.device_used() == [sizes[d] for d in range(CHIPS)]
    assert sum(cache.device_used()) == cache._used
    saved, cache.budget = cache.budget, max(sizes.values())
    try:
        with cache._lock:
            cache._evict_locked(set())
        assert len(cache._super) == CHIPS and cache._used > 3 * cache.budget
        fleet.db.config.tile.mesh_devices = 0  # one sum, as on one chip
        assert cache.device_used() == [cache._used]
    finally:
        cache.budget = saved
        fleet.db.config.tile.mesh_devices = CHIPS


def test_an_evicted_region_read_back_from_its_files_answers_at_the_dictionary_epoch(fleet):
    """A whole entry evicted comes back from its persisted file set with the
    tag codes at their STORED epoch and no device plane: the repair pass
    leaves it to the lazy upload, which gathers the codes forward."""
    for shape in SHAPES:
        fleet.warm(shape)
    cache = fleet.db.query_engine.tile_cache
    with cache._lock:
        cache._evict_locked(set(), limit=0)
    assert not cache._super and cache.device_used() == [0] * CHIPS
    for shape in SHAPES:
        fleet.warm(shape)
        table, moved = fleet.ask(shape)
        assert moved["TILE_MESH_DISPATCHES"] == 1 and not moved["TILE_MESH_DEGRADED"], moved
        keys_ok, gap = fleet.gap(shape, table)
        assert keys_ok and gap <= fleet.cell.config["guarantees"][fleet.cell.shapes[shape].BAR]


def test_one_region_tables_on_the_same_chip_share_one_chips_budget(tmp_path):
    """A one-region table lies whole on mesh device 0 (region number 0):
    three of them are held to ONE chip's share, not to four."""
    db = program.open_database(
        str(tmp_path), {"query.fallback_to_cpu": False, "tile.mesh_devices": CHIPS}
    )
    try:
        cache = db.query_engine.tile_cache
        for t in "abc":
            db.sql_one(
                f"CREATE TABLE one_{t} (host STRING, ts TIMESTAMP TIME INDEX, v DOUBLE, "
                "PRIMARY KEY (host)) WITH (append_mode = 'true')"
            )
            rows = ", ".join(f"('h{i % 7}', {1000 * i}, {i}.5)" for i in range(2000))
            db.sql_one(f"INSERT INTO one_{t} VALUES {rows}")
            db.sql_one(f"ADMIN flush_table('one_{t}')")

        def ask(t):
            out = db.sql_one(f"SELECT host, max(v) FROM one_{t} GROUP BY host ORDER BY host")
            program.wait_builds(db)
            assert out["max(v)"].to_pylist()[0] == 1995.5

        ask("a")
        used = cache.device_used()
        assert used[0] == cache._used > 0 and used[1:] == [0] * (CHIPS - 1)
        cache.budget = int(2.5 * used[0])  # room for two of the three tables
        evicted = metrics.TILE_CACHE_EVICTIONS.total()
        ask("b")
        assert metrics.TILE_CACHE_EVICTIONS.total() == evicted
        ask("c")
        assert metrics.TILE_CACHE_EVICTIONS.total() > evicted
        assert 0 < cache.device_used()[0] <= cache.budget
    finally:
        db.close()


def test_a_tag_plane_uploaded_again_after_a_release_is_at_the_dictionary_epoch(fleet):
    """Where an entry's planes pass half a chip's budget, a query drops the
    columns it does not read (`release_unneeded`): a `groupby-orderby-limit`
    drops `hostname`, and the next `lastpoint` that compares `hostname`
    uploads it again from the persisted codes, at their stored epoch, after
    the query's repair pass has run (its groups come off the series tables;
    the plane is its filter's).  It has to be gathered forward there: four
    regions grew the dictionary in turn, so every region's stored epoch is
    stale."""
    for shape in SHAPES:
        fleet.warm(shape)
    # a range no partition rule prunes: every region reads the plane
    sql = fleet.sql("lastpoint").replace(" GROUP BY", " WHERE hostname >= 'host_1' GROUP BY")
    fleet.db.config.query.backend = "cpu"
    try:
        want = fleet.db.sql_one(sql)
    finally:
        fleet.db.config.query.backend = "tpu"
    assert 0 < want.num_rows < HOSTS
    fleet.db.sql_one(sql)
    program.wait_builds(fleet.db)
    cache = fleet.db.query_engine.tile_cache
    for _ in range(2):
        for entry in list(cache._super.values()):
            cache.release_unneeded(entry, {"ts", "usage_user"})
            assert "hostname" not in entry.cols
        before = {k: getattr(metrics, k).total() for k in MESH_COUNTERS}
        table = fleet.db.sql_one(sql)
        moved = {k: getattr(metrics, k).total() - before[k] for k in MESH_COUNTERS}
        assert moved["TILE_MESH_DISPATCHES"] == 1 and not moved["TILE_MESH_DEGRADED"], moved
        assert table.sort_by("hostname").to_pydict() == want.sort_by("hostname").to_pydict()
        assert all("hostname" in e.cols for e in cache._super.values())


# ---- group ids over a source's own series ordinals (PR 34) ------------------


@pytest.fixture(scope="module")
def one_region(fleet, tmp_path_factory):
    """The fleet's twin in ONE region on one chip: the same seed, hosts and
    hours, so the same rows; the deployment of `tsbs-heavy`.  (`fleet`'s
    patch of the window-tile sizes outlives this fixture.)"""
    hours = fleet.cell.config["hours"]
    f = Fleet(str(tmp_path_factory.mktemp(f"one{hours}")), hours, regions=1)
    f.db.config.tile.mesh_devices = 0
    f.literals = fleet.literals
    yield f
    f.db.close()


def rows_close(a, b, rtol):
    a, b = a.to_pydict(), b.to_pydict()
    assert list(a) == list(b)
    for name in a:
        if a[name] and isinstance(a[name][0], float):
            np.testing.assert_allclose(a[name], b[name], rtol=rtol, err_msg=name)
        else:
            assert a[name] == b[name], name


# a dispatch of the shape groups by its regions' series: its gid leads with `hostname`
ORDINAL = {"double-groupby-1": 1, "lastpoint": 1, "high-cpu-1": 0, "groupby-orderby-limit": 0}


@pytest.mark.parametrize("mesh_devices", [CHIPS, 0], ids=["mesh4", "one-chip"])
@pytest.mark.parametrize("shape", ["double-groupby-1", "lastpoint", "high-cpu-1"])
def test_a_hash_partitioned_table_answers_as_the_one_region_table_by_ordinals(
    fleet, one_region, shape, mesh_devices
):
    fleet.warm(shape)
    one_region.warm(shape)
    want, moved = one_region.ask(shape)
    assert moved["TILE_SERIES_GIDS"] == moved["TILE_ORDINAL_GIDS"] == 0, "one region holds the whole dictionary"
    assert not moved["TPU_FALLBACK_TOTAL"] and not moved["TPU_ROUTED_TO_CPU"], moved
    fleet.db.config.tile.mesh_devices = mesh_devices
    try:
        for _ in range(2):
            got, moved = fleet.ask(shape)
            assert moved["TILE_SERIES_GIDS"] == ORDINAL[shape] * moved["TPU_DEVICE_DISPATCHES"], moved
            assert moved["TILE_ORDINAL_GIDS"] == moved["TILE_SERIES_GIDS"], moved
            assert moved["TILE_MESH_DISPATCHES"] == (moved["TPU_DEVICE_DISPATCHES"] if mesh_devices else 0)
            assert not moved["TPU_FALLBACK_TOTAL"] and not moved["TPU_ROUTED_TO_CPU"], moved
            assert not moved["TILE_MESH_DEGRADED"] and not moved["TILE_MESH_INELIGIBLE"], moved
            # the limb sums of a group quantize by the blocks its rows share
            # with their neighbours, which a region's own plane changes
            rows_close(got, want, rtol=1e-9 if shape == "high-cpu-1" else 2e-7)
        if shape != "high-cpu-1":  # the host fast path may answer one host
            assert moved["TPU_DEVICE_DISPATCHES"] == 1, moved
            keys_ok, gap = fleet.gap(shape, got)
            assert keys_ok and gap <= fleet.cell.config["guarantees"][fleet.cell.shapes[shape].BAR]
    finally:
        fleet.db.config.tile.mesh_devices = CHIPS


def plans_of(f, shape, monkeypatch):
    """The plans `shape` hands the program cache, and the counter moves of
    its second warm request."""
    seen = []
    real = tile_cache._tile_program_cached
    monkeypatch.setattr(
        tile_cache, "_tile_program_cached",
        lambda plan, nullable, spec: (seen.append(plan), real(plan, nullable, spec))[1],
    )
    f.warm(shape)
    f.ask(shape)
    _table, moved = f.ask(shape)
    return seen, moved


@pytest.mark.parametrize("shape", SHAPES)
def test_a_one_region_table_keeps_the_parents_plan_and_program(one_region, shape, monkeypatch):
    seen, moved = plans_of(one_region, shape, monkeypatch)
    assert seen and not any(p.series_slots for p in seen)
    # the field at its default is the parent's plan: the same cache key,
    # so the same compiled program, found again by the second request
    parents = {f.name for f in dataclasses.fields(seen[-1])} - {"series_slots", "partitioned"}
    twin = type(seen[-1])(**{k: getattr(seen[-1], k) for k in parents})
    assert twin == seen[-1] and hash(twin) == hash(seen[-1])
    assert moved["TPU_COMPILE_CACHE_HITS"] >= 1 and moved["TPU_COMPILE_CACHE_MISSES"] == 0, moved
    assert moved["TILE_SERIES_GIDS"] == moved["TILE_ORDINAL_GIDS"] == 0, moved
    assert moved["TPU_DEVICE_DISPATCHES"] == 1, moved


@pytest.mark.parametrize("shape", SHAPES)
def test_a_region_of_a_table_partitioned_on_its_leading_tag_plans_ordinals(fleet, shape, monkeypatch):
    seen, moved = plans_of(fleet, shape, monkeypatch)
    assert seen and {bool(p.series_slots) for p in seen} == {bool(ORDINAL[shape])}
    assert {p.partitioned for p in seen} == {bool(ORDINAL[shape])}
    assert all(p.time_major for p in seen) == (shape == "groupby-orderby-limit")
    assert moved["TILE_SERIES_GIDS"] == moved["TILE_ORDINAL_GIDS"] == ORDINAL[shape], moved
    assert moved["TPU_DEVICE_DISPATCHES"] == 1, moved
    assert moved["TPU_COMPILE_CACHE_MISSES"] == 0, moved


def small_table(db, name, partition, hosts):
    db.sql_one(
        f"CREATE TABLE {name} (host STRING, dc STRING, ts TIMESTAMP TIME INDEX, v DOUBLE, "
        f"PRIMARY KEY (host, dc)){partition} WITH (append_mode = 'true')"
    )
    put(db, name, hosts, 0)


def put(db, name, hosts, t0):
    rows = ", ".join(
        f"('{h}', 'dc{i % 3}', {t0 + 1000 * k}, {i * 100 + k}.25)"
        for i, h in enumerate(hosts) for k in range(50)
    )
    db.sql_one(f"INSERT INTO {name} VALUES {rows}")
    db.sql_one(f"ADMIN flush_table('{name}')")


def answers(hosts) -> dict:
    """What `grouped` returns for hosts written by `put`, in its order."""
    return {h: (i * 100 + 49.25, 50, i * 100 + 49.25) for i, h in enumerate(hosts)}


def grouped(db, name):
    moved = metrics.TILE_SERIES_GIDS.total(), metrics.TPU_DEVICE_DISPATCHES.total()
    out = db.sql_one(
        f"SELECT host, max(v) AS hi, count(v) AS n, last_value(v) AS last FROM {name} GROUP BY host ORDER BY host"
    )
    program.wait_builds(db)
    moved = metrics.TILE_SERIES_GIDS.total() - moved[0], metrics.TPU_DEVICE_DISPATCHES.total() - moved[1]
    return {h: (hi, n, last) for h, hi, n, last in zip(*out.to_pydict().values())}, moved


@pytest.mark.parametrize("mesh_devices", [CHIPS, 0], ids=["mesh4", "one-chip"])
def test_a_dictionary_grown_after_the_planes_were_built_is_repaired_before_the_ordinals(tmp_path, mesh_devices):
    """New hosts that sort before, between and after the old ones move every
    code; the regions' planes are gathered forward to the new epoch, and the
    series of a region's runs fold to the NEW codes."""
    db = program.open_database(
        str(tmp_path), {"query.fallback_to_cpu": False, "tile.mesh_devices": mesh_devices}
    )
    try:
        old = [f"h{i:02d}" for i in range(10, 50, 2)]
        small_table(db, "grown", " PARTITION BY HASH (host) PARTITIONS 4", old)
        for _ in range(3):
            got, moved = grouped(db, "grown")
        assert moved == (1, 1), moved
        assert got == answers(old)
        new = ["h00", "h05", "h21", "h33", "h77", "h99"]
        put(db, "grown", new, 10_000_000)
        for _ in range(3):
            got, moved = grouped(db, "grown")
        assert moved == (1, 1), moved
        want = {**answers(old), **answers(new)}
        assert got == want and list(got) == sorted(want)
    finally:
        db.close()


def test_a_rule_over_another_column_leaves_the_codes(tmp_path):
    """Partitioned on `dc`, every region holds every host: the leading sort
    tag's codes are dense in a region and the plan is the parent's."""
    db = program.open_database(str(tmp_path), {"query.fallback_to_cpu": False, "tile.mesh_devices": 0})
    try:
        hosts = [f"h{i:02d}" for i in range(12)]
        small_table(db, "by_dc", " PARTITION BY HASH (dc) PARTITIONS 2", hosts)
        for _ in range(3):
            got, moved = grouped(db, "by_dc")
        assert moved == (0, 1), moved
        assert got == answers(hosts)
    finally:
        db.close()
