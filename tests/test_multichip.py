"""Multi-chip sharded tile execution (tile.mesh_devices).

The promotion of the MULTICHIP dryrun to the real path: the single-
dispatch tile program runs under shard_map over the 8-device virtual CPU
mesh, per-device partial aggregates merge via psum/pmin/pmax (hash slot
tables by keyed scatter into a union table), and the contract under test
is BIT parity — a 1-device mesh run, an 8-device mesh run and the
single-chip path (mesh_devices = 0) must produce byte-identical SQL
results across strategies, null-bearing tags/values and device-finalize
on/off — plus off-safety (0 = today's path), config validation, and the
degrade-to-single-chip contract on collective failure (fault point
`mesh.collective`)."""

import random

import numpy as np
import pyarrow as pa
import pytest

from greptimedb_tpu.database import Database
from greptimedb_tpu.utils import fault_injection as fi
from greptimedb_tpu.utils import metrics


@pytest.fixture(scope="module")
def db(tmp_path_factory):
    d = Database(data_home=str(tmp_path_factory.mktemp("multichip") / "db"))
    # force real device dispatches (no host-serve shortcuts) and several
    # chunks per region so the mesh actually has shards to place
    d.config.query.disabled_passes = ("cold_host_serve", "host_fast_path")
    d.config.query.tile_chunk_rows = 4096
    d.query_engine.tile_cache.chunk_rows = 4096
    d.sql(
        "CREATE TABLE t (host STRING, region STRING, ts TIMESTAMP TIME INDEX,"
        " v DOUBLE, w DOUBLE, PRIMARY KEY (host, region))"
        " PARTITION BY HASH (host) PARTITIONS 3"
    )
    rng = np.random.default_rng(42)
    n = 9000
    hosts = np.array([f"h{i % 40}" for i in range(n)])
    # NULL tag codes + NULL values: the parity bar covers the null paths
    regions = [None if i % 11 == 0 else f"r{i % 5}" for i in range(n)]
    ts = np.arange(n, dtype=np.int64) * 700
    v = rng.uniform(-100, 100, n)
    w = np.where(rng.uniform(0, 1, n) < 0.25, np.nan, rng.uniform(0, 50, n))
    d.insert_rows(
        "t",
        pa.table({
            "host": pa.array(hosts),
            "region": pa.array(regions),
            "ts": pa.array(ts, pa.timestamp("ms")),
            "v": pa.array(v),
            "w": pa.array(w, pa.float64()),
        }),
    )
    d.sql("ADMIN flush_table('t')")
    yield d
    d.config.tile.mesh_devices = 0
    d.close()


def _run_mesh(db, q, devices):
    db.config.tile.mesh_devices = devices
    try:
        return db.sql_one(q).to_pydict()
    finally:
        db.config.tile.mesh_devices = 0


def _assert_parity(db, q, expect_mesh=True):
    """single-chip vs 1-device mesh vs 8-device mesh: byte-identical."""
    lowered0 = metrics.TILE_LOWERED_TOTAL.get()
    single = _run_mesh(db, q, 0)
    assert metrics.TILE_LOWERED_TOTAL.get() > lowered0, (
        f"query did not take the tile path; parity vacuous: {q}"
    )
    mesh0 = metrics.TILE_MESH_DISPATCHES.get()
    deg0 = metrics.TILE_MESH_DEGRADED.get()
    eight = _run_mesh(db, q, 8)
    one = _run_mesh(db, q, 1)
    if expect_mesh:
        assert metrics.TILE_MESH_DISPATCHES.get() - mesh0 >= 2, (
            f"mesh path did not engage (parity vacuous): {q}"
        )
        assert metrics.TILE_MESH_DEGRADED.get() == deg0, (
            f"mesh degraded instead of executing: {q}"
        )
    assert eight == single, (q, "8-device mesh != single-chip")
    assert one == eight, (q, "1-device mesh != 8-device mesh")


BASE_QUERIES = [
    # tags + bucket, every kernel family, null value column
    "SELECT host, time_bucket('10s', ts) AS tb, count(*) AS c, sum(v) AS s,"
    " avg(w) AS aw, min(v) AS mn, max(v) AS mx FROM t GROUP BY host, tb",
    # NULL tag group + null-gated count
    "SELECT region, count(w) AS cw, avg(v) AS av FROM t GROUP BY region",
    # scalar aggregate spanning all regions (cross-region sums share gids)
    "SELECT count(*) AS c, sum(v) AS s, min(w) AS mn FROM t",
    # filtered + bucket-only (time-major shapes stay correct via degrade
    # or mesh, whichever engages)
    "SELECT time_bucket('30s', ts) AS tb, max(v) AS mx FROM t"
    " WHERE v > 0 GROUP BY tb",
    # last_value (ts-ordered two-field merge is order-sensitive)
    "SELECT host, last_value(v) AS lv FROM t GROUP BY host",
]


@pytest.mark.parametrize("q", BASE_QUERIES)
def test_mesh_bit_parity(db, q):
    db.config.query.agg_strategy = "auto"
    # time-major / LAST shapes may legitimately decline the mesh (perm
    # sources); parity must hold regardless, so only the plainly
    # mesh-able shapes assert engagement
    expect_mesh = "time_bucket('30s'" not in q
    _assert_parity(db, q, expect_mesh=expect_mesh)


@pytest.mark.parametrize("strategy", ["sort", "hash"])
def test_mesh_parity_across_strategies(db, strategy):
    """The hash-slot tables merge by keyed scatter into a union table on
    the mesh; dense states merge via psum/pmin/pmax + ordered sums — both
    must be bit-identical to their single-chip twins."""
    db.config.query.agg_strategy = strategy
    try:
        _assert_parity(
            db,
            "SELECT host, region, count(*) AS c, sum(v) AS s, avg(w) AS aw,"
            " max(v) AS mx, min(w) AS mnw FROM t GROUP BY host, region",
        )
    finally:
        db.config.query.agg_strategy = "auto"


@pytest.mark.parametrize("topk", [True, False])
def test_mesh_parity_device_finalize(db, topk):
    """Device-finalize (ORDER BY/LIMIT/HAVING) runs ONCE post-merge on
    the first mesh device — on or off, results match the single chip."""
    db.config.query.device_topk = topk
    try:
        _assert_parity(
            db,
            "SELECT host, avg(v) AS av FROM t GROUP BY host"
            " HAVING avg(v) > -5.0 ORDER BY av DESC LIMIT 6",
        )
    finally:
        db.config.query.device_topk = True


def test_mesh_randomized_parity(db):
    """Seeded randomized suite over group keys / aggregates / filters /
    strategies: every draw must be bit-identical between 1-device and
    8-device mesh runs (and the single-chip path)."""
    rng = random.Random(20260804)
    aggs = [
        "count(*) AS c", "sum(v) AS s", "avg(v) AS av", "min(v) AS mn",
        "max(v) AS mx", "avg(w) AS aw", "count(w) AS cw", "sum(w) AS sw",
    ]
    groups = ["host", "region", "host, region"]
    filters = [
        "", " WHERE v > 10", " WHERE w < 40", " WHERE host != 'h3'",
    ]
    checked = 0
    for _ in range(6):
        g = rng.choice(groups)
        picked = rng.sample(aggs, rng.randint(2, 4))
        q = (
            f"SELECT {g}, {', '.join(picked)} FROM t"
            f"{rng.choice(filters)} GROUP BY {g}"
        )
        db.config.query.agg_strategy = rng.choice(["auto", "sort", "hash"])
        try:
            _assert_parity(db, q)
        finally:
            db.config.query.agg_strategy = "auto"
        checked += 1
    assert checked == 6


def test_mesh_collective_failure_degrades_to_single_chip(db):
    """The degrade contract: an error at the shard_map merge choke point
    (fault point `mesh.collective`) must fall back to the single-chip
    dispatch and return the CORRECT answer — never an error, never a
    wrong result."""
    q = "SELECT host, sum(v) AS s, count(*) AS c FROM t GROUP BY host"
    db.config.query.agg_strategy = "auto"
    expected = _run_mesh(db, q, 0)
    deg0 = metrics.TILE_MESH_DEGRADED.get()
    mesh0 = metrics.TILE_MESH_DISPATCHES.get()
    with fi.REGISTRY.armed(
        "mesh.collective", fail_times=1, error=RuntimeError
    ) as plan:
        got = _run_mesh(db, q, 8)
    assert plan.trips == 1, "fault point never fired: test is vacuous"
    assert got == expected, "degraded mesh query returned a wrong result"
    assert metrics.TILE_MESH_DEGRADED.get() == deg0 + 1
    assert metrics.TILE_MESH_DISPATCHES.get() == mesh0, (
        "a degraded dispatch must not count as a mesh dispatch"
    )
    # and the NEXT query (fault disarmed) takes the mesh again
    again = _run_mesh(db, q, 8)
    assert again == expected
    assert metrics.TILE_MESH_DISPATCHES.get() == mesh0 + 1


def test_mesh_devices_validation():
    from greptimedb_tpu.utils.config import Config
    from greptimedb_tpu.utils.errors import ConfigError

    cfg = Config()
    cfg.tile.mesh_devices = -1
    with pytest.raises(ConfigError):
        cfg.validate()
    cfg = Config()
    cfg.tile.mesh_devices = "all"
    with pytest.raises(ConfigError):
        cfg.validate()
    cfg = Config()
    # the test session pins an 8-device virtual mesh (conftest): more
    # than the runtime can see must be rejected at config time
    cfg.tile.mesh_devices = 9
    with pytest.raises(ConfigError):
        cfg.validate()
    cfg = Config()
    cfg.tile.mesh_devices = 8
    cfg.validate()  # exactly the available count is fine
    cfg.tile.mesh_devices = 0
    cfg.validate()


def test_mesh_off_is_default_and_off_safe(db):
    """tile.mesh_devices defaults to 0 and 0 means NOT A SINGLE mesh
    dispatch — today's path bit-for-bit."""
    from greptimedb_tpu.utils.config import TileConfig

    assert TileConfig().mesh_devices == 0
    mesh0 = metrics.TILE_MESH_DISPATCHES.get()
    db.config.tile.mesh_devices = 0
    db.sql_one("SELECT host, sum(v) AS s FROM t GROUP BY host")
    assert metrics.TILE_MESH_DISPATCHES.get() == mesh0


def test_region_chunks_colocated_on_mesh(tmp_path):
    """Chunk placement co-locates a region's planes with its mesh device
    slot (parallel/mesh.py region_device_index) when the mesh is on —
    checked on a FRESH database so the uploads happen under the mesh."""
    from greptimedb_tpu.parallel.mesh import region_device_index

    d = Database(data_home=str(tmp_path / "coloc"))
    try:
        d.config.query.disabled_passes = ("cold_host_serve", "host_fast_path")
        d.config.tile.mesh_devices = 8
        d.sql(
            "CREATE TABLE t (host STRING, ts TIMESTAMP TIME INDEX, v DOUBLE,"
            " PRIMARY KEY (host)) PARTITION BY HASH (host) PARTITIONS 3"
        )
        n = 3000
        d.insert_rows("t", pa.table({
            "host": pa.array([f"h{i % 30}" for i in range(n)]),
            "ts": pa.array(np.arange(n, dtype=np.int64) * 1000,
                           pa.timestamp("ms")),
            "v": pa.array(np.arange(n, dtype=np.float64)),
        }))
        d.sql("ADMIN flush_table('t')")
        d.sql_one("SELECT host, sum(v) AS s FROM t GROUP BY host")
        cache = d.query_engine.tile_cache
        checked = 0
        for rid, entry in cache._super.items():
            chunks = entry.cols.get("v")
            if not chunks:
                continue
            base = region_device_index(rid, 8)
            dev0 = next(iter(chunks[0].devices()))
            assert dev0 == cache.devices[base], (
                f"region {rid} first chunk on {dev0}, expected slot {base}"
            )
            checked += 1
        assert checked > 0, "no super-tile entries to check"
    finally:
        d.close()


# ---- packed f64 readback (the lastpoint single-fetch fix) -------------------


def test_pack_f64_bits_round_trip():
    """Device-side IEEE composition must be bit-exact for every normal
    value, signed zero and +/-inf; NaN canonicalizes; subnormals degrade
    to signed zero on denormal-flushing backends (XLA CPU)."""
    import jax.numpy as jnp

    from greptimedb_tpu.ops.aggregate import pack_f64_bits, unpack_f64_bits

    rng = np.random.default_rng(7)
    vals = np.concatenate([
        rng.standard_normal(2000)
        * 10 ** rng.integers(-307, 300, 2000).astype(np.float64),
        rng.integers(-(2**53), 2**53, 500).astype(np.float64),
        np.array([
            0.0, -0.0, np.inf, -np.inf, np.nan, 1.0, -1.0,
            2.2250738585072014e-308, 1.7976931348623157e308,
            -1.7976931348623157e308, 123456789.123456789,
        ]),
    ])
    out = unpack_f64_bits(np.asarray(pack_f64_bits(jnp.asarray(vals))))
    a, b = vals.view(np.uint64), out.view(np.uint64)
    finite_normal = (
        (np.abs(vals) >= 2.2250738585072014e-308) | (vals == 0)
    ) & np.isfinite(vals)
    assert (a[finite_normal] == b[finite_normal]).all()
    assert (a[np.isinf(vals)] == b[np.isinf(vals)]).all()
    assert np.isnan(out[np.isnan(vals)]).all()
    # signed-zero degrade for subnormals
    sub = unpack_f64_bits(
        np.asarray(pack_f64_bits(jnp.asarray(np.array([5e-324, -5e-324]))))
    )
    assert list(sub) == [0.0, 0.0] and list(np.signbit(sub)) == [False, True]


def test_pack_f64_bits_float32_exponent_range():
    """On the chip float64 is a float32 pair with float32's exponent range:
    every constant of the pack beyond it folds to inf or 0.  With x64 off
    the same folding happens here, so the special cases are held to it —
    exact 0.0 once came back as -inf because zero was told by `< DBL_MIN`,
    which folds to `< 0`."""
    import warnings

    import jax
    import jax.numpy as jnp

    from greptimedb_tpu.ops.aggregate import pack_f64_bits, unpack_f64_bits

    rng = np.random.default_rng(11)
    vals = np.concatenate([
        np.array([
            0.0, -0.0, 100.0, -100.0, -37.25, 0.5, 1.0, np.inf, -np.inf,
            np.nan, 1.17549435e-38, 3.4028235e38, -3.4028235e38, 1e-30,
        ]),
        rng.standard_normal(500) * 10.0 ** rng.integers(-37, 38, 500),
    ]).astype(np.float32)
    with jax.enable_x64(False), warnings.catch_warnings():
        warnings.simplefilter("ignore")  # float64 truncates: the point
        out = unpack_f64_bits(np.asarray(pack_f64_bits(jnp.asarray(vals))))
    want = vals.astype(np.float64)
    normal = ~np.isnan(vals) & (
        (np.abs(vals) >= 1.17549435e-38) | (vals == 0)
    )
    assert (out[normal].view(np.uint64) == want[normal].view(np.uint64)).all()
    assert np.isnan(out[np.isnan(vals)]).all()
    # under float32's smallest normal: signed zero, never garbage
    tiny = ~normal & ~np.isnan(vals)
    assert (out[tiny] == 0).all()
    assert (np.signbit(out[tiny]) == np.signbit(vals[tiny])).all()


def test_compact_readback_is_single_buffer(db):
    """The compact (device-finalize) result — lastpoint included — ships
    as ONE flat buffer: a single device_get of a single array (each extra
    array is its own device->host crossing)."""
    from greptimedb_tpu.parallel.tile_cache import TileExecutor

    fetched_parts = []
    orig = TileExecutor._fetch_result

    def spy(self, packed):
        out = orig(self, packed)
        fetched_parts.append(len(out))
        return out

    q = "SELECT host, last_value(v) AS lv FROM t GROUP BY host"
    db.sql_one(q)  # warm
    TileExecutor._fetch_result = spy
    try:
        d0 = metrics.TPU_DEVICE_DISPATCHES.get()
        f0 = metrics.TPU_DEVICE_FETCHES.get()
        db.sql_one(q)
        assert metrics.TPU_DEVICE_DISPATCHES.get() - d0 == 1
        assert metrics.TPU_DEVICE_FETCHES.get() - f0 == 1
        assert fetched_parts and fetched_parts[-1] == 1, (
            f"lastpoint fetched {fetched_parts} buffer(s), expected one"
        )
    finally:
        TileExecutor._fetch_result = orig


# ---- cpu-max-all-8 host-path routing ----------------------------------------


def test_wide_multihost_slice_leaves_host_path(tmp_path):
    """cpu-max-all-8 shape: a multi-host x many-column slice with WARM
    device planes routes to the tile dispatch; the single-host probe
    keeps the zero-round-trip host fast path."""
    d = Database(data_home=str(tmp_path / "hp"))
    try:
        d.config.query.disabled_passes = ("cold_host_serve",)
        cols = ", ".join(f"m{i} DOUBLE" for i in range(10))
        d.sql(
            f"CREATE TABLE c (host STRING, ts TIMESTAMP TIME INDEX, {cols},"
            " PRIMARY KEY (host)) WITH (append_mode = 'true')"
        )
        rng = np.random.default_rng(1)
        n_hosts, ticks = 20, 2000
        hosts = np.repeat([f"host_{i}" for i in range(n_hosts)], ticks)
        ts = np.tile(np.arange(ticks, dtype=np.int64) * 1000, n_hosts)
        tbl = {
            "host": pa.array(hosts),
            "ts": pa.array(ts, pa.timestamp("ms")),
        }
        for i in range(10):
            tbl[f"m{i}"] = pa.array(rng.uniform(0, 100, n_hosts * ticks))
        d.insert_rows("c", pa.table(tbl))
        d.sql("ADMIN flush_table('c')")
        # prewarm every numeric field after flush, as the benchmark's
        # set-up does: the gate keys on WARM planes — cold slices keep the
        # host path because an upload would cost more than the slice
        d.prewarm(tables=["c"])
        sel = ", ".join(f"max(m{i}) AS x{i}" for i in range(10))
        eight = ", ".join(f"'host_{i}'" for i in range(8))
        q8 = (
            f"SELECT time_bucket('1h', ts) AS tb, {sel} FROM c"
            f" WHERE host IN ({eight}) GROUP BY tb"
        )
        q1 = (
            f"SELECT time_bucket('1h', ts) AS tb, {sel} FROM c"
            f" WHERE host = 'host_0' GROUP BY tb"
        )
        d.sql_one(q8)  # builds + warms the device planes
        hfp0 = metrics.TILE_HOST_FAST_PATH.get()
        disp0 = metrics.TPU_DEVICE_DISPATCHES.get()
        t8 = d.sql_one(q8)
        assert metrics.TILE_HOST_FAST_PATH.get() == hfp0, (
            "wide multi-host slice stayed on the contention-sensitive "
            "host path despite warm planes"
        )
        assert metrics.TPU_DEVICE_DISPATCHES.get() > disp0
        hfp1 = metrics.TILE_HOST_FAST_PATH.get()
        t1 = d.sql_one(q1)
        assert metrics.TILE_HOST_FAST_PATH.get() == hfp1 + 1, (
            "single-host probe lost its host fast path"
        )
        # correctness: host-path single-host result == device-path slice
        d.config.query.backend = "cpu"
        try:
            t8c = d.sql_one(q8)
            t1c = d.sql_one(q1)
        finally:
            d.config.query.backend = "tpu"
        assert t8.to_pydict() == t8c.to_pydict()
        assert t1.to_pydict() == t1c.to_pydict()
    finally:
        d.close()
