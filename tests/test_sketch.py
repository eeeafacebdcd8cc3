"""Approx aggregate tests: HLL + UDDSketch (host math, device kernels,
SQL surface, multi-device merge).

Mirrors the reference's approx aggregate coverage
(reference common/function/src/aggrs/: hll, uddsketch state/merge/calc)
with the TPU two-step bar: per-shard partial sketches merged across an
8-device mesh must equal the single-pass sketch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from greptimedb_tpu.ops import sketch as sk


def test_hll_accuracy_and_merge():
    rng = np.random.default_rng(0)
    vals = pa.array(rng.integers(0, 10**12, 100_000))
    regs = sk.hll_build(sk.hash64(vals))
    est = sk.hll_estimate(regs)
    true = len(set(vals.to_pylist()))
    assert abs(est - true) / true < 0.05

    # merge == union
    a = pa.array(rng.integers(0, 50_000, 30_000))
    b = pa.array(rng.integers(25_000, 75_000, 30_000))
    u = sk.hll_estimate(sk.hll_merge(sk.hll_build(sk.hash64(a)), sk.hll_build(sk.hash64(b))))
    true_u = len(set(a.to_pylist()) | set(b.to_pylist()))
    assert abs(u - true_u) / true_u < 0.05


def test_hll_hash_determinism_and_types():
    s = pa.array(["a", "b", None, "a"])
    h1, h2 = sk.hash64(s), sk.hash64(s.dictionary_encode())
    np.testing.assert_array_equal(h1, h2)
    assert h1[0] == h1[3] and h1[2] == 0
    # -0.0 and 0.0 hash identically; int and timestamp hash via int64
    f = sk.hash64(pa.array([0.0, -0.0]))
    assert f[0] == f[1]
    sk.hash64(pa.array(np.arange(5), pa.int32()))
    sk.hash64(pa.array(np.arange(5), pa.timestamp("ms")))
    with pytest.raises(TypeError):
        sk.hash64(pa.array([[1]], pa.list_(pa.int64())))


def test_hll_serialize_roundtrip():
    regs = sk.hll_build(sk.hash64(pa.array([1, 2, 3])))
    data = sk.hll_serialize(regs)
    np.testing.assert_array_equal(sk.hll_deserialize(data), regs)
    with pytest.raises(ValueError):
        sk.hll_deserialize(b"nope")


def test_udd_quantiles_and_merge():
    rng = np.random.default_rng(1)
    data = rng.lognormal(3, 1.5, 100_000)
    u = sk.UddSketch(128, 0.01)
    u.add_array(data)
    for q in (0.1, 0.5, 0.9, 0.99):
        rel = abs(u.quantile(q) - np.quantile(data, q)) / np.quantile(data, q)
        assert rel < 0.15, (q, rel)
    # sharded merge == whole (same collapse sequence -> identical estimates)
    u1, u2 = sk.UddSketch(128, 0.01), sk.UddSketch(128, 0.01)
    u1.add_array(data[:50_000])
    u2.add_array(data[50_000:])
    u1.merge(u2)
    assert abs(u1.quantile(0.5) - u.quantile(0.5)) / u.quantile(0.5) < 0.1
    # serialize roundtrip preserves estimates
    u3 = sk.UddSketch.deserialize(u1.serialize())
    assert u3.quantile(0.5) == u1.quantile(0.5)


def test_udd_negatives_zero_nan():
    rng = np.random.default_rng(2)
    pos = rng.lognormal(1, 1, 1000)
    mix = np.concatenate([-pos, np.zeros(100), pos, [np.nan] * 7])
    u = sk.UddSketch(128, 0.01)
    u.add_array(mix)
    assert u.count() == 2100  # NaNs dropped
    assert u.quantile(0.5) == 0.0
    assert u.quantile(0.01) < 0 < u.quantile(0.99)
    empty = sk.UddSketch()
    assert np.isnan(empty.quantile(0.5))


def test_udd_collapse_keeps_bucket_bound():
    rng = np.random.default_rng(3)
    u = sk.UddSketch(16, 0.001)  # tiny bound forces collapses
    u.add_array(rng.lognormal(0, 4, 10_000))
    assert len(u.pos) + len(u.neg) <= 16
    assert u.gamma > (1 + 0.001) / (1 - 0.001)  # collapsed at least once


def test_device_hll_matches_host_grouped():
    rng = np.random.default_rng(4)
    n, g = 20_000, 5
    hashes = sk.hash64(pa.array(rng.integers(0, 3000, n)))
    gids = rng.integers(0, g, n).astype(np.int32)
    idx, rho = sk.hll_inputs(hashes, 12)
    dev = np.asarray(
        sk.segment_hll(jnp.asarray(idx), jnp.asarray(rho), jnp.asarray(gids), g, 1 << 12)
    )
    host = sk.hll_build_grouped(hashes, gids, g, 12)
    np.testing.assert_array_equal(dev.astype(np.uint8), host)


def test_device_mesh_sketch_merge():
    """Per-device partial sketches merged over the mesh == single pass:
    HLL via lax.pmax on registers, UDDSketch via psum on bucket counts —
    the sketch analogue of the state/merge aggregate split."""
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()
    n_dev = len(devs)
    assert n_dev >= 8, "conftest forces an 8-device CPU mesh"
    mesh = Mesh(np.array(devs), ("regions",))

    rng = np.random.default_rng(5)
    n = 4096 * n_dev
    raw = rng.integers(0, 2000, n)
    hashes = sk.hash64(pa.array(raw))
    idx, rho = sk.hll_inputs(hashes, 10)
    gamma = (1 + 0.01) / (1 - 0.01)
    vals = rng.lognormal(2, 1, n)
    bids = sk.udd_bucket_ids(vals, gamma, 1024)

    @jax.jit
    def run(idx, rho, bids):
        def step(idx, rho, bids):
            regs = sk.segment_hll(idx, rho, jnp.zeros(idx.shape, jnp.int32), 1, 1 << 10)
            regs = jax.lax.pmax(regs, "regions")
            counts = sk.segment_udd(
                bids, jnp.zeros(bids.shape, jnp.int32), jnp.ones(bids.shape, bool), 1, 1024
            )
            counts = jax.lax.psum(counts, "regions")
            return regs, counts

        return jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(P("regions"), P("regions"), P("regions")),
            out_specs=(P(), P()),
        )(idx, rho, bids)

    regs, counts = run(jnp.asarray(idx), jnp.asarray(rho), jnp.asarray(bids))
    est = sk.hll_estimate(np.asarray(regs)[0])
    true = len(np.unique(raw))
    assert abs(est - true) / true < 0.07
    p50 = sk.udd_quantile_dense(np.asarray(counts)[0], 0.5, gamma)
    assert abs(p50 - np.quantile(vals, 0.5)) / np.quantile(vals, 0.5) < 0.05


def test_sql_sketch_aggregates(tmp_path):
    from greptimedb_tpu.database import Database

    db = Database(data_home=str(tmp_path))
    db.sql(
        "CREATE TABLE t (host STRING, ts TIMESTAMP(3), v DOUBLE,"
        " TIME INDEX (ts), PRIMARY KEY (host))"
    )
    rng = np.random.default_rng(0)
    n = 9000
    db.insert_rows(
        "t",
        pa.record_batch(
            {
                "host": pa.array([f"h{i % 3}" for i in range(n)]),
                "ts": pa.array(np.arange(n, dtype=np.int64), pa.timestamp("ms")),
                "v": pa.array(np.floor(rng.uniform(0, 500, n))),
            }
        ),
    )
    t = db.sql_one("SELECT host, hll_count(hll(v)) AS c FROM t GROUP BY host ORDER BY host")
    assert t["host"].to_pylist() == ["h0", "h1", "h2"]
    for c in t["c"].to_pylist():
        assert abs(c - 500) / 500 < 0.06

    t = db.sql_one("SELECT hll_count(hll(host)) AS c FROM t")
    assert t["c"].to_pylist() == [3]

    t = db.sql_one(
        "SELECT host, uddsketch_calc(0.5, uddsketch_state(128, 0.01, v)) AS p50"
        " FROM t GROUP BY host ORDER BY host"
    )
    for p in t["p50"].to_pylist():
        assert abs(p - 250) / 250 < 0.1

    # two-step by hand: states from two halves, merged then counted
    db.sql("CREATE TABLE states (id STRING, ts TIMESTAMP(3), s BINARY, TIME INDEX (ts), PRIMARY KEY (id))")
    h1 = db.sql_one("SELECT hll(v) AS s FROM t WHERE ts < 4500")["s"].to_pylist()[0]
    h2 = db.sql_one("SELECT hll(v) AS s FROM t WHERE ts >= 4500")["s"].to_pylist()[0]
    merged = sk.hll_merge(sk.hll_deserialize(h1), sk.hll_deserialize(h2))
    est = sk.hll_estimate(merged)
    assert abs(est - 500) / 500 < 0.06
    db.close()
