"""Kernel tests: tiles, predicate masks, segmented aggregates, rate.

Every kernel is checked against a straightforward numpy reference — the
TPU==CPU result-equality bar from SURVEY.md section 7 step 3.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from greptimedb_tpu.datatypes import ColumnSchema, ConcreteDataType, Schema, SemanticType
from greptimedb_tpu.ops.aggregate import (
    AggState,
    finalize,
    group_ids,
    merge_states,
    segment_aggregate,
    time_bucket,
)
from greptimedb_tpu.ops.filter import compile_predicate
from greptimedb_tpu.ops.rate import (
    RangeSpec,
    extrapolated_rate,
    over_time,
    range_windows,
    sorted_run_bounds,
    strip_counter_resets,
)
from greptimedb_tpu.ops.tiles import padded_size, tiles_from_table


def test_padded_size_quantization():
    assert padded_size(0) == 1024
    assert padded_size(1) == 1
    assert padded_size(1000) == 1024
    assert padded_size(1 << 20) == 1 << 20
    assert padded_size((1 << 20) + 1, 1 << 20) == 2 << 20
    # Only O(log) distinct shapes below one tile.
    sizes = {padded_size(n) for n in range(1, 5000)}
    assert len(sizes) <= 14


def test_tiles_from_table_encoding():
    t = pa.table(
        {
            "host": pa.array(["a", "b", "a", None]),
            "ts": pa.array([1, 2, 3, 4], pa.timestamp("ms")),
            "v": pa.array([1.0, None, 3.0, 4.0]),
        }
    )
    batch = tiles_from_table(t, tile_rows=8)
    assert batch.num_rows == 4
    assert batch.padded_rows == 4
    assert batch.dicts["host"] == ["a", "b", None]
    np.testing.assert_array_equal(np.asarray(batch.columns["host"]), [0, 1, 0, 2])
    np.testing.assert_array_equal(np.asarray(batch.columns["ts"]), [1, 2, 3, 4])
    # v nulls: mask False at index 1
    assert not bool(batch.nulls["v"][1])
    assert bool(batch.nulls["v"][0])
    assert bool(batch.valid[3])


def test_tiles_pinned_dictionary():
    t = pa.table({"host": ["x", "y", "z"]})
    batch = tiles_from_table(t, dicts={"host": {"y": 0, "x": 1}})
    np.testing.assert_array_equal(np.asarray(batch.columns["host"])[: batch.num_rows], [1, 0, -1])
    assert batch.dicts["host"] == ["y", "x"]


def test_compile_predicate_ops():
    t = pa.table({"host": ["a", "b", "c", "a"], "v": [1.0, 2.0, 3.0, 4.0]})
    batch = tiles_from_table(t)
    mask_fn = compile_predicate(batch, [("host", "in", ["a", "c"]), ("v", ">", 1.5)])
    mask = np.asarray(mask_fn(batch.columns, batch.valid))[: batch.num_rows]
    np.testing.assert_array_equal(mask, [False, False, True, True])
    # String literal not present in batch matches nothing.
    mask_fn = compile_predicate(batch, [("host", "=", "zzz")])
    assert not np.asarray(mask_fn(batch.columns, batch.valid)).any()
    # != on missing literal matches everything valid.
    mask_fn = compile_predicate(batch, [("host", "!=", "zzz")])
    assert np.asarray(mask_fn(batch.columns, batch.valid))[:4].all()


def _np_groupby(hosts, buckets, vals, mask):
    out = {}
    for h, b, v, m in zip(hosts, buckets, vals, mask):
        if not m:
            continue
        key = (h, b)
        out.setdefault(key, []).append(v)
    return out


def test_segment_aggregate_matches_numpy():
    rng = np.random.default_rng(42)
    n, n_hosts, n_buckets = 5000, 7, 12
    hosts = rng.integers(0, n_hosts, n)
    ts = rng.integers(0, n_buckets * 1000, n).astype(np.int64)
    vals = rng.normal(50, 20, n)
    mask = rng.random(n) > 0.3

    buckets = time_bucket(jnp.asarray(ts), 0, 1000)
    gids = group_ids(
        [(jnp.asarray(hosts), n_hosts), (buckets, n_buckets)],
        jnp.asarray(mask),
        n_hosts * n_buckets,
    )
    state = segment_aggregate(
        jnp.asarray(vals),
        gids,
        n_hosts * n_buckets,
        aggs=("sum", "count", "min", "max", "avg"),
        mask=jnp.asarray(mask),
        acc_dtype=jnp.float64,
    )
    out = finalize(state, ("sum", "count", "min", "max", "avg"))

    ref = _np_groupby(hosts, ts // 1000, vals, mask)
    for (h, b), vs in ref.items():
        g = h * n_buckets + b
        assert out["count"][g] == len(vs)
        np.testing.assert_allclose(out["sum"][g], np.sum(vs), rtol=1e-12)
        np.testing.assert_allclose(out["avg"][g], np.mean(vs), rtol=1e-12)
        np.testing.assert_allclose(out["min"][g], np.min(vs))
        np.testing.assert_allclose(out["max"][g], np.max(vs))
    # Empty groups flagged.
    empty = [g for g in range(n_hosts * n_buckets) if (g // n_buckets, g % n_buckets) not in ref]
    for g in empty[:5]:
        assert not bool(out["non_empty"][g])


def test_merge_states_equals_single_pass():
    rng = np.random.default_rng(0)
    n, groups = 2000, 10
    gids_np = rng.integers(0, groups, n)
    vals = rng.normal(size=n)
    mask = np.ones(n, dtype=bool)
    full = segment_aggregate(
        jnp.asarray(vals), jnp.asarray(gids_np, dtype=jnp.int32), groups,
        ("sum", "count", "min", "max"), jnp.asarray(mask), acc_dtype=jnp.float64,
    )
    half1 = segment_aggregate(
        jnp.asarray(vals[: n // 2]), jnp.asarray(gids_np[: n // 2], dtype=jnp.int32), groups,
        ("sum", "count", "min", "max"), jnp.asarray(mask[: n // 2]), acc_dtype=jnp.float64,
    )
    half2 = segment_aggregate(
        jnp.asarray(vals[n // 2 :]), jnp.asarray(gids_np[n // 2 :], dtype=jnp.int32), groups,
        ("sum", "count", "min", "max"), jnp.asarray(mask[n // 2 :]), acc_dtype=jnp.float64,
    )
    merged = merge_states(half1, half2)
    np.testing.assert_allclose(np.asarray(merged.sums), np.asarray(full.sums), rtol=1e-12)
    np.testing.assert_array_equal(np.asarray(merged.counts), np.asarray(full.counts))
    np.testing.assert_array_equal(np.asarray(merged.mins), np.asarray(full.mins))
    np.testing.assert_array_equal(np.asarray(merged.maxs), np.asarray(full.maxs))


def test_last_value_aggregation():
    # lastpoint: value at max ts per group.
    ts = jnp.asarray(np.array([10, 30, 20, 5, 50], dtype=np.int64))
    vals = jnp.asarray(np.array([1.0, 3.0, 2.0, 9.0, 5.0]))
    gids = jnp.asarray(np.array([0, 0, 0, 1, 1], dtype=np.int32))
    state = segment_aggregate(vals, gids, 2, ("last",), jnp.ones(5, dtype=bool), ts=ts, acc_dtype=jnp.float64)
    out = finalize(state, ("last",))
    np.testing.assert_array_equal(np.asarray(out["last"]), [3.0, 5.0])
    np.testing.assert_array_equal(np.asarray(out["last_ts"]), [30, 50])


def test_group_ids_overflow_slot():
    comp = jnp.asarray(np.array([0, 5, -1, 2], dtype=np.int32))
    mask = jnp.asarray(np.array([True, True, True, False]))
    gids = group_ids([(comp, 4)], mask, 4)
    np.testing.assert_array_equal(np.asarray(gids), [0, 4, 4, 4])


# ---- rate kernels ----------------------------------------------------------


def test_strip_counter_resets():
    series = jnp.asarray(np.array([0, 0, 0, 1, 1], dtype=np.int32))
    vals = jnp.asarray(np.array([5.0, 2.0, 4.0, 10.0, 1.0]))  # resets at idx1, idx4
    valid = jnp.ones(5, dtype=bool)
    adj = np.asarray(strip_counter_resets(series, vals, valid))
    np.testing.assert_allclose(adj, [5.0, 7.0, 9.0, 10.0, 11.0])


def test_range_windows_and_rate_regular_grid():
    # One series, perfectly regular 10s scrape, counter increasing 1/s.
    step = 60_000
    spec = RangeSpec(start=300_000, end=600_000, step=step, range_=300_000)
    ts_np = np.arange(0, 600_001, 10_000, dtype=np.int64)
    vals_np = ts_np / 1000.0  # 1 unit per second
    n = len(ts_np)
    series = jnp.zeros(n, dtype=jnp.int32)
    valid = jnp.ones(n, dtype=bool)
    adj = strip_counter_resets(series, jnp.asarray(vals_np), valid)
    stats = range_windows(series, jnp.asarray(ts_np), adj, valid, spec, sorted_run_bounds(series, 1))
    rate, defined = extrapolated_rate(stats, spec, "rate")
    rate = np.asarray(rate)[np.asarray(defined)]
    # Perfect 1/s counter -> rate 1.0 everywhere (extrapolation exact on grid).
    np.testing.assert_allclose(rate, 1.0, rtol=1e-6)

    inc, defined = extrapolated_rate(stats, spec, "increase")
    np.testing.assert_allclose(np.asarray(inc)[np.asarray(defined)], 300.0, rtol=1e-6)


def test_over_time_functions():
    spec = RangeSpec(start=100, end=100, step=100, range_=100)  # one window (0,100]
    series = jnp.zeros(4, dtype=jnp.int32)
    ts = jnp.asarray(np.array([10, 40, 70, 100], dtype=np.int64))
    vals = jnp.asarray(np.array([1.0, 5.0, 3.0, 7.0]))
    valid = jnp.ones(4, dtype=bool)
    stats = range_windows(series, ts, vals, valid, spec, sorted_run_bounds(series, 1))
    for func, want in [
        ("avg_over_time", 4.0),
        ("sum_over_time", 16.0),
        ("min_over_time", 1.0),
        ("max_over_time", 7.0),
        ("count_over_time", 4.0),
        ("last_over_time", 7.0),
    ]:
        v, d = over_time(stats, func)
        assert bool(d[0])
        np.testing.assert_allclose(float(v[0]), want)


def test_range_windows_overlapping_windows():
    # step < range: samples must appear in multiple windows.
    spec = RangeSpec(start=100, end=300, step=100, range_=200)
    series = jnp.zeros(3, dtype=jnp.int32)
    ts = jnp.asarray(np.array([50, 150, 250], dtype=np.int64))
    vals = jnp.asarray(np.array([1.0, 2.0, 3.0]))
    valid = jnp.ones(3, dtype=bool)
    stats = range_windows(series, ts, vals, valid, spec, sorted_run_bounds(series, 1))
    counts = np.asarray(stats.count)
    # windows: (−100,100]→{50}, (0,200]→{50,150}, (100,300]→{150,250}
    np.testing.assert_array_equal(counts, [1, 2, 2])
    np.testing.assert_allclose(np.asarray(stats.sum), [1.0, 3.0, 5.0])


def test_segment_aggregate_under_jit_and_masked_all():
    @jax.jit
    def run(vals, gids, mask):
        return segment_aggregate(vals, gids, 4, ("sum", "count"), mask, acc_dtype=jnp.float64)

    vals = jnp.asarray(np.array([1.0, 2.0, 3.0]))
    gids = jnp.asarray(np.array([4, 4, 4], dtype=np.int32))  # all overflow
    mask = jnp.zeros(3, dtype=bool)
    state = run(vals, gids, mask)
    assert np.asarray(state.counts).sum() == 0
    assert np.asarray(state.sums).sum() == 0


def _np_segment(vals, gids, mask, num_groups):
    sums = np.zeros(num_groups)
    counts = np.zeros(num_groups, dtype=np.int64)
    mins = np.full(num_groups, np.inf)
    maxs = np.full(num_groups, -np.inf)
    for v, g, m in zip(vals, gids, mask):
        if not m or not (0 <= g < num_groups):
            continue
        sums[g] += v
        counts[g] += 1
        mins[g] = min(mins[g], v)
        maxs[g] = max(maxs[g], v)
    return sums, counts, mins, maxs


@pytest.mark.parametrize("layout", ["sorted", "unsorted", "wide_span"])
def test_segment_aggregate_blocked_fast_path(layout):
    """Large-n inputs route through the runtime lax.cond guard: sorted ids
    with narrow per-block span take the blocked kernel; unsorted or
    wide-span ids must fall back to scatter.  All layouts must agree with
    the numpy reference (fast/scatter equivalence)."""
    from greptimedb_tpu.ops import aggregate as agg

    rng = np.random.default_rng(7)
    n = agg._FAST_MIN_ROWS + 1234  # odd tail exercises the tail scatter
    num_groups = 512
    if layout == "sorted":
        gids = np.sort(rng.integers(0, num_groups, n)).astype(np.int32)
    elif layout == "unsorted":
        gids = rng.integers(0, num_groups, n).astype(np.int32)
    else:  # sorted overall but one block spans > BLOCK_SPAN ids
        gids = np.sort(rng.integers(0, num_groups, n)).astype(np.int32)
        assert gids[agg.BLOCK_ROWS - 1] - gids[0] >= agg.BLOCK_SPAN
    vals = rng.normal(10, 5, n)
    mask = rng.random(n) > 0.2

    state = segment_aggregate(
        jnp.asarray(vals),
        jnp.asarray(gids),
        num_groups,
        ("sum", "count", "min", "max"),
        mask=jnp.asarray(mask),
        acc_dtype=jnp.float64,
    )
    sums, counts, mins, maxs = _np_segment(vals, gids, mask, num_groups)
    np.testing.assert_array_equal(np.asarray(state.counts), counts)
    np.testing.assert_allclose(np.asarray(state.sums), sums, rtol=1e-9)
    nz = counts > 0
    np.testing.assert_allclose(np.asarray(state.mins)[nz], mins[nz])
    np.testing.assert_allclose(np.asarray(state.maxs)[nz], maxs[nz])


@pytest.mark.parametrize("layout", ["sorted", "unsorted"])
def test_limb_segment_sums_matches_numpy(layout):
    """MXU limb kernel (fast one-hot matmul path AND the scatter fallback
    over reconstructed values) vs numpy: sums within the quantization
    bound (~1e-9 relative for same-magnitude data), counts/presence
    exact."""
    from greptimedb_tpu.ops import aggregate as agg

    rng = np.random.default_rng(5)
    n = agg.BLOCK_ROWS * 32
    num_groups = 256
    if layout == "sorted":
        gids = np.sort(rng.integers(0, num_groups, n)).astype(np.int32)
    else:
        gids = rng.integers(0, num_groups, n).astype(np.int32)
    mask = rng.random(n) > 0.2
    v0 = rng.normal(50, 30, n)
    v1 = rng.uniform(-1e6, 1e6, n)
    nn1 = rng.random(n) > 0.1  # v1 nullable: null rows decode to 0.0
    v1 = np.where(nn1, v1, 0.0)

    limb0 = agg.quantize_limbs(jnp.asarray(v0))
    limb1 = agg.quantize_limbs(jnp.asarray(v1))
    sums, errs, counts, presence = jax.jit(
        lambda a, b, g, m, c1: agg.limb_segment_sums(
            [a, b], g, m, num_groups, span=64, count01=[None, c1]
        )
    )(limb0, limb1, jnp.asarray(gids), jnp.asarray(mask), jnp.asarray(nn1))

    s0, c0, _mn, _mx = _np_segment(v0, gids, mask, num_groups)
    s1, c1n, _mn, _mx = _np_segment(v1, gids, mask & nn1, num_groups)
    # null rows of v1 hold value 0.0 so they don't move the sum
    np.testing.assert_array_equal(np.asarray(presence), c0)
    np.testing.assert_array_equal(np.asarray(counts[0]), c0)
    np.testing.assert_array_equal(np.asarray(counts[1]), c1n)
    np.testing.assert_allclose(np.asarray(sums[0]), s0, rtol=1e-7, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sums[1]), s1, rtol=1e-7, atol=1e-1)
    # the error bound must actually bound the observed error
    assert np.all(np.abs(np.asarray(sums[0]) - s0) <= np.asarray(errs[0]) + 1e-9)
    assert np.all(np.abs(np.asarray(sums[1]) - s1) <= np.asarray(errs[1]) + 1e-6)


def test_limb_sums_nonfinite_confined():
    """One inf/NaN row must not poison other groups' sums (scale=inf would
    have NaN'd every group; the guard saturates inf to 1e308 and zeroes
    NaN, so only the affected group goes huge)."""
    from greptimedb_tpu.ops import aggregate as agg

    n = agg.BLOCK_ROWS * 16
    rng = np.random.default_rng(2)
    v = rng.uniform(0, 100, n)
    v[5] = np.inf
    v[7] = np.nan
    gids = np.sort(np.arange(n) % 8).astype(np.int32)  # 8 groups, sorted
    limbs = agg.quantize_limbs(jnp.asarray(v))
    sums, _e, _c, _p = jax.jit(
        lambda L, g, m: agg.limb_segment_sums([L], g, m, 8, 16)
    )(limbs, jnp.asarray(gids), jnp.ones(n, dtype=bool))
    out = np.asarray(sums[0])
    assert np.all(np.isfinite(out))
    # groups 1..7 unaffected (rows 5 and 7 both land in group 0)
    gt = np.zeros(8)
    np.add.at(gt, gids, np.nan_to_num(v, nan=0.0, posinf=0.0))
    np.testing.assert_allclose(out[1:], gt[1:], rtol=1e-6)
    assert out[0] > 1e300  # inf saturated, dominates its own group


def test_quantize_limbs_roundtrip_precision():
    """v-hat reconstructed from limbs deviates from v by <= s/2 per row
    (the documented quantization bound); exact for integer-valued data."""
    from greptimedb_tpu.ops import aggregate as agg

    rng = np.random.default_rng(9)
    n = agg.BLOCK_ROWS * 2
    v = rng.uniform(-100, 100, n)
    limbs, scale = agg.quantize_limbs(jnp.asarray(v))
    q = np.zeros((n // agg.BLOCK_ROWS, agg.BLOCK_ROWS), np.int64)
    ln = np.asarray(limbs.astype(jnp.float32)).astype(np.int64)
    for j in range(agg.N_LIMBS):
        q += ln[:, :, j] << (8 * j)
    vhat = (q - (1 << agg._LIMB_Q_EXP)) * np.asarray(scale)[:, None]
    s = np.asarray(scale)
    assert np.max(np.abs(vhat - v.reshape(vhat.shape)) / s[:, None]) <= 0.5 + 1e-9

    vi = rng.integers(-(1 << 20), 1 << 20, n).astype(np.float64)
    limbs, scale = agg.quantize_limbs(jnp.asarray(vi))
    ln = np.asarray(limbs.astype(jnp.float32)).astype(np.int64)
    q = np.zeros((n // agg.BLOCK_ROWS, agg.BLOCK_ROWS), np.int64)
    for j in range(agg.N_LIMBS):
        q += ln[:, :, j] << (8 * j)
    vhat = (q - (1 << agg._LIMB_Q_EXP)) * np.asarray(scale)[:, None]
    np.testing.assert_array_equal(vhat, vi.reshape(vhat.shape))


def test_segment_aggregate_blocked_narrow_span_engages():
    """A layout engineered to pass every fast-path guard (dense sorted ids,
    span << BLOCK_SPAN) still matches numpy — this is the configuration the
    blocked kernel actually executes."""
    from greptimedb_tpu.ops import aggregate as agg

    rng = np.random.default_rng(11)
    n = agg._FAST_MIN_ROWS
    num_groups = n // agg.BLOCK_ROWS * 2  # ~2 groups per block
    gids = np.sort(rng.integers(0, num_groups, n)).astype(np.int32)
    vals = rng.normal(0, 1, n)
    mask = np.ones(n, dtype=bool)

    state = segment_aggregate(
        jnp.asarray(vals), jnp.asarray(gids), num_groups,
        ("sum", "count", "min", "max"), mask=jnp.asarray(mask),
        acc_dtype=jnp.float64,
    )
    sums, counts, mins, maxs = _np_segment(vals, gids, mask, num_groups)
    np.testing.assert_array_equal(np.asarray(state.counts), counts)
    np.testing.assert_allclose(np.asarray(state.sums), sums, rtol=1e-9)
    np.testing.assert_allclose(np.asarray(state.mins), mins)
    np.testing.assert_allclose(np.asarray(state.maxs), maxs)


def test_raw_group_ids_empty_components():
    """Ungrouped aggregate (no GROUP BY, no bucket): every row lands in the
    single global group."""
    from greptimedb_tpu.ops.aggregate import raw_group_ids

    gid, in_range = raw_group_ids([], shape=(5,))
    np.testing.assert_array_equal(np.asarray(gid), np.zeros(5, dtype=np.int32))
    assert bool(jnp.all(in_range))
    mask = jnp.asarray(np.array([True, True, False, True, True]))
    legacy = group_ids([], mask, 1)
    np.testing.assert_array_equal(np.asarray(legacy), [0, 0, 1, 0, 0])


@pytest.mark.parametrize("layout", ["clustered", "unsorted"])
def test_segment_aggregate_blocked_last(layout):
    """last_value at large n: clustered layouts take the two-pass blocked
    LAST kernel, unsorted ids its scatter fallback — both must agree with
    a numpy last-by-(ts, row-order) reference (ties -> later row, the
    engine's last-write-wins)."""
    from greptimedb_tpu.ops import aggregate as agg

    rng = np.random.default_rng(13)
    n = agg._FAST_MIN_ROWS + 777
    num_groups = 64
    if layout == "clustered":
        gids = np.sort(rng.integers(0, num_groups, n)).astype(np.int32)
    else:
        gids = rng.integers(0, num_groups, n).astype(np.int32)
    ts = rng.integers(0, 1000, n).astype(np.int64)  # duplicate ts exercise ties
    vals = rng.normal(10, 5, n)
    mask = rng.random(n) > 0.15

    state = segment_aggregate(
        jnp.asarray(vals), jnp.asarray(gids), num_groups, ("last", "count"),
        mask=jnp.asarray(mask), ts=jnp.asarray(ts), acc_dtype=jnp.float64,
    )
    last_ts = np.full(num_groups, np.iinfo(np.int64).min)
    last_val = np.full(num_groups, -np.inf)
    counts = np.zeros(num_groups, np.int64)
    for g, t, v, m in zip(gids, ts, vals, mask):
        if not m:
            continue
        counts[g] += 1
        if t >= last_ts[g]:
            last_ts[g], last_val[g] = t, v
    nz = counts > 0
    np.testing.assert_array_equal(np.asarray(state.counts), counts)
    np.testing.assert_array_equal(np.asarray(state.last_ts)[nz], last_ts[nz])
    np.testing.assert_allclose(np.asarray(state.last_val)[nz], last_val[nz])


def test_fold_series_states_folds_and_places():
    """Stage 2 of a series plan: a [S x bucket] state folded through its
    series -> group table must match numpy's per-group reductions, the
    padding slots (-1) dropped; LAST states of a one-to-one table are
    placed, each series in its group's row."""
    from greptimedb_tpu.ops.aggregate import AggState, fold_series_states

    rng = np.random.default_rng(5)
    s, b, g = 12, 5, 4
    group = np.array([0, 2, 2, 1, 3, 0, 2, -1, 1, 3, -1, 0], np.int32)
    sums = rng.normal(size=s * b)
    counts = rng.integers(0, 9, s * b).astype(np.int64)
    mins = rng.normal(size=s * b)
    maxs = rng.normal(size=s * b)
    st = AggState(
        sums=jnp.asarray(sums), counts=jnp.asarray(counts),
        mins=jnp.asarray(mins), maxs=jnp.asarray(maxs),
    )
    out = fold_series_states(st, jnp.asarray(group), g, b)
    for k in range(g):
        rows = group == k
        np.testing.assert_allclose(
            np.asarray(out.sums).reshape(g, b)[k], sums.reshape(s, b)[rows].sum(0)
        )
        np.testing.assert_array_equal(
            np.asarray(out.counts).reshape(g, b)[k], counts.reshape(s, b)[rows].sum(0)
        )
        np.testing.assert_allclose(
            np.asarray(out.mins).reshape(g, b)[k], mins.reshape(s, b)[rows].min(0)
        )
        np.testing.assert_allclose(
            np.asarray(out.maxs).reshape(g, b)[k], maxs.reshape(s, b)[rows].max(0)
        )
    # a permutation: series k is group perm[k]; two padding slots
    perm = np.array([3, 0, 2, 1, -1, -1], np.int32)
    last_ts = rng.integers(0, 1000, 6 * b).astype(np.int64)
    last_val = rng.normal(size=6 * b)
    out = fold_series_states(
        AggState(last_ts=jnp.asarray(last_ts), last_val=jnp.asarray(last_val)),
        jnp.asarray(perm), g, b,
    )
    for k, grp in enumerate(perm[:4]):
        np.testing.assert_array_equal(
            np.asarray(out.last_ts).reshape(g, b)[grp], last_ts.reshape(6, b)[k]
        )
        np.testing.assert_array_equal(
            np.asarray(out.last_val).reshape(g, b)[grp], last_val.reshape(6, b)[k]
        )


def test_compute_partial_states_hierarchical_matches_direct():
    """A plan grouped by a non-prefix pk subset (stage 1 by the sorted
    rows' series over the full pk, folded down) must produce the same
    states as the direct plan, from the series' row bounds (planes) and
    from each row's series (a memtable tail) alike."""
    from greptimedb_tpu.parallel.executor import (
        SERIES_GROUP,
        SERIES_MARKS,
        SERIES_ROWS,
        DistGroupByPlan,
        compute_partial_states,
    )

    rng = np.random.default_rng(3)
    n = 4096
    a = rng.integers(0, 4, n).astype(np.int32)
    b = rng.integers(0, 8, n).astype(np.int32)
    ts = rng.integers(0, 16_000, n).astype(np.int64)
    order = np.lexsort((ts, b, a))  # (pk, ts) sorted, as the planes are
    a, b, ts = a[order], b[order], ts[order]
    v = rng.normal(10, 2, n)
    cols = {
        "a": jnp.asarray(a), "b": jnp.asarray(b),
        "ts": jnp.asarray(ts), "v": jnp.asarray(v),
    }
    valid = jnp.asarray(np.ones(n, bool))
    common = dict(
        bucket_col="ts", bucket_origin=0, bucket_interval=1000, n_buckets=16,
        agg_specs=(("avg", "v"), ("max", "v")), acc_dtype="float64",
    )
    direct = DistGroupByPlan(group_tags=("b",), tag_cards=(8,), **common)
    by_series = DistGroupByPlan(
        group_tags=("b",), tag_cards=(8,), series_slots=32, **common,
    )
    key = a * 8 + b
    starts = np.flatnonzero(np.diff(key, prepend=-1))
    marks = np.full(32, n, np.int32)
    marks[: len(starts)] = starts
    group = np.full(32, -1, np.int32)
    group[: len(starts)] = b[starts]
    planes = {**cols, SERIES_MARKS: jnp.asarray(marks), SERIES_GROUP: jnp.asarray(group)}
    rows = {
        **cols, SERIES_ROWS: jnp.asarray(np.cumsum(np.diff(key, prepend=-1) != 0) - 1),
        SERIES_GROUP: jnp.asarray(group),
    }
    s1 = compute_partial_states(direct, cols, valid, {})
    for series in (planes, rows):
        s2 = compute_partial_states(by_series, series, valid, {})
        for k in s1:
            if s1[k].sums is not None:
                np.testing.assert_allclose(
                    np.asarray(s1[k].sums), np.asarray(s2[k].sums), rtol=1e-12
                )
            np.testing.assert_array_equal(np.asarray(s1[k].counts), np.asarray(s2[k].counts))
            if s1[k].maxs is not None:
                np.testing.assert_allclose(np.asarray(s1[k].maxs), np.asarray(s2[k].maxs))


def test_compute_partial_states_time_major_perm():
    """Time-major: passing a ts-ascending perm must leave results identical
    (aggregation is order-independent) while making gids sorted."""
    from greptimedb_tpu.parallel.executor import DistGroupByPlan, compute_partial_states

    rng = np.random.default_rng(9)
    n = 2048
    ts = rng.permutation(np.arange(n)).astype(np.int64)
    v = rng.normal(size=n)
    cols = {"ts": jnp.asarray(ts), "v": jnp.asarray(v)}
    valid = jnp.asarray(np.ones(n, bool))
    plan = DistGroupByPlan(
        group_tags=(), tag_cards=(), bucket_col="ts", bucket_origin=0,
        bucket_interval=128, n_buckets=16, agg_specs=(("sum", "v"),),
        acc_dtype="float64", time_major=True,
    )
    perm = jnp.asarray(np.argsort(ts).astype(np.int32))
    s_perm = compute_partial_states(plan, cols, valid, {}, perm=perm)
    s_plain = compute_partial_states(plan, cols, valid, {}, perm=None)
    np.testing.assert_allclose(
        np.asarray(s_perm["v"].sums), np.asarray(s_plain["v"].sums), rtol=1e-12
    )
