"""Group ids over a source's own series ordinals (a series plan,
`DistGroupByPlan.series_slots`, whose series are the runs of the grouped
tag): on a plane whose leading tag's codes lie apart as `PARTITION BY HASH
(hostname) PARTITIONS 4` leaves them, the blocked kernels' span guard fails
over code gids and holds over ordinal gids (`ops/aggregate.series_rows`),
and the states folded to the codes are the code-space states, field by
field.
"""

import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from greptimedb_tpu.ops.aggregate import (
    BLOCK_ROWS,
    merge_states,
    series_rows,
    time_bucket,
)
from greptimedb_tpu.parallel.executor import (
    SERIES_GROUP,
    SERIES_MARKS,
    DistGroupByPlan,
    compute_partial_states,
)

CARD, BUCKETS, SPAN, HOUR = 4096, 14, 32, 3_600_000
PER_SERIES = 4320  # a 12 h window of 10 s ticks
FIELDS = ("sums", "counts", "mins", "maxs", "last_ts", "last_val")


def region_codes(region: int, hosts: int = 4000) -> np.ndarray:
    """The dictionary codes (ranks among the sorted names) of the hosts that
    crc32 mod 4 puts in `region`, as `HashPartitionRule` hashes them."""
    names = sorted(f"host_{i}" for i in range(hosts))
    return np.array(
        [c for c, n in enumerate(names) if zlib.crc32(repr(n).encode()) % 4 == region],
        np.int32,
    )


def plane(codes, per=PER_SERIES, pad_to=None, seed=0, pad_code=0):
    """A (hostname, ts)-sorted source: `per` ticks a series of `codes`, in
    that order, the tail padded invalid with `pad_code` as a window tile's."""
    rng = np.random.default_rng(seed)
    n = len(codes) * per
    pad = pad_to or -(-n // (1 << 16)) * (1 << 16)
    code = np.full(pad, pad_code, np.int32)
    code[:n] = np.repeat(np.asarray(codes, np.int32), per)
    ts = np.zeros(pad, np.int64)
    ts[:n] = np.tile(np.arange(per, dtype=np.int64) * 10_000, len(codes))
    valid = np.zeros(pad, bool)
    valid[:n] = True
    user, idle = np.zeros(pad), np.zeros(pad)
    user[:n], idle[:n] = rng.uniform(0, 100, n), rng.uniform(0, 100, n)
    cols = {"hostname": code, "ts": ts, "usage_user": user, "usage_idle": idle}
    return {k: jnp.asarray(v) for k, v in cols.items()}, jnp.asarray(valid)


def plan(aggs, **kw):
    base = dict(
        group_tags=("hostname",), tag_cards=(CARD,), bucket_col="ts", bucket_origin=0,
        bucket_interval=HOUR, n_buckets=16, agg_specs=tuple(aggs), acc_dtype="limb",
        ts_col="ts" if any(f == "last_value" for f, _ in aggs) else None,
        block_span=SPAN,
    )
    base.update(kw)
    return DistGroupByPlan(**base)


SLOTS = 64  # padded series a source


def with_series(cols, valid):
    """The source's series as its region's series table hands them: the
    row at which each run of equal `hostname` codes among the valid rows
    opens (`SERIES_MARKS`, the plane's length past them) and the code each
    run groups to (`SERIES_GROUP`, -1 for a code outside the dictionary)."""
    code = np.asarray(cols["hostname"])
    n = int(np.asarray(valid).sum())
    opens = np.flatnonzero(np.diff(code[:n], prepend=code[:1] - 1)) if n else np.zeros(0, int)
    marks = np.full(SLOTS, code.shape[0], np.int32)
    marks[: len(opens)] = opens
    group = np.full(SLOTS, -1, np.int32)
    group[: len(opens)] = np.where((code[opens] >= 0) & (code[opens] < CARD), code[opens], -1)
    return {**cols, SERIES_MARKS: jnp.asarray(marks), SERIES_GROUP: jnp.asarray(group)}, valid


def states_of(p, sources, nulls=None, count_cols=None):
    """Merged states of `sources` under `p`, as the tile program folds them."""
    fn = jax.jit(
        lambda c, v, n: compute_partial_states(p, c, v, n, count_cols=count_cols)
    )
    merged = None
    for i, (cols, valid) in enumerate(sources):
        st = fn(cols, valid, {} if nulls is None else nulls[i])
        merged = st if merged is None else {k: merge_states(merged[k], st[k]) for k in st}
    return merged


def assert_states_equal(by_code, by_ordinal):
    present = np.asarray(by_code["__presence"].counts) > 0
    assert by_code.keys() == by_ordinal.keys()
    for key in by_code:
        for f in FIELDS:
            a, b = getattr(by_code[key], f), getattr(by_ordinal[key], f)
            assert (a is None) == (b is None), (key, f)
            if a is None:
                continue
            a, b = np.asarray(a), np.asarray(b)
            assert a.shape == b.shape and a.dtype == b.dtype, (key, f)
            if f in ("sums", "counts", "last_ts"):
                assert np.array_equal(a, b), (key, f)  # sums to the bit, absent groups too
            else:
                # an absent group's min / max / last value is whatever its
                # kernel initialised it with: nothing reads it
                assert np.array_equal(a[present], b[present]), (key, f)


def span_ok(gids, mask, span=SPAN):
    """The blocked kernels' guard predicate, as `limb_segment_sums` has it."""
    gb = np.asarray(gids).reshape(-1, BLOCK_ROWS)
    mb = np.asarray(mask).reshape(-1, BLOCK_ROWS)
    bmin = np.min(np.where(mb, gb, 2**31 - 1), axis=1)
    bmax = np.max(np.where(mb, gb, -1), axis=1)
    return bool(np.all(bmax - bmin < span))


DOUBLE_GROUPBY = (("avg", "usage_user"), ("avg", "usage_idle"))


def test_hash_gapped_codes_fail_the_guard_by_code_and_hold_it_by_ordinal():
    codes = region_codes(0)[:64]
    gaps = np.diff(codes)
    assert gaps.min() >= 1 and 2 <= gaps.max() <= 9, "crc32 mod 4 leaves gaps of 1-9"
    (cols, valid) = src = plane(codes)
    bucket = np.asarray(time_bucket(cols["ts"], 0, HOUR))
    assert bucket.max() < BUCKETS
    assert not span_ok(np.asarray(cols["hostname"]) * 16 + bucket, valid)
    series = with_series(*src)
    ordinal = series_rows(series[0][SERIES_MARKS], valid.shape[0])
    assert span_ok(np.asarray(ordinal) * 16 + bucket, valid)
    assert np.array_equal(np.asarray(series[0][SERIES_GROUP])[: len(codes)], codes)
    aggs = DOUBLE_GROUPBY + (("max", "usage_user"), ("count", "usage_idle"))
    by_code = states_of(plan(aggs), [src])  # the guard fails: `scatter`
    by_ordinal = states_of(plan(aggs, series_slots=SLOTS), [series])
    assert_states_equal(by_code, by_ordinal)
    assert int(np.asarray(by_ordinal["__presence"].counts).sum()) == len(codes) * PER_SERIES


def straddling():
    codes = region_codes(1)[:20]
    whole, valid = plane(codes, pad_to=1 << 17)
    cut = 9 * PER_SERIES + 1111  # inside the tenth series
    halves = []
    for lo, hi in ((0, cut), (cut, 1 << 17)):
        cols = {k: jnp.zeros(1 << 17, v.dtype).at[: hi - lo].set(v[lo:hi]) for k, v in whole.items()}
        halves.append((cols, jnp.zeros(1 << 17, bool).at[: hi - lo].set(valid[lo:hi])))
    return halves, DOUBLE_GROUPBY, None


def one_series():
    return [plane(region_codes(2)[5:6], per=70_000)], DOUBLE_GROUPBY + (("min", "usage_user"),), None


def all_invalid():
    cols, valid = plane(region_codes(0)[:16])
    return [(cols, valid), (cols, jnp.zeros_like(valid))], DOUBLE_GROUPBY, None


def tail_padding_holds_a_real_code():
    # the pad tail's zeros are code 0, which is a series of this source too
    codes = np.array([0, 3, 5, 12, 14], np.int32)
    return [plane(codes, pad_to=1 << 16, pad_code=0), plane(codes, pad_to=1 << 16, pad_code=12, seed=1)], DOUBLE_GROUPBY, None


def lacks_first_and_last_code():
    codes = region_codes(3)
    codes = codes[(codes > 0) & (codes < 3999)][:15]
    return [plane(codes)], DOUBLE_GROUPBY + (("max", "usage_idle"),), None


def runs_not_ascending():
    return [plane(np.array([700, 30, 31, 2900, 8, 1500], np.int32))], DOUBLE_GROUPBY, None


def a_code_in_two_runs():
    # not sorted by the tag (a memtable tail in arrival order): two series
    # of one code fold to its group
    return [plane(np.array([40, 44, 40, 49, 44], np.int32), per=20_000)], DOUBLE_GROUPBY, None


def codes_outside_the_dictionary():
    return [plane(np.array([-1, 3, 9, 4096, 5000], np.int32), per=15_000)], DOUBLE_GROUPBY, None


def null_gated():
    cols, valid = plane(region_codes(0)[:16])
    present = jnp.asarray(np.random.default_rng(5).uniform(size=valid.shape[0]) < 0.8)
    return [(cols, valid)], DOUBLE_GROUPBY + (("count", "usage_user"),), [{"usage_user": present}]


def last_value():
    return [plane(region_codes(1)[3:30], per=8640)], (("last_value", "usage_user"),), None


CASES = [
    straddling, one_series, all_invalid, tail_padding_holds_a_real_code,
    lacks_first_and_last_code, runs_not_ascending, a_code_in_two_runs,
    codes_outside_the_dictionary, null_gated, last_value,
]


@pytest.mark.parametrize("case", CASES, ids=[c.__name__ for c in CASES])
def test_ordinal_states_carried_back_equal_the_code_space_states(case):
    sources, aggs, nulls = case()
    kw = {}
    if case is last_value:  # `lastpoint`: no bucket in the gid, span 16
        kw = dict(bucket_col=None, n_buckets=1, block_span=16)
    count_cols = ("usage_user",) if nulls is not None else ()
    by_code = states_of(plan(aggs, **kw), sources, nulls, count_cols)
    by_ordinal = states_of(
        plan(aggs, series_slots=SLOTS, **kw), [with_series(*src) for src in sources], nulls,
        count_cols,
    )
    assert_states_equal(by_code, by_ordinal)
    assert int(np.asarray(by_code["__presence"].counts).sum()) > 0


@pytest.mark.parametrize("case", [a_code_in_two_runs, runs_not_ascending], ids=["two-runs", "one-run"])
def test_ordinals_stand_for_codes_only_where_a_code_is_one_run(case):
    """A row's ordinal is its run's; the runs fold to their codes whatever
    their order, two runs of one code into the one group."""
    (cols, valid), = case()[0]
    series, _valid = with_series(cols, valid)
    ordinal = np.asarray(series_rows(series[SERIES_MARKS], valid.shape[0]))
    group = np.asarray(series[SERIES_GROUP])
    n = int(np.asarray(valid).sum())
    assert np.array_equal(group[ordinal[:n]], np.asarray(cols["hostname"])[:n])
    runs = [700, 30, 31, 2900, 8, 1500] if case is runs_not_ascending else [40, 44, 40, 49, 44]
    assert group[: len(runs)].tolist() == runs and (group >= 0).sum() == len(runs)
    by_host = plan(DOUBLE_GROUPBY, series_slots=SLOTS, bucket_col=None, n_buckets=1, block_span=16)
    presence = np.asarray(states_of(by_host, [(series, valid)])["__presence"].counts)
    per = n // len(runs)
    assert {int(c): int(presence[c]) for c in np.flatnonzero(presence)} == {
        c: per * runs.count(c) for c in runs
    }


def test_a_series_plan_folds_a_gapped_region_to_its_groups():
    """Key (hostname, rack) on a region whose hostname codes lie apart,
    grouped by rack: stage 1 by the region's series (row bounds as the
    series table gives them, not the codes) folds to the same rack states
    as a plan composed over rack's own codes."""
    from greptimedb_tpu.parallel.executor import SERIES_GROUP, SERIES_MARKS

    codes = region_codes(2)[:24]
    cols, valid = plane(codes)
    cols["rack"] = (cols["hostname"] % 4).astype(jnp.int32)
    p = plan(
        (("sum", "usage_user"), ("max", "usage_idle")), group_tags=("rack",), tag_cards=(4,),
        acc_dtype="float64", block_span=128,
    )
    by_code = states_of(p, [(cols, valid)])
    n = int(valid.shape[0])
    marks = np.full(32, n, np.int32)
    marks[: len(codes)] = np.arange(len(codes)) * PER_SERIES
    group = np.full(32, -1, np.int32)
    group[: len(codes)] = codes % 4
    series = {**cols, SERIES_MARKS: jnp.asarray(marks), SERIES_GROUP: jnp.asarray(group)}
    by_series = states_of(
        dataclasses.replace(p, series_slots=32, block_span=SPAN), [(series, valid)]
    )
    present = np.asarray(by_code["__presence"].counts) > 0
    assert present.shape == (4 * 16,) and present.sum() in (36, 48)  # 12 buckets a rack
    for key in by_code:
        for f in ("sums", "counts", "maxs"):
            a, b = getattr(by_code[key], f), getattr(by_series[key], f)
            if a is not None:
                np.testing.assert_allclose(np.asarray(a)[present], np.asarray(b)[present], rtol=1e-12)
