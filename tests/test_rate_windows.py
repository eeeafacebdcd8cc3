"""`ops/rate.range_windows_dyn` finds a window's first and last sample by
row position (two searches inside the series' run, whose bounds the caller
hands in, and a gather); here it is held, field for field and bit for bit,
to a loop over (series, window), and its `rate` program to holding no
scatter and no loop but the window search."""

import dataclasses
import re

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from greptimedb_tpu.ops.rate import (
    REDUCTIONS,
    WindowStats,
    extrapolated_rate_dyn,
    over_time,
    range_windows_dyn,
    reductions_for,
    sorted_run_bounds,
    strip_counter_resets_segmented,
)

I64 = np.iinfo(np.int64)
F64 = np.finfo(np.float64)


def _loop(sid, ts, vals, raw, valid, start, step, range_, n_steps, n_actual, n_series):
    """The nine fields by a loop over (series, window): the rows of a cell
    in plane order, the first and the last of them, and plain folds."""
    out = {
        "count": np.zeros((n_series, n_steps), np.int32),
        "first_ts": np.full((n_series, n_steps), I64.max, np.int64),
        "last_ts": np.full((n_series, n_steps), I64.min, np.int64),
        "first_val": np.full((n_series, n_steps), F64.min),
        "first_raw": np.full((n_series, n_steps), F64.min),
        "last_val": np.full((n_series, n_steps), F64.min),
        "sum": np.zeros((n_series, n_steps)),
        "min": np.full((n_series, n_steps), F64.max),
        "max": np.full((n_series, n_steps), F64.min),
    }
    for s in range(n_series):
        for w in range(min(n_steps, n_actual)):
            t_w = start + w * step
            rows = np.nonzero(valid & (sid == s) & (ts > t_w - range_) & (ts <= t_w))[0]
            if not len(rows):
                continue
            first, last = rows[0], rows[-1]
            out["count"][s, w] = len(rows)
            out["first_ts"][s, w], out["last_ts"][s, w] = ts[first], ts[last]
            out["first_val"][s, w], out["last_val"][s, w] = vals[first], vals[last]
            out["first_raw"][s, w] = raw[first]
            out["sum"][s, w] = vals[rows].sum()
            out["min"][s, w], out["max"][s, w] = vals[rows].min(), vals[rows].max()
    return {k: v.reshape(-1) for k, v in out.items()}


def _plane(rng, n_series, per_series, scrape, jitter, t0=0, gap_share=0.0):
    """Rows sorted by (series, ts): `per_series` scrapes of `scrape` ms
    each, jittered, a `gap_share` of them followed by a gap of 3-6 scrapes.
    Values are whole numbers, so that a sum is exact in any order."""
    sid, ts = [], []
    for s in range(n_series):
        gaps = np.where(rng.random(per_series) < gap_share, rng.integers(3, 7, per_series), 1)
        t = t0 + np.cumsum(gaps * scrape) + rng.integers(0, jitter + 1, per_series)
        ts.append(np.sort(t))
        sid.append(np.full(per_series, s, np.int32))
    sid, ts = np.concatenate(sid), np.concatenate(ts).astype(np.int64)
    vals = rng.integers(0, 1000, len(ts)).astype(np.float64)
    return sid, ts, vals


def _pad_tail(sid, ts, vals, valid, pad):
    """The cache's pad rows at the tail: code 0, ts 0, value 0, never valid."""
    return (
        np.concatenate([sid, np.zeros(pad, np.int32)]),
        np.concatenate([ts, np.zeros(pad, np.int64)]),
        np.concatenate([vals, np.zeros(pad)]),
        np.concatenate([valid, np.zeros(pad, bool)]),
    )


def _bounds(real_sid, n_series, n):
    """Run bounds as `tile_exec._run_bounds` makes them: the runs of the
    real rows' series (sorted), every series past them empty at `n`, the
    end of the rows the program sees (the pad tail joins the last run)."""
    n_data = int(real_sid.max()) + 1 if len(real_sid) else 0
    bounds = np.full(n_series + 1, n, np.int32)
    bounds[:n_data + 1] = sorted_run_bounds(real_sid, n_data)
    return bounds


def _case(name):
    """(sid, ts, vals, valid, bounds, grid) of one named case; grid =
    (start, step, range_, n_steps, n_actual, k, n_series)."""
    rng = np.random.default_rng(sum(map(ord, name)))
    bounds = None
    if name == "regular_grid":
        sid, ts, vals = _plane(rng, 5, 60, 10_000, 0)
        valid = np.ones(len(ts), bool)
        grid = (120_000, 60_000, 300_000, 8, 8, 5, 5)
    elif name == "gaps_longer_than_the_range":
        # empty cells and one-sample cells: a range of two scrapes
        sid, ts, vals = _plane(rng, 7, 40, 15_000, 4_000, gap_share=0.3)
        valid = np.ones(len(ts), bool)
        grid = (60_000, 20_000, 30_000, 40, 40, 2, 7)
    elif name == "invalid_rows_masked_series_pad_tail":
        sid, ts, vals = _plane(rng, 6, 50, 10_000, 3_000)
        valid = rng.random(len(ts)) > 0.2  # dedup losers, rows out of the fetch
        valid[sid == 2] = False  # a series the matcher masks
        bounds = _bounds(sid, 8, len(sid) + 37)
        sid, ts, vals, valid = _pad_tail(sid, ts, vals, valid, 37)
        grid = (100_000, 30_000, 120_000, 16, 16, 4, 8)  # 2 series beyond the data
    elif name == "padded_steps_and_k":
        sid, ts, vals = _plane(rng, 4, 80, 10_000, 2_000)
        valid = rng.random(len(ts)) > 0.1
        grid = (200_000, 60_000, 300_000, 16, 9, 8, 4)  # ceil(range/step) = 5 -> k 8
    elif name == "start_off_the_grid_negative_offset":
        # ts shifted by a negative offset modifier to before the epoch
        sid, ts, vals = _plane(rng, 5, 60, 10_000, 5_000, t0=-400_000)
        valid = rng.random(len(ts)) > 0.1
        grid = (-123_457, 17_001, 90_003, 24, 24, 6, 5)
    elif name == "ns_scale_timestamps":
        base = 1_700_000_000_000_000_000 // 1_000_000
        sid, ts, vals = _plane(rng, 3, 60, 15_000, 0, t0=base)
        valid = np.ones(len(ts), bool)
        grid = (base + 120_000, 30_000, 120_000, 17, 17, 4, 3)
    elif name == "duplicate_series_ts":
        # an append-mode table: the same (series, ts) twice or thrice among
        # the valid rows, the values differing
        sid, ts, vals = _plane(rng, 4, 30, 10_000, 0)
        twice = np.sort(np.concatenate([np.arange(len(ts))] * 2 + [np.arange(0, len(ts), 3)]))
        sid, ts = sid[twice], ts[twice]
        vals = rng.integers(0, 1000, len(ts)).astype(np.float64)
        valid = rng.random(len(ts)) > 0.1
        grid = (50_000, 10_000, 30_000, 26, 26, 3, 4)
    elif name == "span_past_32_bits":
        # a month at a day's step: the grid's span in ms passes 2**31
        sid, ts, vals = _plane(rng, 3, 200, 3_600_000, 600_000, gap_share=0.1)
        valid = rng.random(len(ts)) > 0.1
        grid = (86_400_000, 86_400_000, 2 * 86_400_000, 32, 31, 2, 3)
    elif name == "rows_far_outside_the_grid":
        # months before and after a short grid: the 32-bit keys saturate
        sid, ts, vals = _plane(rng, 4, 40, 10_000, 3_000, t0=1_700_000_000_000)
        far = rng.random(len(ts))
        ts = np.where(far < 0.2, ts - 10**15, np.where(far > 0.8, ts + 10**15, ts))
        order = np.lexsort((ts, sid))
        sid, ts, vals = sid[order], ts[order], vals[order]
        valid = rng.random(len(ts)) > 0.1
        grid = (1_700_000_100_000, 20_000, 60_000, 16, 16, 3, 4)
    elif name in ("span_one_under_the_32_bit_stop", "span_at_the_32_bit_stop"):
        # (n_steps - 1) * step + range = 2**31 - 2 takes the 32-bit keys,
        # 2**31 - 1 the 64-bit ones; samples sit on and beside every edge
        step = 715_827_000
        range_ = 2**31 - 2 - 3 * step + (name == "span_at_the_32_bit_stop")
        start = 1_700_000_000_000
        edges = np.array([start + w * step for w in range(4)], np.int64)
        one = np.unique(np.concatenate(
            [edges + d for d in (-range_ - 1, -range_, -range_ + 1, -1, 0, 1)]))
        sid = np.repeat(np.arange(3, dtype=np.int32), len(one))
        ts = np.tile(one, 3)
        vals = rng.integers(0, 1000, len(ts)).astype(np.float64)
        valid = rng.random(len(ts)) > 0.1
        grid = (start, step, range_, 4, 4, 1, 3)
    elif name == "one_row":
        sid, ts, vals = np.zeros(1, np.int32), np.full(1, 500, np.int64), np.full(1, 7.0)
        valid = np.ones(1, bool)
        grid = (0, 250, 600, 4, 4, 3, 2)
    elif name == "no_row":
        # a subquery whose every point is NaN hands the kernel no sample
        sid, ts, vals = np.zeros(0, np.int32), np.zeros(0, np.int64), np.zeros(0)
        valid = np.zeros(0, bool)
        grid = (0, 10_000, 30_000, 4, 4, 3, 2)
    elif name == "no_valid_row":
        sid, ts, vals = _plane(rng, 3, 20, 10_000, 0)
        valid = np.zeros(len(ts), bool)
        grid = (0, 10_000, 30_000, 8, 8, 3, 3)
    elif name == "invalid_rows_between_a_series_samples_hold_anything":
        # dedup losers and rows the matcher drops between one series'
        # samples, holding another series' id and a ts past every window
        sid, ts, vals = _plane(rng, 4, 50, 10_000, 2_000)
        valid = np.ones(len(ts), bool)
        junk = (sid == 1) & (np.arange(len(ts)) % 3 == 1)
        valid[junk] = False
        sid = np.where(junk, 3, sid).astype(np.int32)
        ts = np.where(junk, 10**12, ts)
        bounds = sorted_run_bounds(np.repeat(np.arange(4), 50), 4)
        grid = (100_000, 20_000, 60_000, 16, 16, 3, 4)
    elif name == "series_with_no_row_in_the_fetch":
        # the first, a middle and the last series: every row outside the
        # fetch range (invalid), so each is an empty run, not present
        sid, ts, vals = _plane(rng, 6, 30, 10_000, 1_000)
        valid = rng.random(len(ts)) > 0.1
        valid[np.isin(sid, [0, 3, 5])] = False
        grid = (50_000, 30_000, 90_000, 8, 8, 3, 6)
    elif name == "pad_series_past_the_data":
        # the series space padded to a power of two: four empty runs at
        # the end of the rows
        sid, ts, vals = _plane(rng, 4, 40, 10_000, 0)
        valid = rng.random(len(ts)) > 0.1
        bounds = _bounds(sid, 8, len(sid))
        grid = (60_000, 30_000, 60_000, 8, 8, 2, 8)
    elif name == "pad_tail_after_a_valid_last_row":
        # the last real row valid, the pad rows' zeros right after it
        sid, ts, vals = _plane(rng, 3, 40, 10_000, 0)
        valid = np.ones(len(ts), bool)
        bounds = _bounds(sid, 4, len(sid) + 100)
        sid, ts, vals, valid = _pad_tail(sid, ts, vals, valid, 100)
        grid = (60_000, 30_000, 60_000, 8, 8, 2, 4)
    elif name == "logical_slice_off_and_lo":
        # a logical table's slice: the neighbours' rows before the
        # table's first row (`at["lo"]` > 0; their series id clipped to 0,
        # never valid) and after its last (they continue its last series
        # and lie in no run: the last bound is the table's `at["hi"]`)
        sid, ts, vals = _plane(rng, 5, 30, 10_000, 2_000)
        valid = rng.random(len(ts)) > 0.1
        head, tail = 23, 41
        bounds = head + sorted_run_bounds(sid, 5)
        sid = np.concatenate([np.zeros(head, np.int32), sid, np.full(tail, 4, np.int32)])
        ts = np.concatenate([rng.integers(0, 10**9, head), ts, rng.integers(0, 10**9, tail)])
        vals = np.concatenate([np.zeros(head), vals, np.zeros(tail)])
        valid = np.concatenate([np.zeros(head, bool), valid, np.zeros(tail, bool)])
        grid = (60_000, 30_000, 90_000, 8, 8, 3, 5)
    elif name == "last_valid_row_then_invalid_rows_up_to_b":
        # of every five rows two valid: a window's last sample is followed
        # by invalid rows up to the next valid one past t_w, so b - 1 is
        # invalid and the carries answer for the last sample
        sid, ts, vals = _plane(rng, 5, 40, 10_000, 0)
        valid = np.arange(len(ts)) % 5 < 2
        grid = (80_000, 50_000, 30_000, 8, 8, 3, 5)
    elif name == "empty_window_at_the_run_end":
        # series that end early, their rows cut (not masked): a window
        # past a series' last sample has a = b = the run's end, where the
        # next series' first row lies
        sid, ts, vals = _plane(rng, 4, 30, 10_000, 0)
        keep = ts <= np.array([300_000, 100_000, 200_000, 50_000])[sid]
        sid, ts, vals = sid[keep], ts[keep], vals[keep]
        valid = np.ones(len(ts), bool)
        grid = (60_000, 30_000, 60_000, 9, 9, 2, 4)
    elif name == "run_ending_in_invalid_rows_before_the_next_series":
        # each series' last three rows invalid, the next series' first row
        # valid right after them
        sid, ts, vals = _plane(rng, 5, 30, 10_000, 0)
        valid = np.arange(len(ts)) % 30 < 27
        grid = (100_000, 40_000, 90_000, 8, 8, 3, 5)
    elif name == "million_series_shape_small":
        # prom-1m-series-rules' shape: ten rows a series at a 60 s scrape,
        # rate[5m] from 5 min on, six steps padded to eight, the series
        # space and the rows padded to powers of two
        sid, ts, vals = _plane(rng, 100, 10, 60_000, 0)
        valid = np.ones(len(ts), bool)
        bounds = _bounds(sid, 128, 2048)
        sid, ts, vals, valid = _pad_tail(sid, ts, vals, valid, 2048 - len(sid))
        grid = (300_000, 60_000, 300_000, 8, 6, 8, 128)
    else:
        raise KeyError(name)
    if bounds is None:
        bounds = sorted_run_bounds(sid, grid[-1])
    return sid, ts, vals, valid, bounds, grid


CASES = [
    "regular_grid", "gaps_longer_than_the_range", "invalid_rows_masked_series_pad_tail",
    "padded_steps_and_k", "start_off_the_grid_negative_offset", "ns_scale_timestamps",
    "duplicate_series_ts", "span_past_32_bits", "rows_far_outside_the_grid",
    "span_one_under_the_32_bit_stop", "span_at_the_32_bit_stop", "one_row", "no_row", "no_valid_row",
    "invalid_rows_between_a_series_samples_hold_anything", "series_with_no_row_in_the_fetch",
    "pad_series_past_the_data", "pad_tail_after_a_valid_last_row", "logical_slice_off_and_lo",
    "last_valid_row_then_invalid_rows_up_to_b", "empty_window_at_the_run_end",
    "run_ending_in_invalid_rows_before_the_next_series", "million_series_shape_small",
]


def _cells(sid, ts, valid, grid):
    """(series, window, rows) of every cell of the real grid: the cell's
    valid rows in plane order."""
    start, step, range_, n_steps, n_actual, _k, n_series = grid
    for s in range(n_series):
        for w in range(min(n_steps, n_actual)):
            t_w = start + w * step
            yield s, w, np.nonzero(valid & (sid == s) & (ts > t_w - range_) & (ts <= t_w))[0]


@pytest.mark.parametrize("name", CASES)
def test_range_windows_equal_a_loop_over_series_and_windows(name):
    """All nine fields and the presence bit-equal to the loop, with a raw
    plane beside the (here arbitrarily) adjusted one, jitted with a traced
    grid and the run bounds as the tile program calls it."""
    sid, ts, vals, valid, bounds, grid = _case(name)
    start, step, range_, n_steps, n_actual, k, n_series = grid
    raw = vals[::-1].copy()  # any other plane: first_raw is a selection from it
    want = _loop(sid, ts, vals, raw, valid, start, step, range_, n_steps, n_actual, n_series)

    @jax.jit
    def run(sid, ts, vals, raw, valid, bounds, start, step, range_, n_actual):
        stats, present = range_windows_dyn(
            sid, ts, vals, valid, bounds, start=start, step=step, range_=range_,
            n_steps=n_steps, k=k, n_steps_actual=n_actual, raw_values=raw,
        )
        return dataclasses.astuple(stats) + (present,)

    *got, present = run(
        jnp.asarray(sid), jnp.asarray(ts), jnp.asarray(vals), jnp.asarray(raw),
        jnp.asarray(valid), jnp.asarray(bounds), np.int64(start), np.int64(step),
        np.int64(range_), np.int64(n_actual),
    )
    for f, field in zip(dataclasses.fields(WindowStats), got):
        np.testing.assert_array_equal(np.asarray(field), want[f.name], f.name)
    np.testing.assert_array_equal(
        np.asarray(present), np.isin(np.arange(n_series), sid[valid])
    )
    if name == "gaps_longer_than_the_range":
        assert (want["count"] == 0).any() and (want["count"] == 1).any()
    if name == "series_with_no_row_in_the_fetch":
        assert not np.asarray(present)[[0, 3, 5]].any()
    cells = list(_cells(sid, ts, valid, grid))
    if name == "last_valid_row_then_invalid_rows_up_to_b":
        # a cell whose last sample's next row is invalid, and its series
        # has a valid row after it: b - 1 lies past the last sample
        assert any(
            len(r) and not valid[r[-1] + 1] and (valid & (sid == s))[r[-1] + 1:].any()
            for s, _w, r in cells
        )
    if name == "empty_window_at_the_run_end":
        # an empty cell of a present series, past its last sample
        assert any(
            not len(r) and ts[valid & (sid == s)].max() <= start + w * step - range_
            for s, w, r in cells
        )


def test_without_a_raw_plane_first_raw_is_first_val_and_unasked_reductions_keep_their_fill():
    sid, ts, vals, valid, bounds, grid = _case("invalid_rows_masked_series_pad_tail")
    start, step, range_, n_steps, n_actual, k, n_series = grid
    want = _loop(sid, ts, vals, vals, valid, start, step, range_, n_steps, n_actual, n_series)
    for reduce in [(), ("sum",), ("min",), ("max",), REDUCTIONS]:
        got, _present = range_windows_dyn(  # eager, python ints: the legacy scan's call
            jnp.asarray(sid), jnp.asarray(ts), jnp.asarray(vals), jnp.asarray(valid),
            bounds, start=start, step=step, range_=range_, n_steps=n_steps, k=k,
            reduce=reduce,
        )
        np.testing.assert_array_equal(np.asarray(got.first_raw), want["first_val"])
        fills = {"sum": 0.0, "min": F64.max, "max": F64.min}
        for name, fill in fills.items():
            field = np.asarray(getattr(got, name))
            if name in reduce:
                np.testing.assert_array_equal(field, want[name])
            else:
                assert (field == fill).all()


def test_duplicate_series_ts_read_by_position():
    """Of valid rows that share (series, ts), the first in plane order is
    the window's first sample and the last its last, not the largest value
    at that timestamp."""
    sid = jnp.zeros(5, jnp.int32)
    ts = jnp.asarray(np.array([10, 10, 20, 30, 30], np.int64))
    vals = jnp.asarray(np.array([9.0, 1.0, 5.0, 2.0, 8.0]))
    valid = jnp.asarray(np.array([True, True, True, True, False]))
    got, _present = range_windows_dyn(
        sid, ts, vals, valid, sorted_run_bounds(sid, 1),
        start=30, step=10, range_=25, n_steps=1, k=4,
    )
    assert int(got.count[0]) == 4
    assert (float(got.first_val[0]), float(got.last_val[0])) == (9.0, 2.0)
    assert (int(got.first_ts[0]), int(got.last_ts[0])) == (10, 30)


def test_reductions_for_names_what_over_time_reads():
    assert reductions_for("rate") == reductions_for("increase") == reductions_for("delta") == ()
    assert reductions_for("count_over_time") == reductions_for("last_over_time") == ()
    assert reductions_for("__last_ts") == ()
    assert reductions_for("avg_over_time") == reductions_for("sum_over_time") == ("sum",)
    assert reductions_for("min_over_time") == ("min",)
    assert reductions_for("max_over_time") == ("max",)
    # what `over_time` reads of a stats whose other reductions are fills
    sid, ts, vals, valid, bounds, grid = _case("regular_grid")
    start, step, range_, n_steps, n_actual, k, n_series = grid
    args = (jnp.asarray(sid), jnp.asarray(ts), jnp.asarray(vals), jnp.asarray(valid), bounds)
    kw = dict(start=start, step=step, range_=range_, n_steps=n_steps, k=k)
    whole, _present = range_windows_dyn(*args, **kw)
    for func in ("avg_over_time", "sum_over_time", "min_over_time", "max_over_time",
                 "count_over_time", "last_over_time"):
        lean, _present = range_windows_dyn(*args, **kw, reduce=reductions_for(func))
        for a, b in zip(over_time(lean, func), over_time(whole, func)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---- the lowered program ----------------------------------------------------


def _scatters_by_scope(func):
    """The scatter ops of the jitted pipeline strip -> windows -> value, in
    the text the compiler is handed (nothing eliminated yet), split by
    whether their location lies inside the `range_windows` scope."""
    n, n_series, n_steps, k = 4096, 16, 8, 4

    def program(sid, ts, raw, valid, bounds, start, step, range_, n_actual):
        v = strip_counter_resets_segmented(sid, raw, valid) if func == "rate" else raw
        stats, present = range_windows_dyn(
            sid, ts, v, valid, bounds, start=start, step=step, range_=range_,
            n_steps=n_steps, k=k, n_steps_actual=n_actual,
            raw_values=raw if func == "rate" else None, reduce=reductions_for(func),
        )
        if func == "rate":
            return extrapolated_rate_dyn(stats, start, step, range_, n_steps, "rate"), present
        return over_time(stats, func), present

    scalar = jax.ShapeDtypeStruct((), jnp.int64)
    lines = jax.jit(program).lower(
        jax.ShapeDtypeStruct((n,), jnp.int32), jax.ShapeDtypeStruct((n,), jnp.int64),
        jax.ShapeDtypeStruct((n,), jnp.float64), jax.ShapeDtypeStruct((n,), jnp.bool_),
        jax.ShapeDtypeStruct((n_series + 1,), jnp.int32), scalar, scalar, scalar, scalar,
    ).as_text(debug_info=True).splitlines()
    # `#loc7 = loc("jit(program)/range_windows/scatter-add"(#loc3))`
    names = dict(
        m.groups() for m in (re.match(r'(#loc\d+) = loc\("([^"]*)"', line) for line in lines) if m
    )
    assert any("/range_windows/" in name for name in names.values())
    inside, outside = [], []
    for i, line in enumerate(lines):
        if '"stablehlo.scatter"' in line:
            # the op's location follows its reduction region
            close = next(x for x in lines[i:] if x.lstrip().startswith("})"))
            name = names[re.search(r"loc\((#loc\d+)\)\s*$", close).group(1)]
            (inside if "/range_windows/" in name else outside).append(name)
    return inside, outside


def test_the_rate_program_holds_no_scatter_and_sum_over_time_still_does():
    inside, outside = _scatters_by_scope("rate")
    assert inside == [] and outside == []
    inside, _ = _scatters_by_scope("sum_over_time")
    assert len(inside) == 4  # k unrolled windows, `sum` alone
    inside, _ = _scatters_by_scope("last_over_time")
    assert inside == []


# ---- the reset strip: a carry, bit-equal to the gather it replaced -----------


def _gather_strip(sid, vals, valid):
    """The strip as it was until PR 38: the previous valid row's index by a
    running max, its value and series GATHERED; the segmented sum is the
    module's own (unchanged), so the outputs can be held to the bit."""
    from greptimedb_tpu.ops.rate import _sum_since_start

    last_valid = np.maximum.accumulate(np.where(valid, np.arange(len(sid)), -1))
    prev = np.concatenate([[-1], last_valid[:-1]])
    pv, ps = vals[np.clip(prev, 0, None)], sid[np.clip(prev, 0, None)]
    same = valid & (prev >= 0) & (ps == sid)
    with np.errstate(invalid="ignore"):
        reset_add = np.where(same & (vals < pv), pv, 0.0)
    return vals + np.asarray(_sum_since_start(jnp.asarray(valid & ~same), jnp.asarray(reset_add)))


def _strip_case(name):
    """(series ids, values, valid) of one named case; series sorted, as the
    tile planes hold them."""
    nan = np.nan
    if name == "invalid_rows_between_samples":
        sid = [0] * 7 + [1] * 6
        vals = [5, 99, 7, 9, 1, 50, 3, 4, 8, 2, 77, 6, 1]
        valid = [1, 0, 1, 1, 0, 1, 1, 1, 0, 1, 0, 1, 1]
    elif name == "pad_tail":
        sid = [0, 0, 0, 1, 1, 1] + [0] * 10
        vals = [10, 4, 12, 3, 1, 2] + [0] * 10
        valid = [1] * 6 + [0] * 10
    elif name == "reset_at_a_series_first_valid_row":
        sid, vals, valid = [0, 0, 1, 1, 2], [100, 200, 5, 6, 1], [1] * 5
    elif name == "series_whose_first_rows_are_invalid":
        sid = [0, 0, 1, 1, 1, 1, 2, 2]
        vals = [3, 9, 500, 600, 7, 2, 0, 1]
        valid = [1, 1, 0, 0, 1, 1, 0, 1]
    elif name == "nan_values":
        sid = [0, 0, 0, 0, 1, 1, 1]
        vals = [4, nan, 2, 1, nan, 3, nan]
        valid = [1] * 7
    elif name == "all_invalid":
        sid, vals, valid = [0, 0, 1, 1], [9, 1, 8, 2], [0] * 4
    elif name == "one_row":
        sid, vals, valid = [0], [42.5], [1]
    else:  # 2^16 random rows: resets, NaNs, invalid rows and a pad tail
        rng = np.random.default_rng(3800001)
        n, real = 1 << 16, 60_000
        sid = np.zeros(n, np.int32)
        sid[:real] = np.sort(rng.integers(0, 700, real))
        vals = np.zeros(n)
        vals[:real] = np.cumsum(rng.uniform(0, 10, real))
        vals[:real][rng.random(real) < 0.02] = rng.uniform(0, 50, 1)[0]
        vals[:real][rng.random(real) < 0.01] = np.nan
        valid = np.zeros(n, bool)
        valid[:real] = rng.random(real) < 0.85
    return (np.asarray(sid, np.int32), np.asarray(vals, np.float64),
            np.asarray(valid, bool))


STRIP_CASES = [
    "invalid_rows_between_samples", "pad_tail", "reset_at_a_series_first_valid_row",
    "series_whose_first_rows_are_invalid", "nan_values", "all_invalid", "one_row",
    "random_2_16_rows",
]


@pytest.mark.parametrize("name", STRIP_CASES)
def test_reset_strip_is_bit_equal_to_the_gather_form(name):
    sid, vals, valid = _strip_case(name)
    got = np.asarray(strip_counter_resets_segmented(
        jnp.asarray(sid), jnp.asarray(vals), jnp.asarray(valid)
    ))
    want = _gather_strip(sid, vals, valid)
    assert np.array_equal(got.view(np.int64), want.view(np.int64))


def _primitives(jaxpr):
    """Every primitive name of a jaxpr, its sub-jaxprs' included."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


def test_reset_strip_holds_no_gather():
    n = 1 << 16
    closed = jax.make_jaxpr(strip_counter_resets_segmented)(
        jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.float64), jnp.zeros(n, bool)
    )
    assert "gather" not in _primitives(closed.jaxpr)
    # the walk finds a gather where there is one
    taken = jax.make_jaxpr(lambda v, i: jnp.take(v, i))(jnp.zeros(n), jnp.zeros(n, jnp.int32))
    assert "gather" in _primitives(taken.jaxpr)


# ---- the planes the tile program hands the kernel ---------------------------


def _plane_order(db, table, tags, ts_name):
    """(real rows in (series, ts) order?, pad rows at the tail and never
    valid?, pad rows' keys) of the table's consolidated entry, with the
    series id `tile_exec._region_stats` computes."""
    from greptimedb_tpu.query.logical_plan import TableScan
    from greptimedb_tpu.query.promql.tile_exec import _pow2

    ctx = db._tile_context(TableScan(table=table, database=db.current_database))
    (entry,) = [
        e for e in db.query_engine.tile_cache._super.values()
        if e.region_id in {r.region_id for r in ctx.regions}
    ]
    db.query_engine.tile_cache.repair_super([entry], ctx.dictionary, tags)
    cat = lambda chunks: np.concatenate([np.asarray(c) for c in chunks])  # noqa: E731
    radices = [_pow2(max(ctx.dictionary.cardinality(t), 1)) for t in tags]
    sid = np.zeros(entry.pad, np.int64)
    for tag, radix in zip(tags, radices):
        sid = sid * radix + cat(entry.cols[tag])
    ts, valid = cat(entry.cols[ts_name]).astype(np.int64), cat(entry.valid)
    n = entry.num_rows
    key = list(zip(sid[:n].tolist(), ts[:n].tolist()))
    return (
        key == sorted(key),
        n < entry.pad and not valid[n:].any() and valid[:n].all(),
        set(zip(sid[n:].tolist(), ts[n:].tolist())),
    )


def test_consolidated_planes_are_in_series_ts_order_after_a_delta_merge_too():
    """What `range_windows_dyn`'s docstring says of `tile_exec`'s planes:
    the real rows in (series, ts) order whatever their validity, the pad
    rows (code 0, ts 0, never valid) at the tail; held through a delta
    merge that grows the dictionary in the middle of its order."""
    import tempfile

    from greptimedb_tpu.database import Database
    from greptimedb_tpu.utils import metrics
    from greptimedb_tpu.utils.config import Config

    cfg = Config()
    cfg.storage.data_home = tempfile.mkdtemp()
    cfg.storage.compaction_background_enable = False
    cfg.tile.fused_build = False  # planes build in the query, not behind it
    db = Database(config=cfg)
    try:
        db.sql(
            "CREATE TABLE pm (host STRING, dc STRING, greptime_value DOUBLE,"
            " ts TIMESTAMP(3) TIME INDEX, PRIMARY KEY (host, dc))"
        )
        rng = np.random.default_rng(30)

        def flush(hosts, ticks):
            rows = [
                f"('{h}', 'dc{i % 3}', {float(rng.integers(0, 999))}, {int(t) * 15000})"
                for i, h in enumerate(hosts) for t in ticks
            ]
            rng.shuffle(rows)
            db.sql("INSERT INTO pm VALUES " + ",".join(rows))
            db.sql("ADMIN flush_table('pm')")

        q = "TQL EVAL (60, 540, '30s') rate(pm[2m])"
        # two files that overlap in time: duplicate (pk, ts) across them,
        # so the dedup plane masks rows BETWEEN a series' samples
        flush(["h1", "h3", "h5", "h7"], range(0, 30))
        flush(["h1", "h3", "h5", "h7"], range(20, 40))
        dispatched = metrics.TQL_TILE_DISPATCHES.get()
        db.sql_one(q)
        db.sql_one(q)
        assert metrics.TQL_TILE_DISPATCHES.get() > dispatched
        ordered, pads_last, pad_keys = _plane_order(db, "pm", ["host", "dc"], "ts")
        assert ordered and pads_last and pad_keys == {(0, 0)}

        # only a program that still reduces by segment moves the counter
        held = metrics.TQL_TILE_SEGMENT_STATS.get()
        for func in ("rate", "increase", "delta", "count_over_time", "last_over_time"):
            db.sql_one(f"TQL EVAL (60, 540, '30s') {func}(pm[2m])")
        assert metrics.TQL_TILE_SEGMENT_STATS.get() == held
        dispatched = metrics.TQL_TILE_DISPATCHES.get()
        for func in ("avg_over_time", "sum_over_time", "min_over_time", "max_over_time"):
            db.sql_one(f"TQL EVAL (60, 540, '30s') {func}(pm[2m])")
        assert metrics.TQL_TILE_DISPATCHES.get() == dispatched + 4
        assert metrics.TQL_TILE_SEGMENT_STATS.get() == held + 4

        merges = metrics.TILE_DELTA_MERGES.get()
        flush(["h0", "h4", "h7", "h9"], range(35, 50))  # h0 / h4 shift every code after them
        want = db.sql_one(q)
        assert metrics.TILE_DELTA_MERGES.get() == merges + 1
        ordered, pads_last, pad_keys = _plane_order(db, "pm", ["host", "dc"], "ts")
        assert ordered and pads_last and pad_keys == {(0, 0)}
        db.config.tql.tile = False
        legacy = db.sql_one(q)
        assert want.num_rows == legacy.num_rows > 0
        for name in want.column_names:
            a, b = want[name].to_pylist(), legacy[name].to_pylist()
            if isinstance(a[0], float):
                np.testing.assert_allclose(a, b, rtol=1e-12)
            else:
                assert a == b
    finally:
        db.close()


# ---- the tile program's loops ------------------------------------------------


def _loops(jaxpr, under=()):
    """Every loop primitive (`while`, `scan`: a `fori_loop` with a static
    trip count traces to `scan`) of a jaxpr, its sub-jaxprs' included, each
    as the path of loop and `cond` primitives that holds it."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        here = under + (name,) if name in ("cond", "while", "scan") else under
        if name in ("while", "scan"):
            found.append(here)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _loops(sub, here)
    return found


def _panel_program_jaxpr(n=1 << 12, s_pad=256, w_pad=8, g_pad=16):
    """The TQL tile program of a `sum by (…) (rate(…[5m]))` panel over a
    logical table's slice (the `prom-1m-series-rules` shape, small)."""
    from greptimedb_tpu.query.promql import tile_exec

    csig = ("rate", "sum", s_pad, w_pad, 8, 1_000_000, g_pad, False)
    at = {k: np.int32(0) for k in ("sid0", "off", "lo", "hi")}
    at["bounds"] = np.zeros(s_pad + 1, np.int32)
    src = (((np.zeros(n, np.int32),),), (np.zeros(n, np.int64),), (np.zeros(n),), None,
           (np.zeros(n, bool),), at)
    dyn = {k: np.int64(0) for k in ("lo", "hi", "offset", "start", "step", "range", "nsteps")}
    dyn["gid"] = np.zeros(s_pad, np.int32)
    program = tile_exec._full_program((csig, ((tile_exec._source_sig(src), (n, 0, n)),)))
    return jax.make_jaxpr(program)((src,), dyn).jaxpr


def test_the_panel_program_searches_no_series_run_over_the_plane():
    """The program's only loops are the window search's, one in each branch
    of its `cond` (the 32-bit and the 64-bit keys): no search of the
    series' run starts over the plane, no running max for presence."""
    assert sorted(_loops(_panel_program_jaxpr())) == [("cond", "while"), ("cond", "while")]


def test_the_loop_walk_finds_a_search_over_the_plane():
    """What the program held until its run bounds came with the request:
    a search of each series' first row over the whole plane, outside any
    `cond`, with as many rounds as the plane's length needs."""
    from greptimedb_tpu.ops.rate import _first_greater

    n, s = 1 << 12, 256

    def search(keys):
        return _first_greater(
            keys, jnp.zeros(s + 1, jnp.int32), jnp.full(s + 1, n, jnp.int32),
            jnp.arange(s + 1, dtype=jnp.int32) - 1, n.bit_length(),
        )

    assert _loops(jax.make_jaxpr(search)(jnp.zeros(n, jnp.int32)).jaxpr) == [("scan",)]


def _gathers(jaxpr, tainted=(), under=()):
    """Every gather of a jaxpr and its sub-jaxprs as (path, shape, dtype,
    indexed by a gather): `path` the loop primitives and `cond` branches
    ("cond0", "cond1", …) that hold it, the last whether its indices are
    made of another gather's output by plain operations, not through a
    loop (a search's positions count as the loop's).  `tainted`: which of
    the jaxpr's inputs are such outputs."""
    found = []
    bad = {v for v, t in zip(jaxpr.invars, tainted) if t}

    def is_bad(v):
        return not isinstance(v, jax.extend.core.Literal) and v in bad

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        ins = [is_bad(v) for v in eqn.invars]
        outs = [False] * len(eqn.outvars)
        if name == "gather":
            aval = eqn.outvars[0].aval
            found.append((under, aval.shape, str(aval.dtype), ins[1]))
            outs = [True]
        elif name in ("while", "scan"):
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += _gathers(sub, (), under + (name,))[0]
        elif name == "cond":
            for i, branch in enumerate(eqn.params["branches"]):
                got, out = _gathers(branch.jaxpr, ins[1:], under + (f"cond{i}",))
                found += got
                outs = [a or b for a, b in zip(outs, out)]
        else:
            subs = list(jax.core.jaxprs_in_params(eqn.params))
            if len(subs) == 1 and len(subs[0].invars) == len(eqn.invars):
                got, outs = _gathers(subs[0], ins, under)  # a jit: follow it
                found += got
            else:
                for sub in subs:
                    found += _gathers(sub, [any(ins)] * len(sub.invars), under)[0]
                outs = [any(ins)] * len(eqn.outvars)
        bad |= {v for v, t in zip(eqn.outvars, outs) if t}
    return found, [is_bad(v) for v in jaxpr.outvars]


def test_a_cell_reads_carried_planes_at_the_searched_positions():
    """Outside the window search's loops, the panel program's gathers of
    [S*W] elements or more read at the search's own positions, none at
    indices another gather made, and only the 64-bit branch of the
    search's `cond` reads an int64 plane.  The program until the carried
    value came held seven such gathers: `before` (n_valid at a - 1 and
    b - 1), `last_row` (last_valid at b - 1) and five statistics (ts at the
    first and the last row, the value at both, the raw value at the
    first), two of them at `last_row`'s indices and both ts reads int64.
    Now five: the stacked words (n_valid, 32-bit key) at a and b - 1 in
    the 32-bit branch, n_valid and the 64-bit ts there in the other, the
    carried value at both positions, the raw value at a."""
    s_pad, w_pad = 256, 8
    gathers, _ = _gathers(_panel_program_jaxpr(s_pad=s_pad, w_pad=w_pad))
    big = [
        g for g in gathers
        if np.prod(g[1]) >= s_pad * w_pad and not {"while", "scan"} & set(g[0])
    ]
    assert [g for g in big if g[3]] == []
    (wide,) = {g[0][-2] for g in gathers if g[0][-1:] == ("while",) and g[2] == "int64"}
    assert [g[0] for g in big if g[2] == "int64"] == [(wide,)]
    assert len(big) == 5


def test_the_gather_walk_finds_a_read_through_another_gather():
    """What the program held until the value was carried: the last sample
    read at a row that a gather of `last_valid` found."""
    n, cells = 1 << 12, 256

    def last_ts(ts, last_valid, pos):
        return jnp.take(ts, jnp.take(last_valid, pos - 1, mode="clip"), mode="clip")

    gathers, _ = _gathers(jax.make_jaxpr(last_ts)(
        jnp.zeros(n, jnp.int64), jnp.zeros(n, jnp.int32), jnp.zeros(cells, jnp.int32)
    ).jaxpr)
    assert [(g[1], g[3]) for g in gathers] == [((cells,), False), ((cells,), True)]


def test_a_logical_slice_reads_its_series_at_the_bounds_made_for_it():
    """A logical table's rows, cut out of two chunks of its region's planes
    at a dynamic offset (`_slice_plan`, `_logical_slice`: off > 0, the
    table's first row lo > 0 into the slice), read at the run bounds that
    `_run_bounds` makes of the region's series table: bit-equal to the
    loop over the table's own rows."""
    from greptimedb_tpu.parallel.tile_cache import SeriesTable
    from greptimedb_tpu.query.promql.tile_exec import (
        _logical_slice,
        _run_bounds,
        _slice_plan,
        _Span,
    )

    rng = np.random.default_rng(4101)
    lengths = rng.integers(20, 120, 90)  # rows a series: three tables' series
    starts = np.concatenate([[0], np.cumsum(lengths)]).astype(np.int64)
    s_lo, s_hi = 30, 60  # the middle table's series
    chunk = 4096
    total = 2 * chunk
    assert starts[-1] <= total
    region_sid = np.zeros(total, np.int32)
    region_ts = np.zeros(total, np.int64)
    for s in range(len(lengths)):
        rows = slice(starts[s], starts[s + 1])
        region_sid[rows] = s
        region_ts[rows] = np.sort(rng.integers(0, 600_000, lengths[s]))
    region_valid = np.arange(total) < starts[-1]
    region_valid &= rng.random(total) > 0.15
    region_vals = rng.integers(0, 1000, total).astype(np.float64)

    r_lo, r_hi = int(starts[s_lo]), int(starts[s_hi])
    first, last, cut, off, base = _slice_plan([chunk, chunk], r_lo, r_hi)
    assert (first, last) == (0, 1) and off > 0 and r_lo - base > 0
    lo, hi = r_lo - base, r_hi - base
    table = SeriesTable(key=(), tags=(), starts=starts, codes=np.zeros((len(lengths), 0), np.int32))
    n_series, s_pad = s_hi - s_lo, 32
    bounds = _run_bounds(_Span(table, s_lo, s_hi, r_lo, r_hi, 0), s_pad, base, hi)
    assert bounds[0] == lo and bounds[n_series] == hi and (bounds[n_series:] == hi).all()

    def cut_out(plane):
        return np.asarray(_logical_slice(
            (jnp.asarray(plane[:chunk]), jnp.asarray(plane[chunk:])), cut, np.int32(off)
        ))

    rows = np.arange(cut[0])
    sid = np.clip(cut_out(region_sid) - s_lo, 0, s_pad - 1).astype(np.int32)
    ts, vals = cut_out(region_ts), cut_out(region_vals)
    valid = cut_out(region_valid) & (rows >= lo) & (rows < hi)
    start, step, range_, n_steps = 120_000, 60_000, 180_000, 8
    stats, present = range_windows_dyn(
        jnp.asarray(sid), jnp.asarray(ts), jnp.asarray(vals), jnp.asarray(valid), bounds,
        start=start, step=step, range_=range_, n_steps=n_steps, k=3,
    )
    mine = slice(r_lo, r_hi)
    in_table = region_valid[mine]
    want = _loop(region_sid[mine] - s_lo, region_ts[mine], region_vals[mine], region_vals[mine],
                 in_table, start, step, range_, n_steps, n_steps, s_pad)
    for f in dataclasses.fields(WindowStats):
        np.testing.assert_array_equal(np.asarray(getattr(stats, f.name)), want[f.name], f.name)
    np.testing.assert_array_equal(
        np.asarray(present), np.isin(np.arange(s_pad), region_sid[mine][in_table] - s_lo)
    )
    assert np.asarray(present)[:n_series].any() and not np.asarray(present)[n_series:].any()
