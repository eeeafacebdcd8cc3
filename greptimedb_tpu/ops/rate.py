"""PromQL range-vector kernels: rate / increase / delta + *_over_time.

TPU-native port of the reference's PromQL extension operators
(reference src/promql/src/extension_plan/range_manipulate.rs building the
range-vector matrix, and src/promql/src/functions/extrapolate_rate.rs
implementing Prometheus' extrapolated rate — itself a port of Prometheus'
`extrapolatedRate`).

Design: no ragged range-vector matrix is materialized (dynamic shapes).
The samples arrive as flat columns sorted by (series, ts) with each
series' run bounds beside them, so the rows of a (series, window) cell
are one contiguous run of the plane: two binary searches inside the
series' run find it, and the statistics `rate` / `increase` / `delta` /
`count_over_time` / `last_over_time` / `timestamp()` read (count, first and
last timestamp and value) are a difference of row counts and reads of
carried planes at the run's first row and its last — selections, no
arithmetic, no scatter.  Only sum / min / max
(`avg/sum/min/max_over_time`) still assign every sample to the K eval
windows that can contain it (K = ceil(range/step), static from the query)
and reduce by segment.  Counter resets are removed up front by a per-series
monotonic re-accumulation so first/last arithmetic needs no pairwise pass
inside windows.

Inputs are flat sorted columns (series id, ts, value) — exactly what the
region scan produces after dedup — padded per `tiles.py`; invalid rows may
sit anywhere among them (`range_windows_dyn` has the precondition).
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class RangeSpec:
    """Static description of a PromQL range query evaluation grid."""

    start: int  # first eval timestamp (ms)
    end: int  # last eval timestamp (ms, inclusive)
    step: int  # eval step (ms)
    range_: int  # range-vector selector length (ms)

    @property
    def num_steps(self) -> int:
        return (self.end - self.start) // self.step + 1

    @property
    def windows_per_sample(self) -> int:
        return -(-self.range_ // self.step)  # ceil


# Row width of the blocked prefix scan below: one pass per doubling
# inside a row (log2 = 10 passes over the plane), then the same scan over
# the n/1024 row totals.
_SCAN_COLS = 1024


def _shift_right(x: jnp.ndarray, k: int, fill) -> jnp.ndarray:
    """x shifted k places along its LAST axis, `fill` shifted in."""
    pad = jnp.full(x.shape[:-1] + (k,), fill, x.dtype)
    return jnp.concatenate([pad, x[..., :-k]], axis=-1)


def prefix_scan(combine, xs: tuple, identity: tuple) -> tuple:
    """Inclusive prefix scan of the 1-D arrays `xs` (scanned together as
    one element tuple) under the associative `combine(left, right)`,
    with `identity` the per-array left-identity values.

    Two-level Hillis-Steele: the plane is viewed as [n/1024, 1024], each
    doubling step combines a row with itself shifted along the minor
    axis, and the row totals are scanned the same way (recursively) and
    folded back in.  Only whole-array shifts and elementwise ops — the
    forms whose compile time for the chip does not grow with the plane:
    `jnp.cumsum` on float64 and a 1-D `lax.associative_scan` (strided
    slices of a long 1-D array) both take minutes to compile at 2^20
    rows, this takes seconds at 2^24."""

    def scan_last_axis(ys):
        k = 1
        while k < ys[0].shape[-1]:
            shifted = tuple(_shift_right(y, k, i) for y, i in zip(ys, identity))
            ys = combine(shifted, ys)
            k *= 2
        return ys

    n = xs[0].shape[0]
    if n <= _SCAN_COLS:
        return scan_last_axis(xs)
    rows = -(-n // _SCAN_COLS)
    padded = rows * _SCAN_COLS
    ys = scan_last_axis(tuple(
        jnp.pad(x, (0, padded - n), constant_values=i).reshape(rows, _SCAN_COLS)
        for x, i in zip(xs, identity)
    ))
    totals = prefix_scan(combine, tuple(y[:, -1] for y in ys), identity)
    before = tuple(
        _shift_right(t, 1, i)[:, None] for t, i in zip(totals, identity)
    )
    ys = combine(before, ys)
    return tuple(y.reshape(padded)[:n] for y in ys)


def _sum_since_start(starts: jnp.ndarray, adds: jnp.ndarray) -> jnp.ndarray:
    """Inclusive running sum of `adds` that restarts at every row where
    `starts` is set (a segmented scan: the sum a row sees never leaves
    its own series, so its magnitude — and its rounding error — is the
    series', not the whole plane's)."""

    def combine(a, b):
        (fa, va), (fb, vb) = a, b
        return fa | fb, jnp.where(fb, vb, va + vb)

    return prefix_scan(combine, (starts, adds), (False, 0.0))[1]


@jax.named_scope("reset_strip")
def strip_counter_resets_segmented(
    series: jnp.ndarray, values: jnp.ndarray, valid: jnp.ndarray
) -> jnp.ndarray:
    """`strip_counter_resets` for PADDED tile planes: invalid rows (pad
    rows, dedup losers, rows outside the fetch range) may sit BETWEEN a
    series' samples, so "previous sample" means the previous VALID row of
    the same series.  It is a CARRY, as `_window_cells` carries its keys:
    one prefix scan brings the last valid row's series id and value
    forward over every row, and the carry shifted one place is the
    previous valid row's (series id -1: there is none; valid rows' series
    ids are >= 0).  Not a gather of that row: this chip gathers at ≈ 250 ×
    a scan's cost a row, and two gathers over the plane were most of a
    PromQL `rate` request.  Pure selection, so the values compared and
    added are the stored ones, bit for bit.
    The accumulation is `strip_counter_resets`' (a running sum of reset
    adds restarting at each series' first valid row); invalid rows
    contribute exact 0.0 terms, so on the same logical sample sequence
    the two agree to the last ulp or two (the scan tree's shape follows
    the row positions).  Only valid rows' outputs are meaningful."""

    def carry(a, b):
        take = b[0] >= 0
        return jnp.where(take, b[0], a[0]), jnp.where(take, b[1], a[1])

    sid_c, val_c = prefix_scan(
        carry,
        (jnp.where(valid, series, -1), jnp.where(valid, values, 0.0)),
        (-1, 0.0),
    )
    ps = _shift_right(sid_c, 1, -1)
    pv = _shift_right(val_c, 1, 0.0)
    same = valid & (ps >= 0) & (ps == series)
    reset_add = jnp.where(same & (values < pv), pv, 0.0)
    return values + _sum_since_start(valid & ~same, reset_add)


@jax.named_scope("reset_strip")
def strip_counter_resets(series: jnp.ndarray, values: jnp.ndarray, valid: jnp.ndarray):
    """Per-series monotonic re-accumulation: after a counter reset
    (v[i] < v[i-1]), add the pre-reset level so adjusted values never
    decrease.  increase() over [a, b] then equals adj[b] - adj[a].
    Matches prometheus' reset handling in extrapolatedRate."""
    prev_v = jnp.concatenate([values[:1], values[:-1]])
    prev_s = jnp.concatenate([series[:1], series[:-1]])
    prev_valid = jnp.concatenate([jnp.zeros(1, dtype=bool), valid[:-1]])
    same = (series == prev_s) & prev_valid & valid
    reset_add = jnp.where(same & (values < prev_v), prev_v, 0.0)
    # accumulation restarts at each series' first row (series are
    # contiguous in the sorted layout)
    return values + _sum_since_start(valid & ~same, reset_add)


@dataclass
class WindowStats:
    """Per-(series, window) statistics; arrays are [num_series * num_steps]."""

    count: jnp.ndarray
    first_ts: jnp.ndarray
    last_ts: jnp.ndarray
    first_val: jnp.ndarray
    # the sample at `first_ts` as stored, before any reset adjustment:
    # Prometheus' zero-point clamp reads the raw first sample
    first_raw: jnp.ndarray
    last_val: jnp.ndarray
    sum: jnp.ndarray
    min: jnp.ndarray
    max: jnp.ndarray


# The statistics a caller may ask `range_windows*` to reduce by segment;
# the other six fields of `WindowStats` are `count` and these selections,
# found by position.
REDUCTIONS = ("sum", "min", "max")
_SELECTED = ("first_ts", "last_ts", "first_val", "first_raw", "last_val")


_READS = {
    "avg_over_time": ("sum",),
    "sum_over_time": ("sum",),
    "min_over_time": ("min",),
    "max_over_time": ("max",),
}


def reductions_for(func: str) -> tuple[str, ...]:
    """Which of `REDUCTIONS` the range function `func` reads (`over_time`'s
    arms): none for the rate family, `count_over_time`, `last_over_time`
    and `timestamp()`."""
    return _READS.get(func, ())


def range_windows(
    series: jnp.ndarray,
    ts: jnp.ndarray,
    values: jnp.ndarray,
    valid: jnp.ndarray,
    spec: RangeSpec,
    run_bounds,
    acc_dtype=jnp.float64,
    raw_values: jnp.ndarray | None = None,
    reduce: tuple[str, ...] = REDUCTIONS,
) -> WindowStats:
    """Per-(series, window) statistics of the samples on a static grid.

    Window w covers (t_w - range, t_w] with t_w = start + w*step —
    Prometheus range selector semantics (left-open, right-closed).
    `run_bounds`, `raw_values` and `reduce`: see `range_windows_dyn`,
    whose precondition on the rows' order holds here too; its presence
    is left out.
    """
    stats, _present = range_windows_dyn(
        series, ts, values, valid, run_bounds,
        start=spec.start, step=spec.step, range_=spec.range_,
        n_steps=spec.num_steps, k=spec.windows_per_sample,
        acc_dtype=acc_dtype, raw_values=raw_values, reduce=reduce,
    )
    return stats


def _first_greater(keys, lo, hi, target, rounds):
    """Per element of the (broadcast-alike) `lo` / `hi` / `target`: the
    first position p of [lo, hi) with keys[p] > target, or hi; `keys` is
    non-decreasing over each [lo, hi) and no run is longer than
    2**rounds - 1.  `rounds` gathers of `keys`, one per halving; `rounds`
    may be traced (the loop then runs as many rounds as the longest run
    needs, not as many as the plane's length would)."""

    def halve(i, pos):
        cand = pos + jnp.left_shift(1, jnp.asarray(rounds - 1 - i, jnp.int32))
        below = jnp.take(keys, cand - 1, mode="clip") <= target
        return jnp.where((cand <= hi) & below, cand, pos)

    return jax.lax.fori_loop(0, rounds, halve, lo)


def sorted_run_bounds(series, num_series: int) -> np.ndarray:
    """[num_series + 1] int32 run bounds of host rows sorted by series
    with no row outside [0, num_series): series s is rows
    [bounds[s], bounds[s + 1]) (the legacy scan's dense samples, a test's
    plane)."""
    return np.searchsorted(
        np.asarray(series), np.arange(num_series + 1), side="left"
    ).astype(np.int32)


# A grid spanning fewer milliseconds than this, (n_steps - 1) * step +
# range, searches and reads 32-bit keys off its left edge (`_window_cells`).
_KEY_STOP = int(np.iinfo(np.int32).max)


def wide_grid(n_steps, step, range_):
    """Whether the grid of `n_steps` (the static, padded count) windows at
    `step` and `range_` ms spans 2**31 - 1 ms or more, so that
    `range_windows_dyn` searches and reads the 64-bit timestamps, not
    32-bit keys.  Python ints and traced scalars alike."""
    return (n_steps - 1) * step + range_ >= _KEY_STOP


@functools.partial(jax.jit, static_argnames=("n_steps",))
def _window_cells(ts, values, raw, valid, bounds, start, step, range_,
                  n_steps_actual, *, n_steps: int):
    """The selections of every (series, window) cell: (count, first_ts,
    last_ts, first_val, first_raw, last_val), each [num_series * n_steps]
    in [S*W] order and meaningful where count > 0 (`raw` None: first_raw
    is first_val); and [num_series] bools, which series hold a valid row.
    `bounds` is the [num_series + 1] int32 run bounds: series s is rows
    [bounds[s], bounds[s + 1]).  See `range_windows_dyn` for the order it
    needs."""
    n = ts.shape[0]
    tsmin = jnp.iinfo(jnp.int64).min
    rows = jnp.arange(n, dtype=jnp.int32)

    # Over every row, the last VALID row at or before it: its index, its
    # ts and value, and how many valid rows the plane holds up to here;
    # and, read back to front, the nearest valid row at or after it.
    # Whatever an invalid row holds (a pad row's zeros, a dedup loser, a
    # series the matcher masked) is never read: the carried keys are
    # non-decreasing within a run because the valid rows' are.
    def carry(a, b):
        take = b[0] >= 0
        return (
            jnp.where(take, b[0], a[0]), jnp.where(take, b[1], a[1]),
            jnp.where(take, b[2], a[2]), a[3] + b[3], jnp.maximum(a[4], b[4]),
        )

    last_valid, ts_c, v_c, n_valid, back = prefix_scan(
        carry,
        (
            jnp.where(valid, rows, -1),
            jnp.where(valid, ts, tsmin),
            jnp.where(valid, values, 0),
            valid.astype(jnp.int32),
            jnp.where(valid[::-1], rows, -1),
        ),
        (-1, tsmin, 0, 0, -1),
    )
    next_valid = (n - 1 - back)[::-1]  # n past the last valid row

    # a series' run [lo, hi): from its first valid row to one past its
    # last (the rows around them, up to the neighbours' valid rows, carry
    # another series' key or hold no sample: a masked neighbour or the pad
    # tail must not lengthen the search); a series without one is empty
    b_lo, b_hi = bounds[:-1], bounds[1:]
    lo = jnp.take(next_valid, b_lo, mode="fill", fill_value=n)
    hi = jnp.where(b_hi > 0, jnp.take(last_valid, b_hi - 1, mode="clip") + 1, 0)
    present = lo < hi
    hi = jnp.maximum(hi, lo)
    rounds = 32 - jax.lax.clz(jnp.max(hi - lo))
    num_series = bounds.shape[0] - 1
    lo, hi = lo[None, :], hi[None, :]

    # a window's rows [a, b) inside the run: a the first row past
    # t_w - range, b the first past t_w; both are valid rows (the carried
    # key changes only there) or the run's end.  Every target is the
    # grid's left edge + d with 0 <= d <= span, so where the span fits 31
    # bits the search runs on 32-bit keys, a row's distance from that edge
    # saturated at both ends (exact: a saturated key still compares with
    # every d as the row's ts does with the target); a 64-bit gather and
    # compare cost the chip three times as much.  The search is laid out
    # [2, W, S], the series on the minor axis: the chip tiles an array's
    # minor axis by 128 lanes, and [2, S, W] with a few steps there held
    # 128 / W times the cells (a 6-step panel over 2^20 series: 5.34 GB of
    # temporaries by the compiler's count, 2.16 GB laid out so).
    w = jnp.arange(n_steps, dtype=jnp.int64)[:, None]
    edge = start - range_
    d = jnp.stack([w * step, w * step + range_])
    run = jnp.broadcast_to(lo, (2, n_steps, num_series))
    # A cell reads its rows at two positions, [2, W, S] as the search lays
    # them out: a, which is a valid row where the cell holds one (the plain
    # planes and the carried ones agree there, and n_valid[a - 1] is
    # n_valid[a] - 1), and b - 1, whose carries are the window's last
    # sample's.  Inside a window a row's 32-bit key is exactly ts - edge,
    # so a timestamp is read as one word, beside n_valid in ONE gather of
    # the two stacked along the major axis (a [N, 2] plane would pad its
    # minor axis to 128 lanes).
    ends = jnp.array([0, 1], jnp.int32)[:, None, None]

    def narrow():
        key = jnp.where(last_valid >= 0, jnp.clip(ts_c - edge, -1, _KEY_STOP), -1)
        key = key.astype(jnp.int32)
        found = _first_greater(key, run, hi, d.astype(jnp.int32), rounds)
        words = jnp.take(
            jnp.stack([n_valid, key]), (found - ends).reshape(-1), axis=1, mode="clip"
        ).reshape((2,) + found.shape)
        return found, words[0], edge + words[1].astype(jnp.int64)

    def wide():
        found = _first_greater(ts_c, run, hi, edge + d, rounds)
        pos = found - ends
        return found, jnp.take(n_valid, pos, mode="clip"), jnp.take(ts_c, pos, mode="clip")

    found, nv, ts_at = jax.lax.cond(wide_grid(n_steps, step, range_), wide, narrow)
    a, b = found
    count = jnp.where((w < n_steps_actual) & (a < b), nv[1] - nv[0] + 1, 0)
    v_at = jnp.take(v_c, found - ends, mode="clip")
    raw_at = v_at[0] if raw is None else jnp.take(raw, a, mode="clip")

    def cells(x):  # [W, S] -> [S*W]
        return x.T.reshape(-1)

    return tuple(map(cells, (
        count, ts_at[0], ts_at[1], v_at[0], raw_at, v_at[1],
    ))), present


@jax.named_scope("range_windows")
def range_windows_dyn(
    series: jnp.ndarray,
    ts: jnp.ndarray,
    values: jnp.ndarray,
    valid: jnp.ndarray,
    run_bounds,
    start,
    step,
    range_,
    n_steps: int,
    k: int,
    acc_dtype=jnp.float64,
    n_steps_actual=None,
    raw_values: jnp.ndarray | None = None,
    reduce: tuple[str, ...] = REDUCTIONS,
) -> tuple[WindowStats, jnp.ndarray]:
    """`range_windows` with the evaluation grid split into STATIC shape
    parameters (`n_steps`, `k` — the [S*W] layout and the per-sample
    window unroll of the reductions) and DYNAMIC values (`start`/`step`/
    `range_` may be traced scalars), so one compiled program serves every
    query in a (padded-series, padded-steps, padded-k) shape bucket — a
    dashboard sliding its window re-hits the compile cache instead of
    re-tracing.  `n_steps_actual` (dynamic, defaults to `n_steps`) masks
    the padded windows past the real grid.

    Returns the stats and [num_series] bools, which series hold a valid
    row (a scanned series with no windowed sample still occupies a
    matrix row in the legacy path — `absent()` and binary ops see it).

    **The run bounds.**  `run_bounds` is an int32 [num_series + 1] array
    (its length gives the series space): series s is rows
    [run_bounds[s], run_bounds[s + 1]) of the plane, an empty run where
    the two are equal.  The caller knows them, so the kernel searches no
    series' run: `tile_exec._dispatch` reads them off the region's
    `SeriesTable`, `sorted_run_bounds` makes them of host rows sorted by
    series.

    **The order it needs.**  A series' VALID rows lie in its run, `ts`
    non-decreasing among them, so the rows of a cell are one run [a, b)
    of the plane; `series` holds s on them (the reductions group by it).
    Invalid rows may sit anywhere and hold anything: the kernel carries
    the last valid row's key over them (`_window_cells`), so a pad row's
    zeros at the tail or a masked series' rows in the middle break
    nothing.  Valid rows outside every run are in no cell.  What each
    caller hands it:

    * `tile_exec._region_stats` (the one-jit program and the mesh
      partials): the super-tile planes, whose real rows [0, num_rows) the
      cache sorted by (pk codes..., ts) at consolidation and keeps so
      through delta merges and dictionary repairs (value-sorted codes, the
      NULL slot last: a repair is an order-preserving map); `series` is
      the codes' run ordinals and `ts` a monotone map of the stored one,
      so ALL real rows are in (series, ts) order, valid or not, and the
      pad rows [num_rows, pad) (code 0, ts 0, never valid) sit at the
      tail; the series table's run starts are the bounds.
    * `engine._range_from_samples` (the legacy scan and subqueries): dense
      samples in `np.lexsort((ts, sid))` order, every row valid, no pad,
      the bounds `sorted_run_bounds(sid, num_series)`.

    **By position.**  count, first_ts, last_ts, first_val, last_val and
    first_raw are selections: two searches over the carried keys inside
    the series' run find [a, b), and the planes carried over every row
    (the last valid row's ts and value, the count of valid rows) are read
    at a and at b - 1 (`_window_cells`): the difference of the counts is
    `count`, the rest the first and the last sample.  No arithmetic
    touches a value, so the six are bit-equal to a loop over (series,
    window).  Of duplicate
    (series, ts) among valid rows (an append-mode table the dedup plane
    does not cover; Prometheus data has none) the FIRST in plane order is
    the window's first sample and the LAST its last, as the reference's
    `range_manipulate` + `extrapolate_rate` read them.  `raw_values` are
    the samples as stored where `values` are reset-adjusted (`rate` /
    `increase`): `first_raw` is the raw sample of the first row; without
    them `values` are the raw samples and `first_raw` is `first_val`.

    **By segment.**  `reduce` names which of sum / min / max to compute
    (`reductions_for(func)`; the rest keep their fill values): each still
    assigns a sample to the <= k windows that can contain it and reduces
    with one `jax.ops.segment_*` per unrolled window, because a
    prefix-sum difference rounds differently from a segment sum and
    min / max want a range-minimum structure.  On the chip such a
    reduction over 64-bit values lowers to a scatter of ~90 ns a row."""
    num_series = run_bounds.shape[0] - 1
    num_groups = num_series * n_steps
    if n_steps_actual is None:
        n_steps_actual = n_steps
    big = jnp.asarray(jnp.finfo(acc_dtype).max, acc_dtype)
    small = jnp.asarray(jnp.finfo(acc_dtype).min, acc_dtype)

    def fill(value, dtype=acc_dtype):
        return jnp.full(num_groups, value, dtype)

    # what a cell without a sample reads, and a window past the real grid
    empty = WindowStats(
        count=fill(0, jnp.int32),
        first_ts=fill(jnp.iinfo(jnp.int64).max, jnp.int64),
        last_ts=fill(jnp.iinfo(jnp.int64).min, jnp.int64),
        first_val=fill(small), first_raw=fill(small), last_val=fill(small),
        sum=fill(0), min=fill(big), max=fill(small),
    )
    if ts.shape[0] == 0:  # a subquery whose every point is NaN
        return empty, jnp.zeros(num_series, bool)

    v = values.astype(acc_dtype)
    ts = ts.astype(jnp.int64)
    cells, present = _window_cells(
        ts, v, None if raw_values is None else raw_values.astype(acc_dtype),
        valid, run_bounds, start, step, range_, n_steps_actual, n_steps=n_steps,
    )
    live = cells[0] > 0
    stats = dataclasses.replace(empty, count=cells[0], **{
        name: jnp.where(live, x, getattr(empty, name))
        for name, x in zip(_SELECTED, cells[1:])
    })
    if not reduce:
        return stats, present

    segs = num_groups + 1
    sum_ = jnp.zeros(segs, acc_dtype)
    min_ = jnp.full(segs, big, acc_dtype)
    max_ = jnp.full(segs, small, acc_dtype)
    # First window index that can contain sample t: smallest w with t_w >= t.
    w0 = jnp.ceil((ts - start) / step).astype(jnp.int32)
    w0 = jnp.maximum(w0, 0)
    for j in range(k):  # static unroll: samples fall in at most k windows
        w = w0 + j
        t_w = start + w.astype(jnp.int64) * step
        in_win = valid & (w >= 0) & (w < n_steps_actual) & (ts <= t_w) & (ts > t_w - range_)
        gid = jnp.where(in_win, series.astype(jnp.int32) * n_steps + w, num_groups)
        if "sum" in reduce:
            sum_ = sum_ + jax.ops.segment_sum(jnp.where(in_win, v, 0), gid, num_segments=segs)
        if "min" in reduce:
            min_ = jnp.minimum(
                min_, jax.ops.segment_min(jnp.where(in_win, v, big), gid, num_segments=segs)
            )
        if "max" in reduce:
            max_ = jnp.maximum(
                max_, jax.ops.segment_max(jnp.where(in_win, v, small), gid, num_segments=segs)
            )
    return dataclasses.replace(
        stats, sum=sum_[:num_groups], min=min_[:num_groups], max=max_[:num_groups]
    ), present


def extrapolated_rate(
    stats: WindowStats,
    spec: RangeSpec,
    kind: str,  # "rate" | "increase" | "delta"
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Prometheus `extrapolatedRate` on window stats; returns (value, defined).

    Port of the semantics in reference
    promql/src/functions/extrapolate_rate.rs (is_counter = rate/increase,
    is_rate divides by range seconds).  For counters the caller must have
    applied `strip_counter_resets` so last-first already includes resets,
    and handed `range_windows` the raw samples beside the adjusted ones:
    the zero-point clamp reads `stats.first_raw`.
    """
    return extrapolated_rate_dyn(
        stats, spec.start, spec.step, spec.range_, spec.num_steps, kind
    )


@jax.named_scope("extrapolate")
def extrapolated_rate_dyn(
    stats: WindowStats,
    start,
    step,
    range_,
    n_steps: int,
    kind: str,  # "rate" | "increase" | "delta"
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """`extrapolated_rate` with dynamic grid values (traced scalars OK);
    `n_steps` is the STATIC [S*W] layout width.  Same arithmetic, so
    results are bit-identical to the static form on the real windows."""
    num_groups = stats.count.shape[0]
    w = jnp.arange(num_groups, dtype=jnp.int64) % n_steps
    t_end = start + w * step
    t_start = t_end - range_

    defined = stats.count >= 2
    sampled_interval = (stats.last_ts - stats.first_ts).astype(jnp.float64)
    safe_count = jnp.maximum(stats.count, 2)
    avg_between = sampled_interval / (safe_count - 1).astype(jnp.float64)
    dur_to_start = (stats.first_ts - t_start).astype(jnp.float64)
    dur_to_end = (t_end - stats.last_ts).astype(jnp.float64)
    threshold = avg_between * 1.1

    extend_start = jnp.where(dur_to_start < threshold, dur_to_start, avg_between / 2.0)
    extend_end = jnp.where(dur_to_end < threshold, dur_to_end, avg_between / 2.0)

    result = (stats.last_val - stats.first_val).astype(jnp.float64)
    if kind in ("rate", "increase"):
        # Counter: cannot extrapolate below zero at the window start.  As
        # Prometheus does, the zero point comes from the RAW first sample
        # of the window (not the reset-adjusted one), and only where the
        # counter went up and that sample is not negative.
        first_raw = stats.first_raw.astype(jnp.float64)
        clamps = (result > 0) & (first_raw >= 0)
        zero_dur = sampled_interval * (first_raw / jnp.where(clamps, result, 1.0))
        extend_start = jnp.where(clamps, jnp.minimum(extend_start, zero_dur), extend_start)
    extrapolate_to = sampled_interval + extend_start + extend_end
    safe_si = jnp.where(sampled_interval == 0, 1.0, sampled_interval)
    value = result * (extrapolate_to / safe_si)
    if kind == "rate":
        value = value / (range_ / 1000.0)
    return value, defined


def merge_disjoint_stats(a: WindowStats, b: WindowStats) -> WindowStats:
    """Union of per-(series, window) stats from sources whose SERIES are
    disjoint (the partition rule puts each pk in exactly one region): a
    cell is non-empty in at most one input, so this is pure selection —
    no cross-source arithmetic — and the merged stats are bit-identical
    to computing each series on its owning source alone, regardless of
    merge order or device count."""
    own_a = a.count > 0

    def pick(x, y):
        return jnp.where(own_a, x, y)

    return WindowStats(
        count=pick(a.count, b.count),
        first_ts=pick(a.first_ts, b.first_ts),
        last_ts=pick(a.last_ts, b.last_ts),
        first_val=pick(a.first_val, b.first_val),
        first_raw=pick(a.first_raw, b.first_raw),
        last_val=pick(a.last_val, b.last_val),
        sum=pick(a.sum, b.sum),
        min=pick(a.min, b.min),
        max=pick(a.max, b.max),
    )


def over_time(stats: WindowStats, func: str) -> tuple[jnp.ndarray, jnp.ndarray]:
    """avg/sum/min/max/count/last_over_time from window stats
    (reference promql/src/functions/aggr_over_time.rs)."""
    defined = stats.count >= 1
    if func == "avg_over_time":
        return stats.sum / jnp.maximum(stats.count, 1), defined
    if func == "sum_over_time":
        return stats.sum, defined
    if func == "min_over_time":
        return stats.min, defined
    if func == "max_over_time":
        return stats.max, defined
    if func == "count_over_time":
        return stats.count.astype(jnp.float64), defined
    if func == "last_over_time":
        return stats.last_val, defined
    raise ValueError(f"unknown over_time func: {func}")
