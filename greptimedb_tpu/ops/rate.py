"""PromQL range-vector kernels: rate / increase / delta + *_over_time.

TPU-native port of the reference's PromQL extension operators
(reference src/promql/src/extension_plan/range_manipulate.rs building the
range-vector matrix, and src/promql/src/functions/extrapolate_rate.rs
implementing Prometheus' extrapolated rate — itself a port of Prometheus'
`extrapolatedRate`).

Design: instead of materializing a ragged range-vector matrix (dynamic
shapes), every sample is assigned to the K eval windows that can contain it
(K = ceil(range/step), static from the query), and per-(series, window)
statistics are computed with segment reductions.  Counter resets are removed
up front by a per-series monotonic re-accumulation so first/last arithmetic
needs no pairwise pass inside windows.

Inputs are flat sorted columns (series id, ts, value) — exactly what the
region scan produces after dedup — padded per `tiles.py`.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


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


def _running_max(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive running maximum of a non-negative-or-(-1) index array."""
    (out,) = prefix_scan(
        lambda a, b: (jnp.maximum(a[0], b[0]),), (x,), (-1,)
    )
    return out


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
    the same series, found with a running max over valid row indices.
    The accumulation is `strip_counter_resets`' (a running sum of reset
    adds restarting at each series' first valid row); invalid rows
    contribute exact 0.0 terms, so on the same logical sample sequence
    the two agree to the last ulp or two (the scan tree's shape follows
    the row positions).  Only valid rows' outputs are meaningful."""
    n = series.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    last_valid = _running_max(jnp.where(valid, idx, -1))
    prev_idx = jnp.concatenate([jnp.full((1,), -1, jnp.int32), last_valid[:-1]])
    safe_prev = jnp.clip(prev_idx, 0, None)
    pv = jnp.take(values, safe_prev)
    ps = jnp.take(series, safe_prev)
    same = valid & (prev_idx >= 0) & (ps == series)
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


def range_windows(
    series: jnp.ndarray,
    ts: jnp.ndarray,
    values: jnp.ndarray,
    valid: jnp.ndarray,
    spec: RangeSpec,
    num_series: int,
    acc_dtype=jnp.float64,
    raw_values: jnp.ndarray | None = None,
) -> WindowStats:
    """Assign each sample to its <=K containing windows and reduce.

    Window w covers (t_w - range, t_w] with t_w = start + w*step —
    Prometheus range selector semantics (left-open, right-closed).
    `raw_values`: the samples as stored, where `values` went through
    `strip_counter_resets` (see `range_windows_dyn`).
    """
    return range_windows_dyn(
        series, ts, values, valid,
        start=spec.start, step=spec.step, range_=spec.range_,
        n_steps=spec.num_steps, k=spec.windows_per_sample,
        num_series=num_series, acc_dtype=acc_dtype, raw_values=raw_values,
    )


@jax.named_scope("range_windows")
def range_windows_dyn(
    series: jnp.ndarray,
    ts: jnp.ndarray,
    values: jnp.ndarray,
    valid: jnp.ndarray,
    start,
    step,
    range_,
    n_steps: int,
    k: int,
    num_series: int,
    acc_dtype=jnp.float64,
    n_steps_actual=None,
    raw_values: jnp.ndarray | None = None,
) -> WindowStats:
    """`range_windows` with the evaluation grid split into STATIC shape
    parameters (`n_steps`, `k` — the [S*W] layout and the per-sample
    window unroll) and DYNAMIC values (`start`/`step`/`range_` may be
    traced scalars), so one compiled program serves every query in a
    (padded-series, padded-steps, padded-k) shape bucket — a dashboard
    sliding its window re-hits the compile cache instead of re-tracing.
    `n_steps_actual` (dynamic, defaults to `n_steps`) masks the padded
    windows past the real grid; arithmetic on the surviving windows is
    identical to the static form, so results are bit-identical.
    `raw_values` are the samples as stored where `values` are reset-
    adjusted (`rate` / `increase`): `first_raw` is then the raw sample at
    `first_ts`, found through one more segment reduction per unrolled
    window, over ROW INDICES (int32: an eighth of what a reduction over
    the chip's emulated 64-bit values costs) and one gather; without them
    `values` are the raw samples and `first_raw` is `first_val`."""
    num_groups = num_series * n_steps
    if n_steps_actual is None:
        n_steps_actual = n_steps
    segs = num_groups + 1
    v = values.astype(acc_dtype)
    raw = None if raw_values is None else raw_values.astype(acc_dtype)

    tsmax = jnp.iinfo(jnp.int64).max
    tsmin = jnp.iinfo(jnp.int64).min
    big = jnp.asarray(jnp.finfo(acc_dtype).max, acc_dtype)
    small = jnp.asarray(jnp.finfo(acc_dtype).min, acc_dtype)

    count = jnp.zeros(segs, jnp.int32)
    first_ts = jnp.full(segs, tsmax, jnp.int64)
    last_ts = jnp.full(segs, tsmin, jnp.int64)
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
        count = count + jax.ops.segment_sum(in_win.astype(jnp.int32), gid, num_segments=segs)
        first_ts = jnp.minimum(
            first_ts, jax.ops.segment_min(jnp.where(in_win, ts, tsmax), gid, num_segments=segs)
        )
        last_ts = jnp.maximum(
            last_ts, jax.ops.segment_max(jnp.where(in_win, ts, tsmin), gid, num_segments=segs)
        )
        sum_ = sum_ + jax.ops.segment_sum(jnp.where(in_win, v, 0), gid, num_segments=segs)
        min_ = jnp.minimum(
            min_, jax.ops.segment_min(jnp.where(in_win, v, big), gid, num_segments=segs)
        )
        max_ = jnp.maximum(
            max_, jax.ops.segment_max(jnp.where(in_win, v, small), gid, num_segments=segs)
        )

    count, first_ts, last_ts = count[:num_groups], first_ts[:num_groups], last_ts[:num_groups]
    sum_, min_, max_ = sum_[:num_groups], min_[:num_groups], max_[:num_groups]

    # Second pass: values at the first/last timestamps (two-field argmin/max).
    fv = jnp.full(num_groups + 1, small, acc_dtype)
    lv = jnp.full(num_groups + 1, small, acc_dtype)
    n_rows = ts.shape[0]
    rows = jnp.arange(n_rows, dtype=jnp.int32)
    first_row = jnp.full(num_groups + 1, n_rows, jnp.int32)
    for j in range(k):
        w = w0 + j
        t_w = start + w.astype(jnp.int64) * step
        in_win = valid & (w >= 0) & (w < n_steps_actual) & (ts <= t_w) & (ts > t_w - range_)
        gid = jnp.where(in_win, series.astype(jnp.int32) * n_steps + w, num_groups)
        safe_gid = jnp.clip(gid, 0, num_groups - 1)
        at_first = in_win & (ts == first_ts[safe_gid])
        at_last = in_win & (ts == last_ts[safe_gid])
        fv = jnp.maximum(
            fv, jax.ops.segment_max(jnp.where(at_first, v, small), gid, num_segments=num_groups + 1)
        )
        lv = jnp.maximum(
            lv, jax.ops.segment_max(jnp.where(at_last, v, small), gid, num_segments=num_groups + 1)
        )
        if raw is not None:
            first_row = jnp.minimum(
                first_row,
                jax.ops.segment_min(
                    jnp.where(at_first, rows, n_rows), gid, num_segments=num_groups + 1
                ),
            )
    first_val = fv[:num_groups]
    last_val = lv[:num_groups]
    if raw is None:
        first_raw = first_val
    else:
        first_row = first_row[:num_groups]
        first_raw = jnp.where(
            first_row < n_rows, jnp.take(raw, jnp.minimum(first_row, n_rows - 1)), small
        )

    return WindowStats(
        count=count,
        first_ts=first_ts,
        last_ts=last_ts,
        first_val=first_val,
        first_raw=first_raw,
        last_val=last_val,
        sum=sum_,
        min=min_,
        max=max_,
    )


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
