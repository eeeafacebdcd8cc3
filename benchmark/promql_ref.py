"""Prometheus' `extrapolatedRate`, the plain reference of the PromQL shapes.

Written from Prometheus' published algorithm (promql/functions.go,
`extrapolatedRate`), window by window in plain numpy over the dataset's
ground-truth samples; it imports nothing of the program.  For the eval
timestamp t of a range query and the selector `m[range]`:

  samples   those with t - range < ts <= t; fewer than two give no point
  increase  last - first, plus the previous value of every sample that is
            lower than the one before it (a counter that restarted)
  to the    durationToStart = first.ts - (t - range), durationToEnd =
  edges     t - last.ts; either one that reaches 1.1 x the average gap
            between the samples becomes half that gap
  the zero  a counter cannot lie below 0: where the increase is > 0 and
  point     the RAW first sample >= 0, durationToStart is at most
            sampledInterval x first / increase
  factor    (sampledInterval + durationToStart + durationToEnd) /
            sampledInterval; `rate` divides by the range's seconds besides

Every series of the datasets here is scraped at the same instants, so a
window's timestamps are scalars and its values one row per series.  All of
the arithmetic runs in `dtype`: float64 is the reference, float32 the
control that the comparison has to refuse.
"""

from __future__ import annotations

import numpy as np


def window_ticks(ds, lo_ms: int, hi_ms: int) -> tuple:
    """Tick indices [i0, i1) with lo_ms < ts <= hi_ms."""
    step = ds.scrape_s * 1000
    i0 = max(0, (lo_ms - ds.t0) // step + 1)
    i1 = min(ds.ticks, (hi_ms - ds.t0) // step + 1)
    return int(i0), int(max(i0, i1))


def extrapolated(ds, samples: np.ndarray, steps_ms, range_ms: int, per_second: bool,
                 dtype=np.float64) -> np.ndarray:
    """`rate` (`per_second`) or `increase` of the counters `samples`
    ([ticks, series], as stored) at every eval timestamp of `steps_ms`:
    [series, steps], NaN where Prometheus gives no point."""
    f = np.dtype(dtype).type
    tick_ts = ds.tick_ts()
    out = np.full((samples.shape[1], len(steps_ms)), np.nan)
    for w, t in enumerate(int(t) for t in steps_ms):
        i0, i1 = window_ticks(ds, t - range_ms, t)
        if i1 - i0 < 2:
            continue
        v = samples[i0:i1].astype(dtype)
        first, last = v[0], v[-1]
        dropped = np.where(v[1:] < v[:-1], v[:-1], f(0)).sum(axis=0, dtype=dtype)
        increase = last - first + dropped
        first_ts, last_ts = int(tick_ts[i0]), int(tick_ts[i1 - 1])
        sampled = f((last_ts - first_ts) / 1000.0)
        gap = sampled / f(i1 - i0 - 1)
        to_start = f((first_ts - (t - range_ms)) / 1000.0)
        to_end = f((t - last_ts) / 1000.0)
        threshold = gap * f(1.1)
        if to_start >= threshold:
            to_start = gap / f(2)
        if to_end >= threshold:
            to_end = gap / f(2)
        clamps = (increase > 0) & (first >= 0)
        to_zero = sampled * (first / np.where(clamps, increase, f(1)))
        start = np.where(clamps & (to_zero < to_start), to_zero, to_start)
        value = increase * ((sampled + start + to_end) / sampled)
        if per_second:
            value = value / f(range_ms / 1000.0)
        out[:, w] = value
    return out


# The grid both shapes of `prom-rate-range` ask for: `m[5m]` at 31 steps of a
# drawn half hour, a minute apart.
RANGE_S, SPAN_S, STEP_S = 300, 1800, 60


def tql_eval(start_ms: int, promql: str) -> dict:
    """The `/v1/sql` request: `TQL EVAL` over the half hour from `start_ms`,
    its bounds as seconds with three decimals, digit for digit."""
    lo, hi = (f"{ms // 1000}.{ms % 1000:03d}" for ms in (start_ms, start_ms + SPAN_S * 1000))
    return {"sql": f"TQL EVAL ({lo}, {hi}, '{STEP_S}s') {promql}"}


def fetched_ticks(ds, start_ms: int) -> tuple:
    """The samples of (start - 5 min, start + 30 min], for the roofline."""
    return window_ticks(ds, start_ms - RANGE_S * 1000, start_ms + SPAN_S * 1000)


def range_answer(ds, start_ms: int, hosts: np.ndarray, per_second: bool, dtype) -> list:
    """The expected columns of `rate` / `increase` of `hosts`' counters over
    the grid from `start_ms`, series in the order `hosts` has them."""
    steps = start_ms + np.arange(SPAN_S // STEP_S + 1, dtype=np.int64) * (STEP_S * 1000)
    matrix = extrapolated(ds, ds.requests[:, hosts], steps, RANGE_S * 1000, per_second, dtype)
    return long_format(ds, hosts, matrix, steps)


def long_format(ds, hosts: np.ndarray, matrix: np.ndarray, steps_ms: np.ndarray) -> list:
    """The answer's columns as `TQL EVAL` gives them: (hostname, ts, value),
    one row per present point, series by series in the order `hosts` has
    them, steps ascending; a series with no point at all is left out."""
    s_idx, w_idx = np.nonzero(~np.isnan(matrix))
    return [
        ds.host_names[hosts][s_idx],
        np.asarray(steps_ms, np.int64)[w_idx],
        matrix[s_idx, w_idx].astype(np.float64),
    ]
