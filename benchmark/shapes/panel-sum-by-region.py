"""A dashboard's "last 5 minutes" panel over the same counter:
`sum by (region) (rate(nginx_requests[5m]))` at six steps a minute apart
from s to s + 5 min, sent as `TQL EVAL (s, s + 300, '60s')` through
`/v1/sql`.  A million series are folded into nine.  s is the drawn `start`
plus five minutes, so (s - 5m, s + 5m] lies in the data."""

import numpy as np

KIND = "sql"
BAR = "value_rtol_f64"
COLUMNS = ("ts", "tag", "field")
SERIES = "all"
METRIC = "requests"
BY = "region"
RANGE_S, SPAN_S, STEP_S = 300, 300, 60


def _steps(lit) -> np.ndarray:
    first = lit["start"] + RANGE_S * 1000
    return first + np.arange(SPAN_S // STEP_S + 1, dtype=np.int64) * (STEP_S * 1000)


def _seconds(ms: int) -> str:
    return f"{ms // 1000}.{ms % 1000:03d}"


def request(ds, lit):
    steps = _steps(lit)
    return {"sql": (
        f"TQL EVAL ({_seconds(int(steps[0]))}, {_seconds(int(steps[-1]))}, '{STEP_S}s') "
        f"sum by ({BY}) (rate({ds.table_of(METRIC)}[{RANGE_S // 60}m]))"
    )}


def ticks(ds, lit):
    from benchmark.promql_ref import window_ticks

    steps = _steps(lit)
    return window_ticks(ds, int(steps[0]) - RANGE_S * 1000, int(steps[-1]))


def reference(ds, lit, dtype=np.float64):
    """(region, ts, value): per region in ascending order of its name, the
    sum of its series' rates at every step at which one of them has a
    point, folded in `dtype`."""
    from benchmark.promql_ref import extrapolated

    steps = _steps(lit)
    matrix = extrapolated(ds, ds.samples[METRIC], steps, RANGE_S * 1000, True, dtype)
    present = ~np.isnan(matrix)
    rates = np.where(present, matrix, 0.0).astype(dtype)
    label = ds.label_values[BY]
    names, sums = [], []
    for value in np.unique(label):
        rows = label == value
        total = np.add.reduce(rates[rows], axis=0, dtype=dtype).astype(np.float64)
        names.append(value)
        sums.append(np.where(present[rows].any(axis=0), total, np.nan))
    sums = np.array(sums)
    g_idx, w_idx = np.nonzero(~np.isnan(sums))
    return [np.array(names)[g_idx], steps[w_idx], sums[g_idx, w_idx]]
