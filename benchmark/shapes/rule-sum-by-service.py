"""The recording rule `service:nginx_requests:rate5m` as Prometheus'
rule evaluator runs it, an instant query at one evaluation time t:
`sum by (service) (rate(nginx_requests[5m]))`, sent as `TQL EVAL (t, t,
'1m')` through `/v1/sql`.  A million series are folded into twenty.  t is
the drawn `start` plus five minutes, so (t - 5m, t] lies in the data."""

import numpy as np

KIND = "sql"
BAR = "value_rtol_f64"
COLUMNS = ("ts", "tag", "field")
SERIES = "all"
METRIC = "requests"
BY = "service"
RANGE_S = 300


def _at(lit) -> int:
    return lit["start"] + RANGE_S * 1000


def _seconds(ms: int) -> str:
    return f"{ms // 1000}.{ms % 1000:03d}"


def request(ds, lit):
    t = _seconds(_at(lit))
    return {"sql": (
        f"TQL EVAL ({t}, {t}, '1m') "
        f"sum by ({BY}) (rate({ds.table_of(METRIC)}[{RANGE_S // 60}m]))"
    )}


def ticks(ds, lit):
    from benchmark.promql_ref import window_ticks

    return window_ticks(ds, _at(lit) - RANGE_S * 1000, _at(lit))


def reference(ds, lit, dtype=np.float64):
    """(service, ts, value): per service in ascending order of its name,
    the sum of its series' rates at t, folded in `dtype`."""
    from benchmark.promql_ref import extrapolated

    steps = np.array([_at(lit)], np.int64)
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
