"""Requests per second by region over the last half hour, from a metric
engine's logical table: `sum by (region) (rate(nginx_requests[5m]))` at 31
steps of a drawn half hour, sent as `TQL EVAL` through `/v1/sql`.  All
4000 series of the logical table are folded into nine."""

import numpy as np

KIND = "sql"
BAR = "value_rtol_f64"
COLUMNS = ("ts", "tag", "field")
SERIES = "all"
METRIC = "requests"


def request(ds, lit):
    from benchmark.promql_ref import RANGE_S, tql_eval

    return tql_eval(
        lit["start"], f"sum by (region) (rate({ds.table_of(METRIC)}[{RANGE_S // 60}m]))"
    )


def ticks(ds, lit):
    from benchmark.promql_ref import fetched_ticks

    return fetched_ticks(ds, lit["start"])


def reference(ds, lit, dtype=np.float64):
    """(region, ts, value): per region in ascending order of its name, the
    sum of its hosts' rates at every step at which one of them has a point,
    folded in `dtype`."""
    from benchmark.promql_ref import RANGE_S, SPAN_S, STEP_S, extrapolated

    steps = lit["start"] + np.arange(SPAN_S // STEP_S + 1, dtype=np.int64) * (STEP_S * 1000)
    matrix = extrapolated(ds, ds.samples[METRIC], steps, RANGE_S * 1000, True, dtype)
    present = ~np.isnan(matrix)
    rates = np.where(present, matrix, 0.0).astype(dtype)
    region_of = ds.label_values["region"]
    names, sums = [], []
    for region in np.unique(region_of):
        rows = region_of == region
        total = np.add.reduce(rates[rows], axis=0, dtype=dtype).astype(np.float64)
        names.append(region)
        sums.append(np.where(present[rows].any(axis=0), total, np.nan))
    sums = np.array(sums)
    g_idx, w_idx = np.nonzero(~np.isnan(sums))
    return [np.array(names)[g_idx], steps[w_idx], sums[g_idx, w_idx]]
