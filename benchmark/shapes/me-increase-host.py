"""One host's drill-down into another logical table of the same region:
`increase(nginx_handled{hostname="host_N"}[5m])` at 31 steps of a drawn half
hour, sent as `TQL EVAL` through `/v1/sql`.  The answer carries the series'
twelve labels."""

import numpy as np

KIND = "sql"
BAR = "value_rtol_f64"
COLUMNS = ("ts", "tag", "field")
SERIES = "one"
METRIC = "handled"


def request(ds, lit):
    from benchmark.promql_ref import RANGE_S, tql_eval

    host = ds.host_names[lit["host"]]
    return tql_eval(
        lit["start"],
        f'increase({ds.table_of(METRIC)}{{hostname="{host}"}}[{RANGE_S // 60}m])',
    )


def ticks(ds, lit):
    from benchmark.promql_ref import fetched_ticks

    return fetched_ticks(ds, lit["start"])


def reference(ds, lit, dtype=np.float64):
    """(the twelve labels in ascending name order, ts, value) of the host's
    present points, steps ascending."""
    from benchmark.promql_ref import RANGE_S, SPAN_S, STEP_S, extrapolated

    hosts = ds.label_order(np.array([lit["host"]]))
    steps = lit["start"] + np.arange(SPAN_S // STEP_S + 1, dtype=np.int64) * (STEP_S * 1000)
    matrix = extrapolated(
        ds, ds.samples[METRIC][:, hosts], steps, RANGE_S * 1000, False, dtype
    )
    s_idx, w_idx = np.nonzero(~np.isnan(matrix))
    return [ds.label_values[label][hosts][s_idx] for label in ds.labels] + [
        steps[w_idx], matrix[s_idx, w_idx].astype(np.float64),
    ]
