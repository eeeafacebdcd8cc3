"""Prometheus' canonical counter query over the whole fleet, as a range
query: `rate(<requests counter>[5m])` at 31 steps of a drawn half hour,
sent as `TQL EVAL` through `/v1/sql`."""

import numpy as np

KIND = "sql"
BAR = "value_rtol_f64"
COLUMNS = ("ts", "tag", "field")
SERIES = "all"


def request(ds, lit):
    from benchmark.promql_ref import RANGE_S, tql_eval

    return tql_eval(lit["start"], f"rate({ds.table}[{RANGE_S // 60}m])")


def ticks(ds, lit):
    from benchmark.promql_ref import fetched_ticks

    return fetched_ticks(ds, lit["start"])


def reference(ds, lit, dtype=np.float64):
    from benchmark.promql_ref import range_answer

    return range_answer(ds, lit["start"], ds.host_order, True, dtype)
