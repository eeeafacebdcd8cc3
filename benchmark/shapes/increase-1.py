"""One host's drill-down: `increase(<requests counter>{hostname="host_N"}[5m])`
at 31 steps of a drawn half hour, sent as `TQL EVAL` through `/v1/sql`."""

import numpy as np

KIND = "sql"
BAR = "value_rtol_f64"
COLUMNS = ("ts", "tag", "field")
SERIES = "one"


def request(ds, lit):
    from benchmark.promql_ref import RANGE_S, tql_eval

    host = ds.host_names[lit["host"]]
    return tql_eval(
        lit["start"], f'increase({ds.table}{{hostname="{host}"}}[{RANGE_S // 60}m])'
    )


def ticks(ds, lit):
    from benchmark.promql_ref import fetched_ticks

    return fetched_ticks(ds, lit["start"])


def reference(ds, lit, dtype=np.float64):
    from benchmark.promql_ref import range_answer

    return range_answer(ds, lit["start"], np.array([lit["host"]]), False, dtype)
