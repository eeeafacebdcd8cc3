"""TSBS lastpoint: the newest value of one metric for every host."""

import numpy as np

KIND = "sql"
BAR = "value_rtol_f64"
COLUMNS = ("ts", "tag", "field")
SERIES = "all"


def request(ds, lit):
    return {"sql": (
        f"SELECT hostname, last_value(usage_user) AS last_user FROM {ds.table} "
        "GROUP BY hostname"
    )}


def ticks(ds, lit):
    return 0, ds.ticks


def reference(ds, lit, dtype=np.float64):
    order = ds.host_order
    return [
        ds.host_names[order],
        ds.usage_user[-1, order].astype(dtype).astype(np.float64),
    ]
