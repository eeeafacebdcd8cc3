"""TSBS double-groupby-1: avg of one metric per (host, hour) over 12 h."""

import numpy as np

KIND = "sql"
BAR = "value_rtol_avg_f32"
COLUMNS = ("ts", "tag", "field")
SERIES = "all"
WINDOW_S = 12 * 3600


def request(ds, lit):
    lo = lit["start"]
    return {"sql": (
        "SELECT hostname, time_bucket('1h', ts) AS tb, avg(usage_user) AS avg_usage_user "
        f"FROM {ds.table} WHERE ts >= {lo} AND ts < {lo + WINDOW_S * 1000} "
        "GROUP BY hostname, tb"
    )}


def ticks(ds, lit):
    from benchmark.folds import tick_range

    return tick_range(ds, lit["start"], lit["start"] + WINDOW_S * 1000)


def reference(ds, lit, dtype=np.float64):
    from benchmark.folds import bucket_starts

    i0, i1 = ticks(ds, lit)
    starts, tb = bucket_starts(ds, i0, i1, 3600_000)
    block = ds.usage_user[i0:i1].astype(dtype)
    counts = np.diff(np.append(starts, i1 - i0)).astype(dtype)
    avg = np.add.reduceat(block, starts, axis=0, dtype=dtype) / counts[:, None]
    order = ds.host_order
    return [
        np.repeat(ds.host_names[order], len(tb)),
        np.tile(tb, ds.hosts),
        avg[:, order].T.reshape(-1).astype(np.float64),
    ]
