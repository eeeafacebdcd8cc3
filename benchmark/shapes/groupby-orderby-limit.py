"""TSBS groupby-orderby-limit: max per minute before a drawn instant,
the newest five minutes."""

import numpy as np

KIND = "sql"
BAR = "value_rtol_f64"
COLUMNS = ("ts", "field")
SERIES = "all"


def request(ds, lit):
    return {"sql": (
        "SELECT time_bucket('1m', ts) AS minute, max(usage_user) AS mu "
        f"FROM {ds.table} WHERE ts < {lit['end']} GROUP BY minute "
        "ORDER BY minute DESC LIMIT 5"
    )}


def ticks(ds, lit):
    from benchmark.folds import tick_range

    return tick_range(ds, ds.t0, lit["end"])


def reference(ds, lit, dtype=np.float64):
    from benchmark.folds import bucket_max

    per_min = 60 // ds.scrape_s
    i0, i1 = ticks(ds, lit)
    minutes = i1 // per_min  # `end` lies on a minute
    first = max(minutes - 5, 0)
    mx = bucket_max(ds.usage_user[first * per_min:minutes * per_min].astype(dtype), per_min)
    tb = ds.t0 + np.arange(first, minutes, dtype=np.int64) * 60_000
    return [tb[::-1], mx[::-1].astype(np.float64)]
