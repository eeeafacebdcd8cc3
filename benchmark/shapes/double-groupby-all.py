"""TSBS double-groupby-all: avg of all ten metrics per (host, hour) over 12 h.

The reference folds ten fields over 17.28 M window rows for every answer of
the window (a whole hour of the data is summed once per field and dtype and
kept on the dataset, the window's cut hours at its ends each time), so that
checking a window's answers stays a small part of a run.
"""

import numpy as np

KIND = "sql"
BAR = "value_rtol_avg_f32"
COLUMNS = ("ts", "tag") + ("field",) * 10
SERIES = "all"
WINDOW_S = 12 * 3600
HOUR_MS = 3600_000


def request(ds, lit):
    lo = lit["start"]
    avgs = ", ".join(f"avg({f}) AS avg_{f}" for f in ds.fields)
    return {"sql": (
        f"SELECT hostname, time_bucket('1h', ts) AS tb, {avgs} "
        f"FROM {ds.table} WHERE ts >= {lo} AND ts < {lo + WINDOW_S * 1000} "
        "GROUP BY hostname, tb"
    )}


def ticks(ds, lit):
    from benchmark.folds import tick_range

    return tick_range(ds, lit["start"], lit["start"] + WINDOW_S * 1000)


def _hours(ds, field, dtype):
    """(first tick of each hour of the data, each host's sum over it in
    `dtype`): made once per field and dtype, kept on the dataset."""
    from benchmark.folds import bucket_starts

    kept = ds.__dict__.setdefault("hour_sums", {})
    key = (field, np.dtype(dtype).str)
    if key not in kept:
        starts, _tb = bucket_starts(ds, 0, ds.ticks, HOUR_MS)
        kept[key] = (starts, np.add.reduceat(
            ds.values[field].astype(dtype), starts, axis=0, dtype=dtype,
        ))
    return kept[key]


def reference(ds, lit, dtype=np.float64):
    from benchmark.folds import bucket_starts

    i0, i1 = ticks(ds, lit)
    starts, tb = bucket_starts(ds, i0, i1, HOUR_MS)
    ends = np.append(starts[1:], i1 - i0)
    counts = (ends - starts).astype(dtype)
    order = ds.host_order
    out = [np.repeat(ds.host_names[order], len(tb)), np.tile(tb, ds.hosts)]
    for field in ds.fields:
        hour_starts, hour_sums = _hours(ds, field, dtype)
        sums = np.empty((len(starts), ds.hosts), dtype)
        for k, (a, b) in enumerate(zip(starts + i0, ends + i0)):
            h = np.searchsorted(hour_starts, a)
            whole = h < len(hour_starts) and hour_starts[h] == a and (
                (hour_starts[h + 1] if h + 1 < len(hour_starts) else ds.ticks) == b
            )
            sums[k] = hour_sums[h] if whole else np.add.reduce(
                ds.values[field][a:b].astype(dtype), axis=0, dtype=dtype,
            )
        avg = sums / counts[:, None]
        out.append(avg[:, order].T.reshape(-1).astype(np.float64))
    return out
