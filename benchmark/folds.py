"""Numpy helpers shared by the shapes' folds, the plain reference.

They read only the dataset's seed-made arrays and import nothing of the
program.  A shape's `reference` takes `dtype`: float64 is the reference;
float32 is the control, the same fold in the precision below the one the
configuration states, which the comparison has to refuse.
"""

from __future__ import annotations

import numpy as np


def tick_range(ds, start_ms: int, end_ms: int) -> tuple:
    """Tick indices [i0, i1) with start_ms <= ts < end_ms."""
    step = ds.scrape_s * 1000
    i0 = max(0, -(-(start_ms - ds.t0) // step))
    i1 = min(ds.ticks, -(-(end_ms - ds.t0) // step))
    return int(i0), int(max(i0, i1))


def bucket_max(col: np.ndarray, per_bucket: int) -> np.ndarray:
    """max over consecutive groups of `per_bucket` rows of axis 0."""
    return col.reshape(col.shape[0] // per_bucket, -1).max(axis=1)


def bucket_starts(ds, i0: int, i1: int, bucket_ms: int) -> tuple:
    """For ticks [i0, i1): the offset of each `time_bucket` group's first
    tick, and the groups' timestamps (buckets are aligned to the epoch, not
    to the window)."""
    bucket = ds.tick_ts()[i0:i1] // bucket_ms
    starts = np.nonzero(np.diff(bucket, prepend=bucket[0] - 1))[0]
    return starts, bucket[starts] * bucket_ms
