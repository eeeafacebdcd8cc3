"""The comparison that decides `correct`: one served answer against the
reference's columns.  Row count, row order and every key column exact;
float columns by the widest relative gap, which the caller holds to the
bar the configuration states."""

from __future__ import annotations

import numpy as np


def _floats(col) -> np.ndarray:
    try:
        return np.asarray(col, dtype=np.float64)
    except TypeError:  # a NULL in the column
        return np.array([np.nan if x is None else x for x in col], np.float64)


def compare(rows: list, want: list) -> tuple:
    """(keys_ok, widest relative gap of the float columns).  `want` is the
    reference's columns in the answer's column order: float64 arrays are
    values (NaN = NULL), anything else is a key."""
    n = len(want[0])
    if len(rows) != n:
        return False, float("inf")
    if n == 0:
        return True, 0.0
    got = list(zip(*rows))
    if len(got) != len(want):
        return False, float("inf")
    gap = 0.0
    for g, w in zip(got, want):
        if w.dtype == np.float64:
            g = _floats(g)
            null = np.isnan(w)
            if (np.isnan(g) != null).any():
                return False, float("inf")
            scale = np.maximum(np.abs(w[~null]), 1e-300)
            rel = np.abs(g[~null] - w[~null]) / scale
            if rel.size:
                gap = max(gap, float(rel.max()))
        elif not (np.asarray(g) == w).all():
            return False, gap
    return True, gap
