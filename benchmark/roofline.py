"""The table of peaks and the bytes a request cannot avoid reading.

Both scan programs are memory-bound folds (a compare and an add or max per
value read), so the roofline of a request is bytes over HBM bandwidth: the
rows its time predicate and series selection leave, times the stored widths
of the columns its text names.  What the program really reads (padded
planes, every series, every pass) is its own affair and the reason the
share is low.
"""

from __future__ import annotations

import functools
import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


@functools.lru_cache(maxsize=None)
def _table() -> dict:
    with open(PEAKS_FILE) as f:
        return json.load(f)


def peaks(device_kind: str) -> dict:
    table = _table()
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE}")
    return table[device_kind]


def row_bytes(columns: tuple, stored_bytes: dict) -> int:
    return sum(stored_bytes[c] for c in columns)


def request_bytes(rows: int, columns: tuple, stored_bytes: dict) -> int:
    return rows * row_bytes(columns, stored_bytes)


def shape_bytes(shape, ds, lit: dict) -> int:
    """Least bytes of one request of `shape` with literals `lit`."""
    i0, i1 = shape.ticks(ds, lit)
    series = ds.hosts if shape.SERIES == "all" else 1
    return request_bytes((i1 - i0) * series, shape.COLUMNS, ds.cfg["stored_bytes"])


def least_seconds(nbytes: int, device_kind: str) -> float:
    return nbytes / peaks(device_kind)["hbm_bytes_per_s"]
