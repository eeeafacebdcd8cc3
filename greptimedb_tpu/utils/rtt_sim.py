"""Synthetic link delay for counting device boundary crossings offline.

A knob for tests and `bench.py --mode mixed --rtt-ms N` (env
`GRAFT_BENCH_RTT_MS`), not a description of any deployment: when
configured, every dispatch submission and every `device_get` sleeps a
symmetric half of N ms on each side of the crossing.  That makes the
number of crossings per query visible in wall time on any backend — the
regime where batching and mega-program fusion (ONE invocation per batch
tick) pay for themselves — without an accelerator.  The sleeps are host
sleeps: they say nothing about a device's speed and are never reported
as a device time.

Off by default (`configure(0)` / unset env): `round_trip()` is a
zero-overhead no-op and the hot path is bit-for-bit today's.  Ghost
dispatches inside the fused cold build never pay the delay (the build
pipelines its uploads).
"""

from __future__ import annotations

import contextlib
import time

_RTT_S: float = 0.0


def configure(rtt_ms: float) -> None:
    """Set the synthetic symmetric round-trip delay in milliseconds
    (0 disables).  Process-global: the bench owns it, tests must reset."""
    global _RTT_S
    _RTT_S = max(float(rtt_ms), 0.0) / 1000.0


def rtt_ms() -> float:
    return _RTT_S * 1000.0


@contextlib.contextmanager
def round_trip(enabled: bool = True):
    """Sleep half the configured delay before and after the wrapped
    device boundary crossing (submit or fetch)."""
    half = _RTT_S / 2.0 if enabled else 0.0
    if half > 0.0:
        time.sleep(half)
    try:
        yield
    finally:
        if half > 0.0:
            time.sleep(half)
