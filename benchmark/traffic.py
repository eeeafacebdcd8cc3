"""The one general traffic generator: a traffic file's parameters and a
seed in, an endless stream of (shape name, literals) out.

A traffic file (`benchmark/traffic/<traffic>.json`) holds
  clients  closed-loop clients (this generator drives them from one thread
           each; 1 in every mix so far)
  order    "round_robin": the shapes in turn; a cycle holds each `weight`
           times, the heavier ones spread through it
  shapes   [{"shape": <file under shapes/>, "weight": n,
             "literals": {<name>: <rule>}}]
  trace    {"after_s": seconds into the window at which the traced slice
            starts, "cycles": whole cycles of the mix it holds}
Literal rules, each drawn per request from the seed as TSBS's query
generator draws them:
  "uniform_host"                  a host index, uniform over the fleet
  {"uniform_window_s": s}         the start (ms) of a window of s seconds,
                                  uniform over the milliseconds at which
                                  it fits the data (TSBS draws it anywhere
                                  in the data); the data's start where it
                                  does not fit
  {"uniform_minute_after_s": s}   a minute boundary (ms), uniform from s
                                  seconds after the data's start to its end
Every seed draws from the same distributions, so it changes which hosts and
windows are asked for and never how much work a request is.

`edges` gives the warm-up each shape at both ends of what its rules can
draw: the program builds another variant of its tile program for a window
that starts within about 47 s of the data's start, which one draw in 77
hits and a warm-up of drawn literals alone leaves to the window
(`TPU_COMPILE_CACHE_MISSES` +1 in three runs of six: 0.7 s from the
persistent cache, 18 s where it compiles; my chip runs, PR 26, calls 9-10).
"""

from __future__ import annotations

import numpy as np


def draw(rule, ds, rng) -> int:
    if rule == "uniform_host":
        return int(rng.integers(0, ds.hosts))
    if isinstance(rule, dict) and "uniform_window_s" in rule:
        spare_ms = ds.end - ds.t0 - 1000 * rule["uniform_window_s"]
        return int(ds.t0 + (rng.integers(0, spare_ms + 1) if spare_ms > 0 else 0))
    if isinstance(rule, dict) and "uniform_minute_after_s" in rule:
        lo = rule["uniform_minute_after_s"] // 60
        hi = (ds.end - ds.t0) // 60_000
        return int(ds.t0 + 60_000 * rng.integers(min(lo, hi), hi + 1))
    raise ValueError(f"unknown literal rule {rule!r}")


def ends(rule, ds) -> tuple:
    """The lowest and the highest value `draw` gives with any weight: a
    millisecond inside a window's range, whose two ends are each one draw in
    millions and fall on a bucket's edge."""
    if rule == "uniform_host":
        return 0, ds.hosts - 1
    if isinstance(rule, dict) and "uniform_window_s" in rule:
        spare_ms = ds.end - ds.t0 - 1000 * rule["uniform_window_s"]
        return (ds.t0 + 1, ds.t0 + spare_ms - 1) if spare_ms > 2 else (ds.t0, ds.t0)
    if isinstance(rule, dict) and "uniform_minute_after_s" in rule:
        lo = rule["uniform_minute_after_s"] // 60
        hi = (ds.end - ds.t0) // 60_000
        return ds.t0 + 60_000 * min(lo, hi), ds.t0 + 60_000 * hi
    raise ValueError(f"unknown literal rule {rule!r}")


def edges(mix: dict, ds) -> list:
    """(shape name, literals) with every literal at its low end, then at its
    high end, for each shape of the mix that has literals."""
    return [
        (entry["shape"], {name: ends(rule, ds)[end] for name, rule in entry["literals"].items()})
        for entry in mix["shapes"] if entry.get("literals") for end in (0, 1)
    ]


def cycle(mix: dict) -> list:
    if mix["order"] != "round_robin":
        raise ValueError(f"unknown order {mix['order']!r}")
    most = max(s["weight"] for s in mix["shapes"])
    return [s for turn in range(most) for s in mix["shapes"] if s["weight"] > turn]


def requests(mix: dict, ds, seed: int, stream: int):
    """Endless (shape name, literals).  `stream` separates the warm-up's
    draws (0) from each client's (1, 2, ...)."""
    rng = np.random.default_rng([seed, 7, stream])
    turn = cycle(mix)
    while True:
        for entry in turn:
            yield entry["shape"], {
                name: draw(rule, ds, rng)
                for name, rule in sorted(entry.get("literals", {}).items())
            }
