"""From a profiler trace to the device's numbers.

`load` reads an `.xplane.pb` with `jax.profiler.ProfileData` into plain
lists, `{plane: {line: [[name, start_ns, duration_ns], ...]}}` (the form
the recorded trace under `tests/benchmark_harness/` is kept in); `reduce`
turns them into busy seconds, the traced slice's seconds, the device
operations that took most time and the longest idle gaps.

Busy is the union of the intervals in which an operation ran on a device
(the plane's `XLA Ops` line), clipped to the slice and averaged over the
device planes.  The slice is the benchmark's own `bench.slice` annotation
on the host plane, which shares the trace's clock with the device planes;
a gap is named by the `bench.request` annotation it falls in and by
whether that request's device work lies before it, after it or both; one
that spans several requests says how many.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SLICE, REQUEST = "bench.slice", "bench.request"
_HLO = re.compile(r"^(%[\w.\-]+) = \(*(\w+\[[\d,]*\])?")


def short_name(name: str) -> str:
    """An op event's name is its whole HLO text; keep the instruction's
    name and its first result shape: `%fusion.71 f32[131073]`."""
    m = _HLO.match(name)
    return " ".join(g for g in m.groups() if g) if m else name[:80]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, keep=None) -> dict:
    """Planes -> lines -> events.  `keep(plane, line, name)` filters; the
    default keeps the device planes' op line and the benchmark's own
    annotations."""
    from jax.profiler import ProfileData

    if keep is None:
        def keep(plane, line, name):
            if plane.startswith(DEVICE_PLANE):
                return line == OPS_LINE
            return name in (SLICE, REQUEST)
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = []
            for ev in line.events:
                if not keep(plane.name, line.name, ev.name):
                    continue
                name = short_name(ev.name)
                if name == REQUEST:
                    name = f"{REQUEST} {dict(ev.stats).get('shape', '')}"
                events.append([name, float(ev.start_ns), float(ev.duration_ns)])
            if events:
                out.setdefault(plane.name, {})[line.name] = events
    return out


def union(intervals: list) -> list:
    """Sorted, merged [start, end] intervals."""
    merged: list = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def _annotations(planes: dict) -> tuple:
    slices, requests = [], []
    for name, lines in planes.items():
        if name.startswith(DEVICE_PLANE):
            continue
        for events in lines.values():
            for ev, start, dur in events:
                if ev == SLICE:
                    slices.append((start, start + dur))
                elif ev.startswith(REQUEST):
                    requests.append((start, start + dur, ev[len(REQUEST):].strip()))
    return slices, sorted(requests)


def reduce(planes: dict, top: int = 10):
    """None where the trace holds no device plane or no slice (a CPU
    rehearsal): a reader then reports nothing, never a 0."""
    slices, requests = _annotations(planes)
    devices = {
        name: lines[OPS_LINE]
        for name, lines in planes.items()
        if name.startswith(DEVICE_PLANE) and OPS_LINE in lines
    }
    if not slices or not devices:
        return None
    lo, hi = slices[0]
    busy_ns, by_op, gaps = 0.0, {}, []
    for events in devices.values():
        clipped = []
        for name, s, d in events:
            if s + d > lo and s < hi:
                a, b = max(s, lo), min(s + d, hi)
                clipped.append((a, b))
                by_op[name] = by_op.get(name, 0.0) + (b - a)
        merged = union(clipped)
        busy_ns += sum(b - a for a, b in merged)
        edges = [lo] + [x for pair in merged for x in pair] + [hi]
        gaps += [
            (edges[i + 1] - edges[i], edges[i])
            for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]
        ]
    busy_ns /= len(devices)
    starts = sorted(s for events in devices.values() for _n, s, _d in events)
    named = []
    for length, start in sorted(gaps, reverse=True)[:top]:
        mid = start + length / 2.0
        over = [r for r in requests if r[0] < start + length and r[1] > start]
        if len(over) > 2:  # two: the gap only laps over a neighbour's edge
            named.append([f"across {len(over)} requests with no device op", length / 1e9])
            continue
        inside = [r for r in over if r[0] <= mid < r[1]]
        if not inside:
            named.append(["between requests", length / 1e9])
            continue
        a, b, shape = inside[0]
        ran_before = any(a <= s < start for s in starts)
        ran_after = any(start + length <= s < b for s in starts)
        where = {
            (False, True): "before its first device op",
            (True, False): "after its last device op",
            (True, True): "between its device ops",
            (False, False): "with no device op",
        }[ran_before, ran_after]
        named.append([f"in request {shape}, {where}", length / 1e9])
    ops = sorted(by_op.items(), key=lambda kv: kv[1], reverse=True)[:top]
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": len(devices),
        "device_ops": [[name, ns / 1e9 / len(devices)] for name, ns in ops],
        "idle_gaps": named,
    }


def summary(path: str) -> dict:
    """Every plane and line of a trace with its event count, span and most
    frequent names: what to read by hand before trusting `reduce`."""
    planes = load(path, keep=lambda *_a: True)
    out = {}
    for plane, lines in planes.items():
        for line, events in lines.items():
            names: dict = {}
            for name, _s, dur in events:
                names[name] = names.get(name, 0.0) + dur
            top = sorted(names.items(), key=lambda kv: kv[1], reverse=True)[:8]
            out[f"{plane} | {line}"] = {
                "events": len(events),
                "first_ns": min(e[1] for e in events),
                "last_ns": max(e[1] + e[2] for e in events),
                "top": [[n, d / 1e9] for n, d in top],
            }
    return out


def record(path: str, max_events: int) -> dict:
    """A cut of a real trace small enough to keep as a test's input: the
    start of the slice, as long as `max_events` device events reach, with
    the slice's annotation shortened to it and what `reduce` reads there."""
    planes = load(path)
    lo = _annotations(planes)[0][0][0]
    device = sorted(
        (e for name, lines in planes.items() if name.startswith(DEVICE_PLANE)
         for e in lines.get(OPS_LINE, []) if e[1] >= lo),
        key=lambda e: e[1],
    )[:max_events]
    cut = device[-1][1] + device[-1][2]
    for name, lines in planes.items():
        for line, events in lines.items():
            kept = [e for e in events if lo <= e[1] < cut or e[0] == SLICE]
            lines[line] = [[n, s, cut - s if n == SLICE else min(d, cut - s)] for n, s, d in kept]
    got = reduce(planes)
    return {"planes": planes, "expect": {k: got[k] for k in ("busy_s", "window_s")}}


if __name__ == "__main__":
    import json
    import sys

    if len(sys.argv) == 4:  # <xplane.pb> <max device events> <out.json>
        with open(sys.argv[3], "w") as out:
            json.dump(record(sys.argv[1], int(sys.argv[2])), out)
        sys.exit(0)
    print(json.dumps(summary(sys.argv[1]), indent=1))
    print(json.dumps(reduce(load(sys.argv[1])), indent=1))
