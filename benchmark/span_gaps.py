#!/usr/bin/env python3
"""Whose time is the device's idle time?  A kept trace, split by the
program's own stages.

    python3 benchmark/run.py --workload <cell> ... --trace 1 --keep-trace <dir>
    python3 benchmark/span_gaps.py <dir>/<name>.xplane.pb [--json]
    python3 benchmark/span_gaps.py <file.xplane.pb> --record <out.json>

The program's stages (`utils/tracing.stage`: `http.request`, `query.tpu`,
`tile.readback`, ...) are `jax.profiler.TraceAnnotation`s, so inside the
benchmark's traced slice they lie on the host plane, on the clock of the
device's `XLA Ops`.  One closed-loop client means one request at a time,
so attribution by time is exact: every instant of the slice in which no
operation ran on the device belongs to the `bench.request` that covers it
(or lies between two), to the part of that request before its first
device operation, between its operations or after its last, and to the
innermost stage open at that instant (or to the client, where a request
is open and its `http.request` is not).

Printed: the idle seconds by (request shape, phase, stage) with each
shape's milliseconds per request; the share put down to a named stage,
the client or "between requests"; where each shape's last device
operation ends (the stage that covers that instant, and how long before
`tile.decode` opens); and, where the trace's event metadata carries the
`jax.named_scope` path (`tf_op`; read with the protobuf classes of
`xprof` or `tensorflow`, whichever is installed), the device's busy
seconds by scope.  Nothing of the benchmark imports this file;
`trace_reduce.py` keeps the numbers the benchmark reports.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import trace_reduce  # noqa: E402
from benchmark.trace_reduce import DEVICE_PLANE, OPS_LINE, REQUEST, SLICE  # noqa: E402

# A stage of the program: a dotted lower-case name (the README's span
# taxonomy).  The runtime's own host events hold `::`, capitals or spaces.
STAGE = re.compile(r"^[a-z_]+(\.[a-z_]+)+$")
ROOT_STAGE, DECODE_STAGE = "http.request", "tile.decode"
CLIENT, BETWEEN, UNNAMED = "client", "between requests", "no program stage in the trace"
PHASES = ("before first op", "between ops", "after last op", "no device op")


def is_stage(name: str) -> bool:
    return bool(STAGE.match(name)) and not name.startswith("bench.")


def load(path: str) -> tuple:
    """(`planes`, `scopes`): the planes in `trace_reduce.load`'s form with
    the device's op line, the benchmark's annotations and the program's
    stages kept; `scopes` gives, per device plane, each kept op's
    named-scope path in the same order ("" where the trace has none).

    Not through `trace_reduce.load`: it files a plane's lines under their
    names, and the host plane has one line per thread, several of them
    named `python3` (client, handler, kernel thread, device worker), so
    all but the last are lost.  Here a line's key is its name and its
    position."""
    from jax.profiler import ProfileData

    by_text = op_scopes(path)
    planes: dict = {}
    scopes: dict = {}
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        for i, line in enumerate(plane.lines):
            if device and line.name != OPS_LINE:
                continue
            events = []
            for ev in line.events:
                if device:
                    scopes.setdefault(plane.name, []).append(by_text.get(ev.name, ""))
                elif ev.name == REQUEST:
                    name = f"{REQUEST} {dict(ev.stats).get('shape', '')}"
                    events.append([name, float(ev.start_ns), float(ev.duration_ns)])
                    continue
                elif ev.name != SLICE and not is_stage(ev.name):
                    continue
                events.append(
                    [trace_reduce.short_name(ev.name), float(ev.start_ns), float(ev.duration_ns)]
                )
            if events:
                planes.setdefault(plane.name, {})[line.name if device else f"{line.name}#{i}"] = events
    return planes, scopes


def op_scopes(path: str) -> dict:
    """HLO text of a device op -> the `jax.named_scope` path it was traced
    under, without the primitive's own name: `tf_op` of the event's
    metadata, which `jax.profiler.ProfileData` does not expose.  Empty
    where no protobuf class for `.xplane.pb` is installed."""
    xplane_pb2 = None
    for module in ("xprof.protobuf.xplane_pb2", "tensorflow.tsl.profiler.protobuf.xplane_pb2"):
        try:
            xplane_pb2 = __import__(module, fromlist=["XSpace"])
            break
        except ImportError:
            continue
    if xplane_pb2 is None:
        return {}
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        keys = [k for k, m in plane.stat_metadata.items() if m.name == "tf_op"]
        for meta in plane.event_metadata.values():
            for stat in meta.stats:
                if stat.metadata_id in keys:
                    value = stat.str_value or plane.stat_metadata[stat.ref_value].name
                    out[meta.name] = value.rstrip(":").rpartition("/")[0]
    return out


def self_seconds(events: list, labels: list, lo: float, hi: float) -> dict:
    """Seconds by label, each op counted for the time no op nested inside
    it covers (a `%while` or `%conditional` encloses its body's ops),
    clipped to [lo, hi]."""
    out: dict = {}
    stack: list = []  # [end, label, start, seconds its children covered]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, label, start, covered = stack.pop()
            took = max(0.0, min(end, hi) - max(start, lo))
            out[label] = out.get(label, 0.0) + max(0.0, took - covered)
            if stack:
                stack[-1][3] += took

    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    for i in order:
        _name, start, dur = events[i]
        close(start)
        stack.append([start + dur, labels[i], start, 0.0])
    close(float("inf"))
    return {k: v / 1e9 for k, v in out.items() if v > 0}


def busy_runs(events: list, lo: float, hi: float) -> list:
    """The op line as sorted, disjoint [start, end] runs in which some
    operation ran, clipped to [lo, hi]."""
    return trace_reduce.union([
        (max(s, lo), min(s + d, hi)) for _n, s, d in events if s + d > lo and s < hi
    ])


def attribute(planes: dict, scopes: dict | None = None) -> dict | None:
    """The slice's idle seconds by (shape, phase, stage); see the module's
    docstring.  None where the trace holds no slice or no device plane."""
    slices, requests = trace_reduce._annotations(planes)
    devices = {
        name: lines[OPS_LINE] for name, lines in planes.items()
        if name.startswith(DEVICE_PLANE) and OPS_LINE in lines
    }
    if not slices or not devices:
        return None
    lo, hi = slices[0]
    stages = sorted(
        (start, start + dur, name)
        for plane, lines in planes.items() if not plane.startswith(DEVICE_PLANE)
        for events in lines.values() for name, start, dur in events if is_stage(name)
    )
    requests = [r for r in requests if r[1] > lo and r[0] < hi]
    cuts = sorted({lo, hi, *(
        t for a, b, _n in [*requests, *stages] for t in (a, b) if lo < t < hi
    )})

    def innermost(t):
        open_ = [s for s in stages if s[0] <= t < s[1]]
        return max(open_, key=lambda s: (s[0], -s[1])) if open_ else None

    idle: dict = {}
    last_op: dict = {}
    busy_s = 0.0
    for events in devices.values():
        merged = busy_runs(events, lo, hi)
        starts = [a for a, _b in merged]
        total = [0.0]
        for a, b in merged:
            total.append(total[-1] + (b - a))
        busy_s += total[-1] / 1e9

        def busy(a, b):
            """Busy nanoseconds inside [a, b]."""
            i, j = bisect.bisect_right(starts, a), bisect.bisect_left(starts, b)
            inside = total[j] - total[i]
            if i > 0:  # the run that opened before a
                inside += max(0.0, min(merged[i - 1][1], b) - a)
            if j > i and merged[j - 1][1] > b:  # the last run laps over b
                inside -= merged[j - 1][1] - b
            return inside

        ops = {}  # request -> (first op start, last op end)
        for r in requests:
            i, j = bisect.bisect_left(starts, r[0]), bisect.bisect_left(starts, r[1])
            if j > i:
                ops[r] = (merged[i][0], merged[j - 1][1])
                covering = innermost(merged[j - 1][1])
                decode = [s for s in stages if s[2] == DECODE_STAGE and r[0] <= s[0] < r[1]]
                last_op.setdefault(r[2], []).append({
                    "ends_in": covering[2] if covering else None,
                    # the request's last decode: a rerun verdict dispatches twice
                    "ms_before_decode": (decode[-1][0] - merged[j - 1][1]) / 1e6 if decode else None,
                })
        edges = sorted({*cuts, *(t for pair in ops.values() for t in pair if lo < t < hi)})
        for a, b in zip(edges, edges[1:]):
            mid = (a + b) / 2.0
            inside = [r for r in requests if r[0] <= mid < r[1]]
            if not inside:
                key = ("", "", BETWEEN)
            else:
                r = inside[0]
                first, last = ops.get(r, (None, None))
                phase = PHASES[3] if first is None else (
                    PHASES[0] if mid < first else PHASES[2] if mid >= last else PHASES[1]
                )
                stage = innermost(mid)
                in_server = any(s[2] == ROOT_STAGE and s[0] <= mid < s[1] for s in stages)
                name = stage[2] if stage and in_server else (CLIENT if stages else UNNAMED)
                key = (r[2], phase, name)
            seconds = ((b - a) - busy(a, b)) / 1e9
            if seconds > 0:
                idle[key] = idle.get(key, 0.0) + seconds
    n = len(devices)
    idle = {k: v / n for k, v in idle.items()}
    total_idle = sum(idle.values())
    named = sum(v for (_s, _p, name), v in idle.items() if name != UNNAMED)
    counts: dict = {}
    for r in requests:
        counts[r[2]] = counts.get(r[2], 0) + 1
    by_scope = {}
    if scopes and any(any(labels) for labels in scopes.values()):
        for plane, events in devices.items():
            for label, s in self_seconds(events, scopes[plane], lo, hi).items():
                by_scope[label or "(no scope)"] = by_scope.get(label or "(no scope)", 0.0) + s / n
    return {
        "window_s": (hi - lo) / 1e9, "busy_s": busy_s / n, "idle_s": total_idle,
        "named_share": named / total_idle if total_idle else 1.0,
        "requests": counts,
        "idle": sorted(([*k, v] for k, v in idle.items()), key=lambda row: -row[3]),
        "last_op": last_op,
        "busy_by_scope": sorted(by_scope.items(), key=lambda kv: -kv[1]),
    }


def record(path: str) -> dict:
    """A kept trace cut to its first cycle of the mix, the device's ops
    coalesced into busy runs, with what `attribute` reads there."""
    planes, _scopes = load(path)
    slices, requests = trace_reduce._annotations(planes)
    lo = slices[0][0]
    shapes = [r[2] for r in requests if r[0] >= lo]
    cycle = shapes.index(shapes[0], 1) if shapes[0] in shapes[1:] else len(shapes)
    cut = [r for r in requests if r[0] >= lo][cycle - 1][1] + 1e5
    out: dict = {}
    for plane, lines in planes.items():
        for line, events in lines.items():
            if plane.startswith(DEVICE_PLANE):
                # the busy runs are all `attribute` needs of the op line,
                # and small enough to keep as a test's input
                kept = [["busy", a, b - a] for a, b in busy_runs(events, lo, cut)]
            else:
                kept = [
                    [n, s, cut - s if n == SLICE else d] for n, s, d in events
                    if n == SLICE or (s >= lo and s + d <= cut)
                ]
            if kept:
                out.setdefault(plane, {})[line] = kept
    got = attribute(out)
    return {"planes": out, "expect": {k: got[k] for k in ("window_s", "busy_s", "idle_s")}}


def render(got: dict) -> str:
    lines = [
        f"slice {got['window_s']:.6f} s, device busy {got['busy_s']:.6f} s, "
        f"idle {got['idle_s']:.6f} s; {100 * got['named_share']:.1f} % of the idle "
        f"seconds named (a stage, the client, between requests)",
        "",
        f"{'shape':<24}{'phase':<18}{'stage':<22}{'idle s':>10}{'share':>8}{'ms/request':>12}",
    ]
    rest = [0, 0.0]
    for shape, phase, stage, seconds in got["idle"]:
        per = seconds * 1e3 / got["requests"][shape] if shape else 0.0
        if per < 0.5 and stage != BETWEEN:  # under half a millisecond a request
            rest = [rest[0] + 1, rest[1] + seconds]
            continue
        lines.append(
            f"{shape or '-':<24}{phase or '-':<18}{stage:<22}{seconds:>10.4f}"
            f"{100 * seconds / got['idle_s']:>7.1f}%{per:>12.2f}"
        )
    if rest[0]:
        lines.append(f"({rest[0]} more rows under 0.5 ms a request: {rest[1]:.4f} s)")
    lines.append("")
    for shape, ends in got["last_op"].items():
        where = sorted({str(e["ends_in"]) for e in ends})
        before = [e["ms_before_decode"] for e in ends if e["ms_before_decode"] is not None]
        lines.append(
            f"last device op of {shape}: ends in {', '.join(where)}"
            + (f"; {min(before):.2f} to {max(before):.2f} ms before {DECODE_STAGE} opens"
               if before else "")
        )
    if got["busy_by_scope"]:
        lines += ["", "device busy seconds by named scope (each op's self time):"]
        lines += [f"  {s:>10.4f}  {scope}" for scope, s in got["busy_by_scope"][:24]]
    else:
        lines += ["", "the op events carry no named scope (or no protobuf class reads them)"]
    return "\n".join(lines)


if __name__ == "__main__":
    args = sys.argv[1:]
    if len(args) == 3 and args[1] == "--record":
        with open(args[2], "w") as out:
            json.dump(record(args[0]), out)
        sys.exit(0)
    result = attribute(*load(args[0]))
    if result is None:
        sys.exit("no traced slice or no device plane in this trace")
    print(json.dumps(result) if "--json" in args else render(result))
