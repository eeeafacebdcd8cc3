#!/usr/bin/env python3
"""One run of one cell of `BENCHMARK.json`, in one process on one chip.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (all of it `setup_s`): the cell's data made from `--seed`, ingested
through `Database.insert_rows`, flushed and prewarmed; the real `HttpServer`
on 127.0.0.1:0 in this process; every shape of the cell's mix sent until
its planes are resident and its programs compiled.  Then a closed loop over
the socket for `--seconds` (it closes when the cycle of the mix in flight
at `--seconds` completes, so every window holds whole cycles, and every
rate divides by that whole time).  Then,
outside any rate, every answer of that window is compared with the plain
reference.  The last line of standard output is the result.

`--rehearse k=v,...` is the CPU rehearsal for the tests: it overrides the
configuration's scale, and refuses to run on (or name) a TPU.  `--control`
adds the float32 control's readings to the result (see README.md).
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import compare, manifest, program, roofline, trace_reduce, traffic  # noqa: E402
from benchmark.client import Client, parse  # noqa: E402

# What a request that found its planes and programs ready does not move.
COLD_SIGNS = ("TILE_CACHE_MISSES", "TILE_COLD_SERVES", "TQL_TILE_COLD_SERVES")
SETTLE_CYCLES = 6


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", default="", help="CPU rehearsal: scale overrides k=v,...")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--keep-trace", default="", help="copy the traced run's .xplane.pb here")
    return ap.parse_args()


def find_device(chips: int, rehearse: bool) -> dict:
    from greptimedb_tpu.utils.jax_env import ensure_x64

    import jax

    ensure_x64()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if rehearse and device["platform"] == "tpu":
        raise SystemExit("--rehearse is the CPU rehearsal; on the chip run without it")
    if not rehearse and device["platform"] != "tpu":
        raise SystemExit(f"no accelerator: jax reports {device}")
    if not rehearse and device["count"] < chips:
        raise SystemExit(f"the cell needs {chips} chips, jax reports {device}")
    return device


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    return int(max(peaks))


def send(cell, ds, cl, shape: str, lit: dict):
    return cl.send(cell.shapes[shape].request(ds, lit))


def warm_up(cell, ds, db, cl, seed: int, compiles) -> int:
    """Each shape once cold (a first touch is served from the host while
    the background builder makes the family's planes and programs), each
    at both ends of what its literals can be, then whole cycles of the mix
    until one moves no cold sign and compiles nothing.  Its drawn literals
    come from a stream of their own."""
    stream = traffic.requests(cell.traffic, ds, seed, 0)
    n_cycle = len(traffic.cycle(cell.traffic))
    for _ in range(n_cycle):
        send(cell, ds, cl, *next(stream))
        program.wait_builds(db)
    for request in traffic.edges(cell.traffic, ds):
        send(cell, ds, cl, *request)
        program.wait_builds(db)
    for cycles in range(1, SETTLE_CYCLES + 1):
        before, t0 = program.counters(), time.perf_counter()
        for _ in range(n_cycle):
            send(cell, ds, cl, *next(stream))
        program.wait_builds(db)
        after = program.counters()
        cold = sum(after[k] - before[k] for k in COLD_SIGNS)
        if not cold and not compiles.between(t0, time.perf_counter()):
            return cycles
    raise RuntimeError(f"the mix did not settle in {SETTLE_CYCLES} cycles")


def run_window(cell, ds, cl, seed: int, seconds: float, trace_dir: str | None) -> dict:
    """The closed loop.  With `trace_dir`, a slice of whole cycles of the
    mix runs under `jax.profiler`, each request inside an annotation."""
    import jax

    stream = traffic.requests(cell.traffic, ds, seed, 1)
    n_cycle = len(traffic.cycle(cell.traffic))
    plan = cell.traffic["trace"] if trace_dir else None
    records, traced, slice_cm, slice_end = [], None, None, 0
    t_open = time.perf_counter()
    while True:
        now = time.perf_counter()
        if now - t_open >= seconds and len(records) % n_cycle == 0:
            break
        if plan and traced is None and now - t_open >= plan["after_s"] \
                and len(records) % n_cycle == 0:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            slice_cm = jax.profiler.TraceAnnotation(trace_reduce.SLICE)
            slice_cm.__enter__()
            traced, slice_end = [len(records), None], len(records) + plan["cycles"] * n_cycle
        shape, lit = next(stream)
        span = (
            jax.profiler.TraceAnnotation(trace_reduce.REQUEST, shape=shape)
            if slice_cm else contextlib.nullcontext()
        )
        t0 = time.perf_counter()
        with span:
            # the answer is parsed inside the timed call and kept as the
            # bytes that came: a window's parsed rows are millions of
            # objects for this process's collector, which the server shares
            request = cell.shapes[shape].request(ds, lit)
            try:
                body = cl.fetch(request)
                parse(request, body)
                error = None
            except Exception as e:  # noqa: BLE001 — a failed request is counted, not fatal
                body, error = None, repr(e)
        records.append({
            "shape": shape, "lit": lit, "request": request, "body": body,
            "error": error, "ms": (time.perf_counter() - t0) * 1000.0,
        })
        if slice_cm and len(records) >= slice_end:
            slice_cm.__exit__(None, None, None)
            jax.profiler.stop_trace()
            slice_cm, traced[1] = None, len(records)
    return {
        "records": records, "elapsed_s": time.perf_counter() - t_open,
        "t_open": t_open, "traced": traced,
    }


def check_answers(cell, ds, records: list, dtype=None) -> dict:
    """Every answer of the window against the reference.  With `dtype`, the
    control: the reference folded in that precision stands in the
    program's place.  Returns the widest gap per bar, the answers wrong in
    row count, keys or order, and those with a value over their bar."""
    bars = cell.config["guarantees"]
    gaps = {shape.BAR: 0.0 for shape in cell.shapes.values()}
    wrong, over = [], []
    for i, rec in enumerate(records):
        if rec["error"] is not None:
            continue
        shape = cell.shapes[rec["shape"]]
        want = shape.reference(ds, rec["lit"])
        rows = parse(rec["request"], rec["body"]) if dtype is None else list(zip(*[
            c.tolist() for c in shape.reference(ds, rec["lit"], dtype)
        ]))
        keys_ok, gap = compare.compare(rows, want)
        if not keys_ok:
            wrong.append(i)
            continue
        gaps[shape.BAR] = max(gaps[shape.BAR], gap)
        if gap > bars[shape.BAR]:
            over.append(i)
    return {"gaps": gaps, "wrong": wrong, "over": over}


def verdict(cell, checked: dict, failed_requests: int, moved: float, unhealthy: int) -> dict:
    """Each number compared beside its limit, as [number, limit]."""
    bars = cell.config["guarantees"]
    numbers = {
        "failed_requests": [failed_requests, 0],
        "answers_wrong": [len(checked["wrong"]), 0],
        "fallbacks": [moved, 0],
        "unhealthy_devices": [unhealthy, 0],
    }
    for bar, gap in sorted(checked["gaps"].items()):
        numbers[f"gap.{bar}"] = [gap, bars[bar]]
    return numbers


def main(args) -> dict:
    scale = {k: int(v) for k, v in (kv.split("=") for kv in args.rehearse.split(",") if kv)}
    cell = manifest.Cell(args.workload, scale)
    device = find_device(cell.chips, bool(args.rehearse))
    import jax

    compiles = program.CompileLog()
    home = os.path.join(ROOT, ".bench_home", args.workload)
    shutil.rmtree(home, ignore_errors=True)
    os.makedirs(home)
    clock: dict = {}
    db = server = None
    try:
        t0 = time.perf_counter()
        ds = cell.dataset(args.seed)
        db = program.open_database(home, cell.config["database"])
        # set-up only: the program's cache keeps no compile under 0.5 s, so
        # the eager programs of a first touch compile again in every process
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        clock.update(program.load(db, ds))
        clock["make_s"] = time.perf_counter() - t0 - sum(
            clock[k] for k in ("insert_s", "flush_s", "compact_s")
        )
        clock["prewarm_s"] = program.prewarm(db, ds.tables)
        server = program.start_server(db)
        cl = Client(server.address)
        t0 = time.perf_counter()
        clock["settle_cycles"] = warm_up(cell, ds, db, cl, args.seed, compiles)
        clock["warm_s"] = time.perf_counter() - t0
        clock["setup_s"] = time.perf_counter() - PROCESS_START

        before = program.counters()
        trace_dir = os.path.join(home, "trace") if args.trace else None
        window = run_window(cell, ds, cl, args.seed, args.seconds, trace_dir)
        t_close = window["t_open"] + window["elapsed_s"]
        after = program.counters()
        counters = {k: after[k] - before.get(k, 0.0) for k in after}
        peak = memory_peak_bytes()
        unhealthy = program.unhealthy_devices(db)
        trace = None
        if trace_dir and window["traced"]:
            xplane = trace_reduce.find_xplane(trace_dir)
            trace = trace_reduce.reduce(trace_reduce.load(xplane))
            if args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                shutil.copy(xplane, args.keep_trace)
    finally:
        if server is not None:
            server.stop()
        if db is not None:
            db.close()
        shutil.rmtree(home, ignore_errors=True)

    records = window["records"]
    checked = check_answers(cell, ds, records)
    errors = [i for i, r in enumerate(records) if r["error"] is not None]
    moved = sum(counters[k] for k in program.MUST_NOT_MOVE)
    numbers = verdict(cell, checked, len(errors), moved, len(unhealthy))
    correct = all(number <= limit for number, limit in numbers.values())
    # a window that fell back, degraded or lost the device counts every request as failed
    failed = len(records) if moved or unhealthy else len(
        {*errors, *checked["wrong"], *checked["over"]}
    )

    first, last = window["traced"] or (0, 0)
    run = {
        "requests": len(records), "ok": len(records) - failed,
        "elapsed_s": window["elapsed_s"],
        "latencies_ms": [r["ms"] for r in records],
        "counters": counters, "clock": clock, "trace": trace,
        "compiles": {
            "setup": compiles.between(PROCESS_START, window["t_open"]),
            "window": compiles.between(window["t_open"], t_close),
        },
        "traced_least_s": sum(
            roofline.least_seconds(
                roofline.shape_bytes(cell.shapes[r["shape"]], ds, r["lit"]), device["kind"]
            ) for r in records[first:last]
        ) if trace else 0.0,
    }
    metrics = {}
    kind, specs = (
        ("layer_metrics", cell.per_layer) if args.trace else ("end_to_end", cell.end_to_end)
    )
    for spec in specs:
        value = cell.read_metric(kind, spec["name"], run)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    device["memory_peak_bytes"] = peak
    result = {
        "correct": correct, "attempted": len(records), "failed": failed,
        "metrics": metrics, "device": device,
    }
    if trace:
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        result["breakdown"] = {k: trace[k] for k in ("device_ops", "idle_gaps")}
    result["run"] = {
        "workload": args.workload, "seed": args.seed, "seconds": args.seconds,
        "clock": clock, "errors": [records[i]["error"] for i in errors[:3]],
        # every counter of the program that moved inside the window
        "moved": {k: v for k, v in sorted(counters.items()) if v},
        "unhealthy": unhealthy,
        "wrong": [
            {"shape": records[i]["shape"], "lit": records[i]["lit"],
             "body": records[i]["body"][:300].decode(errors="replace")}
            for i in (checked["wrong"] + checked["over"])[:3]
        ],
        "by_shape": {
            name: {
                "n": len(ms), "median_ms": float(np.median(ms)), "max_ms": float(max(ms)),
            }
            for name in cell.shapes
            if (ms := [r["ms"] for r in records if r["shape"] == name])
        },
    }
    if args.control:
        control = check_answers(cell, ds, records, np.float32)
        result["control"] = verdict(cell, control, 0, 0, 0)
    result["compared"] = numbers
    return result


if __name__ == "__main__":
    cli = parse_args()
    try:
        out = main(cli)
    except BaseException:  # noqa: BLE001 — the boundary: any failure is the exit code, with no result line
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        # not sys.exit: a builder thread still inside an XLA compile must
        # not be able to turn a failure into a hang
        os._exit(1)
    print(json.dumps(out), flush=True)
    for name, (number, limit) in out["compared"].items():
        print(f"{name} = {number!r} (limit {limit!r})", file=sys.stderr)
    sys.stderr.flush()
    os._exit(0)
