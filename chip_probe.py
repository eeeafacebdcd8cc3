#!/usr/bin/env python3
"""chip_probe.py — stand-alone readings of the kernels under `chip_smoke.py`'s
PromQL requests and of the device boundary, at the smoke's plane size.

Where PERF.md section 5's kernel numbers come from.  Not a check and not a
benchmark: it asserts nothing about speed and prints what it read, one JSON
object per line (also written to `chiprun_out/probe.jsonl`).  Host clock
around `block_until_ready`; `first_s` is compile + first run, `ms` the runs
after it.  One process, about four minutes on one v5e:

    chiprun -- python chip_probe.py

The plane is `tql_cpu`'s: 4000 series x 720 samples (2 h at 10 s) = 2.88 M
rows padded to 2^22, `rate(...[5m])` at 60 s step -> 4096 x 128 cells, 8
window slots per sample.  Values are `uniform(0, 100)` from `--seed`.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "probe.jsonl")

SERIES, SAMPLES, ROWS = 4000, 720, 1 << 22
S_PAD, W_PAD, K = 4096, 128, 8
SCRAPE_MS, STEP_MS, RANGE_MS = 10_000, 60_000, 300_000


def emit(obj: dict):
    line = json.dumps(obj)
    print(line, flush=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def timed(name: str, fn, *args, reps: int = 3, **extra):
    """Emits the reading; returns what `fn` returned."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ms.append(round((time.perf_counter() - t0) * 1000.0, 3))
    emit({"probe": name, "first_s": round(first, 2), "ms": ms, **extra})
    return out


def probe_pack():
    """What `pack_f64_bits` returns on this device for the values a
    uniform draw never holds, next to the host's own bits."""
    from greptimedb_tpu.utils.jax_env import ensure_x64

    import jax
    import jax.numpy as jnp

    from greptimedb_tpu.ops.aggregate import pack_f64_bits, unpack_f64_bits

    ensure_x64()
    vals = np.array([
        0.0, -0.0, 100.0, -100.0, -37.25, 0.5, 1.0, 2.0**100, -(2.0**-100),
        np.inf, -np.inf, np.nan, 47.310768125,
    ])
    got = unpack_f64_bits(np.asarray(jax.jit(pack_f64_bits)(jnp.asarray(vals))))
    emit({
        "probe": "pack_f64_bits",
        "rows": [
            {"value": repr(float(v)), "got": repr(float(g)),
             "want_bits": hex(int(np.float64(v).view(np.uint64))),
             "got_bits": hex(int(np.float64(g).view(np.uint64)))}
            for v, g in zip(vals, got)
        ],
    })


def main(seed: int):
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    open(OUT, "w").close()
    probe_pack()

    import jax
    import jax.numpy as jnp

    from greptimedb_tpu.ops import rate as R

    dev = jax.devices()[0]
    emit({"device": {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices())}})

    # the plane: series-major, ts ascending, pad rows invalid at the end
    rng = np.random.default_rng(seed)
    n = SERIES * SAMPLES
    sid = np.zeros(ROWS, np.int32)
    sid[:n] = np.repeat(np.arange(SERIES, dtype=np.int32), SAMPLES)
    ts = np.zeros(ROWS, np.int64)
    ts[:n] = np.tile(np.arange(SAMPLES, dtype=np.int64) * SCRAPE_MS, SERIES)
    val = np.zeros(ROWS, np.float64)
    val[:n] = rng.uniform(0.0, 100.0, n)
    valid = np.arange(ROWS) < n
    start = np.int64(600_000)  # the smoke's grid: 10 min in, 110 steps
    nsteps = np.int32(110)

    t0 = time.perf_counter()
    planes = jax.block_until_ready(
        [jax.device_put(x) for x in (sid, ts, val, valid)]
    )
    emit({"probe": "upload", "bytes": sum(x.nbytes for x in (sid, ts, val, valid)),
          "ms": round((time.perf_counter() - t0) * 1000.0, 1)})
    d_sid, d_ts, d_val, d_valid = planes

    bump = jax.jit(lambda x: x + 1)
    one = jax.device_put(np.int32(1))
    jax.device_get(bump(one))
    walls = []
    for _ in range(20):
        t0 = time.perf_counter()
        jax.device_get(bump(one))
        walls.append((time.perf_counter() - t0) * 1000.0)
    emit({"probe": "dispatch+fetch scalar", "median_ms": round(float(np.median(walls)), 3),
          "max_ms": round(max(walls), 3)})
    t0 = time.perf_counter()
    jax.device_get(d_val)
    emit({"probe": "fetch f64 plane", "bytes": val.nbytes,
          "ms": round((time.perf_counter() - t0) * 1000.0, 1)})

    timed("strip_counter_resets_segmented",
          jax.jit(R.strip_counter_resets_segmented), d_sid, d_val, d_valid)
    timed("segmented f64 sum (prefix_scan)", jax.jit(R._sum_since_start),
          d_ts == 0, d_val)

    # the series' row bounds, as the tile path reads them off the series
    # table: the pad series empty at the end of the plane
    bounds = np.full(S_PAD + 1, ROWS, np.int32)
    bounds[:SERIES + 1] = np.arange(SERIES + 1, dtype=np.int32) * SAMPLES
    d_bounds = jax.device_put(bounds)

    def windows(sid, ts, v, valid, start, nsteps, raw=None, reduce=R.REDUCTIONS):
        return R.range_windows_dyn(
            sid, ts, v, valid, d_bounds, start=start, step=STEP_MS, range_=RANGE_MS,
            n_steps=W_PAD, k=K, n_steps_actual=nsteps, raw_values=raw, reduce=reduce,
        )[0]

    fields = ("count", "first_ts", "last_ts", "first_val", "first_raw",
              "last_val", "sum", "min", "max")

    def all_stats(*a):
        st = windows(*a)
        return tuple(getattr(st, f) for f in fields)

    def rate_program(sid, ts, v, valid, start, nsteps):
        """What the tile program runs for `rate`: every statistic it reads
        is found by row position (two searches over the carried keys, the
        carried planes read at two positions a cell), no segment reduction
        at all."""
        st = windows(sid, ts, R.strip_counter_resets_segmented(sid, v, valid),
                     valid, start, nsteps, raw=v, reduce=())
        vals, defined = R.extrapolated_rate_dyn(
            st, start, STEP_MS, RANGE_MS, W_PAD, "rate"
        )
        return jnp.where(defined, vals, jnp.nan)

    args = (d_sid, d_ts, d_val, d_valid, start, nsteps)
    timed("rate program: strip + range_windows_dyn + extrapolated_rate_dyn",
          jax.jit(rate_program), *args, reps=3, scatters=0)
    timed("window cells: carry scan + runs from the bounds + two searches + reads",
          lambda *a: R._window_cells(*a, n_steps=W_PAD),
          d_ts, d_val, None, d_valid, d_bounds, start, np.int64(STEP_MS),
          np.int64(RANGE_MS), nsteps)
    cells = jax.block_until_ready(
        (jnp.arange(S_PAD * W_PAD, dtype=jnp.int32) * 7) % (SERIES * SAMPLES)
    )
    for name, plane in (("f64", d_val), ("int64", d_ts), ("int32", d_sid)):
        timed(f"one gather of S*W rows from the {name} plane",
              jax.jit(lambda p, i: jnp.take(p, i, mode="clip")), plane, cells)
    stats = timed("range_windows_dyn k=8, all nine stats (no raw plane)", jax.jit(all_stats),
                  *args, reps=2, scatters=24)

    segs = S_PAD * W_PAD + 1
    gid = jax.block_until_ready(jnp.where(
        d_valid, d_sid * W_PAD + (d_ts // STEP_MS).astype(jnp.int32), segs - 1
    ))
    for name, fn, x in (
        ("one segment_sum f64 -> S*W+1", jax.ops.segment_sum, d_val),
        ("one segment_max int64 -> S*W+1", jax.ops.segment_max, d_ts),
        ("one segment_sum f32 -> S*W+1", jax.ops.segment_sum,
         d_val.astype(jnp.float32)),
    ):
        timed(name, jax.jit(lambda v, g, fn=fn: fn(v, g, num_segments=segs)),
              x, gid)

    timed("extrapolated_rate_dyn", jax.jit(
        lambda st, s: R.extrapolated_rate_dyn(
            R.WindowStats(**dict(zip(fields, st))), s, STEP_MS, RANGE_MS,
            W_PAD, "rate",
        )
    ), stats, start)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    main(ap.parse_args().seed)
