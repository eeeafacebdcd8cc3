#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, default `device.*` settings, the normal entry points:

  * deployment: TSBS `cpu-only` (4000 hosts, 10 s scrape, the 10 CPU metrics)
    for `--hours` (default 12 = 17.28 M rows), made from `--seed`, ingested
    through `Database.insert_rows` (partition split, WAL, memtable), flushed
    to SSTs and prewarmed; the last 2 h of `usage_user` again as the
    single-field PromQL metric table `tql_cpu`, and once more as `me_cpu`,
    a metric-engine logical table of twelve labels on a physical table;
  * requests, over a real `HttpServer` socket in this process: five TSBS SQL
    shapes on `/v1/sql`, `rate(...)` and `increase(...)` on
    `/v1/prometheus/api/v1/query_range` (the `rate` over the logical table
    too, equal to the mito table's), and one InfluxDB line-protocol write
    that is read back;
  * every answer is compared with an independent numpy fold over the
    seed-generated arrays: group keys, row count and row order exact,
    values to the tolerances in `RTOL_*` below;
  * the values `uniform(0, 100)` never draws — 0.0, -0.0, 100.0, negatives,
    far exponents, NULL — go through the compact f64 readback in a small
    table of their own, and with +/-inf and NaN through `pack_f64_bits`
    directly, compared exactly;
  * the device is proved, not assumed: the device path may not fall back,
    route to the CPU or degrade, every warm repetition must show a device
    dispatch, and the device supervisor must end HEALTHY with nothing
    abandoned.  Any failed assertion, phase or comparison exits non-zero.

`--chips 4` runs ONLY the mesh path and what it is compared with: the same
dataset hash-partitioned into 4 regions, `double-groupby-1`, `high-cpu-1`,
`lastpoint` and `groupby-orderby-limit` with `tile.mesh_devices = 4` (each
warm repetition a mesh dispatch, none degraded or handed to the single chip)
against `mesh_devices = 0`.

`--rehearse` relaxes exactly one thing, the platform assertion, so the
script can be rehearsed on the CPU at a tiny size
(`--rehearse --hosts 10 --hours 1`).  A rehearsal never reports a TPU.

Output: one JSON object per line; the LAST line is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import sys
import time
import traceback
import urllib.parse
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
# Dataset home: one fixed, git-ignored path, so a second invocation inside
# one chip command reuses the ingested SSTs (the compile cache has its own
# fixed path, see greptimedb_tpu/utils/jax_env.py).  Never under the
# directory the chip tool copies back.
SMOKE_HOME = os.path.join(HERE, ".chip_smoke")

SCRAPE_S = 10
T0 = 1_767_225_600_000  # 2026-01-01 UTC, epoch ms
METRICS = [
    "usage_user", "usage_system", "usage_idle", "usage_nice", "usage_iowait",
    "usage_irq", "usage_softirq", "usage_steal", "usage_guest", "usage_guest_nice",
]
WARM_REPS = 3

# Tolerances, relative.  f64 on the chip is emulated (a float32 pair, ~48
# mantissa bits), so even a max() or last_value() comes back a few 1e-15
# off the host's bits: 1e-9 is ample for everything the device computes in
# f64.  avg over a group space >= 2^14 is divided on the device and shipped
# as float32 (parallel/tile_cache.py _tile_program: "6e-8 relative, far
# under the engine's 1e-6 result bar"), and its sums ride the fixed-point
# limb kernel (~1e-9 per block): 1e-6, the engine's own documented bar.
RTOL_F64 = 1e-9
# the labels of the metric-engine logical table `me_cpu`: nginx's twelve
ME_LABELS = (
    "hostname", "region", "datacenter", "rack", "os", "arch", "team", "service",
    "service_version", "service_environment", "port", "server",
)
RTOL_AVG = 1e-6


def emit(obj: dict):
    print(json.dumps(obj), flush=True)


def check(cond, msg: str):
    if not cond:
        raise AssertionError(msg)


# ---- compile accounting (jax's own monitoring events) ----------------------

COMPILE = {
    "compiles": 0, "compile_s": 0.0, "compile_s_max": 0.0,
    "cache_requests": 0, "cache_hits": 0, "cache_misses": 0,
    "slowest": [],  # [seconds, jitted function name], the five longest
}


def _watch_compiles():
    from jax import monitoring

    def on_event(name, **_kw):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            COMPILE["cache_requests"] += 1
        elif name == "/jax/compilation_cache/cache_hits":
            COMPILE["cache_hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            COMPILE["cache_misses"] += 1

    def on_duration(name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            COMPILE["compiles"] += 1
            COMPILE["compile_s"] += secs
            COMPILE["compile_s_max"] = max(COMPILE["compile_s_max"], secs)
            COMPILE["slowest"] = sorted(
                COMPILE["slowest"] + [[round(secs, 2), str(kw.get("fun_name"))]],
                reverse=True,
            )[:5]

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)


def _compile_snapshot(slowest: bool = False) -> dict:
    return {
        k: (round(v, 3) if isinstance(v, float) else v)
        for k, v in COMPILE.items()
        if slowest or k != "slowest"
    }


# ---- dataset ---------------------------------------------------------------


class Dataset:
    """A TSBS cpu-only data set (one row per host per 10 s scrape): per
    chunk of ticks, ten `rng.uniform(0, 100)` draws in METRICS order.  Keeps
    `usage_user` as a [ticks, hosts] array — the ground truth every fold
    reads."""

    def __init__(self, hosts: int, hours: int, seed: int):
        self.hosts, self.hours, self.seed = hosts, hours, seed
        self.ticks = hours * 3600 // SCRAPE_S
        self.end = T0 + hours * 3600_000
        self.host_names = np.array([f"host_{i}" for i in range(hosts)])
        self.host1_index = 703 % hosts  # the host of the one-host shapes
        self.host1 = f"host_{self.host1_index}"
        # the PromQL metric table holds the last 2 h
        self.tql_ticks = min(self.ticks, 2 * 3600 // SCRAPE_S)
        self.usage_user = np.empty((self.ticks, hosts), np.float64)

    @property
    def rows(self) -> int:
        return self.ticks * self.hosts

    def key(self, regions: int) -> str:
        sig = json.dumps({
            "hosts": self.hosts, "hours": self.hours, "seed": self.seed,
            "regions": regions, "v": 2,
        }, sort_keys=True)
        return hashlib.sha1(sig.encode()).hexdigest()[:12]

    def chunks(self):
        """Yields (ts[n], hostname[n], {metric: values[n]}) per chunk and
        fills `usage_user` — run it even on reuse, the folds need it."""
        rng = np.random.default_rng(self.seed)
        chunk_ticks = max(1, 2_000_000 // self.hosts)
        for start in range(0, self.ticks, chunk_ticks):
            ticks = min(chunk_ticks, self.ticks - start)
            ts = T0 + (start + np.arange(ticks, dtype=np.int64))[:, None] * (
                SCRAPE_S * 1000
            )
            ts = np.broadcast_to(ts, (ticks, self.hosts)).reshape(-1)
            hs = np.broadcast_to(
                self.host_names[None, :], (ticks, self.hosts)
            ).reshape(-1)
            vals = {
                mm: rng.uniform(0.0, 100.0, ticks * self.hosts)
                for mm in METRICS
            }
            self.usage_user[start:start + ticks] = vals["usage_user"].reshape(
                ticks, self.hosts
            )
            yield ts, hs, vals

    # the engine emits groups in dictionary-code order: tag values sorted
    # as strings ("host_10" < "host_2")
    def host_order(self) -> np.ndarray:
        return np.argsort(self.host_names, kind="stable")


def load(db, ds: Dataset, home: str, regions: int) -> dict:
    """Ingest through the servers' insert_rows path, flush, and build the
    PromQL metric table.  Reuses a finished ingest found at `home`."""
    import pyarrow as pa

    marker = os.path.join(home, "INGESTED.json")
    reuse = False
    if os.path.exists(marker):
        with open(marker) as f:
            reuse = json.load(f).get("key") == ds.key(regions)
    cols_sql = ", ".join(f"{mm} DOUBLE" for mm in METRICS)
    partition = (
        f" PARTITION BY HASH (hostname) PARTITIONS {regions}"
        if regions > 1 else ""
    )
    if not reuse:
        db.sql(
            f"CREATE TABLE cpu (hostname STRING, ts TIMESTAMP(3) TIME INDEX, "
            f"{cols_sql}, PRIMARY KEY (hostname)){partition} "
            f"WITH (append_mode = 'true')"
        )
    t_ing = 0.0
    for ts, hs, vals in ds.chunks():
        if reuse:
            continue
        batch = pa.table({
            "hostname": pa.array(hs),
            "ts": pa.array(ts, pa.timestamp("ms")),
            **{mm: pa.array(vals[mm], pa.float64()) for mm in METRICS},
        })
        t0 = time.perf_counter()
        db.insert_rows("cpu", batch)
        t_ing += time.perf_counter() - t0
    tql_rows = 0
    if regions == 1:
        # the single-field metric table the PromQL engine needs: the last
        # 2 h of usage_user
        n_tql = ds.tql_ticks
        tql_rows = n_tql * ds.hosts
        if not reuse:
            db.sql(
                "CREATE TABLE tql_cpu (hostname STRING, greptime_value DOUBLE,"
                " ts TIMESTAMP(3) TIME INDEX, PRIMARY KEY (hostname))"
                " WITH (append_mode = 'true')"
            )
            first = ds.ticks - n_tql
            ts = T0 + (first + np.arange(n_tql, dtype=np.int64))[:, None] * (
                SCRAPE_S * 1000
            )
            t0 = time.perf_counter()
            db.insert_rows("tql_cpu", pa.table({
                "hostname": pa.array(np.broadcast_to(
                    ds.host_names[None, :], (n_tql, ds.hosts)
                ).reshape(-1)),
                "greptime_value": pa.array(
                    ds.usage_user[first:].reshape(-1), pa.float64()
                ),
                "ts": pa.array(
                    np.broadcast_to(ts, (n_tql, ds.hosts)).reshape(-1),
                    pa.timestamp("ms"),
                ),
            }))
            t_ing += time.perf_counter() - t0
            # the same samples as upstream's Prometheus remote write stores
            # them: a logical table of twelve labels on a metric engine's
            # physical table (`tile_exec`'s row-range source)
            db.sql(
                "CREATE TABLE smoke_phy (ts TIMESTAMP(3) TIME INDEX, "
                "greptime_value DOUBLE) WITH ('physical_metric_table' = '')"
            )
            db.sql(
                "CREATE TABLE me_cpu (ts TIMESTAMP(3) TIME INDEX, greptime_value "
                f"DOUBLE, {', '.join(f'{l} STRING' for l in ME_LABELS)}, "
                f"PRIMARY KEY ({', '.join(ME_LABELS)})) "
                "ENGINE = metric WITH ('on_physical_table' = 'smoke_phy')"
            )
            codes = pa.array(np.tile(np.arange(ds.hosts, dtype=np.int32), n_tql))
            t0 = time.perf_counter()
            db.insert_rows("me_cpu", pa.table({
                **{
                    l: pa.DictionaryArray.from_arrays(codes, pa.array([
                        str(name) if l == "hostname" else f"{l}-{(h * 7 + k) % (k + 2)}"
                        for h, name in enumerate(ds.host_names)
                    ]))
                    for k, l in enumerate(ME_LABELS)
                },
                "greptime_value": pa.array(
                    ds.usage_user[first:].reshape(-1), pa.float64()
                ),
                "ts": pa.array(
                    np.broadcast_to(ts, (n_tql, ds.hosts)).reshape(-1),
                    pa.timestamp("ms"),
                ),
            }))
            t_ing += time.perf_counter() - t0
            tql_rows *= 2
    t0 = time.perf_counter()
    if not reuse:
        db.storage.flush_all()
        with open(marker, "w") as f:
            json.dump({"key": ds.key(regions), "rows": ds.rows}, f)
    t_flush = time.perf_counter() - t0
    return {
        "rows": ds.rows, "tql_rows": tql_rows, "regions": regions,
        "reused": reuse, "ingest_s": round(t_ing, 2),
        "flush_s": round(t_flush, 2),
        "ingest_rows_per_s": (
            None if reuse else round((ds.rows + tql_rows) / max(t_ing, 1e-9))
        ),
    }


# ---- numpy folds (the plain reference) -------------------------------------
# Each returns the expected rows as a list of tuples, in the order the
# engine must produce them.  Timestamps are epoch ms.


def fold_single_groupby(ds: Dataset):
    """max(usage_user) per minute, one host, the last hour."""
    n = min(ds.ticks, 3600 // SCRAPE_S)
    col = ds.usage_user[ds.ticks - n:, ds.host1_index]
    per_min = 60 // SCRAPE_S
    lo = ds.end - n * SCRAPE_S * 1000
    mx = col.reshape(n // per_min, per_min).max(axis=1)
    return [(lo + i * 60_000, float(v)) for i, v in enumerate(mx)]


def fold_double_groupby(ds: Dataset):
    """avg(usage_user) per (host, hour) over the whole data set."""
    per_hour = 3600 // SCRAPE_S
    avg = ds.usage_user.reshape(ds.hours, per_hour, ds.hosts).mean(axis=1)
    return [
        (str(ds.host_names[h]), T0 + hr * 3600_000, float(avg[hr, h]))
        for h in ds.host_order() for hr in range(ds.hours)
    ]


def fold_high_cpu(ds: Dataset):
    col = ds.usage_user[:, ds.host1_index]
    hot = col[col > 90.0]
    return [(int(hot.size), float(hot.max()))]


def fold_lastpoint(ds: Dataset):
    return [
        (str(ds.host_names[h]), float(ds.usage_user[-1, h]))
        for h in ds.host_order()
    ]


def fold_groupby_orderby_limit(ds: Dataset):
    """max(usage_user) per minute over ts < end - 30 min, newest 5."""
    per_min = 60 // SCRAPE_S
    n = (ds.hours * 3600 - 1800) // SCRAPE_S
    mx = ds.usage_user[:n].reshape(n // per_min, per_min * ds.hosts).max(axis=1)
    minutes = len(mx)
    return [
        (T0 + i * 60_000, float(mx[i]))
        for i in range(minutes - 1, max(minutes - 6, -1), -1)
    ]


def fold_rate(ds: Dataset, start: int, end: int, step: int, range_ms: int,
              hosts: np.ndarray, per_second: bool):
    """PromQL rate/increase over `tql_cpu` for the host indices `hosts`:
    the engine's semantics — counter resets stripped by a running sum from
    the first fetched sample (ts >= start - range), then Prometheus'
    extrapolatedRate per (series, step) — written against the regular
    [tick, host] grid instead of flat sorted samples.  Returns
    {host index: [(ts_ms, value)...]} for the defined steps."""
    first_tick = ds.ticks - ds.tql_ticks
    tick_ts = T0 + (first_tick + np.arange(ds.tql_ticks, dtype=np.int64)) * (
        SCRAPE_S * 1000
    )
    fetched = (tick_ts >= start - range_ms) & (tick_ts <= end)
    tts = tick_ts[fetched]
    v = ds.usage_user[first_tick:][fetched][:, hosts].T  # [series, ticks]
    drop = np.where(v[:, 1:] < v[:, :-1], v[:, :-1], 0.0)
    adj = v.copy()
    adj[:, 1:] += np.cumsum(drop, axis=1)
    steps = np.arange(start, end + 1, step, dtype=np.int64)
    # window (t - range, t]: first/last fetched tick inside it
    i_first = np.searchsorted(tts, steps - range_ms, side="right")
    i_last = np.searchsorted(tts, steps, side="right") - 1
    count = i_last - i_first + 1
    defined = count >= 2
    i_first_c = np.clip(i_first, 0, len(tts) - 1)
    i_last_c = np.clip(i_last, 0, len(tts) - 1)
    first_ts, last_ts = tts[i_first_c], tts[i_last_c]
    fv, lv = adj[:, i_first_c], adj[:, i_last_c]
    raw_first = v[:, i_first_c]  # the clamp's zero point: the raw sample
    si = (last_ts - first_ts).astype(np.float64)
    avg_between = si / np.maximum(count - 1, 1)
    d_start = (first_ts - (steps - range_ms)).astype(np.float64)
    d_end = (steps - last_ts).astype(np.float64)
    thr = avg_between * 1.1
    ext_s = np.where(d_start < thr, d_start, avg_between / 2.0)
    ext_e = np.where(d_end < thr, d_end, avg_between / 2.0)
    result = lv - fv
    with np.errstate(all="ignore"):
        clamps = (result > 0) & (raw_first >= 0)
        zero_dur = si * (raw_first / np.where(clamps, result, 1.0))
        ext_s = np.where(clamps, np.minimum(ext_s, zero_dur), ext_s)
        out = result * ((si + ext_s + ext_e) / np.where(si == 0, 1.0, si))
    if per_second:
        out = out / (range_ms / 1000.0)
    return {
        int(h): [
            (int(steps[w]), float(out[i, w]))
            for w in np.nonzero(defined)[0]
        ]
        for i, h in enumerate(hosts)
    }


# ---- the values the generator never draws ----------------------------------
# A device-finalized f64 result (lastpoint, ORDER BY/LIMIT/HAVING) comes
# back through ops/aggregate.pack_f64_bits, which has a branch each for
# zero, sign, inf and NaN/NULL; `uniform(0, 100)` reaches none of them
# (TSBS's own clamped walk sits on 0 and 100 all the time).  Table `edge`:
# one series per case, three rows each in ts order.  Every value is exact
# in float32, so the chip's emulated f64 holds it exactly and the answers
# are compared exactly (rtol 0), not to a tolerance.  No inf in the table:
# the tile path's min/max/last clamp a stored inf to the largest finite
# f64 where the CPU executor returns inf (PERF.md, open questions); inf
# and NaN go through the pack directly in `check_pack_on_device`.

EDGE = {  # hostname -> (v rows, w rows); v's last row is the lastpoint
    "e0": ([5.0, 3.0, 0.0], [0.0, 0.0, 0.0]),
    "e1": ([1.0, 2.0, -0.0], [-0.0, -0.0, -0.0]),
    "e2": ([0.0, 50.0, 100.0], [100.0, 100.0, 100.0]),
    "e3": ([None, None, None], [-37.25, 37.25, 0.0]),
    "e4": ([12.5, 0.0, -37.25], [-1.5, -2.5, -3.0]),
    "e5": ([1.0, 2.0, -(2.0**-100)], [None, 0.0, None]),
    "e6": ([-1.0, -2.0, 2.0**100], [None, None, None]),
    "e7": ([-8.0, -0.5, 0.5], [0.5, 0.25, 0.125]),
}


def load_edge(db):
    import pyarrow as pa

    db.sql(
        "CREATE TABLE IF NOT EXISTS edge (hostname STRING, ts TIMESTAMP(3) "
        "TIME INDEX, v DOUBLE, w DOUBLE, PRIMARY KEY (hostname)) "
        "WITH (append_mode = 'true')"
    )
    if db.sql_one("SELECT count(*) AS n FROM edge")["n"][0].as_py():
        return  # a reused data home holds it already
    names = sorted(EDGE)
    db.insert_rows("edge", pa.table({
        "hostname": pa.array([h for h in names for _ in range(3)]),
        "ts": pa.array(
            [T0 + i * SCRAPE_S * 1000 for _ in names for i in range(3)],
            pa.timestamp("ms"),
        ),
        "v": pa.array([x for h in names for x in EDGE[h][0]], pa.float64()),
        "w": pa.array([x for h in names for x in EDGE[h][1]], pa.float64()),
    }))
    db.sql("ADMIN flush_table('edge')")


def fold_edge_lastpoint(_ds):
    return [(h, EDGE[h][0][-1]) for h in sorted(EDGE)]


def fold_edge_orderby_limit(_ds):
    """max, min and sum of `w` per series (NULLs skipped, NULL if none)."""
    out = []
    for h in sorted(EDGE):
        w = [x for x in EDGE[h][1] if x is not None]
        out.append((h, max(w), min(w), sum(w)) if w else (h, None, None, None))
    return out


def check_pack_on_device():
    """`pack_f64_bits` on the device against the host's own bits."""
    import jax
    import jax.numpy as jnp

    from greptimedb_tpu.ops.aggregate import pack_f64_bits, unpack_f64_bits

    vals = np.array([
        0.0, -0.0, 100.0, -100.0, -37.25, 0.5, 1.0, 2.0**100, -(2.0**-100),
        np.inf, -np.inf, np.nan,
    ])  # each exact in float32, so exact in the chip's emulated f64
    got = unpack_f64_bits(np.asarray(jax.jit(pack_f64_bits)(jnp.asarray(vals))))
    nan = np.isnan(vals)
    check(np.isnan(got[nan]).all(), f"pack_f64_bits: NaN came back as {got[nan]}")
    same = got[~nan].view(np.uint64) == vals[~nan].view(np.uint64)
    check(same.all(), f"pack_f64_bits: {vals[~nan][~same]} came back as "
                      f"{got[~nan][~same]}")
    emit({"event": "pack_f64_bits", "values": len(vals), "bit_exact": True})


# ---- comparison ------------------------------------------------------------


def compare_rows(name: str, got: list, want: list, rtol: float):
    """Row count, row order and every key column exact; float columns to
    `rtol` (0 = equal; ints — count(*) — and NULLs exact)."""
    check(len(got) == len(want), f"{name}: {len(got)} rows, want {len(want)}")
    for i, (g, w) in enumerate(zip(got, want)):
        check(len(g) == len(w), f"{name}: row {i} has {len(g)} columns")
        for a, b in zip(g, w):
            if isinstance(b, float):
                check(
                    a is not None and abs(a - b) <= rtol * max(abs(b), 1e-300),
                    f"{name}: row {i}: {g!r} != {w!r} (rtol {rtol})",
                )
            else:
                check(a == b, f"{name}: row {i}: {g!r} != {w!r}")


# ---- the requests ----------------------------------------------------------


class Client:
    def __init__(self, address: str):
        self.base = f"http://{address}"

    def _open(self, path: str, params: dict | None, body: bytes | None):
        url = self.base + path
        if params:
            url += "?" + urllib.parse.urlencode(params)
        req = urllib.request.Request(
            url, data=body, method="POST" if body is not None else "GET"
        )
        with urllib.request.urlopen(req, timeout=600) as resp:
            return resp.status, resp.read()

    def sql(self, sql: str) -> list:
        status, body = self._open(
            "/v1/sql", None, urllib.parse.urlencode({"sql": sql}).encode()
        )
        check(status == 200, f"/v1/sql -> {status}")
        out = json.loads(body)["output"][0]
        return [tuple(r) for r in out["records"]["rows"]]

    def query_range(self, query: str, start_s: int, end_s: int, step_s: int):
        status, body = self._open(
            "/v1/prometheus/api/v1/query_range",
            {"query": query, "start": start_s, "end": end_s, "step": step_s},
            None,
        )
        check(status == 200, f"query_range -> {status}")
        doc = json.loads(body)
        check(doc["status"] == "success", f"query_range: {doc}")
        return doc["data"]["result"]

    def influx_write(self, lines: str):
        status, _ = self._open(
            "/v1/influxdb/write", {"precision": "ms"}, lines.encode()
        )
        check(status == 204, f"/v1/influxdb/write -> {status}")


def sql_requests(ds: Dataset) -> list:
    """(name, sql, fold, rtol) — TSBS cpu-only query shapes as SQL."""
    end = ds.end
    w_all = (T0, end)
    w1 = (end - min(ds.hours, 1) * 3600_000, end)
    return [
        (
            "single-groupby-1-1-1",
            f"SELECT time_bucket('1m', ts) AS tb, max(usage_user) AS max_usage_user "
            f"FROM cpu WHERE ts >= {w1[0]} AND ts < {w1[1]} "
            f"AND hostname = '{ds.host1}' GROUP BY tb",
            fold_single_groupby, RTOL_F64,
        ),
        (
            "double-groupby-1",
            f"SELECT hostname, time_bucket('1h', ts) AS tb, "
            f"avg(usage_user) AS avg_usage_user FROM cpu "
            f"WHERE ts >= {w_all[0]} AND ts < {w_all[1]} GROUP BY hostname, tb",
            fold_double_groupby, RTOL_AVG,
        ),
        (
            "high-cpu-1",
            f"SELECT count(*) AS n, max(usage_user) AS m FROM cpu "
            f"WHERE usage_user > 90.0 AND hostname = '{ds.host1}' "
            f"AND ts >= {w_all[0]} AND ts < {w_all[1]}",
            fold_high_cpu, RTOL_F64,
        ),
        (
            "lastpoint",
            "SELECT hostname, last_value(usage_user) AS last_user FROM cpu "
            "GROUP BY hostname",
            fold_lastpoint, RTOL_F64,
        ),
        (
            "groupby-orderby-limit",
            f"SELECT time_bucket('1m', ts) AS minute, max(usage_user) AS mu "
            f"FROM cpu WHERE ts < {end - 1800_000} GROUP BY minute "
            f"ORDER BY minute DESC LIMIT 5",
            fold_groupby_orderby_limit, RTOL_F64,
        ),
    ]


class Counters:
    """Deltas of the engine's own counters (utils/metrics.py) around one
    request."""

    MUST_NOT_MOVE = ("TPU_FALLBACK_TOTAL", "TPU_ROUTED_TO_CPU", "TQL_TILE_DEGRADED")
    WATCHED = MUST_NOT_MOVE + (
        "TPU_DEVICE_DISPATCHES", "TILE_LOWERED_TOTAL", "TQL_TILE_DISPATCHES",
        "TQL_TILE_LOGICAL_DISPATCHES", "TQL_TILE_COLD_SERVES", "TPU_READBACK_BYTES", "TILE_MESH_DISPATCHES",
        "TILE_MESH_DEGRADED", "TILE_MESH_INELIGIBLE", "TPU_DEVICE_FINALIZE",
    )

    def __init__(self):
        from greptimedb_tpu.utils import metrics

        self._m = metrics
        self._at = self._read()

    def _read(self) -> dict:
        return {k: getattr(self._m, k).total() for k in self.WATCHED}

    def delta(self) -> dict:
        now = self._read()
        d = {k: int(now[k] - self._at[k]) for k in self.WATCHED}
        self._at = now
        return d


def _wait_builds(db, timeout_s: float = 900.0):
    """Wait out the background family builder (the cold request scheduled
    the plane build; warm requests must find the planes resident)."""
    te = db.query_engine._tile_executor
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        with te._fused_lock:
            if not te._fused_builds and not te._fused_queue:
                return
        time.sleep(0.05)
    raise AssertionError("background plane build did not finish")


def run_request(name: str, call, parse, want, rtol, db, engaged: tuple,
                cold_may_host_serve: bool) -> dict:
    """One request: once cold, WARM_REPS times warm.  `engaged` names the
    counters every warm repetition must advance."""
    counters = Counters()
    t0 = time.perf_counter()
    got = parse(call())
    cold_ms = (time.perf_counter() - t0) * 1000.0
    compare_rows(f"{name} (cold)", got, want, rtol)
    cold = counters.delta()
    for k in Counters.MUST_NOT_MOVE:
        check(cold[k] == 0, f"{name} (cold): {k} moved by {cold[k]}")
    if not cold_may_host_serve:
        check(cold["TQL_TILE_COLD_SERVES"] == 0, f"{name}: cold host serve")
    _wait_builds(db)
    counters.delta()  # the builder's own dispatches are not a repetition's
    warm_ms, dispatches, readback = [], [], []
    for rep in range(WARM_REPS):
        t0 = time.perf_counter()
        got = parse(call())
        warm_ms.append(round((time.perf_counter() - t0) * 1000.0, 3))
        compare_rows(f"{name} (warm {rep})", got, want, rtol)
        d = counters.delta()
        for k in engaged:
            check(d[k] > 0, f"{name} (warm {rep}): {k} did not advance")
        for k in Counters.MUST_NOT_MOVE + ("TQL_TILE_COLD_SERVES",):
            check(d[k] == 0, f"{name} (warm {rep}): {k} moved by {d[k]}")
        dispatches.append(d["TPU_DEVICE_DISPATCHES"])
        readback.append(d["TPU_READBACK_BYTES"])
    return {
        "query": name, "cold_ms": round(cold_ms, 3), "warm_ms": warm_ms,
        "rows_out": len(got), "dispatches": dispatches,
        "readback_bytes": readback, "cold_host_served":
        cold["TQL_TILE_COLD_SERVES"], "rtol": rtol,
    }


def _matrix_rows(result: list) -> list:
    """Prometheus matrix JSON -> (hostname, ts ms, value) rows in order."""
    return [
        (s["metric"]["hostname"], int(ts) * 1000, float(v))
        for s in result for ts, v in s["values"]
    ]


def _device_bytes(db) -> dict:
    """Device bytes resident, three views: the tile cache's own plane
    accounting, the runtime's memory_stats per device (0 on the CPU
    backend, which reports none) and jax's live arrays per device."""
    import jax

    entries = db.sql_one(
        "SELECT sum(device_bytes) AS b FROM information_schema.tile_cache_entries"
    )["b"][0].as_py()
    mem = db.sql_one(
        "SELECT device, bytes_in_use FROM information_schema.device_memory"
    )
    live: dict = {}
    for arr in jax.live_arrays():
        for shard in arr.addressable_shards:
            live[shard.device.id] = live.get(shard.device.id, 0) + int(
                shard.data.nbytes
            )
    return {
        "tile_cache_entries_device_bytes": int(entries or 0),
        "device_memory_bytes_in_use": dict(zip(
            mem["device"].to_pylist(), mem["bytes_in_use"].to_pylist()
        )),
        "live_array_bytes": {str(k): v for k, v in sorted(live.items())},
    }


def _check_health(db):
    t = db.sql_one(
        "SELECT device, state, abandoned_calls, quarantines "
        "FROM information_schema.device_health"
    )
    rows = list(zip(*[t[c].to_pylist() for c in t.column_names]))
    emit({"event": "device_health", "rows": rows})
    check(rows, "device_health is empty")
    for device, state, abandoned, quarantines in rows:
        check(state == "HEALTHY", f"device {device} is {state}")
        check(abandoned == 0, f"device {device}: {abandoned} abandoned calls")
        check(quarantines == 0, f"device {device}: {quarantines} quarantines")


def open_database(home: str, mesh_devices: int = 0):
    """Default settings (`device.*` included) plus the three existing
    query knobs that make a silent host answer impossible."""
    from greptimedb_tpu.database import Database
    from greptimedb_tpu.utils.config import Config

    cfg = Config.load()
    db = Database(config=cfg, data_home=home)
    db.config.query.fallback_to_cpu = False
    db.config.query.tpu_min_rows = 0
    db.config.query.disabled_passes = ("cold_host_serve", "host_fast_path")
    db.config.tile.mesh_devices = mesh_devices
    return db


def smoke_one_chip(ds: Dataset, home: str):
    from greptimedb_tpu import native
    from greptimedb_tpu.servers.http import HttpServer

    emit({"event": "native", "loaded": native.available()})
    db = open_database(home)
    server = None
    try:
        emit({"event": "loaded", **load(db, ds, home, regions=1)})
        t0 = time.perf_counter()
        built = db.prewarm(tables=["cpu", "tql_cpu", "me_cpu"])
        for key, stats in built.items():
            check("error" not in stats, f"prewarm {key}: {stats}")
        emit({
            "event": "prewarm", "secs": round(time.perf_counter() - t0, 2),
            **_device_bytes(db),
        })
        server = HttpServer(db, "127.0.0.1:0").start()
        client = Client(server.address)

        for name, sql, fold, rtol in sql_requests(ds):
            emit(run_request(
                name, lambda: client.sql(sql), lambda rows: rows, fold(ds),
                rtol, db, ("TPU_DEVICE_DISPATCHES", "TILE_LOWERED_TOTAL"),
                cold_may_host_serve=False,
            ))
            emit({"event": "compile", "after": name, **_compile_snapshot()})

        # the values the generator never draws, through the compact f64
        # readback
        check_pack_on_device()
        load_edge(db)
        for name, sql, fold in (
            (
                "edge-lastpoint",
                "SELECT hostname, last_value(v) AS lv FROM edge GROUP BY hostname",
                fold_edge_lastpoint,
            ),
            (
                "edge-orderby-limit",
                "SELECT hostname, max(w) AS mx, min(w) AS mn, sum(w) AS s "
                "FROM edge GROUP BY hostname ORDER BY hostname LIMIT 8",
                fold_edge_orderby_limit,
            ),
        ):
            emit(run_request(
                name, lambda: client.sql(sql), lambda rows: rows, fold(ds),
                0.0, db,
                ("TPU_DEVICE_DISPATCHES", "TILE_LOWERED_TOTAL",
                 "TPU_DEVICE_FINALIZE"),
                cold_may_host_serve=False,
            ))

        # PromQL over the last 2 h: rate over every series, increase over one
        start_s = (ds.end - ds.tql_ticks * SCRAPE_S * 1000) // 1000 + 600
        end_s = ds.end // 1000 - 60
        for name, query, hosts, per_second in (
            ("rate", "rate(tql_cpu[5m])", ds.host_order(), True),
            ("increase-1", 'increase(tql_cpu{hostname="host_1"}[5m])',
             np.array([1]), False),
        ):
            fold = fold_rate(
                ds, start_s * 1000, end_s * 1000, 60_000, 300_000, hosts,
                per_second,
            )
            want = [
                (str(ds.host_names[h]), ts, v)
                for h in hosts for ts, v in fold[int(h)]
            ]
            emit(run_request(
                name, lambda: client.query_range(query, start_s, end_s, 60),
                _matrix_rows, want, RTOL_F64, db,
                ("TPU_DEVICE_DISPATCHES", "TQL_TILE_DISPATCHES"),
                cold_may_host_serve=True,
            ))
            emit({"event": "compile", "after": name, **_compile_snapshot()})

        # the same rate over the logical table of twelve labels, one rack's
        # hosts of it: equal to the mito table's, series for series (its own
        # order is the labels')
        rack = ME_LABELS.index("rack")
        hosts = np.array([
            h for h in ds.host_order() if (h * 7 + rack) % (rack + 2) == 1
        ])
        fold = fold_rate(
            ds, start_s * 1000, end_s * 1000, 60_000, 300_000, hosts, True,
        )
        emit(run_request(
            "rate-logical",
            lambda: client.query_range(
                'rate(me_cpu{rack="rack-1"}[5m])', start_s, end_s, 60
            ),
            lambda result: sorted(_matrix_rows(result)),
            sorted(
                (str(ds.host_names[h]), ts, v)
                for h in hosts for ts, v in fold[int(h)]
            ),
            RTOL_F64, db,
            ("TPU_DEVICE_DISPATCHES", "TQL_TILE_DISPATCHES",
             "TQL_TILE_LOGICAL_DISPATCHES"),
            cold_may_host_serve=True,
        ))
        emit({"event": "compile", "after": "rate-logical", **_compile_snapshot()})

        # an acknowledged write is read back (the value differs per run,
        # so a reused data home cannot answer from an older write)
        stamp = float(time.time_ns() % 1_000_000_007)
        client.influx_write(f"smoke_write,probe=a v={stamp!r} {ds.end}")
        got = client.sql("SELECT probe, v FROM smoke_write WHERE probe = 'a'")
        check(got == [("a", stamp)], f"influx write read back as {got!r}")
        emit({"event": "write_read_back", "rows": len(got)})

        emit({"event": "resident", **_device_bytes(db)})
        _check_health(db)
    finally:
        if server is not None:
            server.stop()
        db.close()


def _table_rows(table) -> list:
    return list(zip(*[
        [int(v.timestamp() * 1000) if hasattr(v, "timestamp") else v
         for v in table[c].to_pylist()]
        for c in table.column_names
    ]))


def smoke_four_chips(ds: Dataset, home: str, chips: int):
    """The mesh path and what it is compared with, nothing else.  The mesh
    knob is on when the planes are built (a region's planes co-locate with
    its mesh device only then), and flipped to 0 live for the comparison,
    as an operator would."""
    db = open_database(home, mesh_devices=chips)
    try:
        emit({"event": "loaded", **load(db, ds, home, regions=chips)})
        requests = [
            r for r in sql_requests(ds)
            if r[0] in (
                "double-groupby-1", "high-cpu-1", "lastpoint", "groupby-orderby-limit",
            )
        ]
        meshed = {}
        for name, sql, fold, rtol in requests:
            counters = Counters()
            db.sql_one(sql)  # cold: builds the planes
            _wait_builds(db)
            counters.delta()
            walls = []
            for rep in range(WARM_REPS):
                t0 = time.perf_counter()
                table = db.sql_one(sql)
                walls.append(round((time.perf_counter() - t0) * 1000.0, 3))
                compare_rows(
                    f"{name} (mesh_devices={chips}, warm {rep})",
                    _table_rows(table), fold(ds), rtol,
                )
                d = counters.delta()
                check(d["TILE_MESH_DISPATCHES"] > 0,
                      f"{name} (warm {rep}): no mesh dispatch")
                check(d["TILE_MESH_DEGRADED"] == 0,
                      f"{name} (warm {rep}): mesh degraded")
                check(d["TILE_MESH_INELIGIBLE"] == 0,
                      f"{name} (warm {rep}): handed to the single chip")
                for k in Counters.MUST_NOT_MOVE:
                    check(d[k] == 0, f"{name} (warm {rep}): {k} moved")
            meshed[name] = table.to_pydict()
            emit({"query": name, "mesh_devices": chips, "warm_ms": walls,
                  "rows_out": table.num_rows})
        resident = _device_bytes(db)
        emit({"event": "resident", "mesh_devices": chips, **resident})
        per_device = resident["live_array_bytes"]
        check(
            sum(1 for v in per_device.values() if v > 0) > 1,
            f"every resident byte sits on one device: {per_device}",
        )
        in_use = resident["device_memory_bytes_in_use"]
        if any(in_use.values()):  # the CPU backend reports no memory_stats
            check(
                sum(1 for v in in_use.values() if v > 0) > 1,
                f"device_memory shows one device in use: {in_use}",
            )

        db.config.tile.mesh_devices = 0
        for name, sql, _fold, _rtol in requests:
            counters = Counters()
            walls = []
            for rep in range(WARM_REPS):
                t0 = time.perf_counter()
                table = db.sql_one(sql)
                walls.append(round((time.perf_counter() - t0) * 1000.0, 3))
                d = counters.delta()
                check(d["TILE_MESH_DISPATCHES"] == 0,
                      f"{name}: mesh dispatch with mesh_devices=0")
                check(d["TILE_LOWERED_TOTAL"] > 0,
                      f"{name} (mesh_devices=0): tile path did not engage")
                check(
                    table.to_pydict() == meshed[name],
                    f"{name}: mesh_devices={chips} differs from mesh_devices=0",
                )
            emit({"query": name, "mesh_devices": 0, "warm_ms": walls,
                  "rows_out": table.num_rows})
        _check_health(db)
    finally:
        db.close()


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--hosts", type=int, default=4000)
    ap.add_argument("--hours", type=int, default=12)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument(
        "--rehearse", action="store_true",
        help="relax the platform assertion (CPU rehearsal; never a TPU)",
    )
    return ap.parse_args()


def main(args) -> dict:
    from greptimedb_tpu.utils.jax_env import ensure_x64

    import jax

    ensure_x64()
    _watch_compiles()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if args.rehearse:
        check(device["platform"] != "tpu",
              "--rehearse is the CPU rehearsal; on the chip run without it")
    else:
        check(device["platform"] == "tpu",
              f"no accelerator: jax reports {device}")
    check(device["count"] >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, jax has {device}")
    emit({"event": "start", "device": device, "args": vars(args)})

    ds = Dataset(args.hosts, args.hours, args.seed)
    home = os.path.join(SMOKE_HOME, f"tsbs_{ds.key(args.chips)}")
    if os.path.isdir(home) and not os.path.exists(
        os.path.join(home, "INGESTED.json")
    ):
        shutil.rmtree(home)  # torn previous ingest: start clean
    os.makedirs(home, exist_ok=True)
    if args.chips == 1:
        smoke_one_chip(ds, home)
    else:
        smoke_four_chips(ds, home, args.chips)
    emit({"event": "compile", "after": "all", **_compile_snapshot(slowest=True)})
    return device


if __name__ == "__main__":
    cli = parse_args()
    try:
        result = {"ok": True, "device": main(cli)}
    except Exception:  # noqa: BLE001 — the boundary: any failure is the exit code
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        # not sys.exit: a builder thread still inside an XLA compile must
        # not be able to turn a failure into a hang
        os._exit(1)
    print(json.dumps(result), flush=True)
    sys.stderr.flush()
    os._exit(0)
