"""End-to-end TSBS benchmark through the FULL engine path.

Every number is measured through `Database.sql()`: SQL parse -> plan -> TPU
lowering -> HBM super-tile cache (parallel/tile_cache.py) -> ONE compiled
dispatch -> ONE device->host fetch -> finalized Arrow result.  Data is
really ingested (the servers' `insert_rows` path: partition split, WAL,
memtable) and really flushed to Parquet SSTs first; the cold run pays
Parquet decode + dictionary encode + H2D upload + XLA compile, warm runs
hit the HBM-resident super-tiles — the engine's design point, matching the
reference's warm-page-cache TSBS runs.

Timeout-proof by construction (round-2 lesson: rc=124 left zero evidence;
round-4 lesson: a SOFT budget checked between queries cannot stop a
runaway query — the driver run died inside an unbounded CPU parquet scan):
  * one JSON line per query is printed (and flushed) AS IT COMPLETES;
  * partial results are continuously written to BENCH_PARTIAL.json;
  * GRAFT_BENCH_BUDGET_S (default 3000) is a wall-clock budget — when
    exceeded the bench stops starting new queries and prints the final
    summary line with whatever finished;
  * every query runs under a HARD per-query deadline
    (query.timeout_s -> utils/deadline.py): a query that degrades to a
    CPU scan aborts with QueryTimeoutError, is recorded as an error, and
    the bench moves on — partial artifacts always land;
  * SIGTERM/SIGINT emit the final summary line before dying, so even an
    external kill leaves a parseable record.

Workload (reference docs/benchmarks/tsbs/v0.12.0.md, BASELINE.md): scale
4000 hosts @ 10s scrape, 10 CPU metrics, GRAFT_BENCH_HOURS of data
(default 24; TSBS uses 3 days).  Reference numbers: GreptimeDB v0.12.0 on
EC2 c5d.2xlarge (8 vCPU).

Latency context printed in `detail`: `device_fetch_ms` is the round trip of
a fresh-buffer device fetch on this machine (dispatch + device->host copy of
one scalar) — the floor every query that touches the device pays.
`--mode mixed --rtt-ms N` (env GRAFT_BENCH_RTT_MS) adds a SYNTHETIC delay on
top: every dispatch/fetch boundary sleeps a symmetric half of N ms
(utils/rtt_sim.py), so the QPS-knee sweep can be run in the regime where
batching + mega-program fusion (ONE XLA invocation per batch tick) pays for
itself.  It is a knob for counting crossings, not a description of any
deployment, and its sleeps are never a device time.

Prints ONE final JSON line; headline = double-groupby-1 warm end-to-end p50.
"""

from __future__ import annotations

import faulthandler
import hashlib
import json
import math
import os
import signal
import sys
import time

# a fatal signal (segfault, external kill) must leave a stack trace in the
# log — round 3's first full-scale run died silently mid-compile
faulthandler.enable()
if hasattr(faulthandler, "register") and hasattr(signal, "SIGTERM"):
    try:
        faulthandler.register(signal.SIGTERM, chain=True)
    except (ValueError, OSError):
        pass

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from greptimedb_tpu.utils.jax_env import ensure_x64

N_HOSTS = int(os.environ.get("GRAFT_BENCH_HOSTS", 4000))
HOURS = int(os.environ.get("GRAFT_BENCH_HOURS", 72))
SCRAPE_S = 10
T0 = 1_767_225_600_000  # 2026-01-01 UTC, epoch ms
METRICS = [
    "usage_user", "usage_system", "usage_idle", "usage_nice", "usage_iowait",
    "usage_irq", "usage_softirq", "usage_steal", "usage_guest", "usage_guest_nice",
]
WARM_REPS = int(os.environ.get("GRAFT_BENCH_REPS", 5))
BUDGET_S = float(os.environ.get("GRAFT_BENCH_BUDGET_S", 3000))
PARTIAL_PATH = os.environ.get("GRAFT_BENCH_PARTIAL", "BENCH_PARTIAL.json")
HTTP_INGEST_ROWS = int(os.environ.get("GRAFT_BENCH_HTTP_ROWS", 400_000))
# GRAFT_BENCH_PREWARM=1 (default): after flush, Database.prewarm() builds
# the super-tiles + limb planes OFF the query path, so per-query "cold"
# stops paying 10-170 s of consolidation and the whole suite fits the
# wall budget (the rc=0 mandate).  =0 restores first-query cold builds.
PREWARM = os.environ.get("GRAFT_BENCH_PREWARM", "1") != "0"
# larger-than-HBM probe: >=2^28 rows, region-streamed (see
# _larger_than_hbm_probe).  Starts only when the TSBS suite finished with
# wall clock to spare; every stage runs under query deadlines so the
# worst case stays bounded.
LTH_ROWS = int(os.environ.get("GRAFT_BENCH_LTH_ROWS", 1 << 28))
# the probe must START early enough that its bounded stages still finish
# inside the wall budget (round-5 default of 3300 s sat PAST the 3000 s
# budget — the probe began after the budget and the driver's timeout won)
LTH_START_MAX_S = float(
    os.environ.get("GRAFT_BENCH_LTH_START_MAX_S", BUDGET_S * 0.55)
)
# hard rc=0 guarantee: a watchdog emits the final summary line and exits 0
# this many seconds BEFORE the budget, whatever is still running
WATCHDOG_GRACE_S = float(os.environ.get("GRAFT_BENCH_WATCHDOG_GRACE_S", 60))
# Persistent dataset + tile-artifact home: ingested SSTs, persisted
# super-tile consolidations (_persist_async) and the XLA compile cache
# survive under a dataset-parameter hash, so the ~260 s ingest and the
# first-build colds are paid ONCE — later runs reopen and go straight to
# queries.  Empty disables (fresh tmpdir per run).
DATA_DIR = os.environ.get(
    "GRAFT_BENCH_DATA_DIR",
    os.path.join(
        os.environ.get("TMPDIR", "/tmp"), "graft_bench_data"
    ),
)


def _argv_value(flag: str, default: str) -> str:
    argv = sys.argv[1:]
    for i, a in enumerate(argv):
        if a == flag and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith(flag + "="):
            return a.split("=", 1)[1]
    return default


# --wal-backend kafka-fake: run a wire-latency probe of the group-commit
# path against an offline fake broker (remote/fake_kafka.py) next to the
# in-process ingest.  The headline ingest numbers stay on the local WAL.
WAL_BACKEND = _argv_value(
    "--wal-backend", os.environ.get("GRAFT_BENCH_WAL_BACKEND", "local")
)


def _dataset_key() -> str:
    sig = json.dumps(
        {
            "hosts": N_HOSTS, "hours": HOURS, "scrape": SCRAPE_S,
            "metrics": METRICS, "seed": 7, "t0": T0, "v": 1,
        },
        sort_keys=True,
    )
    return hashlib.sha1(sig.encode()).hexdigest()[:12]

END = T0 + HOURS * 3600_000
W12 = (END - 12 * 3600_000, END)
W8 = (END - 8 * 3600_000, END)
W1 = (END - 3600_000, END)

HOST1 = f"host_{703 % N_HOSTS}"
HOSTS8 = [
    f"host_{i % N_HOSTS}" for i in (703, 1217, 2048, 99, 3777, 1500, 2901, 42)
]

_START = time.perf_counter()


def _elapsed() -> float:
    return time.perf_counter() - _START


def _emit(obj: dict, compact: bool = False):
    # compact=True strips separators: the driver captures only the LAST
    # ~2000 bytes of output and parses the final line — a record that
    # doesn't fit is a record that doesn't exist (r03 exited rc=0 with a
    # 3 KB summary line and still went down as unparsed)
    print(
        json.dumps(obj, separators=(",", ":") if compact else None),
        flush=True,
    )


def _q(window, metrics_n, hosts=None, bucket="1h", funcs="max"):
    lo, hi = window
    cols = ", ".join(f"{funcs}({m}) AS {funcs}_{m}" for m in METRICS[:metrics_n])
    where = f"ts >= {lo} AND ts < {hi}"
    if hosts is not None:
        where += (
            f" AND hostname = '{hosts}'"
            if isinstance(hosts, str)
            else f" AND hostname IN ({', '.join(repr(h) for h in hosts)})"
        )
    group = "tb" if hosts is not None else "hostname, tb"
    sel_host = "" if hosts is not None else "hostname, "
    return (
        f"SELECT {sel_host}time_bucket('{bucket}', ts) AS tb, {cols} "
        f"FROM cpu WHERE {where} GROUP BY {group}"
    )


QUERIES = [
    # (name, sql, reference_ms)
    ("double-groupby-1", _q(W12, 1, funcs="avg"), 673.08),
    ("double-groupby-5", _q(W12, 5, funcs="avg"), 963.99),
    ("double-groupby-all", _q(W12, 10, funcs="avg"), 1330.05),
    ("cpu-max-all-1", _q(W8, 10, hosts=HOST1), 12.46),
    ("cpu-max-all-8", _q(W8, 10, hosts=HOSTS8), 24.20),
    ("single-groupby-1-1-1", _q(W1, 1, hosts=HOST1, bucket="1m"), 4.06),
    ("single-groupby-1-1-12", _q(W12, 1, hosts=HOST1, bucket="1m"), 4.73),
    ("single-groupby-1-8-1", _q(W1, 1, hosts=HOSTS8, bucket="1m"), 8.23),
    ("single-groupby-5-1-1", _q(W1, 5, hosts=HOST1, bucket="1m"), 4.61),
    ("single-groupby-5-1-12", _q(W12, 5, hosts=HOST1, bucket="1m"), 5.61),
    ("single-groupby-5-8-1", _q(W1, 5, hosts=HOSTS8, bucket="1m"), 9.74),
    (
        "groupby-orderby-limit",
        f"SELECT time_bucket('1m', ts) AS minute, max(usage_user) AS mu FROM cpu "
        f"WHERE ts < {END - 1800_000} GROUP BY minute ORDER BY minute DESC LIMIT 5",
        952.46,
    ),
    (
        "lastpoint",
        "SELECT hostname, last_value(usage_user) AS last_user FROM cpu GROUP BY hostname",
        591.02,
    ),
    (
        "high-cpu-all",
        f"SELECT count(*) AS n, max(usage_user) AS m FROM cpu "
        f"WHERE usage_user > 90.0 AND ts >= {W12[0]} AND ts < {W12[1]}",
        4638.57,
    ),
    (
        "high-cpu-1",
        f"SELECT count(*) AS n, max(usage_user) AS m FROM cpu "
        f"WHERE usage_user > 90.0 AND hostname = '{HOST1}' "
        f"AND ts >= {W12[0]} AND ts < {W12[1]}",
        5.08,
    ),
]


def _remaining() -> float:
    """Wall budget left before the watchdog must emit (probe gating)."""
    return BUDGET_S - WATCHDOG_GRACE_S - _elapsed()


def _recorder():
    from greptimedb_tpu.utils import flight_recorder

    return flight_recorder


def _recorder_delta(cursor: int, table_key: str) -> list:
    """Non-ghost flight-recorder records for `table_key` since `cursor`
    (the per-query delta; the builder's priming dispatches stay out)."""
    fr = _recorder()
    return [
        r for r in fr.RECORDER.since(cursor)
        if r.table == table_key and not r.ghost
    ]


def _stage_digest(recs: list) -> str | None:
    """Compact stage attribution for the summary record: dominant stage
    shorthand + its ms from the LAST dispatching record (a warm rep), or
    "ho" when the query was answered host-side without a dispatch.
    Integer ms at >= 10 ms, one decimal below — every byte of the
    emitted line is contended ("di3.2", "rt128", "ho")."""
    fr = _recorder()
    dispatched = [r for r in recs if r.stage_ms("dispatch") > 0]
    if dispatched:
        name, ms = dispatched[-1].dominant_stage()
        if name:
            short = fr.STAGE_SHORT.get(name, name)
            return f"{short}{round(ms) if ms >= 10 else round(ms, 1)}"
    if recs:
        return "ho"
    return None


class _BudgetSkip(Exception):
    """Control-flow marker: a phase was skipped on remaining budget (the
    skip reason is recorded separately — this is not an error)."""


def _write_partial(payload: dict, record: dict | None = None):
    """Persist the partial AND a fully-parseable summary record built
    from whatever has finished so far: a driver timeout (or kill -9) at
    ANY point after the first query still leaves BENCH_PARTIAL.json
    holding a record in the official format — the guard process prints
    it verbatim instead of reconstructing one.  Callers that already
    built the record pass it in so the persisted copy is the EMITTED
    one, not a second (possibly later) snapshot."""
    try:
        payload = dict(payload)
        payload["record"] = record if record is not None else _build_record()
        with open(PARTIAL_PATH, "w") as f:
            json.dump(payload, f)
    except Exception:  # noqa: BLE001 — bookkeeping must never kill a query
        pass


# state shared with the final-summary emitter so a signal handler (or an
# escaping exception) can still print the one-line record
_STATE: dict = {"detail": {}, "results": {}, "headline": None, "emitted": False}
import threading as _threading

# RLock, not Lock: the SIGTERM handler runs ON the main thread — if the
# main thread is mid-emit when the signal lands, a plain Lock would
# self-deadlock the handler (and then the watchdog), reproducing the
# exact hang this machinery exists to prevent
_EMIT_LOCK = _threading.RLock()


def _emit_final():
    # the budget watchdog thread and the main thread can race here: the
    # record must be exactly ONE line, and the watchdog's os._exit must
    # not truncate a line the main thread is mid-writing — so the WHOLE
    # emission holds the lock (a racing caller blocks, then no-ops)
    with _EMIT_LOCK:
        if _STATE["emitted"]:
            return
        _STATE["emitted"] = True
        _emit_final_locked()


# keys kept in the EMITTED record (the full per-query diagnostics live in
# BENCH_PARTIAL.json): the acceptance checks read geomeans + per-query
# cold_ms/reference_ms/vs_baseline, and the whole line must stay well
# under the driver's ~2000-byte tail capture.  The flight recorder's
# per-query stage attribution rides as ONE detail-level "stages" string
# (queries-dict order, comma-joined, "di3.2" = dispatch-dominated at
# 3.2 ms) — per-query keys would not fit the tail capture.
_COMPACT_QUERY_KEYS = ("cold_ms", "warm_ms", "vs_baseline", "reference_ms")
_COMPACT_DETAIL_KEYS = (
    "device", "rows", "dataset_hours", "geomean_vs_baseline_all",
    "geomean_vs_baseline_heavy", "prewarm_s", "budget_watchdog_fired",
    "killed_by_signal", "budget_exhausted", "dataset_reused", "tql",
    "ingest", "qps_sweep", "batched_members", "result_cache_hits",
    "zero_failed_queries",
)


def _build_record() -> dict:
    """The COMPACT one-line summary record, built from the CURRENT state —
    shared by the end-of-run emitter, the per-query incremental partial
    write, and (via BENCH_PARTIAL.json) the guard process, so every exit
    path lands the same parseable format.  Full per-query diagnostics stay
    in the BENCH_PARTIAL payload; the record itself must FIT the driver's
    tail capture."""
    # shallow snapshots: the watchdog can emit while the main thread is
    # still inserting per-query entries — iterating the live dicts could
    # tear mid-json.dumps
    detail, results = dict(_STATE["detail"]), dict(_STATE["results"])
    ok = {k: v for k, v in results.items() if "vs_baseline" in v}
    if ok:
        try:
            # max(x, 1e-9): a pathological rep can round vs_baseline to
            # 0.0 and log(0) must not kill the ONLY summary line (a
            # validation run died exactly here)
            detail["geomean_vs_baseline_all"] = round(
                math.exp(sum(
                    math.log(max(v["vs_baseline"], 1e-9)) for v in ok.values()
                ) / len(ok)), 2
            )
            heavy = [k for k in ok if ok[k]["reference_ms"] >= 500]
            if heavy:
                detail["geomean_vs_baseline_heavy"] = round(
                    math.exp(sum(
                        math.log(max(ok[k]["vs_baseline"], 1e-9)) for k in heavy
                    ) / len(heavy)), 2
                )
            # the live detail keeps the geomeans too, so partial writes
            # and later snapshots carry them
            for k in ("geomean_vs_baseline_all", "geomean_vs_baseline_heavy"):
                if k in detail:
                    _STATE["detail"][k] = detail[k]
        except Exception as e:  # noqa: BLE001 — summary must still land
            detail["geomean_error"] = repr(e)
    compact_q: dict = {}
    cold_over: list = []
    for name, v in results.items():
        cq = {k: v[k] for k in _COMPACT_QUERY_KEYS if k in v}
        if "error" in v and "vs_baseline" not in v:
            cq["error"] = str(v["error"])[:60]
        compact_q[name] = cq
        ref, c = v.get("reference_ms"), v.get("cold_ms")
        if ref and c is not None and c > 2 * ref:
            cold_over.append(name)
    cdetail = {k: detail[k] for k in _COMPACT_DETAIL_KEYS if k in detail}
    # falsy convenience flags cost bytes without carrying information:
    # their absence IS the false reading
    for k in ("budget_watchdog_fired", "budget_exhausted", "dataset_reused"):
        if k in cdetail and not cdetail[k]:
            del cdetail[k]
    cdetail["cold_over_2x_ref"] = cold_over
    # per-query stage attribution (flight recorder): one comma-joined
    # string in queries-dict order — "-" marks a query with no digest
    stages = [str(v.get("stage", "-")) for v in results.values()]
    if any(s != "-" for s in stages):
        cdetail["stages"] = ",".join(stages)
    cdetail["queries"] = compact_q
    headline = _STATE["headline"] or {"warm_ms": None, "vs_baseline": None}
    record = {
        "metric": "tsbs_double_groupby_1_e2e_warm_p50",
        "value": headline.get("warm_ms"),
        "unit": "ms",
        "vs_baseline": headline.get("vs_baseline"),
        "detail": cdetail,
    }
    return _clamp_record(record)


# The emitted line must FIT the driver's ~2000-byte tail capture in EVERY
# state — including the pathological all-queries-timed-out run where each
# cold_ms/warm_ms is 6+ digits (r03 died to an oversized line once; the
# unit pin in tests/test_bench_smoke.py proves the worst case).  Trims
# apply in order of information value until the line fits: the stage
# digests and the cold_over list are conveniences (their data survives in
# the per-query fields / BENCH_PARTIAL.json), the tql digest is
# informational, and integer-rounded millisecond floats lose nothing the
# acceptance checks read.
_RECORD_BYTES_MAX = 1880


def _clamp_record(record: dict) -> dict:
    def size(r) -> int:
        return len(json.dumps(r, separators=(",", ":")))

    if size(record) <= _RECORD_BYTES_MAX:
        return record
    d = record.get("detail") or {}
    # tsbs records carry a per-query dict here; the mixed record reuses
    # the key as a completed-queries COUNTER — treat that as "no queries"
    q = d.get("queries")
    q = q if isinstance(q, dict) else {}
    # 1. round per-query millisecond floats >= 100 to ints (123456.8 ->
    # 123457; sub-100 ms figures keep their decimals — that precision is
    # the measurement)
    for entry in q.values():
        for k in ("cold_ms", "warm_ms"):
            v = entry.get(k)
            if isinstance(v, float) and v >= 100:
                entry[k] = round(v)
    if size(record) <= _RECORD_BYTES_MAX:
        return record
    # 2. cap the cold_over convenience list (per-query cold_ms vs
    # reference_ms still carry the full verdict)
    co = d.get("cold_over_2x_ref")
    if isinstance(co, list) and len(co) > 4:
        d["cold_over_2x_ref"] = co[:4] + [f"+{len(co) - 4} more"]
    if size(record) <= _RECORD_BYTES_MAX:
        return record
    # 2b. mixed-mode conveniences, cheapest first: the hotspot phase
    # latencies and long error strings are diagnostics whose full copies
    # live in BENCH_PARTIAL.json
    hs = d.get("hotspot")
    if isinstance(hs, dict):
        hs.pop("phases", None)
    # the device-health digest keeps its verdict scalars (wedged /
    # quarantines / healed / zero_failed_queries); nested per-state maps
    # are diagnostics whose full copy lives in BENCH_PARTIAL.json
    dvh = d.get("device_health")
    if isinstance(dvh, dict):
        d["device_health"] = {
            k: v for k, v in dvh.items()
            if not isinstance(v, (dict, list))
        }
    errs = d.get("errors")
    if isinstance(errs, list) and errs:
        d["errors"] = [str(e)[:40] for e in errs[:2]]
    if size(record) <= _RECORD_BYTES_MAX:
        return record
    # 2c. only then spend the sweep CURVES — the knee/sustained scalars
    # (the verdict) survive in every regime
    sw = d.get("qps_sweep")
    if isinstance(sw, dict):
        for mode in ("off", "on"):
            ms = sw.get(mode)
            if isinstance(ms, dict):
                ms.pop("curve", None)
    if size(record) <= _RECORD_BYTES_MAX:
        return record
    # 3. slim the ingest digest to its headline — one "rows/s;frames/
    # writes" string — BEFORE spending the per-query stage digests; the
    # full ingest stage breakdown survives in BENCH_PARTIAL.json
    ing = d.get("ingest")
    if isinstance(ing, dict):
        d["ingest"] = f"{ing.get('rps', '?')};{ing.get('fw', '?')}"
    if size(record) <= _RECORD_BYTES_MAX:
        return record
    # 4. drop the stage-attribution string (full recorder detail lives
    # in BENCH_PARTIAL.json)
    d.pop("stages", None)
    if size(record) <= _RECORD_BYTES_MAX:
        return record
    # 5. slim the tql digest to its scalar evidence
    tql = d.get("tql")
    if isinstance(tql, dict):
        d["tql"] = {
            k: v for k, v in tql.items() if not isinstance(v, (list, dict))
        } or {"trimmed": True}
    if size(record) <= _RECORD_BYTES_MAX:
        return record
    # 6. truncate error strings hard
    for entry in q.values():
        if "error" in entry:
            entry["error"] = str(entry["error"])[:24]
    if size(record) <= _RECORD_BYTES_MAX:
        return record
    # 7. last resort (the all-queries-timed-out regime, where every ms
    # figure is 6+ digits): drop per-query reference_ms — the reference
    # numbers are static constants published in bench.py's QUERIES table
    # and the driver's baseline, so the failed-run evidence (cold/warm/
    # vs_baseline) survives intact
    for entry in q.values():
        entry.pop("reference_ms", None)
    if isinstance(d.get("device"), str):
        d["device"] = d["device"][:24]
    return record


def _failed_phases(detail: dict, results: dict) -> list:
    """Phases and queries that recorded an error where a result belongs
    (each phase catches its own failure so the record still lands)."""
    failed = [k for k in detail if k.endswith("_error")]
    failed += [
        k for k, v in detail.items() if isinstance(v, dict) and "error" in v
    ]
    failed += [
        f"query:{k}" for k, v in results.items()
        if "error" in v or "verify_error" in v
    ]
    return failed


def _emit_final_locked():
    record = _build_record()
    _emit(record, compact=True)
    # partial keeps the FULL diagnostics; the record inside it is the
    # compact emitted line (what the guard prints verbatim)
    _write_partial(
        {
            "detail": dict(_STATE["detail"]),
            "queries": dict(_STATE["results"]),
        },
        record=record,
    )
    try:
        # tells the guard process the record landed (see _start_guard)
        with open(PARTIAL_PATH + ".done", "w") as f:
            f.write("1")
    except OSError:
        pass


def _on_term(signum, frame):  # noqa: ARG001 — signal signature
    _STATE["detail"]["killed_by_signal"] = signum
    try:
        faulthandler.dump_traceback(file=sys.stderr)
    except Exception:  # noqa: BLE001 — diagnostics only
        pass
    _emit_final()
    os._exit(113)


for _sig in (signal.SIGTERM, signal.SIGINT):
    try:
        signal.signal(_sig, _on_term)
    except (ValueError, OSError):
        pass


def _start_budget_watchdog():
    """rc=0 within GRAFT_BENCH_BUDGET_S, unconditionally: whatever phase
    is still running (a stuck query, a probe, even XLA compile), the
    watchdog emits the one-line summary with everything that finished and
    exits 0 before the driver's external timeout can produce rc=124
    (rounds 2-5 all timed out; the official record stayed unparsed)."""
    import threading

    def run():
        while True:
            left = BUDGET_S - WATCHDOG_GRACE_S - _elapsed()
            if left <= 0:
                break
            time.sleep(min(left, 5.0))
            # keep the on-disk partial fresh on every tick: even if this
            # thread never gets to emit (a wedged native op holds the
            # GIL), the guard process can still publish a parseable
            # record from the last write BEFORE the deadline
            try:
                _write_partial({
                    "detail": dict(_STATE["detail"]),
                    "queries": dict(_STATE["results"]),
                })
            except Exception:  # noqa: BLE001 — bookkeeping only
                pass
        if _STATE["emitted"]:
            return
        _STATE["detail"]["budget_watchdog_fired"] = True
        try:
            _emit_final()
        except BaseException:  # noqa: BLE001 — the main thread mutates
            # results/detail concurrently; a torn iteration must not kill
            # the watchdog before it can exit 0 with SOME parseable line
            try:
                _emit({
                    "metric": "tsbs_double_groupby_1_e2e_warm_p50",
                    "value": None, "unit": "ms", "vs_baseline": None,
                    "detail": {"budget_watchdog_fired": True,
                               "emit_error": True},
                })
            except BaseException:  # noqa: BLE001
                pass
        try:
            sys.stdout.flush()
        finally:
            os._exit(0)

    threading.Thread(target=run, name="bench-budget-watchdog", daemon=True).start()


def _start_guard_process():
    """Wedge-proof parseable-output guarantee: a tiny subprocess sharing
    this process's stdout that, if the parent has NOT emitted its summary
    by the deadline (done-marker absent), prints a one-line record built
    from BENCH_PARTIAL.json itself.  The in-process watchdog cannot run
    when a native op (XLA compile, a blocked device fetch) wedges every
    Python thread — rounds 2-5 all ended rc=124 with the record emitted
    only AFTER the driver's kill, i.e. never.  The guard's line lands on
    the shared stdout BEFORE the deadline regardless of parent state."""
    import subprocess

    deadline = max(BUDGET_S - max(WATCHDOG_GRACE_S / 3.0, 15.0), 30.0)
    code = (
        "import json,os,sys,time\n"
        "deadline=float(sys.argv[1]); partial=sys.argv[2]; ppid=int(sys.argv[3])\n"
        "marker=partial+'.done'\n"
        "t0=time.time()\n"
        "while time.time()-t0 < deadline:\n"
        "    time.sleep(2.0)\n"
        "    if os.path.exists(marker): sys.exit(0)\n"
        "    try: os.kill(ppid, 0)\n"
        "    except OSError: sys.exit(0)\n"
        "if os.path.exists(marker): sys.exit(0)\n"
        "detail={'guard_emitted': True}; queries={}; rec=None\n"
        "try:\n"
        "    with open(partial) as f: d=json.load(f)\n"
        "    rec=d.get('record')\n"
        "    detail.update(d.get('detail', {})); queries=d.get('queries', {})\n"
        "except Exception: pass\n"
        "if rec:\n"
        "    rec.setdefault('detail', {})['guard_emitted']=True\n"
        "    print(json.dumps(rec,separators=(',',':')), flush=True)\n"
        "    sys.exit(0)\n"
        "detail.pop('queries', None)\n"
        "print(json.dumps({'metric':'tsbs_double_groupby_1_e2e_warm_p50',"
        "'value':None,'unit':'ms','vs_baseline':None,'detail':detail},"
        "separators=(',',':')), flush=True)\n"
    )
    try:
        os.unlink(PARTIAL_PATH + ".done")
    except OSError:
        pass
    try:
        subprocess.Popen(
            [sys.executable, "-c", code, str(deadline), PARTIAL_PATH,
             str(os.getpid())],
            stdin=subprocess.DEVNULL, stdout=None, stderr=subprocess.DEVNULL,
        )
    except Exception:  # noqa: BLE001 — the guard is insurance, not a dep
        pass


def _probe_device_fetch(jax, jnp) -> dict:
    """Dispatch floor of this machine: a real fetch of a FRESH device
    buffer (fetching the same buffer twice is host-cached and free), and
    the enqueue cost of a dispatch nobody waits for."""
    import numpy as _np

    f = jax.jit(lambda x: x + 1.0)
    f(jnp.float32(0.0))  # compile
    fetches = []
    for i in range(5):
        t0 = time.perf_counter()
        _ = jax.device_get(f(jnp.float32(float(i))))
        fetches.append((time.perf_counter() - t0) * 1000)
    enq = []
    for i in range(5):
        t0 = time.perf_counter()
        _ = f(jnp.float32(float(i + 100)))
        enq.append((time.perf_counter() - t0) * 1000)
    return {
        "device_fetch_ms": round(float(_np.median(fetches)), 1),
        "dispatch_enqueue_ms": round(float(_np.median(enq)), 2),
    }


def _http_ingest_probe(db) -> dict:
    """Honest protocol-path ingest: influx line protocol POSTed over a real
    HTTP socket (reference BASELINE ingest is measured through the TSBS
    client/HTTP path; round 2's in-process number was apples-to-oranges)."""
    import urllib.request

    from greptimedb_tpu.servers.http import HttpServer

    srv = HttpServer(db).start()
    try:
        url = f"http://{srv.address}/v1/influxdb/write?db=public"
        rng = np.random.default_rng(3)
        batch_rows = 5000
        n_batches = max(HTTP_INGEST_ROWS // batch_rows, 1)
        bodies = []
        for b in range(n_batches):
            # distinct (host, ms-timestamp) per row — sub-ms offsets would
            # collapse after the server's ns->ms conversion and the dedup'd
            # rows would inflate the rows/s number
            ts_ms0 = T0 + HOURS * 3600_000 + b * 10_000 + 1000
            vals = rng.uniform(0, 100, batch_rows)
            bodies.append("\n".join(
                f"cpu_http,hostname=host_{h % 1000} usage_user={vals[h]:.3f} "
                f"{(ts_ms0 + h) * 1_000_000}"
                for h in range(batch_rows)
            ).encode())
        total = 0
        t0 = time.perf_counter()
        for body in bodies:
            req = urllib.request.Request(
                url, data=body, method="POST",
                headers={"Content-Type": "text/plain"},
            )
            with urllib.request.urlopen(req) as resp:
                resp.read()
            total += batch_rows
        t_total = time.perf_counter() - t0
        out = {
            "ingest_http_rows_per_sec": round(total / max(t_total, 1e-9)),
            "ingest_http_rows": total,
        }
        # parse-path attribution: the vectorized columnar parse (what the
        # server ran above) vs the per-line Point parser it replaced on
        # this shape — the probe's rows/s improvement must be assertable
        # from the record, not inferred
        try:
            from greptimedb_tpu.servers.influx import (
                parse_line_protocol, parse_line_protocol_columnar,
            )

            body = bodies[0]
            t0 = time.perf_counter()
            for _ in range(3):
                assert parse_line_protocol_columnar(body, "ns") is not None
            t_col = (time.perf_counter() - t0) / 3 * 1000
            t0 = time.perf_counter()
            parse_line_protocol(body.decode(), "ns")
            t_point = (time.perf_counter() - t0) * 1000
            out["ingest_http_parse"] = {
                "columnar_ms": round(t_col, 1),
                "point_ms": round(t_point, 1),
                "speedup": round(t_point / max(t_col, 1e-9), 1),
            }
        except Exception as e:  # noqa: BLE001 — attribution is best-effort
            out["ingest_http_parse"] = {"error": repr(e)[:60]}
        return out
    finally:
        srv.stop()


def _wal_wire_probe() -> dict:
    """--wal-backend kafka-fake: group commits over a real socket to the
    fake broker vs the local file WAL on the same shape — the wire-latency
    datapoint for the remote WAL, kept OFF the headline ingest numbers
    (throwaway tempdir engines, small row count)."""
    import shutil
    import tempfile

    from greptimedb_tpu.datatypes import (
        ColumnSchema, ConcreteDataType, Schema, SemanticType,
    )
    from greptimedb_tpu.remote.fake_kafka import FakeKafkaBroker
    from greptimedb_tpu.storage.engine import TimeSeriesEngine
    from greptimedb_tpu.utils.config import StorageConfig

    schema = Schema(columns=[
        ColumnSchema("hostname", ConcreteDataType.STRING, SemanticType.TAG),
        ColumnSchema(
            "ts", ConcreteDataType.TIMESTAMP_MILLISECOND,
            SemanticType.TIMESTAMP,
        ),
        ColumnSchema("usage_user", ConcreteDataType.FLOAT64),
    ])
    rng = np.random.default_rng(11)
    groups, per_group, rows = 100, 4, 500

    def batches(g):
        ts0 = (g * per_group + 1) * 10_000
        return [
            pa.RecordBatch.from_arrays(
                [
                    pa.array([f"host_{i % 97}" for i in range(rows)]),
                    pa.array(
                        [ts0 + b * 1000 + i for i in range(rows)],
                        pa.timestamp("ms"),
                    ),
                    pa.array(rng.uniform(0, 100, rows)),
                ],
                schema=schema.to_arrow(),
            )
            for b in range(per_group)
        ]

    def drive(cfg) -> dict:
        engine = TimeSeriesEngine(cfg)
        engine.create_region(1, schema)
        lat = []
        t0 = time.perf_counter()
        for g in range(groups):
            t1 = time.perf_counter()
            engine.write_group(1, batches(g))
            lat.append(time.perf_counter() - t1)
        total = time.perf_counter() - t0
        engine.close()
        lat.sort()
        return {
            "rows_per_sec": round(groups * per_group * rows / max(total, 1e-9)),
            "commit_p50_ms": round(lat[len(lat) // 2] * 1000, 3),
            "commit_p99_ms": round(lat[int(len(lat) * 0.99)] * 1000, 3),
        }

    home = tempfile.mkdtemp(prefix="graft_walwire_")
    try:
        with FakeKafkaBroker() as broker:
            wire = drive(StorageConfig(
                data_home=os.path.join(home, "kafka"),
                wal_provider="kafka",
                wal_kafka_endpoints=broker.endpoint,
            ))
        local = drive(StorageConfig(data_home=os.path.join(home, "local")))
        return {
            "backend": "kafka-fake",
            "rows": groups * per_group * rows,
            "group_size": per_group,
            "wire": wire,
            "local": local,
        }
    finally:
        shutil.rmtree(home, ignore_errors=True)


def _larger_than_hbm_probe() -> dict:
    """>=2^28 rows whose device working set exceeds the tile budget:
    the engine's region-streamed path (tile_cache._streamed_execute)
    builds/dispatches/releases one region at a time.  Recorded evidence:
    per-region wall times (flatness = the 1B-row trajectory — more rows
    is more regions at the same per-region cost, bounded HBM throughout)
    and the resident-bytes ceiling.  Reference scale anchor: the 1B-row
    JSONBench claim (reference README.md:104-106) and TSBS
    docs/benchmarks/tsbs/v0.12.0.md."""
    import shutil
    import tempfile

    from greptimedb_tpu.database import Database
    from greptimedb_tpu.parallel import tile_cache as tc
    from greptimedb_tpu.utils import metrics as m

    out: dict = {"rows": LTH_ROWS}
    n_parts = 16
    metrics_n = 3
    budget_mb = int(os.environ.get("GRAFT_BENCH_LTH_BUDGET_MB", 4096))
    home = None
    db = None
    try:
        # ~25 GB of Parquet+WAL for 2^28 rows; refusing beats filling the
        # disk under the main dataset (a validation run hit 100%)
        free_gb = shutil.disk_usage(tempfile.gettempdir()).free / 2**30
        if free_gb < 35:
            out["skipped"] = f"only {free_gb:.0f} GB free disk (need 35)"
            return out
        home = tempfile.mkdtemp(prefix="graft_lth_")
        db = Database(data_home=home)
        db.config.query.tpu_min_rows = 300_000
        db.config.query.tile_cache_mb = budget_mb
        if db.query_engine.tile_cache is not None:
            db.query_engine.tile_cache.budget = budget_mb << 20
            # throwaway dataset: persisted consolidations would double the
            # disk footprint for a cold-start the probe doesn't measure
            db.query_engine.tile_cache.persist_dir = None
        out["tile_budget_mb"] = budget_mb
        cols_sql = ", ".join(f"m{i} DOUBLE" for i in range(metrics_n))
        db.sql(
            f"CREATE TABLE big (hostname STRING, ts TIMESTAMP(3) TIME INDEX,"
            f" {cols_sql}, PRIMARY KEY (hostname))"
            f" PARTITION BY HASH (hostname) PARTITIONS {n_parts}"
            f" WITH (append_mode = 'true')"
        )
        n_hosts = 256
        hosts_arr = np.array([f"host_{i:03d}" for i in range(n_hosts)])
        chunk = 4_194_304
        rng = np.random.default_rng(17)
        gt_sum = np.zeros(n_hosts)
        gt_cnt = np.zeros(n_hosts, np.int64)
        t0 = time.perf_counter()
        done = 0
        while done < LTH_ROWS:
            n = min(chunk, LTH_ROWS - done)
            hidx = np.arange(done, done + n) % n_hosts
            ts = T0 + np.arange(done, done + n, dtype=np.int64) * 50
            vals = {f"m{i}": rng.uniform(0, 100, n) for i in range(metrics_n)}
            batch = pa.table({
                "hostname": pa.array(hosts_arr[hidx]),
                "ts": pa.array(ts, pa.timestamp("ms")),
                **{k: pa.array(v) for k, v in vals.items()},
            })
            db.insert_rows("big", batch)
            np.add.at(gt_sum, hidx, vals["m0"])
            np.add.at(gt_cnt, hidx, 1)
            done += n
            if _elapsed() > BUDGET_S - 300:
                # the probe's queries + the summary must still fit INSIDE
                # the wall budget (the rc=0 contract) — stop ingesting
                out["ingest_aborted_at_rows"] = done
                return out
        db.storage.flush_all()
        out["ingest_s"] = round(time.perf_counter() - t0, 1)
        _emit({"event": "lth_ingested", "rows": done,
               "secs": out["ingest_s"], "elapsed_s": round(_elapsed(), 1)})

        agg = ", ".join(
            f"sum(m{i}) AS s{i}, avg(m{i}) AS a{i}" for i in range(metrics_n)
        )
        sql = (f"SELECT hostname, count(*) AS c, {agg} FROM big"
               f" GROUP BY hostname ORDER BY hostname")
        stream0 = m.TILE_STREAM_QUERIES.get()

        def probe_timeout(ceiling: float) -> float:
            return max(min(ceiling, BUDGET_S - WATCHDOG_GRACE_S - _elapsed() - 20), 20.0)

        try:
            db.config.query.timeout_s = probe_timeout(900.0)
            t0 = time.perf_counter()
            table = db.sql_one(sql)
            out["cold_ms"] = round((time.perf_counter() - t0) * 1000, 1)
            out["streamed"] = m.TILE_STREAM_QUERIES.get() > stream0
            chunk_ms = list(tc.LAST_STREAM_CHUNK_MS)
            if chunk_ms:
                med = float(np.median(chunk_ms))
                out["region_ms_median"] = round(med, 1)
                out["region_ms_max"] = round(max(chunk_ms), 1)
                out["regions"] = len(chunk_ms)
                if len(chunk_ms) > 2:
                    # region 0 pays the one-off XLA compile; flatness is
                    # about the steady state the 1B-row trajectory rides
                    tail = chunk_ms[1:]
                    out["region_flatness_excl_compile"] = round(
                        max(tail) / max(float(np.median(tail)), 1e-9), 2
                    )
            cache = db.query_engine.tile_cache
            if cache is not None:
                out["resident_mb_after"] = cache._used >> 20
            # one warm rep: planes re-stream (they were released), host
            # consolidation + dictionary cached
            db.config.query.timeout_s = probe_timeout(600.0)
            t0 = time.perf_counter()
            table = db.sql_one(sql)
            out["warm_ms"] = round((time.perf_counter() - t0) * 1000, 1)
            if tc.LAST_STREAM_CHUNK_MS:
                warm_chunks = list(tc.LAST_STREAM_CHUNK_MS)
                out["warm_region_ms_median"] = round(
                    float(np.median(warm_chunks)), 1
                )
                if len(warm_chunks) > 1:
                    out["warm_region_flatness"] = round(
                        max(warm_chunks)
                        / max(float(np.median(warm_chunks)), 1e-9), 2
                    )
            # verify against independent numpy ground truth
            got_h = table["hostname"].to_pylist()
            got_c = table["c"].to_pylist()
            got_s = table["s0"].to_pylist()
            ok = len(got_h) == n_hosts
            for h, c, s in zip(got_h, got_c, got_s):
                i = int(h.split("_")[1])
                ok = ok and c == int(gt_cnt[i]) and abs(
                    s - gt_sum[i]
                ) < 1e-7 * max(abs(gt_sum[i]), 1.0)
            out["verified"] = bool(ok)
        finally:
            db.config.query.timeout_s = 0.0
    except Exception as e:  # noqa: BLE001 — probe must never kill the bench
        out["error"] = repr(e)
    finally:
        if db is not None:
            try:
                db.close()
            except Exception:  # noqa: BLE001
                pass
        if home is not None:
            shutil.rmtree(home, ignore_errors=True)
    return out


def _agg_strategy_probe(db) -> dict:
    """Hash vs sort on a HIGH-CARDINALITY group-by (the shape TSBS never
    has: ~64k distinct (a, b) pairs whose padded dense space is ~2^32).
    The dense path cannot hold [G] states at that size and degrades off
    the device; the hash path runs it as one device dispatch over a
    bounded slot table.  Both must return the same row count — the probe
    records warm medians and the speedup."""
    from greptimedb_tpu.utils import metrics as m

    out: dict = {}
    n = int(os.environ.get("GRAFT_AGG_PROBE_ROWS", 1 << 20))
    keys = int(os.environ.get("GRAFT_AGG_PROBE_KEYS", 1 << 16))
    out["rows"], out["distinct_keys"] = n, keys
    rng = np.random.default_rng(23)
    db.sql(
        "CREATE TABLE agg_probe (a STRING, b STRING, ts TIMESTAMP(3) TIME"
        " INDEX, v DOUBLE, PRIMARY KEY (a, b))"
        " WITH (append_mode = 'true')"
    )
    try:
        chunk = 1 << 19
        done = 0
        while done < n:
            c = min(chunk, n - done)
            k = rng.integers(0, keys, c)
            batch = pa.table({
                "a": pa.array([f"a{i >> 8:03d}" for i in k]),
                "b": pa.array([f"b{i:05d}" for i in k]),
                "ts": pa.array(
                    T0 + np.arange(done, done + c, dtype=np.int64),
                    pa.timestamp("ms"),
                ),
                "v": pa.array(rng.integers(0, 1000, c).astype(np.float64)),
            })
            db.insert_rows("agg_probe", batch)
            done += c
            if _remaining() < 120:
                out["ingest_aborted_at_rows"] = done
                return out
        db.storage.flush_all()
        q = ("SELECT a, b, sum(v) AS s, count(*) AS c FROM agg_probe"
             " GROUP BY a, b")
        rows_out = {}
        h0 = m.AGG_STRATEGY_TOTAL.get(strategy="hash")
        for strat in ("sort", "hash"):
            db.config.query.agg_strategy = strat
            db.config.query.timeout_s = max(min(240.0, _remaining() - 30), 20.0)
            try:
                t = db.sql_one(q)  # cold: builds planes / falls back
                walls = []
                for _ in range(3):
                    if _remaining() < 45:
                        break
                    t0 = time.perf_counter()
                    t = db.sql_one(q)
                    walls.append((time.perf_counter() - t0) * 1000)
                rows_out[strat] = t.num_rows
                if walls:
                    out[f"{strat}_warm_ms"] = round(float(np.median(walls)), 1)
            except Exception as e:  # noqa: BLE001 — record, keep probing
                out[f"{strat}_error"] = repr(e)
            finally:
                db.config.query.timeout_s = 0.0
        db.config.query.agg_strategy = "auto"
        # delta, not the cumulative process counter: earlier TSBS queries
        # choosing hash must not be misattributed to the probe
        out["hash_dispatches"] = m.AGG_STRATEGY_TOTAL.get(strategy="hash") - h0
        if len(rows_out) == 2 and len(set(rows_out.values())) == 1:
            out["rows_out"] = next(iter(rows_out.values()))
            out["strategies_agree"] = True
        elif rows_out:
            # one strategy errored (or row counts differ): never claim an
            # agreement that was not actually tested
            out["rows_out_by_strategy"] = rows_out
            out["strategies_agree"] = False
        if "sort_warm_ms" in out and "hash_warm_ms" in out:
            out["speedup_hash_vs_sort"] = round(
                out["sort_warm_ms"] / max(out["hash_warm_ms"], 1e-9), 2
            )
    finally:
        try:
            db.sql("DROP TABLE agg_probe")
        except Exception:  # noqa: BLE001 — probe cleanup is best-effort
            pass
    return out


def _numpy_rate_twin_ms(sid, ts, vals, num_series, start, end, step, rng_ms):
    """Host-numpy reference for PromQL rate over flat sorted samples —
    the TQL phase's equivalent of the TSBS reference_ms twin: vectorized
    reset strip + K-windows-per-sample fold + extrapolatedRate, timed.
    Returns (elapsed_ms, defined_cell_count)."""
    t0 = time.perf_counter()
    steps = np.arange(start, end + 1, step, dtype=np.int64)
    W = len(steps)
    k = -(-rng_ms // step)
    G = num_series * W
    prev_v = np.concatenate([vals[:1], vals[:-1]])
    prev_s = np.concatenate([sid[:1], sid[:-1]])
    same = sid == prev_s
    if len(same):
        same[0] = False
    drop = np.where(same & (vals < prev_v), prev_v, 0.0)
    cum = np.cumsum(drop)
    idx = np.arange(len(sid))
    marked = np.where(~same, idx, 0)
    last_first = np.maximum.accumulate(marked)
    adj = vals + (cum - (cum - drop)[last_first])
    w0 = np.maximum(np.ceil((ts - start) / step).astype(np.int64), 0)
    count = np.zeros(G, np.int64)
    first_ts = np.full(G, np.iinfo(np.int64).max)
    last_ts = np.full(G, np.iinfo(np.int64).min)
    fv = np.zeros(G)
    lv = np.zeros(G)
    sidW = sid.astype(np.int64) * W
    for j in range(k):
        w = w0 + j
        t_w = start + w * step
        in_w = (w < W) & (ts <= t_w) & (ts > t_w - rng_ms)
        g = (sidW + w)[in_w]
        np.add.at(count, g, 1)
        np.minimum.at(first_ts, g, ts[in_w])
        np.maximum.at(last_ts, g, ts[in_w])
    for j in range(k):
        w = w0 + j
        t_w = start + w * step
        in_w = (w < W) & (ts <= t_w) & (ts > t_w - rng_ms)
        g = (sidW + w)[in_w]
        at_f = ts[in_w] == first_ts[g]
        at_l = ts[in_w] == last_ts[g]
        fv[g[at_f]] = adj[in_w][at_f]
        lv[g[at_l]] = adj[in_w][at_l]
    defined = count >= 2
    si = (last_ts - first_ts).astype(np.float64)
    safe_c = np.maximum(count, 2)
    avg_b = si / (safe_c - 1)
    w_idx = np.arange(G, dtype=np.int64) % W
    t_end = start + w_idx * step
    d_s = (first_ts - (t_end - rng_ms)).astype(np.float64)
    d_e = (t_end - last_ts).astype(np.float64)
    thr = avg_b * 1.1
    ext_s = np.where(d_s < thr, d_s, avg_b / 2.0)
    ext_e = np.where(d_e < thr, d_e, avg_b / 2.0)
    result = lv - fv
    with np.errstate(all="ignore"):
        zero_dur = np.where(result > 0, si * (fv / np.where(result == 0, 1.0, result)), np.inf)
        ext_s = np.minimum(ext_s, np.where(zero_dur < 0, ext_s, zero_dur))
        safe_si = np.where(si == 0, 1.0, si)
        rate = result * ((si + ext_s + ext_e) / safe_si) / (rng_ms / 1000.0)
    n_def = int(defined.sum())
    _sink = float(np.nansum(np.where(defined, rate, 0.0)))  # force compute
    return (time.perf_counter() - t0) * 1000.0, n_def


def _tql_phase(db) -> dict:
    """TQL bench phase (ISSUE 13): PromQL rate / increase / sum by
    (hostname) of rate over a single-field metric twin of the persisted
    TSBS cpu data — warm tile path vs the legacy upload-per-query path
    (tql.tile=false) vs the host-numpy reference twin.  Every step is
    gated on REMAINING budget with the abort point recorded, so this
    phase can never jeopardize the main record."""
    from greptimedb_tpu.utils import metrics as m

    out: dict = {}
    te_ms = END
    ts_ms = END - 2 * 3600_000  # last 2 h of the dataset
    # single-field metric table (the PromQL engine needs one value
    # column); persists with the dataset dir and is reused across runs
    have = 0
    try:
        have = db.sql_one("SELECT count(*) AS n FROM tql_cpu")["n"][0].as_py()
    except Exception:  # noqa: BLE001 — table does not exist yet
        db.sql(
            "CREATE TABLE tql_cpu (hostname STRING, greptime_value DOUBLE,"
            " ts TIMESTAMP(3) TIME INDEX, PRIMARY KEY (hostname))"
            " WITH (append_mode = 'true')"
        )
    src = db.sql_one(
        f"SELECT hostname, ts, usage_user FROM cpu"
        f" WHERE ts >= {ts_ms} AND ts < {te_ms}"
    )
    if have < src.num_rows:
        t0 = time.perf_counter()
        batch = pa.table({
            "hostname": src["hostname"],
            "greptime_value": pc.cast(src["usage_user"], pa.float64()),
            "ts": src["ts"],
        })
        db.insert_rows("tql_cpu", batch)
        db.storage.flush_all()
        out["ingest_ms"] = round((time.perf_counter() - t0) * 1000, 1)
    out["rows"] = src.num_rows
    if _remaining() < 180:
        out["skipped"] = "remaining budget after tql ingest"
        return out

    # host-numpy reference twin over the same flat samples
    hn = src["hostname"].to_pylist()
    ts_np = np.asarray(pc.cast(src["ts"], pa.int64()).to_numpy(zero_copy_only=False))
    v_np = np.asarray(pc.cast(src["usage_user"], pa.float64()).to_numpy(zero_copy_only=False))
    combos: dict = {}
    sid = np.empty(len(hn), np.int32)
    for i, h in enumerate(hn):
        if h not in combos:
            combos[h] = len(combos)
        sid[i] = combos[h]
    order = np.lexsort((ts_np, sid))
    sid, ts_np, v_np = sid[order], ts_np[order], v_np[order]
    start_s, end_s = ts_ms // 1000 + 600, te_ms // 1000 - 60
    start, end, step, rng_ms = start_s * 1000, end_s * 1000, 60_000, 300_000
    twin_ms, twin_cells = _numpy_rate_twin_ms(
        sid, ts_np, v_np, len(combos), start, end, step, rng_ms
    )
    out["twin_ms"] = round(twin_ms, 1)
    out["twin_cells"] = twin_cells

    queries = [
        ("rate", f"TQL EVAL ({start_s}, {end_s}, '60s') rate(tql_cpu[5m])",
         True),
        ("sumby", f"TQL EVAL ({start_s}, {end_s}, '60s')"
                  " sum by (hostname) (rate(tql_cpu[5m]))", True),
        ("inc1", f"TQL EVAL ({start_s}, {end_s}, '60s')"
                 " increase(tql_cpu{hostname='host_1'}[5m])", False),
    ]
    for name, q, heavy in queries:
        if _remaining() < 120:
            out.setdefault("skipped_queries", []).append(
                {"query": name, "reason": "remaining budget"}
            )
            continue
        rec: dict = {"heavy": heavy}
        try:
            db.config.query.timeout_s = max(min(240.0, _remaining() - 30), 20.0)
            cs0 = m.TQL_TILE_COLD_SERVES.get()
            t0 = time.perf_counter()
            t = db.sql_one(q)
            rec["cold_ms"] = round((time.perf_counter() - t0) * 1000, 1)
            rec["rows_out"] = t.num_rows
            rec["cold_served"] = int(m.TQL_TILE_COLD_SERVES.get() - cs0)
            # wait out the background family build (budget-bounded)
            te = db.query_engine._tile_executor
            deadline = time.monotonic() + max(min(120.0, _remaining() - 60), 5.0)
            while time.monotonic() < deadline:
                with te._fused_lock:
                    if not te._fused_builds and not te._fused_queue:
                        break
                time.sleep(0.1)
            walls = []
            d0 = m.TQL_TILE_DISPATCHES.get()
            for _ in range(3):
                if _remaining() < 60:
                    break
                t0 = time.perf_counter()
                db.sql_one(q)
                walls.append((time.perf_counter() - t0) * 1000)
            if walls:
                rec["warm_ms"] = round(float(np.median(walls)), 1)
                rec["tile_dispatches"] = int(m.TQL_TILE_DISPATCHES.get() - d0)
            legacy = []
            db.config.tql.tile = False
            try:
                for _ in range(2):
                    if _remaining() < 60:
                        break
                    t0 = time.perf_counter()
                    db.sql_one(q)
                    legacy.append((time.perf_counter() - t0) * 1000)
            finally:
                db.config.tql.tile = True
            if legacy:
                rec["legacy_ms"] = round(float(np.median(legacy)), 1)
            if walls and legacy:
                rec["vs_legacy"] = round(rec["legacy_ms"] / max(rec["warm_ms"], 1e-9), 2)
        except Exception as e:  # noqa: BLE001 — record, keep phasing
            rec["error"] = repr(e)
        finally:
            db.config.query.timeout_s = 0.0
        out[name] = rec
    return out


def main():
    ensure_x64()
    _start_budget_watchdog()
    _start_guard_process()
    import shutil
    import tempfile

    import jax

    from greptimedb_tpu.database import Database
    from greptimedb_tpu.utils import metrics as m

    detail: dict = _STATE["detail"]
    detail.update({"device": str(jax.devices()[0]), "dataset_hours": HOURS})
    results: dict = _STATE["results"]
    headline = None

    # persistent dataset home: the ingest + flush + persisted tile
    # consolidations are keyed by the dataset-parameter hash and reused
    # by later runs
    reuse = False
    marker = None
    if DATA_DIR:
        home = os.path.join(DATA_DIR, f"tsbs_{_dataset_key()}")
        marker = os.path.join(home, "INGESTED.json")
        if os.path.exists(marker):
            try:
                with open(marker) as f:
                    reuse = json.load(f).get("key") == _dataset_key()
            except Exception:  # noqa: BLE001 — torn marker = no reuse
                reuse = False
        if not reuse and os.path.isdir(home) and os.listdir(home):
            # torn previous ingest (killed mid-run): start clean
            shutil.rmtree(home, ignore_errors=True)
        os.makedirs(home, exist_ok=True)
    else:
        home = tempfile.mkdtemp(prefix="graft_bench_")
    detail["dataset_reused"] = reuse
    db = Database(data_home=home)
    # cost-based routing: sub-threshold scans run on the host CPU path
    # (no device dispatch + fetch), where a small Arrow aggregation wins
    db.config.query.tpu_min_rows = int(os.environ.get("GRAFT_TPU_MIN_ROWS", 300_000))
    detail["tpu_min_rows"] = db.config.query.tpu_min_rows
    # 3-day TSBS needs ~10 GB of limb/value planes resident; the 8 GB
    # default budget would thrash between query families on a 16 GB chip
    tile_mb = int(os.environ.get("GRAFT_TILE_CACHE_MB", 9216))
    db.config.query.tile_cache_mb = tile_mb
    if db.query_engine.tile_cache is not None:
        db.query_engine.tile_cache.budget = tile_mb << 20
        if os.environ.get("GRAFT_TILE_PERSIST", "1") == "0":
            # larger-than-disk runs: skip the on-disk consolidation copy
            db.query_engine.tile_cache.persist_dir = None
    detail["tile_cache_mb"] = tile_mb
    if os.environ.get("GRAFT_BENCH_NO_FALLBACK"):
        db.config.query.fallback_to_cpu = False
    cols_sql = ", ".join(f"{mm} DOUBLE" for mm in METRICS)
    if not reuse:
        db.sql(
            f"CREATE TABLE cpu (hostname STRING, ts TIMESTAMP(3) TIME INDEX, "
            f"{cols_sql}, PRIMARY KEY (hostname)) WITH (append_mode = 'true')"
        )

    # ---- ingest (chunked; the servers' insert_rows path) -------------------
    # On reuse the SSTs are already on disk: the loop still runs the SAME
    # rng stream to rebuild the independent ground truth, skipping only
    # the inserts — generation is ~seconds, ingest was the ~260 s cost.
    rng = np.random.default_rng(7)
    ticks_total = HOURS * 3600 // SCRAPE_S
    chunk_ticks = max(1, 2_000_000 // N_HOSTS)
    hosts_arr = np.array([f"host_{i}" for i in range(N_HOSTS)])
    gt: dict[int, list] = {}  # (host, hour) ground truth for double-groupby-1
    n_rows = 0
    t_ing = 0.0
    t_synth = 0.0
    # per-stage attribution baselines (greptime_ingest_*): a slow r06
    # ingest must name its stage, not just its total
    ing0 = {
        "split": m.INGEST_SPLIT_MS.sum(), "wal": m.INGEST_WAL_MS.sum(),
        "mem": m.INGEST_MEMTABLE_MS.sum(),
        "enc": m.INGEST_FLUSH_ENCODE_MS.sum(),
        "frames": m.INGEST_WAL_FRAMES.get(),
        "writes": m.INGEST_WRITES_TOTAL.get(),
    }
    for start in range(0, ticks_total, chunk_ticks):
        t_s0 = time.perf_counter()
        ticks = min(chunk_ticks, ticks_total - start)
        ts = T0 + (start + np.arange(ticks, dtype=np.int64))[:, None] * (SCRAPE_S * 1000)
        ts = np.broadcast_to(ts, (ticks, N_HOSTS)).reshape(-1)
        hs = np.broadcast_to(hosts_arr[None, :], (ticks, N_HOSTS)).reshape(-1)
        vals = {mm: rng.uniform(0.0, 100.0, ticks * N_HOSTS) for mm in METRICS}
        t_synth += time.perf_counter() - t_s0
        if not reuse:
            batch = pa.table(
                {
                    "hostname": pa.array(hs),
                    "ts": pa.array(ts, pa.timestamp("ms")),
                    **{mm: pa.array(vals[mm], pa.float64()) for mm in METRICS},
                }
            )
            t0 = time.perf_counter()
            db.insert_rows("cpu", batch)
            t_ing += time.perf_counter() - t0
        n_rows += ticks * N_HOSTS
        in_w = (ts >= W12[0]) & (ts < W12[1])
        if in_w.any():
            hour = ((ts[in_w] - W12[0]) // 3600_000).astype(np.int64)
            hidx = np.broadcast_to(
                np.arange(N_HOSTS)[None, :], (ticks, N_HOSTS)
            ).reshape(-1)[in_w]
            key = hidx * 100 + hour
            sums = np.bincount(key, weights=vals["usage_user"][in_w])
            cnts = np.bincount(key)
            for k in np.nonzero(cnts)[0]:
                acc = gt.setdefault(int(k), [0.0, 0])
                acc[0] += sums[k]
                acc[1] += int(cnts[k])
    t0 = time.perf_counter()
    if not reuse:
        db.storage.flush_all()
    t_flush = time.perf_counter() - t0
    detail["rows"] = n_rows
    if not reuse:
        detail["ingest_inprocess_rows_per_sec"] = round(n_rows / max(t_ing, 1e-9))
        # compact per-stage digest for the summary record (clamp-aware:
        # the stage string is dropped before per-query evidence if the
        # line outgrows the tail capture) — a slow r06 ingest names its
        # stage, not just a total.  `st` = seconds per stage ("sy" synth,
        # "in" insert wall, "sp" split, "wa" wal, "me" memtable, "fe"
        # flush encode incl. async, "fl" final flush_all); `fw` =
        # frames/writes — merged-frame evidence (frames < writes when
        # group commit coalesced).  Stage seconds come from the
        # greptime_ingest_* histograms; the full breakdown also lands in
        # BENCH_PARTIAL.json via `ingest_stages`.
        stages_s = {
            "sy": t_synth, "in": t_ing,
            "sp": (m.INGEST_SPLIT_MS.sum() - ing0["split"]) / 1000,
            "wa": (m.INGEST_WAL_MS.sum() - ing0["wal"]) / 1000,
            "me": (m.INGEST_MEMTABLE_MS.sum() - ing0["mem"]) / 1000,
            "fe": (m.INGEST_FLUSH_ENCODE_MS.sum() - ing0["enc"]) / 1000,
            "fl": t_flush,
        }
        frames = int(m.INGEST_WAL_FRAMES.get() - ing0["frames"])
        writes = int(m.INGEST_WRITES_TOTAL.get() - ing0["writes"])
        detail["ingest"] = {
            "rps": detail["ingest_inprocess_rows_per_sec"],
            "st": ",".join(
                f"{k}{round(v) if v >= 10 else round(v, 1)}"
                for k, v in stages_s.items()
            ),
            "fw": f"{frames}/{writes}",
        }
        detail["ingest_stages"] = {
            k: round(v, 2) for k, v in stages_s.items()
        }
    detail["ingest_reference_rows_per_sec"] = 326_839
    detail["flush_secs"] = round(t_flush, 1)
    if marker and not reuse:
        try:
            with open(marker, "w") as f:
                json.dump({"key": _dataset_key(), "rows": n_rows}, f)
        except OSError:
            pass
    _emit({"event": "ingested", "rows": n_rows, "reused": reuse,
           "secs": round(t_ing + t_flush, 1),
           "elapsed_s": round(_elapsed(), 1)})
    _write_partial({"detail": detail, "queries": results})

    # ---- prewarm phase (cold path off the query path) ----------------------
    if PREWARM and _elapsed() < BUDGET_S * 0.6:
        try:
            pw0 = m.PREWARM_BUILDS.get()
            t0 = time.perf_counter()
            db.config.query.timeout_s = max(
                min(600.0, BUDGET_S * 0.6 - _elapsed()), 30.0
            )
            try:
                db.prewarm(tables=["cpu"])
            finally:
                db.config.query.timeout_s = 0.0
            detail["prewarm_s"] = round(time.perf_counter() - t0, 1)
            detail["prewarm_builds"] = m.PREWARM_BUILDS.get() - pw0
            _emit({"event": "prewarm", "secs": detail["prewarm_s"],
                   "regions_built": detail["prewarm_builds"],
                   "elapsed_s": round(_elapsed(), 1)})
        except Exception as e:  # noqa: BLE001 — prewarm must never kill the bench
            detail["prewarm_error"] = repr(e)

    # ---- honest protocol-path ingest probe ---------------------------------
    if HTTP_INGEST_ROWS > 0 and _elapsed() < BUDGET_S:
        try:
            detail.update(_http_ingest_probe(db))
            _emit({"event": "http_ingest",
                   "rows_per_sec": detail.get("ingest_http_rows_per_sec"),
                   "elapsed_s": round(_elapsed(), 1)})
        except Exception as e:  # noqa: BLE001 — probe must never kill the bench
            detail["ingest_http_error"] = repr(e)

    # ---- remote-WAL wire probe (--wal-backend kafka-fake) ------------------
    if WAL_BACKEND == "kafka-fake":
        if _remaining() < 60:
            detail["wal_wire"] = {
                "skipped": "remaining budget below wal-wire floor"
            }
        else:
            try:
                detail["wal_wire"] = _wal_wire_probe()
                _emit({"event": "wal_wire", **detail["wal_wire"],
                       "elapsed_s": round(_elapsed(), 1)})
            except Exception as e:  # noqa: BLE001 — probe must never kill
                detail["wal_wire"] = {"error": repr(e)[:80]}
    elif WAL_BACKEND != "local":
        detail["wal_wire"] = {"skipped": f"unknown backend {WAL_BACKEND!r}"}

    # ---- dispatch floor ----------------------------------------------------
    import jax.numpy as jnp

    detail.update(_probe_device_fetch(jax, jnp))
    _emit({"event": "device_fetch_probe", **{k: detail[k] for k in
           ("device_fetch_ms", "dispatch_enqueue_ms")}})

    # ---- queries -----------------------------------------------------------
    only = os.environ.get("GRAFT_BENCH_ONLY")
    queries = [q for q in QUERIES if only is None or q[0] in only.split(",")]
    budget_hit = False
    for name, sql, ref_ms in queries:
        if _remaining() <= 0:
            # REMAINING-budget gate (not just elapsed): the watchdog's
            # grace window is part of the contract — nothing may start
            # inside it
            budget_hit = True
            _emit({"event": "budget_exhausted", "skipped_from": name,
                   "skip_reason": "remaining budget below watchdog grace",
                   "elapsed_s": round(_elapsed(), 1)})
            break
        cold_ms = None
        entry_build_ms = None
        build_err = None
        build_skipped = None
        reps_skipped = None
        walls: list[float] = []
        table = None
        err = None
        cs0 = m.TILE_COLD_SERVES.get()
        bc0 = m.TILE_BUILD_COALESCED.get()
        rec_cursor = _recorder().RECORDER.cursor()
        # cold-phase readback accounting starts HERE: the cold query +
        # the untimed build rep fetch through the same counters, and
        # mixing them into the warm average made the record misleading
        # (dg-5: warm_ms 290 with readback_ms_avg 8431)
        rb_cold0 = m.TPU_READBACK_MS.sum()
        rep_readback: list[float] = []
        try:
            # HARD per-query watchdog (round-4 driver lesson): cold pays
            # consolidation/upload/compile, so it gets the wide ceiling;
            # warm reps must be cache hits, so a rep that degrades to a
            # CPU scan aborts fast and is recorded instead of eating the
            # whole run
            remaining = max(_remaining(), 30.0)
            db.config.query.timeout_s = min(600.0, remaining)
            t0 = time.perf_counter()
            table = db.sql_one(sql)
            cold_ms = (time.perf_counter() - t0) * 1000
            # one UNTIMED warm-up rep between cold and the timed reps: it
            # joins the fused family build the cold-serve router kicked
            # off in the background (legacy: pays the synchronous plane
            # build; ~70 s at TSBS scale, 300 s gives link-weather
            # margin).  Folding it into `walls` would poison the
            # cache-hit p50 the warm metric claims to be.
            if _remaining() <= 30:
                build_skipped = "remaining budget below watchdog grace"
                raise _BudgetSkip()
            db.config.query.timeout_s = min(
                300.0, max(_remaining(), 30.0)
            )
            t0 = time.perf_counter()
            try:
                table = db.sql_one(sql)
                entry_build_ms = round((time.perf_counter() - t0) * 1000, 1)
            except Exception as be:  # noqa: BLE001 — a timed-out build
                # rep commits partial planes; the timed reps finish them
                entry_build_ms = None
                build_err = repr(be)
            # readback accounting over the TIMED reps only (cold/build
            # fetches would poison the warm number), recorded for EVERY
            # query — readback_bytes is the honest O(rows_out) evidence;
            # readback_ms conflates transfer with waiting out the async
            # dispatch (device_get blocks on compute)
            rb0 = (
                m.TPU_READBACK_MS.sum(), m.TPU_READBACK_MS.total(),
                m.TPU_READBACK_BYTES.get(),
            )
            rbs0 = (
                m.TPU_READBACK_TRANSFER_MS.sum(),
                m.TPU_READBACK_DECODE_MS.sum(),
                m.TPU_READBACK_STREAMED.get(),
            )
            cc0 = m.TPU_COMPILE_CACHE_MISSES.get()
            rep_errs = 0
            for _rep in range(WARM_REPS):
                if _remaining() <= 10:
                    # warm reps ride the same remaining-budget gate as
                    # the probes: no phase may start inside the
                    # watchdog's grace window
                    reps_skipped = (
                        f"remaining budget: {len(walls)}/{WARM_REPS} done"
                    )
                    break
                # timed reps are cache hits; a tight ceiling kills
                # runaway CPU scans
                db.config.query.timeout_s = min(
                    120.0, max(_remaining(), 15.0)
                )
                rb_rep0 = m.TPU_READBACK_MS.sum()
                t0 = time.perf_counter()
                try:
                    table = db.sql_one(sql)
                except Exception as rep_e:  # noqa: BLE001 — one bad rep
                    # must not void the query: later reps hit the planes
                    # an aborted build already committed
                    rep_errs += 1
                    err = repr(rep_e)
                    if rep_errs >= 2 and not walls:
                        raise
                    continue
                walls.append((time.perf_counter() - t0) * 1000)
                rep_readback.append(m.TPU_READBACK_MS.sum() - rb_rep0)
        except _BudgetSkip:
            pass  # recorded via build_skipped; cold_ms already landed
        except Exception as e:  # noqa: BLE001 — one bad query must not kill the run
            err = repr(e)
        finally:
            db.config.query.timeout_s = 0.0
        # record whatever finished: a timeout on warm rep 4 must not throw
        # away the measured cold + 3 valid warm samples
        entry = {"reference_ms": ref_ms}
        if cold_ms is not None:
            entry["cold_ms"] = round(cold_ms, 1)
            # fused cold-path evidence: the cold run answered from the
            # host router / joined the background family build
            served = int(m.TILE_COLD_SERVES.get() - cs0)
            coalesced = int(m.TILE_BUILD_COALESCED.get() - bc0)
            if served:
                entry["cold_served"] = served
            if coalesced:
                entry["build_coalesced"] = coalesced
        if entry_build_ms is not None:
            entry["build_ms"] = entry_build_ms
        if build_err is not None:
            entry["build_error"] = build_err
        if build_skipped is not None:
            entry["build_skipped"] = build_skipped
        if reps_skipped is not None:
            entry["warm_reps_skipped"] = reps_skipped
        if walls:
            warm_ms = float(np.median(walls))
            rb1 = (
                m.TPU_READBACK_MS.sum(), m.TPU_READBACK_MS.total(),
                m.TPU_READBACK_BYTES.get(),
            )
            n_rb = rb1[1] - rb0[1]
            ratio = ref_ms / warm_ms
            entry.update(
                warm_ms=round(warm_ms, 2),
                # keep 4 decimals below 0.05: round(0.0027, 2) == 0.0
                # poisoned the geomean log in a validation run
                vs_baseline=round(ratio, 2 if ratio >= 0.05 else 4),
                rows_out=table.num_rows,
                warm_reps_done=len(walls),
                # uniform for EVERY query (0 = served without a device
                # fetch: host fast path / cold serve / CPU route)
                device_fetches=int(n_rb),
                # WARM-only: median of per-rep readback deltas — a rep
                # that rebuilt planes no longer poisons the average
                readback_ms_avg=round(float(np.median(rep_readback)), 2)
                if rep_readback else 0.0,
                # cold + untimed build rep readback, reported separately
                readback_ms_cold=round(rb0[0] - rb_cold0, 2),
                # transfer vs host-decode split per query (streamed-
                # readback wins must be attributable, not inferred)
                readback_transfer_ms_avg=round(
                    (m.TPU_READBACK_TRANSFER_MS.sum() - rbs0[0]) / n_rb, 2
                ) if n_rb else 0.0,
                readback_decode_ms_avg=round(
                    (m.TPU_READBACK_DECODE_MS.sum() - rbs0[1]) / n_rb, 2
                ) if n_rb else 0.0,
                readback_streamed=int(
                    m.TPU_READBACK_STREAMED.get() - rbs0[2]
                ),
                readback_bytes_avg=round((rb1[2] - rb0[2]) / n_rb) if n_rb else 0,
                # a warm rep that re-traces is a cache bug: make it visible
                compile_misses_warm=int(m.TPU_COMPILE_CACHE_MISSES.get() - cc0),
            )
        if err is not None:
            if walls:
                # reps that landed define the result; the stray failure
                # stays visible without voiding the measurement
                entry["rep_error"] = err
            else:
                entry["error"] = err
        # flight-recorder delta for THIS query (ghost/builder dispatches
        # excluded): full records ride BENCH_PARTIAL.json only; the
        # compact record carries the one-token stage digest
        try:
            q_recs = _recorder_delta(rec_cursor, "public.cpu")
            digest = _stage_digest(q_recs)
            if digest is not None:
                entry["stage"] = digest
            if q_recs:
                entry["recorder"] = [r.to_dict() for r in q_recs[-8:]]
        except Exception as rec_e:  # noqa: BLE001 — introspection is
            # best-effort: it must never void a measured query
            entry["recorder_error"] = repr(rec_e)
        results[name] = entry
        _emit({"query": name, **entry, "elapsed_s": round(_elapsed(), 1)})
        _write_partial({"detail": detail, "queries": results})

        if name == "double-groupby-1" and "error" not in entry:
            headline = entry
            _STATE["headline"] = entry
            try:
                got = {}
                hv = table["hostname"].to_pylist()
                tv = table["tb"].to_pylist()
                av = table[table.column_names[2]].to_pylist()
                host_to_idx = {f"host_{i}": i for i in range(N_HOSTS)}
                for h, t, a in zip(hv, tv, av):
                    ms = int(t.timestamp() * 1000) if hasattr(t, "timestamp") else int(t)
                    hour = (ms - W12[0]) // 3600_000
                    got[host_to_idx[h] * 100 + hour] = a
                assert len(got) == len(gt), (len(got), len(gt))
                for k, (s, c) in gt.items():
                    assert abs(got[k] - s / c) < 1e-6 * max(1.0, abs(s / c)), (
                        k, got[k], s / c,
                    )
                entry["verified"] = "matches independent numpy ground truth"
            except Exception as e:  # noqa: BLE001 — keep the evidence, flag loudly
                entry["verify_error"] = repr(e)
                _emit({"event": "verify_failed", "query": name, "error": repr(e)})

    # ---- adaptive agg-strategy probe ---------------------------------------
    # High-cardinality group-by, hash vs sort, same data: the record's
    # evidence that the hash device path wins where the dense group space
    # goes sparse (and that forcing sort still completes correctly).
    if not budget_hit and _remaining() > 240 and os.environ.get(
        "GRAFT_BENCH_AGG_PROBE", "1"
    ) != "0":
        try:
            detail["agg_strategy_probe"] = _agg_strategy_probe(db)
            _emit({"event": "agg_strategy_probe",
                   **detail["agg_strategy_probe"],
                   "elapsed_s": round(_elapsed(), 1)})
        except Exception as e:  # noqa: BLE001 — probe must never kill the bench
            detail["agg_strategy_probe"] = {"error": repr(e)}
        _write_partial({"detail": detail, "queries": results})

    # ---- TQL phase ---------------------------------------------------------
    # PromQL rate / increase / sum-by over a single-field twin of the
    # persisted cpu data: warm tile path vs legacy upload-per-query vs
    # the host-numpy reference.  REMAINING-budget gated with the skip
    # reason recorded — it can never jeopardize the main record.
    if os.environ.get("GRAFT_BENCH_TQL", "1") != "0":
        if budget_hit or _remaining() < 300:
            detail["tql"] = {
                "skipped": "remaining budget below tql-phase floor",
                "remaining_s": round(_remaining(), 1),
            }
        else:
            try:
                tql_full = _tql_phase(db)
                detail["tql_full"] = tql_full
                # compact digest for the <1.9 KB record: per query
                # [warm, legacy, speedup] plus the twin reference
                digest: dict = {}
                for k in ("rate", "sumby", "inc1"):
                    r = tql_full.get(k)
                    if isinstance(r, dict) and "warm_ms" in r:
                        digest[k] = [
                            r.get("warm_ms"), r.get("legacy_ms"),
                            r.get("vs_legacy"),
                        ]
                    elif isinstance(r, dict) and "error" in r:
                        digest[k] = {"error": str(r["error"])[:40]}
                if "twin_ms" in tql_full:
                    digest["twin_ms"] = tql_full["twin_ms"]
                if "skipped" in tql_full:
                    digest["skipped"] = tql_full["skipped"]
                detail["tql"] = digest
                _emit({"event": "tql_phase", **tql_full,
                       "elapsed_s": round(_elapsed(), 1)})
            except Exception as e:  # noqa: BLE001 — phase must never kill
                detail["tql"] = {"error": repr(e)[:80]}
        _write_partial({"detail": detail, "queries": results})

    # ---- larger-than-HBM probe ---------------------------------------------
    # Double-gated: the start-time cutoff (rounds 2-5 began the probe
    # with the budget nearly spent) AND an absolute remaining-budget
    # floor — ingest alone needs minutes, and a probe that cannot finish
    # only costs the record its tail.
    lth_min_remaining = float(os.environ.get("GRAFT_BENCH_LTH_MIN_REMAINING_S", 600))
    if (
        not budget_hit
        and LTH_ROWS > 0
        and _elapsed() < LTH_START_MAX_S
        and _remaining() > lth_min_remaining
    ):
        try:
            detail["larger_than_hbm"] = _larger_than_hbm_probe()
        except Exception as e:  # noqa: BLE001 — probe must never kill the bench
            detail["larger_than_hbm"] = {"error": repr(e)}
        _emit({"event": "larger_than_hbm",
               **detail["larger_than_hbm"],
               "elapsed_s": round(_elapsed(), 1)})
        _write_partial({"detail": detail, "queries": results})
    elif LTH_ROWS > 0:
        detail["larger_than_hbm"] = {
            "skipped": (
                "TSBS wall budget exhausted" if budget_hit
                else f"elapsed {round(_elapsed())}s past start cutoff "
                     f"{round(LTH_START_MAX_S)}s"
                if _elapsed() >= LTH_START_MAX_S
                else f"only {round(_remaining())}s of budget left "
                     f"(need {round(lth_min_remaining)})"
            )
        }

    # ---- summary -----------------------------------------------------------
    detail["hbm_tile_cache"] = (
        db.query_engine.tile_cache.stats() if db.query_engine.tile_cache else {}
    )
    detail["budget_exhausted"] = budget_hit
    detail["tpu_compile_cache"] = {
        "hits": m.TPU_COMPILE_CACHE_HITS.get(),
        "misses": m.TPU_COMPILE_CACHE_MISSES.get(),
    }
    detail["device_finalized_queries"] = m.TPU_DEVICE_FINALIZE.get()
    detail["readback_bytes_total"] = m.TPU_READBACK_BYTES.get()
    detail["readback_streamed_total"] = m.TPU_READBACK_STREAMED.get()
    detail["tile_delta"] = {
        "merges": m.TILE_DELTA_MERGES.get(),
        "rows": m.TILE_DELTA_ROWS.get(),
        "pipelined_builds": m.TILE_PIPELINED_BUILDS.get(),
        "precompiles": m.TPU_PRECOMPILES.get(),
    }
    detail["method"] = (
        "end-to-end Database.sql() wall time over real flushed Parquet SSTs: "
        "parse+plan+lowering+ONE dispatch+ONE device fetch+finalize. Warm = "
        f"HBM super-tile hit (p50 of {WARM_REPS}); cold includes Parquet "
        "decode + encode + upload + XLA compile. device_fetch_ms is the "
        "measured round trip of a fresh-buffer device fetch on this machine "
        "— the floor for ANY device query. ingest_http_rows_per_sec is influx line protocol "
        "over a real HTTP socket."
    )
    _STATE["headline"] = headline
    _emit_final()
    db.close()
    failed = _failed_phases(detail, results)
    if failed:
        # the record above is the evidence; a failed phase is not a pass
        print(f"bench: failed phases: {failed}", file=sys.stderr, flush=True)
        sys.exit(1)


# ---- multichip mode (--devices N) -------------------------------------------
# The REAL-path multichip record (MULTICHIP_r06+): the same TSBS dataset
# the tsbs mode persists (reused via GRAFT_BENCH_DATA_DIR — ingest and
# tile consolidations are paid once), driven through the PRODUCTION tile
# executor with `tile.mesh_devices` swept over a per-device-count curve.
# This replaces the dryrun records: every number is a Database.sql() wall
# time through shard_map dispatch + collective merge, and the emitted
# record carries warm p50 per (query, device count) plus the 1->N
# scaling factor for the heavy queries.  Budget-gated per device count
# like the LTH probe: whatever finished is a parseable record.

MULTICHIP_QUERIES = [
    # the heavy queries the scaling claim is about, plus the widened
    # sg-5-* multi-column x multi-host shape and cpu-max-all-8 (now on
    # the tile path)
    ("double-groupby-1", _q(W12, 1, funcs="avg")),
    ("double-groupby-5", _q(W12, 5, funcs="avg")),
    ("double-groupby-all", _q(W12, 10, funcs="avg")),
    ("single-groupby-5-8-1", _q(W1, 5, hosts=HOSTS8, bucket="1m")),
    ("cpu-max-all-8", _q(W8, 10, hosts=HOSTS8)),
]


def multichip_main(max_devices: int):
    """Per-device-count scaling curve through the real mesh tile path."""
    ensure_x64()
    _start_budget_watchdog()
    import shutil
    import tempfile

    import jax

    from greptimedb_tpu.database import Database
    from greptimedb_tpu.utils import metrics as m

    detail: dict = _STATE["detail"]
    results: dict = _STATE["results"]
    avail = len(jax.devices())
    max_devices = min(max_devices, avail)
    counts = [1]
    while counts[-1] * 2 <= max_devices:
        counts.append(counts[-1] * 2)
    detail.update({
        "mode": "multichip",
        "device": str(jax.devices()[0]),
        "devices_available": avail,
        "device_counts": counts,
        "dataset_hours": HOURS,
    })

    reuse = False
    if DATA_DIR:
        home = os.path.join(DATA_DIR, f"tsbs_{_dataset_key()}")
        marker = os.path.join(home, "INGESTED.json")
        if os.path.exists(marker):
            try:
                with open(marker) as f:
                    reuse = json.load(f).get("key") == _dataset_key()
            except Exception:  # noqa: BLE001 — torn marker = no reuse
                reuse = False
        if not reuse and os.path.isdir(home) and os.listdir(home):
            shutil.rmtree(home, ignore_errors=True)
        os.makedirs(home, exist_ok=True)
    else:
        home = tempfile.mkdtemp(prefix="graft_multichip_")
    detail["dataset_reused"] = reuse
    db = Database(data_home=home)
    db.config.query.tpu_min_rows = int(
        os.environ.get("GRAFT_TPU_MIN_ROWS", 300_000)
    )
    tile_mb = int(os.environ.get("GRAFT_TILE_CACHE_MB", 9216))
    db.config.query.tile_cache_mb = tile_mb
    if db.query_engine.tile_cache is not None:
        db.query_engine.tile_cache.budget = tile_mb << 20

    if not reuse:
        # same generator stream as the tsbs mode so the persisted
        # artifacts are interchangeable between the two records
        cols_sql = ", ".join(f"{mm} DOUBLE" for mm in METRICS)
        db.sql(
            f"CREATE TABLE cpu (hostname STRING, ts TIMESTAMP(3) TIME INDEX, "
            f"{cols_sql}, PRIMARY KEY (hostname)) WITH (append_mode = 'true')"
        )
        rng = np.random.default_rng(7)
        ticks_total = HOURS * 3600 // SCRAPE_S
        chunk_ticks = max(1, 2_000_000 // N_HOSTS)
        hosts_arr = np.array([f"host_{i}" for i in range(N_HOSTS)])
        n_rows = 0
        for start in range(0, ticks_total, chunk_ticks):
            ticks = min(chunk_ticks, ticks_total - start)
            ts = T0 + (start + np.arange(ticks, dtype=np.int64))[:, None] * (
                SCRAPE_S * 1000
            )
            ts = np.broadcast_to(ts, (ticks, N_HOSTS)).reshape(-1)
            hs = np.broadcast_to(
                hosts_arr[None, :], (ticks, N_HOSTS)
            ).reshape(-1)
            vals = {
                mm: rng.uniform(0.0, 100.0, ticks * N_HOSTS) for mm in METRICS
            }
            db.insert_rows("cpu", pa.table({
                "hostname": pa.array(hs),
                "ts": pa.array(ts, pa.timestamp("ms")),
                **{mm: pa.array(vals[mm], pa.float64()) for mm in METRICS},
            }))
            n_rows += ticks * N_HOSTS
            if _remaining() < 120:
                break  # record whatever ingested; rc=0 beats completeness
        db.storage.flush_all()
        detail["rows"] = n_rows
        if DATA_DIR:
            try:
                with open(marker, "w") as f:
                    json.dump({"key": _dataset_key(), "rows": n_rows}, f)
            except OSError:
                pass
        _emit({"event": "ingested", "rows": n_rows,
               "elapsed_s": round(_elapsed(), 1)})

    only = os.environ.get("GRAFT_BENCH_ONLY")
    queries = [
        q for q in MULTICHIP_QUERIES if only is None or q[0] in only.split(",")
    ]
    curve: dict[str, dict] = {name: {} for name, _sql in queries}
    min_remaining = float(
        os.environ.get("GRAFT_MULTICHIP_MIN_REMAINING_S", 120)
    )
    for n_dev in counts:
        if _remaining() < min_remaining:
            detail.setdefault("skipped_device_counts", []).append(n_dev)
            detail.setdefault("skip_reasons", []).append({
                "phase": f"devices={n_dev}",
                "reason": f"remaining {round(_remaining())}s < "
                          f"{min_remaining}s gate",
            })
            _emit({"event": "budget_gate", "skipped_devices": n_dev,
                   "remaining_s": round(_remaining(), 1)})
            continue
        db.config.tile.mesh_devices = n_dev
        for name, sql in queries:
            if _remaining() < min_remaining / 2:
                detail.setdefault("skip_reasons", []).append({
                    "phase": f"devices={n_dev} query={name}",
                    "reason": f"remaining {round(_remaining())}s < "
                              f"{min_remaining / 2}s gate",
                })
                _emit({"event": "budget_gate", "skipped_query": name,
                       "devices": n_dev,
                       "remaining_s": round(_remaining(), 1)})
                break
            walls: list[float] = []
            err = None
            reps_skipped = None
            mesh0 = m.TILE_MESH_DISPATCHES.get()
            rec_cursor = _recorder().RECORDER.cursor()
            try:
                db.config.query.timeout_s = min(
                    600.0, max(_remaining(), 30.0)
                )
                db.sql_one(sql)  # cold/build rep (uncounted)
                rec_cursor = _recorder().RECORDER.cursor()  # warm reps only
                for _rep in range(WARM_REPS):
                    if _remaining() <= 10:
                        reps_skipped = (
                            f"remaining budget: {len(walls)}/"
                            f"{WARM_REPS} done"
                        )
                        break
                    db.config.query.timeout_s = min(
                        120.0, max(_remaining(), 15.0)
                    )
                    t0 = time.perf_counter()
                    db.sql_one(sql)
                    walls.append((time.perf_counter() - t0) * 1000)
            except Exception as e:  # noqa: BLE001 — record what landed
                err = repr(e)
            finally:
                db.config.query.timeout_s = 0.0
            entry: dict = {"devices": n_dev}
            if walls:
                entry["warm_ms"] = round(float(np.median(walls)), 2)
                entry["warm_reps_done"] = len(walls)
            entry["mesh_dispatches"] = int(
                m.TILE_MESH_DISPATCHES.get() - mesh0
            )
            try:
                # per-device-count dispatch timing from the recorder: the
                # warm reps' device-stage split, so the sweep attributes
                # scaling wins/losses to dispatch vs readback (not wall
                # time alone)
                q_recs = [
                    r for r in _recorder_delta(rec_cursor, "public.cpu")
                    if r.stage_ms("dispatch") > 0
                ]
                if q_recs:
                    entry["dispatch_ms_p50"] = round(float(np.median(
                        [r.stage_ms("dispatch") for r in q_recs]
                    )), 2)
                    entry["readback_ms_p50"] = round(float(np.median(
                        [r.stage_ms("readback_transfer") for r in q_recs]
                    )), 2)
                    entry["recorder_mesh_devices"] = q_recs[-1].mesh_devices
            except Exception as rec_e:  # noqa: BLE001 — best-effort
                entry["recorder_error"] = repr(rec_e)
            if err is not None:
                entry["error"] = err
            if reps_skipped is not None:
                entry["warm_reps_skipped"] = reps_skipped
            curve[name][str(n_dev)] = entry
            _emit({"query": name, **entry,
                   "elapsed_s": round(_elapsed(), 1)})
            _write_partial({"detail": detail, "queries": results})
    db.config.tile.mesh_devices = 0

    # scaling factors 1 -> max measured, per query + heavy geomean
    factors = []
    for name, per_dev in curve.items():
        ms1 = per_dev.get("1", {}).get("warm_ms")
        top = str(counts[-1])
        msn = per_dev.get(top, {}).get("warm_ms")
        rec = {"curve": per_dev}
        if ms1 and msn:
            rec["scaling_1_to_max"] = round(ms1 / msn, 2)
            factors.append(ms1 / msn)
        results[name] = rec
    detail["mesh_degraded_total"] = m.TILE_MESH_DEGRADED.get()
    detail["method"] = (
        "end-to-end Database.sql() wall time through the PRODUCTION tile "
        "path with tile.mesh_devices swept per device count: shard_map "
        "partial aggregation over the regions mesh + psum/pmin/pmax "
        "merge, device-finalize post-merge.  Dataset/tile artifacts "
        "reused from the persisted tsbs-mode home.  warm_ms = p50 of "
        f"{WARM_REPS} cache-hit reps; scaling_1_to_max = warm_ms(1 dev) "
        "/ warm_ms(max devs)."
    )
    headline_val = (
        round(float(np.exp(np.mean(np.log(factors)))), 2) if factors else None
    )
    _STATE["headline"] = {
        "warm_ms": headline_val, "vs_baseline": headline_val,
    }
    with _EMIT_LOCK:
        if not _STATE["emitted"]:
            _STATE["emitted"] = True
            # compact emitted line (driver tail capture is ~2000 bytes):
            # per-device warm medians only; the full curve + method stay
            # in BENCH_PARTIAL.json
            slim_q = {
                name: {
                    "scaling_1_to_max": rec.get("scaling_1_to_max"),
                    **{
                        dev: e.get("warm_ms")
                        for dev, e in rec.get("curve", {}).items()
                    },
                }
                for name, rec in results.items()
                if isinstance(rec, dict)
            }
            slim_detail = {
                k: detail[k]
                for k in ("device", "rows", "mesh_degraded_total",
                          "skipped_device_counts")
                if k in detail
            }
            _emit({
                "metric": "multichip_heavy_scaling_geomean",
                "value": headline_val,
                "unit": "x (1 device -> max devices warm speedup)",
                "vs_baseline": headline_val,
                "detail": slim_detail,
                "queries": slim_q,
            }, compact=True)
            _write_partial({"detail": detail, "queries": results})
            try:
                with open(PARTIAL_PATH + ".done", "w") as f:
                    f.write("1")
            except OSError:
                pass
    db.close()


# ---- mixed ingest+query overload mode (--mode mixed) -----------------------
# The production-concurrency harness (ROADMAP open item 3): N query workers
# race M ingest workers against ONE device under admission control, dispatch
# coalescing, and a tile budget FORCED below the working-set size (HBM
# overcommit).  The contract under test is graceful degradation: ZERO failed
# queries, bounded p99, coalesced dispatches observable, sheds surfacing as
# RETRY_LATER (which the workers count separately — a shed is the admission
# layer WORKING, not a failure).

MIXED_HOSTS = int(os.environ.get("GRAFT_MIXED_HOSTS", 64))
MIXED_TICKS = int(os.environ.get("GRAFT_MIXED_TICKS", 1500))  # seed rows/host
MIXED_SECONDS = float(os.environ.get("GRAFT_MIXED_SECONDS", 30))
MIXED_QUERY_WORKERS = int(os.environ.get("GRAFT_MIXED_QUERY_WORKERS", 8))
MIXED_INGEST_WORKERS = int(os.environ.get("GRAFT_MIXED_INGEST_WORKERS", 2))
MIXED_OVERCOMMIT_MB = int(os.environ.get("GRAFT_MIXED_OVERCOMMIT_MB", 1))


MIXED_HOTSPOT_STEPS = int(os.environ.get("GRAFT_MIXED_HOTSPOT_STEPS", 160))

# ---- dashboard-fleet QPS sweep (cross-query batching + result cache) -------
# Offered-load levels (queries/s), swept twice: batching+cache OFF, then
# ON.  The headline is the knee — the highest offered load the engine
# sustains (achieved within 85% of offered) at bounded p99.
MIXED_SWEEP_QPS = tuple(
    float(x)
    for x in os.environ.get(
        "GRAFT_MIXED_SWEEP_QPS", "25,50,100,200,400,800,1600"
    ).split(",")
    if x.strip()
)
MIXED_SWEEP_SECONDS = float(os.environ.get("GRAFT_MIXED_SWEEP_SECONDS", 2.5))
MIXED_SWEEP_WORKERS = int(os.environ.get("GRAFT_MIXED_SWEEP_WORKERS", 8))
MIXED_BATCH_WINDOW_MS = float(os.environ.get("GRAFT_MIXED_BATCH_WINDOW_MS", 2.0))
MIXED_RESULT_CACHE_MB = int(os.environ.get("GRAFT_MIXED_RESULT_CACHE_MB", 64))


def _mixed_fleet(lo12: int, end_ms: int) -> list:
    """The dashboard fleet: DISTINCT panel queries (different aggregates,
    group shapes, literals) over the same fixed window — the shape PR 6
    coalescing canNOT merge (plans differ) and the batcher exists for."""
    fleet = [
        ("panel-groupby", (
            f"SELECT hostname, time_bucket('1h', ts) AS tb, "
            f"avg(usage_user) AS au FROM cpu WHERE ts >= {lo12} AND "
            f"ts < {end_ms} GROUP BY hostname, tb"
        )),
        ("panel-max", (
            f"SELECT time_bucket('1h', ts) AS tb, max(usage_user) AS mu, "
            f"min(usage_user) AS nu FROM cpu WHERE ts >= {lo12} AND "
            f"ts < {end_ms} GROUP BY tb"
        )),
        ("panel-count", (
            f"SELECT count(*) AS n, max(usage_system) AS mx FROM cpu "
            f"WHERE ts >= {lo12} AND ts < {end_ms}"
        )),
    ]
    for i in range(3):
        fleet.append((f"panel-host{i}", (
            f"SELECT time_bucket('1h', ts) AS tb, avg(usage_user) AS au, "
            f"max(usage_system) AS ms FROM cpu WHERE hostname = 'host_{i}' "
            f"AND ts >= {lo12} AND ts < {end_ms} GROUP BY tb"
        )))
    return fleet


def _sweep_level(db, fleet, offered_qps: float, seconds: float, workers: int) -> dict:
    """Open-loop arrival pacing: arrival i is SCHEDULED at t0 + i/qps
    regardless of completions — the generator never slows down when the
    server does, so achieved < offered IS the overload signal (a closed
    loop would flatter the knee by self-throttling)."""
    import threading

    from greptimedb_tpu.utils.errors import RetryLaterError

    walls: list[float] = []
    c = {"cursor": 0, "ok": 0, "shed": 0, "failed": 0}
    lock = threading.Lock()
    t0 = time.perf_counter()
    deadline = t0 + seconds
    total = max(int(offered_qps * seconds), 1)

    def worker():
        while True:
            now = time.perf_counter()
            if now > deadline:
                return
            with lock:
                i = c["cursor"]
                if i >= total:
                    return
                c["cursor"] = i + 1
            at = t0 + i / offered_qps
            delay = at - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            _name, sql = fleet[i % len(fleet)]
            tq = time.perf_counter()
            try:
                db.sql_one(sql)
            except RetryLaterError:
                with lock:
                    c["shed"] += 1
                continue
            except Exception:  # noqa: BLE001 — the zero-failed contract
                with lock:
                    c["failed"] += 1
                continue
            wall = (time.perf_counter() - tq) * 1000
            with lock:
                c["ok"] += 1
                walls.append(wall)

    threads = [
        threading.Thread(target=worker, daemon=True) for _ in range(workers)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + 60)
    elapsed = max(time.perf_counter() - t0, 1e-6)
    arr = np.array(walls) if walls else None
    return {
        "offered_qps": offered_qps,
        "achieved_qps": round(c["ok"] / elapsed, 1),
        "p50_ms": round(float(np.percentile(arr, 50)), 2) if arr is not None else None,
        "p99_ms": round(float(np.percentile(arr, 99)), 2) if arr is not None else None,
        "ok": c["ok"],
        "shed": c["shed"],
        "failed": c["failed"],
    }


def _sweep_knee(levels: list) -> dict:
    """The knee: the highest-throughput level still keeping up with its
    offered rate (achieved >= 85% of offered); past it the curve bends —
    falling back to the best-achieved level when every level is bent."""
    kept = [
        lv for lv in levels
        if lv["achieved_qps"] >= 0.85 * lv["offered_qps"]
    ]
    pool = kept or levels
    return max(pool, key=lambda lv: lv["achieved_qps"])


def _qps_sweep_phase(db, lo12: int, end_ms: int) -> dict:
    """Sweep the offered-load ladder twice — batching+cache OFF then ON —
    on the now-static snapshot (ingest stopped, so the dashboard fleet's
    repeated aligned windows are cacheable, exactly the between-ticks
    regime the result cache exists for).  OFF runs first so plane builds
    and XLA compiles are paid OUTSIDE the ON timings."""
    from greptimedb_tpu.utils import metrics as _m
    from greptimedb_tpu.utils import rtt_sim as _rtt

    fleet = _mixed_fleet(lo12, end_ms)
    bcfg = db.config.batch
    db.config.query.timeout_s = 30.0
    sweep: dict = {"batch_window_ms": MIXED_BATCH_WINDOW_MS,
                   "fleet": len(fleet), "workers": MIXED_SWEEP_WORKERS,
                   "rtt_ms": round(_rtt.rtt_ms(), 1)}
    for mode in ("off", "on"):
        if mode == "on":
            bcfg.window_ms = MIXED_BATCH_WINDOW_MS
            bcfg.result_cache_mb = MIXED_RESULT_CACHE_MB
            bcfg.fuse_programs = True
            fused0 = _m.QUERY_BATCH_FUSED_DISPATCHES_TOTAL.get()
        else:
            bcfg.window_ms = 0.0
            bcfg.result_cache_mb = 0
            for _name, sql in fleet:  # warm: build + compile off the clock
                db.sql_one(sql)
        levels = [
            _sweep_level(db, fleet, qps, MIXED_SWEEP_SECONDS, MIXED_SWEEP_WORKERS)
            for qps in MIXED_SWEEP_QPS
        ]
        knee = _sweep_knee(levels)
        sweep[mode] = {
            "curve": [
                [lv["offered_qps"], lv["achieved_qps"], lv["p50_ms"],
                 lv["p99_ms"], lv["shed"]]
                for lv in levels
            ],
            "knee_offered_qps": knee["offered_qps"],
            "knee_qps": knee["achieved_qps"],
            "p99_at_knee_ms": knee["p99_ms"],
            "sustained_qps": max(lv["achieved_qps"] for lv in levels),
            "failed": sum(lv["failed"] for lv in levels),
        }
        if mode == "on":
            # mega-fusion evidence for the ON sweep: ticks that executed
            # as ONE XLA invocation (scalar — survives every clamp trim)
            sweep["on"]["fused_dispatches"] = int(
                _m.QUERY_BATCH_FUSED_DISPATCHES_TOTAL.get() - fused0
            )
        _emit({"event": "mixed_qps_sweep", "mode": mode,
               "knee_qps": sweep[mode]["knee_qps"],
               "sustained_qps": sweep[mode]["sustained_qps"],
               "elapsed_s": round(_elapsed(), 1)})
    off_s = max(sweep["off"]["sustained_qps"], 1e-9)
    sweep["speedup"] = round(sweep["on"]["sustained_qps"] / off_s, 1)
    return sweep


def _batch_burst_phase(db, fleet_n: int = 4) -> dict:
    """Deterministic mega-dispatch evidence: K DISTINCT warm panel
    queries released at a barrier inside one WIDE batch window — the
    record's batched_members counter cannot depend on probabilistic
    steady-state overlap.  The result cache is held OFF for the burst
    (a cache hit never dispatches, so it would starve the batcher)."""
    import threading

    from greptimedb_tpu.utils import metrics as m

    bcfg = db.config.batch
    win0, mb0 = bcfg.window_ms, bcfg.result_cache_mb
    bcfg.window_ms, bcfg.result_cache_mb = 60.0, 0
    lo = T0
    hi = T0 + 3600_000
    fleet = _mixed_fleet(lo, hi)[:fleet_n]
    d0 = m.QUERY_BATCH_DISPATCHES_TOTAL.get()
    m0 = m.QUERY_BATCH_MEMBERS_TOTAL.get()
    f0 = m.QUERY_BATCH_FUSED_DISPATCHES_TOTAL.get()
    failed = 0
    rounds = 0
    try:
        for _name, sql in fleet:  # warm every family (build + mark)
            db.sql_one(sql)
            db.sql_one(sql)
        for rounds in range(1, 6):
            barrier = threading.Barrier(len(fleet))

            def one(sql):
                nonlocal failed
                try:
                    barrier.wait(timeout=30)
                    db.sql_one(sql)
                except Exception:  # noqa: BLE001 — counted in the record
                    failed += 1

            threads = [
                threading.Thread(target=one, args=(sql,), daemon=True)
                for _name, sql in fleet
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            if m.QUERY_BATCH_DISPATCHES_TOTAL.get() > d0:
                break
    finally:
        bcfg.window_ms, bcfg.result_cache_mb = win0, mb0
    return {
        "dispatches": m.QUERY_BATCH_DISPATCHES_TOTAL.get() - d0,
        "members": m.QUERY_BATCH_MEMBERS_TOTAL.get() - m0,
        "fused_dispatches": int(
            m.QUERY_BATCH_FUSED_DISPATCHES_TOTAL.get() - f0
        ),
        "rounds": rounds,
        "failed": failed,
    }


def _hotspot_phase() -> dict:
    """Elastic hot-spot scenario: skewed ingest (every row on one tag key)
    drives a single region hot on a 3-node cluster with the balancer ON;
    the balancer must auto-split the table while writes and reads keep
    running.  Zero-failed-query contract: reads never raise and always see
    every acked row; writes may surface RetryLaterError only as the
    documented retryable fence race (the retry must then land).  Latencies
    are split into pre_split/post_split phases so the reconfiguration cost
    is visible in the record."""
    import tempfile

    from greptimedb_tpu.datatypes import (
        ColumnSchema,
        ConcreteDataType,
        Schema,
        SemanticType,
    )
    from greptimedb_tpu.distributed.cluster import Cluster
    from greptimedb_tpu.utils.config import Config
    from greptimedb_tpu.utils.errors import RetryLaterError

    cfg = Config()
    cfg.balance.enabled = True
    cfg.balance.ewma_alpha = 0.6
    cfg.balance.min_dwell_ticks = 2
    cfg.balance.cooldown_ticks = 2
    cfg.balance.split_hot_score = 12.0
    cfg.balance.merge_cold_score = 2.0
    cfg.validate()
    now = [1_000_000.0]
    schema = Schema(columns=[
        ColumnSchema("host", ConcreteDataType.STRING, SemanticType.TAG),
        ColumnSchema(
            "ts", ConcreteDataType.TIMESTAMP_MILLISECOND, SemanticType.TIMESTAMP
        ),
        ColumnSchema("v", ConcreteDataType.FLOAT64),
    ])
    c = Cluster(
        tempfile.mkdtemp(prefix="graft_hotspot_"), num_datanodes=3,
        clock=lambda: now[0], config=cfg,
    )
    acked = 0
    key = 0
    failed_queries = 0
    retried_writes = 0
    write_exhausted = 0
    lat: dict[str, list] = {"pre_split": [], "post_split": []}
    first_split_step = None
    try:
        c.create_table("hot", schema)
        for _ in range(4):
            now[0] += 1000
            c.heartbeat_all()
        split_seen = False
        for step in range(MIXED_HOTSPOT_STEPS):
            now[0] += 250
            n = 4 + (step % 7)
            batch = pa.RecordBatch.from_arrays(
                [
                    pa.array(["h0"] * n, pa.string()),  # pure hot spot
                    pa.array(
                        [(key + i) * 1000 for i in range(n)],
                        pa.timestamp("ms"),
                    ),
                    pa.array([float(key + i) for i in range(n)]),
                ],
                schema=schema.to_arrow(),
            )
            key += n
            for _attempt in range(4):
                try:
                    c.insert("hot", batch)
                    acked += n
                    break
                except RetryLaterError:
                    # the ONE permitted surface: a write racing the split
                    # fence; the retry after the swap must land
                    retried_writes += 1
                    now[0] += 500
                    c.heartbeat_all()
                    c.supervise()
            else:
                write_exhausted += 1
            t0 = time.perf_counter()
            try:
                t = c.query("SELECT count(*) AS n FROM hot")
                if t["n"].to_pylist() != [acked]:
                    failed_queries += 1
            except Exception:  # noqa: BLE001 — the zero-failed contract
                failed_queries += 1
            wall = (time.perf_counter() - t0) * 1000
            if step % 3 == 0:
                c.heartbeat_all()
                c.supervise()
            if not split_seen:
                split_seen = any(
                    d["ok"] and d["kind"] == "split"
                    for d in c.balancer.decisions
                )
                if split_seen:
                    first_split_step = step
            lat["post_split" if split_seen else "pre_split"].append(wall)
        splits = [
            d for d in c.balancer.decisions if d["ok"] and d["kind"] == "split"
        ]
        regions = len(c.catalog.table("hot", "public").region_ids)
        phases = {}
        for ph, walls in lat.items():
            if not walls:
                phases[ph] = {"n": 0}
                continue
            arr = np.array(walls)
            p50 = float(np.percentile(arr, 50))
            p99 = float(np.percentile(arr, 99))
            # clamp-order aware: rounding may never invert p50 <= p99
            phases[ph] = {
                "n": len(walls),
                "p50_ms": round(min(p50, p99), 2),
                "p99_ms": round(max(p50, p99), 2),
            }
        return {
            "steps": MIXED_HOTSPOT_STEPS,
            "acked_rows": acked,
            "retried_writes": retried_writes,
            "write_retries_exhausted": write_exhausted,
            "splits_enacted": len(splits),
            "first_split_step": first_split_step,
            "regions": regions,
            "auto_split": bool(splits) and regions >= 2,
            "failed_queries": failed_queries,
            "zero_failed_queries": failed_queries == 0 and write_exhausted == 0,
            "phases": phases,
        }
    finally:
        c.close()


def _device_wedge_phase(db, sql: str) -> dict:
    """Chaos leg over the now-static snapshot: wedge ONE warm dispatch
    (fault point `device.wedge` blocking the supervised worker) and prove
    the device-health contract end to end — the wedged query still
    answers via the degrade ladder, the devices quarantine, the heal
    prober re-admits them, and a post-heal query matches.  The record's
    `device_health` digest carries the verdict scalars."""
    import threading

    from greptimedb_tpu.utils import device_health as dh
    from greptimedb_tpu.utils import fault_injection as fi

    sup = dh.SUPERVISOR
    out: dict = {"supervised": sup.enabled, "wedged": False,
                 "healed": False, "zero_failed_queries": False}
    if not sup.enabled or db.query_engine.tile_cache is None:
        return out
    # the hotspot phase booted its own cluster Databases, each of which
    # re-pointed the process-wide supervisor at ITS config — wire it back
    # to this db, with a chaos-speed deadline (restored below)
    cache = db.query_engine.tile_cache
    saved_timeout = db.config.device.call_timeout_s
    db.config.device.call_timeout_s = 2.0
    sup.configure(db.config.device, cache.devices)
    db.config.query.timeout_s = 30.0
    try:
        want = db.sql_one(sql).num_rows  # warm + reference
        release = threading.Event()
        t0 = time.perf_counter()
        try:
            with fi.REGISTRY.armed(
                "device.wedge", fail_times=1,
                match=lambda ctx: ctx.get("kind") == "dispatch",
                callback=lambda ctx: release.wait(timeout=60),
            ) as plan:
                got = db.sql_one(sql)  # must still answer, degraded
        finally:
            release.set()
        out["wedge_wall_ms"] = round((time.perf_counter() - t0) * 1000, 1)
        out["wedged"] = plan.trips >= 1
        answered = got is not None and got.num_rows == want
        out["quarantines"] = int(sup.digest().get("quarantines", 0))
        n = len(cache.devices)
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if sup.healthy_indices(n) == tuple(range(n)):
                break
            time.sleep(0.05)
        out["healed"] = sup.healthy_indices(n) == tuple(range(n))
        post_heal = db.sql_one(sql).num_rows == want
        out["post_heal_ok"] = post_heal
        out["zero_failed_queries"] = answered and post_heal
        out.update(sup.digest())
        return out
    finally:
        db.config.device.call_timeout_s = saved_timeout


def mixed_main():
    """Concurrent ingest+query under forced HBM overcommit; emits one JSON
    line with p50/p99 per query family and the overload-survival counters."""
    ensure_x64()
    _start_budget_watchdog()
    import tempfile
    import threading

    import jax

    from greptimedb_tpu.database import Database
    from greptimedb_tpu.utils import metrics as m
    from greptimedb_tpu.utils import rtt_sim
    from greptimedb_tpu.utils.config import Config
    from greptimedb_tpu.utils.errors import RetryLaterError

    # synthetic link delay (--rtt-ms / GRAFT_BENCH_RTT_MS): every device
    # dispatch/fetch boundary pays a symmetric half-delay sleep, so the
    # QPS knee of a costly crossing — and the one-invocation-per-tick
    # fusion win — can be counted offline.  0 (the default) is a strict
    # no-op; the sleeps are never a device time.
    rtt_ms = float(os.environ.get("GRAFT_BENCH_RTT_MS", "0") or 0)
    rtt_sim.configure(rtt_ms)

    detail: dict = _STATE["detail"]
    detail.update({
        "mode": "mixed", "device": str(jax.devices()[0]),
        "hosts": MIXED_HOSTS, "seed_ticks": MIXED_TICKS,
        "seconds": MIXED_SECONDS,
        "query_workers": MIXED_QUERY_WORKERS,
        "ingest_workers": MIXED_INGEST_WORKERS,
        "tile_budget_mb": MIXED_OVERCOMMIT_MB,
        "rtt_ms": round(rtt_ms, 1),
    })
    cfg = Config()
    # the admission/overload stack under test, all knobs ON
    cfg.admission.enable = True
    cfg.admission.max_concurrent = max(MIXED_QUERY_WORKERS // 2, 2)
    cfg.admission.max_queue_wait_ms = 30_000.0
    cfg.admission.coalesce = True
    cfg.admission.hbm_probe = True
    cfg.admission.hbm_retry = True
    cfg.admission.min_chunk_rows = 4096
    cfg.query.tpu_min_rows = 1  # everything takes the device path
    home = tempfile.mkdtemp(prefix="graft_mixed_")
    db = Database(data_home=home, config=cfg)
    # FORCED overcommit: the budget sits far below the working set, so the
    # eviction/stream/halve-chunk machinery carries the whole run
    if db.query_engine.tile_cache is not None:
        db.query_engine.tile_cache.budget = MIXED_OVERCOMMIT_MB << 20

    db.sql(
        "CREATE TABLE cpu (hostname STRING, ts TIMESTAMP(3) TIME INDEX, "
        "usage_user DOUBLE, usage_system DOUBLE, PRIMARY KEY (hostname)) "
        "WITH (append_mode = 'true')"
    )
    hosts_arr = np.array([f"host_{i}" for i in range(MIXED_HOSTS)])

    def batch_for(tick_lo: int, ticks: int, seed: int) -> pa.Table:
        rng = np.random.default_rng(seed)
        ts = T0 + (tick_lo + np.arange(ticks, dtype=np.int64))[:, None] * (
            SCRAPE_S * 1000
        )
        ts = np.broadcast_to(ts, (ticks, MIXED_HOSTS)).reshape(-1)
        hs = np.broadcast_to(
            hosts_arr[None, :], (ticks, MIXED_HOSTS)
        ).reshape(-1)
        return pa.table({
            "hostname": pa.array(hs),
            "ts": pa.array(ts, pa.timestamp("ms")),
            "usage_user": pa.array(rng.uniform(0, 100, ticks * MIXED_HOSTS)),
            "usage_system": pa.array(rng.uniform(0, 100, ticks * MIXED_HOSTS)),
        })

    db.insert_rows("cpu", batch_for(0, MIXED_TICKS, seed=11))
    db.storage.flush_all()
    detail["seed_rows"] = MIXED_TICKS * MIXED_HOSTS
    _emit({"event": "mixed_seeded", "rows": detail["seed_rows"],
           "elapsed_s": round(_elapsed(), 1)})

    end_ms = T0 + MIXED_TICKS * SCRAPE_S * 1000
    lo12 = end_ms - 12 * 3600_000
    families = [
        ("double-groupby", (
            f"SELECT hostname, time_bucket('1h', ts) AS tb, "
            f"avg(usage_user) AS au FROM cpu WHERE ts >= {lo12} AND "
            f"ts < {end_ms} GROUP BY hostname, tb"
        )),
        ("cpu-max-host", (
            "SELECT time_bucket('1h', ts) AS tb, max(usage_user) AS mu, "
            "max(usage_system) AS ms FROM cpu WHERE hostname = 'host_3' "
            "GROUP BY tb"
        )),
        ("high-cpu-all", (
            "SELECT count(*) AS n, max(usage_user) AS mx FROM cpu "
            "WHERE usage_user > 90.0"
        )),
    ]
    stop = threading.Event()
    lat: dict[str, list] = {name: [] for name, _ in families}
    counters = {"queries": 0, "failed": 0, "shed": 0, "ingest_batches": 0,
                "ingest_failed": 0}
    errors: list[str] = []
    lock = threading.Lock()

    def run_one(name: str, sql: str) -> str:
        """One timed query with the shared zero-failed-queries accounting:
        shed = admission working (not a failure), anything else failed."""
        t0 = time.perf_counter()
        try:
            db.config.query.timeout_s = 30.0
            db.sql_one(sql)
        except RetryLaterError:
            with lock:
                counters["shed"] += 1
            return "shed"
        except Exception as exc:  # noqa: BLE001 — the zero-failed contract
            with lock:
                counters["failed"] += 1
                if len(errors) < 5:
                    errors.append(f"{name}: {exc!r}")
            return "failed"
        wall = (time.perf_counter() - t0) * 1000
        with lock:
            counters["queries"] += 1
            lat[name].append(wall)
        return "ok"

    def query_worker(wid: int):
        # fixed family per worker (dashboard-style steady load): workers
        # sharing a family overlap constantly, which is what dispatch
        # coalescing exists for
        name, sql = families[wid % len(families)]
        while not stop.is_set():
            if run_one(name, sql) == "shed":
                time.sleep(0.02)

    def ingest_worker(wid: int):
        tick = MIXED_TICKS + wid * 1_000_000
        while not stop.is_set():
            try:
                db.insert_rows("cpu", batch_for(tick, 20, seed=tick))
                with lock:
                    counters["ingest_batches"] += 1
            except RetryLaterError:
                time.sleep(0.05)
            except Exception:  # noqa: BLE001 — counted, not fatal
                with lock:
                    counters["ingest_failed"] += 1
            tick += 20
            if counters["ingest_batches"] % 10 == 5:
                try:
                    db.storage.flush_all()  # keep flush racing the queries
                except Exception:  # noqa: BLE001 — flush pressure only
                    pass
            time.sleep(0.01)

    # Deterministic coalesce phase: with the snapshot still static (ingest
    # has not started), every query worker hits ONE family at a barrier.
    # Concurrent same-family arrivals on one snapshot are guaranteed, so
    # the coalesced-dispatch observability contract cannot flake on a
    # loaded box where steady-state overlap is merely probabilistic.
    burst_name, burst_sql = families[0]
    db.config.query.timeout_s = 30.0
    db.sql_one(burst_sql)  # warm the family: build + compile off the burst
    barrier = threading.Barrier(MIXED_QUERY_WORKERS)

    def burst_worker():
        barrier.wait(timeout=30)
        run_one(burst_name, burst_sql)

    burst = [
        threading.Thread(target=burst_worker, daemon=True)
        for _ in range(MIXED_QUERY_WORKERS)
    ]
    for b in burst:
        b.start()
    for b in burst:
        b.join(timeout=60)

    workers = [
        threading.Thread(target=query_worker, args=(i,), daemon=True)
        for i in range(MIXED_QUERY_WORKERS)
    ] + [
        threading.Thread(target=ingest_worker, args=(i,), daemon=True)
        for i in range(MIXED_INGEST_WORKERS)
    ]
    t_run = time.perf_counter()
    for w in workers:
        w.start()
    while time.perf_counter() - t_run < MIXED_SECONDS:
        time.sleep(1.0)
        with lock:
            snap = dict(counters)
        _write_partial({"detail": {**detail, **snap}, "queries": {}})
    stop.set()
    for w in workers:
        w.join(timeout=60.0)

    # Dashboard-fleet QPS sweep (cross-query batching + result cache):
    # offered-load ladder OFF then ON over the now-static snapshot; the
    # record carries both curves, the knee, and the ON/OFF speedup.
    try:
        qps_sweep = _qps_sweep_phase(db, lo12, end_ms)
    except Exception as exc:  # noqa: BLE001 — surfaced in the record
        qps_sweep = {"error": repr(exc)[:200]}
    detail["qps_sweep"] = qps_sweep
    _write_partial({"detail": detail, "queries": {}})

    # Deterministic mega-dispatch evidence (distinct warm queries at a
    # barrier in one wide window) so batched_members never flakes to 0.
    try:
        burst = _batch_burst_phase(db)
    except Exception as exc:  # noqa: BLE001 — surfaced in the record
        burst = {"error": repr(exc)[:200], "dispatches": 0, "members": 0}
    detail["batch_dispatches"] = burst.get("dispatches", 0)
    detail["batched_members"] = burst.get("members", 0)
    detail["batch_burst"] = burst
    detail["result_cache_hits"] = m.QUERY_BATCH_RESULT_CACHE_HITS_TOTAL.get()
    detail["fused_dispatches"] = int(
        m.QUERY_BATCH_FUSED_DISPATCHES_TOTAL.get()
    )
    detail["fuse_degraded"] = int(m.QUERY_BATCH_FUSE_DEGRADED_TOTAL.get())
    _emit({"event": "mixed_batch_phase",
           "batched_members": detail["batched_members"],
           "result_cache_hits": detail["result_cache_hits"],
           "fused_dispatches": detail["fused_dispatches"],
           "sweep_speedup": qps_sweep.get("speedup"),
           "elapsed_s": round(_elapsed(), 1)})
    db.config.query.timeout_s = 0.0

    # Elastic hot-spot scenario on a distributed cluster (balancer ON):
    # the record asserts the skew auto-split with zero failed queries.
    try:
        hotspot = _hotspot_phase()
    except Exception as exc:  # noqa: BLE001 — surfaced in the record
        hotspot = {"error": repr(exc)[:200], "auto_split": False,
                   "zero_failed_queries": False}
    detail["hotspot"] = hotspot
    _emit({"event": "mixed_hotspot", **{
        k: hotspot.get(k)
        for k in ("auto_split", "zero_failed_queries", "splits_enacted",
                  "regions", "first_split_step")
    }, "elapsed_s": round(_elapsed(), 1)})

    # Device-health chaos leg: wedge one warm dispatch, watch quarantine
    # + heal, zero failed queries throughout (fault point `device.wedge`).
    try:
        wedge = _device_wedge_phase(db, families[1][1])
    except Exception as exc:  # noqa: BLE001 — surfaced in the record
        wedge = {"error": repr(exc)[:200], "wedged": False,
                 "healed": False, "zero_failed_queries": False}
    detail["device_health"] = wedge
    _emit({"event": "mixed_device_wedge", **{
        k: wedge.get(k)
        for k in ("supervised", "wedged", "quarantines", "healed",
                  "zero_failed_queries", "wedge_wall_ms")
    }, "elapsed_s": round(_elapsed(), 1)})

    per_family = {}
    all_walls: list[float] = []
    for name, walls in lat.items():
        if not walls:
            per_family[name] = {"n": 0}
            continue
        arr = np.array(walls)
        all_walls.extend(walls)
        per_family[name] = {
            "n": len(walls),
            "p50_ms": round(float(np.percentile(arr, 50)), 1),
            "p99_ms": round(float(np.percentile(arr, 99)), 1),
        }
    detail.update({
        **counters,
        "families": per_family,
        "errors": errors,
        "coalesced_dispatches": m.DISPATCH_COALESCED_TOTAL.get(),
        "coalition_leaders": m.DISPATCH_COALESCE_LEADERS_TOTAL.get(),
        "admission": {
            "admitted": m.ADMISSION_ADMITTED_TOTAL.get(),
            # every shed carries a reason= label; sum across them
            "shed": m.ADMISSION_SHED_TOTAL.total(),
        },
        "hbm": {
            "probe_free_bytes": m.HBM_PROBE_FREE_BYTES.get(),
            "exhausted": m.HBM_EXHAUSTED_TOTAL.get(),
            "chunk_rows": (
                db.query_engine.tile_cache.chunk_rows
                if db.query_engine.tile_cache else None
            ),
        },
        "zero_failed_queries": counters["failed"] == 0,
    })
    p99 = round(float(np.percentile(np.array(all_walls), 99)), 1) if all_walls else None
    p50 = round(float(np.percentile(np.array(all_walls), 50)), 1) if all_walls else None
    detail["p50_ms"] = p50
    _STATE["headline"] = {"warm_ms": p99, "vs_baseline": None}
    with _EMIT_LOCK:
        if not _STATE["emitted"]:
            _STATE["emitted"] = True
            # the emitted line must fit the driver's tail capture like the
            # tsbs record does; the partial keeps the UNCLAMPED detail
            record = _clamp_record({
                "metric": "mixed_load_e2e_p99",
                "value": p99,
                "unit": "ms",
                "vs_baseline": None,
                "detail": json.loads(json.dumps(detail)),
            })
            _emit(record)
            _write_partial({"detail": detail, "queries": {}}, record=record)
            try:
                with open(PARTIAL_PATH + ".done", "w") as f:
                    f.write("1")
            except OSError:
                pass
    db.close()


def _supervise() -> int:
    """Wedge-proof record: run the real bench in a CHILD process sharing
    this stdout.  The in-child watchdog cannot fire when a native op (XLA
    compile, a blocked device fetch) wedges every Python thread — the GIL
    never comes back, and rounds 2-5 all ended rc=124 exactly there.  The
    supervisor never calls into jax (the chip belongs to the worker), so
    its deadline ALWAYS fires: at BUDGET - grace/2 it kills the child and
    prints the compact record from BENCH_PARTIAL.json before the driver's
    timeout.  The record is evidence, not a pass: a worker that failed or
    had to be killed makes the exit code non-zero."""
    import subprocess

    deadline = max(BUDGET_S - max(WATCHDOG_GRACE_S / 2.0, 15.0), 30.0)
    child = subprocess.Popen(
        [sys.executable, sys.argv[0], "--worker", *sys.argv[1:]]
    )

    def _print_partial_record(why: str):
        rec = None
        try:
            with open(PARTIAL_PATH) as f:
                rec = json.load(f).get("record")
        except Exception:  # noqa: BLE001 — torn partial: minimal record
            rec = None
        if rec is None:
            rec = {
                "metric": "tsbs_double_groupby_1_e2e_warm_p50",
                "value": None, "unit": "ms", "vs_baseline": None,
                "detail": {},
            }
        rec.setdefault("detail", {})["supervisor"] = why
        print(json.dumps(rec, separators=(",", ":")), flush=True)

    def on_term(signum, frame):  # noqa: ARG001 — forward + publish
        try:
            child.kill()
        except OSError:
            pass
        _print_partial_record(f"supervisor got signal {signum}")
        os._exit(113)

    for s in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(s, on_term)
        except (ValueError, OSError):
            pass

    killed = False
    try:
        child.wait(timeout=deadline)
    except subprocess.TimeoutExpired:
        killed = True
        child.kill()
        try:
            child.wait(timeout=10)
        except Exception:  # noqa: BLE001 — unkillable child: exit anyway
            pass
    if not killed and child.returncode == 0:
        return 0
    _print_partial_record(
        "killed wedged worker at deadline" if killed
        else f"worker exited rc={child.returncode}"
    )
    return 124 if killed else 1


if __name__ == "__main__":
    try:
        argv = [a for a in sys.argv if a != "--worker"]
        worker = "--worker" in sys.argv
        mode = "tsbs"
        if "--mode" in argv:
            idx = argv.index("--mode") + 1
            if idx >= len(argv):
                raise ValueError("--mode requires a value (tsbs | mixed)")
            mode = argv[idx]
            if mode not in ("tsbs", "mixed"):
                raise ValueError(f"unknown --mode {mode!r} (tsbs | mixed)")
        devices_n = None
        if "--devices" in argv:
            idx = argv.index("--devices") + 1
            if idx >= len(argv):
                raise ValueError("--devices requires a device count")
            devices_n = int(argv[idx])
            if devices_n < 1:
                raise ValueError(f"--devices must be >= 1, got {devices_n}")
        if "--rtt-ms" in argv:
            # synthetic link delay for mixed mode; rides the env so the
            # supervisor's child inherits it
            idx = argv.index("--rtt-ms") + 1
            if idx >= len(argv):
                raise ValueError("--rtt-ms requires a millisecond value")
            rtt_arg = float(argv[idx])
            if rtt_arg < 0:
                raise ValueError(f"--rtt-ms must be >= 0, got {rtt_arg}")
            os.environ["GRAFT_BENCH_RTT_MS"] = str(rtt_arg)
        if (
            not worker
            and devices_n is None
            and mode == "tsbs"
            and os.environ.get("GRAFT_BENCH_SUPERVISE", "1") != "0"
        ):
            sys.exit(_supervise())
        if devices_n is not None:
            multichip_main(devices_n)
        elif mode == "mixed":
            mixed_main()
        else:
            main()
    except SystemExit:
        raise
    except Exception:
        # the one-line record must land even when the bench itself dies
        import traceback

        _STATE["detail"]["bench_error"] = traceback.format_exc(limit=20)
        traceback.print_exc()
        _emit_final()
        raise
