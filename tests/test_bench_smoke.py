"""bench-smoke: a ~60 s mini-bench through the FULL engine path (one
query family, tiny dataset, prewarm + delta-flush + query) so cold-path
regressions fail tier-1 instead of only surfacing in the 4-round bench
record.  Select alone with `pytest -m bench_smoke`.

Wall-clock assertions are deliberately loose (CI machines vary); the
hard contracts are metric-based: prewarm builds the tiles off the query
path, the post-flush delta merges instead of rebuilding, the delta
query is no slower than the initial cold (which pays consolidation +
XLA compile), and results match the authoritative CPU path.
"""

import math
import time

import numpy as np
import pyarrow as pa
import pytest

from greptimedb_tpu.database import Database
from greptimedb_tpu.utils import metrics
from greptimedb_tpu.utils.config import Config

N_HOSTS = 8
TICKS = 720  # 2 h at 10 s scrape
T0 = 1_767_225_600_000


def _ingest(db, tick_lo, tick_hi, seed):
    rng = np.random.default_rng(seed)
    ticks = tick_hi - tick_lo
    ts = (
        T0 + (tick_lo + np.arange(ticks, dtype=np.int64))[:, None] * 10_000
    )
    ts = np.broadcast_to(ts, (ticks, N_HOSTS)).reshape(-1)
    hosts = np.broadcast_to(
        np.array([f"host_{i}" for i in range(N_HOSTS)])[None, :],
        (ticks, N_HOSTS),
    ).reshape(-1)
    db.insert_rows("cpu", pa.table({
        "hostname": pa.array(hosts),
        "ts": pa.array(ts, pa.timestamp("ms")),
        "usage_user": pa.array(rng.uniform(0, 100, ticks * N_HOSTS)),
        "usage_system": pa.array(rng.uniform(0, 100, ticks * N_HOSTS)),
    }))
    return ticks * N_HOSTS


@pytest.mark.bench_smoke
def test_bench_smoke_prewarm_delta_query(tmp_path):
    t_suite = time.perf_counter()
    cfg = Config()
    cfg.storage.compaction_background_enable = False
    db = Database(data_home=str(tmp_path / "bench"), config=cfg)
    try:
        db.sql(
            "CREATE TABLE cpu (hostname STRING, ts TIMESTAMP(3) TIME INDEX,"
            " usage_user DOUBLE, usage_system DOUBLE,"
            " PRIMARY KEY (hostname)) WITH (append_mode = 'true')"
        )
        n = _ingest(db, 0, TICKS, seed=1)
        db.storage.flush_all()

        # prewarm: the cold consolidation runs OFF the query path
        pw0 = metrics.PREWARM_BUILDS.get()
        db.prewarm(tables=["cpu"])
        assert metrics.PREWARM_BUILDS.get() > pw0

        q = (
            "SELECT hostname, time_bucket('1m', ts) AS tb,"
            " avg(usage_user) AS au FROM cpu GROUP BY hostname, tb"
        )
        lowered0 = metrics.TILE_LOWERED_TOTAL.get()
        t0 = time.perf_counter()
        db.sql_one(q)
        db.sql_one(q)  # device planes warm (cold-serve answered once)
        initial_cold_ms = (time.perf_counter() - t0) * 1000
        assert metrics.TILE_LOWERED_TOTAL.get() > lowered0, (
            "mini-bench query did not take the tile path"
        )

        # delta flush (~5% new rows) + re-query: must delta-merge, not
        # rebuild, and serve no slower than the initial cold
        merges0 = metrics.TILE_DELTA_MERGES.get()
        entry = next(iter(db.query_engine.tile_cache._super.values()))
        _ingest(db, TICKS, TICKS + TICKS // 20, seed=2)
        db.storage.flush_all()
        t0 = time.perf_counter()
        t_delta = db.sql_one(q)
        delta_ms = (time.perf_counter() - t0) * 1000
        assert metrics.TILE_DELTA_MERGES.get() == merges0 + 1, (
            "post-flush query rebuilt the super-tile instead of delta-merging"
        )
        assert (
            next(iter(db.query_engine.tile_cache._super.values())) is entry
        )
        assert delta_ms <= max(initial_cold_ms, 1000.0), (
            f"delta cold ({delta_ms:.0f} ms) regressed past the initial "
            f"cold ({initial_cold_ms:.0f} ms)"
        )

        # correctness vs the authoritative CPU path
        db.config.query.backend = "cpu"
        t_cpu = db.sql_one(q)
        db.config.query.backend = "tpu"
        k = [("hostname", "ascending"), ("tb", "ascending")]
        got = t_delta.sort_by(k).to_pydict()
        want = t_cpu.sort_by(k).to_pydict()
        assert got["hostname"] == want["hostname"]
        for x, y in zip(got["au"], want["au"]):
            assert math.isclose(x, y, rel_tol=1e-9), (x, y)
        assert n == TICKS * N_HOSTS
    finally:
        db.close()
    assert time.perf_counter() - t_suite < 60, (
        "bench-smoke exceeded its 60 s budget"
    )


@pytest.mark.bench_smoke
def test_bench_smoke_fused_cold_path(tmp_path):
    """The REAL `bench.py` (tsbs mode, tiny dataset) end-to-end under the
    standard budget guard: the multi-query TSBS family cold-serves from
    the host consolidation before device planes exist (cold_served per
    query event), the build rep coalesces onto ONE consolidated background
    build, rc=0, and the emitted record is a single COMPACT line — it must
    fit the driver's ~2000-byte tail capture or the official record
    cannot parse (the r03 lesson)."""
    import json
    import os
    import subprocess
    import sys

    t_suite = time.perf_counter()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "GRAFT_BENCH_HOSTS": "24",
        "GRAFT_BENCH_HOURS": "1",
        "GRAFT_BENCH_REPS": "2",
        "GRAFT_BENCH_BUDGET_S": "100",
        "GRAFT_BENCH_HTTP_ROWS": "0",
        "GRAFT_BENCH_COLD_PROBE": "0",
        "GRAFT_BENCH_AGG_PROBE": "0",
        "GRAFT_BENCH_LTH_ROWS": "0",
        "GRAFT_BENCH_DATA_DIR": "",
        "GRAFT_BENCH_PARTIAL": str(tmp_path / "fused_partial.json"),
    }
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py")],
        capture_output=True, text=True, timeout=160, env=env,
        cwd=str(tmp_path),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    record = json.loads(lines[-1])
    assert record["metric"] == "tsbs_double_groupby_1_e2e_warm_p50"
    assert len(lines[-1]) < 1900, (
        f"summary record is {len(lines[-1])} bytes — it will not survive "
        "the driver's tail capture"
    )
    q = record["detail"]["queries"]
    assert len(q) == 15 and all("cold_ms" in v for v in q.values()), q
    assert "cold_over_2x_ref" in record["detail"]
    assert record["detail"].get("geomean_vs_baseline_all") is not None
    # cold-serve + build-coalescing evidence from the per-query events:
    # the dg family answers from host while the fused build runs behind
    served = coalesced = 0
    for line in lines:
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        served += int(obj.get("cold_served") or 0)
        coalesced += int(obj.get("build_coalesced") or 0)
    assert served >= 3, "TSBS families did not cold-serve from host"
    assert coalesced >= 1, (
        "no build rep coalesced onto the background fused build"
    )
    assert time.perf_counter() - t_suite < 120, (
        "fused bench-smoke exceeded its 120 s budget"
    )


@pytest.mark.bench_smoke
def test_bench_smoke_ingest_pipeline(tmp_path):
    """ISSUE 15 ingest micro-check: pipelined (group commit + vectorized
    routing + flush overlap, the defaults) vs legacy ingest on a small
    dataset — bit-identical query results, the greptime_ingest_* stage
    metrics present, and merged-frame evidence (WAL frames < writes)
    asserted via counters.  No wall-clock assertion: CI-safe."""
    from concurrent.futures import Future

    from greptimedb_tpu.storage.worker import _WriteRequest

    def mk_db(name, pipelined: bool) -> Database:
        cfg = Config()
        cfg.storage.compaction_background_enable = False
        if not pipelined:
            cfg.storage.ingest_group_commit = False
            cfg.storage.ingest_flush_workers = 1
            cfg.storage.ingest_flush_overlap = False
        db = Database(data_home=str(tmp_path / name), config=cfg)
        db.sql(
            "CREATE TABLE cpu (hostname STRING, ts TIMESTAMP(3) TIME INDEX,"
            " usage_user DOUBLE, usage_system DOUBLE, PRIMARY KEY (hostname))"
            " PARTITION BY HASH (hostname) PARTITIONS 2"
        )
        return db

    db_new = mk_db("pipelined", True)
    db_old = mk_db("legacy", False)
    try:
        w0 = metrics.INGEST_WRITES_TOTAL.get()
        f0 = metrics.INGEST_WAL_FRAMES.get()
        split0 = metrics.INGEST_SPLIT_MS.total()
        wal0 = metrics.INGEST_WAL_MS.total()
        mem0 = metrics.INGEST_MEMTABLE_MS.total()
        enc0 = metrics.INGEST_FLUSH_ENCODE_MS.total()
        for db in (db_new, db_old):
            for lo in range(0, 300, 100):
                _ingest(db, lo, lo + 100, seed=lo)
            # the multi-row VALUES path (zip transpose + coercion)
            db.sql(
                "INSERT INTO cpu VALUES"
                " ('host_0', 1767225600001, 1.5, 2.5),"
                " ('host_1', 1767225600002, 3.5, 4.5)"
            )
        # a deterministic drained group through the pipelined worker:
        # five requests commit as ONE merged WAL frame, five entry ids
        engine = db_new.storage
        frames1 = metrics.INGEST_WAL_FRAMES.get()
        writes1 = metrics.INGEST_WRITES_TOTAL.get()
        rid = db_new.catalog.table("cpu", "public").region_ids[0]
        reqs = [
            _WriteRequest(rid, pa.record_batch(
                {"hostname": pa.array([f"gh_{i}"]),
                 "ts": pa.array([T0 + 10_000_000 + i], pa.timestamp("ms")),
                 "usage_user": pa.array([1.0]),
                 "usage_system": pa.array([2.0])},
            ), Future())
            for i in range(5)
        ]
        engine.workers._worker_for(rid)._handle(reqs)
        assert [r.future.result(timeout=30) for r in reqs] == [1] * 5
        assert metrics.INGEST_WAL_FRAMES.get() - frames1 == 1
        assert metrics.INGEST_WRITES_TOTAL.get() - writes1 == 5
        db_old.sql(
            "INSERT INTO cpu VALUES"
            + ", ".join(
                f"('gh_{i}', {T0 + 10_000_000 + i}, 1.0, 2.0)"
                for i in range(5)
            )
        )
        # merged-frame evidence overall: fewer frames than write requests
        writes_d = metrics.INGEST_WRITES_TOTAL.get() - w0
        frames_d = metrics.INGEST_WAL_FRAMES.get() - f0
        assert writes_d > 0 and frames_d < writes_d, (frames_d, writes_d)
        # every ingest stage metric observed something
        assert metrics.INGEST_SPLIT_MS.total() > split0
        assert metrics.INGEST_WAL_MS.total() > wal0
        assert metrics.INGEST_MEMTABLE_MS.total() > mem0
        db_new.storage.flush_all()
        db_old.storage.flush_all()
        assert metrics.INGEST_FLUSH_ENCODE_MS.total() > enc0
        # bit-identical query results across the two ladders
        for q in (
            "SELECT hostname, ts, usage_user, usage_system FROM cpu"
            " ORDER BY hostname, ts",
            "SELECT hostname, avg(usage_user), count(usage_system) FROM cpu"
            " GROUP BY hostname ORDER BY hostname",
        ):
            t_new, t_old = db_new.sql_one(q), db_old.sql_one(q)
            assert t_new.to_pydict() == t_old.to_pydict(), q
    finally:
        db_new.close()
        db_old.close()


@pytest.mark.bench_smoke
def test_bench_smoke_mixed_overload(tmp_path):
    """`bench.py --mode mixed` smoke: concurrent ingest+query against a
    tile budget FORCED below the working set, admission + coalescing +
    HBM feedback all on.  The graceful-degradation contract: rc=0, ZERO
    failed queries, a parseable record carrying p50/p99, and >= 1
    coalesced dispatch (concurrent same-family queries shared an
    in-flight dispatch)."""
    import json
    import os
    import subprocess
    import sys

    t_suite = time.perf_counter()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "GRAFT_MIXED_SECONDS": "12",
        "GRAFT_MIXED_HOSTS": "16",
        "GRAFT_MIXED_TICKS": "400",
        "GRAFT_MIXED_QUERY_WORKERS": "6",
        "GRAFT_MIXED_INGEST_WORKERS": "1",
        # keep the batching phases inside this test's 60 s budget (the
        # dedicated sweep contract lives in test_bench_smoke_qps_sweep)
        "GRAFT_MIXED_SWEEP_QPS": "10,25",
        "GRAFT_MIXED_SWEEP_SECONDS": "1.0",
        "GRAFT_BENCH_BUDGET_S": "150",
        "GRAFT_BENCH_PARTIAL": str(tmp_path / "mixed_partial.json"),
    }
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"), "--mode", "mixed"],
        capture_output=True, text=True, timeout=170, env=env, cwd=str(tmp_path),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    record = None
    for line in out.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if obj.get("metric") == "mixed_load_e2e_p99":
            record = obj
    assert record is not None, out.stdout[-2000:]
    d = record["detail"]
    assert d["zero_failed_queries"] and d["failed"] == 0, d.get("errors")
    assert d["queries"] > 0 and d["ingest_batches"] > 0
    # the record must carry the latency shape (p50 overall + p99 headline)
    assert record["value"] is not None and d["p50_ms"] is not None
    for fam, stats in d["families"].items():
        assert stats["n"] > 0, f"family {fam} never completed a query"
        assert stats["p99_ms"] is not None
    # coalesced dispatches observable under concurrent same-family load
    assert d["coalesced_dispatches"] > 0
    assert time.perf_counter() - t_suite < 60, (
        "mixed bench-smoke exceeded its 60 s budget"
    )


@pytest.fixture(scope="module")
def sweep_record(tmp_path_factory):
    """ONE `bench.py --mode mixed --rtt-ms 100` subprocess shared by the
    QPS-sweep and fused-batch smokes (both read the same record; two
    subprocess runs would double the wall cost for no extra coverage).
    The injected 100 ms synthetic delay makes every device crossing
    costly — every sweep/burst contract below must hold under it too."""
    import json
    import os
    import subprocess
    import sys

    tmp_path = tmp_path_factory.mktemp("sweep")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "GRAFT_MIXED_SECONDS": "6",
        "GRAFT_MIXED_HOSTS": "16",
        "GRAFT_MIXED_TICKS": "400",
        "GRAFT_MIXED_QUERY_WORKERS": "6",
        "GRAFT_MIXED_INGEST_WORKERS": "1",
        "GRAFT_MIXED_SWEEP_QPS": "10,30",
        "GRAFT_MIXED_SWEEP_SECONDS": "1.5",
        "GRAFT_MIXED_HOTSPOT_STEPS": "40",
        "GRAFT_BENCH_BUDGET_S": "150",
        "GRAFT_BENCH_PARTIAL": str(tmp_path / "sweep_partial.json"),
    }
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"),
         "--mode", "mixed", "--rtt-ms", "100"],
        capture_output=True, text=True, timeout=200, env=env,
        cwd=str(tmp_path),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    record = line = None
    for raw in out.stdout.splitlines():
        try:
            obj = json.loads(raw)
        except ValueError:
            continue
        if obj.get("metric") == "mixed_load_e2e_p99":
            record, line = obj, raw
    assert record is not None, out.stdout[-2000:]
    return record, line


@pytest.mark.bench_smoke
def test_bench_smoke_qps_sweep(sweep_record):
    """`bench.py --mode mixed` QPS-sweep smoke: the dashboard-fleet
    offered-load ladder runs OFF then ON, the record carries both curves
    (offered -> achieved, p50/p99, shed) plus the knee and speedup, the
    deterministic burst proves a mega-dispatch happened
    (batched_members > 0), the ON sweep proves the result cache served
    (result_cache_hits > 0), zero queries failed, and the emitted line
    stays inside the driver's tail capture."""
    import json

    record, line = sweep_record
    d = record["detail"]
    assert d["zero_failed_queries"] and d["failed"] == 0, d.get("errors")
    sweep = d["qps_sweep"]
    assert "error" not in sweep, sweep
    for mode in ("off", "on"):
        ms = sweep[mode]
        # the curve: one [offered, achieved, p50, p99, shed] row per level
        assert len(ms["curve"]) == 2
        for offered, achieved, p50, p99, shed in ms["curve"]:
            assert offered > 0 and achieved > 0
            assert p50 is not None and p99 is not None and p50 <= p99
            assert shed >= 0
        assert ms["knee_qps"] > 0 and ms["knee_offered_qps"] > 0
        assert ms["sustained_qps"] >= ms["knee_qps"]
        assert ms["p99_at_knee_ms"] is not None
        assert ms["failed"] == 0
    assert sweep["speedup"] > 0
    # the deterministic burst packed >= 2 DISTINCT queries into one
    # mega-dispatch, and the ON sweep re-served from the result cache
    assert d["batched_members"] >= 2 and d["batch_dispatches"] >= 1
    assert d["result_cache_hits"] > 0
    # the emitted line survives the driver's ~2000-byte tail capture
    assert len(json.dumps(record, separators=(",", ":"))) < 1900, line


@pytest.mark.bench_smoke
def test_bench_smoke_fused_batch(sweep_record):
    """`bench.py --mode mixed --rtt-ms 100` smoke (same subprocess as
    the sweep test): a symmetric 100 ms synthetic host<->device delay
    around every dispatch and fetch boundary, with
    mega-program fusion on.  The contract: rc=0, the record carries the
    injected rtt_ms, at least one batch tick answered as ONE fused XLA
    invocation (fused_dispatches >= 1), zero failed queries, and the
    emitted line stays inside the driver's tail capture."""
    import json

    record, line = sweep_record
    d = record["detail"]
    assert d["zero_failed_queries"] and d["failed"] == 0, d.get("errors")
    assert d["rtt_ms"] == 100
    # the deterministic burst (and/or the ON sweep) fused >= 1 batch
    # tick into a single XLA invocation under the injected RTT
    assert d["fused_dispatches"] >= 1, d
    assert d["batched_members"] >= 2 and d["batch_dispatches"] >= 1
    assert len(json.dumps(record, separators=(",", ":"))) < 1900, line


def test_compact_record_stays_under_tail_capture():
    """Unit pin of the r03 failure mode: the compact summary record —
    with EVERY per-query field populated worst-case (including the PR 14
    per-query stage digests), the PR 13 `tql` section, every
    skip-reason/error permutation, all 15 queries over the 2x-ref cold
    bound and the budget flags set — must stay under 1.9 KB so the
    driver's ~2000-byte tail capture can never truncate it again."""
    import importlib
    import json

    bench = importlib.import_module("bench")
    # worst-case realistic values: 5-6 digit cold times, 4-decimal
    # sub-0.05 ratios, a stage digest on every query
    queries = {}
    for name, _sql, ref in bench.QUERIES:
        queries[name] = {
            "reference_ms": ref,
            "cold_ms": 123456.8,
            "warm_ms": 104857.36,
            "vs_baseline": 0.0123,
            "stage": "rt99999",
        }
    # permutations that surface per-query in the compact record: a query
    # that ERRORED before any rep (error string, truncated to 60)
    queries["high-cpu-all"] = {
        "reference_ms": 4638.57,
        "error": "QueryTimeoutError('query exceeded its deadline of 600.0 s a",
    }
    state = dict(bench._STATE)
    try:
        bench._STATE["results"] = queries
        bench._STATE["headline"] = {
            "warm_ms": 104857.36, "vs_baseline": 0.0123,
        }
        bench._STATE["detail"] = {
            "device": "TFRT_CPU_0 (a long device string; machine-features quieted)",
            "rows": 103_680_000,
            "dataset_hours": 72,
            "prewarm_s": 3599.9,
            "budget_watchdog_fired": True,
            "killed_by_signal": 15,
            "budget_exhausted": True,
            "dataset_reused": True,
            # the PR 13 tql digest: every shape it can take at once —
            # measured pairs, an errored query, the twin reference AND a
            # phase-level skip reason
            "tql": {
                "rate": [104857.36, 104857.36, 0.0123],
                "sumby": [104857.36, 104857.36, 0.0123],
                "inc1": {"error": "RuntimeError('tile path degraded mid-"},
                "twin_ms": 99999.9,
                "skipped": "remaining budget below tql-phase floor",
            },
            # the ISSUE 15 ingest digest at its widest (all stages 5
            # digits + worst-case frame accounting) — clamp step 4b slims
            # it to its headline when the line is contended
            "ingest": {
                "rps": 398_000,
                "st": "sy99999,in99999,sp99999,wa99999,me99999,fe99999,fl99999",
                "fw": "1036800/103680000",
            },
        }
        record = bench._build_record()
        line = json.dumps(record, separators=(",", ":"))
    finally:
        bench._STATE.update(state)
    # the clamp may spend conveniences (stage digests, the full
    # cold_over list) but the acceptance fields survive for ALL queries
    q = record["detail"]["queries"]
    assert len(q) == 15
    assert all("cold_ms" in v or "error" in v for v in q.values())
    assert "cold_over_2x_ref" in record["detail"]
    assert record["detail"]["tql"].get("skipped")
    # the ingest digest survives clamping as its headline string:
    # rows/s + the frames/writes merge evidence
    assert record["detail"]["ingest"] == "398000;1036800/103680000"
    assert len(line) < 1900, (
        f"compact record is {len(line)} bytes — it will not survive the "
        f"driver's ~2000-byte tail capture: {line[:300]}..."
    )


def test_compact_record_realistic_keeps_stage_digests():
    """In a realistic run (the r06 shape: warm wins, small numbers) the
    per-query stage digests survive the clamp into the emitted record —
    that is the stage-attribution evidence the driver round reads."""
    import importlib
    import json

    bench = importlib.import_module("bench")
    # r05-shaped numbers: colds mostly inside 2x ref (a couple over, so
    # the cold_over list is short), warm wins of 1-4000 ms
    queries = {}
    for i, (name, _sql, ref) in enumerate(bench.QUERIES):
        over = i in (2, 13)  # two queries over the 2x-ref cold bound
        queries[name] = {
            "reference_ms": ref,
            "cold_ms": round(ref * (4.0 if over else 1.5), 1),
            "warm_ms": round(ref / 4.9, 2),
            "vs_baseline": 4.9,
            "stage": "di3.2",
        }
    state = dict(bench._STATE)
    try:
        bench._STATE["results"] = queries
        bench._STATE["headline"] = {"warm_ms": 13.3, "vs_baseline": 50.61}
        bench._STATE["detail"] = {
            "device": "TFRT_CPU_0",
            "rows": 103_680_000,
            "dataset_hours": 72,
            "prewarm_s": 210.4,
            "budget_exhausted": False,
            # a run that emits an ingest digest by definition did NOT
            # reuse the dataset (the digest only exists for real ingests)
            "dataset_reused": False,
            "tql": {
                "rate": [1.9, 38.2, 20.1],
                "sumby": [2.3, 41.0, 17.8],
                "inc1": [1.7, 36.9, 21.7],
                "twin_ms": 55.0,
            },
            "ingest": {
                "rps": 812_400,
                "st": "sy12.1,in128,sp3.1,wa41.2,me22.8,fe88.0,fl9.4",
                "fw": "52/52",
            },
        }
        record = bench._build_record()
        line = json.dumps(record, separators=(",", ":"))
    finally:
        bench._STATE.update(state)
    stages = record["detail"].get("stages")
    assert stages is not None, (
        "realistic record lost its stage-attribution string to the clamp"
    )
    assert stages.split(",") == ["di3.2"] * 15
    assert record["detail"]["tql"]["rate"] == [1.9, 38.2, 20.1]
    # the ingest digest keeps at least its headline (rows/s + frame
    # merge evidence) alongside the surviving stage digests
    ing = record["detail"]["ingest"]
    assert (ing == "812400;52/52") or ing.get("rps") == 812_400
    assert len(line) < 1900, f"realistic record is {len(line)} bytes"


def test_compact_record_mixed_sweep_worstcase_clamps():
    """Worst-case MIXED record (the shape mixed_main emits): full-ladder
    sweep curves with 6-digit figures, five long error strings, the
    hotspot phase latencies and every counter populated — the clamp must
    land it under the driver's ~2000-byte tail capture while the verdict
    scalars (knee/sustained QPS, speedup, batched_members,
    result_cache_hits, zero_failed_queries) survive."""
    import importlib
    import json

    bench = importlib.import_module("bench")
    curve = [
        [float(q), round(q * 0.993, 1), 104857.36, 123456.78, 99999]
        for q in (25, 50, 100, 200, 400, 800, 1600)
    ]
    detail = {
        "mode": "mixed",
        "device": "TFRT_CPU_0 (a long device string; machine-features quieted)",
        "hosts": 64, "seed_ticks": 1500, "seconds": 30.0,
        "query_workers": 8, "ingest_workers": 2, "tile_budget_mb": 1,
        "seed_rows": 96_000,
        "qps_sweep": {
            "batch_window_ms": 2.0, "fleet": 6, "workers": 8,
            "off": {"curve": curve, "knee_offered_qps": 1600.0,
                    "knee_qps": 104857.3, "p99_at_knee_ms": 123456.78,
                    "sustained_qps": 104857.3, "failed": 0},
            "on": {"curve": curve, "knee_offered_qps": 1600.0,
                   "knee_qps": 104857.3, "p99_at_knee_ms": 123456.78,
                   "sustained_qps": 104857.3, "failed": 0},
            "speedup": 104857.3,
        },
        "batch_dispatches": 1_048_576.0, "batched_members": 1_048_576.0,
        "batch_burst": {"dispatches": 1_048_576.0, "members": 1_048_576.0,
                        "rounds": 5, "failed": 0},
        "result_cache_hits": 104_857_600.0,
        "hotspot": {
            "steps": 160, "acked_rows": 1_048_576, "retried_writes": 99,
            "write_retries_exhausted": 0, "splits_enacted": 3,
            "first_split_step": 42, "regions": 8, "auto_split": True,
            "failed_queries": 0, "zero_failed_queries": True,
            "phases": {
                "pre_split": {"n": 42, "p50_ms": 104857.36,
                              "p99_ms": 123456.78},
                "post_split": {"n": 118, "p50_ms": 104857.36,
                               "p99_ms": 123456.78},
            },
        },
        "queries": 1_048_576, "failed": 0, "shed": 99_999,
        "ingest_batches": 99_999, "ingest_failed": 0,
        "families": {
            name: {"n": 99_999, "p50_ms": 104857.4, "p99_ms": 123456.8}
            for name in ("double-groupby", "cpu-max-host", "high-cpu-all")
        },
        "errors": [
            f"family-{i}: QueryTimeoutError('query exceeded its deadline "
            f"of 600.0 s after spending it all inside one wedged dispatch')"
            for i in range(5)
        ],
        "coalesced_dispatches": 104_857_600.0,
        "coalition_leaders": 104_857_600.0,
        "admission": {"admitted": 104_857_600.0, "shed": 99_999},
        "hbm": {"probe_free_bytes": 103_680_000_000, "exhausted": 99_999.0,
                "chunk_rows": 16_777_216},
        "device_health": {
            "supervised": True, "wedged": True, "wedge_wall_ms": 123456.7,
            "quarantines": 8, "healed": True, "post_heal_ok": True,
            "zero_failed_queries": True, "abandoned_calls": 8, "heals": 8,
            "states": {f"QUARANTINED_{i}": "QUARANTINED" for i in range(8)},
        },
        "zero_failed_queries": True, "p50_ms": 104857.4,
    }
    record = bench._clamp_record({
        "metric": "mixed_load_e2e_p99", "value": 123456.78, "unit": "ms",
        "vs_baseline": None, "detail": detail,
    })
    line = json.dumps(record, separators=(",", ":"))
    assert len(line) < 1900, (
        f"worst-case mixed record is {len(line)} bytes — it will not "
        f"survive the driver's ~2000-byte tail capture: {line[:300]}..."
    )
    d = record["detail"]
    # the verdict scalars survive every clamp step
    for mode in ("off", "on"):
        assert d["qps_sweep"][mode]["knee_qps"] == 104857.3
        assert d["qps_sweep"][mode]["sustained_qps"] == 104857.3
    assert d["qps_sweep"]["speedup"] == 104857.3
    assert d["batched_members"] == 1_048_576.0
    assert d["result_cache_hits"] == 104_857_600.0
    assert d["zero_failed_queries"] is True
    # conveniences were spent, not the verdict: curves + hotspot phases
    assert "curve" not in d["qps_sweep"]["on"]
    assert "phases" not in d["hotspot"]
    assert len(d["errors"]) <= 2 and all(len(e) <= 40 for e in d["errors"])
    # the device-health digest survives clamping with its verdict scalars
    # (nested per-state maps are the convenience spent)
    dvh = d["device_health"]
    assert dvh["wedged"] is True and dvh["healed"] is True
    assert dvh["quarantines"] == 8
    assert dvh["zero_failed_queries"] is True
    assert "states" not in dvh


def test_recorder_overhead_within_noise(tmp_path):
    """PR 14 overhead contract: the always-on flight recorder must not
    slow the warm tile dispatch.  Interleaved A/B sampling (recorder
    on/off alternating reps, median of each) bounds the delta within
    measurement noise — <5% plus a small absolute allowance for timer
    jitter at millisecond scale."""
    import numpy as np

    from greptimedb_tpu.utils import flight_recorder as fr

    db = Database(data_home=str(tmp_path / "db"))
    try:
        db.sql(
            "CREATE TABLE cpu (hostname STRING, ts TIMESTAMP(3) TIME INDEX,"
            " usage_user DOUBLE, usage_system DOUBLE,"
            " PRIMARY KEY (hostname)) WITH (append_mode = 'true')"
        )
        _ingest(db, 0, TICKS, seed=11)
        db.sql("ADMIN flush_table('cpu')")
        q = (
            "SELECT hostname, time_bucket('1m', ts) AS tb,"
            " avg(usage_user) AS au FROM cpu GROUP BY hostname, tb"
        )
        for _ in range(4):  # cold + build + settle onto the warm path
            db.sql_one(q)
        on: list[float] = []
        off: list[float] = []
        for _rep in range(20):
            for enabled, sink in ((True, on), (False, off)):
                fr.RECORDER.enabled = enabled
                t0 = time.perf_counter()
                db.sql_one(q)
                sink.append((time.perf_counter() - t0) * 1000.0)
        med_on = float(np.median(on))
        med_off = float(np.median(off))
        assert med_on <= med_off * 1.05 + 2.0, (
            f"recorder-on warm median {med_on:.2f} ms vs off "
            f"{med_off:.2f} ms — overhead above the noise bound"
        )
    finally:
        fr.RECORDER.enabled = True
        db.close()
