"""The one module of the benchmark that touches the system under test.

Copied from `chip_smoke.py` (PR 22), where each piece has run green on the
chip: the database with default settings, the load through
`Database.insert_rows`, the wait for the background family builder, the
counter snapshots, jax's own compile events and the device supervisor's
verdict.  Everything else under `benchmark/` is numpy and the standard
library, so the yardstick shares no code with what it measures.
"""

from __future__ import annotations

import time

MUST_NOT_MOVE = ("TPU_FALLBACK_TOTAL", "TPU_ROUTED_TO_CPU", "TQL_TILE_DEGRADED")


def open_database(home: str, settings: dict):
    """`Config.load()` defaults plus the configuration file's `database`
    group (dotted `section.field` keys)."""
    from greptimedb_tpu.database import Database
    from greptimedb_tpu.utils.config import Config

    db = Database(config=Config.load(), data_home=home)
    for dotted, value in settings.items():
        section, field = dotted.split(".")
        target = getattr(db.config, section)
        if not hasattr(target, field):
            raise KeyError(f"no setting {dotted}")
        setattr(target, field, tuple(value) if isinstance(value, list) else value)
    return db


def start_server(db):
    from greptimedb_tpu.servers.http import HttpServer

    return HttpServer(db, "127.0.0.1:0").start()


def load(db, dataset) -> dict:
    """Create the configuration's tables, ingest every batch through the
    servers' `insert_rows` path (partition split, WAL, memtable), flush, and
    let compaction finish."""
    for statement in dataset.create_statements():
        db.sql(statement)
    insert_s, rows = 0.0, 0
    for table, batch in dataset.batches():
        t0 = time.perf_counter()
        db.insert_rows(table, batch)
        insert_s += time.perf_counter() - t0
        rows += batch.num_rows
    t0 = time.perf_counter()
    db.storage.flush_all()
    flush_s = time.perf_counter() - t0
    # the background compaction scheduler, run here to its end: left to its
    # own ticks it swaps the region's files after the prewarm, and the next
    # request rebuilds every plane, in the warm-up (26 s for 12 s) or, when
    # it comes late, inside the window (my chip runs, PR 26, calls 9-10)
    t0 = time.perf_counter()
    compactor = db.storage.compactor
    while compactor is not None and compactor.run_once():
        pass
    return {
        "rows": rows, "insert_s": insert_s, "flush_s": flush_s,
        "compact_s": time.perf_counter() - t0,
    }


def prewarm(db, tables: list) -> float:
    t0 = time.perf_counter()
    for key, stats in db.prewarm(tables=tables).items():
        if "error" in stats:
            raise RuntimeError(f"prewarm {key}: {stats}")
    return time.perf_counter() - t0


def wait_builds(db, timeout_s: float = 900.0):
    """Wait out the background family builder: a cold request schedules
    the plane build, and warm requests must find the planes resident."""
    te = db.query_engine._tile_executor
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        with te._fused_lock:
            if not te._fused_builds and not te._fused_queue:
                return
        time.sleep(0.05)
    raise RuntimeError("background plane build did not finish")


def counters() -> dict:
    """Every counter of `utils/metrics.py`, by its module-level name."""
    from greptimedb_tpu.utils import metrics

    return {
        name: float(obj.total())
        for name, obj in vars(metrics).items()
        if name.isupper() and type(obj).__name__ == "Counter"
    }


class CompileLog:
    """jax's own monitoring events: one (clock, seconds) per backend compile."""

    def __init__(self):
        from jax import monitoring

        self.events: list = []
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, name, secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), float(secs)))

    def between(self, t0: float, t1: float) -> list:
        return [secs for at, secs in self.events if t0 <= at < t1]


def unhealthy_devices(db) -> list:
    """Rows of `information_schema.device_health` that are not HEALTHY with
    nothing abandoned or quarantined; an empty table counts as one."""
    t = db.sql_one(
        "SELECT device, state, abandoned_calls, quarantines "
        "FROM information_schema.device_health"
    )
    rows = list(zip(*[t[c].to_pylist() for c in t.column_names]))
    bad = [r for r in rows if r[1] != "HEALTHY" or r[2] or r[3]]
    return bad if rows else [("none", "NO_DEVICE", 0, 0)]
