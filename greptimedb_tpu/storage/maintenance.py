"""Background maintenance: the compaction scheduler.

Role-equivalent of the reference's `CompactionScheduler` driven off the
region worker loop (reference mito2/src/compaction.rs + worker.rs periodic
tick + flush-finished notifications): flushes nudge the scheduler, a
periodic tick catches anything missed, and each round runs the TWCS picker
(`compaction.py`) over the flagged regions.  Without this, L0 accumulates
until an explicit `ADMIN compact_table` — scans degrade silently.

One daemon thread per engine; per-region work is serialized by the region's
own lock (compaction commits via `apply_compaction`), and a region is never
compacted concurrently with itself because the scheduler is the only
automatic driver.
"""

from __future__ import annotations

import threading

from ..utils import metrics


class FollowerSyncer:
    """Follower freshness loop (replica.sync_interval_ms): every interval,
    each READ-ONLY region this engine hosts replays the shared-WAL tail
    past its applied entry id and refreshes its manifest view when the
    leader's version advanced — so hedged reads against followers are
    bounded-staleness instead of frozen-at-open snapshots.

    One daemon thread per engine (like FlushScheduler); a round's failures
    are per-region and retried next round (Region.follower_sync resumes
    from the persisted applied position).  `sync_now()` runs one round
    inline for deterministic tests."""

    def __init__(self, engine, interval_ms: float):
        self.engine = engine
        self.interval_s = max(interval_ms, 1.0) / 1000.0
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="follower-sync", daemon=True
        )
        self._thread.start()

    def sync_now(self) -> dict[int, int]:
        return self.engine.sync_followers()

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5)

    def _loop(self):
        while not self._stop.wait(self.interval_s):
            try:
                self.engine.sync_followers()
            except Exception:  # noqa: BLE001 — engine logs per-region; a
                # whole-round failure must never kill the loop
                pass


class FlushScheduler:
    """Background flush worker: threshold-triggered flushes run OFF the
    write path (reference mito2/src/flush.rs FlushScheduler — the write
    loop only signals; a scheduler task does the Parquet encode + upload).
    Stall-triggered flushes stay synchronous in the engine: that is the
    backpressure mechanism, not an optimization target.

    This is the §2.5 pipeline-parallelism axis for ingest: WAL append +
    memtable insert proceed for new writes while earlier memtables encode
    to SSTs on this thread."""

    def __init__(self, engine):
        self.engine = engine
        self._cv = threading.Condition()
        self._pending: set[int] = set()
        self._inflight: set[int] = set()
        self._stop = False
        self._thread = threading.Thread(target=self._loop, name="flush-scheduler", daemon=True)
        self._thread.start()

    def schedule(self, region_id: int):
        with self._cv:
            # always enqueue — a trigger during an in-flight flush means NEW
            # rows landed in the fresh memtable; dropping it would leave an
            # over-threshold memtable unflushed once writes stop
            self._pending.add(region_id)
            self._cv.notify()

    def wait_idle(self, timeout: float = 30.0):
        """Block until no flush is pending or running (tests, shutdown)."""
        import time as _t

        deadline = _t.monotonic() + timeout
        with self._cv:
            while (self._pending or self._inflight) and _t.monotonic() < deadline:
                self._cv.wait(0.05)

    def stop(self):
        with self._cv:
            self._stop = True
            self._cv.notify()
        self._thread.join(timeout=10)

    def _loop(self):
        while True:
            with self._cv:
                while not self._pending and not self._stop:
                    self._cv.wait(1.0)
                if self._stop and not self._pending:
                    return
                rid = self._pending.pop()
                self._inflight.add(rid)
            try:
                self.engine.flush_region(rid, cause="threshold")
            except Exception:  # noqa: BLE001 — a failed flush retries on the
                # next threshold trip; the WAL still holds the data
                pass
            finally:
                with self._cv:
                    self._inflight.discard(rid)
                    self._cv.notify_all()


class CompactionScheduler:
    def __init__(
        self,
        engine,
        tick_secs: float = 5.0,
        window_ms: int | None = None,
        max_active_runs: int = 4,
        max_inactive_runs: int = 1,
        memory_mb: int = 512,
    ):
        self.engine = engine
        self.tick_secs = tick_secs
        self.window_ms = window_ms
        self.max_active_runs = max_active_runs
        self.max_inactive_runs = max_inactive_runs
        self.memory_mb = memory_mb
        self._cv = threading.Condition()
        self._dirty: set[int] = set()
        self._stop = False
        self._thread = threading.Thread(
            target=self._loop, name="compaction-scheduler", daemon=True
        )
        self._rounds = 0
        self._thread.start()

    # ---- signals -----------------------------------------------------------
    def notify_flush(self, region_id: int):
        """A flush added an L0 file — check this region soon."""
        with self._cv:
            self._dirty.add(region_id)
            self._cv.notify()

    def stop(self):
        with self._cv:
            self._stop = True
            self._cv.notify()
        self._thread.join(timeout=10)

    def run_once(self) -> int:
        """One synchronous round over every region (tests + ADMIN path)."""
        from .compaction import compact_region

        done = 0
        for rid in self.engine.region_ids():
            try:
                region = self.engine.region(rid)
            except Exception:  # noqa: BLE001 — region closed mid-round
                continue
            if not getattr(region, "writable", True):
                # follower replica / downgraded leader: compaction belongs
                # to the leader — two compactors on shared storage would
                # corrupt the manifest
                continue
            try:
                done += compact_region(
                    region,
                    window_ms=self.window_ms,
                    max_active_runs=self.max_active_runs,
                    max_inactive_runs=self.max_inactive_runs,
                    memory_mb=self.memory_mb,
                )
            except Exception:  # noqa: BLE001 — keep the scheduler alive
                metrics.COMPACTION_FAILED.inc()
        self._rounds += 1
        return done

    # ---- loop --------------------------------------------------------------
    def _loop(self):
        from .compaction import compact_region

        while True:
            with self._cv:
                self._cv.wait(timeout=self.tick_secs)
                if self._stop:
                    return
                dirty = self._dirty
                self._dirty = set()
            region_ids = list(dirty) if dirty else self.engine.region_ids()
            for rid in region_ids:
                with self._cv:
                    if self._stop:
                        return
                try:
                    region = self.engine.region(rid)
                except Exception:  # noqa: BLE001 — closed between list and get
                    continue
                if not getattr(region, "writable", True):
                    continue  # follower replica: the leader compacts
                try:
                    n = compact_region(
                        region,
                        window_ms=self.window_ms,
                        max_active_runs=self.max_active_runs,
                        max_inactive_runs=self.max_inactive_runs,
                        memory_mb=self.memory_mb,
                    )
                    if n:
                        metrics.COMPACTION_BACKGROUND.inc(n)
                except Exception:  # noqa: BLE001 — never kill the loop
                    metrics.COMPACTION_FAILED.inc()
            self._rounds += 1
