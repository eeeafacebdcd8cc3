"""Sharded region-worker write loops with request batching.

Role-equivalent of the reference's `WorkerGroup`/`RegionWorkerLoop`
(mito2/src/worker.rs:136,459,863): requests are hashed to one of
`num_workers` single-threaded loops by region id (`region_id_to_index` —
one writer per region, races structured out), and each loop drains its
queue in batches of up to `worker_request_batch_size`, grouping writes by
region so one WAL append + memtable insert covers many requests
(worker/handle_write.rs stages the same batching).

The synchronous `TimeSeriesEngine.write` remains the single-region
path; the Database inserter pipelines MULTI-REGION writes through the
group (database.py write_batch) so per-region WAL appends overlap.
"""

from __future__ import annotations

import queue
import threading
from concurrent.futures import Future
from dataclasses import dataclass

import pyarrow as pa


@dataclass
class _WriteRequest:
    region_id: int
    batch: pa.RecordBatch
    future: Future
    # the submitter's own dict for the write's stage durations, or None
    stages: dict | None = None


class RegionWorkerLoop:
    """One single-threaded worker: the only writer for its region subset
    (reference RegionWorkerLoop, worker.rs:863 — `tokio::select!` over the
    request channel; here a queue.get with a drain)."""

    def __init__(self, engine, index: int, batch_size: int):
        self.engine = engine
        self.index = index
        self.batch_size = batch_size
        self.stopped = False
        self.queue: queue.Queue[_WriteRequest | None] = queue.Queue()
        self.thread = threading.Thread(
            target=self._run, name=f"region-worker-{index}", daemon=True
        )
        self.thread.start()

    def submit(self, req: _WriteRequest):
        if self.stopped:
            req.future.set_exception(
                RuntimeError("region worker group is stopped")
            )
            return
        self.queue.put(req)

    def stop(self):
        self.stopped = True
        self.queue.put(None)
        self.thread.join(timeout=10)
        # fail anything still queued: a caller blocked on future.result()
        # must see shutdown, not hang
        while True:
            try:
                req = self.queue.get_nowait()
            except queue.Empty:
                break
            if req is not None and not req.future.done():
                req.future.set_exception(
                    RuntimeError("region worker group stopped before write ran")
                )

    def _run(self):
        while True:
            req = self.queue.get()
            if req is None:
                return
            batch = [req]
            # drain: batch up to batch_size requests per wakeup
            while len(batch) < self.batch_size:
                try:
                    nxt = self.queue.get_nowait()
                except queue.Empty:
                    break
                if nxt is None:
                    self._handle(batch)
                    return
                batch.append(nxt)
            self._handle(batch)

    def _handle(self, reqs: list[_WriteRequest]):
        """Group by region; one WAL frame + one memtable lock per (region,
        drained group) (reference handle_write_requests,
        worker/handle_write.rs:40).  With ingest.group_commit on the group
        commits through engine.write_group — ONE frame carrying one entry
        id per request, so replay/lag/prune semantics match frame-per-
        write.  Off restores the legacy merge (one batch, one entry id)
        bit-for-bit."""
        by_region: dict[int, list[_WriteRequest]] = {}
        for r in reqs:
            by_region.setdefault(r.region_id, []).append(r)
        for rid, group in by_region.items():
            # what the write's `write.wal` / `write.memtable` stages measured,
            # handed back in each request's own dict BEFORE its future
            # resolves: a per-request value, so a concurrent caller's later
            # write on this region can never be mis-attributed to this
            # statement's write.region span
            stages: dict = {}
            try:
                if len(group) == 1:
                    rows_list = [self.engine.write(rid, group[0].batch, stages)]
                elif getattr(self.engine.config, "ingest_group_commit", True):
                    rows_list = self.engine.write_group(
                        rid, [g.batch for g in group], stages
                    )
                else:
                    merged = pa.Table.from_batches(
                        [g.batch for g in group]
                    ).combine_chunks()
                    self.engine.write(
                        rid, merged.to_batches()[0]
                        if merged.num_rows
                        else group[0].batch,
                        stages,
                    )
                    rows_list = [g.batch.num_rows for g in group]
                for g, n in zip(group, rows_list):
                    if g.stages is not None:
                        g.stages.update(stages)
                    g.future.set_result(n)
            except Exception as e:  # noqa: BLE001 — deliver per-request
                for g in group:
                    if not g.future.done():
                        g.future.set_exception(e)


class WorkerGroup:
    """Hash regions across workers (reference WorkerGroup, worker.rs:136;
    region_id_to_index :459)."""

    def __init__(self, engine, num_workers: int = 4, batch_size: int = 64):
        self.workers = [
            RegionWorkerLoop(engine, i, batch_size) for i in range(max(num_workers, 1))
        ]

    def _worker_for(self, region_id: int) -> RegionWorkerLoop:
        return self.workers[region_id % len(self.workers)]

    def submit_write(
        self, region_id: int, batch: pa.RecordBatch, stages: dict | None = None
    ) -> Future:
        """`stages`, the caller's own dict, holds `wal_ms` / `memtable_ms`
        (and `group_writes` of a merged frame) once the future resolves."""
        fut: Future = Future()
        self._worker_for(region_id).submit(_WriteRequest(region_id, batch, fut, stages))
        return fut

    def write(self, region_id: int, batch: pa.RecordBatch, timeout: float = 60.0) -> int:
        return self.submit_write(region_id, batch).result(timeout)

    def stop(self):
        for w in self.workers:
            w.stop()
