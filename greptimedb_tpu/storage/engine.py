"""TimeSeriesEngine: the region engine facade.

Role-equivalent of the reference's `MitoEngine` (reference
src/mito2/src/engine.rs:255) implementing the `RegionEngine` surface
(store-api/src/region_engine.rs:785): create/open/close/drop regions, route
write/flush/truncate/alter requests, serve scans, report region statistics.
Flush pressure is driven by a `WriteBufferManager` exactly like the
reference's (flush.rs): per-region and global thresholds, with stall
signalling when the global budget is exhausted.
"""

from __future__ import annotations

import os
import shutil
import threading

import pyarrow as pa

from ..datatypes.schema import Schema
from ..utils import metrics
from ..utils.config import StorageConfig
from ..utils.errors import RegionNotFoundError
from .flush import WriteBufferManager
from .region import Region, RegionStat
from .sst import ScanPredicate
from .wal import WalManager


class TimeSeriesEngine:
    def __init__(self, config: StorageConfig | None = None):
        from .object_store import build_object_store

        self.config = config or StorageConfig()
        os.makedirs(self.config.data_home, exist_ok=True)
        # SSTs + manifests live behind the object-store abstraction
        # (fs by default); the WAL is a local append log (raft-engine
        # analogue) or a shared-topic remote WAL for failover deployments.
        self.object_store = build_object_store(self.config)
        provider = getattr(self.config, "wal_provider", "local")
        if provider == "local":
            self.wal_mgr = WalManager(self.config.effective_wal_dir(), fsync=self.config.wal_fsync)
        elif provider == "shared_file":
            from .remote_wal import RemoteWalManager

            self.wal_mgr = RemoteWalManager(
                self.config.effective_wal_dir(),
                fsync=self.config.wal_fsync,
                num_topics=getattr(self.config, "wal_num_topics", 4),
                segment_bytes=getattr(self.config, "wal_segment_mb", 4) << 20,
            )
        elif provider == "kafka":
            endpoints = getattr(self.config, "wal_kafka_endpoints", "")
            if not endpoints:
                from ..utils.errors import ConfigError

                raise ConfigError(
                    "wal provider 'kafka' needs remote.kafka_endpoints (a "
                    "broker address — remote/fake_kafka.py runs one offline); "
                    "use 'shared_file' on shared storage for the same "
                    "failover semantics without a broker"
                )
            from ..remote.kafka import KafkaWalManager

            self.wal_mgr = KafkaWalManager(
                endpoints,
                num_topics=getattr(self.config, "wal_num_topics", 4),
                pool_size=getattr(self.config, "remote_pool_size", 2),
                call_deadline_s=getattr(self.config, "remote_call_deadline_s", 5.0),
                connect_timeout_s=getattr(self.config, "remote_connect_timeout_s", 2.0),
                retry_attempts=getattr(self.config, "remote_retry_attempts", 5),
            )
        else:
            from ..utils.errors import ConfigError

            raise ConfigError(f"unknown wal provider {provider!r}")
        self.buffer_mgr = WriteBufferManager(
            global_limit_bytes=self.config.global_write_buffer_size_mb << 20,
            region_limit_bytes=self.config.write_buffer_size_mb << 20,
        )
        self._regions: dict[int, Region] = {}
        self._lock = threading.Lock()
        # flush listeners: called with the region id after a flush that
        # added SSTs (the tile.prewarm_on_flush hook rides this); always
        # best-effort, never on the write path's critical section
        self.flush_listeners: list = []
        # delta listeners: called with (region_id, added_file_ids) — the
        # flush's delta notification, so tile maintenance can size its
        # incremental work.  A SEPARATE list (not arity-sniffed off
        # flush_listeners): signature guessing misdispatches callbacks
        # with defaulted or **kw second parameters
        self.delta_listeners: list = []
        self.compactor = None
        self.flusher = None
        self._workers = None  # lazy sharded write loops (storage/worker.py)
        if getattr(self.config, "async_flush_enable", True):
            from .maintenance import FlushScheduler

            self.flusher = FlushScheduler(self)
        if getattr(self.config, "compaction_background_enable", True):
            from .maintenance import CompactionScheduler

            self.compactor = CompactionScheduler(
                self,
                tick_secs=getattr(self.config, "compaction_tick_secs", 5.0),
                window_ms=(self.config.compaction_time_window_secs * 1000) or None,
                max_active_runs=self.config.compaction_max_active_window_runs,
                max_inactive_runs=self.config.compaction_max_inactive_window_runs,
                memory_mb=getattr(self.config, "compaction_memory_mb", 512),
            )
        # Follower freshness loop (replica.sync_interval_ms, copied down to
        # storage.follower_sync_interval_ms): read-only regions tail the
        # shared WAL + refresh their manifest view on this cadence.  0 (the
        # default) starts no thread and keeps open-time-snapshot followers.
        self.follower_syncer = None
        interval_ms = getattr(self.config, "follower_sync_interval_ms", 0.0)
        if interval_ms and interval_ms > 0:
            from .maintenance import FollowerSyncer

            self.follower_syncer = FollowerSyncer(self, interval_ms)

    # ---- region lifecycle -------------------------------------------------
    def create_region(
        self, region_id: int, schema: Schema, writable: bool = True,
        append_mode: bool = False, memtable_kind: str | None = None,
        merge_mode: str | None = None,
    ) -> Region:
        with self._lock:
            if region_id in self._regions:
                return self._regions[region_id]
            region = Region(
                region_id,
                self._region_store(region_id),
                schema,
                self.wal_mgr.region_wal(region_id),
                time_partition_ms=self.config.memtable_time_partition_secs * 1000,
                checkpoint_distance=self.config.manifest_checkpoint_distance,
                writable=writable,
                index_enable=self.config.index_enable,
                index_segment_rows=self.config.index_segment_rows,
                index_inverted_max_terms=self.config.index_inverted_max_terms,
                index_segmented=getattr(self.config, "index_segmented", True),
                index_segment_terms=getattr(self.config, "index_segment_terms", 512),
                index_max_terms=getattr(self.config, "index_max_terms", 1 << 20),
                append_mode=append_mode,
                merge_mode=merge_mode,
                memtable_kind=memtable_kind
                or getattr(self.config, "memtable_kind", "time_partition"),
                flush_workers=getattr(self.config, "ingest_flush_workers", 2),
            )
            self._wire_ingest(region)
            self._regions[region_id] = region
            return region

    def open_region(
        self, region_id: int, append_mode: bool = False, memtable_kind: str | None = None,
        merge_mode: str | None = None,
    ) -> Region:
        """Open an existing region from its manifest + WAL (crash recovery)."""
        with self._lock:
            if region_id in self._regions:
                return self._regions[region_id]
            store = self._region_store(region_id)
            if not store.list("manifest"):
                raise RegionNotFoundError(f"region {region_id} has no manifest")
            region = Region(
                region_id,
                store,
                Schema(columns=[]),  # overwritten by manifest recovery
                self.wal_mgr.region_wal(region_id),
                time_partition_ms=self.config.memtable_time_partition_secs * 1000,
                checkpoint_distance=self.config.manifest_checkpoint_distance,
                index_enable=self.config.index_enable,
                index_segment_rows=self.config.index_segment_rows,
                index_inverted_max_terms=self.config.index_inverted_max_terms,
                index_segmented=getattr(self.config, "index_segmented", True),
                index_segment_terms=getattr(self.config, "index_segment_terms", 512),
                index_max_terms=getattr(self.config, "index_max_terms", 1 << 20),
                append_mode=append_mode,
                merge_mode=merge_mode,
                memtable_kind=memtable_kind
                or getattr(self.config, "memtable_kind", "time_partition"),
                flush_workers=getattr(self.config, "ingest_flush_workers", 2),
            )
            self._wire_ingest(region)
            self._regions[region_id] = region
            return region

    def _wire_ingest(self, region: Region):
        """Flush-overlapped ingest (ingest.flush_overlap): give the region
        the write-buffer manager so freezing a memtable moves its bytes
        out of the mutable budget for the duration of the encode.  Off =
        no hook = pre-overlap stall accounting bit-for-bit."""
        if getattr(self.config, "ingest_flush_overlap", True):
            region.buffer_mgr = self.buffer_mgr

    def close_region(self, region_id: int):
        with self._lock:
            region = self._regions.pop(region_id, None)
        if region is not None and not region.writable:
            # a closing follower must stop pinning the shared-WAL tail
            region.release_follower_watermark()
        self.buffer_mgr.remove_region(region_id)

    def drop_region(self, region_id: int):
        self.close_region(region_id)
        self.wal_mgr.drop_region(region_id)
        store = self._region_store(region_id)
        for sub in ("manifest", "sst"):
            view = store.scoped(sub)
            for name in view.list():
                view.delete(name)
        shutil.rmtree(self._region_dir(region_id), ignore_errors=True)

    def region(self, region_id: int) -> Region:
        region = self._regions.get(region_id)
        if region is None:
            raise RegionNotFoundError(f"region {region_id} not found")
        return region

    def region_ids(self) -> list[int]:
        with self._lock:
            return list(self._regions)

    # ---- request routing --------------------------------------------------
    def write(self, region_id: int, batch: pa.RecordBatch, stages: dict | None = None) -> int:
        region = self.region(region_id)
        self._relieve_stall()
        rows = region.write(batch, stages)
        self._post_write(region_id, region)
        return rows

    def write_group(
        self, region_id: int, batches: list[pa.RecordBatch], stages: dict | None = None
    ) -> list[int]:
        """Group-commit write (ingest.group_commit): one WAL frame for the
        whole group, per-write entry ids and row counts.  Same stall /
        flush-pressure envelope as `write`."""
        region = self.region(region_id)
        self._relieve_stall()
        rows = region.write_group(batches, stages)
        self._post_write(region_id, region)
        return rows

    def _relieve_stall(self):
        """Under pressure: flush the biggest offenders synchronously
        instead of rejecting (single-process analogue of stalling).  The
        seconds the write waits here are `greptime_mito_write_stall_seconds_total`."""
        if not self.buffer_mgr.should_stall():
            return
        metrics.WRITE_STALL_TOTAL.inc()
        for rid in self.buffer_mgr.pick_flush_candidates():
            self.flush_region(rid, cause="stall")
            if not self.buffer_mgr.should_stall():
                break

    def _post_write(self, region_id: int, region: Region):
        self.buffer_mgr.set_region_usage(region_id, region.memtable.memory_usage)
        if self.buffer_mgr.should_flush_region(region_id) or self.buffer_mgr.should_flush_engine():
            # threshold flush runs OFF the write path (reference
            # FlushScheduler); stall flushes above stay synchronous
            if self.flusher is not None:
                self.flusher.schedule(region_id)
            else:
                self.flush_region(region_id, cause="threshold")

    def delete(self, region_id: int, keys: pa.Table) -> int:
        """Tombstone-delete rows by (primary key, time index) keys.
        Tombstones are memtable writes too, so the same stall/flush
        backpressure as `write` applies."""
        region = self.region(region_id)
        self._relieve_stall()
        deleted = region.delete(keys)
        self._post_write(region_id, region)
        return deleted

    def truncate_region(self, region_id: int):
        self.region(region_id).truncate()
        self.buffer_mgr.set_region_usage(region_id, 0)

    def flush_region(self, region_id: int, cause: str = "manual"):
        """`cause` names who asked, for the `flush.region` stage: `stall`
        (a foreground write under pressure), `threshold` (the write buffer's
        limits, off the write path where the flush scheduler runs) or
        `manual` (ADMIN, `flush_all`, migration, close)."""
        region = self._regions.get(region_id)
        if region is None:
            return
        added = region.flush(cause)
        self.buffer_mgr.set_region_usage(region_id, region.memtable.memory_usage)
        if added and self.compactor is not None:
            self.compactor.notify_flush(region_id)
        if added:
            # delta notification: listeners learn WHICH SSTs the flush
            # appended, so tile maintenance can size its delta work (the
            # incremental super-tile build merges exactly these files'
            # rows instead of rebuilding from scratch)
            ids = [m.file_id for m in added]
            metrics.TILE_FLUSH_DELTA_FILES.inc(len(ids))
            for cb in list(self.flush_listeners):
                try:
                    cb(region_id)
                except Exception:  # noqa: BLE001 — listeners are advisory
                    pass
            for cb in list(self.delta_listeners):
                try:
                    cb(region_id, ids)
                except Exception:  # noqa: BLE001 — listeners are advisory
                    pass

    def flush_all(self):
        """Every region's rows in SSTs on return.  The background flushes
        are waited out however long they take: `wait_idle`'s default of
        30 s is for tests and shutdown, and a bulk load's backlog outlasts
        it (a logical-table load of 10 M rows: `flush_s` read 30.0 s in
        every run, the cap), which left rows in a frozen memtable behind a
        caller that had been told they were flushed."""
        if self.flusher is not None:
            self.flusher.wait_idle(timeout=float("inf"))
        for rid in self.region_ids():
            self.flush_region(rid)

    def scan(
        self,
        region_id: int,
        pred: ScanPredicate | None = None,
        columns: list[str] | None = None,
    ) -> pa.Table:
        return self.region(region_id).scan(pred, columns)

    def region_statistics(self) -> list[RegionStat]:
        return [r.stat() for r in list(self._regions.values())]

    # ---- follower freshness -----------------------------------------------
    def sync_followers(self) -> dict[int, int]:
        """One WAL-tail + manifest-refresh round over every READ-ONLY
        region this engine hosts; returns {region_id: entries_applied}.
        Failures are per-region and transient by contract (shared-storage
        weather, a segment pruned mid-replay): the round records them and
        the next round resumes from the persisted applied position."""
        import logging

        out: dict[int, int] = {}
        for region in list(self._regions.values()):
            if region.writable:
                continue
            try:
                applied, _refreshed = region.follower_sync()
            except Exception as exc:  # noqa: BLE001 — next round retries
                metrics.FOLLOWER_SYNC_FAILURES_TOTAL.inc()
                logging.getLogger("greptimedb_tpu.engine").warning(
                    "follower sync of region %s failed: %s",
                    region.region_id, exc,
                )
                continue
            out[region.region_id] = applied
        return out

    # ---- helpers ----------------------------------------------------------
    def _region_dir(self, region_id: int) -> str:
        return os.path.join(self.config.effective_sst_dir(), f"region_{region_id}")

    def _region_store(self, region_id: int):
        return self.object_store.scoped(f"region_{region_id}")

    @property
    def workers(self):
        """Sharded single-writer-per-region loops with request batching
        (reference mito2/src/worker.rs WorkerGroup); created on first use
        so simple embedded engines never spawn threads."""
        if self._workers is None:
            from .worker import WorkerGroup

            with self._lock:
                if self._workers is None:
                    self._workers = WorkerGroup(
                        self, num_workers=self.config.num_workers
                    )
        return self._workers

    def submit_write(self, region_id: int, batch: pa.RecordBatch, stages: dict | None = None):
        """Queue a write on the region's worker loop; returns a Future of
        affected rows (pipelined ingest: protocol servers overlap decode
        of the next request with this write's WAL+memtable apply).  The
        caller's `stages` dict is filled before the future resolves."""
        return self.workers.submit_write(region_id, batch, stages)

    def pending_writes(self, region_id: int) -> bool:
        """True when the region's worker loop has queued requests — i.e.
        a submitted write would coalesce into a drain group (WAL group
        commit) rather than run solo.  Never spawns the worker threads:
        no workers yet means nothing is pending."""
        if self._workers is None:
            return False
        return not self._workers._worker_for(region_id).queue.empty()

    def scan_stream(
        self,
        region_id: int,
        pred: ScanPredicate | None = None,
        columns: list[str] | None = None,
        governor=None,
    ):
        """Bounded-memory streaming scan: k-way merge over per-source
        sorted streams (Region.scan_merge_stream — one row group per source
        in memory), with the scan governor admitting each emitted batch."""
        for chunk in self.region(region_id).scan_merge_stream(pred, columns):
            if governor is not None:
                with governor.scan_guard(chunk.nbytes):
                    yield chunk
            else:
                yield chunk

    def close(self):
        if self.follower_syncer is not None:
            self.follower_syncer.stop()
        if self._workers is not None:
            self._workers.stop()
        if self.flusher is not None:
            self.flusher.stop()
        if self.compactor is not None:
            self.compactor.stop()
        for rid in self.region_ids():
            region = self._regions.get(rid)
            if region is not None and not region.writable:
                region.release_follower_watermark()
        self.wal_mgr.close()
