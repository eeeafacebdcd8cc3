"""Region: the unit of storage, replication and scan parallelism.

Role-equivalent of the reference's `MitoRegion` (reference
src/mito2/src/region.rs:121) plus its opener (region/opener.rs): a region
owns a WAL stream, an active memtable, a set of immutable SSTs tracked by a
manifest, and a monotonically increasing sequence number.  Writes go
WAL-then-memtable (reference worker/handle_write.rs:83-135); flush turns the
memtable into time-window-aligned SSTs and advances `flushed_entry_id` so
the WAL can be truncated; open replays manifest then WAL from
`flushed_entry_id` (reference region/opener.rs:500-516).

Concurrency model: like the reference's single-writer-per-region actor
(worker.rs:459), all mutations take the region write lock; scans only read
immutable snapshots (memtable materialization + SST list copy).
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass

import numpy as np
import pyarrow as pa

from ..datatypes.schema import Schema
from ..utils import metrics, tracing
from ..utils.deadline import check_deadline
from ..utils.errors import IllegalStateError, RegionReadonlyError
from .manifest import ManifestManager
from .memtable import Memtable, make_memtable
from .sst import FileMeta, ScanPredicate, SstReader, SstWriter
from .wal import RegionWal

# Per-row operation marker carried through memtable, WAL and SSTs
# (reference api::v1::OpType / mito2 key-value op types): 0 = put,
# 1 = delete tombstone.  Tombstones win dedup (they carry a later
# sequence) and are dropped from scan output; they persist through
# flush/compaction so deletes survive restarts and file merges.
OP_COL = "__op"
OP_PUT = 0
OP_DELETE = 1


@dataclass
class RegionStat:
    region_id: int
    num_rows: int
    sst_count: int
    sst_bytes: int
    memtable_bytes: int
    wal_entry_id: int
    flushed_entry_id: int
    # follower-replica fields (ride heartbeat stats to the metasrv so the
    # frontend can gate hedging on staleness): lag_ms is milliseconds since
    # the last successful WAL-tail sync; lag_entries is best-effort (the
    # log head is only observed at sync time)
    writable: bool = True
    follower_lag_entries: int = 0
    follower_lag_ms: float = 0.0


class Region:
    def __init__(
        self,
        region_id: int,
        region_dir: str,
        schema: Schema,
        wal: RegionWal,
        *,
        time_partition_ms: int = 86_400_000,
        checkpoint_distance: int = 10,
        writable: bool = True,
        index_enable: bool = True,
        index_segment_rows: int = 1024,
        index_inverted_max_terms: int = 4096,
        index_segmented: bool = True,
        index_segment_terms: int = 512,
        index_max_terms: int = 1 << 20,
        append_mode: bool = False,
        merge_mode: str | None = None,
        memtable_kind: str = "time_partition",
        flush_workers: int = 1,
    ):
        from .object_store import FsObjectStore, ObjectStore

        self.region_id = region_id
        # `region_dir` may be a local path (standalone default) or an
        # ObjectStore view for this region (reference: SSTs+manifest live on
        # object storage; only the WAL is local).
        if isinstance(region_dir, ObjectStore):
            self.store: ObjectStore = region_dir
            self.region_dir = None
        else:
            self.store = FsObjectStore(region_dir)
            self.region_dir = region_dir
        self.wal = wal
        self.time_partition_ms = time_partition_ms
        self._lock = threading.RLock()
        self.writable = writable  # follower replicas are read-only
        # Serializes compaction drivers (background scheduler vs ADMIN
        # compact_table): two concurrent rounds would pick the same L0
        # group and commit the merged rows twice.
        self.compaction_lock = threading.Lock()
        # Dedup strategy (reference mito2 `merge_mode` table option):
        # "last_row" keeps the newest version whole; "last_non_null"
        # merges fieldwise — the newest NON-NULL value per field wins
        # (read/dedup.rs LastNonNull).
        self.merge_mode = merge_mode or "last_row"
        # Append-only mode (reference mito2 `append_mode` table option):
        # duplicates are kept (no last-write-wins dedup) and DELETE is
        # rejected — the shape log/trace workloads want, and the condition
        # under which the device tile cache can aggregate SSTs directly.
        self.append_mode = append_mode

        self.manifest_mgr = ManifestManager(self.store, region_id, checkpoint_distance)
        if self.manifest_mgr.manifest.schema is None:
            self.manifest_mgr.apply({"kind": "change", "schema": schema.to_json()})
        self.schema = self.manifest_mgr.manifest.schema
        sst_store = self.store.scoped("sst")
        self.sst_writer = SstWriter(
            sst_store,
            self.schema,
            index_enable=index_enable,
            index_segment_rows=index_segment_rows,
            index_inverted_max_terms=index_inverted_max_terms,
            index_segmented=index_segmented,
            index_segment_terms=index_segment_terms,
            index_max_terms=index_max_terms,
        )
        self.sst_reader = SstReader(sst_store, self.schema)

        self.memtable_kind = memtable_kind
        self.memtable = make_memtable(self.schema, time_partition_ms, memtable_kind)
        # Frozen memtables: flushed but whose SSTs are not yet committed to the
        # manifest; readable by scans so flush never opens a visibility gap.
        self._frozen_memtables: list[Memtable] = []
        # SSTs removed from the manifest but not yet safe to delete (readers
        # in flight may hold the old file list); purged when readers drain.
        # (file_id, tombstoned_at): physical deletion waits out BOTH
        # local in-flight scans AND a wall-clock grace, because ANOTHER
        # region holder (transient split-brain during failover, or a
        # second process on shared storage) may still scan from an older
        # manifest snapshot that references these files (the reference's
        # file purger + object-store GC grace plays the same role)
        self._garbage_files: list[tuple[str, float]] = []
        self.gc_grace_secs: float = 60.0
        self._active_scans = 0
        self.sequence = self.manifest_mgr.manifest.flushed_sequence
        # Future WAL entry ids must exceed the flush watermark, else writes
        # after an obsolete()+restart would replay below it and be lost.
        self.wal.advance_to(
            max(
                self.manifest_mgr.manifest.flushed_entry_id,
                self.manifest_mgr.manifest.truncated_entry_id or 0,
            )
        )
        # Replay progress marker: the highest WAL entry id applied to this
        # region's memtable.  Leaders advance it on every write; followers
        # advance it as follower_sync() tails the shared log, and the
        # shared-WAL prune keeps everything a registered follower has not
        # yet applied.
        self.applied_entry_id = 0
        self.last_sync_ms = time.time() * 1000
        # set once the follower watermark is released (close/promotion);
        # an in-flight sync round must not re-pin the shared log after it
        self._lw_released = False
        # Pipelined ingest: parallel per-SST flush encode pool width and the
        # optional write-buffer freeze hook (set by the engine when
        # ingest.flush_overlap is on — flush moves the frozen memtable's
        # bytes out of the mutable budget so writes keep flowing during
        # the encode).
        # clamp to REAL cores: on a 1-core box the pool (and the window
        # slicing keyed off it) is pure overhead — more files, more index
        # builds, zero parallelism
        try:
            cores = len(os.sched_getaffinity(0))
        except AttributeError:  # non-linux
            cores = os.cpu_count() or 1
        self.flush_workers = max(1, min(flush_workers, cores))
        self.buffer_mgr = None
        self._conform_cache: tuple | None = None
        self._replay_wal()

    # ---- open/replay ------------------------------------------------------
    def _replay_wal(self):
        """Replay WAL entries newer than flushed_entry_id into the memtable."""
        flushed = self.manifest_mgr.manifest.flushed_entry_id
        truncated = self.manifest_mgr.manifest.truncated_entry_id or 0
        start = max(flushed, truncated)
        last = start
        replayed = 0
        for entry in self.wal.replay(start):
            self.sequence += 1
            self.memtable.write(self._conform(entry.batch), self.sequence)
            last = entry.entry_id
            replayed += entry.batch.num_rows
        self.applied_entry_id = last
        return replayed

    # ---- write ------------------------------------------------------------
    def write(self, batch: pa.RecordBatch, stages: dict | None = None) -> int:
        """WAL append then memtable insert; returns affected rows.  A
        caller's `stages` dict takes what the two stages measured
        (`wal_ms`, `memtable_ms`): the `write.region` span's attributes."""
        with self._lock:
            # the writable check lives INSIDE the lock: set_writable(False)
            # (migration downgrade) takes the same lock, so once the fence
            # returns, no in-flight write can still append to the WAL the
            # migration candidate is about to replay
            if not self.writable:
                raise RegionReadonlyError(f"region {self.region_id} is read-only")
            batch = self._conform(batch)
            with tracing.stage("write.wal", bytes=batch.nbytes, group=1) as wal:
                self.wal.append(batch)
            with tracing.stage("write.memtable", rows=batch.num_rows) as mem:
                self.sequence += 1
                self.memtable.write(batch, self.sequence)
            self.applied_entry_id = self.wal.last_entry_id
        self._note_write(wal, mem, stages)
        metrics.INGEST_WRITES_TOTAL.inc()
        metrics.WRITE_ROWS_TOTAL.inc(batch.num_rows)
        return batch.num_rows

    def write_group(
        self, batches: list[pa.RecordBatch], stages: dict | None = None
    ) -> list[int]:
        """Group commit (ingest.group_commit): one WAL frame for a whole
        region-worker drain group, one entry id AND one sequence per write
        — live state equals a crash replay of the same frame entry for
        entry.  Returns per-write affected row counts in order; `stages`
        as in `write`, with `group_writes`."""
        from ..utils import fault_injection

        if not batches:
            return []
        with self._lock:
            if not self.writable:
                raise RegionReadonlyError(f"region {self.region_id} is read-only")
            fault_injection.fire(
                "ingest.group_commit", region_id=self.region_id, n=len(batches)
            )
            conformed = [self._conform(b) for b in batches]
            rows = [b.num_rows for b in conformed]
            with tracing.stage(
                "write.wal", bytes=sum(b.nbytes for b in conformed), group=len(batches)
            ) as wal:
                append_group = getattr(self.wal, "append_group", None)
                if append_group is not None:
                    append_group(conformed)
                else:  # a WAL impl without group frames: per-write appends
                    for b in conformed:
                        self.wal.append(b)
            with tracing.stage("write.memtable", rows=sum(rows)) as mem:
                # one sequence per write, exactly like replay assigns them
                for b in conformed:
                    self.sequence += 1
                    self.memtable.write(b, self.sequence)
            self.applied_entry_id = self.wal.last_entry_id
        self._note_write(wal, mem, stages, group_writes=len(batches))
        metrics.INGEST_WRITES_TOTAL.inc(len(batches))
        metrics.WRITE_ROWS_TOTAL.inc(sum(rows))
        return rows

    @staticmethod
    def _note_write(wal, mem, stages: dict | None, **more):
        """The two histograms observe what the stages measured (one clock)."""
        wal_ms, mem_ms = wal.duration_s * 1000, mem.duration_s * 1000
        metrics.INGEST_WAL_MS.observe(wal_ms)
        metrics.INGEST_MEMTABLE_MS.observe(mem_ms)
        if stages is not None:
            stages.update(wal_ms=wal_ms, memtable_ms=mem_ms, **more)

    def _conform(self, batch: pa.RecordBatch) -> pa.RecordBatch:
        """Project a write onto the region's current schema (+ the __op
        marker): a batch built against an older (narrower) schema gets nulls
        for columns added by a concurrent ALTER, puts without a marker get
        __op=0, and columns come out in schema order so every memtable chunk
        shares one schema (the reference's write-compat shim,
        mito2/src/read/compat.rs, does this on read instead)."""
        cache = self._conform_cache
        if cache is None or cache[0] is not self.schema:
            # keyed on schema object identity: ALTER/manifest refresh swap
            # the Schema instance, invalidating the cached Arrow target
            target = self.schema.to_arrow().append(pa.field(OP_COL, pa.int8()))
            self._conform_cache = (self.schema, target)
        else:
            target = cache[1]
        if batch.schema.equals(target):
            return batch
        n = batch.num_rows
        arrays = []
        for f in target:
            i = batch.schema.get_field_index(f.name)
            if i >= 0:
                col = batch.column(i)
                arrays.append(col if col.type == f.type else col.cast(f.type))
            elif f.name == OP_COL:
                arrays.append(pa.array(np.zeros(n, dtype=np.int8)))
            else:
                arrays.append(pa.nulls(n, f.type))
        return pa.RecordBatch.from_arrays(arrays, schema=target)

    def delete(self, keys: pa.Table | pa.RecordBatch) -> int:
        """Delete by key: `keys` carries the primary-key + time-index columns
        of the rows to remove.  Writes tombstone rows (__op=1) through the
        normal WAL/memtable path — _conform null-fills the field columns —
        and dedup hides the victims immediately (reference mito2 handles
        OpType::Delete the same way)."""
        if self.append_mode:
            from ..utils.errors import UnsupportedError

            raise UnsupportedError("DELETE is not supported on append_mode tables")
        if isinstance(keys, pa.Table):
            keys = keys.combine_chunks()
            batches = keys.to_batches()
        else:
            batches = [keys]
        deleted = 0
        for b in batches:
            if b.num_rows == 0:
                continue
            op = pa.array(np.full(b.num_rows, OP_DELETE, dtype=np.int8))
            self.write(b.append_column(pa.field(OP_COL, pa.int8()), op))
            deleted += b.num_rows
        return deleted

    # ---- flush ------------------------------------------------------------
    def flush(self, cause: str = "manual") -> list[FileMeta]:
        """Freeze the memtable, write one SST per time window, commit the
        manifest edit, truncate WAL.  The frozen memtable stays scannable
        (in _frozen_memtables) until the manifest edit lands, so concurrent
        scans never see the flush-in-progress rows vanish.  `cause` is why
        the engine asked: `stall` (a foreground write waits for it),
        `threshold` or `manual`."""
        if self.memtable.is_empty():
            return []  # no stage for a flush with nothing to do (`flush_all`)
        with tracing.stage("flush.region", cause=cause, region=self.region_id) as st:
            added, committed = self._flush(st)
        if committed:
            metrics.FLUSH_TOTAL.inc()
            metrics.FLUSH_ELAPSED.observe(st.duration_s)
        if cause == "stall":
            metrics.WRITE_STALL_S.inc(st.duration_s)
        return added

    def _flush(self, st) -> tuple[list[FileMeta], bool]:
        """(files added, whether the edit was committed)."""
        with self._lock:
            if self.memtable.is_empty():
                return [], False
            frozen = self.memtable
            frozen_bytes = frozen.memory_usage
            frozen_entry_id = self.wal.last_entry_id
            frozen_sequence = self.sequence
            self.memtable = make_memtable(self.schema, self.time_partition_ms, self.memtable_kind)
            self._frozen_memtables.append(frozen)
            if self.buffer_mgr is not None:
                # flush overlap (ingest.flush_overlap): the frozen bytes
                # leave the MUTABLE budget now, so new writes are admitted
                # while this encode runs; the flushing bucket keeps the
                # total bounded (see WriteBufferManager.should_stall)
                self.buffer_mgr.freeze_region(self.region_id, frozen_bytes)
        try:
            # no counter of its own: its time stays with `flush.region`,
            # `flush.sort` and the encode pool's stages
            with tracing.stage("flush.windows") as windows:
                added = self._encode_sst_windows(frozen)
        finally:
            if self.buffer_mgr is not None:
                self.buffer_mgr.unfreeze_region(self.region_id, frozen_bytes)
        metrics.INGEST_FLUSH_ENCODE_MS.observe(windows.duration_s * 1000)
        metrics.FLUSH_SST_BYTES.inc(sum(m.stored_bytes for m in added))
        st.set(rows=sum(m.num_rows for m in added), files=len(added))
        with self._lock:
            truncated = self.manifest_mgr.manifest.truncated_entry_id or 0
            if truncated >= frozen_entry_id:
                # a TRUNCATE landed while the SSTs were being written: the
                # frozen rows are logically gone — discard the files instead
                # of committing them (the reference versions flushes against
                # the truncate watermark the same way)
                if frozen in self._frozen_memtables:
                    self._frozen_memtables.remove(frozen)
                self._garbage_files.extend(
                    (m.file_id, time.time()) for m in added
                )
                self._purge_garbage_locked()
                return [], False
            self.manifest_mgr.apply(
                {
                    "kind": "edit",
                    "files_to_add": [m.to_dict() for m in added],
                    "files_to_remove": [],
                    "flushed_entry_id": frozen_entry_id,
                    "flushed_sequence": frozen_sequence,
                }
            )
            self._frozen_memtables.remove(frozen)
        self.wal.obsolete(frozen_entry_id)
        return added, True

    # Rows per SST slice when one time window dominates a flush: a
    # window's sorted run splits into consecutive slices (disjoint key
    # ranges by construction) so the encode pool has work even when the
    # whole flush lands in ONE window (the TSBS shape: days-wide
    # partitions, minutes-wide flushes).
    _FLUSH_SLICE_ROWS = 1 << 20

    def _encode_sst_windows(self, frozen: Memtable) -> list[FileMeta]:
        """Encode the frozen memtable's time windows into SSTs — in
        parallel over `flush_workers` (ingest.flush_workers; Parquet
        encode and index builds release the GIL, so the pool overlaps
        real work).  Big single-window flushes slice their sorted run
        into consecutive ~1M-row SSTs: slices of a sorted table cover
        disjoint (pk, ts) ranges, so downstream merge/dedup treats them
        exactly like any other L0 run split.  Output order stays window
        order (slices in run order), so manifest positions are
        deterministic."""
        with tracing.stage("flush.sort"):
            parts = frozen.split_by_time_partition(
                # last_non_null must NOT last-row-dedup on flush: older
                # versions' non-null fields are still live until the READ-side
                # fieldwise merge combines them
                dedup=not self.append_mode and self.merge_mode != "last_non_null"
            )
        tables: list[pa.Table] = []
        for _w, t in parts:
            if (self.flush_workers > 1
                    and t.num_rows > 2 * self._FLUSH_SLICE_ROWS):
                step = self._FLUSH_SLICE_ROWS
                tables.extend(
                    t.slice(off, step) for off in range(0, t.num_rows, step)
                )
            else:
                tables.append(t)
        if self.flush_workers > 1 and len(tables) > 1:
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(
                max_workers=min(self.flush_workers, len(tables)),
                thread_name_prefix=f"flush-encode-{self.region_id}",
            ) as ex:
                metas = list(ex.map(
                    lambda t: self.sst_writer.write(t, level=0), tables
                ))
        else:
            metas = [self.sst_writer.write(t, level=0) for t in tables]
        return [m for m in metas if m is not None]

    # ---- compaction hook (files swapped by CompactionScheduler) -----------
    def apply_compaction(
        self, files_to_add: list[FileMeta], files_to_remove: list[str]
    ) -> bool:
        """Commit a compaction edit.  The output is INSERTED at the newest
        input's manifest position (not appended): flushes landing DURING
        the merge stay newer, so last-write-wins order — which scans judge
        by manifest position — survives concurrent overwrites.  Returns
        False (caller discards the output) when the commit would be
        unsound: an input vanished, or a file outside the group that
        time-overlaps an input sits BETWEEN input positions — one output
        position cannot rank above its older inputs yet below such an
        interleaved outsider (the reference dedups by persisted per-row
        sequences instead; mito2/src/read/dedup.rs)."""
        with self._lock:
            order = list(self.manifest_mgr.manifest.files)
            pos = {fid: i for i, fid in enumerate(order)}
            metas = self.manifest_mgr.manifest.files
            in_pos = sorted(
                pos[fid] for fid in files_to_remove if fid in pos
            )
            if len(in_pos) != len(files_to_remove):
                return False  # an input left the manifest mid-merge
            anchor = order[in_pos[-1]] if in_pos else None
            if not self.append_mode and len(in_pos) > 1:
                from .sst import interleaved_overlap_unsafe

                inputs = [metas[fid] for fid in files_to_remove]
                if interleaved_overlap_unsafe(
                    inputs, list(metas.values()), pos
                ):
                    return False
            self.manifest_mgr.apply(
                {
                    "kind": "edit",
                    "files_to_add": [m.to_dict() for m in files_to_add],
                    "files_to_remove": files_to_remove,
                    "insert_at": anchor,
                }
            )
            # Defer physical deletion: in-flight scans may hold the old file
            # list (the reference defers via a file purger + refcounts).
            self._garbage_files.extend(
                (fid, time.time()) for fid in files_to_remove
            )
            self._purge_garbage_locked()
        metrics.COMPACTION_TOTAL.inc()
        return True

    def _purge_garbage_locked(self):
        if self._active_scans > 0 or not self._garbage_files:
            return
        now = time.time()
        keep: list[tuple[str, float]] = []
        for fid, t0 in self._garbage_files:
            if now - t0 >= self.gc_grace_secs:
                self.sst_reader.delete(fid)
            else:
                keep.append((fid, t0))
        self._garbage_files = keep

    # ---- read -------------------------------------------------------------
    def scan(
        self,
        pred: ScanPredicate | None = None,
        columns: list[str] | None = None,
    ) -> pa.Table:
        """Snapshot scan: SSTs (pruned) + frozen + active memtables, dedup
        last-write-wins across sources.  Memtable rows shadow SST rows for
        equal (pk, ts) because they carry later sequences."""
        pred = pred or ScanPredicate()
        with self._lock:
            files = list(self.manifest_mgr.manifest.files.values())
            mems = list(self._frozen_memtables) + [self.memtable]
            self._active_scans += 1
        try:
            # Filters on key columns (tags + time index) are dedup-safe for
            # pruning/pre-filtering: a newer version of a row (overwrite or
            # tombstone) has the same key, so both versions pass or fail
            # together.  Filters on FIELD columns must wait until after
            # cross-source dedup — a stale SST row could pass a field filter
            # while its memtable replacement (new value / tombstone with null
            # fields) fails it, resurrecting overwritten data (the reference
            # orders DedupReader before filter eval the same way).
            key_cols = set(c.name for c in self.schema.tag_columns())
            if self.schema.time_index is not None:
                key_cols.add(self.schema.time_index.name)
            key_filters = [f for f in pred.filters if f[0] in key_cols]
            post_filters = [f for f in pred.filters if f[0] not in key_cols]
            # append_mode has no dedup, so FIELD filters (incl. fulltext
            # match) may prune files/segments too — dropping a non-matching
            # row can never resurrect an older version when versions don't
            # shadow each other (the logs fast path: matches() + fulltext
            # index pruning before any Parquet decode)
            prune_filters = list(pred.filters) if self.append_mode else key_filters
            prune_pred = ScanPredicate(time_range=pred.time_range, filters=prune_filters)

            # Projection pushdown: read only requested columns plus the
            # pk/ts/__op columns dedup needs; final select() trims extras.
            read_cols = None
            if columns:
                need = list(dict.fromkeys(columns))
                for c in self.schema.primary_key():
                    if c not in need:
                        need.append(c)
                if self.schema.time_index and self.schema.time_index.name not in need:
                    need.append(self.schema.time_index.name)
                for name, _op, _v in pred.filters:
                    if self.schema.has_column(name) and name not in need:
                        need.append(name)
                need.append(OP_COL)
                read_cols = need
            tables = []
            for meta in self.sst_reader.prune_files(files, prune_pred):
                check_deadline()
                t = self.sst_reader.read(meta, prune_pred, columns=read_cols)
                if t.num_rows:
                    tables.append(self._compat_cast(_undict(t)))
            n_sst_tables = len(tables)
            from .sst import _apply_residual

            ts_name = self.schema.time_index.name if self.schema.time_index else None
            mem_rows = 0
            keep_versions = self.merge_mode == "last_non_null"
            for mem in mems:
                mem_table = mem.scan(
                    pred.time_range,
                    dedup=not self.append_mode and not keep_versions,
                )
                if mem_table.num_rows:
                    mem_table = _apply_residual(mem_table, prune_pred, ts_name)
                if mem_table.num_rows:
                    if read_cols:
                        mem_table = mem_table.select(
                            [c for c in read_cols if c in mem_table.column_names]
                        )
                    mem_rows += mem_table.num_rows
                    tables.append(_undict(mem_table))
            if not tables:
                out = self.schema.to_arrow().empty_table()
            else:
                out = pa.concat_tables(tables, promote_options="permissive")
                out = self._dedup_across_sources(
                    out,
                    had_multiple=len(tables) > 1
                    or (n_sst_tables and mem_rows)
                    or self.merge_mode == "last_non_null",
                )
                out = self._drop_tombstones(out)
                if post_filters:
                    out = _apply_residual(
                        out, ScanPredicate(filters=post_filters), None
                    )
            # schema evolution: columns added by ALTER after this data was
            # written materialize as NULL (reference mito2/src/read/compat.rs
            # fills missing columns with default vectors at read)
            for c in self.schema.columns:
                if c.name not in out.column_names:
                    out = out.append_column(
                        c.name, pa.nulls(out.num_rows, c.data_type.to_arrow())
                    )
            if columns:
                out = out.select([c for c in columns if c in out.column_names])
            else:
                # normalize to the CURRENT schema: old SSTs may still carry
                # columns dropped by ALTER
                want = [c for c in self.schema.column_names() if c in out.column_names]
                if want != out.column_names:
                    out = out.select(want)
            return out
        finally:
            with self._lock:
                self._active_scans -= 1
                self._purge_garbage_locked()

    def scan_windows(
        self,
        pred: ScanPredicate | None = None,
        columns: list[str] | None = None,
        window_ms: int | None = None,
        governor=None,
    ):
        """Bounded-memory streaming scan: yield one time window at a time.

        The reference streams via PartitionRanges (mito2/src/read/range.rs +
        seq_scan.rs); here the partition unit is the memtable time-partition
        window.  Correctness: dedup keys include the time index, so a
        (pk, ts) duplicate lives in exactly ONE window — per-window
        sort+dedup equals the global pass.  Peak memory is one window's
        rows, admitted against `governor.scan_guard` when provided."""
        pred = pred or ScanPredicate()
        w = window_ms or self.time_partition_ms
        with self._lock:
            files = list(self.manifest_mgr.manifest.files.values())
            mems = list(self._frozen_memtables) + [self.memtable]
            self._active_scans += 1
        try:
            # window set from file metas + memtable ranges, intersected with
            # the predicate's time range
            starts: set[int] = set()
            lo_q, hi_q = pred.time_range if pred.time_range else (None, None)

            def add_range(lo, hi):
                lo = lo if lo_q is None else max(lo, lo_q)
                hi = hi if hi_q is None else min(hi, hi_q - 1)
                if hi < lo:
                    return
                s = (lo // w) * w
                while s <= hi:
                    starts.add(s)
                    s += w
            for fm in files:
                add_range(*fm.time_range)
            for mem in mems:
                r = mem.time_range()
                if r is not None:
                    add_range(*r)
            if self.schema.time_index is None:
                # no time index: single-shot fallback
                yield self.scan(pred, columns)
                return
            for s in sorted(starts):
                win_pred = ScanPredicate(
                    time_range=(
                        max(s, lo_q) if lo_q is not None else s,
                        min(s + w, hi_q) if hi_q is not None else s + w,
                    ),
                    filters=pred.filters,
                )
                chunk = self.scan(win_pred, columns)
                if chunk.num_rows == 0:
                    continue
                if governor is not None:
                    with governor.scan_guard(chunk.nbytes):
                        yield chunk
                else:
                    yield chunk
        finally:
            with self._lock:
                self._active_scans -= 1
                self._purge_garbage_locked()

    def _compat_cast(self, table: pa.Table) -> pa.Table:
        """Adapt an old SST to the CURRENT schema (reference
        mito2/src/read/compat.rs): cast columns to the declared type after
        ALTER ... MODIFY COLUMN, and null out name-collisions whose stored
        column_id differs — data of a DROPped column must not resurrect when
        a new column reuses its name."""
        import pyarrow.compute as pc

        for col in self.schema.columns:
            i = table.schema.get_field_index(col.name)
            if i < 0:
                continue
            fmeta = table.schema.field(i).metadata or {}
            stored_id = int(fmeta.get(b"greptime:column_id", 0))
            want = col.data_type.to_arrow()
            if stored_id and col.column_id and stored_id != col.column_id:
                table = table.set_column(
                    i, col.to_arrow(), pa.nulls(table.num_rows, want)
                )
            elif table.schema.field(i).type != want:
                table = table.set_column(
                    i, col.name, pc.cast(table.column(i), want)
                )
        return table

    @staticmethod
    def _drop_tombstones(table: pa.Table) -> pa.Table:
        """Remove delete markers from scan output (rows from pre-__op files
        have a null marker and count as puts)."""
        if OP_COL not in table.column_names:
            return table
        import pyarrow.compute as pc

        op = pc.fill_null(pc.cast(table[OP_COL], pa.int8()), OP_PUT)
        table = table.filter(pc.equal(op, OP_PUT))
        return table.drop_columns([OP_COL])

    def _dedup_across_sources(self, table: pa.Table, had_multiple: bool) -> pa.Table:
        if not had_multiple or table.num_rows <= 1:
            return table
        # Order sources oldest->newest (SSTs then memtable appended last);
        # reuse memtable sort+dedup with the append order as sequence.
        # append_mode keeps duplicates but still sorts by (pk, ts) so
        # downstream consumers (PromQL, range kernels) see ordered series.
        import numpy as np

        if self.merge_mode == "last_non_null" and not self.append_mode:
            from .merge import _SEQ, _dedup_chunk

            key_cols = [c.name for c in self.schema.tag_columns()]
            if self.schema.time_index is not None:
                key_cols.append(self.schema.time_index.name)
            seq = pa.array(np.arange(table.num_rows, dtype=np.int64))
            table = table.append_column(_SEQ, seq)
            return _dedup_chunk(table, key_cols, self.schema, True, "last_non_null")

        from .memtable import _SEQ_COL, _sort_and_dedup

        seq = pa.array(np.arange(table.num_rows, dtype=np.int64))
        table = table.append_column(_SEQ_COL, seq)
        table = _sort_and_dedup(table, self.schema, dedup=not self.append_mode)
        return table.drop_columns([_SEQ_COL])

    def scan_merge_stream(
        self,
        pred: ScanPredicate | None = None,
        columns: list[str] | None = None,
        batch_rows: int = 65536,
    ):
        """Streaming scan: per-source sorted batches merged through a
        k-way run-cutting merger with mode-aware dedup (reference
        mito2/src/read/merge.rs MergeReader + dedup.rs DedupReader).
        Peak memory is O(batch + one row group per source) instead of the
        whole scan; SSTs stream row-group-at-a-time."""
        import numpy as np

        from .merge import _SEQ, merge_sorted
        from .sst import _apply_residual

        pred = pred or ScanPredicate()
        with self._lock:
            files = list(self.manifest_mgr.manifest.files.values())
            mems = list(self._frozen_memtables) + [self.memtable]
            self._active_scans += 1
        try:
            key_cols = {c.name for c in self.schema.tag_columns()}
            if self.schema.time_index is not None:
                key_cols.add(self.schema.time_index.name)
            key_filters = [f for f in pred.filters if f[0] in key_cols]
            post_filters = [f for f in pred.filters if f[0] not in key_cols]
            prune_pred = ScanPredicate(
                time_range=pred.time_range,
                filters=list(pred.filters) if self.append_mode else key_filters,
            )
            read_cols = None
            if columns:
                need = list(dict.fromkeys(columns))
                for c in self.schema.primary_key():
                    if c not in need:
                        need.append(c)
                if self.schema.time_index and self.schema.time_index.name not in need:
                    need.append(self.schema.time_index.name)
                for name, _op, _v in pred.filters:
                    if self.schema.has_column(name) and name not in need:
                        need.append(name)
                need.append(OP_COL)
                read_cols = need
            base = 0

            def sst_source(meta, base_seq):
                for t in self.sst_reader.read_batches(
                    meta, prune_pred, columns=read_cols
                ):
                    t = self._compat_cast(_undict(t))
                    seq = pa.array(
                        base_seq + np.arange(t.num_rows, dtype=np.int64)
                    )
                    yield t.append_column(_SEQ, seq)

            def mem_source(mem, base_seq):
                t = mem.scan(pred.time_range, dedup=False)
                if t.num_rows:
                    t = _apply_residual(t, prune_pred, None)
                if t.num_rows and read_cols:
                    t = t.select([c for c in read_cols if c in t.column_names])
                if t.num_rows:
                    seq = pa.array(
                        base_seq + np.arange(t.num_rows, dtype=np.int64)
                    )
                    yield _undict(t).append_column(_SEQ, seq)

            sources = []
            for meta in self.sst_reader.prune_files(files, prune_pred):
                sources.append(sst_source(meta, base))
                base += 1 << 40
            for mem in mems:
                sources.append(mem_source(mem, base))
                base += 1 << 40
            ts_name = (
                self.schema.time_index.name if self.schema.time_index else None
            )
            for out in merge_sorted(
                sources,
                self.schema,
                dedup=not self.append_mode,
                mode=self.merge_mode,
                batch_rows=batch_rows,
            ):
                out = self._drop_tombstones(out)
                if post_filters:
                    out = _apply_residual(
                        out, ScanPredicate(filters=post_filters), None
                    )
                # schema evolution: late columns read as NULL
                for c in self.schema.columns:
                    if c.name not in out.column_names:
                        out = out.append_column(
                            c.name, pa.nulls(out.num_rows, c.data_type.to_arrow())
                        )
                if columns:
                    out = out.select(
                        [c for c in columns if c in out.column_names]
                    )
                else:
                    want = [
                        c for c in self.schema.column_names()
                        if c in out.column_names
                    ]
                    if want != out.column_names:
                        out = out.select(want)
                if out.num_rows:
                    yield out
        finally:
            with self._lock:
                self._active_scans -= 1
                self._purge_garbage_locked()

    # ---- tile-cache support ------------------------------------------------
    def pin_scan(self):
        """Hold the deferred-purge refcount open while the device tile cache
        reads SST files outside `scan()` (same protection in-flight scans
        get: compaction must not delete files under us)."""
        with self._lock:
            self._active_scans += 1

    def unpin_scan(self):
        with self._lock:
            self._active_scans -= 1
            self._purge_garbage_locked()

    def approx_rows(self) -> int:
        """Cheap row-count estimate (manifest stats + memtables) for the
        query planner's layout/cost decisions — the role of the
        reference's region statistics (store-api region_statistic)."""
        with self._lock:
            rows = sum(
                m.num_rows for m in self.manifest_mgr.manifest.files.values()
            )
            rows += self.memtable.num_rows
            rows += sum(m.num_rows for m in self._frozen_memtables)
        return rows

    def distinct_estimate(self, column: str) -> int | None:
        """Upper-bound distinct-value estimate for `column` from the
        per-SST segmented term index metas (one small cached ranged read
        per file): the sum of per-file term counts over-counts values
        shared across files, which is the safe direction for sizing a
        hash table.  None when no file carries a segmented index for the
        column (the planner falls back to dictionary cardinality)."""
        with self._lock:
            files = list(self.manifest_mgr.manifest.files.values())
        total = None
        for meta in files:
            if column not in meta.indexed_columns:
                continue
            n = self.sst_reader.distinct_terms(meta, column)
            if n is not None:
                total = n if total is None else total + n
        return total

    def tile_snapshot(self) -> tuple[list[FileMeta], list[Memtable], int]:
        """Consistent (files, memtables, manifest_version) snapshot for the
        tile executor.  Caller must hold pin_scan() around use."""
        with self._lock:
            files = list(self.manifest_mgr.manifest.files.values())
            mems = list(self._frozen_memtables) + [self.memtable]
            version = self.manifest_mgr.manifest.manifest_version
        return files, mems, version

    # ---- admin ------------------------------------------------------------
    def truncate(self):
        with self._lock:
            entry_id = self.wal.last_entry_id
            dropped = list(self.manifest_mgr.manifest.files)
            self.manifest_mgr.apply({"kind": "truncate", "truncated_entry_id": entry_id})
            self.memtable = make_memtable(self.schema, self.time_partition_ms, self.memtable_kind)
            # frozen memtables hold pre-truncate rows an in-flight flush froze;
            # drop them so scans stop seeing truncated data immediately (the
            # flush itself discards its SSTs when it observes the watermark)
            self._frozen_memtables.clear()
            self.wal.obsolete(entry_id)
            # the truncated SSTs are unreferenced now; reclaim them once
            # in-flight scans drain (same deferred purge as compaction)
            self._garbage_files.extend((fid, time.time()) for fid in dropped)
            self._purge_garbage_locked()

    def alter_schema(self, new_schema: Schema):
        """Schema change: flush first so existing SSTs stay self-describing."""
        with self._lock:
            self.flush()
            self.manifest_mgr.apply({"kind": "change", "schema": new_schema.to_json()})
            self.schema = new_schema
            self.sst_writer.schema = new_schema
            self.sst_reader.schema = new_schema
            self.memtable = make_memtable(new_schema, self.time_partition_ms, self.memtable_kind)

    # ---- follower freshness (bounded-staleness replicas) ------------------
    def follower_sync(self) -> tuple[int, bool]:
        """One freshness round for a READ-ONLY follower region: refresh the
        manifest view when the leader's version advanced (flush/compaction/
        truncate/alter — compaction-deleted SSTs drop out of the file list
        before a hedged read trips over them), then replay the shared-WAL
        tail past `applied_entry_id` into the memtable.  Returns
        (entries_applied, manifest_refreshed).

        Correctness of the refresh path: adopting a fresh manifest resets
        the memtable and restarts the tail from the NEW flushed watermark —
        rows the leader flushed are now served from the refreshed SST set,
        rows it has not are still in the log above the watermark, so the
        follower view equals what a fresh open would build, without the
        open cost.  A leader never runs this (writable regions return
        immediately), so the snapshot behavior with syncing disabled is
        bit-for-bit the pre-freshness one."""
        from ..utils import fault_injection

        fault_injection.fire("replica.sync", region_id=self.region_id)
        with self._lock:
            if self.writable:
                return 0, False
            applied, refreshed = self._catch_up_locked()
            applied_to = self.applied_entry_id
        # register the replay low-watermark OUTSIDE the region lock (it
        # writes a shared file); shared-WAL prune keeps everything above it
        register = getattr(self.wal, "register_replay_position", None)
        if register is not None:
            register(applied_to)
            # close_region/promotion may have released the watermark while
            # the registration write was in flight — a released region must
            # never be re-pinned by a stale sync round (the orphan would
            # hold pruning back for the whole registration TTL)
            with self._lock:
                released = self._lw_released
            if released:
                self.release_follower_watermark()
        label = str(self.region_id)
        metrics.FOLLOWER_SYNC_TOTAL.inc()
        metrics.FOLLOWER_LAG_ENTRIES.set(0.0, region=label)
        metrics.FOLLOWER_LAG_MS.set(0.0, region=label)
        return applied, refreshed

    def _catch_up_locked(self) -> tuple[int, bool]:
        """Adopt the leader's manifest state if it advanced, then replay the
        log tail past `applied_entry_id` into the memtable.  Shared by the
        follower sync round and the promotion path (`set_writable(True)`).
        Returns (entries_applied, manifest_refreshed)."""
        manifest, refreshed = self.manifest_mgr.refresh()
        if refreshed:
            metrics.FOLLOWER_MANIFEST_REFRESH_TOTAL.inc()
            if manifest.schema is not None:
                self.schema = manifest.schema
                self.sst_writer.schema = manifest.schema
                self.sst_reader.schema = manifest.schema
            self.memtable = make_memtable(
                self.schema, self.time_partition_ms, self.memtable_kind
            )
            self._frozen_memtables.clear()
            self.sequence = manifest.flushed_sequence
            self.applied_entry_id = max(
                manifest.flushed_entry_id, manifest.truncated_entry_id or 0
            )
        applied = 0
        for entry in self.wal.replay(self.applied_entry_id):
            self.sequence += 1
            self.memtable.write(self._conform(entry.batch), self.sequence)
            self.applied_entry_id = entry.entry_id
            applied += 1
        self.wal.advance_to(self.applied_entry_id)
        self.last_sync_ms = time.time() * 1000
        return applied, refreshed

    def release_follower_watermark(self):
        """Stop holding the shared WAL back (follower closed/promoted).
        Latches `_lw_released` so an in-flight sync round that registers
        concurrently undoes its own registration (see follower_sync)."""
        with self._lock:
            self._lw_released = True
        release = getattr(self.wal, "release_replay_position", None)
        if release is not None:
            release()

    def set_writable(self, writable: bool):
        """Leader/follower role flip (reference set_region_role).  Takes
        the region lock so a downgrade returns only after in-flight writes
        finish their WAL append — the migration candidate's catch-up replay
        must never race a torn tail."""
        with self._lock:
            was = self.writable
            if writable and not was:
                # promotion catch-up: adopt the leader's final manifest
                # state and replay the un-applied shared-log tail BEFORE
                # the first write — entries above the last sync round would
                # otherwise be lost from the memtable, and the first append
                # would reuse entry ids the old leader already wrote to the
                # shared topic (append allocates last_entry_id + 1)
                self._catch_up_locked()
            if not writable:
                # (re)entering the follower role: sync rounds may pin the
                # shared log again (a later promotion re-latches)
                self._lw_released = False
            self.writable = writable
        if writable and not was:
            # a promoted follower must not keep pinning the shared log
            self.release_follower_watermark()

    def stat(self) -> RegionStat:
        m = self.manifest_mgr.manifest
        lag_entries, lag_ms = 0, 0.0
        if not self.writable:
            lag_entries = max(0, self.wal.last_entry_id - self.applied_entry_id)
            lag_ms = max(0.0, time.time() * 1000 - self.last_sync_ms)
            label = str(self.region_id)
            metrics.FOLLOWER_LAG_ENTRIES.set(lag_entries, region=label)
            metrics.FOLLOWER_LAG_MS.set(lag_ms, region=label)
        return RegionStat(
            region_id=self.region_id,
            num_rows=sum(f.num_rows for f in m.files.values()) + self.memtable.num_rows,
            sst_count=len(m.files),
            sst_bytes=sum(f.file_size for f in m.files.values()),
            memtable_bytes=self.memtable.memory_usage,
            wal_entry_id=self.wal.last_entry_id,
            flushed_entry_id=m.flushed_entry_id,
            writable=self.writable,
            follower_lag_entries=lag_entries,
            follower_lag_ms=lag_ms,
        )

    def files(self) -> list[FileMeta]:
        with self._lock:
            return list(self.manifest_mgr.manifest.files.values())

    def read_sst(self, meta: FileMeta, pred: ScanPredicate | None = None) -> pa.Table:
        return _undict(self.sst_reader.read(meta, pred))


def _undict(table: pa.Table) -> pa.Table:
    """Decode dictionary columns back to plain values for cross-file concat."""
    import pyarrow.compute as pc

    for i, f in enumerate(table.schema):
        if pa.types.is_dictionary(f.type):
            table = table.set_column(i, f.name, pc.cast(table[f.name], f.type.value_type))
    return table
