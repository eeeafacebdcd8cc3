"""Compaction: time-windowed merge of SSTs with sorted-run selection.

Role-equivalent of the reference's TWCS (time-windowed compaction strategy,
reference mito2/src/compaction/twcs.rs:45) plus its sorted-run math
(reference mito2/src/compaction/run.rs): SSTs are grouped by time window;
within a window, files partition into SORTED RUNS (sets of files whose
time ranges don't overlap).  Only windows whose RUN count exceeds the
limit compact, and only the cheapest runs merge — files that are already
disjoint never rewrite, which is what actually bounds write
amplification (the round-3 picker merged every level-0 file in an
over-populated window, re-merging disjoint data each round).

A global memory budget (reference compaction/memory_manager.rs) bounds
concurrent merge working sets: oversized groups split into sub-merges
that each fit the budget, and concurrent compactions serialize through
the budget gate.
"""

from __future__ import annotations

import threading
from collections import defaultdict

import pyarrow as pa

from ..utils import metrics, tracing
from .memtable import _SEQ_COL, _sort_and_dedup
from .region import Region, _undict
from .sst import FileMeta, interleaved_overlap_unsafe

# Parquet bytes expand roughly this much when decoded for the merge.
_DECODE_FACTOR = 4


def find_sorted_runs(files: list[FileMeta]) -> list[list[FileMeta]]:
    """Partition a window's files into sorted runs — each run holds files
    with pairwise-disjoint (inclusive) time ranges, greedily assigned in
    start order (interval-partitioning; reference run.rs
    find_sorted_runs).  len(result) == the window's run count."""
    runs: list[list[FileMeta]] = []
    for f in sorted(files, key=lambda m: m.time_range):
        for run in runs:
            if run[-1].time_range[1] < f.time_range[0]:
                run.append(f)
                break
        else:
            runs.append([f])
    return runs


def reduce_runs(runs: list[list[FileMeta]], target: int) -> list[FileMeta]:
    """Pick the files to merge so the window's run count drops to
    `target`: merging k runs into one removes k-1 runs, so take the
    k = len(runs) - target + 1 CHEAPEST runs by bytes (reference run.rs
    reduce_runs picks the minimal-penalty selection)."""
    if len(runs) <= target:
        return []
    k = len(runs) - target + 1
    by_cost = sorted(runs, key=lambda r: sum(f.file_size for f in r))
    return [f for run in by_cost[:k] for f in run]


def merge_seq_files(
    run: list[FileMeta], max_output_bytes: int
) -> list[list[FileMeta]]:
    """Within ONE sorted run, group consecutive SMALL files for merging
    (reference run.rs merge_seq_files): disjoint files don't need dedup,
    but dozens of tiny flush outputs cost read amplification — merge
    neighbors while the combined output stays under the size cap, which
    also bounds how often a byte can be rewritten (a file at the cap
    never joins another group)."""
    def balanced(group: list[FileMeta]) -> bool:
        # tiering guard: don't fold a tiny tail into a much larger file
        # every round (that rewrites the big file per flush — quadratic
        # write amp); wait until the smaller files together are worth it
        sizes = sorted(f.file_size for f in group)
        return len(group) > 1 and sizes[-1] <= 3 * max(sum(sizes[:-1]), 1)

    groups: list[list[FileMeta]] = []
    cur: list[FileMeta] = []
    cur_bytes = 0
    for f in sorted(run, key=lambda m: m.time_range):
        if f.file_size >= max_output_bytes:
            if balanced(cur):
                groups.append(cur)
            cur, cur_bytes = [], 0
            continue
        if cur and cur_bytes + f.file_size > max_output_bytes:
            if balanced(cur):
                groups.append(cur)
            cur, cur_bytes = [], 0
        cur.append(f)
        cur_bytes += f.file_size
    if balanced(cur):
        groups.append(cur)
    return groups


def pick_compaction(
    files: list[FileMeta],
    window_ms: int,
    max_active_runs: int = 4,
    max_inactive_runs: int = 1,
    max_output_bytes: int = 128 << 20,
) -> list[list[FileMeta]]:
    """TWCS picker: group files by window, count sorted runs per window,
    and for each over-run window emit the cheapest run set whose merge
    brings it back to the limit; within-limit windows still merge
    consecutive small files of a run (read-amplification control).  The
    most recent window (still being written, "active") tolerates more
    runs than older ("inactive") ones."""
    if not files:
        return []
    by_window: dict[int, list[FileMeta]] = defaultdict(list)
    for f in files:
        by_window[(f.time_range[0] // window_ms) * window_ms].append(f)
    active_window = max(by_window)
    picks = []
    for window, group in by_window.items():
        limit = max_active_runs if window == active_window else max_inactive_runs
        runs = find_sorted_runs(group)
        merge = reduce_runs(runs, limit)
        if len(merge) > 1:
            picks.append(merge)
            continue
        for run in runs:
            picks.extend(merge_seq_files(run, max_output_bytes))
    return picks


class CompactionMemoryManager:
    """Global budget for concurrent compaction working sets (reference
    mito2/src/compaction/memory_manager.rs): acquire blocks until the
    estimated decode footprint fits; a single estimate larger than the
    whole budget is admitted alone (it must run eventually — the split
    logic in compact_files keeps such groups rare)."""

    def __init__(self, budget_bytes: int):
        self.budget = budget_bytes
        self._used = 0
        self._cv = threading.Condition()

    def acquire(self, est: int):
        with self._cv:
            while self._used > 0 and self._used + est > self.budget:
                self._cv.wait(timeout=30)
            self._used += est

    def release(self, est: int):
        with self._cv:
            self._used -= est
            self._cv.notify_all()


# process-wide gate, sized on first use (all engines share one budget:
# compaction memory is a machine resource, not a per-region one)
_memory_manager: CompactionMemoryManager | None = None
_memory_manager_lock = threading.Lock()


def _memory_gate(memory_mb: int) -> CompactionMemoryManager:
    global _memory_manager
    with _memory_manager_lock:
        if _memory_manager is None:
            _memory_manager = CompactionMemoryManager((memory_mb or 512) << 20)
        return _memory_manager


def overlap_clusters(group: list[FileMeta]) -> list[list[FileMeta]]:
    """Partition a merge group into clusters of transitively-overlapping
    files (sorted by start; a cluster breaks where the next file starts
    after everything seen so far ends).  Files that might hold the same
    (pk, ts) key are ALWAYS in one cluster — the unit that must dedup
    together."""
    out: list[list[FileMeta]] = []
    cur: list[FileMeta] = []
    cur_end = None
    for f in sorted(group, key=lambda m: m.time_range):
        if cur and f.time_range[0] > cur_end:
            out.append(cur)
            cur = []
            cur_end = None
        cur.append(f)
        cur_end = f.time_range[1] if cur_end is None else max(cur_end, f.time_range[1])
    if cur:
        out.append(cur)
    return out


def split_group_for_memory(
    group: list[FileMeta], budget_bytes: int
) -> list[list[FileMeta]]:
    """Split an oversized merge into sub-merges whose decode footprints
    fit the budget — along OVERLAP-CLUSTER boundaries only: duplicates
    of one key must dedup in a single merge (a split that separates two
    versions would let both survive into overlapping outputs and make
    last-write-wins order-dependent).  A single cluster larger than the
    budget merges alone (the memory gate admits oversized jobs solo).
    Each sub-merge output is therefore a genuine sorted-run piece."""
    out: list[list[FileMeta]] = []
    cur: list[FileMeta] = []
    cur_bytes = 0
    for cluster in overlap_clusters(group):
        est = sum(f.file_size for f in cluster) * _DECODE_FACTOR
        if cur and cur_bytes + est > budget_bytes:
            out.append(cur)
            cur, cur_bytes = [], 0
        cur.extend(cluster)
        cur_bytes += est
    if cur:
        if len(cur) == 1 and out:
            out[-1].extend(cur)
        else:
            out.append(cur)
    return out


def infer_window_ms(files: list[FileMeta]) -> int:
    """Pick a TWCS window from data spread (reference twcs window inference):
    smallest bucket from a ladder that keeps total windows reasonable."""
    if not files:
        return 86_400_000
    lo = min(f.time_range[0] for f in files)
    hi = max(f.time_range[1] for f in files)
    span = max(hi - lo, 1)
    for w in (3_600_000, 7_200_000, 43_200_000, 86_400_000, 604_800_000):
        if span // w <= 64:
            return w
    return 604_800_000


def compact_files(region: Region, group: list[FileMeta]) -> FileMeta | None:
    """Merge one window's files: read, concat, sort(+dedup unless the
    region is append_mode — duplicates are semantically kept there), write
    level-1 (`sst.encode` / `sst.index` with `level=1`)."""
    import numpy as np

    with tracing.stage(
        "compact.read", files=len(group), bytes=sum(m.file_size for m in group)
    ):
        tables = []
        for meta in group:
            t = region.read_sst(meta)
            if t.num_rows:
                tables.append(_undict(t))
    if not tables:
        return None
    with tracing.stage("compact.merge", rows=sum(t.num_rows for t in tables)):
        merged = pa.concat_tables(tables, promote_options="permissive")
        if region.merge_mode == "last_non_null" and not region.append_mode:
            # fieldwise merge is associative: the compacted row carries the
            # newest non-null value per field among its inputs, and future
            # reads fieldwise-merge it with newer sources exactly as if the
            # versions were still separate (reference dedup.rs LastNonNull)
            from .merge import _SEQ, _dedup_chunk

            key_cols = [c.name for c in region.schema.tag_columns()]
            if region.schema.time_index is not None:
                key_cols.append(region.schema.time_index.name)
            seq = pa.array(np.arange(merged.num_rows, dtype=np.int64))
            merged = merged.append_column(_SEQ, seq)
            merged = _dedup_chunk(merged, key_cols, region.schema, True, "last_non_null")
        else:
            seq = pa.array(np.arange(merged.num_rows, dtype=np.int64))
            merged = merged.append_column(_SEQ_COL, seq)
            merged = _sort_and_dedup(merged, region.schema, dedup=not region.append_mode)
            merged = merged.drop_columns([_SEQ_COL])
    return region.sst_writer.write(merged, level=1)


def widen_for_order(
    sub: list[FileMeta], all_files: list[FileMeta], pos: dict[str, int]
) -> list[FileMeta]:
    """Grow an order-unsafe merge group to its safe closure: while a file
    outside the group both time-overlaps a member and sits between the
    group's manifest positions (interleaved_overlap_unsafe — one output
    position cannot rank it correctly), pull it INTO the group.  The
    closure always exists (at worst every file between min and max
    position joins) and merging it preserves last-write-wins, so refused
    picks never starve — they merge with their interleaved overwrites
    included instead of waiting for a round that may never come."""
    cur = {f.file_id: f for f in sub}
    changed = True
    while changed:
        changed = False
        ps = sorted(pos[fid] for fid in cur)
        lo, hi = ps[0], ps[-1]
        for x in all_files:
            if x.file_id in cur or not (lo < pos[x.file_id] < hi):
                continue
            if any(
                x.time_range[1] >= g.time_range[0]
                and x.time_range[0] <= g.time_range[1]
                for g in cur.values()
            ):
                cur[x.file_id] = x
                changed = True
    return sorted(cur.values(), key=lambda m: pos[m.file_id])


def compact_region(
    region: Region,
    window_ms: int | None = None,
    max_active_runs: int = 4,
    max_inactive_runs: int = 1,
    memory_mb: int = 512,
) -> int:
    """Run one compaction round; returns number of window merges done.
    Serialized per region: the background scheduler and ADMIN
    compact_table must never pick the same group concurrently (the file
    list is re-read under the lock so a waiter sees the winner's edits)."""
    with region.compaction_lock:
        files = region.files()
        window = window_ms or infer_window_ms(files)
        picks = pick_compaction(files, window, max_active_runs, max_inactive_runs)
        if not picks:
            # no stage for a round with nothing to merge: the scheduler's
            # idle tick leaves no annotation and moves no counter
            return 0
        with tracing.stage("compact.region", region=region.region_id, picks=len(picks)) as st:
            done = _merge_picks(region, files, picks, _memory_gate(memory_mb))
            st.set(merges=done)
        return done


def _merge_picks(region: Region, files: list[FileMeta], picks: list, gate) -> int:
    # dedup correctness depends on WRITE order: compact_files assigns
    # its dedup sequence by concat position, so every merge list must
    # follow manifest (flush) order — the pickers sort by cost/time
    # for SELECTION only
    manifest_pos = {f.file_id: i for i, f in enumerate(files)}
    done = 0
    for group in picks:
        # oversized merges split into budget-sized sub-merges; each
        # sub-merge output is a sorted run, so the next round's run
        # count still drops even when one pass can't merge everything
        for sub in split_group_for_memory(group, gate.budget):
            sub = sorted(sub, key=lambda m: manifest_pos[m.file_id])
            if not region.append_mode and interleaved_overlap_unsafe(
                sub, files, manifest_pos
            ):
                # a partial merge here would resurrect overwritten
                # values — widen to the safe closure (pulls the
                # interleaved overwrites into the merge) instead of
                # skipping, so refused picks never starve
                sub = widen_for_order(sub, files, manifest_pos)
                if (
                    sum(f.file_size for f in sub) * _DECODE_FACTOR
                    > gate.budget
                ):
                    continue  # closure too big this round
            est = min(
                sum(f.file_size for f in sub) * _DECODE_FACTOR, gate.budget
            )
            gate.acquire(est)
            try:
                new_meta = compact_files(region, sub)
            finally:
                gate.release(est)
            adds = [new_meta] if new_meta is not None else []
            if region.apply_compaction(adds, [f.file_id for f in sub]):
                done += 1
                metrics.COMPACTION_INPUT_BYTES.inc(sum(f.stored_bytes for f in sub))
                metrics.COMPACTION_OUTPUT_BYTES.inc(sum(f.stored_bytes for f in adds))
            elif new_meta is not None:
                # commit refused (a flush interleaved an overlapping
                # file mid-merge): the output must not enter the
                # manifest — discard it and retry a later round
                metrics.COMPACTION_DISCARDED_BYTES.inc(new_meta.stored_bytes)
                region.sst_reader.delete(new_meta.file_id)
    return done
