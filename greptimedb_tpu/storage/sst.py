"""Parquet SST read/write with time-range pruning.

Role-equivalent of the reference's SST layer (reference
src/mito2/src/sst/parquet/{writer.rs,reader.rs,stats.rs}): immutable sorted
Parquet files with min/max time statistics used to prune whole files and row
groups at scan time.  We persist data in the reference's "flat format"
(flat_format.rs) spirit — plain columnar, tags as dictionary-encoded
columns — because flat columns are exactly what the TPU tile loader wants.
"""

from __future__ import annotations

import os
import uuid
from dataclasses import dataclass, field

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

from ..datatypes.schema import Schema
from ..utils import fault_injection, metrics, tracing
from ..utils.deadline import check_deadline, current_deadline
from . import index as idx
from .index import BLOOM_BLOB, FULLTEXT_BLOB, INVERTED_BLOB, VECTOR_BLOB
from .object_store import FsObjectStore, ObjectStore
from .puffin import PuffinReader, PuffinWriter

DEFAULT_ROW_GROUP_SIZE = 1 << 20  # rows per row group; big groups = big tiles

INDEX_FULLTEXT_PRUNES = metrics.Counter(
    "greptime_index_fulltext_applied_total",
    "match predicates answered by the fulltext index",
)
INDEX_PRUNED_GROUPS = metrics.Counter(
    "sst_index_pruned_row_groups", "row groups skipped via secondary indexes"
)
INDEX_VECTOR_APPLIED = metrics.Counter(
    "greptime_index_vector_applied_total",
    "top-k vector searches answered via the IVF index",
)


@dataclass
class FileMeta:
    """Catalog entry for one SST (reference mito2/src/sst/file.rs FileMeta)."""

    file_id: str
    time_range: tuple[int, int]  # [min_ts, max_ts] inclusive, int64 native unit
    num_rows: int
    file_size: int
    level: int = 0
    indexed_columns: list[str] = field(default_factory=list)
    index_file_size: int = 0
    # Delete-tombstone rows in the file; -1 = unknown (file written before
    # this field existed).  The device tile cache only aggregates files it
    # can PROVE tombstone-free.
    num_deletes: int = 0

    @property
    def stored_bytes(self) -> int:
        """Parquet file + index sidecar."""
        return self.file_size + self.index_file_size

    def to_dict(self) -> dict:
        return {
            "file_id": self.file_id,
            "time_range": list(self.time_range),
            "num_rows": self.num_rows,
            "file_size": self.file_size,
            "level": self.level,
            "indexed_columns": self.indexed_columns,
            "index_file_size": self.index_file_size,
            "num_deletes": self.num_deletes,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "FileMeta":
        return cls(
            file_id=d["file_id"],
            time_range=tuple(d["time_range"]),
            num_rows=d["num_rows"],
            file_size=d["file_size"],
            level=d.get("level", 0),
            indexed_columns=d.get("indexed_columns", []),
            index_file_size=d.get("index_file_size", 0),
            num_deletes=d.get("num_deletes", -1),
        )


def interleaved_overlap_unsafe(
    inputs: list[FileMeta],
    all_files: list[FileMeta],
    pos: dict[str, int],
) -> bool:
    """True when merging `inputs` cannot express last-write-wins with ONE
    output manifest position: some file outside the group both
    time-overlaps an input (so they may share (pk, ts) keys) and sits
    BETWEEN the group's manifest positions (so it is newer than some
    inputs and older than others).  Shared by the compaction picker and
    the commit gate in Region.apply_compaction — the two must never
    diverge (scans rank duplicate versions by manifest position; the
    reference persists per-row sequences instead, mito2/src/read/dedup.rs)."""
    in_ids = {f.file_id for f in inputs}
    ps = sorted(pos[f.file_id] for f in inputs)
    if len(ps) <= 1:
        return False
    lo, hi = ps[0], ps[-1]
    for x in all_files:
        if x.file_id in in_ids or not (lo < pos.get(x.file_id, -1) < hi):
            continue
        for g in inputs:
            if (
                x.time_range[1] >= g.time_range[0]
                and x.time_range[0] <= g.time_range[1]
            ):
                return True
    return False


@dataclass
class ScanPredicate:
    """Pushed-down predicates the reader can use for pruning: a time range
    plus simple column comparisons (reference sst/parquet/stats.rs)."""

    time_range: tuple[int, int] | None = None  # [lo, hi) half-open
    # list of (column, op, value) with op in {"=", "!=", "<", "<=", ">", ">=", "in"}
    filters: list[tuple[str, str, object]] = field(default_factory=list)


class SstWriter:
    def __init__(
        self,
        store: ObjectStore | str,
        schema: Schema,
        row_group_size: int = DEFAULT_ROW_GROUP_SIZE,
        index_enable: bool = True,
        index_segment_rows: int = idx.DEFAULT_SEGMENT_ROWS,
        index_inverted_max_terms: int = 4096,
        index_segmented: bool = True,
        index_segment_terms: int = 512,
        index_max_terms: int = 1 << 20,
    ):
        # A bare directory path means "local fs store rooted there" — the
        # common standalone config and what unit tests pass.
        self.store = FsObjectStore(store) if isinstance(store, str) else store
        self.schema = schema
        self.row_group_size = row_group_size
        self.index_enable = index_enable
        self.index_segment_rows = index_segment_rows
        self.index_inverted_max_terms = index_inverted_max_terms
        # Segmented term index (greptimedb_tpu/index/): fence-keyed term
        # segments with ranged reads.  On (the default) it REPLACES the
        # whole-blob inverted/fulltext payloads for new SSTs and lifts
        # the legacy cardinality cap to `index_max_terms`; off restores
        # the legacy formats bit-for-bit (old sidecars stay readable
        # either way — the read router handles both).
        self.index_segmented = index_segmented
        self.index_segment_terms = index_segment_terms
        self.index_max_terms = index_max_terms

    def _build_indexes(self, table: pa.Table, file_id: str) -> tuple[list[str], int]:
        """Build bloom + term indexes over tag columns, and tokenized
        fulltext indexes over FULLTEXT-declared text columns, into the
        puffin sidecar (reference mito2/src/sst/index/indexer/ builds
        during flush; fulltext_index/ for the tantivy analogue)."""
        from .. import index as term_index

        cols = [c.name for c in self.schema.tag_columns() if c.name in table.column_names]
        no_terms = {c.name for c in self.schema.tag_columns() if not c.inverted_index}
        ft_cols = [
            c.name
            for c in self.schema.columns
            if getattr(c, "fulltext", False) and c.name in table.column_names
        ]
        vec_cols = [
            c
            for c in self.schema.columns
            if getattr(c, "vector_index", False) and c.name in table.column_names
        ]
        if not cols and not ft_cols and not vec_cols:
            return [], 0
        fault_injection.fire("index.build", file=file_id)
        writer = PuffinWriter(self.store, f"{file_id}.puffin")
        indexed = []
        for name in cols:
            col = table[name]
            col = col.combine_chunks() if isinstance(col, pa.ChunkedArray) else col
            # the column's distinct values, made once for both indexes
            terms = idx.ColumnTerms(col)
            bloom = idx.build_bloom_index(terms, self.index_segment_rows)
            writer.add_blob(BLOOM_BLOB, bloom, {"column": name})
            if name in no_terms:  # its bloom stays
                pass
            elif self.index_segmented:
                postings = term_index.term_postings(terms, self.index_segment_rows)
                if len(postings) <= self.index_max_terms:
                    term_index.write_term_index(
                        writer, name, "inverted", postings,
                        segment_rows=self.index_segment_rows, n_rows=len(col),
                        seg_terms=self.index_segment_terms,
                    )
            else:
                inverted = idx.build_inverted_index(
                    col, self.index_segment_rows, self.index_inverted_max_terms
                )
                if inverted is not None:
                    writer.add_blob(INVERTED_BLOB, inverted, {"column": name})
            indexed.append(name)
        for name in ft_cols:
            col = table[name]
            col = col.combine_chunks() if isinstance(col, pa.ChunkedArray) else col
            if self.index_segmented:
                toks = term_index.build_token_postings(col, self.index_segment_rows)
                if len(toks) and len(toks) <= self.index_max_terms:
                    term_index.write_term_index(
                        writer, name, "fulltext", toks,
                        segment_rows=self.index_segment_rows, n_rows=len(col),
                        seg_terms=self.index_segment_terms,
                    )
                    if name not in indexed:
                        indexed.append(name)
            else:
                ft = idx.build_fulltext_index(col, self.index_segment_rows)
                if ft is not None:
                    writer.add_blob(FULLTEXT_BLOB, ft, {"column": name})
                    if name not in indexed:
                        indexed.append(name)
        for c in vec_cols:
            col = table[c.name]
            col = col.combine_chunks() if isinstance(col, pa.ChunkedArray) else col
            vec = idx.build_vector_index(col, c.vector_dim or 0)
            if vec is not None:
                writer.add_blob(VECTOR_BLOB, vec, {"column": c.name})
                if c.name not in indexed:
                    indexed.append(c.name)
        return indexed, writer.finish()

    def write(self, table: pa.Table, level: int = 0) -> FileMeta | None:
        """Write one sorted table as one SST file; returns its FileMeta."""
        if table.num_rows == 0:
            return None
        with tracing.stage("sst.encode", level=level, rows=table.num_rows) as st:
            ts_name = self.schema.time_index.name if self.schema.time_index else None
            if ts_name is not None:
                ts = pc.cast(table[ts_name], pa.int64())
                t_min, t_max = pc.min(ts).as_py(), pc.max(ts).as_py()
            else:
                t_min = t_max = 0
            num_deletes = 0
            if "__op" in table.column_names:
                num_deletes = int(
                    pc.sum(
                        pc.fill_null(pc.cast(table["__op"], pa.int64()), 0)
                    ).as_py()
                    or 0
                )
            # Dictionary-encode tag columns: small files + pre-built codes for TPU.
            for tag in self.schema.tag_columns():
                if tag.name in table.column_names and not pa.types.is_dictionary(
                    table.schema.field(tag.name).type
                ):
                    i = table.schema.get_field_index(tag.name)
                    table = table.set_column(
                        i, tag.name, pc.dictionary_encode(table[tag.name].combine_chunks())
                    )
            file_id = uuid.uuid4().hex
            key = f"{file_id}.parquet"
            scratch = self.store.scratch_path(key)
            pq.write_table(
                table,
                scratch,
                row_group_size=self.row_group_size,
                compression="zstd",
                use_dictionary=True,
            )
            file_size = os.path.getsize(scratch)
            self.store.put_file(key, scratch)
            st.set(bytes=file_size)
        indexed, index_size = ([], 0)
        if self.index_enable:
            try:
                with tracing.stage("sst.index") as st:
                    indexed, index_size = self._build_indexes(table, file_id)
                    st.set(columns=len(indexed), bytes=index_size)
            except Exception as e:  # noqa: BLE001 — an index build failure
                # must never lose the data write: the SST lands without a
                # sidecar (unpruned but correct), and the failure is loud
                import logging

                logging.getLogger("greptimedb_tpu.index").warning(
                    "index build for %s failed; SST written unindexed: %s",
                    file_id, e,
                )
                indexed, index_size = [], 0
        return FileMeta(
            file_id=file_id,
            time_range=(t_min, t_max),
            num_rows=table.num_rows,
            file_size=file_size,
            level=level,
            indexed_columns=indexed,
            index_file_size=index_size,
            num_deletes=num_deletes,
        )


_INDEX_CACHE = idx.IndexCache(capacity=128)


class SstReader:
    def __init__(self, store: ObjectStore | str, schema: Schema):
        self.store = FsObjectStore(store) if isinstance(store, str) else store
        self.schema = schema

    def delete(self, file_id: str):
        """Remove an SST and its index sidecar from the store."""
        self.store.delete(f"{file_id}.parquet")
        self.store.delete(f"{file_id}.puffin")

    def prune_files(self, files: list[FileMeta], pred: ScanPredicate) -> list[FileMeta]:
        """File-level pruning on time range (whole-file min/max)."""
        if pred.time_range is None:
            return list(files)
        lo, hi = pred.time_range
        return [f for f in files if f.time_range[1] >= lo and f.time_range[0] < hi]

    def read(
        self,
        meta: FileMeta,
        pred: ScanPredicate | None = None,
        columns: list[str] | None = None,
    ) -> pa.Table:
        """Read one SST with row-group pruning + residual filter application."""
        pred = pred or ScanPredicate()
        pf = pq.ParquetFile(self.store.open_input(f"{meta.file_id}.parquet"))
        ts_name = self.schema.time_index.name if self.schema.time_index else None
        groups = self._prune_row_groups(pf, pred, ts_name)
        if groups and meta.indexed_columns:
            before = len(groups)
            groups = self._prune_with_indexes(pf, meta, pred, groups)
            if len(groups) < before:
                INDEX_PRUNED_GROUPS.inc(before - len(groups))
        if columns:
            # tolerate requested columns the file predates (e.g. __op or a
            # column added by ALTER after this SST was written)
            columns = [c for c in columns if c in pf.schema_arrow.names]
        if not groups:
            schema = pf.schema_arrow
            if columns:
                schema = pa.schema([schema.field(c) for c in columns])
            return schema.empty_table()
        check_deadline()
        if current_deadline() is None or len(groups) <= 4:
            table = pf.read_row_groups(groups, columns=columns, use_threads=True)
        else:
            # under an active deadline, decode in row-group batches so a
            # runaway scan aborts between batches instead of grinding
            # through the whole file in one opaque C call
            parts = []
            for i in range(0, len(groups), 4):
                check_deadline()
                parts.append(
                    pf.read_row_groups(groups[i : i + 4], columns=columns, use_threads=True)
                )
            table = pa.concat_tables(parts)
        # Parquet has no seconds timestamp unit: a timestamp("s") column comes
        # back as timestamp("ms").  Restore the declared logical type so
        # residual predicates (expressed in the native unit) compare correctly.
        if ts_name is not None and ts_name in table.column_names:
            want = self.schema.time_index.data_type.to_arrow()
            i = table.schema.get_field_index(ts_name)
            if table.schema.field(i).type != want:
                table = table.set_column(i, ts_name, pc.cast(table[ts_name], want))
        table = _apply_residual(table, pred, ts_name)
        return table

    def read_batches(
        self,
        meta: FileMeta,
        pred: ScanPredicate | None = None,
        columns: list[str] | None = None,
    ):
        """Stream one SST row-group at a time (reference FileRange scan
        units, mito2/src/sst/parquet/reader.rs): the streaming merge reader
        holds at most one row group per source in memory."""
        pred = pred or ScanPredicate()
        pf = pq.ParquetFile(self.store.open_input(f"{meta.file_id}.parquet"))
        ts_name = self.schema.time_index.name if self.schema.time_index else None
        groups = self._prune_row_groups(pf, pred, ts_name)
        if groups and meta.indexed_columns:
            groups = self._prune_with_indexes(pf, meta, pred, groups)
        if columns:
            columns = [c for c in columns if c in pf.schema_arrow.names]
        want = (
            self.schema.time_index.data_type.to_arrow()
            if self.schema.time_index
            else None
        )
        for g in groups:
            table = pf.read_row_groups([g], columns=columns, use_threads=False)
            if ts_name is not None and ts_name in table.column_names:
                i = table.schema.get_field_index(ts_name)
                if want is not None and table.schema.field(i).type != want:
                    table = table.set_column(i, ts_name, pc.cast(table[ts_name], want))
            table = _apply_residual(table, pred, ts_name)
            if table.num_rows:
                yield table

    def _prune_with_indexes(
        self, pf: pq.ParquetFile, meta: FileMeta, pred: ScanPredicate, groups: list[int]
    ) -> list[int]:
        """Row-group pruning via the puffin sidecar, routed through the
        shared TermIndexReader (reference mito2/src/read/scan_region.rs
        index appliers): segmented term index with ranged reads when the
        sidecar carries it, legacy whole-blob parses otherwise.  Any
        index failure degrades to no pruning — the residual filter keeps
        results exact."""
        usable = [
            (name, op, value)
            for name, op, value in pred.filters
            if name in meta.indexed_columns
            and op in ("=", "in", "!=", "match", "match_term")
        ]
        if not usable:
            return groups
        reader = self.term_index(meta)
        if reader is None:
            return groups
        seg_bitmap: np.ndarray | None = None
        for name, op, value in usable:
            bm = reader.search(name, op, value)
            if bm is None:
                continue
            if op in ("match", "match_term"):
                INDEX_FULLTEXT_PRUNES.inc()
            seg_bitmap = bm if seg_bitmap is None else (seg_bitmap & bm)
        if seg_bitmap is None:
            return groups
        seg_rows = reader.segment_rows()
        md = pf.metadata
        offsets = [0]
        for g in range(md.num_row_groups):
            offsets.append(offsets[-1] + md.row_group(g).num_rows)
        keep = []
        for g in groups:
            s0 = offsets[g] // seg_rows
            s1 = (offsets[g + 1] - 1) // seg_rows
            if seg_bitmap[s0 : s1 + 1].any():
                keep.append(g)
        return keep

    def term_index(self, meta: FileMeta):
        """The file's cached TermIndexReader, or None without a sidecar."""
        from ..index import TermIndexReader

        cached = _INDEX_CACHE.get(meta.file_id)
        if cached is not None:
            return cached
        reader = TermIndexReader(self.store, meta.file_id)
        if not reader.exists():
            return None
        _INDEX_CACHE.put(meta.file_id, reader)
        return reader

    def distinct_terms(self, meta: FileMeta, column: str) -> int | None:
        """Unique-term count of `column` in this SST from the segmented
        index meta (one small ranged read; None when unindexed) — the
        planner's distinct-key stats feed."""
        reader = self.term_index(meta)
        return None if reader is None else reader.distinct_terms(column)

    def vector_index(self, meta: FileMeta, column: str):
        """Parsed per-SST IVF index for `column`, or None."""
        reader = self.term_index(meta)
        return None if reader is None else reader.vector_index(column)

    def _prune_row_groups(self, pf: pq.ParquetFile, pred: ScanPredicate, ts_name) -> list[int]:
        md = pf.metadata
        if pred.time_range is None or ts_name is None:
            return list(range(md.num_row_groups))
        ts_idx = pf.schema_arrow.get_field_index(ts_name)
        if ts_idx < 0:
            return list(range(md.num_row_groups))  # no stats to prune on
        unit_ns = self.schema.time_index.data_type.timestamp_unit_ns()
        lo, hi = pred.time_range
        keep = []
        for g in range(md.num_row_groups):
            stats = md.row_group(g).column(ts_idx).statistics
            if stats is None or not stats.has_min_max:
                keep.append(g)
                continue
            g_min, g_max = _ts_to_int(stats.min, unit_ns), _ts_to_int(stats.max, unit_ns)
            if g_max >= lo and g_min < hi:
                keep.append(g)
        return keep


def _ts_to_int(v, unit_ns: int) -> int:
    """Convert a parquet stats value to the column's NATIVE timestamp unit.

    pyarrow surfaces timestamp stats as datetimes; predicates arrive in the
    column's own unit, so scale by the schema's unit (not hardcoded ms)."""
    if hasattr(v, "timestamp"):
        import calendar

        ns = calendar.timegm(v.utctimetuple()) * 1_000_000_000 + v.microsecond * 1000
        return ns // unit_ns
    return int(v)


def _apply_residual(table: pa.Table, pred: ScanPredicate, ts_name) -> pa.Table:
    """Apply exact time-range + pushed filters on the decoded table."""
    if table.num_rows == 0:
        return table
    mask = None
    if pred.time_range is not None and ts_name is not None and ts_name in table.column_names:
        lo, hi = pred.time_range
        ts = pc.cast(table[ts_name], pa.int64())
        mask = pc.and_(pc.greater_equal(ts, lo), pc.less(ts, hi))
    for name, op, value in pred.filters:
        if name not in table.column_names:
            continue
        col = table[name]
        if pa.types.is_dictionary(col.type):
            col = pc.cast(col, col.type.value_type)
        m = _cmp(col, op, value)
        mask = m if mask is None else pc.and_(mask, m)
    if mask is not None:
        table = table.filter(mask)
    return table


def _cmp(col, op: str, value):
    if op == "match":
        return idx.matches_mask(col, value)
    if op == "match_term":
        return idx.matches_term_mask(col, value)
    if isinstance(value, str):
        from ..datatypes.coercion import coerce_string_scalar

        value = coerce_string_scalar(value, col.type)
    if op == "=":
        return pc.equal(col, value)
    if op == "!=":
        return pc.not_equal(col, value)
    if op == "<":
        return pc.less(col, value)
    if op == "<=":
        return pc.less_equal(col, value)
    if op == ">":
        return pc.greater(col, value)
    if op == ">=":
        return pc.greater_equal(col, value)
    if op == "in":
        return pc.is_in(col, value_set=pa.array(list(value)))
    if op == "not in":
        return pc.invert(pc.is_in(col, value_set=pa.array(list(value))))
    raise ValueError(f"unknown filter op: {op}")
