"""Metric engine: thousands of small logical tables on one physical region.

Role-equivalent of the reference's `metric-engine` crate (reference
src/metric-engine/src/engine.rs:58-130): Prometheus workloads create one
tiny table per metric name; storing each in its own region would drown the
system in region overhead.  Instead all logical tables share one physical
mito region pair — a *data region* holding every row with two synthetic tag
columns (`__table_id`, `__tsid` — reference
src/metric-engine/src/row_modifier.rs) and a *metadata region* recording
which logical tables exist and which label columns each owns (reference
src/metric-engine/src/metadata_region.rs).

TPU-first consequence: one wide physical region means the PromQL hot path
scans ONE arrow column set filtered by `__table_id` — a dense predicate mask
over contiguous tiles — instead of thousands of tiny per-table scans.  The
`__tsid` series hash is exactly the pre-hashed int64 group key the segmented
TPU aggregates want (SURVEY.md §7 hard part (b)).

DDL mapping (reference src/metric-engine/src/engine/create.rs):
  CREATE TABLE phy (ts ..., val ...) WITH ('physical_metric_table' = '')
  CREATE TABLE m1 (ts ..., val ..., host STRING PRIMARY KEY)
      WITH ('on_physical_table' = 'phy')
New labels on an existing logical table ALTER the physical schema in place
(nullable string tags), mirroring reference engine/alter.rs.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading

import numpy as np
import pyarrow as pa

from ..datatypes.data_type import ConcreteDataType
from ..datatypes.schema import ColumnSchema, Schema, SemanticType
from ..models.catalog import DEFAULT_SCHEMA, TableMeta, region_id
from ..storage.sst import ScanPredicate, _apply_residual
from ..utils import tracing
from ..utils.errors import (
    InvalidArgumentsError,
    TableAlreadyExistsError,
    TableNotFoundError,
)

# Synthetic physical columns (reference row_modifier.rs injects the same pair).
TABLE_ID_COL = "__table_id"
TSID_COL = "__tsid"

# Table-option keys (reference metric-engine consts PHYSICAL_TABLE_METADATA_KEY
# / LOGICAL_TABLE_METADATA_KEY).
PHYSICAL_TABLE_OPT = "physical_metric_table"
LOGICAL_TABLE_OPT = "on_physical_table"

# Default column names for auto-created Prometheus tables (reference
# greptime_timestamp / greptime_value).
TS_COL = "greptime_timestamp"
VAL_COL = "greptime_value"


def is_physical_meta(meta: TableMeta) -> bool:
    return PHYSICAL_TABLE_OPT in meta.options


def is_logical_meta(meta: TableMeta) -> bool:
    return LOGICAL_TABLE_OPT in meta.options


def tsid_hash(pairs: list[tuple[str, str]]) -> int:
    """Stable 64-bit series id from sorted (label, value) pairs (reference
    row_modifier.rs TsidGenerator).  Signed so it fits arrow int64."""
    digest = hashlib.blake2b(
        b"".join(
            b"%b\x00%b\x01" % (k.encode(), str(v).encode())
            for k, v in sorted(pairs)
        ),
        digest_size=8,
    ).digest()
    return int.from_bytes(digest, "little", signed=True)


def _batch_tsids(metric: str, labels: dict, n: int):
    """The `__tsid` of every row of a batch."""
    hashes, inverse = _label_set_tsids(metric, labels, n)
    return hashes[inverse]


def _label_set_tsids(metric: str, labels: dict, n: int):
    """(the `__tsid` of each DISTINCT label set of a batch, each row's
    index into them): `tsid_hash` of the set's non-null (label, value)
    pairs and `__name__`, hashed once a set.  Each label column is
    dictionary-encoded (as it comes, or by one hash pass), the rows' codes
    are folded into one int64 key a row, and the distinct keys are the
    distinct label sets; a key space past 2^62 is made dense again before
    the next column joins it."""
    import pyarrow.compute as pc

    from ..utils import metrics

    if n == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    names, values, indices = [], [], []
    key = np.zeros(n, np.int64)
    space = 1
    for name, col in labels.items():
        if isinstance(col, pa.ChunkedArray):
            col = col.combine_chunks()
        if not pa.types.is_dictionary(col.type):
            col = pc.dictionary_encode(col)
        vals = col.dictionary.to_pylist()
        # a row's digit in the key: 0 = the label is absent from the row,
        # else 1 + the value's index
        index = col.indices
        if index.null_count:
            index = pc.fill_null(index, -1)
        index = index.to_numpy(zero_copy_only=False)
        card = len(vals) + 1
        if space * card >= 1 << 62:
            dense = pc.dictionary_encode(pa.array(key))
            key = dense.indices.to_numpy().astype(np.int64)
            space = len(dense.dictionary)
        # in place: a fresh 8 B x rows array a step costs more than the step
        np.multiply(key, card, out=key)
        np.add(key, index, out=key)
        key += 1
        space *= card
        names.append(name)
        values.append(vals)
        indices.append(index)
    # distinct keys by one hash pass; a set's first row by one scatter (of
    # the rows written back to front the first is the one that stays)
    sets = pc.dictionary_encode(pa.array(key))
    inverse = sets.indices.to_numpy()
    first = np.empty(len(sets.dictionary), np.int64)
    first[inverse[::-1]] = np.arange(n - 1, -1, -1)
    picked = [index[first].tolist() for index in indices]
    hashes = np.empty(len(first), np.int64)
    for i in range(len(first)):
        pairs = [("__name__", metric)]
        for name, vals, at in zip(names, values, picked):
            if at[i] >= 0 and vals[at[i]] is not None:
                pairs.append((name, vals[at[i]]))
        hashes[i] = tsid_hash(pairs)
    metrics.METRIC_TSID_HASHES.inc(len(first))
    return hashes, inverse


class MetadataRegion:
    """The metadata half of the region pair: which logical tables live on a
    physical table and which columns each owns (reference
    src/metric-engine/src/metadata_region.rs — there a mito region with
    key/value rows; here a fsynced JSON journal per physical table)."""

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        self.logical: dict[str, dict] = {}
        if os.path.exists(path):
            with open(path) as f:
                self.logical = json.load(f)["logical"]

    def add_logical(self, qualified: str, table_id: int, columns: list[str]):
        with self._lock:
            self.logical[qualified] = {"table_id": table_id, "columns": columns}
            self._persist()

    def update_columns(self, qualified: str, columns: list[str]):
        with self._lock:
            self.logical[qualified]["columns"] = columns
            self._persist()

    def remove_logical(self, qualified: str):
        with self._lock:
            self.logical.pop(qualified, None)
            self._persist()

    def _persist(self):
        tmp = self.path + ".tmp"
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        with open(tmp, "w") as f:
            json.dump({"logical": self.logical}, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.path)


class MetricEngine:
    """Facade over the catalog + storage engine (reference
    src/metric-engine/src/engine.rs:130 `MetricEngine` over mito2)."""

    def __init__(self, db):
        self.db = db
        self._meta_regions: dict[str, MetadataRegion] = {}
        self._lock = threading.Lock()
        # Serializes logical-table DDL: concurrent ingest threads racing
        # create/widen of the same metric (ThreadingHTTPServer handlers) must
        # not double-create (the reference serializes DDL through the
        # procedure framework's key locks, common/procedure/src/local/rwlock.rs).
        self._ddl_lock = threading.RLock()

    # ---- metadata region handles -----------------------------------------
    def _metadata_region(self, phys_meta: TableMeta) -> MetadataRegion:
        key = f"{phys_meta.database}.{phys_meta.name}"
        with self._lock:
            if key not in self._meta_regions:
                path = os.path.join(
                    self.db.config.storage.data_home,
                    "metric_metadata",
                    f"{phys_meta.table_id}.json",
                )
                self._meta_regions[key] = MetadataRegion(path)
            return self._meta_regions[key]

    # ---- DDL --------------------------------------------------------------
    def create_physical_table(
        self,
        name: str,
        database: str = DEFAULT_SCHEMA,
        ts_col: str = TS_COL,
        val_col: str = VAL_COL,
        if_not_exists: bool = False,
    ) -> TableMeta:
        """Data region schema: ts + value + (__table_id, __tsid) tags.
        Label columns are added lazily as logical tables appear (reference
        engine/create.rs create_physical_region)."""
        with self._ddl_lock:
            return self._create_physical_table_locked(
                name, database, ts_col, val_col, if_not_exists
            )

    def _create_physical_table_locked(
        self, name, database, ts_col, val_col, if_not_exists
    ) -> TableMeta:
        columns = [
            ColumnSchema(ts_col, ConcreteDataType.TIMESTAMP_MILLISECOND, SemanticType.TIMESTAMP),
            ColumnSchema(val_col, ConcreteDataType.FLOAT64, SemanticType.FIELD),
            ColumnSchema(TABLE_ID_COL, ConcreteDataType.INT64, SemanticType.TAG, nullable=False),
            ColumnSchema(TSID_COL, ConcreteDataType.INT64, SemanticType.TAG, nullable=False),
        ]
        meta = self.db.catalog.create_table(
            name,
            Schema(columns=columns),
            database=database,
            if_not_exists=if_not_exists,
            options={PHYSICAL_TABLE_OPT: "", "ts_col": ts_col, "val_col": val_col},
            on_create=lambda m: [
                self.db.storage.create_region(rid, m.schema) for rid in m.region_ids
            ],
        )
        return meta

    def ensure_physical_table(
        self, name: str, database: str = DEFAULT_SCHEMA
    ) -> TableMeta:
        """Create-if-absent with regions guaranteed to exist on return —
        safe under concurrent ingest threads (the bare catalog has_table
        check can observe the catalog entry before the data region)."""
        with self._ddl_lock:
            if self.db.catalog.has_table(name, database):
                return self.db.catalog.table(name, database)
            return self._create_physical_table_locked(
                name, database, TS_COL, VAL_COL, True
            )

    def create_logical_table(
        self,
        name: str,
        labels: list[str],
        physical: str,
        database: str = DEFAULT_SCHEMA,
        ts_col: str | None = None,
        val_col: str | None = None,
        if_not_exists: bool = False,
    ) -> TableMeta:
        """Register a logical table and make sure the physical data region
        has every label column (reference engine/create.rs
        create_logical_tables → alter physical on demand)."""
        with self._ddl_lock:
            return self._create_logical_table_locked(
                name, labels, physical, database, ts_col, val_col, if_not_exists
            )

    def _create_logical_table_locked(
        self, name, labels, physical, database, ts_col, val_col, if_not_exists
    ) -> TableMeta:
        if self.db.catalog.has_table(name, database):
            if if_not_exists:
                return self.db.catalog.table(name, database)
            raise TableAlreadyExistsError(f"table {name!r} already exists")
        phys_meta = self.db.catalog.table(physical, database)
        if not is_physical_meta(phys_meta):
            raise InvalidArgumentsError(
                f"{physical!r} is not a physical metric table"
            )
        ts_col = ts_col or phys_meta.options.get("ts_col", TS_COL)
        val_col = val_col or phys_meta.options.get("val_col", VAL_COL)
        self._ensure_physical_labels(phys_meta, labels)
        columns = [
            ColumnSchema(ts_col, ConcreteDataType.TIMESTAMP_MILLISECOND, SemanticType.TIMESTAMP),
            ColumnSchema(val_col, ConcreteDataType.FLOAT64, SemanticType.FIELD),
        ] + [
            ColumnSchema(lbl, ConcreteDataType.STRING, SemanticType.TAG, nullable=True)
            for lbl in sorted(labels)
        ]
        meta = self.db.catalog.create_table(
            name,
            Schema(columns=columns),
            database=database,
            options={
                LOGICAL_TABLE_OPT: physical,
                "ts_col": ts_col,
                "val_col": val_col,
            },
        )
        self._metadata_region(phys_meta).add_logical(
            f"{database}.{name}", meta.table_id, sorted(labels)
        )
        return meta

    def ensure_logical_table(
        self,
        name: str,
        labels: list[str],
        physical: str,
        database: str = DEFAULT_SCHEMA,
    ) -> TableMeta:
        """Auto-create-or-widen used by the ingest path (reference
        operator Inserter create_or_alter_tables_on_demand for the metric
        engine's logical tables)."""
        with self._ddl_lock:
            if not self.db.catalog.has_table(name, database):
                return self._create_logical_table_locked(
                    name, labels, physical, database, None, None, True
                )
            meta = self.db.catalog.table(name, database)
            if not is_logical_meta(meta):
                raise InvalidArgumentsError(
                    f"{name!r} is not a metric-engine logical table"
                )
            missing = [l for l in labels if not meta.schema.has_column(l)]
            if missing:
                phys_meta = self.db.catalog.table(
                    meta.options[LOGICAL_TABLE_OPT], database
                )
                self._ensure_physical_labels(phys_meta, missing)
                schema = meta.schema
                for lbl in sorted(missing):
                    schema = schema.add_column(
                        ColumnSchema(
                            lbl, ConcreteDataType.STRING, SemanticType.TAG, nullable=True
                        )
                    )
                meta.schema = schema
                self.db.catalog.update_table(meta)
                self._metadata_region(phys_meta).update_columns(
                    f"{database}.{name}",
                    sorted(c.name for c in schema.tag_columns()),
                )
            return meta

    def write_series_rows(
        self,
        rows: dict[str, list[tuple[dict, int, float]]],
        physical_table: str,
        database: str = DEFAULT_SCHEMA,
    ) -> int:
        """Ingest metric -> [(labels, ts_ms, value)] rows, auto-creating or
        widening one logical table per metric.  Shared by the Prometheus
        remote-write and OTLP metrics paths (the reference funnels both
        through row_writer::MultiTableData the same way)."""
        import pyarrow as pa

        if not rows:
            return 0
        self.ensure_physical_table(physical_table, database)
        total = 0
        for metric, entries in rows.items():
            label_names = sorted({k for labels, _, _ in entries for k in labels})
            meta = self.ensure_logical_table(
                metric, label_names, physical_table, database
            )
            ts_name = meta.schema.time_index.name
            val_name = meta.schema.field_columns()[0].name
            cols: dict[str, list] = {ts_name: [], val_name: []}
            for lbl in label_names:
                cols[lbl] = []
            for labels, ts_ms, value in entries:
                cols[ts_name].append(ts_ms)
                cols[val_name].append(value)
                for lbl in label_names:
                    cols[lbl].append(labels.get(lbl))
            arrays = {
                ts_name: pa.array(cols[ts_name], pa.timestamp("ms")),
                val_name: pa.array(cols[val_name], pa.float64()),
            }
            for lbl in label_names:
                arrays[lbl] = pa.array(cols[lbl], pa.string())
            total += self.db.insert_rows(metric, pa.table(arrays), database=database)
        return total

    def drop_logical_table(self, meta: TableMeta):
        """Remove the registration; rows stay in the data region until
        compaction GC (the reference likewise drops metadata only)."""
        phys_meta = self.db.catalog.table(meta.options[LOGICAL_TABLE_OPT], meta.database)
        self._metadata_region(phys_meta).remove_logical(f"{meta.database}.{meta.name}")
        self.db.catalog.drop_table(meta.name, meta.database)

    def drop_physical_table(self, meta: TableMeta):
        leftovers = [
            m.name
            for m in self.db.catalog.tables(meta.database)
            if is_logical_meta(m) and m.options[LOGICAL_TABLE_OPT] == meta.name
        ]
        if leftovers:
            raise InvalidArgumentsError(
                f"physical table {meta.name!r} still hosts logical tables: {leftovers}"
            )
        self.db.catalog.drop_table(meta.name, meta.database)
        for rid in meta.region_ids:
            self.db.storage.drop_region(rid)
        # Drop the metadata-region journal + cached handle so a recreated
        # physical table of the same name starts clean.
        key = f"{meta.database}.{meta.name}"
        with self._lock:
            reg = self._meta_regions.pop(key, None)
        path = reg.path if reg is not None else os.path.join(
            self.db.config.storage.data_home, "metric_metadata", f"{meta.table_id}.json"
        )
        if os.path.exists(path):
            os.remove(path)

    def _ensure_physical_labels(self, phys_meta: TableMeta, labels: list[str]):
        missing = [l for l in labels if not phys_meta.schema.has_column(l)]
        if not missing:
            return
        schema = phys_meta.schema
        for lbl in sorted(missing):
            schema = schema.add_column(
                ColumnSchema(lbl, ConcreteDataType.STRING, SemanticType.TAG, nullable=True)
            )
        phys_meta.schema = schema
        self.db.catalog.update_table(phys_meta)
        for rid in phys_meta.region_ids:
            self.db.storage.region(rid).alter_schema(schema)

    # ---- write path -------------------------------------------------------
    def write_logical(self, meta: TableMeta, batch: pa.RecordBatch) -> int:
        """Inject __table_id/__tsid and write into the data region
        (reference row_modifier.rs + engine/put.rs)."""
        phys_meta = self.db.catalog.table(meta.options[LOGICAL_TABLE_OPT], meta.database)
        with tracing.stage("write.logical", table=meta.name, rows=batch.num_rows) as st:
            phys_batch = self._physical_batch(meta, phys_meta, batch, st)
        return self.db.write_batch(phys_meta, phys_batch)

    def _physical_batch(
        self, meta: TableMeta, phys_meta: TableMeta, batch: pa.RecordBatch, st
    ) -> pa.RecordBatch:
        """A logical table's batch on the physical schema; `st`, the
        `write.logical` stage, takes `labels` and `tsids` (the distinct
        label sets hashed)."""
        # SNAPSHOT the physical schema once: concurrent logical-table
        # creation widens the physical table by REPLACING phys_meta.schema
        # (_ensure_physical_labels under _ddl_lock), and round 4 read it
        # twice — once to build the arrays, once in from_arrays — so a
        # widen in between raised "Schema and number of arrays unequal"
        # on the Prometheus ingest hot path.  A consistent old-schema
        # batch is always safe: the region's read path null-fills columns
        # a batch predates (_compat_cast), matching the reference's
        # serialized logical DDL (metric-engine/src/engine.rs:58-90).
        phys_schema = phys_meta.schema
        label_cols = [c.name for c in meta.schema.tag_columns()]
        n = batch.num_rows
        # Map logical ts/value columns onto the physical pair by semantic
        # role, so differing names still land correctly (reference
        # row_modifier maps by column id, not name).
        remap: dict[str, str] = {}
        phys_ts = phys_meta.options.get("ts_col", TS_COL)
        phys_val = phys_meta.options.get("val_col", VAL_COL)
        if meta.schema.time_index is not None:
            remap[phys_ts] = meta.schema.time_index.name
        fields = meta.schema.field_columns()
        if fields:
            remap[phys_val] = fields[0].name
        hashes, inverse = _label_set_tsids(
            meta.name,
            {
                name: batch.column(batch.schema.get_field_index(name))
                for name in label_cols
                if batch.schema.get_field_index(name) >= 0
            },
            n,
        )
        tsids = hashes[inverse]
        st.set(labels=len(label_cols), tsids=len(hashes))
        # Conform to the physical schema: logical ts/val keep their names
        # (schemas share them); absent physical labels become nulls.
        by_name = {batch.schema.field(i).name: batch.column(i) for i in range(batch.num_columns)}
        arrays = []
        for col in phys_schema.columns:
            source = remap.get(col.name, col.name)
            if col.name == TABLE_ID_COL:
                arrays.append(pa.array(np.full(n, meta.table_id, np.int64)))
            elif col.name == TSID_COL:
                arrays.append(pa.array(tsids, pa.int64()))
            elif source in by_name:
                arr = by_name[source]
                want = col.data_type.to_arrow()
                if arr.type != want:
                    arr = arr.cast(want)
                arrays.append(arr)
            else:
                arrays.append(pa.nulls(n, col.data_type.to_arrow()))
        return pa.RecordBatch.from_arrays(arrays, schema=phys_schema.to_arrow())

    # ---- read path --------------------------------------------------------
    def scan_logical(self, meta: TableMeta, scan) -> list[pa.Table]:
        """Per-region scan of the data region filtered to this logical
        table, projected to the logical schema (reference engine/read.rs
        transforms the request onto the physical region).

        Only `__table_id` + time range are pushed into the SST scan — label
        predicates are applied after projection so SSTs written before a
        label column existed (rows = NULL for that label) filter correctly.
        """
        phys_meta = self.db.catalog.table(meta.options[LOGICAL_TABLE_OPT], meta.database)
        pred = ScanPredicate(
            time_range=scan.time_range if scan is not None else None,
            filters=[(TABLE_ID_COL, "=", meta.table_id)],
        )
        label_filters = [tuple(f) for f in (scan.filters if scan is not None else [])]
        out = []
        for rid in phys_meta.region_ids:
            t = self.db.storage.scan(rid, pred)
            t = self._project_logical(t, meta)
            if label_filters:
                t = _apply_residual(
                    t, ScanPredicate(time_range=None, filters=label_filters), None
                )
            out.append(t)
        return out

    def _project_logical(self, table: pa.Table, meta: TableMeta) -> pa.Table:
        phys_meta = self.db.catalog.table(meta.options[LOGICAL_TABLE_OPT], meta.database)
        # Inverse of the write-side remap: logical ts/value read from the
        # physical pair whatever the logical names are.
        remap: dict[str, str] = {}
        if meta.schema.time_index is not None:
            remap[meta.schema.time_index.name] = phys_meta.options.get("ts_col", TS_COL)
        fields = meta.schema.field_columns()
        if fields:
            remap[fields[0].name] = phys_meta.options.get("val_col", VAL_COL)
        arrays = []
        for col in meta.schema.columns:
            source = remap.get(col.name, col.name)
            if source in table.column_names:
                arr = table[source]
                want = col.data_type.to_arrow()
                if arr.type != want:
                    arr = arr.cast(want)
                arrays.append(arr)
            else:
                arrays.append(pa.nulls(table.num_rows, col.data_type.to_arrow()))
        return pa.Table.from_arrays(arrays, schema=meta.schema.to_arrow())

    # ---- introspection ----------------------------------------------------
    def logical_tables(self, physical: str, database: str = DEFAULT_SCHEMA) -> list[str]:
        phys_meta = self.db.catalog.table(physical, database)
        reg = self._metadata_region(phys_meta)
        return sorted(name.split(".", 1)[1] for name in reg.logical)
