"""Database: the standalone all-in-one facade.

Role-equivalent of the reference's standalone mode gluing frontend +
datanode + metadata into one process (reference cmd/src/standalone.rs:327):
catalog (metadata plane) + TimeSeriesEngine (region engine) + QueryEngine
(SQL/PromQL) + row routing via partition rules (the reference Inserter's
split_rows fan-out, operator/src/insert.rs:321).
"""

from __future__ import annotations

import os
import threading

import numpy as np
import pyarrow as pa

from .datatypes.data_type import ConcreteDataType
from .datatypes.schema import ColumnSchema, Schema, SemanticType
from .models.catalog import DEFAULT_SCHEMA, Catalog, region_id
from .models.partition import HashPartitionRule, SingleRegionRule
from .query.engine import QueryEngine
from .query.logical_plan import TableScan
from .query.expr import Column
from .query.sql_parser import (
    AdminStmt,
    AlterTableStmt,
    CloseCursorStmt,
    CopyStmt,
    CreateDatabaseStmt,
    CreateFlowStmt,
    CreateViewStmt,
    CreateTableStmt,
    DeclareCursorStmt,
    DeleteStmt,
    DescribeStmt,
    DropStmt,
    ExplainFlowStmt,
    ExplainStmt,
    FetchCursorStmt,
    InsertStmt,
    KillStmt,
    SelectStmt,
    SetStmt,
    ShowStmt,
    TqlStmt,
    TransactionStmt,
    TruncateStmt,
    UseStmt,
    parse_sql,
)
from .metric.engine import (
    LOGICAL_TABLE_OPT,
    PHYSICAL_TABLE_OPT,
    MetricEngine,
    is_logical_meta,
    is_physical_meta,
)
from .storage.engine import TimeSeriesEngine
from .storage.sst import ScanPredicate
from .utils import tracing
from .utils.config import Config
from .utils.errors import (
    DatabaseNotFoundError,
    InvalidArgumentsError,
    PlanError,
    TableNotFoundError,
    UnsupportedError,
)


class SessionState:
    """Per-connection mutable state (reference session/src/context.rs
    QueryContext: schema, timezone, cursors)."""

    __slots__ = ("database", "timezone", "cursors")

    def __init__(self):
        self.database: str | None = None
        self.timezone: str | None = None
        self.cursors: dict = {}


import contextvars as _contextvars

# maps id(Database) -> SessionState within one connection's context
_SESSION_TOKENS = __import__("itertools").count()
_SESSION: _contextvars.ContextVar[dict | None] = _contextvars.ContextVar(
    "gt_session", default=None
)


class Database:
    def __init__(
        self,
        config: Config | None = None,
        data_home: str | None = None,
        plugins=None,
    ):
        from .utils.plugins import Plugins

        self.config = config or Config()
        self.plugins = plugins or Plugins()
        if data_home is not None:
            self.config.storage.data_home = data_home
            # wal/sst dirs derive from data_home at use time
            # (StorageConfig.effective_*_dir) — never bake them here
            self.config.storage.wal_dir = ""
            self.config.storage.sst_dir = ""
        self.storage = TimeSeriesEngine(self.config.storage)
        catalog_path = os.path.join(self.config.storage.data_home, "catalog.json")
        self.catalog = Catalog(catalog_path)
        # Serializes schema-mutating DDL (auto-alter on ingest, ALTER TABLE)
        # the way the reference's DDL procedures take key-range locks
        # (common/procedure/src/local/rwlock.rs).
        self.ddl_lock = threading.RLock()

        self.metric = MetricEngine(self)
        from .flow.engine import FlowManager

        self.flows = FlowManager(self)
        # Per-thread session database (reference QueryContext carries the
        # schema per connection): protocol servers handle each connection on
        # its own thread, so USE / startup database choices must not leak
        # across connections sharing this Database.
        self._default_database = DEFAULT_SCHEMA
        from .models.process import ProcessManager

        # Running-query registry behind information_schema.process_list and
        # KILL (reference catalog/src/process_manager.rs:43).
        self.process_manager = ProcessManager()
        from .utils.events import EventRecorder
        from .utils.memory import MemoryGovernor

        # Slow queries + system events into greptime_private (reference
        # common/event-recorder); admission budgets (common/memory-manager).
        self.event_recorder = EventRecorder(self)
        self.memory = MemoryGovernor(
            self.config.memory.max_in_flight_write_bytes,
            self.config.memory.max_concurrent_queries,
            getattr(self.config.memory, "max_scan_bytes", 0),
            gate_wait_s=getattr(self.config.memory, "gate_wait_s", 5.0),
        )
        from .utils.admission import AdmissionController

        # Multi-tenant admission in FRONT of the flat memory gates: which
        # statement runs next (weighted fairness + EDF), and which should
        # not wait at all (queue-depth / wait-time / deadline shedding).
        # Off by default — admission.enable=False is a pure pass-through.
        self.admission = AdmissionController(
            self.config.admission, self.config.memory
        )
        from .storage.dictionary import DictionaryRegistry
        from .utils.jax_env import ensure_compilation_cache

        ensure_compilation_cache()

        # Persisted super-tile consolidations live beside the data so a
        # fresh process mmaps them instead of re-decoding Parquet.
        if not self.config.query.tile_persist_dir:
            self.config.query.tile_persist_dir = os.path.join(
                self.config.storage.data_home, "tile_cache"
            )
        # Per-table tag dictionaries backing the HBM tile cache (stable
        # codes across files/queries — reference mito-codec pre-encoded keys).
        self.dicts = DictionaryRegistry(
            os.path.join(self.config.storage.data_home, "dicts")
        )
        self.query_engine = QueryEngine(
            schema_provider=self._schema_of,
            scan_provider=self._scan,
            region_scan_provider=self._region_scan,
            time_bounds_provider=self._time_bounds,
            config=self.config.query,
            tile_context_provider=self._tile_context,
            view_provider=self._view_stmt,
            vector_search_provider=self._vector_search,
        )
        # Lifecycle knobs (tile.incremental delta maintenance,
        # tile.pipelined_build) reach the cache through config.tile, read
        # at decision time so tests and operators can flip them live.
        # Device flight recorder: per-dispatch introspection ring behind
        # information_schema.device_dispatches / EXPLAIN ANALYZE's
        # device-stage split / /debug/tile.  The ring is process-wide
        # (like the span exporter); the most recently opened Database's
        # knobs govern it.
        from .utils import flight_recorder as _flight_recorder

        _flight_recorder.RECORDER.configure(getattr(self.config, "recorder", None))
        # Device health supervisor: process-wide like the recorder — the
        # most recently opened Database's device.* knobs govern it.  It
        # must see the tile cache's device list (not jax.devices()) so
        # health state lines up with chunk-placement indices.
        from .utils import device_health as _device_health

        _device_health.SUPERVISOR.configure(
            getattr(self.config, "device", None),
            self.query_engine.tile_cache.devices
            if self.query_engine.tile_cache is not None
            else None,
        )
        if self.query_engine.tile_cache is not None:
            self.query_engine.tile_cache.tile_config = self.config.tile
            # overload-survival knobs (dispatch coalescing, HBM feedback)
            self.query_engine.tile_cache.admission_config = self.config.admission
            # cross-query batching window + windowed result cache
            self.query_engine.tile_cache.batch_config = self.config.batch
            from .utils import metrics as _metrics

            _metrics.HBM_CHUNK_ROWS.set(self.query_engine.tile_cache.chunk_rows)
            if self.config.admission.hbm_probe:
                self.query_engine.tile_cache.probe_hbm(
                    self.config.admission.hbm_probe_headroom
                )
        from collections import OrderedDict

        from .utils.telemetry_report import TelemetryTask

        # plan cache: (sql text, database) -> (catalog revision, plan, schema)
        self._plan_cache: OrderedDict = OrderedDict()
        self._session_token = next(_SESSION_TOKENS)
        self._plan_cache_lock = threading.Lock()
        self.telemetry = TelemetryTask(self, self.config.telemetry).start()
        self._reopen_regions()
        self._prewarm_thread = None
        if getattr(self.config, "tile", None) is not None and self.config.tile.prewarm_on_flush:
            self._start_flush_prewarmer()

    # ---- session state (reference session QueryContext) -------------------
    # Stored in a contextvar holding MUTABLE per-connection state, not a
    # threading.local: query execution hops to the kernel-executor thread
    # (utils/kernel_executor.py), which runs closures under a COPY of the
    # caller's context — mutations land in the shared SessionState object,
    # so SET/USE made inside executed statements stay visible to the
    # connection thread, while separate connections stay isolated.
    def ensure_session(self):
        """Get-or-create this connection's session.  Protocol servers call
        this on their handler thread before dispatching work so the state
        object is anchored in the connection's own context.

        Keyed by a process-unique instance token, NOT id(self): a context's
        session dict outlives any one Database, and CPython recycles ids,
        so a new Database could inherit a closed one's session state
        (observed as flaky database/timezone leakage across the sqlness
        runner's sequential Databases)."""
        sessions = _SESSION.get()
        if sessions is None:
            sessions = {}
            _SESSION.set(sessions)
        s = sessions.get(self._session_token)
        if s is None:
            s = sessions[self._session_token] = SessionState()
        return s

    @property
    def current_database(self) -> str:
        return self.ensure_session().database or self._default_database

    @current_database.setter
    def current_database(self, value: str):
        self.ensure_session().database = value

    # ---- session timezone (reference QueryContext timezone) ---------------
    @property
    def session_timezone(self) -> str:
        return self.ensure_session().timezone or "UTC"

    def set_session_timezone(self, tz: str):
        self.session_tz_offset_minutes(tz)  # validates
        self.ensure_session().timezone = tz

    def session_tz_offset_minutes(self, tz: str | None = None) -> int:
        """Current offset of the session zone (validation + fixed-offset
        rendering); DST-correct per-value conversion uses session_tzinfo."""
        info = self.session_tzinfo(tz)
        if info is None:
            return 0
        import datetime as _dt

        off = _dt.datetime.now(_dt.timezone.utc).astimezone(info).utcoffset()
        return int(off.total_seconds() // 60) if off else 0

    def session_tzinfo(self, tz: str | None = None):
        """tzinfo for the session zone, or None for UTC.  Named zones keep
        their DST rules so each VALUE converts with the offset in force at
        that instant (the reference converts per-value the same way)."""
        tz = tz if tz is not None else self.session_timezone
        t = tz.strip()
        if t.upper() in ("UTC", "GMT", "SYSTEM", "Z", ""):
            return None
        import datetime as _dt
        import re as _re

        m = _re.match(r"^([+-])(\d{1,2}):(\d{2})$", t)
        if m:
            sign = 1 if m.group(1) == "+" else -1
            minutes = sign * (int(m.group(2)) * 60 + int(m.group(3)))
            return _dt.timezone(_dt.timedelta(minutes=minutes))
        try:
            from zoneinfo import ZoneInfo

            return ZoneInfo(t)
        except Exception as exc:  # noqa: BLE001
            raise InvalidArgumentsError(f"unknown time zone: {tz!r}") from exc

    def close(self):
        if getattr(self, "_prewarm_thread", None) is not None:
            with self._prewarm_cv:
                self._prewarm_stop = True
                self._prewarm_cv.notify()
            self._prewarm_thread.join(timeout=5.0)
        te = getattr(self.query_engine, "_tile_executor", None)
        if te is not None:
            # stop the fused family builder: pending background builds are
            # abandoned and their waiters woken before storage closes
            te.shutdown_fused()
        from .utils import self_trace

        self_trace.stop(self)
        self.telemetry.stop()
        self.event_recorder.stop()
        self.flows.stop()
        self.storage.close()

    # ---- SQL entry --------------------------------------------------------
    def sql(self, text: str):
        """Execute ;-separated SQL; returns a list of results (pa.Table for
        queries, int affected-rows for writes, None for DDL)."""
        from .utils.plugins import SqlQueryInterceptor

        interceptors = self.plugins.get_all(SqlQueryInterceptor)
        ctx = {"database": self.current_database}
        for ic in interceptors:
            text = ic.pre_parsing(text, ctx)
        with tracing.stage("query.parse"):
            stmts = parse_sql(text)
        # plan-cacheable only when the text is exactly one SELECT (the cache
        # key is the full text; see _execute).  ALIGN TO NOW plans are
        # rejected at plan level (plan_uncacheable) wherever they nest.
        cacheable = len(stmts) == 1 and isinstance(stmts[0], SelectStmt)
        results = []
        for stmt in stmts:
            for ic in interceptors:
                ic.pre_execute(stmt, ctx)
            result = self._execute(
                stmt, query_text=text, plan_cacheable=cacheable
            )
            for ic in interceptors:
                result = ic.post_execute(stmt, result, ctx)
            results.append(result)
        return results

    def sql_one(self, text: str):
        out = self.sql(text)
        return out[-1] if out else None

    # ---- dispatch (reference StatementExecutor::execute_stmt) -------------
    def _execute(self, stmt, query_text: str | None = None, plan_cacheable: bool = False):
        from .utils.events import SlowQueryTimer

        if isinstance(stmt, SelectStmt):
            from .utils.deadline import deadline_scope
            from .utils.self_trace import statement_trace

            # statement_trace is OUTERMOST so admission queue wait, the
            # memory gate and the whole engine pipeline are stages of the
            # statement's trace (and the tail decision sees the true
            # end-to-end latency); off (trace.self=false) it is a pure
            # pass-through
            with statement_trace(
                self, "sql", query_text or "SELECT ...", self.current_database
            ), deadline_scope(
                self.config.query.timeout_s
            ), self.admission.admit(
                self.current_database
            ), self.memory.query_guard(), self.process_manager.track(
                self.current_database, query_text or "SELECT ..."
            ), SlowQueryTimer(
                self.event_recorder, self.config.slow_query,
                query_text or "SELECT ...", self.current_database,
            ):
                if plan_cacheable and query_text:
                    return self._execute_select_cached(stmt, query_text)
                return self.query_engine.execute_select(stmt, self.current_database)
        if isinstance(stmt, CreateTableStmt):
            return self._create_table(stmt)
        if isinstance(stmt, CreateDatabaseStmt):
            self.catalog.create_database(stmt.name, if_not_exists=stmt.if_not_exists)
            return None
        if isinstance(stmt, CreateFlowStmt):
            self.flows.create_flow(stmt, self.current_database)
            return None
        if isinstance(stmt, CreateViewStmt):
            return self._create_view(stmt)
        if isinstance(stmt, DropStmt):
            return self._drop(stmt)
        if isinstance(stmt, InsertStmt):
            from .utils.self_trace import statement_trace

            # the WRITE hot path is traced too: routing, per-region WAL
            # appends and flow mirroring all become child stages
            with statement_trace(
                self, "insert", query_text or "INSERT ...",
                self.current_database,
            ):
                return self._insert(stmt)
        if isinstance(stmt, ShowStmt):
            return self._show(stmt)
        if isinstance(stmt, DescribeStmt):
            return self._describe(stmt)
        if isinstance(stmt, ExplainFlowStmt):
            return self._explain_flow(stmt.name)
        if isinstance(stmt, ExplainStmt):
            if isinstance(stmt.inner, SelectStmt):
                if stmt.analyze:
                    return self.query_engine.explain_analyze(
                        stmt.inner, self.current_database
                    )
                return self.query_engine.explain(stmt.inner, self.current_database)
            raise UnsupportedError("EXPLAIN only supports SELECT")
        if isinstance(stmt, UseStmt):
            from .models import information_schema as info

            from .models import pg_catalog as pg

            if (
                stmt.database not in self.catalog.databases()
                and not info.is_information_schema(stmt.database)
                and not pg.is_pg_catalog(stmt.database)
            ):
                raise InvalidArgumentsError(f"database not found: {stmt.database}")
            self.current_database = stmt.database
            return None
        if isinstance(stmt, AdminStmt):
            return self._admin(stmt)
        if isinstance(stmt, TqlStmt):
            from .utils.self_trace import statement_trace

            with statement_trace(
                self, "tql", query_text or "TQL ...", self.current_database,
                is_promql=True,
            ), self.admission.admit(
                self.current_database
            ), self.memory.query_guard(), self.process_manager.track(
                self.current_database, query_text or "TQL ..."
            ), SlowQueryTimer(
                self.event_recorder, self.config.slow_query,
                query_text or "TQL ...", self.current_database, is_promql=True,
            ):
                return self._tql(stmt)
        if isinstance(stmt, DeclareCursorStmt):
            cursors = self._session_cursors()
            if stmt.name in cursors:
                raise InvalidArgumentsError(f"cursor {stmt.name!r} already open")
            result = self._execute(stmt.select, query_text=query_text)
            cursors[stmt.name] = [result, 0]  # (materialized table, position)
            return None
        if isinstance(stmt, FetchCursorStmt):
            cursors = self._session_cursors()
            if stmt.name not in cursors:
                raise InvalidArgumentsError(f"cursor {stmt.name!r} is not open")
            table, pos = cursors[stmt.name]
            if stmt.count < 0:  # FETCH ALL
                out = table.slice(pos)
                cursors[stmt.name][1] = table.num_rows
            else:
                out = table.slice(pos, stmt.count)
                cursors[stmt.name][1] = min(pos + stmt.count, table.num_rows)
            return out
        if isinstance(stmt, CloseCursorStmt):
            cursors = self._session_cursors()
            if cursors.pop(stmt.name, None) is None:
                raise InvalidArgumentsError(f"cursor {stmt.name!r} is not open")
            return None
        if isinstance(stmt, KillStmt):
            self.process_manager.kill(stmt.process_id)
            return None
        if isinstance(stmt, DeleteStmt):
            return self._delete(stmt)
        if isinstance(stmt, AlterTableStmt):
            return self._alter(stmt)
        if isinstance(stmt, TruncateStmt):
            return self._truncate(stmt)
        if isinstance(stmt, CopyStmt):
            return self._copy(stmt)
        if isinstance(stmt, SetStmt):
            # session variables (reference session/src/context.rs): the
            # timezone affects timestamp TEXT rendering on the wire servers;
            # everything else is accepted client-bootstrap noise
            import re as _re

            m = _re.match(
                r"(?is)^(?:set\s+)?(?:session\s+|local\s+)?(?:@@)?(?:session\.)?time[\s_]*zone\s*(?:=|to)?\s*'?([^';]+)'?",
                stmt.raw,
            )
            if m:
                self.set_session_timezone(m.group(1).strip())
                return None
            if _re.match(r"(?is)^set\s+session\s+disabled_passes\b", stmt.raw):
                raise InvalidArgumentsError(
                    "disabled_passes is instance-global (it reconfigures "
                    "the shared query engine); use SET [GLOBAL] "
                    "disabled_passes = '...'"
                )
            m = _re.match(
                r"(?is)^set\s+(?:global\s+)?disabled_passes\s*(?:=|to)\s*"
                r"(?:'([^']*)'|([A-Za-z0-9_,\s]+?))\s*;?\s*$",
                stmt.raw,
            )
            if m:
                # operator control over the optimizer-pass pipeline
                # (query/passes.py registry; EXPLAIN shows the effect) —
                # GLOBAL semantics: the engine is shared, so this changes
                # planning for every connection until reset
                from .query import passes as _passes

                raw_val = m.group(1) if m.group(1) is not None else m.group(2)
                names = tuple(
                    n.strip() for n in raw_val.split(",") if n.strip()
                )
                known = {p.name for p in _passes.registry()}
                bad = [n for n in names if n not in known]
                if bad:
                    raise InvalidArgumentsError(
                        f"unknown optimizer pass(es) {bad}; known: "
                        f"{sorted(known)}"
                    )
                self.config.query.disabled_passes = names
            return None
        if isinstance(stmt, TransactionStmt):
            return None  # accepted client-bootstrap no-ops
        raise UnsupportedError(f"unsupported statement: {type(stmt).__name__}")

    def execute_stmt(self, stmt, query_text: str | None = None):
        """Execute one parsed statement (protocol servers dispatch per
        statement to derive wire-level command tags; pass the original SQL
        so process_list shows real query text)."""
        return self._execute(stmt, query_text=query_text)

    # ---- DDL --------------------------------------------------------------
    def _create_table(self, stmt: CreateTableStmt):
        if stmt.external or stmt.engine == "file":
            return self._create_external_table(stmt)

        # Metric-engine routing (reference metric-engine DDL rewrite,
        # src/metric-engine/src/engine/create.rs).
        if PHYSICAL_TABLE_OPT in stmt.options or (
            stmt.engine == "metric" and LOGICAL_TABLE_OPT not in stmt.options
        ):
            ts = stmt.time_index or next(
                (c.name for c in stmt.columns if c.is_time_index), None
            )
            pks = set(stmt.primary_key) | {
                c.name for c in stmt.columns if c.is_primary_key
            }
            val = next(
                (
                    c.name
                    for c in stmt.columns
                    if not c.is_time_index and c.name != ts and c.name not in pks
                ),
                None,
            )
            self.metric.create_physical_table(
                stmt.name,
                database=self.current_database,
                ts_col=ts or "greptime_timestamp",
                val_col=val or "greptime_value",
                if_not_exists=stmt.if_not_exists,
            )
            return None
        if LOGICAL_TABLE_OPT in stmt.options:
            ts = stmt.time_index or next(
                (c.name for c in stmt.columns if c.is_time_index), None
            )
            pks = set(stmt.primary_key) | {
                c.name for c in stmt.columns if c.is_primary_key
            }
            val = next(
                (c.name for c in stmt.columns if c.name != ts and c.name not in pks),
                None,
            )
            self.metric.create_logical_table(
                stmt.name,
                labels=sorted(pks),
                physical=str(stmt.options[LOGICAL_TABLE_OPT]),
                database=self.current_database,
                ts_col=ts,
                val_col=val,
                if_not_exists=stmt.if_not_exists,
            )
            return None
        schema, rule = build_schema_and_rule(stmt)
        self.catalog.create_table(
            stmt.name,
            schema,
            partition_rule=rule,
            database=getattr(stmt, "database", None) or self.current_database,
            if_not_exists=stmt.if_not_exists,
            options=stmt.options,
            on_create=lambda m: [
                self.storage.create_region(
                    rid,
                    schema,
                    append_mode=_opt_bool(stmt.options, "append_mode"),
                    merge_mode=str(stmt.options.get("merge_mode", "")) or None,
                    memtable_kind=str(
                        stmt.options.get("memtable.type", stmt.options.get("memtable_type", ""))
                    )
                    or None,
                )
                for rid in m.region_ids
            ],
        )
        return None

    def _create_external_table(self, stmt: CreateTableStmt):
        """CREATE EXTERNAL TABLE over CSV/JSON/Parquet files (reference
        file-engine + `CREATE EXTERNAL TABLE ... WITH (location, format)`)."""
        from .storage import file_engine as fe

        location = stmt.options.get("location")
        if not location:
            raise InvalidArgumentsError(
                "external table requires WITH (location = '...')"
            )
        fmt = fe.detect_format(str(location), stmt.options.get("format"))
        if stmt.columns:
            columns = []
            time_index = stmt.time_index or next(
                (c.name for c in stmt.columns if c.is_time_index), None
            )
            pks = set(stmt.primary_key) | {
                c.name for c in stmt.columns if c.is_primary_key
            }
            for c in stmt.columns:
                if c.name == time_index:
                    sem = SemanticType.TIMESTAMP
                elif c.name in pks:
                    sem = SemanticType.TAG
                else:
                    sem = SemanticType.FIELD
                columns.append(
                    ColumnSchema(
                        name=c.name,
                        data_type=ConcreteDataType.parse(c.type_name),
                        semantic_type=sem,
                    )
                )
            schema = Schema(columns=columns)
        else:
            schema = fe.infer_schema(str(location), fmt)
        self.catalog.create_table(
            stmt.name,
            schema,
            database=self.current_database,
            if_not_exists=stmt.if_not_exists,
            options={fe.LOCATION_OPT: str(location), fe.FORMAT_OPT: fmt},
        )
        return None

    def _copy(self, stmt: CopyStmt):
        """COPY table/database TO|FROM path (reference
        operator/src/statement/copy_*.rs)."""
        from .storage import file_engine as fe

        if stmt.kind == "database":
            if stmt.direction == "to":
                fmt = str(stmt.options.get("format", "parquet")).lower()
                fe.detect_format(f"x.{fmt}", fmt)  # validate
                total = 0
                for meta in self.catalog.tables(stmt.name):
                    if is_logical_meta(meta) or fe.is_external_meta(meta):
                        continue
                    out = os.path.join(stmt.path, f"{meta.name}.{fmt}")
                    t = self._scan(TableScan(meta.name, stmt.name))
                    fe.write_file(t, out, fmt)
                    total += t.num_rows
                return total
            total = 0
            for path in fe.expand_location(stmt.path):
                table_name = os.path.splitext(os.path.basename(path))[0]
                t = fe.read_file(path, fe.detect_format(path))
                total += self.insert_rows(table_name, t, database=stmt.name)
            return total
        fmt = fe.detect_format(stmt.path, stmt.options.get("format"))
        if stmt.direction == "to":
            t = self._scan(TableScan(stmt.name, self.current_database))
            fe.write_file(t, stmt.path, fmt)
            return t.num_rows
        total = 0
        for path in fe.expand_location(stmt.path):
            t = fe.read_file(path, fmt)
            total += self.insert_rows(stmt.name, t, database=self.current_database)
        return total

    @staticmethod
    def _reject_external(meta):
        from .storage import file_engine as fe

        if fe.is_external_meta(meta):
            raise UnsupportedError(f"external table {meta.name!r} is read-only")

    # ---- ALTER / TRUNCATE / DELETE ----------------------------------------
    def _alter(self, stmt: AlterTableStmt):
        """ALTER TABLE (reference operator/src/statement/ddl.rs alter path +
        common/meta/src/ddl/alter_table.rs procedure)."""
        with self.ddl_lock:
            meta = self.catalog.table(stmt.table, self.current_database)
            if is_logical_meta(meta) or is_physical_meta(meta):
                raise UnsupportedError(
                    "ALTER TABLE on metric-engine tables is not supported"
                )
            from .storage import file_engine as fe

            if fe.is_external_meta(meta):
                raise UnsupportedError(
                    f"external table {stmt.table!r} is read-only; "
                    "recreate it to change the schema"
                )
            if stmt.action == "rename":
                referencing = self.flows.flows_referencing(
                    stmt.table, self.current_database
                )
                if referencing:
                    # flows hold the table name in their SQL and mirror keys;
                    # renaming underneath them would silently detach them
                    raise InvalidArgumentsError(
                        f"table {stmt.table!r} is referenced by flows "
                        f"{referencing}; drop them before renaming"
                    )
                self.catalog.rename_table(
                    stmt.table, stmt.new_name, self.current_database
                )
                return None
            if stmt.action == "set_options":
                meta.options.update({k: str(v) for k, v in stmt.options.items()})
                self.catalog.update_table(meta)
                return None
            if stmt.action == "unset_options":
                for k in stmt.unset_keys:
                    meta.options.pop(k, None)
                self.catalog.update_table(meta)
                return None
            schema = compute_altered_schema(stmt, meta.schema)
            # regions first, catalog publish second (same ordering rationale
            # as pipeline widening: queries never see columns regions lack)
            for rid in meta.region_ids:
                self.storage.region(rid).alter_schema(schema)
            meta.schema = schema
            self.catalog.update_table(meta)
            return None

    def _truncate(self, stmt: TruncateStmt):
        meta = self.catalog.table(stmt.table, self.current_database)
        self._reject_external(meta)
        if is_logical_meta(meta) or is_physical_meta(meta):
            # truncating the shared physical regions would wipe every
            # logical table multiplexed onto them
            raise UnsupportedError("TRUNCATE on metric-engine tables is not supported")
        for rid in meta.region_ids:
            self.storage.truncate_region(rid)
        return None

    def _delete(self, stmt: DeleteStmt) -> int:
        """DELETE FROM t [WHERE ...]: resolve live matching keys through the
        query engine, then tombstone them per region (the reference converts
        deletes to OpType::Delete rows routed like inserts,
        operator/src/delete.rs)."""
        meta = self.catalog.table(stmt.table, self.current_database)
        self._reject_external(meta)
        if is_logical_meta(meta) or is_physical_meta(meta):
            raise UnsupportedError(
                "DELETE on metric-engine tables is not supported"
            )
        proj = [c.name for c in meta.schema.tag_columns()]
        if meta.schema.time_index is not None:
            proj.append(meta.schema.time_index.name)
        if not proj:
            raise UnsupportedError("DELETE requires a table with keys")
        sel = SelectStmt(
            projections=[Column(c) for c in proj], table=stmt.table, where=stmt.where
        )
        keys = self.query_engine.execute_select(sel, self.current_database)
        if keys.num_rows == 0:
            return 0
        region_ids = meta.region_ids
        for i, part in enumerate(meta.partition_rule.split(keys)):
            if part.num_rows:
                self.storage.delete(region_ids[i], part)
        return keys.num_rows

    def _drop(self, stmt: DropStmt):
        if stmt.kind == "flow":
            if stmt.database and stmt.database != self.current_database:
                from .utils.errors import UnsupportedError

                raise UnsupportedError("flows are not database-scoped")
            self.flows.drop_flow(stmt.name, if_exists=stmt.if_exists)
            return None
        if stmt.kind == "view":
            self.catalog.drop_view(
                stmt.name, stmt.database or self.current_database,
                if_exists=stmt.if_exists,
            )
            return None
        if stmt.kind == "database":
            for meta in self.catalog.tables(stmt.name):
                for rid in meta.region_ids:
                    self.storage.drop_region(rid)
                    if self.query_engine.tile_cache is not None:
                        self.query_engine.tile_cache.invalidate_region(rid, set())
                self.dicts.drop(f"{stmt.name}.{meta.name}")
            self.catalog.drop_database(stmt.name)
            return None
        db_name = stmt.database or self.current_database
        if stmt.if_exists and not self.catalog.has_table(stmt.name, db_name):
            return None

        meta = self.catalog.table(stmt.name, db_name)
        if is_logical_meta(meta):
            self.metric.drop_logical_table(meta)
            return None
        if is_physical_meta(meta):
            self.metric.drop_physical_table(meta)
            return None
        from .storage import file_engine as fe

        external = fe.is_external_meta(meta)
        meta = self.catalog.drop_table(stmt.name, db_name)
        if not external:  # external tables own no regions (files stay put)
            for rid in meta.region_ids:
                self.storage.drop_region(rid)
                if self.query_engine.tile_cache is not None:
                    self.query_engine.tile_cache.invalidate_region(rid, set())
        self.dicts.drop(f"{db_name}.{stmt.name}")
        return None

    # ---- DML --------------------------------------------------------------
    def _insert(self, stmt: InsertStmt) -> int:
        meta = self.catalog.table(
            stmt.table, getattr(stmt, "database", None) or self.current_database
        )
        schema = meta.schema
        columns = stmt.columns or schema.column_names()
        if any(not schema.has_column(c) for c in columns):
            bad = [c for c in columns if not schema.has_column(c)]
            raise InvalidArgumentsError(f"unknown columns in INSERT: {bad}")
        if getattr(stmt, "query", None) is not None:
            # INSERT INTO ... SELECT: source columns map POSITIONALLY onto
            # the target column list (SQL semantics; reference inserter
            # does the same through its logical plan)
            result = self.query_engine.execute_select(
                stmt.query, self.current_database
            )
            if result.num_columns != len(columns):
                raise InvalidArgumentsError(
                    f"INSERT ... SELECT column count mismatch: target has "
                    f"{len(columns)}, query returned {result.num_columns}"
                )
            by_name = {
                c: result.column(i).combine_chunks()
                for i, c in enumerate(columns)
            }
            n_rows = result.num_rows
        else:
            n_rows = len(stmt.rows)
            by_name = rows_to_columns(stmt.rows, columns)
        arrays = []
        fields = []
        for col in schema.columns:
            field = col.to_arrow()
            if col.name in by_name:
                values = by_name[col.name]
            else:
                values = [col.default] * n_rows
            if isinstance(values, (pa.Array, pa.ChunkedArray)):
                # INSERT ... SELECT source: already typed, just cast
                arr = (
                    values
                    if values.type == field.type
                    else values.cast(field.type)
                )
                arrays.append(
                    arr.combine_chunks()
                    if isinstance(arr, pa.ChunkedArray)
                    else arr
                )
            else:
                arrays.append(_coerce_array(values, col))
            fields.append(field)
        batch = pa.RecordBatch.from_arrays(arrays, schema=schema.to_arrow())
        return self.write_batch(meta, batch)

    def write_batch(self, meta, batch: pa.RecordBatch, mirror: bool = True, system: bool = False) -> int:
        """Route rows to regions via the partition rule and write each
        (the reference Inserter fan-out).  `mirror` feeds flows on the
        source table (reference FlowMirrorTask, insert.rs:397-406); flow
        sink writes pass mirror=False to avoid self-feeding."""

        from .storage import file_engine as fe

        if fe.is_external_meta(meta):
            raise UnsupportedError(
                f"external table {meta.name!r} is read-only"
            )
        if not system:
            # writes share the admission budget with queries (same device,
            # same flush/compaction pressure); system writes (event
            # recorder) bypass it like they bypass the write-bytes budget.
            # Reentrancy-safe: a flow sink write issued from an admitted
            # statement's thread passes through instead of self-queueing.
            with self.admission.admit(meta.database, kind="write"):
                return self._write_batch_admitted(meta, batch, mirror)
        return self._write_batch_admitted(meta, batch, mirror, system=True)

    def _write_batch_admitted(
        self, meta, batch: pa.RecordBatch, mirror: bool, system: bool = False
    ) -> int:
        if is_logical_meta(meta):
            affected = self.metric.write_logical(meta, batch)
            if mirror and self.flows.infos:
                self.flows.mirror_insert(
                    meta.name, meta.database, pa.Table.from_batches([batch])
                )
            return affected
        from .utils import metrics as _metrics
        from .utils.memory import batch_nbytes

        table = pa.Table.from_batches([batch])
        affected = 0
        with tracing.stage("write.split") as split:
            parts = meta.partition_rule.split(table)
            non_empty = [
                (i, part) for i, part in enumerate(parts) if part.num_rows
            ]
            split.set(regions=len(non_empty))
        _metrics.INGEST_SPLIT_MS.observe(split.duration_s * 1000)
        region_ids = meta.region_ids  # includes any repartition generation base
        # system writes (event recorder) bypass the user write budget
        with self.memory.write_guard(0 if system else batch_nbytes(batch)):
            # Pipeline through the sharded worker loops so per-region WAL
            # appends overlap (reference Inserter fans per-region requests
            # out concurrently, insert.rs:409-427, onto worker.rs write
            # loops).  With ingest.group_commit on, SINGLE-region writes
            # ride the workers too when there is something to gain: the
            # part splits into several batches (appends overlap each
            # other), or the region's worker queue is non-empty (this
            # append would merge into a concurrent callers' group frame).
            # A solo big batch with an idle worker writes DIRECT — the
            # thread hop buys nothing and costs scheduler round-trips
            # against the flush pool (measured ~25% on the TSBS ladder).
            pipelined = bool(
                getattr(self.config.storage, "ingest_group_commit", True)
            )
            if len(non_empty) == 1 and pipelined:
                i, part = non_empty[0]
                pipelined = (
                    len(part.to_batches()) > 1
                    or self.storage.pending_writes(region_ids[i])
                )
            if len(non_empty) > 1 or (pipelined and non_empty):
                futures = []
                for i, part in non_empty:
                    for b in part.to_batches():
                        # the worker fills this request's own dict with what
                        # its `write.wal` / `write.memtable` stages measured,
                        # before the future resolves: concurrent callers'
                        # writes cannot be mis-attributed to this span
                        stages: dict = {}
                        futures.append((
                            region_ids[i], b.num_rows, stages,
                            self.storage.submit_write(region_ids[i], b, stages),
                        ))
                for rid, rows, stages, f in futures:
                    with tracing.span("write.region", region=rid, rows=rows) as sp:
                        affected += f.result(timeout=60)
                        sp.attributes.update(
                            (k, round(v, 3) if isinstance(v, float) else v)
                            for k, v in stages.items()
                        )
            else:
                for i, part in non_empty:
                    for b in part.to_batches():
                        affected += self.storage.write(region_ids[i], b)
        if mirror and self.flows.infos:
            self.flows.mirror_insert(meta.name, meta.database, table)
        return affected

    # ---- ingest API (line-protocol style, used by servers/) ---------------
    def insert_rows(
        self,
        table: str,
        rows: pa.Table | pa.RecordBatch,
        database: str | None = None,
        system: bool = False,
    ) -> int:
        meta = self.catalog.table(table, database or self.current_database)
        if isinstance(rows, pa.Table):
            rows = rows.combine_chunks()
            batches = rows.to_batches()
        else:
            batches = [rows]
        total = 0
        # a logical table's label columns stay dictionary-encoded where they
        # come so: the metric engine hashes a `__tsid` per distinct label
        # set, which the codes give it without a pass over the strings
        from .utils.metrics import WRITE_BATCH_S

        keep = is_logical_meta(meta)
        for b in batches:
            with tracing.stage("write.batch", table=table, rows=b.num_rows) as st:
                total += self.write_batch(
                    meta, _conform_batch(b, meta.schema, keep_dictionaries=keep),
                    system=system,
                )
            if tracing.counting():
                WRITE_BATCH_S.inc(st.duration_s)
        return total

    # ---- SHOW/DESCRIBE ----------------------------------------------------
    def _show(self, stmt: ShowStmt):
        from .models import information_schema as info

        if stmt.what == "tables":
            db_name = stmt.database or self.current_database
            if info.is_information_schema(db_name):
                return pa.table({"Tables": info.table_names()})
            names = [m.name for m in self.catalog.tables(db_name)]
            return pa.table({"Tables": filter_like(names, stmt.like)})
        if stmt.what == "databases":
            return pa.table({"Database": self.catalog.databases()})
        if stmt.what == "create_table":
            meta = self.catalog.table(stmt.target, self.current_database)
            return pa.table({"Table": [meta.name], "Create Table": [_render_create(meta)]})
        if stmt.what == "flows":
            flows = [
                f
                for f in self.flows.list_flows()
                if stmt.like is None or f.name in filter_like([f.name], stmt.like)
            ]
            return pa.table(
                {
                    "Flows": [f.name for f in flows],
                    "Mode": [f.mode for f in flows],
                    "Source": [", ".join(f.all_sources()) for f in flows],
                    "Sink": [f.sink_table for f in flows],
                    "Fallback Reason": [f.fallback_reason or "" for f in flows],
                }
            )
        if stmt.what == "views":
            names = sorted(self.catalog.views(self.current_database))
            return pa.table({"Views": filter_like(names, stmt.like)})
        if stmt.what == "create_view":
            sql_text = self.catalog.view(stmt.target, self.current_database)
            if sql_text is None:
                raise TableNotFoundError(f"view not found: {stmt.target}")
            return pa.table(
                {
                    "View": [stmt.target],
                    "Create View": [f"CREATE VIEW {stmt.target} AS {sql_text}"],
                }
            )
        if stmt.what == "create_flow":
            info = self.flows.infos.get(stmt.target)
            if info is None:
                from .utils.errors import FlowNotFoundError

                raise FlowNotFoundError(f"flow not found: {stmt.target}")
            parts = [f"CREATE FLOW {info.name}", f"SINK TO {info.sink_table}"]
            if info.expire_after_ms is not None:
                parts.append(f"EXPIRE AFTER '{info.expire_after_ms // 1000}s'")
            if info.eval_interval_ms is not None:
                parts.append(f"EVAL INTERVAL '{info.eval_interval_ms // 1000}s'")
            if info.comment:
                parts.append(f"COMMENT '{info.comment}'")
            parts.append(f"AS {info.sql}")
            return pa.table({"Flow": [info.name], "Create Flow": [" ".join(parts)]})
        raise UnsupportedError(f"unsupported SHOW {stmt.what}")

    def _describe(self, stmt: DescribeStmt):
        from .models import information_schema as info

        if info.is_information_schema(self.current_database):
            # virtual system tables: synthesize the meta shim
            # render_describe needs (reference DESC on information_schema
            # works the same way) — schemas here are a stable contract
            # documented in README "Runtime introspection"
            import types

            schema = info.schema_of(self, stmt.table)
            return render_describe(types.SimpleNamespace(schema=schema))
        meta = self.catalog.table(stmt.table, self.current_database)
        return render_describe(meta)

    def _explain_flow(self, name: str):
        """EXPLAIN FLOW <name>: the flow's operator graph — mode, operator
        chain, and (for batch fallbacks) the inexpressible feature that
        caused the degradation."""
        info = self.flows.infos.get(name)
        if info is None:
            from .utils.errors import FlowNotFoundError

            raise FlowNotFoundError(f"flow not found: {name}")
        task = self.flows.flows[name]
        if hasattr(task, "describe"):
            lines = task.describe()
        else:
            lines = [f"{info.mode} flow sink={info.sink_table}"]
        return pa.table({"Flow": [name] * len(lines), "Plan": lines})

    # ---- ADMIN ------------------------------------------------------------
    def _admin(self, stmt: AdminStmt):
        f = stmt.func.lower()
        if f == "flush_table":

            meta = self.catalog.table(str(stmt.args[0]), self.current_database)
            if is_logical_meta(meta):
                meta = self.catalog.table(
                    meta.options[LOGICAL_TABLE_OPT], self.current_database
                )
            for rid in meta.region_ids:
                self.storage.flush_region(rid)
            return pa.table({"result": [0]})
        if f == "flush_region":
            self.storage.flush_region(int(stmt.args[0]))
            return pa.table({"result": [0]})
        if f == "compact_table":
            from .storage.compaction import compact_region

            meta = self.catalog.table(str(stmt.args[0]), self.current_database)
            if is_logical_meta(meta):
                meta = self.catalog.table(
                    meta.options[LOGICAL_TABLE_OPT], self.current_database
                )
            for rid in meta.region_ids:
                compact_region(self.storage.region(rid))
            return pa.table({"result": [0]})
        if f == "flush_flow":
            self.flows.flush_flow(str(stmt.args[0]))
            return pa.table({"result": [0]})
        raise UnsupportedError(f"unknown admin function: {stmt.func}")

    # ---- TQL (PromQL-in-SQL) ----------------------------------------------
    def _tql(self, stmt: TqlStmt):
        from .query.promql.engine import PromqlEngine

        engine = PromqlEngine(self)
        # seconds to the NEAREST millisecond: 1767225600.123 * 1000 is
        # ...122.9999 in binary and would truncate one millisecond low
        return engine.query_range(
            stmt.query,
            start_ms=round(stmt.start * 1000),
            end_ms=round(stmt.end * 1000),
            step_ms=round(stmt.step * 1000),
        )

    # ---- providers for the query engine ------------------------------------
    def _schema_of(self, table: str, database: str) -> Schema:
        from .models import information_schema as info
        from .models import pg_catalog as pg

        if info.is_information_schema(database):
            return info.schema_of(self, table)
        if pg.is_pg_catalog(database):
            return pg.schema_of(self, table)
        return self.catalog.table(table, database).schema

    def _pred_of(self, scan: TableScan) -> ScanPredicate:
        return ScanPredicate(
            time_range=scan.time_range, filters=[tuple(f) for f in scan.filters]
        )

    def _session_cursors(self) -> dict:
        """Per-thread (per-connection) open cursors, like the reference's
        per-session cursor map (session QueryContext)."""
        return self.ensure_session().cursors

    def _region_scan(self, scan: TableScan) -> list[pa.Table]:
        from .models import information_schema as info

        self.process_manager.check_cancelled()  # KILL cancellation point
        if info.is_information_schema(scan.database):
            return [info.build(self, scan.table)]
        from .models import pg_catalog as pg

        if pg.is_pg_catalog(scan.database):
            return [pg.build(self, scan.table)]
        meta = self.catalog.table(scan.table, scan.database)
        if is_logical_meta(meta):
            return self.metric.scan_logical(meta, scan)
        from .storage import file_engine as fe

        if fe.is_external_meta(meta):
            return [fe.scan(meta, self._pred_of(scan))]
        pred = self._pred_of(scan)
        out = []
        if self.memory.max_scan_bytes > 0:
            # bounded-memory path: admit each window slice against the scan
            # budget; a too-large SELECT fails cleanly instead of OOMing
            with self.memory.scan_tracker() as tracker:
                for rid in meta.region_ids:
                    chunks = []
                    for chunk in self.storage.scan_stream(rid, pred):
                        tracker.add(chunk.nbytes)
                        chunks.append(chunk)
                        self.process_manager.check_cancelled()
                    out.append(
                        pa.concat_tables(chunks, promote_options="permissive")
                        if chunks
                        else meta.schema.to_arrow().empty_table()
                    )
                return out
        if len(meta.region_ids) > 1:
            # intra-node scan parallelism: regions decode Parquet
            # concurrently (Arrow releases the GIL) — the role of the
            # reference's ParallelizeScan redistributing PartitionRanges
            # (query/src/optimizer/parallelize_scan.rs)
            from concurrent.futures import ThreadPoolExecutor

            from .utils.deadline import propagate

            with ThreadPoolExecutor(
                max_workers=min(len(meta.region_ids), 8)
            ) as pool:
                out = list(
                    pool.map(
                        propagate(lambda rid: self.storage.scan(rid, pred)),
                        meta.region_ids,
                    )
                )
            self.process_manager.check_cancelled()
            return out
        for rid in meta.region_ids:
            out.append(self.storage.scan(rid, pred))
            self.process_manager.check_cancelled()  # between-region point
        return out

    def _tile_context(self, scan: TableScan, logical: bool = False):
        """TileContext for the HBM tile cache, or None when this scan's
        source can't be tiled (virtual/external tables).  A metric-engine
        logical table tiles as its physical table's context with
        `logical_table_id` set, for the callers that ask with `logical`
        (the TQL tile path and `prewarm`): its rows are a range of the
        physical region's planes, which every logical table shares."""
        from .models import information_schema as info
        from .parallel.tile_cache import TileContext
        from .storage import file_engine as fe

        if not scan.table or info.is_information_schema(scan.database):
            return None
        database = scan.database or self.current_database
        try:
            meta = self.catalog.table(scan.table, scan.database)
            table_id = None
            if is_logical_meta(meta):
                if not logical:
                    return None
                table_id = meta.table_id
                meta = self.catalog.table(meta.options[LOGICAL_TABLE_OPT], database)
        except TableNotFoundError:
            return None
        if fe.is_external_meta(meta):
            return None
        try:
            regions = [self.storage.region(rid) for rid in meta.region_ids]
        except Exception:  # noqa: BLE001 — region mid-drop: fall back
            return None
        key = f"{database}.{meta.name}"
        return TileContext(
            table_key=key,
            dictionary=self.dicts.get(key),
            regions=regions,
            append_mode=any(r.append_mode for r in regions),
            partition_columns=meta.partition_rule.key_columns(),
            logical_table_id=table_id,
        )

    # ---- tile prewarm (cold path off the query path) ----------------------
    def prewarm(self, tables=None, database: str | None = None) -> dict:
        """Build HBM super-tiles for flushed data OFF the query path: host
        consolidation (Parquet decode + dictionary encode + (pk, ts)
        lexsort), device plane uploads and MXU limb quantization — the
        10-170 s the FIRST query of each TSBS family otherwise pays.
        Explicit form of `tile.prewarm_on_flush`; returns per-table build
        stats.  `tables` restricts to the named tables (bare or
        db-qualified).  Logical tables of one physical table share its
        region's planes: they are built once, and each logical table's
        entry is that build's stats with `physical` naming the table
        built.  A build that raises is an `error` in the table's stats."""
        from .models import information_schema as info

        te = self.query_engine._tile_executor
        if te is None:
            return {}
        out: dict = {}
        built: dict = {}  # physical table key -> its build's stats
        dbs = [database] if database else self.catalog.databases()
        want = set(tables) if tables else None
        cfg_tables = set(getattr(self.config.tile, "prewarm_tables", ()) or ())
        for db in dbs:
            if info.is_information_schema(db):
                continue
            for meta in self.catalog.tables(db):
                key = f"{db}.{meta.name}"
                if want is not None and meta.name not in want and key not in want:
                    continue
                if cfg_tables and meta.name not in cfg_tables and key not in cfg_tables:
                    continue
                if is_logical_meta(meta) and want is None:
                    continue  # its physical table is in the walk
                ctx = self._tile_context(
                    TableScan(table=meta.name, database=db), logical=True
                )
                if ctx is None:
                    continue
                if ctx.table_key not in built:
                    built[ctx.table_key] = self._prewarm_one(te, ctx, db)
                out[key] = (
                    {**built[ctx.table_key], "physical": ctx.table_key}
                    if ctx.logical_table_id is not None else built[ctx.table_key]
                )
        return out

    def _prewarm_one(self, te, ctx, db: str) -> dict:
        from .utils.deadline import deadline_scope

        try:
            schema = self._schema_of(ctx.table_key.split(".", 1)[1], db)
            # arm the per-statement deadline ourselves: sql() does this
            # for queries, but prewarm is not a statement — without it
            # query.timeout_s would be advisory here and a huge
            # consolidation could run unbounded
            with deadline_scope(self.config.query.timeout_s):
                return te.prewarm(
                    ctx, schema,
                    limbs=getattr(self.config.tile, "prewarm_limbs", True),
                )
        except Exception as e:  # noqa: BLE001 — prewarm never fails callers
            return {"error": repr(e)}

    def _start_flush_prewarmer(self):
        """tile.prewarm_on_flush: coalesce flush notifications per table
        and rebuild its super-tiles on a background thread once the storm
        settles (tile.prewarm_debounce_s after the LAST flush)."""
        import time as _t

        from .models.catalog import MAX_REGIONS_PER_TABLE

        self._prewarm_pending: dict[str, float] = {}
        self._prewarm_cv = threading.Condition()
        self._prewarm_stop = False
        # table_id -> "db.table" memo so a flush storm doesn't pay an
        # O(all tables) catalog scan per flush; a stale entry (rename/
        # drop) just prewarms a missing table, which no-ops
        tid_cache: dict[int, str] = {}

        def resolve(tid: int) -> str | None:
            key = tid_cache.get(tid)
            if key is not None:
                return key
            for db in self.catalog.databases():
                for meta in self.catalog.tables(db):
                    if meta.table_id == tid:
                        tid_cache[tid] = f"{db}.{meta.name}"
                        return tid_cache[tid]
            return None

        def on_flush(region_id: int, added_file_ids=None):
            # `added_file_ids` is the engine's delta notification (the SSTs
            # this flush appended): the debounced prewarm below re-enters
            # TileCacheManager.super_tiles, which merges exactly those
            # files' rows into the cached entry (tile.incremental) instead
            # of rebuilding — so a flush storm costs O(sum of deltas).
            key = resolve(region_id // MAX_REGIONS_PER_TABLE)
            if key is None:
                return
            with self._prewarm_cv:
                self._prewarm_pending[key] = _t.monotonic()
                self._prewarm_cv.notify()

        def loop():
            import time as _t

            debounce = max(self.config.tile.prewarm_debounce_s, 0.0)
            while True:
                with self._prewarm_cv:
                    while not self._prewarm_pending and not self._prewarm_stop:
                        self._prewarm_cv.wait(timeout=1.0)
                    if self._prewarm_stop:
                        return
                    now = _t.monotonic()
                    due = [
                        k
                        for k, t in self._prewarm_pending.items()
                        if now - t >= debounce
                    ]
                    if not due:
                        self._prewarm_cv.wait(timeout=max(debounce / 4, 0.05))
                        continue
                    for k in due:
                        self._prewarm_pending.pop(k, None)
                for key in due:
                    db, _, name = key.partition(".")
                    try:
                        self.prewarm(tables=[name], database=db)
                    except Exception:  # noqa: BLE001 — background, advisory
                        pass

        self._prewarm_thread = threading.Thread(
            target=loop, name="tile-prewarm", daemon=True
        )
        self._prewarm_thread.start()
        # delta_listeners carries (region_id, added_file_ids) — the
        # incremental build consumes exactly those files' rows
        self.storage.delta_listeners.append(on_flush)

    def _vector_search(self, vs) -> pa.Table:
        """Top-k nearest rows for a VectorSearch node.

        Append-mode regions consult the per-SST IVF index (reference
        vector-index applier): distances are computed only over the probed
        candidate rows; dedup-mode regions rank the authoritative merged
        scan (last-write-wins must win before ranking).  Rows with NULL
        vectors are excluded from the top-k, like the reference's index
        search."""
        import numpy as np

        from .query.vector import decode_matrix, distances
        from .storage.sst import INDEX_VECTOR_APPLIED

        q = np.frombuffer(vs.query, dtype="<f4")

        def topk_of(table: pa.Table) -> pa.Table:
            if table.num_rows == 0 or vs.column not in table.column_names:
                # pre-ALTER data may lack the vector column entirely: those
                # rows have NULL vectors and never rank
                return table.schema.empty_table() if table.num_rows else table
            from .ops.vector import topk_host

            mat, valid = decode_matrix(table[vs.column], len(q))
            _dist, sel = topk_host(mat, valid, q, vs.metric, vs.k, vs.ascending)
            return table.take(pa.array(np.sort(sel)))

        meta = self.catalog.table(vs.scan.table, vs.scan.database)
        out: list[pa.Table] = []
        pred = self._pred_of(vs.scan)
        regions = []
        for rid in meta.region_ids:
            try:
                regions.append(self.storage.region(rid))
            except Exception:  # noqa: BLE001 — virtual/logical/remote table:
                # one whole-table scan REPLACES per-region work (augmenting
                # it would rank already-processed regions twice)
                return topk_of(self._scan(vs.scan))
        for region in regions:
            if region.append_mode:
                # per-SST IVF candidates + memtable brute force; no dedup to
                # disturb in append mode
                for fm in region.sst_reader.prune_files(region.files(), pred):
                    t = region.sst_reader.read(fm, pred)
                    vi = region.sst_reader.vector_index(fm, vs.column)
                    if vi is not None and t.num_rows == fm.num_rows:
                        cand = vi.candidates(q, nprobe=8)
                        if len(cand) >= min(vs.k, vi.n) and len(cand) < t.num_rows:
                            INDEX_VECTOR_APPLIED.inc()
                            t = t.take(pa.array(np.sort(cand)))
                    out.append(topk_of(t))
                from .storage.sst import _apply_residual

                ts_name = meta.schema.time_index.name if meta.schema.time_index else None
                for mem in [*region._frozen_memtables, region.memtable]:
                    mt = _apply_residual(mem.to_table(dedup=False), pred, ts_name)
                    out.append(topk_of(mt))
            else:
                out.append(topk_of(region.scan(pred)))
        tables = [t for t in out if t.num_rows]
        if not tables:
            return meta.schema.to_arrow().empty_table()
        return pa.concat_tables(tables, promote_options="permissive")

    def _execute_select_cached(self, stmt, query_text: str) -> pa.Table:
        """Plan cache for repeated query texts (prepared statements re-parse
        per execute in the reference's MySQL shim; this is the plan-cache
        tier it lacks).  Keyed by (text, database); any catalog mutation —
        DDL, view change, repartition — bumps catalog.revision and
        invalidates."""
        key = (query_text, self.current_database)
        with self._plan_cache_lock:
            hit = self._plan_cache.get(key)
            if hit is not None and hit[0] == self.catalog.revision:
                self._plan_cache.move_to_end(key)
            else:
                hit = None
        if hit is not None:
            plan, schema = hit[1], hit[2]
            tracing.set_attribute("plan_cache", "hit")
        else:
            from .query.planner import plan_query, plan_uncacheable

            with tracing.span("query.plan", table=stmt.table or "") as s:
                plan, schema = plan_query(
                    stmt, self._schema_of, self.current_database, self._view_stmt
                )
                s.attributes["plan_ms"] = round(s.duration() * 1000.0, 3)
            if not plan_uncacheable(plan):
                with self._plan_cache_lock:
                    self._plan_cache[key] = (self.catalog.revision, plan, schema)
                    self._plan_cache.move_to_end(key)
                    while len(self._plan_cache) > 256:
                        self._plan_cache.popitem(last=False)
        return self.query_engine.execute_plan(plan, schema)

    def _view_stmt(self, name: str, database: str):
        """view_provider for the planner: view name -> freshly parsed
        defining SELECT (fresh parse per query so planning never mutates a
        shared statement)."""
        try:
            sql_text = self.catalog.view(name, database)
        except DatabaseNotFoundError:
            return None
        if sql_text is None:
            return None
        stmts = parse_sql(sql_text)
        return stmts[0] if stmts and isinstance(stmts[0], SelectStmt) else None

    def _create_view(self, stmt: CreateViewStmt):
        """CREATE [OR REPLACE] VIEW: validate the definition plans against
        the current catalog, then persist its SQL text (reference
        create_view.rs validates the logical plan before committing)."""
        from .query.planner import plan_query

        plan_query(stmt.stmt, self._schema_of, self.current_database, self._view_stmt)
        self.catalog.create_view(
            stmt.name,
            stmt.sql_text,
            database=self.current_database,
            or_replace=stmt.or_replace,
            if_not_exists=stmt.if_not_exists,
        )
        return None

    def _scan(self, scan: TableScan) -> pa.Table:
        from .models import information_schema as info

        if not scan.table:
            return pa.table({"__dummy": [0]})  # constant SELECTs
        if info.is_information_schema(scan.database):
            from .storage.sst import _apply_residual

            t = info.build(self, scan.table)
            return _apply_residual(t, self._pred_of(scan), None)
        from .models import pg_catalog as pg

        if pg.is_pg_catalog(scan.database):
            from .storage.sst import _apply_residual

            return _apply_residual(pg.build(self, scan.table), self._pred_of(scan), None)
        tables = [t for t in self._region_scan(scan) if t.num_rows]
        meta = self.catalog.table(scan.table, scan.database)
        if not tables:
            return meta.schema.to_arrow().empty_table()
        return pa.concat_tables(tables, promote_options="permissive")

    def _time_bounds(self, table: str, database: str) -> tuple[int, int]:
        """Min/max time over a table, from SST metadata + memtable ranges
        (no data scan — the reference prunes from FileMeta the same way)."""

        meta = self.catalog.table(table, database)
        if is_logical_meta(meta):
            # Logical tables share the physical region's bounds (cheap and
            # conservative — pruning still applies __table_id at scan time).
            meta = self.catalog.table(meta.options[LOGICAL_TABLE_OPT], database)
        from .storage import file_engine as fe

        if fe.is_external_meta(meta):
            return fe.time_bounds(meta) or (0, 0)
        lo, hi = None, None
        for rid in meta.region_ids:
            region = self.storage.region(rid)
            for fm in region.files():
                lo = fm.time_range[0] if lo is None else min(lo, fm.time_range[0])
                hi = fm.time_range[1] if hi is None else max(hi, fm.time_range[1])
            for mem in [region.memtable] + region._frozen_memtables:
                r = mem.time_range()
                if r is not None:
                    lo = r[0] if lo is None else min(lo, r[0])
                    hi = r[1] if hi is None else max(hi, r[1])
        if lo is None:
            return (0, 0)
        return (lo, hi)

    # ---- recovery ---------------------------------------------------------
    def _reopen_regions(self):

        from .storage import file_engine as fe

        for db in self.catalog.databases():
            for meta in self.catalog.tables(db):
                if is_logical_meta(meta) or fe.is_external_meta(meta):
                    continue  # no regions of their own
                append = _opt_bool(meta.options, "append_mode")
                mm = str(meta.options.get("merge_mode", "")) or None
                mk = str(
                    meta.options.get("memtable.type", meta.options.get("memtable_type", ""))
                ) or None
                for rid in meta.region_ids:
                    try:
                        self.storage.open_region(
                            rid, append_mode=append, memtable_kind=mk, merge_mode=mm
                        )
                    except Exception:
                        self.storage.create_region(
                            rid, meta.schema, append_mode=append,
                            memtable_kind=mk, merge_mode=mm,
                        )


def render_describe(meta) -> pa.Table:
    """DESCRIBE TABLE rendering, shared by the standalone Database and the
    distributed Frontend so shared sqlness goldens stay byte-identical."""
    rows = {
        "Column": [],
        "Type": [],
        "Key": [],
        "Null": [],
        "Default": [],
        "Semantic Type": [],
    }
    for c in meta.schema.columns:
        rows["Column"].append(c.name)
        rows["Type"].append(c.data_type.value)
        rows["Key"].append("PRI" if c.semantic_type == SemanticType.TAG else "")
        rows["Null"].append("YES" if c.nullable else "NO")
        rows["Default"].append(str(c.default) if c.default is not None else "")
        rows["Semantic Type"].append(
            {
                SemanticType.TAG: "TAG",
                SemanticType.FIELD: "FIELD",
                SemanticType.TIMESTAMP: "TIMESTAMP",
            }[c.semantic_type]
        )
    return pa.table(rows)


def filter_like(names: list[str], like: str | None) -> list[str]:
    """SHOW ... LIKE pattern filter (SQL % glob), shared for the same
    golden-parity reason as render_describe."""
    if not like:
        return names
    import fnmatch

    return [n for n in names if fnmatch.fnmatch(n, like.replace("%", "*"))]


def build_schema_and_rule(stmt: CreateTableStmt):
    """CreateTableStmt -> (Schema, partition rule): the single source of
    CREATE TABLE semantics, shared by the standalone Database and the
    distributed Frontend role so both build identical tables."""
    columns: list[ColumnSchema] = []
    time_index = stmt.time_index
    pks = set(stmt.primary_key)
    for c in stmt.columns:
        if c.is_time_index:
            time_index = c.name
        if c.is_primary_key:
            pks.add(c.name)
    for c in stmt.columns:
        if c.name == time_index:
            sem = SemanticType.TIMESTAMP
        elif c.name in pks:
            sem = SemanticType.TAG
        else:
            sem = SemanticType.FIELD
        dt = ConcreteDataType.parse(c.type_name)
        vdim = None
        if dt == ConcreteDataType.VECTOR:
            import re as _re

            m = _re.match(r"vector\s*\(\s*(\d+)\s*\)", c.type_name.strip().lower())
            if not m:
                raise InvalidArgumentsError(
                    f"VECTOR column {c.name!r} needs a dimension: VECTOR(n)"
                )
            vdim = int(m.group(1))
        columns.append(
            ColumnSchema(
                name=c.name,
                data_type=dt,
                semantic_type=sem,
                nullable=c.nullable and sem == SemanticType.FIELD,
                default=c.default,
                fulltext=getattr(c, "fulltext", False),
                vector_dim=vdim,
                vector_index=getattr(c, "vector_index", False),
            )
        )
    if time_index is None:
        raise InvalidArgumentsError("table requires a TIME INDEX column")
    schema = Schema(columns=columns)
    mm = str(stmt.options.get("merge_mode", "")).strip()
    if mm not in ("", "last_row", "last_non_null"):
        raise InvalidArgumentsError(
            f"invalid merge_mode {mm!r}: expected 'last_row' or 'last_non_null'"
        )
    if mm == "last_non_null" and _opt_bool(stmt.options, "append_mode"):
        raise InvalidArgumentsError(
            "merge_mode = 'last_non_null' conflicts with append_mode "
            "(append tables keep every row; there is nothing to merge)"
        )
    rule = SingleRegionRule()
    if stmt.partition_by_hash is not None:
        cols, n = stmt.partition_by_hash
        rule = HashPartitionRule(cols, n)
    elif stmt.partition_on_columns is not None:
        from .models.partition import MultiDimPartitionRule

        pcols, pexprs = stmt.partition_on_columns
        if pexprs:
            from .query.expr import to_sql

            for pc_name in pcols:
                if not schema.has_column(pc_name):
                    raise InvalidArgumentsError(
                        f"partition column {pc_name!r} is not a table column"
                    )
            # fully-parenthesized rendering: the rule text must re-parse
            # to the same tree (name() drops OR/AND grouping)
            rule = MultiDimPartitionRule(pcols, [to_sql(e) for e in pexprs])
    return schema, rule


def _opt_bool(options: dict, key: str) -> bool:
    v = options.get(key)
    if isinstance(v, str):
        return v.lower() in ("1", "true", "yes", "on")
    return bool(v)


def rows_to_columns(rows: list, columns: list[str]) -> dict:
    """Columnar transpose of INSERT VALUES rows in ONE zip pass (C speed)
    instead of a per-cell Python comprehension per column — shared by the
    standalone Database and the distributed Frontend so the two roles
    cannot diverge on VALUES handling (like compute_altered_schema)."""
    if any(len(r) != len(columns) for r in rows):
        raise InvalidArgumentsError(
            f"INSERT row width mismatch: expected {len(columns)} "
            "values per row"
        )
    cols = list(zip(*rows)) if rows else [() for _ in columns]
    return {c: cols[i] for i, c in enumerate(columns)}


def _coerce_array(values: list, col: ColumnSchema) -> pa.Array:
    t = col.data_type.to_arrow()
    if col.data_type == ConcreteDataType.VECTOR:
        from .query.vector import parse_vector_literal

        coerced = [
            None if v is None else (v if isinstance(v, bytes) else parse_vector_literal(v, col.vector_dim))
            for v in values
        ]
        return pa.array(coerced, t)
    if col.data_type.is_timestamp():
        unit_ms = col.data_type.timestamp_unit_ns() // 1_000_000
        if all(v is None or type(v) is int for v in values):
            # already epoch ints in the column's unit: ONE typed build
            # (identical to the per-value int() loop below)
            return pa.array(values, t)
        coerced = []
        for v in values:
            if isinstance(v, str):
                import datetime

                dt = datetime.datetime.fromisoformat(v.replace(" ", "T"))
                if dt.tzinfo is None:
                    dt = dt.replace(tzinfo=datetime.timezone.utc)
                coerced.append(int(dt.timestamp() * 1000) // max(unit_ms, 1))
            else:
                coerced.append(None if v is None else int(v))
        return pa.array(coerced, t)
    return pa.array(values, t)


def compute_altered_schema(stmt, schema: Schema) -> Schema:
    """Schema transform for ALTER TABLE add/drop/modify columns — shared
    by the standalone Database and the distributed Frontend so the two
    roles can never diverge on ALTER semantics."""
    if stmt.action == "add_columns":
        for cd in stmt.add_columns:
            if cd.is_time_index or cd.is_primary_key:
                raise InvalidArgumentsError(
                    "only FIELD columns can be added (tags are part "
                    "of the primary key; the time index is fixed)"
                )
            schema = schema.add_column(
                ColumnSchema(
                    name=cd.name,
                    data_type=ConcreteDataType.parse(cd.type_name),
                    semantic_type=SemanticType.FIELD,
                    nullable=True,
                    default=cd.default,
                )
            )
        return schema
    if stmt.action == "drop_columns":
        for name in stmt.drop_columns:
            schema = schema.drop_column(name)
        return schema
    if stmt.action == "modify_columns":
        for name, tname in stmt.modify_columns:
            col = schema.column(name)
            if col.semantic_type != SemanticType.FIELD:
                raise InvalidArgumentsError(
                    f"only FIELD columns can change type: {name!r}"
                )
            new_dt = ConcreteDataType.parse(tname)
            old_dt = col.data_type
            castable = (
                (old_dt.is_numeric() and new_dt.is_numeric())
                or new_dt == ConcreteDataType.STRING
                or old_dt == new_dt
            )
            if not castable:
                # existing SST data must remain scannable: only
                # lossless-ish casts are allowed (the reference
                # rejects incompatible modify the same way)
                raise InvalidArgumentsError(
                    f"cannot change column {name!r} from "
                    f"{old_dt.value} to {new_dt.value}"
                )
            new_cols = [
                ColumnSchema(
                    name=c.name,
                    data_type=new_dt if c.name == name else c.data_type,
                    semantic_type=c.semantic_type,
                    nullable=c.nullable,
                    default=c.default,
                    column_id=c.column_id,  # type change keeps identity
                )
                for c in schema.columns
            ]
            schema = Schema(
                columns=new_cols,
                version=schema.version + 1,
                next_column_id=schema.next_column_id,
            )
        return schema
    raise UnsupportedError(f"unsupported ALTER action: {stmt.action}")


def _conform_batch(
    batch: pa.RecordBatch, schema: Schema, keep_dictionaries: bool = False
) -> pa.RecordBatch:
    """Reorder/cast incoming batch columns to the table schema; with
    `keep_dictionaries` a dictionary-encoded column of the wanted value
    type passes as it is (the batch then carries its own Arrow schema)."""
    arrays, kept = [], False
    for col in schema.columns:
        i = batch.schema.get_field_index(col.name)
        if i < 0:
            arrays.append(pa.nulls(batch.num_rows, col.data_type.to_arrow()))
        else:
            arr = batch.column(i)
            want = col.data_type.to_arrow()
            if (
                keep_dictionaries and pa.types.is_dictionary(arr.type)
                and arr.type.value_type == want
            ):
                kept = True
            elif arr.type != want:
                arr = arr.cast(want)
            arrays.append(arr)
    if kept:
        return pa.RecordBatch.from_arrays(arrays, names=[c.name for c in schema.columns])
    return pa.RecordBatch.from_arrays(arrays, schema=schema.to_arrow())


def _render_create(meta) -> str:
    cols = []
    for c in meta.schema.columns:
        line = f'  "{c.name}" {c.data_type.value.upper()}'
        if not c.nullable:
            line += " NOT NULL"
        cols.append(line)
    if meta.schema.time_index:
        cols.append(f'  TIME INDEX ("{meta.schema.time_index.name}")')
    pk = meta.schema.primary_key()
    if pk:
        cols.append(f"  PRIMARY KEY ({', '.join(repr(p)[1:-1] for p in pk)})")
    body = ",\n".join(cols)
    return f'CREATE TABLE "{meta.name}" (\n{body}\n)'
