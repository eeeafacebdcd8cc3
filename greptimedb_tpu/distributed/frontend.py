"""Deployable distributed frontend role.

Role-equivalent of the reference's `greptime frontend start` process
(reference cmd/src/bin/greptime.rs:37-61 spawning
frontend/src/instance.rs:110 `Instance`): a stateless node that serves
SQL over HTTP/MySQL by

  * resolving table metadata from the shared catalog (the reference reads
    it from the metasrv-backed KV; here the catalog file lives on the
    shared storage the datanodes already require),
  * asking the metasrv for region routes and peer addresses
    (distributed/meta_service.py MetaClient — the reference's
    meta-client),
  * fanning writes out per region over Arrow Flight DoPut and queries out
    as serialized sub-plans / partial-aggregate tickets over Flight
    do_get (reference operator/src/insert.rs:441 group_requests_by_peer,
    query/src/dist_plan/merge_scan.rs:250-330 MergeScanExec).

The frontend holds NO storage engine: every row it touches arrives over
the wire.  DDL placement goes through the metasrv selector the same way
the in-process Cluster does.
"""

from __future__ import annotations

import logging
import os
import threading
import time as _time

import pyarrow as pa

from ..database import _coerce_array, _opt_bool, build_schema_and_rule
from ..models.catalog import Catalog
from ..query.engine import QueryEngine
from ..query.logical_plan import TableScan
from ..query.sql_parser import (
    AlterTableStmt,
    CreateTableStmt,
    DeleteStmt,
    DescribeStmt,
    DropStmt,
    InsertStmt,
    SelectStmt,
    ShowStmt,
    TruncateStmt,
    UseStmt,
    parse_sql,
)
from ..storage.sst import ScanPredicate
from ..utils import metrics, tracing
from ..utils.circuit_breaker import (
    BreakerBoard,
    CircuitBreaker,
    CircuitOpenError,
    LatencyTracker,
)
from ..utils.config import Config
from ..utils.deadline import current_deadline, deadline_scope, propagate
from ..utils.errors import (
    GreptimeError,
    IllegalStateError,
    InvalidArgumentsError,
    QueryTimeoutError,
    RetryLaterError,
    TableNotFoundError,
    UnsupportedError,
)
from ..utils.retry import RetryPolicy, is_transient
from .flight import FlightDatanodeClient
from .flownode import BestEffortMirror
from .meta_service import MetaClient

_LOG = logging.getLogger("greptimedb_tpu.frontend")


def _maybe_span(name: str, parent, **attrs):
    """A tracing span only when the statement is being traced (`parent`
    non-None): fan-out workers run on pool threads, which do not inherit
    contextvars, so the parent is captured on the submitting thread and
    passed explicitly — this is what stitches per-region sub-query spans
    under the statement root across the thread (and, via the injected
    traceparent, the Flight) boundary."""
    if parent is None:
        import contextlib

        return contextlib.nullcontext()
    return tracing.span(name, parent=parent, **attrs)


class _MetaChangedError(RetryLaterError):
    """A retry discovered the table's region set changed underneath the
    in-flight request (a repartition swapped the partition generation).
    Subclasses RetryLaterError so anywhere it escapes uncaught it keeps
    the retryable SQL contract; `write_batch` and the read providers
    catch it specifically to re-run against the FRESH meta instead of
    bubbling a retryable error for work the frontend can finish itself."""


class Frontend:
    """Distributed SQL front door over remote datanodes."""

    def __init__(
        self,
        data_home: str,
        metasrv_peers: list[str],
        node_id: int = 0,
    ):
        self.node_id = node_id
        self.data_home = data_home
        self.meta = MetaClient(metasrv_peers)
        self.catalog = Catalog(os.path.join(data_home, "catalog.json"))
        self.current_database = "public"
        # layered load so env configuration (GREPTIMEDB_TPU__TRACE__SELF,
        # breaker/replica knobs, ...) reaches the deployable frontend role
        # the same way it reaches `greptimedb_tpu datanode`
        self.config = Config.load()
        # backend stays "tpu" so the engine's distributed planner engages
        # (state shipping / sub-plan fan-out); with no tile context the
        # frontend never touches local devices — datanodes own the
        # data-proximate compute and ship bounded states/rows
        self._clients: dict[int, FlightDatanodeClient] = {}
        self._clients_lock = threading.Lock()
        # per-datanode circuit breakers ride the client cache: a flapping
        # node sheds load the moment its failure rate trips, long before
        # its metasrv lease lapses (utils/circuit_breaker.py); disabled
        # breakers cost one config check per call
        self._breakers = BreakerBoard(self._make_breaker)
        # recent sub-request latencies feed the adaptive hedge delay
        self._latency = LatencyTracker()
        # follower lookups are TTL-cached per table: the follower set
        # changes only on add_follower/failover, and a per-query metasrv
        # round-trip would tax every SELECT once hedging is on.  Staleness
        # is benign — a hedge to an ex-follower fails and the primary wins
        self._follower_cache: dict[int, tuple[float, dict[int, list[int]]]] = {}
        self._follower_ttl_s = 5.0
        # same multi-tenant admission layer as the standalone Database
        # (off by default): which statement runs next, which sheds now
        from ..utils.admission import AdmissionController

        self.admission = AdmissionController(
            self.config.admission, self.config.memory
        )
        # mirrored inserts to flownodes are best-effort and asynchronous:
        # a mirror failure retries in the background, never the user write.
        # The mirror gets its OWN MetaClient — its discovery runs on a
        # background thread, and sharing the SQL path's client would share
        # the cached-leader state across threads
        self.mirror = BestEffortMirror(MetaClient(metasrv_peers))
        # one retry policy governs every frontend->datanode request
        # (reference client/src/region.rs RegionRequester retries with
        # channel invalidation); tests may swap it for a tighter one
        self.retry_policy = RetryPolicy(
            max_attempts=4, base_delay_s=0.05, max_delay_s=1.0
        )
        # fan-out pool is shared across queries and shut down in close()
        # (round-1 built a fresh ThreadPoolExecutor per _fanout call)
        self._pool = None
        self._pool_lock = threading.Lock()
        # shared timer wheel arming EVERY region's hedge at fan-out submit
        # (not as the sequential settle loop reaches it) — the ROADMAP
        # "fully concurrent hedge scheduling" item.  Constructed eagerly
        # (the wheel's own thread starts lazily on first schedule) so
        # concurrent first fan-outs cannot race a lazy init into two
        # wheels, one of which close() would never stop.
        from ..utils.timer_wheel import TimerWheel

        self._hedge_wheel = TimerWheel(
            name=f"frontend{node_id}-hedge-wheel"
        )
        self.query_engine = QueryEngine(
            schema_provider=lambda t, d: self._table(t, d).schema,
            scan_provider=self._scan,
            region_scan_provider=self._region_scan,
            time_bounds_provider=self._time_bounds,
            config=self.config.query,
            partial_agg_provider=self._partial_agg,
            subplan_provider=self._sub_plan,
        )

    # ---- peers -------------------------------------------------------------
    def _make_breaker(self, node_id: int) -> CircuitBreaker | None:
        bc = self.config.breaker
        if not bc.enable:
            return None
        return CircuitBreaker(
            name=f"datanode-{node_id}",
            window=bc.window,
            min_calls=bc.min_calls,
            failure_rate=bc.failure_rate,
            open_cooldown_s=bc.open_cooldown_s,
            half_open_probes=bc.half_open_probes,
        )

    def _breaker(self, node_id: int | None) -> CircuitBreaker | None:
        if node_id is None:
            return None
        return self._breakers.get(node_id)

    def _guarded_call(self, node_id: int, thunk, record_latency: bool = False):
        """One datanode call under the node's circuit breaker: an open
        breaker fails fast (CircuitOpenError is RETRY_LATER-shaped, so
        retry loops re-route instead of aborting), outcomes feed the
        breaker's window.  `record_latency` samples the call into the
        hedge-delay tracker — READ sub-queries only, or a batch-insert
        workload would inflate the adaptive read p95 until hedging never
        fires."""
        br = self._breaker(node_id)
        if br is not None and not br.allow():
            metrics.BREAKER_SHED_TOTAL.inc()
            tracing.add_event("breaker.shed", node=node_id)
            raise CircuitOpenError(
                f"datanode {node_id} circuit open; shedding load"
            )
        t0 = _time.monotonic()
        try:
            out = thunk()
        except Exception as exc:  # noqa: BLE001 — classified, re-raised
            if br is not None:
                if is_transient(exc):
                    br.record_failure()
                else:
                    # no verdict on the node's health: a half-open probe
                    # slot spent on this call must be returned, not leaked
                    br.release_probe()
            raise
        if br is not None:
            br.record_success()
        if record_latency:
            self._latency.record(_time.monotonic() - t0)
        return out

    def _client(self, node_id: int) -> FlightDatanodeClient:
        with self._clients_lock:
            c = self._clients.get(node_id)
        if c is not None and c.alive:
            return c
        addrs = self.meta.node_addresses()
        addr = addrs.get(node_id)
        if addr is None:
            raise RetryLaterError(f"datanode {node_id} has no registered address")
        c = FlightDatanodeClient(node_id, f"grpc://{addr}")
        with self._clients_lock:
            self._clients[node_id] = c
        return c

    def _drop_client(self, node_id: int | None):
        """Evict a node's cached Flight client; returns the evicted client
        (None when absent) so deadline abandonment can best-effort cancel
        its in-flight calls before letting it go."""
        if node_id is None:
            return None
        with self._clients_lock:
            return self._clients.pop(node_id, None)

    def _abandon_client(self, node_id: int | None, threads: set | None = None):
        """Deadline-expiry path: drop the node's client AND attempt to
        cancel its in-flight Flight readers (feature-detected pyarrow
        cancel; detach-and-drop stays the fallback) so the wire call stops
        burning the datanode instead of running to completion server-side.
        `threads` restricts the cancel to the abandoned workers' own calls
        — the client is shared, and a concurrent query's healthy call must
        survive the eviction."""
        dropped = self._drop_client(node_id)
        if dropped is not None:
            try:
                dropped.cancel_inflight(threads)
            except Exception:  # noqa: BLE001 — cancellation is best-effort
                pass

    def _with_client(self, node_id: int, fn):
        """Run `fn(client)` against a FIXED node under the retry policy; a
        transient failure drops the cached client so the next attempt
        re-resolves the node's address from the metasrv — a restarted
        datanode comes back on a fresh port, and the old Flight channel
        reports errors without ever marking itself dead (reference
        client_manager channel invalidation).  Route-aware calls go through
        `_call_region`, which additionally re-fetches the region route."""
        try:
            return self.retry_policy.call(
                lambda: self._guarded_call(
                    node_id, lambda: fn(self._client(node_id))
                ),
                on_retry=lambda exc, attempt: self._drop_client(node_id),
            )
        except Exception as exc:  # noqa: BLE001 — classified below
            wrapped = self._wrap_exhausted(exc, f"datanode {node_id}")
            if wrapped is exc:
                raise
            raise wrapped from exc

    def _call_region(
        self, meta, rid: int, fn, routes: dict | None = None,
        inflight: dict | None = None, record_latency: bool = False,
        write: bool = False,
    ):
        """Run `fn(client, rid)` against region `rid`'s CURRENT route with
        bounded backoff.  Between attempts the cached client is dropped and
        the route is re-fetched from the metasrv, so a completed
        `RegionFailoverProcedure` is consumed by in-flight queries/writes:
        the retried sub-request lands on the failed-over replica instead of
        hammering the dead node (reference frontend invalidates its
        table-route cache on request failure).  A node whose circuit
        breaker is open is skipped WITHOUT a wire call — the retry budget
        is spent on route refreshes (consuming failover) instead of
        timeouts against a flapping node.  `inflight`, when given, tracks
        the node currently serving `rid` so a timed-out fan-out can drop
        the right client."""
        state = {"routes": routes, "node": None}

        def attempt():
            r = state["routes"]
            if r is None:
                try:
                    r = self.meta.get_route(meta.table_id)
                except (OSError, RuntimeError, IllegalStateError) as exc:
                    # metasrv churn (restart, mid-election 409, 5xx reply,
                    # refused connection as URLError) is exactly what the
                    # retry budget exists to ride out — reclassify so the
                    # policy keeps attempting instead of aborting hard
                    raise RetryLaterError(
                        f"route fetch for table {meta.table_id} failed: {exc}"
                    ) from exc
            node = self._routed(r, rid, meta)
            state["node"] = node
            if inflight is not None:
                # (node, worker thread): a timed-out fan-out drops the right
                # client AND scopes in-flight cancellation to this worker's
                # own wire call
                inflight[rid] = (node, threading.get_ident())
            try:
                return self._guarded_call(
                    node, lambda: fn(self._client(node), rid),
                    record_latency=record_latency,
                )
            except CircuitOpenError:
                # breaker-aware write routing (the PR-2 follow-up): a
                # WRITE meeting an open breaker asks the metasrv to fail
                # the region over NOW instead of waiting for lease-lapse
                # detection.  The metasrv refuses while the node's lease
                # is live (it may be healthy from everyone else's view) —
                # then the write sheds like a read.  On acceptance the
                # failover runs synchronously server-side, so the retry
                # policy's next attempt (route refresh) lands on the
                # promoted candidate.
                if write and self.config.breaker.write_hedge:
                    self._request_write_failover(meta, rid, node)
                raise

        def on_retry(exc, attempt_no):
            self._drop_client(state["node"])
            state["node"] = None
            state["routes"] = None  # force a fresh route on the next attempt
            metrics.ROUTE_REFRESH_TOTAL.inc()
            # retries are point-in-time facts on the region's span, not
            # stages: a hedged/retried read shows every attempt in ONE trace
            tracing.add_event(
                "retry", region=rid, attempt=attempt_no,
                error=f"{type(exc).__name__}: {exc}"[:200],
            )
            if write:
                # A write retry racing a repartition must not burn the rest
                # of the budget against the fenced (read-only) or already-
                # dropped old region: re-check the catalog once per retry —
                # fence up -> surface RetryLaterError NOW for the client's
                # coarse retry; region set swapped -> _MetaChangedError so
                # write_batch re-splits the batch through the new rule.
                self.catalog.reload()
                fresh = self.catalog.table(meta.name, meta.database)
                if fresh.options.get("repartitioning"):
                    raise RetryLaterError(
                        f"table {meta.name!r} is repartitioning; retry the write"
                    ) from exc
                if fresh.region_ids != meta.region_ids:
                    raise _MetaChangedError(
                        f"table {meta.name!r} repartitioned mid-write "
                        f"(region {rid} superseded); re-splitting"
                    ) from exc

        try:
            return self.retry_policy.call(attempt, on_retry=on_retry)
        except Exception as exc:  # noqa: BLE001 — classified below
            wrapped = self._wrap_exhausted(exc, f"region {rid} of {meta.name!r}")
            if wrapped is exc:
                raise
            raise wrapped from exc

    def _request_write_failover(self, meta, rid: int, node: int):
        """Best-effort frontend-initiated failover for a write shed by an
        open breaker (breaker.write_hedge).  Never raises: a refusal
        (lease live, procedure already running, metasrv churn) simply
        leaves the CircuitOpenError to the retry loop."""
        try:
            pid = self.meta.request_failover(meta.table_id, rid, node)
        except Exception as exc:  # noqa: BLE001 — hedging is best-effort
            _LOG.warning(
                "write-hedge failover request for region %s off node %s "
                "failed: %s", rid, node, exc,
            )
            metrics.WRITE_HEDGE_REFUSED_TOTAL.inc()
            return
        if pid:
            metrics.WRITE_HEDGE_TOTAL.inc()
            _LOG.info(
                "write hedged off open-breaker node %s: region %s failed "
                "over (procedure %s)", node, rid, pid,
            )
        else:
            metrics.WRITE_HEDGE_REFUSED_TOTAL.inc()

    def _wrap_exhausted(self, exc: Exception, what: str) -> Exception:
        """A transient error that survived the whole retry budget must
        reach the SQL surface as RETRY_LATER (status 2001), never as a raw
        ConnectionError/Flight exception that protocol layers map to an
        opaque 500 — writes and DDL get the same retryable contract the
        read fan-out's give_up() provides."""
        if is_transient(exc) and not isinstance(exc, GreptimeError):
            return RetryLaterError(
                f"{what} unavailable after "
                f"{self.retry_policy.max_attempts} attempts: {exc}"
            )
        return exc

    def _table(self, name: str, database: str | None = None):
        database = database or self.current_database
        try:
            return self.catalog.table(name, database)
        except TableNotFoundError:
            # another frontend may have created it: reload from the
            # shared catalog file once (reference frontends see DDL via
            # KV cache invalidation; the file IS our KV here)
            self.catalog.reload()
            return self.catalog.table(name, database)

    # ---- SQL entry (same contract as Database.sql) -------------------------
    def sql(self, text: str) -> list:
        """Execute ;-separated SQL; returns a list of results (pa.Table
        for queries, int affected-rows for writes, None for DDL)."""
        return [self._execute(stmt, query_text=text) for stmt in parse_sql(text)]

    def sql_one(self, text: str):
        out = self.sql(text)
        return out[-1] if out else None

    # protocol-server shims (the HTTP/MySQL servers speak the Database
    # surface; the frontend is per-process single-session for now)
    def ensure_session(self):
        return self

    def session_tzinfo(self, tz: str | None = None):
        return None  # UTC

    @property
    def session_timezone(self) -> str:
        return "UTC"

    def _execute(self, stmt, query_text: str | None = None):
        if isinstance(stmt, SelectStmt):
            from ..utils.self_trace import statement_trace

            # same per-statement budget as Database._execute: the fan-out
            # (and every retry sleep under it) checks this deadline, so a
            # hung datanode yields QueryTimeoutError, not a stuck query.
            # statement_trace is outermost so admission wait, fan-out and
            # per-region sub-queries are stages of one trace (off-safe:
            # trace.self=false is a pass-through)
            with statement_trace(
                self, "sql", query_text or "SELECT ...", self.current_database
            ), deadline_scope(self.config.query.timeout_s), self.admission.admit(
                self.current_database
            ):
                return self.query_engine.execute_select(stmt, self.current_database)
        if isinstance(stmt, CreateTableStmt):
            return self._create_table(stmt)
        if isinstance(stmt, InsertStmt):
            from ..utils.self_trace import statement_trace

            with statement_trace(
                self, "insert", query_text or "INSERT ...",
                self.current_database,
            ):
                return self._insert(stmt)
        if isinstance(stmt, ShowStmt):
            return self._show(stmt)
        if isinstance(stmt, DescribeStmt):
            return self._describe(stmt)
        if isinstance(stmt, DropStmt):
            return self._drop(stmt)
        if isinstance(stmt, UseStmt):
            self.current_database = stmt.database
            return None
        if isinstance(stmt, AlterTableStmt):
            return self._alter(stmt)
        if isinstance(stmt, DeleteStmt):
            return self._delete(stmt)
        if isinstance(stmt, TruncateStmt):
            return self._truncate(stmt)
        raise UnsupportedError(
            f"the distributed frontend does not support {type(stmt).__name__} yet"
        )

    def _alter(self, stmt: AlterTableStmt):
        """ALTER through the frontend: regions first (fan alter_region over
        Flight), catalog publish second — queries never see columns the
        regions lack (same ordering as the standalone Database._alter and
        the reference's alter procedure, common/meta/src/ddl/alter_table.rs)."""
        from ..database import compute_altered_schema

        meta = self._table(stmt.table, self.current_database)
        if stmt.action == "rename":
            self.catalog.rename_table(
                stmt.table, stmt.new_name, self.current_database
            )
            return None
        schema = compute_altered_schema(stmt, meta.schema)
        routes = self.meta.get_route(meta.table_id)
        for rid in meta.region_ids:
            self._call_region(
                meta, rid, lambda c, r: c.alter_region(r, schema), routes=routes
            )
        meta.schema = schema
        self.catalog.update_table(meta)
        return None

    def _delete(self, stmt: DeleteStmt) -> int:
        """DELETE: resolve matching keys through the distributed query
        engine, split by the partition rule, tombstone per region over
        Flight (reference operator/src/delete.rs routes deletes like
        inserts)."""
        from ..query.expr import Column

        meta = self._table(stmt.table, self.current_database)
        proj = [c.name for c in meta.schema.tag_columns()]
        if meta.schema.time_index is not None:
            proj.append(meta.schema.time_index.name)
        if not proj:
            raise UnsupportedError("DELETE requires a table with keys")
        sel = SelectStmt(
            projections=[Column(c) for c in proj],
            table=stmt.table,
            where=stmt.where,
        )
        keys = self.query_engine.execute_select(sel, self.current_database)
        if keys.num_rows == 0:
            return 0
        routes = self.meta.get_route(meta.table_id)
        deleted = 0
        region_ids = meta.region_ids
        for i, part in enumerate(meta.partition_rule.split(keys)):
            if not part.num_rows:
                continue
            rid = region_ids[i]
            deleted += self._call_region(
                meta, rid, lambda c, r, _p=part: c.delete_rows(r, _p),
                routes=routes, write=True,
            )
        return deleted

    def _truncate(self, stmt: TruncateStmt):
        meta = self._table(stmt.table, self.current_database)
        routes = self.meta.get_route(meta.table_id)
        for rid in meta.region_ids:
            self._call_region(
                meta, rid, lambda c, r: c.truncate_region(r), routes=routes
            )
        return None

    # ---- DDL ---------------------------------------------------------------
    def _cleanup(self, op: str, fn, **attrs):
        """Best-effort rollback/cleanup step.  Only errors cleanup can do
        nothing about are swallowed — transient transport failures, the
        database's own status-coded errors (region already gone, metasrv
        mid-election), and the meta client's RuntimeError surface for
        metasrv 5xx replies.  Anything else (TypeError, KeyError, ...) is
        a bug and propagates.  Every swallowed error is recorded on a
        tracing span AND logged, so cleanup failures are observable
        instead of silently dropped (round-1 used bare `except
        Exception: pass`)."""
        with tracing.span(f"frontend.cleanup.{op}", **attrs) as s:
            try:
                fn()
            except Exception as e:  # noqa: BLE001 — re-raised unless benign
                if not (
                    is_transient(e)
                    or isinstance(e, (GreptimeError, OSError, RuntimeError))
                ):
                    raise
                s.attributes["error"] = f"{type(e).__name__}: {e}"
                _LOG.warning(
                    "cleanup step %s %s failed: %s", op, attrs or "", e
                )

    def _place_regions(self, m, schema):
        """Open `m`'s regions on selected datanodes and publish the route
        (shared by CREATE TABLE and programmatic system-table creation)."""
        routes: dict[int, int] = {}
        try:
            for rid in m.region_ids:
                node = self.meta.select_datanode()
                if node is None:
                    raise RetryLaterError("no live datanode to place region on")
                self._with_client(node, lambda c, _r=rid: c.open_region(_r, schema))
                routes[rid] = node
        except Exception:
            for rid, node in routes.items():
                self._cleanup(
                    "close_region",
                    lambda _r=rid, _n=node: self._client(_n).close_region(_r),
                    region_id=rid,
                    node_id=node,
                )
            raise
        self.meta.set_route(m.table_id, routes)

    def ensure_system_table(self, name: str, schema, database: str = "public"):
        """Create a single-region system table if missing (the frontend
        twin of servers/otlp.py ensure_table — used by the self-trace
        writer to land span rows through the normal write path)."""
        try:
            return self._table(name, database)
        except TableNotFoundError:
            pass
        from ..models.partition import SingleRegionRule

        return self.catalog.create_table(
            name,
            schema,
            partition_rule=SingleRegionRule(),
            database=database,
            if_not_exists=True,
            on_create=lambda m: self._place_regions(m, schema),
        )

    def _create_table(self, stmt: CreateTableStmt):
        if stmt.external or stmt.engine in ("file", "metric"):
            raise UnsupportedError(
                "external/metric tables are standalone-only for now"
            )
        schema, rule = build_schema_and_rule(stmt)

        self.catalog.create_table(
            stmt.name,
            schema,
            partition_rule=rule,
            database=getattr(stmt, "database", None) or self.current_database,
            if_not_exists=stmt.if_not_exists,
            options=stmt.options,
            on_create=lambda m: self._place_regions(m, schema),
        )
        return None

    def _drop(self, stmt: DropStmt):
        if stmt.kind != "table":
            raise UnsupportedError(f"DROP {stmt.kind} is standalone-only for now")
        database = getattr(stmt, "database", None) or self.current_database
        try:
            meta = self._table(stmt.name, database)
        except TableNotFoundError:
            if stmt.if_exists:
                return None
            raise
        routes = self.meta.get_route(meta.table_id)
        self.catalog.drop_table(stmt.name, database)
        for rid in meta.region_ids:
            node = routes.get(rid)
            if node is None:
                continue
            self._cleanup(
                "close_region",
                lambda _r=rid, _n=node: self._client(_n).close_region(_r),
                region_id=rid,
                node_id=node,
            )
        # clear the metasrv route so dead table ids don't accumulate
        # in the KV (Cluster's DropTableProcedure removes metadata)
        self._cleanup(
            "clear_route",
            lambda: self.meta.set_route(meta.table_id, {}),
            table_id=meta.table_id,
        )
        return None

    # ---- DML ---------------------------------------------------------------
    def _insert(self, stmt: InsertStmt) -> int:
        meta = self._table(stmt.table, getattr(stmt, "database", None))
        schema = meta.schema
        columns = stmt.columns or schema.column_names()
        if any(not schema.has_column(c) for c in columns):
            bad = [c for c in columns if not schema.has_column(c)]
            raise InvalidArgumentsError(f"unknown columns in INSERT: {bad}")
        if getattr(stmt, "query", None) is not None:
            # INSERT ... SELECT through the distributed query engine:
            # source columns map positionally (same as Database._insert —
            # the two roles must not diverge)
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
            from ..database import rows_to_columns

            n_rows = len(stmt.rows)
            by_name = rows_to_columns(stmt.rows, columns)
        arrays = []
        for col in schema.columns:
            values = by_name.get(col.name, [col.default] * n_rows)
            if isinstance(values, (pa.Array, pa.ChunkedArray)):
                want = col.data_type.to_arrow()
                arr = values if values.type == want else values.cast(want)
                arrays.append(
                    arr.combine_chunks()
                    if isinstance(arr, pa.ChunkedArray)
                    else arr
                )
            else:
                arrays.append(_coerce_array(values, col))
        batch = pa.RecordBatch.from_arrays(arrays, schema=schema.to_arrow())
        return self.write_batch(meta, batch)

    def write_batch(self, meta, batch: pa.RecordBatch) -> int:
        """Per-region fan-out over Flight DoPut (reference Inserter).  Each
        region write runs under the retry policy with route refresh, so a
        write in flight when its datanode dies lands on the failed-over
        replica once the metasrv moves the route.  A repartition racing the
        write is absorbed here: an active fence surfaces as RetryLaterError
        without burning the per-region retry budget, and a completed swap
        re-splits the WHOLE batch through the new rule — safe because
        region writes are last-write-wins upserts on (primary key, ts), so
        replaying rows that landed pre-swap (and were copied) dedups."""
        for _ in range(3):
            if meta.options.get("repartitioning"):
                # confirm against the shared catalog before shedding: this
                # meta may be a stale cache of an already-popped fence
                self.catalog.reload()
                meta = self.catalog.table(meta.name, meta.database)
                if meta.options.get("repartitioning"):
                    raise RetryLaterError(
                        f"table {meta.name!r} is repartitioning; retry the write"
                    )
            try:
                return self._write_batch_once(meta, batch)
            except _MetaChangedError:
                self.catalog.reload()
                meta = self.catalog.table(meta.name, meta.database)
                tracing.add_event(
                    "write.meta_refresh", table=meta.name,
                    regions=len(meta.region_ids),
                )
        return self._write_batch_once(meta, batch)

    def _write_batch_once(self, meta, batch: pa.RecordBatch) -> int:
        routes = self.meta.get_route(meta.table_id)
        table = pa.Table.from_batches([batch])
        affected = 0
        region_ids = meta.region_ids
        trace_parent = tracing.current_span()
        with self.admission.admit(meta.database, kind="write"):
            with tracing.stage("write.split") as split:
                non_empty = [
                    (i, part) for i, part in enumerate(meta.partition_rule.split(table))
                    if part.num_rows
                ]
                split.set(regions=len(non_empty))
            metrics.INGEST_SPLIT_MS.observe(split.duration_s * 1000)
            for i, part in non_empty:
                rid = region_ids[i]
                for b in part.to_batches():
                    with _maybe_span(
                        "write.region", trace_parent, region=rid,
                        rows=b.num_rows,
                    ):
                        affected += self._call_region(
                            meta, rid, lambda c, r, _b=b: c.write(r, _b),
                            routes=routes, write=True,
                        )
        if affected:
            # flows are a derived view: mirror AFTER the write is durable,
            # asynchronously, and never let a mirror failure reach the user
            # (reference detaches FlowMirrorTask the same way)
            self.mirror.submit(meta.name, meta.database, table)
        return affected

    def insert_rows(self, table: str, rows, database: str | None = None) -> int:
        meta = self._table(table, database)
        if isinstance(rows, pa.Table):
            batches = rows.combine_chunks().to_batches()
        else:
            batches = [rows]
        from ..database import _conform_batch

        total = 0
        for b in batches:
            with tracing.stage("write.batch", table=table, rows=b.num_rows) as st:
                total += self.write_batch(meta, _conform_batch(b, meta.schema))
            if tracing.counting():
                metrics.WRITE_BATCH_S.inc(st.duration_s)
        return total

    # ---- SHOW / DESCRIBE ---------------------------------------------------
    def _show(self, stmt: ShowStmt):
        # shared renderers keep this byte-identical to the standalone
        # Database (shared sqlness goldens enforce it)
        from ..database import filter_like

        if stmt.what == "tables":
            self.catalog.reload()
            db_name = getattr(stmt, "database", None) or self.current_database
            names = [m.name for m in self.catalog.tables(db_name)]
            return pa.table({"Tables": filter_like(names, stmt.like)})
        if stmt.what == "databases":
            self.catalog.reload()
            return pa.table({"Database": self.catalog.databases()})
        raise UnsupportedError(f"SHOW {stmt.what} is standalone-only for now")

    def _describe(self, stmt: DescribeStmt):
        from ..database import render_describe

        return render_describe(self._table(stmt.table))

    # ---- query providers (mirror Cluster's, over Flight) -------------------
    def _pred(self, scan: TableScan) -> ScanPredicate:
        return ScanPredicate(
            time_range=scan.time_range, filters=[tuple(f) for f in scan.filters]
        )

    def _routed(self, routes: dict, rid: int, meta) -> int:
        node = routes.get(rid)
        if node is None:
            # same retryable shape as the write path: an unrouted region
            # (metasrv restarted, table created outside the cluster) must
            # never surface as a raw KeyError / HTTP 500
            raise RetryLaterError(
                f"region {rid} of {meta.name!r} has no route yet; retry"
            )
        return node

    def _executor(self):
        with self._pool_lock:
            if self._pool is None:
                from concurrent.futures import ThreadPoolExecutor

                # sized for I/O-bound waiting, not CPU: workers spend their
                # time blocked on Flight RPCs (and retry backoff sleeps), so
                # the pool must absorb several concurrent multi-region
                # queries without one query's regions starving another's
                # into a spurious deadline
                self._pool = ThreadPoolExecutor(
                    max_workers=32,
                    thread_name_prefix=f"frontend{self.node_id}-fanout",
                )
            return self._pool

    # ---- hedged reads ------------------------------------------------------
    def _followers_for(self, meta) -> dict[int, list[int]]:
        """Hedge-eligible follower replicas per region, or {} when hedging
        is off (the off-safe default: replica.read_followers=False,
        hedge_delay_ms=0).  With replica.max_lag_ms set, followers whose
        reported staleness exceeds the bound are filtered out HERE — a
        hedge must beat the primary's tail, not serve data older than the
        contract allows.  Unknown lag (no heartbeat stats yet) stays
        eligible — the pre-freshness behavior.  A follower that never
        syncs reports lag growing from its open time, so max_lag_ms with
        tailing disabled would silently gate every follower out within
        max_lag_ms of its open; Config.validate rejects that combination
        (manual sync_followers() deployments refresh last_sync_ms and
        stay gateable, which is why the gate itself doesn't key off
        sync_interval_ms)."""
        if not (
            self.config.replica.read_followers
            and self.config.query.hedge_delay_ms > 0
        ):
            return {}
        cached = self._follower_cache.get(meta.table_id)
        if cached is not None and _time.monotonic() - cached[0] < self._follower_ttl_s:
            return cached[1]
        try:
            followers, lag = self.meta.get_followers_full(meta.table_id)
        except Exception:  # noqa: BLE001 — hedging is advisory, reads proceed
            followers, lag = {}, {}
        max_lag = self.config.replica.max_lag_ms
        if max_lag > 0 and followers:
            gated: dict[int, list[int]] = {}
            for rid, nodes in followers.items():
                keep = []
                for node in nodes:
                    node_lag = lag.get(rid, {}).get(node)
                    if node_lag is not None and node_lag > max_lag:
                        metrics.HEDGE_SKIPPED_STALE_TOTAL.inc()
                        continue
                    keep.append(node)
                if keep:
                    gated[rid] = keep
            followers = gated
        self._follower_cache[meta.table_id] = (_time.monotonic(), followers)
        return followers

    def _hedge_delay_s(self) -> float:
        """Configured floor, raised to the observed latency percentile once
        enough sub-requests have been sampled ("hedge after the p95")."""
        base = self.config.query.hedge_delay_ms / 1000.0
        p = self._latency.percentile(self.config.query.hedge_percentile)
        return base if p is None else max(base, p)

    def _hedge_call(self, node: int, rid: int, fn):
        """ONE attempt against a follower — no retries, no route refresh:
        the primary (which has both) is still in flight; the hedge only
        exists to beat its tail."""
        return self._guarded_call(
            node, lambda: fn(self._client(node), rid), record_latency=True
        )

    def _submit_hedge(self, pool, flist: list[int], rid: int, hedge_fn):
        """Pick the first follower whose breaker would admit a call (a
        non-consuming peek — the consuming gate runs in `_guarded_call`
        inside the worker); (None, None) when every follower is shedding.
        `hedge_fn` is the deadline-propagated hedge thunk pre-wrapped on
        the fan-out thread (the wheel thread has no deadline context)."""
        for node in flist:
            br = self._breaker(node)
            if br is not None and not br.would_allow():
                continue
            metrics.HEDGE_REQUESTS_TOTAL.inc()
            return node, pool.submit(hedge_fn, node, rid)
        return None, None

    def _arm_hedge(
        self, pool, rid: int, fut, flist, hedge_delay, deadline, hedges,
        queues, hedge_fn,
    ):
        """Arm region `rid`'s hedge on the shared timer wheel at FAN-OUT
        SUBMIT time: every region's hedge fires at t0 + hedge_delay
        concurrently, regardless of where the sequential settle loop is
        (previously a slow early region delayed every later region's
        hedge past its schedule).  The callback runs on the wheel thread:
        cheap checks + one pool submit."""

        def arm():
            if fut.done():
                return  # primary already answered (or failed): no hedge
            if deadline is not None and _time.monotonic() >= deadline:
                return  # a dead query must not dispatch duplicate reads
            node, hedge = self._submit_hedge(pool, flist, rid, hedge_fn)
            if hedge is not None:
                hedges[rid] = (node, hedge)
                hedge.add_done_callback(queues[rid].put)

        return self._hedge_wheel.schedule(hedge_delay, arm)

    def _settle_region(self, rid: int, fut, meta, q, timer, hedges, deadline):
        """Wait for region `rid`'s primary sub-request (and its hedge, if
        the wheel armed one — first response wins; reference: hedged
        requests over MergeScan fan-out; The Tail at Scale).  Completions
        arrive on the region's queue via future done-callbacks, so a
        hedge armed while this loop is blocked wakes it naturally.
        Raises QueryTimeoutError when the deadline expires with nothing
        settled."""
        import queue as _queue

        def remaining():
            return max(deadline - _time.monotonic(), 0.0) if deadline is not None else None

        errors: list[Exception] = []
        primary_done = False
        hedge_done = False
        while True:
            if deadline is not None and remaining() <= 0.0:
                raise QueryTimeoutError(
                    f"distributed fan-out for {meta.name!r} exceeded "
                    f"the query deadline; region {rid} still pending"
                )
            try:
                f = q.get(timeout=remaining())
            except _queue.Empty:
                raise QueryTimeoutError(
                    f"distributed fan-out for {meta.name!r} exceeded "
                    f"the query deadline; region {rid} still pending"
                ) from None
            entry = hedges.get(rid)
            hedge_fut = entry[1] if entry is not None else None
            is_hedge = hedge_fut is not None and f is hedge_fut
            if is_hedge:
                hedge_done = True
            else:
                primary_done = True
            try:
                value = f.result()
            except QueryTimeoutError:
                raise
            except Exception as exc:  # noqa: BLE001 — maybe the twin wins
                # the PRIMARY's error first: the hedge is a single
                # best-effort attempt against a possibly-stale follower
                # (its failure must not mask/reclassify the region's
                # real outcome when both sides fail)
                if is_hedge:
                    errors.append(exc)
                else:
                    errors.insert(0, exc)
                if not primary_done:
                    continue  # hedge failed, primary still in flight
                # primary has failed: is a hedge still (or about to be)
                # in flight?  cancel() True = the wheel will never arm
                # one; False = the arm callback ran — wait it out (it is
                # cheap) and re-check what it submitted.
                if timer is not None and not timer.cancel():
                    timer.wait(5.0)
                    entry = hedges.get(rid)
                    hedge_fut = entry[1] if entry is not None else None
                if hedge_fut is not None and not hedge_done:
                    continue  # wait for the in-flight hedge
                raise errors[0]
            if is_hedge:
                metrics.HEDGE_WINS_TOTAL.inc()
                tracing.add_event("hedge.win", region=rid)
            return value

    def _fanout(self, meta, fn):
        """Run `fn(client, rid)` for every region of `meta` concurrently on
        the shared pool (reference MergeScanExec fans sub-queries per
        region, merge_scan.rs:250-330).  Semantics:

          * each region request runs under the retry policy with route
            refresh (`_call_region`), so mid-query failover is consumed;
          * nodes with an open circuit breaker are skipped without a wire
            call (load shedding; see `_guarded_call`);
          * with follower replicas registered and hedging enabled, a region
            sub-query still outstanding after the hedge delay is duplicated
            to a follower — first response wins;
          * the active query deadline crosses into the pool workers
            (deadline.propagate) AND bounds the gather — a datanode that
            hangs without erroring yields QueryTimeoutError, never a stuck
            frontend — and the hung sub-request is ABANDONED: its future is
            detached and its client dropped, so the next query dials a
            fresh connection instead of queueing behind the hung call;
          * regions still failing transiently after retries surface as ONE
            RetryLaterError naming the failed region ids (the SQL layer's
            retryable status), while non-transient errors propagate as-is.
        """
        routes = self.meta.get_route(meta.table_id)
        rids = meta.region_ids
        submit_rids = rids
        mesh_n = int(getattr(self.config.tile, "mesh_devices", 0) or 0)
        if mesh_n > 0 and len(rids) > 1:
            # Device-local fan-out (tile.mesh_devices): SUBMIT region
            # sub-queries in their co-located mesh-device order — the
            # same region -> device mapping the tile cache places
            # super-tile chunks with (parallel/mesh.py
            # region_device_index) — so a datanode's work starts on the
            # device that already holds its region shards instead of
            # interleaving every region through device 0 first.  Results
            # are still SETTLED and returned in the original region-id
            # order: the fan-out's output feeds state merges and scan
            # concats whose fold order must not change with a locality
            # knob.
            from ..parallel.mesh import region_device_index

            submit_rids = sorted(
                rids, key=lambda r: (region_device_index(r, mesh_n), r)
            )
        deadline = current_deadline()
        followers = self._followers_for(meta)
        hedge_delay = self._hedge_delay_s() if followers else None
        # captured HERE (the statement's thread): pool workers see no
        # contextvars, so each region sub-query span is parented explicitly
        trace_parent = tracing.current_span()

        def give_up(failed: list[int], last_exc: Exception):
            raise RetryLaterError(
                f"regions {failed} of {meta.name!r} unavailable after "
                f"{self.retry_policy.max_attempts} attempts: {last_exc}"
            ) from last_exc

        if len(rids) <= 1 and deadline is None and not followers:
            results = []
            for rid in rids:
                try:
                    with _maybe_span("fanout.region", trace_parent, region=rid):
                        results.append(
                            self._call_region(
                                meta, rid, fn, routes=routes, record_latency=True
                            )
                        )
                except Exception as exc:  # noqa: BLE001 — classified below
                    if not is_transient(exc):
                        raise
                    give_up([rid], exc)
            return results
        import queue as _queue

        pool = self._executor()
        inflight: dict[int, tuple[int, int]] = {}  # rid -> (node, worker thread)

        def _region_worker(rid):
            # one child span per region sub-query; its traceparent is
            # injected into the Flight ticket by the client, extracted on
            # the datanode (the reference propagates tracing context
            # across every RPC boundary the same way)
            with _maybe_span("fanout.region", trace_parent, region=rid):
                return self._call_region(meta, rid, fn, routes, inflight, True)

        futures = {
            rid: pool.submit(propagate(_region_worker), rid)
            for rid in submit_rids
        }
        # settle in ORIGINAL region order regardless of submit order
        futures = {rid: futures[rid] for rid in rids}
        # per-region completion queues fed by future done-callbacks: the
        # settle loop blocks on its region's queue, so hedges armed by the
        # wheel while it waits wake it without polling
        queues = {rid: _queue.SimpleQueue() for rid in rids}
        for rid, fut in futures.items():
            fut.add_done_callback(queues[rid].put)
        hedges: dict[int, object] = {}
        timers: dict[int, object] = {}
        hedge_threads: dict[int, int] = {}  # rid -> hedge worker thread
        if hedge_delay is not None:
            # deadline context is thread-local: wrap the hedge call HERE
            # so the wheel-thread submit still propagates this query's
            # deadline into the pool worker
            def _hedge_worker(node, hrid):
                hedge_threads[hrid] = threading.get_ident()
                with _maybe_span(
                    "fanout.hedge", trace_parent, region=hrid, node=node
                ):
                    return self._hedge_call(node, hrid, fn)

            hedge_fn = propagate(_hedge_worker)
            for rid, fut in futures.items():
                flist = followers.get(rid)
                if flist:
                    timers[rid] = self._arm_hedge(
                        pool, rid, fut, flist, hedge_delay, deadline,
                        hedges, queues, hedge_fn,
                    )
        results: list = []
        failed: list[int] = []
        last_exc: Exception | None = None
        timed_out = False

        def note_failure(rid: int, exc: Exception):
            nonlocal last_exc
            if not is_transient(exc):
                raise exc
            failed.append(rid)
            last_exc = exc

        try:
            for rid, fut in futures.items():
                try:
                    results.append(
                        self._settle_region(
                            rid, fut, meta, queues[rid], timers.get(rid),
                            hedges, deadline,
                        )
                    )
                except QueryTimeoutError:
                    timed_out = True
                    raise
                except Exception as exc:  # noqa: BLE001 — classified
                    note_failure(rid, exc)
        finally:
            # cancel pending timers; a callback already RUNNING on the
            # wheel thread may still be inserting into `hedges`, so wait
            # it out before iterating the dict (a mid-iteration insert
            # raises RuntimeError inside this finally, replacing the real
            # outcome and skipping the abandoned-client cleanup)
            for timer in timers.values():
                if not timer.cancel():
                    timer.wait(1.0)
            # no-op for completed futures; sheds queued work on early exit
            for fut in list(futures.values()) + [f for _n, f in hedges.values()]:
                fut.cancel()
            if timed_out:
                # deadline expired with sub-requests still running: DETACH
                # them (nobody joins a hung worker), best-effort CANCEL the
                # in-flight Flight readers when the installed pyarrow
                # supports it, and drop their clients so the next query
                # dials a fresh connection instead of sharing a channel
                # with a stuck call
                # group abandoned workers PER NODE before cancelling: the
                # client is shared per datanode, so abandoning region-by-
                # region would evict it on the first call and leave the
                # second worker's in-flight call uncancelled (and its
                # foreign-looking token would also suppress the channel-
                # close fallback for the first)
                abandoned: dict[int | None, set] = {}
                for rid, fut in futures.items():
                    if not fut.done() and not fut.cancelled():
                        metrics.FANOUT_ABANDONED_TOTAL.inc()
                        entry = inflight.get(rid)
                        if entry is not None:
                            node, worker = entry
                            abandoned.setdefault(node, set()).add(worker)
                for hrid, (node, fut) in hedges.items():
                    if not fut.done() and not fut.cancelled():
                        metrics.FANOUT_ABANDONED_TOTAL.inc()
                        worker = hedge_threads.get(hrid)
                        workers = abandoned.setdefault(node, set())
                        if worker is not None:
                            workers.add(worker)
                for node, workers in abandoned.items():
                    self._abandon_client(node, workers)
        if failed:
            give_up(failed, last_exc)
        return results

    def _with_fresh_meta(self, table: str, database: str | None, run):
        """Run `run(meta)` with repartition-staleness recovery: when every
        retry under it failed (RetryLaterError) and a catalog reload shows
        the table's region set CHANGED — a repartition swapped generations
        and dropped the old regions this meta still names — re-run against
        the fresh meta instead of surfacing a retryable error for a query
        the frontend can answer.  Route refresh alone cannot absorb a
        repartition for reads: the region IDS change, not just their
        placement.  Unchanged region set = a real outage: re-raise."""
        meta = self._table(table, database)
        for _ in range(3):
            try:
                return run(meta)
            except RetryLaterError:
                self.catalog.reload()
                fresh = self._table(table, database)
                if fresh.region_ids == meta.region_ids:
                    raise
                tracing.add_event(
                    "read.meta_refresh", table=table,
                    regions=len(fresh.region_ids),
                )
                meta = fresh
        return run(meta)

    def _region_scan(self, scan: TableScan) -> list[pa.Table]:
        pred = self._pred(scan)
        return self._with_fresh_meta(
            scan.table, scan.database,
            lambda meta: self._fanout(meta, lambda c, rid: c.scan(rid, pred)),
        )

    def _partial_agg(self, scan: TableScan, spec_dict: dict) -> list[pa.Table]:
        pred = self._pred(scan)
        return self._with_fresh_meta(
            scan.table, scan.database,
            lambda meta: self._fanout(
                meta, lambda c, rid: c.partial_agg(rid, pred, spec_dict)
            ),
        )

    def _sub_plan(self, scan: TableScan, plan_dict: dict) -> list[pa.Table]:
        return self._with_fresh_meta(
            scan.table, scan.database,
            lambda meta: self._fanout(
                meta, lambda c, rid: c.execute_plan(rid, plan_dict)
            ),
        )

    def _scan(self, scan: TableScan) -> pa.Table:
        if not scan.table:
            return pa.table({"__dummy": [0]})  # constant SELECTs (UNION arms)
        tables = [t for t in self._region_scan(scan) if t.num_rows]
        meta = self._table(scan.table, scan.database)
        if not tables:
            return meta.schema.to_arrow().empty_table()
        return pa.concat_tables(tables, promote_options="permissive")

    def _time_bounds(self, table: str, database: str):
        def run(meta):
            routes = self.meta.get_route(meta.table_id)
            lo = hi = None
            for rid in meta.region_ids:
                b = self._call_region(
                    meta, rid, lambda c, r: c.time_bounds(r), routes=routes
                )
                if b is None:
                    continue
                lo = b[0] if lo is None else min(lo, b[0])
                hi = b[1] if hi is None else max(hi, b[1])
            return (lo or 0, hi or 0)

        return self._with_fresh_meta(table, database, run)

    # ---- liveness ----------------------------------------------------------
    def heartbeat(self):
        """Frontend liveness ping to the metasrv (reference
        frontend/src/heartbeat.rs)."""
        try:
            self.meta.handle_heartbeat(
                self.node_id, [], _time.time() * 1000, role="frontend"
            )
        except Exception:  # noqa: BLE001 — liveness is advisory
            pass

    def close(self):
        from ..utils import self_trace

        self_trace.stop(self)
        self._hedge_wheel.stop()
        self.mirror.close()
        with self._pool_lock:
            if self._pool is not None:
                self._pool.shutdown(wait=False, cancel_futures=True)
                self._pool = None
        with self._clients_lock:
            self._clients.clear()
