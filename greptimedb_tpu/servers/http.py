"""HTTP protocol server.

Role-equivalent of the reference's axum HTTP surface (reference
servers/src/http.rs:542-734): /v1/sql, InfluxDB /v1/influxdb/write,
Prometheus HTTP API v1 (query, query_range, labels, label values, series —
reference servers/src/http/prometheus.rs), /metrics exposition, /health and
/config.  Built on the stdlib ThreadingHTTPServer — the serving plane has no
exotic needs and zero extra dependencies this way.
"""

from __future__ import annotations

import json
import threading
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from ..utils.errors import GreptimeError, StatusCode
from ..utils.metrics import (
    HTTP_RENDER_COLUMNAR_CELLS,
    HTTP_RENDER_FALLBACK_CELLS,
    HTTP_REQUEST_S,
    REGISTRY,
)
from ..utils.tracing import stage
from .influx import parse_line_protocol, write_points


def _json_value(v):
    import datetime

    if isinstance(v, datetime.datetime):
        return int(v.timestamp() * 1000)
    if isinstance(v, float) and (np.isnan(v) or np.isinf(v)):
        return None
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    return v


# ---- /v1/sql and /v1/logs records, rendered by column ----------------------
# A column first becomes an array of values JSON has, by Arrow kernels
# chosen from its type (`_json_ready`).  An answer of some size is then
# assembled as text in Arrow too, a large_string array of JSON texts per
# column and one join per row, so no Python object exists per cell between
# the table and the response bytes; a small one, where the dozen Arrow
# calls of that cost more than its cells, goes to `json` as lists.  What
# the kernels do not cover goes cell by cell through `_json_value`, a
# column at a time.

_TEXT = pa.large_string()
_EMPTY, _QUOTE, _COMMA, _ROW_SEP, _POINT_ZERO = (
    pa.scalar(t, _TEXT) for t in ("", '"', ",", "],[", ".0")
)
# towards zero, as `int()` cut the old float of milliseconds
_TO_MS = {"s": (pc.multiply_checked, 1000), "us": (pc.divide, 1000), "ns": (pc.divide, 1_000_000)}
# a character `json.dumps` would escape, or DEL: anything but printable
# ASCII without `"` and `\`
_MUST_ESCAPE = r'[^ !#-\[\]-~]'
# rows from which assembling the text in Arrow is the cheaper way: its
# calls cost what some 90 rows of three columns cost as lists
_TEXT_MIN_ROWS = 64
_compact_json = json.JSONEncoder(separators=(",", ":")).encode


def _json_ready(col: pa.Array) -> pa.Array | None:
    """`col` with a value JSON has in every cell (a null cell stays null);
    None for a type the kernels do not cover."""
    t = col.type
    if pa.types.is_dictionary(t):
        t = t.value_type
        col = col.cast(t)
    if pa.types.is_timestamp(t):
        # the stored integer, in milliseconds: no datetime, no host zone
        ms = col.cast(pa.int64())
        if t.unit != "ms":
            scale, by = _TO_MS[t.unit]
            ms = scale(ms, by)
        return ms
    if pa.types.is_floating(t):
        x = col if t == pa.float64() else col.cast(pa.float64())
        values = x.to_numpy(zero_copy_only=False)  # a null reads NaN
        finite = np.isfinite(values)
        return x if finite.all() else pa.array(values, mask=~finite)
    if (pa.types.is_integer(t) or pa.types.is_boolean(t) or pa.types.is_null(t)
            or pa.types.is_string(t) or pa.types.is_large_string(t)):
        return col
    return None


def _json_text(ready: pa.Array) -> pa.Array:
    """Every cell of a `_json_ready` column as JSON text."""
    t = ready.type
    text = ready.cast(_TEXT)
    if pa.types.is_floating(t):
        # Arrow writes the shortest digits that round-trip, and a whole
        # number without its fraction: 100.0 has to stay a float
        whole = pc.invert(pc.match_substring_regex(text, "[.e]"))
        if pc.any(whole).as_py():
            dotted = pc.binary_join_element_wise(text, _POINT_ZERO, _EMPTY)
            text = pc.if_else(whole, dotted, text)
    elif pa.types.is_string(t) or pa.types.is_large_string(t):
        if pc.any(pc.match_substring_regex(text, _MUST_ESCAPE)).as_py():
            escape = json.encoder.encode_basestring_ascii
            cells = [v if v is None else escape(v) for v in text.to_pylist()]
            return pa.array(cells, _TEXT)
        text = pc.binary_join_element_wise(_QUOTE, text, _QUOTE, _EMPTY)
    return text


def _rows_json(batch: pa.RecordBatch) -> bytes:
    """`batch`'s rows as `[a,b],[c,d]`: the `rows` array without its own
    brackets."""
    if not batch.num_columns:
        return b",".join([b"[]"] * batch.num_rows)
    as_text = batch.num_rows >= _TEXT_MIN_ROWS
    columns = []
    for col in batch.columns:
        ready = _json_ready(col)
        if ready is None:
            HTTP_RENDER_FALLBACK_CELLS.inc(len(col))
            cells = [_json_value(v) for v in col.to_pylist()]
            columns.append(pa.array(map(_compact_json, cells), _TEXT) if as_text else cells)
        else:
            HTTP_RENDER_COLUMNAR_CELLS.inc(len(col))
            columns.append(_json_text(ready) if as_text else ready.to_pylist())
    if not as_text:
        return _compact_json(list(zip(*columns)))[1:-1].encode()
    rows = pc.binary_join_element_wise(
        *columns, _COMMA, null_handling="replace", null_replacement="null"
    )
    whole = pa.LargeListArray.from_arrays(pa.array([0, len(rows)], pa.int64()), rows)
    return b"[%b]" % pc.binary_join(whole, _ROW_SEP)[0].as_buffer().to_pybytes()


def _result_json(result: pa.Table | int | None) -> bytes:
    """One statement's result in the reference's /v1/sql response shape
    (servers/src/http/handler.rs GreptimedbV1 output)."""
    if result is None:
        return b'{"affectedrows":0}'
    if isinstance(result, int):
        return b'{"affectedrows":%d}' % result
    schema = _compact_json({
        "column_schemas": [
            {"name": f.name, "data_type": str(f.type)} for f in result.schema
        ]
    })
    rows = b",".join(
        _rows_json(batch)
        for batch in result.combine_chunks().to_batches() if batch.num_rows
    )
    return b'{"records":{"schema":%b,"rows":[%b]}}' % (schema.encode(), rows)


def _results_json(results) -> bytes:
    """The whole /v1/sql (and /v1/logs) response document."""
    return b'{"output":[%b],"execution_time_ms":0}' % b",".join(
        _result_json(result) for result in results
    )


class _Handler(BaseHTTPRequestHandler):
    server_version = "greptimedb-tpu/0.1"
    db = None  # set by HttpServer

    # ---- plumbing ---------------------------------------------------------
    def log_message(self, fmt, *args):
        pass  # quiet; metrics cover it

    def _send(self, code: int, payload, content_type="application/json"):
        """Render (`http.render`, unless the caller already holds bytes),
        then write (`http.write`): two stages, so that rendering a large
        answer is not timed as socket time.  A callable `payload` builds
        the document, or its bytes, inside the render stage."""
        if isinstance(payload, bytes):
            body = payload
        else:
            with stage("http.render"):
                if callable(payload):
                    payload = payload()
                body = payload if isinstance(payload, bytes) else json.dumps(payload).encode()
        with stage("http.write", bytes=len(body)):
            self.send_response(code)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    def _params(self) -> dict:
        parsed = urllib.parse.urlparse(self.path)
        params = {k: v[-1] for k, v in urllib.parse.parse_qs(parsed.query).items()}
        length = int(self.headers.get("Content-Length") or 0)
        if length:
            body = self.rfile.read(length)
            encoding = (self.headers.get("Content-Encoding") or "").lower()
            if "gzip" in encoding:
                import gzip

                body = gzip.decompress(body)
            elif "deflate" in encoding:
                import zlib

                body = zlib.decompress(body)
            ctype = self.headers.get("Content-Type", "")
            # ALWAYS keep the raw body: many clients (urllib, some influx
            # SDKs) default to the form content-type for payloads that are
            # not forms (line protocol, SQL text); handlers that expect raw
            # bodies read __body, form-style handlers read the parsed keys.
            params["__body"] = body
            if "application/x-www-form-urlencoded" in ctype:
                try:
                    for k, v in urllib.parse.parse_qs(body.decode()).items():
                        params[k] = v[-1]
                except UnicodeDecodeError:
                    pass  # binary body mislabelled as a form
        return params

    @property
    def route(self) -> str:
        return urllib.parse.urlparse(self.path).path

    # ---- dispatch ---------------------------------------------------------
    def do_GET(self):
        self._dispatch()

    def do_POST(self):
        self._dispatch()

    def do_DELETE(self):
        self._dispatch()

    def _dispatch(self):
        """One `http.request` stage per request, every route: the root of
        the stage clocks.  Its self time is reading the body, the hand-off
        to the kernel thread and whatever no child stage covers; a traced
        statement adds its `trace_id` (`tracing.set_root_attribute`)."""
        with stage("http.request", route=self.route) as st:
            self._route()
        HTTP_REQUEST_S.inc(st.duration_s)

    def _route(self):
        try:
            self.db.ensure_session()  # per-request session anchor
            route = self.route
            params = self._params()
            if route == "/health" or route == "/ping":
                return self._send(200, {})
            if route == "/metrics":
                return self._send(200, REGISTRY.render().encode(), "text/plain; version=0.0.4")
            if route == "/config":
                import dataclasses

                return self._send(200, dataclasses.asdict(self.db.config))
            if route == "/v1/sql":
                return self._handle_sql(params)
            if route == "/v1/logs":
                return self._handle_logs(params)
            if route == "/v1/influxdb/write" or route == "/v1/influxdb/api/v2/write":
                return self._handle_influx(params)
            if route.startswith("/v1/prometheus/api/v1/") or route.startswith("/api/v1/"):
                return self._handle_prometheus(route.rsplit("/api/v1/", 1)[1], params)
            if route == "/v1/prometheus/write":
                return self._handle_prom_write(params)
            if route == "/v1/prometheus/read":
                return self._handle_prom_read(params)
            if route.startswith("/v1/otlp/v1/"):
                return self._handle_otlp(route.rsplit("/", 1)[1], params)
            if route.startswith("/v1/pipelines/"):
                return self._handle_pipelines(route[len("/v1/pipelines/") :], params)
            if route == "/v1/ingest":
                return self._handle_ingest(params)
            if route in ("/v1/loki/api/v1/push", "/loki/api/v1/push"):
                return self._handle_loki(params)
            if route.startswith("/v1/elasticsearch") and route.endswith("/_bulk"):
                mid = route[len("/v1/elasticsearch") : -len("/_bulk")].strip("/")
                return self._handle_elasticsearch(mid or None, params)
            if route in ("/v1/opentsdb/api/put", "/opentsdb/api/put"):
                return self._handle_opentsdb(params)
            if route.startswith("/v1/jaeger/api/") or route.startswith("/jaeger/api/"):
                endpoint = route.split("/api/", 1)[1]
                return self._handle_jaeger(endpoint, params)
            if route == "/debug/prof/cpu":
                return self._handle_prof_cpu(params)
            if route == "/debug/prof/mem":
                return self._handle_prof_mem(params)
            if route == "/debug/tile":
                return self._handle_tile(params)
            return self._send(404, {"error": f"no route {route}"})
        except GreptimeError as e:
            # the root trace id (attached by the self-observability loop
            # when trace.self is on) makes a user-reported failure one
            # Jaeger lookup away
            payload = {"error": str(e), "code": int(e.status_code())}
            trace_id = getattr(e, "trace_id", None)
            if trace_id:
                payload["trace_id"] = trace_id
            self._send(400, payload)
        except Exception as e:  # noqa: BLE001
            import logging
            import traceback

            logging.getLogger("greptimedb_tpu.http").error(
                "500 on %s: %s", self.path, traceback.format_exc()
            )
            self._send(500, {"error": f"{type(e).__name__}: {e}"})

    # ---- handlers ---------------------------------------------------------
    def _handle_loki(self, params):
        from . import loki

        n = loki.ingest(
            self.db,
            params.get("__body") or b"",
            content_type=self.headers.get("Content-Type", ""),
            database=params.get("db", "public"),
        )
        # Loki replies 204 No Content on success
        self.send_response(204)
        self.send_header("Content-Length", "0")
        self.end_headers()
        return n

    def _handle_elasticsearch(self, index, params):
        from . import elasticsearch as es

        resp = es.handle_bulk(
            self.db,
            params.get("__body") or b"",
            default_index=index,
            database=params.get("db", "public"),
        )
        return self._send(200, resp)

    def _handle_opentsdb(self, params):
        from . import opentsdb

        n = opentsdb.ingest(
            self.db, params.get("__body") or b"", database=params.get("db", "public")
        )
        # `?summary` / `?details` are bare flags (no value) — parse_qs drops
        # them, so check the raw query string
        query = urllib.parse.urlparse(self.path).query
        flags = {p.split("=", 1)[0] for p in query.split("&") if p}
        if "details" in flags:
            return self._send(200, {"success": n, "failed": 0, "errors": []})
        if "summary" in flags:
            return self._send(200, {"success": n, "failed": 0})
        self.send_response(204)
        self.send_header("Content-Length", "0")
        self.end_headers()
        return n

    def _handle_prof_cpu(self, params):
        """Statistical CPU profile of live traffic for N seconds (reference
        /debug/prof/cpu via common/pprof's sampling pprof-rs): samples every
        thread's stack at ~100 Hz and renders the hottest frames, flamegraph-
        style folded lines."""
        import sys
        import time as _time
        from collections import Counter as _Counter

        seconds = min(float(params.get("seconds", "2")), 30.0)
        me = __import__("threading").get_ident()
        counts: _Counter = _Counter()
        deadline = _time.monotonic() + seconds
        samples = 0
        while _time.monotonic() < deadline:
            for tid, frame in sys._current_frames().items():
                if tid == me:
                    continue
                stack = []
                f = frame
                while f is not None and len(stack) < 24:
                    code = f.f_code
                    stack.append(f"{code.co_name} ({code.co_filename.rsplit('/', 1)[-1]}:{f.f_lineno})")
                    f = f.f_back
                counts[";".join(reversed(stack))] += 1
            samples += 1
            _time.sleep(0.01)
        lines = [f"cpu profile: {samples} sampling rounds over {seconds}s"]
        for stack, n in counts.most_common(50):
            lines.append(f"{n} {stack}")
        return self._send(200, ("\n".join(lines) + "\n").encode(), "text/plain")

    def _handle_prof_mem(self, params):
        """Heap snapshot (reference /debug/prof/mem via jemalloc heap
        profiling; here tracemalloc top allocations)."""
        import tracemalloc

        top_n = int(params.get("top", "40"))
        started_here = not tracemalloc.is_tracing()
        if started_here:
            # first call arms tracing and reports from now on (jemalloc's
            # activation flag works the same way)
            tracemalloc.start()
            return self._send(
                200,
                b"tracemalloc armed; call again for a snapshot\n",
                "text/plain",
            )
        snap = tracemalloc.take_snapshot()
        lines = [f"heap top {top_n} by size:"]
        for stat in snap.statistics("lineno")[:top_n]:
            lines.append(str(stat))
        total = sum(s.size for s in snap.statistics("filename"))
        lines.append(f"total traced: {total / 1024 / 1024:.1f} MiB")
        return self._send(200, ("\n".join(lines) + "\n").encode(), "text/plain")

    def _handle_tile(self, params):
        """Glass-box view of the TPU hot path (sits beside /debug/prof/*):
        the flight recorder's newest dispatch records, the tile cache's
        per-region residency summary, and per-device HBM accounting —
        the same data information_schema.{device_dispatches,
        tile_cache_entries, device_memory, device_health} serves over SQL,
        as one JSON document for curl-level debugging.  `?n=` bounds the dispatch
        tail (default 50); `?table=` filters it."""
        from ..utils.flight_recorder import RECORDER

        n = max(int(params.get("n", "50")), 1)
        table_filter = params.get("table")
        recs = RECORDER.snapshot()
        if table_filter:
            recs = [r for r in recs if r.table == table_filter]
        cache = getattr(
            getattr(self.db, "query_engine", None), "tile_cache", None
        )
        entries = []
        memory = []
        if cache is not None:
            # the same under-lock snapshot + device collector the
            # information_schema tables use — two surfaces, one impl
            for e in cache.introspect_entries():
                entries.append({k: v for k, v in e.items() if k != "planes"})
            memory = cache.device_memory_rows()
        from ..utils import device_health

        sup = device_health.SUPERVISOR
        return self._send(200, {
            "recorder": {
                "enabled": RECORDER.enabled,
                "ring_size": RECORDER.ring_size,
                "records": len(recs),
                "dropped_since_start": RECORDER.dropped,
            },
            "dispatches": [r.to_dict() for r in recs[-n:]],
            "tile_cache": (
                {**cache.stats(),
                 "budget": int(cache.budget),
                 "chunk_rows": int(cache.chunk_rows),
                 "degrade_rounds": int(cache.degrade_rounds)}
                if cache is not None else {}
            ),
            "entries": entries,
            "memory": memory,
            "device_health": {
                **sup.digest(),
                "devices": sup.health_rows(
                    cache.devices if cache is not None else None
                ),
            },
        })

    def _handle_jaeger(self, endpoint: str, params):
        from . import jaeger

        database = params.get("db", "public")
        if endpoint == "services":
            return self._send(200, jaeger.services(self.db, database))
        if endpoint == "operations":
            svc = params.get("service")
            if not svc:
                return self._send(400, {"error": "missing service parameter"})
            return self._send(
                200, jaeger.operations(self.db, svc, params.get("spanKind"), database)
            )
        if endpoint.startswith("services/") and endpoint.endswith("/operations"):
            svc = endpoint[len("services/") : -len("/operations")]
            return self._send(200, jaeger.operation_names(self.db, svc, database))
        if endpoint.startswith("traces/"):
            return self._send(
                200, jaeger.get_trace(self.db, endpoint[len("traces/") :], database)
            )
        if endpoint == "traces":
            return self._send(200, jaeger.find_traces(self.db, params, database))
        return self._send(404, {"error": f"no jaeger endpoint {endpoint!r}"})

    def _handle_sql(self, params):
        sql = params.get("sql") or (params.get("__body") or b"").decode()
        if not sql:
            return self._send(400, {"error": "missing sql"})
        if params.get("db"):
            self.db.current_database = params["db"]
        from ..utils import kernel_executor
        from ..utils.tracing import protocol_scope

        # protocol tag for the statement's root span (kernel_executor runs
        # the closure under a COPY of this context, so the scope crosses)
        with protocol_scope("http"):
            results = kernel_executor.run(lambda: list(self.db.sql(sql)))
        return self._send(200, lambda: _results_json(results))

    def _handle_logs(self, params):
        """Structured log search (reference /v1/logs, log-query crate DSL)."""
        from ..query.log_query import LogQuery, execute_log_query
        from ..utils import kernel_executor

        body = params.get("__body") or b"{}"
        try:
            payload = json.loads(body.decode())
        except ValueError as e:
            return self._send(400, {"error": f"bad log query JSON: {e}"})
        if not isinstance(payload, dict):
            return self._send(400, {"error": "log query body must be a JSON object"})
        query = LogQuery.from_json(payload)
        if params.get("db") and not query.database:
            # per-query database, NOT the shared session default: concurrent
            # requests on other threads must not see this request's db
            query.database = params["db"]
        table = kernel_executor.run(lambda: execute_log_query(self.db, query))
        return self._send(200, lambda: _results_json([table]))

    def _handle_influx(self, params):
        body_raw = params.get("__body") or b""
        precision = params.get("precision", "ns")
        # columnar fast path for homogeneous batches (native parser, no
        # str round-trip); mixed/escaped batches take the Point parser
        from .influx import parse_line_protocol_columnar, write_columnar

        col = parse_line_protocol_columnar(body_raw, precision)
        if col is not None:
            measurement, table, tag_keys = col
            n = write_columnar(self.db, measurement, table, tag_keys)
        else:
            points = parse_line_protocol(body_raw.decode(), precision)
            n = write_points(self.db, points)
        REGISTRY.counter("greptime_http_influx_rows_total", "Influx rows").inc(n)
        return self._send(204, b"", "text/plain")

    def _handle_prom_write(self, params):
        from .prom_store import DEFAULT_PHYSICAL_TABLE, remote_write

        n = remote_write(
            self.db,
            params.get("__body") or b"",
            database=params.get("db", "public"),
            physical_table=params.get("physical_table", DEFAULT_PHYSICAL_TABLE),
        )
        REGISTRY.counter(
            "greptime_http_prom_write_rows_total", "Prom remote-write rows"
        ).inc(n)
        return self._send(204, b"", "text/plain")

    def _handle_pipelines(self, name: str, params):
        """Create (POST yaml body) / fetch (GET) / delete (DELETE) a pipeline
        (reference servers/src/http/event.rs pipeline handlers)."""
        from ..pipeline.manager import _pipelines

        mgr = _pipelines(self.db)
        if self.command == "POST":
            body = params.get("__body") or b""
            yaml_text = body.decode() if isinstance(body, bytes) else str(body)
            if not yaml_text.strip():
                return self._send(400, {"error": "empty pipeline body"})
            version = mgr.save(name, yaml_text)
            return self._send(200, {"pipelines": [{"name": name, "version": version}]})
        if self.command == "DELETE":
            mgr.delete(name, params.get("version"))
            return self._send(200, {"pipelines": [{"name": name}]})
        pipeline = mgr.get(name, params.get("version"))
        return self._send(200, pipeline.source.encode(), "application/x-yaml")

    def _handle_ingest(self, params):
        """Log ingestion through a named pipeline: NDJSON / JSON array body
        (reference servers/src/http/event.rs log_ingester)."""
        import json as _json

        from ..pipeline import GREPTIME_IDENTITY, run_pipeline_ingest

        table = params.get("table")
        if not table:
            return self._send(400, {"error": "missing table parameter"})
        pipeline_name = params.get("pipeline_name", GREPTIME_IDENTITY)
        body = params.get("__body") or b""
        text = body.decode() if isinstance(body, bytes) else str(body)
        docs: list[dict] = []
        stripped = text.strip()
        whole = None
        if stripped.startswith(("[", "{")):
            # whole-body JSON first (array of docs, or one possibly
            # pretty-printed object); fall back to NDJSON line splitting
            try:
                whole = _json.loads(stripped)
            except _json.JSONDecodeError:
                if stripped.startswith("["):
                    return self._send(400, {"error": "invalid JSON array body"})
        if isinstance(whole, list):
            docs = [d for d in whole if isinstance(d, dict)]
        elif isinstance(whole, dict):
            docs = [whole]
        else:
            for line in stripped.splitlines():
                line = line.strip()
                if not line:
                    continue
                try:
                    doc = _json.loads(line)
                except _json.JSONDecodeError:
                    doc = None
                if not isinstance(doc, dict):
                    doc = {"message": line}  # plain-text / scalar log lines
                docs.append(doc)
        n = run_pipeline_ingest(
            self.db,
            pipeline_name,
            docs,
            table,
            database=params.get("db", "public"),
            version=params.get("version"),
        )
        REGISTRY.counter("greptime_http_ingest_rows_total", "Pipeline ingest rows").inc(n)
        return self._send(200, {"rows": n})

    def _handle_otlp(self, signal: str, params):
        from . import otlp

        body = params.get("__body") or b""
        db_name = self.headers.get("X-Greptime-DB-Name") or params.get("db", "public")
        if signal == "arrow":
            # ONLY /v1/otlp/v1/metrics/arrow exists (reference
            # otel_arrow.rs is metrics-only); traces/arrow etc. must 404
            if not self.path.split("?")[0].endswith("/metrics/arrow"):
                return self._send(404, {"error": "unknown OTel-Arrow endpoint"})
            n = otlp.ingest_metrics_arrow(self.db, body, database=db_name)
            REGISTRY.counter("greptime_http_otlp_rows_total", "OTLP rows").inc(n)
            return self._send(200, {"batch_status": "ok", "rows": n})
        if signal == "metrics":
            n = otlp.ingest_metrics(self.db, body, database=db_name)
        elif signal == "traces":
            n = otlp.ingest_traces(
                self.db,
                body,
                database=db_name,
                table=self.headers.get("X-Greptime-Trace-Table-Name")
                or otlp.TRACE_TABLE_NAME,
            )
        elif signal == "logs":
            n = otlp.ingest_logs(
                self.db,
                body,
                database=db_name,
                table=self.headers.get("X-Greptime-Log-Table-Name")
                or otlp.LOG_TABLE_NAME,
                pipeline_name=self.headers.get("X-Greptime-Log-Pipeline-Name"),
            )
        else:
            return self._send(404, {"error": f"unknown OTLP signal {signal}"})
        REGISTRY.counter("greptime_http_otlp_rows_total", "OTLP rows").inc(n)
        # Export*ServiceResponse with no rejected points = empty message.
        return self._send(200, b"", "application/x-protobuf")

    def _handle_prom_read(self, params):
        from .prom_store import remote_read

        body = remote_read(
            self.db, params.get("__body") or b"", database=params.get("db", "public")
        )
        self.send_response(200)
        self.send_header("Content-Type", "application/x-protobuf")
        self.send_header("Content-Encoding", "snappy")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _handle_prometheus(self, endpoint: str, params):
        from ..query.promql.engine import PromqlEngine

        from ..utils import kernel_executor

        engine = PromqlEngine(self.db)
        if endpoint == "query_range":
            start = float(params["start"])
            end = float(params["end"])
            step = _prom_duration_s(params.get("step", "60"))
            table = kernel_executor.run(
                engine.query_range,
                params["query"], int(start * 1000), int(end * 1000), int(step * 1000),
            )
            return self._send(200, lambda: _prom_matrix_json(table))
        if endpoint == "query":
            t = float(params.get("time", 0))
            table = kernel_executor.run(
                engine.query_instant, params["query"], int(t * 1000)
            )
            return self._send(200, lambda: _prom_vector_json(table))
        if endpoint == "labels":
            labels = set()
            for meta in self.db.catalog.tables(self.db.current_database):
                labels.update(c.name for c in meta.schema.tag_columns())
            labels.add("__name__")
            return self._send(200, {"status": "success", "data": sorted(labels)})
        if endpoint.startswith("label/") and endpoint.endswith("/values"):
            label = endpoint[len("label/") : -len("/values")]
            values = set()
            if label == "__name__":
                values = {m.name for m in self.db.catalog.tables(self.db.current_database)}
            else:
                import pyarrow.compute as pc

                for meta in self.db.catalog.tables(self.db.current_database):
                    if any(c.name == label for c in meta.schema.tag_columns()):
                        from ..query.logical_plan import TableScan

                        for t in self.db._region_scan(TableScan(meta.name, meta.database)):
                            if label in t.column_names and t.num_rows:
                                col = t[label]
                                if pa.types.is_dictionary(col.type):
                                    col = pc.cast(col, col.type.value_type)
                                values.update(v for v in pc.unique(col).to_pylist() if v)
            return self._send(200, {"status": "success", "data": sorted(values)})
        if endpoint == "series":
            return self._send(200, {"status": "success", "data": []})
        return self._send(404, {"status": "error", "error": f"unknown endpoint {endpoint}"})


def _prom_duration_s(s: str) -> float:
    try:
        return float(s)
    except ValueError:
        from ..query.promql.parser import _duration_ms

        return _duration_ms(s) / 1000.0


def _prom_matrix_json(table: pa.Table) -> dict:
    label_cols = [c for c in table.column_names if c not in ("ts", "value")]
    series: dict[tuple, list] = {}
    ts = [int(v.timestamp()) if hasattr(v, "timestamp") else int(v) // 1000 for v in table["ts"].to_pylist()]
    vals = table["value"].to_pylist()
    labels = [table[c].to_pylist() for c in label_cols]
    for i in range(table.num_rows):
        key = tuple(col[i] for col in labels)
        series.setdefault(key, []).append([ts[i], str(vals[i])])
    result = [
        {"metric": dict(zip(label_cols, key)), "values": points}
        for key, points in series.items()
    ]
    return {"status": "success", "data": {"resultType": "matrix", "result": result}}


def _prom_vector_json(table: pa.Table) -> dict:
    label_cols = [c for c in table.column_names if c not in ("ts", "value")]
    ts = [int(v.timestamp()) if hasattr(v, "timestamp") else int(v) // 1000 for v in table["ts"].to_pylist()]
    vals = table["value"].to_pylist()
    labels = [table[c].to_pylist() for c in label_cols]
    result = [
        {
            "metric": dict(zip(label_cols, (col[i] for col in labels))),
            "value": [ts[i], str(vals[i])],
        }
        for i in range(table.num_rows)
    ]
    return {"status": "success", "data": {"resultType": "vector", "result": result}}


class HttpServer:
    def __init__(self, db, addr: str = "127.0.0.1:0", tls=None):
        """`tls`: optional (cert_path, key_path) serving HTTPS (reference
        servers/src/tls.rs TlsOption on the axum router)."""
        host, port = addr.rsplit(":", 1)
        handler = type("BoundHandler", (_Handler,), {"db": db})
        if tls is not None:
            from ..utils.tls import make_server_context

            ctx = make_server_context(*tls)

            class _TlsHTTPServer(ThreadingHTTPServer):
                # wrap PER CONNECTION in the worker thread: wrapping the
                # LISTENING socket runs the handshake inside accept(), so
                # one silent TCP client would block every other connection
                def finish_request(self, request, client_address):
                    request.settimeout(10.0)
                    try:
                        request = ctx.wrap_socket(request, server_side=True)
                    except Exception:  # noqa: BLE001 — bad handshake: drop
                        try:
                            request.close()
                        except OSError:
                            pass
                        return
                    request.settimeout(None)
                    super().finish_request(request, client_address)

            self._httpd = _TlsHTTPServer((host, int(port)), handler)
        else:
            self._httpd = ThreadingHTTPServer((host, int(port)), handler)
        self._thread: threading.Thread | None = None

    @property
    def address(self) -> str:
        host, port = self._httpd.server_address[:2]
        return f"{host}:{port}"

    def start(self, warm: bool = True):
        if warm:
            from ..utils import kernel_executor

            # Bind the jax backend to the long-lived kernel thread BEFORE
            # serving: PJRT first-touch from short-lived handler threads can
            # abort the process (see utils/kernel_executor.py).
            kernel_executor.warm_up()
        self._thread = threading.Thread(target=self._httpd.serve_forever, daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread:
            self._thread.join(timeout=5)
