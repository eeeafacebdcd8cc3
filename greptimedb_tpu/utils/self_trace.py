"""Self-observability loop: the database tracing itself into itself.

The reference's standalone mode imports its own telemetry so one process
is both the workload and the monitor (common/telemetry +
tracing_context.rs).  This is the zero-egress twin:

  * `statement_trace` wraps every statement's hot path in a root span
    carrying the statement fingerprint and a per-trace tail-sampling
    collector: slow or erroring statements are FORCE-kept with their full
    span tree (and land in greptime_private.slow_queries), fast clean
    ones head-sample at `trace.sample_ratio`;
  * `SelfTraceWriter` drains the exporter ring in batches through the
    normal write path into the same `opentelemetry_traces` table the OTLP
    ingest owns — so a query's trace is immediately queryable through the
    database's OWN Jaeger endpoint (servers/jaeger.py) and plain SQL;
  * `MetricScrapeTask` periodically snapshots the /metrics registry into
    the metric engine, making every `greptime_*` counter range-queryable
    with PromQL `rate()` over our own storage.

All of it is best-effort and off-safe: `trace.self = false` (default)
creates no root spans, starts no threads and restores today's behavior
bit-for-bit; a trace-write failure can never fail or slow the traced
query; and the writer runs under `tracing.suppressed()` so self-trace
writes are never themselves traced (no recursion, proven by test).
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import logging
import re
import threading
import time

from . import metrics, tracing
from .errors import QueryTimeoutError, RetryLaterError
from .fault_injection import fire

_LOG = logging.getLogger("greptimedb_tpu.self_trace")

# Physical metric-engine table backing the /metrics self-scrape; each
# scraped metric becomes a logical table of the same name in `public`.
SELF_METRICS_PHYSICAL_TABLE = "greptime_self_metrics"

# Bound on spans buffered per trace: a runaway statement (thousands of
# region sub-queries) keeps the newest spans and counts the shed.
_MAX_TRACE_SPANS = 8192

_QUOTED = re.compile(r"'(?:[^']|'')*'")
_NUMBER = re.compile(r"\b\d+(?:\.\d+)?\b")
_WS = re.compile(r"\s+")


def statement_fingerprint(text: str) -> str:
    """Stable fingerprint of a statement SHAPE: literals normalized away,
    whitespace collapsed, case-folded — the key that groups 'the same
    query with different parameters' in the slow-query log and on spans
    (reference slow-query fingerprinting does the same)."""
    norm = _QUOTED.sub("?", text or "")
    norm = _NUMBER.sub("?", norm)
    norm = _WS.sub(" ", norm).strip().lower()
    return hashlib.sha1(norm.encode()).hexdigest()[:16]


class TraceCollector:
    """Per-trace span buffer for tail sampling: descendants of a collected
    root (including spans on worker threads parented explicitly) land
    here instead of the exporter; the root's finalizer decides keep/drop
    once the statement's outcome is known.  Spans finishing AFTER the
    decision (abandoned hedges) follow it: kept traces forward them to
    the exporter, dropped traces discard them."""

    __slots__ = ("_spans", "_lock", "_closed", "_kept", "dropped")

    def __init__(self):
        from collections import deque

        # deque(maxlen): O(1) drop-oldest — a runaway statement crossing
        # the cap must not pay a list shift per span under the lock on
        # the fan-out hot path (same rule as the exporter ring)
        self._spans: object = deque(maxlen=_MAX_TRACE_SPANS)
        self._lock = threading.Lock()
        self._closed = False
        self._kept = False
        self.dropped = 0

    def add(self, span):
        with self._lock:
            if self._closed:
                kept = self._kept
            else:
                if len(self._spans) >= _MAX_TRACE_SPANS:
                    self.dropped += 1
                self._spans.append(span)
                return
        if kept:
            tracing.EXPORTER.export(span)

    def close(self, keep: bool) -> list:
        with self._lock:
            self._closed = True
            self._kept = keep
            spans = list(self._spans)
            self._spans.clear()
        if self.dropped:
            metrics.TRACE_SPANS_DROPPED.inc(self.dropped)
        return spans


def _service_of(owner) -> str:
    return (
        "greptimedb_tpu.standalone"
        if hasattr(owner, "storage")
        else "greptimedb_tpu.frontend"
    )


def attach_trace_id(exc: BaseException, trace_id: str):
    """Wire the root trace id into the error surface: RETRY_LATER/timeout
    failures become one Jaeger lookup away.  The id also rides as an
    attribute so protocol layers (HTTP error JSON) can emit it as a
    field instead of parsing the message."""
    exc.trace_id = trace_id
    if (
        isinstance(exc, (RetryLaterError, QueryTimeoutError))
        and exc.args
        and isinstance(exc.args[0], str)
        and "trace_id=" not in exc.args[0]
    ):
        exc.args = (f"{exc.args[0]} [trace_id={trace_id}]",) + exc.args[1:]


@contextlib.contextmanager
def statement_trace(owner, kind: str, query_text: str, database: str = "",
                    is_promql: bool = False):
    """Root span + tail-sampling collector around one statement.

    Off (`trace.self = false`) this context manager is a pass-through —
    no span, no collector, no threads.  A statement nested inside an
    already-collected trace (INSERT ... SELECT, cursors) becomes a child
    span of the ambient trace instead of opening a second collector."""
    cfg = getattr(getattr(owner, "config", None), "trace", None)
    if cfg is None or not cfg.enabled or tracing.suppressed_active():
        yield None
        return
    fp = statement_fingerprint(query_text)
    ambient = tracing.current_span()
    if ambient is not None and ambient.collector is not None:
        with tracing.span(
            f"statement.{kind}", fingerprint=fp, db=database
        ) as s:
            yield s
        return
    ensure_started(owner)
    collector = TraceCollector()
    err: BaseException | None = None
    holder: dict = {}
    try:
        with tracing.span(
            f"statement.{kind}",
            parent=None,
            collector=collector,
            service=_service_of(owner),
            fingerprint=fp,
            db=database,
            protocol=tracing.current_protocol() or "api",
            statement=(query_text or "")[:512],
        ) as root:
            holder["root"] = root
            # registered by trace id so `extract_context` on an RPC's
            # receiving side (same process) joins THIS collector and
            # follows the tail decision — no root-less orphan rows for
            # sampled-out traces
            tracing.register_collector(root.trace_id, collector)
            tracing.set_root_attribute(trace_id=root.trace_id)
            yield root
    except BaseException as exc:
        err = exc
        root = holder.get("root")
        if root is not None:
            attach_trace_id(exc, root.trace_id)
        raise
    finally:
        root = holder.get("root")
        if root is not None:
            _finalize_trace(
                owner, cfg, collector, root, err, query_text, database,
                fp, is_promql,
            )


def _finalize_trace(owner, cfg, collector, root, err, query_text, database,
                    fingerprint, is_promql):
    """Tail decision at root finish: error/slow force-keep, else head
    sample.  Best-effort throughout — a failure here must never replace
    the statement's own outcome."""
    try:
        tracing.unregister_collector(root.trace_id)
        elapsed_ms = root.duration() * 1000.0
        slow = elapsed_ms >= cfg.slow_query_ms
        if err is not None:
            decision = "error"
        elif slow:
            decision = "slow"
        else:
            import random

            decision = (
                "sampled" if random.random() < cfg.sample_ratio else "dropped"
            )
        keep = decision != "dropped"
        spans = collector.close(keep)
        owner.last_trace_id = root.trace_id
        owner.last_trace_kept = keep
        metrics.TRACE_SAMPLED_TOTAL.inc(decision=decision)
        if keep:
            tracing.EXPORTER.export_batch(spans)
        # The slow-queries ROW honors the legacy slow_query section too:
        # its enable switch stays authoritative, and its threshold keeps
        # logging queries the trace threshold alone would miss (an
        # operator's slow_query.threshold_ms=100 must not silently stop
        # logging 100ms-5s queries because tracing was turned on).  The
        # row's threshold column records whichever bound fired.
        legacy = getattr(getattr(owner, "config", None), "slow_query", None)
        row_enabled = legacy is None or legacy.enable
        row_threshold_ms = (
            min(cfg.slow_query_ms, float(legacy.threshold_ms))
            if legacy is not None
            else cfg.slow_query_ms
        )
        recorder = getattr(owner, "event_recorder", None)
        if (
            recorder is not None
            and row_enabled
            and (err is not None or elapsed_ms >= row_threshold_ms)
        ):
            recorder.record_slow_query(
                query_text or "",
                int(elapsed_ms),
                int(row_threshold_ms),
                database,
                is_promql=is_promql,
                trace_id=root.trace_id,
                fingerprint=fingerprint,
                span_tree=span_tree_json(spans),
            )
    except Exception:  # noqa: BLE001 — observability never owns the outcome
        _LOG.warning("trace finalize failed", exc_info=True)


def span_tree_json(spans) -> str:
    """Compact JSON rendering of a trace's span tree (flat, start-ordered;
    parent ids stitch the hierarchy) for the slow_queries row."""
    return json.dumps(
        [
            {
                "name": s.name,
                "span_id": s.span_id,
                "parent_id": s.parent_id,
                "service": s.service,
                "start_ms": int(s.start * 1000),
                "duration_ms": round(s.duration() * 1000.0, 3),
                "status": s.status,
                "attrs": s.attributes,
                "events": [e.get("name") for e in s.events],
            }
            for s in sorted(spans, key=lambda s: s.start)
        ],
        default=str,
    )


def spans_to_table(spans):
    """Finished spans -> one Arrow table in the OTLP trace-table column
    model (servers/otlp.py trace_table_schema) so the rows are
    indistinguishable from OTLP-ingested spans to the Jaeger API."""
    import pyarrow as pa

    from ..servers.otlp import trace_table_schema

    schema = trace_table_schema()
    cols: dict[str, list] = {c.name: [] for c in schema.columns}
    for s in spans:
        start_ns = int(s.start * 1_000_000_000)
        end_ns = int((s.end or s.start) * 1_000_000_000)
        cols["timestamp"].append(start_ns)
        cols["timestamp_end"].append(end_ns)
        cols["duration_nano"].append(max(0, end_ns - start_ns))
        cols["service_name"].append(s.service or "greptimedb_tpu")
        cols["trace_id"].append(s.trace_id)
        cols["span_id"].append(s.span_id)
        cols["parent_span_id"].append(s.parent_id or "")
        cols["span_kind"].append(
            "SPAN_KIND_SERVER" if s.parent_id is None else "SPAN_KIND_INTERNAL"
        )
        cols["span_name"].append(s.name)
        cols["span_status_code"].append(
            "STATUS_CODE_ERROR"
            if s.status == "ERROR"
            else ("STATUS_CODE_OK" if s.status == "OK" else "STATUS_CODE_UNSET")
        )
        cols["span_status_message"].append(s.status_message)
        cols["trace_state"].append("")
        cols["scope_name"].append("greptimedb_tpu.self_trace")
        cols["scope_version"].append("")
        cols["span_attributes"].append(json.dumps(s.attributes, default=str))
        cols["span_events"].append(json.dumps(s.events, default=str))
        cols["span_links"].append("[]")
        cols["resource_attributes"].append(
            json.dumps({"service.name": s.service or "greptimedb_tpu"})
        )
    arrays = {
        c.name: pa.array(cols[c.name], c.data_type.to_arrow())
        for c in schema.columns
    }
    return pa.table(arrays)


def _write_trace_rows(owner, table):
    """Role-adapted write of span rows into `public.opentelemetry_traces`
    through the normal ingest path (standalone: local regions + the
    system-write budget bypass; frontend: Flight fan-out)."""
    from ..servers.otlp import TRACE_TABLE_NAME, ensure_table, trace_table_schema

    if hasattr(owner, "storage"):
        ensure_table(owner, TRACE_TABLE_NAME, trace_table_schema(), "public")
        owner.insert_rows(TRACE_TABLE_NAME, table, database="public", system=True)
    else:
        owner.ensure_system_table(TRACE_TABLE_NAME, trace_table_schema(), "public")
        owner.insert_rows(TRACE_TABLE_NAME, table, database="public")


class SelfTraceWriter:
    """Background drain of the exporter ring into the own trace table.

    Best-effort by contract: a failed batch is dropped and counted
    (`greptime_self_trace_write_failures_total`), never retried into the
    hot path's way, and the whole flush runs under
    `tracing.suppressed()` so exporting traces generates no spans."""

    def __init__(self, owner, cfg):
        self.owner = owner
        self.cfg = cfg
        self._stop = threading.Event()
        self._flush_lock = threading.Lock()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="self-trace-writer"
        )

    def start(self):
        self._thread.start()
        return self

    def _run(self):
        while not self._stop.wait(max(self.cfg.export_interval_s, 0.05)):
            if self.cfg.enabled:
                self.flush()
        if self.cfg.enabled:
            self.flush()  # final best-effort drain on close

    def flush(self) -> int:
        """Drain + write one batch synchronously; returns spans written."""
        with self._flush_lock:
            spans = tracing.EXPORTER.drain()
            if not spans:
                return 0
            with tracing.suppressed():
                try:
                    fire("trace.self_write", spans=len(spans))
                    _write_trace_rows(self.owner, spans_to_table(spans))
                except Exception:  # noqa: BLE001 — best-effort by contract
                    metrics.SELF_TRACE_WRITE_FAILURES.inc()
                    _LOG.debug(
                        "self-trace write failed; dropping %d spans",
                        len(spans), exc_info=True,
                    )
                    return 0
            metrics.SELF_TRACE_ROWS.inc(len(spans))
            return len(spans)

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5.0)


def spans_to_otlp(spans, service: str = "") -> bytes:
    """Finished `tracing.Span`s -> one OTLP ExportTraceServiceRequest
    (protobuf bytes) — the wire twin of `spans_to_table`, for roles with
    no local writer to drain into.  `service` overrides the resource
    service.name (a bare datanode's spans default to the standalone
    service label, which would misattribute them)."""
    from ..servers.otlp import OtlpSpan, encode_traces_request

    if not service:
        service = (spans[0].service if spans else "") or "greptimedb_tpu"
    out = []
    for s in spans:
        out.append(OtlpSpan(
            trace_id=s.trace_id,
            span_id=s.span_id,
            parent_span_id=s.parent_id or "",
            name=s.name,
            kind=2 if s.parent_id is None else 1,  # SERVER / INTERNAL
            start_unix_nano=int(s.start * 1_000_000_000),
            end_unix_nano=int((s.end or s.start) * 1_000_000_000),
            attrs={k: str(v) for k, v in s.attributes.items()},
            events=[
                {
                    "time_unix_nano": int(e.get("ts", 0) * 1_000_000_000),
                    "name": e.get("name", ""),
                    "attrs": {k: str(v) for k, v in e.get("attrs", {}).items()},
                }
                for e in s.events
            ],
            status_code=2 if s.status == "ERROR" else (1 if s.status == "OK" else 0),
            status_message=s.status_message,
        ))
    return encode_traces_request(
        {"service.name": service}, out,
        scope_name="greptimedb_tpu.self_trace",
    )


class OtlpExportTask:
    """OTLP/HTTP self-export for roles with NO writer path (a bare
    datanode in a multi-process cluster has regions but no SQL frontend):
    drain the exporter ring and POST protobuf trace batches to
    `trace.otlp_endpoint` — normally a frontend/standalone's own
    `/v1/otlp/v1/traces`, closing the loop so datanode spans land in the
    same `opentelemetry_traces` table as everyone else's.

    Best-effort like every self-observability path: a failed batch is
    dropped and counted, never retried into the hot path's way."""

    def __init__(self, endpoint: str, cfg=None, service: str = "",
                 interval_s: float | None = None):
        from ..remote.wire import parse_endpoints

        self.host, self.port = parse_endpoints(endpoint)[0]
        self.service = service or "greptimedb_tpu.datanode"
        self.interval_s = (
            interval_s if interval_s is not None
            else getattr(cfg, "export_interval_s", 1.0)
        )
        self._stop = threading.Event()
        self._flush_lock = threading.Lock()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="otlp-self-export"
        )

    def start(self):
        self._thread.start()
        return self

    def _run(self):
        while not self._stop.wait(max(self.interval_s, 0.05)):
            self.flush()
        self.flush()  # final best-effort drain on close

    def flush(self) -> int:
        """Drain + POST one batch synchronously; returns spans shipped
        (0 on failure — the batch is dropped and counted)."""
        with self._flush_lock:
            spans = tracing.EXPORTER.drain()
            if not spans:
                return 0
            body = spans_to_otlp(spans, service=self.service)
            try:
                import http.client

                conn = http.client.HTTPConnection(
                    self.host, self.port, timeout=5.0
                )
                try:
                    conn.request(
                        "POST", "/v1/otlp/v1/traces", body=body,
                        headers={"Content-Type": "application/x-protobuf"},
                    )
                    resp = conn.getresponse()
                    resp.read()
                    if resp.status >= 400:
                        raise OSError(f"otlp export -> {resp.status}")
                finally:
                    conn.close()
            except Exception:  # noqa: BLE001 — best-effort by contract
                metrics.OTLP_SELF_EXPORT_FAILURES.inc()
                _LOG.debug(
                    "otlp self-export failed; dropping %d spans",
                    len(spans), exc_info=True,
                )
                return 0
            metrics.OTLP_SELF_EXPORT_SPANS.inc(len(spans))
            return len(spans)

    def stop(self):
        self._stop.set()
        if self._thread.ident is not None:
            self._thread.join(timeout=5.0)


class MetricScrapeTask:
    """Periodic snapshot of the /metrics registry into the metric engine:
    counters/gauges verbatim, histograms expanded into Prometheus
    `_bucket`/`_sum`/`_count` series — so `rate(greptime_mito_flush_total[5m])`
    runs over OUR storage instead of an external Prometheus."""

    def __init__(self, db, cfg):
        self.db = db
        self.cfg = cfg
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="metric-self-scrape"
        )

    def start(self):
        self._thread.start()
        return self

    def _run(self):
        while not self._stop.wait(max(self.cfg.scrape_interval_s, 0.05)):
            if self.cfg.enabled and self.cfg.scrape_interval_s > 0:
                self.run_once()

    def run_once(self) -> int:
        try:
            snap = metrics.REGISTRY.snapshot()
            now_ms = int(time.time() * 1000)
            rows = {
                name: [(labels, now_ms, value) for labels, value in entries]
                for name, _kind, entries in snap
            }
            with tracing.suppressed():
                n = self.db.metric.write_series_rows(
                    rows, SELF_METRICS_PHYSICAL_TABLE, "public"
                )
            metrics.SELF_SCRAPE_ROWS.inc(n)
            metrics.SELF_SCRAPE_RUNS.inc()
            return n
        except Exception:  # noqa: BLE001 — the scrape never owns the server
            _LOG.debug("metric self-scrape failed", exc_info=True)
            return 0

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5.0)


_START_LOCK = threading.Lock()


def ensure_started(owner):
    """Idempotently start the owner's self-trace writer (and, standalone
    only, the metric scrape).  Called lazily from the first traced
    statement so tests and operators can flip `trace.self` on a live
    instance."""
    if getattr(owner, "_self_trace_writer", None) is not None:
        return owner._self_trace_writer
    with _START_LOCK:
        if getattr(owner, "_self_trace_writer", None) is None:
            cfg = owner.config.trace
            owner._self_trace_writer = SelfTraceWriter(owner, cfg).start()
            if cfg.scrape_interval_s > 0 and getattr(owner, "metric", None) is not None:
                owner._self_scrape_task = MetricScrapeTask(owner, cfg).start()
    return owner._self_trace_writer


def stop(owner):
    """Stop any self-observability threads the owner started."""
    writer = getattr(owner, "_self_trace_writer", None)
    if writer is not None:
        writer.stop()
        owner._self_trace_writer = None
    scrape = getattr(owner, "_self_scrape_task", None)
    if scrape is not None:
        scrape.stop()
        owner._self_scrape_task = None
