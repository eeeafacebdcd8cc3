"""Lightweight span tracing with W3C-style context propagation.

The reference propagates tracing context across RPC boundaries in request
headers (reference src/common/telemetry/src/tracing_context.rs) and
instruments hot entry points.  We provide the same surface: spans with
trace/span ids, a contextvar-based current span, `traceparent` encode/decode
for cross-process propagation, and an in-memory exporter.

The exporter is a RING buffer (drop-oldest): a process that traces faster
than its `SelfTraceWriter` drains keeps the NEWEST spans — the ones an
operator debugging a live incident actually wants — and counts what it
sheds in `greptime_trace_spans_dropped_total` instead of silently pinning
the oldest 4096 spans forever.

Tail sampling rides a per-trace `TraceCollector`: the root span of a
self-traced statement carries a collector, every descendant (including
spans created on worker threads with an explicit `parent=`) buffers into
it, and the root's finalizer decides keep-or-drop AFTER the outcome is
known — slow/erroring statements are force-kept, fast ones head-sample
(utils/self_trace.py owns the policy; this module only carries spans).
Spans with no collector in scope export straight to the ring, exactly the
pre-collector behavior.

Two layers of one primitive.  `stage(name)` is the MEASUREMENT: one
monotonic clock pair, a `jax.profiler.TraceAnnotation` held for the stage's
life (inert outside a profiler session; inside one the stage sits on the
host plane, on the device trace's clock) and the stage's SELF time added to
its counter in `metrics.STAGE_SELF_S` (duration minus what its counted
descendants covered; a name with no counter is transparent).  `span(name)`
is a stage plus the trace identity: ids, parent, collector, export.

A counter holds seconds on the THREAD that ran the stage.  The stack of open
stages crosses a thread hop only where the context is copied and the caller
blocks (`kernel_executor.run`); a pool whose tasks run side by side (the
flush encode pool, the region workers) gets no copy, so its stages are roots
on their own threads: concurrent children would make a parent's self time
negative.
"""

from __future__ import annotations

import contextlib
import contextvars
import random
import threading
import time
from collections import deque
from dataclasses import dataclass, field

from . import metrics

# Span/trace ids need uniqueness, not unpredictability: a process-local
# PRNG (seeded from the OS once) is ~50x cheaper than secrets.token_hex's
# per-call urandom read on this hot path.
_ids = random.Random()
_ids_lock = threading.Lock()


def _new_id(nbytes: int) -> str:
    with _ids_lock:
        return f"{_ids.getrandbits(nbytes * 8):0{nbytes * 2}x}"

# Span stage names observed in this process (the CI taxonomy gate in
# tests/conftest.py checks dotted names against the README contract so
# instrumentation cannot silently drift from the documented taxonomy).
SEEN_SPAN_NAMES: set[str] = set()

_HEX = set("0123456789abcdef")


@dataclass
class Span:
    name: str
    trace_id: str
    span_id: str
    parent_id: str | None
    start: float = field(default_factory=time.time)
    end: float | None = None
    attributes: dict = field(default_factory=dict)
    events: list = field(default_factory=list)
    status: str = ""  # "" (unset) | "OK" | "ERROR"
    status_message: str = ""
    service: str = ""
    collector: object | None = field(default=None, repr=False)
    # the stage that measured this span (None for a hand-made Span)
    stage: object | None = field(default=None, repr=False)

    def duration(self) -> float:
        """Seconds on the monotonic clock; `start`/`end` are wall time for
        the OTLP row (`end` = `start` + this)."""
        if self.stage is not None:
            return self.stage.elapsed()
        return (self.end or time.time()) - self.start

    def add_event(self, name: str, **attrs):
        self.events.append({"name": name, "ts": time.time(), "attrs": attrs})

    def record_exception(self, exc: BaseException):
        """Mark this span failed with the exception as status + event
        (reference tracing_context records errors the same way): a span
        that unwinds through a raise must not look like a success."""
        self.status = "ERROR"
        self.status_message = f"{type(exc).__name__}: {exc}"
        self.add_event(
            "exception",
            type=type(exc).__name__,
            message=str(exc),
        )


class SpanExporter:
    """In-memory ring-buffer exporter; `SelfTraceWriter` drains it into the
    database's own trace table when self-tracing is on."""

    # drops accumulate locally and publish to the metric in batches of
    # this size (plus a flush at every drain) — per-drop Counter.inc on a
    # full ring measurably taxed the span hot path
    _PUBLISH_EVERY = 64

    def __init__(self, capacity: int = 4096):
        # deque(maxlen) evicts the oldest in O(1) — a full ring must stay
        # cheap, because with self-tracing off nothing ever drains it and
        # EVERY span pays the steady-state export cost
        self._spans: deque[Span] = deque(maxlen=capacity)
        self._cap = capacity
        self._lock = threading.Lock()
        self.dropped = 0  # drops since the last drain
        self._unpublished = 0

    def _note_drop_locked(self) -> int:
        """Returns a batch of drops to publish outside the lock, or 0."""
        self.dropped += 1
        self._unpublished += 1
        if self._unpublished >= self._PUBLISH_EVERY:
            out, self._unpublished = self._unpublished, 0
            return out
        return 0

    def export(self, span: Span):
        publish = 0
        with self._lock:
            if len(self._spans) >= self._cap:
                publish = self._note_drop_locked()
            self._spans.append(span)
        if publish:
            _publish_drops(publish)

    def export_batch(self, spans: list[Span]):
        publish = 0
        with self._lock:
            for s in spans:
                if len(self._spans) >= self._cap:
                    publish += self._note_drop_locked()
                self._spans.append(s)
        if publish:
            _publish_drops(publish)

    def spans(self) -> list[Span]:
        with self._lock:
            return list(self._spans)

    def drain(self) -> list[Span]:
        """Atomically take every buffered span (the writer's batch), and
        flush any unpublished drop count to the metric."""
        with self._lock:
            out = list(self._spans)
            self._spans.clear()
            self.dropped = 0
            publish, self._unpublished = self._unpublished, 0
        if publish:
            _publish_drops(publish)
        return out

    def clear(self):
        with self._lock:
            self._spans.clear()


def _publish_drops(n: int):
    from . import metrics

    metrics.TRACE_SPANS_DROPPED.inc(n)


EXPORTER = SpanExporter()

# Open tail-sampling collectors by trace id: `extract_context` (the
# receiving side of an RPC) looks its caller's trace up here, so in
# one-process clusters the datanode-side spans JOIN the statement's
# collector and follow its keep/drop fate instead of bypassing tail
# sampling into the ring as root-less orphans.  Multi-process receivers
# miss the lookup and keep the export-direct behavior.
_collectors: dict[str, object] = {}
_collectors_lock = threading.Lock()


def register_collector(trace_id: str, collector):
    with _collectors_lock:
        _collectors[trace_id] = collector


def unregister_collector(trace_id: str):
    with _collectors_lock:
        _collectors.pop(trace_id, None)

_current: contextvars.ContextVar[Span | None] = contextvars.ContextVar("span", default=None)
# Reentrancy guard: the SelfTraceWriter's own writes (and the metric
# self-scrape) run with tracing suppressed, so exporting traces can never
# generate new spans — no self-feeding loop, by construction.
_suppress: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "span_suppress", default=False
)
# Wire-protocol tag for root statement spans ("http" | "mysql" | "postgres"
# | ...): protocol servers set it around dispatch; the root span reads it.
_protocol: contextvars.ContextVar[str] = contextvars.ContextVar(
    "span_protocol", default=""
)
# Default service name for spans created without an explicit parent chain;
# roles override per-context (frontend statements, datanode RPC handlers).
_service: contextvars.ContextVar[str] = contextvars.ContextVar(
    "span_service", default="greptimedb_tpu.standalone"
)

_UNSET = object()


def current_span() -> Span | None:
    return _current.get()


def current_trace_id() -> str | None:
    s = _current.get()
    return s.trace_id if s is not None else None


def active_collector():
    s = _current.get()
    return s.collector if s is not None else None


def suppressed_active() -> bool:
    return _suppress.get()


@contextlib.contextmanager
def suppressed():
    """Scope in which `span()` is a no-op (nothing recorded anywhere)."""
    token = _suppress.set(True)
    try:
        yield
    finally:
        _suppress.reset(token)


@contextlib.contextmanager
def protocol_scope(name: str):
    """Tag statements dispatched under this scope with their wire protocol."""
    token = _protocol.set(name)
    try:
        yield
    finally:
        _protocol.reset(token)


def current_protocol() -> str:
    return _protocol.get()


@contextlib.contextmanager
def service_scope(name: str):
    """Default service.name for spans opened under this scope."""
    token = _service.set(name)
    try:
        yield
    finally:
        _service.reset(token)


class _NoopSpan(Span):
    """Returned under `suppressed()`: callers can set attributes/events
    freely, nothing is recorded."""


def _noop() -> _NoopSpan:
    return _NoopSpan(name="", trace_id="", span_id="", parent_id=None)


# The innermost open stage of this context: the frame self time is charged
# against.  `kernel_executor.run` copies the context, so the stack crosses
# the hop to the `gt-kernel` thread as `_current` does (the frame is the
# same object on both sides, and the caller blocks while the callee adds).
_frame: contextvars.ContextVar["stage | None"] = contextvars.ContextVar(
    "stage_frame", default=None
)
# Scope in which stages move no counter: the background fused builder's
# priming run (the convention `TPU_DEVICE_DISPATCHES` keeps for ghosts).
_muted: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "stage_muted", default=False
)
_annotation = None  # jax.profiler.TraceAnnotation, imported at first use


@contextlib.contextmanager
def counters_muted():
    """Scope in which `stage()` still measures and annotates but adds to
    no counter."""
    token = _muted.set(True)
    try:
        yield
    finally:
        _muted.reset(token)


def counting() -> bool:
    """Whether a stage closed here would move its counter: what a site that
    adds a root's inclusive seconds by hand asks (`WRITE_BATCH_S`: the
    self-trace writer inserts under `suppressed()`), so that the root and
    its parts move together."""
    return not _suppress.get() and not _muted.get()


def _plain(attrs: dict) -> dict:
    """What an annotation can carry: the profiler writes `name#k=v,k=v#`,
    so only numbers and short strings free of its delimiters (a statement's
    text is neither).  Outside a profiler session nothing is carried."""
    if not attrs or not _annotation.is_enabled():
        return {}
    return {
        k: v for k, v in attrs.items()
        if isinstance(v, (bool, int, float))
        or (isinstance(v, str) and len(v) <= 64 and not set(v) & {"#", ",", "="})
    }


class stage:
    """One measured stage: `with stage("tile.decode") as st: ...`.

    Reads `time.perf_counter()` at entry and exit (`duration_s` afterwards,
    `elapsed()` at any time), holds a `jax.profiler.TraceAnnotation` of the
    same name (inert outside a profiler session) and, at exit, adds its
    SELF time to its counter in `metrics.STAGE_SELF_S`: `duration_s` minus
    `child_s`, the seconds its counted descendants covered.  It then adds
    its whole duration to the enclosing stage's `child_s`; a stage whose
    name has no counter is transparent and passes its own `child_s` up, so
    its time stays with the nearest counted ancestor.  Under `suppressed()`
    only the clock is read."""

    __slots__ = (
        "name", "attrs", "duration_s", "child_s", "_t0", "_parent", "_token",
        "_ann",
    )

    def __init__(self, name: str, **attrs):
        self.name, self.attrs = name, attrs
        self.duration_s: float | None = None
        self.child_s = 0.0
        self._ann = None

    def __enter__(self):
        if not _suppress.get():
            global _annotation
            if _annotation is None:
                from jax.profiler import TraceAnnotation

                _annotation = TraceAnnotation
            SEEN_SPAN_NAMES.add(self.name)
            self._parent = _frame.get()
            self._token = _frame.set(self)
            self._ann = _annotation(self.name, **_plain(self.attrs))
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.duration_s = d = time.perf_counter() - self._t0
        if self._ann is None:
            return False
        self._ann.__exit__(*exc)
        _frame.reset(self._token)
        counter = None if _muted.get() else metrics.STAGE_SELF_S.get(self.name)
        if counter is not None:
            counter.inc(d - self.child_s)
        if self._parent is not None:
            self._parent.child_s += d if counter is not None else self.child_s
        return False

    def elapsed(self) -> float:
        if self.duration_s is not None:
            return self.duration_s
        return time.perf_counter() - self._t0

    def set(self, **attrs):
        """Attributes learned inside the stage (the annotation takes them
        until it closes)."""
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**_plain(attrs))


def set_root_attribute(**attrs):
    """Attributes for the outermost open stage of this context: a traced
    statement hands its `trace_id` to the `http.request` it runs in."""
    f = _frame.get()
    while f is not None and f._parent is not None:
        f = f._parent
    if f is not None:
        f.set(**attrs)


@contextlib.contextmanager
def _open_span(s: Span):
    """Run `s` as the current span inside its stage, then record it."""
    s.stage = st = stage(s.name, **s.attributes)
    token = _current.set(s)
    st.__enter__()
    try:
        yield s
    except BaseException as exc:
        s.record_exception(exc)
        raise
    finally:
        st.__exit__(None, None, None)
        s.end = s.start + st.duration_s
        _current.reset(token)
        _record(s)


@contextlib.contextmanager
def span(name: str, parent=_UNSET, service: str | None = None, collector=_UNSET, **attributes):
    """One traced stage: `stage(name)` plus ids, parent, collector, export.

    `parent` defaults to the ambient contextvar span; pass it explicitly to
    parent a span created on a worker thread (thread pools do not inherit
    contextvars), which also carries the trace's collector across the hop.
    `collector`, when given, attaches a tail-sampling buffer at this span
    (the statement root); descendants inherit it through the parent chain.
    An exception unwinding through the span is recorded as status + event
    before re-raising.
    """
    if _suppress.get():
        yield _noop()
        return
    p = _current.get() if parent is _UNSET else parent
    inherited = p.collector if p is not None else None
    s = Span(
        name=name,
        trace_id=p.trace_id if p else _new_id(16),
        span_id=_new_id(8),
        parent_id=p.span_id if p else None,
        attributes=attributes,
        service=service or (p.service if p and p.service else _service.get()),
        collector=inherited if collector is _UNSET else collector,
    )
    with _open_span(s):
        yield s


def _record(s: Span):
    if s.collector is not None:
        s.collector.add(s)
    else:
        EXPORTER.export(s)


def add_event(name: str, **attrs):
    """Attach an event to the current span, if any (retry attempts, hedge
    wins, breaker sheds, HBM degrade rounds — point-in-time facts that are
    not stages of their own)."""
    s = _current.get()
    if s is not None:
        s.add_event(name, **attrs)


def set_attribute(key: str, value):
    s = _current.get()
    if s is not None:
        s.attributes[key] = value


def inject_context() -> dict[str, str]:
    """Produce a `traceparent` header for the current span (W3C format)."""
    s = _current.get()
    if s is None or isinstance(s, _NoopSpan):
        return {}
    return {"traceparent": f"00-{s.trace_id}-{s.span_id}-01"}


def _parse_traceparent(tp: str) -> tuple[str, str] | None:
    """(trace_id, parent_span_id) from a traceparent header, or None when
    the header is malformed.  Per W3C: a version field that is not two hex
    chars, or the reserved 'ff', invalidates the header — previously only
    part LENGTHS were checked, so 'zz-<32 junk chars>-...' silently seeded
    a span with a garbage trace id."""
    parts = tp.split("-")
    if len(parts) < 4:
        return None
    version, trace_id, span_id = parts[0], parts[1], parts[2]
    if len(version) != 2 or not set(version.lower()) <= _HEX:
        return None
    if version.lower() == "ff":
        return None  # reserved/invalid per the spec
    if len(trace_id) != 32 or not set(trace_id.lower()) <= _HEX:
        return None
    if len(span_id) != 16 or not set(span_id.lower()) <= _HEX:
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id


@contextlib.contextmanager
def extract_context(headers: dict[str, str], name: str = "remote", service: str | None = None, **attributes):
    """Continue a trace from a `traceparent` header on the receiving side.
    A missing or malformed header degrades to a fresh root span — the RPC
    is still traced, just not stitched into the caller's trace."""
    if _suppress.get():
        yield _noop()
        return
    parsed = _parse_traceparent(headers.get("traceparent", ""))
    if parsed is None:
        with span(name, service=service, **attributes) as s:
            yield s
        return
    trace_id, parent_span_id = parsed
    s = Span(
        name=name,
        trace_id=trace_id,
        span_id=_new_id(8),
        parent_id=parent_span_id,
        attributes=attributes,
        service=service or _service.get(),
        collector=_collectors.get(trace_id),
    )
    with _open_span(s):
        yield s
