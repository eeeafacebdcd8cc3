"""Device flight recorder: a bounded, always-on account of every tile
dispatch.

The reference exposes its runtime through system tables and /debug
endpoints (catalog/src/system_schema/information_schema/,
servers /debug/prof/*); this module applies the same glass-box idea to
the TPU hot path itself.  Every tile dispatch (SQL tile path, TQL tile
path, the table-fed mesh path) appends ONE `DispatchRecord` — plan
fingerprint + trace id, strategy, build mode, per-stage milliseconds
(build / upload / compile / dispatch / readback-transfer /
readback-decode), bytes up/down, an HBM budget snapshot and
degrade/coalesce/retry flags — into a drop-oldest ring (the span
exporter's pattern: a process that dispatches faster than anyone reads
keeps the NEWEST records, the ones an operator debugging a live miss
actually wants).

Surfaces (all read-only views over the ring):
  * `information_schema.device_dispatches` (models/information_schema.py)
  * EXPLAIN ANALYZE's device-stage split (query/tpu_exec.py)
  * the `/debug/tile` HTTP endpoint (servers/http.py)

Contract: recording must never fail or slow the recorded query.  Every
`emit` crosses the `recorder.emit` fault point inside a try/except that
swallows ANY failure into `greptime_recorder_errors_total`; with
`recorder.enabled = false` the draft scope is a no-op and the hot path
pays one thread-local read per query.

Ghost (background fused-builder) dispatches are recorded but LABELED
(`ghost = True`) so per-query views (EXPLAIN ANALYZE) exclude the
builder's priming run, exactly like the per-query metric counters do.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from dataclasses import dataclass, field

# Stage keys, in pipeline order.  `build` is host-side consolidation
# (Parquet decode + encode + sort, upload time subtracted), `upload` the
# host->device plane traffic, `compile` program-cache assembly,
# `dispatch` the compiled-program enqueue, and the readback pair the
# device->host fetch split into link transfer vs host decode.  On an
# async dispatch the transfer time INCLUDES waiting out device compute —
# the same honesty note the readback span carries.
STAGES = (
    "build",
    "upload",
    "compile",
    "dispatch",
    "readback_transfer",
    "readback_decode",
)


@dataclass
class DispatchRecord:
    """One tile dispatch (or host serve), as the ring stores it."""

    seq: int = 0
    ts_ms: int = 0
    table: str = ""
    trace_id: str = ""
    plan_fp: str = ""
    strategy: str = ""  # sort | hash | tql | mesh_table | host | batched | result_cache | fused_batch
    build_mode: str = ""  # warm | delta | persisted | cold | fused | cold_serve | host_fast
    mesh_devices: int = 0
    compile_cache: str = ""  # hit | miss | "" (no compile this dispatch)
    ghost: bool = False
    stages_ms: dict = field(default_factory=dict)
    bytes_up: int = 0
    bytes_down: int = 0
    hbm_in_use: int = 0
    hbm_budget: int = 0
    flags: tuple = ()  # retry, degraded, streamed, coalesced, hedged...
    regions: tuple = ()  # ((region_id, mode, build_ms, rows), ...)

    def stage_ms(self, name: str) -> float:
        return float(self.stages_ms.get(name, 0.0))

    def to_dict(self) -> dict:
        return {
            "seq": self.seq,
            "ts_ms": self.ts_ms,
            "table": self.table,
            "trace_id": self.trace_id,
            "plan_fp": self.plan_fp,
            "strategy": self.strategy,
            "build_mode": self.build_mode,
            "mesh_devices": self.mesh_devices,
            "compile_cache": self.compile_cache,
            "ghost": self.ghost,
            "stages_ms": {k: round(v, 3) for k, v in self.stages_ms.items()},
            "bytes_up": self.bytes_up,
            "bytes_down": self.bytes_down,
            "hbm_in_use": self.hbm_in_use,
            "hbm_budget": self.hbm_budget,
            "flags": list(self.flags),
            "regions": [list(r) for r in self.regions],
        }


class FlightRecorder:
    """Drop-oldest ring of DispatchRecords (the SpanExporter pattern:
    deque(maxlen) evicts the oldest in O(1), drops are counted, never
    silent)."""

    def __init__(self, ring_size: int = 4096):
        self._lock = threading.Lock()
        self._ring: deque[DispatchRecord] = deque(maxlen=max(int(ring_size), 1))
        self.enabled = True
        self._seq = 0
        self.dropped = 0

    @property
    def ring_size(self) -> int:
        return self._ring.maxlen or 0

    def configure(self, cfg) -> None:
        """Apply a RecorderConfig (utils/config.py).  Resizing preserves
        the newest records."""
        if cfg is None:
            return
        self.enabled = bool(getattr(cfg, "enabled", True))
        size = max(int(getattr(cfg, "ring_size", 4096)), 1)
        with self._lock:
            if size != self._ring.maxlen:
                self._ring = deque(list(self._ring)[-size:], maxlen=size)

    def emit(self, rec: DispatchRecord) -> bool:
        """Append one record.  NEVER raises — a recorder failure must not
        fail (or slow) the recorded query; failures count in
        `greptime_recorder_errors_total` instead (fault point
        `recorder.emit` proves the contract under test)."""
        if not self.enabled:
            return False
        try:
            from .fault_injection import fire as _fault_fire

            _fault_fire("recorder.emit", table=rec.table)
            with self._lock:
                self._seq += 1
                rec.seq = self._seq
                if len(self._ring) >= (self._ring.maxlen or 1):
                    self.dropped += 1
                    _metric("RECORDER_DROPPED").inc()
                self._ring.append(rec)
            _metric("RECORDER_RECORDS").inc()
            return True
        except Exception:  # noqa: BLE001 — recording is always best-effort
            try:
                _metric("RECORDER_ERRORS").inc()
            except Exception:  # noqa: BLE001 — truly never raise
                pass
            return False

    def snapshot(self) -> list[DispatchRecord]:
        with self._lock:
            return list(self._ring)

    def cursor(self) -> int:
        """Current sequence watermark; pair with `since` for deltas."""
        with self._lock:
            return self._seq

    def since(self, seq: int) -> list[DispatchRecord]:
        """Records emitted after `seq` (oldest first); records that fell
        off the ring in between are simply absent."""
        with self._lock:
            return [r for r in self._ring if r.seq > seq]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.dropped = 0


def _metric(name: str):
    from . import metrics

    return getattr(metrics, name)


RECORDER = FlightRecorder()


# ---- per-query draft scope --------------------------------------------------
# The stages of one dispatch are measured at sites spread across layers
# (cache build facade, upload chokepoint, program cache, dispatch sites,
# readback finalize).  A thread-local draft collects them; the scope
# opened at the executor entry emits ONE record on exit when anything
# marked it emit-worthy (a dispatch ran, or a host/cold serve answered).

_tls = threading.local()


class _Draft:
    __slots__ = ("rec", "emit", "hbm")

    def __init__(self, rec: DispatchRecord, hbm):
        self.rec = rec
        self.emit = False
        self.hbm = hbm  # () -> (in_use, budget) | None


def _draft() -> _Draft | None:
    return getattr(_tls, "draft", None)


@contextlib.contextmanager
def dispatch_scope(table: str, plan_fp: str = "", ghost: bool = False,
                   strategy: str = "", hbm=None):
    """Open a dispatch draft for the current thread.  Nested scopes are
    pass-throughs (the outer scope owns the record; pending flags fold
    into it).  `hbm` is a callable returning (in_use_bytes,
    budget_bytes) sampled at emit time."""
    if not RECORDER.enabled:
        # armed flags must not survive a disabled window and stick to a
        # later unrelated query once recording resumes
        _tls.pending_flags = ()
        yield None
        return
    outer = _draft()
    if outer is not None:
        for f in getattr(_tls, "pending_flags", ()) or ():
            _add_flag(outer.rec, f)
        _tls.pending_flags = ()
        yield None
        return
    rec = DispatchRecord(
        ts_ms=int(time.time() * 1000), table=table, plan_fp=plan_fp,
        strategy=strategy, ghost=ghost,
    )
    d = _Draft(rec, hbm)
    for f in getattr(_tls, "pending_flags", ()) or ():
        _add_flag(rec, f)
    _tls.pending_flags = ()
    _tls.draft = d
    try:
        yield d
    finally:
        _tls.draft = None
        if d.emit:
            try:
                from . import tracing

                rec.trace_id = tracing.current_trace_id() or ""
            except Exception:  # noqa: BLE001 — best-effort context
                pass
            if d.hbm is not None:
                try:
                    in_use, budget = d.hbm()
                    rec.hbm_in_use = int(in_use)
                    rec.hbm_budget = int(budget)
                except Exception:  # noqa: BLE001 — snapshot is best-effort
                    pass
            if RECORDER.emit(rec):
                _tls.last = rec


def _add_flag(rec: DispatchRecord, name: str):
    if name not in rec.flags:
        rec.flags = rec.flags + (name,)


def stage_add(name: str, ms: float):
    """Accumulate `ms` into a stage of the current draft (no-op outside a
    scope).  A dispatch or readback stage marks the draft emit-worthy."""
    d = _draft()
    if d is None:
        return
    d.rec.stages_ms[name] = d.rec.stages_ms.get(name, 0.0) + float(ms)
    if name == "dispatch":
        d.emit = True


def stage_total(name: str) -> float:
    """Current accumulated ms of a stage (0.0 outside a scope) — the
    build facade uses it to subtract nested upload time from build."""
    d = _draft()
    if d is None:
        return 0.0
    return float(d.rec.stages_ms.get(name, 0.0))


def note(**kw):
    """Set record fields (strategy, build_mode, mesh_devices,
    compile_cache) on the current draft."""
    d = _draft()
    if d is None:
        return
    for k, v in kw.items():
        if hasattr(d.rec, k):
            setattr(d.rec, k, v)


def flag(name: str):
    d = _draft()
    if d is not None:
        _add_flag(d.rec, name)


def flag_next(name: str):
    """Arm a flag for the NEXT scope this thread opens — the HBM degrade
    loop re-enters the executor after the current scope closed.  No-op
    while the recorder is disabled: the executor's disabled fast path
    never opens a scope, so an armed flag would otherwise outlive the
    disabled window and stick to the first query after re-enable."""
    if not RECORDER.enabled:
        return
    pending = tuple(getattr(_tls, "pending_flags", ()) or ())
    if name not in pending:
        _tls.pending_flags = pending + (name,)


def mark():
    """Force-emit the current draft (host/cold serves have no dispatch
    stage but are still dispatch-path outcomes worth a record)."""
    d = _draft()
    if d is not None:
        d.emit = True


def region_build(region_id: int, mode: str, ms: float, rows: int = 0):
    """Record one region's build leg (mode = warm|delta|persisted|cold|
    fused) and fold it into the record's aggregate build_mode: any
    cold/fused leg outranks delta, delta outranks persisted, persisted
    outranks warm."""
    d = _draft()
    if d is None:
        return
    d.rec.regions = d.rec.regions + ((int(region_id), mode, round(ms, 3), int(rows)),)
    rank = {"warm": 0, "persisted": 1, "delta": 2, "fused": 3, "cold": 3}
    if rank.get(mode, -1) > rank.get(d.rec.build_mode, -1):
        d.rec.build_mode = mode


def add_bytes(up: int = 0, down: int = 0):
    d = _draft()
    if d is None:
        return
    d.rec.bytes_up += int(up)
    d.rec.bytes_down += int(down)


def emit_adopted(rec: DispatchRecord) -> bool:
    """Emit a record built outside any scope (result-cache hits and
    coalesced waiters finish on paths that never open dispatch_scope)
    and adopt it as this thread's `last_record()` so EXPLAIN ANALYZE
    still sees the per-query outcome.  Returns False (and adopts
    nothing) while the recorder is disabled."""
    if not RECORDER.enabled:
        return False
    if RECORDER.emit(rec):
        _tls.last = rec
        return True
    return False


def emit_fused_batch(table: str, plan_fps, members: int, warmup: bool = False,
                     stages_ms=None, bytes_down: int = 0) -> bool:
    """One record per mega-fused batch tick: strategy `fused_batch`, the
    member count in flags, every member's family fingerprint comma-joined
    in `plan_fp`.  The tick that paid the fused trace (the warm-up) is
    ghost-labeled with a `fuse_warmup` flag so per-query latency views
    separate the one-time compile from steady-state one-invocation
    ticks — same convention as the cold builder's ghost dispatch."""
    if not RECORDER.enabled:
        return False
    flags = ["batched", "fused", f"members={int(members)}"]
    if warmup:
        flags.append("fuse_warmup")
    try:
        from . import tracing
        trace_id = tracing.current_trace_id() or ""
    except Exception:  # noqa: BLE001 — tracing is optional here
        trace_id = ""
    return emit_adopted(DispatchRecord(
        ts_ms=int(time.time() * 1000),
        table=table,
        trace_id=trace_id,
        plan_fp=",".join(plan_fps),
        strategy="fused_batch",
        ghost=bool(warmup),
        flags=tuple(flags),
        stages_ms={k: round(float(v), 3) for k, v in (stages_ms or {}).items()},
        bytes_down=int(bytes_down),
    ))


def last_record() -> DispatchRecord | None:
    """The record most recently emitted from THIS thread's scope — the
    per-query view EXPLAIN ANALYZE reads (ghost records are emitted on
    the builder thread, so they never appear here)."""
    return getattr(_tls, "last", None)


def clear_last():
    _tls.last = None
