"""Cross-query device batching + windowed result cache.

Every dispatch pays a fixed host cost (plan, enqueue, a device->host
fetch) that does not shrink with the warm compute, so at dashboard-fleet
QPS the number of dispatches and fetches, not the kernel, sets the
ceiling.  Admission coalescing (`
admission.coalesce`) already merges bit-identical concurrent plans onto
one dispatch; this module extends the same contract to DISTINCT plans:

  * `QueryBatcher` — warm queries against the same table that arrive
    within `batch.window_ms` of each other form a batch.  The first
    arrival is the LEADER: it waits out the window, then executes every
    member's dispatch back-to-back on the device stream in *deferred-
    fetch* mode (the executor returns a `PendingFetch` instead of
    fetching), flattens every member's packed output leaves and brings
    them home in ONE `jax.device_get` — one fetch amortized
    across the whole batch — then runs each member's decode
    continuation host-side.  Members share the READBACK, never each
    other's math: each ran its own compiled program over its own plan,
    so results are bit-identical to solo runs by construction.  Any
    member that cannot be packed (dispatch error, decode verdict such
    as a hash-slot overflow, an injected `batch.pack` fault) degrades
    to its own solo dispatch on its own thread — batching can delay a
    query, never wrong it.  `batch.window_ms = 0` (the default)
    disables the layer entirely: today's path bit-for-bit.

  * `WindowedResultCache` — finished executor results keyed on
    (literal-insensitive plan fingerprint, filter-literal digest,
    bucket-aligned time window, per-region manifest version + WAL tail
    id).  A sliding dashboard that re-asks for the same aligned window
    re-serves with ZERO dispatch; any write moves the WAL tail and any
    flush/compaction bumps the manifest version, so stale entries are
    simply never reachable — the key IS the invalidation rule.  The
    snapshot versions are read BEFORE the query executes, so a write
    landing mid-query can only strand an unreachable old-versions
    entry, never publish a newer result under an older snapshot key.
    LRU-bounded by `batch.result_cache_mb` (0 = off).

  * **Mega-program fusion** (`batch.fuse_programs`, default ON) — the
    leader goes one step further than the shared readback: each member's
    dispatch is CAPTURED at the executor's dispatch site (lowered plan,
    device-resident sources, dynamic traced inputs, decode continuation)
    instead of executed, and the whole tick compiles into ONE fused XLA
    program that replays every member's fold op-for-op as independent
    branches over the shared resident planes — one XLA invocation per
    batch tick, not per member, so the chip rather than the dispatch
    loop sets the ceiling.  The fused program is keyed on the multiset
    of the members' literal-insensitive program keys (plan structure +
    shape buckets; literals, grids and time bounds ride as dynamic
    traced inputs, PR 13-style), so a dashboard fleet sliding its
    windows re-hits the fused compile cache with zero recompiles.  Any
    capture, trace, compile, or dispatch failure — including a
    multi-member HBM exhaustion, which must retry at per-member
    granularity to shrink — degrades to the per-member packed path
    above (`greptime_batch_fuse_degraded_total`); a member the capture
    cannot reach (host/cold/streamed serves) is answered by the
    per-member path in the same tick (partial fusion).

Fault points: `batch.pack` fires immediately before the mega-readback;
`batch.result_cache` fires on every cache get/put; `batch.fuse` fires
before each member's capture (op="capture") and before the fused
dispatch (op="fuse").  All degrade, never corrupt: a pack failure solos
every member, a cache failure is a miss, a fuse failure re-runs the
tick through the per-member path.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import OrderedDict

import jax

from ..utils import device_health, flight_recorder, metrics, tracing
from ..utils.deadline import check_deadline, current_deadline
from ..utils.fault_injection import fire as _fault_fire

# ---- deferred device->host fetches -----------------------------------------
# Thread-local flag the batch leader raises around each member's dispatch:
# the executor's _finalize sees it and returns a PendingFetch (dispatched,
# unfetched) instead of paying a per-member device_get.

_DEFER = threading.local()


def defer_active() -> bool:
    return getattr(_DEFER, "active", False)


@contextlib.contextmanager
def defer_fetch():
    prev = getattr(_DEFER, "active", False)
    _DEFER.active = True
    try:
        yield
    finally:
        _DEFER.active = prev


@contextlib.contextmanager
def defer_suppressed():
    """Force eager fetches inside a deferred scope.  The region-streamed
    path releases each region's planes right after folding its partials,
    so its intermediate fetches must complete while the planes are
    guaranteed alive — it never defers."""
    prev = getattr(_DEFER, "active", False)
    _DEFER.active = False
    try:
        yield
    finally:
        _DEFER.active = prev


# ---- mega-fusion dispatch capture -------------------------------------------
# Thread-local flag the batch leader raises around each member's execute:
# the executor's dispatch site sees it and returns a CapturedDispatch
# (everything the fused program needs, nothing executed) instead of
# dispatching.  Serve paths that answer BEFORE the dispatch site (host
# fast path, cold consolidation, streamed spill) return their final
# result straight through the capture — those members simply aren't
# fusable this tick and the per-member path owns them.

_CAPTURE = threading.local()


def capture_active() -> bool:
    return getattr(_CAPTURE, "active", False)


@contextlib.contextmanager
def capture_dispatch():
    prev = getattr(_CAPTURE, "active", False)
    _CAPTURE.active = True
    try:
        yield
    finally:
        _CAPTURE.active = prev


class CapturedDispatch:
    """One member's dispatch-ready state, captured instead of executed.

    `key` is the member's `_tile_program` cache key (plan, nullable
    count-cols, finalize spec) — literal-insensitive by the dynamic-spec
    contract, so the multiset of member keys IS the fused program's
    compile key.  `sources`/`dyn` are the device-resident source planes
    and the dynamic traced inputs for this specific tick.  `finish` is
    the decode continuation (host-fetched leaves in, decoded pa.Table or
    a rerun-verdict None out — same contract as `PendingFetch.finish`).
    Only the FIRST attempts-ladder rung is captured: a rerun verdict in
    the fused leaves degrades the member to a solo run that walks the
    full ladder."""

    __slots__ = ("key", "sources", "dyn", "finish")

    def __init__(self, key, sources, dyn, finish):
        self.key = key
        self.sources = sources
        self.dyn = dyn
        self.finish = finish


class PendingFetch:
    """One query's dispatched-but-unfetched packed device result: the
    output leaves still on device plus the decode continuation.  `finish`
    takes the host-fetched leaves (same order as `leaves`) and returns
    the decoded pa.Table — or None for a rerun verdict (hash-slot
    overflow / limb quantization bound), which the batcher turns into a
    solo degrade."""

    __slots__ = ("leaves", "finish")

    def __init__(self, leaves, finish):
        self.leaves = list(leaves)
        self.finish = finish


# ---- windowed result cache --------------------------------------------------


class WindowedResultCache:
    """LRU byte-bounded memo of finished executor results.

    Values are (pa.Table, post_done) — both immutable, so a hit hands
    back the stored objects directly.  `post_done` rides along because a
    device-finalized result already consumed some post-ops; the host
    replay must skip exactly those on a hit too, or the hit would
    double-apply LIMIT/HAVING."""

    # per-entry bookkeeping floor: a tiny table still costs key storage
    _ENTRY_OVERHEAD = 1 << 10

    def __init__(self, budget_bytes: int):
        self.budget = int(budget_bytes)
        self._lock = threading.Lock()
        self._entries: OrderedDict = OrderedDict()  # key -> (table, post_done, nbytes)
        self._used = 0

    @staticmethod
    def key_for(executor, lowering, schema, ctx):
        """Cache key for one query, or None when not fingerprintable.

        (plan_fp, literals, window, versions): `plan_fp` is the literal-
        insensitive family fingerprint (filter STRUCTURE, bucket
        geometry); `literals` digests the filter values it elides;
        `window` is the effective scan time range, expressed in bucket
        units when both bounds sit exactly on the query's bucket grid
        (the canonical form a refreshing dashboard re-hits) and verbatim
        otherwise — both forms are exact, never merging windows that
        could select different rows; `versions` pins the data snapshot
        exactly like coalescing's `_family_key` does."""
        plan_fp = executor._plan_fp(lowering, ctx)
        if plan_fp is None:
            return None
        try:
            versions = tuple(
                (
                    r.region_id,
                    r.manifest_mgr.manifest.manifest_version,
                    r.wal.last_entry_id,
                )
                for r in ctx.regions
            )
            literals = repr(tuple(lowering.scan.filters))
            window = WindowedResultCache._window_key(lowering, schema)
        except Exception:  # noqa: BLE001 — fingerprinting is best-effort
            return None
        return (plan_fp, literals, window, versions)

    @staticmethod
    def _window_key(lowering, schema):
        tr = getattr(lowering.scan, "time_range", None)
        if tr is None:
            return ("full",)
        lo, hi = int(tr[0]), int(tr[1])
        bucket = getattr(lowering, "bucket", None)
        if bucket is not None and lo > -(1 << 61) and hi < (1 << 61):
            try:
                _ts, interval_ms, origin = bucket
                # same ms->native conversion as the plan's bucket geometry
                unit_ns = schema.time_index.data_type.timestamp_unit_ns()
                step = max(int(interval_ms * 1_000_000) // max(unit_ns, 1), 1)
                if (lo - origin) % step == 0 and (hi - origin) % step == 0:
                    # bijective given the plan: interval + origin are
                    # structural and already inside plan_fp
                    return ("aligned", (lo - origin) // step, (hi - origin) // step)
            except Exception:  # noqa: BLE001 — fall back to the verbatim form
                pass
        return ("raw", lo, hi)

    def get(self, key):
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                return None
            self._entries.move_to_end(key)
            return entry[0], entry[1]

    def put(self, key, table, post_done):
        try:
            nbytes = int(table.nbytes) + self._ENTRY_OVERHEAD
        except Exception:  # noqa: BLE001 — unsized results are uncacheable
            return
        if nbytes > self.budget:
            return
        evicted = 0
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._used -= old[2]
            self._entries[key] = (table, frozenset(post_done or ()), nbytes)
            self._used += nbytes
            while self._used > self.budget and self._entries:
                _, dropped = self._entries.popitem(last=False)
                self._used -= dropped[2]
                evicted += 1
        if evicted:
            metrics.QUERY_BATCH_RESULT_CACHE_EVICTIONS_TOTAL.inc(evicted)

    def purge_region(self, region_id: int):
        """Proactive drop of every entry touching the region.  The
        version-carrying key already makes stale entries unreachable;
        purging just returns their bytes to the budget immediately."""
        evicted = 0
        with self._lock:
            for key in list(self._entries):
                versions = key[3]
                if any(v[0] == region_id for v in versions):
                    self._used -= self._entries.pop(key)[2]
                    evicted += 1
        if evicted:
            metrics.QUERY_BATCH_RESULT_CACHE_EVICTIONS_TOTAL.inc(evicted)

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._entries), "bytes": self._used}


# ---- the query batcher ------------------------------------------------------


class _Member:
    __slots__ = (
        "lowering", "schema", "time_bounds", "ctx",
        "event", "result", "post_done", "solo", "served",
    )

    def __init__(self, lowering, schema, time_bounds, ctx):
        self.lowering = lowering
        self.schema = schema
        self.time_bounds = time_bounds
        self.ctx = ctx
        self.event = threading.Event()
        self.result = None
        self.post_done = frozenset()
        self.solo = False  # degrade: owner thread runs its own solo dispatch
        self.served = False  # result/post_done came from the batch


class _Batch:
    __slots__ = ("members", "closed")

    def __init__(self):
        self.members: list[_Member] = []
        self.closed = False


class QueryBatcher:
    """Forms per-table batches of warm queries and runs each batch as
    back-to-back async dispatches sharing ONE packed readback.  The
    executor calls `submit` only for warm, fingerprintable families with
    `batch.window_ms > 0`; everything else takes the existing path."""

    # sanity ceiling on the leader's window sleep, whatever the knob says
    _WINDOW_CAP_S = 0.25

    def __init__(self, executor):
        self._ex = executor
        self._lock = threading.Lock()
        self._open: dict[str, _Batch] = {}  # table_key -> forming batch

    def submit(self, lowering, schema, time_bounds, ctx, adm, bc):
        m = _Member(lowering, schema, time_bounds, ctx)
        key = ctx.table_key
        cap = max(int(getattr(bc, "max_members", 16)), 2)
        with self._lock:
            batch = self._open.get(key)
            if batch is not None and not batch.closed and len(batch.members) < cap:
                batch.members.append(m)
                leader = False
            else:
                batch = _Batch()
                batch.members.append(m)
                self._open[key] = batch
                leader = True
        if leader:
            return self._lead(batch, m, key, adm, bc)
        # joiner: wait for the leader under this query's own deadline
        deadline = current_deadline()
        while not m.event.is_set():
            timeout = None if deadline is None else deadline - time.monotonic()
            if timeout is not None and timeout <= 0:
                check_deadline()
            m.event.wait(timeout if timeout is None else max(timeout, 0.001))
        if m.served:
            m.lowering.post_done = m.post_done
            tracing.add_event("dispatch.batched", table=key)
            flight_recorder.emit_adopted(flight_recorder.DispatchRecord(
                ts_ms=int(time.time() * 1000), table=key,
                trace_id=tracing.current_trace_id() or "",
                plan_fp=self._ex._recorder_fp(m.lowering, m.ctx),
                strategy="batched", flags=("batched",),
            ))
            return m.result
        # degrade: solo dispatch under this thread's own budget
        return self._ex._overload_safe_execute(
            m.lowering, m.schema, m.time_bounds, m.ctx, adm
        )

    def _lead(self, batch, m, key, adm, bc):
        # wait out the window for peers (bounded by the leader's own
        # remaining deadline), close the batch, run it, wake everyone.
        # The ENTIRE body sits under one try/finally: a leader dying in
        # the window sleep or the lock-close step (deadline alarm, async
        # interrupt, wedge-abandon raise) before the old finally was
        # entered used to strand every already-enqueued joiner on an
        # event nobody would ever set — they'd hang until their own
        # deadline instead of soloing immediately.  The finally both
        # closes the batch (so no NEW joiner can board a dead batch) and
        # wakes every peer with the solo-rerun verdict (served=False).
        try:
            window_s = min(float(bc.window_ms) / 1000.0, self._WINDOW_CAP_S)
            deadline = current_deadline()
            if deadline is not None:
                window_s = max(min(window_s, deadline - time.monotonic()), 0.0)
            if window_s > 0:
                time.sleep(window_s)
            with self._lock:
                batch.closed = True
                if self._open.get(key) is batch:
                    del self._open[key]
            try:
                self._run(batch, adm)
            except BaseException:  # noqa: BLE001 — every member degrades solo
                pass
        finally:
            with self._lock:
                batch.closed = True
                if self._open.get(key) is batch:
                    del self._open[key]
            for peer in batch.members:
                if peer is not m:
                    peer.event.set()
        if m.served:
            m.lowering.post_done = m.post_done
            return m.result
        return self._ex._overload_safe_execute(
            m.lowering, m.schema, m.time_bounds, m.ctx, adm
        )

    def _run(self, batch, adm):
        ex = self._ex
        # dedupe bit-identical (plan, snapshot) members: dupes adopt the
        # primary's result, exactly like admission coalescing would
        primaries: list[_Member] = []
        adopt: list[tuple[_Member, _Member]] = []
        by_key: dict = {}
        for m in batch.members:
            fk = ex._family_key(m.lowering, m.ctx)
            if fk is not None and fk in by_key:
                adopt.append((m, by_key[fk]))
                continue
            if fk is not None:
                by_key[fk] = m
            primaries.append(m)
        if len(primaries) == 1:
            # one unique plan: a plain solo dispatch (today's path, no
            # deferred fetch) — dupes below adopt it coalescing-style
            self._run_solo_into(primaries[0], adm)
        else:
            self._run_packed(primaries, adm)
        for dupe, prim in adopt:
            if prim.served:
                dupe.result = prim.result
                dupe.post_done = prim.post_done
                dupe.served = True
            else:
                dupe.solo = True

    def _run_solo_into(self, m: _Member, adm):
        try:
            m.result = self._ex._overload_safe_execute(
                m.lowering, m.schema, m.time_bounds, m.ctx, adm
            )
            m.post_done = m.lowering.post_done
            m.served = True
        except BaseException:  # noqa: BLE001 — owner thread owns the error
            m.solo = True

    def _fusion_enabled(self, bc) -> bool:
        if bc is None or not bool(getattr(bc, "fuse_programs", True)):
            return False
        # the fused trace replays the single-chip fold inline; the mesh
        # path shards planes across datanode devices with host-side
        # device_put hops that cannot ride one trace — it keeps
        # per-member dispatch.  Non-mesh multi-device hosts fuse: the
        # dispatcher colocates the member planes onto one chip first.
        try:
            return self._ex.cache.mesh_devices() == 0
        except Exception:  # noqa: BLE001 — unknowable topology: don't fuse
            return False

    def _run_fused(self, primaries: list[_Member], adm) -> list[_Member]:
        """Capture every member's dispatch, fuse the captured set into
        ONE XLA invocation, decode each member from the fused leaves.
        Returns the members the per-member packed path still owns:
        capture-ineligible members (their capture ran to a final answer
        or an injected `batch.fuse` capture fault marked them unfusable),
        plus EVERY captured member when the fused dispatch itself fails —
        degrade, never wrong."""
        ex = self._ex
        captured: list[tuple[_Member, CapturedDispatch]] = []
        leftover: list[_Member] = []
        for m in primaries:
            try:
                _fault_fire("batch.fuse", op="capture", table=m.ctx.table_key)
            except BaseException:  # noqa: BLE001 — member unfusable this tick
                leftover.append(m)
                continue
            try:
                with capture_dispatch():
                    out = ex._overload_safe_execute(
                        m.lowering, m.schema, m.time_bounds, m.ctx, adm
                    )
            except BaseException:  # noqa: BLE001 — degrade, never propagate
                m.solo = True
                continue
            if isinstance(out, CapturedDispatch):
                captured.append((m, out))
            else:
                # host fast path / cold serve / streamed / inapplicable:
                # the capture ran through to a final answer — the member
                # is already served, nothing to fuse for it
                m.result = out
                m.post_done = m.lowering.post_done
                m.served = True
        if len(captured) < 2:
            # nothing worth fusing: hand the captures back to the
            # per-member path (planes stay warm; relowering is cheap)
            leftover.extend(m for m, _ in captured)
            return leftover
        try:
            _fault_fire("batch.fuse", op="fuse", members=len(captured))
            tables, info = ex._fused_dispatch([cd for _, cd in captured])
        except BaseException:  # noqa: BLE001 — whole-tick degrade
            metrics.QUERY_BATCH_FUSE_DEGRADED_TOTAL.inc()
            leftover.extend(m for m, _ in captured)
            return leftover
        served = 0
        for (m, _cd), table in zip(captured, tables):
            if table is None:
                # rerun verdict (hash overflow / limb bound) or decode
                # failure: the solo rerun walks the full attempts ladder
                m.solo = True
                continue
            m.result = table
            m.post_done = m.lowering.post_done
            m.served = True
            served += 1
        metrics.QUERY_BATCH_DISPATCHES_TOTAL.inc()
        metrics.QUERY_BATCH_MEMBERS_TOTAL.inc(served)
        metrics.QUERY_BATCH_FUSED_DISPATCHES_TOTAL.inc()
        metrics.QUERY_BATCH_FUSE_MEMBERS.observe(float(len(captured)))
        flight_recorder.emit_fused_batch(
            table=captured[0][0].ctx.table_key,
            plan_fps=[
                ex._recorder_fp(m.lowering, m.ctx) for m, _ in captured
            ],
            members=len(captured),
            warmup=bool(info.get("traced")),
            stages_ms=info.get("stages_ms") or {},
            bytes_down=int(info.get("bytes_down") or 0),
        )
        return leftover

    def _run_packed(self, primaries: list[_Member], adm):
        ex = self._ex
        bc = getattr(ex.cache, "batch_config", None)
        if len(primaries) >= 2 and self._fusion_enabled(bc):
            primaries = self._run_fused(primaries, adm)
            if not primaries:
                return
        pendings: list[tuple[_Member, PendingFetch]] = []
        for m in primaries:
            # the member's own dispatch record (opened inside
            # _try_execute on THIS thread) carries the batched flag
            flight_recorder.flag_next("batched")
            try:
                with defer_fetch():
                    out = ex._overload_safe_execute(
                        m.lowering, m.schema, m.time_bounds, m.ctx, adm
                    )
            except BaseException:  # noqa: BLE001 — degrade, never propagate
                m.solo = True
                continue
            if isinstance(out, PendingFetch):
                pendings.append((m, out))
            else:
                # host fast path / inapplicable (None): already final
                m.result = out
                m.post_done = m.lowering.post_done
                m.served = True
        if not pendings:
            return
        try:
            _fault_fire(
                "batch.pack",
                members=len(pendings),
                leaves=sum(len(p.leaves) for _, p in pendings),
            )
            leaves = []
            for _, p in pendings:
                leaves.extend(p.leaves)
            with tracing.span("tile.batch_readback", members=len(pendings)) as rb:
                fetched = device_health.supervised_call(
                    "readback", lambda: jax.device_get(leaves)
                )
            transfer_ms = rb.duration() * 1000.0
        except BaseException:  # noqa: BLE001 — pack failure solos everyone
            for m, _ in pendings:
                m.solo = True
            return
        off = 0
        served = 0
        for m, p in pendings:
            part = fetched[off : off + len(p.leaves)]
            off += len(p.leaves)
            try:
                table = p.finish(part)
            except BaseException:  # noqa: BLE001 — degrade, never propagate
                m.solo = True
                continue
            if table is None:
                # rerun verdict (hash overflow / limb bound): the solo
                # rerun walks the full attempts ladder, exactly as today
                m.solo = True
                continue
            m.result = table
            m.post_done = m.lowering.post_done
            m.served = True
            served += 1
        if len(pendings) >= 2:
            metrics.QUERY_BATCH_DISPATCHES_TOTAL.inc()
            metrics.QUERY_BATCH_MEMBERS_TOTAL.inc(served)
            if flight_recorder.RECORDER.enabled:
                flight_recorder.RECORDER.emit(flight_recorder.DispatchRecord(
                    ts_ms=int(time.time() * 1000),
                    table=pendings[0][0].ctx.table_key,
                    trace_id=tracing.current_trace_id() or "",
                    plan_fp=",".join(
                        ex._recorder_fp(m.lowering, m.ctx) for m, _ in pendings
                    ),
                    strategy="batched", flags=("batched",),
                    stages_ms={"readback_transfer": round(transfer_ms, 3)},
                    bytes_down=int(
                        sum(getattr(a, "nbytes", 0) for a in fetched)
                    ),
                ))
