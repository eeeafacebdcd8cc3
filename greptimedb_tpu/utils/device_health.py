"""Per-device health supervision: bounded device calls, wedge detection,
quarantine + heal.

The failure mode this module closes: a wedged native XLA call holds the
GIL-adjacent runtime hostage and no raised-error ladder (HBM retry, CPU
fallback) ever fires, because nothing is *raised* — the call simply never
returns.  Every blocking device interaction on the query path (upload,
compile+dispatch, readback, memory_stats probe, mesh collective) runs
through `supervised_call`, which executes the call on a dedicated
per-device worker thread under a hard deadline:

    timeout = min(device.call_timeout_s, statement's remaining budget)

A call that neither returns nor raises by the deadline is **abandoned** —
the future is detached and the worker thread written off (the PR 2
`_fanout` abandonment pattern; a wedged native call cannot be cancelled,
only orphaned) — a fresh worker is spawned in its place
(`greptime_device_worker_refills_total` counts the bounded leak), the
device transitions to QUARANTINED, and the caller gets a
`DeviceWedgedError` it can degrade on immediately: the existing ladder
(host consolidation / cold-serve / scan path / CPU fallback) turns the
wedge into bounded added latency, never a failed query.

Per-device state machine:

    HEALTHY --raised device error--> SUSPECT
    SUSPECT --error_threshold consecutive errors--> QUARANTINED
    SUSPECT --success--> HEALTHY
    any     --abandoned (wedged) call--> QUARANTINED
    QUARANTINED --heal prober picks it up--> PROBING
    PROBING --probe_successes consecutive in-deadline ghost calls--> HEALTHY
    PROBING --probe failure/timeout--> QUARANTINED

Quarantine consequences are wired at the call sites: the tile cache drops
device planes (resident state is rebuildable cache, not truth — see
`TileCacheManager.health_sync`), chunk placement and the mesh path shrink
to the surviving device set, and the batcher's members degrade to solo
runs that land on healthy devices or the host path.

`device.supervised = false` restores direct in-thread calls bit-for-bit:
`supervised_call` then IS `fn()` — no worker hop, no timeout, no state.

Fault points (conftest coverage gate): `device.wedge` fires inside the
worker-run callable so a test-controlled callback that blocks on an Event
wedges the worker exactly like stuck native code (releases the GIL, so
the supervising thread still times out); `device.error` fires at the same
spot for raised-error storms that drive the breaker-style SUSPECT →
QUARANTINED path without any wedge.
"""

from __future__ import annotations

import contextlib
import logging
import queue
import threading
import time

from . import flight_recorder, metrics, tracing
from .deadline import check_deadline, current_deadline
from .errors import QueryTimeoutError
from .fault_injection import fire as _fault_fire

HEALTHY = "HEALTHY"
SUSPECT = "SUSPECT"
QUARANTINED = "QUARANTINED"
PROBING = "PROBING"

# gauge encoding for greptime_device_health_state (per device label)
_STATE_CODE = {HEALTHY: 0, SUSPECT: 1, QUARANTINED: 2, PROBING: 3}

_LOG = logging.getLogger("greptimedb_tpu.device_health")

# ---- ambient-scope propagation ---------------------------------------------
# Supervised callables run on a WORKER thread, but callers' thread-local
# execution scopes (tile_cache's flow-maintenance and fused-build depths)
# must hold inside them — metric attribution like
# greptime_flow_device_dispatch_total reads those flags at dispatch time.
# A module owning such a scope registers a (capture, apply) pair:
# capture() runs on the calling thread and returns a token, apply(token)
# is a context manager entered on the worker around the callable.
_PROPAGATORS: list = []


def register_scope_propagator(capture, apply) -> None:
    _PROPAGATORS.append((capture, apply))


# Bypass predicates: when any returns True on the CALLING thread, the
# supervisor runs the callable inline (unsupervised).  Background
# best-effort work (tile_cache's fused family builder) registers here:
# on a saturated box its ghost dispatches can genuinely outlast the
# foreground deadline, and abandoning one would quarantine devices — and
# drop every resident plane — over a stall no query is waiting on.  A
# wedge there hangs only the daemon builder thread (pre-supervisor
# behavior); the foreground path it primes stays fully supervised.
_BYPASS: list = []


def register_bypass(predicate) -> None:
    _BYPASS.append(predicate)


class DeviceWedgedError(RuntimeError):
    """A supervised device call was abandoned at its deadline (or failed
    fast because every target device is quarantined).  Deliberately NOT a
    QueryTimeoutError: the statement's own deadline still owns the query,
    and the engine's CPU-fallback ladder must catch this one."""


class DeviceCallError(RuntimeError):
    """Raised-error twin for the `device.error` fault point."""


class _Box:
    """One supervised call's detachable future."""

    __slots__ = ("event", "result", "exc")

    def __init__(self):
        self.event = threading.Event()
        self.result = None
        self.exc = None


class _Worker:
    """One device's dedicated call thread.  A wedged call never returns,
    so the thread is single-purpose and disposable: the supervisor writes
    it off (`dead = True`) and spawns a replacement; if the orphan ever
    wakes it notices and exits instead of racing its successor."""

    def __init__(self, name: str):
        self.dead = False
        self._q: queue.Queue = queue.Queue()
        self.thread = threading.Thread(target=self._loop, name=name, daemon=True)
        self.thread.start()

    def submit(self, fn) -> _Box:
        box = _Box()
        self._q.put((fn, box))
        return box

    def stop(self):
        self.dead = True
        self._q.put(None)

    def _loop(self):
        while True:
            item = self._q.get()
            if item is None or self.dead:
                return
            fn, box = item
            try:
                box.result = fn()
            except BaseException as e:  # noqa: BLE001 — ferried to the caller
                box.exc = e
            box.event.set()
            if self.dead:
                return


class _DeviceState:
    __slots__ = (
        "state", "consecutive_failures", "abandoned_calls", "quarantines",
        "heals", "probe_streak", "last_probe_ms", "quarantined_at",
        "last_error",
    )

    def __init__(self):
        self.state = HEALTHY
        self.consecutive_failures = 0
        self.abandoned_calls = 0
        self.quarantines = 0
        self.heals = 0
        self.probe_streak = 0
        self.last_probe_ms = 0
        self.quarantined_at = None  # monotonic seconds, while quarantined
        self.last_error = ""


class DeviceSupervisor:
    """Process-wide device health authority (one per process, like the
    flight recorder): the most recently opened Database's `device.*`
    config governs it.  Unconfigured (or `supervised = false`) it is a
    strict no-op — `call()` runs the callable in-thread, bit-for-bit."""

    def __init__(self):
        self._lock = threading.RLock()
        self._cfg = None
        self._devices: list = []
        self._states: dict[int, _DeviceState] = {}
        self._workers: dict[int, _Worker] = {}
        self._worker_gen = 0
        self._abandoned: list[threading.Thread] = []
        # bumped on every quarantine AND heal: the tile cache compares it
        # to decide when to drop device planes / re-read placement
        self._generation = 0
        self._prober: threading.Thread | None = None
        self._prober_stop = threading.Event()

    # ---- configuration -----------------------------------------------------
    def configure(self, cfg, devices=None):
        """Wire the `device.*` config section (and the live device list)
        from Database startup.  Passing cfg=None leaves supervision off."""
        with self._lock:
            self._cfg = cfg
            if devices is not None:
                self._devices = list(devices)

    @property
    def enabled(self) -> bool:
        cfg = self._cfg
        return cfg is not None and bool(getattr(cfg, "supervised", False))

    @property
    def generation(self) -> int:
        return self._generation

    def _ensure_devices(self):
        if not self._devices:
            import jax

            self._devices = list(jax.devices())
        return self._devices

    # ---- state queries -----------------------------------------------------
    def _state(self, idx: int) -> _DeviceState:
        st = self._states.get(idx)
        if st is None:
            st = self._states[idx] = _DeviceState()
        return st

    def state_of(self, idx: int) -> str:
        with self._lock:
            st = self._states.get(idx)
            return st.state if st is not None else HEALTHY

    def healthy_indices(self, n: int) -> tuple[int, ...]:
        """Device indices usable for placement/dispatch (not quarantined
        and not mid-probe).  Unknown devices are healthy by default."""
        if not self.enabled:
            return tuple(range(n))
        with self._lock:
            return tuple(
                i for i in range(n)
                if self._states.get(i) is None
                or self._states[i].state not in (QUARANTINED, PROBING)
            )

    def all_quarantined(self, n: int) -> bool:
        return n > 0 and not self.healthy_indices(n)

    # ---- the supervised call -----------------------------------------------
    def call(self, kind: str, fn, devices=None, countable=None,
             _probe: bool = False):
        """Run `fn` on the target device's worker thread under the hard
        deadline.  `devices` names the involved device indices (None =
        unknown: the call is attributed to every known device — a wedge
        then quarantines them all and the heal prober re-admits the
        innocent ones).  `countable` filters which raised exceptions feed
        the error breaker (site-specific benign errors — mesh shape
        ineligibility, RESOURCE_EXHAUSTED owned by the HBM ladder — must
        not poison device health)."""
        if not self.enabled or any(p() for p in _BYPASS):
            return fn()
        cfg = self._cfg
        devs = self._ensure_devices()
        if devices is None:
            indices = tuple(range(len(devs))) or (0,)
        else:
            indices = tuple(devices) or (0,)
        if not _probe and all(
            self.state_of(i) in (QUARANTINED, PROBING) for i in indices
        ):
            raise DeviceWedgedError(
                f"device call {kind!r} refused: device(s) "
                f"{sorted(indices)} quarantined"
            )
        timeout = float(getattr(cfg, "call_timeout_s", 30.0) or 30.0)
        if not _probe:
            d = current_deadline()
            if d is not None:
                remaining = d - time.monotonic()
                if remaining <= 0:
                    check_deadline()
                timeout = min(timeout, remaining)
        timeout = max(timeout, 0.001)

        tokens = [(apply, capture()) for capture, apply in _PROPAGATORS]

        def job():
            with contextlib.ExitStack() as scopes:
                for apply, token in tokens:
                    scopes.enter_context(apply(token))
                _fault_fire("device.wedge", kind=kind, device=indices[0])
                _fault_fire("device.error", kind=kind, device=indices[0])
                return fn()

        worker = self._worker_for(indices[0])
        box = worker.submit(job)
        if not box.event.wait(timeout):
            self._abandon(worker, kind, indices, timeout)
            raise DeviceWedgedError(
                f"device call {kind!r} abandoned after {timeout:.3f}s "
                f"(device(s) {sorted(indices)} quarantined; worker thread "
                "written off)"
            )
        if box.exc is not None:
            if not isinstance(
                box.exc, (QueryTimeoutError, DeviceWedgedError)
            ) and "RESOURCE_EXHAUSTED" not in str(box.exc) and (
                countable is None or countable(box.exc)
            ):
                self._record_error(indices, box.exc)
            raise box.exc
        self._record_success(indices)
        return box.result

    def _worker_for(self, idx: int) -> _Worker:
        with self._lock:
            w = self._workers.get(idx)
            if w is None or w.dead:
                if w is not None:
                    # replacing a written-off worker: the bounded leak
                    metrics.DEVICE_WORKER_REFILLS.inc()
                self._worker_gen += 1
                w = self._workers[idx] = _Worker(
                    f"device-worker-{idx}-g{self._worker_gen}"
                )
            return w

    def _abandon(self, worker: _Worker, kind: str, indices, timeout: float):
        with self._lock:
            # written off but left in the slot: _worker_for sees the dead
            # entry on the next call and replaces it, counting the refill
            worker.dead = True
            self._abandoned.append(worker.thread)
        metrics.DEVICE_HEALTH_ABANDONED.inc(kind=kind)
        flight_recorder.flag("device_abandoned")
        _LOG.warning(
            "device call %r abandoned after %.3fs on device(s) %s; "
            "worker %s written off",
            kind, timeout, sorted(indices), worker.thread.name,
        )
        with self._lock:
            for i in indices:
                st = self._state(i)
                st.abandoned_calls += 1
                st.consecutive_failures += 1
                st.last_error = f"abandoned:{kind}"
                self._transition_locked(i, st, QUARANTINED)
        self._start_prober()

    # ---- error breaker -----------------------------------------------------
    def _record_error(self, indices, exc: BaseException):
        threshold = max(int(getattr(self._cfg, "error_threshold", 3) or 3), 1)
        with self._lock:
            for i in indices:
                st = self._state(i)
                if st.state in (QUARANTINED, PROBING):
                    continue  # only the heal prober moves these
                st.consecutive_failures += 1
                st.last_error = f"{type(exc).__name__}: {exc}"[:160]
                if st.consecutive_failures >= threshold:
                    self._transition_locked(i, st, QUARANTINED)
                elif st.state == HEALTHY:
                    self._transition_locked(i, st, SUSPECT)
        self._start_prober()

    def _record_success(self, indices):
        with self._lock:
            for i in indices:
                st = self._states.get(i)
                if st is None:
                    continue
                if st.state == SUSPECT:
                    self._transition_locked(i, st, HEALTHY)
                if st.state == HEALTHY:
                    st.consecutive_failures = 0

    # ---- transitions -------------------------------------------------------
    def _transition_locked(self, idx: int, st: _DeviceState, to: str):
        frm = st.state
        if frm == to:
            return
        st.state = to
        if to == QUARANTINED:
            if frm != PROBING:
                st.quarantines += 1
                self._generation += 1
                metrics.DEVICE_HEALTH_QUARANTINES.inc()
            if st.quarantined_at is None:
                st.quarantined_at = time.monotonic()
            st.probe_streak = 0
        elif to == HEALTHY and frm == PROBING:
            st.heals += 1
            st.consecutive_failures = 0
            st.probe_streak = 0
            st.quarantined_at = None
            self._generation += 1
            metrics.DEVICE_HEALTH_HEALS.inc()
        metrics.DEVICE_HEALTH_TRANSITIONS.inc(to=to)
        metrics.DEVICE_HEALTH_STATE.set(_STATE_CODE[to], device=str(idx))
        tracing.add_event(
            "device.health", device=idx, from_state=frm, to_state=to
        )
        flight_recorder.flag_next(f"device_{to.lower()}")
        _LOG.warning("device %d health: %s -> %s", idx, frm, to)

    # ---- heal prober -------------------------------------------------------
    def _start_prober(self):
        with self._lock:
            if self._prober is not None and self._prober.is_alive():
                return
            self._prober_stop = threading.Event()
            self._prober = threading.Thread(
                target=self._probe_loop, name="device-heal-prober", daemon=True
            )
            self._prober.start()

    def _probe_loop(self):
        stop = self._prober_stop
        interval = float(getattr(self._cfg, "probe_interval_s", 1.0) or 1.0)
        while not stop.wait(interval):
            with self._lock:
                pending = [
                    i for i, st in self._states.items()
                    if st.state in (QUARANTINED, PROBING)
                ]
            if not pending:
                return  # idle prober exits; next quarantine restarts it
            for i in pending:
                if stop.is_set():
                    return
                self._probe_one(i)

    def _probe_one(self, idx: int):
        cfg = self._cfg
        need = max(int(getattr(cfg, "probe_successes", 3) or 3), 1)
        with self._lock:
            st = self._states.get(idx)
            if st is None or st.state not in (QUARANTINED, PROBING):
                return
            self._transition_locked(idx, st, PROBING)

        def ghost():
            # a tiny real round-trip on the quarantined device: upload,
            # compute, fetch — the minimal proof the device answers again
            import jax
            import numpy as np

            dev = self._ensure_devices()[idx]
            x = jax.device_put(np.arange(8, dtype=np.float32), dev)
            return float(jax.device_get(x).sum())

        ok = False
        try:
            self.call("probe", ghost, devices=(idx,), _probe=True)
            ok = True
        except BaseException:  # noqa: BLE001 — a failing probe re-quarantines
            ok = False
        now_ms = int(time.time() * 1000)
        with self._lock:
            st = self._states.get(idx)
            if st is None:
                return
            st.last_probe_ms = now_ms
            metrics.DEVICE_HEALTH_PROBES.inc(result="ok" if ok else "fail")
            if st.state != PROBING:
                return
            if ok:
                st.probe_streak += 1
                if st.probe_streak >= need:
                    self._transition_locked(idx, st, HEALTHY)
            else:
                st.probe_streak = 0
                self._transition_locked(idx, st, QUARANTINED)

    # ---- introspection -----------------------------------------------------
    def health_rows(self, devices=None) -> list[dict]:
        """Per-device snapshot shared by information_schema.device_health,
        /debug/tile and the bench digest."""
        devs = list(devices) if devices is not None else list(self._devices)
        if not devs:
            devs = list(self._devices)
        now = time.monotonic()
        rows = []
        with self._lock:
            for i, dev in enumerate(devs):
                st = self._states.get(i)
                q_age = 0
                if st is not None and st.quarantined_at is not None:
                    q_age = int((now - st.quarantined_at) * 1000)
                rows.append({
                    "device": i,
                    "device_kind": str(dev),
                    "state": st.state if st is not None else HEALTHY,
                    "consecutive_failures": (
                        st.consecutive_failures if st is not None else 0
                    ),
                    "abandoned_calls": (
                        st.abandoned_calls if st is not None else 0
                    ),
                    "quarantines": st.quarantines if st is not None else 0,
                    "heals": st.heals if st is not None else 0,
                    "last_probe_ms": st.last_probe_ms if st is not None else 0,
                    "quarantine_age_ms": q_age,
                    "last_error": st.last_error if st is not None else "",
                })
        return rows

    def digest(self) -> dict:
        """Compact rollup for /debug/tile and the bench mixed record."""
        with self._lock:
            states: dict[str, int] = {}
            abandoned = quarantines = heals = failures = 0
            for st in self._states.values():
                states[st.state] = states.get(st.state, 0) + 1
                abandoned += st.abandoned_calls
                quarantines += st.quarantines
                heals += st.heals
                failures += st.consecutive_failures
            n_known = len(self._states)
        n_devices = len(self._devices)
        if n_devices > n_known:
            states[HEALTHY] = states.get(HEALTHY, 0) + (n_devices - n_known)
        return {
            "supervised": self.enabled,
            "states": states,
            "abandoned_calls": abandoned,
            "quarantines": quarantines,
            "heals": heals,
            "consecutive_failures": failures,
        }

    def abandoned_worker_threads(self) -> list[threading.Thread]:
        """Written-off worker threads (the conftest session-teardown gate
        asserts none outlive the suite except under `wedge`-marked tests,
        which hold the wedge Event and must release it at teardown)."""
        with self._lock:
            return list(self._abandoned)

    # ---- test / lifecycle hooks --------------------------------------------
    def reset(self):
        """Return every device to HEALTHY and drop per-device counters —
        test isolation (the supervisor is process-wide, the golden suite
        runs in the same process as the chaos tests).  Written-off worker
        threads stay recorded for the teardown gate; live workers are
        stopped so an idle process holds no supervision threads."""
        with self._lock:
            self._prober_stop.set()
            self._states.clear()
            for w in self._workers.values():
                w.stop()
            self._workers.clear()
        prober = self._prober
        if prober is not None and prober is not threading.current_thread():
            prober.join(timeout=5.0)
        with self._lock:
            self._prober = None


SUPERVISOR = DeviceSupervisor()


def supervised_call(kind: str, fn, devices=None, countable=None):
    """Module-level convenience: route one blocking device interaction
    through the process supervisor (a direct `fn()` when supervision is
    off — the off-safe bit-for-bit contract)."""
    return SUPERVISOR.call(kind, fn, devices=devices, countable=countable)
