"""Device health supervisor chaos suite (utils/device_health.py).

The failure mode under test is the one no raised-error ladder can catch: a
device call that neither returns nor raises.  The supervisor bounds every
blocking device interaction with a per-device worker thread + hard
deadline; a wedged call is ABANDONED (worker written off — the bounded
leak the conftest `wedge` gate polices), the device quarantines, and the
query degrades down the existing ladder (host consolidation / scan path /
CPU fallback) — zero failed queries.  A background prober re-admits the
device after consecutive in-deadline ghost dispatches, and the post-heal
results must be byte-identical to pre-wedge.

Fault points exercised here (the conftest coverage gate):
    "device.wedge"  in-worker callback blocking on a test Event: the
                    supervising thread times out exactly as with stuck
                    native code (the callback releases the GIL)
    "device.error"  raised-error storm driving the breaker-style
                    SUSPECT -> QUARANTINED path without any wedge
"""

import io
import threading
import time
import types

import numpy as np
import pyarrow as pa
import pytest

from greptimedb_tpu.database import Database
from greptimedb_tpu.utils import device_health as dh
from greptimedb_tpu.utils import fault_injection as fi
from greptimedb_tpu.utils import metrics
from greptimedb_tpu.utils.config import Config


@pytest.fixture(autouse=True)
def _clean_supervisor():
    fi.REGISTRY.disarm()
    dh.SUPERVISOR.reset()
    yield
    fi.REGISTRY.disarm()
    dh.SUPERVISOR.reset()


def _ser(t: pa.Table) -> bytes:
    sink = io.BytesIO()
    with pa.ipc.new_stream(sink, t.schema) as w:
        w.write_table(t)
    return sink.getvalue()


def _mk_db(tmp_path, name, *, mesh=0, window_ms=0.0, supervised=True,
           timeout_s=2.0):
    cfg = Config()
    cfg.storage.compaction_background_enable = False
    cfg.query.tpu_min_rows = 1
    cfg.tile.fused_build = False  # first dispatch marks the family warm
    cfg.tile.mesh_devices = mesh
    cfg.batch.window_ms = window_ms
    cfg.device.supervised = supervised
    # chaos-speed knobs: abandon fast, probe fast, heal after 2 probes.
    # The timeout must clear a GENUINE first-compile inside a supervised
    # call (the warm-up mesh/dispatch compile runs ~0.6 s on this box) —
    # post-warm calls are all <10 ms, so only the armed wedge trips it.
    cfg.device.call_timeout_s = timeout_s
    cfg.device.probe_interval_s = 0.05
    cfg.device.probe_successes = 2
    cfg.validate()
    return Database(data_home=str(tmp_path / name), config=cfg)


def _load(db, seed, n=2_000):
    rng = np.random.default_rng(seed)
    db.sql(
        "CREATE TABLE t (k STRING, ts TIMESTAMP TIME INDEX, v DOUBLE,"
        " PRIMARY KEY (k)) WITH (append_mode='true')"
    )
    keys = rng.integers(0, 40, n)
    db.insert_rows("t", pa.table({
        "k": pa.array([f"k{i:03d}" for i in keys]),
        "ts": pa.array(np.arange(n, dtype=np.int64) * 1000, pa.timestamp("ms")),
        "v": pa.array(rng.integers(-500, 500, n).astype(np.float64)),
    }))
    db.storage.flush_all()


_Q = "SELECT k, sum(v) AS sv, count(*) AS c FROM t GROUP BY k"


class _Wedge:
    """Arms `device.wedge` with a callback that blocks the worker thread
    on a test-controlled Event until release() — stuck-native-code à la
    carte.  Always release before leaving the test so the written-off
    thread exits (the conftest leak gate joins it)."""

    def __init__(self, kind):
        self.event = threading.Event()
        self.entered = threading.Event()
        self.plan = fi.REGISTRY.arm(
            "device.wedge", fail_times=1,
            match=lambda ctx: ctx.get("kind") == kind,
            callback=self._block,
        )

    def _block(self, ctx):
        self.entered.set()
        self.event.wait(timeout=30)

    def release(self):
        self.event.set()


def _join_abandoned(max_s=10.0):
    """Every written-off worker thread must exit once its wedge releases —
    the per-test 'no hung threads at teardown' assertion."""
    for t in dh.SUPERVISOR.abandoned_worker_threads():
        t.join(timeout=max_s)
        assert not t.is_alive(), f"abandoned worker {t.name} never exited"


def _await_heal(n_devices, max_s=15.0):
    deadline = time.monotonic() + max_s
    while time.monotonic() < deadline:
        if dh.SUPERVISOR.healthy_indices(n_devices) == tuple(range(n_devices)):
            return
        time.sleep(0.02)
    pytest.fail(
        f"devices never healed: {dh.SUPERVISOR.digest()}"
    )


# ---- wedge chaos: zero failed queries, quarantine, heal, bit-parity ---------

@pytest.mark.wedge
def test_wedge_mid_warm_dispatch_quarantine_and_heal(tmp_path, monkeypatch):
    """A warm dispatch that never returns: the query must still answer
    (abandon -> quarantine -> degrade ladder), the device health machinery
    must record the abandonment, the prober must re-admit the devices once
    the wedge clears, and the post-heal answer is byte-identical."""
    db = _mk_db(tmp_path, "warm")
    try:
        _load(db, 21)
        db.sql_one(_Q)  # cold: plane build + warm marking
        want = _ser(db.sql_one(_Q))  # warm reference bytes
        a0 = metrics.DEVICE_HEALTH_ABANDONED.get(kind="dispatch")
        q0 = metrics.DEVICE_HEALTH_QUARANTINES.get()
        # read before the wedge: the prober may re-admit the devices any
        # moment after the wedge is released
        h0 = metrics.DEVICE_HEALTH_HEALS.get()
        # both deadlines armed for the wedged statement: the call's own
        # must be the one that fires
        db.config.query.timeout_s = 600.0
        abandoned = []
        real_abandon = dh.SUPERVISOR._abandon

        def spy(worker, kind, indices, timeout):
            abandoned.append((kind, timeout))
            return real_abandon(worker, kind, indices, timeout)

        monkeypatch.setattr(dh.SUPERVISOR, "_abandon", spy)
        w = _Wedge("dispatch")
        try:
            # the wedged query — must still answer: a statement deadline
            # that fired would raise QueryTimeoutError here
            got = db.sql_one(_Q)
        finally:
            w.release()
            db.config.query.timeout_s = 0.0
        assert _ser(got) == want, "the degraded answer diverged"
        assert w.plan.trips == 1
        assert w.entered.is_set()
        # bounded: abandoned at call_timeout_s, not at what the statement
        # deadline had left
        assert [t for kind, t in abandoned if kind == "dispatch"] == [
            db.config.device.call_timeout_s
        ]
        assert metrics.DEVICE_HEALTH_ABANDONED.get(kind="dispatch") == a0 + 1
        assert metrics.DEVICE_HEALTH_QUARANTINES.get() > q0
        dig = dh.SUPERVISOR.digest()
        assert dig["abandoned_calls"] >= 1 and dig["quarantines"] >= 1
        # while quarantined, queries still answer (scan path / fallback)
        assert _ser(db.sql_one(_Q)) == want
        # heal: the prober's ghost dispatches re-admit every device
        n = len(db.query_engine.tile_cache.devices)
        _await_heal(n)
        assert metrics.DEVICE_HEALTH_HEALS.get() > h0
        assert dh.SUPERVISOR.digest()["heals"] >= 1
        # post-heal: planes rebuilt on the healed set, bytes identical
        assert _ser(db.sql_one(_Q)) == want
        assert _ser(db.sql_one(_Q)) == want  # and again, warm
        _join_abandoned()
    finally:
        db.close()


@pytest.mark.wedge
def test_wedge_mid_fused_batch_tick(tmp_path):
    """A wedge inside a batch tick's shared readback: every member of the
    batch still answers, bit-identical to its solo run."""
    db = _mk_db(tmp_path, "tick", window_ms=60.0)
    try:
        _load(db, 22)
        queries = (
            _Q,
            "SELECT k, max(v) AS xv FROM t GROUP BY k",
            "SELECT count(*) AS c FROM t",
        )
        solo = {}
        for q in queries:
            db.sql_one(q)
            solo[q] = _ser(db.sql_one(q))
        w = _Wedge("readback")
        results = [None] * len(queries)
        errors = []
        barrier = threading.Barrier(len(queries))

        def run(i, q):
            try:
                barrier.wait(timeout=30)
                results[i] = db.sql_one(q)
            except Exception as exc:  # noqa: BLE001 — asserted below
                errors.append(exc)

        try:
            threads = [
                threading.Thread(target=run, args=(i, q))
                for i, q in enumerate(queries)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            w.release()
        assert not errors, f"zero failed queries violated: {errors}"
        for q, r in zip(queries, results):
            assert r is not None and _ser(r) == solo[q], (
                f"wedged-tick result diverged for {q!r}"
            )
        if w.plan.trips:  # the tick reached the wedged readback
            assert dh.SUPERVISOR.digest()["quarantines"] >= 1
        _join_abandoned()
    finally:
        db.close()


@pytest.mark.wedge
def test_wedge_mid_cold_build_upload(tmp_path):
    """A wedge in the cold build's host->device upload: the first query
    that touches the device still answers correctly from the degrade
    ladder.  (The very first query only marks the family warm on the scan
    path — with fused_build off, device planes build on the next one.)"""
    db = _mk_db(tmp_path, "cold")
    try:
        _load(db, 23)
        db.sql_one(_Q)  # family warm marking: scan path, no device work
        w = _Wedge("upload")
        try:
            got = db.sql_one(_Q)  # cold device-plane build, upload wedged
        finally:
            w.release()
        assert w.plan.trips == 1
        assert got is not None and got.num_rows > 0
        assert dh.SUPERVISOR.digest()["abandoned_calls"] >= 1
        # the supervisor quarantined; the answer must match the healed run
        n = len(db.query_engine.tile_cache.devices)
        _await_heal(n)
        want = _ser(db.sql_one(_Q))
        assert _ser(got) == want, "cold-wedge degrade diverged from healed"
        _join_abandoned()
    finally:
        db.close()


@pytest.mark.wedge
def test_wedge_mid_mesh_collective(tmp_path):
    """A wedge inside the multi-chip collective: the mesh degrades to the
    single-chip dispatch (the surviving devices), bit-correct, and the
    mesh slots quarantine — mesh_devices() then reports the shrunken
    surviving set."""
    db = _mk_db(tmp_path, "mesh", mesh=2)
    try:
        _load(db, 24)
        db.sql_one(_Q)
        want = _ser(db.sql_one(_Q))
        cache = db.query_engine.tile_cache
        assert cache.mesh_devices() == 2
        w = _Wedge("mesh")
        try:
            got = db.sql_one(_Q)
        finally:
            w.release()
        assert w.plan.trips == 1
        assert _ser(got) == want, "mesh-wedge degrade diverged"
        # the two mesh slots quarantined; placement shrinks around them
        assert dh.SUPERVISOR.state_of(0) in (dh.QUARANTINED, dh.PROBING)
        n = len(cache.devices)
        assert len(dh.SUPERVISOR.healthy_indices(n)) <= n - 1
        assert cache.mesh_devices() <= n - 1
        _await_heal(n)
        assert cache.mesh_devices() == 2
        assert _ser(db.sql_one(_Q)) == want
        _join_abandoned()
    finally:
        db.close()


# ---- raised-error storm: the breaker path (no wedge, no abandoned thread) ---

def test_device_error_storm_trips_breaker_quarantine(tmp_path):
    """error_threshold consecutive raised device errors quarantine the
    device WITHOUT any wedge: every erroring query still answers via the
    CPU fallback, the state walks HEALTHY -> SUSPECT -> QUARANTINED, and
    the prober heals once the storm stops."""
    db = _mk_db(tmp_path, "storm")
    db.config.device.error_threshold = 3
    try:
        _load(db, 25)
        db.sql_one(_Q)
        want = _ser(db.sql_one(_Q))
        q0 = metrics.DEVICE_HEALTH_QUARANTINES.get()
        # written-off threads from EARLIER wedge tests stay listed (the
        # session leak gate audits them) — only NEW ones would be a bug
        ab0 = {id(t) for t in dh.SUPERVISOR.abandoned_worker_threads()}
        with fi.REGISTRY.armed(
            "device.error", fail_times=3, error=dh.DeviceCallError,
            match=lambda ctx: ctx.get("kind") == "dispatch",
        ) as plan:
            assert _ser(db.sql_one(_Q)) == want  # error 1: SUSPECT
            assert dh.SUPERVISOR.state_of(0) == dh.SUSPECT
            assert _ser(db.sql_one(_Q)) == want  # error 2: still SUSPECT
            assert _ser(db.sql_one(_Q)) == want  # error 3: QUARANTINED
            assert plan.trips == 3
        assert metrics.DEVICE_HEALTH_QUARANTINES.get() > q0
        assert dh.SUPERVISOR.digest()["quarantines"] >= 1
        # no thread was written off — the breaker path raises, never wedges
        assert not [
            t for t in dh.SUPERVISOR.abandoned_worker_threads()
            if id(t) not in ab0
        ]
        n = len(db.query_engine.tile_cache.devices)
        _await_heal(n)
        assert _ser(db.sql_one(_Q)) == want
    finally:
        db.close()


# ---- latent batcher hang: leader dying before the packed fetch --------------

def test_batcher_leader_death_wakes_joiners(tmp_path):
    """Regression: a leader killed between enqueue and the packed fetch
    (async deadline alarm / interrupt during the window sleep) used to
    strand every joiner on an event nobody would set.  The finally-
    guaranteed release must wake them all with the solo-rerun verdict."""
    from greptimedb_tpu.parallel import batcher as batcher_mod

    db = _mk_db(tmp_path, "lead", window_ms=200.0)
    try:
        _load(db, 26)
        queries = (
            _Q,
            "SELECT k, max(v) AS xv FROM t GROUP BY k",
            "SELECT k, min(v) AS mv FROM t GROUP BY k",
        )
        solo = {}
        for q in queries:
            db.sql_one(q)
            solo[q] = _ser(db.sql_one(q))

        entered = threading.Event()
        release = threading.Event()
        real_time = batcher_mod.time

        def killer_sleep(s):
            # only the leader's window sleep (~0.2 s) is hijacked; any
            # other sleep in the module passes through untouched
            if s > 0.1 and not entered.is_set():
                entered.set()
                release.wait(timeout=30)
                raise KeyboardInterrupt("leader killed in the window sleep")
            real_time.sleep(s)

        stub = types.SimpleNamespace(
            sleep=killer_sleep,
            monotonic=real_time.monotonic,
            perf_counter=real_time.perf_counter,
            time=real_time.time,
        )
        results = [None] * len(queries)
        failures = [None] * len(queries)

        def run(i, q):
            try:
                results[i] = db.sql_one(q)
            except BaseException as exc:  # noqa: BLE001 — leader dies by design
                failures[i] = exc

        batcher_mod.time = stub
        try:
            leader = threading.Thread(target=run, args=(0, queries[0]))
            leader.start()
            assert entered.wait(timeout=30), "leader never reached the window"
            joiners = [
                threading.Thread(target=run, args=(i, q))
                for i, q in enumerate(queries[1:], start=1)
            ]
            for t in joiners:
                t.start()
            # wait until both joiners are actually enqueued on the batch
            batcher = db.query_engine._tile_executor._batcher
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                open_batches = list(batcher._open.values())
                if open_batches and len(open_batches[0].members) >= 3:
                    break
                time.sleep(0.005)
            release.set()  # the leader now dies mid-_lead
            t0 = time.monotonic()
            for t in joiners:
                t.join(timeout=30)
                assert not t.is_alive(), (
                    "joiner stranded after leader death — the finally-"
                    "guaranteed waiter release is broken"
                )
            leader.join(timeout=30)
            assert time.monotonic() - t0 < 20
        finally:
            batcher_mod.time = real_time
        # the leader died by injected interrupt; every JOINER must have
        # answered correctly via its solo rerun
        assert isinstance(failures[0], KeyboardInterrupt) or results[0] is not None
        for i, q in enumerate(queries[1:], start=1):
            assert failures[i] is None, f"joiner failed: {failures[i]!r}"
            assert results[i] is not None and _ser(results[i]) == solo[q]
    finally:
        db.close()


# ---- off-safe + unit-level supervisor behavior ------------------------------

def test_supervised_false_is_bit_for_bit_off(tmp_path):
    """device.supervised=false restores direct in-thread calls: results
    byte-identical to the supervised run, no device-worker threads, no
    health state accrued."""
    db_on = _mk_db(tmp_path, "on", supervised=True)
    try:
        _load(db_on, 27)
        db_on.sql_one(_Q)
        want = _ser(db_on.sql_one(_Q))
    finally:
        db_on.close()
    dh.SUPERVISOR.reset()
    db_off = _mk_db(tmp_path, "off", supervised=False)
    try:
        assert not dh.SUPERVISOR.enabled
        _load(db_off, 27)
        db_off.sql_one(_Q)
        assert _ser(db_off.sql_one(_Q)) == want
        assert dh.SUPERVISOR.digest()["supervised"] is False
        assert dh.SUPERVISOR.digest()["abandoned_calls"] == 0
        assert not [
            t for t in threading.enumerate()
            if t.name.startswith("device-worker-")
        ], "supervision off must spawn no worker threads"
    finally:
        db_off.close()


def test_supervisor_unit_fail_fast_and_refill():
    """Unit level: a wedged call abandons its worker (refill counter
    moves), subsequent calls on an all-quarantined target fail fast with
    DeviceWedgedError, and a probe-path success ladder re-admits."""
    cfg = Config().device
    cfg.call_timeout_s = 0.15
    cfg.probe_successes = 1
    cfg.probe_interval_s = 0.03
    sup = dh.DeviceSupervisor()
    sup.configure(cfg, devices=["cpu:0"])
    gate = threading.Event()
    r0 = metrics.DEVICE_WORKER_REFILLS.get()
    try:
        with pytest.raises(dh.DeviceWedgedError, match="abandoned"):
            sup.call("dispatch", lambda: gate.wait(30), devices=(0,))
        assert sup.state_of(0) == dh.QUARANTINED
        # fail fast: no new worker hop while the only device is down
        with pytest.raises(dh.DeviceWedgedError, match="refused"):
            sup.call("dispatch", lambda: 1, devices=(0,))
        # a fresh (non-quarantined-target) call refills the worker slot
        sup._states.clear()  # simulate heal for the refill check
        assert sup.call("dispatch", lambda: 7, devices=(0,)) == 7
        assert metrics.DEVICE_WORKER_REFILLS.get() == r0 + 1
    finally:
        gate.set()
        for t in sup.abandoned_worker_threads():
            t.join(timeout=10)
            assert not t.is_alive()
        sup.reset()


def test_supervisor_benign_errors_not_countable():
    """RESOURCE_EXHAUSTED (HBM ladder's) and site-filtered benign errors
    must not feed the breaker."""
    cfg = Config().device
    cfg.error_threshold = 1
    sup = dh.DeviceSupervisor()
    sup.configure(cfg, devices=["cpu:0"])
    try:
        def oom():
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

        with pytest.raises(RuntimeError):
            sup.call("dispatch", oom, devices=(0,))
        assert sup.state_of(0) == dh.HEALTHY

        class Benign(Exception):
            pass

        def benign():
            raise Benign("shape ineligible")

        with pytest.raises(Benign):
            sup.call(
                "mesh", benign, devices=(0,),
                countable=lambda e: not isinstance(e, Benign),
            )
        assert sup.state_of(0) == dh.HEALTHY
        # a countable error at threshold=1 quarantines immediately
        def boom():
            raise dh.DeviceCallError("XLA runtime error")

        with pytest.raises(dh.DeviceCallError):
            sup.call("dispatch", boom, devices=(0,))
        assert sup.state_of(0) == dh.QUARANTINED
    finally:
        sup.reset()


def test_information_schema_device_health_live(tmp_path):
    """The introspection table reports one HEALTHY row per device with
    the full column contract."""
    db = _mk_db(tmp_path, "schema")
    try:
        t = db.sql_one(
            "SELECT device, state, abandoned_calls, quarantines, heals"
            " FROM information_schema.device_health ORDER BY device"
        )
        n = len(db.query_engine.tile_cache.devices)
        assert t.num_rows == n
        assert t.column("state").to_pylist() == ["HEALTHY"] * n
        assert t.column("device").to_pylist() == list(range(n))
    finally:
        db.close()
