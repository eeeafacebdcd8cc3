"""Test harness configuration.

Tests run on an 8-device virtual CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (the driver's dryrun does the same).  This
must be set before jax is imported anywhere.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
# Hold jax to the CPU via jax.config too, not just env: the tests never
# touch an accelerator (the chip is reached only by chip_smoke.py through
# the builder's chip tool).  The query layer uses float64 accumulators to
# match CPU results.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402

# ---- stall watchdog --------------------------------------------------------
# If any single test runs longer than WATCHDOG_S, dump EVERY thread's stack
# to a side file (fd-capture-proof — pytest redirects fd 2, so faulthandler's
# default target vanishes into the capture tempfile).  Purely diagnostic: the
# run is not killed, but a hung tier-1 run leaves the evidence behind.
_WATCHDOG_S = float(os.environ.get("GREPTIMEDB_TPU_TEST_WATCHDOG_S", "600"))
_WATCHDOG_FILE = os.environ.get(
    "GREPTIMEDB_TPU_TEST_WATCHDOG_FILE", "/tmp/greptimedb_tpu_test_watchdog.txt"
)
_watchdog_fh = None


def _dump_follower_lag(fh):
    """Write the per-region follower lag gauges into the watchdog file just
    before the faulthandler stack dump fires: a wedged follower sync loop
    (the thread stuck, lag_ms growing) leaves numeric evidence next to the
    stacks instead of only an inscrutable hang."""
    try:
        from greptimedb_tpu.utils import metrics as _m

        lines = ["", "-- follower lag at watchdog deadline --"]
        lines += _m.FOLLOWER_LAG_ENTRIES.render()
        lines += _m.FOLLOWER_LAG_MS.render()
        fh.write("\n".join(lines) + "\n")
        fh.flush()
    except Exception:  # noqa: BLE001 — diagnostics must never fail a test
        pass


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    import faulthandler
    import threading

    global _watchdog_fh
    lag_timer = None
    if _WATCHDOG_S > 0:
        if _watchdog_fh is None:
            _watchdog_fh = open(_WATCHDOG_FILE, "w")
        _watchdog_fh.truncate(0)
        _watchdog_fh.seek(0)
        _watchdog_fh.write(f"watchdog armed for: {item.nodeid}\n")
        _watchdog_fh.flush()
        # the lag snapshot runs a beat BEFORE faulthandler's C-level dump
        # (which cannot run Python code) so both land in the same file
        lag_timer = threading.Timer(
            max(_WATCHDOG_S - 2.0, _WATCHDOG_S * 0.9),
            _dump_follower_lag,
            args=(_watchdog_fh,),
        )
        lag_timer.daemon = True
        lag_timer.start()
        faulthandler.dump_traceback_later(
            _WATCHDOG_S, exit=False, file=_watchdog_fh
        )
    yield
    if _WATCHDOG_S > 0:
        faulthandler.cancel_dump_traceback_later()
        if lag_timer is not None:
            lag_timer.cancel()


def pytest_sessionstart(session):
    """Every NAMED fault-injection point must be exercised by at least one
    test: a new point landing without a chaos/unit test firing it is dead
    coverage, and this check fails the run before a single test executes.
    The check is static (scans test sources for the point name in an
    arm()/armed()/fire() call) so it holds for any test subset the session
    actually runs."""
    import pathlib
    import re

    from greptimedb_tpu.utils.fault_injection import POINTS

    root = pathlib.Path(__file__).parent
    blob = "\n".join(
        p.read_text(encoding="utf-8") for p in sorted(root.glob("test_*.py"))
    )
    missing = [
        point
        for point in sorted(POINTS)
        if not re.search(r"""['"]{}['"]""".format(re.escape(point)), blob)
    ]
    if missing:
        raise pytest.UsageError(
            "fault-injection points with no test exercising them: "
            f"{missing} — add a chaos test arming each point "
            "(tests/test_chaos.py) before registering it in "
            "greptimedb_tpu/utils/fault_injection.py"
        )


@pytest.fixture(scope="session", autouse=True)
def _span_taxonomy_gate():
    """Every DOTTED span stage name emitted while the session ran must
    appear in the README's documented span taxonomy (the
    `<!-- span-taxonomy:begin -->` block) — stage names are a stable
    contract consumed by operators querying the own trace store, so
    instrumentation cannot silently drift from the docs.  Undotted names
    are exempt: tests create synthetic spans ("parent", "child") that are
    not product stages.  Mirrors the fault-point coverage gate above,
    enforced at session teardown because spans are only known after the
    tests ran."""
    yield
    import fnmatch
    import pathlib
    import re

    from greptimedb_tpu.utils.tracing import SEEN_SPAN_NAMES

    seen = {n for n in SEEN_SPAN_NAMES if "." in n}
    if not seen:
        return
    readme = pathlib.Path(__file__).parent.parent / "README.md"
    text = readme.read_text(encoding="utf-8")
    m = re.search(
        r"<!-- span-taxonomy:begin -->(.*?)<!-- span-taxonomy:end -->",
        text,
        re.S,
    )
    assert m, (
        "README.md lost its span-taxonomy block "
        "(<!-- span-taxonomy:begin --> ... <!-- span-taxonomy:end -->)"
    )
    taxonomy = set(re.findall(r"`([^`\s]+)`", m.group(1)))
    unmatched = sorted(
        n
        for n in seen
        if n not in taxonomy
        and not any(
            fnmatch.fnmatch(n, pat) for pat in taxonomy if "*" in pat
        )
    )
    assert not unmatched, (
        f"span stage names emitted but missing from the README span "
        f"taxonomy: {unmatched} — document them in the "
        "<!-- span-taxonomy:begin --> block (stage names are a stable "
        "contract) or rename the span"
    )


@pytest.fixture(scope="session", autouse=True)
def _metrics_name_gate():
    """Every `greptime_*` metric name registered while the session ran
    must appear in the README's documented metric inventory (the
    `<!-- metrics:begin -->` block) — metric names are a stable contract
    consumed by dashboards and the self-scrape, so a new counter landing
    undocumented is instrumentation drift.  Twin of the span-taxonomy
    gate below, enforced at session teardown because label-created
    metrics only exist after the tests ran."""
    yield
    import fnmatch
    import pathlib
    import re

    from greptimedb_tpu.utils.metrics import REGISTRY

    with REGISTRY._lock:
        seen = {n for n in REGISTRY._metrics if n.startswith("greptime_")}
    if not seen:
        return
    readme = pathlib.Path(__file__).parent.parent / "README.md"
    text = readme.read_text(encoding="utf-8")
    m = re.search(
        r"<!-- metrics:begin -->(.*?)<!-- metrics:end -->", text, re.S
    )
    assert m, (
        "README.md lost its metric-inventory block "
        "(<!-- metrics:begin --> ... <!-- metrics:end -->)"
    )
    documented = set(re.findall(r"`([^`\s]+)`", m.group(1)))
    unmatched = sorted(
        n
        for n in seen
        if n not in documented
        and not any(
            fnmatch.fnmatch(n, pat) for pat in documented if "*" in pat
        )
    )
    assert not unmatched, (
        f"greptime_* metric names registered but missing from the README "
        f"metric inventory: {unmatched} — document them in the "
        "<!-- metrics:begin --> block (metric names are a stable "
        "contract) or rename the metric"
    )


# ---- abandoned device-worker thread gate -----------------------------------
# The device supervisor (utils/device_health.py) writes off a worker thread
# when its call wedges past the hard deadline — that is the designed bounded
# leak, but ONLY tests that deliberately wedge a device (@pytest.mark.wedge)
# may create one, and those tests must release their wedge Events so the
# orphan exits.  Anything else alive at session end is a real thread leak.
_wedge_attributed: set = set()


@pytest.fixture(autouse=True)
def _wedge_thread_attribution(request):
    from greptimedb_tpu.utils import device_health

    sup = device_health.SUPERVISOR
    before = {id(t) for t in sup.abandoned_worker_threads()}
    yield
    new = [t for t in sup.abandoned_worker_threads() if id(t) not in before]
    if not new:
        return
    if request.node.get_closest_marker("wedge") is None:
        pytest.fail(
            "test abandoned device-worker thread(s) "
            f"{[t.name for t in new]} without @pytest.mark.wedge — either "
            "mark the test `wedge` (and release the wedge at teardown) or "
            "stop wedging the supervisor"
        )
    _wedge_attributed.update(id(t) for t in new)


@pytest.fixture(scope="session", autouse=True)
def _device_worker_leak_gate():
    """No abandoned device-worker thread may still be ALIVE at session end
    unless a `wedge`-marked test created it (and even those are expected to
    release their wedge Events — a brief grace join absorbs the exit race).
    Twin of the README gates above: the supervisor's thread leak is bounded
    by design, and this keeps 'bounded' honest suite-wide."""
    yield
    from greptimedb_tpu.utils import device_health

    leaked = []
    for t in device_health.SUPERVISOR.abandoned_worker_threads():
        if t.is_alive():
            t.join(timeout=2.0)
        if t.is_alive() and id(t) not in _wedge_attributed:
            leaked.append(t.name)
    assert not leaked, (
        f"abandoned device-worker thread(s) still alive at session end "
        f"and not attributed to any @pytest.mark.wedge test: {leaked}"
    )


@pytest.fixture()
def tmp_engine(tmp_path):
    from greptimedb_tpu.storage.engine import TimeSeriesEngine
    from greptimedb_tpu.utils.config import StorageConfig

    cfg = StorageConfig(data_home=str(tmp_path))
    cfg.wal_dir = str(tmp_path / "wal")
    cfg.sst_dir = str(tmp_path / "data")
    engine = TimeSeriesEngine(cfg)
    yield engine
    engine.close()


_gc_freeze_counter = 0


def pytest_runtest_teardown(item, nextitem):
    """Periodically collect-then-freeze the heap.  A long suite run
    accumulates hundreds of thousands of long-lived objects (jaxprs,
    compiled executables, cached planes) that gen-2 GC re-scans on every
    collection; by test ~400 that overhead measurably slows BOTH
    in-process tests and the subprocess-driving ones (the parent's GC
    pauses starve the single-core box).  Freezing moves the survivors to
    the permanent generation so later collections skip them — dead
    cycles from the 20 tests since the last checkpoint are collected
    first, so only checkpoint-surviving objects are exempted (a bounded
    memory trade the suite box can easily afford)."""
    global _gc_freeze_counter
    _gc_freeze_counter += 1
    if _gc_freeze_counter % 20 == 0:
        import gc

        gc.collect()
        gc.freeze()


_session_exitstatus = None


def pytest_sessionfinish(session, exitstatus):
    global _session_exitstatus
    _session_exitstatus = int(exitstatus)


@pytest.hookimpl(trylast=True)
def pytest_unconfigure(config):
    """Skip interpreter teardown.  After ~900 tests the process holds a
    multi-GB object graph (jax executables, cached planes, frozen GC
    generations); CPython's exit sweep walks and frees it object by
    object, which costs >10 s on this box AFTER the summary line has
    printed — enough to blow a wall-clock budget the tests themselves
    met.  unconfigure runs after the whole sessionfinish chain — the
    terminal summary and every session-scoped finalizer (the README
    metric/span/fault-point gates) — so the only thing skipped is
    deallocation the OS does for free."""
    import sys

    if _session_exitstatus is None:
        return  # collection-less invocations (--help, --version)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(_session_exitstatus)
