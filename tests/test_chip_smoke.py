"""chip_smoke.py rehearsed on the CPU, and where the compile cache lives.

The smoke itself only means something on the chip (the builder's chip tool
runs it there); here it is held to its contract: a tiny rehearsal passes
and reports the CPU, a run whose device path cannot engage fails, and a
run without `--rehearse` refuses to start without an accelerator.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
TINY = ["--hosts", "10", "--hours", "1"]


def _smoke(args, tmp_path, **env):
    """Runs from an empty cwd with its own compile cache, so neither the
    checkout's cache nor a parallel worker's is involved; the data home is
    the script's fixed one, keyed by (hosts, hours, seed, regions)."""
    full_env = {
        **os.environ, "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache"), **env,
    }
    return subprocess.run(
        [sys.executable, SMOKE, *args], cwd=tmp_path, env=full_env,
        capture_output=True, text=True, timeout=600,
    )


def test_rehearsal_passes_and_reports_the_cpu(tmp_path):
    p = _smoke(["--rehearse", *TINY], tmp_path)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    events = [json.loads(l) for l in lines[:-1]]
    queries = {e["query"]: e for e in events if "query" in e}
    assert set(queries) == {
        "single-groupby-1-1-1", "double-groupby-1", "high-cpu-1", "lastpoint",
        "groupby-orderby-limit", "edge-lastpoint", "edge-orderby-limit",
        "rate", "increase-1", "rate-logical",
    }
    # the logical table of twelve labels answers for one rack's hosts (of
    # ten: hosts 1 and 6) as the mito table does for them
    assert queries["rate-logical"]["rows_out"] * 5 == queries["rate"]["rows_out"]
    for e in queries.values():
        assert len(e["warm_ms"]) == 3 and all(d >= 1 for d in e["dispatches"])
    # the values the generator never draws were compared exactly
    assert queries["edge-lastpoint"]["rtol"] == 0.0
    assert {"event": "pack_f64_bits", "values": 12, "bit_exact": True} in events


def test_device_path_that_cannot_engage_fails(tmp_path):
    """query.backend=cpu through the existing env configuration: every
    answer is still right (the CPU executor gives it), and the smoke must
    fail because no dispatch reached the device."""
    p = _smoke(
        ["--rehearse", *TINY], tmp_path, GREPTIMEDB_TPU__QUERY__BACKEND="cpu"
    )
    assert p.returncode != 0
    assert "did not advance" in p.stderr
    assert '"ok"' not in p.stdout


def test_refuses_to_start_without_an_accelerator(tmp_path):
    p = _smoke(TINY, tmp_path)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert p.stdout.strip() == ""


@pytest.fixture
def fresh_cache_config(monkeypatch):
    import jax

    from greptimedb_tpu.utils import jax_env

    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(jax_env, "_cache_done", False)
    yield jax, jax_env
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_placed_from_outside(fresh_cache_config, monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the program sets no directory."""
    jax, jax_env = fresh_cache_config
    outside = str(tmp_path / "placed_outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    jax.config.update("jax_compilation_cache_dir", outside)  # what jax reads at import
    jax_env.ensure_compilation_cache()
    assert jax.config.jax_compilation_cache_dir == outside
    assert not os.path.exists(outside)  # nor does the program create it


def test_compile_cache_defaults_into_the_checkout(fresh_cache_config, monkeypatch):
    jax, jax_env = fresh_cache_config
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax_env.ensure_compilation_cache()
    assert jax.config.jax_compilation_cache_dir == os.path.join(REPO, ".jax_cache")
    assert jax_env.DEFAULT_COMPILATION_CACHE_DIR == os.path.join(REPO, ".jax_cache")
