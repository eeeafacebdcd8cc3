"""The main path's kernels compile for a v5e chip that is described, not
attached (the TPU's compiler is installed with jax; nothing runs).

These guard what a CPU run cannot see: `pack_f64_bits` once lowered through
a 64-bit bitcast the chip's compiler rejects, and the PromQL reset strip
took minutes to compile (an f64 cumsum and 1-D associative scans).  Each
test asserts that its program compiles and prints the seconds; a pass is a
compile, never a chip run.

One file on purpose: only one process may hold the TPU library, so the
topology is described inside a module-scoped fixture (never at import) and
every test of it stays with one xdist worker.
"""

import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu / library held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip executable is written to the persistent cache but
    # cannot be read back without a chip: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(name, fn, *specs) -> float:
    t0 = time.perf_counter()
    jax.jit(fn).lower(*specs).compile()
    secs = time.perf_counter() - t0
    print(f"{name}: compiled for v5e in {secs:.1f} s")
    return secs


def test_pack_f64_bits_compiles(one_chip):
    from greptimedb_tpu.ops.aggregate import pack_f64_bits

    x = jax.ShapeDtypeStruct((4, 96000), jnp.float64, sharding=one_chip)
    _compile("pack_f64_bits [4, 96000]", pack_f64_bits, x)


# A repaired strip needs seconds; the f64-cumsum strip took 218 s for the
# rate program at this size.  Loose on purpose: the sandbox's cores are
# shared with five other test workers.
RESET_STRIP_CEILING_S = 120.0
ROWS = 1 << 20


def _rows(one_chip, dtype, shape=(ROWS,)):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_reset_strip_compiles(one_chip):
    from greptimedb_tpu.ops.rate import strip_counter_resets_segmented

    secs = _compile(
        "strip_counter_resets_segmented, 2^20 rows",
        strip_counter_resets_segmented,
        _rows(one_chip, jnp.int32), _rows(one_chip, jnp.float64),
        _rows(one_chip, jnp.bool_),
    )
    assert secs < RESET_STRIP_CEILING_S


def test_rate_windows_compile(one_chip):
    """range_windows_dyn + extrapolated_rate_dyn at the smoke's grid:
    4000 series -> 4096, 110 steps -> 128, 5 windows per sample -> 8;
    the raw plane rides along for the clamp's `first_raw`, as in the tile
    program (the strip itself compiles in the test above)."""
    from greptimedb_tpu.ops.rate import extrapolated_rate_dyn, range_windows_dyn

    def rate(sid, ts, v, valid, start, step, range_):
        stats = range_windows_dyn(
            sid, ts, v + 1.0, valid, start=start, step=step, range_=range_,
            n_steps=128, k=8, num_series=4096, raw_values=v,
        )
        return extrapolated_rate_dyn(stats, start, step, range_, 128, "rate")

    scalar = _rows(one_chip, jnp.int64, ())
    _compile(
        "range_windows_dyn + extrapolated_rate_dyn, 2^20 rows", rate,
        _rows(one_chip, jnp.int32), _rows(one_chip, jnp.int64),
        _rows(one_chip, jnp.float64), _rows(one_chip, jnp.bool_),
        scalar, scalar, scalar,
    )


def test_segment_aggregate_compiles(one_chip):
    """sum/count/min/max with f64 accumulators, 2^22 rows x 96,000 groups
    (TSBS double-groupby: 4000 hosts x 24 hourly buckets)."""
    from greptimedb_tpu.ops.aggregate import segment_aggregate

    n, groups = 1 << 22, 96_000
    values = jax.ShapeDtypeStruct((n,), jnp.float64, sharding=one_chip)
    gids = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip)

    def agg(values, gids, mask):
        state = segment_aggregate(
            values, gids, groups, ("sum", "count", "min", "max"), mask=mask,
            acc_dtype=jnp.float64,
        )
        return state.sums, state.counts, state.mins, state.maxs

    _compile("segment_aggregate 2^22 x 96000", agg, values, gids, mask)


def test_series_ordinals_compile(one_chip):
    """The ordinals of a 2^24-row plane (one int32 prefix scan, a search of
    4096 keys, a 4096-element scatter) and the carry of a [4096 x 16] state
    back to codes: `lastpoint` on a region of `tsbs-mesh4-heavy`."""
    from greptimedb_tpu.ops.aggregate import (
        AggState,
        ordinal_states_to_codes,
        series_ordinals,
    )

    def lead(codes, valid, sums, last_ts):
        ordinal, slot_of_code, ok = series_ordinals(codes, valid, 4096)
        state = ordinal_states_to_codes(
            AggState(sums=sums, last_ts=last_ts), slot_of_code, ok
        )
        return ordinal, state.sums, state.last_ts

    secs = _compile(
        "series_ordinals + ordinal_states_to_codes, 2^24 rows", lead,
        _rows(one_chip, jnp.int32, (1 << 24,)), _rows(one_chip, jnp.bool_, (1 << 24,)),
        _rows(one_chip, jnp.float64, (4096 * 16,)), _rows(one_chip, jnp.int64, (4096 * 16,)),
    )
    assert secs < RESET_STRIP_CEILING_S


@pytest.mark.parametrize("chunks,cut,agg", [
    (1, (1 << 21, 0, 1 << 21), "sum"),          # inside one chunk, with the by fold
    (2, (1 << 21, (1 << 22) - (1 << 21), 1 << 21), None),  # across a chunk boundary
])
def test_logical_table_program_compiles(one_chip, chunks, cut, agg):
    """The whole TQL tile program over a metric-engine logical table, as
    `prom-metric-engine-range` runs it: a 2^21-row slice cut on the device
    out of 2^22-row chunks at a dynamic offset, series by `run_ordinals`,
    a matcher's bool and a group id per series as dynamic inputs."""
    from greptimedb_tpu.query.promql import tile_exec

    rows = 1 << 22
    csig = ("rate", agg, 4096, 32, 8, 1_000_000, 16 if agg else None, True)

    def planes(dtype):
        return tuple(_rows(one_chip, dtype, (rows,)) for _ in range(chunks))

    at = {k: _rows(one_chip, jnp.int32, ()) for k in ("sid0", "off", "lo", "hi")}
    src = ((planes(jnp.int32),), planes(jnp.int64), planes(jnp.float64), None,
           planes(jnp.bool_), at)
    dyn = {
        k: _rows(one_chip, jnp.int64, ())
        for k in ("lo", "hi", "offset", "start", "step", "range", "nsteps")
    }
    dyn["sel"] = _rows(one_chip, jnp.bool_, (4096,))
    if agg:
        dyn["gid"] = _rows(one_chip, jnp.int32, (4096,))
    program = tile_exec._full_program((csig, ((tile_exec._source_sig(src), cut),)))
    t0 = time.perf_counter()
    program.lower((src,), dyn).compile()
    secs = time.perf_counter() - t0
    print(f"logical-table program, {chunks} chunk(s): compiled for v5e in {secs:.1f} s")
    assert secs < RESET_STRIP_CEILING_S
