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

    def rate(sid, ts, v, valid, bounds, start, step, range_):
        stats, present = range_windows_dyn(
            sid, ts, v + 1.0, valid, bounds, start=start, step=step,
            range_=range_, n_steps=128, k=8, raw_values=v,
        )
        return extrapolated_rate_dyn(stats, start, step, range_, 128, "rate"), present

    scalar = _rows(one_chip, jnp.int64, ())
    _compile(
        "range_windows_dyn + extrapolated_rate_dyn, 2^20 rows", rate,
        _rows(one_chip, jnp.int32), _rows(one_chip, jnp.int64),
        _rows(one_chip, jnp.float64), _rows(one_chip, jnp.bool_),
        _rows(one_chip, jnp.int32, (4097,)), scalar, scalar, scalar,
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
    """The series ordinals of a 2^24-row plane from its 1024 series' row
    bounds (one scatter of the marks, one int32 prefix scan) and the fold
    of a [1024 x 16] state to the 4096 hosts' codes: `lastpoint` on a
    region of `tsbs-mesh4-heavy`."""
    from greptimedb_tpu.ops.aggregate import AggState, fold_series_states, series_rows

    def lead(marks, group, sums, last_ts):
        ordinal = series_rows(marks, 1 << 24)
        state = fold_series_states(AggState(sums=sums, last_ts=last_ts), group, 4096, 16)
        return ordinal, state.sums, state.last_ts

    secs = _compile(
        "series_rows + fold_series_states, 2^24 rows", lead,
        _rows(one_chip, jnp.int32, (1024,)), _rows(one_chip, jnp.int32, (1024,)),
        _rows(one_chip, jnp.float64, (1024 * 16,)), _rows(one_chip, jnp.int64, (1024 * 16,)),
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
    a matcher's bool, a group id and the row bounds per series as dynamic
    inputs."""
    from greptimedb_tpu.query.promql import tile_exec

    rows = 1 << 22
    csig = ("rate", agg, 4096, 32, 8, 1_000_000, 16 if agg else None, True)

    def planes(dtype):
        return tuple(_rows(one_chip, dtype, (rows,)) for _ in range(chunks))

    at = {k: _rows(one_chip, jnp.int32, ()) for k in ("sid0", "off", "lo", "hi")}
    at["bounds"] = _rows(one_chip, jnp.int32, (4096 + 1,))
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


def test_million_series_panel_program_compiles_and_fits(one_chip):
    """The TQL tile program of `prom-1m-series-rules`' panel: a logical table
    of 2^20 series and 2^24 rows in four 2^22-row chunks, 6 steps padded to
    8, a `sum by` fold into 16 groups.  What it holds beside its inputs, by
    the compiler's memory analysis (the number `tile_exec._program_bytes`
    reads before a dispatch), is 1.90 GB with the window search's series on
    the minor axis and a cell read at two positions of carried planes
    (2.17 GB while it gathered five statistics at its first and last row;
    5.34 GB with the 8 steps on the minor axis, padded to 128 lanes)."""
    from greptimedb_tpu.query.promql import tile_exec

    chunk, s_pad, w_pad, g_pad = 1 << 22, 1 << 20, 8, 16
    csig = ("rate", "sum", s_pad, w_pad, 8, 1_000_000, g_pad, False)

    def planes(dtype):
        return tuple(_rows(one_chip, dtype, (chunk,)) for _ in range(4))

    at = {k: _rows(one_chip, jnp.int32, ()) for k in ("sid0", "off", "lo", "hi")}
    at["bounds"] = _rows(one_chip, jnp.int32, (s_pad + 1,))
    src = ((planes(jnp.int32),), planes(jnp.int64), planes(jnp.float64), None,
           planes(jnp.bool_), at)
    dyn = {
        k: _rows(one_chip, jnp.int64, ())
        for k in ("lo", "hi", "offset", "start", "step", "range", "nsteps")
    }
    dyn["gid"] = _rows(one_chip, jnp.int32, (s_pad,))
    program = tile_exec._full_program(
        (csig, ((tile_exec._source_sig(src), (4 * chunk, 0, chunk)),))
    )
    t0 = time.perf_counter()
    analysis = program.lower((src,), dyn).compile().memory_analysis()
    secs = time.perf_counter() - t0
    held = analysis.temp_size_in_bytes + analysis.output_size_in_bytes
    print(f"million-series panel program: compiled for v5e in {secs:.1f} s, holds {held} B")
    assert secs < RESET_STRIP_CEILING_S
    assert held < 3 << 30


def test_series_plan_program_compiles_and_fits(one_chip):
    """Stage 1 of a series plan as `tsbs-tags10-double-groupby` runs its
    `double-groupby-all`: a 2^24-row chunk, the row's series from 4,096
    row bounds (`series_rows`), ten limb columns by (series, hour), and
    the `series_fold` to 4,096 hosts x 16 hours."""
    from greptimedb_tpu.ops.aggregate import quantize_limbs
    from greptimedb_tpu.parallel.executor import (
        SERIES_GROUP,
        SERIES_MARKS,
        DistGroupByPlan,
        compute_partial_states,
    )

    n, fields = 1 << 24, [f"f{i}" for i in range(10)]
    plan = DistGroupByPlan(
        group_tags=("hostname",), tag_cards=(4096,), bucket_col="ts", bucket_origin=0,
        bucket_interval=3_600_000, n_buckets=16, agg_specs=tuple(("avg", f) for f in fields),
        filters=(("ts", ">=", None), ("ts", "<", None)), acc_dtype="limb", block_span=32,
        series_slots=4096,
    )
    limb = jax.eval_shape(quantize_limbs, jax.ShapeDtypeStruct((n,), jnp.float64))
    limbs = {
        f: jax.tree_util.tree_map(lambda s: _rows(one_chip, s.dtype, s.shape), limb)
        for f in fields
    }
    cols = {
        "ts": _rows(one_chip, jnp.int64, (n,)),
        SERIES_MARKS: _rows(one_chip, jnp.int32, (4096,)),
        SERIES_GROUP: _rows(one_chip, jnp.int32, (4096,)),
    }
    scalar = _rows(one_chip, jnp.int64, ())
    dyn = {"filter_values": (scalar, scalar), "bucket_origin": scalar, "bucket_interval": scalar}

    def partial(cols, valid, dyn, limbs):
        return compute_partial_states(plan, cols, valid, {}, dyn, limbs=limbs, count_cols=())

    t0 = time.perf_counter()
    compiled = jax.jit(partial).lower(
        cols, _rows(one_chip, jnp.bool_, (n,)), dyn, limbs
    ).compile()
    secs = time.perf_counter() - t0
    print(f"series plan partial, 2^24 rows x 10 limb columns: compiled for v5e in {secs:.1f} s")
    assert secs < RESET_STRIP_CEILING_S
    mem = compiled.memory_analysis()
    # the chunk's planes and the program's transients inside a 16 GB chip
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 4 << 30


@pytest.mark.parametrize("acc", ["limb", "float64"])
def test_series_plan_whole_program_compiles_inside_the_dispatch_deadline(one_chip, acc):
    """`double-groupby-all`'s tile program as `tsbs-tags10-double-groupby`
    runs it: the finalize of the limb pass (its partial is the test above)
    and the whole exact-f64 rerun that a failed limb verdict dispatches.  A
    first dispatch compiles inside the device supervisor's deadline
    (`device.call_timeout_s`, 30 s by default).  The finalize once took
    200 s here: its ten float32 rows of 57,344 groups were bitcast to bytes
    for the flat result buffer (to 32-bit words now)."""
    from greptimedb_tpu.parallel.executor import SERIES_GROUP, SERIES_MARKS, DistGroupByPlan
    from greptimedb_tpu.parallel.tile_cache import _tile_program
    from greptimedb_tpu.utils.config import DeviceConfig

    n, fields = 1 << 24, [f"f{i}" for i in range(10)]
    plan = DistGroupByPlan(
        group_tags=("hostname",), tag_cards=(4096,), bucket_col="ts", bucket_origin=0,
        bucket_interval=1, n_buckets=14, agg_specs=tuple(("avg", f) for f in fields),
        filters=(("ts", ">=", None), ("ts", "<", None)), acc_dtype=acc, block_span=32,
        series_slots=4096,
    )
    program, *_layouts = _tile_program(plan, (), None)
    cols = {
        "ts": _rows(one_chip, jnp.int64, (n,)),
        SERIES_MARKS: _rows(one_chip, jnp.int32, (4096,)),
        SERIES_GROUP: _rows(one_chip, jnp.int32, (4096,)),
        **{f: _rows(one_chip, jnp.float64, (n,)) for f in fields},
    }
    scalar = _rows(one_chip, jnp.int64, ())
    dyn = {"filter_values": (scalar, scalar), "bucket_origin": scalar, "bucket_interval": scalar}
    args = (cols, _rows(one_chip, jnp.bool_, (n,)), {}, dyn, None)
    t0 = time.perf_counter()
    if acc == "float64":
        states = program._partial_jit.lower(*args).compile().out_info
    else:  # the limb planes quantize in-program here: the shapes alone
        states = jax.tree_util.tree_map(
            lambda s: _rows(one_chip, s.dtype, s.shape), jax.eval_shape(program._partial_jit, *args)
        )
    t1 = time.perf_counter()
    program._final_jit.lower(states, _rows(one_chip, jnp.float64, (1,)), None).compile()
    t2 = time.perf_counter()
    print(f"series plan program ({acc}): partial {t1 - t0:.1f} s, finalize {t2 - t1:.1f} s for v5e")
    assert t2 - t0 < DeviceConfig().call_timeout_s
