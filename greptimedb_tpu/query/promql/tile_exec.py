"""Warm TQL hot path: PromQL range-vector evaluation on the device tile
cache.

Role-equivalent of running the reference's PromQL extension operators
(range_manipulate.rs building the range-vector matrix,
extrapolate_rate.rs implementing Prometheus' extrapolatedRate) INSIDE the
storage engine's hot path instead of over a fresh scan: the legacy
`PromqlEngine._fetch` re-scans the region, re-uploads the samples and
aggregates the rate matrix host-side on EVERY query — exactly the
repeated-sliding-window pattern the SQL tile path already made cheap.

Routing ladder (the `tql_tile` optimizer pass, off-switch `tql.tile`):

  warm    every region's super-tile planes (tag codes, ts, value, nulls,
          dedup keep) are device-resident -> ONE compiled dispatch fuses
          counter-reset stripping + window statistics + extrapolated
          rate / *_over_time + the by-label sum/avg/min/max/count
          aggregation, and the readback ships the compacted
          [series_out, steps] result (never raw samples);
  cold    the query answers from the legacy scan path immediately and
          schedules its family's plane build on the shared fused-build
          worker (`tile.fused_build`, build coalescing included) so the
          NEXT query is warm; with fused builds off the planes build
          synchronously like the pre-fused SQL ladder;
  legacy  any ineligibility (memtable rows in the window, tombstones,
          unsupported matcher target, series*steps cell bound) or ANY
          tile-path failure — fault point `tql.tile`,
          `greptime_tql_tile_degraded_total` — falls back to the
          upload-per-query path, bit-for-bit `tql.tile = false` behavior.

Compiled programs are cached per SHAPE BUCKET (padded series space,
padded step count, padded windows-per-sample, padded group space, chunk
geometry), with the evaluation grid (start/step/range), time bounds and
matcher literals as dynamic inputs — the literal-insensitive `_plan_fp`
discipline — so a dashboard sliding its window re-hits the compile cache
with zero host->device plane traffic.

Series and labels.  A row's series id is the ORDINAL of its run of equal
key codes in the (pk, ts)-sorted planes (`ops/aggregate.run_ordinals`), so
the series space is the series that exist whatever the key's width.  What
a request needs of the labels it reads off the region's `SeriesTable`
(`parallel/tile_cache.py`: run starts and codes per key column, made once
per plane build): matchers fold to one bool a series, a `by` aggregation
to one group id a series, both dynamic inputs; the answer's label columns
come from the same table.

Logical tables.  A metric-engine logical table (Prometheus remote write,
OTLP metrics) has no planes of its own: its rows are ONE RANGE of its
physical region's planes (`__table_id` leads the key, `__tsid` follows;
both ride the planes as dictionary codes like any tag).  The program is
handed a padded power-of-two slice of the chunks that range touches
(`_slice_plan`, `_logical_slice`: static size, dynamic offset — tables of
about one size share a program) and compares `__tsid` alone; its work
grows with the table's rows, not the region's.

Parity contract (tests/test_tql_tile.py): per-series delta/*_over_time
values, instant vectors, matcher filtering and the by-label folds are
BIT-identical to the legacy path on single-region tables (same kernels,
same sample sequence, same f64 op order — the device segment fold and
the host np.add.at fold visit series in the same dictionary-code
order).  Two documented ulp-level exceptions: (1) rate/increase over
series WITH counter resets — the reset strip's prefix scan lowers to an
XLA tree scan whose association depends on the array length, and the
tile plane's padded length differs from the legacy scan's dense length;
(2) multi-region float sums — the legacy fold visits series in
region-appearance order.  Both are last-ulp only (the sqlness
renderer's 6-significant-digit format never sees them) and covered by
tight-tolerance assertions.  1-device and N-device (mesh) execution are
bit-identical by construction: regions are series-disjoint, so the
stats merge is pure selection (ops/rate.merge_disjoint_stats).
"""

from __future__ import annotations

import dataclasses
import logging
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.aggregate import run_ordinals
from ...ops.rate import (
    WindowStats,
    extrapolated_rate_dyn,
    merge_disjoint_stats,
    over_time,
    range_windows_dyn,
    reductions_for,
    series_present,
    strip_counter_resets_segmented,
)
from ...utils import flight_recorder, metrics
from ...utils import tracing
from ...utils.errors import QueryTimeoutError, UnsupportedError
from ...utils.fault_injection import fire as _fault_fire
from .. import passes
from ..logical_plan import TableScan

log = logging.getLogger("greptimedb_tpu.tql")

_RATE_KINDS = ("rate", "increase", "delta")
_AGG_OPS = ("sum", "avg", "mean", "min", "max", "count")


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


# ---- compiled program cache (process-wide: PromqlEngine is per-query) ------

_PROGRAMS: dict = {}
_PROGRAMS_LOCK = threading.Lock()
_PROGRAMS_MAX = 128


def _cached_program(sig, build):
    with _PROGRAMS_LOCK:
        fn = _PROGRAMS.get(sig)
    if fn is not None:
        return fn
    fn = build()
    with _PROGRAMS_LOCK:
        if len(_PROGRAMS) >= _PROGRAMS_MAX:
            _PROGRAMS.pop(next(iter(_PROGRAMS)))
        _PROGRAMS.setdefault(sig, fn)
        return _PROGRAMS[sig]


class _Ineligible(Exception):
    """Query/table shape the tile path does not express: degrade silently
    to the legacy scan path — never an error."""


class LegacyFallbackDisabled(UnsupportedError):
    """`tql.legacy_fallback = false` and the statement has no answer from
    the device: the message names what sent it towards the legacy scan."""

    def __init__(self, reason: str):
        super().__init__(
            f"tql.legacy_fallback = false: no answer from the device tiles ({reason})"
        )


# `try_range_eval`'s answer at a family's first touch: the planes are
# being built in the background and this one evaluation is the legacy
# scan's by design, whatever `tql.legacy_fallback` says
COLD_SERVE = object()


def _logical_slice(chunks, cut, off):
    """Rows [off, off + L) of the planes that `chunks` lay end to end: the
    rows of one logical table (and the neighbours its padded size reaches),
    cut out of the region's planes on the device.  `cut` = (L, head, tail)
    is static: of several chunks the first is entered `head` rows in and
    the last `tail` rows deep, so what is copied is at most 2 L rows plus
    the chunks in between, never the region; `off` is dynamic and counts
    from that entry point, so another logical table of the same padded
    size runs the same program."""
    size, head, tail = cut
    with jax.named_scope("logical_slice"):
        if len(chunks) == 1:
            plane = chunks[0]
        else:
            plane = jnp.concatenate(
                [chunks[0][head:], *chunks[1:-1], chunks[-1][:tail]]
            )
        return jax.lax.dynamic_slice(plane, (off,), (size,))


def _region_stats(src, dyn, rsig, csig):
    """Traced per-region pipeline: planes -> per-(series, window) stats +
    per-series presence.  `src` = (key_chunks..., ts_chunks, val_chunks,
    null_chunks|None, valid_chunks, rows): the planes and, dynamic, where
    the source's rows and series lie in them; shapes and the static cut of
    a logical table come from `rsig`, query structure from `csig`."""
    (key_chunks, ts_chunks, val_chunks, null_chunks, valid_chunks, at) = src
    (func, _agg, s_pad, w_pad, k, unit_ns, _g_pad, matched) = csig
    _planes, cut = rsig

    def cat(chunks):
        if cut is not None:
            return _logical_slice(chunks, cut, at["off"])
        return chunks[0] if len(chunks) == 1 else jnp.concatenate(list(chunks))

    keys = [cat(c) for c in key_chunks]
    ts_nat = cat(ts_chunks)
    valid = cat(valid_chunks)
    vf = cat(val_chunks).astype(jnp.float64)
    if null_chunks is not None:
        vf = jnp.where(cat(null_chunks), vf, jnp.nan)

    # the source's own rows [lo, hi) of what was cut (a mito table: all of
    # it), and fetch-range membership in the column's NATIVE unit — the
    # exact region-scan bound semantics ([lo, hi) exclusive upper)
    rows = jnp.arange(ts_nat.shape[0], dtype=jnp.int32)
    in_fetch = (
        valid & (rows >= at["lo"]) & (rows < at["hi"])
        & (ts_nat >= dyn["lo"]) & (ts_nat < dyn["hi"])
    )
    for c in keys:
        in_fetch = in_fetch & (c >= 0)

    # series id = the ordinal of the row's run of equal key codes among the
    # source's rows, from the source's first series on: the planes are
    # (pk codes..., ts) sorted, so a series is one run and the rows are in
    # (series, ts) order — contiguity is what the reset scan needs, the
    # order what `range_windows_dyn` searches (its docstring has the
    # precondition and why these planes meet it).  The ordinal costs the
    # same whatever the key's width or its tags' cardinalities, and the
    # series space is as large as the series that exist.
    if keys:
        sid = at["sid0"] + run_ordinals(keys, at["lo"], at["hi"])
    else:
        sid = jnp.zeros(ts_nat.shape, jnp.int32)
    sid = jnp.clip(sid, 0, s_pad - 1)
    # label matchers: one bool a series, folded on the host from the
    # per-series label table, read here by the row's series (a gather over
    # the rows: left out where every series passes)
    if matched:
        with jax.named_scope("label_gids"):
            in_fetch = in_fetch & jnp.take(dyn["sel"], sid)

    # native -> ms exactly like the legacy fetch (truncating div), then
    # the offset modifier shift
    ts_ms = ts_nat * unit_ns // 1_000_000 + dyn["offset"]

    # counters: the reset-adjusted samples carry the increase, the raw
    # ones the zero point of Prometheus' clamp (WindowStats.first_raw)
    raw = vf if func in ("rate", "increase") else None
    if raw is not None:
        vf = strip_counter_resets_segmented(sid, raw, in_fetch)
    stats = range_windows_dyn(
        sid, ts_ms, vf, in_fetch,
        start=dyn["start"], step=dyn["step"], range_=dyn["range"],
        n_steps=w_pad, k=k, num_series=s_pad,
        n_steps_actual=dyn["nsteps"], raw_values=raw,
        reduce=reductions_for(func),
    )
    # scan-presence per series (a scanned series with no windowed sample
    # still occupies a matrix row in the legacy path — `absent()` and
    # binary ops see it)
    presence = series_present(sid, in_fetch, s_pad)
    return stats, presence


def _finalize(stats: WindowStats, dyn, csig):
    """Traced tail: window stats -> [S, W] matrix (NaN = undefined) and,
    when an aggregation is fused, the grouped [G, W] matrix using the
    exact host formulas from PromqlEngine._eval_aggregate."""
    (func, agg, s_pad, w_pad, _k, _unit, g_pad, _matched) = csig
    if func in _RATE_KINDS:
        vals, defined = extrapolated_rate_dyn(
            stats, dyn["start"], dyn["step"], dyn["range"], w_pad, func
        )
    elif func == "__last_ts":
        vals, defined = stats.last_ts / 1000.0, stats.count >= 1
    else:
        vals, defined = over_time(stats, func)
    vals = jnp.where(defined, vals.astype(jnp.float64), jnp.nan)
    mat = vals.reshape(s_pad, w_pad)
    if agg is None:
        return mat
    with jax.named_scope("by_fold"):
        op = agg
        # the series -> group map comes with the request: the host reads it
        # off the per-series label table (made once a plane build), so the
        # group space is the groups that exist
        with jax.named_scope("label_gids"):
            gid = dyn["gid"]
        present = ~jnp.isnan(mat)
        zeroed = jnp.where(present, mat, 0.0)
        sums = jax.ops.segment_sum(zeroed, gid, num_segments=g_pad)
        counts = jax.ops.segment_sum(
            present.astype(jnp.float64), gid, num_segments=g_pad
        )
        if op == "sum":
            out = jnp.where(counts > 0, sums, jnp.nan)
        elif op in ("avg", "mean"):
            out = jnp.where(counts > 0, sums / jnp.maximum(counts, 1), jnp.nan)
        elif op == "count":
            out = jnp.where(counts > 0, counts, jnp.nan)
        else:  # min / max
            fill = jnp.inf if op == "min" else -jnp.inf
            filled = jnp.where(present, mat, fill)
            seg = jax.ops.segment_min if op == "min" else jax.ops.segment_max
            ext = seg(filled, gid, num_segments=g_pad)
            out = jnp.where(counts > 0, ext, jnp.nan)
    return out


def _full_program(sig):
    """One jit over every region's sources: per-region stats, disjoint
    merge in region order, finalize — the single-dispatch warm path."""
    csig, region_sigs = sig

    def build():
        def fn(sources, dyn):
            stats = None
            pres = []
            for src, rsig in zip(sources, region_sigs):
                st, p = _region_stats(src, dyn, rsig, csig)
                pres.append(p)
                stats = st if stats is None else merge_disjoint_stats(stats, st)
            return _finalize(stats, dyn, csig), tuple(pres)

        return jax.jit(fn)

    return _cached_program(("full", sig), build)


def _partial_program(sig):
    """Per-region stats program for the mesh path (dispatched on the
    region's co-located device)."""
    csig, rsig = sig

    def build():
        def fn(src, dyn):
            st, p = _region_stats(src, dyn, rsig, csig)
            # field order, which `_merge_program` rebuilds from
            return tuple(
                getattr(st, f.name) for f in dataclasses.fields(st)
            ), p

        return jax.jit(fn)

    return _cached_program(("partial", sig), build)


def _merge_program(sig):
    """Mesh fan-in: merge the per-region stats tuples (moved to device 0)
    in region order and finalize — same fold, same ops as the one-jit
    path, so 1-device and N-device results are bit-identical."""
    csig, n_regions = sig

    def build():
        def fn(stats_tuples, dyn):
            stats = None
            for t in stats_tuples:
                st = WindowStats(*t)
                stats = st if stats is None else merge_disjoint_stats(stats, st)
            return _finalize(stats, dyn, csig)

        return jax.jit(fn)

    return _cached_program(("merge", sig), build)


class TqlTileExecutor:
    """Routes one range-function evaluation through the device tile
    cache.  Constructed per PromqlEngine (cheap); compiled programs and
    fused-build family state live process-wide."""

    def __init__(self, db):
        self.db = db
        self.cache = db.query_engine.tile_cache
        self.executor = db.query_engine._tile_executor

    # ---- public entry ------------------------------------------------------
    def try_range_eval(self, func, sel, range_ms, start, end, step, agg=None):
        """Evaluate `func` over sel[range_ms] on the eval grid
        (start..end@step, all ms) from device tiles; `agg` fuses a
        by-label aggregation: (op, by_labels|None, without_labels|None).
        Returns an engine Matrix; COLD_SERVE at a family's first touch,
        or None (the path is off, or the shape ineligible: reason recorded
        on the `tql_tile` pass trace), both for the legacy path to answer;
        under `tql.legacy_fallback = false` an ineligible shape raises
        `LegacyFallbackDisabled` instead."""
        cfg = getattr(self.db, "config", None)
        tql_cfg = getattr(cfg, "tql", None)
        if tql_cfg is None or not tql_cfg.tile:
            return None
        if not passes.enabled("tql_tile", getattr(cfg, "query", None)):
            passes.note("tql_tile", False, "pass disabled: legacy scan path")
            return None
        from ...parallel.tile_cache import _in_fused_build

        try:
            _fault_fire("tql.tile", table=sel.metric, func=func)
            cache = self.cache
            # db-qualified key, matching the SQL tile path's
            # ctx.table_key so device_dispatches.table_name filters see
            # both strategies for one table
            table_key = f"{self.db.current_database}.{sel.metric}"
            with flight_recorder.dispatch_scope(
                table=table_key, strategy="tql",
                ghost=_in_fused_build(),
                hbm=(
                    (lambda: (cache._used, cache.budget))
                    if cache is not None else None
                ),
            ):
                # self time = the host work around the dispatch: catalog,
                # matcher masks, grid, plane residency (the dispatch, the
                # fetch and the assembly are counted stages inside it)
                with tracing.stage("tql.plan", func=func) as plan:
                    try:
                        return self._attempt(
                            func, sel, range_ms, start, end, step, agg, plan
                        )
                    except _Ineligible as ie:
                        plan.set(ineligible=str(ie))
                        raise
        except QueryTimeoutError:
            raise  # the deadline owns the query, tile or not
        except _Ineligible as ie:
            if not _in_fused_build():
                metrics.TQL_TILE_INELIGIBLE.inc()
            passes.note("tql_tile", False, f"{ie}: legacy scan path")
            self._no_legacy(f"ineligible: {ie}")
            return None
        except Exception as exc:  # noqa: BLE001 — degrade, never fail
            metrics.TQL_TILE_DEGRADED.inc()
            tracing.add_event(
                "tql.tile_degraded", table=sel.metric,
                error=type(exc).__name__,
            )
            log.warning(
                "tql tile path failed; degrading to the legacy scan: %s",
                exc, exc_info=True,
            )
            passes.note(
                "tql_tile", False,
                f"tile-path failure ({type(exc).__name__}): degraded to "
                "the legacy scan path",
            )
            self._no_legacy(f"tile-path failure: {exc!r}")
            return None

    def _no_legacy(self, reason: str):
        """`tql.legacy_fallback = false`: what would now be answered from
        the legacy scan fails instead, naming why (the builder's own ghost
        run answers nobody and has nothing to refuse)."""
        from ...parallel.tile_cache import _in_fused_build

        if not self.db.config.tql.legacy_fallback and not _in_fused_build():
            raise LegacyFallbackDisabled(reason)

    # ---- attempt -----------------------------------------------------------
    def _attempt(self, func, sel, range_ms, start, end, step, agg, plan):
        db = self.db
        meta = db.catalog.table(sel.metric, db.current_database)
        schema = meta.schema
        if schema.time_index is None:
            raise _Ineligible("metric table has no time index")
        tags = [c.name for c in schema.tag_columns()]

        steps = np.arange(start, end + 1, step, dtype=np.int64)
        w = len(steps)
        if w == 0:
            raise _Ineligible("empty evaluation grid")

        # matcher split — the legacy `_fetch` semantics, replicated on
        # dictionary-code masks (dynamic inputs: literal changes never
        # recompile)
        eq_matchers, regex_matchers = [], []
        for mt in sel.matchers:
            if mt.label not in tags:
                if mt.op in ("=", "=~"):
                    # legacy: equality on a non-existent label matches no
                    # series at all
                    return _empty_matrix(tags, agg, steps)
                continue  # != / !~ on a missing label: matches everything
            (eq_matchers if mt.op in ("=", "!=") else regex_matchers).append(mt)

        scan = TableScan(table=sel.metric, database=db.current_database)
        ctx = db._tile_context(scan, logical=True)
        if ctx is None:
            raise _Ineligible("table source cannot tile")
        if not ctx.regions:
            raise _Ineligible("no regions")
        if any(
            getattr(r, "merge_mode", "last_row") == "last_non_null"
            for r in ctx.regions
        ) and not ctx.append_mode:
            raise _Ineligible("last_non_null merge mode")
        src = self._source(ctx, meta, tags)
        if src.table_id is not None:
            plan.set(logical_table=sel.metric, table_id=src.table_id)
            if self.cache.mesh_devices() > 0:
                raise _Ineligible("logical table under tile.mesh_devices")
        ts_name, value_col = src.ts, src.value

        # fetch bounds: scan time_range semantics in the native unit
        unit_ns = src.schema.time_index.data_type.timestamp_unit_ns()
        offset = sel.offset_ms
        t_lo = start - range_ms
        lo_nat = (t_lo - offset) * 1_000_000 // unit_ns
        hi_nat = (end - offset) * 1_000_000 // unit_ns + 1

        from ...parallel.tile_cache import _in_fused_build

        fused = self.executor is not None and self.executor._fused_enabled()
        fp = self._family_fp(ctx, value_col, func, agg, eq_matchers,
                             regex_matchers)
        if fused and not _in_fused_build():
            # a family whose background build is in flight waits and
            # adopts the leader's planes instead of host-serving again —
            # but the builder's own ghost execution must not join (and
            # deadlock on) the very build it is running
            self.executor._fused_join(fp)

        dictionary = ctx.dictionary
        pinned = []
        with dictionary.table_lock:
            try:
                sources_meta = self._acquire_regions(
                    ctx, lo_nat, hi_nat, ts_name, pinned
                )
                warm = all(self._warm_entry(s, src) for s in sources_meta)
                if not warm:
                    if (
                        fused
                        and not _in_fused_build()
                        and self.executor.fused_first_touch_fp(fp)
                    ):
                        # FIRST touch of the family: answer from the
                        # legacy scan now, build in the background
                        self._schedule_build(
                            fp, ctx, src, sources_meta,
                            func, sel, range_ms, start, end, step, agg,
                        )
                        metrics.TQL_TILE_COLD_SERVES.inc()
                        flight_recorder.note(
                            strategy="tql", build_mode="cold_serve"
                        )
                        flight_recorder.mark()
                        passes.note(
                            "tql_tile", False,
                            "cold: served from the legacy scan; background "
                            "family build scheduled",
                            cold=True,
                        )
                        return COLD_SERVE
                    # known family gone stale (post-flush delta), fused
                    # builds off, or already inside the builder: build
                    # synchronously — delta-extend keeps this O(delta)
                    self._build_sync(ctx, src, sources_meta)
                    sources_meta = self._acquire_regions(
                        ctx, lo_nat, hi_nat, ts_name, pinned
                    )
                    if not all(self._warm_entry(s, src) for s in sources_meta):
                        raise _Ineligible("planes did not build")
                self.cache.repair_super(
                    [s["entry"] for s in sources_meta], dictionary, src.pk
                )
                return self._dispatch(
                    func, agg, sources_meta, dictionary, src, unit_ns,
                    offset, lo_nat, hi_nat, start, end, step, steps,
                    range_ms, eq_matchers, regex_matchers, plan,
                )
            finally:
                for r in pinned:
                    r.unpin_scan()

    def _source(self, ctx, meta, tags) -> "_Source":
        """Which planes answer `meta`'s table: its own for a mito table;
        for a metric-engine logical table its PHYSICAL table's, of whose
        key the program compares `__tsid` alone (the slice is one
        `__table_id`'s rows)."""
        from ...metric.engine import TS_COL, TSID_COL, VAL_COL

        schema = meta.schema
        if ctx.logical_table_id is None:
            fields = schema.field_columns()
            value_col = None
            for cand in ("greptime_value", "value", "val"):
                if any(f.name == cand for f in fields):
                    value_col = cand
                    break
            if value_col is None:
                if len(fields) != 1:
                    raise _Ineligible(
                        f"metric has {len(fields)} fields; expected one"
                    )
                value_col = fields[0].name
            pk = tuple(tags)
            return _Source(
                tags=tuple(tags), schema=schema, pk=pk, key_cols=pk,
                ts=schema.time_index.name, value=value_col, table_id=None,
            )
        phys = self.db.catalog.table(
            ctx.table_key.split(".", 1)[1], self.db.current_database
        )
        return _Source(
            tags=tuple(tags), schema=phys.schema,
            pk=tuple(c.name for c in phys.schema.tag_columns()),
            key_cols=(TSID_COL,),
            ts=phys.options.get("ts_col", TS_COL),
            value=phys.options.get("val_col", VAL_COL),
            table_id=ctx.logical_table_id,
        )

    # ---- region acquisition ------------------------------------------------
    def _acquire_regions(self, ctx, lo_nat, hi_nat, ts_name, pinned):
        """Per region: snapshot, eligibility gates, and the WARM check —
        entry present for the current file set with every needed plane
        resident.  Returns [{region, metas, entry|None, dedup}]. Raises
        _Ineligible on shapes the tile path must not serve."""
        import pyarrow as pa
        import pyarrow.compute as pc

        from ...storage.region import OP_COL

        out = []
        for region in ctx.regions:
            if region not in pinned:
                region.pin_scan()
                pinned.append(region)
            metas, mems, version = region.tile_snapshot()
            self.cache.invalidate_region_if_changed(
                region.region_id, {m.file_id for m in metas}, version
            )
            in_window = []
            ranges = []
            for m in metas:
                flo, fhi = m.time_range
                if fhi >= lo_nat and flo < hi_nat:
                    if m.num_deletes != 0:
                        raise _Ineligible("tombstones in the fetch window")
                    in_window.append(m)
                    ranges.append((flo, fhi))
            # memtable rows in the fetch window: the legacy scan would
            # merge them; the tile entry covers flushed files only
            for mem in mems:
                mem_table = mem.scan(None, dedup=not ctx.append_mode)
                if mem_table.num_rows == 0:
                    continue
                if ts_name not in mem_table.column_names:
                    raise _Ineligible("memtable rows without a time index")
                ts_i = pc.cast(mem_table[ts_name], pa.int64())
                mlo = pc.min(ts_i).as_py()
                mhi = pc.max(ts_i).as_py()
                if mhi >= lo_nat and mlo < hi_nat:
                    raise _Ineligible("memtable rows in the fetch window")
                if OP_COL in mem_table.column_names:
                    raise _Ineligible("memtable delete markers")
            dedup = (not ctx.append_mode) and not _disjoint_ranges(ranges)
            entry = None
            cached = self.cache._super.get(region.region_id)
            if cached is not None and set(cached.file_ids) == {
                m.file_id for m in metas
            }:
                entry = cached
            out.append({
                "region": region, "metas": metas, "entry": entry,
                "dedup": dedup,
            })
        return out

    def _warm_entry(self, item, src):
        """True when every plane this query needs is device-resident."""
        entry = item["entry"]
        if entry is None or entry.valid is None:
            return False
        need = [*src.key_cols, src.ts, src.value]
        if any(c not in entry.cols for c in need):
            return False
        if item["dedup"] and entry.valid_dedup is None:
            return False
        return True

    # ---- cold: background / synchronous builds -----------------------------
    def _manifest(self, ctx, src, dedup):
        from ...parallel.tile_cache import PlaneManifest

        return PlaneManifest(
            table_key=ctx.table_key, tag_cols=src.pk, ts_col=src.ts,
            value_cols=(src.value,), dedup=dedup,
        )

    def _family_fp(self, ctx, value_col, func, agg, eq_matchers,
                   regex_matchers):
        """Literal-insensitive family fingerprint: matcher STRUCTURE
        (label, op) stays, values do not — swapping the filtered host or
        sliding the window re-uses the warm family."""
        structure = tuple(
            sorted((m.label, m.op) for m in eq_matchers + regex_matchers)
        )
        agg_fp = None if agg is None else (
            agg[0],
            None if agg[1] is None else tuple(agg[1]),
            None if agg[2] is None else tuple(agg[2]),
        )
        return (ctx.table_key, ctx.append_mode,
                ("tql", ctx.logical_table_id, value_col, func in _RATE_KINDS,
                 structure, agg_fp))

    def _schedule_build(self, fp, ctx, src, sources_meta,
                        func, sel, range_ms, start, end, step, agg):
        dedup = any(s["dedup"] for s in sources_meta)
        manifest = self._manifest(ctx, src, dedup)

        def ghost():
            # runs on the fused worker inside fused_build_scope(): the
            # union build already materialized the planes; this primes
            # the compile + dispatch for the family's geometry
            self.try_range_eval(func, sel, range_ms, start, end, step, agg)

        self.executor.fused_schedule_custom(
            fp, manifest, ctx, src.schema, ghost
        )

    def _build_sync(self, ctx, src, sources_meta):
        """Synchronous plane build (tile.fused_build off, or the ghost
        run finishing what the union build skipped)."""
        pk = list(src.pk)
        pinned_ids = {r.region_id for r in ctx.regions}
        for item in sources_meta:
            if self._warm_entry(item, src):
                continue
            entry, _excluded = self.cache.super_tiles(
                item["region"], ctx.dictionary, item["metas"], pk, src.ts,
                [src.value], pinned_ids, pk,
            )
            if entry is None:
                raise _Ineligible("region cannot tile")
            if item["dedup"] and not self.cache.ensure_dedup_keep(entry):
                raise _Ineligible("dedup keep plane unavailable")
            item["entry"] = entry

    # ---- dispatch ----------------------------------------------------------
    def _dispatch(self, func, agg, sources_meta, dictionary, src, unit_ns,
                  offset, lo_nat, hi_nat, start, end, step, steps, range_ms,
                  eq_matchers, regex_matchers, plan):
        from ...parallel.tile_cache import _in_fused_build

        cfg = self.db.config
        tags = list(src.tags)
        for item in sources_meta:
            if not self._warm_entry(item, src):
                raise _Ineligible("needed planes not resident")

        # --- the series that exist: each region's run of the global series
        # space, and of a logical table its rows in the region's planes ---
        spans = self._series_spans(sources_meta, dictionary, src)
        n_series = sum(sp.s_hi - sp.s_lo for sp in spans)
        if src.table_id is not None and n_series == 0:
            return _empty_matrix(tags, agg, steps)  # as the legacy scan
        labels = _SeriesLabels(spans, tags, dictionary)

        # --- geometry buckets (pow2: sliding queries share programs) ---
        s_pad = _pow2(max(n_series, 1))
        w = len(steps)
        w_pad = _pow2(w)
        k = _pow2(max(-(-range_ms // step), 1))
        if s_pad * w_pad > int(cfg.tql.max_cells):
            raise _Ineligible(
                f"series*steps cells {s_pad}x{w_pad} exceed tql.max_cells"
            )

        # --- matchers: a bool per series, folded from code masks over the
        # per-series label table (a dynamic input: literals never recompile)
        selected = np.zeros(s_pad, bool)
        selected[:n_series] = (labels.codes >= 0).all(axis=1)
        for mt in eq_matchers:
            col = labels.codes[:, tags.index(mt.label)]
            code = dictionary.code_of(mt.label, mt.value)
            if mt.op == "=":
                selected[:n_series] &= (col == code) & (code >= 0)
            else:  # != — scan-filter semantics: null rows do not match
                selected[:n_series] &= (
                    (col != code) & (col != _null_code(dictionary, mt.label))
                )
        for mt in regex_matchers:
            pat = re.compile(mt.value)
            hit = np.array([
                bool(pat.fullmatch(v if v is not None else ""))
                for v in dictionary.values(mt.label)
            ] + [False], bool)
            if mt.op == "!~":
                hit[:-1] = ~hit[:-1]
            col = labels.codes[:, tags.index(mt.label)]
            selected[:n_series] &= hit[np.where(col < len(hit) - 1, col, -1)]

        # --- fused aggregation structure: a group id per series ---
        agg_op = None
        keep: list[str] = []
        gids = g_pad = None
        if agg is not None:
            agg_op, by, without = agg
            if by is not None:
                keep = [l for l in by if l in tags]
            elif without is not None:
                keep = [l for l in tags if l not in without]
            gids = labels.group_ids(keep)
            g_pad = _pow2(max(len(gids.keys), 1))

        matched = not bool(selected[:n_series].all())
        csig = (func, agg_op, s_pad, w_pad, k, unit_ns, g_pad, matched)

        # --- device sources ---
        sources = []
        region_sigs = []
        plane_rows = 0
        for item, sp in zip(sources_meta, spans):
            entry = item["entry"]
            valid = entry.valid_dedup if item["dedup"] else entry.valid
            planes = [
                tuple(tuple(entry.cols[t]) for t in src.key_cols),
                tuple(entry.cols[src.ts]),
                tuple(entry.cols[src.value]),
                tuple(entry.nulls[src.value])
                if src.value in entry.nulls else None,
                tuple(valid),
            ]
            at = {"sid0": np.int32(sp.sid0)}
            if src.table_id is None:
                cut = None
                at.update(off=np.int32(0), lo=np.int32(0), hi=np.int32(entry.pad))
                plane_rows += entry.pad
            else:
                # the table's rows [r_lo, r_hi) as a padded slice of the
                # chunks it touches: static size, dynamic place
                lengths = [int(c.shape[0]) for c in planes[1]]
                first, last, cut, off, base = _slice_plan(
                    lengths, sp.r_lo, sp.r_hi
                )
                touched = slice(first, last + 1)
                planes = [
                    tuple(chunks[touched] for chunks in planes[0]),
                    *(p if p is None else p[touched] for p in planes[1:]),
                ]
                at.update(
                    off=np.int32(off), lo=np.int32(sp.r_lo - base),
                    hi=np.int32(sp.r_hi - base),
                )
                plane_rows += cut[0]
                plan.set(slice_rows=cut[0], series=n_series)
            source = (*planes, at)
            sources.append(source)
            region_sigs.append((_source_sig(source), cut))

        dyn = {
            "lo": np.int64(lo_nat), "hi": np.int64(hi_nat),
            "offset": np.int64(offset), "start": np.int64(start),
            "step": np.int64(step), "range": np.int64(range_ms),
            "nsteps": np.int64(w),
        }
        if matched:
            dyn["sel"] = selected
        if gids is not None:
            by_series = np.zeros(s_pad, np.int32)
            by_series[:n_series] = gids.of_series
            dyn["gid"] = by_series

        ghost = _in_fused_build()
        mesh_n = self.cache.mesh_devices()
        with tracing.span(
            "tile.dispatch", strategy="tql", func=func,
            series=s_pad, steps=w, regions=len(sources),
            mesh_devices=mesh_n,
        ) as disp:
            if mesh_n > 0 and len(sources) > 1:
                mat, pres = self._mesh_dispatch(
                    csig, sources, region_sigs, dyn, sources_meta, ghost
                )
            else:
                sources = [
                    _colocate(source, self.cache.devices[0])
                    for source in sources
                ]
                fn = _full_program((csig, tuple(region_sigs)))
                if not ghost:
                    metrics.TPU_DEVICE_DISPATCHES.inc()
                mat, pres = fn(tuple(sources), dyn)
        flight_recorder.stage_add("dispatch", disp.duration() * 1000.0)
        flight_recorder.note(
            strategy="tql", mesh_devices=mesh_n, build_mode="warm"
        )
        np_mat, np_pres, pregathered = self._readback(
            mat, pres, ghost, cfg, compact_ok=agg_op is None
        )
        if not ghost:
            metrics.TQL_TILE_DISPATCHES.inc()
            metrics.TQL_TILE_PLANE_ROWS.inc(plane_rows)
            if src.table_id is not None:
                metrics.TQL_TILE_LOGICAL_DISPATCHES.inc()
            if reductions_for(func):
                metrics.TQL_TILE_SEGMENT_STATS.inc()
        passes.note(
            "tql_tile", True,
            f"warm: {func} over {len(sources)} region(s) served from "
            "device tiles in one fused dispatch"
            + (f" (+{agg_op} by-label fold)" if agg_op else ""),
            series=s_pad, steps=w, mesh_devices=mesh_n,
            compact_readback=pregathered is not None,
        )
        with tracing.stage("tql.assemble"):
            return self._assemble(
                np_mat, np_pres, labels, steps, w, keep, gids, pregathered
            )

    def _series_spans(self, sources_meta, dictionary, src) -> list:
        """Per region: where the source's series lie in the region's
        series table and its rows in the planes, and the first of its ids
        in the global series space (regions in scan order)."""
        from ...metric.engine import TABLE_ID_COL

        spans, sid0 = [], 0
        for item in sources_meta:
            table = self.cache.series_table(item["entry"], dictionary, src.pk)
            if table is None:
                raise _Ineligible("no sorted host planes for the series table")
            if src.table_id is None:
                s_lo, s_hi = 0, len(table)
            else:
                code = dictionary.code_of(TABLE_ID_COL, src.table_id)
                s_lo, s_hi = table.code_range(TABLE_ID_COL, code) if code >= 0 else (0, 0)
            spans.append(_Span(
                table, s_lo, s_hi, int(table.starts[s_lo]),
                int(table.starts[s_hi]), sid0,
            ))
            sid0 += s_hi - s_lo
        return spans

    def _mesh_dispatch(self, csig, sources, region_sigs, dyn, sources_meta,
                       ghost):
        """Multi-chip path (tile.mesh_devices > 0): each region's stats
        partial runs on its co-located mesh device, the [S*W] partials —
        tiny next to the planes — fan in to device 0 and merge in region
        order.  Regions are series-disjoint, so the merge is selection:
        1-vs-N device results are bit-identical."""
        from ...parallel.mesh import region_device_index

        mesh_n = self.cache.mesh_devices()
        partials = []
        for src, rsig, item in zip(sources, region_sigs, sources_meta):
            dev = self.cache.devices[
                region_device_index(item["region"].region_id, mesh_n)
            ]
            fn = _partial_program((csig, rsig))
            if not ghost:
                metrics.TPU_DEVICE_DISPATCHES.inc()
            partials.append(fn(_colocate(src, dev), dyn))
        dev0 = self.cache.devices[0]
        moved = tuple(
            tuple(jax.device_put(a, dev0) for a in stats_t)
            for stats_t, _p in partials
        )
        merge = _merge_program((csig, len(partials)))
        if not ghost:
            metrics.TPU_DEVICE_DISPATCHES.inc()
        mat = merge(moved, dyn)
        if not ghost:
            metrics.TILE_MESH_DISPATCHES.inc()
        return mat, tuple(p for _s, p in partials)

    def _readback(self, mat, pres, ghost, cfg, compact_ok=True):
        """Device -> host fetch.  Small results ship in ONE round-trip
        (matrix + presence batched).  Past `tql.compact_readback_kb` the
        fetch goes two-phase: presence first (tiny), then a device-side
        gather of only the PRESENT rows — the readback ships the compact
        [series_out, steps] result, never the padded series space.
        Fused by-label results are already compact [groups, steps] and
        always take the one-round-trip form."""
        threshold = int(getattr(cfg.tql, "compact_readback_kb", 1024)) << 10
        pregathered = None
        with tracing.span("tile.readback") as rb:
            if compact_ok and mat.size * 8 > threshold:
                np_pres = [np.asarray(p) for p in jax.device_get(pres)]
                pregathered = _legacy_order(np_pres)
                if pregathered:
                    sel = jnp.asarray(np.asarray(pregathered, np.int32))
                    np_mat = np.asarray(jax.device_get(jnp.take(mat, sel, axis=0)))
                else:
                    np_mat = np.zeros((0, mat.shape[1]))
            else:
                np_mat, np_pres = jax.device_get((mat, pres))
                np_mat = np.asarray(np_mat)
                np_pres = [np.asarray(p) for p in np_pres]
            nbytes = int(np_mat.nbytes + sum(p.nbytes for p in np_pres))
            rb.attributes["bytes"] = nbytes
            rb.attributes["compact"] = pregathered is not None
        ms = rb.duration() * 1000.0
        flight_recorder.stage_add("readback_transfer", ms)
        flight_recorder.add_bytes(down=nbytes)
        if not ghost:
            metrics.TPU_DEVICE_FETCHES.inc()
            metrics.TPU_READBACK_MS.observe(ms)
            metrics.TPU_READBACK_BYTES.inc(nbytes)
        return np_mat, np_pres, pregathered

    # ---- host assembly -----------------------------------------------------
    def _assemble(self, np_mat, np_pres, labels, steps, w, keep, gids,
                  pregathered):
        """Fetched matrix -> engine Matrix, in the legacy scan's order:
        regions in scan order, pk-sorted within each (= ascending series
        id), groups at first appearance along it.  (Over a logical table
        that is `__tsid` order, a hash's: `PromqlEngine.query_range` puts
        the finished answer in label order, whichever path made it.)"""
        from .engine import Matrix

        order = np.asarray(
            pregathered if pregathered is not None else _legacy_order(np_pres),
            np.int64,
        )
        if gids is None:
            tuples = labels.tuples()
            at = np.arange(len(order)) if pregathered is not None else order
            return Matrix(
                list(labels.tags), [tuples[s] for s in order],
                np_mat[at][:, :w] if len(order) else np.zeros((0, w)), steps,
            )
        # grouped: the groups that hold a present series
        g_order = list(dict.fromkeys(gids.of_series[order].tolist()))
        keys = labels.group_tuples(keep, gids)
        return Matrix(
            list(keep), [keys[g] for g in g_order],
            np_mat[np.asarray(g_order, np.int64)][:, :w]
            if g_order else np.zeros((0, w)), steps,
        )


# ---- helpers ---------------------------------------------------------------


def _legacy_order(np_pres) -> list[int]:
    """The legacy scan's series order: regions in scan order, pk-sorted
    (= dictionary-code ascending) within a region, first appearance
    wins."""
    order: list[int] = []
    seen: set[int] = set()
    for p in np_pres:
        for sid in np.nonzero(p)[0]:
            s = int(sid)
            if s not in seen:
                seen.add(s)
                order.append(s)
    return order


@dataclasses.dataclass(frozen=True)
class _Source:
    """Which planes answer a metric and how its series are told apart."""

    tags: tuple  # the answer's label columns
    schema: object  # of the table that owns the planes
    pk: tuple  # that table's key columns: the series table's columns
    key_cols: tuple  # key planes the program compares row to row
    ts: str
    value: str
    table_id: int | None  # a logical table's `__table_id`


@dataclasses.dataclass(frozen=True)
class _Span:
    """One region's share of a source: series [s_lo, s_hi) of its series
    table, rows [r_lo, r_hi) of its planes, ids from `sid0` on."""

    table: object
    s_lo: int
    s_hi: int
    r_lo: int
    r_hi: int
    sid0: int


@dataclasses.dataclass(frozen=True)
class _GroupIds:
    of_series: np.ndarray  # [n_series] int32
    keys: np.ndarray  # [groups, len(keep)] label codes


class _SeriesLabels:
    """The answer's labels of every series of a source, by global series
    id: codes straight off the regions' series tables; what costs a pass
    over the series (decoded tuples, label order, group ids) is made once
    a plane build and kept in the table's `memo` where the source is one
    region's, which a logical table's always is."""

    def __init__(self, spans, tags, dictionary):
        self.tags, self.dictionary = list(tags), dictionary
        self._spans = spans
        cols = None
        parts = []
        for sp in spans:
            cols = [sp.table.tags.index(t) for t in tags]
            parts.append(sp.table.codes[sp.s_lo:sp.s_hi][:, cols])
        self.codes = (
            parts[0] if len(parts) == 1 else np.concatenate(parts)
        ) if parts else np.zeros((0, len(tags)), np.int32)

    def _memo(self, what, make):
        if len(self._spans) != 1:
            return make()
        sp = self._spans[0]
        return sp.table.remember((what, sp.s_lo, sp.s_hi, tuple(self.tags)), make)

    def _decode(self, codes: np.ndarray, names) -> list:
        lists = [self.dictionary.values(t) for t in names]
        return [
            tuple(
                vals[c] if 0 <= c < len(vals) else None
                for c, vals in zip(row, lists)
            )
            for row in codes.tolist()
        ]

    def tuples(self) -> list:
        return self._memo("tuples", lambda: self._decode(self.codes, self.tags))

    def group_ids(self, keep) -> _GroupIds:
        def make():
            cols = [self.tags.index(l) for l in keep]
            if not cols or not len(self.codes):
                return _GroupIds(
                    np.zeros(len(self.codes), np.int32),
                    np.zeros((1, len(cols)), np.int32),
                )
            keys, of_series = np.unique(
                self.codes[:, cols], axis=0, return_inverse=True
            )
            return _GroupIds(of_series.reshape(-1).astype(np.int32), keys)

        return self._memo(("gids", tuple(keep)), make)

    def group_tuples(self, keep, gids: _GroupIds) -> list:
        return self._memo(
            ("group_tuples", tuple(keep)), lambda: self._decode(gids.keys, keep)
        )


def _slice_plan(lengths, r_lo: int, r_hi: int):
    """Where a logical table's rows [r_lo, r_hi) lie in planes stored as
    chunks of `lengths`: (first, last) chunk touched, the program's static
    `cut` = (L, head, tail) (see `_logical_slice`), the dynamic offset
    into what the cut leaves, and the plane row the slice starts at.  L is
    a power of two from an offset on a 1024-row boundary, so tables of
    about one size share a program; it never leaves the planes."""
    total = sum(lengths)
    start = (r_lo // 1024) * 1024
    size = _pow2(max(r_hi - start, 1024))
    if size >= total:
        size, start = total, 0
    else:
        start = min(start, total - size)
    ends = np.cumsum(lengths)
    first = int(np.searchsorted(ends, start, side="right"))
    last = int(np.searchsorted(ends, start + size - 1, side="right"))
    begin = int(ends[first] - lengths[first])
    if first == last:
        return first, last, (size, 0, size), start - begin, start
    head = lengths[first] - min(size, lengths[first])
    tail = min(size, lengths[last])
    return first, last, (size, head, tail), start - begin - head, start


def _null_code(dictionary, name) -> int:
    cd = dictionary._cols.get(name)
    return cd.null_code if cd is not None else -1


def _source_sig(src):
    def leaf_sig(chunks):
        return tuple((tuple(c.shape), str(c.dtype)) for c in chunks)

    tags, ts, vals, nulls, valid, _at = src
    return (
        tuple(leaf_sig(t) for t in tags), leaf_sig(ts), leaf_sig(vals),
        None if nulls is None else leaf_sig(nulls), leaf_sig(valid),
    )


def _colocate(src, device):
    """Move a region's chunk planes onto one device (no-op when already
    there — the common single-device case); device-to-device only, never
    a host upload."""

    def move(x):
        devs = getattr(x, "devices", None)
        if devs is not None and device in devs():
            return x
        return jax.device_put(x, device)

    tags, ts, vals, nulls, valid, at = src
    return (
        tuple(tuple(move(c) for c in t) for t in tags),
        tuple(move(c) for c in ts),
        tuple(move(c) for c in vals),
        None if nulls is None else tuple(move(c) for c in nulls),
        tuple(move(c) for c in valid),
        at,
    )


def _disjoint_ranges(ranges) -> bool:
    if len(ranges) <= 1:
        return True
    s = sorted(ranges)
    return all(s[i][1] < s[i + 1][0] for i in range(len(s) - 1))


def _empty_matrix(tags, agg, steps):
    from .engine import Matrix

    if agg is not None:
        op, by, without = agg
        if by is not None:
            keep = [l for l in by if l in tags]
        elif without is not None:
            keep = [l for l in tags if l not in without]
        else:
            keep = []
        return Matrix(keep, [], np.zeros((0, len(steps))), steps)
    return Matrix(list(tags), [], np.zeros((0, len(steps))), steps)
