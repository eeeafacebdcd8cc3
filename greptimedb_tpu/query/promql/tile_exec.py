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
padded step count, padded windows-per-sample, chunk geometry), with the
evaluation grid (start/step/range), time bounds and matcher literals as
dynamic inputs — the literal-insensitive `_plan_fp` discipline — so a
dashboard sliding its window re-hits the compile cache with zero
host->device plane traffic.

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
from ...utils.errors import QueryTimeoutError
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


def _region_stats(src, dyn, rsig, csig):
    """Traced per-region pipeline: planes -> per-(series, window) stats +
    per-series presence.  `src` = (tag_chunks..., ts_chunks, val_chunks,
    null_chunks|None, valid_chunks); shapes come from `rsig`, query
    structure from `csig`."""
    (tag_chunks, ts_chunks, val_chunks, null_chunks, valid_chunks) = src
    (func, _agg, s_pad, w_pad, k, radices, unit_ns, mask_spec, _gid) = csig

    def cat(chunks):
        return chunks[0] if len(chunks) == 1 else jnp.concatenate(list(chunks))

    codes = [cat(c) for c in tag_chunks]
    ts_nat = cat(ts_chunks)
    valid = cat(valid_chunks)
    vf = cat(val_chunks).astype(jnp.float64)
    if null_chunks is not None:
        vf = jnp.where(cat(null_chunks), vf, jnp.nan)

    # fetch-range membership in the column's NATIVE unit — the exact
    # region-scan bound semantics ([lo, hi) exclusive upper)
    in_fetch = valid & (ts_nat >= dyn["lo"]) & (ts_nat < dyn["hi"])
    for c in codes:
        in_fetch = in_fetch & (c >= 0)
    for (ti, card_pad), mask in zip(mask_spec, dyn["masks"]):
        c = codes[ti]
        in_fetch = (
            in_fetch
            & (c < card_pad)
            & jnp.take(mask, jnp.clip(c, 0, card_pad - 1))
        )

    # mixed-radix series id over the pk tag codes (the same code space
    # the (pk, ts) super-tile sort ordered rows by, so the rows are in
    # (series, ts) order — contiguity is what the reset scan needs, the
    # order what `range_windows_dyn` searches; its docstring has the
    # precondition and why these planes meet it)
    sid = jnp.zeros(ts_nat.shape, jnp.int32)
    stride = 1
    for c, r in zip(reversed(codes), reversed(radices)):
        sid = sid + c.astype(jnp.int32) * stride
        stride *= r

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
    (func, agg, s_pad, w_pad, _k, radices, _unit, _mask, keep_idx) = csig
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
        # the sid -> gid map is derivable from (radices, keep_idx) — built
        # here at TRACE time so it constant-folds into the compiled program
        # and never costs the warm path a per-query numpy pass
        gidmap = _gid_map(radices, list(keep_idx))
        gid = jnp.asarray(gidmap)
        g_pad = 1
        for i in keep_idx:
            g_pad *= radices[i]
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
        Returns an engine Matrix, or None to fall back to the legacy
        path (reason recorded on the `tql_tile` pass trace)."""
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
                            func, sel, range_ms, start, end, step, agg
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
            return None

    # ---- attempt -----------------------------------------------------------
    def _attempt(self, func, sel, range_ms, start, end, step, agg):
        db = self.db
        meta = db.catalog.table(sel.metric, db.current_database)
        schema = meta.schema
        if schema.time_index is None:
            raise _Ineligible("metric table has no time index")
        ts_name = schema.time_index.name
        tags = [c.name for c in schema.tag_columns()]
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
        ctx = db._tile_context(scan)
        if ctx is None:
            raise _Ineligible("table source cannot tile")
        if not ctx.regions:
            raise _Ineligible("no regions")
        if any(
            getattr(r, "merge_mode", "last_row") == "last_non_null"
            for r in ctx.regions
        ) and not ctx.append_mode:
            raise _Ineligible("last_non_null merge mode")

        # fetch bounds: scan time_range semantics in the native unit
        unit_ns = schema.time_index.data_type.timestamp_unit_ns()
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
                warm = all(
                    self._warm_entry(s, tags, ts_name, value_col)
                    for s in sources_meta
                )
                if not warm:
                    if (
                        fused
                        and not _in_fused_build()
                        and self.executor.fused_first_touch_fp(fp)
                    ):
                        # FIRST touch of the family: answer from the
                        # legacy scan now, build in the background
                        self._schedule_build(
                            fp, ctx, schema, sources_meta, value_col, ts_name,
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
                        return None
                    # known family gone stale (post-flush delta), fused
                    # builds off, or already inside the builder: build
                    # synchronously — delta-extend keeps this O(delta)
                    self._build_sync(
                        ctx, schema, sources_meta, value_col, ts_name
                    )
                    sources_meta = self._acquire_regions(
                        ctx, lo_nat, hi_nat, ts_name, pinned
                    )
                    if not all(
                        self._warm_entry(s, tags, ts_name, value_col)
                        for s in sources_meta
                    ):
                        raise _Ineligible("planes did not build")
                pk = [c.name for c in schema.tag_columns()]
                self.cache.repair_super(
                    [s["entry"] for s in sources_meta], dictionary, pk
                )
                return self._dispatch(
                    func, agg, sources_meta, dictionary, tags, ts_name,
                    value_col, unit_ns, offset, lo_nat, hi_nat,
                    start, end, step, steps, range_ms,
                    eq_matchers, regex_matchers,
                )
            finally:
                for r in pinned:
                    r.unpin_scan()

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

    def _warm_entry(self, item, tags, ts_name, value_col):
        """True when every plane this query needs is device-resident."""
        entry = item["entry"]
        if entry is None or entry.valid is None:
            return False
        need = list(tags) + [ts_name, value_col]
        if any(c not in entry.cols for c in need):
            return False
        if item["dedup"] and entry.valid_dedup is None:
            return False
        return True

    # ---- cold: background / synchronous builds -----------------------------
    def _manifest(self, ctx, schema, value_col, ts_name, dedup):
        from ...parallel.tile_cache import PlaneManifest

        pk = tuple(c.name for c in schema.tag_columns())
        return PlaneManifest(
            table_key=ctx.table_key, tag_cols=pk, ts_col=ts_name,
            value_cols=(value_col,), dedup=dedup,
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
                ("tql", value_col, func in _RATE_KINDS, structure, agg_fp))

    def _schedule_build(self, fp, ctx, schema, sources_meta, value_col,
                        ts_name, func, sel, range_ms, start, end, step, agg):
        dedup = any(s["dedup"] for s in sources_meta)
        manifest = self._manifest(ctx, schema, value_col, ts_name, dedup)

        def ghost():
            # runs on the fused worker inside fused_build_scope(): the
            # union build already materialized the planes; this primes
            # the compile + dispatch for the family's geometry
            self.try_range_eval(func, sel, range_ms, start, end, step, agg)

        self.executor.fused_schedule_custom(fp, manifest, ctx, schema, ghost)

    def _build_sync(self, ctx, schema, sources_meta, value_col, ts_name):
        """Synchronous plane build (tile.fused_build off, or the ghost
        run finishing what the union build skipped)."""
        pk = [c.name for c in schema.tag_columns()]
        pinned_ids = {r.region_id for r in ctx.regions}
        for item in sources_meta:
            if self._warm_entry(item, pk, ts_name, value_col):
                continue
            entry, _excluded = self.cache.super_tiles(
                item["region"], ctx.dictionary, item["metas"], pk, ts_name,
                [value_col], pinned_ids, pk,
            )
            if entry is None:
                raise _Ineligible("region cannot tile")
            if item["dedup"] and not self.cache.ensure_dedup_keep(entry):
                raise _Ineligible("dedup keep plane unavailable")
            item["entry"] = entry

    # ---- dispatch ----------------------------------------------------------
    def _dispatch(self, func, agg, sources_meta, dictionary, tags, ts_name,
                  value_col, unit_ns, offset, lo_nat, hi_nat,
                  start, end, step, steps, range_ms,
                  eq_matchers, regex_matchers):
        from ...parallel.tile_cache import _in_fused_build

        cfg = self.db.config
        for item in sources_meta:
            if not self._warm_entry(item, tags, ts_name, value_col):
                raise _Ineligible("needed planes not resident")

        # --- geometry buckets (pow2: sliding queries share programs) ---
        cards = [max(dictionary.cardinality(t), 1) for t in tags]
        radices = tuple(_pow2(c) for c in cards)
        s_pad = 1
        for r in radices:
            s_pad *= r
        w = len(steps)
        w_pad = _pow2(w)
        k = _pow2(max(-(-range_ms // step), 1))
        if s_pad * w_pad > int(cfg.tql.max_cells):
            raise _Ineligible(
                f"series*steps cells {s_pad}x{w_pad} exceed tql.max_cells"
            )

        # --- matcher masks (dynamic [card_pad] bools per filtered tag) ---
        mask_arrays: dict[int, np.ndarray] = {}

        def mask_for(ti):
            if ti not in mask_arrays:
                card_pad = radices[ti]
                m = np.zeros(card_pad, dtype=bool)
                m[: cards[ti]] = True
                mask_arrays[ti] = m
            return mask_arrays[ti]

        for mt in eq_matchers:
            ti = tags.index(mt.label)
            m = mask_for(ti)
            code = dictionary.code_of(mt.label, mt.value)
            if mt.op == "=":
                sel_mask = np.zeros(len(m), dtype=bool)
                if code >= 0:
                    sel_mask[code] = True
                mask_arrays[ti] = m & sel_mask
            else:  # != — scan-filter semantics: null rows do not match
                if code >= 0:
                    m[code] = False
                nc = _null_code(dictionary, mt.label)
                if nc >= 0:
                    m[nc] = False
        for mt in regex_matchers:
            ti = tags.index(mt.label)
            m = mask_for(ti)
            pat = re.compile(mt.value)
            values = dictionary.values(mt.label)
            rx = np.zeros(len(m), dtype=bool)
            for code, v in enumerate(values):
                rx[code] = bool(pat.fullmatch(v if v is not None else ""))
            if mt.op == "!~":
                rx[: len(values)] = ~rx[: len(values)]
            mask_arrays[ti] = m & rx
        mask_spec = tuple(sorted((ti, radices[ti]) for ti in mask_arrays))
        masks = tuple(mask_arrays[ti] for ti, _c in mask_spec)

        # --- fused aggregation structure ---
        agg_op = None
        keep: list[str] = []
        keep_idx: list[int] = []
        if agg is not None:
            agg_op, by, without = agg
            if by is not None:
                keep = [l for l in by if l in tags]
            elif without is not None:
                keep = [l for l in tags if l not in without]
            keep_idx = [tags.index(l) for l in keep]

        csig = (
            func, agg_op, s_pad, w_pad, k, radices, unit_ns, mask_spec,
            tuple(keep_idx),
        )

        # --- device sources ---
        sources = []
        region_sigs = []
        for item in sources_meta:
            entry = item["entry"]
            valid = entry.valid_dedup if item["dedup"] else entry.valid
            null_chunks = (
                tuple(entry.nulls[value_col])
                if value_col in entry.nulls else None
            )
            src = (
                tuple(tuple(entry.cols[t]) for t in tags),
                tuple(entry.cols[ts_name]),
                tuple(entry.cols[value_col]),
                null_chunks,
                tuple(valid),
            )
            rsig = _source_sig(src)
            sources.append(src)
            region_sigs.append(rsig)

        dyn = {
            "lo": np.int64(lo_nat), "hi": np.int64(hi_nat),
            "offset": np.int64(offset), "start": np.int64(start),
            "step": np.int64(step), "range": np.int64(range_ms),
            "nsteps": np.int64(w), "masks": masks,
        }

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
                    _colocate(src, self.cache.devices[0]) for src in sources
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
                np_mat, np_pres, dictionary, tags, steps, w, agg_op, keep,
                radices, keep_idx, pregathered,
            )

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
    def _assemble(self, np_mat, np_pres, dictionary, tags, steps, w,
                  agg_op, keep, radices, keep_idx, pregathered=None):
        from .engine import Matrix

        # legacy series order: regions in scan order, dictionary-code
        # (= pk-sorted) order within each region, first appearance wins
        order = (
            pregathered if pregathered is not None else _legacy_order(np_pres)
        )

        value_lists = [dictionary.values(t) for t in tags]

        def decode_sid(sid):
            out = []
            stride = 1
            codes = []
            for r in reversed(radices):
                codes.append((sid // stride) % r)
                stride *= r
            codes.reverse()
            for c, vals in zip(codes, value_lists):
                out.append(vals[c] if c < len(vals) else None)
            return tuple(out)

        if agg_op is None:
            label_values = [decode_sid(s) for s in order]
            if pregathered is not None:
                values = np_mat[:, :w] if order else np.zeros((0, w))
            else:
                values = (
                    np_mat[np.asarray(order, dtype=np.int64)][:, :w]
                    if order else np.zeros((0, w))
                )
            return Matrix(list(tags), label_values, values, steps)

        # grouped result: legacy group order = first appearance of each
        # group key along the legacy series order.  Only PRESENT sids
        # need a gid — computed directly from the radix arithmetic, so
        # the host never materializes the full [S_pad] map
        g_order: list[int] = []
        g_seen: set[int] = set()
        for s in order:
            g = _gid_of(s, radices, keep_idx)
            if g not in g_seen:
                g_seen.add(g)
                g_order.append(g)
        kept_value_lists = [value_lists[i] for i in keep_idx]
        kept_radices = [radices[i] for i in keep_idx]

        def decode_gid(gid):
            out = []
            stride = 1
            codes = []
            for r in reversed(kept_radices):
                codes.append((gid // stride) % r)
                stride *= r
            codes.reverse()
            for c, vals in zip(codes, kept_value_lists):
                out.append(vals[c] if c < len(vals) else None)
            return tuple(out)

        label_values = [decode_gid(g) for g in g_order]
        values = (
            np_mat[np.asarray(g_order, dtype=np.int64)][:, :w]
            if g_order else np.zeros((0, w))
        )
        return Matrix(list(keep), label_values, values, steps)


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


def _gid_of(sid: int, radices, keep_idx) -> int:
    """Group id of ONE series id (mixed radix over the kept tag subset,
    keep order) — the scalar form of `_gid_map` for host-side decode of
    the few present sids."""
    codes = []
    stride = 1
    for r in reversed(radices):
        codes.append((sid // stride) % r)
        stride *= r
    codes.reverse()
    gid = 0
    g_stride = 1
    for i in reversed(keep_idx):
        gid += codes[i] * g_stride
        g_stride *= radices[i]
    return gid


def _gid_map(radices, keep_idx) -> np.ndarray:
    """sid -> group id over the kept tag subset (mixed radix, keep
    order)."""
    s_pad = 1
    for r in radices:
        s_pad *= r
    sids = np.arange(s_pad, dtype=np.int64)
    codes = []
    stride = 1
    for r in reversed(radices):
        codes.append((sids // stride) % r)
        stride *= r
    codes.reverse()
    gid = np.zeros(s_pad, dtype=np.int64)
    g_stride = 1
    for i in reversed(keep_idx):
        gid = gid + codes[i] * g_stride
        g_stride *= radices[i]
    return gid.astype(np.int32)


def _null_code(dictionary, name) -> int:
    cd = dictionary._cols.get(name)
    return cd.null_code if cd is not None else -1


def _source_sig(src):
    def leaf_sig(chunks):
        return tuple((tuple(c.shape), str(c.dtype)) for c in chunks)

    tags, ts, vals, nulls, valid = src
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

    tags, ts, vals, nulls, valid = src
    return (
        tuple(tuple(move(c) for c in t) for t in tags),
        tuple(move(c) for c in ts),
        tuple(move(c) for c in vals),
        None if nulls is None else tuple(move(c) for c in nulls),
        tuple(move(c) for c in valid),
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
