"""Query engine facade: parse -> plan -> execute (TPU or CPU backend).

Role-equivalent of the reference's `QueryEngine` trait +
`DatafusionQueryEngine` (reference query/src/query_engine.rs:58,
query/src/datafusion.rs:74): owns planning and execution, with the TPU
backend gated by config (`query.execution.backend = "tpu"`, the
BASELINE.json plug-point) and automatic CPU fallback for plans the TPU
planner cannot prove lowerable.
"""

from __future__ import annotations

import logging
import time

import pyarrow as pa

from ..datatypes.schema import Schema
from ..utils import metrics
from ..utils.config import QueryConfig
from ..utils.errors import PlanError, TableNotFoundError
from ..utils.tracing import span
from . import passes
from .cpu_exec import CpuExecutor
from .logical_plan import LogicalPlan, TableScan
from .planner import plan_query
from .sql_parser import SelectStmt
from .tpu_exec import TpuExecutor, try_lower


class QueryEngine:
    def __init__(
        self,
        schema_provider,
        scan_provider,
        region_scan_provider,
        time_bounds_provider,
        config: QueryConfig | None = None,
        mesh=None,
        tile_context_provider=None,
        partial_agg_provider=None,
        view_provider=None,
        vector_search_provider=None,
        subplan_provider=None,
    ):
        """
        schema_provider(table, database) -> Schema
        scan_provider(scan: TableScan) -> pa.Table           (merged regions)
        region_scan_provider(scan) -> list[pa.Table]         (one per region)
        time_bounds_provider(table, database) -> (min_ts, max_ts)
        tile_context_provider(scan) -> TileContext | None    (HBM tile cache)
        partial_agg_provider(scan, spec_dict) -> list[pa.Table] | None
            (distributed lower/state stage: datanodes return [groups]-sized
            mergeable states instead of raw rows — MergeScan on the wire)
        """
        self.config = config or QueryConfig()
        self.schema_of = schema_provider
        self.view_of = view_provider
        self.cpu = CpuExecutor(scan_provider, vector_search_provider)
        self._mesh = mesh
        self._region_scan = region_scan_provider
        self._time_bounds = time_bounds_provider
        self._tile_ctx = tile_context_provider
        self._partial_agg = partial_agg_provider
        self._subplan = subplan_provider
        self.tile_cache = None
        self._tile_executor = None
        if self.config.tile_cache_enable and tile_context_provider is not None:
            from ..parallel.tile_cache import TileCacheManager, TileExecutor

            self.tile_cache = TileCacheManager(
                self.config.tile_cache_mb << 20,
                chunk_rows=getattr(self.config, "tile_chunk_rows", 1 << 24),
                persist_dir=(
                    getattr(self.config, "tile_persist_dir", "") or None
                    if getattr(self.config, "tile_persist_enable", True)
                    else None
                ),
            )
            self._tile_executor = TileExecutor(self.tile_cache, self.config)

    @property
    def mesh(self):
        if self._mesh is None:
            from ..parallel.mesh import make_mesh

            self._mesh = make_mesh()
        return self._mesh

    # ---- entry ------------------------------------------------------------
    def execute_select(self, stmt: SelectStmt, database: str = "public") -> pa.Table:
        with span("query.plan", table=stmt.table or "") as s:
            plan, schema = plan_query(stmt, self.schema_of, database, self.view_of)
            s.attributes["plan_ms"] = round(s.duration() * 1000.0, 3)
        return self.execute_plan(plan, schema)

    def execute_plan(self, plan: LogicalPlan, schema: Schema) -> pa.Table:
        from ..utils import tracing

        root = tracing.current_span()
        if root is None or passes.active_trace() is not None:
            # untraced, or EXPLAIN ANALYZE already owns a trace: run plain
            return self._execute_plan_inner(plan, schema)
        # traced statement: record optimizer-pass decisions (which
        # strategies fired and why — the agg_strategy verdict especially)
        # as attributes on the enclosing span
        trace = passes.PassTrace()
        try:
            with passes.use_trace(trace):
                return self._execute_plan_inner(plan, schema)
        finally:
            _note_passes_on_span(root, trace)

    def _execute_plan_inner(self, plan: LogicalPlan, schema: Schema) -> pa.Table:
        t0 = time.perf_counter()
        backend = "cpu"
        try:
            if self.config.backend == "tpu" and schema.columns:
                lowering = try_lower(plan, schema)
                if (
                    lowering is not None
                    and self.config.tpu_min_rows > 0
                    and self._tile_ctx is not None
                    and passes.enabled("cost_route", self.config)
                ):
                    est = self._estimate_scan_rows(lowering.scan, schema)
                    if (
                        est is not None
                        and est < self.config.tpu_min_rows
                        and not self._tiles_resident(lowering.scan)
                    ):
                        # cost-based routing: building device tiles for a
                        # tiny scan isn't worth it — but once a super-tile
                        # is resident, the tile path's host fast branch
                        # beats the CPU scan, so routing only applies cold
                        # (reference analogue: the optimizer choosing a
                        # plain scan over a parallelized one for tiny
                        # inputs)
                        metrics.TPU_ROUTED_TO_CPU.inc()
                        passes.note(
                            "cost_route", True,
                            f"estimated {est} rows < tpu_min_rows="
                            f"{self.config.tpu_min_rows} and tiles not "
                            "resident: local CPU path", est_rows=est,
                        )
                        lowering = None
                    else:
                        passes.note(
                            "cost_route", False,
                            "scan large enough (or tiles resident) for the "
                            "device path", est_rows=est,
                        )
                if lowering is not None:
                    # the HBM super-tile path wins whenever it applies
                    # (standalone hot path: resident tiles, one dispatch,
                    # host fast branch for selective queries) — try it
                    # BEFORE state shipping.  backend is flipped first so
                    # a tile-path error falls back instead of re-raising.
                    scan = lowering.scan
                    backend = "tpu"
                    tpu = TpuExecutor(
                        None,
                        self._region_scan,
                        acc_dtype="float64" if _x64_enabled() else "float32",
                        tile_executor=self._tile_executor,
                        tile_context_provider=self._tile_ctx,
                    )
                    with span("query.tpu", table=scan.table):
                        table = tpu.try_tile(
                            lowering,
                            schema,
                            lambda: self._time_bounds(scan.table, scan.database),
                        )
                    if table is not None:
                        return table
                    backend = "cpu"
                    if not self.config.legacy_fallback:
                        from .promql.tile_exec import LegacyFallbackDisabled

                        raise LegacyFallbackDisabled(
                            tpu.declined, "query.legacy_fallback"
                        )
                if (
                    lowering is not None
                    and self._partial_agg is not None
                    and passes.enabled("state_ship", self.config)
                ):
                    # distributed: ship the aggregate, merge states — never
                    # rows — across nodes (reference MergeScan split)
                    from .dist_agg import merge_states, spec_from_lowering

                    spec = spec_from_lowering(lowering, schema)
                    if spec is not None:
                        from .analyze import stage as _stage

                        with _stage("dist.partial_states") as info:
                            states = self._partial_agg(lowering.scan, spec.to_dict())
                            if states is not None:
                                info["nodes"] = len(states)
                                info["state_rows"] = sum(s.num_rows for s in states)
                                info["state_bytes"] = sum(s.nbytes for s in states)
                        if states is not None:
                            backend = "dist_states"
                            passes.note(
                                "state_ship", True,
                                "aggregate decomposed into mergeable "
                                "states shipped from datanodes",
                                nodes=len(states),
                            )
                            with _stage("dist.merge_states") as info:
                                merged = merge_states(states, spec)
                                info["groups"] = merged.num_rows
                            shaper = TpuExecutor(None, None)
                            metrics.DIST_STATE_QUERIES.inc()
                            return shaper._shape_output(merged, lowering, schema)
                if lowering is not None:
                    backend = "tpu"
                    with span("query.tpu", table=lowering.scan.table):
                        # tile path already declined above — mesh only
                        tpu = TpuExecutor(
                            self.mesh,
                            self._region_scan,
                            acc_dtype="float64" if _x64_enabled() else "float32",
                        )
                        scan = lowering.scan
                        return tpu.execute(
                            lowering,
                            schema,
                            time_bounds=lambda: self._time_bounds(scan.table, scan.database),
                        )
            if self._subplan is not None and passes.enabled(
                "subplan_ship", self.config
            ):
                # general sub-plan shipping: push the maximal commutative
                # prefix (filter/project/sort/limit) below the region-merge
                # boundary so datanodes return BOUNDED rows instead of the
                # raw region (reference dist_plan/analyzer.rs:97 +
                # substrait shipping; ORDER BY ... LIMIT ships n x limit
                # rows, not the table)
                from .plan_wire import split_for_regions

                split = split_for_regions(plan)
                if split is not None:
                    passes.note(
                        "subplan_ship", True,
                        "commutative prefix shipped below the "
                        "region-merge boundary",
                        categories=",".join(split.categories),
                    )
                    from .analyze import stage as _stage

                    with _stage("dist.subplan") as info:
                        tables = self._subplan(split.scan, split.ship)
                        info["nodes"] = len(tables)
                        info["rows_shipped"] = sum(t.num_rows for t in tables)
                        info["bytes_shipped"] = sum(t.nbytes for t in tables)
                        info["categories"] = ",".join(split.categories)
                    backend = "dist_subplan"
                    return _merge_subplan_results(tables, split)
            with span("query.cpu"):
                return self.cpu.execute(plan)
        except Exception as e:
            from ..utils.errors import QueryTimeoutError

            if isinstance(e, QueryTimeoutError):
                raise  # deadline passed: the CPU fallback IS the runaway scan
            if backend == "tpu" and self.config.fallback_to_cpu:
                metrics.TPU_FALLBACK_TOTAL.inc()
                # the fallback keeps the query alive but must never hide
                # the device-path failure from operators (a silent
                # catch here masked a TPU-only lowering bug once)
                logging.getLogger("greptimedb_tpu.query").warning(
                    "tpu path failed; serving from cpu (tile cache: %s)",
                    self.tile_cache.stats() if self.tile_cache else {},
                    exc_info=True,
                )
                with span("query.cpu_fallback"):
                    return self.cpu.execute(plan)
            raise
        finally:
            metrics.QUERY_ELAPSED.observe(time.perf_counter() - t0, backend=backend)

    def _tiles_resident(self, scan: TableScan) -> bool:
        if self.tile_cache is None:
            return False
        ctx = self._tile_ctx(scan)
        if ctx is None or not ctx.regions:
            return False
        return all(self.tile_cache.has_region(r.region_id) for r in ctx.regions)

    def _estimate_scan_rows(self, scan: TableScan, schema: Schema) -> int | None:
        """Cheap pre-execution cardinality estimate for backend routing:
        file rows intersecting the time window + memtable rows, scaled by
        tag-equality selectivity from the dictionary cardinalities (the
        role of the reference's region-stat based planning inputs)."""
        ctx = self._tile_ctx(scan)
        if ctx is None:
            return None
        window = scan.time_range
        rows = 0
        try:
            for region in ctx.regions:
                files, mems, _v = region.tile_snapshot()
                for meta in files:
                    lo, hi = meta.time_range
                    if window is None or (hi >= window[0] and lo < window[1]):
                        rows += meta.num_rows
                for mem in mems:
                    rows += mem.num_rows
        except Exception:  # noqa: BLE001 — estimate only, never fatal
            return None
        sel = 1.0
        if ctx.dictionary is not None:
            tag_names = {c.name for c in schema.tag_columns()}
            for name, op, value in scan.filters:
                if name in tag_names:
                    card = max(ctx.dictionary.cardinality(name), 1)
                    if op == "=":
                        sel /= card
                    elif op == "in":
                        sel *= min(len(value) / card, 1.0)
        return int(rows * sel)

    def explain(self, stmt: SelectStmt, database: str = "public") -> pa.Table:
        plan, schema = plan_query(stmt, self.schema_of, database, self.view_of)
        lowered = try_lower(plan, schema) if schema.columns else None
        lines = plan.describe().split("\n")
        backend = ["tpu" if lowered is not None else "cpu"] * len(lines)
        # static pass listing (reference EXPLAIN shows the optimizer rule
        # pipeline); per-query firing needs EXPLAIN ANALYZE
        lines.append("── optimizer passes ──")
        backend.append("")
        for p in passes.registry():
            state = "on" if passes.enabled(p.name, self.config) else "DISABLED"
            lines.append(f"  [{p.kind}] {p.name} ({state})")
            backend.append(p.description)
        return pa.table({"plan": lines, "backend": backend})

    def explain_analyze(self, stmt: SelectStmt, database: str = "public") -> pa.Table:
        """EXPLAIN ANALYZE: execute for real, report per-stage metrics
        (reference query/src/analyze.rs DistAnalyzeExec)."""
        from .analyze import StageCollector, render, use_collector

        plan, schema = plan_query(stmt, self.schema_of, database, self.view_of)
        lowered = try_lower(plan, schema) if schema.columns else None
        collector = StageCollector()
        trace = passes.PassTrace()
        t0 = time.perf_counter()
        with use_collector(collector), passes.use_trace(trace):
            result = self.execute_plan(plan, schema)
        total_ms = (time.perf_counter() - t0) * 1000.0
        backend = "cpu"
        if lowered is not None:
            # distinguish how the lowered plan actually ran
            names = {r.name for r in collector.records}
            if "dist.merge_states" in names:
                backend = "dist_states"
            elif any(n.startswith("tpu.") for n in names):
                backend = "tpu"
        collector.add("output", 0.0, {"rows": result.num_rows}, depth=0)
        table = render(collector, plan.describe().split("\n"), total_ms, backend)
        # optimizer-pass decisions: which strategies fired and why
        # (reference analyze.rs renders per-rule effects the same way)
        stages = table["stage"].to_pylist() + ["── optimizer passes ──"]
        mets = table["metrics"].to_pylist() + [""]
        for p, d, n_fired in trace.summary():
            if d is None:
                continue  # decision point never reached for this plan shape
            mark = "fired" if d.fired else "skipped"
            extra = "".join(f" {k}={v}" for k, v in d.attrs.items())
            count = f" x{n_fired}" if n_fired > 1 else ""
            stages.append(f"  {p.name}")
            mets.append(f"{mark}{count}: {d.why}{extra}")
        return pa.table({"stage": stages, "metrics": mets})


def _note_passes_on_span(root, trace) -> None:
    """Optimizer decisions -> span attributes: `pass.<name>` per fired
    pass plus the `agg_strategy` verdict as a first-class attribute (the
    ISSUE's 'agg_strategy verdict as an attribute' contract).  Advisory:
    a failure here never owns the query."""
    try:
        for p, d, n_fired in trace.summary():
            if d is None or not d.fired:
                continue
            extra = "".join(f" {k}={v}" for k, v in d.attrs.items())
            root.attributes[f"pass.{p.name}"] = f"{d.why}{extra}"
        for d in reversed(trace.decisions):
            if d.name == "agg_strategy":
                root.attributes["agg_strategy"] = (
                    d.attrs.get("strategy")
                    or ("fired" if d.fired else "sort")
                )
                break
    except Exception:  # noqa: BLE001 — observability is advisory
        pass


def _merge_subplan_results(tables, split) -> pa.Table:
    """Frontend side of the sub-plan boundary: concatenate the bounded
    region results and re-apply merge sort + exact offset/limit (reference
    MergeScanExec stream merge + the upper plan, merge_scan.rs:186)."""
    from .logical_plan import Limit, Sort, TableScan

    non_empty = [t for t in tables if t.num_rows]
    if non_empty:
        merged = pa.concat_tables(non_empty, promote_options="permissive")
    else:
        merged = tables[0] if tables else pa.table({})
    plan: object = TableScan(table="__merged")
    if split.merge_sort:
        plan = Sort(plan, split.merge_sort)
    if split.limit is not None:
        plan = Limit(plan, split.limit, split.offset)
    if isinstance(plan, TableScan):
        return merged
    return CpuExecutor(lambda _scan: merged).execute(plan)


def _x64_enabled() -> bool:
    import jax

    return bool(jax.config.jax_enable_x64)
