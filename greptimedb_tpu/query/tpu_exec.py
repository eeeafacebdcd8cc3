"""TPU physical planner: recognize lowerable plans, execute on the mesh.

Role-equivalent of the north-star `TpuPhysicalPlanner` (BASELINE.json): it
pattern-matches the scan -> filter -> time-bucketed GROUP BY aggregate shape
(the same boundary the reference's DistPlannerAnalyzer pushes below
MergeScan, reference query/src/dist_plan/analyzer.rs) and lowers it to the
mesh executor in `parallel/executor.py`.  Anything it cannot prove lowerable
returns None and the CPU path runs — the reference's
`query.execution.backend` gating with CPU authoritative.

Post-aggregation operators (HAVING / projection arithmetic / ORDER BY /
LIMIT) run on the CPU executor over the small aggregated result — the same
split as the reference's frontend-side upper plan.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import pyarrow as pa

from ..datatypes.schema import Schema, SemanticType
from ..utils import metrics
from .cpu_exec import CpuExecutor
from .expr import AggCall, Alias, Column, Expr, FuncCall, Literal, strip_alias
from .logical_plan import (
    Aggregate,
    Filter,
    Having,
    Limit,
    LogicalPlan,
    Project,
    Sort,
    TableScan,
)

LOWERABLE_AGGS = {"sum", "avg", "min", "max", "count", "last_value"}


@dataclass
class Lowering:
    """A proven-lowerable plan: the scan+aggregate for the device, and the
    post-plan (relative to the aggregate output) for the host."""

    scan: TableScan
    group_tags: list[str]
    bucket: tuple[str, int, int] | None  # (ts_col, interval, origin_hint)
    agg_specs: list[tuple[str, str | None]]  # (func, col or None for count(*))
    post_ops: list[LogicalPlan] = field(default_factory=list)  # outer-first
    group_exprs: list[Expr] = field(default_factory=list)
    agg_exprs: list[Expr] = field(default_factory=list)
    # indices into post_ops the DEVICE program already applied (set by the
    # tile executor when Sort/LIMIT/HAVING finalized on device — see
    # query/device_finalize.py); _run_post_ops skips exactly these
    post_done: frozenset = frozenset()


def _post_has_subquery(node) -> bool:
    from .expr import Expr, PlannedSubquery, Subquery

    exprs: list = []
    if isinstance(node, Having):
        exprs.append(node.predicate)
    elif isinstance(node, Project):
        exprs.extend(node.exprs)
    elif isinstance(node, Sort):
        exprs.extend(e for e, _asc in node.keys)
    for e in exprs:
        if isinstance(e, Expr) and any(
            isinstance(x, (Subquery, PlannedSubquery)) for x in e.walk()
        ):
            return True
    return False


def try_lower(plan: LogicalPlan, schema: Schema) -> Lowering | None:
    """Walk from the root: collect post-aggregation ops until the Aggregate,
    then prove Aggregate(TableScan) matches the kernel shape."""
    post: list[LogicalPlan] = []
    node = plan
    while isinstance(node, (Limit, Sort, Project, Having)):
        if _post_has_subquery(node):
            # the post-op replay resolves every TableScan to the kernel's
            # RESULT table — a scalar subquery over a real table would
            # silently read the wrong data (caught by having_subquery)
            return None
        post.append(node)
        node = node.children()[0]
    if not isinstance(node, Aggregate):
        return None
    agg = node
    if not isinstance(agg.input, TableScan):
        return None  # residual Filter exprs block lowering (non-simple preds)
    scan = agg.input

    ts_col = schema.time_index.name if schema.time_index else None
    tag_names = {c.name for c in schema.tag_columns()}
    field_names = {c.name for c in schema.field_columns()}

    group_tags: list[str] = []
    bucket: tuple[str, int, int] | None = None
    for ge in agg.group_exprs:
        e = strip_alias(ge)
        if isinstance(e, Column) and e.column in tag_names:
            group_tags.append(e.column)
        elif isinstance(e, FuncCall) and e.func in ("time_bucket", "date_bin"):
            if bucket is not None:
                return None  # at most one time bucket dimension
            if len(e.args) < 2 or not isinstance(e.args[1], Column):
                return None
            if e.args[1].column != ts_col:
                return None
            if not isinstance(e.args[0], Literal):
                return None
            from .sql_parser import _parse_interval

            iv = e.args[0].value
            interval_ms = _parse_interval(iv) if isinstance(iv, str) else int(iv)
            origin = 0
            if len(e.args) > 2:
                if not isinstance(e.args[2], Literal) or not isinstance(e.args[2].value, (int, float)):
                    return None
                origin = int(e.args[2].value)
            bucket = (ts_col, interval_ms, origin)
        else:
            return None

    agg_specs: list[tuple[str, str | None]] = []
    for ae in agg.agg_exprs:
        inner = strip_alias(ae)
        if not isinstance(inner, AggCall):
            return None  # arithmetic over aggs not lowered yet
        func = "avg" if inner.func == "mean" else inner.func
        if func not in LOWERABLE_AGGS:
            return None
        if inner.distinct:
            return None  # count(DISTINCT x) has no segment-sum lowering
        if inner.arg is None:
            agg_specs.append(("count", None))
            continue
        if not isinstance(inner.arg, Column) or inner.arg.column not in field_names:
            return None
        col_schema = schema.column(inner.arg.column)
        if not col_schema.data_type.is_numeric():
            return None
        if getattr(col_schema.data_type, "value", "") in ("int64", "uint64"):
            # BIGINT aggregates stay on the authoritative CPU path: the
            # device kernels accumulate in float64, whose 53-bit mantissa
            # cannot represent int64 extremes exactly (the reference
            # returns exact int64 for min/max/sum)
            return None
        if func == "last_value" and inner.order_by not in (None, ts_col):
            return None
        agg_specs.append((func, inner.arg.column))
    if not agg_specs:
        return None

    return Lowering(
        scan=scan,
        group_tags=group_tags,
        bucket=bucket,
        agg_specs=agg_specs,
        post_ops=post,
        group_exprs=agg.group_exprs,
        agg_exprs=agg.agg_exprs,
    )


class TpuExecutor:
    """Executes lowered plans on the device mesh; delegates post-ops to CPU.

    When a tile executor is wired in (the HBM-resident SST tile cache,
    parallel/tile_cache.py), it is tried FIRST: warm queries skip the
    Arrow scan + re-encode + upload entirely and go straight to one
    compiled dispatch over cached device tiles."""

    def __init__(
        self,
        mesh,
        region_scan_provider,
        acc_dtype: str = "float64",
        tile_executor=None,
        tile_context_provider=None,
    ):
        # region_scan_provider(scan: TableScan) -> list[pa.Table], one per region
        self.mesh = mesh
        self.region_scan = region_scan_provider
        self.acc_dtype = acc_dtype
        self.tile_executor = tile_executor
        self.tile_context_provider = tile_context_provider
        # why the last `try_tile` found no answer on the tile path
        self.declined: str | None = None

    def try_tile(self, lowering: Lowering, schema: Schema, time_bounds) -> pa.Table | None:
        """HBM super-tile path only: the standalone hot path.  Returns the
        finished result table, or None when the tile executor doesn't
        apply (caller then weighs dist-state shipping vs the mesh path);
        `self.declined` then says why."""
        from .analyze import stage

        scan = lowering.scan
        self.declined = "no tile context for the table"
        if self.tile_executor is None or self.tile_context_provider is None:
            return None
        ctx = self.tile_context_provider(scan)
        if ctx is None:
            return None
        from ..utils import flight_recorder

        with stage("tpu.tile_cache") as info:
            # per-query transfer vs host-decode split of the readback
            # (greptime_tpu_readback_{transfer,decode}_ms): surfaces in
            # EXPLAIN ANALYZE so streamed-readback wins are attributable
            # per query.  Thread-local on the executor — execute() runs
            # on THIS thread, and global-metric deltas would cross-
            # attribute concurrent queries' readbacks.
            rbl = getattr(self.tile_executor, "_rb_local", None)
            if rbl is not None:
                rbl.transfer_ms = rbl.decode_ms = None
            flight_recorder.clear_last()
            table = self.tile_executor.execute(
                lowering, schema, lambda: time_bounds(), ctx
            )
            info["hit"] = table is not None
            if table is None:
                from ..parallel.tile_cache import last_decline
                from ..utils import tracing

                self.declined = last_decline()
                root = tracing.current_span()
                if root is not None:
                    root.attributes["declined"] = self.declined
            if (
                table is not None
                and rbl is not None
                and getattr(rbl, "transfer_ms", None) is not None
            ):
                info["readback_transfer_ms"] = round(rbl.transfer_ms, 2)
                info["readback_decode_ms"] = round(rbl.decode_ms or 0.0, 2)
            if table is not None:
                self._analyze_device_stages(flight_recorder)
        if table is None:
            return None
        with stage("tpu.post_ops"):
            return self._shape_output(table, lowering, schema)

    @staticmethod
    def _analyze_device_stages(flight_recorder):
        """Render the flight recorder's per-stage device split for this
        query into the EXPLAIN ANALYZE tree: the REAL measured stage
        milliseconds (upload/compile/dispatch/readback-transfer/
        readback-decode) plus one line per region build leg, replacing
        the coarse tile_cache total as the only device evidence.  No-op
        when EXPLAIN ANALYZE is not running or the recorder is off."""
        from . import analyze

        if analyze.active_collector() is None:
            return
        rec = flight_recorder.last_record()
        if rec is None:
            return
        if rec.strategy == "result_cache":
            # zero-dispatch serve: the one line that matters is WHY
            analyze.record("device.result_cache", result_cache="hit")
            if rec.flags:
                analyze.record("device.flags", flags=",".join(rec.flags))
            return
        if rec.strategy == "fused_batch":
            # this query's math ran as one branch of a mega-fused batch
            # tick: the stage times are TICK-level (shared by every
            # member), so render them under the fused header instead of
            # pretending they were paid per query
            members = next(
                (f.split("=", 1)[1] for f in rec.flags
                 if f.startswith("members=")),
                "?",
            )
            analyze.record("device.fused_batch", members=members)
            for name in ("compile", "dispatch", "readback_transfer"):
                ms = rec.stage_ms(name)
                attrs = {"shared": True}
                if name == "compile" and rec.compile_cache:
                    attrs["cache"] = rec.compile_cache
                if name == "readback_transfer" and rec.bytes_down:
                    attrs["bytes"] = rec.bytes_down
                analyze.timed(f"device.{name}", ms, **attrs)
            if rec.flags:
                analyze.record("device.flags", flags=",".join(rec.flags))
            return
        for name in flight_recorder.STAGES:
            ms = rec.stage_ms(name)
            attrs = {}
            if name == "compile" and rec.compile_cache:
                attrs["cache"] = rec.compile_cache
            if name == "dispatch":
                attrs["strategy"] = rec.strategy
                if rec.mesh_devices:
                    attrs["mesh_devices"] = rec.mesh_devices
            if name == "upload" and rec.bytes_up:
                attrs["bytes"] = rec.bytes_up
            if name == "readback_transfer" and rec.bytes_down:
                attrs["bytes"] = rec.bytes_down
            analyze.timed(f"device.{name}", ms, **attrs)
        for region_id, mode, build_ms, rows in rec.regions:
            analyze.timed(
                "device.region", build_ms,
                region=region_id, mode=mode, rows=rows,
            )
        if rec.flags:
            analyze.record("device.flags", flags=",".join(rec.flags))

    def execute(self, lowering: Lowering, schema: Schema, time_bounds) -> pa.Table:
        """time_bounds: callback () -> (min_ts, max_ts) over the scanned data,
        used when the query has no explicit time range (bucket count must be
        static for XLA)."""
        from ..parallel.executor import distributed_groupby
        from .analyze import stage

        scan = lowering.scan
        table = self.try_tile(lowering, schema, time_bounds)
        if table is not None:
            return table
        if lowering.bucket is not None:
            ts_col, interval, origin_hint = lowering.bucket
            if scan.time_range is not None and scan.time_range[0] > -(1 << 61) and scan.time_range[1] < (1 << 61):
                lo, hi = scan.time_range
            else:
                lo, hi = time_bounds()
                hi += 1  # bounds are inclusive; range is half-open
            unit_ns = schema.time_index.data_type.timestamp_unit_ns()
            interval_native = max(int(interval * 1_000_000) // max(unit_ns, 1), 1)
            origin = origin_hint + ((lo - origin_hint) // interval_native) * interval_native
            n_buckets = max(int((hi - origin + interval_native - 1) // interval_native), 1)
            bucket_col = ts_col
        else:
            bucket_col, interval_native, origin, n_buckets = None, 1, 0, 1

        with stage("tpu.region_scan") as info:
            region_tables = self.region_scan(scan)
            info["regions"] = len(region_tables)
            info["rows"] = sum(t.num_rows for t in region_tables)
        needs_ts = any(f == "last_value" for f, _ in lowering.agg_specs)
        from ..utils import flight_recorder

        with stage("tpu.device_groupby") as info, \
                flight_recorder.dispatch_scope(
                    table=f"{scan.database}.{scan.table}",
                    strategy="mesh_table",
                ):
            result = distributed_groupby(
                self.mesh,
                region_tables,
                group_tags=lowering.group_tags,
                bucket_col=bucket_col,
                bucket_origin=origin,
                bucket_interval=interval_native,
                n_buckets=n_buckets,
                agg_specs=[(f, c) for f, c in lowering.agg_specs],
                filters=list(scan.filters),
                acc_dtype=self.acc_dtype,
                ts_col=schema.time_index.name if needs_ts and schema.time_index else None,
            )
            table = result.to_table()
            info["groups"] = table.num_rows
        metrics.TPU_LOWERED_TOTAL.inc()
        with stage("tpu.post_ops"):
            return self._shape_output(table, lowering, schema)

    def _shape_output(self, table: pa.Table, lowering: Lowering, schema: Schema) -> pa.Table:
        """Kernel output -> SQL result: plan names, empty-input semantics,
        host-side post ops.  Shared by the mesh and tile-cache paths."""
        table = self._rename_to_plan_names(table, lowering, schema)
        if (
            not lowering.group_tags
            and lowering.bucket is None
            and table.num_rows == 0
        ):
            # SQL semantics: an ungrouped aggregate over empty input yields
            # one row — count()=0, everything else null
            cols = {}
            for ae in lowering.agg_exprs:
                inner = strip_alias(ae)
                is_count = isinstance(inner, AggCall) and inner.func == "count"
                cols[inner.name()] = pa.array(
                    [0 if is_count else None],
                    pa.int64() if is_count else pa.float64(),
                )
            table = pa.table(cols)
        return self._run_post_ops(table, lowering)

    def _rename_to_plan_names(self, table: pa.Table, lowering: Lowering, schema: Schema) -> pa.Table:
        """Kernel output names -> the plan's expression names, and bucket ts
        ints -> the time index's timestamp type."""
        rename: dict[str, str] = {}
        for ge in lowering.group_exprs:
            e = strip_alias(ge)
            if isinstance(e, FuncCall) and lowering.bucket is not None:
                rename[lowering.bucket[0]] = ge.name() if not isinstance(ge, Alias) else e.name()
        for ae in lowering.agg_exprs:
            inner = strip_alias(ae)
            assert isinstance(inner, AggCall)
            kernel_name = f"{'avg' if inner.func == 'mean' else inner.func}({inner.arg.column})" if inner.arg is not None else "count(*)"
            if inner.func == "last_value" and inner.arg is not None:
                kernel_name = f"last_value({inner.arg.column})"
            rename[kernel_name] = inner.name()
        cols, names = [], []
        for name in table.column_names:
            out_name = rename.get(name, name)
            col = table[name]
            if lowering.bucket is not None and name == lowering.bucket[0]:
                col = col.cast(schema.time_index.data_type.to_arrow())
            cols.append(col)
            names.append(out_name)
        return pa.table(dict(zip(names, cols)))

    def _run_post_ops(self, table: pa.Table, lowering: Lowering) -> pa.Table:
        """Replay Having/Project/Sort/Limit over the aggregated table with
        the CPU executor (the small, frontend-side upper plan).  Operators
        the device program already finalized (lowering.post_done — on-device
        Sort/LIMIT/HAVING over the [K, G] states) are skipped; the replay
        order of the rest is preserved, which stays correct because the
        skipped set is always an inner prefix modulo pass-through Projects
        (see query/device_finalize.py)."""
        remaining = [
            op
            for i, op in enumerate(lowering.post_ops)
            if i not in lowering.post_done
        ]
        if not remaining:
            return table
        # Rebuild the post-plan bottom-up over a scan of the result table.
        plan: LogicalPlan = TableScan(table="__tpu_result")
        for op in reversed(remaining):
            if isinstance(op, Having):
                plan = Having(plan, op.predicate)
            elif isinstance(op, Project):
                plan = Project(plan, op.exprs)
            elif isinstance(op, Sort):
                # keep the per-key NULLS FIRST/LAST spec — dropping it
                # made the merged-states path diverge from standalone on
                # ORDER BY <nullable tag> (caught by null_groups_dist)
                plan = Sort(plan, op.keys, nulls=op.nulls)
            elif isinstance(op, Limit):
                plan = Limit(plan, op.limit, op.offset)
        cpu = CpuExecutor(lambda _scan: table)
        return cpu.execute(plan)
