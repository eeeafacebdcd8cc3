"""Distributed group-by execution over a device mesh.

TPU-native equivalent of the reference's distributed planner + MergeScan
(reference query/src/dist_plan/merge_scan.rs, commutativity.rs): each device
owns one region shard of the scan, computes the lower/state aggregate with
segment reductions, and the upper/merge aggregate rides an all-reduce
(psum/pmin/pmax) over the `regions` mesh axis — replacing the reference's
N:1 Flight stream merge at the frontend.

`compute_partial_states` below is the shared lower stage for BOTH this
table-fed mesh path and the HBM super-tile executor — including its
promoted multi-chip form (parallel/tile_cache.py `_mesh_merge_program`,
`tile.mesh_devices`), which runs the same per-source math under shard_map
and merges with the same psum/pmin/pmax collectives plus an
order-preserving fold for float sums.

Host-side responsibilities (the "frontend" role):
  - union tag dictionaries across region tables so codes agree globally
    (the reference ships dictionary mappings inside Flight IPC frames,
    common/grpc/src/flight.rs:48-63 — here codes must agree BEFORE upload);
  - pad every shard to one static shape and stack to [D, N];
  - decode finalized group ids back to (tags..., bucket timestamp) rows.

Cardinalities are quantized to powers of two so per-query recompiles are
bounded; out-of-range rows fall into the masked overflow slot.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
from jax.sharding import Mesh, PartitionSpec as P


from ..ops.aggregate import (
    BLOCK_ROWS,
    _FAST_MIN_ROWS,
    AggState,
    finalize,
    fold_series_states,
    hash_group_slots,
    limb_segment_sums,
    psum_states,
    quantize_limbs,
    raw_group_ids,
    segment_aggregate,
    series_rows,
    time_bucket,
)
from ..ops.tiles import TileBatch, padded_size, tiles_from_table
from .mesh import REGION_AXIS

COUNT_STAR = "__count_star"  # pseudo-column for count(*)
# a series plan's per-source inputs, carried among the source's columns
# (see compute_partial_states)
SERIES_MARKS, SERIES_ROWS, SERIES_GROUP = (
    "__series_marks", "__series_rows", "__series_group",
)
SERIES_KEYS = (SERIES_MARKS, SERIES_ROWS, SERIES_GROUP)

# SQL agg func -> kernel agg name
_FUNC_TO_KERNEL = {
    "sum": "sum",
    "count": "count",
    "min": "min",
    "max": "max",
    "avg": "avg",
    "last_value": "last",
}


@dataclass(frozen=True)
class DistGroupByPlan:
    """Static (hashable) description of a scan->filter->groupby aggregate.

    The jit cache key: two queries with the same plan structure share one
    compiled executable.  agg_specs is ((func, value_col), ...).
    """

    group_tags: tuple[str, ...]
    tag_cards: tuple[int, ...]
    bucket_col: str | None
    bucket_origin: int
    bucket_interval: int
    n_buckets: int
    agg_specs: tuple[tuple[str, str], ...]
    filters: tuple[tuple[str, str, object], ...] = ()
    acc_dtype: str = "float64"
    ts_col: str | None = None  # needed for last_value ordering
    # nullable filter columns whose present-mask must gate the row mask
    # (SQL: NULL never satisfies a predicate); the table-based path
    # pre-filters on the host so this only matters for the tile path
    filter_null_cols: tuple[str, ...] = ()
    # Series grouping (ops/aggregate.py fold_series_states): where the
    # requested group keys are not the leading columns of a wider primary
    # key, or lead a key the table is partitioned on (a region holds every
    # n-th code of that tag), stage 1 groups by the source's SERIES
    # ORDINAL (one run of the (pk, ts) sorted rows per series) x bucket
    # over `series_slots` padded series, and the state folds down to
    # `group_tags` on device
    # through a [series_slots] series -> group-code table.  The stage-1
    # space is the series present, never the product of the key's tag
    # cardinalities.  0 = the gid is composed over `group_tags` directly.
    series_slots: int = 0
    # A series plan over the regions of a table partitioned on its leading
    # key tag, where a region's series stand in for that tag's table-wide
    # codes (counted by TILE_ORDINAL_GIDS); it changes no program.
    partitioned: bool = False
    # Time-major execution: sources are gathered through a ts-ascending
    # permutation before aggregation, making `gid = bucket` globally
    # non-decreasing for ANY bucket interval (bucket-only group-bys like
    # TSBS single-groupby / groupby-orderby-limit).
    time_major: bool = False
    # Blocked-kernel span (ops/aggregate.py): sized by the planner from
    # expected groups-per-block so layouts with more than 16 consecutive
    # groups per 4096-row block (e.g. hour buckets over long windows)
    # still take the scatter-free kernel.
    block_span: int = 16
    # Device group-by strategy (the `agg_strategy` planner pass):
    # "sort" = the dense mixed-radix path above (states are [G], the
    # (pk, ts) sort makes the blocked kernel engage);
    # "hash" = group ids hash into a `hash_slots`-sized device table
    # (ops/aggregate.hash_group_slots) threaded through every source of
    # the query, states are [hash_slots + 1] and the host decodes slot ->
    # group key from the table — the dense [G] space never materializes,
    # so group spaces far past max_groups stay executable.
    agg_strategy: str = "sort"
    hash_slots: int = 0

    @property
    def num_groups(self) -> int:
        """Output group-space size (the [G] the caller sees)."""
        g = 1
        for c in self.tag_cards:
            g *= c
        if self.bucket_col is not None:
            g *= self.n_buckets
        return g

    @property
    def internal_groups(self) -> int:
        """Stage-1 group-space size (= num_groups unless series-grouped)."""
        if not self.series_slots:
            return self.num_groups
        return self.series_slots * (
            self.n_buckets if self.bucket_col is not None else 1
        )

    def value_cols(self) -> list[str]:
        out = []
        for _f, c in self.agg_specs:
            if c != COUNT_STAR and c not in out:
                out.append(c)
        return out


def streamed_device_get(parts: list, chunk_bytes: int = 1 << 20) -> list:
    """Chunked device->host fetch with transfer/host-copy overlap: each
    part is sliced (flat) into ~chunk_bytes device_gets, and slice i+1's
    transfer is in flight on a helper thread while slice i copies into
    its preallocated host destination — the host-side "decode" work rides
    under the wire time instead of serializing after it.  The caller's
    one-logical-fetch contract holds: this IS the query's single result
    readback, just pipelined.

    Returns numpy arrays matching `parts`' shapes/dtypes, bit-identical
    to a plain jax.device_get (tests assert it)."""
    outs: list[np.ndarray] = []
    flats: list = []
    jobs: list[tuple[int, int, int]] = []
    for pi, p in enumerate(parts):
        out = np.empty(p.shape, np.dtype(p.dtype))
        outs.append(out)
        flats.append(p.reshape(-1))
        n = int(out.size)
        if n == 0:
            continue
        per = max(chunk_bytes // max(out.itemsize, 1), 1)
        for a in range(0, n, per):
            jobs.append((pi, a, min(a + per, n)))
    if not jobs:
        return outs

    def fetch(job):
        # the device slice materializes HERE, just before its fetch, so
        # at most two slices are alive at once — building every slice up
        # front would dispatch all of them and double the result's device
        # footprint on exactly the memory-pressured paths streaming is for
        pi, a, b = job
        return jax.device_get(flats[pi][a:b])

    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(
        max_workers=1, thread_name_prefix="readback"
    ) as pool:
        fut = pool.submit(fetch, jobs[0])
        for i, (pi, a, b) in enumerate(jobs):
            got = fut.result()
            if i + 1 < len(jobs):
                fut = pool.submit(fetch, jobs[i + 1])
            outs[pi].reshape(-1)[a:b] = got
    return outs


def _quantize_card(n: int) -> int:
    p = 1
    while p < max(n, 1):
        p <<= 1
    return p


def _apply_filters(plan: DistGroupByPlan, columns, mask, values=None):
    """Evaluate pushed-down predicates.  `values` (optional) supplies the
    literals as RUNTIME arguments — the tile path passes them dynamically
    so changing a literal reuses the compiled program; the mesh path bakes
    them into the plan (position i of `values` pairs with filter i)."""
    for i, (name, op, static_v) in enumerate(plan.filters):
        value = static_v if values is None else values[i]
        col = columns[name]
        if op == "=":
            mask = mask & (col == value)
        elif op == "!=":
            mask = mask & (col != value)
        elif op == "<":
            mask = mask & (col < value)
        elif op == "<=":
            mask = mask & (col <= value)
        elif op == ">":
            mask = mask & (col > value)
        elif op == ">=":
            mask = mask & (col >= value)
        elif op == "in":
            m = jnp.zeros_like(mask)
            for v in value:
                m = m | (col == v)
            mask = mask & m
        elif op == "not in":
            for v in value:
                mask = mask & (col != v)
    return mask


def compute_partial_states(plan: DistGroupByPlan, columns, valid, nulls, dyn=None, perm=None, count_cols=None, limbs=None, hash_table=None):
    """Shared lower/state stage: mask -> group ids -> partial AggStates.
    No collectives — callers merge across devices (psum) or across tile
    sources (merge_states).  `dyn` optionally carries runtime-dynamic plan
    parameters: {'filter_values', 'bucket_origin', 'bucket_interval'} —
    only shapes (cards, n_buckets, filter structure) stay compile-static.
    `perm` (time-major plans) re-gathers every per-row array into
    ts-ascending order first, so bucket-composed gids are sorted.
    `count_cols` fixes WHICH columns carry their own null-gated count
    pass: multi-source callers (the tile program) must pass the union
    decision so every source produces structurally identical AggStates —
    deciding per-source from `col in nulls` made merge_states silently
    drop counts (or crash) when sources disagreed on a column's
    nullability.  None = decide from this source's nulls (single-source
    mesh path).

    `plan.acc_dtype == "limb"` routes sum/avg/count columns through the
    MXU limb kernel (ops/aggregate.py `limb_segment_sums`) — one batched
    matmul for ALL such columns instead of a per-column VPU pass; min/max/
    last keep the f64 blocked kernels.  `limbs` optionally supplies cached
    quantized planes per column (dict col -> (limbs, scale)); missing
    columns quantize in-program from their f64 plane.

    With `plan.series_slots` the gid is the row's series ordinal in THIS
    source x bucket, and every state leaves here folded to the group
    space (`fold_series_states`).  The source carries its series among
    its columns, as `SERIES_GROUP` (the [series_slots] group code of each
    of its series, -1 past them) and either `SERIES_MARKS` (the row at
    which each series opens, for (pk, ts) sorted planes) or `SERIES_ROWS`
    (each row's ordinal, for a memtable tail in arrival order).

    With `plan.agg_strategy == "hash"` the caller must pass `hash_table`
    (the [hash_slots] int64 key table threaded across this query's
    sources) and gets back `(states, hash_table')`: group ids are
    composed in int64 (the sparse space may exceed int32), hashed to
    compact slots, and every kernel aggregates into [hash_slots + 1]
    scatter-space — the dense [G] never exists on device.  States carry
    an extra `__hash_overflow` row counting rows the table could not
    place (sum-merged across sources) so the executor can fall back to
    the dense path instead of ever returning a wrong result."""
    acc = jnp.float32 if plan.acc_dtype == "float32" else jnp.float64
    series = {k: columns[k] for k in SERIES_KEYS if k in columns}
    if series:
        columns = {k: v for k, v in columns.items() if k not in series}
    if perm is not None:
        columns = {k: v[perm] for k, v in columns.items()}
        valid = valid[perm]
        nulls = {k: v[perm] for k, v in nulls.items()}
        # cached limb planes encode the UNpermuted block layout — they
        # cannot be row-gathered (block scales would be wrong); callers
        # with a perm must supply order-matched limbs (time-major planes)
        # or none at all
        limbs = None
    mask = _apply_filters(
        plan, columns, valid, None if dyn is None else dyn["filter_values"]
    )
    for c in plan.filter_null_cols:
        if c in nulls:
            mask = mask & nulls[c]

    components: list[tuple[jnp.ndarray, int]] = []
    internal = plan.internal_groups
    n_per = plan.n_buckets if plan.bucket_col is not None else 1
    if plan.series_slots:
        # the source's own series table sizes its stage 1 (a memtable
        # tail may hold more series than the planes' padded count)
        slots = series[SERIES_GROUP].shape[0]
        if SERIES_ROWS in series:
            sid = series[SERIES_ROWS]
        else:
            sid = series_rows(series[SERIES_MARKS], valid.shape[0])
        components.append((sid, slots))
        internal = slots * n_per
    else:
        for tag, card in zip(plan.group_tags, plan.tag_cards):
            components.append((columns[tag], card))
    if plan.bucket_col is not None:
        origin = plan.bucket_origin if dyn is None else dyn["bucket_origin"]
        interval = plan.bucket_interval if dyn is None else dyn["bucket_interval"]
        b = time_bucket(columns[plan.bucket_col], origin, interval)
        components.append((b, plan.n_buckets))
    is_hash = plan.agg_strategy == "hash"
    overflow = None
    if is_hash:
        if hash_table is None:
            raise ValueError("hash agg strategy requires the threaded hash_table")
        # int64 ids: the SPARSE space may exceed int32 — it never
        # materializes, only its occupied keys do (one per table slot)
        gid64, in_range = raw_group_ids(
            components, shape=valid.shape, dtype=jnp.int64
        )
        active = mask & in_range
        hash_table, gids, overflow = hash_group_slots(hash_table, gid64, active)
        mask = active
        n_internal = plan.hash_slots
    else:
        n_internal = internal
        # raw in-range ids + mask (NOT overflow-encoded): keeps scan-order
        # sortedness intact so segment_aggregate's block kernel can engage.
        # Tail padding rows (valid=False) get the max id so they don't break
        # the ascending-order guard; their mask keeps them out of every sum.
        gids, in_range = raw_group_ids(components, shape=valid.shape)
        mask = mask & in_range
        gids = jnp.where(valid, gids, n_internal - 1)

    ts = None
    if plan.ts_col is not None and plan.ts_col in columns:
        ts = columns[plan.ts_col]

    # Columns sharing an aggregate set are STACKED into one
    # segment_aggregate_multi call — one layout guard, one compiled branch
    # trio, vmapped over columns (compile and guard cost stop scaling with
    # column count).  "count" is always included: it doubles as the
    # per-column null mask for SQL NULL semantics (sum over an all-null
    # group is NULL, not 0).  last_value keeps the per-column path (needs
    # the ts-ordered two-pass kernel).
    from ..ops.aggregate import segment_aggregate_multi

    if plan.series_slots:
        def fold(state: AggState) -> AggState:
            return fold_series_states(
                state, series[SERIES_GROUP], plan.num_groups // n_per, n_per
            )
    else:
        def fold(state: AggState) -> AggState:
            return state

    per_col_aggs: dict[str, set] = {}
    for func, col in plan.agg_specs:
        per_col_aggs.setdefault(col, set()).add(_FUNC_TO_KERNEL[func])
    states = {}
    groups: dict[tuple, list[str]] = {}
    last_presence: str | None = None
    n_rows = valid.shape[0]
    # Limb routing is decided from the PLAN alone (never per-source size):
    # every source of a multi-source program must emit structurally
    # identical AggStates or merge_states breaks — sources too small for
    # the limb geometry take segment_sums_scatter, which produces the
    # same trio exactly.
    limb_mode = plan.acc_dtype == "limb"
    limb_fits = n_rows >= _FAST_MIN_ROWS and n_rows % BLOCK_ROWS == 0
    limb_batch: list[tuple[str, bool]] = []  # (col, counted)
    for col, aggs in per_col_aggs.items():
        if "last" in aggs:
            # LAST states are placed, never folded: the planner builds a
            # series plan with last_value only where each series is a group
            key = tuple(sorted(aggs | {"count"}))
            col_mask = mask & nulls[col] if col in nulls else mask
            if col not in nulls:
                last_presence = col  # its count IS the presence count
            states[col] = fold(segment_aggregate(
                columns[col], gids, n_internal, key,
                mask=col_mask, ts=ts, acc_dtype=acc, span=plan.block_span,
                force_scatter=is_hash,
            ))
            continue
        # Count-pass sharing: for a column with NO null mask, its count
        # equals the group presence count, so the per-column kernel skips
        # the count pass entirely — at TSBS scale (10 avg columns, no
        # nulls) this halves device work.  Null-bearing columns keep their
        # own count (SQL NULL-gating).  count(*) is presence by definition.
        if col == COUNT_STAR:
            continue  # presence covers it
        null_gated = (col in count_cols) if count_cols is not None else (col in nulls)
        kernel_aggs = set()
        if "sum" in aggs or "avg" in aggs:
            kernel_aggs.add("sum")
        if "min" in aggs:
            kernel_aggs.add("min")
        if "max" in aggs:
            kernel_aggs.add("max")
        if null_gated:
            kernel_aggs.add("count")
        elif not kernel_aggs:
            continue  # count(col) on a non-null column: presence covers it
        if limb_mode and "sum" in kernel_aggs:
            # sum + null-gated count ride the MXU batch; min/max (order
            # statistics have no matmul form) keep the blocked kernel,
            # and count-only columns stay on their near-free count pass
            limb_batch.append((col, null_gated))
            kernel_aggs -= {"sum", "count"}
        if kernel_aggs:
            groups.setdefault(tuple(sorted(kernel_aggs)), []).append(col)
    # Presence fusing: a NON-null-gated value column counts exactly the
    # base-mask rows, which IS the group presence — ride its kernel pass
    # (the count reduction fuses with the column's sum/min/max over the
    # same one-hot, nearly free) instead of spending a whole separate
    # pass on a pseudo-column.  Only when every column is null-gated (or
    # there are none) does presence pay its own pass.  The limb batch
    # carries presence for free (its ones column), so it wins outright.
    presence_from: str | None = None
    if not limb_batch:
        for key in list(groups):
            if "count" in key:
                continue
            cols = groups[key]
            rep = cols[0]
            if len(cols) == 1:
                del groups[key]
            else:
                groups[key] = cols[1:]
            groups.setdefault(tuple(sorted(set(key) | {"count"})), []).insert(0, rep)
            presence_from = rep
            break
        if presence_from is None and last_presence is not None:
            presence_from = last_presence
        if presence_from is None:
            # pseudo-column whose "values" are the mask itself
            groups.setdefault(("count",), []).append("__presence")
    for key, cols in groups.items():
        # per-column lists, never a stacked [C, n] (HBM: see
        # segment_aggregate_multi); count-only pseudo-columns reuse the
        # mask as a dummy values array — counts come from the mask alone
        vals = [
            mask if c in ("__presence", COUNT_STAR) else columns[c].astype(acc)
            for c in cols
        ]
        col_masks = [
            mask & nulls[c] if c in nulls else mask
            for c in cols
        ]
        multi = segment_aggregate_multi(
            vals, gids, n_internal, key, col_masks, mask, acc_dtype=acc,
            span=plan.block_span, force_scatter=is_hash,
        )
        for i, c in enumerate(cols):
            states[c] = fold(AggState(
                sums=None if multi.sums is None else multi.sums[i],
                counts=None if multi.counts is None else multi.counts[i],
                mins=None if multi.mins is None else multi.mins[i],
                maxs=None if multi.maxs is None else multi.maxs[i],
            ))
    if limb_batch:
        count01 = [
            nulls[c] if (counted and c in nulls) else None
            for c, counted in limb_batch
        ]
        any_counted = any(counted for _c, counted in limb_batch)
        c01 = count01 if any_counted else None
        if limb_fits:
            limb_inputs = []
            for c, _counted in limb_batch:
                if limbs is not None and c in limbs:
                    limb_inputs.append(limbs[c])
                else:
                    limb_inputs.append(quantize_limbs(columns[c]))
            lsums, lerrs, lcounts, lpresence = limb_segment_sums(
                limb_inputs, gids, mask, n_internal, plan.block_span,
                count01=c01,
            )
        else:
            from ..ops.aggregate import segment_sums_scatter

            lsums, lerrs, lcounts, lpresence = segment_sums_scatter(
                [columns[c] for c, _counted in limb_batch],
                gids, mask, n_internal, count01=c01,
            )
        for i, (c, counted) in enumerate(limb_batch):
            st = fold(AggState(
                sums=lsums[i],
                counts=lcounts[i] if counted else None,
            ))
            prev = states.get(c)
            if prev is not None:  # min/max part from the blocked kernel
                st = AggState(
                    sums=st.sums, counts=st.counts,
                    mins=prev.mins, maxs=prev.maxs,
                    last_ts=prev.last_ts, last_val=prev.last_val,
                )
            states[c] = st
            # worst-case quantization error bound per group: merges by
            # addition and folds like a sum — the tile program checks it
            # against |sum| and reruns in exact f64 when it's too loose
            states["__limb_err:" + c] = fold(AggState(sums=lerrs[i]))
        states["__presence"] = fold(AggState(counts=lpresence))
    elif presence_from is not None:
        states["__presence"] = AggState(counts=states[presence_from].counts)
    if is_hash:
        # sum-merges across sources like any count; > 0 after the final
        # merge means some row never found a slot -> dense-path rerun
        states["__hash_overflow"] = AggState(counts=overflow.reshape(1))
        return states, hash_table
    return states


def _device_step(plan: DistGroupByPlan, columns, valid, nulls):
    """Per-device: partial states then psum merge over the mesh axis.
    Runs under shard_map; `nulls` maps value col -> present-mask."""
    states = compute_partial_states(plan, columns, valid, nulls)
    return {k: psum_states(v, REGION_AXIS) for k, v in states.items()}


@functools.lru_cache(maxsize=64)
def _compiled_step(mesh: Mesh, plan: DistGroupByPlan):
    def per_device(cols, valid, nulls):
        cols = {k: v[0] for k, v in cols.items()}
        nulls = {k: v[0] for k, v in nulls.items()}
        return _device_step(plan, cols, valid[0], nulls)

    # the outputs ARE replicated (every device folds the same gathered
    # partials), but min/max merge by all_gather + fold (mesh_min/mesh_max),
    # which the static replication checker cannot see through
    sharded = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=P(REGION_AXIS, None),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(sharded)


def host_last_winners(g, t, v, lexsort_cap: int = 1 << 22):
    """Numpy twin of the device last_value kernel for ONE source range:
    one (gid, ts, value) winner per gid present in `g`, where the winner
    is the max-ts row and a ts tie resolves to the LAST row in scan order
    (the device `_segment_blocked_last` highest-row-index rule — layout is
    (pk, ts, write-order) sorted, so that is exactly last-write-wins).

    Rows already sorted (gid non-decreasing, ts non-decreasing within each
    gid run) take the O(n)-compare run-boundary path; unsorted tails
    lexsort, whose STABLE order preserves the same tie rule.  Returns
    None when the range is unsorted beyond `lexsort_cap` rows (callers
    fall back to the device path).  Cross-source merging is the caller's
    job: fold winners in source order with ties going to the later source
    (`merge_states`' newer_or_tie rule)."""
    if not len(g):
        return g[:0], t[:0], v[:0]
    runs_ok = bool(np.all(g[1:] >= g[:-1])) and bool(
        np.all((g[1:] != g[:-1]) | (t[1:] >= t[:-1]))
    )
    if not runs_ok:
        if len(g) > lexsort_cap:
            return None
        order = np.lexsort((t, g))
        g, t, v = g[order], t[order], v[order]
    ends = np.append(np.flatnonzero(g[1:] != g[:-1]), len(g) - 1)
    return g[ends], t[ends], v[ends]


@dataclass
class GroupByResult:
    """Finalized aggregates plus the host-side group key decode."""

    outputs: dict[str, np.ndarray]  # "func(col)" -> [G]
    non_empty: np.ndarray
    tag_values: dict[str, list]
    plan: DistGroupByPlan
    # actual bucket geometry when the plan carries dynamic placeholders
    bucket_origin: int | None = None
    bucket_interval: int | None = None

    def to_table(self) -> pa.Table:
        idx = np.nonzero(self.non_empty)[0]
        cols: dict[str, object] = {}
        dims: list[tuple[str, int]] = list(zip(self.plan.group_tags, self.plan.tag_cards))
        if self.plan.bucket_col is not None:
            dims.append(("__bucket", self.plan.n_buckets))
        decoded = {}
        div = 1
        for name, card in reversed(dims):
            decoded[name] = (idx // div) % card
            div *= card
        for tag in self.plan.group_tags:
            values = self.tag_values.get(tag, [])
            codes = decoded[tag]
            cols[tag] = [values[c] if c < len(values) else None for c in codes]
        if self.plan.bucket_col is not None:
            origin = (
                self.bucket_origin
                if self.bucket_origin is not None
                else self.plan.bucket_origin
            )
            interval = (
                self.bucket_interval
                if self.bucket_interval is not None
                else self.plan.bucket_interval
            )
            ts = origin + decoded["__bucket"].astype(np.int64) * interval
            cols[self.plan.bucket_col] = ts
        for name, arr in self.outputs.items():
            sel = np.asarray(arr)[idx]
            if np.issubdtype(sel.dtype, np.floating):
                cols[name] = pa.array(sel, mask=np.isnan(sel))  # NaN -> NULL
            else:
                cols[name] = pa.array(sel)
        return pa.table(cols)


def distributed_groupby(
    mesh: Mesh,
    region_tables: list[pa.Table],
    *,
    group_tags: list[str],
    bucket_col: str | None,
    bucket_origin: int,
    bucket_interval: int,
    n_buckets: int,
    agg_specs: list[tuple[str, str]] | None = None,
    # Backwards-compatible single-column form:
    value_col: str | None = None,
    aggs: tuple[str, ...] | None = None,
    filters: list[tuple[str, str, object]] | None = None,
    acc_dtype: str = "float64",
    tile_rows: int = 1 << 20,
    ts_col: str | None = None,
) -> GroupByResult:
    """Execute a scan->filter->time-bucketed-groupby over region tables."""
    n_dev = mesh.devices.size
    filters = filters or []
    if agg_specs is None:
        assert value_col is not None and aggs is not None
        agg_specs = [(("avg" if a == "avg" else a), value_col) for a in aggs]
    # Normalize func names (count(*) -> COUNT_STAR pseudo column).
    norm_specs: list[tuple[str, str]] = []
    for func, col in agg_specs:
        if func == "count" and col is None:
            col = COUNT_STAR
        norm_specs.append((func, col))

    # 1. Distribute tables over device slots (round-robin concat).
    slots: list[list[pa.Table]] = [[] for _ in range(n_dev)]
    for i, t in enumerate(region_tables):
        slots[i % n_dev].append(t)
    slot_tables = [
        pa.concat_tables(ts, promote_options="permissive") if ts else None for ts in slots
    ]
    if all(t is None for t in slot_tables):
        raise ValueError("no region tables to scan")

    # 2. Union tag dictionaries across shards so codes agree globally.
    value_cols = [c for _f, c in norm_specs if c != COUNT_STAR]
    needed_cols = set(group_tags) | set(value_cols) | {f[0] for f in filters}
    if bucket_col is not None:
        needed_cols.add(bucket_col)
    if ts_col is not None:
        needed_cols.add(ts_col)
    union_dicts: dict[str, dict] = {}
    for t in slot_tables:
        if t is None:
            continue
        for name in t.column_names:
            if name not in needed_cols:
                continue
            col = t[name]
            typ = col.type
            if pa.types.is_dictionary(typ):
                typ = typ.value_type
            if pa.types.is_string(typ) or pa.types.is_large_string(typ) or pa.types.is_binary(typ):
                mapping = union_dicts.setdefault(name, {})
                if col.type != typ:
                    col = col.cast(typ)
                for v in pc.unique(col).to_pylist():
                    if v not in mapping:
                        mapping[v] = len(mapping)

    # 3. Tile each shard to ONE padded size.
    max_rows = max((t.num_rows if t is not None else 0) for t in slot_tables)
    padded = padded_size(max_rows, tile_rows)
    empty_schema = next(t for t in slot_tables if t is not None).schema
    batches: list[TileBatch] = []
    for t in slot_tables:
        if t is None:
            t = empty_schema.empty_table()
        t = t.select([c for c in t.column_names if c in needed_cols])
        batches.append(tiles_from_table(t, tile_rows=padded, dicts=union_dicts))

    # 4. Stack shards to [D, N] host arrays.
    col_names = tuple(sorted(batches[0].columns))
    cols_stacked = {k: jnp.stack([b.columns[k] for b in batches]) for k in col_names}
    valid_stacked = jnp.stack([b.valid for b in batches])
    ones = jnp.ones(padded, dtype=bool)
    nulls_stacked = {
        c: jnp.stack([b.nulls.get(c, ones) for b in batches])
        for c in value_cols
        if any(c in b.nulls for b in batches)  # all-ones masks would defeat
        # count-pass sharing and ship [D, N] bools for nothing
    }

    # 5. Encode filter literals to codes; quantize cardinalities.
    enc_filters = []
    for name, op, value in filters:
        if name in union_dicts:
            if op in ("in", "not in"):
                value = tuple(union_dicts[name].get(v, -1) for v in value)
            else:
                value = union_dicts[name].get(value, -1)
        elif op in ("in", "not in"):
            value = tuple(value)
        enc_filters.append((name, op, value))
    tag_cards = tuple(_quantize_card(len(union_dicts.get(t, {}))) for t in group_tags)

    needs_ts = any(f == "last_value" for f, _c in norm_specs)
    plan = DistGroupByPlan(
        group_tags=tuple(group_tags),
        tag_cards=tag_cards,
        bucket_col=bucket_col,
        bucket_origin=bucket_origin,
        bucket_interval=bucket_interval,
        n_buckets=n_buckets,
        agg_specs=tuple(norm_specs),
        filters=tuple(enc_filters),
        acc_dtype=acc_dtype,
        ts_col=(ts_col or bucket_col) if needs_ts else None,
    )

    # 6. Compile + run + finalize.
    import time as _time

    from ..utils import flight_recorder

    t0 = _time.perf_counter()
    step = _compiled_step(mesh, plan)
    flight_recorder.stage_add(
        "compile", (_time.perf_counter() - t0) * 1000.0
    )
    from ..utils import device_health as _device_health

    mesh_slots = tuple(range(int(mesh.devices.size)))
    t0 = _time.perf_counter()
    states = _device_health.supervised_call(
        "dispatch",
        lambda: step(cols_stacked, valid_stacked, nulls_stacked),
        devices=mesh_slots,
    )
    flight_recorder.stage_add(
        "dispatch", (_time.perf_counter() - t0) * 1000.0
    )
    flight_recorder.note(
        strategy="mesh_table", mesh_devices=int(mesh.devices.size)
    )

    outputs: dict[str, np.ndarray] = {}
    per_col_aggs: dict[str, set] = {}
    for func, col in norm_specs:
        per_col_aggs.setdefault(col, set()).add(_FUNC_TO_KERNEL[func])
    presence = states["__presence"].counts
    finals = {
        col: finalize(states[col], tuple(sorted(aggs)), counts=presence)
        for col, aggs in per_col_aggs.items()
        if col in states
    }
    # ONE batched device->host fetch of every finalized row (per-array
    # np.asarray conversions would each be their own device->host
    # crossing), metered as transfer time so readback stays
    # attributable on the mesh path too
    from ..utils import metrics as _metrics

    t0 = _time.perf_counter()
    presence_np, finals = _device_health.supervised_call(
        "readback",
        lambda: jax.device_get((presence, finals)),
        devices=mesh_slots,
    )
    fetch_ms = (_time.perf_counter() - t0) * 1000.0
    _metrics.TPU_READBACK_TRANSFER_MS.observe(fetch_ms)
    flight_recorder.stage_add("readback_transfer", fetch_ms)
    flight_recorder.add_bytes(down=int(
        np.asarray(presence_np).nbytes
        + sum(
            np.asarray(a).nbytes
            for d in finals.values()
            for a in d.values()
        )
    ))
    presence_np = np.asarray(presence_np)
    non_empty = presence_np > 0
    for func, col in norm_specs:
        out = finals.get(col, {})
        kernel = _FUNC_TO_KERNEL[func]
        arr = out.get(kernel)
        if arr is None and kernel == "count":
            arr = presence_np  # count-pass sharing: presence IS the count
        arr = np.asarray(arr)
        col_count = np.asarray(out.get("count", presence_np))
        if col == COUNT_STAR:
            outputs["count(*)"] = arr.astype(np.int64)
        elif func == "count":
            outputs[f"count({col})"] = arr.astype(np.int64)
        else:
            # NULL semantics: no non-null values in the group -> NULL output.
            outputs[f"{func}({col})"] = np.where(col_count > 0, arr, np.nan)

    tag_values = {}
    for tag in group_tags:
        mapping = union_dicts.get(tag, {})
        values = [None] * len(mapping)
        for v, code in mapping.items():
            values[code] = v
        tag_values[tag] = values
    return GroupByResult(outputs=outputs, non_empty=non_empty, tag_values=tag_values, plan=plan)
