"""Segmented (group-by) aggregation kernels: the two-step state/merge pattern.

TPU-native equivalent of the reference's split of steppable aggregates into a
lower **state** stage per region and an upper **merge** stage at the frontend
(reference query/src/dist_plan/commutativity.rs:45 `step_aggr_to_upper_aggr`,
StateMergeHelper): `segment_aggregate` computes per-shard partial states with
`jax.ops.segment_*` reductions, `merge_states`/`psum_states` combine partials
(psum over ICI replaces the Flight N:1 MergeScan), and `finalize` produces
sum/avg/min/max/count outputs with empty groups marked invalid.

Group ids are dense ints computed on device from time buckets and tag codes:
    gid = ((tag0 * card1 + tag1) * ... ) * n_buckets + time_bucket
Rows failing the predicate mask get gid = num_groups (one overflow slot) so
reductions stay branch-free; the slot is dropped at finalize.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

SUM, COUNT, MIN, MAX, LAST = "sum", "count", "min", "max", "last"
_MERGEABLE = (SUM, COUNT, MIN, MAX, LAST)


@jax.tree_util.register_pytree_node_class
@dataclass
class AggState:
    """Partial aggregation state for one value column over G groups.

    Mirrors the reference's state-aggregate output (e.g. `sum_state`,
    `count_state` columns shipped from datanodes).  All arrays are [G].
    `last_ts`/`last_val` implement last_value(value ORDER BY ts).
    """

    sums: jnp.ndarray | None = None
    counts: jnp.ndarray | None = None
    mins: jnp.ndarray | None = None
    maxs: jnp.ndarray | None = None
    last_ts: jnp.ndarray | None = None
    last_val: jnp.ndarray | None = None

    def tree_flatten(self):
        fields = (self.sums, self.counts, self.mins, self.maxs, self.last_ts, self.last_val)
        mask = tuple(f is not None for f in fields)
        return tuple(f for f in fields if f is not None), mask

    @classmethod
    def tree_unflatten(cls, mask, leaves):
        it = iter(leaves)
        vals = [next(it) if present else None for present in mask]
        return cls(*vals)


def raw_group_ids(
    components: list[tuple[jnp.ndarray, int]],
    shape: tuple[int, ...] | None = None,
    dtype=jnp.int32,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Mixed-radix combine (component, cardinality) pairs into dense gids.

    Returns (gid, in_range): gid is ALWAYS in [0, num_groups) — out-of-range
    component codes (e.g. dict code -1 for "unseen") are clipped and flagged
    in `in_range` instead of being redirected, so scan-order sortedness of
    the ids is preserved for the block fast path.

    `components` may be empty (ungrouped aggregate, one global group); pass
    `shape` so the all-zeros gid array can be built.  `dtype=jnp.int64`
    serves the hash strategy, whose sparse group space may exceed int32
    (the dense path never materializes [G] there, so a wide id is free)."""
    if not components and shape is None:
        raise ValueError("raw_group_ids needs `shape` when components is empty")
    if components:
        shape = components[0][0].shape
    gid = jnp.zeros(shape, dtype=dtype)
    in_range = jnp.ones(shape, dtype=bool)
    for comp, card in components:
        c = comp.astype(dtype)
        in_range = in_range & (c >= 0) & (c < card)
        gid = gid * card + jnp.clip(c, 0, card - 1)
    return gid, in_range


def group_ids(
    components: list[tuple[jnp.ndarray, int]],
    mask: jnp.ndarray,
    num_groups: int,
) -> jnp.ndarray:
    """Overflow-encoded variant: masked or out-of-range rows map to the
    overflow slot `num_groups` (legacy call shape; the engine path passes
    raw ids + mask so the sorted block kernel can engage)."""
    gid, in_range = raw_group_ids(components, shape=mask.shape)
    return jnp.where(mask & in_range, gid, num_groups)


def time_bucket(ts: jnp.ndarray, origin: int, interval: int) -> jnp.ndarray:
    """Floor timestamps into interval buckets (reference date_bin / RANGE ALIGN)."""
    return ((ts - origin) // interval).astype(jnp.int32)


# ---- series ordinals ---------------------------------------------------------
#
# Sources are (pk, ts) sorted, so a series is one run of rows.  Stage 1 can
# group by the run's ORDINAL in the source (consecutive series exactly one
# apart) where the requested tags' codes would not keep a block's gids
# inside the blocked kernels' span: a wider key's subset or reorder, and a
# region of a table partitioned on its leading key tag (under HASH
# (hostname) consecutive hosts of one region lie 2-9 codes apart).  The
# states then fold to the requested groups (`fold_series_states`).


@jax.named_scope("series_ordinals")
def run_ordinals(keys, lo, hi) -> jnp.ndarray:
    """Ordinal [n] int32 of each row's series among the rows [lo, hi) of a
    (series, ts) sorted source: the number of rows of [lo, row] at which a
    plane of `keys` (one or more [n] planes, compared together) differs
    from the row before, less one; row `lo` opens series 0.  One compare a
    plane with the previous row and one int32 prefix sum, whatever the
    number of planes or of distinct values.  Rows before `lo` read -1 and
    rows from `hi` on continue the last series: the caller masks both."""
    from .rate import prefix_scan

    n = keys[0].shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    start = rows == lo
    for k in keys:
        start = start | (k != jnp.concatenate([k[:1], k[:-1]]))
    start = start & (rows >= lo) & (rows < hi)
    (seen,) = prefix_scan(
        lambda a, b: (a[0] + b[0],), (start.astype(jnp.int32),), (0,)
    )
    return seen - 1


# ---- hash group-by ----------------------------------------------------------
#
# The alternative to the dense mixed-radix group space: when the PADDED
# group space G = prod(tag_cards) * n_buckets dwarfs the number of groups
# that actually occur (sparse cross products, log-style high-cardinality
# keys), dense [G] state rows waste HBM, readback bytes and finalize work
# — and past the planner's max_groups bound the dense path refuses
# outright.  The hash/sort group-by study (arXiv:2411.13245) is the
# motivation: neither strategy dominates, the winner flips with group
# cardinality and duplication, so the engine carries both and a planner
# pass picks per query.

HASH_EMPTY = -1  # table sentinel; real gids are >= 0


def hash_group_slots(
    table_keys: jnp.ndarray,  # [H] int64, HASH_EMPTY where unoccupied
    gids: jnp.ndarray,        # [n] int64 raw group ids
    active: jnp.ndarray,      # [n] bool rows that participate
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Insert-or-find every active row's group id in a linear-probing
    device hash table; returns (table_keys', slots [n] int32, overflow).

    Deterministic by construction, so a multi-source fold that threads
    `table_keys` through source after source assigns every gid exactly
    one slot, stable across the whole query: per probe round, all active
    rows claim their probe position with a scatter-min (ties broken by
    smallest gid — data-order independent), winners land, losers advance
    one position.  Masked rows and overflow rows (table full — the
    planner sizes H at 2x the distinct estimate, so this means the
    estimate was badly wrong) report slot == H; `overflow` counts rows
    that never placed so the caller can rerun on the dense path instead
    of ever returning a wrong result.

    Cost per round is one [n] scatter-min + one [n] gather; rounds track
    the longest probe cluster (O(log n) expected at load <= 0.5, so the
    hard round cap below never binds in a correctly-sized table — it
    bounds the FULL-table pathology, where unplaceable rows would
    otherwise probe all H positions before reporting overflow)."""
    h = table_keys.shape[0]
    bits = max(int(h).bit_length() - 1, 1)  # h = 2^bits
    mult = jnp.uint64(0x9E3779B97F4A7C15)
    h0 = ((gids.astype(jnp.uint64) * mult) >> jnp.uint64(64 - bits)).astype(jnp.int32)
    h0 = jnp.minimum(h0, jnp.int32(h - 1))
    maxi = jnp.int64(2**63 - 1)
    n = gids.shape[0]
    max_rounds = min(2 * h, 1024)

    def cond(state):
        _table, _slots, _probe, act, rounds = state
        return jnp.any(act) & (rounds < max_rounds)

    def body(state):
        table, slots, probe, act, rounds = state
        pos = (h0 + probe) & jnp.int32(h - 1)
        safe_pos = jnp.where(act, pos, 0)
        claim = jnp.full((h,), maxi, jnp.int64).at[safe_pos].min(
            jnp.where(act, gids, maxi)
        )
        table = jnp.where((table == HASH_EMPTY) & (claim != maxi), claim, table)
        found = act & (table[pos] == gids)
        slots = jnp.where(found, pos, slots)
        act = act & ~found
        probe = jnp.where(act, probe + 1, probe)
        return table, slots, probe, act, rounds + 1

    init = (
        table_keys,
        jnp.full((n,), h, jnp.int32),
        jnp.zeros((n,), jnp.int32),
        active,
        jnp.int32(0),
    )
    table, slots, _probe, act, _rounds = jax.lax.while_loop(cond, body, init)
    overflow = jnp.sum(act, dtype=jnp.int32)
    return table, slots, overflow


# Fast-path geometry: rows are processed in blocks of BLOCK_ROWS; a block
# may touch at most BLOCK_SPAN distinct (consecutive) group ids.  Chosen by
# measurement on v5e: 4096x16 runs the 17.28M-row TSBS double-groupby in
# ~2.6 ms vs ~307 ms for XLA's scatter-add segment_sum (~120x).
BLOCK_ROWS = 4096
BLOCK_SPAN = 16
_FAST_MIN_ROWS = 1 << 16
# The two lowerings a `lax.cond` picks between at run time trace under these
# scopes, so a profiler trace's device ops say which branch ran (metadata
# only: the compiled program is the same).
_blocked = jax.named_scope("blocked")
_scatter = jax.named_scope("scatter")


def windowed_slot_sum(ps, base, segs: int, span: int):
    """Level-2 assembly: fold [nb, span(,C)] block partials — row b covers
    the `span` consecutive groups starting at base[b] — into a dense
    [segs(,C)] accumulator with ONE row-windowed scatter-add.

    TPU scatter cost scales with the number of scattered elements for
    scalar updates (~75 ns/elem measured on v5e) but a windowed scatter
    moves a whole row per index, ~6x cheaper at the [4096, 64] shapes the
    blocked kernels produce.  `base` entries may be any value in
    [0, segs-1] (rows for the overflow slot land there); the operand is
    over-allocated by `span` so base+span never writes out of bounds, and
    the tail slice is dropped.
    """
    return windowed_slot_reduce(ps, base, segs, span, "sum")


def windowed_slot_reduce(ps, base, segs: int, span: int, kind: str):
    """windowed_slot_sum generalized over the reduction monoid
    (sum / min / max); init value picked so untouched slots finalize the
    same way the scalar segment_* ops initialized them."""
    multi = ps.ndim == 3  # [nb, span, C]
    out_shape = (segs + span, ps.shape[2]) if multi else (segs + span,)
    if kind == "sum":
        init = 0
        op = jax.lax.scatter_add
    elif kind == "min":
        init = jnp.finfo(ps.dtype).max if jnp.issubdtype(ps.dtype, jnp.floating) else jnp.iinfo(ps.dtype).max
        op = jax.lax.scatter_min
    elif kind == "max":
        init = jnp.finfo(ps.dtype).min if jnp.issubdtype(ps.dtype, jnp.floating) else jnp.iinfo(ps.dtype).min
        op = jax.lax.scatter_max
    else:  # pragma: no cover
        raise ValueError(kind)
    out = jnp.full(out_shape, init, ps.dtype)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2) if multi else (1,),
        inserted_window_dims=(),
        scatter_dims_to_operand_dims=(0,),
    )
    # NOT indices_are_sorted: the blocked guard only proves per-block
    # clustering — descending runs or an all-masked mid-stream block
    # (base jumps to the overflow slot) legally produce unsorted bases,
    # and a false sortedness claim makes XLA scatter undefined.
    out = op(out, base[:, None], ps, dnums)
    return out[:segs]


# ---- MXU limb kernels ------------------------------------------------------
#
# The one-hot blocked VPU kernel above is layout-bound, not FLOP-bound
# (K-minor one-hot uses 16/128 vector lanes; measured ~8 ms per f64 column
# at 2^24 rows on v5e, and switching the accumulate to f32 bought <10%).
# For the multi-column sum/avg/count shape (TSBS double-groupby-*) the MXU
# is the right unit: encode every value as 4 base-256 digits that are
# exactly representable in bfloat16, build the block one-hot ONCE as bf16,
# and compute ALL columns' block partials in a single batched matmul whose
# f32 accumulation is exact (integer sums < 2^24).  Quantization is the
# only error: ~2^-30 of the per-block max per row (~1e-9 relative for
# same-magnitude data; integers stay exact up to 2^29), far inside the
# engine's result-equality bar but distinct from true f64.  The tile
# executor selects it via plan acc_dtype "limb" (config
# query.tile_acc_dtype, opt-out to "float64" for exact accumulation of
# >2^29-magnitude integer data); callers that pass an explicit f64
# acc_dtype to segment_aggregate* are never rerouted here.

N_LIMBS = 4
_LIMB_Q_EXP = 29  # |round(v/s)| <= 2^29; +2^29 offset makes digits unsigned


def quantize_limbs(values: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-block fixed-point encode of one value column for
    `limb_segment_sums`.  Length must be a multiple of BLOCK_ROWS.

    Each block gets a power-of-two scale s = 2^(e-29) sized to its max
    |v|; rows encode q = round(v/s) + 2^29 (unsigned, <= 2^30) split into
    N_LIMBS base-256 digits in bfloat16 (digits in [0,255] are exact).
    Zero-valued rows (including padding and decoded NULLs) encode
    q = 2^29, which the offset correction cancels exactly.

    Returns (limbs [nb, BLOCK_ROWS, N_LIMBS] bf16, scale [nb] f64).
    """
    n = values.shape[0]
    nb = n // BLOCK_ROWS
    vv = values.reshape(nb, BLOCK_ROWS).astype(jnp.float64)
    # Non-finite guard: a single inf row would give scale=inf and poison
    # EVERY group's sum with NaN (the f64 path confines inf to its own
    # group).  Sanitize like the tile encode does — NaN contributes
    # nothing, +/-inf saturates to a huge finite value that still
    # dominates its own group's sum.
    vv = jnp.nan_to_num(vv, nan=0.0, posinf=1e308, neginf=-1e308)
    amax = jnp.max(jnp.abs(vv), axis=1)
    e = jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-30)))
    inv = jnp.exp2(_LIMB_Q_EXP - e)
    q = jnp.round(vv * inv[:, None]).astype(jnp.int32) + (1 << _LIMB_Q_EXP)
    limbs = jnp.stack(
        [((q >> (8 * j)) & 0xFF).astype(jnp.bfloat16) for j in range(N_LIMBS)],
        axis=-1,
    )
    return limbs, jnp.exp2(e - jnp.float64(_LIMB_Q_EXP))


def limb_segment_sums(
    limb_cols: list,
    gids: jnp.ndarray,
    mask: jnp.ndarray,
    num_groups: int,
    span: int,
    count01: list | None = None,
):
    """Multi-column segmented sum + count on the MXU.

    limb_cols: C tuples (limbs [nb, L, N_LIMBS] bf16, scale [nb] f64)
      from `quantize_limbs`.
    count01: optional C-list of per-column non-null indicators ([n] bool
      or None); columns with an indicator get their own null-gated count.

    One bf16 one-hot [nb, L, span] contracts against the concatenated
    digit planes [nb, L, M] (M = 1 ones column + count columns + 4C limb
    planes) in a single batched matmul; per-(block, slot) integer sums
    accumulate exactly in f32, are recombined/scaled in f64 at [nb, span]
    size, and land in dense [G] space via `windowed_slot_sum`.  A runtime
    `lax.cond` guard (same clustering condition as `segment_aggregate`)
    falls back to a scatter path over values reconstructed from the limbs
    — both branches share the quantized representation, so results are
    branch-independent.

    Every column also gets a per-group WORST-CASE quantization error
    bound: err_g = sum over contributing blocks of count * scale_b / 2
    (each row's error is at most half a quantization step of ITS block).
    The caller compares err against |sum| to certify the result — the
    per-block shared scale means a small-magnitude group co-blocked with
    huge values can lose precision far beyond the homogeneous-data ~1e-9,
    and the bound is what makes that case detectable instead of silent.

    Returns (sums [C, G] f64, errs [C, G] f64, counts [C, G] int32 or
    None, presence [G] int32): `counts` rows are presence for columns
    without an indicator.
    """
    n = gids.shape[0]
    nb = n // BLOCK_ROWS
    L = BLOCK_ROWS
    C = len(limb_cols)
    segs = num_groups + 1
    g32 = gids.astype(jnp.int32)
    has_counts = count01 is not None

    gb = g32.reshape(nb, L)
    mb = mask.reshape(nb, L)
    sentinel = jnp.int32(2**31 - 1)
    bmin = jnp.min(jnp.where(mb, gb, sentinel), axis=1)
    bmax = jnp.max(jnp.where(mb, gb, -1), axis=1)
    in_range_ok = jnp.all(jnp.where(mask, (g32 >= 0) & (g32 < num_groups), True))
    ok_block = in_range_ok & jnp.all(bmax - bmin < span)

    def fast(args):
        gb, mb, limbs_scales, counts01 = args
        base = jnp.minimum(bmin, jnp.int32(num_groups))
        local = gb - base[:, None]
        ks = jnp.arange(span, dtype=jnp.int32)
        sel = (
            (local[:, :, None] == ks[None, None, :]) & mb[:, :, None]
        ).astype(jnp.bfloat16)  # [nb, L, span]
        planes = [jnp.ones((nb, L, 1), jnp.bfloat16)]
        for c01 in counts01:
            if c01 is not None:
                planes.append(c01.reshape(nb, L, 1).astype(jnp.bfloat16))
        for limbs, _s in limbs_scales:
            planes.append(limbs)
        # einsum in bounded column groups: ONE concatenated [nb, L, M]
        # digit matrix for 10 columns is a ~2.7 GB transient at 2^24 rows
        # — on top of ~10 GB of resident planes that overcommitted HBM at
        # TSBS 3-day scale.  Grouping caps the transient at ~0.7 GB; sel
        # is reused across groups, and XLA frees each group's buffers
        # before the next materializes.
        group_cols = 24  # digit planes per einsum (~6 value columns)
        parts = []
        i = 0
        while i < len(planes):
            g = planes[i:]
            width = 0
            take = 0
            for p in g:
                if take and width + p.shape[-1] > group_cols:
                    break
                width += p.shape[-1]
                take += 1
            M = (
                jnp.concatenate(planes[i : i + take], axis=-1)
                if take > 1
                else planes[i]
            )
            parts.append(jnp.einsum(
                "blk,blm->bkm", sel, M, preferred_element_type=jnp.float32
            ))
            i += take
        P = jnp.concatenate(parts, axis=-1) if len(parts) > 1 else parts[0]
        presence_b = P[:, :, 0].astype(jnp.int32)  # exact (<= L per slot)
        presence = windowed_slot_sum(presence_b, base, segs, span)[:num_groups]
        off = 1
        counts = None
        if has_counts:
            ccols = []
            ci = 0
            for c01 in counts01:
                if c01 is None:
                    ccols.append(presence_b)
                else:
                    ccols.append(P[:, :, off + ci].astype(jnp.int32))
                    ci += 1
            off += ci
            pc = jnp.stack(ccols, axis=-1)  # [nb, span, C] int32
            counts = windowed_slot_sum(pc, base, segs, span)[:num_groups].T
        sums_cols = []
        err_cols = []
        pres64 = presence_b.astype(jnp.float64)
        for c, (_limbs, scale) in enumerate(limbs_scales):
            acc = -pres64 * jnp.float64(1 << _LIMB_Q_EXP)
            for j in range(N_LIMBS):
                acc = acc + P[:, :, off + N_LIMBS * c + j].astype(
                    jnp.float64
                ) * jnp.float64(1 << (8 * j))
            sums_cols.append(acc * scale[:, None])
            err_cols.append(pres64 * (scale[:, None] * 0.5))
        ps = jnp.stack(sums_cols + err_cols, axis=-1)  # [nb, span, 2C] f64
        packed = windowed_slot_sum(ps, base, segs, span)[:num_groups].T
        sums, errs = packed[:C], packed[C:]
        return sums, errs, counts, presence

    def slow(args):
        gb, mb, limbs_scales, counts01 = args
        safe = jnp.where(mb, gb, num_groups).reshape(-1)
        flat_mask = mb.reshape(-1)
        presence = jax.ops.segment_sum(
            flat_mask.astype(jnp.int32), safe, num_segments=segs
        )[:num_groups]
        counts = None
        if has_counts:
            rows = []
            for c01 in counts01:
                if c01 is None:
                    rows.append(presence)
                else:
                    rows.append(
                        jax.ops.segment_sum(
                            (flat_mask & c01).astype(jnp.int32),
                            safe,
                            num_segments=segs,
                        )[:num_groups]
                    )
            counts = jnp.stack(rows)
        sums_rows = []
        err_rows = []
        for limbs, scale in limbs_scales:
            q = jnp.zeros((nb, L), jnp.int32)
            for j in range(N_LIMBS):
                q = q + (limbs[:, :, j].astype(jnp.int32) << (8 * j))
            vhat = (q - (1 << _LIMB_Q_EXP)).astype(jnp.float64) * scale[:, None]
            sums_rows.append(
                jax.ops.segment_sum(
                    jnp.where(mb, vhat, 0.0).reshape(-1),
                    safe,
                    num_segments=segs,
                )[:num_groups]
            )
            half_step = jnp.broadcast_to(scale[:, None] * 0.5, (nb, L))
            err_rows.append(
                jax.ops.segment_sum(
                    jnp.where(mb, half_step, 0.0).reshape(-1),
                    safe,
                    num_segments=segs,
                )[:num_groups]
            )
        return jnp.stack(sums_rows), jnp.stack(err_rows), counts, presence

    counts01 = tuple(count01) if count01 is not None else tuple([None] * C)
    return jax.lax.cond(
        ok_block, _blocked(fast), _scatter(slow),
        (gb, mb, tuple(limb_cols), counts01),
    )


def segment_sums_scatter(
    values_list: list,
    gids: jnp.ndarray,
    mask: jnp.ndarray,
    num_groups: int,
    count01: list | None = None,
):
    """Structure-compatible small-source companion to `limb_segment_sums`:
    the same (sums [C, G] f64, errs, counts [C, G] int32 | None, presence
    [G] int32) tuple computed with scalar segment ops over RAW values —
    sources below the limb kernel's geometry (memtable tails, sub-block
    chunks) are cheap enough to aggregate exactly (errs = 0), and emitting
    the identical AggState shape keeps merge_states well-defined when a
    query mixes limb-sized and tiny sources."""
    segs = num_groups + 1
    safe = jnp.where(mask, gids.astype(jnp.int32), num_groups)
    presence = jax.ops.segment_sum(
        mask.astype(jnp.int32), safe, num_segments=segs
    )[:num_groups]
    counts = None
    if count01 is not None:
        rows = []
        for c01 in count01:
            if c01 is None:
                rows.append(presence)
            else:
                rows.append(
                    jax.ops.segment_sum(
                        (mask & c01).astype(jnp.int32), safe, num_segments=segs
                    )[:num_groups]
                )
        counts = jnp.stack(rows)
    sums = jnp.stack([
        jax.ops.segment_sum(
            jnp.where(mask, v.astype(jnp.float64), 0.0), safe, num_segments=segs
        )[:num_groups]
        for v in values_list
    ])
    return sums, jnp.zeros_like(sums), counts, presence


def segment_aggregate(
    values: jnp.ndarray,
    gids: jnp.ndarray,
    num_groups: int,
    aggs: tuple[str, ...],
    mask: jnp.ndarray | None = None,
    ts: jnp.ndarray | None = None,
    acc_dtype=jnp.float32,
    span: int = BLOCK_SPAN,
    force_scatter: bool = False,
) -> AggState:
    """Per-shard partial aggregation (the lower/state stage).

    Two lowerings, selected at RUNTIME by a `lax.cond` on data layout:

    * **blocked kernel** — when every BLOCK_ROWS block's MASKED rows span
      fewer than BLOCK_SPAN distinct group ids (the engine's (pk, ts) sort
      guarantees clustering whenever the group keys follow primary-key
      order — a plan grouped by a subset of a wider key composes its ids
      over the source's series ordinals so this holds, see
      `fold_series_states` — and selective
      filters make sparse blocks trivially narrow), each block reduces
      into a tiny dense [SPAN] accumulator via compare-broadcast sums
      (VPU-friendly, no scatter), and only the [blocks, SPAN] partials hit
      a scatter.  The guard is mask-aware and does NOT require global
      sortedness.  This is the TPU answer to the reference's sorted-run
      merge: layout makes the hot loop branch- and scatter-free.
    * **scatter fallback** — XLA segment_* for arbitrary id layouts.

    A third segmented-`associative_scan` kernel existed through round 2
    (`_segment_scan_sorted`); it was removed from the hot dispatch because
    its XLA compile time grows superlinearly with array length (measured
    on v5e: 4.7 s at 2^16, 66 s at 2^20 — it alone was the round-2 bench
    compile blowup), while blocked+scatter compile in ~3 s flat at any
    shape.  The layouts it served are now handled statically by
    grouping stage 1 by series (`fold_series_states`).

    `gids` may be raw in-range ids (preferred; pass `mask` for filtering)
    or legacy overflow-encoded ids (those fail the in-range guard and take
    the fallback).
    """
    if mask is None:
        mask = gids < num_groups
    n = values.shape[0]
    if force_scatter or n < _FAST_MIN_ROWS:
        # force_scatter: hash-strategy callers pass hashed slot ids, which
        # are unclustered by construction — skip compiling the blocked
        # branch and its runtime guard entirely
        return _segment_scatter(values, gids, num_groups, aggs, mask, ts, acc_dtype)

    g32 = gids.astype(jnp.int32)
    in_range_ok = jnp.all(jnp.where(mask, (g32 >= 0) & (g32 < num_groups), True))
    nb = n // BLOCK_ROWS
    gb = g32[: nb * BLOCK_ROWS].reshape(nb, BLOCK_ROWS)
    mb = mask[: nb * BLOCK_ROWS].reshape(nb, BLOCK_ROWS)
    sentinel = jnp.int32(2**31 - 1)
    bmin = jnp.min(jnp.where(mb, gb, sentinel), axis=1)  # empty block -> sentinel
    bmax = jnp.max(jnp.where(mb, gb, -1), axis=1)  # empty block -> -1
    span_ok = jnp.all(bmax - bmin < span)  # empty: -1 - sentinel < span
    ok_block = in_range_ok & span_ok

    if LAST in aggs:
        if ts is None:
            raise ValueError("LAST aggregation requires ts")

        def fast_last(args):
            v, g, m, t = args
            return _segment_blocked_last(
                v, g, num_groups, aggs, m, t, acc_dtype, bmin, span
            )

        def slow_last(args):
            v, g, m, t = args
            return _segment_scatter(v, g, num_groups, aggs, m, t, acc_dtype)

        return jax.lax.cond(
            ok_block, _blocked(fast_last), _scatter(slow_last),
            (values, g32, mask, ts),
        )

    def fast(args):
        v, g, m = args
        return _segment_blocked(v, g, num_groups, aggs, m, acc_dtype, bmin, span)

    def slow(args):
        v, g, m = args
        return _segment_scatter(v, g, num_groups, aggs, m, None, acc_dtype)

    return jax.lax.cond(
        ok_block, _blocked(fast), _scatter(slow), (values, g32, mask)
    )


def _segment_scatter(
    values, gids, num_groups, aggs, mask, ts, acc_dtype
) -> AggState:
    """XLA scatter-based segment reduction (handles any id order)."""
    segs = num_groups + 1  # + overflow slot
    safe = jnp.where(mask, gids, num_groups)
    v = values.astype(acc_dtype)
    v0 = jnp.where(mask, v, 0)
    state = AggState()
    if SUM in aggs or "avg" in aggs:
        state.sums = jax.ops.segment_sum(v0, safe, num_segments=segs)[:num_groups]
    if COUNT in aggs or "avg" in aggs:
        state.counts = jax.ops.segment_sum(
            mask.astype(jnp.int32), safe, num_segments=segs
        )[:num_groups]
    if MIN in aggs:
        big = jnp.asarray(jnp.finfo(acc_dtype).max, acc_dtype)
        state.mins = jax.ops.segment_min(
            jnp.where(mask, v, big), safe, num_segments=segs
        )[:num_groups]
    if MAX in aggs:
        small = jnp.asarray(jnp.finfo(acc_dtype).min, acc_dtype)
        state.maxs = jax.ops.segment_max(
            jnp.where(mask, v, small), safe, num_segments=segs
        )[:num_groups]
    if LAST in aggs:
        if ts is None:
            raise ValueError("LAST aggregation requires ts")
        tsmin = jnp.iinfo(jnp.int64).min
        t = jnp.where(mask, ts, tsmin)
        state.last_ts = jax.ops.segment_max(t, safe, num_segments=segs)[:num_groups]
        # Second pass: among rows at the group's max ts, the LAST one in
        # layout order wins — the (pk, ts, write-order) sort makes this
        # exactly last-write-wins, matching the CPU path on ts ties.
        n = values.shape[0]
        is_last = mask & (ts == state.last_ts[jnp.clip(safe, 0, num_groups - 1)])
        ridx = jnp.arange(n, dtype=jnp.int32)
        pick = jax.ops.segment_max(
            jnp.where(is_last, ridx, -1), safe, num_segments=segs
        )[:num_groups]
        state.last_val = v[jnp.clip(pick, 0, n - 1)]
    return state


def _segment_blocked(
    values, gids, num_groups, aggs, mask, acc_dtype, bmin, span=BLOCK_SPAN
) -> AggState:
    """Blocked kernel: dense per-block accumulators, scatter only the
    [blocks, span] partials (BLOCK_ROWS/span fewer scatters).
    `bmin` = per-block min of MASKED gids (sentinel for all-masked blocks),
    so clustering — not global sortedness — is the only layout demand.
    `span` is sized by the planner from expected groups-per-block (compute
    cost scales with it, so it stays as small as the layout allows)."""
    n = values.shape[0]
    nb = n // BLOCK_ROWS
    L, K = BLOCK_ROWS, span
    segs = num_groups + 1

    g = gids[: nb * L].reshape(nb, L)
    m = mask[: nb * L].reshape(nb, L)
    v = values[: nb * L].reshape(nb, L).astype(acc_dtype)
    # all-masked blocks land on the overflow slot; their partials are
    # init values only (sel is False everywhere in them)
    base = jnp.minimum(bmin, jnp.int32(num_groups))
    local = g - base[:, None]  # masked rows: in [0, K) — span guard
    ks = jnp.arange(K, dtype=jnp.int32)
    sel = (local[:, :, None] == ks[None, None, :]) & m[:, :, None]  # [nb, L, K]

    # tail rows (< BLOCK_ROWS of them) take the scatter path
    tail_v = values[nb * L :]
    tail_g = jnp.where(mask[nb * L :], gids[nb * L :], num_groups)
    tail_m = mask[nb * L :]

    state = AggState()
    if SUM in aggs or "avg" in aggs:
        ps = jnp.sum(jnp.where(sel, v[:, :, None], 0), axis=1)  # [nb, K]
        s = windowed_slot_sum(ps, base, segs, K)
        s = s + jax.ops.segment_sum(
            jnp.where(tail_m, tail_v.astype(acc_dtype), 0), tail_g, num_segments=segs
        )
        state.sums = s[:num_groups]
    if COUNT in aggs or "avg" in aggs:
        pc = jnp.sum(sel, axis=1, dtype=jnp.int32)
        c = windowed_slot_sum(pc, base, segs, K)
        c = c + jax.ops.segment_sum(
            tail_m.astype(jnp.int32), tail_g, num_segments=segs
        )
        state.counts = c[:num_groups]
    if MIN in aggs:
        big = jnp.asarray(jnp.finfo(acc_dtype).max, acc_dtype)
        pm = jnp.min(jnp.where(sel, v[:, :, None], big), axis=1)
        mn = windowed_slot_reduce(pm, base, segs, K, "min")
        mn = jnp.minimum(
            mn,
            jax.ops.segment_min(
                jnp.where(tail_m, tail_v.astype(acc_dtype), big),
                tail_g,
                num_segments=segs,
            ),
        )
        state.mins = mn[:num_groups]
    if MAX in aggs:
        small = jnp.asarray(jnp.finfo(acc_dtype).min, acc_dtype)
        pm = jnp.max(jnp.where(sel, v[:, :, None], small), axis=1)
        mx = windowed_slot_reduce(pm, base, segs, K, "max")
        mx = jnp.maximum(
            mx,
            jax.ops.segment_max(
                jnp.where(tail_m, tail_v.astype(acc_dtype), small),
                tail_g,
                num_segments=segs,
            ),
        )
        state.maxs = mx[:num_groups]
    return state


def _stack_states(states: list[AggState]) -> AggState:
    """Stack per-column AggStates into [C, G] arrays (G-sized, tiny)."""
    out = AggState()
    if states[0].sums is not None:
        out.sums = jnp.stack([st.sums for st in states])
    if states[0].counts is not None:
        out.counts = jnp.stack([st.counts for st in states])
    if states[0].mins is not None:
        out.mins = jnp.stack([st.mins for st in states])
    if states[0].maxs is not None:
        out.maxs = jnp.stack([st.maxs for st in states])
    return out


def segment_aggregate_multi(
    values: list,  # C arrays of [n]
    gids: jnp.ndarray,  # [n]
    num_groups: int,
    aggs: tuple[str, ...],
    masks: list,  # C arrays of [n] per-column row masks (base & non-null)
    base_mask: jnp.ndarray,  # [n] the filter mask before null-gating
    acc_dtype=jnp.float32,
    span: int = BLOCK_SPAN,
    force_scatter: bool = False,
) -> AggState:
    """Multi-column variant of `segment_aggregate`: C value columns share
    ONE layout guard and ONE compiled branch pair (blocked / scatter),
    with the columns traced as a PYTHON loop inside each branch — NOT a
    vmap over a stacked [C, n] array.  Stacking materialized several
    [C, n] temporaries (values concat, iota broadcasts, mask stacks); at
    TSBS scale (C=10, n=2^26) that alone exceeded HBM (measured: 22.25 GB
    program requirement on a 15.75 GB v5e, 66 s warm after spill).  The
    loop lets XLA schedule columns sequentially and reuse buffers, so peak
    memory stays one column's working set.  Guards use `base_mask`; since
    every per-column mask is a subset, clustering established on the base
    mask holds for each column.  Arrays in the result are [C, G].
    LAST is not supported here (callers route last_value per-column)."""
    if LAST in aggs:
        raise ValueError("segment_aggregate_multi does not support LAST")
    n = values[0].shape[0]
    use_fast = n >= _FAST_MIN_ROWS and not force_scatter
    if not use_fast:
        return _stack_states([
            _segment_scatter(v, gids, num_groups, aggs, m, None, acc_dtype)
            for v, m in zip(values, masks)
        ])

    g32 = gids.astype(jnp.int32)
    in_range_ok = jnp.all(
        jnp.where(base_mask, (g32 >= 0) & (g32 < num_groups), True)
    )
    nb = n // BLOCK_ROWS
    gb = g32[: nb * BLOCK_ROWS].reshape(nb, BLOCK_ROWS)
    mb = base_mask[: nb * BLOCK_ROWS].reshape(nb, BLOCK_ROWS)
    sentinel = jnp.int32(2**31 - 1)
    bmin = jnp.min(jnp.where(mb, gb, sentinel), axis=1)
    bmax = jnp.max(jnp.where(mb, gb, -1), axis=1)
    span_ok = jnp.all(bmax - bmin < span)
    ok_block = in_range_ok & span_ok

    def fast(args):
        vs, ms = args
        return _stack_states([
            _segment_blocked(v, g32, num_groups, aggs, m, acc_dtype, bmin, span)
            for v, m in zip(vs, ms)
        ])

    def slow(args):
        vs, ms = args
        return _stack_states([
            _segment_scatter(v, g32, num_groups, aggs, m, None, acc_dtype)
            for v, m in zip(vs, ms)
        ])

    return jax.lax.cond(
        ok_block, _blocked(fast), _scatter(slow), (tuple(values), tuple(masks))
    )


def _segment_blocked_last(
    values, gids, num_groups, aggs, mask, ts, acc_dtype, bmin, span=BLOCK_SPAN
) -> AggState:
    """Blocked lowering of last_value(value ORDER BY ts): same dense
    per-block [span] accumulator trick as `_segment_blocked`, two passes —
    (1) blocked max of ts -> last_ts[G]; (2) among rows at their group's
    last_ts, the highest ROW INDEX wins (layout is (pk, ts, write-order)
    sorted, so this is exactly last-write-wins, matching the CPU path on
    ts ties), and ONE [G]-sized gather fetches the winning values.  All
    per-row work is block-local — no n-sized gather/scatter — so
    full-table lastpoint stays bandwidth-bound (scatter at 2^24 rows
    measured ~1.8 s on v5e vs milliseconds blocked)."""
    n = values.shape[0]
    nb = n // BLOCK_ROWS
    L, K = BLOCK_ROWS, span
    segs = num_groups + 1

    g = gids[: nb * L].reshape(nb, L)
    m = mask[: nb * L].reshape(nb, L)
    t = ts[: nb * L].reshape(nb, L)
    base = jnp.minimum(bmin, jnp.int32(num_groups))
    local = g - base[:, None]
    ks = jnp.arange(K, dtype=jnp.int32)
    sel = (local[:, :, None] == ks[None, None, :]) & m[:, :, None]  # [nb, L, K]

    tail_v = values[nb * L :]
    tail_g = jnp.where(mask[nb * L :], gids[nb * L :], num_groups)
    tail_m = mask[nb * L :]
    tail_t = ts[nb * L :]

    tsmin = jnp.iinfo(jnp.int64).min
    # pass 1: last_ts per group via block partials
    pt = jnp.max(jnp.where(sel, t[:, :, None], tsmin), axis=1)  # [nb, K]
    lt = windowed_slot_reduce(pt, base, segs, K, "max")
    lt = jnp.maximum(
        lt,
        jax.ops.segment_max(
            jnp.where(tail_m, tail_t, tsmin), tail_g, num_segments=segs
        ),
    )
    last_ts = lt[:num_groups]
    # pass 2: highest row index among block rows at the block-slot max ts,
    # gated by whether that slot's ts IS the global max ([nb, K] gather)
    ridx = jnp.arange(nb * L, dtype=jnp.int32).reshape(nb, L)
    slot_is_global = pt == lt[jnp.minimum(base[:, None] + ks[None, :], segs - 1)]  # [nb, K]
    row_at_slot_max = sel & (t[:, :, None] == pt[:, None, :])  # [nb, L, K]
    pidx = jnp.max(
        jnp.where(row_at_slot_max, ridx[:, :, None], -1), axis=1
    )  # [nb, K]
    pidx = jnp.where(slot_is_global, pidx, -1)
    pick = windowed_slot_reduce(pidx, base, segs, K, "max")
    tail_is_last = tail_m & (tail_t == last_ts[jnp.clip(tail_g, 0, num_groups - 1)])
    tail_idx = nb * L + jnp.arange(tail_v.shape[0], dtype=jnp.int32)
    pick = jnp.maximum(
        pick,
        jax.ops.segment_max(
            jnp.where(tail_is_last, tail_idx, -1), tail_g, num_segments=segs
        ),
    )
    pick = pick[:num_groups]
    lv = values.astype(acc_dtype)[jnp.clip(pick, 0, n - 1)]
    state = AggState(last_ts=last_ts, last_val=lv)
    if COUNT in aggs or SUM in aggs or "avg" in aggs or MIN in aggs or MAX in aggs:
        extra = _segment_blocked(
            values, gids, num_groups,
            tuple(a for a in aggs if a != LAST), mask, acc_dtype, bmin, span,
        )
        state.sums, state.counts = extra.sums, extra.counts
        state.mins, state.maxs = extra.mins, extra.maxs
    return state


@jax.named_scope("series_ordinals")
def series_rows(marks: jnp.ndarray, n: int) -> jnp.ndarray:
    """Ordinal [n] int32 of each row's series in a (series, ts) sorted
    source whose series k opens at row `marks[k]`: `marks` ascends from 0,
    and its entries at n or past (the padding of a fixed-size table) mark
    nothing.  The series' row bounds come with the request, off the
    region's `SeriesTable`: no key plane is read and nothing is searched,
    one scatter of the marks and one int32 prefix sum.  The pad tail
    continues the last series: the caller's valid mask drops it."""
    from .rate import prefix_scan

    hist = jnp.zeros(n, jnp.int32).at[marks].add(1, mode="drop")
    (seen,) = prefix_scan(lambda a, b: (a[0] + b[0],), (hist,), (0,))
    return seen - 1


@jax.named_scope("series_fold")
def fold_series_states(
    state: AggState, series_group: jnp.ndarray, num_groups: int,
    n_buckets: int,
) -> AggState:
    """Stage 2 of a plan that groups by series (`DistGroupByPlan.
    series_slots`): fold a [S * B] state (series-major, bucket minor) to
    the [G * B] the query asked for, through `series_group` [S] int32, the
    group code of each series (-1 for a padding slot: dropped).  One
    segment reduction per field, on the device, before any readback: sums
    and counts add, mins and maxs take the extreme.  LAST states only
    permute (each series its own group, the planner's rule): they are
    placed, never folded."""
    s = series_group.shape[0]
    ids = jnp.where(series_group < 0, num_groups, series_group)

    def fold(arr, op):
        if arr is None:
            return None
        return op(arr.reshape(s, n_buckets), ids, num_segments=num_groups).reshape(-1)

    def place(arr, identity):
        if arr is None:
            return None
        out = jnp.full((num_groups, n_buckets), identity, arr.dtype)
        return out.at[ids].set(arr.reshape(s, n_buckets), mode="drop").reshape(-1)

    return AggState(
        sums=fold(state.sums, jax.ops.segment_sum),
        counts=fold(state.counts, jax.ops.segment_sum),
        mins=fold(state.mins, jax.ops.segment_min),
        maxs=fold(state.maxs, jax.ops.segment_max),
        last_ts=None if state.last_ts is None
        else place(state.last_ts, jnp.iinfo(state.last_ts.dtype).min),
        last_val=place(state.last_val, 0),
    )


def merge_states(a: AggState, b: AggState) -> AggState:
    """Combine two partials (the upper/merge stage, tree or pairwise)."""
    out = AggState()
    if a.sums is not None:
        out.sums = a.sums + b.sums
    if a.counts is not None:
        out.counts = a.counts + b.counts
    if a.mins is not None:
        out.mins = jnp.minimum(a.mins, b.mins)
    if a.maxs is not None:
        out.maxs = jnp.maximum(a.maxs, b.maxs)
    if a.last_ts is not None:
        # ties go to b: callers merge sources in write order (SSTs before
        # memtable tails), so the later write wins — same rule the CPU
        # path's (pk, ts, seq) sort implements
        newer_or_tie = b.last_ts >= a.last_ts
        out.last_ts = jnp.maximum(a.last_ts, b.last_ts)
        out.last_val = jnp.where(newer_or_tie, b.last_val, a.last_val)
    return out


def pack_f64_bits(x: jnp.ndarray) -> jnp.ndarray:
    """IEEE-754 bit pattern of float64 values as two int32 words
    (..., [hi, lo]), composed ARITHMETICALLY because the TPU x64 rewrite
    has no lowering for a 64-bit bitcast-convert — and `jnp.frexp` /
    `jnp.signbit` on float64 lower through exactly that bitcast, so
    neither appears here.  The exponent comes from a branch-free binary
    search over exact power-of-two scalings, the mantissa from two exact
    floor splits, the sign from the float32 cast (sign-preserving for
    every input, and a 32-bit bitcast lowers fine).  Everything but the
    float64 compares/multiplies is int32.  int32 words bitcast to uint32
    fine, so f64 rows can join the one flat result buffer and the whole
    compact readback ships as a SINGLE device_get.

    Bit-exact for every NORMAL finite value and signed zero; +/-inf keep
    their sign; NaNs canonicalize to the positive quiet NaN (payloads
    never survive SQL semantics — a NaN output only ever means NULL or
    propagates as NaN either way).  Subnormals flush to signed zero:
    XLA CPU treats a subnormal operand as zero even in comparisons, so
    no arithmetic re-encode can see one, and device kernels flush them
    identically in the aggregation itself, so this loses nothing the
    dispatch had.  On the chip float64 itself is emulated (a float32
    pair: ~48 mantissa bits, float32 exponent range); the words are then
    the exact bits of the emulated value.  There every constant below
    outside float32's range folds to inf or 0: the search steps it cannot
    represent then never fire (no value there needs them), and zero is
    recognised by `== 0` and by what the search could not normalize, never
    by a comparison with a constant only float64 holds."""
    xf = x.astype(jnp.float64)
    is_nan = jnp.isnan(xf)
    neg = jnp.signbit(xf.astype(jnp.float32)) & ~is_nan
    ax = jnp.abs(xf)
    is_inf = jnp.isinf(xf)
    # normalize ax = m * 2^e with m in [1, 2): every scaling is by an
    # exact power of two, so m keeps all 53 significant bits.  The
    # garbage inf/NaN/zero inputs produce is discarded by the wheres.
    m = ax
    e = jnp.zeros(ax.shape, jnp.int32)
    steps = (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
    for k in steps:
        big = m >= jnp.float64(2.0**k)
        m = jnp.where(big, m * jnp.float64(2.0**-k), m)
        e = e + jnp.where(big, k, 0)
    for k in steps:
        small = m < jnp.float64(2.0 ** (1 - k))
        m = jnp.where(small, m * jnp.float64(2.0**k), m)
        e = e - jnp.where(small, k, 0)
    # zero, and whatever lies under the platform's smallest normal (the
    # subnormal flush above): the search leaves those outside [1, 2)
    is_zero = (ax == 0) | ~((m >= 1) & (m < 2))
    # 52 fraction bits = 20 (hi word) + 16 + 16 (lo word), split with
    # exact floors so no conversion ever leaves the int32 range
    t = m * jnp.float64(1 << 20)  # [2^20, 2^21)
    t_hi = jnp.floor(t)
    frac_hi = t_hi.astype(jnp.int32) - (1 << 20)
    r = (t - t_hi) * jnp.float64(1 << 16)  # [0, 2^16)
    r_hi = jnp.floor(r)
    r_lo = (r - r_hi) * jnp.float64(1 << 16)  # exact integer < 2^16
    lo = (r_hi.astype(jnp.int32) << 16) | r_lo.astype(jnp.int32)
    stored_e = e + 1023  # IEEE biased exponent, [1, 2046] for normals
    frac_hi = jnp.where(is_zero | is_inf, 0, frac_hi)
    frac_hi = jnp.where(is_nan, 1 << 19, frac_hi)  # canonical qNaN
    lo = jnp.where(is_zero | is_inf | is_nan, 0, lo)
    stored_e = jnp.where(is_zero, 0, stored_e)
    stored_e = jnp.where(is_inf | is_nan, 0x7FF, stored_e)
    hi = (stored_e << 20) | frac_hi
    # sign bit via addition: hi is < 2^31 here, so adding INT32_MIN sets
    # exactly bit 31 in two's complement
    hi = hi + jnp.where(neg, jnp.int32(-(2**31)), jnp.int32(0))
    return jnp.stack([hi, lo], axis=-1)


def unpack_f64_bits(hilo) -> "object":
    """Host-side inverse of `pack_f64_bits`: (..., [hi, lo]) int32 words
    back to float64 via a numpy view — the device never needed the
    64-bit bitcast, the host always had it."""
    import numpy as np

    arr = np.asarray(hilo, dtype=np.int32)
    hi = arr[..., 0].astype(np.uint32).astype(np.uint64)
    lo = arr[..., 1].astype(np.uint32).astype(np.uint64)
    bits = np.ascontiguousarray((hi << np.uint64(32)) | lo)
    return bits.view(np.float64)


def mesh_min(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Elementwise minimum across a mesh axis, as all_gather + a local
    fold instead of `lax.pmin`: for 64-bit types (f64 accumulators, int64
    timestamps) the TPU compiler lowers only SUM all-reduces
    ("UNIMPLEMENTED: Supported lowering only of Sum all reduce").  Order
    statistics are exact under any order, so the result is pmin's."""
    return jnp.min(jax.lax.all_gather(x, axis_name), axis=0)


def mesh_max(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """`mesh_min`'s twin for the maximum."""
    return jnp.max(jax.lax.all_gather(x, axis_name), axis=0)


def psum_states(state: AggState, axis_name: str) -> AggState:
    """Merge partials across a mesh axis with XLA collectives over ICI.

    This is the TPU-native MergeScan: sums/counts ride psum, min/max an
    all_gather + fold (mesh_min/mesh_max), LAST does an argmax-style
    two-field reduction.
    """
    out = AggState()
    if state.sums is not None:
        out.sums = jax.lax.psum(state.sums, axis_name)
    if state.counts is not None:
        out.counts = jax.lax.psum(state.counts, axis_name)
    if state.mins is not None:
        out.mins = mesh_min(state.mins, axis_name)
    if state.maxs is not None:
        out.maxs = mesh_max(state.maxs, axis_name)
    if state.last_ts is not None:
        max_ts = mesh_max(state.last_ts, axis_name)
        mine = state.last_ts == max_ts
        small = jnp.asarray(jnp.finfo(state.last_val.dtype).min, state.last_val.dtype)
        out.last_ts = max_ts
        out.last_val = mesh_max(jnp.where(mine, state.last_val, small), axis_name)
    return out


def topk_group_select(
    mask: jnp.ndarray,
    order_keys: list[tuple],
    cap: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k over finalized [G] states: the device half of ORDER BY/LIMIT
    pushdown (and of empty-group compaction, with no order keys).

    `mask` marks surviving groups (non-empty AND HAVING-true);
    `order_keys` is a list of (values [G], isnull [G] | None, ascending,
    nulls_first).  Returns (sel [cap] int32 group ids, n_out int32): the
    first `cap` groups ordered survivors-first, then by each key with an
    explicit null bucket, ties broken by group id ASCENDING — exactly the
    order a stable host sort produces over the gid-ordered aggregate
    table, so device truncation is bit-identical to the host replay.

    Implemented as one multi-operand `lax.sort` rather than
    `jax.lax.top_k`: the gid tiebreak and per-key null buckets need a
    lexicographic total order a single top_k operand cannot encode
    without colliding masked groups with genuine -inf values; G is
    planner-bounded so the full sort is cheap next to the aggregation."""
    g = mask.shape[0]
    gid = jnp.arange(g, dtype=jnp.int32)
    keys = [jnp.where(mask, jnp.int8(0), jnp.int8(1))]
    for values, isnull, ascending, nulls_first in order_keys:
        v = values
        if isnull is not None:
            nb = jnp.where(
                isnull,
                jnp.int8(-1 if nulls_first else 1),
                jnp.int8(0),
            )
            keys.append(nb)
            v = jnp.where(isnull, 0, v)
        if jnp.issubdtype(v.dtype, jnp.integer) or v.dtype == bool:
            v = v.astype(jnp.int64)
        else:
            v = v.astype(jnp.float64)
        keys.append(v if ascending else -v)
    keys.append(gid)
    sorted_ops = jax.lax.sort(tuple(keys), num_keys=len(keys))
    sel = jax.lax.slice_in_dim(sorted_ops[-1], 0, cap)
    return sel, jnp.sum(mask).astype(jnp.int32)


def having_mask(tree, ref_value, values: jnp.ndarray, shape) -> jnp.ndarray:
    """On-device HAVING over finalized states with SQL's Kleene 3-valued
    semantics (NULL-aware and/or/not — the CPU executor's pc.and_kleene
    path).  `tree` is the encoded predicate from
    query/device_finalize.py; `ref_value(ref) -> (value [G], isnull [G] |
    None)` resolves aggregate refs; `values` carries the comparison
    literals by slot (runtime args, so thresholds reuse the compile).
    Returns the boolean keep mask (unknown = dropped, per SQL)."""

    def ev(node):
        kind = node[0]
        ones = jnp.ones(shape, bool)
        if kind in ("cmp", "cmpref"):
            if kind == "cmp":
                _k, op, ref, slot = node
                x, xnull = ref_value(ref)
                y, ynull = values[slot], None
            else:
                _k, op, ref1, ref2 = node
                x, xnull = ref_value(ref1)
                y, ynull = ref_value(ref2)
            x = x.astype(jnp.float64)
            y = jnp.asarray(y, jnp.float64)
            v = {
                "=": lambda: x == y, "!=": lambda: x != y,
                "<": lambda: x < y, "<=": lambda: x <= y,
                ">": lambda: x > y, ">=": lambda: x >= y,
            }[op]()
            valid = ones
            if xnull is not None:
                valid = valid & ~xnull
            if ynull is not None:
                valid = valid & ~ynull
            return v, valid
        if kind == "isnull":
            _k, ref, neg = node
            _v, isn = ref_value(ref)
            isn = jnp.zeros(shape, bool) if isn is None else isn
            return (~isn if neg else isn), ones
        if kind == "not":
            v, valid = ev(node[1])
            return ~v, valid
        av, avalid = ev(node[1])
        bv, bvalid = ev(node[2])
        if kind == "and":
            return av & bv, (
                (avalid & bvalid) | (avalid & ~av) | (bvalid & ~bv)
            )
        # "or"
        return av | bv, (
            (avalid & bvalid) | (avalid & av) | (bvalid & bv)
        )

    v, valid = ev(tree)
    return v & valid


def finalize(
    state: AggState, aggs: tuple[str, ...], counts=None
) -> dict[str, jnp.ndarray]:
    """State -> final outputs; `non_empty` marks groups with any row.
    `counts` supplies the group counts when the state skipped its own
    count pass (count-pass sharing: a column with no null mask counts
    exactly the group presence)."""
    out: dict[str, jnp.ndarray] = {}
    counts = state.counts if state.counts is not None else counts
    if counts is not None:
        out["count"] = counts
    if SUM in aggs or "avg" in aggs:
        out["sum"] = state.sums
    if "avg" in aggs:
        safe = jnp.maximum(counts, 1)
        out["avg"] = state.sums / safe
    if MIN in aggs:
        out["min"] = state.mins
    if MAX in aggs:
        out["max"] = state.maxs
    if LAST in aggs:
        out["last"] = state.last_val
        out["last_ts"] = state.last_ts
    if counts is not None:
        out["non_empty"] = counts > 0
    else:
        probe = state.mins if state.mins is not None else state.maxs
        if probe is not None:
            extreme = jnp.finfo(probe.dtype).max if probe is state.mins else jnp.finfo(probe.dtype).min
            out["non_empty"] = probe != extreme
    return out
