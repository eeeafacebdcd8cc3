"""HBM-resident SST super-tiles + single-dispatch aggregation executor.

This is the engine's answer to "the tiles are resident in HBM": instead of
re-reading Parquet, re-encoding tags and re-uploading columns on every query
(the round-1 hot path), each region's flushed SSTs are encoded ONCE — tag
strings to stable per-table dictionary codes (storage/dictionary.py),
timestamps to int64, values to float — and consolidated into ONE device
buffer per column (the "super-tile"), globally re-sorted by (pk..., ts) so
primary-key runs stay long and the blocked aggregation kernel
(ops/aggregate.py `_segment_blocked`) sees the layout it wants regardless
of how many time-sliced flushes produced the data.  A query then:

  1. snapshots each region's (files, memtables) under the region lock,
  2. fetches/extends the region's super-tile (host-side per-file encodes
     are cached, so a new flush re-uploads only concatenation, and
     dictionary growth is repaired with one device gather — no Parquet
     re-read),
  3. encodes only the memtable tail (small, vectorized),
  4. runs ONE jit-compiled program over ALL sources that computes partial
     AggStates with the shared kernels (ops/aggregate.py), merges them,
     finalizes, and packs the outputs into one [K, G] buffer,
  5. fetches that single buffer (ONE device->host transfer: every
     fetched array is its own crossing, so everything rides one buffer)
     and decodes rows on the host.

Latency is therefore flat in data size and SST count: one dispatch + one
fetch regardless of scale.

Layout strategy (what makes the hot kernel scatter-free):
  * group keys that are a primary-key prefix (in pk order) ride the
    engine's (pk, ts) sort directly;
  * other tag subsets aggregate by the sorted rows' SERIES (one run per
    series, row bounds off the region's `SeriesTable`) x bucket and fold
    down on device (ops/aggregate.py `fold_series_states`): the stage-1
    space is the series present, whatever the key's width;
  * bucket-only group-bys (TSBS single-groupby, groupby-orderby-limit) go
    time-major: rows are gathered through a cached ts-ascending
    permutation, making `gid = bucket` sorted for ANY interval.

Role-equivalents in the reference: the write/page caches
(mito2/src/cache/write_cache.rs, cache.rs — "upload on flush, serve reads
from cached media"; here the medium is HBM), the pre-encoded primary keys
(mito-codec/src/row_converter/), and the windowed-sort optimizer's use of
physical order (query/src/optimizer/windowed_sort.rs).

Correctness gate: the tile path aggregates raw file rows WITHOUT the
last-write-wins dedup pass a normal scan performs, so it only engages when
dedup is provably a no-op:
  * the table is append_mode (duplicates are semantically kept), or
  * every pair of sources (SST files + memtable) has disjoint inclusive
    time ranges — two versions of one row need equal timestamps;
and never when any source holds delete tombstones or a file predates
tombstone accounting (FileMeta.num_deletes < 0).  Anything else returns
None and the authoritative scan path runs.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import logging
import os
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from ..ops.aggregate import (
    BLOCK_ROWS,
    _FAST_MIN_ROWS as _LIMB_MIN_ROWS,
    AggState,
    finalize,
    merge_states,
    quantize_limbs,
)

# module-level jit: one trace cache shared by every ensure_limbs call
_quantize_limbs_jit = jax.jit(quantize_limbs)
from ..ops.tiles import padded_size
from ..storage.dictionary import TableDictionary
from ..storage.region import OP_COL, Region
from ..storage.sst import FileMeta
from ..query import analyze, passes
from ..utils import device_health, flight_recorder, metrics, tracing
from ..utils.deadline import check_deadline, current_deadline
from ..utils.errors import QueryTimeoutError
from ..utils.fault_injection import fire as _fault_fire
from .batcher import (
    CapturedDispatch,
    PendingFetch,
    QueryBatcher,
    WindowedResultCache,
    capture_active as _capture_active,
    defer_active as _defer_fetch_active,
    defer_suppressed as _defer_fetch_suppressed,
)
from .executor import (
    COUNT_STAR,
    SERIES_GROUP,
    SERIES_KEYS,
    SERIES_MARKS,
    SERIES_ROWS,
    DistGroupByPlan,
    GroupByResult,
    _FUNC_TO_KERNEL,
    _quantize_card,
    compute_partial_states,
    host_last_winners,
)
from .mesh import REGION_AXIS, region_device_index



# Max rows per device chunk: one chunk's kernel working set fits HBM
# comfortably even for 10-column programs (see _SuperTiles.cols).
TILE_CHUNK_ROWS = 1 << 24
# Hash-strategy gids are int64 mixed-radix composites; past this padded
# group-space size the composition would WRAP and silently alias distinct
# groups (the dense path is protected by max_groups, the hash path needs
# its own ceiling).  Margin below 2^63 keeps every intermediate
# `gid * card + c` in range too.
_HASH_GID_LIMIT = 1 << 62

# ---- declines ----------------------------------------------------------------
# Why the tile path gave the statement back (TileExecutor.execute returns
# None and the engine hands it on): the first reason noted since the
# call began, read by the engine for the `query.tpu` span's `declined`
# and for `query.legacy_fallback = false`.
_DECLINE = threading.local()


def _decline(reason: str):
    """Note `reason` for the statement in flight (the first one noted
    wins: it is the deepest check) and return None, the decline."""
    if getattr(_DECLINE, "reason", None) is None:
        _DECLINE.reason = reason
    return None


def last_decline() -> str | None:
    """The reason the current thread's last SQL tile call declined, None
    where it answered."""
    return getattr(_DECLINE, "reason", None)


# ---- flow-maintenance attribution ------------------------------------------
# Dirty-window flow recompute (flow/dataflow.py) drives its per-window
# aggregate rebuild through the normal engine entry, so it reuses this
# module's whole machinery — super-tiles, delta-extend, dispatch
# coalescing.  The thread-local scope below lets the dispatch site
# attribute those device dispatches to materialized-view maintenance
# (greptime_flow_device_dispatch_total) without threading a flag through
# every call layer.
_FLOW_MAINT = threading.local()


@contextlib.contextmanager
def flow_maintenance():
    """Scope marking the current thread's dispatches as flow maintenance."""
    prev = getattr(_FLOW_MAINT, "depth", 0)
    _FLOW_MAINT.depth = prev + 1
    try:
        yield
    finally:
        _FLOW_MAINT.depth = prev


def _in_flow_maintenance() -> bool:
    return getattr(_FLOW_MAINT, "depth", 0) > 0


# ---- fused family build scope ----------------------------------------------
# The background fused builder re-enters the NORMAL execution path to build
# planes + compile + prime the family's dispatch ("ghost" execution).  The
# thread-local scope below disables the host-serve routing and the
# family-build wait inside, so the ghost actually builds instead of
# answering from host (or deadlocking on its own future).
_FUSED_BUILD = threading.local()


@contextlib.contextmanager
def fused_build_scope():
    """Scope marking the current thread as the fused background builder."""
    prev = getattr(_FUSED_BUILD, "depth", 0)
    _FUSED_BUILD.depth = prev + 1
    try:
        # a ghost's stages move no counter, as its dispatches move none
        with tracing.counters_muted():
            yield
    finally:
        _FUSED_BUILD.depth = prev


def _in_fused_build() -> bool:
    return getattr(_FUSED_BUILD, "depth", 0) > 0


@contextlib.contextmanager
def _ambient_scope(token):
    """Re-establish the CALLER's flow-maintenance / fused-build depths on
    the device supervisor's worker thread: dispatch-time attribution
    (greptime_flow_device_dispatch_total, the fused builder's ghost
    counter skips) reads these thread-locals inside the supervised
    callable."""
    flow, fused = token
    prev = (getattr(_FLOW_MAINT, "depth", 0), getattr(_FUSED_BUILD, "depth", 0))
    _FLOW_MAINT.depth, _FUSED_BUILD.depth = flow, fused
    try:
        yield
    finally:
        _FLOW_MAINT.depth, _FUSED_BUILD.depth = prev


device_health.register_scope_propagator(
    lambda: (
        getattr(_FLOW_MAINT, "depth", 0),
        getattr(_FUSED_BUILD, "depth", 0),
    ),
    _ambient_scope,
)

# The background fused builder's ghost dispatches are best-effort work no
# query is waiting on: on a saturated box they can genuinely outlast the
# foreground call deadline, and abandoning one would quarantine every
# device (dropping all resident planes) over a harmless stall.  Bypass
# supervision on the builder thread — its own failure handling already
# owns errors there, and the foreground path it primes stays supervised.
device_health.register_bypass(_in_fused_build)

# GRAFT_TILE_TIMING=1 prints per-phase wall times of the cold path
_TIMING = os.environ.get("GRAFT_TILE_TIMING") == "1"

# Per-region wall times (ms) of the most recent region-streamed query
# (_streamed_execute).  Single-query diagnostic, not a metric.
LAST_STREAM_CHUNK_MS: list[float] = []


def _timed(phase: str):
    """Context manager printing `phase took N ms` when timing is on."""
    import contextlib

    @contextlib.contextmanager
    def cm():
        t0 = time.perf_counter()
        try:
            yield
        finally:
            if _TIMING:
                print(
                    f"TILE_TIMING {phase} {(time.perf_counter() - t0) * 1000:.0f}ms",
                    flush=True,
                )

    return cm()


def _window_tile_bytes(entry) -> int:
    """Device bytes of an entry's window tiles (a copy of the dict's values:
    callers outside the cache lock race its builds)."""
    return sum(wt["nbytes"] for wt in list(entry.window_tiles.values()))


# The most a group's limb quantization bound may be of its |sum| before the
# query reruns in exact f64.  An avg over a large group space is shipped as
# float32, one more rounding of up to 2^-24 = 5.96e-8: together under 1.1e-7,
# inside the 1.2e-7 a deployment's `value_rtol_avg_f32` states.  (At 1e-7,
# until PR 36, a window's edge bucket of two or three small rows could pass
# the verdict and read 1.6e-7: `tsbs-mesh4-heavy`, seed 3600001003.)
_LIMB_VERDICT_RTOL = 5e-8

# why `ensure_window_tile` served no tile, as the `window_tile` pass notes it
_WINDOW_DECLINED = {
    "unprobed": "plane under the window-tile floor, or without its sorted ts",
    "empty": "no row in the window",
    "cover": "window covers most of retention",
    "resident": "planes resident and their masked scan cheaper than a tile's build",
    "build": "tile build declined (a null plane or host encode is missing)",
}


@functools.partial(jax.jit, static_argnums=2)
def _permuted_chunks(chunks, perm, bounds):
    """A region's chunks in `perm`'s row order, chunked again by `bounds`.
    Jitted so that the gather and its index arithmetic are ONE program to
    compile a dtype and device."""
    full = jnp.concatenate(chunks)[perm]
    return [full[a:b] for a, b in bounds]


def _chunk_bounds(pad: int, chunk_rows: int = TILE_CHUNK_ROWS) -> list[tuple[int, int]]:
    if pad <= chunk_rows:
        return [(0, pad)]
    return [
        (o, min(o + chunk_rows, pad))
        for o in range(0, pad, chunk_rows)
    ]


def _ascending_run_starts(ts: np.ndarray) -> np.ndarray:
    """Start offsets of the maximal non-decreasing runs of `ts`.  In
    (pk, ts) order every series is one run or part of one (two adjacent
    series whose concatenation still ascends share a run, which a search
    does not mind), so no tag plane is read."""
    if len(ts) == 0:
        return np.zeros(0, np.int64)
    breaks = np.flatnonzero(ts[1:] < ts[:-1]) + 1
    return np.concatenate([np.zeros(1, np.int64), breaks])


def _run_lower_bounds(
    ts: np.ndarray, lo: np.ndarray, hi: np.ndarray, value: int
) -> np.ndarray:
    """Per run r over rows [lo[r], hi[r]) of ascending `ts`: the first row
    with ts >= value (hi[r] where none).  One bisection over all runs at
    once — log2(longest run) rounds of a gather of one row per run — so
    nothing the size of the plane is read or allocated."""
    lo, hi = lo.copy(), hi.copy()
    last = len(ts) - 1
    while True:
        live = lo < hi
        if not live.any():
            return lo
        mid = (lo + hi) >> 1
        # (a closed run's mid is its end, at most one past the plane: clipped)
        below = live & (np.asarray(ts[np.minimum(mid, last)]) < value)
        lo[below] = mid[below] + 1
        above = live & ~below
        hi[above] = mid[above]


def _range_rows(
    first: np.ndarray, end: np.ndarray, keep: np.ndarray | None = None
) -> np.ndarray:
    """Concatenation of arange(first[r], end[r]) over r, as int32 row
    indices — for disjoint ascending ranges, the ascending list of their
    rows — less the rows a `keep` plane drops."""
    lens = end - first
    total = int(lens.sum())
    # each range's first row minus the rows laid down before it, repeated
    # over the range; adding 0..total-1 gives the rows themselves
    rows = np.repeat((first - (np.cumsum(lens) - lens)).astype(np.int32), lens)
    rows += np.arange(total, dtype=np.int32)
    return rows if keep is None else rows[keep[rows]]


def _lex_merge_positions(
    old_keys: list[np.ndarray], new_keys: list[np.ndarray]
) -> np.ndarray:
    """Merge positions of two LEXICOGRAPHICALLY sorted runs: for each row
    of the (sorted) delta run, the number of old-run rows that precede it
    in the merged order.  Ties place the old run FIRST (side='right'),
    which is exactly flush order — so merging with these positions is
    bit-identical to the stable lexsort of the full concatenation a
    from-scratch rebuild performs.  Keys are listed major-first.

    Vectorized binary search over the old run: O(delta · keys · log old)
    — the delta build's whole point is that no O(total · log total)
    re-sort happens."""
    n_old = len(old_keys[0]) if old_keys else 0
    n_new = len(new_keys[0]) if new_keys else 0
    if n_new == 0:
        return np.zeros(0, np.int64)
    lo = np.zeros(n_new, np.int64)
    if n_old == 0:
        return lo
    hi = np.full(n_new, n_old, np.int64)
    while True:
        active = lo < hi
        if not active.any():
            break
        mid = (lo + hi) >> 1
        # inactive lanes (lo == hi) may sit at n_old: clip the index —
        # their comparison result is discarded by the `active` mask
        safe = np.minimum(mid, n_old - 1)
        # lexicographic old[mid] <= new: undecided ties fall through to
        # the next (more minor) key; fully-equal keys compare <=.
        gt = np.zeros(n_new, bool)
        decided = np.zeros(n_new, bool)
        for a, b in zip(old_keys, new_keys):
            av = a[safe]
            lt_k = ~decided & (av < b)
            gt_k = ~decided & (av > b)
            gt |= gt_k
            decided |= lt_k | gt_k
        le = ~gt
        lo = np.where(active & le, mid + 1, lo)
        hi = np.where(active & ~le, mid, hi)
    return lo


@functools.partial(jax.jit, static_argnames=("old_n", "new_pad"))
def _delta_patch(full, delta_vals, pos, old_n: int, new_pad: int):
    """On-device plane patch for a delta merge: scatter the resident
    (sorted) rows and the uploaded delta-sorted run into the merged
    order.  Only `pos` (O(delta) int32) and `delta_vals` cross the
    host->device link — the old rows move at HBM bandwidth."""
    n_delta = delta_vals.shape[0]
    iota_old = jnp.arange(old_n, dtype=jnp.int32)
    idx_old = iota_old + jnp.searchsorted(pos, iota_old, side="right").astype(
        jnp.int32
    )
    idx_new = pos + jnp.arange(n_delta, dtype=jnp.int32)
    out = jnp.zeros(new_pad, full.dtype)
    out = out.at[idx_old].set(full[:old_n])
    out = out.at[idx_new].set(delta_vals.astype(full.dtype))
    return out


def _entry_device_bytes(entry: "_SuperTiles") -> int:
    """Recompute an entry's resident device bytes from its live planes
    (the delta merge swaps whole plane sets; recomputing beats chasing
    increments)."""
    total = 0
    for d in (entry.cols, entry.nulls, entry.tm_cols, entry.tm_nulls):
        for chunks in d.values():
            total += sum(int(x.nbytes) for x in chunks)
    for planes in (
        entry.valid, entry.valid_dedup, entry.tm_valid, entry.tm_valid_dedup
    ):
        if planes is not None:
            total += sum(int(x.nbytes) for x in planes)
    if entry.perm is not None:
        total += int(entry.perm.nbytes)
    for chunks in entry.limb_cols.values():
        total += sum(int(l.nbytes) + int(s.nbytes) for l, s in chunks)
    total += sum(wt["nbytes"] for wt in entry.window_tiles.values())
    return total


@dataclass
class TileContext:
    """What the Database hands the tile executor for one table scan."""

    table_key: str
    dictionary: TableDictionary
    regions: list[Region]
    append_mode: bool = False
    # the columns the table's partition rule splits its rows on (empty
    # for one region): a region of a rule over a tag holds a strict subset
    # of that tag's dictionary
    partition_columns: tuple[str, ...] = ()
    # a metric-engine logical table: key, dictionary and regions above are
    # its PHYSICAL table's, and this is the `__table_id` its rows carry
    logical_table_id: int | None = None


@dataclass
class SeriesTable:
    """The series of one region's consolidated rows, read once per plane
    build off the (pk, ts) sorted host planes: the rows of series k are
    [starts[k], starts[k + 1]) and `codes[k]` its dictionary code per pk
    column (`tags`), at the dictionary epoch in `key`.  A metric engine's
    physical region holds its logical tables one after the other
    (`__table_id` leads the key), so a logical table is a range of series
    and of rows.  What a request derives from it (label tuples, label
    order) is kept in `memo` for the next: at most `MEMO_MAX` of them,
    the oldest going first, so a client that sends ever new `by` sets
    grows nothing."""

    MEMO_MAX = 32

    key: tuple
    tags: tuple[str, ...]
    starts: np.ndarray  # [S + 1] int64
    codes: np.ndarray  # [S, len(tags)] int32
    memo: dict = field(default_factory=dict)

    def __len__(self) -> int:
        return len(self.codes)

    @property
    def nbytes(self) -> int:
        return self.starts.nbytes + self.codes.nbytes

    def remember(self, key, make):
        """`memo[key]`, made by `make()` where it is not there yet."""
        if key not in self.memo:
            if len(self.memo) >= self.MEMO_MAX:
                self.memo.pop(next(iter(self.memo)))
            self.memo[key] = make()
        return self.memo[key]

    def prefix(self, j: int) -> "SeriesTable":
        """The series of the key's first `j` columns: runs of this table's
        series whose first `j` codes are equal (kept in the memo)."""

        def make():
            lead = self.codes[:, :j]
            first = np.flatnonzero(
                np.concatenate([[True], (lead[1:] != lead[:-1]).any(axis=1)])
            ) if len(lead) else np.zeros(0, np.int64)
            return SeriesTable(
                key=self.key + (j,), tags=self.tags[:j],
                starts=np.append(self.starts[first], self.starts[-1]),
                codes=lead[first],
            )

        return self.remember(("prefix", j), make)

    def code_range(self, tag: str, code: int) -> tuple[int, int]:
        """Series [s_lo, s_hi) whose LEADING key column holds `code`."""
        col = self.codes[:, self.tags.index(tag)]
        return (
            int(np.searchsorted(col, code, side="left")),
            int(np.searchsorted(col, code, side="right")),
        )


@dataclass
class _FileHostTiles:
    """Host-side encoded columns for one SST file (the build cache the
    device super-tile consolidates from; survives super-tile rebuilds so a
    new flush or eviction never re-reads Parquet for old files).

    `absent` lists value columns the file predates (ALTER ADD COLUMN):
    consolidation NULL-fills their segment — the same schema-evolution
    semantics as the reference's read compat shim
    (mito2/src/read/compat.rs)."""

    cols: dict[str, np.ndarray] = field(default_factory=dict)
    nulls: dict[str, np.ndarray] = field(default_factory=dict)
    epochs: dict[str, int] = field(default_factory=dict)
    absent: set[str] = field(default_factory=set)
    num_rows: int = 0
    nbytes: int = 0


@dataclass
class _SuperTiles:
    """One region's consolidated device tiles.

    Rows are GLOBALLY re-sorted by (pk..., ts) at consolidation (`order`):
    concatenating time-sliced flushes keeps each primary-key run short
    (rows-per-key-per-file), which explodes the blocked kernel's per-block
    group span and silently demoted round-3's first super-tiles to the
    scatter path.  The tile path never needs file boundaries (its
    eligibility gate already guarantees dedup is a no-op), so the cache
    owns the layout and picks the one the kernels want — long pk runs.
    The reference gets the same effect from compaction's sorted-run merge
    (mito2/src/compaction/run.rs); here one host-side lexsort per
    (region, file-set) replaces it."""

    region_id: int
    file_ids: tuple[str, ...]
    num_rows: int  # real rows (sum of file rows)
    pad: int  # padded (pow2) total length
    order: np.ndarray | None = None  # (pk, ts) sort of the file concat
    # Device columns are stored CHUNKED (lists of <= TILE_CHUNK_ROWS
    # arrays): one jit source per chunk keeps any single dispatch's
    # temporaries bounded — a 10-column program over one 2^26 buffer
    # overcommitted HBM (XLA schedules columns concurrently; measured
    # 38 s warm after runtime spill), while four 2^24 chunks dispatched
    # back-to-back peak at a quarter of the working set.
    cols: dict[str, list] = field(default_factory=dict)
    nulls: dict[str, list] = field(default_factory=dict)
    epochs: dict[str, int] = field(default_factory=dict)
    valid: list | None = None
    perm: jnp.ndarray | None = None  # ts-ascending gather (time-major plans)
    # host-side sorted copies of (pk codes..., ts) + file row offsets:
    # selective pk-equality queries binary-search these and aggregate the
    # tiny slice on the host, skipping the device link entirely (the role
    # of the reference's inverted index + page pruning point lookups)
    sorted_host: dict[str, np.ndarray] = field(default_factory=dict)
    # start offsets of the ascending runs of the sorted ts plane: built at
    # the first window probe (TileCacheManager._ts_runs), dropped wherever
    # the plane is replaced or grows
    ts_run_starts: np.ndarray | None = None
    # the region's series (TileCacheManager.series_table), keyed by the
    # file set and the dictionary epoch it was read at
    series_table: SeriesTable | None = None
    host_epochs: dict[str, int] = field(default_factory=dict)
    file_row_offsets: np.ndarray | None = None
    # the cold-serve router answered from host once: the next grouped
    # query builds device planes (tile_cache._host_cold_grouped)
    cold_served: bool = False
    # ts-ascending (time-major) device copies, built once per column so
    # bucket-only queries dispatch with zero per-query gathers
    tm_cols: dict[str, list] = field(default_factory=dict)
    tm_nulls: dict[str, list] = field(default_factory=dict)
    tm_valid: list | None = None
    # cached MXU limb planes (ops/aggregate.py quantize_limbs) per value
    # column, keyed ("" | "tm:") + column for the two row orders; built
    # ON DEVICE from the resident f64 plane at first sum/avg/count query,
    # so warm aggregates skip the ~3 ms/column/chunk quantize pass.
    # Evicted before whole entries under HBM pressure (_evict_locked).
    limb_cols: dict[str, list] = field(default_factory=dict)
    # last-write-wins dedup planes (built when a region's files overlap in
    # time): keep[i] = row i is the LAST version of its (pk..., ts) key.
    # The (pk, ts) lexsort is STABLE, so duplicate keys sit adjacent in
    # flush order and one shifted != over the sorted host encodes finds
    # the survivors — the TPU answer to the reference's in-stream
    # DedupReader (mito2/src/read/dedup.rs).  keep_host serves the host
    # fast path; valid_dedup replaces `valid` in device dispatches.
    keep_host: np.ndarray | None = None
    # keep_prefix[i] = kept rows among the first i (built with keep_host):
    # a window's deduplicated row count is a difference per run
    keep_prefix: np.ndarray | None = None
    valid_dedup: list | None = None
    tm_valid_dedup: list | None = None
    # consolidated (sorted, padded) host arrays mmap'd from the persisted
    # tile store: device upload slices straight out of these, skipping
    # Parquet decode + tag encode + lexsort on a fresh process
    persisted_cols: dict[str, np.ndarray] = field(default_factory=dict)
    persisted_nulls: dict[str, np.ndarray] = field(default_factory=dict)
    # window tiles: compact device tiles holding ONLY the rows inside one
    # query time window (and surviving dedup), gathered host-side from
    # the sorted encodes.  A 12 h window over 3 days of retention scans
    # 6x less data than masking the full super-tile — retention must not
    # tax windowed queries (the reference prunes SSTs/row groups by time;
    # this is the tile-resident equivalent).  Key: (wlo, whi, dedup).
    window_tiles: dict[tuple, dict] = field(default_factory=dict)
    # dictionary epochs the persisted tag codes were written at: survives
    # release_unneeded (which pops entry.epochs), so a RE-upload from the
    # mmap stamps the true stored epoch and repair still gathers forward
    persisted_epochs: dict[str, int] = field(default_factory=dict)
    nbytes: int = 0
    host_nbytes: int = 0  # sorted_host/order/offsets bytes (host budget)
    # introspection (information_schema.tile_cache_entries): in-place
    # delta merges absorbed since the entry was built, and the wall-clock
    # stamp of the last query that touched it
    delta_extends: int = 0
    last_hit: float = 0.0


@dataclass(frozen=True)
class PlaneManifest:
    """One query plan's (or prewarm request's) device-plane requirements —
    the unit the fused build planner consolidates.  Each cold query (and
    each `Database.prewarm()` call) emits one; the consolidation layer
    unions manifests across the whole family before building, so one pass
    decodes each SST file once, host-encodes each column once, and batches
    uploads through the pipelined `_upload_missing` producer/consumer (the
    SystemML fused-operator-plan idea applied to the tile cold path:
    sibling consumers share scans/encodes instead of re-materializing)."""

    table_key: str
    tag_cols: tuple = ()  # tag code planes (group + filter + layout tags)
    ts_col: str | None = None
    value_cols: tuple = ()  # f64 value planes (or window-tile columns)
    limb_cols: tuple = ()  # MXU limb planes (sum/avg columns)
    time_major: bool = False  # ts-ascending copies + perm
    window: tuple | None = None  # (lo, hi): compact window-tile geometry
    dedup: bool = False  # LWW keep plane


def _files_overlap(regions) -> bool:
    """Whether some region's SSTs overlap in time (rows of one key may then
    sit in two files, and last-write-wins needs the keep plane)."""
    for region in regions:
        metas, _mems, _version = region.tile_snapshot()
        ranges = sorted(m.time_range for m in metas)
        if any(a[1] >= b[0] for a, b in zip(ranges, ranges[1:])):
            return True
    return False


class _FamilyBuild:
    """One in-flight fused family build: the leader runs the consolidated
    build + priming dispatch; concurrent queries of the family wait on
    `event` and adopt the leader's planes instead of building twice."""

    __slots__ = ("event", "error")

    def __init__(self):
        self.event = threading.Event()
        self.error = None


@dataclass
class _FusedItem:
    """One queued background family build (ghost execution inputs).
    SQL families carry the lowering for the default ghost execution;
    other engines (the TQL tile path) pass `run` — a self-contained
    callable that warms + primes their family — and leave the lowering
    fields None."""

    fp: tuple
    rec: _FamilyBuild
    lowering: object  # copy — ghost execution mutates post_done
    schema: object
    time_bounds: object
    ctx: TileContext
    manifest: PlaneManifest
    run: object = None  # callable | None — custom ghost execution


class TileCacheManager:
    """Device-resident per-region super-tiles + host-side per-file encode
    cache, both LRU-bounded.

    With more than one local device, chunks place ROUND-ROBIN across the
    device list: each chunk's partial AggState is computed where its data
    lives (jit follows committed inputs) and the [G]-sized states — tiny
    next to the chunks — merge on device 0, the reference MergeScan's
    N:1 fan-in (merge_scan.rs:250) with ICI playing the stream transport.
    `chunk_rows` is configurable so the multichip dryrun can drive this
    exact path with toy chunks on virtual CPU devices."""

    def __init__(
        self,
        budget_bytes: int = 8 << 30,
        host_budget_bytes: int | None = None,
        chunk_rows: int = TILE_CHUNK_ROWS,
        devices: list | None = None,
        persist_dir: str | None = None,
    ):
        self.budget = budget_bytes
        self.host_budget = host_budget_bytes or budget_bytes * 2
        self.chunk_rows = chunk_rows
        self.devices = devices if devices is not None else list(jax.devices())
        # On-disk home for consolidated encodes (persisted super-tiles):
        # a FRESH process mmaps them instead of re-reading Parquet,
        # re-encoding tags and re-sorting 100M rows — the dominant cold
        # cost (measured minutes at TSBS 3-day scale; the reference's
        # cold path has no consolidation step to pay, so ours must not
        # either).  None disables persistence.
        self.persist_dir = persist_dir
        # QueryConfig wired by the engine: pass toggles (disabled_passes)
        # reach chunk placement through it
        self.config = None
        # TileConfig wired by the Database: lifecycle knobs (incremental
        # delta maintenance, pipelined cold builds).  None = defaults on.
        self.tile_config = None
        # AdmissionConfig wired by the Database: overload-survival knobs
        # (dispatch coalescing, HBM probe, halve-chunk retry).  None =
        # everything off, pre-layer behavior bit-for-bit.
        self.admission_config = None
        # BatchConfig wired by the Database: cross-query batching window
        # + windowed result cache.  None = both off, pre-layer bit-for-bit.
        self.batch_config = None
        # WindowedResultCache, created lazily by the executor when
        # batch.result_cache_mb > 0; invalidate_region purges it
        self.result_cache = None
        self._persist_pool: set[str] = set()  # filesets being written
        self._meshes: dict[tuple, object] = {}  # (n, device ids) -> Mesh
        self._lock = threading.RLock()
        self._super: OrderedDict[int, _SuperTiles] = OrderedDict()
        self._host: OrderedDict[tuple[int, str], _FileHostTiles] = OrderedDict()
        self._used = 0
        self._host_used = 0
        self._region_versions: dict[int, int] = {}
        # files that can never join a super-tile (missing tag/ts column,
        # row-count mismatch): excluded from the entry; queries whose
        # window touches them fall back to the scan path
        self._bad_files: set[tuple[int, str]] = set()
        # fused build planner (tile.fused_build): per-table ring of
        # plane-requirement manifests recorded by query plans / prewarm —
        # the union the consolidated family build materializes in one pass
        self._manifests: dict[str, OrderedDict] = {}
        # per-(table, plane-key) in-flight cold-build events: concurrent
        # full builds (prewarm-on-flush racing a live query, two cold
        # queries) coalesce onto the leader's build (build_gate)
        self._build_events: dict[tuple, threading.Event] = {}
        # halve-chunk degrade rounds survived (information_schema
        # device_memory / the flight recorder's HBM snapshot)
        self.degrade_rounds = 0
        # last device-health generation this cache synced against: a
        # quarantine bumps the supervisor's generation, and health_sync
        # drops device planes lazily on the next query (resident planes
        # on a wedged device are unreachable state, not truth).  Snapshot
        # the live generation: a cache born after an old quarantine holds
        # nothing worth invalidating
        self._health_gen = device_health.SUPERVISOR.generation

    _MANIFESTS_PER_TABLE = 64

    def record_manifest(self, manifest: PlaneManifest) -> bool:
        """Register one family's plane requirements for the fused build
        planner.  Returns True when the manifest is new for the table."""
        with self._lock:
            d = self._manifests.setdefault(manifest.table_key, OrderedDict())
            if manifest in d:
                d.move_to_end(manifest)
                return False
            d[manifest] = None
            while len(d) > self._MANIFESTS_PER_TABLE:
                d.popitem(last=False)
        metrics.TILE_FUSED_MANIFESTS.inc()
        return True

    def family_manifests(self, table_key: str) -> list[PlaneManifest]:
        with self._lock:
            return list(self._manifests.get(table_key, ()))

    @contextlib.contextmanager
    def build_gate(self, table_key: str, kind: str = "fused"):
        """Per-(table, plane-key) cold-build coalescing: the first caller
        becomes the LEADER (yields True) and runs the build; concurrent
        callers block until the leader finishes and yield False — they
        adopt the leader's planes (every ensure_*/super_tiles call is then
        a cache hit) instead of running a duplicate full build
        (`greptime_tile_build_coalesced_total`)."""
        key = (table_key, kind)
        with self._lock:
            ev = self._build_events.get(key)
            leader = ev is None
            if leader:
                ev = self._build_events[key] = threading.Event()
        if leader:
            try:
                yield True
            finally:
                with self._lock:
                    self._build_events.pop(key, None)
                ev.set()
            return
        metrics.TILE_BUILD_COALESCED.inc()
        tracing.add_event("tile.build_coalesced", table=table_key)
        deadline = current_deadline()
        while not ev.is_set():
            timeout = None if deadline is None else deadline - time.monotonic()
            if timeout is not None and timeout <= 0:
                check_deadline()
            ev.wait(timeout if timeout is None else max(timeout, 0.01))
        yield False

    def _tile_opt(self, name: str, default):
        """Lifecycle knob lookup: config.tile when wired, else default."""
        if self.tile_config is not None:
            return getattr(self.tile_config, name, default)
        return default

    # ---- bookkeeping -------------------------------------------------------
    def has_region(self, region_id: int) -> bool:
        """True when a consolidated super-tile is resident for the region
        (the cost model skips CPU routing then — the tile path's host fast
        branch serves selective queries in milliseconds)."""
        with self._lock:
            return region_id in self._super

    def stats(self) -> dict:
        with self._lock:
            return {
                "regions": len(self._super),
                "bytes": self._used,
                "host_files": len(self._host),
                "host_bytes": self._host_used,
            }

    def invalidate_region(self, region_id: int, keep_file_ids: set[str] | None = None):
        """Drop host tiles of files no longer in the region's manifest and
        the region's super-tile when its file set changed."""
        with self._lock:
            for key in list(self._host):
                if key[0] == region_id and (
                    keep_file_ids is None or key[1] not in keep_file_ids
                ):
                    self._host_used -= self._host.pop(key).nbytes
            for key in list(self._bad_files):
                if key[0] == region_id and (
                    keep_file_ids is None or key[1] not in keep_file_ids
                ):
                    self._bad_files.discard(key)
            entry = self._super.get(region_id)
            if entry is not None and (
                keep_file_ids is None
                or not set(entry.file_ids) <= keep_file_ids
            ):
                dropped = self._super.pop(region_id)
                self._used -= dropped.nbytes
                self._host_used -= dropped.host_nbytes
            self._region_versions.pop(region_id, None)
        rc = self.result_cache
        if rc is not None:
            rc.purge_region(region_id)

    def invalidate_region_if_changed(
        self, region_id: int, keep_file_ids: set[str], manifest_version: int
    ):
        """Version-gated sweep: runs only when the region's manifest
        actually advanced since the last query."""
        with self._lock:
            if self._region_versions.get(region_id) == manifest_version:
                return
        self.invalidate_region(region_id, keep_file_ids)
        with self._lock:
            self._region_versions[region_id] = manifest_version

    def device_used(self) -> list[int]:
        """This cache's bytes on each mesh device (tile.mesh_devices > 1),
        from where chunk_device places an entry's chunks: a region's
        first chunk on its own mesh slot, further chunks round robin from
        there, an entry's bytes taken as even over its chunks.  Off the
        mesh path one sum, `_used`: chunks round-robin over ALL devices
        there and `budget` has always been held against their total."""
        mesh_n = self.mesh_devices()
        if mesh_n <= 1:
            return [self._used]
        used = [0] * mesh_n
        for rid, entry in list(self._super.items()):
            slots = self._region_slots(rid, mesh_n)
            for d in slots:
                used[d] += entry.nbytes // len(slots)
        return used

    def _region_slots(self, region_id: int, mesh_n: int) -> list[int]:
        """The mesh slot of each chunk of a region's entry (chunk_device);
        of a region with no entry yet, the slot of its first chunk."""
        if not passes.enabled("chunk_placement", self.config):
            return [0]
        base = region_device_index(region_id, mesh_n)
        entry = self._super.get(region_id)
        chunks = len(_chunk_bounds(entry.pad if entry else 0, self.chunk_rows))
        return [(base + i) % mesh_n for i in range(chunks)]

    def _over_locked(
        self, limit: int, est: int = 0, est_regions: set[int] = frozenset(),
    ) -> set[int]:
        """The regions with planes on a device that holds more than
        `limit` bytes of this cache, `est` bytes about to land beside
        `est_regions` counted in: empty where the budget holds.
        `budget` is ONE chip's share (query.tile_cache_mb, sized against a
        16 GB chip) and under the mesh path every chip is held to it by
        itself, so four one-region tables that all hash to chip 0 cannot
        fill it fourfold while a table spread over four chips may hold the
        share of each."""
        used = self.device_used()
        if len(used) == 1:
            return set(self._super) if used[0] + est > limit else set()
        for rid in est_regions:
            for d in set(self._region_slots(rid, len(used))):
                used[d] += est
        full = {d for d, n in enumerate(used) if n > limit}
        return {
            rid for rid in self._super
            if full.intersection(self._region_slots(rid, len(used)))
        }

    def _reserve_locked(self, est: int, pinned_regions: set[int]):
        """Make room for `est` bytes ABOUT to allocate on device beside
        `pinned_regions`' planes: evict as if they were already there.
        Every ensure_* path that allocates must reserve first — charging
        after allocation let transients overshoot HBM at TSBS 3-day
        scale."""
        if est and self._over_locked(self.budget, est, pinned_regions):
            self._evict_locked(pinned_regions, est=est)

    def release_unneeded(self, entry: _SuperTiles, keep_cols: set[str]):
        """Drop THIS entry's device planes for columns the current query
        does not touch (f64/null/limb).  Whole-entry eviction can't help
        when one region holds everything (TSBS 3-day = one entry whose
        resident planes alone approach the budget): a time-major build
        would OOM against column planes only OTHER query families use.
        In-flight queries on those columns keep their arrays alive via
        references; the cache just forgets and rebuilds later."""
        with self._lock:
            freed = 0
            for d in (entry.cols, entry.nulls):
                for name in list(d):
                    if name not in keep_cols:
                        freed += sum(int(x.nbytes) for x in d[name])
                        del d[name]
                        entry.epochs.pop(name, None)
            for d in (entry.tm_cols, entry.tm_nulls):
                for name in list(d):
                    if name not in keep_cols:
                        freed += sum(int(x.nbytes) for x in d[name])
                        del d[name]
            for key in list(entry.limb_cols):
                base = key.split(":", 1)[-1]
                if base not in keep_cols:
                    freed += sum(
                        int(l.nbytes) + int(s.nbytes)
                        for l, s in entry.limb_cols[key]
                    )
                    del entry.limb_cols[key]
            for key in list(entry.window_tiles):
                wt = entry.window_tiles[key]
                if not all(
                    c in wt["cols"] or c in wt["limbs"] for c in keep_cols
                ):
                    freed += wt["nbytes"]
                    del entry.window_tiles[key]
            entry.nbytes -= freed
            if self._super.get(entry.region_id) is entry:
                self._used -= freed
            return freed

    def emergency_release(self, pinned_regions: set[int]):
        """Device OOM recovery: strip every re-derivable plane (limb +
        time-major copies + perms) and evict unpinned entries down to
        half the budget, so a retry dispatch sees maximal free HBM.
        In-flight queries keep their own arrays alive via references."""
        with self._lock:
            for entry in list(self._super.values()):
                freed = sum(
                    sum(int(l.nbytes) + int(s.nbytes) for l, s in chunks)
                    for chunks in entry.limb_cols.values()
                )
                entry.limb_cols.clear()
                freed += sum(wt["nbytes"] for wt in entry.window_tiles.values())
                entry.window_tiles.clear()
                for attr in ("tm_valid", "tm_valid_dedup"):
                    planes = getattr(entry, attr)
                    if planes is not None:
                        freed += sum(int(x.nbytes) for x in planes)
                        setattr(entry, attr, None)
                for d in (entry.tm_cols, entry.tm_nulls):
                    for chunks in d.values():
                        freed += sum(int(x.nbytes) for x in chunks)
                    d.clear()
                if entry.perm is not None:
                    freed += int(entry.perm.nbytes)
                    entry.perm = None
                entry.nbytes -= freed
                self._used -= freed
            self._evict_locked(pinned_regions, self.budget // 2)

    def probe_hbm(self, headroom: float = 0.9) -> int:
        """Startup allocation probe (admission.hbm_probe): measure REAL
        free device memory — a touch allocation forces the runtime to
        materialize its allocator, then `memory_stats` reports what is
        actually free — and clamp the tile budget to headroom x measured
        instead of trusting the configured model-based number.  Backends
        without memory_stats (CPU, some plugins) report 0 and leave the
        configured budget in force.  Returns the measured free bytes."""
        free = 0
        try:
            dev = self.devices[0]

            def _probe():
                probe = jax.device_put(np.zeros(1 << 16, np.uint8), dev)
                probe.block_until_ready()
                stats = dev.memory_stats() or {}
                del probe
                return stats

            stats = device_health.supervised_call(
                "memory_stats", _probe, devices=(0,)
            )
            limit = int(stats.get("bytes_limit", 0))
            in_use = int(stats.get("bytes_in_use", 0))
            free = max(limit - in_use, 0)
        except Exception:  # noqa: BLE001 — the probe is best-effort
            free = 0
        metrics.HBM_PROBE_FREE_BYTES.set(free)
        if free > 0:
            clamped = int(free * headroom)
            if clamped < self.budget:
                logging.getLogger("greptimedb_tpu.tile").warning(
                    "HBM probe: measured free %d MB < configured tile "
                    "budget %d MB; clamping to %d MB (headroom %.2f)",
                    free >> 20, self.budget >> 20, clamped >> 20, headroom,
                )
                self.budget = clamped
        return free

    def degrade_chunks(self, floor_rows: int) -> bool:
        """Closed HBM feedback loop, step 2 (admission.hbm_retry): after a
        RESOURCE_EXHAUSTED survived the one-shot emergency retry, halve
        the chunk geometry (never below `floor_rows`) and drop every
        super-tile entry so the rebuild uploads at the smaller size —
        each dispatch's working set halves, which is the degradation the
        runtime asked for.  Per-file host encodes and persisted
        consolidations survive, so the rebuild is consolidate (or mmap)
        + upload, not a Parquet re-read.  In-flight queries keep their
        arrays alive via references.  Returns False once already at the
        floor (the caller stops halving and lets the error surface)."""
        with self._lock:
            # Clamp the floor to the CURRENT geometry: a floor above a
            # small configured tile_chunk_rows must never GROW the
            # per-dispatch working set mid-OOM.
            floor = min(max(int(floor_rows), 4096), self.chunk_rows)
            new = max(self.chunk_rows // 2, floor)
            halved = new < self.chunk_rows
            self.chunk_rows = new
            for rid in list(self._super):
                dropped = self._super.pop(rid)
                self._used -= dropped.nbytes
                self._host_used -= dropped.host_nbytes
                self._region_versions.pop(rid, None)
            self.degrade_rounds += 1
        metrics.HBM_CHUNK_ROWS.set(self.chunk_rows)
        return halved

    # ---- introspection snapshots (information_schema + /debug/tile) -------
    def introspect_entries(self) -> list[dict]:
        """Point-in-time snapshot of every resident super-tile entry for
        the introspection surfaces (information_schema.tile_cache_entries
        and /debug/tile).  The WHOLE walk — including each entry's plane
        dicts — runs under the cache lock: a background fused build, limb
        quantize or eviction mutates those dicts concurrently, and
        iterating them unlocked is a 'dictionary changed size during
        iteration' crash on exactly the query an operator runs while the
        system is busy.  One shared impl so the two surfaces cannot
        diverge."""
        out: list[dict] = []
        with self._lock:
            for rid, e in self._super.items():
                state = "cold_served" if e.cold_served else (
                    "persisted" if e.persisted_cols and not e.cols else "live"
                )
                planes: list[tuple] = []  # (kind, plane, dev_b, host_b, chunks)
                for name, chunks in sorted(e.cols.items()):
                    planes.append(("column", name,
                                   sum(int(c.nbytes) for c in chunks), 0,
                                   len(chunks)))
                for name, chunks in sorted(e.nulls.items()):
                    planes.append(("null", name,
                                   sum(int(c.nbytes) for c in chunks), 0,
                                   len(chunks)))
                for name, chunks in sorted(e.tm_cols.items()):
                    planes.append(("time_major", name,
                                   sum(int(c.nbytes) for c in chunks), 0,
                                   len(chunks)))
                for name, chunks in sorted(e.limb_cols.items()):
                    planes.append(("limb", name,
                                   sum(int(l.nbytes) + int(s.nbytes)
                                       for l, s in chunks), 0, len(chunks)))
                for key, wt in sorted(e.window_tiles.items(), key=repr):
                    planes.append(("window", f"[{key[0]},{key[1]})",
                                   int(wt.get("nbytes", 0)), 0, 1))
                for name, arr in sorted(e.persisted_cols.items()):
                    planes.append(("persisted", name, 0, int(arr.nbytes), 1))
                for name, arr in sorted(e.sorted_host.items()):
                    planes.append(("sorted_host", name, 0, int(arr.nbytes), 1))
                out.append({
                    "region_id": rid,
                    "state": state,
                    "rows": e.num_rows,
                    "padded_rows": e.pad,
                    "device_bytes": int(e.nbytes),
                    "host_bytes": int(e.host_nbytes),
                    "columns": sorted(e.cols),
                    "time_major": sorted(e.tm_cols),
                    "limbs": sorted(e.limb_cols),
                    "window_tiles": len(e.window_tiles),
                    "persisted": sorted(e.persisted_cols),
                    "delta_extends": e.delta_extends,
                    "cold_served": e.cold_served,
                    "last_hit_ms": int(e.last_hit * 1000),
                    "planes": planes,
                })
        return out

    def device_memory_rows(self) -> list[dict]:
        """Per-device HBM accounting — the runtime's own memory_stats
        beside the tile cache's budget loop; shared by
        information_schema.device_memory and /debug/tile."""
        rows: list[dict] = []
        used = self.device_used()
        for i, dev in enumerate(self.devices):
            try:
                stats = device_health.supervised_call(
                    "memory_stats",
                    lambda d=dev: d.memory_stats() or {},
                    devices=(i,),
                ) or {}
            except Exception:  # noqa: BLE001 — CPU devices have no stats
                stats = {}
            # off the mesh path one sum, shown on every row as ever
            in_use = used[0] if len(used) == 1 else (used[i] if i < len(used) else 0)
            rows.append({
                "device": i,
                "device_kind": str(dev),
                "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                "bytes_limit": int(stats.get("bytes_limit", 0)),
                "tile_budget": int(self.budget),
                "tile_in_use": int(in_use),
                "tile_headroom": int(self.budget - in_use),
                "chunk_rows": int(self.chunk_rows),
                "degrade_rounds": int(self.degrade_rounds),
            })
        return rows

    # ---- persisted consolidated encodes ------------------------------------
    def _fileset_dir(self, region_id: int, file_ids: tuple[str, ...]) -> str | None:
        if not self.persist_dir:
            return None
        import hashlib

        h = hashlib.sha1("|".join(file_ids).encode()).hexdigest()[:16]
        return os.path.join(self.persist_dir, f"region_{region_id}", h)

    def _try_load_persisted(
        self, entry: _SuperTiles, dictionary: TableDictionary
    ) -> bool:
        """Attach a persisted consolidation to a fresh entry: order,
        sorted host planes, file offsets and mmap'd column buffers.
        Returns True when the store matched this exact file-set AND its
        tag codes can still be brought to the current dictionary."""
        d = self._fileset_dir(entry.region_id, entry.file_ids)
        if d is None or not os.path.exists(os.path.join(d, "meta.json")):
            return False
        try:
            import json

            with open(os.path.join(d, "meta.json")) as f:
                meta = json.load(f)
            if tuple(meta["file_ids"]) != entry.file_ids:
                return False
            stored_epochs = [
                *meta.get("host_epochs", {}).values(),
                *meta.get("epochs", {}).values(),
            ]
            if not all(dictionary.can_repair_from(e) for e in stored_epochs):
                # written by an earlier process before the dictionary
                # last grew (another region of the table added tag values
                # that sort in between): the codes are stale and the
                # permutation that would repair them died with that
                # process.  Drop the store; the rebuild persists afresh.
                import shutil

                shutil.rmtree(d, ignore_errors=True)
                return False
            entry.order = np.load(os.path.join(d, "order.npy"), mmap_mode="r")
            entry.file_row_offsets = np.load(os.path.join(d, "offsets.npy"))
            for c in meta["sorted_host"]:
                entry.sorted_host[c] = np.load(
                    os.path.join(d, f"sh_{c}.npy"), mmap_mode="r"
                )
            entry.ts_run_starts = None  # run bounds are one plane's, never carried
            for c, epoch in meta.get("host_epochs", {}).items():
                entry.host_epochs[c] = epoch
            for c in meta["cols"]:
                entry.persisted_cols[c] = np.load(
                    os.path.join(d, f"col_{c}.npy"), mmap_mode="r"
                )
            for c in meta.get("nulls", []):
                entry.persisted_nulls[c] = np.load(
                    os.path.join(d, f"nul_{c}.npy"), mmap_mode="r"
                )
            for c, epoch in meta.get("epochs", {}).items():
                entry.epochs[c] = epoch
                entry.persisted_epochs[c] = epoch
            hb = entry.order.nbytes + entry.file_row_offsets.nbytes
            hb += sum(a.nbytes for a in entry.sorted_host.values())
            entry.host_nbytes += hb
            with self._lock:
                self._host_used += hb
            metrics.TILE_PERSIST_HITS.inc()
            return True
        except Exception:  # noqa: BLE001 — a torn store is just a miss
            return False

    def attach_persisted(self, entry: _SuperTiles, wait_s: float = 0.0) -> bool:
        """mmap an existing persisted consolidation's column buffers into
        the LIVE entry (`persisted_cols`/`persisted_nulls`), optionally
        waiting out an in-flight `_persist_async` writer.  The cold-serve
        router's value-column reads then page straight off the mmap (only
        the rows a window mask touches) instead of re-gathering the whole
        column from per-file host tiles — at TSBS 3-day scale that gather
        costs seconds per column, which is the difference between a
        first-query cold under 2x reference and one over it."""
        import json as _json

        d = self._fileset_dir(entry.region_id, entry.file_ids)
        if d is None:
            return False
        meta_p = os.path.join(d, "meta.json")
        deadline = time.monotonic() + max(wait_s, 0.0)
        grace = 40  # ~2 s for _persist_async's thread to register/spawn
        while not os.path.exists(meta_p):
            with self._lock:
                writing = d in self._persist_pool
            if not writing:
                grace -= 1
                if grace <= 0:
                    return False  # persist never started (or failed)
            if time.monotonic() >= deadline:
                return False
            check_deadline()
            time.sleep(0.05)
        try:
            with open(meta_p) as f:
                meta = _json.load(f)
            if tuple(meta.get("file_ids", ())) != entry.file_ids:
                return False
            for c in meta.get("cols", ()):
                if c not in entry.persisted_cols:
                    entry.persisted_cols[c] = np.load(
                        os.path.join(d, f"col_{c}.npy"), mmap_mode="r"
                    )
            for c in meta.get("nulls", ()):
                if c not in entry.persisted_nulls:
                    entry.persisted_nulls[c] = np.load(
                        os.path.join(d, f"nul_{c}.npy"), mmap_mode="r"
                    )
            for c, epoch in meta.get("epochs", {}).items():
                entry.persisted_epochs.setdefault(c, epoch)
            return True
        except Exception:  # noqa: BLE001 — a torn store is just a miss
            return False

    def _persist_async(self, entry: _SuperTiles, host_tiles, tag_cols, dictionary):
        """Write the consolidation to disk in the background so the NEXT
        process skips Parquet decode + encode + lexsort.  One writer per
        fileset; files land under a tmp name and meta.json commits last,
        so readers never see a torn store."""
        d = self._fileset_dir(entry.region_id, entry.file_ids)
        if d is None:
            return
        if os.path.exists(os.path.join(d, "meta.json")):
            return  # completed store: a cold re-entry must not rewrite GBs
        with self._lock:
            if d in self._persist_pool:
                return
            self._persist_pool.add(d)
            # snapshot UNDER the cache lock: repairs swap tile arrays and
            # advance epochs under this same lock, so the captured code
            # arrays and their epoch labels cannot tear apart (codes at
            # epoch N persisted with label N+1 would skip repair forever)
            order = entry.order
            offsets = entry.file_row_offsets
            sorted_host = dict(entry.sorted_host)
            host_epochs = dict(entry.host_epochs)
            num_rows, pad = entry.num_rows, entry.pad
            cols_src: dict[str, tuple] = {}
            union: set[str] = set()
            for ht in host_tiles:
                union |= set(ht.cols)
            epochs: dict[str, int] = {}
            for name in union:
                if not all(name in ht.cols or name in ht.absent for ht in host_tiles):
                    continue
                cols_src[name] = (
                    [ht.cols.get(name) for ht in host_tiles],
                    [ht.nulls.get(name) for ht in host_tiles],
                    [name in ht.absent for ht in host_tiles],
                    [ht.num_rows for ht in host_tiles],
                )
                if name in tag_cols:
                    # the epoch the captured arrays are ACTUALLY at
                    epochs[name] = next(
                        (
                            ht.epochs[name]
                            for ht in host_tiles
                            if name in ht.epochs
                        ),
                        dictionary.epoch,
                    )

        def write():
            import json
            import tempfile

            try:
                os.makedirs(d, exist_ok=True)
                # prune older filesets of this region (superseded stores)
                parent = os.path.dirname(d)
                for sib in os.listdir(parent):
                    p = os.path.join(parent, sib)
                    if p != d:
                        import shutil

                        shutil.rmtree(p, ignore_errors=True)

                def save(name, arr):
                    tmp = os.path.join(d, f".tmp_{name}")
                    np.save(tmp, arr)
                    os.replace(tmp + ".npy", os.path.join(d, f"{name}.npy"))

                save("order", np.asarray(order, dtype=np.int32))
                save("offsets", np.asarray(offsets))
                for c, arr in sorted_host.items():
                    save(f"sh_{c}", np.asarray(arr))
                col_names, null_names = [], []
                for name, (parts, nulls, absents, nrows) in cols_src.items():
                    dtype = next(
                        (p.dtype for p in parts if p is not None), np.float64
                    )
                    cat = np.concatenate([
                        p if p is not None else np.zeros(n, dtype)
                        for p, n in zip(parts, nrows)
                    ])
                    buf = np.zeros(pad, dtype=cat.dtype)
                    buf[:num_rows] = cat[order]
                    save(f"col_{name}", buf)
                    col_names.append(name)
                    if any(n is not None for n in nulls) or any(absents):
                        ncat = np.concatenate([
                            n if n is not None else np.full(cnt, not absent)
                            for n, absent, cnt in zip(nulls, absents, nrows)
                        ])
                        nbuf = np.zeros(pad, bool)
                        nbuf[:num_rows] = ncat[order]
                        save(f"nul_{name}", nbuf)
                        null_names.append(name)
                meta = {
                    "file_ids": list(entry.file_ids),
                    "num_rows": num_rows,
                    "pad": pad,
                    "cols": col_names,
                    "nulls": null_names,
                    "sorted_host": sorted(sorted_host),
                    "host_epochs": host_epochs,
                    "epochs": epochs,
                }
                fd, tmp = tempfile.mkstemp(dir=d)
                with os.fdopen(fd, "w") as f:
                    json.dump(meta, f)
                os.replace(tmp, os.path.join(d, "meta.json"))
                metrics.TILE_PERSIST_WRITES.inc()
            except Exception:  # noqa: BLE001 — persistence is best-effort
                pass
            finally:
                with self._lock:
                    self._persist_pool.discard(d)

        threading.Thread(target=write, name="tile-persist", daemon=True).start()

    def mesh(self, n_devices: int):
        """The (cached) 1-D `regions` mesh for multi-chip tile dispatch
        (tile.mesh_devices); built lazily per device count — over the
        SURVIVING device set, so a quarantine re-shards the mesh onto
        healthy chips (the cache key carries the device identities)."""
        devs = tuple(self.placement_devices()[:n_devices])
        key = (n_devices, tuple(id(d) for d in devs))
        with self._lock:
            m = self._meshes.get(key)
            if m is None:
                from .mesh import make_mesh

                m = self._meshes[key] = make_mesh(
                    n_devices, devices=list(devs)
                )
            return m

    def mesh_devices(self) -> int:
        """Live tile.mesh_devices knob, clamped to what exists AND
        answers: quarantined devices don't count, so the mesh path
        shrinks to the surviving set (1 survivor = single-chip)."""
        n = int(self._tile_opt("mesh_devices", 0) or 0)
        return min(max(n, 0), len(self.placement_devices()))

    def placement_devices(self) -> list:
        """Devices eligible for chunk placement / mesh sharding: the
        healthy subset per the device supervisor.  With every device
        quarantined the full list returns (the executor bails to the
        host path before dispatching; an empty list would just crash
        placement arithmetic)."""
        sup = device_health.SUPERVISOR
        if not sup.enabled:
            return self.devices
        idx = sup.healthy_indices(len(self.devices))
        if not idx or len(idx) == len(self.devices):
            return self.devices
        return [self.devices[i] for i in idx]

    def health_sync(self):
        """Lazy quarantine reaction, called on the query path before any
        dispatch: when the supervisor's generation moved (a device was
        quarantined or healed since the last sync), drop every super-tile
        entry's device planes — chunks round-robin across ALL devices, so
        any entry may hold planes on the wedged chip, and a rebuild on
        the surviving set is exactly what the fused builder is for.
        Host-side encodes and the windowed result cache survive (both
        host memory, both still correct)."""
        sup = device_health.SUPERVISOR
        if not sup.enabled:
            return
        gen = sup.generation
        if gen == self._health_gen:
            return
        with self._lock:
            if gen == self._health_gen:
                return
            self._health_gen = gen
            for rid in list(self._super):
                dropped = self._super.pop(rid)
                self._used -= dropped.nbytes
                self._host_used -= dropped.host_nbytes
                self._region_versions.pop(rid, None)
        metrics.TILE_HEALTH_INVALIDATIONS.inc()
        logging.getLogger("greptimedb_tpu.tile").warning(
            "device health generation %d: dropped device planes for "
            "rebuild on the surviving device set", gen,
        )

    def chunk_device(self, i: int, region_id: int | None = None):
        """Device for chunk index i (round-robin over healthy local
        devices; disabling the chunk_placement pass pins every chunk to
        the first healthy device, e.g. while debugging a multi-device
        state merge).  With the mesh path on (tile.mesh_devices > 0) a
        region's chunks start at the region's co-located device slot
        (parallel/mesh.py region_device_index) so single-chunk regions
        land whole on their owning datanode's device and the mesh
        dispatch consumes them without a cross-device hop."""
        devs = self.placement_devices()
        if not passes.enabled("chunk_placement", self.config):
            return devs[0]
        mesh_n = self.mesh_devices()
        if mesh_n > 0 and region_id is not None:
            base = region_device_index(region_id, mesh_n)
            return devs[(base + i) % mesh_n]
        return devs[i % len(devs)]

    def _up_chunks(self, buf: np.ndarray, bounds, region_id: int | None = None) -> list:
        """Upload a consolidated host buffer chunk-wise, each chunk onto
        its round-robin device (single-device: plain uploads).  The one
        host->device chokepoint for plane traffic, so the flight
        recorder meters its wall time + bytes as the `upload` stage —
        and a supervised call (device_health): a wedged upload abandons
        at the hard deadline instead of hanging the query."""
        t0 = time.perf_counter()
        if len(self.devices) <= 1:
            out = device_health.supervised_call(
                "upload",
                lambda: [jnp.asarray(buf[a:b]) for a, b in bounds],
                devices=(0,),
            )
        else:
            # placement decided on the caller thread (it reads config /
            # supervisor state); only the raw uploads ride the worker
            placed = [
                (self.chunk_device(i, region_id), a, b)
                for i, (a, b) in enumerate(bounds)
            ]
            dev_index = {id(d): i for i, d in enumerate(self.devices)}
            involved = tuple(sorted({
                dev_index[id(d)] for d, _, _ in placed if id(d) in dev_index
            })) or (0,)
            out = device_health.supervised_call(
                "upload",
                lambda: [
                    jax.device_put(buf[a:b], d) for d, a, b in placed
                ],
                devices=involved,
            )
        flight_recorder.stage_add(
            "upload", (time.perf_counter() - t0) * 1000.0
        )
        flight_recorder.add_bytes(up=int(buf.nbytes))
        return out

    def _evict_locked(
        self, pinned_regions: set[int], limit: int | None = None, est: int = 0,
    ):
        """Evict until no device holds more than `limit` (default: the
        budget) of this cache's bytes, `est` bytes about to land beside
        `pinned_regions` counted in; only from the regions that lie on a
        device over it (`_over_locked`)."""
        if limit is None:
            limit = self.budget

        def still_over():
            return self._over_locked(limit, est, pinned_regions)

        over = still_over()
        # Re-derivable planes strip FIRST, and INCREMENTALLY — per limb
        # column, then per window tile — stopping as soon as the budget
        # holds.  Round 4 cleared every limb plane and window tile of an
        # entry at once, so one over-budget allocation evicted every warm
        # query family's working set and the next query of each family
        # paid a full rebuild (the per-family churn behind the 72 h bench
        # blowup).  Limb planes cost a few ms of device quantize to
        # rebuild; window tiles cost a host gather + upload (seconds);
        # whole super-tiles cost a Parquet decode — evict in that order.
        for entry in list(self._super.values()):
            for key in list(entry.limb_cols):
                if entry.region_id not in over:
                    break
                freed = sum(
                    int(l.nbytes) + int(s.nbytes)
                    for l, s in entry.limb_cols.pop(key)
                )
                entry.nbytes -= freed
                self._used -= freed
                over = still_over()
        for entry in list(self._super.values()):
            for key in list(entry.window_tiles):
                if entry.region_id not in over:
                    break
                freed = entry.window_tiles.pop(key)["nbytes"]
                entry.nbytes -= freed
                self._used -= freed
                over = still_over()
        while over - pinned_regions:
            rid = next(r for r in self._super if r in over and r not in pinned_regions)
            dropped = self._super.pop(rid)
            self._used -= dropped.nbytes
            self._host_used -= dropped.host_nbytes
            metrics.TILE_CACHE_EVICTIONS.inc()
            over = still_over()
        while self._host_used > self.host_budget and len(self._host) > 0:
            key, entry = next(iter(self._host.items()))
            self._host_used -= entry.nbytes
            del self._host[key]

    # ---- host-side per-file encode cache -----------------------------------
    def _file_host_tiles(
        self,
        region: Region,
        dictionary: TableDictionary,
        meta: FileMeta,
        columns: list[str],
        tag_cols: list[str],
        ts_col: str | None,
    ) -> _FileHostTiles | None:
        key = (region.region_id, meta.file_id)
        with self._lock:
            entry = self._host.get(key)
            if entry is not None:
                self._host.move_to_end(key)
        if entry is None:
            entry = _FileHostTiles(num_rows=meta.num_rows)
        missing = [c for c in columns if c not in entry.cols and c not in entry.absent]
        fused_on = self._tile_opt("fused_build", True)
        if missing:
            # the fused-build contract counter: exactly ONE real Parquet
            # decode per source file per family build (test-asserted)
            metrics.TILE_FILE_DECODES.inc()
            if fused_on and len(missing) < len(columns):
                # columns an earlier family member already host-encoded
                metrics.TILE_FUSED_ENCODES_SAVED.inc(
                    len(columns) - len(missing)
                )
            table = region.sst_reader.read(meta, None, columns=missing)
            if table.num_rows != meta.num_rows:
                # unexpected — mark unusable rather than mis-aggregate
                with self._lock:
                    self._bad_files.add(key)
                return None
            present = [c for c in missing if c in table.column_names]
            for name in missing:
                if name in table.column_names:
                    continue
                # file predates the column (ALTER ADD COLUMN): value
                # columns NULL-fill at consolidation; a missing tag/ts
                # column cannot be represented — exclude the file
                if name in tag_cols or name == ts_col:
                    with self._lock:
                        self._bad_files.add(key)
                    return None
                entry.absent.add(name)
            built = _encode_host_tiles(dictionary, table, present, tag_cols, ts_col)
            if built is None:
                with self._lock:
                    self._bad_files.add(key)
                return None
            cols, nulls, epochs, nbytes = built
            entry.cols.update(cols)
            entry.nulls.update(nulls)
            entry.epochs.update(epochs)
            entry.nbytes += nbytes
            metrics.TILE_CACHE_MISSES.inc()
            with self._lock:
                old = self._host.pop(key, None)
                if old is not None and old is not entry:
                    self._host_used -= old.nbytes
                self._host[key] = entry
                self._host_used += nbytes
        elif fused_on and entry.cols:
            # the whole request served from the per-file encode cache: a
            # decode AND every column encode saved by the shared pass
            metrics.TILE_FUSED_DECODES_SAVED.inc()
            metrics.TILE_FUSED_ENCODES_SAVED.inc(len(columns))
        return entry

    def _repair_host_locked(self, entry: _FileHostTiles, dictionary: TableDictionary):
        """Bring a host tile's tag codes to the current dictionary epoch
        with one np gather per stale column."""
        for tag, epoch in list(entry.epochs.items()):
            perm = dictionary.perm_since(tag, epoch)
            if perm is not None:
                codes = entry.cols[tag]
                ok = (codes >= 0) & (codes < len(perm))
                entry.cols[tag] = np.where(
                    ok, perm[np.clip(codes, 0, len(perm) - 1)], -1
                ).astype(np.int32)
            entry.epochs[tag] = dictionary.epoch

    # ---- super-tile build / fetch -----------------------------------------
    def super_tiles(
        self,
        region: Region,
        dictionary: TableDictionary,
        metas: list[FileMeta],
        tag_cols: list[str],
        ts_col: str | None,
        value_cols: list[str],
        pinned_regions: set[int],
        pk_cols: list[str],
        device_upload: bool = True,
    ) -> tuple[_SuperTiles | None, list[FileMeta]]:
        """Traced facade over `_super_tiles_impl`: one `tile.build` span
        per region with the resolved mode — warm hit, delta extend,
        persisted load or cold build — so ROADMAP's cold-path hunts read
        the structure off a trace instead of print statements."""
        with tracing.span(
            "tile.build", region=region.region_id, files=len(metas)
        ) as s:
            t0 = time.perf_counter()
            up0 = flight_recorder.stage_total("upload")
            out = self._super_tiles_impl(
                region, dictionary, metas, tag_cols, ts_col, value_cols,
                pinned_regions, pk_cols, device_upload, s,
            )
            entry = out[0]
            if entry is not None:
                s.attributes.setdefault("mode", "cold")
                s.attributes["rows"] = entry.num_rows
                entry.last_hit = time.time()
            else:
                s.attributes.setdefault("mode", "none")
            if _in_fused_build() and s.attributes["mode"] == "cold":
                # a real cold build performed by the fused family builder
                s.attributes["mode"] = "fused"
            if entry is not None:
                # flight recorder: this region's build leg.  Upload ms
                # accumulated INSIDE the call (the _up_chunks chokepoint)
                # is metered as its own stage, so build = host-side
                # consolidation only.
                build_ms = (time.perf_counter() - t0) * 1000.0
                build_ms -= flight_recorder.stage_total("upload") - up0
                flight_recorder.stage_add("build", max(build_ms, 0.0))
                flight_recorder.region_build(
                    region.region_id, s.attributes["mode"],
                    max(build_ms, 0.0), entry.num_rows,
                )
            return out

    def _super_tiles_impl(
        self,
        region: Region,
        dictionary: TableDictionary,
        metas: list[FileMeta],
        tag_cols: list[str],
        ts_col: str | None,
        value_cols: list[str],
        pinned_regions: set[int],
        pk_cols: list[str],
        device_upload: bool = True,
        build_span=None,
    ) -> tuple[_SuperTiles | None, list[FileMeta]]:
        """Cached (or freshly consolidated) device tiles for one region's
        SST set.  Returns (entry, excluded): `excluded` lists files that
        cannot join the super-tile (missing tag/ts column, row-count
        mismatch) — the caller must fall back when any of them intersects
        the query window.  entry is None when no file is includable.

        `pk_cols` + `ts_col` define the global sort order: they are always
        host-encoded (cheap, host-RAM only) so the (pk, ts) `order` can be
        computed at entry creation and reused for columns added later."""
        need = list(dict.fromkeys(tag_cols + ([ts_col] if ts_col else []) + value_cols))
        sort_cols = list(dict.fromkeys(pk_cols + ([ts_col] if ts_col else [])))
        host_need = list(dict.fromkeys(sort_cols + need))
        # eager columns: the FIRST consolidation of a region reads Parquet
        # anyway — decode every numeric field column in that same pass so a
        # later query needing a different metric pays compile only, not a
        # 34M-row re-read per column (measured: +180 s of cold spread over
        # the TSBS suite)
        try:
            schema = region.schema
            eager = [
                c.name
                for c in schema.field_columns()
                if c.data_type.is_numeric()
            ]
            host_need = list(dict.fromkeys(host_need + eager))
            # device upload stays LAZY (only queried columns ride HBM);
            # eagerness applies to the host-side Parquet decode only
        except Exception:  # noqa: BLE001 — eagerness is an optimization
            pass
        rid = region.region_id

        for _attempt in range(len(metas) + 1):
            with self._lock:
                included = [
                    m for m in metas if (rid, m.file_id) not in self._bad_files
                ]
            excluded = [m for m in metas if m not in included]
            if not included:
                return None, excluded
            ids = tuple(m.file_id for m in included)
            with self._lock:
                entry = self._super.get(rid)
                if entry is not None:
                    self._super.move_to_end(rid)
            if entry is not None and entry.file_ids != ids:
                # a flush APPENDED files: extend the cached entry in place
                # (delta encode + merge of sorted runs + on-device plane
                # patch) instead of rebuilding from scratch — post-flush
                # cold cost becomes O(delta rows).  Compactions/removals
                # change the prefix and take the full rebuild.
                extended = None
                if not self._tile_opt("incremental", True):
                    why = "tile.incremental off: full rebuild"
                elif not passes.enabled("incremental_tile", self.config):
                    why = "pass disabled: full rebuild"
                elif not (
                    len(ids) > len(entry.file_ids)
                    and ids[: len(entry.file_ids)] == entry.file_ids
                ):
                    why = (
                        "file set not an append of the cached one "
                        "(compaction/removal): full rebuild"
                    )
                elif entry.order is None:
                    why = "cached entry has no sort order yet: full rebuild"
                else:
                    why = "delta could not merge: full rebuild"
                    extended = self._delta_extend(
                        region, dictionary, entry, included, ids, host_need,
                        tag_cols + pk_cols, ts_col, sort_cols,
                        pinned_regions,
                    )
                if extended is not None and build_span is not None:
                    build_span.attributes["mode"] = "delta"
                if extended is None:
                    passes.note("incremental_tile", False, why, region=rid)
                    with self._lock:
                        if self._super.get(rid) is entry:
                            dropped = self._super.pop(rid)
                            self._used -= dropped.nbytes
                            self._host_used -= dropped.host_nbytes
                    entry = None
                else:
                    entry = extended
            if entry is None:
                total = sum(m.num_rows for m in included)
                entry = _SuperTiles(
                    region_id=rid, file_ids=ids,
                    num_rows=total, pad=padded_size(max(total, 1)),
                )
                with _timed("super.load_persisted"):
                    self._try_load_persisted(entry, dictionary)
            missing = [c for c in need if c not in entry.cols]
            if not missing and entry.valid is not None:
                metrics.TILE_CACHE_HITS.inc()
                if build_span is not None and "mode" not in build_span.attributes:
                    build_span.attributes["mode"] = "warm"
                return entry, excluded

            # a matching persisted consolidation already holds the order +
            # every needed column: skip Parquet decode/encode/sort — THE
            # cold-start cost — and upload straight from the mmap
            use_persisted = entry.order is not None and all(
                c in entry.persisted_cols for c in missing
            )
            host_tiles: list[_FileHostTiles] | None
            if use_persisted:
                host_tiles = None
                if build_span is not None and "mode" not in build_span.attributes:
                    build_span.attributes["mode"] = "persisted"
            else:
                # host encodes (cheap when cached); these may GROW the
                # dictionary, so callers build the plan only after every
                # region is prepared
                host_tiles = []
                for meta in included:
                    check_deadline()  # per-file Parquet decode + encode
                    if _TIMING:
                        print(f"TILE_TIMING super.host_tile.{meta.file_id[:8]} start", flush=True)
                    ht = self._file_host_tiles(
                        region, dictionary, meta, host_need, tag_cols + pk_cols, ts_col
                    )
                    if ht is None:
                        break  # newly-discovered bad file: retry without it
                    host_tiles.append(ht)
                if len(host_tiles) != len(included):
                    continue
                with self._lock:
                    for ht in host_tiles:
                        self._repair_host_locked(ht, dictionary)

            if entry.order is None and _TIMING:
                print("TILE_TIMING super.order start", flush=True)
            if entry.order is None:
                # global (pk, ts) sort of the concatenation — lexsort keys
                # are listed minor-to-major.  Code repair is a permutation
                # of code VALUES that preserves relative order (the
                # dictionary is value-sorted), so `order` stays valid
                # across dictionary growth.
                cats = {
                    name: np.concatenate([ht.cols[name] for ht in host_tiles])
                    for name in sort_cols
                }
                if cats:
                    entry.order = np.lexsort(
                        [cats[name] for name in reversed(sort_cols)]
                    ).astype(np.int32)
                else:
                    entry.order = np.arange(entry.num_rows, dtype=np.int32)
                for name in sort_cols:
                    entry.sorted_host[name] = cats[name][entry.order]
                    if name != ts_col:
                        entry.host_epochs[name] = dictionary.epoch
                entry.ts_run_starts = None  # as in _try_load_persisted
                entry.file_row_offsets = np.concatenate(
                    [[0], np.cumsum([ht.num_rows for ht in host_tiles])]
                ).astype(np.int64)
                hb = sum(a.nbytes for a in entry.sorted_host.values())
                hb += entry.order.nbytes + entry.file_row_offsets.nbytes
                entry.host_nbytes += hb
                with self._lock:
                    self._host_used += hb

            if not device_upload:
                # host-only build (cold-serve routing): consolidation,
                # order, sorted planes and persist — NO device uploads;
                # a later device-path query re-enters with uploads on
                with self._lock:
                    old = self._super.pop(rid, None)
                    if old is not None and old is not entry:
                        self._used -= old.nbytes
                        self._host_used -= old.host_nbytes
                    self._super[rid] = entry
                    # the host-RAM budget must hold on this path too: the
                    # device branch's commit-time sweep never runs here
                    self._evict_locked(pinned_regions | {rid})
                if host_tiles is not None:
                    self._persist_async(
                        entry, host_tiles, set(tag_cols) | set(pk_cols),
                        dictionary,
                    )
                return entry, excluded

            # pre-upload eviction: make room for the columns about to
            # upload BEFORE the device allocations happen — charging the
            # budget afterwards let the transient overshoot HBM at
            # TSBS 3-day scale (resident limb planes + a 10-column f64
            # upload exceeded the chip; the budget check came too late)
            est = 0
            for name in missing:
                if host_tiles is None:
                    item = entry.persisted_cols[name].dtype.itemsize
                    any_nulls_est = name in entry.persisted_nulls
                else:
                    any_nulls_est = any(
                        name in ht.nulls or name in ht.absent for ht in host_tiles
                    )
                    src0 = next(
                        (ht.cols[name] for ht in host_tiles if name in ht.cols), None
                    )
                    item = src0.dtype.itemsize if src0 is not None else 8
                est += entry.pad * (item + (1 if any_nulls_est else 0))
            with self._lock:
                self._reserve_locked(est, pinned_regions | {rid})

            acc = [0]
            bounds = _chunk_bounds(entry.pad, self.chunk_rows)
            try:
                if entry.valid is None:
                    v = np.zeros(entry.pad, bool)
                    v[: entry.num_rows] = True
                    entry.valid = self._up_chunks(v, bounds, entry.region_id)
                    acc[0] += v.nbytes
                self._upload_missing(
                    entry, missing, host_tiles, bounds, acc,
                    tag_cols, pk_cols, dictionary,
                )
            except BaseException:
                # a deadline abort (or OOM) mid-loop must not leave the
                # already-uploaded planes invisible to the budget: commit
                # what landed before re-raising (a cache-hit entry is LIVE
                # in self._super — uncharged planes would accumulate until
                # the reserve-first eviction could no longer prevent OOM)
                with self._lock:
                    entry.nbytes += acc[0]
                    if self._super.get(rid) is entry:
                        self._used += acc[0]
                raise
            added = acc[0]
            entry.nbytes += added
            with self._lock:
                old = self._super.pop(rid, None)
                if old is not None and old is not entry:
                    self._used -= old.nbytes
                    self._host_used -= old.host_nbytes
                self._super[rid] = entry
                self._used += added
                self._evict_locked(pinned_regions | {rid})
            if host_tiles is not None:
                # freshly consolidated (or extended): persist in the
                # background so the NEXT process mmaps instead of re-doing
                # decode + encode + sort
                self._persist_async(
                    entry, host_tiles, set(tag_cols) | set(pk_cols), dictionary
                )
            return entry, excluded
        return None, list(metas)

    def _consolidate_column(self, entry: _SuperTiles, name, host_tiles):
        """Host-side assembly of one column's consolidated (sorted,
        padded) value buffer + optional null plane — the producer stage of
        the pipelined cold build (CPU-bound: concat + order gather; mmap
        page-in on the persisted path)."""
        if host_tiles is None:
            return entry.persisted_cols[name], entry.persisted_nulls.get(name)
        src = next(
            (ht.cols[name] for ht in host_tiles if name in ht.cols), None
        )
        dtype = src.dtype if src is not None else np.float64
        cat = np.concatenate(
            [
                ht.cols[name]
                if name in ht.cols
                else np.zeros(ht.num_rows, dtype)
                for ht in host_tiles
            ]
        )
        buf = np.zeros(entry.pad, dtype=cat.dtype)
        buf[: entry.num_rows] = cat[entry.order]
        any_nulls = any(
            name in ht.nulls or name in ht.absent for ht in host_tiles
        )
        nbuf = None
        if any_nulls:
            ncat = np.concatenate(
                [
                    ht.nulls[name]
                    if name in ht.nulls
                    else np.full(ht.num_rows, name not in ht.absent)
                    for ht in host_tiles
                ]
            )
            nbuf = np.zeros(entry.pad, bool)
            nbuf[: entry.num_rows] = ncat[entry.order]
        return buf, nbuf

    def _land_column(
        self, entry: _SuperTiles, name, buf, nbuf, bounds, acc: list,
        tag_cols, pk_cols, dictionary, host_tiles,
    ):
        """Consumer stage: upload one consolidated column (+ null plane)
        and stamp its dictionary epoch."""
        if _TIMING:
            print(f"TILE_TIMING super.upload.{name} start", flush=True)
        entry.cols[name] = self._up_chunks(buf, bounds, entry.region_id)
        acc[0] += buf.nbytes
        if nbuf is not None:
            entry.nulls[name] = self._up_chunks(nbuf, bounds, entry.region_id)
            acc[0] += nbuf.nbytes
        if name in tag_cols or name in pk_cols:
            if host_tiles is None:
                # persisted codes keep their STORED epoch (repair
                # gathers them forward) — persisted_epochs, not
                # entry.epochs, is authoritative: release_unneeded
                # pops the latter, and restamping a re-upload with
                # the current epoch would skip the repair gather
                entry.epochs.setdefault(
                    name,
                    entry.persisted_epochs.get(name, dictionary.epoch),
                )
            else:
                entry.epochs[name] = dictionary.epoch

    def _upload_missing(
        self, entry: _SuperTiles, missing, host_tiles, bounds, acc: list,
        tag_cols, pk_cols, dictionary,
    ):
        """Consolidate + upload the missing columns of a super-tile entry.
        Device bytes accumulate into acc[0] AS each plane lands, so the
        caller can commit partial progress when a deadline abort unwinds
        mid-loop (see super_tiles).

        With tile.pipelined_build (and the pipelined_build pass) on, the
        serial per-column encode->upload loop becomes a two-stage
        pipeline: a small worker pool consolidates column N+1 on the host
        while column N's chunks cross the host->device link — the
        overlap-compute-with-transfer discipline applied to the cold
        path.  Workers inherit the caller's query deadline (propagate)."""
        pipeline = (
            self._tile_opt("pipelined_build", True)
            and len(missing) > 1
            and passes.enabled("pipelined_build", self.config)
        )
        if not pipeline:
            for name in missing:
                check_deadline()  # per-column consolidate + upload
                buf, nbuf = self._consolidate_column(entry, name, host_tiles)
                self._land_column(
                    entry, name, buf, nbuf, bounds, acc,
                    tag_cols, pk_cols, dictionary, host_tiles,
                )
            return
        from concurrent.futures import ThreadPoolExecutor

        from ..utils.deadline import propagate

        workers = max(1, int(self._tile_opt("build_workers", 2)))
        metrics.TILE_PIPELINED_BUILDS.inc()
        passes.note(
            "pipelined_build", True,
            f"{len(missing)} column encodes overlap uploads on "
            f"{workers} worker(s)",
            columns=len(missing),
        )
        with ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix="tile-build"
        ) as pool:
            pending = list(missing)
            inflight: list[tuple] = []

            def pump():
                # bounded look-ahead: at most workers+1 consolidated
                # buffers alive at once (each is pad * itemsize of host
                # RAM — unbounded submission would hold every column)
                while pending and len(inflight) <= workers:
                    nm = pending.pop(0)
                    inflight.append((
                        nm,
                        pool.submit(
                            propagate(self._consolidate_column),
                            entry, nm, host_tiles,
                        ),
                    ))

            pump()
            while inflight:
                name, fut = inflight.pop(0)
                buf, nbuf = fut.result()
                pump()  # next column consolidates while this one uploads
                check_deadline()
                self._land_column(
                    entry, name, buf, nbuf, bounds, acc,
                    tag_cols, pk_cols, dictionary, host_tiles,
                )

    def _delta_extend(
        self,
        region: Region,
        dictionary: TableDictionary,
        entry: _SuperTiles,
        included: list[FileMeta],
        ids: tuple[str, ...],
        host_need: list[str],
        tag_like: list[str],
        ts_col: str | None,
        sort_cols: list[str],
        pinned_regions: set[int],
    ) -> _SuperTiles | None:
        """Extend a cached super-tile IN PLACE after a flush appended
        files: host-encode ONLY the delta files, merge their (pk, ts)-
        sorted run into the cached sorted order (a binary-search merge of
        two sorted runs — no O(total log total) re-sort), and PATCH every
        resident device plane with one on-device scatter (`_delta_patch`)
        so only the O(delta) positions + values cross the host->device
        link.  Re-derivable planes (time-major copies, perm, limb planes,
        dedup masks) drop and rebuild lazily from the patched planes;
        window tiles whose window cannot contain a delta row survive
        untouched.  Returns the extended entry (committed atomically under
        the cache lock), or None when the delta cannot merge — the caller
        then falls back to the drop-and-rebuild path, which is also the
        exact `tile.incremental = false` behavior.

        Parity invariant: both runs were STABLY sorted and ties resolve
        old-run-first (= flush order), so the merged (order, sorted_host)
        is bit-identical to a from-scratch stable lexsort of the full
        concatenation — asserted by tests/test_tile_incremental.py.

        Concurrency: every super_tiles caller holds the table's
        dictionary lock (queries' epoch-sensitive section, prewarm's
        per-region section), which serializes delta merges per table.
        The commit below still re-checks the entry's identity AND that
        its (file_ids, num_rows) are exactly the state this merge was
        computed against, so even a caller bypassing the lock could
        never double-apply a delta — it falls back to the rebuild."""
        rid = entry.region_id
        old_k = len(entry.file_ids)
        old_ids = entry.file_ids
        delta_metas = included[old_k:]
        delta_rows = sum(m.num_rows for m in delta_metas)
        if delta_rows == 0:
            return None
        if any(c not in entry.sorted_host for c in sort_cols):
            return None  # entry predates a sort column: rebuild owns it
        t_start = time.perf_counter()

        # 1. host-encode the delta files only (per-file cache; old files
        # are never touched).  Resident device columns must be patchable,
        # so the delta decode also covers them.
        resident = sorted(set(entry.cols) | set(entry.nulls))
        need = list(dict.fromkeys(host_need + resident))
        delta_tiles: list[_FileHostTiles] = []
        for meta in delta_metas:
            check_deadline()  # per-delta-file Parquet decode + encode
            ht = self._file_host_tiles(
                region, dictionary, meta, need, tag_like, ts_col
            )
            if ht is None:
                return None  # bad delta file: the rebuild path re-gates it
            delta_tiles.append(ht)

        # 2. one epoch for every code plane BEFORE keys are compared: the
        # delta encode may have grown the dictionary
        with self._lock:
            for ht in delta_tiles:
                self._repair_host_locked(ht, dictionary)
        self.repair_super([entry], dictionary, sorted(entry.epochs))

        # 3. sort the delta, merge the two sorted runs
        old_n = entry.num_rows
        total = old_n + delta_rows
        new_pad = padded_size(max(total, 1))
        delta_cats = {
            c: np.concatenate([ht.cols[c] for ht in delta_tiles])
            for c in sort_cols
        }
        if sort_cols:
            delta_order = np.lexsort(
                [delta_cats[c] for c in reversed(sort_cols)]
            ).astype(np.int64)
        else:
            delta_order = np.arange(delta_rows, dtype=np.int64)
        delta_sorted = {c: delta_cats[c][delta_order] for c in sort_cols}
        old_sorted = {c: np.asarray(entry.sorted_host[c]) for c in sort_cols}
        pos = _lex_merge_positions(
            [old_sorted[c] for c in sort_cols],
            [delta_sorted[c] for c in sort_cols],
        )
        shift = np.searchsorted(pos, np.arange(old_n), side="right")
        old_global = np.arange(old_n, dtype=np.int64) + shift
        delta_global = pos + np.arange(delta_rows, dtype=np.int64)
        new_order = np.empty(total, np.int32)
        new_order[old_global] = np.asarray(entry.order, np.int32)
        new_order[delta_global] = (old_n + delta_order).astype(np.int32)
        new_sorted: dict[str, np.ndarray] = {}
        for c in sort_cols:
            arr = np.empty(total, old_sorted[c].dtype)
            arr[old_global] = old_sorted[c]
            arr[delta_global] = delta_sorted[c].astype(old_sorted[c].dtype)
            new_sorted[c] = arr
        new_offsets = np.concatenate([
            np.asarray(entry.file_row_offsets),
            old_n + np.cumsum([m.num_rows for m in delta_metas]),
        ]).astype(np.int64)

        # 4. patch resident device planes (single-device only: chunked
        # multi-device planes have no cheap global scatter — those drop
        # and re-upload lazily, still skipping the re-sort).
        bounds = _chunk_bounds(new_pad, self.chunk_rows)
        patch_device = entry.valid is not None and len(self.devices) == 1
        patched_cols: dict[str, list] = {}
        patched_nulls: dict[str, list] = {}
        new_valid = None
        if patch_device:
            est = new_pad  # valid plane
            for name, chunks in entry.cols.items():
                # output plane + the jnp.concatenate transient of the old
                # chunks inside patch() (skipped for single-chunk entries)
                est += new_pad * chunks[0].dtype.itemsize * (
                    2 if len(chunks) > 1 else 1
                )
            est += (len(entry.nulls) + len(entry.cols)) * new_pad  # nulls
            with self._lock:
                self._reserve_locked(est, pinned_regions | {rid})
            pos_dev = jnp.asarray(pos.astype(np.int32))

            def delta_col(name, dtype):
                cat = np.concatenate([
                    ht.cols[name]
                    if name in ht.cols
                    else np.zeros(ht.num_rows, dtype)
                    for ht in delta_tiles
                ])
                return np.ascontiguousarray(cat[delta_order])

            def delta_null(name):
                if not any(
                    name in ht.nulls or name in ht.absent
                    for ht in delta_tiles
                ):
                    return None
                ncat = np.concatenate([
                    ht.nulls[name]
                    if name in ht.nulls
                    else np.full(ht.num_rows, name not in ht.absent)
                    for ht in delta_tiles
                ])
                return np.ascontiguousarray(ncat[delta_order])

            def patch(chunks, delta_np):
                full = (
                    jnp.concatenate(chunks) if len(chunks) > 1 else chunks[0]
                )
                out = _delta_patch(
                    full, jnp.asarray(delta_np), pos_dev, old_n, new_pad
                )
                return [out[a:b] for a, b in bounds]

            try:
                for name, chunks in entry.cols.items():
                    check_deadline()  # per-column delta upload + scatter
                    dv = delta_col(name, np.dtype(chunks[0].dtype))
                    patched_cols[name] = patch(chunks, dv)
                    dn = delta_null(name)
                    if name in entry.nulls:
                        if dn is None:
                            dn = np.ones(delta_rows, bool)
                        patched_nulls[name] = patch(entry.nulls[name], dn)
                    elif dn is not None and not dn.all():
                        # the delta introduces the column's FIRST nulls:
                        # old rows are all present
                        patched_nulls[name] = patch(
                            [jnp.ones(old_n, bool)], dn
                        )
                new_valid = [
                    jnp.arange(a, b, dtype=jnp.int64) < total
                    for a, b in bounds
                ]
            except QueryTimeoutError:
                raise  # entry untouched: the old file set stays queryable
            except Exception:  # noqa: BLE001 — e.g. device OOM mid-patch
                # the contract is "None = caller falls back to the full
                # rebuild", whose own OOM handling (reserve-first +
                # emergency release) owns the recovery; the entry is
                # untouched because the commit below never ran
                logging.getLogger("greptimedb_tpu.tile").warning(
                    "delta plane patch failed; falling back to rebuild",
                    exc_info=True,
                )
                return None

        # 5. atomic commit: nothing above mutated the entry, so a deadline
        # abort or merge failure leaves the old file set fully queryable
        delta_ts = delta_sorted.get(ts_col) if ts_col else None
        with self._lock:
            if (
                self._super.get(rid) is not entry
                or entry.file_ids != old_ids
                or entry.num_rows != old_n
            ):
                # evicted or mutated mid-merge: the rebuild owns it (and a
                # delta can never double-apply)
                return None
            old_dev = entry.nbytes
            old_host = entry.host_nbytes
            entry.file_ids = ids
            entry.num_rows = total
            entry.pad = new_pad
            entry.order = new_order
            entry.sorted_host = new_sorted
            entry.host_epochs = {
                c: dictionary.epoch for c in sort_cols if c != ts_col
            }
            entry.file_row_offsets = new_offsets
            entry.ts_run_starts = None  # the plane grew: its runs moved
            entry.series_table = None  # and its series' rows with them
            entry.keep_host = None
            entry.keep_prefix = None
            entry.valid_dedup = None
            if patch_device:
                entry.cols = patched_cols
                entry.nulls = patched_nulls
                entry.valid = new_valid
            else:
                entry.cols = {}
                entry.nulls = {}
                entry.valid = None
                entry.epochs = {}
            # re-derivable planes rebuild lazily from the patched planes
            entry.tm_cols = {}
            entry.tm_nulls = {}
            entry.tm_valid = None
            entry.tm_valid_dedup = None
            entry.perm = None
            entry.limb_cols = {}
            # window tiles whose window cannot contain a delta row stay
            # bit-identical; intersecting ones rebuild on next touch
            if delta_ts is not None and len(delta_ts):
                dmin, dmax = int(delta_ts[0]), int(delta_ts[-1])
            else:
                dmin, dmax = -(1 << 62), 1 << 62
            for key in [
                k
                for k in entry.window_tiles
                if dmax >= k[0] and dmin < k[1]
            ]:
                del entry.window_tiles[key]
            # the persisted store describes the OLD file set
            entry.persisted_cols = {}
            entry.persisted_nulls = {}
            entry.persisted_epochs = {}
            entry.cold_served = False
            entry.nbytes = _entry_device_bytes(entry)
            entry.host_nbytes = (
                entry.order.nbytes
                + entry.file_row_offsets.nbytes
                + sum(a.nbytes for a in entry.sorted_host.values())
            )
            self._used += entry.nbytes - old_dev
            self._host_used += entry.host_nbytes - old_host
            self._evict_locked(pinned_regions | {rid})
        entry.delta_extends += 1
        metrics.TILE_DELTA_MERGES.inc()
        metrics.TILE_DELTA_ROWS.inc(delta_rows)
        passes.note(
            "incremental_tile", True,
            f"{delta_rows} delta rows merged into the cached super-tile "
            "(sorted-run merge + on-device plane patch)",
            region=rid, delta_rows=delta_rows, total_rows=total,
            ms=round((time.perf_counter() - t_start) * 1000, 1),
        )
        if _TIMING:
            print(
                f"TILE_TIMING super.delta_merge "
                f"{(time.perf_counter() - t_start) * 1000:.0f}ms "
                f"({delta_rows} rows)",
                flush=True,
            )
        return entry

    def repair_super(
        self,
        entries: list[_SuperTiles],
        dictionary: TableDictionary,
        tag_cols: list[str],
    ):
        """Dictionary-growth repair: one device gather per stale tag
        column.  MUST run after every source of the query has updated the
        dictionary.  Serialized under the cache lock so concurrent queries
        can't double-apply a permutation."""
        with self._lock:
            for entry in entries:
                for tag in tag_cols:
                    # an entry read back from its persisted file set has
                    # the stored epochs and no device plane yet: the lazy
                    # upload repairs that one when it lands
                    if tag not in entry.epochs or tag not in entry.cols:
                        continue
                    perm = dictionary.perm_since(tag, entry.epochs[tag])
                    if perm is not None:
                        pdev = jnp.asarray(perm)
                        entry.cols[tag] = [
                            jnp.take(pdev, c, mode="fill", fill_value=-1).astype(jnp.int32)
                            for c in entry.cols[tag]
                        ]
                    entry.epochs[tag] = dictionary.epoch
                    entry.tm_cols.pop(tag, None)
                for tag, epoch in list(entry.host_epochs.items()):
                    perm = dictionary.perm_since(tag, epoch)
                    if perm is not None:
                        codes = entry.sorted_host[tag]
                        ok = (codes >= 0) & (codes < len(perm))
                        entry.sorted_host[tag] = np.where(
                            ok, perm[np.clip(codes, 0, len(perm) - 1)], -1
                        ).astype(codes.dtype)
                    entry.host_epochs[tag] = dictionary.epoch

    def ensure_time_major(
        self, entry: _SuperTiles, ts_name: str, cols_needed: set[str],
        dedup: bool = False,
    ):
        """Materialize ts-ascending device copies of the needed columns
        (one gather each, once per (region, file-set, column)) so
        time-major dispatches are gather-free.  Returns (cols, valid,
        nulls) views limited to `cols_needed`; with `dedup` the valid
        planes carry the last-write-wins keep mask (ensure_dedup_keep
        must have run)."""
        perm = self.ensure_perm(entry, ts_name)
        home = next(iter(perm.devices()))
        bounds = _chunk_bounds(entry.pad, self.chunk_rows)
        added = 0
        with self._lock:
            # reserve for the copies about to materialize (each gather
            # also holds a concatenated source transiently)
            est = 0
            for c in cols_needed:
                if c in entry.cols and c not in entry.tm_cols:
                    est += 2 * sum(int(x.nbytes) for x in entry.cols[c])
                if c in entry.nulls and c not in entry.tm_nulls:
                    est += 2 * entry.pad
            if entry.tm_valid is None:
                est += 2 * entry.pad
            self._reserve_locked(est, {entry.region_id})

            def permuted_chunks(chunks):
                # time-major copies live on ONE device, the region's first
                # (the perm's): the ts-ascending gather is a permutation of
                # the whole region, which has no chunk-local form.  Under
                # the mesh path that is the region's own mesh device, so a
                # one-chunk region's copies never leave its chip
                if len(self.devices) > 1:
                    chunks = [jax.device_put(x, home) for x in chunks]
                return _permuted_chunks(tuple(chunks), perm, tuple(bounds))

            if entry.tm_valid is None:
                entry.tm_valid = permuted_chunks(entry.valid)
                added += entry.pad
            if dedup and entry.tm_valid_dedup is None:
                entry.tm_valid_dedup = permuted_chunks(entry.valid_dedup)
                added += entry.pad
            for c in cols_needed:
                if c in entry.cols and c not in entry.tm_cols:
                    entry.tm_cols[c] = permuted_chunks(entry.cols[c])
                    added += sum(int(x.nbytes) for x in entry.cols[c])
                if c in entry.nulls and c not in entry.tm_nulls:
                    entry.tm_nulls[c] = permuted_chunks(entry.nulls[c])
                    added += entry.pad
            if added:
                entry.nbytes += added
                if self._super.get(entry.region_id) is entry:
                    self._used += added
        return (
            {c: entry.tm_cols[c] for c in cols_needed if c in entry.tm_cols},
            entry.tm_valid_dedup if dedup else entry.tm_valid,
            {c: entry.tm_nulls[c] for c in cols_needed if c in entry.tm_nulls},
        )

    def ensure_limbs(
        self,
        entry: _SuperTiles,
        cols_needed: list[str],
        time_major: bool,
        pinned_regions: set[int] = frozenset(),
    ) -> dict[str, list]:
        """Materialize cached MXU limb planes (quantize_limbs) for the
        given value columns, one device-side quantize per (column, chunk)
        once per (region, file-set); returns col -> per-chunk
        (limbs, scale) lists for the requested row order.  Columns with
        any chunk below the limb kernel's geometry (multiple of
        BLOCK_ROWS, >= the fast-path minimum) are skipped — those sources
        take the exact scatter trio instead (executor.py limb_fits).

        Quantization dispatches OUTSIDE the cache lock (it's device work);
        a concurrent build of the same column wastes one dispatch and the
        second store wins — benign."""
        src = entry.tm_cols if time_major else entry.cols
        prefix = "tm:" if time_major else ""
        out: dict[str, list] = {}
        to_build: list[tuple[str, list]] = []
        with self._lock:
            pending = []
            for c in cols_needed:
                key = prefix + c
                if key in entry.limb_cols:
                    out[c] = entry.limb_cols[key]
                    continue
                pending.append(c)
        for c in pending:
            chunks = src.get(c)
            if chunks is None and not time_major:
                # f64 plane never uploaded (limb-only column): quantize
                # straight from the host encodes — the f64 chunk uploads
                # transiently (each onto its chunk's device) and is freed
                # once its limbs exist
                np_chunks = self.host_column_chunks(entry, c)
                if np_chunks is not None and len(self.devices) > 1:
                    chunks = [
                        jax.device_put(x, self.chunk_device(i, entry.region_id))
                        for i, x in enumerate(np_chunks)
                    ]
                else:
                    chunks = np_chunks
            if chunks is None or any(
                x.shape[0] % BLOCK_ROWS or x.shape[0] < _LIMB_MIN_ROWS
                for x in chunks
            ):
                continue
            to_build.append((c, chunks))
        if not to_build:
            return out
        # pre-evict for the planes about to allocate (4 bf16 digits =
        # 8 B/row per column) — see the matching super_tiles pre-upload
        # eviction; reserving after allocation can overshoot HBM
        est = sum(
            x.shape[0] * 8 + (x.shape[0] // BLOCK_ROWS) * 8
            for _c, chunks in to_build
            for x in chunks
        )
        with self._lock:
            self._reserve_locked(est, pinned_regions | {entry.region_id})
        built_all = []
        for c, chunks in to_build:
            check_deadline()  # per-column quantize dispatches
            built_all.append((c, [_quantize_limbs_jit(x) for x in chunks]))
        added = 0
        with self._lock:
            for c, built in built_all:
                key = prefix + c
                if key in entry.limb_cols:
                    out[c] = entry.limb_cols[key]
                    continue
                entry.limb_cols[key] = built
                out[c] = built
                added += sum(int(l.nbytes) + int(s.nbytes) for l, s in built)
            if added:
                entry.nbytes += added
                if self._super.get(entry.region_id) is entry:
                    self._used += added
                # limb planes can push a warm cache past budget with no
                # cold build in sight — evict here too (limb planes of
                # other entries strip first; this query's references
                # keep its own arrays alive regardless)
                self._evict_locked(pinned_regions | {entry.region_id})
        return out

    # A window tile is a compact copy of one window's rows, made by the HOST
    # (a fancy-gather of each needed column, an upload, a limb quantize) so
    # that the device scans pad(n) rows instead of the region's padded
    # plane.  The window's rows are COUNTED first from the runs of the
    # sorted ts plane (_window_ranges: two searches a run, nothing
    # allocated); whether the tile is then built follows where the region's
    # full planes are:
    #  - NOT on the device (the fused planner's deferred upload, planes
    #    dropped by release_unneeded or evicted, retention beyond the
    #    chip's share): the alternative is to upload the planes, and a tile
    #    uploads n rows instead.  Built while the window covers at most
    #    _WINDOW_TILE_MAX_COVER of the rows (over it the tile is nearly as
    #    big as the plane).
    #  - resident: the tile spares device time only, the masked scan of
    #    the padded rows it leaves out, and a window is as a rule drawn
    #    once.  Built only where n * _WINDOW_BUILD_NS_PER_ROW is less than
    #    (entry.pad - pad(n)) * _WINDOW_SCAN_NS_PER_ROW: a cover under
    #    about 4 % of a plane, a "last hour" panel over days.
    # The two costs are v5e readings of `tsbs-mesh4-heavy` (PERF.md section
    # 5): the build from the ledger's PR 35 row, `window_build_ms` 261.16
    # ms/query x 3 shapes / 4 regions / 4.32 M rows of three columns = 45 ns
    # (PR 36's parent run read 52: 225.4 ms a build); the scan from PR 36's
    # traced run, 35.06 ms of a chip's busy time a `double-groupby-1` over
    # its region's 2^24 padded rows = 2.1 ns (`tsbs-heavy`: 62 ms over 2^25 =
    # 1.8).  At these a 12 h window of 24 h pays 194 ms of host a region to
    # spare 18 ms of device.
    _WINDOW_TILE_MAX_COVER = 0.5  # the bound where the planes are not resident
    _WINDOW_BUILD_NS_PER_ROW = 45.0  # host: gather + upload + quantize a window row
    _WINDOW_SCAN_NS_PER_ROW = 2.1  # device: masked scan of a padded plane row
    _WINDOW_TILE_MIN_ROWS = 1 << 22  # below this the full scan is cheap
    _WINDOW_TILE_GRID = 1 << 22  # a tile's rows pad to a multiple of this

    def _window_pad(self, n: int) -> int:
        """A tile's padded rows: a 2^22 grid, so that compile shapes stay
        few and chunks stay BLOCK_ROWS multiples."""
        grid = self._WINDOW_TILE_GRID
        return -(-n // grid) * grid

    def _planes_resident(
        self, entry: _SuperTiles, cols_needed: list[str],
        limb_cols: set[str], dedup: bool,
    ) -> bool:
        """Whether a masked scan of `entry` could start now: the valid (or
        dedup keep) plane and every needed column on the device, a limb
        column as its limb plane or as the f64 plane that is quantized
        from."""
        with self._lock:
            if (entry.valid_dedup if dedup else entry.valid) is None:
                return False
            return all(
                c in entry.cols or (c in limb_cols and c in entry.limb_cols)
                for c in cols_needed
            )

    def ensure_window_tile(
        self,
        entry: _SuperTiles,
        window: tuple[int, int],
        ts_name: str,
        need_cols: set[str],
        limb_cols: set[str],
        dedup: bool,
        dict_epoch: int,
    ) -> tuple[list | None, str | None]:
        """Fetch, or build where it pays, the compact device tile for one
        query window: `(sources, None)`, a list of source tuples (cols,
        valid, nulls, perm, limbs), or `(None, declined)` with the reason
        no tile serves the window.  A tile that exists for the exact
        window is used.  Otherwise the window's rows are one contiguous
        range per ascending run of the sorted ts plane, found by two
        searches per run; their count `n` (less the rows the dedup keep
        plane drops, so stale versions never even upload) decides BEFORE
        anything the size of the plane is allocated (see the comment over
        `_WINDOW_TILE_MAX_COVER`): `"empty"` for no row, `"cover"` for more
        than half the entry's rows, `"resident"` where the entry's planes
        are on the device and their masked scan costs less than the build;
        `"unprobed"` is a plane under `_WINDOW_TILE_MIN_ROWS` or without
        its sorted ts or keep plane, `"build"` a build that found a null
        plane or host encode missing.  The build: the ranges' rows, an mmap
        fancy-gather of each needed column, upload in chunk-device order,
        limb planes quantized from the gathered values.  Rows keep their
        (pk, ts) order, so the blocked kernel geometry holds on the
        compacted tile."""
        if entry.num_rows < self._WINDOW_TILE_MIN_ROWS or ts_name not in entry.sorted_host:
            return None, "unprobed"
        key = (int(window[0]), int(window[1]), bool(dedup))
        cols_needed = list(
            dict.fromkeys([c for c in need_cols if c != ts_name] + [ts_name])
        )
        with self._lock:
            wt = entry.window_tiles.get(key)
            if wt is not None and wt["epoch"] != dict_epoch:
                # tag codes moved: drop and rebuild at the current epoch
                freed = wt["nbytes"]
                entry.window_tiles.pop(key)
                entry.nbytes -= freed
                if self._super.get(entry.region_id) is entry:
                    self._used -= freed
                wt = None
            snap = None
            if wt is not None:
                missing = [c for c in cols_needed if c not in wt["cols"]]
                missing_limbs = [
                    c
                    for c in limb_cols
                    if c in need_cols
                    and c not in wt["limbs"]
                    and c not in missing
                ]
                if not missing and not missing_limbs:
                    return self._window_sources(wt, need_cols, limb_cols), None
                # EXTEND the cached tile: build only the missing planes
                # and merge them in (the round-4 code rebuilt everything
                # and then DISCARDED the rebuild in its race branch,
                # returning a tile missing columns — every multi-column
                # query after a narrower one over the same window then
                # fell back to the CPU scan, the round-4 driver-bench
                # timeout).  Snapshot the existing planes so the merge
                # commit below can survive a concurrent eviction.
                snap = {
                    "cols": dict(wt["cols"]),
                    "nulls": dict(wt["nulls"]),
                    "limbs": dict(wt["limbs"]),
                    "valid": wt["valid"],
                    "rows": wt["rows"],
                }
            else:
                missing = list(cols_needed)
                missing_limbs = []

        n = snap["rows"] if snap is not None else -1
        ranges = None
        if missing:
            if dedup and not self.ensure_dedup_keep(entry):
                return None, "unprobed"
            *ranges, n = self._window_ranges(entry, window, ts_name, dedup)
            metrics.TILE_WINDOW_COUNTED.inc()
            if snap is not None and n != snap["rows"]:
                # row set changed under the same epoch (shouldn't happen:
                # the file set pins sorted_host) — full rebuild, replace
                snap = None
                missing = list(cols_needed)
                missing_limbs = []
            if n == 0:
                return None, "empty"
            if n > entry.num_rows * self._WINDOW_TILE_MAX_COVER:
                return None, "cover"
            spared = entry.pad - self._window_pad(n)
            if (
                n * self._WINDOW_BUILD_NS_PER_ROW >= spared * self._WINDOW_SCAN_NS_PER_ROW
                and self._planes_resident(entry, cols_needed, limb_cols, dedup)
            ):
                metrics.TILE_WINDOW_RESIDENT_SCANS.inc()
                return None, "resident"
        with tracing.stage("tile.window_build", region=entry.region_id, rows=n):
            wsrc = self._build_window_tile(
                entry, key, need_cols, limb_cols, dict_epoch,
                snap, missing, missing_limbs, n, ranges,
            )
        return wsrc, ("build" if wsrc is None else None)

    def _build_window_tile(
        self, entry: _SuperTiles, key: tuple, need_cols: set[str],
        limb_cols: set[str], dict_epoch: int, snap: dict | None,
        missing: list[str], missing_limbs: list[str], n: int,
        ranges: list | None,
    ):
        """The build half of `ensure_window_tile` (stage `tile.window_build`):
        gather the `n` in-window rows `ranges` bounds (`key[2]`: less the
        dedup losers) for each `missing` column, upload, quantize, and
        commit (or extend) the tile."""
        idx = (
            _range_rows(*ranges, entry.keep_host if key[2] else None)
            if ranges is not None else None
        )
        # Window tiles dispatch at 2^22-row chunks
        # (not the 2^24 super-tile chunk): a 10-column limb program over a
        # 2^24 chunk allocates multi-GB transients (f64->bf16 casts, digit
        # planes, masks for every column scheduled concurrently) — the
        # round-4 driver dg-all OOM.  Equal-size chunks also mean ONE
        # compile shape per tile, and the size is stable across column
        # extensions (cached planes and new planes must chunk identically).
        pad = self._window_pad(n)
        bounds = _chunk_bounds(pad, min(self.chunk_rows, self._WINDOW_TILE_GRID))

        # nullable columns without a persisted null plane can't build
        # their gathered mask here — full super-tile path owns those.
        # (All bail-outs happen BEFORE the device reservation below, so an
        # aborted build never evicts other tiles for nothing.)
        for name in missing:
            if name in entry.nulls and name not in entry.persisted_nulls:
                return None

        def host_source(name):
            # all sources are in SORTED row order; idx indexes real rows
            if name in entry.sorted_host:
                return np.asarray(entry.sorted_host[name])
            if name in entry.persisted_cols:
                return np.asarray(entry.persisted_cols[name])
            chunks = self.host_column_chunks(entry, name)
            if chunks is None:
                return None
            return np.concatenate([np.asarray(x) for x in chunks])

        # gather every host buffer FIRST (host RAM only) so the device
        # reservation below never evicts tiles for a build that then
        # aborts on a concurrently-evicted host encode
        host_bufs: dict[str, tuple[np.ndarray, np.ndarray | None]] = {}
        for name in missing:
            check_deadline()  # 10-column gathers over 100M rows take seconds each
            with _timed(f"wtile.gather.{name}"):
                src = host_source(name)
                if src is None:
                    return None  # host encode evicted mid-flight: scan path
                buf = np.zeros(pad, dtype=src.dtype)
                buf[:n] = src[idx]
                nb = None
                pres = entry.persisted_nulls.get(name)
                if pres is not None:
                    nb = np.zeros(pad, bool)
                    nb[:n] = np.asarray(pres)[idx]
                host_bufs[name] = (buf, nb)

        # reserve what is ABOUT to allocate, counting every plane: f64
        # value + null planes for missing columns, limb digit planes
        # (8 B/row) + per-block scales for limb columns, the valid plane
        # for a fresh tile (round 4 under-counted limbs/nulls here, so
        # _used drifted below actual HBM at TSBS scale)
        limb_build = set(missing_limbs) | (set(limb_cols) & set(missing))
        est = sum(
            buf.nbytes + (0 if nb is None else nb.nbytes)
            for buf, nb in host_bufs.values()
        )
        est += len(limb_build) * (pad * 8 + (pad // BLOCK_ROWS) * 8)
        if snap is None:
            est += pad
        with self._lock:
            self._reserve_locked(est, {entry.region_id})

        cols_dev: dict[str, list] = {}
        nulls_dev: dict[str, list] = {}
        limbs_dev: dict[str, list] = {}
        for name in missing:
            check_deadline()  # per-column upload + quantize is device-bound but slow
            buf, nb = host_bufs[name]
            with _timed(f"wtile.upload.{name}"):
                chunks = self._up_chunks(buf, bounds, entry.region_id)
            if name in limb_build:
                with _timed(f"wtile.quantize.{name}"):
                    limbs_dev[name] = [_quantize_limbs_jit(x) for x in chunks]
            # the f64 plane stays EVEN for limb columns: the exact-f64
            # rerun after a failed limb verdict, mixed min/max+avg
            # queries, and cache hits with a different limb set all read
            # columns[c] — window tiles are small enough to afford both
            cols_dev[name] = chunks
            if nb is not None:
                nulls_dev[name] = self._up_chunks(nb, bounds, entry.region_id)
        for name in missing_limbs:
            # column already on the tile: quantize straight from its
            # resident device chunks, no host gather
            limbs_dev[name] = [
                _quantize_limbs_jit(x) for x in snap["cols"][name]
            ]
        valid = snap["valid"] if snap is not None else None
        if valid is None:
            v = np.zeros(pad, bool)
            v[:n] = True
            valid = self._up_chunks(v, bounds, entry.region_id)

        def plane_bytes(kind: str, chunks) -> int:
            if kind == "limbs":
                return sum(int(l.nbytes) + int(s.nbytes) for l, s in chunks)
            return sum(int(x.nbytes) for x in chunks)

        built = {"cols": cols_dev, "nulls": nulls_dev, "limbs": limbs_dev}
        with self._lock:
            race = entry.window_tiles.get(key)
            if (
                race is not None
                and race["epoch"] == dict_epoch
                and race["rows"] == n
            ):
                # merge the freshly built planes into the live tile —
                # never discard them (see above).  The SNAPSHOT's planes
                # merge too: if the tile we extended was evicted and a
                # concurrent build committed a replacement for a different
                # column set, `built` alone would leave the race tile
                # missing columns this query needs.  Double-charging is
                # avoided by only adding planes the race tile lacks
                # (race usually IS the snapshotted dict, so snap's planes
                # are already present and skip).
                added = 0
                for kind, d in built.items():
                    merged_d = (
                        {**snap[kind], **d} if snap is not None else d
                    )
                    for c, chunks in merged_d.items():
                        if c not in race[kind]:
                            race[kind][c] = chunks
                            added += plane_bytes(kind, chunks)
                race["nbytes"] += added
                entry.nbytes += added
                if self._super.get(entry.region_id) is entry:
                    self._used += added
                wt = race
            else:
                if race is not None:
                    freed = race["nbytes"]
                    entry.window_tiles.pop(key)
                    entry.nbytes -= freed
                    if self._super.get(entry.region_id) is entry:
                        self._used -= freed
                # commit snapshot ∪ new as a complete tile (the snapshot
                # arrays are kept alive by our references even if the
                # original entry was evicted mid-build)
                merged = {
                    kind: {**(snap[kind] if snap is not None else {}), **d}
                    for kind, d in built.items()
                }
                wt = {
                    **merged,
                    "valid": valid,
                    "rows": n,
                    "epoch": dict_epoch,
                    "nbytes": (
                        sum(
                            plane_bytes(kind, chunks)
                            for kind, d in merged.items()
                            for chunks in d.values()
                        )
                        + plane_bytes("valid", valid)
                    ),
                }
                entry.window_tiles[key] = wt
                entry.nbytes += wt["nbytes"]
                if self._super.get(entry.region_id) is entry:
                    self._used += wt["nbytes"]
        metrics.TILE_WINDOW_BUILDS.inc()
        return self._window_sources(wt, need_cols, limb_cols)

    def _ts_runs(self, entry: _SuperTiles, ts_name: str) -> np.ndarray:
        """The run bounds of the entry's sorted ts plane: one diff over it,
        once per plane (the first probe falls in a warm-up), charged to
        the host budget like the plane itself."""
        with self._lock:
            if entry.ts_run_starts is None:
                ts = np.asarray(entry.sorted_host[ts_name][: entry.num_rows])
                entry.ts_run_starts = _ascending_run_starts(ts)
                entry.host_nbytes += entry.ts_run_starts.nbytes
                if self._super.get(entry.region_id) is entry:
                    self._host_used += entry.ts_run_starts.nbytes
            return entry.ts_run_starts

    def _window_ranges(
        self, entry: _SuperTiles, window: tuple[int, int], ts_name: str,
        dedup: bool,
    ) -> tuple[np.ndarray, np.ndarray, int]:
        """Rows of the sorted plane with window[0] <= ts < window[1]: per
        ascending run the range [first[r], end[r]), and their exact count
        — with `dedup` the count of rows the keep plane leaves (the caller
        has built it).  Reads 2 x log2(longest run) rows per run."""
        with self._lock:  # one plane's ts, length, runs and keep count
            ts = entry.sorted_host[ts_name]
            starts = self._ts_runs(entry, ts_name)
            ends = np.append(starts[1:], entry.num_rows)
            kept = entry.keep_prefix
        first = _run_lower_bounds(ts, starts, ends, window[0])
        # searched from `first`, so an inverted window is empty, not negative
        end = _run_lower_bounds(ts, first, ends, window[1])
        if dedup:
            return first, end, int((kept[end] - kept[first]).sum())
        return first, end, int((end - first).sum())

    def window_positions(
        self, entry: _SuperTiles, window: tuple[int, int], ts_name: str,
        dedup: bool, rows: np.ndarray,
    ) -> np.ndarray:
        """For rows of the sorted planes, the row of `window`'s tile at or
        after which each falls: how many of the tile's rows (the window's
        rows, less the dedup losers) lie before it."""
        first, end, _n = self._window_ranges(entry, window, ts_name, dedup)
        kept = entry.keep_prefix if dedup else None
        held = (kept[end] - kept[first]) if dedup else (end - first)
        before = np.concatenate([[0], np.cumsum(held)])
        run = np.clip(np.searchsorted(first, rows, side="right") - 1, 0, None)
        inside = np.clip(rows, first[run], end[run])
        return before[run] + (
            (kept[inside] - kept[first[run]]) if dedup else (inside - first[run])
        )

    @staticmethod
    def _window_sources(wt: dict, need_cols: set[str], limb_cols: set[str]):
        n_chunks = len(wt["valid"])
        out = []
        for i in range(n_chunks):
            out.append((
                {c: wt["cols"][c][i] for c in need_cols if c in wt["cols"]},
                wt["valid"][i],
                {c: wt["nulls"][c][i] for c in need_cols if c in wt["nulls"]},
                None,
                {c: wt["limbs"][c][i] for c in limb_cols if c in wt["limbs"]},
            ))
        return out

    def series_table(
        self, entry: _SuperTiles, dictionary: TableDictionary, pk_cols,
    ) -> SeriesTable | None:
        """The entry's `SeriesTable` over `pk_cols`, made at the first
        request after a plane build (or a dictionary growth), kept on the
        entry and charged to the host budget like the sorted planes it is
        read from.  The caller holds the table lock and has run
        `repair_super`, so the sorted host codes are the dictionary's
        current ones.  None where the entry has no sorted host planes."""
        pk_cols = tuple(pk_cols)
        key = (entry.file_ids, entry.num_rows, dictionary.epoch, pk_cols)
        table = entry.series_table
        if table is not None and table.key == key:
            return table
        if entry.order is None or any(c not in entry.sorted_host for c in pk_cols):
            return None
        n = entry.num_rows
        planes = [np.asarray(entry.sorted_host[c][:n]) for c in pk_cols]
        change = np.zeros(max(n - 1, 0), bool)
        for plane in planes:
            change |= plane[1:] != plane[:-1]
        first = np.concatenate([[0], np.flatnonzero(change) + 1]) if n else np.zeros(0, np.int64)
        table = SeriesTable(
            key=key, tags=pk_cols,
            starts=np.concatenate([first, [n]]).astype(np.int64),
            codes=np.stack([plane[first] for plane in planes], axis=1).astype(np.int32)
            if pk_cols else np.zeros((len(first), 0), np.int32),
        )
        with self._lock:
            added = table.nbytes - (
                entry.series_table.nbytes if entry.series_table is not None else 0
            )
            entry.series_table = table
            entry.host_nbytes += added
            if self._super.get(entry.region_id) is entry:
                self._host_used += added
        return table

    def ensure_dedup_keep(self, entry: _SuperTiles) -> bool:
        """Build (once per file-set) the last-write-wins keep plane from
        the sorted host encodes: a row survives unless the NEXT row holds
        the same (pk..., ts) — lexsort stability orders duplicates by
        flush sequence, so the newest version sits last in its run.
        Returns False when the entry lacks sorted host planes."""
        with self._lock:
            if entry.valid_dedup is not None:
                return True
            if not entry.sorted_host or entry.order is None:
                return False
            n = entry.num_rows
            keep = np.zeros(entry.pad, bool)
            keep[:n] = True
            if n > 1:
                same = np.ones(n - 1, bool)
                for arr in entry.sorted_host.values():
                    same &= arr[:-1] == arr[1:]
                keep[: n - 1] &= ~same
            bounds = _chunk_bounds(entry.pad, self.chunk_rows)
            entry.keep_host = keep[:n]
            entry.keep_prefix = np.zeros(n + 1, np.int32)
            np.cumsum(entry.keep_host, dtype=np.int32, out=entry.keep_prefix[1:])
            entry.valid_dedup = self._up_chunks(keep, bounds, entry.region_id)
            added = entry.pad  # device bools
            host_added = entry.keep_host.nbytes + entry.keep_prefix.nbytes
            entry.nbytes += added
            entry.host_nbytes += host_added
            if self._super.get(entry.region_id) is entry:
                self._used += added
                self._host_used += host_added
            return True

    def host_column_chunks(self, entry: _SuperTiles, name: str):
        """Consolidated (sorted, padded, chunked) host-side numpy arrays
        for one column, built from the per-file encode cache — the same
        assembly `super_tiles` performs for device upload, without the
        upload.  Lets `ensure_limbs` quantize a column whose f64 plane was
        never sent to HBM (limb-only columns at TSBS 3-day scale: both
        representations together exceed device memory).  Returns None when
        a needed host tile was evicted."""
        if name in entry.persisted_cols:
            buf = entry.persisted_cols[name]
            return [buf[a:b] for a, b in _chunk_bounds(entry.pad, self.chunk_rows)]
        with self._lock:
            tiles = [
                self._host.get((entry.region_id, fid)) for fid in entry.file_ids
            ]
        if any(t is None for t in tiles):
            return None
        if not all(name in t.cols or name in t.absent for t in tiles):
            return None
        dtype = next(
            (t.cols[name].dtype for t in tiles if name in t.cols), np.float64
        )
        cat = np.concatenate([
            t.cols[name] if name in t.cols else np.zeros(t.num_rows, dtype)
            for t in tiles
        ])
        buf = np.zeros(entry.pad, dtype=cat.dtype)
        buf[: entry.num_rows] = cat[entry.order]
        return [buf[a:b] for a, b in _chunk_bounds(entry.pad, self.chunk_rows)]

    def gather_host_values(
        self, entry: _SuperTiles, col: str, positions: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray | None] | None:
        """Host-side value gather for the selective fast path: `positions`
        are concat-order rows (= entry.order[a:b]); values come straight
        from the per-file host encode cache.  Returns (values, present) or
        None when a needed host tile was evicted (caller falls back to the
        device path)."""
        offs = entry.file_row_offsets
        with self._lock:
            tiles = [
                self._host.get((entry.region_id, fid)) for fid in entry.file_ids
            ]
        if any(t is None for t in tiles):
            return None
        fidx = np.searchsorted(offs, positions, side="right") - 1
        rows = positions - offs[fidx]
        dtype = next(
            (t.cols[col].dtype for t in tiles if col in t.cols), np.float64
        )
        out = np.zeros(len(positions), dtype=dtype)
        present: np.ndarray | None = None
        for i, t in enumerate(tiles):
            m = fidx == i
            if not m.any():
                continue
            if col in t.absent or col not in t.cols:
                if present is None:
                    present = np.ones(len(positions), bool)
                present[m] = False
                continue
            out[m] = t.cols[col][rows[m]]
            if col in t.nulls:
                if present is None:
                    present = np.ones(len(positions), bool)
                present[m] = t.nulls[col][rows[m]]
        return out, present

    def ensure_perm(self, entry: _SuperTiles, ts_name: str):
        """Lazily build the ts-ascending permutation for time-major plans
        (padding rows last).  Cached on the entry; one sort per (region,
        file-set).  The sort runs on the HOST over the consolidation's
        own sorted ts plane: the plane is a concatenation of per-series
        ascending runs, which numpy's stable sort merges in about a
        second, where a device `argsort` of the same plane costs a
        minute or more of XLA compile before it runs at all (measured:
        74 s on a v5e host at 2^25 rows, int64).  Stable on both sides,
        so the permutation is the one the device sort gave.  Build +
        budget accounting run under the lock so a concurrent eviction
        can't leave phantom bytes in the counter (bytes are only charged
        while the entry is still cached) and the sort never runs twice."""
        with self._lock:
            if entry.perm is None:
                self._reserve_locked(entry.pad * 4, {entry.region_id})
                n = entry.num_rows  # valid rows are exactly the first n
                perm = np.arange(entry.pad, dtype=np.int32)
                perm[:n] = np.argsort(
                    np.asarray(entry.sorted_host[ts_name][:n]), kind="stable"
                )
                entry.perm = jax.device_put(
                    perm, self.chunk_device(0, entry.region_id)
                )
                entry.nbytes += entry.pad * 4
                if self._super.get(entry.region_id) is entry:
                    self._used += entry.pad * 4
            return entry.perm

    # ---- fused family build ------------------------------------------------
    def fused_union_build(
        self, ctx: TileContext, schema, manifests, device: bool = True
    ) -> dict:
        """ONE consolidated cold build for a whole query family: union the
        plane-requirement manifests and materialize every plane any family
        member needs in a single pass per region — one Parquet decode per
        SST file (the eager host decode grabs every numeric column on the
        first read), one host encode per column, ONE batched
        `_upload_missing` upload covering the union of full-plane columns,
        limb quantize / time-major permute / window gathers each once for
        the union geometry.  `device=False` stops at the host
        consolidation + sorted planes (what the cold-serve router and the
        selective host fast path read) — the prewarm form.

        A region without flushed files is skipped; a region whose build
        RAISES is skipped too, logged, and named under `errors` in the
        stats, so that a caller who asked for the planes (`prewarm`) can
        tell a table that cannot build from one that has nothing to build.
        Callers serialize whole-table builds through `build_gate` so
        concurrent builders coalesce."""
        t0 = time.perf_counter()
        pk = [c.name for c in schema.tag_columns()]
        ts_name = schema.time_index.name if schema.time_index else None
        tag_union = list(dict.fromkeys(
            [t for m in manifests for t in m.tag_cols] + pk
        ))
        value_union = list(dict.fromkeys(
            c for m in manifests for c in m.value_cols
            if schema.has_column(c) and c != ts_name
        ))
        limb_union = list(dict.fromkeys(
            c for m in manifests for c in m.limb_cols if schema.has_column(c)
        ))
        # full-plane columns: families with no window geometry scan the
        # whole super-tile, so their columns ride full device planes;
        # time-major families additionally need the full ts plane to
        # build the permutation
        full_cols = list(dict.fromkeys(
            c
            for m in manifests
            if m.window is None
            for c in m.value_cols
            if schema.has_column(c) and c != ts_name
        ))
        tm_cols = list(dict.fromkeys(
            c
            for m in manifests
            if m.time_major
            for c in m.value_cols
            if schema.has_column(c) and c != ts_name
        ))
        tm_dedup = any(m.dedup for m in manifests if m.time_major)
        windows: dict[tuple, dict] = {}
        for m in manifests:
            if m.window is None:
                continue
            w = windows.setdefault(
                (int(m.window[0]), int(m.window[1]), bool(m.dedup)),
                {"cols": set(), "limbs": set()},
            )
            w["cols"].update(m.tag_cols)
            w["cols"].update(m.value_cols)
            if m.ts_col:
                w["cols"].add(m.ts_col)
            w["limbs"].update(m.limb_cols)
        dedup_any = any(m.dedup for m in manifests)
        built = 0
        built_entries: list[_SuperTiles] = []
        errors: list[str] = []
        pinned_ids = {r.region_id for r in ctx.regions}
        log = logging.getLogger("greptimedb_tpu.tile")
        # the table lock is taken PER REGION (the prewarm discipline): a
        # multi-region background build must stall a concurrent query by
        # at most one region's build
        for region in ctx.regions:
            with ctx.dictionary.table_lock:
                region.pin_scan()
                try:
                    metas, _mems, version = region.tile_snapshot()
                    self.invalidate_region_if_changed(
                        region.region_id, {m.file_id for m in metas}, version
                    )
                    if not metas:
                        continue
                    # host consolidation first: Parquet decode (once per
                    # file), dictionary encode (once per column), (pk, ts)
                    # lexsort — shared by every family member
                    entry, _excluded = self.super_tiles(
                        region, ctx.dictionary, metas, tag_union, ts_name,
                        value_union, pinned_ids, pk, device_upload=False,
                    )
                    if entry is None:
                        continue
                    built += 1
                    built_entries.append(entry)
                    if not device:
                        continue
                    if dedup_any:
                        self.ensure_dedup_keep(entry)
                    if full_cols or tm_cols:
                        # ONE batched upload for the union of full-plane
                        # columns (pipelined encode/upload overlap)
                        up_cols = list(dict.fromkeys(full_cols + tm_cols))
                        entry, _excluded = self.super_tiles(
                            region, ctx.dictionary, metas, tag_union,
                            ts_name, up_cols, pinned_ids, pk,
                        )
                        if entry is None:
                            continue
                        # the upload can rebuild the entry object (evicted
                        # mid-build): keep the LIVE one for the mmap attach
                        built_entries[-1] = entry
                    if limb_union and full_cols:
                        self.ensure_limbs(
                            entry,
                            [c for c in limb_union if c in full_cols],
                            False, pinned_ids,
                        )
                    if tm_cols and ts_name:
                        if tm_dedup:
                            self.ensure_dedup_keep(entry)
                        self.ensure_time_major(
                            entry, ts_name, set(tm_cols) | {ts_name},
                            dedup=tm_dedup,
                        )
                    for (wlo, whi, wd), want in windows.items():
                        self.ensure_window_tile(
                            entry, (wlo, whi), ts_name,
                            {
                                c for c in want["cols"]
                                if c == ts_name or schema.has_column(c)
                            },
                            set(want["limbs"]), wd, ctx.dictionary.epoch,
                        )
                except QueryTimeoutError:
                    raise
                except Exception as exc:  # noqa: BLE001 — one region's failure is not the table's
                    errors.append(f"region {region.region_id}: {exc!r}")
                    log.warning(
                        "fused build skipped region %s", region.region_id,
                        exc_info=True,
                    )
                finally:
                    region.unpin_scan()
        if self.persist_dir:
            # wait out the background persist writer and mmap the column
            # buffers back into the live entries (OUTSIDE the table lock):
            # the cold-serve router then pages value columns off the mmap
            # instead of re-gathering whole columns from per-file tiles
            for entry in built_entries:
                try:
                    self.attach_persisted(entry, wait_s=600.0)
                except QueryTimeoutError:
                    break  # deadline owns the caller; mmaps are optional
        metrics.TILE_FUSED_BUILDS.inc()
        return {
            "regions_built": built,
            "manifests": len(manifests),
            "ms": round((time.perf_counter() - t0) * 1000.0, 1),
            **({"errors": errors} if errors else {}),
        }


def _encode_host_tiles(
    dictionary: TableDictionary,
    table: pa.Table,
    columns: list[str],
    tag_cols: list[str],
    ts_col: str | None,
):
    """Shared host encode for SST files and memtable tails: tag strings
    -> dictionary codes (growing the dictionary), ts -> int64, values ->
    numeric.  Returns (cols, nulls, epochs, nbytes) of unpadded numpy
    arrays, or None when a column can't tile."""
    n = table.num_rows
    cols: dict[str, np.ndarray] = {}
    nulls: dict[str, np.ndarray] = {}
    epochs: dict[str, int] = {}
    nbytes = 0
    # every tag column's new values first, the dictionary saved once
    dictionary.update_table(table, [name for name in columns if name in tag_cols])
    for name in columns:
        col = table[name]
        if name in tag_cols:
            np_arr = dictionary.encode(name, col)
            epochs[name] = dictionary.epoch
        elif name == ts_col:
            np_arr = np.asarray(
                pc.cast(col, pa.int64()).to_numpy(zero_copy_only=False)
            )
        else:
            np_arr = _value_to_numpy(col)
            if np_arr is None:
                return None
            if col.null_count:
                present = np.asarray(
                    pc.is_valid(col).to_numpy(zero_copy_only=False), bool
                )
                nulls[name] = present
                nbytes += present.nbytes
        cols[name] = np.ascontiguousarray(np_arr)
        nbytes += np_arr.nbytes
    return cols, nulls, epochs, nbytes


def _value_to_numpy(col) -> np.ndarray | None:
    t = col.type
    if pa.types.is_dictionary(t):
        col = pc.cast(col, t.value_type)
        t = t.value_type
    if not (pa.types.is_floating(t) or pa.types.is_integer(t) or pa.types.is_boolean(t)):
        return None
    arr = col.to_numpy(zero_copy_only=False)
    if arr.dtype == object:
        arr = np.array([0 if v is None else v for v in arr], dtype=np.float64)
    elif np.issubdtype(arr.dtype, np.floating):
        arr = np.nan_to_num(arr, nan=0.0)
    elif arr.dtype == bool:
        arr = arr.astype(np.float32)
    return arr


def _record_dispatch(disp, plan, **noted):
    """A closed `tile.dispatch` span's duration into the flight recorder:
    one clock for the trace, the counter, `device_dispatches` and EXPLAIN
    ANALYZE."""
    flight_recorder.stage_add("dispatch", disp.duration() * 1000.0)
    flight_recorder.note(strategy=plan.agg_strategy, **noted)


# ---- the single-dispatch program -------------------------------------------


_program_cache_lock = threading.Lock()


def _tile_program_cached(plan, nullable_cols, spec):
    """_tile_program + compile-cache hit/miss accounting (the lru_cache is
    the in-process program cache; the persistent XLA cache sits below).
    The lock makes the miss-delta attribution exact under concurrent
    queries — program BUILD is cheap closure assembly (XLA tracing happens
    at first dispatch), so serializing it costs nothing."""
    with _program_cache_lock, tracing.span("tile.compile") as s:
        t0 = time.perf_counter()
        before = _tile_program.cache_info().misses
        out = _tile_program(plan, nullable_cols, spec)
        if _tile_program.cache_info().misses > before:
            metrics.TPU_COMPILE_CACHE_MISSES.inc()
            s.attributes["cache"] = "miss"
        else:
            metrics.TPU_COMPILE_CACHE_HITS.inc()
            s.attributes["cache"] = "hit"
        flight_recorder.stage_add(
            "compile", (time.perf_counter() - t0) * 1000.0
        )
        flight_recorder.note(compile_cache=s.attributes["cache"])
    return out


@functools.lru_cache(maxsize=256)
def _tile_program(plan: DistGroupByPlan, nullable_cols: tuple[str, ...], spec=None):
    """jit program over ALL of a query's sources: per-source partial
    states (blocked/scatter kernels), merged pairwise, FINALIZED on
    device, and packed into TWO result buffers — int32 [Ki, G] for
    presence/count rows, float64 [Kf, G] for value rows — holding ONLY
    the rows this query's output consumes.  One dispatch in, one
    device_get of the buffer trio out (multiple buffers batch into one
    fetch; past the first megabyte result BYTES dominate it).

    Source count is small by construction (one super-tile per region plus
    memtable tails), so the traced unroll stays bounded; jax re-traces
    per distinct source-shape signature, and pow2 padding keeps that set
    O(log N).  Compile time is flat in shape since the blocked/scatter
    kernel pair compiles in ~3 s at any size (the superlinear
    associative-scan branch was removed — see ops/aggregate.py).

    Count rows ship only for (a) explicit count() outputs and (b) columns
    whose sources actually carry a null mask this query (NULL-group
    gating); other columns gate on the single presence row.

    Result packing minimizes FETCHED BYTES (the [K, G] transfer is the
    wide-result floor) once the group space is large
    enough for bytes to matter (>= 2^14 groups): avg rows — already
    divided on device — ship as float32 (6e-8 relative, far under the
    engine's 1e-6 result bar), sum/min/max keep float64 (sums of integer
    data must stay exact), and the int buffer drops to saturating uint8
    when no output consumes an exact count (presence/count rows then only
    NULL-gate via `> 0`).  Small results ship full-precision — their
    transfer is round-trip-bound, not byte-bound.

    With `spec` (a query.device_finalize DeviceFinalizeSpec) the program
    extends the lowering boundary PAST the aggregate: HAVING masks, the
    ORDER BY key sort (ties broken by group id ascending — exactly the
    CPU replay's stable sort over the gid-ordered aggregate table) and
    LIMIT truncation all run on device over the finalized [G] states, and
    the fetch ships a compact [K, cap] buffer + the selected group-id
    vector + a survivor count instead of the full group space — the
    O(rows_out) readback contract.  Compact results skip the f32/uint8
    byte packing (they are small; f64 keeps them bit-identical to the
    host path on the same aggregates) and their f64 rows join the SAME
    flat word buffer as arithmetically-composed IEEE bit pairs
    (ops/aggregate.pack_f64_bits), so the whole compact result —
    lastpoint included — is ONE device_get of one array (each extra
    fetched array is another device->host crossing).
    With `plan.agg_strategy == "hash"` the program carries a
    [hash_slots] int64 key table through the per-source fold
    (ops/aggregate.hash_group_slots assigns each gid one stable slot
    across ALL sources), every state row is [hash_slots]-sized, and the
    fetch ships (buf, accs64, table_keys) — the host decodes slot ->
    group key from the table, so the dense [G] space never exists on
    device OR on the wire.  An overflow word rides the flat buffer like
    the limb verdict: 1 means some row never found a slot and the caller
    must rerun on the dense path (never a wrong result).

    Returns (fn, int_layout, acc32_layout, acc64_layout, int_dtype)."""
    per_col_aggs: dict[str, set] = {}
    for func, col in plan.agg_specs:
        per_col_aggs.setdefault(col, set()).add(_FUNC_TO_KERNEL[func])
    is_hash = plan.agg_strategy == "hash"
    # spec (device finalize) and hash are mutually exclusive by planner
    # construction: hash results are already compact (O(slots)), and the
    # host replay owns Sort/LIMIT/HAVING for them
    assert spec is None or not is_hash
    # byte-packing keys off the LOGICAL group space for BOTH strategies,
    # so hash and sort ship identical value precision (f32 avgs, uint8
    # presence bits) and stay bit-comparable end to end
    pack_bytes = plan.num_groups >= 1 << 14 and spec is None
    int_layout: list[tuple[str, str]] = [("__presence", "count")]
    acc32_layout: list[tuple[str, str]] = []
    acc64_layout: list[tuple[str, str]] = []
    for col, aggs in per_col_aggs.items():
        for agg in sorted(aggs):
            if agg == "count":
                continue  # count rides the int buffer (or presence)
            target = acc32_layout if (pack_bytes and agg == "avg") else acc64_layout
            target.append((col, agg))
        # a per-column count row ships only when the column carries its
        # own null-gated count; otherwise presence substitutes exactly
        # (count-pass sharing, see compute_partial_states)
        if col in nullable_cols and col != COUNT_STAR:
            int_layout.append((col, "count"))
    needs_exact_counts = any(
        _FUNC_TO_KERNEL[func] == "count" for func, _c in plan.agg_specs
    )
    int_dtype = jnp.int32 if (needs_exact_counts or not pack_bytes) else jnp.uint8
    # columns whose sums carry a quantization-error bound (limb mode):
    # the program appends a one-word verdict — 1 iff every group's bound
    # is within _LIMB_VERDICT_RTOL of |sum| — and the caller reruns in
    # exact f64 on 0
    limb_err_cols = (
        TileExecutor._limb_sum_cols(plan) if plan.acc_dtype == "limb" else []
    )

    # THREE small jitted pieces with a host-side loop, NOT one jit over
    # every source: per-source partials share one compile per chunk shape
    # (chunks are equal-sized by construction) and successive dispatches
    # execute in order on the device stream, so peak HBM is ONE chunk's
    # working set.  A single unrolled program over 4 chunks x 10 columns
    # both overcommitted HBM (concurrent column scheduling) and took
    # minutes to compile.
    # Each piece traces under a `jax.named_scope`, so the device ops of a
    # profiler trace say which piece they belong to (metadata only).
    def _partial_states(*args, **kwargs):
        with jax.named_scope("partial"):
            return compute_partial_states(
                plan, *args, count_cols=nullable_cols, **kwargs
            )

    partial_jit = jax.jit(_partial_states)

    def _partial(cols, valid, nulls, dyn, perm, limbs, hash_table=None):
        if is_hash:
            return partial_jit(
                cols, valid, nulls, dyn, perm, limbs=limbs, hash_table=hash_table
            )
        return partial_jit(cols, valid, nulls, dyn, perm, limbs=limbs)

    def _merge(a, b):
        with jax.named_scope("merge"):
            return {k: merge_states(a[k], b[k]) for k in a}

    merge_jit = jax.jit(_merge)

    def _device_select(merged, outs, presence, hv):
        """Device finalization: HAVING mask (ops/aggregate.having_mask)
        -> top-k-over-states (ops/aggregate.topk_group_select) -> the
        first `cap` group ids.  Returns (sel_gids [cap] int32, n_out)."""
        from ..ops.aggregate import having_mask, topk_group_select

        g = presence.shape[0]
        gid = jnp.arange(g, dtype=jnp.int32)
        dims = list(plan.tag_cards)
        if plan.bucket_col is not None:
            dims.append(plan.n_buckets)

        def ref_val(ref):
            """-> (value [G], isnull [G] | None).  Dim refs decode from
            the gid iota (tag codes are value-sorted, NULL last, so code
            order IS SQL-default order); agg refs read the finalized
            outputs with the same count>0 NULL gate the host applies."""
            if ref[0] == "dim":
                i = ref[1]
                div = 1
                for c in dims[i + 1:]:
                    div *= c
                return (gid // div) % dims[i], None
            _kind, col, agg = ref
            if col == COUNT_STAR or col not in merged:
                return presence, None
            if agg == "count":
                cc = merged[col].counts
                return (cc if cc is not None else presence), None
            counts = merged[col].counts
            isnull = (counts == 0) if counts is not None else None
            v = outs[col][agg]
            if jnp.issubdtype(v.dtype, jnp.floating):
                # the host masks NaN outputs to NULL (inf-inf etc.); the
                # device key must use the same NULL bucket or the two
                # paths place such groups differently under ORDER BY
                nan = jnp.isnan(v)
                isnull = nan if isnull is None else (isnull | nan)
            return v, isnull

        mask = presence > 0
        if spec.having is not None:
            mask = mask & having_mask(spec.having, ref_val, hv, (g,))
        order_keys = []
        for ref, asc, nulls_first in spec.order:
            v, isn = ref_val(ref)
            order_keys.append((v, isn, asc, nulls_first))
        return topk_group_select(mask, order_keys, spec.cap)

    def _final(merged, hv, table_keys=None):
        presence = merged["__presence"].counts
        outs = {"__presence": {"count": presence}}
        for col, aggs in per_col_aggs.items():
            if col in merged:
                outs[col] = finalize(
                    merged[col], tuple(sorted(aggs)), counts=presence
                )
        if spec is not None:
            sel, n_out = _device_select(merged, outs, presence, hv)

            def pick(row):
                return row[sel]
        else:
            sel = n_out = None

            def pick(row):
                return row

        def as_int(row):
            if int_dtype == jnp.uint8:
                # gating-only rows (consumed as `> 0`): pack to 1 bit/group,
                # 32 to a word whose little-endian bytes are np.packbits'
                # (group 8j + k of a word is bit 7 - k of its byte j)
                g = row.shape[0]
                gp = -(-g // 32) * 32
                k = jnp.arange(32, dtype=jnp.uint32)
                weight = jnp.left_shift(jnp.uint32(1), k // 8 * 8 + 7 - k % 8)
                bits = jnp.pad(row > 0, (0, gp - g)).reshape(gp // 32, 32)
                return jnp.sum(bits * weight, axis=1, dtype=jnp.uint32)
            return row.astype(jnp.int32)

        parts = [
            jnp.stack([pick(as_int(outs[col][agg])) for col, agg in int_layout])
        ]
        if acc32_layout:
            parts.append(jnp.stack(
                [pick(outs[col][agg]).astype(jnp.float32) for col, agg in acc32_layout]
            ))
        if spec is not None:
            # compact-path extras: the selected group ids (host tag/bucket
            # decode) and the survivor count ride the same flat buffer
            parts.append(sel.astype(jnp.int32).reshape(1, -1))
            parts.append(n_out.astype(jnp.int32).reshape(1, 1))
            if acc64_layout:
                # f64 rows JOIN the flat buffer as arithmetically-composed
                # IEEE bit pairs (ops/aggregate.pack_f64_bits — the TPU x64
                # rewrite cannot lower a 64-bit bitcast), so the whole
                # compact result — lastpoint included — ships as ONE
                # device_get of one buffer instead of a buffer pair
                from ..ops.aggregate import pack_f64_bits

                parts.append(pack_f64_bits(jnp.stack(
                    [pick(outs[col][agg]) for col, agg in acc64_layout]
                )))
        # ONE flat buffer of 32-bit words for the int and f32 rows: every
        # array of a jax.device_get is its own device->host crossing, so
        # the rows bitcast to uint32 and concatenate; the host reads the
        # words' bytes.  Words, not bytes: a bitcast to uint8 compiles for
        # a v5e in ~0.25 ms an element (a [10, 57344] float32 stack took
        # 200 s), one to a type of the same width in none.  f64 rows
        # CANNOT join it — the TPU x64 rewrite has no lowering for 64-bit
        # bitcast-convert — so they ride as a second (usually empty)
        # array in the same device_get.
        flat = [
            p.reshape(-1)
            if p.dtype == jnp.uint32
            else jax.lax.bitcast_convert_type(p, jnp.uint32).reshape(-1)
            for p in parts
        ]
        if limb_err_cols:
            ok = jnp.bool_(True)
            for col in limb_err_cols:
                err = merged["__limb_err:" + col].sums
                s = merged[col].sums
                ok = ok & jnp.all(
                    err <= jnp.maximum(jnp.abs(s) * _LIMB_VERDICT_RTOL, 1e-12)
                )
            flat.append(ok.astype(jnp.uint32).reshape(1))
        if is_hash:
            # trailing verdict word, like the limb bound: 0 = clean,
            # 1 = some row never placed -> caller reruns dense
            flat.append(
                (merged["__hash_overflow"].counts > 0).astype(jnp.uint32).reshape(1)
            )
        buf = jnp.concatenate(flat) if len(flat) > 1 else flat[0]
        if spec is not None:
            # compact path: EVERYTHING (f64 rows included, bit-packed
            # above) rides the one flat buffer — a single-array fetch
            return (buf,)
        out_g = presence.shape[0]
        if acc64_layout:
            accs64 = jnp.stack(
                [pick(outs[col][agg]).astype(jnp.float64) for col, agg in acc64_layout]
            )
        else:
            accs64 = jnp.zeros((0, out_g), jnp.float64)
        if is_hash:
            return buf, accs64, table_keys
        return buf, accs64

    final_jit = jax.jit(jax.named_scope("finalize")(_final))

    def run_all(sources, dyn, sync=False):
        # per-source partials compute WHERE THE CHUNK LIVES (jit follows
        # committed inputs; chunks round-robin over local devices); the
        # [G]-sized states then hop to the first source's device for the
        # N:1 merge — tiny transfers riding ICI on a real slice, the
        # reference MergeScan fan-in (merge_scan.rs:250).
        # sync=True (region-streamed mode) blocks after each merge so the
        # producer can safely RELEASE a region's input planes before
        # building the next one — peak HBM stays one region's working set.
        if not _in_fused_build():
            # builder (ghost) dispatches stay out of the per-query counter
            metrics.TPU_DEVICE_DISPATCHES.inc()
            if plan.series_slots:
                metrics.TILE_SERIES_GIDS.inc()
            if plan.partitioned:
                metrics.TILE_ORDINAL_GIDS.inc()
        if _in_flow_maintenance():
            metrics.FLOW_DEVICE_DISPATCH_TOTAL.inc()
        hv = jnp.asarray(
            dyn.get("having_values") or (0.0,), jnp.float64
        )
        pdyn = {
            k: dyn[k]
            for k in ("filter_values", "bucket_origin", "bucket_interval")
        }
        merged = None
        target = None
        table_keys = None
        if is_hash:
            from ..ops.aggregate import HASH_EMPTY

            table_keys = jnp.full((plan.hash_slots,), HASH_EMPTY, jnp.int64)
        for cols, valid, nulls, perm, limbs in sources:
            check_deadline()  # one dispatch per chunk source
            if is_hash:
                # the key table follows the chunk (jit inputs must share a
                # device); the [H] hop is tiny next to the chunk planes
                in_leaves = jax.tree_util.tree_leaves((cols, valid))
                src_dev = (
                    next(iter(in_leaves[0].devices()))
                    if in_leaves and hasattr(in_leaves[0], "devices")
                    else None
                )
                if src_dev is not None:
                    table_keys = jax.device_put(table_keys, src_dev)
                states, table_keys = _partial(
                    cols, valid, nulls, pdyn, perm, limbs, hash_table=table_keys
                )
            else:
                states = _partial(cols, valid, nulls, pdyn, perm, limbs)
            leaves = jax.tree_util.tree_leaves(states)
            dev = next(iter(leaves[0].devices())) if leaves else None
            if merged is None:
                merged, target = states, dev
            else:
                if dev is not None and dev != target:
                    states = jax.device_put(states, target)
                merged = merge_jit(merged, states)
            if sync:
                jax.block_until_ready(jax.tree_util.tree_leaves(merged))
        if merged is None:
            raise ValueError("tile program received no sources")
        if is_hash and target is not None:
            table_keys = jax.device_put(table_keys, target)
        return final_jit(merged, hv, table_keys)

    # shape-metadata precompile hook (pipelined cold path): the executor
    # lowers+compiles this jit from ShapeDtypeStructs in the background
    # while plane uploads are still in flight — the persistent XLA cache
    # then serves the dispatch-time compile as a hit
    run_all._partial_jit = partial_jit
    # the mesh path (tile.mesh_devices) reuses THIS finalize so its
    # result packing is byte-identical to the single-chip dispatch
    run_all._final_jit = final_jit

    return (
        run_all,
        tuple(int_layout),
        tuple(acc32_layout),
        tuple(acc64_layout),
        int_dtype,
    )


# ---- mega-program fusion (batch.fuse_programs) ------------------------------
#
# ONE fused XLA program over a whole batch tick: each member of the tick
# contributes its `_tile_program` pieces as an independent branch of a
# single outer jit, so N distinct warm queries over the same resident
# planes cost ONE XLA invocation instead of N.  The members' folds are
# replayed op-for-op (partial states per source, pairwise merge in
# source order, device finalize) via each member's own partial_jit /
# final_jit — jit-of-jit INLINES them into the one executable, so every
# member's result leaves are bit-identical to its solo dispatch.
#
# Compile-once contract: the lru key is the multiset (sorted tuple) of
# the members' `_tile_program` cache keys — literal-insensitive plan
# structure + shape buckets.  Literals, bucket geometry, HAVING bounds,
# and the source planes themselves ride as dynamic traced inputs, so a
# dashboard fleet sliding its windows re-hits BOTH this cache and jit's
# trace cache with zero recompiles.  `_MEGA_STATS["traces"]` moves once
# per outer (re)trace — the slid-window zero-recompile tests read its
# delta directly.

_MEGA_STATS = {"traces": 0, "programs": 0}


@functools.lru_cache(maxsize=64)
def _mega_program(member_keys: tuple):
    """Fused program over `member_keys`, each a `_tile_program` cache key
    (plan, nullable count-cols, finalize spec).  The returned jit takes
    one argument: a tuple of per-member (sources, pdyn, hv) pytrees, and
    returns the tuple of per-member packed result leaves — exactly what
    each member's solo `run_all` would have returned, emitted from one
    dispatch.  Single-device only (the caller gates): the solo path's
    per-source device hops don't exist inside one trace."""
    pieces = [_tile_program(*k) for k in member_keys]
    plans = [k[0] for k in member_keys]

    def _fused(member_inputs):
        _MEGA_STATS["traces"] += 1
        from ..ops.aggregate import HASH_EMPTY

        outs = []
        for (run_all, *_), plan, (sources, pdyn, hv) in zip(
            pieces, plans, member_inputs
        ):
            partial_jit = run_all._partial_jit
            final_jit = run_all._final_jit
            is_hash = plan.agg_strategy == "hash"
            table_keys = (
                jnp.full((plan.hash_slots,), HASH_EMPTY, jnp.int64)
                if is_hash
                else None
            )
            merged = None
            for cols, valid, nulls, perm, limbs in sources:
                if is_hash:
                    states, table_keys = partial_jit(
                        cols, valid, nulls, pdyn, perm, limbs=limbs,
                        hash_table=table_keys,
                    )
                else:
                    states = partial_jit(
                        cols, valid, nulls, pdyn, perm, limbs=limbs
                    )
                if merged is None:
                    merged = states
                else:
                    with jax.named_scope("merge"):
                        merged = {
                            k: merge_states(merged[k], states[k]) for k in merged
                        }
            outs.append(final_jit(merged, hv, table_keys))
        return tuple(outs)

    _MEGA_STATS["programs"] += 1
    return jax.jit(_fused)


# ---- multi-chip mesh execution (tile.mesh_devices) --------------------------
#
# The promotion of the MULTICHIP dryrun to the real tile path: the same
# per-source partial-state math runs under shard_map over the 1-D
# `regions` mesh — every device scans + partially aggregates its shard of
# the chunk sources in ONE collective dispatch — and the merge rides XLA
# collectives over ICI instead of the host-side N:1 device_put loop.
#
# Accumulation-order contract (the dense/hash parity bar from the
# agg-strategy work): counts merge with psum and min/max with an
# all_gather + fold (ops/aggregate.mesh_min/mesh_max: the chip lowers no
# 64-bit all-reduce but SUM) — integer adds and order statistics are
# bit-exact under ANY reduction order — while float sums and LAST states, whose merge is order-
# sensitive, all_gather the per-source partials and fold them in GLOBAL
# SOURCE ORDER, exactly the single-chip loop's left fold.  The merged
# states are therefore bit-identical for any mesh size (1 device == 8
# devices == the single-chip path when sources form one shape run).
# Device-finalize (ORDER BY/LIMIT/HAVING + compaction) runs ONCE
# post-merge on the first mesh device via the same final_jit the
# single-chip program uses, so readback stays O(rows_out) from one chip.


class _MeshIneligible(Exception):
    """Query shape the mesh program does not express (per-source perms,
    hash plans over heterogeneous source shapes): degrade silently to the
    single-chip dispatch — never an error."""


def _mesh_runs(device_sources) -> list[list]:
    """Split the global source list into CONTIGUOUS runs of identical
    pytree structure + leaf shapes/dtypes: one shard_map dispatch per run
    (stacking needs uniform shapes), cross-run states merge pairwise in
    run order.  Contiguity preserves the global source order inside each
    run, which is what the sums fold keys on."""
    runs: list[list] = []
    last_sig = None
    for src in device_sources:
        cols, valid, nulls, perm, limbs = src
        if perm is not None:
            raise _MeshIneligible(
                "per-source permutation has no stacked mesh form"
            )
        leaves, treedef = jax.tree_util.tree_flatten((cols, valid, nulls, limbs))
        sig = (
            treedef,
            tuple((tuple(l.shape), str(l.dtype)) for l in leaves),
        )
        if runs and sig == last_sig:
            runs[-1].append(src)
        else:
            runs.append([src])
            last_sig = sig
    return runs


def _stack_mesh_inputs(mesh, devices, sources, n_local):
    """Assemble one run's sources into `n_local` global pytrees, slot s
    holding every device's s-th source: each leaf a global array whose
    leading axis is sharded over the `regions` axis, MADE OF the planes
    where they lie (make_array_from_single_device_arrays: no copy, no
    device op, nothing to compile).  Chunk placement co-locates a source
    with its mesh device; an off-mesh source hops once.  Devices short of
    S sources pad with all-invalid dummies (valid=False ⇒ identity
    states).  Returns (slots, positions) where positions[k] = (device,
    local slot) of global source k — the static fold order."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .mesh import REGION_AXIS

    n_dev = len(devices)
    dev_index = {d: i for i, d in enumerate(devices)}
    per_dev: list[list] = [[] for _ in range(n_dev)]
    positions: list[tuple[int, int]] = []
    for cols, valid, nulls, _perm, limbs in sources:
        d = dev_index.get(
            next(iter(valid.devices())) if hasattr(valid, "devices") else None
        )
        if d is None or len(per_dev[d]) >= n_local:
            d = min(range(n_dev), key=lambda i: (len(per_dev[i]), i))
        positions.append((d, len(per_dev[d])))
        per_dev[d].append((cols, valid, nulls, limbs))
    template = per_dev[positions[0][0]][0]
    treedef = jax.tree_util.tree_structure(template)

    def local_leaves(d, s):
        dev = devices[d]
        if s < len(per_dev[d]):
            leaves = jax.tree_util.tree_leaves(per_dev[d][s])
            if all(
                getattr(l, "devices", None) and l.devices() == {dev}
                for l in leaves
            ):
                return leaves
            make = lambda: [jax.device_put(l, dev) for l in leaves]  # noqa: E731
        else:
            make = lambda: [  # noqa: E731
                jax.device_put(np.zeros(l.shape, l.dtype), dev)
                for l in jax.tree_util.tree_leaves(template)
            ]
        # the caller's thread is outside the supervisor: the rare hop and
        # the dummies are uploads like any other
        return device_health.supervised_call("upload", make, devices=(d,))

    sharding = NamedSharding(mesh, P(REGION_AXIS))
    slots = []
    for s in range(n_local):
        by_dev = [local_leaves(d, s) for d in range(n_dev)]
        slots.append(jax.tree_util.tree_unflatten(treedef, [
            jax.make_array_from_single_device_arrays(
                (n_dev * leaf.shape[0],) + tuple(leaf.shape[1:]), sharding,
                [by_dev[d][i] for d in range(n_dev)],
            )
            for i, leaf in enumerate(by_dev[0])
        ]))
    return tuple(slots), tuple(positions)


@functools.lru_cache(maxsize=64)
def _mesh_merge_program(plan, nullable_cols, mesh, positions):
    """jit'd shard_map over the `regions` mesh computing per-source
    partial AggStates (this device's slots of `_stack_mesh_inputs`) and merging
    them with collectives — see the module-section comment above for the
    order contract.  Hash plans thread a LOCAL key table per device, then
    merge by keyed scatter before/through the collective: the gathered
    per-device tables union into one deterministic table
    (ops/aggregate.hash_group_slots over their keys — scatter-min claims,
    data-order independent) and every source's state rows scatter through
    its device's slot map in global source order.  Returns the merged
    state dict (plus the union key table for hash), replicated."""
    from jax.sharding import PartitionSpec as P

    from ..ops.aggregate import HASH_EMPTY, hash_group_slots, mesh_max, mesh_min
    from .mesh import REGION_AXIS

    is_hash = plan.agg_strategy == "hash"
    n_dev = int(mesh.devices.size)
    real = positions

    def per_device(data, dyn):
        local_states = []
        table = (
            jnp.full((plan.hash_slots,), HASH_EMPTY, jnp.int64)
            if is_hash
            else None
        )
        for cols, valid, nulls, limbs in data:
            # a slot's leaves arrive as this device's own planes: the
            # shard of the leading axis IS the resident source
            with jax.named_scope("mesh_partial"):
                if is_hash:
                    st, table = compute_partial_states(
                        plan, cols, valid, nulls, dyn, None,
                        count_cols=nullable_cols, limbs=limbs,
                        hash_table=table,
                    )
                else:
                    st = compute_partial_states(
                        plan, cols, valid, nulls, dyn, None,
                        count_cols=nullable_cols, limbs=limbs,
                    )
            local_states.append(st)
        with jax.named_scope("mesh_merge"):
            return merge(local_states, table)

    def merge(local_states, table):
        def gathered(sts, get):
            # [D, S, rows]: every device sees every source's partial
            return jax.lax.all_gather(
                jnp.stack([get(st) for st in sts]), REGION_AXIS
            )

        if is_hash:
            # keyed-scatter merge: union the per-device tables, then fold
            # every source's rows through its device's slot map
            tables = jax.lax.all_gather(table, REGION_AXIS)  # [D, H]
            keys_flat = tables.reshape(-1)
            union = jnp.full((plan.hash_slots,), HASH_EMPTY, jnp.int64)
            union, uslots, overflow_u = hash_group_slots(
                union, keys_flat, keys_flat != HASH_EMPTY
            )
            slot_map = uslots.reshape(n_dev, plan.hash_slots)

            def dev_idx(d, rows):
                m = slot_map[d]
                if rows == plan.hash_slots + 1:
                    # the trailing masked/overflow row maps onto itself
                    m = jnp.concatenate(
                        [m, jnp.full((1,), plan.hash_slots, m.dtype)]
                    )
                return m

            merged = {}
            for key in local_states[0]:
                sts = [ls[key] for ls in local_states]
                if key == "__hash_overflow":
                    local = sts[0].counts
                    for st in sts[1:]:
                        local = local + st.counts
                    total = jax.lax.psum(local, REGION_AXIS)
                    total = total + overflow_u.astype(total.dtype).reshape(1)
                    merged[key] = AggState(counts=total)
                    continue
                kwargs = {}
                if sts[0].sums is not None:
                    g = gathered(sts, lambda st: st.sums)
                    rows = g.shape[-1]
                    acc = jnp.zeros((rows,), g.dtype)
                    for d, s in real:
                        acc = acc.at[dev_idx(d, rows)].add(g[d, s])
                    kwargs["sums"] = acc
                if sts[0].counts is not None:
                    g = gathered(sts, lambda st: st.counts)
                    rows = g.shape[-1]
                    acc = jnp.zeros((rows,), g.dtype)
                    for d, s in real:
                        acc = acc.at[dev_idx(d, rows)].add(g[d, s])
                    kwargs["counts"] = acc
                if sts[0].mins is not None:
                    g = gathered(sts, lambda st: st.mins)
                    rows = g.shape[-1]
                    acc = jnp.full((rows,), jnp.finfo(g.dtype).max, g.dtype)
                    for d, s in real:
                        acc = acc.at[dev_idx(d, rows)].min(g[d, s])
                    kwargs["mins"] = acc
                if sts[0].maxs is not None:
                    g = gathered(sts, lambda st: st.maxs)
                    rows = g.shape[-1]
                    acc = jnp.full((rows,), jnp.finfo(g.dtype).min, g.dtype)
                    for d, s in real:
                        acc = acc.at[dev_idx(d, rows)].max(g[d, s])
                    kwargs["maxs"] = acc
                merged[key] = AggState(**kwargs)
            return merged, union

        merged = {}
        for key in local_states[0]:
            sts = [ls[key] for ls in local_states]
            kwargs = {}
            if sts[0].counts is not None:
                local = sts[0].counts
                for st in sts[1:]:
                    local = local + st.counts
                kwargs["counts"] = jax.lax.psum(local, REGION_AXIS)
            if sts[0].mins is not None:
                local = sts[0].mins
                for st in sts[1:]:
                    local = jnp.minimum(local, st.mins)
                kwargs["mins"] = mesh_min(local, REGION_AXIS)
            if sts[0].maxs is not None:
                local = sts[0].maxs
                for st in sts[1:]:
                    local = jnp.maximum(local, st.maxs)
                kwargs["maxs"] = mesh_max(local, REGION_AXIS)
            if sts[0].sums is not None:
                g = gathered(sts, lambda st: st.sums)
                d0, s0 = real[0]
                acc = g[d0, s0]
                for d, s in real[1:]:
                    acc = acc + g[d, s]
                kwargs["sums"] = acc
            if sts[0].last_ts is not None:
                gt = gathered(sts, lambda st: st.last_ts)
                gv = gathered(sts, lambda st: st.last_val)
                d0, s0 = real[0]
                lt, lv = gt[d0, s0], gv[d0, s0]
                for d, s in real[1:]:
                    bt, bv = gt[d, s], gv[d, s]
                    # ties go to the later source — merge_states' rule
                    newer = bt >= lt
                    lv = jnp.where(newer, bv, lv)
                    lt = jnp.maximum(lt, bt)
                kwargs["last_ts"], kwargs["last_val"] = lt, lv
            merged[key] = AggState(**kwargs)
        return merged

    # the outputs ARE replicated — collectives plus a fold every device
    # computes identically — but the static replication checker cannot
    # prove it through the gather-indexed fold
    return jax.jit(
        jax.shard_map(
            per_device,
            mesh=mesh,
            in_specs=(P(REGION_AXIS), P()),
            out_specs=P(),
            check_vma=False,
        )
    )


# cross-run merge on the first mesh device (tiny [G] leaves); shared
# trace cache across queries
_mesh_cross_merge = jax.jit(
    lambda a, b: {k: merge_states(a[k], b[k]) for k in a}
)


@functools.lru_cache(maxsize=32)
def _mesh_hash_cross_program(plan):
    """Cross-run merge for hash plans: two runs' slot spaces are keyed by
    DIFFERENT tables, so the pairwise merge is a keyed scatter — union
    the two key tables deterministically, then scatter both runs' state
    rows through their slot maps (a first, b second: run order)."""
    from ..ops.aggregate import HASH_EMPTY, hash_group_slots

    h = plan.hash_slots

    def cross(a, akeys, b, bkeys):
        keys = jnp.concatenate([akeys, bkeys])
        union = jnp.full((h,), HASH_EMPTY, jnp.int64)
        union, slots, overflow_u = hash_group_slots(
            union, keys, keys != HASH_EMPTY
        )
        ia, ib = slots[:h], slots[h:]

        def idx(part, rows):
            if rows == h + 1:  # trailing masked/overflow row -> itself
                part = jnp.concatenate([part, jnp.full((1,), h, part.dtype)])
            return part

        out = {}
        for key in a:
            sa, sb = a[key], b[key]
            if key == "__hash_overflow":
                tot = sa.counts + sb.counts
                out[key] = AggState(
                    counts=tot + overflow_u.astype(tot.dtype).reshape(1)
                )
                continue
            kwargs = {}
            if sa.sums is not None:
                rows = sa.sums.shape[0]
                acc = jnp.zeros((rows,), sa.sums.dtype)
                acc = acc.at[idx(ia, rows)].add(sa.sums)
                acc = acc.at[idx(ib, rows)].add(sb.sums)
                kwargs["sums"] = acc
            if sa.counts is not None:
                rows = sa.counts.shape[0]
                acc = jnp.zeros((rows,), sa.counts.dtype)
                acc = acc.at[idx(ia, rows)].add(sa.counts)
                acc = acc.at[idx(ib, rows)].add(sb.counts)
                kwargs["counts"] = acc
            if sa.mins is not None:
                rows = sa.mins.shape[0]
                acc = jnp.full((rows,), jnp.finfo(sa.mins.dtype).max, sa.mins.dtype)
                acc = acc.at[idx(ia, rows)].min(sa.mins)
                acc = acc.at[idx(ib, rows)].min(sb.mins)
                kwargs["mins"] = acc
            if sa.maxs is not None:
                rows = sa.maxs.shape[0]
                acc = jnp.full((rows,), jnp.finfo(sa.maxs.dtype).min, sa.maxs.dtype)
                acc = acc.at[idx(ia, rows)].max(sa.maxs)
                acc = acc.at[idx(ib, rows)].max(sb.maxs)
                kwargs["maxs"] = acc
            out[key] = AggState(**kwargs)
        return out, union

    return jax.jit(cross)


def _mesh_stage(mesh, device_sources) -> list[tuple]:
    """The host half of a mesh dispatch, on the caller's thread (stage
    `tile.mesh_stack`): split the sources into shape runs and assemble
    each run's sharded inputs.  Raises _MeshIneligible."""
    devices = list(mesh.devices.reshape(-1))
    return [
        _stack_mesh_inputs(mesh, devices, run, -(-len(run) // len(devices)))
        for run in _mesh_runs(device_sources)
    ]


def _mesh_run(plan, nullable_cols, mesh, staged, pdyn, hv, program):
    """Execute one query's staged sources (`_mesh_stage`) on the mesh: one
    shard_map dispatch per shape run, cross-run pairwise merge, then the
    single-chip program's OWN final_jit on the first mesh device
    (device-finalize once, post-merge).  Returns the packed result
    buffers exactly as the single-chip run_all would."""
    devices = list(mesh.devices.reshape(-1))
    merged = None
    table_keys = None
    for data, positions in staged:
        prog = _mesh_merge_program(plan, nullable_cols, mesh, positions)
        out = prog(data, pdyn)
        if plan.agg_strategy == "hash":
            states, keys = out
            if merged is None:
                merged, table_keys = states, keys
            else:
                merged, table_keys = _mesh_hash_cross_program(plan)(
                    merged, table_keys, states, keys
                )
        else:
            states = out
            merged = (
                states
                if merged is None
                else _mesh_cross_merge(merged, states)
            )
    if merged is None:
        raise ValueError("mesh program received no sources")
    merged = jax.device_put(merged, devices[0])
    if table_keys is not None:
        table_keys = jax.device_put(table_keys, devices[0])
    packed = program._final_jit(merged, hv, table_keys)
    # Dispatch is ASYNC: a runtime failure in the collective program
    # would otherwise surface at fetch time, OUTSIDE the caller's degrade
    # handler, and fail a query the single chip can answer.  Settling
    # here costs nothing — the very next step is the blocking fetch —
    # and makes "any collective failure degrades" actually hold.
    jax.block_until_ready(jax.tree_util.tree_leaves(packed))
    # count the dispatch only once it SUCCEEDED: a degraded attempt must
    # not double-count against the single-chip dispatch that follows
    if not _in_fused_build():
        metrics.TPU_DEVICE_DISPATCHES.inc()
        if plan.series_slots:
            metrics.TILE_SERIES_GIDS.inc()
        if plan.partitioned:
            metrics.TILE_ORDINAL_GIDS.inc()
    if _in_flow_maintenance():
        metrics.FLOW_DEVICE_DISPATCH_TOTAL.inc()
    return packed


class _InflightFamily:
    """One in-flight device dispatch N same-family queries share: the
    leader executes, waiters block on `event` and adopt the finalized
    result (plus the leader's post_done set, so a waiter's host replay
    skips exactly the post-ops the device already applied)."""

    __slots__ = ("event", "result", "post_done", "error", "waiters")

    def __init__(self):
        self.event = threading.Event()
        self.result = None
        self.post_done = frozenset()
        self.error = None
        self.waiters = 0


class TileExecutor:
    """Aggregation over cached HBM super-tiles; returns None when not
    applicable so the caller can fall back to the authoritative path."""

    def __init__(self, cache: TileCacheManager, config):
        self.cache = cache
        self.config = config
        # program signatures already precompiled (or dispatched): warm
        # queries must not spawn background compile threads
        self._precompiled: set = set()
        self._precompile_lock = threading.Lock()
        # per-query readback attribution (transfer vs decode ms): written
        # by _finalize, read by tpu_exec.try_tile for EXPLAIN ANALYZE.
        # Thread-local, NOT a global-metric delta — concurrent queries
        # would cross-attribute each other's readback time
        self._rb_local = threading.local()
        # dispatch coalescing (admission.coalesce): family key -> the
        # in-flight dispatch concurrent same-family queries attach to
        self._coalesce_lock = threading.Lock()
        self._inflight: dict = {}
        # fused family builds (tile.fused_build): per plan-family state —
        # `served` marks families answered from host once (first touch),
        # `done` marks families whose background build completed (device
        # path warm + compiled), `builds` holds the in-flight build each
        # concurrent same-family query waits on instead of building solo
        self._fused_lock = threading.Lock()
        self._fused_served: OrderedDict = OrderedDict()
        self._fused_done: OrderedDict = OrderedDict()
        self._fused_builds: dict = {}
        self._fused_queue: list = []
        self._fused_worker_live = False
        self._fused_thread = None
        self._fused_stop = False
        # cross-query batcher (batch.window_ms): idle until the knob is
        # on AND a family is warm; holds only a lock and an open-batch map
        self._batcher = QueryBatcher(self)

    _FUSED_FAMILIES_MAX = 4096

    # -- public entry --------------------------------------------------------
    def execute(self, lowering, schema, time_bounds, ctx: TileContext):
        ghost = _in_fused_build()
        if not ghost:
            _DECLINE.reason = None
        out = self._execute(lowering, schema, time_bounds, ctx)
        if out is None and not ghost:
            # every statement the tile path gives back is counted, with
            # the check that declined it
            _DECLINE.reason = _DECLINE.reason or "the tile path does not apply"
            metrics.TILE_DECLINED.inc()
        return out

    def _execute(self, lowering, schema, time_bounds, ctx: TileContext):
        t0 = time.perf_counter()
        # device-health reaction point: drop device planes when a
        # quarantine/heal moved the generation, and bail to the scan path
        # outright when NO device is currently serving — the supervised
        # call layer would only fail-fast the dispatch anyway, and the
        # scan path answers from host memory
        self.cache.health_sync()
        sup = device_health.SUPERVISOR
        if sup.enabled and sup.all_quarantined(len(self.cache.devices)):
            flight_recorder.flag_next("device_all_quarantined")
            return _decline("every device quarantined")
        fp = None
        bc = self.cache.batch_config
        batching = (
            bc is not None
            and float(getattr(bc, "window_ms", 0) or 0) > 0
            and not _in_fused_build()
            and not _defer_fetch_active()
        )
        if (self._fused_enabled() or batching) and not _in_fused_build():
            fp = self._plan_fp(lowering, ctx)
            if fp is not None and self._fused_enabled():
                # build-side coalescing: a family whose fused build is in
                # flight WAITS and adopts the leader's planes instead of
                # running a second full build under the table lock
                self._fused_join(fp)
        adm = self.cache.admission_config
        # windowed result cache: probe BEFORE any dispatch.  The key is
        # computed once here and reused for the store below, so a write
        # landing mid-query can only strand an unreachable old-versions
        # entry — never publish a newer result under an older snapshot key
        rc = None if _in_fused_build() else self._result_cache(bc)
        ck = None
        if rc is not None:
            ck = WindowedResultCache.key_for(self, lowering, schema, ctx)
            hit = None
            if ck is not None:
                try:
                    _fault_fire(
                        "batch.result_cache", op="get", table=ctx.table_key
                    )
                    hit = rc.get(ck)
                except Exception:  # noqa: BLE001 — a failing probe is a miss
                    hit = None
            if hit is not None and not self._versions_current(ctx, ck[3]):
                # adoption-time re-validation (the purge_region race): a
                # write can land between this key's version snapshot and
                # the probe winning the cache lock; the racing purge may
                # not have dropped the entry yet.  A key whose versions
                # no longer match the LIVE region state must not serve —
                # the same snapshot-pinning rule `_family_key` applies to
                # dispatch coalescing, enforced at the cache boundary.
                hit = None
            if hit is not None:
                table, post_done = hit
                lowering.post_done = post_done
                metrics.QUERY_BATCH_RESULT_CACHE_HITS_TOTAL.inc()
                metrics.TILE_QUERY_ELAPSED.observe(time.perf_counter() - t0)
                tracing.add_event(
                    "tile.result_cache_hit", table=ctx.table_key
                )
                flight_recorder.emit_adopted(flight_recorder.DispatchRecord(
                    ts_ms=int(time.time() * 1000), table=ctx.table_key,
                    trace_id=tracing.current_trace_id() or "",
                    plan_fp=self._recorder_fp(lowering, ctx),
                    strategy="result_cache", flags=("cache_hit",),
                ))
                return table
        out = None
        ran = False
        if batching and fp is not None:
            with self._fused_lock:
                warm = fp in self._fused_done
            if warm:
                # warm family inside the batching window: pack with any
                # concurrent warm peers into one fused mega-dispatch
                out = self._batcher.submit(
                    lowering, schema, time_bounds, ctx, adm, bc
                )
                ran = True
        if not ran:
            if adm is not None and getattr(adm, "coalesce", False):
                out = self._coalesced_execute(
                    lowering, schema, time_bounds, ctx, adm
                )
            else:
                out = self._overload_safe_execute(
                    lowering, schema, time_bounds, ctx, adm
                )
        if out is not None:
            metrics.TILE_QUERY_ELAPSED.observe(time.perf_counter() - t0)
            if fp is not None:
                with self._fused_lock:
                    if fp not in self._fused_served:
                        # the device path answered without a host serve:
                        # the family is warm — stop first-touch probing
                        self._mark_fused_locked(self._fused_done, fp)
            if rc is not None and ck is not None:
                try:
                    _fault_fire(
                        "batch.result_cache", op="put", table=ctx.table_key
                    )
                    # store-time re-validation: the batch window means the
                    # key's version snapshot and the actual dispatch can be
                    # tens of ms apart (the leader SLEEPS out window_ms
                    # before executing).  A write landing in that gap makes
                    # the dispatch read NEWER data than the key claims —
                    # publishing it under the older snapshot key would let
                    # a racing adopter serve a stale/mismatched window that
                    # purge_region has no entry to drop yet.  Skip the
                    # store instead; the next aligned ask re-caches under
                    # the current versions.
                    if self._versions_current(ctx, ck[3]):
                        rc.put(ck, out, lowering.post_done)
                except Exception:  # noqa: BLE001 — a failing store keeps
                    pass  # the computed result; the cache is best-effort
        return out

    @staticmethod
    def _versions_current(ctx, versions) -> bool:
        """True when every region's (manifest version, WAL tail id) still
        matches the snapshot a result-cache key was computed from.  Used
        on BOTH cache boundaries: a store whose key predates a mid-query
        write must not publish, and a probe must not adopt an entry whose
        key no longer names the live snapshot."""
        try:
            return versions == tuple(
                (
                    r.region_id,
                    r.manifest_mgr.manifest.manifest_version,
                    r.wal.last_entry_id,
                )
                for r in ctx.regions
            )
        except Exception:  # noqa: BLE001 — unverifiable means not current
            return False

    def _result_cache(self, bc):
        """The process-wide WindowedResultCache, created lazily the first
        time batch.result_cache_mb engages (None while the knob is 0)."""
        if bc is None or int(getattr(bc, "result_cache_mb", 0) or 0) <= 0:
            return None
        rc = self.cache.result_cache
        if rc is None:
            with self._coalesce_lock:
                rc = self.cache.result_cache
                if rc is None:
                    rc = self.cache.result_cache = WindowedResultCache(
                        int(bc.result_cache_mb) << 20
                    )
        return rc

    # -- fused family builds (tile.fused_build) ------------------------------
    def _fused_enabled(self) -> bool:
        return bool(
            self.cache._tile_opt("fused_build", True)
            and passes.enabled("fused_build", self.config)
        )

    def _mark_fused_locked(self, od: OrderedDict, fp):
        od[fp] = None
        od.move_to_end(fp)
        while len(od) > self._FUSED_FAMILIES_MAX:
            od.popitem(last=False)

    @staticmethod
    def _plan_fp(lowering, ctx: TileContext):
        """Family identity WITHOUT the data-snapshot versions (unlike
        `_family_key`) and WITHOUT scan literals: plane warmth survives
        writes AND literal changes — a dashboard sliding its time window
        (or swapping the filtered host) re-uses the same family, so it
        hits the warm device path instead of host-serving (and queueing a
        fresh ghost build) on every refresh.  Filter STRUCTURE stays in
        the key: (column, op, arity) distinguishes cpu-max-all-1 from
        cpu-max-all-8; bucket geometry and post-op literals (LIMIT/HAVING
        bounds) are structural and stay too."""
        try:
            scan = lowering.scan
            scan_fp = (
                scan.table,
                scan.database,
                None if scan.projection is None else tuple(scan.projection),
                tuple(
                    (
                        f[0], f[1],
                        len(f[2])
                        if isinstance(f[2], (list, tuple, set, frozenset))
                        else None,
                    )
                    for f in scan.filters
                ),
                # window SHAPE (bounded below / above), not its literals
                scan.time_range is not None
                and scan.time_range[0] > -(1 << 61),
                scan.time_range is not None
                and scan.time_range[1] < (1 << 61),
            )
            plan_fp = repr((
                scan_fp, tuple(lowering.group_tags), lowering.bucket,
                tuple(lowering.agg_specs), lowering.group_exprs,
                lowering.agg_exprs,
                tuple(TileExecutor._post_op_fp(op) for op in lowering.post_ops),
            ))
        except Exception:  # noqa: BLE001 — fingerprinting is best-effort
            return None
        return (ctx.table_key, ctx.append_mode, plan_fp)

    def _fused_first_touch(self, lowering, ctx: TileContext) -> bool:
        """True when this query's family has never been served nor built:
        the widened cold-serve router answers from host and schedules the
        background fused build."""
        if _in_fused_build() or not self._fused_enabled():
            return False
        fp = self._plan_fp(lowering, ctx)
        if fp is None:
            return False
        with self._fused_lock:
            return (
                fp not in self._fused_served
                and fp not in self._fused_done
                and fp not in self._fused_builds
            )

    def _fused_join(self, fp):
        """Wait out an in-flight fused build of this family (deadline-
        aware).  On leader failure the caller simply proceeds and builds
        solo under its own budget."""
        with self._fused_lock:
            rec = self._fused_builds.get(fp)
        if rec is None:
            return
        metrics.TILE_BUILD_COALESCED.inc()
        tracing.add_event("tile.build_coalesced", table=fp[0])
        deadline = current_deadline()
        while not rec.event.is_set():
            timeout = None if deadline is None else deadline - time.monotonic()
            if timeout is not None and timeout <= 0:
                check_deadline()
            rec.event.wait(timeout if timeout is None else max(timeout, 0.01))

    def _fused_schedule(
        self, lowering, schema, time_bounds, ctx: TileContext, manifest
    ):
        """Record the family's manifest and queue its background build;
        the worker thread consolidates every queued manifest into one
        fused pass, then primes each family's compile + dispatch."""
        import copy

        fp = self._plan_fp(lowering, ctx)
        if fp is None:
            self.cache.record_manifest(manifest)
            return
        ghost = copy.copy(lowering)
        ghost.post_done = frozenset()
        self._fused_enqueue(_FusedItem(
            fp=fp, rec=None, lowering=ghost, schema=schema,
            time_bounds=time_bounds, ctx=ctx, manifest=manifest,
        ))

    def fused_schedule_custom(self, fp, manifest, ctx: TileContext, schema,
                              run):
        """Schedule a NON-SQL family build (the TQL tile path): same
        manifest recording, same consolidated union pass, same build
        coalescing/bookkeeping — but the per-family ghost execution is
        the caller's `run` callable instead of a lowering replay."""
        self._fused_enqueue(_FusedItem(
            fp=fp, rec=None, lowering=None, schema=schema,
            time_bounds=None, ctx=ctx, manifest=manifest, run=run,
        ))

    def fused_first_touch_fp(self, fp) -> bool:
        """True when `fp` has never been served, built nor queued."""
        with self._fused_lock:
            return (
                fp not in self._fused_served
                and fp not in self._fused_done
                and fp not in self._fused_builds
            )

    def _fused_enqueue(self, item: _FusedItem):
        self.cache.record_manifest(item.manifest)
        spawn = False
        with self._fused_lock:
            self._mark_fused_locked(self._fused_served, item.fp)
            if (
                self._fused_stop
                or item.fp in self._fused_builds
                or item.fp in self._fused_done
            ):
                return
            if len(self._fused_queue) >= 128:
                # backstop only: families are literal-insensitive, so a
                # workload cannot mint unbounded distinct fps — but a
                # pathological one must degrade to the legacy ladder, not
                # an unbounded build queue
                return
            item.rec = self._fused_builds[item.fp] = _FamilyBuild()
            self._fused_queue.append(item)
            if not self._fused_worker_live:
                self._fused_worker_live = True
                self._fused_thread = threading.Thread(
                    target=self._fused_worker, name="tile-fused-build",
                    daemon=True,
                )
                spawn = True
        if spawn:
            self._fused_thread.start()

    def _fused_worker(self):
        """Background fused builder: drains queued family builds in
        batches — ONE consolidated union build per table (decode once,
        encode once, one batched upload), then a ghost execution per
        family that compiles + primes its dispatch so waiters and warm
        reps hit a fully-built path."""
        from ..utils.deadline import deadline_scope

        log = logging.getLogger("greptimedb_tpu.tile")
        timeout_s = float(
            self.cache._tile_opt("fused_build_timeout_s", 900.0)
        )
        while True:
            with self._fused_lock:
                items, self._fused_queue = self._fused_queue, []
                if not items or self._fused_stop:
                    for it in items:  # shutdown drain: wake waiters
                        it.rec.error = RuntimeError("fused builder stopped")
                        self._fused_builds.pop(it.fp, None)
                    self._fused_worker_live = False
                    for it in items:
                        it.rec.event.set()
                    return
            by_table: dict[str, list] = {}
            for it in items:
                by_table.setdefault(it.ctx.table_key, []).append(it)
            for tkey, group in by_table.items():
                # the union pass is shared; coalesce with prewarm (and any
                # concurrent builder) through the per-table build gate
                try:
                    with deadline_scope(timeout_s):
                        with fused_build_scope():
                            _fault_fire("tile.fused_build", table=tkey)
                            manifests = list(dict.fromkeys(
                                self.cache.family_manifests(tkey)
                                + [it.manifest for it in group]
                            ))
                            with self.cache.build_gate(tkey) as leader:
                                if leader:
                                    self.cache.fused_union_build(
                                        group[0].ctx, group[0].schema,
                                        manifests,
                                    )
                except BaseException:  # noqa: BLE001 — per-family ghosts
                    # below still run; they rebuild what the union missed
                    log.warning(
                        "fused union build failed for %s", tkey,
                        exc_info=True,
                    )
                for it in group:
                    err = None
                    try:
                        with deadline_scope(timeout_s):
                            with fused_build_scope():
                                _fault_fire(
                                    "tile.fused_build", table=tkey,
                                    phase="ghost",
                                )
                                if it.run is not None:
                                    it.run()
                                else:
                                    self._overload_safe_execute(
                                        it.lowering, it.schema,
                                        it.time_bounds, it.ctx,
                                        self.cache.admission_config,
                                    )
                    except BaseException as e:  # noqa: BLE001 — waiters
                        # must never inherit a builder-side verdict
                        err = e
                        log.warning(
                            "fused family build failed for %s", tkey,
                            exc_info=True,
                        )
                    with self._fused_lock:
                        it.rec.error = err
                        if err is None:
                            self._mark_fused_locked(self._fused_done, it.fp)
                        self._fused_builds.pop(it.fp, None)
                    it.rec.event.set()

    def shutdown_fused(self, timeout: float = 5.0):
        """Stop the background builder (Database.close): pending builds
        are abandoned and their waiters woken with an error so nobody
        blocks on a build that will never run."""
        with self._fused_lock:
            self._fused_stop = True
            items, self._fused_queue = self._fused_queue, []
            for it in items:
                it.rec.error = RuntimeError("fused builder stopped")
                self._fused_builds.pop(it.fp, None)
            t = self._fused_thread
        for it in items:
            it.rec.event.set()
        if t is not None and t.is_alive():
            t.join(timeout)

    # -- overload survival ---------------------------------------------------
    def _overload_safe_execute(self, lowering, schema, time_bounds, ctx, adm):
        """`_try_execute` under the closed HBM feedback loop
        (admission.hbm_retry): a RESOURCE_EXHAUSTED that survived the
        dispatch-site emergency retry triggers emergency release + a
        halve-chunk rebuild, so forced overcommit degrades to smaller
        dispatches instead of a failed query.  Off (hbm_retry=False) the
        error propagates exactly as before this layer existed."""
        try:
            return self._try_execute(lowering, schema, time_bounds, ctx)
        except Exception as exc:  # noqa: BLE001 — only OOM enters the loop
            if (
                adm is None
                or not getattr(adm, "hbm_retry", False)
                or "RESOURCE_EXHAUSTED" not in str(exc)
            ):
                raise
            last = exc
        log = logging.getLogger("greptimedb_tpu.tile")
        for attempt in range(max(int(adm.hbm_retry_attempts), 1)):
            metrics.HBM_EXHAUSTED_TOTAL.inc()
            halved = self.cache.degrade_chunks(int(adm.min_chunk_rows))
            self.cache.emergency_release(set())
            # the retried _try_execute opens a fresh recorder scope; arm
            # its degraded flag now (this thread re-enters immediately)
            flight_recorder.flag_next("degraded")
            # degrade rounds are events on the statement's trace, so an
            # OOM-surviving query shows every halve-and-retry rung
            tracing.add_event(
                "hbm.degrade",
                attempt=attempt + 1,
                chunk_rows=self.cache.chunk_rows,
                halved=halved,
            )
            log.warning(
                "device OOM survived emergency retry: chunk_rows -> %d "
                "(attempt %d/%d), rebuilding with smaller dispatches",
                self.cache.chunk_rows, attempt + 1, adm.hbm_retry_attempts,
            )
            try:
                return self._try_execute(lowering, schema, time_bounds, ctx)
            except Exception as exc:  # noqa: BLE001 — classified below
                if "RESOURCE_EXHAUSTED" not in str(exc):
                    raise
                last = exc
                if not halved:
                    break  # at the floor and still exhausted: surface it
        raise last

    # -- dispatch coalescing -------------------------------------------------
    @staticmethod
    def _post_op_fp(op):
        """Full-fidelity fingerprint of one post-op plan node.  Plan-node
        __repr__s are display-oriented and LOSSY — Sort omits its nulls
        (NULLS FIRST/LAST) field, Having/Project render exprs via name()
        — so two queries differing only there would falsely coalesce and
        a waiter would adopt the wrong ordering.  Fingerprint the fields
        themselves instead (Exprs are frozen dataclasses whose default
        reprs carry every field); `input` is the child subtree, already
        covered by the scan/group/agg parts of the family key."""
        return (
            type(op).__name__,
            repr({
                f.name: getattr(op, f.name)
                for f in dataclasses.fields(op)
                if f.name != "input"
            }),
        )

    @staticmethod
    def _family_key(lowering, ctx: TileContext):
        """Identity of a query family AND its data snapshot: two queries
        coalesce only when the logical plan fingerprints match and no
        region took a write/flush/compaction between them (manifest
        version covers flush/compaction, the WAL tail id covers memtable
        writes) — a waiter's result must be bit-identical to a solo run.
        None = not fingerprintable, run solo."""
        try:
            versions = tuple(
                (
                    r.region_id,
                    r.manifest_mgr.manifest.manifest_version,
                    r.wal.last_entry_id,
                )
                for r in ctx.regions
            )
            plan_fp = repr((
                lowering.scan, tuple(lowering.group_tags), lowering.bucket,
                tuple(lowering.agg_specs), lowering.group_exprs,
                lowering.agg_exprs,
                tuple(TileExecutor._post_op_fp(op) for op in lowering.post_ops),
            ))
        except Exception:  # noqa: BLE001 — fingerprinting is best-effort
            return None
        return (ctx.table_key, ctx.append_mode, plan_fp, versions)

    def _coalesced_execute(self, lowering, schema, time_bounds, ctx, adm):
        """Shared-data-path across concurrent queries: the first arrival
        of a (family, snapshot) becomes the LEADER and runs the dispatch;
        later arrivals attach as WAITERS to the same in-flight future and
        adopt the finalized result instead of serializing a duplicate
        dispatch behind the table lock (the GPU data-path fusion idea
        applied across queries instead of across operators)."""
        key = self._family_key(lowering, ctx)
        if key is None:
            return self._overload_safe_execute(lowering, schema, time_bounds, ctx, adm)
        with self._coalesce_lock:
            rec = self._inflight.get(key)
            leader = rec is None
            if leader:
                rec = self._inflight[key] = _InflightFamily()
            else:
                rec.waiters += 1
        if leader:
            # leader: execute, publish, wake the coalition
            try:
                out = self._overload_safe_execute(
                    lowering, schema, time_bounds, ctx, adm
                )
                rec.result = out
                rec.post_done = lowering.post_done
                return out
            except BaseException as exc:
                rec.error = exc
                raise
            finally:
                with self._coalesce_lock:
                    self._inflight.pop(key, None)
                    had_waiters = rec.waiters
                if had_waiters:
                    metrics.DISPATCH_COALESCE_LEADERS_TOTAL.inc()
                rec.event.set()
        # waiter: attach to the leader's in-flight dispatch
        _fault_fire("dispatch.coalesce", table=ctx.table_key)
        deadline = current_deadline()
        while not rec.event.is_set():
            timeout = None if deadline is None else deadline - time.monotonic()
            if timeout is not None and timeout <= 0:
                check_deadline()  # the waiter's own budget owns its fate
            rec.event.wait(timeout)
        if rec.error is not None:
            # the leader's failure may be its own (deadline, injected
            # fault): run solo under this query's budget instead of
            # inheriting a verdict that may not apply
            return self._overload_safe_execute(
                lowering, schema, time_bounds, ctx, adm
            )
        if rec.result is not None:
            metrics.DISPATCH_COALESCED_TOTAL.inc()
            tracing.add_event("dispatch.coalesced", table=ctx.table_key)
            lowering.post_done = rec.post_done
            # the waiter ran no dispatch of its own: record the adoption
            # so per-query views show WHERE the time went (waiting on the
            # leader's in-flight dispatch, not a duplicate one)
            if flight_recorder.RECORDER.enabled:
                flight_recorder.RECORDER.emit(flight_recorder.DispatchRecord(
                    ts_ms=int(time.time() * 1000), table=ctx.table_key,
                    trace_id=tracing.current_trace_id() or "",
                    plan_fp=self._recorder_fp(lowering, ctx),
                    strategy="coalesced", flags=("coalesced",),
                ))
        return rec.result

    def _recorder_fp(self, lowering, ctx: TileContext) -> str:
        """Short stable plan-family fingerprint for the flight recorder
        (12 hex chars of the literal-insensitive `_plan_fp`)."""
        fp = self._plan_fp(lowering, ctx)
        if fp is None:
            return ""
        import hashlib

        return hashlib.sha1(repr(fp).encode()).hexdigest()[:12]

    def _try_execute(self, lowering, schema, time_bounds, ctx: TileContext):
        if not flight_recorder.RECORDER.enabled:
            # recorder off = no fingerprint assembly, no draft: the
            # documented off-cost is this one flag read
            return self._try_execute_impl(lowering, schema, time_bounds, ctx)
        with flight_recorder.dispatch_scope(
            table=ctx.table_key,
            plan_fp=self._recorder_fp(lowering, ctx),
            ghost=_in_fused_build(),
            hbm=lambda: (self.cache._used, self.cache.budget),
        ):
            return self._try_execute_impl(lowering, schema, time_bounds, ctx)

    def _try_execute_impl(self, lowering, schema, time_bounds, ctx: TileContext):
        scan = lowering.scan
        ts_name = schema.time_index.name if schema.time_index else None
        tag_cols = list(lowering.group_tags)
        # tag-typed filter columns also need code tiles
        tag_names = {c.name for c in schema.tag_columns()}
        filter_tag_cols = [
            f[0] for f in scan.filters if f[0] in tag_names and f[0] not in tag_cols
        ]
        value_cols = list(
            dict.fromkeys(
                [c for _f, c in lowering.agg_specs if c is not None]
                + [
                    f[0]
                    for f in scan.filters
                    if f[0] not in tag_names and f[0] != ts_name
                ]
            )
        )
        needs_ts = (
            lowering.bucket is not None
            or any(f == "last_value" for f, _ in lowering.agg_specs)
            or scan.time_range is not None
            or any(f[0] == ts_name for f in scan.filters)
        )
        use_ts = ts_name if (needs_ts and ts_name) else None
        # a plan that groups by series reads each series' key codes off
        # the region's series table, and a memtable tail's off its own
        # rows: the series key is encoded, though no key plane is uploaded
        pk = [c.name for c in schema.tag_columns()]
        series_key = _series_key(
            pk, tag_cols, lowering.bucket is not None, _partitioned(ctx, pk)
        )
        needs_last = any(f == "last_value" for f, _ in lowering.agg_specs)
        if needs_last and series_key is not None and set(tag_cols) != set(series_key):
            # LAST states cannot fold series together (only permute) —
            # bail BEFORE pinning/encoding
            return _decline("last_value over groups of several series")
        if needs_last and lowering.bucket is not None and not tag_cols:
            return _decline("last_value has no time-major variant")
        extra_tag_cols = [
            t for t in series_key or ()
            if t not in tag_cols and t not in filter_tag_cols
        ]
        all_tag_cols = tag_cols + filter_tag_cols + extra_tag_cols

        # 1. snapshot + safety gate, pinning every region until dispatch
        # done.  The table's dictionary gate serializes the whole
        # epoch-sensitive section (tile fetch -> repair -> memtable encode
        # -> plan build -> arg pack): without it a concurrent query could
        # grow the dictionary and repair SHARED tile entries between our
        # phases, mixing code epochs inside one dispatch.
        if any(
            getattr(r, "merge_mode", "last_row") == "last_non_null"
            for r in ctx.regions
        ) and not ctx.append_mode:
            # fieldwise (last_non_null) merging is not a per-row no-op even
            # over disjoint sources when the memtable holds partial-null
            # versions — the authoritative scan path owns this mode
            return _decline("last_non_null merge mode")
        pinned_regions: list[Region] = []
        with ctx.dictionary.table_lock:
            try:
                return self._locked_execute(
                    lowering, schema, scan, ctx, time_bounds, pinned_regions,
                    ts_name, tag_names, tag_cols, all_tag_cols, value_cols, use_ts,
                    series_key,
                )
            finally:
                for region in pinned_regions:
                    region.unpin_scan()

    def _locked_execute(
        self, lowering, schema, scan, ctx, time_bounds, pinned_regions,
        ts_name, tag_names, tag_cols, all_tag_cols, value_cols, use_ts,
        series_key,
    ):
        # Eligibility is judged on the sources that INTERSECT the query's
        # time window: the super-tile spans every file, but rows outside
        # the window are masked out on device, so overlap/tombstones in
        # out-of-window history cannot affect this query's result — a
        # windowed query over disjoint recent files stays on the tile path
        # even when old compacted files overlap each other.
        window = scan.time_range if scan.time_range is not None else None

        def in_window(lo: int, hi: int) -> bool:
            if window is None:
                return True
            wlo, whi = window
            return hi >= wlo and lo < whi

        region_sources = []  # (region, [FileMeta], [mem pa.Table])
        dedup_regions: set[int] = set()  # regions whose files overlap
        for region in ctx.regions:
            region.pin_scan()
            pinned_regions.append(region)
            all_files, mems, version = region.tile_snapshot()
            # drop cached tiles of files compaction removed — but only
            # when the manifest actually changed since the last sweep
            self.cache.invalidate_region_if_changed(
                region.region_id, {m.file_id for m in all_files}, version
            )
            file_ranges: list[tuple[int, int]] = []
            mem_ranges: list[tuple[int, int]] = []
            mem_tables = []
            for meta in all_files:
                if not in_window(*meta.time_range):
                    continue
                if meta.num_deletes != 0:
                    # tombstones (or unknown) -> dedup needed
                    return _decline("tombstones in the window")
                file_ranges.append(meta.time_range)
            for mem in mems:
                mem_table = mem.scan(None, dedup=not ctx.append_mode)
                if mem_table.num_rows == 0:
                    continue
                if OP_COL in mem_table.column_names:
                    op_rows = mem_table
                    if window is not None and ts_name in mem_table.column_names:
                        ts_i = pc.cast(mem_table[ts_name], pa.int64())
                        sel = pc.and_(
                            pc.greater_equal(ts_i, window[0]),
                            pc.less(ts_i, window[1]),
                        )
                        op_rows = mem_table.filter(sel)
                    if (
                        op_rows.num_rows
                        and pc.sum(
                            pc.fill_null(pc.cast(op_rows[OP_COL], pa.int64()), 0)
                        ).as_py()
                    ):
                        return _decline("tombstones in the window")
                    mem_table = mem_table.drop_columns([OP_COL])
                if ts_name and ts_name in mem_table.column_names:
                    ts_i = pc.cast(mem_table[ts_name], pa.int64())
                    mlo, mhi = pc.min(ts_i).as_py(), pc.max(ts_i).as_py()
                    if not in_window(mlo, mhi):
                        continue  # fully out of window: skip the encode
                    mem_ranges.append((mlo, mhi))
                else:
                    mem_ranges.append((0, 0))
                mem_tables.append(mem_table)
            if not ctx.append_mode:
                # A memtable version of a row always BEATS file versions
                # and other memtables hold later writes still — those
                # cross-source merges stay on the authoritative scan path,
                # so any memtable time-overlap bails.  FILE-only overlap
                # within a region is handled on-device: the keep plane
                # (ensure_dedup_keep) makes dedup a mask, so out-of-order
                # and overwrite ingest keeps the TPU path (the round-3
                # gate silently fell back to the CPU scan here).
                # Cross-REGION overlap needs nothing: the partition rule
                # puts each pk in exactly one region.
                if mem_ranges and not _disjoint(mem_ranges + file_ranges):
                    if not _disjoint(mem_ranges):
                        return _decline("memtables overlap in time")
                    for mr in mem_ranges:
                        if any(
                            fr[1] >= mr[0] and fr[0] <= mr[1]
                            for fr in file_ranges
                        ):
                            return _decline("a memtable overlaps the files in time")
                if not _disjoint(file_ranges):
                    dedup_regions.add(region.region_id)
            region_sources.append((region, all_files, mem_tables))
        if not any(fs or ms for _r, fs, ms in region_sources):
            # empty table: let the normal path shape output
            return _decline("empty table")

        # 2. phase A — every dictionary mutation happens BEFORE the plan
        # is built: memtable values first (cheap), then per-file host
        # encodes inside super_tiles (cached after the first query)
        for _region, _metas, mem_tables in region_sources:
            for mt in mem_tables:
                ctx.dictionary.update_table(mt, all_tag_cols)
        pinned_ids = {r.region_id for r, _f, _m in region_sources}
        pk = [c.name for c in schema.tag_columns()]
        # Limb-only columns skip the f64 device upload entirely: their
        # aggregation reads quantized limb planes (same 8 B/row), so
        # uploading both representations would double value-column HBM —
        # at TSBS 3-day scale that alone exceeds device memory.  A column
        # stays on the f64 plane when any query shape still needs raw
        # values: min/max/last, value filters, nullable columns (the null
        # plane rides the f64 upload), or time-major plans (tm copies
        # gather from the f64 plane).
        per_col_funcs: dict[str, set] = {}
        for f, c in lowering.agg_specs:
            if c is not None:
                per_col_funcs.setdefault(c, set()).add(_FUNC_TO_KERNEL[f])
        filter_col_names = {f[0] for f in scan.filters}
        time_major_probe = (
            lowering.bucket is not None
            and not lowering.group_tags
            and series_key is None  # same probe _try_execute computed
        )
        # agg-strategy probe runs BEFORE limb decisions: a hash plan
        # accumulates exact f64, so its value columns must keep their f64
        # plane uploads (skipping them would strand the query)
        agg_probe = self._choose_agg_strategy(
            lowering, schema, scan, ctx, tag_cols, time_bounds
        )
        # a series plan reads its groups off the series tables: of the tag
        # planes, only those its filters compare reach the device
        if series_key is not None and agg_probe is None:
            device_tag_cols = [t for t in all_tag_cols if t in filter_col_names]
        else:
            series_key = None
            device_tag_cols = all_tag_cols
        limb_skip_upload: set[str] = set()
        if (
            self.config_acc_dtype() == "limb"
            and not time_major_probe
            and agg_probe is None
        ):
            for c, funcs in per_col_funcs.items():
                if (
                    funcs & {"sum", "avg"}
                    and not funcs & {"min", "max", "last"}
                    and c not in filter_col_names
                    and schema.has_column(c)
                    and not schema.column(c).nullable
                ):
                    limb_skip_upload.add(c)
        has_sum_avg = any(
            funcs & {"sum", "avg"} for funcs in per_col_funcs.values()
        )
        if agg_probe is not None and has_sum_avg:
            passes.note(
                "limb_quantize", False,
                "hash agg strategy accumulates exact f64 (hashed slot ids "
                "defeat the limb block geometry)",
            )
        elif self.config_acc_dtype() == "limb" and has_sum_avg:
            passes.note(
                "limb_quantize", True,
                "sum/avg accumulate via MXU fixed-point limb matmuls",
                f64_upload_skipped=len(limb_skip_upload),
            )
        elif has_sum_avg:
            passes.note(
                "limb_quantize", False,
                "exact float accumulation (disabled or configured off)",
            )
        else:
            passes.note(
                "limb_quantize", False,
                "no sum/avg aggregate: compare/count kernels only",
            )
        device_value_cols = [c for c in value_cols if c not in limb_skip_upload]

        # Region-streamed spill: a working set the budget cannot hold
        # all-at-once (the 1B-row trajectory) executes region-by-region —
        # the all-at-once build below would evict its own planes mid-query
        # and thrash (or OOM outright)
        if (
            getattr(self.config, "tile_stream_enable", True)
            and passes.enabled("stream_spill", self.config)
        ):
            limb_est = (
                [c for c, f in per_col_funcs.items() if f & {"sum", "avg"}]
                if self.config_acc_dtype() == "limb"
                else []
            )
            est_dev = 0
            # ... and by mesh slot: under the mesh path a region lies on
            # its own chip, and the budget is every chip's own share
            mesh_n = self.cache.mesh_devices()
            est_slot: dict[int, int] = {}
            total_rows = 0
            win_rows = 0
            for region_i, metas_i, _mems in region_sources:
                rows_i = sum(m.num_rows for m in metas_i)
                if not rows_i:
                    continue
                total_rows += rows_i
                win_rows += sum(
                    m.num_rows for m in metas_i if in_window(*m.time_range)
                )
                per_row = 1 + (8 if use_ts else 0)
                per_row += 4 * len(set(device_tag_cols))
                per_row += 8 * len(device_value_cols)
                per_row += 8 * len(limb_est)
                est_dev += padded_size(rows_i) * per_row
                slot = (
                    region_device_index(region_i.region_id, mesh_n)
                    if mesh_n > 1 else 0
                )
                est_slot[slot] = est_slot.get(slot, 0) + padded_size(rows_i) * per_row
            threshold = getattr(self.config, "tile_stream_threshold", 0.6)
            # A bounded window that the compact window-tile path can serve
            # (cover under ~half the retention) manages its own HBM —
            # streaming would upload FULL planes for rows the gather
            # skips.  Stream only when the query really touches most of a
            # beyond-budget working set.
            window_served = (
                window is not None
                and window[0] > -(1 << 61)
                and window[1] < (1 << 61)  # half-bounded windows cannot
                # take the window-tile branch below — stream those
                and passes.enabled("window_tile", self.config)
                and total_rows > 0
                and win_rows <= 0.55 * total_rows
            )
            if max(est_slot.values(), default=0) > threshold * self.cache.budget and not window_served:
                # the streamed path releases each region's planes right
                # after folding its partials: its fetches must stay
                # eager even under a batch leader's deferred-fetch scope
                with _defer_fetch_suppressed():
                    streamed = self._streamed_execute(
                        lowering, schema, scan, ctx, time_bounds,
                        region_sources, dedup_regions, ts_name, tag_cols,
                        all_tag_cols, value_cols, use_ts,
                        device_value_cols, pinned_ids, pk, window,
                        in_window, est_dev,
                    )
                if streamed is not None:
                    return streamed
                # shape not streamable (dedup/time-major/bail): the
                # all-at-once build below still applies its own gates;
                # phase-A host encodes are RAM-cached, nothing is wasted

        super_entries: list[_SuperTiles] = []
        slots: list = []
        for region, metas, mem_tables in region_sources:
            if metas:
                # sort/encode with the SCHEMA time index even when this
                # query doesn't touch ts: the entry is shared across
                # queries, and one built by a ts-free query must still
                # carry the (pk, ts) order + sorted ts the host fast path
                # and blocked-kernel layout of later queries rely on.
                # The f64-upload skip only pays off (and the limb
                # geometry only holds) for regions big enough that every
                # chunk meets the limb fast-path floor.
                big = padded_size(
                    max(sum(m.num_rows for m in metas), 1)
                ) >= _LIMB_MIN_ROWS
                # host-only first: consolidation + sorted planes, NO
                # uploads — the cold-serve router below may answer from
                # host and skip the (link-dominated) plane uploads
                entry, excluded = self.cache.super_tiles(
                    region, ctx.dictionary, metas, device_tag_cols,
                    ts_name or use_ts,
                    device_value_cols if big else value_cols,
                    pinned_ids, pk, device_upload=False,
                )
                # a file that cannot join the super-tile only blocks
                # queries whose window its rows could affect
                for meta in excluded:
                    if in_window(*meta.time_range):
                        return _decline("a file in the window cannot join the planes")
                if entry is not None:
                    super_entries.append(entry)
                    slots.append(entry)
            for mt in mem_tables:
                slots.append((region, mt))
        if not slots:
            return _decline("nothing in the window")

        # 3. phase B — dictionary is final for this query: repair stale
        # device tiles with one gather; a series plan reads its series off
        # the repaired sorted host codes
        self.cache.repair_super(super_entries, ctx.dictionary, all_tag_cols)
        series_tables: dict[int, SeriesTable] = {}
        for entry in super_entries if series_key is not None else ():
            # the table of the whole key (shared with the PromQL path); a
            # prefix's series are runs of its series
            table = self.cache.series_table(entry, ctx.dictionary, pk)
            if table is None:
                return _decline("series table: the sorted host planes are gone")
            if len(series_key) < len(pk):
                table = table.prefix(len(series_key))
            series_tables[entry.region_id] = table

        # 4. the static plan (cards AFTER all dictionary updates) plus
        # its runtime-dynamic parameters (filter literals, bucket
        # geometry) — changing a literal or window reuses the compile
        built = self._build_plan(
            lowering, schema, scan, ctx, tag_cols, time_bounds, use_ts,
            agg_probe=agg_probe,
            n_series=max((len(t) for t in series_tables.values()), default=1),
        )
        if built is None:
            return _decline("no tile plan for the statement")
        plan, dyn_host, fspec = built
        if plan.agg_strategy == "hash":
            # the dense [G] space never materializes — only the slot
            # table must fit, and _size_hash_slots already clamps it to
            # the internal-groups bound (this is what lets group spaces
            # past max_groups stay on the device path at all)
            pass
        else:
            if plan.num_groups > self.config.max_groups * 64:
                # group space too large for dense [G] states
                return _decline(
                    f"max_groups: {plan.num_groups} groups over "
                    f"{self.config.max_groups * 64}"
                )
            if plan.internal_groups > self.config.max_internal_groups:
                return _decline(
                    f"max_internal_groups: {plan.internal_groups} stage-1 "
                    f"groups over {self.config.max_internal_groups}"
                )

        # 4.5 host fast path: a highly selective pk-equality query (TSBS
        # single-groupby / cpu-max-all / high-cpu-1 shapes) binary-searches
        # the (pk, ts)-sorted host copies and aggregates the tiny slice
        # with numpy — no device link round-trip at all.  The reference
        # serves these through its inverted index + page pruning; here the
        # sorted encode cache plays that role.
        host_table = None
        host_hints: dict = {}
        dense_host_ok = plan.num_groups <= self.config.max_groups * 64
        hfp_enabled = (
            passes.enabled("host_fast_path", self.config)
            and dense_host_ok
            # the fused builder's ghost execution must actually BUILD: a
            # host serve inside it would leave the family cold forever
            and not _in_fused_build()
        )
        if hfp_enabled:
            host_table = self._host_execute(
                plan, dyn_host, super_entries,
                [s for s in slots if not isinstance(s, _SuperTiles)],
                schema, ctx, use_ts, pk, value_cols, all_tag_cols,
                dedup_regions, hints=host_hints,
            )
        if host_table is not None:
            metrics.TILE_LOWERED_TOTAL.inc()
            metrics.TILE_HOST_FAST_PATH.inc()
            flight_recorder.note(strategy="host", build_mode="host_fast")
            flight_recorder.mark()
            if host_hints.get("wide_cold") and self._fused_first_touch(
                lowering, ctx
            ):
                # wide multi-key slice served cold from host because its
                # device planes aren't resident: warm them in the
                # background so warm reps take the flat tile dispatch
                # (the cpu-max-all-8 contention fix needs WARM planes)
                manifest = PlaneManifest(
                    table_key=ctx.table_key,
                    tag_cols=tuple(device_tag_cols),
                    ts_col=use_ts,
                    value_cols=tuple(value_cols),
                    limb_cols=tuple(self._limb_sum_cols(plan)),
                    time_major=bool(plan.time_major),
                    dedup=bool(dedup_regions),
                )
                self._fused_schedule(
                    lowering, schema, time_bounds, ctx, manifest
                )
            passes.note(
                "host_fast_path", True,
                "pk-equality slice served from sorted host planes",
                rows_out=host_table.num_rows,
            )
            return host_table
        passes.note(
            "host_fast_path", False,
            "query not selective enough for the sorted-host binary search"
            if hfp_enabled else "pass disabled",
        )

        # 4.6 cold grouped serve.  Legacy ladder (tile.fused_build=false):
        # device planes not built yet -> answer from the host
        # consolidation once per entry, dense group bound only.  Fused
        # ladder: EVERY family's first touch answers from the host pass
        # (last_value, hash-scale spaces, chunk-parallel folds) and the
        # fused family build warms device planes in the background.
        fused_serve = self._fused_first_touch(lowering, ctx)
        cold_table = None
        if (dense_host_ok or fused_serve) and not _in_fused_build():
            cold_table = self._host_cold_grouped(
                plan, dyn_host, super_entries,
                [s for s in slots if not isinstance(s, _SuperTiles)],
                ctx, use_ts, value_cols, all_tag_cols, dedup_regions, window,
                fused=fused_serve,
            )
        if cold_table is not None:
            metrics.TILE_LOWERED_TOTAL.inc()
            metrics.TILE_COLD_SERVES.inc()
            flight_recorder.note(strategy="host", build_mode="cold_serve")
            flight_recorder.mark()
            if fused_serve:
                win_manifest = None
                if (
                    not plan.time_major
                    and window is not None
                    and use_ts
                    and window[0] > -(1 << 61)
                    and window[1] < (1 << 61)
                    and passes.enabled("window_tile", self.config)
                ):
                    win_manifest = (int(window[0]), int(window[1]))
                manifest = PlaneManifest(
                    table_key=ctx.table_key,
                    tag_cols=tuple(device_tag_cols),
                    ts_col=use_ts,
                    value_cols=tuple(dict.fromkeys(
                        list(device_value_cols)
                        + [c for c in value_cols if c in limb_skip_upload]
                    )) if win_manifest is not None
                    else tuple(device_value_cols),
                    limb_cols=tuple(self._limb_sum_cols(plan)),
                    time_major=bool(plan.time_major),
                    window=win_manifest,
                    dedup=bool(dedup_regions),
                )
                self._fused_schedule(
                    lowering, schema, time_bounds, ctx, manifest
                )
                passes.note(
                    "fused_build", True,
                    "family manifest recorded; fused background build "
                    "scheduled (waiters coalesce onto it)",
                    window=bool(win_manifest),
                    time_major=bool(plan.time_major),
                )
                passes.note(
                    "cold_host_serve", True,
                    "grouped aggregate served from the host consolidation "
                    "while the fused family build warms device planes in "
                    "the background",
                    rows_out=cold_table.num_rows, fused=True,
                )
            else:
                passes.note(
                    "cold_host_serve", True,
                    "grouped aggregate served from the host consolidation; "
                    "device tiles build on the next touch",
                    rows_out=cold_table.num_rows,
                )
            return cold_table

        # pipelined cold path, stage 3: start the tile program's jit
        # trace/compile from shape metadata ALONE, in the background —
        # XLA compiles (into the persistent compilation cache) while the
        # plane uploads below are still crossing the link, instead of
        # serializing encode -> upload -> compile
        if (
            super_entries
            and plan.agg_strategy != "hash"  # hash partials thread the
            # key table; shape-only precompile doesn't model it
            and not plan.series_slots  # nor a series plan's inputs
            and self.cache._tile_opt("pipelined_build", True)
            and passes.enabled("pipelined_build", self.config)
            # it compiles the single-chip partial program, which a mesh
            # dispatch never runs
            and self.cache.mesh_devices() == 0
        ):
            self._precompile_async(
                plan, fspec, super_entries[0], dyn_host,
                tag_names | set(pk), ts_name, limb_skip_upload,
            )

        # device path: upload the planes the host-only build deferred
        # (warm entries hit the cache and return immediately).  Under the
        # fused planner the upload is LAZY per region: a region whose
        # window tile serves the query never uploads its full planes at
        # all (pre-fused, a 12 h windowed query paid the full hostname+ts
        # plane uploads it then ignored) — deferred_upload carries the
        # regions still pending, resolved inside the slots loop.
        deferred_upload: dict[int, tuple] = {}
        lazy = self._fused_enabled()
        for region, metas, _mems in region_sources:
            if not metas:
                continue
            if lazy:
                deferred_upload[region.region_id] = (region, metas)
                continue
            big = padded_size(
                max(sum(m.num_rows for m in metas), 1)
            ) >= _LIMB_MIN_ROWS
            entry, _excluded = self.cache.super_tiles(
                region, ctx.dictionary, metas, device_tag_cols,
                ts_name or use_ts,
                device_value_cols if big else value_cols,
                pinned_ids, pk,
            )
            if entry is None:
                return _decline("no planes for a region")

        device_sources = []
        limb_need = self._limb_sum_cols(plan)
        for s in slots:
            if isinstance(s, _SuperTiles):
                need_cols = self._plan_cols(plan)
                dedup = s.region_id in dedup_regions
                if dedup:
                    dp_enabled = passes.enabled("dedup_plane", self.config)
                    if not dp_enabled or not self.cache.ensure_dedup_keep(s):
                        passes.note(
                            "dedup_plane", False,
                            "keep plane unavailable: merge scan owns dedup"
                            if dp_enabled else "pass disabled",
                        )
                        # host planes evicted: scan path owns it
                        return _decline("dedup keep plane unavailable")
                    passes.note(
                        "dedup_plane", True,
                        "overlapping-SST LWW dedup lowered to a device keep "
                        "mask", region=s.region_id,
                    )
                if (
                    not plan.time_major
                    and window is not None
                    and use_ts
                    and window[0] > -(1 << 61)
                    and window[1] < (1 << 61)
                    and passes.enabled("window_tile", self.config)
                ):
                    # windowed query over deep retention: gather ONLY the
                    # in-window (and dedup-surviving) rows into a compact
                    # tile — the kernel then scans the window, not the
                    # retention (reference prunes SSTs/row-groups by time).
                    # Where the region's planes are already on the device a
                    # tile is built only if that is cheaper than scanning
                    # them with the window as a mask (ensure_window_tile)
                    with tracing.stage("tile.window", region=s.region_id) as st:
                        wsrc, declined = self.cache.ensure_window_tile(
                            s, window, use_ts, self._plan_cols(plan),
                            set(limb_need), dedup, ctx.dictionary.epoch,
                        )
                        if declined:
                            st.set(declined=declined)
                    if wsrc is not None:
                        passes.note(
                            "window_tile", True,
                            "in-window rows gathered into a compact tile",
                            region=s.region_id, sources=len(wsrc),
                        )
                        if plan.series_slots:
                            # the tile's rows are the window's rows of the
                            # sorted planes: its series open where theirs do
                            held = series_tables[s.region_id]
                            series = self._series_inputs(
                                held, plan, [src[1] for src in wsrc],
                                starts=self.cache.window_positions(
                                    s, window, use_ts, dedup, held.starts
                                ),
                            )
                            wsrc = [
                                ({**cols, **extra}, *rest)
                                for (cols, *rest), extra in zip(wsrc, series)
                            ]
                        device_sources.extend(wsrc)
                        continue
                    passes.note(
                        "window_tile", False,
                        _WINDOW_DECLINED[declined] + ": full-tile scan with "
                        "device masking",
                        region=s.region_id, declined=declined,
                    )
                if s.region_id in deferred_upload:
                    # lazy full-plane upload: only reached when the window
                    # tile did NOT serve this region — the fused planner's
                    # no-wasted-uploads rule
                    region_d, metas_d = deferred_upload.pop(s.region_id)
                    big = padded_size(
                        max(sum(m.num_rows for m in metas_d), 1)
                    ) >= _LIMB_MIN_ROWS
                    up, _excluded = self.cache.super_tiles(
                        region_d, ctx.dictionary, metas_d, device_tag_cols,
                        ts_name or use_ts,
                        device_value_cols if big else value_cols,
                        pinned_ids, pk,
                    )
                    if up is None:
                        return _decline("no planes for a region")
                    # a tag plane uploaded HERE (its device copy released by
                    # release_unneeded, or never made) comes from the
                    # persisted codes at their stored epoch, after phase
                    # B's repair has run: gather it forward now, or this
                    # dispatch groups by stale codes
                    self.cache.repair_super([up], ctx.dictionary, all_tag_cols)
                    if up is not s:
                        # entry was evicted + rebuilt mid-query: adopt the
                        # live object (and re-derive its dedup plane)
                        s = up
                        if dedup and not self.cache.ensure_dedup_keep(s):
                            return _decline("dedup keep plane unavailable")
                if s.nbytes - _window_tile_bytes(s) > self.cache.budget // 2:
                    # one-entry deployments: make room for THIS query's
                    # planes by dropping the entry's own unused columns
                    # (whole-entry eviction can't, the entry is pinned).
                    # Window tiles do not count: the budget's eviction
                    # sheds those, and with them counted a region whose
                    # windows pile up drops and re-uploads its tag planes
                    # with every other query
                    self.cache.release_unneeded(s, need_cols)
                if plan.time_major:
                    cols, valid, nulls = self.cache.ensure_time_major(
                        s, use_ts, need_cols, dedup=dedup
                    )
                else:
                    cols = {k: v for k, v in s.cols.items() if k in need_cols}
                    valid = s.valid_dedup if dedup else s.valid
                    nulls = {k: v for k, v in s.nulls.items() if k in need_cols}
                limbs = (
                    self.cache.ensure_limbs(
                        s, limb_need, plan.time_major, pinned_ids
                    )
                    if limb_need
                    else {}
                )
                # every limb column needs SOME device representation —
                # cached limb planes or the f64 plane; a column with
                # neither (f64 upload skipped + host tile evicted or
                # geometry too small) cannot aggregate: authoritative
                # scan path takes over
                if any(
                    c not in limbs and c not in s.cols for c in limb_need
                ):
                    return _decline("a limb column has no device plane")
                series = (
                    self._series_inputs(series_tables[s.region_id], plan, valid)
                    if plan.series_slots else [{}] * len(valid)
                )
                # one jit source per chunk: bounded per-dispatch temporaries
                # (see _SuperTiles.cols), merged on device like any source
                for i in range(len(valid)):
                    device_sources.append(
                        (
                            {**{k: v[i] for k, v in cols.items()}, **series[i]},
                            valid[i],
                            {k: v[i] for k, v in nulls.items()},
                            None,
                            {k: v[i] for k, v in limbs.items()},
                        )
                    )
            else:
                src = self._encode_mem(
                    ctx.dictionary, s[1], all_tag_cols, use_ts, value_cols,
                    series_plan=plan if plan.series_slots else None,
                    series_key=series_key,
                )
                if src is None:
                    return _decline("the memtable tail cannot be encoded")
                need_cols = self._plan_cols(plan) | set(SERIES_KEYS)
                cols, valid, nulls = src
                device_sources.append(
                    (
                        {k: v for k, v in cols.items() if k in need_cols},
                        valid,
                        {k: v for k, v in nulls.items() if k in need_cols},
                        None,
                        {},
                    )
                )

        # 5. one dispatch, one fetch.  NULL-gating count rows ship only
        # for columns whose dispatched sources actually carry a null mask
        # — a schema-nullable column with no nulls on disk costs nothing
        # (every dropped [G] row is fewer result bytes to fetch)
        null_present = set()
        for _cols, _valid, nulls, _perm, _limbs in device_sources:
            null_present |= set(nulls)
        nullable_cols = tuple(
            sorted(
                c
                for _f, c in plan.agg_specs
                if c != COUNT_STAR and c in null_present
            )
        )
        dyn = {
            "filter_values": tuple(dyn_host["filter_values"]),
            "bucket_origin": np.int64(dyn_host["bucket_origin"]),
            "bucket_interval": np.int64(dyn_host["bucket_interval"]),
            "having_values": tuple(dyn_host["having_values"]),
            # the series stage 1 holds (a memtable tail's included)
            "series": sum(len(t) for t in series_tables.values()) + sum(
                int(np.sum(np.asarray(src[0][SERIES_GROUP]) >= 0))
                for src in device_sources if SERIES_ROWS in src[0]
            ),
        }
        ndev = len(self.cache.devices)
        placed = ndev > 1 and passes.enabled("chunk_placement", self.config)
        if placed:
            why = (f"{len(device_sources)} tile chunk(s) round-robin over "
                   f"{ndev} devices, states merged N:1")
        elif ndev > 1:
            why = "pass disabled: all chunks pinned to device 0"
        else:
            why = f"{len(device_sources)} tile chunk(s) on the single device"
        passes.note(
            "chunk_placement", placed, why,
            chunks=len(device_sources), devices=ndev,
        )
        if not _in_fused_build() and not _capture_active():
            # ghost (background-build) dispatches stay out of the per-
            # query counters: a metric delta a test or dashboard reads
            # around one query must not absorb the builder's priming run.
            # A fusion CAPTURE also defers these: whichever path finally
            # answers the member (the fused dispatch or the per-member
            # degrade re-running this code) emits them exactly once.
            metrics.TILE_LOWERED_TOTAL.inc()
            metrics.AGG_STRATEGY_TOTAL.inc(strategy=plan.agg_strategy)
        if plan.agg_strategy == "hash":
            passes.note(
                "agg_strategy", True, agg_probe["why"],
                slots=plan.hash_slots, groups=plan.num_groups,
                distinct_est=agg_probe["d_est"], stats=agg_probe["stats_src"],
            )
            analyze.record(
                "agg_strategy", strategy="hash", slots=plan.hash_slots,
                dense_groups=plan.num_groups,
            )
        elif tag_cols:
            analyze.record(
                "agg_strategy", strategy="sort", dense_groups=plan.num_groups
            )
        # first pass normally runs the MXU limb kernel; when its per-group
        # error bound fails the verdict (mixed-magnitude data sharing
        # blocks), rerun the same sources with exact f64 accumulation.
        # A hash plan's rerun rung is the DENSE plan instead (slot-table
        # overflow = the distinct estimate was badly low) — and only when
        # the dense bounds allow it; otherwise the scan path owns it.
        if plan.agg_strategy == "hash":
            attempts = [plan]
            dense = dataclasses.replace(
                plan, agg_strategy="sort", hash_slots=0, acc_dtype="float64"
            )
            if (
                dense.num_groups <= self.config.max_groups * 64
                and dense.internal_groups <= self.config.max_internal_groups
            ):
                attempts.append(dense)
        else:
            attempts = [plan, dataclasses.replace(plan, acc_dtype="float64")]
        if _capture_active() and not _in_fused_build():
            # mega-fusion capture (batch.fuse_programs): the batch leader
            # wants this member's dispatch-ready state, not a dispatch.
            # Only the first attempts rung is captured — a rerun verdict
            # (hash-slot overflow / limb bound) decoded from the fused
            # leaves degrades the member to a solo run that walks the
            # full ladder, exactly like the packed path's verdicts.
            # Going through _tile_program_cached keeps compile-cache
            # hit/miss accounting identical to a solo dispatch.
            first = attempts[0]
            _program, int_layout, acc32_layout, acc64_layout, int_dtype = (
                _tile_program_cached(first, nullable_cols, fspec)
            )
            return CapturedDispatch(
                key=(first, nullable_cols, fspec),
                sources=tuple(device_sources),
                dyn=dyn,
                finish=functools.partial(
                    self._finish_fetched, int_layout, acc32_layout,
                    acc64_layout, int_dtype, first, lowering, schema, ctx,
                    dyn_host, fspec,
                ),
            )
        if plan.series_slots and not _in_fused_build():
            metrics.TILE_STAGE1_SERIES.inc(dyn["series"])
        for attempt_plan in attempts:
            program, int_layout, acc32_layout, acc64_layout, int_dtype = (
                _tile_program_cached(attempt_plan, nullable_cols, fspec)
            )
            # multi-chip first (tile.mesh_devices > 0): the same sources
            # under shard_map with collective merge; ANY failure there
            # degrades to the single-chip dispatch below, never an error
            packed, off_mesh = self._mesh_attempt(
                attempt_plan, nullable_cols, device_sources, dyn, ctx,
                program,
            )
            try:
                if packed is None:
                    # fault point: arm with an error whose text contains
                    # RESOURCE_EXHAUSTED to drive the emergency-release +
                    # halve-chunk feedback loop without a real 16 GB set
                    _fault_fire("hbm.exhausted", table=ctx.table_key)
                    with tracing.span(
                        "tile.dispatch",
                        strategy=attempt_plan.agg_strategy,
                        acc=attempt_plan.acc_dtype,
                        mesh_devices=0,
                        **({"mesh_ineligible": off_mesh} if off_mesh else {}),
                    ) as disp:
                        packed = device_health.supervised_call(
                            "dispatch",
                            lambda: program(tuple(device_sources), dyn),
                        )
                    _record_dispatch(disp, attempt_plan)
                table = self._finalize(
                    packed, int_layout, acc32_layout, acc64_layout, int_dtype,
                    attempt_plan, lowering, schema, ctx, dyn_host, fspec,
                )
            except Exception as e:  # noqa: BLE001 — only OOM is retryable
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                # device OOM: release every re-derivable plane AND the
                # pinned entries' own columns this query doesn't touch
                # (a sole-entry deployment can hold 10 f64 planes another
                # query family uploaded), then retry once; a second
                # failure falls back to the authoritative scan path
                logging.getLogger("greptimedb_tpu.tile").warning(
                    "device OOM at dispatch: cache=%s device=%s",
                    self.cache.stats(), _device_memory_stats(),
                )
                need = self._plan_cols(plan)
                for s in slots:
                    if isinstance(s, _SuperTiles):
                        self.cache.release_unneeded(s, need)
                self.cache.emergency_release(pinned_ids)
                tracing.add_event(
                    "hbm.emergency_release", table=ctx.table_key
                )
                _fault_fire("hbm.exhausted", table=ctx.table_key)
                with tracing.span(
                    "tile.dispatch",
                    strategy=attempt_plan.agg_strategy,
                    acc=attempt_plan.acc_dtype,
                    retry=True,
                ) as disp:
                    packed = device_health.supervised_call(
                        "dispatch",
                        lambda: program(tuple(device_sources), dyn),
                    )
                _record_dispatch(disp, attempt_plan)
                flight_recorder.flag("retry")
                table = self._finalize(
                    packed, int_layout, acc32_layout, acc64_layout, int_dtype,
                    attempt_plan, lowering, schema, ctx, dyn_host, fspec,
                )
            if table is not None:
                return table
        # reachable only for a hash plan whose slot table overflowed AND
        # whose dense twin exceeds the [G] bounds: the scan path owns it
        return _decline("hash slots overflowed and the dense plan is too large")

    def _precompile_async(
        self, plan, fspec, entry, dyn_host, tag_like, ts_name, skip_f64,
    ):
        """Best-effort background compile of the tile program for
        `entry`'s chunk shape, started BEFORE the data planes finish
        uploading: chunk shapes are known from metadata (pow2 pad /
        chunk_rows), dtypes from the host encodes, so a
        jax.ShapeDtypeStruct lowering + compile can run concurrently with
        the uploads and land in the persistent XLA compilation cache —
        the dispatch-time compile then hits.  The nullable-column set is
        guessed from host-side knowledge; a wrong guess wastes one
        background compile and the dispatch path compiles its real
        signature as usual.  Never raises, never blocks the query.  The
        worker is NON-daemon (a daemon thread torn down inside an XLA
        compile aborts interpreter shutdown) and each program signature
        spawns at most once per executor."""
        try:
            null_guess = set(entry.nulls) | set(entry.persisted_nulls)
            nullable = tuple(sorted(
                c
                for _f, c in plan.agg_specs
                if c != COUNT_STAR and c in null_guess
            ))
            need_cols = self._plan_cols(plan)
            limb_need = list(self._limb_sum_cols(plan))
            rows0 = min(entry.pad, self.cache.chunk_rows)
            pad = entry.pad

            def col_dtype(c):
                if c in entry.cols:
                    return np.dtype(entry.cols[c][0].dtype)
                if c in entry.persisted_cols:
                    return np.dtype(entry.persisted_cols[c].dtype)
                if c in tag_like:
                    return np.dtype(np.int32)
                if c == ts_name:
                    return np.dtype(np.int64)
                return np.dtype(np.float64)

            dtypes = {c: col_dtype(c) for c in need_cols}
            pdyn = {
                "filter_values": tuple(dyn_host["filter_values"]),
                "bucket_origin": np.int64(dyn_host["bucket_origin"]),
                "bucket_interval": np.int64(dyn_host["bucket_interval"]),
            }
            sig = (plan, nullable, fspec, rows0)
            with self._precompile_lock:
                if sig in self._precompiled:
                    return  # already compiled (or a warm program exists)
                self._precompiled.add(sig)
        except Exception:  # noqa: BLE001 — purely an optimization
            return

        def run():
            try:
                program, *_layouts = _tile_program_cached(
                    plan, nullable, fspec
                )
                pj = getattr(program, "_partial_jit", None)
                if pj is None:
                    return
                sd = jax.ShapeDtypeStruct
                cols_spec = {
                    c: sd((rows0,), dtypes[c])
                    for c in need_cols
                    if c not in skip_f64
                }
                nulls_spec = {
                    c: sd((rows0,), np.bool_)
                    for c in nullable
                    if c in need_cols
                }
                limbs_spec = {}
                if (
                    plan.acc_dtype == "limb"
                    and limb_need
                    and pad % BLOCK_ROWS == 0
                    and rows0 >= _LIMB_MIN_ROWS
                ):
                    limb_struct = jax.eval_shape(
                        quantize_limbs, sd((rows0,), np.float64)
                    )
                    limbs_spec = {c: limb_struct for c in limb_need}
                pj.lower(
                    cols_spec, sd((rows0,), np.bool_), nulls_spec,
                    pdyn, None, limbs=limbs_spec,
                ).compile()
                metrics.TPU_PRECOMPILES.inc()
            except Exception:  # noqa: BLE001 — best-effort, see docstring
                pass

        threading.Thread(
            target=run, name="tile-precompile", daemon=False
        ).start()

    def _streamed_execute(
        self, lowering, schema, scan, ctx, time_bounds, region_sources,
        dedup_regions, ts_name, tag_cols, all_tag_cols, value_cols, use_ts,
        device_value_cols, pinned_ids, pk, window, in_window, est_dev,
    ):
        """Region-streamed execution for working sets larger than the HBM
        budget: host-encode EVERY file first (all dictionary growth
        happens before any group id exists), then per region build planes
        -> dispatch chunk partials -> merge [G] states on device ->
        RELEASE the region's planes.  Peak HBM = one region's planes +
        the [G] states; total latency is linear in regions with flat
        per-region cost — the contract that scales to 1B rows on a
        fixed-HBM chip.  Role-equivalent of the reference MergeScan
        processing per-region streams without materializing the table
        (reference query/src/dist_plan/merge_scan.rs:250-330), applied to
        HBM instead of server RAM.  Returns None when the shape cannot
        stream (dedup, time-major) — the scan path owns it."""
        if dedup_regions:
            passes.note(
                "stream_spill", False,
                "overlapping SSTs need dedup planes: not streamable",
            )
            return None

        # phase A: host encodes for every file of every region, growing
        # the dictionary to its final state; per-file host tiles are
        # RAM-cached so the per-region builds below skip Parquet
        sort_cols = list(dict.fromkeys(pk + ([ts_name] if ts_name else [])))
        need = list(dict.fromkeys(
            all_tag_cols + ([use_ts] if use_ts else []) + value_cols
        ))
        host_need = list(dict.fromkeys(sort_cols + need))
        null_present: set[str] = set()
        for region, metas, mem_tables in region_sources:
            for meta in metas:
                check_deadline()  # per-file Parquet decode + encode
                ht = self.cache._file_host_tiles(
                    region, ctx.dictionary, meta, host_need,
                    all_tag_cols + pk, ts_name,
                )
                if ht is None:
                    return None  # undecodable file: scan path owns it
                null_present |= set(ht.nulls) | set(ht.absent)
            for mt in mem_tables:
                for name in mt.column_names:
                    if mt[name].null_count:
                        null_present.add(name)

        # a series plan sizes stage 1 before any region is built: by the
        # series its key's dictionaries allow (a region of a table
        # partitioned on its leading tag holds a subset of them)
        series_key = _series_key(
            pk, tag_cols, lowering.bucket is not None, _partitioned(ctx, pk)
        )
        n_series = 1
        for t in series_key or ():
            n_series *= max(ctx.dictionary.cardinality(t), 1)
        built = self._build_plan(
            lowering, schema, scan, ctx, tag_cols, time_bounds, use_ts,
            n_series=n_series,
        )
        if built is None:
            return None
        plan, dyn_host, fspec = built
        if plan.series_slots and plan.internal_groups > self.config.max_internal_groups:
            passes.note(
                "stream_spill", False,
                "series plan: the key's dictionaries allow more series than "
                "max_internal_groups; the all-at-once build counts them",
            )
            return None
        if tag_cols:
            passes.note(
                "agg_strategy", False,
                "region-streamed execution keeps dense [G] states (the "
                "per-region release cycle owns HBM already)",
            )
        if plan.time_major:
            # time-major copies double a region's planes and the
            # permutation build is per-entry; bucket-only group-bys at
            # beyond-budget scale take the scan path
            passes.note("stream_spill", False, "time-major plan: not streamable")
            return None
        if plan.num_groups > self.config.max_groups * 64:
            return None
        if plan.internal_groups > self.config.max_internal_groups:
            return None
        limb_need = self._limb_sum_cols(plan)
        need_cols = self._plan_cols(plan)
        nullable_cols = tuple(sorted(
            c for _f, c in plan.agg_specs
            if c != COUNT_STAR and c in null_present
        ))
        dyn = {
            "filter_values": tuple(dyn_host["filter_values"]),
            "bucket_origin": np.int64(dyn_host["bucket_origin"]),
            "bucket_interval": np.int64(dyn_host["bucket_interval"]),
            "having_values": tuple(dyn_host["having_values"]),
        }
        n_regions = sum(1 for _r, m, _t in region_sources if m)
        bail: dict = {}
        counted = False
        stage1 = {"series": 0}

        def make_sources():
            prev: list = [None]

            def release_prev():
                if prev[0] is not None:
                    self.cache.release_unneeded(prev[0], set())
                    prev[0] = None

            def gen():
                for region, metas, mem_tables in region_sources:
                    check_deadline()  # per-region build + dispatch
                    release_prev()
                    if metas:
                        t0 = time.perf_counter()
                        big = padded_size(
                            max(sum(m.num_rows for m in metas), 1)
                        ) >= _LIMB_MIN_ROWS
                        entry, excluded = self.cache.super_tiles(
                            region, ctx.dictionary, metas, all_tag_cols,
                            ts_name or use_ts,
                            device_value_cols if big else value_cols,
                            pinned_ids, pk,
                        )
                        if entry is None or any(
                            in_window(*m.time_range) for m in excluded
                        ):
                            bail["why"] = "file excluded from super-tile"
                            return
                        self.cache.repair_super(
                            [entry], ctx.dictionary, all_tag_cols
                        )
                        limbs = (
                            self.cache.ensure_limbs(
                                entry, limb_need, False, pinned_ids
                            )
                            if limb_need
                            else {}
                        )
                        if any(
                            c not in limbs and c not in entry.cols
                            for c in limb_need
                        ):
                            bail["why"] = "limb plane unavailable"
                            return
                        cols = {
                            k: v for k, v in entry.cols.items()
                            if k in need_cols
                        }
                        nulls = {
                            k: v for k, v in entry.nulls.items()
                            if k in need_cols
                        }
                        series = [{}] * len(entry.valid)
                        if plan.series_slots:
                            table = self.cache.series_table(
                                entry, ctx.dictionary, pk
                            )
                            if table is not None and len(series_key) < len(pk):
                                table = table.prefix(len(series_key))
                            if table is None or len(table) > plan.series_slots:
                                bail["why"] = "no series table"
                                return
                            series = self._series_inputs(
                                table, plan, entry.valid
                            )
                            stage1["series"] += len(table)
                        for i in range(len(entry.valid)):
                            yield (
                                {**{k: v[i] for k, v in cols.items()}, **series[i]},
                                entry.valid[i],
                                {k: v[i] for k, v in nulls.items()},
                                None,
                                {k: v[i] for k, v in limbs.items()},
                            )
                        prev[0] = entry
                        # per-region wall (build + every chunk dispatch:
                        # the consumer runs sync'd partials between
                        # yields) — the flat-latency evidence the bench
                        # records
                        LAST_STREAM_CHUNK_MS.append(
                            (time.perf_counter() - t0) * 1000
                        )
                    for mt in mem_tables:
                        src = self._encode_mem(
                            ctx.dictionary, mt, all_tag_cols, use_ts,
                            value_cols,
                            series_plan=plan if plan.series_slots else None,
                            series_key=series_key,
                        )
                        if src is None:
                            bail["why"] = "memtable encode failed"
                            return
                        mcols, mvalid, mnulls = src
                        if SERIES_GROUP in mcols:
                            stage1["series"] += int(
                                np.sum(np.asarray(mcols[SERIES_GROUP]) >= 0)
                            )
                        yield (
                            {
                                k: v for k, v in mcols.items()
                                if k in need_cols or k in SERIES_KEYS
                            },
                            mvalid,
                            {k: v for k, v in mnulls.items() if k in need_cols},
                            None,
                            {},
                        )
                release_prev()

            return gen()

        for attempt_plan in (
            plan, dataclasses.replace(plan, acc_dtype="float64")
        ):
            program, int_layout, acc32_layout, acc64_layout, int_dtype = (
                _tile_program_cached(attempt_plan, nullable_cols, fspec)
            )
            LAST_STREAM_CHUNK_MS.clear()  # per attempt: the f64 rerun
            # (limb verdict failure) re-streams and re-records
            try:
                _fault_fire("hbm.exhausted", table=ctx.table_key)
                with tracing.span(
                    "tile.dispatch",
                    strategy=attempt_plan.agg_strategy,
                    acc=attempt_plan.acc_dtype,
                    streamed=True,
                ) as disp:
                    packed = program(make_sources(), dyn, sync=True)
                _record_dispatch(disp, attempt_plan)
                flight_recorder.flag("streamed")
            except QueryTimeoutError:
                raise  # the deadline owns the query
            except Exception as e:  # noqa: BLE001 — fall to all-at-once
                # zero-source bail (run_all's ValueError) or a mid-stream
                # device error: the all-at-once path below applies its own
                # gates; never let the engine's CPU full-scan fallback own
                # a beyond-budget working set by default
                logging.getLogger("greptimedb_tpu.tile").warning(
                    "streamed tile query failed (%s): %s",
                    bail.get("why", "mid-stream error"), e,
                )
                return None
            if bail:
                logging.getLogger("greptimedb_tpu.tile").warning(
                    "streamed tile query bailed: %s", bail["why"]
                )
                return None
            if not counted:
                counted = True
                passes.note(
                    "stream_spill", True,
                    f"estimated {est_dev >> 20} MB of planes exceeds the "
                    f"{self.cache.budget >> 20} MB budget: {n_regions} "
                    "regions streamed with per-region release",
                    regions=n_regions, est_mb=est_dev >> 20,
                )
                metrics.TILE_STREAM_QUERIES.inc()
                if not _in_fused_build():
                    metrics.TILE_LOWERED_TOTAL.inc()
                    metrics.AGG_STRATEGY_TOTAL.inc(strategy="sort")
                    if plan.series_slots:
                        metrics.TILE_STAGE1_SERIES.inc(stage1["series"])
            table = self._finalize(
                packed, int_layout, acc32_layout, acc64_layout, int_dtype,
                attempt_plan, lowering, schema, ctx, dyn_host, fspec,
            )
            if table is not None:
                return table
        return None  # unreachable: the f64 pass never fails the verdict

    # -- multi-chip dispatch -------------------------------------------------
    def _mesh_attempt(
        self, attempt_plan, nullable_cols, device_sources, dyn, ctx, program,
    ):
        """Try the multi-chip shard_map dispatch (tile.mesh_devices > 0).
        Returns (packed result buffers, None), or (None, why) to run the
        single-chip dispatch instead — shape ineligible, pass disabled, or
        ANY failure in the collective program (the degrade contract: a
        broken mesh must never fail a query the single chip can answer).
        `why` is None with the mesh path off; else the single-chip
        `tile.dispatch` carries it, and a counter moves: TILE_MESH_DEGRADED
        for a failure, TILE_MESH_INELIGIBLE for the rest."""
        mesh_n = self.cache.mesh_devices()
        if mesh_n <= 0:
            return None, None
        if not passes.enabled("mesh_dispatch", self.config):
            passes.note(
                "mesh_dispatch", False, "pass disabled: single-chip dispatch"
            )
            metrics.TILE_MESH_INELIGIBLE.inc()
            return None, "mesh_dispatch pass disabled"
        pdyn = {
            k: dyn[k]
            for k in ("filter_values", "bucket_origin", "bucket_interval")
        }
        hv = jnp.asarray(dyn.get("having_values") or (0.0,), jnp.float64)
        try:
            mesh = self.cache.mesh(mesh_n)
            # fault point: an injected error here IS a collective failure
            # at the shard_map merge choke point — the degrade path below
            # must serve the query from the single chip, bit-correct
            _fault_fire(
                "mesh.collective", table=ctx.table_key, devices=mesh_n
            )
            with tracing.span(
                "tile.dispatch",
                strategy=attempt_plan.agg_strategy,
                acc=attempt_plan.acc_dtype,
                mesh_devices=mesh_n,
                shard_axis=REGION_AXIS,
            ) as disp:
                # shape-ineligibility is a benign verdict, not a device
                # error: it is raised here, outside the supervisor, so it
                # never feeds the breaker
                with tracing.stage(
                    "tile.mesh_stack", sources=len(device_sources)
                ):
                    staged = _mesh_stage(mesh, device_sources)
                # supervised with the mesh's device slots as the blast radius
                packed = device_health.supervised_call(
                    "mesh",
                    lambda: _mesh_run(
                        attempt_plan, nullable_cols, mesh, staged,
                        pdyn, hv, program,
                    ),
                    devices=tuple(range(mesh_n)),
                )
            _record_dispatch(disp, attempt_plan, mesh_devices=mesh_n)
            metrics.TILE_MESH_DISPATCHES.inc()
            passes.note(
                "mesh_dispatch", True,
                f"{len(device_sources)} source(s) sharded over the "
                f"{mesh_n}-device `{REGION_AXIS}` mesh: per-device partial "
                "aggregates, psum/pmin/pmax merge, finalize once "
                "post-merge",
                devices=mesh_n, sources=len(device_sources),
            )
            return packed, None
        except QueryTimeoutError:
            raise  # the deadline owns the query, mesh or not
        except _MeshIneligible as mi:
            passes.note(
                "mesh_dispatch", False, f"{mi}: single-chip dispatch"
            )
            metrics.TILE_MESH_INELIGIBLE.inc()
            return None, str(mi)
        except Exception as exc:  # noqa: BLE001 — degrade, never fail
            metrics.TILE_MESH_DEGRADED.inc()
            flight_recorder.flag("mesh_degraded")
            tracing.add_event(
                "mesh.degraded",
                table=ctx.table_key,
                error=type(exc).__name__,
            )
            logging.getLogger("greptimedb_tpu.tile").warning(
                "mesh dispatch failed; degrading to single-chip: %s",
                exc, exc_info=True,
            )
            passes.note(
                "mesh_dispatch", False,
                f"collective failure ({type(exc).__name__}): degraded to "
                "the single-chip dispatch",
            )
            return None, f"degraded: {type(exc).__name__}"

    # -- helpers -------------------------------------------------------------
    @staticmethod
    def _limb_sum_cols(plan: DistGroupByPlan) -> list[str]:
        """Value columns whose aggregation rides the MXU limb kernel
        (sum/avg; see compute_partial_states) — worth caching quantized
        planes for.  Count-only and min/max/last columns are excluded."""
        if plan.acc_dtype != "limb":
            return []
        per: dict[str, set] = {}
        for f, c in plan.agg_specs:
            per.setdefault(c, set()).add(_FUNC_TO_KERNEL[f])
        return [
            c
            for c, aggs in per.items()
            if c != COUNT_STAR and "last" not in aggs and aggs & {"sum", "avg"}
        ]

    @staticmethod
    def _plan_cols(plan: DistGroupByPlan) -> set:
        """The planes a dispatch of `plan` reads: a series plan reads no
        group tag's (its groups come with its series)."""
        need = {f[0] for f in plan.filters}
        if not plan.series_slots:
            need |= set(plan.group_tags)
        if plan.bucket_col:
            need.add(plan.bucket_col)
        if plan.ts_col:
            need.add(plan.ts_col)
        for _f, c in plan.agg_specs:
            if c != COUNT_STAR:
                need.add(c)
        return need

    @staticmethod
    def _series_groups(codes: np.ndarray, tags, plan: DistGroupByPlan) -> np.ndarray:
        """The group code [S] int32 of each series whose key codes are the
        rows of `codes` (columns `tags`): `plan.group_tags`' codes composed
        over `plan.tag_cards` as the gid composes them, -1 where a code is
        out of range (its rows are out of every group, as a direct gid
        finds them)."""
        g = np.zeros(len(codes), np.int64)
        ok = np.ones(len(codes), bool)
        for tag, card in zip(plan.group_tags, plan.tag_cards):
            c = codes[:, list(tags).index(tag)].astype(np.int64)
            ok &= (c >= 0) & (c < card)
            g = g * card + np.clip(c, 0, card - 1)
        return np.where(ok, g, -1).astype(np.int32)

    def _series_inputs(
        self, table: SeriesTable, plan: DistGroupByPlan, valid, starts=None,
    ) -> list:
        """Per chunk of a region's planes (`valid`, the chunk list), the
        inputs of a series plan's stage 1 (`compute_partial_states`):
        SERIES_MARKS, the chunk row at which each of its series opens, and
        SERIES_GROUP, each one's group code, both [plan.series_slots]
        int32, padded past the chunk's series with its length / -1.  The
        series' row bounds come with the request off the region's series
        table: nothing is searched over the planes.  Made once per group
        space and chunk geometry and kept, on the chunks' devices, in the
        table's memo.  `starts`: where the series open in a window tile's
        rows in place of the planes' (a series with no row in the window
        opens where the next one does: it holds no row), made anew."""
        sizes = tuple(int(v.shape[0]) for v in valid)
        devices = tuple(next(iter(v.devices())) for v in valid)

        def make():
            groups = self._series_groups(table.codes, table.tags, plan)
            n_series = len(table)
            opens = table.starts if starts is None else starts
            out, lo = [], 0
            for i, n in enumerate(sizes):
                k0 = max(int(np.searchsorted(opens[:n_series], lo, side="right")) - 1, 0)
                k1 = int(np.searchsorted(opens[:n_series], lo + n, side="left"))
                k1 = max(k1, k0)
                marks = np.full(plan.series_slots, n, np.int32)
                group = np.full(plan.series_slots, -1, np.int32)
                marks[: k1 - k0] = np.maximum(opens[k0:k1] - lo, 0)
                group[: k1 - k0] = groups[k0:k1]
                out.append({
                    SERIES_MARKS: jax.device_put(marks, devices[i]),
                    SERIES_GROUP: jax.device_put(group, devices[i]),
                })
                lo += n
            return out

        if starts is not None:
            return make()
        key = ("sql", plan.group_tags, plan.tag_cards, plan.series_slots, sizes, devices)
        return table.remember(key, make)

    def _encode_mem(
        self, dictionary, table, tag_cols, ts_col, value_cols,
        series_plan: DistGroupByPlan | None = None, series_key=None,
    ):
        """Encode the (small, fresh) memtable tail; same host encode as
        file tiles (_encode_host_tiles) so the two can never diverge.
        With `series_plan` its rows, in arrival order, carry their series
        over `series_key` as SERIES_ROWS and the series' group codes as
        SERIES_GROUP (see `compute_partial_states`)."""
        need = list(
            dict.fromkeys(tag_cols + ([ts_col] if ts_col else []) + value_cols)
        )
        for name in need:
            if name not in table.column_names:
                return None
        built = _encode_host_tiles(dictionary, table, need, tag_cols, ts_col)
        if built is None:
            return None
        cols, nulls, _epochs, _nbytes = built
        n = table.num_rows
        pad = padded_size(n, 1024)
        out_cols = {}
        out_nulls = {}
        for name, arr in cols.items():
            buf = np.zeros(pad, dtype=arr.dtype)
            buf[:n] = arr
            out_cols[name] = jnp.asarray(buf)
        for name, arr in nulls.items():
            buf = np.zeros(pad, bool)
            buf[:n] = arr
            out_nulls[name] = jnp.asarray(buf)
        if series_plan is not None:
            keys = np.stack([cols[t] for t in series_key], axis=1)
            uniq, inv = np.unique(keys, axis=0, return_inverse=True)
            rows = np.zeros(pad, np.int32)
            rows[:n] = inv.reshape(-1)
            group = np.full(_quantize_card(len(uniq)), -1, np.int32)
            group[: len(uniq)] = self._series_groups(uniq, series_key, series_plan)
            out_cols[SERIES_ROWS] = jnp.asarray(rows)
            out_cols[SERIES_GROUP] = jnp.asarray(group)
        v = np.zeros(pad, bool)
        v[:n] = True
        return (out_cols, jnp.asarray(v), out_nulls)

    def _bucket_geometry(self, lowering, schema, scan, time_bounds):
        """(bucket_col, interval_native, origin, n_buckets_real, n_buckets)
        shared by the plan builder and the agg-strategy probe."""
        if lowering.bucket is not None:
            ts_col, interval, origin_hint = lowering.bucket
            if scan.time_range is not None and scan.time_range[0] > -(1 << 61) and scan.time_range[1] < (1 << 61):
                lo, hi = scan.time_range
            else:
                lo, hi = time_bounds()
                hi += 1
            unit_ns = schema.time_index.data_type.timestamp_unit_ns()
            interval_native = max(int(interval * 1_000_000) // max(unit_ns, 1), 1)
            origin = origin_hint + ((lo - origin_hint) // interval_native) * interval_native
            n_buckets_real = max(int((hi - origin + interval_native - 1) // interval_native), 1)
            n_buckets = _quantize_soft(n_buckets_real)
            return ts_col, interval_native, origin, n_buckets_real, n_buckets
        return None, 1, 0, 1, 1

    def _size_hash_slots(self, d_est: int) -> int:
        """Slot-table size for a distinct-key estimate: next power of two
        past 2x (load factor <= 0.5), floored at 1024, capped at the
        internal-groups bound.  ONE implementation for the choose-time
        probe and the plan builder — a drifting headroom factor between
        them would desynchronize estimate from runtime table size.

        The result must stay a power of two: hash_group_slots addresses
        with `& (H - 1)`, and a non-pow2 H would strand most slots and
        overflow every dispatch — so a non-pow2 max_internal_groups knob
        clamps DOWN to its largest contained power of two."""
        cap = max(int(self.config.max_internal_groups), 1 << 10)
        cap = 1 << (cap.bit_length() - 1)  # largest pow2 <= cap
        slots = 1 << 10
        while slots < 2 * d_est and slots < cap:
            slots <<= 1
        return min(slots, cap)

    def _choose_agg_strategy(
        self, lowering, schema, scan, ctx, tag_cols, time_bounds,
        region_sources=None,
    ):
        """Pick hash vs sort BEFORE the plan is built, from table stats:
        per-tag distinct estimates (dictionary cardinality when warm, the
        segmented term index's per-file term counts when cold) against
        the padded dense group space.  The hash/sort winner flips with
        group cardinality (arXiv:2411.13245): dense [G] states win while
        G is small and the (pk, ts) sort feeds the blocked kernel; a
        slot table sized to the DISTINCT keys wins when G is sparse —
        and is the only option once G exceeds the dense-path bound.

        Runs early because the decision gates limb-plane uploads (hash
        accumulates exact f64); returns a dict consumed by _build_plan,
        or None meaning "sort, the pre-hash path"."""
        knob = getattr(self.config, "agg_strategy", "auto")
        enabled = passes.enabled("agg_strategy", self.config)
        has_last = any(f == "last_value" for f, _c in lowering.agg_specs)
        why_sort = None
        if not enabled:
            why_sort = "pass disabled"
        elif knob == "sort":
            why_sort = "query.agg_strategy=sort forces the dense path"
        elif not tag_cols:
            why_sort = "bucket-only group-by: dense space is one axis, trivially small"
        elif has_last:
            why_sort = "last_value needs the ts-ordered dense kernels"
        if why_sort is not None:
            passes.note("agg_strategy", False, why_sort)
            return None
        d = ctx.dictionary
        est_rows = max(sum(r.approx_rows() for r in ctx.regions), 1)
        _bc, _iv, _orig, n_buckets_real, n_buckets = self._bucket_geometry(
            lowering, schema, scan, time_bounds
        )
        d_prod = 1
        g_est = n_buckets
        src = "dictionary"
        for t in tag_cols:
            card = d.cardinality(t)
            if card <= 0:
                # cold start: the dictionary has not encoded this column
                # yet — ask the segmented term index metas (one small
                # ranged read per file, cached)
                for region in ctx.regions:
                    n = region.distinct_estimate(t)
                    if n:
                        card = max(card, n)
                        src = "term_index"
                card = max(card, 1)
            d_prod *= card
            g_est *= _quantize_card(card)
        if g_est >= _HASH_GID_LIMIT:
            # the mixed-radix gid must fit int64: past this the composed
            # ids would WRAP and alias distinct groups into one slot with
            # no overflow verdict — decline (the scan path owns it)
            passes.note(
                "agg_strategy", False,
                f"padded group space {g_est} exceeds the int64 gid range: "
                "neither strategy can address it; scan path owns the query",
            )
            return None
        d_est = min(est_rows, d_prod * max(n_buckets_real, 1))
        slots = self._size_hash_slots(d_est)
        if slots < 2 * d_est and knob != "hash":
            # the cap clamped the table below 2x the distinct estimate:
            # overflow is likely and the dispatch would be wasted work —
            # auto declines upfront (forced hash proceeds: the estimate
            # is an upper bound and the overflow verdict stays the net)
            passes.note(
                "agg_strategy", False,
                f"~{d_est} distinct keys exceed half the {slots}-slot cap "
                "(query.max_internal_groups): hash would overflow, dense/"
                "scan paths own the query",
            )
            return None
        info = {
            "strategy": "hash",
            "slots": slots,
            "d_est": int(d_est),
            "g_est": int(g_est),
            "stats_src": src,
        }
        if knob == "hash":
            info["why"] = (
                f"query.agg_strategy=hash forced: ~{d_est} distinct keys "
                f"into {slots} slots (dense space {g_est})"
            )
            return info
        min_space = int(getattr(self.config, "agg_hash_min_group_space", 1 << 16))
        if g_est >= min_space and d_est * 4 <= g_est:
            info["why"] = (
                f"sparse group space: ~{d_est} distinct keys ({src}) vs "
                f"{g_est} dense groups -> {slots}-slot hash table"
            )
            return info
        passes.note(
            "agg_strategy", False,
            f"dense space {g_est} is small or well-filled (~{d_est} "
            "distinct keys): sorted dense states win",
            groups=int(g_est), distinct_est=int(d_est),
        )
        return None

    def _build_plan(self, lowering, schema, scan, ctx, tag_cols, time_bounds, use_ts,
                    agg_probe=None, n_series=1):
        """Returns (plan, dyn_host): `plan` is the compile-static structure
        (filter literals replaced by placeholders, n_buckets quantized to a
        power of two) and `dyn_host` carries the runtime values — so
        dashboards that vary literals or time windows reuse one compile.
        Also decides the LAYOUT strategy (direct / series / time-major)
        from the primary-key order — see module docstring; a series plan
        holds `n_series`, the most series of one region, padded.
        `agg_probe` (a _choose_agg_strategy result) switches the plan to
        the hash group-by: no series fold, no time-major, exact f64
        accumulation, slot table re-sized from the now-final dictionary."""
        d = ctx.dictionary
        bucket_col, interval_native, origin, n_buckets_real, n_buckets = (
            self._bucket_geometry(lowering, schema, scan, time_bounds)
        )

        # filters: tag values -> sorted codes (order-preserving, so even
        # inequalities translate); time range -> explicit ts filters.
        # Structure (name, op, arity) is static; values ride `dyn`.
        ts_name = schema.time_index.name if schema.time_index else None
        tag_names = {c.name for c in schema.tag_columns()}
        enc_filters: list[tuple[str, str, object]] = []
        filter_vals: list = []

        def push(name, op, value, dtype):
            if op in ("in", "not in"):
                enc_filters.append((name, op, len(value)))
                filter_vals.append(tuple(dtype(v) for v in value))
            else:
                enc_filters.append((name, op, None))
                filter_vals.append(dtype(value))

        for name, op, value in scan.filters:
            if name in tag_names:
                f = _encode_tag_filter(d, name, op, value)
                if f is None:
                    return _decline("a tag filter has no code-space form")
                for fname, fop, fval in f:
                    push(fname, fop, fval, np.int32)
            else:
                from ..datatypes.coercion import coerce_string_scalar

                def _coerce(v):
                    # numeric literal as string (prepared statements);
                    # a truly non-numeric string on a value column cannot
                    # tile — signalled as None
                    if isinstance(v, str):
                        try:
                            c = coerce_string_scalar(v, pa.float64())
                        except (ValueError, TypeError):
                            return None
                        v = c.as_py() if isinstance(c, pa.Scalar) else c
                        if isinstance(v, str):
                            return None
                    return v

                if op in ("in", "not in"):
                    vals = [_coerce(v) for v in value]
                    if any(v is None for v in vals):
                        return _decline("a value filter literal is not numeric")
                    value = tuple(vals)
                else:
                    value = _coerce(value)
                    if value is None:
                        return _decline("a value filter literal is not numeric")
                dtype = np.int64 if name == ts_name else np.float64
                push(name, op, value, dtype)
        if scan.time_range is not None and use_ts:
            lo, hi = scan.time_range
            if lo > -(1 << 61):
                push(use_ts, ">=", int(lo), np.int64)
            if hi < (1 << 61):
                push(use_ts, "<", int(hi), np.int64)

        norm_specs = []
        for func, col in lowering.agg_specs:
            norm_specs.append((func, COUNT_STAR if col is None else col))
        needs_ts_order = any(f == "last_value" for f, _ in norm_specs)

        # layout strategy
        pk = [c.name for c in schema.tag_columns()]
        is_hash = agg_probe is not None and agg_probe.get("strategy") == "hash"
        partitioned = _partitioned(ctx, pk)
        series_key = (
            None if is_hash
            else _series_key(pk, tag_cols, bucket_col is not None, partitioned)
        )
        time_major = (
            not is_hash
            and bucket_col is not None
            and not tag_cols
            and series_key is None
            and passes.enabled("time_major", self.config)
        )
        if time_major:
            passes.note(
                "time_major", True,
                "bucket-only group-by reduces over a time-major permutation",
            )
        elif bucket_col is not None and not tag_cols:
            passes.note(
                "time_major", False,
                "time-major disabled or layout claims the sort order",
            )
        if (
            series_key is not None
            and needs_ts_order
            and set(tag_cols) != set(series_key)
        ):
            # LAST states only permute, never fold series together
            return _decline("last_value over groups of several series")
        if time_major and needs_ts_order:
            return _decline("last_value has no time-major variant")

        filter_null_cols = tuple(
            sorted(
                {
                    name
                    for name, _op, _v in enc_filters
                    if name not in tag_names
                    and name != ts_name
                    and schema.has_column(name)
                    and schema.column(name).nullable
                }
            )
        )

        # blocked-kernel span: expected distinct gids per 4096-row block of
        # the (pk, ts)-sorted (or time-major) layout, plus the bucket-axis
        # jump at a pk boundary.  Compute cost of the blocked kernel scales
        # with span, so size it to the layout instead of hard-coding; past
        # the cap the runtime guard fails and the scatter path (always
        # correct) takes over.
        est_rows = sum(r.approx_rows() for r in ctx.regions)
        series_slots = _quantize_card(n_series) if series_key is not None else 0
        real_groups = max(n_buckets, 1)
        if series_key is not None:
            # a region's rows over its series: the regions split the rows
            real_groups *= max(n_series, 1) * max(len(ctx.regions), 1)
        for t in tag_cols if series_key is None else ():
            real_groups *= max(d.cardinality(t), 1)
        if time_major:
            # window rows spread over n_buckets; out-of-window rows are
            # masked and don't count against the span guard
            per_group = max(est_rows // max(n_buckets, 1), 1)
            span_est = -(-BLOCK_ROWS // per_group) + 2
        else:
            per_group = max(est_rows // real_groups, 1)
            span_est = -(-BLOCK_ROWS // per_group) + 2
            if bucket_col is not None:
                span_est += n_buckets  # pk-boundary bucket jump
        block_span = 16
        while block_span < min(span_est, 128):
            block_span <<= 1
        acc_dtype = self.config_acc_dtype()
        hash_slots = 0
        if is_hash:
            # the dictionary is FINAL here (every encode ran), so re-check
            # the gid range (cards can GROW between probe and build) and
            # re-size the slot table from exact per-tag distinct counts
            # with 2x headroom (load factor <= 0.5) against co-occurrence
            # we cannot know without scanning
            g_final = max(n_buckets, 1)
            d_prod = 1
            for t in tag_cols:
                card = max(d.cardinality(t), 1)
                d_prod *= card
                g_final *= _quantize_card(card)
            if g_final >= _HASH_GID_LIMIT:
                # int64 gids would wrap: scan path owns it
                return _decline("hash gid range past int64")
            d_est = min(max(est_rows, 1), d_prod * max(n_buckets_real, 1))
            hash_slots = self._size_hash_slots(d_est)
            # hash accumulates exact f64: slot ids defeat both the limb
            # kernel's block geometry and the blocked guard, and the MXU
            # batch would only hit its scatter fallback anyway
            if acc_dtype == "limb":
                acc_dtype = "float64" if jax.config.jax_enable_x64 else "float32"
        plan = DistGroupByPlan(
            group_tags=tuple(tag_cols),
            tag_cards=tuple(_quantize_card(d.cardinality(t)) for t in tag_cols),
            bucket_col=bucket_col,
            bucket_origin=0,  # dynamic — see dyn_host
            bucket_interval=1,
            n_buckets=n_buckets,
            agg_specs=tuple(norm_specs),
            filters=tuple(enc_filters),
            acc_dtype=acc_dtype,
            ts_col=use_ts if needs_ts_order else None,
            filter_null_cols=filter_null_cols,
            series_slots=series_slots,
            partitioned=partitioned and series_key is not None,
            time_major=time_major,
            block_span=block_span,
            agg_strategy="hash" if is_hash else "sort",
            hash_slots=hash_slots,
        )
        dyn_host = {
            "filter_values": filter_vals,
            "bucket_origin": origin,
            "bucket_interval": interval_native,
            "having_values": (),
        }
        if is_hash:
            # hash results are already compact (O(slots) fetch + host
            # slot->key decode); Sort/LIMIT/HAVING replay on host
            passes.note(
                "device_finalize", False,
                "hash agg strategy ships compact slots; host post-ops own "
                "Sort/LIMIT/HAVING",
            )
            return plan, dyn_host, None
        spec = self._plan_device_finalize(
            lowering, schema, ctx, plan, dyn_host, n_buckets_real
        )
        return plan, dyn_host, spec

    def _plan_device_finalize(
        self, lowering, schema, ctx, plan, dyn_host, n_buckets_real
    ):
        """Decide whether (and how) this query's post-plan finalizes on
        device.  Engages when the device can consume Sort/Limit/HAVING
        operators, or when the real group bound is far enough under the
        padded group space that compaction alone pays (> 2x).  With no
        LIMIT, `cap` is a true upper bound on non-empty groups (real
        dictionary cardinalities x real bucket count), so the compact
        fetch can never overflow and no second dispatch is ever needed."""
        enabled = passes.enabled("device_finalize", self.config) and getattr(
            self.config, "device_topk", True
        )
        if not enabled or plan.num_groups <= 1:
            if not enabled:
                passes.note(
                    "device_finalize", False,
                    "pass disabled or query.device_topk off: full-buffer "
                    "fetch + host post-ops",
                )
            return None
        from ..query.device_finalize import (
            DeviceFinalizeSpec,
            derive_post_lowering,
        )

        post = derive_post_lowering(lowering, schema)
        if post is None:
            passes.note(
                "device_finalize", False,
                "post-plan not resolvable to device refs: host replay",
            )
            return None
        real_groups = max(n_buckets_real, 1)
        for t in plan.group_tags:
            real_groups *= max(ctx.dictionary.cardinality(t), 1)
        if post.limit is not None:
            cap = min(plan.num_groups, post.offset + post.limit)
        else:
            cap = min(plan.num_groups, _quantize_soft(real_groups))
        # last_value plans (TSBS lastpoint) ALWAYS take the compact path
        # (cap is min'd against num_groups above, so it always fits):
        # their LAST states scan the full retention, so the result should
        # ship O(rows_out) like the other finalized queries instead of
        # the padded group space + a host-side empty-group scan
        has_last = any(f == "last_value" for f, _c in plan.agg_specs)
        if cap <= 0 or not (
            post.consumed or cap * 2 <= plan.num_groups or has_last
        ):
            passes.note(
                "device_finalize", False,
                "no consumable Sort/LIMIT/HAVING and compaction would not "
                "shrink the fetch: full-buffer path",
                cap=cap, groups=plan.num_groups,
            )
            return None
        dyn_host["having_values"] = tuple(post.having_values)
        dyn_host["post_consumed"] = post.consumed
        return DeviceFinalizeSpec(
            order=post.order,
            having=post.having,
            n_having_values=len(post.having_values),
            limit=post.limit,
            offset=post.offset,
            cap=int(cap),
        )

    def config_acc_dtype(self) -> str:
        import jax as _jax

        mode = getattr(self.config, "tile_acc_dtype", "limb")
        if mode == "limb" and passes.enabled("limb_quantize", self.config):
            return "limb"
        return "float64" if _jax.config.jax_enable_x64 else "float32"

    # -- prewarm -------------------------------------------------------------
    def prewarm(self, ctx: TileContext, schema, limbs: bool = True) -> dict:
        """Build a table's super-tiles OFF the query path: host
        consolidation (Parquet decode + dictionary encode + (pk, ts)
        lexsort), device plane upload for every numeric field, and
        (optionally) the MXU limb quantization — the dominant cold-query
        costs, paid at flush time (tile.prewarm_on_flush) or explicitly
        (Database.prewarm) instead of on the first query of each TSBS
        family.  XLA compiles still happen on first dispatch but ride the
        persistent compilation cache (utils/jax_env.py).  A region with
        nothing flushed is skipped; one whose build raises is logged and
        reported as `error` in the stats."""
        t0 = time.perf_counter()
        built = 0
        pk = [c.name for c in schema.tag_columns()]
        ts_name = schema.time_index.name if schema.time_index else None
        value_cols = [
            c.name for c in schema.field_columns() if c.data_type.is_numeric()
        ]
        limb_wanted = limbs and self.config_acc_dtype() == "limb"
        if self._fused_enabled():
            # fused planner: prewarm emits the table's base manifest and
            # runs the consolidated HOST build (decode + encode + sort +
            # persist — what cold-serve and the selective fast path read);
            # device planes ride the per-family background builds, which
            # upload only what queries actually touch — except where the
            # table has ONE field (a Prometheus metric table): its only
            # family of planes is what any first query touches, so they
            # upload here and that query dispatches instead of being
            # served cold.  The build gate coalesces with a racing
            # query-triggered family build.
            nonnull = [
                c for c in value_cols
                if schema.has_column(c) and not schema.column(c).nullable
            ]
            manifest = PlaneManifest(
                table_key=ctx.table_key,
                tag_cols=tuple(pk),
                ts_col=ts_name,
                value_cols=tuple(value_cols),
                limb_cols=tuple(nonnull) if limb_wanted else (),
                # last-write-wins where files overlap in time: a query over
                # such a table reads the keep plane, and built here its first
                # touch dispatches instead of evaluating every series cold
                dedup=not ctx.append_mode and _files_overlap(ctx.regions),
            )
            self.cache.record_manifest(manifest)
            with self.cache.build_gate(ctx.table_key) as leader:
                if leader:
                    out = self.cache.fused_union_build(
                        ctx, schema, [manifest], device=len(value_cols) == 1,
                    )
                else:
                    out = {"regions_built": 0, "coalesced": True, "ms": 0.0}
            ms = (time.perf_counter() - t0) * 1000.0
            if out.get("regions_built"):
                metrics.PREWARM_BUILDS.inc(out["regions_built"])
            metrics.PREWARM_MS.observe(ms)
            return {
                "regions_built": out.get("regions_built", 0),
                "ms": round(ms, 1),
                **({"coalesced": True} if out.get("coalesced") else {}),
                **({"error": "; ".join(out["errors"])} if out.get("errors") else {}),
            }
        pinned_ids = {r.region_id for r in ctx.regions}
        errors: list[str] = []
        nonnull = [
            c
            for c in value_cols
            if schema.has_column(c) and not schema.column(c).nullable
        ]
        # the table lock (which serializes queries' epoch-sensitive
        # sections) is taken PER REGION, not across the whole build: a
        # background prewarm of a 10-170 s multi-region table must stall
        # a concurrent query by at most one region's build
        for region in ctx.regions:
            with ctx.dictionary.table_lock:
                region.pin_scan()
                try:
                    metas, _mems, version = region.tile_snapshot()
                    self.cache.invalidate_region_if_changed(
                        region.region_id,
                        {m.file_id for m in metas},
                        version,
                    )
                    if not metas:
                        continue
                    entry, _excluded = self.cache.super_tiles(
                        region, ctx.dictionary, metas, pk, ts_name,
                        value_cols, pinned_ids, pk,
                    )
                    if entry is None:
                        continue
                    built += 1
                    if limb_wanted and nonnull:
                        self.cache.ensure_limbs(
                            entry, nonnull, False, pinned_ids
                        )
                except QueryTimeoutError:
                    raise
                except Exception as exc:  # noqa: BLE001 — one region's failure is not the table's
                    errors.append(f"region {region.region_id}: {exc!r}")
                    logging.getLogger("greptimedb_tpu.tile").warning(
                        "prewarm skipped region %s", region.region_id,
                        exc_info=True,
                    )
                finally:
                    region.unpin_scan()
        ms = (time.perf_counter() - t0) * 1000.0
        if built:
            metrics.PREWARM_BUILDS.inc(built)
        metrics.PREWARM_MS.observe(ms)
        return {
            "regions_built": built, "ms": round(ms, 1),
            **({"error": "; ".join(errors)} if errors else {}),
        }

    # -- host fast path ------------------------------------------------------
    _HOST_PATH_MAX_ROWS = 4 << 20
    # Multi-key slices larger than this many (rows x value columns) cells
    # route to the warm tile dispatch instead of the frontend-thread
    # numpy pass (the cpu-max-all-8 contention fix); single-key probes
    # are exempt — they are the host path's whole reason to exist.
    _HOST_PATH_MAX_CELLS = 1 << 17

    # cold-serve shape bounds: past _COLD_COMPACT_GROUPS the dense [G]
    # numpy states would blow up host RAM, so the fused router switches to
    # a unique-compacted fold; _COLD_PAR_ROWS is where the fused fold
    # chunks each source and folds ranges on a small thread pool (the
    # legacy fused_build=False path never chunks — bit-for-bit today).
    _COLD_COMPACT_GROUPS = 1 << 22
    _COLD_PAR_ROWS = 1 << 23
    _COLD_COMPACT_MAX_ROWS = 1 << 26

    def _host_cold_grouped(
        self, plan, dyn_host, super_entries, mem_slots,
        ctx, use_ts, value_cols, all_tag_cols, dedup_regions, window,
        fused: bool = False,
    ):
        """Cold-start router: a grouped aggregate whose device planes are
        not resident yet answers straight from the host consolidation —
        a bounded numpy pass over the (mmap'd) sorted columns, zero
        uploads.  The plane uploads dominate a first touch; the host
        pass is a bounded scan of what is already in memory.

        Legacy mode (`fused=False`, the tile.fused_build=False ladder):
        dense bincount folds only, serves at most ONCE per super-tile
        entry (cold_served flag), declines last_value and hash-scale group
        spaces — today's behavior bit-for-bit.

        Fused mode (`fused=True`, family first touch): serves ALL query
        families — last_value folds via run boundaries over the (pk, ts)
        sort (lexsort for unsorted memtails), hash-scale group spaces fold
        unique-compacted, and large sources chunk across a small thread
        pool — while the fused family build warms the device planes in the
        background.  Role-equivalent of the reference answering cold
        queries from its SST scan while the page cache warms."""
        if not passes.enabled("cold_host_serve", self.config):
            return None
        kernels = {_FUNC_TO_KERNEL[f] for f, _ in plan.agg_specs}
        compact = plan.num_groups > self._COLD_COMPACT_GROUPS
        has_last = "last" in kernels
        if has_last and not (
            fused and not compact and plan.bucket_col is None
            and plan.group_tags
        ):
            return None
        if compact and not fused:
            return None
        need_cols = self._plan_cols(plan)
        win_bounds = (
            (int(window[0]), int(window[1])) if window is not None else None
        )
        cold_entries = []
        for entry in super_entries:
            if not fused:
                dedup = entry.region_id in dedup_regions
                wt = (
                    entry.window_tiles.get((*win_bounds, dedup))
                    if win_bounds else None
                )
                wt_warm = wt is not None and all(
                    c in wt["cols"] or c in wt["limbs"] for c in need_cols
                )
                planes_warm = all(
                    c in entry.cols or ("" + c) in entry.limb_cols
                    for c in need_cols if c != COUNT_STAR
                )
                if wt_warm or planes_warm:
                    return None  # device path is warm: it wins
                if entry.cold_served:
                    return None  # second touch: let the device tiles build
            if entry.order is None:
                return None
            cold_entries.append(entry)
        if not cold_entries:
            # memtable-only sources: without an entry to carry the
            # cold_served flag (or a family build to warm) the router
            # would answer FOREVER and the device path would never engage
            return None

        n_buckets = max(plan.n_buckets, 1) if plan.bucket_col else 1
        origin = dyn_host["bucket_origin"]
        interval = dyn_host["bucket_interval"]
        num_groups = plan.num_groups
        per_col_aggs: dict[str, set] = {}
        for func, col in plan.agg_specs:
            per_col_aggs.setdefault(col, set()).add(_FUNC_TO_KERNEL[func])
        # dense [G] state arrays — NEVER in compact mode, where num_groups
        # is a hash-scale dense-space estimate (allocating it is exactly
        # what the unique-compacted fold exists to avoid)
        finals: dict[str, dict[str, np.ndarray]] = {}
        if not compact:
            finals["__presence"] = {"count": np.zeros(num_groups, np.int64)}
            for col, aggs in per_col_aggs.items():
                d = finals.setdefault(col, {})
                for agg in sorted(aggs | {"count"}):
                    if agg == "count":
                        d["count"] = np.zeros(num_groups, np.int64)
                    elif agg in ("sum", "avg"):
                        d.setdefault("sum", np.zeros(num_groups, np.float64))
                    elif agg == "min":
                        d["min"] = np.full(num_groups, np.inf)
                    elif agg == "max":
                        d["max"] = np.full(num_groups, -np.inf)

        filters = list(zip(plan.filters, dyn_host["filter_values"]))

        # state keys each output column needs ("last" rides last_state,
        # everything else the finals/partial arrays)
        want_aggs: dict[str, set] = {}
        for col, aggs in per_col_aggs.items():
            w = {"count"}
            for agg in aggs:
                if agg in ("sum", "avg"):
                    w.add("sum")
                elif agg in ("min", "max", "last"):
                    w.add(agg)
            want_aggs[col] = w

        # last_value dense states: per-group (ts, value, has) winners,
        # merged across sources/ranges IN ORDER so a ts tie resolves to
        # the LATER source — the device merge_states newer_or_tie rule
        last_cols = [c for c, aggs in per_col_aggs.items() if "last" in aggs]
        last_state = {
            c: (
                np.full(num_groups, np.iinfo(np.int64).min, np.int64),
                np.full(num_groups, np.nan),
                np.zeros(num_groups, bool),
            )
            for c in last_cols
        }

        BAIL = object()

        def _last_winners(g, t, v):
            # shared numpy twin of the device last kernel (executor.py);
            # None = unsorted beyond lexsort comfort -> device path
            w = host_last_winners(g, t, v)
            return BAIL if w is None else w

        def _merge_last(col_name, w):
            # fold one range's winners into the dense last state — always
            # called in source/range order, so a ts tie resolves to the
            # LATER source (the device merge_states newer_or_tie rule)
            wg, wt, wv = w
            if not len(wg):
                return
            lt, lv, lh = last_state[col_name]
            take = (~lh[wg]) | (wt >= lt[wg])
            tg = wg[take]
            lt[tg] = wt[take]
            lv[tg] = wv[take]
            lh[tg] = True

        def fold_range(get_col, ts_arr, keep, a, b, part=None):
            """Fold rows [a, b) of one source.  `part=None` (the
            sequential dense path) accumulates IN PLACE into the shared
            finals/last_state — the exact op sequence of the legacy fold,
            no transient [G] partials; a dict accumulates into fresh
            partial arrays (dense for the parallel path, unique-compacted
            + their keys in compact mode) merged in range order by the
            caller.  Returns BAIL when the source cannot serve (evicted
            host tile, out-of-range code)."""
            ts_r = ts_arr[a:b]
            if window is not None and use_ts:
                mask = (ts_r >= window[0]) & (ts_r < window[1])
            else:
                mask = np.ones(b - a, bool)
            if keep is not None:
                mask = mask & keep[a:b]
            for (name, op, _a), val in filters:
                if name == use_ts:
                    col = ts_r
                else:
                    got = get_col(name)
                    if got is None:
                        return BAIL
                    col, pres = got
                    col = col[a:b]
                    if pres is not None:
                        mask = mask & pres[a:b]
                mask = _np_filter(mask, col, op, val)
            if not mask.any():
                return {}
            idx = np.flatnonzero(mask)
            if a:
                idx = idx + a
            check_deadline()
            gid = np.zeros(len(idx), np.int64)
            for tag, card in zip(plan.group_tags, plan.tag_cards):
                got = get_col(tag)
                if got is None:
                    return BAIL
                codes = got[0][idx]
                if (codes < 0).any() or (codes >= card).any():
                    return BAIL  # out-of-range code: device path owns it
                gid = gid * card + codes.astype(np.int64)
            if plan.bucket_col is not None:
                bucket = ((ts_arr[idx] - origin) // interval).astype(np.int64)
                if (bucket < 0).any() or (bucket >= n_buckets).any():
                    in_b = (bucket >= 0) & (bucket < n_buckets)
                    idx, gid, bucket = idx[in_b], gid[in_b], bucket[in_b]
                gid = gid * n_buckets + bucket
            inplace = part is None and not compact
            if part is None:
                part = {}
            part["rows"] = len(gid)
            if compact:
                ukeys, gid = np.unique(gid, return_inverse=True)
                part["keys"] = ukeys
                size = len(ukeys)
            else:
                size = num_groups
            pb = np.bincount(gid, minlength=size).astype(np.int64)
            if inplace:
                finals["__presence"]["count"] += pb
            else:
                part["presence"] = pb
            cols_part = part["cols"] = {}
            for col_name, _aggs in per_col_aggs.items():
                want = want_aggs[col_name]
                if col_name == COUNT_STAR:
                    if inplace:
                        finals[col_name]["count"] += pb
                    else:
                        cols_part[col_name] = {"count": pb}
                    continue
                got = get_col(col_name)
                if got is None:
                    return BAIL
                vals, pres = got
                vsel = vals[idx].astype(np.float64)
                g = gid
                sel = None
                if pres is not None:
                    sel = pres[idx]
                else:
                    nan = np.isnan(vsel)
                    if nan.any():  # NULLs decoded as NaN must not fold in
                        sel = ~nan
                if sel is not None:
                    vsel, g = vsel[sel], g[sel]
                d: dict = finals[col_name] if inplace else {}
                if "count" in want:
                    cb = np.bincount(g, minlength=size).astype(np.int64)
                    if inplace:
                        d["count"] += cb
                    else:
                        d["count"] = cb
                if "sum" in want:
                    sb = np.bincount(g, weights=vsel, minlength=size)
                    if inplace:
                        d["sum"] += sb
                    else:
                        d["sum"] = sb
                if "min" in want:
                    if inplace:
                        np.minimum.at(d["min"], g, vsel)
                    else:
                        m = np.full(size, np.inf)
                        np.minimum.at(m, g, vsel)
                        d["min"] = m
                if "max" in want:
                    if inplace:
                        np.maximum.at(d["max"], g, vsel)
                    else:
                        m = np.full(size, -np.inf)
                        np.maximum.at(m, g, vsel)
                        d["max"] = m
                if "last" in want:
                    t_sel = ts_arr[idx]
                    if sel is not None:
                        t_sel = t_sel[sel]
                    w = _last_winners(g, t_sel, vsel)
                    if w is BAIL:
                        return BAIL
                    if inplace:
                        _merge_last(col_name, w)
                    else:
                        d["last"] = w
                if not inplace:
                    cols_part[col_name] = d
            return part

        def merge_dense(part):
            """Fold one range's partial into the shared finals — called in
            source/range ORDER, so accumulation order is deterministic
            (and bit-identical to the sequential legacy fold for a single
            full-source range)."""
            if not part:
                return
            finals["__presence"]["count"] += part["presence"]
            for col_name, d in part["cols"].items():
                tgt = finals[col_name]
                if "count" in d and "count" in tgt:
                    tgt["count"] += d["count"]
                if "sum" in d:
                    tgt["sum"] += d["sum"]
                if "min" in d:
                    np.minimum(tgt["min"], d["min"], out=tgt["min"])
                if "max" in d:
                    np.maximum(tgt["max"], d["max"], out=tgt["max"])
                if "last" in d:
                    _merge_last(col_name, d["last"])

        parts_compact: list = []
        compact_rows = [0]

        def fold_source(get_col, ts_arr, keep, n, parallel_ok):
            """Folds one whole source; False = bail to the device path."""
            if compact:
                step = self._COLD_PAR_ROWS
                for a in range(0, max(n, 1), step):
                    part = fold_range(
                        get_col, ts_arr, keep, a, min(a + step, n), part={}
                    )
                    if part is BAIL:
                        return False
                    if part.get("rows"):
                        compact_rows[0] += part["rows"]
                        if compact_rows[0] > self._COLD_COMPACT_MAX_ROWS:
                            return False  # too many rows to unique-fold
                        parts_compact.append(part)
                return True
            if (
                fused
                and parallel_ok
                and n >= 2 * self._COLD_PAR_ROWS
                and num_groups <= (1 << 20)
            ):
                # chunk the source across a small pool: every numpy op in
                # the fold releases the GIL, so ranges fold concurrently;
                # partials merge in RANGE ORDER (deterministic result)
                from concurrent.futures import ThreadPoolExecutor

                from ..utils.deadline import propagate

                # prefetch shared columns on this thread so workers hit
                # the source cache instead of racing the same decode
                prefetch = list(dict.fromkeys(
                    [f[0][0] for f in filters if f[0][0] != use_ts]
                    + list(plan.group_tags)
                    + [c for c in per_col_aggs if c != COUNT_STAR]
                ))
                for name in prefetch:
                    if get_col(name) is None:
                        return False
                ranges = [
                    (a, min(a + self._COLD_PAR_ROWS, n))
                    for a in range(0, n, self._COLD_PAR_ROWS)
                ]
                workers = min(4, os.cpu_count() or 1, len(ranges))
                with ThreadPoolExecutor(
                    max_workers=workers, thread_name_prefix="cold-serve"
                ) as pool:
                    parts = list(pool.map(
                        propagate(
                            lambda r: fold_range(
                                get_col, ts_arr, keep, *r, part={}
                            )
                        ),
                        ranges,
                    ))
                if any(p is BAIL for p in parts):
                    return False
                for p in parts:
                    merge_dense(p)
                return True
            # sequential dense: accumulate straight into finals (the
            # legacy op sequence — no transient [G] partials)
            return fold_range(get_col, ts_arr, keep, 0, n) is not BAIL

        for entry in cold_entries:
            check_deadline()  # full-column host pass per region
            if use_ts and use_ts not in entry.sorted_host:
                return None
            n = entry.num_rows
            ts_arr = (
                np.asarray(entry.sorted_host[use_ts])
                if use_ts else np.zeros(n, np.int64)
            )
            keep = None
            if entry.region_id in dedup_regions:
                if not self.cache.ensure_dedup_keep(entry):
                    return None
                keep = entry.keep_host
            if fused and not entry.persisted_cols and self.cache.persist_dir:
                # no-wait mmap attach: value columns then page off the
                # persisted consolidation instead of a per-file re-gather
                self.cache.attach_persisted(entry)
            col_cache: dict[str, object] = {}

            def get_col(name, _e=entry, _cache=col_cache, _n=n):
                # every source normalizes to length num_rows: persisted
                # consolidations are pow2-PADDED on disk, and a padded
                # array would broadcast-crash against the row mask
                if name in _cache:
                    return _cache[name]
                if name in _e.sorted_host:
                    got = (np.asarray(_e.sorted_host[name])[:_n], None)
                elif name in _e.persisted_cols:
                    pres = _e.persisted_nulls.get(name)
                    got = (
                        np.asarray(_e.persisted_cols[name])[:_n],
                        None if pres is None else np.asarray(pres)[:_n],
                    )
                else:
                    got = self.cache.gather_host_values(
                        _e, name, np.asarray(_e.order, np.int64)
                    )
                    if got is not None and len(got[0]) != _n:
                        got = (
                            got[0][:_n],
                            None if got[1] is None else got[1][:_n],
                        )
                _cache[name] = got
                return got

            if not fold_source(get_col, ts_arr, keep, n, True):
                return None

        for _region, mem_table in mem_slots:
            need = list(dict.fromkeys(
                list(plan.group_tags)
                + ([use_ts] if use_ts else [])
                + [c for c in value_cols if c in need_cols]
            ))
            for name in need:
                if name not in mem_table.column_names:
                    return None
            built = _encode_host_tiles(
                ctx.dictionary, mem_table, need, all_tag_cols, use_ts
            )
            if built is None:
                return None
            mcols, mnulls, _e, _b = built
            n = mem_table.num_rows
            ts_arr = mcols[use_ts] if use_ts else np.zeros(n, np.int64)

            def get_mem_col(name, _mcols=mcols, _mnulls=mnulls):
                if name not in _mcols:
                    return None
                return _mcols[name], _mnulls.get(name)

            if not fold_source(get_mem_col, ts_arr, None, n, False):
                return None

        if compact:
            # hash-scale group space: stitch the unique-compacted partials
            # into one gid-ascending compact result (the same order the
            # hash assembly produces — empty groups never existed)
            if not parts_compact:
                allk = np.zeros(0, np.int64)
            else:
                allk = np.unique(
                    np.concatenate([p["keys"] for p in parts_compact])
                )
            finals_c: dict[str, dict[str, np.ndarray]] = {
                "__presence": {"count": np.zeros(len(allk), np.int64)}
            }
            for col, aggs in per_col_aggs.items():
                d = finals_c.setdefault(col, {})
                for agg in sorted(want_aggs[col]):
                    if agg == "count":
                        d["count"] = np.zeros(len(allk), np.int64)
                    elif agg == "sum":
                        d.setdefault("sum", np.zeros(len(allk), np.float64))
                    elif agg == "min":
                        d["min"] = np.full(len(allk), np.inf)
                    elif agg == "max":
                        d["max"] = np.full(len(allk), -np.inf)
            for p in parts_compact:
                pos = np.searchsorted(allk, p["keys"])
                finals_c["__presence"]["count"][pos] += p["presence"]
                for col_name, d in p["cols"].items():
                    tgt = finals_c[col_name]
                    if "count" in d and "count" in tgt:
                        tgt["count"][pos] += d["count"]
                    if "sum" in d:
                        tgt["sum"][pos] += d["sum"]
                    if "min" in d:
                        tgt["min"][pos] = np.minimum(tgt["min"][pos], d["min"])
                    if "max" in d:
                        tgt["max"][pos] = np.maximum(tgt["max"][pos], d["max"])
            for col, aggs in per_col_aggs.items():
                d = finals_c[col]
                if "avg" in aggs:
                    cnt = d.get("count", finals_c["__presence"]["count"])
                    d["avg"] = d["sum"] / np.maximum(cnt, 1)
            for entry in cold_entries:
                entry.cold_served = True
            nz = np.flatnonzero(finals_c["__presence"]["count"] > 0)
            cols_out = self._group_key_columns(plan, ctx, dyn_host, allk[nz])
            return pa.table(
                self._append_agg_columns(cols_out, finals_c, plan, nz)
            )

        for col, aggs in per_col_aggs.items():
            d = finals[col]
            if "avg" in aggs:
                cnt = d.get("count", finals["__presence"]["count"])
                d["avg"] = d["sum"] / np.maximum(cnt, 1)
        for col in last_cols:
            finals[col]["last"] = last_state[col][1]
        for entry in cold_entries:
            entry.cold_served = True
        return self._assemble_result(finals, plan, ctx, dyn_host)

    def _host_execute(
        self, plan, dyn_host, super_entries, mem_slots,
        schema, ctx, use_ts, pk, value_cols, all_tag_cols,
        dedup_regions=frozenset(), hints=None,
    ):
        """Selective pk-equality fast path: returns the result table, or
        None when the query shape/size doesn't qualify.  `hints` (optional
        dict) reports routing facts to the caller — `wide_cold` marks a
        wide multi-key slice served from host ONLY because its device
        planes aren't resident yet (the fused planner then warms them in
        the background)."""
        if plan.group_tags or not pk:
            return None  # only scalar / bucket-grouped outputs
        if any(_FUNC_TO_KERNEL[f] == "last" for f, _ in plan.agg_specs):
            return None
        pk0 = pk[0]
        # split filters: pk0 equalities select row ranges; everything else
        # is a residual mask on the slice
        eq_codes: set[int] | None = None
        residual: list[tuple[str, str, object]] = []
        for (name, op, _arity), val in zip(plan.filters, dyn_host["filter_values"]):
            if name == pk0 and op == "=":
                codes = {int(val)}
                eq_codes = codes if eq_codes is None else (eq_codes & codes)
            elif name == pk0 and op == "in":
                codes = {int(v) for v in val}
                eq_codes = codes if eq_codes is None else (eq_codes & codes)
            elif name == pk0 and op == "!=":
                if eq_codes is not None:
                    eq_codes.discard(int(val))
                else:
                    residual.append((name, op, val))
            else:
                residual.append((name, op, val))
        if not eq_codes:
            return None
        # residuals must be computable on the slice: ts, pk codes, values
        for name, _op, _v in residual:
            if name != use_ts and name not in pk and name not in value_cols:
                return None

        n_buckets = plan.n_buckets if plan.bucket_col else 1
        origin = dyn_host["bucket_origin"]
        interval = dyn_host["bucket_interval"]

        # explicit ts bounds from the pushed-down window: rows are
        # (pk, ts)-sorted, so each pk run narrows by two more binary
        # searches — without this the slice scales with the table's
        # retention (72 h of history made a 1 h-window query 4x slower)
        ts_lo = ts_hi = None
        if use_ts:
            for (name, op, _a), val in zip(plan.filters, dyn_host["filter_values"]):
                if name != use_ts:
                    continue
                if op == ">=":
                    ts_lo = val if ts_lo is None else max(ts_lo, val)
                elif op == ">":
                    ts_lo = val + 1 if ts_lo is None else max(ts_lo, val + 1)
                elif op == "<":
                    ts_hi = val if ts_hi is None else min(ts_hi, val)
                elif op == "<=":
                    ts_hi = val + 1 if ts_hi is None else min(ts_hi, val + 1)

        # row ranges per (entry, code) + total-size guard
        ranges: list[tuple[object, int, int]] = []
        total = 0
        for entry in super_entries:
            if entry.order is None or pk0 not in entry.sorted_host:
                return None
            if use_ts and use_ts not in entry.sorted_host:
                return None  # entry predates ts-inclusive sorting
            arr = entry.sorted_host[pk0]
            ts_arr = entry.sorted_host[use_ts] if use_ts else None
            # one vectorized dtype-matched search for all codes: a python
            # int scalar makes numpy value-cast the whole 4 M-row array
            # per call (measured ~1.2 ms each)
            codes_sorted = np.asarray(sorted(eq_codes), dtype=arr.dtype)
            lefts = np.searchsorted(arr, codes_sorted, side="left")
            rights = np.searchsorted(arr, codes_sorted, side="right")
            for a, b in zip(lefts.tolist(), rights.tolist()):
                if a >= b:
                    continue
                # ts is only sorted WITHIN a pk run when pk == (pk0,):
                # more pk columns interleave their own runs
                if (
                    ts_arr is not None
                    and len(pk) == 1
                    and (ts_lo is not None or ts_hi is not None)
                ):
                    run = ts_arr[a:b]
                    if ts_lo is not None:
                        a += int(np.searchsorted(run, ts_lo, side="left"))
                    if ts_hi is not None:
                        b = (
                            b - len(run)
                            + int(np.searchsorted(run, ts_hi, side="left"))
                        )
                if a < b:
                    ranges.append((entry, a, b))
                    total += b - a
        if total > self._HOST_PATH_MAX_ROWS:
            return None

        per_col_aggs: dict[str, set] = {}
        for func, col in plan.agg_specs:
            per_col_aggs.setdefault(col, set()).add(_FUNC_TO_KERNEL[func])

        # Multi-key wide slices (TSBS cpu-max-all-8: 8 hosts x 10 value
        # columns) leave the host path once the device planes are warm:
        # the numpy pass scales with keys x columns ON THE FRONTEND
        # THREAD, so under concurrency it contends for the very CPU the
        # admission layer is protecting, while the warm tile dispatch is
        # flat.  Single-key probes (cpu-max-all-1, high-cpu-1) keep the
        # zero-round-trip host serve; cold planes keep it too — an upload
        # would cost more than the slice.
        plan_value_cols = [
            c for c in per_col_aggs if c != COUNT_STAR
        ]
        if (
            len(eq_codes) > 1
            and total * max(len(plan_value_cols), 1) > self._HOST_PATH_MAX_CELLS
        ):
            warm = super_entries and all(
                all(
                    c in e.cols
                    or ("" + c) in e.limb_cols
                    or any(
                        c in wt["cols"] or c in wt["limbs"]
                        for wt in e.window_tiles.values()
                    )
                    for c in plan_value_cols
                )
                for e in super_entries
            )
            if warm:
                passes.note(
                    "host_fast_path", False,
                    f"{len(eq_codes)}-key x {len(plan_value_cols)}-column "
                    "slice with warm device planes: tile dispatch beats "
                    "the contention-sensitive host pass",
                    keys=len(eq_codes), rows=total,
                )
                return None
            if hints is not None:
                hints["wide_cold"] = True

        finals: dict[str, dict[str, np.ndarray]] = {
            "__presence": {"count": np.zeros(n_buckets, np.int64)}
        }
        for col, aggs in per_col_aggs.items():
            d = finals.setdefault(col, {})
            for agg in sorted(aggs | {"count"}):
                if agg == "count":
                    d["count"] = np.zeros(n_buckets, np.int64)
                elif agg in ("sum", "avg"):
                    d.setdefault("sum", np.zeros(n_buckets, np.float64))
                elif agg == "min":
                    d["min"] = np.full(n_buckets, np.inf)
                elif agg == "max":
                    d["max"] = np.full(n_buckets, -np.inf)

        def accumulate(get_col, ts_arr, base_mask, n):
            """get_col(name) -> (values, present|None); accumulates into
            finals.  Shared by SST slices and memtable tails."""
            mask = base_mask
            for name, op, val in residual:
                if name == use_ts:
                    col = ts_arr
                else:
                    got = get_col(name)
                    if got is None:
                        return False
                    col, pres = got
                    if pres is not None:
                        mask = mask & pres
                mask = _np_filter(mask, col, op, val)
            if plan.bucket_col is not None:
                bucket = ((ts_arr - origin) // interval).astype(np.int64)
                in_b = (bucket >= 0) & (bucket < n_buckets)
                mask = mask & in_b
                bucket = np.clip(bucket, 0, n_buckets - 1)
            else:
                bucket = np.zeros(n, np.int64)
            if not mask.any():
                return True
            bsel = bucket[mask]
            finals["__presence"]["count"] += np.bincount(
                bsel, minlength=n_buckets
            ).astype(np.int64)
            for col_name, aggs in per_col_aggs.items():
                if col_name == COUNT_STAR:
                    finals[col_name]["count"] += np.bincount(
                        bsel, minlength=n_buckets
                    ).astype(np.int64)
                    continue
                got = get_col(col_name)
                if got is None:
                    return False
                vals, pres = got
                cmask = mask if pres is None else (mask & pres)
                vsel = vals[cmask].astype(np.float64)
                bs = bucket[cmask]
                d = finals[col_name]
                if "count" in d:
                    d["count"] += np.bincount(bs, minlength=n_buckets).astype(np.int64)
                if "sum" in d:
                    d["sum"] += np.bincount(bs, weights=vsel, minlength=n_buckets)
                if "min" in d:
                    np.minimum.at(d["min"], bs, vsel)
                if "max" in d:
                    np.maximum.at(d["max"], bs, vsel)
            return True

        for entry, a, b in ranges:
            positions = entry.order[a:b].astype(np.int64)
            cache: dict[str, object] = {}

            def get_col(name, _entry=entry, _pos=positions, _a=a, _b=b, _cache=cache):
                if name in _cache:
                    return _cache[name]
                if name in _entry.sorted_host:
                    got = (_entry.sorted_host[name][_a:_b], None)
                elif name in _entry.persisted_cols:
                    # persisted consolidations are already in sorted
                    # order: slice directly, no per-file gather
                    pres = _entry.persisted_nulls.get(name)
                    got = (
                        np.asarray(_entry.persisted_cols[name][_a:_b]),
                        None if pres is None else np.asarray(pres[_a:_b]),
                    )
                else:
                    got = self.cache.gather_host_values(_entry, name, _pos)
                _cache[name] = got
                return got

            ts_arr = (
                entry.sorted_host[use_ts][a:b] if use_ts else np.zeros(b - a, np.int64)
            )
            base = np.ones(b - a, bool)
            if entry.region_id in dedup_regions:
                # last-write-wins: stale versions are masked, same plane
                # the device path ANDs in (ensure_dedup_keep)
                if not self.cache.ensure_dedup_keep(entry):
                    return None
                base &= entry.keep_host[a:b]
            if not accumulate(get_col, ts_arr, base, b - a):
                return None

        for _region, mem_table in mem_slots:
            need = list(
                dict.fromkeys(
                    [pk0]
                    + ([use_ts] if use_ts else [])
                    + value_cols
                    + [n for n, _o, _v in residual if n in pk]
                )
            )
            for name in need:
                if name not in mem_table.column_names:
                    return None
            built = _encode_host_tiles(
                ctx.dictionary, mem_table, need, all_tag_cols + pk, use_ts
            )
            if built is None:
                return None
            mcols, mnulls, _e, _b = built
            codes_arr = mcols[pk0]
            sel = np.isin(codes_arr, list(eq_codes))
            ts_arr = (
                mcols[use_ts] if use_ts else np.zeros(mem_table.num_rows, np.int64)
            )

            def get_mem_col(name, _mcols=mcols, _mnulls=mnulls):
                if name not in _mcols:
                    return None
                return _mcols[name], _mnulls.get(name)

            if not accumulate(get_mem_col, ts_arr, sel, mem_table.num_rows):
                return None

        # avg + non-finite cleanup to match the device finalize
        for col, aggs in per_col_aggs.items():
            d = finals[col]
            if "avg" in aggs:
                cnt = d.get("count", finals["__presence"]["count"])
                d["avg"] = d["sum"] / np.maximum(cnt, 1)
        return self._assemble_result(finals, plan, ctx, dyn_host)

    def _fetch_result(self, packed):
        """ONE logical device->host fetch of the packed result trio.
        Large results stream as chunked device_gets with transfer
        overlapping the host-side copy (query.streamed_readback); small
        results keep the single batched device_get — extra crossings
        would cost more than the overlap saves."""
        from .executor import streamed_device_get

        chunk = max(int(getattr(self.config, "readback_chunk_kb", 1024)), 64) << 10
        total = sum(
            int(np.prod(p.shape)) * np.dtype(p.dtype).itemsize for p in packed
        )
        streamed = (
            getattr(self.config, "streamed_readback", True)
            and passes.enabled("streamed_readback", self.config)
            and total >= 2 * chunk
        )
        if streamed:
            out = device_health.supervised_call(
                "readback",
                lambda: streamed_device_get(list(packed), chunk),
            )
            metrics.TPU_READBACK_STREAMED.inc()
            passes.note(
                "streamed_readback", True,
                f"{total >> 10} KiB fetched as ~{chunk >> 10} KiB slices "
                "overlapped with the host copy",
                bytes=total,
            )
            return tuple(np.asarray(p) for p in out)
        got = device_health.supervised_call(
            "readback", lambda: jax.device_get(packed)
        )
        return tuple(np.asarray(p) for p in got)

    def _finalize(
        self, packed, int_layout, acc32_layout, acc64_layout, int_dtype,
        plan, lowering, schema, ctx, dyn_host, spec=None,
    ):
        if _defer_fetch_active() and not _in_fused_build():
            # batch-leader mode: the dispatch is in flight on the device
            # stream; hand back the output leaves + the decode
            # continuation so the batcher can fetch EVERY member's
            # results in one device_get.  The leaves are the program's
            # own output buffers — plane eviction only drops references,
            # so they stay alive until the mega-fetch lands.
            return PendingFetch(
                leaves=packed,
                finish=functools.partial(
                    self._finish_fetched, int_layout, acc32_layout,
                    acc64_layout, int_dtype, plan, lowering, schema, ctx,
                    dyn_host, spec,
                ),
            )
        # ONE logical host fetch total, regardless of how many aggregates
        # ran; transfer and host-decode are metered separately so
        # streamed-readback wins stay attributable (the combined
        # readback_ms conflates link time with waiting out the dispatch).
        # The span carries both figures: on an async dispatch the transfer
        # time here INCLUDES waiting out the device compute, which is what
        # makes readback the honest place to look for slow dispatches.
        # `tile.readback` holds the fetch and, as its child stage,
        # `tile.decode`: its self time is the fetch.  The two stages' clocks
        # are the only ones here; histograms, the flight recorder, EXPLAIN
        # ANALYZE and the stage counters all read them.
        dec = tracing.stage("tile.decode")
        fetched = None
        try:
            with tracing.span("tile.readback") as rb_span:
                fetched = self._fetch_result(packed)
                # compact (device-finalize) results are ONE flat buffer —
                # the f64 rows ride it as packed bit pairs; full-buffer
                # results keep the (buf, accs64) pair
                buf = fetched[0]
                accs64 = fetched[1] if len(fetched) > 1 else None
                # hash strategy ships the slot->gid key table as a third part
                table_keys = fetched[2] if len(fetched) > 2 else None
                nbytes = int(sum(p.nbytes for p in fetched))
                rb_span.attributes["bytes"] = nbytes
                rb_span.attributes["device_finalize"] = bool(
                    getattr(lowering, "post_done", None)
                )
                with dec:
                    return self._decode_result(
                        buf, accs64, int_layout, acc32_layout, acc64_layout,
                        int_dtype, plan, lowering, ctx, dyn_host, spec,
                        table_keys=table_keys,
                    )
        finally:
            if fetched is not None:
                dec_ms = (dec.duration_s or 0.0) * 1000.0
                ms = rb_span.duration() * 1000.0 - dec_ms
                if not _in_fused_build():
                    # the builder's priming fetch stays out of the per-query
                    # readback accounting (bench + EXPLAIN read deltas)
                    metrics.TPU_READBACK_MS.observe(ms)
                    metrics.TPU_READBACK_TRANSFER_MS.observe(ms)
                    metrics.TPU_READBACK_BYTES.inc(nbytes)
                    metrics.TPU_DEVICE_FETCHES.inc()
                metrics.TPU_READBACK_DECODE_MS.observe(dec_ms)
                self._rb_local.transfer_ms = ms
                self._rb_local.decode_ms = dec_ms
                rb_span.attributes["transfer_ms"] = round(ms, 3)
                rb_span.attributes["decode_ms"] = round(dec_ms, 3)
                flight_recorder.stage_add("readback_transfer", ms)
                flight_recorder.stage_add("readback_decode", dec_ms)
                flight_recorder.add_bytes(down=nbytes)

    def _fused_dispatch(self, cds):
        """Dispatch N captured members as ONE fused XLA invocation and
        decode each member from the shared readback.  Returns (tables,
        info): tables[i] is member i's decoded result — None means a
        rerun verdict or decode failure, and that member degrades to a
        solo run.  Raises on any trace/compile/dispatch failure: the
        batcher then degrades the WHOLE tick to the per-member packed
        path, which owns the HBM halve-and-retry ladder — a multi-member
        RESOURCE_EXHAUSTED retried at mega granularity would just
        exhaust again, while per-member dispatches retry at a size the
        emergency release can actually satisfy."""
        # canonicalize the multiset: member order inside the program is
        # sort-by-key, so {A,B} and {B,A} ticks share one compile
        order = sorted(range(len(cds)), key=lambda i: repr(cds[i].key))
        keys = tuple(cds[i].key for i in order)
        with _program_cache_lock, tracing.span("tile.compile") as s:
            t0 = time.perf_counter()
            before = _mega_program.cache_info().misses
            fused = _mega_program(keys)
            if _mega_program.cache_info().misses > before:
                metrics.TPU_COMPILE_CACHE_MISSES.inc()
                s.attributes["cache"] = "miss"
            else:
                metrics.TPU_COMPILE_CACHE_HITS.inc()
                s.attributes["cache"] = "hit"
            compile_ms = (time.perf_counter() - t0) * 1000.0
        inputs = []
        for i in order:
            cd = cds[i]
            # same host-side dynamic-input assembly as run_all, so the
            # traced values match the solo dispatch dtype-for-dtype
            hv = jnp.asarray(
                cd.dyn.get("having_values") or (0.0,), jnp.float64
            )
            pdyn = {
                k: cd.dyn[k]
                for k in ("filter_values", "bucket_origin", "bucket_interval")
            }
            inputs.append((cd.sources, pdyn, hv))
        if len(self.cache.devices) > 1:
            # non-mesh chunk placement round-robins planes over local
            # devices, but one jit needs colocated inputs: hop every
            # member's planes to device 0 (a no-op for leaves already
            # there).  pdyn/hv stay host-side so their weak-typing
            # matches the solo run_all trace exactly.
            dev0 = self.cache.devices[0]
            inputs = device_health.supervised_call(
                "upload",
                lambda: [
                    (jax.device_put(sources, dev0), pdyn, hv)
                    for sources, pdyn, hv in inputs
                ],
                devices=(0,),
            )
        traces0 = _MEGA_STATS["traces"]
        metrics.TPU_DEVICE_DISPATCHES.inc()
        if any(cd.key[0].series_slots for cd in cds):
            metrics.TILE_SERIES_GIDS.inc()
        if any(cd.key[0].partitioned for cd in cds):
            metrics.TILE_ORDINAL_GIDS.inc()
        for cd in cds:
            metrics.TILE_STAGE1_SERIES.inc(cd.dyn.get("series", 0))
        with tracing.span("tile.fused_dispatch", members=len(cds)) as disp:
            packed_all = device_health.supervised_call(
                "dispatch", lambda: fused(tuple(inputs))
            )
        dispatch_ms = disp.duration() * 1000.0
        leaves = [a for packed in packed_all for a in packed]
        with tracing.span("tile.batch_readback", members=len(cds)) as rb:
            fetched = device_health.supervised_call(
                "readback", lambda: jax.device_get(leaves)
            )
        transfer_ms = rb.duration() * 1000.0
        tables = [None] * len(cds)
        off = 0
        for pos, i in enumerate(order):
            cd = cds[i]
            part = fetched[off : off + len(packed_all[pos])]
            off += len(packed_all[pos])
            # the per-member lowering counters the capture deferred:
            # exactly one inc per member now that the fused path answers
            metrics.TILE_LOWERED_TOTAL.inc()
            metrics.AGG_STRATEGY_TOTAL.inc(strategy=cd.key[0].agg_strategy)
            try:
                tables[i] = cd.finish(part)
            except Exception:  # noqa: BLE001 — this member degrades solo
                tables[i] = None
        info = {
            "traced": _MEGA_STATS["traces"] > traces0,
            "stages_ms": {
                "compile": compile_ms,
                "dispatch": dispatch_ms,
                "readback_transfer": transfer_ms,
            },
            "bytes_down": int(sum(getattr(a, "nbytes", 0) for a in fetched)),
        }
        return tables, info

    def _finish_fetched(
        self, int_layout, acc32_layout, acc64_layout, int_dtype, plan,
        lowering, schema, ctx, dyn_host, spec, fetched,
    ):
        """Deferred-fetch continuation: everything `_finalize` does AFTER
        `_fetch_result`, applied to leaves the batcher already brought
        home inside the mega-readback.  Returns the decoded table, or
        None on a rerun verdict (the member then degrades to solo)."""
        fetched = tuple(np.asarray(p) for p in fetched)
        buf = fetched[0]
        accs64 = fetched[1] if len(fetched) > 1 else None
        table_keys = fetched[2] if len(fetched) > 2 else None
        metrics.TPU_READBACK_BYTES.inc(sum(p.nbytes for p in fetched))
        dec = tracing.stage("tile.decode")
        try:
            with dec:
                return self._decode_result(
                    buf, accs64, int_layout, acc32_layout, acc64_layout,
                    int_dtype, plan, lowering, ctx, dyn_host, spec,
                    table_keys=table_keys,
                )
        finally:
            dec_ms = dec.duration_s * 1000.0
            metrics.TPU_READBACK_DECODE_MS.observe(dec_ms)
            self._rb_local.decode_ms = dec_ms

    def _decode_result(
        self, buf, accs64, int_layout, acc32_layout, acc64_layout,
        int_dtype, plan, lowering, ctx, dyn_host, spec, table_keys=None,
    ):
        is_hash = plan.agg_strategy == "hash"
        if is_hash and buf[-1] != 0:
            # slot-table overflow: the distinct-key estimate was badly
            # low; the caller reruns on the dense path (never wrong)
            metrics.AGG_HASH_OVERFLOW.inc()
            return None
        if plan.acc_dtype == "limb" and self._limb_sum_cols(plan):
            if buf[-1] == 0:
                # quantization-error bound exceeded _LIMB_VERDICT_RTOL of
                # some group's sum (mixed-magnitude data sharing blocks, a
                # group of a few small rows): caller must rerun with exact
                # f64 accumulation
                metrics.TILE_LIMB_RERUNS.inc()
                return None
        if spec is not None:
            g = spec.cap
        elif is_hash:
            g = plan.hash_slots
        else:
            g = plan.num_groups
        # the words' bytes (little-endian on host and device alike)
        buf = np.ascontiguousarray(buf).view(np.uint8)
        bit_packed = int_dtype == jnp.uint8
        int_row = -(-g // 32) * 4 if bit_packed else g
        ni = len(int_layout)
        off = ni * int_row * (1 if bit_packed else 4)
        ints = np.frombuffer(
            buf[:off].tobytes(), np.uint8 if bit_packed else np.int32
        ).reshape(ni, int_row)
        n32 = len(acc32_layout)
        accs32 = np.frombuffer(
            buf[off : off + n32 * g * 4].tobytes(), np.float32
        ).reshape(n32, g)
        off += n32 * g * 4
        sel = n_out = None
        if spec is not None:
            sel = np.frombuffer(
                buf[off : off + g * 4].tobytes(), np.int32
            )
            off += g * 4
            n_out = int(np.frombuffer(buf[off : off + 4].tobytes(), np.int32)[0])
            off += 4
            if acc64_layout:
                # f64 rows rode the flat buffer as IEEE bit pairs
                # (pack_f64_bits): decode back to float64 on the host
                from ..ops.aggregate import unpack_f64_bits

                n64 = len(acc64_layout)
                pairs = np.frombuffer(
                    buf[off : off + n64 * g * 8].tobytes(), np.int32
                ).reshape(n64, g, 2)
                off += n64 * g * 8
                accs64 = unpack_f64_bits(pairs)
        finals: dict[str, dict[str, np.ndarray]] = {}
        for i, (col, agg) in enumerate(int_layout):
            row = ints[i]
            if bit_packed:
                row = np.unpackbits(row)[:g].astype(np.int64)
            finals.setdefault(col, {})[agg] = row
        for i, (col, agg) in enumerate(acc32_layout):
            finals.setdefault(col, {})[agg] = accs32[i].astype(np.float64)
        for i, (col, agg) in enumerate(acc64_layout):
            finals.setdefault(col, {})[agg] = accs64[i]
        if spec is not None:
            table = self._assemble_compact(
                finals, plan, ctx, dyn_host, sel, n_out, spec
            )
            # the device consumed these post-ops: the host replay
            # (tpu_exec._run_post_ops) must skip exactly them
            lowering.post_done = dyn_host.get("post_consumed", frozenset())
            metrics.TPU_DEVICE_FINALIZE.inc()
            passes.note(
                "device_finalize", True,
                "Sort/LIMIT/HAVING + compaction ran on device: fetch is "
                "O(rows_out)",
                rows_out=table.num_rows, cap=spec.cap,
                groups=plan.num_groups,
                fetched_bytes=buf.nbytes,
            )
            return table
        if is_hash:
            return self._assemble_hash_result(
                finals, plan, ctx, dyn_host, table_keys
            )
        return self._assemble_result(finals, plan, ctx, dyn_host)

    def _group_key_columns(self, plan, ctx, dyn_host, gids) -> dict:
        """gid vector -> ordered {tag..., bucket} output columns: the
        mixed-radix decode shared by every compact assembly (identical to
        GroupByResult.to_table's, so all paths agree byte-for-byte)."""
        cols: dict[str, object] = {}
        dims: list[tuple[str, int]] = list(zip(plan.group_tags, plan.tag_cards))
        if plan.bucket_col is not None:
            dims.append(("__bucket", plan.n_buckets))
        decoded = {}
        div = 1
        for name, card in reversed(dims):
            decoded[name] = (gids // div) % card
            div *= card
        for tag in plan.group_tags:
            values = ctx.dictionary.values(tag)
            codes = decoded[tag]
            cols[tag] = [values[c] if c < len(values) else None for c in codes]
        if plan.bucket_col is not None:
            cols[plan.bucket_col] = (
                dyn_host["bucket_origin"]
                + decoded["__bucket"].astype(np.int64) * dyn_host["bucket_interval"]
            )
        return cols

    @staticmethod
    def _append_agg_columns(cols, finals, plan, indexer):
        """Append the per-agg-spec output columns, rows taken via
        `indexer` (a slice or fancy index into the finalized buffers) —
        ONE copy of the count-sharing/NULL-gating/naming contract the
        compact and hash assemblies must keep in lockstep."""
        presence = finals["__presence"]["count"]
        for func, col in plan.agg_specs:
            out = finals.get(col, {})
            kernel = _FUNC_TO_KERNEL[func]
            arr = out.get(kernel)
            if arr is None and kernel == "count":
                arr = presence  # count-pass sharing: presence IS the count
            arr = np.asarray(arr)[indexer]
            col_count = np.asarray(out.get("count", presence))[indexer]
            if col == COUNT_STAR:
                cols["count(*)"] = pa.array(arr.astype(np.int64))
            elif func == "count":
                cols[f"count({col})"] = pa.array(arr.astype(np.int64))
            else:
                vals = np.where(col_count > 0, arr, np.nan)
                cols[f"{func}({col})"] = pa.array(vals, mask=np.isnan(vals))
        return cols

    def _assemble_hash_result(self, finals, plan, ctx, dyn_host, table_keys):
        """[K, hash_slots] buffers + the slot->gid key table -> SQL rows.

        Bit-for-bit twin of the dense `_assemble_result` + to_table pair:
        occupied slots are ordered by their group id ASCENDING (exactly
        the order the dense path's nonzero scan over [G] produces), tags
        and buckets decode from the gid with the same mixed radix, and
        NULL gating/naming are shared verbatim — the only difference is
        that empty groups never existed to be skipped."""
        keys = np.asarray(table_keys, dtype=np.int64)
        presence = np.asarray(finals["__presence"]["count"])
        occ = (keys >= 0) & (presence[: keys.shape[0]] > 0)
        slot_idx = np.nonzero(occ)[0]
        order = np.argsort(keys[slot_idx], kind="stable")
        slots = slot_idx[order]
        cols = self._group_key_columns(plan, ctx, dyn_host, keys[slots])
        return pa.table(self._append_agg_columns(cols, finals, plan, slots))

    def _assemble_compact(
        self, finals, plan, ctx, dyn_host, sel, n_out, spec
    ):
        """Compact [K, cap] buffers + selected group ids -> SQL rows in
        DEVICE order (the consumed Sort/Limit already ordered and
        truncated them).  Same naming and NULL-gating as
        `_assemble_result`; the host's only remaining work is the
        offset/limit slice and the tag/bucket decode over rows_out ids."""
        rows_avail = max(min(n_out, spec.cap), 0)
        start, stop = 0, rows_avail
        if spec.limit is not None:
            start = min(spec.offset, rows_avail)
            stop = min(start + spec.limit, rows_avail)
        sl = slice(start, stop)
        idx = np.asarray(sel[sl], np.int64)
        cols = self._group_key_columns(plan, ctx, dyn_host, idx)
        return pa.table(self._append_agg_columns(cols, finals, plan, sl))

    def _assemble_result(self, finals, plan, ctx, dyn_host):
        """Shared [G]-state -> SQL rows assembly for the device and host
        fast paths (identical NULL-gating and naming semantics)."""
        outputs: dict[str, np.ndarray] = {}
        presence = finals["__presence"]["count"]
        non_empty = presence > 0
        for func, col in plan.agg_specs:
            out = finals.get(col, {})
            kernel = _FUNC_TO_KERNEL[func]
            arr = out.get(kernel)
            if arr is None and kernel == "count":
                arr = presence  # count-pass sharing: presence IS the count
            arr = np.asarray(arr)
            # NULL gating: nullable columns carry their own count row;
            # non-nullable columns have count == presence by construction
            col_count = out.get("count", presence)
            if col == COUNT_STAR:
                outputs["count(*)"] = arr.astype(np.int64)
            elif func == "count":
                outputs[f"count({col})"] = arr.astype(np.int64)
            else:
                outputs[f"{func}({col})"] = np.where(col_count > 0, arr, np.nan)
        tag_values = {t: ctx.dictionary.values(t) for t in plan.group_tags}
        result = GroupByResult(
            outputs=outputs,
            non_empty=non_empty,
            tag_values=tag_values,
            plan=plan,
            bucket_origin=dyn_host["bucket_origin"],
            bucket_interval=dyn_host["bucket_interval"],
        )
        return result.to_table()


def _device_memory_stats() -> dict:
    """Best-effort live-HBM numbers for OOM diagnostics (the budget is
    our accounting; this is the runtime's)."""
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
        return {
            k: stats[k]
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
            if k in stats
        }
    except Exception:  # noqa: BLE001 — diagnostics only
        return {}


def _quantize_soft(n: int) -> int:
    """Round up keeping 3 significant bits (12 -> 12, 13 -> 14, 25 -> 28):
    bounds the compile-key variety of window-derived bucket counts to ~8
    per octave while wasting at most 12.5% of the [K, G] result transfer
    (full pow2 padding wasted 33% on a 12-bucket window)."""
    if n <= 8:
        return n
    step = 1 << (n.bit_length() - 3)
    return -(-n // step) * step


def _np_filter(mask: np.ndarray, col: np.ndarray, op: str, val) -> np.ndarray:
    if op == "=":
        return mask & (col == val)
    if op == "!=":
        return mask & (col != val)
    if op == "<":
        return mask & (col < val)
    if op == "<=":
        return mask & (col <= val)
    if op == ">":
        return mask & (col > val)
    if op == ">=":
        return mask & (col >= val)
    if op == "in":
        return mask & np.isin(col, list(val))
    if op == "not in":
        return mask & ~np.isin(col, list(val))
    return np.zeros_like(mask)


def _partitioned(ctx, pk: list[str]) -> bool:
    """Whether the table's regions split it on its leading key tag, so
    that a region holds every n-th code of that tag or so."""
    return bool(pk) and len(ctx.regions) > 1 and pk[0] in ctx.partition_columns


def _series_key(
    pk: list[str], group_tags: list[str], has_bucket: bool, partitioned: bool,
) -> list[str] | None:
    """The key whose series stage 1 groups by (`DistGroupByPlan.
    series_slots`), or None where the gid is composed over the requested
    tags' own codes.  Series are runs of the (pk, ts) sorted rows
    whatever the key's width; groups are runs only where the requested
    tags are the key's leading columns in key order.  Elsewhere — a
    bucketed group-by on a strict subset of the key or on the key in
    another order (the whole key, since the bucket follows ts), an
    unbucketed one off the key's prefix (the shortest prefix that holds
    the tags) — the gid is (series, bucket) and the state folds down to
    the groups on device.  Where the groups are runs but the table is
    `partitioned` on the key's leading tag, a region's codes of that tag
    lie apart and its blocks' gids past the blocked kernels' span: stage
    1 groups by the series of the requested tags themselves.  A
    bucket-only group-by goes time-major, a scalar one is one group."""
    if not all(t in pk for t in group_tags):
        return None  # non-pk group tag: no layout claim (scatter handles)
    if has_bucket:
        if not group_tags:
            return None  # bucket-only: time-major path instead
        if list(group_tags) == pk:
            # direct: (full pk, bucket) rides the sort
            return pk if partitioned else None
        return pk  # aggregate at (series, bucket), fold down
    if not group_tags:
        return None  # scalar aggregate: single group
    if list(group_tags) == pk[: len(group_tags)]:
        # direct: pk prefix in pk order
        return list(group_tags) if partitioned else None
    j = 1 + max(pk.index(t) for t in group_tags)
    return pk[:j]


def _encode_tag_filter(
    d: TableDictionary, name: str, op: str, value
) -> list[tuple[str, str, object]] | None:
    """Translate a tag-string predicate to code space.  Sorted codes make
    inequalities exact; a null slot (always the max code) must be excluded
    from every operator except '=' (SQL: NULL never satisfies a filter)."""
    null_code = d.code_of(name, None)
    guard = [(name, "!=", null_code)] if null_code >= 0 else []
    if op == "=":
        return [(name, "=", d.code_of(name, value))]
    if op == "!=":
        return guard + [(name, "!=", d.code_of(name, value))]
    if op == "in":
        return guard + [(name, "in", tuple(d.code_of(name, v) for v in value))]
    if op == "not in":
        return guard + [(name, "not in", tuple(d.code_of(name, v) for v in value))]
    if op == "<":
        return guard + [(name, "<", d.bound(name, value))]
    if op == ">=":
        return guard + [(name, ">=", d.bound(name, value))]
    if op == "<=":
        return guard + [(name, "<", d.bound_right(name, value))]
    if op == ">":
        return guard + [(name, ">=", d.bound_right(name, value))]
    return None


def _disjoint(ranges: list[tuple[int, int]]) -> bool:
    """True when every pair of inclusive [lo, hi] ranges is non-overlapping."""
    if len(ranges) <= 1:
        return True
    s = sorted(ranges)
    for (alo, ahi), (blo, bhi) in zip(s, s[1:]):
        if ahi >= blo:
            return False
    return True
