"""Layered configuration: defaults -> TOML file -> environment variables.

Mirrors the reference's `Configurable::load_layered_options`
(reference src/common/config/src/config.rs:29-74): env vars use the
`GREPTIMEDB_TPU__SECTION__KEY` convention (double underscore separates
nesting levels), analogous to the reference's `GREPTIMEDB_<ROLE>__A__B`.
"""

from __future__ import annotations

import dataclasses
import os
import tomllib
from typing import Any

ENV_PREFIX = "GREPTIMEDB_TPU"


def _coerce(value: str, template: Any) -> Any:
    """Coerce an env-var string to the type of the default it overrides."""
    if isinstance(template, bool):
        return value.lower() in ("1", "true", "yes", "on")
    if isinstance(template, int):
        return int(value)
    if isinstance(template, float):
        return float(value)
    if isinstance(template, (list, tuple)):
        return [v.strip() for v in value.split(",")]
    return value


def _deep_merge(base: dict, overlay: dict) -> dict:
    out = dict(base)
    for k, v in overlay.items():
        if k in out and isinstance(out[k], dict) and isinstance(v, dict):
            out[k] = _deep_merge(out[k], v)
        else:
            out[k] = v
    return out


@dataclasses.dataclass
class StorageConfig:
    data_home: str = "./greptimedb_data"
    wal_dir: str = ""  # defaults to {data_home}/wal
    sst_dir: str = ""  # defaults to {data_home}/data
    manifest_checkpoint_distance: int = 10
    write_buffer_size_mb: int = 64
    global_write_buffer_size_mb: int = 512
    memtable_time_partition_secs: int = 86400
    num_workers: int = 4
    wal_fsync: bool = False
    compaction_max_active_window_runs: int = 4
    compaction_max_inactive_window_runs: int = 1
    compaction_time_window_secs: int = 0  # 0 = infer from data
    # Budget for concurrent compaction working sets (reference
    # compaction/memory_manager.rs); oversized merges split to fit.
    compaction_memory_mb: int = 512
    # Background compaction scheduler (reference mito2 CompactionScheduler):
    # flushes nudge it, a periodic tick catches the rest.
    compaction_background_enable: bool = True
    compaction_tick_secs: float = 5.0
    # SST secondary indexes (reference mito2 `[region_engine.mito.index]`):
    index_enable: bool = True
    index_segment_rows: int = 1024  # bloom/inverted segment granularity
    index_inverted_max_terms: int = 4096  # cardinality cap for LEGACY inverted index
    # Storage-plane mirrors of the user-facing `index.*` section (engines
    # built from a bare StorageConfig see these; Config.__post_init__
    # copies the index.* knobs down, same pattern as follower_sync):
    index_segmented: bool = True
    index_segment_terms: int = 512
    index_max_terms: int = 1 << 20
    # WAL provider (reference `[wal] provider = "raft_engine" | "kafka"`):
    # "local" = per-region append logs (raft-engine analogue);
    # "shared_file" = shared-topic segmented log on wal_dir (the remote-WAL
    # interface with a file backend — point wal_dir at shared storage for
    # stateless-datanode failover); "kafka" = the wire-protocol adapter
    # over a broker (requires remote.kafka_endpoints; the offline fake in
    # remote/fake_kafka.py speaks the same framing for no-egress runs).
    wal_provider: str = "local"
    wal_num_topics: int = 4
    wal_segment_mb: int = 4
    # Object store under SSTs/manifests (reference `[storage]` with OpenDAL
    # fs/s3/gcs/oss/azblob builders).  "s3" = the SigV4 REST adapter
    # (requires remote.s3_endpoint; remote/fake_s3.py is the offline
    # twin); gcs/oss/azblob stay gated (no egress); "memory" for tests.
    store_type: str = "fs"
    # mock_remote tuning (SimulatedRemoteStore): per-op latency and
    # transient-failure injection for exercising the remote layer stack
    store_mock_latency_ms: float = 0.0
    store_mock_fail_every: int = 0
    object_cache_mb: int = 0  # >0 enables the LRU whole-object read cache
    store_retry_attempts: int = 3
    write_cache_enable: bool = False  # local staging in front of non-fs stores
    write_cache_capacity_mb: int = 512
    # Storage-plane mirror of replica.sync_interval_ms: engines built from
    # a bare StorageConfig (datanode roles) read the follower-sync cadence
    # here; Config.__post_init__ copies the replica.* knob down so the
    # user-facing surface stays `replica.sync_interval_ms`.  0 = no
    # follower tailing (open-time snapshots).
    follower_sync_interval_ms: float = 0.0
    # Storage-plane mirrors of the user-facing `ingest.*` section (same
    # copy-down pattern as index.*/replica.*): WAL group commit on the
    # region-worker loops, the flush encode pool width, and write
    # admission during an in-flight flush encode.
    ingest_group_commit: bool = True
    ingest_flush_workers: int = 2
    ingest_flush_overlap: bool = True
    # Storage-plane mirrors of the user-facing `remote.*` section (same
    # copy-down pattern): wire-adapter endpoints + shared wire-layer
    # knobs.  Engines built from a bare StorageConfig read these;
    # empty endpoints keep the in-memory/file sims.
    wal_kafka_endpoints: str = ""
    store_s3_endpoint: str = ""
    store_s3_bucket: str = "greptimedb"
    store_s3_region: str = "us-east-1"
    store_s3_access_key: str = ""
    store_s3_secret_key: str = ""
    store_s3_multipart_mb: int = 8
    remote_pool_size: int = 2
    remote_call_deadline_s: float = 5.0
    remote_connect_timeout_s: float = 2.0
    remote_retry_attempts: int = 5

    def __post_init__(self):
        # NOTE: wal_dir/sst_dir stay EMPTY unless explicitly set — they are
        # derived from data_home at USE time (effective_*), so mutating
        # data_home after construction keeps all three consistent.  Baking
        # them here made every Database whose caller set data_home late
        # share the DEFAULT ./greptimedb_data storage — colliding region
        # ids across supposedly-isolated instances (recovered the wrong
        # region's manifest; observed as cross-database data bleed in the
        # sqlness runner under load).
        pass

    def effective_wal_dir(self) -> str:
        return self.wal_dir or os.path.join(self.data_home, "wal")

    def effective_sst_dir(self) -> str:
        return self.sst_dir or os.path.join(self.data_home, "data")


@dataclasses.dataclass
class QueryConfig:
    # "tpu" lowers eligible plans to JAX kernels; "cpu" is the authoritative
    # Arrow-compute path (reference gates similarly via query.execution hooks).
    backend: str = "tpu"
    tile_rows: int = 1 << 20
    max_groups: int = 1 << 16
    # stage-1 group-space cap for hierarchical (pk x bucket) aggregation
    # (ops/aggregate.py reduce_state_axes); dense [G] states at 8 bytes make
    # 2^24 = 128 MB per tracked aggregate — fine in HBM, folded before fetch
    max_internal_groups: int = 1 << 24
    # Cost-based backend routing: lowerable plans whose post-prune row
    # estimate falls below this stay on the host CPU path — a device
    # query pays a dispatch and a fetch that a small Arrow aggregation
    # on the host can beat.
    # 0 disables routing (device path for every lowerable plan).
    tpu_min_rows: int = 0
    parallelism: int = 0  # 0 = number of local devices
    fallback_to_cpu: bool = True
    # HBM-resident SST tile cache (parallel/tile_cache.py): warm queries run
    # as one dispatch over cached device tiles instead of re-scanning Arrow.
    tile_cache_enable: bool = True
    tile_cache_mb: int = 8192
    # Rows per device chunk (pow2, multiple of the 4096-row kernel block).
    # Chunks round-robin over local devices; the multichip dryrun shrinks
    # this to drive the multi-device path with toy data.
    tile_chunk_rows: int = 1 << 24
    # Persist consolidated super-tile encodes to <data_home>/tile_cache so
    # a fresh process mmaps them instead of re-decoding/sorting (the
    # dominant cold-query cost).  Directory is set by the Database from
    # data_home; empty disables.
    tile_persist_enable: bool = True
    tile_persist_dir: str = ""
    # Region-streamed execution for working sets LARGER THAN the HBM
    # budget (parallel/tile_cache.py _streamed_execute): when the
    # estimated device planes of a query exceed tile_stream_threshold x
    # tile_cache_mb, regions build -> dispatch -> merge states -> release
    # one at a time, so peak HBM stays one region's working set (the
    # 1B-row trajectory: per-region latency is flat, total is linear).
    tile_stream_enable: bool = True
    # Stream only when the planes genuinely cannot be resident: estimates
    # below budget keep the all-at-once cached path (0.6 misfired at TSBS
    # scale — a 5.8 GB fits-fine working set streamed, so every 'warm'
    # rep re-uploaded and released everything)
    tile_stream_threshold: float = 0.9
    # Accumulation mode for tile-path sum/avg: "limb" routes them through
    # the MXU fixed-point kernel (ops/aggregate.py limb_segment_sums; one
    # batched matmul for every column).  Precision: ~1e-9 relative
    # quantization error per block; integer data is exact up to 2^29 per
    # value but loses low bits beyond that — set "float64" for exact f64
    # accumulation (per-column VPU kernels, ~6x slower at TSBS scale).
    tile_acc_dtype: str = "limb"
    # Device-side result finalization (parallel/tile_cache.py + the
    # "device_finalize" pass): recognized Sort/LIMIT/HAVING post-plans and
    # empty-group compaction run INSIDE the compiled tile program over the
    # finalized [K, G] states, so the single device->host fetch ships
    # O(rows_out) bytes (a [K, limit]/[K, top_groups] buffer + a compact
    # group-id vector) instead of O(groups).  Off restores the host
    # post-op path exactly (full-buffer fetch, CPU Sort/Limit/Having).
    device_topk: bool = True
    # Streamed device->host readback (parallel/executor.py
    # streamed_device_get): large result fetches split into
    # readback_chunk_kb-sized device_get slices with ONE slice in flight
    # while the previous one copies into the host buffer, so transfer
    # overlaps host-side decode instead of serializing ahead of it.
    # Small results (< 2 chunks) keep the single batched fetch — extra
    # crossings would cost more than the overlap saves.  Off restores the one-device_get path bit-for-bit.
    streamed_readback: bool = True
    readback_chunk_kb: int = 1024
    # Per-statement wall-clock budget (seconds; 0 disables).  Enforced
    # cooperatively (utils/deadline.py): scan loops, row-group reads and
    # plan-node execution check it between units of work, so a query that
    # degrades to a full CPU scan aborts with QueryTimeoutError instead of
    # grinding unbounded (the reference cancels the DataFusion stream on
    # its request timeouts).
    timeout_s: float = 0.0
    # Named optimizer passes to switch off (query/passes.py registry) —
    # comma list via env: GREPTIMEDB_TPU__QUERY__DISABLED_PASSES=
    # "window_tile,host_fast_path".  Each strategy decision point checks
    # `passes.enabled(name, config)`, so disabling one composes with the
    # rest (the reference removes individual physical optimizer rules the
    # same way in its tests).
    disabled_passes: tuple = ()
    # Device group-by strategy (the `agg_strategy` optimizer pass,
    # parallel/tile_cache.py): "auto" picks hash vs sort per query from
    # table stats (distinct-key estimates via the segmented term index +
    # tag dictionaries vs the dense group-space size — the hash/sort
    # winner flips with group cardinality, arXiv:2411.13245); "sort"
    # forces the dense mixed-radix path (pre-hash behavior bit-for-bit);
    # "hash" forces the hash-table path wherever structurally possible.
    agg_strategy: str = "auto"
    # Auto only considers hash when the dense (padded) group space is at
    # least this large — below it dense [G] states are trivially cheap.
    agg_hash_min_group_space: int = 1 << 16
    # Hedged region reads (tail tolerance): once a region sub-query has been
    # outstanding this long, the frontend sends a duplicate to a follower
    # replica and takes whichever lands first.  0 disables hedging; it also
    # requires replica.read_followers and at least one registered follower,
    # so single-node setups are unaffected.
    hedge_delay_ms: float = 0.0
    # Once enough sub-query latencies are observed, the hedge delay adapts
    # to this percentile of recent latencies (hedge_delay_ms stays the
    # floor) — the "hedge after the p95" recipe.
    hedge_percentile: float = 0.95


@dataclasses.dataclass
class ParallelConfig:
    # Mesh axes for distributed execution: regions (data parallel over
    # devices) is the DB analogue of DP; within-host reduction rides ICI.
    mesh_shape: str = "auto"  # "auto" or e.g. "4x2"
    region_axis: str = "regions"


@dataclasses.dataclass
class ServerConfig:
    http_addr: str = "127.0.0.1:4000"
    grpc_addr: str = "127.0.0.1:4001"
    mysql_addr: str = "127.0.0.1:4002"
    postgres_addr: str = "127.0.0.1:4003"


@dataclasses.dataclass
class TelemetryConfig:
    """Anonymous usage telemetry (reference common/greptimedb-telemetry:
    version/mode/node-count every N hours unless disabled).  Default OFF;
    with no egress the report sinks to a local JSON file, where the
    reference POSTs it."""

    enable: bool = False
    interval_hours: float = 6.0
    sink_path: str = ""  # empty = <data_home>/telemetry_report.json


@dataclasses.dataclass
class TraceConfig:
    """Self-observability loop (utils/self_trace.py): end-to-end statement
    tracing exported into the database's OWN trace table, a tail-sampled
    slow-query log with full span trees, and a periodic /metrics
    self-scrape into the metric engine — the zero-egress twin of the
    reference's standalone self-monitoring (its standalone mode imports
    its own telemetry).

    Everything is off-safe: `enabled = False` (the `trace.self` knob on
    the TOML/env surface — `self` cannot be a dataclass field name)
    restores today's behavior bit-for-bit — no root statement spans, no
    writer threads, no scrape.  With it on, fast statements head-sample
    at `sample_ratio`; statements slower than `slow_query_ms` (or
    erroring) are always kept AND land in greptime_private.slow_queries
    with their span tree."""

    # TOML/env alias: `[trace] self = true` / GREPTIMEDB_TPU__TRACE__SELF.
    _ALIASES = {"self": "enabled"}

    enabled: bool = False
    # Head-sampling ratio for statements that finish fast and clean; slow
    # or erroring statements are force-kept regardless (tail sampling).
    sample_ratio: float = 0.01
    # Force-keep threshold: a statement slower than this keeps its full
    # trace and writes a slow_queries row with the span tree attached.
    slow_query_ms: float = 5000.0
    # Metric self-scrape cadence: every interval the /metrics registry is
    # snapshotted into the metric engine (database greptime_private is NOT
    # used — rows land in `public` so PromQL/TQL range queries work
    # without USE), 0 disables.  Standalone only (needs the metric engine).
    scrape_interval_s: float = 0.0
    # SelfTraceWriter drain cadence (exporter ring -> opentelemetry_traces).
    export_interval_s: float = 0.25
    # OTLP/HTTP self-export for roles with no local writer (bare
    # datanodes): spans drain to `<endpoint>/v1/otlp/v1/traces` as OTLP
    # protobuf over the wire client instead of into a local table.
    # Empty = off (standalone/frontend keep their in-process writers).
    otlp_endpoint: str = ""


@dataclasses.dataclass
class RecorderConfig:
    """Device flight recorder (utils/flight_recorder.py): every tile
    dispatch appends one bounded record — plan fingerprint + trace id,
    strategy, build mode, per-stage ms, bytes up/down, HBM snapshot and
    degrade/coalesce/retry flags — into a drop-oldest ring surfaced via
    `information_schema.device_dispatches`, EXPLAIN ANALYZE's
    device-stage split and the `/debug/tile` endpoint.

    Default ON: the steady-state cost is one thread-local dict per
    dispatch plus a handful of perf_counter reads (the tier-1 bench
    smoke pins the warm-dispatch overhead under noise).  `enabled =
    false` makes the whole surface a no-op — empty tables, coarse
    EXPLAIN totals, today's behavior bit-for-bit."""

    enabled: bool = True
    # Records kept before drop-oldest eviction (one record ≈ 600 bytes of
    # host RAM; 4096 ≈ 2.5 MB).
    ring_size: int = 4096


@dataclasses.dataclass
class SlowQueryConfig:
    """Slow-query recording (reference common/telemetry SlowQueryOptions +
    event recorder into greptime_private.slow_queries)."""

    enable: bool = True
    threshold_ms: int = 5000
    sample_ratio: float = 1.0  # record this fraction of slow queries


@dataclasses.dataclass
class BreakerConfig:
    """Per-datanode circuit breakers in the frontend's client cache
    (utils/circuit_breaker.py).  Default OFF: a single-node setup never
    pays the bookkeeping, and tests opt in explicitly."""

    enable: bool = False
    window: int = 20  # sliding window of recent call outcomes (count-based)
    min_calls: int = 5  # don't judge a node on fewer samples than this
    failure_rate: float = 0.5  # trip when failures/window >= this
    open_cooldown_s: float = 5.0  # OPEN -> HALF_OPEN after this long
    half_open_probes: int = 1  # probe budget while HALF_OPEN
    # Breaker-aware write routing: when a WRITE meets an open breaker,
    # ask the metasrv to fail the region over to a candidate (refused
    # while the node's lease is still live) and retry against the new
    # leader instead of failing fast.  Off = writes shed like reads.
    write_hedge: bool = False


@dataclasses.dataclass
class ReplicaConfig:
    """Follower read replicas: read-only opens of a region on extra
    datanodes over the shared storage, registered in the metasrv route
    table.  Default OFF — followers must be added explicitly
    (MetaClient.add_follower) or placed by the metasrv selector
    (target_followers > 0), and reads only consult them when enabled."""

    read_followers: bool = False
    # Follower freshness: every sync_interval_ms a follower replays the
    # shared-WAL tail past its applied entry id and refreshes its manifest
    # view when the leader's manifest version advanced (so compaction-
    # deleted SSTs are dropped before a hedged read trips over them).
    # 0 disables tailing entirely and restores the open-time-snapshot
    # behavior bit-for-bit.
    sync_interval_ms: float = 0.0
    # Hedge gating: the fan-out skips hedging to a follower whose reported
    # lag (ms since its last successful sync) exceeds this bound, so
    # hedged reads are bounded-staleness by contract.  0 disables gating
    # (any registered follower is hedge-eligible, today's behavior).
    max_lag_ms: float = 0.0
    # Automatic placement: the metasrv selector keeps this many followers
    # per region on distinct live datanodes — creating them on node
    # join/failover and garbage-collecting orphans on node death.
    # 0 keeps placement manual (MetaClient.add_follower only).
    target_followers: int = 0


@dataclasses.dataclass
class TileConfig:
    """HBM super-tile lifecycle knobs that are about WHEN tiles build, not
    how queries run (those live under query.*): `prewarm_on_flush` moves
    the cold-path consolidation + upload + limb quantize off the first
    query of each TSBS family and onto a background thread at flush time,
    reusing the persistent XLA compilation cache (utils/jax_env.py).
    `Database.prewarm()` is the explicit form of the same build."""

    # Build super-tiles (and limb planes) in the background after a flush
    # lands, so the first query of a family stops paying the 10-170 s cold.
    prewarm_on_flush: bool = False
    # Coalesce flush storms: a region's prewarm runs this long after its
    # LAST flush notification, not once per flush.
    prewarm_debounce_s: float = 2.0
    # Also quantize MXU limb planes during prewarm (sum/avg families).
    prewarm_limbs: bool = True
    # Restrict prewarm to these tables (empty = every tileable base table).
    prewarm_tables: tuple = ()
    # Incremental (delta) super-tile maintenance: when a flush APPENDS
    # files to a region's set, merge only the new rows into the existing
    # entry — delta encode, merge of two sorted runs (not a re-sort),
    # on-device patch of resident planes — so post-flush cold cost is
    # O(delta rows), not O(total rows).  Off restores the
    # invalidate-and-rebuild-from-scratch path bit-for-bit.
    incremental: bool = True
    # Pipelined cold build: host-encode of column N+1 overlaps the device
    # upload of column N over a small worker pool, and the tile program's
    # jit trace/compile starts from shape metadata alone, before data
    # upload finishes.  Off restores the serial encode->upload->compile
    # loop.
    pipelined_build: bool = True
    # Host consolidation workers feeding the pipelined upload (>= 1).
    build_workers: int = 2
    # Fused family cold build (parallel/tile_cache.py): query plans (and
    # prewarm) emit plane-requirement manifests; a cold grouped query of a
    # NEW family answers from the host consolidation immediately while one
    # consolidated background build — the UNION of the family's manifests
    # (decode each SST once, encode each column once, one batched upload
    # through the pipelined producer/consumer) — warms the device planes;
    # concurrent cold builds for overlapping manifests coalesce onto one
    # in-flight build future whose waiters adopt the leader's planes.
    # False restores the per-query build ladder bit-for-bit: cold-serve at
    # most once per entry, device planes built synchronously on the next
    # touch, no background builder.
    fused_build: bool = True
    # Deadline for one background fused family build (upload + limb
    # quantize + compile + priming dispatch); an expired build surfaces as
    # a failed future and waiters fall back to building solo.
    fused_build_timeout_s: float = 900.0
    # Multi-chip sharded execution (parallel/tile_cache.py mesh path):
    # N > 0 runs the single-dispatch tile program under shard_map over a
    # 1-D `regions` mesh of the first N local devices — each device scans
    # + partially aggregates its shard of the super-tile chunks and the
    # partial AggStates merge via psum/pmin/pmax collectives (hash-slot
    # tables merge by keyed scatter into a union table first), with
    # device-finalize running once post-merge so readback stays
    # O(rows_out) from one chip.  0 (default) keeps today's single-chip
    # dispatch path bit-for-bit; any collective failure degrades to that
    # path automatically (fault point `mesh.collective`).  Values above
    # the available device count are rejected at config validation.
    mesh_devices: int = 0


@dataclasses.dataclass
class TqlConfig:
    """Warm TQL hot path (query/promql/tile_exec.py, the `tql_tile`
    optimizer pass): PromQL range-vector evaluation — rate/increase/
    delta, *_over_time, and the by-label sum/avg/min/max/count fold —
    runs as ONE fused dispatch over the device tile cache, sharing the
    SQL path's plane manifests, fused background builds, delta-extend
    and build coalescing.  Programs are cached per padded (series,
    steps, windows-per-sample) shape bucket with the grid and matcher
    literals as dynamic inputs, so a sliding dashboard re-hits the
    compile cache with zero host->device plane traffic.

    `tile = False` restores the legacy upload-per-query evaluation
    bit-for-bit; ANY tile-path failure (fault point `tql.tile`) degrades
    to that path too (`greptime_tql_tile_degraded_total`)."""

    tile: bool = True
    # Upper bound on padded series x padded steps cells per evaluation
    # ([S, W] f64 window-stat planes live on device); beyond it the
    # query stays on the legacy path.
    max_cells: int = 1 << 22
    # Per-series results larger than this fetch in TWO round-trips:
    # presence first, then a device-side gather of only the present
    # rows — the compacted [series_out, steps] readback.  Below it one
    # batched round-trip wins (RTT-bound, not byte-bound).
    compact_readback_kb: int = 1024
    # False: a TQL statement the tile path cannot answer from the device
    # fails, naming the reason, where it would be answered from the legacy
    # scan with no sign (the guarantee `query.fallback_to_cpu = false`
    # gives SQL).  The one legacy answer left is a family's designed first
    # touch (`greptime_tql_tile_cold_serves_total`), served while its
    # planes build.
    legacy_fallback: bool = True


@dataclasses.dataclass
class IndexConfig:
    """Segmented term index (greptimedb_tpu/index/): new SSTs write their
    inverted/fulltext term indexes as fence-keyed term segments with
    per-segment puffin blobs, so a term lookup is binary search over
    in-memory fence keys + ONE ranged read of one segment — O(log terms)
    time, O(segment) memory, no cardinality cap below `max_terms`.

    `segmented = False` restores the legacy whole-blob formats for new
    SSTs bit-for-bit (including the 4096-term inverted cap); sidecars of
    EITHER vintage stay readable — the read router handles both."""

    segmented: bool = True
    # Terms per segment blob: the unit of both lookup memory and ranged
    # read size.  512 terms ≈ 10-40 KB per segment at typical tag widths.
    segment_terms: int = 512
    # Hard cardinality ceiling for building a term index at all (beyond
    # it the column keeps only its bloom filters).  High on purpose: the
    # segmented format is built FOR high cardinality.
    max_terms: int = 1 << 20


@dataclasses.dataclass
class IngestConfig:
    """Pipelined columnar ingest (storage/worker.py + storage/wal.py +
    storage/region.py).  Everything here is off-safe: all three knobs at
    their off positions restore the pre-pipeline write path bit-for-bit
    (frame-per-write WAL bytes, serial flush encode, stall-on-flush).

    Durability note: with `group_commit` on and `storage.wal_fsync` on,
    the fsync runs once per MERGED frame, not once per write — every
    acked write is still durable (futures resolve only after the group
    frame is written and fsynced), but writes share their fsync with the
    group.  An operator who needs one fsync *syscall* per write request
    must run with `group_commit = false`."""

    # Merge each region-worker drain group into ONE WAL frame (one Arrow
    # IPC encode, one write syscall, one optional fsync) while keeping
    # per-write entry ids — replay, follower lag accounting and
    # shared-WAL pruning see the same entries as frame-per-write.  Also
    # routes single-region inserts through the worker loops so WAL
    # appends overlap the caller building its next batch.
    group_commit: bool = True
    # Flush encode pool: SSTs of one flush (one per time window) encode
    # Parquet + indexes concurrently on this many workers.  1 = the
    # serial pre-pipeline loop.
    flush_workers: int = 2
    # Admit new writes while a flush encode is in flight: freezing a
    # memtable moves its bytes out of the mutable write-buffer budget
    # into a flushing bucket, so ingest keeps running during the encode.
    # Total (mutable + flushing) stays bounded at 2x the global buffer
    # limit before writes stall.
    flush_overlap: bool = True


@dataclasses.dataclass
class FlowConfig:
    """Incremental dataflow for materialized views (flow/dataflow.py).

    `incremental = True` routes CREATE FLOW plans the operator graph can
    express — map/filter/project, count(DISTINCT), dirty-window inner
    joins, windowed heavy aggregates — through diff-driven incremental
    maintenance; plans it cannot express fall back to the periodic-batch
    engine with the reason recorded (SHOW FLOWS / EXPLAIN FLOW /
    greptime_flow_batch_fallback_total).  `incremental = False` restores
    the pre-dataflow mode selection bit-for-bit: decomposable single-table
    aggregates stream, everything else batches, joins are rejected."""

    incremental: bool = True
    # Dirty-window granularity for recompute flows whose plan has no
    # date_bin/time_bucket group key (joins/projections over raw
    # timestamps): diffs dirty ranges of this width.
    window_ms: int = 3_600_000
    # Upper bound on windows recomputed per diff batch; the overflow stays
    # dirty and is picked up by the next diff/flush (protects the insert
    # path from a single backfill batch fanning into thousands of
    # synchronous re-runs).
    max_windows_per_recompute: int = 64


@dataclasses.dataclass
class AdmissionConfig:
    """Multi-tenant admission control in front of the query/write paths
    (utils/admission.py) and the tile executor's overload machinery
    (parallel/tile_cache.py).  EVERYTHING here defaults off-safe: with
    `enable = False` (and coalesce/hbm_* off) the engine behaves
    bit-for-bit as before this layer existed."""

    # Master switch for the per-tenant weighted admission queues.
    enable: bool = False
    # Concurrent statements the scheduler admits at once.  0 falls back
    # to memory.max_concurrent_queries; if both are 0 admission never
    # queues (ordering/shedding need a finite concurrency budget).
    max_concurrent: int = 0
    # Per-tenant pending-queue cap: an arrival past this depth is shed
    # immediately with RETRY_LATER (queue-depth shedding).
    max_queue_depth: int = 64
    # Longest a query may sit queued before it is shed (wait-time
    # shedding).  Deadlined queries additionally clip to their own
    # remaining budget; 0 disables the wait bound (deadline-only).
    max_queue_wait_ms: float = 2000.0
    # Weighted fairness: "tenant:weight" pairs (e.g. "gold:4,free:1");
    # unlisted tenants get default_weight.  Weights drive a stride
    # scheduler — a weight-4 tenant drains 4x the slots of a weight-1
    # tenant under contention, and an idle tenant costs nothing.
    tenant_weights: tuple = ()
    default_weight: int = 1
    # Dispatch coalescing: concurrent queries of one family attach to a
    # single in-flight device dispatch (leader executes, waiters share
    # the finalized result — the shared-data-path idea applied across
    # concurrent queries).
    coalesce: bool = False
    # Startup allocation probe: measure REAL free device memory
    # (device.memory_stats + a touch allocation) and clamp the tile
    # budget to hbm_probe_headroom x measured-free instead of trusting
    # the configured model-based budget.
    hbm_probe: bool = False
    hbm_probe_headroom: float = 0.9
    # Closed HBM feedback loop: a RESOURCE_EXHAUSTED escaping the tile
    # path's one-shot emergency retry triggers emergency_release + a
    # halve-chunk-rows retry (down to min_chunk_rows), so forced
    # overcommit degrades to smaller dispatches instead of failing.
    hbm_retry: bool = False
    hbm_retry_attempts: int = 3
    min_chunk_rows: int = 1 << 18

    def weight_of(self, tenant: str) -> int:
        for pair in self.tenant_weights:
            name, _, w = str(pair).partition(":")
            if name == tenant:
                try:
                    return max(1, int(w))
                except ValueError:
                    return max(1, int(self.default_weight))
        return max(1, int(self.default_weight))


@dataclasses.dataclass
class BatchConfig:
    """Cross-query device batching + windowed result cache
    (parallel/batcher.py, hooked into the tile executor).  EVERYTHING
    here defaults off-safe: with `window_ms = 0` and `result_cache_mb
    = 0` the dispatch path behaves bit-for-bit as before this layer
    existed.

    Batching extends PR 6 coalescing from *identical* plans to
    *distinct* plans over the same resident table: warm queries that
    arrive within `window_ms` of each other are dispatched back-to-back
    on the device stream and their packed result buffers come home in
    ONE readback, amortizing the per-dispatch fetch across the
    batch.  Results are bit-identical to solo runs — members share the
    readback, never each other's math — and any member that cannot be
    packed degrades to its own solo dispatch."""

    # Batching window: a warm query waits up to this long for peers to
    # join its mega-dispatch.  0 disables batching entirely (today's
    # path, bit-for-bit).
    window_ms: float = 0.0
    # Most members one mega-dispatch may carry; arrivals past the cap
    # start the next batch rather than queueing behind this one.
    max_members: int = 16
    # Windowed result cache budget.  Keyed on (literal-insensitive plan
    # fingerprint + literal digest, per-region manifest version + WAL
    # tail id, bucket-aligned time window) so a sliding dashboard
    # re-serves without any dispatch; flush/delta bumps the manifest
    # version out from under stale entries.  0 disables the cache.
    result_cache_mb: int = 0
    # Mega-program fusion: the members of a batch tick compile into ONE
    # fused XLA program (shared plane scan, per-member masks/folds as
    # fused branches) keyed on the multiset of their literal-insensitive
    # program keys — one XLA invocation per tick, not per member.  Only
    # engages when batching does (window_ms > 0, single device, mesh
    # off); any trace/compile/dispatch failure degrades to the
    # per-member packed path, so False restores that path bit-for-bit.
    fuse_programs: bool = True


@dataclasses.dataclass
class MemoryConfig:
    """Admission-style memory governance (reference common/memory-manager,
    servers request_memory_limiter `max_in_flight_write_bytes`,
    `max_concurrent_queries`).  0 = unlimited."""

    max_in_flight_write_bytes: int = 0
    max_concurrent_queries: int = 0
    # Bounded-memory scans: windowed scan slices are admitted against this
    # budget (0 = unlimited), so one huge SELECT cannot OOM the process.
    max_scan_bytes: int = 0
    # Longest an UNdeadlined statement blocks for a concurrency slot
    # before degrading to RETRY_LATER (deadlined statements clip to their
    # own remaining budget; fail-fast happens only when the deadline
    # cannot absorb the expected queue wait).
    gate_wait_s: float = 5.0


@dataclasses.dataclass
class BalanceConfig:
    """Elastic balancer (distributed/balancer.py): load-driven region
    split/merge/migration driven from heartbeat RegionStats + flight-
    recorder dispatch costs.  Default OFF — with `enabled=false` the
    balancer tick is a no-op and the cluster behaves bit-for-bit as
    before this knob existed."""

    enabled: bool = False
    # EWMA smoothing factor for per-region load scores (1.0 = raw last
    # observation, no smoothing).
    ewma_alpha: float = 0.3
    # Consecutive ticks a condition (hot region / cold table / overloaded
    # node) must persist before the balancer acts — a one-tick burst can
    # never trigger a split/merge/migration.
    min_dwell_ticks: int = 3
    # Ticks a table rests after any decision before the balancer will
    # touch it again (anti-flap: a split must settle before a merge of
    # the same table can even start dwelling).
    cooldown_ticks: int = 5
    # A region is HOT when its EWMA score exceeds this absolute floor AND
    # split_hot_ratio x the mean score of its siblings.
    split_hot_score: float = 512.0
    split_hot_ratio: float = 2.0
    # A table is COLD when every region's EWMA score is below this; cold
    # multi-region tables merge down to half the partitions.
    merge_cold_score: float = 1.0
    # A datanode is OVERLOADED when its aggregate score exceeds the fleet
    # median by this ratio; its hottest region migrates to the least
    # loaded live node.
    migrate_ratio: float = 2.0
    # Split ceiling per table (the catalog's hard cap is 1024).
    max_regions_per_table: int = 16
    # Score weights: rows written since the last tick, resident memtable
    # MiB (heartbeat RegionStats), and flight-recorder device build/
    # dispatch milliseconds attributed to the region.
    write_weight: float = 1.0
    memtable_mb_weight: float = 1.0
    dispatch_ms_weight: float = 1.0


@dataclasses.dataclass
class RemoteConfig:
    """Wire-level remote backends (remote/): etcd v3 for metadata KV +
    election, Kafka for the shared WAL, S3 for the object store — each a
    real protocol client behind the same interface its in-memory sim
    implements.  Default OFF: every endpoint empty keeps the sims and
    today's behavior bit-for-bit.

    Engagement is two-knob by design: the endpoint here supplies the
    address, the existing backend selector opts the subsystem in
    (`storage.wal_provider = "kafka"`, `storage.store_type = "s3"`;
    etcd engages on the endpoint alone since the cluster KV had no
    selector).  An endpoint-less selector fails validation instead of
    silently falling back."""

    # etcd v3 gRPC-gateway endpoints ("host:port[,host:port]") for the
    # cluster metadata KV and metasrv election.  Empty = MemoryKvBackend.
    etcd_endpoints: str = ""
    # Kafka broker endpoints for the shared remote WAL; engaged together
    # with `storage.wal_provider = "kafka"`.
    kafka_endpoints: str = ""
    # S3 REST endpoint + bucket/credentials; engaged together with
    # `storage.store_type = "s3"`.
    s3_endpoint: str = ""
    s3_bucket: str = "greptimedb"
    s3_region: str = "us-east-1"
    s3_access_key: str = ""
    s3_secret_key: str = ""
    # Writes above this size go as multipart uploads.
    s3_multipart_mb: int = 8
    # Shared wire-layer knobs (all three adapters): pooled connections
    # per endpoint, per-call deadline, connect timeout, retry ladder.
    pool_size: int = 2
    call_deadline_s: float = 5.0
    connect_timeout_s: float = 2.0
    retry_attempts: int = 5


@dataclasses.dataclass
class DeviceConfig:
    """Device health supervisor (utils/device_health.py): every blocking
    device interaction (upload, compile+dispatch, readback, memory_stats
    probe, mesh collective) runs on a dedicated per-device worker thread
    under a hard deadline; a call that neither returns nor raises is
    abandoned (worker thread written off — a wedged native call cannot be
    cancelled), the device quarantines, and the query degrades down the
    existing ladder instead of hanging.  `supervised = false` restores
    direct in-thread calls bit-for-bit."""

    supervised: bool = True
    # Hard per-call deadline in seconds; each supervised call is further
    # clamped to the statement's remaining deadline budget.
    call_timeout_s: float = 30.0
    # Consecutive raised device errors (not HBM RESOURCE_EXHAUSTED — the
    # halve-and-retry ladder owns those) before a SUSPECT device
    # quarantines, breaker-style.
    error_threshold: int = 3
    # Heal prober: a QUARANTINED device re-admits only after this many
    # consecutive ghost dispatches complete within call_timeout_s.
    probe_successes: int = 3
    # Seconds between heal-probe rounds.
    probe_interval_s: float = 1.0


@dataclasses.dataclass
class Config:
    storage: StorageConfig = dataclasses.field(default_factory=StorageConfig)
    query: QueryConfig = dataclasses.field(default_factory=QueryConfig)
    parallel: ParallelConfig = dataclasses.field(default_factory=ParallelConfig)
    server: ServerConfig = dataclasses.field(default_factory=ServerConfig)
    slow_query: SlowQueryConfig = dataclasses.field(default_factory=SlowQueryConfig)
    memory: MemoryConfig = dataclasses.field(default_factory=MemoryConfig)
    telemetry: TelemetryConfig = dataclasses.field(default_factory=TelemetryConfig)
    breaker: BreakerConfig = dataclasses.field(default_factory=BreakerConfig)
    replica: ReplicaConfig = dataclasses.field(default_factory=ReplicaConfig)
    tile: TileConfig = dataclasses.field(default_factory=TileConfig)
    admission: AdmissionConfig = dataclasses.field(default_factory=AdmissionConfig)
    batch: BatchConfig = dataclasses.field(default_factory=BatchConfig)
    flow: FlowConfig = dataclasses.field(default_factory=FlowConfig)
    index: IndexConfig = dataclasses.field(default_factory=IndexConfig)
    ingest: IngestConfig = dataclasses.field(default_factory=IngestConfig)
    tql: TqlConfig = dataclasses.field(default_factory=TqlConfig)
    trace: TraceConfig = dataclasses.field(default_factory=TraceConfig)
    recorder: RecorderConfig = dataclasses.field(default_factory=RecorderConfig)
    balance: BalanceConfig = dataclasses.field(default_factory=BalanceConfig)
    remote: RemoteConfig = dataclasses.field(default_factory=RemoteConfig)
    device: DeviceConfig = dataclasses.field(default_factory=DeviceConfig)

    def __post_init__(self):
        self.storage.__post_init__()
        # index.* is the user-facing surface for the segmented term index;
        # engines only see StorageConfig, so copy the knobs down — but,
        # like the replica.sync copy, only when the index knob was
        # actually engaged (moved off its default), so an explicitly-set
        # storage.index_* survives (a bare StorageConfig is the engines'
        # own config surface and tests set it directly)
        ix_defaults = IndexConfig()
        if self.index.segmented != ix_defaults.segmented:
            self.storage.index_segmented = self.index.segmented
        if self.index.segment_terms != ix_defaults.segment_terms:
            self.storage.index_segment_terms = self.index.segment_terms
        if self.index.max_terms != ix_defaults.max_terms:
            self.storage.index_max_terms = self.index.max_terms
        # replica.sync_interval_ms is the user-facing follower-tailing
        # knob; engines only see StorageConfig, so copy it down (an
        # explicitly-set storage.follower_sync_interval_ms survives when
        # the replica knob is off)
        if self.replica.sync_interval_ms > 0:
            self.storage.follower_sync_interval_ms = self.replica.sync_interval_ms
        # ingest.* is the user-facing pipelined-ingest surface; engines
        # only see StorageConfig, so copy engaged knobs down like index.*
        ing_defaults = IngestConfig()
        if self.ingest.group_commit != ing_defaults.group_commit:
            self.storage.ingest_group_commit = self.ingest.group_commit
        if self.ingest.flush_workers != ing_defaults.flush_workers:
            self.storage.ingest_flush_workers = self.ingest.flush_workers
        if self.ingest.flush_overlap != ing_defaults.flush_overlap:
            self.storage.ingest_flush_overlap = self.ingest.flush_overlap
        # remote.* is the user-facing wire-adapter surface; engines only
        # see StorageConfig, so copy engaged knobs down like index.* —
        # with every endpoint at its empty default nothing moves and the
        # storage plane stays bit-for-bit the sims
        rm, rm_defaults = self.remote, RemoteConfig()
        if rm.kafka_endpoints != rm_defaults.kafka_endpoints:
            self.storage.wal_kafka_endpoints = rm.kafka_endpoints
        if rm.s3_endpoint != rm_defaults.s3_endpoint:
            self.storage.store_s3_endpoint = rm.s3_endpoint
        if rm.s3_bucket != rm_defaults.s3_bucket:
            self.storage.store_s3_bucket = rm.s3_bucket
        if rm.s3_region != rm_defaults.s3_region:
            self.storage.store_s3_region = rm.s3_region
        if rm.s3_access_key != rm_defaults.s3_access_key:
            self.storage.store_s3_access_key = rm.s3_access_key
        if rm.s3_secret_key != rm_defaults.s3_secret_key:
            self.storage.store_s3_secret_key = rm.s3_secret_key
        if rm.s3_multipart_mb != rm_defaults.s3_multipart_mb:
            self.storage.store_s3_multipart_mb = rm.s3_multipart_mb
        if rm.pool_size != rm_defaults.pool_size:
            self.storage.remote_pool_size = rm.pool_size
        if rm.call_deadline_s != rm_defaults.call_deadline_s:
            self.storage.remote_call_deadline_s = rm.call_deadline_s
        if rm.connect_timeout_s != rm_defaults.connect_timeout_s:
            self.storage.remote_connect_timeout_s = rm.connect_timeout_s
        if rm.retry_attempts != rm_defaults.retry_attempts:
            self.storage.remote_retry_attempts = rm.retry_attempts
        self.validate()

    def validate(self):
        """Reject nonsense knob values with errors that name the knob —
        a breaker with failure_rate=0 would trip on the first blip and a
        negative hedge delay would hedge every read immediately; both are
        config mistakes, not modes."""
        from .errors import ConfigError

        dv = self.device
        if not isinstance(dv.supervised, bool):
            raise ConfigError(
                "device.supervised must be a boolean (per-device worker-"
                f"thread call supervision); got {dv.supervised!r}"
            )
        if dv.call_timeout_s <= 0:
            raise ConfigError(
                "device.call_timeout_s must be > 0 seconds (the hard "
                "deadline every supervised device call is abandoned at); "
                f"got {dv.call_timeout_s!r}"
            )
        if dv.error_threshold < 1:
            raise ConfigError(
                "device.error_threshold must be >= 1 consecutive raised "
                "device errors before quarantine; got "
                f"{dv.error_threshold!r}"
            )
        if dv.probe_successes < 1:
            raise ConfigError(
                "device.probe_successes must be >= 1 consecutive in-"
                "deadline heal probes before re-admission; got "
                f"{dv.probe_successes!r}"
            )
        if dv.probe_interval_s <= 0:
            raise ConfigError(
                "device.probe_interval_s must be > 0 seconds between "
                f"heal-probe rounds; got {dv.probe_interval_s!r}"
            )
        q, b, t, r = self.query, self.breaker, self.tile, self.replica
        if r.sync_interval_ms < 0:
            raise ConfigError(
                "replica.sync_interval_ms must be >= 0 milliseconds (0 disables "
                f"follower WAL tailing); got {r.sync_interval_ms!r}"
            )
        if r.max_lag_ms < 0:
            raise ConfigError(
                "replica.max_lag_ms must be >= 0 milliseconds (0 disables hedge "
                f"staleness gating); got {r.max_lag_ms!r}"
            )
        if (r.max_lag_ms > 0 and r.sync_interval_ms <= 0
                and self.storage.follower_sync_interval_ms <= 0):
            # a never-syncing follower's reported lag grows from open time,
            # so this combination silently gates every follower out of
            # hedging within max_lag_ms of its open — a config mistake,
            # not a mode
            raise ConfigError(
                "replica.max_lag_ms > 0 requires follower WAL tailing "
                "(replica.sync_interval_ms > 0), or every follower ages "
                f"out of hedging at its open-time snapshot; got max_lag_ms="
                f"{r.max_lag_ms!r} with sync_interval_ms="
                f"{r.sync_interval_ms!r}"
            )
        if r.target_followers < 0:
            raise ConfigError(
                "replica.target_followers must be >= 0 followers per region "
                f"(0 keeps placement manual); got {r.target_followers!r}"
            )
        if not isinstance(q.device_topk, bool):
            raise ConfigError(
                "query.device_topk must be a boolean (on-device Sort/LIMIT/"
                f"HAVING finalization); got {q.device_topk!r}"
            )
        if not isinstance(t.incremental, bool):
            raise ConfigError(
                "tile.incremental must be a boolean (delta super-tile "
                f"maintenance on flush); got {t.incremental!r}"
            )
        if not isinstance(q.streamed_readback, bool):
            raise ConfigError(
                "query.streamed_readback must be a boolean (chunked "
                f"device->host fetches overlapped with decode); got "
                f"{q.streamed_readback!r}"
            )
        if q.readback_chunk_kb < 64:
            raise ConfigError(
                "query.readback_chunk_kb must be >= 64 KiB — smaller slices "
                "pay more link round-trips than the transfer they carry; "
                f"got {q.readback_chunk_kb!r}"
            )
        if t.build_workers < 1:
            raise ConfigError(
                "tile.build_workers must be >= 1 host consolidation worker; "
                f"got {t.build_workers!r}"
            )
        if not isinstance(t.mesh_devices, int) or isinstance(t.mesh_devices, bool):
            raise ConfigError(
                "tile.mesh_devices must be an integer device count "
                f"(0 = single-chip dispatch); got {t.mesh_devices!r}"
            )
        if t.mesh_devices < 0:
            raise ConfigError(
                "tile.mesh_devices must be >= 0 devices (0 = single-chip "
                f"dispatch, N = shard over the first N); got {t.mesh_devices!r}"
            )
        if t.mesh_devices > 0:
            # reject more mesh devices than the process can see — a mesh
            # the runtime cannot build would otherwise fail at the first
            # dispatch instead of at config time (jax is already resident
            # in any process that runs queries; tolerate its absence so a
            # config-only tool can still validate the rest)
            try:
                import jax

                available = len(jax.devices())
            except Exception:  # noqa: BLE001 — no runtime: skip the bound
                available = None
            if available is not None and t.mesh_devices > available:
                raise ConfigError(
                    f"tile.mesh_devices ({t.mesh_devices}) exceeds the "
                    f"{available} available local device(s) — the regions "
                    "mesh cannot be built; lower it or raise "
                    "XLA_FLAGS=--xla_force_host_platform_device_count"
                )
        if not isinstance(t.fused_build, bool):
            raise ConfigError(
                "tile.fused_build must be a boolean (fused one-pass family "
                f"cold builds + universal cold-serve); got {t.fused_build!r}"
            )
        if t.fused_build_timeout_s <= 0:
            raise ConfigError(
                "tile.fused_build_timeout_s must be > 0 seconds (deadline "
                "for one background fused family build); got "
                f"{t.fused_build_timeout_s!r}"
            )
        if t.prewarm_debounce_s < 0:
            raise ConfigError(
                "tile.prewarm_debounce_s must be >= 0 seconds (how long after "
                f"the last flush a prewarm build starts); got {t.prewarm_debounce_s!r}"
            )
        tq = self.tql
        if not isinstance(tq.tile, bool):
            raise ConfigError(
                "tql.tile must be a boolean (warm TQL device tile path; "
                f"false = legacy upload-per-query evaluation); got {tq.tile!r}"
            )
        if not isinstance(tq.max_cells, int) or isinstance(tq.max_cells, bool) \
                or tq.max_cells < 1:
            raise ConfigError(
                "tql.max_cells must be a positive integer bound on padded "
                f"series x steps cells per evaluation; got {tq.max_cells!r}"
            )
        if not isinstance(tq.compact_readback_kb, int) \
                or isinstance(tq.compact_readback_kb, bool) \
                or tq.compact_readback_kb < 1:
            raise ConfigError(
                "tql.compact_readback_kb must be a positive size in KiB "
                "(per-series results past it fetch via the two-phase "
                f"compacted readback); got {tq.compact_readback_kb!r}"
            )
        if q.hedge_delay_ms < 0:
            raise ConfigError(
                "query.hedge_delay_ms must be >= 0 milliseconds (0 disables hedging); "
                f"got {q.hedge_delay_ms!r}"
            )
        if not (0.0 < q.hedge_percentile < 1.0):
            raise ConfigError(
                "query.hedge_percentile must be in (0, 1) — a fraction of the "
                f"latency distribution; got {q.hedge_percentile!r}"
            )
        if b.window < 1:
            raise ConfigError(
                f"breaker.window must be >= 1 recent calls; got {b.window!r}"
            )
        if b.min_calls < 1:
            raise ConfigError(
                f"breaker.min_calls must be >= 1; got {b.min_calls!r}"
            )
        if b.min_calls > b.window:
            raise ConfigError(
                f"breaker.min_calls ({b.min_calls}) cannot exceed breaker.window "
                f"({b.window}) — the window can never hold enough samples to trip"
            )
        if not (0.0 < b.failure_rate <= 1.0):
            raise ConfigError(
                "breaker.failure_rate must be in (0, 1] — the failing fraction of "
                f"the window that trips the breaker; got {b.failure_rate!r}"
            )
        if b.open_cooldown_s <= 0:
            raise ConfigError(
                "breaker.open_cooldown_s must be > 0 seconds (how long an open "
                f"breaker sheds before probing); got {b.open_cooldown_s!r}"
            )
        if b.half_open_probes < 1:
            raise ConfigError(
                f"breaker.half_open_probes must be >= 1; got {b.half_open_probes!r}"
            )
        a = self.admission
        if a.max_concurrent < 0:
            raise ConfigError(
                "admission.max_concurrent must be >= 0 statements (0 falls "
                f"back to memory.max_concurrent_queries); got {a.max_concurrent!r}"
            )
        if a.max_queue_depth < 1:
            raise ConfigError(
                "admission.max_queue_depth must be >= 1 queued statements "
                f"per tenant; got {a.max_queue_depth!r}"
            )
        if a.max_queue_wait_ms < 0:
            raise ConfigError(
                "admission.max_queue_wait_ms must be >= 0 milliseconds "
                f"(0 = deadline-bounded only); got {a.max_queue_wait_ms!r}"
            )
        if a.default_weight < 1:
            raise ConfigError(
                f"admission.default_weight must be >= 1; got {a.default_weight!r}"
            )
        for pair in a.tenant_weights:
            name, sep, w = str(pair).partition(":")
            if not sep or not name:
                raise ConfigError(
                    "admission.tenant_weights entries must be 'tenant:weight' "
                    f"pairs; got {pair!r}"
                )
            try:
                if int(w) < 1:
                    raise ValueError
            except ValueError:
                raise ConfigError(
                    "admission.tenant_weights weight must be an integer >= 1; "
                    f"got {pair!r}"
                ) from None
        if not (0.0 < a.hbm_probe_headroom <= 1.0):
            raise ConfigError(
                "admission.hbm_probe_headroom must be in (0, 1] — the "
                "fraction of measured-free HBM the tile budget may take; "
                f"got {a.hbm_probe_headroom!r}"
            )
        if a.hbm_retry_attempts < 1:
            raise ConfigError(
                "admission.hbm_retry_attempts must be >= 1 halve-and-retry "
                f"rounds; got {a.hbm_retry_attempts!r}"
            )
        if a.min_chunk_rows < 4096:
            raise ConfigError(
                "admission.min_chunk_rows must be >= 4096 (the kernel block "
                "size — halving below one block cannot help an OOM); got "
                f"{a.min_chunk_rows!r}"
            )
        bt = self.batch
        if bt.window_ms < 0:
            raise ConfigError(
                "batch.window_ms must be >= 0 milliseconds (0 disables "
                f"cross-query batching); got {bt.window_ms!r}"
            )
        if bt.max_members < 2:
            raise ConfigError(
                "batch.max_members must be >= 2 queries per mega-dispatch "
                "— a one-member batch is just a solo dispatch with extra "
                f"latency; got {bt.max_members!r}"
            )
        if bt.result_cache_mb < 0:
            raise ConfigError(
                "batch.result_cache_mb must be >= 0 MB (0 disables the "
                f"windowed result cache); got {bt.result_cache_mb!r}"
            )
        if not isinstance(bt.fuse_programs, bool):
            raise ConfigError(
                "batch.fuse_programs must be a boolean (fuse a batch "
                "tick's member programs into one XLA invocation); got "
                f"{bt.fuse_programs!r}"
            )
        ix = self.index
        if not isinstance(ix.segmented, bool):
            raise ConfigError(
                "index.segmented must be a boolean (fence-keyed segmented "
                f"term index for new SSTs); got {ix.segmented!r}"
            )
        if ix.segment_terms < 16:
            raise ConfigError(
                "index.segment_terms must be >= 16 terms per segment — "
                "smaller segments pay a ranged read per handful of terms; "
                f"got {ix.segment_terms!r}"
            )
        if ix.max_terms < ix.segment_terms:
            raise ConfigError(
                f"index.max_terms ({ix.max_terms}) cannot be below "
                f"index.segment_terms ({ix.segment_terms}) — the index "
                "could never hold even one full segment"
            )
        ing = self.ingest
        if not isinstance(ing.group_commit, bool):
            raise ConfigError(
                "ingest.group_commit must be a boolean (merge each region-"
                "worker drain group into one WAL frame; false restores "
                "frame-per-write bytes bit-for-bit — the shape to run when "
                "you need one fsync SYSCALL per write rather than per-write "
                f"durability, which group commit preserves); got "
                f"{ing.group_commit!r}"
            )
        if not isinstance(ing.flush_overlap, bool):
            raise ConfigError(
                "ingest.flush_overlap must be a boolean (admit writes while "
                f"a flush encode is in flight); got {ing.flush_overlap!r}"
            )
        if not isinstance(ing.flush_workers, int) \
                or isinstance(ing.flush_workers, bool) \
                or not (1 <= ing.flush_workers <= 64):
            raise ConfigError(
                "ingest.flush_workers must be an integer in [1, 64] — the "
                "per-flush SST encode pool width (1 = serial pre-pipeline "
                f"loop); got {ing.flush_workers!r}"
            )
        if q.agg_strategy not in ("auto", "hash", "sort"):
            raise ConfigError(
                "query.agg_strategy must be 'auto', 'hash' or 'sort' (the "
                "device group-by strategy; 'sort' restores the dense "
                f"pre-hash path bit-for-bit); got {q.agg_strategy!r}"
            )
        if q.agg_hash_min_group_space < 1024:
            raise ConfigError(
                "query.agg_hash_min_group_space must be >= 1024 groups — "
                "below that the dense path is always cheaper than a hash "
                f"table; got {q.agg_hash_min_group_space!r}"
            )
        tr = self.trace
        if not isinstance(tr.enabled, bool):
            raise ConfigError(
                "trace.self must be a boolean (self-observability loop: "
                f"statement tracing into the own trace store); got {tr.enabled!r}"
            )
        if not (0.0 <= tr.sample_ratio <= 1.0):
            raise ConfigError(
                "trace.sample_ratio must be in [0, 1] — the head-sampling "
                f"fraction for fast clean statements; got {tr.sample_ratio!r}"
            )
        if tr.slow_query_ms < 0:
            raise ConfigError(
                "trace.slow_query_ms must be >= 0 milliseconds (statements "
                "slower than this force-keep their trace and land in "
                f"slow_queries); got {tr.slow_query_ms!r}"
            )
        if tr.scrape_interval_s < 0:
            raise ConfigError(
                "trace.scrape_interval_s must be >= 0 seconds (0 disables "
                f"the /metrics self-scrape); got {tr.scrape_interval_s!r}"
            )
        if tr.export_interval_s <= 0:
            raise ConfigError(
                "trace.export_interval_s must be > 0 seconds — the "
                f"SelfTraceWriter drain cadence; got {tr.export_interval_s!r}"
            )
        rec = self.recorder
        if not isinstance(rec.enabled, bool):
            raise ConfigError(
                "recorder.enabled must be a boolean (device flight "
                "recorder behind information_schema.device_dispatches); "
                f"got {rec.enabled!r}"
            )
        if not (16 <= int(rec.ring_size) <= (1 << 20)):
            raise ConfigError(
                "recorder.ring_size must be in [16, 1048576] records — "
                "the drop-oldest ring bound of the device flight "
                f"recorder; got {rec.ring_size!r}"
            )
        fl = self.flow
        if not isinstance(fl.incremental, bool):
            raise ConfigError(
                "flow.incremental must be a boolean (diff-driven dataflow "
                f"maintenance for CREATE FLOW); got {fl.incremental!r}"
            )
        if fl.window_ms < 1:
            raise ConfigError(
                "flow.window_ms must be >= 1 millisecond — the dirty-window "
                "granularity for recompute flows without a time-bucket "
                f"group key; got {fl.window_ms!r}"
            )
        if fl.max_windows_per_recompute < 1:
            raise ConfigError(
                "flow.max_windows_per_recompute must be >= 1 window per "
                f"diff batch; got {fl.max_windows_per_recompute!r}"
            )
        bal = self.balance
        if not isinstance(bal.enabled, bool):
            raise ConfigError(
                "balance.enabled must be a boolean (elastic region "
                f"split/merge/migration tick); got {bal.enabled!r}"
            )
        if not (0.0 < bal.ewma_alpha <= 1.0):
            raise ConfigError(
                "balance.ewma_alpha must be in (0, 1] — the EWMA smoothing "
                f"factor for region load scores; got {bal.ewma_alpha!r}"
            )
        if bal.min_dwell_ticks < 1:
            raise ConfigError(
                "balance.min_dwell_ticks must be >= 1 tick — 0 would let a "
                "single burst trigger a repartition, defeating hysteresis; "
                f"got {bal.min_dwell_ticks!r}"
            )
        if bal.cooldown_ticks < 0:
            raise ConfigError(
                "balance.cooldown_ticks must be >= 0 ticks of post-decision "
                f"rest per table; got {bal.cooldown_ticks!r}"
            )
        if bal.split_hot_score <= 0:
            raise ConfigError(
                "balance.split_hot_score must be > 0 — the absolute EWMA "
                f"score floor for a hot region; got {bal.split_hot_score!r}"
            )
        if bal.split_hot_ratio < 1.0:
            raise ConfigError(
                "balance.split_hot_ratio must be >= 1 — a hot region must "
                "be at least as loaded as its mean sibling; got "
                f"{bal.split_hot_ratio!r}"
            )
        if bal.merge_cold_score < 0:
            raise ConfigError(
                "balance.merge_cold_score must be >= 0 (0 disables merges); "
                f"got {bal.merge_cold_score!r}"
            )
        if bal.migrate_ratio < 1.0:
            raise ConfigError(
                "balance.migrate_ratio must be >= 1 — the overload multiple "
                f"of the fleet median score; got {bal.migrate_ratio!r}"
            )
        if not (1 <= bal.max_regions_per_table <= 1024):
            raise ConfigError(
                "balance.max_regions_per_table must be in [1, 1024] (the "
                f"catalog region-id space per table); got "
                f"{bal.max_regions_per_table!r}"
            )
        for wname in ("write_weight", "memtable_mb_weight", "dispatch_ms_weight"):
            w = getattr(bal, wname)
            if not isinstance(w, (int, float)) or isinstance(w, bool) or w < 0:
                raise ConfigError(
                    f"balance.{wname} must be a number >= 0 (its term's "
                    f"contribution to the region load score); got {w!r}"
                )
        rm = self.remote
        for ep_name in ("etcd_endpoints", "kafka_endpoints", "s3_endpoint"):
            spec = getattr(rm, ep_name)
            if not spec:
                continue
            # parse now so a malformed address fails at config time, not
            # on the adapter's first call
            from ..remote.wire import parse_endpoints

            try:
                parse_endpoints(spec)
            except ConfigError as exc:
                raise ConfigError(
                    f"remote.{ep_name} must be host:port[,host:port]; "
                    f"got {spec!r} ({exc})"
                ) from None
        if self.storage.wal_provider == "kafka" and not (
            rm.kafka_endpoints or self.storage.wal_kafka_endpoints
        ):
            raise ConfigError(
                "storage.wal_provider = 'kafka' requires "
                "remote.kafka_endpoints (a broker address — the offline "
                "fake in remote/fake_kafka.py works); the in-memory sims "
                "stay on 'local'/'shared_file'"
            )
        if self.storage.store_type == "s3" and not (
            rm.s3_endpoint or self.storage.store_s3_endpoint
        ):
            raise ConfigError(
                "storage.store_type = 's3' requires remote.s3_endpoint "
                "(an S3 REST address — the offline fake in "
                "remote/fake_s3.py works); 'fs'/'memory' need no endpoint"
            )
        if rm.s3_endpoint and not (rm.s3_access_key and rm.s3_secret_key):
            raise ConfigError(
                "remote.s3_endpoint is set but remote.s3_access_key / "
                "remote.s3_secret_key are empty — SigV4 signing needs both"
            )
        if rm.pool_size < 1:
            raise ConfigError(
                "remote.pool_size must be >= 1 pooled connection per "
                f"endpoint; got {rm.pool_size!r}"
            )
        if rm.call_deadline_s <= 0:
            raise ConfigError(
                "remote.call_deadline_s must be > 0 seconds (the per-call "
                f"socket budget); got {rm.call_deadline_s!r}"
            )
        if rm.connect_timeout_s <= 0:
            raise ConfigError(
                "remote.connect_timeout_s must be > 0 seconds; got "
                f"{rm.connect_timeout_s!r}"
            )
        if rm.retry_attempts < 1:
            raise ConfigError(
                "remote.retry_attempts must be >= 1 total attempts; got "
                f"{rm.retry_attempts!r}"
            )
        if rm.s3_multipart_mb < 1:
            raise ConfigError(
                "remote.s3_multipart_mb must be >= 1 MiB (the multipart "
                f"upload threshold/part size); got {rm.s3_multipart_mb!r}"
            )

    @classmethod
    def load(cls, path: str | None = None, env: dict[str, str] | None = None) -> "Config":
        """defaults -> TOML at `path` -> GREPTIMEDB_TPU__SECTION__KEY env vars."""
        layers: dict = {}
        if path and os.path.exists(path):
            with open(path, "rb") as f:
                layers = _deep_merge(layers, tomllib.load(f))
        env = env if env is not None else dict(os.environ)
        for key, val in env.items():
            if not key.startswith(ENV_PREFIX + "__"):
                continue
            parts = [p.lower() for p in key[len(ENV_PREFIX) + 2 :].split("__")]
            node: dict = layers
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = val
        return cls._from_dict(layers)

    @classmethod
    def _from_dict(cls, d: dict) -> "Config":
        cfg = cls()
        for section_field in dataclasses.fields(cls):
            section = getattr(cfg, section_field.name)
            overlay = d.get(section_field.name, {})
            if not isinstance(overlay, dict):
                continue
            # per-section key aliases (e.g. the documented `trace.self`
            # knob maps to TraceConfig.enabled — `self` cannot be a
            # dataclass field name)
            aliases = getattr(type(section), "_ALIASES", {})
            if aliases:
                overlay = {aliases.get(k, k): v for k, v in overlay.items()}
            for f in dataclasses.fields(section):
                if f.name in overlay:
                    raw = overlay[f.name]
                    default = getattr(section, f.name)
                    if isinstance(raw, str) and not isinstance(default, str):
                        raw = _coerce(raw, default)
                    setattr(section, f.name, raw)
        cfg.__post_init__()
        return cfg
