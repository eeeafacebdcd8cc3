"""Prometheus-style in-process metrics registry.

The reference exposes lazy_static prometheus counters/histograms per crate
(e.g. reference src/mito2/src/metrics.rs) served at /metrics.  We keep the
same shape: a process-global registry of counters, gauges and histograms,
renderable in the Prometheus text exposition format.
"""

from __future__ import annotations

import bisect
import threading
import time
from collections import defaultdict

_DEFAULT_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)


class Counter:
    def __init__(self, name: str, help_: str):
        self.name, self.help = name, help_
        self._values: dict[tuple, float] = defaultdict(float)
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0, **labels):
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] += amount

    def get(self, **labels) -> float:
        return self._values.get(tuple(sorted(labels.items())), 0.0)

    def total(self) -> float:
        """Sum across every label combination.  get() with no labels reads
        only the unlabeled key — which stays 0 forever on a counter whose
        inc() sites always attach labels — so aggregate readers (bench
        records, dashboards) must use this instead."""
        with self._lock:
            return sum(self._values.values())

    def render(self) -> list[str]:
        lines = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} counter"]
        for key, v in sorted(self._values.items()):
            lines.append(f"{self.name}{_fmt_labels(key)} {v}")
        return lines


class Gauge(Counter):
    def set(self, value: float, **labels):
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] = value

    def render(self) -> list[str]:
        lines = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} gauge"]
        for key, v in sorted(self._values.items()):
            lines.append(f"{self.name}{_fmt_labels(key)} {v}")
        return lines


class Histogram:
    def __init__(self, name: str, help_: str, buckets=_DEFAULT_BUCKETS):
        self.name, self.help = name, help_
        self.buckets = tuple(sorted(buckets))
        self._counts: dict[tuple, list[int]] = {}
        self._sums: dict[tuple, float] = defaultdict(float)
        self._totals: dict[tuple, int] = defaultdict(int)
        self._lock = threading.Lock()

    def observe(self, value: float, **labels):
        key = tuple(sorted(labels.items()))
        with self._lock:
            counts = self._counts.setdefault(key, [0] * len(self.buckets))
            i = bisect.bisect_left(self.buckets, value)
            if i < len(counts):
                counts[i] += 1
            self._sums[key] += value
            self._totals[key] += 1

    class _Timer:
        def __init__(self, hist, labels):
            self._hist, self._labels = hist, labels

        def __enter__(self):
            self._start = time.perf_counter()
            return self

        def __exit__(self, *exc):
            self._hist.observe(time.perf_counter() - self._start, **self._labels)
            return False

    def time(self, **labels) -> "Histogram._Timer":
        return self._Timer(self, labels)

    def total(self, **labels) -> int:
        return self._totals.get(tuple(sorted(labels.items())), 0)

    def sum(self, **labels) -> float:
        return self._sums.get(tuple(sorted(labels.items())), 0.0)

    def render(self) -> list[str]:
        lines = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} histogram"]
        for key in sorted(self._counts):
            cum = 0
            for ub, c in zip(self.buckets, self._counts[key]):
                cum += c
                lk = key + (("le", repr(ub)),)
                lines.append(f"{self.name}_bucket{_fmt_labels(lk)} {cum}")
            lk = key + (("le", "+Inf"),)
            lines.append(f"{self.name}_bucket{_fmt_labels(lk)} {self._totals[key]}")
            lines.append(f"{self.name}_sum{_fmt_labels(key)} {self._sums[key]}")
            lines.append(f"{self.name}_count{_fmt_labels(key)} {self._totals[key]}")
        return lines


def _fmt_labels(key: tuple) -> str:
    if not key:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in key)
    return "{" + inner + "}"


class Registry:
    def __init__(self):
        self._metrics: dict[str, object] = {}
        self._lock = threading.Lock()

    def counter(self, name: str, help_: str = "") -> Counter:
        return self._get_or_create(name, lambda: Counter(name, help_), Counter)

    def gauge(self, name: str, help_: str = "") -> Gauge:
        return self._get_or_create(name, lambda: Gauge(name, help_), Gauge)

    def histogram(self, name: str, help_: str = "", buckets=_DEFAULT_BUCKETS) -> Histogram:
        return self._get_or_create(name, lambda: Histogram(name, help_, buckets), Histogram)

    def _get_or_create(self, name, factory, kind):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = factory()
                self._metrics[name] = m
            assert isinstance(m, kind), f"metric {name} registered as {type(m)}"
            return m

    def render(self) -> str:
        lines: list[str] = []
        for name in sorted(self._metrics):
            lines.extend(self._metrics[name].render())
        return "\n".join(lines) + "\n"

    def snapshot(self) -> list[tuple[str, str, list[tuple[dict, float]]]]:
        """Point-in-time numeric view of every metric, for the metric
        self-scrape (utils/self_trace.py MetricScrapeTask): a list of
        (name, kind, [(labels, value)]) with histograms expanded into
        Prometheus-convention `_bucket` (cumulative, `le` label) / `_sum`
        / `_count` series — the exact series a real Prometheus scrape of
        /metrics would store, so PromQL over the self-scraped tables
        behaves like PromQL over an external scrape."""
        with self._lock:
            metrics_items = list(self._metrics.items())
        out: list[tuple[str, str, list[tuple[dict, float]]]] = []
        for name, m in metrics_items:
            if isinstance(m, Histogram):
                buckets: list[tuple[dict, float]] = []
                sums: list[tuple[dict, float]] = []
                counts: list[tuple[dict, float]] = []
                with m._lock:
                    keys = list(m._counts)
                    for key in keys:
                        labels = dict(key)
                        cum = 0
                        for ub, c in zip(m.buckets, m._counts[key]):
                            cum += c
                            buckets.append(({**labels, "le": repr(ub)}, float(cum)))
                        buckets.append(({**labels, "le": "+Inf"}, float(m._totals[key])))
                        sums.append((labels, float(m._sums[key])))
                        counts.append((labels, float(m._totals[key])))
                if counts:
                    out.append((f"{name}_bucket", "histogram", buckets))
                    out.append((f"{name}_sum", "histogram", sums))
                    out.append((f"{name}_count", "histogram", counts))
                continue
            kind = "gauge" if isinstance(m, Gauge) else "counter"
            with m._lock:
                entries = [(dict(key), float(v)) for key, v in m._values.items()]
            if entries:
                out.append((name, kind, entries))
        return out


REGISTRY = Registry()

# Core engine metrics, named after the reference's (mito2/src/metrics.rs).
WRITE_ROWS_TOTAL = REGISTRY.counter("greptime_mito_write_rows_total", "Rows written")
FLUSH_TOTAL = REGISTRY.counter("greptime_mito_flush_total", "Memtable flushes")
FLUSH_ELAPSED = REGISTRY.histogram("greptime_mito_flush_elapsed", "Flush seconds")
COMPACTION_TOTAL = REGISTRY.counter("greptime_mito_compaction_total", "Compactions")
WRITE_STALL_TOTAL = REGISTRY.counter("greptime_mito_write_stall_total", "Write stalls")
WRITE_STALL_S = REGISTRY.counter(
    "greptime_mito_write_stall_seconds_total",
    "Seconds foreground writes spent in their synchronous stall flush "
    "(the `flush.region` stages of cause=stall)")
FLUSH_SST_BYTES = REGISTRY.counter(
    "greptime_mito_flush_sst_bytes_total", "Parquet + index bytes of the level-0 files flushes wrote")
COMPACTION_INPUT_BYTES = REGISTRY.counter(
    "greptime_mito_compaction_input_bytes_total",
    "File + index bytes read by compaction merges that committed")
COMPACTION_OUTPUT_BYTES = REGISTRY.counter(
    "greptime_mito_compaction_output_bytes_total",
    "File + index bytes written by compaction merges that committed")
COMPACTION_DISCARDED_BYTES = REGISTRY.counter(
    "greptime_mito_compaction_discarded_bytes_total",
    "File + index bytes of merge outputs whose commit was refused")
# Pipelined columnar ingest: per-stage timings + WAL frame accounting.
# The stage histograms split a write's wall time between partition-split
# (frontend), WAL append and memtable apply; flush_encode covers the sort,
# Parquet and index encode of one flush.  Each observes the duration of its
# stage (`write.split`, `write.wal`, `write.memtable`, `flush.windows`; and
# FLUSH_ELAPSED above that of `flush.region`): one clock.  The frame counters are the
# group-commit observability contract: with ingest.group_commit on,
# wal_frames_total grows SLOWER than writes_total (merged frames), and
# group_writes_total counts the write entries those merged frames carried.
INGEST_SPLIT_MS = REGISTRY.histogram(
    "greptime_ingest_split_ms", "Partition-rule row routing milliseconds per write batch")
INGEST_WAL_MS = REGISTRY.histogram(
    "greptime_ingest_wal_ms", "WAL append milliseconds per write (group appends count once)")
INGEST_MEMTABLE_MS = REGISTRY.histogram(
    "greptime_ingest_memtable_ms", "Memtable apply milliseconds per write")
INGEST_FLUSH_ENCODE_MS = REGISTRY.histogram(
    "greptime_ingest_flush_encode_ms", "Parquet + index encode milliseconds per flush")
INGEST_WRITES_TOTAL = REGISTRY.counter(
    "greptime_ingest_writes_total", "Write requests through the region write path")
INGEST_WAL_FRAMES = REGISTRY.counter(
    "greptime_ingest_wal_frames_total", "WAL frames written (solo or merged group)")
INGEST_WAL_BYTES = REGISTRY.counter(
    "greptime_ingest_wal_bytes_total", "WAL bytes written (frame headers + payload)")
INGEST_GROUP_FRAMES = REGISTRY.counter(
    "greptime_ingest_wal_group_frames_total", "Merged group-commit WAL frames written")
INGEST_GROUP_WRITES = REGISTRY.counter(
    "greptime_ingest_wal_group_writes_total", "Write entries carried by merged group frames")
QUERY_ELAPSED = REGISTRY.histogram("greptime_query_elapsed", "Query seconds")
TPU_LOWERED_TOTAL = REGISTRY.counter("greptime_query_tpu_lowered_total", "Plans lowered to TPU")
TPU_FALLBACK_TOTAL = REGISTRY.counter("greptime_query_tpu_fallback_total", "Plans that fell back to CPU")
TPU_ROUTED_TO_CPU = REGISTRY.counter("greptime_query_tpu_routed_cpu_total", "Lowerable plans routed to CPU by the cost model")
TILE_CACHE_HITS = REGISTRY.counter("greptime_tile_cache_hits_total", "HBM tile cache hits (files)")
TILE_CACHE_MISSES = REGISTRY.counter("greptime_tile_cache_misses_total", "HBM tile cache builds (files)")
TILE_CACHE_EVICTIONS = REGISTRY.counter("greptime_tile_cache_evictions_total", "HBM tile cache evictions")
TILE_QUERY_ELAPSED = REGISTRY.histogram("greptime_query_tile_elapsed", "Tile-path query seconds")
TILE_LOWERED_TOTAL = REGISTRY.counter("greptime_query_tile_lowered_total", "Queries served from the HBM tile cache")
TILE_LIMB_RERUNS = REGISTRY.counter("greptime_tile_limb_reruns_total", "Tile queries rerun in exact f64 after the limb error-bound verdict failed")
AGG_STRATEGY_TOTAL = REGISTRY.counter(
    "greptime_agg_strategy_total",
    "Device group-by dispatches by chosen strategy {strategy=hash|sort}",
)
AGG_HASH_OVERFLOW = REGISTRY.counter(
    "greptime_agg_hash_overflow_total",
    "Hash group-by dispatches whose slot table overflowed (distinct-key "
    "estimate badly low) and fell back to the dense path",
)
TILE_PERSIST_HITS = REGISTRY.counter("greptime_tile_persist_hits_total", "Super-tile consolidations loaded from the persisted store (cold-start skip)")
TILE_PERSIST_WRITES = REGISTRY.counter("greptime_tile_persist_writes_total", "Super-tile consolidations written to the persisted store")
TILE_WINDOW_BUILDS = REGISTRY.counter("greptime_tile_window_builds_total", "Compact window tiles gathered from sorted encodes")
TILE_WINDOW_COUNTED = REGISTRY.counter("greptime_tile_window_counted_total", "Window-tile probes whose row count came from the run bounds of the sorted ts plane (less the builds: probes that declined without touching the plane)")
TILE_WINDOW_RESIDENT_SCANS = REGISTRY.counter("greptime_tile_window_resident_scans_total", "Counted window-tile probes that declined because the region's full planes were on the device and their masked scan was cheaper than the tile's host build")
TILE_ORDINAL_GIDS = REGISTRY.counter("greptime_tile_ordinal_gids_total", "Tile dispatches whose plan groups by the source's own series ordinals in place of the leading tag's table-wide codes (a region of a table partitioned on that tag)")
TILE_HOST_FAST_PATH = REGISTRY.counter("greptime_tile_host_fast_path_total", "Selective queries served from the sorted host encode cache")
TILE_STREAM_QUERIES = REGISTRY.counter("greptime_tile_stream_total", "Queries whose working set exceeded the HBM budget, executed region-streamed")
TILE_DELTA_MERGES = REGISTRY.counter(
    "greptime_tile_delta_merges_total",
    "Super-tile entries extended IN PLACE by a flush delta (merge of sorted "
    "runs + on-device plane patch) instead of a from-scratch rebuild",
)
TILE_DELTA_ROWS = REGISTRY.counter(
    "greptime_tile_delta_rows_total",
    "Rows merged into existing super-tiles by delta builds (the O(delta) "
    "post-flush cold contract)",
)
TILE_FUSED_MANIFESTS = REGISTRY.counter(
    "greptime_tile_fused_manifests_total",
    "Plane-requirement manifests recorded by query plans / prewarm for the "
    "fused family build planner",
)
TILE_FUSED_BUILDS = REGISTRY.counter(
    "greptime_tile_fused_builds_total",
    "Fused family builds: one consolidated pass building the UNION of the "
    "family's plane manifests (decode each SST once, encode each column "
    "once, one batched upload)",
)
TILE_FUSED_DECODES_SAVED = REGISTRY.counter(
    "greptime_tile_fused_decodes_saved_total",
    "SST file decodes avoided because the fused family pass already holds "
    "the file's host-encoded columns (per file per build request)",
)
TILE_FUSED_ENCODES_SAVED = REGISTRY.counter(
    "greptime_tile_fused_encodes_saved_total",
    "Per-column host encodes avoided because an earlier family member of "
    "the fused build already encoded the column",
)
TILE_FILE_DECODES = REGISTRY.counter(
    "greptime_tile_file_decodes_total",
    "Real SST Parquet decodes performed by the tile build path — the "
    "fused-build contract is exactly ONE per source file per family build",
)
TILE_BUILD_COALESCED = REGISTRY.counter(
    "greptime_tile_build_coalesced_total",
    "Cold tile builds that did NOT run because an in-flight fused family "
    "build covered them; the waiter adopted the leader's planes",
)
TILE_COLD_SERVES = REGISTRY.counter(
    "greptime_tile_cold_serves_total",
    "Queries answered from the host consolidation by the cold-serve router "
    "while device planes build in the background",
)
TILE_FLUSH_DELTA_FILES = REGISTRY.counter(
    "greptime_tile_flush_delta_files_total",
    "SST files announced to flush listeners as delta notifications",
)
TILE_PIPELINED_BUILDS = REGISTRY.counter(
    "greptime_tile_pipelined_builds_total",
    "Cold super-tile builds whose host encode overlapped device upload "
    "(the three-stage encode/upload/compile pipeline)",
)
TPU_PRECOMPILES = REGISTRY.counter(
    "greptime_tpu_precompile_total",
    "Tile-program compiles started from shape metadata alone, before data "
    "upload finished (pipelined cold path)",
)

# Device-side result finalization + readback accounting (the O(rows_out)
# fetch contract): BYTES are the honest unit —
# greptime_tile_readback_ms conflates compute with transfer because
# device_get blocks on the async dispatch, so tests and the bench assert
# on bytes.  Dispatch/fetch counters back the one-dispatch-one-fetch
# invariant test.
TPU_READBACK_BYTES = REGISTRY.counter(
    "greptime_tpu_readback_bytes_total",
    "Device->host result bytes fetched per lowered query (the O(rows_out) contract)",
)
TPU_READBACK_MS = REGISTRY.histogram(
    "greptime_tpu_readback_ms",
    "Device->host result fetch milliseconds (includes waiting out the async dispatch)",
)
TPU_READBACK_TRANSFER_MS = REGISTRY.histogram(
    "greptime_tpu_readback_transfer_ms",
    "Device->host transfer milliseconds of the result fetch (wire/link time, "
    "including waiting out the async dispatch on the first slice)",
)
TPU_READBACK_DECODE_MS = REGISTRY.histogram(
    "greptime_tpu_readback_decode_ms",
    "Host-side milliseconds decoding the fetched result buffers into Arrow "
    "rows (unpack, NULL-gate, tag/bucket decode, table assembly)",
)
TPU_READBACK_STREAMED = REGISTRY.counter(
    "greptime_tpu_readback_streamed_total",
    "Result fetches split into chunked device_gets overlapped with host "
    "decode (query.streamed_readback)",
)
TPU_DEVICE_DISPATCHES = REGISTRY.counter(
    "greptime_tpu_device_dispatches_total",
    "Compiled tile programs dispatched (one per lowered query attempt)",
)
TILE_MESH_DISPATCHES = REGISTRY.counter(
    "greptime_tile_mesh_dispatches_total",
    "Tile dispatches executed under shard_map on the regions device mesh "
    "(tile.mesh_devices > 0)",
)
TILE_MESH_DEGRADED = REGISTRY.counter(
    "greptime_tile_mesh_degraded_total",
    "Mesh tile dispatches that failed (collective error / OOM) and "
    "degraded to the single-chip path",
)
TILE_MESH_INELIGIBLE = REGISTRY.counter(
    "greptime_tile_mesh_ineligible_total",
    "Tile dispatches handed to the single chip while tile.mesh_devices > 0: "
    "a source shape the mesh program does not express, or the mesh_dispatch "
    "pass disabled (the reason is the tile.dispatch span's mesh_ineligible)",
)
TPU_DEVICE_FETCHES = REGISTRY.counter(
    "greptime_tpu_device_fetches_total",
    "Device->host result fetches (one per lowered query attempt)",
)
TQL_TILE_DISPATCHES = REGISTRY.counter(
    "greptime_tql_tile_dispatch_total",
    "TQL range-vector evaluations served warm from device tiles in one "
    "fused dispatch (the tql_tile pass)",
)
TQL_TILE_DEGRADED = REGISTRY.counter(
    "greptime_tql_tile_degraded_total",
    "TQL tile-path attempts that failed (fault tql.tile / device error) "
    "and degraded to the legacy upload-per-query path",
)
TQL_TILE_INELIGIBLE = REGISTRY.counter(
    "greptime_tql_tile_ineligible_total",
    "TQL range-vector evaluations the tile path found ineligible and handed "
    "to the legacy scan (the reason is on the `tql.plan` stage)",
)
TQL_TILE_SEGMENT_STATS = REGISTRY.counter(
    "greptime_tql_tile_segment_stats_total",
    "TQL tile dispatches whose program reduces sum / min / max by segment "
    "over the plane (avg/sum/min/max_over_time); the rate family, count, "
    "last and timestamp() read rows by position and never move it",
)
TQL_TILE_LOGICAL_DISPATCHES = REGISTRY.counter(
    "greptime_tql_tile_logical_dispatch_total",
    "TQL tile dispatches whose source is a metric-engine logical table: a "
    "row range of its physical region's planes",
)
TQL_TILE_PLANE_ROWS = REGISTRY.counter(
    "greptime_tql_tile_plane_rows_total",
    "Padded rows of the planes handed to the TQL tile program, summed over "
    "dispatches: the region's for a mito table, the slice's for a logical one",
)
METRIC_TSID_HASHES = REGISTRY.counter(
    "greptime_metric_tsid_hashes_total",
    "Label sets hashed to a __tsid by the metric engine's write path (one "
    "per distinct label set of a batch, not one per row)",
)
TQL_TILE_COLD_SERVES = REGISTRY.counter(
    "greptime_tql_tile_cold_serves_total",
    "Cold TQL queries answered from the legacy scan while their family's "
    "background plane build was scheduled",
)
TPU_DEVICE_FINALIZE = REGISTRY.counter(
    "greptime_tpu_device_finalize_total",
    "Queries whose Sort/Limit/HAVING/compaction ran on device (O(rows_out) readback)",
)
TPU_COMPILE_CACHE_HITS = REGISTRY.counter(
    "greptime_tpu_compile_cache_hits_total",
    "Tile-program builds served from the in-process program cache",
)
TPU_COMPILE_CACHE_MISSES = REGISTRY.counter(
    "greptime_tpu_compile_cache_misses_total",
    "Tile-program builds that traced + compiled fresh",
)
PREWARM_BUILDS = REGISTRY.counter(
    "greptime_tpu_prewarm_builds_total",
    "Regions whose super-tiles/limb planes were built by prewarm (off the query path)",
)
PREWARM_MS = REGISTRY.histogram(
    "greptime_tpu_prewarm_ms",
    "Wall milliseconds spent in prewarm builds",
)
DIST_STATE_QUERIES = REGISTRY.counter("greptime_query_dist_state_total", "Distributed queries merged from shipped states")
COMPACTION_BACKGROUND = REGISTRY.counter("greptime_mito_compaction_background_total", "Background compaction merges")
COMPACTION_FAILED = REGISTRY.counter("greptime_mito_compaction_failed_total", "Compaction rounds that errored")

# Fault-tolerance / tail-tolerance metrics (frontend + metasrv planes).
RETRY_ATTEMPTS_TOTAL = REGISTRY.counter(
    "greptime_retry_attempts_total", "Retry re-attempts under the unified RetryPolicy"
)
ROUTE_REFRESH_TOTAL = REGISTRY.counter(
    "greptime_route_refresh_total", "Region route re-fetches between retry attempts"
)
BREAKER_STATE = REGISTRY.gauge(
    "greptime_breaker_state", "Circuit breaker state per peer (0 closed, 1 open, 2 half-open)"
)
BREAKER_TRIPS_TOTAL = REGISTRY.counter(
    "greptime_breaker_trips_total", "Circuit breaker closed/half-open -> open transitions"
)
BREAKER_SHED_TOTAL = REGISTRY.counter(
    "greptime_breaker_shed_total", "Calls failed fast because the peer's breaker was open"
)
HEDGE_REQUESTS_TOTAL = REGISTRY.counter(
    "greptime_hedge_requests_total", "Hedged duplicate region reads sent to followers"
)
HEDGE_WINS_TOTAL = REGISTRY.counter(
    "greptime_hedge_wins_total", "Hedged reads that returned before the primary"
)
FANOUT_ABANDONED_TOTAL = REGISTRY.counter(
    "greptime_fanout_abandoned_total",
    "In-flight region sub-requests abandoned at deadline expiry (client dropped)",
)
PROCEDURE_RETRIES_TOTAL = REGISTRY.counter(
    "greptime_procedure_step_retries_total", "Procedure steps retried after transient failures"
)
FLOW_MIRROR_TOTAL = REGISTRY.counter(
    "greptime_flow_mirror_total", "Flow mirror batches enqueued to flownodes"
)
FLOW_MIRROR_FAILURES_TOTAL = REGISTRY.counter(
    "greptime_flow_mirror_failures_total", "Flow mirror deliveries that failed an attempt"
)
FLOW_MIRROR_DROPPED_TOTAL = REGISTRY.counter(
    "greptime_flow_mirror_dropped_total", "Flow mirror batches dropped after exhausting retries"
)
FLOW_DEDUPE_TOTAL = REGISTRY.counter(
    "greptime_flow_dedupe_total",
    "Mirrored batches the flownode deduplicated by (source, batch_id) — "
    "applied-but-reply-lost retries that would have double-counted",
)

# Incremental dataflow (flow/dataflow.py): diff-driven map/filter/project/
# join flows with dirty-window recompute.  The fallback counter is the
# observability half of the degradation ladder — a CREATE FLOW that cannot
# take the incremental graph leaves a labeled trace instead of silently
# degrading to periodic batch re-runs.
FLOW_BATCH_FALLBACK_TOTAL = REGISTRY.counter(
    "greptime_flow_batch_fallback_total",
    "CREATE FLOW plans that fell back to periodic batch re-runs "
    "(labels: reason = the first graph-inexpressible feature found)",
)
FLOW_DIFF_BATCHES_TOTAL = REGISTRY.counter(
    "greptime_flow_diff_batches_total",
    "Insert diff batches propagated through dataflow operator graphs",
)
FLOW_DIFF_ROWS_TOTAL = REGISTRY.counter(
    "greptime_flow_diff_rows_total",
    "Diff rows (sum of multiplicities) propagated through dataflow "
    "operator graphs",
)
FLOW_DIRTY_WINDOWS_TOTAL = REGISTRY.counter(
    "greptime_flow_dirty_windows_total",
    "Time windows recomputed by dirty-window dataflow operators "
    "(joins + heavy-aggregate window recompute)",
)
FLOW_EXPIRED_TOTAL = REGISTRY.counter(
    "greptime_flow_expired_total",
    "Diff rows / group states / index windows dropped by flow EXPIRE AFTER",
)
FLOW_DEVICE_DISPATCH_TOTAL = REGISTRY.counter(
    "greptime_flow_device_dispatch_total",
    "Flow window recomputes whose aggregate state rebuild dispatched "
    "through the device tile path (materialized-view maintenance riding "
    "the TPU)",
)

# Follower freshness (bounded-staleness replicas): per-region lag gauges
# exported by the follower's own engine, and the hedge/placement/pruning
# counters that ride on them.
FOLLOWER_LAG_ENTRIES = REGISTRY.gauge(
    "greptime_follower_lag_entries",
    "WAL entries a follower region has not yet replayed (best-effort: the "
    "log head is observed at sync time)",
)
FOLLOWER_LAG_MS = REGISTRY.gauge(
    "greptime_follower_lag_ms",
    "Milliseconds since a follower region's last successful WAL-tail sync "
    "(grows monotonically while the sync loop is wedged or disabled)",
)
FOLLOWER_SYNC_TOTAL = REGISTRY.counter(
    "greptime_follower_sync_total", "Follower WAL-tail sync rounds completed"
)
FOLLOWER_SYNC_FAILURES_TOTAL = REGISTRY.counter(
    "greptime_follower_sync_failures_total",
    "Follower sync rounds that failed (transient WAL/manifest weather)",
)
FOLLOWER_MANIFEST_REFRESH_TOTAL = REGISTRY.counter(
    "greptime_follower_manifest_refresh_total",
    "Follower manifest-view refreshes taken because the leader's manifest "
    "version advanced (flush/compaction/truncate/alter)",
)
HEDGE_SKIPPED_STALE_TOTAL = REGISTRY.counter(
    "greptime_hedge_skipped_stale_total",
    "Hedge candidates skipped because the follower's lag exceeded "
    "replica.max_lag_ms",
)
FANOUT_CANCELLED_TOTAL = REGISTRY.counter(
    "greptime_fanout_cancelled_total",
    "In-flight Flight calls best-effort cancelled at deadline expiry "
    "(feature-detected reader cancel, channel close for calls still "
    "waiting on the stream; detach-and-drop is the fallback)",
)
FOLLOWER_PLACEMENTS_TOTAL = REGISTRY.counter(
    "greptime_follower_placements_total",
    "Followers opened by the metasrv placement selector",
)
FOLLOWER_GC_TOTAL = REGISTRY.counter(
    "greptime_follower_gc_total",
    "Orphaned followers (dead node / now-the-leader) garbage-collected "
    "from region routes by the placement pass",
)
WAL_PRUNE_HELD_TOTAL = REGISTRY.counter(
    "greptime_wal_prune_held_total",
    "Shared-WAL segments whose deletion was held back by a follower "
    "replay low-watermark",
)

# Multi-tenant admission control + overload survival (utils/admission.py,
# the tile executor's coalescing/HBM feedback in parallel/tile_cache.py).
ADMISSION_QUEUE_DEPTH = REGISTRY.gauge(
    "greptime_admission_queue_depth",
    "Statements currently queued by the admission scheduler, per tenant",
)
ADMISSION_RUNNING = REGISTRY.gauge(
    "greptime_admission_running",
    "Statements currently admitted and executing under the admission gate",
)
ADMISSION_WAIT_MS = REGISTRY.histogram(
    "greptime_admission_wait_ms",
    "Milliseconds a statement waited in the admission queue before running",
)
ADMISSION_ADMITTED_TOTAL = REGISTRY.counter(
    "greptime_admission_admitted_total",
    "Statements admitted by the scheduler (immediately or after queueing)",
)
ADMISSION_SHED_TOTAL = REGISTRY.counter(
    "greptime_admission_shed_total",
    "Statements shed by the admission layer (labels: reason = "
    "queue_depth | deadline | wait_timeout | injected)",
)
DISPATCH_COALESCED_TOTAL = REGISTRY.counter(
    "greptime_dispatch_coalesced_total",
    "Tile queries served by attaching to another query's in-flight "
    "device dispatch (leader executes once, waiters share the result)",
)
DISPATCH_COALESCE_LEADERS_TOTAL = REGISTRY.counter(
    "greptime_dispatch_coalesce_leader_total",
    "Tile dispatches that executed as a coalition leader with >= 1 waiter",
)
QUERY_BATCH_DISPATCHES_TOTAL = REGISTRY.counter(
    "greptime_query_batch_dispatches_total",
    "Fused mega-dispatches executed by the cross-query batcher (>= 2 "
    "distinct warm queries sharing one packed device readback)",
)
QUERY_BATCH_MEMBERS_TOTAL = REGISTRY.counter(
    "greptime_query_batch_members_total",
    "Queries whose result came home inside a batched mega-readback "
    "(members per dispatch = members_total / dispatches_total)",
)
QUERY_BATCH_FUSED_DISPATCHES_TOTAL = REGISTRY.counter(
    "greptime_batch_fused_dispatches_total",
    "Batch ticks whose members executed as ONE mega-fused XLA invocation "
    "(shared plane scan, per-member masks/folds/finalize fused branches)",
)
QUERY_BATCH_FUSE_MEMBERS = REGISTRY.histogram(
    "greptime_batch_fuse_members",
    "Members fused into one mega-program invocation, per batch tick",
    buckets=(2, 3, 4, 6, 8, 12, 16, 24, 32),
)
QUERY_BATCH_FUSE_DEGRADED_TOTAL = REGISTRY.counter(
    "greptime_batch_fuse_degraded_total",
    "Batch ticks that fell back to per-member dispatches after a fused "
    "capture/trace/compile/dispatch failure (served correctly, unfused)",
)
QUERY_BATCH_RESULT_CACHE_HITS_TOTAL = REGISTRY.counter(
    "greptime_query_batch_result_cache_hits_total",
    "Warm queries served from the windowed result cache with zero "
    "device dispatch (key: plan fingerprint + literal digest + region "
    "versions + aligned window)",
)
QUERY_BATCH_RESULT_CACHE_EVICTIONS_TOTAL = REGISTRY.counter(
    "greptime_query_batch_result_cache_evictions_total",
    "Result-cache entries dropped: LRU pressure against "
    "batch.result_cache_mb or region invalidation on flush/delta",
)
HBM_EXHAUSTED_TOTAL = REGISTRY.counter(
    "greptime_hbm_exhausted_total",
    "RESOURCE_EXHAUSTED dispatch failures absorbed by the closed HBM "
    "feedback loop (emergency release + halve-chunk retry)",
)
HBM_CHUNK_ROWS = REGISTRY.gauge(
    "greptime_hbm_chunk_rows",
    "Current tile chunk size in rows (halved by the HBM feedback loop "
    "after RESOURCE_EXHAUSTED; never below admission.min_chunk_rows)",
)
HBM_PROBE_FREE_BYTES = REGISTRY.gauge(
    "greptime_hbm_probe_free_bytes",
    "Free device memory measured by the startup allocation probe "
    "(0 = probe unavailable on this backend)",
)
GOVERNOR_GATE_WAIT_MS = REGISTRY.histogram(
    "greptime_memory_gate_wait_ms",
    "Milliseconds a statement blocked in MemoryGovernor's concurrency "
    "gate before a slot freed (deadline-clipped bounded wait)",
)
WRITE_HEDGE_TOTAL = REGISTRY.counter(
    "greptime_write_hedge_total",
    "Writes that met an open breaker and successfully hedged to the "
    "failover candidate (breaker.write_hedge; metasrv accepted the "
    "frontend-initiated failover)",
)
WRITE_HEDGE_REFUSED_TOTAL = REGISTRY.counter(
    "greptime_write_hedge_refused_total",
    "Write-hedge failover requests the metasrv refused (node lease still "
    "live / procedure already running / metasrv churn): the write sheds "
    "like a read",
)
FAILOVER_REQUESTED_TOTAL = REGISTRY.counter(
    "greptime_failover_requested_total",
    "Frontend-initiated failovers the metasrv accepted and ran "
    "(breaker-aware write routing)",
)

# Self-observability loop (utils/tracing.py ring exporter +
# utils/self_trace.py writer/scrape): the database tracing itself into its
# own trace store, slow-query log and metric engine.
TRACE_SPANS_DROPPED = REGISTRY.counter(
    "greptime_trace_spans_dropped_total",
    "Spans shed by the exporter ring buffer (oldest-first) because the "
    "self-trace writer fell behind or self-tracing is off",
)
TRACE_SAMPLED_TOTAL = REGISTRY.counter(
    "greptime_trace_sampled_total",
    "Tail-sampling decisions per traced statement (labels: decision = "
    "slow | error | sampled | dropped)",
)
SELF_TRACE_ROWS = REGISTRY.counter(
    "greptime_self_trace_rows_total",
    "Span rows the SelfTraceWriter wrote into the own trace table",
)
SELF_TRACE_WRITE_FAILURES = REGISTRY.counter(
    "greptime_self_trace_write_failures_total",
    "Self-trace write batches dropped after a write failure (best-effort "
    "by contract: a trace-write failure never fails the traced query)",
)
SELF_SCRAPE_ROWS = REGISTRY.counter(
    "greptime_self_scrape_rows_total",
    "Metric samples the self-scrape task wrote into the metric engine",
)
SELF_SCRAPE_RUNS = REGISTRY.counter(
    "greptime_self_scrape_runs_total",
    "Completed /metrics self-scrape rounds",
)

# Device flight recorder (utils/flight_recorder.py): the per-dispatch
# introspection ring behind information_schema.device_dispatches,
# EXPLAIN ANALYZE's device-stage split and /debug/tile.
RECORDER_RECORDS = REGISTRY.counter(
    "greptime_recorder_records_total",
    "Dispatch records appended to the flight-recorder ring",
)
RECORDER_DROPPED = REGISTRY.counter(
    "greptime_recorder_dropped_total",
    "Flight-recorder records evicted oldest-first by the bounded ring",
)
RECORDER_ERRORS = REGISTRY.counter(
    "greptime_recorder_errors_total",
    "Flight-recorder emit failures swallowed (recording is best-effort "
    "by contract: a recorder failure never fails the recorded query)",
)

# Elastic balancer (distributed/balancer.py): load-driven region
# split/merge/migration decisions behind information_schema.region_balance.
BALANCE_DECISIONS_TOTAL = REGISTRY.counter(
    "greptime_balance_decisions_total",
    "Balancer decisions that cleared hysteresis and were enacted "
    "(labels: decision = split | merge | migrate)",
)
BALANCE_SPLITS_TOTAL = REGISTRY.counter(
    "greptime_balance_splits_total",
    "Hot-region splits the balancer drove through RepartitionProcedure",
)
BALANCE_MERGES_TOTAL = REGISTRY.counter(
    "greptime_balance_merges_total",
    "Cold-sibling merges the balancer drove through RepartitionProcedure",
)
BALANCE_MIGRATIONS_TOTAL = REGISTRY.counter(
    "greptime_balance_migrations_total",
    "Region migrations the balancer drove off overloaded datanodes",
)
BALANCE_SKIPPED_HYSTERESIS_TOTAL = REGISTRY.counter(
    "greptime_balance_skipped_hysteresis_total",
    "Decisions deferred by hysteresis (EWMA dwell not yet met, table "
    "cooling down after a recent decision, or a conflicting procedure "
    "holds the region lock)",
)

# Wire-level remote backends (remote/): etcd v3 / Kafka / S3 adapters
# routed through the shared wire resilience layer.
REMOTE_CALLS_TOTAL = REGISTRY.counter(
    "greptime_remote_calls_total",
    "Remote backend wire calls issued (labels: backend = etcd | kafka | "
    "s3, op = protocol-level operation name)",
)
REMOTE_ERRORS_TOTAL = REGISTRY.counter(
    "greptime_remote_errors_total",
    "Remote backend wire calls that failed after exhausting the retry "
    "policy (labels: backend, op)",
)
REMOTE_RETRIES_TOTAL = REGISTRY.counter(
    "greptime_remote_retries_total",
    "Transient remote-call failures that were retried by the wire layer "
    "(labels: backend)",
)
REMOTE_CALL_MS = REGISTRY.histogram(
    "greptime_remote_call_elapsed_ms",
    "End-to-end remote call latency in milliseconds, retries included "
    "(labels: backend)",
)
REMOTE_THROTTLED_TOTAL = REGISTRY.counter(
    "greptime_remote_throttled_total",
    "Server throttle responses honored with a Retry-After style backoff "
    "(S3 503 SlowDown; labels: backend)",
)
OTLP_SELF_EXPORT_SPANS = REGISTRY.counter(
    "greptime_otlp_self_export_spans_total",
    "Self-observability spans shipped over the wire as OTLP protobuf by "
    "roles with no local writer (bare datanodes)",
)
OTLP_SELF_EXPORT_FAILURES = REGISTRY.counter(
    "greptime_otlp_self_export_failures_total",
    "OTLP self-export batches dropped after the wire layer gave up "
    "(export is best-effort: a full buffer never blocks the hot path)",
)

# Device health supervisor (utils/device_health.py): bounded device calls,
# wedge detection, quarantine + heal behind
# information_schema.device_health.
DEVICE_HEALTH_TRANSITIONS = REGISTRY.counter(
    "greptime_device_health_transitions_total",
    "Device health state-machine transitions (labels: to = HEALTHY | "
    "SUSPECT | QUARANTINED | PROBING)",
)
DEVICE_HEALTH_STATE = REGISTRY.gauge(
    "greptime_device_health_state",
    "Current per-device health state (labels: device; 0 healthy, "
    "1 suspect, 2 quarantined, 3 probing)",
)
DEVICE_HEALTH_ABANDONED = REGISTRY.counter(
    "greptime_device_health_abandoned_calls_total",
    "Supervised device calls abandoned at their hard deadline — the "
    "future detached and the worker thread written off, since a wedged "
    "native call cannot be cancelled (labels: kind = upload | dispatch | "
    "readback | mesh | memory_stats | probe)",
)
DEVICE_HEALTH_QUARANTINES = REGISTRY.counter(
    "greptime_device_health_quarantines_total",
    "Devices quarantined (abandoned call, or error_threshold consecutive "
    "raised device errors)",
)
DEVICE_HEALTH_HEALS = REGISTRY.counter(
    "greptime_device_health_heals_total",
    "Quarantined devices re-admitted after probe_successes consecutive "
    "in-deadline ghost dispatches",
)
DEVICE_HEALTH_PROBES = REGISTRY.counter(
    "greptime_device_health_probes_total",
    "Heal-prober ghost dispatches against quarantined devices "
    "(labels: result = ok | fail)",
)
DEVICE_WORKER_REFILLS = REGISTRY.counter(
    "greptime_device_worker_refills_total",
    "Replacement device-call worker threads spawned after an abandonment "
    "wrote the previous worker off (the supervisor's bounded thread leak)",
)
TILE_HEALTH_INVALIDATIONS = REGISTRY.counter(
    "greptime_tile_health_invalidations_total",
    "Tile-cache device-plane drops triggered by a device-health "
    "generation change (quarantine or heal): entries rebuild on the "
    "surviving device set",
)

# ---- stage clocks (utils/tracing.py `stage`) --------------------------------
# SELF seconds per stage of the request path and of the write path: a
# stage's duration minus what its counted descendants covered, so the
# counters of one request sum to HTTP_REQUEST_S's move, and those of one
# direct `insert_rows` batch to WRITE_BATCH_S's.  A counter holds seconds ON
# THE THREAD that ran the stage: the flush pool's and the region workers'
# stages are roots on their own threads and overlap their callers in wall time.  One module-level Counter each (benchmark readers
# find counters by these names); STAGE_SELF_S below is the only registry,
# and a stage whose name is not in it is transparent.


def _stage_self_s(stage: str, what: str) -> Counter:
    return REGISTRY.counter(
        f"greptime_stage_self_seconds_{stage.replace('.', '_')}_total",
        f"Self seconds of the `{stage}` stage: {what}",
    )


STAGE_SELF_S_HTTP_REQUEST = _stage_self_s(
    "http.request", "body read, kernel-thread hand-off, whatever no child stage covers")
STAGE_SELF_S_HTTP_RENDER = _stage_self_s("http.render", "result to response bytes")
STAGE_SELF_S_HTTP_WRITE = _stage_self_s("http.write", "status line, headers, socket write")
STAGE_SELF_S_QUERY_PARSE = _stage_self_s("query.parse", "SQL / PromQL text to statements")
STAGE_SELF_S_QUERY_PLAN = _stage_self_s("query.plan", "statement to logical plan")
STAGE_SELF_S_QUERY_TPU = _stage_self_s(
    "query.tpu", "device-path host work outside the tile stages")
STAGE_SELF_S_QUERY_CPU = _stage_self_s("query.cpu", "CPU engine, fallback included")
STAGE_SELF_S_TILE_BUILD = _stage_self_s("tile.build", "super-tile resolution")
STAGE_SELF_S_TILE_COMPILE = _stage_self_s("tile.compile", "tile-program cache lookup")
STAGE_SELF_S_TILE_WINDOW = _stage_self_s(
    "tile.window", "window-tile probe: the in-window rows counted on the host")
STAGE_SELF_S_TILE_WINDOW_BUILD = _stage_self_s(
    "tile.window_build", "a window tile's gather of the in-window rows and its upload")
STAGE_SELF_S_TILE_DISPATCH = _stage_self_s("tile.dispatch", "compiled program invocation")
STAGE_SELF_S_TILE_MESH_STACK = _stage_self_s(
    "tile.mesh_stack", "a mesh dispatch's stacking of each device's local planes")
STAGE_SELF_S_TILE_READBACK = _stage_self_s(
    "tile.readback", "device->host fetch, waiting out the device included")
STAGE_SELF_S_TILE_DECODE = _stage_self_s("tile.decode", "fetched buffers to Arrow rows")
STAGE_SELF_S_TQL_PLAN = _stage_self_s(
    "tql.plan", "TQL tile path's host work around the dispatch: catalog, masks, grid, residency")
STAGE_SELF_S_TQL_ASSEMBLE = _stage_self_s(
    "tql.assemble", "fetched PromQL matrix to labelled series, then to the Arrow table")
HTTP_REQUEST_S = REGISTRY.counter(
    "greptime_http_request_seconds_total",
    "Inclusive seconds of every HTTP request (the `http.request` stage)",
)
STAGE_SELF_S_WRITE_BATCH = _stage_self_s(
    "write.batch", "conform, admission, write guard, waiting on region futures, flow mirror")
STAGE_SELF_S_WRITE_LOGICAL = _stage_self_s(
    "write.logical", "a logical table's batch onto the physical schema: remap, __tsid hashes, casts")
STAGE_SELF_S_WRITE_SPLIT = _stage_self_s("write.split", "partition-rule split of a batch")
STAGE_SELF_S_WRITE_WAL = _stage_self_s("write.wal", "WAL append: IPC encode, write, fsync")
STAGE_SELF_S_WRITE_MEMTABLE = _stage_self_s("write.memtable", "memtable apply")
STAGE_SELF_S_FLUSH_REGION = _stage_self_s(
    "flush.region", "freeze, waiting on the encode pool, manifest edit, WAL truncation")
STAGE_SELF_S_FLUSH_SORT = _stage_self_s(
    "flush.sort", "a frozen memtable's sort, dedup and time-window split")
STAGE_SELF_S_SST_ENCODE = _stage_self_s(
    "sst.encode", "one SST's statistics, dictionary encode, Parquet write and store")
STAGE_SELF_S_SST_INDEX = _stage_self_s(
    "sst.index", "one SST's bloom / inverted / term / vector builds and puffin write")
STAGE_SELF_S_COMPACT_REGION = _stage_self_s(
    "compact.region", "a compaction round that merges: memory gate, order checks, manifest commit")
STAGE_SELF_S_COMPACT_READ = _stage_self_s("compact.read", "SST decode of a merge's inputs")
STAGE_SELF_S_COMPACT_MERGE = _stage_self_s("compact.merge", "concat, sort and dedup of a merge")
WRITE_BATCH_S = REGISTRY.counter(
    "greptime_write_batch_seconds_total",
    "Inclusive seconds of every `insert_rows` batch (the `write.batch` stage)",
)
# how often `servers/http.py` renders a records answer by column, and how
# often a column's type sends it through the per-cell `_json_value` instead
HTTP_RENDER_COLUMNAR_CELLS = REGISTRY.counter(
    "greptime_http_render_columnar_cells_total",
    "Cells of /v1/sql and /v1/logs answers rendered to JSON a column at a time, by its Arrow type",
)
HTTP_RENDER_FALLBACK_CELLS = REGISTRY.counter(
    "greptime_http_render_fallback_cells_total",
    "Cells of /v1/sql and /v1/logs answers rendered one by one (a column type the kernels do not cover)",
)
STAGE_SELF_S: dict[str, Counter] = {
    "http.request": STAGE_SELF_S_HTTP_REQUEST,
    "http.render": STAGE_SELF_S_HTTP_RENDER,
    "http.write": STAGE_SELF_S_HTTP_WRITE,
    "query.parse": STAGE_SELF_S_QUERY_PARSE,
    "query.plan": STAGE_SELF_S_QUERY_PLAN,
    "query.tpu": STAGE_SELF_S_QUERY_TPU,
    "query.cpu": STAGE_SELF_S_QUERY_CPU,
    "query.cpu_fallback": STAGE_SELF_S_QUERY_CPU,
    "tile.build": STAGE_SELF_S_TILE_BUILD,
    "tile.compile": STAGE_SELF_S_TILE_COMPILE,
    "tile.window": STAGE_SELF_S_TILE_WINDOW,
    "tile.window_build": STAGE_SELF_S_TILE_WINDOW_BUILD,
    "tile.dispatch": STAGE_SELF_S_TILE_DISPATCH,
    "tile.mesh_stack": STAGE_SELF_S_TILE_MESH_STACK,
    "tile.fused_dispatch": STAGE_SELF_S_TILE_DISPATCH,
    "tile.readback": STAGE_SELF_S_TILE_READBACK,
    "tile.batch_readback": STAGE_SELF_S_TILE_READBACK,
    "tile.decode": STAGE_SELF_S_TILE_DECODE,
    "tql.plan": STAGE_SELF_S_TQL_PLAN,
    "tql.assemble": STAGE_SELF_S_TQL_ASSEMBLE,
    "write.batch": STAGE_SELF_S_WRITE_BATCH,
    "write.logical": STAGE_SELF_S_WRITE_LOGICAL,
    "write.split": STAGE_SELF_S_WRITE_SPLIT,
    "write.wal": STAGE_SELF_S_WRITE_WAL,
    "write.memtable": STAGE_SELF_S_WRITE_MEMTABLE,
    "flush.region": STAGE_SELF_S_FLUSH_REGION,
    "flush.sort": STAGE_SELF_S_FLUSH_SORT,
    "sst.encode": STAGE_SELF_S_SST_ENCODE,
    "sst.index": STAGE_SELF_S_SST_INDEX,
    "compact.region": STAGE_SELF_S_COMPACT_REGION,
    "compact.read": STAGE_SELF_S_COMPACT_READ,
    "compact.merge": STAGE_SELF_S_COMPACT_MERGE,
}
