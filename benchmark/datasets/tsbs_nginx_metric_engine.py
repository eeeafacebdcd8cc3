"""TSBS `devops`, measurement `nginx`, all seven fields, in the Prometheus
layout as upstream's remote write stores it: one physical metric table, a
logical table a field (`nginx_<field>`), one series per host and field
carrying nginx's twelve labels, one sample per series every `scrape_s`,
made from the seed.

`accepts`, `handled`, `requests` are monotonic counters as in
`tsbs_nginx_counters.py`: the state grows by |normal(5, 1)| a scrape and is
stored as its whole part, starting at the level the hours the configuration
cut away would have left; `restart_share` of the HOSTS restart once, at a
scrape drawn from the seed, and all three counters of that host read 0 at
that scrape and count on.  `active`, `reading`, `waiting`, `writing` are
gauges: a random walk of normal(0, 1) a scrape clamped to [0, 100] (TSBS's
clamped random walk, as remembered from `nginx.go`, so under `assumed`).
The nine host tags are drawn as `tsbs_cpu.py` draws them (the same stream:
a seed gives a host the same tags in both); `port` and `server` per host.

Ground truth kept for the folds: `samples[field]` as a [ticks, hosts]
float64 array of the samples as stored, and `label_values[label]` per host.
"""

from __future__ import annotations

import numpy as np

from benchmark.manifest import load_module

# Rows a batch: an hour of one metric at 4000 hosts.  Smaller batches were
# slower on the chip's host, not faster (PR 35, call 3: at 120,000 and
# 480,000 rows a batch insert 77-82 s against 39 s and compaction 70-101 s
# against 40 s for the same 10.08 M rows).
CHUNK_ROWS = 1_500_000
COUNTERS = ("accepts", "handled", "requests")


class Dataset:
    def __init__(self, cfg: dict, seed: int):
        self.cfg, self.seed = cfg, seed
        self.hosts, self.scrape_s = cfg["hosts"], cfg["scrape_s"]
        self.t0 = cfg["start_ms"]
        self.ticks = cfg["hours"] * 3600 // self.scrape_s
        self.end = self.t0 + cfg["hours"] * 3600_000
        self.physical = cfg["physical_table"]
        self.metrics = list(cfg["metrics"])
        self.labels = sorted(cfg["labels"])
        self.tables = [self.table_of(m) for m in self.metrics]
        assumed = cfg["assumed"]

        # labels: hostname, the nine host tags, port and server
        self.host_names = np.array([f"host_{i}" for i in range(self.hosts)])
        rng = np.random.default_rng([seed, 1])
        # the value lists of the nine host tags, as `tsbs_cpu.py` makes them
        # from the same `assumed.tag_cardinality`
        domains = load_module("datasets", "tsbs_cpu")._tag_domains(cfg)
        per_region = len(domains["datacenter"]) // len(domains["region"])
        codes = {
            tag: rng.integers(0, len(values), self.hosts).astype(np.int32)
            for tag, values in domains.items()
        }
        # a host's datacenter lies in its region
        codes["datacenter"] = (
            codes["region"] * per_region + codes["datacenter"] % per_region
        ).astype(np.int32)
        self.label_values = {"hostname": self.host_names}
        for tag, values in domains.items():
            self.label_values[tag] = np.array(values)[codes[tag]]
        rng = np.random.default_rng([seed, 3])
        lo, hi = assumed["port_range"]
        self.label_values["port"] = rng.integers(lo, hi + 1, self.hosts).astype(str)
        self.label_values["server"] = np.array([
            f"nginx_{i}" for i in rng.integers(0, assumed["server_ids"], self.hosts)
        ])

        # samples
        mean, sd = assumed["increment_normal"]
        before = assumed["hours_before"] * 3600 // self.scrape_s
        rng = np.random.default_rng([seed, 2])
        restarts = rng.random(self.hosts) < assumed["restart_share"]
        self.restart_tick = np.where(
            restarts, rng.integers(1, self.ticks, self.hosts), -1
        )
        self.samples = {}
        for metric in self.metrics:
            if metric in COUNTERS:
                steps = np.abs(rng.normal(mean, sd, (self.ticks, self.hosts)))
                # the level the hours before the data would have left: their
                # sum is normal by the central limit, drawn whole
                steps[0] += before * mean + np.sqrt(before) * sd * rng.normal(size=self.hosts)
                state = np.cumsum(steps, axis=0)
                for h in np.nonzero(restarts)[0]:
                    r = self.restart_tick[h]
                    state[r:, h] -= state[r, h]
                self.samples[metric] = np.floor(state)
            else:
                walk = np.empty((self.ticks, self.hosts))
                level = rng.uniform(0.0, 100.0, self.hosts)
                moves = rng.normal(0.0, 1.0, (self.ticks, self.hosts))
                for t in range(self.ticks):
                    level = np.clip(level + moves[t], 0.0, 100.0)
                    walk[t] = level
                self.samples[metric] = walk

    @property
    def rows(self) -> int:
        return self.ticks * self.hosts * len(self.metrics)

    def table_of(self, metric: str) -> str:
        return f"{self.cfg['table_prefix']}{metric}"

    def tick_ts(self) -> np.ndarray:
        return self.t0 + np.arange(self.ticks, dtype=np.int64) * (self.scrape_s * 1000)

    def label_order(self, hosts: np.ndarray) -> np.ndarray:
        """`hosts` in the order of their label values, compared column by
        column in ascending label-name order (Prometheus' order for series
        that carry the same label names)."""
        keys = [self.label_values[label][hosts] for label in reversed(self.labels)]
        return hosts[np.lexsort(keys)]

    def create_statements(self) -> list:
        """Upstream's DDL: the physical table, then a logical table a metric
        on it."""
        columns = ", ".join(f"{label} STRING" for label in self.labels)
        return [
            f"CREATE TABLE {self.physical} (greptime_timestamp TIMESTAMP(3) TIME INDEX, "
            "greptime_value DOUBLE) WITH ('physical_metric_table' = '')"
        ] + [
            f"CREATE TABLE {self.table_of(metric)} (greptime_timestamp TIMESTAMP(3) "
            f"TIME INDEX, greptime_value DOUBLE, {columns}, "
            f"PRIMARY KEY ({', '.join(self.labels)})) "
            f"ENGINE = metric WITH ('on_physical_table' = '{self.physical}')"
            for metric in self.metrics
        ]

    def batches(self):
        """(table, pyarrow table) per chunk of scrapes and metric, in scrape
        order inside: every host's sample of a scrape, then the next
        scrape's; the labels dictionary-encoded, as a remote-write decoder
        interns them."""
        import pyarrow as pa

        chunk_ticks = max(1, CHUNK_ROWS // self.hosts)
        tick_ts = self.tick_ts()
        dictionaries = {}
        for label in self.labels:
            values, codes = np.unique(self.label_values[label], return_inverse=True)
            dictionaries[label] = (pa.array(list(values)), codes.astype(np.int32))
        for start in range(0, self.ticks, chunk_ticks):
            ticks = min(chunk_ticks, self.ticks - start)
            labels = {
                label: pa.DictionaryArray.from_arrays(pa.array(np.tile(codes, ticks)), values)
                for label, (values, codes) in dictionaries.items()
            }
            ts = pa.array(np.repeat(tick_ts[start:start + ticks], self.hosts), pa.timestamp("ms"))
            for metric in self.metrics:
                yield self.table_of(metric), pa.table({
                    **labels,
                    "greptime_timestamp": ts,
                    "greptime_value": pa.array(
                        self.samples[metric][start:start + ticks].reshape(-1), pa.float64()
                    ),
                })
