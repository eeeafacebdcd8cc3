"""TSBS `cpu-only`: `hosts` hosts, one row per host every `scrape_s`, the
ten tags and ten DOUBLE fields TSBS publishes, made from the seed.

Ground truth kept for the folds: `usage_user` as a [ticks, hosts] array and
each host's tag values.  Everything else is drawn, ingested and dropped.
"""

from __future__ import annotations

import numpy as np

CHUNK_ROWS = 2_000_000


def _tag_domains(cfg: dict) -> dict:
    """The value lists of the nine tags beside `hostname` (TSBS devops
    generator; cardinalities are the configuration's `assumed` group)."""
    card = cfg["assumed"]["tag_cardinality"]
    regions = [f"region-{i}" for i in range(card["region"])]
    per_region = card["datacenter"] // card["region"]
    return {
        "region": regions,
        "datacenter": [f"{r}{chr(97 + k)}" for r in regions for k in range(per_region)],
        "rack": [str(i) for i in range(card["rack"])],
        "os": ["Ubuntu15.10", "Ubuntu16.04LTS", "Ubuntu16.10"][: card["os"]],
        "arch": ["x64", "x86"][: card["arch"]],
        "team": ["CHI", "LON", "NYC", "SF"][: card["team"]],
        "service": [str(i) for i in range(card["service"])],
        "service_version": [str(i) for i in range(card["service_version"])],
        "service_environment": ["production", "staging", "test"][
            : card["service_environment"]
        ],
    }


class Dataset:
    def __init__(self, cfg: dict, seed: int):
        self.cfg, self.seed = cfg, seed
        self.hosts, self.scrape_s = cfg["hosts"], cfg["scrape_s"]
        self.t0 = cfg["start_ms"]
        self.ticks = cfg["hours"] * 3600 // self.scrape_s
        self.end = self.t0 + cfg["hours"] * 3600_000
        self.table, self.fields = cfg["table"], cfg["fields"]
        self.tables = [self.table]
        self.host_names = np.array([f"host_{i}" for i in range(self.hosts)])
        # the engine emits groups in dictionary-code order: tag values
        # sorted as strings ("host_10" < "host_2")
        self.host_order = np.argsort(self.host_names, kind="stable")
        self.usage_user = np.empty((self.ticks, self.hosts), np.float64)
        rng = np.random.default_rng([seed, 1])
        self.domains = _tag_domains(cfg)
        per_region = len(self.domains["datacenter"]) // len(self.domains["region"])
        self.tag_codes = {
            tag: rng.integers(0, len(values), self.hosts).astype(np.int32)
            for tag, values in self.domains.items()
        }
        # a host's datacenter lies in its region
        self.tag_codes["datacenter"] = (
            self.tag_codes["region"] * per_region
            + self.tag_codes["datacenter"] % per_region
        ).astype(np.int32)

    @property
    def rows(self) -> int:
        return self.ticks * self.hosts

    def tick_ts(self) -> np.ndarray:
        return self.t0 + np.arange(self.ticks, dtype=np.int64) * (self.scrape_s * 1000)

    def create_statements(self) -> list:
        tags = self.cfg["tags"]
        cols = [f"{t} STRING" for t in tags] + ["ts TIMESTAMP(3) TIME INDEX"]
        cols += [f"{f} DOUBLE" for f in self.fields]
        regions = self.cfg["regions"]
        partition = (
            f" PARTITION BY HASH (hostname) PARTITIONS {regions}" if regions > 1 else ""
        )
        append = "true" if self.cfg["append_mode"] else "false"
        return [
            f"CREATE TABLE {self.table} ({', '.join(cols)}, "
            f"PRIMARY KEY ({', '.join(self.cfg['primary_key'])})){partition} "
            f"WITH (append_mode = '{append}')"
        ]

    def batches(self):
        """Yields (table, pyarrow table) per chunk of ticks and fills
        `usage_user`: per chunk, one `uniform(0, 100)` draw per field in
        the configuration's order."""
        import pyarrow as pa

        rng = np.random.default_rng([self.seed, 0])
        tag_values = {"hostname": self.host_names, **self.domains}
        tag_codes = {"hostname": np.arange(self.hosts, dtype=np.int32), **self.tag_codes}
        chunk_ticks = max(1, CHUNK_ROWS // self.hosts)
        for start in range(0, self.ticks, chunk_ticks):
            ticks = min(chunk_ticks, self.ticks - start)
            n = ticks * self.hosts
            ts = np.repeat(self.tick_ts()[start:start + ticks], self.hosts)
            columns = {
                tag: pa.DictionaryArray.from_arrays(
                    pa.array(np.tile(tag_codes[tag], ticks)),
                    pa.array(list(tag_values[tag])),
                )
                for tag in self.cfg["tags"]
            }
            columns["ts"] = pa.array(ts, pa.timestamp("ms"))
            for field in self.fields:
                vals = rng.uniform(0.0, 100.0, n)
                if field == "usage_user":
                    self.usage_user[start:start + ticks] = vals.reshape(ticks, self.hosts)
                columns[field] = pa.array(vals, pa.float64())
            yield self.table, pa.table(columns)
