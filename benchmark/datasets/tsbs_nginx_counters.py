"""TSBS `devops`, measurement `nginx`, field `requests`, in the Prometheus
layout: one metric table, one series per host, one sample per host every
`scrape_s`, made from the seed.

`requests` is a monotonic counter: TSBS draws it from its monotonic random
walk (the state grows by |normal(5, 1)| a scrape and is written as an
integer; remembered from TSBS's `nginx.go`, so under `assumed`).  Each
counter starts the data at the level the hours the configuration cut away
would have given it, and `restart_share` of the series restart once, at a
scrape drawn from the seed: the sample of that scrape is 0 and the counter
counts on from there.

Ground truth kept for the folds: `requests` as a [ticks, hosts] float64
array of the samples as stored, `restart_tick` per host (-1 = never).
"""

from __future__ import annotations

import numpy as np


class Dataset:
    def __init__(self, cfg: dict, seed: int):
        self.cfg, self.seed = cfg, seed
        self.hosts, self.scrape_s = cfg["hosts"], cfg["scrape_s"]
        self.t0 = cfg["start_ms"]
        self.ticks = cfg["hours"] * 3600 // self.scrape_s
        self.end = self.t0 + cfg["hours"] * 3600_000
        self.table = cfg["table"]
        self.tables = [self.table]
        self.host_names = np.array([f"host_{i}" for i in range(self.hosts)])
        # the engine emits series in dictionary-code order: tag values
        # sorted as strings ("host_10" < "host_2")
        self.host_order = np.argsort(self.host_names, kind="stable")

        assumed = cfg["assumed"]
        mean, sd = assumed["increment_normal"]
        rng = np.random.default_rng([seed, 2])
        steps = np.abs(rng.normal(mean, sd, (self.ticks, self.hosts)))
        # the level the hours before the data would have left: their sum is
        # normal by the central limit, drawn whole
        before = assumed["hours_before"] * 3600 // self.scrape_s
        steps[0] += before * mean + np.sqrt(before) * sd * rng.normal(size=self.hosts)
        state = np.cumsum(steps, axis=0)
        restarts = rng.random(self.hosts) < assumed["restart_share"]
        self.restart_tick = np.where(
            restarts, rng.integers(1, self.ticks, self.hosts), -1
        )
        for h in np.nonzero(restarts)[0]:
            r = self.restart_tick[h]
            state[r:, h] -= state[r, h]
        self.requests = np.floor(state)

    @property
    def rows(self) -> int:
        return self.ticks * self.hosts

    def tick_ts(self) -> np.ndarray:
        return self.t0 + np.arange(self.ticks, dtype=np.int64) * (self.scrape_s * 1000)

    def create_statements(self) -> list:
        append = "true" if self.cfg["append_mode"] else "false"
        return [
            f"CREATE TABLE {self.table} (hostname STRING, "
            "greptime_timestamp TIMESTAMP(3) TIME INDEX, greptime_value DOUBLE, "
            f"PRIMARY KEY ({', '.join(self.cfg['series_key'])})) "
            f"WITH (append_mode = '{append}')"
        ]

    def batches(self):
        """One (table, pyarrow table) in scrape order: every host's sample
        of a scrape, then the next scrape's."""
        import pyarrow as pa

        codes = np.tile(np.arange(self.hosts, dtype=np.int32), self.ticks)
        yield self.table, pa.table({
            "hostname": pa.DictionaryArray.from_arrays(
                pa.array(codes), pa.array(list(self.host_names))
            ),
            "greptime_timestamp": pa.array(
                np.repeat(self.tick_ts(), self.hosts), pa.timestamp("ms")
            ),
            "greptime_value": pa.array(self.requests.reshape(-1), pa.float64()),
        })
