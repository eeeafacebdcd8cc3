"""`tsbs_nginx_metric_engine.py` over a span given in minutes: the same
labels, counters, restarts and layout (its `Dataset` makes them), for a
deployment whose span is no whole number of hours.

The configuration gives `minutes` where that generator reads `hours`, and
`assumed.minutes_before` where it reads `assumed.hours_before`; both are
handed on as exact fractions of an hour, so every tick count and timestamp
comes out a whole number as it does there.  `batch_scrapes` is how many
scrapes of every series one `insert_rows` batch carries: that generator's
1.5 M rows a batch are a single scrape at a million series.
"""

from __future__ import annotations

from fractions import Fraction

from benchmark.manifest import load_module

# a copy of its own (load_module executes the file anew): its batch size,
# the module's `CHUNK_ROWS`, is this configuration's, set as it is made
_HOURS = load_module("datasets", "tsbs_nginx_metric_engine")


class Dataset(_HOURS.Dataset):
    def __init__(self, cfg: dict, seed: int):
        assumed = dict(
            cfg["assumed"], hours_before=Fraction(cfg["assumed"]["minutes_before"], 60)
        )
        super().__init__(
            dict(cfg, hours=Fraction(cfg["minutes"], 60), assumed=assumed), seed
        )
        self.ticks, self.end = int(self.ticks), int(self.end)
        _HOURS.CHUNK_ROWS = cfg["batch_scrapes"] * self.hosts
