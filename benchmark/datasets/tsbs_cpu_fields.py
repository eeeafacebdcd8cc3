"""TSBS `cpu-only` as `tsbs_cpu.py` makes it, with every field kept as
ground truth: its `Dataset` draws, tags and batches everything; this one
reads each batch's ten field columns back out of the Arrow tables it
yields, into one [ticks, hosts] float64 array a field (`values[field]`),
so that a shape can fold any of them.  1.5 GB of host memory at 4000
hosts and 13 h.
"""

from __future__ import annotations

import numpy as np

from benchmark.manifest import load_module

_CPU = load_module("datasets", "tsbs_cpu")


class Dataset(_CPU.Dataset):
    def __init__(self, cfg: dict, seed: int):
        super().__init__(cfg, seed)
        self.values = {
            field: np.empty((self.ticks, self.hosts), np.float64) for field in self.fields
        }

    def batches(self):
        """`tsbs_cpu.Dataset.batches`, unchanged, filling `values` from
        each table as it passes: a table's rows are its ticks, each one
        row a host in host order."""
        tick = 0
        for table, batch in super().batches():
            ticks = batch.num_rows // self.hosts
            for field in self.fields:
                self.values[field][tick:tick + ticks] = (
                    batch.column(field).to_numpy().reshape(ticks, self.hosts)
                )
            tick += ticks
            yield table, batch
