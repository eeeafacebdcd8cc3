"""Drives `benchmark/run.py`'s `main` with the timed path broken underneath:
an answer is altered where the program produces it, below the HTTP socket.
`python fault_run.py <fault> <run.py's arguments>`; prints the result line.

  sql-value   one float of every SELECT answer over the cell's table is
              scaled by 1 + 1e-5
  sql-row     the last row of every such answer is dropped
  sql-order   the first two rows of every such answer change places
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pyarrow as pa  # noqa: E402

from benchmark import run  # noqa: E402


def _scale_one_float(table):
    for i, field in enumerate(table.schema):
        if pa.types.is_float64(field.type) and table.num_rows:
            values = table.column(i).to_pylist()
            values[0] = values[0] * (1.0 + 1e-5)
            return table.set_column(i, field, pa.array(values, pa.float64()))
    return table


def _drop_last_row(table):
    return table.slice(0, max(table.num_rows - 1, 0))


def _swap_first_rows(table):
    if table.num_rows < 2:
        return table
    return table.take([1, 0, *range(2, table.num_rows)])


FAULTS = {"sql-value": _scale_one_float, "sql-row": _drop_last_row, "sql-order": _swap_first_rows}


def plant(fault: str):
    if fault not in FAULTS:
        raise SystemExit(f"unknown fault {fault!r}")
    from greptimedb_tpu.database import Database

    real, alter = Database.sql, FAULTS[fault]

    def sql(self, text, *args, **kwargs):
        results = list(real(self, text, *args, **kwargs))
        if "usage_user" not in text:
            return results
        return [alter(r) if isinstance(r, pa.Table) else r for r in results]

    Database.sql = sql


if __name__ == "__main__":
    plant(sys.argv[1])
    sys.argv = ["run.py"] + sys.argv[2:]
    print(json.dumps(run.main(run.parse_args())), flush=True)
    os._exit(0)
