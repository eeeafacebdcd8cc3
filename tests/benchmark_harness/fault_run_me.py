"""`fault_run_tql.py` for the metric-engine cell: drives `benchmark/run.py`'s
`main` with the timed path broken underneath, where `Database.sql` hands back
the answer of a `TQL EVAL` over one of the cell's logical tables.
`python fault_run_me.py <fault> <run.py's arguments>`; prints the result line.

  tql-value    one value of every such answer is scaled by 1 + 1e-5
  tql-point    the last point of every such answer is dropped
  tql-series   the first two series (or groups) of every such answer that has
               two change places
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import fault_run_tql  # noqa: E402  (puts the repo's root on the path)
import pyarrow as pa  # noqa: E402

from benchmark import run  # noqa: E402


def _swap_first_two_series(table: pa.Table) -> pa.Table:
    labels = [c for c in table.column_names if c not in ("ts", "value")]
    keys = list(zip(*[table[c].to_pylist() for c in labels]))
    starts = [i for i in range(len(keys)) if i == 0 or keys[i] != keys[i - 1]]
    if len(starts) < 2:
        return table
    a, b = starts[0], starts[1]
    c = starts[2] if len(starts) > 2 else len(keys)
    return table.take([*range(b, c), *range(a, b), *range(c, len(keys))])


FAULTS = {**fault_run_tql.FAULTS, "tql-series": _swap_first_two_series}


def plant(fault: str):
    if fault not in FAULTS:
        raise SystemExit(f"unknown fault {fault!r}")
    from greptimedb_tpu.database import Database

    real, alter = Database.sql, FAULTS[fault]

    def sql(self, text, *args, **kwargs):
        results = list(real(self, text, *args, **kwargs))
        if not text.startswith("TQL EVAL") or "nginx_" not in text:
            return results
        return [alter(r) if isinstance(r, pa.Table) else r for r in results]

    Database.sql = sql


if __name__ == "__main__":
    plant(sys.argv[1])
    sys.argv = ["run.py"] + sys.argv[2:]
    print(json.dumps(run.main(run.parse_args())), flush=True)
    os._exit(0)
