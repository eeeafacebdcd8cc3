"""`fault_run.py` for the PromQL cell: drives `benchmark/run.py`'s `main`
with the timed path broken underneath, where `Database.sql` hands back the
answer of a `TQL EVAL` over the cell's metric table.
`python fault_run_tql.py <fault> <run.py's arguments>`; prints the result line.

  tql-value   one value of every such answer is scaled by 1 + 1e-5
  tql-point   the last point of every such answer is dropped
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import fault_run  # noqa: E402  (puts the repo's root on the path)
import pyarrow as pa  # noqa: E402

from benchmark import run  # noqa: E402

FAULTS = {"tql-value": fault_run.FAULTS["sql-value"], "tql-point": fault_run.FAULTS["sql-row"]}


def plant(fault: str):
    if fault not in FAULTS:
        raise SystemExit(f"unknown fault {fault!r}")
    from greptimedb_tpu.database import Database

    real, alter = Database.sql, FAULTS[fault]

    def sql(self, text, *args, **kwargs):
        results = list(real(self, text, *args, **kwargs))
        if not text.startswith("TQL EVAL") or "nginx_requests" not in text:
            return results
        return [alter(r) if isinstance(r, pa.Table) else r for r in results]

    Database.sql = sql


if __name__ == "__main__":
    plant(sys.argv[1])
    sys.argv = ["run.py"] + sys.argv[2:]
    print(json.dumps(run.main(run.parse_args())), flush=True)
    os._exit(0)
