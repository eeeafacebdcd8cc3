"""Drives `benchmark/run.py`'s `main` with the cell's mesh path switched off
underneath: the database opens with `mesh_dispatch` among its disabled
passes, so every grouped request is answered by one chip, rightly, and moves
`TILE_MESH_INELIGIBLE`.  `python fault_run_mesh.py <run.py's arguments>`;
prints the result line.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import program, run  # noqa: E402


def plant():
    real = program.open_database

    def open_database(home, settings):
        return real(home, {**settings, "query.disabled_passes": ["mesh_dispatch"]})

    program.open_database = open_database


if __name__ == "__main__":
    plant()
    sys.argv = ["run.py"] + sys.argv[1:]
    print(json.dumps(run.main(run.parse_args())), flush=True)
    os._exit(0)
