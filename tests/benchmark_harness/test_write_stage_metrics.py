"""The write path's per-layer metrics (PR 37): fifteen metric files and the
reader `counter_setup`, which reads a counter of the program over SET-UP (the
process's total less the window's delta).  `BENCHMARK.json` lists none of them
yet: a new `per_layer` entry goes at the END of the list, and
`test_ordinal_gid_dispatches.py` (PR 34) holds the list's last entry to its
own, which only a `benchmark` PR may edit.  So the files are here, tested,
with the entries that PR appends built from them below; a program without the
counters (the parent) reads nothing for each and raises nothing.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import manifest, program, span_gaps  # noqa: E402

CELLS = ["tsbs-heavy", "prom-rate-range", "tsbs-mesh4-heavy", "prom-metric-engine-range"]
ME = "prom-metric-engine-range"
# metric -> the stage whose self seconds over set-up it reads
STAGES = {
    "write_batch_s": "write.batch", "write_logical_s": "write.logical",
    "write_split_s": "write.split", "write_wal_s": "write.wal",
    "write_memtable_s": "write.memtable", "flush_region_s": "flush.region",
    "flush_sort_s": "flush.sort", "sst_encode_s": "sst.encode", "sst_index_s": "sst.index",
    "compact_region_s": "compact.region", "compact_read_s": "compact.read",
    "compact_merge_s": "compact.merge",
}
# metric -> (counter, unit, args beside the counter)
COUNTS = {
    "write_stall_s": ("WRITE_STALL_S", "s", {}),
    "wal_bytes_per_row": ("INGEST_WAL_BYTES", "bytes/row", {"rows_per": 1.0}),
    "compaction_rewritten_mb": ("COMPACTION_OUTPUT_BYTES", "MB", {"times": 1 / 2**20}),
}
MAY_READ_ZERO = {"compaction_rewritten_mb", "write_stall_s"}
ALL = sorted([*STAGES, *COUNTS])


def _spec(name: str) -> dict:
    return manifest.read_json(ROOT, "benchmark", "layer_metrics", name + ".json")


def _entry(name: str) -> dict:
    """The `per_layer` entry a `benchmark` PR appends for this file."""
    spec = _spec(name)
    entry = {key: spec[key] for key in ("name", "unit", "better", "source", "layer", "moves")}
    entry["workloads"] = [ME] if name == "write_logical_s" else list(CELLS)
    return entry


def test_there_are_fifteen_and_the_manifest_lists_none_yet():
    assert len(ALL) == 15
    listed = {p["name"] for p in manifest.manifest()["per_layer"]}
    assert not listed & set(ALL)
    assert manifest.manifest()["per_layer"][-1]["name"] == "ordinal_gid_dispatches_per_query"


@pytest.mark.parametrize("name", ALL)
def test_a_metric_file_names_its_counter_and_the_keys_the_manifest_wants(name):
    from greptimedb_tpu.utils import metrics

    spec = _spec(name)
    assert sorted(spec) == sorted(
        ["name", "layer", "unit", "better", "source", "moves", "reader", "args"]
    )
    assert spec["name"] == name and spec["reader"] == "counter_setup"
    assert (spec["layer"], spec["moves"], spec["better"]) == ("write path", "setup_s", "lower")
    if name in STAGES:
        counter = metrics.STAGE_SELF_S[STAGES[name]]
        module_name = "STAGE_SELF_S_" + STAGES[name].replace(".", "_").upper()
        assert getattr(metrics, module_name) is counter
        assert (spec["unit"], spec["source"]) == ("s", "program_span")
        assert spec["args"] == {"counter": module_name}
    else:
        module_name, unit, more = COUNTS[name]
        assert (spec["unit"], spec["source"]) == (unit, "program_counter")
        assert spec["args"] == {"counter": module_name, **more}
    assert type(getattr(metrics, module_name)).__name__ == "Counter"  # program.counters() finds it
    assert module_name in program.counters()
    # the entry's fields fit the manifest's rules: a name, a unit without a space
    entry = _entry(name)
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert len(name) <= 64 and " " not in entry["unit"] and len(entry["unit"]) <= 16


def test_the_entries_appended_put_each_metric_in_its_cells(monkeypatch):
    plain = manifest.manifest()
    appended = {**plain, "per_layer": plain["per_layer"] + [_entry(name) for name in ALL]}
    monkeypatch.setattr(manifest, "manifest", lambda: appended)
    for cell in CELLS:
        here = {p["name"] for p in manifest.Cell(cell).per_layer}
        assert here >= set(ALL) - {"write_logical_s"}, cell
        assert ("write_logical_s" in here) == (cell == ME)
        # the layer the accepted `ingest_krows_per_s` already names, and the metric it moves
        assert "setup_s" in {e["name"] for e in manifest.Cell(cell).end_to_end}
    assert _spec("ingest_krows_per_s")["layer"] == "write path"


def test_counter_setup_is_the_total_less_the_windows_delta(monkeypatch):
    cell = manifest.Cell("tsbs-heavy")
    monkeypatch.setattr(program, "counters", lambda: {
        "STAGE_SELF_S_WRITE_WAL": 12.5, "INGEST_WAL_BYTES": 3.0e9,
        "COMPACTION_OUTPUT_BYTES": 3.0 * 2**20,
    })
    run = {
        "requests": 520, "clock": {"rows": 1.5e7},
        "counters": {"STAGE_SELF_S_WRITE_WAL": 0.5, "INGEST_WAL_BYTES": 0.0},
    }
    assert cell.read_metric("layer_metrics", "write_wal_s", run) == 12.0
    assert cell.read_metric("layer_metrics", "wal_bytes_per_row", run) == pytest.approx(200.0)
    # a counter the window's dict lacks did not move in it
    assert cell.read_metric("layer_metrics", "compaction_rewritten_mb", run) == pytest.approx(3.0)
    assert cell.read_metric(
        "layer_metrics", "wal_bytes_per_row", {**run, "clock": {"rows": 0}}
    ) is None


@pytest.mark.parametrize("name", ALL)
def test_the_parents_program_leaves_the_metric_out(name, monkeypatch):
    """A program with none of the counters: nothing, no raise.  (The real
    parent has `INGEST_WAL_BYTES`, PR 15's, so its line carries
    `wal_bytes_per_row` and leaves the other fourteen out: my chip run, PR 37.)"""
    parents = {"TPU_DEVICE_DISPATCHES": 64.0, "HTTP_REQUEST_S": 9.0}
    monkeypatch.setattr(program, "counters", lambda: dict(parents))
    run = {"requests": 64, "counters": dict(parents), "clock": {"rows": 1.0e7}}
    assert manifest.Cell(ME).read_metric("layer_metrics", name, run) is None


def test_the_new_stage_names_are_stages_to_span_gaps():
    for stage in [*STAGES.values(), "flush.windows", "write.region"]:
        assert span_gaps.is_stage(stage), stage


def test_a_rehearsal_sized_load_reads_every_metric(tmp_path):
    """In this process, because the reader reads the process's counters: the
    metric-engine cell at 10 hosts x 1 h (25,200 rows in seven logical
    tables) through `program.open_database` + `program.load`."""
    cell = manifest.Cell(ME, {"hosts": 10, "hours": 1})
    ds = cell.dataset(2**31 + 37)
    db = program.open_database(str(tmp_path / "home"), cell.config["database"])
    try:
        # at this size one flush would hold everything and nothing would be
        # compacted: every write flushes, inline, as the chip-sized load's
        # write buffer does every few batches
        db.storage.flusher.stop()
        db.storage.flusher = None
        db.storage.buffer_mgr.region_limit = 0
        before = program.counters()
        clock = program.load(db, ds)
    finally:
        db.close()
    assert clock["rows"] == 7 * 10 * 360
    # what moved before the load stands in for the window's delta
    run = {"requests": 1, "counters": before, "clock": clock}
    read = {name: cell.read_metric("layer_metrics", name, run) for name in ALL}
    for name, value in read.items():
        assert value is not None and value >= 0.0, name
        assert value > 0.0 or name in MAY_READ_ZERO, name
    assert read["write_stall_s"] == 0.0
    assert read["compaction_rewritten_mb"] > 0.0  # seven overlapping files: a round merges
    # thread-seconds of the caller, which ran these here (the files' encodes
    # it shares with the compaction thread a flush wakes): they fit in the
    # clocks the harness took around the calls
    on_caller = sum(read[n] for n in (
        "write_batch_s", "write_logical_s", "write_split_s", "write_wal_s", "write_memtable_s",
        "flush_region_s", "flush_sort_s",
    ))
    assert on_caller <= clock["insert_s"] + clock["flush_s"]
    assert 50 < read["wal_bytes_per_row"] < 1000
