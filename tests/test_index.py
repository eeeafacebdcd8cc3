"""SST secondary index tests: puffin container, bloom filter, inverted
index, and scan-time row-group pruning.

Models the reference's index test strategy (index/src/bloom_filter/,
index/src/inverted_index/ unit tests + mito2 sst index integration).
"""

import os
import tempfile

import numpy as np
import pyarrow as pa
import pytest

from greptimedb_tpu.datatypes.data_type import ConcreteDataType
from greptimedb_tpu.datatypes.schema import ColumnSchema, Schema, SemanticType
from greptimedb_tpu.storage import index as idx
from greptimedb_tpu.storage.puffin import PuffinReader, PuffinWriter
from greptimedb_tpu.storage.sst import INDEX_PRUNED_GROUPS, FileMeta, ScanPredicate, SstReader, SstWriter


def test_puffin_roundtrip(tmp_path):
    p = str(tmp_path / "x.puffin")
    w = PuffinWriter(p)
    w.add_blob("type-a", b"hello", {"column": "c1"})
    w.add_blob("type-b", b"world" * 100, {"column": "c2"})
    size = w.finish()
    assert size == os.path.getsize(p)
    r = PuffinReader(p)
    blobs = r.blobs()
    assert len(blobs) == 2
    assert r.read_blob(blobs[0]) == b"hello"
    assert r.read_blob(blobs[1]) == b"world" * 100
    assert r.find("type-b", column="c2") is not None
    assert r.find("type-b", column="c1") is None


def test_puffin_empty_writes_nothing(tmp_path):
    p = str(tmp_path / "none.puffin")
    assert PuffinWriter(p).finish() == 0
    assert not os.path.exists(p)


def test_bloom_filter_basics():
    bf = idx.BloomFilter.with_capacity(100)
    for i in range(100):
        bf.add(f"val{i}".encode())
    assert all(bf.contains(f"val{i}".encode()) for i in range(100))
    misses = sum(bf.contains(f"other{i}".encode()) for i in range(1000))
    assert misses < 50  # ~1% fpp target, generous bound
    rt = idx.BloomFilter.from_bytes(bf.to_bytes())
    assert rt.contains(b"val0") and rt.k == bf.k


def test_bloom_index_segments():
    col = pa.array([f"h{i // 10}" for i in range(100)])  # h0..h9, 10 rows each
    blob = idx.build_bloom_index(col, segment_rows=10)
    bm = idx.search_bloom_index(blob, "=", "h3")
    assert bm is not None and bm[3] and bm.sum() == 1
    bm = idx.search_bloom_index(blob, "in", ("h0", "h9"))
    assert bm[0] and bm[9] and bm.sum() == 2
    assert idx.search_bloom_index(blob, "<", "h5") is None  # can't prune ranges


def test_inverted_index_exact():
    col = pa.array(["a"] * 50 + ["b"] * 50 + [None] * 10)
    blob = idx.build_inverted_index(col, segment_rows=25)
    bm = idx.search_inverted_index(blob, "=", "a")
    assert list(bm) == [True, True, False, False, False]
    # NULL rows never match != (SQL three-valued logic), so the all-null
    # segment 4 is correctly prunable
    bm = idx.search_inverted_index(blob, "!=", "a")
    assert list(bm) == [False, False, True, True, False]
    bm = idx.search_inverted_index(blob, "in", ("a", "b"))
    assert list(bm) == [True, True, True, True, False]


def test_inverted_index_cardinality_cap():
    col = pa.array([f"u{i}" for i in range(100)])
    assert idx.build_inverted_index(col, segment_rows=10, max_terms=50) is None
    assert idx.build_inverted_index(col, segment_rows=10, max_terms=200) is not None


SCHEMA = Schema(
    columns=[
        ColumnSchema("ts", ConcreteDataType.TIMESTAMP_MILLISECOND, SemanticType.TIMESTAMP),
        ColumnSchema("host", ConcreteDataType.STRING, SemanticType.TAG),
        ColumnSchema("v", ConcreteDataType.FLOAT64, SemanticType.FIELD),
    ]
)


def _write_sst(tmp, n=4000, rg=500):
    w = SstWriter(str(tmp), SCHEMA, row_group_size=rg, index_segment_rows=250)
    # hosts are clustered: rows [i*500, (i+1)*500) all have host=f"h{i}"
    table = pa.table(
        {
            "ts": pa.array(np.arange(n, dtype=np.int64), pa.timestamp("ms")),
            "host": pa.array([f"h{i // 500}" for i in range(n)]),
            "v": pa.array(np.random.default_rng(0).uniform(size=n)),
        }
    )
    return w, w.write(table)


def test_sst_write_builds_sidecar(tmp_path):
    _, meta = _write_sst(tmp_path)
    assert meta.indexed_columns == ["host"]
    assert meta.index_file_size > 0
    assert os.path.exists(tmp_path / f"{meta.file_id}.puffin")


def test_sst_index_prunes_row_groups(tmp_path):
    _, meta = _write_sst(tmp_path)
    r = SstReader(str(tmp_path), SCHEMA)
    before = INDEX_PRUNED_GROUPS.get()
    t = r.read(meta, ScanPredicate(filters=[("host", "=", "h3")]))
    after = INDEX_PRUNED_GROUPS.get()
    assert t.num_rows == 500
    assert set(t["host"].to_pylist()) == {"h3"}
    assert after - before == 7  # 8 row groups, 7 skipped


def test_sst_index_absent_value_reads_nothing(tmp_path):
    _, meta = _write_sst(tmp_path)
    r = SstReader(str(tmp_path), SCHEMA)
    t = r.read(meta, ScanPredicate(filters=[("host", "=", "nope")]))
    assert t.num_rows == 0


def test_sst_index_disabled(tmp_path):
    w = SstWriter(str(tmp_path), SCHEMA, index_enable=False)
    table = pa.table(
        {
            "ts": pa.array(np.arange(10, dtype=np.int64), pa.timestamp("ms")),
            "host": pa.array(["a"] * 10),
            "v": pa.array(np.zeros(10)),
        }
    )
    meta = w.write(table)
    assert meta.indexed_columns == []
    assert not os.path.exists(tmp_path / f"{meta.file_id}.puffin")


def test_filemeta_index_fields_roundtrip():
    m = FileMeta("abc", (0, 10), 5, 100, indexed_columns=["host"], index_file_size=42)
    rt = FileMeta.from_dict(m.to_dict())
    assert rt.indexed_columns == ["host"] and rt.index_file_size == 42
    # old manifests without the fields still load
    legacy = FileMeta.from_dict(
        {"file_id": "x", "time_range": [0, 1], "num_rows": 1, "file_size": 10}
    )
    assert legacy.indexed_columns == []


def test_end_to_end_index_correctness(tmp_path):
    """Index pruning must never change results vs a full scan."""
    import tempfile

    from greptimedb_tpu.database import Database

    d = Database(data_home=str(tmp_path / "db"))
    d.sql("CREATE TABLE t (ts TIMESTAMP TIME INDEX, host STRING PRIMARY KEY, v DOUBLE)")
    rows = ",".join(f"({i}, 'h{i % 7}', {i}.0)" for i in range(2000))
    d.sql(f"INSERT INTO t VALUES {rows}")
    d.sql("ADMIN flush_table('t')") if hasattr(d, "_admin") else None
    [r] = d.sql("SELECT count(*) FROM t WHERE host = 'h3'")
    expect = sum(1 for i in range(2000) if i % 7 == 3)
    assert r.to_pylist()[0]["count(*)"] == expect
    d.close()


# ---- segmented term index (greptimedb_tpu/index/) ---------------------------

from greptimedb_tpu import index as term_index
from greptimedb_tpu.index.segmented import (
    INDEX_BYTES_READ,
    INDEX_DEGRADED,
    INDEX_SEGMENTS_READ,
    TERM_META_BLOB,
    TERM_SEGMENT_BLOB,
)
from greptimedb_tpu.utils import fault_injection as fi


def test_segmented_sidecar_layout(tmp_path):
    """Default writer emits fence-keyed segment blobs + one meta blob per
    tag column instead of the legacy whole-blob inverted payload."""
    _, meta = _write_sst(tmp_path)
    r = PuffinReader(str(tmp_path / f"{meta.file_id}.puffin"))
    types = [b.blob_type for b in r.blobs()]
    assert TERM_META_BLOB in types
    assert TERM_SEGMENT_BLOB in types
    assert idx.INVERTED_BLOB not in types  # replaced, not duplicated
    assert idx.BLOOM_BLOB in types  # blooms still ride along


def test_segmented_pruning_is_ranged(tmp_path):
    """A term lookup reads O(segment) bytes of a sidecar, not O(file)."""
    _, meta = _write_sst(tmp_path)
    r = SstReader(str(tmp_path), SCHEMA)
    b0 = INDEX_BYTES_READ.get()
    t = r.read(meta, ScanPredicate(filters=[("host", "=", "h3")]))
    assert t.num_rows == 500 and set(t["host"].to_pylist()) == {"h3"}
    bytes_read = INDEX_BYTES_READ.get() - b0
    assert 0 < bytes_read < meta.index_file_size  # strictly less than the blob


def test_legacy_format_still_readable(tmp_path):
    """index.segmented=false writes the old whole-blob formats, and the
    new TermIndexReader router serves them — old SSTs keep working."""
    w = SstWriter(
        str(tmp_path), SCHEMA, row_group_size=500, index_segment_rows=250,
        index_segmented=False,
    )
    n = 4000
    table = pa.table(
        {
            "ts": pa.array(np.arange(n, dtype=np.int64), pa.timestamp("ms")),
            "host": pa.array([f"h{i // 500}" for i in range(n)]),
            "v": pa.array(np.random.default_rng(0).uniform(size=n)),
        }
    )
    meta = w.write(table)
    pr = PuffinReader(str(tmp_path / f"{meta.file_id}.puffin"))
    types = [b.blob_type for b in pr.blobs()]
    assert idx.INVERTED_BLOB in types and TERM_META_BLOB not in types
    r = SstReader(str(tmp_path), SCHEMA)
    before = INDEX_PRUNED_GROUPS.get()
    t = r.read(meta, ScanPredicate(filters=[("host", "=", "h5")]))
    assert t.num_rows == 500 and set(t["host"].to_pylist()) == {"h5"}
    assert INDEX_PRUNED_GROUPS.get() - before == 7
    # legacy != pruning still answered (segmented declines it)
    t = r.read(meta, ScanPredicate(filters=[("host", "!=", "h5")]))
    assert t.num_rows == 3500


def test_segmented_matches_legacy_pruning(tmp_path):
    """Same data, both formats: identical surviving rows for =/in, and
    the segmented bitmap for '=' is exact (same segments as legacy)."""
    rng = np.random.default_rng(3)
    n = 3000
    hosts = [f"h{rng.integers(0, 40):02d}" for _ in range(n)]
    table = pa.table(
        {
            "ts": pa.array(np.arange(n, dtype=np.int64), pa.timestamp("ms")),
            "host": pa.array(hosts),
            "v": pa.array(rng.uniform(size=n)),
        }
    )
    outs = []
    for segmented in (True, False):
        sub = tmp_path / ("seg" if segmented else "legacy")
        w = SstWriter(
            str(sub), SCHEMA, row_group_size=300, index_segment_rows=100,
            index_segmented=segmented, index_segment_terms=8,
        )
        meta = w.write(table)
        r = SstReader(str(sub), SCHEMA)
        t = r.read(meta, ScanPredicate(filters=[("host", "in", ("h03", "h17"))]))
        outs.append(t.sort_by([("ts", "ascending")]))
    assert outs[0].equals(outs[1])


def test_segment_read_fault_degrades_to_full_scan(tmp_path):
    """An injected segment-read error must cost pruning, never rows."""
    _, meta = _write_sst(tmp_path)
    r = SstReader(str(tmp_path), SCHEMA)
    d0 = INDEX_DEGRADED.get()
    with fi.REGISTRY.armed("index.segment_read", fail_times=100, error=OSError):
        t = r.read(meta, ScanPredicate(filters=[("host", "=", "h3")]))
    # bloom may still prune (it parses whole-blob), but the RESULT is what
    # the contract is about: exactly the h3 rows survive the residual filter
    assert t.num_rows == 500 and set(t["host"].to_pylist()) == {"h3"}
    assert INDEX_DEGRADED.get() > d0


def test_index_build_fault_writes_unindexed_sst(tmp_path):
    """An injected build error yields an SST with no sidecar; the data
    write itself survives and scans stay correct."""
    w = SstWriter(str(tmp_path), SCHEMA, row_group_size=500)
    n = 1000
    table = pa.table(
        {
            "ts": pa.array(np.arange(n, dtype=np.int64), pa.timestamp("ms")),
            "host": pa.array([f"h{i // 500}" for i in range(n)]),
            "v": pa.array(np.zeros(n)),
        }
    )
    with fi.REGISTRY.armed("index.build", fail_times=1, error=RuntimeError):
        meta = w.write(table)
    assert meta is not None and meta.indexed_columns == []
    assert not os.path.exists(tmp_path / f"{meta.file_id}.puffin")
    r = SstReader(str(tmp_path), SCHEMA)
    t = r.read(meta, ScanPredicate(filters=[("host", "=", "h1")]))
    assert t.num_rows == 500


def test_segmented_null_terms_and_distinct_stats(tmp_path):
    w = SstWriter(str(tmp_path), SCHEMA, row_group_size=100, index_segment_rows=100)
    n = 600
    hosts = [None if i % 3 == 0 else f"h{i % 5}" for i in range(n)]
    table = pa.table(
        {
            "ts": pa.array(np.arange(n, dtype=np.int64), pa.timestamp("ms")),
            "host": pa.array(hosts, pa.string()),
            "v": pa.array(np.zeros(n)),
        }
    )
    meta = w.write(table)
    r = SstReader(str(tmp_path), SCHEMA)
    # NULL never satisfies '=', the residual filter guarantees it; the
    # index must not crash on the null term either way
    t = r.read(meta, ScanPredicate(filters=[("host", "=", "h1")]))
    assert all(v == "h1" for v in t["host"].to_pylist())
    # distinct stats: 4 non-null hosts (h0 never occurs on non-null rows:
    # i%3!=0 and i%5==0 -> h0 occurs at i=5,10,20,25...; so 5 values) + null
    stats = r.distinct_terms(meta, "host")
    uniq = len(set(hosts))  # includes None
    assert stats == uniq


@pytest.mark.slow
def test_million_term_index_bounded_lookup(tmp_path):
    """The log-scale acceptance: 10^6 unique terms, and a term lookup
    reads O(segments touched) bytes — thousands, against an index of tens
    of MB — with the result exact."""
    n = 1_000_000
    w = SstWriter(
        str(tmp_path), SCHEMA, row_group_size=1 << 16, index_segment_rows=1024,
    )
    terms = np.array([f"trace_{i:07d}" for i in range(n)])
    rng = np.random.default_rng(11)
    rng.shuffle(terms)
    table = pa.table(
        {
            "ts": pa.array(np.arange(n, dtype=np.int64), pa.timestamp("ms")),
            "host": pa.array(terms),
            "v": pa.array(np.zeros(n)),
        }
    )
    meta = w.write(table)
    assert meta.index_file_size > 5 << 20  # a real multi-MB index
    r = SstReader(str(tmp_path), SCHEMA)
    reader = r.term_index(meta)
    assert reader.distinct_terms("host") == n
    b0, s0 = INDEX_BYTES_READ.get(), INDEX_SEGMENTS_READ.get()
    t = r.read(meta, ScanPredicate(filters=[("host", "=", "trace_0123456")]))
    assert t.num_rows == 1
    segs_read = INDEX_SEGMENTS_READ.get() - s0
    bytes_read = INDEX_BYTES_READ.get() - b0
    assert segs_read <= 2  # fence search -> ONE term segment
    # bounded by O(segments touched): meta (fences) + one segment blob,
    # orders of magnitude below the whole sidecar
    assert bytes_read < meta.index_file_size / 50


def _lists(flat):
    """A `Postings` as (its term bytes, each term's segment id array)."""
    return flat.terms.to_pylist(), np.split(flat.segs, flat.starts[1:-1]) if len(flat) else []


def test_fence_keys_roundtrip_mid_multibyte_truncation(tmp_path):
    """A term truncated mid-multibyte-character at MAX_TERM_BYTES can
    become a segment fence; the latin-1 JSON round-trip must reproduce
    its exact bytes or lookups near it silently misroute."""
    from greptimedb_tpu.index import segmented as seg

    long_tail = "é" * 700  # 2 bytes each: 1400 bytes, truncated at 1024
    col = pa.array(
        [f"aa_{i:03d}" for i in range(40)]
        + ["zz_" + long_tail] * 5  # truncation cuts a 2-byte char in half
        + ["zz_zz"] * 5
    )
    flat = term_index.term_postings(col, 10)
    terms, postings, n_segs = *_lists(flat), flat.n_segs
    # the truncated term's bytes end mid-character
    trunc = [t for t in terms if t.startswith(b"zz_\xc3")][0]
    assert len(trunc) == seg.MAX_TERM_BYTES
    p = str(tmp_path / "f.puffin")
    w = PuffinWriter(p)
    term_index.write_term_index(
        w, "h", "inverted", flat, segment_rows=10, n_rows=len(col), seg_terms=8,
    )
    w.finish()
    import json as _json

    r = PuffinReader(p, ranged=True)
    meta_bm = [m for m in r.blobs() if m.blob_type == TERM_META_BLOB][0]
    meta = _json.loads(r.read_blob(meta_bm))
    s = term_index.SegmentedTermIndex(r, "k", "h", "inverted", meta)
    # every stored term must be findable, including ones at/after the
    # truncated fence
    for t, post in zip(terms, postings):
        bm = s.lookup(t)  # routes through the fence binary search
        expect = np.zeros(n_segs, bool)
        expect[post] = True
        assert (bm == expect).all(), t[:40]


# ---- the index builds against the loops they replaced ---------------------

import json as _json_ref
import struct as _struct_ref

import pyarrow.compute as _pc_ref


def _loop_bloom(column, segment_rows, fpp=idx.BLOOM_FPP):
    """`build_bloom_index` as a loop a segment and a value."""
    n = len(column)
    if pa.types.is_dictionary(column.type):
        column = _pc_ref.cast(column, column.type.value_type)
    segs = []
    for start in range(0, n, segment_rows):
        distinct = _pc_ref.unique(column.slice(start, segment_rows))
        bf = idx.BloomFilter.with_capacity(len(distinct), fpp)
        for v in distinct.to_pylist():
            bf.add(idx._encode_value(v))
        segs.append(bf.to_bytes())
    header = _json_ref.dumps(
        {"segment_rows": segment_rows, "n_rows": n, "seg_sizes": [len(s) for s in segs]}
    ).encode()
    return _struct_ref.pack("<I", len(header)) + header + b"".join(segs)


def _loop_postings(column, segment_rows):
    """`term_postings` as a dict of term bytes to segment sets."""
    from greptimedb_tpu.index.segmented import MAX_TERM_BYTES

    if pa.types.is_dictionary(column.type):
        column = _pc_ref.cast(column, column.type.value_type)
    by_key: dict = {}
    for i, v in enumerate(column.to_pylist()):
        by_key.setdefault(idx._encode_value(v)[:MAX_TERM_BYTES], set()).add(i // segment_rows)
    terms = sorted(by_key)
    return terms, [np.array(sorted(by_key[t]), np.int64) for t in terms]


def _loop_segment_blob(terms, postings):
    """`_encode_segment` as a loop a term and a posting."""
    def varint(out, v):
        while v >= 0x80:
            out.append((v & 0x7F) | 0x80)
            v >>= 7
        out.append(v)

    out = bytearray(_struct_ref.pack("<I", len(terms)))
    for t in terms:
        out += _struct_ref.pack("<H", len(t)) + t
    for p in postings:
        varint(out, len(p))
        prev = 0
        for v in p.tolist():
            varint(out, v - prev)
            prev = v
    return bytes(out)


class _Blobs:
    def __init__(self):
        self.blobs = []

    def add_blob(self, kind, data, props):
        self.blobs.append((kind, data, props))


def _tag_column(kind: str, rows_a_series: int, n: int, rng) -> pa.Array:
    """A sorted file's tag column: runs of `rows_a_series` rows a series."""
    series = -(-n // rows_a_series)
    ids = np.repeat(np.arange(series), rows_a_series)[:n]
    if kind == "hostname":
        values = np.array([f"host_{i}" for i in rng.permutation(series)])
        return pa.array(values[ids])
    if kind == "region":  # few values, a drawn one a series
        return pa.array(np.array([f"region-{i}" for i in range(9)])[rng.integers(0, 9, series)][ids])
    if kind == "nullable":
        values = [None if rng.random() < 0.2 else f"v{rng.integers(0, 50)}" for _ in range(series)]
        return pa.array([values[i] for i in ids], pa.string())
    if kind == "tsid":  # the metric engine's int64 series hash
        return pa.array(rng.integers(-(2 ** 63), 2 ** 63 - 1, series, dtype=np.int64)[ids])
    if kind == "int64-null":
        values = rng.integers(-5, 5, series)
        return pa.array([None if values[i] == 0 else int(values[i]) for i in ids], pa.int64())
    if kind == "dictionary":
        return pa.array(np.array(["x", "y", "z"])[rng.integers(0, 3, series)][ids]).dictionary_encode()
    raise ValueError(kind)


@pytest.mark.parametrize("rows_a_series", [360, 30, 10])
@pytest.mark.parametrize("kind", ["hostname", "region", "nullable", "tsid", "int64-null", "dictionary"])
def test_the_index_blobs_are_the_loops_byte_for_byte(kind, rows_a_series):
    """The bloom blob and every term-index blob made for all segments at
    once are the per-segment, per-value loops' bytes (old sidecars and new
    ones read alike)."""
    rng = np.random.default_rng(rows_a_series)
    n = 20_000
    col = _tag_column(kind, rows_a_series, n, rng)
    for segment_rows in (1024, 100):
        assert idx.build_bloom_index(col, segment_rows) == _loop_bloom(col, segment_rows)
        terms, postings = _loop_postings(col, segment_rows)
        flat = term_index.term_postings(idx.ColumnTerms(col), segment_rows)
        got_terms, got_postings = _lists(flat)
        assert got_terms == terms and flat.n_segs == -(-n // segment_rows)
        assert all((a == b).all() for a, b in zip(got_postings, postings))
        new, old = _Blobs(), _Blobs()
        term_index.write_term_index(
            new, "c", "inverted", flat, segment_rows=segment_rows, n_rows=n, seg_terms=64,
        )
        term_index.write_term_index(
            old, "c", "inverted", term_index.Postings.from_lists(terms, postings, flat.n_segs),
            segment_rows=segment_rows, n_rows=n, seg_terms=64,
        )
        assert new.blobs == old.blobs
        segments = [b for b in new.blobs if b[0] == TERM_SEGMENT_BLOB]
        assert [data for _k, data, _p in segments] == [
            _loop_segment_blob(terms[i:i + 64], postings[i:i + 64])
            for i in range(0, len(terms), 64)
        ]


def test_the_metric_engines_series_hash_gets_a_bloom_and_no_term_index(tmp_path):
    """`__tsid` is a term a series that no query filters on: the metric
    engine declares it without a term index, as upstream's data region
    ignores it; its bloom and the other tags' indexes stay, and a mito
    column of the same name keeps its term index."""
    from greptimedb_tpu.database import Database

    db = Database(data_home=str(tmp_path / "db"))
    db.sql("CREATE TABLE phy (ts TIMESTAMP TIME INDEX, val DOUBLE) "
           "WITH ('physical_metric_table' = '')")
    tsid = db.catalog.table("phy").schema.column("__tsid")
    assert not tsid.inverted_index
    assert not Schema.from_json(db.catalog.table("phy").schema.to_json()).column("__tsid").inverted_index
    db.close()
    plain = ColumnSchema("__tsid", ConcreteDataType.INT64, SemanticType.TAG)
    assert plain.inverted_index
    schema = Schema(columns=[
        ColumnSchema("ts", ConcreteDataType.TIMESTAMP_MILLISECOND, SemanticType.TIMESTAMP),
        ColumnSchema("__tsid", ConcreteDataType.INT64, SemanticType.TAG, inverted_index=False),
        ColumnSchema("host", ConcreteDataType.STRING, SemanticType.TAG),
        ColumnSchema("v", ConcreteDataType.FLOAT64, SemanticType.FIELD),
    ])
    n = 3000
    table = pa.table({
        "ts": pa.array(np.arange(n, dtype=np.int64), pa.timestamp("ms")),
        "__tsid": pa.array(np.repeat(np.arange(30, dtype=np.int64) * 7919, 100)),
        "host": pa.array([f"h{i // 100}" for i in range(n)]),
        "v": pa.array(np.zeros(n)),
    })
    w = SstWriter(str(tmp_path), schema, row_group_size=500, index_segment_rows=100)
    meta = w.write(table)
    assert meta.indexed_columns == ["__tsid", "host"]
    blobs = PuffinReader(str(tmp_path / f"{meta.file_id}.puffin")).blobs()
    kinds = {(b.blob_type, b.properties.get("column")) for b in blobs}
    assert (idx.BLOOM_BLOB, "__tsid") in kinds and (idx.BLOOM_BLOB, "host") in kinds
    assert (TERM_META_BLOB, "host") in kinds and (TERM_META_BLOB, "__tsid") not in kinds
    r = SstReader(str(tmp_path), schema)
    t = r.read(meta, ScanPredicate(filters=[("__tsid", "=", 7919 * 3)]))
    assert t.num_rows == 100 and set(t["host"].to_pylist()) == {"h3"}
    mito = Schema(columns=[plain if c.name == "__tsid" else c for c in schema.columns])
    meta = SstWriter(str(tmp_path), mito, row_group_size=500, index_segment_rows=100).write(table)
    blobs = PuffinReader(str(tmp_path / f"{meta.file_id}.puffin")).blobs()
    assert (TERM_META_BLOB, "__tsid") in {(b.blob_type, b.properties.get("column")) for b in blobs}
