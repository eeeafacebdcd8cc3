"""Segmented term index: fence-keyed term segments with ranged reads.

Layout (per indexed column, inside the SST's puffin sidecar):

    greptime-term-index-meta-v1   {column, kind}
        JSON: segment geometry + the sparse FENCE-KEY array (first term
        of every segment) + per-segment term counts.  Small: one fence
        per `seg_terms` terms (10^6 terms @ 512/segment = ~2000 fences).

    greptime-term-seg-v1          {column, kind, seg}  x n_segments
        Binary: the segment's sorted term dictionary (len-prefixed
        bytes) followed by one delta-encoded varint posting list per
        term.  Postings are ROW-SEGMENT ids (the same `segment_rows`
        granularity the bloom/legacy indexes prune at), so a decoded
        posting list expands to the row-segment candidacy bitmap the
        scan-time applier already consumes.

A term lookup binary-searches the fence keys (in memory after one small
meta read), issues ONE ranged puffin read for the single term segment
that can contain the term, and decodes O(seg_terms) entries — O(log
terms) time and O(segment) memory regardless of index size.  Decoded
segments live in a process-wide LRU so repeated lookups (dashboards
re-filtering the same tag) skip the read entirely.

Terms are stored as their canonical `storage.index._encode_value` bytes
(NULL sorts first via its \\x00 sentinel), so build-time and search-time
normalization agree with the legacy formats byte-for-byte.
"""

from __future__ import annotations

import bisect
import json
import struct
import threading
import time
from collections import OrderedDict

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from ..utils import metrics
from ..utils.fault_injection import fire as _fault_fire

TERM_META_BLOB = "greptime-term-index-meta-v1"
TERM_SEGMENT_BLOB = "greptime-term-seg-v1"

# terms longer than this are truncated at build AND lookup: collisions
# only widen the candidate bitmap (the residual filter stays exact)
MAX_TERM_BYTES = 1024

INDEX_LOOKUP_MS = metrics.REGISTRY.histogram(
    "greptime_index_lookup_ms",
    "Milliseconds per term-index lookup (fence search + segment read + decode)",
    buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 500.0),
)
INDEX_SEGMENTS_READ = metrics.REGISTRY.counter(
    "greptime_index_segments_read_total",
    "Term-index segment blobs fetched from storage (LRU misses)",
)
INDEX_BYTES_READ = metrics.REGISTRY.counter(
    "greptime_index_bytes_read_total",
    "Bytes fetched from term-index sidecars via ranged reads",
)
INDEX_SEGMENT_CACHE_HITS = metrics.REGISTRY.counter(
    "greptime_index_segment_cache_hits_total",
    "Term-index segment lookups served from the decoded-segment LRU",
)
INDEX_DEGRADED = metrics.REGISTRY.counter(
    "greptime_index_degraded_total",
    "Index lookups that degraded to a full scan after a read error",
)


# ---- varint codec -----------------------------------------------------------


def _read_varint(buf: bytes, pos: int) -> tuple[int, int]:
    v = 0
    shift = 0
    while True:
        b = buf[pos]
        pos += 1
        v |= (b & 0x7F) << shift
        if not b & 0x80:
            return v, pos
        shift += 7


# ---- build ------------------------------------------------------------------


class Postings:
    """A column's term index before it is cut into blobs: `terms`, the
    distinct term bytes in ascending order (an arrow binary array), and
    the row-segment ids of term i, `segs[starts[i]:starts[i + 1]]`,
    ascending."""

    def __init__(self, terms: pa.Array, starts: np.ndarray, segs: np.ndarray, n_segs: int):
        self.terms, self.starts, self.segs, self.n_segs = terms, starts, segs, n_segs

    def __len__(self) -> int:
        return len(self.terms)

    @classmethod
    def from_lists(cls, terms: list[bytes], postings: list[np.ndarray], n_segs: int):
        counts = np.array([len(p) for p in postings], np.int64)
        return cls(
            pa.array(terms, pa.binary()),
            np.concatenate([[0], np.cumsum(counts)]).astype(np.int64),
            np.concatenate(postings).astype(np.int64) if postings else np.zeros(0, np.int64),
            n_segs,
        )


def term_postings(column, segment_rows: int) -> Postings:
    """Tag column (or its `storage.index.ColumnTerms`) -> its `Postings`,
    with NO cardinality cap — segmenting is what makes high cardinality
    affordable.  A term is `_encode_value(v)[:MAX_TERM_BYTES]` (NULL rows:
    `_encode_value(None)`); values whose terms coincide share one posting
    list, the union of theirs."""
    from ..storage.index import ColumnTerms, _encode_value

    col = column if isinstance(column, ColumnTerms) else ColumnTerms(column)
    n_segs = -(-col.n // segment_rows)
    seg, code = col.segment_pairs(segment_rows)
    keys = col.keys()
    merge = not (
        pa.types.is_string(col.values.type) or pa.types.is_large_string(col.values.type)
        or pa.types.is_integer(col.values.type)
    )
    if len(keys) and pc.max(pc.binary_length(keys)).as_py() > MAX_TERM_BYTES:
        keys, merge = pc.binary_slice(keys, 0, MAX_TERM_BYTES), True
    keys = pa.concat_arrays([keys.cast(pa.binary()), pa.array([_encode_value(None)], pa.binary())])
    merge = merge or (col.has_null and pc.any(pc.equal(keys[:-1], keys[-1])).as_py())
    if merge:
        # one id per distinct term bytes
        enc = pc.dictionary_encode(keys)
        distinct, key_of_code = enc.dictionary, np.asarray(enc.indices, np.int64)
    else:
        distinct, key_of_code = keys, np.arange(len(keys), dtype=np.int64)
    order = np.asarray(pc.sort_indices(distinct), np.int64)
    rank = np.empty(len(order), np.int64)
    rank[order] = np.arange(len(order), dtype=np.int64)
    # (term rank, segment) pairs ascending; coinciding terms leave doubles
    pair = np.sort(rank[key_of_code[code]] * max(n_segs, 1) + seg)
    if merge and len(pair):
        pair = pair[np.append(True, pair[1:] != pair[:-1])]
    ranks, segs = pair // max(n_segs, 1), pair % max(n_segs, 1)
    first = np.flatnonzero(np.append(True, ranks[1:] != ranks[:-1])) if len(ranks) else ranks
    terms = distinct.take(pa.array(order[ranks[first]]))
    return Postings(terms, np.append(first, len(pair)).astype(np.int64), segs, n_segs)


def build_token_postings(column: pa.Array, segment_rows: int) -> Postings:
    """Tokenized text column -> its token `Postings` (fulltext kind)."""
    from ..storage.index import tokenize

    n = len(column)
    if isinstance(column, pa.ChunkedArray):
        column = column.combine_chunks()
    if pa.types.is_dictionary(column.type):
        column = pc.cast(column, column.type.value_type)
    n_segs = (n + segment_rows - 1) // segment_rows
    vocab: dict[str, set] = {}
    for i, v in enumerate(column.to_pylist()):
        if v is None:
            continue
        seg = i // segment_rows
        for t in tokenize(str(v)):
            vocab.setdefault(t, set()).add(seg)
    tok_by_bytes: dict[bytes, set] = {}
    for t, segs in vocab.items():
        tok_by_bytes.setdefault(t.encode()[:MAX_TERM_BYTES], set()).update(segs)
    terms = sorted(tok_by_bytes)
    postings = [np.array(sorted(tok_by_bytes[t]), dtype=np.int64) for t in terms]
    return Postings.from_lists(terms, postings, n_segs)


def _decode_segment(buf: bytes) -> dict[bytes, np.ndarray]:
    (n_terms,) = struct.unpack_from("<I", buf, 0)
    pos = 4
    terms: list[bytes] = []
    for _ in range(n_terms):
        (ln,) = struct.unpack_from("<H", buf, pos)
        pos += 2
        terms.append(buf[pos : pos + ln])
        pos += ln
    out: dict[bytes, np.ndarray] = {}
    for t in terms:
        cnt, pos = _read_varint(buf, pos)
        vals = np.empty(cnt, dtype=np.int64)
        prev = 0
        for i in range(cnt):
            d, pos = _read_varint(buf, pos)
            prev += d
            vals[i] = prev
        out[t] = vals
    return out


def _varints(values: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Every value as an LEB128 varint (seven bits a byte, low first, the
    high bit set on all but the last), end to end: (the bytes, the offset
    at which each value's bytes start)."""
    v = values.astype(np.uint64)
    width = np.ones(len(v), np.int64)
    for shift in range(7, 64, 7):
        width += v >= np.uint64(1 << shift)
    at = np.cumsum(width) - width
    which = np.repeat(np.arange(len(v)), width)
    j = (np.arange(int(width.sum())) - at[which]).astype(np.uint64)
    out = ((v[which] >> (np.uint64(7) * j)) & np.uint64(0x7F)).astype(np.uint8)
    out[j < (width[which] - 1).astype(np.uint64)] |= 0x80
    return out, at


def _segment_streams(postings: Postings) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """The two halves of a segment blob for every term, end to end, for
    the blobs to cut from: (the `<H` length + bytes of each term, each
    term's offset in them, the varint posting lists — a term's count, then
    its segment ids as deltas from 0 — and each term's offset in them);
    either offset array has one entry past the last term."""
    from ..storage.index import binary_parts

    data, offs = binary_parts(postings.terms)
    lens = np.diff(offs)
    n = len(lens)
    at = offs[:-1] + 2 * np.arange(n)
    dict_stream = np.empty(len(data) + 2 * n, np.uint8)
    dict_stream[at] = lens & 0xFF
    dict_stream[at + 1] = lens >> 8
    owner = np.repeat(np.arange(n), lens)
    dict_stream[np.arange(len(data)) + 2 * (owner + 1)] = np.frombuffer(data, np.uint8)
    # a term's list: its count, then its segment ids as deltas from 0
    counts = np.diff(postings.starts)
    deltas = np.diff(postings.segs, prepend=0)
    deltas[postings.starts[:-1]] = postings.segs[postings.starts[:-1]]
    head = postings.starts[:-1] + np.arange(n)
    values = np.empty(n + len(deltas), np.int64)
    is_head = np.zeros(len(values), bool)
    is_head[head] = True
    values[is_head], values[~is_head] = counts, deltas
    var_stream, var_at = _varints(values)
    return (
        dict_stream, np.append(at, len(dict_stream)),
        var_stream, np.append(var_at[head], len(var_stream)),
    )


def write_term_index(
    writer,
    column: str,
    kind: str,
    flat: Postings,
    *,
    segment_rows: int,
    n_rows: int,
    seg_terms: int = 512,
) -> int:
    """Emit the meta blob + one segment blob per `seg_terms` terms of
    `flat` into `writer` (a PuffinWriter).  A segment blob is its term
    count (`<I`), its terms' halves of `_segment_streams` and then their
    posting lists, cut out of streams made for all terms at once
    (`_decode_segment` reads it).  Returns the number of segment blobs."""
    n_terms = len(flat)
    fences: list[str] = []
    seg_lens: list[int] = []
    n_written = 0
    if n_terms:
        dict_stream, dict_at, var_stream, var_at = _segment_streams(flat)
        firsts = flat.terms.take(pa.array(np.arange(0, n_terms, seg_terms))).to_pylist()
    for start in range(0, n_terms, seg_terms):
        stop = min(start + seg_terms, n_terms)
        # latin-1 maps bytes 1:1 into JSON-safe codepoints, so the fence
        # round-trips EXACTLY even when MAX_TERM_BYTES truncation cut a
        # multibyte character in half — a utf-8 'replace' decode would
        # mangle such a fence and misroute every lookup near it (wrongly
        # pruning row groups that hold the term)
        fences.append(firsts[start // seg_terms].decode("latin-1"))
        seg_lens.append(stop - start)
        writer.add_blob(
            TERM_SEGMENT_BLOB,
            struct.pack("<I", stop - start)
            + dict_stream[dict_at[start]:dict_at[stop]].tobytes()
            + var_stream[var_at[start]:var_at[stop]].tobytes(),
            {"column": column, "kind": kind, "seg": n_written},
        )
        n_written += 1
    meta = {
        "version": 1,
        "kind": kind,
        "segment_rows": segment_rows,
        "n_rows": n_rows,
        "n_segs": flat.n_segs,
        "n_terms": n_terms,
        "seg_terms": seg_terms,
        "fences": fences,
        "seg_lens": seg_lens,
    }
    writer.add_blob(
        TERM_META_BLOB, json.dumps(meta).encode(), {"column": column, "kind": kind}
    )
    return n_written


# ---- decoded-segment LRU ----------------------------------------------------


class SegmentCache:
    """Process-wide LRU of DECODED term segments, keyed by
    (sidecar identity, column, kind, segment id).  Entry-bounded: each
    entry is O(seg_terms) small objects, so a few hundred entries is a
    few MB — the working set of a dashboard's hot tags."""

    def __init__(self, capacity: int = 1024):
        self.capacity = capacity
        self._data: OrderedDict[tuple, dict] = OrderedDict()
        self._lock = threading.Lock()

    def get(self, key: tuple):
        with self._lock:
            v = self._data.get(key)
            if v is not None:
                self._data.move_to_end(key)
            return v

    def put(self, key: tuple, value: dict):
        with self._lock:
            self._data[key] = value
            self._data.move_to_end(key)
            while len(self._data) > self.capacity:
                self._data.popitem(last=False)

    def clear(self):
        with self._lock:
            self._data.clear()


SEGMENT_CACHE = SegmentCache()


# ---- read -------------------------------------------------------------------


class SegmentedTermIndex:
    """One column's segmented term index, bound to a ranged PuffinReader.

    Holds the parsed meta (fence keys pre-encoded for bisect) and fetches
    term segments on demand through the shared LRU; every storage touch
    is a ranged read metered by greptime_index_{segments,bytes}_read."""

    def __init__(self, puffin, cache_key: str, column: str, kind: str, meta: dict):
        self._puffin = puffin
        self._cache_key = cache_key
        self.column = column
        self.kind = kind
        self.segment_rows = meta["segment_rows"]
        self.n_segs = meta["n_segs"]
        self.n_terms = meta["n_terms"]
        self._fences = [f.encode("latin-1") for f in meta["fences"]]
        self._seg_blobs: dict[int, object] | None = None  # seg id -> BlobMeta

    def _segment_blob(self, seg: int):
        if self._seg_blobs is None:
            self._seg_blobs = {
                m.properties.get("seg"): m
                for m in self._puffin.blobs()
                if m.blob_type == TERM_SEGMENT_BLOB
                and m.properties.get("column") == self.column
                and m.properties.get("kind") == self.kind
            }
        return self._seg_blobs.get(seg)

    def _segment(self, seg: int) -> dict[bytes, np.ndarray]:
        key = (self._cache_key, self.column, self.kind, seg)
        cached = SEGMENT_CACHE.get(key)
        if cached is not None:
            INDEX_SEGMENT_CACHE_HITS.inc()
            return cached
        _fault_fire("index.segment_read", column=self.column, seg=seg)
        bm = self._segment_blob(seg)
        if bm is None:
            raise FileNotFoundError(
                f"term segment {seg} of {self.column} missing from {self._puffin.key}"
            )
        before = self._puffin.bytes_read
        blob = self._puffin.read_blob(bm)
        INDEX_SEGMENTS_READ.inc()
        INDEX_BYTES_READ.inc(max(self._puffin.bytes_read - before, len(blob)))
        decoded = _decode_segment(blob)
        SEGMENT_CACHE.put(key, decoded)
        return decoded

    def lookup(self, term_bytes: bytes) -> np.ndarray:
        """Row-segment candidacy bitmap for ONE term.  Exact: an absent
        term returns all-False (the index is complete over the file)."""
        t0 = time.perf_counter()
        try:
            out = np.zeros(self.n_segs, dtype=bool)
            term_bytes = term_bytes[:MAX_TERM_BYTES]
            i = bisect.bisect_right(self._fences, term_bytes) - 1
            if i < 0:
                return out
            segs = self._segment(i).get(term_bytes)
            if segs is not None:
                out[segs] = True
            return out
        finally:
            INDEX_LOOKUP_MS.observe((time.perf_counter() - t0) * 1000.0)

    # -- predicate answering (mirrors the legacy classes' search API) --------

    def search(self, op: str, value) -> np.ndarray | None:
        if self.kind == "fulltext":
            return self._search_fulltext(op, value)
        return self._search_inverted(op, value)

    def _search_inverted(self, op: str, value) -> np.ndarray | None:
        from ..storage.index import _encode_value

        if op == "=":
            return self.lookup(_encode_value(value))
        if op == "in":
            out = np.zeros(self.n_segs, dtype=bool)
            for v in value:
                out |= self.lookup(_encode_value(v))
            return out
        # "!=" would have to union every OTHER term's postings — an
        # O(index) read that defeats the segmented contract; decline to
        # prune (the residual filter stays exact)
        return None

    def _search_fulltext(self, op: str, value) -> np.ndarray | None:
        from ..storage.index import parse_match_query, tokenize

        if op == "match_term":
            toks = tokenize(str(value))
            if not toks:
                return None
            out = np.ones(self.n_segs, dtype=bool)
            for t in toks:
                out &= self.lookup(t.encode())
            return out
        if op != "match":
            return None
        out = np.zeros(self.n_segs, dtype=bool)
        for terms, _phrases, _negs in parse_match_query(str(value)):
            # terms AND; phrases need substring scans over the whole
            # vocabulary (the legacy reader's _substr_token_segs), which a
            # ranged-read index cannot answer in O(segment) — skip the
            # phrase constraint (conservative: keeps more segments);
            # negations cannot prune either
            cand = np.ones(self.n_segs, dtype=bool)
            for t in terms:
                cand &= self.lookup(t.encode())
            out |= cand
        return out
