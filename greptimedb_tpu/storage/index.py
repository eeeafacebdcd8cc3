"""Per-SST secondary indexes: bloom-filter skip index + inverted index.

Role-equivalent of the reference's `index` crate and
`mito2/src/sst/index/` (reference index/src/bloom_filter/,
index/src/inverted_index/, mito2/src/sst/index/indexer/): indexes are
built while an SST is written, stored in a Puffin sidecar, and consulted
at scan time to prune row groups / row segments before any Parquet decode.

Both indexes work at *segment* granularity (`segment_rows` rows per
segment, reference bloom_filter creator's `rows_per_segment`): an equality
or IN predicate on an indexed column yields a bitmap of candidate
segments; segments map to Parquet row groups for pruning, and the residual
filter still runs afterwards so index false positives are harmless.

TPU note: pruning happens host-side before tiles are staged to HBM — the
fewer segments survive, the fewer tiles the device sees; this is the
reference's "indexes shrink the scan" design carried over.
"""

from __future__ import annotations

import hashlib
import json
import struct
import zlib

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

BLOOM_BLOB = "greptime-bloom-filter-v1"
INVERTED_BLOB = "greptime-inverted-index-v1"
FULLTEXT_BLOB = "greptime-fulltext-index-v1"
VECTOR_BLOB = "greptime-vector-index-v1"
DEFAULT_SEGMENT_ROWS = 1024
BLOOM_FPP = 0.01


def _hash2(value: bytes) -> tuple[int, int]:
    d = hashlib.blake2b(value, digest_size=16).digest()
    h1, h2 = struct.unpack("<QQ", d)
    # h2 must be odd: nbits is often a power of two, and an even stride makes
    # the double-hash probe sequence cycle over a handful of positions,
    # destroying the false-positive guarantee.
    return h1, h2 | 1


class BloomFilter:
    """Split-bloom with double hashing (k probes from two 64-bit hashes)."""

    def __init__(self, nbits: int, k: int, bits: np.ndarray | None = None):
        self.nbits = nbits
        self.k = k
        self.bits = bits if bits is not None else np.zeros((nbits + 7) // 8, dtype=np.uint8)

    @classmethod
    def with_capacity(cls, n_items: int, fpp: float = BLOOM_FPP) -> "BloomFilter":
        n_items = max(n_items, 1)
        nbits = max(int(-n_items * np.log(fpp) / (np.log(2) ** 2)), 256)
        k = max(int(round(nbits / n_items * np.log(2))), 1)
        return cls(nbits, min(k, 16))

    def _positions(self, value: bytes) -> np.ndarray:
        h1, h2 = _hash2(value)
        i = np.arange(self.k, dtype=np.uint64)
        return ((h1 + i * h2) % np.uint64(self.nbits)).astype(np.int64)

    def add(self, value: bytes):
        p = self._positions(value)
        np.bitwise_or.at(self.bits, p >> 3, (1 << (p & 7)).astype(np.uint8))

    def contains(self, value: bytes) -> bool:
        p = self._positions(value)
        return bool(np.all(self.bits[p >> 3] & (1 << (p & 7)).astype(np.uint8)))

    def to_bytes(self) -> bytes:
        return struct.pack("<II", self.nbits, self.k) + self.bits.tobytes()

    @classmethod
    def from_bytes(cls, b: bytes) -> "BloomFilter":
        nbits, k = struct.unpack("<II", b[:8])
        return cls(nbits, k, np.frombuffer(b[8:], dtype=np.uint8).copy())


def _term_key(v) -> str | None:
    """Canonical string for a term so the SAME normalization applies at
    build and at search (a float literal 3.0 must find integer key 3)."""
    if v is None:
        return None
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, float) and v.is_integer():
        v = int(v)
    if isinstance(v, bytes):
        return v.decode("utf-8", "replace")
    return str(v)


def _encode_value(v) -> bytes:
    key = _term_key(v)
    if key is None:
        return b"\x00<null>"
    return key.encode()


# ---- build ------------------------------------------------------------------


def binary_parts(arr: pa.Array) -> tuple[bytes, np.ndarray]:
    """(data, offsets) of a binary / string array: value i is
    data[offsets[i]:offsets[i + 1]]."""
    wide = pa.types.is_large_binary(arr.type) or pa.types.is_large_string(arr.type)
    offsets = np.frombuffer(arr.buffers()[1], np.int64 if wide else np.int32)
    offsets = offsets[arr.offset:arr.offset + len(arr) + 1].astype(np.int64)
    data = arr.buffers()[2]
    return (data.to_pybytes() if data is not None else b""), offsets


class ColumnTerms:
    """A tag column's distinct values as the indexes key them, made once
    for its bloom and term builds: `codes`, each row's index into
    `values` (a NULL row: `len(values)`, the null slot), and on demand the
    values' `_encode_value` bytes, their hashes, and the distinct codes of
    each segment.  Distinct is arrow's hash equality, the one `pc.unique`
    judges a segment by."""

    def __init__(self, column):
        if isinstance(column, pa.ChunkedArray):
            column = column.combine_chunks()
        self.n = len(column)
        if pa.types.is_dictionary(column.type):
            # unify the dictionary's own duplicates; its unused entries hold
            # no row and so reach no segment
            unified = pc.dictionary_encode(column.dictionary)
            self.values = unified.dictionary
            null = len(self.values)
            remap = np.asarray(pc.fill_null(unified.indices, null), np.int64)
            remap = np.append(remap, null)
            at = np.asarray(pc.fill_null(column.indices, len(remap) - 1), np.int64)
            self.codes = remap[at]
        else:
            enc = pc.dictionary_encode(column)
            self.values = enc.dictionary
            self.codes = np.asarray(
                pc.fill_null(pc.cast(enc.indices, pa.int64()), len(self.values)), np.int64
            )
        self.has_null = bool(self.n) and bool((self.codes == len(self.values)).any())
        self._keys = self._hashes = None
        self._pairs: dict[int, tuple] = {}

    def keys(self) -> pa.Array:
        """`_encode_value(v)` of each distinct value, in `values` order."""
        if self._keys is None:
            t = self.values.type
            if pa.types.is_string(t) or pa.types.is_large_string(t):
                # str(v).encode(): the utf-8 arrow already holds
                self._keys = self.values.cast(
                    pa.large_binary() if pa.types.is_large_string(t) else pa.binary()
                )
            elif pa.types.is_integer(t):
                # str(int): arrow's decimal text is Python's, digit for digit
                self._keys = pc.cast(self.values, pa.string()).cast(pa.binary())
            else:
                self._keys = pa.array(
                    [_encode_value(v) for v in self.values.to_pylist()], pa.binary()
                )
        return self._keys

    def hashes(self) -> np.ndarray:
        """[len(values) + 1, 2] uint64: `_hash2` of each key, the null
        slot's (`_encode_value(None)`) last; the second word made odd."""
        if self._hashes is None:
            data, offs = binary_parts(self.keys())
            data, blake = memoryview(data), hashlib.blake2b
            digests = b"".join([
                blake(data[a:b], digest_size=16).digest()
                for a, b in zip(offs[:-1].tolist(), offs[1:].tolist())
            ] + [blake(_encode_value(None), digest_size=16).digest()])
            h = np.frombuffer(digests, "<u8").reshape(-1, 2).astype(np.uint64)
            h[:, 1] |= np.uint64(1)
            self._hashes = h
        return self._hashes

    def segment_pairs(self, segment_rows: int) -> tuple[np.ndarray, np.ndarray]:
        """(segment, code) of every distinct code of every segment of
        `segment_rows` rows, ascending by segment, then code."""
        got = self._pairs.get(segment_rows)
        if got is not None:
            return got
        n, n_codes = self.n, len(self.values) + 1
        n_segs = -(-n // segment_rows)
        # the first row of each run of one code inside a segment (a sorted
        # file's tag is runs, so these are few), as segment * n_codes + code
        head = np.zeros(n, bool)
        head[::segment_rows] = True
        head[1:] |= self.codes[1:] != self.codes[:-1]
        at = np.flatnonzero(head)
        key = (at // segment_rows) * n_codes + self.codes[at]
        if n_segs * n_codes <= 4 * len(key) + 4096:
            # few values: a mark a (segment, code)
            mark = np.zeros(n_segs * n_codes, bool)
            mark[key] = True
            key = np.flatnonzero(mark)
        else:
            key = np.unique(key)
        got = (key // n_codes, key % n_codes)
        self._pairs[segment_rows] = got
        return got


def build_bloom_index(
    column, segment_rows: int = DEFAULT_SEGMENT_ROWS, fpp: float = BLOOM_FPP
) -> bytes:
    """One bloom filter per segment; blob = header json + concatenated filters
    (reference index/src/bloom_filter/creator.rs).  Each segment's filter
    is `BloomFilter.with_capacity(its distinct values)` with every distinct
    value `add`ed, made for all segments at once: the sizes by the same
    float formulas, the probe positions by the same uint64 arithmetic, so
    the blob is the filter-by-filter one byte for byte.  `column` may be a
    `ColumnTerms` already made for it."""
    terms = column if isinstance(column, ColumnTerms) else ColumnTerms(column)
    n = terms.n
    seg, code = terms.segment_pairs(segment_rows)
    n_segs = -(-n // segment_rows)
    items = np.bincount(seg, minlength=n_segs).astype(np.int64)
    # BloomFilter.with_capacity, segment by segment
    nbits = np.maximum(
        (-items * np.log(fpp) / (np.log(2) ** 2)).astype(np.int64), 256
    )
    k = np.minimum(
        np.maximum(np.rint(nbits / items * np.log(2)).astype(np.int64), 1), 16
    )
    size = 8 + (nbits + 7) // 8
    start = np.cumsum(size) - size
    body = np.zeros(int(size.sum()) * 8, bool)
    if len(seg):
        # BloomFilter.add of each (segment, value): bit (h1 + i h2) mod nbits
        # of the segment's bits, for i < k; probe i for the pairs whose k
        # passes it
        h = terms.hashes()[code]
        h1, h2 = h[:, 0], h[:, 1]
        kk, nb, base = k[seg], nbits[seg].astype(np.uint64), (start[seg] + 8) * 8
        for i in range(int(k.max())):
            live = kk > i
            if not live.all():
                h1, h2, kk, nb, base = h1[live], h2[live], kk[live], nb[live], base[live]
            body[base + ((h1 + np.uint64(i) * h2) % nb).astype(np.int64)] = True
    out = np.packbits(body, bitorder="little")
    # each filter's (nbits, k) header, as BloomFilter.to_bytes packs it
    head = np.stack([nbits, k], axis=1).astype("<u4").view(np.uint8)
    out[start[:, None] + np.arange(8)] = head.reshape(-1, 8)
    header = json.dumps(
        {"segment_rows": segment_rows, "n_rows": n, "seg_sizes": size.tolist()}
    ).encode()
    return struct.pack("<I", len(header)) + header + out.tobytes()


def build_inverted_index(
    column: pa.Array, segment_rows: int = DEFAULT_SEGMENT_ROWS, max_terms: int = 4096
) -> bytes | None:
    """term -> packed segment bitmap (reference index/src/inverted_index/
    format: FST + per-value bitmaps; here a sorted term table + bitmaps).

    Returns None when the column is too high-cardinality to index usefully.
    """
    n = len(column)
    if isinstance(column, pa.ChunkedArray):
        column = column.combine_chunks()
    if pa.types.is_dictionary(column.type):
        column = pc.cast(column, column.type.value_type)
    n_segs = (n + segment_rows - 1) // segment_rows
    d = pc.dictionary_encode(column)
    terms = d.dictionary.to_pylist()
    if len(terms) > max_terms:
        return None
    codes = np.asarray(pc.fill_null(pc.cast(d.indices, pa.int64()), len(terms)), dtype=np.int64)
    seg_ids = np.arange(n) // segment_rows
    # bitmap[term, seg]
    bm = np.zeros((len(terms) + 1, n_segs), dtype=bool)
    bm[codes, seg_ids] = True
    packed = np.packbits(bm, axis=1)
    payload = zlib.compress(packed.tobytes(), 3)
    header = json.dumps(
        {
            "segment_rows": segment_rows,
            "n_rows": n,
            "n_segs": n_segs,
            "terms": [_term_key(t) for t in terms],
            "row_bytes": packed.shape[1],
        }
    ).encode()
    return struct.pack("<I", len(header)) + header + payload


# ---- fulltext ---------------------------------------------------------------

import re as _re

_TOKEN_RE = _re.compile(r"[a-z0-9_]+")


def tokenize(text: str) -> list[str]:
    """Lowercase word tokenizer (the reference's default English analyzer
    shape: tantivy SimpleTokenizer + lowercase, index/src/fulltext_index/)."""
    return _TOKEN_RE.findall(text.lower())


def build_fulltext_index(
    column: pa.Array,
    segment_rows: int = DEFAULT_SEGMENT_ROWS,
    max_tokens: int = 1 << 16,
) -> bytes | None:
    """token -> segment bitmap over a tokenized text column (reference
    mito2/src/sst/index/fulltext_index/ creator; segment-granular like the
    bloom/inverted indexes so pruning plugs into the same applier).  None
    when the vocabulary exceeds `max_tokens` (index would not pay off)."""
    n = len(column)
    if isinstance(column, pa.ChunkedArray):
        column = column.combine_chunks()
    if pa.types.is_dictionary(column.type):
        column = pc.cast(column, column.type.value_type)
    n_segs = (n + segment_rows - 1) // segment_rows
    vocab: dict[str, int] = {}
    rows_tok: list[set] = []
    for i, v in enumerate(column.to_pylist()):
        if v is None:
            continue
        seg = i // segment_rows
        while len(rows_tok) <= seg:
            rows_tok.append(set())
        for t in tokenize(str(v)):
            code = vocab.setdefault(t, len(vocab))
            rows_tok[seg].add(code)
        if len(vocab) > max_tokens:
            return None
    while len(rows_tok) < n_segs:
        rows_tok.append(set())
    bm = np.zeros((len(vocab), n_segs), dtype=bool)
    for seg, codes in enumerate(rows_tok):
        for c in codes:
            bm[c, seg] = True
    packed = np.packbits(bm, axis=1) if len(vocab) else np.zeros((0, 1), np.uint8)
    payload = zlib.compress(packed.tobytes(), 3)
    header = json.dumps(
        {
            "segment_rows": segment_rows,
            "n_rows": n,
            "n_segs": n_segs,
            "tokens": sorted(vocab, key=vocab.get),
            "row_bytes": int(packed.shape[1]) if len(vocab) else 1,
        }
    ).encode()
    return struct.pack("<I", len(header)) + header + payload


def parse_match_query(query: str) -> list[tuple[list[str], list[str], list[str]]]:
    """`matches()` query -> disjuncts of (AND terms, AND phrases, NOT terms).

    Grammar subset of the reference's matches() language: whitespace terms
    are ANDed, `OR` splits alternatives, `"quoted phrases"` must appear
    verbatim (case-insensitive), `-term` negates."""
    disjuncts: list[tuple[list[str], list[str], list[str]]] = []
    for part in _re.split(r"\s+OR\s+", query.strip()):
        terms: list[str] = []
        phrases: list[str] = []
        negs: list[str] = []
        for m in _re.finditer(r'"([^"]*)"|(\S+)', part):
            if m.group(1) is not None:
                phrases.append(m.group(1))
            else:
                tok = m.group(2)
                if tok.startswith("-") and len(tok) > 1:
                    negs.extend(tokenize(tok[1:]))
                else:
                    terms.extend(tokenize(tok))
        disjuncts.append((terms, phrases, negs))
    return disjuncts


class FulltextIndex:
    """Parsed token -> segment-bitmap table."""

    def __init__(self, blob: bytes):
        header, payload = _split_blob(blob)
        self.segment_rows = header["segment_rows"]
        self.tokens: list[str] = header["tokens"]
        self.n_segs = header["n_segs"]
        if self.tokens:
            packed = np.frombuffer(zlib.decompress(payload), dtype=np.uint8).reshape(
                -1, header["row_bytes"]
            )
            self.bm = np.unpackbits(packed, axis=1)[:, : self.n_segs].astype(bool)
        else:
            self.bm = np.zeros((0, self.n_segs), dtype=bool)
        self._tok_idx = {t: i for i, t in enumerate(self.tokens)}

    def _token_segs(self, token: str) -> np.ndarray:
        i = self._tok_idx.get(token.lower())
        if i is None:
            return np.zeros(self.n_segs, dtype=bool)
        return self.bm[i]

    def _substr_token_segs(self, token: str) -> np.ndarray:
        """Segments whose vocabulary contains `token` as a SUBSTRING of any
        stored token.  Phrase row-matching is substring-based
        (matches_mask uses pc.match_substring), so phrase pruning must be
        substring-conservative: '\"err\"' must keep segments holding
        'error'.  A phrase token is pure word chars, so it can only occur
        inside a single text token — the OR over containing vocab tokens
        is exact segment candidacy."""
        t = token.lower()
        out = np.zeros(self.n_segs, dtype=bool)
        for v, i in self._tok_idx.items():
            if t in v:
                out |= self.bm[i]
        return out

    def search(self, op: str, value) -> np.ndarray | None:
        """Conservative segment candidacy for match predicates: a segment
        survives when it MIGHT match (phrases fall back to their tokens;
        negations cannot prune)."""
        if op == "match_term":
            # the term may tokenize into several vocab tokens ('foo-bar' ->
            # foo, bar): AND their bitmaps (conservative); an un-tokenizable
            # term cannot prune at all
            toks = tokenize(str(value))
            if not toks:
                return None
            out = np.ones(self.n_segs, dtype=bool)
            for t in toks:
                out &= self._token_segs(t)
            return out
        if op != "match":
            return None
        out = np.zeros(self.n_segs, dtype=bool)
        for terms, phrases, _negs in parse_match_query(str(value)):
            cand = np.ones(self.n_segs, dtype=bool)
            for t in terms:
                cand &= self._token_segs(t)
            for p in phrases:
                for t in tokenize(p):
                    cand &= self._substr_token_segs(t)
            out |= cand
        return out


# word-boundary regex: equals the tokenizer's word split for [a-z0-9_] terms
def _term_regex(term: str) -> str:
    return r"(?i)(?:^|[^A-Za-z0-9_])" + _re.escape(term) + r"(?:[^A-Za-z0-9_]|$)"


def matches_term_mask(col, term) -> pa.Array:
    """Exact per-row matches_term predicate (reference matches_term UDF)."""
    return pc.match_substring_regex(col, _term_regex(str(term)))


def matches_mask(col, query) -> pa.Array:
    """Exact per-row matches() predicate over the parsed query language."""
    result = None
    for terms, phrases, negs in parse_match_query(str(query)):
        cand = None
        for t in terms:
            m = matches_term_mask(col, t)
            cand = m if cand is None else pc.and_kleene(cand, m)
        for p in phrases:
            m = pc.match_substring(col, p, ignore_case=True)
            cand = m if cand is None else pc.and_kleene(cand, m)
        for t in negs:
            m = pc.invert(matches_term_mask(col, t))
            cand = m if cand is None else pc.and_kleene(cand, m)
        if cand is None:
            continue
        result = cand if result is None else pc.or_kleene(result, cand)
    if result is None:
        import numpy as _np

        return pa.array(_np.ones(len(col), dtype=bool))
    return result


# ---- search -----------------------------------------------------------------


def _split_blob(blob: bytes) -> tuple[dict, bytes]:
    hlen = struct.unpack("<I", blob[:4])[0]
    header = json.loads(blob[4 : 4 + hlen])
    return header, blob[4 + hlen :]


class BloomIndex:
    """Parsed per-segment bloom filters (decode once, search many times)."""

    def __init__(self, blob: bytes):
        header, body = _split_blob(blob)
        self.segment_rows = header["segment_rows"]
        self.filters: list[BloomFilter] = []
        off = 0
        for sz in header["seg_sizes"]:
            self.filters.append(BloomFilter.from_bytes(body[off : off + sz]))
            off += sz

    def search(self, op: str, value) -> np.ndarray | None:
        """Segment candidacy bitmap for `col op value`; None = can't prune."""
        if op not in ("=", "in"):
            return None
        values = [_encode_value(v) for v in (value if op == "in" else [value])]
        out = np.zeros(len(self.filters), dtype=bool)
        for i, bf in enumerate(self.filters):
            out[i] = any(bf.contains(v) for v in values)
        return out


class InvertedIndex:
    """Parsed term -> segment-bitmap table (decode once, search many times)."""

    def __init__(self, blob: bytes):
        header, payload = _split_blob(blob)
        self.segment_rows = header["segment_rows"]
        self.terms: list[str | None] = header["terms"]
        self.n_segs = header["n_segs"]
        packed = np.frombuffer(zlib.decompress(payload), dtype=np.uint8).reshape(
            -1, header["row_bytes"]
        )
        self.bm = np.unpackbits(packed, axis=1)[:, : self.n_segs].astype(bool)
        self._term_idx = {t: i for i, t in enumerate(self.terms)}

    def _term_rows(self, v) -> np.ndarray:
        i = self._term_idx.get(_term_key(v))
        if i is None:
            return np.zeros(self.n_segs, dtype=bool)
        return self.bm[i]

    def search(self, op: str, value) -> np.ndarray | None:
        """Segment bitmap; supports =, in, != (exact, no false positives)."""
        if op == "=":
            return self._term_rows(value)
        if op == "in":
            out = np.zeros(self.n_segs, dtype=bool)
            for v in value:
                out |= self._term_rows(v)
            return out
        if op == "!=":
            # segments containing at least one row of any OTHER term
            # (NULL rows never match != under SQL three-valued logic)
            out = np.zeros(self.n_segs, dtype=bool)
            key = _term_key(value)
            for i, t in enumerate(self.terms):
                if t != key:
                    out |= self.bm[i]
            return out
        return None


def search_bloom_index(blob: bytes, op: str, value) -> np.ndarray | None:
    return BloomIndex(blob).search(op, value)


def search_inverted_index(blob: bytes, op: str, value) -> np.ndarray | None:
    return InvertedIndex(blob).search(op, value)


class IndexCache:
    """Tiny LRU for parsed puffin sidecars (reference mito2/src/cache/index/)."""

    def __init__(self, capacity: int = 64):
        self.capacity = capacity
        self._data: dict[str, dict] = {}

    def get(self, key: str):
        v = self._data.pop(key, None)
        if v is not None:
            self._data[key] = v
        return v

    def put(self, key: str, value):
        if key in self._data:
            self._data.pop(key)
        elif len(self._data) >= self.capacity:
            self._data.pop(next(iter(self._data)))
        self._data[key] = value


# ---- vector (ANN) index -----------------------------------------------------
# IVF-flat per SST (reference mito2/src/sst/index/vector_index/, which wraps
# usearch HNSW — IVF-flat is the TPU-friendly choice: probing is a batched
# centroid matmul, re-ranking a candidate matmul, both MXU shapes).


def build_vector_index(column: pa.Array, dim: int) -> bytes | None:
    """Binary-f32 vector column -> serialized IVF-flat index (coarse
    centroids + per-row assignments).  None for empty columns."""
    from ..query.vector import build_ivf, decode_matrix

    if isinstance(column, pa.ChunkedArray):
        column = column.combine_chunks()
    mat, valid = decode_matrix(column, dim)
    if not valid.any():
        return None
    cent, assign = build_ivf(mat, valid)
    header = json.dumps(
        {"dim": dim, "nlist": len(cent), "n": len(assign)}
    ).encode()
    payload = zlib.compress(cent.astype("<f4").tobytes() + assign.astype("<i4").tobytes())
    return struct.pack("<I", len(header)) + header + payload


class VectorIndex:
    """Parsed IVF-flat blob: probe nprobe nearest cells -> candidate rows."""

    def __init__(self, blob: bytes):
        header, payload = _split_blob(blob)
        self.dim = header["dim"]
        self.nlist = header["nlist"]
        self.n = header["n"]
        raw = zlib.decompress(payload)
        cbytes = self.nlist * self.dim * 4
        self.centroids = np.frombuffer(raw[:cbytes], dtype="<f4").reshape(
            self.nlist, self.dim
        )
        self.assign = np.frombuffer(raw[cbytes:], dtype="<i4")

    def candidates(self, q: np.ndarray, nprobe: int = 4) -> np.ndarray:
        from ..query.vector import ivf_candidates

        return ivf_candidates(self.centroids, self.assign, q, nprobe)
