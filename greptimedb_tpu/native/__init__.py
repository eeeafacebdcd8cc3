"""ctypes bindings for the native runtime library.

Builds `libgreptime_native.<source hash>.so` with g++ from the committed
`src/greptime_native.cpp` whenever no library for THAT source exists — the
binary is git-ignored, so a file left by an older checkout must never win
over the source.  Every entry point has a pure-Python fallback so the
package works without a toolchain — but the hot paths (WAL recovery scan,
line-protocol tokenize, crc32) run native when available; `available()`
says which (chip_smoke.py prints it).
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess

_DIR = os.path.dirname(__file__)
_SRC = os.path.join(_DIR, "src", "greptime_native.cpp")
_lib = None
_load_failed = False


def _lib_path() -> str | None:
    try:
        with open(_SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:12]
    except OSError:
        return None
    return os.path.join(_DIR, f"libgreptime_native.{digest}.so")


def _build(lib_path: str) -> bool:
    # build beside the target and rename: concurrent builders (test
    # workers) each install a complete file
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-fPIC", "-shared", "-std=c++17", "-o", tmp, _SRC],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, lib_path)
    except (OSError, subprocess.SubprocessError):
        try:
            os.remove(tmp)
        except OSError:
            pass
        return False
    for stale in glob.glob(os.path.join(_DIR, "libgreptime_native*.so")):
        if stale != lib_path:
            try:
                os.remove(stale)
            except OSError:
                pass
    return True


def load() -> ctypes.CDLL | None:
    global _lib, _load_failed
    if _lib is not None:
        return _lib
    if _load_failed:
        return None
    lib_path = _lib_path()
    if lib_path is None or (not os.path.exists(lib_path) and not _build(lib_path)):
        _load_failed = True
        return None
    try:
        lib = ctypes.CDLL(lib_path)
    except OSError:
        _load_failed = True
        return None
    lib.gt_crc32.restype = ctypes.c_uint32
    lib.gt_crc32.argtypes = [ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint32]
    lib.gt_wal_scan.restype = ctypes.c_int64
    lib.gt_wal_scan.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
    ]
    lib.gt_lp_tokenize.restype = ctypes.c_int64
    lib.gt_lp_tokenize.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
    ]
    lib.gt_snappy_uncompressed_length.restype = ctypes.c_int64
    lib.gt_snappy_uncompressed_length.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.gt_snappy_decompress.restype = ctypes.c_int64
    lib.gt_snappy_decompress.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_char),
        ctypes.c_int64,
    ]
    lib.gt_snappy_compress.restype = ctypes.c_int64
    lib.gt_snappy_compress.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_char),
        ctypes.c_int64,
    ]
    lib.gt_snappy_max_compressed_length.restype = ctypes.c_int64
    lib.gt_snappy_max_compressed_length.argtypes = [ctypes.c_int64]
    _lib = lib
    return lib


def available() -> bool:
    return load() is not None


def crc32(data: bytes, seed: int = 0) -> int:
    lib = load()
    if lib is None:
        import zlib

        return zlib.crc32(data, seed)
    return lib.gt_crc32(data, len(data), seed)


def wal_scan(buf: bytes, max_entries: int = 1 << 20) -> list[tuple[int, int, int]]:
    """Scan WAL frames -> [(payload_offset, payload_len, entry_id)]."""
    lib = load()
    if lib is None:
        return _wal_scan_py(buf, max_entries)
    out = (ctypes.c_int64 * (3 * max_entries))()
    n = lib.gt_wal_scan(buf, len(buf), out, max_entries)
    return [(out[i * 3], out[i * 3 + 1], out[i * 3 + 2]) for i in range(n)]


def _wal_scan_py(buf: bytes, max_entries: int):
    import struct
    import zlib

    header = struct.Struct("<IIQ")
    out, pos = [], 0
    while len(out) < max_entries and pos + header.size <= len(buf):
        length, crc, entry_id = header.unpack_from(buf, pos)
        payload_start = pos + header.size
        if payload_start + length > len(buf):
            break
        payload = buf[payload_start : payload_start + length]
        if zlib.crc32(payload) != crc:
            break
        out.append((payload_start, length, entry_id))
        pos = payload_start + length
    return out


# Token kinds from greptime_native.cpp (kind >= 100 means "has escapes").
TOK_MEASUREMENT = 0
TOK_TAG_KEY = 1
TOK_TAG_VAL = 2
TOK_FIELD_KEY = 3
TOK_FIELD_FLOAT = 4
TOK_FIELD_INT = 5
TOK_FIELD_STR = 6
TOK_FIELD_BOOL_T = 7
TOK_FIELD_BOOL_F = 8
TOK_TIMESTAMP = 9
TOK_LINE_END = 10


def lp_parse_homogeneous(buf: bytes, mult_num: int, mult_den: int,
                         max_tags: int = 16, max_fields: int = 32):
    """Columnar parse of a HOMOGENEOUS line-protocol batch (one
    measurement, fixed tag/float-field keys, timestamps present).
    Returns (measurement, tag_keys, field_keys, ts int64[n],
    fields float64[n, n_fields], tag_spans int64[n, n_tags, 2]) or None
    (unavailable / batch not homogeneous — fall back to the tokenizer)."""
    lib = load()
    if lib is None or not hasattr(lib, "gt_lp_parse_homogeneous"):
        return None
    import numpy as np

    # size outputs from LINE 1's shape (every later line must match it or
    # the parse bails anyway) — sizing by the caps wasted ~500 MB on
    # million-line single-field batches
    first = buf.split(b"\n", 1)[0]
    head = first.split(b" ", 1)
    max_tags = min(max_tags, max(head[0].count(b","), 1))
    if len(head) > 1:
        max_fields = min(max_fields, max(head[1].count(b",") + 2, 2))
    max_lines = buf.count(b"\n") + 2
    ts = np.empty(max_lines, dtype=np.int64)
    fields = np.empty(max_lines * max_fields, dtype=np.float64)
    tag_spans = np.empty(max_lines * max_tags * 2, dtype=np.int64)
    shape = np.zeros(4 + 2 * max_tags + 2 * max_fields, dtype=np.int64)
    fn = lib.gt_lp_parse_homogeneous
    fn.restype = ctypes.c_int64
    n = fn(
        buf, ctypes.c_int64(len(buf)),
        ctypes.c_int64(mult_num), ctypes.c_int64(mult_den),
        ts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        fields.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        tag_spans.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ctypes.c_int64(max_lines), ctypes.c_int64(max_tags),
        ctypes.c_int64(max_fields),
        shape.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    if n <= 0:
        return None
    n_tags, n_fields = int(shape[0]), int(shape[1])
    measurement = buf[shape[2]:shape[3]].decode()
    tag_keys = [
        buf[shape[4 + t * 2]:shape[4 + t * 2 + 1]].decode() for t in range(n_tags)
    ]
    base = 4 + max_tags * 2
    field_keys = [
        buf[shape[base + f * 2]:shape[base + f * 2 + 1]].decode()
        for f in range(n_fields)
    ]
    return (
        measurement, tag_keys, field_keys,
        ts[:n].copy(),
        fields.reshape(max_lines, max_fields)[:n, :n_fields].copy(),
        tag_spans.reshape(max_lines, max_tags, 2)[:n, :n_tags].copy(),
    )


def lp_tokenize(buf: bytes, max_tokens: int | None = None):
    """Tokenize line protocol -> [(kind, start, end)] or None if the native
    lib is unavailable (caller falls back to the Python parser)."""
    lib = load()
    if lib is None:
        return None
    if max_tokens is None:
        max_tokens = max(64, buf.count(b"\n") * 16 + 64)
    out = (ctypes.c_int64 * (3 * max_tokens))()
    n = lib.gt_lp_tokenize(buf, len(buf), out, max_tokens)
    if n < 0:
        from ..utils.errors import InvalidArgumentsError

        raise InvalidArgumentsError(f"bad line protocol near offset {-(n + 1)}")
    return [(out[i * 3], out[i * 3 + 1], out[i * 3 + 2]) for i in range(n)]


# ---- snappy (Prometheus remote write/read bodies) --------------------------


class SnappyError(ValueError):
    pass


def snappy_decompress(data: bytes) -> bytes:
    lib = load()
    if lib is None:
        return _snappy_decompress_py(data)
    n = lib.gt_snappy_uncompressed_length(data, len(data))
    # Snappy's worst-case expansion is a 2-byte copy element emitting 64
    # bytes (32x); a preamble claiming more than that is hostile — reject
    # before allocating (the length is attacker-controlled input).
    if n < 0 or n > len(data) * 32 + 64:
        raise SnappyError("bad snappy preamble")
    out = ctypes.create_string_buffer(n)
    got = lib.gt_snappy_decompress(data, len(data), out, n)
    if got < 0:
        raise SnappyError(f"snappy decompress failed (code {got})")
    return out.raw[:got]


def snappy_compress(data: bytes) -> bytes:
    lib = load()
    if lib is None:
        return _snappy_compress_py(data)
    cap = lib.gt_snappy_max_compressed_length(len(data))
    out = ctypes.create_string_buffer(cap)
    got = lib.gt_snappy_compress(data, len(data), out, cap)
    if got < 0:
        raise SnappyError(f"snappy compress failed (code {got})")
    return out.raw[:got]


def _uvarint(buf: bytes, pos: int) -> tuple[int, int]:
    v, shift = 0, 0
    while pos < len(buf):
        b = buf[pos]
        pos += 1
        v |= (b & 0x7F) << shift
        if not (b & 0x80):
            return v, pos
        shift += 7
        if shift > 63:
            break
    raise SnappyError("bad varint")


def _snappy_decompress_py(data: bytes) -> bytes:
    expect, ip = _uvarint(data, 0)
    if expect > len(data) * 32 + 64:
        raise SnappyError("bad snappy preamble")
    out = bytearray()
    n = len(data)
    while ip < n:
        tag = data[ip]
        ip += 1
        kind = tag & 3
        if kind == 0:
            lit_len = (tag >> 2) + 1
            if lit_len > 60:
                extra = lit_len - 60
                if ip + extra > n:
                    raise SnappyError("truncated literal length")
                lit_len = int.from_bytes(data[ip : ip + extra], "little") + 1
                ip += extra
            if ip + lit_len > n:
                raise SnappyError("truncated literal")
            out += data[ip : ip + lit_len]
            ip += lit_len
        else:
            if kind == 1:
                if ip + 1 > n:
                    raise SnappyError("truncated copy")
                cp_len = ((tag >> 2) & 7) + 4
                offset = ((tag >> 5) << 8) | data[ip]
                ip += 1
            elif kind == 2:
                if ip + 2 > n:
                    raise SnappyError("truncated copy")
                cp_len = (tag >> 2) + 1
                offset = int.from_bytes(data[ip : ip + 2], "little")
                ip += 2
            else:
                if ip + 4 > n:
                    raise SnappyError("truncated copy")
                cp_len = (tag >> 2) + 1
                offset = int.from_bytes(data[ip : ip + 4], "little")
                ip += 4
            if offset == 0 or offset > len(out):
                raise SnappyError("bad copy offset")
            for _ in range(cp_len):  # may overlap its own output
                out.append(out[-offset])
    if len(out) != expect:
        raise SnappyError("snappy length mismatch")
    return bytes(out)


def _snappy_compress_py(data: bytes) -> bytes:
    """Literal-only encoding — valid snappy, zero compression (fallback)."""
    out = bytearray()
    v = len(data)
    while v >= 0x80:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)
    pos = 0
    while pos < len(data):
        chunk = data[pos : pos + 65536]
        n = len(chunk) - 1
        if n < 60:
            out.append(n << 2)
        else:
            out.append(61 << 2)
            out += n.to_bytes(2, "little")
        out += chunk
        pos += len(chunk)
    return bytes(out)
