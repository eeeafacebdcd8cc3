"""Column tiles: Arrow columns -> fixed-shape padded device arrays.

XLA traces one program per shape, so variable-length scan output must be
padded to a static tile size with a validity mask — the TPU analogue of the
reference's `PartitionRange` blocking (reference mito2/src/read/range.rs).
String/tag columns are dictionary-encoded to int32 codes on the host before
upload, mirroring the reference's primary-key pre-encoding
(mito-codec/src/row_converter/): group-by and equality filters then run on
codes, and the host maps codes back to strings when shipping results.

Padding sizes are quantized to powers of two (min one tile) so repeated
queries over slightly different row counts reuse compiled programs.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from ..datatypes.data_type import ConcreteDataType
from ..datatypes.schema import Schema

DEFAULT_TILE_ROWS = 1 << 20


def padded_size(n: int, tile_rows: int = DEFAULT_TILE_ROWS) -> int:
    """Quantized padded length: next power of two, everywhere.

    Shape count must stay O(log N), NOT O(N / tile_rows): XLA compiles of
    the segment-aggregate program over multi-million-row arrays take
    seconds to tens of seconds each, and flush timing (async
    threshold flushes) jitters SST row counts run-to-run — multiple-of-tile
    padding turned that jitter into fresh compiles per file.  Power-of-two
    padding wastes at most 2x HBM per staged tile batch and collapses every
    file of similar magnitude onto one compiled shape that also survives in
    the persistent compilation cache across processes."""
    if n <= 0:
        return min(tile_rows, 1024)
    p = 1
    while p < n:
        p <<= 1
    return p


@dataclass
class TileBatch:
    """A padded, device-ready batch of columns.

    columns: name -> jnp array of shape [padded_rows]
    valid:   bool [padded_rows]; False for padding AND null rows
    nulls:   name -> bool [padded_rows] per-column validity (True = present)
    dicts:   name -> list of python values; column holds int32 codes into it
    num_rows: real (unpadded) row count
    """

    columns: dict[str, jnp.ndarray]
    valid: jnp.ndarray
    nulls: dict[str, jnp.ndarray]
    dicts: dict[str, list] = field(default_factory=dict)
    num_rows: int = 0

    @property
    def padded_rows(self) -> int:
        return int(self.valid.shape[0])

    def device_arrays(self) -> tuple[dict[str, jnp.ndarray], jnp.ndarray]:
        """The jit-traceable payload: (columns dict, valid mask).  Dicts and
        num_rows are host-side metadata and stay out of traced signatures."""
        return self.columns, self.valid


def tiles_from_table(
    table: pa.Table,
    schema: Schema | None = None,
    tile_rows: int = DEFAULT_TILE_ROWS,
    device=None,
    dicts: dict[str, dict] | None = None,
) -> TileBatch:
    """Host-side: convert an Arrow table to a padded TileBatch.

    `dicts` optionally pins pre-agreed dictionary code assignments (needed
    when multiple shards must agree on tag codes for a global group-by).
    """
    n = table.num_rows
    padded = padded_size(n, tile_rows)
    columns: dict[str, jnp.ndarray] = {}
    nulls: dict[str, jnp.ndarray] = {}
    out_dicts: dict[str, list] = {}

    for name in table.column_names:
        col = table[name].combine_chunks() if table.num_rows else table[name]
        arr, null_mask, dict_values = _encode_column(col, name, dicts)
        if dict_values is not None:
            out_dicts[name] = dict_values
        pad_arr = np.zeros(padded, dtype=arr.dtype)
        pad_arr[:n] = arr
        columns[name] = jnp.asarray(pad_arr)
        if null_mask is not None:
            pad_null = np.zeros(padded, dtype=bool)
            pad_null[:n] = null_mask
            nulls[name] = jnp.asarray(pad_null)

    valid_np = np.zeros(padded, dtype=bool)
    valid_np[:n] = True
    valid = jnp.asarray(valid_np)
    if device is not None:
        columns = {k: jax.device_put(v, device) for k, v in columns.items()}
        nulls = {k: jax.device_put(v, device) for k, v in nulls.items()}
        valid = jax.device_put(valid, device)
    return TileBatch(columns=columns, valid=valid, nulls=nulls, dicts=out_dicts, num_rows=n)


def _encode_column(col: pa.ChunkedArray, name: str, pinned: dict[str, dict] | None):
    """Return (np values, null mask present=True or None, dict values or None)."""
    t = col.type
    null_mask = None
    if col.null_count:
        null_mask = np.asarray(pc.is_valid(col))  # True = value present
    if pa.types.is_dictionary(t):
        col = pc.cast(col, t.value_type)
        t = t.value_type
    if pa.types.is_string(t) or pa.types.is_large_string(t) or pa.types.is_binary(t):
        if pinned and name in pinned:
            # vectorized lookup against the pre-agreed code assignment
            dict_values = _mapping_to_list(pinned[name])
            none_code = pinned[name].get(None, -1)
            idx = pc.index_in(col, value_set=pa.array(dict_values, t))
            codes = np.asarray(
                pc.fill_null(idx, -1).to_numpy(zero_copy_only=False), np.int32
            )
            if none_code >= 0 and col.null_count:
                null_np = np.asarray(
                    pc.is_null(col).to_numpy(zero_copy_only=False), bool
                )
                codes = np.where(null_np, none_code, codes)
        else:
            flat = col
            if isinstance(flat, pa.ChunkedArray):
                flat = flat.combine_chunks()
                if isinstance(flat, pa.ChunkedArray):
                    flat = (
                        flat.chunk(0)
                        if flat.num_chunks
                        else pa.array([], type=t)
                    )
            enc = pc.dictionary_encode(flat)  # Array in -> DictionaryArray out
            dict_values = enc.dictionary.to_pylist()
            codes = np.asarray(
                pc.fill_null(enc.indices, -1).to_numpy(zero_copy_only=False),
                np.int32,
            )
            if col.null_count:
                # nulls become a dictionary value of their own (legacy
                # first-seen behavior: None was a dict key)
                null_np = np.asarray(
                    pc.is_null(col).to_numpy(zero_copy_only=False), bool
                )
                codes = np.where(null_np, len(dict_values), codes)
                dict_values = dict_values + [None]
        return codes, null_mask, dict_values
    if pa.types.is_timestamp(t) or pa.types.is_duration(t):
        arr = np.asarray(pc.cast(col, pa.int64()).to_numpy(zero_copy_only=False))
        return arr, null_mask, None
    if pa.types.is_boolean(t):
        return col.to_numpy(zero_copy_only=False).astype(bool), null_mask, None
    arr = col.to_numpy(zero_copy_only=False)
    if arr.dtype == object:  # nullable numeric came back as object
        arr = np.array([0 if v is None else v for v in arr], dtype=np.float64)
    elif null_mask is not None and np.issubdtype(arr.dtype, np.floating):
        arr = np.nan_to_num(arr, nan=0.0)  # nulls decoded as NaN -> 0 + mask
    return arr, null_mask, None


def _mapping_to_list(mapping: dict) -> list:
    out = [None] * len(mapping)
    for v, code in mapping.items():
        if 0 <= code < len(out):
            out[code] = v
    return out


def column_or_mask(batch: TileBatch, name: str) -> tuple[jnp.ndarray, jnp.ndarray]:
    """A column plus its effective validity (row valid AND not null)."""
    col = batch.columns[name]
    valid = batch.valid
    if name in batch.nulls:
        valid = valid & batch.nulls[name]
    return col, valid
