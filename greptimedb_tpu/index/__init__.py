"""Log-scale secondary index subsystem.

The original per-SST term indexes (`storage/index.py` InvertedIndex /
FulltextIndex) are whole-blob loads: one dict+bitmap payload per column,
deserialized in full on first touch.  Fine at dashboard cardinalities,
O(index) memory per query at log-tenant ones — a column with 10^7 unique
terms pays tens of MB of decode to answer one term lookup.

This package is the scalable replacement (reference: the `index` crate's
FST-backed inverted index with ranged puffin reads):

* `segmented` — the on-disk format and its builder/reader: a sorted term
  dictionary split into fixed-size segments, each written as its OWN
  puffin blob with delta-varint posting lists, plus one small meta blob
  holding the sparse fence-key array.  A term lookup is binary search
  over the in-memory fence keys -> ONE ranged `PuffinReader` read of one
  segment -> posting decode: O(log terms) time, O(segment) memory.
* `reader` — `TermIndexReader`, the shared per-SST router consulted by
  scan-time pruning: it serves segmented blobs and the legacy whole-blob
  formats through one interface (old SSTs keep working), degrades any
  segment-read failure to "cannot prune" (never a wrong result), and
  answers distinct-term stats the query planner's `agg_strategy` pass
  feeds on.
"""

from .reader import TermIndexReader
from .segmented import (
    TERM_META_BLOB,
    TERM_SEGMENT_BLOB,
    Postings,
    SegmentedTermIndex,
    build_token_postings,
    term_postings,
    write_term_index,
)

__all__ = [
    "TERM_META_BLOB",
    "TERM_SEGMENT_BLOB",
    "Postings",
    "SegmentedTermIndex",
    "TermIndexReader",
    "build_token_postings",
    "term_postings",
    "write_term_index",
]
