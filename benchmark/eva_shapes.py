"""Operations and bytes a chunk summary NEEDS, from its shapes alone
(the yardstick side of ``eva_summarise_roofline``; the program is not
asked, and the count is the same whatever implements the summary).

A chunk of ``chunk`` K/V rows of ``kv`` heads of ``hd`` values becomes
one summary row a head: a score a row (``phi . k``), a softmax down
the chunk, the pooled key and the pooled value.
"""

from __future__ import annotations


def chunk_summaries(z: dict, chunks: float, kv_bytes: int = 1):
    """``chunks`` summaries in ONE layer, as ``(ops, bytes)``: reads the
    chunk's K and V rows once (plus their f32 scales when the cache is
    int8) and writes two float32 rows a head; 2 operations an element
    for the score, for the pooled key and for the pooled value."""
    elements = z["chunk"] * z["kv"] * z["hd"]
    read = 2 * elements * kv_bytes
    if kv_bytes == 1:
        read += 2 * z["chunk"] * z["kv"] * 4
    written = 2 * z["kv"] * z["hd"] * 4
    return chunks * 6.0 * elements, chunks * (read + written)
