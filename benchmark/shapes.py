"""Operations and bytes a kernel call NEEDS, from its shapes alone
(the yardstick side of a roofline share; the program is not asked).

``roofline_seconds`` is the least time the chip could take: the larger
of operations over peak rate and bytes over peak bandwidth; the share
is that over the measured kernel time, and ``bound`` says which of the
two applied.
"""

from __future__ import annotations


def roofline_seconds(ops: float, op_peak: float, bytes_moved: float,
                     byte_peak: float):
    compute, memory = ops / op_peak, bytes_moved / byte_peak
    return max(compute, memory), ("compute" if compute >= memory
                                  else "memory")


def int8_matmul(m: int, k: int, n: int, act_bytes: int = 2):
    """``x (m, k) @ dequant(q (k, n) int8, s (1, n) f32)``: 2mkn
    operations; reads the int8 weights once, the activations, the
    scales; writes the result."""
    return (2.0 * m * k * n,
            k * n + act_bytes * m * k + 4 * n + act_bytes * m * n)


def decode_step_matmuls(z: dict, rows: int):
    """Every int8 weight matmul of ONE decode step of ``rows`` live
    rows, as ``(ops, bytes)`` summed: per layer wq, wk, wv, wo and the
    dense MLP's three (absent with experts), plus the output head."""
    d, f = z["d"], z["f"]
    qkv, kvw = z["heads"] * z["hd"], z["kv"] * z["hd"]
    per_layer = [(d, qkv), (d, kvw), (d, kvw), (qkv, d)]
    if not z["experts"]:
        per_layer += [(d, f), (d, f), (f, d)]
    ops = bytes_moved = 0.0
    for k, n in per_layer * z["layers"] + [(d, z["vocab"])]:
        o, b = int8_matmul(rows, k, n)
        ops, bytes_moved = ops + o, bytes_moved + b
    return ops, bytes_moved


def decode_attention(z: dict, context_tokens: float, rows: int,
                     kv_bytes: int = 1, block: int = 16):
    """One decode step's attention over ``context_tokens`` cached
    positions in total (summed over the ``rows`` live rows, each
    rounded up to whole blocks by the caller), all layers: reads every
    cached K and V byte once (plus their f32 scales when the cache is
    int8), 4 operations per cached element per query head group."""
    per_position = 2 * z["kv"] * z["hd"] * kv_bytes
    if kv_bytes == 1:
        per_position += 2 * z["kv"] * 4
    bytes_moved = z["layers"] * (context_tokens * per_position
                                 + rows * z["heads"] * z["hd"] * 2 * 2)
    ops = z["layers"] * 4.0 * context_tokens * z["heads"] * z["hd"]
    return ops, bytes_moved
