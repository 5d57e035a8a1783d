"""Operations and bytes the kernels of a latent-attention (MLA) stack
with routed SwiGLU experts NEED, from their shapes and the routes
taken: the yardstick side of ``latent_decode_roofline``,
``latent_prefill_roofline`` and ``swiglu_expert_roofline``.  Beside
``shapes.py``, which the accepted metrics use and no PR but a benchmark
PR edits."""

from __future__ import annotations


def latent_row_bytes(rank: int, rope: int, value_bytes: int = 2) -> int:
    """What a cached position needs in one layer: ``c_kv`` and ``k_r``
    (640 B at rank 256, rope 64, bfloat16).  Lane padding of the pool's
    rows is not needed and not counted."""
    return (rank + rope) * value_bytes


def latent_decode(positions: float, rows: int, heads: int, rank: int,
                  rope: int, value_bytes: int = 2):
    """One layer's absorbed decode attention over ``positions`` cached
    positions in total (summed over the ``rows`` query rows): every
    head contracts its query with the row (``rank + rope``) and
    weights the row's ``c_kv`` (``rank``): ``2 heads (2 rank + rope)``
    operations a position; each position's row read once; a query
    ``(heads, rank + rope)`` in and a result ``(heads, rank)`` out a
    row."""
    ops = 2.0 * positions * heads * (2 * rank + rope)
    moved = (positions * latent_row_bytes(rank, rope, value_bytes)
             + rows * heads * (2 * rank + rope) * value_bytes)
    return ops, moved


def latent_prefill(block_visits: float, block: int, q_tile: int,
                   heads: int, rank: int, rope: int,
                   value_bytes: int = 2):
    """One layer's prefill-path attention, from the key blocks its
    query tiles swept (``block_visits``: blocks of ``block`` keys x
    tiles of ``q_tile`` query tokens, the program's count with
    causality at tile granularity): the absorbed products of every
    (query, head) of the tile with every key of the block, and the
    block's latent rows read once a visit."""
    pairs = block_visits * block * q_tile
    return (2.0 * pairs * heads * (2 * rank + rope),
            block_visits * block * latent_row_bytes(rank, rope,
                                                    value_bytes))


def swiglu_up(rows: int, pairs: float, experts: int, d: int, f: int,
              matrices: int = 2, weight_bytes: int = 2,
              act_bytes: int = 2):
    """The routed gate and up projections of one expert layer
    (``matrices``: how many of the two the call reads): ``pairs``
    token-expert choices that fell on experts held here, of ``rows``
    rows.  ``2 pairs d f`` operations a matrix; reads each held
    expert's ``(d, f)`` matrix once, the rows and a gate a pair;
    writes a hidden row a pair.  What a dense dispatch computes beyond
    its rows' routes is not needed and not counted."""
    return (2.0 * matrices * pairs * d * f,
            matrices * experts * d * f * weight_bytes
            + rows * d * act_bytes + pairs * 4 + pairs * f * act_bytes)


def swiglu_down(rows: int, pairs: float, experts: int, d: int, f: int,
                weight_bytes: int = 2, act_bytes: int = 2):
    """The routed down-projection: ``2 pairs f d`` operations; reads
    each held expert's ``(f, d)`` matrix once and a hidden row a pair;
    writes ``(rows, d)``."""
    return (2.0 * pairs * f * d,
            experts * f * d * weight_bytes + pairs * f * act_bytes
            + rows * d * act_bytes)
