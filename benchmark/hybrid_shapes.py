"""Operations and bytes the kernels of a hybrid (Mamba-2 + latent
experts) stack NEED, from their shapes and the routes taken: the
yardstick side of
``moe_expert_roofline`` and ``ssm_update_roofline``.  Beside
``shapes.py``, which the accepted metrics use and no PR but a
benchmark PR edits."""

from __future__ import annotations


def expert_up(rows: int, pairs: float, experts: int, d_in: int, f: int,
              weight_bytes: int = 2, act_bytes: int = 2):
    """The routed up-projection of one expert layer: ``pairs``
    token-expert choices that fell on experts held here, of ``rows``
    rows.  ``2 pairs d_in f`` operations (``relu**2`` and the gate
    folded in); reads each held expert's ``(d_in, f)`` matrix once,
    the rows and a gate a pair; writes a hidden row a pair.  What a
    dispatch computes beyond its rows' routes (every held expert on
    every row) is not needed and not counted."""
    return (2.0 * pairs * d_in * f,
            experts * d_in * f * weight_bytes + rows * d_in * act_bytes
            + pairs * 4 + pairs * f * act_bytes)


def expert_down(rows: int, pairs: float, experts: int, d_in: int, f: int,
                weight_bytes: int = 2, act_bytes: int = 2):
    """The routed down-projection: ``2 pairs f d_in`` operations;
    reads each held expert's ``(f, d_in)`` matrix once and a hidden
    row a pair; writes ``(rows, d_in)``."""
    return (2.0 * pairs * f * d_in,
            experts * f * d_in * weight_bytes + pairs * f * act_bytes
            + rows * d_in * act_bytes)


def ssm_update(slots: int, heads: int, head_dim: int, state: int,
               groups: int):
    """One decode token per slot of one Mamba-2 layer: the float32
    state ``(slots, heads, head_dim, state)`` read once and written
    once (an idle row's too: it is kept by a select), 6 operations an
    element (decay, drive, accumulate, and the product and sum with
    C); x, dt, B, C in and y out are the small change."""
    elements = slots * heads * head_dim * state
    small = slots * (2 * heads * head_dim + heads + 2 * groups * state)
    return 6.0 * elements, 4 * (2 * elements + small)
