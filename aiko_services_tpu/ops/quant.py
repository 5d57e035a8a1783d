"""Int8 weight-only quantization with a fused dequant-matmul Pallas kernel.

Autoregressive decode on TPU is HBM-bandwidth-bound: every step streams
every weight matrix once.  Storing weights as int8 with per-output-channel
f32 scales halves the bytes per step vs bfloat16 (≈2× decode throughput
ceiling) and lets an 8B-parameter model fit in a single v5e chip's 16 GB
HBM.  The reference framework has no tensor abstraction at all (SURVEY.md
§2.6) — this op exists for the framework's own native model families.

Two execution paths with identical numerics:
- Pallas TPU kernel: grid over output-column blocks; each program loads an
  int8 weight tile into VMEM, converts in-register, feeds the MXU with
  ``preferred_element_type=f32``, and applies the column scales before the
  single store — the f32 dequantized weights never exist in HBM.
- XLA fallback (CPU/tests, odd shapes): ``(x @ q.astype(dt)) * s``.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["quantize_int8", "quantize_named_int8", "dequantize",
           "int8_matmul",
           "quantize_int4", "dequantize_int4", "int4_matmul",
           "quantize_tree", "is_quantized", "is_quantized_int4"]

#: int8 symmetric range (−127…127; −128 unused to keep scales symmetric).
_QMAX = 127.0
#: int4 symmetric range (−7…7; −8 unused to keep scales symmetric).
_QMAX4 = 7.0


def quantize_int8(w) -> Dict:
    """Per-output-channel symmetric int8 quantization of a 2-D weight
    ``(in, out)`` → ``{"q": int8 (in, out), "s": f32 (1, out)}``."""
    w32 = jnp.asarray(w, jnp.float32)
    scale = jnp.max(jnp.abs(w32), axis=0, keepdims=True) / _QMAX
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(w32 / scale), -_QMAX, _QMAX).astype(jnp.int8)
    return {"q": q, "s": scale}


def dequantize(qw: Dict, dtype=jnp.bfloat16):
    return (qw["q"].astype(jnp.float32) * qw["s"]).astype(dtype)


def is_quantized(w) -> bool:
    return isinstance(w, dict) and ("q" in w or "q4" in w) and "s" in w


def is_quantized_int4(w) -> bool:
    return isinstance(w, dict) and "q4" in w and "s" in w


# --------------------------------------------------------------------------- #
# Int4 (nibble-packed, per-group scales)
#
# Packing layout: adjacent input rows share a byte — packed[k, n] holds
# w[2k, n] in its low nibble and w[2k+1, n] in its high nibble.  A
# contiguous slice of packed rows [a, b) therefore covers the contiguous
# original rows [2a, 2b), so megatron row-parallel sharding of the packed
# matrix along axis 0 stays correct (each TP shard's packed rows line up
# with its activation slice), and per-group scales shard the same way.


def quantize_int4(w, group_size: int = 128) -> Dict:
    """Per-(input-group, output-channel) symmetric int4 quantization of a
    2-D weight ``(in, out)`` → ``{"q4": int8 (in/2, out) nibble-packed,
    "s": f32 (in/group, out)}``.  Grouped scales (default 128) bound the
    quantization error per small row-block — the standard accuracy fix
    for 4-bit weights."""
    w32 = jnp.asarray(w, jnp.float32)
    k, n = w32.shape
    if k % 2:
        raise ValueError(f"int4 packing needs an even input dim, got {k}")
    if group_size % 2 or k % group_size:
        group_size = k  # degenerate: one group per column
    g = k // group_size
    grouped = w32.reshape(g, group_size, n)
    scale = jnp.max(jnp.abs(grouped), axis=1, keepdims=True) / _QMAX4
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(grouped / scale), -_QMAX4, _QMAX4)
    q = q.reshape(k, n).astype(jnp.int32)
    packed = (q[0::2] & 0xF) | ((q[1::2] & 0xF) << 4)
    packed = jnp.where(packed >= 128, packed - 256, packed).astype(jnp.int8)
    return {"q4": packed, "s": scale.reshape(g, n)}


def _unpack_int4(packed):
    """int8 (K/2, N) → (low, high) int32 nibbles, sign-extended; low[k]
    is original row 2k, high[k] row 2k+1."""
    p = packed.astype(jnp.int32)
    low = (p << 28) >> 28
    high = p >> 4
    return low, high


def dequantize_int4(qw: Dict, dtype=jnp.bfloat16):
    packed, scale = qw["q4"], qw["s"]
    khalf, n = packed.shape
    k = 2 * khalf
    g = scale.shape[0]
    low, high = _unpack_int4(packed)
    q = jnp.stack([low, high], axis=1).reshape(k, n).astype(jnp.float32)
    w = q.reshape(g, k // g, n) * scale[:, None, :]
    return w.reshape(k, n).astype(dtype)


def _kernel(x_ref, q_ref, s_ref, o_ref):
    acc = jnp.dot(x_ref[:], q_ref[:].astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    o_ref[:] = (acc * s_ref[:]).astype(o_ref.dtype)


#: VMEM budget per program (v5e has 16 MB more-or-less shared with XLA's
#: own scoped allocations; stay well under).
_VMEM_BUDGET = 6 * 1024 * 1024


def _pick_block(m: int, k: int, n: int) -> int:
    """Largest output-column block whose working set (x bf16 + int8 weight
    tile + f32 out/scales) fits the VMEM budget; 0 = no fit."""
    for block in (1024, 512, 256, 128):
        if n % block:
            continue
        working_set = 2 * m * k + k * block + 4 * m * block + 4 * block
        if working_set <= _VMEM_BUDGET:
            return block
    return 0


@functools.partial(jax.jit, static_argnames=("interpret",))
def int8_matmul(x, q, s, interpret: bool = False):
    """``x (…, K) @ dequant(q (K, N), s (1, N)) → (…, N)`` in x.dtype.

    Uses the fused Pallas kernel on TPU when shapes tile cleanly (K a
    multiple of the int8 sublane tile 32, N of 128); otherwise the XLA
    fallback, which still stores int8 in HBM and fuses the convert into
    the matmul."""
    lead = x.shape[:-1]
    k, n = q.shape
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    block_n = _pick_block(m, k, n)
    on_tpu = jax.default_backend() == "tpu"
    # The kernel targets bandwidth-bound small-m (decode) matmuls; large-m
    # (prefill/training) shapes are compute-bound and XLA's own int8
    # convert+dot fusion handles them without VMEM pressure.
    if not (on_tpu or interpret) or block_n == 0 \
            or k % 32 or m > 64:
        out = jnp.dot(x2, q.astype(x.dtype),
                      preferred_element_type=jnp.float32) * s
        return out.astype(x.dtype).reshape(*lead, n)
    out = pl.pallas_call(
        _kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((m, k), lambda j: (0, 0)),
            pl.BlockSpec((k, block_n), lambda j: (0, j)),
            pl.BlockSpec((1, block_n), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((m, block_n), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(x2, q, s)
    return out.reshape(*lead, n)


def _int4_kernel_repeat(xe_ref, xo_ref, p_ref, s_ref, o_ref,
                        *, gs_half: int, compute_dtype):
    """Whole-tile fused int4 dequant-matmul: unpack the packed nibble
    tile in-register, expand the group scales along rows, scale to
    bf16, and run TWO full-K/2 MXU dots (even/odd original rows).
    Mosaic fuses the unpack/scale chain into the dot's operand stream,
    so neither the dequantized weights nor the f32 intermediates
    materialize in HBM — measured 2.6x faster than the grouped-unroll
    kernel at K=4096 decode shapes on v5e and equal at K=14336 (a
    pre-ledger lab run; no cell of the benchmark runs int4)."""
    low, high = _unpack_int4(p_ref[:])
    se = jnp.repeat(s_ref[:], gs_half, axis=0)
    # bf16 weights feed the MXU at full rate on TPU; interpret mode
    # (CPU tests) computes in f32 because the CPU dot thunk has no
    # bf16 x bf16 path.
    wl = (low.astype(jnp.float32) * se).astype(compute_dtype)
    wh = (high.astype(jnp.float32) * se).astype(compute_dtype)
    xe = xe_ref[:].astype(compute_dtype)
    xo = xo_ref[:].astype(compute_dtype)
    acc = (jnp.dot(xe, wl, preferred_element_type=jnp.float32)
           + jnp.dot(xo, wh, preferred_element_type=jnp.float32))
    o_ref[:] = acc.astype(o_ref.dtype)


#: khalf -> output-column blocks (preferred first), drawn from the tile
#: classes compiled and run on the v5e (a pre-ledger lab run):
#: K=4096 (khalf 2048) ran at bn 128/256/512 — 256 measured fastest,
#: 512 validated but never preferred (any n divisible by 512 picks 256
#: first anyway) — and K=14336 (khalf 7168) at bn=128.  A bn=512 tile
#: at K=14336 failed to compile, and no other khalf class has ever been
#: compiled, so no other is dispatched on hardware; the PR that times
#: int4 on the chip (ROADMAP C6) decides whether the restriction stays.
_REPEAT_VALIDATED = {2048: (256, 128), 7168: (128,)}


def _pick_block_repeat(khalf: int, n: int, interpret: bool) -> int:
    """Output-column block for the repeat kernel.  On hardware the
    dispatch is restricted to the validated classes above; interpret
    mode runs no Mosaic compile, so tests may exercise any tileable
    shape."""
    if interpret:
        blocks = (256, 128) if khalf <= 2048 else (128,)
    else:
        blocks = _REPEAT_VALIDATED.get(khalf, ())
    for block in blocks:
        if n % block == 0:
            return block
    return 0


def _int4_kernel(xe_ref, xo_ref, p_ref, s_ref, o_ref, *, gs_half: int,
                 groups: int):
    """Grouped fused int4 dequant-matmul (fallback for shapes outside
    the repeat kernel's validated envelope): per scale group, unpack
    the packed nibble tile in-register, run two MXU dots (even/odd
    original rows), and apply the group's column scales into the f32
    accumulator.  The dequantized weights never exist in HBM."""
    m = xe_ref.shape[0]
    acc = jnp.zeros((m, o_ref.shape[1]), jnp.float32)
    # Static (unrolled) group loop: Mosaic has no dynamic_slice on
    # values, and `groups` is a trace-time constant anyway (≤ ~112).
    for g in range(groups):
        rows = slice(g * gs_half, (g + 1) * gs_half)
        low, high = _unpack_int4(p_ref[rows, :])
        xe_g = xe_ref[:, rows].astype(jnp.float32)
        xo_g = xo_ref[:, rows].astype(jnp.float32)
        part = (jnp.dot(xe_g, low.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
                + jnp.dot(xo_g, high.astype(jnp.float32),
                          preferred_element_type=jnp.float32))
        acc = acc + part * s_ref[g:g + 1, :]
    o_ref[:] = acc.astype(o_ref.dtype)


def _pick_block_int4(m: int, khalf: int, n: int, groups: int) -> int:
    """Largest output-column block fitting the VMEM budget: x halves
    (bf16, whole K), packed int8 tile, f32 scales, f32 accumulator plus
    per-group unpack temporaries (~3 int32/f32 copies of one group)."""
    for block in (1024, 512, 256, 128):
        if n % block:
            continue
        gs_half = khalf // groups
        working_set = (2 * 2 * m * khalf          # xe + xo bf16
                       + khalf * block            # packed int8 tile
                       + 4 * groups * block       # scales f32
                       + 4 * m * block            # accumulator
                       + 12 * gs_half * block)    # unpack temporaries
        if working_set <= _VMEM_BUDGET:
            return block
    return 0


@functools.partial(jax.jit, static_argnames=("interpret",))
def int4_matmul(x, q4, s, interpret: bool = False):
    """``x (…, K) @ dequant(q4 (K/2, N) packed, s (G, N)) → (…, N)``.

    Decode shapes (m ≤ 64) on TPU use the fused Pallas kernel — int4
    halves the HBM bytes per step vs int8, so the weight-streaming
    decode ceiling roughly doubles.  Other shapes take an XLA grouped
    einsum that never materializes the full dequantized matrix at rest
    (XLA fuses the unpack/scale into the contraction)."""
    lead = x.shape[:-1]
    khalf, n = q4.shape
    k = 2 * khalf
    groups = s.shape[0]
    gs_half = khalf // groups
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    on_tpu = jax.default_backend() == "tpu"
    pallas_ok = (on_tpu or interpret) and m <= 64
    repeat_block = _pick_block_repeat(khalf, n, interpret) \
        if pallas_ok else 0
    unroll_block = _pick_block_int4(m, khalf, n, groups) \
        if pallas_ok else 0
    # gs_half alignment: validation used group_size=128 (gs_half 64);
    # 32-multiples share its int8 sublane tiling.
    if repeat_block and gs_half >= 32 and gs_half % 32 == 0:
        kernel = functools.partial(
            _int4_kernel_repeat, gs_half=gs_half,
            compute_dtype=jnp.float32 if interpret else jnp.bfloat16)
        block_n = repeat_block
    elif unroll_block and gs_half >= 32 and gs_half % 32 == 0:
        kernel = functools.partial(_int4_kernel, gs_half=gs_half,
                                   groups=groups)
        block_n = unroll_block
    else:
        low, high = _unpack_int4(q4)
        q = jnp.stack([low, high], axis=1).reshape(k, n)
        x3 = x2.astype(jnp.float32).reshape(m, groups, k // groups)
        w3 = q.reshape(groups, k // groups, n).astype(jnp.float32)
        out = jnp.einsum("mgk,gkn,gn->mn", x3, w3, s,
                         preferred_element_type=jnp.float32)
        return out.astype(x.dtype).reshape(*lead, n)
    xe = x2[:, 0::2]
    xo = x2[:, 1::2]
    out = pl.pallas_call(
        kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((m, khalf), lambda j: (0, 0)),
            pl.BlockSpec((m, khalf), lambda j: (0, 0)),
            pl.BlockSpec((khalf, block_n), lambda j: (0, j)),
            pl.BlockSpec((groups, block_n), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((m, block_n), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(xe, xo, q4, s)
    return out.reshape(*lead, n)


def quantize_tree(tree, bits: int = 8, group_size: int = 128):
    """Quantize every 2-D float leaf of a parameter pytree (norm vectors
    and anything 1-D stay as-is).  ``bits`` ∈ {8, 4}; int4 uses
    nibble-packed storage with per-group scales."""
    if bits not in (8, 4):
        raise ValueError(f"bits must be 8 or 4, got {bits}")

    def visit(leaf):
        if isinstance(leaf, jnp.ndarray) and leaf.ndim == 2 and \
                jnp.issubdtype(leaf.dtype, jnp.floating):
            if bits == 4:
                return quantize_int4(leaf, group_size)
            return quantize_int8(leaf)
        return leaf
    return jax.tree_util.tree_map(
        visit, tree, is_leaf=lambda x: isinstance(x, jnp.ndarray))


def quantize_named_int8(tree, names) -> Dict:
    """int8 weight-only for the leaves of a parameter tree of dicts and
    lists whose KEY is in ``names``; every other leaf stays as it is
    (a model module says which of its 2-D matrices are served int8:
    its router, small vectors and 3-D expert leaves are not)."""
    def visit(node):
        return {name: (visit(leaf) if isinstance(leaf, dict)
                       else [visit(item) for item in leaf]
                       if isinstance(leaf, list)
                       else quantize_int8(leaf) if name in names
                       else leaf)
                for name, leaf in node.items()}

    return visit(tree)
