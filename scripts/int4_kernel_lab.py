"""Kernel lab: race int4 fused dequant-matmul variants on the real chip.

Not part of the framework — a scratch harness for picking the fastest
Mosaic structure for ops/quant.int4_matmul.  Run: python scripts/int4_kernel_lab.py
"""
import functools
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: INT4LAB_INTERPRET=1 runs the kernels in interpret mode (CPU
#: sanity of the --one path; timings meaningless there).
_INTERPRET = os.environ.get("INT4LAB_INTERPRET", "") not in ("", "0")

from aiko_services_tpu.ops.quant import (
    quantize_int4, quantize_int8, int4_matmul, int8_matmul,
)


def _unpack(p):
    pi = p.astype(jnp.int32)
    return (pi << 28) >> 28, pi >> 4


# Variant B: unpack the whole tile, repeat-expand scales, two big dots.
def _kernel_repeat(xe_ref, xo_ref, p_ref, s_ref, o_ref, *, gs_half):
    low, high = _unpack(p_ref[:])
    se = jnp.repeat(s_ref[:], gs_half, axis=0)
    wl = (low.astype(jnp.float32) * se).astype(jnp.bfloat16)
    wh = (high.astype(jnp.float32) * se).astype(jnp.bfloat16)
    acc = (jnp.dot(xe_ref[:], wl, preferred_element_type=jnp.float32)
           + jnp.dot(xo_ref[:], wh, preferred_element_type=jnp.float32))
    o_ref[:] = acc.astype(o_ref.dtype)


def matmul_repeat(x, q4, s, block_n):
    khalf, n = q4.shape
    k = 2 * khalf
    groups = s.shape[0]
    gs_half = khalf // groups
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    xe, xo = x2[:, 0::2], x2[:, 1::2]
    return pl.pallas_call(
        functools.partial(_kernel_repeat, gs_half=gs_half),
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((m, khalf), lambda j: (0, 0)),
            pl.BlockSpec((m, khalf), lambda j: (0, 0)),
            pl.BlockSpec((khalf, block_n), lambda j: (0, j)),
            pl.BlockSpec((groups, block_n), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((m, block_n), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=_INTERPRET,
    )(xe, xo, q4, s)


# Variant C: 3-D blocks, batched dot_general over the group axis.
def _kernel_batched(x3_ref, p3_ref, s3_ref, o_ref):
    low, high = _unpack(p3_ref[:])           # (G, gs_half, bn)
    x3 = x3_ref[:]                            # (G, 2*gs_half, m) bf16
    gsh = low.shape[1]
    xe = x3[:, :gsh, :]
    xo = x3[:, gsh:, :]
    dims = (((1,), (1,)), ((0,), (0,)))       # contract gs_half, batch G
    acc = (jax.lax.dot_general(xe, low.astype(jnp.bfloat16), dims,
                               preferred_element_type=jnp.float32)
           + jax.lax.dot_general(xo, high.astype(jnp.bfloat16), dims,
                                 preferred_element_type=jnp.float32))
    # acc (G, m, bn) * s (G, 1, bn) summed over groups
    o_ref[:] = jnp.sum(acc * s3_ref[:], axis=0).astype(o_ref.dtype)


def matmul_batched(x, q4, s, block_n):
    khalf, n = q4.shape
    k = 2 * khalf
    groups = s.shape[0]
    gs_half = khalf // groups
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    xe, xo = x2[:, 0::2], x2[:, 1::2]
    # (G, 2*gs_half, m): even rows stacked over odd rows, transposed so
    # the contraction dim is dense.
    xe3 = xe.reshape(m, groups, gs_half).transpose(1, 2, 0)
    xo3 = xo.reshape(m, groups, gs_half).transpose(1, 2, 0)
    x3 = jnp.concatenate([xe3, xo3], axis=1)
    p3 = q4.reshape(groups, gs_half, n)
    s3 = s[:, None, :]
    out = pl.pallas_call(
        _kernel_batched,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((groups, 2 * gs_half, m), lambda j: (0, 0, 0)),
            pl.BlockSpec((groups, gs_half, block_n),
                         lambda j: (0, 0, j)),
            pl.BlockSpec((groups, 1, block_n), lambda j: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((m, block_n), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=_INTERPRET,
    )(x3, p3, s3)
    return out



def _time_kernel(fn, x, kk, nn, label=None):
    """Shared scan-loop timing harness: the c + y[0,0]*0 carry keeps a
    data dependency between iterations so XLA cannot hoist the kernel
    out of the scan; dt is per-iteration over 50."""
    @jax.jit
    def loop(x):
        def body(c, _):
            y = fn(x + c)
            return c + y[0, 0].astype(jnp.bfloat16) * 0, y[0, 0]
        return jax.lax.scan(body, jnp.bfloat16(0), None, length=50)[1]

    np.asarray(loop(x))
    t0 = time.perf_counter()
    np.asarray(loop(x))
    dt = (time.perf_counter() - t0) / 50
    gbs = kk * nn / 2 / dt / 1e9
    return dt, gbs


def race(kk, nn, m=64):
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(kk, nn)) * 0.02, jnp.float32)
    x = jnp.asarray(rng.normal(size=(m, kk)), jnp.bfloat16)
    q4 = quantize_int4(w, 128)
    q8 = quantize_int8(w)
    want = np.asarray(int4_matmul(x, q4["q4"], q4["s"]), np.float32)

    def scan_time(fn, label, check=True):
        try:
            if check:
                got = np.asarray(fn(x), np.float32)
                err = np.abs(got - want).max() / (np.abs(want).max())
                assert err < 0.05, f"{label} wrong: {err}"
            dt, gbs = _time_kernel(fn, x, kk, nn)
            print(f"  {label:28s} {dt*1e6:7.0f} us  {gbs:6.0f} GB/s(int4)")
        except Exception as e:  # noqa: BLE001
            print(f"  {label:28s} FAILED: {type(e).__name__}: {e}")

    print(f"shape K={kk} N={nn} m={m}")
    scan_time(lambda xx: int8_matmul(xx, q8["q"], q8["s"]),
              "int8 kernel (ref)", check=False)
    scan_time(lambda xx: int4_matmul(xx, q4["q4"], q4["s"]),
              "int4 unrolled (current)")
    for bn in (128, 256, 512):
        if nn % bn == 0:
            scan_time(lambda xx, b=bn: matmul_repeat(xx, q4["q4"],
                                                     q4["s"], b),
                      f"int4 repeat bn={bn}")
    for bn in (128, 256, 512):
        if nn % bn == 0:
            scan_time(lambda xx, b=bn: matmul_batched(xx, q4["q4"],
                                                      q4["s"], b),
                      f"int4 batched bn={bn}")


def race_one(variant, kk, nn, bn, m=64):
    """Validate + time EXACTLY ONE kernel variant/tile, so a compile
    failure names the one shape that caused it."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(kk, nn)) * 0.02, jnp.float32)
    x = jnp.asarray(rng.normal(size=(m, kk)), jnp.bfloat16)
    q4 = quantize_int4(w, 128)
    # Reference in NUMPY, not int4_matmul: at unvalidated khalf classes
    # the dispatcher would fall back to the UNROLLED Pallas kernel —
    # an uncontrolled never-before-compiled Mosaic kernel on hardware,
    # exactly the one-risk-per-run rule this harness exists to keep.
    packed = np.asarray(q4["q4"]).astype(np.int32)
    low = (packed << 28) >> 28
    high = packed >> 4
    scales = np.asarray(q4["s"], np.float32)
    gs_half = packed.shape[0] // scales.shape[0]
    expanded = np.repeat(scales, gs_half, axis=0)
    x_np = np.asarray(x, np.float32)
    want = (x_np[:, 0::2] @ (low * expanded)
            + x_np[:, 1::2] @ (high * expanded))
    fn = {"repeat": matmul_repeat, "batched": matmul_batched}[variant]
    got = np.asarray(fn(x, q4["q4"], q4["s"], bn), np.float32)
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 0.05, f"wrong numerics: {err}"

    dt, gbs = _time_kernel(
        lambda xx: fn(xx, q4["q4"], q4["s"], bn), x, kk, nn)
    print(f"OK {variant} K={kk} N={nn} bn={bn} khalf={kk // 2}: "
          f"{dt * 1e6:.0f} us  {gbs:.0f} GB/s(int4)")


if __name__ == "__main__":
    import argparse
    parser = argparse.ArgumentParser()
    parser.add_argument("--one", nargs=4,
                        metavar=("VARIANT", "K", "N", "BN"),
                        help="validate+time one variant/tile, e.g. "
                             "--one repeat 8192 1024 128")
    args = parser.parse_args()
    if args.one:
        race_one(args.one[0], int(args.one[1]), int(args.one[2]),
                 int(args.one[3]))
    else:
        race(4096, 14336)
        race(14336, 4096)
        race(4096, 4096)
