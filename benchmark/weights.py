"""Seeded weights, drawn where they are used.

One integer hash (murmur3's 32-bit finalizer over a counter) gives
every weight of a configuration from ``--seed``: a leaf's values are a
pure function of (seed, leaf id, element index), so the served tree is
made on the device in one jitted call, in the type it is served in,
and the plain reference regenerates any one layer later without being
handed anything the program holds.  No ``jax.random``: a
``jax.random.randint`` over a weight-sized shape cost the TPU compiler
26-96 s per shape (PERF.md, PR 21 finding 7); this is three integer
multiplies in one fusion.

A weight is an int8 draw ``q`` in [-127, 127] times a per-output-channel
f32 scale ``s``: the dequantized values are uniform with the standard
deviation ``fan_in ** -0.5`` of a fan-in-scaled gaussian init, and the
scales differ from channel to channel by up to +-25 %, so the scale
path carries information.  ``bits < 8`` coarsens ``q`` onto
``2 ** bits - 1`` levels inside the same int8 container: the control
of "How correct is decided" (same programs, same speed, lower weight
precision).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

_U = jnp.uint32


def _fmix(x):
    x = x ^ (x >> 16)
    x = x * _U(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * _U(0xC2B2AE35)
    return x ^ (x >> 16)


def seed_words(seed: int):
    """``--seed`` (any whole number, over 32 bits too) as the two uint32
    words the jitted draws take as a runtime argument, so that one
    compiled program serves every seed."""
    seed = int(seed) & (2 ** 64 - 1)
    return jnp.asarray([seed & 0xFFFFFFFF, seed >> 32], jnp.uint32)


def leaf_key(words, leaf_id):
    """Key of one leaf; ``leaf_id`` may be traced (a layer index)."""
    leaf = jnp.asarray(leaf_id).astype(_U)
    return _fmix(words[0] ^ _fmix(words[1] + leaf * _U(0x9E3779B1)
                                  + _U(0x7F4A7C15)))


def _counter(shape):
    index = jnp.zeros(shape, _U)
    stride = 1
    for axis in range(len(shape) - 1, -1, -1):
        index = index + lax.broadcasted_iota(_U, shape, axis) * _U(stride)
        stride *= shape[axis]
    return index


def draw_q(key, shape, bits: int = 8, offset=0):
    """int8 draws in [-127, 127], coarsened to ``bits`` where < 8.
    ``offset`` starts the element counter there: a slice of a larger
    leaf drawn alone (one expert of a 3-D leaf)."""
    assert math.prod(shape) < 2 ** 32
    counter = _counter(shape) + jnp.asarray(offset).astype(_U)
    h = _fmix(counter * _U(0x9E3779B1) + key)
    q = jnp.maximum((h >> 24).astype(jnp.int32) - 128, -127)
    if bits < 8:
        step = 2 ** (8 - bits)
        top = 2 ** (bits - 1) - 1
        q = jnp.clip(jnp.round(q / step), -top, top).astype(jnp.int32) * step
    return q.astype(jnp.int8)


def draw_scale(key, fan_in: int, n_out: int):
    """f32 ``(1, n_out)`` scales: dequantized std ``fan_in ** -0.5``,
    varied +-25 % by channel."""
    h = _fmix(lax.iota(_U, n_out) * _U(0x9E3779B1) + (key ^ _U(0x5BD1E995)))
    u = (h >> 8).astype(jnp.float32) / float(2 ** 24)
    base = math.sqrt(3.0) * fan_in ** -0.5 / 127.0
    return (base * (0.75 + 0.5 * u))[None, :]


def int8_weight(words, leaf_id, shape, bits: int = 8):
    """``{"q": int8 (in, out), "s": f32 (1, out)}``: the program's int8
    weight-only layout."""
    key = leaf_key(words, leaf_id)
    return {"q": draw_q(key, shape, bits),
            "s": draw_scale(key, shape[0], shape[1])}


def float_weight(words, leaf_id, shape, dtype, bits: int = 8):
    """A float leaf ``(..., in, out)`` in ``dtype``: the same draws,
    scaled and rounded to the served type once."""
    key = leaf_key(words, leaf_id)
    q = draw_q(key, shape, bits).astype(jnp.float32)
    return (q * draw_scale(key, shape[-2], shape[-1])).astype(dtype)


def dequantized(weight):
    """f32 view of either layout, exactly the values that are served."""
    if isinstance(weight, dict):
        return weight["q"].astype(jnp.float32) * weight["s"]
    return weight.astype(jnp.float32)
