"""Plain reference: a pre-norm decoder of grouped-query attention with
rotary positions and an optional sliding window, and a SwiGLU
feed-forward block (Mistral-7B as its paper and ``config.json``
describe it).

float32 ``jax.numpy`` under ``default_matmul_precision("highest")``;
no kernel, no cache, no batching; it imports nothing of the program.
One sequence at a time runs the whole prompt-plus-answer through every
layer; to keep one float32 layer resident, the layer loop is outermost
and the sequences of a check ride through it together.  Attention is
computed a block of queries at a time against all keys.

Published description followed: RMSNorm before each block; rotary
embedding in the rotate-half form over the full head, base
``rope_theta``; key ``j`` is visible to query ``i`` when ``j <= i`` and
``i - j < sliding_window``; ``silu(x W_gate) * (x W_up) W_down``.
Departure: sequences are padded to a multiple of ``PAD`` positions so
that few shapes compile; causality keeps the padding out of every
scored position.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD = 512          # sequence padding quantum == query block
HIGHEST = "highest"


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * weight


def rope(x, positions, theta):
    """x (S, heads, hd), rotate-half convention."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


@functools.partial(jax.jit, static_argnames=("heads", "kv", "window",
                                             "theta", "eps"))
def attention(layer, x, *, heads, kv, window, theta, eps):
    seq, d = x.shape
    hd = d // heads
    h = rms_norm(x, layer["attn_norm"], eps)
    positions = jnp.arange(seq)
    q = rope((h @ layer["wq"]).reshape(seq, heads, hd), positions, theta)
    k = rope((h @ layer["wk"]).reshape(seq, kv, hd), positions, theta)
    v = (h @ layer["wv"]).reshape(seq, kv, hd)
    group = heads // kv

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, PAD, 0)
        qb = qb.reshape(PAD, kv, group, hd)
        scores = jnp.einsum("qkgd,skd->kgqs", qb, k) * hd ** -0.5
        qpos = start + jnp.arange(PAD)[:, None]
        visible = positions[None, :] <= qpos
        if window:
            visible &= qpos - positions[None, :] < window
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        out = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(scores, -1), v)
        return out.reshape(PAD, heads * hd)

    out = jax.lax.map(block, jnp.arange(0, seq, PAD)).reshape(seq, d)
    return x + out @ layer["wo"]


@functools.partial(jax.jit, static_argnames=("eps",))
def dense_ffn(layer, x, *, eps):
    h = rms_norm(x, layer["mlp_norm"], eps)
    return x + (jax.nn.silu(h @ layer["w_gate"]) * (h @ layer["w_up"])) \
        @ layer["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def head(top, rows, *, eps):
    return rms_norm(rows, top["final_norm"], eps) @ top["lm_head"]


def _ffn(cfg, weights, index, layer, x):
    return dense_ffn(layer, x, eps=cfg["rms_norm_eps"])


def run(cfg, weights, sequences, spans, ffn=_ffn):
    """Reference logits.  ``sequences``: token arrays; ``spans``: for
    each, ``(first, stop)`` positions whose next-token logits are
    wanted.  Returns one float32 ``(stop - first, vocab)`` array each.
    ``weights`` gives ``top()`` and ``layer(i)`` as float32."""
    with jax.default_matmul_precision(HIGHEST):
        top = weights.top()
        states = []
        for tokens in sequences:
            padded = np.zeros(-(-len(tokens) // PAD) * PAD, np.int32)
            padded[:len(tokens)] = tokens
            states.append(top["embed"][jnp.asarray(padded)])
        kwargs = dict(heads=cfg["num_attention_heads"],
                      kv=cfg["num_key_value_heads"],
                      window=cfg.get("sliding_window") or 0,
                      theta=float(cfg["rope_theta"]),
                      eps=cfg["rms_norm_eps"])
        for index in range(cfg["num_hidden_layers"]):
            layer = weights.layer(index)
            states = [attention(layer, x, **kwargs) for x in states]
            states = [ffn(cfg, weights, index, layer, x) for x in states]
            del layer
        return [np.asarray(head(top, x[first:stop],
                                eps=cfg["rms_norm_eps"]))
                for x, (first, stop) in zip(states, spans)]
