"""Plain reference: a byte-level pre-norm decoder whose attention keeps
a window of exact keys and ONE SUMMARY ROW for every chunk behind it
(EvaByte 6.5B as its ``config.json`` names it: ``attention_class``
``eva``, ``window_size`` 2048, ``chunk_size`` 16; EVA: Zheng, Yuan,
Wang, Kong, *Efficient Attention via Control Variates*, ICLR 2023).

float32 ``jax.numpy`` under ``default_matmul_precision("highest")``;
no kernel, no cache, no batching; it imports nothing of the program.
The layer loop is outermost and the sequences of a check ride through
it together, so one float32 layer is resident; attention is computed a
block of queries at a time, against its window's keys and every
summary.

The equations.  Bytes ``t = 0 .. n-1``; window ``w(t) = t // W``
(``W = window_size``); chunk ``j = t // C`` (``C = chunk_size``),
``C_j`` its positions that exist; ``s = head_dim ** -0.5``.  For one
layer, ``x`` of ``n`` rows, residual adds in float32
(``fp32_skip_add``):

1. ``h = RMSNorm(x) (1 + g_in)``, eps ``rms_norm_eps``
   (``norm_add_unit_offset``).
2. ``q, k, v = h W_q, h W_k, h W_v`` as ``num_attention_heads`` heads
   each (as many kv heads; no bias, no q/k norm); RoPE (rotate-half,
   base ``rope_theta``, the absolute position ``t``) on ``q`` and ``k``.
3. Per head with learned ``phi, mu`` of ``head_dim`` values, for every
   chunk ``j``: ``a_m = softmax_{m in C_j}(s phi . k_m)``,
   ``k~_j = sum_m a_m k_m + mu``, ``v~_j = sum_m a_m v_m`` (from the
   rotated ``k``).
4. Query ``t`` sees ``E_t = {m : W w(t) <= m <= t}`` exactly and
   ``S_t = {j : C (j + 1) <= W w(t)}`` (every chunk of every earlier
   window) as summaries, under ONE softmax:
   ``o_t = [sum_{E_t} e^{s q_t . k_m} v_m + sum_{S_t} e^{s q_t . k~_j}
   v~_j] / [sum_{E_t} e^{s q_t . k_m} + sum_{S_t} e^{s q_t . k~_j}]``.
5. ``x <- x + o W_o``; ``x <- x + W_down(silu(W_gate h') * W_up h')``,
   ``h' = RMSNorm(x) (1 + g_post)``.
6. After the layers: ``logits = RMSNorm(x) (1 + g_f) W_head`` in
   float32 (``fp32_logits``), ``(n, num_pred_heads, vocab_size)``;
   head 0 is the next byte, head ``i`` the byte ``i + 1`` ahead.

So for ``n <= W`` the layer IS causal softmax attention, and a query
never sees more than ``W + t / C`` rows.

Departures.  (a) Step 3's parameterisation is ASSUMED (the
configuration file's ``assumed`` says so): a learned per-head ``phi``
scoring the chunk's keys and ``mu`` added to the pooled key stand in
for the paper's sampled ``omega_c ~ N(mu_c, I)`` and mean-pooled key;
``s`` on ``phi . k``; RoPE before the summary; the head laid out
``(hidden, num_pred_heads x vocab_size)``.  None changes a tensor's
shape or the rows a query reads.  (b) Sequences are padded to a
multiple of the query block so that few shapes compile; causality and
``S_t`` keep the padding out of every scored position (a last chunk
the sequence leaves partial is in no ``S_t``).  (c) ``run`` returns
head 0's logits, what the served path picks from; ``all_heads=True``
gives all of them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD = 512          # query block, where the window holds whole ones
HIGHEST = "highest"


def rms_norm(x, offset, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + offset)


def rope(x, positions, theta):
    """x (S, heads, hd), rotate-half convention."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def query_block(window: int) -> int:
    return min(PAD, window)


@functools.partial(jax.jit, static_argnames=("heads", "window", "chunk",
                                             "theta", "eps"))
def attention(layer, x, *, heads, window, chunk, theta, eps):
    seq, d = x.shape
    hd = d // heads
    block = query_block(window)
    scale = hd ** -0.5
    h = rms_norm(x, layer["attn_norm"], eps)
    positions = jnp.arange(seq)
    q = rope((h @ layer["wq"]).reshape(seq, heads, hd), positions, theta)
    k = rope((h @ layer["wk"]).reshape(seq, heads, hd), positions, theta)
    v = (h @ layer["wv"]).reshape(seq, heads, hd)
    # Step 3: every chunk's summary (seq is whole chunks).
    chunks = seq // chunk
    kc = k.reshape(chunks, chunk, heads, hd)
    vc = v.reshape(chunks, chunk, heads, hd)
    a = jax.nn.softmax(
        jnp.einsum("jmhd,hd->jmh", kc, layer["phi"]) * scale, axis=1)
    k_sum = jnp.einsum("jmh,jmhd->jhd", a, kc) + layer["mu"]
    v_sum = jnp.einsum("jmh,jmhd->jhd", a, vc)
    # Keys padded so that every window can be sliced whole.
    whole = -(-seq // window) * window
    kp = jnp.pad(k, ((0, whole - seq), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, whole - seq), (0, 0), (0, 0)))

    def one(start):
        first = start // window * window            # W w(t)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        ke = jax.lax.dynamic_slice_in_dim(kp, first, window, 0)
        ve = jax.lax.dynamic_slice_in_dim(vp, first, window, 0)
        qpos = start + jnp.arange(block)[:, None]
        exact = first + jnp.arange(window)[None, :] <= qpos
        behind = jnp.broadcast_to(
            (jnp.arange(chunks)[None, :] + 1) * chunk <= first,
            (block, chunks))
        scores = jnp.concatenate(
            [jnp.einsum("qhd,shd->hqs", qb, ke),
             jnp.einsum("qhd,jhd->hqj", qb, k_sum)], axis=-1) * scale
        visible = jnp.concatenate([exact, behind], axis=-1)
        weights = jax.nn.softmax(
            jnp.where(visible[None], scores, -jnp.inf), axis=-1)
        out = jnp.einsum("hqs,shd->qhd", weights[..., :window], ve) \
            + jnp.einsum("hqj,jhd->qhd", weights[..., window:], v_sum)
        return out.reshape(block, heads * hd)

    out = jax.lax.map(one, jnp.arange(0, seq, block)).reshape(seq, d)
    return x + out @ layer["wo"]


@functools.partial(jax.jit, static_argnames=("eps",))
def dense_ffn(layer, x, *, eps):
    h = rms_norm(x, layer["mlp_norm"], eps)
    return x + (jax.nn.silu(h @ layer["w_gate"]) * (h @ layer["w_up"])) \
        @ layer["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def head(top, rows, *, eps):
    return rms_norm(rows, top["final_norm"], eps) @ top["lm_head"]


def run(cfg, weights, sequences, spans, all_heads: bool = False):
    """Reference logits.  ``sequences``: byte-id arrays; ``spans``: for
    each, ``(first, stop)`` positions whose next-byte logits are wanted.
    Returns one float32 ``(stop - first, vocab)`` array each: head 0's
    (``all_heads``: ``(stop - first, num_pred_heads, vocab)``).
    ``weights`` gives ``top()`` and ``layer(i)`` as float32."""
    window, chunk = cfg["window_size"], cfg["chunk_size"]
    block = query_block(window)
    assert window % block == 0 and block % chunk == 0
    with jax.default_matmul_precision(HIGHEST):
        top = weights.top()
        states = []
        for tokens in sequences:
            padded = np.zeros(-(-len(tokens) // block) * block, np.int32)
            padded[:len(tokens)] = tokens
            states.append(top["embed"][jnp.asarray(padded)])
        kwargs = dict(heads=cfg["num_attention_heads"], window=window,
                      chunk=chunk, theta=float(cfg["rope_theta"]),
                      eps=cfg["rms_norm_eps"])
        for index in range(cfg["num_hidden_layers"]):
            layer = weights.layer(index)
            states = [attention(layer, x, **kwargs) for x in states]
            states = [dense_ffn(layer, x, eps=cfg["rms_norm_eps"])
                      for x in states]
            del layer
        vocab, heads = cfg["vocab_size"], cfg["num_pred_heads"]
        out = []
        for x, (first, stop) in zip(states, spans):
            logits = np.asarray(head(top, x[first:stop],
                                     eps=cfg["rms_norm_eps"]))
            logits = logits.reshape(-1, heads, vocab)
            out.append(logits if all_heads else logits[:, 0])
        return out
