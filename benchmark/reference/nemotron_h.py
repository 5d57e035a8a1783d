"""Plain reference: a ``nemotron_h`` stack of Mamba-2, attention and
latent mixture-of-experts layers (NVIDIA Nemotron-H / Nemotron 3, the
``config.json`` keys and the ``nemotron_h`` modelling code's layer
equations), as one chip of an expert-parallel deployment computes it.

float32 ``jax.numpy`` under ``default_matmul_precision("highest")``;
no kernel, no cache, no batching, no chunked scan; it imports nothing
of the program.  One sequence at a time runs the whole prompt-plus-
answer through every layer, the layer loop outermost so that one
float32 layer is resident, and an expert layer one expert at a time.

Every layer is ``x + mixer(rms_norm(x))``, one mixer a layer, the kind
from the configuration's pattern:

* ``M``, Mamba-2.  ``[z | xBC | dt] = u W_in``; ``xBC`` through a
  causal depthwise convolution of ``conv_kernel`` taps plus bias, then
  silu; ``xBC`` splits into ``x`` (heads x head_dim) and ``B``, ``C``
  (groups x state, a group serves heads / groups heads); ``dt =
  softplus(dt + dt_bias)``; ``A = -exp(A_log)``; per head ``h_t =
  exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t`` and ``y_t = h_t C_t + D
  x_t``, written as that recurrence, one token after another; then
  ``rms_norm over each group of d_inner / groups channels of (y *
  silu(z))`` times its weight, and ``W_out``.
* ``*``, attention.  Grouped queries, causal softmax(q k / sqrt(head
  dim)), ``o_proj``; no bias and NO rotary embedding (``nemotron_h``
  attention applies none).
* ``E``, latent experts.  ``s = sigmoid(u W_r)`` over ALL routed
  experts; the ``num_experts_per_tok`` largest of ``s + bias`` are
  chosen (one group); gates ``routed_scaling_factor * s / sum of the
  chosen s``; ``l = u W_dn``; the routed result is ``(sum_k g_k W2_k
  relu(W1_k l) ** 2) W_up`` over the chosen experts THAT ARE HELD HERE
  (the configuration's share; the other chips' experts add their part
  elsewhere, and nothing stands in for them); plus the shared expert
  ``W2s relu(W1s u) ** 2`` on the full width.

Departures: sequences are padded to a multiple of ``PAD`` positions so
that few shapes compile (causality and the recurrence's direction keep
the padding out of every scored position); the multi-token-prediction
head is not built (plain serving does not run it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD = 512          # sequence padding quantum == query block
HEAD_ROWS = 256    # rows the output head takes at a time
HIGHEST = "highest"


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * weight


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


@functools.partial(jax.jit, static_argnames=("heads", "head_dim",
                                             "groups", "state", "eps"))
def mamba(layer, x, *, heads, head_dim, groups, state, eps):
    seq = x.shape[0]
    d_inner, width = heads * head_dim, groups * state
    u = rms_norm(x, layer["norm"], eps)
    zxbcdt = u @ layer["in_proj"]
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:2 * d_inner + 2 * width]
    dt = jax.nn.softplus(zxbcdt[:, 2 * d_inner + 2 * width:]
                         + layer["dt_bias"])                  # (S, H)
    taps = layer["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc])
    # Tap k weighs the input taps - 1 - k positions back.
    xbc = jax.nn.silu(sum(padded[k:k + seq] * layer["conv_w"][k]
                          for k in range(taps)) + layer["conv_b"])
    xs = xbc[:, :d_inner].reshape(seq, heads, head_dim)
    per_group = heads // groups
    bs = jnp.repeat(xbc[:, d_inner:d_inner + width].reshape(
        seq, groups, state), per_group, axis=1)                # (S, H, N)
    cs = jnp.repeat(xbc[:, d_inner + width:].reshape(
        seq, groups, state), per_group, axis=1)
    a = -jnp.exp(layer["a_log"])

    def token(h, inputs):
        x_t, b_t, c_t, dt_t = inputs
        h = jnp.exp(dt_t * a)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        y_t = (h * c_t[:, None, :]).sum(-1) \
            + layer["d_skip"][:, None] * x_t
        return h, y_t

    _, y = jax.lax.scan(token, jnp.zeros((heads, head_dim, state)),
                        (xs, bs, cs, dt))
    gated = (y.reshape(seq, d_inner) * jax.nn.silu(z)).reshape(
        seq, groups, d_inner // groups)
    gated = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + eps)
    return x + (gated.reshape(seq, d_inner) * layer["gate_norm"]) \
        @ layer["out_proj"]


@functools.partial(jax.jit, static_argnames=("heads", "kv", "head_dim",
                                             "eps"))
def attention(layer, x, *, heads, kv, head_dim, eps):
    seq = x.shape[0]
    u = rms_norm(x, layer["norm"], eps)
    q = (u @ layer["wq"]).reshape(seq, heads, head_dim)
    k = (u @ layer["wk"]).reshape(seq, kv, head_dim)
    v = (u @ layer["wv"]).reshape(seq, kv, head_dim)
    group = heads // kv
    positions = jnp.arange(seq)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, PAD, 0)
        qb = qb.reshape(PAD, kv, group, head_dim)
        scores = jnp.einsum("qkgd,skd->kgqs", qb, k) * head_dim ** -0.5
        visible = positions[None, :] <= start + jnp.arange(PAD)[:, None]
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        out = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(scores, -1), v)
        return out.reshape(PAD, heads * head_dim)

    out = jax.lax.map(block, jnp.arange(0, seq, PAD)).reshape(seq, -1)
    return x + out @ layer["wo"]


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "eps"))
def gates(layer, x, *, top_k, scale, eps):
    """(normed x, latent input, (S, E) gates over ALL routed experts,
    zero off the chosen ones)."""
    u = rms_norm(x, layer["norm"], eps)
    scores = jax.nn.sigmoid(u @ layer["router"])
    _, ids = jax.lax.top_k(scores + layer["router_bias"], top_k)
    chosen = jnp.take_along_axis(scores, ids, -1)
    chosen = scale * chosen / chosen.sum(-1, keepdims=True)
    dense = jnp.zeros_like(scores)
    dense = dense.at[jnp.arange(x.shape[0])[:, None], ids].set(chosen)
    return u, u @ layer["latent_in"], dense


@jax.jit
def expert_term(expert, latent, gate):
    return (relu2(latent @ expert["w_up"]) @ expert["w_down"]) \
        * gate[:, None]


@jax.jit
def experts_out(layer, x, u, routed):
    shared = relu2(u @ layer["shared_up"]) @ layer["shared_down"]
    return x + routed @ layer["latent_out"] + shared


def experts(cfg, weights, index, layer, states, held):
    """An ``E`` layer over every sequence of ``states``, with the
    routed experts ``held`` (a range of expert numbers) evaluated one
    at a time: one expert's float32 weights are resident, and every
    sequence rides through them."""
    routes = [gates(layer, x, top_k=cfg["num_experts_per_tok"],
                    scale=float(cfg["routed_scaling_factor"]),
                    eps=cfg["norm_eps"]) for x in states]
    routed = [jnp.zeros_like(latent) for _, latent, _ in routes]
    for which in held:
        expert = weights.expert(index, which)
        routed = [total + expert_term(expert, latent, dense[:, which])
                  for total, (_, latent, dense) in zip(routed, routes)]
    return [experts_out(layer, x, u, total)
            for x, (u, _, _), total in zip(states, routes, routed)]


@functools.partial(jax.jit, static_argnames=("eps",))
def head(top, rows, *, eps):
    return rms_norm(rows, top["final_norm"], eps) @ top["lm_head"]


def run(cfg, weights, sequences, spans):
    """Reference logits.  ``sequences``: token arrays; ``spans``: for
    each, ``(first, stop)`` positions whose next-token logits are
    wanted.  Returns one float32 ``(stop - first, vocab)`` array each.
    ``weights`` gives ``top()``, ``layer(i)`` and ``expert(i, e)`` as
    float32 and ``kept``, the pattern of the layers it holds."""
    lowest = cfg.get("experts_first", 0)
    held = range(lowest, lowest + cfg["n_routed_experts"])
    eps = cfg["norm_eps"]
    with jax.default_matmul_precision(HIGHEST):
        top = weights.top()
        states = []
        for tokens in sequences:
            padded = np.zeros(-(-len(tokens) // PAD) * PAD, np.int32)
            padded[:len(tokens)] = tokens
            states.append(top["embed"][jnp.asarray(padded)])
        for index, kind in enumerate(weights.kept):
            layer = weights.layer(index)
            if kind == "M":
                states = [mamba(
                    layer, x, heads=cfg["mamba_num_heads"],
                    head_dim=cfg["mamba_head_dim"], groups=cfg["n_groups"],
                    state=cfg["ssm_state_size"], eps=eps)
                    for x in states]
            elif kind == "*":
                states = [attention(
                    layer, x, heads=cfg["num_attention_heads"],
                    kv=cfg["num_key_value_heads"],
                    head_dim=cfg["head_dim"], eps=eps) for x in states]
            else:
                states = experts(cfg, weights, index, layer, states, held)
            del layer
        return [_logits(top, x, first, stop, eps)
                for x, (first, stop) in zip(states, spans)]


def _logits(top, x, first, stop, eps):
    """The head over positions ``first .. stop``, their count rounded
    up to whole ``HEAD_ROWS`` so that a span's length is not a shape
    of its own (answers come in a hundred lengths)."""
    rows = -(-(stop - first) // HEAD_ROWS) * HEAD_ROWS
    block = jnp.zeros((rows, x.shape[1]), x.dtype).at[
        :stop - first].set(x[first:stop])
    return np.asarray(head(top, block, eps=eps))[:stop - first]
