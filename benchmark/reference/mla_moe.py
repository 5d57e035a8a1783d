"""Plain reference: a decoder of multi-head latent attention (MLA) and
routed SwiGLU experts beside a shared one (DeepSeek-V2, arXiv:2405.04434
section 2.1; the ``mistral4`` ``config.json`` keys), as one chip of an
expert-parallel deployment computes it.

float32 ``jax.numpy`` under ``default_matmul_precision("highest")``;
no kernel, no cache, no batching; it imports nothing of the program.
One sequence at a time runs the whole prompt-plus-answer through every
layer, the layer loop outermost so that one float32 layer is resident,
the experts one at a time, attention one block of queries at a time.

Every layer is two pre-norm residual blocks (RMSNorm, ``rms_norm_eps``):

* attention, EXPANDED form, ``h`` a head.  ``x' = norm(x)``; ``c_q =
  norm(x' W_dq)``; ``[q_nope_h | q_rope_h] = c_q W_uq``; ``[c_kv | k_r]
  = x' W_dkv``; ``c_kv = norm(c_kv)``; ``k_nope_h = c_kv W_uk_h``,
  ``v_h = c_kv W_uv_h``.  ``q_rope_h`` and ``k_r`` (one row, shared by
  all heads) are rotated: pair ``i`` is dimensions ``(2i, 2i + 1)``
  (``rope_interleave``), turned by ``p * f_i`` with YaRN's blended
  frequencies ``f_i`` (below).  Scores ``(q_nope_h . k_nope_h + q_rope_h
  . k_r) * scale``, causal softmax, ``o_h = p v_h``, output
  ``concat(o_h) W_o``.
* ``scale = qk_head_dim ** -0.5 * m ** 2``, ``m = 0.1 * mscale_all_dim
  * ln(factor) + 1``; the query of position ``p`` is first multiplied by
  ``1 + llama_4_scaling_beta * ln(1 + floor(p / original_max))``.
* YaRN: ``f_i = theta ** (-2i / rope_dim)``; with ``c(r) = rope_dim *
  ln(original_max / (2 pi r)) / (2 ln theta)``, ``low = floor(c(
  beta_fast))``, ``high = ceil(c(beta_slow))`` (clipped to the pairs),
  ``t_i = clip((i - low) / (high - low), 0, 1)``: ``f_i (1 - t_i) +
  f_i / factor * t_i``.
* feed-forward.  ``g = softmax(x' W_r)`` over ALL routed experts, the
  ``num_experts_per_tok`` largest kept and renormalised to sum to one,
  times ``routed_scaling_factor``; ``sum_e g_e W_down_e (silu(W_gate_e
  x') * W_up_e x')`` over the chosen experts THAT ARE HELD HERE (the
  configuration's share; the other chips' experts add their part
  elsewhere, and nothing stands in for them); plus the shared expert's
  SwiGLU on every row.

Departures: sequences are padded to a multiple of ``PAD`` positions
(of ``QUERIES``, under ``PAD``) so that few shapes compile (causality
keeps the padding out of every scored position); the vision tower is
not built.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PAD = 2048         # sequence padding quantum
QUERIES = 256      # queries attended at a time
HEAD_ROWS = 128    # rows the output head takes at a time
HIGHEST = "highest"


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * weight


def yarn_frequencies(rope: dict, dim: int):
    theta, factor = rope["rope_theta"], rope["factor"]
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1:
        return plain.astype(np.float32)

    def pair(rotations):
        return dim * math.log(rope["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair(rope["beta_fast"])), 0)
    high = min(math.ceil(pair(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (plain * (1 - ramp) + plain / factor * ramp).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    rope = cfg["rope_parameters"]
    m = 1.0
    if rope["factor"] > 1:
        m = 0.1 * rope["mscale_all_dim"] * math.log(rope["factor"]) + 1.0
    return cfg["qk_head_dim"] ** -0.5 * m * m


def rotate(x, angles):
    """Pairs ``(x[..., 2i], x[..., 2i + 1])`` turned by ``angles[...,
    i]``."""
    even, odd = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope_dim", "rank", "v_dim", "eps", "scale", "beta",
    "original_max"))
def attention(layer, x, frequencies, *, heads, nope, rope_dim, rank, v_dim,
              eps, scale, beta, original_max):
    seq = x.shape[0]
    u = rms_norm(x, layer["attn_norm"], eps)
    positions = jnp.arange(seq)
    angles = positions[:, None].astype(jnp.float32) * frequencies
    c_q = rms_norm(u @ layer["q_a"], layer["q_norm"], eps)
    q = (c_q @ layer["q_b"]).reshape(seq, heads, nope + rope_dim)
    q = q * (1.0 + beta * jnp.log1p(jnp.floor(
        positions / original_max)))[:, None, None]
    q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], angles[:, None])
    kv = u @ layer["kv_a"]
    c_kv = rms_norm(kv[:, :rank], layer["kv_norm"], eps)
    k_rope = rotate(kv[:, rank:], angles)                      # (S, rope)
    k_nope = jnp.einsum("sr,hrn->shn", c_kv, layer["w_uk"])
    values = jnp.einsum("sr,hrv->shv", c_kv, layer["w_uv"])

    def block(start):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, start, QUERIES, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, start, QUERIES, 0)
        scores = (jnp.einsum("qhn,shn->hqs", qn, k_nope)
                  + jnp.einsum("qhr,sr->hqs", qr, k_rope)) * scale
        visible = positions[None, :] <= start + jnp.arange(QUERIES)[:, None]
        scores = jnp.where(visible[None], scores, -jnp.inf)
        out = jnp.einsum("hqs,shv->qhv", jax.nn.softmax(scores, -1), values)
        return out.reshape(QUERIES, heads * v_dim)

    out = jax.lax.map(block, jnp.arange(0, seq, QUERIES)).reshape(seq, -1)
    return x + out @ layer["wo"]


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "eps"))
def gates(layer, x, *, top_k, scale, eps):
    """(normed x, (S, E) gates over ALL routed experts, zero off the
    chosen ones)."""
    u = rms_norm(x, layer["ffn_norm"], eps)
    probs = jax.nn.softmax(u @ layer["router"], -1)
    chosen, ids = jax.lax.top_k(probs, top_k)
    chosen = scale * chosen / chosen.sum(-1, keepdims=True)
    dense = jnp.zeros_like(probs)
    dense = dense.at[jnp.arange(x.shape[0])[:, None], ids].set(chosen)
    return u, dense


def swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


@jax.jit
def expert_term(expert, u, gate):
    return swiglu(u, expert["w_gate"], expert["w_up"],
                  expert["w_down"]) * gate[:, None]


@jax.jit
def shared_term(layer, u):
    return swiglu(u, layer["shared_gate"], layer["shared_up"],
                  layer["shared_down"])


def experts(cfg, weights, index, layer, states, held):
    """The feed-forward block over every sequence of ``states``, with
    the routed experts ``held`` (a range of expert numbers) evaluated
    one at a time: one expert's float32 weights are resident, and every
    sequence rides through them."""
    routes = [gates(layer, x, top_k=cfg["num_experts_per_tok"],
                    scale=float(cfg["routed_scaling_factor"]),
                    eps=cfg["rms_norm_eps"]) for x in states]
    totals = [x + shared_term(layer, u) for x, (u, _) in zip(states,
                                                             routes)]
    for which in held:
        expert = weights.expert(index, which)
        totals = [total + expert_term(expert, u, dense[:, which])
                  for total, (u, dense) in zip(totals, routes)]
    return totals


@functools.partial(jax.jit, static_argnames=("eps",))
def head(top, rows, *, eps):
    return rms_norm(rows, top["final_norm"], eps) @ top["lm_head"]


def run(cfg, weights, sequences, spans):
    """Reference logits.  ``sequences``: token arrays; ``spans``: for
    each, ``(first, stop)`` positions whose next-token logits are
    wanted.  Returns one float32 ``(stop - first, vocab)`` array each.
    ``weights`` gives ``top()``, ``layer(i)`` and ``expert(i, e)`` as
    float32."""
    lowest = cfg.get("experts_first", 0)
    held = range(lowest, lowest + cfg["n_routed_experts"])
    eps = cfg["rms_norm_eps"]
    rope = cfg["rope_parameters"]
    frequencies = jnp.asarray(yarn_frequencies(rope,
                                               cfg["qk_rope_head_dim"]))
    with jax.default_matmul_precision(HIGHEST):
        top = weights.top()
        states = []
        for tokens in sequences:
            quantum = PAD if len(tokens) > PAD else QUERIES
            padded = np.zeros(-(-len(tokens) // quantum) * quantum,
                              np.int32)
            padded[:len(tokens)] = tokens
            states.append(top["embed"][jnp.asarray(padded)])
        for index in range(cfg["num_hidden_layers"]):
            layer = weights.layer(index)
            states = [attention(
                layer, x, frequencies, heads=cfg["num_attention_heads"],
                nope=cfg["qk_nope_head_dim"],
                rope_dim=cfg["qk_rope_head_dim"],
                rank=cfg["kv_lora_rank"], v_dim=cfg["v_head_dim"],
                eps=eps, scale=softmax_scale(cfg),
                beta=float(rope["llama_4_scaling_beta"]),
                original_max=int(rope["original_max_position_embeddings"]))
                for x in states]
            states = experts(cfg, weights, index, layer, states, held)
            del layer
        return [_logits(top, x, first, stop, eps)
                for x, (first, stop) in zip(states, spans)]


def _logits(top, x, first, stop, eps):
    """The head over positions ``first .. stop``, their count rounded
    up to whole ``HEAD_ROWS`` so that a span's length is not a shape
    of its own."""
    rows = -(-(stop - first) // HEAD_ROWS) * HEAD_ROWS
    block = jnp.zeros((rows, x.shape[1]), x.dtype).at[
        :stop - first].set(x[first:stop])
    return np.asarray(head(top, block, eps=eps))[:stop - first]
