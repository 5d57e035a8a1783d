"""Plain reference: the same decoder as ``dense_gqa`` with the
feed-forward block replaced by a sparse mixture of SwiGLU experts
(Mixtral of Experts, arXiv:2401.04088, and its ``config.json``).

Published description followed: router logits ``x W_r`` over all
experts, softmax over all experts, the ``num_experts_per_tok`` largest
kept and renormalised to sum to one, output the weighted sum of the
chosen experts' SwiGLU outputs; no token is ever dropped.  Written the
slow way: every expert is evaluated on every token and masked, one
expert's float32 weights resident at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import dense_gqa


@functools.partial(jax.jit, static_argnames=("top_k", "eps"))
def gates(layer, x, *, top_k, eps):
    """(normed x, (S, E) gate weights, zero off the chosen experts)."""
    h = dense_gqa.rms_norm(x, layer["mlp_norm"], eps)
    probs = jax.nn.softmax(h @ layer["router"], -1)
    chosen, ids = jax.lax.top_k(probs, top_k)
    chosen = chosen / chosen.sum(-1, keepdims=True)
    dense = jnp.zeros_like(probs)
    dense = dense.at[jnp.arange(x.shape[0])[:, None], ids].set(chosen)
    return h, dense


@jax.jit
def expert_term(expert, h, gate):
    out = (jax.nn.silu(h @ expert["w_gate"]) * (h @ expert["w_up"])) \
        @ expert["w_down"]
    return out * gate[:, None]


def _ffn(cfg, weights, index, layer, x):
    h, dense = gates(layer, x, top_k=cfg["num_experts_per_tok"],
                     eps=cfg["rms_norm_eps"])
    for which in range(cfg["num_local_experts"]):
        x = x + expert_term(weights.expert(index, which), h,
                            dense[:, which])
    return x


def run(cfg, weights, sequences, spans):
    return dense_gqa.run(cfg, weights, sequences, spans, ffn=_ffn)
