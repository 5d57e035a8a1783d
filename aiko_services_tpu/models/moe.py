"""Mixture-of-Experts feed-forward layer with expert parallelism (EP).

The reference framework has no tensor math at all (SURVEY.md §2.6); EP
completes this framework's parallelism matrix (dp/tp/pp/sp/ep).  The
design is the standard TPU dispatch/combine formulation (GShard/Switch):
top-k routing builds a ``(tokens, experts, capacity)`` dispatch one-hot,
token→expert transport is two einsums (which XLA lowers to all-to-all
when experts are sharded over the ``ep`` mesh axis), and every expert
runs as one batched FFN — no per-token Python, fully jit/pjit-friendly,
static shapes via the capacity bound.

Tokens overflowing an expert's capacity are dropped (standard capacity-
factor semantics): their combine weight is zero, so they pass through
the residual unchanged.

One layer serves every routed model of the repo; :class:`MoEConfig`
says which one it is:

* **scoring**: ``softmax`` over all experts, the ``top_k`` largest
  renormalised (Mixtral), or ``sigmoid`` scores with the ``top_k``
  picked by ``score + router_bias`` and the picked scores normalised
  and multiplied by ``routed_scale`` (DeepSeek-V3 / Nemotron-H);
* **activation**: ``swiglu`` (three matrices) or ``relu2`` (two:
  ``relu(x W_up) ** 2 W_down``);
* **latent projections** (``d_latent``): routed experts work in a
  narrower width, reached by ``latent_in`` and left by ``latent_out``;
* **shared expert** (``d_shared``): a dense block of the same
  activation on the full width (two matrices under ``relu2``, three
  with a ``shared_gate`` under ``swiglu``), added to the routed result;
* **experts held here** (``held = (first, count)``): the chip's share
  of an expert-parallel deployment.  The router keeps its full width
  and ``top_k``, and the layer computes the part of the result its own
  experts give; what the absent experts would add is left out;
* **capacity** (``capacity_factor``): the GShard dispatch above
  (softmax routing over experts that are all here: Mixtral's), or
  ``None`` for no capacity buffer at all: every held expert sees every
  row and the gate is zero off its route, so no token can be dropped
  (:func:`moe_experts`; at decode the experts' weights, not the
  arithmetic, bound the step).  Two dispatches, one a model, because
  Mixtral's programs stay what they were; which of them could serve
  both is not measured (PERF.md §7).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.quant import (int4_matmul, int8_matmul, is_quantized,
                         is_quantized_int4)

__all__ = ["MoEConfig", "init_moe_params", "moe_ffn", "moe_layer",
           "moe_param_specs", "route", "top_k_gating", "moe_experts",
           "moe_expert_counts"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int = 128
    d_ff: int = 256
    n_experts: int = 8
    top_k: int = 2
    #: ``None``: no capacity buffer, every held expert sees every row.
    capacity_factor: Optional[float] = 1.25
    dtype: Any = jnp.bfloat16
    scoring: str = "softmax"          # or "sigmoid" (+ router_bias)
    routed_scale: float = 1.0
    activation: str = "swiglu"        # or "relu2"
    d_latent: int = 0                 # 0: experts on the full width
    d_shared: int = 0                 # 0: no shared expert
    #: ``(first, count)`` of the routed experts whose weights are here;
    #: ``None``: all of them.
    held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.capacity_factor is not None and (
                self.held or self.scoring != "softmax"
                or self.routed_scale != 1.0):
            raise ValueError(
                "the capacity dispatch routes by softmax over experts "
                "that are all here; a share of the experts (held), "
                "sigmoid scoring or a routed scale take "
                "capacity_factor=None")

    @property
    def n_held(self) -> int:
        return self.held[1] if self.held else self.n_experts

    @property
    def d_expert_in(self) -> int:
        return self.d_latent or self.d_model


def init_moe_params(config: MoEConfig, key) -> Dict:
    kr, kg, ku, kd = jax.random.split(key, 4)
    more = jax.random.split(jax.random.fold_in(key, 1), 5)
    d, f = config.d_model, config.d_ff
    e, din = config.n_held, config.d_expert_in
    dt = config.dtype

    def init(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * shape[-2] ** -0.5).astype(dt)

    params = {
        "router": (jax.random.normal(kr, (d, config.n_experts),
                                     jnp.float32) * d ** -0.5).astype(dt),
        "w_up": init(ku, (e, din, f)),
        "w_down": init(kd, (e, f, din)),
    }
    if config.activation == "swiglu":
        params["w_gate"] = init(kg, (e, din, f))
    if config.scoring == "sigmoid":
        params["router_bias"] = 0.1 * jax.random.normal(
            more[0], (config.n_experts,), jnp.float32)
    if config.d_latent:
        params["latent_in"] = init(more[1], (d, din))
        params["latent_out"] = init(more[2], (din, d))
    if config.d_shared:
        params["shared_up"] = init(more[3], (d, config.d_shared))
        params["shared_down"] = init(more[4], (config.d_shared, d))
        if config.activation == "swiglu":
            params["shared_gate"] = init(jax.random.fold_in(key, 2),
                                         (d, config.d_shared))
    return params


def moe_param_specs(ep_axis: str = "ep", feature_axis=None) -> Dict:
    """Experts shard over the ``ep`` mesh axis — and their per-expert
    feature dim over ``feature_axis`` when given (the TP engine passes
    its tensor axis, so experts shard over BOTH axes of a 2-D
    tp × ep ReplicaMesh).  The router entry here replicates; the TP
    engine's generic output-axis rule shards it instead (both layouts
    are exact — router logits all-gather either way)."""
    return {
        "router": P(),
        "w_gate": P(ep_axis, None, feature_axis),
        "w_up": P(ep_axis, None, feature_axis),
        "w_down": P(ep_axis, None, feature_axis),
    }


def route(logits, config: MoEConfig, bias=None):
    """Router logits ``(T, E)`` over ALL experts -> ``(ids (T, k),
    gates (T, k) f32)``, highest first."""
    logits = logits.astype(jnp.float32)
    if config.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        # The bias steers WHICH experts are picked (load balancing);
        # the gate is the unbiased score.
        choose = scores if bias is None else scores + bias
        _, expert_ids = jax.lax.top_k(choose, config.top_k)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        # Top-k expert ids per token, highest prob first.
        _, expert_ids = jax.lax.top_k(scores, config.top_k)    # (T, k)
    gate = jnp.take_along_axis(scores, expert_ids, axis=-1)     # (T, k)
    # Renormalize over the chosen k (standard top-2 normalization).
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    if config.routed_scale != 1.0:
        gate = gate * config.routed_scale
    return expert_ids, gate


def top_k_gating(logits, top_k: int, capacity: int):
    """Router logits ``(T, E)`` → dispatch ``(T, E, C)`` one-hot and
    combine ``(T, E, C)`` weights (f32).

    Position within each expert's capacity buffer is the token's rank
    among tokens routed to that expert (cumsum order); ranks ≥ capacity
    are dropped.
    """
    tokens, n_experts = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    # Top-k expert ids per token, highest prob first.
    _, expert_ids = jax.lax.top_k(probs, top_k)          # (T, k)
    one_hot = jax.nn.one_hot(expert_ids, n_experts,
                             dtype=jnp.float32)           # (T, k, E)
    # Slot position: rank among all (token, choice) pairs bound for the
    # expert, counted token-major then choice-major.
    flat = one_hot.reshape(tokens * top_k, n_experts)
    position = jnp.cumsum(flat, axis=0) - flat            # (T*k, E)
    position = (position * flat).sum(-1).reshape(tokens, top_k)
    keep = position < capacity
    gate = jnp.take_along_axis(probs, expert_ids, axis=-1)   # (T, k)
    # Renormalize over the chosen k (standard top-2 normalization).
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    gate = jnp.where(keep, gate, 0.0)
    position = jnp.where(keep, position, 0).astype(jnp.int32)
    slot_hot = jax.nn.one_hot(position, capacity, dtype=jnp.float32)
    # (T, k, E, C) → sum over choices k.
    dispatch = jnp.einsum("tke,tkc->tec", one_hot,
                          slot_hot * keep[..., None].astype(jnp.float32))
    combine = jnp.einsum("tke,tkc->tec", one_hot,
                         slot_hot * gate[..., None])
    return dispatch, combine


def _router_logits(params, xt):
    router = params["router"]
    if is_quantized_int4(router):
        return int4_matmul(xt.astype(jnp.float32), router["q4"],
                           router["s"])
    if is_quantized(router):
        # quantize_tree quantizes every 2-D leaf, the router included;
        # the 3-D expert weights stay in the model dtype (weight-only
        # quant targets the big dense matrices, not einsum experts).
        return int8_matmul(xt.astype(jnp.float32), router["q"],
                           router["s"])
    return xt.astype(jnp.float32) @ router.astype(jnp.float32)


def _dense(x, w):
    """A 2-D matrix of the layer, int8 weight-only or plain."""
    if is_quantized(w):
        return int8_matmul(x, w["q"], w["s"])
    return x @ w


def _activate(up, gate=None):
    """f32 in, f32 out: SwiGLU's ``silu(gate) * up`` or ``relu(up)**2``."""
    if gate is not None:
        return jax.nn.silu(gate) * up
    return jnp.square(jax.nn.relu(up))


def _expert_ffn_capacity(params, xt, logits, config: MoEConfig):
    """GShard dispatch: tokens ride one-hot einsums into per-expert
    capacity buffers and back."""
    tokens = xt.shape[0]
    capacity = max(1, int(config.capacity_factor * tokens
                          * config.top_k / config.n_experts))
    dispatch, combine = top_k_gating(logits, config.top_k, capacity)
    # Token → expert slot transport (all-to-all under an ep-sharded mesh).
    expert_in = jnp.einsum("tec,td->ecd",
                           dispatch.astype(xt.dtype), xt)   # (E, C, d)
    up = functools.partial(jnp.einsum, "ecd,edf->ecf", expert_in)
    if "w_gate" in params:
        # SiLU on the gate before the up-projection is traced: the
        # order Mixtral's programs have always had.
        gate = jax.nn.silu(up(params["w_gate"]).astype(jnp.float32))
        hidden = gate * up(params["w_up"]).astype(jnp.float32)
    else:
        hidden = _activate(up(params["w_up"]).astype(jnp.float32))
    expert_out = jnp.einsum("ecf,efd->ecd", hidden.astype(xt.dtype),
                            params["w_down"])              # (E, C, d)
    return jnp.einsum("tec,ecd->td", combine.astype(xt.dtype),
                      expert_out)


@jax.jit
def moe_experts(xt, gates, w_up, w_down, w_gate=None):
    """Every held expert on every row, weighted by ``gates (T, E)``
    (zero off a row's route): ``sum_e gates[:, e] * act(xt W_up[e])
    W_down[e]``, ``act`` being ``relu ** 2``, or SwiGLU where the
    experts have a ``w_gate``.  Matmuls that read each expert's
    weights once; the last contracts experts and features together.  A
    jit of its own so that the device trace shows the experts' work
    under one name."""
    tokens = xt.shape[0]
    experts, _, features = w_up.shape
    up = jnp.einsum("td,edf->tef", xt, w_up,
                    preferred_element_type=jnp.float32)
    if w_gate is not None:
        w_gate = jnp.einsum("td,edf->tef", xt, w_gate,
                            preferred_element_type=jnp.float32)
    hidden = (_activate(up, w_gate) * gates[:, :, None]).astype(xt.dtype)
    return jnp.dot(hidden.reshape(tokens, experts * features),
                   w_down.reshape(experts * features, -1),
                   preferred_element_type=jnp.float32).astype(xt.dtype)


def _held_gates(expert_ids, gate, config: MoEConfig):
    """``(T, E_held)`` f32: a row's gate for each expert held here,
    zero where the expert is not on the row's route."""
    first = config.held[0] if config.held else 0
    # one_hot of an index outside [0, E_held) is all zero: choices
    # that fell on experts of other chips drop out here.
    hot = jax.nn.one_hot(expert_ids - first, config.n_held,
                         dtype=jnp.float32)                 # (T, k, E)
    return jnp.einsum("tke,tk->te", hot, gate)


def _expert_ffn_dense(params, xt, logits, config: MoEConfig, bias):
    """No capacity buffer: ``(out, gates (T, E_held))``."""
    gates = _held_gates(*route(logits, config, bias), config)
    return moe_experts(xt, gates, params["w_up"], params["w_down"],
                       params.get("w_gate")), gates


def moe_expert_counts(gates, rows=None):
    """``int32 (3,)`` from the held gates of one call: token-expert
    choices that fell on held experts, held experts that received a
    token, and rows counted — over the rows ``rows`` marks (all)."""
    chosen = gates > 0
    if rows is not None:
        chosen = chosen & rows[:, None]
        n_rows = rows.sum()
    else:
        n_rows = gates.shape[0]
    return jnp.stack([chosen.sum(), chosen.any(0).sum(),
                      jnp.asarray(n_rows)]).astype(jnp.int32)


def moe_layer(params, x, config: MoEConfig, rows=None):
    """``x (batch, seq, d)`` → ``(out, counts)``: the layer's output
    (same shape, residual NOT included — caller adds) and
    :func:`moe_expert_counts` of the call (``None`` under the capacity
    dispatch, which keeps no per-expert gates)."""
    batch, seq, d = x.shape
    xt = x.reshape(batch * seq, d)
    logits = _router_logits(params, xt)
    bias = params.get("router_bias")
    routed_in = xt
    if config.d_latent:
        routed_in = _dense(xt, params["latent_in"])
    counts = None
    if config.capacity_factor is None:
        out, gates = _expert_ffn_dense(params, routed_in, logits, config,
                                       bias)
        flat_rows = None if rows is None else jnp.broadcast_to(
            rows[:, None], (batch, seq)).reshape(-1)
        counts = moe_expert_counts(gates, flat_rows)
    else:
        out = _expert_ffn_capacity(params, routed_in, logits, config)
    if config.d_latent:
        out = _dense(out.astype(x.dtype), params["latent_out"])
    if config.d_shared:
        gate = params.get("shared_gate")
        if gate is not None:
            gate = _dense(xt, gate).astype(jnp.float32)
        hidden = _activate(
            _dense(xt, params["shared_up"]).astype(jnp.float32), gate)
        out = out + _dense(hidden.astype(x.dtype),
                           params["shared_down"])
    return out.reshape(batch, seq, d), counts


@functools.partial(jax.jit, static_argnames=("config",))
def moe_ffn(params, x, config: MoEConfig):
    """``x (batch, seq, d)`` → the layer's output (same shape, residual
    NOT included — caller adds)."""
    return moe_layer(params, x, config)[0]


def moe_ffn_reference(params, x, config: MoEConfig):
    """Per-token loop oracle (numpy-slow; tests only)."""
    import numpy as np
    batch, seq, d = x.shape
    xt = np.asarray(x, np.float32).reshape(-1, d)
    tokens = xt.shape[0]
    capacity = max(1, int(config.capacity_factor * tokens
                          * config.top_k / config.n_experts))
    logits = xt @ np.asarray(params["router"], np.float32)
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1))
    w_gate = np.asarray(params["w_gate"], np.float32)
    w_up = np.asarray(params["w_up"], np.float32)
    w_down = np.asarray(params["w_down"], np.float32)
    counts = [0] * config.n_experts
    out = np.zeros_like(xt)
    for t in range(tokens):
        ids = np.argsort(-probs[t])[:config.top_k]
        gates = probs[t, ids]
        gates = gates / max(gates.sum(), 1e-9)
        for expert, g in zip(ids, gates):
            if counts[expert] >= capacity:
                continue
            counts[expert] += 1
            h = xt[t] @ w_gate[expert]
            silu = h / (1.0 + np.exp(-h)) * (xt[t] @ w_up[expert])
            out[t] += g * (silu @ w_down[expert])
    return out.reshape(batch, seq, d)
