"""Nemotron-H-architecture hybrid decoder: Mamba-2 state-space layers,
grouped-query attention layers and latent mixture-of-experts layers in
one stack, served through the same paged engine as :mod:`.llama`.

A config's ``pattern`` gives one character per layer (``nemotron_h``'s
``hybrid_override_pattern``): ``M`` Mamba-2, ``*`` attention, ``E``
expert feed-forward.  Every layer is ``x + mixer(rms_norm(x))`` with
ONE mixer; there is no separate MLP sub-block and attention applies no
rotary embedding.

What the engine holds for it (:func:`init_paged_cache`) is a pytree:
``{"kv": [block pool per attention layer], "ssm": [{"conv", "state"}
per Mamba layer]}``.  The block pools are the ones :mod:`.llama` serves
(same kernels, same tables); a Mamba layer keeps, per SLOT, the last
``conv_kernel - 1`` inputs of its causal convolution and a float32
state ``(heads, head_dim, state)``.  Both are donated with the pool and
updated in place.  The state follows three rules the scheduler leans on:

* a prefill slice that starts at position 0 starts from a zero state,
  whatever the slot held before (admission resets nothing by hand);
* a slice advances the state over its first ``valid_len`` tokens only:
  padding of the prompt bucket, and the prompt's LAST token (which the
  first decode step processes, :meth:`_activate_slot`), leave it
  untouched — step size zero, and the window taken at the true length;
* an idle decode row changes nothing, bit for bit.

Prefill computes the recurrence in the chunked (SSD) form, decode one
token at a time; both in float32.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import attention_reference
from ..ops.paged_attention import decode_dispatch, paged_decode_attention
from ..ops.paged_prefill import paged_prefill_attention, prefill_dispatch
from ..ops.quant import quantize_named_int8
from . import llama as _llama
from .llama import (_cached_gqa_attention, _embed_lookup, _matmul,
                    _paged_gather, _paged_write_rows, _paged_write_slab,
                    _serve_scan, rms_norm, scatter_state_rows)
from .moe import MoEConfig, init_moe_params, moe_layer

__all__ = ["NemotronHConfig", "CONFIGS", "COUNTERS", "RECURRENT_STATE",
           "UNSUPPORTED",
           "init_params", "quantize_params", "forward",
           "init_paged_cache", "kv_pool_layers", "kv_geometry",
           "state_bytes_per_slot", "layer_kinds",
           "prefill_append_paged", "serve_chunk_paged",
           "serve_chunk_mixed", "scatter_state_rows", "ssm_update"]

#: The engine refuses, for a model module that says so, what needs a
#: snapshot or a rollback of per-slot state it cannot take yet.
RECURRENT_STATE = True
#: What that is, and per feature the piece it lacks: a copy of the
#: state the engine cannot take yet.
UNSUPPORTED = ("per-slot recurrent state", {
    "mesh": "a sharding rule for the per-slot state (this "
            "model module has only the single-chip programs)",
    "replica_mesh": "a shard_map engine for this model module "
                    "(llama_tp serves Llama-family layers only)",
    "adapters": "LoRA factors through the Mamba and expert "
                "projections",
    "speculation": "a rollback of the recurrent state to the "
                   "last accepted token (a rejected window has "
                   "already advanced it)",
    "prefix_cache": "a snapshot of the recurrent state at "
                    "block boundaries (a block hit has keys "
                    "and values behind it, and no state)",
    "host_tier": "a snapshot of the recurrent state at block "
                 "boundaries to demote with the blocks",
    "spill": "a snapshot of the recurrent state at block "
             "boundaries to spill with the blocks",
    "kv_transfer": "the recurrent state at the segment's end to "
                   "travel with its blocks (a snapshot at block "
                   "boundaries)",
    "migration": "the slot's live recurrent state to travel "
                 "with its block chain",
    "contiguous_layout": "contiguous-cache programs in this "
                         "model module (serve it with "
                         "PagedContinuousServer)",
})
#: Counters a serve chunk returns beside its tokens (no extra sync);
#: the engine adds them to ``server.counters`` when it reads the chunk.
COUNTERS = ("moe_pairs", "moe_pairs_here", "moe_experts_hit")

_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 1024
    d_model: int = 128
    #: One character per layer: M Mamba-2, * attention, E experts.
    pattern: str = "*EMEM"
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 32
    mamba_heads: int = 8
    mamba_head_dim: int = 32
    ssm_groups: int = 2
    ssm_state: int = 16
    conv_kernel: int = 4
    #: Tokens per block of the chunked (SSD) prefill recurrence.
    chunk_size: int = 128
    n_experts: int = 16
    moe_top_k: int = 4
    d_ff: int = 64                    # one routed expert's width
    d_latent: int = 64
    d_shared: int = 128
    routed_scale: float = 2.5
    #: ``(first, count)`` of the routed experts held here (None: all).
    experts_held: Optional[Tuple[int, int]] = None
    norm_eps: float = 1e-5
    max_seq_len: int = 512
    dtype: Any = jnp.bfloat16
    #: The engine's block accounting asks every config (no window here).
    sliding_window: Optional[int] = None

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def moe_config(self) -> MoEConfig:
        return MoEConfig(
            d_model=self.d_model, d_ff=self.d_ff,
            n_experts=self.n_experts, top_k=self.moe_top_k,
            capacity_factor=None, dtype=self.dtype, scoring="sigmoid",
            routed_scale=self.routed_scale, activation="relu2",
            d_latent=self.d_latent, d_shared=self.d_shared,
            held=self.experts_held)


CONFIGS: Dict[str, NemotronHConfig] = {
    # All three layer kinds, two of each recurrent kind, at test size.
    "nemotron_tiny": NemotronHConfig(),
    # The same model on the chip that holds experts 4-7 of 16.
    "nemotron_tiny_share": NemotronHConfig(experts_held=(4, 4)),
}


def layer_kinds(config: NemotronHConfig) -> Dict[str, int]:
    return {"mamba": config.pattern.count("M"),
            "attention": config.pattern.count("*"),
            "experts": config.pattern.count("E")}


def kv_geometry(config: NemotronHConfig, quantize_kv: bool):
    """``(head_dim, kv heads, KV dtype)`` of the attention layers'
    block pools, as the attention dispatch sees them."""
    return (config.head_dim, config.n_kv_heads,
            jnp.int8 if quantize_kv else config.dtype)


# --------------------------------------------------------------------------- #
# Parameters


def init_params(config: NemotronHConfig, key) -> Dict:
    c, dt = config, config.dtype
    d, heads = c.d_model, c.mamba_heads
    keys = jax.random.split(key, c.n_layers + 2)

    def dense(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * shape[0] ** -0.5).astype(dt)

    layers = []
    for kind, lk in zip(c.pattern, keys):
        lk = jax.random.split(lk, 6)
        layer = {"norm": jnp.ones((d,), dt)}
        if kind == "M":
            layer.update(
                in_proj=dense(lk[0], (d, c.d_inner + c.conv_dim + heads)),
                conv_w=(jax.random.normal(lk[1], (c.conv_kernel,
                                                  c.conv_dim))
                        * c.conv_kernel ** -0.5).astype(jnp.float32),
                conv_b=0.1 * jax.random.normal(lk[2], (c.conv_dim,)),
                # softplus(dt_bias) spread over [0.001, 0.1], the
                # published time_step_min .. time_step_max.
                dt_bias=jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
                    lk[3], (heads,), minval=jnp.log(0.001),
                    maxval=jnp.log(0.1))))),
                a_log=jnp.log(jax.random.uniform(lk[4], (heads,),
                                                 minval=1.0, maxval=16.0)),
                d_skip=jnp.ones((heads,), jnp.float32),
                gate_norm=jnp.ones((c.d_inner,), dt),
                out_proj=dense(lk[5], (c.d_inner, d)))
        elif kind == "*":
            width = c.n_heads * c.head_dim
            kv_width = c.n_kv_heads * c.head_dim
            layer.update(wq=dense(lk[0], (d, width)),
                         wk=dense(lk[1], (d, kv_width)),
                         wv=dense(lk[2], (d, kv_width)),
                         wo=dense(lk[3], (width, d)))
        else:
            layer["moe"] = init_moe_params(c.moe_config, lk[0])
        layers.append(layer)
    return {"embed": (jax.random.normal(keys[-2], (c.vocab_size, d))
                      ).astype(dt),
            "layers": layers,
            "final_norm": jnp.ones((d,), dt),
            "lm_head": dense(keys[-1], (d, c.vocab_size))}


#: The 2-D matrices served int8 weight-only; the convolution taps, the
#: router and every vector stay as they are (experts are 3-D leaves).
_INT8_LEAVES = ("in_proj", "out_proj", "wq", "wk", "wv", "wo",
                "latent_in", "latent_out", "shared_up", "shared_down",
                "embed", "lm_head")


def quantize_params(params, bits: int = 8) -> Dict:
    if bits != 8:
        raise NotImplementedError("int8 weight-only is the one "
                                  "quantized layout of this model")

    return quantize_named_int8(params, _INT8_LEAVES)


# --------------------------------------------------------------------------- #
# Mamba-2


def _split_projection(config: NemotronHConfig, zxbcdt):
    d_inner, conv_dim = config.d_inner, config.conv_dim
    return (zxbcdt[..., :d_inner],
            zxbcdt[..., d_inner:d_inner + conv_dim],
            zxbcdt[..., d_inner + conv_dim:])


def _split_conv(config: NemotronHConfig, xbc):
    """Convolved ``xBC (..., conv_dim)`` -> x ``(..., H, P)``, B and C
    ``(..., G, N)``, float32."""
    c = config
    width = c.ssm_groups * c.ssm_state
    lead = xbc.shape[:-1]
    xbc = xbc.astype(jnp.float32)
    return (xbc[..., :c.d_inner].reshape(
                lead + (c.mamba_heads, c.mamba_head_dim)),
            xbc[..., c.d_inner:c.d_inner + width].reshape(
                lead + (c.ssm_groups, c.ssm_state)),
            xbc[..., c.d_inner + width:].reshape(
                lead + (c.ssm_groups, c.ssm_state)))


def _gated_out(layer, config: NemotronHConfig, y, z):
    """``rms_norm over each group of (y * silu(z))``, then out_proj."""
    c = config
    lead = y.shape[:-2]
    gated = y.reshape(lead + (c.d_inner,)) * jax.nn.silu(
        z.astype(jnp.float32))
    grouped = gated.reshape(lead + (c.ssm_groups, -1))
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, -1, keepdims=True) + c.norm_eps)
    normed = grouped.reshape(lead + (c.d_inner,)).astype(c.dtype) \
        * layer["gate_norm"]
    return _matmul(normed, layer["out_proj"])


def _by_group(config: NemotronHConfig, per_head, axis: int = 1):
    """The head axis ``H`` of an array split into ``(G, H / G)``: a
    group's heads share its B and C."""
    groups = config.ssm_groups
    return per_head.reshape(per_head.shape[:axis]
                            + (groups, config.mamba_heads // groups)
                            + per_head.shape[axis + 1:])


def _ssd_chunk(config: NemotronHConfig, x, dt, a, b, c, state):
    """One block of the chunked recurrence, float32 throughout.
    ``x (L, H, P)``, ``dt (L, H)`` (zero where a token does not count),
    ``a (H,)`` negative, ``b``/``c (L, G, N)``, ``state (H, P, N)``.
    Returns ``(y (L, H, P), state after the block)``."""
    length = x.shape[0]
    log_decay = jnp.cumsum(dt * a, axis=0)                   # (L, H)
    # decay[h, l, s] = exp(sum_{s < r <= l} dt_r a): what of token s's
    # input is left at token l; zero above the diagonal.
    span = log_decay.T[:, :, None] - log_decay.T[:, None, :]
    lower = jnp.tril(jnp.ones((length, length), bool))
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, span, 0.0)), 0.0)
    cb = jnp.einsum("lgn,sgn->gls", c, b, precision=_HIGHEST)
    weights = _by_group(config, decay * dt.T[:, None, :], axis=0) \
        * cb[:, None]                                        # (G, Hg, L, S)
    xg = _by_group(config, x)                                # (L, G, Hg, P)
    y = jnp.einsum("gils,sgip->lgip", weights, xg, precision=_HIGHEST)
    # The carried state as every token of the block sees it.
    carried = jnp.einsum("lgn,gipn->lgip", c,
                         _by_group(config, state, axis=0),
                         precision=_HIGHEST)
    y = y + carried * _by_group(config, jnp.exp(log_decay))[..., None]
    # The block's own contribution to the state at its end.
    to_end = jnp.exp(log_decay[-1][None] - log_decay) * dt   # (L, H)
    grown = jnp.einsum("lgip,lgn->gipn",
                       xg * _by_group(config, to_end)[..., None], b,
                       precision=_HIGHEST)
    state = state * jnp.exp(log_decay[-1])[:, None, None] \
        + grown.reshape(state.shape)
    return y.reshape(x.shape), state


def _mamba_sequence(layer, config: NemotronHConfig, u, conv, state,
                    valid_len):
    """A run of tokens of ONE row through a Mamba-2 mixer.  ``u (T, d)``
    normed input; ``conv (K - 1, conv_dim)`` and ``state (H, P, N)``
    carried in; only the first ``valid_len`` tokens advance them.
    Returns ``(out (T, d), conv, state)``."""
    c = config
    tokens = u.shape[0]
    z, xbc, dt = _split_projection(c, _matmul(u, layer["in_proj"]))
    taps = c.conv_kernel
    history = jnp.concatenate([conv.astype(xbc.dtype), xbc], axis=0)
    mixed = sum(history[k:k + tokens].astype(jnp.float32)
                * layer["conv_w"][k] for k in range(taps))
    x, b, cm = _split_conv(c, jax.nn.silu(mixed + layer["conv_b"]))
    # The window as of the true length: the last K-1 inputs that count.
    conv = jax.lax.dynamic_slice_in_dim(history, valid_len, taps - 1,
                                        axis=0).astype(conv.dtype)
    counted = jnp.arange(tokens) < valid_len
    dt = jnp.where(counted[:, None],
                   jax.nn.softplus(dt.astype(jnp.float32)
                                   + layer["dt_bias"]), 0.0)
    a = -jnp.exp(layer["a_log"])
    block = min(c.chunk_size, tokens)
    ys = []
    for start in range(0, tokens, block):
        part = slice(start, start + block)
        y, state = _ssd_chunk(c, x[part], dt[part], a, b[part], cm[part],
                              state)
        ys.append(y)
    y = jnp.concatenate(ys, axis=0) + layer["d_skip"][:, None] * x
    return _gated_out(layer, c, y, z), conv, state


@jax.jit
def ssm_update(state, x, dt, a, b, c, d_skip, active):
    """One decode token per slot: ``h = exp(dt a) h + dt x (x) B`` and
    ``y = h C + D x``, float32.  ``state (S, H, P, N)``, ``x (S, H,
    P)``, ``dt (S, H)``, ``b``/``c (S, G, N)``.  An idle row keeps its
    state bit for bit.  Elementwise on purpose (no matmul precision to
    choose) and a jit of its own, so that the decode scan's largest
    read-and-write has one name."""
    slots, heads = dt.shape
    groups = b.shape[1]
    shape = state.shape
    grouped = state.reshape((slots, groups, heads // groups) + shape[2:])
    decay = jnp.exp(dt * a).reshape(slots, groups, -1)[..., None, None]
    drive = (dt[..., None] * x).reshape(grouped.shape[:-1])[..., None]
    new = grouped * decay + drive * b[:, :, None, None, :]
    y = (new * c[:, :, None, None, :]).sum(-1).reshape(x.shape) \
        + d_skip[:, None] * x
    new = jnp.where(active[:, None, None, None], new.reshape(shape), state)
    return new, y


def _mamba_decode(layer, config: NemotronHConfig, u, ssm, active):
    """One token per slot.  ``u (S, 1, d)``; ``ssm`` the layer's
    ``{"conv" (S, K - 1, conv_dim), "state" (S, H, P, N)}``."""
    c = config
    z, xbc, dt = _split_projection(c, _matmul(u, layer["in_proj"])[:, 0])
    window = jnp.concatenate([ssm["conv"].astype(xbc.dtype),
                              xbc[:, None]], axis=1)          # (S, K, C)
    mixed = (window.astype(jnp.float32)
             * layer["conv_w"][None]).sum(1) + layer["conv_b"]
    x, b, cm = _split_conv(c, jax.nn.silu(mixed))
    dt = jnp.where(active[:, None],
                   jax.nn.softplus(dt.astype(jnp.float32)
                                   + layer["dt_bias"]), 0.0)
    state, y = ssm_update(ssm["state"], x, dt, -jnp.exp(layer["a_log"]),
                          b, cm, layer["d_skip"], active)
    conv = jnp.where(active[:, None, None],
                     window[:, 1:].astype(ssm["conv"].dtype), ssm["conv"])
    return _gated_out(layer, c, y, z)[:, None], \
        {"conv": conv, "state": state}


# --------------------------------------------------------------------------- #
# Attention (no rotary embedding)


def _qkv(layer, config: NemotronHConfig, normed):
    batch, seq, _ = normed.shape
    c = config
    return (_matmul(normed, layer["wq"]).reshape(batch, seq, c.n_heads,
                                                 c.head_dim),
            _matmul(normed, layer["wk"]).reshape(batch, seq,
                                                 c.n_kv_heads, c.head_dim),
            _matmul(normed, layer["wv"]).reshape(batch, seq,
                                                 c.n_kv_heads, c.head_dim))


def _attention_decode(layer, config: NemotronHConfig, normed, pool_layer,
                      tables, positions):
    c = config
    batch, seq, _ = normed.shape
    q, k, v = _qkv(layer, c, normed)
    new_pool = _paged_write_rows(pool_layer, k, v, tables, positions)
    planes = _llama._scale_planes(new_pool)
    q_g = q.reshape(batch, seq, c.n_kv_heads, c.n_heads // c.n_kv_heads,
                    c.head_dim)
    use_kernel, interpret = decode_dispatch(c.head_dim, c.n_kv_heads,
                                            new_pool["k"].dtype)
    if use_kernel:
        out = paged_decode_attention(
            q_g[:, 0], planes["k"], planes["v"], tables, positions,
            ks=planes.get("ks"), vs=planes.get("vs"),
            interpret=interpret)[:, None]
    else:
        out = _cached_gqa_attention(q_g, _paged_gather(planes, tables),
                                    positions[:, None], c.head_dim)
    out = out.reshape(batch, seq, c.n_heads * c.head_dim)
    return _matmul(out, layer["wo"]), new_pool


def _attention_append(layer, config: NemotronHConfig, normed, pool_layer,
                      tables, start_index, kv_limit):
    c = config
    batch, width, _ = normed.shape
    q, k, v = _qkv(layer, c, normed)
    q_g = q.reshape(batch, width, c.n_kv_heads,
                    c.n_heads // c.n_kv_heads, c.head_dim)
    positions_b = jnp.broadcast_to(
        start_index + jnp.arange(width, dtype=jnp.int32), (batch, width))
    use_kernel, interpret = prefill_dispatch(
        c.head_dim, c.n_kv_heads, pool_layer["k"].dtype,
        pool_layer["k"].shape[1], width)
    if use_kernel:
        out, pool_layer = paged_prefill_attention(
            q_g, k, v, pool_layer, tables,
            jnp.broadcast_to(start_index, (batch,)),
            jnp.full((batch,), width, jnp.int32), interpret=interpret,
            kv_limit=kv_limit)
    else:
        pool_layer = _paged_write_slab(pool_layer, k, v, tables,
                                       positions_b)
        out = _cached_gqa_attention(q_g, _paged_gather(pool_layer, tables),
                                    positions_b, c.head_dim)
    out = out.reshape(batch, width, c.n_heads * c.head_dim)
    return _matmul(out, layer["wo"]), pool_layer


# --------------------------------------------------------------------------- #
# Entry points


def _head(params, config: NemotronHConfig, x):
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    return _matmul(x, params["lm_head"]).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("config",))
def forward(params, tokens, config: NemotronHConfig):
    """Full-sequence forward, no cache: tokens ``(batch, seq)`` ->
    logits ``(batch, seq, vocab)`` f32."""
    c = config
    batch, seq = tokens.shape
    x = _embed_lookup(params, tokens, c.dtype)
    conv0 = jnp.zeros((c.conv_kernel - 1, c.conv_dim), c.dtype)
    state0 = jnp.zeros((c.mamba_heads, c.mamba_head_dim, c.ssm_state),
                       jnp.float32)
    for kind, layer in zip(c.pattern, params["layers"]):
        normed = rms_norm(x, layer["norm"], c.norm_eps)
        if kind == "M":
            out = jnp.stack([
                _mamba_sequence(layer, c, normed[row], conv0, state0,
                                seq)[0] for row in range(batch)])
        elif kind == "*":
            q, k, v = _qkv(layer, c, normed)
            group = c.n_heads // c.n_kv_heads
            out = attention_reference(
                q.transpose(0, 2, 1, 3),
                jnp.repeat(k.transpose(0, 2, 1, 3), group, axis=1),
                jnp.repeat(v.transpose(0, 2, 1, 3), group, axis=1),
                causal=True).transpose(0, 2, 1, 3)
            out = _matmul(out.reshape(batch, seq, -1), layer["wo"])
        else:
            out, _ = moe_layer(layer["moe"], normed, c.moe_config)
        x = x + out.astype(x.dtype)
    return _head(params, c, x)


def init_paged_cache(config: NemotronHConfig, n_blocks: int,
                     block_size: int = 16, quantize_kv: bool = False,
                     slots: int = 1) -> Dict:
    """What the engine donates to every serving program: a block pool
    per attention layer (``n_blocks`` INCLUDES scratch block 0) and a
    convolution window and state per Mamba layer per slot."""
    c = config
    kinds = layer_kinds(c)
    return {
        "kv": _llama.init_paged_cache(_kv_view(c), n_blocks, block_size,
                                      quantize_kv=quantize_kv),
        "ssm": [{"conv": jnp.zeros((slots, c.conv_kernel - 1,
                                    c.conv_dim), c.dtype),
                 "state": jnp.zeros((slots, c.mamba_heads,
                                     c.mamba_head_dim, c.ssm_state),
                                    jnp.float32)}
                for _ in range(kinds["mamba"])]}


def _kv_view(config: NemotronHConfig):
    """The attention layers as :mod:`.llama`'s pool helpers size them."""
    return _llama.LlamaConfig(
        n_layers=layer_kinds(config)["attention"], n_heads=config.n_heads,
        n_kv_heads=config.n_kv_heads,
        d_model=config.n_heads * config.head_dim, dtype=config.dtype)


def kv_pool_layers(pool) -> list:
    """The block pools among what :func:`init_paged_cache` returns."""
    return pool["kv"]


def state_bytes_per_slot(config: NemotronHConfig) -> int:
    c = config
    window = (c.conv_kernel - 1) * c.conv_dim * jnp.dtype(c.dtype).itemsize
    state = c.mamba_heads * c.mamba_head_dim * c.ssm_state * 4
    return layer_kinds(c)["mamba"] * (window + state)


def _prefill_core(params, tokens, pool, tables, start_index,
                  config: NemotronHConfig, state_row, valid_len,
                  kv_limit, compute_logits):
    c = config
    start_index = jnp.asarray(start_index, jnp.int32)
    state_row = jnp.asarray(state_row, jnp.int32)
    valid_len = jnp.asarray(valid_len, jnp.int32)
    x = _embed_lookup(params, tokens, c.dtype)
    kv, ssm = list(pool["kv"]), list(pool["ssm"])
    kv_at = ssm_at = 0
    fresh = start_index == 0
    for kind, layer in zip(c.pattern, params["layers"]):
        normed = rms_norm(x, layer["norm"], c.norm_eps)
        if kind == "M":
            held = ssm[ssm_at]
            conv = jax.lax.dynamic_index_in_dim(held["conv"], state_row,
                                                keepdims=False)
            state = jax.lax.dynamic_index_in_dim(held["state"], state_row,
                                                 keepdims=False)
            # A prompt's first slice starts from nothing, whoever had
            # the slot before.
            conv = jnp.where(fresh, jnp.zeros_like(conv), conv)
            state = jnp.where(fresh, jnp.zeros_like(state), state)
            out, conv, state = _mamba_sequence(layer, c, normed[0], conv,
                                               state, valid_len)
            ssm[ssm_at] = {
                "conv": jax.lax.dynamic_update_index_in_dim(
                    held["conv"], conv, state_row, 0),
                "state": jax.lax.dynamic_update_index_in_dim(
                    held["state"], state, state_row, 0)}
            ssm_at += 1
            out = out[None]
        elif kind == "*":
            out, kv[kv_at] = _attention_append(layer, c, normed, kv[kv_at],
                                               tables, start_index,
                                               kv_limit)
            kv_at += 1
        else:
            out, _ = moe_layer(layer["moe"], normed, c.moe_config)
        x = x + out.astype(x.dtype)
    pool = {"kv": kv, "ssm": ssm}
    return (_head(params, c, x) if compute_logits else None), pool


@functools.partial(jax.jit,
                   static_argnames=("config", "kv_limit",
                                    "compute_logits"),
                   donate_argnames=("pool",))
def prefill_append_paged(params, tokens, pool, tables, start_index,
                         config: NemotronHConfig, lora=None,
                         kv_limit=None, compute_logits: bool = True,
                         state_row=0, valid_len=None):
    """Admit a ``(1, K)`` prompt slice of the slot ``state_row``: its
    K/V append to the row's blocks as in :mod:`.llama`, and its first
    ``valid_len`` tokens (all, when None) advance the slot's recurrent
    state from where the previous slice left it (from zero at
    ``start_index`` 0)."""
    if lora is not None:
        raise NotImplementedError("no LoRA path in this model module")
    if tokens.shape[0] != 1:
        raise ValueError("one row per prefill call: the state is the "
                         "slot's")
    if valid_len is None:
        valid_len = tokens.shape[1]
    return _prefill_core(params, tokens, pool, tables, start_index,
                         config, state_row, valid_len, kv_limit,
                         compute_logits)


def _decode_core(params, token, pool, tables, positions, active,
                 config: NemotronHConfig):
    """One token per slot through every layer.  Idle rows write K/V to
    the scratch block (``tables``/``positions`` already point there)
    and leave their recurrent state alone.  Returns ``(logits, pool,
    int32 (3,) expert counts summed over the E layers)``."""
    c = config
    x = _embed_lookup(params, token, c.dtype)
    kv, ssm = list(pool["kv"]), list(pool["ssm"])
    kv_at = ssm_at = 0
    counts = jnp.zeros((3,), jnp.int32)
    for kind, layer in zip(c.pattern, params["layers"]):
        normed = rms_norm(x, layer["norm"], c.norm_eps)
        if kind == "M":
            out, ssm[ssm_at] = _mamba_decode(layer, c, normed,
                                             ssm[ssm_at], active)
            ssm_at += 1
        elif kind == "*":
            out, kv[kv_at] = _attention_decode(layer, c, normed,
                                               kv[kv_at], tables,
                                               positions)
            kv_at += 1
        else:
            out, layer_counts = moe_layer(layer["moe"], normed,
                                          c.moe_config, rows=active)
            counts = counts + layer_counts
        x = x + out.astype(x.dtype)
    return _head(params, c, x), {"kv": kv, "ssm": ssm}, counts


def _serve(params, state, pool, num_steps, config: NemotronHConfig,
           eos_id, sampled, rng_key):
    block_size = pool["kv"][0]["k"].shape[1]
    tables = state["tables"]
    slots = tables.shape[0]
    scratch_tables = jnp.zeros_like(tables)
    scratch_positions = jnp.arange(slots, dtype=jnp.int32) % block_size

    def step_core(token, carried, positions, active):
        pool, counts = carried
        write_tables = jnp.where(active[:, None], tables, scratch_tables)
        write_pos = jnp.where(active, positions, scratch_positions)
        logits, pool, step_counts = _decode_core(
            params, token, pool, write_tables, write_pos, active, config)
        return logits, (pool, counts + step_counts)

    scan_pool = dict(pool, kv=_llama._scan_scale_rows(pool["kv"],
                                                      _kv_view(config)))
    tokens, emitted, new_state, (scan_pool, counts) = _serve_scan(
        step_core, state, (scan_pool, jnp.zeros((3,), jnp.int32)),
        num_steps, eos_id, sampled, rng_key)
    pool = dict(scan_pool, kv=_llama._rest_scale_planes(scan_pool["kv"]))
    chunk_counters = {
        "moe_pairs": counts[2] * config.moe_top_k,
        "moe_pairs_here": counts[0],
        "moe_experts_hit": counts[1]}
    return tokens, emitted, new_state, pool, chunk_counters


@functools.partial(jax.jit,
                   static_argnames=("config", "num_steps", "eos_id",
                                    "sampled"),
                   donate_argnames=("pool",))
def serve_chunk_paged(params, state, pool, num_steps,
                      config: NemotronHConfig, eos_id: int = -1,
                      sampled: bool = False, rng_key=None,
                      lora_shared=None):
    """``num_steps`` decode steps of every live slot; the contract of
    :func:`.llama.serve_chunk_paged`, plus the chunk's
    :data:`COUNTERS` as a fifth result."""
    if lora_shared is not None:
        raise NotImplementedError("no LoRA path in this model module")
    return _serve(params, state, pool, num_steps, config, eos_id, sampled,
                  rng_key)


@functools.partial(jax.jit,
                   static_argnames=("config", "num_steps", "eos_id",
                                    "sampled", "prefill_kv_limit"),
                   donate_argnames=("pool",))
def serve_chunk_mixed(params, state, pool, prefill_tokens, prefill_row,
                      prefill_start, num_steps, config: NemotronHConfig,
                      eos_id: int = -1, sampled: bool = False,
                      rng_key=None, lora_shared=None,
                      prefill_kv_limit=None, state_row=None,
                      valid_len=None):
    """One prefill slice of the slot ``prefill_row`` (its state carried
    as in :func:`prefill_append_paged`), then the decode chunk.  The
    engine hands both entry points the same slice arguments; the
    slice's state is the slot's, so ``state_row`` says nothing new."""
    if lora_shared is not None:
        raise NotImplementedError("no LoRA path in this model module")
    prefill_row = jnp.asarray(prefill_row, jnp.int32)
    tables_row = jax.lax.dynamic_slice_in_dim(state["tables"],
                                              prefill_row, 1, axis=0)
    del state_row
    if valid_len is None:
        valid_len = prefill_tokens.shape[1]
    _, pool = _prefill_core(params, prefill_tokens, pool, tables_row,
                            prefill_start, config, prefill_row,
                            valid_len, prefill_kv_limit, False)
    return _serve(params, state, pool, num_steps, config, eos_id, sampled,
                  rng_key)
