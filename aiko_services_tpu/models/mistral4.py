"""Latent-attention mixture-of-experts decoder (``mistral4``; the layer
of DeepSeek-V2/V3): multi-head latent attention (MLA) over a compressed
cache, and a SwiGLU feed-forward of routed experts beside a shared one,
served through the same paged engine as :mod:`.llama`.

Every layer is two pre-norm residual blocks.  Attention, with ``h`` a
head: ``c_q = norm(x W_dq)``, ``[q_nope_h | q_rope_h] = c_q W_uq``,
``[c_kv | k_r] = x W_dkv``, ``c_kv = norm(c_kv)``; ``q_rope_h`` and the
ONE ``k_r`` all heads share are rotated (interleaved pairs, YaRN-blended
frequencies).  What a position leaves in the cache is ``[c_kv | k_r]``
and nothing else: ``kv_lora_rank + qk_rope_head_dim`` values, no head
axis.  Two forms compute the same attention:

* expanded (:func:`forward`): ``k_nope_h = c_kv W_uk_h``, ``v_h = c_kv
  W_uv_h``, scores ``q_nope_h . k_nope_h + q_rope_h . k_r``;
* absorbed (every serving program): ``q~_h = q_nope_h W_uk_h^T``,
  scores ``q~_h . c_kv + q_rope_h . k_r``, output ``(p c_kv) W_uv_h``
  — the cache is read as it lies, by the kernels of
  :mod:`..ops.latent_attention`, and never expanded.

What the engine holds (:func:`init_paged_cache`) is one latent block
pool a layer, ``{"c": (n_blocks, block_size, pool width)}``, where the
pool width is the row's ``rank + rope`` values padded with zeros to
whole 128-lane rows (320 -> 384 at the published widths: the padding
the TPU's tiled layout gives a 320-wide array anyway, made explicit so
that a block is a slice Mosaic can copy).  A row NEEDS ``rank + rope``
values; the zeros are read with it.

The feed-forward is :mod:`.moe`'s layer: softmax over all routed
experts, the ``top_k`` largest renormalised, the dense dispatch over
the experts held here (``experts_held``: a chip's share; the router
keeps its full width), and a SwiGLU shared expert on every row.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.latent_attention import (latent_append, latent_attention_paths,
                                    latent_decode_attention,
                                    latent_prefill_attention,
                                    latent_slice_key_blocks)
from ..ops.paged_attention import decode_kernel_mode
from ..ops.paged_prefill import prefill_kernel_mode
from ..ops.quant import quantize_named_int8
from .llama import (_embed_lookup, _matmul, _serve_scan, rms_norm,
                    scatter_state_rows)
from .moe import MoEConfig, init_moe_params, moe_layer

__all__ = ["Mistral4Config", "CONFIGS", "COUNTERS", "RECURRENT_STATE",
           "UNSUPPORTED", "init_params", "quantize_params", "forward",
           "init_paged_cache", "kv_pool_layers", "kv_geometry",
           "state_bytes_per_slot", "layer_kinds", "attention_paths",
           "slice_key_blocks", "prefill_append_paged",
           "serve_chunk_paged", "serve_chunk_mixed", "scatter_state_rows",
           "cache_row_values"]

RECURRENT_STATE = False
#: Counters a serve chunk returns beside its tokens (no extra sync).
#: ``moe_slice_rows_merged``: prefill-slice rows x layers whose
#: feed-forward rode a decode step's pass over the experts (a mixed
#: chunk's first step; 0 for a chunk with no slice).
COUNTERS = ("moe_pairs", "moe_pairs_here", "moe_experts_hit",
            "moe_slice_rows_merged")

#: What the engine refuses at construction for this module, each with
#: the piece it lacks: ``(what the model has, {feature: missing})``.
UNSUPPORTED = ("a latent block pool", {
    "mesh": "a sharding rule for a pool without a head axis (this "
            "model module has only the single-chip programs)",
    "replica_mesh": "a latent pool under the shard_map engine "
                    "(llama_tp shards a pool on its kv-head axis, and "
                    "a latent row has none)",
    "adapters": "LoRA factors through the latent projections",
    "speculation": "a verify program over a latent pool (the draft "
                   "and verify paths append K/V rows)",
    "host_tier": "host rows sized and restored as latent blocks (the "
                 "tier's staging is checked against K/V pools only)",
    "spill": "latent blocks in the spill store's pool signature",
    "kv_transfer": "a wire format for a latent block (the transfer "
                   "wire carries full-head-width K/V rows and its "
                   "signature is (layers, kv heads, head_dim))",
    "migration": "a latent block chain over the transfer wire",
    "contiguous_layout": "contiguous-cache programs in this model "
                         "module (serve it with PagedContinuousServer)",
})

LANES = 128


@dataclasses.dataclass(frozen=True)
class Mistral4Config:
    vocab_size: int = 1024
    d_model: int = 128
    n_layers: int = 2
    n_heads: int = 4
    q_lora_rank: int = 48
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 16
    v_head_dim: int = 32
    n_experts: int = 16
    moe_top_k: int = 4
    d_ff: int = 64                    # one routed expert's width
    d_shared: int = 64                # the shared expert's width
    routed_scale: float = 1.0
    #: ``(first, count)`` of the routed experts held here (None: all).
    experts_held: Optional[Tuple[int, int]] = None
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    #: YaRN: wavelengths past ``rope_original_max`` are stretched by
    #: ``rope_factor``, with a ramp between ``beta_fast`` and
    #: ``beta_slow`` rotations.  Tiny here so that a test reaches past
    #: the original context.
    rope_factor: float = 8.0
    rope_original_max: int = 64
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    #: Queries at position p are scaled by ``1 + beta * ln(1 + floor(p
    #: / rope_original_max))``.
    llama4_scaling_beta: float = 0.1
    max_seq_len: int = 512
    dtype: Any = jnp.bfloat16
    #: The engine's block accounting asks every config (no window here).
    sliding_window: Optional[int] = None

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def sm_scale(self) -> float:
        """``qk_head_dim ** -0.5 * m ** 2``, ``m`` YaRN's attention
        factor at ``mscale_all_dim`` (the cos and sin carry none:
        ``mscale`` equals it)."""
        m = 1.0
        if self.rope_factor > 1.0:
            m = 0.1 * self.rope_mscale_all_dim * math.log(
                self.rope_factor) + 1.0
        return self.qk_head_dim ** -0.5 * m * m

    @property
    def moe_config(self) -> MoEConfig:
        return MoEConfig(
            d_model=self.d_model, d_ff=self.d_ff,
            n_experts=self.n_experts, top_k=self.moe_top_k,
            capacity_factor=None, dtype=self.dtype, scoring="softmax",
            routed_scale=self.routed_scale, activation="swiglu",
            d_shared=self.d_shared, held=self.experts_held)


CONFIGS: Dict[str, Mistral4Config] = {
    "mistral4_tiny": Mistral4Config(),
    # The same model on the chip that holds experts 4-7 of 16.
    "mistral4_tiny_share": Mistral4Config(experts_held=(4, 4)),
}


def cache_row_values(config: Mistral4Config) -> int:
    """Values a position NEEDS in a layer's cache: ``c_kv`` and ``k_r``."""
    return config.kv_lora_rank + config.qk_rope_head_dim


def _pool_width(config: Mistral4Config) -> int:
    """The cache row padded to whole lane rows."""
    return -(-cache_row_values(config) // LANES) * LANES


def layer_kinds(config: Mistral4Config) -> Dict[str, int]:
    return {"latent_attention": config.n_layers,
            "experts": config.n_layers}


def kv_geometry(config: Mistral4Config, quantize_kv: bool):
    """``(row width, kv heads, dtype)`` of the block pools: one latent
    row a position, no head axis, in the model's float type."""
    if quantize_kv:
        raise ValueError("a latent pool has no int8 layout: its rows "
                         "are the model's float type (quantize_kv)")
    return _pool_width(config), 1, config.dtype


def attention_paths(config: Mistral4Config, block_size: int, chunk: int):
    """``(decode, prefill)`` serving path tags at this geometry."""
    del config, block_size, chunk
    return latent_attention_paths()


def slice_key_blocks(config: Mistral4Config, start: int, width: int,
                     block_size: int) -> int:
    """Latent key blocks x query tiles a prefill slice's attention
    sweeps in one layer (the counter ``prefill_key_blocks``)."""
    del config
    return latent_slice_key_blocks(start, width, block_size)


def state_bytes_per_slot(config: Mistral4Config) -> int:
    return 0


# --------------------------------------------------------------------------- #
# Parameters


def init_params(config: Mistral4Config, key) -> Dict:
    c, dt = config, config.dtype
    d, heads = c.d_model, c.n_heads
    keys = jax.random.split(key, c.n_layers + 2)

    def dense(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * shape[-2] ** -0.5).astype(dt)

    layers = []
    for lk in keys[:c.n_layers]:
        lk = jax.random.split(lk, 7)
        layers.append({
            "attn_norm": jnp.ones((d,), dt),
            "ffn_norm": jnp.ones((d,), dt),
            "q_a": dense(lk[0], (d, c.q_lora_rank)),
            "q_norm": jnp.ones((c.q_lora_rank,), dt),
            "q_b": dense(lk[1], (c.q_lora_rank, heads * c.qk_head_dim)),
            "kv_a": dense(lk[2], (d, cache_row_values(c))),
            "kv_norm": jnp.ones((c.kv_lora_rank,), dt),
            "w_uk": dense(lk[3], (heads, c.kv_lora_rank,
                                  c.qk_nope_head_dim)),
            "w_uv": dense(lk[4], (heads, c.kv_lora_rank, c.v_head_dim)),
            "wo": dense(lk[5], (heads * c.v_head_dim, d)),
            "moe": init_moe_params(c.moe_config, lk[6])})
    return {"embed": jax.random.normal(keys[-2], (c.vocab_size, d)
                                       ).astype(dt),
            "layers": layers,
            "final_norm": jnp.ones((d,), dt),
            "lm_head": dense(keys[-1], (d, c.vocab_size))}


#: The 2-D matrices served int8 weight-only; the router, the per-head
#: ``w_uk`` / ``w_uv`` and the experts (3-D leaves) stay as they are.
_INT8_LEAVES = ("q_a", "q_b", "kv_a", "wo", "shared_gate", "shared_up",
                "shared_down", "embed", "lm_head")


def quantize_params(params, bits: int = 8) -> Dict:
    if bits != 8:
        raise NotImplementedError("int8 weight-only is the one "
                                  "quantized layout of this model")
    return quantize_named_int8(params, _INT8_LEAVES)


# --------------------------------------------------------------------------- #
# Rotary embedding and the two projections


def _inv_freq(config: Mistral4Config):
    """YaRN-blended inverse frequencies ``(rope / 2,)``: a pair whose
    wavelength fits ``beta_fast`` rotations in the original context
    keeps its frequency, one that fits under ``beta_slow`` is divided
    by ``rope_factor``, a linear ramp between."""
    c, dim = config, config.qk_rope_head_dim
    plain = c.rope_theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32)
                             / dim)
    if c.rope_factor <= 1.0:
        return plain

    def pair_of(rotations):
        return dim * math.log(c.rope_original_max
                              / (rotations * 2.0 * math.pi)) \
            / (2.0 * math.log(c.rope_theta))

    low = max(math.floor(pair_of(c.rope_beta_fast)), 0)
    high = min(math.ceil(pair_of(c.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    stretched = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                         / (high - low), 0.0, 1.0)
    return plain / c.rope_factor * stretched + plain * (1.0 - stretched)


def _rotate(x, positions, config: Mistral4Config):
    """Interleaved pairs ``(x[2i], x[2i + 1])`` of the last axis turned
    by ``positions * inv_freq[i]``; ``positions`` broadcasts against
    ``x``'s leading axes.  Float32 inside."""
    angles = positions[..., None].astype(jnp.float32) * _inv_freq(config)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                       axis=-1)
    return turned.reshape(x.shape).astype(x.dtype)


def _query_scale(positions, config: Mistral4Config):
    return 1.0 + config.llama4_scaling_beta * jnp.log1p(jnp.floor(
        positions.astype(jnp.float32) / config.rope_original_max))


def _query_heads(layer, config: Mistral4Config, normed, positions):
    """``normed (..., d)`` at ``positions (...)`` -> ``(q_nope (..., H,
    nope), q_rope (..., H, rope))``, the rope part rotated and both
    carrying the position's query scale."""
    c = config
    c_q = rms_norm(_matmul(normed, layer["q_a"]), layer["q_norm"],
                   c.norm_eps)
    q = _matmul(c_q, layer["q_b"]).reshape(
        normed.shape[:-1] + (c.n_heads, c.qk_head_dim))
    q = q * _query_scale(positions, c)[..., None, None].astype(q.dtype)
    q_rope = _rotate(q[..., c.qk_nope_head_dim:], positions[..., None], c)
    return q[..., :c.qk_nope_head_dim], q_rope


def _latent_rows(layer, config: Mistral4Config, normed, positions):
    """``(c_kv (..., rank) after its norm, k_r (..., rope) rotated)``."""
    c = config
    kv = _matmul(normed, layer["kv_a"])
    c_kv = rms_norm(kv[..., :c.kv_lora_rank], layer["kv_norm"], c.norm_eps)
    return c_kv, _rotate(kv[..., c.kv_lora_rank:], positions, c)


def _pool_rows(config: Mistral4Config, c_kv, k_r):
    """``[c_kv | k_r | 0]``: a position's row as the pool holds it."""
    pad = _pool_width(config) - cache_row_values(config)
    parts = [c_kv, k_r.astype(c_kv.dtype)]
    if pad:
        parts.append(jnp.zeros(c_kv.shape[:-1] + (pad,), c_kv.dtype))
    return jnp.concatenate(parts, axis=-1)


def _absorbed_queries(layer, config: Mistral4Config, q_nope, q_rope):
    """``[q~ | q_rope | 0]`` at the pool's width: the query carried into
    the latent space, ``q~_h = q_nope_h W_uk_h^T``."""
    carried = jnp.einsum("...hn,hrn->...hr", q_nope, layer["w_uk"],
                         preferred_element_type=jnp.float32
                         ).astype(q_nope.dtype)
    return _pool_rows(config, carried, q_rope)


def _attention_out(layer, config: Mistral4Config, latent_out):
    """``(..., H, rank)`` weighted latent rows -> ``(..., d)``."""
    out = jnp.einsum("...hr,hrv->...hv", latent_out, layer["w_uv"],
                     preferred_element_type=jnp.float32
                     ).astype(latent_out.dtype)
    return _matmul(out.reshape(out.shape[:-2] + (-1,)), layer["wo"])


def _attention_expanded(layer, config: Mistral4Config, normed):
    """Causal attention over a whole ``(batch, seq, d)`` sequence in
    the expanded form: per-head keys and values from the latent rows."""
    c = config
    batch, seq, _ = normed.shape
    positions = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32),
                                 (batch, seq))
    q_nope, q_rope = _query_heads(layer, c, normed, positions)
    c_kv, k_r = _latent_rows(layer, c, normed, positions)
    k_nope = jnp.einsum("bsr,hrn->bshn", c_kv, layer["w_uk"],
                        preferred_element_type=jnp.float32)
    values = jnp.einsum("bsr,hrv->bshv", c_kv, layer["w_uv"],
                        preferred_element_type=jnp.float32)
    scores = (jnp.einsum("bqhn,bshn->bhqs", q_nope.astype(jnp.float32),
                         k_nope)
              + jnp.einsum("bqhr,bsr->bhqs", q_rope.astype(jnp.float32),
                           k_r.astype(jnp.float32))) * c.sm_scale
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqs,bshv->bqhv", weights, values).astype(c.dtype)
    return _matmul(out.reshape(batch, seq, -1), layer["wo"])


# --------------------------------------------------------------------------- #
# Attention over the pool


def _attention_decode(layer, config: Mistral4Config, normed, pool_layer,
                      tables, positions):
    """One token a slot: its row appended in place, then the absorbed
    attention over the slot's blocks.  ``normed (S, 1, d)``."""
    c = config
    block_size = pool_layer["c"].shape[1]
    q_nope, q_rope = _query_heads(layer, c, normed[:, 0], positions)
    rows = _pool_rows(c, *_latent_rows(layer, c, normed[:, 0], positions))
    block_ids = jnp.take_along_axis(
        tables, (positions // block_size)[:, None], axis=1)[:, 0]
    use_kernel, interpret = decode_kernel_mode()
    q = _absorbed_queries(layer, c, q_nope, q_rope)
    pool = latent_append(pool_layer["c"], rows, block_ids,
                         positions % block_size, interpret=interpret,
                         use_kernel=use_kernel)
    out = latent_decode_attention(
        q, pool, tables, positions, rank=c.kv_lora_rank,
        sm_scale=c.sm_scale, interpret=interpret, use_kernel=use_kernel)
    return _attention_out(layer, c, out)[:, None], {"c": pool}


def _attention_append(layer, config: Mistral4Config, normed, pool_layer,
                      table, start_index, attend: bool = True):
    """A prefill slice ``normed (1, T, d)`` of the row whose table is
    ``table (table width,)``: its rows written to the row's blocks
    (whole blocks: slices are block-aligned), and its queries attended
    over the ``start_index`` cached positions, shared blocks included,
    and over its own rows.  ``attend=False`` leaves the rows in the
    pool and returns no output: all that a slice nobody asks logits of
    needs from its last layer."""
    c = config
    tokens = normed.shape[1]
    block_size = pool_layer["c"].shape[1]
    positions = start_index + jnp.arange(tokens, dtype=jnp.int32)
    rows = _pool_rows(c, *_latent_rows(layer, c, normed[0], positions))
    block_ids = jax.lax.dynamic_slice_in_dim(
        table, start_index // block_size, tokens // block_size)
    blocks = rows.reshape(tokens // block_size, block_size, -1)
    use_kernel, interpret = prefill_kernel_mode()
    pool = latent_append(pool_layer["c"], blocks, block_ids,
                         interpret=interpret, use_kernel=use_kernel)
    if not attend:
        return None, {"c": pool}
    q_nope, q_rope = _query_heads(layer, c, normed[0], positions)
    q = _absorbed_queries(layer, c, q_nope, q_rope)
    out = latent_prefill_attention(
        q, rows, pool, table, start_index, rank=c.kv_lora_rank,
        sm_scale=c.sm_scale, interpret=interpret, use_kernel=use_kernel)
    return _attention_out(layer, c, out)[None], {"c": pool}


# --------------------------------------------------------------------------- #
# Entry points


def _head(params, config: Mistral4Config, x):
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    return _matmul(x, params["lm_head"]).astype(jnp.float32)


def _feed_forward(layer, config: Mistral4Config, x, rows=None):
    normed = rms_norm(x, layer["ffn_norm"], config.norm_eps)
    out, counts = moe_layer(layer["moe"], normed, config.moe_config,
                            rows=rows)
    return x + out.astype(x.dtype), counts


@functools.partial(jax.jit, static_argnames=("config",))
def forward(params, tokens, config: Mistral4Config):
    """Full-sequence forward, no cache, attention in the EXPANDED form:
    tokens ``(batch, seq)`` -> logits ``(batch, seq, vocab)`` f32."""
    c = config
    x = _embed_lookup(params, tokens, c.dtype)
    for layer in params["layers"]:
        normed = rms_norm(x, layer["attn_norm"], c.norm_eps)
        x = x + _attention_expanded(layer, c, normed).astype(x.dtype)
        x, _ = _feed_forward(layer, c, x)
    return _head(params, c, x)


def init_paged_cache(config: Mistral4Config, n_blocks: int,
                     block_size: int = 16, quantize_kv: bool = False,
                     slots: int = 1) -> list:
    """One latent block pool a layer (``n_blocks`` INCLUDES scratch
    block 0).  Zeros, so the padding lanes are zero from the start."""
    del slots
    width, _, dtype = kv_geometry(config, quantize_kv)
    return [{"c": jnp.zeros((n_blocks, block_size, width), dtype)}
            for _ in range(config.n_layers)]


def kv_pool_layers(pool) -> list:
    """The block pools among what :func:`init_paged_cache` returns:
    all of it."""
    return pool


def _prefill_core(params, tokens, pool, table, start_index,
                  config: Mistral4Config, compute_logits):
    c = config
    if tokens.shape[0] != 1:
        raise ValueError("one row per prefill call")
    start_index = jnp.asarray(start_index, jnp.int32)
    x = _embed_lookup(params, tokens, c.dtype)
    pool = list(pool)
    last = len(params["layers"]) - 1
    for index, layer in enumerate(params["layers"]):
        normed = rms_norm(x, layer["attn_norm"], c.norm_eps)
        # Without logits the last layer has only its rows to leave.
        attend = compute_logits or index < last
        out, pool[index] = _attention_append(layer, c, normed, pool[index],
                                             table, start_index,
                                             attend=attend)
        if attend:
            x = x + out.astype(x.dtype)
            x, _ = _feed_forward(layer, c, x)
    return (_head(params, c, x) if compute_logits else None), pool


def _program(name: str, **jit_options):
    """``jax.jit`` of a function under ``name``: the name a device
    trace and the compile ledger show the program by."""
    def wrap(function):
        function.__name__ = function.__qualname__ = name
        return jax.jit(function, **jit_options)
    return wrap


@_program("prefill_append_paged",
          static_argnames=("config", "compute_logits"),
          donate_argnames=("pool",))
def _prefill_program(params, tokens, pool, table, start_index, config,
                     compute_logits):
    return _prefill_core(params, tokens, pool, table, start_index, config,
                         compute_logits)


def prefill_append_paged(params, tokens, pool, tables, start_index,
                         config: Mistral4Config, lora=None,
                         kv_limit=None, compute_logits: bool = True):
    """Admit a ``(1, K)`` prompt slice at ``start_index`` (whole
    blocks) of the row whose table is ``tables (1, table width)``.
    ``kv_limit`` (the static bound the engine hands the K/V kernels'
    sweep) bounds nothing here, the kernel walks the cached blocks
    alone, so it stays outside the jit: as a static argument it would
    compile one identical program a prompt bucket."""
    del kv_limit
    if lora is not None:
        raise NotImplementedError("no LoRA path in this model module")
    return _prefill_program(params, tokens, pool, tables[0], start_index,
                            config, compute_logits)


def _decode_core(params, token, pool, tables, positions, active,
                 config: Mistral4Config, prefill=None):
    """One token per slot through every layer.  Idle rows write the
    scratch block (``tables`` / ``positions`` already point there).
    Returns ``(logits, pool, int32 (3,) expert counts summed over the
    layers)``.

    ``prefill = (tokens (1, T), table, start_index)`` carries a prefill
    slice through the layers beside the slots' rows.  The two meet in
    no attention (each layer appends and attends the slice's rows, then
    the slots', by the calls they have alone; the slice's slot is idle
    among the decode rows) and share the feed-forward: ONE pass over
    the router, the held experts and the shared expert for the ``T + S``
    rows, so an expert's matrices are read once for both.  Nobody asks
    logits of such a slice, so its last layer only leaves its rows in
    the pool.  The counts stay those of the slots' rows."""
    c = config
    x = _embed_lookup(params, token, c.dtype)
    pool = list(pool)
    counts = jnp.zeros((3,), jnp.int32)
    if prefill is not None:
        slice_tokens, table, start_index = prefill
        if slice_tokens.shape[0] != 1:
            raise ValueError("one row per prefill slice")
        width = slice_tokens.shape[1]
        x_slice = _embed_lookup(params, slice_tokens, c.dtype)
        rows = jnp.concatenate([jnp.zeros((width,), bool), active])
    last = len(params["layers"]) - 1
    for index, layer in enumerate(params["layers"]):
        merge = prefill is not None and index < last
        if prefill is not None:
            normed = rms_norm(x_slice, layer["attn_norm"], c.norm_eps)
            out, pool[index] = _attention_append(
                layer, c, normed, pool[index], table, start_index,
                attend=merge)
            if merge:
                x_slice = x_slice + out.astype(x_slice.dtype)
        normed = rms_norm(x, layer["attn_norm"], c.norm_eps)
        out, pool[index] = _attention_decode(layer, c, normed, pool[index],
                                             tables, positions)
        x = x + out.astype(x.dtype)
        if merge:
            both, layer_counts = _feed_forward(
                layer, c, jnp.concatenate([x_slice[0, :, None], x]),
                rows=rows)
            x_slice, x = both[None, :width, 0], both[width:]
        else:
            x, layer_counts = _feed_forward(layer, c, x, rows=active)
        counts = counts + layer_counts
    return _head(params, c, x), pool, counts


def _serve(params, state, pool, num_steps, config: Mistral4Config,
           eos_id, sampled, rng_key, prefill=None):
    """The decode chunk; with ``prefill`` (see :func:`_decode_core`) its
    first step carries that slice."""
    block_size = pool[0]["c"].shape[1]
    tables = state["tables"]
    slots = tables.shape[0]
    scratch_tables = jnp.zeros_like(tables)
    scratch_positions = jnp.arange(slots, dtype=jnp.int32) % block_size

    def step_core(token, carried, positions, active, prefill=None):
        pool, counts = carried
        write_tables = jnp.where(active[:, None], tables, scratch_tables)
        write_pos = jnp.where(active, positions, scratch_positions)
        logits, pool, step_counts = _decode_core(
            params, token, pool, write_tables, write_pos, active, config,
            prefill=prefill)
        return logits, (pool, counts + step_counts)

    merged = 0
    first_core = None
    if prefill is not None:
        first_core = functools.partial(step_core, prefill=prefill)
        merged = prefill[0].shape[1] * (config.n_layers - 1)
    tokens, emitted, new_state, (pool, counts) = _serve_scan(
        step_core, state, (pool, jnp.zeros((3,), jnp.int32)), num_steps,
        eos_id, sampled, rng_key, first_core=first_core)
    chunk_counters = {
        "moe_pairs": counts[2] * config.moe_top_k,
        "moe_pairs_here": counts[0],
        "moe_experts_hit": counts[1],
        "moe_slice_rows_merged": jnp.int32(merged)}
    return tokens, emitted, new_state, pool, chunk_counters


@functools.partial(jax.jit,
                   static_argnames=("config", "num_steps", "eos_id",
                                    "sampled"),
                   donate_argnames=("pool",))
def serve_chunk_paged(params, state, pool, num_steps,
                      config: Mistral4Config, eos_id: int = -1,
                      sampled: bool = False, rng_key=None,
                      lora_shared=None):
    """``num_steps`` decode steps of every live slot; the contract of
    :func:`.llama.serve_chunk_paged`, plus the chunk's
    :data:`COUNTERS` as a fifth result."""
    if lora_shared is not None:
        raise NotImplementedError("no LoRA path in this model module")
    return _serve(params, state, pool, num_steps, config, eos_id, sampled,
                  rng_key)


@_program("serve_chunk_mixed",
          static_argnames=("config", "num_steps", "eos_id", "sampled"),
          donate_argnames=("pool",))
def _mixed_program(params, state, pool, prefill_tokens, prefill_row,
                   prefill_start, num_steps, config, eos_id, sampled,
                   rng_key):
    prefill_row = jnp.asarray(prefill_row, jnp.int32)
    table = jax.lax.dynamic_index_in_dim(state["tables"], prefill_row,
                                         keepdims=False)
    return _serve(params, state, pool, num_steps, config, eos_id, sampled,
                  rng_key, prefill=(prefill_tokens, table,
                                    jnp.asarray(prefill_start, jnp.int32)))


def serve_chunk_mixed(params, state, pool, prefill_tokens, prefill_row,
                      prefill_start, num_steps, config: Mistral4Config,
                      eos_id: int = -1, sampled: bool = False,
                      rng_key=None, lora_shared=None,
                      prefill_kv_limit=None):
    """One prefill slice of the slot ``prefill_row`` and the decode
    chunk as one program, the slice riding the chunk's first step
    through the experts (:func:`_decode_core`; ``prefill_kv_limit``:
    see :func:`prefill_append_paged`)."""
    del prefill_kv_limit
    if lora_shared is not None:
        raise NotImplementedError("no LoRA path in this model module")
    return _mixed_program(params, state, pool, prefill_tokens, prefill_row,
                          prefill_start, num_steps, config, eos_id,
                          sampled, rng_key)
