"""Llama-3-architecture decoder-only transformer, TPU-first.

The flagship model family for the llm_chat workload (the reference calls
an external Ollama llama3.1 over HTTP, ``examples/llm/elements_llm.py:
191-220``; here the model *is* the framework's).  Pure functional JAX:
parameters are a pytree dict, the forward is jit/pjit-friendly, and every
parameter carries a logical sharding spec so the same code runs single-
chip or TP/DP-sharded over a mesh.

Architecture (Llama 3): RMSNorm pre-norm, rotary position embeddings,
grouped-query attention, SwiGLU MLP, untied LM head, bfloat16 params with
f32 layernorm/softmax accumulation.  Prefill uses the Pallas flash
attention kernel; single-token decode attends over a preallocated KV
cache (dense dot — one query row doesn't need flash).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.attention import attention_reference, flash_attention
from ..ops.paged_attention import (cached_gqa_attention,
                                   contiguous_block_size,
                                   decode_append_dispatch,
                                   decode_dispatch,
                                   decode_scale_row,
                                   paged_decode_append,
                                   paged_decode_attention)
from ..ops.paged_prefill import (paged_prefill_attention,
                                 paged_verify_attention,
                                 prefill_dispatch, verify_dispatch)
from ..ops.quant import (_unpack_int4, int4_matmul, int8_matmul,
                         is_quantized, is_quantized_int4, quantize_tree)

__all__ = ["LlamaConfig", "init_params", "forward",
           "forward_sequence_parallel", "init_cache",
           "decode_step", "generate_tokens", "prefill", "param_specs",
           "quantize_params", "random_quantized_params",
           "quantized_param_specs", "prefill_sequence_parallel",
           "pipeline_forward", "stack_pipeline_params",
           "decode_chunk_ragged", "prefill_chunk", "sample_logits",
           "init_paged_cache", "decode_chunk_paged",
           "serve_chunk_ragged", "serve_chunk_paged",
           "serve_chunk_mixed", "prefill_append_paged",
           "verify_chunk_paged",
           "paged_insert_prefix", "paged_scatter_blocks",
           "paged_gather_blocks", "complete", "CONFIGS",
           "RECURRENT_STATE", "COUNTERS", "UNSUPPORTED", "kv_geometry",
           "kv_pool_layers", "scatter_state_rows",
           "state_bytes_per_slot", "layer_kinds"]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32_000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4
    d_ff: int = 1376
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    #: > 0 switches the MLP to a mixture-of-experts (Mixtral-class);
    #: experts shard over the "ep" mesh axis.
    n_experts: int = 0
    moe_top_k: int = 2
    #: Capacity-based token dropping makes routing batch-dependent (a
    #: dropped token depends on its neighbors — standard GShard
    #: semantics).  cf >= n_experts/top_k guarantees no drops, which
    #: keeps decode exactly consistent with full-sequence forward.
    moe_capacity_factor: float = 2.0
    #: Mistral-style sliding-window attention: each position attends to
    #: at most this many most-recent positions (None = full causal).
    #: Long-context prefill cost becomes O(seq·window) via two-sided
    #: block skipping in the flash kernel.
    sliding_window: Optional[int] = None
    #: Llama-3.1-style RoPE frequency rescaling as (factor,
    #: low_freq_factor, high_freq_factor, original_max_position
    #: embeddings) — a tuple so the config stays hashable for jit.
    #: None = plain theta^-2k/d frequencies (Llama-3-8B and earlier).
    rope_scaling: Optional[Tuple[float, float, float, int]] = None

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def moe_config(self):
        from .moe import MoEConfig
        return MoEConfig(d_model=self.d_model, d_ff=self.d_ff,
                         n_experts=self.n_experts, top_k=self.moe_top_k,
                         capacity_factor=self.moe_capacity_factor,
                         dtype=self.dtype)


#: Named configs: tiny/small for tests on one chip, the real ones
#: for parity with BASELINE.json targets.
CONFIGS: Dict[str, LlamaConfig] = {
    "tiny": LlamaConfig(vocab_size=1024, d_model=128, n_layers=2,
                        n_heads=4, n_kv_heads=2, d_ff=352,
                        max_seq_len=512),
    # TP-shardable test config: every sharded dim (kv heads, q heads,
    # d_model, d_ff, vocab) divides by 8, so one config exercises
    # TP=1/2/4/8 on the virtual CPU mesh; GQA group of 2 keeps the
    # grouped-head slicing honest.
    "tiny_tp": LlamaConfig(vocab_size=1024, d_model=128, n_layers=2,
                           n_heads=16, n_kv_heads=8, d_ff=352,
                           max_seq_len=512),
    "small": LlamaConfig(vocab_size=32_000, d_model=1024, n_layers=8,
                         n_heads=16, n_kv_heads=8, d_ff=2816,
                         max_seq_len=2048),
    "1b": LlamaConfig(vocab_size=128_256, d_model=2048, n_layers=16,
                      n_heads=32, n_kv_heads=8, d_ff=8192,
                      max_seq_len=8192),
    "llama3_8b": LlamaConfig(vocab_size=128_256, d_model=4096,
                             n_layers=32, n_heads=32, n_kv_heads=8,
                             d_ff=14_336, max_seq_len=8192),
    "llama3_70b": LlamaConfig(vocab_size=128_256, d_model=8192,
                              n_layers=80, n_heads=64, n_kv_heads=8,
                              d_ff=28_672, max_seq_len=8192),
    "moe_tiny": LlamaConfig(vocab_size=1024, d_model=128, n_layers=2,
                            n_heads=4, n_kv_heads=2, d_ff=352,
                            max_seq_len=512, n_experts=4),
    # 8-expert test config: exercises every tp × ep ReplicaMesh on the
    # virtual 8-device mesh (ep up to 8); cf=4.0 = E/k, drop-free.
    "moe_tiny8": LlamaConfig(vocab_size=1024, d_model=128, n_layers=2,
                             n_heads=4, n_kv_heads=2, d_ff=352,
                             max_seq_len=512, n_experts=8,
                             moe_capacity_factor=4.0),
    # Single-chip MoE config (~0.6 B params, int8 ≈ 0.6 GB);
    # cf=4.0 = E/k keeps decode drop-free (see moe_capacity_factor).
    "moe_small": LlamaConfig(vocab_size=32_000, d_model=1024,
                             n_layers=8, n_heads=16, n_kv_heads=8,
                             d_ff=2816, max_seq_len=2048, n_experts=8,
                             moe_capacity_factor=4.0),
    # cf=4.0 = n_experts/top_k: the no-drop bound, so cached decode stays
    # exactly consistent with full-sequence forward (see moe_capacity_factor).
    "mixtral_8x7b": LlamaConfig(vocab_size=32_000, d_model=4096,
                                n_layers=32, n_heads=32, n_kv_heads=8,
                                d_ff=14_336, max_seq_len=32_768,
                                rope_theta=1e6, n_experts=8,
                                moe_capacity_factor=4.0),
    # Mistral-7B-v0.1-class: sliding-window attention (4096).  PREFILL
    # cost is O(seq*window) via the flash kernel's two-sided block
    # skipping; decode masks out-of-window keys but keeps the full
    # cache resident (no rolling KV buffer yet), so decode memory stays
    # O(max_seq_len).
    "mistral_7b": LlamaConfig(vocab_size=32_000, d_model=4096,
                              n_layers=32, n_heads=32, n_kv_heads=8,
                              d_ff=14_336, max_seq_len=32_768,
                              rope_theta=10_000.0, sliding_window=4096),
    "mistral_tiny": LlamaConfig(vocab_size=1024, d_model=128,
                                n_layers=2, n_heads=4, n_kv_heads=2,
                                d_ff=352, max_seq_len=512,
                                sliding_window=16),
}


# --------------------------------------------------------------------------- #
# Parameters

def _dense_init(key, shape, dtype, scale=None):
    fan_in = shape[0]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(config: LlamaConfig, key) -> Dict:
    keys = jax.random.split(key, config.n_layers + 3)
    dt = config.dtype
    d, h, kv, hd, f = (config.d_model, config.n_heads, config.n_kv_heads,
                       config.head_dim, config.d_ff)
    layers = []
    for i in range(config.n_layers):
        lk = jax.random.split(keys[i], 8)
        layer = {
            "attn_norm": jnp.ones((d,), dt),
            "wq": _dense_init(lk[0], (d, h * hd), dt),
            "wk": _dense_init(lk[1], (d, kv * hd), dt),
            "wv": _dense_init(lk[2], (d, kv * hd), dt),
            "wo": _dense_init(lk[3], (h * hd, d), dt),
            "mlp_norm": jnp.ones((d,), dt),
        }
        if config.n_experts:
            from .moe import init_moe_params
            layer["moe"] = init_moe_params(config.moe_config, lk[7])
        else:
            layer.update({
                "w_gate": _dense_init(lk[4], (d, f), dt),
                "w_up": _dense_init(lk[5], (d, f), dt),
                "w_down": _dense_init(lk[6], (f, d), dt),
            })
        layers.append(layer)
    return {
        "embed": _dense_init(keys[-3], (config.vocab_size, d), dt, 1.0),
        "layers": layers,
        "final_norm": jnp.ones((d,), dt),
        "lm_head": _dense_init(keys[-2], (d, config.vocab_size), dt),
    }


def param_specs(config: LlamaConfig) -> Dict:
    """PartitionSpecs for tensor parallelism over the "tp" mesh axis
    (megatron-style: column-parallel qkv/gate/up, row-parallel o/down;
    vocab-sharded embedding + head)."""
    layer = {
        "attn_norm": P(),
        "wq": P(None, "tp"), "wk": P(None, "tp"), "wv": P(None, "tp"),
        "wo": P("tp", None),
        "mlp_norm": P(),
    }
    if config.n_experts:
        from .moe import moe_param_specs
        layer["moe"] = moe_param_specs()
    else:
        layer.update({
            "w_gate": P(None, "tp"), "w_up": P(None, "tp"),
            "w_down": P("tp", None),
        })
    return {
        "embed": P("tp", None),
        "layers": [dict(layer) for _ in range(config.n_layers)],
        "final_norm": P(),
        "lm_head": P(None, "tp"),
    }


def quantize_params(params, bits: int = 8) -> Dict:
    """Weight-only quantization of the whole parameter tree (norm
    vectors stay bf16).  ``bits=8``: per-output-channel int8 — halves
    HBM bytes per decode step and fits 8B-class params in one v5e
    chip's 16 GB.  ``bits=4``: nibble-packed int4 with per-128-group
    scales — halves them again (~2× the int8 decode ceiling); the
    embedding stays int8 because its read path is a row gather, and
    gathering packed nibble rows would split bytes."""
    if bits == 4:
        quantized = quantize_tree(params, bits=4)
        quantized["embed"] = quantize_tree(params["embed"])
        return quantized
    return quantize_tree(params)


def quantized_param_specs(config: LlamaConfig, bits: int = 8) -> Dict:
    """PartitionSpecs matching :func:`quantize_params` output.  int8:
    the matrix keeps its dense spec, the (1, out) scales shard with the
    output axis.  int4: packed rows cover contiguous original rows (two
    per byte), so the packed matrix keeps the dense spec; the (G, out)
    group scales shard only on the output axis (G can be smaller than a
    row-parallel mesh axis, and replicated scales cost ~nothing)."""
    def visit(spec):
        if isinstance(spec, P) and len(spec) == 2:
            return {"q4" if bits == 4 else "q": spec,
                    "s": P(None, spec[1])}
        return spec
    specs = jax.tree_util.tree_map(
        visit, param_specs(config),
        is_leaf=lambda x: isinstance(x, P))
    if bits == 4:
        embed = param_specs(config)["embed"]
        specs["embed"] = {"q": embed, "s": P(None, embed[1])}
    if config.n_experts:
        # The 2-D MoE router also quantizes, but its spec is a bare P()
        # (len 0) which the length-2 rule above misses; 3-D expert
        # weights stay dense (quantize_tree only touches ndim==2).
        for layer in specs["layers"]:
            layer["moe"]["router"] = (
                {"q4": P(), "s": P()} if bits == 4 else
                {"q": P(), "s": P()})
    return specs


def random_quantized_params(config: LlamaConfig, key, bits: int = 8) -> Dict:
    """Random quantized params built DIRECTLY in quantized form — a bf16
    llama3_8b (~16 GB) would not fit next to itself in one chip's HBM,
    so the bf16 tree is never materialized.  Structure matches
    ``quantize_params(init_params(config, key), bits)`` exactly:
    int8 → ``{"q": int8 (in, out), "s": f32 (1, out)}``; int4 →
    ``{"q4": int8 (in/2, out) nibble-packed, "s": f32 (in/128, out)}``
    with the embedding kept int8 (row-gather path).  1-D norm vectors
    stay in the model dtype.  Scales are sized so dequantized weights
    look like fan-in-scaled gaussians — activations stay finite through
    all layers.  Used for benchmarking/capacity checks where real
    checkpoint weights are unavailable."""
    if config.n_experts:
        raise NotImplementedError(
            "random_quantized_params covers dense configs; MoE expert "
            "weights are 3-D and stay bf16 under quantize_params")
    c = config
    d, h, kv, hd, f = (c.d_model, c.n_heads, c.n_kv_heads, c.head_dim,
                       c.d_ff)
    counter = iter(range(10_000))

    # Weights come from the HOST's numpy generator on every backend and
    # ride the ordinary host-to-device transfer.  Generating on the
    # device costs more than it saves: a ``jax.random.randint`` over a
    # weight-sized shape took the TPU compiler 26-96 s per distinct
    # shape — 290 s (threefry) to 430 s (rbg) for the six shapes of
    # llama3_8b, measured on a v5e (PR 21) — against seconds for numpy
    # plus the copy.  It also makes a seed mean the same weights on the
    # CPU and on the chip.
    import numpy as np
    seed_base = int(jax.random.randint(key, (), 0, 2**31 - 1))

    def _randint8(shape, low):
        # A full-range int8 draw is numpy's fast path (~0.7 GB/s, 8x a
        # bounded draw); the excluded -128 folds onto its neighbour.
        rng = np.random.default_rng(seed_base + next(counter))
        rows = rng.integers(-128, 128, shape, np.int8)
        return jnp.asarray(np.maximum(rows, low, out=rows))

    def q8weight(shape):
        q = _randint8(shape, -127)
        s = jnp.full((1, shape[1]), shape[0] ** -0.5 / 127.0, jnp.float32)
        return {"q": q, "s": s}

    def q4weight(shape):
        kin, n = shape
        packed = _randint8((kin // 2, n), -128)
        groups = max(1, kin // 128)
        s = jnp.full((groups, n), kin ** -0.5 / 7.0, jnp.float32)
        return {"q4": packed, "s": s}

    qweight = q4weight if bits == 4 else q8weight
    layers = []
    for _ in range(c.n_layers):
        layers.append({
            "attn_norm": jnp.ones((d,), c.dtype),
            "wq": qweight((d, h * hd)),
            "wk": qweight((d, kv * hd)),
            "wv": qweight((d, kv * hd)),
            "wo": qweight((h * hd, d)),
            "mlp_norm": jnp.ones((d,), c.dtype),
            "w_gate": qweight((d, f)),
            "w_up": qweight((d, f)),
            "w_down": qweight((f, d)),
        })
    return {
        # The embedding read path is a row gather, so it stays int8
        # even at bits=4 (matches quantize_params).
        "embed": q8weight((c.vocab_size, d)),
        "layers": layers,
        "final_norm": jnp.ones((d,), c.dtype),
        "lm_head": qweight((d, c.vocab_size)),
    }


def _matmul(x, w):
    """Dense or int8/int4-quantized matmul, transparently."""
    if is_quantized_int4(w):
        return int4_matmul(x, w["q4"], w["s"])
    if is_quantized(w):
        return int8_matmul(x, w["q"], w["s"])
    return x @ w


# --------------------------------------------------------------------------- #
# Batched multi-adapter LoRA (SLoRA/punica-style serving)
#
# A serving batch where every row may run a DIFFERENT fine-tuned
# adapter: per-layer factors are stacked over a leading adapter axis
# (n_adapters, d_in, r) / (n_adapters, r, d_out) with index 0 reserved
# as the all-zero identity (a base-model row), and each batch row
# gathers its own pair.  The base weight stream — the decode
# bottleneck — is paid ONCE for the whole mixed batch; the rank-r
# delta adds O(r·(d_in+d_out)) per row.  The reference serves exactly
# one model binary per process (its LLM element shells out to one
# Ollama model, examples/llm/elements_llm.py:185-191).

def _lora_delta(x, factors, ids, scale):
    """Per-row low-rank delta: ``x`` (batch, q, d_in) through row
    ``i``'s own (A, B) = (factors["a"][ids[i]], factors["b"][ids[i]]).
    Computed in f32 (rank-r intermediates are tiny) and cast back.

    Two PINNED einsums, never one 3-operand contraction: the rank-r
    hidden ``x@A`` depends only on the replicated inputs, and each
    output column of ``hidden@B`` is an independent dot over r — so a
    TP shard holding a column slice of B computes exactly its slice of
    this delta, bitwise (llama_tp threads the same two einsums with B
    column-sharded; the all-gather is then pure data movement)."""
    a = factors["a"][ids].astype(jnp.float32)     # (batch, d_in, r)
    b = factors["b"][ids].astype(jnp.float32)     # (batch, r, d_out)
    hidden = jnp.einsum("bqd,bdr->bqr", x.astype(jnp.float32), a)
    delta = jnp.einsum("bqr,bro->bqo", hidden, b)
    return (scale * delta).astype(x.dtype)


def _lora_matmul(x, w, lora_layer, target, lora):
    """Base matmul plus the row-gathered adapter delta when ``target``
    is adapted; exactly ``_matmul`` otherwise (and for lora=None the
    call sites skip this entirely — the compiled program is
    unchanged)."""
    out = _matmul(x, w)
    factors = lora_layer.get(target) if lora_layer else None
    if factors is not None:
        out = out + _lora_delta(x, factors, lora["ids"], lora["scale"])
    return out


def _embed_lookup(params, tokens, dtype):
    embed = params["embed"]
    if is_quantized_int4(embed):
        # Packed rows hold vocab rows (2k, 2k+1) in (low, high) nibbles;
        # gather the byte row, then select the token's nibble.
        low, high = _unpack_int4(embed["q4"][tokens // 2])
        q = jnp.where((tokens % 2 == 0)[..., None], low, high)
        group = 2 * embed["q4"].shape[0] // embed["s"].shape[0]
        scale = embed["s"][tokens // group]
        return (q.astype(jnp.float32) * scale).astype(dtype)
    if is_quantized(embed):
        # Gather int8 rows, dequantize with the per-feature scales.
        return (embed["q"][tokens].astype(jnp.float32)
                * embed["s"]).astype(dtype)
    return embed[tokens]


# --------------------------------------------------------------------------- #
# Building blocks

def rms_norm(x, weight, eps):
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms).astype(x.dtype) * weight


def _rope_freqs(config: LlamaConfig, positions):
    """positions: (batch, seq) int32 → cos/sin (batch, seq, head_dim/2)."""
    dim = config.head_dim
    inv_freq = 1.0 / (config.rope_theta **
                      (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if config.rope_scaling is not None:
        # Llama-3.1 frequency rescaling: wavelengths beyond the
        # original context are slowed by ``factor``, in-band ones kept,
        # with a smooth ramp between (checkpoints are TRAINED with
        # these frequencies — skipping this garbles long-range heads).
        factor, low_fac, high_fac, original_max = config.rope_scaling
        wavelen = 2.0 * jnp.pi / inv_freq
        low_wavelen = original_max / low_fac
        high_wavelen = original_max / high_fac
        smooth = (original_max / wavelen - low_fac) / (high_fac - low_fac)
        smoothed = ((1.0 - smooth) * inv_freq / factor
                    + smooth * inv_freq)
        inv_freq = jnp.where(
            wavelen > low_wavelen, inv_freq / factor,
            jnp.where(wavelen < high_wavelen, inv_freq, smoothed))
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x, cos, sin):
    """x: (batch, seq, heads, head_dim); rotate-half convention."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _attention_block(layer, config, x, cos, sin, use_flash=True,
                     attention_fn=None):
    """Full-sequence (no-cache) attention block; returns
    (output, (k, v)) with k/v post-rope in (batch, seq, kv, hd) layout
    — callers that don't need them (plain forward) drop the tuple and
    XLA dead-code-eliminates it; the SP-prefill handoff writes them
    into a decode cache.  The cached-decode path lives in
    :func:`_attention_decode_ragged` (single implementation for both
    shared-position and per-row-position decode).  ``attention_fn``
    overrides the attention itself (e.g. ring attention over an sp
    mesh axis); it receives (q, k, v) in (batch, heads, seq, hd)
    layout and must handle GQA."""
    batch, seq, _ = x.shape
    h, kv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    normed = rms_norm(x, layer["attn_norm"], config.norm_eps)
    q = _matmul(normed, layer["wq"]).reshape(batch, seq, h, hd)
    k = _matmul(normed, layer["wk"]).reshape(batch, seq, kv, hd)
    v = _matmul(normed, layer["wv"]).reshape(batch, seq, kv, hd)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    q_t = q.transpose(0, 2, 1, 3)
    k_t = k.transpose(0, 2, 1, 3)
    v_t = v.transpose(0, 2, 1, 3)
    if attention_fn is not None:
        out = attention_fn(q_t, k_t, v_t)
    elif use_flash:
        # flash_attention is GQA-native (no repeated K/V in memory).
        out = flash_attention(q_t, k_t, v_t, causal=True,
                              window=config.sliding_window)
    else:
        group = h // kv
        out = attention_reference(
            q_t, jnp.repeat(k_t, group, axis=1),
            jnp.repeat(v_t, group, axis=1), causal=True,
            window=config.sliding_window)
    out = out.transpose(0, 2, 1, 3)

    out = _matmul(out.reshape(batch, seq, h * hd), layer["wo"])
    return x + out.astype(x.dtype), (k, v)


def _mlp_block(layer, config, x):
    normed = rms_norm(x, layer["mlp_norm"], config.norm_eps)
    if "moe" in layer:
        from .moe import moe_ffn
        return x + moe_ffn(layer["moe"], normed,
                           config.moe_config).astype(x.dtype)
    gate = jax.nn.silu(_matmul(normed, layer["w_gate"]).astype(jnp.float32))
    up = _matmul(normed, layer["w_up"]).astype(jnp.float32)
    return x + _matmul((gate * up).astype(x.dtype), layer["w_down"])


# --------------------------------------------------------------------------- #
# Entry points

@functools.partial(jax.jit, static_argnames=("config", "use_flash"))
def forward(params, tokens, config: LlamaConfig, use_flash: bool = True):
    """Full-sequence forward (training / prefill-style): tokens
    (batch, seq) int32 → logits (batch, seq, vocab) f32."""
    batch, seq = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(seq), (batch, seq))
    cos, sin = _rope_freqs(config, positions)
    x = _embed_lookup(params, tokens, config.dtype)
    for layer in params["layers"]:
        x, _ = _attention_block(layer, config, x, cos, sin,
                                use_flash=use_flash)
        x = _mlp_block(layer, config, x)
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    return _matmul(x, params["lm_head"]).astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("config", "mesh", "attention"))
def forward_sequence_parallel(params, tokens, config: LlamaConfig,
                              mesh, attention: str = "ring"):
    """Full-sequence forward with attention sharded over the ``sp``
    mesh axis — the long-context path, exact vs :func:`forward`.
    Sequence length must divide by the sp size.  Everything OUTSIDE
    attention (projections, MLP, norms) is local to each sequence
    shard, so XLA keeps those fully parallel with no collectives.

    ``attention="ring"``: K/V shards rotate around the ICI ring
    (GQA-native — only kv heads move); per-device attention memory
    O(seq/sp).  ``attention="ulysses"``: one all-to-all swaps the
    shard dimension from sequence to heads and back — fewer, larger
    collectives (MXU-friendly dense local attention) but needs
    ``n_heads % sp == 0`` and materializes the full sequence per head
    group (K/V repeated to the full head count first).

    Sliding-window (Mistral-class) configs compose with both:  the ring
    masks by global position and skips shards entirely below the
    window (windowed long-context prefill cost O(seq·window/sp));
    Ulysses holds the full sequence locally after the head scatter, so
    plain windowed masking is globally correct."""
    attention_fn = _sp_attention_fn(config, mesh, attention,
                                    tokens.shape[1])
    batch, seq = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(seq), (batch, seq))
    cos, sin = _rope_freqs(config, positions)
    x = _embed_lookup(params, tokens, config.dtype)
    for layer in params["layers"]:
        x, _ = _attention_block(layer, config, x, cos, sin,
                                attention_fn=attention_fn)
        x = _mlp_block(layer, config, x)
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    return _matmul(x, params["lm_head"]).astype(jnp.float32)


def _sp_attention_fn(config: LlamaConfig, mesh, attention: str,
                     seq_len: int):
    """Validate the sp mesh/config combination and build the
    sequence-parallel attention closure shared by
    :func:`forward_sequence_parallel` and
    :func:`prefill_sequence_parallel`."""
    if "sp" not in mesh.axis_names:
        raise ValueError(
            f"mesh has no 'sp' axis (axes: {mesh.axis_names}) — build "
            "it with make_mesh(sp=...)")
    sp = mesh.shape["sp"]
    if seq_len % sp:
        raise ValueError(
            f"sequence length {seq_len} must divide by the sp "
            f"mesh size {sp}")
    from ..parallel.ring_attention import ring_attention_sharded

    if attention == "ring":
        def ring(q_t, k_t, v_t):
            # ring_attention is GQA-native: only the kv heads rotate.
            return ring_attention_sharded(q_t, k_t, v_t, mesh,
                                          causal=True,
                                          window=config.sliding_window)
        attention_fn = ring
    elif attention == "ulysses":
        from ..parallel.ulysses import ulysses_attention_sharded
        if config.n_heads % sp:
            raise ValueError(
                f"ulysses needs n_heads ({config.n_heads}) divisible "
                f"by the sp mesh size ({sp})")
        group = config.n_heads // config.n_kv_heads
        kv_divides = config.n_kv_heads % sp == 0
        if group > 1 and not kv_divides:
            # Trace-time, so it fires once per compile, not per step.
            import warnings
            warnings.warn(
                f"Ulysses GQA fallback: n_kv_heads "
                f"({config.n_kv_heads}) % sp ({sp}) != 0, so K/V are "
                f"repeated x{group} BEFORE the all-to-all — K/V "
                f"collective bytes multiply by {group}.  Prefer "
                f"sp <= n_kv_heads (or ring attention) for this "
                "config.", stacklevel=2)

        def ulysses(q_t, k_t, v_t):
            if group > 1 and not kv_divides:
                # Head-scatter needs a divisible head count; repeating
                # BEFORE the all-to-all multiplies K/V collective
                # bytes by `group` — only the fallback when the kv
                # heads cannot be scattered directly.
                k_t = jnp.repeat(k_t, group, axis=1)
                v_t = jnp.repeat(v_t, group, axis=1)
            return ulysses_attention_sharded(
                q_t, k_t, v_t, mesh, window=config.sliding_window)
        attention_fn = ulysses
    else:
        raise ValueError(f"unknown attention {attention!r} "
                         "(ring | ulysses)")
    return attention_fn


@functools.partial(jax.jit,
                   static_argnames=("config", "mesh", "attention"),
                   donate_argnames=("cache",))
def prefill_sequence_parallel(params, tokens, cache,
                              config: LlamaConfig, mesh,
                              attention: str = "ring"):
    """SP-prefill → decode handoff: prefill a long prompt with
    attention sharded over the ``sp`` mesh axis (ring or Ulysses, as
    :func:`forward_sequence_parallel`), writing each layer's K/V into a
    standard decode cache.  The cache keeps whatever sharding it was
    created with (typically replicated / single-chip), so XLA inserts
    the sequence all-gather at the slab write — after this returns,
    :func:`generate_tokens` / :func:`decode_step` continue decoding
    from ``start_index = seq`` on a single chip (or any decode
    topology), which is how long-context serving actually runs: SP for
    the O(seq²) prefill, plain cached decode for the O(seq) tail.

    Rolling caches compose: the slab write keeps the last ``window``
    rows.  Returns (last-position logits (batch, vocab), cache)."""
    attention_fn = _sp_attention_fn(config, mesh, attention,
                                    tokens.shape[1])
    batch, seq = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(seq), (batch, seq))
    cos, sin = _rope_freqs(config, positions)
    x = _embed_lookup(params, tokens, config.dtype)
    new_cache = []
    for layer, cache_layer in zip(params["layers"], cache):
        x, (k, v) = _attention_block(layer, config, x, cos, sin,
                                     attention_fn=attention_fn)
        new_cache.append(_cache_write_slab(cache_layer, k, v, 0))
        x = _mlp_block(layer, config, x)
    x = rms_norm(x[:, -1], params["final_norm"], config.norm_eps)
    logits = _matmul(x, params["lm_head"]).astype(jnp.float32)
    return logits, new_cache


def init_cache(config: LlamaConfig, batch: int,
               max_seq: Optional[int] = None,
               quantize_kv: bool = False,
               rolling: bool = False) -> list:
    """KV cache: list (one per layer) of dicts.  ``quantize_kv`` stores
    K/V as int8 with per-(token, kv-head) f32 scales — halves KV bytes
    per decode step AND cache HBM footprint, which is what bounds batch
    (and therefore throughput) at long context.  ``rolling`` (requires
    ``config.sliding_window``) keeps only the last ``window`` rows in a
    ring buffer — row ``pos % window`` — with each row's ABSOLUTE
    position stored for masking, so sliding-window decode memory is
    O(window) instead of O(max_seq).  Single-token decode paths
    (decode_step, generate_tokens) handle any layout;
    :func:`prefill_chunk` rejects rolling caches for chunk length > 1
    (pre-attention slab writes can evict ring rows still inside earlier
    chunk queries' windows), and :func:`decode_chunk_ragged`'s
    slot-scratch trick is incompatible with rolling and rejects it."""
    if rolling:
        if not config.sliding_window:
            raise ValueError("rolling cache requires sliding_window")
        rows = config.sliding_window
    else:
        rows = max_seq or config.max_seq_len
    cache = _kv_layer_buffers(
        config, (batch, rows, config.n_kv_heads, config.head_dim),
        quantize_kv)
    if rolling:
        for layer in cache:
            # -1 = "row never written": masked out by the position test.
            layer["pos"] = jnp.full((batch, rows), -1, jnp.int32)
    return cache


def _kv_layer_buffers(config: LlamaConfig, shape, quantize_kv: bool):
    """Per-layer KV buffer dicts — the ONE place the cache layout
    (dtypes, scale keys) is defined; the contiguous cache and the
    paged pool differ only in the shape they pass."""
    if quantize_kv:
        sshape = shape[:-1]
        return [{"k": jnp.zeros(shape, jnp.int8),
                 "v": jnp.zeros(shape, jnp.int8),
                 "ks": jnp.ones(sshape, jnp.float32),
                 "vs": jnp.ones(sshape, jnp.float32)}
                for _ in range(config.n_layers)]
    return [{"k": jnp.zeros(shape, config.dtype),
             "v": jnp.zeros(shape, config.dtype)}
            for _ in range(config.n_layers)]


def _kv_quantize(rows):
    """(…, hd) bf16 → (int8 rows, f32 scales (…,)) — symmetric absmax
    per vector (one scale per cached token per kv head)."""
    r32 = rows.astype(jnp.float32)
    amax = jnp.max(jnp.abs(r32), axis=-1)
    scale = jnp.where(amax == 0, 1.0, amax / 127.0)
    q = jnp.clip(jnp.round(r32 / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _quantize_pairs(cache_layer, k, v):
    """(key → source) map for a write: k/v plus int8 scales when the
    layer is quantized."""
    if "ks" in cache_layer:
        kq, ks = _kv_quantize(k)
        vq, vs = _kv_quantize(v)
        return {"k": kq, "v": vq, "ks": ks, "vs": vs}
    return {"k": k, "v": v}


def _cache_write_slab(cache_layer, k, v, start_index):
    """Write a contiguous (batch, K, kv, hd) slab at ``start_index``
    (prefill / chunked-prefill path), any layout.  Rolling layout: only
    the last ``window`` slab rows can survive, so just those are
    scattered at ``pos % window`` (unique targets) and their absolute
    positions recorded."""
    if "pos" in cache_layer:
        window = cache_layer["pos"].shape[1]
        seq = k.shape[1]
        effective = min(seq, window)
        positions = start_index + jnp.arange(seq)[-effective:]
        rows = positions % window
        updated = {}
        for key, src in _quantize_pairs(cache_layer, k[:, -effective:],
                                        v[:, -effective:]).items():
            buf = cache_layer[key]
            updated[key] = buf.at[:, rows].set(src.astype(buf.dtype))
        updated["pos"] = cache_layer["pos"].at[:, rows].set(positions)
        return updated

    def dus(dst, src, start):
        zeros = (0,) * (dst.ndim - 2)
        return jax.lax.dynamic_update_slice(
            dst, src.astype(dst.dtype), (0, start) + zeros)
    return {key: dus(cache_layer[key], src, start_index)
            for key, src in _quantize_pairs(cache_layer, k, v).items()}


def _cache_write_rows(cache_layer, k, v, positions):
    """Write one (batch, 1, kv, hd) row per batch element at per-row
    ``positions`` (ragged decode path), any layout.  vmapped
    dynamic_update_slice lowers to an in-place scatter under
    donation."""
    window = cache_layer["pos"].shape[1] if "pos" in cache_layer else None
    rows = positions % window if window else positions

    def write_row(buf_rows, new, row):
        zeros = (0,) * (buf_rows.ndim - 1)
        return jax.lax.dynamic_update_slice(
            buf_rows, new.astype(buf_rows.dtype), (row,) + zeros)
    write = jax.vmap(write_row)
    updated = {key: write(cache_layer[key], src, rows)
               for key, src in _quantize_pairs(cache_layer, k, v).items()}
    if window:
        updated["pos"] = write(cache_layer["pos"],
                               positions[:, None].astype(jnp.int32),
                               rows)
    return updated


@functools.partial(jax.jit, static_argnames=("config",),
                   donate_argnames=("cache",))
def prefill(params, tokens, cache, config: LlamaConfig, lora=None):
    """Run the prompt through the model filling the KV cache; returns
    (logits_last, cache).  The input cache is DONATED (every caller
    rebinds it): without aliasing, the empty input cache and the
    filled output cache are simultaneously resident, doubling KV
    footprint exactly when prefill peaks — hardware-observed
    RESOURCE_EXHAUSTED for 8B int8 + int8-KV at batch 256 (r04),
    which fits comfortably once donated.  ``lora``: optional batched
    per-row adapters (see :func:`_decode_core_ragged`) — admission
    prefill must apply the SAME adapter the decode chunks will, or
    the prompt KV would be base-model state."""
    batch, seq = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(seq), (batch, seq))
    cos, sin = _rope_freqs(config, positions)
    x = _embed_lookup(params, tokens, config.dtype)
    new_cache = []
    lora_layers = lora["layers"] if lora else [None] * len(cache)
    for layer, cache_layer, lora_layer in zip(params["layers"], cache,
                                              lora_layers):
        normed = rms_norm(x, layer["attn_norm"], config.norm_eps)
        h, kv, hd = config.n_heads, config.n_kv_heads, config.head_dim
        q = _lora_matmul(normed, layer["wq"], lora_layer, "wq",
                         lora).reshape(batch, seq, h, hd)
        k = _lora_matmul(normed, layer["wk"], lora_layer, "wk",
                         lora).reshape(batch, seq, kv, hd)
        v = _lora_matmul(normed, layer["wv"], lora_layer, "wv",
                         lora).reshape(batch, seq, kv, hd)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        new_cache.append(_cache_write_slab(cache_layer, k, v, 0))
        q_t = q.transpose(0, 2, 1, 3)
        k_t = k.transpose(0, 2, 1, 3)
        v_t = v.transpose(0, 2, 1, 3)
        out = flash_attention(q_t, k_t, v_t, causal=True,
                              window=config.sliding_window)
        out = out.transpose(0, 2, 1, 3).reshape(batch, seq, h * hd)
        x = x + _lora_matmul(out, layer["wo"], lora_layer, "wo",
                             lora).astype(x.dtype)
        x = _mlp_block(layer, config, x)
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    logits = _matmul(x[:, -1:], params["lm_head"]).astype(jnp.float32)
    return logits, new_cache


# --------------------------------------------------------------------------- #
# Paged KV cache (vLLM-style block pool)
#
# The contiguous cache reserves ``slots x max_seq`` rows up front; a
# paged pool sizes HBM to the tokens actually LIVE (requests rarely all
# run at max length), so a serving replica admits more concurrent
# requests per GB.  Layout per layer: pool (n_blocks, block_size, kv,
# hd); each slot owns a block table (max_blocks,) of pool indices.
# Block 0 is reserved scratch: unallocated table entries and inactive
# slots point there, and it is never attendable (masking is by absolute
# position, and live positions always map to allocated blocks).

#: No per-slot recurrent state, and no counters beyond the engine's own
#: come back with a serve chunk (see :mod:`.nemotron_h` for a module
#: that has both).
RECURRENT_STATE = False
COUNTERS = ()
#: Nothing the engine offers is refused for this module.
UNSUPPORTED = None


def kv_geometry(config: LlamaConfig, quantize_kv: bool):
    """``(head_dim, kv heads, KV dtype)`` of the block pools, as the
    attention dispatch sees them."""
    return (config.head_dim, config.n_kv_heads,
            jnp.int8 if quantize_kv else config.dtype)


def kv_pool_layers(pool) -> list:
    """The block pools among what :func:`init_paged_cache` returns:
    here, all of it."""
    return pool


def state_bytes_per_slot(config: LlamaConfig) -> int:
    """Bytes a slot holds beside its KV blocks: none."""
    return 0


def layer_kinds(config: LlamaConfig) -> Dict[str, int]:
    """Every layer is attention plus a feed-forward block, dense or
    routed."""
    return {"attention": config.n_layers,
            "experts" if config.n_experts else "mlp": config.n_layers}


def init_paged_cache(config: LlamaConfig, n_blocks: int,
                     block_size: int = 16,
                     quantize_kv: bool = False, slots: int = 1) -> list:
    """Block pool, one dict per layer.  ``n_blocks`` INCLUDES the
    reserved scratch block 0.  (``slots``: the engine tells every model
    module how many rows it serves; only per-slot state needs it.)"""
    return _kv_layer_buffers(
        config,
        (n_blocks, block_size, config.n_kv_heads, config.head_dim),
        quantize_kv)


def _scan_scale_rows(pool, config: LlamaConfig):
    """Entry of a paged decode scan: where the decode kernel will read
    an int8 pool, its scale planes ``(n, bs, kv)`` ride the scan as
    lane rows ``(n · bs·kv / W, W)`` — the same values in the same
    order, so the view the kernel copies blocks from
    (:func:`~..ops.paged_attention.decode_scale_row`) and the append
    kernel patches (:func:`~..ops.paged_attention.paged_decode_append`)
    is what the scan carries.  Carried as planes, XLA re-lays each one
    out before every kernel call: two copies of the whole plane per
    layer per step, as long as the kernel itself (TPU compiler,
    PR 25).  :func:`_rest_scale_planes` undoes it at the scan's exit;
    the pool at rest never changes shape."""
    block_size, kv_heads = pool[0]["k"].shape[1:3]
    if "ks" not in pool[0] or not decode_append_dispatch(
            config.head_dim, kv_heads, pool[0]["k"].dtype,
            block_size)[0]:
        return pool
    width = decode_scale_row(block_size, kv_heads)
    return [dict(layer, ks=layer["ks"].reshape(-1, width),
                 vs=layer["vs"].reshape(-1, width))
            for layer in pool]


def _scale_planes(pool_layer):
    """A pool layer with its scales as planes ``(n, bs, kv)``, whatever
    it carries them as (:func:`_scan_scale_rows`)."""
    if "ks" not in pool_layer or pool_layer["ks"].ndim == 3:
        return pool_layer
    plane = pool_layer["k"].shape[:3]
    return dict(pool_layer, ks=pool_layer["ks"].reshape(plane),
                vs=pool_layer["vs"].reshape(plane))


def _rest_scale_planes(pool):
    """Exit of a paged decode scan: scale rows back to planes."""
    return [_scale_planes(layer) for layer in pool]


def _paged_write_rows(pool_layer, k, v, tables, positions):
    """Write one (batch, 1, kv, hd) row per slot into the pool at
    (tables[s, pos // bs], pos % bs).  Where the scan carries an int8
    pool's scales as lane rows (:func:`_scan_scale_rows`) one kernel
    call appends the token, rows and scales, in place; anything else
    (the CPU, the reference, float pools) takes a batched scatter per
    buffer."""
    block_size, kv_heads, head_dim = pool_layer["k"].shape[1:]
    block_ids = jnp.take_along_axis(
        tables, (positions // block_size)[:, None], axis=1)[:, 0]
    offsets = positions % block_size
    rows = _quantize_pairs(pool_layer, k[:, 0], v[:, 0])
    if "ks" in pool_layer and pool_layer["ks"].ndim == 2:
        _, interpret = decode_append_dispatch(
            head_dim, kv_heads, pool_layer["k"].dtype, block_size)
        return paged_decode_append(pool_layer, rows, block_ids, offsets,
                                   interpret=interpret)
    return {key: pool_layer[key].at[block_ids, offsets].set(
                src.astype(pool_layer[key].dtype))
            for key, src in rows.items()}


def _paged_gather(pool_layer, tables):
    """Per-slot cache view: pool[tables] → (slots, max_blocks*bs, …) —
    the same layout :func:`_cached_gqa_attention` reads, so paged and
    contiguous attention share ONE implementation.  XLA keeps the pool
    itself compact; the gathered view is a transient."""
    def view(pool):
        gathered = pool[tables]          # (slots, max_blocks, bs, ...)
        slots, max_blocks, block_size = gathered.shape[:3]
        return gathered.reshape((slots, max_blocks * block_size)
                                + gathered.shape[3:])
    return {key: view(buf) for key, buf in pool_layer.items()}


def _paged_write_slab(pool_layer, k, v, tables, positions_b):
    """Scatter a (batch, K, kv, hd) chunk slab into the pool at per-row
    absolute positions — the append-admission reference path: the chunk
    lands straight in its blocks, no bucket cache ever exists."""
    block_size = pool_layer["k"].shape[1]
    block_ids = jnp.take_along_axis(tables, positions_b // block_size,
                                    axis=1)
    offsets = positions_b % block_size

    def scatter(pool, rows):
        return pool.at[block_ids, offsets].set(rows.astype(pool.dtype))

    return {key: scatter(pool_layer[key], src)
            for key, src in _quantize_pairs(pool_layer, k, v).items()}


def _attention_decode_paged(layer, config, x, cos, sin, pool_layer,
                            tables, positions, lora=None,
                            lora_layer=None):
    """Single-token decode against the block pool (per-row positions,
    continuous batching)."""
    batch, seq, _ = x.shape
    h, kv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    normed = rms_norm(x, layer["attn_norm"], config.norm_eps)
    q = _lora_matmul(normed, layer["wq"], lora_layer, "wq",
                     lora).reshape(batch, seq, h, hd)
    k = _lora_matmul(normed, layer["wk"], lora_layer, "wk",
                     lora).reshape(batch, seq, kv, hd)
    v = _lora_matmul(normed, layer["wv"], lora_layer, "wv",
                     lora).reshape(batch, seq, kv, hd)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    new_pool = _paged_write_rows(pool_layer, k, v, tables, positions)
    planes = _scale_planes(new_pool)
    q_g = q.reshape(batch, seq, kv, h // kv, hd)
    use_kernel, interpret = decode_dispatch(hd, kv, new_pool["k"].dtype)
    if use_kernel:
        # The kernel walks the block table directly in HBM — the
        # steady-state decode path never gathers the pool.
        out = paged_decode_attention(
            q_g[:, 0], planes["k"], planes["v"], tables, positions,
            ks=planes.get("ks"), vs=planes.get("vs"),
            window=config.sliding_window, interpret=interpret)[:, None]
    else:
        gathered = _paged_gather(planes, tables)
        out = _cached_gqa_attention(q_g, gathered, positions[:, None],
                                    hd, window=config.sliding_window)
    out = out.reshape(batch, seq, h * hd)
    return x + _lora_matmul(out, layer["wo"], lora_layer, "wo",
                            lora).astype(x.dtype), new_pool


def _decode_core_paged(params, token, pool, tables, positions,
                       config: LlamaConfig, lora=None):
    positions_2d = positions[:, None]
    cos, sin = _rope_freqs(config, positions_2d)
    x = _embed_lookup(params, token, config.dtype)
    new_pool = []
    lora_layers = lora["layers"] if lora else [None] * len(pool)
    for layer, pool_layer, lora_layer in zip(params["layers"], pool,
                                             lora_layers):
        x, updated = _attention_decode_paged(layer, config, x, cos, sin,
                                             pool_layer, tables,
                                             positions, lora,
                                             lora_layer)
        new_pool.append(updated)
        x = _mlp_block(layer, config, x)
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    logits = _matmul(x, params["lm_head"]).astype(jnp.float32)
    return logits, new_pool


def _chunk_scan(step_core, tokens, positions, cache_state, active,
                num_steps, temperatures, top_ps, rng_key,
                collect_logits: bool = False):
    """Shared chunk-decode scaffolding for the contiguous and paged
    layouts: per-slot greedy/sampled pick, active-mask token/position
    advance, one ``lax.scan`` over steps.  ``step_core(token,
    cache_state, positions) -> (logits, cache_state)`` supplies the
    layout-specific write/read; everything else (the sampling semantics
    the exactness tests pin down) exists ONCE here.

    ``collect_logits``: also stack each step's next-token logits —
    speculative DRAFT runs need them so acceptance can reconstruct the
    exact proposal distribution (``sampling_probs``)."""
    sampled_mode = temperatures is not None
    if rng_key is None:
        rng_key = jax.random.PRNGKey(0)
    if sampled_mode and top_ps is None:
        top_ps = jnp.ones_like(temperatures)

    def pick(logits, key):
        greedy = logits.argmax(-1).astype(jnp.int32)
        if not sampled_mode:
            return greedy
        sampled = _sample_logits_per_row(logits, key, temperatures,
                                         top_ps)
        return jnp.where(temperatures > 0, sampled, greedy)

    def body(carry, _):
        token, positions, cache_state, key = carry
        key, step_key = jax.random.split(key)
        logits, cache_state = step_core(token, cache_state, positions)
        next_token = pick(logits[:, -1], step_key)[:, None]
        next_token = jnp.where(active[:, None], next_token, token)
        positions = jnp.where(active, positions + 1, positions)
        ys = (next_token[:, 0], logits[:, -1]) if collect_logits \
            else next_token[:, 0]
        return (next_token, positions, cache_state, key), ys

    (token, positions, cache_state, _), ys = jax.lax.scan(
        body, (tokens, positions, cache_state, rng_key), None,
        length=num_steps)
    if collect_logits:
        tokens_out, step_logits = ys
        # (steps, slots, vocab) -> (slots, steps, vocab)
        return (tokens_out.T, step_logits.transpose(1, 0, 2), token,
                positions, cache_state)
    return ys.T, token, positions, cache_state


@functools.partial(jax.jit,
                   static_argnames=("config", "num_steps",
                                    "return_logits"),
                   donate_argnames=("pool",))
def decode_chunk_paged(params, tokens, pool, tables, positions, active,
                       num_steps, config: LlamaConfig,
                       temperatures=None, top_ps=None, rng_key=None,
                       lora=None, return_logits: bool = False):
    """Paged twin of :func:`decode_chunk_ragged`: one compiled scan of
    ``num_steps`` steps over the block pool.  Inactive slots write into
    scratch block 0 at their slot offset (blocked from live tables by
    the allocator) and do not advance.

    Returns (tokens_out (slots, num_steps), last_token, positions,
    pool) — ``return_logits=True`` inserts the per-step next-token
    logits after ``tokens_out``, same contract as
    :func:`decode_chunk_ragged` (paged DRAFT runs for speculative
    serving)."""
    block_size = pool[0]["k"].shape[1]
    slots = tokens.shape[0]
    scratch_tables = jnp.zeros_like(tables)
    scratch_positions = (jnp.arange(slots, dtype=jnp.int32)
                         % block_size)

    def step_core(token, pool, positions):
        write_tables = jnp.where(active[:, None], tables,
                                 scratch_tables)
        write_pos = jnp.where(active, positions, scratch_positions)
        return _decode_core_paged(params, token, pool, write_tables,
                                  write_pos, config, lora=lora)

    *out, pool = _chunk_scan(
        step_core, tokens, positions, _scan_scale_rows(pool, config),
        active, num_steps, temperatures, top_ps, rng_key,
        collect_logits=return_logits)
    return (*out, _rest_scale_planes(pool))


@functools.partial(jax.jit, donate_argnames=("pool",))
def paged_insert_prefix(pool, tables, prefix_cache, slot):
    """Copy a contiguous prefilled cache (1, padded, kv, hd per layer;
    same quantize_kv layout as the pool) into ``slot``'s allocated
    blocks.  ``tables`` (slots, max_blocks); padded must be a multiple
    of the pool block size."""
    block_size = pool[0]["k"].shape[1]
    padded = prefix_cache[0]["k"].shape[1]
    n_blocks = padded // block_size
    block_ids = jax.lax.dynamic_slice_in_dim(
        tables[slot], 0, n_blocks, 0)
    return paged_scatter_blocks(pool, block_ids, prefix_cache,
                                jnp.int32(0))


@functools.partial(jax.jit, donate_argnames=("pool",))
def paged_scatter_blocks(pool, block_ids, prefix_cache, start_block):
    """Write contiguous prefilled rows into explicit pool blocks:
    prefix rows ``[start_block*bs, (start_block+len(ids))*bs)`` land in
    ``pool[block_ids]`` (prefix-cache tail insertion writes ONLY the
    private tail blocks; shared prefix blocks are never touched)."""
    block_size = pool[0]["k"].shape[1]
    n_blocks = block_ids.shape[0]
    new_pool = []
    for pool_layer, prefix_layer in zip(pool, prefix_cache):
        padded = prefix_layer["k"].shape[1]
        updated = {}
        for key, buf in pool_layer.items():
            src = prefix_layer[key][0]
            blocked = src.reshape((padded // block_size, block_size)
                                  + src.shape[1:]).astype(buf.dtype)
            sel = jax.lax.dynamic_slice_in_dim(blocked, start_block,
                                               n_blocks, 0)
            updated[key] = buf.at[block_ids].set(sel)
        new_pool.append(updated)
    return new_pool


@functools.partial(jax.jit, donate_argnames=("bucket",))
def paged_gather_blocks(pool, block_ids, bucket, start_block=None):
    """Read ``pool[block_ids]`` into ``len(ids)*bs`` contiguous rows of
    a bucket cache starting at block ``start_block`` (prefix-cache
    admission: materialize the shared prefix so the tail's chunked
    prefill can attend over it).  ``start_block`` is TRACED (default 0)
    so a long shared prefix can be gathered in a handful of
    power-of-two sub-gathers without compiling one program per prefix
    length."""
    block_size = pool[0]["k"].shape[1]
    rows = block_ids.shape[0] * block_size
    start_row = (jnp.int32(0) if start_block is None
                 else start_block.astype(jnp.int32) * block_size)
    new_bucket = []
    for pool_layer, bucket_layer in zip(pool, bucket):
        updated = {}
        for key, buf in bucket_layer.items():
            src = pool_layer[key][block_ids]
            flat = src.reshape((rows,) + src.shape[2:])
            starts = (jnp.int32(0), start_row) + (jnp.int32(0),) * (
                buf.ndim - 2)
            updated[key] = jax.lax.dynamic_update_slice(
                buf, flat[None].astype(buf.dtype), starts)
        new_bucket.append(updated)
    return new_bucket


def _decode_core(params, token, cache, cache_index, config: LlamaConfig):
    """One autoregressive step (traceable core): token (batch, 1) +
    shared cache position → (logits (batch, 1, vocab), new_cache).

    Delegates to the ragged (per-row-position) core with a constant
    position vector, so the plain and continuous-batching decode paths
    are ONE implementation (their exact equivalence is what the
    continuous-batching tests assert)."""
    batch = token.shape[0]
    positions = jnp.full((batch,), cache_index, jnp.int32)
    return _decode_core_ragged(params, token, cache, positions, config)


decode_step = functools.partial(jax.jit, static_argnames=("config",),
                                donate_argnames=("cache",))(_decode_core)


# Masked GQA attention over a KV cache — the ONE jnp implementation
# shared by ragged decode (CPU fallback), chunked prefill, and
# speculative verify.  Lives in ops/paged_attention.py next to the
# Pallas decode kernel it is the oracle for; the int8-KV path
# dequantizes one span at a time (the kv8 per-step full-cache-copy
# regression fix).
_cached_gqa_attention = cached_gqa_attention


def _decode_attention_contiguous(q_g, cache_layer, positions, hd,
                                 window):
    """Single-token ragged decode attention over a CONTIGUOUS cache:
    dispatch to the Pallas paged-decode kernel (the cache reshaped to a
    degenerate block pool — a free reshape — with iota block tables) on
    TPU, else the jnp oracle.  Rolling caches always take the oracle
    (ring rows need the stored-position mask)."""
    use_kernel, interpret = decode_dispatch(
        hd, cache_layer["k"].shape[2], cache_layer["k"].dtype)
    max_seq = cache_layer["k"].shape[1]
    block_size = contiguous_block_size(max_seq)
    if not use_kernel or not block_size or "pos" in cache_layer:
        return cached_gqa_attention(q_g, cache_layer,
                                    positions[:, None], hd,
                                    window=window)
    batch = q_g.shape[0]
    blocks_per_row = max_seq // block_size
    tables = (jnp.arange(batch, dtype=jnp.int32)[:, None]
              * blocks_per_row
              + jnp.arange(blocks_per_row, dtype=jnp.int32)[None, :])
    pool = {key: buf.reshape((batch * blocks_per_row, block_size)
                             + buf.shape[2:])
            for key, buf in cache_layer.items()}
    out = paged_decode_attention(
        q_g[:, 0], pool["k"], pool["v"], tables, positions,
        ks=pool.get("ks"), vs=pool.get("vs"), window=window,
        interpret=interpret)
    return out[:, None]


def _attention_decode_ragged(layer, config, x, cos, sin, cache_layer,
                             positions, lora=None, lora_layer=None):
    """Single-token decode where every batch row sits at its OWN cache
    position (continuous batching: slots admit/finish independently).
    ``x`` (batch, 1, d), ``positions`` (batch,) int32.  ``lora``:
    optional per-row batched adapters (see :func:`_lora_delta`)."""
    batch, seq, _ = x.shape
    h, kv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    normed = rms_norm(x, layer["attn_norm"], config.norm_eps)
    q = _lora_matmul(normed, layer["wq"], lora_layer, "wq",
                     lora).reshape(batch, seq, h, hd)
    k = _lora_matmul(normed, layer["wk"], lora_layer, "wk",
                     lora).reshape(batch, seq, kv, hd)
    v = _lora_matmul(normed, layer["wv"], lora_layer, "wv",
                     lora).reshape(batch, seq, kv, hd)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    new_cache = _cache_write_rows(cache_layer, k, v, positions)

    group = h // kv
    q_g = q.reshape(batch, seq, kv, group, hd)
    out = _decode_attention_contiguous(q_g, new_cache, positions, hd,
                                       config.sliding_window)
    out = out.reshape(batch, seq, h * hd)
    return x + _lora_matmul(out, layer["wo"], lora_layer, "wo",
                            lora).astype(x.dtype), new_cache


def _decode_core_ragged(params, token, cache, positions,
                        config: LlamaConfig, lora=None):
    """One autoregressive step with PER-ROW cache positions: token
    (batch, 1) + positions (batch,) → (logits (batch, 1, vocab),
    new_cache).  ``lora``: optional batched per-row adapters —
    ``{"ids": (batch,), "scale": float, "layers": [per-layer
    {target: {"a": (n, d_in, r), "b": (n, r, d_out)}}]}``."""
    positions_2d = positions[:, None]
    cos, sin = _rope_freqs(config, positions_2d)
    x = _embed_lookup(params, token, config.dtype)
    new_cache = []
    lora_layers = lora["layers"] if lora else [None] * len(cache)
    for layer, cache_layer, lora_layer in zip(params["layers"], cache,
                                              lora_layers):
        x, updated = _attention_decode_ragged(layer, config, x, cos,
                                              sin, cache_layer,
                                              positions, lora,
                                              lora_layer)
        new_cache.append(updated)
        x = _mlp_block(layer, config, x)
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    logits = _matmul(x, params["lm_head"]).astype(jnp.float32)
    return logits, new_cache


def _cache_write_ragged_slab(cache_layer, k, v, starts):
    """Write a (batch, K, kv, hd) slab at PER-ROW start positions
    (speculative verify inside continuous batching: each slot scores
    its drafted tokens at its OWN absolute position).  Contiguous
    layouts only (rolling is rejected by the caller)."""
    def write(buf_rows, new, start):
        zeros = (0,) * (buf_rows.ndim - 1)
        return jax.lax.dynamic_update_slice(
            buf_rows, new.astype(buf_rows.dtype), (start,) + zeros)

    write = jax.vmap(write)
    return {key: write(cache_layer[key], src, starts)
            for key, src in _quantize_pairs(cache_layer, k, v).items()}


@functools.partial(jax.jit, static_argnames=("config",),
                   donate_argnames=("cache",))
def verify_chunk_ragged(params, tokens, cache, positions, active,
                        config: LlamaConfig, lora=None):
    """Teacher-forced scoring of K given tokens per slot, every row at
    its OWN absolute start position — the speculative-verification
    twin of :func:`prefill_chunk` for the continuous-batching slot
    layout.  ``tokens`` (batch, K) int32, ``positions`` (batch,)
    absolute position of tokens[:, 0].  Returns (logits (batch, K,
    vocab) — ``logits[:, j]`` predicts position ``positions + j + 1``
    — and the cache with the K rows written per slot).

    Inactive slots write their slab at row 0 of their OWN slot rows —
    slot isolation makes those rows garbage-tolerant, and admission's
    bucket prefill rewrites ``[0, padded)`` before the slot ever
    decodes (callers keep K ≤ the bucket floor).  Stale rows past a
    rejected proposal are unattendable by the absolute-position mask
    until rewritten (the module-wide invariant)."""
    if cache and "pos" in cache[0]:
        raise ValueError(
            "verify_chunk_ragged does not support rolling caches")
    starts = jnp.where(active, positions, 0)
    positions_b = starts[:, None] + jnp.arange(tokens.shape[1])[None]
    return _chunk_forward(
        params, tokens, cache, positions_b,
        lambda cache_layer, k, v: _cache_write_ragged_slab(
            cache_layer, k, v, starts),
        config, lora)


def _chunk_forward(params, tokens, cache, positions_b, cache_write,
                   config: LlamaConfig, lora):
    """The ONE transformer stack for chunked forwards over an existing
    cache — :func:`prefill_chunk` (scalar start) and
    :func:`verify_chunk_ragged` (per-row starts) differ only in how
    positions are built and how the K new rows are written
    (``cache_write(cache_layer, k, v) -> layer_cache``)."""
    batch, K = tokens.shape
    cos, sin = _rope_freqs(config, positions_b)
    x = _embed_lookup(params, tokens, config.dtype)
    h, kv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    new_cache = []
    lora_layers = lora["layers"] if lora else [None] * len(cache)
    for layer, cache_layer, lora_layer in zip(params["layers"], cache,
                                              lora_layers):
        normed = rms_norm(x, layer["attn_norm"], config.norm_eps)
        q = _lora_matmul(normed, layer["wq"], lora_layer, "wq",
                         lora).reshape(batch, K, h, hd)
        k = _lora_matmul(normed, layer["wk"], lora_layer, "wk",
                         lora).reshape(batch, K, kv, hd)
        v = _lora_matmul(normed, layer["wv"], lora_layer, "wv",
                         lora).reshape(batch, K, kv, hd)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        layer_cache = cache_write(cache_layer, k, v)
        new_cache.append(layer_cache)
        q_g = q.reshape(batch, K, kv, h // kv, hd)
        out = _cached_gqa_attention(q_g, layer_cache, positions_b, hd,
                                    window=config.sliding_window)
        x = x + _lora_matmul(out.reshape(batch, K, h * hd),
                             layer["wo"], lora_layer, "wo",
                             lora).astype(x.dtype)
        x = _mlp_block(layer, config, x)
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    logits = _matmul(x, params["lm_head"]).astype(jnp.float32)
    return logits, new_cache


@functools.partial(jax.jit,
                   static_argnames=("config", "num_steps",
                                    "return_logits"),
                   donate_argnames=("cache",))
def decode_chunk_ragged(params, tokens, cache, positions, active,
                        num_steps, config: LlamaConfig,
                        temperatures=None, top_ps=None, rng_key=None,
                        lora=None, return_logits: bool = False):
    """Decode ``num_steps`` tokens for a slot batch where each row has
    its own position and an ``active`` flag — ONE compiled scan (the
    continuous-batching inner loop; admission happens between chunks).
    Inactive rows still flow through the math but their cache writes
    land at position ``max_seq-1`` reserved as scratch and their
    position does not advance.

    Per-slot sampling: ``temperatures``/``top_ps`` are (batch,) vectors
    — a row with temperature 0 stays EXACTLY greedy while its
    neighbors sample (mixed batches; tested).  ``None`` (trace-time)
    compiles the pure-greedy program with no sampling math.

    Not for ROLLING caches: the inactive-slot scratch row (max_seq-1)
    is a live ring row there.  Rolling serves the plain decode path
    (prefill/generate_tokens/decode_step).

    Returns (tokens_out (batch, num_steps), last_token (batch, 1),
    positions (batch,), cache) — with ``return_logits=True``, the
    per-step next-token logits (batch, num_steps, vocab) are inserted
    after ``tokens_out`` (speculative draft runs: acceptance
    reconstructs the exact proposal distribution from them).
    """
    if "pos" in cache[0]:
        raise ValueError(
            "decode_chunk_ragged does not support rolling caches: the "
            "inactive-slot scratch row would land on a live ring row")
    max_seq = cache[0]["k"].shape[1]

    def step_core(token, cache, positions):
        # Inactive slots write into the scratch row so they cannot
        # corrupt a live slot's KV prefix.
        write_pos = jnp.where(active, positions, max_seq - 1)
        return _decode_core_ragged(params, token, cache, write_pos,
                                   config, lora=lora)

    return _chunk_scan(step_core, tokens, positions, cache, active,
                       num_steps, temperatures, top_ps, rng_key,
                       collect_logits=return_logits)


def _serve_scan(step_core, state, cache_state, num_steps, eos_id,
                sampled, rng_key, first_core=None):
    """Device-resident serving scan: like :func:`_chunk_scan` but the
    per-slot state (token/positions/active/remaining) lives in a device
    ``state`` dict and EOS/budget retirement happens IN-JIT, so the
    host never uploads decode state or downloads logits on the steady
    path.  Emit-then-deactivate: the EOS token itself is emitted (the
    host loop's semantics), then the lane goes inactive for the rest of
    the chunk — inactive lanes write scratch and freeze.

    ``step_core(token, cache_state, positions, active)`` supplies the
    layout-specific read/write.  Returns ``(tokens_out (slots, steps),
    counts (slots,), new_state, cache_state)`` where ``counts[s]`` is
    the number of leading entries of ``tokens_out[s]`` actually emitted
    (active only transitions True→False inside a chunk, so emissions
    are a prefix).

    ``first_core`` (same signature) stands in for ``step_core`` at the
    chunk's first step, which then runs ahead of a scan of the other
    ``num_steps - 1``: a step that carries work of its own (a prefill
    slice through the layers beside the slots' rows).  The step around
    it is the scan's own ``body``: same key split, pick, retirement."""
    if rng_key is None:
        rng_key = jax.random.PRNGKey(0)
    temps, tops = state["temps"], state["tops"]

    def pick(logits, key):
        greedy = logits.argmax(-1).astype(jnp.int32)
        if not sampled:
            return greedy
        drawn = _sample_logits_per_row(logits, key, temps, tops)
        return jnp.where(temps > 0, drawn, greedy)

    def body(carry, _, core=step_core):
        token, positions, active, remaining, cache_state, key = carry
        key, step_key = jax.random.split(key)
        logits, cache_state = core(token, cache_state, positions, active)
        next_token = pick(logits[:, -1], step_key)[:, None]
        next_token = jnp.where(active[:, None], next_token, token)
        emitted = active
        positions = jnp.where(active, positions + 1, positions)
        remaining = jnp.where(active, remaining - 1, remaining)
        if eos_id >= 0:
            hit_eos = next_token[:, 0] == eos_id
        else:
            hit_eos = jnp.zeros_like(active)
        active = active & ~(hit_eos | (remaining <= 0))
        return ((next_token, positions, active, remaining, cache_state,
                 key), (next_token[:, 0], emitted))

    carry = (state["token"], state["positions"], state["active"],
             state["remaining"], cache_state, rng_key)
    if first_core is None:
        carry, (tokens_out, emits) = jax.lax.scan(body, carry, None,
                                                  length=num_steps)
    else:
        carry, first = body(carry, None, core=first_core)
        tokens_out, emits = (out[None] for out in first)
        if num_steps > 1:
            carry, rest = jax.lax.scan(body, carry, None,
                                       length=num_steps - 1)
            tokens_out, emits = (jnp.concatenate(pair) for pair in zip(
                (tokens_out, emits), rest))
    token, positions, active, remaining, cache_state, _ = carry
    counts = emits.astype(jnp.int32).sum(axis=0)
    new_state = dict(state, token=token, positions=positions,
                     active=active, remaining=remaining)
    return tokens_out.T, counts, new_state, cache_state


@jax.jit
def scatter_state_rows(state, rows, packet):
    """Compact host→device merge for the serving loop's dirty slots:
    write ``packet`` — the gathered rows of ONLY the slots an
    admission/retirement/sampling-edit actually touched — into
    ``state`` at ``rows``.  Upload cost is O(dirty rows), not
    O(slots): a fleet-sized server admitting one request no longer
    snapshots and re-merges every mirror.

    The caller pads ``rows``/``packet`` to a pow2 bucket by REPEATING
    the last dirty row, so compile shapes stay log-bounded under the
    steady-state-zero-compiles gate; duplicate indices are benign
    because every duplicate carries an identical payload — the scatter
    result is the same whichever write lands last.

    Nothing is donated: the state dict is a small immutable chain the
    host may hold references into (the in-flight ring)."""
    def scatter(dev, host):
        return dev.at[rows].set(host.astype(dev.dtype))
    return jax.tree.map(scatter, state, packet)


@functools.partial(jax.jit,
                   static_argnames=("config", "num_steps", "eos_id",
                                    "sampled"),
                   donate_argnames=("cache",))
def serve_chunk_ragged(params, state, cache, num_steps,
                       config: LlamaConfig, eos_id: int = -1,
                       sampled: bool = False, rng_key=None,
                       lora_shared=None):
    """Device-resident twin of :func:`decode_chunk_ragged` for the
    serving loop: all per-slot decode state (token tail, positions,
    active mask, remaining budget, sampling controls, adapter ids)
    arrives in the device ``state`` dict, EOS/budget retirement runs
    in-jit, and only the tiny ``(tokens_out, counts, state)`` result
    ever needs to cross back to the host.

    ``eos_id`` is STATIC (-1 disables EOS detection); ``sampled``
    statically selects the pure-greedy program when False so greedy
    traffic never pays sampling math.  ``lora_shared`` is the stacked
    adapter factors WITHOUT per-row ids — ids come from
    ``state["adapter_ids"]``, so adapter routing rides the resident
    state instead of a per-chunk upload.

    Only ``cache`` is donated: the state dict stays a small immutable
    chain the host may hold references into (the in-flight ring)."""
    if "pos" in cache[0]:
        raise ValueError(
            "serve_chunk_ragged does not support rolling caches: the "
            "inactive-slot scratch row would land on a live ring row")
    max_seq = cache[0]["k"].shape[1]
    lora = (dict(lora_shared, ids=state["adapter_ids"])
            if lora_shared is not None else None)

    def step_core(token, cache, positions, active):
        write_pos = jnp.where(active, positions, max_seq - 1)
        return _decode_core_ragged(params, token, cache, write_pos,
                                   config, lora=lora)

    return _serve_scan(step_core, state, cache, num_steps, eos_id,
                       sampled, rng_key)


@functools.partial(jax.jit,
                   static_argnames=("config", "num_steps", "eos_id",
                                    "sampled"),
                   donate_argnames=("pool",))
def serve_chunk_paged(params, state, pool, num_steps,
                      config: LlamaConfig, eos_id: int = -1,
                      sampled: bool = False, rng_key=None,
                      lora_shared=None):
    """Paged twin of :func:`serve_chunk_ragged`: block tables are part
    of the resident ``state`` (``state["tables"]``), so table updates
    on admission merge in with the rest of the dirty rows instead of a
    per-run upload.  Inactive lanes write scratch block 0 at their slot
    offset, exactly like :func:`decode_chunk_paged`."""
    block_size = pool[0]["k"].shape[1]
    tables = state["tables"]
    slots = tables.shape[0]
    scratch_tables = jnp.zeros_like(tables)
    scratch_positions = (jnp.arange(slots, dtype=jnp.int32)
                         % block_size)
    lora = (dict(lora_shared, ids=state["adapter_ids"])
            if lora_shared is not None else None)

    def step_core(token, pool, positions, active):
        write_tables = jnp.where(active[:, None], tables,
                                 scratch_tables)
        write_pos = jnp.where(active, positions, scratch_positions)
        return _decode_core_paged(params, token, pool, write_tables,
                                  write_pos, config, lora=lora)

    *out, pool = _serve_scan(step_core, state,
                             _scan_scale_rows(pool, config), num_steps,
                             eos_id, sampled, rng_key)
    return (*out, _rest_scale_planes(pool))


def _prefill_append_core(params, tokens, pool, tables, start_index,
                         config: LlamaConfig, lora=None, kv_limit=None,
                         compute_logits: bool = True):
    """Append-attention prefill straight against the block pool: the
    chunk's K/V land in their pool blocks and its queries attend over
    cached prefix blocks + the causally-visible chunk itself — no
    bucket gather, no scatter-back.  All rows share one scalar
    ``start_index`` (the admission loop prefills one request per call;
    ``tables`` is that request's (1, max_blocks) row, or a slot batch
    at a common boundary).

    Kernel dispatch mirrors the decode path
    (:func:`~..ops.paged_prefill.prefill_dispatch`); the reference
    dispatch writes the slab in place and attends over the gathered
    pool VIEW — still no bucket cache, so admission semantics are
    identical either way.  ``compute_logits=False`` skips the final
    norm + lm_head: the mixed serving step never reads prefill logits
    (activation seeds the LAST prompt token, so the first decode step
    produces the first output)."""
    batch, K = tokens.shape
    h, kv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    start_index = jnp.asarray(start_index, jnp.int32)
    positions_b = jnp.broadcast_to(
        start_index + jnp.arange(K, dtype=jnp.int32), (batch, K))
    cached_lens = jnp.broadcast_to(start_index, (batch,))
    chunk_lens = jnp.full((batch,), K, jnp.int32)
    cos, sin = _rope_freqs(config, positions_b)
    x = _embed_lookup(params, tokens, config.dtype)
    use_kernel, interpret = prefill_dispatch(
        hd, kv, pool[0]["k"].dtype, pool[0]["k"].shape[1], K)
    new_pool = []
    lora_layers = lora["layers"] if lora else [None] * len(pool)
    for layer, pool_layer, lora_layer in zip(params["layers"], pool,
                                             lora_layers):
        normed = rms_norm(x, layer["attn_norm"], config.norm_eps)
        q = _lora_matmul(normed, layer["wq"], lora_layer, "wq",
                         lora).reshape(batch, K, h, hd)
        k = _lora_matmul(normed, layer["wk"], lora_layer, "wk",
                         lora).reshape(batch, K, kv, hd)
        v = _lora_matmul(normed, layer["wv"], lora_layer, "wv",
                         lora).reshape(batch, K, kv, hd)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        q_g = q.reshape(batch, K, kv, h // kv, hd)
        if use_kernel:
            out, pool_layer = paged_prefill_attention(
                q_g, k, v, pool_layer, tables, cached_lens, chunk_lens,
                window=config.sliding_window, interpret=interpret,
                kv_limit=kv_limit)
        else:
            pool_layer = _paged_write_slab(pool_layer, k, v, tables,
                                           positions_b)
            gathered = _paged_gather(pool_layer, tables)
            out = _cached_gqa_attention(q_g, gathered, positions_b, hd,
                                        window=config.sliding_window)
        new_pool.append(pool_layer)
        x = x + _lora_matmul(out.reshape(batch, K, h * hd),
                             layer["wo"], lora_layer, "wo",
                             lora).astype(x.dtype)
        x = _mlp_block(layer, config, x)
    if not compute_logits:
        return None, new_pool
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    logits = _matmul(x, params["lm_head"]).astype(jnp.float32)
    return logits, new_pool


@functools.partial(jax.jit,
                   static_argnames=("config", "kv_limit",
                                    "compute_logits"),
                   donate_argnames=("pool",))
def prefill_append_paged(params, tokens, pool, tables, start_index,
                         config: LlamaConfig, lora=None,
                         kv_limit=None, compute_logits: bool = True):
    """Admit a (batch, K) prompt chunk into the block pool by append
    attention — the replacement for the gather → contiguous prefill →
    scatter admission chain.  Prefix-cache hits skip straight past the
    shared blocks: pass ``start_index = n_shared * block_size`` and the
    cached blocks are only READ, never materialized into a bucket.

    ``kv_limit`` (static) clips the kernel's block sweep to the
    request's own allocation so short prompts don't pay for the full
    table width; ``tokens`` width must be a multiple of the pool block
    size for the kernel path (the dispatcher falls back to the
    reference slab write otherwise)."""
    return _prefill_append_core(params, tokens, pool, tables,
                                start_index, config, lora=lora,
                                kv_limit=kv_limit,
                                compute_logits=compute_logits)


@functools.partial(jax.jit,
                   static_argnames=("config", "num_steps", "eos_id",
                                    "sampled", "prefill_kv_limit"),
                   donate_argnames=("pool",))
def serve_chunk_mixed(params, state, pool, prefill_tokens, prefill_row,
                      prefill_start, num_steps, config: LlamaConfig,
                      eos_id: int = -1, sampled: bool = False,
                      rng_key=None, lora_shared=None,
                      prefill_kv_limit=None):
    """Sarathi-style mixed step: ONE jitted dispatch that appends a
    chunked-prefill slice for one admitting request and then runs
    ``num_steps`` decode steps for the live slots — prefill no longer
    stalls decode between chunks.

    ``prefill_row`` is a TRACED slot index (the admitting slot's block
    table row and adapter id are dynamically sliced out of the resident
    state), so which slot is prefilling never triggers a recompile —
    only the slice width and ``prefill_kv_limit`` (both shape-bounded
    by the bucket ladder) are static.  The prefilling slot stays
    inactive in ``state`` until its last slice lands, so the decode
    scan treats it as a scratch lane; prefill logits are never
    computed (the activation seed is the last prompt token)."""
    block_size = pool[0]["k"].shape[1]
    tables = state["tables"]
    slots = tables.shape[0]
    prefill_row = jnp.asarray(prefill_row, jnp.int32)
    tables_row = jax.lax.dynamic_slice_in_dim(tables, prefill_row, 1,
                                              axis=0)
    if lora_shared is not None:
        row_ids = jax.lax.dynamic_slice_in_dim(state["adapter_ids"],
                                               prefill_row, 1, axis=0)
        prefill_lora = dict(lora_shared, ids=row_ids)
        lora = dict(lora_shared, ids=state["adapter_ids"])
    else:
        prefill_lora = lora = None
    _, pool = _prefill_append_core(params, prefill_tokens, pool,
                                   tables_row, prefill_start, config,
                                   lora=prefill_lora,
                                   kv_limit=prefill_kv_limit,
                                   compute_logits=False)
    scratch_tables = jnp.zeros_like(tables)
    scratch_positions = (jnp.arange(slots, dtype=jnp.int32)
                         % block_size)

    def step_core(token, pool, positions, active):
        write_tables = jnp.where(active[:, None], tables,
                                 scratch_tables)
        write_pos = jnp.where(active, positions, scratch_positions)
        return _decode_core_paged(params, token, pool, write_tables,
                                  write_pos, config, lora=lora)

    *out, pool = _serve_scan(step_core, state,
                             _scan_scale_rows(pool, config), num_steps,
                             eos_id, sampled, rng_key)
    return (*out, _rest_scale_planes(pool))


def _verify_append_core(params, tokens, pool, tables, positions,
                        active, config: LlamaConfig, lora=None,
                        kv_limit=None):
    """Teacher-forced scoring of a (batch, K) speculative window
    straight against the block pool — the paged twin of
    :func:`verify_chunk_ragged`: every row at its OWN absolute start
    position (mid-block starts included), the window's K/V appended
    into table-resolved pool blocks, no gather, no bucket.

    Kernel dispatch mirrors :func:`_prefill_append_core`; the reference
    dispatch writes the slab in place (:func:`_paged_write_slab`, the
    SAME quantizer the decode write path uses, so verify-written rows
    are byte-identical to what plain decode would have written) and
    attends over the gathered pool view.  Inactive rows write scratch
    block 0 and their logits are garbage the acceptance mask
    discards."""
    batch, K = tokens.shape
    h, kv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    starts = jnp.where(active, positions, 0).astype(jnp.int32)
    positions_b = starts[:, None] + jnp.arange(K, dtype=jnp.int32)[None]
    cached_lens = starts
    chunk_lens = jnp.where(active, K, 0).astype(jnp.int32)
    scratch_tables = jnp.zeros_like(tables)
    write_tables = jnp.where(active[:, None], tables, scratch_tables)
    cos, sin = _rope_freqs(config, positions_b)
    x = _embed_lookup(params, tokens, config.dtype)
    use_kernel, interpret = verify_dispatch(hd, kv, pool[0]["k"].dtype,
                                            K)
    new_pool = []
    lora_layers = lora["layers"] if lora else [None] * len(pool)
    for layer, pool_layer, lora_layer in zip(params["layers"], pool,
                                             lora_layers):
        normed = rms_norm(x, layer["attn_norm"], config.norm_eps)
        q = _lora_matmul(normed, layer["wq"], lora_layer, "wq",
                         lora).reshape(batch, K, h, hd)
        k = _lora_matmul(normed, layer["wk"], lora_layer, "wk",
                         lora).reshape(batch, K, kv, hd)
        v = _lora_matmul(normed, layer["wv"], lora_layer, "wv",
                         lora).reshape(batch, K, kv, hd)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        q_g = q.reshape(batch, K, kv, h // kv, hd)
        if use_kernel:
            out, pool_layer = paged_verify_attention(
                q_g, k, v, pool_layer, write_tables, cached_lens,
                chunk_lens, window=config.sliding_window,
                interpret=interpret, kv_limit=kv_limit)
        else:
            pool_layer = _paged_write_slab(pool_layer, k, v,
                                           write_tables, positions_b)
            gathered = _paged_gather(pool_layer, write_tables)
            out = _cached_gqa_attention(q_g, gathered, positions_b, hd,
                                        window=config.sliding_window)
        new_pool.append(pool_layer)
        x = x + _lora_matmul(out.reshape(batch, K, h * hd),
                             layer["wo"], lora_layer, "wo",
                             lora).astype(x.dtype)
        x = _mlp_block(layer, config, x)
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    logits = _matmul(x, params["lm_head"]).astype(jnp.float32)
    return logits, new_pool


@functools.partial(jax.jit,
                   static_argnames=("config", "kv_limit"),
                   donate_argnames=("pool",))
def verify_chunk_paged(params, tokens, pool, tables, positions, active,
                       config: LlamaConfig, lora=None, kv_limit=None):
    """Speculative verify on the PAGED layout: score K tokens per slot
    against the block pool, each row at its own absolute position —
    the pool-backed twin of :func:`verify_chunk_ragged`.  ``tokens``
    (batch, K) int32 windows (seed token + proposals), ``tables`` the
    resident (slots, max_blocks) block tables, ``positions`` (batch,)
    the absolute position of ``tokens[:, 0]``.

    Returns ``(logits (batch, K, vocab), pool)`` — ``logits[:, j]``
    predicts position ``positions + j + 1``.  The window's K/V rows
    land in each slot's own blocks at ``[positions, positions + K)``;
    rejected-tail rows are left stale (unattendable by the absolute-
    position mask until a later round rewrites them — the module-wide
    invariant; the server counts them as ``spec_rollback_blocks``).
    Callers must reserve ``K`` rows of block headroom past the last
    committed position (the paged server's worst-case reservation
    includes ``spec_k + 1``)."""
    return _verify_append_core(params, tokens, pool, tables, positions,
                               active, config, lora=lora,
                               kv_limit=kv_limit)


def _sample_logits_per_row(logits, key, temperatures, top_ps):
    """Per-row temperature + nucleus: :func:`sample_logits` broadcasts
    (B, 1)-shaped controls, so the vector case is the SAME
    implementation (``top_p >= 1`` rows are a numeric no-op; the best
    token is always kept)."""
    return sample_logits(logits, key,
                         temperature=temperatures[:, None],
                         top_p=top_ps[:, None])


def _mask_logits(logits, temperature: float = 1.0, top_k: int = 0,
                 top_p=None):
    """Temperature-scale + top-k/top-p mask ``logits (batch, vocab)``
    — THE truncation implementation: sampling draws from it
    (:func:`sample_logits`) and speculative acceptance computes the
    matching distributions from it (:func:`sampling_probs`), so the
    two can never disagree."""
    logits = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    if isinstance(top_p, (int, float)) and top_p >= 1.0:
        top_p = None                 # trace-time no-op, not a tracer
    if (top_k and top_k > 0) or top_p is not None:
        sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
        if top_k and top_k > 0:
            kth = sorted_desc[:, top_k - 1][:, None]
            logits = jnp.where(logits < kth, -1e30, logits)
            sorted_desc = jnp.where(
                jnp.arange(sorted_desc.shape[-1])[None, :] < top_k,
                sorted_desc, -1e30)
        if top_p is not None:
            probs = jax.nn.softmax(sorted_desc, axis=-1)
            cumulative = jnp.cumsum(probs, axis=-1)
            # Keep the minimal prefix with cumulative mass >= top_p;
            # rank 0 is force-kept so top_p <= 0 degrades to argmax
            # instead of masking every token (uniform garbage).
            cutoff_mask = (cumulative - probs >= top_p) & (
                jnp.arange(sorted_desc.shape[-1])[None, :] > 0)
            # Cutoff = smallest KEPT logit (drop candidates -> +inf so
            # the min ranges over the nucleus only).
            cutoff = jnp.where(cutoff_mask, jnp.inf,
                               sorted_desc).min(axis=-1, keepdims=True)
            logits = jnp.where(logits < cutoff, -1e30, logits)
    return logits


def sample_logits(logits, key, temperature: float = 1.0,
                  top_k: int = 0, top_p=None):
    """Sample token ids from ``logits (batch, vocab)`` with the standard
    serving controls: temperature scaling, top-k truncation, and
    nucleus (top-p) truncation — jit-compatible (static vocab sort, no
    data-dependent shapes).  ``top_k`` must be static (it sizes a
    slice).  ``top_p=None`` (or a static value >= 1) compiles the
    nucleus out entirely; a float < 1 or a TRACED value applies it
    (per-request nucleus without recompiling).  One shared descending
    sort serves both truncations; the best token is always kept."""
    return jax.random.categorical(
        key, _mask_logits(logits, temperature, top_k,
                          top_p)).astype(jnp.int32)


def sampling_probs(logits, temperature: float = 1.0, top_p=None):
    """The EXACT distribution :func:`sample_logits` draws from at
    these controls (batch-shaped temperature/top_p broadcast like the
    per-row sampler): softmax of the same masked, scaled logits."""
    return jax.nn.softmax(_mask_logits(logits, temperature,
                                       top_k=0, top_p=top_p), axis=-1)


@functools.partial(jax.jit,
                   static_argnames=("config", "num_steps", "temperature",
                                    "top_k"),
                   donate_argnames=("cache",))
def generate_tokens(params, first_token, cache, start_index, num_steps,
                    config: LlamaConfig, temperature: float = 0.0,
                    rng_key=None, top_k: int = 0, top_p=None):
    """Greedy (or sampled) decode of ``num_steps`` tokens as ONE compiled
    program (``lax.scan`` over steps) — a single device dispatch instead
    of one per token, which matters both for dispatch overhead and for
    XLA's ability to keep the KV cache resident.

    Returns (tokens (batch, num_steps), cache)."""
    if rng_key is None:
        rng_key = jax.random.PRNGKey(0)

    def body(carry, step):
        token, cache, key = carry
        logits, cache = _decode_core(params, token, cache,
                                     start_index + step, config)
        logits = logits[:, -1]
        if temperature and temperature > 0:
            key, sample_key = jax.random.split(key)
            next_token = sample_logits(logits, sample_key, temperature,
                                       top_k=top_k, top_p=top_p)
        else:
            next_token = logits.argmax(-1).astype(jnp.int32)
        next_token = next_token[:, None]
        return (next_token, cache, key), next_token[:, 0]

    (_, cache, _), tokens = jax.lax.scan(
        body, (first_token, cache, rng_key),
        jnp.arange(num_steps, dtype=jnp.int32))
    return tokens.T, cache   # (batch, num_steps)


@functools.partial(jax.jit,
                   static_argnames=("config", "num_steps"),
                   donate_argnames=("cache",))
def sample_tokens_with_logits(params, first_token, cache, start_index,
                              num_steps, config: LlamaConfig,
                              temperature, rng_key):
    """Sampled decode that ALSO returns each step's logits row — the
    speculative draft primitive: one compiled scan (no per-step host
    round-trips), one (batch, steps, vocab) transfer for the
    acceptance math.  Returns (tokens (batch, steps), logits (batch,
    steps, vocab) f32, cache)."""
    def body(carry, step):
        token, cache, key = carry
        logits, cache = _decode_core(params, token, cache,
                                     start_index + step, config)
        row = logits[:, -1].astype(jnp.float32)
        key, step_key = jax.random.split(key)
        scaled = row / jnp.maximum(temperature, 1e-6)
        next_token = jax.random.categorical(
            step_key, scaled).astype(jnp.int32)
        return (next_token[:, None], cache, key), (next_token, row)

    (_, cache, _), (tokens, rows) = jax.lax.scan(
        body, (first_token, cache, rng_key),
        jnp.arange(num_steps, dtype=jnp.int32))
    return tokens.T, rows.transpose(1, 0, 2), cache


@functools.partial(jax.jit, static_argnames=("config",),
                   donate_argnames=("cache",))
def prefill_chunk(params, tokens, cache, start_index,
                  config: LlamaConfig, lora=None):
    """Chunked prefill: run ``tokens (batch, K)`` through the model at
    absolute positions ``start_index + [0, K)``, extending an EXISTING
    cache prefix.  Returns (logits (batch, K, vocab) — every position,
    not just the last — and the cache).

    Uses: admitting long prompts chunk-by-chunk (continuous batching),
    and speculative-decode verification (score K draft tokens in one
    pass).  Attention masks by ABSOLUTE position (key_pos <= query_pos),
    so stale cache rows beyond the chunk are never attended.

    Rolling (ring-buffer) caches are rejected for chunk length > 1: the
    slab write lands all K rows BEFORE attention runs, so ring rows
    holding positions still inside earlier chunk queries' sliding
    windows would be overwritten (their stored position becomes future
    → masked out) and softmax would silently normalize over missing
    keys.  Feed rolling caches token-by-token (K=1) instead."""
    batch, K = tokens.shape
    if cache and "pos" in cache[0] and K > 1:
        raise ValueError(
            "prefill_chunk does not support rolling caches with chunk "
            "length > 1: the pre-attention slab write can evict ring "
            "rows still inside earlier chunk queries' sliding windows "
            "(silently wrong logits); feed K=1 chunks instead")
    positions = start_index + jnp.arange(K)
    positions_b = jnp.broadcast_to(positions, (batch, K))
    return _chunk_forward(
        params, tokens, cache, positions_b,
        lambda cache_layer, k, v: _cache_write_slab(cache_layer, k, v,
                                                    start_index),
        config, lora)


def stack_pipeline_params(params, config: LlamaConfig, pp: int):
    """Split ``params["layers"]`` into ``pp`` contiguous stage groups and
    stack them ``(pp, per_stage, …)`` — the layout
    :func:`~..parallel.pipeline_parallel.pipeline_apply_sharded` shards
    over the ``pp`` mesh axis.  Do this ONCE and pass the result as
    ``stages=`` for repeated :func:`pipeline_forward` calls; stacking is
    an O(model) copy."""
    from ..parallel.pipeline_parallel import stack_stages
    layers = params["layers"]
    assert len(layers) % pp == 0, (len(layers), pp)
    per_stage = len(layers) // pp
    groups = [stack_stages(layers[s * per_stage:(s + 1) * per_stage])
              for s in range(pp)]
    return stack_stages(groups)


@functools.partial(jax.jit,
                   static_argnames=("config", "mesh", "n_microbatches",
                                    "pp_axis"))
def pipeline_forward(params, tokens, config: LlamaConfig, mesh,
                     n_microbatches: int = 4, pp_axis: str = "pp",
                     stages=None):
    """Full-sequence forward with the transformer layers split into
    GPipe pipeline stages over the ``pp_axis`` mesh axis (embed, final
    norm and LM head stay replicated outside the pipeline; activations
    hop stage-to-stage with ``ppermute`` over ICI).  Numerics match
    :func:`forward` up to bf16 rounding at stage boundaries: the loop
    carry materializes activations in the model dtype each hop, where
    the fused single-program forward may keep excess precision.

    The host-level PP story (reference remote PipelineElements with MQTT
    frame hops) stays for cross-pod boundaries; this is the on-pod
    equivalent inside ONE jitted program.
    """
    from ..parallel.pipeline_parallel import pipeline_apply_sharded
    pp = mesh.shape[pp_axis]
    assert config.n_layers % pp == 0, (config.n_layers, pp)
    per_stage = config.n_layers // pp
    if stages is None:
        # Convenience path: stacks inside the compiled program (an
        # O(model) copy per call) — for repeated calls pre-stack with
        # :func:`stack_pipeline_params` and pass ``stages=``.
        stages = stack_pipeline_params(params, config, pp)

    batch, seq = tokens.shape

    def stage_fn(stage_params, x):
        positions = jnp.broadcast_to(jnp.arange(seq),
                                     (x.shape[0], seq))
        cos, sin = _rope_freqs(config, positions)
        for j in range(per_stage):
            layer = jax.tree.map(lambda leaf: leaf[j], stage_params)
            x, _ = _attention_block(layer, config, x, cos, sin,
                                    use_flash=False)
            x = _mlp_block(layer, config, x)
        return x

    x = _embed_lookup(params, tokens, config.dtype)
    x = pipeline_apply_sharded(stage_fn, stages, x, mesh, axis=pp_axis,
                               n_microbatches=n_microbatches)
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    return _matmul(x, params["lm_head"]).astype(jnp.float32)


def complete(params, prompt_tokens, config: LlamaConfig,
             max_new_tokens: int = 32, temperature: float = 0.0,
             rng_key=None, top_k: int = 0, top_p=None,
             eos_token: Optional[int] = None, quantize_kv: bool = False):
    """Convenience end-to-end completion: prefill + one-scan decode.

    ``prompt_tokens`` (batch, prompt_len) int32 → (batch, <=max_new)
    numpy array of generated token ids (prompt excluded), truncated at
    the first ``eos_token`` per row when given.  This is the API the
    chat elements and the golden-completion tests use against imported
    checkpoints; serving paths keep the explicit prefill/decode calls.
    """
    import numpy as np
    tokens = jnp.asarray(prompt_tokens, jnp.int32)
    batch, prompt_len = tokens.shape
    cache = init_cache(config, batch, prompt_len + max_new_tokens,
                       quantize_kv=quantize_kv)
    logits, cache = prefill(params, tokens, cache, config)
    last = logits[:, -1]
    if temperature and temperature > 0:
        if rng_key is None:
            rng_key = jax.random.PRNGKey(0)
        rng_key, first_key = jax.random.split(rng_key)
        first = sample_logits(last, first_key, temperature,
                              top_k=top_k, top_p=top_p)[:, None]
    else:
        first = last.argmax(-1).astype(jnp.int32)[:, None]
    generated, _ = generate_tokens(
        params, first, cache, jnp.int32(prompt_len),
        max_new_tokens - 1, config, temperature=temperature,
        rng_key=rng_key, top_k=top_k, top_p=top_p)
    out = np.concatenate([np.asarray(first), np.asarray(generated)],
                         axis=1)
    if eos_token is not None:
        rows = []
        for row in out:
            hits = np.nonzero(row == eos_token)[0]
            rows.append(row[:hits[0]] if hits.size else row)
        width = max((len(r) for r in rows), default=0)
        padded = np.full((len(rows), width), eos_token, out.dtype)
        for i, row in enumerate(rows):
            padded[i, :len(row)] = row
        return padded
    return out
