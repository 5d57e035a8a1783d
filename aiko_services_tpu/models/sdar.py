"""Block-diffusion mixture-of-experts decoder (``sdar_moe``; SDAR's
layer is Qwen3-MoE's): grouped-query attention with an explicit head
width and a per-head RMSNorm of queries and keys, a feed-forward of
routed SwiGLU experts with no shared one, and GENERATION BY DIFFUSION
OVER BLOCKS, served through the same paged engine as :mod:`.llama`.

Every layer is two pre-norm residual blocks.  Attention: ``q = x W_q``
as ``n_heads`` heads of ``head_dim`` (NOT ``d_model / n_heads``), ``k``
and ``v`` as ``n_kv_heads``; each head of ``q`` and of ``k`` is
RMS-normalised over its ``head_dim`` (``q_norm`` / ``k_norm``) and then
rotated (rotate-half RoPE at the absolute position).  The mask is
**block-causal**: with ``B = block_length``, key ``j`` is visible to
query ``i`` iff ``j // B <= i // B``.  The feed-forward is
:mod:`.moe`'s held, no-capacity dispatch (softmax over all experts, the
``top_k`` largest renormalised, the experts held here computing their
part).  The logits at a position are the distribution of the token AT
that position: there is no shift.

Generation.  Positions are blocks of ``B`` by absolute index.  The
prompt is prefilled under the mask above (:func:`prefill_append_paged`;
the rows of a block the prompt only opens are written again by that
block's passes before anything reads them).  A slot then works on ONE
block at a time, held in its resident state: ``window`` (the block's
tokens), ``masked`` (which of them are still ``[MASK]``: a flag, never
a comparison with the id) and ``positions`` (the block's first
position).  A **pass** (:func:`_block_core`) runs the block's ``B``
positions — the committed tokens, the ``[MASK]`` embedding where masked
— through the layers: its ``B`` K/V rows are written over the block's
rows in the pool, then all ``B`` queries attend over every earlier
block and the whole of their own (one call of the paged decode kernel
with the ``B`` queries stacked beside a kv head's group: they see the
same keys).  At each masked position the pass takes ``x0``, the picked
token, and ``c``, its probability, and commits by the slot's rule
(:func:`commit_rule`): *static*, the ``B // T`` (+1 for the first
``B mod T`` passes) masked positions of largest ``c``; *dynamic*, every
masked position with ``c`` above the threshold when those are at least
the static count, else the static choice.  A committed token is never
masked again.  A pass that finds no mask left is the block's **store
pass**: it commits nothing, its rows are the block's clean K/V, and the
slot moves to the next block, all masked.  What streams is the
contiguous committed prefix; the slot retires when that reaches the
asked length (the answer is cut there: positions of its last block past
that length stay masked and are never committed, so the served answer
is a function of the delivered tokens alone) or holds the
end-of-sequence id.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.paged_attention import (decode_dispatch, paged_decode_attention,
                                   paged_decode_reference)
from ..ops.paged_prefill import (block_causal_positions,
                                 paged_prefill_attention,
                                 paged_prefill_reference, prefill_dispatch)
from ..ops.quant import quantize_named_int8
from .llama import (_embed_lookup, _mask_logits, _matmul, _rope_freqs,
                    apply_rope, rms_norm, scatter_state_rows)
from .mistral4 import _program
from .moe import MoEConfig, init_moe_params, moe_layer

__all__ = ["SdarConfig", "CONFIGS", "COUNTERS", "RECURRENT_STATE",
           "UNSUPPORTED", "MARK_LIVE", "MARK_STORE", "init_params",
           "quantize_params", "forward", "commit_rule", "init_paged_cache",
           "kv_pool_layers", "kv_geometry", "state_bytes_per_slot",
           "layer_kinds", "block_slot_state", "block_pass",
           "prefill_append_paged", "serve_chunk_paged",
           "serve_chunk_mixed", "scatter_state_rows"]

RECURRENT_STATE = False
#: Counters a serve chunk returns beside its windows (no extra sync).
COUNTERS = ("moe_pairs", "moe_pairs_here", "moe_experts_hit")

#: What the engine refuses at construction for this module, each with
#: the piece it lacks: ``(what the model has, {feature: missing})``.
UNSUPPORTED = ("generation by block passes", {
    "mesh": "a sharding rule for the block programs (this model "
            "module has only the single-chip programs)",
    "replica_mesh": "the block-pass programs under the shard_map "
                    "engine (llama_tp has the one-token step only)",
    "adapters": "LoRA factors through the block-pass projections",
    "speculation": "a draft and an acceptance rule for a pass that "
                   "commits out of order (grammar constraints mask "
                   "one next token, and a block has several)",
    "prefix_cache": "publication of a generated block's rows only "
                    "once the block is final (the index would offer "
                    "rows the next pass rewrites)",
    "host_tier": "demotion that waits for a block to be final",
    "spill": "spilled rows that are known to be a final block's",
    "kv_transfer": "an export that leaves out the block in progress",
    "migration": "the block in progress (window, flags, pass count) "
                 "in the migration snapshot",
    "contiguous_layout": "contiguous-cache programs in this model "
                         "module (serve it with PagedContinuousServer)",
})

#: A pass's per-slot mark (``marks`` of a serve chunk): its low
#: ``block_length`` bits say which positions are still masked after the
#: pass; these two, counted from there: the slot was live at the pass's
#: start; the pass was the block's store pass.
MARK_LIVE, MARK_STORE = 0, 1


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    vocab_size: int = 1024
    d_model: int = 128
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: int = 2
    #: Explicit: NOT ``d_model // n_heads`` (2048 / 32 = 64 against the
    #: published 128).
    head_dim: int = 32
    n_experts: int = 8
    moe_top_k: int = 2
    d_ff: int = 64                    # one routed expert's width
    #: ``(first, count)`` of the routed experts held here (None: all).
    experts_held: Optional[Tuple[int, int]] = None
    norm_eps: float = 1e-6
    rope_theta: float = 1e6
    rope_scaling: Optional[Tuple] = None
    max_seq_len: int = 512
    dtype: Any = jnp.bfloat16
    #: The engine's block accounting asks every config (no window here).
    sliding_window: Optional[int] = None
    #: Generation: positions a block, the ``[MASK]`` token's id, and
    #: the defaults of a request that names no schedule.
    block_length: int = 4
    mask_id: int = 1023
    denoise_steps: int = 4
    denoise_dynamic: bool = True
    denoise_threshold: float = 0.9

    @property
    def moe_config(self) -> MoEConfig:
        return MoEConfig(
            d_model=self.d_model, d_ff=self.d_ff,
            n_experts=self.n_experts, top_k=self.moe_top_k,
            capacity_factor=None, dtype=self.dtype, scoring="softmax",
            activation="swiglu", held=self.experts_held)


CONFIGS: Dict[str, SdarConfig] = {
    "sdar_tiny": SdarConfig(dtype=jnp.float32),
    # The same model on the chip that holds experts 2-3 of 8.
    "sdar_tiny_share": SdarConfig(dtype=jnp.float32, experts_held=(2, 2)),
}


def layer_kinds(config: SdarConfig) -> Dict[str, int]:
    return {"attention": config.n_layers, "experts": config.n_layers}


def kv_geometry(config: SdarConfig, quantize_kv: bool):
    """``(head_dim, kv heads, dtype)`` of the block pools."""
    if quantize_kv:
        raise ValueError("a block pass writes its rows by scatter: no "
                         "int8 scale append for a window of rows "
                         "(quantize_kv)")
    return config.head_dim, config.n_kv_heads, config.dtype


def state_bytes_per_slot(config: SdarConfig) -> int:
    """Bytes a slot holds beside its KV blocks: the block in progress
    (:func:`block_slot_state`), a few words."""
    return sum(leaf[0].nbytes for leaf
               in block_slot_state(config, 1).values())


def block_slot_state(config: SdarConfig, slots: int) -> Dict:
    """The leaves a slot's resident state has beside the engine's own
    (``positions`` is the block's first position, ``remaining`` the
    tokens still to deliver): the block in progress and the slot's
    schedule.  Zeros, as numpy: the engine's host mirrors; their device
    copies ride the same dirty-row upload.  ``delivered``: positions of
    the block, from its first, that are given or already streamed."""
    B = config.block_length
    return {"window": np.zeros((slots, B), np.int32),
            "masked": np.zeros((slots, B), bool),
            "delivered": np.zeros((slots,), np.int32),
            "passes": np.zeros((slots,), np.int32),
            "denoise_steps": np.full((slots,), config.denoise_steps,
                                     np.int32),
            "dynamic": np.full((slots,), config.denoise_dynamic, bool),
            "threshold": np.full((slots,), config.denoise_threshold,
                                 np.float32)}


# --------------------------------------------------------------------------- #
# Parameters


def init_params(config: SdarConfig, key) -> Dict:
    c, dt = config, config.dtype
    d, hd = c.d_model, c.head_dim
    keys = jax.random.split(key, c.n_layers + 2)

    def dense(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * shape[-2] ** -0.5).astype(dt)

    layers = []
    for lk in keys[:c.n_layers]:
        lk = jax.random.split(lk, 5)
        layers.append({
            "attn_norm": jnp.ones((d,), dt),
            "ffn_norm": jnp.ones((d,), dt),
            "wq": dense(lk[0], (d, c.n_heads * hd)),
            "wk": dense(lk[1], (d, c.n_kv_heads * hd)),
            "wv": dense(lk[2], (d, c.n_kv_heads * hd)),
            "wo": dense(lk[3], (c.n_heads * hd, d)),
            "q_norm": jnp.ones((hd,), dt),
            "k_norm": jnp.ones((hd,), dt),
            "moe": init_moe_params(c.moe_config, lk[4])})
    return {"embed": jax.random.normal(keys[-2], (c.vocab_size, d)
                                       ).astype(dt),
            "layers": layers,
            "final_norm": jnp.ones((d,), dt),
            "lm_head": dense(keys[-1], (d, c.vocab_size))}


#: The 2-D matrices served int8 weight-only; the router and the
#: experts (3-D leaves) stay as they are.
_INT8_LEAVES = ("wq", "wk", "wv", "wo", "embed", "lm_head")


def quantize_params(params, bits: int = 8) -> Dict:
    if bits != 8:
        raise NotImplementedError("int8 weight-only is the one "
                                  "quantized layout of this model")
    return quantize_named_int8(params, _INT8_LEAVES)


# --------------------------------------------------------------------------- #
# The layer's two halves


def _qkv(layer, config: SdarConfig, normed, positions):
    """``normed (batch, seq, d)`` at ``positions (batch, seq)`` ->
    ``(q (batch, seq, H, hd), k, v (batch, seq, kv, hd))``: each head of
    q and k normalised over its ``hd``, then rotated."""
    c = config
    lead = normed.shape[:2]
    cos, sin = _rope_freqs(c, positions)
    q = _matmul(normed, layer["wq"]).reshape(lead + (c.n_heads,
                                                     c.head_dim))
    k = _matmul(normed, layer["wk"]).reshape(lead + (c.n_kv_heads,
                                                     c.head_dim))
    v = _matmul(normed, layer["wv"]).reshape(lead + (c.n_kv_heads,
                                                     c.head_dim))
    q = apply_rope(rms_norm(q, layer["q_norm"], c.norm_eps), cos, sin)
    k = apply_rope(rms_norm(k, layer["k_norm"], c.norm_eps), cos, sin)
    return q, k, v


def _feed_forward(layer, config: SdarConfig, x, rows=None):
    normed = rms_norm(x, layer["ffn_norm"], config.norm_eps)
    out, counts = moe_layer(layer["moe"], normed, config.moe_config,
                            rows=rows)
    return x + out.astype(x.dtype), counts


def _head(params, config: SdarConfig, x):
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    return _matmul(x, params["lm_head"]).astype(jnp.float32)


def _embed(params, config: SdarConfig, tokens, masked=None):
    if masked is not None:
        tokens = jnp.where(masked, config.mask_id, tokens)
    return _embed_lookup(params, tokens, config.dtype)


@functools.partial(jax.jit, static_argnames=("config",))
def forward(params, tokens, config: SdarConfig, masked=None):
    """Full-sequence forward under the block-causal mask, no cache:
    tokens ``(batch, seq)``, ``masked (batch, seq)`` (positions that
    carry the ``[MASK]`` embedding whatever their id; None: none) ->
    logits ``(batch, seq, vocab)`` f32, row ``p`` the distribution of
    the token AT ``p``."""
    c = config
    batch, seq = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32),
                                 (batch, seq))
    visible = (positions[0][None, :]
               <= block_causal_positions(positions[0],
                                         c.block_length)[:, None])
    group = c.n_heads // c.n_kv_heads
    x = _embed(params, c, tokens, masked)
    for layer in params["layers"]:
        normed = rms_norm(x, layer["attn_norm"], c.norm_eps)
        q, k, v = _qkv(layer, c, normed, positions)
        q = q.reshape(batch, seq, c.n_kv_heads, group, c.head_dim)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                            preferred_element_type=jnp.float32
                            ) * c.head_dim ** -0.5
        weights = jax.nn.softmax(
            jnp.where(visible, scores, -jnp.inf), axis=-1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", weights.astype(v.dtype), v)
        x = x + _matmul(out.reshape(batch, seq, -1),
                        layer["wo"]).astype(x.dtype)
        x, _ = _feed_forward(layer, c, x)
    return _head(params, c, x)


# --------------------------------------------------------------------------- #
# The pool, and the prompt's slices


def init_paged_cache(config: SdarConfig, n_blocks: int,
                     block_size: int = 16, quantize_kv: bool = False,
                     slots: int = 1) -> list:
    """One K/V block pool a layer (``n_blocks`` INCLUDES scratch
    block 0)."""
    del slots
    head_dim, kv_heads, dtype = kv_geometry(config, quantize_kv)
    if block_size % config.block_length:
        raise ValueError(
            f"block_length {config.block_length} must divide the pool "
            f"block {block_size}: a block's rows lie in one pool block")
    shape = (n_blocks, block_size, kv_heads, head_dim)
    return [{"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
            for _ in range(config.n_layers)]


def kv_pool_layers(pool) -> list:
    """The block pools among what :func:`init_paged_cache` returns:
    all of it."""
    return pool


def _prefill_core(params, tokens, pool, tables, start_index,
                  config: SdarConfig, kv_limit, compute_logits):
    """A ``(1, K)`` prompt slice at ``start_index`` (whole pool blocks)
    appended to the row whose table is ``tables (1, width)``, attended
    under the block-causal mask."""
    c = config
    batch, width = tokens.shape
    start_index = jnp.asarray(start_index, jnp.int32)
    positions = jnp.broadcast_to(
        start_index + jnp.arange(width, dtype=jnp.int32), (batch, width))
    cached = jnp.broadcast_to(start_index, (batch,))
    lens = jnp.full((batch,), width, jnp.int32)
    group = c.n_heads // c.n_kv_heads
    use_kernel, interpret = prefill_dispatch(
        c.head_dim, c.n_kv_heads, pool[0]["k"].dtype,
        pool[0]["k"].shape[1], width)
    x = _embed(params, c, tokens)
    pool = list(pool)
    for index, layer in enumerate(params["layers"]):
        normed = rms_norm(x, layer["attn_norm"], c.norm_eps)
        q, k, v = _qkv(layer, c, normed, positions)
        q = q.reshape(batch, width, c.n_kv_heads, group, c.head_dim)
        if use_kernel:
            out, pool[index] = paged_prefill_attention(
                q, k, v, pool[index], tables, cached, lens,
                interpret=interpret, kv_limit=kv_limit,
                mask_block=c.block_length)
        else:
            out, pool[index] = paged_prefill_reference(
                q, k, v, pool[index], tables, cached, lens,
                mask_block=c.block_length)
        x = x + _matmul(out.reshape(batch, width, -1),
                        layer["wo"]).astype(x.dtype)
        x, _ = _feed_forward(layer, c, x)
    return (_head(params, c, x) if compute_logits else None), pool


@_program("prefill_append_paged",
          static_argnames=("config", "kv_limit", "compute_logits"),
          donate_argnames=("pool",))
def _prefill_program(params, tokens, pool, tables, start_index, config,
                     kv_limit, compute_logits):
    return _prefill_core(params, tokens, pool, tables, start_index,
                         config, kv_limit, compute_logits)


def prefill_append_paged(params, tokens, pool, tables, start_index,
                         config: SdarConfig, lora=None, kv_limit=None,
                         compute_logits: bool = True):
    """Admit a ``(1, K)`` prompt slice at ``start_index`` (whole
    blocks); the contract of :func:`.llama.prefill_append_paged`."""
    if lora is not None:
        raise NotImplementedError("no LoRA path in this model module")
    return _prefill_program(params, tokens, pool, tables, start_index,
                            config, kv_limit, compute_logits)


# --------------------------------------------------------------------------- #
# A pass over every slot's block


def _block_core(params, ids, masked, pool, tables, base, active,
                config: SdarConfig):
    """One pass: the ``B`` positions ``base .. base + B - 1`` of every
    slot (``ids (S, B)``, the ``[MASK]`` embedding where ``masked``)
    through every layer.  Each layer writes the window's ``B`` K/V rows
    over the block's rows in the pool, then attends: every query of
    the window sees all keys up to the block's last, so the ``B``
    queries ride ONE call of the paged decode kernel stacked beside a
    kv head's group (``B x group`` query rows a kv head, the query
    position the block's last).  ``tables`` / ``base`` of an idle slot
    already point at the scratch block.  Returns ``(logits (S, B,
    vocab), pool, int32 (3,) expert counts summed over the layers)``."""
    c = config
    slots, B = ids.shape
    group = c.n_heads // c.n_kv_heads
    block_size = pool[0]["k"].shape[1]
    positions = base[:, None] + jnp.arange(B, dtype=jnp.int32)[None, :]
    block_ids = jnp.take_along_axis(tables, positions // block_size,
                                    axis=1)
    offsets = positions % block_size
    last = base + B - 1
    use_kernel, interpret = decode_dispatch(c.head_dim, c.n_kv_heads,
                                            pool[0]["k"].dtype)
    x = _embed(params, c, ids, masked)
    pool = list(pool)
    counts = jnp.zeros((3,), jnp.int32)
    for index, layer in enumerate(params["layers"]):
        normed = rms_norm(x, layer["attn_norm"], c.norm_eps)
        q, k, v = _qkv(layer, c, normed, positions)
        held = pool[index]
        held = {"k": held["k"].at[block_ids, offsets].set(
                    k.astype(held["k"].dtype)),
                "v": held["v"].at[block_ids, offsets].set(
                    v.astype(held["v"].dtype))}
        pool[index] = held
        # (S, B, kv, group, hd) -> (S, kv, B x group, hd)
        stacked = q.reshape(slots, B, c.n_kv_heads, group, c.head_dim
                            ).transpose(0, 2, 1, 3, 4).reshape(
            slots, c.n_kv_heads, B * group, c.head_dim)
        if use_kernel:
            out = paged_decode_attention(stacked, held["k"], held["v"],
                                         tables, last,
                                         interpret=interpret)
        else:
            out = paged_decode_reference(stacked, held["k"], held["v"],
                                         tables, last)
        out = out.reshape(slots, c.n_kv_heads, B, group, c.head_dim
                          ).transpose(0, 2, 1, 3, 4).reshape(slots, B, -1)
        x = x + _matmul(out.astype(x.dtype), layer["wo"]).astype(x.dtype)
        x, layer_counts = _feed_forward(layer, c, x, rows=active)
        counts = counts + layer_counts
    return _head(params, c, x), pool, counts


def commit_rule(confidence, masked, passes, denoise_steps, dynamic,
                threshold):
    """Which masked positions a pass commits, ``(S, B)`` bool.

    ``confidence (S, B)`` f32 (the picked token's probability),
    ``masked (S, B)``; per slot ``passes`` (denoise passes this block
    has had), ``denoise_steps`` T, ``dynamic`` and ``threshold``.
    Static: the ``B // T`` (+1 while ``passes < B mod T``) masked
    positions of largest confidence, ties to the lower position, never
    more than are masked (a block past its T passes commits what is
    left).  Dynamic: every masked position above the threshold when
    those are at least the static count, else the static choice."""
    B = masked.shape[1]
    steps = jnp.clip(denoise_steps, 1, B)
    count = B // steps + (passes < B % steps)
    count = jnp.where(passes >= steps, B, count)
    conf = jnp.where(masked, confidence.astype(jnp.float32), -jnp.inf)
    index = jnp.arange(B)
    ahead = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None])
        & (index[None, None, :] < index[None, :, None]))
    rank = ahead.sum(-1)                     # (S, B): 0 is the largest
    static = masked & (rank < count[:, None])
    high = masked & (conf > threshold[:, None])
    take_high = dynamic & (high.sum(-1) >= count)
    return jnp.where(take_high[:, None], high, static)


def _pick(logits, key, temps, tops, sampled: bool):
    """``(x0, confidence)`` of ``logits (S, B, vocab)``: the picked
    token at every position and its probability under the distribution
    it was picked from (greedy rows: the plain softmax)."""
    slots, B, vocab = logits.shape
    greedy = logits.argmax(-1).astype(jnp.int32)
    best = jnp.exp(logits.max(-1) - jax.nn.logsumexp(logits, axis=-1))
    if not sampled:
        return greedy, best
    scaled = _mask_logits(logits.reshape(slots * B, vocab),
                          jnp.repeat(temps, B)[:, None], 0,
                          jnp.repeat(tops, B)[:, None])
    drawn = jax.random.categorical(key, scaled).astype(jnp.int32)
    prob = jnp.exp(jnp.take_along_axis(
        jax.nn.log_softmax(scaled, axis=-1), drawn[:, None], axis=1))
    hot = (temps > 0)[:, None]
    return (jnp.where(hot, drawn.reshape(slots, B), greedy),
            jnp.where(hot, prob.reshape(slots, B), best))


_BLOCK_LEAVES = ("window", "masked", "delivered", "passes")


def _pass(params, state, carried, key, config: SdarConfig, eos_id: int,
          sampled: bool, with_logits: bool = False):
    """One pass of every live slot and what it does to the slots'
    state: pick, rule, commit, the delivered prefix, retirement, and
    for a slot that began the pass with no mask the move to its next
    block.  ``carried = (block, pool, counts)``, ``block`` the leaves
    that change (``positions``, ``active``, ``remaining`` and
    :data:`_BLOCK_LEAVES`).  Returns ``(carried, (window (S, B), mark
    (S,)))`` (and the logits for a test)."""
    block, pool, counts = carried
    c = config
    B = c.block_length
    tables = state["tables"]
    slots = tables.shape[0]
    block_size = pool[0]["k"].shape[1]
    active, base = block["active"], block["positions"]
    window, masked = block["window"], block["masked"]
    delivered, remaining = block["delivered"], block["remaining"]
    index = jnp.arange(B, dtype=jnp.int32)[None, :]
    # Positions of the answer's last block past the asked length stay
    # masked and are never committed: the served answer is a function
    # of the delivered tokens alone.
    open_ = masked & (index < (delivered + remaining)[:, None])
    denoising = open_.any(-1)
    # Idle rows write the scratch block, each at rows of its own.
    write_tables = jnp.where(active[:, None], tables, 0)
    write_base = jnp.where(
        active, base,
        jnp.arange(slots, dtype=jnp.int32) * B % block_size)
    logits, pool, pass_counts = _block_core(
        params, window, masked, pool, write_tables, write_base, active,
        c)
    x0, confidence = _pick(logits, key, state["temps"], state["tops"],
                           sampled)
    commit = commit_rule(confidence, open_, block["passes"],
                         state["denoise_steps"], state["dynamic"],
                         state["threshold"]) & active[:, None]
    window = jnp.where(commit, x0, window)
    masked = masked & ~commit
    # What streams: the committed prefix past what was delivered.
    prefix = jnp.where(masked, index, B).min(-1)
    fresh = (index >= delivered[:, None]) & (index < prefix[:, None]) & (
        index < (delivered + remaining)[:, None])
    newly = jnp.where(active, fresh.sum(-1), 0)
    done = remaining - newly <= 0
    if eos_id >= 0:
        done |= (fresh & (window == eos_id)).any(-1)
    stored = active & ~denoising
    mark = ((masked.astype(jnp.int32) << index).sum(-1)
            + (active.astype(jnp.int32) << (B + MARK_LIVE))
            + (stored.astype(jnp.int32) << (B + MARK_STORE)))
    block = dict(
        window=window,
        masked=jnp.where(stored[:, None], True, masked),
        positions=jnp.where(stored, base + B, base),
        delivered=jnp.where(stored, 0, jnp.where(
            active, jnp.maximum(delivered, prefix), delivered)),
        passes=jnp.where(stored, 0, jnp.where(
            active, block["passes"] + 1, block["passes"])),
        remaining=remaining - newly,
        active=active & ~done)
    carried = (block, pool, counts + pass_counts)
    if with_logits:
        return carried, (window, mark), logits
    return carried, (window, mark)


def _carried(state, pool):
    block = {name: state[name] for name
             in _BLOCK_LEAVES + ("positions", "active", "remaining")}
    return block, pool, jnp.zeros((3,), jnp.int32)


@_program("block_pass", static_argnames=("config", "eos_id", "sampled"),
          donate_argnames=("pool",))
def block_pass(params, state, pool, config: SdarConfig, eos_id: int = -1,
               sampled: bool = False, rng_key=None):
    """ONE pass as a program of its own, with its logits: ``(logits
    (S, B, vocab), window (S, B), mark (S,), new_state, pool)``.  What
    a test compares with the reference pass by pass; the served path
    runs :func:`serve_chunk_paged` and returns ids."""
    if rng_key is None:
        rng_key = jax.random.PRNGKey(0)
    (block, pool, _), (window, mark), logits = _pass(
        params, state, _carried(state, pool), rng_key, config, eos_id,
        sampled, with_logits=True)
    return logits, window, mark, dict(state, **block), pool


def _serve(params, state, pool, num_steps, config: SdarConfig, eos_id,
           sampled, rng_key):
    """``num_steps`` passes in a device loop.  Returns ``(windows (S,
    steps, B), marks (S, steps), new_state, pool, counters)``: after
    each pass a slot's block as it then stood and its mark (masked
    bits, :data:`MARK_LIVE`, :data:`MARK_STORE`)."""
    if rng_key is None:
        rng_key = jax.random.PRNGKey(0)

    def body(carry, _):
        carried, key = carry
        key, pass_key = jax.random.split(key)
        carried, out = _pass(params, state, carried, pass_key, config,
                             eos_id, sampled)
        return (carried, key), out

    ((block, pool, counts), _), (windows, marks) = jax.lax.scan(
        body, (_carried(state, pool), rng_key), None, length=num_steps)
    chunk_counters = {"moe_pairs": counts[2] * config.moe_top_k,
                      "moe_pairs_here": counts[0],
                      "moe_experts_hit": counts[1]}
    return (windows.transpose(1, 0, 2), marks.T, dict(state, **block),
            pool, chunk_counters)


@functools.partial(jax.jit,
                   static_argnames=("config", "num_steps", "eos_id",
                                    "sampled"),
                   donate_argnames=("pool",))
def serve_chunk_paged(params, state, pool, num_steps,
                      config: SdarConfig, eos_id: int = -1,
                      sampled: bool = False, rng_key=None,
                      lora_shared=None):
    """``num_steps`` block passes of every live slot (:func:`_serve`):
    where :func:`.llama.serve_chunk_paged` returns a token a step and
    a count, this returns a window a pass and its mark."""
    if lora_shared is not None:
        raise NotImplementedError("no LoRA path in this model module")
    return _serve(params, state, pool, num_steps, config, eos_id,
                  sampled, rng_key)


@_program("serve_chunk_mixed",
          static_argnames=("config", "num_steps", "eos_id", "sampled",
                           "prefill_kv_limit"),
          donate_argnames=("pool",))
def _mixed_program(params, state, pool, prefill_tokens, prefill_row,
                   prefill_start, num_steps, config, eos_id, sampled,
                   rng_key, prefill_kv_limit):
    tables_row = jax.lax.dynamic_slice_in_dim(
        state["tables"], jnp.asarray(prefill_row, jnp.int32), 1, axis=0)
    _, pool = _prefill_core(params, prefill_tokens, pool, tables_row,
                            prefill_start, config, prefill_kv_limit,
                            False)
    return _serve(params, state, pool, num_steps, config, eos_id,
                  sampled, rng_key)


def serve_chunk_mixed(params, state, pool, prefill_tokens, prefill_row,
                      prefill_start, num_steps, config: SdarConfig,
                      eos_id: int = -1, sampled: bool = False,
                      rng_key=None, lora_shared=None,
                      prefill_kv_limit=None):
    """One prefill slice of the slot ``prefill_row`` (idle among the
    passes' rows until its last slice lands), then the passes, as one
    program."""
    if lora_shared is not None:
        raise NotImplementedError("no LoRA path in this model module")
    return _mixed_program(params, state, pool, prefill_tokens,
                          prefill_row, prefill_start, num_steps, config,
                          eos_id, sampled, rng_key, prefill_kv_limit)
