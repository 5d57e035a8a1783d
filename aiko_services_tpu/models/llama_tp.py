"""Tensor-parallel serving engine: one replica = one mesh.

shard_map mirrors of the paged serving entry points in
:mod:`.llama` (``serve_chunk_paged`` / ``serve_chunk_mixed`` /
``prefill_append_paged``) that run TP-sharded over a
:class:`~..parallel.mesh.ReplicaMesh`:

* Every 2-D weight leaf is sharded on its LAST (output-feature) axis —
  one uniform rule that covers dense bf16 weights, int8 ``{"q","s"}``
  and int4 ``{"q4","s"}`` trees (scales are 2-D with the output axis
  last), the embedding (feature-sharded rows), and the LM head
  (vocab-sharded logits).  Each local matmul therefore keeps the FULL
  contraction dimension and computes a contiguous slice of output
  columns; the only collective is an ``all_gather`` of those columns.
  An all-gather is pure data movement — no partial-sum reduction whose
  float ordering could differ from the single-chip program — which is
  what makes TP greedy decode token-identical to single-chip greedy
  (the exact-equality gate in tests/test_tp_serving.py).  The
  row-parallel/``reduce-scatter`` layout (see
  :mod:`..parallel.collective_matmul`, usable on TPU to overlap the
  collective with the matmul) trades that exactness for bandwidth and
  is deliberately NOT used here.

* The paged KV pool shards along its kv-head axis (dim 2) as GLOBAL
  ``jax.Array``s — host-side block bookkeeping (prefix-cache
  scatter/gather, kvstore export/import) keeps operating on full-width
  arrays and jax resolves blocks to per-shard slices.  Because wq/wk/wv
  shard by whole heads (contiguous output ranges), shard ``i`` computes
  exactly q-heads ``[i*h/tp, (i+1)*h/tp)`` and kv-heads
  ``[i*kv/tp, (i+1)*kv/tp)`` — and since ``tp | n_kv_heads``, every
  shard's q-head range covers whole GQA groups of its local kv heads.
  Attention is a per-kv-head computation, so it stays entirely local
  between the QKV projections and the output-projection gather: the
  pool is NEVER gathered across shards (jaxpr-guarded).

* Per-slot decode state (tokens/positions/active/remaining/tables) is
  replicated, so the host admission/commit/dirty-sync protocol is
  byte-identical to the single-chip server, and the tiny per-step
  (tokens, counts) sync stays tiny.

* SECOND mesh axis (2-D ReplicaMesh).  ``sp`` (sequence parallel):
  one chunked-admission dispatch carries ``sp`` consecutive prompt
  chunks, sharded over the axis — each shard prefills its own chunk
  at its own absolute offset, all-gathers the window's K/V over
  ``sp`` (pure data movement) and writes the FULL window into its
  pool copy, so the pool stays sharded on ``tp`` and bitwise
  REPLICATED on ``sp``.  Attention for chunk ``j`` runs with
  ``cached_lens = start + j*W`` — exactly the sequential chunk-``j``
  program — so sp-sharded prefill is bitwise the single-chip chunked
  admission (invariant 19).  ``ep`` (expert parallel): MoE expert
  weights shard ``P(ep, None, tp)``; the dispatch/combine einsums run
  on exact expert/feature slices and only all-gathers recombine them,
  so MoE TP/EP greedy decode equals single-chip bit for bit — the old
  blanket MoE rejection is gone.  Decode runs replicated over the
  second axis (every sp/ep row computes identical tokens).

* LoRA adapters compose with TP (multi-tenant serving): the stacked
  factor tree shards by the SAME output-column rule as the base
  weights — A factors and the scale replicate, B factors column-shard
  on d_out (:func:`tp_lora_specs` / :func:`shard_lora`).  Because
  :func:`.llama._lora_delta` is two PINNED einsums, the rank-r hidden
  ``x@A`` is computed identically on every shard and each output
  column of ``hidden@B`` is an independent r-dot — a shard's local
  delta is bitwise the column slice of the single-chip delta, added
  before the same all-gather the base matmul takes.  TP LoRA greedy
  decode is therefore token-identical to single-chip LoRA serving
  (tests/test_multi_lora.py TP gates).

``overlap=True`` (opt-in) routes the dense-MLP
down-projection through :func:`..parallel.collective_matmul.
matmul_reducescatter` — the row-parallel lossy-LAYOUT path whose
ring partial sums reorder float addition vs single-chip, trading
exactness for ICI/compute overlap on real hardware.  Off by default;
every exactness test pins the exact all-gather path.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.paged_attention import paged_decode_attention
from ..ops.paged_prefill import (paged_prefill_attention,
                                 paged_verify_attention)
from . import llama
from .llama import LlamaConfig

__all__ = ["TPEngine", "tp_param_specs", "tp_pool_specs",
           "tp_lora_specs", "shard_params", "shard_pool", "shard_lora",
           "replicate", "scatter_state_rows"]


# --------------------------------------------------------------------------- #
# Sharding layout


def tp_param_specs(params, axis: str = "tp", ep_axis=None,
                   overlap: bool = False):
    """Output-axis PartitionSpecs for an ACTUAL parameter tree (dense
    or quantized): every 2-D leaf shards its last axis, everything
    else (1-D norm vectors) replicates.  Operating on the real tree —
    not the config — means one rule serves bf16, int8 and int4
    layouts identically.

    Two structured exceptions to the generic rule:

    * MoE expert weights (3-D ``(E, d, f)`` / ``(E, f, d)`` leaves
      under ``layers[i]["moe"]``) shard ``P(ep_axis, None, axis)`` AT
      REST — experts over the second mesh axis (replicated when
      ``ep_axis`` is None), per-expert features over ``tp`` — and are
      all-gathered per layer by :func:`_tp_moe_block` (weight-gathered
      EP).  The router REPLICATES (``moe_param_specs``): the gathered
      forward runs the exact single-chip ``moe_ffn`` program, which
      needs the full router resident.
    * ``overlap=True`` re-lays the dense MLP ``w_down`` row-parallel
      (``P(axis, None)`` on its contraction dim) for the
      reduce-scatter overlap path — lossy layout, opt-in.
    """
    specs = jax.tree.map(
        lambda leaf: P(None, axis) if getattr(leaf, "ndim", 0) == 2
        else P(), params)
    for layer, layer_specs in zip(params.get("layers", ()),
                                  specs.get("layers", ())):
        if "moe" in layer:
            from .moe import moe_param_specs
            moe_specs = moe_param_specs(ep_axis=ep_axis,
                                        feature_axis=axis)
            for name, leaf in layer["moe"].items():
                spec = moe_specs.get(name, P())
                # A quantized router is a {"q","s"} subtree — every
                # leaf under the name takes the same spec.
                layer_specs["moe"][name] = jax.tree.map(
                    lambda _leaf: spec, leaf)
        if overlap and getattr(layer.get("w_down"), "ndim", 0) == 2:
            layer_specs["w_down"] = P(axis, None)
    return specs


def tp_pool_specs(pool, axis: str = "tp"):
    """Kv-head-axis PartitionSpecs for a paged pool (list of per-layer
    ``{"k","v"[,"ks","vs"]}`` dicts): the 4-D k/v buffers
    ``(n_blocks, block_size, kv_heads, head_dim)`` shard dim 2, the
    3-D int8 scales shard their trailing kv-head dim."""
    return jax.tree.map(
        lambda buf: P(None, None, axis, None) if buf.ndim == 4
        else P(None, None, axis), pool)


def shard_params(params, mesh: Mesh, axis: str = "tp", ep_axis=None,
                 overlap: bool = False):
    """Lay a parameter tree out over the replica mesh (global arrays,
    output axis sharded; MoE experts over ``ep_axis`` when given)."""
    return jax.tree.map(
        lambda leaf, spec: jax.device_put(leaf,
                                          NamedSharding(mesh, spec)),
        params, tp_param_specs(params, axis, ep_axis=ep_axis,
                               overlap=overlap))


def tp_lora_specs(lora, axis: str = "tp"):
    """PartitionSpecs for a stacked-adapter tree
    (:func:`.lora.stack_adapters` layout): A factors
    ``(n_adapters, d_in, r)`` and the scalar scale REPLICATE — the
    rank-r hidden ``x@A`` must be computed identically on every shard
    — while B factors ``(n_adapters, r, d_out)`` column-shard their
    output axis exactly like the base weight they adapt, so the local
    delta columns line up with the local base-matmul columns.  An
    ``ids`` leaf (per-row adapter indices, present on the verify /
    standalone-prefill call shapes) replicates like the rest of the
    decode state."""
    specs = {
        "scale": P(),
        "layers": [{target: {"a": P(), "b": P(None, None, axis)}
                    for target in layer}
                   for layer in lora["layers"]],
    }
    if "ids" in lora:
        specs["ids"] = P()
    return specs


def shard_lora(lora_shared, mesh: Mesh, axis: str = "tp"):
    """Lay a stacked-adapter tree out over the replica mesh (A + scale
    replicated, B output-column-sharded).  The python-float scale stays
    host-side — jit traces it as the same weak-typed scalar the
    single-chip program folds in."""
    specs = tp_lora_specs(lora_shared, axis)

    def put(leaf, spec):
        if isinstance(leaf, (int, float)):
            return leaf
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree.map(put, lora_shared, specs)


def shard_pool(pool, mesh: Mesh, axis: str = "tp"):
    """Lay a paged pool out over the replica mesh (global arrays,
    kv-head axis sharded)."""
    return jax.tree.map(
        lambda buf, spec: jax.device_put(buf, NamedSharding(mesh, spec)),
        pool, tp_pool_specs(pool, axis))


def replicate(tree, mesh: Mesh):
    """Replicate a pytree onto every device of the replica mesh (the
    per-slot decode state layout)."""
    sharding = NamedSharding(mesh, P())
    return jax.tree.map(lambda leaf: jax.device_put(leaf, sharding),
                        tree)


def scatter_state_rows(state, rows, packet, mesh: Mesh):
    """TP twin of :func:`.llama.scatter_state_rows`: the compact
    dirty-row packet (tiny numpy rows) is explicitly replicated onto
    the replica mesh before the jitted scatter, so the merged decode
    state stays a replicated ``jax.Array`` that shard_map's ``P()``
    in_specs accept — same contract as :func:`replicate`."""
    sharding = NamedSharding(mesh, P())
    rows = jax.device_put(rows, sharding)
    packet = jax.tree.map(
        lambda leaf: jax.device_put(leaf, sharding), packet)
    return llama.scatter_state_rows(state, rows, packet)


# --------------------------------------------------------------------------- #
# Shard-local model mirrors
#
# These mirror llama's paged decode/prefill cores LINE FOR LINE, with
# three mechanical changes: head counts become shard-local
# (h/tp, kv/tp), LoRA factors ride the SAME column sharding as the
# base weight they adapt (A + scale replicated, B column-sharded —
# see :func:`tp_lora_specs`), and an output-column all_gather follows
# each matmul whose result the next (replicated-input) op needs in
# full.  f32 cast discipline is kept exactly where the originals cast
# — every gathered value is bitwise the concatenation of per-shard
# values, so the math matches the single-chip program bit for bit.
# LoRA exactness leans on llama._lora_delta's two pinned einsums: the
# rank-r hidden x@A depends only on replicated inputs (identical on
# every shard), and each output column of hidden@B is an independent
# r-length dot — a shard holding B's column slice computes exactly its
# column slice of the single-chip delta, added BEFORE the gather.


def _gather_cols(x, axis_name: str):
    """All-gather the local output columns back to the full feature
    axis (pure data movement — the exactness-preserving collective)."""
    return jax.lax.all_gather(x, axis_name, axis=x.ndim - 1, tiled=True)


def _tp_embed(params, tokens, config: LlamaConfig, axis: str):
    return _gather_cols(
        llama._embed_lookup(params, tokens, config.dtype), axis)


def _tp_lm_head(params, config: LlamaConfig, axis: str, x):
    x = llama.rms_norm(x, params["final_norm"], config.norm_eps)
    logits = llama._matmul(x, params["lm_head"]).astype(jnp.float32)
    return _gather_cols(logits, axis)


def _tp_mlp_block(layer, config: LlamaConfig, axis: str, x,
                  ep_axis=None, ep: int = 1, overlap: bool = False):
    if "moe" in layer:
        return _tp_moe_block(layer, config, axis, x, ep_axis, ep)
    normed = llama.rms_norm(x, layer["mlp_norm"], config.norm_eps)
    gate = jax.nn.silu(
        llama._matmul(normed, layer["w_gate"]).astype(jnp.float32))
    up = llama._matmul(normed, layer["w_up"]).astype(jnp.float32)
    if overlap:
        # Opt-in lossy-layout path: w_down is laid out row-parallel
        # (P(axis, None) — its CONTRACTION rows match the act columns
        # this shard already holds), so the down-projection skips the
        # act gather entirely and reduce-scatters ring partial sums
        # behind the matmuls.  Partial-sum float order differs from
        # the single-chip program — opt-in, never the default.
        from ..parallel.collective_matmul import matmul_reducescatter
        act = (gate * up).astype(x.dtype)
        b, s, fl = act.shape
        down = matmul_reducescatter(act.reshape(b * s, fl),
                                    layer["w_down"], axis)
        return x + _gather_cols(down, axis).reshape(x.shape)
    act = _gather_cols((gate * up).astype(x.dtype), axis)
    return x + _gather_cols(llama._matmul(act, layer["w_down"]), axis)


def _tp_moe_block(layer, config: LlamaConfig, axis: str, x,
                  ep_axis=None, ep: int = 1):
    """Shard-local mirror of ``llama._mlp_block``'s MoE branch:
    WEIGHT-GATHERED expert parallelism.

    The 3-D expert weights live SHARDED at rest — experts over the
    ``ep`` mesh axis, per-expert feature columns over ``tp`` (that is
    the HBM-capacity win: each chip holds ``E/ep`` experts' columns).
    The forward pass all-gathers the expert tree (tiled all-gathers =
    pure data movement) and then runs the EXACT single-chip
    :func:`..models.moe.moe_ffn` program on the full tree, replicated.

    Why not compute-sharded dispatch (all-to-all)?  Exactness.  The
    XLA backend does not guarantee the same bits for a re-decomposed
    MoE graph — measured on CPU, even an op-by-op re-statement of
    ``moe_ffn``'s own einsums (barriered, same shapes) diverges from
    the fused single-chip program in the last bf16 ulp.  Running the
    same traced ``moe_ffn`` on identical full inputs is the only
    layout for which 2-D greedy ≡ single-chip holds BITWISE
    (invariants 9 + 19); compute-sharded token dispatch is a
    documented lossy-layout future step, same bucket as the
    ``overlap`` matmul path."""
    from .moe import moe_ffn
    moe, mcfg = layer["moe"], config.moe_config
    normed = llama.rms_norm(x, layer["mlp_norm"], config.norm_eps)
    full = dict(moe)
    for name in ("w_gate", "w_up", "w_down"):
        w = moe[name]
        if ep > 1:
            w = jax.lax.all_gather(w, ep_axis, axis=0, tiled=True)
        # Feature columns gather over tp (axis size 1 is a no-op).
        full[name] = jax.lax.all_gather(w, axis, axis=2, tiled=True)
    out = moe_ffn(full, normed, mcfg)
    return x + out.astype(x.dtype)


def _tp_attention_decode_paged(layer, config: LlamaConfig, tp: int,
                               axis: str, x, cos, sin, pool_layer,
                               tables, positions, lora=None,
                               lora_layer=None):
    """Shard-local mirror of ``llama._attention_decode_paged``:
    projections produce this shard's contiguous head range, the pool
    write and the attention kernel/reference run on the LOCAL kv-head
    slice, and only the attention output's feature columns gather
    before the output projection.  ``lora_layer`` holds this shard's
    column slice of the stacked B factors (A replicated), so each
    ``_lora_matmul`` delta lands on exactly the local output columns
    — added BEFORE the gather, like the base matmul's columns."""
    batch, seq = x.shape[:2]
    h, kv = config.n_heads // tp, config.n_kv_heads // tp
    hd = config.head_dim
    normed = llama.rms_norm(x, layer["attn_norm"], config.norm_eps)
    q = llama._lora_matmul(normed, layer["wq"], lora_layer, "wq",
                           lora).reshape(batch, seq, h, hd)
    k = llama._lora_matmul(normed, layer["wk"], lora_layer, "wk",
                           lora).reshape(batch, seq, kv, hd)
    v = llama._lora_matmul(normed, layer["wv"], lora_layer, "wv",
                           lora).reshape(batch, seq, kv, hd)
    q = llama.apply_rope(q, cos, sin)
    k = llama.apply_rope(k, cos, sin)
    new_pool = llama._paged_write_rows(pool_layer, k, v, tables,
                                       positions)
    use_kernel, interpret = llama.decode_dispatch(
        hd, kv, new_pool["k"].dtype)
    q_g = q.reshape(batch, seq, kv, h // kv, hd)
    if use_kernel:
        out = paged_decode_attention(
            q_g[:, 0], new_pool["k"], new_pool["v"], tables, positions,
            ks=new_pool.get("ks"), vs=new_pool.get("vs"),
            window=config.sliding_window, interpret=interpret)[:, None]
    else:
        gathered = llama._paged_gather(new_pool, tables)
        out = llama._cached_gqa_attention(q_g, gathered,
                                          positions[:, None], hd,
                                          window=config.sliding_window)
    out = _gather_cols(out.reshape(batch, seq, h * hd), axis)
    attn = _gather_cols(
        llama._lora_matmul(out, layer["wo"], lora_layer, "wo", lora),
        axis)
    return x + attn.astype(x.dtype), new_pool


def _lora_layers(lora, n_layers: int):
    """Per-layer factor dicts (or Nones) matching llama's iteration."""
    return lora["layers"] if lora else [None] * n_layers


def _tp_decode_core_paged(params, token, pool, tables, positions,
                          config: LlamaConfig, tp: int, axis: str,
                          lora=None, ep_axis=None, ep: int = 1,
                          overlap: bool = False):
    positions_2d = positions[:, None]
    cos, sin = llama._rope_freqs(config, positions_2d)
    x = _tp_embed(params, token, config, axis)
    new_pool = []
    lora_layers = _lora_layers(lora, len(pool))
    for layer, pool_layer, lora_layer in zip(params["layers"], pool,
                                             lora_layers):
        x, layer_pool = _tp_attention_decode_paged(
            layer, config, tp, axis, x, cos, sin, pool_layer, tables,
            positions, lora=lora, lora_layer=lora_layer)
        new_pool.append(layer_pool)
        x = _tp_mlp_block(layer, config, axis, x, ep_axis=ep_axis,
                          ep=ep, overlap=overlap)
    logits = _tp_lm_head(params, config, axis, x)
    return logits, new_pool


def _tp_prefill_append_core(params, tokens, pool, tables, start_index,
                            config: LlamaConfig, tp: int, axis: str,
                            lora=None, kv_limit=None,
                            compute_logits: bool = False,
                            ep_axis=None, ep: int = 1,
                            overlap: bool = False):
    """Shard-local mirror of ``llama._prefill_append_core``: the
    chunk's K/V land in the LOCAL pool slice, append attention runs
    per local kv head, activations gather after each projection."""
    batch, K = tokens.shape
    h, kv = config.n_heads // tp, config.n_kv_heads // tp
    hd = config.head_dim
    start_index = jnp.asarray(start_index, jnp.int32)
    positions_b = jnp.broadcast_to(
        start_index + jnp.arange(K, dtype=jnp.int32), (batch, K))
    cached_lens = jnp.broadcast_to(start_index, (batch,))
    chunk_lens = jnp.full((batch,), K, jnp.int32)
    cos, sin = llama._rope_freqs(config, positions_b)
    x = _tp_embed(params, tokens, config, axis)
    use_kernel, interpret = llama.prefill_dispatch(
        hd, kv, pool[0]["k"].dtype, pool[0]["k"].shape[1], K)
    new_pool = []
    lora_layers = _lora_layers(lora, len(pool))
    for layer, pool_layer, lora_layer in zip(params["layers"], pool,
                                             lora_layers):
        normed = llama.rms_norm(x, layer["attn_norm"], config.norm_eps)
        q = llama._lora_matmul(normed, layer["wq"], lora_layer, "wq",
                               lora).reshape(batch, K, h, hd)
        k = llama._lora_matmul(normed, layer["wk"], lora_layer, "wk",
                               lora).reshape(batch, K, kv, hd)
        v = llama._lora_matmul(normed, layer["wv"], lora_layer, "wv",
                               lora).reshape(batch, K, kv, hd)
        q = llama.apply_rope(q, cos, sin)
        k = llama.apply_rope(k, cos, sin)
        q_g = q.reshape(batch, K, kv, h // kv, hd)
        if use_kernel:
            out, pool_layer = paged_prefill_attention(
                q_g, k, v, pool_layer, tables, cached_lens, chunk_lens,
                window=config.sliding_window, interpret=interpret,
                kv_limit=kv_limit)
        else:
            pool_layer = llama._paged_write_slab(pool_layer, k, v,
                                                 tables, positions_b)
            gathered = llama._paged_gather(pool_layer, tables)
            out = llama._cached_gqa_attention(
                q_g, gathered, positions_b, hd,
                window=config.sliding_window)
        new_pool.append(pool_layer)
        out = _gather_cols(out.reshape(batch, K, h * hd), axis)
        x = x + _gather_cols(
            llama._lora_matmul(out, layer["wo"], lora_layer, "wo",
                               lora), axis).astype(x.dtype)
        x = _tp_mlp_block(layer, config, axis, x, ep_axis=ep_axis,
                          ep=ep, overlap=overlap)
    if not compute_logits:
        return None, new_pool
    return _tp_lm_head(params, config, axis, x), new_pool


def _tp_sp_prefill_core(params, tokens, pool, tables, start_index,
                        config: LlamaConfig, tp: int, axis: str,
                        sp_axis: str, sp: int, lora=None, kv_limit=None,
                        ep_axis=None, ep: int = 1,
                        overlap: bool = False):
    """Sequence-parallel chunked-prefill core: the dispatch window
    ``(batch, sp*W)`` arrives sharded over ``sp_axis`` — this shard
    holds chunk ``j = axis_index(sp_axis)`` of width ``W`` at absolute
    start ``start_index + j*W``.  Per layer:

    * project this chunk's q/k/v (tp-local heads), rope at the chunk's
      own absolute positions;
    * all-gather the WINDOW's K/V over ``sp`` (pure data movement) and
      slab-write all ``sp`` chunks into the local pool copy — the pool
      is sharded on ``tp`` and replicated on ``sp``, and every copy
      receives bitwise the same rows, so the replicas never diverge;
    * run the SAME append attention as the sequential core with
      ``cached_lens = start_index + j*W``: rows of later chunks sit
      beyond the absolute-position mask / cached-length bound, so
      chunk ``j``'s math is bitwise the sequential chunk-``j``
      dispatch of the single-chip server (invariant 19) — the
      sp window just runs all ``sp`` chunk programs at once.

    The kernel path quantizes int8 rows with the slab writer's own
    per-row absmax (see ops/paged_prefill), so re-writing this shard's
    own chunk leaves every sp copy byte-identical too."""
    batch, W = tokens.shape
    h, kv = config.n_heads // tp, config.n_kv_heads // tp
    hd = config.head_dim
    start_index = jnp.asarray(start_index, jnp.int32)
    j = jax.lax.axis_index(sp_axis).astype(jnp.int32)
    my_start = start_index + j * W
    positions_b = jnp.broadcast_to(
        my_start + jnp.arange(W, dtype=jnp.int32), (batch, W))
    win_positions = jnp.broadcast_to(
        start_index + jnp.arange(sp * W, dtype=jnp.int32),
        (batch, sp * W))
    cached_lens = jnp.broadcast_to(my_start, (batch,))
    chunk_lens = jnp.full((batch,), W, jnp.int32)
    cos, sin = llama._rope_freqs(config, positions_b)
    x = _tp_embed(params, tokens, config, axis)
    use_kernel, interpret = llama.prefill_dispatch(
        hd, kv, pool[0]["k"].dtype, pool[0]["k"].shape[1], W)
    new_pool = []
    lora_layers = _lora_layers(lora, len(pool))
    for layer, pool_layer, lora_layer in zip(params["layers"], pool,
                                             lora_layers):
        normed = llama.rms_norm(x, layer["attn_norm"], config.norm_eps)
        q = llama._lora_matmul(normed, layer["wq"], lora_layer, "wq",
                               lora).reshape(batch, W, h, hd)
        k = llama._lora_matmul(normed, layer["wk"], lora_layer, "wk",
                               lora).reshape(batch, W, kv, hd)
        v = llama._lora_matmul(normed, layer["wv"], lora_layer, "wv",
                               lora).reshape(batch, W, kv, hd)
        q = llama.apply_rope(q, cos, sin)
        k = llama.apply_rope(k, cos, sin)
        k_win = jax.lax.all_gather(k, sp_axis, axis=1, tiled=True)
        v_win = jax.lax.all_gather(v, sp_axis, axis=1, tiled=True)
        pool_layer = llama._paged_write_slab(pool_layer, k_win, v_win,
                                             tables, win_positions)
        q_g = q.reshape(batch, W, kv, h // kv, hd)
        if use_kernel:
            out, pool_layer = paged_prefill_attention(
                q_g, k, v, pool_layer, tables, cached_lens, chunk_lens,
                window=config.sliding_window, interpret=interpret,
                kv_limit=kv_limit)
        else:
            gathered = llama._paged_gather(pool_layer, tables)
            out = llama._cached_gqa_attention(
                q_g, gathered, positions_b, hd,
                window=config.sliding_window)
        new_pool.append(pool_layer)
        out = _gather_cols(out.reshape(batch, W, h * hd), axis)
        x = x + _gather_cols(
            llama._lora_matmul(out, layer["wo"], lora_layer, "wo",
                               lora), axis).astype(x.dtype)
        x = _tp_mlp_block(layer, config, axis, x, ep_axis=ep_axis,
                          ep=ep, overlap=overlap)
    return new_pool


def _tp_verify_core(params, tokens, pool, tables, positions, active,
                    config: LlamaConfig, tp: int, axis: str,
                    lora=None, kv_limit=None, ep_axis=None, ep: int = 1,
                    overlap: bool = False):
    """Shard-local mirror of ``llama._verify_append_core`` (the
    speculative verify): every row at its OWN absolute start position,
    the window's K/V appended into the LOCAL kv-head slice of the
    pool, inactive rows routed to scratch block 0.  The all-gathers
    are the same column gathers as the decode/prefill mirrors —
    bitwise concatenations — so TP verify logits equal single-chip
    verify logits bit for bit (invariants 9 + 11)."""
    batch, K = tokens.shape
    h, kv = config.n_heads // tp, config.n_kv_heads // tp
    hd = config.head_dim
    starts = jnp.where(active, positions, 0).astype(jnp.int32)
    positions_b = starts[:, None] + jnp.arange(K, dtype=jnp.int32)[None]
    cached_lens = starts
    chunk_lens = jnp.where(active, K, 0).astype(jnp.int32)
    write_tables = jnp.where(active[:, None], tables,
                             jnp.zeros_like(tables))
    cos, sin = llama._rope_freqs(config, positions_b)
    x = _tp_embed(params, tokens, config, axis)
    use_kernel, interpret = llama.verify_dispatch(
        hd, kv, pool[0]["k"].dtype, K)
    new_pool = []
    lora_layers = _lora_layers(lora, len(pool))
    for layer, pool_layer, lora_layer in zip(params["layers"], pool,
                                             lora_layers):
        normed = llama.rms_norm(x, layer["attn_norm"], config.norm_eps)
        q = llama._lora_matmul(normed, layer["wq"], lora_layer, "wq",
                               lora).reshape(batch, K, h, hd)
        k = llama._lora_matmul(normed, layer["wk"], lora_layer, "wk",
                               lora).reshape(batch, K, kv, hd)
        v = llama._lora_matmul(normed, layer["wv"], lora_layer, "wv",
                               lora).reshape(batch, K, kv, hd)
        q = llama.apply_rope(q, cos, sin)
        k = llama.apply_rope(k, cos, sin)
        q_g = q.reshape(batch, K, kv, h // kv, hd)
        if use_kernel:
            out, pool_layer = paged_verify_attention(
                q_g, k, v, pool_layer, write_tables, cached_lens,
                chunk_lens, window=config.sliding_window,
                interpret=interpret, kv_limit=kv_limit)
        else:
            pool_layer = llama._paged_write_slab(pool_layer, k, v,
                                                 write_tables,
                                                 positions_b)
            gathered = llama._paged_gather(pool_layer, write_tables)
            out = llama._cached_gqa_attention(
                q_g, gathered, positions_b, hd,
                window=config.sliding_window)
        new_pool.append(pool_layer)
        out = _gather_cols(out.reshape(batch, K, h * hd), axis)
        x = x + _gather_cols(
            llama._lora_matmul(out, layer["wo"], lora_layer, "wo",
                               lora), axis).astype(x.dtype)
        x = _tp_mlp_block(layer, config, axis, x, ep_axis=ep_axis,
                          ep=ep, overlap=overlap)
    return _tp_lm_head(params, config, axis, x), new_pool


# --------------------------------------------------------------------------- #
# The engine


class TPEngine:
    """Per-server dispatcher for the TP serving entry points.

    Built once per :class:`PagedContinuousServer` (the shard_map
    in/out spec trees depend on the server's actual parameter and pool
    pytree structure — quantization layout, layer count — so the
    jitted closures are constructed per engine and cached per static
    signature).  Mirrors the llama entry points' signatures so the
    server's dispatch sites stay one-line switches:

    * :meth:`serve_chunk_paged` — decode chunk (pool donated)
    * :meth:`serve_chunk_mixed` — chunked-prefill slice + decode chunk
      (``sp_shard=True`` runs the slice as an sp-sharded window)
    * :meth:`prefill_append_paged` — standalone prefill append
    * :meth:`prefill_append_sp` — standalone sp-window prefill
    * :meth:`verify_chunk_paged` — speculative verify window
    """

    def __init__(self, config: LlamaConfig, mesh: Mesh, params, pool,
                 axis: str = "tp", sp_axis: Optional[str] = None,
                 ep_axis: Optional[str] = None,
                 overlap: bool = False):
        if axis not in mesh.axis_names:
            raise ValueError(
                f"mesh has no '{axis}' axis: {mesh.axis_names}")
        self.config = config
        self.mesh = mesh
        self.axis = axis
        self.tp = mesh.shape[axis]
        # Second mesh axis (at most one): sp shards prefill windows,
        # ep shards MoE experts.  Size 1 ⇔ absent.
        self.sp_axis = sp_axis if (sp_axis in mesh.axis_names) else None
        self.ep_axis = ep_axis if (ep_axis in mesh.axis_names) else None
        self.sp = mesh.shape[self.sp_axis] if self.sp_axis else 1
        self.ep = mesh.shape[self.ep_axis] if self.ep_axis else 1
        self.overlap = bool(overlap)
        if config.n_kv_heads % self.tp or config.n_heads % self.tp:
            raise ValueError(
                f"tp={self.tp} must divide n_kv_heads="
                f"{config.n_kv_heads} and n_heads={config.n_heads}")
        if config.n_experts and config.n_experts % self.ep:
            raise ValueError(
                f"ep={self.ep} must divide n_experts="
                f"{config.n_experts}")
        if self.overlap:
            for layer in params.get("layers", ()):
                if getattr(layer.get("w_down"), "ndim", 0) != 2:
                    raise ValueError(
                        "overlap mode needs dense (unquantized) MLP "
                        "weights: w_down re-lays row-parallel for the "
                        "reduce-scatter path")
        self._param_specs = tp_param_specs(params, axis,
                                           ep_axis=self.ep_axis,
                                           overlap=self.overlap)
        self._pool_specs = tp_pool_specs(pool, axis)
        self._cache: Dict[Any, Any] = {}

    # -- spec helpers -------------------------------------------------- #

    def _shard_map(self, body, in_specs, out_specs):
        return shard_map(body, mesh=self.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    def _core_kwargs(self):
        """Second-axis / overlap context threaded into every mirror
        core (inert on a 1-D exact-path mesh)."""
        return dict(ep_axis=self.ep_axis, ep=self.ep,
                    overlap=self.overlap)

    # -- decode chunk -------------------------------------------------- #

    def _lora_specs(self, lora):
        """Spec tree for a stacked-adapter operand (or None)."""
        return (tp_lora_specs(lora, self.axis)
                if lora is not None else None)

    def serve_chunk_paged(self, params, state, pool, num_steps,
                          eos_id: int = -1, sampled: bool = False,
                          rng_key=None, lora_shared=None):
        """TP twin of :func:`llama.serve_chunk_paged`.  ``lora_shared``
        is the stacked adapter tree laid out by :func:`shard_lora`
        (A + scale replicated, B column-sharded); per-row ids come from
        ``state["adapter_ids"]`` exactly like the single-chip twin."""
        num_steps = int(num_steps)
        key = ("serve", num_steps, int(eos_id), bool(sampled),
               rng_key is not None, lora_shared is not None)
        fn = self._cache.get(key)
        if fn is None:
            fn = self._build_serve(num_steps, int(eos_id),
                                   bool(sampled), rng_key is not None,
                                   self._lora_specs(lora_shared))
            self._cache[key] = fn
        args = (params, state, pool) + (
            (rng_key,) if rng_key is not None else ()) + (
            (lora_shared,) if lora_shared is not None else ())
        return fn(*args)

    def _build_serve(self, num_steps, eos_id, sampled, has_rng,
                     lora_specs=None):
        config, tp, axis = self.config, self.tp, self.axis
        core_kwargs = self._core_kwargs()

        def body(params, state, pool, rng_key=None, lora_shared=None):
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
                write_pos = jnp.where(active, positions,
                                      scratch_positions)
                return _tp_decode_core_paged(params, token, pool,
                                             write_tables, write_pos,
                                             config, tp, axis,
                                             lora=lora, **core_kwargs)

            return llama._serve_scan(step_core, state, pool, num_steps,
                                     eos_id, sampled, rng_key)

        if lora_specs is not None:
            if has_rng:
                def wrapped(params, state, pool, rng_key, lora_shared):
                    return body(params, state, pool, rng_key,
                                lora_shared)
            else:
                def wrapped(params, state, pool, lora_shared):
                    return body(params, state, pool, None, lora_shared)
        else:
            wrapped = body
        in_specs = (self._param_specs, P(), self._pool_specs)
        if has_rng:
            in_specs += (P(),)
        if lora_specs is not None:
            in_specs += (lora_specs,)
        out_specs = (P(), P(), P(), self._pool_specs)
        return jax.jit(self._shard_map(wrapped, in_specs, out_specs),
                       donate_argnums=(2,))

    # -- mixed prefill/decode chunk ------------------------------------ #

    def serve_chunk_mixed(self, params, state, pool, prefill_tokens,
                          prefill_row, prefill_start, num_steps,
                          eos_id: int = -1, sampled: bool = False,
                          rng_key=None, lora_shared=None,
                          prefill_kv_limit=None,
                          sp_shard: bool = False):
        """TP twin of :func:`llama.serve_chunk_mixed` — the admitting
        slot's adapter id is dynamically sliced out of the resident
        state for the prefill leg, exactly like the single-chip twin.

        ``sp_shard=True`` (needs an sp mesh axis): the prefill slice is
        an sp-WINDOW — ``sp`` consecutive chunks in one dispatch,
        sharded over the sp axis through
        :func:`_tp_sp_prefill_core` — while the decode part runs
        replicated over sp exactly as before."""
        num_steps = int(num_steps)
        if sp_shard and self.sp <= 1:
            raise ValueError("sp_shard needs an sp mesh axis > 1")
        key = ("mixed", num_steps, int(eos_id), bool(sampled),
               rng_key is not None, prefill_kv_limit, bool(sp_shard),
               lora_shared is not None)
        fn = self._cache.get(key)
        if fn is None:
            fn = self._build_mixed(num_steps, int(eos_id),
                                   bool(sampled), rng_key is not None,
                                   prefill_kv_limit, bool(sp_shard),
                                   self._lora_specs(lora_shared))
            self._cache[key] = fn
        args = (params, state, pool, prefill_tokens,
                jnp.asarray(prefill_row, jnp.int32),
                jnp.asarray(prefill_start, jnp.int32)) + (
            (rng_key,) if rng_key is not None else ()) + (
            (lora_shared,) if lora_shared is not None else ())
        return fn(*args)

    def _build_mixed(self, num_steps, eos_id, sampled, has_rng,
                     prefill_kv_limit, sp_shard=False,
                     lora_specs=None):
        config, tp, axis = self.config, self.tp, self.axis
        sp_axis, sp = self.sp_axis, self.sp
        core_kwargs = self._core_kwargs()

        def body(params, state, pool, prefill_tokens, prefill_row,
                 prefill_start, rng_key=None, lora_shared=None):
            block_size = pool[0]["k"].shape[1]
            tables = state["tables"]
            slots = tables.shape[0]
            tables_row = jax.lax.dynamic_slice_in_dim(
                tables, prefill_row, 1, axis=0)
            if lora_shared is not None:
                row_ids = jax.lax.dynamic_slice_in_dim(
                    state["adapter_ids"], prefill_row, 1, axis=0)
                prefill_lora = dict(lora_shared, ids=row_ids)
                lora = dict(lora_shared, ids=state["adapter_ids"])
            else:
                prefill_lora = lora = None
            if sp_shard:
                pool = _tp_sp_prefill_core(
                    params, prefill_tokens, pool, tables_row,
                    prefill_start, config, tp, axis, sp_axis, sp,
                    lora=prefill_lora, kv_limit=prefill_kv_limit,
                    **core_kwargs)
            else:
                _, pool = _tp_prefill_append_core(
                    params, prefill_tokens, pool, tables_row,
                    prefill_start, config, tp, axis, lora=prefill_lora,
                    kv_limit=prefill_kv_limit, compute_logits=False,
                    **core_kwargs)
            scratch_tables = jnp.zeros_like(tables)
            scratch_positions = (jnp.arange(slots, dtype=jnp.int32)
                                 % block_size)

            def step_core(token, pool, positions, active):
                write_tables = jnp.where(active[:, None], tables,
                                         scratch_tables)
                write_pos = jnp.where(active, positions,
                                      scratch_positions)
                return _tp_decode_core_paged(params, token, pool,
                                             write_tables, write_pos,
                                             config, tp, axis,
                                             lora=lora, **core_kwargs)

            return llama._serve_scan(step_core, state, pool, num_steps,
                                     eos_id, sampled, rng_key)

        if lora_specs is not None:
            if has_rng:
                def wrapped(params, state, pool, prefill_tokens,
                            prefill_row, prefill_start, rng_key,
                            lora_shared):
                    return body(params, state, pool, prefill_tokens,
                                prefill_row, prefill_start, rng_key,
                                lora_shared)
            else:
                def wrapped(params, state, pool, prefill_tokens,
                            prefill_row, prefill_start, lora_shared):
                    return body(params, state, pool, prefill_tokens,
                                prefill_row, prefill_start, None,
                                lora_shared)
        else:
            wrapped = body
        prefill_spec = P(None, sp_axis) if sp_shard else P()
        in_specs = (self._param_specs, P(), self._pool_specs,
                    prefill_spec, P(), P())
        if has_rng:
            in_specs += (P(),)
        if lora_specs is not None:
            in_specs += (lora_specs,)
        out_specs = (P(), P(), P(), self._pool_specs)
        return jax.jit(self._shard_map(wrapped, in_specs, out_specs),
                       donate_argnums=(2,))

    # -- speculative verify window ------------------------------------- #

    def verify_chunk_paged(self, params, tokens, pool, tables,
                           positions, active, lora=None,
                           kv_limit=None):
        """TP twin of :func:`llama.verify_chunk_paged`: score a
        (slots, k+1) speculative window against the sharded pool, each
        row at its own absolute position.  ``lora`` is the full dict
        WITH per-row ids (the llama signature).  Returns ``(logits
        (slots, k+1, vocab), pool)`` with the pool donated — bitwise
        equal to the single-chip verify (all-gather is the only
        collective)."""
        K = int(tokens.shape[1])
        key = ("verify", K, kv_limit, lora is not None)
        fn = self._cache.get(key)
        if fn is None:
            fn = self._build_verify(kv_limit, self._lora_specs(lora))
            self._cache[key] = fn
        args = (params, tokens, pool, tables, positions, active) + (
            (lora,) if lora is not None else ())
        return fn(*args)

    def _build_verify(self, kv_limit, lora_specs=None):
        config, tp, axis = self.config, self.tp, self.axis
        core_kwargs = self._core_kwargs()

        def body(params, tokens, pool, tables, positions, active,
                 lora=None):
            return _tp_verify_core(params, tokens, pool, tables,
                                   positions, active, config, tp,
                                   axis, lora=lora, kv_limit=kv_limit,
                                   **core_kwargs)

        in_specs = (self._param_specs, P(), self._pool_specs,
                    P(), P(), P())
        if lora_specs is not None:
            in_specs += (lora_specs,)
        out_specs = (P(), self._pool_specs)
        return jax.jit(self._shard_map(body, in_specs, out_specs),
                       donate_argnums=(2,))

    # -- standalone prefill append ------------------------------------- #

    def prefill_append_paged(self, params, tokens, pool, tables,
                             start_index, lora=None, kv_limit=None,
                             compute_logits: bool = False):
        """TP twin of :func:`llama.prefill_append_paged` — ``lora`` is
        the full dict WITH per-row ids (the llama signature).  Always
        dispatched with ``compute_logits=False`` by the paged server
        (the mixed step owns logits); returns ``(None, new_pool)`` to
        match the llama call-site unpacking."""
        if compute_logits:
            raise NotImplementedError(
                "TP prefill_append_paged serves the paged admission "
                "path, which never reads prefill logits")
        key = ("prefill", kv_limit, lora is not None)
        fn = self._cache.get(key)
        if fn is None:
            fn = self._build_prefill(kv_limit, self._lora_specs(lora))
            self._cache[key] = fn
        args = (params, tokens, pool, tables,
                jnp.asarray(start_index, jnp.int32)) + (
            (lora,) if lora is not None else ())
        return None, fn(*args)

    def _build_prefill(self, kv_limit, lora_specs=None):
        config, tp, axis = self.config, self.tp, self.axis
        core_kwargs = self._core_kwargs()

        def body(params, tokens, pool, tables, start_index, lora=None):
            _, new_pool = _tp_prefill_append_core(
                params, tokens, pool, tables, start_index, config, tp,
                axis, lora=lora, kv_limit=kv_limit,
                compute_logits=False, **core_kwargs)
            return new_pool

        in_specs = (self._param_specs, P(), self._pool_specs, P(), P())
        if lora_specs is not None:
            in_specs += (lora_specs,)
        out_specs = self._pool_specs
        return jax.jit(self._shard_map(body, in_specs, out_specs),
                       donate_argnums=(2,))

    # -- sequence-parallel prefill window ------------------------------ #

    def prefill_append_sp(self, params, tokens, pool, tables,
                          start_index, lora=None, kv_limit=None):
        """Standalone sp-window prefill: ``tokens (1, sp*W)`` is
        ``sp`` consecutive chunks of one prompt, sharded over the sp
        axis — each shard appends its own chunk at its own absolute
        offset and every pool copy receives the full window (see
        :func:`_tp_sp_prefill_core`).  Returns ``(None, new_pool)``
        to match the ``prefill_append_paged`` call-site unpacking."""
        if self.sp <= 1:
            raise ValueError("prefill_append_sp needs an sp mesh "
                             "axis > 1")
        if tokens.shape[1] % self.sp:
            raise ValueError(
                f"sp window width {tokens.shape[1]} must divide by "
                f"sp={self.sp}")
        key = ("prefill_sp", kv_limit, lora is not None)
        fn = self._cache.get(key)
        if fn is None:
            fn = self._build_prefill_sp(kv_limit,
                                        self._lora_specs(lora))
            self._cache[key] = fn
        args = (params, tokens, pool, tables,
                jnp.asarray(start_index, jnp.int32)) + (
            (lora,) if lora is not None else ())
        return None, fn(*args)

    def _build_prefill_sp(self, kv_limit, lora_specs=None):
        config, tp, axis = self.config, self.tp, self.axis
        sp_axis, sp = self.sp_axis, self.sp
        core_kwargs = self._core_kwargs()

        def body(params, tokens, pool, tables, start_index, lora=None):
            return _tp_sp_prefill_core(
                params, tokens, pool, tables, start_index, config, tp,
                axis, sp_axis, sp, lora=lora, kv_limit=kv_limit,
                **core_kwargs)

        in_specs = (self._param_specs, P(None, sp_axis),
                    self._pool_specs, P(), P())
        if lora_specs is not None:
            in_specs += (lora_specs,)
        out_specs = self._pool_specs
        return jax.jit(self._shard_map(body, in_specs, out_specs),
                       donate_argnums=(2,))
