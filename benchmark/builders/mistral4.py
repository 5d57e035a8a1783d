"""Builder for the configurations that ``models/mistral4.py`` serves:
latent attention (MLA) over a compressed cache and routed SwiGLU
experts beside a shared one (``mistral4``; DeepSeek-V2's layer).

The one place that knows the program's names for this family: it turns
a configuration file's published keys into the program's
``Mistral4Config`` and lays the seeded draws of ``benchmark/weights.py``
out as the program's parameter tree: int8 weight-only 2-D matrices;
routed experts, the router and the per-head ``W_uk`` / ``W_uv`` (3-D
leaves) in the model's float type.  The same draws, one layer or one
expert at a time and widened to float32, are what the plain reference
is given.

A configuration cut to a chip's share keeps ``num_hidden_layers`` of the
identical layers and holds ``n_routed_experts`` routed experts from
``experts_first`` on, of the ``reduced_from`` count the router still
scores.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import weights as W

# Leaf ids: the top of the tree, then 16 per layer.
_EMBED, _HEAD, _LAYER0, _PER_LAYER = 1, 2, 16, 16
#: Slot of each leaf within its layer's 16 ids.
_SLOTS = {"q_a": 0, "q_b": 1, "kv_a": 2, "w_uk": 3, "w_uv": 4, "wo": 5,
          "router": 6, "shared_gate": 7, "shared_up": 8, "shared_down": 9,
          "w_gate": 10, "w_up": 11, "w_down": 12}
_INT8 = ("q_a", "q_b", "kv_a", "wo")
_SHARED = ("shared_gate", "shared_up", "shared_down")
_PER_HEAD = ("w_uk", "w_uv")
_EXPERT = ("w_gate", "w_up", "w_down")


def sizes(cfg: dict) -> dict:
    """The published keys under the short names the per-layer readers
    use.

    ``kv`` and ``hd`` are what the accepted ``decode_attn_roofline``
    divides by: it counts ``2 x kv x hd x (bytes a value)`` a cached
    position a layer, the K and the V row of a GQA pool.  A latent pool
    has ONE row a position, ``kv_lora_rank + qk_rope_head_dim`` values
    read once for keys and values alike, so ``kv`` is 1 and ``hd`` half
    the row: ``2 x 1 x 160 x 2 B = 640 B``, the bytes a position needs
    (the pool's zero padding to 384 values is not needed and not
    counted).  ``layers`` counts the layers that own a pool: all."""
    held = cfg["n_routed_experts"]
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return dict(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv=1, hd=(rank + rope) // 2, vocab=cfg["vocab_size"],
        layers=cfg["num_hidden_layers"],
        expert_layers=cfg["num_hidden_layers"],
        q_lora=cfg["q_lora_rank"], rank=rank, rope=rope,
        nope=cfg["qk_nope_head_dim"], v=cfg["v_head_dim"],
        f=cfg["moe_intermediate_size"],
        shared=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        experts=held, top_k=cfg["num_experts_per_tok"],
        experts_total=cfg.get("reduced_from", {}).get(
            "n_routed_experts", held),
        experts_first=cfg.get("experts_first", 0))


def program_config(name: str, cfg: dict):
    """Register and return the program's config for this file."""
    from aiko_services_tpu.models import mistral4
    z = sizes(cfg)
    rope = cfg["rope_parameters"]
    held = None
    if z["experts"] != z["experts_total"]:
        held = (z["experts_first"], z["experts"])
    assert cfg["first_k_dense_replace"] == 0 and cfg["n_group"] == 1
    assert rope["mscale"] == rope["mscale_all_dim"]
    config = mistral4.Mistral4Config(
        vocab_size=z["vocab"], d_model=z["d"], n_layers=z["layers"],
        n_heads=z["heads"], q_lora_rank=z["q_lora"],
        kv_lora_rank=z["rank"], qk_nope_head_dim=z["nope"],
        qk_rope_head_dim=z["rope"], v_head_dim=z["v"],
        n_experts=z["experts_total"], moe_top_k=z["top_k"], d_ff=z["f"],
        d_shared=z["shared"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        experts_held=held, norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(rope["rope_theta"]),
        rope_factor=float(rope["factor"]),
        rope_original_max=int(rope["original_max_position_embeddings"]),
        rope_beta_fast=float(rope["beta_fast"]),
        rope_beta_slow=float(rope["beta_slow"]),
        rope_mscale_all_dim=float(rope["mscale_all_dim"]),
        llama4_scaling_beta=float(rope["llama_4_scaling_beta"]),
        max_seq_len=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["assumed"]["activation_dtype"]))
    mistral4.CONFIGS[name] = config
    return config


def _shapes(z: dict) -> dict:
    d, heads = z["d"], z["heads"]
    return {"q_a": (d, z["q_lora"]),
            "q_b": (z["q_lora"], heads * (z["nope"] + z["rope"])),
            "kv_a": (d, z["rank"] + z["rope"]),
            "w_uk": (heads, z["rank"], z["nope"]),
            "w_uv": (heads, z["rank"], z["v"]),
            "wo": (heads * z["v"], d),
            "router": (d, z["experts_total"]),
            "shared_gate": (d, z["shared"]), "shared_up": (d, z["shared"]),
            "shared_down": (z["shared"], d),
            "w_gate": (d, z["f"]), "w_up": (d, z["f"]),
            "w_down": (z["f"], d)}


def _leaf(layer, name):
    return _LAYER0 + layer * _PER_LAYER + _SLOTS[name]


def _layer_tree(words, layer, z, dtype, bits, expert_leaves):
    """One layer of the tree; ``layer`` may be traced."""
    shapes = _shapes(z)
    tree = {"attn_norm": jnp.ones((z["d"],), dtype),
            "ffn_norm": jnp.ones((z["d"],), dtype),
            "q_norm": jnp.ones((z["q_lora"],), dtype),
            "kv_norm": jnp.ones((z["rank"],), dtype)}
    for name in _INT8:
        tree[name] = W.int8_weight(words, _leaf(layer, name), shapes[name],
                                   bits)
    for name in _PER_HEAD:
        tree[name] = W.float_weight(words, _leaf(layer, name),
                                    shapes[name], dtype, bits)
    moe = {"router": W.float_weight(words, _leaf(layer, "router"),
                                    shapes["router"], dtype, bits)}
    for name in _SHARED:
        moe[name] = W.int8_weight(words, _leaf(layer, name), shapes[name],
                                  bits)
    if expert_leaves:
        for name in _EXPERT:
            moe[name] = _experts(words, layer, name, z, dtype, bits)
    tree["moe"] = moe
    return tree


def _experts(words, layer, name, z, dtype, bits):
    """The held experts of one 3-D leaf.  Element (e, k, n) of the
    whole leaf is drawn from its own counter, so the experts held here
    are the ones any other share, or the uncut model, would draw."""
    shape = _shapes(z)[name]
    key = W.leaf_key(words, _leaf(layer, name))
    q = W.draw_q(key, (z["experts"],) + shape, bits,
                 offset=z["experts_first"] * shape[0] * shape[1])
    return (q.astype(jnp.float32)
            * W.draw_scale(key, shape[0], shape[1])).astype(dtype)


def _top_tree(words, z, dtype, bits):
    return {"embed": W.int8_weight(words, _EMBED, (z["vocab"], z["d"]),
                                   bits),
            "final_norm": jnp.ones((z["d"],), dtype),
            "lm_head": W.int8_weight(words, _HEAD, (z["d"], z["vocab"]),
                                     bits)}


def build_params(cfg: dict, seed: int, bits: int = 8):
    """The served parameter tree, made on the device in ONE jitted call
    whose only runtime argument is the seed."""
    z = sizes(cfg)
    dtype = jnp.dtype(cfg["assumed"]["activation_dtype"])
    # A 3-D leaf is drawn with one 32-bit counter.
    assert z["experts_total"] * z["d"] * z["f"] < 2 ** 32

    @jax.jit
    def build(words):
        tree = _top_tree(words, z, dtype, bits)
        tree["layers"] = [_layer_tree(words, layer, z, dtype, bits, True)
                          for layer in range(z["layers"])]
        return tree

    return build(W.seed_words(seed))


class ReferenceWeights:
    """What the plain reference is given: the same draws at 8 bits,
    widened to float32, one layer (or one expert) at a time."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg = cfg
        self.z = z = sizes(cfg)
        self.words = W.seed_words(seed)
        dtype = jnp.dtype(cfg["assumed"]["activation_dtype"])
        shapes = _shapes(z)

        def widen(tree):
            return jax.tree.map(
                W.dequantized, tree,
                is_leaf=lambda leaf: isinstance(leaf, dict)
                and "q" in leaf)

        @jax.jit
        def top(words):
            return widen(_top_tree(words, z, dtype, 8))

        @jax.jit
        def layer(words, index):
            tree = _layer_tree(words, index, z, dtype, 8, False)
            tree.update(tree.pop("moe"))
            return widen(tree)

        @functools.partial(jax.jit, static_argnames=("name",))
        def expert(words, index, which, name):
            # Element (e, k, n) of the 3-D leaf, drawn alone.
            shape = shapes[name]
            key = W.leaf_key(words, _leaf(index, name))
            offset = which.astype(jnp.uint32) * jnp.uint32(
                shape[0] * shape[1])
            q = W.draw_q(key, shape, 8, offset=offset)
            scale = W.draw_scale(key, shape[0], shape[1])
            return (q.astype(jnp.float32) * scale).astype(dtype).astype(
                jnp.float32)

        self._top, self._layer, self._expert = top, layer, expert

    def top(self):
        return self._top(self.words)

    def layer(self, index: int):
        """Layer ``index``, float32: everything but its routed
        experts."""
        return self._layer(self.words, jnp.int32(index))

    def expert(self, index: int, which: int):
        """Routed expert ``which`` (its number among ALL experts)."""
        return {name: self._expert(self.words, jnp.int32(index),
                                   jnp.int32(which), name)
                for name in _EXPERT}
