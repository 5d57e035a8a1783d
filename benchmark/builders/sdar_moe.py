"""Builder for the configurations that ``models/sdar.py`` serves:
grouped-query attention with an explicit head width and a per-head
RMSNorm of queries and keys, routed SwiGLU experts with no shared one,
generation by diffusion over blocks (``sdar_moe``; SDAR's layer is
Qwen3-MoE's).

The one place that knows the program's names for this family: it turns
a configuration file's published keys into the program's ``SdarConfig``
(the block length, the ``[MASK]`` id and the schedule a request gets
when it names none come from the file's ``serving`` and ``assumed``)
and lays the seeded draws of ``benchmark/weights.py`` out as the
program's parameter tree: int8 weight-only 2-D matrices; routed experts
(3-D leaves) and the router in the model's float type.  The same draws,
one layer or one expert at a time and widened to float32, are what the
plain reference is given.

A configuration cut to a chip's share keeps ``num_hidden_layers`` of the
identical layers and holds ``num_experts`` routed experts from
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
_SLOTS = {"wq": 0, "wk": 1, "wv": 2, "wo": 3, "router": 4, "w_gate": 5,
          "w_up": 6, "w_down": 7}
_INT8 = ("wq", "wk", "wv", "wo")
_EXPERT = ("w_gate", "w_up", "w_down")


def sizes(cfg: dict) -> dict:
    """The published keys under the short names the per-layer readers
    use (``kv`` and ``hd``: what the accepted ``decode_attn_roofline``
    counts a cached position by, ``2 x kv x hd x 2 B`` a layer;
    ``layers`` counts the layers that own a pool: all)."""
    held = cfg["num_experts"]
    return dict(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        vocab=cfg["vocab_size"], layers=cfg["num_hidden_layers"],
        expert_layers=cfg["num_hidden_layers"],
        f=cfg["moe_intermediate_size"], experts=held,
        top_k=cfg["num_experts_per_tok"],
        experts_total=cfg.get("reduced_from", {}).get("num_experts",
                                                      held),
        experts_first=cfg.get("experts_first", 0),
        block=cfg["serving"]["block_length"])


def program_config(name: str, cfg: dict):
    """Register and return the program's config for this file."""
    from aiko_services_tpu.models import sdar
    z = sizes(cfg)
    serving = cfg["serving"]
    held = None
    if z["experts"] != z["experts_total"]:
        held = (z["experts_first"], z["experts"])
    assert cfg.get("norm_topk_prob", True)
    assert not cfg.get("mlp_only_layers") \
        and cfg.get("decoder_sparse_step", 1) == 1
    config = sdar.SdarConfig(
        vocab_size=z["vocab"], d_model=z["d"], n_layers=z["layers"],
        n_heads=z["heads"], n_kv_heads=z["kv"], head_dim=z["hd"],
        n_experts=z["experts_total"], moe_top_k=z["top_k"], d_ff=z["f"],
        experts_held=held, norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]),
        max_seq_len=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["assumed"]["activation_dtype"]),
        block_length=z["block"],
        mask_id=int(cfg["assumed"]["mask_token_id"]),
        denoise_steps=int(serving["denoise_steps"]),
        denoise_dynamic=serving["denoise_rule"] == "dynamic",
        denoise_threshold=float(serving["denoise_threshold"]))
    sdar.CONFIGS[name] = config
    return config


def _shapes(z: dict) -> dict:
    d, q, kv = z["d"], z["heads"] * z["hd"], z["kv"] * z["hd"]
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "router": (d, z["experts_total"]),
            "w_gate": (d, z["f"]), "w_up": (d, z["f"]),
            "w_down": (z["f"], d)}


def _leaf(layer, name):
    return _LAYER0 + layer * _PER_LAYER + _SLOTS[name]


def _layer_tree(words, layer, z, dtype, bits, expert_leaves):
    """One layer of the tree; ``layer`` may be traced."""
    shapes = _shapes(z)
    tree = {"attn_norm": jnp.ones((z["d"],), dtype),
            "ffn_norm": jnp.ones((z["d"],), dtype),
            "q_norm": jnp.ones((z["hd"],), dtype),
            "k_norm": jnp.ones((z["hd"],), dtype)}
    for name in _INT8:
        tree[name] = W.int8_weight(words, _leaf(layer, name), shapes[name],
                                   bits)
    moe = {"router": W.float_weight(words, _leaf(layer, "router"),
                                    shapes["router"], dtype, bits)}
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
