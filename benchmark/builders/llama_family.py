"""Builder for the configurations that ``models/llama.py`` serves:
dense SwiGLU blocks (Mistral) and top-k expert blocks (Mixtral).

It is the one place that knows the program's names: it turns a
configuration file's published keys into the program's ``LlamaConfig``
(registered under the configuration's own name, as ``chip_smoke.py``
registers its depth-cut copy), and lays the seeded draws of
``benchmark/weights.py`` out as the program's parameter tree: int8
weight-only matrices, and experts in the model's float type because
the program keeps 3-D leaves unquantized.  The same draws, one layer
or one expert at a time and widened to float32, are what the plain
reference is given.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import weights as W

# Leaf ids: the top of the tree, then 16 per layer.
_EMBED, _HEAD, _LAYER0, _PER_LAYER = 1, 2, 16, 16
_ATTN = ("wq", "wk", "wv", "wo")
_MLP = ("w_gate", "w_up", "w_down")
_ROUTER = 7


def sizes(cfg: dict) -> dict:
    """The published keys under the short names used below."""
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    return dict(
        d=d, heads=heads, kv=cfg["num_key_value_heads"],
        hd=cfg.get("head_dim") or d // heads, f=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], layers=cfg["num_hidden_layers"],
        experts=cfg.get("num_local_experts", 0),
        top_k=cfg.get("num_experts_per_tok", 0))


def program_config(name: str, cfg: dict):
    """Register and return the program's config for this file."""
    from aiko_services_tpu.models import llama
    z = sizes(cfg)
    assert z["hd"] * z["heads"] == z["d"], "head_dim is d_model / heads"
    kwargs = dict(
        vocab_size=z["vocab"], d_model=z["d"], n_layers=z["layers"],
        n_heads=z["heads"], n_kv_heads=z["kv"], d_ff=z["f"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        max_seq_len=cfg["max_position_embeddings"],
        sliding_window=cfg.get("sliding_window"),
        dtype=jnp.dtype(cfg["assumed"]["activation_dtype"]))
    if z["experts"]:
        kwargs.update(
            n_experts=z["experts"], moe_top_k=z["top_k"],
            moe_capacity_factor=float(
                cfg["assumed"]["moe_capacity_factor"]))
    config = llama.LlamaConfig(**kwargs)
    llama.CONFIGS[name] = config
    return config


def _shapes(z: dict) -> dict:
    qkv = z["heads"] * z["hd"]
    kvw = z["kv"] * z["hd"]
    return {"wq": (z["d"], qkv), "wk": (z["d"], kvw),
            "wv": (z["d"], kvw), "wo": (qkv, z["d"]),
            "w_gate": (z["d"], z["f"]), "w_up": (z["d"], z["f"]),
            "w_down": (z["f"], z["d"])}


def _layer_base(layer):
    return _LAYER0 + layer * _PER_LAYER


def _attention_tree(words, layer, z, dtype, bits):
    shapes = _shapes(z)
    base = _layer_base(layer)
    tree = {"attn_norm": jnp.ones((z["d"],), dtype),
            "mlp_norm": jnp.ones((z["d"],), dtype)}
    for slot, name in enumerate(_ATTN):
        tree[name] = W.int8_weight(words, base + slot, shapes[name], bits)
    return tree


def _dense_mlp_tree(words, layer, z, bits):
    shapes = _shapes(z)
    return {name: W.int8_weight(words, _layer_base(layer) + 4 + slot,
                                shapes[name], bits)
            for slot, name in enumerate(_MLP)}


def _router(words, layer, z, dtype, bits):
    return W.float_weight(words, _layer_base(layer) + _ROUTER,
                          (z["d"], z["experts"]), dtype, bits)


def _layer_tree(words, layer, z, dtype, bits):
    tree = _attention_tree(words, layer, z, dtype, bits)
    if not z["experts"]:
        tree.update(_dense_mlp_tree(words, layer, z, bits))
        return tree
    shapes = _shapes(z)
    moe = {"router": _router(words, layer, z, dtype, bits)}
    for slot, name in enumerate(_MLP):
        moe[name] = W.float_weight(
            words, _layer_base(layer) + 4 + slot,
            (z["experts"],) + shapes[name], dtype, bits)
    tree["moe"] = moe
    return tree


def _top_tree(words, z, dtype, bits):
    return {"embed": W.int8_weight(words, _EMBED, (z["vocab"], z["d"]),
                                   bits),
            "final_norm": jnp.ones((z["d"],), dtype),
            "lm_head": W.int8_weight(words, _HEAD, (z["d"], z["vocab"]),
                                     bits)}


def build_params(cfg: dict, seed: int, bits: int = 8):
    """The served parameter tree, made on the device in ONE jitted call
    whose only runtime argument is the seed (so every seed is the same
    cached program)."""
    z = sizes(cfg)
    dtype = jnp.dtype(cfg["assumed"]["activation_dtype"])

    @jax.jit
    def build(words):
        tree = _top_tree(words, z, dtype, bits)
        tree["layers"] = [_layer_tree(words, layer, z, dtype, bits)
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
            tree = _attention_tree(words, index, z, dtype, 8)
            if z["experts"]:
                tree["router"] = _router(words, index, z, dtype, 8)
            else:
                tree.update(_dense_mlp_tree(words, index, z, 8))
            return widen(tree)

        @functools.partial(jax.jit, static_argnames=("name",))
        def expert(words, index, which, name):
            # Element (e, k, n) of the 3-D leaf, drawn alone.
            slot = _MLP.index(name)
            shape = shapes[name]
            key = W.leaf_key(words, _layer_base(index) + 4 + slot)
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
        return self._layer(self.words, jnp.int32(index))

    def expert(self, index: int, which: int):
        return {name: self._expert(self.words, jnp.int32(index),
                                   jnp.int32(which), name)
                for name in _MLP}
