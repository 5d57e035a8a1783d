"""Builder for the configurations that ``models/evabyte.py`` serves: a
byte-level decoder whose attention keeps a window of exact rows and one
summary row for every chunk behind it (``evabyte``; EVA attention).

The one place that knows the program's names for this family: it turns
a configuration file's published keys into the program's
``EvaByteConfig`` and lays the seeded draws of ``benchmark/weights.py``
out as the program's parameter tree: int8 weight-only 2-D matrices
(projections, SwiGLU, embedding, the head of ``num_pred_heads x
vocab_size`` outputs); the per-head summary vectors ``phi`` and ``mu``
in the model's float type; norm offsets zero (a norm's weight is
``1 + g``).  The same draws, one layer at a time and widened to
float32, are what the plain reference is given.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import weights as W

# Leaf ids: the top of the tree, then 16 per layer.
_EMBED, _HEAD, _LAYER0, _PER_LAYER = 1, 2, 16, 16
#: Slot of each leaf within its layer's 16 ids.
_SLOTS = {"wq": 0, "wk": 1, "wv": 2, "wo": 3, "w_gate": 4, "w_up": 5,
          "w_down": 6, "phi": 7, "mu": 8}
_INT8 = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def sizes(cfg: dict) -> dict:
    """The published keys under the short names the per-layer readers
    use (``kv`` and ``hd``: what the accepted ``decode_attn_roofline``
    counts a cached row by, exact or summary alike)."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(
        d=d, heads=heads, kv=cfg["num_key_value_heads"], hd=d // heads,
        f=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        layers=cfg["num_hidden_layers"], experts=0, top_k=0,
        window=cfg["window_size"], chunk=cfg["chunk_size"],
        pred_heads=cfg["num_pred_heads"])


def program_config(name: str, cfg: dict):
    """Register and return the program's config for this file."""
    from aiko_services_tpu.models import evabyte
    z = sizes(cfg)
    assert cfg["norm_add_unit_offset"] and cfg["fp32_skip_add"] \
        and cfg["fp32_logits"] and not cfg["attention_bias"] \
        and cfg.get("rope_scaling") is None
    config = evabyte.EvaByteConfig(
        vocab_size=z["vocab"], d_model=z["d"], n_layers=z["layers"],
        n_heads=z["heads"], n_kv_heads=z["kv"], d_ff=z["f"],
        n_pred_heads=z["pred_heads"], window_size=z["window"],
        chunk_size=z["chunk"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        max_seq_len=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["assumed"]["activation_dtype"]))
    evabyte.CONFIGS[name] = config
    return config


def _shapes(z: dict) -> dict:
    d, f, width = z["d"], z["f"], z["heads"] * z["hd"]
    return {"wq": (d, width), "wk": (d, z["kv"] * z["hd"]),
            "wv": (d, z["kv"] * z["hd"]), "wo": (width, d),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def _leaf(layer: int, name: str) -> int:
    return _LAYER0 + layer * _PER_LAYER + _SLOTS[name]


def _layer_tree(words, layer, z, dtype, bits):
    shapes = _shapes(z)
    per_head = (z["kv"], z["hd"])
    tree = {"attn_norm": jnp.zeros((z["d"],), dtype),
            "mlp_norm": jnp.zeros((z["d"],), dtype),
            # Scores s phi . k of about unit spread over a chunk's keys
            # (a draw's own spread is kv ** -0.5), so that the summary
            # weighs its rows unevenly; mu a fifth of a key's spread.
            "phi": (W.float_weight(words, _leaf(layer, "phi"), per_head,
                                   jnp.float32, bits)
                    * z["kv"] ** 0.5).astype(dtype),
            "mu": W.float_weight(words, _leaf(layer, "mu"), per_head,
                                 dtype, bits)}
    for name in _INT8:
        tree[name] = W.int8_weight(words, _leaf(layer, name),
                                   shapes[name], bits)
    return tree


def _top_tree(words, z, dtype, bits):
    return {"embed": W.int8_weight(words, _EMBED, (z["vocab"], z["d"]),
                                   bits),
            "final_norm": jnp.zeros((z["d"],), dtype),
            "lm_head": W.int8_weight(
                words, _HEAD, (z["d"], z["pred_heads"] * z["vocab"]),
                bits)}


def build_params(cfg: dict, seed: int, bits: int = 8):
    """The served parameter tree, made on the device in ONE jitted call
    whose only runtime argument is the seed."""
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
    widened to float32, one layer at a time."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg = cfg
        self.z = z = sizes(cfg)
        self.words = W.seed_words(seed)
        dtype = jnp.dtype(cfg["assumed"]["activation_dtype"])

        def widen(tree):
            return jax.tree.map(
                W.dequantized, tree,
                is_leaf=lambda leaf: isinstance(leaf, dict)
                and "q" in leaf)

        self._top = jax.jit(
            lambda words: widen(_top_tree(words, z, dtype, 8)))
        self._layer = jax.jit(
            lambda words, index: widen(_layer_tree(words, index, z,
                                                   dtype, 8)))

    def top(self):
        return self._top(self.words)

    def layer(self, index: int):
        return self._layer(self.words, jnp.int32(index))
