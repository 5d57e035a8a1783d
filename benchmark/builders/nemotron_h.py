"""Builder for the configurations that ``models/nemotron_h.py`` serves:
a stack of Mamba-2, attention and latent mixture-of-experts layers
(``nemotron_h``'s ``hybrid_override_pattern``).

The one place that knows the program's names for this family: it turns
a configuration file's published keys into the program's
``NemotronHConfig`` and lays the seeded draws of
``benchmark/weights.py`` out as the program's parameter tree: int8
weight-only 2-D matrices, routed experts (3-D leaves) and the router in
the model's float type, the small vectors in float32.  The same draws,
one layer or one expert at a time and widened to float32, are what the
plain reference is given.

A configuration cut to a chip's share keeps ``num_hidden_layers``
layers of the published pattern from ``layer_offset`` on, and holds
``n_routed_experts`` routed experts from ``experts_first`` on, of the
``reduced_from`` count the router still scores.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import weights as W

# Leaf ids: the top of the tree, then 16 per layer.
_EMBED, _HEAD, _LAYER0, _PER_LAYER = 1, 2, 16, 16
#: Slot of each leaf within its layer's 16 ids.
_SLOTS = {"in_proj": 0, "out_proj": 1, "conv_w": 2, "conv_b": 3,
          "dt_bias": 4, "a_log": 5,
          "wq": 0, "wk": 1, "wv": 2, "wo": 3,
          "router": 0, "router_bias": 1, "latent_in": 2, "latent_out": 3,
          "shared_up": 4, "shared_down": 5, "w_up": 6, "w_down": 7}
_EXPERT = ("w_up", "w_down")


def pattern(cfg: dict) -> str:
    """The layers kept: one character each, M, * or E."""
    first = cfg.get("layer_offset", 0)
    return cfg["hybrid_override_pattern"][
        first:first + cfg["num_hidden_layers"]]


def sizes(cfg: dict) -> dict:
    """The published keys under the short names the per-layer readers
    use.  ``layers`` counts the layers that own a KV pool (the decode
    attention reader multiplies one layer's K/V bytes by it); the
    other kinds have counts of their own."""
    kept = pattern(cfg)
    held = cfg["n_routed_experts"]
    return dict(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        vocab=cfg["vocab_size"], layers=kept.count("*"),
        mamba_layers=kept.count("M"), expert_layers=kept.count("E"),
        f=cfg["moe_intermediate_size"], latent=cfg["moe_latent_size"],
        shared=cfg["moe_shared_expert_intermediate_size"],
        experts=held, top_k=cfg["num_experts_per_tok"],
        experts_total=cfg.get("reduced_from", {}).get(
            "n_routed_experts", held),
        experts_first=cfg.get("experts_first", 0),
        mamba_heads=cfg["mamba_num_heads"],
        mamba_hd=cfg["mamba_head_dim"], groups=cfg["n_groups"],
        state=cfg["ssm_state_size"], conv=cfg["conv_kernel"],
        chunk=cfg["chunk_size"])


def _derived(z: dict) -> dict:
    d_inner = z["mamba_heads"] * z["mamba_hd"]
    conv_dim = d_inner + 2 * z["groups"] * z["state"]
    return dict(d_inner=d_inner, conv_dim=conv_dim,
                proj=d_inner + conv_dim + z["mamba_heads"])


def program_config(name: str, cfg: dict):
    """Register and return the program's config for this file."""
    from aiko_services_tpu.models import nemotron_h
    z = sizes(cfg)
    held = None
    if z["experts"] != z["experts_total"]:
        held = (z["experts_first"], z["experts"])
    config = nemotron_h.NemotronHConfig(
        vocab_size=z["vocab"], d_model=z["d"], pattern=pattern(cfg),
        n_heads=z["heads"], n_kv_heads=z["kv"], head_dim=z["hd"],
        mamba_heads=z["mamba_heads"], mamba_head_dim=z["mamba_hd"],
        ssm_groups=z["groups"], ssm_state=z["state"],
        conv_kernel=z["conv"], chunk_size=z["chunk"],
        n_experts=z["experts_total"], moe_top_k=z["top_k"], d_ff=z["f"],
        d_latent=z["latent"], d_shared=z["shared"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        experts_held=held, norm_eps=float(cfg["norm_eps"]),
        max_seq_len=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["assumed"]["activation_dtype"]))
    nemotron_h.CONFIGS[name] = config
    return config


def _shapes(z: dict) -> dict:
    x = _derived(z)
    d, width = z["d"], z["heads"] * z["hd"]
    kv_width = z["kv"] * z["hd"]
    return {"in_proj": (d, x["proj"]), "out_proj": (x["d_inner"], d),
            "conv_w": (z["conv"], x["conv_dim"]),
            "wq": (d, width), "wk": (d, kv_width), "wv": (d, kv_width),
            "wo": (width, d), "router": (d, z["experts_total"]),
            "latent_in": (d, z["latent"]), "latent_out": (z["latent"], d),
            "shared_up": (d, z["shared"]), "shared_down": (z["shared"], d),
            "w_up": (z["latent"], z["f"]), "w_down": (z["f"], z["latent"])}


def _leaf(layer, name):
    return _LAYER0 + layer * _PER_LAYER + _SLOTS[name]


def _unit(words, leaf_id, count):
    """``count`` float32 draws in [0, 1] (255 levels) for the small
    vectors; never coarsened, they are not weight matrices."""
    q = W.draw_q(W.leaf_key(words, leaf_id), (1, count))[0]
    return (q.astype(jnp.float32) + 127.0) / 254.0


def _vectors(words, layer, z):
    """A Mamba layer's vectors, float32: softplus(dt_bias) spread
    log-uniformly over the published time_step_min..max, A over 1..16,
    the convolution bias within +-0.1, D one."""
    heads, x = z["mamba_heads"], _derived(z)
    step = jnp.exp(math.log(0.001) + _unit(words, _leaf(layer, "dt_bias"),
                                           heads)
                   * (math.log(0.1) - math.log(0.001)))
    return {"conv_b": 0.2 * _unit(words, _leaf(layer, "conv_b"),
                                  x["conv_dim"]) - 0.1,
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "a_log": jnp.log(1.0 + 15.0 * _unit(
                words, _leaf(layer, "a_log"), heads)),
            "d_skip": jnp.ones((heads,), jnp.float32)}


def _layer_tree(words, layer, kind, z, dtype, bits, matrix, expert_leaves):
    """One layer of the tree.  ``matrix(leaf_id, shape)`` makes a 2-D
    matrix in the layout wanted (int8 container or float32)."""
    shapes = _shapes(z)
    tree = {"norm": jnp.ones((z["d"],), dtype)}
    if kind == "M":
        for name in ("in_proj", "out_proj"):
            tree[name] = matrix(_leaf(layer, name), shapes[name])
        tree["conv_w"] = W.float_weight(words, _leaf(layer, "conv_w"),
                                        shapes["conv_w"], jnp.float32, bits)
        tree["gate_norm"] = jnp.ones((_derived(z)["d_inner"],), dtype)
        tree.update(_vectors(words, layer, z))
    elif kind == "*":
        for name in ("wq", "wk", "wv", "wo"):
            tree[name] = matrix(_leaf(layer, name), shapes[name])
    else:
        moe = {"router": W.float_weight(words, _leaf(layer, "router"),
                                        shapes["router"], dtype, bits),
               "router_bias": 0.1 * _unit(
                   words, _leaf(layer, "router_bias"),
                   z["experts_total"]) - 0.05}
        for name in ("latent_in", "latent_out", "shared_up",
                     "shared_down"):
            moe[name] = matrix(_leaf(layer, name), shapes[name])
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
    per_expert = shape[0] * shape[1]
    q = W.draw_q(key, (z["experts"],) + shape, bits,
                 offset=z["experts_first"] * per_expert)
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
    kept = pattern(cfg)
    dtype = jnp.dtype(cfg["assumed"]["activation_dtype"])
    # The 3-D leaf is drawn with one 32-bit counter.
    assert z["experts_total"] * z["latent"] * z["f"] < 2 ** 32

    @jax.jit
    def build(words):
        def matrix(leaf_id, shape):
            return W.int8_weight(words, leaf_id, shape, bits)

        tree = _top_tree(words, z, dtype, bits)
        tree["layers"] = [
            _layer_tree(words, layer, kind, z, dtype, bits, matrix, True)
            for layer, kind in enumerate(kept)]
        return tree

    return build(W.seed_words(seed))


class ReferenceWeights:
    """What the plain reference is given: the same draws at 8 bits,
    widened to float32, one layer (or one expert) at a time."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg = cfg
        self.z = z = sizes(cfg)
        self.kept = kept = pattern(cfg)
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

        @functools.partial(jax.jit, static_argnames=("index",))
        def layer(words, index):
            def matrix(leaf_id, shape):
                return W.int8_weight(words, leaf_id, shape, 8)

            tree = _layer_tree(words, index, kept[index], z, dtype, 8,
                               matrix, False)
            tree.update(tree.pop("moe", {}))
            return widen(tree)

        @functools.partial(jax.jit, static_argnames=("name",))
        def expert(words, index, which, name):
            # Element (e, k, n) of the 3-D leaf, drawn alone.
            shape = shapes[name]
            key = W.leaf_key(words, _LAYER0 + index * _PER_LAYER
                             + _SLOTS[name])
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
        """Layer ``index`` of the kept pattern, float32; an ``E``
        layer's tree holds everything but its routed experts."""
        return self._layer(self.words, index)

    def expert(self, index: int, which: int):
        """Routed expert ``which`` (its number among ALL experts)."""
        return {name: self._expert(self.words, jnp.int32(index),
                                   jnp.int32(which), name)
                for name in _EXPERT}
