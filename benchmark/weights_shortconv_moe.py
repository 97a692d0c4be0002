"""Weights from --seed for the short-convolution / attention hybrid with
routed experts, in the tree the program takes (stacked by kind:
``conv_layers``, ``attn_layers``, ``dense_ffns``, ``moe_ffns``; every matrix
input-dim first; the head is the embedding). Same rule as
benchmark/weights.py, leaf ids of this family's own: leaf ``name`` of layer
``l`` (counted over the whole model) is ``normal(fold_in(fold_in(base(seed),
LEAF_ID[name]), l)) / sqrt(fan_in)`` rounded to bfloat16 before scaling (the
taps' fan-in is their count); norm gains ones; the router's weight float32;
the router's selection bias ``normal * 0.02`` in float32 (a trained one is
not zero, and zero would hide a program that gates with the biased score).
The reference makes the same numbers again one layer at a time.

The router's two leaves are drawn from the CONFIGURATION's ``router_seed``,
not from --seed, as benchmark/weights_latent_moe.py does and for its reason:
the routing is the cell's; a seed changes the token ids and every other weight.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.weights import base_key  # the one seed -> key rule

CONV_LEAVES = ("op_norm", "w_in", "taps", "w_out")
ATTN_LEAVES = ("op_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo")
DENSE_LEAVES = ("ffn_norm", "w1", "w3", "w2")
MOE_LEAVES = ("ffn_norm", "router", "router_bias", "w1", "w3", "w2")
TOP_LEAVES = ("tok_emb", "final_norm")
# w1/w3/w2 of a dense layer and of an expert layer differ in shape, not in id
LEAF_ID = {n: 200 + i for i, n in enumerate(dict.fromkeys(
    TOP_LEAVES + CONV_LEAVES + ATTN_LEAVES + MOE_LEAVES))}
ROUTER_LEAVES = ("router", "router_bias")
BIAS_STD = 0.02
STACKS = {"conv_layers": CONV_LEAVES, "attn_layers": ATTN_LEAVES,
          "dense_ffns": DENSE_LEAVES, "moe_ffns": MOE_LEAVES}


def sizes_of(model: dict) -> dict:
    """The sizes everything of this family needs, from a configuration file's
    keys (named as the source's ``config.json`` names them)."""
    return {
        "d": model["hidden_size"], "h": model["num_attention_heads"],
        "kv": model["num_key_value_heads"], "hd": model["head_dim"],
        "K": model["conv_L_cache"], "f": model["intermediate_size"],
        "fm": model["moe_intermediate_size"], "e": model["num_experts"],
        "n_local": model["num_experts"], "first": 0, "k": model["num_experts_per_tok"],
        "scale": float(model["routed_scaling_factor"]), "norm_topk": bool(model["norm_topk_prob"]),
        "v": model["vocab_size"], "layer_types": tuple(model["layer_types"]),
        "layers": model["num_hidden_layers"], "dense": model["num_dense_layers"],
        "theta": float(model["rope_theta"]), "eps": float(model["norm_eps"]),
        "router_seed": int(model["router_seed"]),
    }


def kinds_of(s: dict, layer: int) -> tuple[str, str]:
    """(operator stack, feed-forward stack) of layer ``layer``."""
    return ("conv_layers" if s["layer_types"][layer] == "conv" else "attn_layers",
            "moe_ffns" if layer >= s["dense"] else "dense_ffns")


def layers_of_kind(s: dict, stack: str) -> list[int]:
    return [l for l in range(len(s["layer_types"])) if stack in kinds_of(s, l)]


def leaf_shape(name: str, s: dict, moe: bool = False) -> tuple[tuple[int, ...], int]:
    """(shape of one layer's leaf or of a top-level leaf, fan-in; 0 = ones,
    -1 = the router's bias)."""
    d, nq, nkv = s["d"], s["h"] * s["hd"], s["kv"] * s["hd"]
    table = {
        "tok_emb": ((s["v"], d), d), "final_norm": ((d,), 0),
        "op_norm": ((d,), 0), "ffn_norm": ((d,), 0),
        "w_in": ((d, 3 * d), d), "taps": ((s["K"], d), s["K"]), "w_out": ((d, d), d),
        "wq": ((d, nq), d), "wk": ((d, nkv), d), "wv": ((d, nkv), d),
        "q_norm": ((s["hd"],), 0), "k_norm": ((s["hd"],), 0), "wo": ((nq, d), nq),
        "router": ((d, s["e"]), d), "router_bias": ((s["e"],), -1),
    }
    if name in ("w1", "w3"):
        return (((s["n_local"], d, s["fm"]), d) if moe else ((d, s["f"]), d))
    if name == "w2":
        return (((s["n_local"], s["fm"], d), s["fm"]) if moe else ((s["f"], d), s["f"]))
    return table[name]


def make_leaf(key: jax.Array, name: str, s: dict, dtype, layer=None, moe: bool = False):
    shape, fan_in = leaf_shape(name, s, moe)
    if not fan_in:
        return jnp.ones(shape, dtype)
    if name in ROUTER_LEAVES:
        key = base_key(s["router_seed"])
    k = jax.random.fold_in(key, LEAF_ID[name])
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    w = jax.random.normal(k, shape, jnp.float32)
    if fan_in < 0:
        return w * BIAS_STD
    # rounded to bfloat16 BEFORE scaling (benchmark/weights.py says why)
    w = w.astype(jnp.bfloat16).astype(jnp.float32) * (1.0 / math.sqrt(fan_in))
    return w if name == "router" else w.astype(dtype)


def make_stack_layer(key: jax.Array, s: dict, dtype, layer, stack: str) -> dict:
    """One kind's leaves of layer ``layer`` (its index in the whole model)."""
    return {n: make_leaf(key, n, s, dtype, layer, stack == "moe_ffns") for n in STACKS[stack]}


def make_layer(key: jax.Array, s: dict, dtype, layer, kinds: tuple[str, str]) -> dict:
    """Layer ``layer`` whole: its operator's and its feed-forward's leaves."""
    return {**make_stack_layer(key, s, dtype, layer, kinds[0]),
            **make_stack_layer(key, s, dtype, layer, kinds[1])}


def make_params(key: jax.Array, s: dict, dtype) -> dict:
    """The whole tree, each kind's layers stacked. Call under jit."""
    out = {n: make_leaf(key, n, s, dtype) for n in TOP_LEAVES}
    for stack in STACKS:
        # one layer after another (lax.map): the sampler's float32
        # temporaries are one layer's, not a stack's
        out[stack] = jax.lax.map(
            lambda l, stack=stack: make_stack_layer(key, s, dtype, l, stack),
            jnp.asarray(layers_of_kind(s, stack), jnp.int32))
    return out


def n_params(s: dict) -> int:
    def count(names, moe=False):
        return sum(math.prod(leaf_shape(n, s, moe)[0]) for n in names)

    return count(TOP_LEAVES) + sum(
        len(layers_of_kind(s, stack)) * count(names, stack == "moe_ffns")
        for stack, names in STACKS.items())
