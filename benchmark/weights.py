"""Weights from --seed, made by the benchmark, in the tree layout the system
under test takes (per-layer arrays stacked on axis 0, every matrix laid out
input-dim first so that ``x @ w`` applies it).

The program gets the whole tree from ONE jitted call on the device, in the
type it serves or trains in. The plain reference regenerates the very same
numbers one layer (or one leaf) at a time from the same keys, so it takes
nothing the program has made: leaf ``name`` of layer ``l`` is
``normal(fold_in(fold_in(base(seed), LEAF_ID[name]), l)) / sqrt(fan_in)``
rounded to the configuration's dtype; norm gains are ones.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "ffn_norm", "w1", "w3", "w2")
TOP_LEAVES = ("tok_emb", "final_norm", "lm_head")
LEAF_ID = {n: i for i, n in enumerate(TOP_LEAVES + LAYER_LEAVES)}


def sizes_of(model: dict) -> dict:
    """The few sizes everything here needs, from a configuration file's
    model keys (named as the source's ``config.json`` names them)."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    hd = model.get("head_dim") or d // h
    return {
        "d": d, "h": h, "kv": model["num_key_value_heads"], "hd": hd,
        "f": model["intermediate_size"], "v": model["vocab_size"],
        "layers": model["num_hidden_layers"], "theta": float(model["rope_theta"]),
        "eps": float(model["rms_norm_eps"]),
    }


def leaf_shape(name: str, s: dict) -> tuple[tuple[int, ...], int]:
    """(shape of one layer's leaf or of a top-level leaf, fan-in; 0 = ones)."""
    d, nq, nkv, f, v = s["d"], s["h"] * s["hd"], s["kv"] * s["hd"], s["f"], s["v"]
    return {
        "tok_emb": ((v, d), d), "lm_head": ((d, v), d), "final_norm": ((d,), 0),
        "attn_norm": ((d,), 0), "ffn_norm": ((d,), 0),
        "wq": ((d, nq), d), "wk": ((d, nkv), d), "wv": ((d, nkv), d),
        "wo": ((nq, d), nq), "w1": ((d, f), d), "w3": ((d, f), d), "w2": ((f, d), f),
    }[name]


def base_key(seed: int) -> jax.Array:
    # the driver's seeds pass 2**31: key() takes 31 bits, fold_in the rest
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def make_leaf(key: jax.Array, name: str, s: dict, dtype, layer: int | jax.Array | None = None):
    shape, fan_in = leaf_shape(name, s)
    if not fan_in:
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, LEAF_ID[name])
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    # rounded to bfloat16 BEFORE scaling: a compiler may fold the scale into
    # the sampler's own constants, and then a layer made alone and the same
    # layer made inside the whole tree differ in the last bit; across a
    # conversion nothing can be folded
    w = jax.random.normal(k, shape, jnp.float32).astype(jnp.bfloat16)
    return (w.astype(jnp.float32) * (1.0 / math.sqrt(fan_in))).astype(dtype)


def make_layer(key: jax.Array, s: dict, dtype, layer) -> dict:
    return {n: make_leaf(key, n, s, dtype, layer) for n in LAYER_LEAVES}


def make_params(key: jax.Array, s: dict, dtype) -> dict:
    """The whole tree, layers stacked. Call under jit (one program)."""
    # one layer after another (lax.map), so that the sampler's float32
    # temporaries are one layer's, not the whole stack's
    layers = jax.lax.map(lambda l: make_layer(key, s, dtype, l), jnp.arange(s["layers"]))
    top = {n: make_leaf(key, n, s, dtype) for n in TOP_LEAVES}
    return {"tok_emb": top["tok_emb"], "layers": layers,
            "final_norm": top["final_norm"], "lm_head": top["lm_head"]}


def n_matmul_params(s: dict) -> int:
    per_layer = sum(
        math.prod(leaf_shape(n, s)[0]) for n in LAYER_LEAVES if leaf_shape(n, s)[1]
    )
    return s["layers"] * per_layer + s["d"] * s["v"]


def n_params(s: dict) -> int:
    return n_matmul_params(s) + s["v"] * s["d"] + (2 * s["layers"] + 1) * s["d"]
