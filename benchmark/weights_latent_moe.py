"""Weights from --seed for the latent-attention expert decoder, in the tree
the program takes (two stacks of layers, ``dense_layers`` then ``moe_layers``,
every matrix input-dim first). Same rule as benchmark/weights.py, leaf ids of
this family's own: leaf ``name`` of layer ``l`` (counted over the whole model)
is ``normal(fold_in(fold_in(base(seed), LEAF_ID[name]), l)) / sqrt(fan_in)``
rounded to bfloat16 before scaling; norm gains ones; the router's weight
float32; the router's selection bias ``normal * 0.02`` in float32 (a trained
one is not zero, and zero would hide a program that gates with the biased
score). The reference makes the same numbers again one layer at a time.

The router's two leaves are drawn from the CONFIGURATION's ``router_seed``,
not from --seed: which experts a router favours decides how many of this
holder's are hit a decode step, and so the step's bytes and time (a seeded
router made seeds differ by 1.3% in the step and 2% in tokens/s on the chip,
PR 27). Like the schedule of sizes, the routing is the cell's; a seed changes
the token ids and every other weight.

``s`` (``sizes_of``) holds this chip's share: ``n_local`` experts from
``first``, a vocabulary slice of ``v`` rows.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.weights import base_key  # the one seed -> key rule

ATTN_LEAVES = ("attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo",
               "ffn_norm")
DENSE_LEAVES = ATTN_LEAVES + ("w1", "w3", "w2")
MOE_LEAVES = ATTN_LEAVES + ("router", "router_bias", "w1", "w3", "w2", "ws1", "ws3", "ws2")
TOP_LEAVES = ("tok_emb", "final_norm", "lm_head")
# w1/w3/w2 of a dense layer and of an expert layer differ in shape, not in id:
# a layer is one or the other
LEAF_ID = {n: 100 + i for i, n in enumerate(TOP_LEAVES + MOE_LEAVES)}
ROUTER_LEAVES = ("router", "router_bias")
BIAS_STD = 0.02


def sizes_of(model: dict) -> dict:
    """The sizes everything of this family needs, from a configuration file's
    keys (named as the source's ``config.json`` names them)."""
    rs = model["rope_scaling"]
    return {
        "d": model["hidden_size"], "h": model["num_attention_heads"],
        "qr": model["q_lora_rank"], "kr": model["kv_lora_rank"],
        "nope": model["qk_nope_head_dim"], "rope": model["qk_rope_head_dim"],
        "vd": model["v_head_dim"], "f": model["intermediate_size"],
        "fm": model["moe_intermediate_size"], "e": model["router_outputs"],
        "n_local": model["n_routed_experts"], "first": model["first_expert"],
        "shared": model["n_shared_experts"], "k": model["num_experts_per_tok"],
        "groups": model["n_group"], "topk_groups": model["topk_group"],
        "scale": float(model["routed_scaling_factor"]), "norm_topk": bool(model["norm_topk_prob"]),
        "v": model["vocab_size"], "layers": model["num_hidden_layers"],
        "dense": model["first_k_dense_replace"], "theta": float(model["rope_theta"]),
        "eps": float(model["rms_norm_eps"]), "router_seed": int(model["router_seed"]),
        "yarn": {"factor": float(rs["factor"]), "orig": int(rs["original_max_position_embeddings"]),
                 "beta_fast": float(rs["beta_fast"]), "beta_slow": float(rs["beta_slow"]),
                 "mscale": float(rs["mscale"]), "mscale_all_dim": float(rs["mscale_all_dim"])},
    }


def leaf_shape(name: str, s: dict, moe: bool = False) -> tuple[tuple[int, ...], int]:
    """(shape of one layer's leaf or of a top-level leaf, fan-in; 0 = ones,
    -1 = the router's bias)."""
    d, H, qr, kr = s["d"], s["h"], s["qr"], s["kr"]
    fs = s["shared"] * s["fm"]
    table = {
        "tok_emb": ((s["v"], d), d), "lm_head": ((d, s["v"]), d), "final_norm": ((d,), 0),
        "attn_norm": ((d,), 0), "ffn_norm": ((d,), 0), "q_norm": ((qr,), 0), "kv_norm": ((kr,), 0),
        "wq_a": ((d, qr), d), "wq_b": ((qr, H * (s["nope"] + s["rope"])), qr),
        "wkv_a": ((d, kr + s["rope"]), d), "wkv_b": ((kr, H * (s["nope"] + s["vd"])), kr),
        "wo": ((H * s["vd"], d), H * s["vd"]),
        "router": ((d, s["e"]), d), "router_bias": ((s["e"],), -1),
        "ws1": ((d, fs), d), "ws3": ((d, fs), d), "ws2": ((fs, d), fs),
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


def make_layer(key: jax.Array, s: dict, dtype, layer, moe: bool) -> dict:
    """Layer ``layer`` (its index in the whole model) as a dense or an expert layer."""
    return {n: make_leaf(key, n, s, dtype, layer, moe)
            for n in (MOE_LEAVES if moe else DENSE_LEAVES)}


def make_params(key: jax.Array, s: dict, dtype) -> dict:
    """The whole tree, each stack's layers stacked. Call under jit."""
    nd, L = s["dense"], s["layers"]
    out = {n: make_leaf(key, n, s, dtype) for n in TOP_LEAVES}
    # one layer after another (lax.map): the sampler's float32 temporaries
    # are one layer's, not a stack's
    out["dense_layers"] = jax.lax.map(
        lambda l: make_layer(key, s, dtype, l, False), jnp.arange(0, nd))
    out["moe_layers"] = jax.lax.map(
        lambda l: make_layer(key, s, dtype, l, True), jnp.arange(nd, L))
    return out


def n_params(s: dict) -> int:
    def count(names, moe):
        return sum(math.prod(leaf_shape(n, s, moe)[0]) for n in names)

    return (count(TOP_LEAVES, False) + s["dense"] * count(DENSE_LEAVES, False)
            + (s["layers"] - s["dense"]) * count(MOE_LEAVES, True))
