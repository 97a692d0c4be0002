"""Weights from --seed for the selective state-space / attention hybrid, in
the tree the program takes (stacked by kind: ``mamba_layers``,
``attn_layers``, ``dense_ffns``; every matrix input-dim first; the head is the
embedding). Same rule as benchmark/weights.py, leaf ids of this family's own:
leaf ``name`` of layer ``l`` (counted over the whole model) is drawn from
``fold_in(fold_in(base(seed), LEAF_ID[name]), l)``.

Matrices, the convolution's taps and its bias are ``normal / sqrt(fan_in)``
rounded to bfloat16 before scaling (the taps' and the bias's fan-in is the
number of taps); norm gains ones. The recurrence gets the family's own
initialisation, so that random weights remember as trained ones do (with a
unit-variance ``w_dt`` the state forgets within a few tokens and a dropped
state would not show): ``a_log = log(1..N)`` a channel (float32, stored ``[N,
E]`` as the state is), ``d_skip`` ones (float32), ``b_dt`` the inverse
softplus of a step size drawn log-uniform in [1e-3, 0.1] (float32), ``w_dt``
uniform(+-R^-1/2). The reference makes the same numbers again one layer at a
time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.weights import base_key  # noqa: F401  (the one seed -> key rule)

MAMBA_LEAVES = ("op_norm", "w_in", "conv_w", "conv_b", "w_x", "dt_norm", "b_norm", "c_norm",
                "w_dt", "b_dt", "a_log", "d_skip", "w_out")
ATTN_LEAVES = ("op_norm", "wq", "wk", "wv", "wo")
FFN_LEAVES = ("ffn_norm", "w1", "w3", "w2")
TOP_LEAVES = ("tok_emb", "final_norm")
LEAF_ID = {n: 300 + i for i, n in enumerate(dict.fromkeys(
    TOP_LEAVES + MAMBA_LEAVES + ATTN_LEAVES + FFN_LEAVES))}
STACKS = {"mamba_layers": MAMBA_LEAVES, "attn_layers": ATTN_LEAVES, "dense_ffns": FFN_LEAVES}
F32_LEAVES = ("a_log", "d_skip", "b_dt")
DT_MIN, DT_MAX = 1e-3, 0.1
ONES, A_LOG, DT_BIAS, DT_WEIGHT = 0, -1, -2, -3


def sizes_of(model: dict) -> dict:
    """The sizes everything of this family needs, from a configuration file's
    keys (named as the source's ``config.json`` names them)."""
    layers = model["num_hidden_layers"]
    period, offset = model["attn_layer_period"], model["attn_layer_offset"]
    return {
        "d": model["hidden_size"], "h": model["num_attention_heads"],
        "kv": model["num_key_value_heads"], "hd": model["head_dim"],
        "f": model["intermediate_size"], "v": model["vocab_size"], "layers": layers,
        "e": model["mamba_expand"] * model["hidden_size"], "n": model["mamba_d_state"],
        "K": model["mamba_d_conv"], "r": model["mamba_dt_rank"],
        "period": period, "offset": offset, "eps": float(model["rms_norm_eps"]),
        "layer_types": tuple("attention" if l % period == offset else "mamba"
                             for l in range(layers)),
    }


def kind_of(s: dict, layer: int) -> str:
    """The mixer's stack of layer ``layer``."""
    return "attn_layers" if s["layer_types"][layer] == "attention" else "mamba_layers"


def layers_of_kind(s: dict, stack: str) -> list[int]:
    if stack == "dense_ffns":
        return list(range(s["layers"]))
    return [l for l in range(s["layers"]) if kind_of(s, l) == stack]


def leaf_shape(name: str, s: dict) -> tuple[tuple[int, ...], int]:
    """(shape of one layer's leaf or of a top-level leaf, how it is drawn: a
    fan-in, or ONES, A_LOG, DT_BIAS, DT_WEIGHT)."""
    d, nq, nkv, f = s["d"], s["h"] * s["hd"], s["kv"] * s["hd"], s["f"]
    E, N, R, K = s["e"], s["n"], s["r"], s["K"]
    return {
        "tok_emb": ((s["v"], d), d), "final_norm": ((d,), ONES),
        "op_norm": ((d,), ONES), "ffn_norm": ((d,), ONES),
        "w_in": ((d, 2 * E), d), "conv_w": ((K, E), K), "conv_b": ((E,), K),
        "w_x": ((E, R + 2 * N), E), "dt_norm": ((R,), ONES), "b_norm": ((N,), ONES),
        "c_norm": ((N,), ONES), "w_dt": ((R, E), DT_WEIGHT), "b_dt": ((E,), DT_BIAS),
        "a_log": ((N, E), A_LOG), "d_skip": ((E,), ONES), "w_out": ((E, d), E),
        "wq": ((d, nq), d), "wk": ((d, nkv), d), "wv": ((d, nkv), d), "wo": ((nq, d), nq),
        "w1": ((d, f), d), "w3": ((d, f), d), "w2": ((f, d), f),
    }[name]


def make_leaf(key: jax.Array, name: str, s: dict, dtype, layer=None):
    shape, how = leaf_shape(name, s)
    dtype = jnp.float32 if name in F32_LEAVES else dtype
    if how == ONES:
        return jnp.ones(shape, dtype)
    if how == A_LOG:
        rows = jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
        return jnp.broadcast_to(rows[:, None], shape).astype(dtype)
    k = jax.random.fold_in(key, LEAF_ID[name])
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    if how == DT_BIAS:
        u = jax.random.uniform(k, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)       # softplus^-1(dt)
    if how == DT_WEIGHT:
        bound = shape[0] ** -0.5
        w = jax.random.uniform(k, shape, jnp.float32, -1.0, 1.0)
        return (w.astype(jnp.bfloat16).astype(jnp.float32) * bound).astype(dtype)
    # rounded to bfloat16 BEFORE scaling (benchmark/weights.py says why)
    w = jax.random.normal(k, shape, jnp.float32)
    return (w.astype(jnp.bfloat16).astype(jnp.float32) * (1.0 / math.sqrt(how))).astype(dtype)


def make_stack_layer(key: jax.Array, s: dict, dtype, layer, stack: str) -> dict:
    """One kind's leaves of layer ``layer`` (its index in the whole model)."""
    return {n: make_leaf(key, n, s, dtype, layer) for n in STACKS[stack]}


def make_layer(key: jax.Array, s: dict, dtype, layer, stack: str) -> dict:
    """Layer ``layer`` whole: its mixer's and its feed-forward's leaves."""
    return {**make_stack_layer(key, s, dtype, layer, stack),
            **make_stack_layer(key, s, dtype, layer, "dense_ffns")}


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
    def count(names):
        return sum(math.prod(leaf_shape(n, s)[0]) for n in names)

    return count(TOP_LEAVES) + sum(
        len(layers_of_kind(s, stack)) * count(names) for stack, names in STACKS.items())
