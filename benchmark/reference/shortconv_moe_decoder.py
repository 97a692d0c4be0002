"""Plain reference: a pre-norm decoder whose operator is, layer by layer as a
declared list says, a gated short convolution or grouped-query attention with
a norm on every query and key head, and whose feed-forward is a dense SwiGLU
in the leading layers and sigmoid-routed experts after them; tied head — in
straightforward ``jax.numpy`` and float32 with matrix products at precision
``highest``. No cache, no state, no kernels, no batching: the convolution is
three shifted multiply-adds over the whole sequence, the experts a loop. It
imports nothing of the program and takes nothing the program has made.

It follows ISSUE 31 section 1 line for line (the published equations; the
short convolution and the attention block are ``Lfm2ShortConv`` and
``Lfm2Attention`` of the dense sibling's public source). Departures, each also
in the configuration file: rope in rotate-half layout on split halves (what
the source does too); the gates' normaliser is ``sum + 1e-6`` as published
(the program adds 1e-20: four sigmoids sum far above either); nothing stands
in for the layers the depth cut leaves out.

Every function takes ONE sequence ``[S, D]``; the layer is a function of one
layer's weights. Scores are made for ``HEAD_BLOCK`` heads at a time. ``cast``
is the hook for the lower-precision control (both operands of every matrix
product); ``fault`` plants one of ``FAULTS`` for the tests of the comparison.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import Cast, HIGHEST, identity, mm, rms_norm, rounded_to  # noqa: F401
from benchmark.reference.dense_decoder import attention as causal_attention, embed, rope  # noqa: F401

HEAD_BLOCK = 8
GATE_EPS = 1e-6
# conv_state_dropped: the convolution sees only its own position (what a
# decode step that lost the slot's state computes); conv_gate_left_out: ``C *``
# omitted; biased_gate: gating with the selection score ``s + b``;
# experts_shifted: expert j answers with expert j + 1's weights
FAULTS = ("conv_state_dropped", "conv_gate_left_out", "qk_norm_left_out",
          "gates_not_normalised", "biased_gate", "experts_shifted")


def short_conv(h: jax.Array, lp: dict, s: dict, cast: Cast = identity, fault: str = "") -> jax.Array:
    """Gated short convolution of one normed sequence h [S, D] -> [S, D]."""
    S, D, K = h.shape[0], s["d"], s["K"]
    bcu = mm(h, lp["w_in"], cast)
    b, c, u = bcu[:, :D], bcu[:, D:2 * D], bcu[:, 2 * D:]
    z = b * u
    taps = lp["taps"].astype(jnp.float32)                          # [K, D]
    if fault == "conv_state_dropped":
        taps = taps.at[:K - 1].set(0.0)
    zz = jnp.concatenate([jnp.zeros((K - 1, D), jnp.float32), z])  # z_{<0} = 0
    conv = sum(taps[j] * zz[j:j + S] for j in range(K))            # c_t = sum_j w_j z_{t-(K-1)+j}
    y = conv if fault == "conv_gate_left_out" else c * conv
    return mm(y, lp["w_out"], cast)


def attention(h: jax.Array, lp: dict, s: dict, cast: Cast = identity, fault: str = "") -> jax.Array:
    """Grouped-query attention of one normed sequence h [S, D] -> [S, D]."""
    S, H, KV, hd = h.shape[0], s["h"], s["kv"], s["hd"]
    q = mm(h, lp["wq"], cast).reshape(S, H, hd)
    k = mm(h, lp["wk"], cast).reshape(S, KV, hd)
    if fault != "qk_norm_left_out":
        q, k = rms_norm(q, lp["q_norm"], s["eps"]), rms_norm(k, lp["k_norm"], s["eps"])
    q, k = rope(q, s["theta"]), rope(k, s["theta"])
    v = mm(h, lp["wv"], cast).reshape(S, KV, hd)
    rep = H // KV
    hb = min(HEAD_BLOCK, H)                                        # a few heads at a time
    kvb = max(hb // rep, 1)

    def heads(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * hb, hb, axis=1)
        ks = jax.lax.dynamic_slice_in_dim(k, i * kvb, kvb, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v, i * kvb, kvb, axis=1)
        return causal_attention(qs, ks, vs, cast)                  # [S, hb, hd]

    out = jax.lax.map(heads, jnp.arange(H // hb))                  # [H/hb, S, hb, hd]
    return mm(out.transpose(1, 0, 2, 3).reshape(S, H * hd), lp["wo"], cast)


def swiglu(h, w1, w3, w2, cast: Cast = identity):
    return mm(jax.nn.silu(mm(h, w1, cast)) * mm(h, w3, cast), w2, cast)


def route(h: jax.Array, lp: dict, s: dict, fault: str = "") -> tuple[jax.Array, jax.Array]:
    """h [S, D] -> gates [S, e] float32 (0 where not chosen), chosen [S, e] bool.
    Scores are always float32 at ``highest`` (the configuration states float32
    router scores: the control does not round them)."""
    sc = jax.nn.sigmoid(mm(h, lp["router"]))                       # [S, e]
    biased = sc + lp["router_bias"].astype(jnp.float32)            # selection only
    _, idx = jax.lax.top_k(biased, s["k"])
    chosen = jnp.zeros(sc.shape, bool).at[jnp.arange(h.shape[0])[:, None], idx].set(True)
    gates = jnp.where(chosen, biased if fault == "biased_gate" else sc, 0.0)
    if s["norm_topk"] and fault != "gates_not_normalised":
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + GATE_EPS)
    return gates * s["scale"], chosen


def experts(h: jax.Array, lp: dict, s: dict, cast: Cast = identity, fault: str = "",
            with_routes: bool = False):
    """This holder's part of the expert layer (all of it when it holds every
    expert); with ``with_routes`` also which experts each token chose."""
    gates, chosen = route(h, lp, s, fault)
    y = jnp.zeros_like(h)
    for j in range(s["n_local"]):                                   # a plain loop over experts
        g = gates[:, s["first"] + j]
        w = (j + 1) % s["n_local"] if fault == "experts_shifted" else j
        y = y + g[:, None] * swiglu(h, lp["w1"][w], lp["w3"][w], lp["w2"][w], cast)
    return (y, chosen) if with_routes else y


def layer(x: jax.Array, lp: dict, s: dict, cast: Cast = identity, fault: str = "",
          with_routes: bool = False):
    """One decoder layer on one sequence x [S, D]: a convolution layer if
    ``lp`` holds ``w_in``, an expert layer if it holds a router."""
    h = rms_norm(x, lp["op_norm"], s["eps"])
    op = short_conv if "w_in" in lp else attention
    x = x + op(h, lp, s, cast, fault)
    h = rms_norm(x, lp["ffn_norm"], s["eps"])
    if "router" in lp:
        y, chosen = experts(h, lp, s, cast, fault, with_routes=True)
    else:
        y, chosen = swiglu(h, lp["w1"], lp["w3"], lp["w2"], cast), None
    return (x + y, chosen) if with_routes else x + y


def logits(x: jax.Array, final_norm: jax.Array, tok_emb: jax.Array, s: dict,
           cast: Cast = identity) -> jax.Array:
    """The tied head: the embedding, transposed."""
    return mm(rms_norm(x, final_norm, s["eps"]), tok_emb.astype(jnp.float32).T, cast)


def layers_of(params: dict, s: dict):
    """Each layer's weights in the declared order, from the program's tree
    (stacked by kind: ``conv_layers``, ``attn_layers``, ``dense_ffns``,
    ``moe_ffns``)."""
    seen = {"conv_layers": 0, "attn_layers": 0, "dense_ffns": 0, "moe_ffns": 0}
    for l, kind in enumerate(s["layer_types"]):
        op = "conv_layers" if kind == "conv" else "attn_layers"
        ff = "moe_ffns" if l >= s["dense"] else "dense_ffns"
        lp = {}
        for stack in (op, ff):
            lp.update(jax.tree.map(lambda a, i=seen[stack]: a[i], params[stack]))
            seen[stack] += 1
        yield lp


def forward(params: dict, tokens: jax.Array, s: dict, cast: Cast = identity,
            fault: str = "") -> jax.Array:
    """Whole model on one sequence of tokens [S] -> logits [S, V] (small sizes
    only: the driver walks the layers itself)."""
    x = embed(params["tok_emb"], tokens)
    for lp in layers_of(params, s):
        x = layer(x, lp, s, cast, fault)
    return logits(x, params["final_norm"], params["tok_emb"], s, cast)
