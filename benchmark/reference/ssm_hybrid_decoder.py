"""Plain reference: a pre-norm decoder whose mixer is, layer by layer as a
period and an offset say, a Mamba-1 selective state-space mixer (with an RMS
norm on each of its three inner streams) or multi-query attention with NO
position term, and whose feed-forward is a dense SwiGLU; tied head — in
straightforward ``jax.numpy`` and float32 with matrix products at precision
``highest``. No cache, no carried state, no kernels, no batching: the
convolution is four shifted multiply-adds over the whole sequence, the
recurrence a plain ``lax.scan`` over the positions from ``h = 0``, attention a
full causal softmax. It imports nothing of the program and takes nothing the
program has made.

It follows ISSUE 33's equations line for line (source: the catalog row's
``config`` and the public ``model_type: jamba``). One layout is shared with
the program because the weights file makes it so: ``a_log`` and the state are
``[N, E]`` (the source's are ``[E, N]``; the numbers are the same).

Every function takes ONE sequence ``[S, D]``; the layer is a function of one
layer's weights. ``cast`` is the hook for the lower-precision control (both
operands of every matrix product; the recurrence has none and stays float32);
``fault`` plants one of ``FAULTS`` for the tests of the comparison.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import Cast, HIGHEST, identity, mm, rms_norm, rounded_to  # noqa: F401
from benchmark.reference.dense_decoder import attention as causal_attention, embed, rope  # noqa: F401

HEAD_BLOCK = 4
ROPE_THETA_OF_THE_FAULT = 1e4
# ssm_state_dropped: every position's recurrence starts from h = 0 (what a
# decode step that lost the slot's state computes); conv_tail_dropped: the
# convolution sees only its own position; inner_norms_left_out: step 3's three
# norms; gate_left_out: ``* silu(z)``; skip_left_out: ``+ D * c``;
# rope_applied: the attention layers rotate q and k (rotate-half, theta 1e4);
# state_bfloat16: ``h`` rounded to bfloat16 after every position
FAULTS = ("ssm_state_dropped", "conv_tail_dropped", "inner_norms_left_out", "gate_left_out",
          "skip_left_out", "rope_applied", "state_bfloat16")


def mamba(h: jax.Array, lp: dict, s: dict, cast: Cast = identity, fault: str = "") -> jax.Array:
    """Selective state-space mixer of one normed sequence h [S, D] -> [S, D]."""
    S, E, N, R, K = h.shape[0], s["e"], s["n"], s["r"], s["K"]
    f32 = jnp.float32
    uz = mm(h, lp["w_in"], cast)
    u, z = uz[:, :E], uz[:, E:]
    taps = lp["conv_w"].astype(f32)                                # [K, E]
    if fault == "conv_tail_dropped":
        taps = taps.at[:K - 1].set(0.0)
    uu = jnp.concatenate([jnp.zeros((K - 1, E), f32), u])          # u_{<0} = 0
    c = jax.nn.silu(sum(taps[j] * uu[j:j + S] for j in range(K)) + lp["conv_b"].astype(f32))
    dbc = mm(c, lp["w_x"], cast)
    d, b, cc = dbc[:, :R], dbc[:, R:R + N], dbc[:, R + N:]
    if fault != "inner_norms_left_out":
        d = rms_norm(d, lp["dt_norm"], s["eps"])
        b = rms_norm(b, lp["b_norm"], s["eps"])
        cc = rms_norm(cc, lp["c_norm"], s["eps"])
    delta = jax.nn.softplus(mm(d, lp["w_dt"], cast) + lp["b_dt"].astype(f32))   # [S, E]
    a = -jnp.exp(lp["a_log"].astype(f32))                          # [N, E]

    def position(state, row):
        d_t, c_t, b_t, c2_t = row
        if fault == "ssm_state_dropped":
            state = jnp.zeros_like(state)
        state = jnp.exp(d_t[None, :] * a) * state + (d_t * c_t)[None, :] * b_t[:, None]
        if fault == "state_bfloat16":
            # an explicit rounding: the compiler drops a convert pair as excess precision
            state = jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)
        return state, jnp.sum(state * c2_t[:, None], axis=0)

    _, y = jax.lax.scan(position, jnp.zeros((N, E), f32), (delta, c, b, cc))
    if fault != "skip_left_out":
        y = y + lp["d_skip"].astype(f32) * c
    if fault != "gate_left_out":
        y = y * jax.nn.silu(z)
    return mm(y, lp["w_out"], cast)


def attention(h: jax.Array, lp: dict, s: dict, cast: Cast = identity, fault: str = "") -> jax.Array:
    """Multi-query attention of one normed sequence h [S, D] -> [S, D]: no
    rotation, no other position term."""
    S, H, KV, hd = h.shape[0], s["h"], s["kv"], s["hd"]
    q = mm(h, lp["wq"], cast).reshape(S, H, hd)
    k = mm(h, lp["wk"], cast).reshape(S, KV, hd)
    v = mm(h, lp["wv"], cast).reshape(S, KV, hd)
    if fault == "rope_applied":
        q, k = rope(q, ROPE_THETA_OF_THE_FAULT), rope(k, ROPE_THETA_OF_THE_FAULT)
    rep = H // KV
    hb = next(n for n in range(min(HEAD_BLOCK, rep), 0, -1) if rep % n == 0)

    def heads(i):                                                  # hb heads of ONE K/V head
        qs = jax.lax.dynamic_slice_in_dim(q, i * hb, hb, axis=1)
        g = (i * hb) // rep
        ks = jax.lax.dynamic_slice_in_dim(k, g, 1, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v, g, 1, axis=1)
        return causal_attention(qs, ks, vs, cast)                  # [S, hb, hd]

    out = jax.lax.map(heads, jnp.arange(H // hb))                  # [H/hb, S, hb, hd]
    return mm(out.transpose(1, 0, 2, 3).reshape(S, H * hd), lp["wo"], cast)


def swiglu(h, w1, w3, w2, cast: Cast = identity):
    return mm(jax.nn.silu(mm(h, w1, cast)) * mm(h, w3, cast), w2, cast)


def layer(x: jax.Array, lp: dict, s: dict, cast: Cast = identity, fault: str = "") -> jax.Array:
    """One decoder layer on one sequence x [S, D]: a Mamba layer if ``lp``
    holds ``w_in``."""
    h = rms_norm(x, lp["op_norm"], s["eps"])
    mixer = mamba if "w_in" in lp else attention
    x = x + mixer(h, lp, s, cast, fault)
    h = rms_norm(x, lp["ffn_norm"], s["eps"])
    return x + swiglu(h, lp["w1"], lp["w3"], lp["w2"], cast)


def logits(x: jax.Array, final_norm: jax.Array, tok_emb: jax.Array, s: dict,
           cast: Cast = identity) -> jax.Array:
    """The tied head: the embedding, transposed."""
    return mm(rms_norm(x, final_norm, s["eps"]), tok_emb.astype(jnp.float32).T, cast)


def layers_of(params: dict, s: dict):
    """Each layer's weights in order, from the program's tree (stacked by
    kind: ``mamba_layers``, ``attn_layers``, ``dense_ffns``)."""
    seen = {"mamba_layers": 0, "attn_layers": 0}
    for l, kind in enumerate(s["layer_types"]):
        stack = "attn_layers" if kind == "attention" else "mamba_layers"
        lp = jax.tree.map(lambda a, i=seen[stack]: a[i], params[stack])
        lp.update(jax.tree.map(lambda a, l=l: a[l], params["dense_ffns"]))
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
