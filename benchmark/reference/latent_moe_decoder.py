"""Plain reference: a pre-norm decoder with latent attention (queries and
keys/values through low-rank projections with their norms, a rope key shared
by all heads, YaRN frequencies) and, after the leading dense layers, sigmoid
group-limited experts plus a shared expert — in straightforward ``jax.numpy``
and float32 with matrix products at precision ``highest``. EXPANDED attention
only: no absorption, no cache, no kernels. It imports nothing of the program
and takes nothing the program has made.

It follows ISSUE 27 section 1 (the published equations). Departures, each also
in the configuration file: rope in rotate-half layout (seeded weights: the
source's interleaved layout is the same model up to a fixed permutation of the
rope columns); the multi-token-prediction head is not there; the expert layer
is ONE holder's share — the router scores all ``e`` experts and normalises the
gates over all ``k`` chosen ones, and the terms of experts outside
``[first, first + n_local)`` are left out, in the program and here alike.

Every function takes ONE sequence ``[S, D]``; the layer is a function of one
layer's weights. Scores are made for ``HEAD_BLOCK`` heads at a time so that a
4096-token sequence fits beside an expert layer. ``cast`` is the hook for the
lower-precision control (applied to both operands of every matrix product);
``fault`` plants one of ``FAULTS`` for the tests of the comparison itself.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import Cast, HIGHEST, identity, mm, rms_norm, rounded_to  # noqa: F401
from benchmark.reference.dense_decoder import embed, logits  # noqa: F401  (same head)

HEAD_BLOCK = 8
# experts_shifted: this holder's expert j answers with the weights of expert
# j + 1 (the last with the first's): the fault of a wrong row of the stacked
# experts, which only the tokens routed HERE can show
FAULTS = ("no_shared_expert", "gates_unnormalised", "no_rope_key", "experts_shifted")


def yarn_freqs(s: dict) -> jax.Array:
    """[rope/2] float32: f_j = theta**(-2j/rope); (f_j/factor) r_j + f_j (1 - r_j)."""
    y, rope = s["yarn"], s["rope"]
    half = rope // 2

    def corr(beta):
        return rope * math.log(y["orig"] / (2 * math.pi * beta)) / (2 * math.log(s["theta"]))

    low, high = max(math.floor(corr(y["beta_fast"])), 0), min(math.ceil(corr(y["beta_slow"])), rope - 1)
    j = jnp.arange(half, dtype=jnp.float32)
    f = s["theta"] ** (-j / half)
    r = jnp.clip((j - low) / (high - low), 0.0, 1.0)
    return f / y["factor"] * r + f * (1.0 - r)


def mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(s: dict) -> float:
    m = mscale(s["yarn"]["factor"], s["yarn"]["mscale_all_dim"])
    return (s["nope"] + s["rope"]) ** -0.5 * m * m


def rope(x: jax.Array, s: dict) -> jax.Array:
    """x [S, ..., rope]: position p rotates the pair (x[i], x[i + rope/2])."""
    S, half = x.shape[0], s["rope"] // 2
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * yarn_freqs(s)[None, :]
    m = mscale(s["yarn"]["factor"], s["yarn"]["mscale"]) / mscale(
        s["yarn"]["factor"], s["yarn"]["mscale_all_dim"])
    shape = (S,) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = (jnp.cos(ang) * m).reshape(shape), (jnp.sin(ang) * m).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(h: jax.Array, lp: dict, s: dict, cast: Cast = identity, fault: str = "") -> jax.Array:
    """Latent attention of one normed sequence h [S, D] -> [S, H * v]."""
    S, H, nope, rp, vd, kr = h.shape[0], s["h"], s["nope"], s["rope"], s["vd"], s["kr"]
    cq = rms_norm(mm(h, lp["wq_a"], cast), lp["q_norm"], s["eps"])
    q = mm(cq, lp["wq_b"], cast).reshape(S, H, nope + rp)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], s)
    kv = mm(h, lp["wkv_a"], cast)
    c_kv = rms_norm(kv[:, :kr], lp["kv_norm"], s["eps"])
    k_rope = rope(kv[:, kr:], s)                                   # [S, rope], all heads
    kvb = mm(c_kv, lp["wkv_b"], cast).reshape(S, H, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    causal = jnp.tril(jnp.ones((S, S), bool))
    scale = softmax_scale(s)
    hb = min(HEAD_BLOCK, H)

    def heads(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * hb, hb, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", cast(sl(q_nope)), cast(sl(k_nope)), precision=HIGHEST)
        if fault != "no_rope_key":
            sc = sc + jnp.einsum("qhd,kd->hqk", cast(sl(q_rope)), cast(k_rope), precision=HIGHEST)
        p = jax.nn.softmax(jnp.where(causal[None], sc * scale, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", cast(p), cast(sl(v)), precision=HIGHEST)

    out = jax.lax.map(heads, jnp.arange(H // hb))                  # [H/hb, S, hb, v]
    return out.transpose(1, 0, 2, 3).reshape(S, H * vd)


def swiglu(h, w1, w3, w2, cast: Cast = identity):
    return mm(jax.nn.silu(mm(h, w1, cast)) * mm(h, w3, cast), w2, cast)


def route(h: jax.Array, lp: dict, s: dict, fault: str = "") -> tuple[jax.Array, jax.Array]:
    """h [S, D] -> gates [S, e] float32 (0 where not chosen), chosen [S, e] bool.
    Scores are always float32 at ``highest`` (the control does not round them:
    the configuration states float32 router scores)."""
    E, G = s["e"], s["groups"]
    sc = jax.nn.sigmoid(mm(h, lp["router"]))                       # [S, e]
    biased = sc + lp["router_bias"].astype(jnp.float32)
    per_group = biased.reshape(-1, G, E // G)
    group_score = jnp.sum(jax.lax.top_k(per_group, 2)[0], axis=-1)  # [S, G]
    _, best = jax.lax.top_k(group_score, s["topk_groups"])
    group_mask = jnp.zeros_like(group_score, bool).at[jnp.arange(h.shape[0])[:, None], best].set(True)
    allowed = jnp.repeat(group_mask, E // G, axis=1)                # [S, e]
    _, idx = jax.lax.top_k(jnp.where(allowed, biased, -jnp.inf), s["k"])
    chosen = jnp.zeros_like(allowed).at[jnp.arange(h.shape[0])[:, None], idx].set(True)
    gates = jnp.where(chosen, sc, 0.0)
    if s["norm_topk"] and fault != "gates_unnormalised":
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates * s["scale"], chosen


def experts(h: jax.Array, lp: dict, s: dict, cast: Cast = identity, fault: str = "",
            with_routes: bool = False):
    """This holder's part of the expert layer, and the shared expert (with
    ``with_routes`` also which experts each token chose, [S, e] bool)."""
    gates, chosen = route(h, lp, s, fault)
    y = jnp.zeros_like(h)
    for j in range(s["n_local"]):                                   # a plain loop over experts
        g = gates[:, s["first"] + j]
        w = (j + 1) % s["n_local"] if fault == "experts_shifted" else j
        y = y + g[:, None] * swiglu(h, lp["w1"][w], lp["w3"][w], lp["w2"][w], cast)
    if fault != "no_shared_expert":
        y = y + swiglu(h, lp["ws1"], lp["ws3"], lp["ws2"], cast)
    return (y, chosen) if with_routes else y


def layer(x: jax.Array, lp: dict, s: dict, cast: Cast = identity, fault: str = "",
          with_routes: bool = False):
    """One decoder layer on one sequence x [S, D]; an expert layer if ``lp``
    holds a router (``with_routes``: also its tokens' choices, None for a
    dense layer)."""
    h = rms_norm(x, lp["attn_norm"], s["eps"])
    x = x + mm(attention(h, lp, s, cast, fault), lp["wo"], cast)
    h = rms_norm(x, lp["ffn_norm"], s["eps"])
    if "router" in lp:
        y, chosen = experts(h, lp, s, cast, fault, with_routes=True)
    else:
        y, chosen = swiglu(h, lp["w1"], lp["w3"], lp["w2"], cast), None
    return (x + y, chosen) if with_routes else x + y


def forward(params: dict, tokens: jax.Array, s: dict, cast: Cast = identity,
            fault: str = "") -> jax.Array:
    """Whole model on one sequence of tokens [S] -> logits [S, V]. ``params``
    is the stacked tree of benchmark/weights_latent_moe.py (small sizes only:
    the driver walks the layers itself)."""
    x = embed(params["tok_emb"], tokens)
    for stack in ("dense_layers", "moe_layers"):
        n = jax.tree.leaves(params[stack])[0].shape[0]
        for l in range(n):
            x = layer(x, jax.tree.map(lambda a: a[l], params[stack]), s, cast, fault)
    return logits(x, params["final_norm"], params["lm_head"], s, cast)
