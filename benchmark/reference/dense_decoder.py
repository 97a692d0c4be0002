"""Plain reference: a pre-norm decoder (RMSNorm, rotary positions on split
halves, grouped-query causal attention, SwiGLU, untied output head) and its
next-token loss, in straightforward ``jax.numpy`` and float32 with matrix
products at precision ``highest``. No kernels, no cache, no batching tricks.
It imports nothing of the program and takes nothing the program has made.

Two things keep it inside one chip's memory at published widths: every
function takes ONE sequence ``[S, D]`` (callers loop over rows), and the
layer is a function of one layer's weights (callers loop over layers, and
may make each layer's weights just before they use them).

``cast`` is the hook for the lower-precision control: it is applied to both
operands of every matrix product (identity here). A control passes a
function that rounds to the nearest lower precision; accumulation stays
float32, as a real low-precision matrix unit accumulates.
"""

from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp

Cast = Callable[[jax.Array], jax.Array]
HIGHEST = jax.lax.Precision.HIGHEST


def identity(x: jax.Array) -> jax.Array:
    return x


def rounded_to(dtype) -> Cast:
    """Operands rounded to ``dtype`` and brought back: the control's cast."""
    return lambda x: x.astype(dtype).astype(jnp.float32)


def mm(a: jax.Array, b: jax.Array, cast: Cast = identity) -> jax.Array:
    return jnp.matmul(cast(a.astype(jnp.float32)), cast(b.astype(jnp.float32)),
                      precision=HIGHEST)


def rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(jnp.float32)


def rope(x: jax.Array, theta: float) -> jax.Array:
    """x [S, H, hd]; position p rotates the pair (x[i], x[i + hd/2]) by
    p * theta**(-i / (hd/2))."""
    S, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, cast: Cast = identity) -> jax.Array:
    """Causal softmax attention. q [S, H, hd], k/v [S, KV, hd]; query head h
    reads key/value head h // (H / KV)."""
    S, H, hd = q.shape
    rep = H // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", cast(q), cast(k), precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", cast(probs), cast(v), precision=HIGHEST)


def layer(x: jax.Array, lp: dict, s: dict, cast: Cast = identity) -> jax.Array:
    """One decoder layer on one sequence x [S, D]."""
    S = x.shape[0]
    h = rms_norm(x, lp["attn_norm"], s["eps"])
    q = rope(mm(h, lp["wq"], cast).reshape(S, s["h"], s["hd"]), s["theta"])
    k = rope(mm(h, lp["wk"], cast).reshape(S, s["kv"], s["hd"]), s["theta"])
    v = mm(h, lp["wv"], cast).reshape(S, s["kv"], s["hd"])
    x = x + mm(attention(q, k, v, cast).reshape(S, s["h"] * s["hd"]), lp["wo"], cast)
    h = rms_norm(x, lp["ffn_norm"], s["eps"])
    gate = jax.nn.silu(mm(h, lp["w1"], cast)) * mm(h, lp["w3"], cast)
    return x + mm(gate, lp["w2"], cast)


def embed(tok_emb: jax.Array, tokens: jax.Array) -> jax.Array:
    return tok_emb[tokens].astype(jnp.float32)


def logits(x: jax.Array, final_norm: jax.Array, lm_head: jax.Array, s: dict,
           cast: Cast = identity) -> jax.Array:
    return mm(rms_norm(x, final_norm, s["eps"]), lm_head, cast)


def token_losses(lg: jax.Array, targets: jax.Array) -> jax.Array:
    """Cross-entropy of each position's target, [S]."""
    return jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]


def forward(params: dict, tokens: jax.Array, s: dict, cast: Cast = identity) -> jax.Array:
    """Whole model on one sequence of tokens [S] -> logits [S, V]. ``params``
    is the stacked tree of benchmark/weights.py (small sizes only: the
    drivers walk the layers themselves)."""
    x = embed(params["tok_emb"], tokens)
    for l in range(s["layers"]):
        x = layer(x, jax.tree.map(lambda a: a[l], params["layers"]), s, cast)
    return logits(x, params["final_norm"], params["lm_head"], s, cast)


def loss(params: dict, inputs: jax.Array, targets: jax.Array, s: dict,
         cast: Cast = identity) -> jax.Array:
    """Mean next-token loss over rows inputs/targets [B, S]."""
    rows = [token_losses(forward(params, i, s, cast), t) for i, t in zip(inputs, targets)]
    return jnp.mean(jnp.stack(rows))
