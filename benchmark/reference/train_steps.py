"""The plain reference's training steps: loss and gradients of
``dense_decoder`` taken one row and one layer at a time (so that published
widths fit one chip in float32), global-norm clipping, and AdamW with
decoupled weight decay written out by hand. Imports nothing of the program.

Parameters are held in the configuration's stated storage type and rounded
back to it after every update (the stored weights ARE numbers of that type);
all arithmetic in between is float32 at precision ``highest``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import dense_decoder as ref

LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "ffn_norm", "w1", "w3", "w2")


def unstack(params: dict) -> dict:
    """The stacked tree of benchmark/weights.py -> a list of layer dicts."""
    L = params["layers"]["wq"].shape[0]
    return {
        "tok_emb": params["tok_emb"], "final_norm": params["final_norm"],
        "lm_head": params["lm_head"],
        "layers": [{k: params["layers"][k][l] for k in LAYER_KEYS} for l in range(L)],
    }


class Fns:
    """The jitted pieces for one (sizes, cast)."""

    def __init__(self, s: dict, cast: ref.Cast = ref.identity):
        self.s = s
        self.layer_fwd = jax.jit(lambda lp, x: ref.layer(x, lp, s, cast))

        f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)

        def layer_vjp(lp, x, dy):
            # differentiate with respect to float32 copies: a cotangent takes
            # its primal's type, and the gradient must not be rounded to the
            # parameters' storage type
            _, pull = jax.vjp(lambda lp, x: ref.layer(x, lp, s, cast), f32(lp), x)
            return pull(dy)

        self.layer_vjp = jax.jit(layer_vjp)

        def head(final_norm, lm_head, x, targets, scale):
            def f(final_norm, lm_head, x):
                lg = ref.logits(x, final_norm, lm_head, s, cast)
                return jnp.sum(ref.token_losses(lg, targets)) * scale

            return jax.value_and_grad(f, argnums=(0, 1, 2))(f32(final_norm), f32(lm_head), x)

        self.head = jax.jit(head)
        self.head_loss = jax.jit(
            lambda fn, lm, x, t: jnp.sum(ref.token_losses(ref.logits(x, fn, lm, s, cast), t))
        )
        self.embed = jax.jit(ref.embed)
        self.embed_grad = jax.jit(
            lambda acc, tokens, dx: acc.at[tokens].add(dx), donate_argnums=0
        )
        self.acc = jax.jit(
            lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0
        )


def loss_and_grads(fns: Fns, p: dict, inputs: np.ndarray, targets: np.ndarray,
                   want_grads: bool = True):
    """Mean loss over all tokens of the rows given, and its gradient (float32,
    same tree as ``p``). Rows and layers are walked one at a time."""
    B, S = inputs.shape
    scale = 1.0 / (B * S)
    L = len(p["layers"])
    xs = []
    for b in range(B):
        x = [fns.embed(p["tok_emb"], jnp.asarray(inputs[b]))]
        for l in range(L):
            x.append(fns.layer_fwd(p["layers"][l], x[-1]))
        xs.append(x)
    if not want_grads:
        total = sum(
            fns.head_loss(p["final_norm"], p["lm_head"], xs[b][L], jnp.asarray(targets[b]))
            for b in range(B)
        )
        return float(total) * scale, None
    total, dxs, d_fn, d_lm = 0.0, [], None, None
    for b in range(B):
        val, (g_fn, g_lm, dx) = fns.head(
            p["final_norm"], p["lm_head"], xs[b][L], jnp.asarray(targets[b]), scale
        )
        total = total + val
        dxs.append(dx)
        d_fn, d_lm = (g_fn, g_lm) if d_fn is None else fns.acc((d_fn, d_lm), (g_fn, g_lm))
    g_layers = [None] * L
    for l in reversed(range(L)):
        acc = None
        for b in range(B):
            dlp, dxs[b] = fns.layer_vjp(p["layers"][l], xs[b][l], dxs[b])
            acc = dlp if acc is None else fns.acc(acc, dlp)
            xs[b][l + 1] = None
        g_layers[l] = acc
    d_emb = jnp.zeros(p["tok_emb"].shape, jnp.float32)
    for b in range(B):
        d_emb = fns.embed_grad(d_emb, jnp.asarray(inputs[b]), dxs[b])
    grads = {"tok_emb": d_emb, "final_norm": d_fn, "lm_head": d_lm, "layers": g_layers}
    return float(total), grads


def leaf_norms(tree: dict) -> dict[str, float]:
    """{"tok_emb": n, ..., "layers.3.wq": n}: the L2 norm of every leaf."""
    out = {}
    sq = jax.jit(lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))))
    for k in ("tok_emb", "final_norm", "lm_head"):
        out[k] = float(sq(tree[k]))
    for l, lp in enumerate(tree["layers"]):
        for k in LAYER_KEYS:
            out[f"layers.{l}.{k}"] = float(sq(lp[k]))
    return out


def lr_at(count: int, opt: dict) -> float:
    """Linear warm-up from 0 over ``warmup_steps``, then a cosine to 0 at
    ``decay_steps``; ``count`` is the number of updates already made."""
    w, d, peak = opt["warmup_steps"], opt["decay_steps"], opt["lr"]
    if count < w:
        return peak * count / w
    frac = min(max((count - w) / max(d - w, 1), 0.0), 1.0)
    return peak * 0.5 * (1.0 + math.cos(math.pi * frac))


def clip_scale(grad_norms: dict[str, float], opt: dict) -> float:
    total = math.sqrt(sum(n * n for n in grad_norms.values()))
    return 1.0 if total < opt["grad_clip"] else opt["grad_clip"] / total


def _map_leaves(fn, *trees):
    out = {k: fn(*(t[k] for t in trees)) for k in ("tok_emb", "final_norm", "lm_head")}
    out["layers"] = [
        {k: fn(*(t["layers"][l][k] for t in trees)) for k in LAYER_KEYS}
        for l in range(len(trees[0]["layers"]))
    ]
    return out


@partial(jax.jit, static_argnames=("t", "b1", "b2", "eps", "wd"), donate_argnums=(0,))
def _adamw_leaf(p, g_prev, g, c_prev, c, lr, *, t, b1, b2, eps, wd):
    """Update ``t`` (1 or 2) of one leaf. The moments after it follow from the
    clipped gradients seen so far, so they are never stored."""
    g = g * c
    mu, nu = (1 - b1) * g, (1 - b2) * g * g
    if t == 2:
        gp = g_prev * c_prev
        mu, nu = mu + b1 * (1 - b1) * gp, nu + b2 * (1 - b2) * gp * gp
    p32 = p.astype(jnp.float32)
    u = (mu / (1 - b1**t)) / (jnp.sqrt(nu / (1 - b2**t)) + eps) + wd * p32
    return (p32 - lr * u).astype(p.dtype)


def adamw_update(p: dict, g: dict, c: float, t: int, opt: dict,
                 g_prev: dict | None = None, c_prev: float = 1.0) -> dict:
    """Update number ``t`` (1-based; two are as far as the reference goes) of
    every leaf, one leaf at a time. ``p`` is consumed; ``g_prev`` may live on
    the host. Returns the new parameters in their storage type."""
    if t not in (1, 2) or (t == 2) != (g_prev is not None):
        raise ValueError("the reference follows two updates: t=1, then t=2 with g_prev")
    lr = jnp.float32(lr_at(t - 1, opt))
    hp = dict(t=t, b1=opt["b1"], b2=opt["b2"], eps=opt["eps"], wd=opt["weight_decay"])
    if g_prev is None:
        g_prev = _map_leaves(lambda a: jnp.zeros((), jnp.float32), g)
    return _map_leaves(
        lambda a, gp, gg: _adamw_leaf(a, jnp.asarray(gp), gg, jnp.float32(c_prev),
                                      jnp.float32(c), lr, **hp),
        p, g_prev, g,
    )


def follow(s: dict, opt: dict, dtype, make_layer, make_top, batches: np.ndarray,
           cast: ref.Cast = ref.identity, rows: slice | None = None) -> dict:
    """The first steps of training as the reference takes them: two updates
    and the loss of the step after, on ``batches`` [>=3, B, S+1] token ids.
    ``make_layer(l)`` / ``make_top(name)`` give the seeded starting weights in
    the storage type ``dtype`` (called again at the end for the change).
    ``rows`` keeps only those rows of each batch (a planted fault)."""
    rows = rows or slice(None)
    L = s["layers"]
    p = {n: make_top(n) for n in ("tok_emb", "final_norm", "lm_head")}
    p["layers"] = [make_layer(l) for l in range(L)]
    fns = Fns(s, cast)
    pair = lambda i: (batches[i][rows, :-1], batches[i][rows, 1:])
    losses = []
    l1, g1 = loss_and_grads(fns, p, *pair(0))
    raw1 = leaf_norms(g1)
    c1 = clip_scale(raw1, opt)
    p = adamw_update(p, g1, c1, 1, opt)
    g1 = jax.device_get(g1)  # to the host: two float32 gradients do not fit beside the rest
    l2, g2 = loss_and_grads(fns, p, *pair(1))
    raw2 = leaf_norms(g2)
    c2 = clip_scale(raw2, opt)
    p = adamw_update(p, g2, c2, 2, opt, g_prev=g1, c_prev=c1)
    del g1, g2
    l3, _ = loss_and_grads(fns, p, *pair(2), want_grads=False)
    start = {n: make_top(n) for n in ("tok_emb", "final_norm", "lm_head")}
    diff = jax.jit(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32))
    delta = {n: diff(p[n], start[n]) for n in start}
    delta["layers"] = []
    for l in range(L):
        lp0 = make_layer(l)
        delta["layers"].append({k: diff(p["layers"][l][k], lp0[k]) for k in LAYER_KEYS})
    return {
        "losses": [l1, l2, l3],
        "grad1_raw_leaf_norms": raw1,
        "grad_norms_global": [math.sqrt(sum(n * n for n in r.values())) for r in (raw1, raw2)],
        "grad1_leaf_norms": {k: v * c1 for k, v in raw1.items()},
        "delta_leaf_norms": leaf_norms(delta),
        "clip": [c1, c2],
    }
