"""Short-convolution / attention hybrid decoder with sigmoid-routed experts:
the third architecture beside llama.py and latent_moe.py.

A pre-norm decoder whose OPERATOR is chosen per layer from a declared list
(``layer_types``): a gated short convolution (``[B | C | u] = n @ w_in``,
``z = B * u``, a causal depthwise convolution of ``conv_kernel`` taps over
``z``, ``(C * c) @ w_out``) or grouped-query attention with an RMSNorm on
every query and key head before rope. Its FEED-FORWARD is a dense SwiGLU in
the leading ``n_dense_layers`` and after them a sigmoid-scored top-k choice
among routed experts with a selection-only bias and no shared expert
(parallel/moe.py ``route_group_limited`` with one group). The output head is
the embedding (tied). ISSUE 31 spells the equations out as published for the
8B-total / 1B-active open model of this family.

What is here: the configuration (every size a field of its own), seeded
init, logical axes, ONE layer body and the loop over the declared list. The
body does not know where an operator keeps its state: ``keep`` is handed in.
For a convolution layer it is ``keep(z, taps) -> (c, state)`` — the sequence
form (:func:`conv_sequence`: the rows before the first come from the
predecessor's state, the new state is the rows at the true last positions)
or the one-token form (:func:`conv_token`); for an attention layer it is
``keep(q, k, v) -> (o, state)``. ``forward`` and the serving steps
(serve/shortconv.py) hand in their own.

Weights are stacked BY KIND (``conv_layers``, ``attn_layers``,
``dense_ffns``, ``moe_ffns``) and models/layer_walk.py's ``walk_layers``
(shared with models/ssm_hybrid.py) follows the list: runs
of equal layers are scanned with the stacks indexed where they lie, single
layers are called with a static index. The published list is not periodic,
so nothing here assumes a period. Expert layers hold ``n_local_experts`` of
the router's ``n_experts`` from ``first_expert`` (with the defaults, all).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.models.latent_moe import CACHE_LANES, EXPERT_LEAVES, rotate, swiglu
from tony_tpu.models.layer_walk import (
    ATTENTION, CONV, Run, layer_of, put, runs_of, walk_layers,
)
from tony_tpu.models.llama import rms_norm, rope_freqs

Params = dict[str, Any]

# the published list: attention at 2, 6, 10, 14, 18, 21
PUBLISHED_LAYER_TYPES = tuple(
    ATTENTION if i in (2, 6, 10, 14, 18, 21) else CONV for i in range(24))


@dataclass(frozen=True)
class ShortConvMoEConfig:
    vocab_size: int = 65536
    dim: int = 2048
    layer_types: tuple[str, ...] = PUBLISHED_LAYER_TYPES
    n_dense_layers: int = 2          # leading layers with a dense SwiGLU
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    conv_kernel: int = 3             # taps of the short convolution (conv_L_cache)
    ffn_dim: int = 7168              # dense layers' SwiGLU width
    moe_ffn_dim: int = 1792          # every expert's width
    n_experts: int = 32              # the router's outputs (published)
    top_k: int = 4
    routed_scale: float = 1.0
    norm_topk_prob: bool = True
    # the experts held HERE: [first_expert, first_expert + n_local_experts);
    # 0 = all of them
    first_expert: int = 0
    n_local_experts: int = 0
    max_seq_len: int = 4096
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # row tile of the grouped expert products (parallel/moe.py local_expert_ffn)
    moe_group_block: int = 128

    def __post_init__(self):
        unknown = set(self.layer_types) - {CONV, ATTENTION}
        if unknown:
            raise ValueError(f"layer_types names {sorted(unknown)}; known: "
                             f"{CONV!r}, {ATTENTION!r}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_conv_layers(self) -> int:
        return self.layer_types.count(CONV)

    @property
    def n_attn_layers(self) -> int:
        return self.layer_types.count(ATTENTION)

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def n_local(self) -> int:
        return self.n_local_experts or self.n_experts

    @property
    def kv_pack(self) -> int:
        """K/V heads that share one cache row: as many neighbours as fill the
        chip's 128 lanes (2 at the published 64-wide heads). A pool whose
        minor dimension is under the lanes is relaid by the chip's compiler
        with its BLOCK dimension minor-most, in and out of every decode step
        (four pool-sized copies a step at these widths; seen in the chip
        compiler's output, PR 31 — models/latent_moe.py ``CACHE_LANES`` is
        the same finding)."""
        pack = max(CACHE_LANES // self.head_dim, 1)
        while self.n_kv_heads % pack:
            pack -= 1
        return pack

    @property
    def cache_layout(self) -> tuple[int, int, int]:
        """``(heads, width, pools)`` of what an ATTENTION layer caches per
        token (serve/cache.py): K and V rows of ``n_kv_heads x head_dim``
        values, ``kv_pack`` neighbouring heads side by side in one row."""
        return self.n_kv_heads // self.kv_pack, self.head_dim * self.kv_pack, 2

    @property
    def cache_layers(self) -> int:
        """Layers the block pool holds: the attention layers only."""
        return self.n_attn_layers

    @property
    def state_width(self) -> int:
        """Values a convolution layer keeps per slot: the last
        ``conv_kernel - 1`` rows of ``B * u``, oldest first, side by side."""
        return (self.conv_kernel - 1) * self.dim

    @property
    def slot_state(self) -> tuple[int, tuple[int, ...], Any]:
        """``(layers, shape, dtype)`` of the fixed-size state a slot keeps
        beside its blocks (docs/SERVE.md "What a new family must provide"):
        one row of :attr:`state_width` a convolution layer."""
        return self.n_conv_layers, (self.state_width,), self.dtype

    @property
    def runs(self) -> tuple[Run, ...]:
        """The declared list as runs of equal layers, in order."""
        return runs_of(self.layer_types, self.n_dense_layers)

    @property
    def n_params(self) -> int:
        """Parameters held HERE (the tied head once), from :func:`leaf_shapes`."""
        count = {"top": 1, "conv_layers": self.n_conv_layers,
                 "attn_layers": self.n_attn_layers, "dense_ffns": self.n_dense_layers,
                 "moe_ffns": self.n_moe_layers}
        return sum(count[stack] * math.prod(shape)
                   for stack, leaves in leaf_shapes(self).items()
                   for shape, _ in leaves.values())

    @classmethod
    def tiny(cls, **kw: Any) -> "ShortConvMoEConfig":
        """Test-size config (CPU-fast): 7 layers whose list is not periodic
        (attention at 2 and 5), 2 leading dense layers, 8 experts, top-2."""
        base = dict(
            vocab_size=256, dim=64,
            layer_types=(CONV, CONV, ATTENTION, CONV, CONV, ATTENTION, CONV),
            n_dense_layers=2, n_heads=4, n_kv_heads=2, head_dim=16, ffn_dim=128,
            moe_ffn_dim=32, n_experts=8, top_k=2, max_seq_len=128,
            dtype=jnp.float32, moe_group_block=8,
        )
        base.update(kw)
        return cls(**base)


def rope_cos_sin(cfg: ShortConvMoEConfig, pos: jax.Array) -> tuple[jax.Array, jax.Array]:
    """cos/sin ``[*pos.shape, head_dim/2]`` float32 at absolute positions
    ``pos`` (the one frequency formula of ``models/llama.rope_freqs``)."""
    ang = pos.astype(jnp.float32)[..., None] * rope_freqs(cfg)
    return jnp.cos(ang), jnp.sin(ang)


# --- parameter tree -------------------------------------------------------------


def leaf_shapes(cfg: ShortConvMoEConfig) -> dict[str, dict[str, tuple[tuple[int, ...], int]]]:
    """``{stack: {leaf: (shape of ONE layer's leaf, fan-in; 0 = ones, -1 =
    the router's bias)}}`` — the one table of this family's tensors. The
    taps are ``[conv_kernel, D]``: tap ``j`` multiplies the row ``conv_kernel
    - 1 - j`` positions back."""
    d, hd = cfg.dim, cfg.head_dim
    nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    F, Fm, E, n, K = cfg.ffn_dim, cfg.moe_ffn_dim, cfg.n_experts, cfg.n_local, cfg.conv_kernel
    return {
        "top": {"tok_emb": ((cfg.vocab_size, d), d), "final_norm": ((d,), 0)},
        "conv_layers": {"op_norm": ((d,), 0), "w_in": ((d, 3 * d), d),
                        "taps": ((K, d), K), "w_out": ((d, d), d)},
        "attn_layers": {"op_norm": ((d,), 0), "wq": ((d, nq), d), "wk": ((d, nkv), d),
                        "wv": ((d, nkv), d), "q_norm": ((hd,), 0), "k_norm": ((hd,), 0),
                        "wo": ((nq, d), nq)},
        "dense_ffns": {"ffn_norm": ((d,), 0), "w1": ((d, F), d), "w3": ((d, F), d),
                       "w2": ((F, d), F)},
        "moe_ffns": {"ffn_norm": ((d,), 0), "router": ((d, E), d), "router_bias": ((E,), -1),
                     "w1": ((n, d, Fm), d), "w3": ((n, d, Fm), d), "w2": ((n, Fm, d), Fm)},
    }


_AXES = {
    "conv_layers": {"op_norm": ("norm",), "w_in": ("embed", None), "taps": (None, "norm"),
                    "w_out": (None, "embed")},
    "attn_layers": {"op_norm": ("norm",), "wq": ("embed", "heads"), "wk": ("embed", "heads"),
                    "wv": ("embed", "heads"), "q_norm": (None,), "k_norm": (None,),
                    "wo": ("heads", "embed")},
    "dense_ffns": {"ffn_norm": ("norm",), "w1": ("embed", "ffn"), "w3": ("embed", "ffn"),
                   "w2": ("ffn", "embed")},
    "moe_ffns": {"ffn_norm": ("norm",), "router": ("embed", None), "router_bias": (None,),
                 "w1": ("expert", "embed", "ffn"), "w3": ("expert", "embed", "ffn"),
                 "w2": ("expert", "ffn", "embed")},
}


def logical_axes(cfg: ShortConvMoEConfig) -> Params:
    """Pytree (matching init_params) of logical axis-name tuples."""
    del cfg
    out: Params = {"tok_emb": ("vocab", "embed"), "final_norm": ("norm",)}
    for stack, axes in _AXES.items():
        out[stack] = {k: ("layers", *v) for k, v in axes.items()}
    return out


# router statistics stay float32 whatever the activations' dtype
_F32_LEAVES = ("router", "router_bias")


def init_params(rng: jax.Array, cfg: ShortConvMoEConfig) -> Params:
    """Seeded init: normal(0, 1/sqrt(fan_in)) matrices and taps, ones for
    norm gains, normal(0, 0.02) for the router's selection bias (a trained
    one is not zero, and zero would hide a program that gates with the
    biased score)."""
    counts = {"conv_layers": cfg.n_conv_layers, "attn_layers": cfg.n_attn_layers,
              "dense_ffns": cfg.n_dense_layers, "moe_ffns": cfg.n_moe_layers}
    out: Params = {}
    for si, (stack, leaves) in enumerate(leaf_shapes(cfg).items()):
        tree = {}
        for li, (name, (shape, fan_in)) in enumerate(leaves.items()):
            full = shape if stack == "top" else (counts[stack], *shape)
            dtype = jnp.float32 if name in _F32_LEAVES else cfg.dtype
            if fan_in == 0:
                tree[name] = jnp.ones(full, dtype)
                continue
            k = jax.random.fold_in(jax.random.fold_in(rng, si), li)
            scale = 0.02 if fan_in < 0 else 1.0 / math.sqrt(fan_in)
            tree[name] = (jax.random.normal(k, full, jnp.float32) * scale).astype(dtype)
        if stack == "top":
            out.update(tree)
        else:
            out[stack] = tree
    return out


# --- the operators' state -------------------------------------------------------


def conv_sequence(z: jax.Array, taps: jax.Array, prev: jax.Array,
                  last_index: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The convolution over a sequence ``z [B, S, D]`` whose predecessor left
    ``prev [B, (K - 1) * D]`` (zeros for a fresh sequence): ``c_t = sum_j
    taps[j] * z_{t - (K - 1) + j}`` with the rows before the first taken from
    ``prev``. Returns ``(c [B, S, D], state [B, (K - 1) * D])``, the state the
    rows at the TRUE last positions ``last_index - (K - 2) .. last_index`` —
    not at the padded bucket's end."""
    B, S, D = z.shape
    K = taps.shape[0]
    zz = jnp.concatenate([prev.reshape(B, K - 1, D).astype(z.dtype), z], axis=1)
    zf, tf = zz.astype(jnp.float32), taps.astype(jnp.float32)
    c = sum(tf[j] * lax.slice_in_dim(zf, j, j + S, axis=1) for j in range(K))
    state = lax.dynamic_slice_in_dim(zz, last_index + 1, K - 1, axis=1)
    return c.astype(z.dtype), state.reshape(B, (K - 1) * D)


def conv_token(z: jax.Array, taps: jax.Array, prev: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One position a row: ``z [S, D]`` after the rows ``prev [S, (K - 1) *
    D]`` (oldest first). Returns ``(c [S, D], state)``: the state drops its
    oldest row and takes ``z``."""
    S, D = z.shape
    K = taps.shape[0]
    zz = jnp.concatenate([prev.astype(z.dtype), z], axis=-1)       # [S, K * D]
    zf, tf = zz.astype(jnp.float32), taps.astype(jnp.float32)
    c = sum(tf[j] * zf[:, j * D:(j + 1) * D] for j in range(K))
    return c.astype(z.dtype), zz[:, D:]


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, q_pos: jax.Array) -> jax.Array:
    """Queries ``[B, S, H, hd]`` at absolute positions ``q_pos [S]`` over a
    context ``k``/``v [B, C, Hkv, hd]`` (position c at index c), read at its
    native ``Hkv`` width; float32 scores and softmax. Returns ``[B, S, H, hd]``."""
    B, S, H, hd = q.shape
    C, Hkv = k.shape[1], k.shape[2]
    qg = q.reshape(B, S, Hkv, H // Hkv, hd)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    mask = q_pos[:, None] >= jnp.arange(C)[None, :]
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1).astype(q.dtype)
    return jnp.einsum("bgrqk,bkgd->bqgrd", p, v).reshape(B, S, H, hd)


# --- the layer ------------------------------------------------------------------

# for a convolution layer (z [..., D], taps [K, D]) -> (c [..., D], state);
# for an attention layer (q [..., H, hd], k [..., Hkv, hd], v) -> (o [..., H, hd], state)
Keep = Callable[..., tuple[jax.Array, Any]]


def short_conv(h: jax.Array, op: Params, keep: Keep):
    """The gated short convolution on normed ``h [..., D]``."""
    # the product stays as stated (models/generate.layer says why): fused with
    # the three-way split the compiler may relay w_in out of its stack
    bcu = lax.optimization_barrier(h @ op["w_in"])
    b, c, u = jnp.split(bcu, 3, axis=-1)
    conv, state = keep(b * u, op["taps"])
    return (c * conv) @ op["w_out"], state


def attention(h: jax.Array, op: Params, cfg: ShortConvMoEConfig, keep: Keep,
              cos: jax.Array, sin: jax.Array):
    """Grouped-query attention on normed ``h [..., D]``: every query and key
    head normed (gains of ``head_dim``), then rotated at its position."""
    lead, hd = h.shape[:-1], cfg.head_dim
    q, k = lax.optimization_barrier((h @ op["wq"], h @ op["wk"]))
    q = rms_norm(q.reshape(*lead, cfg.n_heads, hd), op["q_norm"], cfg.norm_eps)
    k = rms_norm(k.reshape(*lead, cfg.n_kv_heads, hd), op["k_norm"], cfg.norm_eps)
    q = rotate(q, cos[..., None, :], sin[..., None, :])
    k = rotate(k, cos[..., None, :], sin[..., None, :])
    v = (h @ op["wv"]).reshape(*lead, cfg.n_kv_heads, hd)
    o, state = keep(q, k, v)
    return o.reshape(*lead, cfg.n_heads * hd) @ op["wo"], state


def expert_ffn(h: jax.Array, ff: Params, cfg: ShortConvMoEConfig,
               valid: jax.Array | None = None, experts=None):
    """The routed experts held here on ``h [..., D]``; ``valid [...]`` leaves
    padding rows out of the routing (and its counts). ``experts = (stacked
    weights, layer index)`` where ``ff`` does not hold this layer's own.
    Returns ``(y, routes [n_local] int32)``."""
    from tony_tpu.parallel.moe import GroupRouting, local_expert_ffn, route_group_limited

    flat = h.reshape(-1, h.shape[-1])
    routing = GroupRouting(n_experts=cfg.n_experts, top_k=cfg.top_k,
                           routed_scale=cfg.routed_scale,
                           norm_topk_prob=cfg.norm_topk_prob)
    sel, gates = route_group_limited(flat, ff["router"], ff["router_bias"], routing)
    if valid is not None:
        sel = jnp.where(valid.reshape(-1, 1), sel, -1)
    stacked, index = experts or ({k: ff[k] for k in EXPERT_LEAVES}, None)
    y, routes = local_expert_ffn(
        stacked, flat, sel, gates, layer=index,
        first_expert=cfg.first_expert, group_block=cfg.moe_group_block,
    )
    return y.reshape(h.shape), routes


def layer(x: jax.Array, op: Params, ff: Params, cfg: ShortConvMoEConfig, keep: Keep,
          cos: jax.Array, sin: jax.Array, valid: jax.Array | None = None,
          experts=None):
    """One decoder layer: the operator by what ``op`` holds (a convolution
    where it has ``w_in``), the feed-forward by what ``ff`` holds (experts
    where it has a ``router``). ``keep`` decides where the operator's state
    lives (module docstring). Returns ``(x', keep's state, routes [n_local]
    int32 or None)``."""
    h = rms_norm(x, op["op_norm"], cfg.norm_eps)
    if "w_in" in op:
        o, state = short_conv(h, op, keep)
    else:
        o, state = attention(h, op, cfg, keep, cos, sin)
    x = x + o
    h2 = rms_norm(x, ff["ffn_norm"], cfg.norm_eps)
    if "router" in ff:
        delta, routes = expert_ffn(h2, ff, cfg, valid, experts)
    else:
        delta, routes = swiglu(h2, ff["w1"], ff["w3"], ff["w2"]), None
    return x + delta, state, routes


def forward_states(params: Params, tokens: jax.Array, ctx_k: jax.Array | None,
                   ctx_v: jax.Array | None, conv_state: jax.Array | None,
                   start: jax.Array, last_index: jax.Array, cfg: ShortConvMoEConfig,
                   valid: jax.Array | None = None):
    """tokens ``[B, S]`` at absolute positions ``start + i``, after a
    predecessor that left the attention layers' context ``ctx_k``/``ctx_v
    [La, B, C, Hkv, hd]`` (positions below ``start`` valid; None = none, C =
    S) and the convolution layers' state ``conv_state [Lc, B, state_width]``
    (None = zeros). Returns ``(hidden [B, S, D] before the final norm, K, V
    with the new rows written at ``start``, the convolution state at
    ``last_index``, routes [n_moe_layers, n_local])``."""
    B, S = tokens.shape
    # static choices (an argument that is None), not traced values
    if ctx_k is None:  # graft-lint: disable=GL002
        shape = (cfg.n_attn_layers, B, S, cfg.n_kv_heads, cfg.head_dim)
        ctx_k, ctx_v = jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)
    if conv_state is None:  # graft-lint: disable=GL002
        conv_state = jnp.zeros((cfg.n_conv_layers, B, cfg.state_width), cfg.dtype)
    x = params["tok_emb"][tokens]
    q_pos = start + jnp.arange(S)
    cos, sin = rope_cos_sin(cfg, q_pos)
    routes0 = jnp.zeros((cfg.n_moe_layers, cfg.n_local), jnp.int32)

    def step(carry, op, ff, oi, fi, experts):
        x, ks, vs, conv, routes = carry
        if "w_in" in op:
            def keep(z, taps):
                return conv_sequence(z, taps, layer_of(conv, oi), last_index)
        else:
            def keep(q, k, v):
                k_all = lax.dynamic_update_slice(layer_of(ks, oi), k, (0, start, 0, 0))
                v_all = lax.dynamic_update_slice(layer_of(vs, oi), v, (0, start, 0, 0))
                return causal_attention(q, k_all, v_all, q_pos), (k_all, v_all)
        x, state, r = layer(x, op, ff, cfg, keep, cos, sin, valid, experts)
        if "w_in" in op:
            conv = put(conv, state, oi)
        else:
            ks, vs = put(ks, state[0], oi), put(vs, state[1], oi)
        if r is not None:
            routes = put(routes, r, fi)
        return x, ks, vs, conv, routes

    return walk_layers(step, (x, ctx_k, ctx_v, conv_state, routes0), params, cfg)


def head(params: Params, x: jax.Array, cfg: ShortConvMoEConfig) -> jax.Array:
    """Final norm and the tied head: ``x [..., D]`` -> float32 logits ``[..., V]``."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return jnp.einsum("...d,vd->...v", x, params["tok_emb"],
                      preferred_element_type=jnp.float32)


def forward(params: Params, tokens: jax.Array, cfg: ShortConvMoEConfig) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, vocab] float32 (full sequence)."""
    x = forward_states(params, tokens, None, None, None, jnp.int32(0),
                       jnp.int32(tokens.shape[1] - 1), cfg)[0]
    return head(params, x, cfg)


__all__ = [
    "ATTENTION", "CONV", "PUBLISHED_LAYER_TYPES", "Run", "ShortConvMoEConfig",
    "attention", "causal_attention", "conv_sequence", "conv_token", "expert_ffn",
    "forward", "forward_states", "head", "init_params", "layer", "layer_of",
    "leaf_shapes", "logical_axes", "put", "rope_cos_sin", "short_conv", "walk_layers",
]
