"""Latent-attention expert decoder: the second architecture beside llama.py.

A pre-norm decoder whose attention keeps ONE compressed row per token and
layer (the latent ``c_kv`` plus a rope key shared by every head) and whose
feed-forward is, after a few leading dense layers, a sigmoid-scored,
group-limited top-k choice among routed experts plus a shared expert
(multi-head latent attention and auxiliary-loss-free routing as published
for the 671B open model; ISSUE 27 spells the equations out).

What is here: the configuration (every size a field of its own — no head
size is ``dim / n_heads``), seeded init, logical axes, the YaRN frequency
table, and ONE layer body. The body does not know where keys and values
live: ``attend(q_nope, q_rope, latent, lp)`` is handed in, and the two
forms of it are

- :func:`expanded_attention` — the prompt's (or the whole sequence's)
  latents are expanded through ``wkv_b`` into per-head keys and values and
  attended blockwise over keys with a running softmax, so no
  ``[H, S, context]`` score tensor exists; ``forward`` and the serving
  prefill (serve/latent.py) both use it, the prefill with cached latents
  in front;
- the absorbed one-token decode through the block table
  (serve/latent.py, ``ops/decode_attention.py``): ``wkv_b``'s key half is
  folded into the query and its value half applied after the softmax, so
  each cached row is read once at width ``kv_lora_rank + rope``.

Leading dense layers and expert layers are two stacks (``dense_layers``,
``moe_layers``), each scanned. Expert layers hold ``n_local_experts`` of the
router's ``n_experts`` outputs, starting at ``first_expert`` (one chip's
share of an expert-parallel replica; with the defaults, all of them): the
router keeps its published width and the layer computes its own experts'
part (parallel/moe.py). The multi-token-prediction head of the published
model is a training head and is not here.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.models.llama import rms_norm

Params = dict[str, Any]
# (q_nope [..,H,nope], q_rope [..,H,rope], latent [..,kv_rank+rope], lp)
#   -> (attention output [.., H*v_head_dim], what the caller keeps as state)
Attend = Callable[[jax.Array, jax.Array, jax.Array, Params], tuple[jax.Array, Any]]

# A cache row is the latent padded with zeros to a multiple of the chip's 128
# lanes. A 576-wide minor dimension is padded to 640 in its memory anyway,
# unless the compiler moves the pool's BLOCK dimension minor-most instead —
# which it does, and then relays the whole pool out twice a decode step
# (1.4 GB each way at the published widths; seen in the chip compiler's
# output, PR 27; tests/test_tpu_compile.py pins both layouts).
CACHE_LANES = 128
# keys per block of the expanded attention's running softmax
ATTN_KEY_BLOCK = 256


@dataclass(frozen=True)
class LatentMoEConfig:
    vocab_size: int = 129280
    dim: int = 7168
    n_layers: int = 61
    n_dense_layers: int = 3          # leading layers with a dense SwiGLU
    n_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    ffn_dim: int = 18432             # dense layers' SwiGLU width
    moe_ffn_dim: int = 2048          # every expert's width
    n_experts: int = 256             # the router's outputs (published)
    n_shared_experts: int = 1        # one SwiGLU of n_shared * moe_ffn_dim
    top_k: int = 8
    n_groups: int = 8
    topk_groups: int = 4
    routed_scale: float = 2.5
    norm_topk_prob: bool = True
    # the experts held HERE: [first_expert, first_expert + n_local_experts);
    # 0 = all of them
    first_expert: int = 0
    n_local_experts: int = 0
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    # YaRN (factor 1 = plain rope)
    rope_factor: float = 40.0
    rope_orig_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # row tile of the grouped expert products; up to one tile of tokens every
    # local expert runs ONE tile holding all of them (parallel/moe.py
    # local_expert_ffn)
    moe_group_block: int = 128

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def n_local(self) -> int:
        return self.n_local_experts or self.n_experts

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Values cached per token and layer: compressed vector + rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """Width of a cache row: ``latent_dim`` rounded up to the lanes."""
        return -(-self.latent_dim // CACHE_LANES) * CACHE_LANES

    @property
    def cache_layout(self) -> tuple[int, int, int]:
        """``(heads, width, pools)`` of what serving caches per token and
        layer (serve/cache.py): ONE row of ``cache_width`` in one pool —
        keys and values are expanded from the same row."""
        return 1, self.cache_width, 1

    @property
    def cache_layers(self) -> int:
        """Layers the block pool holds rows for: every layer attends."""
        return self.n_layers

    @property
    def slot_state(self) -> None:
        """No fixed-size per-slot state beside the blocks (docs/SERVE.md
        "What a new family must provide")."""
        return None

    @property
    def shared_ffn_dim(self) -> int:
        return self.n_shared_experts * self.moe_ffn_dim

    @property
    def n_params(self) -> int:
        """Parameters held HERE (the local experts only), from :func:`leaf_shapes`."""
        count = {"top": 1, "dense_layers": self.n_dense_layers, "moe_layers": self.n_moe_layers}
        return sum(count[stack] * math.prod(shape)
                   for stack, leaves in leaf_shapes(self).items()
                   for shape, _ in leaves.values())

    @classmethod
    def tiny(cls, **kw: Any) -> "LatentMoEConfig":
        """Test-size config (CPU-fast): 1 dense + 2 expert layers, 32
        experts in 4 groups, top-4 from the 2 best groups."""
        base = dict(
            vocab_size=256, dim=64, n_layers=3, n_dense_layers=1, n_heads=4,
            q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, ffn_dim=128, moe_ffn_dim=32,
            n_experts=32, n_shared_experts=2, top_k=4, n_groups=4,
            topk_groups=2, max_seq_len=128, rope_orig_max=32,
            dtype=jnp.float32, moe_group_block=8,
        )
        base.update(kw)
        return cls(**base)


# --- rope with YaRN -------------------------------------------------------------


def yarn_freqs(cfg: LatentMoEConfig) -> jax.Array:
    """Rotary frequencies ``[rope/2]`` float32: plain ``theta**(-2j/rope)``
    below the correction range, divided by ``rope_factor`` above it, a
    linear ramp between (the ramp's ends are the dimensions that turn
    ``beta_fast`` and ``beta_slow`` times over the original context)."""
    rope = cfg.qk_rope_head_dim
    half = rope // 2
    f = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if cfg.rope_factor == 1.0:
        return f

    def corr(beta: float) -> float:
        return (rope * math.log(cfg.rope_orig_max / (2 * math.pi * beta))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(corr(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(corr(cfg.rope_beta_slow)), rope - 1)
    ramp = jnp.clip(
        (jnp.arange(half, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0, 1
    )
    return f / cfg.rope_factor * ramp + f * (1 - ramp)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(cfg: LatentMoEConfig) -> float:
    """``qk_head_dim**-0.5 * m**2`` with YaRN's ``m`` from ``mscale_all_dim``."""
    m = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return cfg.qk_head_dim ** -0.5 * m * m


def rope_cos_sin(cfg: LatentMoEConfig, pos: jax.Array) -> tuple[jax.Array, jax.Array]:
    """cos/sin ``[*pos.shape, rope/2]`` float32 at absolute positions ``pos``."""
    ang = pos.astype(jnp.float32)[..., None] * yarn_freqs(cfg)
    m = (_yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
         / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def rotate(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate-half rope on the last axis; cos/sin broadcast against it."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


# --- parameter tree -------------------------------------------------------------

_ATTN_AXES = {
    "attn_norm": ("norm",), "wq_a": ("embed", None), "q_norm": ("norm",),
    "wq_b": (None, "heads"), "wkv_a": ("embed", None), "kv_norm": ("norm",),
    "wkv_b": (None, "heads"), "wo": ("heads", "embed"), "ffn_norm": ("norm",),
}


def logical_axes(cfg: LatentMoEConfig) -> Params:
    """Pytree (matching init_params) of logical axis-name tuples."""
    def stacked(axes: dict) -> dict:
        return {k: ("layers", *v) for k, v in axes.items()}

    dense = {**_ATTN_AXES, "w1": ("embed", "ffn"), "w3": ("embed", "ffn"),
             "w2": ("ffn", "embed")}
    moe = {**_ATTN_AXES, "router": ("embed", None), "router_bias": (None,),
           "w1": ("expert", "embed", "ffn"), "w3": ("expert", "embed", "ffn"),
           "w2": ("expert", "ffn", "embed"), "ws1": ("embed", "ffn"),
           "ws3": ("embed", "ffn"), "ws2": ("ffn", "embed")}
    return {"tok_emb": ("vocab", "embed"), "dense_layers": stacked(dense),
            "moe_layers": stacked(moe), "final_norm": ("norm",),
            "lm_head": ("embed", "vocab")}


def leaf_shapes(cfg: LatentMoEConfig) -> dict[str, dict[str, tuple[tuple[int, ...], int]]]:
    """``{stack: {leaf: (shape of ONE layer's leaf, fan-in; 0 = ones)}}`` —
    the one table of this family's tensors (init here, the benchmark's
    seeded weights, the parameter count)."""
    d, H = cfg.dim, cfg.n_heads
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    attn = {
        "attn_norm": ((d,), 0), "wq_a": ((d, qr), d), "q_norm": ((qr,), 0),
        "wq_b": ((qr, H * cfg.qk_head_dim), qr),
        "wkv_a": ((d, cfg.latent_dim), d), "kv_norm": ((kr,), 0),
        "wkv_b": ((kr, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)), kr),
        "wo": ((H * cfg.v_head_dim, d), H * cfg.v_head_dim),
        "ffn_norm": ((d,), 0),
    }
    F, Fm, Fs, E, n = (cfg.ffn_dim, cfg.moe_ffn_dim, cfg.shared_ffn_dim,
                       cfg.n_experts, cfg.n_local)
    return {
        "top": {"tok_emb": ((cfg.vocab_size, d), d), "final_norm": ((d,), 0),
                "lm_head": ((d, cfg.vocab_size), d)},
        "dense_layers": {**attn, "w1": ((d, F), d), "w3": ((d, F), d),
                         "w2": ((F, d), F)},
        "moe_layers": {**attn, "router": ((d, E), d), "router_bias": ((E,), -1),
                       "w1": ((n, d, Fm), d), "w3": ((n, d, Fm), d),
                       "w2": ((n, Fm, d), Fm), "ws1": ((d, Fs), d),
                       "ws3": ((d, Fs), d), "ws2": ((Fs, d), Fs)},
    }


# router statistics stay float32 whatever the activations' dtype
_F32_LEAVES = ("router", "router_bias")


def init_params(rng: jax.Array, cfg: LatentMoEConfig) -> Params:
    """Seeded init: normal(0, 1/sqrt(fan_in)) matrices, ones for norm gains,
    normal(0, 0.02) for the router's selection bias (a trained one is not
    zero, and zero would hide a program that gates with the biased score)."""
    shapes = leaf_shapes(cfg)
    counts = {"dense_layers": cfg.n_dense_layers, "moe_layers": cfg.n_moe_layers}
    out: Params = {}
    for si, (stack, leaves) in enumerate(shapes.items()):
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


# --- the layer ------------------------------------------------------------------


def attention_inputs(h: jax.Array, lp: Params, cfg: LatentMoEConfig,
                     cos: jax.Array, sin: jax.Array):
    """Normed hidden ``h [..., D]`` -> ``(q_nope [..., H, nope], q_rope
    [..., H, rope] rotated, latent [..., kv_rank + rope])``; ``latent`` is
    the row the cache holds: the normed compressed vector, then the rotated
    rope key. cos/sin ``[..., rope/2]`` at each row's position."""
    H, nope = cfg.n_heads, cfg.qk_nope_head_dim
    cq = rms_norm(h @ lp["wq_a"], lp["q_norm"], cfg.norm_eps)
    # the product stays as stated, as in models/generate.layer: fused with the
    # reshape and the nope/rope split it costs wq_b sliced out of its stack and
    # transposed (constant_dynamic-slice_fusion.9 + copy.766 bf16[1,1536,24576])
    q = lax.optimization_barrier(cq @ lp["wq_b"])
    q = q.reshape(*h.shape[:-1], H, cfg.qk_head_dim)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = rotate(q_rope, cos[..., None, :], sin[..., None, :])
    kv = h @ lp["wkv_a"]
    c_kv = rms_norm(kv[..., :cfg.kv_lora_rank], lp["kv_norm"], cfg.norm_eps)
    k_rope = rotate(kv[..., cfg.kv_lora_rank:], cos, sin)
    return q_nope, q_rope, jnp.concatenate([c_kv, k_rope], axis=-1)


def expanded_attention(q_nope: jax.Array, q_rope: jax.Array, latents: jax.Array,
                       wkv_b: jax.Array, q_pos: jax.Array, cfg: LatentMoEConfig,
                       key_block: int = ATTN_KEY_BLOCK) -> jax.Array:
    """Causal attention of queries ``[B, S, H, .]`` at absolute positions
    ``q_pos [S]`` over the context ``latents [B, C, kv_rank + rope]``
    (position c at index c), keys and values expanded through ``wkv_b`` one
    key block at a time with a running softmax: the largest score tensor is
    ``[B, H, S, key_block]`` float32. Returns ``[B, S, H * v]``."""
    B, S, H, nope = q_nope.shape
    C = latents.shape[1]
    kr, vd = cfg.kv_lora_rank, cfg.v_head_dim
    # the largest block that tiles the context (the engine's contexts are
    # power-of-two block counts, so this is key_block or all of C)
    kb = C if C <= key_block else math.gcd(C, key_block)
    scale = softmax_scale(cfg)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)             # [B,S,H,qk]

    def body(carry, j):
        m, l, acc = carry
        lat = lax.dynamic_slice_in_dim(latents, j * kb, kb, axis=1)
        kv = (lat[..., :kr] @ wkv_b).reshape(B, kb, H, nope + vd)
        k_rope = jnp.broadcast_to(lat[:, :, None, kr:], (B, kb, H, lat.shape[-1] - kr))
        k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)  # [B,kb,H,qk]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        k_pos = j * kb + jnp.arange(kb)
        mask = (q_pos[:, None] >= k_pos[None, :])[None, None]
        s = jnp.where(mask, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # a query row sees key 0 in block 0, so m_new is finite from there on
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(kv.dtype), kv[..., nope:],
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, H, S), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    acc0 = jnp.zeros((B, H, S, vd), jnp.float32)
    (_, l, acc), _ = lax.scan(body, (m0, l0, acc0), jnp.arange(C // kb))
    out = (acc / l[..., None]).astype(q_nope.dtype)            # [B,H,S,v]
    return out.transpose(0, 2, 1, 3).reshape(B, S, H * vd)


def absorb(lp: Params, cfg: LatentMoEConfig) -> tuple[jax.Array, jax.Array]:
    """``wkv_b`` as its two per-head halves ``(w_uk, w_uv)``, each
    ``[kv_rank, H, 128]``: the key half folds into the query, the value
    half is applied after the softmax (the absorbed decode)."""
    w = lp["wkv_b"].reshape(cfg.kv_lora_rank, cfg.n_heads,
                            cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def swiglu(h: jax.Array, w1: jax.Array, w3: jax.Array, w2: jax.Array) -> jax.Array:
    return (jax.nn.silu(h @ w1) * (h @ w3)) @ w2


EXPERT_LEAVES = ("w1", "w3", "w2")


def split_experts(moe_layers: Params) -> tuple[Params, Params]:
    """``(what a layer scan slices per layer, the routed experts' weights)``.
    The experts' ``[layers, n_local, ...]`` stacks stay WHOLE and are indexed
    by (layer, expert) where they are multiplied: sliced per layer by the scan
    they would be copied out of the stack — 1.4 GB a layer at the published
    widths — before the loop over experts could read them."""
    rest = {k: v for k, v in moe_layers.items() if k not in EXPERT_LEAVES}
    return rest, {k: moe_layers[k] for k in EXPERT_LEAVES}


def expert_ffn(h: jax.Array, lp: Params, cfg: LatentMoEConfig,
               valid: jax.Array | None = None, experts=None):
    """Routed experts held here + the shared expert on ``h [..., D]``;
    ``valid [...]`` leaves padding rows out of the routing (and its counts).
    ``experts = (stacked weights, layer index)`` where ``lp`` does not hold
    this layer's own (:func:`split_experts`). Returns ``(y, routes [n_local]
    int32)``."""
    from tony_tpu.parallel.moe import GroupRouting, local_expert_ffn, route_group_limited

    flat = h.reshape(-1, h.shape[-1])
    routing = GroupRouting(
        n_experts=cfg.n_experts, top_k=cfg.top_k, n_groups=cfg.n_groups,
        topk_groups=cfg.topk_groups, routed_scale=cfg.routed_scale,
        norm_topk_prob=cfg.norm_topk_prob,
    )
    sel, gates = route_group_limited(flat, lp["router"], lp["router_bias"], routing)
    if valid is not None:
        sel = jnp.where(valid.reshape(-1, 1), sel, -1)
    stacked, index = experts or ({k: lp[k] for k in EXPERT_LEAVES}, None)
    y, routes = local_expert_ffn(
        stacked, flat, sel, gates, layer=index,
        first_expert=cfg.first_expert, group_block=cfg.moe_group_block,
    )
    y = y + swiglu(flat, lp["ws1"], lp["ws3"], lp["ws2"])
    return y.reshape(h.shape), routes


def layer(x: jax.Array, lp: Params, cfg: LatentMoEConfig, attend: Attend,
          cos: jax.Array, sin: jax.Array, valid: jax.Array | None = None,
          experts=None):
    """One decoder layer, dense or expert by what ``lp`` holds. ``attend``
    decides where keys and values live (module docstring); ``experts`` as
    :func:`expert_ffn` takes it. Returns ``(x', attend's state, routes
    [n_local] int32 or None)``."""
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q_nope, q_rope, latent = attention_inputs(h, lp, cfg, cos, sin)
    o, state = attend(q_nope, q_rope, latent, lp)
    x = x + o @ lp["wo"]
    h2 = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    if "router" in lp:
        delta, routes = expert_ffn(h2, lp, cfg, valid, experts)
    else:
        delta, routes = swiglu(h2, lp["w1"], lp["w3"], lp["w2"]), None
    return x + delta, state, routes


def scan_stacks(body, x, params: Params, xs_dense=None, xs_moe=None):
    """Run ``body(x, lp, extra, experts) -> (x, ys)`` over the dense stack,
    then the expert stack; ``xs_*`` are scanned beside each stack's weights
    (a cache's per-layer slabs). Returns ``(x, ys_dense, ys_moe)``; an empty
    stack is skipped."""
    outs = []
    rest, stacked = split_experts(params["moe_layers"])
    for layers, xs, experts in ((params["dense_layers"], xs_dense, None),
                                (rest, xs_moe, stacked)):
        n = jax.tree.leaves(layers)[0].shape[0]
        if n == 0:
            outs.append(None)
            continue

        def step(x, layer_xs, experts=experts):
            lp, extra, i = layer_xs
            return body(x, lp, extra, (experts, i) if experts else None)

        x, ys = lax.scan(step, x, (layers, xs, jnp.arange(n)))
        outs.append(ys)
    return (x, *outs)


def forward_latents(params: Params, tokens: jax.Array, ctx: jax.Array | None,
                    start: jax.Array, cfg: LatentMoEConfig,
                    valid: jax.Array | None = None):
    """tokens ``[B, S]`` at absolute positions ``start + i``, attending the
    cached context ``ctx [L, B, C, latent]`` (positions below ``start``
    valid; None = no context, C = S) plus themselves. Returns ``(hidden
    [B, S, D] before the final norm, latents [L, B, C, latent] with the new
    rows written at ``start``, routes [n_moe_layers, n_local])``."""
    B, S = tokens.shape
    L = cfg.n_layers
    # a static choice (an argument that is None), not a traced value
    if ctx is None:  # graft-lint: disable=GL002
        ctx = jnp.zeros((L, B, S, cfg.latent_dim), cfg.dtype)
    x = params["tok_emb"][tokens]
    q_pos = start + jnp.arange(S)
    cos, sin = rope_cos_sin(cfg, q_pos)

    def body(x, lp, ctx_l, experts):
        def attend(q_nope, q_rope, latent, lp):
            lat = lax.dynamic_update_slice(ctx_l, latent, (0, start, 0))
            return expanded_attention(q_nope, q_rope, lat, lp["wkv_b"], q_pos, cfg), lat

        x, lat, routes = layer(x, lp, cfg, attend, cos, sin, valid, experts)
        return x, (lat, routes)

    nd = cfg.n_dense_layers
    x, ys_d, ys_m = scan_stacks(body, x, params, ctx[:nd], ctx[nd:])
    lats = [ys[0] for ys in (ys_d, ys_m) if ys is not None]
    routes = ys_m[1] if ys_m is not None else jnp.zeros((0, cfg.n_local), jnp.int32)
    return x, jnp.concatenate(lats, axis=0), routes


def forward(params: Params, tokens: jax.Array, cfg: LatentMoEConfig) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, vocab] float32 (full sequence)."""
    x, _, _ = forward_latents(params, tokens, None, jnp.int32(0), cfg)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return (x @ params["lm_head"]).astype(jnp.float32)


__all__ = [
    "LatentMoEConfig", "absorb", "attention_inputs", "expanded_attention",
    "expert_ffn", "forward", "forward_latents", "init_params", "layer",
    "leaf_shapes", "logical_axes", "rope_cos_sin", "rotate", "scan_stacks",
    "softmax_scale", "split_experts", "swiglu", "yarn_freqs",
]
