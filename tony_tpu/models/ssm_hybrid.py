"""Selective state-space / attention hybrid decoder: the fourth architecture
beside llama.py, latent_moe.py and shortconv_moe.py.

A pre-norm decoder whose MIXER is chosen per layer by a period and an offset
(layer ``l`` is attention iff ``l % attn_layer_period == attn_layer_offset``):
a Mamba-1 selective state-space mixer whose three inner streams are each
RMS-normalised, or multi-query attention WITHOUT any position term (the
recurrence carries the order). Every feed-forward is a dense SwiGLU, the head
is the embedding (tied). ISSUE 33 spells the equations out as published for
the 3B open model of this family (``model_type: jamba``).

The Mamba mixer on a normed position ``n_t`` (D model width, E = expand * D
inner width, N state size, R rank of the step size, K taps):

1. ``[u_t, z_t] = n_t @ w_in``;
2. ``c_t = silu(conv_b + sum_j conv_w[j] * u_{t-(K-1)+j})`` (causal, depthwise);
3. ``[d_t, B_t, C_t] = c_t @ w_x``, each RMS-normalised with its own gain;
4. ``delta_t = softplus(d_t @ w_dt + b_dt)``, ``A = -exp(a_log)``;
5. ``h_t = exp(delta_t * A) * h_{t-1} + (delta_t * c_t) * B_t`` — ``h`` is
   ``[N, E]`` float32 (the channels on the minor dimension, which fills the
   chip's lanes);
6. ``y_t = h_t . C_t + d_skip * c_t``; the mixer is ``(y_t * silu(z_t)) @ w_out``.

Steps 5-6 are ``ops/selective_scan.py``. What a sequence hands to its
continuation is ONE float32 array a layer, ``[N + K - 1, E]``: the ``N`` rows
of ``h`` and under them the last ``K - 1`` rows of ``u`` (oldest first; the
convolution's tail, bfloat16 values in a float32 container) — the
configuration's ``slot_state``.

What is here: the configuration, seeded init, logical axes, ONE layer body
and the loop over the layers (models/layer_walk.py, shared with
shortconv_moe.py: weights stacked by kind, runs of equal layers scanned). The
body does not know where a mixer keeps its state: ``keep`` is handed in —
``keep(u, z, op) -> (gated y, state)`` for a Mamba layer, ``keep(q, k, v) ->
(o, state)`` for an attention layer. ``forward`` and the serving steps
(serve/ssm_hybrid.py) hand in their own.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.models.latent_moe import swiglu
from tony_tpu.models.layer_walk import (
    ATTENTION, MAMBA, Run, layer_of, put, runs_of, walk_layers,
)
from tony_tpu.models.llama import rms_norm
from tony_tpu.models.shortconv_moe import causal_attention
from tony_tpu.ops.selective_scan import selective_scan

Params = dict[str, Any]


@dataclass(frozen=True)
class SSMHybridConfig:
    vocab_size: int = 65536
    dim: int = 2560
    n_layers: int = 28
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    n_heads: int = 20
    n_kv_heads: int = 1
    head_dim: int = 128
    ffn_dim: int = 8192
    mamba_expand: int = 2
    d_state: int = 16                # N
    d_conv: int = 4                  # K, taps of the causal convolution
    dt_rank: int = 160               # R
    max_seq_len: int = 4096
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @property
    def layer_types(self) -> tuple[str, ...]:
        """The mixer of every layer, from the period and the offset."""
        return tuple(
            ATTENTION if l % self.attn_layer_period == self.attn_layer_offset else MAMBA
            for l in range(self.n_layers))

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.dim

    @property
    def n_mamba_layers(self) -> int:
        return self.layer_types.count(MAMBA)

    @property
    def n_attn_layers(self) -> int:
        return self.layer_types.count(ATTENTION)

    @property
    def runs(self) -> tuple[Run, ...]:
        """The layers as runs of equal mixers (every feed-forward is dense)."""
        return runs_of(self.layer_types, self.n_layers)

    @property
    def cache_layout(self) -> tuple[int, int, int]:
        """``(heads, width, pools)`` of what an ATTENTION layer caches per
        token (serve/cache.py): K and V rows of ``n_kv_heads x head_dim``."""
        return self.n_kv_heads, self.head_dim, 2

    @property
    def cache_layers(self) -> int:
        """Layers the block pool holds: the attention layers only."""
        return self.n_attn_layers

    @property
    def state_rows(self) -> int:
        """Rows of ``d_inner`` a Mamba layer keeps per sequence: ``d_state``
        of ``h`` and the convolution's last ``d_conv - 1`` inputs."""
        return self.d_state + self.d_conv - 1

    @property
    def slot_state(self) -> tuple[int, tuple[int, ...], Any]:
        """``(layers, shape, dtype)`` of the fixed-size state a slot keeps
        beside its blocks (docs/SERVE.md "What a new family must provide"):
        ONE float32 array whose leading index is (Mamba layer, state row) and
        whose rows are ``d_inner`` wide, so that the engine's buffer is
        ``[layers x state_rows, slots, d_inner]``: slots on the sublanes and
        channels on the lanes of every row, the layout the chip's compiler
        otherwise relays a ``[.., slots, state_rows, d_inner]`` buffer into,
        in and out of every scanned run of layers (PERF.md section 6, PR 33)."""
        return self.n_mamba_layers * self.state_rows, (self.d_inner,), jnp.float32

    @property
    def n_params(self) -> int:
        """Parameters (the tied head once), from :func:`leaf_shapes`."""
        count = {"top": 1, "mamba_layers": self.n_mamba_layers,
                 "attn_layers": self.n_attn_layers, "dense_ffns": self.n_layers}
        return sum(count[stack] * math.prod(shape)
                   for stack, leaves in leaf_shapes(self).items()
                   for shape, _ in leaves.values())

    @classmethod
    def tiny(cls, **kw: Any) -> "SSMHybridConfig":
        """Test-size config (CPU-fast): 5 layers, attention at layer 2 (a
        non-zero offset), 5 query heads to one K/V head."""
        base = dict(
            vocab_size=256, dim=64, n_layers=5, attn_layer_period=3, attn_layer_offset=2,
            n_heads=5, n_kv_heads=1, head_dim=16, ffn_dim=128, mamba_expand=2, d_state=4,
            d_conv=4, dt_rank=8, max_seq_len=128, dtype=jnp.float32,
        )
        base.update(kw)
        return cls(**base)


# --- parameter tree -------------------------------------------------------------

# how a leaf is drawn: a fan-in (normal / sqrt), or one of these
ONES, A_LOG, DT_BIAS, DT_WEIGHT = 0, -1, -2, -3
DT_MIN, DT_MAX = 1e-3, 0.1
# the recurrence's own parameters stay float32 whatever the activations' dtype
_F32_LEAVES = ("a_log", "d_skip", "b_dt")


def leaf_shapes(cfg: SSMHybridConfig) -> dict[str, dict[str, tuple[tuple[int, ...], int]]]:
    """``{stack: {leaf: (shape of ONE layer's leaf, fan-in or one of ONES,
    A_LOG, DT_BIAS, DT_WEIGHT)}}`` — the one table of this family's tensors.
    ``conv_w`` is ``[K, E]``: tap ``j`` multiplies the input ``K - 1 - j``
    positions back. ``a_log`` is ``[N, E]``, as the state is."""
    d, hd, E = cfg.dim, cfg.head_dim, cfg.d_inner
    N, R, K, F = cfg.d_state, cfg.dt_rank, cfg.d_conv, cfg.ffn_dim
    nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    return {
        "top": {"tok_emb": ((cfg.vocab_size, d), d), "final_norm": ((d,), ONES)},
        "mamba_layers": {
            "op_norm": ((d,), ONES), "w_in": ((d, 2 * E), d),
            "conv_w": ((K, E), K), "conv_b": ((E,), K),
            "w_x": ((E, R + 2 * N), E),
            "dt_norm": ((R,), ONES), "b_norm": ((N,), ONES), "c_norm": ((N,), ONES),
            "w_dt": ((R, E), DT_WEIGHT), "b_dt": ((E,), DT_BIAS),
            "a_log": ((N, E), A_LOG), "d_skip": ((E,), ONES), "w_out": ((E, d), E)},
        "attn_layers": {"op_norm": ((d,), ONES), "wq": ((d, nq), d), "wk": ((d, nkv), d),
                        "wv": ((d, nkv), d), "wo": ((nq, d), nq)},
        "dense_ffns": {"ffn_norm": ((d,), ONES), "w1": ((d, F), d), "w3": ((d, F), d),
                       "w2": ((F, d), F)},
    }


_AXES = {
    "mamba_layers": {
        "op_norm": ("norm",), "w_in": ("embed", None), "conv_w": (None, None),
        "conv_b": (None,), "w_x": (None, None), "dt_norm": (None,), "b_norm": (None,),
        "c_norm": (None,), "w_dt": (None, None), "b_dt": (None,), "a_log": (None, None),
        "d_skip": (None,), "w_out": (None, "embed")},
    "attn_layers": {"op_norm": ("norm",), "wq": ("embed", "heads"), "wk": ("embed", "heads"),
                    "wv": ("embed", "heads"), "wo": ("heads", "embed")},
    "dense_ffns": {"ffn_norm": ("norm",), "w1": ("embed", "ffn"), "w3": ("embed", "ffn"),
                   "w2": ("ffn", "embed")},
}


def logical_axes(cfg: SSMHybridConfig) -> Params:
    """Pytree (matching init_params) of logical axis-name tuples."""
    del cfg
    out: Params = {"tok_emb": ("vocab", "embed"), "final_norm": ("norm",)}
    for stack, axes in _AXES.items():
        out[stack] = {k: ("layers", *v) for k, v in axes.items()}
    return out


def init_leaf(key: jax.Array, shape: tuple[int, ...], how: int, dtype) -> jax.Array:
    """One leaf (a stack of layers' or a top-level one). Matrices, taps and
    the convolution's bias: normal(0, 1/sqrt(fan-in)). The recurrence gets the
    family's own initialisation, so that random weights remember as trained
    ones do: ``a_log = log(1..N)`` a channel, the step size's bias the inverse
    softplus of a log-uniform draw in [DT_MIN, DT_MAX], its weight
    uniform(+-R^-1/2) — with a unit-variance ``w_dt`` the state forgets
    within a few tokens."""
    if how == ONES:
        return jnp.ones(shape, dtype)
    if how == A_LOG:
        n = jnp.arange(1, shape[-2] + 1, dtype=jnp.float32)
        return jnp.broadcast_to(jnp.log(n)[:, None], shape).astype(dtype)
    if how == DT_BIAS:
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)       # softplus^-1(dt)
    if how == DT_WEIGHT:
        bound = shape[-2] ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound).astype(dtype)
    return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(how)).astype(dtype)


def init_params(rng: jax.Array, cfg: SSMHybridConfig) -> Params:
    """Seeded init (:func:`init_leaf`), weights stacked by kind."""
    counts = {"mamba_layers": cfg.n_mamba_layers, "attn_layers": cfg.n_attn_layers,
              "dense_ffns": cfg.n_layers}
    out: Params = {}
    for si, (stack, leaves) in enumerate(leaf_shapes(cfg).items()):
        tree = {}
        for li, (name, (shape, how)) in enumerate(leaves.items()):
            full = shape if stack == "top" else (counts[stack], *shape)
            dtype = jnp.float32 if name in _F32_LEAVES else cfg.dtype
            k = jax.random.fold_in(jax.random.fold_in(rng, si), li)
            tree[name] = init_leaf(k, full, how, dtype)
        if stack == "top":
            out.update(tree)
        else:
            out[stack] = tree
    return out


# --- the Mamba mixer's pieces ---------------------------------------------------


def conv_sequence(u: jax.Array, op: Params, tail: jax.Array,
                  last_index: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Step 2 over a sequence ``u [B, S, E]`` whose predecessor left the
    inputs ``tail [B, K - 1, E]`` (zeros for a fresh sequence). Returns ``(c
    [B, S, E], the new tail)``, the tail the rows at the TRUE last positions
    ``last_index - (K - 2) .. last_index`` — not at the padded bucket's end."""
    S, K = u.shape[1], op["conv_w"].shape[0]
    uu = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    uf, w = uu.astype(jnp.float32), op["conv_w"].astype(jnp.float32)
    acc = sum(w[j] * lax.slice_in_dim(uf, j, j + S, axis=1) for j in range(K))
    c = jax.nn.silu(acc + op["conv_b"].astype(jnp.float32)).astype(u.dtype)
    return c, lax.dynamic_slice_in_dim(uu, last_index + 1, K - 1, axis=1)


def conv_token(u: jax.Array, op: Params, tail: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Step 2 for one position a row: ``u [S, E]`` after the inputs ``tail [K
    - 1, S, E]`` (oldest first; the rows lead, as the serving state keeps
    them). Returns ``(c [S, E], the new tail)``: the tail drops its oldest
    row and takes ``u``."""
    K = op["conv_w"].shape[0]
    uu = jnp.concatenate([tail.astype(u.dtype), u[None]], axis=0)          # [K, S, E]
    w = op["conv_w"].astype(jnp.float32)
    acc = sum(w[j] * uu[j].astype(jnp.float32) for j in range(K))
    c = jax.nn.silu(acc + op["conv_b"].astype(jnp.float32)).astype(u.dtype)
    return c, uu[1:]


def ssm_inputs(c: jax.Array, op: Params, cfg: SSMHybridConfig):
    """Steps 3-4 on ``c [..., E]``: ``(delta [..., E] float32, B [..., N], C
    [..., N])``, the three streams each normed with its own gain."""
    R, N = cfg.dt_rank, cfg.d_state
    dbc = c @ op["w_x"]
    d = rms_norm(dbc[..., :R], op["dt_norm"], cfg.norm_eps)
    b = rms_norm(dbc[..., R:R + N], op["b_norm"], cfg.norm_eps)
    cc = rms_norm(dbc[..., R + N:], op["c_norm"], cfg.norm_eps)
    delta = jnp.einsum("...r,re->...e", d, op["w_dt"], preferred_element_type=jnp.float32)
    return jax.nn.softplus(delta + op["b_dt"]), b, cc


def decay_of(op: Params) -> jax.Array:
    """``A = -exp(a_log)``, ``[N, E]`` float32."""
    return -jnp.exp(op["a_log"].astype(jnp.float32))


def sequence_mixer(u: jax.Array, z: jax.Array, op: Params, cfg: SSMHybridConfig,
                   state: jax.Array, last_index: jax.Array):
    """Steps 2-6 over ``u``, ``z [B, S, E]`` from the state ``[B, N + K - 1,
    E]`` the predecessor left; rows past ``last_index`` are padding and do
    not enter the state handed on (their step size is 0, so ``h`` stands).
    Returns ``(y * silu(z) [B, S, E], the new state)``."""
    N = cfg.d_state
    c, tail = conv_sequence(u, op, state[:, N:], last_index)
    delta, b, cc = ssm_inputs(c, op, cfg)
    valid = jnp.arange(u.shape[1]) <= last_index
    delta = jnp.where(valid[None, :, None], delta, 0.0)
    a, d_skip = decay_of(op), op["d_skip"].astype(jnp.float32)
    if u.shape[0] == 1:      # a serving prefill: the kernel under its own name in a trace
        y, h = selective_scan(c[0], delta[0], b[0], cc[0], z[0], a, d_skip, state[0, :N])
        y, h = y[None], h[None]
    else:
        y, h = jax.vmap(selective_scan, in_axes=(0, 0, 0, 0, 0, None, None, 0))(
            c, delta, b, cc, z, a, d_skip, state[:, :N])
    return y, jnp.concatenate([h, tail.astype(jnp.float32)], axis=1)


# --- the layer ------------------------------------------------------------------

# for a Mamba layer (u [..., E], z [..., E], op) -> (y * silu(z) [..., E], state);
# for an attention layer (q [..., H, hd], k [..., Hkv, hd], v) -> (o [..., H, hd], state)
Keep = Callable[..., tuple[jax.Array, Any]]


def mamba(h: jax.Array, op: Params, keep: Keep):
    """The selective state-space mixer on normed ``h [..., D]``."""
    # the product stays as stated (models/generate.layer says why): fused with
    # the split the compiler may relay w_in out of its stack
    uz = lax.optimization_barrier(h @ op["w_in"])
    u, z = jnp.split(uz, 2, axis=-1)
    y, state = keep(u, z, op)
    return y @ op["w_out"], state


def attention(h: jax.Array, op: Params, cfg: SSMHybridConfig, keep: Keep):
    """Multi-query attention on normed ``h [..., D]``: no rotation and no
    other position term."""
    lead, hd = h.shape[:-1], cfg.head_dim
    q, k = lax.optimization_barrier((h @ op["wq"], h @ op["wk"]))
    q = q.reshape(*lead, cfg.n_heads, hd)
    k = k.reshape(*lead, cfg.n_kv_heads, hd)
    v = (h @ op["wv"]).reshape(*lead, cfg.n_kv_heads, hd)
    o, state = keep(q, k, v)
    return o.reshape(*lead, cfg.n_heads * hd) @ op["wo"], state


def layer(x: jax.Array, op: Params, ff: Params, cfg: SSMHybridConfig, keep: Keep):
    """One decoder layer: the mixer by what ``op`` holds (Mamba where it has
    ``w_in``), then the dense SwiGLU. ``keep`` decides where the mixer's
    state lives (module docstring). Returns ``(x', keep's state)``."""
    h = rms_norm(x, op["op_norm"], cfg.norm_eps)
    if "w_in" in op:
        o, state = mamba(h, op, keep)
    else:
        o, state = attention(h, op, cfg, keep)
    x = x + o
    h2 = rms_norm(x, ff["ffn_norm"], cfg.norm_eps)
    return x + swiglu(h2, ff["w1"], ff["w3"], ff["w2"]), state


def forward_states(params: Params, tokens: jax.Array, ctx_k: jax.Array | None,
                   ctx_v: jax.Array | None, state: jax.Array | None,
                   start: jax.Array, last_index: jax.Array, cfg: SSMHybridConfig):
    """tokens ``[B, S]`` at absolute positions ``start + i``, after a
    predecessor that left the attention layers' context ``ctx_k``/``ctx_v
    [La, B, C, Hkv, hd]`` (positions below ``start`` valid; None = none, C =
    S) and the Mamba layers' state ``[Lm, B, N + K - 1, E]`` float32 (None =
    zeros). Returns ``(hidden [B, S, D] before the final norm, K, V with the
    new rows written at ``start``, the state at ``last_index``)``."""
    B, S = tokens.shape
    # static choices (an argument that is None), not traced values
    if ctx_k is None:  # graft-lint: disable=GL002
        shape = (cfg.n_attn_layers, B, S, cfg.n_kv_heads, cfg.head_dim)
        ctx_k, ctx_v = jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)
    if state is None:  # graft-lint: disable=GL002
        state = jnp.zeros((cfg.n_mamba_layers, B, cfg.state_rows, cfg.d_inner), jnp.float32)
    x = params["tok_emb"][tokens]
    q_pos = start + jnp.arange(S)

    def step(carry, op, ff, oi, fi, experts):
        del fi, experts
        x, ks, vs, state = carry
        if "w_in" in op:
            def keep(u, z, op):
                return sequence_mixer(u, z, op, cfg, layer_of(state, oi), last_index)
        else:
            def keep(q, k, v):
                k_all = lax.dynamic_update_slice(layer_of(ks, oi), k, (0, start, 0, 0))
                v_all = lax.dynamic_update_slice(layer_of(vs, oi), v, (0, start, 0, 0))
                return causal_attention(q, k_all, v_all, q_pos), (k_all, v_all)
        x, new = layer(x, op, ff, cfg, keep)
        if "w_in" in op:
            state = put(state, new, oi)
        else:
            ks, vs = put(ks, new[0], oi), put(vs, new[1], oi)
        return x, ks, vs, state

    return walk_layers(step, (x, ctx_k, ctx_v, state), params, cfg)


def head(params: Params, x: jax.Array, cfg: SSMHybridConfig) -> jax.Array:
    """Final norm and the tied head: ``x [..., D]`` -> float32 logits ``[..., V]``."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return jnp.einsum("...d,vd->...v", x, params["tok_emb"],
                      preferred_element_type=jnp.float32)


def forward(params: Params, tokens: jax.Array, cfg: SSMHybridConfig) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, vocab] float32 (full sequence)."""
    x = forward_states(params, tokens, None, None, None, jnp.int32(0),
                       jnp.int32(tokens.shape[1] - 1), cfg)[0]
    return head(params, x, cfg)


__all__ = [
    "SSMHybridConfig", "attention", "conv_sequence", "conv_token", "decay_of", "forward",
    "forward_states", "head", "init_leaf", "init_params", "layer", "leaf_shapes",
    "logical_axes", "mamba", "sequence_mixer", "ssm_inputs",
]
