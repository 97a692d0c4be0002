"""The serving steps of the short-convolution / attention hybrid with routed
experts (models/shortconv_moe.py): what ``serve/engine.py``'s
``jit_serve_prefill``, ``jit_serve_tail_prefill`` and ``jit_serve_decode``
run when the engine's model is a :class:`ShortConvMoEConfig`. Same names,
same host loop, same block pool and tables as serve/dense.py and
serve/latent.py (docs/SERVE.md "Model families" has the contract); what
differs is that a slot keeps TWO kinds of state:

- K/V blocks for the ATTENTION layers only: the pools are ``[La, P, Hkv /
  pack, block, pack * hd]`` with ``La = cfg.cache_layers`` and ``pack =
  cfg.kv_pack`` neighbouring heads side by side in a row of the chip's 128
  lanes (:func:`_pack_rows`); prefill hands back the prompt's K and V rows
  head-major and the engine scatters them. Decode attends through the one
  paged kernel as it stands: a query head is laid into its K/V head's
  columns of the row, zeros in its neighbours' (:func:`_pack_queries`), so
  its scores are its own head's and its own columns of the output are its
  values — the same mathematics, the pool read once at its native bytes;
- a fixed-size recurrent state for the CONVOLUTION layers (the
  configuration's ``slot_state``): ``cache.slot_state [Lc, S,
  state_width]``, one row a slot and layer holding the last two rows of ``B *
  u``. Prefill returns the state at the prompt's TRUE last positions under
  ``aux['slot_state']`` and the engine writes it into the slot inside
  ``jit_serve_scatter``; a tail or chunked prefill starts from the state its
  predecessor left (the ``slot_state`` argument); decode reads and rewrites
  every live slot's row in place and neither reads nor writes a dead slot's.

Both ride the decode's layer walk as its carry and are written in place.
Every step's ``aux`` holds what its expert layers routed here
(``moe_routes``, ``moe_tokens``), as the latent family's does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.models.generate import sample_tokens
from tony_tpu.models.shortconv_moe import (
    ShortConvMoEConfig, layer_of, conv_token, forward_states, head, init_params,
    layer, put, rope_cos_sin, walk_layers,
)
from tony_tpu.obs import health
from tony_tpu.ops.decode_attention import decode_attention
from tony_tpu.serve.cache import SCRATCH_BLOCK, PagedKVCache, scatter_block_kv

# What this family does not take yet: ``{knob: (the one value it takes, why)}``
# (serve/latent.py's table says what a refusal looks like).
REFUSED_KNOBS = {
    "prefix": (False, "a prefix hit has no convolution state to start from: the store "
                      "keeps K/V blocks, not the state at a block boundary"),
    "quant_kv": ("", "the quantized pool's scale rows are not threaded through this "
                     "family's layer walk"),
    "quant_weights": (False, "the int8 decode matmuls name the dense decoder's seven matrices"),
    "spec": (False, "a rejected draft would have to roll the convolution state back"),
    "decode_impl": ("scan", "no int8 matmul whose form it would pick"),
    "block_handoff": (False, "gang block export/adopt ships (k, v) pools without the "
                             "slot's convolution state"),
}

# the per-slot state is a window of fixed length, not a scan over the prompt
# (docs/SERVE.md item 5)
SCAN_STATE = False


def _pack_rows(rows, cfg: ShortConvMoEConfig):
    """K or V rows ``[..., Hkv, hd]`` as the cache holds them: ``[..., Hkv /
    pack, pack * hd]`` (neighbouring heads are neighbouring columns)."""
    return rows.reshape(*rows.shape[:-2], *cfg.cache_layout[:2])


def _pack_queries(q, cfg: ShortConvMoEConfig):
    """Queries ``[S, H, hd]`` against packed rows: ``[S, H, pack * hd]``,
    each head in the columns of its own K/V head, zeros elsewhere."""
    S, pack, hd = q.shape[0], cfg.kv_pack, cfg.head_dim
    own = jnp.eye(pack, dtype=q.dtype)[:, None, :, None]           # [a, 1, b, 1]
    q = q.reshape(S, -1, pack, cfg.n_heads // cfg.n_kv_heads, 1, hd) * own
    return q.reshape(S, cfg.n_heads, pack * hd)


def _unpack_outputs(o, cfg: ShortConvMoEConfig):
    """The packed attention's output ``[S, H, pack * hd]`` -> ``[S, H, hd]``:
    each head's own columns (the rest are its neighbours' values under its
    probabilities)."""
    S, pack, hd = o.shape[0], cfg.kv_pack, cfg.head_dim
    rep = cfg.n_heads // cfg.n_kv_heads
    o = o.reshape(S, -1, pack, rep, pack, hd)
    own = jnp.arange(pack)[None, None, :, None, None, None]
    return jnp.take_along_axis(o, own, axis=4).reshape(S, cfg.n_heads, hd)


def _head_major(rows, cfg: ShortConvMoEConfig):
    """A prompt's rows ``[La, W, Hkv, hd]`` as the engine scatters them:
    packed and head-major ``[La, Hkv / pack, W, pack * hd]``."""
    return _pack_rows(rows, cfg).transpose(0, 2, 1, 3)


def _prefill(params, tokens, ctx_k, ctx_v, conv_state, start, last_index, temp,
             top_k, top_p, key, cfg: ShortConvMoEConfig, max_top_k: int):
    """``tokens [1, W]`` from position ``start`` after the context and the
    state handed in (None: a whole prompt): ``(first token, rng carry, K, V
    [La, 1, C, Hkv, hd], aux)``; rows past ``last_index`` are padding."""
    valid = (jnp.arange(tokens.shape[1]) <= last_index)[None]
    x, ks, vs, conv, routes = forward_states(
        params, tokens, ctx_k, ctx_v, conv_state, start, last_index, cfg, valid)
    logits = head(params, lax.dynamic_slice_in_dim(x, last_index, 1, axis=1), cfg)
    use, carry = jax.random.split(key)
    tok = sample_tokens(
        logits[:, 0], temp[None], top_k[None], top_p[None], use[None],
        max_k=max_top_k,
    )[0]
    aux = {"moe_routes": routes, "moe_tokens": last_index + 1, "slot_state": conv[:, 0]}
    return tok, carry, ks, vs, aux


def prefill_step(params, prompt, last_index, temp, top_k, top_p, key, *,
                 cfg: ShortConvMoEConfig, bucket: int, max_top_k: int):
    """Whole-prompt prefill of one padded bucket: ``(tok, carry, K rows, V
    rows, aux)``, the rows head-major ``[La, Hkv, bucket, hd]``."""
    del bucket      # the prompt's padded width
    tok, carry, ks, vs, aux = _prefill(
        params, prompt, None, None, None, jnp.int32(0), last_index, temp, top_k,
        top_p, key, cfg, max_top_k)
    return tok, carry, _head_major(ks[:, 0], cfg), _head_major(vs[:, 0], cfg), aux


def tail_prefill_step(params, ctx_k, ctx_v, tail, start, last_index, temp,
                      top_k, top_p, key, *, cfg: ShortConvMoEConfig, tb: int,
                      max_top_k: int, slot_state):
    """One chunk of a chunked prefill: the gathered K/V ``[La, 1, C, Hkv /
    pack, pack * hd]`` (positions below ``start`` valid) are the attention layers' context
    and ``slot_state [Lc, state_width]`` is the slot's convolution state as
    the chunk before left it."""
    heads = (*ctx_k.shape[:3], cfg.n_kv_heads, cfg.head_dim)       # unpacked
    tok, carry, ks, vs, aux = _prefill(
        params, tail, ctx_k.reshape(heads), ctx_v.reshape(heads), slot_state[:, None],
        start, last_index, temp, top_k, top_p, key, cfg, max_top_k)
    tk = lax.dynamic_slice_in_dim(ks[:, 0], start, tb, axis=1)     # [La, tb, Hkv, hd]
    tv = lax.dynamic_slice_in_dim(vs[:, 0], start, tb, axis=1)
    return tok, carry, _head_major(tk, cfg), _head_major(tv, cfg), aux


def decode_step(params, cache: PagedKVCache, table, state, *,
                cfg: ShortConvMoEConfig, kv_block: int, max_top_k: int,
                monitors: bool = False):
    """One token for every slot (serve/dense.py ``decode_step``'s contract
    without drafts). An attention layer writes each live slot's K/V row in
    place at its position — dead slots steer to the layer's scratch block —
    and attends through the table; a convolution layer reads the live slots'
    state rows and rewrites them, a dead slot's row standing as it is. The
    last result carries the step's expert routes beside the health monitors."""
    La, P = cache.k.shape[:2]
    live = state.live
    x = params["tok_emb"][state.last_tok]                      # [S, D]
    pos = cache.lengths
    cos, sin = rope_cos_sin(cfg, pos)                          # [S, hd/2]
    bi, off = pos // kv_block, pos % kv_block
    pid = jnp.where(
        live, jnp.take_along_axis(table, bi[:, None], axis=1)[:, 0], SCRATCH_BLOCK)
    # the pools as [La * P, ...] (serve/cache.scan_layers_paged says why):
    # attention layer ``oi`` reads and writes blocks [oi * P, (oi + 1) * P)
    flat = [a.reshape(La * P, *a.shape[2:]) for a in (cache.k, cache.v)]

    def step(carry, op, ff, oi, fi, experts):
        x, k_pool, v_pool, conv, routes = carry
        if "w_in" in op:
            old = layer_of(conv, oi)                                # [S, state_width]

            def keep(z, taps):
                c, new = conv_token(z, taps, jnp.where(live[:, None], old, 0))
                return c, jnp.where(live[:, None], new, old)
        else:
            def keep(q, k, v):
                base = oi * P
                kp = scatter_block_kv(k_pool, _pack_rows(k, cfg), pid + base, off)
                vp = scatter_block_kv(v_pool, _pack_rows(v, cfg), pid + base, off)
                o = decode_attention(
                    _pack_queries(q, cfg), kp, vp, pos + 1, tables=table + base,
                    block=kv_block, scale=cfg.head_dim ** -0.5)
                return _unpack_outputs(o, cfg), (kp, vp)
        x, new, r = layer(x, op, ff, cfg, keep, cos, sin, valid=live, experts=experts)
        if "w_in" in op:
            conv = put(conv, new, oi)
        else:
            k_pool, v_pool = new
        if r is not None:
            routes = put(routes, r, fi)
        return x, k_pool, v_pool, conv, routes

    routes0 = jnp.zeros((cfg.n_moe_layers, cfg.n_local), jnp.int32)
    x, k_pool, v_pool, conv, routes = walk_layers(
        step, (x, *flat, cache.slot_state, routes0), params, cfg)
    logits = head(params, x, cfg)                              # [S, V]

    both = jax.vmap(jax.random.split)(state.rng)
    nxt = sample_tokens(
        logits, state.temp, state.top_k, state.top_p, both[:, 0], max_k=max_top_k,
    )
    has_eos = state.eos >= 0
    nxt = jnp.where(state.done & has_eos, state.eos, nxt)
    done = state.done | (has_eos & (nxt == state.eos))
    new_state = state._replace(last_tok=nxt, rng=both[:, 1], done=done)
    aux = health.decode_monitors(logits) if monitors else {}
    aux = {**aux, "moe_routes": routes, "moe_tokens": jnp.sum(live.astype(jnp.int32))}
    new_cache = cache._replace(
        k=k_pool.reshape(cache.k.shape), v=v_pool.reshape(cache.v.shape),
        lengths=pos + live.astype(jnp.int32), slot_state=conv)
    return new_cache, new_state, nxt, aux


__all__ = [
    "REFUSED_KNOBS", "SCAN_STATE", "decode_step", "init_params", "prefill_step",
    "tail_prefill_step",
]
