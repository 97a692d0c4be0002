"""The serving steps of the dense grouped-query decoder (models/llama.py
``LlamaConfig``): what ``serve/engine.py``'s ``jit_serve_prefill``,
``jit_serve_tail_prefill``, ``jit_serve_decode`` and
``jit_serve_spec_decode`` run when the engine's model is a
:class:`LlamaConfig`. The contract is the one ``serve/latent.py`` keeps
(docs/SERVE.md "Model families"): the same four names, the same
signatures, and every step's LAST result a dict of what rides the step's
results (empty when nothing does).

- the cache is two pools ``k``, ``v`` ``[L, P, Hkv, block, hd]``; prefill
  hands back the prompt's K and V rows head-major and the engine scatters
  them into the slot's blocks;
- prefill runs ``models/generate.forward_with_cache`` over a contiguous
  context (empty, or the gathered prefix), decode attends through the block
  table (``ops/decode_attention.decode_attention``); both run ONE layer
  body, ``models/generate.layer``, handed how attention keeps its state;
- the pools ride the decode's layer scan as its carry and are written in
  place (``serve/cache.scan_layers_paged``);
- ONE decode step whose query width is static: ``G = draft_k + 1``
  positions a slot, sampled (no drafts) or verified (``serve/spec.py``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.models.generate import (
    KVCache, forward_with_cache, layer, sample_tokens,
)
from tony_tpu.models.llama import (
    LlamaConfig, Params, init_params, rms_norm, rope_freqs,
)
from tony_tpu.obs import health
from tony_tpu.ops.decode_attention import decode_attention
from tony_tpu.ops.quant_mm import quant_matmul, quantize_weights
from tony_tpu.serve.cache import (
    SCRATCH_BLOCK, PagedKVCache, kv_quant_spec, scan_layers_paged,
    scatter_block_kv,
)
from tony_tpu.serve.spec import verify_and_accept

# this family takes every ServeConfig knob (serve/latent.py's table says
# what a refusal looks like)
REFUSED_KNOBS: dict[str, tuple] = {}
# no per-slot state that prefill scans (docs/SERVE.md item 5)
SCAN_STATE = False

_QUANT_WEIGHT_NAMES = ("wq", "wk", "wv", "wo", "w1", "w2", "w3")


def quantize_decode_params(params: Params) -> dict:
    """One-time int8 copy of the decode-path weights (ops/quant_mm.py):
    every layer matmul and lm_head swap to ``<name>_q``/``<name>_s``
    pairs; norms and the embedding stay real-valued. The bf16 master
    params are untouched — prefill keeps using them."""
    layers = dict(params["layers"])
    for name in _QUANT_WEIGHT_NAMES:
        q, s = quantize_weights(layers.pop(name))
        layers[name + "_q"] = q
        layers[name + "_s"] = s
    out = {k: v for k, v in params.items() if k not in ("layers", "lm_head")}
    q, s = quantize_weights(params["lm_head"])
    out["layers"] = layers
    out["lm_head_q"] = q
    out["lm_head_s"] = s
    return out


def _sample_first(logits, temp, top_k, top_p, key, max_top_k):
    """The prompt's first token from its last position's logits ``[1, 1,
    V]``, and the request's rng carry."""
    use, carry = jax.random.split(key)
    tok = sample_tokens(
        logits[:, 0], temp[None], top_k[None], top_p[None], use[None],
        max_k=max_top_k,
    )[0]
    return tok, carry


def prefill_step(params, prompt, last_index, temp, top_k, top_p, key, *,
                 cfg: LlamaConfig, bucket: int, max_top_k: int):
    """Whole-prompt prefill of one padded bucket: ``(tok, carry, K rows, V
    rows, {})``, the rows head-major ``[L, Hkv, bucket, hd]``."""
    logits, kv = forward_with_cache(
        params, prompt, KVCache.create(cfg, 1, bucket), jnp.int32(0), cfg,
        last_index=last_index,
    )
    tok, carry = _sample_first(logits, temp, top_k, top_p, key, max_top_k)
    # [L, 1, bucket, Hkv, hd] -> head-major [L, Hkv, bucket, hd]
    pk = kv.k[:, 0].transpose(0, 2, 1, 3)
    pv = kv.v[:, 0].transpose(0, 2, 1, 3)
    return tok, carry, pk, pv, {}


def tail_prefill_step(params, ctx_k, ctx_v, tail, start, last_index, temp,
                      top_k, top_p, key, *, cfg: LlamaConfig, tb: int,
                      max_top_k: int):
    """Prefill only the unshared tail of a prefix-matched prompt: the
    gathered prefix K/V (``[L, 1, C, Hkv, hd]``, positions ``[0, start)``
    valid) is the attention context, the tail bucket runs from absolute
    position ``start``, and only the prompt's true last position projects
    through lm_head. Bitwise-identical to the full prefill's logits —
    forward_with_cache masks by absolute position and every masked term is
    exactly zero."""
    logits, kv = forward_with_cache(
        params, tail, KVCache(ctx_k, ctx_v), start, cfg,
        last_index=last_index,
    )
    tok, carry = _sample_first(logits, temp, top_k, top_p, key, max_top_k)
    # the tail's K/V, head-major [L, Hkv, tb, hd], for the block scatter
    tk = lax.dynamic_slice_in_dim(kv.k[:, 0], start, tb, axis=1)
    tv = lax.dynamic_slice_in_dim(kv.v[:, 0], start, tb, axis=1)
    return tok, carry, tk.transpose(0, 2, 1, 3), tv.transpose(0, 2, 1, 3), {}


def _q_mm(h, lp, name, quant_weights, impl):
    """One decode matmul: the bf16 master weight, or its int8 copy through
    the fused dequant-matmul (ops/quant_mm.py) when quantized."""
    if quant_weights:
        return quant_matmul(h, lp[name + "_q"], lp[name + "_s"], impl=impl)
    return h @ lp[name]


def _write_kv(pools, k_new, v_new, pids, offs, qmax):
    """This layer's K/V rows into the carried pools ``(k, v, k_scale,
    v_scale)``; with scale pools (a quantized cache) the written amax
    folds into the block scale. ``pids`` already carry the layer's offset."""
    k, v, ks, vs = pools
    if ks is None:
        return (scatter_block_kv(k, k_new, pids, offs),
                scatter_block_kv(v, v_new, pids, offs), None, None)
    k, ks = scatter_block_kv(k, k_new, pids, offs, scale=ks, qmax=qmax)
    v, vs = scatter_block_kv(v, v_new, pids, offs, scale=vs, qmax=qmax)
    return k, v, ks, vs


def decode_step(params, cache: PagedKVCache, table, state, drafts=None,
                draft_len=None, *, cfg: LlamaConfig, decode_impl: str,
                kv_block: int, max_top_k: int, monitors: bool = False,
                quant_kv: str = "", quant_weights: bool = False,
                draft_k: int = 0):
    """One decode step for every slot at a STATIC query width ``G =
    draft_k + 1``: feed each row its last sampled token (and, with
    ``drafts [S, draft_k]``, its drafts; short ones padded), write their
    K/V at positions ``pos .. pos + draft_k`` into the physical blocks the
    row's table names, attend every query position over the row's written
    prefix through the table, then either sample with the row's own stream
    (no drafts: ``(cache, state, toks [S], aux)``) or run the rejection
    rule of serve/spec.py so the emitted prefix is draw-for-draw what G
    one-wide steps would have sampled (``(cache, state, toks [S, G],
    n_emit [S], aux)``).

    Dead slots — and padding positions past a row's draft length — steer
    to the scratch block, so a freed, possibly reallocated block can never
    be corrupted. The pools ride the layer scan as its carry and are
    written in place (:func:`scan_layers_paged`); ``table`` and the write
    ids name blocks of one layer and take the layer's offset inside the
    scan, so a dead slot's row lands in that layer's scratch block.
    Rollback is free: ``lengths`` advance by exactly the emitted count, so
    rejected positions' K/V sit beyond every length mask and are
    overwritten by later steps.

    Without drafts nothing carries a ``G`` axis (rows ``[S, ...]``, as
    ``decode_attention`` takes them) and what the drafted form adds is
    static on ``drafts is None``, so the plain program holds none of it
    (compiled for the chip: PERF.md §6, PR 29).

    ``aux``: the fused per-slot health monitors when ``monitors`` (logits
    nonfinite counts + sampling entropy, obs/health.py; with drafts, of
    the LAST emitted position — the same autoregressive frontier the
    one-wide step reports), else empty.

    ``quant_kv``: the pools are block-scaled quantized — writes fold into
    the running block scale (scales only ever grow, so a rollback never
    leaves a block whose payload overflows its scale) and the attention
    kernels dequantize inline through the scale pools, which ride the
    layer scan next to their payloads. ``quant_weights``: the seven layer
    matmuls + lm_head read int8 weights through the fused dequant-matmul
    (``decode_impl`` picks its form)."""
    qmax = kv_quant_spec(quant_kv)[1] if quant_kv else 0.0
    G = draft_k + 1
    pos0 = cache.lengths                                   # [S]
    live = state.live
    # paged write targets: position p of row s lands in physical block
    # table[s, p // block] at offset p % block
    if drafts is None:
        tokens_in, pos = state.last_tok, pos0              # [S]
        write_ok = live
        blk = jnp.take_along_axis(
            table, (pos // kv_block)[:, None], axis=1)[:, 0]
        off = pos % kv_block
    else:
        # fed tokens: [last_tok, d_1 .. d_k] — token j conditions position
        # pos + j and its logits score the candidate at pos + j + 1
        tokens_in = jnp.concatenate([state.last_tok[:, None], drafts], axis=1)
        goff = jnp.arange(G, dtype=jnp.int32)
        pos = pos0[:, None] + goff[None, :]                # [S, G]
        write_ok = live[:, None] & (goff[None, :] <= draft_len[:, None])
        blk = jnp.take_along_axis(
            table, jnp.minimum(pos // kv_block, table.shape[1] - 1), axis=1)
        off = jnp.where(write_ok, pos % kv_block, 0)
    pid = jnp.where(write_ok, blk, SCRATCH_BLOCK)
    x = params["tok_emb"][tokens_in]                       # [S, (G,) D]
    ang = pos.astype(jnp.float32)[..., None] * rope_freqs(cfg)
    cos = jnp.cos(ang)[..., None, :]                       # [S, (G,) 1, half]
    sin = jnp.sin(ang)[..., None, :]

    def rope(t):  # [S, (G,) H', hd], per-position angle
        t1, t2 = jnp.split(t.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate(
            [t1 * cos - t2 * sin, t2 * cos + t1 * sin], axis=-1
        ).astype(t.dtype)

    mm = partial(_q_mm, quant_weights=quant_weights, impl=decode_impl)

    def block(x, lp, pools, base):
        def attend(q, k_new, v_new):
            # in-place row writes into the carried pool at this layer's
            # blocks (pid is a block of ONE layer; base = l * P moves it —
            # and the scratch block — into layer l's range), then query g
            # of row s sees positions < pos0[s] + g + 1
            written = _write_kv(pools, k_new, v_new, pid + base, off, qmax)
            k_pool, v_pool, k_sc, v_sc = written
            attn = decode_attention(
                q, k_pool, v_pool, pos0 + G, tables=table + base,
                block=kv_block, k_scale=k_sc, v_scale=v_sc,
            )
            return attn, written

        return layer(x, lp, cfg, attend, rope, mm)

    x, pools = scan_layers_paged(block, x, params["layers"], cache)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = mm(x, params, "lm_head").astype(jnp.float32)  # [S, (G,) V]

    def new_cache(lengths):
        return PagedKVCache(*pools[:2], lengths, *pools[2:])

    if drafts is None:
        both = jax.vmap(jax.random.split)(state.rng)       # [S, 2, 2]
        nxt = sample_tokens(
            logits, state.temp, state.top_k, state.top_p, both[:, 0],
            max_k=max_top_k,
        )
        has_eos = state.eos >= 0
        nxt = jnp.where(state.done & has_eos, state.eos, nxt)
        done = state.done | (has_eos & (nxt == state.eos))
        new_state = state._replace(last_tok=nxt, rng=both[:, 1], done=done)
        aux = health.decode_monitors(logits) if monitors else {}
        return new_cache(pos0 + live.astype(jnp.int32)), new_state, nxt, aux

    toks, n_emit, _n_acc, last_tok, new_rng, done = verify_and_accept(
        logits, drafts, draft_len, state, max_top_k=max_top_k,
    )
    new_state = state._replace(
        last_tok=jnp.where(live, last_tok, state.last_tok),
        rng=jnp.where(live[:, None], new_rng, state.rng),
        done=jnp.where(live, done, state.done),
    )
    aux = {}
    if monitors:
        last_idx = jnp.maximum(n_emit - 1, 0)
        aux = health.decode_monitors(jnp.take_along_axis(
            logits, last_idx[:, None, None], axis=1)[:, 0])
    lengths = pos0 + n_emit * live.astype(jnp.int32)
    return new_cache(lengths), new_state, toks, n_emit, aux


__all__ = [
    "REFUSED_KNOBS", "SCAN_STATE", "decode_step", "init_params", "prefill_step",
    "quantize_decode_params", "tail_prefill_step",
]
