"""The serving steps of the latent-attention expert decoder
(models/latent_moe.py): what ``serve/engine.py``'s ``jit_serve_prefill``,
``jit_serve_tail_prefill`` and ``jit_serve_decode`` run when the engine's
model is a :class:`LatentMoEConfig`. Same names, same signatures, same host
loop, same block pool and tables as the dense decoder's steps in
serve/dense.py (docs/SERVE.md "Model families" has the contract); what
differs is the state attention keeps:

- the cache is ONE pool ``[L, P, 1, block, cache_width]`` (the
  configuration's ``cache_layout``; the latent row zero-padded to the
  chip's lanes): prefill hands back the prompt's latent rows and the
  engine scatters them into the slot's blocks; the ``v`` half of every
  (k, v) pair in the engine's plumbing is None;
- prefill attends EXPANDED (``wkv_b`` applied to the context's latents,
  blockwise over keys), decode attends ABSORBED through the block table
  (``ops/decode_attention.latent_decode_attention``), each cached row read
  once;
- the pool rides the layer scan of BOTH stacks (leading dense layers, then
  expert layers) as its carry and is written in place, exactly as
  ``scan_layers_paged`` does for the dense decoder;
- every step's last result (``aux``) holds what its expert layers routed
  here (``moe_routes [n_moe_layers, n_local]``, ``moe_tokens``), which the
  engine fetches with the sampled tokens — no sync of its own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.models.generate import sample_tokens
from tony_tpu.models.latent_moe import (
    LatentMoEConfig, absorb, forward_latents, init_params, layer,
    rope_cos_sin, softmax_scale, split_experts,
)
from tony_tpu.models.llama import rms_norm
from tony_tpu.obs import health
from tony_tpu.ops.decode_attention import latent_decode_attention
from tony_tpu.serve.cache import (
    SCRATCH_BLOCK, PagedKVCache, scan_layers_paged, scatter_block_kv,
)

# What this family does not take yet: ``{knob: (the one value it takes, why)}``.
# A ServeConfig field set otherwise is refused by name at Engine.__init__ and
# never reaches a step; ``block_handoff`` is not a field — the engine refuses
# ``export_prefix_blocks`` / ``adopt_blocks`` when called.
REFUSED_KNOBS = {
    "quant_kv": ("", "the latent pool has no block-scaled quantized form"),
    "quant_weights": (False, "the int8 decode matmuls name the dense decoder's seven matrices"),
    "spec": (False, "the absorbed decode attends one query position per slot"),
    "decode_impl": ("scan", "the absorbed latent decode has a scan form only"),
    "block_handoff": (False, "gang block export/adopt ships (k, v) pools in a BlockPayload"),
}

# no per-slot state that prefill scans (docs/SERVE.md item 5)
SCAN_STATE = False


def _cache_rows(latents, cfg: LatentMoEConfig):
    """Latent rows as the cache holds them: zero lanes up to ``cache_width``."""
    pad = [(0, 0)] * (latents.ndim - 1) + [(0, cfg.cache_width - cfg.latent_dim)]
    return jnp.pad(latents, pad)


def _prefill(params, tokens, ctx, start, last_index, temp, top_k, top_p, key,
             cfg: LatentMoEConfig, max_top_k: int):
    """``tokens [1, W]`` from position ``start`` over the context ``ctx`` (None:
    a whole prompt): ``(first token, rng carry, latents [L, 1, C, latent],
    moe counters)``; rows past ``last_index`` are padding."""
    valid = (jnp.arange(tokens.shape[1]) <= last_index)[None]
    x, lats, routes = forward_latents(params, tokens, ctx, start, cfg, valid)
    x = lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    use, carry = jax.random.split(key)
    tok = sample_tokens(
        logits[:, 0], temp[None], top_k[None], top_p[None], use[None],
        max_k=max_top_k,
    )[0]
    return tok, carry, lats, {"moe_routes": routes, "moe_tokens": last_index + 1}


def prefill_step(params, prompt, last_index, temp, top_k, top_p, key, *,
                 cfg: LatentMoEConfig, bucket: int, max_top_k: int):
    """Whole-prompt prefill of one padded bucket: ``(tok, carry, latents
    [L, 1, bucket, latent], None, moe)``."""
    del bucket      # the prompt's padded width
    tok, carry, lats, moe = _prefill(params, prompt, None, jnp.int32(0), last_index,
                                     temp, top_k, top_p, key, cfg, max_top_k)
    return tok, carry, _cache_rows(lats, cfg), None, moe   # [L, 1, bucket, width]


def tail_prefill_step(params, ctx_k, ctx_v, tail, start, last_index, temp,
                      top_k, top_p, key, *, cfg: LatentMoEConfig, tb: int,
                      max_top_k: int):
    """Prefill the unshared tail of a prefix-matched prompt (or one chunk of
    a chunked prefill): the gathered cache rows ``ctx_k [L, 1, C, 1, width]``
    (positions below ``start`` valid) are the context; ``ctx_v`` is None."""
    del ctx_v
    tok, carry, lats, moe = _prefill(
        params, tail, ctx_k[:, :, :, 0, :cfg.latent_dim], start, last_index,
        temp, top_k, top_p, key, cfg, max_top_k)
    tk = lax.dynamic_slice_in_dim(lats[:, 0], start, tb, axis=1)   # [L, tb, latent]
    return tok, carry, _cache_rows(tk[:, None], cfg), None, moe


def decode_step(params, cache: PagedKVCache, table, state, *,
                cfg: LatentMoEConfig, kv_block: int, max_top_k: int,
                monitors: bool = False):
    """One token for every slot (serve/dense.py ``decode_step``'s contract
    without drafts): the latent row of each live slot is written in place
    at its position — dead slots steer to the layer's scratch block — then
    attended absorbed through the table. The last result carries the
    step's expert routes beside the health monitors."""
    S = state.last_tok.shape[0]
    H, kr, vd = cfg.n_heads, cfg.kv_lora_rank, cfg.v_head_dim
    x = params["tok_emb"][state.last_tok]                      # [S, D]
    pos = cache.lengths
    cos, sin = rope_cos_sin(cfg, pos)                          # [S, rope/2]
    scale = softmax_scale(cfg)
    bi, off = pos // kv_block, pos % kv_block
    pid = jnp.where(
        state.live, jnp.take_along_axis(table, bi[:, None], axis=1)[:, 0],
        SCRATCH_BLOCK,
    )

    rest, stacked = split_experts(params["moe_layers"])

    def block(carry, lp, pools, base):
        x, routes_all, i = carry

        def attend(q_nope, q_rope, latent, lp):
            pool = scatter_block_kv(
                pools[0], _cache_rows(latent[:, None], cfg), pid + base, off)
            w_uk, w_uv = absorb(lp, cfg)
            q = jnp.concatenate(
                [jnp.einsum("shn,chn->shc", q_nope, w_uk), q_rope], axis=-1)
            o = latent_decode_attention(
                q, pool, pos + 1, table + base, v_width=kr, scale=scale)
            return jnp.einsum("shc,chv->shv", o, w_uv).reshape(S, H * vd), pool

        x, pool, routes = layer(x, lp, cfg, attend, cos, sin, valid=state.live,
                                experts=(stacked, i))
        if routes is not None:
            routes_all = lax.dynamic_update_slice(routes_all, routes[None], (i, 0))
            i = i + 1
        return (x, routes_all, i), (pool, None, None, None)

    nd = cfg.n_dense_layers
    carry = (x, jnp.zeros((cfg.n_moe_layers, cfg.n_local), jnp.int32), jnp.int32(0))
    for layers, span in ((params["dense_layers"], (0, nd)), (rest, (nd, cfg.n_layers))):
        if span[0] == span[1]:
            continue
        carry, pools = scan_layers_paged(block, carry, layers, cache, span=span)
        cache = cache._replace(k=pools[0])
    x, routes, _ = carry
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)       # [S, V]

    both = jax.vmap(jax.random.split)(state.rng)
    nxt = sample_tokens(
        logits, state.temp, state.top_k, state.top_p, both[:, 0], max_k=max_top_k,
    )
    has_eos = state.eos >= 0
    nxt = jnp.where(state.done & has_eos, state.eos, nxt)
    done = state.done | (has_eos & (nxt == state.eos))
    live = state.live.astype(jnp.int32)
    new_state = state._replace(last_tok=nxt, rng=both[:, 1], done=done)
    aux = health.decode_monitors(logits) if monitors else {}
    aux = {**aux, "moe_routes": routes, "moe_tokens": jnp.sum(live)}
    return PagedKVCache(cache.k, None, cache.lengths + live), new_state, nxt, aux


__all__ = [
    "REFUSED_KNOBS", "SCAN_STATE", "decode_step", "init_params", "prefill_step",
    "tail_prefill_step",
]
