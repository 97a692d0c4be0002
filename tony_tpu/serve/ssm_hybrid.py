"""The serving steps of the selective state-space / attention hybrid
(models/ssm_hybrid.py): what ``serve/engine.py``'s ``jit_serve_prefill``,
``jit_serve_tail_prefill`` and ``jit_serve_decode`` run when the engine's
model is an :class:`SSMHybridConfig`. Same names, same host loop, same block
pool and tables as the other families (docs/SERVE.md "Model families" has the
contract); a slot keeps TWO kinds of state, as serve/shortconv.py's does, and
here the second is most of a slot:

- K/V blocks for the ATTENTION layers only (2 of 28 at the published depth):
  pools ``[La, P, Hkv, block, hd]``, one 128-wide K/V head filling a cache
  row. Decode attends through the one paged kernel; nothing rotates — this
  family's attention has no position term;
- the Mamba layers' recurrent state (the configuration's ``slot_state``):
  ``cache.slot_state [Lm * (N + K - 1), S, E]`` float32 — a layer's ``N``
  rows of ``h`` and under them the last ``K - 1`` inputs of the causal
  convolution, each row ``[S, E]``: slots on the sublanes, channels on the
  lanes (10 MB a slot at the published widths, 1.3 GB at 128 slots).
  Prefill is a SCAN over the prompt (``ops/selective_scan.py``) and returns
  the state at the prompt's TRUE last position under ``aux['slot_state']``;
  the engine writes it into the slot inside ``jit_serve_scatter``; a tail or
  chunked prefill starts from the state its predecessor left; decode reads
  and rewrites every live slot's rows in place, layer by layer, through
  dynamic slices of the one carried buffer, and a dead slot's rows stand.

``SCAN_STATE`` (every steps module states it, docs/SERVE.md item 5) says that
this family's per-slot state is such a recurrence: ``stats_snapshot`` then
gives ``scan_tokens`` and ``state_stream_bytes`` from the counters it keeps.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.models.generate import sample_tokens
from tony_tpu.models.layer_walk import walk_layers
from tony_tpu.models.ssm_hybrid import (
    SSMHybridConfig, conv_token, decay_of, forward_states, head, init_params, layer,
    ssm_inputs,
)
from tony_tpu.obs import health
from tony_tpu.ops.decode_attention import decode_attention
from tony_tpu.ops.selective_scan import selective_step
from tony_tpu.serve.cache import SCRATCH_BLOCK, PagedKVCache, scatter_block_kv

# prefill scans the prompt into the per-slot state and every decode step reads
# and rewrites it whole (docs/SERVE.md item 5)
SCAN_STATE = True

# What this family does not take yet: ``{knob: (the one value it takes, why)}``
# (serve/latent.py's table says what a refusal looks like).
REFUSED_KNOBS = {
    "prefix": (False, "a prefix hit has no recurrent state to start from: the store "
                      "keeps K/V blocks, not the state at a block boundary"),
    "quant_kv": ("", "the quantized pool's scale rows are not threaded through this "
                     "family's layer walk"),
    "quant_weights": (False, "the int8 decode matmuls name the dense decoder's seven matrices"),
    "spec": (False, "a rejected draft would have to roll the recurrent state back"),
    "decode_impl": ("scan", "no int8 matmul whose form it would pick"),
    "block_handoff": (False, "gang block export/adopt ships (k, v) pools without the "
                             "slot's recurrent state"),
}


def _head_major(rows):
    """A prompt's rows ``[La, W, Hkv, hd]`` as the engine scatters them:
    head-major ``[La, Hkv, W, hd]``."""
    return rows.transpose(0, 2, 1, 3)


def _prefill(params, tokens, ctx_k, ctx_v, state, start, last_index, temp, top_k,
             top_p, key, cfg: SSMHybridConfig, max_top_k: int):
    """``tokens [1, W]`` from position ``start`` after the context and the
    state handed in (None: a whole prompt): ``(first token, rng carry, K, V
    [La, 1, C, Hkv, hd], aux)``; rows past ``last_index`` are padding."""
    x, ks, vs, state = forward_states(
        params, tokens, ctx_k, ctx_v, state, start, last_index, cfg)
    logits = head(params, lax.dynamic_slice_in_dim(x, last_index, 1, axis=1), cfg)
    use, carry = jax.random.split(key)
    tok = sample_tokens(
        logits[:, 0], temp[None], top_k[None], top_p[None], use[None],
        max_k=max_top_k,
    )[0]
    return tok, carry, ks, vs, {"slot_state": state[:, 0].reshape(-1, cfg.d_inner)}


def prefill_step(params, prompt, last_index, temp, top_k, top_p, key, *,
                 cfg: SSMHybridConfig, bucket: int, max_top_k: int):
    """Whole-prompt prefill of one padded bucket: ``(tok, carry, K rows, V
    rows, aux)``, the rows head-major ``[La, Hkv, bucket, hd]``."""
    del bucket      # the prompt's padded width
    tok, carry, ks, vs, aux = _prefill(
        params, prompt, None, None, None, jnp.int32(0), last_index, temp, top_k,
        top_p, key, cfg, max_top_k)
    return tok, carry, _head_major(ks[:, 0]), _head_major(vs[:, 0]), aux


def tail_prefill_step(params, ctx_k, ctx_v, tail, start, last_index, temp,
                      top_k, top_p, key, *, cfg: SSMHybridConfig, tb: int,
                      max_top_k: int, slot_state):
    """One chunk of a chunked prefill: the gathered K/V ``[La, 1, C, Hkv,
    hd]`` (positions below ``start`` valid) are the attention layers' context
    and ``slot_state [Lm * (N + K - 1), E]`` is the slot's recurrent state
    as the chunk before left it."""
    state = slot_state.reshape(cfg.n_mamba_layers, 1, cfg.state_rows, cfg.d_inner)
    tok, carry, ks, vs, aux = _prefill(
        params, tail, ctx_k, ctx_v, state, start, last_index, temp,
        top_k, top_p, key, cfg, max_top_k)
    tk = lax.dynamic_slice_in_dim(ks[:, 0], start, tb, axis=1)     # [La, tb, Hkv, hd]
    tv = lax.dynamic_slice_in_dim(vs[:, 0], start, tb, axis=1)
    return tok, carry, _head_major(tk), _head_major(tv), aux


def decode_step(params, cache: PagedKVCache, table, state, *,
                cfg: SSMHybridConfig, kv_block: int, max_top_k: int,
                monitors: bool = False):
    """One token for every slot (serve/dense.py ``decode_step``'s contract
    without drafts). An attention layer writes each live slot's K/V row in
    place at its position — dead slots steer to the layer's scratch block —
    and attends through the table; a Mamba layer reads the live slots' ``h``
    and convolution tail and rewrites them, a dead slot's rows standing as
    they are."""
    La, P = cache.k.shape[:2]
    N, K = cfg.d_state, cfg.d_conv
    live = state.live
    x = params["tok_emb"][state.last_tok]                      # [S, D]
    pos = cache.lengths
    bi, off = pos // kv_block, pos % kv_block
    pid = jnp.where(
        live, jnp.take_along_axis(table, bi[:, None], axis=1)[:, 0], SCRATCH_BLOCK)
    # the pools as [La * P, ...] (serve/cache.scan_layers_paged says why):
    # attention layer ``oi`` reads and writes blocks [oi * P, (oi + 1) * P)
    flat = [a.reshape(La * P, *a.shape[2:]) for a in (cache.k, cache.v)]

    def step(carry, op, ff, oi, fi, experts):
        del fi, experts
        x, k_pool, v_pool, ssm = carry
        if "w_in" in op:
            # two reads of the layer's rows and ONE write: a second update that
            # read the buffer after the first would make the compiler copy it
            row0 = oi * cfg.state_rows
            h_old = lax.dynamic_slice_in_dim(ssm, row0, N, axis=0)              # [N, S, E]
            tail_old = lax.dynamic_slice_in_dim(ssm, row0 + N, K - 1, axis=0)

            def keep(u, z, op):
                c, tail = conv_token(u, op, tail_old)
                delta, b, cc = ssm_inputs(c, op, cfg)
                # a dead slot's step size is 0: its ``h`` stands (no select
                # over the state, which would read it a second time)
                delta = jnp.where(live[:, None], delta, 0.0)
                y, h = selective_step(c, delta, b, cc, z, decay_of(op),
                                      op["d_skip"].astype(jnp.float32), h_old)
                tail = jnp.where(live[None, :, None], tail.astype(h.dtype), tail_old)
                return y, jnp.concatenate([h, tail], axis=0)
        else:
            def keep(q, k, v):
                base = oi * P
                kp = scatter_block_kv(k_pool, k, pid + base, off)
                vp = scatter_block_kv(v_pool, v, pid + base, off)
                o = decode_attention(q, kp, vp, pos + 1, tables=table + base,
                                     block=kv_block, scale=cfg.head_dim ** -0.5)
                return o, (kp, vp)
        x, new = layer(x, op, ff, cfg, keep)
        if "w_in" in op:
            ssm = lax.dynamic_update_slice_in_dim(ssm, new.astype(ssm.dtype), row0, axis=0)
        else:
            k_pool, v_pool = new
        return x, k_pool, v_pool, ssm

    x, k_pool, v_pool, ssm = walk_layers(step, (x, *flat, cache.slot_state), params, cfg)
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
    new_cache = cache._replace(
        k=k_pool.reshape(cache.k.shape), v=v_pool.reshape(cache.v.shape),
        lengths=pos + live.astype(jnp.int32), slot_state=ssm)
    return new_cache, new_state, nxt, aux


__all__ = [
    "REFUSED_KNOBS", "SCAN_STATE", "decode_step", "init_params", "prefill_step",
    "tail_prefill_step",
]
