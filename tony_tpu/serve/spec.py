"""Speculative decoding: model-free drafts, one-step batched verification.

Decode emits one token per forward because each token conditions on the
last — but a decode forward is memory-bound, so verifying G positions in
one step costs barely more wall time than verifying one. Speculative
decoding (Leviathan et al., arXiv:2211.17192) exploits that: a cheap
draft proposes the next k tokens, the target model scores all of them in
ONE widened forward, and a rejection rule keeps exactly the prefix the
target itself would have produced — output is *distributionally
unchanged*.

This engine needs no draft model. Both draft sources are deterministic
host-side lookups (pure python, GL001 — no device work on the draft
path):

- **radix-trie longest extension** (serve/prefix.py): the prefix store
  is a trie over every sequence the engine has served. If a slot's
  context (prompt + emitted tokens) follows a stored path, the path's
  continuation is the draft — repeated or templated traffic drafts at
  near-100% accept (the SGLang-lineage observation that the radix cache
  doubles as a predictor);
- **n-gram prompt-lookup** (the "prompt lookup decoding" trick): the
  longest trailing n-gram of the slot's own context that occurred
  earlier in it predicts the tokens that followed that earlier
  occurrence — summarisation/extraction workloads copy their input.

Verification is exact, not approximate. For a *deterministic* draft the
Leviathan accept/resample rule collapses to something stronger than
distributional equality: unroll the engine's per-step rng-split chain
over the G = k+1 scored positions (split -> sample with key 0 -> carry
key 1, exactly what the 1-wide step does once), sample the target at
every position, and emit the longest prefix where the target's own
sample agrees with the draft, plus the first disagreeing sample as the
correction/bonus token. Every emitted token is the token the
autoregressive engine would have sampled with the same keys — output is
**draw-for-draw identical** to spec-off decoding (greedy and sampled;
tests/test_spec.py), not merely same-distribution.

Rollback is free by construction: the verify step writes position
``pos + j``'s K/V from fed token j of ``[last_tok, d_1..d_k]``, and the
accepted prefix covers exactly the positions the advanced ``lengths``
expose — rejected positions' K/V lie beyond every row's length, masked
out of attention, and overwritten by later steps (serve/dense.py
``decode_step`` with drafts).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

DRAFT_SOURCES = ("auto", "prefix", "ngram")

# n-gram prompt-lookup window: try the longest trailing n-gram first
_NGRAM_MAX = 3
_NGRAM_MIN = 1


def ngram_propose(ctx: Sequence[int], max_k: int,
                  max_n: int = _NGRAM_MAX, min_n: int = _NGRAM_MIN) -> list[int]:
    """Prompt-lookup draft: find the most recent earlier occurrence of the
    context's trailing n-gram (longest n first) and propose the tokens
    that followed it. Pure host-side python on the slot's own context —
    no model, no device work."""
    L = len(ctx)
    if max_k <= 0 or L < min_n + 1:
        return []
    for n in range(min(max_n, L - 1), min_n - 1, -1):
        suffix = list(ctx[L - n:])
        for start in range(L - n - 1, -1, -1):
            if list(ctx[start:start + n]) == suffix:
                lo = start + n
                return [int(t) for t in ctx[lo:min(lo + max_k, L)]]
    return []


def propose_drafts(ctx: Sequence[int], store, max_k: int,
                   source: str = "auto") -> list[int]:
    """Draft up to ``max_k`` tokens for a slot whose context is ``ctx``
    (prompt + every emitted token, the next input token last). Tries the
    radix store's ``longest_extension`` first (cross-request knowledge),
    then the slot's own n-gram lookup — ``source`` pins one of them.
    Host-side only (GL001): the device never sees a draft until the
    engine uploads the per-step ``[S, k]`` draft batch."""
    if max_k <= 0:
        return []
    out: list[int] = []
    if source in ("auto", "prefix") and store is not None:
        out = store.longest_extension(ctx, max_k)
    if not out and source in ("auto", "ngram"):
        out = ngram_propose(ctx, max_k)
    return out[:max_k]


def verify_and_accept(logits: jax.Array, drafts: jax.Array,
                      draft_len: jax.Array, state, *, max_top_k: int):
    """The rejection rule, as the unrolled rng chain (module docstring).

    ``logits [S, G, V]`` are the target's distributions at the G = k+1
    fed positions; ``drafts [S, k]`` the proposed tokens (``draft_len
    [S]`` of them real per row); ``state`` the engine's ``_SlotState``.
    Samples the target at every position with the exact per-step key
    chain the 1-wide step would burn, then accepts the longest
    draft-agreeing prefix plus one correction/bonus token. EOS semantics
    mirror the 1-wide step: an emitted eos truncates emission and marks
    the row done; a row already done sticks at eos.

    Returns ``(toks [S, G], n_emit [S], n_acc [S], last_tok [S],
    new_rng [S, 2], done [S])`` — per row, the first ``n_emit`` of
    ``toks`` are the emitted tokens, ``last_tok`` feeds the next step,
    and ``new_rng`` is the carry after exactly ``n_emit`` splits (the
    autoregressive stream position)."""
    from tony_tpu.models.generate import sample_tokens

    S, G, _V = logits.shape
    has_eos = state.eos >= 0
    carry = state.rng
    toks, carries = [], [carry]
    for g in range(G):
        both = jax.vmap(jax.random.split)(carry)               # [S, 2, 2]
        toks.append(sample_tokens(
            logits[:, g], state.temp, state.top_k, state.top_p, both[:, 0],
            max_k=max_top_k,
        ))
        carry = both[:, 1]
        carries.append(carry)
    T = jnp.stack(toks, axis=1)                                # [S, G]
    R = jnp.stack(carries, axis=1)                             # [S, G+1, 2]
    # a row that already emitted eos sticks at eos (1-wide step rule)
    T = jnp.where((state.done & has_eos)[:, None], state.eos[:, None], T)
    if G > 1:
        gi = jnp.arange(G - 1, dtype=jnp.int32)[None, :]
        agree = (T[:, :G - 1] == drafts) & (gi < draft_len[:, None])
        n_acc = jnp.sum(jnp.cumprod(agree.astype(jnp.int32), axis=1), axis=1)
    else:
        n_acc = jnp.zeros((S,), jnp.int32)
    n_emit = n_acc + 1                            # accepted drafts + bonus
    # eos truncation: emission stops AT the first emitted eos, inclusive
    is_eos = has_eos[:, None] & (T == state.eos[:, None])
    emitted = jnp.arange(G, dtype=jnp.int32)[None, :] < n_emit[:, None]
    eos_hit = is_eos & emitted
    any_eos = jnp.any(eos_hit, axis=1)
    first_eos = jnp.argmax(eos_hit, axis=1).astype(jnp.int32)
    n_emit = jnp.where(any_eos, first_eos + 1, n_emit).astype(jnp.int32)
    n_acc = jnp.minimum(n_acc, n_emit - 1)
    done = state.done | any_eos
    last_tok = jnp.take_along_axis(T, (n_emit - 1)[:, None], axis=1)[:, 0]
    new_rng = jnp.take_along_axis(R, n_emit[:, None, None], axis=1)[:, 0]
    return T, n_emit, n_acc, last_tok, new_rng, done


__all__ = [
    "DRAFT_SOURCES",
    "ngram_propose",
    "propose_drafts",
    "verify_and_accept",
]
