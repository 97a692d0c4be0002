"""KV-cache decoding + generation for the Llama family.

The reference delegates inference entirely (it launches whatever script the
user brings); here generation is part of the model library. TPU-first
choices: the cache is a static-shape ring of [L, B, max_len, H_kv, hd]
buffers updated with dynamic_update_slice (no growing shapes under jit — one
compile for prefill, one for decode), and attention masks by absolute
position.

``generate()`` is a thin convenience wrapper over the serving engine
(tony_tpu.serve.engine): each prompt row becomes one request into a
slot-batched continuous-decoding loop, so the one-off API and the serving
path share one decode step (native-GQA block-cache attention, sort-free
sampling) and parity between them is a test, not a hope (tests/test_serve.py).

Sampling is sort-free: ``lax.top_k`` over a bounded slice replaces the full
``V log V`` descending sort per decode step; nucleus (top-p) truncation runs
over the sorted top-k slice only (when only top-p is set, a bounded default
k — ``DEFAULT_NUCLEUS_K`` — caps the slice; at real vocab sizes the mass
beyond the top 64 logits is negligible, and for V <= k the semantics are
exact).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tony_tpu.models.llama import LlamaConfig, Params, rms_norm, rope_freqs, apply_rope

# bounded top-k slice used for nucleus truncation when no top_k was given:
# the candidate set for top-p sampling (big enough that the excluded tail
# carries negligible probability mass; exact whenever vocab <= this)
DEFAULT_NUCLEUS_K = 64


class KVCache(NamedTuple):
    """Per-layer stacked K/V buffers [L, B, max_len, n_kv_heads, head_dim]."""

    k: jax.Array
    v: jax.Array

    @classmethod
    def create(cls, cfg: LlamaConfig, batch: int, max_len: int = 0) -> "KVCache":
        shape = (
            cfg.n_layers,
            batch,
            max_len or cfg.max_seq_len,
            cfg.n_kv_heads,
            cfg.head_dim,
        )
        return cls(jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))


def _cached_attention(q, k_cache, v_cache, q_pos, cfg: LlamaConfig):
    """q: [B,S,H,hd]; caches [B,max_len,Hkv,hd]; q_pos: [S] absolute."""
    rep = cfg.n_heads // cfg.n_kv_heads
    if rep > 1:
        k_cache = jnp.repeat(k_cache, rep, axis=2)
        v_cache = jnp.repeat(v_cache, rep, axis=2)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k_cache, preferred_element_type=jnp.float32
    ) * scale
    k_pos = jnp.arange(k_cache.shape[1])
    mask = q_pos[:, None] >= k_pos[None, :]  # causal over absolute positions
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v_cache)


def _matmul(h, lp, name):
    return h @ lp[name]


def layer(x, lp, cfg: LlamaConfig, attend, rope, mm=_matmul):
    """One dense decoder layer of the serving path (the counterpart of
    ``models/latent_moe.layer``): norm -> q/k/v -> rope -> ``attend`` ->
    ``wo`` -> residual -> norm -> SwiGLU -> residual, on ``x [..., D]``
    with any leading axes (a prompt ``[B, S]``, one row a slot ``[S]``, or
    ``[S, G]`` drafted positions).

    The body does not know where keys and values live: ``attend(q [...,
    H, hd], k [..., Hkv, hd], v)`` writes the new rows into the caller's
    state and attends it, returning ``(output [..., H, hd], state)`` — a
    contiguous :class:`KVCache` slab in :func:`forward_with_cache`, the
    paged pools through the block table in ``serve/dense.decode_step``.
    ``rope(t)`` rotates ``[..., heads, hd]`` at the caller's positions;
    ``mm(h, lp, name)`` is ``h @ lp[name]`` or the int8 weight pair.
    Returns ``(x', state)``. The training block (``models/llama.py``
    ``transformer_block``, with its remat names and sharding constraints)
    is a separate spelling (ROADMAP D2)."""
    lead, hd = x.shape[:-1], cfg.head_dim
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    # the products stay as stated: fused with the reshape and rope's split the
    # chip's compiler wants the whole weight sliced out of its stack and
    # transposed first (constant_dynamic-slice_fusion.4 + copy.156
    # bf16[1,4096,4096]: PERF.md §6 PR 30, tests/test_tpu_compile.py)
    q, k = lax.optimization_barrier((mm(h, lp, "wq"), mm(h, lp, "wk")))
    q = rope(q.reshape(*lead, cfg.n_heads, hd))
    k = rope(k.reshape(*lead, cfg.n_kv_heads, hd))
    v = mm(h, lp, "wv").reshape(*lead, cfg.n_kv_heads, hd)
    attn, state = attend(q, k, v)
    x = x + mm(attn.reshape(*lead, cfg.n_heads * hd), lp, "wo")
    h2 = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    delta = mm(jax.nn.silu(mm(h2, lp, "w1")) * mm(h2, lp, "w3"), lp, "w2")
    return x + delta, state


def forward_with_cache(
    params: Params,
    tokens: jax.Array,
    cache: KVCache,
    start_pos: jax.Array,
    cfg: LlamaConfig,
    last_only: bool = False,
    last_index: jax.Array | None = None,
) -> tuple[jax.Array, KVCache]:
    """tokens [B,S] starting at absolute position start_pos (traced scalar).

    Returns (logits [B,S,vocab] f32, updated cache). Used for both prefill
    (S = prompt length) and decode (S = 1) — same trace, two compiles.

    ``last_only`` (static) projects only the final position through
    ``lm_head``, returning logits [B,1,vocab]: prefill needs exactly the
    last position to sample from, and the full projection would build a
    [B,S,V] fp32 tensor (at 7B shapes, ~0.5GB for a 2k prompt) just to
    discard all but one row. ``last_index`` (traced scalar) generalises it
    to an arbitrary position — the engine's bucketed prefill pads prompts
    up to a bucket length and needs the logits at the *prompt's* last
    position, not the bucket's.
    """
    B, S = tokens.shape
    x = params["tok_emb"][tokens]
    freqs = rope_freqs(cfg)
    q_pos = start_pos + jnp.arange(S)
    angles = q_pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)

    def block(x, xs):
        lp, k_cache, v_cache = xs

        def attend(q, k, v):
            ks = lax.dynamic_update_slice(k_cache, k, (0, start_pos, 0, 0))
            vs = lax.dynamic_update_slice(v_cache, v, (0, start_pos, 0, 0))
            return _cached_attention(q, ks, vs, q_pos, cfg), (ks, vs)

        return layer(x, lp, cfg, attend, lambda t: apply_rope(t, cos, sin))

    x, (new_k, new_v) = lax.scan(block, x, (params["layers"], cache.k, cache.v))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if last_index is not None:
        x = lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
    elif last_only:
        x = x[:, -1:]
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    return logits, KVCache(new_k, new_v)


def generate(
    params: Params,
    prompt: jax.Array,
    cfg: LlamaConfig,
    *,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 0.0,
    eos_id: int | None = None,
    rng: jax.Array | None = None,
    max_len: int = 0,
    max_top_k: int = 0,
    serve: dict | None = None,
) -> jax.Array:
    """Autoregressive generation. prompt [B,P] -> [B, P+max_new_tokens].

    temperature 0 = greedy; otherwise softmax sampling, optionally top-k
    and/or nucleus (top-p) truncated. ``eos_id`` makes finished rows stick
    at EOS (the output always has max_new_tokens generated positions; rows
    that hit EOS pad with it).

    Implemented as B requests into the serving engine (one slot per row,
    prefill bucket = the exact prompt length, same jitted decode step the
    server runs). Each row gets its own rng stream derived by
    ``jax.random.split(rng, B)`` — row i's tokens depend only on row i's
    key, so the same row submitted alone or in a batch samples identically.

    ``max_top_k`` widens the sampler's bounded candidate slice (default
    ``max(top_k, DEFAULT_NUCLEUS_K)``): top-p-only sampling truncates to
    the top ``max_top_k`` logits before the nucleus cut, so callers who
    need a wider nucleus than the top-64 tail raise it here.

    ``serve`` overrides ServeConfig fields on the underlying engine (e.g.
    ``dict(quant_kv="int8", quant_weights=True)``). This is how quantized
    serving stays a TESTABLE parity surface: a quantized engine can never
    be token-exact against a bf16 reference, but generate() with the same
    overrides runs the identical quantized step — so engine-vs-generate
    parity remains exact equality, quantization and all.
    """
    from tony_tpu.serve.engine import Engine, Request, ServeConfig

    B, P = prompt.shape
    if max_new_tokens <= 0:
        return prompt
    total = P + max_new_tokens
    if rng is None:
        rng = jax.random.key(0)
    keys = jax.random.split(rng, B)

    sv = dict(
        slots=B,
        max_len=max_len or max(total, 1),
        prefill_buckets=(P,),
        max_top_k=max(top_k, max_top_k, DEFAULT_NUCLEUS_K),
    )
    sv.update(serve or {})
    engine = Engine(params, cfg, ServeConfig(**sv))
    prompt_np = np.asarray(prompt)
    ids = [
        engine.submit(Request(
            prompt=prompt_np[i],
            max_new_tokens=max_new_tokens,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            eos_id=eos_id,
            rng=keys[i],
        ))
        for i in range(B)
    ]
    completions = engine.run()
    rows = []
    for i, rid in enumerate(ids):
        toks = list(completions[rid].tokens)
        if len(toks) < max_new_tokens:  # finished at EOS: stick at it
            toks += [eos_id] * (max_new_tokens - len(toks))
        rows.append(np.concatenate([prompt_np[i], np.asarray(toks, np.int32)]))
    return jnp.asarray(np.stack(rows), jnp.int32)


# --- sampling -----------------------------------------------------------------


def _truncated_logits(logits: jax.Array, top_k: int, top_p: float) -> jax.Array:
    """[B,V] logits -> [B,V] with everything outside the top-k / nucleus set
    at -inf. Sort-free: one ``lax.top_k`` over a bounded slice (k, or
    DEFAULT_NUCLEUS_K when only top-p is set) replaces the full-vocab
    descending sort; the nucleus cumsum runs over that slice only.

    This static-parameter form is the draw-for-draw parity surface against
    the legacy sort-based sampler (tests/test_generate.py); the engine's
    per-row array-parameter twin lives in :func:`sample_tokens` — keep
    their truncation semantics in lockstep."""
    V = logits.shape[-1]
    k = top_k if top_k > 0 else DEFAULT_NUCLEUS_K
    k = min(k, V)
    vals, idx = lax.top_k(logits, k)  # [B,k] descending
    if top_p > 0.0:
        # nucleus: keep the smallest prefix of the sorted slice whose
        # cumulative probability reaches top_p (the top token always stays)
        probs = jax.nn.softmax(vals, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs) < top_p
        vals = jnp.where(keep, vals, -jnp.inf)
    out = jnp.full_like(logits, -jnp.inf)
    return out.at[jnp.arange(logits.shape[0])[:, None], idx].set(vals)


def _sample(logits: jax.Array, temperature: float, top_k: int, top_p: float,
            rng: jax.Array) -> jax.Array:
    """logits [B,V] -> token ids [B] (static sampling params)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0 or top_p > 0.0:
        logits = _truncated_logits(logits, top_k, top_p)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def sample_tokens(
    logits: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
    rngs: jax.Array,
    *,
    max_k: int = DEFAULT_NUCLEUS_K,
) -> jax.Array:
    """Per-row sampling for the decode engine: logits [N,V], per-row
    temperature/top_k/top_p arrays [N], per-row rng keys [N] -> tokens [N].

    Rows with temperature <= 0 are greedy; top_k is clamped to the static
    ``max_k`` slice (0 = no top-k: the slice bound still applies when that
    row also sets top_p). Same truncation semantics as :func:`_sample`,
    vectorised over heterogeneous requests sharing one decode step.

    The vocabulary-wide work — the top-k slice, the ``[N, V]`` scatter and
    the categorical draw's noise — runs only where some row samples
    (:func:`samples_any`): a ``lax.cond`` inside the one compiled program,
    whose other branch is the argmax alone. A batch that samples takes the
    same path, row for row, as every batch did before; the rng keys are the
    caller's either way, so a row's tokens never depend on its neighbours.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return lax.cond(
        samples_any(temperature),
        lambda: jnp.where(temperature <= 0.0, greedy, _draw(
            logits, temperature, top_k, top_p, rngs, max_k)),
        lambda: greedy,
    )


def samples_any(temperature: jax.Array) -> jax.Array:
    """The predicate :func:`sample_tokens` branches on: some row of the
    batch samples (``temperature > 0``). The engine returns it beside a
    decode step's tokens (``vocab_sampler_steps``)."""
    return jnp.any(temperature > 0.0)


def _draw(logits, temperature, top_k, top_p, rngs, max_k):
    """Every row's top-k / nucleus draw over the vocabulary ``[N, V]`` ->
    ``[N]``: the wide branch of :func:`sample_tokens`."""
    N, V = logits.shape
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    k = min(max_k, V)
    vals, idx = lax.top_k(scaled, k)  # [N,k] descending
    eff_k = jnp.where(top_k > 0, jnp.minimum(top_k, k), k)
    keep = jnp.arange(k)[None, :] < eff_k[:, None]
    vals = jnp.where(keep, vals, -jnp.inf)
    probs = jax.nn.softmax(vals, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_p = jnp.where(
        top_p[:, None] > 0.0, (cum - probs) < top_p[:, None], True
    )
    vals = jnp.where(keep & keep_p, vals, -jnp.inf)
    truncate = (top_k > 0) | (top_p > 0.0)
    masked = jnp.full_like(scaled, -jnp.inf).at[
        jnp.arange(N)[:, None], idx
    ].set(vals)
    masked = jnp.where(truncate[:, None], masked, scaled)
    return jax.vmap(
        lambda key, row: jax.random.categorical(key, row)
    )(rngs, masked).astype(jnp.int32)


__all__ = [
    "DEFAULT_NUCLEUS_K", "KVCache", "forward_with_cache", "generate",
    "layer", "sample_tokens", "samples_any",
]
