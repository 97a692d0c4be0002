"""KV-cache decode + generation tests: cache path must match full forward."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import llama
from tony_tpu.models.generate import KVCache, forward_with_cache, generate


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    return cfg, params


def test_prefill_matches_forward(setup):
    cfg, params = setup
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    expect = llama.forward(params, tokens, cfg)
    cache = KVCache.create(cfg, 2, 32)
    got, _ = forward_with_cache(params, tokens, cache, jnp.int32(0), cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=1e-4)


def test_prefill_last_only_matches_full_projection(setup):
    """generate()'s prefill path projects ONLY the last position through
    lm_head ([B,1,V] instead of [B,S,V] fp32): same sampled logits, same
    cache, no prompt-sized logits transient."""
    cfg, params = setup
    tokens = jax.random.randint(jax.random.key(8), (2, 16), 0, cfg.vocab_size)
    full, cache_full = forward_with_cache(
        params, tokens, KVCache.create(cfg, 2, 32), jnp.int32(0), cfg
    )
    last, cache_last = forward_with_cache(
        params, tokens, KVCache.create(cfg, 2, 32), jnp.int32(0), cfg,
        last_only=True,
    )
    assert last.shape == (2, 1, cfg.vocab_size)
    np.testing.assert_allclose(
        np.asarray(last[:, 0]), np.asarray(full[:, -1]), atol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(cache_last.k), np.asarray(cache_full.k))


def test_incremental_decode_matches_forward(setup):
    """Logits from one-token-at-a-time decoding must equal the full forward
    pass at every position — the KV cache is exact, not approximate."""
    cfg, params = setup
    tokens = jax.random.randint(jax.random.key(2), (1, 12), 0, cfg.vocab_size)
    expect = llama.forward(params, tokens, cfg)

    cache = KVCache.create(cfg, 1, 16)
    logits_steps = []
    for i in range(tokens.shape[1]):
        step_logits, cache = forward_with_cache(
            params, tokens[:, i : i + 1], cache, jnp.int32(i), cfg
        )
        logits_steps.append(step_logits[:, 0])
    got = jnp.stack(logits_steps, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=2e-4)


def test_greedy_generation_deterministic_and_shaped(setup):
    cfg, params = setup
    prompt = jax.random.randint(jax.random.key(3), (2, 5), 0, cfg.vocab_size)
    out1 = generate(params, prompt, cfg, max_new_tokens=8)
    out2 = generate(params, prompt, cfg, max_new_tokens=8)
    assert out1.shape == (2, 13)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    np.testing.assert_array_equal(np.asarray(out1[:, :5]), np.asarray(prompt))


def test_greedy_matches_forward_argmax(setup):
    """First generated token == argmax of the full-forward last-position
    logits (cache prefill consistency at the generation boundary)."""
    cfg, params = setup
    prompt = jax.random.randint(jax.random.key(4), (2, 7), 0, cfg.vocab_size)
    out = generate(params, prompt, cfg, max_new_tokens=1)
    expect = jnp.argmax(llama.forward(params, prompt, cfg)[:, -1], axis=-1)
    np.testing.assert_array_equal(np.asarray(out[:, -1]), np.asarray(expect))


def test_sampled_generation_respects_top_k(setup):
    cfg, params = setup
    prompt = jax.random.randint(jax.random.key(5), (1, 4), 0, cfg.vocab_size)
    out = generate(
        params, prompt, cfg, max_new_tokens=6, temperature=0.8, top_k=1,
        rng=jax.random.key(9),
    )
    # top_k=1 sampling degenerates to greedy
    greedy = generate(params, prompt, cfg, max_new_tokens=6)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(greedy))


def test_top_p_restricts_to_nucleus():
    """top_p sampling only ever emits tokens from the smallest prefix whose
    cumulative probability reaches p."""
    from tony_tpu.models.generate import _sample

    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
    seen = set()
    for i in range(64):
        tok = _sample(logits, temperature=1.0, top_k=0, top_p=0.6,
                      rng=jax.random.key(i))
        seen.add(int(tok[0]))
    # 0.5 alone < 0.6, so token 1 joins the nucleus; 2 and 3 never can
    assert seen <= {0, 1}
    assert 0 in seen


def _legacy_sample(logits, temperature, top_k, top_p, rng):
    """The pre-round-9 sampler: full-vocab descending jnp.sort per call
    (V log V per decode step) — kept verbatim as the value oracle for the
    sort-free lax.top_k rewrite."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0 or top_p > 0.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        if top_k > 0:
            kth = sorted_logits[:, top_k - 1][:, None]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
            sorted_logits = jnp.where(
                sorted_logits < kth, -jnp.inf, sorted_logits
            )
        if top_p > 0.0:
            probs = jax.nn.softmax(sorted_logits, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            keep_sorted = (cum - probs) < top_p
            cutoff = jnp.min(
                jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1
            )[:, None]
            logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def test_sample_matches_legacy_sort_impl_topk():
    """The sort-free sampler (lax.top_k + scatter-back) draws EXACTLY the
    legacy full-sort sampler's tokens for any top_k config: identical
    masked logits, identical categorical call, same rng."""
    from tony_tpu.models.generate import _sample

    logits = jax.random.normal(jax.random.key(0), (8, 500)) * 3.0
    for temperature, top_k, top_p in [
        (0.7, 10, 0.0), (1.0, 1, 0.0), (1.3, 40, 0.9), (0.5, 499, 0.3),
    ]:
        for seed in range(5):
            rng = jax.random.key(seed)
            want = _legacy_sample(logits, temperature, top_k, top_p, rng)
            got = _sample(logits, temperature, top_k, top_p, rng)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sample_matches_legacy_sort_impl_top_p_only():
    """top-p without top_k uses the bounded default-k slice; for vocab <=
    DEFAULT_NUCLEUS_K the slice is the whole sorted vocab, so the nucleus
    cutoff — and the draws — match the legacy sampler exactly."""
    from tony_tpu.models.generate import DEFAULT_NUCLEUS_K, _sample

    V = DEFAULT_NUCLEUS_K
    logits = jax.random.normal(jax.random.key(1), (6, V)) * 2.0
    for top_p in (0.3, 0.7, 0.95):
        for seed in range(5):
            rng = jax.random.key(100 + seed)
            want = _legacy_sample(logits, 0.9, 0, top_p, rng)
            got = _sample(logits, 0.9, 0, top_p, rng)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _sample_tokens_before(logits, temperature, top_k, top_p, rngs, *, max_k=64):
    """``sample_tokens`` as it was before it branched (PR 35): every row's
    top-k slice, scatter and draw, then the argmax where a row is greedy.
    The oracle the branching sampler has to equal token for token."""
    N, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    k = min(max_k, V)
    vals, idx = jax.lax.top_k(scaled, k)
    eff_k = jnp.where(top_k > 0, jnp.minimum(top_k, k), k)
    keep = jnp.arange(k)[None, :] < eff_k[:, None]
    vals = jnp.where(keep, vals, -jnp.inf)
    probs = jax.nn.softmax(vals, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_p = jnp.where(top_p[:, None] > 0.0, (cum - probs) < top_p[:, None], True)
    vals = jnp.where(keep & keep_p, vals, -jnp.inf)
    truncate = (top_k > 0) | (top_p > 0.0)
    masked = jnp.full_like(scaled, -jnp.inf).at[jnp.arange(N)[:, None], idx].set(vals)
    masked = jnp.where(truncate[:, None], masked, scaled)
    sampled = jax.vmap(
        lambda key, row: jax.random.categorical(key, row))(rngs, masked).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def _rows(n, seed):
    return jax.random.key_data(jax.random.split(jax.random.key(seed), n))


@functools.lru_cache(maxsize=None)
def _jitted():
    """Both samplers compiled once for the cases that share their shapes."""
    from tony_tpu.models.generate import sample_tokens

    return jax.jit(sample_tokens), jax.jit(_sample_tokens_before)


# (temperature, top_k, top_p) of the rows that sample; "one" puts a single
# such row among greedy rows, "all" makes every row sample
_SAMPLED = [(1.0, 0, 0.0), (0.8, 1, 0.0), (0.7, 5, 0.0), (1.2, 0, 0.5),
            (0.9, 5, 0.9), (0.3, 70, 0.95)]


@pytest.mark.parametrize("case", ["heterogeneous", "greedy", *(
    f"{layout}-{i}" for layout in ("one", "all") for i in range(len(_SAMPLED)))])
def test_sample_tokens_vectorises_heterogeneous_rows(case):
    """The engine's per-row sampler. ``heterogeneous``: greedy rows equal
    argmax regardless of key; top_k=1 rows are deterministic; truncated rows
    only emit admitted tokens. ``greedy``: a batch with no sampling row is
    the argmax, bit for bit, ties included (the first index). The rest: a
    batch with one sampling row among greedy rows, or with every row
    sampling, gives the tokens the unbranched sampler gave for the same keys."""
    sample, before = _jitted()
    if case == "heterogeneous":
        logits = jax.random.normal(jax.random.key(2), (4, 64)) * 2.0
        rngs = _rows(4, 3)
        temp = jnp.asarray([0.0, 1.0, 0.8, 1.2], jnp.float32)
        top_k = jnp.asarray([0, 1, 3, 0], jnp.int32)
        top_p = jnp.asarray([0.0, 0.0, 0.0, 0.5], jnp.float32)
        toks = sample(logits, temp, top_k, top_p, rngs)
        assert int(toks[0]) == int(jnp.argmax(logits[0]))
        assert int(toks[1]) == int(jnp.argmax(logits[1]))  # top_k=1 == greedy
        top3 = set(np.asarray(jax.lax.top_k(logits[2], 3)[1]))
        assert int(toks[2]) in top3
        return
    N, V = 8, 300
    # few distinct values a row: ties for the maximum in most rows
    logits = jax.random.randint(jax.random.key(5), (N, V), -4, 4).astype(jnp.float32)
    zeros_i = jnp.zeros((N,), jnp.int32)
    zeros_f = jnp.zeros((N,), jnp.float32)
    if case == "greedy":
        for seed in range(3):
            toks = sample(logits, zeros_f, zeros_i + 5, zeros_f + 0.9, _rows(N, seed))
            np.testing.assert_array_equal(np.asarray(toks), np.argmax(np.asarray(logits), -1))
        return
    layout, i = case.split("-")
    t, k, p = _SAMPLED[int(i)]
    rows = np.arange(N) == 3 if layout == "one" else np.ones(N, bool)
    temp = jnp.where(rows, t, 0.0).astype(jnp.float32)
    top_k = jnp.where(rows, k, 0).astype(jnp.int32)
    top_p = jnp.where(rows, p, 0.0).astype(jnp.float32)
    logits = logits + jax.random.normal(jax.random.key(6), (N, V))
    for seed in range(4):
        rngs = _rows(N, 10 + seed)
        got = sample(logits, temp, top_k, top_p, rngs)
        want = before(logits, temp, top_k, top_p, rngs)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _primitives(jaxpr):
    """The names of every primitive in ``jaxpr``, its sub-jaxprs included."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    names |= _primitives(inner)
    return names


def test_a_greedy_batch_branches_past_the_vocabulary_wide_draw():
    """The sampler holds a ``cond``; its greedy branch, and everything
    outside the cond, holds no top-k and no rng op: only a batch that
    samples runs the slice, the scatter and the draw (read off the jaxpr,
    not a clock)."""
    from tony_tpu.models.generate import sample_tokens

    N, V = 4, 128
    z = jnp.zeros((N,), jnp.float32)
    closed = jax.make_jaxpr(sample_tokens)(
        jnp.zeros((N, V)), z, jnp.zeros((N,), jnp.int32), z, _rows(N, 0))
    conds = [e for e in closed.jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    greedy, wide = (_primitives(b.jaxpr) for b in conds[0].params["branches"])
    outside = {e.primitive.name for e in closed.jaxpr.eqns} - {"cond"}

    def wide_ops(names):
        return {n for n in names if "top_k" in n or "random" in n or "threefry" in n}

    assert not wide_ops(greedy) and not wide_ops(outside), (greedy, outside)
    assert "top_k" in wide and wide_ops(wide) - {"top_k"}


def test_eos_rows_stick():
    """Rows that emit eos keep emitting it (static-shape early stop)."""
    from tony_tpu.models.generate import generate
    from tony_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig.tiny()
    params = init_params(jax.random.key(0), cfg)
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    # greedy with eos_id equal to whatever the first generated token is:
    # every subsequent token must then repeat it
    first = generate(params, prompt, cfg, max_new_tokens=1)[0, -1]
    out = generate(params, prompt, cfg, max_new_tokens=6, eos_id=int(first))
    tail = np.asarray(out[0, 3:])
    assert (tail == int(first)).all(), tail
