"""Speculative decoding (serve/spec.py): drafts, the rejection rule, the
G-query decode kernels, and engine parity.

The load-bearing claim is *draw-for-draw identity*: with deterministic
drafts the engine's rejection rule emits exactly the tokens autoregressive
decoding would sample with the same per-slot rng chain — so every test
here reduces to "spec on == spec off", greedy and sampled, with the
prefix store live, through both decode kernels.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import llama
from tony_tpu.models.generate import generate, sample_tokens
from tony_tpu.ops.decode_attention import (
    decode_attention, reference_decode_attention,
)
from tony_tpu.serve import Engine, Request, ServeConfig
from tony_tpu.serve.cache import SCRATCH_BLOCK, blocks_for, scatter_block_kv
from tony_tpu.serve.engine import _SlotState
from tony_tpu.serve.prefix import PrefixStore
from tony_tpu.serve.spec import ngram_propose, propose_drafts, verify_and_accept


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    return cfg, params


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32) for n in lengths]


# --- draft sources ------------------------------------------------------------


def _store(block=4):
    st = PrefixStore(block=block, block_bytes=1)
    return st


def test_longest_extension_walks_stored_path():
    st = _store()
    seq = list(range(100, 112))  # 3 full blocks of 4
    st.insert(seq, [1, 2, 3], retain=lambda pid: None)
    # context ending on a block boundary: the extension is the next chunks
    assert st.longest_extension(seq[:4], 8) == seq[4:12]
    assert st.longest_extension(seq[:8], 2) == seq[8:10]  # max_k truncates
    # full stored path: nothing beyond it
    assert st.longest_extension(seq, 4) == []


def test_longest_extension_mid_block():
    """A context ending mid-block extends with the remainder of the
    partially-entered chunk, then onward along the tree — and a mid-block
    extension END (no children) returns the short remainder, not a padded
    or truncated-to-zero draft."""
    st = _store()
    seq = list(range(100, 112))
    st.insert(seq, [1, 2, 3], retain=lambda pid: None)
    # ctx ends 2 tokens into block 1: remainder of that chunk + block 2
    assert st.longest_extension(seq[:6], 8) == seq[6:12]
    # ctx ends 1 token into the LAST block: the extension is the chunk's
    # 3-token remainder and nothing more — the mid-block end case
    assert st.longest_extension(seq[:9], 8) == seq[9:12]
    assert st.longest_extension(seq[:11], 8) == seq[11:12]


def test_longest_extension_unknown_context_is_empty():
    st = _store()
    seq = list(range(100, 112))
    st.insert(seq, [1, 2, 3], retain=lambda pid: None)
    assert st.longest_extension([1, 2, 3], 4) == []          # off-tree
    assert st.longest_extension(seq[:5] + [0], 4) == []      # diverges
    assert st.longest_extension(seq + [0], 4) == []          # past the path
    assert st.longest_extension(seq[:4], 0) == []            # k=0


def test_longest_extension_prefers_hotter_children():
    st = _store(block=2)
    st.insert([1, 2, 3, 4], [1, 2], retain=lambda pid: None)
    st.insert([1, 2, 9, 9], [1, 3], retain=lambda pid: None)
    # touch the [3, 4] branch so it outranks [9, 9] on hits
    st.match([1, 2, 3, 4], limit=4)
    assert st.longest_extension([1, 2], 2) == [3, 4]


def test_longest_extension_is_read_only():
    """Drafting must not perturb eviction order or hit-rate accounting."""
    st = _store()
    seq = list(range(100, 112))
    st.insert(seq, [1, 2, 3], retain=lambda pid: None)
    before = (st._clock, st.hit_tokens, st.prompt_tokens)
    st.longest_extension(seq[:6], 8)
    assert (st._clock, st.hit_tokens, st.prompt_tokens) == before


def test_ngram_propose_prompt_lookup():
    ctx = [5, 6, 7, 1, 2, 3, 9, 5, 6, 7]
    # trailing [5, 6, 7] occurred at the start: propose what followed it
    assert ngram_propose(ctx, 4) == [1, 2, 3, 9]
    assert ngram_propose(ctx, 2) == [1, 2]
    # most RECENT earlier occurrence wins
    ctx2 = [4, 8, 1, 4, 8, 2, 4, 8]
    assert ngram_propose(ctx2, 1) == [2]
    # no earlier occurrence of any trailing n-gram -> no draft
    assert ngram_propose([1, 2, 3, 4], 4) == []
    assert ngram_propose([1, 2], 0) == []


def test_propose_drafts_source_pinning():
    st = _store()
    seq = list(range(100, 112))
    st.insert(seq, [1, 2, 3], retain=lambda pid: None)
    ctx = seq[:6]
    assert propose_drafts(ctx, st, 4, "prefix") == seq[6:10]
    assert propose_drafts(ctx, st, 4, "auto") == seq[6:10]
    # ngram-only ignores the store (ctx has no self-repeats -> empty)
    assert propose_drafts(ctx, st, 4, "ngram") == []
    # auto falls back to ngram when the store has nothing
    rep = [3, 4, 5, 3, 4]
    assert propose_drafts(rep, st, 2, "auto") == [5, 3]
    assert propose_drafts(rep, None, 2, "auto") == [5, 3]


# --- the rejection rule -------------------------------------------------------


def _mk_state(S, rngs, temp=0.0, eos=-1, done=False):
    return _SlotState(
        last_tok=jnp.zeros((S,), jnp.int32),
        rng=jnp.asarray(rngs, jnp.uint32),
        temp=jnp.full((S,), temp, jnp.float32),
        top_k=jnp.zeros((S,), jnp.int32),
        top_p=jnp.zeros((S,), jnp.float32),
        eos=jnp.full((S,), eos, jnp.int32),
        done=jnp.full((S,), done, bool),
        live=jnp.ones((S,), bool),
    )


def _reference_chain(logits, drafts, draft_len, state, max_top_k):
    """Per-row pure-python reference: run the 1-wide step's rng chain
    (split -> sample with key 0 -> carry key 1) position by position,
    stopping at the first draft disagreement or emitted eos — exactly
    what autoregressive decoding would emit across these G steps.
    ``drafts=None`` free-runs the chain (every position "agrees")."""
    S, G, _ = logits.shape
    out = []
    for s in range(S):
        carry = state.rng[s]
        emitted = []
        for g in range(G):
            both = jax.random.split(carry)
            t = int(sample_tokens(
                logits[s:s + 1, g], state.temp[s:s + 1], state.top_k[s:s + 1],
                state.top_p[s:s + 1], both[0][None], max_k=max_top_k,
            )[0])
            carry = both[1]
            emitted.append(t)
            if int(state.eos[s]) >= 0 and t == int(state.eos[s]):
                break
            if drafts is None:
                continue
            if g < G - 1 and g < int(draft_len[s]) and t == int(drafts[s, g]):
                continue
            break
        out.append((emitted, np.asarray(carry)))
    return out


@pytest.mark.parametrize("temp", [0.0, 0.9])
def test_verify_and_accept_matches_reference_chain(temp):
    S, G, V = 4, 5, 32
    ks = jax.random.split(jax.random.key(3), 2)
    logits = jax.random.normal(ks[0], (S, G, V), jnp.float32) * 3
    state = _mk_state(S, np.arange(S * 2).reshape(S, 2) + 1, temp=temp)
    # row 0: drafts that agree with the target everywhere (accept all);
    # row 1: garbage drafts (accept none); rows 2/3: random + short
    free = _reference_chain(logits, None, None, state, 64)
    drafts = np.full((S, G - 1), V + 5, np.int32)
    drafts[0] = free[0][0][:G - 1]
    drafts[2] = np.asarray(jax.random.randint(ks[1], (G - 1,), 0, V))
    drafts[3] = drafts[2]
    draft_len = np.asarray([G - 1, G - 1, G - 1, 2], np.int32)
    drafts_j, dlen_j = jnp.asarray(drafts), jnp.asarray(draft_len)

    T, n_emit, n_acc, last_tok, new_rng, done = verify_and_accept(
        logits, drafts_j, dlen_j, state, max_top_k=64,
    )
    ref = _reference_chain(logits, drafts, draft_len, state, 64)
    for s, (emitted, carry) in enumerate(ref):
        n = int(n_emit[s])
        assert n == len(emitted), s
        assert [int(t) for t in T[s, :n]] == emitted, s
        assert int(last_tok[s]) == emitted[-1], s
        assert np.array_equal(np.asarray(new_rng[s]), carry), s
        assert int(n_acc[s]) == n - 1
        assert not bool(done[s])
    # row 0 accepted every draft position
    assert int(n_emit[0]) == G


def test_verify_and_accept_eos_truncates_accepted_span():
    """An eos emitted INSIDE the accepted draft prefix truncates emission
    at the eos (inclusive) and marks the row done — exactly where the
    1-wide step would have stopped."""
    S, G, V = 1, 4, 16
    logits = jax.random.normal(jax.random.key(5), (S, G, V), jnp.float32)
    state = _mk_state(S, [[7, 8]])
    ref = _reference_chain(logits, None, None, state, 16)[0][0]
    # greedy targets known: make every draft agree, then set eos to the
    # token the target emits at position 1
    drafts = np.asarray([ref[:G - 1]], np.int32)
    eos = ref[1]
    state = _mk_state(S, [[7, 8]], eos=eos)
    T, n_emit, n_acc, last_tok, new_rng, done = verify_and_accept(
        logits, jnp.asarray(drafts), jnp.asarray([G - 1], jnp.int32),
        state, max_top_k=16,
    )
    assert int(n_emit[0]) == 2 and bool(done[0])
    assert int(last_tok[0]) == eos
    # the carry advanced exactly 2 splits
    c = state.rng[0]
    for _ in range(2):
        c = jax.random.split(c)[1]
    assert np.array_equal(np.asarray(new_rng[0]), np.asarray(c))


def test_verify_and_accept_done_row_sticks_at_eos():
    S, G, V = 2, 3, 16
    logits = jax.random.normal(jax.random.key(6), (S, G, V), jnp.float32)
    state = _mk_state(S, [[1, 2], [3, 4]], eos=9, done=True)
    T, n_emit, _, last_tok, _, done = verify_and_accept(
        logits, jnp.zeros((S, G - 1), jnp.int32),
        jnp.zeros((S,), jnp.int32), state, max_top_k=16,
    )
    assert bool(done.all())
    assert int(last_tok[0]) == 9 and int(last_tok[1]) == 9
    assert int(n_emit[0]) == 1  # emitted eos, then truncated


# --- G-query decode kernels ---------------------------------------------------


@pytest.mark.parametrize("impl", ["scan", "pallas"])
def test_multi_query_decode_attention_matches_reference(impl):
    """Both kernels at G query positions match the repeat-expanded
    reference: query g of row b attends positions < lengths[b]-(G-1)+g,
    at ragged lengths including the minimum (lengths == G) and a full
    row."""
    B, G, H, Hkv, hd, T, block = 4, 3, 8, 2, 16, 64, 16
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (B, G, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, T, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, T, hd), jnp.float32)
    lengths = jnp.asarray([3, 17, 33, 64], jnp.int32)
    ref = reference_decode_attention(q, k, v, lengths)
    got = decode_attention(q, k, v, lengths, impl=impl, block=block)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=2e-6, rtol=1e-5
    )


@pytest.mark.parametrize("impl", ["scan", "pallas"])
def test_multi_query_paged_shared_tables_and_scratch_tails(impl, paged_kernel):
    """The paged G-query form through block tables where (a) two rows
    SHARE physical blocks (prefix sharing live during a spec step) and
    (b) table tails beyond each row's length point at the scratch block
    — neither sharing nor scratch garbage may leak into any query
    position."""
    B, G, H, Hkv, hd, block, P, M = 3, 4, 4, 2, 8, 8, 6, 4
    ks = jax.random.split(jax.random.key(13), 3)
    q = jax.random.normal(ks[0], (B, G, H, hd), jnp.float32)
    k_pool = jax.random.normal(ks[1], (P, Hkv, block, hd), jnp.float32)
    v_pool = jax.random.normal(ks[2], (P, Hkv, block, hd), jnp.float32)
    # rows 0 and 1 share blocks 1, 2 (a common prefix); tails at scratch
    tables = jnp.asarray([
        [1, 2, 3, SCRATCH_BLOCK],
        [1, 2, 4, 5],
        [5, SCRATCH_BLOCK, SCRATCH_BLOCK, SCRATCH_BLOCK],
    ], jnp.int32)
    lengths = jnp.asarray([18, 30, 7], jnp.int32)
    paged_kernel(impl == "pallas")   # the paged form takes no ``impl``
    got = decode_attention(
        q, k_pool, v_pool, lengths, tables=tables, block=block,
    )
    # reference: gather each row's contiguous K/V through its table
    kc = k_pool[tables].transpose(0, 2, 1, 3, 4).reshape(B, Hkv, M * block, hd)
    vc = v_pool[tables].transpose(0, 2, 1, 3, 4).reshape(B, Hkv, M * block, hd)
    ref = reference_decode_attention(q, kc, vc, lengths)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=2e-6, rtol=1e-5
    )


@pytest.mark.parametrize("impl", ["scan", "pallas"])
def test_g_zero_slice_matches_single_query(impl):
    """The G-wide kernel's g=0 output IS the 1-wide kernel's output at
    the matching length — the spec step's position-0 compute is the
    autoregressive step's. The scan path is gated BITWISE; pallas runs
    interpreted through XLA on CPU, where fusion choices can reassociate
    at ULP level, so it gets a near-zero tolerance instead (on TPU the
    grid cell runs the identical instruction sequence)."""
    B, G, H, Hkv, hd, T, block = 2, 3, 4, 2, 8, 32, 8
    ks = jax.random.split(jax.random.key(17), 3)
    q1 = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, T, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, T, hd), jnp.float32)
    lengths1 = jnp.asarray([9, 25], jnp.int32)
    qG = jnp.concatenate(
        [q1[:, None], jnp.ones((B, G - 1, H, hd), jnp.float32)], axis=1
    )
    one = decode_attention(q1, k, v, lengths1, impl=impl, block=block)
    wide = decode_attention(
        qG, k, v, lengths1 + (G - 1), impl=impl, block=block,
    )
    if impl == "scan":
        assert np.array_equal(np.asarray(one), np.asarray(wide[:, 0]))
    else:
        np.testing.assert_allclose(
            np.asarray(one), np.asarray(wide[:, 0]), atol=1e-7, rtol=1e-6,
        )


def test_scatter_block_kv_multi_position():
    """[S, G] scatter lands each position in its named (block, offset)
    and the [S] 1-D form stays the classic one-token write."""
    P, Hkv, block, hd = 4, 2, 4, 3
    pool = jnp.zeros((P, Hkv, block, hd), jnp.float32)
    new = jnp.arange(2 * 2 * Hkv * hd, dtype=jnp.float32).reshape(2, 2, Hkv, hd)
    pids = jnp.asarray([[1, 1], [2, 3]], jnp.int32)
    offs = jnp.asarray([[0, 1], [3, 0]], jnp.int32)
    out = scatter_block_kv(pool, new, pids, offs)
    for s in range(2):
        for g in range(2):
            np.testing.assert_array_equal(
                np.asarray(out[pids[s, g], :, offs[s, g], :]),
                np.asarray(new[s, g]),
            )
    one = scatter_block_kv(
        pool, new[:, 0], jnp.asarray([1, 2]), jnp.asarray([2, 2])
    )
    np.testing.assert_array_equal(np.asarray(one[1, :, 2, :]), np.asarray(new[0, 0]))
    np.testing.assert_array_equal(np.asarray(one[2, :, 2, :]), np.asarray(new[1, 0]))


# --- engine parity ------------------------------------------------------------


@pytest.mark.slow  # ~20s: double submission of four prompts across three
# configs; impls_agree + the verify_and_accept reference-chain tests keep
# spec parity under tier-1
def test_engine_spec_matches_generate_greedy(setup):
    """Greedy engine output with spec on equals spec off equals solo
    generate() — on the SECOND submission of each prompt too, when the
    radix store (prefix sharing + the trie draft source) is live."""
    cfg, params = setup
    prompts = _prompts(cfg, [3, 9, 14, 5])
    budgets = [6, 5, 7, 4]

    def run(spec):
        eng = Engine(params, cfg, ServeConfig(
            slots=2, max_len=64, kv_block=8, spec=spec, spec_max_draft=4,
        ))
        out = []
        for _ in range(2):  # second round: prefix store + trie are warm
            res = eng.run([
                Request(prompt=p, max_new_tokens=m)
                for p, m in zip(prompts, budgets)
            ])
            out.append([res[r].tokens for r in sorted(res)])
        return out

    on, off = run(True), run(False)
    assert on == off
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        solo = generate(params, jnp.asarray(p)[None], cfg, max_new_tokens=m)
        assert on[0][i] == list(np.asarray(solo[0, len(p):]))
        assert on[1][i] == on[0][i]


@pytest.mark.slow  # re-pays a full spec-engine build for the sampled variant
# of the greedy spec parity test above; the rejection rule's key-chain
# behaviour is covered by the verify_and_accept unit family (tier-1 budget)
def test_engine_spec_matches_generate_sampled(setup):
    """Same rng -> same tokens with speculation on: the rejection rule
    consumes the per-slot key chain exactly as the 1-wide step does, so
    sampled output is draw-for-draw identical, drafts accepted or not."""
    cfg, params = setup
    prompts = _prompts(cfg, [4, 9, 6], seed=1)
    kwargs = [
        dict(temperature=0.8, top_k=7),
        dict(temperature=1.2, top_p=0.9),
        dict(temperature=0.6, top_k=5, top_p=0.7),
    ]
    keys = [jax.random.key(40 + i) for i in range(3)]
    row_keys = [jax.random.split(k, 1)[0] for k in keys]

    def run(spec, source):
        eng = Engine(params, cfg, ServeConfig(
            slots=2, max_len=64, kv_block=8, spec=spec, spec_max_draft=4,
            spec_draft_source=source,
        ))
        out = []
        for _ in range(2):
            rids = [
                eng.submit(Request(prompt=p, max_new_tokens=5, rng=rk, **kw))
                for p, rk, kw in zip(prompts, row_keys, kwargs)
            ]
            res = eng.run()
            out.append([res[r].tokens for r in rids])
        return out

    off = run(False, "auto")
    for source in ("auto", "prefix", "ngram"):
        assert run(True, source) == off, source
    for i, (p, k) in enumerate(zip(prompts, keys)):
        solo = generate(
            params, jnp.asarray(p)[None], cfg, max_new_tokens=5,
            rng=k, **kwargs[i],
        )
        assert off[0][i] == list(np.asarray(solo[0, len(p):]))


@pytest.mark.slow  # re-pays a full spec-engine build; eos-inside-span
# truncation + done-row latching is covered by the verify_and_accept unit
# family and greedy engine parity rides every decode (870s budget)
def test_engine_spec_eos_inside_accepted_draft(setup):
    """An eos landing INSIDE an accepted multi-token span finishes the
    request at exactly the spec-off position — no overshoot tokens leak
    into the completion past the eos."""
    cfg, params = setup
    p = _prompts(cfg, [8], seed=3)[0]
    solo = generate(params, jnp.asarray(p)[None], cfg, max_new_tokens=10)
    gen = list(np.asarray(solo[0, len(p):]))
    # pick an eos deep enough that accepted drafts can cover it
    eos = gen[4]
    want = gen[:gen.index(eos) + 1]
    eng = Engine(params, cfg, ServeConfig(
        slots=1, max_len=64, kv_block=8, spec=True, spec_max_draft=4,
    ))
    # warm WITHOUT the eos so the trie holds the full path, then the
    # timed request drafts across the eos position
    eng.run([Request(prompt=p, max_new_tokens=10)])
    res = eng.run([Request(prompt=p, max_new_tokens=10, eos_id=int(eos))])
    assert res[1].finish_reason == "eos"
    assert res[1].tokens == want


def test_engine_spec_decode_impls_agree(setup, paged_kernel):
    cfg, params = setup
    prompts = _prompts(cfg, [3, 10], seed=6)
    outs = {}
    for impl in ("scan", "pallas"):
        paged_kernel(impl == "pallas")
        eng = Engine(params, cfg, ServeConfig(
            slots=2, max_len=32, kv_block=8, decode_impl=impl,
            spec=True, spec_max_draft=3,
        ))
        res = eng.run([Request(prompt=p, max_new_tokens=5) for p in prompts])
        res2 = eng.run([Request(prompt=p, max_new_tokens=5) for p in prompts])
        outs[impl] = (
            [res[i].tokens for i in sorted(res)],
            [res2[i].tokens for i in sorted(res2)],
        )
    assert outs["scan"] == outs["pallas"]
    assert outs["scan"][0] == outs["scan"][1]


# --- compile ledger / metrics -------------------------------------------------


@pytest.mark.slow  # 20 warm submissions through a full engine build; the
# signature-family shape (every spec key mirrors a plain (blocks, attended)
# key) is the cheap half and the ledger bound follows from it (870s budget)
def test_spec_compile_count_is_bounded(setup):
    """Speculation adds at most a MIRROR of the plain decode signature
    family (one fixed G per engine) — never a per-draft-length or
    per-request signature."""
    cfg, params = setup
    eng = Engine(params, cfg, ServeConfig(
        slots=2, max_len=40, kv_block=8, prefill_buckets=(8, 16, 24),
        spec=True, spec_max_draft=4,
    ))
    lengths = [2, 3, 5, 7, 8, 9, 12, 15, 17, 21]
    for _ in range(2):
        for p in _prompts(cfg, lengths, seed=3):
            eng.submit(Request(prompt=p, max_new_tokens=3))
        eng.run()
    m_axis = 1 + int(np.ceil(np.log2(blocks_for(40, 8))))
    p_axis = 1 + int(np.ceil(np.log2(eng._pool_cap)))
    assert eng.metrics.decode_compiles <= 2 * (m_axis + p_axis)
    # every spec signature is keyed exactly like a plain one
    assert all(len(sig) == 2 for sig in eng._spec_fns)


@pytest.mark.slow  # re-pays a full spec-engine build to read gauge fields;
# record_spec arithmetic is unit-covered and the counters ride every parity
# test above (tier-1 runs close to its 870s timeout)
def test_spec_metrics_and_snapshot(setup):
    cfg, params = setup
    eng = Engine(params, cfg, ServeConfig(
        slots=2, max_len=64, kv_block=8, spec=True, spec_max_draft=4,
    ))
    p = _prompts(cfg, [8], seed=4)[0]
    eng.run([Request(prompt=p, max_new_tokens=9)])
    eng.run([Request(prompt=p, max_new_tokens=9)])  # trie-drafted repeat
    m = eng.metrics
    assert m.draft_proposed > 0
    assert m.draft_accepted > 0
    assert m.spec_rollbacks == m.draft_proposed - m.draft_accepted
    assert 0 < m.draft_accept_rate <= 1
    assert m.tokens_per_step > 1.0  # accepted drafts beat 1 token/step
    snap = eng.stats_snapshot()
    assert snap["tokens_per_step"] == round(m.tokens_per_step, 4)
    assert snap["draft_accept_rate"] == round(m.draft_accept_rate, 4)
    assert snap["spec_rollbacks"] == float(m.spec_rollbacks)
    summ = m.summary()
    assert summ["draft_accept_rate"] > 0
    assert summ["tokens_per_step"] > 1.0
    # registry counters: accepted never exceeds proposed
    reg = eng.registry
    prop = reg.counter("tony_serve_draft_proposed_total").value
    acc = reg.counter("tony_serve_draft_accepted_total").value
    assert prop == m.draft_proposed and acc == m.draft_accepted


@pytest.mark.slow  # ~8s interaction test; spec parity and the health
# monitors each have their own cheaper tier-1 coverage
def test_spec_accepted_drafts_do_not_trip_health(setup, tmp_path):
    """Accepted multi-token steps report the autoregressive frontier's
    logits to the health monitors — a healthy model serving repeats with
    near-full acceptance must not trip serve_nonfinite or entropy_floor."""
    from tony_tpu.obs import health
    from tony_tpu.obs.health import HealthRules, HealthSentinel

    s = health.install(HealthSentinel(
        HealthRules(), app_dir=str(tmp_path), proc="worker_0_user_a0",
        sample_every=1,
    ))
    try:
        cfg, params = setup
        eng = Engine(params, cfg, ServeConfig(
            slots=2, max_len=64, kv_block=8, spec=True, spec_max_draft=4,
        ))
        p = _prompts(cfg, [8], seed=5)[0]
        for _ in range(3):
            eng.run([Request(prompt=p, max_new_tokens=9)])
        assert eng.metrics.draft_accepted > 0
        assert s.trip_counts() == {}
    finally:
        health.install(None)
