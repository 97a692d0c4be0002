"""Serving-engine tests: continuous batching must not change what any
single request generates, and the length-aware decode path must match the
full-mask reference exactly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import llama
from tony_tpu.models.generate import generate
from tony_tpu.ops.decode_attention import decode_attention, reference_decode_attention
from tony_tpu.serve import Engine, Request, ServeConfig
from tony_tpu.serve.cache import SCRATCH_BLOCK, blocks_for


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    return cfg, params


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32) for n in lengths]


# --- engine vs generate() parity ---------------------------------------------


def test_engine_matches_generate_greedy(setup):
    """Greedy requests of different lengths through a 2-slot engine (forced
    slot churn + bucketed prefill + cache growth) produce exactly the tokens
    a solo generate() call produces for each prompt."""
    cfg, params = setup
    prompts = _prompts(cfg, [3, 7, 12, 5])
    budgets = [5, 4, 6, 3]
    eng = Engine(params, cfg, ServeConfig(slots=2, max_len=32, kv_block=8))
    rids = [
        eng.submit(Request(prompt=p, max_new_tokens=m))
        for p, m in zip(prompts, budgets)
    ]
    got = eng.run()
    for rid, p, m in zip(rids, prompts, budgets):
        solo = generate(params, jnp.asarray(p)[None], cfg, max_new_tokens=m)
        assert got[rid].tokens == list(np.asarray(solo[0, len(p):])), rid


@pytest.mark.slow  # re-pays a full engine build for the sampled variant of
# the greedy parity test above; per-request key isolation is covered at the
# sample_tokens/generate level (tier-1 runs close to its 870s timeout)
def test_engine_matches_generate_sampled(setup):
    """Same rng -> same tokens, batched or solo: a request's sample stream
    depends only on its own key, not on what else occupies the engine."""
    cfg, params = setup
    prompts = _prompts(cfg, [4, 9, 6], seed=1)
    kwargs = [
        dict(temperature=0.8, top_k=7),
        dict(temperature=1.2, top_p=0.9),
        dict(temperature=0.6, top_k=5, top_p=0.7),
    ]
    keys = [jax.random.key(40 + i) for i in range(3)]
    # generate() derives row i's stream from split(rng, B); submit the same
    # derived key so engine-vs-generate compares identical streams (B=1)
    row_keys = [jax.random.split(k, 1)[0] for k in keys]
    eng = Engine(params, cfg, ServeConfig(slots=2, max_len=32, kv_block=8))
    rids = [
        eng.submit(Request(prompt=p, max_new_tokens=5, rng=rk, **kw))
        for p, rk, kw in zip(prompts, row_keys, kwargs)
    ]
    got = eng.run()
    for rid, p, k, rk, kw in zip(rids, prompts, keys, row_keys, kwargs):
        solo = generate(
            params, jnp.asarray(p)[None], cfg, max_new_tokens=5,
            rng=k, **kw,
        )
        direct = Engine(params, cfg, ServeConfig(slots=1, max_len=32))
        dres = direct.run([Request(prompt=p, max_new_tokens=5, rng=rk, **kw)])
        assert got[rid].tokens == list(np.asarray(solo[0, len(p):]))
        assert dres[0].tokens == got[rid].tokens


def test_eos_frees_slot_for_queued_request(setup):
    """A row hitting EOS releases its slot mid-run and the queued request
    takes it over — the continuous-batching contract."""
    cfg, params = setup
    p1, p2 = _prompts(cfg, [4, 6], seed=2)
    # find what the first greedy token of p1 is, then use it as its EOS
    first = int(generate(params, jnp.asarray(p1)[None], cfg, max_new_tokens=1)[0, -1])
    eng = Engine(params, cfg, ServeConfig(slots=1, max_len=32, kv_block=8))
    a = eng.submit(Request(prompt=p1, max_new_tokens=8, eos_id=first))
    b = eng.submit(Request(prompt=p2, max_new_tokens=3))
    out = eng.run()
    assert out[a].finish_reason == "eos"
    assert out[a].tokens == [first]          # stopped immediately, 7 unspent
    assert out[b].finish_reason == "length"
    assert len(out[b].tokens) == 3
    # request b decoded on the slot request a vacated
    assert eng.metrics.requests_finished == 2
    # b's tokens match its solo run (slot reuse leaked nothing)
    solo = generate(params, jnp.asarray(p2)[None], cfg, max_new_tokens=3)
    assert out[b].tokens == list(np.asarray(solo[0, len(p2):]))


def test_bucketed_prefill_compile_count(setup):
    """Ten distinct prompt lengths land in at most len(buckets) prefill
    compiles — admission pads to buckets, so compile count is bounded by
    the bucket set, not by the traffic."""
    cfg, params = setup
    eng = Engine(params, cfg, ServeConfig(
        slots=2, max_len=40, kv_block=8, prefill_buckets=(8, 16, 24),
    ))
    lengths = [2, 3, 5, 7, 8, 9, 12, 15, 17, 21]
    for p in _prompts(cfg, lengths, seed=3):
        eng.submit(Request(prompt=p, max_new_tokens=2))
    eng.run()
    assert eng.metrics.requests_finished == len(lengths)
    assert eng.metrics.prefill_compiles <= 3
    # decode recompiles only on signature changes — attended table width
    # (doubling ladder) x pool size (doubling ladder), never per request:
    # each axis contributes at most 1 + log2 of its block span
    m_axis = 1 + int(np.ceil(np.log2(blocks_for(40, 8))))
    p_axis = 1 + int(np.ceil(np.log2(eng._pool_cap)))
    assert eng.metrics.decode_compiles <= m_axis + p_axis


def test_cache_grows_and_frees_blocks(setup):
    """Attended width tracks the live maximum: it grows in blocks as the
    longest row extends and shrinks back when that row finishes (a freed
    slot returns the blocks nothing else references)."""
    cfg, params = setup
    eng = Engine(params, cfg, ServeConfig(slots=2, max_len=64, kv_block=8))
    long = eng.submit(Request(prompt=_prompts(cfg, [20], seed=4)[0],
                              max_new_tokens=8))
    first = eng.run()
    # attended table widths the engine compiled (decode signature =
    # (pool blocks, attended blocks))
    grown = max(att for _, att in eng._decode_fns) * 8
    assert grown >= 24  # 20-token prompt + decode tail crossed 3 blocks
    # drain left no live rows; a new short request shrinks back to one block
    short = eng.submit(Request(prompt=_prompts(cfg, [3], seed=5)[0],
                               max_new_tokens=2))
    second = eng.run()
    assert eng.attended_positions <= 16, eng.attended_positions
    # the finished rows' private blocks went back to the pool: only the
    # prefix store's registered blocks (plus scratch) stay referenced
    assert eng._pool.n_used <= eng._store.n_nodes
    assert first[long].finish_reason == "length"
    assert second[short].finish_reason == "length"
    # run() drains: each call returns (and evicts) only its own completions
    assert long not in second and not eng._completions


# --- decode attention ---------------------------------------------------------


@pytest.mark.parametrize("impl", ["scan", "pallas"])
def test_decode_attention_matches_repeat_reference(impl):
    """Both decode impls (native-GQA scan and the interpreted Pallas
    kernel) match the repeat-expanded full-mask reference at ragged
    lengths, including length-1 rows and a full row."""
    B, H, Hkv, hd, T, block = 4, 8, 2, 16, 64, 16
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, T, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, T, hd), jnp.float32)
    lengths = jnp.asarray([1, 17, 33, 64], jnp.int32)
    ref = reference_decode_attention(q, k, v, lengths)
    got = decode_attention(q, k, v, lengths, impl=impl, block=block)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=2e-6, rtol=1e-5
    )


def test_decode_attention_ignores_positions_beyond_length():
    """Garbage beyond a row's length (stale cache from a previous slot
    occupant) must not leak into the output — the length mask is the only
    thing standing between slot reuse and cross-request contamination."""
    B, H, Hkv, hd, T = 2, 4, 2, 8, 32
    ks = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, T, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, T, hd), jnp.float32)
    lengths = jnp.asarray([5, 9], jnp.int32)
    base = decode_attention(q, k, v, lengths, impl="scan", block=8)
    # poison everything beyond each row's length
    pos = jnp.arange(T)[None, None, :, None]
    poisoned_k = jnp.where(pos < lengths[:, None, None, None], k, 1e3)
    poisoned_v = jnp.where(pos < lengths[:, None, None, None], v, -1e3)
    for impl in ("scan", "pallas"):
        got = decode_attention(
            q, poisoned_k, poisoned_v, lengths, impl=impl, block=8
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(base), atol=1e-6)


def test_engine_decode_impls_agree(setup, paged_kernel):
    """The engine produces identical greedy tokens under both forms of the
    paged decode attention (the XLA scan vs the interpreted Pallas kernel,
    which the op picks from the platform: the fixture steers it) and of
    the ``decode_impl`` knob that still picks the int8 matmul's form."""
    cfg, params = setup
    prompts = _prompts(cfg, [3, 10], seed=6)
    outs = {}
    for impl in ("scan", "pallas"):
        paged_kernel(impl == "pallas")
        eng = Engine(params, cfg, ServeConfig(
            slots=2, max_len=32, kv_block=8, decode_impl=impl,
        ))
        res = eng.run([Request(prompt=p, max_new_tokens=5) for p in prompts])
        outs[impl] = [res[i].tokens for i in range(len(prompts))]
    assert outs["scan"] == outs["pallas"]


# --- the paged kernel: each row's live blocks only ------------------------------


def _paged_case(form, G, tails):
    """Six rows over a table of 20 blocks of 8 (the kernel walks 8 blocks a
    step): lengths at 1 query's minimum, an exact multiple of the block,
    one step's reach - 1 / exactly / + 1, mid-table, and the full table.
    ``tails``: what the table names past a row's last block — ``"nan"``:
    blocks of its own filled with NaN; ``"scratch"``: the scratch block,
    filled with NaN too. ``form``: dense GQA (4 kv heads x 8), MHA (one
    query row a kv head) or latent (one shared 32-wide row whose first 24
    columns are the values and whose last 4 lanes are padding)."""
    from tony_tpu.ops import decode_attention as da

    blk, M, n = 8, 20, da._STEP_BLOCKS
    Hkv, rep, hd, vw = {
        "dense": (4, 8, 16, 0), "mha": (4, 1, 16, 0), "latent": (1, 16, 32, 24),
    }[form]
    lengths = np.asarray(
        [G, 5 * blk, n * blk - 1, n * blk, n * blk + 1, M * blk], np.int32)
    B = len(lengths)
    rng = np.random.default_rng(5)
    k = rng.standard_normal((1 + B * M, Hkv, blk, hd)).astype(np.float32)
    v = rng.standard_normal((1 + B * M, Hkv, blk, hd)).astype(np.float32)
    if vw:
        k[..., hd - 4:] = 0.0                     # the cache's padded lanes
    tables = 1 + np.arange(B * M, dtype=np.int32).reshape(B, M)
    dead = np.arange(M)[None, :] >= -(-lengths[:, None] // blk)
    clean_k, clean_v = k.copy(), v.copy()
    if tails == "nan":
        k[tables[dead]] = v[tables[dead]] = np.nan
        clean_k[tables[dead]] = clean_v[tables[dead]] = 0.0
    else:
        tables[dead] = SCRATCH_BLOCK
        k[SCRATCH_BLOCK] = v[SCRATCH_BLOCK] = np.nan
        clean_k[SCRATCH_BLOCK] = clean_v[SCRATCH_BLOCK] = 0.0
    q = rng.standard_normal((B, G, Hkv * rep, hd)).astype(np.float32)
    if vw:
        q[..., hd - 4:] = 0.0
    kc = clean_k[tables].transpose(0, 2, 1, 3, 4).reshape(B, Hkv, M * blk, hd)
    vc = clean_v[tables].transpose(0, 2, 1, 3, 4).reshape(B, Hkv, M * blk, hd)
    ref = reference_decode_attention(
        jnp.asarray(q), jnp.asarray(kc),
        jnp.asarray(kc[..., :vw] if vw else vc), jnp.asarray(lengths),
        scale=0.25,
    )
    args = (jnp.asarray(q), jnp.asarray(k), None if vw else jnp.asarray(v),
            jnp.asarray(lengths), jnp.asarray(tables))
    return args, dict(scale=0.25, **({"v_width": vw} if vw else {})), ref


def _paged_kernel(args, kw):
    """The kernel itself, at the blocks a step the op would give it."""
    from tony_tpu.ops import decode_attention as da

    q, k, v, _, tables = args
    return np.asarray(da._paged_attend(
        *args, n=da._step_blocks(k, v, tables), **kw))


@pytest.mark.parametrize("tails", ["nan", "scratch"])
@pytest.mark.parametrize("G", [1, 3])
@pytest.mark.parametrize("form", ["dense", "mha", "latent"])
def test_paged_kernel_reads_each_rows_live_blocks_only(form, G, tails):
    """The paged kernel (interpreted) against the repeat-expanded reference
    at ragged lengths, with every block past a row's length poisoned: the
    output is finite and equal, so those blocks were neither fetched into
    the result nor computed (the XLA scan, which gathers every table entry
    and masks afterwards, reads NaN here)."""
    from tony_tpu.ops import decode_attention as da

    args, kw, ref = _paged_case(form, G, tails)
    got = _paged_kernel(args, kw)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(ref), atol=2e-6, rtol=1e-5)
    assert not np.isfinite(np.asarray(da._paged_scan(*args, **kw))).all()


@pytest.mark.parametrize("step", [3, 4, 32])
def test_paged_kernel_at_other_step_sizes(monkeypatch, step):
    """Blocks a grid step: one that does not divide the table, one that
    does, and more than the table holds — same result."""
    from tony_tpu.ops import decode_attention as da

    monkeypatch.setattr(da, "_STEP_BLOCKS", step)
    args, kw, ref = _paged_case("dense", 1, "nan")
    got = _paged_kernel(args, kw)
    np.testing.assert_allclose(got, np.asarray(ref), atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("Hkv,blk,hd,itemsize,pools,blocks", [
    pytest.param(4, 64, 128, 2, 2, 8, id="yi-1.5-6b"),          # 64 KB tiles
    pytest.param(1, 64, 640, 2, 1, 8, id="deepseek-v3-latent"),
    pytest.param(32, 64, 128, 2, 2, 4, id="llama2_7b"),         # MHA: 512 KB
    pytest.param(40, 64, 128, 2, 2, 3, id="llama2_13b"),
    pytest.param(32, 64, 128, 4, 2, 2, id="llama2_7b-float32"),
    pytest.param(40, 128, 128, 4, 2, 0, id="llama2_13b-float32-block128"),
])
def test_paged_kernel_sizes_its_step_from_the_tile_bytes(
        Hkv, blk, hd, itemsize, pools, blocks):
    """Blocks a grid step: 8 where the tiles are small, fewer so that the
    step's double-buffered tiles stay inside ``_STEP_BYTES`` where a pool
    has many kv heads, float32 rows or long blocks, and none (the scan)
    where one block overruns it (the compiler's own verdict on these
    shapes is in tests/test_tpu_compile.py)."""
    from tony_tpu.ops import decode_attention as da

    dtype = {2: jnp.bfloat16, 4: jnp.float32}[itemsize]
    pool = jax.ShapeDtypeStruct((9, Hkv, blk, hd), dtype)
    tables = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    n = da._step_blocks(pool, pool if pools == 2 else None, tables)
    assert n == blocks
    tile = Hkv * blk * hd * itemsize * pools
    assert 2 * n * tile <= da._STEP_BYTES < 2 * (n + 1) * tile or n == 8


def test_paged_form_keeps_the_scan_where_no_block_fits(monkeypatch, paged_kernel):
    """A pool whose single block overruns a step's VMEM budget is served by
    the scan, on the platform that would otherwise run the kernel: told
    apart by the poisoned tails, which only the scan reads."""
    from tony_tpu.ops import decode_attention as da

    (q, k, v, ln, tb), kw, ref = _paged_case("dense", 1, "nan")
    paged_kernel(True)
    tile = 2 * k[0].size * k.dtype.itemsize
    monkeypatch.setattr(da, "_STEP_BYTES", 2 * tile)      # one block a step
    out = decode_attention(q[:, 0], k, v, ln, tables=tb, scale=0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref[:, 0]),
                               atol=2e-6, rtol=1e-5)
    monkeypatch.setattr(da, "_STEP_BYTES", 2 * tile - 1)  # not even one
    out = decode_attention(q[:, 0], k, v, ln, tables=tb, scale=0.25)
    assert not np.isfinite(np.asarray(out)).all()


def test_public_paged_forms_choose_the_kernel_by_platform(paged_kernel):
    """``decode_attention(tables=...)`` and ``latent_decode_attention`` run
    the scan here (no TPU) whatever ``impl`` says, and the kernel once the
    platform check is steered: told apart by the poisoned tails."""
    from tony_tpu.ops.decode_attention import latent_decode_attention

    (q, k, v, ln, tb), kw, ref = _paged_case("dense", 1, "nan")
    (ql, pool, _, _, _), kwl, refl = _paged_case("latent", 1, "nan")
    for impl in ("scan", "pallas"):
        out = decode_attention(q[:, 0], k, v, ln, tables=tb, impl=impl, scale=0.25)
        assert not np.isfinite(np.asarray(out)).all()
    paged_kernel(True)
    out = decode_attention(q[:, 0], k, v, ln, tables=tb, scale=0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref[:, 0]),
                               atol=2e-6, rtol=1e-5)
    out = latent_decode_attention(ql[:, 0, :, :28], pool, ln, tb, v_width=24,
                                  scale=0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(refl[:, 0]),
                               atol=2e-6, rtol=1e-5)


# --- the carried pool: every layer writes its own blocks, and only those ------


@pytest.mark.parametrize("impl", ["scan", "pallas"])
@pytest.mark.parametrize("step", ["decode", "spec"])
def test_decode_step_writes_each_layers_own_blocks(impl, step):
    """The decode programs carry the pools as one ``[L * P, ...]`` buffer
    and reach layer ``l`` by adding ``l * P`` to block ids. After one step
    on a 3-layer model over a pool of random content: every layer's new
    K/V row sits at ``(table[s, pos // blk], pos % blk)`` of ITS OWN
    layer, a dead slot's row sits in its layer's scratch block, the block
    a dead slot's stale table still names (freed, then reallocated to a
    live slot) is untouched at every layer, and every other value of both
    pools is bitwise what it was."""
    import dataclasses

    from tony_tpu.serve.cache import SCRATCH_BLOCK, PagedKVCache
    from tony_tpu.serve.dense import decode_step
    from tony_tpu.serve.engine import _SlotState

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), n_layers=3)
    params = llama.init_params(jax.random.key(1), cfg)
    S, blk, M, draft_k = 4, 8, 3, 2
    L, Hkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    P = 1 + 3 * M
    kk, kv = jax.random.split(jax.random.key(2))
    shape = (L, P, Hkv, blk, hd)
    old_k = jax.random.normal(kk, shape, cfg.dtype)
    old_v = jax.random.normal(kv, shape, cfg.dtype)
    # slots 0..2 own blocks 1..9; slot 3 is DEAD and its stale row still
    # names slot 0's blocks (freed by slot 3, reallocated to slot 0)
    table = np.arange(1, 1 + 3 * M, dtype=np.int32).reshape(3, M)
    table = np.concatenate([table, table[:1]])
    lengths = np.array([5, 8, 17, 3], np.int32)   # slot 1 opens a new block
    live = np.array([True, True, True, False])
    state = _SlotState(
        last_tok=jnp.array([3, 9, 27, 81], jnp.int32),
        rng=jnp.zeros((S, 2), jnp.uint32),
        temp=jnp.zeros((S,), jnp.float32),
        top_k=jnp.zeros((S,), jnp.int32),
        top_p=jnp.ones((S,), jnp.float32),
        eos=jnp.full((S,), -1, jnp.int32),
        done=jnp.zeros((S,), bool),
        live=jnp.asarray(live),
    )
    cache = PagedKVCache(old_k, old_v, jnp.asarray(lengths))
    kw = dict(cfg=cfg, decode_impl=impl, kv_block=blk, max_top_k=8)
    if step == "spec":
        dlen = np.array([2, 0, 1, 2], np.int32)
        drafts = jnp.array([[1, 2], [3, 4], [5, 6], [7, 8]], jnp.int32)
        new, *_ = decode_step(
            params, cache, jnp.asarray(table), state, drafts,
            jnp.asarray(dlen), draft_k=draft_k, **kw,
        )
        # (slot, position) pairs written for real; padding beyond a row's
        # draft length and the dead slot go to (scratch, offset 0)
        real = [(s, lengths[s] + g) for s in range(S) if live[s]
                for g in range(dlen[s] + 1)]
        scratch_offs = {0}
    else:
        new, *_ = decode_step(params, cache, jnp.asarray(table), state, **kw)
        real = [(s, lengths[s]) for s in range(S) if live[s]]
        scratch_offs = {int(lengths[3]) % blk}

    expect = np.zeros((L, P, blk), bool)       # rows that may change
    for s, pos in real:
        expect[:, table[s, pos // blk], pos % blk] = True
    wrote = expect.copy()
    for off in scratch_offs:
        expect[:, SCRATCH_BLOCK, off] = True
    for old, got in ((old_k, new.k), (old_v, new.v)):
        old, got = np.asarray(old), np.asarray(got)
        assert got.shape == shape
        changed = (old != got).any(axis=(2, 4))               # [L, P, blk]
        # nothing outside the expected rows moved, at any layer — the
        # dead slot's stale target (table[3, 0] = slot 0's block, offset
        # 3) included
        assert not (changed & ~expect).any(), np.argwhere(changed & ~expect)
        assert not changed[:, table[3, 0], int(lengths[3]) % blk].any()
        # every real row was written at EVERY layer (random old content:
        # an unwritten row would compare equal), all kv heads of it
        assert ((old != got).any(axis=4).all(axis=2) | ~wrote).all()
        # the dead slot / padding rows landed in each layer's own scratch
        for off in scratch_offs:
            assert changed[:, SCRATCH_BLOCK, off].all()
        # and the layers wrote different rows (their own projections)
        s0, p0 = real[0]
        rows = got[:, table[s0, p0 // blk], :, p0 % blk, :]
        assert not np.allclose(rows[0], rows[1])
        assert not np.allclose(rows[1], rows[2])


# --- metrics ------------------------------------------------------------------


def test_decode_metrics_populated(setup):
    cfg, params = setup
    eng = Engine(params, cfg, ServeConfig(slots=2, max_len=32, kv_block=8))
    eng.run([
        Request(prompt=p, max_new_tokens=4)
        for p in _prompts(cfg, [3, 5, 4], seed=8)
    ])
    m = eng.metrics.summary()
    assert m["requests_finished"] == 3
    assert m["generated_tokens"] == 12
    assert m["tokens_per_sec_per_chip"] > 0
    assert m["ttft_avg_s"] > 0
    assert 0 < m["slot_occupancy"] <= 1


def test_engine_shutdown_summary(setup, tmp_path, monkeypatch, caplog):
    """Engine.close() surfaces the final DecodeMetrics summary — including
    the compile counts, the classic silent serving regression — plus
    TTFT/TPOT quantiles from the registry histograms, logs it, and
    snapshots the registry into the job history when running under a
    tony-tpu job (TONY_APP_DIR)."""
    import json
    import logging

    cfg, params = setup
    monkeypatch.setenv("TONY_APP_DIR", str(tmp_path))
    monkeypatch.setenv("TONY_JOB_NAME", "serve")
    monkeypatch.setenv("TONY_TASK_INDEX", "0")
    eng = Engine(params, cfg, ServeConfig(slots=2, max_len=32, kv_block=8))
    eng.run([
        Request(prompt=p, max_new_tokens=4)
        for p in _prompts(cfg, [3, 5], seed=9)
    ])
    with caplog.at_level(logging.INFO, logger="tony_tpu.serve.engine"):
        s = eng.close()
    assert s["requests_finished"] == 2
    assert s["prefill_compiles"] >= 1 and s["decode_compiles"] >= 1
    assert s["ttft_p99_s"] >= s["ttft_p50_s"] > 0
    assert any("engine shutdown" in r.message for r in caplog.records)
    # the registry snapshot landed in the job history for the portal
    # (suffixed: a fit() snapshot from the same process must coexist)
    snap_path = tmp_path / "metrics" / "serve_0_user_engine.json"
    assert snap_path.exists()
    snap = json.loads(snap_path.read_text())
    names = {m["name"] for m in snap["metrics"]}
    assert {"tony_ttft_seconds", "tony_decode_step_seconds",
            "tony_requests_finished_total"} <= names


# --- the host touches the device once per event -------------------------------


def _eager_activate(st, slot, tok, carry, temp, top_k, top_p, eos):
    """The eight eager updates ``Engine._activate_slot`` made before one
    program did: kept here as the plain reference."""
    from tony_tpu.serve.engine import _SlotState

    return _SlotState(
        last_tok=st.last_tok.at[slot].set(tok),
        rng=st.rng.at[slot].set(carry),
        temp=st.temp.at[slot].set(temp),
        top_k=st.top_k.at[slot].set(top_k),
        top_p=st.top_p.at[slot].set(top_p),
        eos=st.eos.at[slot].set(eos),
        done=st.done.at[slot].set(False),
        live=st.live.at[slot].set(True),
    )


def _eager_release(st, lengths, slot):
    """The four eager updates of ``Engine._finish``: the temperature too,
    so that a free slot never switches the sampler's wide branch on."""
    st = st._replace(live=st.live.at[slot].set(False),
                     done=st.done.at[slot].set(False),
                     temp=st.temp.at[slot].set(0.0))
    return st, lengths.at[slot].set(0)


def _transition_requests(cfg, params, case):
    """Five requests through three slots, so a transition always has live
    neighbours; the case shapes the first two."""
    prompts = _prompts(cfg, [4, 9, 6, 3, 11], seed=11)
    reqs = [Request(prompt=p, max_new_tokens=m) for p, m in zip(prompts, [5, 3, 4, 6, 2])]
    if case == "sampled":
        reqs[0] = Request(prompt=prompts[0], max_new_tokens=5, temperature=0.8,
                          top_k=7, rng=jax.random.key(3))
        reqs[1] = Request(prompt=prompts[1], max_new_tokens=3, temperature=1.2,
                          top_p=0.9, eos_id=5, rng=17)
    elif case == "eos_first":
        first = int(generate(params, jnp.asarray(prompts[0])[None], cfg,
                             max_new_tokens=1)[0, -1])
        reqs[0] = Request(prompt=prompts[0], max_new_tokens=5, eos_id=first)
    elif case == "one_token":
        reqs[1] = Request(prompt=prompts[1], max_new_tokens=1)
    return reqs


@pytest.mark.parametrize("case", ["greedy", "sampled", "eos_first", "one_token"])
def test_slot_transitions_equal_the_eager_updates(setup, monkeypatch, case):
    """Every ``serve_activate`` and ``serve_release`` of a run leaves the
    slot state — all eight fields, ALL slots, and the cache's lengths —
    bit for bit what the twelve eager ``.at[slot].set`` of the plain
    reference above make of the state the program was given; there is one
    program per activation and one per finish, and no other."""
    from tony_tpu.serve import engine as engine_mod
    from tony_tpu.serve.engine import _SlotState

    cfg, params = setup
    reqs = _transition_requests(cfg, params, case)  # generate() runs an engine too
    real = {"activate": engine_mod._activate_fn(), "release": engine_mod._release_fn()}
    calls = []

    def recording(kind):
        def call(*args):
            # the arguments are donated: copy them to the host first
            before = jax.tree.map(np.asarray, args)
            out = real[kind](*args)
            calls.append((kind, before, jax.tree.map(np.asarray, out)))
            return out
        return lambda: call

    monkeypatch.setattr(engine_mod, "_activate_fn", recording("activate"))
    monkeypatch.setattr(engine_mod, "_release_fn", recording("release"))
    eng = Engine(params, cfg, ServeConfig(slots=3, max_len=32, kv_block=8))
    got = eng.run(reqs)
    kinds = [kind for kind, _, _ in calls]
    assert len(got) == kinds.count("activate") == kinds.count("release") == len(reqs)
    assert eng.metrics.slot_programs == len(calls)
    for kind, before, out in calls:
        before = jax.tree.map(jnp.asarray, before)
        if kind == "activate":
            want = _eager_activate(_SlotState(*before[0]), *before[1:])
        else:
            want = _eager_release(_SlotState(*before[0]), *before[1:])
        want, out = jax.tree.leaves(want), jax.tree.leaves(out)
        assert len(want) == len(out) == (8 if kind == "activate" else 9)
        for w, o in zip(want, out):
            assert w.dtype == o.dtype and w.shape == o.shape
            np.testing.assert_array_equal(np.asarray(w), o)
    if case == "eos_first":
        assert got[0].finish_reason == "eos" and len(got[0].tokens) == 1
    if case == "one_token":
        assert got[1].finish_reason == "length" and len(got[1].tokens) == 1
    # a drained engine: nobody live, nothing done, no length left behind
    assert not np.asarray(eng.state.live).any() and not np.asarray(eng.state.done).any()
    assert not np.asarray(eng.cache.lengths).any()


_MIXED_ENGINES = {
    "plain": dict(slots=2, max_len=48, kv_block=8),
    "chunked": dict(slots=2, max_len=48, kv_block=8, chunk_tokens=8),
    "spec": dict(slots=2, max_len=48, kv_block=8, spec=True, spec_max_draft=3),
}


def _mixed_requests(cfg, params):
    """Greedy, sampled (top-k and top-p, a typed key each), an eos hit
    on the first token, an eos hit later, ``max_new_tokens=1`` and a prompt
    long enough to prefill in three chunks of 8: ``(request, solo)`` pairs,
    ``solo`` what ``generate()`` gives that request alone."""
    prompts = _prompts(cfg, [4, 9, 6, 20, 5, 7], seed=12)
    key = jax.random.key(41)
    row_key = jax.random.split(key, 1)[0]     # what generate() gives its one row

    def solo(p, n, **kw):
        out = generate(params, jnp.asarray(p)[None], cfg, max_new_tokens=n, **kw)
        return list(np.asarray(out[0, len(p):]))

    greedy = [solo(p, 6) for p in prompts]
    sampled = dict(temperature=0.8, top_k=7)
    nucleus = dict(temperature=1.2, top_p=0.9)
    pairs = [
        (Request(prompt=prompts[0], max_new_tokens=6), greedy[0]),
        (Request(prompt=prompts[1], max_new_tokens=5, rng=row_key, **sampled),
         solo(prompts[1], 5, rng=key, **sampled)),
        (Request(prompt=prompts[2], max_new_tokens=6, eos_id=greedy[2][0]),
         greedy[2][:1]),
        (Request(prompt=prompts[3], max_new_tokens=6), greedy[3]),
        (Request(prompt=prompts[4], max_new_tokens=1), greedy[4][:1]),
        (Request(prompt=prompts[5], max_new_tokens=6, eos_id=greedy[5][3]),
         greedy[5][:greedy[5].index(greedy[5][3]) + 1]),
        (Request(prompt=prompts[0], max_new_tokens=4, rng=row_key, **nucleus),
         solo(prompts[0], 4, rng=key, **nucleus)),
    ]
    return pairs, prompts


@pytest.fixture(scope="module")
def mixed(setup):
    cfg, params = setup
    return _mixed_requests(cfg, params)


@pytest.mark.parametrize("kind", sorted(_MIXED_ENGINES))
def test_mixed_run_serves_the_same_tokens(setup, mixed, kind):
    """One program per slot transition and one fetch per step change no
    token: a mixed run through a plain, a chunked-prefill and a speculative
    engine returns, request by request, what ``generate()`` returns for
    the request alone (the values the parity tests above hold), the greedy
    ones also what the full forward's argmax chain gives (no engine in
    that), with the finish reasons the requests ask for."""
    cfg, params = setup
    pairs, prompts = mixed
    eng = Engine(params, cfg, ServeConfig(**_MIXED_ENGINES[kind]))
    for _ in range(2):  # the second round drafts from what the first served
        rids = [eng.submit(r) for r, _ in pairs]
        got = eng.run()
        for rid, (req, solo) in zip(rids, pairs):
            assert got[rid].tokens == solo, (kind, rid)
            assert got[rid].finish_reason == (
                "eos" if req.eos_id is not None else "length"), (kind, rid)
    assert (eng.metrics.draft_accepted > 0) == (kind == "spec")
    # the first request's greedy chain by the plain full forward
    seq = list(prompts[0])
    for _ in range(6):
        logits = llama.forward(params, jnp.asarray(seq, jnp.int32)[None], cfg)
        seq.append(int(jnp.argmax(logits[0, -1])))
    assert got[rids[0]].tokens == seq[len(prompts[0]):]


@pytest.mark.parametrize("kind", sorted(_MIXED_ENGINES))
def test_device_touches_are_counted_once_per_event(setup, mixed, kind):
    """``device_fetches`` = decode steps + first tokens (a prefill's or a
    final chunk's: the dense family's other chunks hand the host nothing),
    ``slot_programs`` = activations + finishes, over the mixed run; both
    are in ``stats_snapshot`` and start again with ``reset_metrics``.
    ``steps_ahead`` is 0 with ``spec`` on and not otherwise."""
    cfg, params = setup
    pairs, _ = mixed
    eng = Engine(params, cfg, ServeConfig(**_MIXED_ENGINES[kind]))
    eng.run([r for r, _ in pairs])
    m, snap = eng.metrics, eng.stats_snapshot()
    assert m.requests_started == m.requests_finished == len(pairs)
    assert m.decode_steps > 0
    assert m.device_fetches == m.decode_steps + m.requests_started
    assert m.slot_programs == m.requests_started + m.requests_finished
    # drafts are proposed from the tokens just read: a speculating engine
    # never dispatches a step before the one before it was read
    assert (m.steps_ahead == 0) == (kind == "spec")
    assert snap["device_fetches"] == m.device_fetches
    assert snap["slot_programs"] == m.slot_programs
    eng.reset_metrics()
    assert eng.metrics.device_fetches == eng.metrics.slot_programs == 0


# --- a pipeline of depth one between host and device ---------------------------

_PIPELINE_FAMILIES = ("dense", "latent", "shortconv", "ssm_hybrid")


def _family_model(family):
    from tony_tpu.models.latent_moe import LatentMoEConfig
    from tony_tpu.models.shortconv_moe import ShortConvMoEConfig
    from tony_tpu.models.ssm_hybrid import SSMHybridConfig
    from tony_tpu.serve import engine as engine_mod

    cfg = {"dense": llama.LlamaConfig, "latent": LatentMoEConfig,
           "shortconv": ShortConvMoEConfig, "ssm_hybrid": SSMHybridConfig}[family].tiny()
    return cfg, engine_mod.steps_for(cfg).init_params(jax.random.key(0), cfg)


def _pipeline_engine(model, **knobs):
    cfg, params = model
    base = dict(slots=3, max_len=64, kv_block=8, prefill_buckets=(16, 32), prefix=False)
    return Engine(params, cfg, ServeConfig(**{**base, **knobs}))


def _hold_off(monkeypatch):
    """The engine with running ahead held off: the private predicate
    patched to name a reason that keeps every step, in the test alone; there
    is no public switch."""
    monkeypatch.setattr(Engine, "_may_run_ahead", lambda self, step: "finish")


def _drive(eng, reqs):
    """Submit everything, step to the end: what a caller can see after
    EVERY ``step()`` call — each request's tokens and finish reason, so
    also the call at which each request was admitted."""
    rids = [eng.submit(r) for r in reqs]
    seen = []
    while eng.queue_depth or eng.n_live:
        eng.step()
        comps = [(rid, eng.completion_of(rid)) for rid in rids]
        seen.append(tuple((rid, tuple(c.tokens), c.finish_reason)
                          for rid, c in comps if c is not None))
    return seen


def _watch(eng, monkeypatch):
    """Log the step loop of ``eng``: (event, step record) for every
    dispatch, sync and emit, the pool growths and the admissions made
    while a step was in flight, the latter with whether the step's row in
    that slot was another request's (a stale row)."""
    from tony_tpu.serve import engine as engine_mod

    log = {"events": [], "grown_in_flight": 0, "admitted_over_stale": 0}
    for name in ("_dispatch", "_sync", "_emit"):
        def wrapped(step, _real=getattr(eng, name), _name=name):
            if _name != "_sync" or step.out is not None:
                log["events"].append((_name, step))
            return _real(step)
        monkeypatch.setattr(eng, name, wrapped)
    real_grow, real_admit = engine_mod.grow_cache, eng._admit_one

    def grow(cache, n):
        log["grown_in_flight"] += eng._inflight is not None
        return real_grow(cache, n)

    def admit_one(slot, rid, req):
        step = eng._inflight
        if step is not None and any(s == slot and r != rid for s, r in step.rows):
            log["admitted_over_stale"] += 1
        return real_admit(slot, rid, req)

    monkeypatch.setattr(engine_mod, "grow_cache", grow)
    monkeypatch.setattr(eng, "_admit_one", admit_one)
    return log


def _steps_ahead_of(events):
    """From the log alone: (emitted steps that were dispatched before the
    step dispatched before them had been read, emitted steps in today's
    order)."""
    unread, ahead = None, []
    for name, step in events:
        if name == "_dispatch":
            if unread is not None:
                ahead.append(step)
            unread = step
        elif name == "_sync" and step is unread:
            unread = None
    emitted = [step for name, step in events if name == "_emit"]
    n_ahead = sum(1 for step in emitted if any(step is a for a in ahead))
    return n_ahead, len(emitted) - n_ahead


def _pipeline_requests(cfg, greedy):
    """The mixed run, from what each prompt gives greedily (``greedy``,
    None on the first pass): finishes by length the host can foresee, an
    eos on the first token, eos hits mid-run while the queue is not empty
    (so a slot is admitted again while a stale step is in flight),
    ``max_new_tokens`` 1 and 2, a sampled request, and rows long enough to
    grow the pool."""
    prompts = _prompts(cfg, [5, 9, 12, 4, 7, 20, 6, 11, 3], seed=21)
    budgets = [40, 12, 1, 2, 6, 12, 12, 9, 30]
    reqs = [Request(prompt=p, max_new_tokens=m) for p, m in zip(prompts, budgets)]
    if greedy is None:
        return reqs
    for i, k in ((1, 3), (5, 0), (6, 5)):       # request -> index of the token made its eos
        reqs[i] = Request(prompt=prompts[i], max_new_tokens=budgets[i], eos_id=greedy[i][k])
    reqs[4] = Request(prompt=prompts[4], max_new_tokens=6, temperature=0.8, top_k=7, rng=5)
    return reqs


@pytest.fixture(scope="module", params=_PIPELINE_FAMILIES)
def pipelined(request):
    """Two runs a family, each through an engine that runs ahead and
    through the same engine held off. ``steady``: every finish is one the
    host can foresee (by length; one- and two-token requests among them),
    as in a closed loop of callers. ``mixed``: eos finishes too. Of each:
    {"ahead", "held": what the callers saw call by call, "log", "engine"
    of the one that ran ahead, "held_engine", "requests"}."""
    model = _family_model(request.param)
    cfg = model[0]
    runs = {}
    greedy = None
    for kind in ("steady", "mixed"):
        reqs = _pipeline_requests(cfg, greedy)
        with pytest.MonkeyPatch.context() as mp:
            _hold_off(mp)
            held_eng = _pipeline_engine(model)
            held = _drive(held_eng, reqs)
        greedy = greedy or [list(toks) for _, toks, _ in held[-1]]
        with pytest.MonkeyPatch.context() as mp:
            eng = _pipeline_engine(model)
            log = _watch(eng, mp)
            ahead = _drive(eng, reqs)
        runs[kind] = {"ahead": ahead, "held": held, "log": log, "engine": eng,
                      "held_engine": held_eng, "requests": reqs}
    return runs


def test_running_ahead_is_invisible_where_every_finish_is_foreseen(pipelined):
    """Token for token, finish for finish and admission step for admission
    step, after EVERY ``step()`` call: where each finish is by length, every
    admission meets an empty pipeline, and dispatching step N+1 before step
    N is read changes nothing a caller can see — through slots that churn,
    a pool grown mid-flight and requests of one and two tokens."""
    run = pipelined["steady"]
    assert run["ahead"] == run["held"]
    final = {rid: (toks, why) for rid, toks, why in run["ahead"][-1]}
    assert [len(final[i][0]) for i in (2, 3)] == [1, 2]
    assert all(why == "length" for _, why in final.values())
    assert run["log"]["grown_in_flight"] >= 1 and run["log"]["admitted_over_stale"] == 0
    assert run["engine"].metrics.steps_ahead > 0


def test_running_ahead_serves_the_held_off_engines_tokens(pipelined):
    """Every request gets, token for token and with the same finish
    reason, what the engine held off gives it, through eos finishes found
    one device step late and a slot admitted again while its last tenant's
    stale step is in flight. (Such a request starts behind that step and
    trails the held-off engine's calls by one: only what it is given is
    compared, not when.)"""
    run = pipelined["mixed"]
    final = {rid: (toks, why) for rid, toks, why in run["ahead"][-1]}
    assert final == {rid: (toks, why) for rid, toks, why in run["held"][-1]}
    reqs = run["requests"]
    assert len(final) == len(reqs) and all(why for _, why in final.values())
    assert final[5] == ((reqs[5].eos_id,), "eos")           # on its first token
    for i in (1, 6):                                        # found at a decode step
        toks, why = final[i]
        assert why == "eos" and 1 < len(toks) < reqs[i].max_new_tokens
        assert toks[-1] == reqs[i].eos_id and reqs[i].eos_id not in toks[:-1]
    assert run["log"]["admitted_over_stale"] >= 1
    # no call made more than one step's tokens visible
    for before, after in zip(run["ahead"], run["ahead"][1:]):
        seen = {rid: len(toks) for rid, toks, _ in before}
        assert all(len(toks) - seen.get(rid, len(toks) - 1) <= 1 for rid, toks, _ in after)


def test_steps_ahead_counts_the_steps_that_ran_ahead(pipelined):
    """``steps_ahead`` + the steps in today's order = ``decode_steps``,
    both counted here from the order of dispatches and fetches alone; the
    mechanism engaged, and never when held off; one fetch a counted step
    and one a first token, as before steps overlapped."""
    for kind in ("steady", "mixed"):
        run = pipelined[kind]
        eng = run["engine"]
        m, held = eng.metrics, run["held_engine"].metrics
        n_ahead, n_in_order = _steps_ahead_of(run["log"]["events"])
        assert n_ahead == m.steps_ahead > 0 and n_in_order > 0
        assert n_ahead + n_in_order == m.decode_steps
        assert held.steps_ahead == 0
        for metrics in (m, held):
            assert metrics.device_fetches == metrics.decode_steps + metrics.requests_started
            assert metrics.slot_programs == 2 * len(run["requests"])
        assert m.decode_tokens == held.decode_tokens
        assert m.decode_live_sum == held.decode_live_sum
        snap = eng.stats_snapshot()
        assert snap["steps_ahead"] == m.steps_ahead and snap["decode_steps"] == m.decode_steps
        assert m.summary()["steps_ahead"] == m.steps_ahead
        # a step whose row is on its last token by length is never run past
        assert eng._inflight is None
    steady = pipelined["steady"]
    assert steady["engine"].metrics.decode_steps == steady["held_engine"].metrics.decode_steps


def test_a_step_in_flight_keeps_the_table_it_was_dispatched_with(setup):
    """The next step's plan writes the host's block table while the step
    before it runs: what was uploaded for that step is a copy of its own,
    also at full width, where the slice is the whole contiguous array and
    an upload that aliases its source (the CPU backend does, alignment
    permitting) would show the device the later writes."""
    cfg, params = setup
    for _ in range(12):     # an alias needs the host array aligned by chance
        eng = Engine(params, cfg, ServeConfig(slots=3, max_len=16, kv_block=8))
        eng._table[:] = 5
        eng._table_dirty = True
        eng._set_attended(eng._m_total)
        assert eng._table_dev.shape == eng._table.shape
        eng._table[:] = 7
        assert (np.asarray(eng._table_dev) == 5).all()


def _two_long_requests(cfg, eos=None):
    return [Request(prompt=p, max_new_tokens=10, eos_id=eos)
            for p in _prompts(cfg, [6, 9], seed=22)]


def test_one_step_call_makes_one_steps_tokens_visible(setup):
    """What the drivers' pre-rolls count on: every ``step()`` call adds
    exactly one token to every decoding request and one to ``decode_steps``,
    whether or not the step after it is already in flight."""
    cfg, params = setup
    eng = Engine(params, cfg, ServeConfig(slots=2, max_len=32, kv_block=8))
    rids = [eng.submit(r) for r in _two_long_requests(cfg)]
    in_flight = []
    for call in range(1, 10):
        eng.step()
        assert eng.metrics.decode_steps == call
        assert [len(eng.completion_of(rid).tokens) for rid in rids] == [call + 1] * 2
        in_flight.append(eng._inflight is not None)
    assert all(in_flight[:8]) and not in_flight[8]   # the ninth is the last token's step
    assert eng.metrics.steps_ahead == 8


@pytest.mark.parametrize("how", ["run", "run_to_an_eos", "close", "reset_metrics"])
def test_a_step_in_flight_is_coped_with(setup, monkeypatch, how):
    """``run()``, ``close()`` and ``reset_metrics()`` with a step in flight
    leave no buffer unread and no request unfinished: ``run()`` ends with
    nothing in flight, also when the last finish is an eos that was found
    with the next step already dispatched; ``close()`` waits for the step
    and drops it; ``reset_metrics()`` leaves it in flight and counts it,
    whole, where it is emitted."""
    cfg, params = setup
    with pytest.MonkeyPatch.context() as mp:
        _hold_off(mp)
        want = Engine(params, cfg, ServeConfig(slots=2, max_len=32, kv_block=8)).run(
            _two_long_requests(cfg))
    eos = want[0].tokens[5] if how == "run_to_an_eos" else None
    eng = Engine(params, cfg, ServeConfig(slots=2, max_len=32, kv_block=8, prefix=False))
    rids = [eng.submit(r) for r in _two_long_requests(cfg, eos)]
    eng.step()
    eng.step()
    step = eng._inflight
    assert step is not None and step.out is not None
    if how == "close":
        eng.close()
        assert eng._inflight is None
        assert all(a.is_ready() for a in jax.tree.leaves((eng.cache, eng.state)))
        return
    if how == "reset_metrics":
        eng.reset_metrics()
        assert eng._inflight is step
    got = eng.run()
    assert eng._inflight is None and eng.n_live == 0 and eng._pool.n_used == 0
    assert not np.asarray(eng.state.live).any() and not np.asarray(eng.cache.lengths).any()
    for rid in rids:
        toks = want[rid].tokens
        if eos in toks:
            toks = toks[:toks.index(eos) + 1]
        assert got[rid].tokens == toks and got[rid].finish_reason
    m = eng.metrics
    started = 0 if how == "reset_metrics" else 2
    assert m.device_fetches == m.decode_steps + started
    if how == "run_to_an_eos":
        assert "eos" in {c.finish_reason for c in got.values()}


@pytest.mark.parametrize("seed", [0, 7, 2**31, 2**40 + 9, -1])
def test_a_seeds_raw_key_is_jax_random_keys(seed):
    """An integer seed's raw key comes from one jitted program at admission
    and is ``jax.random.key``'s own, past 32 bits and below zero too."""
    from tony_tpu.serve.engine import _as_raw_key

    want = jax.random.key_data(jax.random.key(seed))
    for got in (_as_raw_key(seed, 3), _as_raw_key(None, seed)):
        assert got.dtype == jnp.uint32 and got.shape == (2,)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --- the seam between the engine and a model family's steps -------------------


@pytest.mark.parametrize("family", ["dense", "latent", "shortconv"])
def test_a_model_family_keeps_the_steps_contract(family):
    """What ``Engine`` asks of a family (docs/SERVE.md "Model families"):
    ``steps_for`` maps the configuration's class to ONE module with the
    four names, ``REFUSED_KNOBS`` and ``SCAN_STATE``; the configuration says what a cache
    row is (``cache_layout``), how many layers keep one (``cache_layers``)
    and what fixed-size state a slot keeps beside them (``slot_state``,
    None for two of the three); each of the three programs lowers through
    the engine's builder for every family and its LAST result is a dict
    (prefill: 5 results, the V rows None where the cache is one pool;
    decode: 4, the cache back with the structure it came with); and the
    engine refuses every key of the table by name."""
    from dataclasses import fields
    from functools import partial

    from tony_tpu.models.latent_moe import LatentMoEConfig
    from tony_tpu.models.shortconv_moe import ShortConvMoEConfig
    from tony_tpu.serve import dense, engine, latent, shortconv
    from tony_tpu.serve.cache import create_cache

    cfg, steps = {
        "dense": (llama.LlamaConfig.tiny(), dense),
        "latent": (LatentMoEConfig.tiny(), latent),
        "shortconv": (ShortConvMoEConfig.tiny(), shortconv),
    }[family]
    assert engine.steps_for(cfg) is steps
    for name in ("prefill_step", "tail_prefill_step", "decode_step", "init_params"):
        assert callable(getattr(steps, name)), name
    knobs = {f.name for f in fields(ServeConfig)} | {"block_handoff"}
    assert set(steps.REFUSED_KNOBS) <= knobs
    assert steps.SCAN_STATE is False     # none of the three scans its prompt into a state
    heads, width, pools = cfg.cache_layout

    S, blk, P, bucket, M = 2, 8, 5, 16, 2
    sds = jax.ShapeDtypeStruct
    params = jax.eval_shape(partial(steps.init_params, cfg=cfg), jax.random.key(0))
    sample = (sds((), jnp.int32), sds((), jnp.float32), sds((), jnp.int32),
              sds((), jnp.float32), sds((2,), jnp.uint32))
    out = engine._prefill_fn(cfg, bucket, 8).lower(
        params, sds((1, bucket), jnp.int32), *sample).out_info
    assert len(out) == 5 and isinstance(out[-1], dict)
    assert out[2].shape[0] == cfg.cache_layers and (out[3] is None) == (pools == 1)
    assert (cfg.slot_state is None) == (family != "shortconv")
    assert ("slot_state" in out[-1]) == (cfg.slot_state is not None)
    ctx = sds((cfg.cache_layers, 1, 2 * bucket, heads, width), cfg.dtype)
    slot_state = None
    if cfg.slot_state is not None:
        layers, row, dtype = cfg.slot_state
        slot_state = sds((layers, *row), dtype)
        assert out[-1]["slot_state"].shape == slot_state.shape
    out = engine._tail_fn(cfg, bucket, 8).lower(
        params, ctx, ctx if pools == 2 else None, sds((1, bucket), jnp.int32),
        sds((), jnp.int32), *sample, slot_state).out_info
    assert len(out) == 5 and isinstance(out[-1], dict)
    assert (out[3] is None) == (pools == 1)
    cache = jax.eval_shape(partial(create_cache, cfg, S, P, blk))
    assert (cache.v is None) == (pools == 1)
    assert (cache.slot_state is None) == (cfg.slot_state is None)
    state = engine._SlotState(
        sds((S,), jnp.int32), sds((S, 2), jnp.uint32), sds((S,), jnp.float32),
        sds((S,), jnp.int32), sds((S,), jnp.float32), sds((S,), jnp.int32),
        sds((S,), bool), sds((S,), bool))
    out = engine._decode_fn(cfg, "scan", blk, 8).lower(
        params, cache, sds((S, M), jnp.int32), state).out_info
    assert len(out) == 4 and isinstance(out[-1], dict)
    assert jax.tree.structure(out[0]) == jax.tree.structure(cache)

    real = steps.init_params(jax.random.key(0), cfg)
    base = dict(slots=S, max_len=32, kv_block=blk)
    if "prefix" in steps.REFUSED_KNOBS:     # the default ServeConfig is refused for it
        base["prefix"] = False
    for knob, (takes, _why) in steps.REFUSED_KNOBS.items():
        other = {"quant_kv": "int8", "decode_impl": "pallas"}.get(knob, not takes)
        with pytest.raises(NotImplementedError, match=knob):
            if knob == "block_handoff":     # no field: refused where called
                Engine(real, cfg, ServeConfig(**base)).export_prefix_blocks([1] * blk)
            else:
                Engine(real, cfg, ServeConfig(**{**base, knob: other}))


# --- the layer's projections stay the plain formula ---------------------------


def _plain_rope(t, cos, sin):
    half = t.shape[-1] // 2
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], axis=-1)


def _plain_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


@pytest.mark.parametrize("lead", [(5,), (5, 3), (2, 5), "vmap"],
                         ids=["S", "S-G", "B-S", "vmap"])
def test_layer_keeps_the_plain_formula_bitwise(setup, lead):
    """``generate.layer`` holds its q and k products apart from the reshape
    and rope (an optimization barrier, for the chip's compiler: PERF.md §6
    PR 30); in float32 the queries, the keys and the layer's result are
    bit for bit the formula written out here — product, reshape to heads,
    rope — for one row a slot ``[S]``, drafted positions ``[S, G]`` and a
    prompt ``[B, S]``, jitted, and under ``jax.vmap``."""
    from tony_tpu.models.generate import layer

    cfg, params = setup
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    H, Hkv, hd, rep = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.n_heads // cfg.n_kv_heads
    shape = (4, 5) if lead == "vmap" else lead
    kx, ka = jax.random.split(jax.random.key(3))
    x = jax.random.normal(kx, (*shape, cfg.dim), jnp.float32)
    ang = jax.random.uniform(ka, (*shape, 1, hd // 2), jnp.float32, 0.0, 6.0)
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    def attend(q, k, v):
        return q * jnp.repeat(k + v, rep, axis=-2), (q, k)

    def changed(x, cos, sin):
        return layer(x, lp, cfg, attend, lambda t: _plain_rope(t, cos, sin))

    def plain(x, cos, sin):
        rows = x.shape[:-1]
        h = _plain_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = _plain_rope((h @ lp["wq"]).reshape(*rows, H, hd), cos, sin)
        k = _plain_rope((h @ lp["wk"]).reshape(*rows, Hkv, hd), cos, sin)
        v = (h @ lp["wv"]).reshape(*rows, Hkv, hd)
        y = x + attend(q, k, v)[0].reshape(*rows, H * hd) @ lp["wo"]
        h2 = _plain_norm(y, lp["ffn_norm"], cfg.norm_eps)
        return y + (jax.nn.silu(h2 @ lp["w1"]) * (h2 @ lp["w3"])) @ lp["w2"], (q, k)

    fn = jax.vmap(changed) if lead == "vmap" else changed
    got = jax.jit(fn)(x, cos, sin)
    want = jax.jit(plain)(x, cos, sin)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
