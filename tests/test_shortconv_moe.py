"""The short-convolution / attention hybrid with routed experts
(models/shortconv_moe.py, serve/shortconv.py, the engine's per-slot state)
against the plain reference the benchmark keeps
(benchmark/reference/shortconv_moe_decoder.py: float32, the convolution as
shifted multiply-adds over the whole sequence, a loop over experts, nothing of
the program), at test sizes on the CPU in float32: logits, never sampled
tokens.

Tolerance 2e-4 (absolute and relative) on logits of order 1: program and
reference are both float32 and differ in the ORDER of their sums only (the
reference multiplies at precision ``highest`` one sequence at a time, the
program batches rows and sums an expert layer's routes per tile); a wrong tap,
a state taken at the bucket's end or a gate from the biased score reads 1e-2
and more (benchmark/tests/test_shortconv_moe.py plants them).
"""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import shortconv_moe_decoder as ref
from tony_tpu.models import latent_moe
from tony_tpu.models import shortconv_moe as sm
from tony_tpu.models.llama import LlamaConfig, init_params as llama_init
from tony_tpu.parallel.moe import GroupRouting, route_group_limited
from tony_tpu.serve import shortconv as steps
from tony_tpu.serve.cache import block_bytes, create_cache, slot_state_bytes
from tony_tpu.serve.engine import Engine, Request, ServeConfig

TOL = dict(rtol=2e-4, atol=2e-4)


def sizes(cfg: sm.ShortConvMoEConfig) -> dict:
    """The reference's size dict for a program configuration."""
    return {
        "d": cfg.dim, "h": cfg.n_heads, "kv": cfg.n_kv_heads, "hd": cfg.head_dim,
        "K": cfg.conv_kernel, "f": cfg.ffn_dim, "fm": cfg.moe_ffn_dim, "e": cfg.n_experts,
        "n_local": cfg.n_local, "first": cfg.first_expert, "k": cfg.top_k,
        "scale": cfg.routed_scale, "norm_topk": cfg.norm_topk_prob, "v": cfg.vocab_size,
        "layer_types": cfg.layer_types, "dense": cfg.n_dense_layers,
        "theta": cfg.rope_theta, "eps": cfg.norm_eps,
    }


def share_of(params: dict, first: int, n: int) -> dict:
    """The tree a holder of experts ``[first, first + n)`` has."""
    moe = dict(params["moe_ffns"])
    for name in ("w1", "w3", "w2"):
        moe[name] = moe[name][:, first:first + n]
    return {**params, "moe_ffns": moe}


@pytest.fixture(scope="module")
def model():
    cfg = sm.ShortConvMoEConfig.tiny()
    return cfg, sm.init_params(jax.random.key(7), cfg)


def tokens_of(seed: int, n: int, vocab: int = 256) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, vocab, size=n).astype(np.int32)


def _engine(model, **serve):
    cfg, params = model
    base = dict(slots=3, max_len=96, kv_block=8, prefill_buckets=(16, 32, 64), prefix=False)
    base.update(serve)
    return Engine(params, cfg, ServeConfig(**base))


def _capture_logits(monkeypatch):
    """Every ``sample_tokens`` call of the family's steps leaves its logits
    here (also from inside a jitted program) and answers greedily."""
    seen = []

    def fake(logits, *a, **k):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), logits, ordered=True)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    monkeypatch.setattr(steps, "sample_tokens", fake)
    return seen


# --- (a) the whole model against the reference ----------------------------------


@pytest.mark.parametrize("first,n_local", [(0, 0), (0, 4), (4, 4)])
def test_full_forward_matches_the_reference_logits(model, first, n_local):
    cfg, params = model
    cfg = replace(cfg, first_expert=first, n_local_experts=n_local)
    p = share_of(params, first, cfg.n_local)
    toks = tokens_of(1, 48)
    got = sm.forward(p, jnp.asarray(toks)[None], cfg)[0]
    want = ref.forward(p, jnp.asarray(toks), sizes(cfg))
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("kernel", [False, True], ids=["scan", "kernel"])
def test_engine_prefill_then_decode_matches_the_reference_logits(model, monkeypatch,
                                                                 paged_kernel, kernel):
    """Through the ``Engine``: request A (11 tokens, bucket 16) is prefilled
    and decodes three steps alone, then request B (27 tokens, bucket 32) is
    admitted while A decodes — behind A's fourth step, which is in flight by
    then, so B decodes from the fifth; 11 and 9 decode steps. Every logit row the
    programs sampled from — the padded-bucket prefill's, then each decode
    step's through both kinds of state — against the reference's full forward
    of prompt + served tokens. Both forms of the paged attention."""
    paged_kernel(kernel)
    cfg, params = model
    cfg = replace(cfg, max_seq_len=127 - kernel)     # programs of this test's own
    seen = _capture_logits(monkeypatch)
    eng = Engine(params, cfg, ServeConfig(slots=2, max_len=96, kv_block=8,
                                          prefill_buckets=(16, 32), prefix=False))
    prompts, budget = [tokens_of(10, 11), tokens_of(11, 27)], [12, 10]
    rid_a = eng.submit(Request(prompt=prompts[0], max_new_tokens=budget[0]))
    for _ in range(3):
        eng.step()
    rid_b = eng.submit(Request(prompt=prompts[1], max_new_tokens=budget[1]))
    done = eng.run()
    jax.effects_barrier()
    prefills = [x for x in seen if x.shape[0] == 1]
    decodes = [x for x in seen if x.shape[0] == 2]
    assert len(prefills) == 2 and len(decodes) == 13
    s = sizes(cfg)
    for slot, (rid, first_step) in enumerate([(rid_a, 0), (rid_b, 4)]):
        p, toks = prompts[slot], done[rid].tokens
        assert len(toks) == budget[slot]
        seq = np.concatenate([p, np.asarray(toks[:-1], np.int32)])
        want = np.asarray(ref.forward(params, jnp.asarray(seq), s))
        np.testing.assert_allclose(prefills[slot][0], want[len(p) - 1], **TOL)
        for j in range(len(toks) - 1):
            np.testing.assert_allclose(decodes[first_step + j][slot], want[len(p) + j], **TOL)
    assert eng.metrics.state_handoffs == 2 and eng.n_live == 0


# --- (b) the bucket trap ----------------------------------------------------------


def _prefill(params, cfg, prompt, bucket):
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    return steps.prefill_step(
        params, jnp.asarray(padded), jnp.int32(len(prompt) - 1), jnp.float32(0), jnp.int32(0),
        jnp.float32(0), jnp.zeros((2,), jnp.uint32), cfg=cfg, bucket=bucket, max_top_k=8)


@pytest.mark.parametrize("plen", [1, 2, 11, 16])
def test_the_same_prompt_through_two_buckets_gives_the_same_state_and_logits(model, monkeypatch,
                                                                              plen):
    """The convolution state is the ``B * u`` rows at the prompt's TRUE last
    two positions, not at the bucket's end: a prompt padded to 16 and to 32
    leaves the same state and the same logits (one- and two-token prompts
    keep zeros, or the one row there is, in front)."""
    cfg, params = model
    seen = _capture_logits(monkeypatch)
    prompt = tokens_of(3, plen)
    outs = [_prefill(params, cfg, prompt, b) for b in (16, 32)]
    jax.effects_barrier()
    (tok_a, _, ka, _, aux_a), (tok_b, _, kb, _, aux_b) = outs
    assert aux_a["slot_state"].shape == (cfg.n_conv_layers, cfg.state_width)
    np.testing.assert_allclose(aux_a["slot_state"], aux_b["slot_state"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(seen[0], seen[1], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ka[:, :, :plen], kb[:, :, :plen], rtol=1e-5, atol=1e-6)
    assert int(tok_a) == int(tok_b) and int(aux_a["moe_tokens"]) == plen
    # the state is what the reference's convolution would read at the next position
    if plen < cfg.conv_kernel - 1:
        older = np.asarray(aux_a["slot_state"])[:, :(cfg.conv_kernel - 1 - plen) * cfg.dim]
        assert not older.any()


# --- (c) chunked prefill, (d) a freed slot, (e) a dead slot ------------------------


def _serve(model, prompts, budget=6, **serve):
    eng = _engine(model, **serve)
    rids = [eng.submit(Request(prompt=p, max_new_tokens=budget)) for p in prompts]
    done = eng.run()
    return eng, [done[r].tokens for r in rids]


def test_chunked_prefill_equals_one_piece_prefill(model):
    """A 41-token prompt in chunks of 16 (each chunk starts from the state
    and the K/V its predecessor left) serves the tokens of the one-piece
    prefill and leaves the same convolution state and K/V."""
    prompts = [tokens_of(5, 41)]
    whole, toks_whole = _serve(model, prompts, slots=1)
    chunked, toks_chunked = _serve(model, prompts, slots=1, chunk_tokens=16)
    assert toks_whole == toks_chunked
    np.testing.assert_allclose(whole.cache.slot_state, chunked.cache.slot_state,
                               rtol=1e-5, atol=1e-6)
    # admission + two chunk boundaries, against one admission
    assert (whole.metrics.state_handoffs, chunked.metrics.state_handoffs) == (1, 3)
    cfg, params = model
    seq = np.concatenate([prompts[0], np.asarray(toks_whole[0][:-1], np.int32)])
    lg = np.asarray(sm.forward(params, jnp.asarray(seq)[None], cfg)[0, 40:])
    assert (lg.max(-1) - lg[np.arange(6), toks_whole[0]]).max() < 1e-4


@pytest.mark.parametrize("serve", [{}, {"chunk_tokens": 16}], ids=["whole", "chunked"])
def test_a_freed_slot_taken_by_a_new_request_starts_from_zero_state(model, serve):
    """The one slot serves a request, is freed, and its state is poisoned
    with NaN: the next request (its first chunk READS the slot's state) is
    served as a fresh engine serves it."""
    first, second = tokens_of(6, 20), tokens_of(7, 37)
    eng = _engine(model, slots=1, **serve)
    eng.run([Request(prompt=first, max_new_tokens=4)])
    assert np.isfinite(np.asarray(eng.cache.slot_state)).all()
    eng.cache = eng.cache._replace(slot_state=jnp.full_like(eng.cache.slot_state, jnp.nan))
    rid = eng.submit(Request(prompt=second, max_new_tokens=6))
    toks = eng.run()[rid].tokens
    _, fresh = _serve(model, [second], slots=1, **serve)
    assert toks == fresh[0]
    assert np.isfinite(np.asarray(eng.cache.slot_state)).all()


def test_a_dead_slot_s_state_is_neither_read_nor_written(model):
    """Three slots, one request: NaN in the two dead slots' state changes no
    live row, and stays where it is."""
    prompt = tokens_of(8, 19)
    _, clean = _serve(model, [prompt], budget=8)
    eng = _engine(model)
    rid = eng.submit(Request(prompt=prompt, max_new_tokens=8))
    eng.step()
    poisoned = eng.cache.slot_state.at[:, 1:].set(jnp.nan)
    eng.cache = eng.cache._replace(slot_state=poisoned)
    assert eng.run()[rid].tokens == clean[0]
    state = np.asarray(eng.cache.slot_state)
    assert np.isfinite(state[:, 0]).all() and np.isnan(state[:, 1:]).all()


# --- (f) the router -----------------------------------------------------------------


def _routing(cfg):
    return GroupRouting(n_experts=cfg.n_experts, top_k=cfg.top_k, routed_scale=cfg.routed_scale,
                        norm_topk_prob=cfg.norm_topk_prob)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_router_matches_one_group_routing_and_the_reference(model, seed):
    cfg, params = model
    ff = jax.tree.map(lambda a: a[1], params["moe_ffns"])
    h = jax.random.normal(jax.random.key(seed), (23, cfg.dim))
    sel, gates = route_group_limited(h, ff["router"], ff["router_bias"], _routing(cfg))
    want_gates, chosen = ref.route(h, ff, sizes(cfg))
    dense = np.zeros((23, cfg.n_experts), np.float32)
    np.put_along_axis(dense, np.asarray(sel), np.asarray(gates), axis=1)
    np.testing.assert_allclose(dense, want_gates, rtol=1e-5, atol=1e-6)
    assert (np.asarray(chosen).sum(-1) == cfg.top_k).all()
    assert (np.take_along_axis(np.asarray(chosen), np.asarray(sel), axis=1)).all()


def test_router_hand_worked_row_bias_selects_but_does_not_gate():
    """Four experts, top-2, scores sigmoid(0) = 0.5, sigmoid(1), sigmoid(-1),
    sigmoid(2): unbiased the choice is experts 3 and 1; a bias of +0.5 on
    expert 2 and -0.5 on expert 3 makes it experts 1 and 2 — and the gates
    are their UNBIASED scores over their sum."""
    logits = jnp.asarray([[0.0, 1.0, -1.0, 2.0]])
    router = jnp.eye(4)
    routing = GroupRouting(n_experts=4, top_k=2)
    sel, _ = route_group_limited(logits, router, jnp.zeros(4), routing)
    assert sorted(np.asarray(sel)[0]) == [1, 3]
    bias = jnp.asarray([0.0, 0.0, 0.5, -0.5])
    sel, gates = route_group_limited(logits, router, bias, routing)
    s = 1 / (1 + np.exp(-np.asarray(logits[0])))
    assert list(np.asarray(sel)[0]) == [2, 1]      # 0.769 biased against 0.731
    np.testing.assert_allclose(np.asarray(gates)[0], s[[2, 1]] / (s[2] + s[1]), rtol=1e-6)
    want, _ = ref.route(logits, {"router": router, "router_bias": bias},
                        {"k": 2, "norm_topk": True, "scale": 1.0})
    np.testing.assert_allclose(np.asarray(want)[0, [2, 1]], np.asarray(gates)[0], rtol=1e-5)


# --- (g) the shares of an expert layer -----------------------------------------------


@pytest.mark.parametrize("tokens", [6, 40])    # one tile an expert; sorted grouped tiles
def test_two_holders_of_half_the_experts_add_up_to_the_layer(model, tokens):
    cfg, params = model
    ff = jax.tree.map(lambda a: a[2], params["moe_ffns"])
    h = jax.random.normal(jax.random.key(3), (tokens, cfg.dim))
    whole, routes = sm.expert_ffn(h, ff, cfg)
    half = cfg.n_experts // 2
    parts = []
    for first in (0, half):
        c = replace(cfg, first_expert=first, n_local_experts=half)
        mine = {**ff, **{k: ff[k][first:first + half] for k in ("w1", "w3", "w2")}}
        y, r = sm.expert_ffn(h, mine, c)
        np.testing.assert_array_equal(r, routes[first:first + half])
        parts.append(y)
    np.testing.assert_allclose(parts[0] + parts[1], whole, **TOL)
    np.testing.assert_allclose(whole, ref.experts(h, ff, sizes(cfg)), **TOL)
    assert int(routes.sum()) == tokens * cfg.top_k


# --- (h) what the family refuses, by name ----------------------------------------------

REFUSALS = [
    ("prefix", {"prefix": True}),
    ("quant_kv", {"quant_kv": "int8"}),
    ("quant_weights", {"quant_weights": True}),
    ("spec", {"spec": True}),
    ("decode_impl", {"decode_impl": "pallas"}),
    ("block_handoff", {}),      # no ServeConfig field: refused where it is called
]


@pytest.mark.parametrize("knob,serve", REFUSALS)
def test_engine_refuses_what_the_family_lacks_by_name(model, knob, serve):
    with pytest.raises(NotImplementedError, match=knob):
        eng = _engine(model, **serve)
        eng.export_prefix_blocks(list(range(16)))


def test_every_refused_knob_of_the_family_has_a_case():
    assert {knob for knob, _ in REFUSALS} == set(steps.REFUSED_KNOBS)


def test_the_default_serve_config_is_refused_for_its_prefix_store(model):
    cfg, params = model
    with pytest.raises(NotImplementedError, match="prefix.*convolution state"):
        Engine(params, cfg, ServeConfig(slots=2))


# --- (i) the other families keep no per-slot state ---------------------------------------


@pytest.mark.parametrize("family", ["dense", "latent"])
def test_the_dense_and_latent_families_allocate_no_per_slot_state(family):
    if family == "dense":
        cfg = LlamaConfig.tiny()
        params = llama_init(jax.random.key(0), cfg)
    else:
        cfg = latent_moe.LatentMoEConfig.tiny()
        params = latent_moe.init_params(jax.random.key(0), cfg)
    assert cfg.slot_state is None and cfg.cache_layers == cfg.n_layers
    assert slot_state_bytes(cfg, 64) == 0
    eng = Engine(params, cfg, ServeConfig(slots=2, max_len=64, kv_block=8,
                                          prefill_buckets=(16, 32)))
    assert eng.cache.slot_state is None and len(jax.tree.leaves(eng.cache)) in (2, 3)
    eng.run([Request(prompt=tokens_of(1, 20), max_new_tokens=4)])
    snap = eng.stats_snapshot()
    assert snap["slot_state_bytes"] == 0 and snap["state_handoffs"] == 0
    assert eng.cache.slot_state is None


# --- the pieces ----------------------------------------------------------------------------


def test_the_published_list_is_walked_as_runs_of_equal_layers():
    cfg = sm.ShortConvMoEConfig()
    assert cfg.n_layers == 24 and cfg.n_conv_layers == 18 and cfg.n_attn_layers == 6
    assert [i for i, t in enumerate(cfg.layer_types) if t == sm.ATTENTION] == [2, 6, 10, 14, 18, 21]
    runs = cfg.runs
    assert sum(r.n for r in runs) == 24 and runs[0] == sm.Run(sm.CONV, False, 0, 0, 2)
    # not periodic: two convolution layers, not three, before the attention at 21
    assert [r.n for r in runs if r.op == sm.CONV] == [2, 3, 3, 3, 3, 2, 2]
    for r in runs:      # a run's indices continue where its kind's last run ended
        before = [q for q in runs[:runs.index(r)]]
        assert r.op0 == sum(q.n for q in before if q.op == r.op)
        assert r.ff0 == sum(q.n for q in before if q.moe == r.moe)
    with pytest.raises(ValueError, match="sliding"):
        sm.ShortConvMoEConfig(layer_types=("conv", "sliding"))


def test_param_count_matches_the_tree_and_the_published_cut(model):
    cfg, params = model
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.n_params
    cut = sm.ShortConvMoEConfig(layer_types=sm.PUBLISHED_LAYER_TYPES[:14])
    assert cut.n_params == 4_667_077_376          # 4.667 B: ISSUE 31's table
    whole = sm.ShortConvMoEConfig()
    assert round(whole.n_params / 1e9, 2) == 8.34
    shapes = jax.eval_shape(lambda k: sm.init_params(k, cut), jax.random.key(0))
    assert shapes["moe_ffns"]["w1"].shape == (12, 32, 2048, 1792)
    assert shapes["conv_layers"]["taps"].shape == (11, 3, 2048)
    assert shapes["moe_ffns"]["router"].dtype == jnp.float32 and "lm_head" not in shapes
    axes = sm.logical_axes(cut)
    for stack in ("conv_layers", "attn_layers", "dense_ffns", "moe_ffns"):
        assert {k: len(v) for k, v in axes[stack].items()} == {
            k: v.ndim for k, v in shapes[stack].items()}
    assert len(axes["tok_emb"]) == 2 and len(axes["final_norm"]) == 1


def test_the_pool_holds_attention_layers_only_and_the_state_is_beside_it(model):
    cfg, _ = model
    cache = create_cache(cfg, slots=3, n_blocks=5, block=8)
    # 2 attention layers of 7; the 2 K/V heads of 16 share one row of 32
    assert cfg.kv_pack == 2 and cfg.cache_layout == (1, 32, 2)
    assert cache.k.shape == cache.v.shape == (2, 5, 1, 8, 32)
    assert cache.slot_state.shape == (5, 3, 2 * 64) and not cache.slot_state.any()
    assert block_bytes(cfg, 8) == 2 * 2 * 2 * 16 * 8 * 4
    assert slot_state_bytes(cfg, 3) == 5 * 3 * 128 * 4
    cut = sm.ShortConvMoEConfig(layer_types=sm.PUBLISHED_LAYER_TYPES[:14])
    assert cut.cache_layout == (4, 128, 2) and cut.cache_layers == 3
    assert block_bytes(cut, 64) == 64 * 6144      # 6 KB a token
    assert slot_state_bytes(cut, 64) == 64 * 11 * 8192


def test_packed_rows_attend_as_the_unpacked_heads_do(model):
    """Two K/V heads side by side in a row, each query head in its own
    head's columns: the paged attention's output, unpacked, is plain
    grouped-query attention over the unpacked rows."""
    from tony_tpu.ops.decode_attention import decode_attention

    cfg, _ = model
    S, B, M = 3, 8, 2
    k0, k1 = jax.random.split(jax.random.key(0))
    q = jax.random.normal(k0, (S, cfg.n_heads, cfg.head_dim))
    kv = jax.random.normal(k1, (2, S * M + 1, B, cfg.n_kv_heads, cfg.head_dim))
    lengths = jnp.asarray([5, 16, 9], jnp.int32)
    table = 1 + jnp.arange(S * M, dtype=jnp.int32).reshape(S, M)
    plain = decode_attention(q, *kv.transpose(0, 1, 3, 2, 4), lengths, tables=table, block=B)
    packed = steps._pack_rows(kv, cfg).transpose(0, 1, 3, 2, 4)    # [2, P, Hkv/pack, B, pack*hd]
    out = decode_attention(steps._pack_queries(q, cfg), *packed, lengths, tables=table,
                           block=B, scale=cfg.head_dim ** -0.5)
    np.testing.assert_allclose(steps._unpack_outputs(out, cfg), plain, rtol=1e-5, atol=1e-5)


def test_the_sequence_form_and_the_token_form_of_the_convolution_agree():
    """Rows fed one at a time through ``conv_token`` from a zero state give
    what ``conv_sequence`` gives over the whole sequence, and the same state;
    a sequence continued from a predecessor's state equals the whole."""
    D, S, K = 8, 9, 3
    z = jax.random.normal(jax.random.key(0), (1, S, D))
    taps = jax.random.normal(jax.random.key(1), (K, D))
    zero = jnp.zeros((1, (K - 1) * D))
    c, state = sm.conv_sequence(z, taps, zero, jnp.int32(S - 1))
    want = np.zeros((S, D), np.float32)
    zz = np.concatenate([np.zeros((K - 1, D), np.float32), np.asarray(z[0])])
    for t in range(S):
        want[t] = sum(np.asarray(taps[j]) * zz[t + j] for j in range(K))
    np.testing.assert_allclose(c[0], want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(state[0], np.asarray(z[0, -2:]).reshape(-1))
    prev, rows = zero, []
    for t in range(S):
        row, prev = sm.conv_token(z[:, t], taps, prev)
        rows.append(row[0])
    np.testing.assert_allclose(jnp.stack(rows), c[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(prev, state)
    # the first 4 rows, then the rest from the state they left (padded to 8)
    _, mid = sm.conv_sequence(jnp.pad(z[:, :4], ((0, 0), (0, 4), (0, 0))), taps, zero, jnp.int32(3))
    c2, end = sm.conv_sequence(z[:, 4:], taps, mid, jnp.int32(S - 5))
    np.testing.assert_allclose(c2[0], c[0, 4:], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(end, state)


def test_capacity_analysis_counts_the_per_slot_state(model):
    from tony_tpu.serve.capacity import decode_step_analysis, derive_slot_budget

    cfg, _ = model
    a = decode_step_analysis(cfg, slots=2, capacity=32, kv_block=8)
    assert a["slot_state_bytes"] == slot_state_bytes(cfg, 2) == 5 * 2 * 128 * 4
    assert a["kv_bytes_per_slot"] == 4 * block_bytes(cfg, 8)
    dense = decode_step_analysis(LlamaConfig.tiny(), slots=2, capacity=32, kv_block=8)
    assert dense["slot_state_bytes"] == 0
    budget = derive_slot_budget(cfg, max_len=32, hbm_bytes=64 * 2**20, kv_block=8)
    assert budget["slot_state_bytes_per_slot"] == 5 * 128 * 4
    per_slot = (budget["kv_bytes_per_slot_native"] + budget["per_slot_temp_bytes"]
                + budget["slot_state_bytes_per_slot"])
    room = (budget["hbm_bytes"] - budget["param_bytes"] - budget["fixed_temp_bytes"]
            - budget["generated_code_bytes"])
    assert budget["max_slots_native"] == room // per_slot > 0


# --- the engine, end to end -------------------------------------------------------------------


@pytest.mark.parametrize("serve", [{}, {"chunk_tokens": 16}], ids=["plain", "chunked"])
def test_engine_serves_mixed_requests_and_returns_every_slot_and_block(model, serve):
    """6 requests of mixed lengths over 3 slots finish with the greedy tokens
    of the full forward, and afterwards no slot is live and no block is held."""
    cfg, params = model
    eng = _engine(model, prefill_buckets=(16, 64), shrink=False, **serve)
    prompts = [tokens_of(i, n) for i, n in enumerate([5, 17, 41, 9, 1, 64])]
    rids = [eng.submit(Request(prompt=p, max_new_tokens=4 + i)) for i, p in enumerate(prompts)]
    done = eng.run()
    for rid, p in zip(rids, prompts):
        toks = done[rid].tokens
        seq = np.concatenate([p, np.asarray(toks[:-1], np.int32)])
        lg = np.asarray(sm.forward(params, jnp.asarray(seq)[None], cfg)[0, len(p) - 1:])
        # greedy, so each served token is the row's best up to float32 rounding
        assert (lg.max(-1) - lg[np.arange(len(toks)), toks]).max() < 1e-4
    assert eng.n_live == 0 and eng.queue_depth == 0 and eng._pool.n_used == 0
    assert eng.metrics.kv_bytes_per_token == 2 * 2 * 2 * 16 * 4    # 2 attention layers of 7
    m = eng.metrics
    # every expert is local: each routed token leaves top_k routes a layer
    assert m.moe_routes.shape == (5, 8) and m.moe_routes.sum() == m.moe_tokens * 2 * 5
    assert m.moe_steps == m.decode_steps and (m.moe_experts_hit <= 8 * m.moe_steps).all()
    snap = eng.stats_snapshot()
    assert snap["slot_state_bytes"] == 5 * 3 * 128 * 4
    assert snap["state_handoffs"] == m.state_handoffs >= 6
    eng.reset_metrics()
    assert eng.metrics.slot_state_bytes == 5 * 3 * 128 * 4 and eng.metrics.state_handoffs == 0


def test_engine_through_the_paged_kernel_serves_the_scan_paths_tokens(model, paged_kernel):
    outs = []
    for kernel in (False, True):
        paged_kernel(kernel)
        eng = _engine(model, shrink=False)
        rids = [eng.submit(Request(prompt=tokens_of(i, n), max_new_tokens=5))
                for i, n in enumerate([5, 17, 30, 41])]
        done = eng.run()
        outs.append([done[r].tokens for r in rids])
    assert outs[0] == outs[1]


def test_engine_refuses_gang_block_handoff(model):
    eng = _engine(model)
    with pytest.raises(NotImplementedError, match="convolution state"):
        eng.adopt_blocks(list(range(8)), None)
