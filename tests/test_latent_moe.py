"""The latent-attention expert decoder (models/latent_moe.py, serve/latent.py,
parallel/moe.py's group-limited routing) against the plain reference the
benchmark keeps (benchmark/reference/latent_moe_decoder.py: float32, expanded
attention, a loop over experts, nothing of the program), at test sizes on the
CPU, float32 where equality is asked: logits, never sampled tokens.
"""

import math
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import latent_moe_decoder as ref
from tony_tpu.models import latent_moe as lm
from tony_tpu.models.llama import LlamaConfig, init_params as llama_init
from tony_tpu.parallel.moe import GroupRouting, local_expert_ffn, route_group_limited
from tony_tpu.serve import latent as steps
from tony_tpu.serve.cache import PagedKVCache, block_bytes, create_cache
from tony_tpu.serve.engine import Engine, Request, ServeConfig, _scatter_fn, _SlotState

TOL = dict(rtol=2e-4, atol=2e-4)


def sizes(cfg: lm.LatentMoEConfig) -> dict:
    """The reference's size dict for a program configuration."""
    return {
        "d": cfg.dim, "h": cfg.n_heads, "qr": cfg.q_lora_rank, "kr": cfg.kv_lora_rank,
        "nope": cfg.qk_nope_head_dim, "rope": cfg.qk_rope_head_dim, "vd": cfg.v_head_dim,
        "f": cfg.ffn_dim, "fm": cfg.moe_ffn_dim, "e": cfg.n_experts, "n_local": cfg.n_local,
        "first": cfg.first_expert, "shared": cfg.n_shared_experts, "k": cfg.top_k,
        "groups": cfg.n_groups, "topk_groups": cfg.topk_groups, "scale": cfg.routed_scale,
        "norm_topk": cfg.norm_topk_prob, "v": cfg.vocab_size, "layers": cfg.n_layers,
        "dense": cfg.n_dense_layers, "theta": cfg.rope_theta, "eps": cfg.norm_eps,
        "yarn": {"factor": cfg.rope_factor, "orig": cfg.rope_orig_max,
                 "beta_fast": cfg.rope_beta_fast, "beta_slow": cfg.rope_beta_slow,
                 "mscale": cfg.rope_mscale, "mscale_all_dim": cfg.rope_mscale_all_dim},
    }


def share_of(params: dict, first: int, n: int) -> dict:
    """The tree a holder of experts ``[first, first + n)`` has."""
    moe = dict(params["moe_layers"])
    for name in ("w1", "w3", "w2"):
        moe[name] = moe[name][:, first:first + n]
    return {**params, "moe_layers": moe}


@pytest.fixture(scope="module")
def model():
    cfg = lm.LatentMoEConfig.tiny()
    return cfg, lm.init_params(jax.random.key(7), cfg)


def tokens_of(seed: int, n: int, vocab: int = 256) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, vocab, size=n).astype(np.int32)


# --- the whole model against the reference ------------------------------------


@pytest.mark.parametrize("first,n_local", [(0, 0), (8, 8), (24, 8)])
def test_full_forward_matches_the_reference_logits(model, first, n_local):
    cfg, params = model
    cfg = replace(cfg, first_expert=first, n_local_experts=n_local)
    p = share_of(params, first, cfg.n_local)
    toks = tokens_of(1, 48)
    got = lm.forward(p, jnp.asarray(toks)[None], cfg)[0]
    want = ref.forward(p, jnp.asarray(toks), sizes(cfg))
    np.testing.assert_allclose(got, want, **TOL)


def _capture_logits(monkeypatch):
    seen = []

    def fake(logits, *a, **k):
        seen.append(np.asarray(logits))
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    monkeypatch.setattr(steps, "sample_tokens", fake)
    return seen


@pytest.mark.parametrize("kernel", [False, True], ids=["scan", "kernel"])
@pytest.mark.parametrize("first,n_local", [(0, 0), (16, 8)])
def test_prefill_then_paged_decode_matches_the_reference_logits(model, monkeypatch, paged_kernel,
                                                                 first, n_local, kernel):
    """Three slots of different prompt lengths: prefill each into the paged
    latent pool, then decode teacher-forced continuations through the block
    table — every logit row against the reference's full forward. Both forms
    of the paged attention: the XLA scan, and the Pallas kernel (interpreted)."""
    paged_kernel(kernel)
    cfg, params = model
    cfg = replace(cfg, first_expert=first, n_local_experts=n_local)
    params = share_of(params, first, cfg.n_local)
    s = sizes(cfg)
    seen = _capture_logits(monkeypatch)
    B, slots, n_dec, bucket = 8, 3, 6, 32
    plens = [9, 16, 27]
    seqs = [tokens_of(10 + i, p + n_dec) for i, p in enumerate(plens)]
    want = [np.asarray(ref.forward(params, jnp.asarray(q), s)) for q in seqs]
    blocks = bucket // B + 2
    cache = create_cache(cfg, slots, 1 + slots * blocks, B)
    assert cache.v is None and cache.k.shape == (3, 1 + slots * blocks, 1, B, 128)
    table = 1 + np.arange(slots * blocks, dtype=np.int32).reshape(slots, blocks)
    key = jnp.zeros((2,), jnp.uint32)
    for i, p in enumerate(plens):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :p] = seqs[i][:p]
        _, _, pk, pv, moe = steps.prefill_step(
            params, jnp.asarray(padded), jnp.int32(p - 1), jnp.float32(0), jnp.int32(0),
            jnp.float32(0), key, cfg=cfg, bucket=bucket, max_top_k=8)
        assert pv is None and pk.shape == (3, 1, bucket, 128)
        assert int(moe["moe_tokens"]) == p
        np.testing.assert_allclose(seen.pop()[0], want[i][p - 1], **TOL)
        pos = np.arange(bucket)
        pids = np.where(pos < p, table[i, np.minimum(pos // B, blocks - 1)], 0).astype(np.int32)
        offs = np.where(pos < p, pos % B, 0).astype(np.int32)
        cache = _scatter_fn()(cache, pk, None, jnp.asarray(pids), jnp.asarray(offs),
                              jnp.int32(i), jnp.int32(p))
    state = _SlotState(
        last_tok=jnp.zeros((slots,), jnp.int32), rng=jnp.zeros((slots, 2), jnp.uint32),
        temp=jnp.zeros((slots,)), top_k=jnp.zeros((slots,), jnp.int32),
        top_p=jnp.zeros((slots,)), eos=jnp.full((slots,), -1, jnp.int32),
        done=jnp.zeros((slots,), bool), live=jnp.ones((slots,), bool))
    for j in range(n_dec):
        fed = jnp.asarray([seqs[i][p + j] for i, p in enumerate(plens)], jnp.int32)
        cache, state, _, aux = steps.decode_step(
            params, cache, jnp.asarray(table), state._replace(last_tok=fed), cfg=cfg,
            kv_block=B, max_top_k=8)
        got = seen.pop()
        for i, p in enumerate(plens):
            np.testing.assert_allclose(got[i], want[i][p + j], **TOL)
        assert int(aux["moe_tokens"]) == slots
    assert cache.lengths.tolist() == [p + n_dec for p in plens]


def test_absorbed_attention_equals_expanded(model):
    """One layer's attention both ways on the same latents: keys and values
    expanded through wkv_b, against wkv_b folded into the query and applied
    after the softmax over the paged latent rows."""
    from tony_tpu.ops.decode_attention import latent_decode_attention

    cfg, params = model
    lp = jax.tree.map(lambda a: a[0], params["moe_layers"])
    S, B = 21, 8
    k1, k2, k3 = jax.random.split(jax.random.key(3), 3)
    lat = jax.random.normal(k1, (1, 24, cfg.latent_dim))
    q_nope = jax.random.normal(k2, (1, 24, cfg.n_heads, cfg.qk_nope_head_dim))
    q_rope = jax.random.normal(k3, (1, 24, cfg.n_heads, cfg.qk_rope_head_dim))
    exp = lm.expanded_attention(q_nope, q_rope, lat, lp["wkv_b"], jnp.arange(24), cfg)
    rows = jnp.pad(lat, ((0, 0), (0, 0), (0, cfg.cache_width - cfg.latent_dim)))
    pool = jnp.concatenate([jnp.zeros((1, 1, B, cfg.cache_width)),
                            rows.reshape(3, 1, B, cfg.cache_width)])
    w_uk, w_uv = lm.absorb(lp, cfg)
    q = jnp.concatenate([jnp.einsum("shn,chn->shc", q_nope[0, S - 1:S], w_uk),
                         q_rope[0, S - 1:S]], axis=-1)
    o = latent_decode_attention(q, pool, jnp.asarray([S]), jnp.asarray([[1, 2, 3]]),
                                v_width=cfg.kv_lora_rank, scale=lm.softmax_scale(cfg))
    got = jnp.einsum("shc,chv->shv", o, w_uv).reshape(-1)
    np.testing.assert_allclose(got, exp[0, S - 1], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("key_block", [4, 8, 12])
def test_expanded_attention_over_key_blocks_equals_one_block(model, key_block):
    """The running softmax over key blocks (the cell's contexts are several
    blocks of ``ATTN_KEY_BLOCK`` keys) against the whole context at once."""
    cfg, params = model
    lp = jax.tree.map(lambda a: a[0], params["moe_layers"])
    k1, k2, k3 = jax.random.split(jax.random.key(4), 3)
    lat = jax.random.normal(k1, (2, 24, cfg.latent_dim))
    q_nope = jax.random.normal(k2, (2, 24, cfg.n_heads, cfg.qk_nope_head_dim))
    q_rope = jax.random.normal(k3, (2, 24, cfg.n_heads, cfg.qk_rope_head_dim))
    whole = lm.expanded_attention(q_nope, q_rope, lat, lp["wkv_b"], jnp.arange(24), cfg)
    blocks = lm.expanded_attention(q_nope, q_rope, lat, lp["wkv_b"], jnp.arange(24), cfg,
                                   key_block=key_block)
    np.testing.assert_allclose(blocks, whole, rtol=1e-5, atol=1e-5)


# --- the router ------------------------------------------------------------------

ROUTING = GroupRouting(n_experts=32, top_k=4, n_groups=4, topk_groups=2, routed_scale=2.5)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_router_matches_the_reference(seed):
    ks = jax.random.split(jax.random.key(seed), 3)
    h = jax.random.normal(ks[0], (40, 64))
    lp = {"router": jax.random.normal(ks[1], (64, 32)) / 8, "router_bias": jax.random.normal(ks[2], (32,)) * 0.3}
    s = {"e": 32, "groups": 4, "topk_groups": 2, "k": 4, "scale": 2.5, "norm_topk": True}
    sel, gates = route_group_limited(h, lp["router"], lp["router_bias"], ROUTING)
    want, chosen = ref.route(h, lp, s)
    dense = np.zeros((40, 32), np.float32)
    np.put_along_axis(dense, np.asarray(sel), np.asarray(gates), axis=1)
    assert (np.asarray(chosen) == (dense > 0)).all()
    np.testing.assert_allclose(dense, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), 2.5, rtol=1e-5)


def _route_scores(scores, bias, routing=None):
    """Route one token whose sigmoid scores are ``scores`` exactly."""
    r = routing or GroupRouting(n_experts=8, top_k=2, n_groups=4, topk_groups=2, routed_scale=2.5)
    logit = np.log(np.asarray(scores) / (1 - np.asarray(scores)))
    sel, gates = route_group_limited(jnp.ones((1, 1)), jnp.asarray(logit, jnp.float32)[None],
                                     jnp.asarray(bias, jnp.float32), r)
    return sorted(zip(np.asarray(sel[0]).tolist(), np.asarray(gates[0]).tolist()))


def test_router_hand_worked_row_groups_limit_the_choice():
    # groups (0,1) (2,3) (4,5) (6,7); group scores .9+.1, .8+.7, .6+.5, .3+.2:
    # groups 1 and 2 stay, so expert 0 (the best score of all) is NOT chosen;
    # K = {2, 3}; gates 2.5 * .8/1.5 and 2.5 * .7/1.5
    got = _route_scores([.9, .1, .8, .7, .6, .5, .3, .2], [0.0] * 8)
    assert [e for e, _ in got] == [2, 3]
    np.testing.assert_allclose([g for _, g in got], [2.5 * .8 / 1.5, 2.5 * .7 / 1.5], rtol=1e-5)


def test_router_hand_worked_row_bias_selects_but_does_not_gate():
    # the bias lifts expert 7 (s = .2) to .2 + .9: group 3 scores 1.1 + .3 and
    # stays with group 1 (1.5); K = {2, 7}; the gates use s, not s + b:
    # 2.5 * .8/(.8 + .2) and 2.5 * .2/(.8 + .2)
    bias = [0, 0, 0, 0, 0, 0, 0, .9]
    got = _route_scores([.9, .1, .8, .7, .6, .5, .3, .2], bias)
    assert [e for e, _ in got] == [2, 7]
    np.testing.assert_allclose([g for _, g in got], [2.0, 0.5], rtol=1e-5)


def test_router_without_normalisation_keeps_the_raw_scores():
    r = GroupRouting(n_experts=8, top_k=2, n_groups=4, topk_groups=2, routed_scale=2.5,
                     norm_topk_prob=False)
    got = _route_scores([.9, .1, .8, .7, .6, .5, .3, .2], [0.0] * 8, r)
    np.testing.assert_allclose([g for _, g in got], [2.0, 1.75], rtol=1e-5)


# --- YaRN ------------------------------------------------------------------------


def test_yarn_frequencies_against_hand_computed_values():
    cfg = lm.LatentMoEConfig()      # the published numbers
    f = np.asarray(lm.yarn_freqs(cfg), np.float64)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    # c(32) = 10.47, c(1) = 22.5: low 10, high 23
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)          # untouched
    np.testing.assert_allclose(f[23:], plain[23:] / 40.0, rtol=1e-6)   # fully scaled
    r = (16 - 10) / 13.0
    np.testing.assert_allclose(f[16], plain[16] / 40 * r + plain[16] * (1 - r), rtol=1e-6)
    np.testing.assert_allclose(f, np.asarray(ref.yarn_freqs(sizes(cfg))), rtol=1e-6)


def test_yarn_softmax_scale_against_hand_computed_values():
    cfg = lm.LatentMoEConfig()
    m = 0.1 * math.log(40.0) + 1.0
    assert m == pytest.approx(1.3689, abs=1e-4)
    assert lm.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * 1.8739, rel=1e-4)
    cos, _ = lm.rope_cos_sin(cfg, jnp.asarray([0]))
    np.testing.assert_allclose(cos, 1.0)    # mscale(40, 1) / mscale(40, 1) = 1


# --- the share of an expert-parallel replica ---------------------------------------


@pytest.mark.parametrize("tokens", [6, 40])    # one tile an expert; sorted grouped tiles
def test_the_shares_add_up_to_the_uncut_layer(model, tokens):
    """The guide's share test: 4 holders of 8 experts each compute their part
    of an expert layer; the parts, with the shared expert counted once, are
    what the uncut reference gives for the whole layer."""
    cfg, params = model
    lp = jax.tree.map(lambda a: a[1], params["moe_layers"])
    h = jax.random.normal(jax.random.key(5), (tokens, cfg.dim))
    whole = ref.experts(h, lp, sizes(cfg))
    shared = ref.swiglu(h, lp["ws1"], lp["ws3"], lp["ws2"])
    total = jnp.zeros_like(h)
    for first in range(0, 32, 8):
        c = replace(cfg, first_expert=first, n_local_experts=8)
        part = {**lp, **{n: lp[n][first:first + 8] for n in ("w1", "w3", "w2")}}
        y, routes = lm.expert_ffn(h, part, c)
        total = total + (y - shared)
        # each holder alone agrees with the reference given the same share
        np.testing.assert_allclose(y, ref.experts(h, part, sizes(c)), **TOL)
    np.testing.assert_allclose(total + shared, whole, **TOL)


@pytest.mark.parametrize("first", [0, 8])
def test_one_tile_an_expert_equals_sorted_grouped_tiles(first):
    ks = jax.random.split(jax.random.key(11), 6)
    T, D, F, n = 24, 32, 16, 8
    flat = jax.random.normal(ks[0], (T, D))
    params = {"w1": jax.random.normal(ks[1], (n, D, F)) / 6, "w3": jax.random.normal(ks[2], (n, D, F)) / 6,
              "w2": jax.random.normal(ks[3], (n, F, D)) / 4}
    sel = jax.random.randint(ks[4], (T, 4), 0, 32)
    gates = jax.random.uniform(ks[5], (T, 4))
    small, r1 = local_expert_ffn(params, flat, sel, gates, first_expert=first, group_block=T)
    grouped, r2 = local_expert_ffn(params, flat, sel, gates, first_expert=first, group_block=8)
    np.testing.assert_allclose(small, grouped, rtol=1e-5, atol=1e-5)
    local = (np.asarray(sel) >= first) & (np.asarray(sel) < first + n)
    assert r1.tolist() == r2.tolist() == np.bincount(np.asarray(sel)[local] - first, minlength=n).tolist()


# --- the cache -------------------------------------------------------------------


def test_latent_cache_is_one_pool_of_576_value_rows_padded_to_the_lanes():
    """576 values a token a layer (512 + 64), one pool, no second; the row is
    padded with zeros to the chip's 128 lanes (the tiled memory pads a
    576-wide row to 640 anyway): 640 x 2 x L bytes a token."""
    cfg = lm.LatentMoEConfig(n_layers=6, n_dense_layers=1)
    assert cfg.latent_dim == 576 and cfg.cache_width == 640
    assert cfg.cache_layout == (1, 640, 1) and block_bytes(cfg, 64) == 64 * 640 * 2 * 6
    tiny = lm.LatentMoEConfig.tiny()
    assert tiny.latent_dim == 24 and tiny.cache_width == 128
    cache = create_cache(tiny, 4, 9, 8)
    assert cache.k.shape == (3, 9, 1, 8, 128) and cache.v is None and not cache.quantized
    with pytest.raises(NotImplementedError, match="quant_kv"):
        create_cache(tiny, 4, 9, 8, quant_kv="int8")


def test_dense_cache_layout_is_unchanged():
    cfg = LlamaConfig.tiny()
    assert cfg.cache_layout == (2, 16, 2)
    cache = create_cache(cfg, 4, 9, 8)
    assert cache.k.shape == cache.v.shape == (2, 9, 2, 8, 16)
    assert block_bytes(cfg, 8) == 2 * 2 * 2 * 8 * 16 * 4


def test_capacity_analysis_takes_the_latent_cache():
    from tony_tpu.serve.capacity import decode_step_analysis

    cfg = lm.LatentMoEConfig.tiny()
    a = decode_step_analysis(cfg, slots=2, capacity=32, kv_block=8)
    assert a["kv_bytes_per_slot"] == 4 * block_bytes(cfg, 8)
    assert a["cache_bytes"] == 3 * (1 + 2 * 4) * 8 * 128 * 4


# --- the engine ------------------------------------------------------------------


def _engine(model, **serve):
    cfg, params = model
    base = dict(slots=3, max_len=96, kv_block=8, prefill_buckets=(16, 32, 64))
    base.update(serve)
    return Engine(params, cfg, ServeConfig(**base))


@pytest.mark.parametrize("serve", [{}, {"chunk_tokens": 16}, {"prefix": False}],
                         ids=["plain", "chunked", "no-prefix"])
def test_engine_serves_mixed_requests_and_returns_every_slot_and_block(model, serve):
    """8 requests of mixed lengths (two share a 24-token prefix, so one takes
    the tail prefill) finish with the greedy tokens of the full forward, and
    afterwards no slot is live and no block is held but the prefix store's."""
    cfg, params = model
    eng = _engine(model, **serve)
    shared = tokens_of(99, 24)
    prompts = [tokens_of(i, n) for i, n in enumerate([5, 12, 17, 30, 41, 9])]
    prompts += [np.concatenate([shared, tokens_of(50, 6)]), np.concatenate([shared, tokens_of(51, 11)])]
    rids = [eng.submit(Request(prompt=p, max_new_tokens=4 + i)) for i, p in enumerate(prompts)]
    done = eng.run()
    for rid, p in zip(rids, prompts):
        toks = done[rid].tokens
        seq = np.concatenate([p, np.asarray(toks[:-1], np.int32)])
        lg = np.asarray(lm.forward(params, jnp.asarray(seq)[None], cfg)[0, len(p) - 1:])
        # greedy, so each served token is the row's best up to float32 rounding
        assert (lg.max(-1) - lg[np.arange(len(toks)), toks]).max() < 1e-4
    assert eng.n_live == 0 and eng.queue_depth == 0
    held = eng._store.n_nodes if eng._store is not None else 0
    assert eng._pool.n_used == held
    assert eng.metrics.kv_bytes_per_token == 128 * 4 * 3      # cache row x float32 x layers
    m = eng.metrics
    # every expert is local here: each routed token leaves top_k routes a layer
    assert m.moe_routes.shape == (2, 32) and m.moe_routes.sum() == m.moe_tokens * 4 * 2
    assert m.moe_steps == m.decode_steps and (m.moe_experts_hit <= 32 * m.moe_steps).all()
    assert (m.moe_experts_hit >= m.moe_steps).all()


def test_engine_through_the_paged_kernel_serves_the_scan_paths_tokens(model, paged_kernel):
    """The same requests end to end with the paged attention as the XLA scan
    and as the Pallas kernel (interpreted; on the chip the op picks it): the
    same greedy tokens."""
    outs = []
    for kernel in (False, True):
        paged_kernel(kernel)
        eng = _engine(model, shrink=False)
        rids = [eng.submit(Request(prompt=tokens_of(i, n), max_new_tokens=5))
                for i, n in enumerate([5, 17, 30, 41])]
        done = eng.run()
        outs.append([done[r].tokens for r in rids])
    assert outs[0] == outs[1]


def test_counters_count_the_local_share_only(model):
    cfg, params = model
    c = replace(cfg, first_expert=8, n_local_experts=8)
    eng = Engine(share_of(params, 8, 8), c, ServeConfig(slots=2, max_len=64, kv_block=8,
                                                         prefill_buckets=(32,)))
    eng.run([Request(prompt=tokens_of(3, 20), max_new_tokens=6)])
    m = eng.metrics
    assert m.moe_routes.shape == (2, 8) and m.moe_tokens == 20 + 5
    assert 0 < m.moe_routes.sum() < m.moe_tokens * 4 * 2


REFUSALS = [
    ("quant_kv", {"quant_kv": "int8"}),
    ("quant_weights", {"quant_weights": True}),
    ("spec", {"spec": True}),
    ("decode_impl", {"decode_impl": "pallas"}),
    ("block_handoff", {}),      # no ServeConfig field: refused where it is called
]


@pytest.mark.parametrize("knob,serve", REFUSALS)
def test_engine_refuses_what_the_latent_family_lacks_by_name(model, knob, serve):
    with pytest.raises(NotImplementedError, match=knob):
        eng = _engine(model, **serve)
        eng.export_prefix_blocks(list(range(16)))


def test_every_refused_knob_of_the_latent_family_has_a_case():
    """A knob added to the table without a case above fails here."""
    from tony_tpu.serve import latent

    assert {knob for knob, _ in REFUSALS} == set(latent.REFUSED_KNOBS)


@pytest.mark.parametrize("call", ["export", "adopt"])
def test_engine_refuses_gang_block_handoff_for_the_latent_family(model, call):
    eng = _engine(model)
    with pytest.raises(NotImplementedError, match="gang block export/adopt"):
        if call == "export":
            eng.export_prefix_blocks(list(range(16)))
        else:
            eng.adopt_blocks(list(range(8)), None)


def test_engine_still_refuses_a_dense_decoder_with_experts():
    cfg = LlamaConfig.tiny_moe()
    with pytest.raises(NotImplementedError, match="MoE"):
        Engine(llama_init(jax.random.key(0), cfg), cfg, ServeConfig(slots=2))


def test_param_count_matches_the_tree(model):
    cfg, params = model
    assert cfg.n_params == sum(a.size for a in jax.tree.leaves(params))
    axes = lm.logical_axes(cfg)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=lambda x: isinstance(x, tuple)))
    for a, ax in zip(jax.tree.leaves(params),
                     jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))):
        assert a.ndim == len(ax)


@pytest.mark.parametrize("lead", [(5,), (5, 3), (2, 5), "vmap"],
                         ids=["S", "S-G", "B-S", "vmap"])
def test_attention_inputs_keep_the_plain_formula_bitwise(model, lead):
    """``attention_inputs`` holds ``cq @ wq_b`` apart from the reshape and
    the nope/rope split (an optimization barrier, for the chip's compiler:
    PERF.md §6 PR 30); in float32 its three results are bit for bit the
    formula written out here for any leading axes, jitted, and under
    ``jax.vmap``."""
    cfg, params = model
    lp = jax.tree.map(lambda a: a[0], params["dense_layers"])
    H, nope, kr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    shape = (4, 5) if lead == "vmap" else lead
    kx, ka = jax.random.split(jax.random.key(5))
    h = jax.random.normal(kx, (*shape, cfg.dim), jnp.float32)
    ang = jax.random.uniform(ka, (*shape, cfg.qk_rope_head_dim // 2), jnp.float32, 0.0, 6.0)
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    def norm(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + cfg.norm_eps) * w

    def rope(t, cos, sin):
        half = t.shape[-1] // 2
        t1, t2 = t[..., :half], t[..., half:]
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], axis=-1)

    def changed(h, cos, sin):
        return lm.attention_inputs(h, lp, cfg, cos, sin)

    def plain(h, cos, sin):
        cq = norm(h @ lp["wq_a"], lp["q_norm"])
        q = (cq @ lp["wq_b"]).reshape(*h.shape[:-1], H, cfg.qk_head_dim)
        kv = h @ lp["wkv_a"]
        latent = jnp.concatenate(
            [norm(kv[..., :kr], lp["kv_norm"]), rope(kv[..., kr:], cos, sin)], axis=-1)
        return q[..., :nope], rope(q[..., nope:], cos[..., None, :], sin[..., None, :]), latent

    fn = jax.vmap(changed) if lead == "vmap" else changed
    got = jax.jit(fn)(h, cos, sin)
    want = jax.jit(plain)(h, cos, sin)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
