"""The latent-attention expert family's benchmark files: counts against hand
counts for the configuration the cell runs, the plain reference against an
independent few-line attention and loop over experts, the traffic mix's sizes,
and the whole of a run of the cell at test sizes (``run_cell`` through
``drivers/serve_engine_family.py``), sound and with each planted fault."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from benchmark import compare, flops_latent_moe as counts, trafficgen, weights_latent_moe as weights
from benchmark.reference import latent_moe_decoder as ref

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(HERE, "data", "BENCHMARK_latent_moe.json")


@pytest.fixture(scope="module")
def s():
    with open(os.path.join(ROOT, "benchmark", "configs", "deepseek-v3-serve-ep16.json")) as f:
        return weights.sizes_of(json.load(f))


def test_counts_of_a_token_against_hand_counts(s):
    # W_qa 11.0 M + W_qb 37.7 M + W_kva 4.1 M + W_kvb 16.8 M + W_o 117.4 M
    assert counts.attn_matmul_params(s) == (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576
                                            + 512 * 128 * 256 + 128 * 128 * 7168) == 187_105_280
    assert counts.expert_params(s) == 44_040_192 and counts.dense_ffn_params(s) == 396_361_728
    assert counts.expert_layer_fixed_params(s) == 7168 * 256 + 44_040_192
    # half a route a token a layer lands here (8 x 16/256)
    assert counts.token_matmul_params(s, 0.5) == 1_974_304_768
    # expanded: 2 x 128 x (192 + 128); absorbed: 2 x 128 x (576 + 512)
    assert counts.pair_flops(s, "expanded") == 81_920 and counts.pair_flops(s, "absorbed") == 278_528
    need = counts.serve_flops(s, [1000], [2000, 3000], 0.5)
    assert need == pytest.approx(2 * 1_974_304_768 * 1002 + 6 * 81_920 * 500_000 + 6 * 278_528 * 5000)


def test_bytes_of_a_decode_step_against_hand_counts(s):
    # 6 attention blocks, 1 dense SwiGLU, 5 shared experts, 63 experts hit
    # (12.6 a layer), head, norms: 4,629,666,816 weights in bfloat16; 5 float32
    # routers with their bias; 48 slots of 1,000 positions at 6 x 1,152 B
    cost = counts.decode_step_cost(s, [1000] * 48, experts_hit=63, routes=120)
    weights_b = 2 * (6 * 187_105_280 + 396_361_728 + 5 * 44_040_192 + 63 * 44_040_192
                     + 115_834_880 + 105_472) + 5 * (7168 + 1) * 256 * 4
    assert weights_b == 9_296_038_912
    assert cost["bytes"] == weights_b + 48_000 * 6 * 1152
    fixed = 6 * 187_105_280 + 396_361_728 + 5 * (7168 * 256 + 44_040_192) + 115_834_880
    assert cost["flops"] == pytest.approx(2 * fixed * 48 + 2 * 44_040_192 * 120 + 6 * 278_528 * 48_000)
    assert counts.latent_bytes_per_token(s) == 576 * 2 * 6


def test_seeded_tree_has_the_stated_parameters(s):
    # 1 dense layer 583.5 M, 5 expert layers of 937.6 M, embedding + head 231.7 M
    assert weights.n_params(s) == pytest.approx(5.50e9, rel=2e-3)
    shapes = jax.eval_shape(lambda k: weights.make_params(k, s, jnp.bfloat16), jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == weights.n_params(s)
    assert shapes["moe_layers"]["w1"].shape == (5, 16, 7168, 2048)
    assert shapes["moe_layers"]["router"].dtype == jnp.float32


def test_a_seed_changes_every_weight_but_the_router(s):
    """The router's weight and selection bias are the configuration's
    (``router_seed``): two seeds route alike, so the experts hit a step — the
    decode step's bytes — do not depend on the seed."""
    tiny = dict(TINY)
    a, b = (weights.make_layer(weights.base_key(seed), tiny, jnp.float32, 1, True)
            for seed in (5, 2**31 + 6))
    for name in weights.MOE_LEAVES:
        same = bool(jnp.array_equal(a[name], b[name]))
        assert same == (name in weights.ROUTER_LEAVES or name.endswith("norm")), name
    other = weights.make_layer(weights.base_key(5), {**tiny, "router_seed": 1}, jnp.float32, 1, True)
    assert not jnp.array_equal(a["router"], other["router"])
    assert not jnp.array_equal(a["router_bias"], other["router_bias"])
    assert jnp.array_equal(a["w1"], other["w1"])
    # layers differ, and the bias is small beside a sigmoid score
    assert not jnp.array_equal(a["router"], weights.make_leaf(weights.base_key(5), "router", tiny, jnp.float32, 0, True))
    assert 0 < float(jnp.abs(a["router_bias"]).max()) < 0.1 and s["router_seed"] == 0


TINY = {"d": 32, "h": 2, "qr": 12, "kr": 8, "nope": 8, "rope": 4, "vd": 8, "f": 48, "fm": 16,
        "e": 16, "n_local": 4, "first": 4, "shared": 1, "k": 3, "groups": 4, "topk_groups": 2,
        "scale": 2.5, "norm_topk": True, "v": 64, "layers": 2, "dense": 1, "theta": 10000.0,
        "eps": 1e-6, "router_seed": 0, "yarn": {"factor": 40.0, "orig": 16, "beta_fast": 32.0, "beta_slow": 1.0,
                              "mscale": 1.0, "mscale_all_dim": 1.0}}


def test_reference_layer_against_a_few_line_attention_and_a_loop_over_experts():
    """Independent of the reference's own blocking and masks: one head at a
    time with numpy, the experts of each token picked by sorting."""
    s = TINY
    key = weights.base_key(5)
    lp = jax.tree.map(lambda a: np.asarray(a, np.float64), weights.make_layer(key, s, jnp.float32, 1, True))
    S = 11
    x = np.asarray(jax.random.normal(jax.random.key(1), (S, s["d"])), np.float64)
    norm = lambda v, g: v / np.sqrt((v * v).mean(-1, keepdims=True) + s["eps"]) * g
    freqs = np.asarray(ref.yarn_freqs(s), np.float64)

    def rope(v, p):
        half = s["rope"] // 2
        a = p * freqs
        return np.concatenate([v[:half] * np.cos(a) - v[half:] * np.sin(a),
                               v[half:] * np.cos(a) + v[:half] * np.sin(a)])

    h = norm(x, lp["attn_norm"])
    q = (norm(h @ lp["wq_a"], lp["q_norm"]) @ lp["wq_b"]).reshape(S, s["h"], -1)
    kv = h @ lp["wkv_a"]
    c = norm(kv[:, :s["kr"]], lp["kv_norm"])
    kvb = (c @ lp["wkv_b"]).reshape(S, s["h"], -1)
    out = np.zeros((S, s["h"], s["vd"]))
    for i in range(s["h"]):
        for t in range(S):
            qt = np.concatenate([q[t, i, :s["nope"]], rope(q[t, i, s["nope"]:], t)])
            keys = np.stack([np.concatenate([kvb[u, i, :s["nope"]], rope(kv[u, s["kr"]:], u)])
                             for u in range(t + 1)])
            sc = keys @ qt * ref.softmax_scale(s)
            p = np.exp(sc - sc.max())
            out[t, i] = (p / p.sum()) @ kvb[: t + 1, i, s["nope"]:]
    x1 = x + out.reshape(S, -1) @ lp["wo"]
    h = norm(x1, lp["ffn_norm"])
    silu = lambda v: v / (1 + np.exp(-v))
    ffn = lambda v, a, b, c_: (silu(v @ a) * (v @ b)) @ c_
    y = ffn(h, lp["ws1"], lp["ws3"], lp["ws2"])
    sig = 1 / (1 + np.exp(-(h @ lp["router"])))
    for t in range(S):
        biased = sig[t] + lp["router_bias"]
        groups = biased.reshape(s["groups"], -1)
        best = np.argsort(-np.sort(groups, axis=1)[:, -2:].sum(1))[: s["topk_groups"]]
        allowed = [e for e in range(s["e"]) if e // (s["e"] // s["groups"]) in best]
        chosen = sorted(allowed, key=lambda e: -biased[e])[: s["k"]]
        total = sum(sig[t, e] for e in chosen)
        for e in chosen:
            if s["first"] <= e < s["first"] + s["n_local"]:
                j = e - s["first"]
                y[t] += 2.5 * sig[t, e] / total * ffn(h[t], lp["w1"][j], lp["w3"][j], lp["w2"][j])
    want = x1 + y
    got = ref.layer(jnp.asarray(x, jnp.float32), jax.tree.map(jnp.asarray, weights.make_layer(
        key, s, jnp.float32, 1, True)), s)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # and each planted fault moves the layer's output
    for fault in ref.FAULTS:
        bad = ref.layer(jnp.asarray(x, jnp.float32), jax.tree.map(jnp.asarray, weights.make_layer(
            key, s, jnp.float32, 1, True)), s, fault=fault)
        assert float(jnp.abs(bad - got).max()) > 1e-2, fault


def test_the_mix_sends_48_fixed_pairs_inside_its_clips():
    with open(os.path.join(ROOT, "benchmark", "traffic", "reason-closed48.json")) as f:
        mix = json.load(f)
    sizes = trafficgen.request_sizes(mix)
    assert len(sizes) == 48 == len(set(sizes)) and sizes == trafficgen.request_sizes(mix)
    assert min(p for p, _ in sizes) >= 64 and max(p for p, _ in sizes) <= 2048
    assert min(o for _, o in sizes) >= 128 and max(o for _, o in sizes) <= 2048
    assert max(p + o for p, o in sizes) <= 4096     # the engine's max_len
    assert int(np.median([p for p, _ in sizes])) in range(480, 545)
    assert mix["clients"] == 48 and mix["think_s"] == 0 and mix["shared_prefix"]["share"] == 0
    assert mix["sampling"]["temperature"] == 0 and mix["checked_requests"] == 4
    assert mix["preroll_steps"] > 0 and "preroll_s" not in mix      # the window opens by steps


def test_the_cell_s_limits_refuse_what_the_widest_gap_alone_lets_through():
    """The chip's own readings (PERF.md section 4) against the limits file: the
    largest sound run passes; this chip's experts answering with their
    neighbours' weights stays under the widest gap's limit on two seeds of
    three and is refused by the mean gap; the control is refused by both."""
    with open(os.path.join(ROOT, "benchmark", "limits", "serve-dsv3-reason-closed48.json")) as f:
        limits = json.load(f)["limits"]
    assert compare.verdict({"served_logit_gap": 1.905, "served_logit_gap_mean": 0.0179}, limits)[0]
    ok, rows = compare.verdict({"served_logit_gap": 2.354, "served_logit_gap_mean": 0.341}, limits)
    assert not ok
    assert [r["name"] for r in rows if r["value"] > r["limit"]] == ["served_logit_gap_mean"]
    ok, rows = compare.verdict({"served_logit_gap": 3.448, "served_logit_gap_mean": 0.880}, limits)
    assert not ok and all(r["value"] > r["limit"] for r in rows)
    assert not compare.verdict({"served_logit_gap": 1.0}, limits)[0]     # a number left out


def cell(seed, **extra):
    return run.run_cell("tiny-latent-cell", seed, 1.5, False, bench_file=BENCH,
                        require_chip=False, extra=extra)


def test_cell_sound_run_is_correct_and_control_and_faults_are_not():
    r = cell(2**31 + 21, control=1, faults=list(ref.FAULTS), flips=["bfloat16"])
    assert r["correct"], r["checks"]
    flips = r["notes"]["routing_flips"]["bfloat16"]
    # two expert layers over prompt + served tokens of each checked request
    assert flips["token_layers"] > 2 * r["notes"]["checked_tokens"]
    assert 0 <= flips["flipped_local"] <= flips["flipped"] <= flips["token_layers"]
    assert r["notes"]["served"]["served_logit_gap"] == r["checks"][0]["value"]
    assert r["notes"]["served"]["served_not_best_share"] < 0.05
    assert r["failed"] == 0 and r["attempted"] > 10
    for name in ("setup_s", "serve_tokens_per_s", "ttft_p75_ms", "itl_p95_ms"):
        assert r["metrics"][name]["value"] > 0
    limits = {row["name"]: row["limit"] for row in r["checks"]}
    assert not compare.verdict(r["notes"]["control"], limits)[0], r["notes"]["control"]
    for fault in ref.FAULTS:
        assert not compare.verdict(r["notes"]["faults"][fault], limits)[0], fault
    fam = r["notes"]["family"]      # this holder has experts 8..15 of 32, top-4, 2 expert layers
    assert len(fam["routes"]) == 2 and len(fam["routes"][0]) == 8
    assert 0 < sum(map(sum, fam["routes"])) < fam["tokens"] * 4 * 2


@pytest.mark.parametrize("fault", ["token_altered", *ref.FAULTS])
def test_cell_fault_is_not_correct(fault):
    r = cell(22, fault=fault)
    assert not r["correct"], r["checks"]


def _reader_ctx(s_config, family=True, trace=True):
    fam = {"routes": [[30] * 16] * 5, "tokens": 960, "experts_hit": [126] * 5, "steps": 10}
    fam1 = {"routes": [[60] * 16] * 5, "tokens": 1920, "experts_hit": [252] * 5, "steps": 20}
    t = {"module_events": {
        "jit_serve_decode(123)": [(0.1 * i, 0.030) for i in range(10)],
        "jit_serve_prefill(7)": [(5.0, 0.2)], "jit_other(1)": [(9.0, 1.0)]}}
    return {
        "config": s_config, "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "trace": t if trace else None,
        "observed": {
            "prefill_lens": [500, 700], "decode_ctx": [1000] * 900, "window_s": 25.0,
            "traced_decode_lens": [[1000] * 48] * 10, "traced_prefill_lens": [1000],
            "family": {"window": fam, "trace0": fam, "trace1": fam1} if family else None,
        },
    }


def test_reader_reads_every_member_and_is_silent_without_the_program_s_counters(s):
    from benchmark.layer_metrics import latent_moe as reader

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"] if m["name"].startswith("latent_moe.")]
    assert len(names) == 6
    with open(os.path.join(ROOT, "benchmark", "configs", "deepseek-v3-serve-ep16.json")) as f:
        config = json.load(f)
    got = {n: reader.read(n, _reader_ctx(config)) for n in names}
    assert all(v is not None and v > 0 for v in got.values()), got
    # 30 routes x 16 experts x 5 layers over 960 tokens x 5 layers x 8 routes
    assert got["latent_moe.local_route_share"] == pytest.approx(6.25)
    assert got["latent_moe.experts_hit_per_step"] == pytest.approx(12.6)
    assert got["latent_moe.expert_load_max_over_mean"] == pytest.approx(1.0)
    # 63 experts hit a step: 9.30 GB of weights + 48,000 live rows, at 819 GB/s, over 30 ms
    least = (9_296_038_912 + 48_000 * 6912) / 819e9
    assert got["latent_moe.decode_step_roofline"] == pytest.approx(100 * least / 0.030, rel=1e-6)
    assert got["latent_moe.prefill_roofline"] == pytest.approx(
        100 * counts.prefill_flops(s, 1000, 0.5) / 197e12 / 0.2)
    assert all(v <= 100 for k, v in got.items() if k.endswith(("roofline", "mfu")))
    # a program without the counters (the parent), or a run without a trace
    assert all(reader.read(n, _reader_ctx(config, family=False)) is None for n in names)
    quiet = {n: reader.read(n, _reader_ctx(config, trace=False)) for n in names}
    assert quiet["latent_moe.decode_step_roofline"] is None and quiet["latent_moe.step_mfu"] > 0
    # and another family's configuration reads nothing
    assert reader.read(names[0], _reader_ctx({"hidden_size": 64})) is None
