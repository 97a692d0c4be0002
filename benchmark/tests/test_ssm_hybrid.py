"""The selective state-space / attention hybrid family's benchmark files: the
plain reference against hand computation (a 3-token recurrence, the
convolution's causality, a few lines of float64 numpy for both kinds of
layer), the counts against hand counts for the configuration the cell runs,
the configuration file against the catalog row, the traffic mix's sizes and
its pre-roll, the reader, and the whole of a run of the cell at test sizes
(``run_cell`` through ``drivers/serve_engine_family.py``).

The control and the planted faults are judged on a FIXED COUNT of requests
served to their end through the ``Engine`` here, not on those that happen to
finish inside a CPU time window (PERF.md section 7 says why
``test_shortconv_moe.py``'s twin is unsteady)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from benchmark import compare, flops_ssm_hybrid as counts, trafficgen
from benchmark import weights_ssm_hybrid as weights
from benchmark.drivers import serve_engine_family as driver
from benchmark.families import ssm_hybrid as fam
from benchmark.reference import ssm_hybrid_decoder as ref

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(HERE, "data", "BENCHMARK_ssm_hybrid.json")
CELL = "serve-jamba2-reason-closed128"


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _json("benchmark", "configs", "jamba2-3b-serve.json")


@pytest.fixture(scope="module")
def s(config):
    return weights.sizes_of(config)


TINY = {"d": 16, "h": 4, "kv": 1, "hd": 8, "f": 24, "v": 64, "layers": 3, "e": 32, "n": 4,
        "K": 4, "r": 6, "period": 3, "offset": 1, "eps": 1e-6,
        "layer_types": ("mamba", "attention", "mamba")}


def _layer(stack, layer=1, s=TINY, seed=5):
    return weights.make_layer(weights.base_key(seed), s, jnp.float32, layer, stack)


# --- the reference's own checks ---------------------------------------------------


def test_recurrence_hand_computed_3_token_example():
    """E = 1 channel, N = 2 state rows, every projection chosen so that the
    inputs of the recurrence are known: ``c_t = silu(u_t)`` (one tap, no
    bias), ``delta = softplus(b_dt)``, ``B = C = (1, 1)`` (the norm of a
    constant positive pair), ``A = (-1, -2)``, ``D = 0.5``, no gate effect
    beyond ``silu(z)`` with ``z = u``."""
    s = {**TINY, "d": 1, "e": 1, "n": 2, "K": 1, "r": 1}
    u = np.array([1.0, -0.5, 2.0])
    lp = {"w_in": jnp.ones((1, 2)), "conv_w": jnp.ones((1, 1)), "conv_b": jnp.zeros((1,)),
          # d = 0 * c (its norm stays 0), B = C = c * (1, 1) -> normed to (1, 1) * sign(c)
          "w_x": jnp.asarray([[0.0, 1.0, 1.0, 1.0, 1.0]]),
          "dt_norm": jnp.ones((1,)), "b_norm": jnp.ones((2,)), "c_norm": jnp.ones((2,)),
          "w_dt": jnp.ones((1, 1)), "b_dt": jnp.asarray([0.3]),
          "a_log": jnp.log(jnp.asarray([[1.0], [2.0]])), "d_skip": jnp.asarray([0.5]),
          "w_out": jnp.ones((1, 1))}
    got = np.asarray(ref.mamba(jnp.asarray(u)[:, None], lp, s))[:, 0]
    silu = lambda v: v / (1 + np.exp(-v))
    delta = np.log1p(np.exp(0.3))
    h, want = np.zeros(2), []
    for t in range(3):
        c = silu(u[t])
        sign = np.sign(c)                                  # rms_norm of (c, c) is (sign, sign)
        h = np.exp(delta * np.array([-1.0, -2.0])) * h + delta * c * sign
        want.append((h.sum() * sign + 0.5 * c) * silu(u[t]))
    np.testing.assert_allclose(got, want, rtol=1e-4)      # the norms' eps of 1e-6
    # the state dropped: every position starts from h = 0
    dropped = np.asarray(ref.mamba(jnp.asarray(u)[:, None], lp, s, fault="ssm_state_dropped"))[:, 0]
    alone = [(2 * delta * silu(x) + 0.5 * silu(x)) * silu(x) for x in u]
    np.testing.assert_allclose(dropped, alone, rtol=1e-4)
    assert abs(dropped[0] - got[0]) < 1e-6 and abs(dropped[2] - got[2]) > 1e-2


@pytest.mark.parametrize("stack", ["mamba_layers", "attn_layers"])
def test_layers_are_causal(stack):
    """Changing position 6 of a sequence changes no output before it."""
    lp = _layer(stack)
    x = jax.random.normal(jax.random.key(1), (10, TINY["d"]))
    y = ref.layer(x, lp, TINY)
    y2 = ref.layer(x.at[6].add(1.0), lp, TINY)
    np.testing.assert_allclose(y2[:6], y[:6], rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(y2[6:] - y[6:]).max()) > 1e-3
    # a Mamba layer REMEMBERS: the last position still differs
    assert float(jnp.abs(y2[9] - y[9]).max()) > 1e-5


def test_reference_layers_against_a_few_lines_of_numpy():
    """Independent of the reference's own blocking, masks, shifts and scan:
    one head and one position at a time, in float64."""
    s = TINY
    S = 9
    x = np.asarray(jax.random.normal(jax.random.key(2), (S, s["d"])), np.float64)
    norm = lambda v, g: v / np.sqrt((v * v).mean(-1, keepdims=True) + s["eps"]) * g
    silu = lambda v: v / (1 + np.exp(-v))
    ffn = lambda v, a, b, c_: (silu(v @ a) * (v @ b)) @ c_
    f64 = lambda tree: jax.tree.map(lambda a: np.asarray(a, np.float64), tree)

    # a Mamba layer
    lp32 = _layer("mamba_layers", 0)
    lp = f64(lp32)
    E, N, R, K = s["e"], s["n"], s["r"], s["K"]
    h = norm(x, lp["op_norm"])
    uz = h @ lp["w_in"]
    u, z = uz[:, :E], uz[:, E:]
    y = np.zeros((S, E))
    state = np.zeros((N, E))
    for t in range(S):
        acc = lp["conv_b"].copy()
        for j in range(K):
            if t - (K - 1) + j >= 0:
                acc += lp["conv_w"][j] * u[t - (K - 1) + j]
        c = silu(acc)
        dbc = c @ lp["w_x"]
        d = norm(dbc[:R], lp["dt_norm"])
        b = norm(dbc[R:R + N], lp["b_norm"])
        cc = norm(dbc[R + N:], lp["c_norm"])
        delta = np.log1p(np.exp(d @ lp["w_dt"] + lp["b_dt"]))
        state = np.exp(delta[None] * -np.exp(lp["a_log"])) * state + (delta * c)[None] * b[:, None]
        y[t] = ((state * cc[:, None]).sum(0) + lp["d_skip"] * c) * silu(z[t])
    x1 = x + y @ lp["w_out"]
    want = x1 + ffn(norm(x1, lp["ffn_norm"]), lp["w1"], lp["w3"], lp["w2"])
    got = ref.layer(jnp.asarray(x, jnp.float32), lp32, s)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    # an attention layer: no rotation, ONE K/V head
    lp32 = _layer("attn_layers", 1)
    lp = f64(lp32)
    H, hd = s["h"], s["hd"]
    h = norm(x, lp["op_norm"])
    q = (h @ lp["wq"]).reshape(S, H, hd)
    k, v = h @ lp["wk"], h @ lp["wv"]
    out = np.zeros((S, H, hd))
    for i in range(H):
        for t in range(S):
            sc = k[: t + 1] @ q[t, i] / np.sqrt(hd)
            p = np.exp(sc - sc.max())
            out[t, i] = (p / p.sum()) @ v[: t + 1]
    x1 = x + out.reshape(S, -1) @ lp["wo"]
    want = x1 + ffn(norm(x1, lp["ffn_norm"]), lp["w1"], lp["w3"], lp["w2"])
    got = ref.layer(jnp.asarray(x, jnp.float32), lp32, s)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_each_planted_fault_moves_its_layer_s_output_and_no_other():
    x = jax.random.normal(jax.random.key(3), (11, TINY["d"]))
    mamba, attn = _layer("mamba_layers"), _layer("attn_layers")
    moved = {}
    for fault in ref.FAULTS:
        for name, lp in (("mamba", mamba), ("attn", attn)):
            moved[fault, name] = float(jnp.abs(
                ref.layer(x, lp, TINY, fault=fault) - ref.layer(x, lp, TINY)).max())
    for fault in ref.FAULTS:
        own = "attn" if fault == "rope_applied" else "mamba"
        other = "mamba" if own == "attn" else "attn"
        assert moved[fault, own] > (1e-5 if fault == "state_bfloat16" else 1e-3), (fault, moved)
        assert moved[fault, other] == 0.0, (fault, moved)
    assert len(ref.FAULTS) == 7


# --- the configuration, the counts, the weights -----------------------------------


ROW = {"attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
       "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
       "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
       "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
       "max_position_embeddings": 262144, "model_type": "jamba", "num_attention_heads": 20,
       "num_experts": 1, "num_experts_per_tok": 1, "num_hidden_layers": 28,
       "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
       "sliding_window": None, "tie_word_embeddings": True, "use_mamba_kernels": True,
       "vocab_size": 65536}


def test_the_configuration_keeps_every_published_value_and_cuts_nothing(config, s):
    assert {k: config[k] for k in ROW} == ROW
    assert config["reduced"] == {} and config["family"] == "ssm_hybrid"
    entry = next(c for c in _json("BENCHMARK.json")["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == [] and entry["source"] == config["source"]
    assert entry["file"] == "benchmark/configs/jamba2-3b-serve.json"
    assert [l for l, t in enumerate(s["layer_types"]) if t == "attention"] == [7, 21]
    assert set(config["assumed"]) >= {"head_dim", "layer_order", "init", "recurrence_init"}
    assert set(config["departures"]) >= {"state_layout", "conv_tail_container"}
    assert "one v5e chip holds the model whole" in config["deployment"]
    assert config["precision"]["control"] == "float8_e4m3fn" and config["dtype"] == "bfloat16"
    assert "float32 recurrence state" in config["precision"]["stated"]
    serve = config["serve"]
    assert (serve["slots"], serve["max_len"], serve["kv_block"]) == (128, 5120, 64)
    assert serve["prefix"] is False and serve["shrink"] is False
    assert serve["prefill_buckets"] == [64, 128, 256, 512, 640, 1280, 2048]     # ISSUE 33's


def test_counts_of_a_token_against_hand_counts(s):
    # w_in 26.2 M, taps 20 K, w_x 0.98 M, w_dt 0.82 M, w_out 13.1 M
    assert counts.mamba_params(s) == (2560 * 10240 + 4 * 5120 + 5120 * 192 + 160 * 5120
                                      + 5120 * 2560) == 41_144_320
    assert counts.attn_params(s) == 2 * 2560 * 2560 + 2 * 2560 * 128 == 13_762_560
    assert counts.ffn_params(s) == 62_914_560
    assert (counts.n_mamba(s), counts.n_attn(s)) == (26, 2)
    # 3.03 G multiplied weights a token
    assert counts.matmul_params(s) == (26 * 41_144_320 + 2 * 13_762_560 + 28 * 62_914_560
                                       + 2560 * 65536) == 3_026_657_280
    assert counts.pair_flops(s) == 4 * 20 * 128
    assert counts.scan_flops_per_token(s) == 5120 * (16 * 7 + 8)
    need = counts.serve_flops(s, [1000], [2000, 3000])
    per_token = 2 * 3_026_657_280 + 26 * 5120 * 120
    assert need == pytest.approx(per_token * 1002 + 2 * 10240 * (500_000 + 5000))
    assert counts.prefill_flops(s, 1000) == pytest.approx(
        counts.serve_flops(s, [1000], []) - 2 * 2560 * 65536 * 999)


def test_bytes_of_a_decode_step_and_of_the_scan_kernel_against_hand_counts(s):
    """128 live slots of 1,500 positions: 6.06 GB of weights, the state read
    and written (2 x 128 x 10.1 MB), K/V at 1,024 B a token."""
    assert counts.kv_bytes_per_token(s) == 1024
    assert counts.state_bytes_per_slot(s) == 26 * 19 * 5120 * 4 == 10_117_120
    gains = 57 * 2560 + 26 * (160 + 32 + 5120)
    wb = (3_026_657_280 + gains) * 2 + 26 * (16 * 5120 + 2 * 5120) * 4
    assert counts.weight_bytes(s) == wb == 6_063_467_264
    # the whole tree once: every parameter 2 bytes, the recurrence's float32 leaves 2 more
    assert wb == 2 * weights.n_params(s) + 26 * 92_160 * 2
    cost = counts.decode_step_cost(s, [1500] * 128)
    assert cost["bytes"] == wb + 2 * 128 * 10_117_120 + 1024 * 128 * 1500
    assert cost["flops"] == pytest.approx(
        (2 * 3_026_657_280 + 26 * 5120 * 120) * 128 + 2 * 10240 * 128 * 1500)
    # the recurrent state is 29-30% of such a step's bytes
    assert 0.28 < 2 * 128 * 10_117_120 / cost["bytes"] < 0.31
    scan = counts.scan_kernel_cost(s, 512)
    assert scan["bytes"] == 512 * (5120 * 10 + 128) + (3 * 16 * 5120 + 5120) * 4
    assert scan["flops"] == 512 * 5120 * 120


def test_seeded_tree_has_the_stated_parameters(s):
    assert weights.n_params(s) == 3_029_337_472
    shapes = jax.eval_shape(lambda k: weights.make_params(k, s, jnp.bfloat16), jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == weights.n_params(s)
    assert shapes["mamba_layers"]["w_in"].shape == (26, 2560, 10240)
    assert shapes["mamba_layers"]["a_log"].shape == (26, 16, 5120)
    assert shapes["mamba_layers"]["a_log"].dtype == jnp.float32
    assert shapes["attn_layers"]["wk"].shape == (2, 2560, 128)
    assert shapes["dense_ffns"]["w1"].shape == (28, 2560, 8192) and "lm_head" not in shapes


def test_the_program_s_tree_is_the_seeded_tree(s):
    """Same leaves, shapes and dtypes as the program's own init gives."""
    from tony_tpu.models.ssm_hybrid import init_params

    cfg = fam.model(s, {"max_len": 5120}, jnp.bfloat16)
    mine = jax.eval_shape(lambda k: weights.make_params(k, s, jnp.bfloat16), jax.random.key(0))
    theirs = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), mine) == jax.tree.map(
        lambda a: (a.shape, a.dtype), theirs)
    assert cfg.n_params == weights.n_params(s) == 3_029_337_472
    assert cfg.layer_types.count("full_attention") == 2 and cfg.max_seq_len == 5120


def test_a_seed_changes_every_drawn_weight_and_the_recurrence_is_initialised_to_remember():
    a, b = (weights.make_layer(weights.base_key(seed), TINY, jnp.float32, 1, "mamba_layers")
            for seed in (5, 2**31 + 6))
    fixed = {"a_log", "d_skip"}
    for name in a:
        same = bool(jnp.array_equal(a[name], b[name]))
        assert same == (name in fixed or name.endswith("norm")), name
    np.testing.assert_allclose(np.exp(a["a_log"][:, 0]), [1, 2, 3, 4], rtol=1e-6)
    dt = np.asarray(jax.nn.softplus(a["b_dt"]))
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001
    assert float(jnp.abs(a["w_dt"]).max()) <= TINY["r"] ** -0.5 * 1.001
    # the stacked tree holds layer l's leaves at its kind's index
    tree = weights.make_params(weights.base_key(5), TINY, jnp.float32)
    np.testing.assert_array_equal(tree["mamba_layers"]["w_in"][1], weights.make_leaf(
        weights.base_key(5), "w_in", TINY, jnp.float32, 2))
    np.testing.assert_array_equal(tree["dense_ffns"]["w2"][1], a["w2"])


def test_the_mix_sends_128_fixed_pairs_inside_its_clips(config):
    mix = _json("benchmark", "traffic", "reason-closed128.json")
    sizes = trafficgen.request_sizes(mix)
    assert len(sizes) == 128 and sizes == trafficgen.request_sizes(mix)
    assert min(p for p, _ in sizes) >= 64 and max(p for p, _ in sizes) <= 2048
    assert min(o for _, o in sizes) >= 128 and max(o for _, o in sizes) <= 3072
    assert max(p + o for p, o in sizes) <= config["serve"]["max_len"]
    # the reference pads to max_len and reads output_len.max rows from the prompt's end
    assert 2048 - 1 + 3072 <= config["serve"]["max_len"]
    assert mix["prompt_len"] == {"law": "lognormal", "median": 512, "sigma": 0.8, "min": 64, "max": 2048}
    assert mix["output_len"] == {"law": "lognormal", "median": 1024, "sigma": 0.6, "min": 128, "max": 3072}
    assert mix["clients"] == 128 == config["serve"]["slots"] == mix["distinct"]
    assert mix["think_s"] == 0 and mix["shared_prefix"]["share"] == 0 and mix["schedule_seed"] == 0
    assert mix["sampling"]["temperature"] == 0 and mix["checked_requests"] == 4
    assert mix["trace_seconds"] == 3.0 and mix["driver"] == "serve_engine_family"
    assert "preroll_s" not in mix                                   # the window opens by steps
    # the 75th percentile of prompts lies inside one prefill bucket, clear of its edges
    buckets = config["serve"]["prefill_buckets"]
    p75 = float(np.percentile([p for p, _ in sizes], 75))
    bucket = min(b for b in buckets if b >= p75)
    lower = max([b for b in buckets if b < bucket], default=0)
    assert (lower, bucket) == (640, 1280) and lower + 64 < p75 < bucket - 64
    mean_reply = float(np.mean([o for _, o in sizes]))
    assert 1150 < mean_reply < 1250 and mix["preroll_steps"] >= mean_reply


def test_the_pre_roll_opens_the_window_in_the_middle_of_the_longest_gap_between_admissions():
    """A step-exact walk of the schedule: a request of n tokens is admitted at
    one step and seen finished n - 2 steps later (its prefill's token and one
    token a decode step, the first of them in the admitting step); its
    client's next request is admitted the step after."""
    mix = _json("benchmark", "traffic", "reason-closed128.json")
    sizes, cuts = trafficgen.request_sizes(mix), trafficgen.head_start(mix, 128)
    nxt, active, admissions = 0, [], {0}
    for c in range(128):
        active.append(max(2, int(round(sizes[nxt % 128][1] * float(cuts[c])))) - 2)
        nxt += 1
    for step in range(2000):
        keep, new = [a for a in active if a > step], []
        for _ in range(len(active) - len(keep)):
            new.append(step + 1 + sizes[nxt % 128][1] - 2)
            nxt += 1
        if new:
            admissions.add(step + 1)
        active = keep + new
    steps = sorted(admissions)
    gaps = sorted(((b - a, a, b) for a, b in zip(steps, steps[1:]) if 900 <= a <= 1700),
                  reverse=True)
    assert gaps[0] == (42, 1269, 1311) and gaps[1][0] <= 26
    assert mix["preroll_steps"] == (1269 + 1311) // 2 == 1290
    # an admission every ~9 steps once the first requests are replaced
    rate = sum(1 for a in steps if 300 <= a < 2000) / 1700
    assert 1 / 14 < rate < 1 / 7


def test_the_cell_is_entered_as_the_issue_says():
    bench = _json("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "jamba2-3b-serve", "reason-closed128", 1)
    for name in ("serve_tokens_per_s", "ttft_p75_ms", "itl_p95_ms"):
        assert CELL in next(m for m in bench["end_to_end"] if m["name"] == name)["workloads"]
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert {
        "compile_cache_misses", "engine.decode_step_ms_mean", "engine.slot_occupancy",
        "engine.compiles_in_window", "ttft_ms.mean", "ttft_ms.p90", "device_idle.serve",
        "ssm_hybrid.step_mfu", "ssm_hybrid.decode_step_roofline", "ssm_hybrid.prefill_roofline",
        "ssm_hybrid.scan_roofline", "ssm_hybrid.state_stream_share", "ssm_hybrid.handoff_share",
    } <= listed
    mine = [m for m in bench["per_layer"] if m["name"].startswith("ssm_hybrid.")]
    assert len(mine) == 6 and all(CELL in m["workloads"] for m in mine)


def test_the_cell_s_limits_separate_sound_runs_from_the_control_and_six_faults():
    """The chip's own readings (PERF.md section 4, my chip runs, PR 33) against
    the limits file: the largest sound readings pass; the ``float8_e4m3fn``
    control, five planted faults and the state rounded to bfloat16 are refused
    by BOTH numbers (the least reading of each over its seeds);
    ``rope_applied`` reads inside them, which PERF.md says plainly."""
    limits = _json("benchmark", "limits", CELL + ".json")["limits"]
    assert set(limits) == {"served_logit_gap", "served_logit_gap_mean"}
    assert compare.verdict({"served_logit_gap": 0.2856, "served_logit_gap_mean": 0.00700}, limits)[0]
    refused = {"control": (3.322, 0.789), "ssm_state_dropped": (6.30, 2.46),
               "conv_tail_dropped": (7.56, 4.03), "inner_norms_left_out": (6.22, 1.72),
               "gate_left_out": (7.79, 3.95), "skip_left_out": (7.69, 4.11),
               "state_bfloat16": (0.830, 0.0296)}
    for name, (gap, mean) in refused.items():
        ok, rows = compare.verdict({"served_logit_gap": gap, "served_logit_gap_mean": mean}, limits)
        assert not ok and all(r["value"] > r["limit"] for r in rows), name
    assert compare.verdict({"served_logit_gap": 0.520, "served_logit_gap_mean": 0.0120}, limits)[0]
    assert not compare.verdict({"served_logit_gap": 0.2}, limits)[0]     # a number left out


# --- the cell at test sizes ---------------------------------------------------------


def test_cell_sound_run_is_correct():
    r = run.run_cell("tiny-ssm-cell", 2**31 + 21, 1.5, False, bench_file=BENCH,
                     require_chip=False)
    assert r["correct"], r["checks"]
    assert r["notes"]["served"]["served_logit_gap"] == r["checks"][0]["value"]
    assert r["notes"]["served"]["served_not_best_share"] < 0.05
    assert r["failed"] == 0 and r["attempted"] > 10
    for name in ("setup_s", "serve_tokens_per_s", "ttft_p75_ms", "itl_p95_ms"):
        assert r["metrics"][name]["value"] > 0
    f = r["notes"]["family"]
    # 4 slots x 4 Mamba layers x 7 rows of 128 float32; one handoff an admission
    assert f["slot_state_bytes"] == 4 * 4 * 7 * 128 * 4 and f["state_handoffs"] >= r["attempted"]
    assert f["prompt_tokens"] > 0 and 0 < f["decode_live_sum"] <= 4 * f["decode_steps"]


@pytest.fixture(scope="module")
def fixed_sample():
    """SIX requests of fixed sizes served to their end through the Engine at
    the test cell's sizes (no clock anywhere), as the driver records them."""
    from tony_tpu.serve.engine import Engine, Request, ServeConfig

    bench = _json("benchmark", "tests", "data", "BENCHMARK_ssm_hybrid.json")
    config = _json("benchmark", "tests", "data", bench["configs"][0]["file"])
    s, dtype, seed = weights.sizes_of(config), jnp.dtype(config["dtype"]), 2**31 + 22
    serve = {**config["serve"], "prefill_buckets": tuple(config["serve"]["prefill_buckets"])}
    key = weights.base_key(seed)
    params = jax.jit(lambda k: weights.make_params(k, s, dtype))(key)
    engine = Engine(params, fam.model(s, serve, dtype), ServeConfig(**serve))
    mix = _json("benchmark", "tests", "data", "traffic", "tiny-reason.json")
    prompts = trafficgen.Prompts(mix, seed, s["v"])
    sizes = [(9, 12), (24, 30), (60, 40), (17, 8), (33, 21), (48, 16)]
    rids = [engine.submit(Request(prompt=prompts.make(i, p), max_new_tokens=o, temperature=0.0,
                                  eos_id=None)) for i, (p, o) in enumerate(sizes)]
    done = engine.run()
    sample = [{"plen": p, "olen": o, "prompt": prompts.make(i, p),
               "tokens": [int(t) for t in done[rid].tokens]}
              for i, (rid, (p, o)) in enumerate(zip(rids, sizes))]
    assert all(len(r["tokens"]) == r["olen"] for r in sample)
    limits = _json("benchmark", "tests", "data", "limits", "tiny-ssm-cell.json")["limits"]
    ctx = {"config": config, "mix": mix, "refuse": run.Refused, "extra": {}}
    return ctx, s, dtype, key, sample, limits


def test_the_fixed_sample_is_sound_and_the_control_is_not(fixed_sample):
    ctx, s, dtype, key, sample, limits = fixed_sample
    numbers, notes = driver.reference_numbers({**ctx, "extra": {"control": 1}}, fam, s, dtype,
                                              key, sample)
    assert compare.verdict(numbers, limits)[0], numbers
    assert not compare.verdict(notes["control"], limits)[0], notes["control"]


@pytest.mark.parametrize("fault", ["token_altered", *ref.FAULTS])
def test_each_fault_on_the_fixed_sample_is_not_correct(fixed_sample, fault):
    ctx, s, dtype, key, sample, limits = fixed_sample
    if fault == "token_altered":
        sample = [dict(r, tokens=list(r["tokens"])) for r in sample]
        sample[0]["tokens"][len(sample[0]["tokens"]) // 2] ^= 1
    numbers, _ = driver.reference_numbers({**ctx, "extra": {"fault": fault}}, fam, s, dtype, key,
                                          sample)
    if fault == "state_bfloat16":
        # rounding h to bfloat16 moves these float32 logits by ~1e-2 (tests/test_ssm_hybrid.py
        # refuses it on the logits) but hardly a served token's RANK: the served-token gap,
        # which is what a cell's limits are on, does not promise to see it (PERF.md section 4)
        assert 0 <= numbers["served_logit_gap"] < 0.05
        return
    assert not compare.verdict(numbers, limits)[0], (fault, numbers)


# --- the reader ---------------------------------------------------------------------


def _reader_ctx(config, family=True, trace=True):
    window = {"prompt_tokens": 800, "decode_live_sum": 10 * 128,
              "slot_state_bytes": 128 * 10_117_120, "state_handoffs": 2, "decode_steps": 10}
    t = {"module_events": {
        "jit_serve_decode(123)": [(0.1 * i, 0.016) for i in range(10)],
        "jit_serve_prefill(7)": [(5.0, 0.03)], "jit_serve_scatter(3)": [(5.1, 0.002)],
        "jit_serve_zero_slot_state(4)": [(4.9, 0.001)], "jit_other(1)": [(9.0, 0.007)]},
        "op_events": {"selective_scan.3 custom-call:tpu_custom_call (bf16[400,5120], f32[16,5120])":
                      [(5.0 + 0.001 * i, 0.0004) for i in range(26)],
                      "fusion.9 fusion f32[128,5120]": [(5.0, 0.001)]}}
    return {
        "config": config, "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "trace": t if trace else None,
        "observed": {
            "prefill_lens": [300, 500], "decode_ctx": [1500] * 1280, "window_s": 25.0,
            "traced_decode_lens": [[1500] * 128] * 10, "traced_prefill_lens": [400],
            "family": {"window": window, "trace0": window, "trace1": window} if family else None,
        },
    }


def test_reader_reads_every_member_and_is_silent_without_the_program_s_counters(config, s):
    from benchmark.layer_metrics import ssm_hybrid as reader

    names = [m["name"] for m in _json("BENCHMARK.json")["per_layer"]
             if m["name"].startswith("ssm_hybrid.")]
    assert len(names) == 6
    got = {n: reader.read(n, _reader_ctx(config)) for n in names}
    assert all(v is not None and v > 0 for v in got.values()), got
    step_bytes = 6_063_467_264 + 2 * 128 * 10_117_120 + 1024 * 128 * 1500
    assert got["ssm_hybrid.decode_step_roofline"] == pytest.approx(
        100 * step_bytes / 819e9 / 0.016, rel=1e-6)
    assert got["ssm_hybrid.state_stream_share"] == pytest.approx(
        100 * 2 * 128 * 10_117_120 / step_bytes, rel=1e-6)
    assert got["ssm_hybrid.prefill_roofline"] == pytest.approx(
        100 * counts.prefill_flops(s, 400) / 197e12 / 0.03)
    scan = counts.scan_kernel_cost(s, 400)
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12          # its bytes bind, not its operations
    assert got["ssm_hybrid.scan_roofline"] == pytest.approx(
        100 * 26 * scan["bytes"] / 819e9 / (26 * 0.0004))
    assert got["ssm_hybrid.handoff_share"] == pytest.approx(100 * 0.003 / 0.2)
    assert got["ssm_hybrid.step_mfu"] == pytest.approx(
        100 * counts.serve_flops(s, [300, 500], [1500] * 1280) / 25.0 / 197e12)
    assert all(v <= 100 for k, v in got.items())
    # a program without the counters (the parent), or a run without a trace
    assert all(reader.read(n, _reader_ctx(config, family=False)) is None for n in names)
    quiet = {n: reader.read(n, _reader_ctx(config, trace=False)) for n in names}
    assert quiet["ssm_hybrid.decode_step_roofline"] is None
    assert quiet["ssm_hybrid.scan_roofline"] is None and quiet["ssm_hybrid.handoff_share"] is None
    assert quiet["ssm_hybrid.step_mfu"] > 0 and quiet["ssm_hybrid.state_stream_share"] > 0
    # and another family's configuration reads nothing
    assert reader.read(names[0], _reader_ctx({"hidden_size": 64})) is None
    assert fam.counters(object()) is None
