"""The selective state-space / attention hybrid (models/ssm_hybrid.py,
serve/ssm_hybrid.py, ops/selective_scan.py, the engine's per-slot state)
against the plain reference the benchmark keeps
(benchmark/reference/ssm_hybrid_decoder.py: float32, the recurrence a plain
scan from ``h = 0`` over the whole sequence, the convolution as shifted
multiply-adds, nothing of the program), at test sizes on the CPU in float32:
logits, never sampled tokens.

The limits are the benchmark's at test sizes — 1e-3 on the widest logit gap,
1e-4 on the mean: program and reference are both float32 and differ in the
ORDER of their sums only; every planted fault and the lower-precision control
read far outside them.
"""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ssm_hybrid_decoder as ref
from tony_tpu.models import ssm_hybrid as sh
from tony_tpu.models.layer_walk import ATTENTION, MAMBA
from tony_tpu.ops import selective_scan as scan_op
from tony_tpu.serve import ssm_hybrid as steps
from tony_tpu.serve.cache import block_bytes, slot_state_bytes
from tony_tpu.serve.engine import Engine, Request, ServeConfig, steps_for

WIDEST, MEAN = 1e-3, 1e-4


def sizes(cfg: sh.SSMHybridConfig) -> dict:
    """The reference's size dict for a program configuration."""
    return {
        "d": cfg.dim, "h": cfg.n_heads, "kv": cfg.n_kv_heads, "hd": cfg.head_dim,
        "f": cfg.ffn_dim, "v": cfg.vocab_size, "layers": cfg.n_layers, "e": cfg.d_inner,
        "n": cfg.d_state, "K": cfg.d_conv, "r": cfg.dt_rank, "eps": cfg.norm_eps,
        "layer_types": tuple("attention" if t == ATTENTION else "mamba"
                             for t in cfg.layer_types),
    }


@pytest.fixture(scope="module")
def model():
    cfg = sh.SSMHybridConfig.tiny()
    return cfg, sh.init_params(jax.random.key(7), cfg)


def tokens_of(seed: int, n: int, vocab: int = 256) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, vocab, size=n).astype(np.int32)


def close(got, want):
    gap = np.abs(np.asarray(got) - np.asarray(want))
    assert gap.max() < WIDEST and gap.mean() < MEAN, (gap.max(), gap.mean())


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


@pytest.fixture
def scan_kernel(monkeypatch):
    """``scan_kernel(on)``: run the selective scan through its Pallas kernel
    (interpreted here) or through the plain ``lax.scan``. The op chooses from
    the platform, which is the CPU here, so a test steers that name."""
    from tony_tpu.serve import engine

    def steer(on: bool):
        engine._prefill_fn.cache_clear()
        engine._tail_fn.cache_clear()
        engine._aot_prefill_cache.clear()
        monkeypatch.setattr(scan_op, "_run_kernel", lambda: bool(on))

    yield steer
    steer(False)


# --- (a) the whole model against the reference ----------------------------------


def test_the_tiny_model_has_attention_at_a_non_zero_offset(model):
    cfg, _ = model
    assert cfg.layer_types == (MAMBA, MAMBA, ATTENTION, MAMBA, MAMBA)
    assert [(r.op, r.op0, r.ff0, r.n) for r in cfg.runs] == [
        (MAMBA, 0, 0, 2), (ATTENTION, 0, 2, 1), (MAMBA, 2, 3, 2)]
    published = sh.SSMHybridConfig()
    assert [i for i, t in enumerate(published.layer_types) if t == ATTENTION] == [7, 21]
    assert [(r.op, r.n) for r in published.runs] == [
        (MAMBA, 7), (ATTENTION, 1), (MAMBA, 13), (ATTENTION, 1), (MAMBA, 6)]
    assert published.n_params == 3_029_337_472
    assert published.slot_state == (26 * 19, (5120,), jnp.float32)
    assert slot_state_bytes(published, 128) == 26 * 19 * 5120 * 4 * 128
    assert published.cache_layout == (1, 128, 2) and published.cache_layers == 2


def test_param_count_matches_the_tree(model):
    cfg, params = model
    assert cfg.n_params == sum(a.size for a in jax.tree.leaves(params))
    axes = sh.logical_axes(cfg)
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple)) == \
        jax.tree.structure(params)
    assert params["mamba_layers"]["a_log"].dtype == jnp.float32
    np.testing.assert_allclose(np.exp(params["mamba_layers"]["a_log"][0, :, 0]), [1, 2, 3, 4],
                               rtol=1e-6)
    dt = np.asarray(jax.nn.softplus(params["mamba_layers"]["b_dt"]))
    assert dt.min() >= sh.DT_MIN * 0.999 and dt.max() <= sh.DT_MAX * 1.001


@pytest.mark.parametrize("kernel", [False, True], ids=["scan", "kernel"])
def test_full_forward_matches_the_reference_logits(model, scan_kernel, kernel):
    scan_kernel(kernel)
    cfg, params = model
    toks = tokens_of(1, 48)
    got = jax.jit(lambda p, t: sh.forward(p, t, cfg))(params, jnp.asarray(toks)[None])[0]
    close(got, ref.forward(params, jnp.asarray(toks), sizes(cfg)))


CONTROLS = [*ref.FAULTS, "float8_e4m3fn"]


@pytest.mark.parametrize("what", CONTROLS)
def test_each_planted_fault_and_the_control_are_refused_at_test_sizes(model, what):
    """The reference with one fault planted (or with every matrix product's
    operands rounded to float8) lies outside the limits the program is held
    to: by the widest gap and by the mean."""
    cfg, params = model
    toks = jnp.asarray(tokens_of(1, 48))
    sound = ref.forward(params, toks, sizes(cfg))
    if what in ref.FAULTS:
        other = ref.forward(params, toks, sizes(cfg), fault=what)
    else:
        other = ref.forward(params, toks, sizes(cfg), cast=ref.rounded_to(jnp.dtype(what)))
    gap = np.abs(np.asarray(other) - np.asarray(sound))
    assert gap.max() > 5 * WIDEST and gap.mean() > MEAN, (what, gap.max(), gap.mean())


@pytest.mark.parametrize("paged,scanned", [(False, False), (True, True)],
                         ids=["scans", "kernels"])
def test_engine_prefill_then_decode_matches_the_reference_logits(model, monkeypatch, paged_kernel,
                                                                 scan_kernel, paged, scanned):
    """Through the ``Engine``: request A (11 tokens, bucket 16) is prefilled
    and decodes three steps alone, then request B (27 tokens, bucket 32) is
    admitted while A decodes — behind A's fourth step, which is in flight by
    then, so B decodes from the fifth; 11 and 9 decode steps. Every logit row the
    programs sampled from — the padded-bucket prefill's, then each decode
    step's through both kinds of state — against the reference's full forward
    of prompt + served tokens. Both forms of the paged attention and of the
    selective scan (5 query rows to ONE K/V head through the paged kernel)."""
    paged_kernel(paged)
    scan_kernel(scanned)
    cfg, params = model
    cfg = replace(cfg, max_seq_len=127 - paged)     # programs of this test's own
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
        close(prefills[slot][0], want[len(p) - 1])
        close(np.stack([decodes[first_step + j][slot] for j in range(len(toks) - 1)]),
              want[len(p):len(p) + len(toks) - 1])
    m, snap = eng.metrics, eng.stats_snapshot()
    assert m.state_handoffs == 2 and eng.n_live == 0
    assert snap["scan_tokens"] == 11 + 27
    assert snap["state_stream_bytes"] == 2 * slot_state_bytes(cfg, 1) * m.decode_live_sum > 0


# --- (b) the bucket trap, chunks and tails ------------------------------------------


def _prefill(params, cfg, prompt, bucket):
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    return steps.prefill_step(
        params, jnp.asarray(padded), jnp.int32(len(prompt) - 1), jnp.float32(0), jnp.int32(0),
        jnp.float32(0), jnp.zeros((2,), jnp.uint32), cfg=cfg, bucket=bucket, max_top_k=8)


@pytest.mark.parametrize("plen", [1, 2, 11, 16])
def test_the_same_prompt_through_two_buckets_gives_the_same_state_and_logits(model, monkeypatch,
                                                                              plen):
    """The state handed over is the recurrence after the prompt's TRUE last
    position and the convolution's inputs at its true last three, not at the
    bucket's end: a prompt padded to 16 and to 32 leaves the same state and
    the same logits (short prompts keep zeros in front of the tail)."""
    cfg, params = model
    seen = _capture_logits(monkeypatch)
    prompt = tokens_of(3, plen)
    outs = [_prefill(params, cfg, prompt, b) for b in (16, 32)]
    jax.effects_barrier()
    (tok_a, _, ka, _, aux_a), (tok_b, _, kb, _, aux_b) = outs
    assert aux_a["slot_state"].shape == (cfg.n_mamba_layers * cfg.state_rows, cfg.d_inner)
    np.testing.assert_allclose(aux_a["slot_state"], aux_b["slot_state"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(seen[0], seen[1], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ka[:, :, :plen], kb[:, :, :plen], rtol=1e-5, atol=1e-6)
    assert int(tok_a) == int(tok_b)
    if plen < cfg.d_conv - 1:
        state = np.asarray(aux_a["slot_state"]).reshape(cfg.n_mamba_layers, cfg.state_rows, -1)
        assert not state[:, cfg.d_state:cfg.d_state + cfg.d_conv - 1 - plen].any()
        assert state[:, -1].any()


def _serve(model, prompts, budget=6, **serve):
    eng = _engine(model, **serve)
    rids = [eng.submit(Request(prompt=p, max_new_tokens=budget)) for p in prompts]
    done = eng.run()
    return eng, [done[r].tokens for r in rids]


@pytest.mark.parametrize("kernel", [False, True], ids=["scan", "kernel"])
def test_chunked_prefill_equals_one_piece_prefill(model, scan_kernel, kernel):
    """A 41-token prompt in chunks of 16 (each chunk starts from the
    recurrent state and the K/V its predecessor left: the tail-prefill
    program) serves the tokens of the one-piece prefill and leaves the same
    state, and the logits are the full forward's."""
    scan_kernel(kernel)
    prompts = [tokens_of(5, 41)]
    whole, toks_whole = _serve(model, prompts, slots=1)
    chunked, toks_chunked = _serve(model, prompts, slots=1, chunk_tokens=16)
    assert toks_whole == toks_chunked
    np.testing.assert_allclose(whole.cache.slot_state, chunked.cache.slot_state,
                               rtol=1e-4, atol=1e-5)
    # admission + two chunk boundaries, against one admission; the same tokens scanned
    assert (whole.metrics.state_handoffs, chunked.metrics.state_handoffs) == (1, 3)
    assert whole.stats_snapshot()["scan_tokens"] == chunked.stats_snapshot()["scan_tokens"] == 41
    cfg, params = model
    seq = np.concatenate([prompts[0], np.asarray(toks_whole[0][:-1], np.int32)])
    lg = np.asarray(sh.forward(params, jnp.asarray(seq)[None], cfg)[0, 40:])
    assert (lg.max(-1) - lg[np.arange(6), toks_whole[0]]).max() < 1e-4


def test_a_tail_prefill_from_a_handed_state_equals_the_whole_prefill(model, monkeypatch):
    """The first 16 tokens prefilled, then the other 21 as a tail from the
    state and the K/V rows the first piece left: state, rows and logits of
    the one-piece prefill of all 37."""
    cfg, params = model
    seen = _capture_logits(monkeypatch)
    prompt = tokens_of(4, 37)
    _, _, wk, wv, whole = _prefill(params, cfg, prompt, 64)
    _, _, hk, hv, head_aux = _prefill(params, cfg, prompt[:16], 16)
    ctx = [jnp.zeros((cfg.n_attn_layers, 1, 64, 1, cfg.head_dim)).at[:, 0, :16].set(
        r.transpose(0, 2, 1, 3)) for r in (hk, hv)]
    tail = np.zeros((1, 32), np.int32)
    tail[0, :21] = prompt[16:]
    _, _, tk, tv, tail_aux = steps.tail_prefill_step(
        params, *ctx, jnp.asarray(tail), jnp.int32(16), jnp.int32(20), jnp.float32(0),
        jnp.int32(0), jnp.float32(0), jnp.zeros((2,), jnp.uint32), cfg=cfg, tb=32,
        max_top_k=8, slot_state=head_aux["slot_state"])
    jax.effects_barrier()
    np.testing.assert_allclose(tail_aux["slot_state"], whole["slot_state"], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(tk[:, :, :21], wk[:, :, 16:37], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(tv[:, :, :21], wv[:, :, 16:37], rtol=1e-4, atol=1e-5)
    close(seen[2], seen[0])


# --- (c) a freed slot, a dead slot ----------------------------------------------------


@pytest.mark.parametrize("serve", [{}, {"chunk_tokens": 16}], ids=["whole", "chunked"])
def test_a_finished_slot_s_state_never_reaches_its_successor(model, serve):
    """The one slot serves request A, is freed, and its state is poisoned
    with NaN: request B (its first chunk READS the slot's state) is served as
    a fresh engine serves B alone."""
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


def test_a_dead_slot_s_state_stands_and_reaches_no_live_row(model):
    """Three slots, one request: a marker in the two dead slots' state changes
    no live row, and stands where it is after the decode steps."""
    prompt = tokens_of(8, 19)
    _, clean = _serve(model, [prompt], budget=8)
    eng = _engine(model)
    rid = eng.submit(Request(prompt=prompt, max_new_tokens=8))
    eng.step()
    marked = eng.cache.slot_state.at[:, 1:].set(7.5)
    eng.cache = eng.cache._replace(slot_state=marked)
    assert eng.run()[rid].tokens == clean[0]
    state = np.asarray(eng.cache.slot_state)
    assert (state[:, 1:] == 7.5).all() and not (state[:, 0] == 7.5).any()


# --- (d) the kernel against the plain scan ---------------------------------------------


def _scan_inputs(T, E, N, seed=0, dtype=jnp.float32):
    k = jax.random.split(jax.random.key(seed), 7)
    c, z = (jax.random.normal(k[i], (T, E)).astype(dtype) for i in (0, 1))
    delta = jax.nn.softplus(jax.random.normal(k[2], (T, E)) - 3.0)
    b, cc = (jax.random.normal(k[i], (T, N)).astype(dtype) for i in (3, 4))
    a = -jnp.exp(jax.random.normal(k[5], (N, E)))
    return c, delta, b, cc, z, a, jnp.ones((E,)), jax.random.normal(k[6], (N, E))


@pytest.mark.parametrize("T,E,N,dtype", [(64, 256, 4, jnp.float32), (48, 128, 16, jnp.float32),
                                         (96, 1024, 16, jnp.bfloat16)])
def test_selective_scan_kernel_matches_the_plain_scan(monkeypatch, T, E, N, dtype):
    """Interpret mode: values and final state, from an initial state that is
    not zero, with padded rows (``delta`` 0) past the true end — they leave
    the state where the last true row put it."""
    args = list(_scan_inputs(T, E, N, dtype=dtype))
    last = T - 11
    args[1] = args[1].at[last + 1:].set(0.0)
    want_y, want_h = scan_op._scan_plain(*args)
    monkeypatch.setattr(scan_op, "_run_kernel", lambda: True)
    assert scan_op._tiles(T, E) is not None
    got_y, got_h = jax.jit(scan_op.selective_scan)(*args)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_y, np.float32), np.asarray(want_y, np.float32), **tol)
    np.testing.assert_allclose(got_h, want_h, rtol=1e-5, atol=1e-5)
    # the state after the padded rows is the state after the last true row
    head = [x[:last + 1] if x.shape[0] == T else x for x in args]
    np.testing.assert_allclose(scan_op._scan_plain(*head)[1], want_h, rtol=1e-6, atol=1e-6)


def test_selective_scan_takes_the_plain_form_off_the_chip_and_for_untiled_shapes(monkeypatch):
    assert not scan_op._run_kernel()                      # the CPU
    assert scan_op._tiles(40, 128) is None and scan_op._tiles(64, 96) is None
    assert scan_op._tiles(512, 5120) == (256, 512) and scan_op._tiles(1280, 5120) == (256, 512)
    assert scan_op._tiles(640, 5120) == (128, 512) and scan_op._tiles(64, 128) == (64, 128)
    monkeypatch.setattr(scan_op, "_run_kernel", lambda: True)
    args = _scan_inputs(40, 128, 4)
    y, h = scan_op.selective_scan(*args)                   # falls back, no kernel
    np.testing.assert_allclose(h, scan_op._scan_plain(*args)[1], rtol=1e-6, atol=1e-6)


def test_the_one_token_step_is_the_scan_one_position_at_a_time():
    """``selective_step`` over S sequences (the state rows lead: ``[N, S,
    E]``) walks each of them as the scan does."""
    S, T, E, N = 3, 9, 128, 4
    per = [_scan_inputs(T, E, N, seed=i) for i in range(S)]
    a, skip = per[0][5], per[0][6]
    h = jnp.stack([p[7] for p in per], axis=1)
    ys = []
    for t in range(T):
        row = [jnp.stack([p[i][t] for p in per]) for i in range(5)]
        y, h = scan_op.selective_step(*row, a, skip, h)
        ys.append(y)
    for i, p in enumerate(per):
        want_y, want_h = scan_op._scan_plain(*p[:5], a, skip, p[7])
        np.testing.assert_allclose(jnp.stack(ys)[:, i], want_y, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(h[:, i], want_h, rtol=1e-5, atol=1e-5)


def test_the_sequence_form_and_the_token_form_of_the_convolution_agree(model):
    cfg, params = model
    op = jax.tree.map(lambda a: a[0], params["mamba_layers"])
    S, E, K = 9, cfg.d_inner, cfg.d_conv
    u = jax.random.normal(jax.random.key(2), (1, S, E))
    zero = jnp.zeros((1, K - 1, E))
    c, tail = sh.conv_sequence(u, op, zero, jnp.int32(S - 1))
    rows, t = [], jnp.zeros((K - 1, 1, E))
    for i in range(S):
        ci, t = sh.conv_token(u[:, i], op, t)
        rows.append(ci)
    np.testing.assert_allclose(jnp.stack(rows, axis=1), c, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(t[:, 0], tail[0], rtol=1e-6)
    # the first 4 rows, then the rest from the tail they left (padded to 8)
    _, mid = sh.conv_sequence(jnp.pad(u[:, :4], ((0, 0), (0, 4), (0, 0))), op, zero, jnp.int32(3))
    c2, end = sh.conv_sequence(u[:, 4:], op, mid, jnp.int32(S - 5))
    np.testing.assert_allclose(c2[0], c[0, 4:], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(end, tail)


# --- (e) refusals, the contract, the other families ------------------------------------


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


def test_every_refused_knob_of_the_family_has_a_case_and_a_reason(model):
    assert {knob for knob, _ in REFUSALS} == set(steps.REFUSED_KNOBS)
    cfg, params = model
    assert steps_for(cfg) is steps and steps.SCAN_STATE is True
    with pytest.raises(NotImplementedError, match="prefix.*recurrent state"):
        Engine(params, cfg, ServeConfig(slots=2))
    with pytest.raises(NotImplementedError, match="recurrent state"):
        _engine(model).adopt_blocks(list(range(8)), None)


@pytest.mark.parametrize("family", ["dense", "latent", "shortconv"])
def test_the_other_families_count_no_scan_and_no_state_stream(family):
    from tony_tpu.models import latent_moe, shortconv_moe
    from tony_tpu.models.llama import LlamaConfig, init_params as llama_init

    mod, cfg = {"dense": (None, LlamaConfig.tiny()),
                "latent": (latent_moe, latent_moe.LatentMoEConfig.tiny()),
                "shortconv": (shortconv_moe, shortconv_moe.ShortConvMoEConfig.tiny())}[family]
    params = (mod.init_params if mod else llama_init)(jax.random.key(0), cfg)
    eng = Engine(params, cfg, ServeConfig(slots=2, max_len=64, kv_block=8,
                                          prefill_buckets=(16, 32), prefix=False))
    eng.run([Request(prompt=tokens_of(1, 20), max_new_tokens=4)])
    snap = eng.stats_snapshot()
    assert steps_for(cfg).SCAN_STATE is False
    assert snap["scan_tokens"] == 0 and snap["state_stream_bytes"] == 0
    assert snap["generated_tokens"] == 4 and eng.metrics.decode_live_sum > 0


def test_the_pool_holds_attention_layers_only_and_the_state_is_beside_it(model):
    cfg, _ = model
    eng = _engine(model)
    La, Lm = cfg.n_attn_layers, cfg.n_mamba_layers
    assert (La, Lm) == (1, 4)
    assert eng.cache.k.shape[0] == La and eng.cache.k.shape[2:] == (1, 8, 16)
    assert eng.cache.slot_state.shape == (Lm * cfg.state_rows, 3, cfg.d_inner)
    assert eng.cache.slot_state.dtype == jnp.float32
    assert block_bytes(cfg, 8) == 2 * La * 1 * 8 * 16 * 4
    assert eng.metrics.kv_bytes_per_token == 2 * 16 * 4
    assert eng.stats_snapshot()["slot_state_bytes"] == Lm * 7 * 3 * 128 * 4


def test_capacity_analysis_counts_the_per_slot_state(model):
    from tony_tpu.serve.capacity import decode_step_analysis, derive_slot_budget

    cfg, _ = model
    a = decode_step_analysis(cfg, slots=2, capacity=32, kv_block=8)
    assert a["slot_state_bytes"] == slot_state_bytes(cfg, 2) == 4 * 7 * 2 * 128 * 4
    budget = derive_slot_budget(cfg, max_len=32, hbm_bytes=64 * 2**20, kv_block=8)
    assert budget["slot_state_bytes_per_slot"] == 4 * 7 * 128 * 4
    per_slot = (budget["kv_bytes_per_slot_native"] + budget["per_slot_temp_bytes"]
                + budget["slot_state_bytes_per_slot"])
    room = (budget["hbm_bytes"] - budget["param_bytes"] - budget["fixed_temp_bytes"]
            - budget["generated_code_bytes"])
    assert budget["max_slots_native"] == room // per_slot > 0
    # the state, not the K/V blocks, bounds the slots of this family
    assert budget["slot_state_bytes_per_slot"] > budget["kv_bytes_per_slot_native"]


# --- the engine, end to end ---------------------------------------------------------------


@pytest.mark.parametrize("serve", [{}, {"chunk_tokens": 16}, {"max_queue": 8}],
                         ids=["plain", "chunked", "max_queue"])
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
        lg = np.asarray(sh.forward(params, jnp.asarray(seq)[None], cfg)[0, len(p) - 1:])
        # greedy, so each served token is the row's best up to float32 rounding
        assert (lg.max(-1) - lg[np.arange(len(toks)), toks]).max() < 1e-4
    assert eng.n_live == 0 and eng.queue_depth == 0 and eng._pool.n_used == 0
    m, snap = eng.metrics, eng.stats_snapshot()
    assert snap["scan_tokens"] == m.prompt_tokens == sum(len(p) for p in prompts)
    assert snap["state_stream_bytes"] == 2 * slot_state_bytes(cfg, 1) * m.decode_live_sum > 0
    assert snap["state_handoffs"] == m.state_handoffs >= 6
    eng.reset_metrics()
    snap = eng.stats_snapshot()
    assert snap["scan_tokens"] == 0 and snap["state_stream_bytes"] == 0
    assert snap["slot_state_bytes"] == slot_state_bytes(cfg, 3) > 0


def test_shrink_serves_the_same_tokens(model):
    prompts = [tokens_of(i, n) for i, n in enumerate([5, 17, 30, 41])]
    _, plain = _serve(model, prompts, budget=5, shrink=False)
    _, shrunk = _serve(model, prompts, budget=5, shrink=True)
    assert plain == shrunk
