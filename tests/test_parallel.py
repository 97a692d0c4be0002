"""Correctness tests for the parallelism library on the 8-device CPU mesh.

Every scheme is validated against a dense single-device reference — the
harness SURVEY.md section 7 prescribes for kernel-level work ("correctness
harness = compare vs full-attention reference on small shapes").
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tony_tpu.parallel import (
    DEFAULT_RULES,
    MeshShape,
    MoEConfig,
    build_mesh,
    init_moe_params,
    make_ring_attention,
    make_ulysses_attention,
    microbatch,
    moe_block,
    pipeline_apply,
    tree_shardings,
    unmicrobatch,
)
from tony_tpu.parallel.moe import logical_axes as moe_logical_axes


def ref_causal_attention(q, k, v):
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    S = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, -1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.fixture(scope="module")
def qkv():
    B, S, H, D = 2, 64, 8, 16
    ks = jax.random.split(jax.random.key(0), 3)
    return tuple(jax.random.normal(k, (B, S, H, D)) for k in ks)


@pytest.mark.parametrize(
    "shape",
    [MeshShape(sp=8), MeshShape(dp=2, sp=4), MeshShape(tp=2, sp=4)],
    ids=["sp8", "dp2sp4", "tp2sp4"],
)
def test_ring_attention_matches_dense(qkv, shape):
    q, k, v = qkv
    expect = ref_causal_attention(q, k, v)
    got = make_ring_attention(build_mesh(shape))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=1e-4)


@pytest.mark.parametrize(
    "shape",
    [MeshShape(sp=8), MeshShape(dp=2, sp=4), MeshShape(tp=2, sp=4)],
    ids=["sp8", "dp2sp4", "tp2sp4"],
)
def test_ulysses_attention_matches_dense(qkv, shape):
    q, k, v = qkv
    expect = ref_causal_attention(q, k, v)
    got = make_ulysses_attention(build_mesh(shape))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=1e-4)


def test_ring_attention_grads_match_dense(qkv):
    q, k, v = qkv
    mesh = build_mesh(MeshShape(sp=8))
    ring = make_ring_attention(mesh)

    g_ring = jax.grad(lambda a: jnp.sum(ring(a, k, v) ** 2))(q)
    g_ref = jax.grad(lambda a: jnp.sum(ref_causal_attention(a, k, v) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref), atol=1e-3)


def test_model_level_ring_attention_via_default_mesh():
    """LlamaConfig(attention_impl='ring') end to end on an sp mesh."""
    from tony_tpu.models.llama import LlamaConfig, forward, init_params

    from tony_tpu.parallel.mesh import set_default_mesh

    set_default_mesh(build_mesh(MeshShape(sp=8)))
    cfg_ring = LlamaConfig.tiny(attention_impl="ring")
    cfg_dot = LlamaConfig.tiny(attention_impl="dot")
    params = init_params(jax.random.key(0), cfg_dot)
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg_dot.vocab_size)
    expect = forward(params, tokens, cfg_dot)
    got = forward(params, tokens, cfg_ring)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=2e-4)


def test_model_level_ulysses_attention_via_default_mesh():
    """LlamaConfig(attention_impl='ulysses') end to end on an sp mesh."""
    from tony_tpu.models.llama import LlamaConfig, forward, init_params

    from tony_tpu.parallel.mesh import set_default_mesh

    # sp=4 == tiny()'s n_heads: ulysses requires n_heads % sp == 0
    set_default_mesh(build_mesh(MeshShape(sp=4)))
    cfg_uly = LlamaConfig.tiny(attention_impl="ulysses")
    cfg_dot = LlamaConfig.tiny(attention_impl="dot")
    params = init_params(jax.random.key(0), cfg_dot)
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg_dot.vocab_size)
    expect = forward(params, tokens, cfg_dot)
    got = forward(params, tokens, cfg_uly)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=2e-4)


class TestPipeline:
    def _mesh(self, n):
        return Mesh(np.array(jax.devices()[:n]).reshape(n), ("pp",))

    def test_forward_matches_sequential(self):
        n_stages, M, mb, D = 4, 8, 2, 16
        mesh = self._mesh(n_stages)
        Ws = jnp.stack(
            [jax.random.normal(k, (D, D)) * 0.3
             for k in jax.random.split(jax.random.key(0), n_stages)]
        )
        x = jax.random.normal(jax.random.key(9), (M * mb, D))

        def stage_fn(W, h):
            return jnp.tanh(h @ W)

        got = unmicrobatch(pipeline_apply(stage_fn, Ws, microbatch(x, M), mesh=mesh))
        expect = x
        for i in range(n_stages):
            expect = jnp.tanh(expect @ Ws[i])
        np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=1e-5)

    def test_backward_matches_sequential(self):
        n_stages, M, mb, D = 4, 4, 2, 8
        mesh = self._mesh(n_stages)
        Ws = jnp.stack(
            [jax.random.normal(k, (D, D)) * 0.3
             for k in jax.random.split(jax.random.key(1), n_stages)]
        )
        x = jax.random.normal(jax.random.key(2), (M * mb, D))
        xm = microbatch(x, M)

        def stage_fn(W, h):
            return jnp.tanh(h @ W)

        def pp_loss(Ws):
            return jnp.sum(unmicrobatch(pipeline_apply(stage_fn, Ws, xm, mesh=mesh)) ** 2)

        def seq_loss(Ws):
            h = x
            for i in range(n_stages):
                h = jnp.tanh(h @ Ws[i])
            return jnp.sum(h**2)

        np.testing.assert_allclose(
            np.asarray(jax.grad(pp_loss)(Ws)),
            np.asarray(jax.grad(seq_loss)(Ws)),
            atol=1e-4,
        )

    def test_batch_not_divisible_raises(self):
        with pytest.raises(ValueError):
            microbatch(jnp.zeros((5, 2)), 2)


class TestMoE:
    def test_matches_dense_reference_with_ample_capacity(self):
        cfg = MoEConfig(dim=32, ffn_dim=64, n_experts=4, top_k=2, capacity_factor=8.0)
        params = init_moe_params(jax.random.key(0), cfg, dtype=jnp.float32)
        x = jax.random.normal(jax.random.key(1), (2, 16, 32))
        y, aux = moe_block(params, x, cfg)
        assert jnp.isfinite(aux)

        flat = x.reshape(-1, 32)
        probs = jax.nn.softmax(flat @ params["router"], -1)
        top2 = jnp.argsort(probs, axis=-1)[:, -2:]
        outs = []
        for t in range(flat.shape[0]):
            g = probs[t, top2[t]]
            g = g / g.sum()
            o = 0.0
            for i in range(2):
                e = int(top2[t, i])
                h = jax.nn.silu(flat[t] @ params["w1"][e]) * (flat[t] @ params["w3"][e])
                o = o + g[i] * (h @ params["w2"][e])
            outs.append(o)
        ref = jnp.stack(outs).reshape(x.shape)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)

    def test_capacity_overflow_drops_not_crashes(self):
        cfg = MoEConfig(dim=32, ffn_dim=64, n_experts=4, top_k=2, capacity_factor=0.25)
        params = init_moe_params(jax.random.key(0), cfg, dtype=jnp.float32)
        x = jax.random.normal(jax.random.key(1), (2, 16, 32))
        y, aux = moe_block(params, x, cfg)
        assert jnp.isfinite(y).all() and jnp.isfinite(aux)

    def test_expert_parallel_sharded_matches_unsharded(self):
        cfg = MoEConfig(dim=32, ffn_dim=64, n_experts=4, top_k=2, capacity_factor=8.0)
        params = init_moe_params(jax.random.key(0), cfg, dtype=jnp.float32)
        x = jax.random.normal(jax.random.key(1), (2, 16, 32))
        expect, _ = moe_block(params, x, cfg)

        mesh = build_mesh(MeshShape(fsdp=2, tp=4))
        rules = dict(DEFAULT_RULES)
        rules["expert"] = "tp"
        shardings = tree_shardings(moe_logical_axes(), mesh, rules)
        params_s = jax.device_put(params, shardings)
        x_s = jax.device_put(x, NamedSharding(mesh, P(("dp", "fsdp"), None, None)))
        got, _ = jax.jit(lambda p, a: moe_block(p, a, cfg))(params_s, x_s)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expect), atol=1e-4
        )


def test_multislice_mesh_shape_and_training():
    """Hybrid ICI x DCN mesh (CPU fallback layout): dp crosses 'slices'."""
    from tony_tpu.parallel import build_multislice_mesh

    mesh = build_multislice_mesh(MeshShape(fsdp=2, tp=2), n_slices=2)
    assert dict(mesh.shape) == {"dp": 2, "pp": 1, "fsdp": 2, "ep": 1, "tp": 2, "sp": 1}

    from tony_tpu.models.llama import LlamaConfig
    from tony_tpu.train.trainer import default_optimizer, make_train_state, make_train_step

    cfg = LlamaConfig.tiny()
    opt = default_optimizer(warmup_steps=1, decay_steps=10)
    state = make_train_state(jax.random.key(0), cfg, mesh, opt)
    step = make_train_step(cfg, mesh, opt)
    tokens = jax.random.randint(jax.random.key(1), (8, 33), 0, cfg.vocab_size)
    state, metrics = step(state, tokens[:, :-1], tokens[:, 1:])
    assert jnp.isfinite(float(metrics["loss"]))


def test_pp_train_step_matches_sequential():
    """The GPipe train step computes the SAME loss and gradients as the
    plain sharded trainer on identical params/batch (pipelining is a
    schedule, not an approximation)."""
    import dataclasses

    import jax

    from tony_tpu.models.llama import LlamaConfig
    from tony_tpu.train.trainer import (
        default_optimizer, make_train_state, make_train_step, pp_rules,
    )

    cfg = dataclasses.replace(LlamaConfig.tiny(), n_layers=4)
    opt = default_optimizer(warmup_steps=1, decay_steps=5)
    toks = jax.random.randint(jax.random.key(2), (8, 33), 0, cfg.vocab_size)

    mesh_pp = build_mesh(MeshShape(pp=2, fsdp=2, tp=2))
    state_pp = make_train_state(jax.random.key(0), cfg, mesh_pp, opt, pp_rules())
    step_pp = make_train_step(cfg, mesh_pp, opt, n_microbatches=4)
    _, m_pp = step_pp(state_pp, toks[:, :-1], toks[:, 1:])

    mesh_seq = build_mesh(MeshShape(fsdp=2, tp=2), devices=jax.devices()[:4])
    state_seq = make_train_state(jax.random.key(0), cfg, mesh_seq, opt)
    step_seq = make_train_step(cfg, mesh_seq, opt)
    _, m_seq = step_seq(state_seq, toks[:, :-1], toks[:, 1:])

    assert abs(float(m_pp["loss"]) - float(m_seq["loss"])) < 1e-5
    assert abs(float(m_pp["grad_norm"]) - float(m_seq["grad_norm"])) < 1e-4


def test_llama_moe_ep_sharded_matches_replicated():
    """The MoE llama loss is identical whether the expert dim is sharded
    over ep or fully replicated (the all-to-all is exact)."""
    import jax

    from tony_tpu.models.llama import LlamaConfig, init_params, loss_fn

    cfg = LlamaConfig.tiny_moe()
    params = init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (8, 33), 0, cfg.vocab_size)
    loss_rep = float(loss_fn(params, toks, cfg))

    from tony_tpu.parallel.sharding import DEFAULT_RULES, tree_shardings
    from tony_tpu.models.llama import logical_axes

    mesh = build_mesh(MeshShape(fsdp=2, ep=2, sp=2))
    shardings = tree_shardings(logical_axes(cfg), mesh, DEFAULT_RULES)
    sharded = jax.device_put(params, shardings)
    loss_ep = float(jax.jit(loss_fn, static_argnums=2)(sharded, toks, cfg))
    assert abs(loss_rep - loss_ep) < 1e-4


def test_pp_moe_train_step_matches_sequential():
    """pp x MoE: the pipelined MoE step computes the SAME cross-entropy as
    the sequential trainer (pipelining is a schedule, not an approximation);
    the aux load-balancing term is computed per microbatch — the standard
    semantics for pipelined MoE, since routing statistics exist per
    forwarded chunk — so with a nonzero coef the losses agree only closely.
    """
    import dataclasses

    import jax

    from tony_tpu.models.llama import LlamaConfig
    from tony_tpu.train.trainer import (
        default_optimizer, make_train_state, make_train_step, pp_rules,
    )

    def run(coef):
        cfg = dataclasses.replace(
            LlamaConfig.tiny_moe(), n_layers=4, moe_capacity_factor=8.0,
            moe_aux_coef=coef,
        )
        opt = default_optimizer(warmup_steps=1, decay_steps=5)
        toks = jax.random.randint(jax.random.key(2), (8, 33), 0, cfg.vocab_size)

        mesh_pp = build_mesh(MeshShape(pp=2, ep=2, fsdp=2))
        state_pp = make_train_state(jax.random.key(0), cfg, mesh_pp, opt, pp_rules())
        step_pp = make_train_step(cfg, mesh_pp, opt, n_microbatches=4)
        _, m_pp = step_pp(state_pp, toks[:, :-1], toks[:, 1:])

        mesh_seq = build_mesh(MeshShape(ep=2, fsdp=2), devices=jax.devices()[:4])
        state_seq = make_train_state(jax.random.key(0), cfg, mesh_seq, opt)
        step_seq = make_train_step(cfg, mesh_seq, opt)
        _, m_seq = step_seq(state_seq, toks[:, :-1], toks[:, 1:])
        return m_pp, m_seq

    # coef 0 isolates the CE: must match exactly
    m_pp, m_seq = run(0.0)
    assert abs(float(m_pp["loss"]) - float(m_seq["loss"])) < 1e-5
    assert abs(float(m_pp["grad_norm"]) - float(m_seq["grad_norm"])) < 1e-4
    # with the aux term on, per-microbatch routing statistics differ from
    # full-batch ones by O(coef): close, not identical
    m_pp, m_seq = run(0.01)
    assert abs(float(m_pp["loss"]) - float(m_seq["loss"])) < 5e-3


class Test1F1B:
    """The interleaved-backward pipeline schedule (O(P) activation memory)."""

    def _setup(self, P_, M, mb=2, D=8):
        mesh = Mesh(np.array(jax.devices()[:P_]).reshape(P_), ("pp",))
        Ws = jnp.stack(
            [jax.random.normal(k, (D, D)) * 0.3
             for k in jax.random.split(jax.random.key(1), P_)]
        )
        head_w = jax.random.normal(jax.random.key(3), (D, 5)) * 0.3
        x = jax.random.normal(jax.random.key(2), (M * mb, 4, D))
        tgt = jax.random.randint(jax.random.key(4), (M * mb, 4), 0, 5)
        return mesh, Ws, head_w, x, tgt

    @staticmethod
    def _head_fn(hw, y, t):
        logits = y @ hw
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        sel = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - sel)

    @pytest.mark.parametrize("P_,M", [(2, 2), (4, 1), (4, 6), (8, 4)])
    def test_loss_and_grads_match_sequential(self, P_, M):
        from tony_tpu.parallel import pipeline_train_1f1b

        mesh, Ws, head_w, x, tgt = self._setup(P_, M)
        head_fn = self._head_fn

        def stage_fn(W_stack, h):  # local stack [1, D, D]: one layer/stage
            return jnp.tanh(h @ W_stack[0])

        def pp_loss(Ws_, hw, x_):
            return pipeline_train_1f1b(
                stage_fn, head_fn, Ws_, hw, microbatch(x_, M),
                microbatch(tgt, M), mesh=mesh,
            )

        def seq_loss(Ws_, hw, x_):
            h = x_
            for i in range(P_):
                h = jnp.tanh(h @ Ws_[i])
            return head_fn(hw, h, tgt)

        lp = jax.jit(pp_loss)(Ws, head_w, x)
        ls = seq_loss(Ws, head_w, x)
        assert abs(float(lp) - float(ls)) < 1e-5
        gp = jax.jit(jax.grad(pp_loss, argnums=(0, 1, 2)))(Ws, head_w, x)
        gs = jax.grad(seq_loss, argnums=(0, 1, 2))(Ws, head_w, x)
        for a, b in zip(gp, gs):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_pp_1f1b_train_step_matches_sequential():
    """pp_schedule='1f1b' computes the same loss/grads as the plain sharded
    trainer — the interleaved backward is a schedule, not an approximation."""
    import dataclasses

    import jax

    from tony_tpu.models.llama import LlamaConfig
    from tony_tpu.train.trainer import (
        default_optimizer, make_train_state, make_train_step, pp_rules,
    )

    cfg = dataclasses.replace(LlamaConfig.tiny(), n_layers=4)
    opt = default_optimizer(warmup_steps=1, decay_steps=5)
    toks = jax.random.randint(jax.random.key(2), (8, 33), 0, cfg.vocab_size)

    mesh_pp = build_mesh(MeshShape(pp=2, fsdp=2, tp=2))
    state_pp = make_train_state(jax.random.key(0), cfg, mesh_pp, opt, pp_rules())
    step_pp = make_train_step(
        cfg, mesh_pp, opt, n_microbatches=4, pp_schedule="1f1b"
    )
    _, m_pp = step_pp(state_pp, toks[:, :-1], toks[:, 1:])

    mesh_seq = build_mesh(MeshShape(fsdp=2, tp=2), devices=jax.devices()[:4])
    state_seq = make_train_state(jax.random.key(0), cfg, mesh_seq, opt)
    step_seq = make_train_step(cfg, mesh_seq, opt)
    _, m_seq = step_seq(state_seq, toks[:, :-1], toks[:, 1:])

    assert abs(float(m_pp["loss"]) - float(m_seq["loss"])) < 1e-5
    assert abs(float(m_pp["grad_norm"]) - float(m_seq["grad_norm"])) < 1e-4


def test_pp_1f1b_rejects_moe_and_sp_attention():
    import dataclasses

    import jax

    from tony_tpu.models.llama import LlamaConfig
    from tony_tpu.train.trainer import pp_1f1b_loss_from_pairs

    mesh = build_mesh(MeshShape(pp=2, fsdp=2, tp=2))
    toks = jnp.zeros((8, 32), jnp.int32)
    with pytest.raises(NotImplementedError, match="MoE"):
        pp_1f1b_loss_from_pairs(
            {}, toks, toks, cfg=LlamaConfig.tiny_moe(), mesh=mesh,
            n_microbatches=4,
        )
    with pytest.raises(NotImplementedError, match="ring"):
        pp_1f1b_loss_from_pairs(
            {}, toks, toks,
            cfg=dataclasses.replace(LlamaConfig.tiny(), attention_impl="ring"),
            mesh=mesh, n_microbatches=4,
        )


def test_pp_1f1b_memory_is_microbatch_independent():
    """The 1F1B claim, measured: compiled temp memory for the GPipe schedule
    grows O(M) (every microbatch's stage inputs live until the autodiff
    backward), while 1F1B's stays O(P) (ring buffer of 2P-1 inputs). At
    M=32, P=4 the measured ratio is ~20x."""
    import dataclasses
    import functools

    import jax

    from tony_tpu.models.llama import LlamaConfig
    from tony_tpu.train.trainer import (
        default_optimizer, make_train_state, pp_1f1b_loss_from_pairs,
        pp_loss_from_pairs, pp_rules,
    )

    cfg = dataclasses.replace(LlamaConfig.tiny(), n_layers=4, max_seq_len=128)
    mesh = build_mesh(MeshShape(pp=4, fsdp=2))
    opt = default_optimizer(warmup_steps=1, decay_steps=10)
    state = make_train_state(jax.random.key(0), cfg, mesh, opt, pp_rules())
    toks = jax.ShapeDtypeStruct((64, 128), jnp.int32)

    def temp_mb(fn):
        loss = functools.partial(fn, cfg=cfg, mesh=mesh, n_microbatches=32)
        compiled = jax.jit(jax.value_and_grad(loss)).lower(
            state.params, toks, toks
        ).compile()
        return compiled.memory_analysis().temp_size_in_bytes / 2**20

    gpipe, one_f1b = temp_mb(pp_loss_from_pairs), temp_mb(pp_1f1b_loss_from_pairs)
    assert one_f1b < gpipe / 5, (gpipe, one_f1b)


@pytest.mark.parametrize("preset", ["llama2_7b", "llama3_8b"])
def test_real_model_shardings_resolve_on_8dev_mesh(preset):
    """The REAL 7B/8B configs' parameter AND optimizer-state shardings
    resolve on an fsdp4 x tp2 mesh without materialising anything: every
    named dim divides its mesh axes (catches head/ffn/vocab divisibility
    breaks and regressions in the opt-state path-suffix matching)."""
    import jax

    from tony_tpu.models.llama import LlamaConfig, logical_axes
    from tony_tpu.parallel.sharding import tree_shardings
    from tony_tpu.train.trainer import default_optimizer, state_shardings

    import functools

    import numpy as _np

    from tony_tpu.models import llama as _llama

    cfg = getattr(LlamaConfig, preset)()
    mesh = build_mesh(MeshShape(fsdp=4, tp=2))
    opt = default_optimizer()
    shardings = state_shardings(cfg, mesh, opt)

    def check(shapes_tree, shards_tree, what):
        flat_shapes = jax.tree.leaves(shapes_tree)
        flat_shards = jax.tree.leaves(shards_tree)
        assert len(flat_shapes) == len(flat_shards), what
        for leaf, shard in zip(flat_shapes, flat_shards):
            for dim, names in zip(leaf.shape, shard.spec + (None,) * 10):
                if names is None:
                    continue
                axes = names if isinstance(names, tuple) else (names,)
                factor = int(_np.prod([mesh.shape[a] for a in axes]))
                assert dim % factor == 0, (preset, what, leaf.shape, shard.spec)

    params_shape = jax.eval_shape(
        functools.partial(_llama.init_params, cfg=cfg), jax.random.key(0)
    )
    check(params_shape, shardings.params, "params")
    # the optimizer state (Adam mu/nu, matched by path suffix) must divide too
    opt_shape = jax.eval_shape(opt.init, params_shape)
    check(opt_shape, shardings.opt_state, "opt_state")


@pytest.mark.parametrize(
    "shape",
    [MeshShape(sp=4), MeshShape(dp=2, sp=4), MeshShape(tp=2, sp=2)],
    ids=["sp4", "dp2sp4", "tp2sp2"],
)
def test_ring_flash_attention_matches_dense(shape):
    """Ring x flash (pallas inner per chunk): exact vs dense, including
    dk/dv whose accumulators ride the ring back to their owners."""
    from tony_tpu.parallel import make_ring_flash_attention

    B, S, H, D = 2, 256, 4, 32
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D)) for kk in ks)
    attn = make_ring_flash_attention(build_mesh(shape))
    expect = ref_causal_attention(q, k, v)
    got = attn(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=1e-5)

    g_got = jax.grad(
        lambda a, b, c: jnp.sum(attn(a, b, c) ** 2), argnums=(0, 1, 2)
    )(q, k, v)
    g_ref = jax.grad(
        lambda a, b, c: jnp.sum(ref_causal_attention(a, b, c) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), g_got, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, err_msg=name
        )


def test_model_level_ring_flash_attention_via_default_mesh():
    """LlamaConfig(attention_impl='ring_flash') end to end on an sp mesh."""
    from tony_tpu.models.llama import LlamaConfig, forward, init_params

    from tony_tpu.parallel.mesh import set_default_mesh

    set_default_mesh(build_mesh(MeshShape(sp=2)))
    # tiny() has S=64: 2 chunks of 32; blocks clip to the chunk
    cfg_rf = LlamaConfig.tiny(attention_impl="ring_flash")
    cfg_dot = LlamaConfig.tiny(attention_impl="dot")
    params = init_params(jax.random.key(0), cfg_dot)
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg_dot.vocab_size)
    expect = forward(params, tokens, cfg_dot)
    got = forward(params, tokens, cfg_rf)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=2e-4)


def test_ring_flash_gqa_native_kv():
    """GQA rides the ring at native kv width (no repeat per ppermute hop):
    fwd + all grads match the expanded-KV dense reference."""
    from tony_tpu.parallel import make_ring_flash_attention

    B, S, H, Hkv, D = 2, 128, 4, 2, 32
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    rep = H // Hkv
    attn = make_ring_flash_attention(build_mesh(MeshShape(sp=2)))

    def ref(a, b, c):
        return ref_causal_attention(
            a, jnp.repeat(b, rep, axis=2), jnp.repeat(c, rep, axis=2)
        )

    np.testing.assert_allclose(
        np.asarray(attn(q, k, v)), np.asarray(ref(q, k, v)), atol=1e-5
    )
    g_got = jax.grad(
        lambda a, b, c: jnp.sum(attn(a, b, c) ** 2), argnums=(0, 1, 2)
    )(q, k, v)
    g_ref = jax.grad(
        lambda a, b, c: jnp.sum(ref(a, b, c) ** 2), argnums=(0, 1, 2)
    )(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), g_got, g_ref):
        assert a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, err_msg=name
        )


def test_ring_flash_rejects_indivisible_blocks():
    """A per-device chunk that doesn't divide the flash blocks must raise
    (a cdiv'd partial block would silently read garbage K positions)."""
    import dataclasses

    from tony_tpu.models.llama import LlamaConfig
    from tony_tpu.parallel import make_ring_flash_attention

    B, H, D = 1, 4, 32
    attn = make_ring_flash_attention(build_mesh(MeshShape(sp=2)))
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (B, 192, H, D)) for kk in ks)
    cfg = dataclasses.replace(
        LlamaConfig.tiny(), flash_block_q=64, flash_block_k=64
    )
    # S_local = 96, blocks 64 -> 96 % 64 != 0: must raise, not corrupt
    with pytest.raises(ValueError, match="multiple of the flash"):
        attn(q, k, v, cfg)


def test_moe_gather_dispatch_matches_einsum_reference():
    """The gather/scatter dispatch (the production path: zero routing
    matmul FLOPs) must match the one-hot einsum reference exactly —
    outputs, aux loss, AND gradients, including dropped-token semantics
    at a tight capacity factor."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from tony_tpu.parallel.moe import MoEConfig, init_moe_params, moe_block

    base = MoEConfig(dim=32, ffn_dim=64, n_experts=4, top_k=2,
                     capacity_factor=0.6)  # tight: forces real drops
    params = init_moe_params(jax.random.key(0), base, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 24, 32), jnp.float32)

    def run(dispatch):
        cfg = dataclasses.replace(base, dispatch=dispatch)

        def loss(p, xx):
            y, aux = moe_block(p, xx, cfg)
            return jnp.sum(y * y) + aux

        val, grads = jax.value_and_grad(loss)(params, x)
        y, aux = moe_block(params, x, cfg)
        return val, grads, y, aux

    v_g, g_g, y_g, aux_g = run("gather")
    v_e, g_e, y_e, aux_e = run("einsum")
    assert abs(float(v_g) - float(v_e)) < 1e-4
    assert abs(float(aux_g) - float(aux_e)) < 1e-6
    import numpy as np

    np.testing.assert_allclose(np.asarray(y_g), np.asarray(y_e), atol=1e-5)
    for k in g_g:
        np.testing.assert_allclose(
            np.asarray(g_g[k]), np.asarray(g_e[k]), atol=1e-4, err_msg=k
        )
