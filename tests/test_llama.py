"""Model + trainer tests on the 8-device virtual CPU mesh (see conftest.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import llama
from tony_tpu.parallel.mesh import MeshShape, build_mesh
from tony_tpu.train import trainer


@pytest.fixture(scope="module")
def tiny():
    return llama.LlamaConfig.tiny()


@pytest.fixture(scope="module")
def tiny_params(tiny):
    return llama.init_params(jax.random.key(0), tiny)


def test_devices_are_virtual_cpu():
    assert len(jax.devices()) == 8


def test_forward_shape_and_dtype(tiny, tiny_params):
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = llama.forward(tiny_params, tokens, tiny)
    assert logits.shape == (2, 16, tiny.vocab_size)
    assert logits.dtype == jnp.float32


def test_param_count_matches_config(tiny, tiny_params):
    counted = sum(x.size for x in jax.tree.leaves(tiny_params))
    assert counted == tiny.n_params


def test_logical_axes_tree_matches_params(tiny, tiny_params):
    axes = llama.logical_axes(tiny)
    p_struct = jax.tree.structure(tiny_params)
    a_struct = jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple))
    assert p_struct == a_struct
    # every axes tuple has one name per array dim
    for arr, ax in zip(
        jax.tree.leaves(tiny_params),
        jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple)),
    ):
        assert arr.ndim == len(ax)


def test_causality(tiny, tiny_params):
    """Changing a future token must not change past logits."""
    t1 = jnp.zeros((1, 16), jnp.int32)
    t2 = t1.at[0, 10].set(7)
    l1 = llama.forward(tiny_params, t1, tiny)
    l2 = llama.forward(tiny_params, t2, tiny)
    np.testing.assert_allclose(l1[0, :10], l2[0, :10], atol=1e-5)
    assert not np.allclose(l1[0, 10:], l2[0, 10:], atol=1e-5)


def test_gqa_matches_mha_with_duplicated_kv_weights():
    """GQA with kv-head weights duplicated per group must equal full MHA."""
    import dataclasses

    gqa_cfg = llama.LlamaConfig.tiny()  # n_heads=4, n_kv_heads=2
    mha_cfg = dataclasses.replace(gqa_cfg, n_kv_heads=gqa_cfg.n_heads)
    rep = gqa_cfg.n_heads // gqa_cfg.n_kv_heads
    hd = gqa_cfg.head_dim

    gqa_params = llama.init_params(jax.random.key(1), gqa_cfg)
    mha_params = jax.tree.map(lambda x: x, gqa_params)
    for w in ("wk", "wv"):
        g = gqa_params["layers"][w]  # [L, dim, n_kv*hd]
        L, d, _ = g.shape
        # duplicate each kv head `rep` times along the head axis
        expanded = jnp.repeat(g.reshape(L, d, gqa_cfg.n_kv_heads, hd), rep, axis=2)
        mha_params["layers"][w] = expanded.reshape(L, d, mha_cfg.n_kv_heads * hd)

    tokens = jax.random.randint(jax.random.key(2), (2, 16), 0, gqa_cfg.vocab_size)
    out_gqa = llama.forward(gqa_params, tokens, gqa_cfg)
    out_mha = llama.forward(mha_params, tokens, mha_cfg)
    np.testing.assert_allclose(np.asarray(out_gqa), np.asarray(out_mha), atol=1e-4)


def test_rope_rotation_preserves_norm(tiny):
    cos, sin = llama.rope_table(tiny, 8)
    x = jax.random.normal(jax.random.key(0), (1, 8, 2, tiny.head_dim))
    y = llama.apply_rope(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1),
        rtol=1e-5,
    )


@pytest.mark.parametrize(
    "shape",
    [
        # the single-axis / two-axis shapes are slow-marked: each full fit
        # costs ~6s and their axes are exercised by the 3-axis shapes here
        # plus the sharding/overlap suites (tier-1 runs close to its 870s
        # timeout)
        MeshShape(dp=2, fsdp=2, tp=2),
        pytest.param(MeshShape(fsdp=8), marks=pytest.mark.slow),
        pytest.param(MeshShape(dp=4, tp=2), marks=pytest.mark.slow),
        MeshShape(fsdp=2, tp=2, sp=2),
    ],
)
def test_train_loss_decreases_on_mesh(shape, tiny):
    """The keystone model test: sharded init + jitted step on a real mesh;
    loss must fall on a memorisable batch. Exercises DP grad-psum, FSDP
    param sharding, and TP activation collectives depending on shape."""
    mesh = build_mesh(shape)
    opt = trainer.default_optimizer(lr=1e-2, warmup_steps=1, decay_steps=100)
    state = trainer.make_train_state(jax.random.key(0), tiny, mesh, opt)
    step = trainer.make_train_step(tiny, mesh, opt)
    tokens = jax.random.randint(jax.random.key(1), (8, 33), 0, tiny.vocab_size)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    losses = []
    for _ in range(8):
        state, metrics = step(state, inputs, targets)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.9, losses
    assert int(jax.device_get(state.step)) == 8


def test_sharded_state_actually_sharded(tiny):
    mesh = build_mesh(MeshShape(fsdp=4, tp=2))
    opt = trainer.default_optimizer()
    state = trainer.make_train_state(jax.random.key(0), tiny, mesh, opt)
    w1 = state.params["layers"]["w1"]  # ("layers","embed","ffn") -> (None,fsdp,tp)
    assert len(w1.sharding.device_set) == 8
    # each shard holds 1/8 of the array
    assert w1.addressable_shards[0].data.size == w1.size // 8


def test_opt_state_sharding_matches_params_when_shapes_collide():
    """Params with identical shapes but different specs (wq vs wo when
    n_heads*head_dim == dim) must each get their own sharding for Adam
    moments -- a shape-based match would transpose one of them."""
    import dataclasses

    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(), n_heads=4, n_kv_heads=4, dim=64
    )  # wq and wo both (L, 64, 64)
    mesh = build_mesh(MeshShape(fsdp=4, tp=2))
    opt = trainer.default_optimizer()
    shardings = trainer.state_shardings(cfg, mesh, opt)
    p = shardings.params["layers"]
    assert p["wq"].spec != p["wo"].spec  # sanity: they differ
    mu = None
    for leaf in jax.tree.leaves(
        shardings.opt_state, is_leaf=lambda x: isinstance(x, dict)
    ):
        if isinstance(leaf, dict) and "layers" in leaf:
            mu = leaf
            break
    assert mu is not None
    assert mu["layers"]["wq"].spec == p["wq"].spec
    assert mu["layers"]["wo"].spec == p["wo"].spec


def test_unimplemented_attention_impl_raises_clearly():
    import dataclasses

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), attention_impl="nope")
    params = llama.init_params(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="nope"):
        llama.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)


def test_mesh_shape_validation():
    with pytest.raises(ValueError):
        build_mesh(MeshShape(dp=16))  # needs more devices than exist
    with pytest.raises(ValueError):
        MeshShape(dp=0)
    # undersized shapes truncate (with a warning) rather than raise
    assert build_mesh(MeshShape(dp=3)).size == 3


def test_train_flops_positive(tiny):
    # 6*N over the parameters a token is multiplied by (the embedding
    # lookup is a gather and the norm gains elementwise), plus attention
    d, L = tiny.dim, tiny.n_layers
    assert tiny.n_matmul_params == tiny.n_params - tiny.vocab_size * d - (2 * L + 1) * d
    flops = llama.train_flops_per_token(tiny, 64)
    assert flops == 6 * tiny.n_matmul_params + 6 * L * d * 64
    assert 6 * tiny.n_matmul_params < flops < 6 * tiny.n_params
    # the benchmark's hand-checked count at the train cell's sizes
    # (benchmark/tests/test_flops.py): 6.241 GFLOP/token
    mistral4 = llama.LlamaConfig(
        vocab_size=32768, dim=4096, n_layers=4, n_heads=32, n_kv_heads=8,
        ffn_dim=14336, max_seq_len=2048,
    )
    assert llama.train_flops_per_token(mistral4, 2048) == pytest.approx(6.241e9, rel=1e-3)
