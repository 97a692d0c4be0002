"""The plain reference against the program's own model and optimizer at tiny
widths on the CPU (float32: they must agree to rounding), and the seeded
weights made whole against the same weights made a layer at a time."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import weights
from benchmark.reference import dense_decoder as ref
from benchmark.reference import train_steps as ts

MODEL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=128, vocab_size=256, num_hidden_layers=2,
             rope_theta=1e4, rms_norm_eps=1e-5)
OPT = dict(lr=3e-4, warmup_steps=0, decay_steps=1000000, b1=0.9, b2=0.95, eps=1e-8,
           weight_decay=0.1, grad_clip=1.0)


@pytest.fixture(scope="module")
def setup():
    from tony_tpu.models.llama import LlamaConfig

    s = weights.sizes_of(MODEL)
    key = weights.base_key(2**31 + 5)
    params = jax.jit(lambda k: weights.make_params(k, s, jnp.float32))(key)
    tokens = np.random.default_rng(0).integers(0, 256, (3, 4, 33)).astype(np.int32)
    return s, key, params, tokens, LlamaConfig.tiny()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_layer_made_alone_is_the_layer_made_in_the_tree(dtype):
    s = weights.sizes_of(MODEL)
    for seed in (3, 2**31 + 5):
        key = weights.base_key(seed)
        params = jax.jit(lambda k: weights.make_params(k, s, dtype))(key)
        alone = weights.make_layer(key, s, dtype, 1)            # eager
        jitted = jax.jit(lambda key, l: weights.make_layer(key, s, dtype, l))(key, jnp.int32(1))
        for k in weights.LAYER_LEAVES:
            assert (alone[k] == params["layers"][k][1]).all(), k
            assert (jitted[k] == params["layers"][k][1]).all(), k
    assert float(params["layers"]["w2"].astype(jnp.float32).std()) == pytest.approx(128**-0.5, rel=0.05)


def test_forward_matches_the_programs_model(setup):
    from tony_tpu.models.llama import forward

    s, _, params, tokens, cfg = setup
    theirs = forward(params, jnp.asarray(tokens[0, :, :-1]), cfg)
    ours = jnp.stack([ref.forward(params, jnp.asarray(t[:-1]), s) for t in tokens[0]])
    assert float(jnp.abs(theirs - ours).max()) < 2e-5


def test_loss_and_gradients_match_autodiff_of_the_programs_loss(setup):
    from tony_tpu.models.llama import loss_from_pairs

    s, _, params, tokens, cfg = setup
    x, y = tokens[0, :, :-1], tokens[0, :, 1:]
    loss_p, g_p = jax.value_and_grad(
        lambda p: loss_from_pairs(p, jnp.asarray(x), jnp.asarray(y), cfg))(params)
    loss_r, g_r = ts.loss_and_grads(ts.Fns(s), ts.unstack(params), x, y)
    assert loss_r == pytest.approx(float(loss_p), rel=1e-6)
    n_r, n_p = ts.leaf_norms(g_r), ts.leaf_norms(ts.unstack(g_p))
    assert max(abs(n_r[k] - n_p[k]) / n_r[k] for k in n_r) < 1e-5
    assert float(ref.loss(params, jnp.asarray(x), jnp.asarray(y), s)) == pytest.approx(loss_r, rel=1e-6)


def test_two_updates_match_the_programs_optimizer(setup):
    from tony_tpu.models.llama import loss_from_pairs
    from tony_tpu.train.trainer import default_optimizer

    s, key, params, tokens, cfg = setup
    ox = default_optimizer(lr=OPT["lr"], warmup_steps=0, decay_steps=OPT["decay_steps"])
    state, p = ox.init(params), params
    for t in range(2):
        x, y = jnp.asarray(tokens[t, :, :-1]), jnp.asarray(tokens[t, :, 1:])
        g = jax.grad(lambda p: loss_from_pairs(p, x, y, cfg))(p)
        u, state = ox.update(g, state, p)
        p = optax.apply_updates(p, u)
    out = ts.follow(
        s, OPT, jnp.float32, lambda l: weights.make_layer(key, s, jnp.float32, l),
        lambda n: weights.make_leaf(key, n, s, jnp.float32), tokens,
    )
    delta_p = ts.leaf_norms(ts._map_leaves(lambda a, b: a - b, ts.unstack(p), ts.unstack(params)))
    worst = max(abs(delta_p[k] - out["delta_leaf_norms"][k]) / out["delta_leaf_norms"][k]
                for k in delta_p)
    assert worst < 1e-4
    assert out["clip"][0] < 1.0  # the clip is active, as at the cell's own size
