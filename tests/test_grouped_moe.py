"""Grouped (dropless) MoE dispatch: parity, dropless semantics, ep sharding.

The grouped path (parallel.moe dispatch='grouped' over ops.grouped_mm) is
validated against the gather/einsum capacity reference the same way every
kernel in this repo is: identical values AND gradients on undropped tokens,
explicit divergence exactly where the semantics differ (forced overflow),
and mesh-sharded == replicated.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.parallel.mesh import MeshShape, build_mesh, set_default_mesh
from tony_tpu.parallel.moe import (
    MoEConfig,
    init_moe_params,
    moe_block,
    routing_stats,
)

BASE = MoEConfig(dim=32, ffn_dim=64, n_experts=4, top_k=2, capacity_factor=8.0)


@pytest.fixture(scope="module")
def params():
    return init_moe_params(jax.random.key(0), BASE, dtype=jnp.float32)


@pytest.fixture(scope="module")
def x():
    return jax.random.normal(jax.random.key(1), (2, 24, 32), jnp.float32)


def run(params, x, **kw):
    cfg = dataclasses.replace(BASE, **kw)

    def loss(p, xx):
        y, aux = moe_block(p, xx, cfg)
        return jnp.sum(y * y) + aux

    val, grads = jax.value_and_grad(loss)(params, x)
    y, aux = moe_block(params, x, cfg)
    return val, grads, y, aux


@pytest.mark.parametrize("gmm_impl", ["scan", "pallas"])
def test_grouped_matches_gather_values_and_grads(params, x, gmm_impl):
    """With ample capacity nothing is dropped, so the dropless grouped path
    (both the lax.scan fallback and the interpreted pallas kernel) must
    reproduce the gather dispatch exactly: outputs, aux loss, and every
    parameter gradient."""
    v_g, g_g, y_g, aux_g = run(params, x, dispatch="gather")
    v_r, g_r, y_r, aux_r = run(params, x, dispatch="grouped", gmm_impl=gmm_impl)
    assert abs(float(v_g) - float(v_r)) < 1e-4
    assert abs(float(aux_g) - float(aux_r)) < 1e-6
    np.testing.assert_allclose(np.asarray(y_r), np.asarray(y_g), atol=1e-5)
    for k in g_g:
        np.testing.assert_allclose(
            np.asarray(g_r[k]), np.asarray(g_g[k]), atol=1e-4, err_msg=k
        )


def test_grouped_matches_einsum_reference(params, x):
    """And against the one-hot einsum reference directly (the original
    GShard formulation every dispatch is anchored to)."""
    _, _, y_e, aux_e = run(params, x, dispatch="einsum")
    _, _, y_r, aux_r = run(params, x, dispatch="grouped")
    assert abs(float(aux_e) - float(aux_r)) < 1e-6
    np.testing.assert_allclose(np.asarray(y_r), np.asarray(y_e), atol=1e-5)


def test_group_block_invariance(params, x):
    """The row-tile size is a layout knob, not a semantic one: outputs and
    grads are identical across block sizes (including one forcing many
    partial tiles)."""
    v8, g8, y8, _ = run(params, x, dispatch="grouped", group_block=8)
    v128, g128, y128, _ = run(params, x, dispatch="grouped", group_block=128)
    # the scalar is a float32 sum in the hundreds: one unit in its last
    # place is 3e-5, so the bound is relative
    assert abs(float(v8) - float(v128)) <= 1e-6 * abs(float(v128))
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y128), atol=1e-6)
    for k in g8:
        # float32 grads of magnitude ~20 accumulated in another tile order:
        # a few units in the last place (2e-6 each) — relative, not absolute
        np.testing.assert_allclose(
            np.asarray(g8[k]), np.asarray(g128[k]), atol=1e-5, rtol=1e-5,
            err_msg=k,
        )


def test_dropless_vs_capacity_under_forced_overflow(params, x):
    """At a drop-forcing capacity factor the gather path zeroes overflow
    tokens' FFN delta while grouped serves every route: the outputs MUST
    differ, and grouped must equal the ample-capacity result exactly
    (dropless == infinite capacity, by construction)."""
    _, _, y_tight, _ = run(params, x, dispatch="gather", capacity_factor=0.25)
    _, _, y_ample, _ = run(params, x, dispatch="gather", capacity_factor=100.0)
    _, _, y_grouped, _ = run(
        params, x, dispatch="grouped", capacity_factor=0.25
    )
    # sanity: the tight capacity really dropped something
    assert float(jnp.max(jnp.abs(y_tight - y_ample))) > 1e-3
    np.testing.assert_allclose(
        np.asarray(y_grouped), np.asarray(y_ample), atol=1e-5
    )
    # and the training loss sees the difference (the dropped tokens' zero
    # delta is a real modeling change, not a numerics blur)
    lt = float(jnp.sum(y_tight * y_tight))
    lg = float(jnp.sum(y_grouped * y_grouped))
    assert abs(lt - lg) > 1e-4


@pytest.mark.parametrize("gmm_impl", ["scan", "pallas"])
def test_empty_expert_is_well_defined(params, x, gmm_impl):
    """An expert the router never picks still produces finite outputs and a
    defined (zero) weight gradient — the layout guarantees every expert at
    least one (zero-padded) row tile, so no dW block is left unwritten."""
    from tony_tpu.parallel.moe import _moe_grouped

    cfg = dataclasses.replace(BASE, dispatch="grouped", gmm_impl=gmm_impl)
    flat = x.reshape(-1, x.shape[-1])
    # router probabilities with expert 0 pinned to zero mass
    logits = jax.random.normal(jax.random.key(9), (flat.shape[0], 4))
    probs = jax.nn.softmax(logits.at[:, 0].set(-1e9), axis=-1)

    def loss(pp):
        y, aux = _moe_grouped(pp, flat, cfg, probs)
        return jnp.sum(y * y) + aux

    val, grads = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(val))
    for k, g in grads.items():
        assert np.isfinite(np.asarray(g)).all(), k
    # the dead expert's FFN weights get exactly zero gradient
    np.testing.assert_array_equal(np.asarray(grads["w1"][0]), 0.0)
    np.testing.assert_array_equal(np.asarray(grads["w2"][0]), 0.0)


def test_ep_mesh_shard_map_parity(params, x):
    """With a default ep mesh registered, the grouped path shard_maps the
    expert FFN over ep (local experts per shard + psum combine): values and
    grads must match the unsharded single-device path exactly."""
    cfg = dataclasses.replace(BASE, dispatch="grouped")

    def loss(p, xx):
        y, aux = moe_block(p, xx, cfg)
        return jnp.sum(y * y) + aux

    set_default_mesh(None)
    expect_y, expect_aux = moe_block(params, x, cfg)
    expect_g = jax.grad(loss)(params, x)

    mesh = build_mesh(MeshShape(ep=2, fsdp=2))
    set_default_mesh(mesh)
    try:
        got_y, got_aux = jax.jit(lambda p, a: moe_block(p, a, cfg))(params, x)
        got_g = jax.jit(jax.grad(loss))(params, x)
    finally:
        set_default_mesh(None)
    assert abs(float(got_aux) - float(expect_aux)) < 1e-6
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(expect_y), atol=1e-5)
    for k in expect_g:
        np.testing.assert_allclose(
            np.asarray(got_g[k]), np.asarray(expect_g[k]), atol=1e-4, err_msg=k
        )


def test_ep_sharded_params_under_jit(params, x):
    """dispatch='grouped' with expert weights device_put over an ep mesh
    (no default mesh: plain GSPMD auto-sharding) stays exact — the sort/
    scatter dispatch partitions correctly under jit."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tony_tpu.parallel.moe import logical_axes
    from tony_tpu.parallel.sharding import DEFAULT_RULES, tree_shardings

    cfg = dataclasses.replace(BASE, dispatch="grouped")
    expect, _ = moe_block(params, x, cfg)

    mesh = build_mesh(MeshShape(ep=2, fsdp=2, tp=2))
    shardings = tree_shardings(logical_axes(), mesh, DEFAULT_RULES)
    params_s = jax.device_put(params, shardings)
    x_s = jax.device_put(x, NamedSharding(mesh, P(("dp", "fsdp"), None, None)))
    got, _ = jax.jit(lambda p, a: moe_block(p, a, cfg))(params_s, x_s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=1e-5)


def test_model_level_grouped_llama(x):
    """LlamaConfig(moe_dispatch='grouped') end to end: the tiny MoE model's
    loss and gradients match the gather dispatch at ample capacity."""
    from tony_tpu.models.llama import LlamaConfig, init_params, loss_fn

    def run_model(dispatch):
        cfg = LlamaConfig.tiny_moe(
            moe_dispatch=dispatch, moe_capacity_factor=8.0
        )
        p = init_params(jax.random.key(0), LlamaConfig.tiny_moe())
        toks = jax.random.randint(jax.random.key(1), (4, 33), 0, cfg.vocab_size)
        val, grads = jax.value_and_grad(loss_fn)(p, toks, cfg)
        return val, grads

    v_g, g_g = run_model("gather")
    v_r, g_r = run_model("grouped")
    assert abs(float(v_g) - float(v_r)) < 1e-5
    flat_g = jax.tree.leaves(g_g)
    flat_r = jax.tree.leaves(g_r)
    for a, b in zip(flat_r, flat_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_capacity_rounds_to_sublane_multiple():
    """capacity() is always a multiple of 8 (fp32 TPU sublane tile) and
    never below the exact ceil it used to return."""
    for cf, k, e, t in [(1.25, 2, 8, 100), (0.25, 2, 4, 32), (1.0, 1, 3, 7)]:
        cfg = MoEConfig(dim=8, ffn_dim=16, n_experts=e, top_k=k,
                        capacity_factor=cf)
        cap = cfg.capacity(t)
        assert cap % 8 == 0
        assert cap >= max(1, int(np.ceil(cf * k * t / e)))


def test_router_math_is_fp32_for_bf16_inputs():
    """Satellite numerics guard: even with bf16 activations AND a bf16
    router, the softmax/aux math runs in fp32 — the block's probabilities
    match an explicit fp32 recomputation from the same (bf16-rounded)
    operands to fp32 precision, not bf16 precision."""
    cfg = dataclasses.replace(BASE, dispatch="grouped")
    p32 = init_moe_params(jax.random.key(3), cfg, dtype=jnp.float32)
    p16 = {k: v.astype(jnp.bfloat16) for k, v in p32.items()}
    x16 = jax.random.normal(
        jax.random.key(4), (2, 16, 32), jnp.float32
    ).astype(jnp.bfloat16)

    _, aux = moe_block(p16, x16, cfg)
    assert aux.dtype == jnp.float32

    # fp32 reference from the SAME bf16-rounded inputs: if the block's
    # internal math were bf16, this would miss by ~1e-2, not 1e-6
    from tony_tpu.parallel.moe import _top_k_select

    flat = x16.reshape(-1, 32).astype(jnp.float32)
    probs = jax.nn.softmax(flat @ p16["router"].astype(jnp.float32), axis=-1)
    _, _, _, aux_ref = _top_k_select(probs, cfg)
    assert abs(float(aux) - float(aux_ref)) < 1e-6


def test_routing_stats_reports_drops():
    cfg = dataclasses.replace(BASE, capacity_factor=0.25)
    xx = jax.random.normal(jax.random.key(7), (512, 32))
    params = init_moe_params(jax.random.key(8), cfg, dtype=jnp.float32)
    probs = jax.nn.softmax(xx @ params["router"], axis=-1)
    stats = routing_stats(probs, cfg)
    assert 0.0 < stats["dropped_frac"] < 1.0
    assert stats["load_imbalance"] >= 1.0
    assert stats["capacity"] % 8 == 0
    # ample capacity drops nothing
    ample = routing_stats(probs, dataclasses.replace(cfg, capacity_factor=8.0))
    assert ample["dropped_frac"] == 0.0


def test_unknown_dispatch_and_impl_raise(params, x):
    with pytest.raises(ValueError, match="dispatch"):
        moe_block(params, x, dataclasses.replace(BASE, dispatch="nope"))
    with pytest.raises(ValueError, match="gmm impl"):
        moe_block(
            params, x,
            dataclasses.replace(BASE, dispatch="grouped", gmm_impl="nope"),
        )


def test_grouped_is_shard_map_safe():
    """The scan-gmm grouped path runs inside a manual shard_map region (the
    property the pp pipeline stages rely on): a batch-sharded moe_block over
    a manual axis matches the unsharded path exactly — routing is per-token,
    so splitting the batch must not change any token's output."""
    from jax.sharding import PartitionSpec as P

    
    cfg = dataclasses.replace(BASE, dispatch="grouped")
    params = init_moe_params(jax.random.key(0), BASE, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 24, 32), jnp.float32)
    expect, _ = moe_block(params, x, cfg)

    mesh = build_mesh(MeshShape(dp=2))

    def local(p, xx):
        return moe_block(p, xx, cfg)[0]

    got = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P("dp", None, None)),
        out_specs=P("dp", None, None),
    )(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=1e-5)


# --- nonfinite-input robustness (the numerics-health contract) ----------------


@pytest.mark.parametrize("gmm_impl", ["scan", "pallas"])
def test_poisoned_tokens_propagate_nonfinite_like_reference(params, x, gmm_impl):
    """A NaN/Inf token riding into the grouped dispatch must PROPAGATE into
    exactly that token's output — never be masked by the sorted/padded
    group layout (padding rows are zeroed by construction; a bug that
    zeroed a real poisoned row the same way would launder the NaN) and
    never smear into clean tokens' outputs. The per-token nonfinite mask
    and the clean tokens' values match the dense per-token reference (the
    gather dispatch). The router aux loss sees every token, so it goes
    nonfinite — the signal obs/health.py's sentinel counts."""
    xp = x.at[0, 5].set(jnp.nan).at[1, 11].set(jnp.inf)

    def out(dispatch, **kw):
        cfg = dataclasses.replace(BASE, dispatch=dispatch, **kw)
        return moe_block(params, xp, cfg)

    y_ref, aux_ref = out("gather")
    y_got, aux_got = out("grouped", gmm_impl=gmm_impl)
    ref = np.asarray(y_ref)
    got = np.asarray(y_got)
    # the reference poisons exactly the poisoned tokens' rows
    bad_ref = {tuple(i[:2]) for i in np.argwhere(~np.isfinite(ref))}
    assert bad_ref == {(0, 5), (1, 11)}
    np.testing.assert_array_equal(np.isfinite(got), np.isfinite(ref))
    finite = np.isfinite(ref)
    np.testing.assert_allclose(got[finite], ref[finite], atol=1e-5)
    # the fp32 router statistics propagate the poison into the aux loss
    assert not np.isfinite(float(aux_ref))
    assert not np.isfinite(float(aux_got))


@pytest.mark.parametrize("gmm_impl", ["scan", "pallas"])
def test_poisoned_expert_weights_propagate_to_routed_tokens(params, x, gmm_impl):
    """NaN in ONE expert's FFN weights must reach exactly the tokens routed
    to that expert (value-matched masks vs the gather reference): the
    grouped GEMM's block-aligned tiles touch only their expert's weights,
    so the poison must neither vanish in padding nor leak across group
    boundaries into other experts' tokens."""
    bad_params = {
        **params,
        "w1": params["w1"].at[2].set(jnp.nan),  # poison expert 2 only
    }

    def out(dispatch, **kw):
        cfg = dataclasses.replace(BASE, dispatch=dispatch, **kw)
        y, _ = moe_block(bad_params, x, cfg)
        return np.asarray(y)

    ref = out("gather")
    got = out("grouped", gmm_impl=gmm_impl)
    # some but not all tokens hit expert 2 at top_k=2 over 4 experts
    assert 0 < (~np.isfinite(ref)).any(axis=-1).sum() < x.shape[0] * x.shape[1]
    np.testing.assert_array_equal(np.isfinite(got), np.isfinite(ref))
    finite = np.isfinite(ref)
    np.testing.assert_allclose(got[finite], ref[finite], atol=1e-5)
