"""Decomposed fsdp collectives + bucketed dp grad reduce (ops/overlap.py).

The SURVEY harness idiom: every ring decomposition is compared against the
dense single-device reference on small shapes — value AND grad, for both
impls ('scan' pure-XLA, 'pallas' interpret-mode kernels) and both shard
dims. The trainer-side contract is stronger than allclose: bucketing a
grad all-reduce is a schedule, not an approximation, so the bucketed loss
trajectory must be BITWISE-identical to the single-collective one.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map as _shard_map
from tony_tpu.ops.overlap import (
    all_gather_matmul_local,
    bucket_bytes_from_report,
    bucket_plan,
    bucketed_psum,
    matmul_reduce_scatter_local,
    overlap_matmul,
)
from tony_tpu.parallel.mesh import MeshShape, build_mesh, set_default_mesh

IMPLS = ("scan", "pallas")


def _fsdp_mesh():
    return build_mesh(MeshShape(fsdp=4, tp=2))


class TestRingOps:
    """Ring all-gather-matmul / matmul-reduce-scatter vs the dense form."""

    @pytest.mark.parametrize("impl", IMPLS)
    @pytest.mark.parametrize("gather_dim", [0, 1])
    def test_all_gather_matmul_value_and_grad(self, impl, gather_dim):
        mesh = _fsdp_mesh()
        x = jax.random.normal(jax.random.key(0), (8, 16), jnp.float32)
        w = jax.random.normal(jax.random.key(1), (16, 24), jnp.float32)
        x_spec = P("fsdp", None)
        w_spec = P("fsdp", None) if gather_dim == 0 else P(None, "fsdp")

        def ring(x, w):
            return _shard_map(
                lambda xl, wl: all_gather_matmul_local(
                    xl, wl, "fsdp", gather_dim, impl
                ),
                mesh=mesh, in_specs=(x_spec, w_spec), out_specs=x_spec,
                axis_names={"fsdp"},
            )(x, w)

        np.testing.assert_allclose(
            np.asarray(ring(x, w)), np.asarray(x @ w), rtol=1e-5, atol=1e-5
        )
        # grad symmetry: the custom_vjp's mirrored rings vs autodiff of x @ w
        loss = lambda f: lambda a, b: (jnp.sin(f(a, b))).sum()
        gx, gw = jax.grad(loss(ring), argnums=(0, 1))(x, w)
        rx, rw = jax.grad(loss(lambda a, b: a @ b), argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("impl", IMPLS)
    @pytest.mark.parametrize("scatter_dim", [0, 1])
    def test_matmul_reduce_scatter_value_and_grad(self, impl, scatter_dim):
        mesh = _fsdp_mesh()
        x = jax.random.normal(jax.random.key(2), (8, 16), jnp.float32)
        g = jax.random.normal(jax.random.key(3), (8, 24), jnp.float32)
        in_spec = P("fsdp", None)  # batch rows around the ring
        out_spec = P("fsdp", None) if scatter_dim == 0 else P(None, "fsdp")

        def rs(x, g):
            return _shard_map(
                lambda xl, gl: matmul_reduce_scatter_local(
                    xl, gl, "fsdp", scatter_dim, impl
                ),
                mesh=mesh, in_specs=(in_spec, in_spec), out_specs=out_spec,
                axis_names={"fsdp"},
            )(x, g)

        np.testing.assert_allclose(
            np.asarray(rs(x, g)), np.asarray(x.T @ g), rtol=1e-5, atol=1e-5
        )
        loss = lambda f: lambda a, b: (jnp.sin(f(a, b))).sum()
        gx, gg = jax.grad(loss(rs), argnums=(0, 1))(x, g)
        rx, rg = jax.grad(loss(lambda a, b: a.T @ b), argnums=(0, 1))(x, g)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gg), np.asarray(rg),
                                   rtol=1e-4, atol=1e-5)

    def test_unknown_impl_raises(self):
        with pytest.raises(ValueError, match="unknown overlap impl"):
            all_gather_matmul_local(
                jnp.ones((4, 4)), jnp.ones((4, 4)), "fsdp", 0, "mosaic"
            )


class TestOverlapMatmulEntry:
    """The GSPMD-context router: applies when it can, None when it can't."""

    def test_matches_plain_matmul_3d(self):
        mesh = _fsdp_mesh()
        x = jax.random.normal(jax.random.key(0), (8, 4, 16), jnp.float32)
        w = jax.random.normal(jax.random.key(1), (16, 24), jnp.float32)
        y = overlap_matmul(x, w, gather_dim=0, impl="scan", mesh=mesh)
        assert y is not None
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(x @ w), rtol=1e-5, atol=1e-5
        )

    def test_fallbacks_return_none(self):
        mesh = _fsdp_mesh()
        x = jnp.ones((8, 16))
        # no mesh anywhere -> None
        set_default_mesh(None)
        try:
            assert overlap_matmul(x, jnp.ones((16, 8)), gather_dim=0) is None
        finally:
            set_default_mesh(mesh)
        # indivisible gathered dim (17 % fsdp=4) -> None
        assert overlap_matmul(
            x, jnp.ones((16, 17)), gather_dim=1, mesh=mesh
        ) is None
        # axis size 1 -> None
        mesh_tp = build_mesh(MeshShape(tp=8))
        assert overlap_matmul(
            x, jnp.ones((16, 8)), gather_dim=0, mesh=mesh_tp
        ) is None

    def test_none_inside_manual_region(self):
        """Inside an enclosing shard_map (a pp stage, the bucketed-dp
        trainer region) the ring must NOT try to re-bind the fsdp axis —
        the router declines and the caller runs the plain matmul."""
        mesh = _fsdp_mesh()
        w = jnp.ones((16, 24))
        seen = []

        def f(xl):
            seen.append(
                overlap_matmul(xl, w, gather_dim=0, impl="scan", mesh=mesh)
            )
            return xl

        _shard_map(
            f, mesh=mesh, in_specs=(P("fsdp", None),),
            out_specs=P("fsdp", None), axis_names={"fsdp"},
        )(jnp.ones((8, 16)))
        assert seen == [None]


class TestBucketing:
    def test_bucket_plan_order_preserving_greedy(self):
        assert bucket_plan([4, 4, 4], 8) == [[0, 1], [2]]
        assert bucket_plan([4, 4, 4], 100) == [[0, 1, 2]]
        # an oversize leaf gets its own bucket, never split
        assert bucket_plan([2, 10, 2, 2], 4) == [[0], [1], [2, 3]]
        assert bucket_plan([], 8) == []
        with pytest.raises(ValueError, match="positive"):
            bucket_plan([1], 0)

    def test_bucketed_psum_bitwise_equals_whole_tree_psum(self):
        mesh = _fsdp_mesh()
        tree = {
            "a": jax.random.normal(jax.random.key(0), (8, 8), jnp.float32),
            "b": jax.random.normal(jax.random.key(1), (8, 16), jnp.float32),
            "c": jax.random.normal(jax.random.key(2), (8,), jnp.float32),
        }
        spec = {"a": P("fsdp", None), "b": P("fsdp", None), "c": P("fsdp")}

        def run(fn):
            return _shard_map(
                fn, mesh=mesh, in_specs=(spec,), out_specs=spec,
                axis_names={"fsdp"},
            )(tree)

        whole = run(lambda t: jax.tree.map(
            lambda x: jax.lax.psum(x, "fsdp"), t
        ))
        # 40 bytes per bucket forces several buckets; grouping is exact
        bucketed = run(lambda t: bucketed_psum(t, "fsdp", bucket_bytes=40))
        for k in tree:
            assert np.array_equal(np.asarray(whole[k]),
                                  np.asarray(bucketed[k]))

    def test_bucketed_psum_inside_1f1b_style_manual_region(self):
        """The trainer's bucketed reduce runs inside the SAME kind of
        full-manual region the 1F1B schedule owns: a nested in-region call
        must still be exact (one psum per bucket over the live axis)."""
        mesh = build_mesh(MeshShape(dp=2, fsdp=4))
        x = jax.random.normal(jax.random.key(0), (8, 4), jnp.float32)

        def region(xl):
            # a manual region over dp (the 1F1B/bucketed-trainer shape):
            # reduce a 2-leaf tree in 1-leaf buckets
            t = {"w": xl * 2.0, "b": xl.sum(-1)}
            return bucketed_psum(t, "dp", bucket_bytes=1)["w"]

        got = _shard_map(
            region, mesh=mesh, in_specs=(P("dp", None),),
            out_specs=P("dp", None), axis_names={"dp"},
        )(x)
        # psum over dp of (local x * 2): each dp half sees the other's rows
        expect = np.concatenate([np.asarray(x[4:]), np.asarray(x[:4])]) * 2.0
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(x) * 2.0 + expect, rtol=1e-6
        )

    def test_bucket_bytes_from_report_sizing_and_clamps(self):
        # the committed fixture shape drives the knob
        sec = {"compute_ms": 2.8, "top_collective": {"achieved_gbps": 0.85}}
        assert bucket_bytes_from_report(sec, n_layers=4) == 1 << 20  # clamp lo
        big = {"compute_ms": 100.0, "top_collective": {"achieved_gbps": 600.0}}
        assert bucket_bytes_from_report(big, n_layers=1) == 128 << 20
        mid = {"compute_ms": 30.0, "top_collective": {"achieved_gbps": 2.0}}
        # 2e9 B/s * (2/3 * 30e-3 / 2) s = 2e7 B (to fp rounding of 2/3)
        assert abs(bucket_bytes_from_report(mid, n_layers=2) - 2e7) <= 1
        # no measurement -> the default budget
        assert bucket_bytes_from_report(None, n_layers=4) == 8 << 20
        assert bucket_bytes_from_report({}, n_layers=4) == 8 << 20
        assert bucket_bytes_from_report(sec, n_layers=0) == 8 << 20


class TestModelAndTrainer:
    """End to end: llama with overlap_impl on, and the bucketed trainer."""

    @pytest.mark.parametrize("impl", IMPLS)
    def test_llama_loss_matches_plain(self, impl):
        from tony_tpu.models.llama import LlamaConfig, init_params, loss_fn

        mesh = _fsdp_mesh()
        set_default_mesh(mesh)
        cfg = LlamaConfig.tiny()
        params = init_params(jax.random.key(0), cfg)
        toks = jax.random.randint(
            jax.random.key(1), (8, 33), 0, cfg.vocab_size
        )
        base = float(loss_fn(params, toks, cfg))
        ov = float(loss_fn(
            params, toks, dataclasses.replace(cfg, overlap_impl=impl)
        ))
        # f32 ring accumulation reorders sums: equal to ~1e-6, not bitwise
        assert abs(base - ov) < 2e-5

    @pytest.mark.slow  # ~20s: three full dense fits; the psum-of-tuple
    # bitwise contract stays tier-1 at unit level
    # (TestBucketing.test_bucketed_psum_bitwise_equals_whole_tree_psum)
    # and at trainer level on the MoE model
    # (test_moe_overlap.TestTrainerComposition) — round 20 offsets
    def test_bucketed_trainer_loss_trajectory_bitwise_identical(self):
        """Bucketing the dp grad reduce is a schedule change only: within
        the manual decomposition, one big bucket and many small buckets
        produce BITWISE-identical loss trajectories (a psum of a tuple IS
        the tuple of psums). Against the GSPMD trainer the reduction
        ORDER differs (global mean vs psum-of-local-means), so that
        comparison is allclose-tight, not bitwise — the last-ulp drift
        shows up a few optimizer steps in.
        """
        from tony_tpu.models.llama import LlamaConfig
        from tony_tpu.train.trainer import (
            default_optimizer, make_train_state, make_train_step,
        )

        cfg = LlamaConfig.tiny()
        mesh = build_mesh(MeshShape(dp=2, fsdp=2, tp=2))
        opt = default_optimizer(warmup_steps=1, decay_steps=10)
        toks = jax.random.randint(
            jax.random.key(7), (8, 33), 0, cfg.vocab_size
        )

        def run(bucket_bytes, steps=3):
            state = make_train_state(jax.random.key(0), cfg, mesh, opt)
            step = make_train_step(
                cfg, mesh, opt, grad_bucket_bytes=bucket_bytes
            )
            losses = []
            for _ in range(steps):
                state, m = step(state, toks[:, :-1], toks[:, 1:])
                losses.append(float(m["loss"]))
            return losses

        gspmd = run(None)          # partitioner-inserted single all-reduce
        one = run(1 << 30)         # manual region, one big bucket
        many = run(64 << 10)       # manual region, many small buckets
        assert one == many         # bucket count never changes the values
        np.testing.assert_allclose(gspmd, one, rtol=1e-5)
        assert all(np.isfinite(v) for v in gspmd)
