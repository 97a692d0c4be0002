"""Overlapped expert-parallel MoE combine (tony_tpu/ops/moe_overlap.py +
the parallel.moe ``overlap_impl`` wiring; docs/PERF.md "Round 20").

The decomposed combine is a SCHEDULE change: per-token-chunk psums of
disjoint row slices are elementwise the single full-width psum, so on the
deterministic CPU backend the scan form must be BITWISE against the plain
ep path — any drift means the decomposition changed the math, not the
schedule. The pallas form swaps the grouped-GEMM kernel inside each chunk,
so values are allclose within the grouped_mm tolerance instead. Gradients
ride the custom_vjp whose backward is the matching per-chunk collective;
they must match the unsharded reference exactly like the plain ep path
does (atol 1e-4 — f32 accumulation-order drift across chunk boundaries).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tony_tpu.ops.moe_overlap import chunk_tokens_from_report, overlap_chunks
from tony_tpu.parallel.mesh import MeshShape, build_mesh, set_default_mesh
from tony_tpu.parallel.moe import MoEConfig, init_moe_params, moe_block

BASE = MoEConfig(dim=32, ffn_dim=64, n_experts=4, top_k=2,
                 capacity_factor=8.0, dispatch="grouped")


@pytest.fixture(scope="module")
def params():
    return init_moe_params(jax.random.key(0), BASE, dtype=jnp.float32)


@pytest.fixture(scope="module")
def x():
    # T=48 tokens; over the ep=2 x fsdp=2 mesh the fsdp axis carries the
    # batch, so each shard owns t_local=24 rows (auto-split: 4 chunks of 6)
    return jax.random.normal(jax.random.key(1), (2, 24, 32), jnp.float32)


def _ep_mesh():
    return build_mesh(MeshShape(ep=2, fsdp=2))


def _run(params, x, cfg):
    def loss(p, xx):
        y, aux = moe_block(p, xx, cfg)
        return jnp.sum(y * y) + aux

    y, aux = jax.jit(lambda p, a: moe_block(p, a, cfg))(params, x)
    grads = jax.jit(jax.grad(loss))(params, x)
    return y, aux, grads


# --- chunk planning -----------------------------------------------------------


class TestChunkPlanning:
    def test_overlap_chunks_auto_and_pinned(self):
        # auto: largest clean split in {4, 3, 2}
        assert overlap_chunks(24, 0) == 4
        assert overlap_chunks(9, 0) == 3
        assert overlap_chunks(10, 0) == 2
        # pinned chunk size -> t_local / chunk chunks
        assert overlap_chunks(24, 6) == 4
        assert overlap_chunks(24, 12) == 2

    def test_overlap_chunks_declines(self):
        # the decline legs: nothing to split, indivisible chunk, chunk
        # swallowing every row (a 1-chunk "decomposition" is the plain psum)
        assert overlap_chunks(1, 0) is None
        assert overlap_chunks(7, 0) is None          # prime row count, auto
        assert overlap_chunks(24, 7) is None         # 24 % 7 != 0
        assert overlap_chunks(24, 24) is None
        assert overlap_chunks(24, 48) is None

    def test_chunk_tokens_from_report_sizing_and_clamps(self):
        # 0.8 GB/s x (4ms/2) window = 1.6e6 bytes / (1024 dim x 2B)
        # = 781 tokens -> rounded down to the 256 multiple below
        rep = {"compute_ms": 4.0, "top_collective": {"achieved_gbps": 0.8}}
        assert chunk_tokens_from_report(rep, dim=1024, dtype_bytes=2) == 768
        # clamps: a starved link floors at 256, a fat one caps at 8192
        slow = {"compute_ms": 4.0, "top_collective": {"achieved_gbps": 0.001}}
        assert chunk_tokens_from_report(slow, dim=1024, dtype_bytes=2) == 256
        fast = {"compute_ms": 50.0, "top_collective": {"achieved_gbps": 90.0}}
        assert chunk_tokens_from_report(fast, dim=1024, dtype_bytes=2) == 8192
        # no measured bandwidth (ledger-less capture) -> the default
        assert chunk_tokens_from_report({}, dim=1024) == 2048
        assert chunk_tokens_from_report(None, dim=1024) == 2048
        assert chunk_tokens_from_report({"compute_ms": 4.0}, dim=1024) == 2048


# --- parity on the ep mesh ----------------------------------------------------


class TestOverlapParity:
    def test_scan_bitwise_vs_plain_ep(self, params, x):
        """scan overlap vs the single-psum ep path: forward BITWISE (the
        chunked psums are the same sums over the same disjoint rows),
        grads vs the unsharded reference within the ep path's own
        tolerance."""
        set_default_mesh(None)
        ref_cfg = dataclasses.replace(BASE, overlap_impl="off")
        _, _, ref_g = _run(params, x, ref_cfg)

        mesh = _ep_mesh()
        set_default_mesh(mesh)
        try:
            plain_y, plain_aux, _ = _run(params, x, ref_cfg)
            ov_y, ov_aux, ov_g = _run(
                params, x, dataclasses.replace(BASE, overlap_impl="scan")
            )
        finally:
            set_default_mesh(None)
        assert float(ov_aux) == float(plain_aux)  # routing stays outside
        np.testing.assert_array_equal(np.asarray(ov_y), np.asarray(plain_y))
        for k in ref_g:
            np.testing.assert_allclose(
                np.asarray(ov_g[k]), np.asarray(ref_g[k]), atol=1e-4,
                err_msg=k,
            )

    def test_pallas_allclose_vs_plain_ep(self, params, x):
        """pallas overlap (interpret mode on CPU) swaps the per-chunk
        grouped-GEMM kernel: values allclose at the grouped_mm tolerance
        (tile-local f32 accumulation order), grads at the ep tolerance."""
        mesh = _ep_mesh()
        set_default_mesh(mesh)
        try:
            plain_y, _, plain_g = _run(
                params, x, dataclasses.replace(BASE, overlap_impl="off")
            )
            ov_y, _, ov_g = _run(
                params, x, dataclasses.replace(BASE, overlap_impl="pallas")
            )
        finally:
            set_default_mesh(None)
        np.testing.assert_allclose(
            np.asarray(ov_y), np.asarray(plain_y), atol=2e-5
        )
        for k in plain_g:
            np.testing.assert_allclose(
                np.asarray(ov_g[k]), np.asarray(plain_g[k]), atol=1e-4,
                err_msg=k,
            )

    def test_chunk_size_invariance(self, params, x):
        """Any clean split gives bitwise the same answer: the chunk count
        is a schedule knob, never a semantic one."""
        mesh = _ep_mesh()
        set_default_mesh(mesh)
        try:
            runs = [
                _run(params, x,
                     dataclasses.replace(BASE, overlap_impl="scan",
                                         overlap_chunk=c))[0]
                for c in (0, 8)  # 4 / 3 chunks of t_local=24
            ]
        finally:
            set_default_mesh(None)
        for other in runs[1:]:
            np.testing.assert_array_equal(
                np.asarray(runs[0]), np.asarray(other)
            )


# --- fallback triad -----------------------------------------------------------


class TestFallbacks:
    def test_indivisible_chunk_declines_to_single_psum(self, params, x):
        """overlap_chunk=7 does not divide t_local=24: the overlap declines
        and the ep path runs its plain single psum — bitwise identical."""
        mesh = _ep_mesh()
        set_default_mesh(mesh)
        try:
            plain_y, _, _ = _run(
                params, x, dataclasses.replace(BASE, overlap_impl="off")
            )
            ov_y, _, _ = _run(
                params, x,
                dataclasses.replace(BASE, overlap_impl="scan",
                                    overlap_chunk=7),
            )
        finally:
            set_default_mesh(None)
        np.testing.assert_array_equal(np.asarray(ov_y), np.asarray(plain_y))

    def test_no_ep_axis_falls_back_to_plain_grouped(self, params, x):
        """No default mesh (and so no ep axis): overlap_impl is inert and
        the grouped path runs unsharded — bitwise identical to off."""
        set_default_mesh(None)
        plain_y, plain_aux, _ = _run(
            params, x, dataclasses.replace(BASE, overlap_impl="off")
        )
        ov_y, ov_aux, _ = _run(
            params, x, dataclasses.replace(BASE, overlap_impl="scan")
        )
        assert float(ov_aux) == float(plain_aux)
        np.testing.assert_array_equal(np.asarray(ov_y), np.asarray(plain_y))

    def test_declines_inside_manual_region(self, params, x):
        """Inside an enclosing shard_map (a pp stage, the bucketed-dp
        trainer region) the ep path — overlap included — must not try to
        re-bind the ep axis: it declines to the plain grouped FFN and the
        values match the unsharded run."""
        set_default_mesh(None)
        cfg = dataclasses.replace(BASE, overlap_impl="scan")
        expect_y, _ = moe_block(params, x, cfg)

        mesh = _ep_mesh()
        set_default_mesh(mesh)
        try:
            def f(p, xx):
                y, _ = moe_block(p, xx, cfg)
                return y

            got = jax.shard_map(
                f, mesh=mesh, in_specs=(P(), P()), out_specs=P()
            )(params, x)
        finally:
            set_default_mesh(None)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expect_y), atol=1e-5
        )

    def test_unknown_overlap_impl_raises(self, params, x):
        with pytest.raises(ValueError, match="overlap impl"):
            moe_block(params, x,
                      dataclasses.replace(BASE, overlap_impl="turbo"))


# --- nonfinite propagation ----------------------------------------------------


class TestNonfinite:
    @pytest.mark.parametrize("impl", ["scan", "pallas"])
    def test_poisoned_tokens_propagate_like_plain_ep(self, params, x, impl):
        """A nan/inf activation row must poison exactly the same output
        rows through the overlapped combine as through the single psum —
        chunking must neither launder a nonfinite value (a masked-out
        where() eating the nan) nor smear it across chunk boundaries."""
        bad = jnp.asarray(x).at[0, 5, :].set(jnp.nan).at[1, 11, :].set(jnp.inf)
        mesh = _ep_mesh()
        set_default_mesh(mesh)
        try:
            plain_y, _, _ = _run(
                params, bad, dataclasses.replace(BASE, overlap_impl="off")
            )
            ov_y, _, _ = _run(
                params, bad, dataclasses.replace(BASE, overlap_impl=impl)
            )
        finally:
            set_default_mesh(None)
        plain_fin = np.isfinite(np.asarray(plain_y))
        ov_fin = np.isfinite(np.asarray(ov_y))
        np.testing.assert_array_equal(ov_fin, plain_fin)
        assert not plain_fin[0, 5].any()  # the poison actually landed
        np.testing.assert_allclose(
            np.asarray(ov_y)[plain_fin], np.asarray(plain_y)[plain_fin],
            atol=2e-5,
        )


# --- trainer composition ------------------------------------------------------


class TestTrainerComposition:
    def test_moe_trains_with_bucketed_dp_grads(self):
        """MoE + the manual-dp bucketed grad reduce compose: inside the
        bucketed region the ep/overlap path declines (manual region), the
        MoE param grads ride `bucketed_psum` as ordinary tree leaves, and
        the trajectory is bitwise-invariant to the bucket count and
        allclose to the GSPMD trainer."""
        from tony_tpu.models.llama import LlamaConfig
        from tony_tpu.train.trainer import (
            default_optimizer, make_train_state, make_train_step,
        )

        cfg = LlamaConfig.tiny_moe(moe_overlap_impl="scan")
        mesh = build_mesh(MeshShape(dp=2, ep=2))
        set_default_mesh(mesh)
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

        try:
            gspmd = run(None)      # partitioner-inserted single all-reduce
            one = run(1 << 30)     # manual region, one big bucket
            many = run(64 << 10)   # manual region, many small buckets
        finally:
            set_default_mesh(None)
        assert one == many         # bucket count never changes the values
        # vs GSPMD the MoE compute itself restructures (the manual-dp
        # region declines the ep shard_map, so expert partials reduce in
        # a different order), not just the grad reduce — wider f32 drift
        # than the dense trainer's 1e-5
        np.testing.assert_allclose(gspmd, one, rtol=1e-4)
        assert all(np.isfinite(v) for v in gspmd)
