"""Communication–compute overlap: decomposed fsdp collectives.

The GSPMD partitioner materialises each fsdp-sharded weight with one
blocking all-gather per matmul and reduces its gradient with one blocking
reduce-scatter — the step-anatomy report (obs/anatomy.py) prices that as
``exposed_collective_s``. This module spends the report: the collective
matmul decomposition (Wang et al., "Overlap communication with dependent
computation", ASPLOS'23 — the same lineage as Megatron-LM's overlap flags,
arXiv:2104.04473) splits the gathered operand into ring chunks and pipelines
``lax.ppermute`` hops against per-chunk matmuls, so the interconnect runs
while the MXU does — nothing waits on a full-tensor gather.

Three per-device primitives (call inside shard_map, manual over the fsdp
axis), each in the repo's two-impl pattern — ``'scan'`` is the pure-XLA
CPU/shard_map-safe default, ``'pallas'`` runs each chunk's matmul as a tiled
TPU kernel (interpret-mode on CPU), the ring hops staying ``lax.ppermute``
between kernel launches exactly like parallel.ring_attention's ring_flash:

- :func:`all_gather_matmul_local` — ``x @ W`` where W is sharded over the
  ring on ``gather_dim`` (0: contraction rows -> accumulate partial
  products; 1: output columns -> write column slices). custom_vjp: dx is the
  mirrored ring against Wᵀ, dW is the matmul-reduce-scatter below, so the
  backward overlaps symmetrically.
- :func:`matmul_reduce_scatter_local` — ``xᵀ @ g`` reduce-scattered over
  the ring: the accumulator rides the ring (one hop per chunk) while each
  device computes the next partial product, landing shard ``i`` on device
  ``i`` with no full [D, N] gradient ever materialised.
- :func:`bucketed_psum` — the dp gradient-reduction side: leaves grouped
  into byte-budgeted buckets, one collective per bucket, so each bucket's
  reduce dispatches as soon as its leaves' backward is done and rides
  behind the remaining backward compute. Grouping is value-exact: a psum
  of a tuple IS the tuple of psums.

:func:`overlap_matmul` is the GSPMD-context entry llama.py calls: it
shard_maps the ring op over the default mesh's fsdp axis and returns None
when the decomposition does not apply (no mesh, axis size 1, indivisible
shapes, already inside a manual region) so the caller falls back to the
plain matmul — overlap is an optimisation, never a requirement.

Bucket sizing is read off the measured anatomy report, not guessed:
:func:`bucket_bytes_from_report` solves ``bytes = achieved_gbps x
per-layer-backward-window`` from the committed fixture numbers.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from tony_tpu.ops.compat import (
    struct_with_vma as _struct_with_vma,
    use_interpret as _use_interpret,
)

_IMPLS = ("scan", "pallas")


def _pick_block(n: int, block_n: int) -> int:
    """Largest divisor of N out of (block_n, halvings of it, N itself)."""
    bn = min(block_n, n)
    while bn > 1 and n % bn:
        bn //= 2
    return bn if n % bn == 0 else n


def _mm_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _chunk_mm(a: jax.Array, b: jax.Array, impl: str,
              block_n: int = 256) -> jax.Array:
    """One ring chunk's ``a [M,K] @ b [K,N] -> f32 [M,N]``."""
    if impl == "pallas":
        M, K = a.shape
        N = b.shape[1]
        bn = _pick_block(N, block_n)
        return pl.pallas_call(
            _mm_kernel,
            grid=(N // bn,),
            in_specs=[
                pl.BlockSpec((M, K), lambda j: (0, 0)),
                pl.BlockSpec((K, bn), lambda j: (0, j)),
            ],
            out_specs=pl.BlockSpec((M, bn), lambda j: (0, j)),
            out_shape=_struct_with_vma((M, N), jnp.float32, a, b),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)
            ),
            interpret=_use_interpret(),
        )(a, b)
    return lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _vma_zero(*xs) -> jax.Array:
    """An f32 scalar 0 derived from the operands so accumulators carry
    their varying-mesh-axes type (the ring_attention idiom)."""
    z = jnp.float32(0.0)
    for x in xs:
        z = z + x.astype(jnp.float32).sum() * 0.0
    return z


def _ring_contract(x2, w_loc, axis_name, impl):
    """``sum_i x2[:, rows_i] @ W_i`` — W gathered on its contraction dim.

    x2 [M, D] full-width activations, w_loc [D/n, N] this device's row
    shard. Chunk i's rows multiply while the NEXT shard is already in
    flight on the ring: the ppermute and the matmul have no data
    dependency, so XLA schedules them concurrently.
    """
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    Dl, N = w_loc.shape
    perm = [(i, (i + 1) % n) for i in range(n)]
    y0 = jnp.zeros((x2.shape[0], N), jnp.float32) + _vma_zero(x2, w_loc)

    def body(j, carry):
        w_cur, y = carry
        idx = (my - j) % n  # which shard this device holds at step j
        xs = lax.dynamic_slice_in_dim(x2, idx * Dl, Dl, axis=1)
        y = y + _chunk_mm(xs, w_cur, impl)
        w_next = lax.ppermute(w_cur, axis_name, perm)
        return w_next, y

    _, y = lax.fori_loop(0, n, body, (w_loc, y0))
    return y


def _ring_concat(x2, w_loc, axis_name, impl):
    """``y[:, cols_i] = x2 @ W_i`` — W gathered on its output dim.

    x2 [M, D], w_loc [D, N/n] this device's column shard; returns the full
    [M, N] with each column block written as its shard arrives.
    """
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    Nl = w_loc.shape[1]
    perm = [(i, (i + 1) % n) for i in range(n)]
    y0 = jnp.zeros((x2.shape[0], Nl * n), jnp.float32) + _vma_zero(x2, w_loc)

    def body(j, carry):
        w_cur, y = carry
        idx = (my - j) % n
        blk = _chunk_mm(x2, w_cur, impl)
        y = lax.dynamic_update_slice_in_dim(y, blk, idx * Nl, axis=1)
        w_next = lax.ppermute(w_cur, axis_name, perm)
        return w_next, y

    _, y = lax.fori_loop(0, n, body, (w_loc, y0))
    return y


def _ring_reduce_scatter(partial_fn, shape, axis_name, *operands):
    """Ring reduce-scatter of ``sum_devices partial_fn(chunk)``.

    ``partial_fn(c)`` is this device's f32 contribution to output chunk
    ``c``; the accumulator rides the ring (chunk schedule ``(my - j - 1)
    mod n``: what arrives at step j was built by upstream devices for the
    same chunk, and a device adds its OWN chunk last, at j = n-1 — so the
    final hop lands shard ``my`` home fully reduced). Each hop's send
    overlaps the next partial product's matmul.
    """
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    acc0 = partial_fn((my - 1) % n) + (
        jnp.zeros(shape, jnp.float32) + _vma_zero(*operands)
    )

    def body(j, acc):
        acc = lax.ppermute(acc, axis_name, perm)
        return acc + partial_fn((my - j - 1) % n)

    return lax.fori_loop(1, n, body, acc0)


def _check_impl(impl: str) -> None:
    if impl not in _IMPLS:
        raise ValueError(f"unknown overlap impl {impl!r} (scan | pallas)")


def _flat2(x: jax.Array) -> jax.Array:
    return x.reshape(-1, x.shape[-1])


# --- all-gather-matmul --------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def all_gather_matmul_local(x, w_loc, axis_name="fsdp", gather_dim=0,
                            impl="scan"):
    """``x [..., D] @ W [D, N] -> [..., N]`` with W ring-sharded on
    ``gather_dim`` over ``axis_name``; call inside shard_map. Exact (f32
    accumulation), never materialises the gathered W.
    """
    _check_impl(impl)
    y = (_ring_contract if gather_dim == 0 else _ring_concat)(
        _flat2(x), w_loc, axis_name, impl
    )
    out_dtype = jnp.promote_types(x.dtype, w_loc.dtype)
    return y.reshape(*x.shape[:-1], y.shape[-1]).astype(out_dtype)


def _agm_fwd(x, w_loc, axis_name, gather_dim, impl):
    return (
        all_gather_matmul_local(x, w_loc, axis_name, gather_dim, impl),
        (x, w_loc),
    )


def _agm_bwd(axis_name, gather_dim, impl, res, dy):
    x, w_loc = res
    x2, g2 = _flat2(x), _flat2(dy)
    wt = w_loc.T  # sharded on the OPPOSITE dim: the bwd ring mirrors the fwd
    if gather_dim == 0:
        # dx[:, rows_i] = dy @ W_iᵀ ; dW_i = sum_dev x[:, rows_i]ᵀ @ dy
        dx2 = _ring_concat(g2, wt, axis_name, impl)
        Dl = w_loc.shape[0]

        def dw_partial(c):
            xs = lax.dynamic_slice_in_dim(x2, c * Dl, Dl, axis=1)
            return _chunk_mm(xs.T, g2, impl)

        dw = _ring_reduce_scatter(dw_partial, w_loc.shape, axis_name, x, dy)
    else:
        # dx = sum_i dy[:, cols_i] @ W_iᵀ ; dW_i = sum_dev xᵀ @ dy[:, cols_i]
        dx2 = _ring_contract(g2, wt, axis_name, impl)
        Nl = w_loc.shape[1]

        def dw_partial(c):
            gs = lax.dynamic_slice_in_dim(g2, c * Nl, Nl, axis=1)
            return _chunk_mm(x2.T, gs, impl)

        dw = _ring_reduce_scatter(dw_partial, w_loc.shape, axis_name, x, dy)
    dx = dx2.reshape(x.shape).astype(x.dtype)
    return dx, dw.astype(w_loc.dtype)


all_gather_matmul_local.defvjp(_agm_fwd, _agm_bwd)


# --- matmul-reduce-scatter ----------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def matmul_reduce_scatter_local(x, g, axis_name="fsdp", scatter_dim=0,
                                impl="scan"):
    """``reduce_scatter(xᵀ @ g)`` over ``axis_name``: x [..., D], g [..., N]
    -> this device's shard of the [D, N] product (rows for scatter_dim=0,
    columns for 1), summed over the axis. The full product never exists.
    """
    _check_impl(impl)
    x2, g2 = _flat2(x), _flat2(g)
    D, N = x2.shape[1], g2.shape[1]
    n = lax.axis_size(axis_name)
    if scatter_dim == 0:
        Dl = D // n

        def partial_fn(c):
            xs = lax.dynamic_slice_in_dim(x2, c * Dl, Dl, axis=1)
            return _chunk_mm(xs.T, g2, impl)

        shape = (Dl, N)
    else:
        Nl = N // n

        def partial_fn(c):
            gs = lax.dynamic_slice_in_dim(g2, c * Nl, Nl, axis=1)
            return _chunk_mm(x2.T, gs, impl)

        shape = (D, Nl)
    out = _ring_reduce_scatter(partial_fn, shape, axis_name, x, g)
    return out.astype(jnp.promote_types(x.dtype, g.dtype))


def _mrs_fwd(x, g, axis_name, scatter_dim, impl):
    return matmul_reduce_scatter_local(x, g, axis_name, scatter_dim, impl), (x, g)


def _mrs_bwd(axis_name, scatter_dim, impl, res, dy):
    # y_c = sum_dev x[:, rows_c]ᵀ g (scatter_dim=0): the transpose all-gathers
    # dy around the SAME ring — dx streams chunk products, dg accumulates.
    x, g = res
    x2, g2 = _flat2(x), _flat2(g)
    dyt = dy.T  # [N, Dl] (0) / [Nl, D] (1): ring operand, gathered on dim 1/0
    if scatter_dim == 0:
        dx2 = _ring_concat(g2, dyt, axis_name, impl)       # [M, D]
        dg2 = _ring_contract(x2, dy, axis_name, impl)      # [M, N]
    else:
        dx2 = _ring_contract(g2, dyt, axis_name, impl)     # [M, D]
        # dg[:, cols_c] = x2 @ dy_c: dy [D, Nl] is already the per-chunk
        # column block — concat mode over the ring
        dg2 = _ring_concat(x2, dy, axis_name, impl)        # [M, N]
    return (
        dx2.reshape(x.shape).astype(x.dtype),
        dg2.reshape(g.shape).astype(g.dtype),
    )


matmul_reduce_scatter_local.defvjp(_mrs_fwd, _mrs_bwd)


# --- GSPMD-context entry ------------------------------------------------------


def overlap_matmul(x: jax.Array, w: jax.Array, *, gather_dim: int,
                   impl: str = "scan", axis_name: str = "fsdp",
                   mesh=None) -> jax.Array | None:
    """Route ``x [..., D] @ w`` through the decomposed ring inside a
    shard_map over ``axis_name``, or return None when the decomposition
    does not apply so the caller runs the plain matmul. Safe under jit /
    lax.scan / jax.checkpoint (the ring_attention precedent).
    """
    _check_impl(impl)
    if mesh is None:
        from tony_tpu.parallel.mesh import get_default_mesh

        mesh = get_default_mesh()
    from tony_tpu.parallel.mesh import inside_manual_region

    if mesh is None or inside_manual_region():
        return None
    n = int(mesh.shape.get(axis_name, 1))
    if n <= 1:
        return None
    # the ring needs clean shard boundaries: batch rows per device and
    # weight chunks along the gathered dim
    if x.shape[0] % n or w.shape[gather_dim] % n:
        return None

    def f(xl, wl):
        return all_gather_matmul_local(xl, wl, axis_name, gather_dim, impl)

    x_spec = P(axis_name, *([None] * (x.ndim - 1)))
    w_spec = P(axis_name, None) if gather_dim == 0 else P(None, axis_name)
    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(x_spec, w_spec),
        out_specs=x_spec,
        axis_names={axis_name},
    )(x, w)


# --- bucketed gradient reduction ----------------------------------------------


def bucket_plan(nbytes: list[int], bucket_bytes: int) -> list[list[int]]:
    """Group leaf indices (in order) into buckets of ~bucket_bytes each.

    Order-preserving greedy fill: grads materialise roughly in tree order
    during the backward, so contiguous buckets are the ones whose reduce
    can dispatch as soon as their last member's layer finishes. A leaf
    larger than the budget gets its own bucket (never split — splitting
    would change the collective's shape and recompile per plan).
    """
    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes must be positive, got {bucket_bytes}")
    plan: list[list[int]] = []
    cur: list[int] = []
    cur_bytes = 0
    for i, b in enumerate(nbytes):
        if cur and cur_bytes + b > bucket_bytes:
            plan.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += b
    if cur:
        plan.append(cur)
    return plan


def bucketed_psum(tree: Any, axis_name: str, *, bucket_bytes: int) -> Any:
    """All-reduce a grad pytree over ``axis_name`` in byte-budgeted buckets.

    One ``lax.psum`` per bucket (a tuple psum — XLA fuses it into a single
    collective over the bucket's leaves, lowered on TPU as the
    reduce-scatter + all-gather pair), issued in leaf order: the scheduler
    is free to launch bucket k's collective while the backward for bucket
    k+1's layers is still computing. Value-exact vs one whole-tree psum —
    grouping never changes the elementwise sums.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    sizes = [x.size * x.dtype.itemsize for x in leaves]
    out: list[Any] = [None] * len(leaves)
    for idx in bucket_plan(sizes, bucket_bytes):
        red = lax.psum(tuple(leaves[i] for i in idx), axis_name)
        for i, r in zip(idx, red):
            out[i] = r
    return jax.tree_util.tree_unflatten(treedef, out)


def bucket_bytes_from_report(step_anatomy: dict[str, Any] | None, *,
                             n_layers: int,
                             default_bytes: int = 8 << 20) -> int:
    """Solve the bucket size from a measured step-anatomy section
    (bench_report extra.step_anatomy — the committed fixture shape).

    The sizing rule: a bucket's reduce hides iff it finishes within one
    layer's backward window, so ``bytes = achieved_gbps x window`` with
    ``window = backward share (2/3) x compute_ms / n_layers``. Uses the
    top collective's measured bandwidth (the dominant grad reduce); falls
    back to ``default_bytes`` when the report has no measured bandwidth
    (e.g. a capture without a device trace). Clamped to [1 MiB, 128 MiB].
    """
    if not step_anatomy or n_layers <= 0:
        return default_bytes
    top = step_anatomy.get("top_collective") or {}
    gbps = float(top.get("achieved_gbps") or 0.0)
    compute_ms = float(step_anatomy.get("compute_ms") or 0.0)
    if gbps <= 0.0 or compute_ms <= 0.0:
        return default_bytes
    window_s = (2.0 / 3.0) * (compute_ms / 1e3) / n_layers
    raw = int(gbps * 1e9 * window_s)
    return max(1 << 20, min(raw, 128 << 20))


__all__ = [
    "all_gather_matmul_local",
    "bucket_bytes_from_report",
    "bucket_plan",
    "bucketed_psum",
    "matmul_reduce_scatter_local",
    "overlap_matmul",
]
