"""Grouped (ragged) matmul: one GEMM over per-expert row groups.

The MoE dispatch kernel (MegaBlocks, arXiv:2211.15841): tokens are sorted by
assigned expert into contiguous row groups, each group padded up to a
multiple of a row tile, and the expert FFN runs as ONE matmul stream over
row tiles where tile ``i`` contracts against expert ``tile_expert[i]``'s
weight matrix. No fixed per-expert capacity — groups are as long as the
router made them — so nothing is dropped and nothing idles, at the cost of
at most ``block - 1`` padding rows per expert.

Two interchangeable implementations behind :func:`grouped_matmul` (the
``ops/fused_ce.py`` pattern):

- ``'scan'`` — a pure-XLA ``lax.scan`` over row tiles: ``dynamic_slice`` the
  tile out of the sorted buffer, ``jnp.take`` its expert's weights, one dot.
  Runs anywhere (CPU, under ``shard_map``, on an ep mesh) and autodiff
  handles the backward; the default.
- ``'pallas'`` — a TPU kernel over a (row-tiles × out-columns) grid. The
  tile→expert map rides as a scalar-prefetch argument
  (``PrefetchScalarGridSpec``) so the weight BlockSpec can DMA the right
  expert's block before the tile runs; fp32 accumulation on the MXU; the
  backward is a ``custom_vjp`` with dedicated dx and dW kernels (dx
  contracts W's last dim in place — no transposed weight copy; dW carries a
  VMEM accumulator across the consecutive tiles of each expert).
  Interpreter mode on CPU.

The caller owns the layout: build it with :func:`grouped_layout` (per-group
start/size → block-aligned starts + the tile→expert map), scatter rows to
``aligned_start[g] + rank_within_group``, and call ``grouped_matmul`` once
per weight. ``parallel/moe.py`` is the production caller.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tony_tpu.ops.compat import (
    struct_with_vma as _struct,
    use_interpret as _use_interpret,
)


def grouped_layout(group_sizes: jax.Array, block: int, n_tiles: int):
    """Block-aligned ragged layout for ``G`` row groups.

    ``group_sizes``: [G] int32. Returns ``(aligned_starts [G], tile_group
    [n_tiles])`` where group ``g``'s rows occupy ``aligned_starts[g] ..
    aligned_starts[g] + group_sizes[g])`` in a buffer of ``n_tiles * block``
    rows, every start is a multiple of ``block``, and ``tile_group[i]`` is
    the group row-tile ``i`` belongs to. Every group gets at least one tile
    (so a zero-load expert still produces a defined — zero — dW block), and
    trailing tiles beyond the last group clamp to ``G - 1`` (their rows are
    zero padding). ``n_tiles`` must be a static bound of at least
    ``cdiv(sum(sizes), block) + G``.
    """
    g = group_sizes.shape[0]
    tiles_per = jnp.maximum((group_sizes + block - 1) // block, 1)
    tile_cum = jnp.cumsum(tiles_per)
    aligned_starts = (tile_cum - tiles_per) * block
    tile_group = jnp.clip(
        jnp.searchsorted(tile_cum, jnp.arange(n_tiles), side="right"), 0, g - 1
    ).astype(jnp.int32)
    return aligned_starts, tile_group


def _pick_block(n: int, pref: int) -> int:
    """Largest of (pref, 512, 256, 128) dividing n, else n itself (ragged
    column tiles would read past the weight edge; full-width is always safe
    and only bites on shapes too small to tile anyway)."""
    for d in (pref, 512, 256, 128):
        if 0 < d <= n and n % d == 0:
            return d
    return n


# --- scan (XLA) implementation ------------------------------------------------


def _gmm_scan(x: jax.Array, w: jax.Array, tile_group: jax.Array,
              n_valid_tiles: jax.Array | None = None) -> jax.Array:
    """lax.scan over row tiles: slice tile i, take its group's weights, dot.
    Autodiff transposes the slice/take into the scatter-adds of the
    backward — no custom VJP needed. With ``n_valid_tiles`` (a traced
    count) tiles at or beyond it are skipped — zeros out, weights unread:
    the static tile bound of a dropless layout is mostly such tiles when
    few of the routes are local (parallel/moe.py ``_local_grouped``)."""
    n_tiles = tile_group.shape[0]
    br = x.shape[0] // n_tiles

    def tile(i):
        xt = lax.dynamic_slice_in_dim(x, i * br, br)
        wg = jnp.take(w, tile_group[i], axis=0)
        yt = jnp.dot(xt, wg, preferred_element_type=jnp.float32)
        return yt.astype(x.dtype)

    def body(_, i):
        if n_valid_tiles is None:
            return None, tile(i)
        return None, lax.cond(
            i < n_valid_tiles, tile,
            lambda i: jnp.zeros((br, w.shape[-1]), x.dtype), i)

    _, ys = lax.scan(body, None, jnp.arange(n_tiles, dtype=jnp.int32))
    return ys.reshape(x.shape[0], w.shape[-1])


# --- pallas (TPU) implementation ----------------------------------------------


def _gmm_kernel(tg_ref, x_ref, w_ref, o_ref):
    o_ref[...] = lax.dot_general(
        x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def _gmm_pallas_call(x, w, tile_group, block_cols):
    n_tiles = tile_group.shape[0]
    br = x.shape[0] // n_tiles
    d, n = w.shape[1], w.shape[2]
    bc = _pick_block(n, block_cols)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles, n // bc),
        in_specs=[
            pl.BlockSpec((br, d), lambda i, j, tg: (i, 0)),
            # the prefetched tile->group map picks which expert's weight
            # block the DMA brings in for tile i
            pl.BlockSpec((1, d, bc), lambda i, j, tg: (tg[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j, tg: (i, j)),
    )
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=_struct((x.shape[0], n), x.dtype, x, w),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=_use_interpret(),
    )(tile_group, x, w)


def _gmm_dx_kernel(tg_ref, dy_ref, w_ref, dx_ref, acc):
    """dx_tile = dy_tile @ w[g]^T, contracting w's LAST dim in place — no
    HBM-materialised [G, F, D] transpose of the expert weights (whose
    streaming is the measured MoE bottleneck). The out-column (model-dim)
    blocks accumulate over the F grid dim."""
    fi = pl.program_id(2)
    nf = pl.num_programs(2)

    @pl.when(fi == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    acc[:] = acc[:] + lax.dot_general(
        dy_ref[...], w_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(fi == nf - 1)
    def _finalize():
        dx_ref[...] = acc[:].astype(dx_ref.dtype)


def _gmm_dx_call(dy, w, tile_group, block_cols):
    n_tiles = tile_group.shape[0]
    br = dy.shape[0] // n_tiles
    d, f = w.shape[1], w.shape[2]
    bd, bf = _pick_block(d, block_cols), _pick_block(f, block_cols)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles, d // bd, f // bf),
        in_specs=[
            pl.BlockSpec((br, bf), lambda i, di, fi, tg: (i, fi)),
            pl.BlockSpec((1, bd, bf), lambda i, di, fi, tg: (tg[i], di, fi)),
        ],
        out_specs=pl.BlockSpec((br, bd), lambda i, di, fi, tg: (i, di)),
        scratch_shapes=[pltpu.VMEM((br, bd), jnp.float32)],
    )
    return pl.pallas_call(
        _gmm_dx_kernel,
        grid_spec=grid_spec,
        out_shape=_struct((dy.shape[0], d), dy.dtype, dy, w),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "parallel", "arbitrary")
        ),
        interpret=_use_interpret(),
    )(tile_group, dy, w)


def _gmm_dw_kernel(tg_ref, x_ref, dy_ref, dw_ref, acc):
    """dW[g] = sum over g's row tiles of x_tile^T @ dy_tile.

    The tile dimension is innermost and tiles of one group are consecutive
    (the buffer is sorted), so the dW output block is revisited on
    consecutive grid steps: init the VMEM accumulator on the group's first
    tile, write the block back on its last."""
    i = pl.program_id(2)
    n = pl.num_programs(2)
    g = tg_ref[i]

    @pl.when((i == 0) | (tg_ref[jnp.maximum(i - 1, 0)] != g))
    def _init():
        acc[:] = jnp.zeros_like(acc)

    acc[:] = acc[:] + lax.dot_general(
        x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when((i == n - 1) | (tg_ref[jnp.minimum(i + 1, n - 1)] != g))
    def _finalize():
        dw_ref[0] = acc[:].astype(dw_ref.dtype)


def _gmm_dw_call(x, dy, tile_group, n_groups, block_cols):
    n_tiles = tile_group.shape[0]
    br = x.shape[0] // n_tiles
    d, n = x.shape[1], dy.shape[1]
    bd, bn = _pick_block(d, block_cols), _pick_block(n, block_cols)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(d // bd, n // bn, n_tiles),
        in_specs=[
            pl.BlockSpec((br, bd), lambda di, ni, i, tg: (i, di)),
            pl.BlockSpec((br, bn), lambda di, ni, i, tg: (i, ni)),
        ],
        out_specs=pl.BlockSpec((1, bd, bn), lambda di, ni, i, tg: (tg[i], di, ni)),
        scratch_shapes=[pltpu.VMEM((bd, bn), jnp.float32)],
    )
    return pl.pallas_call(
        _gmm_dw_kernel,
        grid_spec=grid_spec,
        out_shape=_struct((n_groups, d, n), jnp.float32, x, dy),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=_use_interpret(),
    )(tile_group, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_pallas(x, w, tile_group, block_cols):
    return _gmm_pallas_call(x, w, tile_group, block_cols)


def _gmm_pallas_fwd(x, w, tile_group, block_cols):
    return _gmm_pallas_call(x, w, tile_group, block_cols), (x, w, tile_group)


def _gmm_pallas_bwd(block_cols, res, dy):
    x, w, tile_group = res
    dx = _gmm_dx_call(dy, w, tile_group, block_cols)
    dw = _gmm_dw_call(x, dy, tile_group, w.shape[0], block_cols).astype(w.dtype)
    return dx, dw, np.zeros(tile_group.shape, jax.dtypes.float0)


_gmm_pallas.defvjp(_gmm_pallas_fwd, _gmm_pallas_bwd)


# --- public entry -------------------------------------------------------------


def grouped_matmul(
    x: jax.Array,
    w: jax.Array,
    tile_group: jax.Array,
    *,
    impl: str = "scan",
    block_cols: int = 512,
    n_valid_tiles: jax.Array | None = None,
) -> jax.Array:
    """``[N, D] x [G, D, F] -> [N, F]`` where row tile ``i`` (of
    ``N / len(tile_group)`` rows) contracts against ``w[tile_group[i]]``.
    ``n_valid_tiles`` (scan impl; the Pallas kernel computes every tile):
    tiles from that index on hold padding only and come back zero.

    ``x`` must be laid out by :func:`grouped_layout` (group-contiguous,
    block-aligned, zero padding rows). Differentiable under both impls.
    """
    if x.ndim != 2 or w.ndim != 3 or w.shape[1] != x.shape[1]:
        raise ValueError(f"grouped_matmul shapes {x.shape} x {w.shape}")
    n_tiles = tile_group.shape[0]
    if n_tiles == 0 or x.shape[0] % n_tiles:
        raise ValueError(
            f"rows {x.shape[0]} not a whole number of {n_tiles} tiles"
        )
    if impl == "pallas":
        return _gmm_pallas(x, w, tile_group, block_cols)
    if impl != "scan":
        raise ValueError(f"unknown gmm impl {impl!r} (expected scan | pallas)")
    return _gmm_scan(x, w, tile_group, n_valid_tiles)


__all__ = ["grouped_layout", "grouped_matmul"]
