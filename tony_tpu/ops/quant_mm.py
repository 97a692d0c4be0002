"""Int8 weight-only matmul with per-output-channel scales.

The serving decode step is memory-bandwidth bound: every step streams the
full weight matrices through the chip to produce ONE row per slot. Weight-
only quantization (the AWQ lineage, arXiv:2306.00978 — store int8, compute
in the activation dtype) halves that stream without touching activations:
``W [D, N]`` is stored as int8 with one float32 scale per OUTPUT channel
(``amax over D / 127``), and the matmul dequantizes tiles of W on the fly.
Per-output-channel granularity keeps the scale a [N] vector the matmul can
fold in after the contraction — no per-group bookkeeping inside the MXU
inner loop — while bounding each channel's quantization error by its own
dynamic range.

Two interchangeable implementations (the ``fused_ce``/``grouped_mm``/
``decode_attention`` pattern), dispatched on ``impl``:

- ``'scan'`` — ``lax.scan`` over column tiles: dequantize one ``[D, bn]``
  tile, matmul, emit. Pure XLA, runs anywhere, bounds the dequantized
  transient to one tile instead of the whole matrix.
- ``'pallas'`` — a TPU kernel over an ``(N / bn,)`` grid that fuses
  dequantize + matmul per tile, so the bf16 copy of W never exists outside
  VMEM. Interpreter mode on CPU.

Inference-only (no backward): the engine quantizes its decode weights once
at construction (serve/engine.py, ``serve.quant.weights``); prefill keeps
the bf16 master weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tony_tpu.ops.compat import use_interpret as _use_interpret

WEIGHT_QMAX = 127.0


def quantize_weights(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``w [..., D, N]`` -> (int8 ``[..., D, N]``, float32 scales
    ``[..., N]``): symmetric per-output-channel quantization (amax over
    the contraction dim / 127). Leading dims (the engine's stacked-layer
    ``[L, D, N]`` weights) quantize independently per layer."""
    wf = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(wf), axis=-2) / WEIGHT_QMAX          # [..., N]
    q = wf / jnp.maximum(scale[..., None, :], 1e-30)
    return (
        jnp.clip(jnp.round(q), -WEIGHT_QMAX, WEIGHT_QMAX).astype(jnp.int8),
        scale,
    )


def _pick_block(n: int, block_n: int) -> int:
    """Largest divisor of N out of (block_n, halvings of it, N itself)."""
    bn = min(block_n, n)
    while bn > 1 and n % bn:
        bn //= 2
    return bn if n % bn == 0 else n


def _scan_impl(x2, wq, scale, bn):
    D, N = wq.shape
    nb = N // bn

    def body(_, j):
        wb = lax.dynamic_slice_in_dim(wq, j * bn, bn, axis=1)
        sb = lax.dynamic_slice_in_dim(scale, j * bn, bn)
        wd = (wb.astype(jnp.float32) * sb[None, :]).astype(x2.dtype)
        y = lax.dot_general(
            x2, wd, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return None, y.astype(x2.dtype)                 # [Bx, bn]

    _, ys = lax.scan(body, None, jnp.arange(nb, dtype=jnp.int32))
    return jnp.moveaxis(ys, 0, 1).reshape(x2.shape[0], N)


def _qmm_kernel(x_ref, wq_ref, s_ref, o_ref):
    w = (wq_ref[...].astype(jnp.float32) * s_ref[0, :][None, :]).astype(
        x_ref.dtype
    )
    o_ref[...] = lax.dot_general(
        x_ref[...], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def _pallas_impl(x2, wq, scale, bn):
    Bx, D = x2.shape
    N = wq.shape[1]
    out = pl.pallas_call(
        _qmm_kernel,
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((Bx, D), lambda j: (0, 0)),
            pl.BlockSpec((D, bn), lambda j: (0, j)),
            pl.BlockSpec((1, bn), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((Bx, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((Bx, N), x2.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=_use_interpret(),
    )(x2, wq, scale[None, :].astype(jnp.float32))
    return out


def quant_matmul(
    x: jax.Array, wq: jax.Array, scale: jax.Array, *,
    impl: str = "scan", block_n: int = 256,
) -> jax.Array:
    """``x [..., D] @ dequant(wq [D, N], scale [N]) -> [..., N]``.

    The contraction runs in ``x.dtype`` with float32 accumulation —
    numerically what the bf16 matmul does, on weights whose per-channel
    error is bounded by ``scale / 2`` (half an int8 step). ``block_n``
    tiles the output channels (rounded down to a divisor of N)."""
    if impl not in ("scan", "pallas"):
        raise ValueError(f"unknown quant_mm impl {impl!r} (scan | pallas)")
    if wq.ndim != 2 or scale.shape != wq.shape[-1:]:
        raise ValueError(
            f"quant_matmul weight shapes wq={wq.shape} scale={scale.shape}"
        )
    D, N = wq.shape
    lead = x.shape[:-1]
    if x.shape[-1] != D:
        raise ValueError(f"quant_matmul x={x.shape} vs wq={wq.shape}")
    x2 = x.reshape(-1, D)
    bn = _pick_block(N, block_n)
    if impl == "pallas":
        out = _pallas_impl(x2, wq, scale, bn)
    else:
        out = _scan_impl(x2, wq, scale, bn)
    return out.reshape(*lead, N)


__all__ = ["WEIGHT_QMAX", "quant_matmul", "quantize_weights"]
