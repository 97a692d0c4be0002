"""Overlapped expert-parallel MoE combine (docs/PERF.md round 20).

The plain ep grouped path (`tony_tpu.parallel.moe._moe_grouped_ep`) runs the
whole local expert FFN and then issues ONE blocking full-width
``psum(y, "ep")`` — every byte of combine traffic waits for the last FLOP of
expert compute, the Megatron-style serialization `ops.overlap` already
removed from the dense fsdp/dp collectives (arXiv:2104.04473). This module
decomposes that combine on the TOKEN dim: the per-shard token rows are split
into ``n_chunks`` static slices and each chunk runs (local grouped FFN over
the chunk's routes) -> (chunk-width psum of the per-expert-group partials).
The loop is python-unrolled, so XLA's latency-hiding scheduler starts chunk
``c``'s psum while chunk ``c+1``'s FFN is still on the MXU — later chunks'
compute hides earlier chunks' combine traffic.

Token-chunking (not expert-group-chunking) is the deliberate schedule:
chunking the combine by expert group would psum each group's full ``[T, D]``
partial separately — ``n_chunks``x the combine bytes — while token slices
keep total traffic exactly equal to the single psum (disjoint row blocks)
and keep every shape static. Each chunk's psum still combines that chunk's
per-expert-group partials across the ep shards.

``overlapped_combine`` is a ``custom_vjp`` so the backward keeps the
decomposition: per chunk, never one full-width collective. Under
``shard_map``'s varying-mesh-axes typing (``check_vma``, the default) a
custom_vjp must hand back each cotangent typed like its primal. The
forward psum makes a chunk's output ep-invariant, so its cotangent slice
arrives whole on every shard and the psum's transpose is a ``pcast`` back
to ep-varying — no collective. The chunk FFN's own cotangents vary over
every axis its inputs meet, so each is summed onto its primal's type
(`_typed_like`): the token/weight rows over ``ep`` (one psum per chunk,
overlapping the next chunk's re-linearised FFN), the ep-sharded expert
weights over the data axes (their own shard's grad, accumulated over
chunks first). This is what AD does on the plain path, decomposed. The int
route tensor takes ``float0`` zeros (the `ops.grouped_mm` idiom).

The two impls follow the repo pattern: ``'scan'`` drives the chunk FFN's
grouped matmuls through the pure-XLA lax.scan kernel (CPU/shard_map-safe
reference), ``'pallas'`` through the TPU Pallas kernel (interpret mode on
CPU). The schedule itself is identical — only the per-chunk GEMM kernel
changes.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

_IMPLS = ("scan", "pallas")


def _check_impl(impl: str) -> None:
    if impl not in _IMPLS:
        raise ValueError(
            f"unknown MoE overlap impl {impl!r}; expected one of {_IMPLS} "
            "(or 'off')"
        )


def overlap_chunks(t_local: int, chunk_tokens: int) -> int | None:
    """Resolve the chunk count for ``t_local`` per-shard token rows, or
    ``None`` when the decomposition does not apply (the caller keeps the
    single blocking psum — overlap is an optimisation, never a
    requirement).

    ``chunk_tokens > 0`` pins the chunk size (from the measured sizing
    rule, `chunk_tokens_from_report`); it must divide ``t_local`` and
    leave >= 2 chunks, else decline — a ragged tail chunk would change
    the collective's shape per chunk and recompile per schedule.
    ``chunk_tokens == 0`` auto-picks the largest clean split in {4, 3, 2}.
    """
    if t_local <= 1:
        return None
    if chunk_tokens > 0:
        if chunk_tokens >= t_local or t_local % chunk_tokens != 0:
            return None
        return t_local // chunk_tokens
    for n in (4, 3, 2):
        if t_local % n == 0:
            return n
    return None


def chunk_tokens_from_report(step_anatomy: dict[str, Any] | None, *,
                             dim: int, dtype_bytes: int = 2,
                             default_tokens: int = 2048) -> int:
    """Solve the overlap chunk size from a measured step-anatomy section
    (the OFF capture of the MoE bench — the `bucket_bytes_from_report`
    rule transposed to tokens).

    A chunk's psum hides iff it finishes within one chunk's FFN window,
    so ``tokens x dim x dtype_bytes = achieved_gbps x window`` with
    ``window = compute_ms / 2`` as the conservative per-chunk compute
    share (the FFN dominates an MoE step; half the step is the floor any
    >= 2-way split guarantees). Uses the top collective's measured
    bandwidth (the ep combine is the dominant MoE collective); falls back
    to ``default_tokens`` when the capture has no measured bandwidth.
    Clamped to [256, 8192] and rounded down to a multiple of 256 so the
    chunk rows stay sublane-tile aligned through the grouped GEMM.
    """
    if not step_anatomy or dim <= 0:
        return default_tokens
    top = step_anatomy.get("top_collective") or {}
    gbps = float(top.get("achieved_gbps") or 0.0)
    compute_ms = float(step_anatomy.get("compute_ms") or 0.0)
    if gbps <= 0.0 or compute_ms <= 0.0:
        return default_tokens
    window_s = 0.5 * (compute_ms / 1e3)
    raw = int(gbps * 1e9 * window_s / (dim * dtype_bytes))
    clamped = max(256, min(raw, 8192))
    return (clamped // 256) * 256


# --- the decomposed combine ---------------------------------------------------


def _chunk_slices(t: int, n_chunks: int) -> list[slice]:
    ct = t // n_chunks
    return [slice(c * ct, (c + 1) * ct) for c in range(n_chunks)]


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def overlapped_combine(ffn_fn: Callable[..., jax.Array], axis_name: str,
                       n_chunks: int, w1: jax.Array, w3: jax.Array,
                       w2: jax.Array, flat: jax.Array, sel: jax.Array,
                       weight: jax.Array) -> jax.Array:
    """Chunked-psum ep combine: ``concat_c(psum(ffn_fn(chunk_c), axis))``.

    Call INSIDE the ep shard_map. ``ffn_fn(w1, w3, w2, flat_c, sel_c,
    weight_c) -> [ct, D]`` is the shard-local chunk FFN (ownership masking
    included — `parallel.moe._chunk_ffn`); it must be a hashable static
    callable. ``flat [t, D]`` / ``sel [t, k]`` / ``weight [t, k]`` are the
    shard-local token rows and routes. Numerically this IS the single
    ``psum(ffn(flat))``: the chunks are disjoint row slices, so the
    per-chunk psums are elementwise identical to one full-width psum.
    """
    outs = []
    for s in _chunk_slices(flat.shape[0], n_chunks):
        y_c = ffn_fn(w1, w3, w2, flat[s], sel[s], weight[s])
        outs.append(jax.lax.psum(y_c, axis_name))
    return jnp.concatenate(outs, axis=0)


def _chunk_primal(ffn_fn, sel_c, w1, w3, w2, flat_c, weight_c):
    """Diff-arg-only view of one chunk's local FFN (sel is int, closed
    over) — what the backward re-linearises per chunk."""
    return ffn_fn(w1, w3, w2, flat_c, sel_c, weight_c)


def _overlapped_combine_fwd(ffn_fn, axis_name, n_chunks, w1, w3, w2, flat,
                            sel, weight):
    y = overlapped_combine(ffn_fn, axis_name, n_chunks, w1, w3, w2, flat,
                           sel, weight)
    return y, (w1, w3, w2, flat, sel, weight)


def _typed_like(ct: jax.Array, primal: jax.Array) -> jax.Array:
    """Give a cotangent its primal's varying-mesh-axes type, as shard_map's
    vma typing requires of a custom_vjp's outputs: axes the cotangent
    varies over and the primal does not are summed out (the transpose of
    the implicit invariant->varying cast the forward made at the primal's
    first use — AD does exactly this on the plain path)."""
    extra = jax.typeof(ct).vma - jax.typeof(primal).vma
    return jax.lax.psum(ct, tuple(sorted(extra))) if extra else ct


def _overlapped_combine_bwd(ffn_fn, axis_name, n_chunks, res, g):
    w1, w3, w2, flat, sel, weight = res
    dw1 = dw3 = dw2 = None
    dflat, dweight = [], []
    for s in _chunk_slices(flat.shape[0], n_chunks):
        # the forward psum made each chunk's output ep-invariant, so its
        # cotangent slice arrives whole on every shard: the psum's
        # transpose is the cast back to ep-varying, no collective
        g_c = jax.lax.pcast(g[s], (axis_name,), to="varying")
        chunk = partial(_chunk_primal, ffn_fn, sel[s])
        _, vjp_fn = jax.vjp(chunk, w1, w3, w2, flat[s], weight[s])
        dw1_c, dw3_c, dw2_c, dfl_c, dwg_c = vjp_fn(g_c)
        # the ep-sharded expert weights keep their own shard's grad,
        # accumulated over chunks; the ep-invariant token/weight rows get
        # every shard's contribution — one per-chunk psum over ep, which
        # overlaps the next chunk's re-linearised FFN like the forward's
        dw1 = dw1_c if dw1 is None else dw1 + dw1_c
        dw3 = dw3_c if dw3 is None else dw3 + dw3_c
        dw2 = dw2_c if dw2 is None else dw2 + dw2_c
        dflat.append(_typed_like(dfl_c, flat))
        dweight.append(_typed_like(dwg_c, weight))
    dsel = np.zeros(sel.shape, jax.dtypes.float0)
    return (_typed_like(dw1, w1), _typed_like(dw3, w3), _typed_like(dw2, w2),
            jnp.concatenate(dflat, axis=0), dsel,
            jnp.concatenate(dweight, axis=0))


overlapped_combine.defvjp(_overlapped_combine_fwd, _overlapped_combine_bwd)


__all__ = [
    "chunk_tokens_from_report",
    "overlap_chunks",
    "overlapped_combine",
]
