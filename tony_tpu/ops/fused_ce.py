"""Fused chunked cross-entropy head: never materialise [B, S, V] logits.

The loss head is the single biggest HBM transient of the dense train step:
``h @ lm_head`` builds fp32 logits of [B, S, V] (~1 GB at bench shapes) and
autodiff materialises a same-sized ``dlogits`` in the backward. This module
applies the recompute-instead-of-materialise trick the attention kernel
already uses (FlashAttention-2, arXiv:2307.08691) to the vocab projection,
the fused/parallel CE head that Megatron-LM (arXiv:2104.04473) makes
standard at scale:

- **forward** walks the vocab in chunks of ``Vc`` columns keeping an online
  logsumexp ``(m, s)`` plus the target logit per row — at most one
  ``[N, Vc]`` logits block is ever live;
- **backward** recomputes each chunk's logits from the saved
  ``(h, lse)`` residuals (``softmax = exp(logits - lse)``), forms the chunk's
  ``dlogits = (softmax - onehot) * g`` in registers/VMEM, and accumulates
  ``dh`` and the chunk's ``d(lm_head)`` columns directly — no full
  ``dlogits`` ever exists.

Two interchangeable implementations behind one ``jax.custom_vjp`` (the
``ops/attention.py`` pattern), selected by ``LlamaConfig.ce_impl``:

- ``'pallas'`` — TPU kernels with VMEM accumulators over a (rows, vocab)
  grid; interpreter mode on CPU for tests.
- ``'scan'`` — a pure-XLA ``lax.scan`` over vocab chunks; runs anywhere
  (CPU, under ``shard_map``, inside the 1F1B pipeline's manual region) and
  is the default train path.

Both return **per-token** losses ``[B, S]`` fp32 (callers take the mean),
so a ``dp``/``fsdp``/``sp``-sharded batch/seq axis stays sharded end to end
and the MoE aux term composes unchanged at the call site.

Sharding note: both paths read the full ``lm_head`` per data shard (the
scan's dynamic vocab slice and the pallas wrapper's replicated W both defeat
the column-parallel vocab layout). A Megatron-style vocab-parallel CE (local
max/sum + two small psums) is the follow-up for large-tp meshes; at the
single-chip/fsdp bench shapes W traffic is one streaming read per pass.
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

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)

# pallas tile defaults (clipped to the actual shapes)
_BLOCK_N = 512
_BLOCK_V = 512

# Mosaic's default scoped-VMEM budget is 16 MiB, which the 512x512 tiles
# overrun from D=2048 up (the v5e compiler: 27.4 MiB for the backward at
# D=2048, 16.8 MiB for the forward at D=4096). The chip has 128 MiB of
# VMEM; each call asks for what its tiles need, capped below that.
_VMEM_CAP = 100 * 2**20


def _vmem_limit(blk_n: int, blk_v: int, D: int, itemsize: int) -> int:
    """Scoped-VMEM bytes for the largest of the three kernels (dh/dW) at
    these tiles. With h = blk_n*D, w = D*blk_v elements:

    - pipelined blocks, double-buffered: 2 * (h + w + max(h, w)) * itemsize
      (inputs h and W, output dh or dW)
    - fp32 accumulator scratch: max(h, w) * 4
    - fp32 temporaries in the body (h and W upcast, the matmul result
      before it is added): (h + w + max(h, w)) * 4
    - the [blk_n, blk_v] fp32 logits / p / dlogits / mask tiles: 4 * n*v * 4

    D=2048 -> 32 MiB, D=4096 -> 64 MiB at the default tiles. Past the cap
    (D=8192 at these tiles) the compiler refuses with its own VMEM error:
    pass smaller ``ce_block_n``/``ce_block_v``."""
    h, w = blk_n * D, D * blk_v
    out = max(h, w)
    need = (
        2 * (h + w + out) * itemsize + out * 4 + (h + w + out) * 4
        + 4 * blk_n * blk_v * 4
    )
    return min(need, _VMEM_CAP)


# --- scan (XLA) implementation ------------------------------------------------


def _scan_chunk_fwd(carry, logits, start, tgt):
    """Online-logsumexp update for one [N, Vc] fp32 logits block."""
    m, s, tl = carry
    m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
    s = s * jnp.exp(m - m_new) + jnp.sum(jnp.exp(logits - m_new[:, None]), axis=-1)
    rel = tgt - start
    in_chunk = (rel >= 0) & (rel < logits.shape[1])
    idx = jnp.clip(rel, 0, logits.shape[1] - 1)
    got = jnp.take_along_axis(logits, idx[:, None], axis=1)[:, 0]
    tl = jnp.where(in_chunk, got, tl)
    return m_new, s, tl


def _scan_fwd(h, w, tgt, vc):
    """h [N, D], w [D, V], tgt [N] -> (lse [N] f32, target_logit [N] f32)."""
    N, D = h.shape
    V = w.shape[1]
    vc = min(vc, V)
    nfull = V // vc

    # derive the carries from h (not fresh zeros) so they inherit h's
    # varying-mesh-axes type: inside a shard_map manual region (the 1F1B
    # head) a fresh-constant carry would fail the scan's vma typing once
    # the body makes it varying
    zrow = jnp.sum(h * 0, axis=1).astype(jnp.float32)  # [N] f32 zeros
    init = (zrow + _NEG, zrow, zrow)

    def body(carry, j):
        start = j * vc
        wc = lax.dynamic_slice(w, (0, start), (D, vc))
        logits = jnp.dot(h, wc, preferred_element_type=jnp.float32)
        return _scan_chunk_fwd(carry, logits, start, tgt), None

    carry, _ = lax.scan(body, init, jnp.arange(nfull))
    if V % vc:
        tail = jnp.dot(h, w[:, nfull * vc:], preferred_element_type=jnp.float32)
        carry = _scan_chunk_fwd(carry, tail, nfull * vc, tgt)
    m, s, tl = carry
    return m + jnp.log(s), tl


def _scan_chunk_bwd(h, wc, tgt, lse, g, start):
    """One chunk of the backward: recompute logits, return (dh_part f32,
    dwc in w.dtype). dlogits = (softmax - onehot(target)) * g, formed only
    at [N, Vc]."""
    vcc = wc.shape[1]
    logits = jnp.dot(h, wc, preferred_element_type=jnp.float32)
    p = jnp.exp(logits - lse[:, None])
    rel = tgt - start
    onehot = (jnp.arange(vcc)[None, :] == rel[:, None]).astype(jnp.float32)
    dlogits = (p - onehot) * g[:, None]
    dh_part = lax.dot_general(
        dlogits, wc, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    dwc = lax.dot_general(
        h, dlogits, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    return dh_part, dwc.astype(wc.dtype)


def _scan_bwd(h, w, tgt, lse, g, vc):
    """Backward accumulation over vocab chunks; returns (dh, dw) in the
    primal dtypes. Each chunk's dW columns are written exactly once (no
    cross-chunk accumulation), dh accumulates fp32."""
    N, D = h.shape
    V = w.shape[1]
    vc = min(vc, V)
    nfull = V // vc

    def body(carry, j):
        dh_acc, dw = carry
        start = j * vc
        wc = lax.dynamic_slice(w, (0, start), (D, vc))
        dh_part, dwc = _scan_chunk_bwd(h, wc, tgt, lse, g, start)
        dw = lax.dynamic_update_slice(dw, dwc, (0, start))
        return (dh_acc + dh_part, dw), None

    # (h*0) / zeros_like(w) keep the operands' varying-axes type (see
    # _scan_fwd); g joins the dh carry so a varying cotangent also taints it
    init = (
        (h * 0).astype(jnp.float32) + (g * 0)[:, None],
        jnp.zeros_like(w),
    )
    (dh_acc, dw), _ = lax.scan(body, init, jnp.arange(nfull))
    if V % vc:
        start = nfull * vc
        dh_part, dwc = _scan_chunk_bwd(h, w[:, start:], tgt, lse, g, start)
        dh_acc = dh_acc + dh_part
        dw = lax.dynamic_update_slice(dw, dwc, (0, start))
    return dh_acc.astype(h.dtype), dw


# --- pallas (TPU) implementation ----------------------------------------------


def _ce_fwd_kernel(h_ref, w_ref, tgt_ref, lse_ref, tl_ref, m_sc, s_sc, t_sc,
                   *, blk_n, blk_v, vocab):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG)
        s_sc[:] = jnp.zeros_like(s_sc)
        t_sc[:] = jnp.zeros_like(t_sc)

    logits = jax.lax.dot_general(
        h_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # the grid over-covers a vocab not divisible by blk_v: mask the padded
    # columns before they touch the online max/sum
    col = j * blk_v + jax.lax.broadcasted_iota(jnp.int32, (blk_n, blk_v), 1)
    logits = jnp.where(col < vocab, logits, _NEG)

    m_prev = m_sc[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1))
    p = jnp.exp(logits - m_new[:, None])
    s_sc[:, 0] = s_sc[:, 0] * jnp.exp(m_prev - m_new) + jnp.sum(p, axis=1)
    m_sc[:, 0] = m_new

    tgt = tgt_ref[0]
    rel = tgt - j * blk_v
    hit = (rel >= 0) & (rel < blk_v)
    eq = col == tgt[:, None]  # col is global, so padded cols never match
    got = jnp.sum(jnp.where(eq, logits, 0.0), axis=1)
    t_sc[:, 0] = jnp.where(hit, got, t_sc[:, 0])

    @pl.when(j == nv - 1)
    def _finalize():
        l = jnp.maximum(s_sc[:, 0], 1e-30)
        lse_ref[0] = m_sc[:, 0] + jnp.log(l)
        tl_ref[0] = t_sc[:, 0]


def _ce_dh_kernel(h_ref, w_ref, tgt_ref, lse_ref, g_ref, dh_ref, acc,
                  *, blk_n, blk_v, vocab):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    # mask padded W columns BEFORE the dh matmul: the vocab contraction
    # mixes every column into every dh element, so garbage lanes (reads past
    # V are unspecified) must be zeroed, not just ignored
    col = j * blk_v + jax.lax.broadcasted_iota(jnp.int32, (blk_n, blk_v), 1)
    valid = col < vocab
    w = jnp.where(valid[:1].reshape(1, blk_v), w_ref[...].astype(jnp.float32), 0.0)
    logits = jax.lax.dot_general(
        h_ref[...].astype(jnp.float32), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # select (not arithmetic) so inf/NaN in padded lanes cannot propagate
    p = jnp.where(valid, jnp.exp(logits - lse_ref[0][:, None]), 0.0)
    eq = (col == tgt_ref[0][:, None]).astype(jnp.float32)
    dlogits = (p - eq) * g_ref[0][:, None]
    acc[:] = acc[:] + jax.lax.dot_general(
        dlogits, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(j == nv - 1)
    def _finalize():
        dh_ref[...] = acc[:].astype(dh_ref.dtype)


def _ce_dw_kernel(h_ref, w_ref, tgt_ref, lse_ref, g_ref, dw_ref, acc,
                  *, blk_n, blk_v, vocab, n_rows):
    j, i = pl.program_id(0), pl.program_id(1)
    ni = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    col = j * blk_v + jax.lax.broadcasted_iota(jnp.int32, (blk_n, blk_v), 1)
    logits = jax.lax.dot_general(
        h_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # rows past N (the grid over-covers) carry garbage h/lse/g: select their
    # softmax AND cotangent to exact zeros so the row contraction below
    # cannot mix inf/NaN into the dW accumulation
    row = i * blk_n + jax.lax.broadcasted_iota(jnp.int32, (blk_n, blk_v), 0)[:, 0]
    rvalid = row < n_rows
    mask = (col < vocab) & rvalid[:, None]
    p = jnp.where(mask, jnp.exp(logits - lse_ref[0][:, None]), 0.0)
    eq = (col == tgt_ref[0][:, None]).astype(jnp.float32)
    g = jnp.where(rvalid, g_ref[0], 0.0)
    dlogits = (p - eq * rvalid[:, None].astype(jnp.float32)) * g[:, None]
    h = jnp.where(rvalid[:, None], h_ref[...].astype(jnp.float32), 0.0)
    acc[:] = acc[:] + jax.lax.dot_general(
        h, dlogits, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(i == ni - 1)
    def _finalize():
        dw_ref[...] = acc[:].astype(dw_ref.dtype)


def _pallas_specs(blk_n, blk_v, D, row_major=True):
    """(h, w, row-vector) BlockSpecs for a (rows, vocab) or (vocab, rows)
    grid. Row vectors (tgt/lse/g/losses) are [1, N] arrays blocked (1, blk_n)."""
    if row_major:  # grid (i=rows, j=vocab)
        hspec = pl.BlockSpec((blk_n, D), lambda i, j: (i, 0))
        wspec = pl.BlockSpec((D, blk_v), lambda i, j: (0, j))
        rowspec = pl.BlockSpec((1, blk_n), lambda i, j: (0, i))
        return hspec, wspec, rowspec
    hspec = pl.BlockSpec((blk_n, D), lambda j, i: (i, 0))
    wspec = pl.BlockSpec((D, blk_v), lambda j, i: (0, j))
    rowspec = pl.BlockSpec((1, blk_n), lambda j, i: (0, i))
    return hspec, wspec, rowspec


def _pallas_fwd(h, w, tgt, blk_n, blk_v):
    N, D = h.shape
    V = w.shape[1]
    blk_n, blk_v = min(blk_n, N), min(blk_v, V)
    ni, nv = pl.cdiv(N, blk_n), pl.cdiv(V, blk_v)
    hspec, wspec, rowspec = _pallas_specs(blk_n, blk_v, D)
    tgt2 = tgt.reshape(1, N)
    lse, tl = pl.pallas_call(
        functools.partial(_ce_fwd_kernel, blk_n=blk_n, blk_v=blk_v, vocab=V),
        grid=(ni, nv),
        in_specs=[hspec, wspec, rowspec],
        out_specs=[rowspec, rowspec],
        out_shape=[
            _struct((1, N), jnp.float32, h, w, tgt),
            _struct((1, N), jnp.float32, h, w, tgt),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_n, 1), jnp.float32),
            pltpu.VMEM((blk_n, 1), jnp.float32),
            pltpu.VMEM((blk_n, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(blk_n, blk_v, D, h.dtype.itemsize),
        ),
        interpret=_use_interpret(),
    )(h, w, tgt2)
    return lse[0], tl[0]


def _pallas_bwd(h, w, tgt, lse, g, blk_n, blk_v):
    N, D = h.shape
    V = w.shape[1]
    blk_n, blk_v = min(blk_n, N), min(blk_v, V)
    ni, nv = pl.cdiv(N, blk_n), pl.cdiv(V, blk_v)
    tgt2, lse2, g2 = tgt.reshape(1, N), lse.reshape(1, N), g.reshape(1, N)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_vmem_limit(blk_n, blk_v, D, h.dtype.itemsize),
    )

    hspec, wspec, rowspec = _pallas_specs(blk_n, blk_v, D)
    dh = pl.pallas_call(
        functools.partial(_ce_dh_kernel, blk_n=blk_n, blk_v=blk_v, vocab=V),
        grid=(ni, nv),
        in_specs=[hspec, wspec, rowspec, rowspec, rowspec],
        out_specs=[hspec],
        out_shape=[_struct((N, D), h.dtype, h, w, g)],
        scratch_shapes=[pltpu.VMEM((blk_n, D), jnp.float32)],
        compiler_params=params,
        interpret=_use_interpret(),
    )(h, w, tgt2, lse2, g2)[0]

    hspec_t, wspec_t, rowspec_t = _pallas_specs(blk_n, blk_v, D, row_major=False)
    dw = pl.pallas_call(
        functools.partial(
            _ce_dw_kernel, blk_n=blk_n, blk_v=blk_v, vocab=V, n_rows=N
        ),
        grid=(nv, ni),
        in_specs=[hspec_t, wspec_t, rowspec_t, rowspec_t, rowspec_t],
        out_specs=[wspec_t],
        out_shape=[_struct((D, V), w.dtype, h, w, g)],
        scratch_shapes=[pltpu.VMEM((D, blk_v), jnp.float32)],
        compiler_params=params,
        interpret=_use_interpret(),
    )(h, w, tgt2, lse2, g2)[0]
    return dh, dw


# --- custom_vjp core ----------------------------------------------------------


def _fwd_impl(h, w, tgt, impl, vc, blk_n, blk_v):
    if impl == "pallas":
        return _pallas_fwd(h, w, tgt, blk_n, blk_v)
    return _scan_fwd(h, w, tgt, vc)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fused_ce(h, w, tgt, impl, vc, blk_n, blk_v):
    lse, tl = _fwd_impl(h, w, tgt, impl, vc, blk_n, blk_v)
    return lse - tl


def _fused_ce_fwd(h, w, tgt, impl, vc, blk_n, blk_v):
    lse, tl = _fwd_impl(h, w, tgt, impl, vc, blk_n, blk_v)
    # residuals: (h, w, tgt, lse) — lse is [N] fp32, target_logit is only
    # part of the VALUE, not the gradient (the -tgt term's grad is the
    # onehot the backward rebuilds from tgt)
    return lse - tl, (h, w, tgt, lse)


def _fused_ce_bwd(impl, vc, blk_n, blk_v, res, g):
    h, w, tgt, lse = res
    if impl == "pallas":
        dh, dw = _pallas_bwd(h, w, tgt, lse, g, blk_n, blk_v)
    else:
        dh, dw = _scan_bwd(h, w, tgt, lse, g, vc)
    return dh, dw, np.zeros(tgt.shape, jax.dtypes.float0)


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


# --- public entries -----------------------------------------------------------


def fused_ce_tokens(
    h: jax.Array,
    w: jax.Array,
    targets: jax.Array,
    cfg=None,
    *,
    impl: str | None = None,
    vocab_chunk: int | None = None,
    block_n: int | None = None,
    block_v: int | None = None,
) -> jax.Array:
    """Per-token cross-entropy [B, S] f32 from hidden states, without full
    logits.

    ``h``: [B, S, D] (post final-norm, any float dtype), ``w``: [D, V]
    lm_head, ``targets``: [B, S] int32. Knobs come from
    ``cfg.ce_impl`` / ``cfg.ce_vocab_chunk`` / ``cfg.ce_block_n`` /
    ``cfg.ce_block_v`` when a config is passed (kwargs win). Callers take
    ``jnp.mean`` (and add the MoE aux term) themselves.
    """
    if impl is None:
        impl = getattr(cfg, "ce_impl", None) or "scan"
    if vocab_chunk is None:
        vocab_chunk = getattr(cfg, "ce_vocab_chunk", None) or 4096
    if block_n is None:
        block_n = getattr(cfg, "ce_block_n", None) or _BLOCK_N
    if block_v is None:
        block_v = getattr(cfg, "ce_block_v", None) or _BLOCK_V
    if impl not in ("scan", "pallas"):
        raise ValueError(f"unknown ce_impl {impl!r} (expected scan | pallas)")
    B, S, D = h.shape
    if w.shape[0] != D:
        raise ValueError(f"lm_head {w.shape} does not match hidden dim {D}")
    if targets.shape != (B, S):
        raise ValueError(f"targets {targets.shape} != batch/seq {(B, S)}")
    h2 = h.reshape(B * S, D)
    t2 = targets.reshape(B * S)
    losses = _fused_ce(h2, w, t2, impl, int(vocab_chunk), int(block_n), int(block_v))
    return losses.reshape(B, S)


def sharded_fused_ce_tokens(h, w, targets, cfg=None, **kwargs) -> jax.Array:
    """Mesh-aware entry for the pallas impl (the model-level hook).

    A raw pallas_call gives the SPMD partitioner no partitioning rule, so
    under a multi-device jit it would replicate the op. The loss is row-wise
    independent: shard_map over the registered default mesh keeps batch on
    dp/fsdp/ep and seq on sp with W replicated per shard, and the per-token
    [B, S] output keeps the batch sharding (the caller's mean inserts the
    cross-shard reduce). The scan impl partitions fine under plain jit and
    never takes this path.
    """
    from jax.sharding import PartitionSpec as P

    from tony_tpu.parallel.mesh import get_default_mesh, inside_manual_region

    impl = kwargs.get("impl") or getattr(cfg, "ce_impl", None) or "scan"
    mesh = get_default_mesh()
    if (
        impl != "pallas"
        or mesh is None
        or mesh.size == 1
        or inside_manual_region()
    ):
        # inside a manual region (a pp pipeline stage) the kernel runs on
        # the region-local data; shardy cannot re-bind mesh axes there
        return fused_ce_tokens(h, w, targets, cfg, **kwargs)
    axes = set(mesh.axis_names)
    batch = tuple(a for a in ("dp", "fsdp", "ep") if a in axes) or None
    seq = "sp" if "sp" in axes else None
    spec = P(batch, seq)
    return jax.shard_map(
        lambda a, b, c: fused_ce_tokens(a, b, c, cfg, **kwargs),
        mesh=mesh,
        in_specs=(P(batch, seq, None), P(), spec),
        out_specs=spec,
    )(h, w, targets)


def reference_ce_tokens(h: jax.Array, w: jax.Array, targets: jax.Array) -> jax.Array:
    """Full-logits logsumexp reference: the parity oracle for both impls
    (and the legacy ``ce_impl='dense'`` math). Materialises [B, S, V]."""
    logits = jnp.einsum(
        "bsd,dv->bsv", h, w, preferred_element_type=jnp.float32
    )
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return lse - tgt


__all__ = ["fused_ce_tokens", "reference_ce_tokens", "sharded_fused_ce_tokens"]
