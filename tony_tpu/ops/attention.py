"""Pallas flash attention for TPU (FlashAttention-2 schedule).

The hot op of the training library (SURVEY.md section 7 layer 7): blockwise
causal attention that never materialises the [S, S] score matrix. Forward and
backward are Pallas kernels; the backward uses the saved logsumexp and the
delta trick (rowsum(dO * O)) per FlashAttention-2 (arXiv:2307.08691).

TPU mapping: inputs are folded to [B*H, S, head_dim] so every block spec ends
in (block, head_dim) — the Mosaic lowering requires the last two block dims
tiled (8, 128)-aligned. The grid is (batch*head, q-block, k-block) with the
k-block dimension innermost: TPU grids iterate sequentially on-core, so the
online-softmax accumulator lives in VMEM scratch across k-steps and the
output block is finalised on the last k-step. Matmuls hit the MXU with fp32
accumulation; blocks entirely above the causal diagonal skip their FLOPs via
pl.when predication.

On non-TPU backends the kernels run in interpreter mode (CPU tests); the
public entry matches the AttnFn contract (q, k, v, cfg) of
tony_tpu.models.llama.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tony_tpu.ops.compat import (
    struct_with_vma as _struct,
    use_interpret as _use_interpret,
)

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)


# --- forward -----------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_sc, l_sc,
                *, scale, blk_q, blk_k, causal):
    i, j = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _NEG)
        l_sc[:] = jnp.zeros_like(l_sc)

    # whole block above the diagonal -> no contribution, skip its FLOPs
    run = (not causal) or (j * blk_k <= i * blk_q + blk_q - 1)

    @pl.when(run)
    def _block():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            q_pos = i * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
            k_pos = j * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG)
        m_prev = m_sc[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_sc[:, 0] = l_sc[:, 0] * corr + jnp.sum(p, axis=1)
        acc[:] = acc[:] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_sc[:, 0] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_sc[:, 0], 1e-30)
        o_ref[0] = (acc[:] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_sc[:, 0] + jnp.log(l)


def _kv_index(b: int, heads: int, kv_heads: int) -> int:
    """Fold a [B*H] grid index onto the [B*kv_heads] K/V array (GQA)."""
    rep = heads // kv_heads
    return (b // heads) * kv_heads + (b % heads) // rep


def _flash_fwd(q, k, v, *, scale, blk_q, blk_k, causal, heads, kv_heads):
    """q: [B*heads, S, D], k/v: [B*kv_heads, S, D] ->
    (out [B*heads, S, D], lse [B*heads, 1, S] fp32)."""
    BH, S, D = q.shape
    nq, nk = pl.cdiv(S, blk_q), pl.cdiv(S, blk_k)
    qspec = pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec(
        (1, blk_k, D), lambda b, i, j: (_kv_index(b, heads, kv_heads), j, 0)
    )
    rowspec = pl.BlockSpec((1, 1, blk_q), lambda b, i, j: (b, 0, i))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, blk_q=blk_q, blk_k=blk_k, causal=causal),
        # a name of its own: a partial has none, and the kernel would go
        # by the JAX primitive that wraps it (closed_call, checkpoint)
        name="flash_fwd",
        grid=(BH, nq, nk),
        in_specs=[qspec, kspec, kspec],
        out_specs=[qspec, rowspec],
        out_shape=[
            _struct((BH, S, D), q.dtype, q, k, v),
            _struct((BH, 1, S), jnp.float32, q, k, v),
        ],
        # out/lse blocks revisit the same index across the k-step dim
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        scratch_shapes=[
            pltpu.VMEM((blk_q, D), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
        ],
        interpret=_use_interpret(),
    )(q, k, v)
    return out, lse


# --- backward ----------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc,
                   *, scale, blk_q, blk_k, causal):
    i, j = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    run = (not causal) or (j * blk_k <= i * blk_q + blk_q - 1)

    @pl.when(run)
    def _block():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse, delta = lse_ref[0, 0], delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            q_pos = i * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
            k_pos = j * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None]) * scale
        acc[:] = acc[:] + jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                    dk_acc, dv_acc, *, scale, blk_q, blk_k, causal, nq):
    # grid: (B*kv_heads, k-block j, rep*q-blocks i) — innermost dim walks all
    # q blocks of every query head sharing this kv head (GQA), accumulating
    # dk/dv across the group; i % nq is the q-block position within one head.
    j, i = pl.program_id(1), pl.program_id(2)
    ni = pl.num_programs(2)
    iq = i % nq

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = (not causal) or (j * blk_k <= iq * blk_q + blk_q - 1)

    @pl.when(run)
    def _block():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse, delta = lse_ref[0, 0], delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            q_pos = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
            k_pos = j * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG)
        p = jnp.exp(s - lse[:, None])                       # [bq, bk]
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None]) * scale
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(i == ni - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def flash_dq_pass(q, k, v, do, lse, delta, *, scale, blk_q, blk_k, causal,
                  heads, kv_heads):
    """dq from explicit (lse, delta) — usable with a GLOBAL lse/delta, which
    is what blockwise/ring backward passes need. Shapes: q/do [B*heads,S,D],
    k/v [B*kv_heads,S,D], lse/delta [B*heads,1,S] fp32."""
    BH, S, D = q.shape
    nq, nk = pl.cdiv(S, blk_q), pl.cdiv(S, blk_k)
    qspec = pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec(
        (1, blk_k, D), lambda b, i, j: (_kv_index(b, heads, kv_heads), j, 0)
    )
    rowspec = pl.BlockSpec((1, 1, blk_q), lambda b, i, j: (b, 0, i))
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, blk_q=blk_q, blk_k=blk_k, causal=causal),
        name="flash_bwd_dq",
        grid=(BH, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=[qspec],
        out_shape=[_struct((BH, S, D), q.dtype, q, k, v, do)],
        scratch_shapes=[pltpu.VMEM((blk_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=_use_interpret(),
    )(q, k, v, do, lse, delta)[0]


def flash_dkv_pass(q, k, v, do, lse, delta, *, scale, blk_q, blk_k, causal,
                   heads, kv_heads):
    """dk/dv from explicit (lse, delta); see flash_dq_pass.

    Grid over the [B*kv_heads] K/V array; k-block outer, then the inner dim
    walks rep*nq q-blocks (all query heads of the GQA group back-to-back) so
    dk/dv accumulate in VMEM scratch across the group."""
    BH, S, D = q.shape
    BKV = k.shape[0]
    rep = heads // kv_heads
    nq, nk = pl.cdiv(S, blk_q), pl.cdiv(S, blk_k)

    def _q_index(b: int, i: int) -> int:
        return (b // kv_heads) * heads + (b % kv_heads) * rep + i // nq

    qspec_t = pl.BlockSpec((1, blk_q, D), lambda b, j, i: (_q_index(b, i), i % nq, 0))
    kspec_t = pl.BlockSpec((1, blk_k, D), lambda b, j, i: (b, j, 0))
    rowspec_t = pl.BlockSpec((1, 1, blk_q), lambda b, j, i: (_q_index(b, i), 0, i % nq))
    return pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, blk_q=blk_q, blk_k=blk_k,
            causal=causal, nq=nq,
        ),
        name="flash_bwd_dkv",
        grid=(BKV, nk, rep * nq),
        in_specs=[qspec_t, kspec_t, kspec_t, qspec_t, rowspec_t, rowspec_t],
        out_specs=[kspec_t, kspec_t],
        out_shape=[
            _struct((BKV, S, D), k.dtype, q, k, v, do),
            _struct((BKV, S, D), v.dtype, q, k, v, do),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_k, D), jnp.float32),
            pltpu.VMEM((blk_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=_use_interpret(),
    )(q, k, v, do, lse, delta)


def _flash_bwd(res, g, *, scale, blk_q, blk_k, causal, heads, kv_heads):
    q, k, v, out, lse = res
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )[:, None, :]  # [BH, 1, S]
    kw = dict(scale=scale, blk_q=blk_q, blk_k=blk_k, causal=causal,
              heads=heads, kv_heads=kv_heads)
    dq = flash_dq_pass(q, k, v, g, lse, delta, **kw)
    dk, dv = flash_dkv_pass(q, k, v, g, lse, delta, **kw)
    return dq, dk, dv


# --- public entry -------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, blk_q, blk_k, causal, heads, kv_heads):
    out, _ = _flash_fwd(q, k, v, scale=scale, blk_q=blk_q, blk_k=blk_k,
                        causal=causal, heads=heads, kv_heads=kv_heads)
    return out


def _flash_fwd_rule(q, k, v, scale, blk_q, blk_k, causal, heads, kv_heads):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _flash_fwd(q, k, v, scale=scale, blk_q=blk_q, blk_k=blk_k,
                          causal=causal, heads=heads, kv_heads=kv_heads)
    # named save point: under remat, a policy saving 'flash_res' keeps the
    # kernel's residuals (out + logsumexp) so the backward pass runs only the
    # dq/dkv kernels instead of re-running this forward kernel first
    out = checkpoint_name(out, "flash_res")
    lse = checkpoint_name(lse, "flash_res")
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(scale, blk_q, blk_k, causal, heads, kv_heads, res, g):
    return _flash_bwd(res, g, scale=scale, blk_q=blk_q, blk_k=blk_k,
                      causal=causal, heads=heads, kv_heads=kv_heads)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    cfg=None,
    *,
    causal: bool = True,
    block_q: int | None = None,
    block_k: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Causal flash attention. q/k/v: [B, S, H, head_dim] -> same shape.

    Matches the AttnFn contract of tony_tpu.models.llama; tile sizes come
    from ``cfg.flash_block_q/flash_block_k`` when a config is passed (kwargs
    win). Sequence length must be a multiple of the (possibly clipped) block
    sizes. The [B,S,H,D] -> [B*H,S,D] fold is done here; XLA fuses the
    transposes into the surrounding projections. K/V may carry fewer heads
    than Q (GQA): the kernel reads each K/V head n_heads/n_kv_heads times via
    its BlockSpec index map instead of materialising the repeat in HBM.
    """
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    if H % Hkv:
        raise ValueError(f"n_heads {H} not a multiple of n_kv_heads {Hkv}")
    if v.shape != k.shape:
        raise ValueError(f"k/v shape mismatch: {k.shape} vs {v.shape}")
    if block_q is None:
        block_q = getattr(cfg, "flash_block_q", None) or 512
    if block_k is None:
        block_k = getattr(cfg, "flash_block_k", None) or 1024
    blk_q = min(block_q, S)
    blk_k = min(block_k, S)
    if S % blk_q or S % blk_k:
        raise ValueError(f"seq len {S} must be a multiple of block sizes ({blk_q}, {blk_k})")
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    def fold(x):
        h = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(B * h, S, D)

    out = _flash(fold(q), fold(k), fold(v), scale, blk_q, blk_k, causal, H, Hkv)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def sharded_flash_attention(q, k, v, cfg=None, **kwargs) -> jax.Array:
    """Mesh-aware flash attention: the model-level 'flash' hook.

    A raw pallas_call gives the SPMD partitioner no partitioning rule, so
    under a multi-device jit it would replicate the op (all-gathering global
    q/k/v onto every chip). Wrapping in shard_map over the registered default
    mesh keeps batch on dp/fsdp and heads on tp; the sequence dim stays local
    (flash needs full K/V — use attention_impl='ring' to shard sequence).
    """
    from tony_tpu.parallel.mesh import get_default_mesh, inside_manual_region
    from tony_tpu.parallel.sharding import attn_spec

    mesh = get_default_mesh()
    if mesh is None or mesh.size == 1:
        return flash_attention(q, k, v, cfg, **kwargs)
    if inside_manual_region():
        # already inside a shard_map region (a pp pipeline stage): shardy
        # cannot re-bind mesh axes in a nested manual computation, so run
        # the kernel on the region-local data and let the outer partitioner
        # own batch/heads (correct; may replicate the op across tp)
        return flash_attention(q, k, v, cfg, **kwargs)
    # GQA under tp: the heads axis is sharded over tp, so the narrower K/V
    # head dim must also divide tp — when it doesn't, fall back to expanding
    # K/V to full width in HBM (correct, just not the bandwidth-lean path).
    tp = int(mesh.shape.get("tp", 1))
    if k.shape[2] % tp:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    spec = attn_spec(mesh)  # seq_axis=None: sequence stays device-local
    return jax.shard_map(
        lambda a, b, c: flash_attention(a, b, c, cfg, **kwargs),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )(q, k, v)


# explicit-residual entry for blockwise/ring composition:
# (q, k, v) -> (out, lse) with lse in [B*heads, 1, S] fp32 kernel layout
flash_fwd_pass = _flash_fwd

__all__ = [
    "flash_attention", "flash_dq_pass", "flash_dkv_pass", "flash_fwd_pass",
    "sharded_flash_attention",
]
