"""Ring attention: sequence/context parallelism over an ICI ring.

Absent from the reference in any form (SURVEY.md section 5 "Long-context":
TonY never touches sequence length); required here as a first-class library
layer. Design follows the blockwise/ring-attention pattern (Liu et al.,
arXiv:2310.01889) expressed the TPU way: the sequence axis is sharded over
the ``sp`` mesh axis, K/V chunks rotate around the ring with
``lax.ppermute`` (one ICI hop per step), and each device folds incoming
chunks into an online-softmax accumulator — peak memory per device is
O(S/n), compute overlaps with the permute because XLA pipelines the loop.

Numerics: scores and the softmax accumulator are float32 regardless of input
dtype; masked positions use a large-negative filler instead of -inf so fully
masked chunks stay NaN-free (the j=0 diagonal chunk always has unmasked
entries, which seeds the running max with a finite value).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)


def _chunk_scores(q, k, scale, q_start, k_start, causal):
    """fp32 scores [B,H,Sq,Sk] with causal mask at global positions."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if causal:
        q_pos = q_start + jnp.arange(q.shape[1])
        k_pos = k_start + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None], s, _NEG)
    return s


def ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "sp",
    causal: bool = True,
    scale: float | None = None,
) -> jax.Array:
    """Per-device ring attention; call inside shard_map.

    q, k, v: [B, S_local, H, head_dim] — this device's contiguous sequence
    chunk (chunk index == its position along ``axis_name``). Returns the
    attention output for the local queries, exact (not approximate).
    """
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, S, H, D = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # Derive the accumulators from q (not jnp.zeros) so they carry the same
    # varying-manual-axes type as the loop outputs (jax>=0.9 shard_map typing).
    zero = jnp.swapaxes(q.astype(jnp.float32).sum(-1), 1, 2) * 0.0  # [B,H,S]
    o0 = jnp.broadcast_to(zero[..., None], (B, H, S, D))
    m0 = zero + _NEG
    l0 = zero

    def body(j, carry):
        k_cur, v_cur, o, m, l = carry
        kv_idx = (my - j) % n  # which chunk this device holds at step j
        s = _chunk_scores(q, k_cur, scale, my * S, kv_idx * S, causal)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_cur.astype(jnp.float32)
        )
        # rotate K/V one step around the ring (ICI-neighbour hop)
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return k_next, v_next, o, m_new, l

    _, _, o, _, l = lax.fori_loop(0, n, body, (k, v, o0, m0, l0))
    out = o / l[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# --- ring flash: the Pallas kernel as the per-chunk inner -------------------


def _fold(x):
    """[B,S,H,D] -> [B*H,S,D] (the flash kernels' layout)."""
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _unfold(x, B, H):
    BH, S, D = x.shape
    return x.reshape(B, H, S, D).transpose(0, 2, 1, 3)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ring_flash_attention_local(q, k, v, axis_name="sp", blk_q=512, blk_k=512):
    """Ring attention whose per-chunk inner is the Pallas flash kernel.

    The dense ring inner materialises [S_local, S_local] fp32 scores per
    step; this one streams them through VMEM, so the per-device sequence
    chunk can itself be long (the production long-context configuration:
    ring over ``sp`` × flash within the chunk). Causal, exact; same
    [B, S_local, H, D] contract as ring_attention_local. The backward is
    the blockwise decomposition: each chunk's dq/dk/dv kernels run against
    the GLOBAL logsumexp, with dk/dv accumulators riding the ring.
    """
    out, _ = _ring_flash_fwd_local(q, k, v, axis_name, blk_q, blk_k)
    return out


def _chunk_rel(my, kv_idx):
    """0 = fully visible (kv before q), 1 = diagonal (causal), 2 = skip."""
    return jnp.where(kv_idx < my, 0, jnp.where(kv_idx == my, 1, 2))


def _check_blocks(S: int, blk_q: int, blk_k: int) -> tuple[int, int]:
    bq, bk = min(blk_q, S), min(blk_k, S)
    if S % bq or S % bk:
        raise ValueError(
            f"per-device seq chunk {S} must be a multiple of the flash "
            f"block sizes ({bq}, {bk}); adjust flash_block_q/k or sp"
        )
    return bq, bk


def _ring_flash_fwd_local(q, k, v, axis_name, blk_q, blk_k):
    from tony_tpu.ops.attention import flash_fwd_pass

    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    scale = 1.0 / math.sqrt(D)
    perm = [(i, (i + 1) % n) for i in range(n)]
    qf = _fold(q)
    bq, bk = _check_blocks(S, blk_q, blk_k)

    def run(causal):
        def f(k_cur, v_cur):
            return flash_fwd_pass(
                qf, _fold(k_cur), _fold(v_cur), scale=scale,
                blk_q=bq, blk_k=bk, causal=causal,
                heads=H, kv_heads=Hkv,
            )
        return f

    def skip(k_cur, v_cur):
        zero_o = jnp.zeros_like(qf)
        # derive from q so the branch output carries the varying-axes type
        neg_lse = (qf.astype(jnp.float32).sum() * 0.0) + jnp.full(
            (B * H, 1, S), _NEG, jnp.float32
        )
        return zero_o, neg_lse

    # accumulators derived from q so they carry the varying-axes type
    o0 = qf.astype(jnp.float32) * 0.0
    lse0 = jnp.full((B * H, 1, S), _NEG, jnp.float32) + (
        qf.astype(jnp.float32).sum() * 0.0
    )

    def body(j, carry):
        k_cur, v_cur, o_num, lse = carry
        kv_idx = (my - j) % n
        out_c, lse_c = lax.switch(
            _chunk_rel(my, kv_idx),
            [run(False), run(True), skip],
            k_cur, v_cur,
        )
        new_lse = jnp.logaddexp(lse, lse_c)
        w_old = jnp.exp(lse - new_lse)[:, 0, :, None]     # [BH,S,1]
        w_new = jnp.exp(lse_c - new_lse)[:, 0, :, None]
        o_num = o_num * w_old + out_c.astype(jnp.float32) * w_new
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return k_next, v_next, o_num, new_lse

    _, _, o_num, lse = lax.fori_loop(0, n, body, (k, v, o0, lse0))
    out = _unfold(o_num.astype(q.dtype), B, H)
    return out, lse


def _ring_flash_fwd_rule(q, k, v, axis_name, blk_q, blk_k):
    out, lse = _ring_flash_fwd_local(q, k, v, axis_name, blk_q, blk_k)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd_rule(axis_name, blk_q, blk_k, res, g):
    from tony_tpu.ops.attention import flash_dq_pass, flash_dkv_pass

    q, k, v, out, lse = res
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    scale = 1.0 / math.sqrt(D)
    perm = [(i, (i + 1) % n) for i in range(n)]
    qf, dof = _fold(q), _fold(g)
    delta = jnp.sum(
        dof.astype(jnp.float32) * _fold(out).astype(jnp.float32), axis=-1
    )[:, None, :]
    bq, bk = _check_blocks(S, blk_q, blk_k)
    kw = dict(scale=scale, blk_q=bq, blk_k=bk, heads=H, kv_heads=Hkv)

    def run(causal):
        def f(kf, vf):
            dq_c = flash_dq_pass(qf, kf, vf, dof, lse, delta,
                                 causal=causal, **kw)
            dk_c, dv_c = flash_dkv_pass(qf, kf, vf, dof, lse, delta,
                                        causal=causal, **kw)
            return dq_c, dk_c, dv_c
        return f

    def skip(kf, vf):
        return jnp.zeros_like(qf), jnp.zeros_like(kf), jnp.zeros_like(vf)

    def body(j, carry):
        k_cur, v_cur, dk_cur, dv_cur, dq_acc = carry
        kv_idx = (my - j) % n
        dq_c, dk_c, dv_c = lax.switch(
            _chunk_rel(my, kv_idx),
            [run(False), run(True), skip],
            _fold(k_cur), _fold(v_cur),
        )
        dq_acc = dq_acc + dq_c.astype(jnp.float32)
        dk_cur = dk_cur + _unfold(dk_c, B, Hkv).astype(jnp.float32)
        dv_cur = dv_cur + _unfold(dv_c, B, Hkv).astype(jnp.float32)
        # the grad accumulators ride the ring WITH their chunk: after n
        # rotations each chunk's dk/dv arrive back at its owner having
        # collected every device's contribution
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        dk_next = lax.ppermute(dk_cur, axis_name, perm)
        dv_next = lax.ppermute(dv_cur, axis_name, perm)
        return k_next, v_next, dk_next, dv_next, dq_acc

    dk0 = k.astype(jnp.float32) * 0.0
    dv0 = v.astype(jnp.float32) * 0.0
    dq0 = qf.astype(jnp.float32) * 0.0
    _, _, dk, dv, dqf = lax.fori_loop(0, n, body, (k, v, dk0, dv0, dq0))
    return (_unfold(dqf, B, H).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype))


ring_flash_attention_local.defvjp(_ring_flash_fwd_rule, _ring_flash_bwd_rule)


def make_ring_attention(
    mesh: Mesh, *, axis_name: str = "sp", causal: bool = True
):
    """AttnFn closure: full arrays in, shard_map over the mesh inside.

    Batch goes over dp/fsdp, sequence over ``axis_name``, heads over tp (all
    only if present in the mesh); the ring collective runs over ``axis_name``.
    Plugs into llama.LlamaConfig(attention_impl='ring') via set_default_mesh.
    """
    from tony_tpu.parallel.mesh import inside_manual_region
    from tony_tpu.parallel.sharding import attn_spec

    spec = attn_spec(mesh, seq_axis=axis_name)
    inner = partial(ring_attention_local, axis_name=axis_name, causal=causal)

    def attn(q, k, v, cfg=None):
        if inside_manual_region():
            # shardy cannot re-bind collective axes inside a parent manual
            # computation (tested: both full-manual and sp-only nesting are
            # rejected by the sdy verifier) — pp_loss_from_pairs raises
            # before reaching here; this guards direct shard_map users
            raise NotImplementedError(
                "ring attention cannot run inside another shard_map region "
                "(e.g. a pp pipeline stage); use attention_impl='flash' or "
                "'dot' with pp, or drop pp and shard the sequence with sp"
            )
        return jax.shard_map(
            lambda a, b, c: inner(a, b, c),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
        )(q, k, v)

    return attn


def ring_attention(q, k, v, cfg=None):
    """Model hook (AttnFn signature): uses the registered default mesh."""
    from tony_tpu.parallel.mesh import get_default_mesh

    mesh = get_default_mesh()
    if mesh is None:
        raise RuntimeError(
            "ring attention needs a mesh: call "
            "tony_tpu.parallel.set_default_mesh(mesh) (fit() does this "
            "automatically for its training mesh)"
        )
    return make_ring_attention(mesh)(q, k, v, cfg)


def make_ring_flash_attention(mesh: Mesh, *, axis_name: str = "sp"):
    """AttnFn closure for ring × flash: sequence over the ring, the Pallas
    kernel within each chunk — the production long-context configuration."""
    from tony_tpu.parallel.mesh import inside_manual_region
    from tony_tpu.parallel.sharding import attn_spec

    spec = attn_spec(mesh, seq_axis=axis_name)

    def attn(q, k, v, cfg=None):
        if inside_manual_region():
            raise NotImplementedError(
                "ring-flash attention cannot run inside another shard_map "
                "region (e.g. a pp pipeline stage)"
            )
        # same defaults as flash_attention (1024/1024 measured fastest on
        # v5e) so the two entries to the identical kernel never diverge
        blk_q = getattr(cfg, "flash_block_q", None) or 1024
        blk_k = getattr(cfg, "flash_block_k", None) or 1024
        # GQA under tp: kv heads must divide tp or fall back to expansion
        # (mirrors sharded_flash_attention)
        tp = int(mesh.shape.get("tp", 1))
        if k.shape[2] % tp:
            rep = q.shape[2] // k.shape[2]
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        # Interpreter-mode pallas (CPU tests) trips jax's varying-axes
        # checker on the kernel's internal dynamic_slice with unvarying
        # grid indices, so the checker is off ONLY there; on real TPU it
        # stays on — same vma discipline as the dense ring path.
        from tony_tpu.ops.attention import _use_interpret

        return jax.shard_map(
            lambda a, b, c: ring_flash_attention_local(
                a, b, c, axis_name, blk_q, blk_k
            ),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=not _use_interpret(),
        )(q, k, v)

    return attn


def ring_flash_attention(q, k, v, cfg=None):
    """Model hook (AttnFn signature): uses the registered default mesh."""
    from tony_tpu.parallel.mesh import get_default_mesh

    mesh = get_default_mesh()
    if mesh is None:
        raise RuntimeError(
            "ring-flash attention needs a mesh: call "
            "tony_tpu.parallel.set_default_mesh(mesh) first"
        )
    return make_ring_flash_attention(mesh)(q, k, v, cfg)


__all__ = [
    "make_ring_attention",
    "make_ring_flash_attention",
    "ring_attention",
    "ring_attention_local",
    "ring_flash_attention",
    "ring_flash_attention_local",
]
