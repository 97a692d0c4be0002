"""GQA decode attention: one query token per row against a length-aware KV
cache, read at native ``n_kv_heads`` width.

The serving hot op (docs/SERVE.md). Training-side flash attention already
reads each K/V head ``n_heads/n_kv_heads`` times through its BlockSpec index
map instead of materialising the repeat (ops/attention.py); the decode path
in ``generate.py`` still ``jnp.repeat``ed the caches — 4x the HBM traffic
AND residency at llama3's 32:8 head ratio, on an op that is pure memory
bandwidth (one query row per request). Here queries fold to
``[B, n_kv_heads, rep, head_dim]`` and contract against the unexpanded
cache, and per-row ``lengths`` bound the attended positions so work stops at
the written prefix instead of ``max_len``.

**Contiguous form**: two interchangeable implementations (the
``fused_ce``/``grouped_mm`` pattern), dispatched on ``impl``:

- ``'scan'`` — ``lax.scan`` over KV blocks with an online softmax (the
  flash recurrence). Pure XLA: runs anywhere, is the default, and keeps the
  score transient at ``[B, Hkv, rep, block]`` instead of ``[B, H, T]``.
- ``'pallas'`` — a TPU kernel over a ``(B * n_kv_heads, T/block)`` grid.
  Per-row lengths ride as a scalar-prefetch argument; KV tiles entirely
  beyond a row's length skip their FLOPs via ``pl.when``. Interpreter mode
  on CPU.

Cache layout is head-major ``[B, n_kv_heads, T, head_dim]`` (the serve
engine's block cache flattens to exactly this), so the kernel fold is a
reshape, not a transpose of the whole cache every step.

**Paged form** (``tables`` given; what the serve engine runs): K/V are
physical-block *pools* ``[P, n_kv_heads, block, head_dim]`` and ``tables
[B, M]`` maps row ``b``'s logical block ``j`` to a physical block id — the
indirection that lets the prefix store (serve/prefix.py) share one physical
block across many rows. The latent form (:func:`latent_decode_attention`)
is the same with one shared row per position whose first columns are the
values. The op takes no ``impl`` here: it chooses from what it can observe
(:func:`_run_kernel`).

- On a TPU, ONE Pallas kernel ``paged_decode_attention`` for the dense, the
  quantized and the latent pool (:func:`_paged_attend`): a grid step is one
  row, all of its kv heads, ``_STEP_BLOCKS`` table blocks; lengths and
  table ride as scalar prefetch and steer the pool's index maps, clamped to
  the row's last live block — so a row's DMA and FLOPs stop at its own
  ``ceil(lengths[b] / block)`` blocks whatever the table's width, and no
  shape depends on the lengths (one compiled decode step serves every
  step, ``shrink=False`` included; PERF.md §6, PR 28). A step takes fewer
  blocks where the tiles are large (MHA pools, float32 rows, long blocks),
  so that they fit the kernel's VMEM (:func:`_step_blocks`).
- Elsewhere, and for a pool one block of which does not fit that VMEM,
  :func:`_paged_scan`: a ``lax.scan`` over all ``M`` table
  entries that gathers block ``j`` of every row and masks afterwards. Pure
  XLA, the reference form of the tests; it reads the whole table's worth
  of pool each call.

Table entries beyond a row's allocation must point at a valid id (the
engine uses the scratch block 0); the kernel never fetches them.

No backward: decode is inference-only. ``T`` must be a multiple of
``block`` (the block cache guarantees it); ``lengths`` must be >= 1 — the
engine always writes position ``t`` before attending over ``t + 1``
positions, so a live row's first block is never empty.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tony_tpu.ops.compat import use_interpret as _use_interpret

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)


def reference_decode_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, lengths: jax.Array,
    *, scale: float | None = None,
) -> jax.Array:
    """Repeat-expanded full-width reference (the parity oracle, and exactly
    what generate.py's ``_cached_attention`` did per decode step).

    q: [B, H, hd]; k/v: [B, Hkv, T, hd]; lengths: [B] int32 (positions
    < lengths[b] are attended). Returns [B, H, hd].

    Speculative form: q ``[B, G, H, hd]`` carries G query positions per
    row (the last real token plus G-1 draft tokens, serve/spec.py) and
    ``lengths`` counts the cache AFTER all G writes — query g of row b
    attends positions ``< lengths[b] - (G - 1) + g``, so G=1 reduces
    exactly to the one-token rule. Returns [B, G, H, hd].
    """
    if q.ndim == 4:
        G = q.shape[1]
        return jnp.stack(
            [
                reference_decode_attention(
                    q[:, g], k, v, lengths - (G - 1) + g, scale=scale
                )
                for g in range(G)
            ],
            axis=1,
        )
    B, H, hd = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    rep = H // Hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bhd,bhkd->bhk", q, k, preferred_element_type=jnp.float32)
    valid = jnp.arange(T)[None, :] < lengths[:, None]          # [B, T]
    s = jnp.where(valid[:, None, :], s * scale, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhk,bhkd->bhd", p, v)


# --- scan (XLA) implementation ------------------------------------------------


def _decode_scan(q, k, v, lengths, *, scale, block):
    """Online-softmax scan over KV blocks, native GQA contraction.
    q ``[B, G, H, hd]``: query g attends ``< lengths[b] - (G-1) + g``."""
    B, G, H, hd = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    rep = H // Hkv
    nb = T // block
    qg = q.reshape(B, G, Hkv, rep, hd)
    goff = jnp.arange(G, dtype=jnp.int32)

    m0 = jnp.full((B, G, Hkv, rep), _NEG, jnp.float32)
    l0 = jnp.zeros((B, G, Hkv, rep), jnp.float32)
    acc0 = jnp.zeros((B, G, Hkv, rep, hd), jnp.float32)

    def body(carry, j):
        m, l, acc = carry
        kb = lax.dynamic_slice_in_dim(k, j * block, block, axis=2)
        vb = lax.dynamic_slice_in_dim(v, j * block, block, axis=2)
        s = jnp.einsum(
            "bgxrd,bxkd->bgxrk", qg, kb, preferred_element_type=jnp.float32
        ) * scale
        pos = j * block + jnp.arange(block)
        valid = pos[None, None, :] < (
            lengths[:, None, None] - (G - 1) + goff[None, :, None]
        )                                                      # [B, G, block]
        vmask = valid[:, :, None, None, :]
        s = jnp.where(vmask, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(vmask, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bgxrk,bxkd->bgxrd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l, acc), None

    (m, l, acc), _ = lax.scan(
        body, (m0, l0, acc0), jnp.arange(nb, dtype=jnp.int32)
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, G, H, hd).astype(q.dtype)


def _paged_scan(q, k, v, lengths, tables, *, scale, k_scale=None,
                v_scale=None, v_width=0):
    """Online-softmax scan over *logical* blocks, each row's block gathered
    through its table entry (native GQA contraction, paged pools). Given
    ``k_scale``/``v_scale`` ``[P, Hkv]`` the pools are quantized: the scale
    row is gathered right next to the block gather and the tile is
    dequantized in registers (serve/cache.py block-scaled quantization).

    Latent form (``v`` is None, ``v_width`` given): the pool holds one
    shared row per position (``Hkv`` 1), every query head contracts against
    all of it, and the values are its first ``v_width`` columns — each
    block is gathered once and serves as keys and as values."""
    B, G, H, hd = q.shape
    Hkv, blk = k.shape[1], k.shape[2]
    rep = H // Hkv
    nb = tables.shape[1]
    vd = hd if v is not None else v_width
    qg = q.reshape(B, G, Hkv, rep, hd)
    goff = jnp.arange(G, dtype=jnp.int32)

    m0 = jnp.full((B, G, Hkv, rep), _NEG, jnp.float32)
    l0 = jnp.zeros((B, G, Hkv, rep), jnp.float32)
    acc0 = jnp.zeros((B, G, Hkv, rep, vd), jnp.float32)

    def body(carry, j):
        m, l, acc = carry
        pid = lax.dynamic_index_in_dim(tables, j, axis=1, keepdims=False)
        kb = jnp.take(k, pid, axis=0)                          # [B, Hkv, blk, hd]
        vb = jnp.take(v, pid, axis=0) if v is not None else kb[..., :vd]
        if k_scale is not None:
            ksc = jnp.take(k_scale, pid, axis=0)               # [B, Hkv]
            vsc = jnp.take(v_scale, pid, axis=0)
            kb = (kb.astype(jnp.float32) * ksc[..., None, None]).astype(qg.dtype)
            vb = (vb.astype(jnp.float32) * vsc[..., None, None]).astype(qg.dtype)
        s = jnp.einsum(
            "bgxrd,bxkd->bgxrk", qg, kb, preferred_element_type=jnp.float32
        ) * scale
        pos = j * blk + jnp.arange(blk)
        valid = pos[None, None, :] < (
            lengths[:, None, None] - (G - 1) + goff[None, :, None]
        )                                                      # [B, G, blk]
        vmask = valid[:, :, None, None, :]
        s = jnp.where(vmask, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(vmask, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bgxrk,bxkd->bgxrd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l, acc), None

    (m, l, acc), _ = lax.scan(
        body, (m0, l0, acc0), jnp.arange(nb, dtype=jnp.int32)
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, G, H, vd).astype(q.dtype)


def latent_decode_attention(q: jax.Array, pool: jax.Array, lengths: jax.Array,
                            tables: jax.Array, *, v_width: int,
                            scale: float) -> jax.Array:
    """Absorbed latent attention, one query token per row, through the block
    table: ``q [B, H, kv_rank + rope]`` (the key half of the up-projection
    already folded into it) against the latent pool ``[P, 1, block, kv_rank
    + rope]``; the values are the first ``v_width`` (= kv_rank) columns of
    the very rows the scores read. Returns ``[B, H, v_width]`` — the caller
    applies the value half of the up-projection. Kernel or scan as the
    paged form chooses (:func:`_run_kernel`). A pool whose rows are wider
    than the query (zero lanes beyond the latent, the cache's padding) is
    contracted whole against a zero-padded query."""
    if pool.shape[1] != 1 or pool.shape[3] < q.shape[-1]:
        raise ValueError(f"latent decode shapes q={q.shape} pool={pool.shape}")
    q = jnp.pad(q, ((0, 0), (0, 0), (0, pool.shape[3] - q.shape[-1])))
    out = _paged(q[:, None], pool, None, lengths, tables, scale=scale,
                 v_width=v_width)
    return out[:, 0]


# --- pallas (TPU) implementation ----------------------------------------------


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc, m_sc, l_sc,
                   *, scale, block, kv_heads, rep, queries):
    b, j = pl.program_id(0), pl.program_id(1)
    nb = pl.num_programs(1)
    row_len = len_ref[b // kv_heads]

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _NEG)
        l_sc[:] = jnp.zeros_like(l_sc)

    # tiles entirely beyond this row's written prefix contribute nothing:
    # skip their FLOPs (their probability mass is exactly zero)
    @pl.when(j * block < row_len)
    def _block():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                      # [queries*rep, block]
        pos = j * block + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # folded row r is query g = r // rep: speculative query g may only
        # see positions < row_len - (G-1) + g (row_len counts the cache
        # AFTER all G writes; G=1 reduces to the plain < row_len rule)
        gq = lax.broadcasted_iota(jnp.int32, s.shape, 0) // rep
        valid = pos < row_len - (queries - 1) + gq
        s = jnp.where(valid, s, _NEG)
        m_prev = m_sc[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.where(valid, jnp.exp(s - m_new[:, None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_sc[:, 0] = l_sc[:, 0] * corr + jnp.sum(p, axis=1)
        acc[:] = acc[:] * corr[:, None] + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_sc[:, 0] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        l = jnp.maximum(l_sc[:, 0], 1e-30)
        o_ref[0] = (acc[:] / l[:, None]).astype(o_ref.dtype)


def _decode_pallas(q, k, v, lengths, *, scale, block):
    B, G, H, hd = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    rep = H // Hkv
    nb = T // block
    R = G * rep
    # fold the G query positions into the tile rows: grid row b*Hkv + x
    # computes every (g, r) pair of row b's kv-head x at once, so the
    # speculative widening adds zero grid steps and zero extra K/V DMA
    qf = q.reshape(B, G, Hkv, rep, hd).transpose(0, 2, 1, 3, 4).reshape(
        B * Hkv, R, hd
    )
    kf = k.reshape(B * Hkv, T, hd)
    vf = v.reshape(B * Hkv, T, hd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * Hkv, nb),
        in_specs=[
            pl.BlockSpec((1, R, hd), lambda b, j, ln: (b, 0, 0)),
            pl.BlockSpec((1, block, hd), lambda b, j, ln: (b, j, 0)),
            pl.BlockSpec((1, block, hd), lambda b, j, ln: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, R, hd), lambda b, j, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((R, hd), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, scale=scale, block=block, kv_heads=Hkv,
            rep=rep, queries=G,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hkv, R, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=_use_interpret(),
    )(lengths.astype(jnp.int32), qf, kf, vf)
    return out.reshape(B, Hkv, G, rep, hd).transpose(0, 2, 1, 3, 4).reshape(
        B, G, H, hd
    )


# table blocks one grid step of the paged kernel fetches and attends: a step
# then moves enough bytes (Hkv * block * head_dim each) to be worth its fixed
# cost, and a row's dead steps are table_width / _STEP_BLOCKS at most
_STEP_BLOCKS = 8
# what a step's pool tiles may take of VMEM, the pipeline's second buffer
# counted: half of the 16 MiB a v5e scopes to one kernel, the other half left
# to q, out, the scratch and the kernel's own values
_STEP_BYTES = 8 * 2**20


def _step_blocks(k, v, tables) -> int:
    """Table blocks a grid step of :func:`_paged_attend` takes at these
    shapes: ``_STEP_BLOCKS``, or as many fewer as fit ``_STEP_BYTES`` where
    the tiles are large (many kv heads, float32 pools, long blocks). 0 where
    not even one block fits: the form :func:`_paged_scan` keeps."""
    tile = math.prod(k.shape[1:]) * k.dtype.itemsize * (1 if v is None else 2)
    return min(_STEP_BLOCKS, tables.shape[1], _STEP_BYTES // (2 * tile))


def _paged_kernel(len_ref, ids_ref, q_ref, *refs, scale, block, n, kv_heads,
                  rep, queries, v_width, shared_kv, quant):
    """One grid step = one row, all of its kv heads, ``n`` table blocks.

    ``refs``: the ``n`` key tiles ``[1, Hkv, block, hd]`` the index maps
    steered through ``ids``, the ``n`` value tiles (none when
    ``shared_kv``: the values are the keys' first ``v_width`` columns), the
    two scale tables in SMEM when ``quant`` (laid out as ``ids`` is: entry
    ``[(b, head), j * n + i]`` is the scale of tile ``i`` of step ``j``),
    the output and the scratch.

    A row's chunks of ``n`` blocks are walked from the table's END: the
    steps past the row's last live chunk come first, name that chunk's
    tiles (so the pipeline fetches them while the row before still
    computes, and fetches nothing more until the row's own first live
    step has run) and compute nothing. Tiles of a live step that lie past
    the row's last block are copies of that block, every position masked."""
    k_refs, refs = refs[:n], refs[n:]
    if not shared_kv:
        v_refs, refs = refs[:n], refs[n:]
    ksc_ref = vsc_ref = None
    if quant:
        (ksc_ref, vsc_ref), refs = refs[:2], refs[2:]
    o_ref, acc, m_sc, l_sc = refs
    b, j = pl.program_id(0), pl.program_id(1)
    first = (pl.num_programs(1) - 1 - j) * n        # this step's first table block
    row_len = len_ref[b]

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _NEG)
        l_sc[:] = jnp.zeros_like(l_sc)

    def tiles(t_refs, sc_ref, x):
        """Head ``x``'s ``[n * block, hd]`` of this step, dequantized per
        block through its scale (one SMEM scalar) when the pool is
        quantized — the wide cache never exists outside registers."""
        if sc_ref is None:
            return jnp.concatenate([r[0, x] for r in t_refs], axis=0)
        return jnp.concatenate([
            (r[0, x].astype(jnp.float32) * sc_ref[b * kv_heads + x, j * n + i]
             ).astype(q_ref.dtype)
            for i, r in enumerate(t_refs)
        ], axis=0)

    @pl.when(first * block < row_len)
    def _attend():
        for x in range(kv_heads):
            q = q_ref[0, x]                                 # [G * rep, hd]
            k = tiles(k_refs, ksc_ref, x)
            v = k[:, :v_width] if shared_kv else tiles(v_refs, vsc_ref, x)
            s = lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                       # [G * rep, n * block]
            pos = first * block + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            # folded row r is query g = r // rep: speculative query g sees
            # positions < row_len - (G-1) + g (row_len counts the cache AFTER
            # all G writes; G=1 is the plain < row_len rule)
            gq = lax.broadcasted_iota(jnp.int32, s.shape, 0) // rep
            valid = pos < row_len - (queries - 1) + gq
            s = jnp.where(valid, s, _NEG)
            m_prev = m_sc[x]                                # [G * rep, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_sc[x] = l_sc[x] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc[x] = acc[x] * corr + lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_sc[x] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc[:] / jnp.maximum(l_sc[:], 1e-30)).astype(o_ref.dtype)


def _paged_attend(q, k, v, lengths, tables, *, n, scale, k_scale=None,
                  v_scale=None, v_width=0):
    """The paged form as one kernel: grid ``(B, ceil(M / n))``; lengths
    and ``ids`` as scalar prefetch. The pool is handed over ``n`` times
    (twice that with a value pool); operand ``i``'s index map names pool
    block ``ids[b, j * n + i]``: table entry ``min(min(c, last // n) * n +
    i, last)`` of row ``b`` for the step's chunk ``c``, ``last =
    (lengths[b] - 1) // block`` the row's last live block — so a row
    fetches its own live blocks (rounded up to ``n``) and nothing else,
    whatever the table's width, and no shape depends on the lengths.
    (``ids`` is worked out here, vectorised, because the pipeline evaluates
    every operand's index map at every step, dead ones too: scalar work the
    kernel's fixed cost is made of.) Same mathematics as
    :func:`_paged_scan` (whose arguments these are): operands in the pool's
    stated dtype, float32 scores and softmax, ``p`` cast before p.v.

    Quantized pools: the scales of the blocks ``ids`` names are gathered
    here (``B * Hkv * M`` floats — the size of the attention work, not of
    the pool) and ride whole in SMEM (a (1, 1) VMEM tile per scale breaks
    the TPU's (8, 128) tiling rule)."""
    B, G, H, hd = q.shape
    Hkv, blk = k.shape[1], k.shape[2]
    rep = H // Hkv
    M = tables.shape[1]
    R = G * rep
    steps = pl.cdiv(M, n)
    shared_kv = v is None
    vd = v_width if shared_kv else hd
    quant = k_scale is not None
    qf = q.reshape(B, G, Hkv, rep, hd).transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, R, hd
    )

    last = jnp.clip((lengths.astype(jnp.int32) - 1) // blk, 0, M - 1)[:, None, None]
    chunk = jnp.minimum(                  # [B, steps, 1], from the table's end
        (steps - 1 - jnp.arange(steps, dtype=jnp.int32))[:, None], last // n)
    walk = jnp.minimum(chunk * n + jnp.arange(n, dtype=jnp.int32), last)
    ids = jnp.take_along_axis(            # [b, j * n + i]: pool block to fetch
        tables.astype(jnp.int32), walk.reshape(B, steps * n), axis=1)

    def kv_spec(i):
        return pl.BlockSpec(
            (1, Hkv, blk, hd), lambda b, j, ln, ids: (ids[b, j * n + i], 0, 0, 0),
        )

    def row_scales(pool):  # [P, Hkv] -> [B * Hkv, steps * n], row = (b, kv head)
        return pool[ids].transpose(0, 2, 1).reshape(B * Hkv, steps * n).astype(
            jnp.float32
        )

    kv_specs = [kv_spec(i) for i in range(n)]
    in_specs = [pl.BlockSpec((1, Hkv, R, hd), lambda b, j, ln, ids: (b, 0, 0, 0))]
    in_specs += kv_specs
    operands = [qf] + [k] * n
    if not shared_kv:
        in_specs += kv_specs
        operands += [v] * n
    if quant:
        in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM)] * 2
        operands += [row_scales(k_scale), row_scales(v_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hkv, R, vd), lambda b, j, ln, ids: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, R, vd), jnp.float32),
            pltpu.VMEM((Hkv, R, 1), jnp.float32),
            pltpu.VMEM((Hkv, R, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, scale=scale, block=blk, n=n, kv_heads=Hkv, rep=rep,
            queries=G, v_width=vd, shared_kv=shared_kv, quant=quant,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, vd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=_use_interpret(),
        name="paged_decode_attention",
    )(lengths.astype(jnp.int32), ids, *operands)
    return out.reshape(B, Hkv, G, rep, vd).transpose(0, 2, 1, 3, 4).reshape(
        B, G, H, vd
    )


def _run_kernel() -> bool:
    """Whether the paged form may run :func:`_paged_attend`: where the
    platform is a TPU. Elsewhere :func:`_paged_scan` does the work; a test
    that wants the kernel interpreted on the CPU steers this name, as
    tests/test_tpu_compile.py steers ``_use_interpret``."""
    return not _use_interpret()


def _paged(q, k, v, lengths, tables, **kw):
    """Kernel or scan, from the platform and the shapes: the scan off the
    TPU, and for a pool one block of which overruns a step's VMEM."""
    n = _step_blocks(k, v, tables) if _run_kernel() else 0
    if n:
        return _paged_attend(q, k, v, lengths, tables, n=n, **kw)
    return _paged_scan(q, k, v, lengths, tables, **kw)


# --- public entry -------------------------------------------------------------


def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths: jax.Array,
    *,
    tables: jax.Array | None = None,
    impl: str = "scan",
    block: int = 128,
    scale: float | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """One decode step of attention at native GQA width.

    Contiguous form (``tables`` is None): q: [B, H, head_dim] (this step's
    query rows); k/v: [B, Hkv, T, head_dim] head-major caches (T = the
    active capacity, a multiple of ``block``); lengths: [B] int32 — row b
    attends positions ``[0, lengths[b])``. Returns [B, H, head_dim].

    Paged form (``tables [B, M]`` given): k/v are physical-block pools
    ``[P, Hkv, block, head_dim]`` and row b's logical block j lives at
    ``tables[b, j]`` — the serve engine's copy-on-write sharing substrate
    (serve/cache.py, serve/prefix.py). Entries beyond a row's length must
    still be valid pool ids (the engine points them at the scratch block).
    ``impl`` is not read here: the kernel on a TPU, the scan elsewhere
    (:func:`_run_kernel`).

    Speculative form: q ``[B, G, H, head_dim]`` verifies G query positions
    per row in one call (serve/spec.py) — ``lengths`` counts the cache
    AFTER all G writes, and query g of row b attends positions
    ``< lengths[b] - (G - 1) + g`` (for G=1 exactly the one-token rule).
    Returns [B, G, H, head_dim]. Works in both contiguous and paged form;
    every implementation folds the G positions into the existing tile
    rows, so the per-step K/V traffic does not grow with G.

    Quantized paged form (``k_scale``/``v_scale [P, Hkv]`` given, paged
    only): the pools hold int8 or fp8 payloads quantized per physical
    block per kv-head (serve/cache.py); scan and kernel dequantize each
    tile inline — the scan gathers the scale row next to the block gather,
    the kernel reads the table-gathered scales as scalars from SMEM.
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, G, H, hd = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    if impl not in ("scan", "pallas"):
        raise ValueError(f"unknown decode impl {impl!r} (expected scan | pallas)")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    if k_scale is not None and tables is None:
        raise ValueError(
            "quantized decode_attention requires the paged form (tables)"
        )
    if tables is not None:
        if k.shape != v.shape or k.shape[3] != hd:
            raise ValueError(
                f"paged decode_attention shapes q={q.shape} k={k.shape} "
                f"v={v.shape}"
            )
        if tables.shape[0] != B:
            raise ValueError(
                f"tables rows {tables.shape[0]} != batch {B}"
            )
        if H % k.shape[1]:
            raise ValueError(
                f"n_heads {H} not a multiple of n_kv_heads {k.shape[1]}"
            )
        out = _paged(
            q, k, v, lengths, tables, scale=scale,
            k_scale=k_scale, v_scale=v_scale,
        )
        return out[:, 0] if squeeze else out
    if k.shape != v.shape or k.shape[0] != B or k.shape[3] != hd:
        raise ValueError(f"decode_attention shapes q={q.shape} k={k.shape} v={v.shape}")
    Hkv, T = k.shape[1], k.shape[2]
    if H % Hkv:
        raise ValueError(f"n_heads {H} not a multiple of n_kv_heads {Hkv}")
    blk = min(block, T)
    if T % blk:
        raise ValueError(f"cache length {T} must be a multiple of block {blk}")
    if impl == "pallas":
        out = _decode_pallas(q, k, v, lengths, scale=scale, block=blk)
    else:
        out = _decode_scan(q, k, v, lengths, scale=scale, block=blk)
    return out[:, 0] if squeeze else out


__all__ = [
    "decode_attention", "latent_decode_attention",
    "reference_decode_attention",
]
