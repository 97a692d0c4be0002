"""Paged block KV cache: a refcounted physical-block pool + per-slot
indirection tables.

The first engine cache was slot-owns-contiguous-blocks: ``[L, S, Hkv, T, hd]``
with slot ``s`` owning positions ``[0, T)`` of its own row. That layout
cannot share anything — two requests with the same system/template prefix
each pay a full prefill and hold duplicate K/V. Here the cache is a **pool**
of physical blocks plus an indirection map (vLLM's paged KV, arXiv:2309.06180,
as the substrate for SGLang-style radix prefix sharing, arXiv:2312.07104):

- buffers are ``[L, P, Hkv, block, hd]`` head-major — ``P`` physical blocks,
  each holding ``block`` token positions across ALL layers (one allocation =
  one refcount covering every layer's K and V for that token span);
- a per-slot **block table** (host-planned, device-threaded through
  ops/decode_attention.py's scan and pallas impls) maps logical block ``j``
  of slot ``s`` to a physical block id — slots no longer own contiguous
  storage, so a physical block can appear in many tables at once;
- physical block **0 is the scratch block**: never allocated, dead slots'
  decode writes are steered into it so a freed (and possibly reallocated)
  block can never be corrupted by a stale slot;
- :class:`BlockPool` carries the host-side refcounts — a block is shared by
  construction (live slots + the prefix store each hold a reference) and
  returns to the free list only when its refcount hits zero, which is what
  lets ``shrink`` free real HBM without ever reclaiming a block the prefix
  store still pins;
- the pool grows by doubling and shrinks by halving (bounded decode-step
  recompiles, one per pool size), and attention cost scales with the
  *table width* (active blocks per slot), not ``max_len`` — the
  tests/test_perf_guard.py contract carries over from the contiguous
  layout unchanged.

The sharing policy itself (which blocks are safe to share, copy-on-write,
eviction) lives in serve/prefix.py; this module only knows physical blocks
and reference counts.

**Quantized pools** (ROADMAP quantized serving): with ``quant_kv`` set the
K/V pools store int8 (or fp8 ``e4m3``) and a parallel *scale pool*
``[L, P, Hkv]`` float32 carries one scale per physical block per kv head —
block granularity because the block is already the unit of allocation,
sharing, and copy-on-write, so a shared block carries its scales with it
and a COW copy duplicates exactly one scale row. Writes quantize in place
(:func:`scatter_block_kv` with ``k_scale`` given): the written positions'
amax folds into the running block scale, and when the scale grows the
block's existing entries requantize by ``old/new`` (exactly a no-op when
the scale is unchanged — round(q * 1.0) == q). A scale of zero marks a
block with no real content (freshly allocated, never written): requantizing
by ``0/new`` zeroes whatever garbage a reused block carried, so the engine
only has to zero the scale row at allocation, never the block itself.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# physical block 0 is reserved: dead slots' writes land here, and table
# entries beyond a slot's allocation point at it (their tiles are masked
# by the per-row length, but the DMA still needs a valid index)
SCRATCH_BLOCK = 0

# kv_dtype knob values -> (storage dtype, largest representable magnitude).
# fp8 e4m3 is the stretch format behind the same knob; it only registers
# where the jax build carries the dtype (kv_quant_spec raises otherwise).
KV_QUANT_DTYPES = ("int8", "fp8_e4m3")


def kv_quant_spec(kv_dtype: str) -> tuple[jnp.dtype, float]:
    """Resolve a ``serve.quant.kv_dtype`` value to (storage dtype, qmax)."""
    if kv_dtype == "int8":
        return jnp.dtype(jnp.int8), 127.0
    if kv_dtype == "fp8_e4m3":
        return jnp.dtype(jnp.float8_e4m3fn), 448.0
    raise ValueError(
        f"unknown kv quant dtype {kv_dtype!r} (expected one of "
        f"{KV_QUANT_DTYPES})"
    )


class PagedKVCache(NamedTuple):
    """k/v: [L, P, Hkv, block, hd] physical-block pools; lengths: [S].

    Quantized pools additionally carry ``k_scale``/``v_scale``
    ``[L, P, Hkv]`` float32 — one dequantization scale per physical block
    per kv head (None on an unquantized cache).

    A **latent cache** (the configuration's ``cache_layout``) is ONE pool: ``k`` is
    ``[L, P, 1, block, cache_width]`` — per token and layer the compressed
    vector every head's keys and values are expanded from, then the rope
    key all heads share, then zeros up to the lanes — and ``v`` is None (an empty pytree
    node: every program that takes the cache simply has no second pool).

    ``L`` is the configuration's ``cache_layers``: the layers that keep
    rows per token (all of them, or a hybrid's attention layers only).
    ``slot_state`` is the second kind of state a family may declare (its
    configuration's ``slot_state``): ``[layers, S, *shape]``, a fixed-size
    row per slot and layer that is not paged — None where the family
    declares none, and then no program has it."""

    k: jax.Array
    v: jax.Array
    lengths: jax.Array
    k_scale: jax.Array | None = None
    v_scale: jax.Array | None = None
    slot_state: jax.Array | None = None

    @property
    def n_blocks(self) -> int:
        """P — physical blocks currently backed (scratch included)."""
        return self.k.shape[1]

    @property
    def block(self) -> int:
        """Token positions per physical block."""
        return self.k.shape[3]

    @property
    def slots(self) -> int:
        return self.lengths.shape[0]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def map_pools(fn, cache: PagedKVCache, *rest):
    """``fn`` over the payload pools that exist — ``(k, v)``, or ``(k,
    None)`` for a latent cache (``None`` is an empty pytree node, so the
    second pool is simply skipped) — each with its entry of every ``rest``
    pair (``(pk, pv)``, the scale pools ...). Returns the pair. The one
    place that knows a cache may hold one pool; callers write the per-pool
    operation once."""
    return jax.tree.map(fn, (cache.k, cache.v), *rest)


def map_scales(fn, cache: PagedKVCache):
    """``fn`` over the scale pools ``(k_scale, v_scale)``; ``(None, None)``
    on an unquantized cache."""
    return jax.tree.map(fn, (cache.k_scale, cache.v_scale))


def map_cache(fn, cache: PagedKVCache) -> PagedKVCache:
    """``cache`` with ``fn`` applied to every pool and scale pool it holds
    (all are ``[L, P, ...]``); the lengths and the per-slot state as they
    are."""
    return PagedKVCache(
        *map_pools(fn, cache), cache.lengths, *map_scales(fn, cache),
        cache.slot_state,
    )


def create_cache(
    cfg, slots: int, n_blocks: int, block: int, dtype=None,
    quant_kv: str = "",
) -> PagedKVCache:
    """Fresh pool of ``n_blocks`` physical blocks (block 0 = scratch), laid
    out as the configuration's ``cache_layout`` says. With ``quant_kv``
    ('int8' | 'fp8_e4m3') the pools store the quantized dtype plus zeroed
    per-block-per-head scale pools (scale 0 = block holds nothing real
    yet). A configuration that declares a per-slot state gets it zeroed,
    ``[layers, slots, *shape]``."""
    heads, width, pools = cfg.cache_layout
    if quant_kv and pools == 1:
        raise NotImplementedError("quant_kv: a latent cache has no quantized form")
    shape = (cfg.cache_layers, n_blocks, heads, block, width)
    dt = kv_quant_spec(quant_kv)[0] if quant_kv else dtype or cfg.dtype
    sc = (cfg.cache_layers, n_blocks, heads)
    state = None
    if cfg.slot_state is not None:
        layers, row, state_dtype = cfg.slot_state
        state = jnp.zeros((layers, slots, *row), state_dtype)
    return PagedKVCache(
        jnp.zeros(shape, dt),
        jnp.zeros(shape, dt) if pools == 2 else None,
        jnp.zeros((slots,), jnp.int32),
        jnp.zeros(sc, jnp.float32) if quant_kv else None,
        jnp.zeros(sc, jnp.float32) if quant_kv else None,
        state,
    )


def slot_state_bytes(cfg, slots: int) -> int:
    """HBM bytes of the per-slot state ``slots`` slots keep (0 where the
    configuration declares none)."""
    if cfg.slot_state is None:
        return 0
    layers, row, dtype = cfg.slot_state
    return layers * slots * math.prod(row) * jnp.dtype(dtype).itemsize


def grow_cache(cache: PagedKVCache, n_blocks: int) -> PagedKVCache:
    """Extend the pool to ``n_blocks`` physical blocks (zero-filled)."""
    extra = n_blocks - cache.n_blocks
    if extra <= 0:
        return cache
    return map_cache(
        lambda a: jnp.pad(a, [(0, 0), (0, extra)] + [(0, 0)] * (a.ndim - 2)),
        cache,
    )


def shrink_cache(cache: PagedKVCache, n_blocks: int) -> PagedKVCache:
    """Release physical blocks beyond ``n_blocks``. The caller guarantees
    every id >= ``n_blocks`` is FREE (``BlockPool.shrink_target`` reports
    the lowest safe size — a block pinned by the prefix store or a live
    slot bounds how far the pool can shrink)."""
    if n_blocks >= cache.n_blocks:
        return cache
    return map_cache(lambda a: a[:, :n_blocks], cache)


def blocks_for(length: int, block: int) -> int:
    """ceil(length / block), minimum 1."""
    return max(1, math.ceil(length / block))


def quantize_values(vals: jax.Array, scale: jax.Array, qmax: float,
                    qdtype) -> jax.Array:
    """``vals / scale`` clipped to the quantized range (rounded for integer
    storage; fp8 rounds in the cast). ``scale`` broadcasts against
    ``vals``; a zero scale maps everything to zero (nothing real stored)."""
    q = vals.astype(jnp.float32) / jnp.maximum(scale, 1e-30)
    q = jnp.clip(q, -qmax, qmax)
    # branch is on the STATIC storage dtype, not a traced value
    if jnp.issubdtype(jnp.dtype(qdtype), jnp.integer):  # graft-lint: disable=GL002
        q = jnp.round(q)
    return q.astype(qdtype)


def dequantize_values(q: jax.Array, scale: jax.Array, out_dtype) -> jax.Array:
    """Stored values back to real ones: ``q * scale`` (broadcast)."""
    return (q.astype(jnp.float32) * scale).astype(out_dtype)


def _rescale_stored(q: jax.Array, factor: jax.Array, qmax: float) -> jax.Array:
    """Requantize stored values by ``factor = old_scale / new_scale``
    (broadcast). factor == 1 is exact (round(q * 1.0) == q for every
    representable q); factor == 0 zeroes a block whose scale was 0 —
    garbage in a freshly allocated block never survives its first write."""
    f = q.astype(jnp.float32) * factor
    f = jnp.clip(f, -qmax, qmax)
    # branch is on the STATIC storage dtype, not a traced value
    if jnp.issubdtype(q.dtype, jnp.integer):  # graft-lint: disable=GL002
        f = jnp.round(f)
    return f.astype(q.dtype)


def _set_rows(pool: jax.Array, rows: jax.Array, pids: jax.Array,
              offs: jax.Array | None = None) -> jax.Array:
    """``rows[i]`` lands in block ``pids[i]`` of ``pool`` — the whole
    block (``rows [n, *pool.shape[1:]]``), or with ``offs`` its position
    ``offs[i]`` on axis 2 only (``rows [n, Hkv, 1, hd]``) — as one
    ``dynamic_update_slice`` per row, applied in order. The row count is
    static and small (slots x draft positions), and a chain of slice
    updates is the form XLA performs in place on a donated or
    loop-carried buffer: the per-row window touches nothing else, so no
    copy or relayout of ``pool`` is needed to apply it (a gather/scatter
    with index dims on non-adjacent axes makes the compiler relayout the
    whole pool around the write; tests/test_perf_guard.py holds the
    difference). Duplicate targets: the last row wins."""
    n = pids.shape[0]
    z = jnp.int32(0)
    off_l = jnp.unstack(offs) if offs is not None else [z] * n
    for row, pid, off in zip(jnp.split(rows, n), jnp.unstack(pids), off_l):
        # start = (block, head, position, value); a scale pool has two axes
        start = (pid, z, off, z)[:pool.ndim]
        pool = lax.dynamic_update_slice(pool, row, start)
    return pool


def _quant_write_rows(pool, scale, new, pids, offs, qmax):
    """One quantized position-per-row write: ``new [S, Hkv, hd]`` lands at
    ``(pids[s], offs[s])``. Gather the touched blocks + scales, fold the
    written amax into the running block scale, requantize the existing
    entries by old/new, insert the quantized row, write both back (whole
    blocks and scale rows, one in-place slice update each)."""
    S = new.shape[0]
    blk = jnp.take(pool, pids, axis=0)                  # [S, Hkv, blk, hd]
    sc = jnp.take(scale, pids, axis=0)                  # [S, Hkv]
    amax = jnp.max(jnp.abs(new.astype(jnp.float32)), axis=-1)   # [S, Hkv]
    sc_new = jnp.maximum(sc, amax / qmax)
    factor = jnp.where(sc_new > 0, sc / jnp.maximum(sc_new, 1e-30), 0.0)
    blk = _rescale_stored(blk, factor[..., None, None], qmax)
    row = quantize_values(new, sc_new[..., None], qmax, pool.dtype)
    # advanced indices (rows on axis 0, offs on axis 2) are non-adjacent:
    # the indexed result moves them to the front — exactly row's layout
    blk = blk.at[jnp.arange(S), :, offs, :].set(row)
    # duplicate pids occur only for scratch-steered rows (dead slots,
    # padding) — scratch content is garbage by contract, any winner is fine
    return _set_rows(pool, blk, pids), _set_rows(scale, sc_new, pids)


def scatter_block_kv(pool: jax.Array, new: jax.Array, pids: jax.Array,
                     offs: jax.Array, scale: jax.Array | None = None,
                     qmax: float = 127.0):
    """Paged KV write into a ``[N, Hkv, block, hd]`` pool of physical
    blocks: one layer's ``[P, ...]`` slab, or the whole cache viewed as
    ``[L * P, ...]`` with the layer's offset ``l * P`` already added to
    ``pids`` (how the decode steps carry it through their layer scan,
    ``scan_layers_paged``).

    **Contract.** The pool is the caller's donated or loop-carried
    buffer and comes back as the SAME buffer: the write touches the
    ``Hkv x hd`` values of each named ``(block, offset)`` and nothing
    else, as a chain of ``dynamic_update_slice`` (:func:`_set_rows`), so
    the compiler neither copies nor relayouts the pool for it. ``pids``
    and ``offs`` must be in range (a slice update clamps where a scatter
    would drop); entries that must not land anywhere real (dead slots,
    padding beyond a row's draft length) are the CALLER's job to steer to
    ``SCRATCH_BLOCK`` — of the layer being written, i.e. *before* the
    layer offset is added, so block ``l * P`` takes them. Rows are
    applied in order; duplicate targets occur only among scratch-steered
    rows, whose content is garbage by contract.

    With 1-D ``[S]`` indices ``new`` is ``[S, Hkv, hd]`` (the classic
    one-token decode step); with 2-D ``[S, G]`` indices it is
    ``[S, G, Hkv, hd]`` — the speculative multi-position write
    (serve/spec.py): row s's G draft positions.

    With ``scale`` given (a quantized pool's ``[N, Hkv]`` scale rows, same
    leading axis as ``pool``) the write QUANTIZES: the written positions'
    amax folds into the running block scale, existing entries requantize
    by old/new (so the unit written back is the whole block), and the
    return value is ``(pool, scale)``. The speculative 2-D form applies
    the G positions as G sequential single-position passes (G is small
    and static) so two writes into the same block compound their scale
    updates correctly."""
    if scale is None:
        rows = new.reshape(-1, new.shape[-2], 1, new.shape[-1])
        return _set_rows(pool, rows, pids.reshape(-1), offs.reshape(-1))
    if pids.ndim == 1:
        return _quant_write_rows(pool, scale, new, pids, offs, qmax)
    for g in range(pids.shape[1]):
        pool, scale = _quant_write_rows(
            pool, scale, new[:, g], pids[:, g], offs[:, g], qmax
        )
    return pool, scale


def quant_scatter_span(pool, scale, new, pids, offs, ub, qmax):
    """Quantized prefill-span write into ONE layer's pool: position ``i``
    of ``new [Hkv, W, hd]`` lands at ``(pids[i], offs[i])``. ``ub`` is the
    touched-block id set (host-computed ``np.unique`` of ``pids``, padded
    with scratch to a static width) — the requantization pass runs once
    per touched block, not once per position. Scale updates use a
    scatter-max so many positions landing in one block fold their amaxes
    correctly in a single pass. Returns ``(pool, scale)``.

    Vmapped over layers by the engine's scatter step (serve/engine.py):
    the per-layer form keeps the gathered requant transient at one layer's
    touched blocks."""
    needed = jnp.max(jnp.abs(new.astype(jnp.float32)), axis=-1) / qmax
    # needed [Hkv, W] -> per-block running max via scatter-max (dup-safe)
    sc_new = scale.at[pids, :].max(needed.T)            # [P, Hkv]
    old_ub = jnp.take(scale, ub, axis=0)                # [nU, Hkv]
    new_ub = jnp.take(sc_new, ub, axis=0)
    factor = jnp.where(new_ub > 0, old_ub / jnp.maximum(new_ub, 1e-30), 0.0)
    blk = jnp.take(pool, ub, axis=0)                    # [nU, Hkv, blk, hd]
    blk = _rescale_stored(blk, factor[..., None, None], qmax)
    # duplicate ub entries are only the scratch padding — identical values
    pool = pool.at[ub].set(blk)
    sc_pos = jnp.take(sc_new, pids, axis=0)             # [W, Hkv]
    row = quantize_values(
        new.transpose(1, 0, 2), sc_pos[..., None], qmax, pool.dtype
    )                                                   # [W, Hkv, hd]
    return pool.at[pids, :, offs, :].set(row), sc_new


class BlockPayload(NamedTuple):
    """Host-side copy of whole physical blocks — the unit of the blockwise
    KV handoff (docs/SERVE.md "Disaggregated serving"). ``k``/``v`` are
    ``[L, n, Hkv, block, hd]`` in the pool's STORAGE dtype (quantized
    payload ships as stored, never dequantized), and on a quantized pool
    ``k_scale``/``v_scale`` ``[L, n, Hkv]`` float32 ride along — a block
    without its scale rows is not decodable, so they travel as one unit."""

    k: np.ndarray
    v: np.ndarray
    k_scale: np.ndarray | None = None
    v_scale: np.ndarray | None = None

    @property
    def n_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def nbytes(self) -> int:
        n = self.k.nbytes + self.v.nbytes
        if self.k_scale is not None:
            n += self.k_scale.nbytes + self.v_scale.nbytes
        return n


def export_blocks(cache: PagedKVCache, pids) -> BlockPayload:
    """Gather physical blocks ``pids`` to the host as a BlockPayload.

    The gather pads the id list to a power of two with scratch (bounded
    device-gather signatures, same policy as the engine's context gather)
    and trims on the host. One explicit D2H per call — the handoff is a
    designed sync point on the prefill host, never on a decode step."""
    nb = len(pids)
    pad = 1
    while pad < nb:
        pad *= 2
    padded = np.full(pad, SCRATCH_BLOCK, np.int32)
    padded[:nb] = pids
    idx = jnp.asarray(padded)
    k, v = _gather_blocks_fn(cache.quantized)(cache, idx)
    if cache.quantized:
        (k, ks), (v, vs) = k, v
        return BlockPayload(
            np.asarray(jax.device_get(k))[:, :nb],
            np.asarray(jax.device_get(v))[:, :nb],
            np.asarray(jax.device_get(ks))[:, :nb],
            np.asarray(jax.device_get(vs))[:, :nb],
        )
    return BlockPayload(
        np.asarray(jax.device_get(k))[:, :nb],
        np.asarray(jax.device_get(v))[:, :nb],
    )


@functools.lru_cache(maxsize=None)
def _gather_blocks_fn(quant: bool = False):
    if quant:
        @jax.jit
        def gat_q(cache: PagedKVCache, idx):
            return (
                (jnp.take(cache.k, idx, axis=1),
                 jnp.take(cache.k_scale, idx, axis=1)),
                (jnp.take(cache.v, idx, axis=1),
                 jnp.take(cache.v_scale, idx, axis=1)),
            )
        return gat_q

    @jax.jit
    def gat(cache: PagedKVCache, idx):
        return jnp.take(cache.k, idx, axis=1), jnp.take(cache.v, idx, axis=1)
    return gat


def write_block(cache: PagedKVCache, pid: int, payload: BlockPayload,
                i: int) -> PagedKVCache:
    """Adopt block ``i`` of ``payload`` into physical block ``pid``: the
    decode-host side of the handoff. Payload dtype/shape must match the
    pool exactly (checked by the caller via :func:`payload_compatible`) —
    adoption is a raw store, scale rows included, so a shipped quantized
    block decodes bit-identically to the block the prefill host held."""
    if cache.quantized:
        return _write_block_fn(True)(
            cache, jnp.int32(pid),
            jnp.asarray(payload.k[:, i]), jnp.asarray(payload.v[:, i]),
            jnp.asarray(payload.k_scale[:, i]),
            jnp.asarray(payload.v_scale[:, i]),
        )
    return _write_block_fn(False)(
        cache, jnp.int32(pid),
        jnp.asarray(payload.k[:, i]), jnp.asarray(payload.v[:, i]),
    )


@functools.lru_cache(maxsize=None)
def _write_block_fn(quant: bool = False):
    if quant:
        @jax.jit
        def wr_q(cache: PagedKVCache, pid, kb, vb, ks, vs):
            return cache._replace(
                k=cache.k.at[:, pid].set(kb),
                v=cache.v.at[:, pid].set(vb),
                k_scale=cache.k_scale.at[:, pid].set(ks),
                v_scale=cache.v_scale.at[:, pid].set(vs),
            )
        return wr_q

    @jax.jit
    def wr(cache: PagedKVCache, pid, kb, vb):
        return cache._replace(
            k=cache.k.at[:, pid].set(kb), v=cache.v.at[:, pid].set(vb)
        )
    return wr


def payload_compatible(cache: PagedKVCache, payload: BlockPayload) -> str:
    """'' when ``payload`` can be adopted into ``cache`` verbatim, else
    the reason it cannot (dtype or geometry mismatch — a bf16 host must
    not adopt int8 blocks and silently decode garbage)."""
    want = cache.k.shape[:1] + cache.k.shape[2:]
    got = payload.k.shape[:1] + payload.k.shape[2:]
    if want != got:
        return f"block geometry {got} != pool {want}"
    if jnp.dtype(payload.k.dtype) != jnp.dtype(cache.k.dtype):
        return f"payload dtype {payload.k.dtype} != pool {cache.k.dtype}"
    if cache.quantized and payload.k_scale is None:
        return "quantized pool needs scale rows in the payload"
    if not cache.quantized and payload.k_scale is not None:
        return "unquantized pool cannot adopt scaled payload"
    return ""


def pack_payload(payload: BlockPayload) -> dict:
    """BlockPayload -> wire fields (raw bytes + shape + dtype name), the
    ShipBlocks request body. ``np.tobytes`` round-trips every storage
    dtype bit-exactly (bfloat16/fp8 via their ml_dtypes registrations)."""
    d = {
        "k": payload.k.tobytes(), "v": payload.v.tobytes(),
        "shape": list(payload.k.shape), "dtype": jnp.dtype(payload.k.dtype).name,
    }
    if payload.k_scale is not None:
        d["k_scale"] = np.ascontiguousarray(
            payload.k_scale, np.float32).tobytes()
        d["v_scale"] = np.ascontiguousarray(
            payload.v_scale, np.float32).tobytes()
    return d


def unpack_payload(k: bytes, v: bytes, shape, dtype: str,
                   k_scale: bytes = b"", v_scale: bytes = b"") -> BlockPayload:
    """Wire fields -> BlockPayload (the ShipBlocks server side). Raises
    ValueError on a malformed body — the RPC layer maps it to an error
    response instead of corrupting the pool."""
    shape = tuple(int(s) for s in shape)
    if len(shape) != 5:
        raise ValueError(f"payload shape {shape} is not [L, n, Hkv, blk, hd]")
    dt = jnp.dtype(dtype)
    n = int(np.prod(shape))
    if len(k) != n * dt.itemsize or len(v) != n * dt.itemsize:
        raise ValueError(
            f"payload bytes {len(k)}/{len(v)} do not match shape {shape} "
            f"dtype {dtype}"
        )
    ka = np.frombuffer(k, dtype=dt).reshape(shape)
    va = np.frombuffer(v, dtype=dt).reshape(shape)
    if not k_scale:
        return BlockPayload(ka, va)
    sshape = shape[:3]
    sn = int(np.prod(sshape)) * 4
    if len(k_scale) != sn or len(v_scale) != sn:
        raise ValueError(f"scale bytes do not match shape {sshape}")
    return BlockPayload(
        ka, va,
        np.frombuffer(k_scale, dtype=np.float32).reshape(sshape),
        np.frombuffer(v_scale, dtype=np.float32).reshape(sshape),
    )


def scan_layers_paged(layer_fn, x, layers, cache: PagedKVCache,
                       span: tuple[int, int] | None = None):
    """Run ``layer_fn`` over the stacked layer weights with the paged
    pools CARRIED through the scan — the one pool discipline of the
    decode programs (plain and speculative, quantized or not).

    The cache's ``[L, P, ...]`` pools (and scale pools) are viewed as
    ``[L * P, ...]`` (a bitcast of the donated argument) and ride the
    scan's carry; its ``xs`` are the layer weights and the layer's block
    offset ``l * P`` only. ``layer_fn(x, lp, pools, base)`` adds ``base``
    to the block ids it writes (``scatter_block_kv``) and to the table it
    attends through, so layer ``l`` reads and writes blocks
    ``[l * P, (l + 1) * P)`` — its own scratch block is ``l * P``, which is
    where a dead slot's ``SCRATCH_BLOCK`` lands after the offset. Carried
    and written by slice updates, the pool is one buffer from the
    program's donated argument to its result. Do NOT hand the pools to the
    scan as ``xs`` and take them back as stacked ``ys``: those are two
    buffers of the loop, so every layer's slab is sliced out, relaid and
    re-stacked — several pool-sized copies a step, 19 ms of a 48 ms step
    on the chip (PERF.md §6, PR 26). ``pools`` is ``(k, v, k_scale, v_scale)``, the
    scales ``None`` on an unquantized cache; returns ``(x, pools)`` with
    the pools back in the cache's ``[L, P, ...]`` shape.

    ``span = (lo, hi)``: ``layers`` is the stack of layers ``lo .. hi - 1``
    of a model whose layers are not one stack (leading dense layers, then
    expert layers: serve/latent.py scans each over the same carried pool)."""
    L, P = cache.k.shape[:2]
    lo, hi = span or (0, L)
    # scale pools are None on an unquantized cache: an empty pytree node,
    # so one 4-tuple serves both kinds through the scan's carry
    pools = (cache.k, cache.v, cache.k_scale, cache.v_scale)
    flat = jax.tree.map(lambda a: a.reshape(L * P, *a.shape[2:]), pools)

    def body(carry, layer):
        x, pools = carry
        lp, base = layer
        x, pools = layer_fn(x, lp, pools, base)
        return (x, pools), None

    bases = jnp.arange(lo, hi, dtype=jnp.int32) * P
    (x, flat), _ = lax.scan(body, (x, flat), (layers, bases))
    return x, jax.tree.map(lambda a, full: a.reshape(full.shape), flat, pools)


def block_bytes(cfg, block: int, dtype=None, quant_kv: str = "") -> int:
    """HBM bytes one physical block costs (K + V across the layers the pool
    holds, ``cfg.cache_layers``). With ``quant_kv`` the payload is priced at
    the quantized dtype plus the block's two scale rows (K and V, float32
    per layer per head)."""
    if quant_kv:
        qdt, _ = kv_quant_spec(quant_kv)
        payload = (2 * cfg.cache_layers * cfg.n_kv_heads * block * cfg.head_dim
                   * qdt.itemsize)
        scales = 2 * cfg.cache_layers * cfg.n_kv_heads * 4
        return payload + scales
    dt = jnp.dtype(dtype or cfg.dtype)
    heads, width, pools = cfg.cache_layout
    return pools * cfg.cache_layers * heads * block * width * dt.itemsize


class BlockPool:
    """Host-side refcounted allocator over physical block ids.

    Pure bookkeeping — no device arrays, no locks (the engine thread is
    the only mutator; see serve/engine.py). A block id is *live* while its
    refcount is positive: live slots hold one reference per table entry,
    and the prefix store holds one per radix node. ``release`` returns a
    block to the free list only at refcount zero — a freed slot therefore
    returns only the blocks nothing else (the store, another slot) still
    references.
    """

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError("pool needs the scratch block plus one")
        self._ref = [0] * n_blocks
        # LIFO free list (reuse-warm blocks first); scratch never enters
        self._free = list(range(n_blocks - 1, SCRATCH_BLOCK, -1))

    @property
    def n_blocks(self) -> int:
        return len(self._ref)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        """Blocks with a positive refcount (scratch excluded)."""
        return self.n_blocks - 1 - self.n_free

    def alloc(self) -> int | None:
        """Pop a free block with refcount 1, or None when exhausted (the
        caller decides whether to grow the pool or evict from the store)."""
        if not self._free:
            return None
        pid = self._free.pop()
        self._ref[pid] = 1
        return pid

    def retain(self, pid: int) -> None:
        if pid == SCRATCH_BLOCK:
            raise ValueError("cannot retain the scratch block")
        if self._ref[pid] <= 0:
            raise ValueError(f"retain of free block {pid}")
        self._ref[pid] += 1

    def refcount(self, pid: int) -> int:
        return self._ref[pid]

    def release(self, pid: int) -> bool:
        """Drop one reference; True when the block returned to the free
        list (refcount hit zero)."""
        if pid == SCRATCH_BLOCK:
            raise ValueError("cannot release the scratch block")
        if self._ref[pid] <= 0:
            raise ValueError(f"release of free block {pid}")
        self._ref[pid] -= 1
        if self._ref[pid] == 0:
            self._free.append(pid)
            return True
        return False

    def grow(self, n_blocks: int) -> None:
        """Extend to ``n_blocks`` ids (mirrors :func:`grow_cache`)."""
        cur = self.n_blocks
        if n_blocks <= cur:
            return
        self._ref.extend([0] * (n_blocks - cur))
        self._free.extend(range(n_blocks - 1, cur - 1, -1))

    def shrink_target(self, floor: int = 2) -> int:
        """Lowest pool size every live block still fits in: one past the
        highest id with a positive refcount. A block pinned high (e.g. by
        the prefix store) bounds how far :func:`shrink_cache` may go."""
        for pid in range(self.n_blocks - 1, SCRATCH_BLOCK, -1):
            if self._ref[pid] > 0:
                return max(pid + 1, floor)
        return floor

    def shrink(self, n_blocks: int) -> None:
        """Drop ids beyond ``n_blocks`` (all must be free — mirrors
        :func:`shrink_cache`'s contract)."""
        if n_blocks >= self.n_blocks:
            return
        if any(self._ref[pid] > 0 for pid in range(n_blocks, self.n_blocks)):
            raise ValueError("shrink below a live block")
        del self._ref[n_blocks:]
        self._free = [pid for pid in self._free if pid < n_blocks]


__all__ = [
    "KV_QUANT_DTYPES",
    "SCRATCH_BLOCK",
    "BlockPayload",
    "BlockPool",
    "PagedKVCache",
    "block_bytes",
    "blocks_for",
    "create_cache",
    "dequantize_values",
    "export_blocks",
    "grow_cache",
    "kv_quant_spec",
    "map_cache",
    "map_pools",
    "map_scales",
    "pack_payload",
    "payload_compatible",
    "quant_scatter_span",
    "quantize_values",
    "scan_layers_paged", "slot_state_bytes",
    "scatter_block_kv",
    "shrink_cache",
    "unpack_payload",
    "write_block",
]
