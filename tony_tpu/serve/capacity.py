"""Measured decode-slot budgets from the compiled step's memory plan.

bench.py's ``gqa_capacity`` used to size the slot budget as
``hbm * 0.92 - param_bytes`` — a hard-coded fragmentation guess standing in
for everything XLA actually allocates. This module replaces the guess with
XLA's own numbers: the decode step is AOT-lowered from shape avals (no
array is ever allocated) at two slot counts, and ``memory_analysis()``
splits the footprint into

- **param/argument bytes** — resident weights + cache (the block pools
  and a family's fixed-size per-slot state) + sampling state,
- **fixed temp** — per-step scratch independent of the slot count,
- **per-slot temp** — the marginal scratch one more slot costs (measured
  as the slot-count difference, so fused/fused-out buffers price
  themselves),
- **generated code** — the executable itself.

The slot budget is then arithmetic, not a fudge factor::

    slots = (hbm - params - fixed_temp - code) // (kv_per_slot + temp_per_slot)

ROADMAP items 4 (quantized serving) and 5 (elastic resize) size against
the same numbers — change the cache dtype or layout and the budget moves
because the *measured plan* moved.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from tony_tpu.models.llama import LlamaConfig
from tony_tpu.obs.compiles import aot_analysis
from tony_tpu.serve.cache import PagedKVCache, blocks_for, create_cache


def _param_avals(cfg: LlamaConfig):
    from tony_tpu.serve.engine import steps_for

    return jax.eval_shape(
        partial(steps_for(cfg).init_params, cfg=cfg), jax.random.key(0)
    )


def _tree_bytes(tree) -> int:
    return sum(
        int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
        for leaf in jax.tree.leaves(tree)
    )


def _cache_avals(cfg: LlamaConfig, slots: int, capacity: int,
                 kv_block: int, quant_kv: str = "") -> tuple[PagedKVCache, Any]:
    """Paged pool + table avals sized so every slot reaches ``capacity``
    positions privately (scratch block included) — the worst case the
    budget must cover; prefix sharing only ever reduces it. With
    ``quant_kv`` the pools carry the quantized storage dtype plus the
    per-block-per-head float32 scale pools, so the measured plan prices
    exactly what the quantized engine allocates."""
    blocks = blocks_for(capacity, kv_block)
    cache = jax.eval_shape(
        partial(create_cache, cfg, slots, 1 + slots * blocks, kv_block,
                quant_kv=quant_kv)
    )
    return cache, jax.ShapeDtypeStruct((slots, blocks), jnp.int32)


def _state_avals(slots: int):
    from tony_tpu.serve.engine import _SlotState

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    return _SlotState(
        last_tok=sds((slots,), jnp.int32),
        rng=sds((slots, 2), jnp.uint32),
        temp=sds((slots,), jnp.float32),
        top_k=sds((slots,), jnp.int32),
        top_p=sds((slots,), jnp.float32),
        eos=sds((slots,), jnp.int32),
        done=sds((slots,), bool),
        live=sds((slots,), bool),
    )


def decode_step_analysis(cfg: LlamaConfig, *, slots: int, capacity: int,
                         kv_block: int = 64, decode_impl: str = "scan",
                         max_top_k: int = 64,
                         quant_kv: str = "") -> dict[str, Any]:
    """Compile (avals only — nothing allocated, nothing executed) the serve
    engine's decode step and return its measured memory plan + FLOPs.
    ``quant_kv`` compiles the quantized-cache variant of the step (scale
    gathers + inline dequant included), so the plan is the quantized
    engine's plan, not the bf16 plan with a smaller dtype penciled in."""
    from tony_tpu.serve.engine import _decode_fn

    fn = _decode_fn(cfg, decode_impl, kv_block, max_top_k, False, quant_kv)
    params = _param_avals(cfg)
    cache, table = _cache_avals(cfg, slots, capacity, kv_block, quant_kv)
    compiled = fn.lower(
        params, cache, table, _state_avals(slots)
    ).compile()
    # per-slot KV bytes: the slot's private blocks (the scratch block is
    # shared overhead, visible in cache_bytes = the whole pool)
    blocks = blocks_for(capacity, kv_block)
    from tony_tpu.serve.cache import block_bytes as _bb

    pool_leaves = [cache.k, cache.v]   # _tree_bytes skips a latent cache's None
    if cache.k_scale is not None:
        pool_leaves += [cache.k_scale, cache.v_scale]
    return {
        "slots": slots,
        "capacity": capacity,
        "param_bytes": _tree_bytes(params),
        "cache_bytes": _tree_bytes(pool_leaves),
        # the second kind of state a family may declare (0 without one)
        "slot_state_bytes": _tree_bytes([cache.slot_state]),
        "table_bytes": _tree_bytes([table]),
        "kv_bytes_per_slot": blocks * _bb(cfg, kv_block, quant_kv=quant_kv),
        **aot_analysis(compiled),
    }


def derive_slot_budget(cfg: LlamaConfig, *, max_len: int,
                       hbm_bytes: int, kv_block: int = 64,
                       decode_impl: str = "scan",
                       shared_prefix_tokens: int = 0,
                       quant_kv: str = "") -> dict[str, Any]:
    """Slot budget at ``max_len`` from the compiled decode step's
    memory_analysis (params + fixed/per-slot temp + code) instead of the
    old ``hbm * 0.92 - params`` guess. Returns the budget plus every
    component, so a consumer (bench JSON, capacity planning) can see what
    the chip's HBM actually buys.

    ``shared_prefix_tokens`` adds the prefix-store accounting
    (serve/prefix.py): when every request carries that much shared
    system/template prefix, the shared blocks are paid ONCE (one
    refcounted physical copy in the pool) and each slot privately holds
    only its unshared tail — the per-slot marginal KV cost drops by the
    shared fraction and the slot budget rises accordingly.

    ``quant_kv`` ('int8' | 'fp8_e4m3') additionally compiles the
    QUANTIZED decode step at the same two slot counts and reports its
    budget (``max_slots_quant``, ``quant_slot_ratio``) next to the bf16
    number — the ROADMAP item 4 capacity gain, measured from the
    quantized step's own memory plan (smaller pools, extra scale rows,
    dequant scratch) rather than assumed from the dtype ratio."""
    capacity = blocks_for(max_len, kv_block) * kv_block
    one = decode_step_analysis(
        cfg, slots=1, capacity=capacity, kv_block=kv_block,
        decode_impl=decode_impl,
    )
    if "temp_bytes" not in one:
        # aot_analysis returned nothing (backend without memory_analysis):
        # a budget of hbm - params with ZERO scratch/code margin would be
        # MORE optimistic than the formula this module replaces, while
        # wearing the "measured" label — refuse, so callers fall back to
        # the formula and say so
        raise RuntimeError(
            "compiled decode step exposes no memory_analysis on this "
            "backend; slot budget cannot be measured"
        )
    two = decode_step_analysis(
        cfg, slots=2, capacity=capacity, kv_block=kv_block,
        decode_impl=decode_impl,
    )
    temp1 = int(one.get("temp_bytes", 0))
    temp2 = int(two.get("temp_bytes", temp1))
    per_slot_temp = max(temp2 - temp1, 0)
    fixed_temp = max(temp1 - per_slot_temp, 0)
    code = int(one.get("generated_code_bytes", 0))
    param_bytes = one["param_bytes"]
    # per-slot KV bytes are exact from the block math (one slot's private
    # blocks; the shared scratch block sits in cache_bytes, not here)
    per_slot_kv = one["kv_bytes_per_slot"]
    # a family's fixed-size per-slot state is one more marginal cost of a
    # slot (0 without one, as for every family that takes quant_kv)
    per_slot_state = one["slot_state_bytes"]
    # the hypothetical repeat-expanded layout keeps K/V at n_heads width —
    # the capacity the native-GQA decode kernel exists to avoid paying
    per_slot_kv_repeat = per_slot_kv * cfg.n_heads // cfg.n_kv_heads
    budget = hbm_bytes - param_bytes - fixed_temp - code
    per_slot_rest = per_slot_temp + per_slot_state
    native = max(budget // (per_slot_kv + per_slot_rest), 0) if budget > 0 else 0
    repeat = (
        max(budget // (per_slot_kv_repeat + per_slot_rest), 0)
        if budget > 0 else 0
    )
    out = {
        "hbm_bytes": int(hbm_bytes),
        "param_bytes": int(param_bytes),
        "fixed_temp_bytes": int(fixed_temp),
        "per_slot_temp_bytes": int(per_slot_temp),
        "generated_code_bytes": code,
        "slot_state_bytes_per_slot": int(per_slot_state),
        "kv_bytes_per_slot_native": int(per_slot_kv),
        "kv_bytes_per_slot_repeat": int(per_slot_kv_repeat),
        "max_slots_native": int(native),
        "max_slots_repeat": int(repeat),
        "source": "memory_analysis",
    }
    if shared_prefix_tokens > 0:
        # shared-block accounting: the prefix's blocks exist once in the
        # pool (refcounted), each slot pays only its unshared tail
        shared_bytes, per_slot_private, slots_shared = _shared_budget(
            per_slot_kv, per_slot_rest, budget,
            shared_prefix_tokens, max_len, kv_block,
        )
        out["shared_prefix_tokens"] = int(shared_prefix_tokens)
        out["shared_prefix_bytes"] = int(shared_bytes)
        out["kv_bytes_per_slot_prefix_shared"] = int(per_slot_private)
        out["max_slots_prefix_shared"] = int(slots_shared)
    if quant_kv:
        q1 = decode_step_analysis(
            cfg, slots=1, capacity=capacity, kv_block=kv_block,
            decode_impl=decode_impl, quant_kv=quant_kv,
        )
        q2 = decode_step_analysis(
            cfg, slots=2, capacity=capacity, kv_block=kv_block,
            decode_impl=decode_impl, quant_kv=quant_kv,
        )
        qtemp1 = int(q1.get("temp_bytes", 0))
        qtemp2 = int(q2.get("temp_bytes", qtemp1))
        q_slot_temp = max(qtemp2 - qtemp1, 0)
        q_fixed = max(qtemp1 - q_slot_temp, 0)
        q_code = int(q1.get("generated_code_bytes", 0))
        per_slot_kv_q = q1["kv_bytes_per_slot"]
        budget_q = hbm_bytes - q1["param_bytes"] - q_fixed - q_code
        quant = (
            max(budget_q // (per_slot_kv_q + q_slot_temp), 0)
            if budget_q > 0 else 0
        )
        out["quant_kv"] = quant_kv
        out["fixed_temp_bytes_quant"] = int(q_fixed)
        out["per_slot_temp_bytes_quant"] = int(q_slot_temp)
        out["kv_bytes_per_slot_quant"] = int(per_slot_kv_q)
        out["max_slots_quant"] = int(quant)
        out["quant_slot_ratio"] = (
            round(quant / native, 3) if native else 0.0
        )
        if shared_prefix_tokens > 0:
            # shared blocks priced at QUANTIZED bytes: a refcounted
            # prefix block in a quantized pool carries the int8/fp8
            # payload plus its scale rows, nothing more
            q_shared, q_private, q_slots_shared = _shared_budget(
                per_slot_kv_q, q_slot_temp, budget_q,
                shared_prefix_tokens, max_len, kv_block,
            )
            out["shared_prefix_bytes_quant"] = int(q_shared)
            out["kv_bytes_per_slot_quant_prefix_shared"] = int(q_private)
            out["max_slots_quant_prefix_shared"] = int(q_slots_shared)
    return out


def _shared_budget(per_slot_kv: int, per_slot_temp: int, budget: int,
                   shared_prefix_tokens: int, max_len: int,
                   kv_block: int) -> tuple[int, int, int]:
    """(shared bytes paid once, per-slot private KV bytes, slot budget)
    under prefix sharing — the common math for the bf16 and quantized
    variants, each feeding its own per-slot KV price."""
    total_blocks = blocks_for(max_len, kv_block)
    shared_blocks = min(shared_prefix_tokens // kv_block, total_blocks)
    per_block = per_slot_kv // total_blocks
    shared_bytes = shared_blocks * per_block
    per_slot_private = per_slot_kv - shared_bytes
    budget_shared = budget - shared_bytes
    slots_shared = (
        max(budget_shared // (per_slot_private + per_slot_temp), 0)
        if budget_shared > 0 and (per_slot_private + per_slot_temp) > 0
        else 0
    )
    return shared_bytes, per_slot_private, slots_shared


__all__ = ["decode_step_analysis", "derive_slot_budget"]
