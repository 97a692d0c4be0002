"""Operations and bytes the algorithm NEEDS, from shapes alone. The yardstick
for every mfu and roofline share: recomputation is never counted, and the
embedding lookup is a gather, not a matrix product.

``s`` is ``weights.sizes_of(model)``.
"""

from __future__ import annotations


def matmul_params(s: dict) -> int:
    """Weights that a token is multiplied by: the layers' seven matrices and
    the output head. The embedding table and the norm gains are not."""
    nq, nkv = s["h"] * s["hd"], s["kv"] * s["hd"]
    per_layer = s["d"] * nq + 2 * s["d"] * nkv + nq * s["d"] + 3 * s["d"] * s["f"]
    return s["layers"] * per_layer + s["d"] * s["v"]


def attn_flops_fwd(s: dict, q_tokens: float, mean_ctx: float) -> float:
    """Scores and weighted values of ``q_tokens`` queries that each attend
    ``mean_ctx`` positions, all query heads, all layers: 2 products of
    2*hd operations per (query, key, head)."""
    return 4.0 * s["layers"] * s["h"] * s["hd"] * q_tokens * mean_ctx


def train_flops_per_token(s: dict, seq: int) -> float:
    """Forward + backward (2x forward) of one token in a causal sequence."""
    return 6.0 * matmul_params(s) + 3.0 * attn_flops_fwd(s, 1, seq / 2.0)


def serve_flops(s: dict, prefill_lens: list[int], decode_ctx: list[int]) -> float:
    """Forward pass of every prompt token prefilled (causal within the
    prompt) and every decode step's token (attending its whole context)."""
    mm = 2.0 * matmul_params(s) * (sum(prefill_lens) + len(decode_ctx))
    attn = sum(attn_flops_fwd(s, p, p / 2.0) for p in prefill_lens)
    attn += attn_flops_fwd(s, 1, 1) * sum(decode_ctx)
    return mm + attn


def flash_attn_cost(s: dict, batch: int, seq: int, dtype_bytes: int = 2,
                    layers: int | None = None) -> dict:
    """Causal flash attention forward + backward over ``layers`` layers of one
    training step. Forward: 2 products; backward: 5 (recomputed scores, dv,
    dp, dq, dk). Bytes: q, k, v, o read or written once forward; q, k, v, o,
    do read and dq, dk, dv written backward (the least a fused kernel moves)."""
    L = s["layers"] if layers is None else layers
    pair = s["h"] * s["hd"] * batch * seq * (seq / 2.0)  # (query, key<=query, head) x hd
    flops = L * (2 + 5) * 2.0 * pair
    qo = batch * seq * s["h"] * s["hd"] * dtype_bytes
    kv = batch * seq * s["kv"] * s["hd"] * dtype_bytes
    fwd_bytes = 2 * qo + 2 * kv
    bwd_bytes = 3 * qo + 2 * kv + qo + 2 * kv
    return {"flops": flops, "bytes": float(L * (fwd_bytes + bwd_bytes))}


def kv_bytes_per_token(s: dict, dtype_bytes: int = 2) -> int:
    return s["layers"] * 2 * s["kv"] * s["hd"] * dtype_bytes


def decode_step_cost(s: dict, live_lens: list[int], dtype_bytes: int = 2) -> dict:
    """One decode step over the live slots: every matrix weight read once,
    each live slot's keys and values read once; 2 operations per weight per
    live token plus attention."""
    n = len(live_lens)
    weight_bytes = (matmul_params(s) + (2 * s["layers"] + 1) * s["d"]) * dtype_bytes
    kv = kv_bytes_per_token(s, dtype_bytes) * sum(live_lens)
    flops = 2.0 * matmul_params(s) * n + attn_flops_fwd(s, 1, 1) * sum(live_lens)
    return {"flops": flops, "bytes": float(weight_bytes + kv)}


def least_seconds(cost: dict, peak: dict) -> tuple[float, str]:
    """The least time the chip could take for ``cost``, and which peak binds."""
    tf = cost["flops"] / peak["bf16_flops_per_s"]
    tb = cost["bytes"] / peak["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "bandwidth")
