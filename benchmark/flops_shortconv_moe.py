"""Operations and bytes the short-convolution / attention hybrid with routed
experts NEEDS, from shapes and from what the router chose (the program's
``moe_routes`` counter: which experts a token uses is data, not shape). The
yardstick for the ``shortconv_moe.*`` shares: recomputation is never counted,
the embedding lookup is a gather, the tied head is counted once, and an expert
no token chose in a step is not read.

``s`` is ``weights_shortconv_moe.sizes_of(config)``.
"""

from __future__ import annotations


def n_conv(s: dict) -> int:
    return sum(1 for t in s["layer_types"] if t == "conv")


def n_attn(s: dict) -> int:
    return len(s["layer_types"]) - n_conv(s)


def n_moe(s: dict) -> int:
    return len(s["layer_types"]) - s["dense"]


def conv_params(s: dict) -> int:
    """A convolution operator's weights a token is multiplied by: ``w_in``
    (D x 3D), the taps (K x D) and ``w_out`` (D x D)."""
    d = s["d"]
    return 3 * d * d + s["K"] * d + d * d


def attn_params(s: dict) -> int:
    d, nq, nkv = s["d"], s["h"] * s["hd"], s["kv"] * s["hd"]
    return 2 * d * nq + 2 * d * nkv


def expert_params(s: dict) -> int:
    return 3 * s["d"] * s["fm"]


def dense_ffn_params(s: dict) -> int:
    return 3 * s["d"] * s["f"]


def fixed_matmul_params(s: dict) -> int:
    """Weights EVERY token is multiplied by: every layer's operator, the dense
    layers' SwiGLU, per expert layer the router, and the tied head."""
    return (n_conv(s) * conv_params(s) + n_attn(s) * attn_params(s)
            + s["dense"] * dense_ffn_params(s) + n_moe(s) * s["d"] * s["e"]
            + s["d"] * s["v"])


def token_matmul_params(s: dict, routes_per_token_layer: float) -> float:
    """Weights one token is multiplied by: the fixed ones and its routed
    experts (``k`` a layer when every expert is local)."""
    return fixed_matmul_params(s) + n_moe(s) * routes_per_token_layer * expert_params(s)


def pair_flops(s: dict) -> int:
    """Scores and weighted values of one (query, key) pair, all heads, one
    attention layer."""
    return 4 * s["h"] * s["hd"]


def serve_flops(s: dict, prefill_lens: list[int], decode_ctx: list[int],
                routes_per_token_layer: float) -> float:
    """Forward pass of every prompt token prefilled (causal within the prompt,
    at its true length) and of every decode step's token (attending its whole
    context in the attention layers)."""
    tokens = sum(prefill_lens) + len(decode_ctx)
    mm = 2.0 * token_matmul_params(s, routes_per_token_layer) * tokens
    pairs = sum(p * p / 2.0 for p in prefill_lens) + float(sum(decode_ctx))
    return mm + n_attn(s) * pair_flops(s) * pairs


def prefill_flops(s: dict, prompt_len: int, routes_per_token_layer: float) -> float:
    """One prompt; the head over its last position only."""
    return (serve_flops(s, [prompt_len], [], routes_per_token_layer)
            - 2.0 * s["d"] * s["v"] * (prompt_len - 1))


def kv_bytes_per_token(s: dict, dtype_bytes: int = 2) -> int:
    return n_attn(s) * 2 * s["kv"] * s["hd"] * dtype_bytes


def conv_state_bytes_per_slot(s: dict, dtype_bytes: int = 2) -> int:
    return n_conv(s) * (s["K"] - 1) * s["d"] * dtype_bytes


def decode_step_cost(s: dict, live_lens: list[int], experts_hit: float,
                     routes: float, dtype_bytes: int = 2) -> dict:
    """One decode step over the live slots. Bytes: every layer's operator, the
    dense layers' SwiGLU, per expert layer the router (float32, with its bias)
    and the ``experts_hit`` experts some token chose (summed over the expert
    layers), the norms and the tied head, each once; each live slot's K/V rows
    of the attention layers once; each live slot's convolution state read and
    written. Operations: 2 per weight per live token (``routes`` = routes of
    this step, all expert layers) plus attention."""
    n, d = len(live_lens), s["d"]
    L = len(s["layer_types"])
    norms = (2 * L + 1) * d + n_attn(s) * 2 * s["hd"]
    fixed = fixed_matmul_params(s)
    router = n_moe(s) * d * s["e"]                 # float32: counted apart
    weights = ((fixed - router + experts_hit * expert_params(s) + norms) * dtype_bytes
               + n_moe(s) * (d + 1) * s["e"] * 4)
    kv = kv_bytes_per_token(s, dtype_bytes) * sum(live_lens)
    state = 2 * conv_state_bytes_per_slot(s, dtype_bytes) * n
    flops = (2.0 * fixed * n + 2.0 * expert_params(s) * routes
             + n_attn(s) * pair_flops(s) * float(sum(live_lens)))
    return {"flops": flops, "bytes": float(weights + kv + state)}
