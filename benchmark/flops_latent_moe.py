"""Operations and bytes the latent-attention expert decoder NEEDS, from shapes
and from what the router sent here (the program's ``moe_routes`` counter:
which experts a token uses is data, not shape). The yardstick for the
``latent_moe.*`` shares: recomputation is never counted, the embedding lookup
is a gather, and an expert no token chose in a step is not read.

``s`` is ``weights_latent_moe.sizes_of(config)``: this chip's share.
"""

from __future__ import annotations


def attn_matmul_params(s: dict) -> int:
    """One layer's attention weights a token is multiplied by. ``wkv_b`` counts
    once in either form: expanded, each cached row goes through it once; absorbed,
    its key half meets the query and its value half the output."""
    d, H = s["d"], s["h"]
    return (d * s["qr"] + s["qr"] * H * (s["nope"] + s["rope"]) + d * (s["kr"] + s["rope"])
            + s["kr"] * H * (s["nope"] + s["vd"]) + H * s["vd"] * d)


def expert_params(s: dict) -> int:
    return 3 * s["d"] * s["fm"]


def dense_ffn_params(s: dict) -> int:
    return 3 * s["d"] * s["f"]


def expert_layer_fixed_params(s: dict) -> int:
    """Router and shared expert: what every token of an expert layer meets."""
    return s["d"] * s["e"] + s["shared"] * expert_params(s)


def fixed_matmul_params(s: dict) -> int:
    """Weights EVERY token is multiplied by: every layer's attention, the dense
    layers' SwiGLU, per expert layer the router and the shared expert, and the
    output head over this chip's slice."""
    n_moe = s["layers"] - s["dense"]
    return (s["layers"] * attn_matmul_params(s) + s["dense"] * dense_ffn_params(s)
            + n_moe * expert_layer_fixed_params(s) + s["d"] * s["v"])


def token_matmul_params(s: dict, routes_per_token_layer: float) -> float:
    """Weights one token is multiplied by: the fixed ones and the routed
    experts that live here for this token (a mean, from the counter)."""
    n_moe = s["layers"] - s["dense"]
    return fixed_matmul_params(s) + n_moe * routes_per_token_layer * expert_params(s)


def pair_flops(s: dict, form: str) -> int:
    """Scores and weighted values of one (query, key) pair, all heads, one
    layer: expanded q.k is nope + rope wide and v is vd; absorbed both run
    over the latent (kr + rope, then kr)."""
    if form == "expanded":
        return 2 * s["h"] * (s["nope"] + s["rope"] + s["vd"])
    return 2 * s["h"] * (2 * s["kr"] + s["rope"])


def serve_flops(s: dict, prefill_lens: list[int], decode_ctx: list[int],
                routes_per_token_layer: float) -> float:
    """Forward pass of every prompt token prefilled (causal within the prompt)
    and of every decode step's token (attending its whole context); attention
    in the cheaper form of each phase: expanded over a prompt (its keys are
    expanded once, in the projections above), absorbed at decode (expanding a
    whole context for one query is never cheaper)."""
    tokens = sum(prefill_lens) + len(decode_ctx)
    mm = 2.0 * token_matmul_params(s, routes_per_token_layer) * tokens
    L = s["layers"]
    prefill_pairs = sum(p * p / 2.0 for p in prefill_lens)
    attn = L * min(pair_flops(s, "expanded"), pair_flops(s, "absorbed")) * prefill_pairs
    attn += L * pair_flops(s, "absorbed") * float(sum(decode_ctx))
    return mm + attn


def prefill_flops(s: dict, prompt_len: int, routes_per_token_layer: float) -> float:
    return serve_flops(s, [prompt_len], [], routes_per_token_layer) - 2.0 * s["d"] * s["v"] * (prompt_len - 1)


def latent_bytes_per_token(s: dict, dtype_bytes: int = 2) -> int:
    return s["layers"] * (s["kr"] + s["rope"]) * dtype_bytes


def decode_step_cost(s: dict, live_lens: list[int], experts_hit: float,
                     routes: float, dtype_bytes: int = 2) -> dict:
    """One decode step over the live slots. Bytes: every layer's attention
    weights, the dense layers' SwiGLU, per expert layer the router (float32),
    the shared expert and the ``experts_hit`` experts some token chose (summed
    over the expert layers), the norms and the head, each once; each live
    slot's latent rows once. Operations: 2 per weight per live token
    (``routes`` = routes that landed here this step, all expert layers) plus
    the absorbed attention."""
    n, d = len(live_lens), s["d"]
    n_moe = s["layers"] - s["dense"]
    norms = s["layers"] * (2 * d + s["qr"] + s["kr"]) + d
    fixed = fixed_matmul_params(s)
    router = n_moe * d * s["e"]                 # float32, with its bias: counted apart
    weights = ((fixed - router + experts_hit * expert_params(s) + norms) * dtype_bytes
               + n_moe * (d + 1) * s["e"] * 4)
    kv = latent_bytes_per_token(s, dtype_bytes) * sum(live_lens)
    flops = (2.0 * fixed * n + 2.0 * expert_params(s) * routes
             + s["layers"] * pair_flops(s, "absorbed") * float(sum(live_lens)))
    return {"flops": flops, "bytes": float(weights + kv)}
