"""Operations and bytes the selective state-space / attention hybrid NEEDS,
from shapes alone. The yardstick for the ``ssm_hybrid.*`` shares:
recomputation is never counted, the embedding lookup is a gather, the tied
head is counted once, and a padded bucket's rows are not work.

``s`` is ``weights_ssm_hybrid.sizes_of(config)``.
"""

from __future__ import annotations

# operations of the recurrence per (state row, channel) of one position:
# delta * A, exp, * h, * B, +, * C, the sum over the rows
SCAN_OPS_PER_ELEMENT = 7
# per channel of one position: delta * c, D * c, +, silu(z) (4), *
SCAN_OPS_PER_CHANNEL = 8


def n_mamba(s: dict) -> int:
    return sum(1 for t in s["layer_types"] if t == "mamba")


def n_attn(s: dict) -> int:
    return s["layers"] - n_mamba(s)


def mamba_params(s: dict) -> int:
    """A Mamba mixer's weights a token is multiplied by: ``w_in`` (D x 2E),
    the taps (K x E), ``w_x`` (E x (R + 2N)), ``w_dt`` (R x E), ``w_out``."""
    d, E = s["d"], s["e"]
    return 2 * d * E + s["K"] * E + E * (s["r"] + 2 * s["n"]) + s["r"] * E + E * d


def attn_params(s: dict) -> int:
    d, nq, nkv = s["d"], s["h"] * s["hd"], s["kv"] * s["hd"]
    return 2 * d * nq + 2 * d * nkv


def ffn_params(s: dict) -> int:
    return 3 * s["d"] * s["f"]


def matmul_params(s: dict) -> int:
    """Weights EVERY token is multiplied by: every layer's mixer and SwiGLU,
    and the tied head."""
    return (n_mamba(s) * mamba_params(s) + n_attn(s) * attn_params(s)
            + s["layers"] * ffn_params(s) + s["d"] * s["v"])


def scan_flops_per_token(s: dict) -> int:
    """The recurrence of ONE Mamba layer on one position (vector work)."""
    return s["e"] * (s["n"] * SCAN_OPS_PER_ELEMENT + SCAN_OPS_PER_CHANNEL)


def pair_flops(s: dict) -> int:
    """Scores and weighted values of one (query, key) pair, all heads, one
    attention layer."""
    return 4 * s["h"] * s["hd"]


def serve_flops(s: dict, prefill_lens: list[int], decode_ctx: list[int]) -> float:
    """Forward pass of every prompt token prefilled (causal within the prompt,
    at its true length) and of every decode step's token (attending its whole
    context in the attention layers)."""
    tokens = sum(prefill_lens) + len(decode_ctx)
    per_token = 2.0 * matmul_params(s) + n_mamba(s) * scan_flops_per_token(s)
    pairs = sum(p * p / 2.0 for p in prefill_lens) + float(sum(decode_ctx))
    return per_token * tokens + n_attn(s) * pair_flops(s) * pairs


def prefill_flops(s: dict, prompt_len: int) -> float:
    """One prompt; the head over its last position only."""
    return serve_flops(s, [prompt_len], []) - 2.0 * s["d"] * s["v"] * (prompt_len - 1)


def kv_bytes_per_token(s: dict, dtype_bytes: int = 2) -> int:
    return n_attn(s) * 2 * s["kv"] * s["hd"] * dtype_bytes


def state_bytes_per_slot(s: dict) -> int:
    """The recurrent state a slot keeps: per Mamba layer ``N`` rows of ``h``
    and the convolution's last ``K - 1`` inputs, ``E`` wide, float32."""
    return n_mamba(s) * (s["n"] + s["K"] - 1) * s["e"] * 4


def weight_bytes(s: dict, dtype_bytes: int = 2) -> int:
    """Every weight once: the matrices, the norms' gains and the biases in the
    serving dtype, the recurrence's own leaves (``a_log``, ``d_skip``,
    ``b_dt``) in float32."""
    d, E, N, R = s["d"], s["e"], s["n"], s["r"]
    gains = (2 * s["layers"] + 1) * d + n_mamba(s) * (R + 2 * N + E)    # norms, conv_b
    f32 = n_mamba(s) * (N * E + 2 * E)
    return (matmul_params(s) + gains) * dtype_bytes + f32 * 4


def decode_step_cost(s: dict, live_lens: list[int], dtype_bytes: int = 2) -> dict:
    """One decode step over the live slots. Bytes: every weight once; each live
    slot's recurrent state read and written; each live slot's K/V rows of the
    attention layers once. Operations: 2 per weight per live token, the
    recurrence, attention."""
    n = len(live_lens)
    state = 2 * state_bytes_per_slot(s) * n
    kv = kv_bytes_per_token(s, dtype_bytes) * sum(live_lens)
    flops = ((2.0 * matmul_params(s) + n_mamba(s) * scan_flops_per_token(s)) * n
             + n_attn(s) * pair_flops(s) * float(sum(live_lens)))
    return {"flops": flops, "bytes": float(weight_bytes(s, dtype_bytes) + state + kv)}


def scan_kernel_cost(s: dict, tokens: int, dtype_bytes: int = 2) -> dict:
    """The ``selective_scan`` kernel of ONE Mamba layer over ``tokens``
    positions. Bytes: ``c`` and ``z`` read and ``y`` written in the serving
    dtype, ``delta``, ``B`` and ``C`` read in float32, ``A`` and ``D`` read,
    the state read and written."""
    E, N = s["e"], s["n"]
    per_token = E * (3 * dtype_bytes + 4) + 2 * N * 4
    fixed = (3 * N * E + E) * 4
    return {"flops": float(tokens * scan_flops_per_token(s)),
            "bytes": float(tokens * per_token + fixed)}
