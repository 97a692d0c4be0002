"""The whole step's share of the chip's bf16 peak: operations the algorithm
needs (benchmark/flops.py: no recomputation, no embedding gather) over the
window's time. ``step_mfu.train`` from tokens/s/chip; ``step_mfu.serve`` from
the prompt and output tokens the window processed."""

from benchmark import flops, weights


def read(name, ctx):
    obs, peak = ctx["observed"], ctx["peak"]
    if peak is None:
        return None
    s = weights.sizes_of(ctx["config"])
    if name.endswith(".train") and "tokens_per_s_chip" in obs:
        per_token = flops.train_flops_per_token(s, obs["seq_len"])
        return 100.0 * per_token * obs["tokens_per_s_chip"] / peak["bf16_flops_per_s"]
    if name.endswith(".serve") and "prefill_lens" in obs:
        need = flops.serve_flops(s, obs["prefill_lens"], obs["decode_ctx"])
        return 100.0 * need / obs["window_s"] / peak["bf16_flops_per_s"]
    return None
