"""``shortconv_moe.<member>``: the per-layer metrics of the short-convolution /
attention hybrid's serving cell. Counts are benchmark/flops_shortconv_moe.py's;
the expert counters are the program's (``DecodeMetrics.moe_*``, copied by the
driver into ``observed['family']``); device times are found by program NAME in
the trace (``jit_serve_decode``, ``jit_serve_prefill``, ``jit_serve_scatter``).
Every member returns nothing, and does not raise, where the program or the
trace has nothing to read.

    step_mfu                  whole step: operations the window's prompt and
                              output tokens need / window / bf16 peak
    decode_step_roofline      least time of a decode step (weights with only
                              the experts HIT, live K/V rows of the attention
                              layers once, the convolution state read and
                              written; or its operations) / device time of
                              jit_serve_decode
    prefill_roofline          operations of the prompts prefilled in the trace
                              / peak / device time of jit_serve_prefill
    experts_hit_per_step      mean over expert layers and decode steps (of 32)
    expert_load_max_over_mean busiest expert's routes over the mean
    handoff_share             device seconds of jit_serve_scatter (the program
                              that writes a prefill's K/V rows and convolution
                              state into the slot) and of the state's reset at
                              admission / device seconds of all programs
"""

from benchmark import flops, flops_shortconv_moe as counts, weights_shortconv_moe as weights

HANDOFF_PROGRAMS = ("jit_serve_scatter", "jit_serve_zero_slot_state")


def _sizes(ctx):
    config = ctx["config"]
    return weights.sizes_of(config) if config.get("family") == "shortconv_moe" else None


def _routes_per_token_layer(s, fam):
    total = sum(sum(row) for row in fam["routes"])
    return total / max(fam["tokens"] * counts.n_moe(s), 1)


def _program_seconds(trace, *programs):
    """(device seconds, executions) of the programs named ``programs``."""
    events = [e for module, ev in (trace.get("module_events") or {}).items()
              if module.split("(", 1)[0] in programs for e in ev]
    return sum(dur for _, dur in events), len(events)


def read(name, ctx):
    member = name.split(".", 1)[1]
    obs, peak, t = ctx["observed"], ctx["peak"], ctx["trace"]
    s = _sizes(ctx)
    fam = (obs.get("family") or {}).get("window")
    if s is None or not fam or not fam["tokens"]:
        return None
    n_moe = counts.n_moe(s)
    if member == "experts_hit_per_step":
        return sum(fam["experts_hit"]) / (fam["steps"] * n_moe) if fam["steps"] else None
    if member == "expert_load_max_over_mean":
        loads = [r for row in fam["routes"] for r in row]
        return max(loads) / (sum(loads) / len(loads)) if sum(loads) else None
    if member == "handoff_share":
        if not t:
            return None
        total = sum(dur for ev in (t.get("module_events") or {}).values() for _, dur in ev)
        seconds, n = _program_seconds(t, *HANDOFF_PROGRAMS)
        return 100.0 * seconds / total if n and total > 0 else None
    if peak is None:
        return None
    if member == "step_mfu":
        need = counts.serve_flops(s, obs["prefill_lens"], obs["decode_ctx"],
                                  _routes_per_token_layer(s, fam))
        return 100.0 * need / obs["window_s"] / peak["bf16_flops_per_s"]
    if not t:
        return None
    if member == "decode_step_roofline":
        steps = obs.get("traced_decode_lens")
        f0, f1 = (obs["family"].get(k) for k in ("trace0", "trace1"))
        seconds, n = _program_seconds(t, "jit_serve_decode")
        if not steps or not f0 or not f1 or f1["steps"] <= f0["steps"] or not n:
            return None
        # the traced seconds' own means, over all expert layers of one step
        dsteps = f1["steps"] - f0["steps"]
        hit = (sum(f1["experts_hit"]) - sum(f0["experts_hit"])) / dsteps
        mean_live = sum(len(x) for x in steps) / len(steps)
        routes = _routes_per_token_layer(s, fam) * n_moe * mean_live
        least = sum(flops.least_seconds(counts.decode_step_cost(s, lens, hit, routes), peak)[0]
                    for lens in steps) / len(steps)
        return 100.0 * least / (seconds / n)
    if member == "prefill_roofline":
        lens = obs.get("traced_prefill_lens")
        seconds, n = _program_seconds(t, "jit_serve_prefill")
        if not lens or not n:
            return None
        need = sum(counts.prefill_flops(s, p, _routes_per_token_layer(s, fam)) for p in lens)
        return 100.0 * need / peak["bf16_flops_per_s"] / seconds
    return None
