"""``ssm_hybrid.<member>``: the per-layer metrics of the selective state-space
/ attention hybrid's serving cell. Counts are benchmark/flops_ssm_hybrid.py's;
the counters are the program's (``DecodeMetrics.slot_state_bytes``,
``.decode_live_sum``, ``.decode_steps``, copied by the driver into
``observed['family']``);
device times are found by NAME in the trace: the programs ``jit_serve_decode``,
``jit_serve_prefill``, ``jit_serve_scatter``, ``jit_serve_zero_slot_state`` and
the kernel ``selective_scan``. Every member returns nothing, and does not
raise, where the program or the trace has nothing to read.

    step_mfu              whole step: operations the window's prompt and
                          output tokens need / window / bf16 peak
    decode_step_roofline  least time of a decode step (every weight once, the
                          live slots' recurrent state read and written, their
                          K/V rows once; or its operations) / device time of
                          jit_serve_decode
    prefill_roofline      operations of the prompts prefilled in the trace, at
                          their true lengths / peak / device time of
                          jit_serve_prefill
    scan_roofline         least time of the selective_scan kernel over those
                          prompts (the larger of its bytes / bandwidth and its
                          operations / peak) / its device time. Reads low: the
                          kernel is bound by the vector unit, whose peak
                          benchmark/peaks.json does not state
    state_stream_share    bytes of slot state the window's decode steps read
                          and wrote (each step's live slots x a slot's share
                          of ``slot_state_bytes`` x 2: what the engine's
                          ``stats_snapshot`` gives as ``state_stream_bytes``)
                          / the least bytes of those steps (lower is better:
                          how much of a step is the recurrent state)
    handoff_share         device seconds of jit_serve_scatter (a prefill's K/V
                          rows and recurrent state into the slot) and of the
                          state's reset at admission / all program seconds
"""

from benchmark import flops, flops_ssm_hybrid as counts, trace_reduce, weights_ssm_hybrid as weights

HANDOFF_PROGRAMS = ("jit_serve_scatter", "jit_serve_zero_slot_state")
SCAN_KERNEL = r"^(vmap_)?selective_scan"


def _program_seconds(trace, *programs):
    """(device seconds, executions) of the programs named ``programs``."""
    events = [e for module, ev in (trace.get("module_events") or {}).items()
              if module.split("(", 1)[0] in programs for e in ev]
    return sum(dur for _, dur in events), len(events)


def read(name, ctx):
    member = name.split(".", 1)[1]
    obs, peak, t = ctx["observed"], ctx["peak"], ctx["trace"]
    if ctx["config"].get("family") != "ssm_hybrid":
        return None
    s = weights.sizes_of(ctx["config"])
    fam = (obs.get("family") or {}).get("window")
    if not fam:
        return None
    if member == "state_stream_share":
        steps, slots = fam["decode_steps"], ctx["config"]["serve"]["slots"]
        streamed = 2 * fam["slot_state_bytes"] // slots * fam.get("decode_live_sum", 0)
        if not steps or not streamed:
            return None
        least = (steps * counts.weight_bytes(s) + streamed
                 + counts.kv_bytes_per_token(s) * float(sum(obs["decode_ctx"])))
        return 100.0 * streamed / least
    if member == "handoff_share":
        if not t:
            return None
        total = sum(dur for ev in (t.get("module_events") or {}).values() for _, dur in ev)
        seconds, n = _program_seconds(t, *HANDOFF_PROGRAMS)
        return 100.0 * seconds / total if n and total > 0 else None
    if peak is None:
        return None
    if member == "step_mfu":
        need = counts.serve_flops(s, obs["prefill_lens"], obs["decode_ctx"])
        return 100.0 * need / obs["window_s"] / peak["bf16_flops_per_s"]
    if not t:
        return None
    if member == "decode_step_roofline":
        steps = obs.get("traced_decode_lens")
        seconds, n = _program_seconds(t, "jit_serve_decode")
        if not steps or not n:
            return None
        least = sum(flops.least_seconds(counts.decode_step_cost(s, lens), peak)[0]
                    for lens in steps) / len(steps)
        return 100.0 * least / (seconds / n)
    lens = obs.get("traced_prefill_lens")
    if not lens:
        return None
    if member == "prefill_roofline":
        seconds, n = _program_seconds(t, "jit_serve_prefill")
        need = sum(counts.prefill_flops(s, p) for p in lens)
        return 100.0 * need / peak["bf16_flops_per_s"] / seconds if n else None
    if member == "scan_roofline":
        seconds = trace_reduce.op_seconds(t, SCAN_KERNEL)
        least = sum(flops.least_seconds(counts.scan_kernel_cost(s, p), peak)[0]
                    for p in lens) * counts.n_mamba(s)
        return 100.0 * least / seconds if seconds > 0 else None
    return None
