"""The decode step's share of its roofline in the traced seconds: least time
for one step = (every weight + the live slots' keys and values) read once at
the chip's memory bandwidth, or its operations at the bf16 peak, whichever is
larger, from shapes and the live lengths the driver recorded at each traced
step; over the device time of one execution of the decode program. The same
work whether XLA or a kernel implements the step.

The engine's programs carry no names of their own (``jit__unknown``), so the
decode program is found by what it does: it is the program that ran once for
each decode step the host counted while the trace was on."""

from benchmark import flops, trace_reduce, weights


def read(name, ctx):
    t, peak, obs = ctx["trace"], ctx["peak"], ctx["observed"]
    steps = obs.get("traced_decode_lens")
    if not t or peak is None or not steps or not t["module_events"]:
        return None
    program = min(t["module_events"], key=lambda k: abs(len(t["module_events"][k]) - len(steps)))
    _, _, n = trace_reduce.whole_executions(t, program)
    # within a fifth of the host's count, or it is some other program
    if n == 0 or abs(n + 2 - len(steps)) > 0.2 * len(steps):
        return None
    whole = sorted(t["module_events"][program])[1:-1]
    per_step = sum(dur for _, dur in whole) / n
    s = weights.sizes_of(ctx["config"])
    least = sum(flops.least_seconds(flops.decode_step_cost(s, lens), peak)[0] for lens in steps)
    return 100.0 * (least / len(steps)) / per_step
