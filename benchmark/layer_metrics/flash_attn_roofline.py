"""The flash-attention kernels' share of their roofline in the traced steps:
least time for the forward and backward passes the algorithm needs (causal,
grouped-query; benchmark/flops.py) over the device time of the kernels'
operations, both taken over the step program's executions that the trace
holds whole. Returns nothing where the trace holds no such kernel.

The kernels of tony_tpu/ops/attention.py reach the trace as custom calls
named after the JAX primitive that wraps them (``checkpoint.22``,
``closed_call.19``), not after themselves, so they are found by what they
are: a custom call whose result is ``[batch x heads, seq, head_dim]``."""

import re

from benchmark import flops, trace_reduce, weights


def read(name, ctx):
    t, peak, obs = ctx["trace"], ctx["peak"], ctx["observed"]
    if not t or peak is None or not t["modules"]:
        return None
    s = weights.sizes_of(ctx["config"])
    kernels = rf" custom-call\S* .*\[\d+,{obs['seq_len']},{s['hd']}\]"
    main = max(t["modules"], key=t["modules"].get)
    start, end, steps = trace_reduce.whole_executions(t, main)
    chips = max(obs.get("chips", 1), 1)
    seconds = trace_reduce.op_seconds(t, kernels, between=(start, end)) / chips
    if steps == 0 or seconds <= 0:
        return None
    cost = flops.flash_attn_cost(s, obs["global_batch"] // chips, obs["seq_len"])
    least, _ = flops.least_seconds(cost, peak)
    return 100.0 * least * (steps / chips) / seconds
