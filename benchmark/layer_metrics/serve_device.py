"""``serve_device.<group>``: each group of the engine's programs as a share
of the device seconds of all program executions in the traced window (the
trace's ``XLA Modules`` line), in percent, by the name the engine gave the
program (the part before the ``(``):

    decode   jit_serve_decode, jit_serve_spec_decode
    prefill  jit_serve_prefill, jit_serve_tail_prefill, jit_serve_gather
    scatter  jit_serve_scatter, jit_serve_copy_block, jit_serve_zero_scales
             (writes into the pool outside the decode step)
    other    every other program: eager dispatch (slot activation, finishes)

The four add up to 100. Nothing where no program carries a ``jit_serve_``
name (a program that names none)."""

GROUPS = {
    "decode": ("jit_serve_decode", "jit_serve_spec_decode"),
    "prefill": ("jit_serve_prefill", "jit_serve_tail_prefill", "jit_serve_gather"),
    "scatter": ("jit_serve_scatter", "jit_serve_copy_block", "jit_serve_zero_scales"),
}
GROUP_OF = {program: group for group, programs in GROUPS.items() for program in programs}


def read(name, ctx):
    t = ctx["trace"]
    modules = (t or {}).get("modules") or {}
    seconds = dict.fromkeys((*GROUPS, "other"), 0.0)
    named = False
    for module, s in modules.items():
        program = module.split("(", 1)[0]
        named = named or program.startswith("jit_serve_")
        seconds[GROUP_OF.get(program, "other")] += s
    total = sum(seconds.values())
    group = name.split(".", 1)[1]
    if not named or total <= 0 or group not in seconds:
        return None
    return 100.0 * seconds[group] / total
