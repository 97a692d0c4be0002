"""Host time per decode step that is not a wait for the device: the seconds
the engine's thread spent under ``serve.plan``, ``serve.dispatch`` and
``serve.emit`` in the traced seconds, over the ``serve.step`` annotations the
trace holds (the profiler keeps only annotations that began and ended while
it was on, so each is whole). Nothing where the program has no such
annotations."""

from benchmark import host_spans

HOST_WORK = ("serve.plan", "serve.dispatch", "serve.emit")


def read(name, ctx):
    split = host_spans.of_run(ctx)
    steps = split["counts"].get("serve.step", 0) if split else 0
    if not steps:
        return None
    return 1e3 * sum(split["host_s"].get(k, 0.0) for k in HOST_WORK) / steps
