"""``serve_idle.<phase>``: device-idle seconds of the traced window while the
engine's host thread was in that phase, over the window, in percent. The
phases are the engine's own ``serve.*`` annotations (benchmark/host_spans.py:
admit, plan, dispatch, emit, and caller = inside none of them); with the idle
under the rest of ``serve.step`` (the sync) they add up to
``device_idle.serve``. Nothing where the program has no such annotations."""

from benchmark import host_spans


def read(name, ctx):
    split = host_spans.of_run(ctx)
    phase = name.split(".", 1)[1]
    if not split or split["window_s"] <= 0 or phase not in split["idle_s"]:
        return None
    return 100.0 * split["idle_s"][phase] / split["window_s"]
