"""The device's idle seconds of a traced serving run, shared out over the host
phases the engine names with ``serve.*`` annotations (tony_tpu/serve/engine.py),
and what the host spent in each phase.

``trace_reduce.reduce`` names a whole idle gap by the one span over its
middle. A gap between two decode programs runs from ``serve.emit`` through
the loop that drives the engine into ``serve.plan`` and ``serve.dispatch``, so
here each gap is split by overlap: every part of it goes to the phase the
host was in at that moment. The phases tile the engine thread's time:

    admit     ``serve.admit`` with its children, and a ``serve.prefill_chunk``
              or ``serve.activate`` that runs outside one
    plan      ``serve.plan``
    dispatch  the part of ``serve.step`` under ``serve.dispatch``
    sync      the rest of ``serve.step`` (``serve.sync``: the device finishing
              while the host wakes)
    emit      ``serve.emit``
    caller    inside no ``serve.*`` annotation: the loop that drives the engine

The six add up to the traced window's idle time (``device_idle.serve``). A
trace without ``serve.*`` annotations (a program that has none) gives None.

The device's clock is first brought onto the host's. The profiler writes both
in one unit, but not always from one origin: in the traces of three chip calls
of four every decode program "started" 0.7-1.1 ms before the ``serve.dispatch``
that launches it had begun, which is half of a 2.3 ms gap; in the fourth call's
none did (PERF.md section 6, PR 25). The
annotations say what cannot be: a decode program does not start before its
``serve.dispatch`` begins, and does not end after its ``serve.sync`` has
returned. ``clock_shift`` is the smallest shift of the device's times that
makes the trace obey both. It is the least the clocks can differ by. What it
moves is where a gap's two ends fall (its start in ``sync`` or ``admit``, its
end in ``dispatch`` or ``admit``), so the idle under ``dispatch`` is a lower
bound and the idle under ``sync`` an upper bound; ``emit``, ``plan`` and
``caller`` lie inside a gap, and they and the total do not depend on it.
"""

from __future__ import annotations

import functools
import os
from collections import defaultdict

from benchmark import trace_reduce

PHASES = ("admit", "plan", "dispatch", "sync", "emit", "caller")

# an annotation that lies inside no other -> its phase. serve.dispatch and
# serve.sync stand alone only where the trace's edge cut their serve.step
TOP_LEVEL = {
    "serve.admit": "admit", "serve.prefill": "admit", "serve.prefill_chunk": "admit",
    "serve.activate": "admit", "serve.plan": "plan", "serve.step": "sync",
    "serve.dispatch": "dispatch", "serve.sync": "sync", "serve.emit": "emit",
}


def phase_segments(host: list[tuple[str, float, float]]) -> list[tuple[float, float, str]]:
    """Non-overlapping (start, end, phase), in time order, from the
    ``serve.*`` host events (name, start, duration). The engine runs on one
    thread, so its annotations nest properly."""
    segments: list[tuple[float, float, str]] = []
    top_name, top_end = "", float("-inf")
    for name, s, dur in sorted(host, key=lambda e: (e[1], -e[2])):
        if not name.startswith("serve."):
            continue
        e = s + dur
        if s >= top_end:
            top_name, top_end = name, e
            if name in TOP_LEVEL:
                segments.append((s, e, TOP_LEVEL[name]))
        elif name == "serve.dispatch" and top_name == "serve.step" and segments:
            s0, e0, _ = segments.pop()  # what is left of the step so far
            e = min(e, e0)
            segments += [seg for seg in ((s0, s, "sync"), (s, e, "dispatch"), (e, e0, "sync"))
                         if seg[1] > seg[0]]
    return segments


DECODE_PROGRAMS = ("jit_serve_decode(", "jit_serve_spec_decode(")
PAIRING_NS = 10e6  # a program and the annotation around it lie within this; steps are 50 ms apart


def clock_shift(modules: list[tuple[str, float, float]],
                serve: list[tuple[str, float, float]]) -> float:
    """ns to add to one device's times so that no decode program starts
    before the ``serve.dispatch`` nearest to its start began, and none ends
    after the ``serve.sync`` nearest to its end returned; 0 where the trace
    obeys both as it is, or holds no decode program to tell by."""
    began = [s for n, s, _ in serve if n == "serve.dispatch"]
    returned = [s + d for n, s, d in serve if n == "serve.sync"]
    early, late = [], []  # by how much a program starts too early, ends too late
    for name, s, d in modules:
        if not name.startswith(DECODE_PROGRAMS):
            continue
        if began:
            b = min(began, key=lambda t: abs(t - s))
            if abs(b - s) < PAIRING_NS:
                early.append(b - s)
        if returned:
            r = min(returned, key=lambda t: abs(t - (s + d)))
            if abs(r - (s + d)) < PAIRING_NS:
                late.append((s + d) - r)
    if early and max(early) > 0:
        return max(early)
    if late and max(late) > 0:
        return -max(late)
    return 0.0


def split_idle(trace: dict) -> dict | None:
    """``trace`` as ``trace_reduce.load`` gives it. Returns idle_s {phase:
    seconds of device idle time while the host was in that phase, one chip's
    mean}, window_s as ``trace_reduce.reduce`` defines it, host_s {annotation name: seconds}, counts {annotation name: events} and
    clock_shift_s (what was added to the device's times, the chips' mean);
    None where the trace holds no device operation or no ``serve.*`` name."""
    devs = [d for d in trace["devices"].values() if d["ops"]]
    serve = [e for e in trace["host"] if e[0].startswith("serve.")]
    if not devs or not serve:
        return None
    segments = phase_segments(serve)
    t0 = min(s for d in devs for _, s, _ in d["ops"])
    t1 = max(s + dur for d in devs for _, s, dur in d["ops"])
    idle: dict[str, float] = dict.fromkeys(PHASES, 0.0)
    shifts = 0.0
    for d in devs:
        merged = trace_reduce._union([(s, s + dur) for _, s, dur in d["ops"]])
        shift = clock_shift(d["modules"], serve)
        shifts += shift
        i = 0
        for (_, a), (b, _) in zip(merged, merged[1:]):
            a, b = a + shift, b + shift
            while i < len(segments) and segments[i][1] <= a:
                i += 1
            named, j = 0.0, i
            while j < len(segments) and segments[j][0] < b:
                s, e, phase = segments[j]
                part = min(e, b) - max(s, a)
                idle[phase] += part
                named += part
                j += 1
            idle["caller"] += (b - a) - named
    host_s: dict[str, float] = defaultdict(float)
    counts: dict[str, int] = defaultdict(int)
    for name, _, dur in serve:
        host_s[name] += dur
        counts[name] += 1
    n, ns = len(devs), 1e-9
    return {
        "idle_s": {k: v / n * ns for k, v in idle.items()},
        "window_s": (t1 - t0) * ns,
        "host_s": {k: v * ns for k, v in host_s.items()}, "counts": dict(counts),
        "clock_shift_s": shifts / n * ns,
    }


@functools.lru_cache(maxsize=2)
def _of_file(path: str) -> dict | None:
    return split_idle(trace_reduce.load(path))


def of_run(ctx: dict) -> dict | None:
    """The split of the run's trace (under ``ctx['work']/trace``), read once
    for all the readers of one run; None where there is no trace."""
    path = trace_reduce.find_trace(os.path.join(ctx["work"], "trace"))
    return _of_file(path) if path else None
