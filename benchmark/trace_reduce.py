"""From a profiler trace (``*.xplane.pb``) to the numbers the benchmark
reports: seconds in which an operation ran on the device, per-operation
device time, and the longest idle gaps named by what the host was doing.

Read with nothing but JAX (``jax.profiler.ProfileData``). Layout of a TPU
trace as this jaxlib writes it (looked at by hand, PERF.md section 6): one
plane per chip named ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one
event per executed operation (a fusion, a custom call, a copy) and whose line
``XLA Modules`` holds one event per executed program; one plane
``/host:CPU`` whose lines are host threads and whose events include every
``jax.profiler.TraceAnnotation``. All planes share one clock (ns).
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_trace(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return files[-1] if files else None


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def load(path: str) -> dict:
    """{"devices": {n: {"ops": [(name, start_ns, dur_ns)], "modules": [...]}},
    "host": [(name, start_ns, dur_ns)]} — host events are kept only when a
    caller could name them: the benchmark's own ``bench.*`` annotations and
    the program's dotted span names (``train.step``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[int, dict] = {}
    host: list[tuple[str, float, float]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
                elif line.name == MODULES_LINE:
                    dev["modules"] = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if HOST_SPAN.match(e.name):
                        host.append((e.name, e.start_ns, e.duration_ns))
    return {"devices": devices, "host": host}


HOST_SPAN = re.compile(r"^(bench\.[\w.]+|[a-z_]+\.[a-z_.]+)$")


OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
LAYOUT = re.compile(r"\{[^}]*\}")
TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(text: str, width: int = 72) -> str:
    """A device operation's event name is its whole HLO line. Keep the
    instruction's name, its opcode (with a custom call's target) and the
    result's type without layouts: ``fusion.410 fusion (f32[4,2048], bf16[...])``."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text[:width]
    m = OPCODE.search(rest)
    opcode = m.group(1) if m else ""
    result = LAYOUT.sub("", rest[: m.start()] if m else rest).strip()
    if opcode == "custom-call":
        t = TARGET.search(rest)
        opcode += ":" + t.group(1) if t else ""
    return f"{head.lstrip('%')} {opcode} {result}"[:width].rstrip()


def _self_times(ops: list[tuple[str, float, float]]) -> dict[str, float]:
    """Device time of each operation without the operations nested in it (a
    ``while`` and a ``call`` hold their bodies' operations as events of the
    same line): what is left sums to the busy time."""
    out: dict[str, float] = defaultdict(float)
    stack: list[tuple[str, float]] = []  # (name, end)
    for name, s, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]] -= dur
        out[name] += dur
        stack.append((name, s + dur))
    return out


def reduce(trace: dict, top: int = 10) -> dict:
    """busy_s (union of the device operations' intervals, averaged over the
    chips), window_s (first device operation's start to the last one's end),
    ops {short name: seconds of its own, one chip's mean}, op_events {short
    name: [(start_s, seconds)]}, modules and module_events likewise by
    program, the top operations by their own time, and the idle gaps by the
    innermost named host span over each gap's middle."""
    devs = trace["devices"]
    if not devs or not any(d["ops"] for d in devs.values()):
        return {"busy_s": 0.0, "window_s": 0.0, "ops": {}, "op_events": {}, "modules": {},
                "module_events": {}, "device_ops": [], "idle_gaps": []}
    t0 = min(s for d in devs.values() for _, s, _ in d["ops"])
    t1 = max(s + dur for d in devs.values() for _, s, dur in d["ops"])
    busy, ops, modules = 0.0, defaultdict(float), defaultdict(float)
    op_events: dict[str, list[tuple[float, float]]] = defaultdict(list)
    module_events: dict[str, list[tuple[float, float]]] = defaultdict(list)
    gaps: dict[str, float] = defaultdict(float)
    host = sorted(trace["host"], key=lambda e: e[1])
    names: dict[str, str] = {}
    ns = 1e-9
    for d in devs.values():
        short = [(names.setdefault(n, short_name(n)), s, dur) for n, s, dur in d["ops"]]
        merged = _union([(s, s + dur) for _, s, dur in short])
        busy += sum(b - a for a, b in merged)
        for name, own in _self_times(short).items():
            ops[name] += own
        for name, s, dur in short:
            op_events[name].append((s * ns, dur * ns))
        for name, s, dur in d["modules"]:
            modules[name] += dur
            module_events[name].append((s * ns, dur * ns))
        for (_, a), (b, _) in zip(merged, merged[1:]):
            gaps[_host_name_at(host, (a + b) / 2)] += b - a
    n = len(devs)
    return {
        "busy_s": busy / n * ns, "window_s": (t1 - t0) * ns,
        "ops": {k: v / n * ns for k, v in ops.items()},
        "op_events": dict(op_events),
        "modules": {k: v / n * ns for k, v in modules.items()},
        "module_events": dict(module_events),
        "device_ops": [[k, v / n * ns] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / n * ns] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
    }


def _host_name_at(host: list[tuple[str, float, float]], t: float) -> str:
    """The innermost (shortest) named host span that covers time ``t``."""
    best, best_dur = "host: no named span", float("inf")
    for name, s, dur in host:
        if s > t:
            break
        if s + dur >= t and dur < best_dur:
            best, best_dur = name, dur
    return best


def whole_executions(reduced: dict, module: str) -> tuple[float, float, int]:
    """(start_s, end_s, count) of a program's executions that the trace holds
    whole: its first and last events may be cut by the trace's edges, so they
    are left out."""
    events = sorted(reduced["module_events"].get(module, []))[1:-1]
    if not events:
        return 0.0, 0.0, 0
    return events[0][0], events[-1][0] + events[-1][1], len(events)


def op_seconds(reduced: dict, pattern: str, between: tuple[float, float] | None = None) -> float:
    """Device seconds (nested operations included) of the operations whose
    short name matches ``pattern``, optionally only those that start inside
    ``between``; summed over the chips."""
    rx = re.compile(pattern)
    lo, hi = between or (float("-inf"), float("inf"))
    return sum(dur for k, ev in reduced["op_events"].items() if rx.search(k)
               for s, dur in ev if lo <= s < hi)


def describe(path: str, top: int = 25) -> str:
    """What a trace holds, for reading by hand: planes, their lines with event
    counts, and each device line's operations by total time with one event's
    stats."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  line {line.name!r}: {len(events)} events")
            if not (DEVICE_PLANE.match(plane.name) or "bench" in line.name.lower()) and len(events) > 0:
                names = defaultdict(float)
                for e in events:
                    names[e.name] += e.duration_ns
                for k, v in sorted(names.items(), key=lambda kv: -kv[1])[:8]:
                    out.append(f"      {v / 1e6:10.3f} ms  {k[:100]}")
                continue
            total, count, sample = defaultdict(float), defaultdict(int), {}
            for e in events:
                total[e.name] += e.duration_ns
                count[e.name] += 1
                sample.setdefault(e.name, e)
            for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:top]:
                stats = {str(a): str(b)[:80] for a, b in sample[k].stats}
                out.append(f"      {v / 1e6:10.3f} ms x{count[k]:<5} {k[:90]}  {stats}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    target = sys.argv[1]
    print(describe(find_trace(target) if os.path.isdir(target) else target))
