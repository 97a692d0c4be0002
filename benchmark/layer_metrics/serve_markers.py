"""The engine's markers in a profiler capture (tony_tpu/serve/engine.py): empty
``serve.*`` annotations on the engine's thread whose ARGUMENTS carry what the
engine measured on its own clock, so that a traced run's line can report it.

    serve.visible        one a request, at the very end of the ``step()`` call
                         that admitted it: ``queue_us``, ``behind_us``,
                         ``prefill_us``, ``activate_us``, ``held_us`` (the five
                         consecutive parts of its time to first token, as its
                         caller feels it)
    serve.ahead, serve.kept_finish, serve.kept_admit, serve.kept_spec,
    serve.kept_chunk, serve.fresh
                         one a decode step, at the end of its emit, named by
                         why it ran as it did: ``n`` (ordinal since the last
                         ``reset_metrics``), ``admitted`` (prefills and chunks
                         the same call ran) and ``admit_us`` (what they took),
                         ``gap_us`` (the emit before -> this emit: one
                         inter-token sample)

Not a metric's reader itself: ``serve_ttft.py`` and ``serve_step.py`` read
through it. This jaxlib's ``ProfileData`` gives an annotation's keyword
arguments as the event's ``stats`` and leaves the name bare. A capture of a
program without the markers gives an empty list.

A request submitted before the capture began is not in the list: its marker's
parts hold what the profiler's own start cost the loop (hundreds of ms of
``queue_us`` in one request of ten), which is not the engine's."""

from __future__ import annotations

import functools
import os

from benchmark import trace_reduce

STEP_MARKERS = ("serve.ahead", "serve.kept_finish", "serve.kept_admit", "serve.kept_spec",
                "serve.kept_chunk", "serve.fresh")
VISIBLE = "serve.visible"
PARTS = ("queue_us", "behind_us", "prefill_us", "activate_us", "held_us")


def of_planes(planes) -> list[tuple[str, float, dict]]:
    """(name, start_ns, {argument: number}) of every marker on the host
    planes, in time order, but for the ``serve.visible`` markers of requests
    submitted before the first host event of the capture."""
    wanted = (*STEP_MARKERS, VISIBLE)
    found, began = [], float("inf")
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                began = min(began, e.start_ns)
                if e.name in wanted:
                    args = {str(k): v for k, v in e.stats if isinstance(v, (int, float))}
                    found.append((e.name, e.start_ns, args))
    # a marker stands where its request became visible: submit() was its five parts before
    return sorted((m for m in found if m[0] != VISIBLE
                   or m[1] - 1e3 * sum(m[2].get(p, 0) for p in PARTS) >= began),
                  key=lambda m: m[1])


@functools.lru_cache(maxsize=2)
def _of_file(path: str) -> list[tuple[str, float, dict]]:
    from jax.profiler import ProfileData

    return of_planes(ProfileData.from_file(path).planes)


def of_run(ctx: dict) -> list[tuple[str, float, dict]]:
    """The markers of the run's capture (under ``ctx['work']/trace``), read
    once for all the readers of one run; empty where there is no capture."""
    path = trace_reduce.find_trace(os.path.join(ctx["work"], "trace"))
    return _of_file(path) if path else []


def mean(values: list[float]) -> float | None:
    return sum(values) / len(values) if values else None
