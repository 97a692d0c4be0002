"""Profiler glue: jax.profiler wired to a flag.

The reference has no profiler integration (SURVEY.md section 5 "Tracing":
logs + metrics sampler only); on TPU this is the highest-leverage
observability upgrade, kept deliberately thin: one flag
(``profiler.enabled``) starts the trace server inside the training process,
and ``trace_window`` dumps a perfetto-readable trace of N steps.

    with trace_window("/tmp/trace", enabled=step == 10) as cap:
        state, metrics = step_fn(state, ...)
        jax.block_until_ready(metrics)
    print(cap.path)  # the run dir the artifacts actually landed in

``trace_window`` is the ONE capture primitive: the coordinated fleet
profiler (obs/profile.py) drives it too, so artifact layout and
finalisation semantics cannot fork between ad-hoc and coordinated
captures.
"""

from __future__ import annotations

import contextlib
import logging
import os

import jax

log = logging.getLogger(__name__)


def start_server(port: int = 9999) -> bool:
    """Start the profiler's TCP server (for `tensorboard --logdir` capture
    or `jax.profiler.trace` remote attach). Returns False if unavailable."""
    try:
        jax.profiler.start_server(port)
        log.info("jax profiler server on :%d", port)
        return True
    except Exception:
        log.warning("could not start profiler server", exc_info=True)
        return False


class CaptureHandle:
    """Where a trace_window capture landed. ``path`` is the timestamped
    run directory jax.profiler actually wrote
    (``<log_dir>/plugins/profile/<run>/``) — the profiler names it by
    wall time, so without this handle callers cannot locate their own
    capture deterministically. Empty until the window finalises; stays
    empty when finalisation failed (the ``ok`` flag says which)."""

    __slots__ = ("log_dir", "path", "ok")

    def __init__(self, log_dir: str = ""):
        self.log_dir = log_dir
        self.path = ""
        self.ok = False


def latest_run_dir(log_dir: str) -> str:
    """Newest profiler run directory under ``log_dir`` ('' when none):
    jax.profiler writes ``<log_dir>/plugins/profile/<wallclock_run>/``."""
    root = os.path.join(log_dir, "plugins", "profile")
    try:
        runs = sorted(
            d for d in os.listdir(root)
            if os.path.isdir(os.path.join(root, d))
        )
    except OSError:
        return ""
    return os.path.join(root, runs[-1]) if runs else ""


@contextlib.contextmanager
def trace_window(log_dir: str, enabled: bool = True):
    """Trace everything inside the block into ``log_dir`` (perfetto/XPlane);
    yields a :class:`CaptureHandle` whose ``path`` is the run directory the
    artifacts landed in once the block exits.

    The caller must block_until_ready inside the window for device activity
    to be attributed (dispatch is async). Finalisation is try/finally: an
    exception inside the traced block still stops the trace and logs where
    it landed — the partial trace of a crashing step is exactly the one
    worth keeping, and an unfinalised profiler session would poison the
    next trace_window with a "already tracing" error."""
    handle = CaptureHandle(log_dir)
    if not enabled:
        yield handle
        return
    jax.profiler.start_trace(log_dir)
    try:
        yield handle
    finally:
        # swallow a stop_trace failure (logging it): raising here would
        # mask an in-flight exception from the traced block, and the
        # success line must not lie about a trace that never landed
        try:
            jax.profiler.stop_trace()
        except Exception:
            log.warning(
                "profiler trace finalisation failed for %s", log_dir,
                exc_info=True,
            )
        else:
            handle.ok = True
            # resolve the timestamped run dir so callers (the fleet
            # profiler's manifest, ad-hoc scripts) can point at THIS
            # capture instead of globbing the shared log_dir
            handle.path = latest_run_dir(log_dir) or log_dir
            log.info("profiler trace written to %s", handle.path)


def annotate(name: str, **numbers):
    """Named region in traces: ``with annotate('data-load'): ...``

    The one spelling of ``jax.profiler.TraceAnnotation`` in ``tony_tpu/``
    (train/loop.py, serve/engine.py, obs/profile.py). Pass a literal, bare
    dotted name (``serve.plan``), no digits. ``numbers`` are numeric
    arguments of the event — durations in microseconds, counts, a step's
    ordinal — and only for a name that has them at every call (the engine's
    markers ``serve.ahead`` ... ``serve.visible``): the phase names that
    readers match whole (``serve.plan``, ``train.step``) take none, ever.
    Never a request id: request identity belongs in the journal span beside
    it (obs/trace.py). With no profiler session on, a block costs one small
    object and the profiler's own active check, and the numbers are not
    encoded (tests/test_perf_guard.py holds both forms to the other
    disarmed hooks' bound)."""
    return jax.profiler.TraceAnnotation(name, **numbers)


__all__ = [
    "CaptureHandle", "annotate", "latest_run_dir", "start_server",
    "trace_window",
]
