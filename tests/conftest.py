"""Test bootstrap: force an 8-device virtual CPU platform BEFORE jax imports.

This is the survey's MiniCluster lesson applied to JAX (SURVEY.md section 4):
fake the substrate (devices), keep every framework code path real. Multi-chip
sharding logic runs on 8 virtual CPU devices; correctness on the real chip is
`python chip_smoke.py` through the chip tool (README "Quick start").
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # tests run on the CPU backend only
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# also through the API: a jax imported before this file (a plugin, a
# sitecustomize) has already read the variable; the backend itself is
# created lazily, so this still wins
import jax

jax.config.update("jax_platforms", "cpu")


import pytest


@pytest.fixture(autouse=True)
def _reset_default_mesh():
    """The default mesh is process-global (fit()/tests register it explicitly);
    reset between tests so a mesh from one test can't leak into another's
    model hooks (attention_impl='flash'/'ring')."""
    yield
    from tony_tpu.parallel.mesh import set_default_mesh

    set_default_mesh(None)


@pytest.fixture
def paged_kernel(monkeypatch):
    """``paged_kernel(on)``: run the paged decode attention through its Pallas
    kernel (interpreted here) or through the XLA scan. The op chooses from the
    platform (ops/decode_attention.py ``_run_kernel``), which is the CPU here,
    so a test that wants the kernel steers that name — no option of the
    program. Decode steps compiled under one choice must not serve the other:
    the engine's module-wide step caches are dropped at every change."""
    from tony_tpu.ops import decode_attention as da
    from tony_tpu.serve import engine

    def drop_steps():
        engine._decode_fn.cache_clear()
        engine._aot_decode_cache.clear()

    def steer(on: bool):
        drop_steps()
        monkeypatch.setattr(da, "_run_kernel", lambda: bool(on))

    yield steer
    drop_steps()


@pytest.fixture(scope="session")
def host_trace_events():
    """``host_trace_events(log_dir)``: the profiler's host events of the ONE
    trace under ``log_dir`` that tell what the serving engine did, as
    ``(name, start_ns, end_ns)`` in start order: its ``serve.*`` phases
    (obs/profiler.annotate) and every jitted call, ``PjitFunction(<name>)``
    for a jitted function, ``PjitFunction(jit(<name>))`` for a compiled
    executable — an eager op is a jitted call of its primitive's name here.
    The profiler writes a call twice (the call and its fast path)."""
    import glob

    def read(log_dir):
        (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"), recursive=True)
        events = []
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                events += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events
                           if e.name.startswith(("serve.", "PjitFunction("))]
        return sorted(events, key=lambda e: e[1])

    return read


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """scripts/lint.py-style budget line: tier-1 runs close to its 870s
    timeout, so every run prints the top-10 slowest tests — future PRs see
    where the wall clock goes BEFORE they blow the budget (the cheap fix
    is usually a slow-mark on a redundant engine build, the PR 14/17
    pattern)."""
    durations = []
    for reports in terminalreporter.stats.values():
        for rep in reports:
            if getattr(rep, "when", "") == "call" and hasattr(rep, "duration"):
                durations.append((rep.duration, rep.nodeid))
    if not durations:
        return
    durations.sort(reverse=True)
    total = sum(d for d, _ in durations)
    top = durations[:10]
    terminalreporter.write_sep(
        "-", f"tier-1 wall clock: {total:.1f}s in test calls; top 10"
    )
    for dur, nodeid in top:
        terminalreporter.write_line(f"  {dur:7.2f}s  {nodeid}")
    terminalreporter.write_line(
        f"  ({sum(d for d, _ in top):.1f}s = "
        f"{100.0 * sum(d for d, _ in top) / total:.0f}% of the call total; "
        "budget 870s — slow-mark redundant heavy tests, don't delete them)"
    )
