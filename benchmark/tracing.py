"""What every process of the benchmark that holds the chip does to JAX the same
way: starting and stopping the profiler (device operations and host
annotations; no Python-function tracing, which slows the host that the trace
is there to watch, and no HLO dump, which is most of the file's size),
counting the persistent compilation cache's hits and misses, and keeping
every program in that cache."""

from __future__ import annotations

CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


def start(trace_dir: str) -> None:
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def count_cache_events() -> dict[str, int]:
    """Registers a listener for JAX's own cache events in this process and
    returns the dict it counts into."""
    import jax.monitoring

    counts = dict.fromkeys(CACHE_EVENTS.values(), 0)

    def on_event(event: str, **_kw) -> None:
        if event in CACHE_EVENTS:
            counts[CACHE_EVENTS[event]] += 1

    jax.monitoring.register_event_listener(on_event)
    return counts


def keep_every_program() -> None:
    """The cache directory is JAX_COMPILATION_CACHE_DIR (run.py sets it). Keep
    every program there, however quick it was to build: the reference alone
    is some dozens, and each later run pays for those not kept."""
    import jax

    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
