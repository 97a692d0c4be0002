"""Where JAX's persistent compilation cache lives: one rule, one call site.

Every process of this repo that compiles for the device (fit(), the serve
gang host, bench.py, chip_smoke.py) enables the cache through
:func:`enable_compile_cache`, so a resubmitted job or a restarted gang host
loads its executables instead of recompiling. The directory is part of the
cache key, so it must not move between runs:

1. ``JAX_COMPILATION_CACHE_DIR`` set from outside wins — JAX reads the
   variable itself, and this module sets NO directory in code (the variable
   rides client -> AM -> container -> executor -> user process because
   every hop copies ``os.environ``);
2. else the job's ``train.jax_cache_dir`` (the executor exports it as
   ``TONY_JAX_CACHE_DIR``);
3. else one fixed path inside the checkout, ``<repo>/.jax_cache`` — never
   under ``~``, never a temp/pid/timestamp name.
"""

from __future__ import annotations

import os

ENV_JAX_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
# the job key's transport (runtime/base.py build_env): absent when the job
# set train.jax_cache = false
ENV_JOB_CACHE_DIR = "TONY_JAX_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` (listed in .gitignore)."""
    import tony_tpu

    pkg = os.path.dirname(os.path.abspath(tony_tpu.__file__))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def enable_compile_cache(job_dir: str = "") -> str:
    """Turn the persistent cache on and return its directory. ``job_dir``
    is the job's ``train.jax_cache_dir`` ('' = not set)."""
    import jax

    directory = os.environ.get(ENV_JAX_CACHE_DIR, "")
    if not directory:
        directory = job_dir or default_cache_dir()
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_enable_compilation_cache", True)
    # cache every executable that took >= 1 s to build, whatever its size
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return directory


def enable_from_job_env() -> str:
    """What fit() and the serve gang host call: enable the cache when the
    job asked for it (``train.jax_cache``, default on — the executor then
    exports ``TONY_JAX_CACHE_DIR``) or ``JAX_COMPILATION_CACHE_DIR`` is set;
    a bare process with neither stays as JAX's defaults leave it.
    Returns the directory, '' when not enabled."""
    job_dir = os.environ.get(ENV_JOB_CACHE_DIR, "")
    if not (job_dir or os.environ.get(ENV_JAX_CACHE_DIR)):
        return ""
    return enable_compile_cache(job_dir)


def disable_compile_cache() -> None:
    """Keep this process off the persistent cache, also one that
    ``JAX_COMPILATION_CACHE_DIR`` would have switched on (elastic fit())."""
    import jax

    jax.config.update("jax_enable_compilation_cache", False)


__all__ = [
    "ENV_JAX_CACHE_DIR", "ENV_JOB_CACHE_DIR", "default_cache_dir",
    "disable_compile_cache", "enable_compile_cache", "enable_from_job_env",
]
