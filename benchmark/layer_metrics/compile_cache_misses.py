"""JAX's own ``/jax/compilation_cache/cache_misses`` events in the process
that holds the chip, over the whole run: 0 once every program is cached."""


def read(name, ctx):
    cache = ctx["observed"].get("cache")
    return None if cache is None else float(cache["cache_misses"])
