"""What the Pallas kernel modules share: interpret-mode selection off-TPU
and the vma-carrying ``out_shape`` struct for kernels under ``shard_map``.

Targets the installed jax line only (``pyproject.toml``): callers use
``jax.shard_map``, ``pltpu.CompilerParams``, ``lax.axis_size``, ``lax.pcast``
and ``jax.typeof`` directly.
"""

from __future__ import annotations

import jax


def struct_with_vma(shape, dtype, *inputs) -> jax.ShapeDtypeStruct:
    """Pallas out_shape carrying the union of the inputs' varying-mesh-axes
    types, so a kernel called under ``shard_map`` type-checks (``check_vma``)
    without every call site spelling the set out."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in inputs))
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def use_interpret() -> bool:
    """Pallas interpret mode everywhere but a real TPU backend (CPU tests).
    Kernel modules bind this name in their own namespace, which is where
    tests/test_tpu_compile.py steers it; chip_smoke.py asserts the compiled
    form (``tpu_custom_call``) so a silent CPU backend cannot pass there."""
    return jax.default_backend() != "tpu"


__all__ = ["struct_with_vma", "use_interpret"]
