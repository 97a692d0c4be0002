"""Device-mesh construction: the ICI x DCN axis layout.

The reference delegates all parallelism to user frameworks (SURVEY.md section 2
"Parallelism strategies": TonY orchestrates NCCL/Gloo rings via env variables,
implements none itself). Here the mesh is first-class: axes

- ``dp``   -- pure data parallel (params replicated, grads psum'd)
- ``pp``   -- pipeline parallel (layer stages, GPipe microbatch schedule)
- ``fsdp`` -- data parallel with parameter/optimizer sharding (ZeRO-style)
- ``ep``   -- expert parallel (MoE expert dim; doubles as a batch axis)
- ``tp``   -- tensor (Megatron-style) parallel over heads / ffn hidden
- ``sp``   -- sequence/context parallel (ring attention over lax.ppermute)

Collectives over these axes ride ICI within a slice; a multi-slice job maps its
slice-crossing axis (usually ``dp``) onto DCN by putting it outermost, which is
what ``mesh_utils.create_device_mesh`` produces for contiguous device order.
``pp`` sits next (stage hops are one point-to-point ppermute per tick —
latency-tolerant); bandwidth-hungry fsdp/ep/tp/sp stay innermost on the
shortest ICI paths. The GPipe schedule lives in tony_tpu.parallel.pipeline,
the expert dispatch in tony_tpu.parallel.moe; both are reachable from the
trainer via this mesh (LlamaConfig n_experts / FitConfig mesh_shape.pp).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import AxisType, Mesh, get_abstract_mesh

# Canonical axis order: slice-crossing / outermost first.
MESH_AXES = ("dp", "pp", "fsdp", "ep", "tp", "sp")


@dataclass(frozen=True)
class MeshShape:
    """Per-axis sizes. Product must equal the number of devices used."""

    dp: int = 1
    pp: int = 1
    fsdp: int = 1
    ep: int = 1
    tp: int = 1
    sp: int = 1

    @property
    def sizes(self) -> tuple[int, ...]:
        return (self.dp, self.pp, self.fsdp, self.ep, self.tp, self.sp)

    @property
    def n_devices(self) -> int:
        return math.prod(self.sizes)

    def __post_init__(self) -> None:
        for name, v in zip(MESH_AXES, self.sizes):
            if v < 1:
                raise ValueError(f"mesh axis {name!r} must be >= 1, got {v}")


def default_shape(n_devices: int, *, tp: int = 1, sp: int = 1) -> MeshShape:
    """FSDP-first default: all non-tp/sp parallelism goes to ``fsdp``.

    FSDP is the right default on TPU (params sharded over ICI, all-gathered
    per-layer: HBM-bound win) the way plain DP was the reference's Horovod
    default.
    """
    if n_devices % (tp * sp):
        raise ValueError(f"{n_devices} devices not divisible by tp*sp={tp * sp}")
    return MeshShape(dp=1, fsdp=n_devices // (tp * sp), tp=tp, sp=sp)


# The mesh model-level hooks (attention_impl='ring'/'flash') resolve against;
# build_mesh registers every mesh it constructs.
_DEFAULT_MESH: Mesh | None = None


def set_default_mesh(mesh: Mesh | None) -> None:
    global _DEFAULT_MESH
    _DEFAULT_MESH = mesh


def get_default_mesh() -> Mesh | None:
    return _DEFAULT_MESH


def inside_manual_region() -> bool:
    """True when tracing inside a shard_map manual computation (e.g. a pp
    pipeline stage). A merely non-empty abstract mesh is NOT enough: a
    ``jax.sharding.use_mesh`` context also sets one, with Auto/Explicit axis
    types — only Manual axes mean an enclosing shard_map region that shardy
    forbids re-binding collective axes inside."""
    mesh = get_abstract_mesh()
    if mesh is None or not mesh.shape_tuple:
        return False
    return any(t == AxisType.Manual for t in mesh.axis_types)


def build_mesh(shape: MeshShape | None = None, devices: list | None = None) -> Mesh:
    """Build a ``jax.sharding.Mesh`` with the canonical axis names.

    ``devices`` defaults to all local devices; shape defaults to
    ``default_shape(len(devices))``. Uses ``mesh_utils.create_device_mesh`` so
    that physically-near devices land on inner (tp/sp) axes -- inner axes carry
    the latency-sensitive collectives and should ride the shortest ICI hops.
    """
    if devices is None:
        devices = jax.devices()
    if shape is None:
        shape = default_shape(len(devices))
    if shape.n_devices > len(devices):
        raise ValueError(
            f"mesh shape {shape.sizes} needs {shape.n_devices} devices, "
            f"got {len(devices)}"
        )
    if shape.n_devices < len(devices):
        # Truncation is only safe single-process (sub-meshes of one host's
        # devices, mostly tests): multi-host, the first-N global devices can
        # exclude every device of some process, which then fails far from the
        # config mistake. Loud warning either way — idle chips are a bug.
        import logging

        if jax.process_count() > 1:
            raise ValueError(
                f"mesh shape {shape.sizes} uses {shape.n_devices} of "
                f"{len(devices)} devices; undersized meshes are not allowed "
                "multi-host (some processes would own no mesh device)"
            )
        logging.getLogger(__name__).warning(
            "mesh shape %s uses only %d of %d devices; %d idle",
            shape.sizes, shape.n_devices, len(devices), len(devices) - shape.n_devices,
        )
    devices = list(devices)[: shape.n_devices]
    try:
        dev_array = mesh_utils.create_device_mesh(shape.sizes, devices=devices)
    except (ValueError, AssertionError):
        if devices[0].platform == "tpu":
            # real chips carry topology metadata: a layout JAX cannot place
            # on it is a config error, not something to paper over with a
            # raveled order that puts far chips on the inner axes
            raise
        # Virtual/CPU device sets lack topology metadata; fall back to raveled order.
        dev_array = np.asarray(devices).reshape(shape.sizes)
    return Mesh(dev_array, MESH_AXES)


def build_multislice_mesh(
    per_slice: MeshShape,
    n_slices: int,
    devices: list | None = None,
) -> Mesh:
    """ICI x DCN hybrid mesh for multi-slice jobs.

    The slice-crossing axis is ``dp`` (gradient all-reduce tolerates DCN
    latency; everything bandwidth-hungry — fsdp/tp/sp — stays inside a
    slice's ICI). The resulting mesh has the same four canonical axes, with
    dp = n_slices * per_slice.dp; on real multi-slice TPU metadata,
    mesh_utils.create_hybrid_device_mesh lays devices out so the outer dp
    factor crosses DCN. SURVEY.md section 2 "Distributed communication
    backend": multi-slice via a dcn-parallel outer axis.
    """
    if devices is None:
        devices = jax.devices()
    total = per_slice.n_devices * n_slices
    if total != len(devices):
        raise ValueError(
            f"{n_slices} slices x {per_slice.sizes} = {total} devices, "
            f"got {len(devices)}"
        )
    ici_shape = per_slice.sizes
    dcn_shape = (n_slices,) + (1,) * (len(MESH_AXES) - 1)
    try:
        dev_array = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devices
        )
    except (ValueError, AssertionError, KeyError, AttributeError):
        # No slice metadata (CPU/virtual devices): raveled fallback keeps the
        # same logical shape so sharding code still compiles.
        dev_array = np.asarray(devices).reshape(
            tuple(i * d for i, d in zip(ici_shape, dcn_shape))
        )
    return Mesh(dev_array, MESH_AXES)


def single_device_mesh() -> Mesh:
    """A 1x1x1x1 mesh over one device -- lets single-chip code share the
    sharded code path (all PartitionSpecs collapse to replication)."""
    return build_mesh(MeshShape(), devices=jax.devices()[:1])
