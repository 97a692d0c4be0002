"""TPU device metrics: the nvidia-smi analogue.

The reference's TaskMonitor shells out to ``nvidia-smi -q -x`` and parses the
XML for GPU utilisation (SURVEY.md section 2 "TaskMonitor"). There is no
device-side daemon to query on TPU; the equivalents live in the runtime the
training process already holds:

- ``device.memory_stats()`` — HBM bytes in use / peak / limit (PJRT exposes
  this on real TPU backends; the CPU backend returns None, in which case
  the source simply yields nothing).
- device duty cycle is not exposed through JAX's public API; the meaningful
  utilisation number on TPU is MFU, which the trainer computes from step
  timing (obs.metrics.StepTimer) and pushes through the same channel.

Because one TPU chip cannot be shared across processes, this source is only
useful *inside* the process that owns the device — fit() attaches it to its
metrics push; TaskMonitor.extra_sources takes it for user processes that run
their own sampler.
"""

from __future__ import annotations

import time

from tony_tpu.obs.monitor import Sample


def tpu_memory_samples() -> list[Sample]:
    """HBM usage samples for every local device; [] when unavailable."""
    import jax

    try:
        devices = jax.local_devices()
    except RuntimeError:  # no backend could be created in this process
        return []
    now = time.time()
    out: list[Sample] = []
    for d in devices:
        stats = d.memory_stats()  # None on the CPU backend
        if not stats:
            continue
        suffix = f"_dev{d.id}" if len(devices) > 1 else ""
        if "bytes_in_use" in stats:
            out.append((f"hbm_mb{suffix}", stats["bytes_in_use"] / 1e6, now))
        if "peak_bytes_in_use" in stats:
            out.append((f"hbm_peak_mb{suffix}", stats["peak_bytes_in_use"] / 1e6, now))
        if "bytes_limit" in stats:
            out.append((f"hbm_limit_mb{suffix}", stats["bytes_limit"] / 1e6, now))
    return out


def tpu_metrics_dict() -> dict[str, float]:
    """Same numbers keyed for a metrics-dict push (fit()'s on_metrics)."""
    return {name: value for name, value, _ in tpu_memory_samples()}


__all__ = ["tpu_memory_samples", "tpu_metrics_dict"]
