"""Runtime adapter interface.

Rebuild of the reference's per-framework ``Framework`` adapter interfaces
(AMAdapter / TaskExecutorAdapter; SURVEY.md section 2 "Runtime adapters"):
given the AM-assembled cluster spec and the task's own identity, a runtime
builds the environment its framework needs to self-organise — TF_CONFIG for
TensorFlow, MASTER_ADDR/RANK for PyTorch, HOROVOD_* for Horovod, and the
jax.distributed coordinator contract for JAX (the TPU-native first-class
path, BASELINE.json north star).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass

from tony_tpu.config.config import TonyConfig
from tony_tpu.config.keys import Keys


@dataclass(frozen=True)
class TaskIdentity:
    """Everything an executor knows about itself after the gang barrier."""

    job_name: str
    index: int
    cluster_spec: dict[str, list[str]]   # type -> ["host:port", ...]
    coordinator_address: str             # rank-0 "host:port"
    process_id: int                      # global rank (-1 for untracked types)
    num_processes: int
    generation: int = 0

    @property
    def own_address(self) -> str:
        return self.cluster_spec[self.job_name][self.index]

    @classmethod
    def from_cluster_spec_response(cls, job_name: str, index: int, resp) -> "TaskIdentity":
        return cls(
            job_name=job_name,
            index=index,
            cluster_spec=json.loads(resp.spec_json),
            coordinator_address=resp.coordinator_address,
            process_id=resp.process_id,
            num_processes=resp.num_processes,
            generation=resp.generation,
        )


class Runtime:
    """Base adapter: subclasses override hooks they need."""

    name = "generic"

    def validate(self, config: TonyConfig) -> None:
        """Raise on invalid config for this framework (AM-side, pre-schedule)."""

    def build_env(self, identity: TaskIdentity, config: TonyConfig) -> dict[str, str]:
        """Env exported into the user training process (executor-side)."""
        env = {
            "TONY_CLUSTER_SPEC": json.dumps(identity.cluster_spec, sort_keys=True),
            "TONY_JOB_NAME": identity.job_name,
            "TONY_TASK_INDEX": str(identity.index),
            "TONY_COORDINATOR_ADDR": identity.coordinator_address,
            "TONY_PROCESS_ID": str(identity.process_id),
            "TONY_NUM_PROCESSES": str(identity.num_processes),
            "TONY_GENERATION": str(identity.generation),
        }
        # Checkpoint/resume glue (milestone config #5): the job config drives
        # the trainer's checkpointing; fit() reads these as FitConfig defaults
        # so a gang restart resumes at the last orbax step without the user
        # script hardcoding paths.
        ckpt_dir = config.get_str(Keys.CHECKPOINT_DIR)
        if ckpt_dir:
            env["TONY_CHECKPOINT_DIR"] = ckpt_dir
            env["TONY_CHECKPOINT_INTERVAL_STEPS"] = str(
                config.get_int(Keys.CHECKPOINT_INTERVAL_STEPS, 0)
            )
            env["TONY_CHECKPOINT_KEEP"] = str(config.get_int(Keys.CHECKPOINT_KEEP, 3))
            env["TONY_RESUME_FROM_CHECKPOINT"] = (
                "true" if config.get_bool(Keys.RESTART_RESUME_FROM_CHECKPOINT, True)
                else "false"
            )
        # Persistent XLA compilation cache: resubmits and gang restarts of
        # the same job load their executables instead of recompiling.
        # fit() and the serve gang host apply it (utils/compile_cache.py
        # holds the directory rule: JAX_COMPILATION_CACHE_DIR from outside
        # wins, else this key, else <checkout>/.jax_cache). Default on.
        if config.get_bool(Keys.TRAIN_JAX_CACHE, True):
            from tony_tpu.utils.compile_cache import (
                ENV_JOB_CACHE_DIR, default_cache_dir,
            )

            env[ENV_JOB_CACHE_DIR] = (
                config.get_str(Keys.TRAIN_JAX_CACHE_DIR, "")
                or default_cache_dir()
            )
        # One flag to get per-host traces (SURVEY.md section 5 "Tracing"):
        # the profiler server must live in the process doing the compute, so
        # the executor exports the intent and fit() starts it.
        if config.get_bool(Keys.PROFILER_ENABLED, False):
            env["TONY_PROFILER_PORT"] = str(config.get_int(Keys.PROFILER_PORT, 9999))
        # stack-trace collection for wedged jobs (obs.diagnostics glue)
        if config.get_bool(Keys.DIAGNOSTICS_ENABLED, False):
            env["TONY_TPU_DIAGNOSTICS"] = "1"
        return env

    def needs_data_port(self) -> bool:
        """Whether each task must reserve a data port for the cluster spec.

        True for frameworks whose processes listen on their spec address (TF
        parameter servers, the JAX coordinator); the executor bind-probes a
        free port before registering (reference: executor port allocation,
        SURVEY.md section 5).
        """
        return True


__all__ = ["Runtime", "TaskIdentity"]
