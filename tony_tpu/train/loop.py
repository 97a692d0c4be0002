"""The user-facing training loop: fit() for tony-tpu jobs.

Ties together the pieces a reference-TonY user had to hand-roll in their
script: jax.distributed bootstrap (from the AM env), mesh construction,
sharded state init, orbax checkpoint resume (the elastic-restart contract,
milestone config #5), the jitted train step, and per-step throughput/MFU
metrics. A complete distributed trainer is:

    from tony_tpu.train import fit, FitConfig
    fit(FitConfig(model=LlamaConfig.llama2_7b(), steps=1000, ...))

Startup and the steady-state loop are overlapped (docs/PERF.md "Overlap"):

- **compile-ahead**: the train step is AOT-lowered and compiled on a worker
  thread, concurrently with sharded state init, checkpoint restore, and
  input warmup — registered->first-step pays max(compile, restore,
  first-batch) instead of their sum, compounding with the persistent XLA
  cache (utils/compile_cache.py).
- **device prefetch**: with DataConfig.prefetch > 0 (default 2) the batch
  stream runs on a background thread (train/prefetch.py), so host batch
  synthesis + H2D placement for step N+1 overlap the device's step N.
- **stall-free telemetry**: metrics pushes are queued to a daemon thread
  (obs/reporter.py) and the log-boundary device sync is deferred until the
  next step is dispatched, so neither an AM RPC stall nor a loss fetch
  drains the pipeline. The very first step still syncs and pushes
  immediately — it timestamps the submit->first-step north-star metric.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from tony_tpu.models.llama import LlamaConfig, train_flops_per_token
from tony_tpu.obs import hbm, health, profile, series, slo, trace
from tony_tpu.obs import compiles as compile_ledger
from tony_tpu.obs.metrics import StepTimer, device_identity, device_samples
from tony_tpu.obs.registry import HistogramWindow, Registry, snapshot_to_app_dir
from tony_tpu.parallel.mesh import MeshShape, build_mesh
from tony_tpu.parallel.sharding import DEFAULT_RULES, Rules, spec_for
from tony_tpu.runtime import jax_tpu
from tony_tpu.train.data import DataConfig, make_batches
from tony_tpu.train.prefetch import close_batches
from tony_tpu.train.trainer import (
    default_optimizer,
    make_train_state,
    make_train_step,
    train_state_avals,
)
from tony_tpu.utils import compile_cache

log = logging.getLogger(__name__)


@dataclass
class FitConfig:
    model: LlamaConfig = field(default_factory=LlamaConfig.tiny)
    data: DataConfig = field(default_factory=DataConfig)
    mesh_shape: MeshShape | None = None   # None -> FSDP over all devices
    steps: int = 100
    log_every: int = 10
    checkpoint_dir: str = ""
    checkpoint_every: int = 0
    checkpoint_keep: int = 3
    lr: float = 3e-4
    warmup_steps: int = 100
    rules: Rules = field(default_factory=lambda: dict(DEFAULT_RULES))
    # pipeline microbatches when mesh_shape.pp > 1 (0 -> 2 per stage)
    pp_microbatches: int = 0
    # 'gpipe' (autodiff bwd, O(M) activations) | '1f1b' (interleaved
    # hand-scheduled bwd, O(P) activations)
    pp_schedule: str = "gpipe"
    # hook called every log_every steps with a metrics dict (obs -> AM push)
    on_metrics: Callable[[dict], None] | None = None
    resume: bool = True  # restore from checkpoint_dir if a checkpoint exists
    # AOT-compile the train step on a worker thread during startup (overlaps
    # state init / restore / input warmup); False pins the lazy jit path
    compile_ahead: bool = True
    # Adam first-moment dtype ('float32' | 'bfloat16'); bf16 frees
    # 2 bytes/param of HBM (see default_optimizer / docs/PERF.md)
    mu_dtype: str = "float32"
    # loss-head implementation override: '' keeps model.ce_impl; 'scan' /
    # 'pallas' select the fused chunked CE (tony_tpu.ops.fused_ce — no
    # [B,S,V] logits transient), 'dense' the legacy full-logits head
    ce_impl: str = ""
    # MoE dispatch override: '' keeps model.moe_dispatch; 'grouped' selects
    # the dropless sorted grouped GEMM, 'gather'/'einsum' the capacity paths
    # (tony_tpu.parallel.moe — docs/PERF.md "Grouped MoE")
    moe_dispatch: str = ""
    # comm/compute overlap override (tony_tpu.ops.overlap, docs/PERF.md
    # "Overlap (collectives)"): '' keeps model.overlap_impl; 'scan'/'pallas'
    # stream the fsdp weight all-gathers per-chunk through the decomposed
    # ppermute-ring matmuls instead of blocking up front
    overlap_impl: str = ""
    # dp gradient-reduction bucket size in MiB (0 disables — GSPMD's single
    # fused all-reduce): > 0 switches the step to the manual-dp bucketed
    # path, one collective per ~bucket of grad leaves so each reduce
    # dispatches as its layers' backward completes. Size it from the
    # measured anatomy report: ops.overlap.bucket_bytes_from_report
    # (achieved_gbps x per-layer backward window). Needs dp > 1, pp == 1.
    grad_bucket_mb: float = 0.0
    # grouped-GEMM row tile override (0 keeps model.moe_group_block)
    moe_group_block: int = 0
    # MoE ep-combine overlap override (tony_tpu.ops.moe_overlap, docs/
    # PERF.md "Round 20"): '' keeps model.moe_overlap_impl; 'scan'/'pallas'
    # decompose the grouped path's post-FFN combine psum into per-token-
    # chunk partial combines so expert compute overlaps combine traffic;
    # 'off' pins the single blocking psum
    moe_overlap_impl: str = ""
    # overlap chunk tokens per shard override (0 keeps
    # model.moe_overlap_chunk; size measured captures via
    # ops.moe_overlap.chunk_tokens_from_report)
    moe_overlap_chunk: int = 0
    # elastic training (tony_tpu/elastic/, docs/ELASTIC.md): gang size at
    # full strength. 0 disables; >= 2 makes the mesh runtime-swappable —
    # the dp axis maps to members and shrinks/grows at AM-declared
    # generation boundaries while training continues from the in-memory
    # state of survivors. mesh_shape then means the PER-MEMBER shape
    # (dp must stay 1) and data.global_batch the full-membership batch.
    elastic_members: int = 0
    # scripted membership plan {step: (member, ...)} applied at step
    # boundaries — the in-process twin of the AM's generation broadcast
    # (bench `elastic` section + tests drive shrink/grow through it)
    elastic_plan: dict | None = None
    # broadcast + journal root; empty -> TONY_APP_DIR (the shared app dir
    # the AM writes generation.json into)
    elastic_dir: str = ""
    # checkpoint-shadow stride in steps (0 -> env/default 16)
    elastic_shadow_steps: int = 0

    def apply_job_env(self) -> None:
        """Fill unset checkpoint fields from the TONY_CHECKPOINT_* env the
        executor exported (the checkpoint.dir / checkpoint.interval_steps /
        restart.resume_from_checkpoint job-config glue), and arm elastic
        membership from the TONY_ELASTIC* env the ElasticRuntime exports."""
        if not self.checkpoint_dir and os.environ.get("TONY_CHECKPOINT_DIR"):
            self.checkpoint_dir = os.environ["TONY_CHECKPOINT_DIR"]
            if self.checkpoint_every == 0:
                self.checkpoint_every = int(
                    os.environ.get("TONY_CHECKPOINT_INTERVAL_STEPS", "0")
                )
            self.checkpoint_keep = int(
                os.environ.get("TONY_CHECKPOINT_KEEP", str(self.checkpoint_keep))
            )
            self.resume = os.environ.get("TONY_RESUME_FROM_CHECKPOINT", "true") == "true"
        if self.elastic_members == 0 and os.environ.get("TONY_ELASTIC") == "1":
            self.elastic_members = int(
                os.environ.get("TONY_ELASTIC_MEMBERS", "0") or 0
            )


def fit(cfg: FitConfig) -> dict:
    """Run the training loop to cfg.steps; returns final metrics."""
    from tony_tpu.obs.diagnostics import diagnostics_context

    # join the job's trace spine (no-op outside a traced tony-tpu job);
    # every span below nests under train.fit on the merged timeline — the
    # root handle rides into _fit because the compile-ahead worker thread
    # has an empty span stack and must parent on it explicitly
    trace.install_from_env()
    # arm the HBM observatory (idempotent; TONY_OBS_HBM=0 disables) and the
    # OOM guard: a RESOURCE_EXHAUSTED escaping the loop dumps the device
    # memory profile + compile ledger + watermark history into the app dir
    # before re-raising (obs/hbm.py, docs/OBS.md "Memory and compiles")
    hbm.install_from_env()
    # arm the numerics sentinel (idempotent; TONY_OBS_HEALTH=0 disables)
    # BEFORE the train step is built, so the in-graph value monitors are
    # fused into it (obs/health.py, docs/OBS.md "Numerics health")
    health.install_from_env()
    # arm the live time-series recorder + SLO engine (idempotent;
    # TONY_OBS_SERIES=0 disables): stride-scraped step/goodput/HBM points
    # journal under the app dir and feed burn-rate alerting
    # (obs/series.py, obs/slo.py, docs/OBS.md "SLO + time series")
    series.install_from_env()
    # arm the coordinated-profiling controller (idempotent; TONY_OBS_PROFILE=0
    # disables): `tony profile <app_id>` broadcasts a bounded window and the
    # maybe_capture seam in the step loop captures a jax.profiler device
    # trace into <app_dir>/profile/<proc>/ (obs/profile.py, docs/OBS.md
    # "Step anatomy")
    profile.install_from_env()
    with diagnostics_context(), trace.span("train.fit", steps=cfg.steps) as root:
        with hbm.oom_guard("fit"):
            return _fit(cfg, root)


def _start_async_host_copy(metrics: dict) -> None:
    """Kick off D2H transfers for the scalars a log boundary will read, so
    the later float() is a cheap wait instead of a fresh blocking fetch."""
    for key in ("loss", "grad_norm"):
        arr = metrics.get(key)
        if hasattr(arr, "copy_to_host_async"):
            arr.copy_to_host_async()


class _Elastic:
    """fit()'s elastic runtime: the swappable topology + its bookkeeping.

    Owns the member-granular :class:`~tony_tpu.elastic.ElasticTopology`,
    the generation watcher, the host-RAM checkpoint shadow, and the
    membership-aware batch stream; :meth:`reshard` is the generation
    boundary — fence, donate, rebuild, continue (docs/ELASTIC.md).
    """

    def __init__(self, cfg: FitConfig):
        from tony_tpu import elastic

        if jax.process_count() > 1:
            raise NotImplementedError(
                "elastic fit() is single-controller: the trainer process "
                "owns every live member's devices (jax.process_count() "
                "must be 1; member seats are separate non-jax agents)"
            )
        self._elastic = elastic
        self.cfg = cfg
        # ONE parser for the TONY_ELASTIC* contract (ElasticSettings
        # .from_env); FitConfig fields override what they own
        settings = (
            elastic.ElasticSettings.from_env() or elastic.ElasticSettings()
        )
        settings.members = cfg.elastic_members or settings.members
        if cfg.elastic_dir:
            settings.app_dir = cfg.elastic_dir
        elif not settings.app_dir:
            # FitConfig-armed elastic inside a tony job still journals to
            # the shared app dir
            settings.app_dir = os.environ.get("TONY_APP_DIR", "")
        if cfg.elastic_shadow_steps:
            settings.shadow_interval_steps = cfg.elastic_shadow_steps
        self.controller = elastic.ElasticController(
            settings, watch=bool(settings.app_dir)
        )
        self.topology = elastic.ElasticTopology(
            cfg.elastic_members, per_member=cfg.mesh_shape
        )
        self.shadow = elastic.ShadowStore(
            interval_steps=settings.shadow_interval_steps
        )
        self.mesh = self.topology.mesh_for(self.controller.members)
        self.stream = None   # built once fit knows the batch sharding
        self.plan = dict(cfg.elastic_plan or {})
        self.reshards = 0
        self.reshard_s = 0.0

    @property
    def journal(self):
        return self.controller.journal

    def make_stream(self, batch_sharding, start_step: int):
        self.stream = self._elastic.ElasticBatchStream(
            self.cfg.data, self.cfg.elastic_members, self.controller.members,
            batch_sharding, start_step=start_step,
        )
        return self.stream

    def pending(self, step: int):
        """The membership change to apply at this boundary, if any: the
        scripted plan (bench/tests) outranks the file broadcast so a plan
        stays deterministic even inside a traced job. A record whose
        membership already matches (e.g. a member died and grew back
        between two boundaries — net no-op) is adopted here, where
        membership is settled, without a reshard."""
        members = self.plan.pop(step, None)
        if members is not None and set(members) != set(self.controller.members):
            old = set(self.controller.members)
            new = set(int(m) for m in members)
            return self._elastic.GenerationRecord(
                generation=self.controller.generation + 1,
                members=tuple(sorted(new)),
                boundary="shrink" if old - new else "grow",
                dead=tuple(sorted(old - new)),
                added=tuple(sorted(new - old)),
                reason="scripted plan",
            )
        rec = self.controller.pending()
        if rec is not None and set(rec.members) == set(self.controller.members):
            self.controller.applied(rec)
            return None
        return rec

    def note_step(self, step: int) -> None:
        if self.journal is not None:
            self.journal.step(
                step, self.controller.generation, self.controller.members
            )

    def reshard(self, rec, step: int, state, optimizer, rules, ledger):
        """One generation boundary: returns the rebuilt
        ``(state, step_fn, compiled_step, mesh, batch_sharding)``.

        The span is the restart-cost evidence: ``tony trace`` goodput's
        ``restart_s`` bucket sums ``elastic.reshard`` spans (the warm
        path) next to relaunch gaps (the cold one).
        """
        from tony_tpu.parallel.mesh import set_default_mesh
        from tony_tpu.parallel.sharding import spec_for
        from tony_tpu.train.trainer import (
            make_train_step, state_shardings, train_state_avals,
        )

        cfg = self.cfg
        members = tuple(sorted(rec.members))
        t0 = time.perf_counter()
        members_str = ",".join(str(m) for m in members)
        dead_str = ",".join(str(m) for m in rec.dead)
        with trace.span(
            "elastic.reshard", generation=rec.generation,
            boundary=rec.boundary, at_step=step,
            members=members_str, dead=dead_str,
        ):
            # fence: drain the dispatch backlog, then take the exact
            # current state device->host — the donation every survivor
            # (and a grown-back member) reshards from. Zero steps lost:
            # the recovery point IS the fenced state, the periodic shadow
            # is only the fallback when a fence cannot complete.
            jax.block_until_ready(state)
            host_state = self.shadow.capture_sync(step, state)
            self.mesh = self.topology.mesh_for(members)
            set_default_mesh(self.mesh)
            shardings = state_shardings(cfg.model, self.mesh, optimizer, rules)
            state = self._elastic.reshard_state(host_state, shardings)
            step_fn = make_train_step(
                cfg.model, self.mesh, optimizer, rules,
                n_microbatches=cfg.pp_microbatches,
                pp_schedule=cfg.pp_schedule,
                grad_bucket_bytes=int(cfg.grad_bucket_mb * (1 << 20)),
            )
            batch_sharding = NamedSharding(
                self.mesh, spec_for(("batch", "seq"), cfg.rules)
            )
            skipped = self.stream.reshard(members, batch_sharding)
            compiled = None
            if cfg.compile_ahead:
                # re-lower against the shrunk/grown topology through the
                # same AOT path startup uses (persistent XLA cache makes a
                # grow back to a previously-seen shape a cache hit)
                batch_aval = jax.ShapeDtypeStruct(
                    (self.stream.global_batch, cfg.data.seq_len), jnp.int32
                )
                try:
                    with ledger.label("train.step"):
                        compiled = step_fn.lower(
                            train_state_avals(cfg.model, optimizer),
                            batch_aval, batch_aval,
                        ).compile()
                except Exception:
                    log.warning(
                        "elastic re-lower failed; jit dispatch compiles "
                        "lazily", exc_info=True,
                    )
        dt = time.perf_counter() - t0
        self.reshards += 1
        self.reshard_s += dt
        if self.journal is not None:
            self.journal.reshard(
                generation=rec.generation, at_step=step,
                boundary=rec.boundary, members=members, dead=rec.dead,
                added=rec.added, skipped=skipped, reshard_s=dt,
                lost_steps=0,
            )
        self.controller.applied(rec)
        if jax.process_index() == 0:
            log.warning(
                "elastic generation %d (%s) applied at step %d in %.2fs: "
                "members=%s global_batch=%d",
                rec.generation, rec.boundary, step, dt, list(members),
                self.stream.global_batch,
            )
        return state, step_fn, compiled, self.mesh, batch_sharding

    def summary(self) -> dict:
        return {
            "generation": self.controller.generation,
            "members": list(self.controller.members),
            "reshards": self.reshards,
            "reshard_s": round(self.reshard_s, 3),
            "shadow_dropped": self.shadow.dropped,
        }

    def close(self) -> None:
        self.shadow.close()
        if self.stream is not None:
            self.stream.close()
        self.controller.close()


def _fit(cfg: FitConfig, fit_span=trace.NOOP_SPAN) -> dict:
    jax_tpu.initialize()  # no-op outside a tony-tpu job
    # what this process came up on, said once: a job that fell to the CPU
    # must be visible in the log and (first metrics push below) the job
    # history, not only slow
    identity = device_identity()
    log.info(
        "devices: platform=%(platform)s kind=%(device_kind)s "
        "count=%(device_count)d", identity,
    )
    # always-on compile journal (obs/compiles.py): every XLA backend
    # compile during this run is an entry; the shutdown summary and
    # `tony compiles <app_id>` report from it
    ledger = compile_ledger.get_ledger()
    compiles_t0 = ledger.backend_compiles
    hbm_watch = hbm.active_watch()
    # run-scoped watermark mark: the shutdown summary reports THIS run's
    # peak via the attribution rule (hbm.measure_since), not the process's
    # cumulative counter — a second fit() in the same process (bench
    # sweeps) must not inherit the first one's peak
    hbm_mark = hbm_watch.mark() if hbm_watch is not None else None
    cfg.apply_job_env()
    if (cfg.ce_impl or cfg.moe_dispatch or cfg.moe_group_block
            or cfg.overlap_impl or cfg.moe_overlap_impl
            or cfg.moe_overlap_chunk):
        from dataclasses import replace as _replace

        overrides = {}
        if cfg.ce_impl:
            overrides["ce_impl"] = cfg.ce_impl
        if cfg.moe_dispatch:
            overrides["moe_dispatch"] = cfg.moe_dispatch
        if cfg.moe_group_block:
            overrides["moe_group_block"] = cfg.moe_group_block
        if cfg.overlap_impl:
            overrides["overlap_impl"] = cfg.overlap_impl
        if cfg.moe_overlap_impl:
            overrides["moe_overlap_impl"] = cfg.moe_overlap_impl
        if cfg.moe_overlap_chunk:
            overrides["moe_overlap_chunk"] = cfg.moe_overlap_chunk
        cfg.model = _replace(cfg.model, **overrides)
    if cfg.elastic_members >= 2:
        # elastic runs re-lower the step per generation; round-tripping
        # those executables through the persistent cache corrupts the
        # process on this jax line (a deserialized executable for a
        # previously-seen topology aborts a few steps after a grow
        # boundary). The cache's win is submit->first-step; the elastic
        # warm path keeps survivors' executables in memory anyway.
        log.info("elastic fit: persistent XLA cache disabled")
        compile_cache.disable_compile_cache()
    else:
        # persistent XLA compilation cache (train.jax_cache, default on in
        # a tony job; outside one only when JAX_COMPILATION_CACHE_DIR asks):
        # a resubmitted or gang-restarted job loads its executables instead
        # of recompiling
        cache_dir = compile_cache.enable_from_job_env()
        if cache_dir:
            log.info("persistent compile cache: %s", cache_dir)
    if os.environ.get("TONY_PROFILER_PORT"):
        from tony_tpu.obs.profiler import start_server

        # one server per process; offset by rank so co-hosted processes
        # (the local backend) don't collide on the port
        start_server(int(os.environ["TONY_PROFILER_PORT"]) + jax_tpu.process_id())
    reporter = None
    sinks = [cfg.on_metrics] if cfg.on_metrics is not None else []
    if jax_tpu.in_tony_job():
        # push step metrics to the AM (TaskMonitor/MetricsRpc pipeline)
        # beside any hook of the user's own; pushes are queued + drained by
        # a daemon thread so an AM stall can never block the step loop
        from tony_tpu.obs.reporter import MetricsReporter

        reporter = MetricsReporter()
        if reporter.active:
            sinks.append(reporter.push)
    el = None
    if cfg.elastic_members >= 2:
        # elastic job: the mesh is a function of the current membership
        # (dp = live members), swapped at generation boundaries below
        el = _Elastic(cfg)
        mesh = el.mesh
    else:
        mesh = build_mesh(cfg.mesh_shape)
    # model-level attention hooks ('ring'/'flash') resolve this mesh
    from tony_tpu.parallel.mesh import set_default_mesh

    set_default_mesh(mesh)
    if jax.process_index() == 0:
        log.info("mesh: %s over %d devices", dict(mesh.shape), mesh.size)

    optimizer = default_optimizer(
        lr=cfg.lr, warmup_steps=cfg.warmup_steps,
        decay_steps=max(cfg.steps, cfg.warmup_steps + 1),
        mu_dtype=jnp.dtype(cfg.mu_dtype),
    )
    rules = cfg.rules
    if int(mesh.shape.get("pp", 1)) > 1:
        from tony_tpu.train.trainer import pp_rules

        rules = pp_rules(rules)
    step_fn = make_train_step(
        cfg.model, mesh, optimizer, rules,
        n_microbatches=cfg.pp_microbatches, pp_schedule=cfg.pp_schedule,
        grad_bucket_bytes=int(cfg.grad_bucket_mb * (1 << 20)),
    )

    # --- compile-ahead: AOT-lower/compile the step on a worker thread while
    # the main thread initialises state, restores the checkpoint, and the
    # prefetcher warms the input pipeline. Shapes suffice to lower (the jit
    # carries in_shardings), so no array needs to exist yet.
    startup: dict[str, float] = {}
    aot: dict[str, object] = {}
    compile_thread = None
    if cfg.compile_ahead:
        state_avals = train_state_avals(cfg.model, optimizer)
        batch_aval = jax.ShapeDtypeStruct(
            (cfg.data.global_batch, cfg.data.seq_len), jnp.int32
        )

        def _compile_ahead() -> None:
            t0 = time.perf_counter()
            # runs on the compile-ahead thread (empty span stack): parent
            # on train.fit explicitly or this lands beside it, not inside
            with trace.span("fit.startup.compile", parent=fit_span.sid or None):
                try:
                    with ledger.label("train.step"):
                        aot["step"] = step_fn.lower(
                            state_avals, batch_aval, batch_aval
                        ).compile()
                except Exception as e:
                    # re-raised on the main thread at the join: a step the
                    # compiler refuses must fail the job there, not
                    # resurface later from a lazy jit dispatch
                    aot["error"] = e
                    return
            startup["compile_s"] = round(time.perf_counter() - t0, 3)
            # AOT entry point: journal the measured memory plan
            # (temp/arg/output/code bytes) + cost-analysis FLOPs
            ledger.record_aot("train.step", aot["step"], startup["compile_s"])

        compile_thread = threading.Thread(
            target=_compile_ahead, name="tony-compile-ahead", daemon=True
        )
        compile_thread.start()

    state = make_train_state(jax.random.key(0), cfg.model, mesh, optimizer, rules)

    manager = None
    start_step = 0
    if cfg.checkpoint_dir:
        from tony_tpu.train.checkpoint import CheckpointManager

        manager = CheckpointManager(
            cfg.checkpoint_dir,
            keep=cfg.checkpoint_keep,
            save_interval_steps=cfg.checkpoint_every,
        )
        if cfg.resume:
            t0 = time.perf_counter()
            with trace.span("fit.startup.restore"):
                state, restored = manager.restore(state)
            startup["restore_s"] = round(time.perf_counter() - t0, 3)
            if restored >= 0:
                start_step = restored
                log.info("resumed from checkpoint step %d", restored)

    batch_sharding = NamedSharding(mesh, spec_for(("batch", "seq"), cfg.rules))
    # the prefetch producer (data.prefetch > 0) starts generating + placing
    # batches here, concurrent with the compile-ahead join below
    if el is not None:
        batches = el.make_stream(batch_sharding, start_step)
    else:
        batches = make_batches(cfg.data, batch_sharding, start_step=start_step)
    if compile_thread is not None:
        compile_thread.join()
        if "error" in aot:
            close_batches(batches)
            raise aot["error"]
    compiled_step = aot.get("step")

    flops_per_token = train_flops_per_token(cfg.model, cfg.data.seq_len)
    tokens_per_step = cfg.data.global_batch * cfg.data.seq_len
    # off-chip the MFU is against obs.metrics.NOMINAL_CPU_FLOPS: say so
    mfu_note = " (nominal CPU peak)" if identity["platform"] == "cpu" else ""

    def _emit(snap: dict) -> None:
        """Resolve a log boundary: device sync on the (already in-flight)
        scalars, then log + push. Called AFTER the next step is dispatched,
        so the sync never leaves the device idle."""
        m = snap["metrics"]
        # EXPLICIT device sync point (jax.device_get, not bare float()):
        # under GRAFT_SANITIZE the steady-state loop runs with implicit
        # device-to-host transfers disallowed — the log boundary is the
        # one place a sync is intended, so it is spelled out
        loss = float(jax.device_get(m["loss"]))
        # the window's clock: from the moment the previous boundary's step
        # was known finished to the moment this one's is (the sync above,
        # or the sampled step's drain where that came first). The host's
        # clock at the boundary's DISPATCH is no such point: it runs up to
        # a window ahead of the device, and falls back into step with it at
        # every sampled-span drain, so windows timed there came out bimodal
        # (PERF.md section 6, PR 25)
        nonlocal t_resolved
        now = snap.get("t_done") or time.perf_counter()
        dt, t_resolved = now - t_resolved, now
        if tracer is None and snap["startup"] is None:
            # disarmed step-time source: the window mean (wall time over
            # completed steps — accurate without a per-step sync). The
            # first window is excluded like everywhere else: it absorbs
            # compile/warmup.
            h_step.observe(dt / max(snap["window"], 1))
        # scale from the snapshot, not the live loop: the deferred emit
        # may resolve after an elastic reshard rebound tokens_per_step
        # and the mesh, and the straddling window must report at the
        # scale it actually ran at
        timer = StepTimer(
            flops_per_token=flops_per_token,
            tokens_per_step=snap.get("tokens_per_step", tokens_per_step),
            n_chips=snap.get("n_chips", mesh.size),
        )
        timer.record(dt, snap["window"], host_blocked_s=snap["host_s"])
        out = {
            "step": snap["step"],
            "loss": round(loss, 4),
            "tokens_per_sec": round(timer.tokens_per_sec, 1),
            "tokens_per_sec_per_chip": round(timer.tokens_per_sec_per_chip, 1),
            "mfu": round(timer.mfu(), 4),
            "grad_norm": round(float(jax.device_get(m["grad_norm"])), 4),
            "host_blocked_ms_per_step": round(timer.host_blocked_ms_per_step, 2),
        }
        if snap["startup"] is not None:
            # first step only: the startup-phase breakdown rides the first
            # METRICS push so submit_latency() can report compile vs restore
            # vs first-batch (am/events.py), and the device identity says
            # what the job actually came up on
            out.update({f"startup_{k}": v for k, v in snap["startup"].items()})
            out.update(device_samples(identity))
        # HBM usage from the device this process owns (the nvidia-smi
        # sampling analogue; empty on platforms without memory_stats)
        from tony_tpu.obs.tpu_metrics import tpu_metrics_dict

        out.update(tpu_metrics_dict())
        if el is not None and el.journal is not None:
            # loss-continuity evidence: the log boundary's already-synced
            # scalars ride into the elastic journal (0-based step index,
            # generation captured at snapshot time — a deferred emit must
            # not stamp a boundary it predates)
            fp = m.get("health/batch_fingerprint")
            el.journal.loss(
                snap["step"] - 1, snap.get("gen", 0), loss,
                int(jax.device_get(fp)) if fp is not None else None,
            )
        if jax.process_index() == 0:
            log.info(
                "step %(step)d loss=%(loss)s %(tokens_per_sec_per_chip)s tok/s/chip "
                "mfu=%(mfu)s" + mfu_note, out,
            )
        for sink in sinks:
            sink(out)

    metrics: dict = {}
    pending = None          # boundary snapshot deferred past the next dispatch
    host_window_s = 0.0     # input-blocked time in the current log window
    host_steady_s = 0.0     # input-blocked time after the first step
    steady_t0 = None        # wall clock after the first step fully resolved
    t_resolved = time.perf_counter()  # when the last boundary's step was known done
    window = 0

    def _dispatch(state, inputs, targets):
        nonlocal compiled_step
        if compiled_step is not None:
            try:
                return compiled_step(state, inputs, targets)
            except (TypeError, ValueError):
                # aval/sharding mismatch between the AOT signature and
                # the live arrays (raised before execution, so nothing
                # was donated) — fall back to jit dispatch permanently;
                # real runtime faults (OOM etc.) propagate as usual
                log.warning(
                    "compile-ahead executable rejected live args; "
                    "falling back to jit dispatch", exc_info=True,
                )
                compiled_step = None
        return step_fn(state, inputs, targets)

    # trace spine: every trace.sample_steps-th step is a span, mirrored
    # onto the device timeline via jax.profiler.TraceAnnotation with the
    # SAME name so a Perfetto/XPlane capture lines up with tony trace
    tracer = trace.active_tracer()
    # steady-state step-time distribution (p50/p95/p99 in the final report
    # and on the portal /metrics endpoint); host-side loop cadence —
    # individual iterations are noisy under async dispatch, the
    # distribution over a run is the signal
    # per-run registry: a second fit() in the same process (bench sweeps)
    # must report THIS run's distribution, not a blend with the last one
    registry = Registry()
    h_step = registry.histogram(
        "tony_step_time_seconds",
        "train step wall time (synced sampled steps; log-window means when untraced)",
    )
    from tony_tpu.obs.profiler import annotate

    # live-series source: step progress, since-last-scrape step-time
    # quantiles, and the goodput split — read on scrape stride hits only,
    # all host-side locals (the closure reads the loop's live variables;
    # no device sync ever happens here). The SLO engine's
    # step_time_p99_s / goodput_floor inputs come from these keys.
    recorder = series.active_recorder()
    step = start_step  # the source may be scraped before the first step
    step_window = HistogramWindow()

    def _series_source() -> dict:
        out = {"step": float(step + 1)}
        if steady_t0 is not None:
            elapsed = max(time.perf_counter() - steady_t0, 1e-9)
            out["host_blocked_frac"] = round(host_steady_s / elapsed, 4)
            out["goodput_frac"] = round(
                max(1.0 - host_steady_s / elapsed, 0.0), 4
            )
        d = step_window.delta(h_step)
        if d["count"]:
            out["step_time_p50_s"] = round(d["p50"], 4)
            out["step_time_p99_s"] = round(d["p99"], 4)
            out["step_time_n"] = d["count"]
        return out

    if recorder is not None:
        recorder.attach("fit", _series_source)

    # runtime sanitizer (GRAFT_SANITIZE=1, analysis/sanitize.py): armed
    # once the first step has fully resolved — steady state must neither
    # implicitly host-sync nor compile (docs/ANALYSIS.md "Sanitizer")
    from tony_tpu.analysis import sanitize

    san_stack = contextlib.ExitStack()
    watchdog = None
    try:
        for step in range(start_step, cfg.steps):
            if el is not None:
                # elastic generation boundary: a pending membership change
                # (AM broadcast or scripted plan) is applied HERE — fence,
                # donate from the fenced state, rebuild mesh/step/stream
                # against the new topology, keep stepping
                rec = el.pending(step)
                if rec is not None:
                    if watchdog is not None:
                        # a reshard legitimately re-compiles: step out of
                        # the sanitizer for the boundary and re-arm after,
                        # so the compile watchdog budgets steady state only
                        san_stack.close()
                        watchdog = None
                    (state, step_fn, compiled_step, mesh,
                     batch_sharding) = el.reshard(
                        rec, step, state, optimizer, rules, ledger
                    )
                    batches = el.stream
                    tokens_per_step = (
                        el.stream.global_batch * cfg.data.seq_len
                    )
                    if sanitize.enabled() and steady_t0 is not None:
                        watchdog = san_stack.enter_context(
                            sanitize.sanitized_loop("fit")
                        )
            t_fetch = time.perf_counter()
            if step == start_step:
                with trace.span("fit.startup.first_batch"):
                    inputs, targets = next(batches)
                fetch_s = time.perf_counter() - t_fetch
                startup["first_batch_s"] = round(fetch_s, 3)
            else:
                inputs, targets = next(batches)
                fetch_s = time.perf_counter() - t_fetch
                host_window_s += fetch_s
                host_steady_s += fetch_s
            # coordinated-profiling seam: one global load + None compare
            # disarmed; during an AM-broadcast window this boundary starts/
            # advances the device-trace capture and attributes this step's
            # input wait (fetch_s is a precomputed local — GL005)
            profile.maybe_capture(fetch_s=fetch_s)
            # first step excluded from sampling (like h_step below): its
            # compile/warmup-inflated duration would be stride-scaled by
            # the goodput roll-up, and its fetch is already attributed to
            # the fit.startup.first_batch span
            sp = trace.NOOP_SPAN
            if tracer is not None and step != start_step:
                sp = tracer.sampled_span(
                    "train.step", step=step + 1,
                    fetch_ms=round(fetch_s * 1e3, 3),
                )
            if sp is not trace.NOOP_SPAN:
                # dispatch is async: an unsynced span times the enqueue
                # (microseconds) and the goodput roll-up would misattribute
                # the whole window. Drain the dispatch backlog BEFORE the
                # span, sync on the result inside it, so the span covers
                # exactly this step's device time; the cost is one pipeline
                # sync per sample_steps — same class as the deferred
                # log-boundary sync.
                jax.block_until_ready(state)
                t_sync = time.perf_counter()
                if pending is not None:
                    # the drain resolved the pending boundary's step: its
                    # window closes here, not after this step's sync
                    pending["t_done"] = t_sync
                with sp, annotate("train.step"):
                    state, metrics = _dispatch(state, inputs, targets)
                    jax.block_until_ready(metrics)
                # the synced iteration observes the span-internal time (true
                # device step, backlog excluded). Unsampled iterations never
                # observe: under async dispatch they time only the enqueue,
                # and mixing the two classes makes the quantiles bimodal
                # nonsense. Disarmed runs fall back to log-window means at
                # the boundary below — every observation in one histogram is
                # measured the same way.
                h_step.observe(time.perf_counter() - t_sync)
            else:
                state, metrics = _dispatch(state, inputs, targets)
            hbm.sample()  # stride-counted device-memory reading (no sync)
            # stride-counted health sample: enqueues DEVICE references for
            # the sentinel's worker thread (the device_get sync happens
            # there, never here — the step loop stays unblocked)
            health.sample(metrics=metrics)
            # stride-counted series scrape: host-side locals + counters
            # only; journaling happens on the recorder's writer thread
            series.sample()
            if el is not None:
                # membership evidence (host-side append, no sync) + the
                # async device->host checkpoint shadow on its stride
                el.note_step(step)
                el.shadow.maybe_update(step + 1, state)
            window += 1
            if pending is not None:
                _emit(pending)  # previous boundary, now that N+1 is in flight
                pending = None
            # the very first step always logs/pushes: it closes the AM-submit
            # -> first-step loop (the north-star latency metric — the AM
            # timestamps the resulting METRICS event) and gives users signal
            # before a long log_every window elapses
            if step == start_step or (step + 1) % cfg.log_every == 0 or step + 1 == cfg.steps:
                snap = {
                    "step": step + 1,
                    "metrics": metrics,
                    "window": window,
                    "host_s": host_window_s,
                    "startup": dict(startup) if step == start_step else None,
                    "gen": el.controller.generation if el is not None else 0,
                    "tokens_per_step": tokens_per_step,
                    "n_chips": mesh.size,
                }
                _start_async_host_copy(metrics)
                if step == start_step or step + 1 == cfg.steps:
                    # first step: latency metric, sync now; last step: the
                    # loop ends here, nothing left to overlap with
                    _emit(snap)
                else:
                    pending = snap
                window = 0
                host_window_s = 0.0
                if step == start_step:
                    steady_t0 = time.perf_counter()
                    if sanitize.enabled():
                        watchdog = san_stack.enter_context(
                            sanitize.sanitized_loop("fit")
                        )
            if watchdog is not None:
                watchdog.check()  # fail at the offending step, not the end
            if manager is not None and manager.should_save(step + 1):
                manager.save(step + 1, state)
        san_stack.close()  # sanitizer covers exactly the steady-state steps
        if pending is not None:
            _emit(pending)
            pending = None
        steady_end = time.perf_counter()  # before checkpoint settling
    finally:
        san_stack.close()
        # a profile window still open when the loop ends (requested window
        # longer than the remaining steps, exception mid-capture) finalises
        # here — the partial trace + manifest land instead of vanishing
        profile.finish_capture()
        close_batches(batches)
        if el is not None:
            # shadow thread + generation watcher + journal handle; the
            # stream was closed above (close_batches), close() tolerates it
            el.close()
        if recorder is not None:
            # final scrape (the shutdown state lands in the journal, and
            # any last-window SLO trip evaluates) before the source whose
            # locals are about to die is detached
            recorder.force_sample()
            recorder.drain()
            recorder.detach("fit")
    if manager is not None:
        manager.wait()  # settle async saves before checking what exists
        if manager.latest_step() != cfg.steps:
            manager.save(cfg.steps, state, force=True)
        manager.close()
    final = {"final_loss": float(metrics.get("loss", float("nan"))), "steps": cfg.steps}
    if h_step.count:
        # step-time distribution (bucketed quantiles): the portal /metrics
        # endpoint re-renders the full histogram from the snapshot below
        final["step_time_p50_s"] = round(h_step.quantile(0.5), 4)
        final["step_time_p95_s"] = round(h_step.quantile(0.95), 4)
        final["step_time_p99_s"] = round(h_step.quantile(0.99), 4)
    if reporter is not None:
        reporter.close()
        if reporter.dropped:
            final["metrics_dropped"] = reporter.dropped
    # health verdict: drain the sentinel's queue so a trip on the final
    # steps lands in the final report, then export tony_health_* into the
    # per-run registry (snapshotted below) and persist the verdict file
    # the portal /healthz and `tony health` read
    sentinel = health.active_sentinel()
    if sentinel is not None:
        sentinel.drain()
        final["health_verdict"] = sentinel.verdict
        trips = sentinel.trip_counts()
        if trips:
            final["health_trips"] = trips
        sentinel.export(registry)
        sentinel.write_verdict()
    # SLO verdict (obs/slo.py): the burn-rate engine evaluated async on
    # the series writer thread (drained above); export tony_slo_* into
    # the per-run registry and persist the verdict — `met` is recorded,
    # so a missing verdict stays distinguishable from a passing one
    slo_engine = slo.active_engine()
    if slo_engine is not None:
        final["slo_verdict"] = slo_engine.verdict
        slo_trips = slo_engine.trip_counts()
        if slo_trips:
            final["slo_trips"] = slo_trips
        slo_engine.export(registry)
        slo_engine.write_verdict()
    # registry snapshot into the job history (no-op outside a tony job);
    # suffixed so a train-then-serve user process cannot overwrite one
    # component's snapshot with the other's. The HBM gauges export into
    # THIS registry first, so tony_hbm_* reaches the portal /metrics.
    if hbm_watch is not None:
        hbm_watch.export_gauges(registry)
    snapshot_to_app_dir(trace.default_proc_name("train") + "_fit", registry)
    # compile-ledger snapshot for `tony compiles <app_id>` (process-scoped,
    # so the bare proc name; no-op outside a tony job) + summary lines
    compile_ledger.snapshot_to_app_dir()
    final["xla_compiles"] = ledger.backend_compiles - compiles_t0
    if hbm_mark is not None:
        peak_gb, peak_exact = hbm_watch.peak_since(hbm_mark)
        if peak_gb:
            final["peak_hbm_gb"] = peak_gb
            final["peak_hbm_exact"] = peak_exact
    # steady-state input-stall + throughput accounting (first step excluded:
    # it absorbs warmup). The last boundary _emit synced the final step, so
    # the wall-clock window below covers completed work only.
    steady_steps = max(cfg.steps - start_step - 1, 0)
    if steady_t0 is not None and steady_steps > 0:
        steady_elapsed = max(steady_end - steady_t0, 1e-9)
        final["tokens_per_sec_per_chip"] = round(
            steady_steps * tokens_per_step / steady_elapsed / mesh.size, 1
        )
        final["host_blocked_ms_per_step"] = round(
            host_steady_s / steady_steps * 1e3, 2
        )
        final["host_blocked_frac"] = round(host_steady_s / steady_elapsed, 4)
    if startup:
        final["startup"] = dict(startup)
    if el is not None:
        # elastic roll-up: final generation/membership, warm-restart count
        # + cost (the same number `tony trace` goodput reads off the
        # elastic.reshard spans as restart_s)
        final["elastic"] = el.summary()
    if jax.process_index() == 0:
        # shutdown summary: silent metric loss must be visible in the
        # worker log, not only behind the portal
        log.info(
            "fit summary: steps=%d loss=%.4f step_p50=%.3fs step_p99=%.3fs "
            "host_blocked=%s metrics_dropped=%d peak_hbm_gb=%s xla_compiles=%d",
            cfg.steps, final["final_loss"],
            final.get("step_time_p50_s", 0.0), final.get("step_time_p99_s", 0.0),
            final.get("host_blocked_frac", 0.0), final.get("metrics_dropped", 0),
            final.get("peak_hbm_gb", 0.0), final["xla_compiles"],
        )
    return final


__all__ = ["FitConfig", "fit"]
