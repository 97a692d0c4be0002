"""Configuration key registry and defaults.

The analogue of TonY's ``TonyConfigurationKeys`` (all keys centralised, named
``tony.*``) plus ``tony-default.xml`` (baked-in defaults layer). See SURVEY.md
section 2 "Config system" and section 5 "Config/flag system". Keys here use
plain dotted names; per-jobtype keys are templated via :func:`job_key`.
"""

from __future__ import annotations


class Keys:
    """Centralised configuration key names (TonyConfigurationKeys analogue)."""

    # --- application-level ---
    APPLICATION_NAME = "application.name"
    APPLICATION_FRAMEWORK = "application.framework"  # jax | tensorflow | pytorch | horovod | generic
    APPLICATION_QUEUE = "application.queue"
    APPLICATION_SECURITY_ENABLED = "application.security.enabled"
    APPLICATION_TIMEOUT_S = "application.timeout_s"  # 0 = no timeout
    APPLICATION_PREPARE_STAGE_DIR = "application.stage_dir"
    APPLICATION_TAGS = "application.tags"

    # --- AM (ApplicationMaster) ---
    AM_MEMORY_MB = "am.memory_mb"  # reserved from backend inventory by the AM
    AM_CPUS = "am.cpus"  # ditto; also sizes the AM RPC thread pool
    AM_RETRY_COUNT = "am.retry_count"  # tony.am.retry-count analogue
    AM_RPC_PORT = "am.rpc_port"  # 0 = ephemeral
    AM_ALLOCATION_TIMEOUT_S = "am.allocation_timeout_s"  # gang partial-alloc guard

    # --- task supervision ---
    TASK_HEARTBEAT_INTERVAL_MS = "task.heartbeat_interval_ms"
    TASK_MAX_MISSED_HEARTBEATS = "task.max_missed_heartbeats"
    TASK_REGISTRATION_TIMEOUT_S = "task.registration_timeout_s"
    TASK_MAX_TOTAL_INSTANCES = "task.max_total_instances"
    TASK_EXECUTOR_PYTHON = "task.executor.python"  # python binary for executors

    # --- elastic / restart policy ---
    RESTART_MAX_WORKER_RESTARTS = "restart.max_worker_restarts"
    RESTART_POLICY = "restart.policy"  # never | failed_only | gang
    RESTART_RESUME_FROM_CHECKPOINT = "restart.resume_from_checkpoint"

    # --- elastic training (tony_tpu/elastic/; docs/ELASTIC.md) ---
    # survive preemption without a cold restart: on a lost training host
    # the AM declares a new cluster generation (members minus the dead
    # host) instead of gang-restarting; the trainer reshards its dp axis
    # and continues from the in-memory state of survivors. Auto-enabled
    # for application.framework = "elastic" jobs.
    ELASTIC_ENABLED = "elastic.enabled"
    # smallest surviving membership the job may shrink to; fewer survivors
    # (or a lost coordinator) falls back to the restart.policy cold path
    ELASTIC_MIN_MEMBERS = "elastic.min_members"
    # re-acquire capacity and restore dead members automatically (the
    # grow-back half; LeaseStore.grow_gang re-leases the REAL container ask)
    ELASTIC_GROW_BACK = "elastic.grow_back"
    # how often the AM retries capacity for a dead member (seconds)
    ELASTIC_GROW_RETRY_S = "elastic.grow_retry_s"
    # trainer-side knobs, exported AM -> executor -> user process:
    # how often the trainer polls the generation broadcast file
    ELASTIC_POLL_S = "elastic.poll_interval_s"
    # async device->host checkpoint-shadow stride (steps); the shadow is
    # the bounded-lag fallback recovery point (the fence capture is exact)
    ELASTIC_SHADOW_STEPS = "elastic.shadow_interval_steps"

    # --- distributed mode ---
    SCHEDULER_MODE = "scheduler.mode"  # GANG | FCFS (SURVEY.md: TaskScheduler modes)

    # --- checkpoint glue ---
    CHECKPOINT_DIR = "checkpoint.dir"
    CHECKPOINT_INTERVAL_STEPS = "checkpoint.interval_steps"
    CHECKPOINT_KEEP = "checkpoint.keep"

    # --- observability ---
    METRICS_INTERVAL_MS = "metrics.interval_ms"
    METRICS_ENABLED = "metrics.enabled"
    PROFILER_ENABLED = "profiler.enabled"
    PROFILER_PORT = "profiler.port"
    # persistent XLA compilation cache for fit() jobs and serve gang hosts:
    # resubmits and gang restarts load their executables instead of
    # recompiling (the compile segment of submit->first-step)
    TRAIN_JAX_CACHE = "train.jax_cache"
    # '' -> <checkout>/.jax_cache; JAX_COMPILATION_CACHE_DIR set from outside
    # overrides both (utils/compile_cache.py)
    TRAIN_JAX_CACHE_DIR = "train.jax_cache_dir"
    # cloud-tpu-diagnostics periodic stack traces (wedged-job debugging)
    DIAGNOSTICS_ENABLED = "diagnostics.enabled"
    # distributed trace spine (obs/trace.py; docs/OBS.md): always-on sampled
    # span recording across AM/executor/user processes, merged by
    # `tony trace <app_id>` into one Chrome-trace JSON
    TRACE_ENABLED = "trace.enabled"
    # record every Nth train/serve step as a span (1 = every step);
    # control-plane and lifecycle spans are never sampled away
    TRACE_SAMPLE_STEPS = "trace.sample_steps"
    # per-process in-memory span ring; overflow drops oldest and counts
    TRACE_RING_EVENTS = "trace.ring_events"
    # per-process journal rotation size: at the cap the journal rotates and
    # the newest window is kept (flight-recorder retention, <= 2x on disk)
    TRACE_MAX_JOURNAL_MB = "trace.max_journal_mb"
    # HBM observatory (obs/hbm.py; docs/OBS.md "Memory and compiles"):
    # phase-scoped device-memory watermarks, sampled per-step readings as
    # Perfetto counter tracks, and OOM forensics dumps
    OBS_HBM_ENABLED = "obs.hbm.enabled"
    # read device memory_stats every Nth train/serve step (the counter-
    # track sampling stride; off-stride calls are one increment + compare)
    OBS_HBM_SAMPLE_STEPS = "obs.hbm.sample_steps"
    # per-process in-memory sample-history ring (lands in OOM forensics)
    OBS_HBM_HISTORY = "obs.hbm.history_events"
    # numerics health sentinel (obs/health.py; docs/OBS.md "Numerics
    # health"): in-graph value monitors (nonfinite counts, update ratio,
    # per-layer grad RMS, batch fingerprint, serve logits/entropy) feeding
    # an async anomaly-rule engine; a trip flips the per-app verdict
    # (portal /healthz, `tony health <app_id>`) and dumps a forensics
    # bundle under <app_dir>/health/
    OBS_HEALTH_ENABLED = "obs.health.enabled"
    # evaluate health rules every Nth train/serve step (monitors stay
    # fused in-graph each step; off-stride seam calls are one increment)
    OBS_HEALTH_SAMPLE_STEPS = "obs.health.sample_steps"
    # rolling-statistics window (loss-spike z-score, stagnation) — also
    # the last-k step-stats ring a forensics bundle carries
    OBS_HEALTH_WINDOW = "obs.health.window_steps"
    # live time-series recorder (obs/series.py; docs/OBS.md "SLO + time
    # series"): stride-scraped per-process points (step time, TTFT/TPOT
    # quantiles, queue depth, HBM live/peak, health verdict, goodput)
    # journaled to ring-rotated series/<proc>.jsonl — the feed `tony top`
    # renders and the SLO engine alerts on
    OBS_SERIES_ENABLED = "obs.series.enabled"
    # scrape every Nth train/serve step (off-stride seam calls are one
    # increment + compare; the disarmed seam is one global load)
    OBS_SERIES_SAMPLE_STEPS = "obs.series.sample_steps"
    # per-process journal rotation size (newest window kept, <= 2x on disk)
    OBS_SERIES_JOURNAL_MB = "obs.series.max_journal_mb"
    # coordinated fleet profiling (obs/profile.py; docs/OBS.md "Step
    # anatomy"): `tony profile <app_id>` asks the AM to broadcast a bounded
    # capture window; every device-owning process records a jax.profiler
    # device trace into <app_dir>/profile/<proc>/ over the same steps,
    # and `tony profile report` merges them into the per-step budget table
    OBS_PROFILE_ENABLED = "obs.profile.enabled"
    # how often each process polls the broadcast request file (seconds);
    # the off-window hot-path seam cost is unaffected by this knob
    OBS_PROFILE_POLL_S = "obs.profile.poll_interval_s"
    # hard cap on the steps one window may capture (device traces are
    # big; a typo'd `--steps 100000` must not fill the disk)
    OBS_PROFILE_MAX_STEPS = "obs.profile.max_steps"

    # --- SLOs (obs/slo.py; docs/OBS.md "SLO + time series") ---
    # declared targets, evaluated as multi-window burn rates over the live
    # series; 0 = not contracted. A trip latches, emits an slo.<name>
    # trace instant + tony_slo_* metrics, and writes a verdict + forensics
    # bundle under <app_dir>/slo/ (the chaos invariant checker's
    # slo-surfaced rule refuses to report a tripped run clean)
    SLO_TTFT_P99_S = "slo.ttft_p99_s"
    SLO_STEP_TIME_P99_S = "slo.step_time_p99_s"
    SLO_GOODPUT_FLOOR = "slo.goodput_floor"
    SLO_HBM_HEADROOM_FRAC = "slo.hbm_headroom_frac"
    SLO_ERROR_RATE = "slo.error_rate"
    # error budget: the bad-point fraction a window may carry before the
    # burn rate (bad_frac / budget) exceeds 1 and the SLO trips
    SLO_BUDGET_FRAC = "slo.budget_frac"
    # SRE-style multi-window gates: the fast window catches the incident
    # now, the slow one (clipped to recorded data) proves it is sustained
    SLO_FAST_WINDOW_S = "slo.fast_window_s"
    SLO_SLOW_WINDOW_S = "slo.slow_window_s"
    # minimum fast-window samples before an SLO may trip (blip guard)
    SLO_MIN_POINTS = "slo.min_points"

    # --- gang serving (`tony serve`; serve/gang.py + serve/frontend.py) ---
    # decode-host containers the AM gang-schedules (the serve job's size)
    SERVE_GANG_HOSTS = "serve.gang.hosts"
    # task-type name of the decode hosts (job.<type>.* keys configure their
    # containers; the command defaults to `python -m tony_tpu.serve.gang`)
    SERVE_GANG_JOB_TYPE = "serve.gang.job_type"
    # model preset each host builds: a LlamaConfig classmethod name
    # (tiny | bench_410m | bench_1b4 | ...)
    SERVE_GANG_MODEL = "serve.gang.model"
    # parameter-init seed: every replica derives identical weights from it,
    # so any host can serve (or replay) any request
    SERVE_GANG_SEED = "serve.gang.seed"
    # per-host engine shape (ServeConfig.slots / max_len; 0 = model max)
    SERVE_GANG_SLOTS = "serve.gang.slots"
    SERVE_GANG_MAX_LEN = "serve.gang.max_len"
    # per-host bounded admission (ServeConfig.max_queue): submits beyond
    # this queue depth are rejected so the frontend reroutes instead of
    # burying work in a saturated host
    SERVE_GANG_MAX_QUEUE = "serve.gang.max_queue"
    # shard each host's params over its local devices via the default mesh
    # (parallel/mesh.py) instead of single-device replication
    SERVE_GANG_SHARD = "serve.gang.shard"
    # frontend admission bound: total requests in flight across the gang
    SERVE_GANG_MAX_INFLIGHT = "serve.gang.frontend_max_inflight"
    # replay budget per request: a request re-queued off a dead host more
    # than this many times finishes with reason=error (never hangs)
    SERVE_GANG_MAX_REPLAYS = "serve.gang.max_replays"
    # TTFT contract recorded into the serve ledger; the chaos invariant
    # checker flags completed requests over budget (0 = uncontracted)
    SERVE_GANG_TTFT_BUDGET_S = "serve.gang.ttft_budget_s"
    # rolling-restart drain: how long a host finishes its live slots
    # before Drain gives up and reports the remainder
    SERVE_GANG_DRAIN_TIMEOUT_S = "serve.gang.drain_timeout_s"
    # lease-store autoscale hooks: grow the gang when the aggregate queue
    # depth stays above `high` for `window_s`, shrink when it stays below
    # `low` (high 0 disables; see LeaseStore.grow_gang/shrink_gang)
    SERVE_GANG_AUTOSCALE_HIGH = "serve.gang.autoscale_queue_high"
    SERVE_GANG_AUTOSCALE_LOW = "serve.gang.autoscale_queue_low"
    SERVE_GANG_AUTOSCALE_WINDOW_S = "serve.gang.autoscale_window_s"

    # --- prefix store (cross-request KV reuse; serve/prefix.py) ---
    # radix prefix store over the paged KV cache: admission matches each
    # prompt's longest cached prefix and prefills only the unshared tail;
    # matched blocks are shared copy-on-write
    SERVE_PREFIX_ENABLED = "serve.prefix.enabled"
    # HBM the store may pin for prefixes no live slot references; LRU
    # leaves evict beyond it (0 = bound only by allocation pressure)
    SERVE_PREFIX_BUDGET_MB = "serve.prefix.budget_mb"
    # frontend prefix-affinity routing: requests sharing a prefix
    # fingerprint route to the host whose store already holds it (falls
    # back to least-loaded when that host is dead/draining/overloaded)
    SERVE_PREFIX_AFFINITY = "serve.prefix.affinity"
    # leading tokens hashed into the routing fingerprint; prompts shorter
    # than this route purely by load (too little prefix to pin a host for)
    SERVE_PREFIX_FINGERPRINT_TOKENS = "serve.prefix.fingerprint_tokens"

    # --- speculative decoding (model-free drafts; serve/spec.py) ---
    # trie/n-gram drafted multi-token decode steps: each slot proposes up
    # to max_draft tokens per step, the engine verifies all of them in
    # ONE widened forward and accepts via the exact rejection rule —
    # output stays draw-for-draw identical to autoregressive decoding
    SERVE_SPEC_ENABLED = "serve.spec.enabled"
    # draft tokens proposed per slot per step (the verify step scores
    # max_draft + 1 positions; one decode signature per engine)
    SERVE_SPEC_MAX_DRAFT = "serve.spec.max_draft"
    # draft source: auto (radix store first, n-gram fallback) | prefix
    # (store only) | ngram (the slot's own prompt-lookup only)
    SERVE_SPEC_DRAFT_SOURCE = "serve.spec.draft_source"

    # --- quantized serving (block-scaled KV + weight-only int8;
    #     serve/cache.py, ops/quant_mm.py, docs/SERVE.md) ---
    # quantize the paged KV cache at physical-block granularity: int8/fp8
    # pools with per-block-per-head float32 scales; decode attention
    # dequantizes inline, roughly doubling the slot budget at a bounded
    # logits drift (bench decode.quant states the tolerance)
    SERVE_QUANT_ENABLED = "serve.quant.enabled"
    # KV storage dtype: int8 | fp8_e4m3 (fp8 needs a jax with
    # jnp.float8_e4m3fn; the engine refuses rather than silently widening)
    SERVE_QUANT_KV_DTYPE = "serve.quant.kv_dtype"
    # also run decode/verify matmuls on int8 weights with per-output-
    # channel scales (prefill keeps the bf16 master weights)
    SERVE_QUANT_WEIGHTS = "serve.quant.weights"

    # --- chunked prefill + disaggregated pools (docs/SERVE.md
    # "Disaggregated serving") ---
    # prompts whose unshared tail exceeds this prefill in block-aligned
    # chunks, one chunk per decode step, so a long prompt cannot stall
    # co-resident streams (TPOT stays bounded, TTFT degrades gracefully);
    # must be a multiple of serve.gang.kv block size; 0 = off
    SERVE_CHUNK_TOKENS = "serve.chunk_tokens"
    # containers in the prefill pool (0 = colocated serving, no pool split);
    # when > 0 the serve gang is heterogeneous: the AM schedules this many
    # prefill-type containers next to serve.gang.hosts decode ones, and the
    # frontend routes long prompts through prefill -> ShipBlocks -> decode
    SERVE_POOL_PREFILL_HOSTS = "serve.pool.prefill_hosts"
    # task-type name of the prefill pool (job.<type>.* keys configure its
    # containers; same worker binary as the decode pool)
    SERVE_POOL_PREFILL_JOB_TYPE = "serve.pool.prefill_job_type"
    # minimum prompt tokens before the frontend routes through the prefill
    # pool — short prompts prefill faster in place than a handoff round-trip
    SERVE_POOL_HANDOFF_MIN_TOKENS = "serve.pool.handoff_min_tokens"

    # --- cluster backend ---
    # Deliberate non-goals vs the reference key surface: docker keys (no
    # container runtime in this environment — processes are the container
    # abstraction) and a max-containers cap (the inventory's memory/cpu/chip
    # capacity already bounds concurrent containers).
    CLUSTER_BACKEND = "cluster.backend"  # local | remote | tpu_vm
    CLUSTER_TPU_CHIPS_PER_HOST = "cluster.tpu_chips_per_host"
    CLUSTER_HOSTS = "cluster.hosts"  # remote backend: comma list of hosts
    CLUSTER_REMOTE_TRANSPORT = "cluster.remote_transport"  # ssh | local
    # copy the app dir to each host over the transport (pod slices without a
    # shared FS) instead of assuming the same path everywhere
    CLUSTER_LOCALIZE = "cluster.localize"
    # destination root for localized app dirs (default ~/.tony-tpu/localized,
    # expanded on the AM host — assumes the same home path on every host)
    CLUSTER_LOCALIZE_ROOT = "cluster.localize_root"
    # shared ResourceManager (YARN-RM analogue): a directory reachable by
    # every submitter (same machine or shared FS); when set, all jobs lease
    # capacity from this file-locked store, so concurrent submits queue
    # FIFO instead of double-booking hosts/chips. Empty = per-job inventory.
    CLUSTER_RM_ROOT = "cluster.rm_root"
    # lease TTL for the shared RM store: a job's leases expire this many
    # seconds after their last renewal (the AM renews on its heartbeat
    # cadence), so a submit host that dies on ANOTHER machine — where pid
    # liveness cannot be checked — frees its chips automatically instead
    # of stranding them until an operator runs `tony rm-status --release`.
    # 0 disables expiry (manual/pid reaping only).
    CLUSTER_LEASE_TTL_S = "cluster.lease_ttl_s"

    # --- portal/history ---
    HISTORY_INTERMEDIATE_DIR = "history.intermediate_dir"
    HISTORY_FINISHED_DIR = "history.finished_dir"
    PORTAL_PORT = "portal.port"

    # --- chaos (fault injection; docs/CHAOS.md) ---
    # master gate: when false (the default) every chaos hook is a no-op and
    # no fault schedule is ever parsed or armed
    CHAOS_ENABLED = "chaos.enabled"
    # declarative fault schedule: a JSON list of fault objects (as a
    # string — portable across TOML readers), e.g.
    # [{"type": "kill_container", "task": "worker:0", "at_count": 3}]
    CHAOS_FAULTS = "chaos.faults"
    # seed for the injector's RNG (delay jitter); same seed = same schedule
    CHAOS_SEED = "chaos.seed"


# Per-jobtype key suffixes (the ``tony.<jobtype>.<suffix>`` templating scheme).
JOB_SUFFIXES = (
    "instances",
    "memory_mb",
    "cpus",
    "tpu_chips",
    "command",
    "env",
    "depends_on",  # inter-task-type dependency (workers wait on ps)
    "depends_timeout_s",
    "untracked",  # excluded from job final-status accounting (e.g. tensorboard)
    "node_label",
)


def job_key(job_type: str, suffix: str) -> str:
    """``job_key("worker", "instances") -> "job.worker.instances"``.

    Analogue of TonY's per-jobtype conf templating
    (``tony.<jobtype>.instances`` / ``.memory`` / ``.vcores`` / ``.gpus``).
    """
    return f"job.{job_type}.{suffix}"


# The tony-default.xml analogue: the base layer of every TonyConfig.
# tests/test_config.py pins these against docs (reference had a
# defaults-vs-docs consistency test, SURVEY.md section 5).
DEFAULTS: dict[str, object] = {
    Keys.APPLICATION_NAME: "tony-tpu-job",
    Keys.APPLICATION_FRAMEWORK: "jax",
    Keys.APPLICATION_QUEUE: "default",
    Keys.APPLICATION_SECURITY_ENABLED: False,
    Keys.APPLICATION_TIMEOUT_S: 0,
    Keys.APPLICATION_PREPARE_STAGE_DIR: "",
    Keys.APPLICATION_TAGS: "",
    Keys.AM_MEMORY_MB: 2048,
    Keys.AM_CPUS: 1,
    Keys.AM_RETRY_COUNT: 0,
    Keys.AM_RPC_PORT: 0,
    Keys.AM_ALLOCATION_TIMEOUT_S: 300,
    Keys.TASK_HEARTBEAT_INTERVAL_MS: 1000,
    Keys.TASK_MAX_MISSED_HEARTBEATS: 25,
    Keys.TASK_REGISTRATION_TIMEOUT_S: 300,
    Keys.TASK_MAX_TOTAL_INSTANCES: -1,
    Keys.TASK_EXECUTOR_PYTHON: "",
    Keys.RESTART_MAX_WORKER_RESTARTS: 0,
    Keys.RESTART_POLICY: "never",
    Keys.RESTART_RESUME_FROM_CHECKPOINT: True,
    Keys.ELASTIC_ENABLED: False,
    Keys.ELASTIC_MIN_MEMBERS: 1,
    Keys.ELASTIC_GROW_BACK: True,
    Keys.ELASTIC_GROW_RETRY_S: 2.0,
    Keys.ELASTIC_POLL_S: 0.5,
    Keys.ELASTIC_SHADOW_STEPS: 16,
    Keys.SCHEDULER_MODE: "GANG",
    Keys.CHECKPOINT_DIR: "",
    Keys.CHECKPOINT_INTERVAL_STEPS: 0,
    Keys.CHECKPOINT_KEEP: 3,
    Keys.METRICS_INTERVAL_MS: 2000,
    Keys.METRICS_ENABLED: True,
    Keys.PROFILER_ENABLED: False,
    Keys.PROFILER_PORT: 9999,
    Keys.TRAIN_JAX_CACHE: True,
    Keys.TRAIN_JAX_CACHE_DIR: "",

    Keys.DIAGNOSTICS_ENABLED: False,
    Keys.TRACE_ENABLED: True,
    Keys.TRACE_SAMPLE_STEPS: 16,
    Keys.TRACE_RING_EVENTS: 4096,
    Keys.TRACE_MAX_JOURNAL_MB: 64,
    Keys.OBS_HBM_ENABLED: True,
    Keys.OBS_HBM_SAMPLE_STEPS: 16,
    Keys.OBS_HBM_HISTORY: 512,
    Keys.OBS_HEALTH_ENABLED: True,
    Keys.OBS_HEALTH_SAMPLE_STEPS: 16,
    Keys.OBS_HEALTH_WINDOW: 64,
    Keys.OBS_SERIES_ENABLED: True,
    Keys.OBS_SERIES_SAMPLE_STEPS: 16,
    Keys.OBS_SERIES_JOURNAL_MB: 16,
    Keys.OBS_PROFILE_ENABLED: True,
    Keys.OBS_PROFILE_POLL_S: 0.5,
    Keys.OBS_PROFILE_MAX_STEPS: 64,
    Keys.SLO_TTFT_P99_S: 0,
    Keys.SLO_STEP_TIME_P99_S: 0,
    Keys.SLO_GOODPUT_FLOOR: 0,
    Keys.SLO_HBM_HEADROOM_FRAC: 0,
    Keys.SLO_ERROR_RATE: 0,
    Keys.SLO_BUDGET_FRAC: 0.1,
    Keys.SLO_FAST_WINDOW_S: 300,
    Keys.SLO_SLOW_WINDOW_S: 3600,
    Keys.SLO_MIN_POINTS: 3,
    Keys.SERVE_GANG_HOSTS: 2,
    Keys.SERVE_GANG_JOB_TYPE: "decode",
    Keys.SERVE_GANG_MODEL: "tiny",
    Keys.SERVE_GANG_SEED: 0,
    Keys.SERVE_GANG_SLOTS: 4,
    Keys.SERVE_GANG_MAX_LEN: 0,
    Keys.SERVE_GANG_MAX_QUEUE: 16,
    Keys.SERVE_GANG_SHARD: False,
    Keys.SERVE_GANG_MAX_INFLIGHT: 64,
    Keys.SERVE_GANG_MAX_REPLAYS: 3,
    Keys.SERVE_GANG_TTFT_BUDGET_S: 0,
    Keys.SERVE_GANG_DRAIN_TIMEOUT_S: 30,
    Keys.SERVE_GANG_AUTOSCALE_HIGH: 0,
    Keys.SERVE_GANG_AUTOSCALE_LOW: 0,
    Keys.SERVE_GANG_AUTOSCALE_WINDOW_S: 10,
    Keys.SERVE_PREFIX_ENABLED: True,
    Keys.SERVE_PREFIX_BUDGET_MB: 64,
    Keys.SERVE_PREFIX_AFFINITY: True,
    Keys.SERVE_PREFIX_FINGERPRINT_TOKENS: 64,
    Keys.SERVE_SPEC_ENABLED: False,
    Keys.SERVE_SPEC_MAX_DRAFT: 4,
    Keys.SERVE_SPEC_DRAFT_SOURCE: "auto",
    Keys.SERVE_QUANT_ENABLED: False,
    Keys.SERVE_QUANT_KV_DTYPE: "int8",
    Keys.SERVE_QUANT_WEIGHTS: False,
    Keys.SERVE_CHUNK_TOKENS: 0,
    Keys.SERVE_POOL_PREFILL_HOSTS: 0,
    Keys.SERVE_POOL_PREFILL_JOB_TYPE: "prefill",
    Keys.SERVE_POOL_HANDOFF_MIN_TOKENS: 64,
    Keys.CLUSTER_BACKEND: "local",
    Keys.CLUSTER_TPU_CHIPS_PER_HOST: 4,
    Keys.CLUSTER_HOSTS: "",
    Keys.CLUSTER_REMOTE_TRANSPORT: "ssh",
    Keys.CLUSTER_LOCALIZE: False,
    Keys.CLUSTER_LOCALIZE_ROOT: "",
    Keys.CLUSTER_RM_ROOT: "",
    Keys.CLUSTER_LEASE_TTL_S: 600,
    Keys.HISTORY_INTERMEDIATE_DIR: "",
    Keys.HISTORY_FINISHED_DIR: "",
    Keys.PORTAL_PORT: 8080,
    Keys.CHAOS_ENABLED: False,
    Keys.CHAOS_FAULTS: "",
    Keys.CHAOS_SEED: 0,
}
