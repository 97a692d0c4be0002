"""Slot-batched continuous decoding: the serving engine.

The reference orchestrates training jobs only; serving "heavy traffic"
(ROADMAP north star) needs an inference loop that never idles the chip.
generate.py's old loop was the opposite of that: a static batch occupied the
whole decode scan until its *slowest* row finished, attention walked the full
``max_len`` cache every step, and K/V were repeat-expanded to ``n_heads``
width. This engine replaces all three:

- **Slots, not batches.** A static-shape decode batch of ``S`` slots runs
  under ONE jitted step (static shapes, no per-request compiles). A request
  owns a slot only while it is decoding; the moment it finishes (EOS or its
  token budget) the slot is freed and the admission queue refills it — the
  continuous batching of Orca/vLLM, with XLA-friendly static shapes.
- **Bucketed prefill.** Admission pads each prompt to a small set of bucket
  lengths, so prefill compiles once per bucket (bounded compile count), and
  projects only the prompt's last position through ``lm_head``
  (``forward_with_cache(last_index=...)``).
- **Paged block cache + native-GQA attention.** The KV cache is the
  refcounted physical-block pool of serve/cache.py with per-slot block
  tables (the engine plans them on the host, the decode step reads K/V
  through them — ops/decode_attention.py's paged form), sized to the
  active block count and read at native ``n_kv_heads`` width with
  per-slot lengths — decode cost scales with what is written, not
  ``max_len``.
- **Cross-request prefix reuse.** Admission matches each prompt against
  the radix prefix store (serve/prefix.py): matched full blocks map
  shared into the slot's table (refcounted, never written), a mid-block
  match gets a private copy-on-write block, and prefill computes only
  the unshared tail — attending the cached prefix K/V gathered from the
  pool, so TTFT and prefill FLOPs scale with the tail, not the prompt.
  Tail prefill is bitwise-identical to a full prefill on the same
  backend (row-independent matmuls + exactly-zero masked softmax terms),
  so engine-vs-generate parity holds with sharing live
  (tests/test_prefix.py).
- **Per-slot state.** Position, EOS, sampling parameters, and an rng stream
  ride per-slot arrays inside the jitted step, so heterogeneous requests
  (different temperatures, eos ids, budgets) share one compiled step. A
  request's tokens depend only on its own rng key — the same request
  submitted alone or into a busy engine samples identically
  (tests/test_serve.py parity).

Throughput/latency counters feed ``obs.metrics.DecodeMetrics`` (decode
tokens/s/chip, TTFT, slot occupancy). docs/SERVE.md has the architecture
notes and knob guide.
"""

from __future__ import annotations

import functools
import logging
import time
import weakref
from collections import deque
from dataclasses import dataclass, field
from functools import partial
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tony_tpu.models.generate import samples_any
from tony_tpu.models.latent_moe import LatentMoEConfig
from tony_tpu.models.llama import LlamaConfig, Params
from tony_tpu.models.shortconv_moe import ShortConvMoEConfig
from tony_tpu.models.ssm_hybrid import SSMHybridConfig
from tony_tpu.obs import hbm, health, profile, series, slo, trace
from tony_tpu.obs import compiles as compile_ledger
from tony_tpu.obs.metrics import DecodeMetrics
from tony_tpu.obs.profiler import annotate
from tony_tpu.obs.registry import HistogramWindow, Registry, snapshot_to_app_dir
from tony_tpu.serve import dense as dense_steps
from tony_tpu.serve import latent as latent_steps
from tony_tpu.serve import shortconv as shortconv_steps
from tony_tpu.serve import ssm_hybrid as ssm_hybrid_steps
from tony_tpu.serve.cache import (
    SCRATCH_BLOCK, BlockPayload, BlockPool, PagedKVCache, block_bytes,
    blocks_for, create_cache, dequantize_values, export_blocks, grow_cache,
    kv_quant_spec, map_cache, map_pools, map_scales, payload_compatible,
    quant_scatter_span, shrink_cache, slot_state_bytes, write_block,
)
from tony_tpu.serve.prefix import MatchResult, PrefixStore
from tony_tpu.serve.spec import DRAFT_SOURCES, propose_drafts

log = logging.getLogger(__name__)

# one marker a decode step in a profiler capture, named by why the step ran
# as it did (literal names: obs/profiler.annotate)
_STEP_MARKER = {
    "ahead": "serve.ahead", "finish": "serve.kept_finish",
    "admit": "serve.kept_admit", "spec": "serve.kept_spec",
    "chunk": "serve.kept_chunk", "fresh": "serve.fresh",
}


@dataclass(frozen=True)
class ServeConfig:
    """Engine knobs (docs/SERVE.md "Knobs")."""

    # concurrent decode slots (the static batch width of the jitted step)
    slots: int = 8
    # longest prompt+generation admitted; 0 -> model.max_seq_len
    max_len: int = 0
    # KV cache block size: capacity grows/shrinks in multiples of this and
    # the decode kernel tiles the sequence by it
    kv_block: int = 64
    # prefill pad lengths; () -> powers of two from 16 up to max_len.
    # Prefill compiles once per bucket (the compile-count bound).
    prefill_buckets: tuple[int, ...] = ()
    # form of the int8 weight matmul (quant_weights): 'scan' (pure XLA,
    # default) | 'pallas' (fused kernel) — ops/quant_mm.py. The paged decode
    # attention does not read it: its kernel on a TPU, the scan elsewhere
    decode_impl: str = "scan"
    # static top-k slice width for sampling: per-request top_k clamps to
    # this, and top-p-only requests use it as the bounded nucleus candidate
    # set (generate.DEFAULT_NUCLEUS_K semantics)
    max_top_k: int = 64
    # release cache blocks when the live maximum drops below half the
    # capacity (each capacity change recompiles the decode step once)
    shrink: bool = True
    # bounded admission: submit() raises AdmissionRejected once this many
    # requests are queued (0 = unbounded, the pre-gang legacy). This is the
    # backpressure seam the gang frontend leans on — a host whose queue is
    # full must say so NOW so the router can pick a survivor, not absorb
    # work it will serve tail-latency-late. Rejections count into the
    # tony_serve_rejected_total registry counter.
    max_queue: int = 0
    # cross-request prefix reuse (serve/prefix.py): admission matches each
    # prompt against the radix store and prefills only the unshared tail;
    # matched blocks are shared copy-on-write. Off = every request pays a
    # full prefill (the pre-store behaviour; the paged cache layout is the
    # same either way).
    prefix: bool = True
    # HBM the store may pin for prefixes no live slot references; LRU
    # leaves evict beyond it (serve.prefix.budget_mb). 0 = bound only by
    # allocation pressure (the pool cap).
    prefix_budget_mb: float = 64.0
    # speculative decoding (serve/spec.py): each slot drafts up to
    # spec_max_draft tokens per step (radix-store longest extension, or
    # n-gram prompt-lookup over its own context) and ONE widened decode
    # step verifies them all — accepted drafts multiply tokens/step with
    # draw-for-draw identical output (docs/SERVE.md "Speculative
    # decoding"). With spec on, finished requests also register their
    # generated tokens' blocks into the prefix store (the draft corpus).
    spec: bool = False
    # draft tokens per slot per step (k; the verify step scores k+1
    # positions). One extra decode signature per (k, pool, attended).
    spec_max_draft: int = 4
    # 'auto' (store first, n-gram fallback) | 'prefix' | 'ngram'
    spec_draft_source: str = "auto"
    # quantized KV cache (serve/cache.py "Quantized pools"): '' = bf16
    # pools (off), 'int8' | 'fp8_e4m3' = block-scaled quantized pools —
    # writes quantize against a running per-block-per-head scale, both
    # decode kernels dequantize inline, and the slot budget roughly
    # doubles (serve/capacity.py max_slots_quant measures it).
    quant_kv: str = ""
    # int8 weight-only decode matmuls (ops/quant_mm.py): the engine keeps
    # the bf16 master params for prefill and decodes through a quantized
    # copy with per-output-channel scales. Only meaningful with decode
    # traffic; requires quant_kv unset or set independently (orthogonal
    # knobs under one serve.quant.* config group).
    quant_weights: bool = False
    # chunked prefill (serve.chunk_tokens; docs/SERVE.md "Disaggregated
    # serving"): a prompt whose unshared tail exceeds this many tokens
    # prefills in chunk_tokens-sized chunks through the restartable
    # tail-prefill path, ONE chunk per engine step — a long prompt can no
    # longer stall co-resident decode streams for a whole prefill (TPOT
    # stays bounded, its own TTFT degrades gracefully). Must be a
    # multiple of kv_block (chunks start block-aligned, so tail-prefill
    # compile signatures stay the bounded per-bucket set). 0 = off.
    chunk_tokens: int = 0
    # pool label this engine serves in ('decode' | 'prefill'): pure
    # observability — stats_snapshot/series/`tony top` carry it so a
    # disaggregated gang's two pools stay distinguishable in rollups
    pool: str = "decode"


class AdmissionRejected(RuntimeError):
    """submit() refused: the admission queue is at ServeConfig.max_queue."""


@dataclass
class Request:
    """One generation request (a prompt row plus sampling parameters)."""

    prompt: Sequence[int] | np.ndarray | jax.Array
    max_new_tokens: int = 32
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0
    eos_id: int | None = None
    # int seed, typed jax key, or raw uint32 key data; None -> keyed by
    # request id (deterministic per submission order)
    rng: Any = None


@dataclass
class Completion:
    """Result of one request: generated tokens (EOS included when hit)."""

    rid: int
    tokens: list[int] = field(default_factory=list)
    prompt_len: int = 0
    finish_reason: str = ""  # 'eos' | 'length'
    ttft_s: float = 0.0


@dataclass
class _ChunkedPrefill:
    """Host-side progress of one slot's chunked prefill: the slot owns
    its blocks (planned at admission) and advances ``pos`` by one chunk
    per engine step until the final chunk samples the first token."""

    rid: int
    req: Request
    prompt: np.ndarray
    pos: int          # tokens already written (prefix match + done chunks)
    key: Any          # the request's sampling key (spent by the FINAL chunk)
    t0: float         # admission start (TTFT spans the whole chunked prefill)


@dataclass
class _InFlight:
    """One decode step between its plan and its emit — what the host keeps
    of it while the device runs it, and while the step AFTER it is already
    dispatched. It holds only results no later program consumes: the cache
    and the slot state a step returns are donated to the next program, so
    whether a row hit its eos is read off the fetched tokens (``tok ==
    eos``; a row that finishes leaves the batch at once, so the host has no
    use for the device's sticky ``done`` flag)."""

    rows: list[tuple[int, int]]   # (slot, rid) in the decode batch at dispatch
    drafts: Any                   # [S, k] draft batch of a speculative step, else None
    dlens: list[int]              # draft tokens per slot (all 0 on a plain step)
    left: list[int] = field(default_factory=list)  # per row: tokens still owed after this step
    # why it ran as it did (obs.metrics.STEP_REASONS): 'ahead' = dispatched
    # before the step before it was read, else what kept it, or 'fresh'
    why: str = "fresh"
    t0: float = 0.0               # dispatch time
    out: Any = None               # device results the host reads: (tokens, n_emit | None)
    aux: dict = field(default_factory=dict)  # rides the fetch; then the health monitors
    host: Any = None              # ``out`` on the host, once fetched
    dt: float = 0.0               # wall time charged to the step (Engine._sync)


@dataclass(slots=True)
class _FirstToken:
    """One request's way to its first token: consecutive readings of ONE
    clock (``time.perf_counter``), from ``submit()`` to the slot's
    activation. ``Engine.step`` takes the last one, when it returns to its
    caller, and the five differences are the request's time to first token,
    tiled (docs/SERVE.md "The step loop")."""

    submit: float          # submit()
    round: float           # the _admit() round that dequeued it began
    dispatch: float = 0.0  # its own prefill (a chunked prompt's first chunk) is dispatched
    token: float = 0.0     # its first token is on the host
    active: float = 0.0    # _activate_slot returned


class _SlotState(NamedTuple):
    """Per-slot device state threaded through the jitted decode step."""

    last_tok: jax.Array   # [S] int32 — token to feed this step
    rng: jax.Array        # [S, 2] uint32 — per-slot rng stream (raw keys)
    temp: jax.Array       # [S] float32
    top_k: jax.Array      # [S] int32
    top_p: jax.Array      # [S] float32
    eos: jax.Array        # [S] int32, -1 = no eos
    done: jax.Array       # [S] bool — row has emitted eos
    live: jax.Array       # [S] bool — slot owned by a request


def _as_raw_key(rng: Any, rid: int) -> jnp.ndarray:
    """Normalise a request rng (seed | typed key | raw data) to uint32[2]."""
    if rng is None:
        rng = rid
    if isinstance(rng, int):
        # the common case (a seed, or the request id) as ONE program at
        # admission; the value is ``jax.random.key``'s own for every int
        return _seed_key_fn()(np.int64(rng))
    arr = jnp.asarray(rng)
    if jnp.issubdtype(arr.dtype, jax.dtypes.prng_key):
        return jax.random.key_data(arr).astype(jnp.uint32)
    return arr.astype(jnp.uint32)


def _weak_stats_source(engine: "Engine", recorder, key: str):
    """A series source that does not own the engine: the closure holds a
    weakref, so an engine dropped without close() (failed construction,
    abandoned bench sweep) is collectable — and the first scrape after
    collection detaches the dead source instead of erroring forever."""
    ref = weakref.ref(engine)

    def source() -> dict:
        eng = ref()
        if eng is None:
            recorder.detach(key)
            return {}
        return eng.stats_snapshot(windowed=True)

    return source


def _default_buckets(max_len: int) -> tuple[int, ...]:
    out, b = [], 16
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


class Engine:
    """Continuous-batching decode engine over a block KV cache.

    Typical use::

        engine = Engine(params, cfg, ServeConfig(slots=8))
        rid = engine.submit(Request(prompt=..., max_new_tokens=64))
        completions = engine.run()         # drain queue + live slots

    ``submit``/``step`` can interleave (a driver can feed arrivals between
    steps — bench.py's mixed-arrival trace does); ``run`` just steps until
    everything drains. Single-process, one model replica; scale-out is
    replica-per-chip above this layer.
    """

    def __init__(self, params: Params, cfg: LlamaConfig, serve: ServeConfig):
        """``cfg`` is a :class:`LlamaConfig` (dense grouped-query decoder),
        a ``models.latent_moe.LatentMoEConfig`` (latent attention, sigmoid
        group-limited experts), a ``models.shortconv_moe.
        ShortConvMoEConfig`` (short convolutions beside attention layers,
        sigmoid-routed experts) or a ``models.ssm_hybrid.SSMHybridConfig``
        (selective state-space layers beside position-free attention
        layers): same loop, pool and tables; the step bodies, the cache's
        rows and the per-slot state are the family's (:func:`steps_for`)."""
        self._steps = steps_for(cfg)
        for knob in self._steps.REFUSED_KNOBS:
            # block_handoff is no field: refused where it is called
            _refuse(self._steps, knob, getattr(serve, knob, False))
        self.params = params
        self.cfg = cfg
        max_len = serve.max_len or cfg.max_seq_len
        buckets = tuple(sorted(serve.prefill_buckets)) or _default_buckets(max_len)
        cap = blocks_for(max_len, serve.kv_block) * serve.kv_block
        if buckets[-1] > cap:
            # an oversized bucket passes submit() validation but cannot be
            # inserted into a cache capped at max_len — reject at build time
            raise ValueError(
                f"prefill bucket {buckets[-1]} exceeds the cache capacity "
                f"ceiling {cap} (max_len {max_len} rounded up to kv_block)"
            )
        if serve.spec_draft_source not in DRAFT_SOURCES:
            raise ValueError(
                f"spec_draft_source {serve.spec_draft_source!r} not in "
                f"{DRAFT_SOURCES}"
            )
        if serve.spec and serve.spec_max_draft < 1:
            raise ValueError("spec_max_draft must be >= 1 with spec on")
        if serve.quant_kv:
            kv_quant_spec(serve.quant_kv)  # validate the knob at build time
        if serve.chunk_tokens and serve.chunk_tokens % serve.kv_block:
            raise ValueError(
                f"chunk_tokens {serve.chunk_tokens} must be a multiple of "
                f"kv_block {serve.kv_block} (chunks start block-aligned so "
                "tail-prefill signatures stay bounded)"
            )
        self.serve = ServeConfig(
            slots=serve.slots, max_len=max_len, kv_block=serve.kv_block,
            prefill_buckets=buckets, decode_impl=serve.decode_impl,
            max_top_k=serve.max_top_k, shrink=serve.shrink,
            max_queue=serve.max_queue, prefix=serve.prefix,
            prefix_budget_mb=serve.prefix_budget_mb, spec=serve.spec,
            spec_max_draft=serve.spec_max_draft,
            spec_draft_source=serve.spec_draft_source,
            quant_kv=serve.quant_kv, quant_weights=serve.quant_weights,
            chunk_tokens=serve.chunk_tokens, pool=serve.pool,
        )
        S = self.serve.slots
        try:
            # tokens/s/chip divides by the devices actually backing the
            # model (a sharded-params engine must not overreport per-chip)
            n_chips = max(1, len(jax.tree.leaves(params)[0].sharding.device_set))
        except AttributeError:  # params are not jax arrays
            n_chips = 1
        self.metrics = DecodeMetrics(n_chips=n_chips)
        # paged pool + per-slot block tables (serve/cache.py): the table is
        # planned on the host (np mirror) and uploaded as a [S, attended]
        # device slice only when it changed — steady-state decode reuses
        # the cached device copy
        B = self.serve.kv_block
        self._m_total = blocks_for(max_len, B)
        blk_bytes = block_bytes(cfg, B, quant_kv=self.serve.quant_kv)
        self._blk_bytes = blk_bytes
        self.metrics.kv_bytes_per_token = blk_bytes / B
        self.metrics.slot_state_bytes = slot_state_bytes(cfg, S)
        budget_bytes = int(self.serve.prefix_budget_mb * 2**20)
        budget_blocks = (
            max(1, -(-budget_bytes // blk_bytes)) if budget_bytes
            else S * self._m_total
        )
        # the pool never needs more than every slot at max_len plus the
        # store's budget (plus scratch) — growth stops here, eviction
        # takes over
        self._pool_cap = 1 + S * self._m_total + (
            budget_blocks if self.serve.prefix else 0
        )
        p0 = max(2, min(1 + S, self._pool_cap))
        self._p0 = p0
        self._pool = BlockPool(p0)
        self.cache = create_cache(cfg, S, p0, B, quant_kv=self.serve.quant_kv)
        # quantized pools: block ids whose scale rows need zeroing before
        # the next device write (allocation-time stale-scale reset — a
        # reused block must not inherit its previous tenant's scale)
        self._fresh_scale: list[int] = []
        # int8 weight-only decode: quantize ONCE at build; prefill keeps
        # the bf16 master params, decode/spec steps read the quantized copy
        self._qparams = (
            self._steps.quantize_decode_params(params)
            if self.serve.quant_weights
            else None
        )
        self._dec_params = self._qparams if self._qparams is not None else params
        self._store: PrefixStore | None = None
        if self.serve.prefix:
            self._store = PrefixStore(
                block=B, block_bytes=blk_bytes, budget_bytes=budget_bytes
            )
        self._table = np.zeros((S, self._m_total), np.int32)
        self._slot_blocks = [0] * S
        self._attended = 1
        self._table_dev = jnp.asarray(self._table[:, :1])
        self._table_dirty = False
        self._cow_copies = 0
        self.state = _SlotState(
            last_tok=jnp.zeros((S,), jnp.int32),
            rng=jnp.zeros((S, 2), jnp.uint32),
            temp=jnp.zeros((S,), jnp.float32),
            top_k=jnp.zeros((S,), jnp.int32),
            top_p=jnp.zeros((S,), jnp.float32),
            eos=jnp.full((S,), -1, jnp.int32),
            done=jnp.zeros((S,), bool),
            live=jnp.zeros((S,), bool),
        )
        self._queue: deque[tuple[int, Request]] = deque()
        # slots mid-chunked-prefill (slot -> progress): they hold their
        # blocks but stay out of the decode batch until the final chunk
        self._chunking: dict[int, _ChunkedPrefill] = {}
        self._completions: dict[int, Completion] = {}
        self._slot_rid: list[int | None] = [None] * S
        self._slot_remaining: list[int] = [0] * S
        self._slot_len: list[int] = [0] * S       # host mirror of lengths
        self._slot_eos: list[int] = [-1] * S      # the request's eos id, -1 = none
        # the decode step dispatched and not yet emitted (docs/SERVE.md "The
        # step loop"): None between steps that ran in today's order
        self._inflight: _InFlight | None = None
        self._synced_t = 0.0                      # when the last decode fetch returned
        self._submit_t: dict[int, float] = {}
        # the step loop's own account (docs/SERVE.md "The step loop"): a
        # request's readings on the way to its first token, and those whose
        # token the current step() call will hand to its caller
        self._first: dict[int, _FirstToken] = {}
        self._came_visible: list[_FirstToken] = []
        self._round_t = 0.0                       # when the current _admit() round began
        self._call_t = 0.0                        # when the current step() call began
        self._call_admitted = 0                   # prefills and chunks it ran
        self._call_admit_s = 0.0                  # and the seconds they took (no drain)
        # what kept the NEXT step planned with nothing in flight ('fresh':
        # no verdict stands before it), when the last emit ended (0: the
        # engine was idle since), and host seconds under plan / dispatch /
        # sync since then
        self._next_why = "fresh"
        self._emit_t = 0.0
        self._phase_s = [0.0, 0.0, 0.0]
        self._next_rid = 0
        self._prefill_fns: dict[int, Any] = {}
        self._tail_fns: dict[tuple[int, int], Any] = {}
        self._decode_fns: dict[tuple[int, int], Any] = {}
        # speculative verify steps, same (pool, attended) signature ladder
        # at the engine's fixed draft width k (one extra signature per
        # ladder rung — the bounded-compile contract carries over)
        self._spec_fns: dict[tuple[int, int], Any] = {}
        # host token context per slot (prompt + every emitted token, the
        # next input token last) — the draft sources read it; maintained
        # only with spec on
        self._slot_ctx: list[list[int]] = [[] for _ in range(S)]
        # trace/metrics spine: join the job's trace from the AM-exported
        # env (no-op outside a traced tony-tpu job, idempotent when the
        # user script armed it already), then per-request span handles
        # (queued -> prefill -> decode -> finish) and the TTFT/TPOT/
        # step-time distributions the portal /metrics endpoint serves
        # (docs/OBS.md catalogue). Per-engine registry: a recreated engine
        # (restart, bench sweep) reports its own distributions, not a
        # blend with its predecessor's
        trace.install_from_env()
        # HBM observatory + compile ledger: sampled memory counter tracks
        # from the decode loop, AOT decode compiles journaled with their
        # measured memory plans (obs/hbm.py, obs/compiles.py)
        hbm.install_from_env()
        # numerics sentinel (obs/health.py): when armed, the decode step
        # fuses per-slot logits-nonfinite counts + sampling entropy and
        # the engine feeds them to the async rule engine with per-request
        # attribution; disarmed, none of it is compiled in
        health.install_from_env()
        self._monitors = health.active_sentinel() is not None
        # live time-series (obs/series.py): the engine publishes its
        # stats_snapshot() as a scrape source — queue depth, occupancy,
        # windowed TTFT/TPOT quantiles — so the recorder (and the SLO
        # engine riding it) never walks private engine state. The source
        # itself attaches at the END of __init__ (after the registry it
        # reads exists) and holds only a weakref: an engine abandoned
        # without close() must not be pinned — params + KV cache — by the
        # process-global recorder forever.
        series.install_from_env()
        # coordinated profiling (obs/profile.py): a `tony profile` window
        # broadcast by the AM captures this host's decode steps too — the
        # maybe_capture seam rides step()
        profile.install_from_env()
        self._series = series.active_recorder()
        self._snap_window = HistogramWindow()   # since-last-scrape quantiles
        self._snap_prev: dict[str, float] = {}  # counter deltas (error rate)
        self._series_key = f"engine@{id(self):x}"
        self._ledger = compile_ledger.get_ledger()
        self._compiles_t0 = self._ledger.backend_compiles
        # engine-scoped watermark mark: close() reports THIS engine's peak
        # via the attribution rule, never the process's cumulative counter
        # (a train-then-serve process must not inherit the trainer's peak)
        watch = hbm.active_watch()
        self._hbm_mark = watch.mark() if watch is not None else None
        self._init_registry()
        self._queued_spans: dict[int, Any] = {}
        self._decode_spans: dict[int, Any] = {}
        self._first_tok_t: dict[int, float] = {}
        if self._series is not None:
            self._series.attach(
                self._series_key,
                _weak_stats_source(self, self._series, self._series_key),
            )

    # --- public API -----------------------------------------------------------

    def submit(self, req: Request) -> int:
        """Queue a request; returns its id (the key into run()'s result)."""
        # np.shape reads metadata only — no device transfer for jax arrays
        shape = np.shape(req.prompt)
        plen = int(shape[-1]) if shape else 0
        if plen < 1:
            raise ValueError("empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {req.max_new_tokens} "
                "(prefill always samples the first token)"
            )
        if plen >= self.serve.max_len:
            # explicit and FIRST: an over-long prompt must fail with the
            # real reason (max_len), deterministically, at submit time —
            # never reach admission where it would wedge a slot. The gang
            # worker maps ValueError to a terminal "invalid" chunk, so the
            # frontend finishes the request instead of replaying it.
            raise ValueError(
                f"prompt length {plen} must be shorter than max_len "
                f"{self.serve.max_len} (at least one generated token must fit)"
            )
        if plen > max(self.serve.prefill_buckets):
            raise ValueError(
                f"prompt length {plen} exceeds the largest prefill bucket "
                f"{max(self.serve.prefill_buckets)}"
            )
        if plen + req.max_new_tokens > self.serve.max_len:
            raise ValueError(
                f"prompt {plen} + max_new_tokens {req.max_new_tokens} "
                f"exceeds max_len {self.serve.max_len}"
            )
        if self.serve.max_queue and len(self._queue) >= self.serve.max_queue:
            # an explicit reject, never silent queueing past the bound: the
            # caller (gang frontend, a driver) owns the backpressure policy
            self._c_rejected.inc()
            raise AdmissionRejected(
                f"admission queue full ({len(self._queue)} >= max_queue "
                f"{self.serve.max_queue})"
            )
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append((rid, req))
        self._submit_t[rid] = time.perf_counter()
        self._g_queue.set(len(self._queue))
        tracer = trace.active_tracer()
        if tracer is not None:
            # queue-wait span: starts now, ends when the request is slotted
            self._queued_spans[rid] = tracer.span(
                "serve.queued", rid=rid, prompt_len=plen
            )
        return rid

    @property
    def n_live(self) -> int:
        return sum(1 for r in self._slot_rid if r is not None)

    @property
    def n_decoding(self) -> int:
        """Live slots actually in the decode batch (a slot mid-chunked-
        prefill holds its blocks but does not decode yet)."""
        return sum(
            1 for s, r in enumerate(self._slot_rid)
            if r is not None and s not in self._chunking
        )

    @property
    def queue_depth(self) -> int:
        """Requests admitted but not yet slotted."""
        return len(self._queue)

    @property
    def rejected_total(self) -> int:
        """Submissions refused by bounded admission since the last
        reset_metrics()."""
        return int(self._c_rejected.value)

    def stats_snapshot(self, windowed: bool = False) -> dict[str, float]:
        """Cheap host-side stats: queue depth, slot occupancy, token/
        request counters, and TTFT/TPOT/step-time quantiles. ONE public
        surface for every consumer — the series recorder, the gang
        ``DecodeStats`` RPC, and the gang worker's AM metrics push — so
        none of them walks private engine state, and none syncs a device
        (everything here is host counters).

        ``windowed=True`` reports quantiles *since the previous windowed
        call* (the series recorder's live view: p99 TTFT now, not blended
        with warmup); the default reports run-cumulative quantiles (the
        RPC/stats view). The windowed state is single-consumer by design
        — only the engine's own series source uses it."""
        scanned = int(self._steps.SCAN_STATE)
        snap: dict[str, float] = {
            "queue_depth": float(len(self._queue)),
            "live_slots": float(self.n_live),
            "slots": float(self.serve.slots),
            "occupancy": round(self.n_live / max(self.serve.slots, 1), 4),
            "generated_tokens": float(self._c_tokens.value),
            "requests_finished": float(self._c_finished.value),
            "rejected_total": float(self._c_rejected.value),
            # decode tokens emitted per decode step: 1.0 autoregressive,
            # > 1 when speculative drafts land (`tony top`'s tok/st)
            "tokens_per_step": round(self.metrics.tokens_per_step, 4),
            # HBM per cached token (block bytes / block positions): the
            # quantized-serving capacity win, live (`tony top`'s kvB/t)
            "kv_bytes_per_token": round(self.metrics.kv_bytes_per_token, 2),
            # the second kind of state (a family's ``slot_state``): bytes
            # resident for all slots, and prefill or chunk results written
            # into a slot's (admissions + chunk boundaries); 0 without one
            "slot_state_bytes": float(self.metrics.slot_state_bytes),
            "state_handoffs": float(self.metrics.state_handoffs),
            # a state that prefill scans and every decode step reads and
            # rewrites (the steps module's SCAN_STATE): the prompt tokens put
            # through the scan, and the bytes of slot state the decode steps
            # streamed (live slots x a slot's bytes x 2, a step); both are
            # arithmetic on the counters above, 0 for the other families
            "scan_tokens": float(scanned * (
                self.metrics.prompt_tokens - self.metrics.prefix_hit_tokens)),
            "state_stream_bytes": float(
                scanned * 2 * slot_state_bytes(self.cfg, 1) * self.metrics.decode_live_sum),
            # the host's touches of the device between model programs: a
            # sound run reads device_fetches = decode steps + first tokens
            # (+ chunks that counted routes), slot_programs = activations
            # + finishes
            "device_fetches": float(self.metrics.device_fetches),
            "slot_programs": float(self.metrics.slot_programs),
            # decode steps dispatched before the step before them was read
            # (over decode_steps: the share of steps whose host wait lay
            # under the device's work)
            "decode_steps": float(self.metrics.decode_steps),
            "steps_ahead": float(self.metrics.steps_ahead),
            # decode steps whose sampler ran the vocabulary-wide top-k and
            # draw (some live row samples), and their share of decode_steps
            "vocab_sampler_steps": float(self.metrics.vocab_sampler_steps),
            "vocab_sampler_share": round(
                self.metrics.vocab_sampler_steps / max(self.metrics.decode_steps, 1), 4),
            # the rest of them by what kept them (`_may_run_ahead`; those with
            # no verdict before them are what is left of decode_steps), and
            # the steps whose visible gap, less the same call's admissions,
            # was STALL_FACTOR x the running median of their kind's (each
            # logged once, with its largest phase)
            **{f"steps_kept_{why}": float(n)
               for why, n in self.metrics.steps_kept.items()},
            "stalled_steps": float(self.metrics.stalled_steps),
            "stalled_s": round(self.metrics.stalled_s, 4),
            # pool label (disaggregated gangs): a string, so it rides the
            # series journal but the numeric AM metrics push drops it —
            # AM-rollup consumers derive the pool from the task type instead
            "pool": self.serve.pool,
        }
        m = self.metrics
        if m.requests_started:
            # a request's time to first token, tiled: the means of its five
            # parts, which add up to the mean of ``ttft_visible`` below
            # (what a caller feels; ``ttft`` ends when the token is on the
            # host, a part and a decode step earlier)
            for part in ("queue", "behind", "prefill", "activate", "held"):
                snap[f"ttft_{part}_mean_s"] = round(
                    getattr(m, f"ttft_{part}_s") / m.requests_started, 6)
        for why in m.step_gap_s:
            n = m.steps_of(why)
            if n:
                # per reason: a step's visible gap and its share of decode_s
                snap[f"step_gap_mean_s_{why}"] = round(m.step_gap_s[why] / n, 6)
                snap[f"step_dt_mean_s_{why}"] = round(m.step_dt_s[why] / n, 6)
        if self._chunking:
            # slots mid-chunked-prefill: occupied but not decoding yet
            snap["chunking_slots"] = float(len(self._chunking))
        shipped = float(self._c_handoff_shipped.value)
        adopted = float(self._c_handoff_adopted.value)
        freed = float(self._c_handoff_freed.value)
        if shipped or adopted or freed:
            # blockwise handoff accounting: on a healthy host every
            # shipped block lands adopted or freed SOMEWHERE — the chaos
            # handoff-no-block-leak invariant audits the frontend's
            # per-request ledger view of these
            snap["handoff_shipped_blocks"] = shipped
            snap["handoff_adopted_blocks"] = adopted
            snap["handoff_freed_blocks"] = freed
        if self.serve.quant_kv:
            resident = float(self._pool.n_blocks * self._blk_bytes)
            snap["quant_pool_resident_bytes"] = resident
            self._g_quant_resident.set(resident)
        if self.serve.spec:
            snap["draft_accept_rate"] = round(
                self.metrics.draft_accept_rate, 4
            )
            snap["spec_rollbacks"] = float(self.metrics.spec_rollbacks)
        if self._store is not None:
            # cross-request reuse health (cumulative): hit rate feeds the
            # series recorder, the portal, and `tony top`'s hit% column
            snap.update(self._store.stats())
            snap["pool_blocks"] = float(self._pool.n_blocks)
        for hist, prefix in (
            (self._h_ttft, "ttft"),
            (self._h_visible, "ttft_visible"),
            (self._h_tpot, "tpot"),
            (self._h_step, "decode_step"),
        ):
            if windowed:
                d = self._snap_window.delta(hist)
                if d["count"]:
                    snap[f"{prefix}_p50_s"] = round(d["p50"], 4)
                    snap[f"{prefix}_p99_s"] = round(d["p99"], 4)
                    snap[f"{prefix}_n"] = d["count"]
            elif hist.count:
                snap[f"{prefix}_p50_s"] = round(hist.quantile(0.5), 4)
                snap[f"{prefix}_p99_s"] = round(hist.quantile(0.99), 4)
                snap[f"{prefix}_n"] = float(hist.count)
        if windowed:
            # windowed serve error rate: explicit rejections over requests
            # resolved in the window (the slo.error_rate input); the
            # engine itself has no other error class — relay/transport
            # errors are the frontend ledger's to count
            rej = snap["rejected_total"] - self._snap_prev.get("rejected", 0.0)
            fin = snap["requests_finished"] - self._snap_prev.get("finished", 0.0)
            self._snap_prev["rejected"] = snap["rejected_total"]
            self._snap_prev["finished"] = snap["requests_finished"]
            if rej + fin > 0:
                snap["error_rate"] = round(rej / (rej + fin), 4)
        return snap

    def _init_registry(self) -> None:
        reg = self.registry = Registry()
        self._h_ttft = reg.histogram(
            "tony_ttft_seconds",
            "request submit -> first sampled token on the engine's host (the "
            "engine's view; a caller sees the token when step() returns: "
            "tony_ttft_visible_seconds)")
        self._h_visible = reg.histogram(
            "tony_ttft_visible_seconds",
            "request submit -> the step() call that admitted it returns to "
            "its caller (what a caller feels: tony_ttft_seconds + the slot's "
            "activation + the decode step the same call runs first)")
        self._h_tpot = reg.histogram("tony_tpot_seconds",
                                     "mean per-token latency after the first")
        self._h_step = reg.histogram("tony_decode_step_seconds",
                                     "one engine decode step (all live slots)")
        self._g_queue = reg.gauge("tony_queue_depth",
                                  "requests admitted but not yet slotted")
        self._c_tokens = reg.counter("tony_generated_tokens_total",
                                     "tokens sampled (prefill + decode)")
        self._c_finished = reg.counter("tony_requests_finished_total",
                                       "requests completed (eos or budget)")
        self._c_rejected = reg.counter(
            "tony_serve_rejected_total",
            "submissions rejected by bounded admission (queue at max_queue)",
        )
        self._c_prefix_hit = reg.counter(
            "tony_serve_prefix_hit_tokens_total",
            "prompt tokens served from the prefix store (no re-prefill)",
        )
        self._c_prompt_tokens = reg.counter(
            "tony_serve_prompt_tokens_total",
            "prompt tokens admitted (the prefix hit-rate denominator)",
        )
        self._g_prefix_bytes = reg.gauge(
            "tony_serve_prefix_resident_bytes",
            "HBM pinned by prefix-store block references",
        )
        self._g_prefix_nodes = reg.gauge(
            "tony_serve_prefix_nodes", "radix nodes resident in the store",
        )
        self._c_draft_prop = reg.counter(
            "tony_serve_draft_proposed_total",
            "speculative draft tokens proposed (serve/spec.py)",
        )
        self._c_draft_acc = reg.counter(
            "tony_serve_draft_accepted_total",
            "speculative draft tokens accepted (target sample agreed)",
        )
        self._g_kv_bpt = reg.gauge(
            "tony_serve_kv_bytes_per_token",
            "HBM per cached token (quantized pools store int8/fp8 + scales)",
        )
        self._g_kv_bpt.set(self._blk_bytes / self.serve.kv_block)
        self._g_quant_resident = reg.gauge(
            "tony_serve_quant_pool_resident_bytes",
            "HBM resident in the quantized KV pool (payload + scale rows)",
        )
        self._c_steps_ahead = reg.counter(
            "tony_serve_steps_ahead_total",
            "decode steps dispatched before the step before them was read",
        )
        self._c_vocab_sampler = reg.counter(
            "tony_serve_vocab_sampler_steps_total",
            "decode steps whose sampler ran its vocabulary-wide top-k and draw "
            "(some live request samples; the rest took the argmax alone)",
        )
        self._c_stalled = reg.counter(
            "tony_serve_stalled_steps_total",
            "decode steps whose visible gap, less the same call's admissions, "
            "exceeded 4x the running median of their kind's (each logged with "
            "its largest host phase)",
        )
        self._c_handoff_shipped = reg.counter(
            "tony_serve_handoff_shipped_blocks_total",
            "physical blocks exported for a blockwise KV handoff",
        )
        self._c_handoff_adopted = reg.counter(
            "tony_serve_handoff_adopted_blocks_total",
            "shipped blocks adopted into this pool (prefix-store owned)",
        )
        self._c_handoff_freed = reg.counter(
            "tony_serve_handoff_freed_blocks_total",
            "shipped blocks freed on arrival (prefix already resident)",
        )

    def reset_metrics(self) -> None:
        """Fresh throughput/latency counters (e.g. after a warmup trace
        that paid the compiles); compile counts persist — they describe
        the engine, not the trace. The registry histograms reset too, or
        close()'s TTFT/TPOT quantiles and the job-history snapshot would
        blend warmup compile time into the measured trace. A step in
        flight stays in flight: it is counted, whole, where it is emitted."""
        self.metrics = DecodeMetrics(
            n_chips=self.metrics.n_chips,
            prefill_compiles=len(self._prefill_fns) + len(self._tail_fns),
            decode_compiles=len(self._decode_fns) + len(self._spec_fns),
            kv_bytes_per_token=self.metrics.kv_bytes_per_token,
            slot_state_bytes=self.metrics.slot_state_bytes,
        )
        self._init_registry()
        # windowed-snapshot baselines re-base with the counters: a stale
        # pre-reset baseline would report negative error-rate deltas
        # (HistogramWindow re-bases itself on the fresh histogram objects)
        self._snap_prev.clear()
        self._g_queue.set(len(self._queue))

    def close(self) -> dict:
        """Shutdown summary: log + return the final DecodeMetrics summary
        (TTFT, tokens/s/chip, and — the silent regression — the compile
        counts) so it is visible without reading the portal, and snapshot
        the metrics registry into the job history when running under a
        tony-tpu job. Quantiles come from the registry histograms.
        Requests still queued or mid-decode get their spans ended with
        reason=shutdown — a hung request must be visible in the trace."""
        for spans in (self._queued_spans, self._decode_spans):
            for sp in spans.values():
                sp.end(reason="shutdown")
            spans.clear()
        self._first_tok_t.clear()
        self._first.clear()
        if self._inflight is not None:
            # a step still in flight: wait for it, so the device is quiet
            # when close() returns, and drop what it sampled
            jax.block_until_ready(self._inflight.out)
            self._inflight = None
        # a profile window still open at shutdown finalises (partial trace
        # + manifest land) instead of dying with the engine
        profile.finish_capture()
        s = self.metrics.summary()
        if self._h_ttft.count:
            s["ttft_p50_s"] = round(self._h_ttft.quantile(0.5), 4)
            s["ttft_p99_s"] = round(self._h_ttft.quantile(0.99), 4)
        if self._h_tpot.count:
            s["tpot_p50_s"] = round(self._h_tpot.quantile(0.5), 4)
        # ledger-sourced lines: XLA compiles this engine actually triggered
        # (the DecodeMetrics counts are per-signature intents; this is what
        # the backend really compiled) and the engine-scoped peak-HBM
        # watermark (marked at __init__, measured by the attribution rule)
        s["xla_compiles"] = self._ledger.backend_compiles - self._compiles_t0
        if self._store is not None:
            # prefix-store lifetime summary: hit rate is the reuse headline,
            # cow_copies the sharing-safety one (each is a block the store
            # protected from a would-be shared write)
            s["prefix"] = dict(self._store.stats())
            s["prefix"]["cow_copies"] = self._cow_copies
        sentinel = health.active_sentinel()
        if sentinel is not None:
            # drain so a trip on the final decode steps reaches the summary,
            # then export tony_health_* into this engine's registry (it is
            # snapshotted below) and persist the verdict file
            sentinel.drain()
            s["health_verdict"] = sentinel.verdict
            trips = sentinel.trip_counts()
            if trips:
                s["health_trips"] = trips
            sentinel.export(self.registry)
            sentinel.write_verdict()
        # live series + SLO teardown: final scrape drained, source
        # detached (a recreated engine must not leave a stale closure
        # scraping freed state), verdict persisted — `met` verdicts exist
        # on disk too, so absence stays distinguishable from success
        if self._series is not None:
            self._series.force_sample()
            self._series.drain()
            self._series.detach(self._series_key)
        slo_engine = slo.active_engine()
        if slo_engine is not None:
            s["slo_verdict"] = slo_engine.verdict
            trips = slo_engine.trip_counts()
            if trips:
                s["slo_trips"] = trips
            slo_engine.export(self.registry)
            slo_engine.write_verdict()
        watch = hbm.active_watch()
        if watch is not None and self._hbm_mark is not None:
            peak_gb, peak_exact = watch.peak_since(self._hbm_mark)
            if peak_gb:
                s["peak_hbm_gb"] = peak_gb
                s["peak_hbm_exact"] = peak_exact
            # gauges into THIS registry so tony_hbm_* lands in the
            # job-history snapshot the portal /metrics serves
            watch.export_gauges(self.registry)
        log.info("engine shutdown: %s", s)
        # suffixed so a train-then-serve user process cannot overwrite one
        # component's snapshot with the other's
        snapshot_to_app_dir(
            trace.default_proc_name("serve") + "_engine", self.registry
        )
        compile_ledger.snapshot_to_app_dir()
        return s

    def step(self) -> int:
        """Admit what fits, make ONE decode step's tokens visible; returns
        live-slot count. The device may be one step further: when ``step()``
        returns, the step after the one just emitted can already be in
        flight (:meth:`_decode_once`), and ``self.cache`` / ``self.state``
        are then that step's results. Callers read tokens and finishes from
        completions, never from device state."""
        # coordinated-profiling seam (one global load + None compare
        # disarmed): a broadcast window brackets decode steps exactly like
        # train steps, so `tony profile` anatomises serving hosts too
        profile.maybe_capture()
        self._call_t = time.perf_counter()
        self._call_admitted = 0
        self._call_admit_s = 0.0
        # chunked-prefill interleave: slots already chunking advance ONE
        # chunk each per step (slots _admit parks into chunking below ran
        # their first chunk inside admission — advancing them again here
        # would burn two chunks in one step)
        pending = sorted(self._chunking)
        self._admit()
        if pending:
            t0, synced = time.perf_counter(), self._phase_s[2]
            for slot in pending:
                if slot in self._chunking:
                    self._prefill_chunk(slot)
            # as a round of admissions: without the wait for a step in flight
            self._call_admit_s += time.perf_counter() - t0 - (self._phase_s[2] - synced)
        decoding = self.n_decoding
        if decoding:
            self._decode_once()
        live = self.n_live
        if not (decoding and live):
            # nothing decoded in this call, or nothing is left alive: no
            # verdict stands before the next step, and its gap starts with
            # the call that runs it
            self._next_why, self._emit_t = "fresh", 0.0
        if self._came_visible:
            self._hand_over_first_tokens()
        return live

    def _hand_over_first_tokens(self) -> None:
        """The last thing a ``step()`` call does: the requests it activated
        become visible to its caller NOW. One more reading of the clock
        closes each one's time to first token — ``held``, the decode step
        (and a step dispatched ahead) the same call ran after the activation
        — and the five parts go to the counters, ``tony_ttft_visible_seconds``
        and, under a profiler session, one ``serve.visible`` marker a request."""
        now = time.perf_counter()
        for f in self._came_visible:
            queue, behind = f.round - f.submit, f.dispatch - f.round
            prefill, activate = f.token - f.dispatch, f.active - f.token
            held = now - f.active
            self.metrics.record_visible(queue, behind, prefill, activate, held)
            self._h_visible.observe(now - f.submit)
            with annotate("serve.visible", queue_us=int(queue * 1e6),
                          behind_us=int(behind * 1e6), prefill_us=int(prefill * 1e6),
                          activate_us=int(activate * 1e6), held_us=int(held * 1e6)):
                pass
        self._came_visible.clear()

    def completion_of(self, rid: int) -> Completion | None:
        """Live view of a request's completion: ``tokens`` grows in place
        as the engine decodes and ``finish_reason`` lands when it ends.
        The gang worker's streaming seam (serve/gang.py) — callers must
        not mutate the returned object."""
        return self._completions.get(rid)

    def take_completion(self, rid: int) -> Completion | None:
        """Pop one finished completion (the incremental form of what
        run() does wholesale, so a long-lived streaming driver never
        accumulates every Completion forever)."""
        return self._completions.pop(rid, None)

    def run(self, requests: Sequence[Request] | None = None) -> dict[int, Completion]:
        """Submit ``requests`` (if given), drain queue and live slots, and
        return — and evict — every completion finished by this call (a
        long-lived engine must not accumulate one Completion per request
        forever; callers keep what run() hands them)."""
        if requests is not None:
            for r in requests:
                self.submit(r)
        self._run_loop()
        done, self._completions = self._completions, {}
        return done

    def _run_loop(self) -> None:
        """Drain queue + live slots under the runtime sanitizer when armed
        (GRAFT_SANITIZE=1): implicit D2H transfers and steady-state
        compiles raise (analysis/sanitize.py). A cold engine compiles per
        prefill bucket / cache capacity by design — sanitize a *warmed*
        engine, or budget via GRAFT_SANITIZE_MAX_COMPILES. A
        RESOURCE_EXHAUSTED escaping the loop dumps OOM forensics into the
        app dir before re-raising (obs/hbm.py)."""
        from tony_tpu.analysis import sanitize

        with hbm.oom_guard("engine.run"), \
                sanitize.sanitized_loop("decode") as watchdog:
            while self._queue or self.n_live:
                self.step()
                if watchdog is not None:
                    watchdog.check()

    # --- admission ------------------------------------------------------------

    def _admit(self) -> None:
        if not self._queue:
            return
        free = [s for s, r in enumerate(self._slot_rid) if r is None]
        if not free:
            return
        self._round_t, synced = time.perf_counter(), self._phase_s[2]
        with annotate("serve.admit"):
            while free and self._queue:
                self._admit_one(free.pop(0), *self._queue.popleft())
        # part of the visible gap of the step this call emits and not that
        # step's own, but for the wait for a step in flight (:meth:`_drain`)
        self._call_admit_s += (time.perf_counter() - self._round_t
                               - (self._phase_s[2] - synced))

    def _bucket_for(self, plen: int) -> int:
        for b in self.serve.prefill_buckets:
            if b >= plen:
                return b
        raise AssertionError("submit() validated bucket coverage")

    def _admit_one(self, slot: int, rid: int, req: Request) -> None:
        t0 = time.perf_counter()
        qspan = self._queued_spans.pop(rid, None)
        if qspan is not None:
            qspan.end(slot=slot)
        self._g_queue.set(len(self._queue))
        # explicit D2H for device-array prompts (no-op for lists/np):
        # transfer-guard-clean under GRAFT_SANITIZE
        prompt = np.asarray(jax.device_get(req.prompt), np.int32).reshape(-1)
        plen = len(prompt)
        bucket = self._bucket_for(plen)
        first = self._first[rid] = _FirstToken(
            submit=self._submit_t[rid], round=self._round_t)
        self._call_admitted += 1
        # prefix match: pure host-side hashing on the admission path (no
        # device work, GL001-clean). A match is used only when it covers at
        # least one full block — shorter overlaps would pay a COW block
        # copy for near-zero prefill savings.
        match: MatchResult | None = None
        matched = 0
        if self._store is not None and plen > 1:
            m = self._store.match(prompt.tolist(), plen - 1)
            if m.full:
                match = self._trim_match(plen, m)
                matched = match.length
        ct = self.serve.chunk_tokens
        chunked = bool(ct) and plen - matched > ct
        if chunked and match is not None and match.partial is not None:
            # chunk starts must stay block-aligned (every chunk boundary
            # is matched + i*chunk_tokens): cut a mid-block COW match back
            # to its full blocks — at chunked-prompt lengths the lost
            # sub-block overlap is noise against the prefill itself
            match = MatchResult(
                len(match.full) * self.serve.kv_block, match.full, None
            )
            matched = match.length
        if self._store is not None and plen > 1:
            self._store.record_prompt(plen, matched)
            self._c_prompt_tokens.inc(plen)
            if matched:
                self._c_prefix_hit.inc(matched)
        self.metrics.record_prompt(plen, matched)
        key = _as_raw_key(req.rng, rid)
        if self.cache.slot_state is not None:
            # the slot's fixed-size state starts from zero, whatever its
            # last tenant left
            self.cache = self.cache._replace(slot_state=_zero_slot_state_fn()(
                self.cache.slot_state, np.int32(slot)))
        if chunked:
            # chunked prefill: plan every prompt block now, then advance
            # one chunk per engine step (docs/SERVE.md "Disaggregated
            # serving" — co-resident decode streams never stall behind a
            # whole-prompt prefill). The slot stays out of the decode
            # batch (state.live False, decode writes scratch-steered)
            # until the final chunk samples the first token.
            with trace.span("serve.prefill", rid=rid, bucket=bucket,
                            slot=slot, matched=matched, chunked=1), \
                    annotate("serve.prefill"):
                self._plan_blocks(slot, plen, match)
            self._slot_rid[slot] = rid
            self._chunking[slot] = _ChunkedPrefill(
                rid=rid, req=req, prompt=prompt, pos=matched, key=key, t0=t0,
            )
            self._prefill_chunk(slot)  # first chunk rides the admission step
            return
        with trace.span("serve.prefill", rid=rid, bucket=bucket, slot=slot,
                        matched=matched), annotate("serve.prefill"):
            self._plan_blocks(slot, plen, match)
            first.dispatch = time.perf_counter()
            if match is None:
                padded = np.zeros((1, bucket), np.int32)
                padded[0, :plen] = prompt
                # ledger attribution: a fresh bucket compile fired inside
                # this call journals under the prefill's name, not
                # anonymously
                with self._ledger.label(f"serve.prefill[{bucket}]"):
                    tok, carry, pk, pv, aux = self._get_prefill(bucket)(
                        self.params, padded, np.int32(plen - 1),
                        np.float32(req.temperature), np.int32(req.top_k),
                        np.float32(req.top_p), key,
                    )
                self._scatter_prompt(slot, pk, pv, 0, plen, aux)
            else:
                tok, carry, aux = self._tail_prefill(slot, prompt, matched, req, key)
            # EXPLICIT sync: the sampled first token steers admission on
            # the host (transfer-guard-clean under GRAFT_SANITIZE)
            tok = int(self._fetch(tok, aux, step=False)[0])
            first.token = time.perf_counter()
        with annotate("serve.activate"):
            self._activate_slot(slot, rid, req, prompt, tok, carry, t0)

    def _prefill_chunk(self, slot: int) -> None:
        """Advance one chunked-prefill slot by ONE chunk (at most
        chunk_tokens tokens through the restartable tail-prefill path).
        Intermediate chunks discard the sampled token (their last_index
        points mid-prompt); the final chunk's sample IS the request's
        first token — same logits, same key as an unchunked prefill, so
        chunking is draw-for-draw invisible in the output."""
        self._drain()
        job = self._chunking[slot]
        plen = len(job.prompt)
        end = min(job.pos + self.serve.chunk_tokens, plen)
        final = 1 if end == plen else 0
        first = self._first[job.rid]
        if not first.dispatch:
            first.dispatch = time.perf_counter()   # the first chunk's
        else:
            self._call_admitted += 1
        with trace.span("serve.prefill_chunk", rid=job.rid, slot=slot,
                        start=job.pos, end=end, final=final), \
                annotate("serve.prefill_chunk"):
            tok, carry, aux = self._tail_prefill(
                slot, job.prompt, job.pos, job.req, job.key, end=end
            )
            if final:
                tok = int(self._fetch(tok, aux, step=False)[0])
                first.token = time.perf_counter()
            elif aux:
                self._fetch((), aux, step=False)
        if not final:
            job.pos = end
            return
        del self._chunking[slot]
        with annotate("serve.activate"):
            self._activate_slot(
                slot, job.rid, job.req, job.prompt, tok, carry, job.t0
            )

    def _fetch(self, out, aux: dict, step: bool = True):
        """A program's one sync, the ONLY place the step loop reads the
        device (``metrics.device_fetches`` counts the calls: one a decode
        step, one a prefill's first token, one a chunk that counted routes),
        and the one reading of a step's ``aux``: ``out`` comes to the host —
        a decode step's sampled tokens and, on a speculative step,
        ``n_emit`` — and with it, in the SAME ``device_get``, the expert
        routes a latent-attention step counted and whether the step's
        sampler took its vocabulary-wide branch. Whatever the host needs of
        a program rides here: a second blocking transfer, with nothing
        queued on the chip, cost ~2 ms a step for the routes and 0.5 ms for
        the ``done`` flags (PERF.md §6, PRs 27 and 32). The flags are not
        fetched at all since steps overlap: they live in the slot state,
        which the NEXT step's dispatch donates before this one is read, so
        the host reads a finish off the tokens (``tok == eos``). ``out`` is
        a result of its own of the program (``nxt`` beside ``state.last_tok``)
        and outlives that donation.
        Returns ``(out on the host, what is left of aux)`` — the health
        monitors, which stay device references for the sentinel's worker
        thread."""
        ride = {k: aux[k] for k in _AUX_FETCHED if k in aux}
        out, ride = jax.device_get((out, ride))
        self.metrics.device_fetches += 1
        if "moe_routes" in ride:
            self.metrics.record_moe(
                ride["moe_routes"], ride["moe_tokens"], step=step)
        if int(ride.get("vocab_sampled", 0)):
            self.metrics.vocab_sampler_steps += 1
            self._c_vocab_sampler.inc()
        return out, {k: v for k, v in aux.items() if k not in ride}

    def _activate_slot(self, slot: int, rid: int, req: Request,
                       prompt: np.ndarray, tok: int, carry, t0: float) -> None:
        """Post-prefill activation: the sampled first token lands, TTFT is
        recorded (``tony_ttft_seconds``: the token on the engine's host; what
        the caller waits for beyond it, this activation and the rest of the
        ``step()`` call, is closed in :meth:`_hand_over_first_tokens`), and
        the slot joins the decode batch."""
        plen = len(prompt)
        self._register_prompt(slot, prompt)
        now = time.perf_counter()
        self.metrics.record_prefill(now - t0, now - self._submit_t[rid])  # popped below
        self._h_ttft.observe(now - self._submit_t[rid])
        self._c_tokens.inc()
        self._first_tok_t[rid] = now
        tracer = trace.active_tracer()
        if tracer is not None:
            # decode-lifetime span: first token -> finish
            self._decode_spans[rid] = tracer.span("serve.decode", rid=rid, slot=slot)

        self._slot_len[slot] = plen
        if self.serve.spec:
            # draft context: prompt + every emitted token (input token
            # last) — what the host-side draft sources extend
            self._slot_ctx[slot] = [int(t) for t in prompt] + [tok]
        eos = -1 if req.eos_id is None else int(req.eos_id)
        # the slot's eight fields in ONE program, the scalars as numpy
        # arguments of that one call (no put, no scatter of its own a field)
        self.state = _activate_fn()(
            self.state, np.int32(slot), np.int32(tok), carry,
            np.float32(req.temperature), np.int32(req.top_k),
            np.float32(req.top_p), np.int32(eos),
        )
        self.metrics.slot_programs += 1
        self._slot_rid[slot] = rid
        self._slot_eos[slot] = eos
        self._slot_remaining[slot] = req.max_new_tokens
        comp = Completion(
            rid=rid, tokens=[tok], prompt_len=plen,
            ttft_s=now - self._submit_t.pop(rid),
        )
        self._completions[rid] = comp
        self._slot_remaining[slot] -= 1
        if tok == eos:
            self._finish(slot, "eos")
        elif self._slot_remaining[slot] <= 0:
            self._finish(slot, "length")
        first = self._first.pop(rid)
        first.active = time.perf_counter()
        self._came_visible.append(first)

    def _finish(self, slot: int, reason: str) -> None:
        rid = self._slot_rid[slot]
        comp = self._completions[rid]
        comp.finish_reason = reason
        if self._store is not None and self.serve.spec:
            # draft corpus: register the GENERATED tokens' full blocks
            # too (prompt blocks landed at admission), so future drafts
            # extend along observed generations — the radix tree caching
            # generated sequences, SGLang-style. The K/V'd sequence is
            # the context minus its last token (sampled, never fed).
            B = self.serve.kv_block
            seq = self._slot_ctx[slot][:self._slot_len[slot]]
            n_full = len(seq) // B
            if n_full:
                self._store.insert(
                    seq[:n_full * B],
                    self._table[slot, :n_full].tolist(), self._pool.retain,
                )
                self._store.evict_to_budget(self._pool.release)
                self._g_prefix_bytes.set(self._store.resident_bytes)
                self._g_prefix_nodes.set(self._store.n_nodes)
        self._slot_ctx[slot] = []
        self.metrics.requests_finished += 1
        self._c_finished.inc()
        t_first = self._first_tok_t.pop(rid, None)
        if t_first is not None and len(comp.tokens) > 1:
            # TPOT: decode-token cadence after the prefill-sampled first
            self._h_tpot.observe(
                (time.perf_counter() - t_first) / (len(comp.tokens) - 1)
            )
        dspan = self._decode_spans.pop(rid, None)
        if dspan is not None:
            dspan.end(tokens=len(comp.tokens), reason=reason)
        self._slot_rid[slot] = None
        self._slot_eos[slot] = -1
        self._slot_remaining[slot] = 0
        self._slot_len[slot] = 0
        ran_on = self._inflight
        if ran_on is not None and not any(
                self._slot_rid[s] == r for s, r in ran_on.rows):
            # every row of the step in flight has finished since its
            # dispatch: nobody is left to read it, and device order keeps
            # whatever follows behind it
            self._inflight = None
        # ``lengths`` alone, not the cache: the pools are no argument of it
        self.state, lengths = _release_fn()(
            self.state, self.cache.lengths, np.int32(slot))
        self.cache = self.cache._replace(lengths=lengths)
        self.metrics.slot_programs += 1
        # a freed slot returns only the blocks whose refcount hits zero —
        # blocks the prefix store (or another slot's table) still
        # references stay resident
        row = self._table[slot]
        for bi in range(self._slot_blocks[slot]):
            self._pool.release(int(row[bi]))
        row[:self._slot_blocks[slot]] = SCRATCH_BLOCK
        self._slot_blocks[slot] = 0
        self._table_dirty = True
        self._maybe_shrink_pool()

    # --- block planning (host side of the paged cache) ------------------------

    @property
    def attended_positions(self) -> int:
        """Positions the decode step currently attends per slot (table
        width x kv_block) — the paged analogue of the old contiguous
        cache's ``capacity``."""
        return self._attended * self.serve.kv_block

    def _alloc_block(self) -> int:
        """One private physical block: free list, else grow the pool
        (doubling, device + host in lockstep), else evict LRU leaves from
        the prefix store until a block frees. The pool cap covers every
        slot at max_len plus the store budget, so the chain terminates."""
        pid = self._pool.alloc()
        while pid is None:
            if self._pool.n_blocks < self._pool_cap:
                new = min(max(2 * self._pool.n_blocks, 4), self._pool_cap)
                self.cache = grow_cache(self.cache, new)
                self._pool.grow(new)
            elif self._store is not None and \
                    self._store.evict_lru(self._pool.release) is not None:
                pass  # evicted; the release may or may not have freed HBM
            else:
                raise RuntimeError(
                    "block pool exhausted (live slots + store exceed the "
                    "pool cap — engine accounting bug)"
                )
            pid = self._pool.alloc()
        if self.cache.quantized:
            # a reused block carries its previous tenant's scale row —
            # queue it for the batched zeroing flush (scale 0 = nothing
            # real stored, so the first write fully defines the scale)
            self._fresh_scale.append(pid)
        return pid

    def _plan_blocks(self, slot: int, plen: int, match: MatchResult | None) -> None:
        """Fill the slot's table row for a prompt: matched full blocks map
        shared (one pool reference each, never written), a mid-block match
        gets a private copy-on-write block, the rest are fresh."""
        B = self.serve.kv_block
        row = self._table[slot]
        nb = blocks_for(plen, B)
        next_bi = 0
        if match is not None:
            for bi, pid in enumerate(match.full):
                self._pool.retain(pid)
                row[bi] = pid
            next_bi = len(match.full)
            if match.partial is not None:
                # COW: the unshared tail writes into this block — hand the
                # slot a private copy of the shared source first
                dst = self._alloc_block()
                if self.cache.quantized:
                    # the copy overwrites dst's scale row with src's — a
                    # later zeroing flush would erase it
                    self._fresh_scale.remove(dst)
                self.cache = _copy_block_fn()(
                    self.cache, np.int32(match.partial), np.int32(dst)
                )
                row[next_bi] = dst
                next_bi += 1
                self._cow_copies += 1
        for bi in range(next_bi, nb):
            row[bi] = self._alloc_block()
        self._slot_blocks[slot] = nb
        self._table_dirty = True

    def _trim_match(self, plen: int, match: MatchResult) -> MatchResult:
        """Drop a mid-block (COW) match when the tail's ladder bucket
        would overrun the cache cap: with the match cut back to its full
        blocks the tail starts block-aligned, so the block-aligned tail
        width always fits — tail-prefill signatures stay multiples of
        kv_block instead of one per match length."""
        B = self.serve.kv_block
        if match.partial is None:
            return match
        tb = self._bucket_for(plen - match.length)
        if match.length + tb <= self._m_total * B:
            return match
        return MatchResult(len(match.full) * B, match.full, None)

    def _flush_fresh_scales(self) -> None:
        """Zero the scale rows of freshly allocated blocks in one batched
        device write (padded to a power-of-two id count with scratch so
        the jitted zeroing keeps a bounded signature set)."""
        if not self._fresh_scale:
            return
        pids = self._fresh_scale
        self._fresh_scale = []
        n = 1
        while n < len(pids):
            n *= 2
        padded = np.full(n, SCRATCH_BLOCK, np.int32)
        padded[:len(pids)] = pids
        self.cache = _zero_scales_fn()(self.cache, padded)

    def _scatter_prompt(self, slot: int, pk, pv, start: int, plen: int,
                        aux: dict) -> None:
        """Write prefilled K/V (``[L, Hkv, W, hd]``, positions ``start +
        i``) into the slot's blocks; padded rows beyond ``plen`` steer to
        the scratch block. Quantized pools quantize the span in the same
        fused step (per-touched-block running-scale update). The handoff of
        the second kind of state rides the same program: what the prefill
        left under ``aux['slot_state']`` (a family that declares one)
        becomes the slot's."""
        handed = aux.get(_AUX_SLOT_STATE)
        if handed is not None:
            self.metrics.state_handoffs += 1
        B = self.serve.kv_block
        row = self._table[slot]
        W = pk.shape[2]
        p = start + np.arange(W)
        valid = p < plen
        pids = np.where(valid, row[np.minimum(p // B, self._m_total - 1)],
                        SCRATCH_BLOCK).astype(np.int32)
        offs = np.where(valid, p % B, 0).astype(np.int32)
        if self.cache.quantized:
            self._flush_fresh_scales()
            # touched-block set at a STATIC width (the span covers at most
            # W//B + 1 blocks, plus scratch) so signatures stay per-bucket
            nU = W // B + 2
            ub = np.full(nU, SCRATCH_BLOCK, np.int32)
            uniq = np.unique(pids)
            ub[:len(uniq)] = uniq
            self.cache = _scatter_fn(self.serve.quant_kv)(
                self.cache, pk, pv, pids, offs, ub, np.int32(slot),
                np.int32(plen),
            )
            return
        self.cache = _scatter_fn()(
            self.cache, pk, pv, pids, offs, np.int32(slot), np.int32(plen),
            handed,
        )

    def _tail_prefill(self, slot: int, prompt: np.ndarray, matched: int,
                      req: Request, key, end: int | None = None):
        """Prefill only the unshared tail: gather the matched prefix K/V
        from the pool (through the slot's own table, COW copy included)
        into a contiguous context, run the tail bucket through the model
        attending it, and scatter the tail K/V back into the slot's
        private blocks. FLOPs scale with the tail, not the prompt.

        ``end`` bounds the prefill to ``prompt[matched:end]`` — the
        chunked-prefill form (one chunk = one call with ``matched`` at the
        previous chunk's end). The restartable-tail contract makes the
        chained chunks bitwise-identical to one full prefill."""
        B = self.serve.kv_block
        plen = end if end is not None else len(prompt)
        tail_len = plen - matched
        cap = self._m_total * B
        tb = self._bucket_for(tail_len)
        if matched + tb > cap:
            # the ladder bucket overruns the cache cap (long match, coarse
            # ladder): fall back to the block-aligned minimum. _trim_match
            # guaranteed `matched` is block-aligned in this case, so
            # matched + ceil(tail/B)*B = ceil(plen/B)*B <= cap always —
            # signatures stay multiples of kv_block, never one per match
            tb = blocks_for(tail_len, B) * B
            assert matched % B == 0 and matched + tb <= cap, (matched, tb)
        # context width: enough for prefix + padded tail, rounded to a
        # power-of-two block count (bounded compile signatures)
        nC = blocks_for(max(plen, matched + tb), B)
        p2 = 1
        while p2 < nC:
            p2 *= 2
        nC = min(p2, self._m_total)
        C = nC * B
        row = self._table[slot]
        n_have = blocks_for(plen, B)
        gather = np.full(nC, SCRATCH_BLOCK, np.int32)
        gather[:min(n_have, nC)] = row[:min(n_have, nC)]
        ctx_k, ctx_v = _gather_fn(self.cache.quantized, self.cfg.dtype)(
            self.cache, gather)
        # the slot's fixed-size state as its predecessor left it (None, an
        # empty argument, for a family without one)
        state = None
        if self.cache.slot_state is not None:
            state = _slot_state_fn()(self.cache.slot_state, np.int32(slot))
        tail = np.zeros((1, tb), np.int32)
        tail[0, :tail_len] = prompt[matched:plen]
        with self._ledger.label(f"serve.prefill_tail[{tb},{C}]"):
            tok, carry, tk, tv, aux = self._get_tail_prefill(
                tb, ctx_k, ctx_v, state)(
                self.params, ctx_k, ctx_v, tail, np.int32(matched),
                np.int32(tail_len - 1), np.float32(req.temperature),
                np.int32(req.top_k), np.float32(req.top_p), key, state,
            )
        self._scatter_prompt(slot, tk, tv, matched, plen, aux)
        return tok, carry, aux

    def _register_prompt(self, slot: int, prompt: np.ndarray) -> None:
        """Insert the prompt's full blocks into the prefix store (each new
        radix node takes its own pool reference), then evict back under
        the HBM budget."""
        if self._store is None:
            return
        B = self.serve.kv_block
        n_full = len(prompt) // B
        if n_full:
            self._store.insert(
                prompt[:n_full * B].tolist(),
                self._table[slot, :n_full].tolist(), self._pool.retain,
            )
            if self._store.evict_to_budget(self._pool.release):
                self._maybe_shrink_pool()
        self._g_prefix_bytes.set(self._store.resident_bytes)
        self._g_prefix_nodes.set(self._store.n_nodes)

    # --- blockwise KV handoff (docs/SERVE.md "Disaggregated serving") ---------

    def export_prefix_blocks(
        self, tokens: Sequence[int]
    ) -> tuple[list[int], BlockPayload] | None:
        """Prefill-host side of the handoff: gather the store-resident
        full blocks covering ``tokens`` to the host as ``(covered_tokens,
        BlockPayload)`` — quantized payload and scale rows travel
        together. Each block is pinned (one extra pool reference) for the
        duration of the gather so LRU eviction cannot hand it away
        mid-export. None when nothing is resident."""
        self._refuse_block_handoff()
        if self._store is None:
            return None
        self._drain()
        B = self.serve.kv_block
        toks = [int(t) for t in tokens]
        n_full = len(toks) // B
        if not n_full:
            return None
        m = self._store.match(toks, n_full * B)
        if not m.full:
            return None
        for pid in m.full:
            self._pool.retain(pid)
        try:
            payload = export_blocks(self.cache, list(m.full))
        finally:
            for pid in m.full:
                self._pool.release(pid)
        self._c_handoff_shipped.inc(len(m.full))
        return toks[:len(m.full) * B], payload

    def adopt_blocks(
        self, tokens: Sequence[int], payload: BlockPayload
    ) -> tuple[int, int]:
        """Decode-host side: adopt shipped blocks into THIS pool through
        the normal refcount rules. Every adopted block is freshly
        allocated (reallocation hands out only refcount-zero ids, so a
        handoff racing a slot-free can never corrupt a reallocated
        block), written payload + scale rows in one device store, and
        registered in the prefix store — which takes the owning
        reference. Blocks whose prefix is already resident are freed
        instead (the temp allocation releases). Every shipped block
        therefore ends adopted or freed — the handoff-no-block-leak
        contract the chaos checker audits. Returns (adopted, freed);
        raises ValueError on an incompatible payload (the gang worker
        maps it to an error response, never a corrupted pool)."""
        self._refuse_block_handoff()
        self._drain()
        B = self.serve.kv_block
        nb = payload.n_blocks
        if len(tokens) != nb * B:
            raise ValueError(
                f"payload covers {nb} block(s) of {B} but {len(tokens)} "
                "tokens were named"
            )
        why = payload_compatible(self.cache, payload)
        if why:
            raise ValueError(f"incompatible handoff payload: {why}")
        toks = [int(t) for t in tokens]
        if self._store is None:
            # no store to own them — nothing adopts, nothing strands
            self._c_handoff_freed.inc(nb)
            return 0, nb
        have = len(self._store.match(toks, nb * B).full)
        new_pids: list[int] = []
        for bi in range(have, nb):
            pid = self._alloc_block()
            if self.cache.quantized:
                # the adopt write lands the shipped scale row verbatim —
                # a queued allocation-time scale zeroing would erase it
                self._fresh_scale.remove(pid)
            self.cache = write_block(self.cache, pid, payload, bi)
            new_pids.append(pid)
        phys = list(self._store.match(toks, nb * B).full)[:have] + new_pids
        created = self._store.insert(toks, phys, self._pool.retain)
        for pid in new_pids:
            self._pool.release(pid)
        if self._store.evict_to_budget(self._pool.release):
            self._maybe_shrink_pool()
        self._g_prefix_bytes.set(self._store.resident_bytes)
        self._g_prefix_nodes.set(self._store.n_nodes)
        self._c_handoff_adopted.inc(created)
        self._c_handoff_freed.inc(nb - created)
        return created, nb - created

    def _refuse_block_handoff(self) -> None:
        _refuse(self._steps, "block_handoff", True)

    def _maybe_shrink_pool(self) -> None:
        """Halve the pool while the trailing half is entirely free — a
        block pinned high (prefix store or a long-lived slot) bounds the
        shrink, exactly the refcount contract shrink_cache documents."""
        if not self.serve.shrink:
            return
        new = self._pool.n_blocks
        target = self._pool.shrink_target(self._p0)
        while new // 2 >= target and new // 2 >= self._p0:
            new //= 2
        if new < self._pool.n_blocks:
            self._drain()
            self.cache = shrink_cache(self.cache, new)
            self._pool.shrink(new)

    def _set_attended(self, need: int) -> None:
        """Size the decode step's table width to the live maximum: grow by
        doubling, shrink when the need halves (the old contiguous-capacity
        policy, now on the indirection table)."""
        cur = self._attended
        if need > cur:
            cur = min(max(need, 2 * cur), self._m_total)
        elif self.serve.shrink and need <= cur // 2:
            cur = max(need, 1)
        if cur != self._attended or self._table_dirty:
            self._attended = cur
            # a private copy: the upload may alias the host array (the CPU
            # backend) or still be on its way when the NEXT step's plan
            # writes ``_table``, while the step dispatched with it runs
            self._table_dev = jnp.asarray(self._table[:, :cur].copy())
            self._table_dirty = False

    # --- jitted steps ---------------------------------------------------------

    def _get_prefill(self, bucket: int):
        if bucket not in self._prefill_fns:
            # AOT-compiled (module-wide cache) so the compile ledger holds
            # the prefill's measured cost_analysis FLOPs — the number the
            # bench/acceptance gate compares against the tail prefill's to
            # prove FLOPs scale with the unshared tail
            self._prefill_fns[bucket] = _aot_prefill(
                self.cfg, bucket, self.serve.max_top_k, self.params,
                self._ledger,
            )
            self.metrics.prefill_compiles = (
                len(self._prefill_fns) + len(self._tail_fns)
            )
        return self._prefill_fns[bucket]

    def _get_tail_prefill(self, tb: int, ctx_k, ctx_v, state):
        ctx = ctx_k.shape[2]
        if (tb, ctx) not in self._tail_fns:
            self._tail_fns[(tb, ctx)] = _aot_tail_prefill(
                self.cfg, tb, ctx_k, ctx_v, state, self.serve.max_top_k,
                self.params, self._ledger,
            )
            self.metrics.prefill_compiles = (
                len(self._prefill_fns) + len(self._tail_fns)
            )
        return self._tail_fns[(tb, ctx)]

    def _get_decode(self, signature: tuple[int, int], draft_k: int = 0):
        """The decode step at ``signature`` = (pool blocks, attended table
        width); with ``draft_k`` the speculative (G = draft_k + 1)-position
        verify step over the same signature space — ONE fixed G per engine
        (``spec_max_draft``), so spec adds at most a bounded mirror of the
        plain ledger, never a per-draft-length family (short drafts pad to G
        with writes steered to the scratch block)."""
        fns = self._spec_fns if draft_k else self._decode_fns
        if signature not in fns:
            # AOT-compiled per (model, kernel, shapes, sharding), shared
            # across engines module-wide (_aot_decode's cache — every
            # pool-size/table-width signature compiles once per process,
            # not once per Engine); the AOT executable is what lets the
            # ledger record the decode step's measured memory plan
            # (memory_analysis: params + temp + per-block KV bytes), which
            # the gqa_capacity slot budget is derived from. The per-engine
            # dicts only count the distinct signatures this engine entered.
            fns[signature] = _aot_decode(
                self.cfg, self.serve.decode_impl, self.serve.kv_block,
                self.serve.max_top_k, self._dec_params, self.cache,
                self._table_dev, self.state, self._ledger,
                monitors=self._monitors, quant_kv=self.serve.quant_kv,
                quant_weights=self.serve.quant_weights, draft_k=draft_k,
            )
            self.metrics.decode_compiles = (
                len(self._decode_fns) + len(self._spec_fns)
            )
        return fns[signature]

    # --- decode loop ----------------------------------------------------------

    def _propose_step_drafts(
        self, live: list[int]
    ) -> tuple[np.ndarray | None, list[int]]:
        """Host-side draft pass (spec on): ask the draft sources for up to
        k tokens per live slot, capped so the emitted count can never
        overrun the slot's token budget (``remaining - 1``: the bonus
        token always emits). Pure python — GL001-clean."""
        k_max = self.serve.spec_max_draft if self.serve.spec else 0
        dlens = [0] * self.serve.slots
        if not k_max:
            return None, dlens
        drafts = np.zeros((self.serve.slots, k_max), np.int32)
        for s in live:
            cap = min(k_max, self._slot_remaining[s] - 1)
            if cap <= 0:
                continue
            prop = propose_drafts(
                self._slot_ctx[s], self._store, cap,
                self.serve.spec_draft_source,
            )
            if prop:
                dlens[s] = len(prop)
                drafts[s, :len(prop)] = prop
        return drafts, dlens

    def _decode_once(self) -> None:
        """One decode step's tokens become visible — the OLDEST unread
        step's. A pipeline of depth one between host and device: where the
        next step's plan needs nothing these tokens decide
        (:meth:`_may_run_ahead`), it is dispatched BEFORE they are read, so
        the device runs step N+1 while the host reads, emits and hands to
        its caller step N. Same programs in the same order on the device,
        the same tokens to the same requests; only the host's wait moves
        under the device's work (docs/SERVE.md "The step loop").

        Device-timeline bridge: the host phases of one decode step as
        profiler annotations (plan / step{dispatch, sync} / emit), so a
        capture names every idle gap; request identity stays in the journal
        spans, the profiler side carries phase names only. In the steady
        state a call is plan(N+1) / step{dispatch(N+1), sync(N)} / emit(N);
        with nothing in flight it is today's plan(N) / step{dispatch(N),
        sync(N)} / emit(N), with N+1's plan and dispatch inside the step,
        before the sync, where the pipeline fills."""
        head, ahead = self._inflight, None
        fresh = head is None        # nothing in flight: today's order
        if fresh:
            head = self._plan(self._next_why)
            self._next_why = "fresh"
        else:
            ahead = self._plan_ahead(head)
        tracer = trace.active_tracer()
        sp = trace.NOOP_SPAN
        if tracer is not None:
            sp = tracer.sampled_span("serve.step", live=len(head.rows))
        with sp, annotate("serve.step"):
            if fresh:
                self._dispatch(head)
                ahead = self._plan_ahead(head)
            if ahead is not None:
                self._dispatch(ahead)
            self._sync(head)
        self._inflight = ahead
        self._emit(head)

    def _plan_ahead(self, head: _InFlight) -> _InFlight | None:
        """The plan of the step after ``head`` where it may be dispatched
        before ``head`` is read; else None, and what kept it is remembered
        for the step that the next call plans with nothing in flight."""
        why = self._may_run_ahead(head)
        if why == "ahead":
            return self._plan("ahead")
        self._next_why = why
        return None

    def _may_run_ahead(self, step: _InFlight) -> str:
        """May the step after ``step`` be dispatched before ``step``'s
        tokens are read? ``'ahead'`` if so, else the FIRST condition below
        that said no (``obs.metrics.KEPT_REASONS``): the name the step after
        ``step`` is counted and marked under. It may only if its plan needs
        nothing those tokens decide:

        - ``'finish'``: no row of ``step`` is on its LAST token by length —
          a finish the host can foresee ends at a boundary with nothing
          queued, so the caller's next request is admitted, and its prefill
          starts, on an idle device exactly as without a pipeline (a queued
          decode step would sit in front of every such first token);
        - ``'admit'``: no request waits for a slot that is already free: the
          next call admits it, and the step after ``step`` is the first it
          decodes in;
        - ``'spec'``: the engine does not speculate: drafts are proposed on
          the host from the tokens just read;
        - ``'chunk'``: no prompt is prefilling in chunks: a chunk, and the
          activation after the last one, run between steps on a drained
          device.

        Decided from what the engine observes at each step; no knob. An eos
        cannot be foreseen: it is found one device step late (:meth:`_emit`),
        and the request admitted into its slot — like one that arrives from
        outside between two calls — starts behind the step in flight and
        decodes from the step after it."""
        if any(n <= 0 for n in step.left):
            return "finish"
        if self._queue and self.n_live < self.serve.slots:
            return "admit"
        if self.serve.spec:
            return "spec"
        if self._chunking:
            return "chunk"
        return "ahead"

    def _plan(self, why: str) -> _InFlight:
        """Per-step block planning: a live row allocates blocks NOW to
        cover every position this step may write (host-side, before
        dispatch) — position pos autoregressively, pos..pos+draft_len
        speculatively; the attended table width tracks the live maximum.
        Reads the host bookkeeping as the step before it left it at ITS
        dispatch, so it is right whether or not that step has been read.
        ``why``: the reason the step will be counted under."""
        t0 = time.perf_counter()
        with annotate("serve.plan"):
            B = self.serve.kv_block
            rows = [
                (s, r) for s, r in enumerate(self._slot_rid)
                if r is not None and s not in self._chunking
            ]
            live = [s for s, _ in rows]
            drafts_np, dlens = self._propose_step_drafts(live)
            spec_step = any(dlens)
            need = 1
            for s in live:
                last = self._slot_len[s] + (dlens[s] if spec_step else 0)
                while self._slot_blocks[s] * B <= last:
                    self._table[s, self._slot_blocks[s]] = self._alloc_block()
                    self._slot_blocks[s] += 1
                    self._table_dirty = True
                need = max(need, last // B + 1)
            if self.cache.quantized:
                self._flush_fresh_scales()
            self._set_attended(need)
            step = _InFlight(
                rows=rows, drafts=drafts_np if spec_step else None,
                dlens=dlens, why=why,
            )
        self._phase_s[0] += time.perf_counter() - t0
        return step

    def _dispatch(self, step: _InFlight) -> None:
        """Launch ``step``'s program and advance what PLANNING reads —
        ``_slot_len``, ``_slot_remaining`` — by what the step is known to
        do: one position a live row (a speculative step's further emitted
        tokens are added at emit; nothing is planned between). What CALLERS
        see advances at emit."""
        step.t0 = time.perf_counter()
        with annotate("serve.dispatch"):
            sig = (self.cache.n_blocks, self._attended)
            if step.drafts is not None:
                self.cache, self.state, toks, n_emit, step.aux = \
                    self._get_decode(sig, self.serve.spec_max_draft)(
                        self._dec_params, self.cache, self._table_dev,
                        self.state, step.drafts,
                        np.asarray(step.dlens, np.int32),
                    )
            else:
                # no live slot drafted: the plain 1-wide step (also the
                # only step compiled with spec off — same signatures as
                # the pre-spec engine)
                n_emit = None
                self.cache, self.state, toks, step.aux = \
                    self._get_decode(sig)(
                        self._dec_params, self.cache, self._table_dev,
                        self.state,
                    )
            step.out = (toks, n_emit)
        self._phase_s[1] += time.perf_counter() - step.t0
        for s, _ in step.rows:
            self._slot_len[s] += 1
            self._slot_remaining[s] -= 1
            step.left.append(self._slot_remaining[s])

    def _sync(self, step: _InFlight) -> None:
        """EXPLICIT per-step sync: continuous batching needs the sampled
        tokens on host to steer admission — the engine's one designed sync
        point per decode step, and its one transfer (a speculative step's
        emit counts and the expert routes ride the tokens' fetch). A step's
        wall time runs from the later of its own dispatch and the previous
        step's fetch returning, to its own fetch returning: ``decode_s``
        stays the wall time spent on decode steps and counts no millisecond
        twice when two steps overlap. Does nothing for a step already
        brought to the host (:meth:`_drain`)."""
        if step.out is None:
            return
        t0 = time.perf_counter()
        with annotate("serve.sync"):
            step.host, step.aux = self._fetch(step.out, step.aux)
        step.out = None
        now = time.perf_counter()
        self._phase_s[2] += now - t0
        step.dt = now - max(step.t0, self._synced_t)
        self._synced_t = now

    def _drain(self) -> None:
        """Wait for the step in flight and bring its results to the host
        now (they stay on the record; the next ``step()`` emits them). What
        rewrites slots or pools between steps on its own — a prefill chunk,
        the gang's block hand-off, a pool shrink — calls this first and then
        runs on a drained device, as it did before steps overlapped."""
        if self._inflight is not None:
            self._sync(self._inflight)

    def _emit(self, step: _InFlight) -> None:
        """What CALLERS see advances here: tokens to completions, finish
        reasons, freed slots, metrics. A row whose request finished since
        the dispatch — an eos found in the step before, which was read only
        after this one went out — no longer owns its slot, also when a new
        request was admitted into it in between: the row ran one more step
        on the device (it re-emitted its eos into a position whose block
        the plan allocated; ``done`` rows are harmless by construction) and
        its tokens of this step are dropped. Last, the step's account
        (:meth:`_account`)."""
        t0 = time.perf_counter()
        with annotate("serve.emit"):
            toks_np, emit_np = step.host
            owned = [(s, n) for (s, r), n in zip(step.rows, step.left)
                     if self._slot_rid[s] == r]
            live = [s for s, _ in owned]
            spec_step = emit_np is not None
            if spec_step:
                new_total = int(sum(int(emit_np[s]) for s in live))
                prop_total = sum(step.dlens[s] for s in live)
                acc_total = sum(max(int(emit_np[s]) - 1, 0) for s in live)
                self.metrics.record_spec(prop_total, acc_total)
                self._c_draft_prop.inc(prop_total)
                self._c_draft_acc.inc(acc_total)
            else:
                new_total = len(live)
            self.metrics.record_decode(
                step.dt, new_total, len(live), self.serve.slots,
                why=step.why,
            )
            if step.why == "ahead":
                self._c_steps_ahead.inc()
            hbm.sample()  # stride-counted device-memory reading (no sync)
            hmon = step.aux
            if hmon:
                # stride-counted health sample: DEVICE references + the host
                # slot->request map for per-request trip attribution; the
                # device_get sync happens on the sentinel's worker thread
                slot_rids = list(self._slot_rid)
                health.sample(
                    metrics=hmon, slot_rids=slot_rids, live_slots=live
                )
            series.sample()  # stride-counted scrape of the attached sources
            self._h_step.observe(step.dt)
            self._c_tokens.inc(new_total)
            for s, left in owned:
                if spec_step:
                    n = int(emit_np[s])
                    new_toks = [int(t) for t in toks_np[s, :n]]
                else:
                    n = 1
                    new_toks = [int(toks_np[s])]
                # the dispatch counted one position; a speculative step's rest
                self._slot_len[s] += n - 1
                self._slot_remaining[s] -= n - 1
                self._completions[self._slot_rid[s]].tokens.extend(new_toks)
                if self.serve.spec:
                    self._slot_ctx[s].extend(new_toks)
                # emission stops AT an eos (serve/spec.py), so it is the last
                if new_toks[-1] == self._slot_eos[s]:
                    self._finish(s, "eos")
                elif left - (n - 1) <= 0:
                    # by what THIS step left owed: ``_slot_remaining`` may
                    # already count the step dispatched after it
                    self._finish(s, "length")
        self._account(step, len(live), t0)

    def _account(self, step: _InFlight, live: int, t_emit: float) -> None:
        """The emitted step's account (``t_emit``: when its emit began): its
        visible gap is counted under why it ran as it did; one far beyond its
        kind's is logged once, with the host phase (plan / dispatch / sync /
        emit since the emit before it) that held most of it; and, under a
        profiler session, ONE marker named by that reason carries the step's
        ordinal, its gap and the same call's admissions onto the capture's
        clock."""
        now = time.perf_counter()
        gap_s, admit_s = now - (self._emit_t or self._call_t), self._call_admit_s
        plan_s, dispatch_s, sync_s = self._phase_s
        self._phase_s = [0.0, 0.0, 0.0]
        self._emit_t = now
        n = self.metrics.decode_steps - 1   # the step's ordinal since reset_metrics()
        if self.metrics.record_step(step.why, gap_s, admit_s):
            self._c_stalled.inc()
            phases = {"plan": plan_s, "dispatch": dispatch_s, "sync": sync_s,
                      "emit": now - t_emit}
            # what they and the admissions leave of the gap: the caller,
            # between two calls
            phases["outside the step"] = gap_s - admit_s - sum(phases.values())
            worst = max(phases, key=phases.get)
            log.warning(
                "decode step %d stalled (%s, %d live, %d admitted): largest phase: %s, "
                "%.1f ms of a %.1f ms gap; plan %.1f, dispatch %.1f, sync %.1f, emit %.1f, "
                "admissions %.1f ms", n, step.why, live, self._call_admitted, worst,
                1e3 * phases[worst], 1e3 * gap_s, 1e3 * plan_s, 1e3 * dispatch_s,
                1e3 * sync_s, 1e3 * phases["emit"], 1e3 * admit_s)
        with annotate(_STEP_MARKER[step.why], n=n, admitted=self._call_admitted,
                      admit_us=int(admit_s * 1e6), gap_us=int(gap_s * 1e6)):
            pass

def steps_for(cfg):
    """The model family, chosen ONCE by the configuration's class: the
    module whose ``prefill_step`` / ``tail_prefill_step`` / ``decode_step``
    the program builders below run and whose ``REFUSED_KNOBS`` the engine
    enforces (docs/SERVE.md "Model families" has the contract). The
    programs keep ONE set of names (``jit_serve_prefill`` ...) whichever
    family's bodies they run; what a token's cache row is comes from the
    configuration too (``cfg.cache_layout``).

    Spelled as plain ``return <module>`` statements for graft-lint: its
    call graph follows a name bound to this function's result into every
    module it can return (analysis/callgraph.py), which is what keeps every
    family's steps inside the GL001 gate (tests/test_lint.py)."""
    if isinstance(cfg, LatentMoEConfig):
        return latent_steps
    if isinstance(cfg, ShortConvMoEConfig):
        return shortconv_steps
    if isinstance(cfg, SSMHybridConfig):
        return ssm_hybrid_steps
    if isinstance(cfg, LlamaConfig):
        if cfg.is_moe:
            # forward_with_cache (the prefill path) has no expert FFN —
            # reject loudly instead of crashing at the first admission
            raise NotImplementedError(
                "serving MoE configs is not supported yet (prefill has no "
                "expert dispatch)"
            )
        return dense_steps
    raise TypeError(f"no serving steps for a {type(cfg).__name__}")


def _refuse(steps, knob: str, value) -> None:
    """Raise, by the knob's name, where the family's ``REFUSED_KNOBS``
    takes another value than ``value``."""
    takes, why = steps.REFUSED_KNOBS.get(knob, (value, ""))
    if value != takes:
        raise NotImplementedError(
            f"{knob}={value!r} is not supported by {steps.__name__}: {why}"
        )


# the entries of a step's ``aux`` that Engine._fetch brings to the host with
# the sampled tokens; whatever else rides there is a health monitor, but for
# the per-slot state a prefill hands to the scatter (it stays on the device)
_AUX_FETCHED = ("moe_routes", "moe_tokens", "vocab_sampled")
_AUX_SLOT_STATE = "slot_state"


@functools.lru_cache(maxsize=512)
def _prefill_fn(cfg: LlamaConfig, bucket: int, max_top_k: int):
    """Jitted bucketed prefill, cached per (model config, bucket): engines
    with the same model share prefill compiles process-wide. A named
    function, not a ``partial``: jit names the program after it, and a
    device trace then reads ``jit_serve_prefill`` where a partial gives
    ``jit__unknown`` (the same holds for every ``serve_*`` below)."""
    steps = steps_for(cfg)

    def serve_prefill(params, prompt, last_index, temp, top_k, top_p, key):
        return steps.prefill_step(
            params, prompt, last_index, temp, top_k, top_p, key,
            cfg=cfg, bucket=bucket, max_top_k=max_top_k,
        )

    return jax.jit(serve_prefill)


@functools.lru_cache(maxsize=512)
def _tail_fn(cfg: LlamaConfig, tb: int, max_top_k: int):
    """Jitted tail prefill (prefix-matched admissions), cached per (model
    config, tail bucket); jit itself caches per context width."""
    steps = steps_for(cfg)

    def serve_tail_prefill(params, ctx_k, ctx_v, tail, start, last_index,
                           temp, top_k, top_p, key, slot_state=None):
        # ``slot_state``: None for a family that declares none, and then
        # neither an argument of the program nor of its step
        state = {} if slot_state is None else {"slot_state": slot_state}
        return steps.tail_prefill_step(
            params, ctx_k, ctx_v, tail, start, last_index, temp, top_k,
            top_p, key, cfg=cfg, tb=tb, max_top_k=max_top_k, **state,
        )

    return jax.jit(serve_tail_prefill)


@functools.lru_cache(maxsize=512)
def _decode_fn(cfg: LlamaConfig, decode_impl: str, kv_block: int,
               max_top_k: int, monitors: bool = False, quant_kv: str = "",
               quant_weights: bool = False, draft_k: int = 0):
    """Jitted decode step, cached per (model config, kernel knobs) — NOT
    per pool-size/table-width: jit itself caches per argument shape, so
    all engines with the same model reuse every compiled signature. With
    ``draft_k`` it is the speculative verify step ``jit_serve_spec_decode``
    (two more arguments, ``n_emit`` among its results); both run the
    family's one ``decode_step``. What the host needs of a step — the
    tokens, ``n_emit``, the routes ``aux`` counts, the sampler's branch —
    it reads in the ONE ``device_get`` of ``Engine._fetch``, possibly
    AFTER the next step was dispatched: only results of their own, never a
    field of the new state (donated by then; ``done`` stays on the device,
    the host derives a finish from the tokens).

    Contract: the cache (arg 1) and the slot state (arg 3) are DONATED and
    the pools come back as the same buffers — carried through the layer
    scan as ``[L * P, ...]`` and written in place, ``S x Hkv x hd`` values
    per layer per pool (``serve/cache.scan_layers_paged``,
    ``scatter_block_kv``); the compiled step's temporaries do
    not grow with the pool (tests/test_perf_guard.py) and on the chip it
    holds no pool- or slab-shaped copy (PERF.md §5), and no layer's query
    (or dense key) weight sliced out of its ``[L, ...]`` stack and relaid:
    the product reads it where it lies, as every other weight product of
    the step does (``test_projection_weights_are_read_in_place`` in
    tests/test_tpu_compile.py; what is left, ``wkv_b``, is pinned there).
    A dead slot's write lands in the scratch block of the layer being
    written (block ``l * P`` of the flat view). The block table (arg 2) is
    NOT donated — it is reused across steps — and names blocks of ONE
    layer; the step adds the layer's offset itself."""
    steps = steps_for(cfg)
    # a knob the family refuses is held at its off value by Engine.__init__
    # and never reaches the step
    knobs = {k: v for k, v in dict(
        decode_impl=decode_impl, quant_kv=quant_kv,
        quant_weights=quant_weights).items() if k not in steps.REFUSED_KNOBS}
    knobs.update(cfg=cfg, kv_block=kv_block, max_top_k=max_top_k,
                 monitors=monitors)

    def counted(out, temp):
        # which branch the step's sampler took (generate.samples_any over
        # the slots' temperatures), for Engine._fetch to count
        *rest, aux = out
        return (*rest, {**aux, "vocab_sampled": samples_any(temp).astype(jnp.int32)})

    def serve_decode(params, cache, table, state):
        return counted(steps.decode_step(params, cache, table, state, **knobs),
                       state.temp)

    def serve_spec_decode(params, cache, table, state, drafts, draft_len):
        return counted(steps.decode_step(
            params, cache, table, state, drafts, draft_len, draft_k=draft_k,
            **knobs), state.temp)

    if draft_k:
        return jax.jit(serve_spec_decode, donate_argnums=(1, 3))
    return jax.jit(serve_decode, donate_argnums=(1, 3))


# AOT executables shared module-wide: keyed by model/kernel knobs + the
# cache/state shapes + the params' sharding, so engines with the same
# model reuse every compiled signature (the lru_cache-on-jit property the
# lazy path had), while the AOT form exposes memory_analysis()/
# cost_analysis() to the compile ledger and serve/capacity.py
_aot_decode_cache: dict = {}
_aot_prefill_cache: dict = {}


def _aot_compile(fn, avals, key, name, ledger, cache=_aot_prefill_cache):
    """Shared AOT-with-ledger path: lower from ``avals`` (live arrays or
    ShapeDtypeStructs) and journal the measured cost/memory plan under
    ``name``. A step the compiler refuses raises here."""
    hit = cache.get(key)
    if hit is not None:
        return hit
    t0 = time.perf_counter()
    with ledger.label(name):
        compiled = fn.lower(*avals).compile()
    ledger.record_aot(name, compiled, time.perf_counter() - t0)
    if len(cache) < 512:
        cache[key] = compiled
    return compiled


def _aot_decode(cfg: LlamaConfig, decode_impl: str, kv_block: int,
                max_top_k: int, params, cache, table, state, ledger, *,
                monitors: bool = False, quant_kv: str = "",
                quant_weights: bool = False, draft_k: int = 0):
    fn = _decode_fn(cfg, decode_impl, kv_block, max_top_k, monitors,
                    quant_kv, quant_weights, draft_k)
    try:
        shard = jax.tree.leaves(params)[0].sharding
        key = (cfg, decode_impl, kv_block, max_top_k, draft_k, monitors,
               quant_kv, quant_weights,
               cache.k.shape, str(cache.k.dtype), table.shape,
               hash(shard), shard)
    except (AttributeError, TypeError):
        # params without a hashable sharding (plain numpy arrays): lazy jit
        # still works and still shares compiles process-wide
        return fn
    S = state.last_tok.shape[0]
    shape = f"slots={S},blocks={cache.k.shape[1]},attended={table.shape[1]}"
    avals = (params, cache, table, state)
    name = f"serve.decode[{shape}]"
    if draft_k:
        avals += (_sds((S, draft_k), jnp.int32), _sds((S,), jnp.int32))
        name = f"serve.decode_spec[{shape},k={draft_k}]"
    return _aot_compile(
        fn, avals, key, name, ledger, cache=_aot_decode_cache,
    )


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _aot_prefill(cfg: LlamaConfig, bucket: int, max_top_k: int, params,
                 ledger):
    """Full-prompt prefill, AOT so the ledger records its cost_analysis
    FLOPs (the full-prompt baseline the prefix store's tail-FLOPs are
    judged against)."""
    fn = _prefill_fn(cfg, bucket, max_top_k)
    try:
        shard = jax.tree.leaves(params)[0].sharding
        key = ("prefill", cfg, bucket, max_top_k, hash(shard), shard)
    except (AttributeError, TypeError):  # params are not jax arrays
        return fn
    # live params (their real shardings bake into the executable — a
    # sharded-params engine must not compile against default layouts),
    # avals for the per-call scalars
    avals = (
        params, _sds((1, bucket), jnp.int32), _sds((), jnp.int32),
        _sds((), jnp.float32), _sds((), jnp.int32), _sds((), jnp.float32),
        _sds((2,), jnp.uint32),
    )
    return _aot_compile(fn, avals, key, f"serve.prefill[{bucket}]", ledger)


def _aot_tail_prefill(cfg: LlamaConfig, tb: int, ctx_k, ctx_v, slot_state,
                      max_top_k: int, params, ledger):
    """``ctx_k`` / ``ctx_v``: the gathered context the program will attend
    (``ctx_v`` None where the cache is one pool); ``slot_state``: the
    slot's fixed-size state (None where the family declares none); their
    shapes are the program's."""
    fn = _tail_fn(cfg, tb, max_top_k)
    ctx = ctx_k.shape[2]
    try:
        shard = jax.tree.leaves(params)[0].sharding
        key = ("tail", cfg, tb, ctx, max_top_k, hash(shard), shard)
    except (AttributeError, TypeError):  # params are not jax arrays
        return fn
    avals = (
        params, ctx_k, ctx_v, _sds((1, tb), jnp.int32),
        _sds((), jnp.int32),
        _sds((), jnp.int32), _sds((), jnp.float32), _sds((), jnp.int32),
        _sds((), jnp.float32), _sds((2,), jnp.uint32), slot_state,
    )
    return _aot_compile(
        fn, avals, key, f"serve.prefill_tail[{tb},{ctx}]", ledger
    )


@functools.lru_cache(maxsize=4)
def _scatter_fn(quant_kv: str = ""):
    """Jitted position-wise KV scatter into the (DONATED) pool: position
    ``i`` of the prefilled span lands in physical block ``pids[i]`` at
    offset ``offs[i]``; masked rows steer to the scratch block. Meant as
    one in-place scatter instead of two whole-cache copies per admission
    — UNPROVEN on the chip: the serve cell's trace shows this program
    copying the pool four times and transposing it twice per admission
    (13.7 ms each, PERF.md §5): its index dims sit on non-adjacent axes,
    the form ``scatter_block_kv`` avoids.
    The quantized form additionally takes the touched-block set ``ub``
    and runs the per-block running-scale update + requantization
    (serve/cache.py quant_scatter_span, vmapped over layers)."""
    if quant_kv:
        _, qmax = kv_quant_spec(quant_kv)
        span = jax.vmap(
            partial(quant_scatter_span, qmax=qmax),
            in_axes=(0, 0, 0, None, None, None),
        )

        def serve_scatter(cache: PagedKVCache, pk, pv, pids, offs, ub, slot,
                          plen):
            k, ksc = span(cache.k, cache.k_scale, pk, pids, offs, ub)
            v, vsc = span(cache.v, cache.v_scale, pv, pids, offs, ub)
            lengths = lax.dynamic_update_slice(
                cache.lengths, plen[None], (slot,)
            )
            return PagedKVCache(k, v, lengths, ksc, vsc)

        return jax.jit(serve_scatter, donate_argnums=(0,))

    def serve_scatter(cache: PagedKVCache, pk, pv, pids, offs, slot, plen,
                      slot_state=None):
        # rows [L, Hkv, W, hd]; advanced indices (pids axis 1, offs axis
        # 3) are non-adjacent, so the indexed result moves to the front:
        # [W, L, Hkv, hd] — match it by transposing the span
        k, v = map_pools(
            lambda pool, rows: pool.at[:, pids, :, offs, :].set(
                rows.transpose(2, 0, 1, 3)),
            cache, (pk, pv),
        )
        lengths = lax.dynamic_update_slice(cache.lengths, plen[None], (slot,))
        # the handoff of the second kind of state: ``slot_state [layers,
        # *shape]`` becomes slot ``slot``'s row (None — no argument and no
        # write — for a family that declares none)
        state = cache.slot_state
        if slot_state is not None:
            state = lax.dynamic_update_slice_in_dim(
                state, slot_state[:, None].astype(state.dtype), slot, axis=1)
        return PagedKVCache(k, v, lengths, slot_state=state)

    return jax.jit(serve_scatter, donate_argnums=(0,))


@functools.lru_cache(maxsize=1)
def _seed_key_fn():
    """Jitted raw key ``uint32[2]`` of an integer seed."""
    def serve_seed_key(seed):
        return jax.random.key_data(jax.random.key(seed)).astype(jnp.uint32)

    return jax.jit(serve_seed_key)


@functools.lru_cache(maxsize=1)
def _activate_fn():
    """Jitted hand-over of a slot to a request (``state`` DONATED): the
    eight fields of :class:`_SlotState` for ONE slot in one program, where
    eight eager ``.at[slot].set`` were eight dispatches with the device
    idle between a prefill's token and the next decode step (PERF.md §6,
    PR 32). ``carry`` is the prefill's key carry; the other values are the
    request's, given as numpy scalars in the call itself."""
    def serve_activate(state, slot, tok, carry, temp, top_k, top_p, eos):
        return _SlotState(
            last_tok=state.last_tok.at[slot].set(tok),
            rng=state.rng.at[slot].set(carry),
            temp=state.temp.at[slot].set(temp),
            top_k=state.top_k.at[slot].set(top_k),
            top_p=state.top_p.at[slot].set(top_p),
            eos=state.eos.at[slot].set(eos),
            done=state.done.at[slot].set(False),
            live=state.live.at[slot].set(True),
        )

    return jax.jit(serve_activate, donate_argnums=(0,))


@functools.lru_cache(maxsize=1)
def _release_fn():
    """Jitted return of a finished request's slot (``state`` and the
    cache's ``lengths`` DONATED): ``live`` and ``done`` off, so the decode
    step steers the slot's writes to the scratch block, its length 0, and
    its temperature 0, so a free slot never switches the sampler's
    vocabulary-wide branch on (``serve_activate`` sets it again).
    It takes ``lengths`` alone — the pools stay arguments of the model's
    programs and of the scatter only."""
    def serve_release(state, lengths, slot):
        state = state._replace(
            live=state.live.at[slot].set(False),
            done=state.done.at[slot].set(False),
            temp=state.temp.at[slot].set(0.0),
        )
        return state, lengths.at[slot].set(0)

    return jax.jit(serve_release, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=1)
def _zero_slot_state_fn():
    """Jitted reset of one slot's fixed-size state (DONATED): what the
    engine runs at admission for a family that declares one."""
    def serve_zero_slot_state(state, slot):
        return lax.dynamic_update_slice_in_dim(
            state, jnp.zeros_like(state[:, :1]), slot, axis=1)

    return jax.jit(serve_zero_slot_state, donate_argnums=(0,))


@functools.lru_cache(maxsize=1)
def _slot_state_fn():
    """Jitted read of one slot's fixed-size state ``[layers, *shape]`` for
    the tail prefill that continues from it."""
    def serve_slot_state(state, slot):
        return lax.dynamic_index_in_dim(state, slot, axis=1, keepdims=False)

    return jax.jit(serve_slot_state)


@functools.lru_cache(maxsize=1)
def _copy_block_fn():
    """Jitted copy-on-write block copy (DONATED pool): duplicate one
    physical block (all layers, every pool) so a slot about to write into
    a shared block writes into its private copy instead. A quantized pool
    copies the block's scale rows with it — the COW copy dequantizes to
    exactly what the shared source did."""
    def serve_copy_block(cache: PagedKVCache, src, dst):
        def copy(pool):
            blk = lax.dynamic_slice_in_dim(pool, src, 1, axis=1)
            return lax.dynamic_update_slice_in_dim(pool, blk, dst, axis=1)

        return map_cache(copy, cache)

    return jax.jit(serve_copy_block, donate_argnums=(0,))


@functools.lru_cache(maxsize=1)
def _zero_scales_fn():
    """Jitted batched scale-row reset (DONATED cache): freshly allocated
    blocks' K and V scale rows go to zero across all layers — the
    nothing-real-stored marker the first quantized write keys off."""
    def serve_zero_scales(cache: PagedKVCache, pids):
        k_scale, v_scale = map_scales(
            lambda sc: sc.at[:, pids, :].set(0.0), cache)
        return cache._replace(k_scale=k_scale, v_scale=v_scale)

    return jax.jit(serve_zero_scales, donate_argnums=(0,))


@functools.lru_cache(maxsize=4)
def _gather_fn(quant: bool = False, out_dtype=None):
    """Jitted prefix gather: pool blocks ``pids`` -> one contiguous
    ``[L, 1, C, Hkv, hd]`` context cache a pool for the tail prefill
    (read-only: the pool is NOT donated — the slot keeps serving from it).
    Quantized pools dequantize through the gathered blocks' scale rows into
    ``out_dtype`` — the tail prefill attends real-valued context."""
    def serve_gather(cache: PagedKVCache, pids):
        def one(pool, scale=None):
            g = jnp.take(pool, pids, axis=1)           # [L, nC, Hkv, blk, hd]
            if quant:
                sc = jnp.take(scale, pids, axis=1)     # [L, nC, Hkv]
                g = dequantize_values(g, sc[..., None, None], out_dtype)
            L, nC, Hkv, blk, hd = g.shape
            return g.transpose(0, 1, 3, 2, 4).reshape(
                L, nC * blk, Hkv, hd
            )[:, None]                                 # [L, 1, C, Hkv, hd]
        scales = ((cache.k_scale, cache.v_scale),) if quant else ()
        return map_pools(one, cache, *scales)

    return jax.jit(serve_gather)


__all__ = [
    "AdmissionRejected", "Completion", "Engine", "Request", "ServeConfig",
    "steps_for",
]
