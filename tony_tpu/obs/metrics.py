"""Throughput / MFU accounting.

The reference's TaskMonitor samples cpu/mem + nvidia-smi GPU utilisation
(SURVEY.md section 2 "TaskMonitor"); on TPU the meaningful utilisation number
is MFU -- achieved model FLOP/s over the chip's peak -- which is also the
north-star metric (BASELINE.md: >= 45% MFU target).
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Any

import jax
import numpy as np

# Peak dense bf16 FLOP/s per chip, keyed by the ``device_kind`` the installed
# libtpu reports for each generation (public spec-sheet numbers; v5e: Google
# Cloud documentation "TPU v5e", 197 TFLOP/s bf16). A kind that is not here
# is an error, not a default.
PEAK_BF16_FLOPS: dict[str, float] = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5": 459e12,        # v5p
    "TPU v6 lite": 918e12,   # v6e
}
# NOMINAL figure for the CPU backend only — it keeps MFU finite in CPU tests
# and is no device's peak; an MFU computed against it is not a measurement.
NOMINAL_CPU_FLOPS = 1e12


def chip_peak_flops(device: jax.Device | None = None) -> float:
    d = device or jax.devices()[0]
    if d.platform == "cpu":
        return NOMINAL_CPU_FLOPS
    try:
        return PEAK_BF16_FLOPS[d.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s known for device kind {d.device_kind!r} "
            f"(platform {d.platform!r}); add it to PEAK_BF16_FLOPS with its "
            "source rather than reporting an MFU against a guess"
        ) from None


def device_identity() -> dict:
    """What this process actually came up on — platform, device kind and
    device count as JAX reports them. fit() and the serve gang host log it
    once at start and push it with their first metrics sample, so a job
    that fell to the CPU is visible in the history, not only slow."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def device_samples(identity: dict | None = None) -> dict[str, float]:
    """:func:`device_identity` as ONE numeric metrics sample (the metrics RPC
    carries name -> float): ``device/<platform>/<device_kind>`` = count."""
    i = identity or device_identity()
    return {
        f"device/{i['platform']}/{i['device_kind']}": float(i["device_count"])
    }


def parse_device_samples(samples: dict) -> dict | None:
    """Inverse of :func:`device_samples` over a METRICS event's samples."""
    for name, value in samples.items():
        if name.startswith("device/"):
            _, platform, kind = name.split("/", 2)
            return {
                "platform": platform, "device_kind": kind,
                "device_count": int(value),
            }
    return None


@dataclass
class StepTimer:
    """Accumulates steps and wall time to report tokens/sec and MFU."""

    flops_per_token: float
    tokens_per_step: int
    n_chips: int = 1
    elapsed_s: float = 0.0
    steps: int = 0
    # wall time the host spent blocked producing/placing input batches
    # (time inside next(batches)); the device is idle for that span unless
    # the data layer prefetches (train/prefetch.py)
    host_blocked_s: float = 0.0

    def record(self, dt_s: float, n_steps: int = 1, host_blocked_s: float = 0.0) -> None:
        self.elapsed_s += dt_s
        self.steps += n_steps
        self.host_blocked_s += host_blocked_s

    @property
    def host_blocked_ms_per_step(self) -> float:
        if self.steps == 0:
            return 0.0
        return self.host_blocked_s / self.steps * 1e3

    @property
    def host_blocked_frac(self) -> float:
        """Fraction of wall time spent input-blocked (0 = stall-free loop)."""
        if self.elapsed_s == 0:
            return 0.0
        return self.host_blocked_s / self.elapsed_s

    @property
    def tokens_per_sec(self) -> float:
        if self.elapsed_s == 0:
            return 0.0
        return self.steps * self.tokens_per_step / self.elapsed_s

    @property
    def tokens_per_sec_per_chip(self) -> float:
        return self.tokens_per_sec / self.n_chips

    def mfu(self, peak_flops_per_chip: float | None = None) -> float:
        peak = peak_flops_per_chip or chip_peak_flops()
        return self.tokens_per_sec_per_chip * self.flops_per_token / peak


# why a decode step ran as it did (Engine._may_run_ahead): dispatched ahead of
# the read before it; kept by the first condition that said no; or dispatched
# with nothing in flight and no verdict before it
KEPT_REASONS = ("finish", "admit", "spec", "chunk")
STEP_REASONS = ("ahead", *KEPT_REASONS, "fresh")
STALL_FACTOR = 4.0    # a gap beyond this many running medians of its kind is a stall
MEDIAN_WINDOW = 32    # gaps of one reason the running median is taken over
MEDIAN_MIN = 8        # ... and how many it needs before it calls a stall


@dataclass
class DecodeMetrics:
    """Serving-side counters fed by the decode engine (serve/engine.py).

    The serving counterpart of StepTimer: decode tokens/s/chip is the
    throughput headline, time-to-first-token the latency one, and slot
    occupancy the continuous-batching health signal (a well-fed engine
    keeps it near 1.0; a draining or admission-starved one decays toward
    1/slots)."""

    n_chips: int = 1
    generated_tokens: int = 0      # sampled tokens (prefill firsts + decode)
    decode_s: float = 0.0          # wall time inside decode steps
    prefill_s: float = 0.0         # wall time inside prefill calls
    decode_steps: int = 0
    occupancy_sum: float = 0.0     # sum over decode steps of live/slots
    ttft_sum_s: float = 0.0        # submit -> first token, summed
    ttft_max_s: float = 0.0
    requests_started: int = 0
    requests_finished: int = 0
    prefill_compiles: int = 0      # distinct prefill buckets compiled
    decode_compiles: int = 0       # distinct pool/table signatures compiled
    prompt_tokens: int = 0         # prompt tokens admitted
    prefix_hit_tokens: int = 0     # prompt tokens served from the prefix
    #                                store (no re-prefill; serve/prefix.py)
    decode_tokens: int = 0         # tokens emitted by decode steps only
    decode_live_sum: int = 0       # sum over decode steps of live slots
    draft_proposed: int = 0        # speculative draft tokens proposed
    draft_accepted: int = 0        # ... of which the target accepted
    spec_rollbacks: int = 0        # ... of which were rejected (discarded)
    kv_bytes_per_token: float = 0.0  # HBM per cached token (block bytes /
    #                                  positions; halves with quantized pools)
    # expert layers (latent-attention family, serve/latent.py): what the
    # programs routed to the experts held HERE, read with the tokens
    moe_routes: Any = None         # [expert layers, local experts] int64 sum
    moe_tokens: int = 0            # tokens routed (prompt + decode)
    moe_experts_hit: Any = None    # [expert layers] int64, summed over steps
    moe_steps: int = 0             # decode steps counted in moe_experts_hit
    # the second kind of state (a family's ``slot_state``, docs/SERVE.md):
    # bytes resident for all slots, and prefill or chunk results written into
    # a slot's (admissions + chunk boundaries); both 0 for a family without
    slot_state_bytes: int = 0
    state_handoffs: int = 0
    # how often the host touched the device between model programs: reads
    # (Engine._fetch: one a decode step, one a prefill's first token, one a
    # chunk that counted routes) and slot transitions (one serve_activate an
    # activation, one serve_release a finish)
    device_fetches: int = 0
    slot_programs: int = 0
    # decode steps dispatched before the step before them was read (the
    # host's wait for step N under the device's work on N+1); the rest of
    # decode_steps ran in dispatch-then-read order: ``steps_kept`` counts
    # them by the FIRST condition of ``Engine._may_run_ahead`` that said no
    # (KEPT_REASONS), ``steps_fresh`` those dispatched with nothing in flight
    # and no such verdict before them (the first step after the engine was
    # idle). steps_ahead + sum(steps_kept) + steps_fresh == decode_steps
    steps_ahead: int = 0
    steps_kept: dict = field(default_factory=lambda: dict.fromkeys(KEPT_REASONS, 0))
    steps_fresh: int = 0
    # decode steps whose sampler took its vocabulary-wide branch (top-k
    # slice, scatter, draw: some live row has temperature > 0); the rest
    # paid an argmax alone (models/generate.sample_tokens)
    vocab_sampler_steps: int = 0
    # per reason (STEP_REASONS), summed over its steps: the step's visible
    # gap (the emit before it ended -> its own emit ended: what one
    # inter-token sample is) and its share of ``decode_s``
    step_gap_s: dict = field(default_factory=lambda: dict.fromkeys(STEP_REASONS, 0.0))
    step_dt_s: dict = field(default_factory=lambda: dict.fromkeys(STEP_REASONS, 0.0))
    # a request's time to first token, tiled (docs/SERVE.md "The step
    # loop"): five consecutive differences of one clock, summed over the
    # requests started; they add up to submit -> the ``step()`` call that
    # admitted the request returning, which is when a caller sees the token
    ttft_queue_s: float = 0.0      # submit -> the admission round that dequeues it begins
    ttft_behind_s: float = 0.0     # round begins -> its own prefill is dispatched
    ttft_prefill_s: float = 0.0    # prefill dispatched -> its first token on the host
    ttft_activate_s: float = 0.0   # token on the host -> the slot is activated
    ttft_held_s: float = 0.0       # activated -> step() returns to its caller
    # a step whose gap, less what the admissions of the same call took,
    # exceeds STALL_FACTOR x the running median of its own reason's is a
    # stall: counted, with the seconds beyond that median (the engine logs
    # each once, with the host phase that held most of it)
    stalled_steps: int = 0
    stalled_s: float = 0.0
    _gaps: dict = field(default_factory=lambda: {
        r: deque(maxlen=MEDIAN_WINDOW) for r in STEP_REASONS}, repr=False)

    def record_prompt(self, plen: int, hit_tokens: int = 0) -> None:
        self.prompt_tokens += plen
        self.prefix_hit_tokens += hit_tokens

    def record_moe(self, routes, tokens, step: bool = True) -> None:
        """One program's expert routes ``[expert layers, local experts]``
        over ``tokens`` tokens; ``step`` = a decode step (its experts hit
        count toward the per-step mean), else a prefill."""
        routes = np.asarray(routes, np.int64)
        if self.moe_routes is None:
            self.moe_routes = np.zeros_like(routes)
            self.moe_experts_hit = np.zeros(routes.shape[0], np.int64)
        self.moe_routes += routes
        self.moe_tokens += int(tokens)
        if step:
            self.moe_experts_hit += (routes > 0).sum(axis=1)
            self.moe_steps += 1

    def record_spec(self, proposed: int, accepted: int) -> None:
        """One speculative step's draft accounting (serve/spec.py)."""
        self.draft_proposed += proposed
        self.draft_accepted += accepted
        self.spec_rollbacks += proposed - accepted

    def record_prefill(self, dt_s: float, ttft_s: float) -> None:
        self.prefill_s += dt_s
        self.ttft_sum_s += ttft_s
        self.ttft_max_s = max(self.ttft_max_s, ttft_s)
        self.requests_started += 1
        self.generated_tokens += 1  # prefill samples the first token

    def record_visible(self, queue_s: float, behind_s: float, prefill_s: float,
                       activate_s: float, held_s: float) -> None:
        """One request's first token became visible to its caller: the five
        parts of its time to first token (``requests_started`` counts the
        requests, :meth:`record_prefill`)."""
        self.ttft_queue_s += queue_s
        self.ttft_behind_s += behind_s
        self.ttft_prefill_s += prefill_s
        self.ttft_activate_s += activate_s
        self.ttft_held_s += held_s

    def record_decode(self, dt_s: float, new_tokens: int, live: int,
                      slots: int, why: str = "fresh") -> None:
        """``why``: one of STEP_REASONS — ``ahead`` where the step was
        dispatched before the one before it was read (``dt_s`` then starts
        where that one's fetch returned), else what kept it."""
        self.decode_s += dt_s
        self.decode_steps += 1
        if why == "ahead":
            self.steps_ahead += 1
        elif why == "fresh":
            self.steps_fresh += 1
        else:
            self.steps_kept[why] += 1
        self.step_dt_s[why] += dt_s
        self.generated_tokens += new_tokens
        self.decode_tokens += new_tokens
        self.decode_live_sum += live
        self.occupancy_sum += live / max(slots, 1)

    def record_step(self, why: str, gap_s: float, admit_s: float = 0.0) -> bool:
        """One emitted decode step's visible gap, ``admit_s`` of it spent in
        the admissions (prefills, chunks) of the same ``step()`` call. True
        if the step stalled: the rest of the gap lies beyond STALL_FACTOR x
        the running median of its reason's, once MEDIAN_MIN of them are
        known. A stalled step does not enter the median's window."""
        self.step_gap_s[why] += gap_s
        own = gap_s - admit_s
        gaps = self._gaps[why]
        if len(gaps) >= MEDIAN_MIN:
            median = sorted(gaps)[len(gaps) // 2]
            if own > STALL_FACTOR * median:
                self.stalled_steps += 1
                self.stalled_s += own - median
                return True
        gaps.append(own)
        return False

    def steps_of(self, why: str) -> int:
        """Decode steps counted under the reason ``why`` (STEP_REASONS)."""
        if why == "ahead":
            return self.steps_ahead
        return self.steps_fresh if why == "fresh" else self.steps_kept[why]

    @property
    def elapsed_s(self) -> float:
        return self.decode_s + self.prefill_s

    @property
    def tokens_per_sec(self) -> float:
        if self.elapsed_s == 0:
            return 0.0
        return self.generated_tokens / self.elapsed_s

    @property
    def tokens_per_sec_per_chip(self) -> float:
        return self.tokens_per_sec / self.n_chips

    @property
    def slot_occupancy(self) -> float:
        if self.decode_steps == 0:
            return 0.0
        return self.occupancy_sum / self.decode_steps

    @property
    def ttft_avg_s(self) -> float:
        if self.requests_started == 0:
            return 0.0
        return self.ttft_sum_s / self.requests_started

    @property
    def prefix_hit_rate(self) -> float:
        if self.prompt_tokens == 0:
            return 0.0
        return self.prefix_hit_tokens / self.prompt_tokens

    @property
    def tokens_per_step(self) -> float:
        """Decode tokens per step per LIVE slot — exactly 1.0
        autoregressively at any batch size, up to ``spec_max_draft + 1``
        with speculative decoding accepting (serve/spec.py)."""
        if self.decode_live_sum == 0:
            return 0.0
        return self.decode_tokens / self.decode_live_sum

    @property
    def draft_accept_rate(self) -> float:
        if self.draft_proposed == 0:
            return 0.0
        return self.draft_accepted / self.draft_proposed

    def summary(self) -> dict:
        out = {
            "tokens_per_sec_per_chip": round(self.tokens_per_sec_per_chip, 1),
            "generated_tokens": self.generated_tokens,
            "ttft_avg_s": round(self.ttft_avg_s, 4),
            "ttft_max_s": round(self.ttft_max_s, 4),
            "slot_occupancy": round(self.slot_occupancy, 3),
            "decode_steps": self.decode_steps,
            "steps_ahead": self.steps_ahead,
            "stalled_steps": self.stalled_steps,
            "requests_finished": self.requests_finished,
            "prefill_compiles": self.prefill_compiles,
            "decode_compiles": self.decode_compiles,
        }
        if self.decode_steps:
            out["tokens_per_step"] = round(self.tokens_per_step, 3)
        if self.prompt_tokens:
            out["prefix_hit_tokens"] = self.prefix_hit_tokens
            out["prefix_hit_rate"] = round(self.prefix_hit_rate, 4)
        if self.draft_proposed:
            out["draft_accept_rate"] = round(self.draft_accept_rate, 4)
            out["spec_rollbacks"] = self.spec_rollbacks
        if self.kv_bytes_per_token:
            out["kv_bytes_per_token"] = round(self.kv_bytes_per_token, 2)
        return out
