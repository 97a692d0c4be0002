"""Tier-1 perf guards: the step loop must stay stall-free and the loss head
must stay fused.

Overlap guard: a data-layer or loop change that re-serializes host input
work against the device step (dropping the prefetch wrap, adding a blocking
sync inside the loop, an accidentally-quadratic sampler) shows up here as
host-blocked wall time. The threshold is deliberately generous — the CPU CI
rig shares two cores between the "device" step and the producer thread —
but a fully re-serialized loop (host_blocked_frac ~= host work / step time)
clears it by an order of magnitude on the failure side.

Loss-head memory guard: a head change that re-materialises [B, S, V] logits
(or lets autodiff build a full dlogits) shows up in the compiled step's
temp-buffer assignment, measured without running anything.
"""

import dataclasses
from functools import partial
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models.llama import LlamaConfig
from tony_tpu.parallel.mesh import MeshShape, build_mesh
from tony_tpu.train import DataConfig, FitConfig, fit
from tony_tpu.train import trainer

# generous: tolerate CI noise and GIL contention; a reserialized input
# path on this config measures well above it (see docs/PERF.md "Overlap")
MAX_HOST_BLOCKED_FRAC = 0.30


def test_steady_state_loop_is_not_host_blocked():
    final = fit(FitConfig(
        model=LlamaConfig.tiny(),
        data=DataConfig(global_batch=4, seq_len=32, vocab_size=256),  # prefetch=2 default
        mesh_shape=MeshShape(fsdp=2),
        steps=25,
        log_every=25,
        lr=5e-3,
        warmup_steps=2,
    ))
    assert np.isfinite(final["final_loss"])
    # the stall metric must exist (bench.py and the BENCH trajectory key on
    # it) and stay under the overlap budget
    assert "host_blocked_ms_per_step" in final
    assert "host_blocked_frac" in final
    assert final["host_blocked_frac"] < MAX_HOST_BLOCKED_FRAC, (
        f"step loop is {final['host_blocked_frac']:.0%} host-blocked "
        f"(host {final['host_blocked_ms_per_step']}ms/step) — input work is "
        "no longer overlapped with the device step"
    )
    # startup phases are reported (compile-ahead instrumentation)
    assert "compile_s" in final.get("startup", {})
    assert "first_batch_s" in final.get("startup", {})


def test_loss_head_stays_fused_in_memory():
    """Lower + compile the tiny-model train step (vocab scaled up so the
    loss head dominates) and assert the compiled temp footprint stays below
    the full-logits bound — one [B, S, V] fp32 tensor. The dense head
    measures ~3.7x that bound on this config (logits + dlogits + fusion
    slack), the fused head ~0.9x, so a head regression that re-materialises
    logits fails with a wide margin while leaving headroom for benign
    scheduling noise in the rest of the step."""
    B, S = 8, 128
    cfg = dataclasses.replace(
        LlamaConfig.tiny(), vocab_size=8192, max_seq_len=S, ce_vocab_chunk=512
    )
    mesh = build_mesh(MeshShape(dp=1))
    opt = trainer.default_optimizer(warmup_steps=1, decay_steps=10)
    state = trainer.make_train_state(jax.random.key(0), cfg, mesh, opt)
    toks = jax.ShapeDtypeStruct((B, S), jnp.int32)

    def temp_bytes(c):
        step = trainer.make_train_step(c, mesh, opt)
        compiled = step.lower(state, toks, toks).compile()
        return compiled.memory_analysis().temp_size_in_bytes

    full_logits = B * S * cfg.vocab_size * 4  # one fp32 [B, S, V]
    fused = temp_bytes(cfg)  # ce_impl='scan' is the default train path
    assert fused < full_logits, (
        f"fused train step temp {fused / 2**20:.1f}MiB >= full-logits bound "
        f"{full_logits / 2**20:.1f}MiB — the loss head is materialising "
        "vocab-sized tensors again"
    )
    # and the guard itself is meaningful: the dense head blows the bound
    dense = temp_bytes(dataclasses.replace(cfg, ce_impl="dense"))
    assert dense > 2 * fused, (fused, dense)


def test_grouped_moe_dispatch_stays_below_einsum_tensors():
    """Lower + compile a grad of the MoE block at a shape where the one-hot
    dispatch/combine tensors dominate, and assert the grouped (dropless)
    path's compiled temp footprint stays below what the einsum dispatch
    materialises for routing alone — two [T, E, C] fp32 tensors. A grouped-
    path regression that re-materialises capacity-slot tensors (or lets the
    sort blow up into per-expert one-hots) fails this without running a
    step; the einsum path itself exceeds the bound, proving it's tight."""
    import jax.numpy as jnp

    from tony_tpu.parallel.moe import MoEConfig, init_moe_params, moe_block

    T, D = 4096, 128
    base = MoEConfig(dim=D, ffn_dim=2 * D, n_experts=8, top_k=2)
    params = init_moe_params(jax.random.key(0), base, dtype=jnp.float32)
    x = jax.ShapeDtypeStruct((1, T, D), jnp.float32)

    def temp_bytes(cfg):
        def loss(p, xx):
            y, aux = moe_block(p, xx, cfg)
            return jnp.sum(y * y) + aux

        compiled = jax.jit(jax.value_and_grad(loss)).lower(params, x).compile()
        return compiled.memory_analysis().temp_size_in_bytes

    dispatch_tensors = 2 * T * base.n_experts * base.capacity(T) * 4
    grouped = temp_bytes(dataclasses.replace(base, dispatch="grouped"))
    assert grouped < dispatch_tensors, (
        f"grouped MoE temp {grouped / 2**20:.1f}MiB >= einsum dispatch-tensor "
        f"bound {dispatch_tensors / 2**20:.1f}MiB — the dropless path is "
        "materialising capacity-sized routing tensors again"
    )
    einsum = temp_bytes(dataclasses.replace(base, dispatch="einsum"))
    assert einsum > dispatch_tensors, (grouped, einsum, dispatch_tensors)


def test_decode_step_reads_kv_proportional_to_active_blocks():
    """Compile the serve engine's decode step over block caches of growing
    capacity and assert (via XLA cost analysis, nothing executed) that its
    bytes accessed scale with the ACTIVE block count, not max_len: a
    regression that re-points decode attention at a max_len-sized buffer
    (the old generate.py ring cache) blows the small-capacity bound by the
    full KV footprint. At these shapes the full-capacity step accesses
    ~5x the one-block step; the guard asserts 2.5x headroom on both
    sides."""
    from tony_tpu.models.llama import LlamaConfig, init_params
    from tony_tpu.serve import Engine, ServeConfig, dense
    from tony_tpu.serve.cache import create_cache

    slots, block, max_len = 4, 16, 512
    cfg = dataclasses.replace(LlamaConfig.tiny(), max_seq_len=max_len)
    params = init_params(jax.random.key(0), cfg)
    eng = Engine(params, cfg, ServeConfig(
        slots=slots, max_len=max_len, kv_block=block,
    ))

    def bytes_at(n_blocks):
        # paged form: a pool of slots * n_blocks physical blocks (plus
        # scratch) attended through an n_blocks-wide table — the active
        # footprint a trace with n_blocks-long rows actually holds
        cache = create_cache(cfg, slots, 1 + slots * n_blocks, block)
        table = jnp.zeros((slots, n_blocks), jnp.int32)
        # the family's step itself, NOT the engine's donating builder: the
        # bounds below were set on a program that also copies its pools out
        step = partial(dense.decode_step, cfg=cfg, decode_impl="scan",
                       kv_block=block, max_top_k=eng.serve.max_top_k)
        compiled = jax.jit(step).lower(
            params, cache, table, eng.state
        ).compile()
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        return float(ca["bytes accessed"])

    small = bytes_at(1)
    full = bytes_at(max_len // block)
    # one full-length read of k+v (the cache the old decode walked per step)
    kv_full = (
        2 * cfg.n_layers * slots * cfg.n_kv_heads * max_len * cfg.head_dim * 4
    )
    assert small < full / 2.5, (
        f"decode step over a 1-block cache accesses {small / 2**20:.1f}MiB "
        f"vs {full / 2**20:.1f}MiB at full capacity — decode traffic no "
        "longer scales with the active prefix"
    )
    # and the full-capacity cost is dominated by the KV buffers (the guard
    # is measuring the cache, not fixed per-step overhead)
    assert full - small > kv_full, (small, full, kv_full)


@pytest.mark.parametrize("step", ["plain", "spec", "quant_kv"])
def test_decode_step_keeps_the_pool_in_one_buffer(step):
    """Compile the decode step (cache and state donated, nothing executed)
    at a FIXED table width over pools of 1x, 4x and 16x the blocks and
    assert the pool is one buffer from argument to result: the compiled
    step's temp bytes do not grow with the pool, and its outputs alias
    both pools. With the pools handed to the layer scan as ``xs`` and taken
    back as stacked ``ys`` (before PR 26), or with the K/V rows written by
    a gather/scatter on non-adjacent axes, temp grew by 1.5-1.8x the
    pool's bytes: the copies the serve cell's trace showed as 19 ms of
    each 48 ms decode step on the chip (PERF.md section 6, PR 26)."""
    from tony_tpu.models.llama import init_params
    from tony_tpu.serve import Engine, ServeConfig
    from tony_tpu.serve.cache import create_cache
    from tony_tpu.serve.engine import _decode_fn

    slots, block, width, draft_k = 4, 16, 8, 2
    cfg = dataclasses.replace(LlamaConfig.tiny(), max_seq_len=block * width)
    params = init_params(jax.random.key(0), cfg)
    quant_kv = "int8" if step == "quant_kv" else ""
    eng = Engine(params, cfg, ServeConfig(
        slots=slots, max_len=block * width, kv_block=block, quant_kv=quant_kv,
    ))
    table = jnp.zeros((slots, width), jnp.int32)

    def plan(n_blocks):
        cache = create_cache(cfg, slots, n_blocks, block, quant_kv=quant_kv)
        k = draft_k if step == "spec" else 0
        drafts = (jnp.zeros((slots, k), jnp.int32),
                  jnp.zeros((slots,), jnp.int32)) if k else ()
        lowered = _decode_fn(
            cfg, "scan", block, eng.serve.max_top_k, False, quant_kv, draft_k=k,
        ).lower(params, cache, table, eng.state, *drafts)
        ma = lowered.compile().memory_analysis()
        pools = cache.k.nbytes + cache.v.nbytes
        return ma.temp_size_in_bytes, ma.alias_size_in_bytes, pools

    base = 1 + slots * width
    plans = [plan(1 + (base - 1) * m) for m in (1, 4, 16)]
    for temp, alias, pools in plans:
        assert alias >= pools, (
            f"decode step aliases {alias} B of outputs to inputs, less than "
            f"its two pools ({pools} B): a donated pool comes back as a copy"
        )
    (t1, _, p1), _, (t16, _, p16) = plans
    assert t16 - t1 < 0.05 * (p16 - p1), (
        f"decode step temp grows with the pool: {t1} B at {p1} B of pool, "
        f"{t16} B at {p16} B — the step copies or relayouts the pool again"
    )


def test_a_slot_transition_is_one_device_program(tmp_path, host_trace_events):
    """Serving-host guard, by counts and not by wall time: a warmed engine
    that serves a one-token request hands the slot over and takes it back
    inside ONE ``serve.activate`` phase with exactly two programs, one
    ``serve_activate`` and one ``serve_release``, and reads the device once
    (the first token). Eleven eager ``.at[slot].set`` there were some forty
    dispatches with the chip idle (PERF.md §6, PR 32); a field updated on
    its own again shows here as a third program."""
    from benchmark import tracing
    from tony_tpu.models import llama
    from tony_tpu.serve import Engine, Request, ServeConfig

    cfg = LlamaConfig.tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    eng = Engine(params, cfg, ServeConfig(slots=2, max_len=32, kv_block=8))
    prompt = np.arange(5, dtype=np.int32)

    def serve_one():
        (comp,) = eng.run([Request(prompt=prompt, max_new_tokens=1)]).values()
        return comp

    serve_one(), serve_one()  # every program built, the prefix in the store
    eng.reset_metrics()
    tracing.start(str(tmp_path))
    try:
        comp = serve_one()
    finally:
        tracing.stop()
    assert comp.finish_reason == "length" and len(comp.tokens) == 1
    assert eng.metrics.slot_programs == 2
    assert eng.metrics.device_fetches == 1 and eng.metrics.decode_steps == 0
    events = host_trace_events(tmp_path)
    ((_, lo, hi),) = [e for e in events if e[0] == "serve.activate"]
    inside = sorted({name for name, start, _ in events
                     if name.startswith("PjitFunction(") and lo <= start <= hi})
    assert inside == ["PjitFunction(serve_activate)", "PjitFunction(serve_release)"]


def test_disarmed_trace_span_is_within_noise_of_noop():
    """The trace spine's no-op contract: a span call on a DISARMED tracer
    is one global load + None compare returning a shared no-op object —
    cheap enough to compile into the train/serve hot paths. Guarded two
    ways: absolute per-call cost (generous for CI noise; an accidentally
    armed tracer pays dict/deque/time work well above it) and zero
    recording side effects."""
    import time

    from tony_tpu.obs import trace

    assert trace.active_tracer() is None  # the default state
    N = 50_000
    # warm up, then measure the full with-statement round trip; best of 5
    # so a CI scheduler hiccup in one repeat cannot fail the guard
    for _ in range(1000):
        with trace.span("x"):
            pass
    per_call = math.inf
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(N):
            with trace.span("x"):
                pass
        per_call = min(per_call, (time.perf_counter() - t0) / N)
    assert per_call < 5e-6, (
        f"disarmed trace.span costs {per_call * 1e9:.0f}ns/call — the no-op "
        "path regressed (is something arming a tracer or allocating?)"
    )
    # and it really is the shared no-op: nothing recorded anywhere
    assert trace.span("x") is trace.NOOP_SPAN
    trace.instant("x")  # no-op, no error


def test_fit_loop_stays_unblocked_with_tracing_armed(tmp_path):
    """The armed contract: with the trace spine recording at the default
    sampling stride AND the HBM observatory AND the numerics sentinel
    AND the live-series recorder sampling at their default strides
    (in-graph value monitors fused into the step, rule engine and series
    writer evaluating async), the tiny-model fit loop must still clear
    the host-blocked overlap budget — all four hooks are always-on in
    jobs, so their cost rides inside the same tier-1 guard as the data
    path."""
    from tony_tpu.obs import hbm, health, series, trace

    tracer = trace.install(trace.Tracer(
        str(tmp_path / "trace" / "guard.jsonl"), "guard", "guardtrace",
        sample_steps=16,  # the trace.sample_steps default
    ))
    # a stats fake so the CPU rig exercises the full armed path (real
    # reading + gauge + counter-track emission) at the default stride
    hbm.install(hbm.HbmWatch(
        stats_fn=lambda: [("dev0", {
            "bytes_in_use": 1 << 30, "peak_bytes_in_use": 2 << 30,
        })],
        sample_every=16,  # the obs.hbm.sample_steps default
    ))
    health.install(health.HealthSentinel(
        sample_every=16,  # the obs.health.sample_steps default
    ))
    series.uninstall()
    series.install(series.SeriesRecorder(
        str(tmp_path / "series" / "guard.jsonl"), "guard",
        sample_every=16,  # the obs.series.sample_steps default
    ))
    try:
        final = fit(FitConfig(
            model=LlamaConfig.tiny(),
            data=DataConfig(global_batch=4, seq_len=32, vocab_size=256),
            mesh_shape=MeshShape(fsdp=2),
            steps=25,
            log_every=25,
            lr=5e-3,
            warmup_steps=2,
        ))
        series.active_recorder().drain()
    finally:
        trace.uninstall()
        hbm.uninstall()
        health.uninstall()
        series.uninstall()
    assert np.isfinite(final["final_loss"])
    assert final["host_blocked_frac"] < MAX_HOST_BLOCKED_FRAC, (
        f"step loop is {final['host_blocked_frac']:.0%} host-blocked with "
        "tracing + memory + health + series sampling armed — a spine is "
        "stalling the loop"
    )
    # the sentinel evaluated real samples and found a clean run
    assert final["health_verdict"] == "healthy"
    # the series recorder scraped fit's source into its journal: step
    # progress plus the built-in HBM reading from the armed (fake) watch
    from tony_tpu.obs.series import read_series

    points = read_series(str(tmp_path / "series"))["guard"]
    assert points, "the fit loop never scraped the series"
    assert points[-1]["step"] == 25          # the shutdown force_sample
    assert points[-1]["hbm_live_bytes"] == 1 << 30
    assert any("goodput_frac" in p for p in points)
    # the spine actually recorded: fit root + sampled step spans, and the
    # step-time distribution made it into the final report
    import json

    recs = [json.loads(l) for l in open(tmp_path / "trace" / "guard.jsonl")
            if l.strip()]
    names = {r.get("name") for r in recs if r.get("ph") == "X"}
    assert "train.fit" in names and "train.step" in names
    steps = [r for r in recs if r.get("name") == "train.step"]
    assert all(r["args"]["every"] == 16 for r in steps)
    assert final["step_time_p99_s"] >= final["step_time_p50_s"] > 0
    # the memory observatory recorded too: per-device counter-track rows
    # in the same journal (the `tony trace` memory timeline)
    counters = [r for r in recs if r.get("ph") == "C"]
    assert counters and counters[0]["name"] == "hbm.dev0"
    assert counters[0]["args"]["live_gb"] == 1.0


def test_disarmed_hbm_sample_is_within_noise_of_noop():
    """The HBM observatory's no-op contract (the trace-span twin): a
    sample() call with no watch armed is one global load + None compare —
    cheap enough to sit in the train/serve step loops unconditionally.
    graft-lint GL005 holds the call-site side of the same contract."""
    import time

    from tony_tpu.obs import hbm

    hbm.uninstall()  # other tests/fit runs may have armed the process
    N = 50_000
    for _ in range(1000):
        hbm.sample()
    per_call = math.inf
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(N):
            hbm.sample()
        per_call = min(per_call, (time.perf_counter() - t0) / N)
    assert per_call < 5e-6, (
        f"disarmed hbm.sample costs {per_call * 1e9:.0f}ns/call — the "
        "no-op path regressed (is something arming a watch or allocating?)"
    )
    # and the armed-but-off-stride path is one counter bump, no reading
    calls = []
    watch = hbm.install(hbm.HbmWatch(
        stats_fn=lambda: calls.append(1) or [], sample_every=1000,
    ))
    try:
        for _ in range(999):
            hbm.sample()
        assert calls == []  # stats never read off-stride
        hbm.sample()
        assert len(calls) == 1
        assert watch is hbm.active_watch()
    finally:
        hbm.uninstall()


def test_disarmed_health_sample_is_within_noise_of_noop():
    """The numerics sentinel's no-op contract (the trace-span/hbm-sample
    twin): a sample() call with no sentinel armed is one global load +
    None compare — cheap enough to sit in the train/serve step loops
    unconditionally. graft-lint GL005 holds the call-site side of the
    same contract (tests/test_lint.py has the health fixtures)."""
    import time

    from tony_tpu.obs import health

    health.uninstall()  # other tests/fit runs may have armed the process
    N = 50_000
    for _ in range(1000):
        health.sample()
    per_call = math.inf
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(N):
            health.sample()
        per_call = min(per_call, (time.perf_counter() - t0) / N)
    assert per_call < 5e-6, (
        f"disarmed health.sample costs {per_call * 1e9:.0f}ns/call — the "
        "no-op path regressed (is something arming a sentinel or allocating?)"
    )
    # armed-but-off-stride: one counter bump, nothing enqueued
    sentinel = health.install(health.HealthSentinel(sample_every=1000))
    try:
        for _ in range(999):
            health.sample(metrics={})
        assert sentinel._pending == 0 and sentinel._q.empty()
        health.sample(metrics={})
        assert sentinel.drain(timeout_s=5.0)
        assert sentinel is health.active_sentinel()
    finally:
        health.uninstall()


def test_disarmed_profile_capture_is_within_noise_of_noop(tmp_path):
    """The coordinated profiler's no-op contract (the fifth twin): a
    maybe_capture() call with no controller armed is one global load +
    None compare — cheap enough to sit in the train/serve step loops
    unconditionally. graft-lint GL005 holds the call-site side of the
    same contract (tests/test_lint.py has the profile fixtures)."""
    import time

    from tony_tpu.obs import profile

    profile.uninstall()  # other tests may have armed the process
    N = 50_000
    for _ in range(1000):
        profile.maybe_capture()
    per_call = math.inf
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(N):
            profile.maybe_capture()
        per_call = min(per_call, (time.perf_counter() - t0) / N)
    assert per_call < 5e-6, (
        f"disarmed profile.maybe_capture costs {per_call * 1e9:.0f}ns/call — "
        "the no-op path regressed (is something arming a controller or "
        "allocating?)"
    )
    # armed-but-idle (no broadcast window): two attribute compares, no
    # window ever opens, nothing lands on disk
    ctl = profile.install(profile.ProfileController(
        str(tmp_path / "profile"), "guard", watch=False,
    ))
    try:
        per_call = math.inf
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(N):
                profile.maybe_capture()
            per_call = min(per_call, (time.perf_counter() - t0) / N)
        assert per_call < 5e-6, (
            f"armed-idle profile.maybe_capture costs {per_call * 1e9:.0f}"
            "ns/call — the off-window path regressed"
        )
        assert ctl._req is None and ctl._pending is None
        assert not (tmp_path / "profile" / "guard").exists()
        assert ctl is profile.active_controller()
    finally:
        profile.uninstall()


def test_disarmed_series_sample_is_within_noise_of_noop():
    """The live-series recorder's no-op contract (the fourth twin): a
    sample() call with no recorder armed is one global load + None
    compare — cheap enough to sit in the train/serve step loops
    unconditionally. graft-lint GL005 holds the call-site side of the
    same contract (tests/test_lint.py has the series fixtures)."""
    import time

    from tony_tpu.obs import series

    series.uninstall()  # other tests/fit runs may have armed the process
    N = 50_000
    for _ in range(1000):
        series.sample()
    per_call = math.inf
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(N):
            series.sample()
        per_call = min(per_call, (time.perf_counter() - t0) / N)
    assert per_call < 5e-6, (
        f"disarmed series.sample costs {per_call * 1e9:.0f}ns/call — the "
        "no-op path regressed (is something arming a recorder or "
        "allocating?)"
    )
    # armed-but-off-stride: one counter bump, no source is ever scraped
    calls = []
    rec = series.install(series.SeriesRecorder(
        None, "guard", sample_every=1000,
    ))
    rec.attach("probe", lambda: calls.append(1) or {"v": 1.0})
    try:
        for _ in range(999):
            series.sample()
        assert calls == []  # sources never scraped off-stride
        series.sample()
        assert len(calls) == 1
        assert rec is series.active_recorder()
    finally:
        series.uninstall()


def test_disarmed_annotate_is_within_noise_of_noop():
    """The device-timeline bridge's no-op contract (the sixth twin): an
    ``annotate(...)`` block with no profiler session on is one small
    object and the profiler's own active check on enter and exit — cheap
    enough to sit ten times in every engine step (serve/engine.py) and
    around every sampled train step. An ``annotate`` that grew work of its
    own (formatting a name, reading a clock) shows up here."""
    import time

    from tony_tpu.obs.profiler import annotate

    N = 50_000
    # a phase name takes no argument; a marker (one a decode step, at the end
    # of Engine._emit) carries the step's numbers, which must not be encoded
    # with no session on: both forms under the one bound
    for name, numbers in (("serve.plan", {}), ("serve.ahead", dict(n=7, gap_us=18000))):
        for _ in range(1000):
            with annotate(name, **numbers):
                pass
        per_call = math.inf
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(N):
                with annotate(name, **numbers):
                    pass
            per_call = min(per_call, (time.perf_counter() - t0) / N)
        assert per_call < 5e-6, (
            f"disarmed annotate({name!r}) costs {per_call * 1e9:.0f}ns/block — "
            "the no-op path regressed (is a profiler session left on, or is "
            "annotate doing work of its own?)"
        )
