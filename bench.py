"""Benchmark: Llama train-step throughput on the local chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tokens/s/chip", "vs_baseline": N}

The reference publishes no throughput numbers (BASELINE.md: "published": {});
the driver's north star is tokens/sec/chip and >= 45% MFU, so ``vs_baseline``
reports achieved MFU / 0.45 (1.0 = the north-star target). MFU is computed
from the compiled step's measured ``cost_analysis()`` FLOPs (the hand
formula rides along as ``mfu_formula`` with the ratio reported), and every
section runs under a phase-scoped ``HbmWatch`` watermark (obs/hbm.py) so
its HBM numbers are its own, not a cumulative process high-water mark.

The primary line is the 1.35B-param dense train step (the largest dense
config whose AdamW state + activations fit one v5e's 16GB HBM — Llama-2-7B
itself cannot fit a single chip, noted in extra.note). ``extra`` carries two
more benchmark results so they land in the driver's BENCH json without
breaking the one-line contract: a flash-vs-dot attention kernel comparison at
S=8192 and a MoE (GShard top-2) train line, plus a TPU-executed
flash-matches-dot correctness check (the CPU test suite only exercises the
Pallas kernels in interpreter mode).

Tuning provenance (round 3, pre-PR-1; its sweep records were deleted in
PR 21): remat save_attn_kernel
(keep q/k/v + flash residuals; bwd skips qkv projections, rope, and the
flash fwd kernel) + bf16 Adam first moment (frees 2.7GB to fund those saves)
+ flash blocks 1024/1024 moved single-chip MFU 52.9% -> 58.6%.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp


def _fence(x) -> None:
    """The sync point of every timed window: JAX returns before the device
    finishes, so a timing without it measures the enqueue."""
    jax.block_until_ready(x)


def _hbm_watch():
    """The bench-wide HbmWatch (obs/hbm.py): phase-scoped watermarks per
    section — each section owns its number (peak_exact says whether it set
    a new process high-water mark), killing the old cumulative-peak caveat
    and its `cum_peak_after_moe` workaround."""
    global _WATCH
    if _WATCH is None:
        from tony_tpu.obs.hbm import HbmWatch

        _WATCH = HbmWatch()
    return _WATCH


_WATCH = None


def train_bench(cfg, batch: int, seq: int, steps: int, mu_dtype,
                label: str = "train") -> dict:
    """One sharded train-step benchmark; returns tok/s + MFU + loss.

    The step is AOT-compiled so its cost_analysis() FLOPs are measured —
    MFU is computed from what XLA actually schedules, with the hand
    formula (train_flops_per_token) reported beside it as `mfu_formula`
    and the ratio as `flops_measured_vs_formula`. The run is wrapped in a
    phase watermark, so the reported HBM keys are scoped to THIS config."""
    from tony_tpu.models.llama import train_flops_per_token
    from tony_tpu.obs.compiles import get_ledger
    from tony_tpu.obs.metrics import StepTimer, chip_peak_flops
    from tony_tpu.parallel.mesh import single_device_mesh
    from tony_tpu.train.trainer import default_optimizer, make_train_state, make_train_step

    watch = _hbm_watch()
    ledger = get_ledger()
    with watch.phase(label) as ph:
        mesh = single_device_mesh()
        opt = default_optimizer(warmup_steps=10, decay_steps=1000, mu_dtype=mu_dtype)
        state = make_train_state(jax.random.key(0), cfg, mesh, opt)
        step = make_train_step(cfg, mesh, opt)
        tokens = jax.random.randint(jax.random.key(1), (batch, seq + 1), 0, cfg.vocab_size)
        inputs, targets = tokens[:, :-1], tokens[:, 1:]

        flops_per_step = 0.0
        t0 = time.perf_counter()
        try:
            with ledger.label(label):
                compiled = step.lower(state, inputs, targets).compile()
            entry = ledger.record_aot(label, compiled, time.perf_counter() - t0)
            flops_per_step = float(entry.get("flops", 0.0))
            step = compiled  # ONE compile, analyses attached
        except Exception:
            pass  # lazy jit fallback: first call below compiles

        state, metrics = step(state, inputs, targets)  # compile/warm
        state, metrics = step(state, inputs, targets)
        float(metrics["loss"])

        flops_formula = train_flops_per_token(cfg, seq)
        flops_measured = flops_per_step / (batch * seq) if flops_per_step else 0.0
        timer = StepTimer(
            flops_per_token=flops_measured or flops_formula,
            tokens_per_step=batch * seq,
            n_chips=1,
        )
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, inputs, targets)
        final_loss = float(metrics["loss"])  # sync fence
        timer.record(time.perf_counter() - t0, steps)
    peak = chip_peak_flops()
    out = {
        "tokens_per_sec_per_chip": round(timer.tokens_per_sec_per_chip, 1),
        # headline MFU from measured FLOPs (cost_analysis) when available
        "mfu": round(timer.mfu(peak), 4),
        "mfu_formula": round(
            timer.tokens_per_sec_per_chip * flops_formula / peak, 4
        ),
        "flops_source": "cost_analysis" if flops_measured else "formula",
        "loss": round(final_loss, 4),
        "batch": batch,
        "seq": seq,
        "steps": steps,
        # phase-scoped HBM watermark (the fused-CE win shows up here)
        **ph.bench_keys(),
    }
    if flops_measured:
        out["flops_per_token_measured"] = round(flops_measured, 1)
        out["flops_per_token_formula"] = round(flops_formula, 1)
        out["flops_measured_vs_formula"] = round(
            flops_measured / flops_formula, 4
        )
    return out


def _timed_scan_grad(attn, q, *, reps: int, steps: int) -> dict:
    """Time ``grad`` of ``reps`` scanned applications of ``attn`` (mirrors
    the model's layer scan so per-dispatch overhead amortises).
    Returns {"ms": N} or {"error": ...}."""

    def loss(qq):
        def body(c, _):
            return attn(c), None

        out, _ = jax.lax.scan(body, qq, None, length=reps)
        return jnp.sum(out.astype(jnp.float32))

    try:
        fn = jax.jit(jax.grad(loss))
        _fence(fn(q)); _fence(fn(q))
        t0 = time.perf_counter()
        for _ in range(steps):
            o = fn(q)
        _fence(o)
        return {"ms": round((time.perf_counter() - t0) / steps * 1e3, 1)}
    except Exception as e:
        return {"error": f"{type(e).__name__}: {str(e)[:120]}"}


def kernel_bench_s8192(steps: int = 8) -> dict:
    """Flash (Pallas) vs dot (XLA) attention at S=8192: fwd+bwd TF/s."""
    from tony_tpu.models.llama import dot_attention
    from tony_tpu.ops.attention import flash_attention

    B, S, H, D = 1, 8192, 16, 128
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.bfloat16)
    reps = 24
    fwd = 4 * B * H * S * S * D / 2        # QK^T + PV matmuls, causal half
    flops = 3.5 * fwd * reps               # + bwd: 5 more matmuls = 2.5x fwd

    out = {}
    for name, attn in [
        ("flash", lambda a: flash_attention(a, k, v, causal=True)),
        ("dot", lambda a: dot_attention(a, k, v)),
    ]:
        r = _timed_scan_grad(attn, q, reps=reps, steps=steps)
        if "ms" in r:
            r["tflops"] = round(flops / (r["ms"] / 1e3) / 1e12, 1)
        elif name == "dot":
            # expected: dot materialises the [S,S] fp32 scores -- 4.3GB per
            # layer at S=8192 -- which is exactly the memory wall the flash
            # kernel removes
            r["error"] = (
                "infeasible at S=8192 (materializes 4.3GB scores/layer); "
                + r["error"]
            )
        out[name] = r
    if "tflops" in out.get("flash", {}) and "tflops" in out.get("dot", {}):
        out["flash_speedup"] = round(out["flash"]["tflops"] / out["dot"]["tflops"], 2)
    return out


def gqa_kernel_bench(steps: int = 8) -> dict:
    """GQA via the kernel's BlockSpec index map vs an HBM-materialised K/V
    repeat, at llama3_8b's 32:8 head ratio (B=1, S=4096). Same math and
    near-equal time (both stream the same blocks); the native path's win is
    HBM CAPACITY -- no 4x-wide K/V tensors resident -- which is what lets
    long-sequence GQA configs fit at all."""
    from tony_tpu.ops.attention import flash_attention

    B, S, H, Hkv, D = 1, 4096, 32, 8, 128
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.bfloat16)
    rep = H // Hkv

    out = {
        "blockspec_gqa": _timed_scan_grad(
            lambda a: flash_attention(a, k, v, causal=True), q, reps=8, steps=steps
        ),
        "expanded_kv": _timed_scan_grad(
            lambda a: flash_attention(
                a, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
                causal=True,
            ),
            q, reps=8, steps=steps,
        ),
    }
    out["note"] = (
        "times agree within run-to-run variance; the BlockSpec path's "
        "advantage is HBM capacity (no 4x-wide K/V resident)"
    )
    return out


def long_context_bench(steps: int = 4) -> dict:
    """Single-chip S=32768 flash attention fwd+bwd — the long-context axis
    the reference never had. 34GB of fp32 scores per layer (32768^2 x 4B x
    8 heads) if materialised; the kernel streams them through VMEM."""
    from tony_tpu.ops.attention import flash_attention

    B, S, H, D = 1, 32768, 8, 128
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.bfloat16)
    reps = 2
    fwd = 4 * B * H * S * S * D / 2
    flops = 3.5 * fwd * reps

    r = _timed_scan_grad(
        lambda a: flash_attention(a, k, v, causal=True), q, reps=reps, steps=steps
    )
    if "ms" in r:
        r["tflops"] = round(flops / (r["ms"] / 1e3) / 1e12, 1)
    return r


def fused_ce_matches_dense_on_tpu() -> dict:
    """Fused-CE correctness on REAL hardware (the CPU suite runs the pallas
    kernels in interpreter mode only): value + grads vs the full-logits
    logsumexp reference at a vocab deliberately not divisible by the tiles."""
    from tony_tpu.ops.fused_ce import fused_ce_tokens, reference_ce_tokens

    B, S, D, V = 2, 512, 512, 4000
    ks = jax.random.split(jax.random.key(11), 3)
    h = jax.random.normal(ks[0], (B, S, D), jnp.bfloat16)
    w = (jax.random.normal(ks[1], (D, V), jnp.float32) * 0.05).astype(jnp.bfloat16)
    t = jax.random.randint(ks[2], (B, S), 0, V)

    def mean_ref(h_, w_):
        return jnp.mean(reference_ce_tokens(h_, w_, t))

    out = {}
    lr, gr = jax.value_and_grad(mean_ref, argnums=(0, 1))(h, w)
    for impl in ("scan", "pallas"):
        def mean_fused(h_, w_, impl=impl):
            return jnp.mean(fused_ce_tokens(h_, w_, t, impl=impl, vocab_chunk=512))

        lf, gf = jax.value_and_grad(mean_fused, argnums=(0, 1))(h, w)
        verr = abs(float(lf) - float(lr)) / max(abs(float(lr)), 1e-9)
        gerr = max(
            float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
            for a, b in zip(gf, gr)
        )
        if verr > 1e-3 or gerr > 1e-2:  # bf16 primals; fp32 parity lives in tier-1
            raise AssertionError(f"{impl} CE != dense on TPU: {verr=} {gerr=}")
        out[impl] = {"rel_value_err": round(verr, 8), "max_grad_err": round(gerr, 6)}
    return out


def ce_head_bench(steps: int = 8) -> dict:
    """Loss-head fwd+bwd at bench shapes (h [8,2048,2048], V=32000), dense
    full-logits vs fused scan vs fused pallas. The dense head materialises
    2.1GB of fp32 logits + 2.1GB dlogits at this batch; the fused paths keep
    one [N, Vc] block live."""
    from tony_tpu.ops.fused_ce import fused_ce_tokens, reference_ce_tokens

    B, S, D, V = 8, 2048, 2048, 32000
    ks = jax.random.split(jax.random.key(3), 3)
    h = jax.random.normal(ks[0], (B, S, D), jnp.bfloat16)
    w = (jax.random.normal(ks[1], (D, V), jnp.float32) * 0.02).astype(jnp.bfloat16)
    t = jax.random.randint(ks[2], (B, S), 0, V)

    def timed(lossf):
        try:
            fn = jax.jit(jax.grad(lossf, argnums=(0, 1)))
            _fence(fn(h, w)); _fence(fn(h, w))
            t0 = time.perf_counter()
            for _ in range(steps):
                o = fn(h, w)
            _fence(o)
            return {"ms": round((time.perf_counter() - t0) / steps * 1e3, 1)}
        except Exception as e:
            return {"error": f"{type(e).__name__}: {str(e)[:120]}"}

    out = {}
    for name, lossf in (
        ("dense", lambda a, b: jnp.mean(reference_ce_tokens(a, b, t))),
        ("scan", lambda a, b: jnp.mean(
            fused_ce_tokens(a, b, t, impl="scan", vocab_chunk=4096))),
        ("pallas", lambda a, b: jnp.mean(
            fused_ce_tokens(a, b, t, impl="pallas"))),
    ):
        # phase-scoped watermark per impl: the dense head's logits+dlogits
        # transient is attributed to the dense phase, not inherited by the
        # fused ones (obs/hbm.py attribution rule)
        with _hbm_watch().phase(f"ce_head_{name}") as ph:
            out[name] = timed(lossf)
        hk = ph.bench_keys()
        if hk:
            out[name]["hbm"] = hk
    return out


def flash_matches_dot_on_tpu() -> bool:
    """Correctness of the Pallas kernels on REAL hardware (the CPU suite
    runs them in interpreter mode only)."""
    from tony_tpu.models.llama import dot_attention
    from tony_tpu.ops.attention import flash_attention

    B, S, H, D = 2, 512, 4, 128
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, block_q=256, block_k=256)
    want = dot_attention(q, k, v)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))))
    if err > 2e-2:
        raise AssertionError(f"flash != dot on TPU: max abs err {err}")
    return True


def moe_routing_stats(cfg) -> dict:
    """Router health at bench shapes: run the (initialised) router over a
    random activation batch and report what the capacity semantics would
    drop vs what dropless serves (parallel.moe.routing_stats)."""
    from tony_tpu.parallel.moe import MoEConfig, init_moe_params, routing_stats

    mcfg = MoEConfig(
        dim=cfg.dim, ffn_dim=cfg.ffn_dim, n_experts=cfg.n_experts,
        top_k=cfg.moe_top_k, capacity_factor=cfg.moe_capacity_factor,
    )
    params = init_moe_params(jax.random.key(5), mcfg)
    x = jax.random.normal(jax.random.key(6), (8 * 2048, cfg.dim), jnp.float32)
    probs = jax.nn.softmax(x @ params["router"], axis=-1)
    return routing_stats(probs, mcfg)


def moe_bench(steps: int = 10) -> dict:
    """MoE train step per dispatch impl: grouped (dropless sorted grouped
    GEMM, scan + pallas kernels) vs the round-4 gather baseline vs the
    einsum reference, plus routing stats (dropped-route fraction, expert
    load imbalance) so the dropless gains are legible in the trajectory.

    4 experts (~1.2B total / ~700M active): the 8-expert preset's AdamW
    state alone exceeds the chip's 16GB. Capacity factor 1.0 for the
    capacity paths (round-4 tuning, docs/PERF.md); irrelevant to grouped.
    Each dispatch runs under its own phase watermark (obs/hbm.py), so the
    per-dispatch HBM keys are scoped to that config — `peak_exact` says
    whether the phase set a new process high-water mark."""
    from tony_tpu.models.llama import LlamaConfig

    def cfg_for(**kw):
        return LlamaConfig.bench_moe(
            n_experts=4, attention_impl="flash",
            remat_policy="save_attn_kernel", moe_capacity_factor=1.0, **kw,
        )

    per_dispatch = {}
    for name, kw in (
        ("grouped", {"moe_dispatch": "grouped"}),
        ("grouped_pallas", {"moe_dispatch": "grouped", "moe_gmm_impl": "pallas"}),
        ("gather", {"moe_dispatch": "gather"}),
        ("einsum", {"moe_dispatch": "einsum"}),
    ):
        try:
            r = train_bench(
                cfg_for(**kw), batch=8, seq=2048, steps=steps,
                mu_dtype=jnp.bfloat16, label=f"moe_{name}",
            )
            per_dispatch[name] = {
                k: r[k]
                for k in ("tokens_per_sec_per_chip", "mfu", "loss",
                          "phase_peak_hbm_gb", "phase_delta_peak_gb",
                          "peak_exact")
                if k in r
            }
        except Exception as e:
            per_dispatch[name] = {"error": f"{type(e).__name__}: {str(e)[:160]}"}

    headline_cfg = cfg_for(moe_dispatch="grouped")
    # headline = first dispatch that actually produced numbers; when every
    # run failed, say so instead of wearing a working dispatch's name (the
    # per-run errors stay visible in per_dispatch)
    headline_name = next(
        (n for n in ("grouped", "gather")
         if "tokens_per_sec_per_chip" in per_dispatch.get(n, {})),
        None,
    )
    out = {
        "n_params": headline_cfg.n_params,
        "n_active_params": headline_cfg.n_active_params,
        "dispatch": headline_name or "all_failed",
        "capacity_factor": 1.0,
        "batch": 8,
        "seq": 2048,
        **(per_dispatch.get(headline_name, {}) if headline_name else {}),
        "per_dispatch": per_dispatch,
    }
    g = per_dispatch.get("grouped", {}).get("tokens_per_sec_per_chip", 0)
    b = per_dispatch.get("gather", {}).get("tokens_per_sec_per_chip", 0)
    if g and b:
        # the PR-4 gate, resolved round 20: `grouped_vs_gather` is a
        # perf-diff-judged ratio (higher-better), and the dispatch
        # decision is recorded as int bits so the diff's flatten (numeric
        # leaves only) holds them to configuration identity — grouped
        # ships as the default exactly while the gate holds
        out["grouped_vs_gather"] = round(g / b, 3)
        out["dispatch_gate_holds"] = int(g > b)
    out["dispatch_default_grouped"] = int(cfg_for().moe_dispatch == "grouped")
    try:
        out["routing"] = moe_routing_stats(headline_cfg)
    except Exception as e:
        out["routing"] = {"error": f"{type(e).__name__}: {str(e)[:120]}"}
    # the ep-combine overlap, OFF vs ON through the real capture path —
    # the MoE counterpart of the `overlap` section ('pallas' = the TPU
    # grouped-GEMM kernel form inside each chunk)
    try:
        out["overlap"] = moe_overlap_bench(
            cfg_for(moe_dispatch="grouped"), batch=8, seq=2048, steps=6,
            impl="pallas",
        )
    except Exception as e:
        out["overlap"] = {"error": f"{type(e).__name__}: {str(e)[:160]}"}
    return out


def moe_overlap_bench(cfg=None, batch: int = 8, seq: int = 64,
                      steps: int = 6, impl: str = "scan") -> dict:
    """The MoE ep-combine overlap section: one expert-parallel train step
    captured through the real ProfileController path twice — the grouped
    path's post-FFN combine as the single blocking psum
    (moe_overlap_impl='off') vs decomposed per-token-chunk partial
    combines (ops/moe_overlap) — so per-step exposed-collective share and
    per-collective achieved_gbps for the ep combine land in the committed
    step-anatomy fixtures next to the dense capture. The ON run's chunk
    size is solved from the OFF capture's measured bandwidth
    (chunk_tokens_from_report): the anatomy report drives the knob the
    report then judges, the same loop the `overlap` section closes for
    the fsdp/dp collectives."""
    import dataclasses
    import glob as _glob
    import tempfile

    from tony_tpu.models.llama import LlamaConfig
    from tony_tpu.obs import anatomy, comms
    from tony_tpu.obs import profile as profile_mod
    from tony_tpu.ops.moe_overlap import chunk_tokens_from_report, overlap_chunks
    from tony_tpu.parallel.mesh import (
        MeshShape, build_mesh, get_default_mesh, set_default_mesh,
    )
    from tony_tpu.train.trainer import (
        default_optimizer, make_train_state, make_train_step,
    )

    n = len(jax.devices())
    if n < 2:
        return {"error": "moe overlap bench needs >= 2 devices (ep ring)"}
    if cfg is None:
        cfg = LlamaConfig.tiny_moe()
    # ep pair (the combine this section decomposes) + dp over the rest so
    # tokens stay sharded over the data axes, the trainer's MoE shape
    dp = n // 2 if n >= 4 else 1
    if batch % max(dp, 1):
        return {"error": f"batch {batch} does not shard over dp={dp}"}
    prev_mesh = get_default_mesh()
    mesh = build_mesh(MeshShape(ep=2, dp=dp))
    set_default_mesh(mesh)
    opt = default_optimizer(warmup_steps=2, decay_steps=100)
    tokens = jax.random.randint(
        jax.random.key(1), (batch, seq + 1), 0, cfg.vocab_size
    )
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    def capture(variant_cfg):
        state = make_train_state(jax.random.key(0), variant_cfg, mesh, opt)
        step = make_train_step(variant_cfg, mesh, opt)
        ledger_rows = []
        try:
            compiled = step.lower(state, inputs, targets).compile()
            ledger_rows = comms.extract_collectives(compiled)
            step = compiled
        except Exception:
            pass  # lazy jit fallback: ledger-less capture still reports
        out_root = tempfile.mkdtemp(prefix="tony-moe-overlap-")
        ctl = profile_mod.ProfileController(out_root, "bench", watch=False)
        state, m = step(state, inputs, targets)  # warm outside the window
        _fence(m["loss"])
        ctl.trigger(steps=steps)
        for _ in range(steps + 1):
            ctl.step(fetch_s=0.0)
            state, m = step(state, inputs, targets)
            _fence(m["loss"])
        ctl.finish()
        mpaths = _glob.glob(
            os.path.join(out_root, "bench", "*", "manifest.json")
        )
        if not mpaths:
            return {"error": "no capture manifest landed"}
        with open(mpaths[-1]) as fh:
            manifest = json.load(fh)
        rep = anatomy.proc_report(manifest, ledger_rows)
        sec = {
            "step_ms": rep["per_step_ms"]["step_time_s"],
            "compute_ms": rep["per_step_ms"]["compute_s"],
            "exposed_collective_ms": rep["per_step_ms"]["exposed_collective_s"],
            "loss": round(float(m["loss"]), 4),
        }
        for k in ("overlap_frac", "pure_comm_steps"):
            if k in rep:
                sec[k] = rep[k]
        top = next(
            (r for r in rep["collectives"]
             if r.get("bytes") and r.get("total_s")),
            None,
        )
        if top is not None:
            sec["top_collective"] = {
                "kind": top["kind"], "bytes": top["bytes"],
            }
            if "achieved_gbps" in top:
                sec["top_collective"]["achieved_gbps"] = top["achieved_gbps"]
        return sec

    try:
        off = capture(dataclasses.replace(cfg, moe_overlap_impl="off"))
        if "error" in off:
            return off
        # size the chunk from the OFF capture's measured bandwidth; when
        # the measured size doesn't divide this shape's per-shard rows,
        # fall back to the auto split rather than silently not overlapping
        dtype_bytes = 2 if cfg.dtype == jnp.bfloat16 else 4
        chunk = chunk_tokens_from_report(off, dim=cfg.dim,
                                         dtype_bytes=dtype_bytes)
        t_local = (batch * seq) // dp
        if overlap_chunks(t_local, chunk) is None:
            chunk = 0
        on = capture(dataclasses.replace(
            cfg, moe_overlap_impl=impl, moe_overlap_chunk=chunk,
        ))
    finally:
        set_default_mesh(prev_mesh)
    out = {
        "devices": n,
        "mesh": {"ep": 2, "dp": dp},
        "impl": impl,
        "chunk_tokens": chunk,
        "off": off,
        "on": on,
    }
    if "error" not in on:
        # lift the judged keys to the section top so perf_diff's dotted
        # rules (extra.moe_top2.overlap.*) see them without digging into
        # variants
        if "overlap_frac" in on:
            out["overlap_frac"] = on["overlap_frac"]
        out["exposed_collective_ms"] = on["exposed_collective_ms"]
        if off.get("exposed_collective_ms"):
            out["exposed_ratio"] = round(
                on["exposed_collective_ms"] / off["exposed_collective_ms"], 4
            )
        if off.get("step_ms"):
            out["step_ms_ratio"] = round(on["step_ms"] / off["step_ms"], 4)
        # value-safety receipt: same batch/state both variants — the
        # decomposed combine is an execution schedule, not a new model
        if "loss" in off and "loss" in on:
            out["loss_delta"] = round(abs(on["loss"] - off["loss"]), 6)
    return out


def decode_bench(on_tpu: bool) -> dict:
    """Serving throughput (the decode counterpart of the training
    headline): the continuous-batching engine over a request trace.

    Reports decode tokens/s/chip, TTFT, and slot occupancy for
    (a) sequential batch-1 decode (one slot: the pre-engine serving
    pattern — a request owns the whole 'batch'), (b) all-slots continuous
    batching over the SAME trace, and (c) steady state under a mixed
    arrival trace (new request every other step). Decode at these shapes
    is HBM-bandwidth-bound on the weights, so batching slots is nearly
    free: the full-slot engine targets >= 4x the sequential tokens/s.
    Also times the native-GQA decode kernel vs the repeat-expanded
    reference at the same shapes."""
    import numpy as np

    from tony_tpu.models.llama import LlamaConfig, init_params
    from tony_tpu.ops.decode_attention import (
        decode_attention, reference_decode_attention,
    )
    from tony_tpu.serve import Engine, Request, ServeConfig

    if on_tpu:
        # bench_1b4 trunk at llama3-style 4:1 GQA (16 q heads / 4 kv heads)
        import dataclasses

        cfg = dataclasses.replace(LlamaConfig.bench_1b4(), n_kv_heads=4)
        slots, max_len, block = 8, 1024, 128
        n_req, max_new = 16, 64
        prompt_lens = [64, 128, 192, 256, 384, 512]
        kern_T = 1024
    else:
        cfg = LlamaConfig.tiny()
        slots, max_len, block = 4, 64, 8
        n_req, max_new = 6, 4
        prompt_lens = [3, 5, 9, 14]
        kern_T = 64
    params = init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)

    def trace():
        return [
            Request(
                prompt=rng.integers(
                    0, cfg.vocab_size, prompt_lens[i % len(prompt_lens)]
                ),
                max_new_tokens=max_new,
                rng=i,
            )
            for i in range(n_req)
        ]

    def serve_cfg(s):
        return ServeConfig(slots=s, max_len=max_len, kv_block=block)

    def warmed(s):
        """Engine with every bucket/capacity compile paid before timing:
        the reported tokens/s is steady-state serving, not XLA compiles."""
        eng = Engine(params, cfg, serve_cfg(s))
        eng.run([
            Request(prompt=rng.integers(0, cfg.vocab_size, pl),
                    max_new_tokens=max_new)
            for pl in prompt_lens
        ])
        # a lone short request after the drain reaches the shrunk-capacity
        # compiles the timed trace would otherwise pay mid-run
        eng.run([Request(prompt=rng.integers(0, cfg.vocab_size, prompt_lens[0]),
                         max_new_tokens=2)])
        eng.reset_metrics()
        return eng

    out = {"model": "bench_1b4_gqa16_4" if on_tpu else "tiny",
           "slots": slots, "max_new_tokens": max_new, "n_requests": n_req}

    # (a) sequential batch-1: the trace drains one request at a time
    eng1 = warmed(1)
    eng1.run(trace())
    out["sequential_b1"] = eng1.metrics.summary()

    # (b) full-slot continuous batching, same trace submitted upfront
    engS = warmed(slots)
    engS.run(trace())
    out["continuous"] = engS.metrics.summary()
    s1 = eng1.metrics.tokens_per_sec_per_chip
    sS = engS.metrics.tokens_per_sec_per_chip
    if s1 > 0:
        out["continuous_vs_b1"] = round(sS / s1, 2)

    # (c) steady state under a mixed arrival trace: half the requests
    # queued upfront, one more lands every other decode step
    engM = warmed(slots)
    reqs = trace()
    for r in reqs[: max(1, n_req // 2)]:
        engM.submit(r)
    rest = reqs[max(1, n_req // 2):]
    i = 0
    while engM._queue or engM.n_live or rest:
        if rest and i % 2 == 0:
            engM.submit(rest.pop(0))
        engM.step()
        i += 1
    out["mixed_arrivals"] = engM.metrics.summary()

    # (d) 90%-shared-prefix trace, store on vs off (serve/prefix.py): the
    # cross-request-reuse headline. Requests share a long template prefix
    # and differ only in a short tail; with the store on, admission
    # matches the prefix and prefills only the tail — TTFT and prefill
    # FLOPs (from the compile ledger's AOT cost_analysis) collapse to the
    # tail's. Sequential single-request runs so TTFT is unblurred.
    from tony_tpu.obs.compiles import get_ledger

    # trace lengths chosen so the tail bucket is genuinely smaller than
    # the full-prompt bucket (at the tiny CPU shapes the default request
    # lengths would pad tail and prompt into the same bucket)
    prefix_total = 512 if on_tpu else 56
    shared_len = int(round(0.9 * prefix_total))
    tail_len = prefix_total - shared_len
    shared_prefix = rng.integers(0, cfg.vocab_size, shared_len)

    def prefix_mode(on: bool) -> dict:
        eng = Engine(params, cfg, ServeConfig(
            slots=2, max_len=max_len, kv_block=block, prefix=on,
        ))
        def reqs(seed):
            r2 = np.random.default_rng(seed)
            return [
                Request(
                    prompt=np.concatenate(
                        [shared_prefix,
                         r2.integers(0, cfg.vocab_size, tail_len)]
                    ),
                    max_new_tokens=max_new, rng=seed * 1000 + i,
                )
                for i in range(n_req)
            ]
        for r in reqs(7):   # warm: compiles paid, prefix registered
            eng.run([r])
        eng.reset_metrics()
        ttfts = []
        for r in reqs(8):
            done = eng.run([r])
            ttfts.extend(c.ttft_s for c in done.values())
        ttfts.sort()
        m = eng.metrics.summary()
        return {
            "ttft_p50_s": round(ttfts[len(ttfts) // 2], 5),
            "ttft_p99_s": round(ttfts[-1], 5),
            "prefix_hit_rate": m.get("prefix_hit_rate", 0.0),
        }

    ledger = get_ledger()
    p_on, p_off = prefix_mode(True), prefix_mode(False)
    # prefill FLOPs per request from the ledger's AOT entries: the full
    # bucket the off-mode pays vs the tail bucket the store leaves
    flops_by_name = {
        e["fn"]: e.get("flops", 0.0) for e in ledger.entries("aot")
    }
    full_flops = max(
        (v for k, v in flops_by_name.items()
         if k.startswith("serve.prefill[")), default=0.0,
    )
    tail_flops = max(
        (v for k, v in flops_by_name.items()
         if k.startswith("serve.prefill_tail[")), default=0.0,
    )
    trace_out = {
        "shared_len": shared_len, "tail_len": tail_len,
        "prefix_on": p_on, "prefix_off": p_off,
    }
    if p_off["ttft_p50_s"] > 0:
        trace_out["ttft_p50_ratio"] = round(
            p_on["ttft_p50_s"] / p_off["ttft_p50_s"], 3
        )
    if full_flops > 0 and tail_flops > 0:
        trace_out["prefill_flops_full"] = full_flops
        trace_out["prefill_flops_tail"] = tail_flops
        trace_out["prefill_flops_ratio"] = round(tail_flops / full_flops, 4)
    out["prefix_trace"] = trace_out

    # (e) speculative decoding (serve/spec.py): repeated greedy traffic,
    # spec on vs off at batch 1 and batch `slots`. The first (warm) pass
    # seeds the radix store with the prompt AND the generation, so the
    # timed repeats draft along the observed path at near-full accept —
    # the verify step emits several tokens per forward while each forward
    # stays memory-bound. Headline: tokens/s/slot on/off speedup at b1
    # (target >= 2x), tokens/step, accept rate, and the compile count
    # (ONE extra signature family, never per-draft-length).
    # prompt + generation block-aligned so the warm pass registers the
    # WHOLE path as full radix blocks — the timed repeats then draft to
    # the end of the generation, not just its full-block prefix
    # gen length \equiv 1 (mod block): the LAST generated token's KV is
    # never written (nothing decodes after it), so the path registered at
    # finish is the first plen+gen-1 tokens — this choice makes that a
    # whole number of blocks and the store covers the entire repeat
    spec_new = max_new * 12 + 1
    spec_draft = 15
    spec_prompt = rng.integers(0, cfg.vocab_size, block)

    def spec_mode(on: bool, batch: int) -> dict:
        eng = Engine(params, cfg, ServeConfig(
            slots=batch, max_len=max_len, kv_block=block,
            spec=on, spec_max_draft=spec_draft,
        ))
        def reqs():
            return [
                Request(prompt=spec_prompt, max_new_tokens=spec_new, rng=i)
                for i in range(batch)
            ]
        # warm TWICE: the first pass seeds the store (and pays the full-
        # prefill compiles), the second pays the compiles only a repeat
        # hits (tail prefill at the matched boundary, the spec step at
        # its steady signatures) — the timed pass then measures serving,
        # not XLA
        eng.run(reqs())
        eng.run(reqs())
        eng.reset_metrics()
        eng.run(reqs())
        m = eng.metrics
        r = {
            "tok_s_slot": round(m.tokens_per_sec_per_chip / batch, 1),
            "tokens_per_step": round(m.tokens_per_step, 3),
            "decode_compiles": m.decode_compiles,
        }
        if on:
            r["accept_rate"] = round(m.draft_accept_rate, 4)
        return r

    spec_out: dict = {"max_draft": spec_draft, "gen_tokens": spec_new}
    for batch in (1, slots):
        s_on, s_off = spec_mode(True, batch), spec_mode(False, batch)
        spec_out[f"b{batch}_on"] = s_on
        spec_out[f"b{batch}_off"] = s_off
        if s_off["tok_s_slot"] > 0:
            spec_out[f"speedup_b{batch}"] = round(
                s_on["tok_s_slot"] / s_off["tok_s_slot"], 2
            )
    out["spec_trace"] = spec_out

    # (f) quantized serving (serve.quant.*: block-scaled int8 KV pools in
    # serve/cache.py + weight-only int8 decode matmuls in ops/quant_mm.py):
    # the same warmed trace, quant on vs off. ``tolerance`` is the STATED
    # quant-vs-bf16 logits bound the kernels hold (tests/test_quant.py
    # asserts it; perf-diff treats it as config identity, so loosening it
    # is a diff failure, not drift). Each mode runs under its own HBM
    # phase so peak_hbm_gb is scoped per mode, not inherited.
    QUANT_TOL = 0.08

    def qreqs(seed):
        r2 = np.random.default_rng(seed)
        return [
            Request(
                prompt=r2.integers(
                    0, cfg.vocab_size, prompt_lens[i % len(prompt_lens)]
                ),
                max_new_tokens=max_new, rng=seed * 1000 + i,
            )
            for i in range(n_req)
        ]

    def quant_mode(on: bool) -> dict:
        name = "decode.quant_on" if on else "decode.quant_off"
        with _hbm_watch().phase(name) as ph:
            eng = Engine(params, cfg, ServeConfig(
                slots=slots, max_len=max_len, kv_block=block,
                quant_kv="int8" if on else "", quant_weights=on,
            ))
            eng.run(qreqs(3))  # warm: compiles paid before timing
            eng.reset_metrics()
            eng.run(qreqs(4))
            m = eng.metrics
            r = {
                "tok_s_slot": round(m.tokens_per_sec_per_chip / slots, 1),
                "ttft_avg_s": round(m.ttft_avg_s, 5),
                "kv_bytes_per_token": round(m.kv_bytes_per_token, 1),
            }
            eng.close()
        hk = ph.bench_keys()
        if hk:
            r["peak_hbm_gb"] = hk["phase_peak_hbm_gb"]
        return r

    q_on, q_off = quant_mode(True), quant_mode(False)
    quant_out: dict = {
        "kv_dtype": "int8", "tolerance": QUANT_TOL,
        "quant_on": q_on, "quant_off": q_off,
    }
    if q_off["tok_s_slot"] > 0:
        quant_out["tok_s_ratio"] = round(
            q_on["tok_s_slot"] / q_off["tok_s_slot"], 3
        )
    out["quant"] = quant_out

    # (g) disaggregated prefill/decode (engine chunked prefill +
    # serve/gang.py pool handoff): a mixed-arrival trace where one LONG
    # prompt lands mid-stream among short decoders — the interference
    # headline. Four modes, chunking off/on x colocated/pooled:
    # colocated means one engine prefills AND decodes, so the long
    # prefill stalls every live decoder for the whole prompt unless it
    # is chunked (one chunk interleaved per decode step); pooled means a
    # second engine plays the prefill host — it prefills the long
    # prompt, exports the finished paged blocks, and the payload rides
    # the real wire format (pack/unpack measured as handoff bytes/ms)
    # into the decode engine, so decode-side admission prefix-hits the
    # shipped blocks and the long prompt never runs on the decode mesh.
    # TTFT/TPOT come from per-request completions polled step-by-step
    # (the engine's windowed snapshot is the series recorder's single
    # window — the bench must not consume it), and the warm passes pay
    # BOTH the compiles and the KV-pool growth: the store retains warm
    # blocks, the pool grows once, a drain frees everything, and the
    # timed pass runs at the settled pool shape with zero recompiles.
    from tony_tpu.serve.cache import pack_payload, unpack_payload

    if on_tpu:
        disagg_long, disagg_chunk = 512, 256
    else:
        disagg_long, disagg_chunk = 56, 16

    def disagg_mode(chunked: bool, pooled: bool) -> dict:
        eng = Engine(params, cfg, ServeConfig(
            slots=slots, max_len=max_len, kv_block=block, prefix=True,
            chunk_tokens=disagg_chunk if chunked else 0,
        ))
        hand = {"blocks": 0, "bytes": 0, "ms": 0.0}

        def run_pass(seed: int, timed: bool) -> dict | None:
            r2 = np.random.default_rng(seed)
            long_prompt = r2.integers(0, cfg.vocab_size, disagg_long)
            shorts = [
                Request(
                    prompt=r2.integers(
                        0, cfg.vocab_size, prompt_lens[i % len(prompt_lens)]
                    ),
                    max_new_tokens=max_new, rng=seed * 1000 + i,
                )
                for i in range(n_req)
            ]
            if pooled:
                peng = Engine(params, cfg, ServeConfig(
                    slots=1, max_len=max_len, kv_block=block,
                    prefix=True, pool="prefill",
                ))
                peng.run([Request(prompt=long_prompt, max_new_tokens=1)])
                covered, payload = peng.export_prefix_blocks(long_prompt)
                t0 = time.perf_counter()
                wire = pack_payload(payload)
                eng.adopt_blocks(covered, unpack_payload(
                    wire["k"], wire["v"], wire["shape"], wire["dtype"],
                    wire.get("k_scale", b""), wire.get("v_scale", b""),
                ))
                if timed:
                    hand["blocks"] = payload.n_blocks
                    hand["bytes"] = payload.nbytes
                    hand["ms"] = round((time.perf_counter() - t0) * 1e3, 3)
                peng.close()
            # half the shorts upfront, the long prompt lands at step 2,
            # remaining shorts one every other step — the long prefill
            # hits while every slot is mid-decode
            rids = [eng.submit(r) for r in shorts[: n_req // 2]]
            rest = shorts[n_req // 2:]
            pending = Request(prompt=long_prompt, max_new_tokens=max_new,
                              rng=seed)
            first_seen: dict[int, float] = {}
            finished: dict[int, tuple[float, int, float]] = {}
            i = 0
            while eng._queue or eng.n_live or rest or pending is not None:
                if i == 2 and pending is not None:
                    rids.append(eng.submit(pending))
                    pending = None
                elif rest and i % 2 == 0:
                    rids.append(eng.submit(rest.pop(0)))
                eng.step()
                now = time.perf_counter()
                for rid in rids:
                    if rid in finished:
                        continue
                    c = eng.completion_of(rid)
                    if c is None or not c.tokens:
                        continue
                    first_seen.setdefault(rid, now)
                    if c.finish_reason:
                        finished[rid] = (now, len(c.tokens), c.ttft_s)
                i += 1
            for rid in rids:
                eng.take_completion(rid)
            if not timed:
                return None
            ttfts = sorted(v[2] for v in finished.values())
            tpots = sorted(
                (v[0] - first_seen[rid]) / max(v[1] - 1, 1)
                for rid, v in finished.items()
            )
            return {
                "ttft_p50_s": round(ttfts[len(ttfts) // 2], 5),
                "ttft_p99_s": round(ttfts[-1], 5),
                "tpot_p50_s": round(tpots[len(tpots) // 2], 5),
                "tpot_p99_s": round(tpots[-1], 5),
            }

        def drain_store() -> None:
            while eng._store.evict_lru(eng._pool.release) is not None:
                pass

        run_pass(10, False)   # warm 1: compiles + the one-time pool growth
        drain_store()
        run_pass(11, False)   # warm 2: every signature at the settled shape
        drain_store()
        r = run_pass(12, True)
        eng.close()
        if pooled:
            r["handoff_blocks"] = hand["blocks"]
            r["handoff_bytes"] = hand["bytes"]
            r["handoff_ms"] = hand["ms"]
        return r

    disagg: dict = {"chunk_tokens": disagg_chunk,
                    "long_prompt_tokens": disagg_long}
    for chunked in (False, True):
        for pooled in (False, True):
            key = (("chunked" if chunked else "unchunked")
                   + ("_pooled" if pooled else "_colocated"))
            disagg[key] = disagg_mode(chunked, pooled)
    base_p99 = disagg["unchunked_colocated"].get("tpot_p99_s", 0.0)
    if base_p99 > 0:
        # the chunking headline: how much of the long-prompt TPOT spike
        # chunked prefill removes on a colocated gang (< 1 = bounded)
        disagg["tpot_p99_chunked_ratio"] = round(
            disagg["chunked_colocated"].get("tpot_p99_s", 0.0) / base_p99, 3
        )
    out["disagg"] = disagg

    # native-GQA decode kernel vs the repeat-expanded reference (one
    # decode step of attention at full cache length, layer-scanned so
    # dispatch overhead amortises)
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (slots, H, hd), cfg.dtype)
    kc = jax.random.normal(ks[1], (slots, Hkv, kern_T, hd), cfg.dtype)
    vc = jax.random.normal(ks[2], (slots, Hkv, kern_T, hd), cfg.dtype)
    lengths = jnp.full((slots,), kern_T, jnp.int32)
    reps = cfg.n_layers

    def timed(fn):
        def loss(qq):
            def body(c, _):
                return fn(c), None

            o, _ = jax.lax.scan(body, qq, None, length=reps)
            return o

        try:
            f = jax.jit(loss)
            _fence(f(q)); _fence(f(q))
            t0 = time.perf_counter()
            n = 8
            for _ in range(n):
                o = f(q)
            _fence(o)
            return {"ms": round((time.perf_counter() - t0) / n * 1e3, 2)}
        except Exception as e:
            return {"error": f"{type(e).__name__}: {str(e)[:120]}"}

    kern = {
        "native_scan": timed(lambda a: decode_attention(
            a, kc, vc, lengths, impl="scan", block=block)),
        "repeat_reference": timed(lambda a: reference_decode_attention(
            a, kc, vc, lengths)),
    }
    if on_tpu:
        kern["native_pallas"] = timed(lambda a: decode_attention(
            a, kc, vc, lengths, impl="pallas", block=block))
    out["decode_kernel_T%d" % kern_T] = kern
    return out


def gqa_capacity_demo() -> dict:
    """Max concurrent decode slots at bench_1b4 GQA shapes: the native
    n_kv_heads cache vs a repeat-expanded (n_heads-wide) one — the HBM
    headroom the native-GQA decode kernel buys, since the repeat layout
    keeps every slot's K/V resident at n_heads width.

    The budget is DERIVED from the decode step's compiled memory plan
    (serve/capacity.py: params + fixed/per-slot temp + code from
    ``memory_analysis()``, avals only — nothing allocated), replacing the
    old ``hbm * 0.92 - params`` fragmentation guess; the formula numbers
    ride along as ``*_formula`` so the delta stays visible in BENCH json."""
    from tony_tpu.models.llama import LlamaConfig
    from tony_tpu.serve.capacity import derive_slot_budget

    import dataclasses

    cfg = dataclasses.replace(LlamaConfig.bench_1b4(), n_kv_heads=4)
    max_len = 2048
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
        hbm = int(stats.get("bytes_limit", 16 * 2**30))
    except Exception:
        hbm = 16 * 2**30
    # the superseded guess, kept visible so the measured delta is legible
    param_bytes_formula = cfg.n_params * 2  # bf16 resident weights
    budget_formula = int(hbm * 0.92) - param_bytes_formula
    per_slot_native = 2 * cfg.n_layers * max_len * cfg.n_kv_heads * cfg.head_dim * 2
    per_slot_repeat = 2 * cfg.n_layers * max_len * cfg.n_heads * cfg.head_dim * 2
    out = {
        "model": "bench_1b4_gqa16_4",
        "max_len": max_len,
        "hbm_gb": round(hbm / 2**30, 1),
        "max_slots_native_formula": max(0, budget_formula // per_slot_native),
        "max_slots_repeat_formula": max(0, budget_formula // per_slot_repeat),
    }
    try:
        # shared_prefix_tokens: the prefix-store accounting — slot budget
        # when every request carries a half-max_len shared template prefix
        # (one refcounted physical copy; each slot pays only its tail)
        # quant_kv adds the quantized decode step's own budget (int8
        # pools + scale rows, measured via the same slots=1/2 plan
        # differencing): max_slots_quant and quant_slot_ratio are the
        # capacity headline of ROADMAP item 4
        measured = derive_slot_budget(
            cfg, max_len=max_len, hbm_bytes=hbm,
            shared_prefix_tokens=max_len // 2,
            quant_kv="int8",
        )
        out.update(measured)
        out["param_gb"] = round(measured["param_bytes"] / 2**30, 2)
        if measured["max_slots_native"]:
            out["formula_vs_measured"] = round(
                out["max_slots_native_formula"] / measured["max_slots_native"],
                3,
            )
    except Exception as e:
        # derivation unavailable (platform without memory_analysis): the
        # formula numbers become the headline, labelled as such
        out.update({
            "source": "formula",
            "error": f"{type(e).__name__}: {str(e)[:160]}",
            "param_gb": round(param_bytes_formula / 2**30, 2),
            "kv_bytes_per_slot_native": per_slot_native,
            "kv_bytes_per_slot_repeat": per_slot_repeat,
            "max_slots_native": out["max_slots_native_formula"],
            "max_slots_repeat": out["max_slots_repeat_formula"],
        })
    native, repeat = out["max_slots_native"], out["max_slots_repeat"]
    out["native_vs_repeat"] = round(native / max(repeat, 1), 2)
    return out


def pipeline_bench() -> dict:
    """GPipe vs 1F1B wall-clock + bubble fraction: runs scripts/pp_bench.py
    in a subprocess on the virtual 8-CPU mesh (the pp mesh needs its own
    device count / platform, which must not disturb this process's
    backend). Results land in docs/PERF.md "Pipeline"."""
    import subprocess

    root = os.path.dirname(os.path.abspath(__file__))
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": root,
    }
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(root, "scripts", "pp_bench.py")],
            capture_output=True, text=True, timeout=850, env=env,
        )
    except subprocess.TimeoutExpired:
        return {"error": "pp_bench timed out"}
    out = {}
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                r = json.loads(line)
                out[r.pop("schedule")] = r
            except (ValueError, KeyError):
                pass
    if "gpipe" in out and "1f1b" in out and out["1f1b"]["step_ms"]:
        out["gpipe_vs_1f1b"] = round(
            out["gpipe"]["step_ms"] / out["1f1b"]["step_ms"], 3
        )
    if not out:
        out["error"] = (proc.stderr or "no output")[-300:]
    return out


def overlap_bench(cfg, batch: int, seq: int, steps: int, mu_dtype: str) -> dict:
    """fit()-driven input-pipeline benchmark. train_bench() feeds a
    pre-staged device batch (no input pipeline at all); this runs the REAL
    loop — synthetic token stream, H2D placement, metrics — with device
    prefetch off vs on, and reports the host-stall metric
    (``host_blocked_ms_per_step``: wall time the loop waits in
    next(batches)) plus fit()'s startup-phase breakdown (compile vs restore
    vs first-batch, which compile-ahead overlaps)."""
    from tony_tpu.train import DataConfig, FitConfig, fit

    out = {}
    for depth in (0, 2):
        final = fit(FitConfig(
            model=cfg,
            data=DataConfig(
                global_batch=batch, seq_len=seq, vocab_size=cfg.vocab_size,
                prefetch=depth,
            ),
            steps=steps, log_every=steps, warmup_steps=2, mu_dtype=mu_dtype,
        ))
        out[f"prefetch{depth}"] = {
            k: final[k]
            for k in (
                "tokens_per_sec_per_chip", "host_blocked_ms_per_step",
                "host_blocked_frac", "startup",
            )
            if k in final
        }
    p0 = out.get("prefetch0", {}).get("tokens_per_sec_per_chip", 0)
    p2 = out.get("prefetch2", {}).get("tokens_per_sec_per_chip", 0)
    if p0 and p2:
        out["prefetch_speedup"] = round(p2 / p0, 3)
    return out


def submit_latency_bench() -> dict:
    """AM-submit -> first-step latency (the second north-star metric,
    BASELINE.json "metric"): submit a tiny fit() job through the REAL
    client -> AM -> executor path twice into one persistent compile cache —
    the second run is the resubmit/gang-restart case, which loads cached
    executables.

    One process per chip: the worker takes whatever platform JAX gives it,
    so this section must run in a parent that has NOT initialised a JAX
    backend (chip_smoke.py is arranged that way; inside run_bench, which
    holds the chip, the worker fails to get it and the section reports the
    job's error instead of a CPU number under a device metric's name)."""
    import tempfile

    from tony_tpu.am.events import submit_latency
    from tony_tpu.cli.client import TonyClient
    from tony_tpu.config.config import TonyConfig

    tmp = tempfile.mkdtemp(prefix="tony-lat-")
    src = os.path.join(tmp, "src")
    os.makedirs(src)
    with open(os.path.join(src, "train.py"), "w") as f:
        f.write(
            "from tony_tpu.models.llama import LlamaConfig\n"
            "from tony_tpu.train import DataConfig, FitConfig, fit\n"
            "fit(FitConfig(model=LlamaConfig.tiny(),\n"
            "    data=DataConfig(global_batch=4, seq_len=64, vocab_size=256),\n"
            "    steps=3, log_every=10, warmup_steps=1))\n"
        )
    out = {}
    for run in ("cold", "warm"):
        # compile cache: utils/compile_cache.py's rule (the fixed
        # in-checkout default unless JAX_COMPILATION_CACHE_DIR is set)
        cfg = TonyConfig.load(overrides={
            "application.stage_dir": os.path.join(tmp, "apps"),
            "application.name": f"lat-{run}",
            "application.framework": "jax",
            "job.worker.instances": 1,
            "job.worker.command": "python train.py",
        })
        client = TonyClient(cfg, src_dir=src)
        code = client.run(quiet=True)
        if code != 0:
            out[run] = {"error": f"job exited {code}"}
            continue
        out[run] = submit_latency(client.app_dir)
    return out


def health_overhead_bench(steps: int = 20) -> dict:
    """Armed-vs-disarmed step-time delta for the numerics health monitors
    (obs/health.py): the same tiny train step compiled WITH the fused
    value monitors (nonfinite counts, update ratio, per-layer grad RMS,
    batch fingerprint) and WITHOUT, timed back to back. The tiny model
    deliberately OVERSTATES the relative cost — the monitors are a fixed
    set of reductions, so their fraction shrinks as the model grows; a
    regression that makes them expensive shows up here first."""
    from tony_tpu.models.llama import LlamaConfig
    from tony_tpu.parallel.mesh import MeshShape, build_mesh
    from tony_tpu.train import trainer as tr

    cfg = LlamaConfig.tiny()
    B, S = 8, 256
    mesh = build_mesh(MeshShape(dp=1))
    opt = tr.default_optimizer(warmup_steps=1, decay_steps=1000)
    inputs = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    targets = jax.random.randint(jax.random.key(2), (B, S), 0, cfg.vocab_size)

    def timed(monitors: bool) -> float:
        step = tr.make_train_step(cfg, mesh, opt, monitors=monitors)
        state = tr.make_train_state(jax.random.key(0), cfg, mesh, opt)
        for _ in range(3):  # compile + warm
            state, m = step(state, inputs, targets)
        _fence(m["loss"])
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, inputs, targets)
        _fence(m["loss"])
        return (time.perf_counter() - t0) / steps * 1e3

    disarmed_ms = timed(False)
    armed_ms = timed(True)
    return {
        "step_ms_disarmed": round(disarmed_ms, 3),
        "step_ms_armed": round(armed_ms, 3),
        "overhead_frac": round((armed_ms - disarmed_ms) / disarmed_ms, 4),
    }


def anatomy_bench(steps: int = 6) -> dict:
    """Step-anatomy microbench (obs/profile.py + obs/anatomy.py): a
    shard_map matmul+psum loop over every local device, captured under a
    real ProfileController window exactly like `tony profile` would — so
    the judged numbers (overlap_frac higher-better, exposed_collective_ms
    lower-better, achieved_gbps on the dominant collective) come from the
    same capture/report path production uses, and a regression in either
    the overlap behaviour or the anatomy plumbing shows up here."""
    import tempfile

    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from tony_tpu.obs import anatomy, comms
    from tony_tpu.obs import profile as profile_mod

    n = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()).reshape(n), ("dp",))

    def f(x, w):
        h = jnp.dot(x, w)
        return jax.lax.psum(h, "dp") if n > 1 else h

    sf = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P("dp"), P(None, None)),
        out_specs=P(),
    )) if n > 1 else jax.jit(f)
    x = jnp.ones((max(n, 1) * 64, 512), jnp.float32)
    w = jnp.ones((512, 512), jnp.float32)
    compiled = sf.lower(x, w).compile()
    ledger_rows = comms.extract_collectives(compiled)
    out_root = tempfile.mkdtemp(prefix="tony-anatomy-")
    ctl = profile_mod.ProfileController(out_root, "bench", watch=False)
    ctl.trigger(steps=steps)
    y = compiled(x, w)
    _fence(y)  # warm outside the window
    for _ in range(steps + 1):
        ctl.step(fetch_s=0.0)
        y = compiled(x, w)
        _fence(y)
    ctl.finish()
    import glob as _glob

    mpaths = _glob.glob(os.path.join(out_root, "bench", "*", "manifest.json"))
    if not mpaths:
        return {"error": "no capture manifest landed"}
    with open(mpaths[-1]) as fh:
        manifest = json.load(fh)
    rep = anatomy.proc_report(manifest, ledger_rows)
    out = {
        "devices": n,
        "steps": rep["steps"],
        "device_trace": rep["device_trace"],
        "step_ms": rep["per_step_ms"]["step_time_s"],
        "compute_ms": rep["per_step_ms"]["compute_s"],
        "exposed_collective_ms": rep["per_step_ms"]["exposed_collective_s"],
        "host_blocked_ms": rep["per_step_ms"]["host_blocked_s"],
    }
    if "overlap_frac" in rep:
        out["overlap_frac"] = rep["overlap_frac"]
    top = next(
        (r for r in rep["collectives"] if r.get("bytes") and r.get("total_s")),
        None,
    )
    if top is not None:
        out["top_collective"] = {
            "kind": top["kind"],
            "bytes": top["bytes"],
            "mean_us": top.get("mean_us", 0.0),
        }
        if "achieved_gbps" in top:
            out["top_collective"]["achieved_gbps"] = top["achieved_gbps"]
    return out


def collective_overlap_bench(cfg=None, batch: int = 8, seq: int = 64,
                             steps: int = 6, impl: str = "scan") -> dict:
    """The overlap section: one sharded train step captured through the
    real ProfileController path twice — decomposed fsdp collectives +
    bucketed dp grad reduce OFF (GSPMD's blocking weight gathers, single
    fused grad all-reduce) vs ON (ops/overlap ppermute rings +
    bucketed_psum) — so the judged numbers (exposed_collective_ms
    lower-better, overlap_frac higher-better, their off→on ratios) come
    from the same capture/report path `tony profile` uses. The ON run's
    grad-bucket budget is solved from the OFF capture's measured bandwidth
    (bucket_bytes_from_report): the anatomy report drives the knob the
    report then judges — the loop this PR closes."""
    import dataclasses
    import glob as _glob
    import tempfile

    from tony_tpu.models.llama import LlamaConfig
    from tony_tpu.obs import anatomy, comms
    from tony_tpu.obs import profile as profile_mod
    from tony_tpu.ops.overlap import bucket_bytes_from_report
    from tony_tpu.parallel.mesh import MeshShape, build_mesh, set_default_mesh
    from tony_tpu.train.trainer import (
        default_optimizer, make_train_state, make_train_step,
    )

    n = len(jax.devices())
    if n < 2:
        return {"error": "collective overlap bench needs >= 2 devices"}
    if cfg is None:
        cfg = LlamaConfig.tiny()
    # fsdp ring (weight gathers) + a dp pair (grad reduce) when devices allow:
    # the two collectives the tentpole decomposes
    dp = 2 if n >= 4 and n % 2 == 0 else 1
    mesh = build_mesh(MeshShape(dp=dp, fsdp=n // dp))
    set_default_mesh(mesh)
    opt = default_optimizer(warmup_steps=2, decay_steps=100)
    tokens = jax.random.randint(
        jax.random.key(1), (batch, seq + 1), 0, cfg.vocab_size
    )
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    def capture(variant_cfg, bucket_bytes):
        state = make_train_state(jax.random.key(0), variant_cfg, mesh, opt)
        step = make_train_step(
            variant_cfg, mesh, opt, grad_bucket_bytes=bucket_bytes
        )
        ledger_rows = []
        try:
            compiled = step.lower(state, inputs, targets).compile()
            ledger_rows = comms.extract_collectives(compiled)
            step = compiled
        except Exception:
            pass  # lazy jit fallback: ledger-less capture still reports
        out_root = tempfile.mkdtemp(prefix="tony-overlap-")
        ctl = profile_mod.ProfileController(out_root, "bench", watch=False)
        state, m = step(state, inputs, targets)  # warm outside the window
        _fence(m["loss"])
        ctl.trigger(steps=steps)
        for _ in range(steps + 1):
            ctl.step(fetch_s=0.0)
            state, m = step(state, inputs, targets)
            _fence(m["loss"])
        ctl.finish()
        mpaths = _glob.glob(
            os.path.join(out_root, "bench", "*", "manifest.json")
        )
        if not mpaths:
            return {"error": "no capture manifest landed"}
        with open(mpaths[-1]) as fh:
            manifest = json.load(fh)
        rep = anatomy.proc_report(manifest, ledger_rows)
        sec = {
            "step_ms": rep["per_step_ms"]["step_time_s"],
            "compute_ms": rep["per_step_ms"]["compute_s"],
            "exposed_collective_ms": rep["per_step_ms"]["exposed_collective_s"],
            "loss": round(float(m["loss"]), 4),
        }
        for k in ("overlap_frac", "pure_comm_steps"):
            if k in rep:
                sec[k] = rep[k]
        top = next(
            (r for r in rep["collectives"]
             if r.get("bytes") and r.get("total_s")),
            None,
        )
        if top is not None:
            sec["top_collective"] = {
                "kind": top["kind"], "bytes": top["bytes"],
            }
            if "achieved_gbps" in top:
                sec["top_collective"]["achieved_gbps"] = top["achieved_gbps"]
        return sec

    off = capture(dataclasses.replace(cfg, overlap_impl=""), None)
    if "error" in off:
        return off
    bucket_bytes = bucket_bytes_from_report(off, n_layers=cfg.n_layers)
    on = capture(
        dataclasses.replace(cfg, overlap_impl=impl),
        bucket_bytes if dp > 1 else None,
    )
    out = {
        "devices": n,
        "mesh": {"dp": dp, "fsdp": n // dp},
        "impl": impl,
        "grad_bucket_bytes": bucket_bytes,
        "off": off,
        "on": on,
    }
    if "error" not in on:
        # lift the judged keys to the section top so perf_diff's dotted
        # rules (extra.overlap.*) see them without digging into variants
        if "overlap_frac" in on:
            out["overlap_frac"] = on["overlap_frac"]
        out["exposed_collective_ms"] = on["exposed_collective_ms"]
        if off.get("exposed_collective_ms"):
            out["exposed_ratio"] = round(
                on["exposed_collective_ms"] / off["exposed_collective_ms"], 4
            )
        if off.get("step_ms"):
            out["step_ms_ratio"] = round(on["step_ms"] / off["step_ms"], 4)
        # the value-safety receipt: both variants trained on the same
        # batch/state — the decomposition is an execution schedule, not a
        # different model
        if "loss" in off and "loss" in on:
            out["loss_delta"] = round(abs(on["loss"] - off["loss"]), 6)
    return out


def elastic_bench(steps: int = 18, members: int = 2) -> dict:
    """Kill-one-member mid-run (tony_tpu/elastic/, docs/ELASTIC.md): an
    elastic fit over ``members`` device groups shrinks at steps/3 (one
    member "preempted") and grows back at 2*steps/3, under an armed
    tracer. Reports lost steps (the no-cold-restart claim: 0), the
    warm-restart seconds BOTH from the run's own journal and read off
    `tony trace` goodput's restart_s bucket (the elastic.reshard spans),
    and the steady-state step-time ratio after shrink (per-member work is
    constant, so ~1.0 is the target; the dcn2x multislice topology maps
    members onto slices the same way)."""
    import statistics
    import tempfile

    from tony_tpu.config.config import TonyConfig
    from tony_tpu.elastic.protocol import journal_files, read_journal
    from tony_tpu.models.llama import LlamaConfig
    from tony_tpu.obs import trace
    from tony_tpu.obs.trace_tool import goodput
    from tony_tpu.train import FitConfig, fit
    from tony_tpu.train.data import DataConfig

    app_dir = tempfile.mkdtemp(prefix="tony-elastic-bench-")
    trace.install_from_config(
        TonyConfig.load(overrides={"trace.sample_steps": 1}),
        app_dir, "elastic-bench", proc="bench_elastic",
    )
    shrink_at, grow_at = steps // 3, (2 * steps) // 3
    seq = 64
    data = DataConfig(global_batch=8, seq_len=seq, vocab_size=256)
    marks: list[dict] = []
    try:
        out = fit(FitConfig(
            model=LlamaConfig.tiny(),
            data=data, steps=steps, log_every=1, warmup_steps=2,
            elastic_members=members,
            elastic_plan={
                shrink_at: tuple(range(members - 1)),
                grow_at: tuple(range(members)),
            },
            elastic_dir=app_dir,
            on_metrics=lambda m: marks.append(dict(m)),
        ))
    finally:
        trace.uninstall()
    g = goodput(app_dir)
    per_member = data.global_batch // members

    def _step_time(phase_members: int, lo: int, hi: int) -> float:
        # per-step wall time from the per-boundary throughput samples:
        # tokens in the window / tokens-per-sec (batch scales with the
        # live membership)
        ts = [
            phase_members * per_member * seq / m["tokens_per_sec"]
            for m in marks
            if lo < m["step"] <= hi and m.get("tokens_per_sec")
        ]
        return statistics.median(ts) if ts else 0.0

    full = _step_time(members, 2, shrink_at)          # warmup excluded
    shrunk = _step_time(members - 1, shrink_at + 1, grow_at)
    lost = sum(
        r.get("lost_steps", 0)
        for p in journal_files(app_dir)
        for r in read_journal(p)
        if r.get("type") == "reshard"
    )
    section = {
        "members": members,
        "steps": steps,
        "reshards": out.get("elastic", {}).get("reshards", 0),
        "lost_steps": lost,
        "restart_s": out.get("elastic", {}).get("reshard_s", 0.0),
        "goodput": {
            "restart_s": g.get("restart_s", 0.0),
            "generation_changes": g.get("generation_changes", 0),
        },
    }
    if full > 0 and shrunk > 0:
        section["step_time_full_ms"] = round(full * 1e3, 2)
        section["step_time_shrunk_ms"] = round(shrunk * 1e3, 2)
        section["shrunk_step_ratio"] = round(shrunk / full, 3)
    return section


def _phased(name: str, fn) -> dict:
    """Run one bench section under its own HBM phase watermark; the
    section's dict gains an ``hbm`` key with the phase-scoped numbers
    (absent on platforms without memory_stats). Errors become the
    section's result, never the bench's."""
    with _hbm_watch().phase(name) as ph:
        try:
            out = fn()
        except Exception as e:
            out = {"error": f"{type(e).__name__}: {str(e)[:160]}"}
    if isinstance(out, dict):
        hk = ph.bench_keys()
        if hk and "hbm" not in out:
            out["hbm"] = hk
    return out


def run_bench() -> dict:
    from tony_tpu.models.llama import LlamaConfig
    from tony_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()  # the one cache-dir rule (utils/compile_cache.py)
    on_tpu = jax.devices()[0].platform != "cpu"
    if not on_tpu:  # CPU fallback so the driver always gets a line
        cfg = LlamaConfig.tiny()
        r = train_bench(cfg, batch=4, seq=64, steps=3, mu_dtype=jnp.float32,
                        label="tiny_cpu")
        extra = {"device": jax.devices()[0].device_kind, **r}
        # batch 8: fit()'s default mesh shards batch over every local
        # device (8 virtual CPU devices under the test rig)
        extra["overlap_fit"] = _phased("overlap_fit", lambda: overlap_bench(
            cfg, batch=8, seq=64, steps=6, mu_dtype="float32"
        ))
        extra["decode"] = _phased(
            "decode", lambda: decode_bench(on_tpu=False)
        )
        extra["gqa_capacity"] = _phased("gqa_capacity", gqa_capacity_demo)
        extra["health_overhead"] = _phased(
            "health_overhead", health_overhead_bench
        )
        extra["step_anatomy"] = _phased("step_anatomy", anatomy_bench)
        extra["overlap"] = _phased(
            "overlap", lambda: collective_overlap_bench(cfg, batch=8, seq=64)
        )
        # the MoE ep-combine counterpart through the same capture path
        # (tiny_moe on the virtual-device mesh; the full moe_top2 sweep is
        # TPU-only, but the overlap capture itself must run everywhere)
        extra["moe_top2"] = _phased("moe_top2", lambda: {
            "overlap": moe_overlap_bench(
                LlamaConfig.tiny_moe(), batch=8, seq=64, steps=6, impl="scan"
            ),
        })
        extra["elastic"] = _phased("elastic", elastic_bench)
        return {
            "metric": "llama_tiny_cpu_tokens_per_sec",
            "value": r["tokens_per_sec_per_chip"],
            "unit": "tokens/s/chip",
            "vs_baseline": round(r["mfu"] / 0.45, 4),
            "extra": extra,
        }

    cfg = LlamaConfig.bench_1b4(
        attention_impl="flash", remat_policy="save_attn_kernel",
        ce_impl="scan",  # fused chunked CE: frees the ~2.1GB logits+dlogits
        # transient that made batch 8 OOM at round 3 (docs/PERF.md)
    )
    try:
        main = train_bench(cfg, batch=8, seq=2048, steps=10,
                           mu_dtype=jnp.bfloat16, label="dense_1b4_b8")
        batch_note = "batch 8 (fused CE freed the loss-head transient)"
    except Exception as e:
        # never lose the headline metric to an OOM regression: fall back to
        # the round-3 batch and record why
        main = train_bench(cfg, batch=4, seq=2048, steps=10,
                           mu_dtype=jnp.bfloat16, label="dense_1b4_b4")
        batch_note = f"batch 8 failed ({type(e).__name__}: {str(e)[:120]}); ran batch 4"

    extra = {
        "device": jax.devices()[0].device_kind,
        "n_params": cfg.n_params,
        "remat_policy": cfg.remat_policy,
        "mu_dtype": "bfloat16",
        "ce_impl": cfg.ce_impl,
        "batch_note": batch_note,
        "note": (
            "1.35B is the largest dense config fitting one v5e (16GB HBM) "
            "with AdamW state; llama2_7b needs >56GB and is a multi-chip "
            "config (see dryrun_multichip)"
        ),
        **main,
    }
    try:
        extra["flash_matches_dot_on_tpu"] = flash_matches_dot_on_tpu()
    except Exception as e:
        extra["flash_matches_dot_on_tpu"] = f"{type(e).__name__}: {str(e)[:120]}"
    try:
        extra["fused_ce_matches_dense_on_tpu"] = fused_ce_matches_dense_on_tpu()
    except Exception as e:
        extra["fused_ce_matches_dense_on_tpu"] = f"{type(e).__name__}: {str(e)[:120]}"
    # every section under its own phase watermark: the HBM numbers in each
    # section are scoped to it, never inherited from an earlier one
    extra["ce_head_b8"] = _phased("ce_head_b8", ce_head_bench)
    extra["attn_kernel_s8192"] = _phased("attn_kernel_s8192", kernel_bench_s8192)
    extra["gqa_kernel_32_8"] = _phased("gqa_kernel_32_8", gqa_kernel_bench)
    extra["flash_s32768"] = _phased("flash_s32768", long_context_bench)
    extra["moe_top2"] = _phased("moe_top2", moe_bench)

    def _overlap():
        # same 1.35B config through the REAL input pipeline, prefetch off/on;
        # lifts the stall metric + startup phases to top-level extra keys so
        # the BENCH trajectory tracks them
        # reuse whatever batch the headline run proved fits (8, or the
        # batch-4 fallback) so an OOM can't erase the stall metrics
        return overlap_bench(
            cfg, batch=main["batch"], seq=2048, steps=10, mu_dtype="bfloat16"
        )

    overlap = extra["overlap_fit"] = _phased("overlap_fit", _overlap)
    p2 = overlap.get("prefetch2", {})
    if "host_blocked_ms_per_step" in p2:
        extra["host_blocked_ms_per_step"] = p2["host_blocked_ms_per_step"]
    if "startup" in p2:
        extra["startup_phases"] = p2["startup"]
    # serving: continuous batching vs sequential batch-1 + TTFT + slot
    # occupancy (the decode counterpart of the training headline)
    extra["decode"] = _phased("decode", lambda: decode_bench(on_tpu=True))
    extra["gqa_capacity"] = _phased("gqa_capacity", gqa_capacity_demo)
    extra["health_overhead"] = _phased("health_overhead", health_overhead_bench)
    extra["step_anatomy"] = _phased("step_anatomy", anatomy_bench)
    # decomposed collectives + bucketed grad reduce, off vs on, through the
    # real capture path ('pallas' = the TPU per-chunk kernel form)
    extra["overlap"] = _phased("overlap", lambda: collective_overlap_bench(
        cfg, batch=main["batch"], seq=2048, steps=6, impl="pallas"
    ))
    extra["elastic"] = _phased("elastic", elastic_bench)
    extra["pipeline"] = _phased("pipeline", pipeline_bench)
    extra["submit_to_first_step_s"] = _phased(
        "submit_to_first_step_s", submit_latency_bench
    )

    return {
        "metric": "llama1.4b_train_tokens_per_sec_per_chip",
        "value": main["tokens_per_sec_per_chip"],
        "unit": "tokens/s/chip",
        "vs_baseline": round(main["mfu"] / 0.45, 4),
        "extra": extra,
    }


if __name__ == "__main__":
    try:
        result = run_bench()
    except Exception as e:  # never leave the driver without a line
        result = {
            "metric": "bench_error",
            "value": 0,
            "unit": "tokens/s/chip",
            "vs_baseline": 0.0,
            "extra": {"error": f"{type(e).__name__}: {e}"},
        }
    print(json.dumps(result))
    # canonical on-disk artifact for `tony perf diff <old> <new>` (the
    # cross-run regression gate, obs/perf_diff.py): BENCH_REPORT overrides
    # the destination; failure to write never fails the bench
    try:
        with open(os.environ.get("BENCH_REPORT", "bench_report.json"), "w") as f:
            json.dump(result, f)
    except OSError:
        pass
    sys.exit(0)
