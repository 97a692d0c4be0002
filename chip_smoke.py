#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that tony-tpu still starts on the chip.

Drives the two user-facing hot paths through their normal entry points on ONE
TPU chip, at the full width of models the repo supports (depth cut, weights
random from a seed), and checks what comes out by the repo's own means:

  probe     a child asks JAX what it sees (fails fast off-chip)
  submit    TonyClient.run() -> AM -> executor -> examples/chip_smoke/fit_job.py
            -> fit() at Llama-2-7B widths, twice into one compile-cache dir
            (the second must load its executables from the cache)
  serve     `python -m tony_tpu.cli serve --demo 8` (AM -> gang host ->
            Engine -> frontend) on the bench_1b4 preset
  engine    in this process, after every child has exited: an Engine at
            Llama-3-8B widths vs models.generate.generate(), scan vs pallas
  kernels   each Pallas kernel vs its plain jnp reference, compiled form
            asserted (`tpu_custom_call`), so a silent CPU backend cannot pass

`--chips 4` runs instead — and only — the sharded-training path and what it
is compared with: the same job on a mesh of jax.devices()[:1], then through
`tony submit` with one worker holding four chips (fsdp=4).

One process per chip: the parent initialises no JAX backend until the child
phases are over. Any phase that fails makes the script exit non-zero; on
success the LAST stdout line is
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
and every other reading goes on earlier lines. Exit codes: 0 ok, 1 a phase
failed, 3 every phase passed but JAX found no TPU (only `--tiny`, the CPU
rehearsal of the control flow, can get that far off-chip).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".chip_smoke")  # app dirs + logs; in .gitignore
JOB_DIR = os.path.join(ROOT, "examples", "chip_smoke")
MARK = "SMOKE_JSON "


def say(phase: str, **kv) -> None:
    print(json.dumps({"phase": phase, **kv}), flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def parent_backend_untouched() -> bool:
    """True while this process has created no JAX backend (importing jax is
    harmless; the first jax.devices()/array/jit call is what takes the chip)."""
    if "jax" not in sys.modules:
        return True
    from jax._src import xla_bridge

    return not xla_bridge.backends_are_initialized()


def child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


# --- phase: probe --------------------------------------------------------------


def phase_probe(expect: str, count: int) -> None:
    code = (
        "import jax, json; d = jax.devices(); print(json.dumps({'platform': "
        "d[0].platform, 'kind': d[0].device_kind, 'count': len(d)}))"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=child_env(), capture_output=True,
        text=True, timeout=300,
    )
    check(out.returncode == 0, f"probe child failed: {out.stderr[-2000:]}")
    seen = json.loads(out.stdout.strip().splitlines()[-1])
    say("probe", **seen)
    check(seen["platform"] == expect,
          f"JAX came up on {seen['platform']!r}, not {expect!r}")
    check(seen["count"] >= count,
          f"need {count} device(s), JAX sees {seen['count']}")


# --- phase: submit -> fit() ----------------------------------------------------


def _marked_json(lines, where: str) -> dict:
    """The job script's one ``SMOKE_JSON {...}`` line among ``lines``."""
    for line in lines:
        if MARK in line:
            return json.loads(line.split(MARK, 1)[1])
    raise AssertionError(f"no {MARK.strip()} line in {where}")


def _worker_json(app_dir: str) -> dict:
    logs = os.path.join(app_dir, "logs")
    lines = []
    for name in sorted(os.listdir(logs)):
        with open(os.path.join(logs, name), errors="replace") as f:
            lines += f.readlines()
    return _marked_json(lines, logs)


def _history_device(app_dir: str) -> dict | None:
    """The device identity a task pushed into the job history with its first
    metrics sample (obs.metrics.device_samples), or None."""
    from tony_tpu.am.events import EventType, _find_history_file, read_history
    from tony_tpu.obs.metrics import parse_device_samples

    for e in read_history(_find_history_file(app_dir)):
        if e["type"] == EventType.METRICS:
            device = parse_device_samples(e.get("samples", {}))
            if device:
                return device
    return None


def _tail_logs(app_dir: str, n: int = 60) -> str:
    out = []
    for base, _, files in os.walk(app_dir):
        for name in files:
            if name.endswith(".log"):
                with open(os.path.join(base, name), errors="replace") as f:
                    out.append(f"--- {name} ---\n" + "".join(f.readlines()[-n:]))
    return "\n".join(out)


def submit_fit(name: str, *, chips: int, job_args: str, expect: str) -> dict:
    """One `tony submit` of the committed job script on the local backend:
    one worker, tpu_chips = chips, NO JAX_PLATFORMS override in the job env.
    Returns the worker's own report plus the history's latency breakdown."""
    from tony_tpu.am.events import submit_latency
    from tony_tpu.cli.client import TonyClient
    from tony_tpu.config.config import TonyConfig

    cfg = TonyConfig.load(overrides={
        "application.stage_dir": os.path.join(WORK, "apps"),
        "application.name": f"smoke-{name}",
        "application.framework": "jax",
        "application.timeout_s": 900,
        "job.worker.instances": 1,
        "job.worker.tpu_chips": chips,
        "job.worker.command": f"{sys.executable} fit_job.py {job_args}",
    })
    client = TonyClient(cfg, src_dir=JOB_DIR)
    code = client.run(quiet=True)
    with open(os.path.join(client.app_dir, "status.json")) as f:
        status = json.load(f)
    if code != 0 or status["state"] != "SUCCEEDED":
        raise AssertionError(
            f"job {client.app_id} ended {status['state']} exit {code}\n"
            + _tail_logs(client.app_dir)
        )
    latency = submit_latency(client.app_dir)  # raises without a step METRICS event
    worker = _worker_json(client.app_dir)
    pushed = _history_device(client.app_dir)
    say(f"submit.{name}", app_id=client.app_id, state=status["state"],
        exit_code=code, worker=worker, history_device=pushed,
        smoke_reading_submit_latency=latency)
    check(worker["platform"] == expect,
          f"worker came up on {worker['platform']!r}, not {expect!r}")
    check(pushed is not None and pushed["platform"] == expect,
          f"job history names device {pushed}, expected platform {expect!r}")
    check(worker["device_count"] >= chips, f"worker saw {worker['device_count']} devices")
    losses = worker["losses"]
    check(len(losses) >= 6, f"expected >= 6 steps, got losses {losses}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss in {losses}")
    ln_v = math.log(worker["model"]["vocab_size"])
    check(abs(losses[0] - ln_v) < 1.0,
          f"step-1 loss {losses[0]} not within 1.0 of ln V = {ln_v:.2f}")
    if expect == "tpu":
        check(worker["tpu_custom_call"],
              "the lowered train step holds no tpu_custom_call (interpreted kernel)")
    return {"worker": worker, "latency": latency}


def phase_submit(tiny: bool, expect: str) -> None:
    job_args = "--tiny --layers 2 --seq-len 64" if tiny else "--layers 3"
    runs = [
        submit_fit(run, chips=1, job_args=job_args, expect=expect)
        for run in ("run1", "run2")
    ]
    if not tiny:
        m = runs[0]["worker"]["model"]
        check((m["dim"], m["n_heads"], m["head_dim"], m["ffn_dim"], m["vocab_size"])
              == (4096, 32, 128, 11008, 32000), f"not Llama-2-7B widths: {m}")
    first, second = (r["worker"] for r in runs)
    say("submit.cache", cache_dir_rule="JAX_COMPILATION_CACHE_DIR, else "
        "train.jax_cache_dir, else <checkout>/.jax_cache",
        run1={"hits": first["cache_hits"], "misses": first["cache_misses"],
              "compile_s": (first["startup"] or {}).get("compile_s")},
        run2={"hits": second["cache_hits"], "misses": second["cache_misses"],
              "compile_s": (second["startup"] or {}).get("compile_s")})
    check(second["cache_hits"] >= 1,
          "second submit loaded nothing from the persistent compile cache "
          f"(JAX cache-hit events: {second['cache_hits']})")


# --- phase: tony serve ---------------------------------------------------------


def phase_serve(tiny: bool, expect: str) -> None:
    import re

    n, new_tokens = 8, 8 if tiny else 32
    stage = os.path.join(WORK, "serve_apps")
    before = set(os.listdir(stage)) if os.path.isdir(stage) else set()
    cmd = [
        sys.executable, "-m", "tony_tpu.cli", "serve", "--demo", str(n),
        "--max-new-tokens", str(new_tokens),
        "-D", f"application.stage_dir={stage}",
        "-D", "application.name=smoke-serve",
        "-D", "serve.gang.hosts=1",
        "-D", f"serve.gang.model={'tiny' if tiny else 'bench_1b4'}",
        "-D", "serve.gang.slots=8",
        "-D", "job.decode.tpu_chips=1",
    ]
    t0 = time.monotonic()
    out = subprocess.run(
        cmd, env=child_env(), capture_output=True, text=True, timeout=900,
    )
    app_ids = sorted(set(os.listdir(stage)) - before) if os.path.isdir(stage) else []
    app_dir = os.path.join(stage, app_ids[-1]) if app_ids else ""
    if out.returncode != 0:
        raise AssertionError(
            f"tony serve exited {out.returncode}\n{out.stdout[-2000:]}\n"
            f"{out.stderr[-3000:]}\n" + (_tail_logs(app_dir) if app_dir else "")
        )
    rows = re.findall(
        r"^\s+(r\d+): (\d+) tokens \((\w+), ttft ([0-9.naninf]+)s, hosts (\S+)\)",
        out.stdout, re.M,
    )
    completions = [
        {"rid": r, "tokens": int(t), "finish": why, "ttft_s": float(ttft),
         "hosts": hosts}
        for r, t, why, ttft, hosts in rows
    ]
    pushed = _history_device(app_dir)
    say("serve", app_id=app_ids[-1], wall_s=round(time.monotonic() - t0, 1),
        completions=completions, gang_host_device=pushed)
    check(len(completions) == n, f"{len(completions)} of {n} completions:\n{out.stdout}")
    for c in completions:
        check(c["tokens"] == new_tokens and c["finish"] == "length",
              f"{c['rid']} returned {c['tokens']} tokens ({c['finish']}), "
              f"asked {new_tokens}")
        check(math.isfinite(c["ttft_s"]) and c["ttft_s"] >= 0, f"TTFT {c}")
    check(pushed is not None and pushed["platform"] == expect,
          f"gang host reported device {pushed}, expected platform {expect!r}")


# --- phase: in-process engine parity ------------------------------------------


def _near_tie(params, cfg, prompt, a, b) -> dict:
    """Where two greedy streams part: the plain forward pass's logits at that
    position say whether the two candidates are a numerical tie."""
    import jax.numpy as jnp
    import numpy as np

    from tony_tpu.models.llama import forward

    i = next(j for j, (x, y) in enumerate(zip(a, b)) if x != y)
    ctx = np.concatenate([np.asarray(prompt), np.asarray(a[:i], np.int32)])
    logits = np.asarray(
        forward(params, jnp.asarray(ctx[None], jnp.int32), cfg)[0, -1], np.float32
    )
    la, lb = float(logits[a[i]]), float(logits[b[i]])
    # one bfloat16 unit in the last place at these logits' magnitude
    ulp = 2.0 ** (math.floor(math.log2(max(abs(la), abs(lb), 1e-6))) - 7)
    return {"at": i, "tokens": [int(a[i]), int(b[i])],
            "logits": [round(la, 5), round(lb, 5)], "bf16_ulp": ulp,
            "below_top": round(float(logits.max()) - min(la, lb), 5)}


def _same_or_tie(what: str, params, cfg, prompt, a, b) -> dict:
    """Greedy streams must be equal. bf16 matmuls of different shapes may
    round a tie the other way, and so may the paged attention's two forms
    (the scan rescales its sums every block, the kernel every 8 blocks);
    such a split is accepted only when the plain forward pass rates both
    tokens within two bfloat16 units in the last place of its best logit
    (at random weights three tokens may tie there, so "the top two" is too
    narrow), and is printed, never silent. Tokens after the split are not
    compared."""
    if list(a) == list(b):
        return {"equal": True, "tokens": len(a)}
    tie = _near_tie(params, cfg, prompt, list(a), list(b))
    check(tie["below_top"] <= 2 * tie["bf16_ulp"],
          f"{what}: streams differ and it is no bf16 tie: {tie}")
    return {"equal": False, "equal_tokens": tie["at"], "bf16_tie": tie}


@contextlib.contextmanager
def paged_kernel(on: bool):
    """The paged decode attention through its kernel (interpreted off the
    chip) or through the XLA scan for the block's length: the op chooses from
    the platform (ops/decode_attention.py ``_run_kernel``), so a comparison
    of its two forms steers that name, as the tests do, and puts it back."""
    from tony_tpu.ops import decode_attention as da

    was = da._run_kernel
    da._run_kernel = lambda: on
    try:
        yield
    finally:
        da._run_kernel = was


def phase_engine(tiny: bool, seed: int) -> None:
    from dataclasses import replace

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tony_tpu.models.generate import generate
    from tony_tpu.models.llama import LlamaConfig, init_params
    from tony_tpu.serve.engine import Engine, Request, ServeConfig

    if tiny:
        cfg, plens, new, max_len = LlamaConfig.tiny(), (5, 12, 30, 44, 9), 8, 64
    else:
        # Llama-3-8B published widths, depth cut 32 -> 8 (2.8 B params, 5.6 GB)
        cfg = replace(LlamaConfig.llama3_8b(), n_layers=8)
        plens, new, max_len = (100, 400, 900, 1500, 250), 64, 2048
    params = init_params(jax.random.key(seed), cfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, size=p).astype(np.int32) for p in plens]
    streams: dict[str, list[list[int]]] = {}
    # two forms of the decode step at real width: the XLA scan over the whole
    # table, and the paged kernel the chip runs. ``decode_impl`` reaches the
    # attention no more, so the form is steered where the op chooses
    for impl in ("scan", "pallas"):
        with paged_kernel(impl == "pallas"):
            eng = Engine(params, cfg, ServeConfig(
                slots=8, max_len=max_len, decode_impl=impl,
            ))
            t0 = time.perf_counter()
            ids = [eng.submit(Request(prompt=p, max_new_tokens=new)) for p in prompts]
            done = eng.run()
        streams[impl] = [list(done[i].tokens) for i in ids]
        summary = eng.close()
        say(f"engine.{impl}", n_params=cfg.n_params, requests=len(ids),
            prompt_lens=list(plens), new_tokens=new,
            wall_s_incl_compile=round(time.perf_counter() - t0, 2),
            ttft_s=[round(done[i].ttft_s, 3) for i in ids],
            decode_compiles=summary.get("decode_compiles"),
            prefill_compiles=summary.get("prefill_compiles"))
        for i in ids:
            check(len(done[i].tokens) == new and math.isfinite(done[i].ttft_s),
                  f"request {i}: {len(done[i].tokens)} tokens, ttft {done[i].ttft_s}")
    verdicts = {}
    for r in (0, 1):  # engine vs generate() for two requests
        ref = np.asarray(generate(
            params, jnp.asarray(prompts[r][None]), cfg, max_new_tokens=new,
        ))[0, plens[r]:].tolist()
        verdicts[f"engine_vs_generate[{plens[r]}]"] = _same_or_tie(
            f"engine vs generate(), prompt {plens[r]}", params, cfg,
            prompts[r], streams["scan"][r], ref,
        )
    for r, p in enumerate(plens):  # scan vs pallas for all
        verdicts[f"scan_vs_pallas[{p}]"] = _same_or_tie(
            f"scan vs pallas, prompt {p}", params, cfg, prompts[r],
            streams["scan"][r], streams["pallas"][r],
        )
    say("engine.parity", **verdicts)


# --- phase: kernels vs their plain references ----------------------------------


def _close(name: str, got, want, tol: float) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    check(got.shape == want.shape, f"{name}: shape {got.shape} vs {want.shape}")
    check(bool(np.isfinite(got).all()), f"{name}: non-finite values")
    err = float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-6))
    check(err < tol, f"{name}: max rel err {err:.4g} >= {tol}")
    return round(err, 5)


def phase_kernels(tiny: bool, expect: str) -> None:
    import jax
    import jax.numpy as jnp

    from tony_tpu.models.llama import dot_attention
    from tony_tpu.ops.attention import flash_attention
    from tony_tpu.ops.decode_attention import (
        decode_attention, reference_decode_attention,
    )
    from tony_tpu.ops.fused_ce import fused_ce_tokens, reference_ce_tokens
    from tony_tpu.ops.grouped_mm import grouped_matmul
    from tony_tpu.ops.quant_mm import quant_matmul, quantize_weights

    bf16, f32 = jnp.bfloat16, jnp.float32
    keys = iter(jax.random.split(jax.random.key(11), 64))

    def rnd(shape, dtype=bf16):
        return jax.random.normal(next(keys), shape, f32).astype(dtype)

    def run(name, fn, *args):
        """jit, assert the compiled (Mosaic) form on the chip, execute."""
        jitted = jax.jit(fn)
        if expect == "tpu":
            check("tpu_custom_call" in jitted.lower(*args).as_text(),
                  f"{name}: no tpu_custom_call in the lowered program")
        return jax.block_until_ready(jitted(*args))

    errs: dict[str, float] = {}
    # flash attention fwd + grads vs plain dot attention, MHA and GQA
    B, S, H, D = (1, 128, 4, 32) if tiny else (2, 2048, 32, 128)
    blk = 64 if tiny else 1024
    for tag, hkv in (("mha", H), ("gqa", H // 4)):
        q, k, v = rnd((B, S, H, D)), rnd((B, S, hkv, D)), rnd((B, S, hkv, D))

        def ref(q, k, v, rep=H // hkv):
            return dot_attention(q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2))

        def flash(q, k, v):
            return flash_attention(q, k, v, block_q=blk, block_k=blk)

        def grads(f):
            return lambda *a: jax.grad(
                lambda *b: (f(*b).astype(f32) ** 2).sum(), argnums=(0, 1, 2))(*a)

        errs[f"flash_fwd[{tag}]"] = _close(
            f"flash fwd {tag}", run("flash", flash, q, k, v), jax.jit(ref)(q, k, v), 2e-2)
        got, want = run("flash bwd", grads(flash), q, k, v), jax.jit(grads(ref))(q, k, v)
        for n, g, w in zip("qkv", got, want):
            errs[f"flash_d{n}[{tag}]"] = _close(f"flash d{n} {tag}", g, w, 4e-2)
    # decode kernel: contiguous, paged, paged int8; G=1 and G=5. The paged
    # form picks its kernel from the platform; the rehearsal off the chip asks
    # for it too (interpreted), as the tests do
    def decode_kernels(tag, Hd, Hkv, queries):
        Bd, hd, T, blkd = (2, 32, 128, 16) if tiny else (8, 128, 4096, 64)
        m = T // blkd
        kc, vc = rnd((Bd, Hkv, T, hd)), rnd((Bd, Hkv, T, hd))
        lengths = jnp.asarray(
            [T - 1 - 37 * i % (T // 2) for i in range(Bd)], jnp.int32)
        # the same cache as a shuffled physical-block pool (block 0 = scratch)
        perm = jax.random.permutation(next(keys), Bd * m) + 1
        tables = perm.reshape(Bd, m).astype(jnp.int32)

        def to_pool(c):
            blocks = c.reshape(Bd, Hkv, m, blkd, hd).transpose(0, 2, 1, 3, 4).reshape(
                Bd * m, Hkv, blkd, hd)
            return jnp.zeros((1 + Bd * m, Hkv, blkd, hd), c.dtype).at[perm].set(blocks)

        kp, vp = to_pool(kc), to_pool(vc)

        def quant(pool):
            sc = jnp.abs(pool.astype(f32)).max(axis=(2, 3)) / 127.0       # [P, Hkv]
            qp = jnp.round(pool.astype(f32) / jnp.maximum(sc, 1e-30)[:, :, None, None])
            return qp.astype(jnp.int8), sc

        (kq, ksc), (vq, vsc) = quant(kp), quant(vp)

        def dequant_cache(qp, sc):
            pool = (qp.astype(f32) * sc[:, :, None, None]).astype(bf16)
            return pool[tables].transpose(0, 2, 1, 3, 4).reshape(Bd, Hkv, T, hd)

        for G in queries:
            q = rnd((Bd, G, Hd, hd))
            want = jax.jit(reference_decode_attention)(q, kc, vc, lengths)
            errs[f"decode_contiguous{tag}[G{G}]"] = _close(
                f"decode contiguous{tag} G{G}",
                run("decode", lambda q, k, v, ln: decode_attention(
                    q, k, v, ln, impl="pallas", block=blkd), q, kc, vc, lengths),
                want, 2e-2)
            errs[f"decode_paged{tag}[G{G}]"] = _close(
                f"decode paged{tag} G{G}",
                run("paged", lambda q, k, v, ln, tb: decode_attention(
                    q, k, v, ln, tables=tb), q, kp, vp, lengths, tables),
                want, 2e-2)
            want_q = jax.jit(reference_decode_attention)(
                q, dequant_cache(kq, ksc), dequant_cache(vq, vsc), lengths)
            errs[f"decode_paged_int8{tag}[G{G}]"] = _close(
                f"decode paged int8{tag} G{G}",
                run("paged int8", lambda q, k, v, ln, tb, ks, vs: decode_attention(
                    q, k, v, ln, tables=tb, k_scale=ks, v_scale=vs),
                    q, kq, vq, lengths, tables, ksc, vsc),
                want_q, 2e-2)

    with paged_kernel(True):
        # GQA 32:8, then one query row a kv head at llama2_7b's 32 heads: the
        # 512 KB tiles at which the paged kernel takes 4 blocks a step, not 8
        decode_kernels("", *((4, 2) if tiny else (32, 8)), (1, 5))
        decode_kernels("_mha", *((4, 4) if tiny else (32, 32)), (1,))
    # fused CE (pallas) value + grads vs the dense logsumexp reference
    for dim in ((64,) if tiny else (2048, 4096)):
        Bc, Sc, V = (2, 64, 256) if tiny else (2, 2048, 32000)
        h, w = rnd((Bc, Sc, dim)), rnd((dim, V)) * (dim ** -0.5)
        w = w.astype(bf16)
        t = jax.random.randint(next(keys), (Bc, Sc), 0, V)

        def vg(f):
            return lambda h, w: jax.value_and_grad(
                lambda h, w: f(h, w, t).mean(), argnums=(0, 1))(h, w)

        (lg, (dhg, dwg)) = run(
            "fused ce", vg(lambda h, w, t: fused_ce_tokens(h, w, t, impl="pallas")), h, w)
        (lw, (dhw, dww)) = jax.jit(vg(reference_ce_tokens))(h, w)
        errs[f"ce_loss[D{dim}]"] = _close(f"ce loss D{dim}", lg, lw, 1e-2)
        errs[f"ce_dh[D{dim}]"] = _close(f"ce dh D{dim}", dhg, dhw, 4e-2)
        errs[f"ce_dw[D{dim}]"] = _close(f"ce dw D{dim}", dwg, dww, 4e-2)
    # grouped GEMM vs per-tile einsum
    N, Dg, F, Gn, blkg = (256, 64, 128, 4, 32) if tiny else (8192, 1024, 2816, 8, 128)
    x, wg = rnd((N, Dg)), rnd((Gn, Dg, F)) * (Dg ** -0.5)
    wg = wg.astype(bf16)
    tg = jnp.sort(jax.random.randint(next(keys), (N // blkg,), 0, Gn)).astype(jnp.int32)
    want = jax.jit(lambda x, w, tg: jnp.einsum(
        "tbd,tdf->tbf", x.reshape(-1, blkg, Dg), w[tg],
        preferred_element_type=f32).reshape(N, F))(x, wg, tg)
    errs["grouped_mm"] = _close(
        "grouped matmul",
        run("gmm", lambda x, w, tg: grouped_matmul(x, w, tg, impl="pallas"), x, wg, tg),
        want, 2e-2)
    # int8 weight-only matmul vs dequantize-then-matmul
    Dq, Nq = (64, 256) if tiny else (4096, 14336)
    xq, wq_full = rnd((8, Dq)), rnd((Dq, Nq)) * (Dq ** -0.5)
    wq, sc = quantize_weights(wq_full.astype(bf16))
    want = jax.jit(lambda x, wq, sc: jnp.dot(
        x, (wq.astype(f32) * sc).astype(bf16), preferred_element_type=f32))(xq, wq, sc)
    errs["int8_mm"] = _close(
        "int8 matmul",
        run("qmm", lambda x, wq, sc: quant_matmul(x, wq, sc, impl="pallas"), xq, wq, sc),
        want, 2e-2)
    say("kernels", compiled_form_asserted=expect == "tpu", max_rel_err=errs)


# --- --chips 4: sharded training vs one device ---------------------------------


def phase_four_chips(tiny: bool, expect: str) -> None:
    n = 4
    job_args = (
        "--tiny --layers 2 --seq-len 64 --global-batch 8" if tiny
        else "--layers 3 --global-batch 8"
    )
    # (1) the comparison: the same job script, mesh over jax.devices()[:1]
    out = subprocess.run(
        [sys.executable, os.path.join(JOB_DIR, "fit_job.py"), "--one-device",
         *job_args.split()],
        env=child_env(), capture_output=True, text=True, timeout=900,
    )
    check(out.returncode == 0, f"one-device run failed:\n{out.stdout[-2000:]}\n{out.stderr[-4000:]}")
    one = _marked_json(out.stdout.splitlines(), "the one-device run's stdout")
    say("four.one_device", worker=one)
    check(one["platform"] == expect and one["device_count"] >= n,
          f"one-device run saw {one['platform']} x {one['device_count']}")
    # (2) a new process, after the first has exited: tony submit, one worker
    # holding four chips, fit()'s default fsdp-first mesh
    four = submit_fit("four", chips=n, job_args=job_args, expect=expect)["worker"]
    check(four["mesh"] == {"fsdp": n}, f"default mesh is {four['mesh']}, not fsdp={n}")
    dl = [abs(a - b) for a, b in zip(one["losses"][:5], four["losses"][:5])]
    check(len(dl) == 5 and max(dl) < 0.05,
          f"losses part: one device {one['losses'][:5]} vs fsdp=4 {four['losses'][:5]}")
    say("four.compare", max_loss_gap=round(max(dl), 5),
        one_device={"planned_bytes": one["planned_bytes"], "memory": one["memory"][:1]},
        fsdp4={"planned_bytes": four["planned_bytes"], "memory": four["memory"]},
        topology_mesh=four["topology_mesh"], all_gathers=four["all_gathers"])
    # parameters + optimizer state are the step's arguments: each fsdp shard
    # plans a quarter of one device's ...
    arg1, arg4 = one["planned_bytes"]["argument"], four["planned_bytes"]["argument"]
    check(0.22 < arg4 / arg1 < 0.30,
          f"fsdp=4 plans {arg4} argument bytes per device vs {arg1} on one")
    if expect == "tpu":
        # ... and each of the four devices really holds it (sampled at step 3,
        # train state alive, the next step's transients in flight): at least
        # its share of the state, at most state + planned transients, and
        # nowhere near the one-device run's footprint
        used = [d["bytes_in_use"] for d in four["memory"]]
        check(len(used) == n and all(used), f"bytes_in_use: {four['memory']}")
        top = arg4 + four["planned_bytes"]["temp"]
        check(all(0.9 * arg4 < u < 1.1 * top for u in used),
              f"per-device bytes_in_use {used} outside [{arg4}, {top}]")
        check(max(used) < 0.5 * one["memory"][0]["bytes_in_use"],
              f"a device holds {max(used)} bytes, one-device run held "
              f"{one['memory'][0]['bytes_in_use']}")
    check(four["all_gathers"] > 0, "the compiled fsdp=4 step holds no all-gather")
    if expect == "tpu":
        check(four["topology_mesh"],
              "the mesh is not mesh_utils.create_device_mesh's topology layout")


# --- main ----------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4: run only the sharded-training path and its comparison")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tiny", action="store_true",
                   help="CPU rehearsal of the control flow at test sizes; "
                        "cannot print the ok line")
    args = p.parse_args(argv)
    expect = "cpu" if args.tiny else "tpu"
    os.makedirs(WORK, exist_ok=True)

    phase_probe(expect, args.chips)
    if args.chips == 4:
        phase_four_chips(args.tiny, expect)
    else:
        phase_submit(args.tiny, expect)
        phase_serve(args.tiny, expect)
    # every child has exited; only now may this process take the chip
    untouched = parent_backend_untouched()
    say("parent", backend_untouched_during_child_phases=untouched)
    check(untouched, "the parent initialised a JAX backend before its children were done")
    if args.chips == 1:
        from tony_tpu.utils.compile_cache import enable_compile_cache

        say("parent.cache", dir=enable_compile_cache())
        phase_engine(args.tiny, args.seed)
        phase_kernels(args.tiny, expect)

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"all phases passed, but JAX found no TPU: {device}", file=sys.stderr)
        return 3
    check(device["count"] == args.chips,
          f"--chips {args.chips} but JAX reports {device['count']} devices")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def _stop_unfinished_jobs() -> None:
    """After a failed phase: ask the AM of every job this run staged that
    never reached a terminal status to stop, so no gang host or worker is
    left holding the chip."""
    for root in ("apps", "serve_apps"):
        base = os.path.join(WORK, root)
        for app in os.listdir(base) if os.path.isdir(base) else ():
            app_dir = os.path.join(base, app)
            if not os.path.exists(os.path.join(app_dir, "status.json")):
                subprocess.run(
                    [sys.executable, "-m", "tony_tpu.cli", "stop", app_dir],
                    env=child_env(), capture_output=True, timeout=30,
                )


def run(argv: list[str] | None = None) -> int:
    """main() with the exit-code contract: any phase that raises -> 1, and
    no result line."""
    try:
        return main(argv)
    except Exception as e:
        import traceback

        traceback.print_exc()
        print(f"chip_smoke FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        try:
            _stop_unfinished_jobs()
        except (OSError, subprocess.SubprocessError):
            traceback.print_exc()
        return 1


if __name__ == "__main__":
    sys.exit(run())
