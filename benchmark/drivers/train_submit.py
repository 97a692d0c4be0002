"""Driver for cells whose entry is `tony submit`: TonyClient.run() on the local
backend -> ApplicationMaster -> executor -> worker ``benchmark/jobs/fit_job.py``
-> ``fit()``. This process creates no JAX backend until the job has ended
(one process per chip); then it runs the plain reference here, on the freed
chip, and compares.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import sys
import time


def _tail(app_dir: str, n: int = 40) -> str:
    out = []
    for base, _, files in os.walk(app_dir):
        for name in files:
            if name.endswith(".log") or name in ("stdout", "stderr"):
                with open(os.path.join(base, name), errors="replace") as f:
                    out.append(f"--- {name} ---\n" + "".join(f.readlines()[-n:]))
    return "\n".join(out)[-6000:]


def run(ctx: dict) -> dict:
    from benchmark import trafficgen, weights
    from tony_tpu.am.events import submit_latency
    from tony_tpu.cli.client import TonyClient
    from tony_tpu.config.config import TonyConfig

    cell, config, mix, work = ctx["cell"], ctx["config"], ctx["mix"], ctx["work"]
    s = weights.sizes_of(config)
    job_dir = os.path.join(work, "job")
    os.makedirs(job_dir)
    shutil.copy(os.path.join(ctx["bench_dir"], "jobs", "fit_job.py"), job_dir)
    tokens = trafficgen.train_tokens(mix, ctx["seed"], s["v"])
    tokens.tofile(os.path.join(job_dir, "tokens.bin"))
    trace_dir = os.path.join(work, "trace") if ctx["trace"] else ""
    plan = {
        "config": config, "mix": mix, "seed": ctx["seed"], "seconds": ctx["seconds"],
        "chips": cell["chips"], "trace_dir": trace_dir, "fault": ctx["extra"].get("fault", ""),
        "require": {"platform": "tpu", "kinds": sorted(ctx["peaks"])} if ctx["require_chip"] else None,
    }
    plan_path, report_path = os.path.join(work, "plan.json"), os.path.join(work, "report.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    tony = TonyConfig.load(overrides={
        "application.stage_dir": os.path.join(work, "apps"),
        "application.name": "bench-" + cell["name"],
        "application.framework": "jax",
        "application.timeout_s": 1500,
        "job.worker.instances": 1,
        "job.worker.tpu_chips": cell["chips"],
        "job.worker.command": (
            f"{sys.executable} fit_job.py --root {ctx['root']} "
            f"--plan {plan_path} --report {report_path}"
        ),
    })
    client = TonyClient(tony, src_dir=job_dir)
    code = client.run(quiet=True)
    report = None
    if os.path.exists(report_path):
        with open(report_path) as f:
            report = json.load(f)
    if report is not None and report.get("refused"):
        raise ctx["refuse"](3, report["refused"])
    if code != 0 or report is None or "bounds" not in report:
        raise ctx["refuse"](1, f"job {client.app_id} ended with exit {code}\n" + _tail(client.app_dir))
    latency = submit_latency(client.app_dir)

    # --- the window: log boundaries from its start to the last one inside it
    start, end = report["window_start"], report["window_end"]
    inside = [b for b in report["bounds"] if start <= b["t"] <= end]
    if len(inside) < 2:
        raise ctx["refuse"](1, f"window of {ctx['seconds']} s holds no whole log window")
    steps = inside[-1]["step"] - inside[0]["step"]
    span = inside[-1]["t"] - inside[0]["t"]
    chips = report["n_devices"]
    e2e = {
        "setup_s": start - ctx["t_start"],
        "train_tokens_per_s_chip": steps * report["tokens_per_step"] / span / chips,
    }
    windows_ms = [
        (b["t"] - a["t"]) / (b["step"] - a["step"]) * 1e3 for a, b in zip(inside, inside[1:])
    ]
    observed = {
        "submit_latency": latency, "step_ms_windows": windows_ms, "cache": report["cache"],
        "tokens_per_s_chip": e2e["train_tokens_per_s_chip"],
        "seq_len": mix["seq_len"], "global_batch": mix["global_batch"], "chips": chips,
    }
    device = {**report["device"], "memory_peak_bytes": report["memory_peak_bytes"]}

    # --- the reference, on the chip the job has freed
    t0 = time.time()
    numbers, notes = reference_numbers(ctx, report["probe"], tokens)
    # for PERF.md: how long the reference took, and what the program itself
    # logs as mfu (its count includes the embedding gather)
    notes.update(reference_s=time.time() - t0, bytes_limit=report["bytes_limit"],
                 program_mfu_p50=statistics.median(b["program_mfu"] or 0.0 for b in inside[1:]),
                 program_tokens_per_s_chip_p50=statistics.median(
                     b["program_tokens_per_sec_per_chip"] or 0.0 for b in inside[1:]),
                 cache_before_window=report["cache_before_window"])
    return {
        "e2e": e2e, "observed": observed, "device": device, "attempted": steps,
        "failed": 0, "numbers": numbers, "trace_dir": trace_dir, "notes": notes,
    }


def reference_numbers(ctx: dict, probe: dict, tokens) -> tuple[dict, dict]:
    """Follow the first steps with the plain reference and compare. With
    ``extra = {"control": 1}`` (limit-setting runs) also the lower-precision
    control and the half-batch fault, put in the program's place."""
    import jax
    import jax.numpy as jnp

    from benchmark import compare, tracing, weights
    from benchmark.reference import dense_decoder as ref
    from benchmark.reference import train_steps

    tracing.keep_every_program()
    config, mix = ctx["config"], ctx["mix"]
    if int(mix["checked_updates"]) != 2:
        raise ctx["refuse"](2, "the reference follows exactly two updates")
    s = weights.sizes_of(config)
    dtype = jnp.dtype(config["dtype"])
    opt = config["train"]["optimizer"]
    key = weights.base_key(ctx["seed"])
    # the key is an argument: closed over, it would be a constant of each program,
    # and every new seed would compile them again
    make_layer = jax.jit(lambda key, l: weights.make_layer(key, s, dtype, l))
    tops = {n: jax.jit(lambda key, n=n: weights.make_leaf(key, n, s, dtype))
            for n in weights.TOP_LEAVES}

    def follow(cast=ref.identity, rows=None):
        return train_steps.follow(
            s, opt, dtype, lambda l: make_layer(key, jnp.int32(l)), lambda n: tops[n](key),
            tokens[:3], cast=cast, rows=rows,
        )

    reference = follow()
    numbers = compare.train_numbers(probe, reference)
    notes = {"losses_program": probe["losses"], "losses_reference": reference["losses"],
             "clip": reference["clip"],
             "worst_leaf": {k: compare.norm_gap(probe[k], reference[k])[1]
                            for k in ("grad1_leaf_norms", "delta_leaf_norms")}}
    if ctx["extra"].get("control"):
        control = follow(cast=ref.rounded_to(jnp.dtype(config["precision"]["control"])))
        notes["control"] = compare.train_numbers(control, reference)
        half = follow(rows=slice(0, tokens.shape[1] // 2))
        notes["fault_half_batch"] = compare.train_numbers(half, reference)
    return numbers, notes
