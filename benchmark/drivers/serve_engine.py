"""Driver for cells whose entry is the serving ``Engine`` in this process:
``submit()``, ``step()``, ``completion_of()``. One thread drives the engine
and plays every client, closed loop (``clients`` callers that each send their
next request when their last one finished, after ``think_s``) or open loop
(send times fixed by the mix, whatever the engine does).

Set-up: weights from --seed in one jitted call, the engine, one warm-up
request for every prefill bucket the mix's prompts reach plus enough longest
ones to grow the block pool and the decode step's table to their largest, a
two-token rehearsal of every size the mix sends, then ``preroll_s`` seconds of the loop itself so that the window opens on a steady
state (the first ``clients`` requests get a share of their output length
that the mix fixes, as if caught mid-answer). Then the window; then the engine
is drained, memory read, the engine's state freed, and the plain reference run
over a sample of what was served.
"""

from __future__ import annotations

import gc
import math
import os
import time

import numpy as np

def _percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile of all the values (no interpolation)."""
    v = sorted(values)
    return v[min(len(v) - 1, max(0, math.ceil(q * len(v)) - 1))]


def run(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp

    cell, config, mix = ctx["cell"], ctx["config"], ctx["mix"]
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if ctx["require_chip"] and (device["platform"] != "tpu" or device["kind"] not in ctx["peaks"]
                                or device["count"] < cell["chips"]):
        raise ctx["refuse"](3, f"cell needs {cell['chips']} TPU chip(s) of a kind in "
                               f"benchmark/peaks.json; JAX sees {device}")
    from benchmark import tracing, trafficgen, weights

    tracing.keep_every_program()
    cache = tracing.count_cache_events()
    from tony_tpu.models.llama import LlamaConfig
    from tony_tpu.serve.engine import Engine, Request, ServeConfig

    s = weights.sizes_of(config)
    dtype = jnp.dtype(config["dtype"])
    serve = config["serve"]
    key = weights.base_key(ctx["seed"])
    params = jax.jit(lambda k: weights.make_params(k, s, dtype))(key)
    model = LlamaConfig(
        vocab_size=s["v"], dim=s["d"], n_layers=s["layers"], n_heads=s["h"],
        n_kv_heads=s["kv"], ffn_dim=s["f"], max_seq_len=serve["max_len"],
        rope_theta=s["theta"], norm_eps=s["eps"], dtype=dtype,
    )
    if model.head_dim != s["hd"]:
        raise ctx["refuse"](2, "head_dim is not dim / n_heads: the program cannot run it")
    engine = Engine(params, model, ServeConfig(**serve))
    sizes = trafficgen.request_sizes(mix)
    prompts = trafficgen.Prompts(mix, ctx["seed"], s["v"])
    greedy = float(mix["sampling"]["temperature"]) == 0.0
    if not greedy:
        raise ctx["refuse"](2, "the comparison with the reference needs greedy requests")

    # --- warm-up: every prefill bucket the mix reaches, the largest pool and table
    rng = np.random.default_rng([ctx["seed"], 6])
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    buckets = engine.serve.prefill_buckets
    reached = [b for i, b in enumerate(buckets) if b >= lo and (i == 0 or buckets[i - 1] < hi)]
    longest = serve["max_len"] - 16
    warm_lens = [min(b, longest) for b in reached]
    warm_lens += [longest] * max(0, serve["slots"] - len(warm_lens))
    for n in warm_lens:
        engine.submit(Request(prompt=rng.integers(0, s["v"], size=n).astype(np.int32),
                              max_new_tokens=8))
    engine.run()
    # a rehearsal of every size the mix sends, two tokens each: what the engine
    # builds at first use beyond its prefill and decode programs (admission
    # into a pool that the warm-up has filled) is built here. Without it the
    # loop's first step took 5.7 s in a run that compiles and 1.3 s in one
    # that does not, the window opened on another part of the schedule, and
    # the compiling run read 7% fewer tokens/s (PR 24)
    for plen, _ in sizes:
        engine.submit(Request(prompt=rng.integers(0, s["v"], size=plen).astype(np.int32),
                              max_new_tokens=2))
    engine.run()

    # --- the loop
    t_trace = {"on": False, "t0": None, "t1": None}
    trace_dir = os.path.join(ctx["work"], "trace") if ctx["trace"] else ""
    reqs: dict[int, dict] = {}
    active: list[int] = []
    state = {"next": 0}
    fault = ctx["extra"].get("fault", "")

    def submit_next(client: int, now: float, cut: float = 1.0) -> None:
        i = state["next"]
        state["next"] += 1
        plen, olen = sizes[i % len(sizes)]
        olen = max(2, int(round(olen * cut)))
        prompt = prompts.make(i, plen)
        rid = engine.submit(Request(prompt=prompt, max_new_tokens=olen, temperature=0.0,
                                    eos_id=None))
        reqs[rid] = {"i": i, "client": client, "t_submit": now, "plen": plen, "olen": olen,
                     "tok_t": [], "prompt": prompt, "tokens": None}
        active.append(rid)

    closed = mix["loop"] == "closed"
    preroll, seconds = float(mix.get("preroll_s", 4.0)), ctx["seconds"]
    t_begin = time.perf_counter()
    t_open = t_begin + preroll      # the window opens
    t_close = t_open + seconds      # and closes
    # a traced run goes on past the window for the trace's seconds, under the
    # same load: the profiler's own stalls (starting, and writing its file
    # when it stops) then fall outside what the window's numbers are read from
    t_end = t_close + (float(mix["trace_seconds"]) if trace_dir else 0.0)
    if closed:
        cuts = trafficgen.head_start(mix, int(mix["clients"]))
        for c in range(int(mix["clients"])):
            submit_next(c, t_begin, float(cuts[c]))
        due: list[tuple[float, int]] = []  # (time, client) after think time
    else:
        sends = list(t_begin + trafficgen.arrivals(mix, ctx["seed"], t_end - t_begin))
        late: list[float] = []
    traced_lens: list[list[int]] = []
    compiles_open = at_close = None
    programs_open = 0
    annotate = jax.profiler.TraceAnnotation
    while True:
        now = time.perf_counter()
        if compiles_open is None and now >= t_open:
            engine.reset_metrics()
            compiles_open = engine.metrics.decode_compiles + engine.metrics.prefill_compiles
            programs_open = sum(cache.values())
        if at_close is None and now >= t_close:
            m = engine.metrics
            at_close = {
                "decode_step_ms_mean": 1e3 * m.decode_s / max(m.decode_steps, 1),
                "slot_occupancy": 100.0 * m.occupancy_sum / max(m.decode_steps, 1),
                "compiles_in_window": (m.decode_compiles + m.prefill_compiles) - compiles_open,
                "prefill_s": m.prefill_s, "decode_s": m.decode_s, "decode_steps": m.decode_steps,
                # programs JAX built or loaded in the window, whoever asked for them
                "jax_programs_in_window": sum(cache.values()) - programs_open,
            }
            if trace_dir:
                tracing.start(trace_dir)
                t_trace.update(on=True, t0=now)
        if t_trace["on"] and now >= t_end:
            tracing.stop()
            t_trace.update(on=False, t1=now)
        if closed:
            while due and due[0][0] <= now:
                submit_next(due.pop(0)[1], now)
        else:
            while sends and sends[0] <= now:
                late.append(now - sends[0])
                # an open loop's request is timed from when it was due
                submit_next(-1, sends.pop(0))
        if not active and now >= t_end:
            break
        if not active:
            time.sleep(0.0005)
            continue
        if t_trace["on"]:
            traced_lens.append([reqs[r]["plen"] + len(reqs[r]["tok_t"]) for r in active
                                if reqs[r]["tok_t"]])
        with annotate("bench.engine.step"):
            engine.step()
        now = time.perf_counter()
        for rid in list(active):
            comp = engine.completion_of(rid)
            if comp is None:
                continue
            r = reqs[rid]
            r["tok_t"].extend([now] * (len(comp.tokens) - len(r["tok_t"])))
            if comp.finish_reason:
                r["tokens"] = [int(t) for t in comp.tokens]
                r["t_done"] = now
                engine.take_completion(rid)
                active.remove(rid)
                if closed and now < t_end:
                    if float(mix.get("think_s", 0.0)) > 0:
                        due.append((now + float(mix["think_s"]), r["client"]))
                    else:
                        submit_next(r["client"], now)
    if t_trace["on"]:
        tracing.stop()

    # --- the window's numbers: everything that happened in [t_open, t_close)
    in_window = [r for r in reqs.values() if t_open <= r["t_submit"] < t_close]
    failed = sum(1 for r in in_window if r["tokens"] is None or len(r["tokens"]) != r["olen"])
    ttft = [(r["tok_t"][0] - r["t_submit"]) * 1e3 for r in in_window if r["tok_t"]]
    gaps, visible, prefill_lens, decode_ctx = [], 0, [], []
    for r in reqs.values():
        for j, t in enumerate(r["tok_t"]):
            if not (t_open <= t < t_close):
                continue
            visible += 1
            if j == 0:
                prefill_lens.append(r["plen"])
            else:
                gaps.append((t - r["tok_t"][j - 1]) * 1e3)
                decode_ctx.append(r["plen"] + j)
    if not ttft or not gaps:
        raise ctx["refuse"](1, "the window finished no request")
    e2e = {
        "setup_s": (time.time() - time.perf_counter() + t_open) - ctx["t_start"],
        "serve_tokens_per_s": visible / seconds,
        "ttft_p75_ms": _percentile(ttft, 0.75),
        "itl_p95_ms": _percentile(gaps, 0.95),
    }
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    device["memory_peak_bytes"] = max((x.get("peak_bytes_in_use", 0) for x in stats), default=0)
    observed = {
        "engine": at_close,
        "cache": dict(cache),
        "prefill_lens": prefill_lens, "decode_ctx": decode_ctx, "window_s": seconds,
        "traced_decode_lens": [x for x in traced_lens if x],
        "ttft_ms": {"mean": sum(ttft) / len(ttft),
                    **{f"p{q}": _percentile(ttft, q / 100) for q in (50, 75, 80, 90, 95)}},
        "itl_ms": {f"p{q}": _percentile(gaps, q / 100) for q in (50, 90, 95, 99)},
        "gaps_in_window": len(gaps),
    }
    if not closed:
        observed["generator_late_ms_max"] = 1e3 * max(late, default=0.0)

    # --- free the engine's state, then the reference over a sample of what was served
    done = [r for r in in_window if r["tokens"] is not None and len(r["tokens"]) == r["olen"]]
    sample = _sample(done, int(mix["checked_requests"]), ctx["seed"])
    if fault == "token_altered":
        sample[0]["tokens"][len(sample[0]["tokens"]) // 2] ^= 1
    engine.close()
    for a in jax.tree.leaves((params, engine.cache)):
        a.delete()
    del engine, params
    gc.collect()
    t0 = time.time()
    numbers, notes = reference_numbers(ctx, s, dtype, key, sample)
    notes.update(reference_s=time.time() - t0,
                 engine=observed["engine"], ttft_ms=observed["ttft_ms"],
                 itl_ms=observed["itl_ms"], gaps_in_window=len(gaps),
                 checked_tokens=sum(len(r["tokens"]) for r in sample))
    return {
        "e2e": e2e, "observed": observed, "device": device, "attempted": len(in_window),
        "failed": failed, "numbers": numbers, "trace_dir": trace_dir, "notes": notes,
    }


def _sample(done: list[dict], n: int, seed: int) -> list[dict]:
    """``n`` finished requests drawn from the seed, the longest among them."""
    if not done:
        return []
    longest = max(done, key=lambda r: r["plen"] + r["olen"])
    rest = [r for r in done if r is not longest]
    pick = np.random.default_rng([seed, 8]).permutation(len(rest))[: max(n - 1, 0)]
    return [longest] + [rest[i] for i in pick]


def reference_numbers(ctx: dict, s: dict, dtype, key, sample: list[dict]) -> tuple[dict, dict]:
    """Teacher-forced full forward pass of the plain reference over each
    sampled prompt with its served tokens, layer by layer (each layer's
    weights made from the seed just before use). The number: the widest gap
    by which a served token's logit lies below the reference's best."""
    import jax
    import jax.numpy as jnp

    from benchmark import trafficgen, weights
    from benchmark.reference import dense_decoder as ref

    if not sample:
        return {}, {"reference": "no finished request to check"}
    control = ctx["extra"].get("control")
    casts = {"reference": ref.identity}
    if control:
        casts["control"] = ref.rounded_to(jnp.dtype(ctx["config"]["precision"]["control"]))
    # every sampled sequence padded to ONE length (causal: the padding cannot
    # reach a position before it), so the reference is two programs that the
    # first run compiles and every later run finds in the cache
    T = int(ctx["config"]["serve"]["max_len"])
    seqs = []
    for r in sample:
        ids = np.concatenate([r["prompt"], np.asarray(r["tokens"][:-1], np.int32)])
        seqs.append(np.pad(ids, (0, T - len(ids))))
    # one head program too: as many rows as the mix's longest output, from
    # each prompt's last position (the mix's own clips leave room for them)
    rows = trafficgen.law_max(ctx["mix"]["output_len"])
    if any(r["plen"] - 1 + rows > T for r in sample):
        raise ctx["refuse"](2, "prompt_len.max + output_len.max exceeds the engine's max_len")
    # the key is an argument: closed over, it would be a constant of the program,
    # and every new seed would compile the program again
    make_layer = jax.jit(lambda key, l: weights.make_layer(key, s, dtype, l))
    tok_emb = weights.make_leaf(key, "tok_emb", s, dtype)
    final_norm = weights.make_leaf(key, "final_norm", s, dtype)
    lm_head = weights.make_leaf(key, "lm_head", s, dtype)
    logits = {}
    for name, cast in casts.items():
        layer = jax.jit(lambda lp, x, cast=cast: ref.layer(x, lp, s, cast))
        # the served positions only: ``rows`` of them from the prompt's last
        head = jax.jit(lambda fn, lm, x, start, cast=cast: ref.logits(
            jax.lax.dynamic_slice_in_dim(x, start, rows), fn, lm, s, cast))
        xs = [ref.embed(tok_emb, jnp.asarray(ids)) for ids in seqs]
        for l in range(s["layers"]):
            lp = make_layer(key, jnp.int32(l))
            xs = [layer(lp, x) for x in xs]
        logits[name] = [
            np.asarray(head(final_norm, lm_head, x, jnp.int32(r["plen"] - 1)))[: len(r["tokens"])]
            for x, r in zip(xs, sample)
        ]
        del xs
    best = [lg.max(axis=-1) for lg in logits["reference"]]
    served_gap = max(
        float((b - lg[np.arange(len(r["tokens"])), r["tokens"]]).max())
        for b, lg, r in zip(best, logits["reference"], sample)
    )
    notes = {}
    if control:
        notes["control"] = {"served_logit_gap": max(
            float((b - lg[np.arange(len(lg)), lc.argmax(axis=-1)]).max())
            for b, lg, lc in zip(best, logits["reference"], logits["control"])
        )}
        top2 = [np.sort(lg, axis=-1)[:, -2:] for lg in logits["reference"]]
        notes["reference_top1_top2_gap_min"] = min(float((t[:, 1] - t[:, 0]).min()) for t in top2)
    return {"served_logit_gap": served_gap}, notes
