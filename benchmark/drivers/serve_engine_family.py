"""Driver for cells whose entry is the serving ``Engine`` in this process and
whose model is NOT the dense decoder ``serve_engine.py`` is written for: the
same loop, window, trace and ``observed`` keys (see that file's docstring for
the set-up and the loop), with everything that depends on the architecture —
sizes, seeded weights, the program's model object, the plain reference, the
planted faults, the expert counters — taken from
``benchmark/families/<config["family"]>.py``. The next architecture adds a
family file, not another copy of this loop.

One thing the loop adds: a closed-loop mix may count its pre-roll in engine
steps (``preroll_steps``) instead of seconds (``preroll_s``). With no think
time the schedule moves by steps, so the window then opens at the same place
of the schedule in every run, whatever the first steps took.

``ctx['extra']``: ``control`` (also run the reference at the configuration's
control precision, into ``notes``), ``controls`` (a list of further precisions
to run it at, into ``notes``), ``faults`` (a list of the family's planted
faults to run into ``notes``), ``flips`` (a list of precisions: how often the
reference rounded to each routes a token otherwise than the float32 one, into
``notes``), ``fault`` (ONE fault that decides ``correct``: ``token_altered``
alters a served token; a family fault replaces the reference by the faulty
one).
"""

from __future__ import annotations

import gc
import math
import os
import time

import numpy as np

from benchmark.drivers.serve_engine import _percentile, _sample


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
    from benchmark.run import load_module

    tracing.keep_every_program()
    cache = tracing.count_cache_events()
    fam = load_module(os.path.join(ctx["bench_dir"], "families", config["family"] + ".py"))
    s = fam.sizes_of(config)
    dtype = jnp.dtype(config["dtype"])
    serve = dict(config["serve"])
    if "prefill_buckets" in serve:
        serve["prefill_buckets"] = tuple(serve["prefill_buckets"])
    try:
        from tony_tpu.serve.engine import Engine, Request, ServeConfig

        model = fam.model(s, serve, dtype)
    except ImportError as e:
        raise ctx["refuse"](4, f"the program cannot run family {config['family']!r}: {e}")
    key = weights.base_key(ctx["seed"])
    params = jax.jit(lambda k: fam.make_params(k, s, dtype))(key)
    engine = Engine(params, model, ServeConfig(**serve))
    vocab = fam.vocab(s)
    sizes = trafficgen.request_sizes(mix)
    prompts = trafficgen.Prompts(mix, ctx["seed"], vocab)
    if float(mix["sampling"]["temperature"]) != 0.0:
        raise ctx["refuse"](2, "the comparison with the reference needs greedy requests")

    # --- warm-up: every prefill bucket the mix reaches, the largest pool and table
    rng = np.random.default_rng([ctx["seed"], 6])
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    buckets = engine.serve.prefill_buckets
    reached = [b for i, b in enumerate(buckets) if b >= lo and (i == 0 or buckets[i - 1] < hi)]
    # prompts cannot pass the largest bucket, so the pool grows to its largest
    # under ``slots`` prompts of that length, and the decode step's table under
    # one request that decodes as far as the mix's longest sequence reaches
    longest = min(max(buckets), serve["max_len"] - 16)
    warm_lens = [min(b, longest) for b in reached]
    warm_lens += [longest] * max(0, serve["slots"] - len(warm_lens))
    for n in warm_lens:
        engine.submit(Request(prompt=rng.integers(0, vocab, size=n).astype(np.int32),
                              max_new_tokens=8))
    engine.run()
    reach = max(p + o for p, o in sizes)
    new = 8
    while engine.attended_positions < reach and longest + new < reach:
        new = min(2 * new, reach - longest)
        engine.submit(Request(prompt=rng.integers(0, vocab, size=longest).astype(np.int32),
                              max_new_tokens=new))
        engine.run()
    # a rehearsal of every size the mix sends, two tokens each (serve_engine.py says why)
    for plen, _ in sizes:
        engine.submit(Request(prompt=rng.integers(0, vocab, size=plen).astype(np.int32),
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
    preroll_steps, steps = int(mix.get("preroll_steps", 0)), 0
    if preroll_steps and not closed:
        raise ctx["refuse"](2, "preroll_steps is for a closed loop: arrivals come by the clock")
    tail = float(mix["trace_seconds"]) if trace_dir else 0.0
    t_begin = time.perf_counter()
    t_open = math.inf if preroll_steps else t_begin + preroll
    t_close, t_end = t_open + seconds, t_open + seconds + tail
    if closed:
        cuts = trafficgen.head_start(mix, int(mix["clients"]))
        for c in range(int(mix["clients"])):
            submit_next(c, t_begin, float(cuts[c]))
        due: list[tuple[float, int]] = []
    else:
        sends = list(t_begin + trafficgen.arrivals(mix, ctx["seed"], t_end - t_begin))
        late: list[float] = []
    traced_lens: list[list[int]] = []
    compiles_open = at_close = family_close = family_trace0 = family_trace1 = None
    programs_open = 0
    annotate = jax.profiler.TraceAnnotation
    while True:
        now = time.perf_counter()
        if t_open == math.inf and steps >= preroll_steps:
            t_open, t_close, t_end = now, now + seconds, now + seconds + tail
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
                "jax_programs_in_window": sum(cache.values()) - programs_open,
            }
            family_close = fam.counters(m)
            if trace_dir:
                tracing.start(trace_dir)
                t_trace.update(on=True, t0=now)
                family_trace0 = fam.counters(m)
        if t_trace["on"] and now >= t_end:
            tracing.stop()
            t_trace.update(on=False, t1=now)
            family_trace1 = fam.counters(engine.metrics)
        if closed:
            while due and due[0][0] <= now:
                submit_next(due.pop(0)[1], now)
        else:
            while sends and sends[0] <= now:
                late.append(now - sends[0])
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
        steps += 1
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
        family_trace1 = fam.counters(engine.metrics)

    # --- the window's numbers: everything that happened in [t_open, t_close)
    in_window = [r for r in reqs.values() if t_open <= r["t_submit"] < t_close]
    failed = sum(1 for r in in_window if r["tokens"] is None or len(r["tokens"]) != r["olen"])
    ttft = [(r["tok_t"][0] - r["t_submit"]) * 1e3 for r in in_window if r["tok_t"]]
    gaps, visible, prefill_lens, decode_ctx, traced_prefills = [], 0, [], [], []
    for r in reqs.values():
        for j, t in enumerate(r["tok_t"]):
            if j == 0 and t_trace["t0"] is not None and t_trace["t0"] <= t < t_trace["t1"]:
                traced_prefills.append(r["plen"])
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
        "traced_prefill_lens": traced_prefills,
        "family": {"window": family_close, "trace0": family_trace0, "trace1": family_trace1},
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
    if fault == "token_altered" and sample:
        sample[0]["tokens"][len(sample[0]["tokens"]) // 2] ^= 1
    engine.close()
    for a in jax.tree.leaves((params, engine.cache)):
        a.delete()
    del engine, params
    gc.collect()
    t0 = time.time()
    numbers, notes = reference_numbers(ctx, fam, s, dtype, key, sample)
    notes.update(reference_s=time.time() - t0,
                 engine=observed["engine"], ttft_ms=observed["ttft_ms"],
                 itl_ms=observed["itl_ms"], gaps_in_window=len(gaps),
                 checked_tokens=sum(len(r["tokens"]) for r in sample),
                 family=family_close)
    return {
        "e2e": e2e, "observed": observed, "device": device, "attempted": len(in_window),
        "failed": failed, "numbers": numbers, "trace_dir": trace_dir, "notes": notes,
    }


def reference_numbers(ctx: dict, fam, s: dict, wdtype, key, sample: list[dict]) -> tuple[dict, dict]:
    """``served_logit_gap``: the widest gap by which a served token's logit
    lies below the reference's best, over the teacher-forced full forward of
    the checked requests (every sequence padded to the engine's ``max_len``:
    causal, so the padding cannot reach a position before it)."""
    from benchmark import trafficgen

    if not sample:
        return {}, {"reference": "no finished request to check"}
    extra = ctx["extra"]
    T = int(ctx["config"]["serve"]["max_len"])
    rows = trafficgen.law_max(ctx["mix"]["output_len"])
    if any(r["plen"] - 1 + rows > T for r in sample):
        raise ctx["refuse"](2, "prompt_len.max + output_len.max exceeds the engine's max_len")
    seqs = []
    for r in sample:
        ids = np.concatenate([r["prompt"], np.asarray(r["tokens"][:-1], np.int32)])
        seqs.append(np.pad(ids, (0, T - len(ids))))
    starts = [r["plen"] - 1 for r in sample]

    def logits(cast_dtype=None, fault=""):
        out = fam.reference_logits(key, s, wdtype, seqs, starts, rows, cast_dtype, fault)
        return [lg[: len(r["tokens"])] for lg, r in zip(out, sample)]

    def gaps_of(lgs, tokens):
        """How far below the row's best each of ``tokens`` lies in ``lgs``:
        the widest gap (the number the limit is on), and the mean, the 99th
        percentile and the share of tokens that are not the row's best."""
        gaps = np.concatenate([lg.max(axis=-1) - lg[np.arange(len(t)), t]
                               for lg, t in zip(lgs, tokens)])
        return {"served_logit_gap": float(gaps.max()),
                "served_logit_gap_mean": float(gaps.mean()),
                "served_logit_gap_p99": float(np.quantile(gaps, 0.99)),
                "served_not_best_share": float((gaps > 0).mean())}

    served = [np.asarray(r["tokens"]) for r in sample]
    fault = extra.get("fault", "")
    sound = logits()
    judge = logits(fault=fault) if fault in fam.FAULTS else sound
    notes: dict = {}
    top2 = [np.sort(lg, axis=-1)[:, -2:] for lg in sound]
    notes["reference_top1_top2_gap_min"] = min(float((t[:, 1] - t[:, 0]).min()) for t in top2)

    def control_gap(dtype):
        """The tokens the reference at ``dtype`` would have served, judged by
        the reference itself."""
        return gaps_of(sound, [lc.argmax(axis=-1) for lc in logits(cast_dtype=dtype)])

    if extra.get("control"):
        notes["control"] = control_gap(ctx["config"]["precision"]["control"])
    for dtype in extra.get("controls", []):
        notes.setdefault("controls", {})[dtype] = control_gap(dtype)
    for f in extra.get("faults", []):
        notes.setdefault("faults", {})[f] = gaps_of(logits(fault=f), served)
    for dtype in extra.get("flips", []):
        notes.setdefault("routing_flips", {})[dtype] = fam.routing_flips(
            key, s, wdtype, seqs, [r["plen"] + len(r["tokens"]) - 1 for r in sample], dtype)
    notes["served"] = numbers = gaps_of(judge, served)
    return numbers, notes
