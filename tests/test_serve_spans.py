"""The serving engine on the profiler's clock: ``serve.*`` annotations inside
``Engine.step`` (through ``obs/profiler.annotate``) and a stable name on every
program the engine runs.

One tiny engine serves a few requests under ``benchmark/tracing.start``
(``jax.profiler.start_trace`` with the Python tracer off) and the tests read
the ``.xplane.pb`` back with ``jax.profiler.ProfileData``: every phase name is
there, they nest as the reader of the trace (benchmark/host_spans.py) takes
them to, there is one ``serve.step`` per counted decode step, and the tokens
are what an untraced engine serves.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import tracing
from benchmark.trace_reduce import HOST_SPAN
from tony_tpu.models import llama
from tony_tpu.models.generate import generate
from tony_tpu.obs import trace
from tony_tpu.serve import Engine, Request, ServeConfig
from tony_tpu.serve import engine as engine_mod

# prompt length, new tokens: four requests through two slots, so slots churn
SIZES = [(3, 5), (7, 4), (12, 6), (5, 3)]
PHASES = {
    "serve.admit", "serve.prefill", "serve.prefill_chunk", "serve.activate",
    "serve.plan", "serve.step", "serve.dispatch", "serve.sync", "serve.emit",
}
# one marker a decode step, named by why it ran as it did, and one a request
STEP_MARKERS = {"serve.ahead", "serve.kept_finish", "serve.kept_admit", "serve.kept_spec",
                "serve.kept_chunk", "serve.fresh"}
MARKERS = STEP_MARKERS | {"serve.visible"}


@pytest.fixture(scope="module")
def model():
    cfg = llama.LlamaConfig.tiny()
    return cfg, llama.init_params(jax.random.key(0), cfg)


def _requests(cfg, sizes=SIZES):
    rng = np.random.default_rng(0)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                    max_new_tokens=m) for n, m in sizes]


def _serve(model, serve_cfg, sizes=SIZES):
    cfg, params = model
    eng = Engine(params, cfg, serve_cfg)
    rids = [eng.submit(r) for r in _requests(cfg, sizes)]
    done = eng.run()
    return eng, [done[rid].tokens for rid in rids]


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory, host_trace_events):
    """{"events": [(name, start_ns, end_ns)] of the ``serve.*`` host events,
    "programs": names of the jitted calls, "tokens", "chunk_tokens",
    "decode_steps", "steps_ahead"} of one plain and one chunked-prefill
    engine, both run with the profiler on."""
    plain = ServeConfig(slots=2, max_len=32, kv_block=8)
    chunked = ServeConfig(slots=2, max_len=32, kv_block=8, chunk_tokens=8)
    _serve(model, plain)  # every program built before the trace starts
    _serve(model, chunked, [(20, 3)])
    log_dir = str(tmp_path_factory.mktemp("serve_trace"))
    tracing.start(log_dir)  # the benchmark's options: Python tracer off, no HLO dump
    try:
        eng, tokens = _serve(model, plain)
        ceng, chunk_tokens = _serve(model, chunked, [(20, 3)])
    finally:
        tracing.stop()
    found = host_trace_events(log_dir)
    return {"events": [e for e in found if e[0].startswith("serve.") and e[0] not in MARKERS],
            "markers": [e for e in found if e[0] in MARKERS], "log_dir": log_dir,
            "kept": {why: eng.metrics.steps_kept[why] + ceng.metrics.steps_kept[why]
                     for why in eng.metrics.steps_kept},
            "fresh": eng.metrics.steps_fresh + ceng.metrics.steps_fresh,
            "programs": {e[0] for e in found if e[0].startswith("PjitFunction(")},
            "tokens": tokens, "chunk_tokens": chunk_tokens,
            "decode_steps": eng.metrics.decode_steps + ceng.metrics.decode_steps,
            "steps_ahead": eng.metrics.steps_ahead + ceng.metrics.steps_ahead}


def _program(event_name):
    """``serve_decode`` of ``PjitFunction(jit(serve_decode))``."""
    return event_name[len("PjitFunction("):-1].removeprefix("jit(").rstrip(")")


def _within(event, events, parent):
    _, s, e = event
    return any(ps <= s and e <= pe for n, ps, pe in events if n == parent)


def _inside(events, child, parent):
    return all(_within(ev, events, parent) for ev in events if ev[0] == child)


def test_every_phase_of_the_step_is_annotated(traced):
    names = {n for n, _, _ in traced["events"]}
    assert names == PHASES
    # the reducer keeps a host event only if its name fits this pattern
    assert all(HOST_SPAN.match(n) for n in names)


def _arguments(log_dir):
    """{event name: [its arguments as a dict, one an event]} of the ``serve.*``
    host events: this jaxlib gives an annotation's keyword arguments as the
    event's ``stats`` and leaves the name bare."""
    import glob
    import os

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serve."):
                        found.setdefault(e.name, []).append(dict(e.stats))
    return found


def test_the_phase_names_take_no_argument_and_the_markers_enclose_none(traced):
    """What reads the capture today (``benchmark/host_spans.phase_segments``,
    ``trace_reduce.HOST_SPAN``) matches the phase names whole and takes a
    top-level ``serve.*`` span as the phase: the nine names stay bare and
    without arguments, and a marker is an EMPTY block — no phase begins
    inside one — whose numbers are the step's or the request's."""
    args = _arguments(traced["log_dir"])
    assert PHASES | MARKERS >= set(args) >= PHASES
    assert all(a == {} for name in PHASES for a in args[name])
    ev = traced["events"]
    for _, ms, me in traced["markers"]:
        assert not any(ms <= s < me for _, s, _ in ev)
    steps = [a for name in STEP_MARKERS for a in args.get(name, [])]
    assert len(steps) == traced["decode_steps"]
    # each argument has its reader (docs/OBS.md): none rides along unread
    assert all(set(a) == {"n", "admitted", "admit_us", "gap_us"} for a in steps)
    assert all((a["admitted"] == 0) == (a["admit_us"] == 0) and a["admit_us"] <= a["gap_us"]
               for a in steps)
    # two engines, each counting its steps from 0
    ordinals = sorted(a["n"] for a in steps)
    assert ordinals[:2] == [0, 0] and ordinals[-1] < traced["decode_steps"]
    assert all(isinstance(v, int) and v >= 0 for a in steps for v in a.values())
    assert len(args["serve.ahead"]) == traced["steps_ahead"]
    for why, n in traced["kept"].items():
        assert len(args.get("serve.kept_" + why, [])) == n, why
    assert traced["kept"]["finish"] > 0
    assert len(args["serve.fresh"]) == traced["fresh"] > 0
    visible = args["serve.visible"]
    assert len(visible) == len(SIZES) + 1
    assert all(set(a) == {"queue_us", "behind_us", "prefill_us", "activate_us", "held_us"}
               for a in visible)
    assert all(v >= 0 for a in visible for v in a.values())
    # every marker's name passes the reducer's pattern as the phases' do
    assert all(HOST_SPAN.match(n) for n in args)


def test_phases_nest_as_the_trace_reader_takes_them_to(traced):
    ev = traced["events"]
    assert _inside(ev, "serve.dispatch", "serve.step")
    assert _inside(ev, "serve.sync", "serve.step")
    assert _inside(ev, "serve.prefill", "serve.admit")
    # step and emit tile a ``step()`` call's decode part side by side, after
    # the plan of the step the call dispatches (none where the step it reads
    # was already in flight and no other may follow it yet); where the
    # pipeline fills (nothing was in flight) the NEXT step's plan lies inside
    # the step, between its two dispatches
    nested = [e for e in ev if e[0] == "serve.plan" and _within(e, ev, "serve.step")]
    order = [e[0] for e in sorted(ev, key=lambda e: e[1])
             if e[0] in ("serve.plan", "serve.step", "serve.emit") and e not in nested]
    assert [n for n in order if n != "serve.plan"] == \
        ["serve.step", "serve.emit"] * traced["decode_steps"]
    assert all(b == "serve.step" for a, b in zip(order, order[1:]) if a == "serve.plan")
    assert not _inside(ev, "serve.step", "serve.plan")
    dispatches = sorted((e for e in ev if e[0] == "serve.dispatch"), key=lambda e: e[1])
    for _, s, e in nested:
        step = next(x for x in ev if x[0] == "serve.step" and x[1] <= s and e <= x[2])
        first, second = [d for d in dispatches if step[1] <= d[1] and d[2] <= step[2]]
        assert first[2] <= s and e <= second[1]
    assert 0 < len(nested) < traced["steps_ahead"]
    # a slot is activated from an admission, or after a chunked prompt's last
    # chunk, which runs from Engine.step itself
    activations = [e for e in ev if e[0] == "serve.activate"]
    in_admit = [e for e in activations if _within(e, ev, "serve.admit")]
    assert len(activations) == len(SIZES) + 1 and len(in_admit) == len(SIZES)


def test_one_step_annotation_for_each_counted_decode_step(traced):
    count = {n: sum(1 for m, _, _ in traced["events"] if m == n) for n in PHASES}
    assert count["serve.step"] == traced["decode_steps"] > 0
    assert count["serve.plan"] == count["serve.dispatch"] == count["serve.sync"] \
        == count["serve.emit"] == count["serve.step"]
    assert count["serve.prefill"] == len(SIZES) + 1  # the chunked prompt's plan too
    assert count["serve.prefill_chunk"] == 3         # 20 tokens in chunks of 8


def test_the_trace_reader_tiles_a_run_ahead_engines_steps(traced):
    """``benchmark.host_spans.phase_segments``, the reader of the traced
    runs, as it is: an engine that dispatches step N+1 before it reads step
    N still gives it one ``serve.step`` a counted decode step, which it
    tiles into ``dispatch`` and ``sync`` with nothing left over, beside the
    ``admit``, ``plan`` and ``emit`` of the same call (``caller`` is what
    lies between the segments)."""
    from benchmark import host_spans

    assert traced["steps_ahead"] > 0
    ev = traced["events"]
    segments = host_spans.phase_segments([(n, s, e - s) for n, s, e in ev])
    assert all(a[1] <= b[0] for a, b in zip(segments, segments[1:]))
    assert {phase for _, _, phase in segments} == set(host_spans.PHASES) - {"caller"}
    steps = [e for e in ev if e[0] == "serve.step"]
    assert len(steps) == traced["decode_steps"]
    dispatched = 0
    for _, s, e in steps:
        inside = [seg for seg in segments if s <= seg[0] and seg[1] <= e]
        assert sum(b - a for a, b, _ in inside) == e - s
        phases = [phase for _, _, phase in inside]
        assert set(phases) <= {"dispatch", "sync"} and phases[-1] == "sync"
        dispatched += phases.count("dispatch")
    # a step's dispatch lies in its own serve.step or, run ahead, in the one before
    assert dispatched == sum(1 for e in ev if e[0] == "serve.dispatch") == len(steps)
    held = {"dispatch": "serve.dispatch", "plan": "serve.plan", "emit": "serve.emit"}
    for phase, name in held.items():
        assert sum(b - a for a, b, p in segments if p == phase) <= \
            sum(e - s for n, s, e in ev if n == name)


def test_traced_calls_carry_the_programs_names(traced):
    # a call through a compiled executable reads PjitFunction(jit(<name>)),
    # one through a jitted function PjitFunction(<name>)
    called = {_program(p) for p in traced["programs"]}
    assert {"serve_decode", "serve_prefill", "serve_tail_prefill", "serve_gather",
            "serve_scatter", "serve_activate", "serve_release",
            "serve_seed_key"} <= called, called
    assert not any("unknown" in p for p in called)


WARMED = {
    "plain": (dict(), SIZES),
    # three of the prompts prefill in chunks, and slots churn between them
    "chunked": (dict(chunk_tokens=8), [(20, 3), (18, 3), (5, 2), (20, 2)]),
    "spec": (dict(spec=True, spec_max_draft=3), SIZES),
}


@pytest.mark.parametrize("kind", sorted(WARMED))
def test_a_warmed_engine_runs_only_its_named_programs(model, tmp_path, host_trace_events,
                                                      kind):
    """Between the first ``serve.step`` and the last of a run on a WARMED
    engine (the same requests served twice before: pool and table at their
    sizes, prefixes in the store, so tail prefills, copy-on-write blocks and
    drafts are in it) every program the host dispatches is a ``serve_*`` one:
    no eager ``.at[slot].set`` (``scatter``, ``convert_element_type``,
    ``broadcast_in_dim`` ... were eleven of them a request), no scalar put
    that runs a conversion, no seed derivation op by op."""
    cfg, params = model
    knobs, sizes = WARMED[kind]
    eng = Engine(params, cfg, ServeConfig(slots=2, max_len=32, kv_block=8,
                                          shrink=False, **knobs))

    def serve():
        rids = [eng.submit(r) for r in _requests(cfg, sizes)]
        done = eng.run()
        return [done[rid].tokens for rid in rids]

    first = serve()
    assert serve() == first
    tracing.start(str(tmp_path))
    try:
        assert serve() == first
    finally:
        tracing.stop()
    events = host_trace_events(tmp_path)
    steps = [e for e in events if e[0] == "serve.step"]
    assert len(steps) > 2
    lo, hi = steps[0][1], max(end for _, _, end in steps)
    inside = [_program(name) for name, start, _ in events
              if name.startswith("PjitFunction(") and lo <= start <= hi]
    assert {"serve_activate", "serve_release"} <= set(inside)
    assert [n for n in inside if not n.startswith("serve_")] == []


def test_outputs_are_the_same_with_no_profiler_and_no_tracer(model, traced):
    cfg, params = model
    assert trace.active_tracer() is None
    _, tokens = _serve(model, ServeConfig(slots=2, max_len=32, kv_block=8))
    assert tokens == traced["tokens"]
    for toks, req in zip(tokens, _requests(cfg)):
        solo = generate(params, jnp.asarray(req.prompt)[None], cfg,
                        max_new_tokens=req.max_new_tokens)
        assert toks == list(np.asarray(solo[0, len(req.prompt):]))
    _, chunk_tokens = _serve(model, ServeConfig(slots=2, max_len=32, kv_block=8, chunk_tokens=8),
                             [(20, 3)])
    assert chunk_tokens == traced["chunk_tokens"]


PROGRAMS = {
    "serve_prefill": lambda cfg: engine_mod._prefill_fn(cfg, 16, 8),
    "serve_tail_prefill": lambda cfg: engine_mod._tail_fn(cfg, 16, 8),
    "serve_decode": lambda cfg: engine_mod._decode_fn(cfg, "scan", 8, 8),
    "serve_spec_decode": lambda cfg: engine_mod._decode_fn(cfg, "scan", 8, 8, draft_k=2),
    "serve_scatter": lambda cfg: engine_mod._scatter_fn(),
    "serve_scatter[int8]": lambda cfg: engine_mod._scatter_fn("int8"),
    "serve_copy_block": lambda cfg: engine_mod._copy_block_fn(),
    "serve_zero_scales": lambda cfg: engine_mod._zero_scales_fn(),
    "serve_gather": lambda cfg: engine_mod._gather_fn(),
    "serve_activate": lambda cfg: engine_mod._activate_fn(),
    "serve_release": lambda cfg: engine_mod._release_fn(),
    "serve_seed_key": lambda cfg: engine_mod._seed_key_fn(),
}


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_every_serving_program_has_a_stable_name(model, name):
    """jit names a program after its function (``jit_<__name__>`` on the
    trace's ``XLA Modules`` line); a ``functools.partial`` has none."""
    fn = PROGRAMS[name](model[0])
    assert fn.__name__ == name.split("[")[0]


def test_the_lowered_program_is_named_after_its_function(model):
    cfg, params = model
    sds = jax.ShapeDtypeStruct
    lowered = engine_mod._prefill_fn(cfg, 16, 8).lower(
        params, sds((1, 16), jnp.int32), sds((), jnp.int32), sds((), jnp.float32),
        sds((), jnp.int32), sds((), jnp.float32), sds((2,), jnp.uint32),
    )
    assert "module @jit_serve_prefill" in lowered.as_text()
