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

import glob
import os

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
def traced(model, tmp_path_factory):
    """{"events": [(name, start_ns, end_ns)] of the ``serve.*`` host events,
    "programs": names of the jitted calls, "tokens", "chunk_tokens",
    "decode_steps"} of one plain and one chunked-prefill engine, both run
    with the profiler on."""
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
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    events, programs = [], set()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    events.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
                elif e.name.startswith("PjitFunction("):
                    programs.add(e.name)
    return {"events": events, "programs": programs, "tokens": tokens,
            "chunk_tokens": chunk_tokens,
            "decode_steps": eng.metrics.decode_steps + ceng.metrics.decode_steps}


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


def test_phases_nest_as_the_trace_reader_takes_them_to(traced):
    ev = traced["events"]
    assert _inside(ev, "serve.dispatch", "serve.step")
    assert _inside(ev, "serve.sync", "serve.step")
    assert _inside(ev, "serve.prefill", "serve.admit")
    # plan, step and emit tile a decode step in that order, side by side
    order = [n for n, _, _ in sorted(ev, key=lambda e: e[1])
             if n in ("serve.plan", "serve.step", "serve.emit")]
    assert order == ["serve.plan", "serve.step", "serve.emit"] * (len(order) // 3)
    assert not _inside(ev, "serve.step", "serve.plan")
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


def test_traced_calls_carry_the_programs_names(traced):
    # a call through a compiled executable reads PjitFunction(jit(<name>)),
    # one through a jitted function PjitFunction(<name>)
    called = {p[len("PjitFunction("):-1].removeprefix("jit(").rstrip(")")
              for p in traced["programs"]}
    assert {"serve_decode", "serve_prefill", "serve_tail_prefill", "serve_gather",
            "serve_scatter"} <= called, called
    assert not any("unknown" in p for p in called)


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
