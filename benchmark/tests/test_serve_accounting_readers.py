"""serve_markers.py and the two readers built on it (``serve_ttft.*``,
``serve_step.*``), on a made-up capture (host events with a name, a start and
their arguments as ``stats``), and on a real one: a tiny engine served under
the profiler on the CPU. A capture without the engine's markers gives None,
not 0."""

import json
import os
import types

import pytest

from benchmark import run, trace_reduce
from benchmark.layer_metrics import serve_markers

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TTFT = ["serve_ttft." + p + "_ms" for p in ("queue", "behind", "prefill", "activate", "held")]
STEP = ["serve_step." + m for m in ("kept_finish_share", "kept_admit_share", "gap_ms_ahead",
                                    "gap_ms_kept", "gap_ms_beside_admission")]


def reader(stem):
    return run.load_module(os.path.join(os.path.dirname(HERE), "layer_metrics", stem + ".py"))


MS = 1e6  # ns: the made-up capture's events are given in ms since it began


def event(name, start_ms, **args):
    """One host event as ``ProfileData`` gives it: the arguments as ``stats``
    under a bare name."""
    return types.SimpleNamespace(name=name, start_ns=5e9 + start_ms * MS, duration_ns=500.0,
                                 stats=list(args.items()))


def planes(events, other=()):
    host = types.SimpleNamespace(name="/host:CPU", lines=[
        types.SimpleNamespace(name="python", events=list(events)),
        types.SimpleNamespace(name="other thread", events=list(other))])
    device = types.SimpleNamespace(name="/device:TPU:0", lines=[types.SimpleNamespace(
        name="XLA Ops", events=[event("serve.visible", 1, queue_us=9)])])  # not a host event
    return [device, host]


def step(name, start_ms, n, gap, admitted=0, admit=0):
    return event(name, start_ms, n=n, admitted=admitted, admit_us=admit, gap_us=gap)


def capture():
    """Five decode steps and two admissions made in ONE round: three steps ran
    ahead (one of them beside an admission over a step in flight), one was kept
    for a finish and followed the round of two admissions (its gap holds their
    31 ms), one was kept for a queued request. Both requests were submitted
    after the capture's first event."""
    return [
        step("serve.ahead", 18, 0, 18000),
        step("serve.ahead", 38, 1, 20000),
        step("serve.kept_finish", 90, 2, 52000, admitted=2, admit=31000),
        event("serve.visible", 90.1, queue_us=400, behind_us=100, prefill_us=21000,
              activate_us=600, held_us=19000),
        event("serve.visible", 90.2, queue_us=200, behind_us=22300, prefill_us=9000,
              activate_us=400, held_us=19000),
        step("serve.kept_admit", 113, 3, 23000),
        step("serve.ahead", 143, 4, 30000, admitted=1, admit=9000),
    ]


@pytest.fixture
def run_ctx(tmp_path, monkeypatch):
    """A reader's ctx whose capture is stood in for by ``events``."""
    def make(events):
        path = tmp_path / "trace" / "plugins" / "profile" / "x" / "host.xplane.pb"
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_bytes(b"")
        monkeypatch.setattr(serve_markers, "_of_file",
                            lambda p: serve_markers.of_planes(planes(events)))
        return {"work": str(tmp_path)}
    return make


def test_each_metric_reads_its_hand_computed_value(run_ctx):
    ctx = run_ctx(capture())
    ttft = {n: reader("serve_ttft").read(n, ctx) for n in TTFT}
    assert ttft == pytest.approx({
        "serve_ttft.queue_ms": 0.3, "serve_ttft.behind_ms": 11.2, "serve_ttft.prefill_ms": 15.0,
        "serve_ttft.activate_ms": 0.5, "serve_ttft.held_ms": 19.0})
    # the second admission of the round waited behind the first's prefill
    steps = {n: reader("serve_step").read(n, ctx) for n in STEP}
    assert steps == pytest.approx({
        "serve_step.kept_finish_share": 20.0, "serve_step.kept_admit_share": 20.0,
        "serve_step.gap_ms_ahead": 19.0,                        # (18 + 20) / 2: not the one beside an admission
        "serve_step.gap_ms_kept": (52.0 - 31.0 + 23.0) / 2,     # less what the admissions took
        "serve_step.gap_ms_beside_admission": (52.0 + 30.0) / 2})
    assert steps["serve_step.kept_finish_share"] + steps["serve_step.kept_admit_share"] <= 100


def test_markers_are_found_by_name_on_host_planes_only_in_time_order():
    events = list(reversed(capture())) + [event("serve.emit", 5), event("serve.aheadness", 6, n=1),
                                          event("bench.engine.step", 7)]
    found = serve_markers.of_planes(planes(events, other=[
        event("serve.fresh", 9, n=9, gap_us=1, note="x")]))
    assert [name for name, _, _ in found] == [
        "serve.fresh", "serve.ahead", "serve.ahead", "serve.kept_finish", "serve.visible",
        "serve.visible", "serve.kept_admit", "serve.ahead"]
    assert found[3][2]["admit_us"] == 31000 and found[4][2]["prefill_us"] == 21000
    # an argument that is no number is left out
    assert found[0][2] == {"n": 9, "gap_us": 1}


def test_a_request_submitted_before_the_capture_began_is_left_out(run_ctx):
    """The loop that starts the profiler stands still meanwhile: the request
    it had just submitted waits that out in ``queue_us``. Its marker lies in
    the capture, its ``submit()`` (the marker's start less its five parts)
    before the capture's first host event: no sample of the engine's."""
    waited = event("serve.visible", 60, queue_us=350000, behind_us=100, prefill_us=21000,
                   activate_us=600, held_us=19000)
    ctx = run_ctx([event("bench.engine.step", 0), waited] + capture())
    assert serve_markers.VISIBLE in [name for name, _, _ in serve_markers.of_run(ctx)]
    assert reader("serve_ttft").read("serve_ttft.queue_ms", ctx) == pytest.approx(0.3)
    # the step markers are all there: a step is no request
    assert reader("serve_step").read("serve_step.kept_finish_share", ctx) == pytest.approx(20.0)
    # submitted 0.2 ms after the first event: kept
    kept = event("serve.visible", 41.8, queue_us=600, behind_us=100, prefill_us=21000,
                 activate_us=600, held_us=19300)
    ctx = run_ctx([event("bench.engine.step", 0), kept] + capture())
    assert reader("serve_ttft").read("serve_ttft.queue_ms", ctx) == pytest.approx(0.4)
    # alone in the capture, none is left to take a mean over
    ctx = run_ctx([event("bench.engine.step", 0), waited])
    assert reader("serve_ttft").read("serve_ttft.queue_ms", ctx) is None


def test_a_capture_without_the_markers_reads_none_not_zero(run_ctx):
    phases = [event("serve.step", 10), event("serve.emit", 20), event("bench.engine.step", 5)]
    ctx = run_ctx(phases)
    assert serve_markers.of_run(ctx) == []
    for name in TTFT:
        assert reader("serve_ttft").read(name, ctx) is None
    for name in STEP:
        assert reader("serve_step").read(name, ctx) is None
    # steps but no request made visible in the traced seconds, and the reverse
    ctx = run_ctx([e for e in capture() if not e.name.startswith("serve.visible")])
    assert all(reader("serve_ttft").read(name, ctx) is None for name in TTFT)
    assert reader("serve_step").read("serve_step.kept_finish_share", ctx) == pytest.approx(20.0)
    ctx = run_ctx([event("bench.engine.step", 0)]
                  + [e for e in capture() if e.name.startswith("serve.visible")])
    assert all(reader("serve_step").read(name, ctx) is None for name in STEP)
    assert reader("serve_ttft").read("serve_ttft.held_ms", ctx) == pytest.approx(19.0)
    # only steps that ran ahead: no kept step to take a mean over, a share of 0
    ctx = run_ctx([e for e in capture() if e.name.startswith("serve.ahead")])
    assert reader("serve_step").read("serve_step.gap_ms_kept", ctx) is None
    assert reader("serve_step").read("serve_step.kept_finish_share", ctx) == 0.0
    # and with no capture at all
    assert serve_markers.of_run({"work": os.path.join(ctx["work"], "nowhere")}) == []


def test_the_engines_own_capture_is_read_whole(tmp_path):
    """A tiny engine served under the profiler, here on the CPU: the readers
    take from the capture what the engine counted on its own clock."""
    import jax
    import numpy as np

    from benchmark import tracing
    from tony_tpu.models import llama
    from tony_tpu.serve import Engine, Request, ServeConfig

    cfg = llama.LlamaConfig.tiny()
    eng = Engine(llama.init_params(jax.random.key(0), cfg), cfg,
                 ServeConfig(slots=2, max_len=32, kv_block=8, shrink=False))
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32), max_new_tokens=m)
            for n, m in [(3, 5), (7, 4), (12, 6), (5, 3)]]
    eng.run(reqs)  # every program built before the capture starts
    eng.reset_metrics()
    parts = ("queue", "behind", "prefill", "activate", "held")
    # as the benchmark's driver does it: a request is submitted, THEN the loop
    # starts the profiler, then it steps under its own annotation
    eng.submit(reqs[0])
    tracing.start(str(tmp_path / "trace"))
    try:
        with jax.profiler.TraceAnnotation("bench.engine.step"):
            eng.step()
        m = eng.metrics
        waited = [getattr(m, f"ttft_{part}_s") for part in parts]  # that one request's
        assert m.requests_started == 1 and waited[0] > 0
        for r in reqs[1:]:
            eng.submit(r)
        while eng.queue_depth or eng.n_live:
            with jax.profiler.TraceAnnotation("bench.engine.step"):
                eng.step()
    finally:
        tracing.stop()
    serve_markers._of_file.cache_clear()
    ctx = {"work": str(tmp_path)}
    found = serve_markers.of_run(ctx)
    steps = [a for name, _, a in found if name in serve_markers.STEP_MARKERS]
    assert [a["n"] for a in steps] == list(range(m.decode_steps))
    # the request that waited out the profiler's start is left out
    assert sum(1 for name, _, _ in found if name == serve_markers.VISIBLE) == len(reqs) - 1
    for part, first in zip(parts, waited):
        read = reader("serve_ttft").read(f"serve_ttft.{part}_ms", ctx)
        rest = (getattr(m, f"ttft_{part}_s") - first) / (len(reqs) - 1)
        assert read == pytest.approx(1e3 * rest, abs=2e-3)
    assert reader("serve_step").read("serve_step.kept_finish_share", ctx) == pytest.approx(
        100.0 * m.steps_kept["finish"] / m.decode_steps)
    gaps = sum(a["gap_us"] for a in steps)
    assert gaps == pytest.approx(1e6 * sum(m.step_gap_s.values()), abs=len(steps))
    assert trace_reduce.find_trace(str(tmp_path / "trace"))
    serve_markers._of_file.cache_clear()


def test_every_new_metric_is_declared_with_its_reader_for_the_three_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    cells = ["serve-yi6b-chat-closed16", "serve-dsv3-reason-closed48",
             "serve-jamba2-reason-closed128"]
    assert [m["name"] for m in bench["per_layer"]][-10:] == TTFT + STEP
    for name in TTFT + STEP:
        entry = per_layer[name]
        assert entry["workloads"] == cells and entry["layer"] == "serve engine", name
        assert os.path.exists(run.reader_path(name, os.path.join(ROOT, "benchmark"))), name
    assert {per_layer[n]["moves"] for n in TTFT} == {"ttft_p75_ms"}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert all(set(cells) <= set(e2e[per_layer[n]["moves"]]["workloads"]) for n in TTFT + STEP)
