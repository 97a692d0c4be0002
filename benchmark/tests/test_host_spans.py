"""host_spans.py and the three readers built on it, on made-up traces in the
shape ``trace_reduce.load`` gives (times in ns): a gap is split by overlap
over the ``serve.*`` phases, the phases add up to the idle time, the program
groups to 100, and a trace without the engine's names gives None, not 0."""

import os

import pytest

from benchmark import host_spans, run, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def reader(stem):
    return run.load_module(os.path.join(os.path.dirname(HERE), "layer_metrics", stem + ".py"))


def op(start, dur, name="%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"):
    return (name, float(start), float(dur))


def serving_trace():
    """Three decode steps and one admission. Device busy [20,100) [140,240)
    [300,400) [420,460): gaps 40 + 60 + 20 = 120 of a 440 window. Every
    program starts after its dispatch began and ends before its sync returned."""
    host = [
        ("bench.engine.step", 90.0, 160.0),
        # step 1 ends, the caller turns the loop, step 2 is planned and sent
        ("serve.step", 10.0, 95.0), ("serve.dispatch", 10.0, 5.0), ("serve.sync", 15.0, 90.0),
        ("serve.emit", 105.0, 10.0),
        ("serve.plan", 120.0, 8.0),
        ("serve.step", 128.0, 117.0), ("serve.dispatch", 128.0, 22.0), ("serve.sync", 150.0, 95.0),
        ("serve.emit", 245.0, 15.0),
        # an admission: prefill and activation inside serve.admit
        ("serve.admit", 270.0, 140.0), ("serve.prefill", 272.0, 100.0),
        ("serve.activate", 380.0, 28.0),
        ("serve.plan", 412.0, 6.0),
        ("serve.step", 418.0, 60.0), ("serve.dispatch", 418.0, 4.0), ("serve.sync", 422.0, 56.0),
    ]
    ops = [op(20, 80), op(140, 100), op(300, 100), op(420, 40)]
    modules = [("jit_serve_decode(123)", 20.0, 80.0), ("jit_serve_decode(123)", 140.0, 100.0),
               ("jit_serve_prefill(77)", 300.0, 60.0), ("jit_serve_scatter(5)", 360.0, 20.0),
               ("jit_dynamic_update_slice(9)", 380.0, 20.0), ("jit_serve_decode(123)", 420.0, 40.0)]
    return {"host": host, "devices": {0: {"ops": ops, "modules": modules}}}


def anonymous_trace():
    """The same device timeline from a program that names nothing."""
    t = serving_trace()
    t["host"] = [e for e in t["host"] if e[0].startswith("bench.")]
    t["devices"][0]["modules"] = [("jit__unknown(1)", s, d) for _, s, d in t["devices"][0]["modules"]]
    return t


def test_a_gap_that_straddles_phases_is_split_by_overlap():
    split = host_spans.split_idle(serving_trace())
    idle = {k: v * 1e9 for k, v in split["idle_s"].items()}
    # gap [100,140): sync to 105, emit to 115, caller to 120, plan to 128, dispatch to 140
    # gap [240,300): sync to 245, emit to 260, caller to 270, admit to 300
    # gap [400,420): admit to 410, caller to 412, plan to 418, dispatch to 420
    assert idle == pytest.approx({"sync": 5 + 5, "emit": 10 + 15, "caller": 5 + 10 + 2,
                                  "plan": 8 + 6, "dispatch": 12 + 2, "admit": 30 + 10})
    # where trace_reduce names the whole first gap by the span over its middle
    gaps = dict(trace_reduce.reduce(serving_trace())["idle_gaps"])
    assert gaps["serve.plan"] == pytest.approx(40e-9)


def test_phases_add_up_to_the_idle_share():
    trace = serving_trace()
    split, reduced = host_spans.split_idle(trace), trace_reduce.reduce(trace)
    assert split["window_s"] == pytest.approx(reduced["window_s"])
    assert set(split["idle_s"]) == set(host_spans.PHASES)
    share = sum(split["idle_s"].values()) / split["window_s"]
    assert share == pytest.approx(1 - reduced["busy_s"] / reduced["window_s"], abs=1e-12)
    assert share == pytest.approx(120 / 440)
    assert split["clock_shift_s"] == 0.0


def test_a_device_clock_that_runs_early_is_shifted_as_far_as_causality_asks():
    """The device's times 7 early: the first thing that cannot be is the third
    program starting 5 before its dispatch began, so 5 is what is added. The
    idle inside the gaps and the total stay; two of the gaps' ends move."""
    true = host_spans.split_idle(serving_trace())["idle_s"]
    early = serving_trace()
    dev = early["devices"][0]
    dev["ops"] = [(n, s - 7, d) for n, s, d in dev["ops"]]
    dev["modules"] = [(n, s - 7, d) for n, s, d in dev["modules"]]
    serve = [e for e in early["host"] if e[0].startswith("serve.")]
    assert host_spans.clock_shift(dev["modules"], serve) == 5.0
    split = host_spans.split_idle(early)
    assert split["clock_shift_s"] == pytest.approx(5e-9)
    idle = split["idle_s"]
    for phase in ("emit", "plan", "caller"):
        assert idle[phase] == pytest.approx(true[phase])
    assert sum(idle.values()) == pytest.approx(sum(true.values()))
    assert idle["sync"] == pytest.approx(true["sync"] + 2 * 2e-9)      # two gaps start in a sync
    assert idle["dispatch"] == pytest.approx(true["dispatch"] - 2 * 2e-9)  # and two end in a dispatch
    # a device clock that runs late is pulled back to where each sync returned
    late = serving_trace()
    late["devices"][0]["modules"] = [(n, s + 9, d) for n, s, d in late["devices"][0]["modules"]]
    assert host_spans.clock_shift(late["devices"][0]["modules"], serve) == -4.0
    assert host_spans.clock_shift(anonymous_trace()["devices"][0]["modules"], serve) == 0.0


def test_segments_tile_the_thread_without_overlap():
    segments = host_spans.phase_segments(serving_trace()["host"])
    assert all(a[1] <= b[0] for a, b in zip(segments, segments[1:]))
    assert [p for _, _, p in segments[:4]] == ["dispatch", "sync", "emit", "plan"]
    # an annotation the trace's edge cut out of its step stands for itself
    cut = host_spans.phase_segments([("serve.sync", 0.0, 50.0), ("serve.emit", 50.0, 5.0)])
    assert cut == [(0.0, 50.0, "sync"), (50.0, 55.0, "emit")]


def test_counts_and_host_seconds():
    split = host_spans.split_idle(serving_trace())
    assert split["counts"]["serve.step"] == 3 and split["counts"]["serve.admit"] == 1
    assert split["host_s"]["serve.emit"] == pytest.approx(25e-9)
    assert "bench.engine.step" not in split["counts"]


@pytest.fixture
def run_ctx(tmp_path, monkeypatch):
    """A reader's ctx whose trace file is stood in for by ``trace``."""
    def make(trace):
        path = tmp_path / "trace" / "plugins" / "profile" / "x" / "host.xplane.pb"
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_bytes(b"")
        monkeypatch.setattr(trace_reduce, "load", lambda p: trace)
        host_spans._of_file.cache_clear()
        return {"work": str(tmp_path), "trace": trace_reduce.reduce(trace)}
    yield make
    host_spans._of_file.cache_clear()


def test_readers_on_a_named_trace(run_ctx):
    ctx = run_ctx(serving_trace())
    idle = {p: reader("serve_idle").read("serve_idle." + p, ctx) for p in host_spans.PHASES}
    assert idle["admit"] == pytest.approx(100 * 40 / 440)
    assert idle["caller"] == pytest.approx(100 * 17 / 440)
    device_idle = reader("device_idle").read("device_idle.serve", ctx)
    assert sum(idle.values()) == pytest.approx(device_idle, abs=1e-9)
    # plan 14 + dispatch 31 + emit 25 ns of host work over three steps
    assert reader("serve_host_overhead_ms").read("serve_host_overhead_ms", ctx) == pytest.approx(
        1e3 * 70e-9 / 3)
    dev = {g: reader("serve_device").read("serve_device." + g, ctx)
           for g in ("decode", "prefill", "scatter", "other")}
    assert dev == pytest.approx({"decode": 100 * 220 / 320, "prefill": 100 * 60 / 320,
                                 "scatter": 100 * 20 / 320, "other": 100 * 20 / 320})
    assert sum(dev.values()) == pytest.approx(100.0)


def test_readers_give_none_without_the_engines_names(run_ctx):
    ctx = run_ctx(anonymous_trace())
    assert host_spans.split_idle(anonymous_trace()) is None
    for name in ("serve_idle.admit", "serve_idle.plan", "serve_idle.dispatch", "serve_idle.emit",
                 "serve_idle.caller"):
        assert reader("serve_idle").read(name, ctx) is None
    assert reader("serve_host_overhead_ms").read("serve_host_overhead_ms", ctx) is None
    for name in ("serve_device.decode", "serve_device.prefill", "serve_device.scatter",
                 "serve_device.other"):
        assert reader("serve_device").read(name, ctx) is None
    # and with no trace directory at all
    assert host_spans.of_run({"work": os.path.join(ctx["work"], "nowhere")}) is None


def test_every_new_metric_is_declared_for_the_serve_cell_with_a_reader():
    import json

    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    new = ["serve_idle." + p for p in ("admit", "plan", "dispatch", "emit", "caller")]
    new += ["serve_host_overhead_ms"] + ["serve_device." + g for g in ("decode", "prefill", "scatter", "other")]
    for name in new:
        assert per_layer[name]["workloads"] == ["serve-yi6b-chat-closed16"], name
        assert os.path.exists(os.path.join(root, "benchmark", "layer_metrics",
                                           name.split(".", 1)[0] + ".py")), name
