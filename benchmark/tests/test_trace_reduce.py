"""trace_reduce.py on one small trace recorded on the chip (TPU v5 lite; three
calls of a jitted 1024 x 1024 bf16 matmul-and-sum, each inside a
``bench.test.call`` annotation, 20 ms of sleep between them), and its
arithmetic on made-up events."""

import os

import pytest

from benchmark import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tiny.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(tr.load(TRACE))


def test_busy_idle_and_window(reduced):
    assert reduced["window_s"] == pytest.approx(0.042714843, rel=1e-6)
    assert reduced["busy_s"] == pytest.approx(3.5572e-05, rel=1e-4)
    assert 100 * (1 - reduced["busy_s"] / reduced["window_s"]) == pytest.approx(99.917, abs=1e-3)


def test_one_operations_time_and_its_program(reduced):
    name = "convolution_reduce_fusion fusion bf16[]"
    assert reduced["ops"][name] == pytest.approx(3.5526e-05, rel=1e-4)
    assert len(reduced["op_events"][name]) == 3
    assert reduced["device_ops"][0][0] == name
    assert tr.op_seconds(reduced, r"^convolution_reduce_fusion ") == pytest.approx(3.5526e-05, rel=1e-4)
    (program, events), = reduced["module_events"].items()
    assert program.startswith("jit__lambda(") and len(events) == 3
    assert tr.whole_executions(reduced, program)[2] == 1  # the first and last are left out


def test_gaps_are_named_by_the_host_span_over_them(reduced):
    gaps = dict(reduced["idle_gaps"])
    # the sleeps lie outside the annotation; the calls' own gaps inside it
    assert gaps["host: no named span"] == pytest.approx(0.042679268, rel=1e-6)
    assert "bench.test.call" in gaps
    assert sum(gaps.values()) == pytest.approx(reduced["window_s"] - reduced["busy_s"], rel=1e-6)


def test_nested_operations_are_counted_once():
    trace = {"host": [("bench.x", 0.0, 10.0)], "devices": {0: {"modules": [], "ops": [
        ("%while.1 = (s32[]{:T(128)}) while((s32[]) %t), body=%b", 100.0, 60.0),
        ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 110.0, 20.0),
        ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 135.0, 20.0),
        ("%copy.3 = f32[8]{0} copy(f32[8]{0} %p)", 180.0, 20.0),
    ]}}}
    r = tr.reduce(trace)
    assert r["busy_s"] == pytest.approx(80e-9) and r["window_s"] == pytest.approx(100e-9)
    assert r["ops"] == pytest.approx({"while.1 while (s32[])": 20e-9, "fusion.2 fusion f32[8]": 40e-9,
                                      "copy.3 copy f32[8]": 20e-9})
    assert sum(r["ops"].values()) == pytest.approx(r["busy_s"])
    assert r["idle_gaps"] == [["host: no named span", pytest.approx(20e-9)]]


def test_short_names():
    assert tr.short_name(
        '%checkpoint.23 = bf16[128,2048,128]{2,1,0:T(8,128)(2,1)} custom-call(bf16[128,2048,128]{2,1,0} %x), '
        'custom_call_target="tpu_custom_call"') == "checkpoint.23 custom-call:tpu_custom_call bf16[128,2048,128]"
    assert tr.short_name("not an hlo line") == "not an hlo line"
