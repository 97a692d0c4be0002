"""``correct`` has to be able to fail. Each test skips the harness's look for
a chip and drives the rest of a run (`run.run_cell`, the cell's own driver,
the program's own entry) at test sizes on the CPU:

* a sound run is correct;
* the control (the reference put in the program's place, computed in the
  nearest precision below the one the test configuration states: bfloat16
  for float32) is NOT correct by the same limits;
* with the timed path broken underneath, once for each fault the cells can
  have, ``correct`` comes out false: a step that returns its state
  unchanged, half of the batch left out with the mean taken over the rest,
  a served token altered where it is produced. (Neither cell exchanges
  anything between chips.)
"""

import os

import pytest

import run
from benchmark import compare

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "BENCHMARK.json")


def cell(name, seed, **extra):
    return run.run_cell(name, seed, 1.5, False, bench_file=BENCH, require_chip=False, extra=extra)


def limits_of(result):
    return {row["name"]: row["limit"] for row in result["checks"]}


def test_train_sound_run_is_correct_and_control_is_not():
    r = cell("tiny-train-cell", 2**31 + 11, control=1)
    assert r["correct"], r["checks"]
    assert r["metrics"]["train_tokens_per_s_chip"]["value"] > 0
    ok, rows = compare.verdict(r["notes"]["control"], limits_of(r))
    assert not ok, rows
    # the reference itself with half of each batch left out fails too
    ok, rows = compare.verdict(r["notes"]["fault_half_batch"], limits_of(r))
    assert not ok, rows


@pytest.mark.parametrize("fault,number", [
    ("state_unchanged", "dparam_norm_gap"),
    ("half_batch", "gnorm1_rel"),
])
def test_train_fault_in_the_timed_path_is_not_correct(fault, number):
    r = cell("tiny-train-cell", 2**31 + 12, fault=fault)
    assert not r["correct"], r["checks"]
    failed = {row["name"] for row in r["checks"] if not row["value"] <= row["limit"]}
    assert number in failed, r["checks"]
    if fault == "state_unchanged":
        gap = next(row["value"] for row in r["checks"] if row["name"] == "dparam_norm_gap")
        assert gap == pytest.approx(1.0, abs=1e-6)  # reads 1 by the measure's own definition


def test_serve_sound_run_is_correct_and_control_is_not():
    r = cell("tiny-serve-cell", 3, control=1)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 10
    ok, rows = compare.verdict(r["notes"]["control"], limits_of(r))
    assert not ok, rows


def test_serve_altered_token_is_not_correct():
    r = cell("tiny-serve-cell", 4, fault="token_altered")
    assert not r["correct"], r["checks"]


def test_open_loop_with_shared_prefixes_is_correct():
    r = cell("tiny-serve-open", 2**31 + 7)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 10
    assert r["metrics"]["ttft_p75_ms"]["value"] > 0


def test_no_chip_means_no_result():
    with pytest.raises(run.Refused) as e:
        run.run_cell("tiny-serve-cell", 1, 1.0, False, bench_file=BENCH, require_chip=True)
    assert e.value.code == 3
