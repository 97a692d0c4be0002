"""The engine's own account of its step loop (docs/SERVE.md "The step loop"):
a request's time to first token tiled into five consecutive parts, every
decode step counted under why it ran as it did, a stalled step counted and
logged with its phase — and none of it changes a served token.

One mixed run a model family: finishes alone and two in one step (so two
admissions in one round), eos finishes found a device step late (so a request
admitted over a step in flight), a one-token request (a slot free again with
the queue not empty), and a prompt long enough to prefill in two chunks.
"""

import dataclasses
import logging
import time
import types

import pytest

from test_generate import _sample_tokens_before
from test_serve import _family_model, _hold_off, _pipeline_engine, _prompts
from tony_tpu.obs import metrics as metrics_mod
from tony_tpu.obs.metrics import KEPT_REASONS, DecodeMetrics
from tony_tpu.serve import Request
from tony_tpu.serve.engine import steps_for

FAMILIES = ("dense", "latent", "shortconv", "ssm_hybrid")
PARTS = ("queue", "behind", "prefill", "activate", "held")
LENS = [5, 9, 20, 4, 7, 12, 6, 11, 3, 5]
BUDGETS = [6, 6, 30, 1, 20, 9, 7, 2, 15, 4]
EOS_FROM = {4: 3, 8: 4}  # request -> its eos is its first new token from this index on
CHUNK = 16               # so the 20-token prompt prefills in two chunks


def _eos_index(tokens, k):
    """The first index from ``k`` on whose token the request has not given
    before: made its eos, the request ends there and not earlier."""
    return next(j for j in range(k, len(tokens)) if tokens[j] not in tokens[:j])


def _requests(cfg, greedy=None):
    prompts = _prompts(cfg, LENS, seed=35)
    eos = {i: greedy[i][_eos_index(greedy[i], k)] for i, k in EOS_FROM.items()} if greedy else {}
    return [Request(prompt=p, max_new_tokens=m, eos_id=eos.get(i))
            for i, (p, m) in enumerate(zip(prompts, BUDGETS))]


def _serve(eng, reqs, mp=None):
    """Submit everything, step to the end. Returns {"final": {rid: (tokens,
    finish reason)}, "visible": [(the five parts, submit -> step() returned as
    the engine took it, the test's own clock round the same)], "rounds":
    admissions a round, "over_inflight": admissions made while a step was in
    flight} — the last three only where ``mp`` lets the engine be watched."""
    seen = {"visible": [], "rounds": [], "over_inflight": 0}
    if mp is not None:
        parts, real_admit, real_round = [], eng._admit_one, eng._admit
        mp.setattr(eng.metrics, "record_visible", lambda *p: parts.append(p))
        mp.setattr(eng, "_h_visible", types.SimpleNamespace(
            observe=lambda v: seen["visible"].append(
                (parts[-1], v, time.perf_counter() - t_submit))))

        def admit_one(slot, rid, req):
            seen["over_inflight"] += eng._inflight is not None
            seen["rounds"][-1] += 1
            return real_admit(slot, rid, req)

        def admit():
            seen["rounds"].append(0)
            return real_round()

        mp.setattr(eng, "_admit_one", admit_one)
        mp.setattr(eng, "_admit", admit)
    t_submit = time.perf_counter()
    rids = [eng.submit(r) for r in reqs]
    while eng.queue_depth or eng.n_live:
        eng.step()
    done = [eng.take_completion(rid) for rid in rids]
    seen["final"] = {i: (tuple(c.tokens), c.finish_reason) for i, c in enumerate(done)}
    return seen


@pytest.fixture(scope="module", params=FAMILIES)
def accounted(request):
    """{"seen": what _serve saw of the engine that runs ahead, "held": the
    same requests through the engine held off, "engine", "snapshot",
    "requests"} of one mixed run a family."""
    model = _family_model(request.param)
    cfg = model[0]
    with pytest.MonkeyPatch.context() as mp:
        _hold_off(mp)
        greedy = _serve(_pipeline_engine(model, chunk_tokens=CHUNK, shrink=False), _requests(cfg))["final"]
        reqs = _requests(cfg, [toks for _, (toks, _) in sorted(greedy.items())])
        held = _serve(_pipeline_engine(model, chunk_tokens=CHUNK, shrink=False), reqs)
    eng = _pipeline_engine(model, chunk_tokens=CHUNK, shrink=False)
    with pytest.MonkeyPatch.context() as mp:
        seen = _serve(eng, reqs, mp)
    # record_visible was watched, not run: the sums are checked on a second,
    # unwatched run of the same engine below
    return {"seen": seen, "held": held, "engine": eng, "requests": reqs}


def test_the_five_parts_of_every_request_add_up_to_what_its_caller_waited(accounted):
    seen = accounted["seen"]
    assert len(seen["visible"]) == len(LENS)
    for parts, visible, by_the_tests_clock in seen["visible"]:
        assert len(parts) == len(PARTS) and all(p >= 0.0 for p in parts), parts
        assert abs(sum(parts) - visible) < 1e-9
        # the engine's readings lie inside the test's own, taken round them
        assert 0.0 < visible <= by_the_tests_clock
    # the run held what it was built to hold: two admissions in one round
    # after the first (two finishes in one step), the second of which waited
    # behind the first's prefill; an admission over a step in flight (an eos
    # found a device step late); a chunked prompt, whose prefill part spans
    # two step() calls
    assert sum(1 for n in seen["rounds"][1:] if n >= 2) >= 1
    assert seen["over_inflight"] >= 1
    by_submission = seen["visible"]  # requests become visible in submission order here
    first, second = by_submission[0][0], by_submission[1][0]
    assert second[1] >= first[1] + first[2]          # behind >= the other's behind + prefill
    assert all(parts[0] > 0.0 for parts, _, _ in by_submission)


def test_the_account_changes_no_token_and_no_finish(accounted):
    assert accounted["seen"]["final"] == accounted["held"]["final"]
    final, reqs = accounted["seen"]["final"], accounted["requests"]
    for i, k in EOS_FROM.items():
        toks, why = final[i]
        assert why == "eos" and toks[-1] == reqs[i].eos_id and k < len(toks) < BUDGETS[i]
        assert reqs[i].eos_id not in toks[:-1]
    assert all(why == "length" and len(toks) == BUDGETS[i]
               for i, (toks, why) in final.items() if i not in EOS_FROM)


def test_every_step_is_counted_under_one_reason(accounted):
    eng = accounted["engine"]
    m, snap = eng.metrics, eng.stats_snapshot()
    assert m.steps_ahead + sum(m.steps_kept.values()) + m.steps_fresh == m.decode_steps > 0
    assert set(m.steps_kept) == set(KEPT_REASONS)
    # a row on its last token; a one-token request freed its slot with the
    # queue not empty; other rows decoded while the long prompt was chunking
    assert m.steps_kept["finish"] > 0 and m.steps_kept["admit"] > 0
    assert m.steps_kept["chunk"] > 0 and m.steps_kept["spec"] == 0
    assert m.steps_ahead > 0 and m.steps_fresh >= 1
    for why in KEPT_REASONS:
        assert snap[f"steps_kept_{why}"] == m.steps_kept[why]
    assert snap["stalled_steps"] == m.stalled_steps
    # per reason the shares of decode_s add up to it
    assert sum(m.step_dt_s.values()) == pytest.approx(m.decode_s)
    assert sum(m.step_gap_s.values()) > 0.0
    assert snap["step_gap_mean_s_ahead"] == pytest.approx(
        m.step_gap_s["ahead"] / m.steps_ahead, abs=1e-6)


def test_the_counters_hold_the_parts_and_the_snapshot_their_means(accounted):
    """A second run of the same requests, unwatched: ``record_visible`` and
    the histogram run as they are."""
    eng, reqs = accounted["engine"], accounted["requests"]
    eng.reset_metrics()
    assert _serve(eng, reqs)["final"] == accounted["held"]["final"]
    m, snap = eng.metrics, eng.stats_snapshot()
    assert m.requests_started == len(reqs)
    sums = [getattr(m, f"ttft_{part}_s") for part in PARTS]
    assert all(s > 0.0 for s in sums)
    assert sum(sums) == pytest.approx(eng._h_visible.sum, abs=1e-9)
    assert eng._h_visible.count == len(reqs) == eng._h_ttft.count
    # what a caller feels ends after what the engine's own TTFT ends at
    assert eng._h_visible.sum > eng._h_ttft.sum
    for part, s in zip(PARTS, sums):
        assert snap[f"ttft_{part}_mean_s"] == pytest.approx(s / len(reqs), abs=1e-6)
    assert snap["ttft_visible_n"] == len(reqs)
    assert snap["ttft_visible_p50_s"] >= snap["ttft_p50_s"]


def test_a_greedy_run_never_takes_the_vocabulary_wide_sampler(accounted):
    eng = accounted["engine"]
    snap = eng.stats_snapshot()
    assert eng.metrics.decode_steps > 0
    assert eng.metrics.vocab_sampler_steps == snap["vocab_sampler_steps"] == 0
    assert snap["vocab_sampler_share"] == 0.0
    assert eng._c_vocab_sampler.value == 0


# the request that samples: the last admitted, so its slot stays free after
# it while greedy rows decode on
SAMPLED = 9


def _sampling_run(model, mp):
    """The accounting's requests, ``SAMPLED`` drawn with top-k and top-p,
    through an engine that runs ahead: (the engine, {request: tokens}, per
    decode step dispatched whether ``SAMPLED`` was on a row of it)."""
    reqs = _requests(model[0])
    reqs[SAMPLED] = dataclasses.replace(
        reqs[SAMPLED], temperature=0.8, top_k=5, top_p=0.9, rng=7)
    eng = _pipeline_engine(model)
    rids = [eng.submit(r) for r in reqs]
    with_sampled, dispatch = [], eng._dispatch

    def watched(step):
        with_sampled.append(any(r == rids[SAMPLED] for _, r in step.rows))
        return dispatch(step)

    mp.setattr(eng, "_dispatch", watched)
    while eng.queue_depth or eng.n_live:
        eng.step()
    final = {i: tuple(eng.take_completion(rid).tokens) for i, rid in enumerate(rids)}
    return eng, final, with_sampled


@pytest.mark.parametrize("family", FAMILIES)
def test_the_vocabulary_wide_sampler_runs_while_a_sampling_request_holds_a_slot(family, monkeypatch):
    """One request that samples among greedy ones: the steps whose sampler
    took its vocabulary-wide branch are those dispatched while it held its
    slot, and none after its release zeroed the slot's temperature; a fetch
    a step as before, and the tokens the unbranched sampler serves."""
    model = _family_model(family)
    eng, final, with_sampled = _sampling_run(model, monkeypatch)
    m, snap = eng.metrics, eng.stats_snapshot()
    assert m.vocab_sampler_steps == sum(with_sampled) >= BUDGETS[SAMPLED] - 1
    # the greedy rows decoded on after the release, and those steps paid
    # the argmax alone
    assert not with_sampled[-1] and m.decode_steps == len(with_sampled) > m.vocab_sampler_steps
    assert snap["vocab_sampler_steps"] == m.vocab_sampler_steps == eng._c_vocab_sampler.value
    assert snap["vocab_sampler_share"] == pytest.approx(
        m.vocab_sampler_steps / m.decode_steps, abs=1e-4)
    assert m.device_fetches == m.decode_steps + m.requests_started
    # the same requests, the family's steps sampling as before this PR
    # (programs of their own: a config the run above compiled nothing for)
    cfg, params = model
    monkeypatch.setattr(steps_for(cfg), "sample_tokens", _sample_tokens_before)
    _, before, _ = _sampling_run(
        (dataclasses.replace(cfg, max_seq_len=cfg.max_seq_len + 1), params), monkeypatch)
    assert final == before
    assert len(final[SAMPLED]) == BUDGETS[SAMPLED]


def test_speculation_keeps_every_step_it_does_not_finish_or_admit_on():
    """``spec`` is the third condition: a speculating engine's steps are kept
    for it wherever no finish and no admission came first; none runs ahead."""
    model = _family_model("dense")
    eng = _pipeline_engine(model, spec=True, spec_max_draft=3, prefix=True)
    _serve(eng, _requests(model[0]))
    m = eng.metrics
    assert m.steps_kept["spec"] > 0 and m.steps_ahead == 0
    assert sum(m.steps_kept.values()) + m.steps_fresh == m.decode_steps


# --- the stall detector --------------------------------------------------------


def test_a_gap_is_a_stall_only_against_its_own_kind_and_once_the_kind_is_known():
    m = DecodeMetrics()
    for _ in range(metrics_mod.MEDIAN_MIN - 1):
        assert not m.record_step("ahead", 0.020)
    # too few of its kind to tell (and one such gap does not move a median)
    assert not m.record_step("ahead", 5.0)
    assert not m.record_step("ahead", 0.079)     # under 4 medians
    assert m.record_step("ahead", 1.0)
    assert (m.stalled_steps, m.stalled_s) == (1, pytest.approx(0.98))
    # a kept step is judged against kept steps only, and the stalled step did
    # not move its kind's median
    assert not m.record_step("finish", 1.0)
    assert m.record_step("ahead", 0.081)
    # what the same call's admissions took is not the step's: a prefill in
    # the gap is no stall, a stall beside a prefill still is one
    assert not m.record_step("ahead", 0.120, admit_s=0.095)
    assert m.record_step("ahead", 0.600, admit_s=0.095)
    assert (m.stalled_steps, m.stalled_s) == (3, pytest.approx(0.98 + 0.061 + 0.485))
    assert m.step_gap_s["ahead"] == pytest.approx(7 * 0.020 + 5.0 + 0.079 + 1.0 + 0.081 + 0.72)


def _slow_fetch(eng, monkeypatch, step_s, prefill_s=0.0):
    """Every decode step's one sync waits ``step_s(k)`` for the k-th step
    (from 1), every prefill's ``prefill_s``: the host's clock is then the
    test's, whatever the CPU does."""
    real_fetch, calls = eng._fetch, {"steps": 0}

    def fetch(out, aux, step=True):
        if step:
            calls["steps"] += 1
            time.sleep(step_s(calls["steps"]))
        elif out:
            time.sleep(prefill_s)
        return real_fetch(out, aux, step=step)

    monkeypatch.setattr(eng, "_fetch", fetch)


def test_a_step_made_slow_is_counted_and_logged_once_with_its_phase(monkeypatch, caplog):
    """A sleep patched into ``_fetch`` (the step's one sync): every decode
    step waits 10 ms there, the twentieth 400 ms — that one alone counts in
    ``stalled_steps`` / ``tony_serve_stalled_steps_total`` and logs one line
    that names the step and ``sync``; ``reset_metrics()`` starts the count
    again."""
    model = _family_model("dense")
    cfg = model[0]
    eng = _pipeline_engine(model, shrink=False)  # a pool shrunk at the finish is a stall of its own
    req = Request(prompt=_prompts(cfg, [6], seed=3)[0], max_new_tokens=40)
    eng.run([req])  # every program built (each such step a stall of its own: not this test's)
    eng.reset_metrics()
    caplog.clear()
    _slow_fetch(eng, monkeypatch, lambda k: 0.4 if k == 20 else 0.01)
    with caplog.at_level(logging.WARNING, logger="tony_tpu.serve.engine"):
        eng.run([req])
    m = eng.metrics
    assert m.decode_steps == 39 and m.stalled_steps == 1
    assert m.stalled_s == pytest.approx(0.39, abs=0.05)
    assert eng._c_stalled.value == 1 and eng.stats_snapshot()["stalled_steps"] == 1.0
    lines = [r.getMessage() for r in caplog.records if "stalled" in r.getMessage()]
    assert len(lines) == 1
    # steps are numbered from 0 since reset_metrics(); the first was fresh
    assert lines[0].startswith("decode step 19 stalled (ahead, 1 live, 0 admitted)")
    assert "largest phase: sync" in lines[0]
    assert m.steps_fresh == 1 and m.steps_ahead == 38
    eng.reset_metrics()
    assert eng.metrics.stalled_steps == 0 and eng._c_stalled.value == 0


def test_an_admission_beside_a_step_is_no_stall(monkeypatch, caplog):
    """An eos is found a device step late, so the request that takes the slot
    is admitted while a step is in flight, and the step that call emits ran
    AHEAD: its gap holds the prefill (100 ms here, against steps of 20 ms).
    That is the admission's time, not a stall of the step."""
    model = _family_model("dense")
    cfg = model[0]
    prompts = _prompts(cfg, [6, 9, 5], seed=5)
    greedy = _pipeline_engine(model, slots=2, shrink=False).run(
        [Request(prompt=prompts[1], max_new_tokens=30)])[0].tokens
    reqs = [Request(prompt=prompts[0], max_new_tokens=40),
            Request(prompt=prompts[1], max_new_tokens=30,
                    eos_id=greedy[_eos_index(greedy, 12)]),
            Request(prompt=prompts[2], max_new_tokens=12)]
    eng = _pipeline_engine(model, slots=2, shrink=False)
    for _ in range(2):  # every program built, those of the grown pool too
        eng.run(reqs)
    eng.reset_metrics()
    caplog.clear()
    _slow_fetch(eng, monkeypatch, lambda k: 0.02, prefill_s=0.1)
    over_inflight, real_admit = [], eng._admit_one

    def admit_one(slot, rid, req):
        over_inflight.append(eng._inflight is not None)
        return real_admit(slot, rid, req)

    monkeypatch.setattr(eng, "_admit_one", admit_one)
    with caplog.at_level(logging.WARNING, logger="tony_tpu.serve.engine"):
        done = eng.run(reqs)
    assert [done[rid].finish_reason for rid in sorted(done)] == ["length", "eos", "length"]
    assert over_inflight == [False, False, True]
    m = eng.metrics
    # the gap of the step beside the admission did hold the prefill ...
    assert m.step_gap_s["ahead"] > m.steps_ahead * 0.02 + 0.09
    # ... and no step stalled
    assert m.stalled_steps == 0 and eng._c_stalled.value == 0
    assert not [r for r in caplog.records if "stalled" in r.getMessage()]
