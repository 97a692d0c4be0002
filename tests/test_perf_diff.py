"""`tony perf diff` (obs/perf_diff.py): the cross-run regression gate.

The committed fixtures under tests/fixtures/perf/ ARE the tier-1 gate:
the identity diff must stay green, and the regression fixture (tok/s
down ~22%, decode TTFT p99 up ~3.4x) must stay red — a rule change that
stops flagging either breaks here, loudly."""

import json
import os

import pytest

from tony_tpu.obs.perf_diff import (
    DEFAULT_RULES, diff, diff_files, flatten, load_report, rule_for,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "perf")
BASE = os.path.join(FIXTURES, "bench_base.json")
REGRESSED = os.path.join(FIXTURES, "bench_regressed.json")


class TestFlattenAndRules:
    def test_flatten_numeric_leaves_only(self):
        flat = flatten({
            "a": 1, "b": {"c": 2.5, "d": "s", "e": True, "f": [1, 2]},
        })
        assert flat == {"a": 1.0, "b.c": 2.5}  # strings/bools/lists excluded

    def test_rule_directions(self):
        assert rule_for("extra.tokens_per_sec_per_chip")[0] == "higher"
        assert rule_for("extra.decode.full_slot.ttft_p99_s")[0] == "lower"
        assert rule_for("extra.loss")[0] == "lower"
        assert rule_for("extra.peak_hbm_gb")[0] == "lower"
        assert rule_for("extra.n_params")[0] == "config"
        assert rule_for("extra.batch")[0] == "config"
        assert rule_for("vs_baseline")[0] == "skip"
        assert rule_for("extra.xla_compiles")[0] == "lower"
        assert rule_for("extra.gqa_capacity.slots")[0] == "higher"
        # headroom is higher-better DESPITE carrying 'hbm': a collapse
        # must flag as a regression, not pass as a memory improvement
        assert rule_for("decode_0.hbm_headroom_frac")[0] == "higher"
        # step anatomy (obs/anatomy.py): overlap + achieved bandwidth are
        # higher-better, exposed collective time lower-better
        assert rule_for("extra.step_anatomy.overlap_frac")[0] == "higher"
        assert rule_for(
            "extra.step_anatomy.top_collective.achieved_gbps"
        )[0] == "higher"
        assert rule_for(
            "extra.step_anatomy.exposed_collective_ms"
        )[0] == "lower"
        # the payload is program configuration, not a measurement: a
        # sharding change's bigger all-reduce must report as
        # config_changed, never as a memory regression
        assert rule_for(
            "extra.step_anatomy.top_collective.bytes"
        )[0] == "config"
        # decomposed-collective overlap (ops/overlap.py, bench `overlap`
        # section): the on/off exposed and step-time ratios are
        # lower-better (drifting toward 1.0 means the decomposition
        # stopped paying); the grad-bucket budget is sized FROM the
        # measured bandwidth, so it is configuration identity, never a
        # memory metric; the within-run loss delta is a value-safety
        # cross-check (~0), never judged relatively
        assert rule_for("extra.overlap.overlap_frac")[0] == "higher"
        assert rule_for("extra.overlap.exposed_collective_ms")[0] == "lower"
        assert rule_for("extra.overlap.exposed_ratio")[0] == "lower"
        assert rule_for("extra.overlap.step_ms_ratio")[0] == "lower"
        assert rule_for("extra.overlap.grad_bucket_bytes")[0] == "config"
        assert rule_for("extra.overlap.loss_delta")[0] == "skip"
        assert rule_for("extra.overlap.on.pure_comm_steps")[0] == "skip"
        assert rule_for(
            "extra.overlap.on.top_collective.achieved_gbps"
        )[0] == "higher"
        # prefix store (serve/prefix.py): hit rate is higher-better; the
        # on/off TTFT and prefill-FLOPs ratios are lower-better (a ratio
        # drifting toward 1.0 means the reuse stopped paying); residency
        # is trace-shaped, never judged
        assert rule_for(
            "extra.decode.prefix_trace.prefix_on.prefix_hit_rate"
        )[0] == "higher"
        assert rule_for(
            "extra.decode.prefix_trace.ttft_p50_ratio"
        )[0] == "lower"
        assert rule_for(
            "extra.decode.prefix_trace.prefill_flops_ratio"
        )[0] == "lower"
        assert rule_for(
            "extra.decode.prefix_trace.prefix_on.ttft_p99_s"
        )[0] == "lower"
        assert rule_for("decode_0.prefix_resident_mb")[0] == "skip"
        # speculative decoding (serve/spec.py): tokens/step, accept rate
        # and the on/off speedup are higher-better; rollbacks are
        # trace-shaped; the draft depth is configuration; the compile
        # count falls through to the zero-tolerance compile rule
        assert rule_for(
            "extra.decode.spec_trace.b1_on.tokens_per_step"
        )[0] == "higher"
        assert rule_for(
            "extra.decode.spec_trace.b1_on.accept_rate"
        )[0] == "higher"
        assert rule_for("extra.decode.spec_trace.speedup_b1")[0] == "higher"
        assert rule_for("decode_0.spec_rollbacks")[0] == "skip"
        assert rule_for("extra.decode.spec_trace.max_draft")[0] == "config"
        assert rule_for(
            "extra.decode.spec_trace.b1_on.decode_compiles"
        )[0] == "lower"
        # quantized serving (serve/cache.py, bench decode.quant +
        # gqa_capacity): the measured slot budget and the quant/bf16
        # ratio are higher-better — they carry no memory token, so
        # without their own rule a budget collapse would go unjudged;
        # the stated accuracy tolerance and KV dtype are configuration
        # identity (loosening the tolerance must be a visible config
        # change, never judged "within tolerance")
        assert rule_for("extra.gqa_capacity.max_slots_quant")[0] == "higher"
        assert rule_for("extra.gqa_capacity.max_slots_native")[0] == "higher"
        assert rule_for("extra.gqa_capacity.quant_slot_ratio")[0] == "higher"
        assert rule_for("extra.decode.quant.tolerance")[0] == "config"
        assert rule_for("extra.decode.quant.quant_on.tok_s_slot")[0] == "higher"
        assert rule_for(
            "extra.decode.quant.quant_on.kv_bytes_per_token"
        )[0] == "lower"
        assert rule_for(
            "extra.decode.quant.quant_on.peak_hbm_gb"
        )[0] == "lower"
        # disaggregated serving (bench decode.disagg): the chunked/
        # unchunked TPOT-p99 ratio is lower-better (drifting toward 1.0
        # means chunked prefill stopped bounding the long-prompt
        # interference); the chunk size and scenario prompt length are
        # configuration identity; the handoff payload is trace-shaped —
        # bytes/blocks (and the per-host shipped/adopted/freed counters
        # in series rollups) must never be judged as memory, while the
        # handoff wall time stays a judged latency
        assert rule_for(
            "extra.decode.disagg.tpot_p99_chunked_ratio"
        )[0] == "lower"
        assert rule_for(
            "extra.decode.disagg.chunked_colocated.tpot_p99_s"
        )[0] == "lower"
        assert rule_for("extra.decode.disagg.chunk_tokens")[0] == "config"
        assert rule_for(
            "extra.decode.disagg.long_prompt_tokens"
        )[0] == "config"
        assert rule_for(
            "extra.decode.disagg.unchunked_pooled.handoff_bytes"
        )[0] == "skip"
        assert rule_for(
            "extra.decode.disagg.unchunked_pooled.handoff_blocks"
        )[0] == "skip"
        assert rule_for("decode_0.handoff_shipped_blocks")[0] == "skip"
        assert rule_for(
            "extra.decode.disagg.unchunked_pooled.handoff_ms"
        )[0] == "lower"
        # MoE fast path (bench moe_top2, round 20): the grouped/gather
        # throughput ratio is higher-better — the PR-4 bench gate,
        # finally judged instead of parked in a docstring; the dispatch
        # decision flags are configuration identity, so a silent flip
        # back to gather surfaces as config_changed, never as a
        # throughput footnote; the overlap subsection (chunked ep
        # combine OFF/ON) rides the decomposed-collective rules above,
        # and its chunk size — derived from the OFF capture's measured
        # bandwidth — is configuration, not a metric
        assert rule_for("extra.moe_top2.grouped_vs_gather")[0] == "higher"
        assert rule_for("extra.moe_top2.dispatch_gate_holds")[0] == "config"
        assert rule_for(
            "extra.moe_top2.dispatch_default_grouped"
        )[0] == "config"
        assert rule_for("extra.moe_top2.mfu")[0] == "higher"
        assert rule_for(
            "extra.moe_top2.tokens_per_sec_per_chip"
        )[0] == "higher"
        assert rule_for("extra.moe_top2.overlap.chunk_tokens")[0] == "config"
        assert rule_for("extra.moe_top2.overlap.exposed_ratio")[0] == "lower"
        assert rule_for(
            "extra.moe_top2.overlap.exposed_collective_ms"
        )[0] == "lower"
        assert rule_for("extra.moe_top2.overlap.step_ms_ratio")[0] == "lower"
        assert rule_for("extra.moe_top2.overlap.overlap_frac")[0] == "higher"
        assert rule_for("extra.moe_top2.overlap.loss_delta")[0] == "skip"

    def test_headroom_collapse_is_a_regression(self):
        v = diff(
            {"p": {"hbm_headroom_frac": 0.5}},
            {"p": {"hbm_headroom_frac": 0.1}},
        )
        assert not v["ok"]
        assert v["regressions"][0]["key"] == "p.hbm_headroom_frac"


class TestVerdict:
    def test_identity_diff_is_green(self):
        base = load_report(BASE)
        v = diff(base, base)
        assert v["ok"] and v["regressions"] == [] and v["compared"] > 5
        assert v["config_changed"] == []

    def test_regression_fixture_is_red_with_the_right_keys(self):
        v = diff_files(BASE, REGRESSED)
        assert not v["ok"]
        keys = {r["key"] for r in v["regressions"]}
        assert "extra.tokens_per_sec_per_chip" in keys
        assert "extra.decode.full_slot.ttft_p99_s" in keys
        assert "extra.mfu" in keys
        # the anatomy section gates too: an overlap collapse, a grown
        # exposed-collective cost, and a bandwidth drop all flag
        assert "extra.step_anatomy.overlap_frac" in keys
        assert "extra.step_anatomy.exposed_collective_ms" in keys
        assert "extra.step_anatomy.top_collective.achieved_gbps" in keys
        # the overlap section gates too: a collapse of the decomposed
        # rings (overlap_frac down, exposed time back up, the on/off
        # ratios drifting past 1.0) all flag
        assert "extra.overlap.overlap_frac" in keys
        assert "extra.overlap.exposed_ratio" in keys
        assert "extra.overlap.step_ms_ratio" in keys
        assert "extra.overlap.on.exposed_collective_ms" in keys
        # the elastic section gates too: warm-restart cost (both the
        # journal number and the trace-goodput one) and the post-shrink
        # step-time ratio all flag
        assert "extra.elastic.restart_s" in keys
        assert "extra.elastic.goodput.restart_s" in keys
        assert "extra.elastic.shrunk_step_ratio" in keys
        # the prefix-store section gates too: a hit-rate collapse, the
        # on/off TTFT ratio drifting past 1.0, and tail FLOPs growing back
        # toward the full-prompt cost all flag
        assert "extra.decode.prefix_trace.prefix_on.prefix_hit_rate" in keys
        assert "extra.decode.prefix_trace.ttft_p50_ratio" in keys
        assert "extra.decode.prefix_trace.prefill_flops_ratio" in keys
        # the speculative-decoding section gates too: an accept-rate
        # collapse drags tokens/step and the on/off speedup with it
        assert "extra.decode.spec_trace.b1_on.accept_rate" in keys
        assert "extra.decode.spec_trace.b1_on.tokens_per_step" in keys
        assert "extra.decode.spec_trace.speedup_b1" in keys
        # the quantized-serving section gates too: a slot-budget collapse
        # (the capacity headline) and the vanished on/off throughput
        # advantage both flag
        assert "extra.gqa_capacity.max_slots_quant" in keys
        assert "extra.gqa_capacity.quant_slot_ratio" in keys
        assert "extra.decode.quant.tok_s_ratio" in keys
        # the disaggregated-serving section gates too: the chunked TPOT
        # tail blowing back toward the unchunked one (the interference
        # chunking exists to bound) and a slowed handoff both flag; the
        # unchanged payload size stays silent (trace-shaped, skipped)
        assert "extra.decode.disagg.chunked_colocated.tpot_p99_s" in keys
        assert "extra.decode.disagg.tpot_p99_chunked_ratio" in keys
        assert "extra.decode.disagg.chunked_pooled.handoff_ms" in keys
        assert "extra.decode.disagg.chunked_pooled.handoff_bytes" not in keys
        # the MoE fast-path section gates too: the grouped-dispatch
        # advantage vanishing, the MFU headline sliding back to the
        # gather-era number, and the overlapped ep combine re-exposing
        # its collective (ratio drifting toward the OFF capture) all
        # flag; the dispatch flags and chunk size are unchanged, so the
        # red report carries no config noise alongside them
        assert "extra.moe_top2.grouped_vs_gather" in keys
        assert "extra.moe_top2.mfu" in keys
        assert "extra.moe_top2.tokens_per_sec_per_chip" in keys
        assert "extra.moe_top2.overlap.exposed_ratio" in keys
        assert "extra.moe_top2.overlap.overlap_frac" in keys
        assert "extra.moe_top2.overlap.on.exposed_collective_ms" in keys
        assert "extra.moe_top2.dispatch_default_grouped" not in keys
        assert not any("moe_top2" in c["key"] for c in v["config_changed"])
        # within-tolerance drift is NOT flagged
        assert "extra.loss" not in keys          # +0.04% << 2%
        assert "extra.peak_hbm_gb" not in keys   # +1.5% << 10%
        # worst regression leads the report (the fixture's 6.6x tail-FLOPs
        # blowup outranks the 3.4x TTFT one)
        assert v["regressions"][0]["key"] == (
            "extra.decode.prefix_trace.prefill_flops_ratio"
        )

    def test_improvements_and_direction(self):
        base = load_report(BASE)
        better = json.loads(json.dumps(base))
        better["extra"]["tokens_per_sec_per_chip"] *= 1.2
        better["extra"]["decode"]["full_slot"]["ttft_p99_s"] *= 0.5
        v = diff(base, better)
        assert v["ok"]
        keys = {r["key"] for r in v["improvements"]}
        assert "extra.tokens_per_sec_per_chip" in keys
        assert "extra.decode.full_slot.ttft_p99_s" in keys

    def test_config_changes_reported_separately(self):
        base = load_report(BASE)
        changed = json.loads(json.dumps(base))
        changed["extra"]["batch"] = 8
        v = diff(base, changed)
        assert v["ok"]  # a config change is not a perf regression...
        assert v["config_changed"] == [
            {"key": "extra.batch", "old": 4.0, "new": 8.0}
        ]  # ...but it is never hidden

    def test_compile_count_regression_has_zero_tolerance(self):
        base = load_report(BASE)
        worse = json.loads(json.dumps(base))
        worse["extra"]["xla_compiles"] = 4
        v = diff(base, worse)
        assert any(
            r["key"] == "extra.xla_compiles" for r in v["regressions"]
        )

    def test_tol_scale_relaxes_the_gate(self):
        v = diff_files(BASE, REGRESSED, tol_scale=100.0)
        assert v["ok"]

    def test_unjudged_keys_are_listed_not_dropped(self):
        v = diff({"weird_quantity": 1.0}, {"weird_quantity": 2.0})
        assert v["ok"] and v["unjudged"] == ["weird_quantity"]


class TestInputShapes:
    def test_loads_driver_bench_wrappers(self):
        """A driver wrapper (`{"tail": "...", "parsed": ...}`) is a
        first-class input: the report is the last JSON line of the tail.
        The fixture is SYNTHETIC (hand-written, labelled so inside)."""
        path = os.path.join(FIXTURES, "bench_wrapper_synthetic.json")
        report = load_report(path)
        assert report["metric"] == "synthetic_train_tokens_per_sec_per_chip"
        flat = flatten(report)
        assert "extra.tokens_per_sec_per_chip" in flat
        assert diff(report, report)["ok"]

    def test_loads_series_rollups(self, tmp_path):
        def rollup(ttft):
            return {
                "procs": {
                    "decode_0": {
                        "points": [
                            {"ts": i, "ttft_p99_s": ttft, "queue_depth": 2}
                            for i in range(5)
                        ],
                    }
                }
            }

        old_p, new_p = tmp_path / "old.json", tmp_path / "new.json"
        old_p.write_text(json.dumps(rollup(0.1)))
        new_p.write_text(json.dumps(rollup(0.5)))
        v = diff_files(str(old_p), str(new_p))
        assert not v["ok"]
        assert v["regressions"][0]["key"] == "decode_0.ttft_p99_s"

    def test_unusable_input_is_a_clean_error(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text("[1, 2]")
        with pytest.raises(ValueError):
            load_report(str(bad))


class TestCli:
    def test_tony_perf_diff_exit_codes(self, tmp_path, capsys):
        from tony_tpu.cli.main import main

        assert main(["perf", "diff", BASE, BASE]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["ok"] is True
        assert main(["perf", "diff", BASE, REGRESSED]) == 1
        out = json.loads(capsys.readouterr().out)
        assert out["ok"] is False and out["regressions"]
        assert main(
            ["perf", "diff", BASE, str(tmp_path / "missing.json")]
        ) == 2

    def test_first_rule_match_wins_is_ordered(self):
        # ordering sanity: the config rule outranks the latency catch-all,
        # or `steps`-ish keys would be judged as latencies
        idx = {kind: i for i, (_, kind, _) in enumerate(DEFAULT_RULES)}
        assert idx["config"] < idx["lower"]
