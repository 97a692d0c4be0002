"""Tests for the HBM observatory (obs/hbm.py), the compile ledger
(obs/compiles.py), the counter-track trace merge, capacity derivation
(serve/capacity.py), and the OOM forensics flow."""

import gzip
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.obs import hbm, trace
from tony_tpu.obs.compiles import (
    aot_analysis, get_ledger, read_app_ledgers, snapshot_to_app_dir,
    summarize,
)


class FakeStats:
    """Deterministic per-device stats provider: tests script the live /
    cumulative-peak sequence the real allocator would produce."""

    def __init__(self, *readings):
        self.readings = list(readings)
        self.i = 0

    def push(self, *readings):
        self.readings.extend(readings)

    def __call__(self):
        r = self.readings[min(self.i, len(self.readings) - 1)]
        self.i += 1
        return [
            ("dev0", {"bytes_in_use": live, "peak_bytes_in_use": peak,
                      "bytes_limit": 1000})
            for live, peak in [r]
        ]


@pytest.fixture(autouse=True)
def _disarm():
    """Every test starts and ends disarmed (fit()/engine runs elsewhere in
    the suite may have armed the process-global watch)."""
    hbm.uninstall()
    yield
    hbm.uninstall()


class TestPhaseWatermarks:
    def test_phase_that_advances_cumulative_peak_owns_it(self):
        # enter at live=100 (cum peak 150); inside, the allocator peaks at
        # 400; exit at live=120 — the phase owns the 400 mark exactly
        stats = FakeStats((100, 150), (120, 400))
        watch = hbm.HbmWatch(stats_fn=stats)
        with watch.phase("alloc") as ph:
            pass
        rec = ph.result["devices"]["dev0"]
        assert rec["peak_bytes"] == 400 and rec["peak_exact"] is True
        assert rec["delta_peak_bytes"] == 300  # above the entering live
        assert rec["live_start_bytes"] == 100
        assert rec["live_end_bytes"] == 120
        assert rec["live_delta_bytes"] == 20
        assert rec["limit_bytes"] == 1000

    def test_phase_under_an_earlier_peak_never_inherits_it(self):
        # THE caveat this class kills: the cumulative counter still says
        # 400 (an earlier phase's mark), but this phase only touched
        # 120->180 live — it must report a live-bound peak, not 400
        stats = FakeStats((120, 400), (180, 400))
        watch = hbm.HbmWatch(stats_fn=stats)
        with watch.phase("quiet") as ph:
            pass
        rec = ph.result["devices"]["dev0"]
        assert rec["peak_exact"] is False
        assert rec["peak_bytes"] == 180  # max(live_start, live_end)
        assert rec["delta_peak_bytes"] == 60

    def test_consecutive_phases_are_independently_scoped(self):
        stats = FakeStats((0, 0), (0, 500), (10, 500), (20, 500))
        watch = hbm.HbmWatch(stats_fn=stats)
        with watch.phase("big") as big:
            pass
        with watch.phase("small") as small:
            pass
        assert big.result["devices"]["dev0"]["peak_bytes"] == 500
        assert big.result["devices"]["dev0"]["peak_exact"] is True
        # the second phase does NOT report the first one's 500
        assert small.result["devices"]["dev0"]["peak_bytes"] == 20
        assert small.result["devices"]["dev0"]["peak_exact"] is False
        assert [p["name"] for p in watch.phases] == ["big", "small"]

    def test_bench_keys_flatten_device0(self):
        watch = hbm.HbmWatch(stats_fn=FakeStats((0, 0), (2**30, 2 * 2**30)))
        with watch.phase("p") as ph:
            pass
        keys = ph.bench_keys()
        assert keys["phase_peak_hbm_gb"] == 2.0
        assert keys["live_end_gb"] == 1.0
        assert keys["peak_exact"] is True
        # no stats -> no keys (platforms without memory_stats)
        watch2 = hbm.HbmWatch(stats_fn=lambda: [])
        with watch2.phase("p") as ph2:
            pass
        assert ph2.bench_keys() == {}

    def test_watermark_across_real_device_allocations(self):
        """On platforms exposing memory_stats (real TPU/GPU), an explicit
        allocation inside a phase must show up in its delta; elsewhere the
        default stats source yields nothing and the phase stays empty."""
        watch = hbm.HbmWatch()
        nbytes = 4 * 2**20
        with watch.phase("alloc") as ph:
            arr = jnp.ones((nbytes // 4,), jnp.float32)
            arr.block_until_ready()
        if not ph.result["devices"]:
            pytest.skip("platform exposes no memory_stats")
        rec = next(iter(ph.result["devices"].values()))
        assert rec["delta_peak_bytes"] >= nbytes
        del arr


class TestSampling:
    def test_stride_and_history(self):
        stats = FakeStats((10, 10))
        watch = hbm.HbmWatch(stats_fn=stats, sample_every=4, history=8)
        got = [watch.sample() for _ in range(8)]
        assert sum(1 for g in got if g is not None) == 2  # every 4th
        assert len(watch.history) == 2
        assert watch.history[0]["dev0"]["live_bytes"] == 10

    def test_sample_updates_registry_gauges(self):
        from tony_tpu.obs.registry import Registry

        reg = Registry()
        watch = hbm.HbmWatch(
            stats_fn=FakeStats((7, 9)), registry=reg, sample_every=1
        )
        watch.sample()
        snap = {(e["name"], e["labels"].get("device")): e["value"]
                for e in reg.snapshot()}
        assert snap[("tony_hbm_live_bytes", "dev0")] == 7
        assert snap[("tony_hbm_peak_bytes", "dev0")] == 9

    def test_module_seam_disarmed_is_inert_and_armed_records(self):
        assert hbm.active_watch() is None
        hbm.sample()  # no-op, no error
        watch = hbm.install(hbm.HbmWatch(stats_fn=FakeStats((1, 1)),
                                         sample_every=1))
        hbm.sample()
        assert len(watch.history) == 1

    def test_install_from_env_gating(self, monkeypatch):
        monkeypatch.setenv(hbm.ENV_ENABLED, "0")
        assert hbm.install_from_env() is None
        monkeypatch.setenv(hbm.ENV_ENABLED, "1")
        monkeypatch.setenv(hbm.ENV_SAMPLE, "7")
        monkeypatch.setenv(hbm.ENV_HISTORY, "33")
        watch = hbm.install_from_env()
        assert watch is not None and watch.sample_every == 7
        assert watch.history.maxlen == 33
        # idempotent: a second arm keeps the installed watch
        assert hbm.install_from_env() is watch


class TestCounterTracks:
    def test_samples_land_as_counter_rows_in_merged_chrome_trace(self, tmp_path):
        """The acceptance path: armed tracer + armed watch -> ph:"C" rows
        in the journal -> a per-device memory counter track in the merged
        Chrome trace (valid JSON, numeric series)."""
        from tony_tpu.obs.trace_tool import load_journals, merge_chrome

        tracer = trace.Tracer(
            str(tmp_path / "trace" / "w.jsonl"), "w", "t",
            flush_interval_s=999.0,
        )
        trace.install(tracer)
        try:
            watch = hbm.install(hbm.HbmWatch(
                stats_fn=FakeStats((2**30, 2**30), (2 * 2**30, 3 * 2**30)),
                sample_every=1,
            ))
            watch.sample()
            watch.sample()
        finally:
            trace.uninstall()
        procs = load_journals(str(tmp_path / "trace"))
        assert len(procs[0]["counters"]) == 2
        merged = merge_chrome(str(tmp_path), procs)
        json.dumps(merged)  # serializable end-to-end
        counters = [e for e in merged["traceEvents"] if e["ph"] == "C"]
        assert len(counters) == 2
        c = counters[0]
        assert c["name"] == "hbm.dev0" and c["pid"] >= 1
        assert c["args"]["live_gb"] == 1.0 and c["args"]["peak_gb"] == 1.0
        assert counters[1]["args"]["peak_gb"] == 3.0


class TestCompileLedger:
    def test_exactly_one_entry_per_fresh_compile_zero_on_cache_hit(self):
        ledger = get_ledger()
        x = jnp.arange(11.0)  # pays its own compiles before the window
        f = jax.jit(lambda v: v * 2.5 + 1)
        n0 = ledger.backend_compiles
        f(x).block_until_ready()
        assert ledger.backend_compiles - n0 == 1  # exactly one fresh
        n1 = ledger.backend_compiles
        f(x).block_until_ready()
        assert ledger.backend_compiles - n1 == 0  # cache hit journals nothing

    def test_label_attributes_the_compile(self):
        ledger = get_ledger()
        x = jnp.arange(5.0)
        with ledger.label("my.entry"):
            jax.jit(lambda v: v - 0.5)(x)
        mine = [e for e in ledger.entries("backend") if e["fn"] == "my.entry"]
        assert len(mine) == 1 and mine[0]["dur_s"] >= 0
        # outside the scope, entries are anonymous again
        jax.jit(lambda v: v + 0.25)(x)
        assert ledger.entries()[-1]["fn"] == ""

    def test_record_aot_captures_memory_plan_and_flops(self):
        ledger = get_ledger()
        aval = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        compiled = jax.jit(lambda a: a @ a).lower(aval).compile()
        entry = ledger.record_aot("mm64", compiled, 0.5)
        assert entry["kind"] == "aot" and entry["fn"] == "mm64"
        assert entry["argument_bytes"] == 64 * 64 * 4
        assert entry["output_bytes"] == 64 * 64 * 4
        assert entry["flops"] > 0
        assert ledger.entries("aot")[-1] == entry
        # the standalone analysis helper agrees
        assert aot_analysis(compiled)["argument_bytes"] == 64 * 64 * 4

    def test_sanitize_compile_count_is_the_ledger_counter(self):
        """One listener serves watchdog and journal: they cannot disagree."""
        from tony_tpu.analysis import sanitize

        ledger = get_ledger()
        assert sanitize.compile_count() == ledger.backend_compiles
        jax.jit(lambda v: v * 7)(jnp.arange(3.0))
        assert sanitize.compile_count() == ledger.backend_compiles

    def test_snapshot_roundtrip_and_cli_report(self, tmp_path, monkeypatch, capsys):
        from tony_tpu.cli.main import main as cli_main

        app_dir = tmp_path / "app-1"
        app_dir.mkdir()
        monkeypatch.setenv("TONY_APP_DIR", str(app_dir))
        monkeypatch.setenv("TONY_TRACE_PROC", "worker_0_user_a0")
        ledger = get_ledger()
        with ledger.label("roundtrip"):
            jax.jit(lambda v: v / 3)(jnp.arange(9.0))
        path = snapshot_to_app_dir()
        assert path.endswith(os.path.join("compiles", "worker_0_user_a0.json"))
        ledgers = read_app_ledgers(str(app_dir))
        assert "worker_0_user_a0" in ledgers
        summary = summarize(ledgers)
        proc = summary["processes"]["worker_0_user_a0"]
        assert proc["backend_compiles"] >= 1
        assert any(e["fn"] == "roundtrip" for e in proc["entries"])
        # the CLI prints the same report
        assert cli_main(["compiles", str(app_dir)]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["total_backend_compiles"] >= 1
        # and exits 1 when there is nothing to report
        empty = tmp_path / "app-2"
        empty.mkdir()
        assert cli_main(["compiles", str(empty)]) == 1


class TestCapacityDerivation:
    def test_budget_refuses_unmeasured_backends(self, monkeypatch):
        """No memory_analysis -> raise (bench falls back to the labelled
        formula), never a zero-margin budget wearing the measured label."""
        from tony_tpu.models.llama import LlamaConfig
        from tony_tpu.serve import capacity

        monkeypatch.setattr(capacity, "aot_analysis", lambda compiled: {})
        with pytest.raises(RuntimeError, match="no memory_analysis"):
            capacity.derive_slot_budget(
                LlamaConfig.tiny(), max_len=32, hbm_bytes=2**28, kv_block=16
            )

    def test_slot_budget_from_memory_analysis(self):
        """The measured budget replaces the 0.92 guess: components are
        positive and consistent, the repeat layout admits fewer slots by
        roughly the GQA factor, and more HBM means more slots."""
        from tony_tpu.models.llama import LlamaConfig
        from tony_tpu.serve.capacity import derive_slot_budget

        cfg = LlamaConfig.tiny()  # 4:2 GQA -> repeat factor 2
        b = derive_slot_budget(cfg, max_len=64, hbm_bytes=256 * 2**20,
                               kv_block=16)
        assert b["source"] == "memory_analysis"
        assert b["param_bytes"] > 0
        assert b["kv_bytes_per_slot_repeat"] == (
            b["kv_bytes_per_slot_native"] * cfg.n_heads // cfg.n_kv_heads
        )
        assert 0 < b["max_slots_repeat"] <= b["max_slots_native"]
        bigger = derive_slot_budget(cfg, max_len=64,
                                    hbm_bytes=512 * 2**20, kv_block=16)
        assert bigger["max_slots_native"] > b["max_slots_native"]

    def test_decode_step_analysis_measures_the_cache(self):
        """argument bytes grow with capacity by exactly the added KV bytes
        — the analysis is reading the real compiled plan, not a formula."""
        from tony_tpu.models.llama import LlamaConfig
        from tony_tpu.serve.capacity import decode_step_analysis

        cfg = LlamaConfig.tiny()
        small = decode_step_analysis(cfg, slots=2, capacity=16, kv_block=16)
        big = decode_step_analysis(cfg, slots=2, capacity=64, kv_block=16)
        # paged layout: the argument side is the physical-block pool plus
        # the per-slot block table — growth is exactly their sum
        assert big["argument_bytes"] - small["argument_bytes"] == (
            (big["cache_bytes"] - small["cache_bytes"])
            + (big["table_bytes"] - small["table_bytes"])
        )


class TestOomForensics:
    def _arm(self, tmp_path, monkeypatch):
        app_dir = tmp_path / "app-oom"
        app_dir.mkdir()
        monkeypatch.setenv("TONY_APP_DIR", str(app_dir))
        monkeypatch.setenv("TONY_TRACE_PROC", "worker_0_user_a0")
        watch = hbm.install(hbm.HbmWatch(
            stats_fn=FakeStats((100, 900)), sample_every=1
        ))
        with watch.phase("before"):
            pass
        watch.sample()
        return app_dir

    def test_resource_exhausted_dumps_and_reraises(self, tmp_path, monkeypatch):
        app_dir = self._arm(tmp_path, monkeypatch)
        err = RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 12345 bytes"
        )
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            with hbm.oom_guard("fit"):
                raise err
        files = hbm.forensics_files(str(app_dir))
        assert "worker_0_user_a0_fit.json" in files
        with open(app_dir / "oom" / "worker_0_user_a0_fit.json") as f:
            report = json.load(f)
        assert report["where"] == "fit"
        assert "RESOURCE_EXHAUSTED" in report["error"]
        # the watermark history and ledger rode along
        assert report["hbm"]["phases"][0]["name"] == "before"
        assert report["hbm"]["history"]
        assert "backend_compiles" in report.get("compiles", {})
        # the device memory profile is ONE gzip layer over the pprof proto
        # (device_memory_profile returns gzipped bytes; dump_oom must not
        # wrap them again or pprof cannot read the artifact)
        prof = app_dir / "oom" / "worker_0_user_a0_fit.memprof.pb.gz"
        if prof.exists():
            proto = gzip.decompress(prof.read_bytes())
            assert not proto.startswith(b"\x1f\x8b"), "double-gzipped profile"

    def test_non_oom_errors_pass_through_untouched(self, tmp_path, monkeypatch):
        app_dir = self._arm(tmp_path, monkeypatch)
        with pytest.raises(ValueError):
            with hbm.oom_guard("fit"):
                raise ValueError("not a memory problem")
        assert hbm.forensics_files(str(app_dir)) == []

    def test_engine_run_oom_lands_in_app_dir(self, tmp_path, monkeypatch):
        """The wired path: an engine whose decode step dies of (simulated)
        RESOURCE_EXHAUSTED writes forensics from inside run()."""
        from tony_tpu.models.llama import LlamaConfig, init_params
        from tony_tpu.serve import Engine, Request, ServeConfig

        app_dir = self._arm(tmp_path, monkeypatch)
        cfg = LlamaConfig.tiny()
        params = init_params(jax.random.key(0), cfg)
        eng = Engine(params, cfg, ServeConfig(slots=2, max_len=32, kv_block=8))

        def boom():
            raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")

        monkeypatch.setattr(eng, "_decode_once", boom)
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            eng.run([Request(prompt=np.arange(4), max_new_tokens=4)])
        assert any(
            name.endswith("engine.run.json")
            for name in hbm.forensics_files(str(app_dir))
        )

    def test_chaos_result_lists_forensics(self, tmp_path):
        from tony_tpu.chaos.invariants import InvariantReport
        from tony_tpu.chaos.runner import ChaosRunResult

        (tmp_path / "oom").mkdir()
        (tmp_path / "oom" / "worker_0_user_a0_fit.json").write_text("{}")
        r = ChaosRunResult(
            app_id="a", app_dir=str(tmp_path), exit_code=1, state="FAILED",
            report=InvariantReport(),
            oom_forensics=hbm.forensics_files(str(tmp_path)),
        )
        assert r.to_dict()["oom_forensics"] == ["worker_0_user_a0_fit.json"]


class TestShutdownSummaries:
    def test_fit_final_report_carries_ledger_lines(self, tmp_path, monkeypatch):
        """fit()'s final dict and ledger snapshot: compile count from the
        ledger, peak-HBM when the platform (here: a fake) reports stats."""
        from tony_tpu.models.llama import LlamaConfig
        from tony_tpu.parallel.mesh import MeshShape
        from tony_tpu.train import DataConfig, FitConfig, fit

        app_dir = tmp_path / "app-fit"
        app_dir.mkdir()
        monkeypatch.setenv("TONY_APP_DIR", str(app_dir))
        monkeypatch.setenv("TONY_TRACE_PROC", "worker_0_user_a0")
        hbm.install(hbm.HbmWatch(
            stats_fn=FakeStats((2**30, 3 * 2**30)), sample_every=4
        ))
        final = fit(FitConfig(
            model=LlamaConfig.tiny(),
            data=DataConfig(global_batch=4, seq_len=16, vocab_size=128),
            mesh_shape=MeshShape(fsdp=2),
            steps=4, log_every=4, warmup_steps=1,
        ))
        assert final["xla_compiles"] >= 1  # the train step compiled
        # run-scoped peak: the fake's cumulative counter (3GB) never
        # advanced during the run, so the run reports its own live bound
        # (1GB), NOT the inherited process peak — the attribution rule
        assert final["peak_hbm_gb"] == 1.0
        assert final["peak_hbm_exact"] is False
        # the HBM gauges landed in the job-history metrics snapshot (the
        # portal /metrics source), not only on the process-global registry
        snap_path = app_dir / "metrics" / "worker_0_user_a0_fit.json"
        with open(snap_path) as f:
            snap = json.load(f)
        gauges = {m["name"]: m["value"] for m in snap["metrics"]
                  if m["name"].startswith("tony_hbm_")}
        assert gauges["tony_hbm_live_bytes"] == 2**30
        assert gauges["tony_hbm_peak_bytes"] == 3 * 2**30
        # the process ledger landed for `tony compiles`
        ledgers = read_app_ledgers(str(app_dir))
        assert "worker_0_user_a0" in ledgers
        aot = [e for e in ledgers["worker_0_user_a0"]["entries"]
               if e.get("kind") == "aot"]
        assert any(e["fn"] == "train.step" for e in aot)
        step_entry = next(e for e in aot if e["fn"] == "train.step")
        # the measured memory plan is attached (compile-ahead AOT path)
        assert step_entry["argument_bytes"] > 0

    def test_engine_close_carries_ledger_lines(self):
        from tony_tpu.models.llama import LlamaConfig, init_params
        from tony_tpu.serve import Engine, Request, ServeConfig
        from tony_tpu.serve import engine as engine_mod

        # many test files run this very engine, and the worker that ran one
        # of them before this file would serve every program from the
        # module-wide step caches: no compile to count. Start from none.
        engine_mod._prefill_fn.cache_clear()
        engine_mod._decode_fn.cache_clear()
        engine_mod._aot_decode_cache.clear()
        cfg = LlamaConfig.tiny()
        params = init_params(jax.random.key(0), cfg)
        eng = Engine(params, cfg, ServeConfig(slots=2, max_len=32, kv_block=8))
        eng.run([Request(prompt=np.arange(3), max_new_tokens=3, rng=0)])
        s = eng.close()
        assert s["xla_compiles"] >= 1  # prefill + decode compiled
        # the decode step's AOT entry carries its measured memory plan
        aot = get_ledger().entries("aot")
        decode = [e for e in aot if e["fn"].startswith("serve.decode[")]
        assert decode and decode[-1]["argument_bytes"] > 0
