"""Numerics health sentinel: in-graph monitors, anomaly rules, forensics.

Covers the third flight-recorder axis (docs/OBS.md "Numerics health"):
the fused value monitors compute the right numbers, every anomaly rule
trips on its designed signal and latches, a trip produces a parseable
forensics bundle + verdict file + trace instant, the portal /healthz and
`tony health` surface the verdict, the chaos invariant checker refuses to
report clean over a tripped verdict, and a real chaos-style job proves
injection -> trip -> forensics end to end across processes.
"""

import json
import os
import sys
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.obs import health, trace
from tony_tpu.obs.health import HealthRules, HealthSentinel


@pytest.fixture(autouse=True)
def _disarm():
    """Every test starts and ends disarmed (fit()/Engine arm the process
    global from env; leakage across tests would blend rule windows)."""
    health.uninstall()
    yield
    health.uninstall()


def make_sentinel(tmp_path=None, rules=None, **kw):
    kw.setdefault("sample_every", 1)
    return health.install(HealthSentinel(
        rules or HealthRules(),
        app_dir=str(tmp_path) if tmp_path is not None else "",
        proc="worker_0_user_a0",
        **kw,
    ))


def feed(sentinel, samples):
    for s in samples:
        sentinel.sample(**s)
    assert sentinel.drain(timeout_s=10.0)


def train_sample(step, loss, grad_norm=1.0, **h):
    metrics = {"step": step, "loss": loss, "grad_norm": grad_norm}
    metrics.update({f"health/{k}": v for k, v in h.items()})
    return {"metrics": metrics}


# --- in-graph monitors --------------------------------------------------------


class TestGraphMonitors:
    def test_nonfinite_counts_and_update_ratio(self):
        loss = jnp.float32(jnp.nan)
        grads = {"a": jnp.array([1.0, jnp.inf, jnp.nan]), "b": jnp.zeros((4,))}
        params = {"a": jnp.array([1.0, 2.0, jnp.nan]), "b": jnp.ones((4,))}
        updates = {"a": jnp.full((3,), 0.1), "b": jnp.full((4,), 0.1)}
        out = jax.jit(health.graph_monitors)(
            loss, grads, params, updates, jnp.zeros((2, 4), jnp.int32)
        )
        assert float(out["health/nonfinite_loss"]) == 1.0
        assert float(out["health/nonfinite_grads"]) == 2.0
        assert float(out["health/nonfinite_params"]) == 1.0
        # |Δ|/|θ| with a NaN'd param norm propagates NaN (itself a signal)
        assert not np.isfinite(float(out["health/update_ratio"]))
        clean = jax.jit(health.graph_monitors)(
            jnp.float32(1.0),
            {"a": jnp.ones((3,))}, {"a": jnp.full((3,), 2.0)},
            {"a": jnp.full((3,), 0.2)}, jnp.zeros((2, 4), jnp.int32),
        )
        assert float(clean["health/nonfinite_grads"]) == 0.0
        np.testing.assert_allclose(
            float(clean["health/update_ratio"]), 0.1, rtol=1e-5
        )

    def test_int_leaves_are_ignored(self):
        # token tables / step counters must not poison the float reductions
        grads = {"a": jnp.ones((3,)), "steps": jnp.zeros((2,), jnp.int32)}
        out = health.graph_monitors(
            jnp.float32(0.0), grads, grads, grads,
            jnp.zeros((1, 2), jnp.int32),
        )
        assert float(out["health/nonfinite_grads"]) == 0.0

    def test_layer_grad_rms_attributes_the_bad_layer(self):
        L = 4
        layers = {"w": jnp.ones((L, 8, 8)), "b": jnp.zeros((L, 8))}
        grads = {"layers": layers, "lm_head": jnp.ones((8, 8))}
        rms = health.layer_grad_rms(grads)
        assert rms.shape == (L,)
        # poison layer 2: its RMS blows up, the others stay put
        bad = {"layers": {"w": layers["w"].at[2].set(100.0), "b": layers["b"]},
               "lm_head": grads["lm_head"]}
        rms_bad = np.asarray(health.layer_grad_rms(bad))
        assert int(np.argmax(rms_bad)) == 2
        assert rms_bad[2] > 10 * rms_bad[1]
        assert health.layer_grad_rms({"lm_head": jnp.ones((4,))}) is None

    def test_batch_fingerprint_semantics(self):
        a = jnp.arange(32, dtype=jnp.int32).reshape(2, 16)
        b = a[::-1]  # same tokens, permuted rows
        fa = int(health.batch_fingerprint(a))
        assert fa == int(health.batch_fingerprint(a))  # deterministic
        assert fa != int(health.batch_fingerprint(b))  # position-weighted
        assert fa != int(health.batch_fingerprint(a + 1))

    def test_decode_monitors_per_slot(self):
        V = 64
        logits = np.zeros((3, V), np.float32)
        logits[1, 5] = np.nan
        logits[2, 7] = 1000.0  # collapsed one-hot distribution
        out = jax.jit(health.decode_monitors)(jnp.asarray(logits))
        nf = np.asarray(out["logits_nonfinite"])
        ent = np.asarray(out["entropy"])
        assert nf.tolist() == [0.0, 1.0, 0.0]
        assert abs(ent[0] - np.log(V)) < 1e-3  # uniform row: ln V nats
        assert ent[2] < 1e-3                   # one-hot row: ~0


# --- the rule engine ----------------------------------------------------------


class TestRuleEngine:
    def test_nonfinite_trips_dumps_bundle_and_verdict(self, tmp_path):
        s = make_sentinel(tmp_path)
        feed(s, [
            train_sample(1, 2.0, nonfinite_grads=0.0),
            train_sample(2, float("nan"), nonfinite_loss=1.0,
                         nonfinite_grads=3.0, batch_fingerprint=77.0),
        ])
        assert s.verdict == "tripped"
        assert s.trip_counts() == {"nonfinite": 1}
        files = health.forensics_files(str(tmp_path))
        assert files == ["worker_0_user_a0_nonfinite_step2.trip.json"]
        with open(tmp_path / "health" / files[0]) as f:
            bundle = json.load(f)
        assert bundle["rule"] == "nonfinite"
        assert bundle["step"] == 2
        assert bundle["detail"]["counts"]["nonfinite_grads"] == 3.0
        # the last-k ring carries the trajectory INTO the bad step
        assert [r["step"] for r in bundle["ring"]] == [1, 2]
        assert bundle["batch"]["stream_step"] == 2
        verdicts = health.read_verdicts(str(tmp_path))
        assert verdicts["worker_0_user_a0"]["verdict"] == "tripped"
        assert "nonfinite" in verdicts["worker_0_user_a0"]["rules"]

    def test_trips_latch_one_bundle_per_cause(self, tmp_path):
        s = make_sentinel(tmp_path)
        feed(s, [train_sample(i, float("nan")) for i in range(1, 6)])
        assert s.trip_counts() == {"nonfinite": 5}  # counted...
        assert len(health.forensics_files(str(tmp_path))) == 1  # ...one bundle

    def test_loss_spike_z_score(self, tmp_path):
        s = make_sentinel(tmp_path, HealthRules(min_samples=8, loss_spike_z=8.0))
        stable = [train_sample(i, 2.0 + 0.01 * (i % 3)) for i in range(1, 20)]
        feed(s, stable)
        assert s.verdict == "healthy"
        feed(s, [train_sample(20, 50.0)])
        assert s.trip_counts() == {"loss_spike": 1}
        detail = s.summary()["detail"]["loss_spike"]
        assert detail["z"] > 8.0 and detail["loss"] == 50.0

    def test_grad_explosion_and_collapse(self, tmp_path):
        s = make_sentinel(tmp_path, HealthRules(grad_explode=100.0))
        feed(s, [train_sample(1, 2.0, grad_norm=1e6)])
        assert "grad_explosion" in s.trip_counts()
        s2 = make_sentinel(tmp_path, HealthRules(collapse_k=3))
        feed(s2, [train_sample(i, 2.0, grad_norm=0.0) for i in range(1, 3)])
        assert "grad_collapse" not in s2.trip_counts()  # needs k consecutive
        feed(s2, [train_sample(3, 2.0, grad_norm=0.0)])
        assert "grad_collapse" in s2.trip_counts()

    def test_stagnation_needs_a_full_flat_window(self, tmp_path):
        s = make_sentinel(tmp_path, HealthRules(window=8))
        feed(s, [train_sample(i, 3.0) for i in range(1, 8)])
        assert s.verdict == "healthy"  # window not yet full
        feed(s, [train_sample(i, 3.0) for i in range(8, 12)])
        assert "stagnation" in s.trip_counts()
        # a moving loss never stagnates
        s2 = make_sentinel(tmp_path, HealthRules(window=8))
        feed(s2, [train_sample(i, 3.0 - 0.01 * i) for i in range(1, 30)])
        assert s2.verdict == "healthy"

    def test_repeated_batch_fingerprint(self, tmp_path):
        s = make_sentinel(tmp_path, HealthRules(repeat_k=3))
        feed(s, [
            train_sample(1, 2.0, batch_fingerprint=11.0),
            train_sample(2, 2.0, batch_fingerprint=22.0),
            train_sample(3, 2.0, batch_fingerprint=22.0),
        ])
        assert s.verdict == "healthy"  # only 2 consecutive
        feed(s, [train_sample(4, 2.0, batch_fingerprint=22.0)])
        assert "repeated_batch" in s.trip_counts()
        assert s.summary()["detail"]["repeated_batch"]["consecutive"] == 3

    def test_step_rewind_resets_rolling_windows(self, tmp_path):
        """A second run re-entering the process (bench sweeps) must not be
        z-scored against the previous run's loss trajectory — and its
        forensics bundle must carry only ITS OWN trajectory, not the
        previous run's ring tail or per-layer snapshot."""
        s = make_sentinel(tmp_path, HealthRules(min_samples=8))
        feed(s, [train_sample(i, 100.0, layer_grad_rms=[9.0, 9.0])
                 for i in range(1, 20)])
        # new run starts at step 1 with a completely different loss scale
        feed(s, [train_sample(i, 2.0 + 0.01 * i) for i in range(1, 4)])
        assert "loss_spike" not in s.trip_counts()
        feed(s, [train_sample(4, float("nan"))])
        name = health.forensics_files(str(tmp_path))[0]
        with open(tmp_path / "health" / name) as f:
            bundle = json.load(f)
        # ring holds run 2's steps only; run 1's layer snapshot is gone
        assert [r["step"] for r in bundle["ring"]] == [1, 2, 3, 4]
        assert bundle["layer_grad_rms"] is None

    def test_partial_metrics_without_loss_never_trip_nonfinite(self, tmp_path):
        """Absence is not NaN: a custom step loop sampling only a subset
        of metrics (no 'loss'/'grad_norm' keys) must not latch a tripped
        verdict on data it simply did not report."""
        s = make_sentinel(tmp_path)
        feed(s, [{"metrics": {"step": i}} for i in range(1, 6)])
        feed(s, [{"metrics": {}}])
        assert s.verdict == "healthy"
        # a PRESENT NaN still trips
        feed(s, [{"metrics": {"step": 7, "grad_norm": float("nan")}}])
        assert "nonfinite" in s.trip_counts()

    def test_checkpoint_pointer_lands_in_bundle(self, tmp_path):
        ckpt = tmp_path / "ckpt"
        (ckpt / "4").mkdir(parents=True)
        (ckpt / "8").mkdir()
        s = make_sentinel(tmp_path, checkpoint_dir=str(ckpt))
        feed(s, [train_sample(1, float("nan"))])
        name = health.forensics_files(str(tmp_path))[0]
        with open(tmp_path / "health" / name) as f:
            bundle = json.load(f)
        assert bundle["checkpoint"] == {"dir": str(ckpt), "latest_step": 8}

    def test_registry_carries_trips_and_verdict(self, tmp_path):
        from tony_tpu.obs.registry import Registry

        live = Registry()
        s = make_sentinel(tmp_path, registry=live)
        feed(s, [train_sample(1, float("nan"))])
        assert live.counter("tony_health_trips_total", rule="nonfinite").value == 1
        assert live.gauge("tony_health_verdict").value == 1.0
        run = Registry()
        s.export(run)
        assert run.counter("tony_health_trips_total", rule="nonfinite").value == 1


# --- serve-side rules ---------------------------------------------------------


class TestServeRules:
    def test_logits_nonfinite_attributes_the_request(self, tmp_path):
        s = make_sentinel(tmp_path)
        feed(s, [{
            "metrics": {"logits_nonfinite": [0.0, 4.0], "entropy": [3.0, 3.0]},
            "slot_rids": [7, 9], "live_slots": [0, 1],
        }])
        assert s.trip_counts() == {"serve_nonfinite": 1}
        detail = s.summary()["detail"]["serve_nonfinite"]
        assert detail["rid"] == 9 and detail["slot"] == 1

    def test_dead_slot_garbage_never_trips(self, tmp_path):
        s = make_sentinel(tmp_path)
        feed(s, [{
            "metrics": {"logits_nonfinite": [0.0, 99.0], "entropy": [3.0, 0.0]},
            "slot_rids": [3, None], "live_slots": [0],  # slot 1 is free
        }])
        assert s.verdict == "healthy"

    def test_entropy_floor_needs_consecutive_low_samples(self, tmp_path):
        s = make_sentinel(tmp_path, HealthRules(entropy_k=3, entropy_floor=0.05))
        low = {"metrics": {"logits_nonfinite": [0.0], "entropy": [0.001]},
               "slot_rids": [5], "live_slots": [0]}
        ok = {"metrics": {"logits_nonfinite": [0.0], "entropy": [4.0]},
              "slot_rids": [5], "live_slots": [0]}
        feed(s, [low, low, ok, low, low])
        assert s.verdict == "healthy"  # the recovery reset the run
        feed(s, [low])
        assert "entropy_floor" in s.trip_counts()
        assert s.summary()["detail"]["entropy_floor"]["rid"] == 5

    def test_engine_nonfinite_logits_trip_end_to_end(self, tmp_path, monkeypatch):
        """The wired path: a NaN'd model serving real requests trips the
        sentinel from inside the jitted decode step's fused monitors, with
        the offending request attributed, and close() reports it."""
        from tony_tpu.models.llama import LlamaConfig, init_params
        from tony_tpu.serve import Engine, Request, ServeConfig

        monkeypatch.setenv("TONY_APP_DIR", str(tmp_path))
        s = make_sentinel(tmp_path)
        cfg = LlamaConfig.tiny()
        params = dict(init_params(jax.random.key(0), cfg))
        params["final_norm"] = params["final_norm"] * jnp.nan
        eng = Engine(params, cfg, ServeConfig(slots=2, max_len=32, kv_block=8))
        eng.run([Request(prompt=np.arange(1, 5), max_new_tokens=4)])
        summary = eng.close()
        assert summary["health_verdict"] == "tripped"
        assert "serve_nonfinite" in summary["health_trips"]
        assert s.summary()["detail"]["serve_nonfinite"]["rid"] == 0
        assert health.forensics_files(str(tmp_path))

    def test_engine_degenerate_sampler_trips_entropy_floor(self, tmp_path):
        """A collapsed output distribution (one-hot logits — the repetition
        -loop signature) trips the entropy-floor detector after k steps."""
        from tony_tpu.models.llama import LlamaConfig, init_params
        from tony_tpu.serve import Engine, Request, ServeConfig

        s = make_sentinel(tmp_path, HealthRules(entropy_k=3))
        cfg = LlamaConfig.tiny()
        params = dict(init_params(jax.random.key(0), cfg))
        lm = np.zeros(params["lm_head"].shape, np.float32)
        lm[:, 7] = 100.0
        params["lm_head"] = jnp.asarray(lm)
        eng = Engine(params, cfg, ServeConfig(slots=2, max_len=64, kv_block=8))
        eng.run([Request(prompt=np.arange(1, 5), max_new_tokens=16)])
        eng.close()
        assert "entropy_floor" in s.trip_counts()
        assert s.summary()["detail"]["entropy_floor"]["rid"] == 0

    def test_disarmed_engine_compiles_no_monitors(self, monkeypatch):
        """With the sentinel disabled the decode step's aux holds no
        monitor, only what ``Engine._fetch`` reads with the tokens — the
        monitors are a compile-time choice, not a masked cost (the engine
        arms itself from env by default)."""
        from tony_tpu.models.llama import LlamaConfig, init_params
        from tony_tpu.serve import Engine, Request, ServeConfig

        monkeypatch.setenv(health.ENV_ENABLED, "0")
        cfg = LlamaConfig.tiny()
        params = init_params(jax.random.key(0), cfg)
        eng = Engine(params, cfg, ServeConfig(slots=2, max_len=32, kv_block=8))
        assert eng._monitors is False
        from tony_tpu.serve.engine import _AUX_FETCHED, _decode_fn

        step = _decode_fn(cfg, "scan", 8, eng.serve.max_top_k, eng._monitors)
        out = step(params, eng.cache, eng._table_dev, eng.state)
        assert set(out[-1]) <= set(_AUX_FETCHED)


# --- fit() integration --------------------------------------------------------


class TestFitIntegration:
    def _fit(self, steps=12, **kw):
        from tony_tpu.models.llama import LlamaConfig
        from tony_tpu.parallel.mesh import MeshShape
        from tony_tpu.train import DataConfig, FitConfig, fit

        return fit(FitConfig(
            model=LlamaConfig.tiny(),
            data=DataConfig(global_batch=4, seq_len=32, vocab_size=256),
            mesh_shape=MeshShape(fsdp=2),
            steps=steps, log_every=steps, warmup_steps=2, **kw,
        ))

    def test_injected_nan_trips_and_instant_sits_between_step_spans(
        self, tmp_path, monkeypatch
    ):
        """The acceptance path in-process: TONY_CHAOS_NAN_STEP poisons the
        loss from step 5, the sentinel trips `nonfinite`, the forensics
        bundle + verdict land under <app_dir>/health/, fit()'s final report
        carries the verdict, and the health.nonfinite trace instant sits
        between the train.step spans it interrupted."""
        monkeypatch.setenv("TONY_CHAOS_NAN_STEP", "5")
        monkeypatch.setenv("TONY_APP_DIR", str(tmp_path))
        monkeypatch.setenv("TONY_TRACE_PROC", "worker_0_user_a0")
        make_sentinel(tmp_path)
        tracer = trace.install(trace.Tracer(
            str(tmp_path / "trace" / "worker_0_user_a0.jsonl"),
            "worker_0_user_a0", "healthtrace", sample_steps=1,
        ))
        try:
            final = self._fit(steps=12)
        finally:
            trace.uninstall()
        assert final["health_verdict"] == "tripped"
        assert final["health_trips"] == {"nonfinite": pytest.approx(8, abs=4)}
        files = health.forensics_files(str(tmp_path))
        assert files == ["worker_0_user_a0_nonfinite_step5.trip.json"]
        with open(tmp_path / "health" / files[0]) as f:
            bundle = json.load(f)
        assert bundle["rule"] == "nonfinite"
        assert bundle["step"] == 5
        assert bundle["layer_grad_rms"]  # per-layer stats rode along
        assert [r["step"] for r in bundle["ring"]] == list(range(1, 6))
        # tony_health_* reached the job-history metrics snapshot
        with open(tmp_path / "metrics" / "worker_0_user_a0_fit.json") as f:
            snap = json.load(f)
        by_name = {(m["name"], tuple(sorted(m["labels"].items()))): m
                   for m in snap["metrics"]}
        assert by_name[("tony_health_verdict", ())]["value"] == 1.0
        # the instant sits between the step spans it interrupted
        recs = [json.loads(l) for l in
                open(tmp_path / "trace" / "worker_0_user_a0.jsonl")
                if l.strip()]
        instants = [r for r in recs
                    if r.get("ph") == "i" and r["name"] == "health.nonfinite"]
        assert len(instants) == 1 and instants[0]["args"]["step"] == 5
        steps = sorted(
            (r for r in recs
             if r.get("ph") == "X" and r["name"] == "train.step"),
            key=lambda r: r["ts"],
        )
        ts = instants[0]["ts"]
        assert steps[0]["ts"] < ts < steps[-1]["ts"] + steps[-1]["dur"]

    def test_clean_fit_reports_healthy(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TONY_APP_DIR", str(tmp_path))
        monkeypatch.setenv("TONY_TRACE_PROC", "worker_0_user_a0")
        make_sentinel(tmp_path)
        final = self._fit(steps=8)
        assert final["health_verdict"] == "healthy"
        assert "health_trips" not in final
        assert health.forensics_files(str(tmp_path)) == []
        verdicts = health.read_verdicts(str(tmp_path))
        assert verdicts["worker_0_user_a0"]["verdict"] == "healthy"

    @pytest.mark.slow  # pays a full fit to assert an absent report key; the
    # disarmed-compiles-nothing contract is covered by the serve-side
    # disarmed test and install_from_env is asserted inline (870s budget)
    def test_health_disabled_by_env(self, monkeypatch):
        monkeypatch.setenv(health.ENV_ENABLED, "0")
        assert health.install_from_env() is None
        final = self._fit(steps=4)
        assert "health_verdict" not in final


# --- portal /healthz + drop counter -------------------------------------------


class TestPortal:
    def _mk_app(self, root, app_id, verdict=None, status="SUCCEEDED"):
        app = root / app_id
        app.mkdir(parents=True, exist_ok=True)
        (app / "status.json").write_text(json.dumps(
            {"state": status, "exit_code": 0 if status == "SUCCEEDED" else 1,
             "tasks": []}
        ))
        if verdict is not None:
            (app / "health").mkdir(exist_ok=True)
            (app / "health" / "verdict_worker_0.json").write_text(json.dumps({
                "verdict": verdict, "proc": "worker_0",
                "rules": {"nonfinite": {"trips": 2, "step": 5}}
                if verdict == "tripped" else {},
            }))
            if verdict == "tripped":
                (app / "health" / "worker_0_nonfinite_step5.trip.json"
                 ).write_text("{}")
        return app

    def test_healthz_endpoints(self, tmp_path):
        from tony_tpu.obs.portal import serve_portal

        self._mk_app(tmp_path, "app-ok", verdict="healthy")
        self._mk_app(tmp_path, "app-bad", verdict="tripped")
        self._mk_app(tmp_path, "app-old")  # predates the sentinel
        server, port = serve_portal(str(tmp_path), port=0, host="127.0.0.1")
        import threading

        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz"
            ) as r:
                fleet = json.loads(r.read())
            assert fleet["app-ok"]["verdict"] == "healthy"
            assert fleet["app-bad"]["verdict"] == "tripped"
            assert fleet["app-bad"]["rules"] == {"nonfinite": 2}
            assert fleet["app-old"]["verdict"] == "unknown"
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz/app-ok"
            ) as r:
                assert json.loads(r.read())["verdict"] == "healthy"
            # a tripped app answers 503: probe-friendly without parsing
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz/app-bad"
                )
            assert exc.value.code == 503
            detail = json.loads(exc.value.read())
            assert detail["bundles"] == ["worker_0_nonfinite_step5.trip.json"]
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz/no-such-app"
                )
        finally:
            server.shutdown()

    def test_nonfinite_metric_drops_are_counted_not_hidden(self, tmp_path):
        """The satellite fix: the chart filter still excludes NaN/Inf from
        polylines (they'd poison the min/max) but every drop lands in the
        tony_portal_nonfinite_dropped counter on /metrics — counted once
        per distinct sample, not once per page render (an auto-refreshing
        dashboard must not inflate the counter)."""
        from tony_tpu.obs.portal import PortalData, _metric_series

        app = self._mk_app(tmp_path, "app-nan")
        (app / "events").mkdir()
        (app / "events" / "e.jhist.jsonl").write_text(
            json.dumps({"type": "METRICS", "task": "worker:0",
                        "samples": {"loss": 1.5, "mfu": 0.4}}) + "\n"
            + json.dumps({"type": "METRICS", "task": "worker:0",
                          "samples": {"loss": float("nan"),
                                      "mfu": float("inf")}}) + "\n"
            + json.dumps({"type": "METRICS", "task": "worker:0",
                          "samples": {"loss": 1.7, "mfu": 0.41}}) + "\n"
        )
        data = PortalData(str(tmp_path))
        detail = data.job("app-nan")
        series = _metric_series(detail["events"])
        # finite values still chart; the poisoned sample is excluded
        assert series["worker:0"]["loss"] == [1.5, 1.7]
        assert data.nonfinite_dropped.value == 2.0
        # re-rendering the same page counts nothing new...
        data.job("app-nan")
        data.job("app-nan")
        assert data.nonfinite_dropped.value == 2.0
        # ...a genuinely new poisoned sample does
        with open(app / "events" / "e.jhist.jsonl", "a") as f:
            f.write(json.dumps({"type": "METRICS", "task": "worker:0",
                                "samples": {"loss": float("-inf")}}) + "\n")
        data.job("app-nan")
        assert data.nonfinite_dropped.value == 3.0
        assert "tony_portal_nonfinite_dropped" in data.prometheus()


# --- tony health CLI ----------------------------------------------------------


class TestCli:
    def test_rollup_exit_codes_and_bundles(self, tmp_path, capsys):
        from tony_tpu.cli.main import main

        app = tmp_path / "app-h"
        (app / "health").mkdir(parents=True)
        (app / "health" / "verdict_worker_0.json").write_text(json.dumps({
            "verdict": "tripped", "proc": "worker_0",
            "rules": {"loss_spike": {"trips": 1, "step": 9, "z": 11.2}},
        }))
        (app / "health" / "worker_0_loss_spike_step9.trip.json").write_text(
            json.dumps({"rule": "loss_spike", "step": 9, "ring": []})
        )
        assert main(["health", str(app), "--bundles"]) == 1  # tripped
        out = json.loads(capsys.readouterr().out)
        assert out["verdict"] == "tripped"
        assert out["rules"] == {"loss_spike": 1}
        assert out["bundle_contents"][
            "worker_0_loss_spike_step9.trip.json"]["step"] == 9
        # healthy app: exit 0
        (app / "health" / "verdict_worker_0.json").write_text(json.dumps({
            "verdict": "healthy", "proc": "worker_0", "rules": {},
        }))
        os.remove(app / "health" / "worker_0_loss_spike_step9.trip.json")
        assert main(["health", str(app)]) == 0
        # no health data at all: exit 2, absence is not read as healthy
        bare = tmp_path / "app-bare"
        bare.mkdir()
        assert main(["health", str(bare)]) == 2


# --- chaos invariant: tripped verdicts cannot report clean --------------------


class TestInvariant:
    def _mk_terminal_app(self, tmp_path, state="SUCCEEDED", verdict=None):
        from tony_tpu.am.events import EventType

        app = tmp_path / "app-inv"
        (app / "events").mkdir(parents=True)
        code = 0 if state == "SUCCEEDED" else 1
        (app / "status.json").write_text(json.dumps(
            {"state": state, "exit_code": code, "tasks": []}
        ))
        (app / "events" / "a.jhist.jsonl").write_text(json.dumps(
            {"type": EventType.APPLICATION_FINISHED, "state": state}
        ) + "\n")
        if verdict is not None:
            (app / "health").mkdir()
            (app / "health" / "verdict_worker_0.json").write_text(json.dumps({
                "verdict": verdict, "proc": "worker_0",
                "rules": {"nonfinite": {"trips": 3}}
                if verdict == "tripped" else {},
            }))
        return app

    def test_succeeded_with_tripped_verdict_is_a_violation(self, tmp_path):
        from tony_tpu.chaos.invariants import check_invariants

        app = self._mk_terminal_app(tmp_path, "SUCCEEDED", verdict="tripped")
        report = check_invariants(str(app))
        assert not report.ok
        v = [x for x in report.violations
             if x.invariant == "health-verdict-surfaced"]
        assert len(v) == 1
        assert "silently ruined" in v[0].detail
        assert "nonfinite" in v[0].detail

    def test_died_with_tripped_verdict_is_a_violation(self, tmp_path):
        from tony_tpu.chaos.invariants import check_invariants

        app = self._mk_terminal_app(tmp_path, "FAILED", verdict="tripped")
        report = check_invariants(str(app))
        assert any(
            x.invariant == "health-verdict-surfaced" for x in report.violations
        )

    def test_healthy_verdict_stays_clean(self, tmp_path):
        from tony_tpu.chaos.invariants import check_invariants

        app = self._mk_terminal_app(tmp_path, "SUCCEEDED", verdict="healthy")
        report = check_invariants(str(app))
        assert report.ok, report.to_json()


# --- end-to-end: chaos-style NaN-injection job --------------------------------


def test_health_chaos_job_end_to_end(tmp_path):
    """Tier-1 acceptance: a REAL client -> AM -> executor job runs fit()
    with a NaN injected at step 5 (the numerics chaos seam rides the
    worker env exactly like a chaos fault schedule). Default sampling
    strides prove the trip lands within one stride; the forensics bundle
    is parseable from the app dir; `tony health` rolls the verdict up;
    the invariant checker refuses to report the run clean; and the merged
    trace carries the health instant between the step spans."""
    from tony_tpu.chaos.invariants import check_invariants
    from tony_tpu.cli.client import TonyClient
    from tony_tpu.cli.main import main as cli_main
    from tony_tpu.config.config import TonyConfig

    src = tmp_path / "src"
    src.mkdir()
    (src / "train.py").write_text(
        "import logging\n"
        "logging.basicConfig(level=logging.INFO)\n"
        "from tony_tpu.train import fit, FitConfig\n"
        "from tony_tpu.train.data import DataConfig\n"
        "from tony_tpu.models.llama import LlamaConfig\n"
        "out = fit(FitConfig(\n"
        "    model=LlamaConfig.tiny(),\n"
        "    data=DataConfig(global_batch=8, seq_len=32, vocab_size=128),\n"
        "    steps=24, log_every=8, warmup_steps=2))\n"
        "print('HEALTH VERDICT', out.get('health_verdict'))\n"
    )
    cfg = TonyConfig.load(overrides={
        "task.heartbeat_interval_ms": 200,
        "task.max_missed_heartbeats": 10,
        "application.timeout_s": 240,
        "application.stage_dir": str(tmp_path),
        "application.name": "nan-chaos",
        "application.framework": "jax",
        "job.worker.instances": 1,
        "job.worker.command": f"{sys.executable} train.py",
        # the numerics fault + every-step trace spans so the instant's
        # position between steps is assertable; health knobs stay DEFAULT
        # (obs.health.sample_steps=16) — the injected NaN at step 5 must
        # trip by sample step 16, i.e. within one sampling stride
        "job.worker.env": [
            "JAX_PLATFORMS=cpu", "TONY_CHAOS_NAN_STEP=5",
        ],
        "trace.sample_steps": 1,
    })
    client = TonyClient(cfg, src_dir=str(src))
    code = client.run(quiet=True)
    app_dir = client.app_dir
    if code != 0:
        logs_dir = os.path.join(app_dir, "logs")
        for n in sorted(os.listdir(logs_dir)):
            print(f"===== {n}", open(os.path.join(logs_dir, n),
                                     errors="replace").read()[-2000:])
    assert code == 0  # the job "succeeds" — that IS the silent-ruin case

    # the bundle landed and parses
    bundles = health.forensics_files(app_dir)
    assert len(bundles) == 1 and "nonfinite" in bundles[0]
    with open(os.path.join(app_dir, "health", bundles[0])) as f:
        bundle = json.load(f)
    assert bundle["rule"] == "nonfinite"
    # default stride 16: the step-16 sample sees the step-5 NaN — the trip
    # lands within one sampling stride of the first sampled bad step
    assert 5 <= bundle["step"] <= 16
    assert bundle["ring"]  # the trajectory into the trip rode along

    # the verdict reaches `tony health` (exit 1 = tripped)
    assert cli_main(["health", app_dir]) == 1

    # the invariant checker refuses to report this run clean
    report = check_invariants(app_dir)
    assert any(
        v.invariant == "health-verdict-surfaced" for v in report.violations
    ), report.to_json()

    # the health instant sits between the step spans it interrupted in
    # the worker's journal
    trace_dir = os.path.join(app_dir, "trace")
    worker = [n for n in os.listdir(trace_dir) if n.startswith("worker_0")]
    recs = []
    for name in worker:
        with open(os.path.join(trace_dir, name)) as f:
            recs += [json.loads(l) for l in f if l.strip()]
    instants = [r for r in recs
                if r.get("ph") == "i" and r["name"] == "health.nonfinite"]
    assert len(instants) == 1
    steps = sorted(
        (r for r in recs if r.get("ph") == "X" and r["name"] == "train.step"),
        key=lambda r: r["ts"],
    )
    ts = instants[0]["ts"]
    assert steps[0]["ts"] < ts < steps[-1]["ts"] + steps[-1]["dur"]
