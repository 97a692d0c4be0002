"""Live time-series recorder (obs/series.py) + fleet read paths + the
engine stats surface + `tony top` rendering.

The SLO engine's own rule/windowing behaviour lives in tests/test_slo.py;
the disarmed-seam cost guards live in tests/test_perf_guard.py; the GL005
call-site contract in tests/test_lint.py."""

import json
import os
import time

from tony_tpu.obs import series
from tony_tpu.obs.registry import Histogram, HistogramWindow


def _mkrec(tmp_path, **kw):
    kw.setdefault("sample_every", 1)
    return series.SeriesRecorder(
        str(tmp_path / "series" / "p0.jsonl"), "p0", **kw
    )


class TestRecorder:
    def test_scrape_merges_sources_and_journals(self, tmp_path):
        rec = _mkrec(tmp_path)
        rec.attach("a", lambda: {"x": 1.0})
        rec.attach("b", lambda: {"y": 2.0})
        point = rec.force_sample(step=3)
        assert point["x"] == 1.0 and point["y"] == 2.0 and point["step"] == 3
        assert "ts" in point
        rec.detach("b")
        rec.force_sample()
        assert rec.drain()
        rec.close()
        procs = series.read_series(str(tmp_path / "series"))
        assert list(procs) == ["p0"]
        assert len(procs["p0"]) == 2
        assert procs["p0"][0]["y"] == 2.0
        assert "y" not in procs["p0"][1]  # detached source gone

    def test_stride_counts_and_broken_source_is_isolated(self, tmp_path):
        rec = _mkrec(tmp_path, sample_every=4)
        calls = []
        rec.attach("good", lambda: calls.append(1) or {"ok": 1.0})

        def boom():
            raise RuntimeError("source died")

        rec.attach("bad", boom)
        for _ in range(7):
            rec.sample()
        assert len(calls) == 1  # one stride hit in 7 calls at stride 4
        assert rec.ring[-1]["ok"] == 1.0  # the broken source cost itself only
        rec.close()

    def test_rotation_keeps_newest_window(self, tmp_path):
        rec = _mkrec(tmp_path, max_journal_mb=1)
        # ~64KB per point x 40 > 2MB: forces at least one rotation
        blob = "x" * 65536
        for i in range(40):
            rec.force_sample(i=i, pad=blob)
        assert rec.drain(timeout_s=10.0)
        rec.close()
        names = sorted(os.listdir(tmp_path / "series"))
        assert "p0.jsonl" in names and "p0.0.jsonl" in names
        points = series.read_series(str(tmp_path / "series"))["p0"]
        # the NEWEST point always survives rotation; the oldest rolled off
        assert points[-1]["i"] == 39
        assert points[0]["i"] > 0

    def test_torn_tail_is_skipped(self, tmp_path):
        rec = _mkrec(tmp_path)
        rec.force_sample(i=1)
        rec.drain()
        rec.close()
        path = tmp_path / "series" / "p0.jsonl"
        with open(path, "a") as f:
            f.write('{"ts": 99, "i":')  # SIGKILL mid-line
        points = series.read_series(str(tmp_path / "series"))["p0"]
        assert [p["i"] for p in points] == [1]

    def test_observer_sees_points_on_writer_thread(self, tmp_path):
        rec = _mkrec(tmp_path)
        import threading

        seen = []
        rec.add_observer(lambda p: seen.append((threading.get_ident(), p)))
        rec.force_sample(v=7)
        assert rec.drain()
        rec.close()
        assert len(seen) == 1
        tid, point = seen[0]
        assert point["v"] == 7
        assert tid != threading.get_ident()  # evaluated off the hot path


class TestFleetRollup:
    def test_staleness_labels_and_clock_skew(self, tmp_path):
        sdir = tmp_path / "series"
        sdir.mkdir()
        now = time.time()
        # host A: fresh but with a clock 120s AHEAD (skewed into the future)
        (sdir / "a.jsonl").write_text(
            json.dumps({"ts": now + 120, "step": 5}) + "\n"
        )
        # host B: dead for 10 minutes
        (sdir / "b.jsonl").write_text(
            "".join(
                json.dumps({"ts": now - 660 + i, "step": i}) + "\n"
                for i in range(3)
            )
        )
        roll = series.fleet_rollup(str(tmp_path), now=now)
        # skewed-ahead host clamps to 0, never negative (and never hides b)
        assert roll["procs"]["a"]["age_s"] == 0.0
        assert roll["procs"]["b"]["age_s"] > 600
        assert roll["procs"]["b"]["latest"]["step"] == 2
        assert roll["procs"]["b"]["n"] == 3

    def test_missing_dir_is_empty_not_error(self, tmp_path):
        assert series.fleet_rollup(str(tmp_path))["procs"] == {}
        assert series.read_series(str(tmp_path / "nope")) == {}
        assert series.freshness(str(tmp_path)) == {}

    def test_freshness_is_stat_only(self, tmp_path):
        sdir = tmp_path / "series"
        sdir.mkdir()
        (sdir / "w.jsonl").write_text('{"ts": 1}\n')
        (sdir / "w.0.jsonl").write_text('{"ts": 0}\n')  # rotated window
        old = time.time() - 100
        os.utime(sdir / "w.jsonl", (old, old))
        os.utime(sdir / "w.0.jsonl", (old - 500, old - 500))
        fresh = series.freshness(str(tmp_path))
        # one entry per proc (rotated window merged), newest mtime wins
        assert list(fresh) == ["w"]
        assert 90 < fresh["w"]["age_s"] < 120
        assert fresh["w"]["bytes"] > 0


class TestHistogramWindow:
    def test_delta_quantiles_are_windowed(self):
        h = Histogram("t", {}, buckets=(0.1, 1.0, 10.0))
        win = HistogramWindow()
        for _ in range(10):
            h.observe(0.05)  # warmup: all tiny
        d1 = win.delta(h)
        assert d1["count"] == 10 and d1["p99"] <= 0.1
        for _ in range(10):
            h.observe(5.0)  # the incident window: all slow
        d2 = win.delta(h)
        assert d2["count"] == 10
        # the WINDOW shows the incident; the cumulative view dilutes it
        assert d2["p50"] > 1.0
        assert h.quantile(0.5) <= 1.0
        # empty window: zeros, no stale carryover
        d3 = win.delta(h)
        assert d3["count"] == 0 and d3["p50"] == 0.0

    def test_replaced_histogram_rebaselines(self):
        win = HistogramWindow()
        h1 = Histogram("t", {}, buckets=(1.0,))
        h1.observe(0.5)
        assert win.delta(h1)["count"] == 1
        h2 = Histogram("t", {}, buckets=(1.0,))  # reset_metrics analogue
        h2.observe(0.5)
        d = win.delta(h2)
        assert d["count"] == 1  # not negative, not 0


class TestInstallFromEnv:
    def test_journal_under_app_dir_and_disable(self, tmp_path, monkeypatch):
        series.uninstall()
        monkeypatch.setenv("TONY_APP_DIR", str(tmp_path))
        monkeypatch.setenv(series.ENV_SAMPLE, "1")
        monkeypatch.setenv("TONY_TRACE_PROC", "worker_0_user")
        try:
            rec = series.install_from_env()
            assert rec is series.active_recorder()
            assert rec.sample_every == 1
            rec.attach("t", lambda: {"v": 1.0})
            rec.force_sample()
            rec.drain()
        finally:
            series.uninstall()
        procs = series.read_series(str(tmp_path / "series"))
        assert "worker_0_user" in procs
        # disabled: nothing arms
        monkeypatch.setenv(series.ENV_ENABLED, "0")
        assert series.install_from_env() is None
        series.uninstall()


class TestPortalSeries:
    def test_api_series_rollup_merges_journals_and_am(self, tmp_path):
        from tony_tpu.obs.portal import PortalData

        app = tmp_path / "app-1"
        sdir = app / "series"
        sdir.mkdir(parents=True)
        now = time.time()
        (sdir / "worker_0_user.jsonl").write_text(
            json.dumps({"ts": now, "step": 7, "queue_depth": 2}) + "\n"
        )
        (sdir / "am_rollup.json").write_text(json.dumps({
            "ts": now - 300,
            "tasks": {"remote:0": {
                "last_ts": now - 300, "age_s": 0.0,  # the AM's stale lie
                "points": [{"ts": now - 300, "step": 3}],
            }},
        }))
        data = PortalData(str(tmp_path))
        roll = data.series_rollup("app-1")
        assert roll["procs"]["worker_0_user"]["latest"]["step"] == 7
        # staleness re-labelled against NOW, not the AM's write time
        assert roll["am_rollup"]["tasks"]["remote:0"]["age_s"] > 250
        assert roll["am_rollup"]["rollup_age_s"] > 250
        fleet = data.series_summaries()
        assert set(fleet["app-1"]["procs"]) == {"worker_0_user", "remote:0"}
        assert data.series_rollup("no-such-app") is None

    def test_metrics_snapshots_carry_age_gauge(self, tmp_path):
        from tony_tpu.obs.portal import PortalData

        mdir = tmp_path / "app-1" / "metrics"
        mdir.mkdir(parents=True)
        (mdir / "w.json").write_text(json.dumps({
            "proc": "w",
            "metrics": [{"kind": "counter", "name": "tony_x_total",
                         "help": "", "labels": {}, "value": 1}],
        }))
        old = time.time() - 500
        os.utime(mdir / "w.json", (old, old))
        data = PortalData(str(tmp_path))
        text = data.prometheus()
        assert "tony_x_total" in text
        # the snapshot-derived series are staleness-labelled
        line = next(
            l for l in text.splitlines()
            if l.startswith("tony_snapshot_age_seconds{")
        )
        assert 'app="app-1"' in line and 'proc="w"' in line
        assert float(line.rsplit(" ", 1)[1]) > 400
        # and the portal's own LIVE registry is served alongside
        data.count_request("metrics")
        assert "tony_portal_requests_total" in data.prometheus()


class TestEngineStatsSnapshot:
    def test_snapshot_is_the_one_stats_surface(self, tmp_path):
        import jax

        from tony_tpu.models.llama import LlamaConfig, init_params
        from tony_tpu.serve.engine import Engine, Request, ServeConfig

        series.uninstall()
        rec = series.install(series.SeriesRecorder(
            str(tmp_path / "series" / "serve.jsonl"), "serve", sample_every=1,
        ))
        try:
            cfg = LlamaConfig.tiny()
            eng = Engine(
                init_params(jax.random.key(0), cfg), cfg,
                ServeConfig(slots=2, max_len=64),
            )
            snap0 = eng.stats_snapshot()
            assert snap0["queue_depth"] == 0 and snap0["slots"] == 2
            done = eng.run([
                Request(prompt=[1, 2, 3], max_new_tokens=4),
                Request(prompt=[4, 5], max_new_tokens=4),
            ])
            assert len(done) == 2
            snap = eng.stats_snapshot()
            assert snap["requests_finished"] == 2
            assert snap["generated_tokens"] >= 8
            assert snap["ttft_p99_s"] > 0  # cumulative quantiles present
            eng.close()
        finally:
            series.uninstall()
        # the decode loop scraped the engine source into the journal
        points = series.read_series(str(tmp_path / "series"))["serve"]
        assert points, "decode steps never scraped the series"
        assert any("occupancy" in p for p in points)
        # windowed quantiles landed (ttft observed within the run)
        assert any(p.get("ttft_p99_s", 0) > 0 for p in points)


class TestTonyTop:
    def test_once_frame_renders_rows_slo_and_staleness(self, tmp_path):
        from tony_tpu.obs.top import build_view, render, sparkline

        app = tmp_path / "app-top"
        sdir = app / "series"
        sdir.mkdir(parents=True)
        now = time.time()
        (sdir / "decode_0_user.jsonl").write_text("".join(
            json.dumps({
                "ts": now - 10 + i, "queue_depth": i, "occupancy": 0.5,
                "ttft_p99_s": 0.2, "stalled_steps": 3.0,
            }) + "\n"
            for i in range(8)
        ))
        (sdir / "decode_1_user.jsonl").write_text(
            json.dumps({"ts": now - 120, "queue_depth": 0}) + "\n"
        )
        slo_dir = app / "slo"
        slo_dir.mkdir()
        (slo_dir / "verdict_decode_0_user.json").write_text(json.dumps({
            "verdict": "tripped", "proc": "decode_0_user",
            "slos": {"ttft_p99_s": {"trips": 4}},
        }))
        (app / "status.json").write_text(
            json.dumps({"state": "RUNNING", "exit_code": "", "tasks": []})
        )
        view = build_view(str(app), now=now)
        rows = {r["proc"]: r for r in view["rows"]}
        assert view["slo"]["verdict"] == "tripped"
        assert rows["decode_0_user"]["slo"] == "TRIP:ttft_p99_s"
        assert rows["decode_1_user"]["slo"] == "ok"
        assert rows["decode_1_user"]["stale"]  # 120s-old series marked
        assert rows["decode_0_user"]["trend"]  # sparkline data present
        frame = render(view)
        assert "decode_0_user" in frame and "TRIP:ttft_p99_s" in frame
        assert "ttft_p99" in frame  # the column header
        # the engine's stalled decode steps have their column (the other row has none)
        head, row = (next(ln for ln in frame.splitlines() if ln.startswith(k))
                     for k in ("proc", "decode_0_user"))
        assert row[head.index("stalls"):head.index("stalls") + 6].split() == ["3"]
        # sparkline maths: monotone values render monotone glyphs
        s = sparkline([0, 1, 2, 3])
        assert len(s) == 4 and s[0] == "▁" and s[-1] == "█"
        assert sparkline([]) == ""
        assert sparkline([2, 2, 2]) == "▄▄▄"

    def test_run_top_once_exits_zero(self, tmp_path, capsys):
        from tony_tpu.obs.top import run_top

        (tmp_path / "status.json").write_text(
            json.dumps({"state": "SUCCEEDED", "exit_code": 0, "tasks": []})
        )
        assert run_top(str(tmp_path), once=True) == 0
        out = capsys.readouterr().out
        assert "tony top" in out and "no series yet" in out
