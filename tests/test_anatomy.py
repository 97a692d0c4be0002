"""Step anatomy (docs/OBS.md "Step anatomy"): fleet-coordinated profiling
(obs/profile.py), the comms ledger (obs/comms.py), and the per-step budget
report (obs/anatomy.py).

The contract under test, bottom-up:

- HLO collective extraction yields op kind / payload bytes / replica
  groups for a program whose collective set is known by construction;
- the ProfileController captures a real jax.profiler device trace over an
  exact step window, and the anatomy budget's four rows (compute /
  exposed-collective / input-wait / host-blocked) sum to the measured
  step time, with at least one collective row carrying bytes AND measured
  device time;
- the AM broadcast path end to end: a real 2-host job, `tony profile`
  issued over the StartProfile RPC, every host captures, and the merged
  report carries both hosts + the cross-host critical path.
"""

import glob
import json
import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from tony_tpu.obs import anatomy, comms
from tony_tpu.obs import profile as profile_mod


@pytest.fixture(autouse=True)
def _no_leaked_controller():
    yield
    profile_mod.uninstall()


def _psum_program():
    """A tiny shard_map program whose optimized HLO contains exactly one
    known all-reduce over all 8 (virtual) devices."""
    n = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()).reshape(n), ("dp",))

    def f(x, w):
        return jax.lax.psum(jnp.dot(x, w), "dp")

    sf = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P("dp"), P(None, None)), out_specs=P(),
    ))
    x = jnp.ones((n * 16, 64), jnp.float32)
    w = jnp.ones((64, 32), jnp.float32)
    return sf.lower(x, w).compile(), x, w, n


# --- comms ledger: HLO extraction ---------------------------------------------


class TestCommsExtraction:
    def test_known_collective_set_from_compiled_hlo(self):
        compiled, _, _, n = _psum_program()
        rows = comms.extract_collectives(compiled)
        ars = [r for r in rows if r["kind"] == "all-reduce"]
        assert len(ars) == 1, rows
        row = ars[0]
        # result is the reduced f32[1? x 32] block per participant; payload
        # bytes are the result type's size — nonzero and 4-byte aligned
        assert row["bytes"] > 0 and row["bytes"] % 4 == 0
        # the NAME is whatever lowered the op (jax 0.9: `psum_invariant.N`);
        # the kind comes from the opcode
        assert row["name"]
        groups = row["replica_groups"]
        # one group over every device (parsed {{...}} form) or the iota
        # string form — both must name all n participants
        if isinstance(groups, list):
            assert sorted(sum(groups, [])) == list(range(n))
        else:
            assert str(n) in groups

    def test_text_extraction_covers_kinds_and_skips_done(self):
        text = """
  HloModule m
  ENTRY e {
    %p = f32[8,16]{1,0} parameter(0)
    ROOT %all-reduce.1 = f32[8,16]{1,0} all-reduce(%p), channel_id=1, replica_groups={{0,1},{2,3}}, to_apply=%add
    %all-gather-start.2 = f32[32,16]{1,0} all-gather-start(%p), replica_groups=[2,2]<=[4], dimensions={0}
    %all-gather-done.2 = f32[32,16]{1,0} all-gather-done(%all-gather-start.2)
    %reduce-scatter.3 = bf16[4,16]{1,0} reduce-scatter(%p), replica_groups={{0,1}}, dimensions={0}, to_apply=%add
    %collective-permute.4 = f32[8,16]{1,0} collective-permute(%p), source_target_pairs={{0,1},{1,0}}
    %fusion.9 = f32[8,16]{1,0} fusion(%p), kind=kLoop, calls=%fused
  }
  """
        rows = comms.extract_collectives(text)
        kinds = [r["kind"] for r in rows]
        assert kinds == [
            "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
        ]  # -done skipped, fusion not a collective
        by_kind = {r["kind"]: r for r in rows}
        assert by_kind["all-reduce"]["bytes"] == 8 * 16 * 4
        assert by_kind["all-reduce"]["replica_groups"] == [[0, 1], [2, 3]]
        assert by_kind["all-gather"]["bytes"] == 32 * 16 * 4
        assert by_kind["all-gather"]["replica_groups"] == "[2,2]<=[4]"
        assert by_kind["reduce-scatter"]["bytes"] == 4 * 16 * 2  # bf16

    def test_tuple_result_and_scalar_shapes(self):
        assert comms.shape_bytes("f32[]") == 4
        assert comms.shape_bytes("(f32[2,2]{1,0}, u32[4]{0})") == 16 + 16
        assert comms.shape_bytes("weird[3]") == 0  # unknown dtype: no guess

    def test_record_aot_carries_the_collective_rows(self):
        from tony_tpu.obs.compiles import CompileLedger

        compiled, _, _, _ = _psum_program()
        ledger = CompileLedger()
        entry = ledger.record_aot("probe.step", compiled)
        assert any(
            c["kind"] == "all-reduce" and c["bytes"] > 0
            for c in entry.get("collectives", [])
        ), entry
        # and the anatomy flattener finds them back in a snapshot payload
        rows = anatomy.ledger_collectives(ledger.to_dict())
        assert rows and rows[0]["fn"] == "probe.step"


# --- budget attribution rule (pure interval math) -----------------------------


class TestBudgetRule:
    def test_rows_follow_the_attribution_rule_exactly(self):
        manifest = {"step_time_s": [0.010], "input_wait_s": [0.002]}
        trace_data = {
            "found": True,
            "step_windows": [(0.0, 0.010)],
            "compute": [(0.000, 0.004)],
            "collective": [(0.003, 0.006)],
            "collective_events": [
                {"name": "all-reduce.1", "ts": 0.003, "dur_s": 0.003}
            ],
        }
        b = anatomy.step_budget(manifest, trace_data)
        row = b["table"][0]
        assert row["compute_s"] == pytest.approx(0.004)
        # collective 3..6ms, compute covers 3..4ms -> exposed 4..6ms = 2ms
        assert row["exposed_collective_s"] == pytest.approx(0.002)
        assert row["input_wait_s"] == pytest.approx(0.002)
        assert row["host_blocked_s"] == pytest.approx(0.002)
        total = sum(
            row[k] for k in ("compute_s", "exposed_collective_s",
                             "input_wait_s", "host_blocked_s")
        )
        assert total == pytest.approx(row["step_time_s"])
        # overlap: 1ms of the 3ms collective hidden under compute
        assert b["overlap_frac"] == pytest.approx(1 / 3, abs=1e-3)

    def test_pure_comm_steps_flagged_and_excluded_from_overlap(self):
        """A step with collective time but ZERO compute (a standalone
        reduce, a warmup gather) is flagged pure_comm and kept out of the
        aggregate overlap_frac: there was no compute to hide under, so
        counting its 100%-exposed collective would read as an overlap
        collapse that no scheduling change can fix."""
        manifest = {
            "step_time_s": [0.010, 0.006], "input_wait_s": [0.002, 0.0],
        }
        trace_data = {
            "found": True,
            "step_windows": [(0.0, 0.010), (0.010, 0.016)],
            "compute": [(0.000, 0.004)],  # none lands in step 2
            "collective": [(0.003, 0.006), (0.011, 0.015)],
            "collective_events": [
                {"name": "all-reduce.1", "ts": 0.003, "dur_s": 0.003},
                {"name": "all-gather.2", "ts": 0.011, "dur_s": 0.004},
            ],
        }
        b = anatomy.step_budget(manifest, trace_data)
        assert "pure_comm" not in b["table"][0]
        assert b["table"][0]["compute_s"] == pytest.approx(0.004)
        assert b["table"][1]["pure_comm"] is True
        assert b["table"][1]["compute_s"] == pytest.approx(0.0)
        assert b["table"][1]["exposed_collective_s"] == pytest.approx(0.004)
        assert b["pure_comm_steps"] == 1
        # step 1 alone: 1ms of its 3ms collective hidden (1/3) — step 2's
        # fully exposed 4ms would have dragged this to 1/7 if counted
        assert b["overlap_frac"] == pytest.approx(1 / 3, abs=1e-3)

    def test_no_device_trace_degrades_to_host_residual(self):
        manifest = {"step_time_s": [0.010, 0.008], "input_wait_s": [0.001, 0.0]}
        b = anatomy.step_budget(manifest, {"found": False})
        assert b["device_trace"] is False
        assert b["table"][0]["host_blocked_s"] == pytest.approx(0.009)
        assert b["table"][1]["host_blocked_s"] == pytest.approx(0.008)
        assert "overlap_frac" not in b

    def test_collective_table_keeps_both_one_sided_rows(self):
        trace_data = {"collective_events": [
            {"name": "all-reduce.1", "ts": 0.0, "dur_s": 0.001},
            {"name": "all-reduce.1", "ts": 0.002, "dur_s": 0.003},
            {"name": "all-gather.7", "ts": 0.0, "dur_s": 0.002},
        ]}
        ledger = [
            {"name": "all-reduce.1", "kind": "all-reduce", "bytes": 4096,
             "replica_groups": [[0, 1]]},
            {"name": "reduce-scatter.9", "kind": "reduce-scatter",
             "bytes": 64, "replica_groups": ""},
        ]
        rows = {r["name"]: r for r in anatomy.collective_table(trace_data, ledger)}
        paired = rows["all-reduce.1"]
        assert paired["bytes"] == 4096 and paired["count"] == 2
        assert paired["mean_us"] == pytest.approx(2000.0)
        assert paired["achieved_gbps"] == pytest.approx(
            4096 * 2 / 0.004 / 1e9, rel=1e-3
        )
        assert "achieved_gbps" not in rows["all-gather.7"]     # no bytes
        assert "total_s" not in rows["reduce-scatter.9"]       # never ran


# --- the capture primitive + controller ---------------------------------------


class TestCapture:
    def test_trace_window_returns_the_artifact_path(self, tmp_path):
        from tony_tpu.obs.profiler import trace_window

        compiled, x, w, _ = _psum_program()
        with trace_window(str(tmp_path / "cap")) as cap:
            jax.block_until_ready(compiled(x, w))
        assert cap.ok and cap.path, "capture did not finalise"
        assert os.path.isdir(cap.path)
        # the run dir is where the artifacts actually are — deterministic,
        # no globbing needed by the caller
        assert glob.glob(os.path.join(cap.path, "*.trace.json*"))
        # disabled window: inert handle, nothing written
        with trace_window(str(tmp_path / "off"), enabled=False) as cap2:
            pass
        assert not cap2.ok and cap2.path == ""

    def test_controller_budget_sums_and_collective_row(self, tmp_path):
        """The acceptance shape on CPU: a psum program captured over an
        exact step window; budget rows sum to measured step time within
        10%, and the all-reduce row carries bytes AND measured time."""
        compiled, x, w, _ = _psum_program()
        ledger_rows = comms.extract_collectives(compiled)
        ctl = profile_mod.ProfileController(
            str(tmp_path / "profile"), "probe", watch=False
        )
        req = ctl.trigger(steps=3)
        jax.block_until_ready(compiled(x, w))  # warm outside the window
        for _ in range(5):  # more boundaries than steps: window self-closes
            ctl.step(fetch_s=0.0005)
            jax.block_until_ready(compiled(x, w))
        ctl.finish()

        manifests = profile_mod.read_manifests(str(tmp_path), req.id)
        assert set(manifests) == {"probe"}
        manifest = manifests["probe"]
        assert manifest["steps"] == 3
        assert len(manifest["step_time_s"]) == 3
        assert manifest["artifact"] and os.path.isdir(manifest["artifact"])

        rep = anatomy.proc_report(manifest, ledger_rows)
        assert rep["device_trace"] is True
        for row in rep["table"]:
            attributed = (row["compute_s"] + row["exposed_collective_s"]
                          + row["input_wait_s"] + row["host_blocked_s"])
            assert attributed == pytest.approx(row["step_time_s"], rel=0.10)
        # at least one collective row has static bytes AND measured time
        assert any(
            r.get("bytes", 0) > 0 and r.get("total_s", 0) > 0
            for r in rep["collectives"]
        ), rep["collectives"]

    def test_broadcast_request_arms_at_install_and_expires(self, tmp_path):
        app_dir = str(tmp_path)
        req = profile_mod.write_request(app_dir, steps=2)
        assert profile_mod.read_request(
            profile_mod.request_path(app_dir)
        ).id == req.id
        # a controller armed AFTER the broadcast picks it up synchronously
        ctl = profile_mod.ProfileController(
            profile_mod.profile_dir(app_dir), "w0",
            request_path=profile_mod.request_path(app_dir),
        )
        try:
            assert ctl._pending is not None and ctl._pending.id == req.id
        finally:
            ctl.close()
        # an expired request can never arm
        stale = profile_mod.write_request(app_dir, steps=2, ttl_s=1.0)
        path = profile_mod.request_path(app_dir)
        blob = json.load(open(path))
        blob["deadline_ts"] = time.time() - 5.0
        blob["id"] = stale.id + "x"
        json.dump(blob, open(path, "w"))
        ctl2 = profile_mod.ProfileController(
            profile_mod.profile_dir(app_dir), "w1",
            request_path=path,
        )
        try:
            assert ctl2._pending is None
        finally:
            ctl2.close()

    def test_duration_window_honours_the_step_cap(self, tmp_path):
        """A `--seconds T` window against a fast step loop must stop at
        obs.profile.max_steps, not record an unbounded device trace."""
        ctl = profile_mod.ProfileController(
            str(tmp_path / "profile"), "probe", watch=False, max_steps=3,
        )
        ctl.trigger(duration_s=600.0)
        for _ in range(10):
            ctl.step()
        assert ctl._req is None  # self-closed at the cap, not at 600s
        m = profile_mod.read_manifests(str(tmp_path))["probe"]
        assert m["steps"] == 3

    def test_maybe_capture_disarmed_and_armed_idle_are_inert(self, tmp_path):
        profile_mod.uninstall()
        assert profile_mod.active_controller() is None
        profile_mod.maybe_capture()           # disarmed: pure no-op
        profile_mod.maybe_capture(fetch_s=0.1)
        profile_mod.finish_capture()
        ctl = profile_mod.install(profile_mod.ProfileController(
            str(tmp_path / "profile"), "idle", watch=False
        ))
        for _ in range(100):
            profile_mod.maybe_capture(fetch_s=0.0)
        assert ctl._req is None               # no window ever opened
        assert not os.path.isdir(str(tmp_path / "profile" / "idle"))

    def test_read_manifests_picks_newest_and_filters(self, tmp_path):
        def _mk(proc, cap_id, ts):
            d = tmp_path / "profile" / proc / cap_id
            d.mkdir(parents=True)
            (d / "manifest.json").write_text(json.dumps({
                "profile_id": cap_id, "proc": proc, "ts": ts,
                "steps": 1, "step_time_s": [0.1], "input_wait_s": [0.0],
                "artifact": "",
            }))
        _mk("w0", "p1_a", 100.0)
        _mk("w1", "p1_a", 101.0)
        _mk("w0", "p2_b", 200.0)
        newest = profile_mod.read_manifests(str(tmp_path))
        assert set(newest) == {"w0"} and newest["w0"]["profile_id"] == "p2_b"
        both = profile_mod.read_manifests(str(tmp_path), "p1_a")
        assert set(both) == {"w0", "w1"}
        assert profile_mod.list_captures(str(tmp_path)) == ["p1_a", "p2_b"]


# --- the merged report + CLI --------------------------------------------------


class TestReport:
    def _capture_app(self, tmp_path, procs=("w0", "w1"), scale=(1, 8)):
        compiled, x, w, _ = _psum_program()
        jax.block_until_ready(compiled(x, w))  # warm outside the windows
        app_dir = str(tmp_path)
        # ONE broadcast id shared by every proc — the AM path's shape
        req = profile_mod.write_request(app_dir, steps=2)
        for proc, mult in zip(procs, scale):
            ctl = profile_mod.ProfileController(
                profile_mod.profile_dir(app_dir), proc, watch=False,
                request_path=profile_mod.request_path(app_dir),
            )
            ctl.check_request()
            for _ in range(4):
                ctl.step()
                # w1 does 8x work — the straggler, by a margin a loaded
                # test host's millisecond hiccups cannot close
                for _ in range(mult):
                    jax.block_until_ready(compiled(x, w))
            ctl.finish()
        return app_dir, req.id

    def test_build_anatomy_merges_procs_and_flags_critical_path(self, tmp_path):
        app_dir, _ = self._capture_app(tmp_path)
        rep = anatomy.build_anatomy(app_dir)
        assert set(rep["procs"]) == {"w0", "w1"}
        cp = rep["critical_path"]
        assert cp["proc"] == "w1"  # 8x work per step dominates every step
        assert cp["dominated_steps"]["w1"] == 2
        assert len(cp["by_step"]) == 2

    def test_cli_profile_report(self, tmp_path, capsys):
        from tony_tpu.cli.main import main as cli_main

        app_dir, cap_id = self._capture_app(tmp_path, procs=("w0",), scale=(1,))
        assert cli_main(["profile", "report", app_dir]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["profile_id"] == cap_id
        assert "w0" in out["procs"]
        assert out["procs"]["w0"]["steps"] == 2
        # empty dir: explicit no-data exit, never a fabricated report
        empty = tmp_path / "empty"
        empty.mkdir()
        assert cli_main(["profile", "report", str(empty)]) == 2

    def test_goodput_reports_unattributed_residual(self):
        from tony_tpu.obs.trace_tool import goodput

        procs = [{
            "proc": "w", "pid": 1, "trace": "t", "dropped": 0,
            "instants": [], "opens": [], "counters": [],
            "spans": [
                {"name": "train.step", "ts": 0, "dur": 1_000_000,
                 "args": {"every": 1}, "sid": "a", "psid": ""},
                {"name": "train.fit", "ts": 0, "dur": 4_000_000,
                 "args": {}, "sid": "b", "psid": ""},
            ],
        }]
        g = goodput("/nonexistent", procs)
        assert g["window_s"] == pytest.approx(4.0)
        assert g["productive_s"] == pytest.approx(1.0)
        # the 3s no bucket claims are REPORTED, not folded silently into
        # the denominator — anatomy and goodput reconcile through this key
        assert g["unattributed_s"] == pytest.approx(3.0)


# --- end-to-end: the AM broadcast over a real 2-host job ----------------------


@pytest.mark.slow  # ~18s: full client->AM->2-executor process stack;
# the capture path (ProfileController window, manifest, proc_report
# math, comms extraction) stays tier-1 in this file's unit/controller
# tests — only the fleet broadcast fan-out re-pays processes here
# (round 20 offsets)
def test_profile_fleet_capture_end_to_end(tmp_path):
    """Acceptance e2e: a REAL client -> AM -> 2-executor job; `tony
    profile <app> --steps 2` broadcast over the StartProfile RPC while the
    workers boot; BOTH hosts capture the window via the app-dir broadcast
    file; the report merges both with a critical path, each host's budget
    rows sum to its measured step time, and at least one collective row
    carries bytes AND measured device time."""
    from tony_tpu.cli.client import TonyClient
    from tony_tpu.cli.main import main as cli_main
    from tony_tpu.config.config import TonyConfig

    src = tmp_path / "src"
    src.mkdir()
    (src / "train.py").write_text(
        "import logging, os\n"
        "logging.basicConfig(level=logging.INFO)\n"
        "# each worker runs an INDEPENDENT tiny fit: the subject here is\n"
        "# the AM profile broadcast + per-host capture, not the data plane\n"
        "os.environ['TONY_NUM_PROCESSES'] = '1'\n"
        "from tony_tpu.train import fit, FitConfig\n"
        "from tony_tpu.train.data import DataConfig\n"
        "from tony_tpu.models.llama import LlamaConfig\n"
        "from tony_tpu.parallel.mesh import MeshShape\n"
        "out = fit(FitConfig(\n"
        "    model=LlamaConfig.tiny(),\n"
        "    data=DataConfig(global_batch=4, seq_len=32, vocab_size=128),\n"
        "    mesh_shape=MeshShape(fsdp=2),\n"
        "    steps=30, log_every=30, warmup_steps=2))\n"
        "print('FIT DONE', out.get('final_loss'))\n"
    )
    cfg = TonyConfig.load(overrides={
        "task.heartbeat_interval_ms": 200,
        "task.max_missed_heartbeats": 10,
        "application.timeout_s": 240,
        "application.stage_dir": str(tmp_path),
        "application.name": "profile-e2e",
        "application.framework": "jax",
        "job.worker.instances": 2,
        "job.worker.command": f"{sys.executable} train.py",
        # 2 virtual CPU devices per worker -> the fsdp=2 mesh all-gathers,
        # so the capture has real collectives to anatomise
        "job.worker.env": [
            "JAX_PLATFORMS=cpu",
            "XLA_FLAGS=--xla_force_host_platform_device_count=2",
        ],
    })
    client = TonyClient(cfg, src_dir=str(src))
    client.stage()
    client.launch_am()
    app_dir = client.app_dir
    try:
        client.am_address()  # AM is up: the broadcast can land
        # trigger via the CLI (the StartProfile RPC path) without waiting —
        # the request file now predates the workers' arming, which is the
        # deterministic pick-up path
        assert cli_main(["profile", app_dir, "--steps", "2", "--no-wait"]) == 0
        # workers boot, arm, capture; manifests land mid-run
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if len(profile_mod.read_manifests(app_dir)) >= 2:
                break
            time.sleep(1.0)
    finally:
        code = client.monitor(quiet=True)
    if code != 0:
        logs_dir = os.path.join(app_dir, "logs")
        for n in sorted(os.listdir(logs_dir)):
            print(f"===== {n}", open(os.path.join(logs_dir, n),
                                     errors="replace").read()[-2000:])
    assert code == 0

    manifests = profile_mod.read_manifests(app_dir)
    assert len(manifests) == 2, sorted(manifests)
    rep = anatomy.build_anatomy(app_dir)
    assert len(rep["procs"]) == 2
    assert rep["critical_path"]["proc"] in rep["procs"]
    saw_paired_collective = False
    for proc, r in rep["procs"].items():
        assert r["steps"] == 2, (proc, r["steps"])
        assert r["device_trace"] is True, proc
        for row in r["table"]:
            attributed = (row["compute_s"] + row["exposed_collective_s"]
                          + row["input_wait_s"] + row["host_blocked_s"])
            assert attributed == pytest.approx(row["step_time_s"], rel=0.10)
        if any(c.get("bytes", 0) > 0 and c.get("total_s", 0) > 0
               for c in r["collectives"]):
            saw_paired_collective = True
    assert saw_paired_collective, {
        p: r["collectives"][:3] for p, r in rep["procs"].items()
    }
    # the trace roll-up points at the capture and reconciles explicitly
    from tony_tpu.obs.trace_tool import report as trace_report

    summary = trace_report(app_dir)
    assert rep["profile_id"] in summary.get("profile_captures", [])
    assert "unattributed_s" in summary["goodput"]
