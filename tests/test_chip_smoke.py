"""CPU rehearsal of chip_smoke.py's control flow, and the pieces it leans on:
the compile-cache directory rule, the device-kind peak table, the device
identity sample. Nothing here is a device measurement."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import chip_smoke
from tony_tpu.obs import metrics
from tony_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ["phase_probe", "phase_submit", "phase_serve", "phase_engine", "phase_kernels"]


def _env(**extra) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", compile_cache.ENV_JAX_CACHE_DIR,
                        compile_cache.ENV_JOB_CACHE_DIR)}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **extra)
    return env


# --- chip_smoke.py control flow -------------------------------------------------


def test_tiny_rehearsal_runs_every_phase_in_order_and_prints_no_result(tmp_path):
    """The whole script at test sizes on the CPU: children first, the parent
    untouched by JAX until they are done, every check of every phase passing
    — and still no result line and a non-zero exit, because this is no TPU."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--tiny"],
        env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")),
        capture_output=True, text=True, timeout=600,
    )
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.startswith("{")]
    phases = [l.get("phase") for l in lines]
    assert phases == [
        "probe", "submit.run1", "submit.run2", "submit.cache", "serve",
        "parent", "parent.cache", "engine.scan", "engine.pallas",
        "engine.parity", "kernels",
    ], (phases, out.stderr[-3000:])
    by = dict(zip(phases, lines))
    assert by["parent"]["backend_untouched_during_child_phases"] is True
    # JAX_COMPILATION_CACHE_DIR set outside: every entry lands there, the
    # second submit loads from it, and the in-process phase reports it too
    assert by["submit.cache"]["run2"]["hits"] >= 1
    assert by["parent.cache"]["dir"] == str(tmp_path / "cache")
    assert os.listdir(tmp_path / "cache")
    assert by["submit.run1"]["history_device"]["platform"] == "cpu"
    assert by["serve"]["gang_host_device"]["platform"] == "cpu"
    assert len(by["serve"]["completions"]) == 8
    assert out.returncode == 3, out.stderr[-3000:]
    assert not any("ok" in l for l in lines)


def test_without_an_accelerator_the_real_run_fails_at_the_probe():
    """`python chip_smoke.py` as the driver runs it, here: exits non-zero
    within seconds (no 0.87 B-parameter job ground out on the CPU) and
    prints no result line."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=_env(), capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 1
    assert [json.loads(l)["phase"] for l in out.stdout.splitlines()] == ["probe"]
    assert "not 'tpu'" in out.stderr


@pytest.mark.parametrize("failing", PHASES)
def test_any_phase_that_raises_exits_nonzero_and_stops_there(
    monkeypatch, capsys, failing
):
    ran = []

    def phase(name):
        def fn(*_a, **_kw):
            ran.append(name)
            if name == failing:
                raise RuntimeError(f"{name} broke")
        return fn

    for name in PHASES:
        monkeypatch.setattr(chip_smoke, name, phase(name))
    monkeypatch.setattr(chip_smoke, "parent_backend_untouched", lambda: True)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda *a: "unused")
    assert chip_smoke.run(["--tiny"]) == 1
    assert ran == PHASES[: PHASES.index(failing) + 1]  # nothing after it ran
    assert '"ok"' not in capsys.readouterr().out


def test_a_parent_that_touched_jax_early_fails_the_run(monkeypatch, capsys):
    for name in PHASES:
        monkeypatch.setattr(chip_smoke, name, lambda *_a, **_kw: None)
    import jax

    jax.devices()  # what a careless parent does: the backend now exists
    assert chip_smoke.parent_backend_untouched() is False
    assert chip_smoke.run(["--tiny"]) == 1
    assert '"ok"' not in capsys.readouterr().out


def test_four_chip_option_runs_only_that_path(monkeypatch, capsys):
    ran = []
    for name in PHASES + ["phase_four_chips"]:
        monkeypatch.setattr(
            chip_smoke, name, lambda *_a, _n=name, **_kw: ran.append(_n))
    monkeypatch.setattr(chip_smoke, "parent_backend_untouched", lambda: True)
    assert chip_smoke.run(["--tiny", "--chips", "4"]) == 3  # CPU: no result
    assert ran == ["phase_probe", "phase_four_chips"]
    assert '"ok"' not in capsys.readouterr().out


# --- compile-cache directory rule -----------------------------------------------

_SHOW = (
    "import jax, json; from tony_tpu.utils import compile_cache as c; "
    "d = c.enable_compile_cache({job!r}); "
    "print(json.dumps([d, jax.config.jax_compilation_cache_dir]))"
)


def _cache_dirs(job: str = "", **env) -> list:
    out = subprocess.run(
        [sys.executable, "-c", _SHOW.format(job=job)], env=_env(**env),
        capture_output=True, text=True, timeout=120, check=True,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cache_dir_variable_set_outside_wins_and_nothing_is_set_in_code(
    tmp_path, monkeypatch
):
    outside = str(tmp_path / "outside")
    # JAX reads the variable itself: the value is there, and it is JAX's
    # own reading of it, not a config.update of ours (which a job key or
    # the default would have won)
    assert _cache_dirs(job=str(tmp_path / "job"),
                       JAX_COMPILATION_CACHE_DIR=outside) == [outside, outside]
    import jax

    calls = []
    monkeypatch.setenv(compile_cache.ENV_JAX_CACHE_DIR, outside)
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.append(k))
    assert compile_cache.enable_compile_cache("/some/job/dir") == outside
    assert "jax_compilation_cache_dir" not in calls
    assert "jax_persistent_cache_min_compile_time_secs" in calls  # thresholds only


def test_cache_dir_job_key_then_fixed_in_checkout_default(tmp_path):
    job = str(tmp_path / "job")
    assert _cache_dirs(job=job) == [job, job]
    default = os.path.join(REPO, ".jax_cache")
    # identical across two calls and two processes; never a temp/pid name
    assert compile_cache.default_cache_dir() == default
    assert compile_cache.default_cache_dir() == default
    assert _cache_dirs() == [default, default] == _cache_dirs()


def test_executor_exports_the_same_rule(monkeypatch):
    """runtime/base.py build_env: the job key if set, else the in-checkout
    default; nothing when the job switched the cache off."""
    from tony_tpu.config.config import TonyConfig
    from tony_tpu.runtime.base import TaskIdentity
    from tony_tpu.runtime.jax_tpu import JaxTpuRuntime

    ident = TaskIdentity(
        job_name="worker", index=0, cluster_spec={"worker": ["h:1"]},
        coordinator_address="h:1", process_id=0, num_processes=1,
    )

    def exported(**over):
        cfg = TonyConfig.load(overrides=over)
        return JaxTpuRuntime().build_env(ident, cfg).get(compile_cache.ENV_JOB_CACHE_DIR)

    assert exported() == os.path.join(REPO, ".jax_cache")
    assert exported(**{"train.jax_cache_dir": "/x/y"}) == "/x/y"
    assert exported(**{"train.jax_cache": False}) is None
    monkeypatch.delenv(compile_cache.ENV_JAX_CACHE_DIR, raising=False)
    monkeypatch.delenv(compile_cache.ENV_JOB_CACHE_DIR, raising=False)
    assert compile_cache.enable_from_job_env() == ""  # bare process: untouched


# --- no fallback that hides the device ------------------------------------------


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_chip_peak_flops_knows_libtpu_kinds_and_raises_on_the_rest():
    assert metrics.chip_peak_flops(_Dev("tpu", "TPU v5 lite")) == 197e12
    assert metrics.chip_peak_flops(_Dev("tpu", "TPU v4")) == 275e12
    # the CPU figure is nominal, and only the CPU platform gets it
    assert metrics.chip_peak_flops(_Dev("cpu", "cpu")) == metrics.NOMINAL_CPU_FLOPS
    for dev in (_Dev("tpu", "TPU v9 mega"), _Dev("gpu", "cpu"), _Dev("tpu", "v5 lite")):
        with pytest.raises(ValueError, match="no peak FLOP/s known"):
            metrics.chip_peak_flops(dev)


def test_device_identity_rides_one_numeric_sample():
    ident = {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 4}
    samples = metrics.device_samples(ident)
    assert samples == {"device/tpu/TPU v5 lite": 4.0}
    assert metrics.parse_device_samples({"step": 1.0, **samples}) == ident
    assert metrics.parse_device_samples({"step": 1.0}) is None
    import jax

    here = metrics.device_identity()
    assert here["platform"] == "cpu" and here["device_count"] == len(jax.devices())
