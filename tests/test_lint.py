"""graft-lint: checker unit fixtures, the tier-1 zero-findings gate, and
the runtime sanitizer (tony_tpu/analysis/; docs/ANALYSIS.md).

Every checker has at least one firing and one non-firing fixture: the
known-bad snippet MUST produce its code and the known-good twin MUST NOT —
the zero-findings gate is only trustworthy if both directions hold.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tony_tpu.analysis import Baseline, lint_paths, load_project, run_checkers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_src(tmp_path, sources: dict[str, str], select: str = ""):
    """Write fixture modules, lint them, return findings (optionally one
    checker code only)."""
    d = tmp_path / "fixture"
    d.mkdir(exist_ok=True)
    for name, src in sources.items():
        (d / name).write_text(textwrap.dedent(src))
    project = load_project([str(d)])
    return run_checkers(project, select=[select] if select else ())


def codes(findings):
    return [f.code for f in findings]


# --- GL001 host-sync-in-jit ---------------------------------------------------


class TestGL001:
    def test_fires_on_item_in_jit_reachable_helper(self, tmp_path):
        """.item() two call-graph hops below a jax.jit entry fires."""
        fs = lint_src(tmp_path, {"mod.py": """
            import jax
            import jax.numpy as jnp

            def helper(x):
                return x.sum().item()

            def entry(x):
                return helper(x) + 1

            step = jax.jit(entry)
        """}, select="GL001")
        assert codes(fs) == ["GL001"]
        assert "helper" in fs[0].symbol and ".item()" in fs[0].message

    def test_fires_on_float_of_tracer_and_device_get(self, tmp_path):
        fs = lint_src(tmp_path, {"mod.py": """
            import jax
            import jax.numpy as jnp

            def entry(x):
                y = jnp.exp(x)
                a = float(y)          # host sync on a traced value
                b = jax.device_get(y) # host sync
                return a + b.sum()

            step = jax.jit(entry)
        """}, select="GL001")
        assert sorted(f.detail.split("#")[0] for f in fs) == [
            "float()", "jax.device_get"
        ]

    def test_silent_on_unjitted_code_and_static_reads(self, tmp_path):
        """The same syncs outside any jit path, and float() of static
        values / .shape reads inside one, must NOT fire."""
        fs = lint_src(tmp_path, {"mod.py": """
            import jax
            import jax.numpy as jnp

            def driver(x):
                return x.sum().item()  # not jit-reachable: fine

            def entry(x, cfg_lr):
                scale = float(cfg_lr)      # static python value
                rows = x.shape[0]          # static under tracing
                return jnp.exp(x) * scale * rows

            step = jax.jit(entry)
        """}, select="GL001")
        assert fs == []

    def test_follows_a_module_chosen_by_a_function_that_returns_modules(self, tmp_path):
        """``m = pick(cfg)`` where every ``return`` of ``pick`` names an
        imported module, then ``m.step(x)`` under jit: the call reaches
        ``step`` of EACH module ``pick`` can return (how serve/engine.py
        chooses a model family) — a sync in one of them fires, and a
        module ``pick`` cannot return stays outside the gate."""
        fs = lint_src(tmp_path, {
            "fam_a.py": """
                def step(x):
                    return x + 1
            """,
            "fam_b.py": """
                def step(x):
                    return x.sum().item()
            """,
            "fam_c.py": """
                def step(x):
                    return x.sum().item()   # never chosen: not traced
            """,
            "eng.py": """
                import jax
                import fam_a
                import fam_b
                import fam_c

                def pick(cfg):
                    if cfg:
                        return fam_a
                    return fam_b

                def build(cfg):
                    steps = pick(cfg)

                    def program(x):
                        return steps.step(x)

                    return jax.jit(program)
            """,
        }, select="GL001")
        assert [os.path.basename(f.path) for f in fs] == ["fam_b.py"], fs

    def test_real_engine_decode_path_is_traced(self):
        """The live tree's jitted hot paths are reachable: every model
        family's three serving steps (chosen through
        ``serve.engine.steps_for`` — the module-returning idiom
        analysis/callgraph.py follows) and the decode step's transitive
        callees (the layer body, sampling, kernels) are in the traced
        closure — the gate actually covers them."""
        project = load_project([os.path.join(REPO, "tony_tpu")])
        for probe in (
            "tony_tpu.serve.dense:prefill_step",
            "tony_tpu.serve.dense:tail_prefill_step",
            "tony_tpu.serve.dense:decode_step",
            "tony_tpu.serve.latent:prefill_step",
            "tony_tpu.serve.latent:tail_prefill_step",
            "tony_tpu.serve.latent:decode_step",
            "tony_tpu.serve.shortconv:prefill_step",
            "tony_tpu.serve.shortconv:tail_prefill_step",
            "tony_tpu.serve.shortconv:decode_step",
            "tony_tpu.serve.ssm_hybrid:prefill_step",
            "tony_tpu.serve.ssm_hybrid:tail_prefill_step",
            "tony_tpu.serve.ssm_hybrid:decode_step",
            "tony_tpu.models.generate:layer",
            "tony_tpu.models.latent_moe:layer",
            "tony_tpu.models.shortconv_moe:layer",
            "tony_tpu.models.ssm_hybrid:layer",
            "tony_tpu.models.layer_walk:walk_layers",
            "tony_tpu.serve.spec:verify_and_accept",
            "tony_tpu.models.generate:sample_tokens",
            "tony_tpu.ops.decode_attention:decode_attention",
            "tony_tpu.models.llama:loss_from_pairs",
            "tony_tpu.ops.fused_ce:fused_ce_tokens",
        ):
            assert project.is_traced(probe), probe


# --- GL002 recompile-hazard ---------------------------------------------------


class TestGL002:
    def test_fires_on_jit_in_loop_and_jit_of_lambda(self, tmp_path):
        fs = lint_src(tmp_path, {"mod.py": """
            import jax

            def run(xs, f):
                out = []
                for x in xs:
                    out.append(jax.jit(f)(x))     # fresh jit per iteration
                return out

            def run2(x):
                g = jax.jit(lambda v: v + 1)      # fresh lambda per call
                return g(x)
        """}, select="GL002")
        assert sorted(f.detail for f in fs) == ["jit-in-loop", "jit-of-lambda"]

    def test_fires_on_shape_keyed_jit_of_partial_in_loop(self, tmp_path):
        """The bucketed-collective regression shape (ops/overlap.py's
        scheduler is exactly this): per step, per bucket, a fresh
        `jit(partial(...))` — the partial is a new object every iteration
        so the jit cache key never repeats and every bucket recompiles
        every step."""
        fs = lint_src(tmp_path, {"mod.py": """
            import functools
            import jax
            from jax import lax

            def reduce_buckets(buckets, axis):
                out = []
                for b in buckets:
                    f = jax.jit(functools.partial(lax.psum, axis_name=axis))
                    out.append(f(b))
                return out
        """}, select="GL002")
        assert [f.detail for f in fs] == ["shape-keyed-jit-in-loop"]

    def test_fires_on_jit_of_partial_built_and_called_per_dispatch(self, tmp_path):
        """The per-dispatch twin of the in-loop case — the MoE routing
        shape: a dispatch helper that re-wraps its kernel around the
        current config in the same expression that calls it. No loop in
        sight, but the caller IS the loop (one routing call per step), so
        every dispatch pays a full recompile."""
        fs = lint_src(tmp_path, {"mod.py": """
            import functools
            import jax

            def _expert_ffn(x, w, n_experts):
                return x @ w

            def route_tokens(x, w, n_experts):
                return jax.jit(functools.partial(_expert_ffn, n_experts=n_experts))(x, w)
        """}, select="GL002")
        assert [f.detail for f in fs] == ["jit-per-dispatch"]
        assert "route_tokens" in fs[0].symbol

    def test_silent_on_hoisted_jit_of_partial(self, tmp_path):
        """The FIX shapes must not fire: a jit-of-partial built once
        outside the loop (the serve/engine.py AOT-family idiom) and
        dispatched per bucket, or memoized per distinct static plan."""
        fs = lint_src(tmp_path, {"mod.py": """
            import functools
            import jax
            from jax import lax

            def reduce_buckets(buckets, axis):
                f = jax.jit(functools.partial(lax.psum, axis_name=axis))
                return [f(b) for b in buckets]

            def reduce_memoized(buckets, axis, cache):
                out = []
                for b in buckets:
                    key = tuple(x.shape for x in b)
                    if key not in cache:
                        cache[key] = _build(axis)
                    out.append(cache[key](b))
                return out

            def _build(axis):
                return jax.jit(functools.partial(lax.psum, axis_name=axis))
        """}, select="GL002")
        assert fs == []

    def test_fires_on_branch_on_tracer(self, tmp_path):
        fs = lint_src(tmp_path, {"mod.py": """
            import jax
            import jax.numpy as jnp

            def entry(x):
                y = jnp.sum(x)
                if y > 0:                  # concretizes the tracer
                    return y
                return -y

            step = jax.jit(entry)
        """}, select="GL002")
        assert [f.detail for f in fs] == ["branch-on-tracer:if"]

    def test_fires_on_unhashable_static_default(self, tmp_path):
        fs = lint_src(tmp_path, {"mod.py": """
            import jax

            def f(x, opts=[1, 2]):
                return x

            g = jax.jit(f, static_argnums=(1,))
        """}, select="GL002")
        assert [f.detail for f in fs] == ["static-unhashable:opts"]

    def test_silent_on_module_level_jit_and_static_branches(self, tmp_path):
        fs = lint_src(tmp_path, {"mod.py": """
            import jax
            import jax.numpy as jnp

            def entry(x, n):
                if n > 4:                # python value: static branch
                    return jnp.exp(x)
                if x.shape[0] > 2:       # shape: static under tracing
                    return x
                return -x

            step = jax.jit(entry, static_argnums=(1,))

            def driver(xs):
                y = jnp.sum(xs)
                if y.shape:              # static metadata read
                    return y
                return y
        """}, select="GL002")
        assert fs == []


# --- GL003 donation-reuse -----------------------------------------------------


class TestGL003:
    def test_fires_on_read_after_donate(self, tmp_path):
        fs = lint_src(tmp_path, {"mod.py": """
            import jax

            def fn(state, batch):
                return state + batch

            step = jax.jit(fn, donate_argnums=(0,))

            def run(state, batch):
                new = step(state, batch)
                return state + new       # state's buffer was donated
        """}, select="GL003")
        assert len(fs) == 1
        assert "donated" in fs[0].detail and "state" in fs[0].message

    def test_silent_on_rebind(self, tmp_path):
        fs = lint_src(tmp_path, {"mod.py": """
            import jax

            def fn(state, batch):
                return state + batch

            step = jax.jit(fn, donate_argnums=(0,))

            def run(state, batches):
                for b in batches:
                    state = step(state, b)   # rebind: canonical donate use
                return state

            def run2(state, batch):
                out = step(state, batch)
                state = out                  # rebound before any read
                return state
        """}, select="GL003")
        assert fs == []


# --- GL004 lock-discipline ----------------------------------------------------


class TestGL004:
    def test_fires_on_sleep_and_unbounded_get_under_lock(self, tmp_path):
        fs = lint_src(tmp_path, {"mod.py": """
            import queue
            import threading
            import time

            _lock = threading.Lock()
            _queue = queue.Queue()

            def f():
                with _lock:
                    time.sleep(1.0)

            def g():
                with _lock:
                    item = _queue.get()
                return item
        """}, select="GL004")
        assert len(fs) == 2
        assert any("time.sleep" in f.message for f in fs)
        assert any("queue" in f.message for f in fs)

    def test_fires_one_hop_deep_and_on_rpcish_calls(self, tmp_path):
        """A helper's blocking call counts against the caller's lock, and
        backend/client attribute calls are RPC-ish blockers."""
        fs = lint_src(tmp_path, {"mod.py": """
            import threading

            class AM:
                def __init__(self, backend):
                    self._lock = threading.Lock()
                    self.backend = backend

                def _helper(self, f):
                    data = f.read()
                    return data

                def tick(self, f):
                    with self._lock:
                        self.backend.release("c1")
                        self._helper(f)
        """}, select="GL004")
        details = sorted(f.detail for f in fs)
        assert any("backend" in d for d in details)
        assert any("via" in d for d in details)

    def test_fires_on_lock_order_inversion(self, tmp_path):
        fs = lint_src(tmp_path, {"mod.py": """
            import threading

            class S:
                def __init__(self):
                    self._a_lock = threading.Lock()
                    self._b_lock = threading.Lock()

                def one(self):
                    with self._a_lock:
                        with self._b_lock:
                            pass

                def two(self):
                    with self._b_lock:
                        with self._a_lock:
                            pass
        """}, select="GL004")
        assert any("inversion" in f.detail for f in fs)

    def test_silent_on_collect_then_release_shape(self, tmp_path):
        """The canonical fix (snapshot under the lock, block outside) and
        bounded waits must not fire."""
        fs = lint_src(tmp_path, {"mod.py": """
            import threading
            import time

            class AM:
                def __init__(self, backend, q):
                    self._lock = threading.Lock()
                    self.backend = backend
                    self._queue = q

                def tick(self):
                    with self._lock:
                        cids = list(range(3))
                        item = self._queue.get(timeout=1.0)
                    for c in cids:
                        self.backend.release(c)
                    time.sleep(0.1)
                    return item
        """}, select="GL004")
        assert fs == []

    def test_inline_suppression_is_honoured(self, tmp_path):
        fs = lint_src(tmp_path, {"mod.py": """
            import threading
            import time

            _lock = threading.Lock()

            def f():
                with _lock:
                    # the sleep IS the feature here (test shim)
                    time.sleep(0.1)  # graft-lint: disable=GL004
        """}, select="GL004")
        assert fs == []


# --- GL005 disarmed-hook-cost -------------------------------------------------


class TestGL005:
    def test_fires_on_eager_expensive_args(self, tmp_path):
        fs = lint_src(tmp_path, {"mod.py": """
            import json
            from tony_tpu.obs import trace
            from tony_tpu.chaos import chaos_hook

            def hot(payload, point):
                trace.instant("step", data=json.dumps(payload))
                chaos_hook(point, ctx=build_ctx(payload))

            def build_ctx(p):
                return dict(p)
        """}, select="GL005")
        assert len(fs) == 2
        assert all("disarmed" in f.message for f in fs)

    def test_silent_when_guarded_or_cheap(self, tmp_path):
        fs = lint_src(tmp_path, {"mod.py": """
            import json
            from tony_tpu.obs import trace

            def hot(payload, rid, slot):
                trace.instant("step", rid=rid, slot=slot)  # cheap args
                tracer = trace.active_tracer()
                if tracer is not None:
                    # armed check already paid: eager args are fine
                    trace.instant("step", data=json.dumps(payload))
                    tracer.span("x", data=json.dumps(payload))
        """}, select="GL005")
        assert fs == []

    def test_hbm_sample_seam_holds_the_same_contract(self, tmp_path):
        """The HBM observatory's sample() seam (obs/hbm.py) is a hook site
        like trace.span: expensive arguments fire, bare calls are clean."""
        fs = lint_src(tmp_path, {"mod.py": """
            from tony_tpu.obs import hbm

            def hot_loop(step):
                hbm.sample()                    # the wired call shape: clean
                hbm.sample(note=describe(step))  # eager call arg: fires

            def describe(step):
                return {"step": step}
        """}, select="GL005")
        assert len(fs) == 1
        assert "disarmed" in fs[0].message and fs[0].line == 6

    def test_health_sample_seam_holds_the_same_contract(self, tmp_path):
        """The numerics sentinel's sample() seam (obs/health.py) is the
        third observatory hook: precomputed-name arguments (the wired call
        shape in train/loop.py and serve/engine.py) are clean; an argument
        that allocates or calls before the armed check fires."""
        fs = lint_src(tmp_path, {"mod.py": """
            from tony_tpu.obs import health

            def hot_loop(step, metrics, slot_rids):
                # the wired call shapes: bare names, nothing evaluated
                health.sample(metrics=metrics)
                health.sample(metrics=metrics, slot_rids=slot_rids)
                # eager call argument: evaluated even when disarmed — fires
                health.sample(metrics=summarize(metrics))
                # comprehension argument: ditto — fires
                health.sample(slot_rids=[r for r in slot_rids])

            def summarize(m):
                return dict(m)
        """}, select="GL005")
        assert len(fs) == 2
        assert all("disarmed" in f.message for f in fs)
        assert sorted(f.line for f in fs) == [9, 11]

    def test_profile_capture_seam_holds_the_same_contract(self, tmp_path):
        """The coordinated profiler's maybe_capture() seam (obs/profile.py)
        is the fifth observatory hook: the wired call shapes (bare call in
        the serve engine, precomputed fetch_s name in fit()) are clean; an
        argument that calls or allocates before the armed check fires."""
        fs = lint_src(tmp_path, {"mod.py": """
            from tony_tpu.obs import profile

            def hot_loop(step, fetch_s):
                # the wired call shapes: bare call / bare names
                profile.maybe_capture()
                profile.maybe_capture(fetch_s=fetch_s)
                # eager call argument: evaluated even when disarmed — fires
                profile.maybe_capture(note=describe(step))
                # comprehension argument: ditto — fires
                profile.maybe_capture(vals=[v for v in (step,)])

            def describe(step):
                return {"step": step}
        """}, select="GL005")
        assert len(fs) == 2
        assert all("disarmed" in f.message for f in fs)
        assert sorted(f.line for f in fs) == [9, 11]

    def test_series_sample_seam_holds_the_same_contract(self, tmp_path):
        """The live-series recorder's sample() seam (obs/series.py) is the
        fourth observatory hook: the wired call shapes (bare call in the
        serve/frontend loops, precomputed names in fit()) are clean; an
        argument that calls or allocates before the armed check fires."""
        fs = lint_src(tmp_path, {"mod.py": """
            from tony_tpu.obs import series

            def hot_loop(step, stats):
                # the wired call shapes: bare call / bare names
                series.sample()
                series.sample(step=step)
                # eager call argument: evaluated even when disarmed — fires
                series.sample(stats=scrape(stats))
                # comprehension argument: ditto — fires
                series.sample(vals=[v for v in stats])

            def scrape(s):
                return dict(s)
        """}, select="GL005")
        assert len(fs) == 2
        assert all("disarmed" in f.message for f in fs)
        assert sorted(f.line for f in fs) == [9, 11]


# --- suppression / baseline machinery ----------------------------------------


class TestMachinery:
    SRC = {"mod.py": """
        import threading
        import time

        _lock = threading.Lock()

        def f():
            with _lock:
                time.sleep(1.0)
    """}

    def test_baseline_covers_by_fingerprint_not_line(self, tmp_path):
        fs = lint_src(tmp_path, self.SRC, select="GL004")
        assert len(fs) == 1
        bl = Baseline({fs[0].fingerprint: "known debt"})
        shifted = dict(self.SRC)
        shifted["mod.py"] = "# a new leading comment shifts every line\n" + \
            textwrap.dedent(self.SRC["mod.py"])
        d = tmp_path / "fixture"
        (d / "mod.py").write_text(shifted["mod.py"])
        fs2 = run_checkers(load_project([str(d)]), select=["GL004"])
        assert len(fs2) == 1 and fs2[0].line != fs[0].line
        assert bl.covers(fs2[0])  # same fingerprint despite the line shift

    def test_baseline_save_keeps_justifications(self, tmp_path):
        fs = lint_src(tmp_path, self.SRC, select="GL004")
        path = str(tmp_path / "bl.json")
        bl = Baseline({fs[0].fingerprint: "why it is ok"}, path)
        bl.save(findings=fs)
        reloaded = Baseline.load(path)
        assert reloaded.entries[fs[0].fingerprint] == "why it is ok"

    def test_single_file_lint_matches_directory_fingerprints(self):
        """Fingerprints anchor at the repo root no matter the argument
        shape: linting one changed file must cover the same baseline
        entries as the whole-tree lint (else per-file CI/dev lints report
        grandfathered findings as new)."""
        baseline = Baseline.load(os.path.join(REPO, "graft_lint_baseline.json"))
        new, old = lint_paths(
            [os.path.join(REPO, "tony_tpu", "cluster", "lease.py")], baseline
        )
        assert new == [], "\n".join(f.render() for f in new)
        assert {f.fingerprint for f in old} <= set(baseline.entries)
        assert all(f.path == "tony_tpu/cluster/lease.py" for f in old)

    def test_cli_json_format_and_exit_codes(self, tmp_path, capsys):
        from tony_tpu.analysis.cli import main as lint_main

        d = tmp_path / "fixture"
        d.mkdir()
        (d / "mod.py").write_text(textwrap.dedent(self.SRC["mod.py"]))
        rc = lint_main([str(d), "--baseline", "none", "--format", "json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1 and [f["code"] for f in out["new"]] == ["GL004"]
        (d / "mod.py").write_text("x = 1\n")
        assert lint_main([str(d), "--baseline", "none"]) == 0


# --- the tier-1 gate ----------------------------------------------------------


@pytest.mark.lint
def test_codebase_is_lint_clean():
    """`tony lint tony_tpu/` on the shipped tree: ZERO non-baselined
    findings — the same stale-doc gate shape as gen_config_doc --check.
    A new finding means: fix it, suppress it inline with a justifying
    comment, or baseline it with a justification (docs/ANALYSIS.md)."""
    baseline = Baseline.load(os.path.join(REPO, "graft_lint_baseline.json"))
    new, old = lint_paths([os.path.join(REPO, "tony_tpu")], baseline)
    assert new == [], "new graft-lint findings:\n" + "\n".join(
        f.render() for f in new
    )


@pytest.mark.lint
def test_baseline_entries_are_current_and_justified():
    """Every baseline entry must still match a live finding (no stale
    grandfathering) and carry a real justification."""
    baseline = Baseline.load(os.path.join(REPO, "graft_lint_baseline.json"))
    _, old = lint_paths([os.path.join(REPO, "tony_tpu")], baseline)
    live = {f.fingerprint for f in old}
    stale = set(baseline.entries) - live
    assert not stale, f"baseline entries no longer firing: {sorted(stale)}"
    for fp, why in baseline.entries.items():
        assert why and "TODO" not in why, f"unjustified baseline entry: {fp}"


@pytest.mark.lint
def test_scripts_lint_entry_point():
    """The CI wrapper exits 0 on the shipped tree."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py")],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


# --- runtime sanitizer (analysis/sanitize.py) ---------------------------------


class TestSanitizer:
    def test_disabled_is_noop(self, monkeypatch):
        from tony_tpu.analysis import sanitize

        monkeypatch.delenv(sanitize.ENV_FLAG, raising=False)
        with sanitize.sanitized_loop("probe") as watchdog:
            assert watchdog is None

    def test_watchdog_trips_on_steady_state_compile(self, monkeypatch):
        """A fresh jit inside the sanitized region is the recompile-per-
        step failure mode; the watchdog must raise."""
        import jax
        import jax.numpy as jnp

        from tony_tpu.analysis import sanitize

        monkeypatch.setenv(sanitize.ENV_FLAG, "1")
        with pytest.raises(sanitize.SanitizeError, match="compile"):
            with sanitize.sanitized_loop("probe", max_compiles=0) as watchdog:
                jax.jit(lambda v: v * 2)(jnp.ones(3)).block_until_ready()
                watchdog.check()

    def test_sanitized_fit_tiny_triggers_neither(self, monkeypatch):
        """The guarded tiny-model training loop runs to completion under
        GRAFT_SANITIZE=1: no implicit D2H transfer, no steady-state
        compile — the loop honours the contract the lint enforces
        statically."""
        from tony_tpu.analysis import sanitize
        from tony_tpu.models.llama import LlamaConfig
        from tony_tpu.parallel.mesh import MeshShape
        from tony_tpu.train.data import DataConfig
        from tony_tpu.train.loop import FitConfig, fit

        monkeypatch.setenv(sanitize.ENV_FLAG, "1")
        out = fit(FitConfig(
            model=LlamaConfig.tiny(),
            data=DataConfig(global_batch=4, seq_len=16, vocab_size=256),
            mesh_shape=MeshShape(fsdp=2),
            steps=4, log_every=2,
        ))
        assert out["steps"] == 4 and out["final_loss"] == out["final_loss"]

    def test_sanitized_warm_engine_decode_triggers_neither(self, monkeypatch):
        """A warmed engine (compiles already paid) drains a trace under
        GRAFT_SANITIZE=1 without tripping either sanitizer arm."""
        import jax
        import numpy as np

        from tony_tpu.analysis import sanitize
        from tony_tpu.models.llama import LlamaConfig, init_params
        from tony_tpu.serve.engine import Engine, Request, ServeConfig

        cfg = LlamaConfig.tiny()
        params = init_params(jax.random.key(0), cfg)
        engine = Engine(params, cfg, ServeConfig(
            slots=2, max_len=64, prefill_buckets=(8,)
        ))
        reqs = lambda seed: [  # noqa: E731
            Request(prompt=np.arange(1, 6), max_new_tokens=4,
                    temperature=0.7, rng=seed + i)
            for i in range(3)
        ]
        warm = engine.run(reqs(0))          # pays every compile
        assert all(len(c.tokens) == 4 for c in warm.values())
        monkeypatch.setenv(sanitize.ENV_FLAG, "1")
        out = engine.run(reqs(10))          # sanitized: same signatures
        assert all(len(c.tokens) == 4 for c in out.values())
        engine.close()
