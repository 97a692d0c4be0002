"""The worker script the ``train_submit`` driver submits: `tony submit` runs it
as the worker's command and it calls ``fit()``, as a user's script would.

What it adds to a user's script is measurement, all from this side of the
program's interface (the program is not changed, and what it lacks is listed
in PERF.md for the tracing issue):

* weights from --seed: ``fit()`` always initialises from key 0, so the
  ``make_train_state`` name that ``fit()`` looks up is wrapped and the
  benchmark's own weights (benchmark/weights.py) go into the state it built;
* the first steps' readings for ``correct``: the ``make_train_step`` name is
  wrapped so that the ONE compiled step ``fit()`` drives, warm-up and window
  alike, is called through a probe. After update 1 the probe reads the norm
  of every leaf of Adam's first moment (the gradient as the optimizer got
  it, times 1 - b1), after update ``checked`` the norm of every leaf's
  change from the seeded weights, and the loss of steps 1..checked+1;
* the window: ``fit()`` takes a step count, not a deadline, so the
  ``on_metrics`` hook (called after the device sync of each log boundary)
  timestamps the boundaries and ends the run by raising once the next
  boundary would pass the deadline;
* the trace: a traced run goes on for ``trace_windows`` log windows past the
  window's last boundary with ``jax.profiler`` on, so that the profiler's own
  stalls (starting, writing its file) fall outside the window's numbers.

Writes one JSON report to --report.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time
from dataclasses import replace

class _EndOfWindow(Exception):
    """Raised from on_metrics to end fit() at a log boundary."""


class _Proxy:
    """Calls go through the probe; everything else is the wrapped object's."""

    def __init__(self, inner, probe):
        self._inner, self._probe = inner, probe

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __call__(self, state, inputs, targets):
        return self._probe.call(self._inner, state, inputs, targets)

    def lower(self, *a, **k):
        return _Proxy(self._inner.lower(*a, **k), self._probe)

    def compile(self, *a, **k):
        return _Proxy(self._inner.compile(*a, **k), self._probe)


class Probe:
    def __init__(self, key, sizes, dtype, checked: int, fault: str = ""):
        self.key, self.s, self.dtype, self.checked = key, sizes, dtype, checked
        self.calls = 0
        self.losses, self.grad_norms = [], []
        self.mu_norms = self.delta_norms = None
        self.broken = fault  # benchmark/tests plant a fault here; runs never do

    def call(self, fn, state, inputs, targets):
        self.calls += 1
        if self.calls > self.checked + 1:
            return fn(state, inputs, targets)
        if self.broken == "half_batch":
            # tests: rows of the second half repeat the first half's, so
            # the mean is taken over half of the batch
            import jax.numpy as jnp

            h = inputs.shape[0] // 2
            inputs = jnp.concatenate([inputs[:h], inputs[:h]])
            targets = jnp.concatenate([targets[:h], targets[:h]])
        if self.broken == "state_unchanged":
            import jax

            keep = jax.tree.map(lambda a: a.copy(), state)
            _, metrics = fn(state, inputs, targets)
            new_state = keep
        else:
            new_state, metrics = fn(state, inputs, targets)
        self.losses.append(metrics["loss"])
        self.grad_norms.append(metrics["grad_norm"])
        if self.calls == 1:
            self.mu_norms = _leaf_norms(_first_moment(new_state.opt_state))
        if self.calls == self.checked:
            self.delta_norms = _delta_norms(new_state.params, self.key, self.s, self.dtype)
        return new_state, metrics

    def report(self, b1: float) -> dict:
        import jax

        get = lambda t: jax.tree.map(lambda a: [float(x) for x in jax.device_get(a).reshape(-1)], t)
        mu = _named(get(self.mu_norms))
        return {
            "losses": [float(jax.device_get(x)) for x in self.losses],
            "grad_norms_global": [float(jax.device_get(x)) for x in self.grad_norms[: self.checked]],
            "grad1_leaf_norms": {k: v / (1.0 - b1) for k, v in mu.items()},
            "delta_leaf_norms": _named(get(self.delta_norms)),
            "checked_updates": self.checked,
        }


def _first_moment(opt_state):
    """The first-moment tree of the Adam state inside an optax chain."""
    import jax

    found = [x for x in jax.tree.leaves(opt_state, is_leaf=lambda x: hasattr(x, "mu"))
             if hasattr(x, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state in the optimizer state, found {len(found)}")
    return found[0].mu


def _leaf_norms(tree):
    """Per leaf, per layer: stacked leaves give a vector of L norms."""
    import jax
    import jax.numpy as jnp

    def per(path, a):
        # leaves under "layers" carry the layer axis first
        in_layers = any(getattr(p, "key", None) == "layers" for p in path)
        a = a.astype(jnp.float32)
        axes = tuple(range(1, a.ndim)) if in_layers else None
        return jnp.sqrt(jnp.sum(a * a, axis=axes))

    return jax.jit(lambda t: jax.tree_util.tree_map_with_path(per, t))(tree)


def _delta_norms(params, key, s, dtype):
    """Norm of each leaf's change from the seeded weights, which are made
    again one leaf at a time (a second copy of the whole tree would set the
    process's memory peak)."""
    import jax
    import jax.numpy as jnp

    from benchmark import weights

    def top(name):
        def f(a, key):
            d = a.astype(jnp.float32) - weights.make_leaf(key, name, s, dtype).astype(jnp.float32)
            return jnp.sqrt(jnp.sum(d * d))
        return jax.jit(f)

    def stacked(name):
        def f(a, key):
            p0 = jax.vmap(lambda l: weights.make_leaf(key, name, s, dtype, l))(jnp.arange(s["layers"]))
            d = a.astype(jnp.float32) - p0.astype(jnp.float32)
            return jnp.sqrt(jnp.sum(d * d, axis=tuple(range(1, d.ndim))))
        return jax.jit(f)

    out = {n: top(n)(params[n], key) for n in weights.TOP_LEAVES}
    out["layers"] = {n: stacked(n)(params["layers"][n], key) for n in weights.LAYER_LEAVES}
    return out


def _named(tree: dict) -> dict[str, float]:
    """{"tok_emb": [n], "layers": {"wq": [n0, n1]}} -> {"tok_emb": n, "layers.0.wq": n0, ...}"""
    out = {}
    for k, v in tree.items():
        if k == "layers":
            for name, per_layer in v.items():
                for l, n in enumerate(per_layer):
                    out[f"layers.{l}.{name}"] = n
        else:
            out[k] = v[0]
    return out


def main() -> None:
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    p = argparse.ArgumentParser()
    p.add_argument("--root", required=True)
    p.add_argument("--plan", required=True, help="JSON file: config, mix, seed, seconds, trace ...")
    p.add_argument("--report", required=True)
    args = p.parse_args()
    sys.path.insert(0, args.root)
    with open(args.plan) as f:
        plan = json.load(f)
    cfg, mix, seed = plan["config"], plan["mix"], int(plan["seed"])

    import jax
    import jax.numpy as jnp

    import tony_tpu.train.loop as loop
    from benchmark import tracing, weights
    from tony_tpu.models.llama import LlamaConfig
    from tony_tpu.train import DataConfig, FitConfig, fit

    cache = tracing.count_cache_events()

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    report: dict = {"device": device}

    def write_report() -> None:
        tmp = args.report + ".tmp"
        with open(tmp, "w") as f:
            json.dump(report, f)
        os.replace(tmp, args.report)

    if plan["require"] and (device["platform"] != plan["require"]["platform"]
                            or device["kind"] not in plan["require"]["kinds"]
                            or device["count"] < plan["chips"]):
        report["refused"] = f"needs {plan['chips']} x {plan['require']}, JAX sees {device}"
        write_report()
        raise SystemExit(3)

    s = weights.sizes_of(cfg)
    dtype = jnp.dtype(cfg["dtype"])
    t = cfg["train"]
    opt = t["optimizer"]
    model = LlamaConfig(
        vocab_size=s["v"], dim=s["d"], n_layers=s["layers"], n_heads=s["h"],
        n_kv_heads=s["kv"], ffn_dim=s["f"], max_seq_len=max(mix["seq_len"], 16),
        rope_theta=s["theta"], norm_eps=s["eps"], dtype=dtype,
        attention_impl=t["attention_impl"], remat=t["remat"],
        remat_policy=t["remat_policy"], ce_impl=t["ce_impl"],
    )
    if model.head_dim != s["hd"]:
        raise SystemExit(f"head_dim {s['hd']} is not dim / n_heads: the program cannot run it")
    key = weights.base_key(seed)
    probe = Probe(key, s, dtype, int(mix["checked_updates"]), plan.get("fault", ""))

    orig_state, orig_step = loop.make_train_state, loop.make_train_step

    def seeded_state(rng, mcfg, mesh, optimizer, rules=None, **kw):
        state = orig_state(rng, mcfg, mesh, optimizer, rules, **kw) if rules is not None \
            else orig_state(rng, mcfg, mesh, optimizer, **kw)
        shardings = jax.tree.map(lambda a: a.sharding, state.params)
        # the benchmark's helper programs are cached whatever they took to build
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        params = jax.jit(lambda k: weights.make_params(k, s, dtype), out_shardings=shardings)(key)
        return replace(state, params=params)

    loop.make_train_state = seeded_state
    loop.make_train_step = lambda *a, **k: _Proxy(orig_step(*a, **k), probe)

    bounds: list[dict] = []     # every log boundary: step, wall time, loss
    win = {"start": None, "end": None, "deadline": None, "trace_stop_at": None}
    warm_step = int(mix["log_every"]) * int(mix.get("warmup_windows", 1))
    trace_dir = plan.get("trace_dir") or ""

    def on_metrics(m: dict) -> None:
        now = time.time()
        bounds.append({"step": m["step"], "t": now, "loss": m["loss"],
                       "program_mfu": m.get("mfu"),
                       "program_tokens_per_sec_per_chip": m.get("tokens_per_sec_per_chip")})
        if m["step"] < warm_step:
            return
        if win["start"] is None:
            win["start"] = now
            win["deadline"] = now + float(plan["seconds"])
            report["cache_before_window"] = dict(cache)
            return
        if win["end"] is not None:  # tracing, past the window
            if m["step"] >= win["trace_stop_at"]:
                raise _EndOfWindow
            return
        last = bounds[-1]["t"] - bounds[-2]["t"]
        if now + last > win["deadline"]:
            win["end"] = now
            if not trace_dir:
                raise _EndOfWindow
            tracing.start(trace_dir)
            win["trace_stop_at"] = m["step"] + int(mix["log_every"]) * int(mix.get("trace_windows", 1))

    token_file = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tokens.bin")
    if not os.path.exists(token_file):
        raise SystemExit(f"the staged token file {token_file} is not beside this script")
    fit_cfg = FitConfig(
        model=model,
        data=DataConfig(
            global_batch=int(mix["global_batch"]), seq_len=int(mix["seq_len"]),
            vocab_size=s["v"], seed=seed & 0x7FFFFFFF,
            path=token_file,
            native=False, prefetch=int(mix["prefetch"]),
        ),
        steps=int(opt["decay_steps"]), log_every=int(mix["log_every"]),
        lr=float(opt["lr"]), warmup_steps=int(opt["warmup_steps"]),
        mu_dtype=t["mu_dtype"], on_metrics=on_metrics,
    )
    try:
        fit(fit_cfg)
    except _EndOfWindow:
        pass
    finally:
        if win["trace_stop_at"] is not None:
            tracing.stop()
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    report.update(
        bounds=bounds, window_start=win["start"], window_end=win["end"], cache=dict(cache),
        memory_peak_bytes=max((m.get("peak_bytes_in_use", 0) for m in stats), default=0),
        bytes_limit=max((m.get("bytes_limit", 0) for m in stats), default=0),
        tokens_per_step=int(mix["global_batch"]) * int(mix["seq_len"]),
        n_devices=len(jax.devices()),
        probe=probe.report(float(opt["b1"])),
    )
    write_report()


if __name__ == "__main__":
    main()
