"""The training job `chip_smoke.py` submits: fit() at Llama-2-7B's published
widths (dim 4096, 32 MHA heads of 128, FFN 11008, vocab 32000) with depth cut
to what one v5e chip holds, flash attention + ``save_attn_kernel`` remat +
fused scan CE + bf16 first moment.

A user-style job script: `tony submit` runs it as the worker's command (the
mesh is fit()'s default — fsdp over every device the worker sees), and bare
``python fit_job.py --one-device`` runs the same config on ``jax.devices()[:1]``
for the four-chip comparison. Prints ONE line, ``SMOKE_JSON {...}``: what the
process came up on, per-step loss and step time, per-device memory, whether
the step program holds the Pallas kernel, and JAX's own persistent-cache
hit/miss events.
"""

import argparse
import json
import logging
import time
from dataclasses import replace

MARK = "SMOKE_JSON "
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


def main() -> None:
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    p = argparse.ArgumentParser()
    p.add_argument("--layers", type=int, default=3)
    p.add_argument("--global-batch", type=int, default=4)
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--steps", type=int, default=6)
    p.add_argument("--tiny", action="store_true",
                   help="test-size widths (CPU rehearsal)")
    p.add_argument("--one-device", action="store_true",
                   help="mesh over jax.devices()[:1] whatever else is visible")
    args = p.parse_args()

    import jax
    import jax.monitoring
    import jax.numpy as jnp

    from tony_tpu.models.llama import LlamaConfig
    from tony_tpu.obs.metrics import device_identity
    from tony_tpu.parallel.mesh import MeshShape, get_default_mesh
    from tony_tpu.train import DataConfig, FitConfig, fit
    from tony_tpu.train.trainer import (
        default_optimizer, make_train_step, train_state_avals,
    )

    cache = dict.fromkeys(CACHE_EVENTS.values(), 0)

    def on_event(event: str, **_kw) -> None:
        if event in CACHE_EVENTS:
            cache[CACHE_EVENTS[event]] += 1

    jax.monitoring.register_event_listener(on_event)

    base = LlamaConfig.tiny(dtype=jnp.bfloat16) if args.tiny else LlamaConfig.llama2_7b()
    model = replace(
        base, n_layers=args.layers, attention_impl="flash",
        remat=True, remat_policy="save_attn_kernel", ce_impl="scan",
        max_seq_len=max(base.max_seq_len, args.seq_len),
    )
    steps: list[dict] = []
    memory: list[dict] = []

    def on_metrics(m: dict) -> None:
        # called at each log boundary AFTER the device sync on that step's
        # loss (fit()'s _emit), so consecutive calls are one synced step apart
        steps.append({"step": m["step"], "loss": m["loss"],
                      "t": time.perf_counter()})
        if m["step"] == 3:
            # the train state is alive and sharded here; after fit()
            # returns it has been freed
            memory.extend(
                {"id": d.id, **{k: (d.memory_stats() or {}).get(k)
                                for k in ("bytes_in_use", "peak_bytes_in_use",
                                          "bytes_limit")}}
                for d in jax.local_devices()
            )

    cfg = FitConfig(
        model=model,
        data=DataConfig(global_batch=args.global_batch, seq_len=args.seq_len,
                        vocab_size=model.vocab_size),
        mesh_shape=MeshShape() if args.one_device else None,
        # FitConfig's default schedule (lr 3e-4 after 100 warm-up steps): the
        # few steps taken here stay on the ramp, so the losses are a stable
        # trajectory two layouts can be compared on
        steps=args.steps, log_every=1, mu_dtype="bfloat16",
        on_metrics=on_metrics,
    )
    final = fit(cfg)
    cache_seen = dict(cache)  # the re-compile below must not count

    # the step program as fit() built it (same mesh, optimizer, shapes):
    # lowering alone shows whether the kernel is a Mosaic custom call or
    # the interpreter's XLA ops, and whether fsdp put all-gathers in
    mesh = get_default_mesh()
    optimizer = default_optimizer(
        lr=cfg.lr, warmup_steps=cfg.warmup_steps,
        decay_steps=max(cfg.steps, cfg.warmup_steps + 1),
        mu_dtype=jnp.dtype(cfg.mu_dtype),
    )
    batch = jax.ShapeDtypeStruct((args.global_batch, args.seq_len), jnp.int32)
    lowered = make_train_step(model, mesh, optimizer, cfg.rules).lower(
        train_state_avals(model, optimizer), batch, batch
    )
    compiled = lowered.compile()
    plan = compiled.memory_analysis()
    from jax.experimental import mesh_utils

    try:
        topo_mesh = mesh_utils.create_device_mesh(
            mesh.devices.shape, devices=list(mesh.devices.flat)
        )
        topology_mesh = bool((topo_mesh == mesh.devices).all())
    except (ValueError, AssertionError):
        topology_mesh = False  # no topology metadata (virtual CPU devices)
    out = {
        **device_identity(),
        "n_params": model.n_params,
        "model": {"dim": model.dim, "n_heads": model.n_heads,
                  "head_dim": model.head_dim, "ffn_dim": model.ffn_dim,
                  "vocab_size": model.vocab_size, "n_layers": model.n_layers},
        "global_batch": args.global_batch, "seq_len": args.seq_len,
        "mesh": {k: int(v) for k, v in mesh.shape.items() if int(v) > 1},
        "topology_mesh": topology_mesh,
        "losses": [s["loss"] for s in steps],
        # step 1 carries the compile; 2.. are host-clock gaps between
        # consecutive device syncs
        "step_s": [round(b["t"] - a["t"], 4) for a, b in zip(steps, steps[1:])],
        "tokens_per_sec_per_chip": final.get("tokens_per_sec_per_chip"),
        "startup": final.get("startup"),
        "memory": memory,
        # the compiler's per-device plan for the same step
        "planned_bytes": {"argument": plan.argument_size_in_bytes,
                          "temp": plan.temp_size_in_bytes},
        "tpu_custom_call": "tpu_custom_call" in lowered.as_text(),
        "all_gathers": compiled.as_text().count("all-gather"),
        **cache_seen,
    }
    if jax.process_index() == 0:
        print(MARK + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
