"""Training-step construction: sharded init, jitted update, metrics.

Replaces the reference's delegated data plane (Horovod allreduce / TF
parameter servers, SURVEY.md section 2 "Distributed communication backend")
with compiled XLA collectives: parameters and batch carry NamedShardings and
XLA inserts the psum/all-gather/reduce-scatter pattern implied by the mesh --
pure DP produces a gradient psum, FSDP produces reduce-scatter + all-gather,
TP produces activation collectives, with zero framework code per strategy.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tony_tpu.models import llama
from tony_tpu.parallel.sharding import DEFAULT_RULES, Rules, spec_for, tree_shardings

Params = dict[str, Any]


@jax.tree_util.register_dataclass
@dataclass
class TrainState:
    step: jax.Array
    params: Params
    opt_state: Any


def default_optimizer(
    lr: float = 3e-4, weight_decay: float = 0.1, warmup_steps: int = 100,
    decay_steps: int = 10000, grad_clip: float = 1.0,
    mu_dtype: Any = jnp.float32,
) -> optax.GradientTransformation:
    sched = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup_steps, max(decay_steps, warmup_steps + 1)
    )
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        # mu_dtype pins the first moment's dtype regardless of param dtype;
        # nu follows the params dtype in optax. fp32 mu is the conservative
        # default; bf16 frees 2 bytes/param of HBM, which on a memory-bound
        # chip funds activation-saving remat (bench.py uses it, +5 MFU pts
        # at 1.35B on 16GB). Full mixed-precision (fp32 master params) is a
        # separate concern from the moment dtype.
        optax.adamw(
            sched, b1=0.9, b2=0.95, weight_decay=weight_decay,
            mu_dtype=mu_dtype,
        ),
    )


def state_shardings(
    cfg: llama.LlamaConfig, mesh: Mesh, optimizer: optax.GradientTransformation,
    rules: Rules = DEFAULT_RULES,
) -> Any:
    """Shardings for the full TrainState (optimizer state mirrors params).

    Optimizer-state leaves are matched to parameters *structurally*: optax
    states embed param-shaped pytrees (Adam mu/nu) whose key paths end with
    the parameter's own path, so a path-suffix match recovers the exact
    sharding even when distinct params share a shape (e.g. wq/wk/wv/wo are
    all (L, 4096, 4096) in llama2_7b but shard differently). Scalar leaves
    (step counts) replicate.
    """
    p_shard = tree_shardings(llama.logical_axes(cfg), mesh, rules)
    params_shape = jax.eval_shape(partial(llama.init_params, cfg=cfg), jax.random.key(0))
    opt_shape = jax.eval_shape(optimizer.init, params_shape)
    replicated = NamedSharding(mesh, P())

    param_paths, _ = jax.tree_util.tree_flatten_with_path(params_shape)
    shard_leaves = jax.tree.leaves(p_shard)
    by_path = {tuple(str(k) for k in path): s
               for (path, _), s in zip(param_paths, shard_leaves)}
    shape_by_path = {tuple(str(k) for k in path): leaf.shape
                     for path, leaf in param_paths}

    def opt_leaf_sharding(path: tuple, leaf: jax.ShapeDtypeStruct) -> NamedSharding:
        keys = tuple(str(k) for k in path)
        for plen in range(len(keys), 0, -1):
            suffix = keys[-plen:]
            if suffix in by_path and shape_by_path[suffix] == leaf.shape:
                return by_path[suffix]
        return replicated

    o_shard = jax.tree_util.tree_map_with_path(opt_leaf_sharding, opt_shape)
    return TrainState(step=replicated, params=p_shard, opt_state=o_shard)


def train_state_avals(
    cfg: llama.LlamaConfig, optimizer: optax.GradientTransformation,
) -> TrainState:
    """Abstract (ShapeDtypeStruct) TrainState matching make_train_state's
    output — enough to ``step_fn.lower(...)`` before any array exists, so
    the train-step compile can run concurrently with state init and
    checkpoint restore (fit()'s compile-ahead path)."""
    params = jax.eval_shape(partial(llama.init_params, cfg=cfg), jax.random.key(0))
    return TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        params=params,
        opt_state=jax.eval_shape(optimizer.init, params),
    )


def make_train_state(
    rng: jax.Array, cfg: llama.LlamaConfig, mesh: Mesh,
    optimizer: optax.GradientTransformation, rules: Rules = DEFAULT_RULES,
) -> TrainState:
    """Initialise the TrainState directly sharded (no host-side full copy --
    required for models that don't fit one host/chip)."""
    shardings = state_shardings(cfg, mesh, optimizer, rules)

    def init(rng: jax.Array) -> TrainState:
        params = llama.init_params(rng, cfg)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=optimizer.init(params),
        )

    return jax.jit(init, out_shardings=shardings)(rng)


def make_train_step(
    cfg: llama.LlamaConfig, mesh: Mesh,
    optimizer: optax.GradientTransformation, rules: Rules = DEFAULT_RULES,
    *, n_microbatches: int = 0, pp_schedule: str = "gpipe",
    monitors: bool | None = None, grad_bucket_bytes: int | None = None,
) -> Callable[..., tuple[TrainState, dict[str, jax.Array]]]:
    """Build the jitted train step:
    ``(state, inputs[B,S], targets[B,S]) -> (state, metrics)``.

    Inputs/targets are pre-shifted next-token pairs (see
    llama.loss_from_pairs) so the seq axis shards cleanly over ``sp``.
    Gradients are computed in the params' dtype (Adam's first moment is kept
    fp32 via mu_dtype); donation avoids a second copy of state.

    ``monitors`` fuses the numerics-health value monitors (nonfinite
    counts, update-to-param ratio, per-layer grad RMS, batch fingerprint —
    obs/health.py) into the step's metrics; None resolves to "is a health
    sentinel armed in this process", so a disarmed run compiles none of
    them (bench.py's ``health_overhead`` measures the armed delta).

    A mesh with ``pp > 1`` selects a pipeline loss (layer stages over the
    ``pp`` axis, ``n_microbatches`` microbatches — default 2 per stage):
    ``pp_schedule='gpipe'`` (autodiff backward, O(M) activations) or
    ``'1f1b'`` (hand-scheduled interleaved backward, O(P) activations —
    raise n_microbatches freely to shrink the bubble). The caller's rules
    must map "layers" to "pp" (fit() does this automatically;
    :func:`pp_rules` applies the override).

    ``grad_bucket_bytes`` (> 0, dp > 1, pp == 1) switches the dp gradient
    reduction from GSPMD's single fused all-reduce to the async bucketed
    path: value_and_grad runs inside a shard_map manual over ``dp`` and the
    grads all-reduce in byte-budgeted buckets (ops.overlap.bucketed_psum),
    one collective per bucket in leaf order — each bucket's reduce
    dispatches as soon as its leaves' backward is done and rides behind the
    remaining backward compute. Size the budget off the measured anatomy
    report (ops.overlap.bucket_bytes_from_report). Value-exact: bucketing
    never changes the sums, so the loss trajectory is bitwise-identical to
    the unbucketed (single-bucket) manual path.
    """
    if pp_schedule not in ("gpipe", "1f1b"):
        # validate even on pp=1 meshes: a typo'd schedule must fail loudly,
        # not silently run the sequential loss
        raise ValueError(
            f"unknown pp_schedule {pp_schedule!r} (expected gpipe | 1f1b)"
        )
    pp = int(mesh.shape.get("pp", 1))
    # pin [B, S, D] activations to the canonical batch/seq sharding at the
    # trunk boundaries: without the constraint the partitioner propagates
    # the fsdp/tp weight shardings into the embedding gather / loss-head
    # reshape and resolves the conflict with involuntary full-remat
    # all-gathers (fwd AND bwd — the constraint's transpose pins the
    # cotangents), visible as "[SPMD] Involuntary full rematerialization"
    # warnings in the multichip dryrun log
    act_sharding = (
        NamedSharding(mesh, spec_for(("batch", "seq", "embed"), rules))
        if mesh.size > 1 else None
    )
    if pp > 1:
        rules = pp_rules(rules)
        pp_loss = pp_loss_from_pairs if pp_schedule == "gpipe" else pp_1f1b_loss_from_pairs
        loss_fn = partial(
            pp_loss, cfg=cfg, mesh=mesh,
            n_microbatches=n_microbatches or 2 * pp,
            act_sharding=act_sharding,
        )
    else:
        loss_fn = partial(
            llama.loss_from_pairs, cfg=cfg, act_sharding=act_sharding
        )
    dp = int(mesh.shape.get("dp", 1))
    if grad_bucket_bytes and dp > 1 and pp == 1:
        # async bucketed dp grad reduce: manualize the dp axis so the
        # reduction is OUR schedule (one psum per bucket, leaf order), not
        # the partitioner's single fused all-reduce. The local loss is the
        # mean over this shard's rows; psum/dp restores the global mean
        # (equal shard sizes), and grads pre-scale by 1/dp so the bucketed
        # psums land on the global-mean gradient directly.
        from tony_tpu.ops.overlap import bucketed_psum

        # no activation pinning inside the manual region: the constraint
        # names mesh axes the region has manualized (and there is no
        # partitioner decision left to pin on this side of the boundary)
        inner_loss = partial(llama.loss_from_pairs, cfg=cfg, act_sharding=None)

        def _local_vg(params, inputs, targets):
            loss, grads = jax.value_and_grad(inner_loss)(
                params, inputs, targets
            )
            n = jax.lax.axis_size("dp")
            loss = jax.lax.psum(loss, "dp") / n
            grads = jax.tree.map(lambda g: g / n, grads)
            grads = bucketed_psum(
                grads, "dp", bucket_bytes=int(grad_bucket_bytes)
            )
            return loss, grads

        batch_spec = P("dp", None)  # [B, S] token pairs, rows over dp
        bucketed_vg = jax.shard_map(
            _local_vg, mesh=mesh,
            in_specs=(P(), batch_spec, batch_spec),
            out_specs=(P(), P()),
            axis_names={"dp"},
        )
    else:
        bucketed_vg = None

    def value_and_grad_fn(params, inputs, targets):
        if bucketed_vg is not None:  # build-time constant, not a tracer
            return bucketed_vg(params, inputs, targets)
        return jax.value_and_grad(loss_fn)(params, inputs, targets)

    shardings = state_shardings(cfg, mesh, optimizer, rules)
    batch_sharding = NamedSharding(mesh, spec_for(("batch", "seq"), rules))
    replicated = NamedSharding(mesh, P())

    from tony_tpu.obs import health as _health

    if monitors is None:
        monitors = _health.active_sentinel() is not None
    # numerics chaos seam: poison the REPORTED loss with an in-graph NaN
    # from a chosen step onward (TONY_CHAOS_NAN_STEP; chaos-style jobs
    # export it into worker env) so a tier-1 job can prove injection ->
    # sentinel trip -> forensics end to end. Persistent like a real NaN'd
    # state — a one-step blip could fall between sampling strides, which
    # a genuine numerics death never does. Grads are untouched: the fault
    # is in the value telemetry, exactly what the sentinel watches.
    nan_step = _health.nan_inject_step()

    def step(state: TrainState, inputs: jax.Array, targets: jax.Array):
        loss, grads = value_and_grad_fn(state.params, inputs, targets)
        updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        gnorm = optax.global_norm(grads)
        if nan_step is not None:
            loss = loss + jnp.where(
                state.step + 1 >= nan_step, jnp.float32(jnp.nan), jnp.float32(0.0)
            )
        metrics = {"loss": loss, "grad_norm": gnorm, "step": state.step + 1}
        if monitors:
            metrics.update(_health.graph_monitors(
                loss, grads, new_params, updates, inputs
            ))
        return TrainState(state.step + 1, new_params, new_opt), metrics

    return jax.jit(
        step,
        in_shardings=(shardings, batch_sharding, batch_sharding),
        out_shardings=(shardings, replicated),
        donate_argnums=(0,),
    )


def pp_1f1b_loss_from_pairs(
    params: Params, inputs: jax.Array, targets: jax.Array, *,
    cfg: llama.LlamaConfig, mesh: Mesh, n_microbatches: int,
    act_sharding=None,
) -> jax.Array:
    """1F1B pipeline loss: same stage decomposition as the GPipe loss, but
    the backward is hand-scheduled (parallel.pipeline.pipeline_train_1f1b)
    with O(P) live activations instead of autodiff's O(M) — the loss head
    (final norm + lm head + CE) moves INSIDE the last stage so each
    microbatch's cotangent is seeded the moment its forward finishes.
    """
    from tony_tpu.parallel.pipeline import microbatch, pipeline_train_1f1b

    if cfg.is_moe:
        raise NotImplementedError(
            "pp_schedule='1f1b' + MoE not supported (aux loss is not "
            "threaded through the interleaved schedule); use 'gpipe'"
        )
    _pp_guard(cfg, mesh)

    x = llama.embed_tokens(params, inputs, act_sharding)
    cos, sin = llama.rope_table(cfg, inputs.shape[1])
    xs = microbatch(x, n_microbatches)
    tgts = microbatch(targets, n_microbatches)

    shared_stage = _pp_stage_fn(cfg, cos, sin)

    def stage_fn(lp_stack: Params, mb: jax.Array) -> jax.Array:
        return shared_stage(lp_stack, mb)[0]  # dense: aux is always 0

    def head_fn(hp: Params, y: jax.Array, tgt: jax.Array) -> jax.Array:
        return _ce_head(hp["final_norm"], hp["lm_head"], y, tgt, cfg)

    head_params = {"final_norm": params["final_norm"], "lm_head": params["lm_head"]}
    return pipeline_train_1f1b(
        stage_fn, head_fn, params["layers"], head_params, xs, tgts, mesh=mesh
    )


def _pp_guard(cfg: llama.LlamaConfig, mesh: Mesh) -> None:
    if cfg.attention_impl in ("ring", "ring_flash", "ulysses"):
        # shardy cannot re-bind collective axes inside the pp-manual stage
        # region (verifier rejects nested manual computations over sp)
        raise NotImplementedError(
            f"pp + attention_impl={cfg.attention_impl!r} is not supported: "
            "sequence-parallel attention cannot nest inside pipeline stages; "
            "use 'flash' or 'dot' with pp, or sp without pp"
        )
    pp = int(mesh.shape["pp"])
    if cfg.n_layers % pp:
        raise ValueError(f"n_layers={cfg.n_layers} not divisible by pp={pp}")


def _ce_head(final_norm: jax.Array, lm_head: jax.Array, h: jax.Array,
             targets: jax.Array, cfg: llama.LlamaConfig) -> jax.Array:
    """final norm + lm head + mean cross-entropy — the ONE copy both
    pipeline schedules share. Routes through llama.ce_tokens, so the fused
    chunked CE (ce_impl scan/pallas — no [mb, S, V] logits or dlogits per
    microbatch) and the dense reference stay interchangeable here exactly
    as in the sequential loss."""
    h = llama.rms_norm(h, final_norm, cfg.norm_eps)
    return jnp.mean(llama.ce_tokens(h, lm_head, targets, cfg))


def _pp_stage_fn(cfg: llama.LlamaConfig, cos: jax.Array, sin: jax.Array):
    """One pipeline stage: scan this stage's [L/P] layer stack over a
    microbatch, returning (y, summed aux). Shared by both schedules."""

    def stage_fn(lp_stack: Params, mb: jax.Array):
        def blk(carry, lp: Params):
            h, aux_acc = carry
            out, aux = llama.transformer_block(h, lp, cfg, cos, sin)
            return (out, aux_acc + aux), None

        if cfg.remat:
            blk = jax.checkpoint(
                blk, policy=jax.checkpoint_policies.nothing_saveable
            )
        # the aux carry must be pp-varying like the stage's layer params
        aux0 = jax.lax.pcast(jnp.zeros((), jnp.float32), ("pp",), to="varying")
        (y, aux), _ = jax.lax.scan(blk, (mb, aux0), lp_stack)
        return y, aux

    return stage_fn


def pp_rules(rules: Rules = DEFAULT_RULES) -> Rules:
    """Rules for pipeline training: the stacked-layer dim becomes the stage
    dim, sharded over ``pp`` (each stage owns n_layers/pp layers)."""
    return {**rules, "layers": "pp"}


def pp_loss_from_pairs(
    params: Params, inputs: jax.Array, targets: jax.Array, *,
    cfg: llama.LlamaConfig, mesh: Mesh, n_microbatches: int,
    act_sharding=None,
) -> jax.Array:
    """GPipe pipeline loss: embedding and head run auto-sharded outside the
    pipeline; the layer stack runs as pp stages under a shard_map that is
    manual over ``pp`` only (dp/fsdp/tp/sp stay XLA-auto inside the stages,
    so the same Megatron/FSDP shardings compose with pipelining).

    Reference: GPipe (arXiv:1811.06965) schedule; bubble (P-1)/(M+P-1).
    """
    from tony_tpu.parallel.pipeline import microbatch, pipeline_local, unmicrobatch

    _pp_guard(cfg, mesh)

    x = llama.embed_tokens(params, inputs, act_sharding)
    cos, sin = llama.rope_table(cfg, inputs.shape[1])
    xs = microbatch(x, n_microbatches)  # [M, mb, S, D]

    def body(stage_layers: Params, xs_: jax.Array, cos_: jax.Array, sin_: jax.Array):
        return pipeline_local(
            _pp_stage_fn(cfg, cos_, sin_), stage_layers, xs_,
            axis_name="pp", with_aux=True,
        )

    layer_specs = jax.tree.map(lambda _: P("pp"), params["layers"])
    h, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(layer_specs, P(), P(), P()),
        out_specs=(P(), P()),
        axis_names={"pp"},  # manual over pp; all other axes stay auto
    )(params["layers"], xs, cos, sin)
    h = unmicrobatch(h)
    if act_sharding is not None:
        # the CE head mixes h with batch-sharded targets; pin h to the same
        # layout so the partitioner doesn't invent a reshard
        h = jax.lax.with_sharding_constraint(h, act_sharding)

    ce = _ce_head(params["final_norm"], params["lm_head"], h, targets, cfg)
    if cfg.is_moe:
        # mirror loss_from_pairs: aux averaged over layers, scaled by coef
        ce = ce + cfg.moe_aux_coef * aux / cfg.n_layers
    return ce
