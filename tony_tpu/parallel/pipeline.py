"""Pipeline parallelism: GPipe-style microbatching over a ``pp`` mesh axis.

Not present in the reference (SURVEY.md section 2 parallelism table: PP "—").
TPU-native design: stages are devices along a mesh axis, activations hop
stage-to-stage with ``lax.ppermute`` (one ICI neighbour hop), and the
microbatch schedule is a single ``lax.fori_loop`` — compiled once, no
data-dependent Python control flow. The bubble is the standard GPipe
(P-1)/(M+P-1) fraction; raise ``n_microbatches`` to amortise.

Usage (inside or outside jit):

    stages = stack_stage_params(per_stage_params)      # leading dim = P
    y = pipeline_apply(stage_fn, stages, x_microbatched, mesh=mesh)

where ``stage_fn(stage_params, x) -> y`` maps one microbatch through one
stage, and x_microbatched has shape [M, mb, ...].
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def _pcast_varying(x, axis_names):
    return lax.pcast(x, tuple(axis_names), to="varying")


StageFn = Callable[[Any, jax.Array], jax.Array]


def pipeline_local(
    stage_fn: StageFn,
    stage_params: Any,
    x: jax.Array,
    *,
    axis_name: str = "pp",
    with_aux: bool = False,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """Per-device GPipe schedule; call inside shard_map.

    ``x``: [M, mb, ...] microbatched input, replicated over the axis (only
    stage 0 reads it). Returns [M, mb, ...] outputs, replicated (the last
    stage's results are broadcast with a psum).

    ``with_aux``: stage_fn returns ``(y, aux_scalar)`` (e.g. a MoE
    load-balancing loss); real ticks' aux is accumulated per stage, summed
    over stages with a psum, and averaged over microbatches — the result is
    ``(out, aux)`` where aux matches the sequential trainer's
    sum-over-layers, mean-over-batch scalar.
    """
    n_stages = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    M = x.shape[0]
    n_ticks = M + n_stages - 1
    # stage s receives from s-1; the (n-1 -> 0) edge carries garbage that
    # stage 0 never reads (it pulls from x), but keeps the perm a bijection.
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def run_stage(params, batch):
        result = stage_fn(params, batch)
        return result if with_aux else (result, jnp.zeros((), jnp.float32))

    def probe_out():
        """Output structure for one microbatch (to size the buffers).

        The probe input must carry the same varying-axes type as the real
        per-tick input (pp-varying): a stage_fn that scans over pp-sharded
        layer params would otherwise fail vma typing at trace time.
        """
        xin = jax.tree.map(lambda a: _pcast_varying(a, (axis_name,)), x[0])
        return jax.eval_shape(lambda p, b: run_stage(p, b)[0], stage_params, xin)

    out_shape = probe_out()
    # pcast marks the zero buffers as device-varying along the pipeline axis
    # (jax>=0.9 shard_map typing: loop carries must match the outputs, which
    # become varying after ppermute/psum).
    recv0 = _pcast_varying(
        jnp.zeros(out_shape.shape, out_shape.dtype), (axis_name,)
    )
    out0 = _pcast_varying(
        jnp.zeros((M, *out_shape.shape), out_shape.dtype), (axis_name,)
    )
    aux0 = _pcast_varying(jnp.zeros((), jnp.float32), (axis_name,))

    def tick(t, carry):
        recv, out, aux_acc = carry
        feed_idx = jnp.clip(t, 0, M - 1)
        first_stage_in = lax.dynamic_index_in_dim(x, feed_idx, 0, keepdims=False)
        first_stage_in = _pcast_varying(
            first_stage_in.astype(recv.dtype), (axis_name,)
        )
        cur = jnp.where(my == 0, first_stage_in, recv)
        y, aux = run_stage(stage_params, cur)
        # stage s holds microbatch t-s at tick t; other ticks are warmup/
        # drain garbage whose aux must not pollute the accumulator
        valid = (t >= my) & (t - my < M)
        aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
        out_idx = jnp.clip(t - (n_stages - 1), 0, M - 1)
        updated = lax.dynamic_update_index_in_dim(out, y, out_idx, 0)
        out = jnp.where(t >= n_stages - 1, updated, out)
        recv = lax.ppermute(y, axis_name, perm)
        return recv, out, aux_acc

    _, out, aux_acc = lax.fori_loop(0, n_ticks, tick, (recv0, out0, aux0))
    # Broadcast the last stage's buffer to every stage.
    out = jnp.where(my == n_stages - 1, out, jnp.zeros_like(out))
    out = lax.psum(out, axis_name)
    if not with_aux:
        return out
    aux = lax.psum(aux_acc, axis_name) / M  # sum stages, mean microbatches
    return out, aux


def pipeline_apply(
    stage_fn: StageFn,
    stacked_stage_params: Any,
    x: jax.Array,
    *,
    mesh: Mesh,
    axis_name: str = "pp",
) -> jax.Array:
    """Full-array entry: shard stage params over ``axis_name``, run the
    schedule, return outputs for all microbatches (replicated over the axis).

    ``stacked_stage_params``: pytree whose leaves have a leading stage dim of
    size mesh.shape[axis_name].
    """
    param_specs = jax.tree.map(lambda _: P(axis_name), stacked_stage_params)

    def body(params, xs):
        params = jax.tree.map(lambda a: a[0], params)  # drop unit stage dim
        return pipeline_local(stage_fn, params, xs, axis_name=axis_name)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
    )(stacked_stage_params, x)


# --- 1F1B (memory-capped) training schedule ----------------------------------


def pipeline_train_1f1b(
    stage_fn: StageFn,
    head_fn: Callable[[Any, jax.Array, jax.Array], jax.Array],
    stage_params: Any,
    head_params: Any,
    xs: jax.Array,
    targets: jax.Array,
    *,
    mesh: Mesh,
    axis_name: str = "pp",
):
    """Pipelined training with 1F1B-style interleaving: loss with grads via
    a hand-scheduled backward (jax.custom_vjp), O(P) activation memory.

    GPipe under autodiff stores every microbatch's stage input until the
    backward phase — O(M) live activations per stage. Here each global tick
    runs one forward AND one backward slot per stage: microbatch i's forward
    hits stage s at tick ``i + s`` and its backward at tick ``i + 2P-2 - s``,
    so at most ``2P-1`` stage inputs are ever buffered (the eager variant of
    PipeDream-flush/1F1B, arXiv:2104.04473: same flush bubble, constant
    memory). The backward slot recomputes its stage forward from the saved
    input (per-microbatch remat) inside ``jax.vjp``.

    - ``stage_fn(stage_params, x_mb) -> y_mb`` — one microbatch through this
      stage's layers (differentiable).
    - ``head_fn(head_params, y_mb, tgt_mb) -> scalar`` — the per-microbatch
      loss (final norm + lm head + CE); runs on the last stage only.
    - ``xs``: [M, mb, ...] microbatched embedded inputs; ``targets``:
      [M, mb, ...] microbatched labels.

    Returns the scalar mean-over-microbatches loss. Gradients flow to
    stage_params / head_params / xs through the custom VJP (targets get
    zeros), so ``jax.value_and_grad`` over a loss built on this function
    computes pipeline-parallel gradients without ever materialising the
    GPipe activation tail.
    """
    return _pipeline_1f1b(
        stage_params, head_params, xs, targets, stage_fn, head_fn, mesh, axis_name
    )


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _pipeline_1f1b(stage_params, head_params, xs, targets,
                   stage_fn, head_fn, mesh, axis_name):
    loss, *_ = _run_1f1b(
        stage_params, head_params, xs, targets, stage_fn, head_fn, mesh, axis_name
    )
    return loss


def _pipeline_1f1b_fwd(stage_params, head_params, xs, targets,
                       stage_fn, head_fn, mesh, axis_name):
    loss, g_stage, g_head, dxs = _run_1f1b(
        stage_params, head_params, xs, targets, stage_fn, head_fn, mesh, axis_name
    )
    return loss, (g_stage, g_head, dxs, targets.shape)


def _pipeline_1f1b_bwd(stage_fn, head_fn, mesh, axis_name, res, g_loss):
    g_stage, g_head, dxs, tgt_shape = res
    scale = lambda t: jax.tree.map(lambda a: a * g_loss, t)  # noqa: E731
    # integer targets take a float0 cotangent
    dt = np.zeros(tgt_shape, jax.dtypes.float0)
    return scale(g_stage), scale(g_head), scale(dxs), dt


_pipeline_1f1b.defvjp(_pipeline_1f1b_fwd, _pipeline_1f1b_bwd)


def _run_1f1b(stage_params, head_params, xs, targets,
              stage_fn, head_fn, mesh, axis_name):
    """The combined fwd+bwd schedule; returns (loss, stage_grads,
    head_grads, dxs)."""
    P_ = int(mesh.shape[axis_name])
    M = xs.shape[0]
    layer_specs = jax.tree.map(lambda _: P(axis_name), stage_params)

    def body(sp, hp, xs_, tg_):
        return _1f1b_local(
            sp, hp, xs_, tg_, stage_fn=stage_fn, head_fn=head_fn,
            axis_name=axis_name, n_stages=P_, M=M,
        )

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(layer_specs, P(), P(), P()),
        out_specs=(P(), layer_specs, P(), P()),
        axis_names={axis_name},
    )(stage_params, head_params, xs, targets)


def _1f1b_local(stage_params, head_params, xs, targets, *,
                stage_fn, head_fn, axis_name, n_stages, M):
    my = lax.axis_index(axis_name)
    # stage_params arrive pp-sharded on dim 0: each stage sees its own
    # [L/P, ...] layer stack and stage_fn owns its interpretation (scan
    # over it for a transformer; index [0] for one-param-per-stage)
    sp_local = stage_params
    n_ticks = M + 2 * n_stages - 2
    buf_n = max(1, 2 * n_stages - 1)  # max in-flight inputs (stage 0)
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    bwd_perm = [(i, (i - 1) % n_stages) for i in range(n_stages)]

    def vary(a):
        # idempotent: zeros_like of pp-sharded params is already varying
        if axis_name in jax.typeof(a).vma:
            return a
        return _pcast_varying(a, (axis_name,))

    xin0 = jax.tree.map(vary, xs[0])
    y_shape = jax.eval_shape(lambda p, b: stage_fn(p, b), sp_local, xin0)
    # the head vjp must see VARYING head params: differentiating a varying
    # computation w.r.t. an unvarying input makes jax insert an implicit
    # psum over the axis (transpose of broadcast), which would sum the
    # non-last stages' garbage head grads into the real ones
    hp_var = jax.tree.map(vary, head_params)

    recv_f0 = vary(jnp.zeros(y_shape.shape, y_shape.dtype))
    recv_b0 = vary(jnp.zeros(y_shape.shape, y_shape.dtype))
    inbuf0 = vary(jnp.zeros((buf_n, *xs.shape[1:]), xs.dtype))
    g_stage0 = jax.tree.map(lambda a: vary(jnp.zeros_like(a)), sp_local)
    g_head0 = jax.tree.map(lambda a: vary(jnp.zeros_like(a)), head_params)
    dxs0 = vary(jnp.zeros_like(xs))
    loss0 = vary(jnp.zeros((), jnp.float32))

    last = n_stages - 1

    def zeros_of(tree):
        # a*0 (not zeros_like) keeps the varying-axes type on the zeros
        return jax.tree.map(lambda a: a * 0, tree)

    def tick(t, carry):
        recv_f, recv_b, inbuf, loss, g_stage, g_head, dxs = carry

        # ---- forward slot: microbatch i_f enters this stage -------------
        i_f = t - my
        valid_f = (i_f >= 0) & (i_f < M)
        idx_f = jnp.clip(i_f, 0, M - 1)
        first_in = vary(
            lax.dynamic_index_in_dim(xs, idx_f, 0, keepdims=False).astype(
                recv_f.dtype
            )
        )
        x_in = jnp.where(my == 0, first_in, recv_f)
        inbuf = jnp.where(
            valid_f,
            lax.dynamic_update_index_in_dim(inbuf, x_in, idx_f % buf_n, 0),
            inbuf,
        )
        # warmup/drain ticks skip the stage compute entirely (lax.cond is
        # per-device inside the manual region and both branches are
        # collective-free): a stage whose slot is empty must not make its
        # ppermute partners wait on garbage compute
        y = lax.cond(
            valid_f,
            lambda a: stage_fn(sp_local, a),
            lambda a: recv_f * 0,  # y-shaped varying zeros
            x_in,
        )
        send_f = lax.ppermute(y, axis_name, fwd_perm)

        # ---- backward slot: microbatch i_b leaves this stage ------------
        i_b = t - (2 * n_stages - 2 - my)
        valid_b = (i_b >= 0) & (i_b < M)
        idx_b = jnp.clip(i_b, 0, M - 1)
        x_saved = lax.dynamic_index_in_dim(inbuf, idx_b % buf_n, 0, keepdims=False)
        tgt = vary(lax.dynamic_index_in_dim(targets, idx_b, 0, keepdims=False))
        one = vary(jnp.asarray(1.0, jnp.float32))  # varying scalar seed

        def bwd_slot(op):
            x_saved_, recv_b_, tgt_, one_ = op
            y_b, pull = jax.vjp(lambda p, a: stage_fn(p, a), sp_local, x_saved_)

            # the loss head (final norm + vocab matmul + CE) runs ONLY on
            # the last stage — running it everywhere and masking after the
            # fact would add P-1 redundant vocab-sized fwd+bwd per tick
            def head_slot(hy):
                hp_, y_ = hy
                loss_i, head_pull = jax.vjp(
                    lambda hp, a: head_fn(hp, a, tgt_), hp_, y_
                )
                dhead_i, dy_head = head_pull(one_ / M)
                return loss_i, dhead_i, dy_head

            def head_skip(hy):
                hp_, y_ = hy
                return one_ * 0, zeros_of(hp_), y_ * 0

            loss_i, dhead_i, dy_head = lax.cond(
                my == last, head_slot, head_skip, (hp_var, y_b)
            )
            ct = jnp.where(my == last, dy_head.astype(y_b.dtype), recv_b_)
            dstage_i, dx_i = pull(ct)
            return loss_i, dhead_i, dstage_i, dx_i

        def bwd_skip(op):
            x_saved_, recv_b_, tgt_, one_ = op
            return (
                one_ * 0,
                zeros_of(hp_var),
                zeros_of(sp_local),
                x_saved_ * 0,
            )

        loss_i, dhead_i, dstage_i, dx_i = lax.cond(
            valid_b, bwd_slot, bwd_skip, (x_saved, recv_b, tgt, one)
        )
        g_stage = jax.tree.map(lambda acc, gi: acc + gi, g_stage, dstage_i)
        g_head = jax.tree.map(lambda acc, gi: acc + gi, g_head, dhead_i)
        loss = loss + loss_i / M
        # stage 0's input cotangent feeds the embedding backward
        dxs = jnp.where(
            valid_b & (my == 0),
            lax.dynamic_update_index_in_dim(dxs, dx_i.astype(dxs.dtype), idx_b, 0),
            dxs,
        )
        # the receiver uses this as a cotangent for ITS output (y dtype),
        # mirroring the forward slot's first_in cast
        send_b = lax.ppermute(dx_i.astype(recv_b.dtype), axis_name, bwd_perm)

        return send_f, send_b, inbuf, loss, g_stage, g_head, dxs

    _, _, _, loss, g_stage, g_head, dxs = lax.fori_loop(
        0, n_ticks, tick,
        (recv_f0, recv_b0, inbuf0, loss0, g_stage0, g_head0, dxs0),
    )
    # loss/head grads live on the last stage, dxs on stage 0: psum replicates
    loss = lax.psum(loss, axis_name)
    g_head = jax.tree.map(lambda a: lax.psum(a, axis_name), g_head)
    dxs = lax.psum(dxs, axis_name)
    # g_stage already has the local [L/P, ...] stack shape of the
    # P(axis_name) out_spec
    return loss, g_stage, g_head, dxs


def microbatch(x: jax.Array, n_microbatches: int) -> jax.Array:
    """[B, ...] -> [M, B/M, ...]."""
    B = x.shape[0]
    if B % n_microbatches:
        raise ValueError(f"batch {B} not divisible by n_microbatches={n_microbatches}")
    return x.reshape(n_microbatches, B // n_microbatches, *x.shape[1:])


def unmicrobatch(x: jax.Array) -> jax.Array:
    """[M, mb, ...] -> [M*mb, ...]."""
    return x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])


__all__ = [
    "microbatch", "pipeline_apply", "pipeline_local",
    "pipeline_train_1f1b", "unmicrobatch",
]
