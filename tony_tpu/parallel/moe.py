"""Mixture-of-Experts with expert parallelism.

Absent from the reference (SURVEY.md section 2 parallelism table: EP "—").
Three interchangeable dispatch implementations behind ``MoEConfig.dispatch``:

- ``'einsum'`` — GShard/Switch (arXiv:2006.16668) dense one-hot
  dispatch/combine einsums over fixed ``[E, C]`` capacity slots. MXU-friendly
  and the parity reference, but the routing einsums cost ~2x the expert FFN
  at bench shapes and overflow tokens are dropped.
- ``'gather'`` — scatter/gather into the same capacity slots: zero routing
  matmul FLOPs, identical drop semantics (docs/PERF.md round 4).
- ``'grouped'`` — dropless sorted grouped GEMM (MegaBlocks,
  arXiv:2211.15841): routes are sorted by expert into ragged contiguous
  groups and the expert FFN runs as a grouped matmul over block-aligned row
  tiles (tony_tpu.ops.grouped_mm — a lax.scan fallback anywhere, a Pallas
  kernel on TPU via ``gmm_impl``). No capacity: nothing padded beyond one
  row tile per expert, nothing dropped.

All routing statistics (softmax, gates, aux loss) are float32 regardless of
activation dtype; expert FFN compute follows the input dtype (bf16 on TPU).
The expert dim is a logical axis ("expert") the sharding rules map onto the
mesh's ``ep`` axis; the grouped path additionally ships an explicit
shard_map-over-ep formulation (each shard runs the grouped FFN for its local
experts only and the combine is a psum) used automatically when a default
mesh with ``ep > 1`` is registered. ``MoEConfig.overlap_impl`` decomposes
that combine into per-token-chunk partial psums so expert compute overlaps
combine traffic (tony_tpu.ops.moe_overlap, docs/PERF.md round 20).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class MoEConfig:
    dim: int
    ffn_dim: int
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    # 'grouped' (dropless sorted grouped GEMM — no capacity slots at all;
    # the DEFAULT since round 20, when its PR-4 bench gate "grouped beats
    # gather tokens/s" was measured to hold and `grouped_vs_gather` became
    # a perf-diff-judged ratio, docs/PERF.md), 'gather' (scatter/gather
    # capacity dispatch, O(T*D) data movement — one knob away), or
    # 'einsum' (dense one-hot dispatch, O(T*E*C*D) matmul FLOPs — the
    # reference implementation the others are parity-tested against).
    dispatch: str = "grouped"
    # dispatch='grouped': row-tile size of the grouped GEMM; each expert's
    # ragged group is padded up to a multiple of this (keep it a multiple
    # of 16 so bf16 sublane tiling is happy on TPU)
    group_block: int = 128
    # dispatch='grouped': 'scan' (pure-XLA lax.scan over row tiles — CPU,
    # shard_map and ep-mesh safe, the default) | 'pallas' (TPU kernel with
    # scalar-prefetched tile->expert map; interpret mode on CPU)
    gmm_impl: str = "scan"
    # dispatch='grouped' on an ep mesh: 'off' keeps the single blocking
    # post-FFN psum; 'scan' | 'pallas' decompose it into per-token-chunk
    # partial combines so later chunks' expert FFN overlaps earlier chunks'
    # combine traffic (tony_tpu.ops.moe_overlap, docs/PERF.md round 20).
    # The impl names the chunk FFN's grouped-GEMM kernel; the schedule is
    # identical. Declines cleanly (single psum) when the chunk split
    # doesn't divide, and rides the ep path's own fallbacks otherwise.
    overlap_impl: str = "off"
    # overlap_impl != 'off': tokens per combine chunk, per shard (0 auto-
    # picks the largest clean split in {4,3,2} chunks; a measured value
    # comes from ops.moe_overlap.chunk_tokens_from_report). Must divide
    # the per-shard token count or the overlap declines to the single psum.
    overlap_chunk: int = 0

    def capacity(self, n_tokens: int) -> int:
        """Per-expert token slots; static given the (padded) token count.

        Rounded up to a multiple of 8 so the [E, C, D] dispatch buffers tile
        cleanly on the TPU sublane dim (fp32 min tile is 8 rows)."""
        cap = max(
            1,
            int(math.ceil(self.capacity_factor * self.top_k * n_tokens / self.n_experts)),
        )
        return -(-cap // 8) * 8


def logical_axes() -> dict[str, tuple[str | None, ...]]:
    """Sharding names; "expert" maps to a mesh axis via the rules table."""
    return {
        "router": ("embed", "expert"),
        "w1": ("expert", "embed", "ffn"),
        "w3": ("expert", "embed", "ffn"),
        "w2": ("expert", "ffn", "embed"),
    }


def init_moe_params(rng: jax.Array, cfg: MoEConfig, dtype=jnp.bfloat16) -> dict[str, Any]:
    k0, k1, k2, k3 = jax.random.split(rng, 4)
    d, f, e = cfg.dim, cfg.ffn_dim, cfg.n_experts

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)

    return {
        "router": dense(k0, (d, e), d).astype(jnp.float32),  # routing in fp32
        "w1": dense(k1, (e, d, f), d),
        "w3": dense(k2, (e, d, f), d),
        "w2": dense(k3, (e, f, d), f),
    }


def _top_k_select(probs: jax.Array, cfg: MoEConfig):
    """One vectorized top-k routing pass shared by every dispatch impl.

    probs: [T, E]. Returns ``(experts [T, k] int32, gates [T, k] f32,
    pos [T, k] int32, aux f32 scalar)`` — each token's chosen experts, their
    router probabilities, and the token's position in each chosen expert's
    queue. Selection and position semantics are identical to the k-round
    argmax-and-mask loop this replaces: ``lax.top_k`` breaks ties toward the
    lower expert index (as repeated argmax did), and queue positions are
    assigned in round-major order (every token's round-0 pick queues before
    any round-1 pick) via a single cumsum over the [k*T, E] route sequence.
    All statistics are float32 regardless of the input dtype.
    """
    T, E = probs.shape
    k = cfg.top_k
    p32 = probs.astype(jnp.float32)
    gates, sel = jax.lax.top_k(p32, k)                        # [T, k] each
    sel = sel.astype(jnp.int32)
    onehot = jax.nn.one_hot(sel, E, dtype=jnp.float32)        # [T, k, E]
    rm = jnp.swapaxes(onehot, 0, 1).reshape(k * T, E)         # round-major
    pos_rm = jnp.cumsum(rm, axis=0) - rm                      # [k*T, E]
    pos = jnp.sum(
        jnp.swapaxes(pos_rm.reshape(k, T, E), 0, 1) * onehot, axis=-1
    ).astype(jnp.int32)                                       # [T, k]
    # load-balancing aux loss (Switch eq. 4): E * sum(frac_routed * mean_prob)
    importance = jnp.sum(jnp.mean(onehot, axis=0), axis=0)    # [E]
    aux = cfg.n_experts * jnp.sum(importance / k * jnp.mean(p32, axis=0))
    return sel, gates, pos, aux


def routing_stats(probs: jax.Array, cfg: MoEConfig) -> dict[str, float]:
    """Routing health under the *capacity* semantics: the route fraction the
    fixed [E, C] slots would drop, and the expert load imbalance (max/mean
    assigned routes). The grouped dispatch drops nothing — these numbers
    quantify exactly what dropless recovers."""
    T = probs.shape[0]
    sel, _, pos, _ = _top_k_select(probs, cfg)
    cap = cfg.capacity(T)
    kept = jnp.mean((pos < cap).astype(jnp.float32))
    counts = jnp.bincount(sel.reshape(-1), length=cfg.n_experts)
    imb = counts.max() / jnp.maximum(jnp.mean(counts.astype(jnp.float32)), 1.0)
    return {
        "dropped_frac": round(float(1.0 - kept), 4),
        "load_imbalance": round(float(imb), 3),
        "capacity": int(cap),
        "capacity_factor": cfg.capacity_factor,
    }


def _top_k_dispatch(probs: jax.Array, cfg: MoEConfig, capacity: int):
    """Build dispatch/combine tensors from router probabilities.

    probs: [T, E] float32. Returns (dispatch [T,E,C] in {0,1}, combine
    [T,E,C] fp32 gates, aux_loss scalar). Tokens beyond an expert's capacity
    are dropped (their combine weight is zero), the Switch/GShard contract.
    """
    E = probs.shape[1]
    sel, gates, pos, aux = _top_k_select(probs, cfg)
    within = (pos < capacity).astype(jnp.float32)             # [T, k]
    oh_e = jax.nn.one_hot(sel, E, dtype=jnp.float32)          # [T, k, E]
    oh_c = jax.nn.one_hot(
        jnp.clip(pos, 0, capacity - 1), capacity, dtype=jnp.float32
    )                                                         # [T, k, C]
    dispatch = jnp.einsum("tke,tkc->tec", oh_e * within[..., None], oh_c)
    combine = jnp.einsum(
        "tke,tkc->tec", oh_e * (gates * within)[..., None], oh_c
    )
    # renormalise combine weights over the selected (and kept) experts
    denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
    combine = combine / jnp.maximum(denom, 1e-9)
    return dispatch, combine, aux


def _moe_gather(params: dict[str, Any], flat: jax.Array, cfg: MoEConfig,
                capacity: int, probs: jax.Array):
    """Scatter/gather dispatch: build the slot->token index map (one scatter
    of int32), gather tokens into [E,C,D], run the expert FFN, and gather
    each token's expert outputs back with gate weighting. Data movement is
    O(E*C*D + k*T*D) with ZERO routing matmul FLOPs — vs the one-hot
    einsums' 2*T*E*C*D FLOPs each way (the measured reason behind the
    round-3 22% MoE MFU; docs/PERF.md). Same capacity/drop semantics as the
    einsum reference."""
    T, D = flat.shape
    E, k = cfg.n_experts, cfg.top_k
    sel, gates, pos, aux = _top_k_select(probs, cfg)
    valid = pos < capacity                                    # [T, k]
    flat_slot = (sel * capacity + jnp.clip(pos, 0, capacity - 1)).reshape(T * k)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)

    # slot -> token map; sentinel T points at a zero pad row (empty slots);
    # kept slots are unique (pos is the global occupancy rank), so one
    # scatter covers all k rounds
    target = jnp.where(valid.reshape(T * k), flat_slot, E * capacity)
    slot_token = (
        jnp.full((E * capacity,), T, jnp.int32).at[target].set(tok, mode="drop")
    )

    padded = jnp.concatenate([flat, jnp.zeros((1, D), flat.dtype)], axis=0)
    expert_in = padded[slot_token].reshape(E, capacity, D)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, params["w1"]))
    h = h * jnp.einsum("ecd,edf->ecf", expert_in, params["w3"])
    expert_out = jnp.einsum("ecf,efd->ecd", h, params["w2"])

    # combine: each token gathers its (<= k) expert outputs, gate-weighted
    # and renormalised over the experts that actually kept it
    denom = jnp.maximum(jnp.sum(gates * valid, axis=1), 1e-9)  # [T]
    out_flat = expert_out.reshape(E * capacity, D)
    tok_out = out_flat[jnp.where(valid.reshape(T * k), flat_slot, 0)]
    w = ((gates * valid) / denom[:, None]).reshape(T * k).astype(flat.dtype)
    y = jnp.zeros((T, D), flat.dtype).at[tok].add(w[:, None] * tok_out)
    return y, aux


# --- grouped (dropless) dispatch ----------------------------------------------


def _grouped_ffn(params: dict[str, Any], flat: jax.Array, tok: jax.Array,
                 group: jax.Array, weight: jax.Array, n_groups: int,
                 cfg: MoEConfig) -> jax.Array:
    """Sorted grouped-GEMM expert FFN over a flat route list.

    ``tok``/``group``/``weight``: [R] routes — the token row each route
    reads, its expert group in [0, n_groups), and its final combine weight
    (gate/denom, already zeroed for routes this shard doesn't own). Sorts
    routes by group (stable), scatters token rows into a block-aligned
    padded buffer (tony_tpu.ops.grouped_mm.grouped_layout), runs the SwiGLU
    FFN as three grouped matmuls, and scatter-adds the weighted outputs back
    per token. Returns [T, D]."""
    from tony_tpu.ops.grouped_mm import grouped_layout, grouped_matmul

    T, D = flat.shape
    R = tok.shape[0]
    block = cfg.group_block
    order = jnp.argsort(group, stable=True)
    g_s, tok_s, w_s = group[order], tok[order], weight[order]
    sizes = jnp.bincount(group, length=n_groups)
    n_tiles = -(-R // block) + n_groups  # static bound: 1 part tile/group
    starts, tile_group = grouped_layout(sizes, block, n_tiles)
    compact_start = jnp.cumsum(sizes) - sizes
    dst = starts[g_s] + (jnp.arange(R, dtype=jnp.int32) - compact_start[g_s])

    x_pad = (
        jnp.zeros((n_tiles * block, D), flat.dtype).at[dst].set(flat[tok_s])
    )
    gmm = partial(grouped_matmul, tile_group=tile_group, impl=cfg.gmm_impl)
    h = jax.nn.silu(gmm(x_pad, params["w1"])) * gmm(x_pad, params["w3"])
    y_pad = gmm(h, params["w2"])
    contrib = w_s.astype(flat.dtype)[:, None] * y_pad[dst]
    return jnp.zeros((T, D), flat.dtype).at[tok_s].add(contrib)


def _moe_grouped(params: dict[str, Any], flat: jax.Array, cfg: MoEConfig,
                 probs: jax.Array):
    """Dropless grouped dispatch: every route is served (no capacity), the
    combine weight is the gate renormalised over all k selections."""
    T, _ = flat.shape
    k = cfg.top_k
    sel, gates, _, aux = _top_k_select(probs, cfg)  # pos unused: dropless
    denom = jnp.maximum(jnp.sum(gates, axis=1), 1e-9)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    weight = (gates / denom[:, None]).reshape(T * k)
    y = _grouped_ffn(params, flat, tok, sel.reshape(T * k), weight,
                     cfg.n_experts, cfg)
    return y, aux


def _chunk_ffn(w1, w3, w2, flat_, sel_, weight_, *, cfg: MoEConfig,
               e_local: int):
    """Shard-local grouped FFN over one token chunk's routes — the body of
    ``_moe_grouped_ep.local`` restricted to a row slice, shared with the
    overlapped combine so both schedules run the identical math. Masks the
    chunk's routes by expert ownership (this shard's contiguous e_local
    experts, located by ``axis_index("ep")``) and returns the LOCAL partial
    [t_chunk, D]; the combine psum stays with the caller so forward and
    backward issue matching (single or decomposed) collectives."""
    t, k = flat_.shape[0], cfg.top_k
    off = jax.lax.axis_index("ep") * e_local
    rel = sel_ - off
    mine = (rel >= 0) & (rel < e_local)
    grp = jnp.where(mine, rel, 0).reshape(t * k)
    wgt = jnp.where(mine, weight_, 0.0).reshape(t * k)
    tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    return _grouped_ffn({"w1": w1, "w3": w3, "w2": w2}, flat_, tok, grp,
                        wgt, e_local, cfg)


def _moe_grouped_ep(params: dict[str, Any], flat: jax.Array, cfg: MoEConfig,
                    probs: jax.Array, mesh):
    """Expert-parallel grouped dispatch: shard_map where each ``ep`` shard
    runs the grouped FFN for its E/ep local experts only and the
    token-indexed combine is a psum over ``ep``. The token dim stays sharded
    over the data axes (the ``sharded_fused_ce_tokens`` pattern — only ep is
    gathered), so per-shard work scales with the LOCAL batch. Expert-weight
    streaming — the measured round-4 MoE bottleneck — shards by ep; per-
    shard row work stays worst-case-bounded at T_local*k (routes to remote
    experts ride along with zero combine weight — the static-shape cost of
    dropless EP, since routing counts are data-dependent). Routing (fp32)
    and the aux loss stay outside the manual region."""
    from dataclasses import replace

    from jax.sharding import PartitionSpec as P

    from tony_tpu.ops.moe_overlap import overlap_chunks, overlapped_combine

    ep = int(mesh.shape["ep"])
    e_local = cfg.n_experts // ep
    sel, gates, _, aux = _top_k_select(probs, cfg)
    denom = jnp.maximum(jnp.sum(gates, axis=1), 1e-9)
    weight = gates / denom[:, None]                           # [T, k]

    axes = set(mesh.axis_names)
    batch = tuple(a for a in ("dp", "fsdp") if a in axes) or None
    n_batch = 1
    for a in batch or ():
        n_batch *= int(mesh.shape[a])

    n_chunks = None
    if cfg.overlap_impl and cfg.overlap_impl != "off":
        # remaining decline leg of the overlap triad (no-ep-axis and
        # already-manual-region decline the whole ep path upstream): a
        # chunk size that doesn't divide the per-shard token rows keeps
        # the single blocking psum below
        n_chunks = overlap_chunks(flat.shape[0] // n_batch, cfg.overlap_chunk)

    def local(w1, w3, w2, flat_, sel_, weight_):
        if n_chunks is not None:
            # the overlap impl names the chunk FFN's grouped-GEMM kernel
            ffn = partial(_chunk_ffn,
                          cfg=replace(cfg, gmm_impl=cfg.overlap_impl),
                          e_local=e_local)
            return overlapped_combine(ffn, "ep", n_chunks, w1, w3, w2,
                                      flat_, sel_, weight_)
        y = _chunk_ffn(w1, w3, w2, flat_, sel_, weight_, cfg=cfg,
                       e_local=e_local)
        return jax.lax.psum(y, "ep")
    wspec = P("ep", None, None)
    bspec = P(batch, None)
    y = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(wspec, wspec, wspec, bspec, bspec, bspec),
        out_specs=bspec,
    )(params["w1"], params["w3"], params["w2"], flat, sel, weight)
    return y, aux


def _moe_grouped_entry(params, flat, cfg, probs):
    from tony_tpu.parallel.mesh import get_default_mesh, inside_manual_region

    mesh = get_default_mesh()
    if (
        mesh is not None
        and int(mesh.shape.get("ep", 1)) > 1
        # the manual region is ep-only: a tp-sharded ffn dim would be
        # all-gathered into every shard inside it (4x weight HBM on tp=4 —
        # exactly the streaming this path exists to shrink), so ep x tp
        # meshes stay on the plain GSPMD path, which partitions the ffn
        # einsums itself
        and int(mesh.shape.get("tp", 1)) == 1
        and cfg.n_experts % int(mesh.shape["ep"]) == 0
        # the ep shard_map keeps tokens sharded over the data axes, which
        # needs an even split; odd batches take the plain GSPMD path
        and flat.shape[0]
        % (int(mesh.shape.get("dp", 1)) * int(mesh.shape.get("fsdp", 1)))
        == 0
        and not inside_manual_region()
    ):
        return _moe_grouped_ep(params, flat, cfg, probs, mesh)
    return _moe_grouped(params, flat, cfg, probs)


def moe_block(params: dict[str, Any], x: jax.Array, cfg: MoEConfig):
    """MoE SwiGLU FFN. x: [B, S, D] -> (y [B, S, D], aux_loss scalar).

    Capacity dispatches ('gather'/'einsum'): dropped (over-capacity) tokens
    pass through with a zero FFN delta, so the residual connection outside
    this block keeps their representation. 'grouped' is dropless — every
    routed token is served.
    """
    B, S, D = x.shape
    T = B * S
    flat = x.reshape(T, D)

    # router math is ALWAYS fp32: a bf16 softmax loses ~2 decimal digits and
    # the aux loss is a mean of small per-expert fractions
    logits = flat.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)

    if cfg.dispatch == "grouped":
        if cfg.overlap_impl not in ("", "off", "scan", "pallas"):
            raise ValueError(
                f"unknown MoE overlap impl {cfg.overlap_impl!r}; expected "
                "'off' | 'scan' | 'pallas'"
            )
        y, aux = _moe_grouped_entry(params, flat, cfg, probs)
        return y.reshape(B, S, D), aux
    capacity = cfg.capacity(T)
    if cfg.dispatch == "gather":
        y, aux = _moe_gather(params, flat, cfg, capacity, probs)
        return y.reshape(B, S, D), aux
    if cfg.dispatch != "einsum":
        raise ValueError(f"unknown MoE dispatch {cfg.dispatch!r}")

    dispatch, combine, aux = _top_k_dispatch(probs, cfg, capacity)
    # [T,E,C]x[T,D] -> [E,C,D]: the EP all-to-all happens inside this einsum
    # when "expert" is mesh-sharded.
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), flat)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, params["w1"]))
    h = h * jnp.einsum("ecd,edf->ecf", expert_in, params["w3"])
    expert_out = jnp.einsum("ecf,efd->ecd", h, params["w2"])
    y = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), expert_out)
    return y.reshape(B, S, D), aux


# --- sigmoid, group-limited routing over a LOCAL range of experts ---------------
#
# The serving path of the latent-attention expert decoder (models/latent_moe.py).
# The softmax top-k block above is the training path and is untouched by it.


@dataclass(frozen=True)
class GroupRouting:
    """Auxiliary-loss-free routing as published for sigmoid-scored experts:
    ``n_experts`` router outputs in ``n_groups`` equal groups; a token may
    only choose from its ``topk_groups`` best groups."""

    n_experts: int
    top_k: int
    n_groups: int = 1
    topk_groups: int = 1
    routed_scale: float = 1.0
    norm_topk_prob: bool = True


def route_group_limited(flat: jax.Array, router: jax.Array, bias: jax.Array,
                        r: GroupRouting) -> tuple[jax.Array, jax.Array]:
    """``flat [T, D]`` -> ``(experts [T, k] int32, gates [T, k] float32)``.

    ``s = sigmoid(flat @ router)`` in float32. SELECTION uses ``s + bias``
    (the load-balancing correction): a group's score is the sum of its two
    largest biased scores, the ``topk_groups`` best groups stay, and the
    ``k`` largest biased scores among them are the token's experts. GATING
    uses ``s`` itself: ``routed_scale * s_e / sum_{e in K} s_e`` (the sum
    over ALL k chosen experts, wherever they live)."""
    T = flat.shape[0]
    logits = jnp.matmul(flat.astype(jnp.float32), router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)                                # [T, E]
    sb = s + bias.astype(jnp.float32)
    if r.n_groups > 1:
        g = sb.reshape(T, r.n_groups, r.n_experts // r.n_groups)
        gscore = jnp.sum(jax.lax.top_k(g, 2)[0], axis=-1)     # [T, G]
        _, gidx = jax.lax.top_k(gscore, r.topk_groups)
        keep = jnp.any(gidx[..., None] == jnp.arange(r.n_groups), axis=1)
        sb = jnp.where(keep[..., None], g, -jnp.inf).reshape(T, r.n_experts)
    _, sel = jax.lax.top_k(sb, r.top_k)
    gates = jnp.take_along_axis(s, sel, axis=-1)
    if r.norm_topk_prob:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return sel.astype(jnp.int32), gates * r.routed_scale


def _local_small(params, flat, rel, mine, gates, n_local, first_row):
    """At most a row tile of tokens: every local expert runs ONE tile that
    holds all ``T`` tokens, weighted by the token's gate for it (0 where it
    was not chosen) — no sort, no scatter, no padding to ``group_block``
    rows an expert; each expert's weights are read once, where they lie
    (row ``first_row + e`` of the flat ``[rows, D, F]`` stacks), and an
    expert no token chose is skipped (``lax.cond``: its weights are not
    read)."""
    onehot = (rel[..., None] == jnp.arange(n_local)) & mine[..., None]  # [T,k,n]
    gate_e = jnp.sum(jnp.where(onehot, gates[..., None], 0.0), axis=1)  # [T,n]
    routes = jnp.sum(onehot, axis=(0, 1)).astype(jnp.int32)             # [n]

    def body(acc, eg):
        e, g, n = eg

        def run(acc):
            w1, w3, w2 = (jax.lax.dynamic_index_in_dim(params[k], first_row + e, keepdims=False)
                          for k in ("w1", "w3", "w2"))
            h = jax.nn.silu(flat @ w1) * (flat @ w3)
            return acc + (h @ w2).astype(jnp.float32) * g[:, None]

        return jax.lax.cond(n > 0, run, lambda acc: acc, acc), None

    acc, _ = jax.lax.scan(
        body, jnp.zeros(flat.shape, jnp.float32),
        (jnp.arange(n_local), gate_e.T, routes),
    )
    return acc.astype(flat.dtype), routes


def _local_grouped(params, flat, rel, mine, gates, n_local, first_row, block):
    """Dropless sorted grouped products over the LOCAL routes only: routes to
    experts that live elsewhere sort behind the last local group and are
    never gathered, and row tiles beyond the last used one are skipped."""
    from tony_tpu.ops.grouped_mm import grouped_layout, grouped_matmul

    T, D = flat.shape
    k = rel.shape[1]
    R = T * k
    grp = jnp.where(mine, rel, n_local).reshape(R)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    order = jnp.argsort(grp, stable=True)
    g_s, tok_s, w_s = grp[order], tok[order], gates.reshape(R)[order]
    routes = jnp.bincount(grp, length=n_local + 1)[:n_local].astype(jnp.int32)
    n_tiles = -(-R // block) + n_local   # static bound: every route local
    starts, tile_group = grouped_layout(routes, block, n_tiles)
    n_used = jnp.sum(jnp.maximum((routes + block - 1) // block, 1))
    local = g_s < n_local
    g_c = jnp.minimum(g_s, n_local - 1)
    compact_start = jnp.cumsum(routes) - routes
    dst = jnp.where(
        local, starts[g_c] + (jnp.arange(R, dtype=jnp.int32) - compact_start[g_c]),
        n_tiles * block,                 # out of range: dropped below
    )
    x_pad = jnp.zeros((n_tiles * block, D), flat.dtype).at[dst].set(
        flat[tok_s], mode="drop")
    gmm = partial(grouped_matmul, tile_group=tile_group + first_row,
                  n_valid_tiles=n_used)
    h = jax.nn.silu(gmm(x_pad, params["w1"])) * gmm(x_pad, params["w3"])
    y_pad = gmm(h, params["w2"])
    rows = jnp.take(y_pad, dst, axis=0, mode="fill", fill_value=0)
    contrib = jnp.where(local, w_s, 0.0).astype(flat.dtype)[:, None] * rows
    return jnp.zeros((T, D), flat.dtype).at[tok_s].add(contrib), routes


def local_expert_ffn(params: dict[str, Any], flat: jax.Array, sel: jax.Array,
                     gates: jax.Array, *, first_expert: int = 0,
                     layer: jax.Array | None = None,
                     group_block: int = 128) -> tuple[jax.Array, jax.Array]:
    """The part of an expert layer that THIS holder of experts computes:
    ``sum_{e in K(t), first <= e < first + n_local} gates[t, e] * SwiGLU_e``.

    ``params`` holds ``n_local`` experts (``w1/w3 [n_local, D, F]``, ``w2
    [n_local, F, D]``), global ids ``first_expert ..``; ``sel``/``gates``
    ``[T, k]`` are the router's choice over ALL experts (gates already
    normalised over all ``k``; a ``sel`` of -1 names no expert). Terms of
    experts that live elsewhere are left out — on an expert-parallel mesh
    the caller sums the holders' parts; on one chip nothing stands in for
    them. Dropless. Returns ``(y [T, D], routes [n_local] int32)``, the
    routes each local expert served.

    ``layer`` (a traced index): the weights are whole stacks ``[layers,
    n_local, ...]`` and this is layer ``layer`` of them. They are viewed as
    ``[layers * n_local, ...]`` and each expert's matrices are read where
    they lie — a layer scan that sliced its own ``[n_local, ...]`` out would
    copy every expert of the layer before the first product."""
    if layer is None:
        n_local, first_row = params["w1"].shape[0], 0
    else:
        n_local = params["w1"].shape[1]
        params = {k: v.reshape(-1, *v.shape[2:]) for k, v in params.items()}
        first_row = layer * n_local
    rel = sel - first_expert
    mine = (rel >= 0) & (rel < n_local)
    if flat.shape[0] <= group_block:   # one tile holds every token
        return _local_small(params, flat, rel, mine, gates, n_local, first_row)
    return _local_grouped(params, flat, rel, mine, gates, n_local, first_row,
                          group_block)


__all__ = [
    "GroupRouting", "MoEConfig", "init_moe_params", "local_expert_ffn",
    "logical_axes", "moe_block", "route_group_limited", "routing_stats",
]
