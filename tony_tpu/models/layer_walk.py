"""The walk over a decoder whose OPERATOR is chosen per layer from a declared
list: what models/shortconv_moe.py (short convolutions beside attention) and
models/ssm_hybrid.py (selective state-space layers beside attention) share.

Weights are stacked BY KIND — one stack an operator kind (:data:`OP_STACKS`
names them), ``dense_ffns`` for the dense SwiGLUs and, where a family has
them, ``moe_ffns`` for the expert layers — and :func:`walk_layers` follows
the list: runs of equal layers are scanned with the stacks indexed where they
lie, single layers are called with a static index. Nothing here assumes a
period. A family's configuration provides ``runs`` (:func:`runs_of` of its
list); its step decides what a layer does and where its state lives.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.models.latent_moe import split_experts

Params = dict[str, Any]

CONV, ATTENTION, MAMBA = "conv", "full_attention", "mamba"
OP_STACKS = {CONV: "conv_layers", ATTENTION: "attn_layers", MAMBA: "mamba_layers"}


class Run(NamedTuple):
    """``n`` consecutive layers of one operator kind and one feed-forward
    kind; ``op0`` / ``ff0`` index the first of them in its kind's stack."""

    op: str
    moe: bool
    op0: int
    ff0: int
    n: int


def runs_of(layer_types: tuple[str, ...], n_dense_layers: int) -> tuple[Run, ...]:
    """The declared list as runs of equal layers, in order; layers from
    ``n_dense_layers`` on have an expert feed-forward."""
    out: list[Run] = []
    seen: dict = {False: 0, True: 0}
    for i, op in enumerate(layer_types):
        moe = i >= n_dense_layers
        if out and (out[-1].op, out[-1].moe) == (op, moe):
            out[-1] = out[-1]._replace(n=out[-1].n + 1)
        else:
            out.append(Run(op, moe, seen.get(op, 0), seen[moe], 1))
        seen[op] = seen.get(op, 0) + 1
        seen[moe] += 1
    return tuple(out)


def layer_of(tree: Params, i):
    """Layer ``i`` of a stack, read where it lies: a static slice for a
    Python index, a dynamic one inside a scanned run."""
    if isinstance(i, int):
        return jax.tree.map(lambda a: a[i], tree)
    return jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


def put(stack: jax.Array, row: jax.Array, i) -> jax.Array:
    """``stack`` with ``row`` written at leading index ``i`` (in place on a
    carried buffer)."""
    return lax.dynamic_update_index_in_dim(stack, row.astype(stack.dtype), i, 0)


def walk_layers(step, carry, params: Params, cfg):
    """Run ``step(carry, op, ff, oi, fi, experts) -> carry`` over the layers
    in the declared order (``cfg.runs``). ``op`` / ``ff`` are the layer's
    operator and feed-forward weights, ``oi`` / ``fi`` its index among the
    layers of its operator kind and of its feed-forward kind (what per-kind
    state — a recurrent state, a pool's layer, a routes row — is indexed
    by), and ``experts = (the expert stacks whole, fi)`` for an expert layer,
    else None. A run of equal layers is ONE scanned body with traced indices;
    a single layer is called with Python ones."""
    rest, stacked = split_experts(params["moe_ffns"]) if "moe_ffns" in params else (None, None)
    for run in cfg.runs:
        ops = params[OP_STACKS[run.op]]
        ffs = rest if run.moe else params["dense_ffns"]

        def one(carry, i, run=run, ops=ops, ffs=ffs):
            oi, fi = run.op0 + i, run.ff0 + i
            return step(carry, layer_of(ops, oi), layer_of(ffs, fi), oi, fi,
                        (stacked, fi) if run.moe else None)

        if run.n == 1:
            carry = one(carry, 0)
        else:
            carry, _ = lax.scan(lambda c, i, one=one: (one(c, i), None), carry,
                                jnp.arange(run.n, dtype=jnp.int32))
    return carry


__all__ = ["ATTENTION", "CONV", "MAMBA", "OP_STACKS", "Run", "layer_of", "put",
           "runs_of", "walk_layers"]
