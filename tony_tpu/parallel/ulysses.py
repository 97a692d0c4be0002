"""Ulysses-style sequence parallelism: all-to-all head sharding.

The alternative context-parallel scheme (DeepSpeed-Ulysses, arXiv:2309.14509)
kept for comparison with ring attention: instead of rotating K/V, one
all-to-all re-shards activations from sequence-sharded to head-sharded, full
(exact) attention runs locally over the whole sequence, and a second
all-to-all restores sequence sharding. Cheaper in collective volume than a
full all-gather (each device ends with S x H/n), but requires
n_heads % axis_size == 0 and peak activation memory O(S) per device —
ring attention wins for the longest sequences, Ulysses for head-rich models
on small rings. Exposed through the same AttnFn contract.
"""

from __future__ import annotations

from functools import partial

import jax
from jax import lax
from jax.sharding import Mesh

from tony_tpu.models.llama import dot_attention as _causal_attention


def ulysses_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "sp",
    attn=_causal_attention,
) -> jax.Array:
    """Per-device Ulysses attention; call inside shard_map.

    q/k/v: [B, S_local, H, D] sequence-sharded chunks. Internally re-shards
    to [B, S, H_local, D] (full sequence, heads split), runs exact attention,
    and re-shards back. ``attn(q, k, v)`` is the local attention function.
    """
    n = lax.axis_size(axis_name)
    H = q.shape[2]
    if H % n:
        raise ValueError(f"n_heads={H} not divisible by {axis_name} size {n}")

    # seq-sharded -> head-sharded: split heads (axis 2), gather seq (axis 1)
    def to_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    # head-sharded -> seq-sharded: split seq, gather heads
    def to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    return to_seq(attn(to_heads(q), to_heads(k), to_heads(v)))


def make_ulysses_attention(mesh: Mesh, *, axis_name: str = "sp"):
    """AttnFn closure over full arrays (mirror of make_ring_attention)."""
    from tony_tpu.parallel.mesh import inside_manual_region
    from tony_tpu.parallel.sharding import attn_spec

    spec = attn_spec(mesh, seq_axis=axis_name)
    inner = partial(ulysses_attention_local, axis_name=axis_name)

    def attn(q, k, v, cfg=None):
        if inside_manual_region():
            raise NotImplementedError(
                "ulysses attention cannot run inside another shard_map "
                "region (e.g. a pp pipeline stage); use attention_impl="
                "'flash' or 'dot' with pp, or drop pp"
            )
        return jax.shard_map(
            lambda a, b, c: inner(a, b, c),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
        )(q, k, v)

    return attn


def ulysses_attention(q, k, v, cfg=None):
    """Model hook (AttnFn signature): uses the registered default mesh."""
    from tony_tpu.parallel.mesh import get_default_mesh

    mesh = get_default_mesh()
    if mesh is None:
        raise RuntimeError(
            "ulysses attention needs a mesh: call "
            "tony_tpu.parallel.set_default_mesh(mesh) (fit() does this "
            "automatically for its training mesh)"
        )
    return make_ulysses_attention(mesh)(q, k, v, cfg)


__all__ = ["make_ulysses_attention", "ulysses_attention", "ulysses_attention_local"]
