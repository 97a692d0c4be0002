"""Family file of the selective state-space / attention hybrid for
``drivers/serve_engine_family.py``: everything the serving loop needs that
depends on the architecture — sizes, seeded weights, the program's model
object, the plain reference's logits, the planted faults, and what of the
engine's counters the readers of ``ssm_hybrid.*`` use."""

from __future__ import annotations

import numpy as np

from benchmark import weights_ssm_hybrid as weights
from benchmark.reference import ssm_hybrid_decoder as ref

FAULTS = ref.FAULTS
sizes_of = weights.sizes_of
make_params = weights.make_params


def vocab(s: dict) -> int:
    return s["v"]


def model(s: dict, serve: dict, dtype):
    """The program's configuration object (the only import of the program here)."""
    from tony_tpu.models.ssm_hybrid import SSMHybridConfig

    return SSMHybridConfig(
        vocab_size=s["v"], dim=s["d"], n_layers=s["layers"], attn_layer_period=s["period"],
        attn_layer_offset=s["offset"], n_heads=s["h"], n_kv_heads=s["kv"], head_dim=s["hd"],
        ffn_dim=s["f"], mamba_expand=s["e"] // s["d"], d_state=s["n"], d_conv=s["K"],
        dt_rank=s["r"], max_seq_len=serve["max_len"], norm_eps=s["eps"], dtype=dtype,
    )


def counters(metrics) -> dict | None:
    """The engine's counters of the per-slot state and of the decode steps'
    live slots as plain numbers; None where the program keeps no such state."""
    if not hasattr(metrics, "slot_state_bytes"):
        return None
    return {"slot_state_bytes": int(metrics.slot_state_bytes),
            "state_handoffs": int(metrics.state_handoffs),
            "decode_steps": int(metrics.decode_steps),
            "decode_live_sum": int(metrics.decode_live_sum),
            "prompt_tokens": int(metrics.prompt_tokens)}


def reference_logits(key, s: dict, dtype, seqs: list[np.ndarray], starts: list[int],
                     rows: int, cast_dtype=None, fault: str = "") -> list[np.ndarray]:
    """Teacher-forced full forward of the plain reference over each padded
    sequence, layer by layer (each layer's weights made from the seed just
    before use); ``rows`` logit rows of each from ``starts[i]``."""
    import jax
    import jax.numpy as jnp

    cast = ref.rounded_to(jnp.dtype(cast_dtype)) if cast_dtype else ref.identity
    # the key and the layer index are arguments (closed over, every seed and
    # layer would compile again)
    make = {k: jax.jit(lambda key, l, k=k: weights.make_layer(key, s, dtype, l, k))
            for k in ("mamba_layers", "attn_layers")}
    layer = jax.jit(lambda lp, x: ref.layer(x, lp, s, cast, fault))
    head = jax.jit(lambda fn, emb, x, start: ref.logits(
        jax.lax.dynamic_slice_in_dim(x, start, rows), fn, emb, s, cast))
    tok_emb = weights.make_leaf(key, "tok_emb", s, dtype)
    xs = [ref.embed(tok_emb, jnp.asarray(ids)) for ids in seqs]
    for l in range(s["layers"]):
        lp = make[weights.kind_of(s, l)](key, jnp.int32(l))
        xs = [layer(lp, x) for x in xs]
        del lp
    final_norm = weights.make_leaf(key, "final_norm", s, dtype)
    return [np.asarray(head(final_norm, tok_emb, x, jnp.int32(st))) for x, st in zip(xs, starts)]
