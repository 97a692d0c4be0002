"""Family file of the short-convolution / attention hybrid with routed experts
for ``drivers/serve_engine_family.py``: everything the serving loop needs that
depends on the architecture — sizes, seeded weights, the program's model
object, the plain reference's logits, the planted faults, and what of the
engine's counters the readers of ``shortconv_moe.*`` use."""

from __future__ import annotations

import numpy as np

from benchmark import weights_shortconv_moe as weights
from benchmark.reference import shortconv_moe_decoder as ref

FAULTS = ref.FAULTS
sizes_of = weights.sizes_of
make_params = weights.make_params


def vocab(s: dict) -> int:
    return s["v"]


def model(s: dict, serve: dict, dtype):
    """The program's configuration object (the only import of the program here)."""
    from tony_tpu.models.shortconv_moe import ShortConvMoEConfig

    return ShortConvMoEConfig(
        vocab_size=s["v"], dim=s["d"], layer_types=s["layer_types"],
        n_dense_layers=s["dense"], n_heads=s["h"], n_kv_heads=s["kv"], head_dim=s["hd"],
        conv_kernel=s["K"], ffn_dim=s["f"], moe_ffn_dim=s["fm"], n_experts=s["e"],
        top_k=s["k"], routed_scale=s["scale"], norm_topk_prob=s["norm_topk"],
        first_expert=s["first"], n_local_experts=s["n_local"],
        max_seq_len=serve["max_len"], rope_theta=s["theta"], norm_eps=s["eps"], dtype=dtype,
    )


def counters(metrics) -> dict | None:
    """The engine's expert counters and the counters of the per-slot state as
    plain numbers; None where the program has none (nothing routed yet)."""
    routes = getattr(metrics, "moe_routes", None)
    if routes is None:
        return None
    return {"routes": np.asarray(routes).tolist(), "tokens": int(metrics.moe_tokens),
            "experts_hit": np.asarray(metrics.moe_experts_hit).tolist(),
            "steps": int(metrics.moe_steps),
            "slot_state_bytes": int(metrics.slot_state_bytes),
            "state_handoffs": int(metrics.state_handoffs)}


def _layer_makers(s: dict, dtype):
    """One jitted maker a combination of kinds; the key and the layer index
    are arguments (closed over, every seed and layer would compile again)."""
    import jax

    kinds = {weights.kinds_of(s, l) for l in range(len(s["layer_types"]))}
    return {k: jax.jit(lambda key, l, k=k: weights.make_layer(key, s, dtype, l, k))
            for k in kinds}


def reference_logits(key, s: dict, dtype, seqs: list[np.ndarray], starts: list[int],
                     rows: int, cast_dtype=None, fault: str = "") -> list[np.ndarray]:
    """Teacher-forced full forward of the plain reference over each padded
    sequence, layer by layer (each layer's weights made from the seed just
    before use); ``rows`` logit rows of each from ``starts[i]``."""
    import jax
    import jax.numpy as jnp

    cast = ref.rounded_to(jnp.dtype(cast_dtype)) if cast_dtype else ref.identity
    make = _layer_makers(s, dtype)
    layer = jax.jit(lambda lp, x: ref.layer(x, lp, s, cast, fault))
    head = jax.jit(lambda fn, emb, x, start: ref.logits(
        jax.lax.dynamic_slice_in_dim(x, start, rows), fn, emb, s, cast))
    tok_emb = weights.make_leaf(key, "tok_emb", s, dtype)
    xs = [ref.embed(tok_emb, jnp.asarray(ids)) for ids in seqs]
    for l in range(len(s["layer_types"])):
        lp = make[weights.kinds_of(s, l)](key, jnp.int32(l))
        xs = [layer(lp, x) for x in xs]
        del lp
    final_norm = weights.make_leaf(key, "final_norm", s, dtype)
    return [np.asarray(head(final_norm, tok_emb, x, jnp.int32(st))) for x, st in zip(xs, starts)]


def routing_flips(key, s: dict, dtype, seqs: list[np.ndarray], lens: list[int],
                  cast_dtype: str) -> dict:
    """How often the reference with every matrix product's operands rounded
    to ``cast_dtype`` routes a token otherwise than the float32 reference,
    both teacher-forced over the same sequences: of the (token, expert layer)
    pairs inside ``lens``, those whose chosen experts differ (every expert is
    local here, so each of them moves the output)."""
    import jax
    import jax.numpy as jnp

    casts = (ref.identity, ref.rounded_to(jnp.dtype(cast_dtype)))
    make = _layer_makers(s, dtype)
    layer = [jax.jit(lambda lp, x, c=c: ref.layer(x, lp, s, c, with_routes=True)) for c in casts]
    tok_emb = weights.make_leaf(key, "tok_emb", s, dtype)
    xs = [[ref.embed(tok_emb, jnp.asarray(ids)) for ids in seqs] for _ in casts]
    del tok_emb
    pairs = flipped = 0
    for l in range(len(s["layer_types"])):
        lp = make[weights.kinds_of(s, l)](key, jnp.int32(l))
        for i, n in enumerate(lens):
            (xs[0][i], a), (xs[1][i], b) = layer[0](lp, xs[0][i]), layer[1](lp, xs[1][i])
            if a is None:
                continue
            pairs += n
            flipped += int(np.asarray(a != b)[:n].any(axis=-1).sum())
        del lp
    return {"token_layers": pairs, "flipped": flipped, "flipped_local": flipped}
