"""Family file of the latent-attention expert decoder for
``drivers/serve_engine_family.py``: everything the serving loop needs that
depends on the architecture — sizes, seeded weights, the program's model
object, the plain reference's logits, the planted faults, and what of the
engine's counters the readers of ``latent_moe.*`` use."""

from __future__ import annotations

import numpy as np

from benchmark import weights_latent_moe as weights
from benchmark.reference import latent_moe_decoder as ref

FAULTS = ref.FAULTS
sizes_of = weights.sizes_of
make_params = weights.make_params


def vocab(s: dict) -> int:
    return s["v"]


def model(s: dict, serve: dict, dtype):
    """The program's configuration object (the only import of the program here)."""
    from tony_tpu.models.latent_moe import LatentMoEConfig

    y = s["yarn"]
    return LatentMoEConfig(
        vocab_size=s["v"], dim=s["d"], n_layers=s["layers"], n_dense_layers=s["dense"],
        n_heads=s["h"], q_lora_rank=s["qr"], kv_lora_rank=s["kr"],
        qk_nope_head_dim=s["nope"], qk_rope_head_dim=s["rope"], v_head_dim=s["vd"],
        ffn_dim=s["f"], moe_ffn_dim=s["fm"], n_experts=s["e"], n_shared_experts=s["shared"],
        top_k=s["k"], n_groups=s["groups"], topk_groups=s["topk_groups"],
        routed_scale=s["scale"], norm_topk_prob=s["norm_topk"], first_expert=s["first"],
        n_local_experts=s["n_local"], max_seq_len=serve["max_len"], rope_theta=s["theta"],
        rope_factor=y["factor"], rope_orig_max=y["orig"], rope_beta_fast=y["beta_fast"],
        rope_beta_slow=y["beta_slow"], rope_mscale=y["mscale"],
        rope_mscale_all_dim=y["mscale_all_dim"], norm_eps=s["eps"], dtype=dtype,
    )


def counters(metrics) -> dict | None:
    """The engine's expert counters as plain numbers (``DecodeMetrics.moe_*``);
    None where the program has none (an older program, or nothing routed yet)."""
    routes = getattr(metrics, "moe_routes", None)
    if routes is None:
        return None
    return {"routes": np.asarray(routes).tolist(), "tokens": int(metrics.moe_tokens),
            "experts_hit": np.asarray(metrics.moe_experts_hit).tolist(),
            "steps": int(metrics.moe_steps)}


def reference_logits(key, s: dict, dtype, seqs: list[np.ndarray], starts: list[int],
                     rows: int, cast_dtype=None, fault: str = "") -> list[np.ndarray]:
    """Teacher-forced full forward of the plain reference over each padded
    sequence, layer by layer (each layer's weights made from the seed just
    before use); ``rows`` logit rows of each from ``starts[i]``."""
    import jax
    import jax.numpy as jnp

    cast = ref.rounded_to(jnp.dtype(cast_dtype)) if cast_dtype else ref.identity
    # the key and the layer index are arguments: closed over, they would be
    # constants and every seed would compile again
    make = {moe: jax.jit(lambda key, l, moe=moe: weights.make_layer(key, s, dtype, l, moe))
            for moe in (False, True)}
    layer = jax.jit(lambda lp, x: ref.layer(x, lp, s, cast, fault))
    head = jax.jit(lambda fn, lm, x, start: ref.logits(
        jax.lax.dynamic_slice_in_dim(x, start, rows), fn, lm, s, cast))
    tok_emb = weights.make_leaf(key, "tok_emb", s, dtype)
    xs = [ref.embed(tok_emb, jnp.asarray(ids)) for ids in seqs]
    del tok_emb
    for l in range(s["layers"]):
        lp = make[l >= s["dense"]](key, jnp.int32(l))
        xs = [layer(lp, x) for x in xs]
        del lp
    final_norm = weights.make_leaf(key, "final_norm", s, dtype)
    lm_head = weights.make_leaf(key, "lm_head", s, dtype)
    return [np.asarray(head(final_norm, lm_head, x, jnp.int32(st))) for x, st in zip(xs, starts)]


def routing_flips(key, s: dict, dtype, seqs: list[np.ndarray], lens: list[int],
                  cast_dtype: str) -> dict:
    """How often the reference with every matrix product's operands rounded
    to ``cast_dtype`` routes a token otherwise than the float32 reference,
    both teacher-forced over the same sequences: of the (token, expert layer)
    pairs inside ``lens``, those whose chosen experts differ, and those whose
    choice among THIS holder's experts differs (the only ones that move its
    output). Each stream follows its own activations, as a program would."""
    import jax
    import jax.numpy as jnp

    casts = (ref.identity, ref.rounded_to(jnp.dtype(cast_dtype)))
    make = {moe: jax.jit(lambda key, l, moe=moe: weights.make_layer(key, s, dtype, l, moe))
            for moe in (False, True)}
    layer = [jax.jit(lambda lp, x, c=c: ref.layer(x, lp, s, c, with_routes=True)) for c in casts]
    tok_emb = weights.make_leaf(key, "tok_emb", s, dtype)
    xs = [[ref.embed(tok_emb, jnp.asarray(ids)) for ids in seqs] for _ in casts]
    del tok_emb
    lo, hi = s["first"], s["first"] + s["n_local"]
    pairs = flipped = flipped_local = 0
    for l in range(s["layers"]):
        lp = make[l >= s["dense"]](key, jnp.int32(l))
        for i, n in enumerate(lens):
            (xs[0][i], a), (xs[1][i], b) = layer[0](lp, xs[0][i]), layer[1](lp, xs[1][i])
            if a is None:
                continue
            differ = np.asarray(a != b)[:n]
            pairs += n
            flipped += int(differ.any(axis=-1).sum())
            flipped_local += int(differ[:, lo:hi].any(axis=-1).sum())
        del lp
    return {"token_layers": pairs, "flipped": flipped, "flipped_local": flipped_local}
