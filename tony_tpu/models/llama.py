"""Llama-2-family decoder transformer, TPU-first.

The reference framework contains no models (TonY delegates training code to
user scripts; SURVEY.md section 0). This module is the training-side library
the rebuild adds, designed for the MXU/XLA rather than translated from torch:

- parameters are a plain pytree of stacked per-layer arrays; the layer stack
  runs under ``lax.scan`` (one trace, one compile, pipeline-ready layout);
- compute dtype bfloat16 end-to-end, softmax/norm statistics and the final
  loss in float32;
- optional ``jax.checkpoint`` rematerialisation per layer (HBM for FLOPs);
- every parameter carries logical axis names (see
  tony_tpu.parallel.sharding.DEFAULT_RULES) so the same code runs single-chip,
  FSDP, Megatron-TP, or sequence-parallel purely by mesh choice;
- attention is pluggable: plain fused attention here, Pallas flash attention
  and ring attention (context parallelism) from tony_tpu.ops/.parallel.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

Params = dict[str, Any]
AttnFn = Callable[..., jax.Array]  # (q, k, v, cfg) -> out


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    ffn_dim: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # what the per-layer jax.checkpoint keeps: 'nothing' recomputes the whole
    # layer in bwd (min HBM); 'dots' saves matmul outputs with no batch dims
    # (nothing in practice here — all our dots carry batch); 'checkpoint_dots'
    # saves every matmul output (min recompute, max HBM)
    remat_policy: str = "nothing"
    # 'dot' = fused plain attention; 'flash' = pallas kernel (tony_tpu.ops);
    # 'ring' = sequence-parallel ring attention (tony_tpu.parallel);
    # 'ring_flash' = ring over sp with the pallas kernel per chunk (the
    # long-context production path); 'ulysses' = all-to-all head sharding.
    attention_impl: str = "dot"
    # pallas flash kernel tile sizes (attention_impl='flash'); clipped to S.
    # 1024/1024 measured fastest on v5e at S=2048 (43.7 -> 53.2 TF/s fwd vs
    # the old 512/1024)
    flash_block_q: int = 1024
    flash_block_k: int = 1024
    # lax.scan unroll factor for the layer stack (trades compile time /
    # code size for cross-layer scheduling freedom)
    scan_unroll: int = 1
    # MoE variant (n_experts > 0): every layer's FFN becomes a GShard-style
    # top-k expert block (tony_tpu.parallel.moe) with the expert dim on the
    # mesh's ``ep`` axis; aux load-balancing loss is added to the objective.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # 'grouped' (dropless sorted grouped GEMM — no capacity, no drops; the
    # default since round 20's judged `grouped_vs_gather` bench gate held)
    # | 'gather' / 'einsum' (fixed-capacity slots, overflow tokens dropped
    # — one knob away; see parallel.moe and docs/PERF.md "Grouped MoE")
    moe_dispatch: str = "grouped"
    # moe_dispatch='grouped': row-tile of the grouped GEMM (each expert's
    # ragged token group pads up to a multiple of this)
    moe_group_block: int = 128
    # moe_dispatch='grouped': 'scan' (pure-XLA, runs anywhere — default) |
    # 'pallas' (TPU kernel, tony_tpu.ops.grouped_mm)
    moe_gmm_impl: str = "scan"
    # moe_dispatch='grouped' on an ep mesh: 'off' = single blocking post-
    # FFN combine psum (default); 'scan' | 'pallas' = decomposed per-token-
    # chunk partial combines so expert compute overlaps combine traffic
    # (tony_tpu.ops.moe_overlap, docs/PERF.md "Round 20"). Declines to the
    # single psum wherever the chunk split doesn't apply.
    moe_overlap_impl: str = "off"
    # moe_overlap_impl != 'off': tokens per combine chunk per shard (0 =
    # auto split; size measured captures via moe_overlap.chunk_tokens_from_report)
    moe_overlap_chunk: int = 0
    moe_aux_coef: float = 0.01
    # loss head (tony_tpu.ops.fused_ce): 'scan' = fused chunked CE via
    # lax.scan (default — never materialises [B,S,V] logits, runs anywhere);
    # 'pallas' = fused TPU kernel (VMEM accumulators over the vocab grid);
    # 'dense' = legacy full-logits logsumexp reference.
    ce_impl: str = "scan"
    # vocab columns per chunk for ce_impl='scan' (the forward/backward
    # transient is one [B*S, ce_vocab_chunk] fp32 block)
    ce_vocab_chunk: int = 4096
    # pallas CE kernel tile sizes (rows x vocab); clipped to B*S and V
    ce_block_n: int = 512
    ce_block_v: int = 512
    # comm/compute overlap for the fsdp-sharded trunk matmuls
    # (tony_tpu.ops.overlap): '' = GSPMD's blocking weight all-gathers
    # (default); 'scan' = decomposed ppermute-ring all-gather-matmul,
    # pure-XLA per-chunk inner; 'pallas' = same ring with the TPU tiled
    # matmul kernel per chunk. Falls back to the plain matmul wherever the
    # decomposition doesn't apply (no fsdp ring, manual region, odd shapes).
    overlap_impl: str = ""

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def cache_layout(self) -> tuple[int, int, int]:
        """``(heads, width, pools)`` of what serving caches per token and
        layer (serve/cache.py): ``n_kv_heads`` rows of ``head_dim`` in each
        of two pools, K and V."""
        return self.n_kv_heads, self.head_dim, 2

    @property
    def cache_layers(self) -> int:
        """Layers the block pool holds rows for: every layer attends."""
        return self.n_layers

    @property
    def slot_state(self) -> None:
        """No fixed-size per-slot state beside the blocks (docs/SERVE.md
        "What a new family must provide")."""
        return None

    @property
    def n_params(self) -> int:
        """Exact parameter count (embeddings included, tied=False)."""
        d, h = self.dim, self.head_dim
        attn = d * self.n_heads * h + 2 * d * self.n_kv_heads * h + self.n_heads * h * d
        if self.is_moe:
            ffn = d * self.n_experts + 3 * self.n_experts * d * self.ffn_dim
        else:
            ffn = 3 * d * self.ffn_dim
        norms = 2 * d
        per_layer = attn + ffn + norms
        return self.vocab_size * d * 2 + self.n_layers * per_layer + d

    @property
    def n_active_params(self) -> int:
        """Parameters touched per token (MoE: only top_k experts fire) —
        the right N for 6*N FLOPs accounting."""
        if not self.is_moe:
            return self.n_params
        inactive = 3 * (self.n_experts - self.moe_top_k) * self.dim * self.ffn_dim
        return self.n_params - self.n_layers * inactive

    @property
    def n_matmul_params(self) -> int:
        """Active parameters a token is MULTIPLIED by: the layers' matrices
        (MoE: router + the top_k experts that fire) and the output head.
        The embedding table is a gather and the norm gains are elementwise
        — neither is a matmul, so neither belongs in 6*N."""
        not_matmul = self.vocab_size * self.dim + (2 * self.n_layers + 1) * self.dim
        return self.n_active_params - not_matmul

    # --- presets -----------------------------------------------------------

    @classmethod
    def llama2_7b(cls, **kw: Any) -> "LlamaConfig":
        return cls(
            vocab_size=32000, dim=4096, n_layers=32, n_heads=32, n_kv_heads=32,
            ffn_dim=11008, max_seq_len=4096, **kw,
        )

    @classmethod
    def llama2_13b(cls, **kw: Any) -> "LlamaConfig":
        return cls(
            vocab_size=32000, dim=5120, n_layers=40, n_heads=40, n_kv_heads=40,
            ffn_dim=13824, max_seq_len=4096, **kw,
        )

    @classmethod
    def llama3_8b(cls, **kw: Any) -> "LlamaConfig":
        return cls(
            vocab_size=128256, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
            ffn_dim=14336, max_seq_len=8192, rope_theta=500000.0, **kw,
        )

    @classmethod
    def bench_410m(cls, **kw: Any) -> "LlamaConfig":
        """~410M-param config that trains comfortably on one v5e chip."""
        return cls(
            vocab_size=32000, dim=1024, n_layers=24, n_heads=16, n_kv_heads=16,
            ffn_dim=2816, max_seq_len=2048, **kw,
        )

    @classmethod
    def bench_1b4(cls, **kw: Any) -> "LlamaConfig":
        """~1.35B-param config: the single-chip (v5e 16GB) benchmark model.

        Large enough that the matmuls fill the MXU (52% MFU vs 37% for the
        410M config at the same batch), small enough that params + AdamW
        state + remat activations fit one chip's HBM."""
        return cls(
            vocab_size=32000, dim=2048, n_layers=24, n_heads=16, n_kv_heads=16,
            ffn_dim=5504, max_seq_len=2048, **kw,
        )

    @classmethod
    def tiny(cls, **kw: Any) -> "LlamaConfig":
        """Test-size config (CPU-fast)."""
        kw.setdefault("dtype", jnp.float32)
        kw.setdefault("remat", False)
        return cls(
            vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            ffn_dim=128, max_seq_len=64, **kw,
        )

    @classmethod
    def tiny_moe(cls, **kw: Any) -> "LlamaConfig":
        """Test-size MoE config (CPU-fast, 4 experts top-2)."""
        kw.setdefault("n_experts", 4)
        return cls.tiny(**kw)

    @classmethod
    def bench_moe(cls, **kw: Any) -> "LlamaConfig":
        """Single-chip MoE benchmark: 8 experts top-2 on the 410M trunk
        (~2.1B total params, ~700M active)."""
        kw.setdefault("n_experts", 8)
        return cls.bench_410m(**kw)


# --- parameter tree -----------------------------------------------------------


def logical_axes(cfg: LlamaConfig) -> Params:
    """Pytree (matching init_params) of logical axis-name tuples.

    Sharding follows the Megatron+FSDP recipe: wide dims (heads/ffn/vocab) on
    ``tp``, model dim on ``fsdp``; the leading stacked-layer dim is never
    sharded. tony_tpu.parallel.sharding turns these into NamedShardings.
    """
    if cfg.is_moe:
        ffn_axes = {
            "router": ("layers", "embed", "expert"),
            "w1": ("layers", "expert", "embed", "ffn"),
            "w3": ("layers", "expert", "embed", "ffn"),
            "w2": ("layers", "expert", "ffn", "embed"),
        }
    else:
        ffn_axes = {
            "w1": ("layers", "embed", "ffn"),
            "w3": ("layers", "embed", "ffn"),
            "w2": ("layers", "ffn", "embed"),
        }
    return {
        "tok_emb": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layers", "norm"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "ffn_norm": ("layers", "norm"),
            **ffn_axes,
        },
        "final_norm": ("norm",),
        "lm_head": ("embed", "vocab"),
    }


def init_params(rng: jax.Array, cfg: LlamaConfig) -> Params:
    """Initialise the parameter pytree (per-layer arrays stacked on axis 0)."""
    d, hd = cfg.dim, cfg.head_dim
    nq, nkv, L = cfg.n_heads * hd, cfg.n_kv_heads * hd, cfg.n_layers
    keys = jax.random.split(rng, 10)

    def dense(key: jax.Array, shape: tuple[int, ...], fan_in: int) -> jax.Array:
        scale = 1.0 / math.sqrt(fan_in)
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(cfg.dtype)

    F, E = cfg.ffn_dim, cfg.n_experts
    if cfg.is_moe:
        ffn = {
            # routing statistics stay float32 (see parallel.moe)
            "router": dense(keys[5], (L, d, E), d).astype(jnp.float32),
            "w1": dense(keys[6], (L, E, d, F), d),
            "w3": dense(keys[7], (L, E, d, F), d),
            "w2": dense(keys[9], (L, E, F, d), F),
        }
    else:
        ffn = {
            "w1": dense(keys[5], (L, d, F), d),
            "w3": dense(keys[6], (L, d, F), d),
            "w2": dense(keys[7], (L, F, d), F),
        }
    return {
        "tok_emb": dense(keys[0], (cfg.vocab_size, d), d),
        "layers": {
            "attn_norm": jnp.ones((L, d), cfg.dtype),
            "wq": dense(keys[1], (L, d, nq), d),
            "wk": dense(keys[2], (L, d, nkv), d),
            "wv": dense(keys[3], (L, d, nkv), d),
            "wo": dense(keys[4], (L, nq, d), nq),
            "ffn_norm": jnp.ones((L, d), cfg.dtype),
            **ffn,
        },
        "final_norm": jnp.ones((d,), cfg.dtype),
        "lm_head": dense(keys[8], (d, cfg.vocab_size), d),
    }


# --- building blocks ----------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms).astype(x.dtype) * weight


def rope_freqs(cfg: LlamaConfig) -> jax.Array:
    """Rotary frequency vector [head_dim/2] fp32 — the ONE copy of the
    formula shared by the train table, the prefill path, and the decode
    engine (a scaling scheme added here reaches all three)."""
    half = cfg.head_dim // 2
    return cfg.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)


def rope_table(cfg: LlamaConfig, seq_len: int, offset: int = 0) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables [seq, head_dim/2], float32."""
    freqs = rope_freqs(cfg)
    pos = jnp.arange(offset, offset + seq_len, dtype=jnp.float32)
    angles = pos[:, None] * freqs[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [B, S, H, hd] -> rotated, same dtype. Pairs (even, odd) halves."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def dot_attention(q: jax.Array, k: jax.Array, v: jax.Array, cfg: LlamaConfig | None = None) -> jax.Array:
    """Plain causal attention, fp32 softmax. q:[B,S,H,hd] k/v:[B,S,H,hd]."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    S = q.shape[1]
    causal = jnp.tril(jnp.ones((S, S), dtype=bool))
    scores = jnp.where(causal[None, None], scores * scale, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _get_attention(cfg: LlamaConfig) -> AttnFn:
    if cfg.attention_impl == "dot":
        return dot_attention
    try:
        if cfg.attention_impl == "flash":
            from tony_tpu.ops.attention import sharded_flash_attention

            return sharded_flash_attention
        if cfg.attention_impl == "ring":
            from tony_tpu.parallel.ring_attention import ring_attention

            return ring_attention
        if cfg.attention_impl == "ring_flash":
            from tony_tpu.parallel.ring_attention import ring_flash_attention

            return ring_flash_attention
        if cfg.attention_impl == "ulysses":
            from tony_tpu.parallel.ulysses import ulysses_attention

            return ulysses_attention
    except ImportError as e:
        raise NotImplementedError(
            f"attention_impl={cfg.attention_impl!r} backend not available: {e}"
        ) from e
    raise ValueError(f"unknown attention_impl {cfg.attention_impl!r}")


def _proj(x: jax.Array, w: jax.Array, cfg: LlamaConfig,
          axes: tuple[str | None, ...]) -> jax.Array:
    """One trunk projection ``x [B,S,D] @ w``. With ``cfg.overlap_impl``
    set, the fsdp weight all-gather streams per-chunk through the
    decomposed ring matmul (tony_tpu.ops.overlap) instead of blocking up
    front; ``axes`` are the weight's per-layer logical axes — which dim
    rides the ring is read off the sharding rules (parallel.sharding), not
    hardcoded here. Silently the plain matmul wherever the decomposition
    doesn't apply: overlap is an optimisation, never a semantic.
    """
    if cfg.overlap_impl:
        from tony_tpu.ops.overlap import overlap_matmul
        from tony_tpu.parallel.sharding import overlap_gather_dim

        gd = overlap_gather_dim(axes)
        if gd is not None:
            y = overlap_matmul(x, w, gather_dim=gd, impl=cfg.overlap_impl)
            if y is not None:
                return y
    return x @ w


def attention_block(x: jax.Array, lp: Params, cfg: LlamaConfig,
                    cos: jax.Array, sin: jax.Array) -> jax.Array:
    B, S, _ = x.shape
    hd = cfg.head_dim
    from jax.ad_checkpoint import checkpoint_name

    q = _proj(x, lp["wq"], cfg, ("embed", "heads")).reshape(B, S, cfg.n_heads, hd)
    k = _proj(x, lp["wk"], cfg, ("embed", "kv_heads")).reshape(B, S, cfg.n_kv_heads, hd)
    v = _proj(x, lp["wv"], cfg, ("embed", "kv_heads")).reshape(B, S, cfg.n_kv_heads, hd)
    q = checkpoint_name(apply_rope(q, cos, sin), "attn_qkv")
    k = checkpoint_name(apply_rope(k, cos, sin), "attn_qkv")
    v = checkpoint_name(v, "attn_qkv")
    # GQA: the flash kernels read each kv head n_heads/n_kv_heads times via
    # their BlockSpec index maps — no HBM-materialised repeat (and for
    # ring_flash, no repeat riding every ppermute hop). Other impls get the
    # expanded kv tensors.
    if (cfg.n_kv_heads != cfg.n_heads
            and cfg.attention_impl not in ("flash", "ring_flash")):
        rep = cfg.n_heads // cfg.n_kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    out = _get_attention(cfg)(q, k, v, cfg)
    # named save point: remat_policy='save_attn' keeps this activation so the
    # bwd recompute skips qkv projections + the attention kernel (~29% of a
    # layer's fwd FLOPs) for ~32MB/layer at bench shapes
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "attn_out")
    return _proj(
        out.reshape(B, S, cfg.n_heads * hd), lp["wo"], cfg, ("heads", "embed")
    )


def ffn_block(x: jax.Array, lp: Params, cfg: LlamaConfig) -> jax.Array:
    from jax.ad_checkpoint import checkpoint_name

    # named save point: remat policies can keep the gate product so the bwd
    # recompute skips the two widest matmuls (w1/w3, ~45% of a layer's fwd)
    gate = checkpoint_name(
        jax.nn.silu(_proj(x, lp["w1"], cfg, ("embed", "ffn")))
        * _proj(x, lp["w3"], cfg, ("embed", "ffn")),
        "ffn_gate",
    )
    return _proj(gate, lp["w2"], cfg, ("ffn", "embed"))


def moe_ffn_block(x: jax.Array, lp: Params, cfg: LlamaConfig):
    """Expert-parallel FFN: (y, aux_loss). See tony_tpu.parallel.moe."""
    from tony_tpu.parallel.moe import MoEConfig, moe_block

    mcfg = MoEConfig(
        dim=cfg.dim, ffn_dim=cfg.ffn_dim, n_experts=cfg.n_experts,
        top_k=cfg.moe_top_k, capacity_factor=cfg.moe_capacity_factor,
        dispatch=cfg.moe_dispatch, group_block=cfg.moe_group_block,
        gmm_impl=cfg.moe_gmm_impl, overlap_impl=cfg.moe_overlap_impl,
        overlap_chunk=cfg.moe_overlap_chunk,
    )
    return moe_block(
        {"router": lp["router"], "w1": lp["w1"], "w3": lp["w3"], "w2": lp["w2"]},
        x, mcfg,
    )


# --- forward ------------------------------------------------------------------


def transformer_block(x: jax.Array, lp: Params, cfg: LlamaConfig,
                      cos: jax.Array, sin: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One decoder layer: (x, lp) -> (x', aux_loss). aux is 0 for dense."""
    h = x + attention_block(
        rms_norm(x, lp["attn_norm"], cfg.norm_eps), lp, cfg, cos, sin
    )
    normed = rms_norm(h, lp["ffn_norm"], cfg.norm_eps)
    if cfg.is_moe:
        delta, aux = moe_ffn_block(normed, lp, cfg)
    else:
        delta, aux = ffn_block(normed, lp, cfg), jnp.zeros((), jnp.float32)
    return h + delta, aux


def _remat_policy(name: str):
    policies = {
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "save_attn": jax.checkpoint_policies.save_only_these_names("attn_out"),
        "save_gate": jax.checkpoint_policies.save_only_these_names("ffn_gate"),
        "save_attn_gate": jax.checkpoint_policies.save_only_these_names(
            "attn_out", "ffn_gate"
        ),
        # keep the flash kernel's inputs + residuals (q/k/v post-rope, out,
        # lse): the bwd recompute skips the qkv projections, rope, AND the
        # flash fwd kernel — the three hottest recompute items in the trace
        # — for ~3.2GB at bench shapes (B=4, S=2048, 24 layers)
        "save_attn_kernel": jax.checkpoint_policies.save_only_these_names(
            "attn_qkv", "flash_res"
        ),
        "save_attn_kernel_gate": jax.checkpoint_policies.save_only_these_names(
            "attn_qkv", "flash_res", "ffn_gate"
        ),
        # flash residuals + gate but NOT q/k/v: bwd re-runs the (cheap) qkv
        # projections but skips the flash fwd kernel and the two widest FFN
        # matmuls — 0.8GB less HBM than save_attn_kernel_gate
        "save_flash_gate": jax.checkpoint_policies.save_only_these_names(
            "flash_res", "ffn_gate"
        ),
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "checkpoint_dots": jax.checkpoint_policies.checkpoint_dots,
    }
    if name not in policies:
        raise ValueError(f"unknown remat_policy {name!r} (expected {sorted(policies)})")
    return policies[name]


def embed_tokens(params: Params, tokens: jax.Array, act_sharding=None) -> jax.Array:
    """Embedding gather with pinned shardings: gather from an explicitly
    replicated table view, batch/seq-sharded output. The fsdp/tp-sharded
    table would otherwise make the partitioner emit the same all-gather
    *involuntarily* (an embed-sharded gather output it then full-remats to
    the activation layout — "[SPMD] Involuntary full rematerialization" in
    the multichip dryrun log); the constraint's transpose pins the bwd
    cotangents too. The ONE copy both the sequential trunk and the
    trainer's pipeline losses use. ``act_sharding=None`` is a plain gather.
    """
    emb = params["tok_emb"]
    if act_sharding is None:
        return emb[tokens]
    from jax.sharding import NamedSharding, PartitionSpec

    emb = lax.with_sharding_constraint(
        emb, NamedSharding(act_sharding.mesh, PartitionSpec())
    )
    return lax.with_sharding_constraint(emb[tokens], act_sharding)


def hidden_states_with_aux(
    params: Params, tokens: jax.Array, cfg: LlamaConfig,
    act_sharding=None,
) -> tuple[jax.Array, jax.Array]:
    """tokens [B, S] int32 -> (post-final-norm hidden [B, S, D], aux_loss).

    The trunk without the vocab projection: the fused CE head consumes this
    directly so the [B, S, V] logits tensor never exists on the train path.

    ``act_sharding`` (a NamedSharding for [B, S, D] activations, or None)
    pins the embedding output and the returned hidden states: without it
    the partitioner propagates ``tok_emb``'s fsdp/tp weight sharding into
    the gather's embed dim while downstream ops want batch/seq-sharded
    activations, and resolves the conflict with "[SPMD] Involuntary full
    rematerialization" all-gathers in both fwd and bwd (the constraint's
    transpose pins the cotangents too). The trainer passes it whenever the
    mesh has more than one device.
    """
    x = embed_tokens(params, tokens, act_sharding)
    cos, sin = rope_table(cfg, tokens.shape[1])

    def block(carry, lp: Params):
        x, aux_acc = carry
        out, aux = transformer_block(x, lp, cfg, cos, sin)
        return (out, aux_acc + aux), None

    if cfg.remat:
        block = jax.checkpoint(block, policy=_remat_policy(cfg.remat_policy))
    (x, aux), _ = lax.scan(
        block, (x, jnp.zeros((), jnp.float32)), params["layers"],
        unroll=cfg.scan_unroll,
    )
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if act_sharding is not None:
        h = lax.with_sharding_constraint(h, act_sharding)
    return h, aux / cfg.n_layers


def forward_with_aux(
    params: Params, tokens: jax.Array, cfg: LlamaConfig
) -> tuple[jax.Array, jax.Array]:
    """tokens [B, S] int32 -> (logits [B, S, vocab] float32, aux_loss)."""
    x, aux = hidden_states_with_aux(params, tokens, cfg)
    return (x @ params["lm_head"]).astype(jnp.float32), aux


def forward(params: Params, tokens: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, vocab] float32."""
    return forward_with_aux(params, tokens, cfg)[0]


def ce_tokens(
    h: jax.Array, lm_head: jax.Array, targets: jax.Array, cfg: LlamaConfig
) -> jax.Array:
    """Per-token CE [B, S] f32 from post-norm hidden states, dispatched on
    ``cfg.ce_impl``. The ONE head every loss path shares (sequential, GPipe,
    1F1B), so schedule-parity tests compare identical math."""
    if cfg.ce_impl == "dense":
        # legacy full-logits path (the fused impls' parity oracle — ONE copy
        # of the math, in ops.fused_ce): the logits and autodiff's dlogits
        # still materialise at [B,S,V]
        from tony_tpu.ops.fused_ce import reference_ce_tokens

        return reference_ce_tokens(h, lm_head, targets)
    from tony_tpu.ops.fused_ce import sharded_fused_ce_tokens

    return sharded_fused_ce_tokens(h, lm_head, targets, cfg)


def loss_from_pairs(
    params: Params, inputs: jax.Array, targets: jax.Array, cfg: LlamaConfig,
    act_sharding=None,
) -> jax.Array:
    """Cross-entropy of predicting targets [B, S] from inputs [B, S].

    Pre-shifted pairs keep the sequence length identical across inputs,
    activations, and targets, so a ``sp``-sharded seq axis stays aligned end
    to end (no off-by-one reshard between forward and loss). The head runs
    through :func:`ce_tokens` (fused chunked CE by default).
    ``act_sharding`` pins [B, S, D] activation shardings at the trunk
    boundaries (see :func:`hidden_states_with_aux`).
    """
    h, aux = hidden_states_with_aux(params, inputs, cfg, act_sharding)
    ce = jnp.mean(ce_tokens(h, params["lm_head"], targets, cfg))
    if cfg.is_moe:
        ce = ce + cfg.moe_aux_coef * aux
    return ce


def loss_fn(params: Params, tokens: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """Next-token cross-entropy over tokens [B, S+1] (shifts internally)."""
    return loss_from_pairs(params, tokens[:, :-1], tokens[:, 1:], cfg)


def train_flops_per_token(cfg: LlamaConfig, seq_len: int) -> float:
    """Training FLOPs per token the algorithm needs: 6*N over the matmul
    parameters (fwd+bwd; MoE counts only the top-k experts that fire per
    token; the embedding lookup is a gather, not a matmul, and is left
    out) plus the causal-attention score/value matmuls (12*L*D*S/2)."""
    return 6.0 * cfg.n_matmul_params + 6.0 * cfg.n_layers * cfg.dim * seq_len


__all__ = [
    "LlamaConfig", "embed_tokens", "init_params", "logical_axes", "forward",
    "forward_with_aux", "hidden_states_with_aux", "ce_tokens",
    "loss_fn", "loss_from_pairs",
    "rms_norm", "rope_freqs", "rope_table", "apply_rope", "dot_attention",
    "transformer_block", "train_flops_per_token",
]
