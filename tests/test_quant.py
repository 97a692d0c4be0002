"""Quantized serving (serve.quant.*): block-scaled int8/fp8 KV cache
(serve/cache.py) + weight-only int8 decode matmuls (ops/quant_mm.py).

The contract under test, layer by layer:

- the quantized decode-attention kernels (scan AND interpreted pallas,
  paged / shared-table / scratch-tail / G-query spec forms) stay within a
  STATED tolerance of the bf16 reference — and match each other tightly;
- the write path's running block scale requantizes without forgetting
  (growing amax keeps earlier positions accurate to the new scale), and a
  copy-on-write block copy carries its scale rows;
- the full engine with quantization + prefix sharing + speculation live
  is EXACTLY reproducible: generate()'s ``serve`` override runs the same
  quantized step, so engine-vs-generate parity is equality, not a bound;
- nonfinite values propagate to exactly the affected slots/channels (a
  poisoned block scale cannot silently read as zeros), and a healthy
  quantized engine trips neither serve_nonfinite nor entropy_floor;
- the measured capacity gain is real: derive_slot_budget's quant pair
  prices the quantized step's own memory plan.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import llama
from tony_tpu.models.generate import generate
from tony_tpu.ops.decode_attention import (
    decode_attention, reference_decode_attention,
)
from tony_tpu.ops.quant_mm import WEIGHT_QMAX, quant_matmul, quantize_weights
from tony_tpu.serve import Engine, Request, ServeConfig
from tony_tpu.serve.cache import (
    block_bytes, create_cache, dequantize_values, kv_quant_spec,
    quant_scatter_span,
)

# stated quant-vs-bf16 logits tolerance (bench decode.quant reports the
# same number; perf-diff pins it as config identity so it cannot loosen)
TOL = 0.08
WTOL = 0.02  # weight-only matmul relative error bound


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    return cfg, params


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32) for n in lengths]


# --- weight-only int8 matmul --------------------------------------------------


class TestQuantMM:
    def test_matches_bf16_within_tolerance_both_impls(self):
        k1, k2 = jax.random.split(jax.random.key(1))
        x = jax.random.normal(k1, (6, 32), jnp.bfloat16)
        w = jax.random.normal(k2, (32, 48), jnp.bfloat16)
        ref = (x.astype(jnp.float32) @ w.astype(jnp.float32))
        wq, s = quantize_weights(w)
        assert wq.dtype == jnp.int8 and s.shape == (48,)
        denom = float(jnp.abs(ref).max())
        for impl in ("scan", "pallas"):
            y = quant_matmul(x, wq, s, impl=impl, block_n=16)
            assert y.shape == ref.shape and y.dtype == x.dtype
            rel = float(jnp.abs(y.astype(jnp.float32) - ref).max()) / denom
            assert rel < WTOL, (impl, rel)
        ys = quant_matmul(x, wq, s, impl="scan", block_n=16)
        yp = quant_matmul(x, wq, s, impl="pallas", block_n=16)
        np.testing.assert_allclose(
            np.asarray(ys, np.float32), np.asarray(yp, np.float32),
            rtol=0, atol=2e-2,
        )

    def test_roundtrip_error_bounded_per_channel(self):
        w = jax.random.normal(jax.random.key(3), (16, 24), jnp.float32)
        wq, s = quantize_weights(w)
        back = wq.astype(jnp.float32) * s[None, :]
        # symmetric rounding: per-channel error <= half an int8 step
        assert float(jnp.abs(back - w).max()) <= float(s.max()) / 2 + 1e-6
        assert float(jnp.abs(wq).max()) <= WEIGHT_QMAX

    def test_poisoned_scale_channel_propagates_to_that_channel_only(self):
        x = jax.random.normal(jax.random.key(4), (4, 16), jnp.float32)
        wq, s = quantize_weights(
            jax.random.normal(jax.random.key(5), (16, 24), jnp.float32)
        )
        s = s.at[7].set(jnp.nan)
        for impl in ("scan", "pallas"):
            y = np.asarray(quant_matmul(x, wq, s, impl=impl, block_n=8))
            assert not np.isfinite(y[:, 7]).any(), impl
            assert np.isfinite(np.delete(y, 7, axis=1)).all(), impl

    def test_shape_validation(self):
        x = jnp.zeros((2, 8))
        wq, s = quantize_weights(jnp.ones((8, 8)))
        with pytest.raises(ValueError):
            quant_matmul(x, wq, s, impl="nope")
        with pytest.raises(ValueError):
            quant_matmul(x, wq, s[:4])
        with pytest.raises(ValueError):
            quant_matmul(jnp.zeros((2, 4)), wq, s)


# --- quantized paged decode attention -----------------------------------------


def _quantize_pool(pool, qmax=127.0):
    """[P, Hkv, blk, hd] bf16 -> (int8 pool, [P, Hkv] f32 scales)."""
    f = pool.astype(jnp.float32)
    scale = jnp.max(jnp.abs(f), axis=(2, 3)) / qmax
    q = f / jnp.maximum(scale[..., None, None], 1e-30)
    return jnp.clip(jnp.round(q), -qmax, qmax).astype(jnp.int8), scale


def _gathered(pool, tables):
    """Pool blocks -> contiguous [B, Hkv, T, hd] caches for the reference."""
    g = jnp.take(pool, tables, axis=0)         # [B, M, Hkv, blk, hd]
    B, M, Hkv, blk, hd = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, M * blk, hd)


class TestQuantKernel:
    B, H, Hkv, hd, blk, M = 3, 4, 2, 8, 8, 3

    def _case(self, seed=0, G=1, shared=False, short=False):
        """(q, quant pools + scales, tables, lengths, bf16 pools)."""
        ks = jax.random.split(jax.random.key(seed), 3)
        P = 1 + self.B * self.M
        qshape = (self.B, G, self.H, self.hd) if G > 1 else (self.B, self.H, self.hd)
        q = jax.random.normal(ks[0], qshape, jnp.bfloat16)
        kp = jax.random.normal(ks[1], (P, self.Hkv, self.blk, self.hd), jnp.bfloat16)
        vp = jax.random.normal(ks[2], (P, self.Hkv, self.blk, self.hd), jnp.bfloat16)
        tables = 1 + np.arange(self.B * self.M).reshape(self.B, self.M)
        if shared:  # every row's first block is the same physical block
            tables[:, 0] = 1
        lengths = np.full((self.B,), self.M * self.blk, np.int32)
        if short:  # rows end mid-block; trailing table entries hit scratch
            lengths = np.array(
                [self.blk + 3, 2 * self.blk, self.blk - 1], np.int32
            )
            for b in range(self.B):
                used = -(-int(lengths[b]) // self.blk)
                tables[b, used:] = 0
        tables = jnp.asarray(tables, jnp.int32)
        lengths = jnp.asarray(lengths)
        kq, ks_ = _quantize_pool(kp)
        vq, vs_ = _quantize_pool(vp)
        return q, (kq, vq, ks_, vs_), tables, lengths, (kp, vp)

    @pytest.mark.parametrize("shared,short,G", [
        (False, False, 1),   # plain paged
        (True, False, 1),    # shared tables (prefix-store substrate)
        (False, True, 1),    # mid-block lengths + scratch tails
        (False, False, 3),   # G-query speculative verify form
        (True, True, 3),     # everything at once
    ])
    def test_within_tolerance_of_bf16_and_impls_agree(self, shared, short, G,
                                                      paged_kernel):
        q, (kq, vq, ksc, vsc), tables, lengths, (kp, vp) = self._case(
            seed=10 + G, G=G, shared=shared, short=short,
        )
        ref = reference_decode_attention(
            q, _gathered(kp, tables), _gathered(vp, tables), lengths,
        )
        outs = {}
        for impl in ("scan", "pallas"):
            paged_kernel(impl == "pallas")   # the paged form takes no impl
            out = decode_attention(
                q, kq, vq, lengths, tables=tables,
                block=self.blk, k_scale=ksc, v_scale=vsc,
            )
            assert out.shape == ref.shape
            err = float(jnp.abs(
                out.astype(jnp.float32) - ref.astype(jnp.float32)
            ).max())
            assert err < TOL, (impl, shared, short, G, err)
            outs[impl] = np.asarray(out, np.float32)
        np.testing.assert_allclose(
            outs["scan"], outs["pallas"], rtol=0, atol=1e-2,
        )

    def test_poisoned_block_scale_hits_exactly_the_referencing_rows(
            self, paged_kernel):
        q, (kq, vq, ksc, vsc), tables, lengths, _ = self._case(seed=20)
        # poison the scale row of row 0's second block; rows 1/2 never
        # reference it, so their outputs must stay finite
        bad = int(tables[0, 1])
        ksc = ksc.at[bad].set(jnp.nan)
        for impl in ("scan", "pallas"):
            paged_kernel(impl == "pallas")
            out = np.asarray(decode_attention(
                q, kq, vq, lengths, tables=tables,
                block=self.blk, k_scale=ksc, v_scale=vsc,
            ), np.float32)
            assert not np.isfinite(out[0]).all(), impl
            assert np.isfinite(out[1:]).all(), impl

    def test_scale_args_are_validated(self):
        q, (kq, vq, ksc, vsc), tables, lengths, _ = self._case(seed=30)
        with pytest.raises(ValueError):
            decode_attention(
                q, kq, vq, lengths, tables=tables, k_scale=ksc,
            )  # k without v
        with pytest.raises(ValueError):
            decode_attention(  # quantized needs the paged form
                q, kq.transpose(1, 0, 2, 3), vq.transpose(1, 0, 2, 3),
                lengths, k_scale=ksc, v_scale=vsc,
            )


# --- cache write path: running scales, COW, accounting ------------------------


class TestQuantCache:
    def test_kv_quant_spec(self):
        dt, qmax = kv_quant_spec("int8")
        assert dt == jnp.int8 and qmax == 127.0
        with pytest.raises(ValueError):
            kv_quant_spec("int4")
        dt8, qmax8 = kv_quant_spec("fp8_e4m3")
        assert dt8 == jnp.float8_e4m3fn and qmax8 == 448.0

    def test_running_scale_growth_keeps_old_positions_accurate(self):
        """Write small-amplitude rows, then 8x larger rows into the SAME
        block: the block scale grows, stored rows requantize, and the
        early rows still dequantize to their originals within the (new,
        coarser) scale's half-step."""
        Hkv, blk, hd, P = 2, 8, 4, 3
        pool = jnp.zeros((P, Hkv, blk, hd), jnp.int8)
        scale = jnp.zeros((P, Hkv), jnp.float32)
        rng = np.random.default_rng(0)
        small = jnp.asarray(rng.normal(size=(Hkv, 4, hd)) * 0.25, jnp.float32)
        big = jnp.asarray(rng.normal(size=(Hkv, 4, hd)) * 2.0, jnp.float32)
        pids = jnp.full((4,), 1, jnp.int32)
        ub = jnp.asarray([1, 0], jnp.int32)
        pool, scale = quant_scatter_span(
            pool, scale, small, pids, jnp.arange(4), ub, 127.0,
        )
        sc_small = float(scale[1].max())
        pool, scale = quant_scatter_span(
            pool, scale, big, pids, 4 + jnp.arange(4), ub, 127.0,
        )
        assert float(scale[1].min()) > sc_small  # the running max grew
        deq = dequantize_values(
            pool[1], scale[1][:, None, None], jnp.float32,
        )  # [Hkv, blk, hd]
        got_small = deq[:, :4]
        got_big = deq[:, 4:8]
        step = float(scale[1].max())  # one quant step at the final scale
        assert float(jnp.abs(got_small - small).max()) <= step
        assert float(jnp.abs(got_big - big).max()) <= step
        # untouched block 2 still reads all-zero (scale 0 marker intact)
        assert float(jnp.abs(scale[2]).max()) == 0.0

    def test_cow_copy_carries_scale_rows(self):
        from tony_tpu.serve.engine import _copy_block_fn

        cfg = llama.LlamaConfig.tiny()
        cache = create_cache(cfg, 2, 4, 8, quant_kv="int8")
        assert cache.quantized
        rng = np.random.default_rng(1)
        k = cache.k.at[:, 1].set(
            jnp.asarray(rng.integers(-127, 128, cache.k.shape[2:]), jnp.int8)
        )
        cache = cache._replace(
            k=k,
            k_scale=cache.k_scale.at[:, 1].set(0.37),
            v_scale=cache.v_scale.at[:, 1].set(0.11),
        )
        out = _copy_block_fn()(cache, 1, 2)
        np.testing.assert_array_equal(
            np.asarray(out.k[:, 2]), np.asarray(out.k[:, 1])
        )
        assert float(out.k_scale[:, 2].min()) == pytest.approx(0.37)
        assert float(out.v_scale[:, 2].max()) == pytest.approx(0.11)
        # the source block is untouched
        assert float(out.k_scale[:, 1].max()) == pytest.approx(0.37)

    def test_block_bytes_prices_payload_plus_scale_rows(self):
        cfg = llama.LlamaConfig.tiny()
        full = block_bytes(cfg, 8)
        q = block_bytes(cfg, 8, quant_kv="int8")
        itemsize = jnp.dtype(cfg.dtype).itemsize
        scales = 2 * cfg.n_layers * cfg.n_kv_heads * 4
        assert q == full // itemsize + scales  # int8 payload + scale rows
        assert q < 0.6 * full


# --- the engine, end to end ---------------------------------------------------


class TestQuantEngine:
    def test_engine_matches_generate_with_everything_live(self, setup):
        """Quantized KV + int8 weights + prefix sharing + speculation, all
        on: engine-vs-generate parity stays EXACT because generate()'s
        ``serve`` override runs the identical quantized step."""
        cfg, params = setup
        sv = dict(quant_kv="int8", quant_weights=True, prefix=True,
                  spec=True, spec_max_draft=3)
        B, P, m = 3, 10, 6
        rng = np.random.default_rng(5)
        shared = rng.integers(0, cfg.vocab_size, 6)
        prompts = np.stack([
            np.concatenate([shared, rng.integers(0, cfg.vocab_size, P - 6)])
            for _ in range(B)
        ]).astype(np.int32)
        key = jax.random.key(9)
        keys = jax.random.split(key, B)
        from tony_tpu.models.generate import DEFAULT_NUCLEUS_K

        eng = Engine(params, cfg, ServeConfig(
            slots=B, max_len=P + m, prefill_buckets=(P,),
            max_top_k=DEFAULT_NUCLEUS_K, **sv,
        ))
        rids = [
            eng.submit(Request(prompt=prompts[i], max_new_tokens=m,
                               rng=keys[i]))
            for i in range(B)
        ]
        got = eng.run()
        assert eng.cache.quantized
        solo = generate(
            params, jnp.asarray(prompts), cfg, max_new_tokens=m,
            rng=key, serve=sv,
        )
        for i, rid in enumerate(rids):
            assert got[rid].tokens == list(np.asarray(solo[i, P:])), i

    # slow: scan-vs-pallas agreement is already tier-1 at the KERNEL level
    # (TestQuantKernel) — the engine-level token identity re-pays two full
    # engine builds and tier-1 runs close to its wall-clock budget
    @pytest.mark.slow
    def test_scan_and_pallas_quant_engines_emit_identical_tokens(
            self, setup, paged_kernel):
        cfg, params = setup
        prompts = _prompts(cfg, [5, 9], seed=7)
        outs = []
        for impl in ("scan", "pallas"):
            paged_kernel(impl == "pallas")
            eng = Engine(params, cfg, ServeConfig(
                slots=2, max_len=24, kv_block=8, decode_impl=impl,
                quant_kv="int8", quant_weights=True,
            ))
            rids = [eng.submit(Request(prompt=p, max_new_tokens=4))
                    for p in prompts]
            got = eng.run()
            outs.append([got[r].tokens for r in rids])
        assert outs[0] == outs[1]

    @pytest.mark.slow
    def test_compile_ledger_count_unchanged_by_quantization(self, setup):
        """Quantization changes WHAT compiles, never HOW MANY: the same
        trace pays the same bounded prefill/decode signature families.
        Slow-marked (two full engine builds over a 5-prompt trace) —
        tier-1 runs close to its wall-clock budget."""
        cfg, params = setup
        counts = {}
        for quant in (False, True):
            eng = Engine(params, cfg, ServeConfig(
                slots=2, max_len=40, kv_block=8, prefill_buckets=(8, 16),
                quant_kv="int8" if quant else "",
                quant_weights=quant,
            ))
            for p in _prompts(cfg, [3, 6, 9, 12, 15], seed=8):
                eng.submit(Request(prompt=p, max_new_tokens=3))
            eng.run()
            counts[quant] = (
                eng.metrics.prefill_compiles, eng.metrics.decode_compiles,
            )
        assert counts[True] == counts[False]

    def test_stats_snapshot_reports_quant_gauges(self, setup):
        cfg, params = setup
        eng = Engine(params, cfg, ServeConfig(
            slots=2, max_len=32, kv_block=8, quant_kv="int8",
        ))
        eng.run([Request(prompt=np.arange(1, 6), max_new_tokens=3)])
        snap = eng.stats_snapshot()
        assert snap["kv_bytes_per_token"] == pytest.approx(
            block_bytes(cfg, 8, quant_kv="int8") / 8
        )
        assert snap["quant_pool_resident_bytes"] > 0
        bf = Engine(params, cfg, ServeConfig(slots=2, max_len=32, kv_block=8))
        assert "quant_pool_resident_bytes" not in bf.stats_snapshot()
        assert bf.stats_snapshot()["kv_bytes_per_token"] > snap["kv_bytes_per_token"]

    # slow: the int8 path above is the tier-1 surface, and the fp8 engine
    # build costs ~3s of a tier-1 budget that runs close to its ceiling.
    # kv_quant_spec's fp8 row itself stays tier-1 in TestQuantCache.
    @pytest.mark.slow
    def test_fp8_gate(self, setup):
        cfg, params = setup
        eng = Engine(params, cfg, ServeConfig(
            slots=1, max_len=16, kv_block=8, quant_kv="fp8_e4m3",
        ))
        got = eng.run([Request(prompt=np.arange(1, 5), max_new_tokens=3)])
        assert len(got[0].tokens) == 3

    def test_unknown_kv_dtype_refused(self, setup):
        cfg, params = setup
        with pytest.raises(ValueError):
            Engine(params, cfg, ServeConfig(
                slots=1, max_len=16, kv_block=8, quant_kv="int4",
            ))

    def test_measured_quant_slot_budget_beats_bf16(self):
        from tony_tpu.serve.capacity import derive_slot_budget

        cfg = llama.LlamaConfig.tiny()
        out = derive_slot_budget(
            cfg, max_len=64, hbm_bytes=8 * 1024 ** 2, kv_block=8,
            shared_prefix_tokens=32, quant_kv="int8",
        )
        assert out["max_slots_quant"] > out["max_slots_native"]
        assert out["quant_slot_ratio"] > 1.0
        assert out["kv_bytes_per_slot_quant"] < 0.6 * out["kv_bytes_per_slot_native"]
        assert out["max_slots_quant_prefix_shared"] >= out["max_slots_quant"]


# --- health: quantization must not read as sickness ---------------------------


class TestQuantHealth:
    @pytest.fixture(autouse=True)
    def _disarm(self):
        from tony_tpu.obs import health

        health.uninstall()
        yield
        health.uninstall()

    @pytest.mark.slow  # re-pays a full quantized-engine build: healthy
    # decode through the quant path is already proven by the
    # everything-live parity test, and sentinel trip/no-trip mechanics by
    # the health suite (tier-1 runs close to its 870s timeout)
    def test_quantized_engine_trips_no_monitors(self, setup, tmp_path):
        """A healthy model served through the quantized path must not trip
        serve_nonfinite (dequant produces real values) or entropy_floor
        (quantization noise must not collapse the output distribution)."""
        from tony_tpu.obs import health
        from tony_tpu.obs.health import HealthRules, HealthSentinel

        cfg, params = setup
        s = health.install(HealthSentinel(
            HealthRules(), app_dir=str(tmp_path), proc="worker_0_user_a0",
            sample_every=1,
        ))
        eng = Engine(params, cfg, ServeConfig(
            slots=2, max_len=48, kv_block=8, quant_kv="int8",
            quant_weights=True,
        ))
        eng.run([
            Request(prompt=p, max_new_tokens=8)
            for p in _prompts(cfg, [4, 7], seed=11)
        ])
        summary = eng.close()
        assert s.verdict == "healthy"
        assert s.trip_counts() == {}
        assert summary.get("health_verdict", "healthy") == "healthy"
