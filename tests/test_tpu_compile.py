"""Described-chip compiles: every Pallas kernel of the main paths, and the
whole 3-layer 7B-width train step, compiled by the real TPU compiler for a
v5e that is *described*, not attached (on-chip-measurement guide §2.3).

Nothing runs here — these are compiler verdicts (tiling rules, scoped VMEM,
HBM fit), the class of fault interpret mode cannot see. The topology is
described inside a module-scoped fixture (never at import: only one process
may hold libtpu, and every xdist worker imports this file), the compiles
happen in the test's own process, and the persistent compile cache is off
around them (a described-chip entry cannot be read back without a chip).
Keep every described-chip test in THIS file: a second file could land on
another worker, whose fixture would then skip in silence.
"""

from __future__ import annotations

import os
import re
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / already locked by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(autouse=True)
def _compiled_kernels(monkeypatch):
    """The kernel modules pick interpret mode from ``jax.default_backend()``,
    which is the CPU here; each reads ``_use_interpret`` from its own
    namespace, so steer it there (no option in the program)."""
    from tony_tpu.ops import (
        attention, decode_attention, fused_ce, grouped_mm, quant_mm, selective_scan,
    )

    for mod in (attention, decode_attention, fused_ce, grouped_mm, quant_mm, selective_scan):
        monkeypatch.setattr(mod, "_use_interpret", lambda: False)


def _compile(fn, one_chip, *shapes):
    """Lower + compile ``fn`` for the described chip; the kernel must be in
    the program as a Mosaic custom call, not as interpreted XLA ops."""
    args = [
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes
    ]
    lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    return lowered.compile()


BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8


def _described(tree, one_chip):
    """The tree's arrays (or shapes) as shapes placed on the described chip."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)


# --- flash attention (train path) ---------------------------------------------


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("kv_heads", [32, 8], ids=["mha", "gqa"])
def test_flash_attention(one_chip, kv_heads, grad):
    from tony_tpu.ops.attention import flash_attention

    attn = partial(flash_attention, block_q=1024, block_k=1024)
    if grad:
        def fn(q, k, v):
            return jax.grad(
                lambda *a: attn(*a).astype(F32).sum(), argnums=(0, 1, 2)
            )(q, k, v)
    else:
        fn = attn
    q = ((2, 2048, 32, 128), BF16)
    kv = ((2, 2048, kv_heads, 128), BF16)
    _compile(fn, one_chip, q, kv, kv)


# --- decode attention (serve path) --------------------------------------------

_B, _H, _HKV, _HD, _T, _BLK = 8, 32, 8, 128, 4096, 64


@pytest.mark.parametrize("queries", [1, 5], ids=["G1", "G5"])
@pytest.mark.parametrize("form", ["contiguous", "paged", "paged_int8"])
def test_decode_attention(one_chip, form, queries):
    from tony_tpu.ops.decode_attention import decode_attention

    q = ((_B, queries, _H, _HD), BF16)
    lengths = ((_B,), I32)
    if form == "contiguous":
        kv = ((_B, _HKV, _T, _HD), BF16)
        fn = partial(decode_attention, impl="pallas", block=_BLK)
        _compile(fn, one_chip, q, kv, kv, lengths)
        return
    m = _T // _BLK
    pool = 1 + _B * m
    tables = ((_B, m), I32)
    # the paged form takes no ``impl``: it runs its kernel wherever
    # ``_use_interpret`` is False, which the fixture above has arranged
    if form == "paged":
        kv = ((pool, _HKV, _BLK, _HD), BF16)

        def fn(q, k, v, ln, tb):
            return decode_attention(q, k, v, ln, tables=tb)

        _compile(fn, one_chip, q, kv, kv, lengths, tables)
        return
    kv = ((pool, _HKV, _BLK, _HD), I8)
    sc = ((pool, _HKV), F32)

    def fn(q, k, v, ln, tb, ks, vs):
        return decode_attention(
            q, k, v, ln, tables=tb, k_scale=ks, v_scale=vs
        )

    _compile(fn, one_chip, q, kv, kv, lengths, tables, sc, sc)


@pytest.mark.parametrize("cell", ["yi-chat-closed16", "dsv3-reason-closed48"])
def test_paged_decode_attention_at_the_serving_cells_shapes(one_chip, cell):
    """The paged kernel at the shapes the two serving cells run it with
    (PERF.md §4): the dense pool of Yi-1.5-6B (16 slots, 32/4 heads x 128,
    a table of 32 blocks of 64) and the latent pool of DeepSeek-V3 (48
    slots, 128 heads against one 640-lane row block, values its first 512
    columns, a table of 64), each pool at all its layers' blocks."""
    from tony_tpu.ops.decode_attention import (
        decode_attention, latent_decode_attention,
    )

    if cell == "yi-chat-closed16":
        kv = ((32 * 513, 4, 64, 128), BF16)

        def fn(q, k, v, ln, tb):
            return decode_attention(q, k, v, ln, tables=tb)

        _compile(fn, one_chip, ((16, 32, 128), BF16), kv, kv,
                 ((16,), I32), ((16, 32), I32))
        return

    def fn(q, pool, ln, tb):
        return latent_decode_attention(q, pool, ln, tb, v_width=512,
                                       scale=0.1)

    _compile(fn, one_chip, ((48, 128, 576), BF16),
             ((6 * 3136, 1, 64, 640), BF16), ((48,), I32), ((48, 64), I32))


@pytest.mark.parametrize("kv_heads,block,dtype,blocks", [
    pytest.param(32, 64, BF16, 4, id="llama2_7b"),
    pytest.param(40, 64, BF16, 3, id="llama2_13b"),
    pytest.param(32, 64, F32, 2, id="llama2_7b-float32"),
    pytest.param(32, 64, I8, 8, id="llama2_7b-int8"),
    pytest.param(40, 128, F32, 0, id="llama2_13b-float32-block128"),
])
def test_paged_decode_attention_at_one_query_row_a_kv_head(
        one_chip, kv_heads, block, dtype, blocks):
    """MHA pools (``LlamaConfig``'s default: as many kv heads as heads, so
    one query row a kv head and tiles of 512 KB and more): the kernel takes
    as many blocks a step as fit the 16 MiB of VMEM a v5e scopes to it —
    at 8 a step these shapes run out of it — and where not even one block
    fits the op hands the shape to the scan, which compiles as it always
    did."""
    from tony_tpu.ops import decode_attention as da

    B, M, hd = 8, 4096 // block, 128
    pool = ((1 + B * M, kv_heads, block, hd), dtype)
    shapes = [((B, kv_heads, hd), BF16 if dtype == I8 else dtype), pool, pool,
              ((B,), I32), ((B, M), I32)]
    if dtype == I8:
        shapes += [((1 + B * M, kv_heads), F32)] * 2

    def fn(q, k, v, ln, tb, ks=None, vs=None):
        return da.decode_attention(q, k, v, ln, tables=tb, k_scale=ks, v_scale=vs)

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    assert da._step_blocks(args[1], args[2], args[4]) == blocks
    lowered = jax.jit(fn).lower(*args)
    assert ("tpu_custom_call" in lowered.as_text()) == bool(blocks)
    lowered.compile()


# --- fused cross-entropy, pallas impl -----------------------------------------


@pytest.mark.parametrize("dim", [2048, 4096], ids=["D2048", "D4096"])
def test_fused_ce_pallas_fwd_bwd(one_chip, dim):
    from tony_tpu.ops.fused_ce import fused_ce_tokens

    def fn(h, w, t):
        return jax.value_and_grad(
            lambda h, w: fused_ce_tokens(h, w, t, impl="pallas").mean(),
            argnums=(0, 1),
        )(h, w)

    _compile(
        fn, one_chip,
        ((2, 2048, dim), BF16), ((dim, 32000), BF16), ((2, 2048), I32),
    )


# --- grouped GEMM (MoE) and int8 weight matmul (serve) ------------------------


def test_grouped_matmul_fwd_bwd(one_chip):
    from tony_tpu.ops.grouped_mm import grouped_matmul

    n, d, f, g, block = 8192, 1024, 2816, 8, 128

    def fn(x, w, tg):
        return jax.value_and_grad(
            lambda x, w: grouped_matmul(x, w, tg, impl="pallas")
            .astype(F32).sum(),
            argnums=(0, 1),
        )(x, w)

    _compile(
        fn, one_chip,
        ((n, d), BF16), ((g, d, f), BF16), ((n // block,), I32),
    )


def test_int8_weight_matmul(one_chip):
    from tony_tpu.ops.quant_mm import quant_matmul

    fn = partial(quant_matmul, impl="pallas")
    _compile(
        fn, one_chip,
        ((8, 4096), BF16), ((4096, 14336), I8), ((14336,), F32),
    )


# --- the whole train step of chip_smoke.py ------------------------------------


def _smoke_step_plan(devices, batch_rows):
    """Compile chip_smoke.py's train step (Llama-2-7B widths, depth 3, flash +
    save_attn_kernel remat + fused scan CE, bf16 first moment) on fit()'s
    default fsdp-first mesh over ``devices``; returns (memory plan, HLO)."""
    from tony_tpu.models.llama import LlamaConfig
    from tony_tpu.parallel.mesh import build_mesh, set_default_mesh
    from tony_tpu.train.trainer import (
        default_optimizer, make_train_step, train_state_avals,
    )

    cfg = replace(
        LlamaConfig.llama2_7b(), n_layers=3, attention_impl="flash",
        remat_policy="save_attn_kernel", ce_impl="scan",
    )
    mesh = build_mesh(devices=devices)
    set_default_mesh(mesh)
    try:
        opt = default_optimizer(
            warmup_steps=100, decay_steps=101, mu_dtype=jnp.dtype("bfloat16")
        )
        step = make_train_step(cfg, mesh, opt)
        batch = jax.ShapeDtypeStruct((batch_rows, 2048), I32)
        lowered = step.lower(train_state_avals(cfg, opt), batch, batch)
        assert "tpu_custom_call" in lowered.as_text()
        compiled = lowered.compile()
    finally:
        set_default_mesh(None)
    return compiled.memory_analysis(), compiled.as_text()


def _planned_bytes(mem) -> int:
    return (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )


def test_train_step_3_layer_7b_width_fits_one_chip(topo):
    """The step `chip_smoke.py` submits (batch 4 x 2048) must compile for one
    chip and plan under its 16 GB."""
    mem, _ = _smoke_step_plan([topo.devices[0]], batch_rows=4)
    assert 0 < _planned_bytes(mem) < HBM_BYTES, mem


def test_train_step_fsdp4_shards_the_state_over_the_2x2_mesh(topo):
    """`chip_smoke.py --chips 4`: the same step at batch 8 on the four
    described chips — each device plans a quarter of the one-chip state
    (arguments = parameters + optimizer) and the step holds all-gathers."""
    one, _ = _smoke_step_plan([topo.devices[0]], batch_rows=8)
    four, hlo = _smoke_step_plan(list(topo.devices), batch_rows=8)
    assert 0 < _planned_bytes(one) < HBM_BYTES, one  # the comparison fits too
    share = four.argument_size_in_bytes / one.argument_size_in_bytes
    assert 0.24 < share < 0.27, (share, four, one)
    assert "all-gather" in hlo


@pytest.mark.parametrize("lanes", [128, 1], ids=["rows-640", "rows-576"])
def test_latent_decode_step_at_published_widths_keeps_the_pool_in_place(one_chip, lanes,
                                                                        monkeypatch):
    """The latent-attention family's decode step at the serving cell's widths
    (2 expert layers are enough for the plan; 48 slots, the 3136-block pool):
    compiles for one chip, and with cache rows padded to the 128 lanes the
    pool is one buffer — no pool-shaped copy, temporaries far under a pool.
    With 576-wide rows the compiler moves the pool's block dimension
    minor-most and relays the whole pool out, which is why the rows are padded
    (models/latent_moe.py ``CACHE_LANES``, a constant: the unpadded case is
    made here by patching it); that case is pinned so that a compiler which
    stops doing it is noticed."""
    from tony_tpu.models import latent_moe
    from tony_tpu.models.latent_moe import LatentMoEConfig, init_params
    from tony_tpu.serve.cache import PagedKVCache
    from tony_tpu.serve.capacity import _state_avals
    from tony_tpu.serve.engine import _decode_fn

    monkeypatch.setattr(latent_moe, "CACHE_LANES", lanes)
    cfg = LatentMoEConfig(vocab_size=16160, n_layers=3, n_dense_layers=1, n_local_experts=16,
                          max_seq_len=4096)
    assert cfg.cache_width == (640 if lanes == 128 else 576)
    S, P = 48, 3136

    sds = partial(_described, one_chip=one_chip)
    params = sds(jax.eval_shape(partial(init_params, cfg=cfg), jax.random.key(0)))
    pool = jax.ShapeDtypeStruct((3, P, 1, 64, cfg.cache_width), BF16, sharding=one_chip)
    cache = PagedKVCache(pool, None, jax.ShapeDtypeStruct((S,), I32, sharding=one_chip))
    table = jax.ShapeDtypeStruct((S, 64), I32, sharding=one_chip)
    lowered = _decode_fn(cfg, "scan", 64, 64).lower(
        params, cache, table, sds(_state_avals(S)))
    assert "paged_decode_attention" in lowered.as_text()   # the kernel, in the layer scans
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    pool_bytes = 3 * P * 64 * cfg.cache_width * 2
    relaid = [l for l in compiled.as_text().splitlines()
              if f"bf16[3,{P},1,64,{cfg.cache_width}]" in l and " copy(" in l]
    if lanes == 128:
        assert not relaid, relaid[:2]
        assert mem.temp_size_in_bytes < pool_bytes // 4, mem
        assert mem.alias_size_in_bytes >= pool_bytes
    else:
        assert relaid and mem.temp_size_in_bytes > pool_bytes


# --- a layer's projection weights are read where they lie in the stack ---------


def _materialised(hlo: str):
    """``(opcode, result type, line)`` of every instruction of the compiled
    text that runs on its own — the entry, loop bodies and branches, not
    the computations a ``fusion`` calls (a ``dynamic-slice`` in there is the
    fusion reading its operand in place)."""
    fused = set(re.findall(r"fusion\(.*?calls=%([\w.\-]+)", hlo))
    inst = re.compile(r"\s*(?:ROOT )?%[\w.\-]+ = (.+?) ([\w\-]+)\(")
    here = None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            here = head.group(1)
        m = inst.match(line)
        if m and here not in fused:
            yield m.group(2), m.group(1), line.strip()


@pytest.mark.parametrize("case", ["dense-decode", "dense-prefill-512",
                                  "latent-decode", "latent-prefill-512"])
def test_projection_weights_are_read_in_place(one_chip, case):
    """The serving programs at the two cells' widths (Yi-1.5-6B with 4
    layers: 16 slots, pool 529, table 32; DeepSeek-V3 as in the test above:
    48 slots, pool 3,136): no layer's query weight — nor the dense key
    weight — is sliced out of its ``[L, ...]`` stack by a fusion of its own
    or copied (the transposition ``constant_dynamic-slice_fusion.4`` +
    ``copy.156 bf16[1,4096,4096]{1,2,0}`` that fusing ``h @ wq`` with the
    reshape and rope's split cost on every layer of every step: PERF.md §6
    PR 30; ``models/generate.layer`` and ``latent_moe.attention_inputs``
    keep the product apart). A same-layout ``copy-start``/``copy-done`` of
    the one leading dense layer's ``wq_b`` is a prefetch, not a relayout.
    What is LEFT is pinned as present, so that a compiler or a PR which
    removes it is noticed: ``wkv_b``, sliced and transposed a layer by the
    absorbed decode and by the expanded prefill alike
    (``bf16[1,512,32768]{1,2,0}``; PERF.md §7)."""
    from tony_tpu.serve.cache import create_cache
    from tony_tpu.serve.capacity import _state_avals
    from tony_tpu.serve.engine import _decode_fn, _prefill_fn

    family, program = case.split("-", 1)
    if family == "dense":
        from tony_tpu.models.llama import LlamaConfig, init_params

        cfg = LlamaConfig(vocab_size=64000, dim=4096, n_layers=4, n_heads=32, n_kv_heads=4,
                          ffn_dim=11008, max_seq_len=2048, rope_theta=5e6, norm_eps=1e-6,
                          dtype=BF16)
        S, P, M = 16, 529, 32
        weights = ("bf16[1,4096,4096]", "bf16[1,4096,512]")
    else:
        from tony_tpu.models.latent_moe import LatentMoEConfig, init_params

        cfg = LatentMoEConfig(vocab_size=16160, n_layers=3, n_dense_layers=1,
                              n_local_experts=16, max_seq_len=4096)
        S, P, M = 48, 3136, 64
        weights = ("bf16[1,1536,24576]",)

    sds = partial(_described, one_chip=one_chip)
    params = sds(jax.eval_shape(partial(init_params, cfg=cfg), jax.random.key(0)))
    if program == "decode":
        cache = sds(jax.eval_shape(partial(create_cache, cfg, S, P, 64)))
        lowered = _decode_fn(cfg, "scan", 64, 64).lower(
            params, cache, sds(jax.ShapeDtypeStruct((S, M), I32)), sds(_state_avals(S)))
    else:
        scalars = [jax.ShapeDtypeStruct(s, d) for s, d in (
            ((1, 512), I32), ((), I32), ((), F32), ((), I32), ((), F32), ((2,), jnp.uint32))]
        lowered = _prefill_fn(cfg, 512, 64).lower(params, *sds(scalars))
    ops = list(_materialised(lowered.compile().as_text()))
    assert any(op == "while" for op, _, _ in ops)          # the layer scan was walked
    relaid = [line for op, result, line in ops
              if result.startswith(weights) and op in ("copy", "fusion")]
    assert not relaid, relaid[:2]
    left = [line for op, result, line in ops
            if op == "copy" and result.startswith("bf16[1,512,32768]{1,2,0")]
    assert bool(left) == (family == "latent"), left[:2]


# --- the short-convolution family's decode step keeps both kinds of state in place ----


@pytest.mark.parametrize("lanes", [128, 1], ids=["rows-128", "rows-64"])
def test_shortconv_decode_step_at_published_widths_keeps_pool_and_state_in_place(
        one_chip, lanes, monkeypatch):
    """The short-convolution / attention hybrid's decode step at the serving
    cell's widths (the first 14 published layers: 11 convolution, 3 attention,
    12 expert layers of 32; 64 slots, a pool of 2,049 blocks of 64, a table of
    32): compiles for one chip with the paged kernel in it, and with two
    64-wide K/V heads side by side in a 128-lane row the pool is one buffer —
    no pool-shaped copy, temporaries far under a pool, pool AND per-slot
    state aliased from the donated argument to the result. With rows of 64
    the compiler moves the pool's block dimension minor-most and relays the
    whole pool in and out, four pool-sized copies a step, which is why the
    heads are packed (models/shortconv_moe.py ``kv_pack``; the unpacked case
    is made here by patching the lanes); that case is pinned so that a
    compiler which stops doing it is noticed."""
    from tony_tpu.models import shortconv_moe as sm
    from tony_tpu.serve.cache import create_cache, slot_state_bytes
    from tony_tpu.serve.capacity import _state_avals
    from tony_tpu.serve.engine import _decode_fn

    monkeypatch.setattr(sm, "CACHE_LANES", lanes)
    cfg = sm.ShortConvMoEConfig(layer_types=sm.PUBLISHED_LAYER_TYPES[:14], max_seq_len=2048)
    assert cfg.cache_layout == ((4, 128, 2) if lanes == 128 else (8, 64, 2))
    S, M = 64, 32
    P = 1 + S * M
    sds = partial(_described, one_chip=one_chip)
    params = sds(jax.eval_shape(partial(sm.init_params, cfg=cfg), jax.random.key(0)))
    cache = sds(jax.eval_shape(partial(create_cache, cfg, S, P, 64)))
    assert cache.k.shape[0] == 3 and cache.slot_state.shape == (11, S, 4096)
    lowered = _decode_fn(cfg, "scan", 64, 64).lower(
        params, cache, sds(jax.ShapeDtypeStruct((S, M), I32)), sds(_state_avals(S)))
    assert "paged_decode_attention" in lowered.as_text()
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    pool_bytes = 2 * 3 * P * 8 * 64 * 64 * 2
    heads, width, _ = cfg.cache_layout
    relaid = [l for l in compiled.as_text().splitlines()
              if f"bf16[3,{P},{heads},64,{width}]" in l and " copy(" in l]
    if lanes == 128:
        assert not relaid, relaid[:2]
        assert mem.temp_size_in_bytes < pool_bytes // 8, mem
        assert mem.alias_size_in_bytes >= pool_bytes + slot_state_bytes(cfg, S)
        # 4.667 B parameters beside the pool: the program fits the chip
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    else:
        assert relaid and mem.temp_size_in_bytes > pool_bytes


# --- the other families' serving programs are the programs they were ----------------------

# (flops, bytes accessed, temporaries, instructions of the compiled text) as PR 36 left
# them: the parent of PR 33 (commit 69de200), which moved the layer walk into
# models/layer_walk.py and added a family to ``steps_for`` (PERF.md section 6's method,
# PR 29), with the sampler branched on whether a row samples (a ``conditional`` whose
# wide branch the cost analysis counts; PERF.md section 6, PR 36)
_PROGRAMS_OF_PR_36 = {
    "dense.decode": (14147382272, 920770560, 2354688, 1847),
    "dense.prefill512": (183018061824, 1227668480, 838144, 1338),
    "latent.decode": (94018191360, 3444212736, 8371200, 3886),
    "latent.prefill512": (853507637248, 8985347072, 169490944, 3522),
    "shortconv.decode": (44899729408, 1304457728, 7148032, 7963),
    "shortconv.prefill512": (171656413184, 5460696064, 35661824, 12478),
}


@pytest.mark.parametrize("case", sorted(_PROGRAMS_OF_PR_36))
def test_the_other_families_serving_programs_are_unchanged(one_chip, case):
    """The decode step and a 512 prefill of the dense (Yi-1.5-6B widths, 4
    layers), latent (DeepSeek-V3 widths, 3 layers) and short-convolution
    (LFM2's 14 layers) families, compiled for the described chip: the same
    operations, bytes, temporaries and instruction count as before a fourth
    family was added beside them, but for the sampler's branch."""
    from tony_tpu.serve.cache import create_cache
    from tony_tpu.serve.capacity import _state_avals
    from tony_tpu.serve.engine import _decode_fn, _prefill_fn

    family, program = case.split(".")
    if family == "dense":
        from tony_tpu.models.llama import LlamaConfig, init_params

        cfg = LlamaConfig(vocab_size=64000, dim=4096, n_layers=4, n_heads=32, n_kv_heads=4,
                          ffn_dim=11008, max_seq_len=2048, rope_theta=5e6, norm_eps=1e-6,
                          dtype=BF16)
        S, P, M = 16, 529, 32
    elif family == "latent":
        from tony_tpu.models.latent_moe import LatentMoEConfig, init_params

        cfg = LatentMoEConfig(vocab_size=16160, n_layers=3, n_dense_layers=1,
                              n_local_experts=16, max_seq_len=4096)
        S, P, M = 48, 3136, 64
    else:
        from tony_tpu.models import shortconv_moe as sm
        from tony_tpu.models.shortconv_moe import init_params

        cfg = sm.ShortConvMoEConfig(layer_types=sm.PUBLISHED_LAYER_TYPES[:14], max_seq_len=2048)
        S, P, M = 64, 2049, 32
    sds = partial(_described, one_chip=one_chip)
    params = sds(jax.eval_shape(partial(init_params, cfg=cfg), jax.random.key(0)))
    if program == "decode":
        cache = sds(jax.eval_shape(partial(create_cache, cfg, S, P, 64)))
        lowered = _decode_fn(cfg, "scan", 64, 64).lower(
            params, cache, sds(jax.ShapeDtypeStruct((S, M), I32)), sds(_state_avals(S)))
    else:
        scalars = [jax.ShapeDtypeStruct(s, d) for s, d in (
            ((1, 512), I32), ((), I32), ((), F32), ((), I32), ((), F32), ((2,), jnp.uint32))]
        lowered = _prefill_fn(cfg, 512, 64).lower(params, *sds(scalars))
    compiled = lowered.compile()
    cost, mem = compiled.cost_analysis(), compiled.memory_analysis()
    instructions = len(re.findall(r" = \S+ ([a-z][\w\-]*)\(", compiled.as_text()))
    assert (int(cost["flops"]), int(cost["bytes accessed"]), mem.temp_size_in_bytes,
            instructions) == _PROGRAMS_OF_PR_36[case]


# --- the state-space family: a 1.3 GB per-slot state touched in place ------------------

_SSM_SLOTS, _SSM_TABLE = 128, 80          # the serving cell: 128 slots, max_len 5120 / 64


def _ssm_cell(one_chip):
    """The state-space / attention hybrid at the serving cell's shapes, as
    shapes on the described chip: ``(cfg, params, cache)`` — all 28 published
    layers, 128 slots, a pool of 10,241 blocks of 64 for the 2 attention
    layers, the recurrent state ``[26 x 19, 128, 5120]`` float32 (1.295 GB)."""
    from tony_tpu.models.ssm_hybrid import SSMHybridConfig, init_params
    from tony_tpu.serve.cache import create_cache

    cfg = SSMHybridConfig(max_seq_len=5120)
    sds = partial(_described, one_chip=one_chip)
    params = sds(jax.eval_shape(partial(init_params, cfg=cfg), jax.random.key(0)))
    pool = 1 + _SSM_SLOTS * _SSM_TABLE
    cache = sds(jax.eval_shape(partial(create_cache, cfg, _SSM_SLOTS, pool, 64)))
    assert cache.k.shape == (2, pool, 1, 64, 128)
    assert cache.slot_state.shape == (26 * 19, _SSM_SLOTS, 5120)
    return cfg, params, cache


def _state_copies(hlo: str) -> list[str]:
    """Instructions of the compiled text that copy or relay a buffer of the
    whole recurrent state's shape."""
    pattern = re.compile(r"= \(?f32\[494,128,5120\]\S* (copy|copy-start|transpose)\(")
    return [l.strip()[:160] for l in hlo.splitlines() if pattern.search(l)]


@pytest.mark.parametrize("program", ["decode", "scatter", "zero_slot_state", "slot_state"])
def test_ssm_hybrid_state_programs_hold_no_copy_of_the_state(one_chip, program):
    """The four programs that touch the state-space family's per-slot state
    at the serving cell's 128 slots (1.295 GB, a sixth of what the chip
    holds beside 6.06 GB of weights): ``jit_serve_decode`` carries it
    through the layer walk and rewrites a layer's rows where they lie (ONE
    dynamic-update-slice a Mamba layer: a second update that read the buffer
    after the first made the compiler copy the whole state every layer);
    ``jit_serve_scatter`` writes a prefill's state into one slot,
    ``jit_serve_zero_slot_state`` resets one slot, ``jit_serve_slot_state``
    reads one — none holds a copy, a relayout or a temporary of the state's
    size, the donated state comes back in the buffer it came in, and the
    paged kernel is in the decode step at 20 query rows to ONE K/V head. The
    state's leading index is (layer, state row) and its rows are ``[slots,
    5120]``: as ``[layers, slots, 19, 5120]`` the compiler relaid it
    slot-minor in and out of every scanned run (six state-sized copies a
    step, 1.3 GB of temporaries: PERF.md §6, PR 33)."""
    from tony_tpu.serve.cache import slot_state_bytes
    from tony_tpu.serve.capacity import _state_avals
    from tony_tpu.serve.engine import (
        _decode_fn, _scatter_fn, _slot_state_fn, _zero_slot_state_fn,
    )

    cfg, params, cache = _ssm_cell(one_chip)
    sds = partial(_described, one_chip=one_chip)
    state_bytes = slot_state_bytes(cfg, _SSM_SLOTS)
    assert state_bytes == 1_294_991_360
    i32 = lambda *shape: sds(jax.ShapeDtypeStruct(shape, I32))  # noqa: E731
    if program == "decode":
        lowered = _decode_fn(cfg, "scan", 64, 64).lower(
            params, cache, i32(_SSM_SLOTS, _SSM_TABLE), sds(_state_avals(_SSM_SLOTS)))
        assert "paged_decode_attention" in lowered.as_text()
    elif program == "scatter":
        rows = sds(jax.ShapeDtypeStruct((2, 1, 512, 128), BF16))
        handed = sds(jax.ShapeDtypeStruct((26 * 19, 5120), F32))
        lowered = _scatter_fn().lower(cache, rows, rows, i32(512), i32(512), i32(), i32(), handed)
    elif program == "zero_slot_state":
        lowered = _zero_slot_state_fn().lower(cache.slot_state, i32())
    else:
        lowered = _slot_state_fn().lower(cache.slot_state, i32())
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert not _state_copies(compiled.as_text())
    # what is left of temporaries is a pool's (the scatter's known pool copies, PERF.md §7)
    pool_bytes = cache.k.size * 2
    assert mem.temp_size_in_bytes < max(state_bytes // 8, pool_bytes + pool_bytes // 8), mem
    if program != "slot_state":
        assert mem.alias_size_in_bytes >= state_bytes, mem
    else:
        assert mem.output_size_in_bytes < state_bytes // _SSM_SLOTS * 2
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("bucket", [512, 1280])
def test_ssm_hybrid_prefill_holds_the_selective_scan_kernel(one_chip, bucket):
    """A prefill of the state-space family at the cell's widths compiles for
    one chip with the ``selective_scan`` kernel in its layer scans (grid: 10
    channel blocks of 512 x chunks of 256 positions), and fits."""
    from tony_tpu.serve.engine import _prefill_fn

    cfg, params, _ = _ssm_cell(one_chip)
    scalars = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((1, bucket), I32), ((), I32), ((), F32), ((), I32), ((), F32), ((2,), jnp.uint32))]
    lowered = _prefill_fn(cfg, bucket, 64).lower(params, *_described(scalars, one_chip))
    text = lowered.as_text()
    assert "selective_scan" in text and "tpu_custom_call" in text
    mem = lowered.compile().memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    assert mem.temp_size_in_bytes < 2**30, mem


@pytest.mark.parametrize("T,dtype", [(64, BF16), (640, BF16), (2048, BF16), (256, F32)])
def test_selective_scan_kernel(one_chip, T, dtype):
    """The kernel alone at the published widths (5120 channels, 16 state
    rows) for the shortest, an odd and the longest bucket."""
    from tony_tpu.ops.selective_scan import selective_scan

    E, N = 5120, 16
    _compile(selective_scan, one_chip, ((T, E), dtype), ((T, E), F32), ((T, N), dtype),
             ((T, N), dtype), ((T, E), dtype), ((N, E), F32), ((E,), F32), ((N, E), F32))


@pytest.mark.parametrize("program", ["serve_activate", "serve_release"])
def test_slot_transition_programs_update_the_state_in_place(one_chip, program):
    """The two programs the serving host runs at a slot's hand-over and
    return (64 slots, the widest cell's): each compiles for one chip and
    returns the donated slot state (and the cache's ``lengths``) in the
    buffers it came in — aliased, every byte of them (the chip pads a
    buffer to its tile, so the aliased bytes are at least the arrays')."""
    from tony_tpu.serve.capacity import _state_avals
    from tony_tpu.serve.engine import _activate_fn, _release_fn

    S = 64
    sds = partial(_described, one_chip=one_chip)
    scalar = lambda dtype: sds(jax.ShapeDtypeStruct((), dtype))  # noqa: E731
    state = sds(_state_avals(S))
    donated = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
    if program == "serve_activate":
        lowered = _activate_fn().lower(
            state, scalar(I32), scalar(I32), sds(jax.ShapeDtypeStruct((2,), jnp.uint32)),
            scalar(F32), scalar(I32), scalar(F32), scalar(I32))
    else:
        lowered = _release_fn().lower(
            state, sds(jax.ShapeDtypeStruct((S,), I32)), scalar(I32))
        donated += S * 4
    assert f"module @jit_{program}" in lowered.as_text()
    mem = lowered.compile().memory_analysis()
    assert mem.alias_size_in_bytes >= donated, mem
