"""flops.py against numbers worked by hand for both configurations."""

import json
import os

import pytest

from benchmark import flops, weights

HERE = os.path.dirname(os.path.abspath(__file__))


def sizes(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return weights.sizes_of(json.load(f))


def test_mistral_train_flops_by_hand():
    s = sizes("mistral-7b-v0.3-train4")
    # per layer: wq 4096*4096, wk and wv 4096*1024 each, wo 4096*4096, three 4096*14336
    layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert flops.matmul_params(s) == 4 * layer + 4096 * 32768 == 1_006_632_960
    assert weights.n_matmul_params(s) == flops.matmul_params(s)
    assert weights.n_params(s) == 1_006_632_960 + 32768 * 4096 + 9 * 4096 == 1_140_887_552
    # causal attention forward, per token: 2 products x 2 x hd x heads x (2048/2) keys x 4 layers
    attn_fwd = 4 * 4 * 32 * 128 * 1024
    assert flops.attn_flops_fwd(s, 1, 1024) == attn_fwd == 67_108_864
    assert flops.train_flops_per_token(s, 2048) == 6 * 1_006_632_960 + 3 * attn_fwd
    assert flops.train_flops_per_token(s, 2048) == pytest.approx(6.241e9, rel=1e-3)


def test_yi_decode_step_by_hand():
    s = sizes("yi-1.5-6b-serve")
    layer = 4096 * 4096 * 2 + 2 * 4096 * 512 + 3 * 4096 * 11008
    assert flops.matmul_params(s) == 32 * layer + 4096 * 64000 == 5_798_625_280
    assert weights.n_params(s) == 5_798_625_280 + 64000 * 4096 + 65 * 4096 == 6_061_035_520
    assert flops.kv_bytes_per_token(s) == 32 * 2 * 4 * 128 * 2 == 65536
    cost = flops.decode_step_cost(s, [100, 300])
    assert cost["bytes"] == (5_798_625_280 + 65 * 4096) * 2 + 65536 * 400
    assert cost["flops"] == 2 * 5_798_625_280 * 2 + 4 * 32 * 32 * 128 * 400
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = flops.least_seconds(cost, peak)
    assert bound == "bandwidth" and least == pytest.approx(cost["bytes"] / 819e9)
    assert least == pytest.approx(14.19e-3, rel=1e-2)


def test_flash_cost_and_serve_flops():
    s = sizes("mistral-7b-v0.3-train4")
    c = flops.flash_attn_cost(s, batch=4, seq=2048)
    pairs = 32 * 128 * 4 * 2048 * 1024  # heads x hd x rows x queries x mean keys
    assert c["flops"] == 4 * 7 * 2 * pairs
    qo, kv = 4 * 2048 * 32 * 128 * 2, 4 * 2048 * 8 * 128 * 2
    assert c["bytes"] == 4 * ((2 * qo + 2 * kv) + (4 * qo + 4 * kv))
    assert flops.least_seconds(c, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})[1] == "compute"
    y = sizes("yi-1.5-6b-serve")
    need = flops.serve_flops(y, [10], [11, 12])
    assert need == 2 * 5_798_625_280 * 12 + 4 * 32 * 32 * 128 * (10 * 5 + 23)
