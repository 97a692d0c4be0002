"""The traffic generator: clips, seed-determinism, one schedule for all seeds."""

import json
import os
import statistics

import numpy as np

from benchmark import trafficgen

HERE = os.path.dirname(os.path.abspath(__file__))


def mix(name):
    with open(os.path.join(HERE, "..", "traffic", name + ".json")) as f:
        return json.load(f)


def test_lengths_clipped_and_fit_the_engine():
    m = mix("chat-closed16")
    sizes = trafficgen.request_sizes(m)
    assert len(sizes) == m["distinct"]
    assert all(32 <= p <= 1536 and 16 <= o <= 448 for p, o in sizes)
    assert max(p + o for p, o in sizes) <= 1984
    prompts = sorted(p for p, _ in sizes)
    assert abs(statistics.median(prompts) - 384) <= 8  # the law's median
    assert prompts[0] == 32 or prompts[-1] == 1536     # a clip is reached


def test_same_seed_same_traffic_other_seed_same_work():
    m = mix("chat-closed16")
    big = 2**31 + 12345
    # sizes, their order and the callers' head start are the mix's, not the seed's
    assert trafficgen.request_sizes(m) == trafficgen.request_sizes(m)
    other = trafficgen.request_sizes(dict(m, schedule_seed=1))
    assert other != trafficgen.request_sizes(m)
    for column in (0, 1):  # another pairing and order of the same lengths
        assert sorted(x[column] for x in other) == sorted(x[column] for x in trafficgen.request_sizes(m))
    cuts = trafficgen.head_start(m, 16)
    assert (cuts == trafficgen.head_start(m, 16)).all() and cuts.min() >= 0.05 and cuts.max() <= 1.0
    a = trafficgen.Prompts(m, big, 64000).make(3, 100)
    assert (a == trafficgen.Prompts(m, big, 64000).make(3, 100)).all()
    assert (a != trafficgen.Prompts(m, 7, 64000).make(3, 100)).any()
    assert a.dtype == np.int32 and a.min() >= 0 and a.max() < 64000


def test_shared_prefix_and_open_loop_parameters():
    m = dict(mix("chat-closed16"), shared_prefix={"share": 1.0, "len": 40, "pool": 1})
    p = trafficgen.Prompts(m, 5, 1000)
    assert (p.make(0, 100)[:40] == p.make(1, 64)[:40]).all()
    t = trafficgen.arrivals({"rate_rps": 50.0, "burst": 4}, 3, 10.0)
    assert len(t) % 4 == 0 and 300 < len(t) < 700 and (np.diff(t) >= 0).all() and t[-1] < 10.0
    assert (t == trafficgen.arrivals({"rate_rps": 50.0, "burst": 4}, 3, 10.0)).all()


def test_train_tokens():
    m = mix("pretrain-b4s2048")
    tok = trafficgen.train_tokens(dict(m, token_file_steps=3), 2**31 + 9, 32768)
    assert tok.shape == (3, 4, 2049) and tok.dtype == np.int32
    assert tok.min() >= 0 and tok.max() < 32768
    assert len({row.tobytes() for step in tok for row in step}) == 12  # rows all differ
    assert (tok == trafficgen.train_tokens(dict(m, token_file_steps=3), 2**31 + 9, 32768)).all()
