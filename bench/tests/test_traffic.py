"""The traffic generator: seeded, clipped, and the same work per seed."""
import json
import os

import numpy as np
import pytest

from bench import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# long-document summarization and QA: the generator on a mix with no
# file of its own yet
DOCS = {"arrival": {"kind": "poisson"}, "schedule_seed": 0,
        "rate_share_of_knee": 1.5,
        "prompt_tokens": {"kind": "lognormal", "median": 1024, "sigma": 0.4,
                          "min": 512, "max": 2048},
        "output_tokens": {"kind": "lognormal", "median": 96, "sigma": 0.5,
                          "min": 32, "max": 256},
        "serving": {"n_slots": 8, "block_size": 128, "view_bucket": 2}}


def mix(name):
    if name == "docs":
        return dict(DOCS)
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chat", "docs"])
def test_same_seed_same_schedule(name):
    m, cell = mix(name), {"knee_req_s": 1.3}
    a = traffic.generate(m, cell, 64000, 2**31 + 17, 51)
    b = traffic.generate(m, cell, 64000, 2**31 + 17, 51)
    assert [(r.arrival, r.max_new_tokens) for r in a] == \
        [(r.arrival, r.max_new_tokens) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # another seed: the same sizes at the same times, other tokens
    c = traffic.generate(m, cell, 64000, 2**31 + 18, 51)
    assert [(r.arrival, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.arrival, len(r.prompt), r.max_new_tokens) for r in c]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", ["chat", "docs"])
def test_lengths_clipped_arrivals_in_window(name):
    m = mix(name)
    reqs = traffic.generate(m, {"knee_req_s": 2.0}, 49152, 5, 51)
    assert len(reqs) == int(2.0 * m["rate_share_of_knee"] * 51)
    p, o = m["prompt_tokens"], m["output_tokens"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in reqs)
    assert all(o["min"] <= r.max_new_tokens <= o["max"] for r in reqs)
    t = [r.arrival for r in reqs]
    assert t[0] == 0.0 and t == sorted(t) and t[-1] < 51
    assert all(0 <= r.prompt.min() and r.prompt.max() < 49152 for r in reqs)


def test_every_seed_offers_the_same_work():
    m = mix("chat")
    a = traffic.generate(m, {"knee_req_s": 1.0}, 64000, 1, 51)
    b = traffic.generate(m, {"knee_req_s": 1.0}, 64000, 99, 51)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in b)


def test_shapes_cover_every_prompt_and_view():
    m = mix("chat")
    sh = traffic.shapes(m)
    assert sh["prefill_tokens"] == [64, 128, 192, 256, 320, 384, 448, 512]
    # contexts up to 512 + 128 - 1 = 639 positions: 10 blocks of 64
    assert sh["view_blocks"] == [2, 4, 6, 8, 10]
    assert traffic.max_context(m) == 640
