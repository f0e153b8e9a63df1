"""The traffic generators reproduce exactly from a seed, and every seed
gets the same amount of work."""

import numpy as np
import pytest

from bench import gen
from bench.tests.tiny import BENCH_DIR

import json
import os

TRAFFIC = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, "traffic")) if f.endswith(".json"))


def spec(name):
    with open(os.path.join(BENCH_DIR, "traffic", f"{name}.json")) as f:
        return json.load(f)


def fingerprint(reqs):
    return [(r.rid, round(r.due_s, 9), r.max_new, r.prompt.tobytes()) for r in reqs]


@pytest.mark.parametrize("name", TRAFFIC)
def test_same_seed_same_requests(name):
    seed = 2**31 + 12345  # above 32 signed bits
    a = gen.generate(spec(name), seed, 40.0, 122_753)
    b = gen.generate(spec(name), seed, 40.0, 122_753)
    assert a and fingerprint(a) == fingerprint(b)


@pytest.mark.parametrize("name", TRAFFIC)
def test_seeds_differ_in_tokens_not_in_work(name):
    s = spec(name)
    a = gen.generate(s, 1, 40.0, 1000)
    b = gen.generate(s, 2, 40.0, 1000)
    assert fingerprint(a) != fingerprint(b)
    assert [(len(r.prompt), r.max_new, r.due_s) for r in a] == [(len(r.prompt), r.max_new, r.due_s) for r in b]
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 1000 for r in a)


def test_open_fills_the_window_at_the_rate():
    s = spec("chat")
    reqs = gen.generate(s, 7, 40.0, 100)
    assert len(reqs) == round(s["rate_rps"] * 40)
    assert reqs[0].due_s == 0.0 and max(r.due_s for r in reqs) < 40.0
    assert all(s["prompt"]["min"] <= len(r.prompt) <= s["prompt"]["max"] for r in reqs)


def test_unknown_kind_raises():
    with pytest.raises(ValueError):
        gen.generate({"kind": "nope"}, 0, 1.0, 10)
