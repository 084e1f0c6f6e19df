"""The traffic generator: fixed work per traffic file, tokens per seed."""
import json
import os

import numpy as np
import pytest

from bench import loadgen
from bench.tests.util import BENCH, DATA

SEED = 2**31 + 977  # the driver's seeds pass 32 signed bits


def traffic(name):
    for d in (os.path.join(BENCH, "traffic"), DATA):
        p = os.path.join(d, name + ".json")
        if os.path.exists(p):
            with open(p) as f:
                return json.load(f)
    raise FileNotFoundError(name)


def plan(name, seed, seconds=51.0):
    return loadgen.make_plan(traffic(name), seed, seconds, 1000)


@pytest.mark.parametrize("name", ["qwen15-4b.chat", "internlm2-20b-s12.decode",
                                  "qwen15-4b.chat-x4", "tiny.chat",
                                  "tiny.decode", "tiny.chat-x4"])
def test_same_seed_same_requests(name):
    a, b = plan(name, SEED), plan(name, SEED)
    assert len(a.requests) == len(b.requests)
    for x, y in zip(a.requests, b.requests):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.gen, x.at, x.in_window) == (y.gen, y.at, y.in_window)


@pytest.mark.parametrize("name", ["qwen15-4b.chat", "internlm2-20b-s12.decode",
                                  "qwen15-4b.chat-x4"])
def test_other_seed_other_tokens_same_work(name):
    a, b = plan(name, SEED), plan(name, SEED + 1)
    assert [(r.gen, len(r.prompt), r.at) for r in a.requests] == \
        [(r.gen, len(r.prompt), r.at) for r in b.requests]
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a.requests, b.requests))


def test_open_loop_counts_and_bounds():
    t = traffic("qwen15-4b.chat")
    p = plan("qwen15-4b.chat", SEED, seconds=51.0)
    inside = [r for r in p.requests if r.in_window]
    assert len(inside) == max(1, round(t["rate_rps"] * 51.0))
    assert all(t["ramp_s"] <= r.at < t["ramp_s"] + 51.0 for r in inside)
    assert all(r.at < t["ramp_s"] for r in p.requests if not r.in_window)
    ats = [r.at for r in p.requests]
    assert ats == sorted(ats)
    for r in p.requests:
        assert t["prompt_len"]["min"] <= len(r.prompt) <= t["prompt_len"]["max"]
        assert t["output_len"]["min"] <= r.gen <= t["output_len"]["max"]
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 1000


def test_closed_loop_pool():
    t = traffic("internlm2-20b-s12.decode")
    p = plan("internlm2-20b-s12.decode", SEED)
    assert p.loop == "closed" and p.clients == t["clients"]
    assert len(p.requests) == t["pool"]
    assert all(r.at is None for r in p.requests)


def test_lognormal_median_and_clip():
    rng = np.random.default_rng(0)
    x = loadgen.draw_lengths({"dist": "lognormal", "median": 160, "sigma": 0.7,
                              "min": 32, "max": 512}, 20000, rng)
    assert 150 <= np.median(x) <= 170
    assert x.min() >= 32 and x.max() <= 512
