"""``archs/dense.py`` gives, for both shipped configurations, the layout,
``program_config`` fields and operation counts that the benchmark's dense
code gave before it moved into the module (``data/dense_golden.json``, written
by that code)."""
import dataclasses
import json
import os

import pytest

from bench import archs
from bench.tests.util import BENCH, DATA

with open(os.path.join(DATA, "dense_golden.json")) as f:
    GOLDEN = json.load(f)


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def plain(v):
    return v if isinstance(v, (int, float, str, bool, type(None))) else repr(v)


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_layout(name):
    c = config(name)
    got = {k: [list(s), kind, std]
           for k, (s, kind, std) in archs.load(c).layout(c).items()}
    assert got == GOLDEN[name]["layout"]


@pytest.mark.parametrize("name", sorted(GOLDEN))
@pytest.mark.parametrize("cache_dtype", ["", "float8_e4m3fn"])
def test_program_config(name, cache_dtype):
    c = config(name)
    cfg = archs.load(c).program_config(c, cache_dtype)
    got = {k: plain(v) for k, v in dataclasses.asdict(cfg).items()}
    assert got == GOLDEN[name]["program_config"][cache_dtype or "default"]


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_flops(name):
    c = config(name)
    arch = archs.load(c)
    for p, want in GOLDEN[name]["prefill_flops"].items():
        assert arch.prefill_flops(c, int(p)) == want
    for k, want in GOLDEN[name]["decode_flops"].items():
        assert arch.decode_flops(c, float(k) if "." in k else int(k)) == want
