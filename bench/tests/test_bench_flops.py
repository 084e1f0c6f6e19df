"""Useful operations from shapes (the dense architecture module's), against
parameter counts, and the table of peaks."""
import json
import math
import os

import pytest

from bench import archs, flops
from bench.tests.util import BENCH

dense = archs.load({"program": {"bench_arch": "dense"}})


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def matmul_params(c):
    """Every drawn weight matrix but the embedding (a gather, not a product)."""
    n = 0
    for name, (shape, kind, _) in dense.layout(c).items():
        if kind != "normal" or name == "embed" or name.endswith(("bq", "bk", "bv")):
            continue
        size = math.prod(shape)
        n += size * (c["num_hidden_layers"] if name.startswith("layers/") else 1)
    return n


@pytest.mark.parametrize("name", ["qwen15-4b", "internlm2-20b-s12"])
def test_decode_is_two_per_matmul_param_plus_attention(name):
    c = config(name)
    mm = matmul_params(c)
    h = c["num_attention_heads"]
    hd = c["hidden_size"] // h
    for ctx in (1, 300, 1024):
        want = 2 * mm + 4 * c["num_hidden_layers"] * h * hd * ctx
        assert dense.decode_flops(c, ctx) == want


def test_published_sizes():
    # qwen1.5-4b: 3.95 B parameters, 2.0 B of them in products... per token
    # about 7.9 GFLOP; internlm2-20b at 12 layers about 10.5 GFLOP.
    assert dense.decode_flops(config("qwen15-4b"), 1) == pytest.approx(
        7.1e9, rel=0.05)
    assert dense.decode_flops(config("internlm2-20b-s12"), 1) == \
        pytest.approx(10.5e9, rel=0.05)


def test_prefill_counts_the_head_once_and_causal_attention():
    c = config("internlm2-20b-s12")
    p = 100
    layers = c["num_hidden_layers"] * dense.layer_matmul_params(c)
    head = c["hidden_size"] * c["vocab_size"]
    attn = sum(dense.attention_flops(c, k) for k in range(1, p + 1))
    assert dense.prefill_flops(c, p) == 2 * layers * p + 2 * head + attn


def test_peaks_known_and_unknown():
    v5e = flops.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v99")
