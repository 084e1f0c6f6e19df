"""The weights drawn in one call equal the reference's per-layer draws."""
import json
import os

import jax
import numpy as np

from bench import archs
from bench import weights as W
from bench.tests.util import DATA

SEED = 2**33 + 7


def test_layer_and_top_draws_match_the_whole_tree():
    with open(os.path.join(DATA, "tiny.json")) as f:
        c = json.load(f)
    layout = archs.load(c).layout
    tree = W.make_params(c, SEED, "bfloat16", layout)
    for i in range(c["num_hidden_layers"]):
        one = W.layer_params(c, SEED, "bfloat16", i, layout)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a[i]), np.asarray(b)), tree["layers"], one)
    top = W.top_params(c, SEED, "bfloat16", layout)
    for k, v in top.items():
        np.testing.assert_array_equal(np.asarray(tree[k]), np.asarray(v))
    other = W.make_params(c, SEED + 1, "bfloat16", layout)
    assert not np.array_equal(np.asarray(tree["embed"]),
                              np.asarray(other["embed"]))
    assert str(tree["lm_head"].dtype) == "bfloat16"
