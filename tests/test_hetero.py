"""Heterogeneous trainer + gradient compression (straggler mitigation path)."""
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.core.device import DeviceGroup
from repro.data import SyntheticTokens
from repro.models import get_model
from repro.models import params as P
from repro.train import make_train_step, state_spec
from repro.train.compression import ErrorFeedback, compress_tree, decompress_tree
from repro.train.hetero import HeteroTrainer


def build():
    cfg = reduced(get_config("granite-34b"))
    api = get_model(cfg)
    sspec = state_spec(cfg, api.param_spec(cfg, 1))
    state = P.materialize(sspec, jax.random.PRNGKey(0), jnp.float32)
    return cfg, api, state


def batch_of(cfg, b=8, s=16, seed=0):
    rng = np.random.default_rng(seed)
    return {"tokens": rng.integers(0, cfg.vocab, (b, s)).astype(np.int32)}


# Same schedule the SPMD loss-decrease test uses: the default warmup (100
# steps) keeps lr ~1e-5 over a 12-step test, far too small to observe
# learning.
LR = {"peak": 1e-3, "warmup": 5, "decay_steps": 10_000}


def test_hetero_single_group_matches_spmd_step():
    cfg, api, state = build()
    state2 = jax.tree_util.tree_map(jnp.copy, state)
    batch = batch_of(cfg)
    trainer = HeteroTrainer(cfg, api, [DeviceGroup("solo")])
    s_h, m_h = trainer.step(state, batch)
    s_s, m_s = jax.jit(make_train_step(cfg, api))(state2, {k: jnp.asarray(v) for k, v in batch.items()})
    assert abs(float(m_h["loss"]) - float(m_s["loss"])) < 1e-5
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), s_h["params"], s_s["params"]
    )
    assert max(jax.tree_util.tree_leaves(diffs)) < 1e-5


def test_hetero_multi_group_loss_decreases():
    cfg, api, state = build()
    groups = [
        DeviceGroup("fast", power=2.0),
        DeviceGroup("slow", power=1.0, sim_time_per_wi=2e-3),
    ]
    trainer = HeteroTrainer(cfg, api, groups, lr_kwargs=LR)
    losses = []
    # Learnable (Zipf-skewed) tokens, as in test_train: uniform-random data
    # sits at the entropy floor and cannot show a decrease.
    for _, batch in zip(range(16), SyntheticTokens(cfg, 8, 16, seed=3)):
        state, m = trainer.step(state, batch)
        losses.append(m["loss"])
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


def test_straggler_share_shrinks():
    """A pod that slows down must receive a smaller share next steps."""
    cfg, api, state = build()
    fast = DeviceGroup("fast", power=1.0, sim_time_per_wi=1e-4)
    slow = DeviceGroup("slow", power=1.0, sim_time_per_wi=8e-3)  # 80x straggler
    trainer = HeteroTrainer(cfg, api, [fast, slow])
    shares = []
    for i in range(6):
        state, m = trainer.step(state, batch_of(cfg, b=16, seed=i))
        shares.append(m["shares"])
    assert shares[-1][0] > shares[0][0], f"fast share should grow: {shares}"
    assert shares[-1][1] < shares[0][1], f"slow share should shrink: {shares}"


def test_partition_covers_batch_exactly():
    cfg, api, _ = build()
    trainer = HeteroTrainer(cfg, api, [DeviceGroup(f"g{i}", power=p) for i, p in
                                       enumerate([1.0, 2.5, 4.0])])
    for b in (3, 8, 17, 64):
        shares = trainer.partition(b)
        assert sum(shares) == b
        assert all(s >= 1 for s in shares)


# ------------------------------------------------------------ compression


@given(st.lists(st.floats(-100, 100, width=32), min_size=1, max_size=64))
@settings(max_examples=50, deadline=None)
def test_quantize_bounded_error(vals):
    g = {"w": jnp.asarray(np.array(vals, np.float32))}
    deq = decompress_tree(compress_tree(g))
    scale = max(abs(np.array(vals)).max(), 1e-12) / 127.0
    err = np.abs(np.asarray(deq["w"]) - np.array(vals, np.float32)).max()
    assert err <= scale * 0.5 + 1e-6


def test_error_feedback_converges_in_mean():
    """Sum of compressed grads over steps tracks sum of true grads."""
    ef = ErrorFeedback()
    rng = np.random.default_rng(0)
    true_sum = np.zeros(32, np.float32)
    comp_sum = np.zeros(32, np.float32)
    for _ in range(200):
        g = {"w": jnp.asarray(rng.normal(size=32).astype(np.float32) * 0.01)}
        true_sum += np.asarray(g["w"])
        comp_sum += np.asarray(decompress_tree(ef.compress(g))["w"])
    # Residual is bounded by one quantization step, not accumulated drift.
    assert np.abs(true_sum - comp_sum).max() < 0.01


def test_compressed_training_still_learns():
    cfg, api, state = build()
    trainer = HeteroTrainer(cfg, api, [DeviceGroup("a"), DeviceGroup("b")],
                            compress=True, lr_kwargs=LR)
    losses = []
    for _, batch in zip(range(16), SyntheticTokens(cfg, 8, 16, seed=3)):
        state, m = trainer.step(state, batch)
        losses.append(m["loss"])
    assert np.mean(losses[-3:]) < np.mean(losses[:3])
