"""Elastic re-meshing: pod-loss survival logic + end-to-end restore onto a
smaller mesh (the fleet fault-tolerance path)."""
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro.distributed.elastic import plan_remesh


@given(n=st.integers(1, 4096), mp=st.sampled_from([1, 2, 4, 8, 16]))
@settings(max_examples=80, deadline=None)
def test_plan_remesh_valid(n, mp):
    if n < mp:
        with pytest.raises(ValueError):
            plan_remesh(n, model_par=mp)
        return
    plan = plan_remesh(n, model_par=mp)
    total = 1
    for d in plan.shape:
        total *= d
    assert total == plan.n_devices <= n
    assert plan.shape[-1] == mp
    assert "model" == plan.axes[-1]
    data = total // mp
    assert data & (data - 1) == 0  # power of two


def test_plan_remesh_pod_loss_example():
    # 512 chips (2 pods) -> lose one pod -> 256 chips, model axis kept.
    full = plan_remesh(512, model_par=16)
    assert full.shape == (2, 16, 16)
    degraded = plan_remesh(256, model_par=16)
    assert degraded.n_devices == 256
    assert degraded.shape[-1] == 16


def test_elastic_restore_smaller_world(tmp_path):
    """Train 3 steps, checkpoint, 'lose' devices, restore+continue on the
    smaller mesh — losses must continue from the checkpointed trajectory."""
    from repro.ckpt import save_checkpoint
    from repro.configs import get_config, reduced
    from repro.data import SyntheticTokens
    from repro.models import get_model
    from repro.models import params as P
    from repro.train import make_train_step, state_spec
    from repro.distributed.elastic import ElasticRunner
    from repro.distributed.sharding import set_current_mesh

    cfg = reduced(get_config("qwen1.5-4b"))
    api = get_model(cfg)
    sspec = state_spec(cfg, api.param_spec(cfg, 1))
    state = P.materialize(sspec, jax.random.PRNGKey(0), jnp.float32)
    ds = SyntheticTokens(cfg, 4, 16, seed=2)
    step = jax.jit(make_train_step(cfg, api))
    for _, batch in zip(range(3), ds):
        state, m = step(state, {k: jnp.asarray(v) for k, v in batch.items()})
    save_checkpoint(tmp_path, 3, state, {"data_cursor": ds.state()["cursor"]})

    runner = ElasticRunner(
        cfg, api,
        state_spec_fn=lambda cfg, plan: state_spec(cfg, api.param_spec(cfg, 1)),
        step_factory=make_train_step,
        ckpt_dir=tmp_path,
        model_par=1,
    )
    mesh, restored, extra = runner.on_failure(jax.devices()[:1])  # world of 1
    try:
        assert extra["data_cursor"] == ds.state()["cursor"]
        for a, b in zip(jax.tree_util.tree_leaves(state["params"]),
                        jax.tree_util.tree_leaves(restored["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # Continue training on the rebuilt world.
        ds2 = SyntheticTokens(cfg, 4, 16, seed=2)
        ds2.seek(extra["data_cursor"])
        with mesh:
            new_state, m = runner.step_fn(restored, {k: jnp.asarray(v) for k, v in next(ds2).items()})
        assert np.isfinite(float(m["loss"]))
    finally:
        set_current_mesh(None)
