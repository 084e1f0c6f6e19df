"""The serving path against the plain float32 reference forward pass: prefill
and then decoding through the cache give the reference's logits at every
position (small dense configs, float32, CPU)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import get_model
from repro.models import params as P
from repro.models.reference import forward
from repro.serve import make_scored_continuation

DENSE = ["qwen1.5-4b", "internlm2-20b", "codeqwen1.5-7b", "granite-34b"]


@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
@pytest.mark.parametrize("arch", DENSE)
def test_prefill_then_decode_matches_reference(arch, impl):
    cfg = dataclasses.replace(reduced(get_config(arch)), kernel_impl=impl)
    api = get_model(cfg)
    params = P.materialize(api.param_spec(cfg, 1), jax.random.PRNGKey(0),
                           jnp.float32)
    rng = np.random.default_rng(1)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (2, 8)), jnp.int32)
    cont = jnp.asarray(rng.integers(0, cfg.vocab, (2, 3)), jnp.int32)
    got = jax.jit(make_scored_continuation(cfg, api))(params, prompts, cont)
    want = forward(params, jnp.concatenate([prompts, cont], 1), cfg, last=4)
    assert got.shape == want.shape == (2, 4, cfg.vocab)
    # float32 on both sides; the paths differ only in summation order.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_reference_rejects_configs_it_does_not_describe():
    with pytest.raises(ValueError, match="no reference"):
        forward({}, jnp.zeros((1, 4), jnp.int32),
                reduced(get_config("recurrentgemma-2b")))
