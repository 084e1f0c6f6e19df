"""The ``mla_moe`` family (reduced kimi-k2) through ``InferenceServer``:
one wave decodes through the latent cache and the Pallas kernel
(interpreted); its tokens equal one-shot generate's; each ``segment`` span
carries the expert counters its Program returned and the latent bytes its
active slots read, as computed by hand; and the serving modes that have no
latent-cache path reject the family."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.core import DeviceGroup, Static
from repro.core.trace import Tracer, set_tracer, tracer
from repro.models import get_model
from repro.models import params as P
from repro.serve import InferenceServer
from repro.serve.paged import PagedSpec, validate_paged
from repro.serve.server import validate_chunked
from repro.serve.step import make_generate

PLEN, GEN, SLOTS, SEG = 8, 7, 2, 2


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(reduced(get_config("kimi-k2-1t-a32b")),
                              kernel_impl="pallas_interpret")
    api = get_model(cfg)
    params = P.materialize(api.param_spec(cfg, 1), jax.random.PRNGKey(0),
                           jnp.float32)
    return cfg, api, params


@pytest.fixture(scope="module")
def served(model):
    cfg, api, params = model
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, PLEN).astype(np.int32)
               for _ in range(SLOTS)]
    set_tracer(Tracer(capacity=1 << 16, enabled=True))
    try:
        with InferenceServer(cfg, api, params, groups=[DeviceGroup("mla")],
                             scheduler=Static(), buckets=(PLEN,),
                             max_batch=SLOTS, seg_len=SEG, max_new_cap=GEN,
                             max_wait_ms=5000.0) as srv:
            out = [np.asarray(h.result(timeout=300))
                   for h in [srv.submit(p, GEN) for p in prompts]]
        return prompts, out, tracer().events()
    finally:
        set_tracer(Tracer(enabled=False))


def test_served_tokens_equal_one_shot_generate(model, served):
    cfg, api, params = model
    prompts, out, _ = served
    gen = make_generate(cfg, api)
    want = gen(params, {"tokens": jnp.asarray(np.stack(prompts))}, GEN)
    np.testing.assert_array_equal(np.stack(out), np.asarray(want))


def test_segment_spans_carry_counters_and_latent_bytes(model, served):
    cfg, _, _ = model
    segs = [e[7] for e in served[2] if e[3] == "X" and e[4] == "segment"]
    # GEN - 1 tokens after the first, SEG a segment.
    assert len(segs) == -(-(GEN - 1) // SEG)
    row = 2 * 4 * (cfg.kv_lora_rank + cfg.qk_rope_dim)  # 2 layers, float32
    for i, a in enumerate(segs):
        # Both slots active: step j of segment i sits at PLEN + SEG*i + j.
        rows = SLOTS * sum(PLEN + SEG * i + j + 1 for j in range(SEG))
        assert a["latent_bytes"] == rows * row
        # One expert layer, top-k rows per token, every expert held.
        assert a["expert_rows"] == SLOTS * SEG * cfg.top_k
        assert a["expert_routed"] == a["expert_rows"]


def test_modes_without_a_latent_path_reject_the_family(model):
    cfg, api, _ = model
    with pytest.raises(ValueError, match="MLA"):
        validate_paged(cfg, [DeviceGroup("a")], Static(),
                       PagedSpec(block_len=8, n_blocks=16))
    with pytest.raises(ValueError, match="family"):
        validate_chunked(cfg, api, 4)
