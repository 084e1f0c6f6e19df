"""Per-arch smoke: reduced same-family config, one forward/train/prefill/
decode step on CPU, asserting output shapes + no NaNs (assignment §f)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import all_archs, get_config, reduced
from repro.launch.specs import make_batch
from repro.configs.base import ShapeCell
from repro.models import get_model
from repro.models import params as P


@pytest.fixture(scope="module", params=all_archs())
def arch_setup(request):
    cfg = reduced(get_config(request.param))
    api = get_model(cfg)
    params = P.materialize(api.param_spec(cfg, 1), jax.random.PRNGKey(0), jnp.float32)
    return cfg, api, params


def _batch(cfg, b=2, s=16):
    return make_batch(cfg, ShapeCell("t", s, b, "train"), jax.random.PRNGKey(1))


def test_train_step_loss_finite(arch_setup):
    cfg, api, params = arch_setup
    loss = api.forward_train(params, _batch(cfg), cfg)
    assert loss.shape == ()
    assert np.isfinite(float(loss))
    assert 1.0 < float(loss) < 20.0  # ~ln(vocab) at init


def test_gradients_flow_everywhere(arch_setup):
    cfg, api, params = arch_setup
    grads = jax.grad(lambda p: api.forward_train(p, _batch(cfg), cfg))(params)
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in leaves)
    nonzero = sum(bool(np.abs(np.asarray(g)).sum() > 0) for g in leaves)
    assert nonzero >= len(leaves) * 0.9  # (a couple of gates may be dead at init)


def test_prefill_decode_shapes_no_nan(arch_setup):
    cfg, api, params = arch_setup
    b, s = 2, 16
    batch = _batch(cfg, b, s)
    cache = P.materialize(api.cache_spec(cfg, b, 32, 1), jax.random.PRNGKey(2), jnp.float32)
    logits, cache = api.prefill(params, batch, cfg, cache)
    assert logits.shape == (b, 1, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    logits2, cache = api.decode(params, tok, jnp.int32(s), cfg, cache)
    assert logits2.shape == (b, 1, cfg.vocab)
    assert np.isfinite(np.asarray(logits2)).all()


def test_decode_consistent_with_prefill(arch_setup):
    """Decoding token t via cache must match prefilling t+1 tokens."""
    cfg, api, params = arch_setup
    b, s = 2, 12
    batch = _batch(cfg, b, s)
    cache = P.materialize(api.cache_spec(cfg, b, 32, 1), jax.random.PRNGKey(2), jnp.float32)
    _, cache = api.prefill(params, batch, cfg, cache)
    tok = batch["tokens"][:, -1:]  # re-decode last prompt token? no: next
    # Decode the next token given full prefix, compare against prefill of s+1.
    nxt = jnp.full((b, 1), 7, jnp.int32)
    # Absolute decode position includes the image-patch prefix (vlm);
    # whisper decoder positions are text-only.
    pos = s + (cfg.n_patches if cfg.family == "vlm" else 0)
    logits_dec, _ = api.decode(params, nxt, jnp.int32(pos), cfg, cache)
    batch2 = {k: (jnp.concatenate([v, nxt], axis=1) if k == "tokens" else v) for k, v in batch.items()}
    cache2 = P.materialize(api.cache_spec(cfg, b, 32, 1), jax.random.PRNGKey(3), jnp.float32)
    logits_pre, _ = api.prefill(params, batch2, cfg, cache2)
    np.testing.assert_allclose(
        np.asarray(logits_dec[:, -1]), np.asarray(logits_pre[:, -1]), atol=2e-3, rtol=2e-3
    )


def test_full_configs_have_exact_dimensions():
    """Assignment table: exact layer/width/head/vocab values."""
    expect = {
        "granite-34b": (88, 6144, 48, 1, 24576, 49152),
        "codeqwen1.5-7b": (32, 4096, 32, 32, 13440, 92416),
        "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151936),
        "internlm2-20b": (48, 6144, 48, 8, 16384, 92544),
        "paligemma-3b": (18, 2048, 8, 1, 16384, 257216),
        "kimi-k2-1t-a32b": (61, 7168, 64, 64, 18432, 163840),
        "arctic-480b": (35, 7168, 56, 8, 4864, 32000),
        "whisper-tiny": (4, 384, 6, 6, 1536, 51865),
        "falcon-mamba-7b": (64, 4096, 0, 0, 0, 65024),
        "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
    }
    for name, (nl, d, h, kv, ff, vocab) in expect.items():
        cfg = get_config(name)
        got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.vocab)
        assert got == (nl, d, h, kv, ff, vocab), f"{name}: {got}"
    # Family features.
    assert get_config("kimi-k2-1t-a32b").n_experts == 384
    assert get_config("kimi-k2-1t-a32b").top_k == 8
    kimi = get_config("kimi-k2-1t-a32b")
    assert (kimi.family, kimi.q_lora_rank, kimi.kv_lora_rank, kimi.qk_nope_dim,
            kimi.qk_rope_dim, kimi.v_head_dim) == ("mla_moe", 1536, 512, 128,
                                                   64, 128)
    assert (kimi.moe_d_ff, kimi.n_shared_experts, kimi.route_scale,
            kimi.rope_theta, kimi.yarn_factor) == (2048, 1, 2.827, 50000.0,
                                                   32.0)
    assert get_config("arctic-480b").n_experts == 128
    assert get_config("arctic-480b").dense_residual
    assert get_config("falcon-mamba-7b").ssm_state == 16
    assert get_config("recurrentgemma-2b").block_pattern == ("rec", "rec", "attn")
    assert get_config("qwen1.5-4b").qkv_bias
