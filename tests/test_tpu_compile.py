"""The serving kernels compile for a TPU v5e at qwen1.5-4b's published
widths (bf16, 20 heads of 128, 40 layers), and the MLA decode kernel at
Kimi-K2's (64 heads over a 512 + 64 latent row).

Nothing runs: each test compiles for a described ``v5e:2x2`` topology, which
raises what the chip's compiler would raise (block shapes that do not tile,
too much fast memory, a program that does not fit the chip's 16 GB).  The
topology is described inside a fixture, never at import, so every pytest
worker collects the same tests and only the one that runs this file loads
the TPU compiler.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.kernels.mla_decode import mla_decode
from repro.models import get_model
from repro.models.params import Spec, tree_map_specs
from repro.serve.batcher import ModelKernels

BF16 = jnp.bfloat16
HBM_BYTES = 16 * 10**9  # one v5e chip
BLOCK_LEN = 16  # the paged block length the chip runs (chip_smoke.py)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # Entries compiled for a described chip cannot be read back here.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(get_config("qwen1.5-4b"), kernel_impl="pallas")


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("sq", [1, 3], ids=["decode", "multirow_k2"])
def test_flash_decode_compiles(one_chip, cfg, sq):
    """Sq 1 is plain decode; Sq k+1 the speculative / chunked multi-row
    mode (every query row of a slot masked at its own depth)."""
    b, s, h, kv, hd = 8, 1024, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    args = (_sds(one_chip, (b, sq, h, hd), BF16),
            _sds(one_chip, (b, s, kv, hd), BF16),
            _sds(one_chip, (b, s, kv, hd), BF16),
            _sds(one_chip, (b, s), jnp.int32),
            _sds(one_chip, (b,), jnp.int32))
    compiled = jax.jit(lambda *a: flash_decode(*a)).lower(*args).compile()
    assert _has_kernel(compiled)


def test_flash_decode_paged_compiles(one_chip, cfg):
    b, h, kv, hd = 8, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    nmax = 1024 // BLOCK_LEN
    n_blocks = 2 + b * nmax
    args = (_sds(one_chip, (b, 1, h, hd), BF16),
            _sds(one_chip, (n_blocks, BLOCK_LEN, kv, hd), BF16),
            _sds(one_chip, (n_blocks, BLOCK_LEN, kv, hd), BF16),
            _sds(one_chip, (n_blocks, BLOCK_LEN), jnp.int32),
            _sds(one_chip, (b, nmax), jnp.int32),
            _sds(one_chip, (b,), jnp.int32))
    compiled = jax.jit(lambda *a: flash_decode_paged(*a)).lower(*args).compile()
    assert _has_kernel(compiled)


def test_mla_decode_compiles(one_chip):
    """16 slots over 3072 latent rows of one of 8 stacked layers, as the
    kimi-k2-s9 cell decodes."""
    b, s, h, r, p, layers = 16, 3072, 64, 512, 64, 8
    args = (_sds(one_chip, (b, h, r), BF16), _sds(one_chip, (b, h, p), BF16),
            _sds(one_chip, (b, layers, s, r), BF16),
            _sds(one_chip, (b, layers, s, p), BF16),
            _sds(one_chip, (b,), jnp.int32), _sds(one_chip, (), jnp.int32))
    compiled = jax.jit(lambda *a: mla_decode(*a, scale=0.13)
                       ).lower(*args).compile()
    assert _has_kernel(compiled)


def test_flash_attention_compiles(one_chip, cfg):
    """Causal prefill of 1024-token prompts."""
    b, s, h, kv, hd = 2, 1024, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    args = (_sds(one_chip, (b, s, h, hd), BF16),
            _sds(one_chip, (b, s, kv, hd), BF16),
            _sds(one_chip, (b, s, kv, hd), BF16))
    compiled = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True)
                       ).lower(*args).compile()
    assert _has_kernel(compiled)


def test_decode_segment_fits_one_chip_with_params_as_arguments(one_chip, cfg):
    """The serving decode-segment program at full width and depth, with the
    weights passed as an argument (as ``ModelKernels.program`` passes them)
    and 4 slots of 1024 tokens of bf16 KV cache, fits one chip."""
    slots, max_seq, seg_len = 4, 1024, 4
    api = get_model(cfg)
    params = tree_map_specs(
        lambda s: _sds(one_chip, s.shape, jnp.dtype(s.dtype or cfg.compute_dtype)),
        api.param_spec(cfg, 1))
    kernels = ModelKernels(cfg, api, params)
    leaves = []
    specs = jax.tree_util.tree_leaves(api.cache_spec(cfg, slots, max_seq, 1),
                                      is_leaf=lambda x: isinstance(x, Spec))
    for s, a in zip(specs, kernels.bax_leaves):
        shape = (s.shape[a],) + s.shape[:a] + s.shape[a + 1:]  # slot-leading
        leaves.append(_sds(one_chip, shape, jnp.dtype(s.dtype or cfg.compute_dtype)))
    tok = _sds(one_chip, (slots, 1), jnp.int32)
    fn = jax.jit(kernels.segment_kernel(seg_len),
                 donate_argnums=tuple(range(3, 3 + len(leaves))))
    compiled = fn.lower(_sds(one_chip, (), jnp.int32), tok, tok, *leaves,
                        kernels.weights).compile()
    assert _has_kernel(compiled)
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    weight_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(params))
    assert mem.argument_size_in_bytes >= weight_bytes  # weights are arguments
    assert need < HBM_BYTES, (
        f"decode segment needs {need / 1e9:.2f} GB of the chip's "
        f"{HBM_BYTES / 1e9:.0f} GB")
