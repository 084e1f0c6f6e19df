"""Decode attention over an MLA latent cache: the Pallas kernel (interpret
mode) against the plain ``jax.numpy`` form of the same arithmetic, and that
form against the expanded attention it stands for (keys and values through
``wkv_b``, per head)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.mla_decode import mla_decode, mla_decode_ref, needed_tiles

H, R, P = 4, 32, 8
SCALE = 0.3


LAYERS = 3


def inputs(seed, b, s, dtype=jnp.float32, cache_dtype=jnp.float32):
    """Queries and a stack of 3 layers' latent caches."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, H, R), dtype),
            jax.random.normal(ks[1], (b, H, P), dtype),
            jax.random.normal(ks[2], (b, LAYERS, s, R)).astype(cache_dtype),
            jax.random.normal(ks[3], (b, LAYERS, s, P)).astype(cache_dtype))


@pytest.mark.parametrize("s,block_k,pos", [
    (64, 16, [0, 15, 16, 63]),  # tile boundaries, a full cache
    (40, 16, [3, 39, 17, 100]),  # a padded last tile; a slot past the end
    (32, 512, [5, 31, 0, 20]),  # one tile
], ids=["boundaries", "padded", "one_tile"])
def test_kernel_matches_plain_form(s, block_k, pos):
    args = inputs(0, len(pos), s)
    posv = jnp.asarray(pos, jnp.int32)
    for layer in range(LAYERS):
        got = mla_decode(*args, posv, layer, scale=SCALE, block_k=block_k,
                         interpret=True)
        want = mla_decode_ref(*args, posv, layer, scale=SCALE)
        # Same float32 products, another summation order (online softmax).
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_rows_past_pos_are_never_read():
    args = list(inputs(1, 2, 48))
    posv = jnp.asarray([10, 30], jnp.int32)
    want = mla_decode(*args, posv, scale=SCALE, block_k=16, interpret=True)
    for i in (2, 3):  # garbage past each slot's position, and in layer 1
        args[i] = (args[i].at[0, 0, 11:].set(1e4).at[1, 0, 31:].set(-1e4)
                   .at[:, 1].set(1e4))
    got = mla_decode(*args, posv, scale=SCALE, block_k=16, interpret=True)
    np.testing.assert_array_equal(got, want)


def test_float8_cache_is_read_through_a_cast():
    args = inputs(2, 3, 32, jnp.bfloat16, jnp.float8_e4m3fn)
    posv = jnp.asarray([4, 31, 12], jnp.int32)
    got = mla_decode(*args, posv, scale=SCALE, block_k=16, interpret=True)
    want = mla_decode_ref(*args, posv, scale=SCALE)
    assert got.dtype == jnp.bfloat16
    # bfloat16 products, two summation orders: a few units of its last place.
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_plain_form_equals_expanded_attention():
    """Absorbing W_uk into the query and W_uv into the output is the same
    sum as expanding keys and values per head."""
    b, s, n, v = 2, 24, 6, 5
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    q_nope = jax.random.normal(ks[0], (b, H, n))
    q_pe = jax.random.normal(ks[1], (b, H, P))
    c = jax.random.normal(ks[2], (b, 1, s, R))
    k_pe = jax.random.normal(ks[3], (b, 1, s, P))
    w = jax.random.normal(ks[4], (R, H, n + v)) * R ** -0.5
    pos = jnp.asarray([7, 23])
    hi = jax.lax.Precision.HIGHEST
    with jax.default_matmul_precision("highest"):
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, w[..., :n], precision=hi)
        o = mla_decode_ref(q_lat, q_pe, c, k_pe, pos, scale=SCALE)
        got = jnp.einsum("bhr,rhv->bhv", o, w[..., n:], precision=hi)
        k = jnp.einsum("bsr,rhn->bshn", c[:, 0], w[..., :n], precision=hi)
        val = jnp.einsum("bsr,rhv->bshv", c[:, 0], w[..., n:], precision=hi)
        sc = (jnp.einsum("bhn,bshn->bhs", q_nope, k, precision=hi)
              + jnp.einsum("bhp,bsp->bhs", q_pe, k_pe[:, 0], precision=hi)
              ) * SCALE
        sc = jnp.where(jnp.arange(s)[None, None] <= pos[:, None, None], sc,
                       -jnp.inf)
        want = jnp.einsum("bhs,bshv->bhv", jax.nn.softmax(sc, -1), val,
                          precision=hi)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_needed_tiles():
    np.testing.assert_array_equal(
        needed_tiles(jnp.asarray([0, 15, 16, 63, 500]), 64, 16),
        [1, 1, 2, 4, 4])
