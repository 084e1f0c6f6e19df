"""The benchmark's weights: drawn on the device from ``--seed``.

Every leaf has a key of its own, folded from the seed, the leaf's name and,
for a stacked layer leaf, the layer's index.  So the whole tree comes out of
one jitted call in the served dtype, and the reference can draw one layer at
a time again, bit for bit, without anything the program has held.

The tree's layout (every leaf's name, shape and spread) is the ``layout`` of
the configuration's architecture module, ``archs/<name>.py``: the top-level
``embed``, ``final_norm`` and ``lm_head``, and ``layers/...`` leaves stacked on
a leading axis.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

NORM_STD = 0.05  # norm gains drawn around 1
BIAS_STD = 0.1
EMBED_STD = 0.02


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as the two 32-bit words of a key."""
    seed = int(seed) % (1 << 64)
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def _leaf_key(words, name: str):
    base = jax.random.wrap_key_data(words)
    return jax.random.fold_in(base, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _draw(key, shape, kind: str, std: float, dtype):
    x = jax.random.normal(key, shape, jnp.float32) * std
    if kind == "norm":
        x = x + 1.0
    return x.astype(dtype)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for name, x in flat.items():
        node = tree
        *path, leaf = name.split("/")
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = x
    return tree


@functools.lru_cache(maxsize=None)
def _init_fn(items: tuple, n_layers: int, dtype: str):
    def init(words):
        flat = {}
        for name, shape, kind, std in items:
            k = _leaf_key(words, name)
            if name.startswith("layers/"):
                keys = jax.vmap(lambda i: jax.random.fold_in(k, i))(
                    jnp.arange(n_layers))
                flat[name] = jax.vmap(
                    lambda kk: _draw(kk, shape, kind, std, dtype))(keys)
            else:
                flat[name] = _draw(k, shape, kind, std, dtype)
        return _nest(flat)

    return jax.jit(init)


def _items(c: dict, layout) -> tuple:
    """The leaves of ``layout(c)``."""
    return tuple((n, s, k, sd) for n, (s, k, sd) in layout(c).items())


def make_params(c: dict, seed: int, dtype: str, layout):
    """Every weight in ``dtype``, on the default device, in one jitted call."""
    fn = _init_fn(_items(c, layout), c["num_hidden_layers"], dtype)
    return fn(jnp.asarray(seed_words(seed)))


@functools.lru_cache(maxsize=None)
def _layer_fn(items: tuple, dtype: str):
    def one(words, i):
        flat = {}
        for name, shape, kind, std in items:
            if name.startswith("layers/"):
                k = jax.random.fold_in(_leaf_key(words, name), i)
                flat[name[len("layers/"):]] = _draw(k, shape, kind, std, dtype)
        return _nest(flat)

    return jax.jit(one)


def layer_params(c: dict, seed: int, dtype: str, i: int, layout):
    """Layer ``i``'s weights exactly as ``make_params`` drew them."""
    fn = _layer_fn(_items(c, layout), dtype)
    return fn(jnp.asarray(seed_words(seed)), jnp.int32(i))


@functools.lru_cache(maxsize=None)
def _top_fn(items: tuple, dtype: str):
    def top(words):
        return {name: _draw(_leaf_key(words, name), shape, kind, std, dtype)
                for name, shape, kind, std in items
                if not name.startswith("layers/")}

    return jax.jit(top)


def top_params(c: dict, seed: int, dtype: str, layout) -> dict:
    """``embed``, ``final_norm`` and ``lm_head`` as ``make_params`` drew them."""
    return _top_fn(_items(c, layout), dtype)(jnp.asarray(seed_words(seed)))
