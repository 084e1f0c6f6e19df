"""Decode attention over an MLA latent cache: a Pallas TPU kernel.

Latent attention (``models/mla.py``) caches per position one latent row
``c`` (``kv_lora_rank`` wide) and one rope key row ``k_pe`` shared by all
heads.  Decode absorbs the key and value up-projections into the query and
the output, so every head attends over the same rows:

    s[h, t] = (q_lat[h] . c[t] + q_pe[h] . k_pe[t]) * scale
    o[h]    = softmax_t(s[h]) . c          (the value is the latent itself)

One grid cell per (slot, key tile) walks the slot's tiles in order with an
online softmax; all query heads of the slot are the rows of one MXU product,
so each latent tile is read from HBM once per slot and layer.  Keys are
valid by index (``t <= pos``): the contiguous cache stores position ``t``
at row ``t``.  ``needed_tiles`` clamps the tile index to the last tile a
slot needs (re-addressing the same block elides its copy) and ``pl.when``
skips the compute, as ``flash_decode`` does.

``mla_decode_ref`` is the same arithmetic in plain ``jax.numpy``: the
``reference`` kernel path and the kernel's oracle in the tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_K = 512  # latent rows per tile: 512 x 576 bf16 is 590 KB


def needed_tiles(pos, n_rows: int, block_k: int):
    """(B,) tiles of ``block_k`` rows that hold rows 0..pos, in [1, n_tiles]
    (a slot past the cache's end reads the whole cache)."""
    nk = -(-n_rows // block_k)
    return jnp.clip(pos // block_k + 1, 1, nk).astype(jnp.int32)


def mla_decode_ref(q_lat, q_pe, c_kv, k_pe, pos, layer=0, *, scale: float):
    """q_lat: (B,H,R); q_pe: (B,H,P); c_kv: (B,L,S,R) and k_pe: (B,L,S,P)
    (any storage dtype), of which layer ``layer`` is read; pos: (B,).
    Returns (B,H,R) in q_lat.dtype."""
    dt = q_lat.dtype
    c, pe = c_kv[:, layer].astype(dt), k_pe[:, layer].astype(dt)
    s = (jnp.einsum("bhr,bkr->bhk", q_lat, c,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhp,bkp->bhk", q_pe, pe,
                      preferred_element_type=jnp.float32)) * scale
    valid = jnp.arange(c.shape[1])[None, None, :] <= pos[:, None, None]
    s = jnp.where(valid, s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.where(valid, jnp.exp(s - m), 0.0)
    l = jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("bhk,bkr->bhr", p.astype(dt), c,
                   preferred_element_type=jnp.float32)
    return (o / l).astype(dt)


def _kernel(nt_ref, pos_ref, layer_ref, ql_ref, qp_ref, c_ref, pe_ref, o_ref,
            m_scr, l_scr, acc_scr, *, nk: int, bk: int, n_rows: int,
            scale: float):
    del layer_ref  # read by the index maps only
    bi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki < nt_ref[bi])
    def _compute():
        ql = ql_ref[0]  # (H, R)
        qp = qp_ref[0]  # (H, P)
        c = c_ref[0, 0].astype(ql.dtype)  # (bk, R): cache_dtype cast
        pe = pe_ref[0, 0].astype(qp.dtype)  # (bk, P)
        dims = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(ql, c, dims,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qp, pe, dims,
                                   preferred_element_type=jnp.float32)
             ) * scale  # (H, bk)
        row = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        valid = (row <= pos_ref[bi]) & (row < n_rows)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(c.dtype), c,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_k", "interpret"))
def mla_decode(q_lat, q_pe, c_kv, k_pe, pos, layer=0, *, scale: float,
               block_k: int = BLOCK_K, interpret: bool = False):
    """The kernel: arguments and result as :func:`mla_decode_ref`.  The
    layer is picked by the index maps, so a scan over layers passes the
    whole cache, with no copy of one layer's rows."""
    b, h, r = q_lat.shape
    p_dim = q_pe.shape[-1]
    s = c_kv.shape[2]
    bk = min(block_k, s)
    pad = (-s) % bk
    if pad:  # padding rows are masked
        c_kv = jnp.pad(c_kv, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k_pe = jnp.pad(k_pe, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nk = (s + pad) // bk
    pos = jnp.asarray(pos, jnp.int32)
    nt = needed_tiles(pos, s + pad, bk)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    def tile(bi, ki, nt, pos, lay):
        return (bi, lay[0], jnp.minimum(ki, nt[bi] - 1), 0)

    def head(bi, ki, nt, pos, lay):
        return (bi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nk),
        in_specs=[pl.BlockSpec((1, h, r), head),
                  pl.BlockSpec((1, h, p_dim), head),
                  pl.BlockSpec((1, 1, bk, r), tile),
                  pl.BlockSpec((1, 1, bk, p_dim), tile)],
        out_specs=pl.BlockSpec((1, h, r), head),
        scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, r), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, bk=bk, n_rows=s, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, r), q_lat.dtype),
        interpret=interpret,
        name="mla_decode",
    )(nt, pos, layer, q_lat, q_pe, c_kv, k_pe)
