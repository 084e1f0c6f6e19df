"""FlashAttention Pallas TPU kernel.

TPU-native blocking (DESIGN.md: adapt, don't port): the KV loop is the
*innermost grid dimension* — TPU grids execute the last axis sequentially on
a core, so running (m, l, acc) carries live in VMEM scratch across KV steps
and only the final step writes the output tile.  Q/K/V tiles stream
HBM→VMEM via BlockSpecs; the (Bq, Bk) score tile hits the MXU via
dot_general with fp32 accumulation.  GQA is folded into the K/V index_map
(kv_head = q_head // n_rep) — no materialized repeat.

Causal/window masking is positional per-tile; fully-masked tiles are
guarded with pl.when so they cost control flow only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, window: int, q_offset: int, bq: int, bk: int,
            nk: int, sk: int, scale: float):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq + q_offset
    k_start = ki * bk
    # Tile-level reachability: skip tiles fully outside the mask.
    reachable = True
    if causal:
        reachable = k_start <= q_start + bq - 1
    if window > 0:
        reachable = jnp.logical_and(reachable, k_start + bk - 1 > q_start - window)

    @pl.when(reachable)
    def _compute():
        q = q_ref[0]  # (bq, hd)
        k = k_ref[0]  # (bk, hd)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < sk  # KV-length mask (tile padding)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "block_q", "block_k", "interpret"),
)
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0, q_offset: int = 0,
                    block_q: int = 128, block_k: int = 128, interpret: bool = False):
    """q: (B,Sq,H,hd), k/v: (B,Sk,KV,hd) with H % KV == 0. Returns (B,Sq,H,hd).

    Differentiable: custom_vjp — the fused Pallas kernel runs forward; the
    backward recomputes attention with the O(S)-memory jnp online-softmax
    reference and differentiates that (flash-style recompute backward).
    """
    return _flash_vjp(q, k, v, causal, window, q_offset, block_q, block_k, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_vjp(q, k, v, causal, window, q_offset, block_q, block_k, interpret):
    return _flash_fwd_impl(q, k, v, causal, window, q_offset, block_q, block_k, interpret)


def _flash_vjp_fwd(q, k, v, causal, window, q_offset, block_q, block_k, interpret):
    out = _flash_fwd_impl(q, k, v, causal, window, q_offset, block_q, block_k, interpret)
    return out, (q, k, v)


def _flash_vjp_bwd(causal, window, q_offset, block_q, block_k, interpret, res, g):
    from repro.models import layers as L

    q, k, v = res

    def ref(q, k, v):
        if q.shape[1] * k.shape[1] <= 1024 * 1024:
            return L.naive_attention(q, k, v, causal=causal, window=window, q_offset=q_offset)
        return L.chunked_attention(q, k, v, causal=causal, window=window, q_offset=q_offset,
                                   q_chunk=min(1024, q.shape[1]), kv_chunk=min(1024, k.shape[1]))

    _, vjp = jax.vjp(ref, q, k, v)
    return vjp(g)


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _flash_fwd_impl(q, k, v, causal, window, q_offset, block_q, block_k, interpret):
    b, sq, h, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    n_rep = h // kv
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    pad_q = (-sq) % bq
    pad_k = (-sk) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        # Padded kv positions are masked out by kpos bounds only when causal
        # covers them; add an explicit length mask via window-free guard:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    sq_p, sk_p = sq + pad_q, sk + pad_k
    nq, nk = sq_p // bq, sk_p // bk

    kernel = functools.partial(
        _kernel, causal=causal, window=window, q_offset=q_offset,
        bq=bq, bk=bk, nk=nk, sk=sk, scale=hd ** -0.5,
    )
    # Heads ride the lane axis ((B, S, H*hd), a free reshape): a head's
    # (rows, hd) tile is then a block the TPU tiles, where a size-1 head
    # axis in the second-minor place is not.
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bi, hi, qi, ki: (bi, qi, hi)),
            pl.BlockSpec((1, bk, hd), lambda bi, hi, qi, ki, n_rep=n_rep: (bi, ki, hi // n_rep)),
            pl.BlockSpec((1, bk, hd), lambda bi, hi, qi, ki, n_rep=n_rep: (bi, ki, hi // n_rep)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bi, hi, qi, ki: (bi, qi, hi)),
        out_shape=jax.ShapeDtypeStruct((b, sq_p, h * hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q.reshape(b, sq_p, h * hd), k.reshape(b, sk_p, kv * hd),
      v.reshape(b, sk_p, kv * hd))
    return out.reshape(b, sq_p, h, hd)[:, :sq]
