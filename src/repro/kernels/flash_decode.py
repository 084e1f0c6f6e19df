"""Ragged flash-decode: batched decode-attention Pallas TPU kernel.

One query token per slot against the KV cache *as stored* — ``(B, S, KV,
hd)`` k/v plus the recorded-position vector ``kpos`` (−1 = empty slot) and a
per-slot absolute position ``pos`` (slots of a continuous batch sit at
different depths of their own timeline).  Three things make it "ragged":

- **GQA in the index_map.**  q is viewed as ``(B, KV, n_rep, hd)`` and the
  grid walks (batch, kv-head, kv-tile); each fetched K/V tile serves its
  whole query-head group — no ``repeat_kv`` materialization, no H/KV×
  duplicate memory traffic.
- **Position masking, not causal masking.**  Validity is ``0 <= kpos <=
  pos`` (AND ``kpos > pos - window`` for rolling caches), so full and
  windowed caches go through one kernel and empty slots never attend.
- **Per-slot tile skip.**  ``needed_tiles`` (host-side O(B·S) integer math)
  finds the last KV tile holding any in-mask key per slot.  The tile count
  rides in as a scalar-prefetch operand: the K/V/kpos index_maps *clamp* the
  tile index to it — on TPU, re-addressing the previous block elides the
  HBM→VMEM copy — and ``pl.when`` skips the compute.  A slot 10 tokens into
  a 4096-deep cache pays ~1 tile, not 32.

Reduction order is strictly per-row (every (slot, kv-head) grid cell carries
its own online-softmax state over *its own* tile count), so a slot's output
is bit-identical whatever batch it shares the kernel with — the serving
equivalence contract (tests/test_server.py) extends to the kernel path.

A slot with no valid keys (``pos = -1`` and an empty cache) returns zeros:
masked probabilities are exactly 0, so l = 0 and the guarded divide yields
0 — the dense reference (`repro.kernels.ref.flash_decode_ref`) defines the
same contract.

``flash_decode_xla`` is the portable lowering of the same algorithm — a
``lax.while_loop`` over KV tiles bounded by the batch's deepest needed tile
— for backends without Pallas TPU (it is what the decode benchmark times on
the CI container).  Extra tiles a shallow row sees under a deeper batch are
fully masked no-ops, but XLA fuses the loop body shape-dependently, so its
rows are batch-invariant only up to ~1 ulp — serving's bit-identity paths
are the dense fallback and this Pallas kernel, never the XLA loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def needed_tiles(kpos, pos, *, window: int = 0, block_k: int = 128,
                 sq: int = 1):
    """Per-slot KV tile count the ragged kernel touches (the tile-skip math).

    ``kpos``: (B, S) recorded positions (−1 = empty); ``pos``: (B,) query
    positions.  Returns (B,) int32 in [1, ceil(S/block_k)]: 1 + the last
    tile index containing any key with ``0 <= kpos <= pos`` (window-masked
    when ``window > 0``); all-empty slots clamp to 1 so the kernel still
    initializes/finalizes its scratch (the lone tile is fully masked).

    ``sq > 1`` (multi-row decode, e.g. speculative verify): the slot's sq
    query rows sit at consecutive positions ``pos .. pos+sq-1``, so the
    tile count covers the UNION of the per-row masks — upper bound from the
    deepest row, window lower bound from the shallowest (a tile a shallow
    row needs must not be skipped just because the deepest row's window
    excludes it)."""
    s = kpos.shape[1]
    valid = _mask(kpos, pos[:, None] + (sq - 1), 0)
    if window > 0:
        valid &= kpos > pos[:, None] - window
    tile = (jnp.arange(s, dtype=jnp.int32) // block_k)[None, :]
    last = jnp.max(jnp.where(valid, tile, -1), axis=1)
    return jnp.maximum(last + 1, 1).astype(jnp.int32)


def _mask(kp, pos_b, window: int):
    # One definition of the validity predicate for every decode path — the
    # bit-identity contract depends on the kernel, the dense fallback, and
    # the mesh combine masking identically.
    from repro.models.attention import ragged_valid_mask

    return ragged_valid_mask(kp, pos_b, window)


def _kernel(nt_ref, pos_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref,
            m_scr, l_scr, acc_scr, *, window: int, nk: int, scale: float,
            n_rep: int):
    bi = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki < nt_ref[bi])
    def _compute():
        q = q_ref[0, 0]  # (rows, hd), rows = sq*n_rep
        rows = q.shape[0]
        k = k_ref[0].astype(q.dtype)  # (bk, hd) — cache_dtype cast
        v = v_ref[0].astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (rows, bk)
        # Row r belongs to query token r // n_rep (multi-row decode: the
        # slot's sq query tokens sit at consecutive positions, each masked
        # at its own depth).  sq == 1 collapses to a uniform row mask.
        j = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // n_rep
        rowpos = pos_ref[bi] + j  # (rows, 1)
        valid = _mask(kpos_ref[0, 0], rowpos, window)  # (rows, bk)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]  # (rows, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        # Mask p explicitly (not via exp underflow): an all-masked tile has
        # m_new == NEG_INF and exp(s - m_new) == 1, which must not count.
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)  # l == 0: no valid keys -> 0
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _scratch(rows: int, hd: int) -> list:
    # Online-softmax state per query row; 2-D so Mosaic lays it out as
    # (sublane, lane) tiles.
    return [pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, hd), jnp.float32)]


def _lane_heads(x):
    """(N, S, KV, hd) -> (N, S, KV*hd): a free reshape that puts the heads
    on the lane axis, so one head's tile is a (rows, hd) block at lane
    offset ``head*hd`` — a block the TPU tiles (the minor two block dims
    must be multiples of (8, 128) or whole), where a size-1 head axis in
    the second-minor place is not."""
    n, s, kv, hd = x.shape
    return x.reshape(n, s, kv * hd)


def _pad_cache(k, v, kpos, bk):
    s = k.shape[1]
    pad = (-s) % bk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # Padding is recorded-position -1 == empty == masked out.
        kpos = jnp.pad(kpos, ((0, 0), (0, pad)), constant_values=-1)
    return k, v, kpos


@functools.partial(jax.jit, static_argnames=("window", "block_k", "interpret"))
def flash_decode(q, k, v, kpos, pos, *, window: int = 0, block_k: int = 128,
                 interpret: bool = False):
    """q: (B,Sq,H,hd); k/v: (B,S,KV,hd) with H % KV == 0 (any storage dtype);
    kpos: (B,S) int32 recorded positions; pos: (B,) int32 query positions.
    Returns (B,Sq,H,hd) in q.dtype.

    Sq > 1 is the multi-row (speculative-verify) mode: the Sq query tokens
    of a slot sit at consecutive positions ``pos .. pos+Sq-1`` and are
    folded into the GQA row axis — q is viewed as (B, KV, Sq·n_rep, hd) and
    each row masks the shared K tile at its own depth.  One kernel call
    scores all candidate rows; Sq == 1 reduces bit-exactly to the original
    single-token layout."""
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    n_rep = h // kv
    rows = sq * n_rep
    bk = min(block_k, k.shape[1])
    k, v, kpos = _pad_cache(k, v, kpos, bk)
    nk = k.shape[1] // bk
    pos = jnp.asarray(pos, jnp.int32)
    nt = needed_tiles(kpos, pos, window=window, block_k=bk, sq=sq)
    # (B, Sq, H, hd) -> (B, KV, Sq*n_rep, hd): row r = query r//n_rep,
    # rep r%n_rep — pure layout, bitwise q[:, 0].reshape(...) at Sq == 1.
    qg = (q.reshape(b, sq, kv, n_rep, hd)
          .transpose(0, 2, 1, 3, 4).reshape(b, kv, rows, hd))

    def tile(ki, nt, bi):
        # Clamp beyond the slot's needed tiles: same block as the previous
        # grid step -> the TPU pipeline elides the copy (ragged fetch skip).
        return jnp.minimum(ki, nt[bi] - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, rows, hd), lambda bi, gi, ki, nt, pos: (bi, gi, 0, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda bi, gi, ki, nt, pos: (bi, tile(ki, nt, bi), gi)),
            pl.BlockSpec((1, bk, hd),
                         lambda bi, gi, ki, nt, pos: (bi, tile(ki, nt, bi), gi)),
            # kpos as (B, nk, 1, bk): each tile's positions are a whole
            # (1, bk) minor block, so any bk tiles.
            pl.BlockSpec((1, 1, 1, bk),
                         lambda bi, gi, ki, nt, pos: (bi, tile(ki, nt, bi), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, hd), lambda bi, gi, ki, nt, pos: (bi, gi, 0, 0)),
        scratch_shapes=_scratch(rows, hd),
    )
    kernel = functools.partial(_kernel, window=window, nk=nk, scale=hd ** -0.5,
                               n_rep=n_rep)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, rows, hd), q.dtype),
        interpret=interpret,
    )(nt, pos, qg, _lane_heads(k), _lane_heads(v), kpos.reshape(b, nk, 1, bk))
    return (out.reshape(b, kv, sq, n_rep, hd)
            .transpose(0, 2, 1, 3, 4).reshape(b, sq, h, hd))


def _paged_kernel(nt_ref, pos_ref, tbl_ref, q_ref, k_ref, v_ref, kpos_ref,
                  o_ref, m_scr, l_scr, acc_scr, *, window: int, nk: int,
                  scale: float, n_rep: int):
    # The block table is consumed entirely by the index_maps (it addresses
    # HBM blocks); the compute body is the contiguous kernel verbatim — the
    # paged kernel differs only in WHERE a logical tile's bytes live.
    del tbl_ref
    _kernel(nt_ref, pos_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref,
            m_scr, l_scr, acc_scr, window=window, nk=nk, scale=scale,
            n_rep=n_rep)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def flash_decode_paged(q, k, v, kpos, tables, pos, *, window: int = 0,
                       interpret: bool = False):
    """Ragged flash-decode over a paged KV **block pool**.

    q: (B,1,H,hd); k/v: (N, bl, KV, hd) — a pool of N physical blocks of
    ``bl`` tokens (any storage dtype); kpos: (N, bl) recorded positions
    (−1 = empty); tables: (B, nmax) int32 block table mapping each slot's
    logical tile to a physical block; pos: (B,) query positions.

    The grid walks logical tiles exactly like :func:`flash_decode` with
    ``block_k = bl``; the K/V/kpos index_maps resolve ``(slot, tile)``
    through the block-table scalar-prefetch operand, *composing* with the
    per-slot ``needed_tiles`` clamp (beyond a slot's needed tiles the same
    physical block is re-addressed, eliding the copy, and ``pl.when`` skips
    the compute).  Because logical tile ``i`` of a slot holds exactly the
    same values as rows ``[i*bl, (i+1)*bl)`` of a contiguous cache, and
    tiles are reduced in the same logical order with the same online-
    softmax state, the output is bit-identical to :func:`flash_decode` on
    the gathered contiguous layout with ``block_k = bl`` — the serving
    bit-identity contract survives physical-block indirection.
    """
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    n_rep = h // kv
    rows = sq * n_rep
    bl = k.shape[1]  # pool layout: (n_blocks, block_len, KV, hd)
    nmax = tables.shape[1]
    tables = jnp.asarray(tables, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    # Logical recorded positions (B, nmax*bl): O(B·S) int gather outside the
    # kernel — the same tile-skip math as the contiguous path, applied to
    # the table-resolved view of each slot's timeline.
    kpos_log = kpos[tables].reshape(b, nmax * bl)
    nt = needed_tiles(kpos_log, pos, window=window, block_k=bl, sq=sq)
    qg = (q.reshape(b, sq, kv, n_rep, hd)
          .transpose(0, 2, 1, 3, 4).reshape(b, kv, rows, hd))

    def block(ki, nt, tbl, bi):
        # Clamp to the slot's needed tiles FIRST (contiguous kernel's ragged
        # fetch skip), then resolve the logical tile to its physical block.
        return tbl[bi, jnp.minimum(ki, nt[bi] - 1)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, kv, nmax),
        in_specs=[
            pl.BlockSpec((1, 1, rows, hd),
                         lambda bi, gi, ki, nt, pos, tbl: (bi, gi, 0, 0)),
            pl.BlockSpec((1, bl, hd), lambda bi, gi, ki, nt, pos, tbl:
                         (block(ki, nt, tbl, bi), 0, gi)),
            pl.BlockSpec((1, bl, hd), lambda bi, gi, ki, nt, pos, tbl:
                         (block(ki, nt, tbl, bi), 0, gi)),
            pl.BlockSpec((1, 1, 1, bl), lambda bi, gi, ki, nt, pos, tbl:
                         (block(ki, nt, tbl, bi), 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, hd),
                               lambda bi, gi, ki, nt, pos, tbl: (bi, gi, 0, 0)),
        scratch_shapes=_scratch(rows, hd),
    )
    kernel = functools.partial(_paged_kernel, window=window, nk=nmax,
                               scale=hd ** -0.5, n_rep=n_rep)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, rows, hd), q.dtype),
        interpret=interpret,
    )(nt, pos, tables, qg, _lane_heads(k), _lane_heads(v),
      kpos.reshape(kpos.shape[0], 1, 1, bl))
    return (out.reshape(b, kv, sq, n_rep, hd)
            .transpose(0, 2, 1, 3, 4).reshape(b, sq, h, hd))


@functools.partial(jax.jit, static_argnames=("window", "block_k"))
def flash_decode_xla(q, k, v, kpos, pos, *, window: int = 0, block_k: int = 128):
    """Portable ragged decode: the kernel's algorithm as a ``lax.while_loop``
    over KV tiles, bounded by the batch's deepest ``needed_tiles`` — FLOPs
    and cache reads scale with actual occupancy depth, not cache capacity.
    Same signature and zero-for-empty-slot contract as ``flash_decode``."""
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    n_rep = h // kv
    rows = sq * n_rep
    bk = min(block_k, k.shape[1])
    k, v, kpos = _pad_cache(k, v, kpos, bk)
    pos = jnp.asarray(pos, jnp.int32)
    n_hi = jnp.max(needed_tiles(kpos, pos, window=window, block_k=bk, sq=sq))
    qg = (q.reshape(b, sq, kv, n_rep, hd)
          .transpose(0, 2, 1, 3, 4).reshape(b, kv, rows, hd))
    rowpos = pos[:, None] + jnp.arange(rows, dtype=jnp.int32) // n_rep  # (B, rows)
    scale = hd ** -0.5

    def cond(carry):
        return carry[0] < n_hi

    def body(carry):
        i, m, l, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(k, i * bk, bk, 1).astype(q.dtype)
        vb = jax.lax.dynamic_slice_in_dim(v, i * bk, bk, 1).astype(q.dtype)
        kp = jax.lax.dynamic_slice_in_dim(kpos, i * bk, bk, 1)  # (B, bk)
        s = jnp.einsum("bgrd,bkgd->bgrk", qg, kb,
                       preferred_element_type=jnp.float32) * scale
        valid = _mask(kp[:, None, :], rowpos[:, :, None], window)[:, None]
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bgrk,bkgd->bgrd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return i + 1, m_new, l, acc

    m0 = jnp.full((b, kv, rows), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kv, rows), jnp.float32)
    a0 = jnp.zeros((b, kv, rows, hd), jnp.float32)
    _, _, l, acc = jax.lax.while_loop(cond, body, (jnp.int32(0), m0, l0, a0))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return (out.reshape(b, kv, sq, n_rep, hd)
            .transpose(0, 2, 1, 3, 4).reshape(b, sq, h, hd).astype(q.dtype))
