"""Attention block: projections, RoPE, KV cache, sharding-scheme selection.

Tensor-parallel scheme is chosen per config by divisibility against the model
axis (``par``):

- ``heads``  : q-heads AND kv-heads both divisible → everything head-sharded,
               zero attention collectives (Megatron style).
- ``qheads`` : only q-heads divisible (GQA, kv < par) → q/wo head-sharded,
               k/v replicated across the model axis.
- ``hd``     : heads not divisible but head_dim is → shard head_dim; QK^T
               contracts a sharded dim (partial-sum all-reduce on scores).
- ``none``   : replicate.

The baseline dry-run uses this static choice; §Perf hillclimbs revisit it
(e.g. sequence-sharded KV cache + flash-decode combine for decode cells).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed import shard
from repro.models import layers as L
from repro.models.params import Spec


def scheme(cfg, par: int) -> str:
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    if par <= 1:
        return "none"
    if H % par == 0 and KV % par == 0:
        return "heads"
    if H % par == 0:
        return "qheads"
    if hd % par == 0:
        return "hd"
    return "none"


def attn_spec(cfg, par: int) -> dict:
    H, KV, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_model
    sc = scheme(cfg, par)
    qa = "model" if sc in ("heads", "qheads") else None
    kva = "model" if sc == "heads" else None
    hda = "model" if sc == "hd" else None
    # Init scales name their fan-in (d in, H*hd out): the default takes the
    # second-minor dim, which for these head-split shapes is a head count,
    # and draws weights so large that attention saturates and the random
    # model turns chaotic (any rounding flips its outputs).
    spec = {
        "wq": Spec((d, H, hd), (None, qa, hda), scale=d ** -0.5),
        "wk": Spec((d, KV, hd), (None, kva, hda), scale=d ** -0.5),
        "wv": Spec((d, KV, hd), (None, kva, hda), scale=d ** -0.5),
        "wo": Spec((H, hd, d), (qa, hda, None), scale=(H * hd) ** -0.5),
    }
    if cfg.qkv_bias:
        spec["bq"] = Spec((H, hd), (qa, hda), "zeros")
        spec["bk"] = Spec((KV, hd), (kva, hda), "zeros")
        spec["bv"] = Spec((KV, hd), (kva, hda), "zeros")
    return spec


def cache_spec(cfg, batch: int, max_seq: int, par: int, window: int = 0) -> dict:
    """Per-layer KV cache. ``pos`` records absolute positions per slot (−1 =
    empty), which makes windowed (rolling) and full caches uniform.

    With cfg.seq_shard_cache the cache TIMELINE is sharded over the model
    axis (flash-decode): memory /par, attention partials combined with a
    tiny (m, l, acc) psum instead of replicating the cache (§Perf)."""
    KV, hd = cfg.n_kv_heads, cfg.hd
    sc = scheme(cfg, par)
    kva = "model" if sc == "heads" else None
    hda = "model" if sc == "hd" else None
    s = min(max_seq, window) if window else max_seq
    cdt = cfg.cache_dtype or None
    if cfg.seq_shard_cache and par > 1 and s % par == 0:
        return {
            "k": Spec((batch, s, KV, hd), ("batch", "model", None, None), "zeros", None, cdt),
            "v": Spec((batch, s, KV, hd), ("batch", "model", None, None), "zeros", None, cdt),
            "pos": Spec((batch, s), ("batch", "model"), "neg_ones", None, "int32"),
        }
    return {
        "k": Spec((batch, s, KV, hd), ("batch", None, kva, hda), "zeros", None, cdt),
        "v": Spec((batch, s, KV, hd), ("batch", None, kva, hda), "zeros", None, cdt),
        "pos": Spec((batch, s), ("batch", None), "neg_ones", None, "int32"),
    }


def _project_qkv(p, x, positions, cfg):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attend_full(p, x, positions, cfg, *, causal=True, window=0, prefix_len=0):
    """Training / prefill (no cache persistence). x: (B, S, d)."""
    q, k, v = _project_qkv(p, x, positions, cfg)
    if prefix_len > 0:
        out = _prefix_lm_attention(q, k, v, cfg, prefix_len, window)
    else:
        out = L.attention(q, k, v, cfg, causal=causal, window=window)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def _prefix_lm_attention(q, k, v, cfg, prefix_len: int, window: int):
    """PaliGemma-style: bidirectional over the first ``prefix_len`` positions,
    causal elsewhere. Implemented as causal + a bidirectional prefix patch."""
    b, s, h, hd = q.shape
    kk = L.repeat_kv(k, h // k.shape[2])
    vv = L.repeat_kv(v, h // v.shape[2])
    scale = hd ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                        preferred_element_type=jnp.float32) * scale
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = kpos <= qpos
    mask |= (qpos < prefix_len) & (kpos < prefix_len)
    if window:
        mask &= (kpos > qpos - window) | ((qpos < prefix_len) & (kpos < prefix_len))
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vv)


def prefill_with_cache(p, x, positions, cfg, cache, *, window=0, prefix_len=0):
    """Prefill that also fills the cache. Assumes S <= cache length."""
    q, k, v = _project_qkv(p, x, positions, cfg)
    s = x.shape[1]
    cs = cache["k"].shape[1]
    if window and s > cs:
        # Only the trailing window survives in a rolling cache.
        k_w, v_w = k[:, -cs:], v[:, -cs:]
        pos_w = positions[:, -cs:]
    else:
        k_w, v_w, pos_w = k, v, positions
    slot = pos_w % cs if window else pos_w
    bidx = jnp.arange(x.shape[0])[:, None]
    new_cache = {
        "k": cache["k"].at[bidx, slot].set(k_w.astype(cache["k"].dtype)),
        "v": cache["v"].at[bidx, slot].set(v_w.astype(cache["v"].dtype)),
        "pos": cache["pos"].at[bidx, slot].set(pos_w.astype(cache["pos"].dtype)),
    }
    if prefix_len > 0:
        out = _prefix_lm_attention(q, k, v, cfg, prefix_len, window)
    else:
        out = L.attention(q, k, v, cfg, causal=True, window=window)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), new_cache


def pos_vector(pos, b: int):
    """Normalize a decode position to a per-slot vector: a scalar (uniform
    batch) broadcasts to (B,); a (B,) vector (continuous batch — slots sit
    at different depths of their own KV timeline) passes through."""
    p = jnp.asarray(pos, jnp.int32)
    if p.ndim == 0:
        return jnp.broadcast_to(p, (b,))
    if p.shape != (b,):
        raise ValueError(f"pos must be scalar or shape ({b},), got {p.shape}")
    return p


def decode_step(p, x, pos, cfg, cache, *, window=0):
    """Decode step. x: (B, Sq, d); pos: scalar int32 absolute position or a
    (B,) vector of per-slot positions (native continuous batching).  Sq > 1
    is the multi-row (speculative-verify) step: the Sq tokens of a slot sit
    at consecutive positions ``pos .. pos+Sq-1``; all Sq candidate keys are
    scattered into the cache *before* attention, and each query row masks
    at its own depth — row j attends exactly the keys the sequential step
    at ``pos+j`` would, so rows are bit-identical to Sq single-token steps
    (rollback after rejection is just the pos timeline never advancing over
    the rejected rows; their stale keys are overwritten by the next step's
    scatter before anything attends them).
    A cache carrying a ``"table"`` leaf is **paged** (a shared block pool +
    per-slot block tables, see serve.paged): writes scatter through the
    table into physical blocks instead of into a per-slot row."""
    b, sq = x.shape[0], x.shape[1]
    posv = pos_vector(pos, b)
    positions = posv[:, None] + jnp.arange(sq, dtype=jnp.int32)
    q, k, v = _project_qkv(p, x, positions, cfg)
    if "table" in cache:
        new_cache = _paged_write(cache, k, v, positions, window)
    else:
        cs = cache["k"].shape[1]
        slot = positions % cs if window else positions  # (B, Sq)
        bidx = jnp.arange(b)[:, None]
        new_cache = {
            "k": cache["k"].at[bidx, slot].set(k.astype(cache["k"].dtype)),
            "v": cache["v"].at[bidx, slot].set(v.astype(cache["v"].dtype)),
            "pos": cache["pos"].at[bidx, slot].set(positions.astype(cache["pos"].dtype)),
        }
    out = cached_attention(q, new_cache, posv, cfg, window=window)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), new_cache


def _paged_write(cache, kt, vt, positions, window):
    """Scatter Sq tokens' K/V/pos through the block table.  kt/vt: (B, Sq,
    KV, hd); positions: (B, Sq).  Logical index = ``pos`` (full cache) or
    ``pos % ring`` (rolling: the logical capacity ``nmax*bl`` equals the
    contiguous ring size by construction, so ring layout — and therefore
    bit-identity — is preserved).  The tile index is clamped so slots whose
    position ran past their table (exited slots decoding garbage on static
    shapes) write into their table's sink entry instead of reading out of
    bounds."""
    bl = cache["k"].shape[1]
    nmax = cache["table"].shape[1]
    li = positions % (nmax * bl) if window else positions
    blk = jnp.minimum(li // bl, nmax - 1)
    off = li % bl  # (B, Sq)
    bidx = jnp.arange(positions.shape[0])[:, None]
    phys = cache["table"][bidx, blk]  # (B, Sq)
    return {
        **cache,
        "k": cache["k"].at[phys, off].set(kt.astype(cache["k"].dtype)),
        "v": cache["v"].at[phys, off].set(vt.astype(cache["v"].dtype)),
        "pos": cache["pos"].at[phys, off].set(positions.astype(cache["pos"].dtype)),
    }


def chunk_step(p, x, posv, valid, cfg, cache, *, window=0):
    """Mixed-phase prefill chunk: Sq prompt tokens per slot at consecutive
    positions ``posv .. posv+Sq-1``, row-masked by ``valid`` (B, Sq).
    Invalid rows (past the slot's prompt end, or rows of slots already
    decoding — their cursor sits at the prompt length, so every row fails
    ``valid``) neither write the cache nor leave attendable keys; their
    outputs are garbage and callers must not consume them.  Valid rows
    scatter-then-attend exactly like :func:`decode_step`, so each attends
    precisely the keys the whole-prompt prefill row at the same position
    would — that is what carries the bit-identity contract across the
    chunk/whole seam (DESIGN.md §12)."""
    b, sq = x.shape[0], x.shape[1]
    posv = pos_vector(posv, b)
    positions = posv[:, None] + jnp.arange(sq, dtype=jnp.int32)
    q, k, v = _project_qkv(p, x, positions, cfg)
    if "table" in cache:
        new_cache = _paged_chunk_write(cache, k, v, positions, valid)
    else:
        cs = cache["k"].shape[1]
        # Invalid rows scatter out of bounds and are dropped — the same
        # mechanism exited slots' decode writes rely on.
        slot = jnp.where(valid, positions, cs)
        bidx = jnp.arange(b)[:, None]
        new_cache = {
            "k": cache["k"].at[bidx, slot].set(
                k.astype(cache["k"].dtype), mode="drop"),
            "v": cache["v"].at[bidx, slot].set(
                v.astype(cache["v"].dtype), mode="drop"),
            "pos": cache["pos"].at[bidx, slot].set(
                positions.astype(cache["pos"].dtype), mode="drop"),
        }
    out = chunk_attention(q, new_cache, posv, cfg, window=window)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), new_cache


def _paged_chunk_write(cache, kt, vt, positions, valid):
    """Masked paged scatter for chunk rows: invalid rows are redirected to
    the pool's sink block (block 0 — reserved, never addressed by a live
    table) instead of writing through the slot's table.  The tile clamp
    only guards the table *gather*; masking happens on the resolved
    physical block, so a slot's real table entries are never doctored."""
    bl = cache["k"].shape[1]
    nmax = cache["table"].shape[1]
    blk = jnp.minimum(positions // bl, nmax - 1)
    off = positions % bl
    bidx = jnp.arange(positions.shape[0])[:, None]
    phys = jnp.where(valid, cache["table"][bidx, blk], 0)
    return {
        **cache,
        "k": cache["k"].at[phys, off].set(kt.astype(cache["k"].dtype)),
        "v": cache["v"].at[phys, off].set(vt.astype(cache["v"].dtype)),
        "pos": cache["pos"].at[phys, off].set(positions.astype(cache["pos"].dtype)),
    }


def chunk_attention(q, cache, posv, cfg, *, window=0):
    """Attention for mixed-phase prefill-chunk rows over the cache as
    stored: row j of slot b attends recorded positions ``<= posv[b]+j``.

    Dispatch mirrors :func:`cached_attention` with one deliberate
    difference: the Pallas tile size is the prefill kernel's 128, NOT
    ``cfg.decode_block`` — the one-shot reference for a chunk row is a
    ``flash_attention`` prefill row whose KV tiles partition at 128, and
    equal tile partitions (plus the exact-zero masked tail) are what make
    chunk rows bitwise equal to prefill rows.  Paged caches gather their
    blocks to the logical contiguous layout first for the same reason:
    ``flash_decode_paged`` tiles at block_len, which would break parity."""
    posv = pos_vector(posv, q.shape[0])
    if "table" in cache:
        tbl = cache["table"]
        b, nmax = tbl.shape
        bl = cache["k"].shape[1]

        def gather(pool):
            return pool[tbl].reshape((b, nmax * bl) + pool.shape[2:])

        k, v, kpos = gather(cache["k"]), gather(cache["v"]), gather(cache["pos"])
    else:
        k, v, kpos = cache["k"], cache["v"], cache["pos"]
    if cfg.kernel_impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops

        return kops.flash_decode(
            q, k, v, kpos, posv, window=window, block_k=128,
            interpret=cfg.kernel_impl == "pallas_interpret",
        )
    return _chunk_dense(q, k, v, kpos, posv, window=window)


def _chunk_dense(q, k, v, kpos, posv, *, window=0):
    """Dense chunk attention: ``layers.naive_attention``'s exact term order
    (the whole-prompt prefill reference — materialized repeat_kv, full
    softmax) with the positional causal mask replaced by the recorded-
    position mask.  On the cache invariant that logical index i only ever
    holds kpos ∈ {i, −1}, the two masks select identical key sets, and the
    masked tail contributes exact zeros to the (sequential) softmax sums —
    so chunk rows are bit-identical to prefill rows.  NOT ``_ragged_dense``
    (grouped-GQA einsum): the reference here is the prefill path, not the
    decode path."""
    b, sq, h, hd = q.shape
    n_rep = h // k.shape[2]
    kk = L.repeat_kv(k.astype(q.dtype), n_rep)
    vv = L.repeat_kv(v.astype(q.dtype), n_rep)
    scale = hd ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                        preferred_element_type=jnp.float32) * scale
    rowpos = posv[:, None] + jnp.arange(sq, dtype=jnp.int32)  # (B, Sq)
    mask = ragged_valid_mask(kpos[:, None, :], rowpos[:, :, None], window)
    logits = jnp.where(mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vv)


def ragged_valid_mask(kpos, pos, window: int):
    """THE ragged-decode validity predicate, shared by every decode path
    (dense fallback, seq-sharded mesh combine, and the Pallas kernel — the
    bit-identity contract requires one definition): a recorded position is
    attendable iff ``0 <= kpos <= pos`` and, for rolling caches, within the
    window.  ``kpos``/``pos`` broadcast elementwise."""
    valid = (kpos >= 0) & (kpos <= pos)
    if window > 0:
        valid &= kpos > pos - window
    return valid


def _ragged_dense(q, k, v, kpos, posv, *, window=0):
    """Dense ragged-decode attention: Sq queries per slot over the cache as
    stored, masked by recorded positions, GQA via grouped-head einsum
    reshape (no materialized ``repeat_kv`` — the eager path used to pay
    H/KV× the cache in memory traffic every step).  ``posv``: (B,) per-slot
    positions; Sq > 1 (multi-row decode, e.g. speculative verify) places
    the slot's query tokens at consecutive positions ``posv .. posv+Sq-1``,
    each masked at its own depth.  Rows are independent, so a slot's output
    is bit-identical whatever batch it shares the einsum with; a slot with
    no valid keys (pos = −1, empty cache) returns zeros — the same contract
    as the ``kernels.flash_decode`` Pallas kernel."""
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, sq, kvh, h // kvh, hd)
    logits = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k.astype(q.dtype),
                        preferred_element_type=jnp.float32) * (hd ** -0.5)
    rowpos = posv[:, None] + jnp.arange(sq, dtype=jnp.int32)  # (B, Sq)
    vm = ragged_valid_mask(kpos[:, None, :], rowpos[:, :, None],
                           window)[:, None, None, :, :]
    logits = jnp.where(vm, logits, -1e30)
    m = logits.max(axis=-1, keepdims=True)
    # Mask p explicitly (not via exp underflow): an all-empty slot has
    # m == -1e30 and exp(0) == 1 everywhere, which must not count.
    p = jnp.where(vm, jnp.exp(logits - m), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    probs = (p / jnp.maximum(l, 1e-30)).astype(q.dtype)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v.astype(q.dtype))
    return out.reshape(b, sq, h, hd)


def flash_decode_attention(q, cache, pos, cfg, *, window=0):
    """Sequence-sharded decode attention (shard_map over the model axis).

    Each model rank holds a 1/par slice of the KV timeline; it computes a
    masked partial softmax over its slice and the partials are merged with
    the online-softmax identity:

        m_g = pmax(m);  l_g = psum(l * e^{m-m_g});  acc_g = psum(acc * e^{m-m_g})

    Collectives per layer: all-gather of q (B*H*hd, ~MBs) at the shard_map
    boundary + two psums of (B,H[,hd]) — vs the replicated-cache baseline's
    per-token cache broadcast (GBs).  This is the §Perf flash-decode change.
    ``pos`` may be a (B,) per-slot vector; GQA is a grouped-head einsum
    (no repeat_kv materialization of the local cache slice).
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import batch_axes, current_mesh

    mesh = current_mesh()
    bax = batch_axes(mesh)
    b, sq, h, hd = q.shape
    assert sq == 1, "seq-sharded mesh decode is single-row (no speculative verify)"
    kvh = cache["k"].shape[2]
    n_rep = h // kvh
    scale = cfg.hd ** -0.5
    posv = pos_vector(pos, b)

    def local_fn(q, k, v, kpos, posv):
        # q: (B, 1, H, hd) replicated over model; k/v: (B, S_loc, KV, hd).
        qg = q[:, 0].reshape(q.shape[0], kvh, n_rep, hd)
        s = jnp.einsum("bgrd,bkgd->bgrk", qg, k.astype(q.dtype),
                       preferred_element_type=jnp.float32) * scale
        vm = ragged_valid_mask(kpos, posv[:, None], window)[:, None, None, :]
        s = jnp.where(vm, s, -1e30)
        m_loc = s.max(axis=-1)  # (B, KV, n_rep)
        p = jnp.where(vm, jnp.exp(s - m_loc[..., None]), 0.0)
        l_loc = p.sum(axis=-1)
        acc = jnp.einsum("bgrk,bkgd->bgrd", p.astype(q.dtype),
                         v.astype(q.dtype)).astype(jnp.float32)
        m_g = jax.lax.pmax(m_loc, "model")
        corr = jnp.exp(m_loc - m_g)
        l_g = jax.lax.psum(l_loc * corr, "model")
        acc_g = jax.lax.psum(acc * corr[..., None], "model")
        out = acc_g / jnp.maximum(l_g[..., None], 1e-30)
        return out.reshape(out.shape[0], 1, h, hd).astype(q.dtype)

    spec_q = P(bax, None, None, None)
    spec_kv = P(bax, "model", None, None)
    spec_pos = P(bax, "model")
    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(spec_q, spec_kv, spec_kv, spec_pos, P(bax)),
        out_specs=P(bax, None, None, None),
        check_vma=False,
    )
    return fn(q, cache["k"], cache["v"], cache["pos"], posv)


def _use_flash_decode(cfg, cache) -> bool:
    from repro.distributed.sharding import current_mesh

    mesh = current_mesh()
    if not cfg.seq_shard_cache or mesh is None or "model" not in mesh.axis_names:
        return False
    return cache["k"].shape[1] % mesh.shape["model"] == 0


def _paged_dense(q, cache, posv, *, window=0):
    """Dense paged-decode attention: gather the slot's physical blocks into
    the logical (B, S_log, KV, hd) layout through the block table, then run
    the SAME dense ragged kernel as the contiguous path.  The gather is a
    bit-exact permutation (logical tile i of a slot holds exactly the rows
    a contiguous cache stores at [i*bl, (i+1)*bl)), and unreserved table
    entries resolve to the pool's never-written null block (kpos = −1 →
    exactly-masked), so paged outputs are bit-identical to contiguous
    outputs on the same recorded timeline."""
    tbl = cache["table"]
    b, nmax = tbl.shape
    bl = cache["k"].shape[1]

    def gather(pool):
        g = pool[tbl]  # (B, nmax, bl, ...)
        return g.reshape((b, nmax * bl) + pool.shape[2:])

    return _ragged_dense(q, gather(cache["k"]), gather(cache["v"]),
                         gather(cache["pos"]), posv, window=window)


def cached_attention(q, cache, pos, cfg, *, window=0):
    """Attention of a single query per slot over the cache, masked by
    recorded slot positions (uniform for full and rolling caches).  ``pos``
    is a scalar (uniform batch) or a (B,) per-slot vector (continuous
    batching — the native decode path).  Dispatch: paged caches (a
    ``"table"`` leaf) go to the block-table Pallas kernel or the gather-
    dense fallback; contiguous caches to the seq-sharded mesh path when
    cfg.seq_shard_cache holds (dense local math), the ragged Pallas kernel
    under cfg.kernel_impl = pallas/pallas_interpret, else the dense
    grouped-GQA fallback."""
    posv = pos_vector(pos, q.shape[0])
    if "table" in cache:
        if cfg.kernel_impl in ("pallas", "pallas_interpret"):
            from repro.kernels import ops as kops

            return kops.flash_decode_paged(
                q, cache["k"], cache["v"], cache["pos"], cache["table"],
                posv, window=window,
                interpret=cfg.kernel_impl == "pallas_interpret",
            )
        return _paged_dense(q, cache, posv, window=window)
    if _use_flash_decode(cfg, cache):
        return flash_decode_attention(q, cache, posv, cfg, window=window)
    if cfg.kernel_impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops

        return kops.flash_decode(
            q, cache["k"], cache["v"], cache["pos"], posv, window=window,
            block_k=cfg.decode_block or 128,
            interpret=cfg.kernel_impl == "pallas_interpret",
        )
    return _ragged_dense(q, cache["k"], cache["v"], cache["pos"], posv,
                         window=window)


def init_cache_pos(cache):
    """Mark all slots empty (pos = -1)."""
    return dict(cache, pos=jnp.full_like(cache["pos"], -1))
