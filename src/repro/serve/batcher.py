"""Shape-bucketed continuous batching over the dataflow runtime.

Layering (see DESIGN.md §Serving): ``InferenceServer`` owns the request
queue and the event loop; this module owns everything between a formed
batch and the runtime —

- ``Buckets``        — prompt-length buckets.  XLA specializes executables
  on shapes, so serving free-form prompt lengths directly would compile per
  length; prompts are right-padded to the smallest bucket that fits
  (padding is part of the serving contract: a padded request generates
  exactly as one-shot generate on the padded prompt).
- ``ModelKernels``   — the jit-able Program kernels, built once per server
  and shared by every group of the same geometry so re-forming a group
  never recompiles: a *prefill* kernel (prompt rows → first token + slot-
  leading cache rows) and a *decode-segment* kernel (``seg_len`` per-slot
  decode steps rolled into one ``lax.scan``).
- ``BatchGroup``     — one live continuous batch: ``n_slots`` KV-cache
  slots backed by slot-leading buffers that form a single ``Program``,
  decoding in fixed-length segments submitted through
  ``Runtime.submit(after=prev_segment)`` — on one device group, one segment
  ahead of the one running (``BatchGroup.advance``).

Where the cache lives: when every run of the batch executes on one device
group (``BatchGroup.home``: a sole group, or a ``group_batches`` member),
the cache leaves are ``Resident`` buffers — the device holds the only
copy, the runtime writes nothing of them back, and no host mirror exists.
Otherwise (the slot axis split across groups; the paged pool) they are
host mirrors, written back after every run.

The segment Program's inputs are the previous segment's outputs, ping-pong
swapped by the run epilogue (``swap_buffers``) — so segment N+1 reads
segment N's cache as the ``Resident`` values it left on the device, and
its small token/position buffers **device-resident** from the transfer
cache (the one-bump-per-(run, buffer) rule: each segment's outputs carry
one coherent write version that the next segment's input probe looks up;
``swap_buffers`` deliberately does not re-version the swapped-in buffer).
Steady-state decode therefore performs zero host→device transfers, and
copies only tokens and positions to host.  A join writes the small buffers
on host and re-uploads them, and copies the joiners' cache rows from the
prefill's device outputs on the device (host mirrors: rewritten and
re-uploaded whole).  Per-request transfers stay O(1) however many segments
its decode spans (asserted in tests/test_server.py via
``DeviceGroup.n_transfers``).

Requests *exit* at segment boundaries (their slot is left to decode
garbage — shapes are static; a segment queued ahead may take it one segment
past the cache's last row, where writes are dropped — until a joiner
overwrites the full slot row,
which is what makes slot reuse safe: a join rewrites token, position, and
every cache leaf row, so no stale KV survives).  Requests *join* at
segment boundaries after their prefill — submitted as its own Program,
concurrently with the in-flight segment — completes; such a boundary drains
first (no segment ahead).

With multiple DeviceGroups the segment Program's slot axis is split by the
engine's scheduler (Static/Dynamic/HGuided) exactly like any co-executed
kernel: slots are the data-parallel axis, the paper's regime.
"""
from __future__ import annotations

import bisect
import math
import time
from typing import Callable, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.program import Program, Resident, copy_rows, fill_rows
from repro.core.trace import tracer
from repro.serve.step import (
    DraftSpec,
    cache_batch_axes,
    make_chunk_step,
    make_decode_step,
    make_draft_verify_step,
    make_prefill_step,
    zeros_cache,
)


# The per-slot counters of a counting family's decode step
# (``api.decode_counts``), in order: (token, held expert) pairs routed, and
# rows of the expert matmuls.
COUNTERS = ("expert_routed", "expert_rows")


def chunks_for(bucket: int, chunk_len: int, start: int = 0) -> int:
    """Mixed-phase segments a prompt needs before its first token: the
    prefill cursor advances ``chunk_len`` positions per segment from
    ``start`` (> 0 when a paged prefix hit skips leading whole blocks)."""
    return max(0, math.ceil((bucket - start) / max(1, chunk_len)))


class Buckets:
    """Prompt-length shape buckets (sorted, ascending)."""

    def __init__(self, sizes: Sequence[int]) -> None:
        if not sizes:
            raise ValueError("need at least one bucket size")
        self.sizes = sorted(set(int(s) for s in sizes))
        if self.sizes[0] < 1:
            raise ValueError(f"bucket sizes must be >= 1: {self.sizes}")

    def bucket_for(self, prompt_len: int) -> Optional[int]:
        """Smallest bucket that fits, or None (prompt too long to serve)."""
        i = bisect.bisect_left(self.sizes, prompt_len)
        return self.sizes[i] if i < len(self.sizes) else None

    @staticmethod
    def pad(prompt: np.ndarray, bucket: int, pad_id: int) -> np.ndarray:
        """Right-pad a 1-D prompt to the bucket boundary."""
        out = np.full(bucket, pad_id, np.int32)
        out[: len(prompt)] = prompt
        return out


def segments_for(new_tokens: int, seg_len: int) -> int:
    """Decode segments a request needs: the first token comes from prefill,
    the remaining ``new_tokens - 1`` from fixed-length segments."""
    return max(0, math.ceil((new_tokens - 1) / seg_len))


def spec_segments_for(new_tokens: int, seg_len: int,
                      tokens_per_step: float) -> int:
    """Expected decode segments under speculation: each of a segment's
    ``seg_len`` draft/verify steps emits ``1 + acceptance * k`` tokens in
    expectation (1..k+1 guaranteed).  ``tokens_per_step = 1.0`` degrades to
    :func:`segments_for` exactly — the non-speculative accounting is the
    zero-acceptance special case, so forecasts stay comparable."""
    tps = max(1.0, float(tokens_per_step))
    return max(0, math.ceil((new_tokens - 1) / (seg_len * tps)))


class ModelKernels:
    """Per-server kernel factory: every BatchGroup of the same geometry
    shares one kernel *object* per (kind, shape-key), so the per-group jit
    cache (``DeviceGroup.compile_kernel`` keys on kernel identity) survives
    group dissolve/re-form without recompiling.

    No kernel closes over the weights: ``jit`` compiles a closed-over array
    into the executable as a constant, which at published widths is the
    whole model in every program.  The weights ride instead as the one
    argument of every Program built by :meth:`program` (``params``, or
    ``(params, draft_params)`` when speculating), so kernels receive them
    last: ``fn(offset, *ins, weights)``."""

    def __init__(self, cfg, api, params,
                 draft: Optional[DraftSpec] = None) -> None:
        self.cfg, self.api = cfg, api
        self.weights = params if draft is None else (params, draft.params)
        # Batch-axis geometry is max_seq-independent; probe with a tiny cache.
        self.bax = cache_batch_axes(cfg, api, 8)
        self.bax_leaves = jax.tree_util.tree_leaves(self.bax)
        self.treedef = jax.tree_util.tree_structure(self.bax)
        self._seg_fns: dict = {}
        self._prefill_fns: dict = {}
        # A family that counts (``api.decode_counts``) adds a per-slot
        # counter output to plain decode segments.
        self.counted = api.decode_counts is not None
        self.draft = draft
        if draft is not None:
            from repro.models import get_model

            self.dapi = get_model(draft.cfg)
            self.dbax = cache_batch_axes(draft.cfg, self.dapi, 8)
            self.dbax_leaves = jax.tree_util.tree_leaves(self.dbax)
            self.dtreedef = jax.tree_util.tree_structure(self.dbax)

    def program(self) -> Program:
        """An empty Program whose argument is this factory's weights."""
        return Program().arg(self.weights)

    @property
    def spec_k(self) -> int:
        """Draft depth (0 = speculation off)."""
        return self.draft.k if self.draft is not None else 0

    def _leaf_specs(self, max_seq: int) -> list:
        from repro.models.params import Spec

        return jax.tree_util.tree_leaves(
            self.api.cache_spec(self.cfg, 1, max_seq, 1),
            is_leaf=lambda x: isinstance(x, Spec),
        )

    def _draft_leaf_specs(self, max_seq: int) -> list:
        from repro.models.params import Spec

        return jax.tree_util.tree_leaves(
            self.dapi.cache_spec(self.draft.cfg, 1, max_seq, 1),
            is_leaf=lambda x: isinstance(x, Spec),
        )

    def leaf_buffers(self, n_slots: int, max_seq: int, *,
                     resident: bool = False) -> list:
        """Slot-leading buffers for every cache leaf, honoring each leaf's
        declared init (position leaves are −1 = empty, the same contract
        ``zeros_cache`` enforces on device): host mirrors, or ``Resident``
        buffers created on the device when ``resident``."""
        return _slot_buffers(self._leaf_specs(max_seq), self.bax_leaves,
                             self.cfg.compute_dtype, n_slots, resident)

    def draft_leaf_buffers(self, n_slots: int, max_seq: int, *,
                           resident: bool = False) -> list:
        """Slot-leading buffers for the *draft* model's cache.  Always
        contiguous slot rows — even when the target cache is paged, the
        draft cache is small (shallow config) and transient (it carries no
        bit-identity obligation: its staleness only moves the acceptance
        rate), so paging it would buy nothing."""
        return _slot_buffers(self._draft_leaf_specs(max_seq), self.dbax_leaves,
                             self.draft.cfg.compute_dtype, n_slots, resident)

    def row_bytes(self) -> int:
        """Cache bytes one slot keeps per position, over every leaf."""
        specs = self._leaf_specs(8)
        return sum(int(np.prod(s.shape)) * np.dtype(s.dtype or
                   self.cfg.compute_dtype).itemsize for s in specs) // 8

    def leaf_neg_init(self, max_seq: int) -> List[bool]:
        """Which cache leaves record positions (init ``neg_ones``) — the
        leaves a paged pool must reset to −1 when a block is reallocated."""
        return [s.init == "neg_ones" for s in self._leaf_specs(max_seq)]

    def leaf_seq_axes(self) -> List[int]:
        """Per-leaf sequence-axis index in *mirror* coordinates (slot axis
        removed), found structurally by probing two cache lengths.  Raises
        for cache families without a per-leaf timeline (SSM/hybrid state):
        those caches cannot be paged."""
        from repro.models.params import Spec

        is_spec = lambda x: isinstance(x, Spec)  # noqa: E731
        a = jax.tree_util.tree_leaves(self.api.cache_spec(self.cfg, 1, 1, 1),
                                      is_leaf=is_spec)
        b = jax.tree_util.tree_leaves(self.api.cache_spec(self.cfg, 1, 2, 1),
                                      is_leaf=is_spec)
        axes = []
        for x, y, bax in zip(a, b, self.bax_leaves):
            sax = None
            for i, (m, n) in enumerate(zip(x.shape, y.shape)):
                if m != n:
                    sax = i
                    break
            if sax is None:
                raise ValueError(
                    f"cache leaf {x.shape} has no sequence axis: "
                    f"{self.cfg.family!r} caches cannot be paged"
                )
            axes.append(sax - 1 if sax > bax else sax)
        return axes

    def segment_kernel(self, seg_len: int) -> Callable:
        """``fn(offset, tok, pos, *cache_leaves) ->
        (toks[b, seg_len], tok', pos', *cache_leaves')`` — ``seg_len``
        per-slot decode steps (vector ``pos``: slots may sit at different
        depths) rolled into one scan, tokens/cache device-resident across
        steps.  Slot axis leads every buffer: the runtime slices it.

        The decode path is natively batched over vector positions, so the
        slot-leading mirror layout is converted to the model's native batch
        axes ONCE per segment (and back once), outside the scan — no
        per-token tree churn, no vmap expand/squeeze of every cache leaf.
        A counting family (:attr:`counted`) also returns its per-slot
        counters summed over the segment's steps, after the cache leaves."""
        fn = self._seg_fns.get(seg_len)
        if fn is not None:
            return fn
        counted = self.counted
        decode = (make_decode_step(self.cfg, self.api, counts=True) if counted
                  else make_decode_step(self.cfg, self.api))
        treedef, bax = self.treedef, self.bax
        tu = jax.tree_util

        def seg(offset, tok, pos, *rest):
            *leaves, params = rest
            cache = tu.tree_unflatten(treedef, leaves)
            cache = tu.tree_map(lambda x, a: jnp.moveaxis(x, 0, a), cache, bax)

            def body(carry, _):
                tok, pos, cache, n = carry
                if counted:
                    ntok, cache, c = decode(params, cache, tok, pos[:, 0])
                    n = n + c
                else:
                    ntok, cache = decode(params, cache, tok, pos[:, 0])
                return (ntok, pos + 1, cache, n), ntok[:, 0]

            n0 = jnp.zeros((tok.shape[0], len(COUNTERS)),
                           jnp.int32) if counted else None
            (tok, pos, cache, n), toks = jax.lax.scan(
                body, (tok, pos, cache, n0), None, length=seg_len
            )
            cache = tu.tree_map(lambda x, a: jnp.moveaxis(x, a, 0), cache, bax)
            out = (jnp.swapaxes(toks, 0, 1), tok, pos, *tu.tree_leaves(cache))
            return out + (n,) if counted else out

        self._seg_fns[seg_len] = seg
        return seg

    def paged_segment_kernel(self, seg_len: int) -> Callable:
        """Paged variant of :meth:`segment_kernel`: ``fn(offset, tok, pos,
        table, *pool_leaves) -> (toks, tok', pos', *pool_leaves')``.  Pool
        leaves are block-leading ``(n_blocks, layers, block_len, ...)``; the
        per-slot block table is broadcast across the layer axis so the
        scan-over-layers cache carry stays a uniform stacked tree, and the
        decode path (``attention._paged_write`` / ``cached_attention``)
        recognizes the ``"table"`` leaf and resolves physical blocks."""
        key = ("paged", seg_len)
        fn = self._seg_fns.get(key)
        if fn is not None:
            return fn
        decode = make_decode_step(self.cfg, self.api)
        treedef, bax = self.treedef, self.bax
        n_layers = self.cfg.n_layers
        tu = jax.tree_util

        def seg(offset, tok, pos, table, *rest):
            *leaves, params = rest
            cache = tu.tree_unflatten(treedef, leaves)
            cache = tu.tree_map(lambda x, a: jnp.moveaxis(x, 0, a), cache, bax)
            cache = dict(cache)
            cache["table"] = jnp.broadcast_to(
                table[None], (n_layers,) + table.shape
            )

            def body(carry, _):
                tok, pos, cache = carry
                ntok, cache = decode(params, cache, tok, pos[:, 0])
                return (ntok, pos + 1, cache), ntok[:, 0]

            (tok, pos, cache), toks = jax.lax.scan(
                body, (tok, pos, cache), None, length=seg_len
            )
            cache = dict(cache)
            cache.pop("table")
            cache = tu.tree_map(lambda x, a: jnp.moveaxis(x, a, 0), cache, bax)
            return (jnp.swapaxes(toks, 0, 1), tok, pos,
                    *tu.tree_leaves(cache))

        self._seg_fns[key] = seg
        return seg

    # ------------------------------------------------- mixed-phase kernels
    #
    # Chunked prefill: the decode segment Program doubles as the prefill
    # engine.  Each segment first advances every still-prefilling slot's
    # cursor by one chunk (``lax.cond``-gated — a segment with no prefilling
    # slot pays one predicate, keeping steady-state decode throughput within
    # noise of the unchunked kernel), then runs the ordinary decode scan
    # over all slots.  A slot whose prefill completes in a segment emits
    # only ``ctok`` (its first generated token, from the chunk's final
    # prompt row) that segment and starts decoding the next one — so the
    # decode scan's phase mask is the cursor as of segment entry, and the
    # still-prefilling slots' token/pos carries are restored after the scan
    # (their in-scan decode writes land at positions >= bucket, which real
    # decode later overwrites before anything attends them).

    def mixed_segment_kernel(self, seg_len: int, bucket: int,
                             chunk_len: int) -> Callable:
        """``fn(offset, tok, pos, pcur, ptoks, *cache_leaves) ->
        (toks[b, seg_len], tok', pos', pcur', ctok, *cache_leaves')`` —
        one chunk stage + ``seg_len`` decode steps.  ``pcur``: (b, 1)
        prefill cursor (``>= bucket`` ⇒ decoding); ``ptoks``: (b, bucket)
        padded-prompt buffer (pure input: uploaded once per join, served
        from the transfer cache every segment after)."""
        key = ("mixed", seg_len, bucket, chunk_len)
        fn = self._seg_fns.get(key)
        if fn is not None:
            return fn
        decode = make_decode_step(self.cfg, self.api)
        chunk = make_chunk_step(self.cfg, self.api, bucket, chunk_len)
        treedef, bax = self.treedef, self.bax
        tu = jax.tree_util

        def seg(offset, tok, pos, pcur, ptoks, *rest):
            *leaves, params = rest
            cache = tu.tree_unflatten(treedef, leaves)
            cache = tu.tree_map(lambda x, a: jnp.moveaxis(x, 0, a), cache, bax)
            decoding = pcur >= bucket  # (b, 1), phase at segment entry

            def run_chunk(cache):
                return chunk(params, cache, ptoks, pcur)

            def skip_chunk(cache):
                return jnp.zeros_like(tok), pcur, cache

            ctok, pcur2, cache = jax.lax.cond(
                jnp.any(~decoding), run_chunk, skip_chunk, cache)

            def body(carry, _):
                tok, pos, cache = carry
                ntok, cache = decode(params, cache, tok, pos[:, 0])
                return (ntok, pos + 1, cache), ntok[:, 0]

            (tok2, pos2, cache), toks = jax.lax.scan(
                body, (tok, pos, cache), None, length=seg_len
            )
            completed = ~decoding & (pcur2 >= bucket)
            tok_out = jnp.where(decoding, tok2, jnp.where(completed, ctok, tok))
            pos_out = jnp.where(decoding, pos2, pos)
            cache = tu.tree_map(lambda x, a: jnp.moveaxis(x, a, 0), cache, bax)
            return (jnp.swapaxes(toks, 0, 1), tok_out, pos_out, pcur2, ctok,
                    *tu.tree_leaves(cache))

        self._seg_fns[key] = seg
        return seg

    def paged_mixed_segment_kernel(self, seg_len: int, bucket: int,
                                   chunk_len: int) -> Callable:
        """Paged variant: ``fn(offset, tok, pos, pcur, ptoks, table,
        *pool_leaves) -> (toks, tok', pos', pcur', ctok, *pool_leaves')``.
        Chunk writes resolve physical blocks through the table exactly like
        decode writes (invalid rows land in the sink block)."""
        key = ("paged_mixed", seg_len, bucket, chunk_len)
        fn = self._seg_fns.get(key)
        if fn is not None:
            return fn
        decode = make_decode_step(self.cfg, self.api)
        chunk = make_chunk_step(self.cfg, self.api, bucket, chunk_len)
        treedef, bax = self.treedef, self.bax
        n_layers = self.cfg.n_layers
        tu = jax.tree_util

        def seg(offset, tok, pos, pcur, ptoks, table, *rest):
            *leaves, params = rest
            cache = tu.tree_unflatten(treedef, leaves)
            cache = tu.tree_map(lambda x, a: jnp.moveaxis(x, 0, a), cache, bax)
            cache = dict(cache)
            cache["table"] = jnp.broadcast_to(
                table[None], (n_layers,) + table.shape
            )
            decoding = pcur >= bucket

            def run_chunk(cache):
                return chunk(params, cache, ptoks, pcur)

            def skip_chunk(cache):
                return jnp.zeros_like(tok), pcur, cache

            ctok, pcur2, cache = jax.lax.cond(
                jnp.any(~decoding), run_chunk, skip_chunk, cache)

            def body(carry, _):
                tok, pos, cache = carry
                ntok, cache = decode(params, cache, tok, pos[:, 0])
                return (ntok, pos + 1, cache), ntok[:, 0]

            (tok2, pos2, cache), toks = jax.lax.scan(
                body, (tok, pos, cache), None, length=seg_len
            )
            completed = ~decoding & (pcur2 >= bucket)
            tok_out = jnp.where(decoding, tok2, jnp.where(completed, ctok, tok))
            pos_out = jnp.where(decoding, pos2, pos)
            cache = dict(cache)
            cache.pop("table")
            cache = tu.tree_map(lambda x, a: jnp.moveaxis(x, a, 0), cache, bax)
            return (jnp.swapaxes(toks, 0, 1), tok_out, pos_out, pcur2, ctok,
                    *tu.tree_leaves(cache))

        self._seg_fns[key] = seg
        return seg

    def prefill_kernel(self, max_seq: int) -> Callable:
        """``fn(offset, tokens[b, S_b]) -> (tok0[b, 1], *slot_leading_cache)``
        — batched prefill against a fresh ``zeros_cache``; rows are
        independent, so the runtime may split requests across groups."""
        fn = self._prefill_fns.get(max_seq)
        if fn is not None:
            return fn
        prefill = make_prefill_step(self.cfg, self.api)
        cfg, api, bax = self.cfg, self.api, self.bax_leaves

        def pre(offset, tokens, params):
            cache = zeros_cache(cfg, api, tokens.shape[0], max_seq)
            tok, cache = prefill(params, {"tokens": tokens}, cache)
            leaves = [jnp.moveaxis(x, a, 0)
                      for x, a in zip(jax.tree_util.tree_leaves(cache), bax)]
            return (tok, *leaves)

        self._prefill_fns[max_seq] = pre
        return pre

    # ------------------------------------------------- speculative kernels
    def _spec_step(self):
        return make_draft_verify_step(self.cfg, self.api, self.draft.cfg,
                                      self.dapi, self.draft.k)

    def _spec_scan(self, seg_len: int, step, weights, tok, ptok, pos,
                   tcache, dcache):
        """Shared draft/verify segment body: ``seg_len`` speculative steps,
        each emitting 1..k+1 tokens, cursor-scattered into one flat
        ``(b, seg_len*(k+1))`` buffer.  Beyond each slot's final cursor the
        buffer holds garbage (rejected-row argmaxes) — exactly like the
        positions past ``need`` in the non-spec ``toks_seg``; harvest only
        reads ``buf[:cnt]``.  Returns (buf, cnt, tok, ptok, pos, caches)."""
        k = self.draft.k
        params, dparams = weights
        b = tok.shape[0]
        buf = jnp.zeros((b, seg_len * (k + 1)), jnp.int32)
        cur = jnp.zeros((b,), jnp.int32)
        bidx = jnp.arange(b)

        def body(carry, _):
            tok, ptok, pos, cur, tc, dc, buf = carry
            y, cnt, tok, ptok, pos, tc, dc = step(
                params, dparams, tc, dc, tok, ptok, pos[:, 0]
            )
            # Scatter all k+1 verified rows at the cursor; the accepted
            # prefix lands at buf[cur:cur+cnt], and the next step's scatter
            # (at cur+cnt) overwrites the rejected overhang before harvest
            # can see it mid-buffer.
            buf = buf.at[bidx[:, None], cur[:, None] + jnp.arange(k + 1)].set(y)
            return (tok, ptok, pos[:, None], cur + cnt, tc, dc, buf), None

        carry = (tok, ptok, pos, cur, tcache, dcache, buf)
        (tok, ptok, pos, cur, tcache, dcache, buf), _ = jax.lax.scan(
            body, carry, None, length=seg_len
        )
        return buf, cur[:, None], tok, ptok, pos, tcache, dcache

    def _plain_scan(self, seg_len: int, decode, weights, tok, ptok, pos,
                    tcache, dcache):
        """Bypass branch of the speculative segment: ``seg_len`` plain
        decode steps on the target cache only, shaped like
        :meth:`_spec_scan`'s outputs (``cnt = seg_len`` per slot, tokens in
        ``buf[:seg_len]``) so harvest reads either branch identically.
        Greedy decode makes the emitted bits equal to the draft/verify
        path's — bypass never changes served streams.  The draft cache
        passes through untouched: its staleness on a later re-probe only
        lowers the acceptance rate, never correctness (verify is always
        against the target)."""
        k = self.draft.k
        params, _ = weights
        b = tok.shape[0]

        def body(carry, _):
            tok, pos, cache = carry
            ntok, cache = decode(params, cache, tok, pos[:, 0])
            return (ntok, pos + 1, cache), ntok[:, 0]

        (tok2, pos2, tcache), toks = jax.lax.scan(
            body, (tok, pos, tcache), None, length=seg_len
        )
        toks = jnp.swapaxes(toks, 0, 1)  # (b, seg_len)
        buf = jnp.zeros((b, seg_len * (k + 1)), jnp.int32)
        buf = buf.at[:, :seg_len].set(toks)
        cnt = jnp.full((b, 1), seg_len, jnp.int32)
        # tok2's predecessor: the segment's second-to-last emission (or the
        # incoming tok for seg_len=1) — what the first draft step re-decodes
        # when speculation resumes.
        ptok2 = toks[:, seg_len - 2:seg_len - 1] if seg_len > 1 else tok
        return buf, cnt, tok2, ptok2, pos2, tcache, dcache

    def _gated_scan(self, seg_len: int, step, decode, weights, spec_on,
                    tok, ptok, pos, tcache, dcache):
        """Segment-granular draft on/off switch: one host-written flag
        (``spec_on[0, 0]``) selects draft/verify or plain decode via
        ``lax.cond`` — flipping modes is a tiny buffer invalidation, never
        a rebuild or recompile."""

        def spec_branch(op):
            return self._spec_scan(seg_len, step, weights, *op)

        def plain_branch(op):
            return self._plain_scan(seg_len, decode, weights, *op)

        return jax.lax.cond(spec_on[0, 0] > 0, spec_branch, plain_branch,
                            (tok, ptok, pos, tcache, dcache))

    def spec_segment_kernel(self, seg_len: int) -> Callable:
        """Speculative variant of :meth:`segment_kernel`:
        ``fn(offset, tok, ptok, pos, *target_leaves, *draft_leaves) ->
        (toks[b, seg_len*(k+1)], cnt[b, 1], tok', ptok', pos', *leaves')``.
        Each scan step drafts ``k`` candidates and verifies them in one
        multi-row decode; slots advance 1..k+1 positions per step (ragged
        tokens-per-step), with ``cnt`` reporting how many of the flat token
        buffer's entries are real."""
        key = ("spec", seg_len)
        fn = self._seg_fns.get(key)
        if fn is not None:
            return fn
        step = self._spec_step()
        decode = make_decode_step(self.cfg, self.api)
        treedef, bax = self.treedef, self.bax
        dtreedef, dbax = self.dtreedef, self.dbax
        nt = len(self.bax_leaves)
        tu = jax.tree_util

        def seg(offset, tok, ptok, pos, *rest):
            *leaves, spec_on, weights = rest
            tcache = tu.tree_unflatten(treedef, leaves[:nt])
            tcache = tu.tree_map(lambda x, a: jnp.moveaxis(x, 0, a), tcache, bax)
            dcache = tu.tree_unflatten(dtreedef, leaves[nt:])
            dcache = tu.tree_map(lambda x, a: jnp.moveaxis(x, 0, a), dcache, dbax)
            buf, cnt, tok, ptok, pos, tcache, dcache = self._gated_scan(
                seg_len, step, decode, weights, spec_on, tok, ptok, pos,
                tcache, dcache
            )
            tcache = tu.tree_map(lambda x, a: jnp.moveaxis(x, a, 0), tcache, bax)
            dcache = tu.tree_map(lambda x, a: jnp.moveaxis(x, a, 0), dcache, dbax)
            return (buf, cnt, tok, ptok, pos,
                    *tu.tree_leaves(tcache), *tu.tree_leaves(dcache))

        self._seg_fns[key] = seg
        return seg

    def paged_spec_segment_kernel(self, seg_len: int) -> Callable:
        """Paged-target speculative segment: ``fn(offset, tok, ptok, pos,
        table, *pool_leaves, *draft_leaves) -> (toks, cnt, tok', ptok',
        pos', *pool_leaves', *draft_leaves')``.  The target cache resolves
        physical blocks through the table exactly as
        :meth:`paged_segment_kernel`; the draft cache stays contiguous."""
        key = ("paged_spec", seg_len)
        fn = self._seg_fns.get(key)
        if fn is not None:
            return fn
        step = self._spec_step()
        decode = make_decode_step(self.cfg, self.api)
        treedef, bax = self.treedef, self.bax
        dtreedef, dbax = self.dtreedef, self.dbax
        nt = len(self.bax_leaves)
        n_layers = self.cfg.n_layers
        tu = jax.tree_util

        def seg(offset, tok, ptok, pos, table, *rest):
            *leaves, spec_on, weights = rest
            tcache = tu.tree_unflatten(treedef, leaves[:nt])
            tcache = tu.tree_map(lambda x, a: jnp.moveaxis(x, 0, a), tcache, bax)
            tcache = dict(tcache)
            tcache["table"] = jnp.broadcast_to(
                table[None], (n_layers,) + table.shape
            )
            dcache = tu.tree_unflatten(dtreedef, leaves[nt:])
            dcache = tu.tree_map(lambda x, a: jnp.moveaxis(x, 0, a), dcache, dbax)
            buf, cnt, tok, ptok, pos, tcache, dcache = self._gated_scan(
                seg_len, step, decode, weights, spec_on, tok, ptok, pos,
                tcache, dcache
            )
            tcache = dict(tcache)
            tcache.pop("table")
            tcache = tu.tree_map(lambda x, a: jnp.moveaxis(x, a, 0), tcache, bax)
            dcache = tu.tree_map(lambda x, a: jnp.moveaxis(x, a, 0), dcache, dbax)
            return (buf, cnt, tok, ptok, pos,
                    *tu.tree_leaves(tcache), *tu.tree_leaves(dcache))

        self._seg_fns[key] = seg
        return seg

    def _mixed_chunk_stage(self, bucket: int, chunk_len: int):
        """Shared chunk stage for the speculative mixed kernels: advances
        BOTH caches' prompt state — the target via the bit-identity chunk
        path, the draft via the same masked chunk path (its logits are
        discarded; draft-cache content only moves the acceptance rate,
        never emitted bits)."""
        chunk = make_chunk_step(self.cfg, self.api, bucket, chunk_len)
        dchunk = make_chunk_step(self.draft.cfg, self.dapi, bucket, chunk_len)

        def stage(weights, tok, pcur, ptoks, tcache, dcache, decoding):
            params, dparams = weights

            def run(op):
                tc, dc = op
                ctok, pcur2, tc = chunk(params, tc, ptoks, pcur)
                _, _, dc = dchunk(dparams, dc, ptoks, pcur)
                return ctok, pcur2, tc, dc

            def skip(op):
                tc, dc = op
                return jnp.zeros_like(tok), pcur, tc, dc

            return jax.lax.cond(jnp.any(~decoding), run, skip,
                                (tcache, dcache))

        return stage

    def spec_mixed_segment_kernel(self, seg_len: int, bucket: int,
                                  chunk_len: int) -> Callable:
        """Speculative mixed segment: ``fn(offset, tok, ptok, pos, pcur,
        ptoks, *target_leaves, *draft_leaves) -> (toks, cnt, tok', ptok',
        pos', pcur', ctok, *leaves')``.  A slot completing prefill leaves
        the segment with ``tok' = ctok`` and ``ptok' = ptoks[:, bucket-1]``
        (the prompt's last token — the predecessor the first draft step
        re-decodes), starting draft/verify next segment."""
        key = ("spec_mixed", seg_len, bucket, chunk_len)
        fn = self._seg_fns.get(key)
        if fn is not None:
            return fn
        step = self._spec_step()
        decode = make_decode_step(self.cfg, self.api)
        stage = self._mixed_chunk_stage(bucket, chunk_len)
        treedef, bax = self.treedef, self.bax
        dtreedef, dbax = self.dtreedef, self.dbax
        nt = len(self.bax_leaves)
        tu = jax.tree_util

        def seg(offset, tok, ptok, pos, pcur, ptoks, *rest):
            *leaves, spec_on, weights = rest
            tcache = tu.tree_unflatten(treedef, leaves[:nt])
            tcache = tu.tree_map(lambda x, a: jnp.moveaxis(x, 0, a), tcache, bax)
            dcache = tu.tree_unflatten(dtreedef, leaves[nt:])
            dcache = tu.tree_map(lambda x, a: jnp.moveaxis(x, 0, a), dcache, dbax)
            decoding = pcur >= bucket
            ctok, pcur2, tcache, dcache = stage(
                weights, tok, pcur, ptoks, tcache, dcache, decoding)
            buf, cnt, tok2, ptok2, pos2, tcache, dcache = self._gated_scan(
                seg_len, step, decode, weights, spec_on, tok, ptok, pos,
                tcache, dcache
            )
            completed = ~decoding & (pcur2 >= bucket)
            last_ptok = ptoks[:, bucket - 1:bucket]
            tok_out = jnp.where(decoding, tok2, jnp.where(completed, ctok, tok))
            ptok_out = jnp.where(decoding, ptok2,
                                 jnp.where(completed, last_ptok, ptok))
            pos_out = jnp.where(decoding, pos2, pos)
            tcache = tu.tree_map(lambda x, a: jnp.moveaxis(x, a, 0), tcache, bax)
            dcache = tu.tree_map(lambda x, a: jnp.moveaxis(x, a, 0), dcache, dbax)
            return (buf, cnt, tok_out, ptok_out, pos_out, pcur2, ctok,
                    *tu.tree_leaves(tcache), *tu.tree_leaves(dcache))

        self._seg_fns[key] = seg
        return seg

    def paged_spec_mixed_segment_kernel(self, seg_len: int, bucket: int,
                                        chunk_len: int) -> Callable:
        """Paged-target speculative mixed segment: ``fn(offset, tok, ptok,
        pos, pcur, ptoks, table, *pool_leaves, *draft_leaves) -> (toks,
        cnt, tok', ptok', pos', pcur', ctok, *leaves')``."""
        key = ("paged_spec_mixed", seg_len, bucket, chunk_len)
        fn = self._seg_fns.get(key)
        if fn is not None:
            return fn
        step = self._spec_step()
        decode = make_decode_step(self.cfg, self.api)
        stage = self._mixed_chunk_stage(bucket, chunk_len)
        treedef, bax = self.treedef, self.bax
        dtreedef, dbax = self.dtreedef, self.dbax
        nt = len(self.bax_leaves)
        n_layers = self.cfg.n_layers
        tu = jax.tree_util

        def seg(offset, tok, ptok, pos, pcur, ptoks, table, *rest):
            *leaves, spec_on, weights = rest
            tcache = tu.tree_unflatten(treedef, leaves[:nt])
            tcache = tu.tree_map(lambda x, a: jnp.moveaxis(x, 0, a), tcache, bax)
            tcache = dict(tcache)
            tcache["table"] = jnp.broadcast_to(
                table[None], (n_layers,) + table.shape
            )
            dcache = tu.tree_unflatten(dtreedef, leaves[nt:])
            dcache = tu.tree_map(lambda x, a: jnp.moveaxis(x, 0, a), dcache, dbax)
            decoding = pcur >= bucket
            ctok, pcur2, tcache, dcache = stage(
                weights, tok, pcur, ptoks, tcache, dcache, decoding)
            buf, cnt, tok2, ptok2, pos2, tcache, dcache = self._gated_scan(
                seg_len, step, decode, weights, spec_on, tok, ptok, pos,
                tcache, dcache
            )
            completed = ~decoding & (pcur2 >= bucket)
            last_ptok = ptoks[:, bucket - 1:bucket]
            tok_out = jnp.where(decoding, tok2, jnp.where(completed, ctok, tok))
            ptok_out = jnp.where(decoding, ptok2,
                                 jnp.where(completed, last_ptok, ptok))
            pos_out = jnp.where(decoding, pos2, pos)
            tcache = dict(tcache)
            tcache.pop("table")
            tcache = tu.tree_map(lambda x, a: jnp.moveaxis(x, a, 0), tcache, bax)
            dcache = tu.tree_map(lambda x, a: jnp.moveaxis(x, a, 0), dcache, dbax)
            return (buf, cnt, tok_out, ptok_out, pos_out, pcur2, ctok,
                    *tu.tree_leaves(tcache), *tu.tree_leaves(dcache))

        self._seg_fns[key] = seg
        return seg

    def draft_leaf_neg_init(self, max_seq: int) -> List[bool]:
        """Draft-cache analog of :meth:`leaf_neg_init` (chunked joins reset
        position leaves of BOTH caches in place of a prefill rewrite)."""
        return [s.init == "neg_ones" for s in self._draft_leaf_specs(max_seq)]

    def spec_prefill_kernel(self, max_seq: int) -> Callable:
        """Prefill for speculative slots: runs the target *and* the draft
        prefill over the same prompt rows, so a joining slot lands with both
        caches populated through the prompt.  ``fn(offset, tokens) ->
        (tok0, ptok0, *target_leaves, *draft_leaves)`` where ``ptok0`` is
        the padded prompt's last token (position ``bucket - 1``) — the
        predecessor the first draft step rewrites."""
        key = ("spec", max_seq)
        fn = self._prefill_fns.get(key)
        if fn is not None:
            return fn
        prefill = make_prefill_step(self.cfg, self.api)
        dprefill = make_prefill_step(self.draft.cfg, self.dapi)
        cfg, api, dcfg = self.cfg, self.api, self.draft.cfg
        dapi, bax, dbax = self.dapi, self.bax_leaves, self.dbax_leaves

        def pre(offset, tokens, weights):
            params, dparams = weights
            b = tokens.shape[0]
            cache = zeros_cache(cfg, api, b, max_seq)
            tok, cache = prefill(params, {"tokens": tokens}, cache)
            dcache = zeros_cache(dcfg, dapi, b, max_seq)
            _, dcache = dprefill(dparams, {"tokens": tokens}, dcache)
            ptok = tokens[:, -1:].astype(jnp.int32)
            tl = [jnp.moveaxis(x, a, 0)
                  for x, a in zip(jax.tree_util.tree_leaves(cache), bax)]
            dl = [jnp.moveaxis(x, a, 0)
                  for x, a in zip(jax.tree_util.tree_leaves(dcache), dbax)]
            return (tok, ptok, *tl, *dl)

        self._prefill_fns[key] = pre
        return pre


class BatchGroup:
    """One live continuous batch for one bucket.  All mutating methods are
    called from the server's single batcher thread; the runtime's worker
    threads only touch the handles (and fire done-callbacks)."""

    def __init__(self, kernels: ModelKernels, runtime, scheduler,
                 bucket: int, n_slots: int, seg_len: int, max_seq: int,
                 chunk_len: int = 0, target=None) -> None:
        self.kernels = kernels
        self.runtime = runtime
        self.scheduler = scheduler
        self.bucket = bucket
        self.n_slots = n_slots
        self.seg_len = seg_len
        self.max_seq = max_seq
        self.chunk_len = chunk_len  # 0 = whole-prompt prefill Programs
        self.spec_k = kernels.spec_k  # draft depth; 0 = speculation off
        # Device groups this batch's runs are pinned to (None = all runtime
        # groups, the legacy slot-splitting co-exec regime).  Per-group
        # serving sub-batches pin to exactly one group each.
        self.target = list(target) if target else None
        self.spec_gate = None  # set by the server when drafting (SpecGate)
        self._seg_mode = "spec" if self.spec_k else "plain"
        self.slots: List[Optional[object]] = [None] * n_slots  # _Request per slot
        self.dead = False
        self.tokens_written = 0  # KV positions actually written (memory_stats)
        self.last_run_metrics: dict = {}
        self.telemetry = None  # set by the owning InferenceServer
        # The one device group every run of this batch executes on; its
        # cache leaves are then ``Resident`` buffers, kept on that group's
        # device.  None: runs split across groups, or a kernel-only group
        # (no runtime), keep host mirrors of the cache.
        self.home = self._home_group()
        # A plain segment chained on one group can be queued on the device
        # behind the one in flight (``Runtime._process``): this batch then
        # keeps one segment ahead (:meth:`advance`).
        self.ahead = self.home is not None and not (self.spec_k or chunk_len)
        # Harvest-only outputs (never inputs) rotate with a spare at each
        # segment, so a harvest reads the buffers its own segment wrote.
        self._spares: dict = {}
        self._row_bytes = kernels.row_bytes() if kernels.counted else 0
        with tracer().span("form_group", track="batcher", bucket=bucket):
            self._build_segment_program()
        # Segments in flight, oldest first: (handle, request per slot when
        # it was submitted, submit time).
        self._segs: List[tuple] = []
        self.prev_handle = None
        self._draining = False  # no segment ahead until the next boundary
        self._drain_t0: Optional[float] = None
        # -- in-flight prefill wave ----------------------------------------
        self.prefill_handle = None
        self.prefill_wave: List[object] = []
        self._prefill_prog: Optional[Program] = None
        self._prefill_t0 = 0.0

    def _home_group(self):
        """The runtime's one device group when this batch's runs are pinned
        to exactly one (``RunHandle.on_one_group``), else None."""
        if self.runtime is None:
            return None
        groups = self.target or self.runtime.groups
        return groups[0] if len(groups) == 1 else None

    def _leaf_buffers(self, n_slots: int) -> list:
        """Target- then (speculating) draft-cache buffers of ``n_slots``
        rows: ``Resident`` on :attr:`home`, else host mirrors."""
        resident = self.home is not None
        leaves = self.kernels.leaf_buffers(n_slots, self.max_seq,
                                           resident=resident)
        if self.spec_k:
            leaves += self.kernels.draft_leaf_buffers(n_slots, self.max_seq,
                                                      resident=resident)
        return leaves

    def _build_segment_program(self) -> None:
        """Contiguous layout: slot-leading cache leaves, ping-pong in/out
        pairs (PagedBatchGroup overrides this with pool buffers + block
        table)."""
        kernels, n_slots, seg_len = self.kernels, self.n_slots, self.seg_len
        tok = np.zeros((n_slots, 1), np.int32)
        pos = np.zeros((n_slots, 1), np.int32)
        leaves = self._leaf_buffers(n_slots)
        if self.chunk_len:
            self._build_mixed_program(tok, pos, leaves)
            return
        if self.spec_k:
            # Speculative layout: a predecessor-token buffer joins the
            # carry (the first draft step re-decodes [ptok, tok] to repair
            # the draft-cache hole), the draft model's cache mirrors ride
            # behind the target's on the same donate/swap machinery, and
            # the token buffer widens to the per-segment emission *cap*
            # seg_len*(k+1) with a per-slot count of how much is real.
            k = self.spec_k
            ptok = np.zeros((n_slots, 1), np.int32)
            toks_seg = np.zeros((n_slots, seg_len * (k + 1)), np.int32)
            prog = kernels.program().in_(tok).in_(ptok).in_(pos)
            for b in leaves:
                prog.in_(b)
            # spec_on rides LAST (after every donated leaf) so the donate
            # range and every leaf slice below stay position-stable; the
            # kernel branches on it per segment (SpecGate auto-bypass).
            self._spec_on = np.ones((n_slots, 1), np.int32)
            prog.in_(self._spec_on)
            prog.out(toks_seg).out(np.zeros((n_slots, 1), np.int32))
            prog.out(np.zeros_like(tok)).out(np.zeros_like(ptok))
            prog.out(np.zeros_like(pos))
            for b in leaves:
                prog.out(_blank(b))
            prog.kernel(kernels.spec_segment_kernel(seg_len),
                        f"spec_seg{seg_len}_k{k}")
            prog.donate(*range(3, 3 + len(leaves)))
            prog.work_items(n_slots, 1)
            self.prog = prog
            self.n_leaves = len(leaves)
            # toks_seg (out 0) and cnt (out 1) are read-only harvest buffers;
            # tok/ptok/pos and every cache leaf ping-pong.
            self._swap_pairs = [(0, 2), (1, 3), (2, 4)] + [
                (3 + i, 5 + i) for i in range(self.n_leaves)
            ]
            return
        toks_seg = np.zeros((n_slots, seg_len), np.int32)
        prog = kernels.program().in_(tok).in_(pos)
        for b in leaves:
            prog.in_(b)
        prog.out(toks_seg).out(np.zeros_like(tok)).out(np.zeros_like(pos))
        for b in leaves:
            prog.out(_blank(b))
        if kernels.counted:  # the per-slot counters, after the leaves
            prog.out(np.zeros((n_slots, len(COUNTERS)), np.int32))
        if self.ahead:
            self._spares = {k: np.zeros_like(prog._outs[k]) for k in
                            ([0, len(prog._outs) - 1] if kernels.counted
                             else [0])}
        prog.kernel(kernels.segment_kernel(seg_len), f"decode_seg{seg_len}")
        # Donate the cache-leaf inputs (mirroring make_generate's
        # donate_argnums=(1,)): each segment's jitted kernel updates the KV
        # slots in place on device instead of copying the full cache per
        # segment.  Safe because segments chain serially (after=prev) and
        # the donated device slices are consumed from the transfer cache.
        prog.donate(*range(2, 2 + len(leaves)))
        prog.work_items(n_slots, 1)
        self.prog = prog
        self.n_leaves = len(leaves)
        # (in_index, out_index) ping-pong pairs: tok, pos, every cache leaf.
        self._swap_pairs = [(0, 1), (1, 2)] + [
            (2 + i, 3 + i) for i in range(self.n_leaves)
        ]

    def _build_mixed_program(self, tok, pos, leaves) -> None:
        """Mixed-phase (chunked-prefill) segment Program.  Two extra carried
        buffers join the layout: ``pcur`` (the per-slot prefill cursor,
        ping-ponged — initialized to ``bucket`` so empty slots read as
        decoding and the chunk stage's ``lax.cond`` stays cold) and
        ``ptoks`` (the padded-prompt buffer, a pure non-donated input: one
        upload per join, transfer-cache hits every segment after).  ``ctok``
        (each slot's first generated token, meaningful the segment its
        prefill completes) is a pure output, never swapped."""
        kernels, n_slots, seg_len = self.kernels, self.n_slots, self.seg_len
        pcur = np.full((n_slots, 1), self.bucket, np.int32)
        ptoks = np.zeros((n_slots, self.bucket), np.int32)
        if self.spec_k:
            k = self.spec_k
            ptok = np.zeros((n_slots, 1), np.int32)
            toks_seg = np.zeros((n_slots, seg_len * (k + 1)), np.int32)
            prog = (kernels.program().in_(tok).in_(ptok).in_(pos).in_(pcur)
                    .in_(ptoks))
            for b in leaves:
                prog.in_(b)
            self._spec_on = np.ones((n_slots, 1), np.int32)
            prog.in_(self._spec_on)
            prog.out(toks_seg).out(np.zeros((n_slots, 1), np.int32))
            prog.out(np.zeros_like(tok)).out(np.zeros_like(ptok))
            prog.out(np.zeros_like(pos)).out(np.zeros_like(pcur))
            prog.out(np.zeros_like(tok))  # ctok
            for b in leaves:
                prog.out(_blank(b))
            prog.kernel(
                kernels.spec_mixed_segment_kernel(seg_len, self.bucket,
                                                  self.chunk_len),
                f"spec_mixed_seg{seg_len}_b{self.bucket}_c{self.chunk_len}_k{k}")
            prog.donate(*range(5, 5 + len(leaves)))
            prog.work_items(n_slots, 1)
            self.prog = prog
            self.n_leaves = len(leaves)
            self._swap_pairs = [(0, 2), (1, 3), (2, 4), (3, 5)] + [
                (5 + i, 7 + i) for i in range(self.n_leaves)
            ]
            self._ctok_out = 6
            return
        toks_seg = np.zeros((n_slots, seg_len), np.int32)
        prog = kernels.program().in_(tok).in_(pos).in_(pcur).in_(ptoks)
        for b in leaves:
            prog.in_(b)
        prog.out(toks_seg).out(np.zeros_like(tok)).out(np.zeros_like(pos))
        prog.out(np.zeros_like(pcur)).out(np.zeros_like(tok))  # pcur', ctok
        for b in leaves:
            prog.out(_blank(b))
        prog.kernel(
            kernels.mixed_segment_kernel(seg_len, self.bucket, self.chunk_len),
            f"mixed_seg{seg_len}_b{self.bucket}_c{self.chunk_len}")
        prog.donate(*range(4, 4 + len(leaves)))
        prog.work_items(n_slots, 1)
        self.prog = prog
        self.n_leaves = len(leaves)
        self._swap_pairs = [(0, 1), (1, 2), (2, 3)] + [
            (4 + i, 5 + i) for i in range(self.n_leaves)
        ]
        self._ctok_out = 4

    # ------------------------------------------------------------- queries
    @property
    def seg_handle(self):
        """The oldest segment in flight, or None."""
        return self._segs[0][0] if self._segs else None

    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slots) if r is None]

    def active(self) -> List[tuple]:
        return [(i, r) for i, r in enumerate(self.slots) if r is not None]

    def idle(self) -> bool:
        return (self.seg_handle is None and self.prefill_handle is None
                and not any(self.slots))

    # ----------------------------------------------------- memory interface
    def reserve_estimate(self, req) -> int:
        """Blocks this request would reserve (0: contiguous slots are
        pre-allocated — memory admission never defers)."""
        return 0

    def memory_available(self, already_reserved: int) -> float:
        return math.inf

    def memory_stats(self) -> dict:
        """KV memory accounting, comparable across layouts: contiguous
        groups allocate their full capacity up front (every slot row at
        ``max_seq``, whatever depth is recorded)."""
        first_leaf = (3 if self.spec_k else 2) + (2 if self.chunk_len else 0)
        allocated = sum(
            b.nbytes
            for b in self.prog._ins[first_leaf:first_leaf + self.n_leaves]
        )
        capacity = self.n_slots * self.max_seq
        return {
            "mode": "contiguous",
            "kv_bytes_allocated": allocated,
            "kv_bytes_device": allocated,
            "kv_bytes_touched": int(
                allocated * self.tokens_written / max(1, capacity)
            ),
            "tokens_written": self.tokens_written,
        }

    # ------------------------------------------------------------- prefill
    def _plan_prefill(self, requests: Sequence) -> List:
        """Pick which wave members need a prefill row (all of them for the
        contiguous layout; the paged override shares prefix blocks and
        skips rows whose whole prompt is cached)."""
        return list(requests)

    def start_prefill(self, requests: Sequence, notify: Callable) -> None:
        """Submit one prefill Program for a join wave (≤ free slots).  Runs
        concurrently with any in-flight decode segment: no shared buffers,
        so the run graph infers no edge between them."""
        assert self.prefill_handle is None
        assert len(requests) <= len(self.free_slots())
        self.prefill_wave = list(requests)
        self._prefill_t0 = _now()
        if self.chunk_len:
            # Chunked mode: there is no prefill Program — joining slots are
            # armed host-side (merge) and the segment kernel's chunk stage
            # does the prefill compute.  Planning still runs (the paged
            # override pins whole-prompt cache hits there); the join state
            # machine completes through an already-done handle.
            from repro.serve.paged import _DoneHandle

            self._plan_prefill(requests)
            self._prefill_prog = None
            h = _DoneHandle()
            self.prefill_handle = h
            h.add_done_callback(lambda _h: notify())
            return
        rows = self._plan_prefill(requests)
        if not rows:
            # Every request hit the whole-prompt cache: nothing to run, but
            # the merge state machine still expects a completed handle.
            from repro.serve.paged import _DoneHandle

            self._prefill_prog = None
            h = _DoneHandle()
        else:
            j = len(rows)
            tokens = np.stack([r.prompt for r in rows]).astype(np.int32)
            prog = self.kernels.program().in_(tokens)
            prog.out(np.zeros((j, 1), np.int32))
            if self.spec_k:
                prog.out(np.zeros((j, 1), np.int32))  # ptok0
                prog.kernel(self.kernels.spec_prefill_kernel(self.max_seq),
                            f"spec_prefill_{self.bucket}")
            else:
                prog.kernel(self.kernels.prefill_kernel(self.max_seq),
                            f"prefill_{self.bucket}")
            for b in self._leaf_buffers(j):
                prog.out(b)
            prog.work_items(j, 1)
            self._prefill_prog = prog
            h = self.runtime.submit(prog, self.scheduler, groups=self.target)
        self.prefill_handle = h
        h.add_done_callback(lambda _h: notify())

    def merge_prefill(self) -> dict:
        """Board a completed prefill wave: write each request's first token
        and start position into a free slot's row of the small host buffers
        (then invalidate them: their device copies are stale), and its
        cache rows into the segment's cache leaves — on the device, one
        donated row scatter for the wave (:func:`copy_rows`), when the
        leaves are ``Resident``; into the host mirrors, invalidated whole,
        when runs are split across groups.  Only legal between segments — an
        in-flight segment may read the buffers at any moment.  Returns
        {"joined": n, "failed": [...], "seconds"}.
        The server calls it under its lock, in a ``merge`` span: ``submit``
        waits behind it."""
        h, wave, prog = self.prefill_handle, self.prefill_wave, self._prefill_prog
        assert h is not None and h.done()
        self.prefill_handle, self.prefill_wave, self._prefill_prog = None, [], None
        seconds = h.metrics.get("response_time") or (_now() - self._prefill_t0)
        tr = tracer()
        _trace_run(tr, "prefill_wave", h, bucket=self.bucket, wave=len(wave))
        if h.has_errors():
            return {"joined": 0, "failed": list(wave), "errors": h.errors(),
                    "seconds": seconds}
        if self.chunk_len:
            return self._merge_chunked(wave, seconds)
        free = self.free_slots()
        if self.spec_k:
            tok_b, ptok_b, pos_b = (self.prog._ins[0], self.prog._ins[1],
                                    self.prog._ins[2])
            leaf_bufs = self.prog._ins[3:3 + self.n_leaves]
            tok0, ptok0 = prog._outs[0], prog._outs[1]
            wave_leaves = prog._outs[2:]
        else:
            tok_b, ptok_b, pos_b = self.prog._ins[0], None, self.prog._ins[1]
            leaf_bufs = self.prog._ins[2:]
            tok0, ptok0 = prog._outs[0], None
            wave_leaves = prog._outs[1:]
        slots = free[:len(wave)]
        for i, (req, slot) in enumerate(zip(wave, slots)):
            tok_b[slot, 0] = tok0[i, 0]
            if ptok_b is not None:
                ptok_b[slot, 0] = ptok0[i, 0]
            pos_b[slot, 0] = self.bucket
            if self.home is None:
                for dst, src in zip(leaf_bufs, wave_leaves):
                    dst[slot] = src[i]
            self.slots[slot] = req
            req.board(slot, int(tok0[i, 0]))
            if tr.enabled:
                tr.async_instant("first_token", req.seq, slot=slot)
        self.tokens_written += len(wave) * min(self.bucket, self.max_seq)
        if self.home is None:
            for b in self.prog._ins:
                self.prog.invalidate(b)
        else:
            copy_rows(leaf_bufs, slots, wave_leaves, self.home)
            for b in (tok_b, ptok_b, pos_b):
                if b is not None:
                    self.prog.invalidate(b)
            for b in wave_leaves:
                b.clear()  # the wave's cache rows: free them now
        return {"joined": len(wave), "failed": [], "seconds": seconds}

    def _merge_chunked(self, wave, seconds: float) -> dict:
        """Board a chunked join wave without a prefill Program: arm each
        request's slot for the segment kernel's chunk stage — cursor 0,
        prompt row uploaded, position leaves reset to −1 (empty; stale k/v
        under kpos −1 is never attended, so the big value leaves stay
        device-resident) — and defer ``req.board`` to the harvest of the
        segment whose chunk completes the prompt (``ctok``).  The join
        re-uploads only the small control buffers; the position-leaf rows
        are set on the device (:func:`fill_rows`) when the leaves are
        ``Resident``, else in the host mirrors, re-uploaded whole."""
        free = self.free_slots()
        if self.spec_k:
            tok_b, ptok_b, pos_b = (self.prog._ins[0], self.prog._ins[1],
                                    self.prog._ins[2])
            pcur_b, ptoks_b = self.prog._ins[3], self.prog._ins[4]
            leaf_bufs = self.prog._ins[5:5 + self.n_leaves]
            neg = (self.kernels.leaf_neg_init(self.max_seq)
                   + self.kernels.draft_leaf_neg_init(self.max_seq))
        else:
            tok_b, ptok_b, pos_b = self.prog._ins[0], None, self.prog._ins[1]
            pcur_b, ptoks_b = self.prog._ins[2], self.prog._ins[3]
            leaf_bufs = self.prog._ins[4:]
            neg = self.kernels.leaf_neg_init(self.max_seq)
        pos_leaves = [dst for dst, is_neg in zip(leaf_bufs, neg) if is_neg]
        slots = free[:len(wave)]
        for req, slot in zip(wave, slots):
            tok_b[slot, 0] = 0
            if ptok_b is not None:
                ptok_b[slot, 0] = int(req.prompt[-1])
            pos_b[slot, 0] = self.bucket
            pcur_b[slot, 0] = 0
            ptoks_b[slot, :] = req.prompt
            if self.home is None:
                for dst in pos_leaves:
                    dst[slot] = -1
            self.slots[slot] = req
            req.slot = slot
            req.chunk_pos = 0
        for b in (tok_b, ptok_b, pos_b, pcur_b, ptoks_b):
            if b is not None:
                self.prog.invalidate(b)
        if self.home is None:
            for dst in pos_leaves:
                self.prog.invalidate(dst)
        else:
            fill_rows(pos_leaves, slots, -1, self.home)
        return {"joined": len(wave), "failed": [], "seconds": seconds}

    # ------------------------------------------------------------ segments
    def submit_segment(self, notify: Callable) -> None:
        """Chain the next decode segment after the newest one (in flight, or
        else the last harvested).  The swap epilogue runs worker-side, so
        the just-produced token/pos/cache buffers become the next segment's
        inputs *device-resident*."""
        if self.spec_k and self.spec_gate is not None:
            # SpecGate auto-bypass: decide this segment's mode and flip the
            # device-side flag only when it changes (one tiny re-upload).
            want = 1 if self.spec_gate.decide(self.bucket) else 0
            if int(self._spec_on[0, 0]) != want:
                self._spec_on[:] = want
                self.prog.invalidate(self._spec_on)
            self._seg_mode = "spec" if want else "plain"

        def epilogue(prog=self.prog, pairs=self._swap_pairs,
                     spares=self._spares):
            for i_in, i_out in pairs:
                prog.swap_buffers(i_in, i_out)
            for k, buf in spares.items():
                spares[k], prog._outs[k] = prog._outs[k], buf

        prev = self._segs[-1][0] if self._segs else self.prev_handle
        t0 = _now()
        h = self.runtime.submit(self.prog, self.scheduler,
                                after=[prev] if prev is not None else None,
                                epilogue=epilogue, groups=self.target)
        self._segs.append((h, list(self.slots), t0))
        h.add_done_callback(lambda _h: notify())

    def advance(self, notify: Callable, drain: bool = False) -> None:
        """Keep this batch's decode segments in flight: one whenever a slot
        holds a request and, when segments may run ahead (:attr:`ahead`), a
        second chained behind it, which the device starts as soon as the
        first ends — so the host's work between segments leaves the
        device's critical path.  No segment goes ahead when the next
        boundary has to drain: a join is coming (a prefill wave in flight
        or waiting to merge), or ``drain`` (the caller will move slots
        there), which holds until that boundary; nor when no request still
        needs tokens after the one in flight.  At a boundary with a join
        coming, the next segment waits for the merge: the prefill is on
        the device ahead of it anyway, and it then decodes the joiners
        too.

        A drain is traced as a ``drain`` span on the batcher track, from
        the moment the boundary had to drain to its resubmission: the
        device idles at its end."""
        if not self._segs:  # at a boundary
            if self._drain_t0 is not None:
                tr = tracer()
                if tr.enabled:
                    tr.complete("drain", self._drain_t0, time.perf_counter(),
                                track="batcher", bucket=self.bucket)
                self._drain_t0 = None
            self._draining = drain
        elif drain:
            self._draining = True
        if not any(self.slots):
            return
        if not self._segs and not (self.ahead and self.prefill_handle is not None):
            self.submit_segment(notify)
        if not self.ahead:
            return
        if self._draining or self.prefill_handle is not None:
            if self._drain_t0 is None:
                self._drain_t0 = time.perf_counter()
        elif len(self._segs) == 1 and any(
                r is not None and r.remaining() > self.seg_len
                for r in self.slots):
            self.submit_segment(notify)

    def harvest_segment(self) -> dict:
        """Collect a completed segment: append each active slot's new tokens
        (truncated to what the request still needs), retire finished
        requests, and free their slots.  Returns stats for this segment.
        The server calls it under its lock, in a ``harvest`` span:
        ``submit`` waits behind it."""
        h, snapshot, t0 = self._segs.pop(0)
        assert h.done()
        seconds = h.metrics.get("response_time") or (_now() - t0)
        if h.has_errors():
            return {"errors": h.errors(), "seconds": seconds}
        self.prev_handle = h
        self.last_run_metrics = h.metrics
        # The host buffers this segment wrote: a later segment writes others.
        outs = h.outputs
        toks_seg = outs[0]
        cnt = outs[1] if self.spec_k else None
        n_active = 0
        finished = []
        emitted = drafted = accepted = chunk_tokens = delivered = 0
        rows_read = 0  # cache rows the active slots' steps attended
        tr = tracer()
        traced = tr.enabled
        # The requests the segment decoded for that still hold their slot: one
        # that finished in the segment before decoded a segment too many.
        for slot, req in enumerate(snapshot):
            if req is None or self.slots[slot] is not req:
                continue
            if self.chunk_len and req.chunk_pos < self.bucket:
                # Prefilling at segment entry: the chunk stage advanced the
                # cursor deterministically — mirror it host-side.  On the
                # segment whose chunk reaches the bucket boundary the slot's
                # first token is in ctok (a pure, never-swapped output whose
                # host mirror write_outputs refreshed); it boards here and
                # decodes from the next segment on.
                old = req.chunk_pos
                req.chunk_pos = min(old + self.chunk_len, self.bucket)
                chunk_tokens += req.chunk_pos - old
                if traced:
                    tr.async_instant("prefill_chunk", req.seq, slot=slot,
                                     cursor=req.chunk_pos,
                                     tokens=req.chunk_pos - old)
                if req.chunk_pos >= self.bucket:
                    ctok = outs[self._ctok_out]
                    req.board(slot, int(ctok[slot, 0]))
                    delivered += 1
                    if traced:
                        tr.async_instant("first_token", req.seq, slot=slot)
                    self.tokens_written += min(self.bucket, self.max_seq)
                    self._on_chunk_complete(slot, req)
                    if req.remaining() <= 0:
                        finished.append(req)
                        self.release_slot(slot)
                continue
            n_active += 1
            need = req.remaining()
            if self.spec_k:
                # Ragged emission: this segment produced cnt tokens for the
                # slot (seg_len steps, each 1 + its accepted draft depth).
                # A bypassed (plain-mode) segment reports cnt = seg_len and
                # contributes nothing to draft accounting — plain segments
                # must not pollute the acceptance EMA.
                c = int(cnt[slot, 0])
                take = toks_seg[slot, : min(c, need)]
                emitted += c
                if self._seg_mode == "spec":
                    d, a = self.spec_k * self.seg_len, c - self.seg_len
                    drafted += d
                    accepted += a
                    req.note_spec(d, a)
                else:
                    d = a = 0
                if traced:
                    tr.async_instant("decode_segment", req.seq, slot=slot,
                                     tokens=int(len(take)), drafted=d,
                                     accepted=a)
            else:
                take = toks_seg[slot, : min(self.seg_len, need)]
                if self._row_bytes:
                    # Step j of the segment sits at position pos0 + j and
                    # attends rows 0..pos0 + j.
                    pos0 = self.bucket + len(req.tokens) - 1
                    rows_read += self.seg_len * (pos0 + 1) + \
                        self.seg_len * (self.seg_len - 1) // 2
                if traced:
                    tr.async_instant("decode_segment", req.seq, slot=slot,
                                     tokens=int(len(take)))
            req.extend(take)
            delivered += int(len(take))
            if req.remaining() <= 0:
                finished.append(req)
                self.release_slot(slot)
        self.tokens_written += emitted if self.spec_k else n_active * self.seg_len
        counts = {}
        if self.kernels.counted and not (self.spec_k or self.chunk_len):
            # Over every slot, as the step computed them; the latent bytes
            # at the active slots' real lengths.
            n = outs[-1].sum(axis=0)
            counts = {k: int(v) for k, v in zip(COUNTERS, n)}
            counts["latent_bytes"] = rows_read * self._row_bytes
        _trace_run(tr, "segment", h, bucket=self.bucket, n_active=n_active,
                   finished=len(finished), chunk_tokens=chunk_tokens,
                   ahead=int(h.ahead), **counts)
        if self.telemetry is not None and chunk_tokens:
            self.telemetry.count("chunk_tokens", chunk_tokens)
        res = {"n_active": n_active, "finished": finished, "seconds": seconds,
               "tokens": delivered}
        if self.spec_k:
            res["drafted"], res["accepted"] = drafted, accepted
            res["mode"] = self._seg_mode
        if self.chunk_len:
            res["chunk_tokens"] = chunk_tokens
        return res

    def _on_chunk_complete(self, slot: int, req) -> None:
        """Hook fired when a slot's chunked prefill completes (its prompt
        KV is now fully written).  The paged override registers the slot's
        prompt blocks with the prefix cache here — the earliest moment
        their content is valid to share."""

    def release_slot(self, slot: int) -> None:
        """Free one KV slot (request retired or failed).  The paged variant
        additionally releases the slot's blocks and re-points its table at
        the sink block."""
        self.slots[slot] = None

    # ------------------------------------------------------------ migration
    def at_boundary(self) -> bool:
        """True between runs: no segment or prefill in flight, so
        ``prog._ins`` hold the authoritative slot state (the epilogue swap
        ran): the token/position buffers on host (every package wrote them
        back), the cache leaves on :attr:`home`'s device when ``Resident``,
        else in host mirrors (written back)."""
        return self.seg_handle is None and self.prefill_handle is None

    def can_accept_migration(self, src: "BatchGroup", slot: int) -> bool:
        """Could ``src``'s ``slot`` move here right now?  Requires a free
        slot and a quiescent destination — a prefill in flight would race
        the wave merge for the free slot we are about to fill."""
        return (not self.dead and self.at_boundary()
                and bool(self.free_slots()))

    def migrate_slot_to(self, slot: int, dst: "BatchGroup") -> bool:
        """Move one active request — tokens, positions, and its entire KV
        slot state — into a free slot of ``dst``.  Legal only at a segment
        boundary on both sides: after the epilogue swap, ``prog._ins`` rows
        ARE the current state (:meth:`at_boundary`), so migration moves
        O(rows)/O(blocks): host rows patched into the destination's device
        copies (:meth:`DeviceGroup.patch_cached`), ``Resident`` cache rows
        read back from the source device and scattered into the
        destination's (:func:`copy_rows`) — never a full-cache rewrite.
        The stream stays bit-identical: decode is deterministic in the slot
        state, and the copied rows are exactly the state the source would
        have decoded from.  Returns False (no partial effects) when either
        side is busy, ``dst`` is full, or its pool cannot cover the blocks."""
        req = self.slots[slot]
        if req is None or self.dead or dst.dead or dst is self:
            return False
        if self.seg_handle is not None or not dst.can_accept_migration(self, slot):
            return False
        d = dst.free_slots()[0]
        if not self._copy_slot_state(slot, dst, d):
            return False
        dst.slots[d] = req
        req.slot = d
        self.release_slot(slot)
        return True

    def _row_bufs(self) -> List[np.ndarray]:
        """The slot-leading input buffers a migration must carry (everything
        except ``spec_on``, which is group-local gate state)."""
        bufs = list(self.prog._ins)
        return bufs[:-1] if self.spec_k else bufs

    def _copy_slot_state(self, slot: int, dst: "BatchGroup", d: int) -> bool:
        """Contiguous layout: copy the slot row of every input buffer
        (token/pos controls + every cache leaf) into ``dst``'s row ``d``:
        host rows patched into ``dst``'s device copies, ``Resident`` rows
        read back (O(rows)) and scattered into ``dst``'s device values."""
        for src_buf, dst_buf in zip(self._row_bufs(), dst._row_bufs()):
            row = (src_buf.read_back([slot]) if isinstance(src_buf, Resident)
                   else src_buf[slot:slot + 1])
            if isinstance(dst_buf, Resident):
                copy_rows([dst_buf], [d], [row], dst.home)
            else:
                dst_buf[d] = row[0]
                dst._patch_or_invalidate(dst_buf, [d])
        return True

    def _patch_or_invalidate(self, buf: np.ndarray, rows: Sequence[int]) -> None:
        """Propagate freshly written host-mirror rows to this batch's device
        groups: in-place O(rows) patch of the stashed device copy when one
        exists (version unchanged — host and device now agree again), full
        invalidation (one re-upload next segment) otherwise."""
        groups = self.target or self.runtime.groups
        vals = buf[np.asarray(rows, np.intp)]
        if not all(g.patch_cached(self.prog, buf, rows, vals) for g in groups):
            self.prog.invalidate(buf)

    def fail_all(self, errors: Sequence[str]) -> List[object]:
        """A segment failed: group state is unrecoverable (mirrors may hold
        partial write-backs).  Collect every request this group owes an
        answer to; the server fails their handles and drops the group."""
        self.dead = True
        victims = [r for _, r in self.active()] + list(self.prefill_wave)
        self.slots = [None] * self.n_slots
        self.prefill_wave = []
        self._segs = []
        self._drain_t0 = None
        self.prefill_handle = None
        return victims


def _slot_buffers(specs, axes, dtype, n_slots: int, resident: bool) -> list:
    out = []
    for s, a in zip(specs, axes):
        dt = np.dtype(s.dtype or dtype)
        shape = (n_slots,) + s.shape[:a] + s.shape[a + 1:]
        fill = {"neg_ones": -1, "ones": 1}.get(s.init, 0)
        out.append(Resident(shape, dt, fill) if resident
                   else np.full(shape, fill, dt))
    return out


def _blank(buf):
    """A fresh output buffer shaped like ``buf`` (an in/out pair's out)."""
    if isinstance(buf, Resident):
        return Resident(buf.shape, buf.dtype, buf.fill)
    return np.zeros_like(buf)


def _now() -> float:
    return time.monotonic()


def _trace_run(tr, name: str, h, **args) -> None:
    """The run behind handle ``h`` as a complete span ``name`` on the
    batcher track: from the run's start to its end (its Introspector's
    ``t_run_start``/``t_run_end``, on the tracer's clock), with
    ``queued_s``, the time from submit to that start.  A stand-in handle
    (nothing ran) or a run that never started emits nothing."""
    intro = getattr(h, "introspector", None)
    if not tr.enabled or intro is None or not intro.t_run_start:
        return
    tr.complete(name, intro.t_run_start, intro.t_run_end, track="batcher",
                queued_s=intro.t_run_start - h.t_submit, **args)
