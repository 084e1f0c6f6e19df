"""Serving steps: prefill (prompt → cache), decode (one token, KV cache),
and decode *chains* (N dependent tokens, device-resident — the serving
analog of the runtime's dataflow run graphs).

``decode_*`` / ``long_*`` dry-run cells lower make_decode_step — one new
token against a seq_len-deep cache — per the assignment.

Params are cast to the compute dtype through a device-resident cache
(``cast_params_cached``): a serving loop calls prefill/decode thousands of
times against the same immutable param tree, so the cast (and its transfer,
when running eagerly) is paid once per (params, dtype), not per token.
Traced values bypass the cache — under ``jax.jit`` XLA already folds the
cast, and caching tracers across traces would leak them.
"""
from __future__ import annotations

import dataclasses
import weakref
from typing import Any

import jax
import jax.numpy as jnp

# (leaf ids, dtype) -> cast tree, dropped when the source tree is collected.
_cast_cache: dict = {}


def _cast_float(tree, dtype):
    dt = jnp.dtype(dtype)
    return jax.tree_util.tree_map(
        lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree
    )


def cast_params_cached(tree, dtype):
    """``_cast_float`` memoized on leaf identities (concrete values only)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if any(isinstance(x, jax.core.Tracer) for x in leaves):
        return _cast_float(tree, dtype)
    # treedef in the key: identical leaves in a different container must
    # not hit the other structure's entry.
    key = (treedef, tuple(map(id, leaves)), str(jnp.dtype(dtype)))
    hit = _cast_cache.get(key)
    if hit is not None:
        return hit
    out = _cast_float(tree, dtype)
    out_leaves = jax.tree_util.tree_leaves(out)
    if all(o is i for o, i in zip(out_leaves, leaves)):
        # No-op cast (params already in compute dtype): nothing to memoize,
        # and caching would hold strong refs to the very leaves whose death
        # is the only eviction trigger — pinning params forever.
        return out
    try:
        # Containers (dicts) aren't weakref-able; finalize on every leaf so
        # the entry dies before any keyed id can be recycled.
        for leaf in leaves:
            weakref.finalize(leaf, _cast_cache.pop, key, None)
    except TypeError:
        return out  # not weakref-able: don't cache (no eviction path)
    _cast_cache[key] = out
    return out


def make_prefill_step(cfg, api):
    def prefill_step(params, batch, cache):
        params = cast_params_cached(params, cfg.compute_dtype)
        logits, cache = api.prefill(params, batch, cfg, cache)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return next_tok, cache

    return prefill_step


def make_decode_step(cfg, api, *, counts: bool = False):
    """``(params, cache, token, pos) -> (token, cache)``; ``pos`` is a
    scalar (uniform batch) or a (B,) per-slot position vector — the model's
    decode path is natively batched over vector positions.  ``counts``:
    ``-> (token, cache, counts)`` through ``api.decode_counts``."""
    def decode_step(params, cache, token, pos):
        params = cast_params_cached(params, cfg.compute_dtype)
        if counts:
            logits, cache, n = api.decode_counts(params, token, pos, cfg, cache)
        else:
            logits, cache = api.decode(params, token, pos, cfg, cache)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return (next_tok, cache, n) if counts else (next_tok, cache)

    return decode_step


def zeros_cache(cfg, api, batch: int, max_seq: int, *, dtype=None, par: int = 1):
    """Fresh empty KV cache honoring each leaf's declared init.

    The cache spec marks ``pos`` leaves ``neg_ones`` (−1 = empty slot) —
    attention masks on recorded positions, so an all-zeros init would leave
    unwritten slots *valid* at position 0 and silently attend zero keys.
    Every cache-materialization path (one-shot generate, co-exec kernels,
    the serving slot groups) must build caches through this one helper so
    they share bit-identical initial state."""
    dt = jnp.dtype(dtype) if dtype is not None else jnp.dtype(cfg.compute_dtype)

    def mk(s):
        ldt = jnp.dtype(s.dtype or dt)
        if s.init == "neg_ones":
            return jnp.full(s.shape, -1, ldt)
        if s.init == "ones":
            return jnp.ones(s.shape, ldt)
        return jnp.zeros(s.shape, ldt)

    from repro.models.params import tree_map_specs

    return tree_map_specs(mk, api.cache_spec(cfg, batch, max_seq, par))


def cache_batch_axes(cfg, api, max_seq: int, *, par: int = 1):
    """Per-leaf batch-axis index of the cache tree (layer-stacked leaves put
    batch at axis 1, not 0).  Found structurally — the axis whose extent
    tracks the requested batch size — so it holds across model families
    without a per-family table."""
    import jax.tree_util as jtu

    from repro.models.params import Spec

    is_spec = lambda x: isinstance(x, Spec)  # noqa: E731

    def ax(a, b):
        for i, (x, y) in enumerate(zip(a.shape, b.shape)):
            if x != y:
                return i
        raise ValueError(f"cache leaf {a.shape} has no batch axis: cannot slot it")

    return jtu.tree_map(ax, api.cache_spec(cfg, 1, max_seq, par),
                        api.cache_spec(cfg, 2, max_seq, par), is_leaf=is_spec)


def make_generate(cfg, api, *, jit: bool = True):
    """One-shot batched generate: prefill + device-resident decode chain.

    The single cache-materialization and prefill+chain path shared by the
    plain and co-executed serving launchers (they previously re-implemented
    it with *different* cache inits) and the reference implementation the
    inference server is tested bit-identical against.  ``jit=False`` returns
    an un-jitted callable for embedding inside an already-jitted kernel.

    Returned ``generate(params, batch, gen, *, cache=None)`` produces
    ``(b, gen)`` greedy tokens; ``cache`` defaults to a fresh
    ``zeros_cache`` sized ``prompt_len + gen`` (a caller-provided cache is
    *donated* to the jitted prefill when ``jit=True`` — consumed, not
    reusable after the call)."""
    prefill = make_prefill_step(cfg, api)
    chain = make_decode_chain(cfg, api)
    if jit:
        # Both stages donate the cache operand: generate's cache is private
        # to the call (fresh zeros_cache or prefill output), so XLA updates
        # it in place instead of copying the full KV cache per stage.
        prefill = jax.jit(prefill, donate_argnums=(2,))
        chain = jax.jit(chain, static_argnums=(4,), donate_argnums=(1,))

    def generate(params, batch, gen: int, *, cache=None):
        from repro.core.trace import tracer

        tr = tracer()
        b, s = batch["tokens"].shape
        if cache is None:
            cache = zeros_cache(cfg, api, b, s + gen)
        # Spans cover host-side dispatch (JAX dispatch is async); device
        # time shows up in the runtime's execute spans when co-executed.
        with tr.span("generate.prefill", track="generate", batch=b, seq=s):
            tok, cache = prefill(params, batch, cache)
        with tr.span("generate.chain", track="generate", steps=gen - 1):
            toks, _, _ = chain(params, cache, tok, jnp.int32(s), gen - 1)
        return jnp.concatenate([tok, toks], axis=1)

    return generate


def make_scored_continuation(cfg, api):
    """``scored(params, prompts, cont) -> logits (B, 1 + T, vocab)``: the
    logits the serving path computes for prompt + continuation — prefill of
    ``prompts`` (B, S), then ``T`` decode steps through the cache fed the
    given ``cont`` (B, T) tokens (teacher forcing).  Row ``t`` scores the
    token at position ``S + t``, so a reference forward pass over
    ``[prompts, cont]`` gives the same rows at its last ``1 + T``
    positions.  Jit it."""

    def scored(params, prompts, cont):
        params = cast_params_cached(params, cfg.compute_dtype)
        b, s = prompts.shape
        cache = zeros_cache(cfg, api, b, s + cont.shape[1])
        logits, cache = api.prefill(params, {"tokens": prompts}, cfg, cache)

        def body(cache, xs):
            tok, pos = xs
            lg, cache = api.decode(params, tok[:, None], pos, cfg, cache)
            return cache, lg[:, -1]

        steps = s + jnp.arange(cont.shape[1], dtype=jnp.int32)
        _, rest = jax.lax.scan(body, cache, (cont.T, steps))
        return jnp.concatenate([logits[:, -1:], jnp.swapaxes(rest, 0, 1)], 1)

    return scored


def make_decode_chain(cfg, api):
    """Multi-step greedy decode with device-resident handoff — the serving
    analog of the runtime's dataflow run graphs: ``n_steps`` dependent
    decode steps are rolled into one ``lax.scan``, so tokens and KV cache
    flow step-to-step on device with no host synchronization (or transfer)
    per token.  ``decode_chain(params, cache, token, pos, n_steps)`` returns
    ``(tokens[b, n_steps], last_token, cache)``; jit with
    ``static_argnums=(4,)``."""
    decode = make_decode_step(cfg, api)

    def decode_chain(params, cache, token, pos, n_steps: int):
        def body(carry, i):
            tok, cache = carry
            tok, cache = decode(params, cache, tok, pos + i)
            return (tok, cache), tok

        (tok, cache), toks = jax.lax.scan(
            body, (token, cache), jnp.arange(n_steps)
        )
        return jnp.swapaxes(toks[..., 0], 0, 1), tok, cache

    return decode_chain


def make_chunk_step(cfg, api, bucket: int, chunk_len: int):
    """One mixed-phase prefill-chunk stage over the whole batch (chunked
    prefill: the decode segment Program advances still-prefilling slots'
    cursors by ``chunk_len`` prompt tokens while other slots decode).

    ``chunk(params, cache, ptoks, pcur) -> (ctok, pcur', cache)`` where
    ``ptoks`` is the (B, bucket) padded-prompt buffer, ``pcur`` the (B, 1)
    prefill cursor (``pcur >= bucket`` ⇒ the slot is decoding: all its
    rows arrive masked and its cache is untouched).  ``ctok`` is the argmax
    of the logits at each slot's final prompt row — the slot's first
    generated token, meaningful only for slots whose prefill completes this
    chunk (``pcur < bucket <= pcur'``); bit-identical to whole-prompt
    prefill's ``argmax(logits[:, -1])``.  Per-slot cursors stagger freely
    (paged prefix-cache hits skip whole blocks), so chunk tokens are
    gathered per slot with clipped ``take_along_axis``."""

    def chunk(params, cache, ptoks, pcur):
        params = cast_params_cached(params, cfg.compute_dtype)
        base = pcur[:, 0]  # (B,)
        positions = base[:, None] + jnp.arange(chunk_len, dtype=jnp.int32)
        valid = positions < bucket
        idx = jnp.clip(positions, 0, bucket - 1)
        toks = jnp.take_along_axis(ptoks, idx, axis=1)  # (B, chunk_len)
        last_idx = jnp.clip(bucket - 1 - base, 0, chunk_len - 1)
        logits, cache = api.prefill_chunk(
            params, toks, base, valid, cfg, cache, last_idx)
        ctok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return ctok, jnp.minimum(pcur + chunk_len, bucket), cache

    return chunk


@dataclasses.dataclass(frozen=True)
class DraftSpec:
    """Speculative-decoding draft model: a small config sharing the target's
    tokenizer/vocab, its own params, and the draft depth ``k`` (candidate
    tokens proposed per verify step).  ``k = 1`` is the shallowest useful
    draft: one candidate, 1–2 tokens emitted per step.

    ``auto_bypass=True`` arms the server's ``SpecGate``: segments run
    plain whenever the forecast speedup (tokens-per-step × measured
    plain/spec segment-time ratio) drops below 1, with periodic re-probes
    of the losing mode.  Off by default — an ungated spec server drafts
    every segment, which keeps drafted/accepted accounting deterministic."""

    cfg: Any
    params: Any
    k: int = 2
    auto_bypass: bool = False

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"draft k must be >= 1, got {self.k}")


def make_draft_verify_step(cfg, api, dcfg, dapi, k: int):
    """One greedy speculative step: draft ``k`` candidates, verify all of
    them (plus the carried token) in a single multi-row decode, accept the
    longest matching prefix.

    ``step(params, dparams, cache, dcache, tok, ptok, pos)`` returns
    ``(y, cnt, tok', ptok', pos', cache, dcache)`` where ``y`` is (B, k+1)
    verified greedy tokens of which the first ``cnt`` (1..k+1 per slot) are
    emitted this step; ``tok``/``ptok`` are (B, 1) — the pending token at
    position ``pos`` and its predecessor at ``pos - 1``; ``pos`` is (B,).

    Greedy acceptance keeps bit-identity exact: every emitted token is the
    target model's own argmax given previously emitted tokens.  Row ``j`` of
    the verify decode attends the cache exactly as sequential decode at
    ``pos + j`` would (its keys through ``pos + j`` are written before
    attention; deeper rows' keys sit beyond its mask), so ``y[:, j]`` is
    bitwise the token sequential decode would produce — whether the draft
    guessed right only decides how many rows we may *keep* (``cnt``), never
    their bits.  Rejected rows leave stale keys above ``pos'``; the next
    step's scatter overwrites them before any row attends those positions.

    The draft cache rides the same timeline: the first draft step is a
    2-row decode of ``[ptok, tok]`` at ``pos - 1``, which both proposes the
    first candidate and repairs the draft cache hole at ``pos - 1`` left
    when the previous step accepted every candidate (draft never saw its
    own last proposal's successor).  Draft-cache staleness can only lower
    the acceptance rate, never corrupt emitted bits."""

    def step(params, dparams, cache, dcache, tok, ptok, pos):
        params = cast_params_cached(params, cfg.compute_dtype)
        dparams = cast_params_cached(dparams, dcfg.compute_dtype)
        b = tok.shape[0]
        bidx = jnp.arange(b)

        # Draft k candidates autoregressively (small model, k tiny).
        x0 = jnp.concatenate([ptok, tok], axis=1)  # (B, 2) at pos-1, pos
        dlog, dcache = dapi.decode(dparams, x0, pos - 1, dcfg, dcache)
        cand = jnp.argmax(dlog[:, -1], axis=-1).astype(jnp.int32)[:, None]
        ds = [cand]
        for j in range(1, k):
            dlog, dcache = dapi.decode(dparams, ds[-1], pos + j, dcfg, dcache)
            ds.append(jnp.argmax(dlog[:, -1], axis=-1).astype(jnp.int32)[:, None])
        drafts = jnp.concatenate(ds, axis=1)  # (B, k)

        # One multi-row verify over [tok, d1..dk] at pos..pos+k.
        xs = jnp.concatenate([tok, drafts], axis=1)  # (B, k+1)
        logits, cache = api.decode(params, xs, pos, cfg, cache)
        y = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, k+1)

        # Longest prefix of drafts matching the target's own greedy chain.
        match = drafts == y[:, :k]
        acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
        cnt = acc + 1  # emitted tokens this step: y[:, :cnt]
        tok2 = y[bidx, acc][:, None]  # next pending token, at pos + cnt
        ptok2 = xs[bidx, acc][:, None]  # its predecessor, at pos + cnt - 1
        return y, cnt, tok2, ptok2, pos + cnt, cache, dcache

    return step
