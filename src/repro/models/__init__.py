"""Model zoo registry: uniform API over all families.

    api = get_model(cfg)
    api.param_spec(cfg, par)              -> Spec tree
    api.cache_spec(cfg, batch, seq, par)  -> Spec tree (decode caches)
    api.forward_train(params, batch, cfg) -> scalar loss
    api.prefill(params, batch, cfg, cache)-> (logits, cache)
    api.decode(params, token, pos, cfg, cache) -> (logits, cache)
    api.prefill_chunk(params, tokens, posv, valid, cfg, cache, last_idx)
        -> (logits, cache)   # mixed-phase chunked prefill; None when the
                             # family has no chunked path (validate_chunked
                             # gates serving accordingly)
    api.decode_counts(params, token, pos, cfg, cache)
        -> (logits, cache, counts)  # decode that also reports per-slot
                             # counters (B, 2) int32, serve.batcher.COUNTERS;
                             # None when the family has none
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional


class ModelAPI(NamedTuple):
    param_spec: Callable
    cache_spec: Callable
    forward_train: Callable
    prefill: Callable
    decode: Callable
    prefill_chunk: Optional[Callable] = None
    decode_counts: Optional[Callable] = None


def get_model(cfg) -> ModelAPI:
    if cfg.family in ("dense", "moe", "vlm", "ssm"):
        from repro.models import transformer as T

        chunk = T.prefill_chunk if cfg.family != "ssm" else None
        return ModelAPI(T.param_spec, T.cache_spec, T.forward_train, T.prefill,
                        T.decode, chunk)
    if cfg.family == "mla_moe":
        from repro.models import mla_moe as M

        return ModelAPI(M.param_spec, M.cache_spec, M.forward_train, M.prefill,
                        M.decode, decode_counts=M.decode_counts)
    if cfg.family == "hybrid":
        from repro.models import rglru as R

        return ModelAPI(R.param_spec, R.cache_spec, R.forward_train, R.prefill, R.decode)
    if cfg.family == "audio":
        from repro.models import whisper as W

        return ModelAPI(W.param_spec, W.cache_spec, W.forward_train, W.prefill, W.decode)
    raise ValueError(f"unknown family {cfg.family!r}")
