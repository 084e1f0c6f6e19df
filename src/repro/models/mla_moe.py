"""The ``mla_moe`` family: DeepSeek-V3's stack, which Kimi-K2 reuses.

Embedding, one dense layer (``dense_layer``: MLA and a SwiGLU of width
``d_ff``), then ``n_layers - 1`` expert layers scanned over a leading axis
(``moe_layers``: MLA and :func:`repro.models.moe.held_moe`), final norm and
LM head.  Every layer is pre-norm: ``x += attn(rms(x)); x += ffn(rms(x))``.

The cache holds each layer's latent rows (``models/mla.py``): ``c_kv`` and
``k_pe``, under ``dense_layer`` and, with a layer axis after the slot axis,
``moe_layers``.  Prefill runs
one request at a time (``lax.map``), so a wave's temporaries are one
prompt's whatever the wave's size.  Decode also reports, per slot, the
(token, held expert) pairs it routed and the rows of the grouped matmuls
(:func:`decode_counts`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import mla
from repro.models import moe
from repro.models import transformer as T
from repro.models.params import Spec, stack_layers


def _check(cfg) -> None:
    if cfg.n_layers < 2:
        raise ValueError(f"mla_moe stacks one dense layer before one or more "
                         f"expert layers: n_layers={cfg.n_layers}")


def _layer_spec(cfg, experts: bool) -> dict:
    d = cfg.d_model
    spec = {"attn": mla.mla_spec(cfg), "norm1": Spec((d,), init="ones"),
            "norm2": Spec((d,), init="ones")}
    if experts:
        spec.update(moe.held_moe_spec(cfg))
    else:
        spec["mlp"] = {"w_gate": Spec((d, cfg.d_ff), scale=d ** -0.5),
                       "w_up": Spec((d, cfg.d_ff), scale=d ** -0.5),
                       "w_down": Spec((cfg.d_ff, d), scale=cfg.d_ff ** -0.5)}
    return spec


def param_spec(cfg, par: int = 1) -> dict:
    _check(cfg)
    spec = T.embed_spec(cfg, par)
    spec["dense_layer"] = _layer_spec(cfg, False)
    spec["moe_layers"] = stack_layers(cfg.n_layers - 1, _layer_spec(cfg, True))
    return spec


def cache_spec(cfg, batch: int, max_seq: int, par: int = 1) -> dict:
    return {"dense_layer": mla.cache_leaf_spec(cfg, batch, max_seq),
            "moe_layers": mla.cache_leaf_spec(cfg, batch, max_seq,
                                              cfg.n_layers - 1)}


def _layer(p, x, positions, cfg, *, mode, cache=None, pos=None, index=0):
    """One layer (``index``: its place among the expert layers, whose
    experts ``p`` holds stacked).  Returns (x, latent rows (prefill) or new
    cache (decode), held pairs per token (B, S) int32)."""
    b, s, d = x.shape
    h = L.rms_norm(x, p["norm1"], cfg.norm_eps)
    if mode == "decode":
        a, out = mla.decode_step(p["attn"], h, pos, cfg, cache,
                                 None if "mlp" in p else index)
    else:
        a, c, k_pe = mla.attend(p["attn"], h, positions, cfg)
        out = {"c_kv": c, "k_pe": k_pe}
    x = x + a
    h = L.rms_norm(x, p["norm2"], cfg.norm_eps)
    if "mlp" in p:
        m = p["mlp"]
        return (x + L.swiglu(h, m["w_gate"], m["w_up"], m["w_down"]), out,
                jnp.zeros((b, s), jnp.int32))
    y, held = moe.held_moe(h.reshape(b * s, d), p, cfg, index)
    return x + y.reshape(b, s, d), out, held.reshape(b, s)


def _stack(params, x, positions, cfg, *, mode, cache=None, pos=None):
    """Dense layer, then the scanned expert layers.  Returns (x, per-layer
    outputs {"dense_layer", "moe_layers"}, held pairs per token summed over
    layers (B, S))."""
    x, out0, n0 = _layer(params["dense_layer"], x, positions, cfg, mode=mode,
                         cache=None if cache is None else cache["dense_layer"],
                         pos=pos)

    # The experts and (decode) the latent cache stay stacked, outside the
    # scan's slices: their kernels take the whole stack and a layer index,
    # where a layer's slice would be a copy made every step.
    stack = dict(params["moe_layers"])
    experts = stack.pop("experts")

    def body(carry, xs):
        x, c = carry
        lp, i = xs
        x, out, n = _layer(dict(lp, experts=experts), x, positions, cfg,
                           mode=mode, cache=c, pos=pos, index=i)
        if c is not None:  # decode: out is the updated stack
            return (x, out), n
        return (x, None), (out, n)

    c = None if cache is None else cache["moe_layers"]
    (x, c), ys = jax.lax.scan(body, (x, c),
                              (stack, jnp.arange(cfg.n_layers - 1)))
    outs, ns = (c, ys) if c is not None else ys
    return x, {"dense_layer": out0, "moe_layers": outs}, n0 + ns.sum(axis=0)


def forward_train(params, batch, cfg):
    """Next-token loss (no auxiliary balance loss: ``noaux_tc`` routing
    balances through its bias, which training does not update here)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x, _, _ = _stack(params, T.embed_tokens(params, tokens, cfg), positions,
                     cfg, mode="train")
    labels = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones_like(tokens, jnp.float32).at[:, -1].set(0.0)
    return T.lm_loss(params, x, labels, mask, cfg)


def prefill(params, batch, cfg, cache):
    """Fill positions 0..S-1 of every slot's cache from its prompt; returns
    (last-position logits (B, 1, V), cache)."""
    tokens = batch["tokens"]
    s = tokens.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)[None]

    def one(tok):
        x = T.embed_tokens(params, tok[None], cfg)
        x, rows, _ = _stack(params, x, positions, cfg, mode="prefill")
        return T.logits_fn(params, x[:, -1:], cfg)[0], rows

    logits, rows = jax.lax.map(one, tokens)

    def write(leaf, new):
        # new: (B, [layers,] 1, S, w) from the map; the leaf (B, [layers,]
        # max_seq, w).
        new = jnp.squeeze(new, axis=-3).astype(leaf.dtype)
        return leaf.at[..., :s, :].set(new)

    return logits, jax.tree_util.tree_map(write, cache, rows)


def decode_counts(params, token, pos, cfg, cache):
    """One decode step; returns (logits (B,1,V), cache, counts (B, 2)
    int32): per slot, the (token, held expert) pairs routed and the rows
    its token took in the grouped matmuls (top_k per expert layer)."""
    x = T.embed_tokens(params, token, cfg)
    x, cache, held = _stack(params, x, None, cfg, mode="decode", cache=cache,
                            pos=pos)
    rows = jnp.full_like(held[:, 0], cfg.top_k * (cfg.n_layers - 1))
    return (T.logits_fn(params, x, cfg), cache,
            jnp.stack([held[:, 0], rows], axis=-1))


def decode(params, token, pos, cfg, cache):
    logits, cache, _ = decode_counts(params, token, pos, cfg, cache)
    return logits, cache
