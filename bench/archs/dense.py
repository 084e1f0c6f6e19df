"""The dense decoder: RMSNorm, rotary embeddings on half-split head dims,
optional q/k/v bias, causal softmax attention with grouped KV heads, SwiGLU
MLP, untied LM head.  Its weight layout is the one the program's dense
decoder takes (``embed``, ``final_norm``, ``lm_head``, and ``layers`` stacked
on a leading axis); every size comes from the configuration file.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench import reference as R
from bench.weights import BIAS_STD, EMBED_STD, NORM_STD


# ------------------------------------------------------------- the program
def program_config(c: dict, cache_dtype: str = ""):
    """The program's ModelConfig, every size taken from the config file."""
    from repro.configs import get_config

    p = c["program"]
    return dataclasses.replace(
        get_config(p["arch"]),
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], head_dim=c.get("head_dim"),
        qkv_bias=p["qkv_bias"], rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=c["tie_word_embeddings"],
        compute_dtype=c["torch_dtype"], kernel_impl=p["kernel_impl"],
        cache_dtype=cache_dtype)


# ----------------------------------------------------------------- weights
def layout(c: dict) -> dict:
    """name -> (shape, kind, std) of every leaf; layer leaves are named
    ``layers/...`` and drawn per layer (shape without the layer axis)."""
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    out = {
        "embed": ((v, d), "normal", EMBED_STD),
        "final_norm": ((d,), "norm", NORM_STD),
        "lm_head": ((d, v), "normal", d ** -0.5),
        "layers/attn/wq": ((d, h, hd), "normal", d ** -0.5),
        "layers/attn/wk": ((d, kv, hd), "normal", d ** -0.5),
        "layers/attn/wv": ((d, kv, hd), "normal", d ** -0.5),
        "layers/attn/wo": ((h, hd, d), "normal", (h * hd) ** -0.5),
        "layers/mlp/w_gate": ((d, f), "normal", d ** -0.5),
        "layers/mlp/w_up": ((d, f), "normal", d ** -0.5),
        "layers/mlp/w_down": ((f, d), "normal", f ** -0.5),
        "layers/norm1": ((d,), "norm", NORM_STD),
        "layers/norm2": ((d,), "norm", NORM_STD),
    }
    if c["program"]["qkv_bias"]:
        out["layers/attn/bq"] = ((h, hd), "normal", BIAS_STD)
        out["layers/attn/bk"] = ((kv, hd), "normal", BIAS_STD)
        out["layers/attn/bv"] = ((kv, hd), "normal", BIAS_STD)
    return out


# --------------------------------------------------------------- reference
def _rope(x, positions, theta):
    """x: (B, S, H, hd); rotate the two halves of each head dim."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=R.F32) / hd))
    ang = positions[:, :, None, None].astype(R.F32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, c, act=R.ident):
    """One decoder layer; ``act`` rounds each activation entering a product
    with a weight (the identity for the reference)."""
    a = p["attn"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    n = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), x.shape[:2])
    h = act(R.rms_norm(x, p["norm1"], eps))
    q = R.mm("bsd,dhk->bshk", h, a["wq"])
    k = R.mm("bsd,dhk->bshk", h, a["wk"])
    v = R.mm("bsd,dhk->bshk", h, a["wv"])
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    rep = c["num_attention_heads"] // c["num_key_value_heads"]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = R.mm("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    o = R.mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    x = x + R.mm("bshk,hkd->bsd", act(o), a["wo"])
    h = act(R.rms_norm(x, p["norm2"], eps))
    m = p["mlp"]
    g = R.mm("bsd,df->bsf", h, m["w_gate"])
    u = R.mm("bsd,df->bsf", h, m["w_up"])
    return x + R.mm("bsf,fd->bsd", act(jax.nn.silu(g) * u), m["w_down"])


# Contracted axes of each weight matrix; norms and biases keep float32.
FP8_AXES = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
            "w_gate": (0,), "w_up": (0,), "w_down": (0,),
            "embed": (1,), "lm_head": (0,)}


def score(c: dict, seed: int, tokens, targets, control: bool = False) -> dict:
    """``reference.score`` through this decoder's layers."""
    return R.score(c, seed, tokens, targets, control, layer=_layer,
                   fp8_axes=FP8_AXES, layout=layout)


# ------------------------------------------------------------------- flops
# Counted: every matrix product of the projections, the MLP and the LM head
# (2 operations per multiply-add) and the two attention products (scores and
# the weighted sum of values) over the real context.  Not counted: padding,
# norms, rotary embeddings, softmax, biases, and the LM head at prompt
# positions whose logits nobody reads.
def layer_matmul_params(c: dict) -> int:
    """Weights of one layer that take part in a matrix product."""
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def attention_flops(c: dict, context: int) -> int:
    """Scores and weighted values of one query over ``context`` keys, all
    layers: 2 products x 2 operations x heads x head size x context."""
    h = c["num_attention_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // h
    return 4 * c["num_hidden_layers"] * h * hd * context


def decode_flops(c: dict, context: int) -> int:
    """One generated token whose query attends ``context`` keys (itself
    included): every layer's products, the LM head, attention."""
    mm = c["num_hidden_layers"] * layer_matmul_params(c) + head_params(c)
    return 2 * mm + attention_flops(c, context)


def prefill_flops(c: dict, prompt_len: int) -> int:
    """A prompt of ``prompt_len`` real tokens: every layer at every position,
    causal attention (position p attends p + 1 keys), LM head once."""
    p = prompt_len
    layers = c["num_hidden_layers"] * layer_matmul_params(c)
    attn = attention_flops(c, 1) * p * (p + 1) // 2
    return 2 * layers * p + 2 * head_params(c) + attn
