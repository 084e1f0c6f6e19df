"""Plain float32 reference forward pass of the dense decoder family.

Written from the architecture's description (Qwen1.5 / LLaMA style:
RMSNorm, rotary embeddings on half-split head dims, optional q/k/v bias,
causal softmax attention with grouped KV heads, SwiGLU MLP, untied LM head)
in straightforward ``jax.numpy``: no kernels, no cache, no batching tricks,
and every product at ``Precision.HIGHEST`` (a TPU otherwise multiplies
float32 in bf16 passes).  It shares no code with ``repro.models``, so the
serving path is checked against it, not against itself.

The weights may be stored in a lower precision (bf16 on the chip); each
layer's weights are upcast to float32 inside the layer loop, so the whole
model is never held in float32 at once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _mm(spec, *xs):
    return jnp.einsum(spec, *xs, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x: (B, S, H, hd); rotate the two halves of each head dim."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = positions[:, :, None, None].astype(F32) * freqs  # (B, S, 1, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, positions, cfg):
    p = jax.tree_util.tree_map(lambda w: w.astype(F32), p)
    a = p["attn"]
    h = _rms_norm(x, p["norm1"], cfg.norm_eps)
    q = _mm("bsd,dhk->bshk", h, a["wq"])
    k = _mm("bsd,dhk->bshk", h, a["wk"])
    v = _mm("bsd,dhk->bshk", h, a["wv"])
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k = _rope(q, positions, cfg.rope_theta), _rope(k, positions, cfg.rope_theta)
    rep = cfg.n_heads // cfg.n_kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = _mm("bqhd,bkhd->bhqk", q, k) * cfg.hd ** -0.5
    n = x.shape[1]
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    o = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    x = x + _mm("bshk,hkd->bsd", o, a["wo"])
    h = _rms_norm(x, p["norm2"], cfg.norm_eps)
    m = p["mlp"]
    g = _mm("bsd,df->bsf", h, m["w_gate"])
    u = _mm("bsd,df->bsf", h, m["w_up"])
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["w_down"])


def forward(params, tokens, cfg, *, last: int = 0):
    """Logits (B, S, vocab) in float32 for ``tokens`` (B, S) at positions
    0..S-1; ``last > 0`` returns only the final ``last`` positions."""
    if cfg.family != "dense" or cfg.tie_embeddings or cfg.window:
        raise ValueError(f"no reference for {cfg.name}: dense, untied, "
                         "full-attention decoders only")
    b, n = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)

    def body(x, p):
        return _layer(x, p, positions, cfg), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    if last:
        x = x[:, -last:]
    x = _rms_norm(x, params["final_norm"].astype(F32), cfg.norm_eps)
    return _mm("bsd,dv->bsv", x, params["lm_head"].astype(F32))
