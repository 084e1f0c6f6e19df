"""Multi-head latent attention (MLA): DeepSeek-V3's, which Kimi-K2 reuses.

One layer, with R = kv_lora_rank, N/P/V = qk_nope/qk_rope/v head dims:

    q          = wq_b(rms(wq_a x))        per head: q_nope (N) | q_pe (P)
    c | k_pe   = wkv_a x                  c: the latent (R); k_pe: one rope
    c          = rms(c)                   key (P) that every head shares
    k_nope | v = wkv_b c                  per head: N | V
    score      = (q_nope . k_nope + rope(q_pe) . rope(k_pe)) * scale
    out        = wo (softmax(score) v)

The cache keeps ``c`` and ``rope(k_pe)`` per position: (R + P) values, for
all heads.  Training and prefill expand k and v through ``wkv_b``
(:func:`attend`); decode absorbs ``wkv_b`` into the query and the output and
attends over the latent rows themselves (:func:`decode_step`):
``q_nope . k_nope = (q_nope W_uk) . c`` and ``p . v = (p . c) W_uv``.

Rope turns only the P dims, with YaRN's frequencies and softmax scale
(:func:`yarn_inv_freq`, :func:`softmax_scale`).  The dims are rotated as two
halves, where the published model rotates interleaved pairs: the same model
under a fixed permutation of the rope columns of ``wq_b`` and ``wkv_a``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models.params import Spec


def mla_spec(cfg) -> dict:
    d, h, ql, r = cfg.d_model, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    n, p, v = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "wq_a": Spec((d, ql), scale=d ** -0.5),
        "q_norm": Spec((ql,), init="ones"),
        "wq_b": Spec((ql, h, n + p), scale=ql ** -0.5),
        "wkv_a": Spec((d, r + p), scale=d ** -0.5),
        "kv_norm": Spec((r,), init="ones"),
        "wkv_b": Spec((r, h, n + v), scale=r ** -0.5),
        "wo": Spec((h, v, d), scale=(h * v) ** -0.5),
    }


def cache_leaf_spec(cfg, batch: int, max_seq: int, layers: int = 0) -> dict:
    """The latent cache: ``c_kv`` (R) and ``k_pe`` (P) per position, of one
    layer (B, S, w) or of ``layers`` (B, L, S, w): slot-leading, as a
    serving slot keeps it.  Rows are valid up to the slot's position."""
    dt = cfg.cache_dtype or None
    lead = (batch, layers) if layers else (batch,)
    return {"c_kv": Spec(lead + (max_seq, cfg.kv_lora_rank), init="zeros",
                         dtype=dt),
            "k_pe": Spec(lead + (max_seq, cfg.qk_rope_dim), init="zeros",
                         dtype=dt)}


# ------------------------------------------------------------------ YaRN
def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg) -> np.ndarray:
    """The P/2 rope frequencies: ``theta^(-2i/P)`` for the dims that turn
    fewer than ``beta_fast`` times over the original context, that over
    ``yarn_factor`` for those that turn more than ``beta_slow`` times, a
    linear ramp between."""
    dim, base = cfg.qk_rope_dim, cfg.rope_theta
    inv = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not cfg.yarn_factor:
        return inv.astype(np.float32)

    def corr(rotations):
        return dim * math.log(cfg.yarn_orig_max_pos
                              / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (inv / cfg.yarn_factor * ramp + inv * (1 - ramp)).astype(np.float32)


def rope_mscale(cfg) -> float:
    """YaRN's factor on cos and sin (1 when mscale == mscale_all_dim)."""
    if not cfg.yarn_factor:
        return 1.0
    return (_yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
            / _yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))


def softmax_scale(cfg) -> float:
    """(N + P)^-1/2, times YaRN's mscale(factor, mscale_all_dim) squared."""
    s = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        s *= _yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return s


def rope(x, positions, cfg):
    """x: (B, S, ..., P); positions: (B, S).  Rotates the two halves."""
    inv = jnp.asarray(yarn_inv_freq(cfg))
    ang = positions.astype(jnp.float32)[..., None] * inv  # (B, S, P/2)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    ms = rope_mscale(cfg)
    cos, sin = jnp.cos(ang) * ms, jnp.sin(ang) * ms
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ------------------------------------------------------------ projections
def _project(p, x, positions, cfg):
    """x: (B, S, d) -> q_nope (B,S,H,N), q_pe (B,S,H,P) roped, c (B,S,R)
    normed, k_pe (B,S,P) roped."""
    n, r = cfg.qk_nope_dim, cfg.kv_lora_rank
    qa = L.rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsl,lhk->bshk", qa, p["wq_b"])
    kv = x @ p["wkv_a"]
    c = L.rms_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    return (q[..., :n], rope(q[..., n:], positions, cfg), c,
            rope(kv[..., r:], positions, cfg))


def _causal_rows(q_nope, q_pe, k_nope, k_pe, v, q0, scale):
    """Queries at positions q0.. over keys 0..S-1, causal."""
    s = (jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhp,bkp->bhqk", q_pe, k_pe,
                      preferred_element_type=jnp.float32)) * scale
    qpos = q0 + jnp.arange(q_nope.shape[1])[:, None]
    s = jnp.where(jnp.arange(k_nope.shape[1])[None, :] <= qpos, s, -1e30)
    probs = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhv->bqhv", probs, v)


def attend(p, x, positions, cfg, *, block: int = 256):
    """Expanded causal attention over the sequence x: (B, S, d) (training
    and prefill; positions 0..S-1).  Queries go in blocks of ``block`` so
    the scores of a block, not of the whole square, are held.  Returns
    (out (B,S,d), c (B,S,R), k_pe (B,S,P)): the rows the cache keeps."""
    n = cfg.qk_nope_dim
    q_nope, q_pe, c, k_pe = _project(p, x, positions, cfg)
    kv = jnp.einsum("bsr,rhk->bshk", c, p["wkv_b"])
    k_nope, v = kv[..., :n], kv[..., n:]
    scale = softmax_scale(cfg)
    b, s = x.shape[:2]
    if s <= block:
        o = _causal_rows(q_nope, q_pe, k_nope, k_pe, v, 0, scale)
    else:
        pad = -s % block
        nb = (s + pad) // block

        def blocks(a):
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            return jnp.moveaxis(a.reshape((b, nb, block) + a.shape[2:]), 1, 0)

        o = jax.lax.map(
            lambda a: _causal_rows(a[0], a[1], k_nope, k_pe, v, a[2], scale),
            (blocks(q_nope), blocks(q_pe), jnp.arange(nb) * block))
        o = jnp.moveaxis(o, 0, 1).reshape((b, nb * block) + o.shape[3:])[:, :s]
    return jnp.einsum("bshv,hvd->bsd", o, p["wo"]), c, k_pe


def decode_step(p, x, pos, cfg, cache, layer=None):
    """One token per slot. x: (B, 1, d); pos: (B,) positions.  Writes the
    token's latent row at ``pos`` and attends rows 0..pos of the latent
    cache with ``wkv_b`` absorbed (``kernels.mla_decode``).  With ``layer``
    the cache leaves hold every layer, (B, L, S, w), and this layer's rows
    are written and read in place."""
    from repro.models.attention import pos_vector

    n = cfg.qk_nope_dim
    b = x.shape[0]
    posv = pos_vector(pos, b)
    q_nope, q_pe, c, k_pe = _project(p, x, posv[:, None], cfg)
    stack = {k: v if layer is not None else v[:, None]
             for k, v in cache.items()}
    i = 0 if layer is None else layer
    bidx = jnp.arange(b)
    stack = {"c_kv": stack["c_kv"].at[bidx, i, posv].set(
                 c[:, 0].astype(stack["c_kv"].dtype)),
             "k_pe": stack["k_pe"].at[bidx, i, posv].set(
                 k_pe[:, 0].astype(stack["k_pe"].dtype))}
    w_uk, w_uv = p["wkv_b"][..., :n], p["wkv_b"][..., n:]
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk)
    args = (q_lat, q_pe[:, 0], stack["c_kv"], stack["k_pe"], posv, i)
    if cfg.kernel_impl in ("pallas", "pallas_interpret"):
        from repro.kernels.mla_decode import mla_decode

        o_lat = mla_decode(*args, scale=softmax_scale(cfg),
                           interpret=cfg.kernel_impl == "pallas_interpret")
    else:
        from repro.kernels.mla_decode import mla_decode_ref

        o_lat = mla_decode_ref(*args, scale=softmax_scale(cfg))
    o = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv)
    out = jnp.einsum("bhv,hvd->bd", o, p["wo"])[:, None]
    if layer is None:
        stack = {k: v[:, 0] for k, v in stack.items()}
    return out, stack
