"""Plain float32 reference of the dense decoder family, run layer by layer.

A copy of the repository's reference forward pass (RMSNorm, rotary
embeddings on half-split head dims, optional q/k/v bias, causal softmax
attention with grouped KV heads, SwiGLU MLP, untied LM head) in
straightforward ``jax.numpy``, every product at ``Precision.HIGHEST``.  It
imports nothing of the program: its weights are drawn again from the seed by
``weights.py``, one layer at a time, so the whole model is never held in
float32.

``score`` reads, at every position of the given sequences, the reference's
largest logit and its logit of the given next token.  With ``control`` it
also runs the same pass computed in float8 (e4m3): every weight matrix
rounded with one scale per output channel, and every activation that enters
a product with a weight rounded with one scale per token; it reads the
reference's logit of the token that this lower precision puts first: the
control that the comparison has to reject.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
ROWS = 256  # LM head rows per block


def _mm(spec, *xs):
    return jnp.einsum(spec, *xs, precision=HIGHEST, preferred_element_type=F32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x: (B, S, H, hd); rotate the two halves of each head dim."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = positions[:, :, None, None].astype(F32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _ident(x):
    return x


def fp8_rows(x):
    """Activations rounded to float8 e4m3, one scale per token."""
    return fp8(x, (-1,))


def _layer(x, p, c, act=_ident):
    """One decoder layer; ``act`` rounds each activation entering a product
    with a weight (the identity for the reference)."""
    a = p["attn"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    n = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), x.shape[:2])
    h = act(_rms_norm(x, p["norm1"], eps))
    q = _mm("bsd,dhk->bshk", h, a["wq"])
    k = _mm("bsd,dhk->bshk", h, a["wk"])
    v = _mm("bsd,dhk->bshk", h, a["wv"])
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    rep = c["num_attention_heads"] // c["num_key_value_heads"]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = _mm("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    o = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    x = x + _mm("bshk,hkd->bsd", act(o), a["wo"])
    h = act(_rms_norm(x, p["norm2"], eps))
    m = p["mlp"]
    g = _mm("bsd,df->bsf", h, m["w_gate"])
    u = _mm("bsd,df->bsf", h, m["w_up"])
    return x + _mm("bsf,fd->bsd", act(jax.nn.silu(g) * u), m["w_down"])


def fp8(w, axes):
    """``w`` rounded to float8 e4m3 with one scale per output channel
    (``axes`` are the contracted ones), back in float32."""
    s = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s


# Contracted axes of each weight matrix; norms and biases keep float32.
_FP8_AXES = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
             "w_gate": (0,), "w_up": (0,), "w_down": (0,),
             "embed": (1,), "lm_head": (0,)}


def _upcast(tree, control: bool):
    def f(path, w):
        w = w.astype(F32)
        name = jax.tree_util.keystr(path[-1:]).strip("[]'")
        return fp8(w, _FP8_AXES[name]) if control and name in _FP8_AXES else w

    return jax.tree_util.tree_map_with_path(f, tree)


@functools.lru_cache(maxsize=None)
def _fns(cfg_items: tuple):
    c = dict(cfg_items)

    @functools.partial(jax.jit, static_argnums=2)
    def layer(x, p, control):
        return _layer(x, _upcast(p, control), c,
                      fp8_rows if control else _ident)

    @functools.partial(jax.jit, static_argnums=2)
    def embed(top, tokens, control):
        return jnp.take(_upcast(top, control)["embed"], tokens, axis=0)

    @functools.partial(jax.jit, static_argnums=4)
    def head(top, x, xc, targets, control):
        """Per row of x (N, d), N a multiple of ROWS: largest reference
        logit, the reference's logit of ``targets``, and (``control``) its
        logit of the float8 pass's first token."""
        ref = _upcast(top, False)
        ctl = _upcast(top, True) if control else None
        eps = c["rms_norm_eps"]

        def block(args):
            xr, xcr, t = args
            lr = _mm("rd,dv->rv", _rms_norm(xr, ref["final_norm"], eps),
                     ref["lm_head"])
            best = jnp.max(lr, axis=-1)
            at_t = jnp.take_along_axis(lr, t[:, None], -1)[:, 0]
            if not control:
                return best, at_t, at_t
            lc = _mm("rd,dv->rv",
                     fp8_rows(_rms_norm(xcr, ctl["final_norm"], eps)),
                     ctl["lm_head"])
            first = jnp.argmax(lc, axis=-1)
            at_c = jnp.take_along_axis(lr, first[:, None], -1)[:, 0]
            return best, at_t, at_c

        n = x.shape[0]
        shape = (n // ROWS, ROWS)
        out = jax.lax.map(block, (x.reshape(shape + x.shape[1:]),
                                  xc.reshape(shape + xc.shape[1:]),
                                  targets.reshape(shape)))
        return tuple(o.reshape(n) for o in out)

    return layer, embed, head


def score(c: dict, seed: int, tokens: np.ndarray, targets: np.ndarray,
          control: bool = False) -> dict:
    """Run the reference over ``tokens`` (B, T) and read each position's
    gap to ``targets`` (B, T): ``best - logit[target]`` (0 where the target
    is the reference's first choice).  With ``control`` also
    ``control_gap``: the same gap of the float8 pass's first choice."""
    b, t = tokens.shape
    n = b * t
    pad = -n % ROWS
    layer, embed, head = _fns(tuple(sorted(
        (k, v) for k, v in c.items() if isinstance(v, (int, float)))))
    dtype = c["torch_dtype"]
    with jax.default_matmul_precision("highest"):
        top = W.top_params(c, seed, dtype)
        tok = jnp.asarray(tokens)
        x = embed(top, tok, False)
        xc = embed(top, tok, True) if control else x
        for i in range(c["num_hidden_layers"]):
            p = W.layer_params(c, seed, dtype, i)
            x = layer(x, p, False)
            if control:
                xc = layer(xc, p, True)
            del p
        d = x.shape[-1]
        rows = [jnp.pad(a.reshape(n, d), ((0, pad), (0, 0))) for a in (x, xc)]
        tgt = np.pad(np.asarray(targets, np.int32).reshape(n), (0, pad))
        best, at_t, at_c = head(top, *rows, jnp.asarray(tgt), control)
    best, at_t, at_c = (np.asarray(a)[:n].reshape(b, t)
                        for a in (best, at_t, at_c))
    out = {"gap": best - at_t}
    if control:
        out["control_gap"] = best - at_c
    return out
