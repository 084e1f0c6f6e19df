"""Plain float32 reference, run layer by layer: what every architecture
module's ``score`` shares.

An architecture module (``archs/<name>.py``) gives one layer's equations in
straightforward ``jax.numpy``; this module runs them, every product at
``Precision.HIGHEST``, over the embedding, each layer in turn and the blocked
LM head.  It imports nothing of the program: the weights are drawn again from
the seed by ``weights.py``, one layer at a time, so the whole model is never
held in float32.

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


def mm(spec, *xs):
    return jnp.einsum(spec, *xs, precision=HIGHEST, preferred_element_type=F32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def ident(x):
    return x


def fp8_rows(x):
    """Activations rounded to float8 e4m3, one scale per token."""
    return fp8(x, (-1,))


def fp8(w, axes):
    """``w`` rounded to float8 e4m3 with one scale per output channel
    (``axes`` are the contracted ones), back in float32."""
    s = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _upcast(tree, control: bool, fp8_axes: dict):
    """Every leaf in float32; with ``control``, each weight matrix named in
    ``fp8_axes`` rounded to float8 over its contracted axes."""
    def f(path, w):
        w = w.astype(F32)
        name = jax.tree_util.keystr(path[-1:]).strip("[]'")
        return fp8(w, fp8_axes[name]) if control and name in fp8_axes else w

    return jax.tree_util.tree_map_with_path(f, tree)


@functools.lru_cache(maxsize=None)
def _fns(cfg_items: tuple, layer_fn, axes_items: tuple):
    c = dict(cfg_items)
    axes = dict(axes_items)

    @functools.partial(jax.jit, static_argnums=2)
    def layer(x, p, control):
        return layer_fn(x, _upcast(p, control, axes), c,
                        fp8_rows if control else ident)

    @functools.partial(jax.jit, static_argnums=2)
    def embed(top, tokens, control):
        return jnp.take(_upcast(top, control, axes)["embed"], tokens, axis=0)

    @functools.partial(jax.jit, static_argnums=4)
    def head(top, x, xc, targets, control):
        """Per row of x (N, d), N a multiple of ROWS: largest reference
        logit, the reference's logit of ``targets``, and (``control``) its
        logit of the float8 pass's first token."""
        ref = _upcast(top, False, axes)
        ctl = _upcast(top, True, axes) if control else None
        eps = c["rms_norm_eps"]

        def block(args):
            xr, xcr, t = args
            lr = mm("rd,dv->rv", rms_norm(xr, ref["final_norm"], eps),
                    ref["lm_head"])
            best = jnp.max(lr, axis=-1)
            at_t = jnp.take_along_axis(lr, t[:, None], -1)[:, 0]
            if not control:
                return best, at_t, at_t
            lc = mm("rd,dv->rv",
                    fp8_rows(rms_norm(xcr, ctl["final_norm"], eps)),
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
          control: bool, *, layer, fp8_axes: dict, layout) -> dict:
    """Run the reference over ``tokens`` (B, T) and read each position's
    gap to ``targets`` (B, T): ``best - logit[target]`` (0 where the target
    is the reference's first choice).  With ``control`` also
    ``control_gap``: the same gap of the float8 pass's first choice.

    ``layer(x, p, c, act)`` is one layer of the architecture, ``fp8_axes``
    the contracted axes of its weight matrices and ``layout`` its weights'
    layout (an architecture module's)."""
    b, t = tokens.shape
    n = b * t
    pad = -n % ROWS
    layer_j, embed, head = _fns(tuple(sorted(
        (k, v) for k, v in c.items() if isinstance(v, (int, float)))),
        layer, tuple(sorted(fp8_axes.items())))
    dtype = c["torch_dtype"]
    with jax.default_matmul_precision("highest"):
        top = W.top_params(c, seed, dtype, layout)
        tok = jnp.asarray(tokens)
        x = embed(top, tok, False)
        xc = embed(top, tok, True) if control else x
        for i in range(c["num_hidden_layers"]):
            p = W.layer_params(c, seed, dtype, i, layout)
            x = layer_j(x, p, False)
            if control:
                xc = layer_j(xc, p, True)
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
