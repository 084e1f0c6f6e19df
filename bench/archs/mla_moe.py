"""DeepSeek-V3's block, which Kimi-K2 reuses: latent attention (MLA) with
YaRN rope, one leading dense layer, then layers of sparse experts routed by
sigmoid score with a selection bias, plus a shared expert.  A configuration
may hold a share of the experts (``n_routed_experts``, from
``program.expert_offset``; the router keeps ``published.n_routed_experts``
outputs): the reference then adds only the held experts' part, as the
program does.

The weight layout is the program's ``mla_moe`` tree: ``embed``,
``final_norm``, ``lm_head``, ``dense_layer/...`` and ``moe_layers/...``
(stacked on an explicit leading axis), each leaf drawn once by
``weights.py``.

The reference (``score``) is plain float32 ``jax.numpy`` at
``Precision.HIGHEST`` and imports nothing of the program: attention in the
expanded form (keys and values through ``wkv_b``), blocked over queries;
every held expert computed for every token and weighted by its routing
weight (0 where not routed).  One departure from the published model, which
random weights cannot see: rope rotates the two halves of the rope dims
where the published model rotates interleaved pairs, the same model under a
fixed permutation of the rope columns of ``wq_b`` and ``wkv_a``.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench import weights as W
from bench.weights import BIAS_STD, EMBED_STD, NORM_STD

Q_BLOCK = 256  # reference attention: queries per block


def _dims(c: dict) -> dict:
    return dict(d=c["hidden_size"], h=c["num_attention_heads"],
                ql=c["q_lora_rank"], r=c["kv_lora_rank"],
                n=c["qk_nope_head_dim"], p=c["qk_rope_head_dim"],
                v=c["v_head_dim"], f=c["intermediate_size"],
                fe=c["moe_intermediate_size"],
                fs=c["moe_intermediate_size"] * c["n_shared_experts"],
                e=c["published"]["n_routed_experts"], eh=c["n_routed_experts"],
                k=c["num_experts_per_tok"], vocab=c["vocab_size"],
                layers=c["num_hidden_layers"],
                moe=c["num_hidden_layers"] - c["first_k_dense_replace"])


def _check(c: dict) -> None:
    """The published routing and depth pattern this module implements."""
    want = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
            "first_k_dense_replace": 1, "moe_layer_freq": 1}
    got = {k: c.get(k) for k in want}
    if got != want or c["rope_scaling"]["type"] != "yarn":
        raise ValueError(f"mla_moe implements {want} with YaRN rope; the "
                         f"configuration has {got}, {c['rope_scaling']}")


# ------------------------------------------------------------- the program
def program_config(c: dict, cache_dtype: str = ""):
    """The program's MlaMoeConfig, every size taken from the config file."""
    from repro.configs import get_config

    _check(c)
    p, rs = c["program"], c["rope_scaling"]
    return dataclasses.replace(
        get_config(p["arch"]),
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=c["tie_word_embeddings"],
        n_experts=c["published"]["n_routed_experts"],
        top_k=c["num_experts_per_tok"], q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"], qk_nope_dim=c["qk_nope_head_dim"],
        qk_rope_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        moe_d_ff=c["moe_intermediate_size"],
        n_shared_experts=c["n_shared_experts"],
        route_scale=float(c["routed_scaling_factor"]),
        experts_held=c["n_routed_experts"], expert_offset=p["expert_offset"],
        yarn_factor=float(rs["factor"]),
        yarn_orig_max_pos=rs["original_max_position_embeddings"],
        yarn_beta_fast=float(rs["beta_fast"]),
        yarn_beta_slow=float(rs["beta_slow"]),
        yarn_mscale=float(rs["mscale"]),
        yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
        compute_dtype=c["torch_dtype"], kernel_impl=p["kernel_impl"],
        cache_dtype=cache_dtype)


# ----------------------------------------------------------------- weights
def layout(c: dict) -> dict:
    """name -> (shape, kind, std) of every leaf; ``moe_layers/...`` carry
    the expert layers' leading axis."""
    m = _dims(c)
    d, h, r = m["d"], m["h"], m["r"]
    layer = {
        "attn/wq_a": ((d, m["ql"]), "normal", d ** -0.5),
        "attn/q_norm": ((m["ql"],), "norm", NORM_STD),
        "attn/wq_b": ((m["ql"], h, m["n"] + m["p"]), "normal", m["ql"] ** -0.5),
        "attn/wkv_a": ((d, r + m["p"]), "normal", d ** -0.5),
        "attn/kv_norm": ((r,), "norm", NORM_STD),
        "attn/wkv_b": ((r, h, m["n"] + m["v"]), "normal", r ** -0.5),
        "attn/wo": ((h, m["v"], d), "normal", (h * m["v"]) ** -0.5),
        "norm1": ((d,), "norm", NORM_STD),
        "norm2": ((d,), "norm", NORM_STD),
    }
    dense = dict(layer)
    dense.update({"mlp/w_gate": ((d, m["f"]), "normal", d ** -0.5),
                  "mlp/w_up": ((d, m["f"]), "normal", d ** -0.5),
                  "mlp/w_down": ((m["f"], d), "normal", m["f"] ** -0.5)})
    experts = dict(layer)
    experts.update({
        "router": ((d, m["e"]), "normal", d ** -0.5),
        "router_bias": ((m["e"],), "normal", BIAS_STD),
        "experts/w_gate": ((m["eh"], d, m["fe"]), "normal", d ** -0.5),
        "experts/w_up": ((m["eh"], d, m["fe"]), "normal", d ** -0.5),
        "experts/w_down": ((m["eh"], m["fe"], d), "normal", m["fe"] ** -0.5),
        "shared/w_gate": ((d, m["fs"]), "normal", d ** -0.5),
        "shared/w_up": ((d, m["fs"]), "normal", d ** -0.5),
        "shared/w_down": ((m["fs"], d), "normal", m["fs"] ** -0.5),
    })
    out = {"embed": ((m["vocab"], d), "normal", EMBED_STD),
           "final_norm": ((d,), "norm", NORM_STD),
           "lm_head": ((d, m["vocab"]), "normal", d ** -0.5)}
    out.update({"dense_layer/" + k: v for k, v in dense.items()})
    out.update({"moe_layers/" + k: ((m["moe"],) + s, kind, std)
                for k, (s, kind, std) in experts.items()})
    return out


# --------------------------------------------------------------- reference
# Contracted axes of each weight matrix (after the layer axis is taken off);
# norms and the router's bias keep float32.
FP8_AXES = {"wq_a": (0,), "wq_b": (0,), "wkv_a": (0,), "wkv_b": (0,),
            "wo": (0, 1), "w_gate": (-2,), "w_up": (-2,), "w_down": (-2,),
            "router": (0,), "embed": (1,), "lm_head": (0,)}


def yarn(c: dict):
    """(rope frequencies (P/2,), cos/sin factor, softmax scale), as the
    published YaRN computes them."""
    rs, dim, base = c["rope_scaling"], c["qk_rope_head_dim"], c["rope_theta"]
    factor = rs["factor"]

    def mscale(m):
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0

    def corr(rot):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inv = extra / factor * ramp + extra * (1 - ramp)
    scale = (c["qk_nope_head_dim"] + dim) ** -0.5 * mscale(
        rs["mscale_all_dim"]) ** 2
    return inv, mscale(rs["mscale"]) / mscale(rs["mscale_all_dim"]), scale


def _rope(x, c):
    """x: (B, S, ..., P) at positions 0..S-1; the two halves rotate."""
    inv, ms, _ = yarn(c)
    ang = jnp.arange(x.shape[1], dtype=R.F32)[:, None] * jnp.asarray(inv, R.F32)
    ang = ang.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + ang.shape[1:])
    cos, sin = jnp.cos(ang) * ms, jnp.sin(ang) * ms
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, a, c, act):
    m = _dims(c)
    eps = c["rms_norm_eps"]
    n, r = m["n"], m["r"]
    qa = act(R.rms_norm(R.mm("bsd,dl->bsl", x, a["wq_a"]), a["q_norm"], eps))
    q = R.mm("bsl,lhk->bshk", qa, a["wq_b"])
    q_nope, q_pe = q[..., :n], _rope(q[..., n:], c)
    kv = R.mm("bsd,dk->bsk", x, a["wkv_a"])
    lat = act(R.rms_norm(kv[..., :r], a["kv_norm"], eps))
    k_pe = _rope(kv[..., r:], c)
    kvb = R.mm("bsr,rhk->bshk", lat, a["wkv_b"])
    k_nope, v = kvb[..., :n], kvb[..., n:]
    scale = yarn(c)[2]
    b, s = x.shape[:2]
    nb = -(-s // Q_BLOCK)
    pad = ((0, 0), (0, nb * Q_BLOCK - s), (0, 0), (0, 0))
    q_nope, q_pe = jnp.pad(q_nope, pad), jnp.pad(q_pe, pad)

    def rows(i):
        q0 = i * Q_BLOCK
        qn = jax.lax.dynamic_slice_in_dim(q_nope, q0, Q_BLOCK, 1)
        qp = jax.lax.dynamic_slice_in_dim(q_pe, q0, Q_BLOCK, 1)
        sc = (R.mm("bqhn,bkhn->bhqk", qn, k_nope)
              + R.mm("bqhp,bkp->bhqk", qp, k_pe)) * scale
        causal = jnp.arange(s)[None, :] <= q0 + jnp.arange(Q_BLOCK)[:, None]
        sc = jnp.where(causal, sc, -jnp.inf)
        return R.mm("bhqk,bkhv->bqhv", jax.nn.softmax(sc, axis=-1), v)

    o = jax.lax.map(rows, jnp.arange(nb))  # (nb, B, Q_BLOCK, H, V)
    o = jnp.moveaxis(o, 0, 1).reshape(b, nb * Q_BLOCK, m["h"], m["v"])[:, :s]
    return R.mm("bshv,hvd->bsd", act(o), a["wo"])


def _swiglu(h, w, act):
    g = R.mm("...d,df->...f", h, w["w_gate"])
    u = R.mm("...d,df->...f", h, w["w_up"])
    return R.mm("...f,fd->...d", act(jax.nn.silu(g) * u), w["w_down"])


def route(h, router, bias, c):
    """Routing weights of every expert (T, E): sigmoid scores; the top-k of
    score + bias keep their score, normalised to sum 1 and scaled; the
    others 0."""
    s = jax.nn.sigmoid(R.mm("td,de->te", h, router))
    _, ids = jax.lax.top_k(s + bias, c["num_experts_per_tok"])
    chosen = jnp.zeros_like(s, bool).at[
        jnp.arange(s.shape[0])[:, None], ids].set(True)
    w = jnp.where(chosen, s, 0.0)
    return w / jnp.sum(w, -1, keepdims=True) * c["routed_scaling_factor"]


def experts(h, p, c, offset: int, act=R.ident):
    """The held experts' part for h (T, d): every held expert on every
    token, weighted by its routing weight.  The shared expert apart."""
    w = route(h, p["router"], p["router_bias"], c)
    out = jnp.zeros_like(h)
    ex = p["experts"]
    for i in range(ex["w_gate"].shape[0]):
        wi = {k: v[i] for k, v in ex.items()}
        out = out + w[:, offset + i, None] * _swiglu(h, wi, act)
    return out


def _layer(x, p, c, offset, act):
    eps = c["rms_norm_eps"]
    x = x + _attention(act(R.rms_norm(x, p["norm1"], eps)), p["attn"], c, act)
    h = act(R.rms_norm(x, p["norm2"], eps))
    if "mlp" in p:
        return x + _swiglu(h, p["mlp"], act)
    b, s, d = h.shape
    y = experts(h.reshape(b * s, d), p, c, offset, act).reshape(b, s, d)
    return x + y + _swiglu(h, p["shared"], act)


def _upcast(tree, control: bool):
    def f(path, w):
        w = w.astype(R.F32)
        name = jax.tree_util.keystr(path[-1:]).strip("[]'")
        return R.fp8(w, FP8_AXES[name]) if control and name in FP8_AXES else w

    return jax.tree_util.tree_map_with_path(f, tree)


@functools.lru_cache(maxsize=None)
def _fns(cfg_json: str):
    import json

    c = json.loads(cfg_json)
    offset = c["program"]["expert_offset"]

    @functools.partial(jax.jit, static_argnums=2)
    def layer(x, p, control):
        return _layer(x, _upcast(p, control), c, offset,
                      R.fp8_rows if control else R.ident)

    @functools.partial(jax.jit, static_argnums=4)
    def head(top, x, xc, targets, control):
        """Per row of x (N, d), N a multiple of R.ROWS: as ``reference``'s
        head: the best logit, the target's, the control's choice's."""
        eps = c["rms_norm_eps"]
        ref = _upcast(top, False)
        ctl = _upcast(top, True) if control else None

        def block(args):
            xr, xcr, t = args
            lr = R.mm("rd,dv->rv", R.rms_norm(xr, ref["final_norm"], eps),
                      ref["lm_head"])
            best = jnp.max(lr, axis=-1)
            at_t = jnp.take_along_axis(lr, t[:, None], -1)[:, 0]
            if not control:
                return best, at_t, at_t
            lc = R.mm("rd,dv->rv",
                      R.fp8_rows(R.rms_norm(xcr, ctl["final_norm"], eps)),
                      ctl["lm_head"])
            first = jnp.argmax(lc, axis=-1)
            return best, at_t, jnp.take_along_axis(lr, first[:, None], -1)[:, 0]

        n = x.shape[0]
        shape = (n // R.ROWS, R.ROWS)
        out = jax.lax.map(block, (x.reshape(shape + x.shape[1:]),
                                  xc.reshape(shape + xc.shape[1:]),
                                  targets.reshape(shape)))
        return tuple(o.reshape(n) for o in out)

    @functools.partial(jax.jit, static_argnums=2)
    def embed(table, tokens, control):
        return jnp.take(_upcast({"embed": table}, control)["embed"], tokens, 0)

    return layer, head, embed


def _nest(flat: dict, prefix: str) -> dict:
    tree: dict = {}
    for name, x in flat.items():
        if name.startswith(prefix):
            node = tree
            *path, leaf = name[len(prefix):].split("/")
            for q in path:
                node = node.setdefault(q, {})
            node[leaf] = x
    return tree


def score(c: dict, seed: int, tokens, targets, control: bool = False) -> dict:
    """``reference.score``'s gaps through this block: the reference over
    ``tokens`` (B, T), each position's best logit less its logit of
    ``targets``; with ``control`` the float8 pass's ``control_gap``."""
    import json

    layer, head, embed = _fns(json.dumps(c, sort_keys=True))
    b, t = tokens.shape
    n = b * t
    pad = -n % R.ROWS
    with jax.default_matmul_precision("highest"):
        flat = W.top_params(c, seed, c["torch_dtype"], layout)
        tok = jnp.asarray(tokens)
        x = embed(flat["embed"], tok, False)
        xc = embed(flat["embed"], tok, True) if control else x
        dense = _nest(flat, "dense_layer/")
        x = layer(x, dense, False)
        if control:
            xc = layer(xc, dense, True)
        stack = _nest(flat, "moe_layers/")
        for i in range(_dims(c)["moe"]):
            p = jax.tree_util.tree_map(lambda a: a[i], stack)
            x = layer(x, p, False)
            if control:
                xc = layer(xc, p, True)
            del p
        d = x.shape[-1]
        rows = [jnp.pad(a.reshape(n, d), ((0, pad), (0, 0))) for a in (x, xc)]
        tgt = np.pad(np.asarray(targets, np.int32).reshape(n), (0, pad))
        top = {k: flat[k] for k in ("final_norm", "lm_head")}
        best, at_t, at_c = head(top, *rows, jnp.asarray(tgt), control)
    best, at_t, at_c = (np.asarray(a)[:n].reshape(b, t)
                        for a in (best, at_t, at_c))
    out = {"gap": best - at_t}
    if control:
        out["control_gap"] = best - at_c
    return out


# ------------------------------------------------------------------- flops
# Counted, 2 operations per multiply-add: every layer's projections (the
# query's two, the latent's, ``wkv_b`` and the output's) and its dense MLP
# or, in an expert layer, the router, the shared expert and the routed
# experts' expected work here: top_k x held / published experts per token
# (the experts a token routes to among those held); the LM head.  Attention
# as each path computes it: prefill in the expanded form (per key and head:
# N + P for the score, V for the value), decode over the latent with
# ``wkv_b`` absorbed (R + P for the score, R for the value).  Not counted:
# padding, norms, rope, softmax, routing's top-k, and the held experts'
# rows that carry no routed token.
def layer_matmul_params(c: dict, moe: bool) -> float:
    m = _dims(c)
    d, h = m["d"], m["h"]
    attn = (d * m["ql"] + m["ql"] * h * (m["n"] + m["p"])
            + d * (m["r"] + m["p"]) + m["r"] * h * (m["n"] + m["v"])
            + h * m["v"] * d)
    if not moe:
        return attn + 3 * d * m["f"]
    routed = m["k"] * m["eh"] / m["e"] * 3 * d * m["fe"]
    return attn + d * m["e"] + 3 * d * m["fs"] + routed


def token_matmul_params(c: dict) -> float:
    """Multiply-adds of one token through every layer and the LM head."""
    m = _dims(c)
    return (layer_matmul_params(c, False) * (m["layers"] - m["moe"])
            + layer_matmul_params(c, True) * m["moe"] + m["d"] * m["vocab"])


def decode_flops(c: dict, context: float) -> float:
    """One generated token whose query attends ``context`` latent rows."""
    m = _dims(c)
    attn = 2 * m["h"] * (2 * m["r"] + m["p"]) * context * m["layers"]
    return 2 * token_matmul_params(c) + attn


def prefill_flops(c: dict, prompt_len: int) -> float:
    """A prompt of ``prompt_len`` real tokens: every layer at every
    position, causal expanded attention, the LM head once."""
    m = _dims(c)
    p = prompt_len
    per_token = token_matmul_params(c) - m["d"] * m["vocab"]
    attn = (2 * m["h"] * (m["n"] + m["p"] + m["v"]) * m["layers"]
            * p * (p + 1) // 2)
    return 2 * per_token * p + 2 * m["d"] * m["vocab"] + attn
