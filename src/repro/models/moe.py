"""Mixture-of-Experts blocks: arctic's capacity-dropping training block, and
the held-expert layer of the ``mla_moe`` family (kimi-k2, :func:`held_moe`).

Top-k routing with capacity-bounded sort-free scatter dispatch:
tokens are scattered into an (E, C, d) buffer (sharded E→model axis,
C→data axis), experts run as one batched einsum, results are gathered
back with routing weights.  This is the dropping dispatch of
Switch/GShard adapted to GSPMD: the scatter/gather lower to
all-to-all-style collectives on the expert axis.

Arctic additionally has a *dense residual* MLP branch in parallel with
the MoE FFN (cfg.dense_residual).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed import shard
from repro.models import attention as A
from repro.models import layers as L
from repro.models.params import Spec

CAPACITY_FACTOR = 1.25


def moe_block_spec(cfg, par: int) -> dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    # EP shard_map mode routes locally on every rank -> router replicated.
    router_pspec = (None, None) if cfg.ep_shard_map else (None, "model")
    spec = {
        "attn": A.attn_spec(cfg, par),
        "router": Spec((d, E), router_pspec, "small_normal", 0.02),
        "experts": {
            "w_gate": Spec((E, d, f), ("model", None, None)),
            "w_up": Spec((E, d, f), ("model", None, None)),
            "w_down": Spec((E, f, d), ("model", None, None)),
        },
        "norm1": Spec((cfg.d_model,), (None,), "ones"),
        "norm2": Spec((cfg.d_model,), (None,), "ones"),
    }
    if cfg.dense_residual:
        spec["dense_mlp"] = {
            "w_gate": Spec((d, f), (None, "model")),
            "w_up": Spec((d, f), (None, "model")),
            "w_down": Spec((f, d), ("model", None)),
        }
    return spec


def capacity(n_tokens: int, cfg) -> int:
    c = int(n_tokens * cfg.top_k * CAPACITY_FACTOR / cfg.n_experts) + 1
    return max(8, -(-c // 8) * 8)  # round up to 8


def moe_ffn(x, p, cfg):
    """GSPMD-path MoE: x (T, d) flat tokens -> (T, d).  The partitioner
    infers the dispatch collectives from the buffer constraints (baseline;
    see moe_ffn_ep for the explicit expert-parallel §Perf path)."""
    E, K = cfg.n_experts, cfg.top_k
    C = capacity(x.shape[0], cfg)
    fids, fw, tok_idx = _route(x, p["router"], E, K)
    return _dispatch_compute_combine(x, fids, fw, tok_idx, p["experts"], E, C, constrain=True)


def aux_load_balance_loss(x, router, cfg):
    """Switch/GShard router losses: load-balance (E·Σ f_e·P_e / K) + z-loss.

    f_e = fraction of routed assignments to expert e; P_e = mean router
    probability. Minimized when routing is uniform; added to the train loss
    with a small coefficient (transformer.forward_train)."""
    E, K = cfg.n_experts, cfg.top_k
    gates = (x @ router.astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(gates, axis=-1)  # (T, E)
    _, ids = jax.lax.top_k(probs, K)
    T = x.shape[0]
    f = jnp.zeros(E, jnp.float32).at[ids.reshape(-1)].add(1.0) / (T * K)
    P = probs.mean(axis=0)
    lb = E * jnp.sum(f * P)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(gates, axis=-1)))
    return lb + 1e-3 * z


def _route(x, router, E: int, K: int):
    """Top-k routing. Returns (flat expert ids (T*K,), flat weights, tok_idx)."""
    gates = (x @ router.astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(gates, axis=-1)
    w, ids = jax.lax.top_k(probs, K)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    T = x.shape[0]
    return ids.reshape(-1), w.reshape(-1).astype(x.dtype), jnp.repeat(jnp.arange(T), K)


def _dispatch_compute_combine(x, fids, fw, tok_idx, experts, E: int, C: int,
                              constrain: bool = False):
    """Scatter tokens into (E, C, d), run experts, gather back.

    Pure local math (no collectives) in the shard_map path; in the GSPMD
    path ``constrain`` annotates the expert buffers so the partitioner keeps
    E on the model axis and C on data."""
    T, d = x.shape
    order = jnp.argsort(fids, stable=True)
    sids = fids[order]
    counts = jnp.bincount(fids, length=E)
    starts = jnp.cumsum(counts) - counts
    pos_sorted = jnp.arange(fids.shape[0], dtype=jnp.int32) - starts[sids].astype(jnp.int32)
    pos_in_e = jnp.zeros(fids.shape[0], jnp.int32).at[order].set(pos_sorted)
    keep = (pos_in_e < C).astype(x.dtype) * (fw != 0).astype(x.dtype)
    slot = jnp.minimum(pos_in_e, C - 1)
    buf = jnp.zeros((E, C, d), x.dtype).at[fids, slot].add(x[tok_idx] * keep[:, None])
    if constrain:
        buf = shard(buf, "model", "batch", None)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, experts["w_gate"])) * jnp.einsum(
        "ecd,edf->ecf", buf, experts["w_up"]
    )
    if constrain:
        h = shard(h, "model", "batch", None)
    y = jnp.einsum("ecf,efd->ecd", h, experts["w_down"])
    if constrain:
        y = shard(y, "model", "batch", None)
    y_tok = y[fids, slot] * (fw * keep)[:, None]
    return jnp.zeros((T, d), x.dtype).at[tok_idx].add(y_tok)


def moe_ffn_ep(h, p, cfg):
    """Expert-parallel MoE via shard_map (§Perf beyond-GSPMD path).

    Experts live sharded over the model axis (E/par per rank); tokens stay
    sharded over data.  Every rank routes ALL of its local tokens, keeps
    only the assignments whose expert it owns, computes locally, and the
    per-rank partial token outputs are combined with ONE psum over "model"
    — replacing the all-gather/reduce-scatter storm GSPMD infers for the
    scattered (E, C, d) buffer.  h: (B, S, d)."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import batch_axes, current_mesh

    mesh = current_mesh()
    bsz, s, d = h.shape
    E, K = cfg.n_experts, cfg.top_k
    par = mesh.shape["model"]
    E_loc = E // par
    bax = batch_axes(mesh)

    def local_fn(h, router, wg, wu, wd):
        b_loc = h.shape[0]
        x = h.reshape(b_loc * h.shape[1], d)
        rank = jax.lax.axis_index("model")
        fids, fw, tok_idx = _route(x, router, E, K)
        mine = (fids // E_loc) == rank
        fw = jnp.where(mine, fw, 0.0)
        fids_loc = jnp.where(mine, fids - rank * E_loc, 0)
        C = capacity(x.shape[0], cfg)
        out = _dispatch_compute_combine(
            x, fids_loc, fw, tok_idx, {"w_gate": wg, "w_up": wu, "w_down": wd}, E_loc, C
        )
        out = jax.lax.psum(out, "model")
        return out.reshape(b_loc, h.shape[1], d)

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(bax, None, None),
            P(None, None),
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=P(bax, None, None),
        check_vma=False,
    )
    return fn(h, p["router"], p["experts"]["w_gate"], p["experts"]["w_up"], p["experts"]["w_down"])


def _use_ep(cfg) -> bool:
    from repro.distributed.sharding import current_mesh

    mesh = current_mesh()
    return (
        cfg.ep_shard_map
        and mesh is not None
        and "model" in mesh.axis_names
        and cfg.n_experts % mesh.shape["model"] == 0
    )


def moe_block_apply(p, x, positions, cfg, *, mode, cache=None, pos=None, prefix_len=0):
    h = L.rms_norm(x, p["norm1"], cfg.norm_eps)
    if mode == "train":
        a = A.attend_full(p["attn"], h, positions, cfg, prefix_len=prefix_len)
        new_cache = None  # replaced by aux loss below
    elif mode == "prefill":
        a, new_cache = A.prefill_with_cache(p["attn"], h, positions, cfg, cache, prefix_len=prefix_len)
    elif mode == "chunk":  # mixed-phase prefill chunk; pos = (posv, valid)
        posv, valid = pos
        a, new_cache = A.chunk_step(p["attn"], h, posv, valid, cfg, cache)
    else:
        a, new_cache = A.decode_step(p["attn"], h, pos, cfg, cache)
    x = x + a
    h = L.rms_norm(x, p["norm2"], cfg.norm_eps)
    b, s, d = h.shape
    if _use_ep(cfg):
        ff = moe_ffn_ep(h, p, cfg)
    else:
        ff = moe_ffn(h.reshape(b * s, d), p, cfg).reshape(b, s, d)
    if cfg.dense_residual:
        ff = ff + L.swiglu(h, p["dense_mlp"]["w_gate"], p["dense_mlp"]["w_up"], p["dense_mlp"]["w_down"])
    x = x + ff
    if mode == "train":
        new_cache = aux_load_balance_loss(h.reshape(b * s, d), p["router"], cfg)
    return shard(x, "batch", None, None), new_cache


# ------------------------------------------------------- held-expert layer
#
# DeepSeek-V3's expert layer (Kimi-K2 reuses it) as one chip of an expert-
# parallel deployment holds it: the router scores all ``n_experts``; this
# chip computes the part of the result that its ``n_held`` experts give for
# the tokens routed to them, plus the shared experts.  What the absent
# experts would add is left out, as their chips would add it.  No token is
# dropped: every (token, expert) pair of the top-k gets a row of the grouped
# matmuls, those on held experts first, sorted by expert.


def held_moe_spec(cfg) -> dict:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_held
    fs = f * cfg.n_shared_experts
    return {
        "router": Spec((d, cfg.n_experts), scale=d ** -0.5),
        "router_bias": Spec((cfg.n_experts,), init="zeros"),
        "experts": {"w_gate": Spec((e, d, f), scale=d ** -0.5),
                    "w_up": Spec((e, d, f), scale=d ** -0.5),
                    "w_down": Spec((e, f, d), scale=f ** -0.5)},
        "shared": {"w_gate": Spec((d, fs), scale=d ** -0.5),
                   "w_up": Spec((d, fs), scale=d ** -0.5),
                   "w_down": Spec((fs, d), scale=fs ** -0.5)},
    }


def route(x, router, bias, cfg):
    """Sigmoid scores of all experts (float32); the top-k of score + bias;
    weights: the chosen scores normalised to sum 1, times route_scale.
    Returns (ids (T, K), weights (T, K) float32)."""
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", x, router,
                                  preferred_element_type=jnp.float32))
    _, ids = jax.lax.top_k(s + bias.astype(jnp.float32), cfg.top_k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    return ids, w / jnp.sum(w, axis=-1, keepdims=True) * cfg.route_scale


def held_moe(x, p, cfg, layer=0):
    """x: (T, d) -> (y (T, d), held (T,) int32: the token's top-k experts
    that are held here).  The grouped matmuls take T * top_k rows (the
    dropless bound; ``jax.lax.ragged_dot`` skips the rows past the held
    groups) and the shared experts run on every token.

    ``p["experts"]`` may hold every expert layer's experts, stacked on a
    leading axis, with ``layer`` this layer's index: the grouped matmuls then
    take the whole stack as ``L * n_held`` groups, the other layers' empty.
    A layer's slice of the stack would be a copy (a kernel's operand is a
    buffer of its own), read and written again every step."""
    t, d = x.shape
    k, e = cfg.top_k, cfg.n_held
    ids, w = route(x, p["router"], p["router_bias"], cfg)
    local = ids - cfg.expert_offset
    held = (local >= 0) & (local < e)  # (T, K)
    group = jnp.where(held, local, e).reshape(-1)  # e: not held, sorted last
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=e + 1)[:e].astype(jnp.int32)
    ex = {n: a.reshape((-1,) + a.shape[-2:]) for n, a in p["experts"].items()}
    sizes = jax.lax.dynamic_update_slice(
        jnp.zeros(ex["w_gate"].shape[0], jnp.int32), sizes, (layer * e,))
    xs = x[order // k]  # (T*K, d), held rows first, by expert
    h = (jax.nn.silu(jax.lax.ragged_dot(xs, ex["w_gate"], sizes))
         * jax.lax.ragged_dot(xs, ex["w_up"], sizes))
    ys = jax.lax.ragged_dot(h, ex["w_down"], sizes)
    # Rows past the held groups are not computed: zero them before weighting.
    ys = jnp.where((jnp.arange(t * k) < jnp.sum(sizes))[:, None], ys, 0)
    ys = ys[jnp.argsort(order)].reshape(t, k, d)  # back to token order
    y = jnp.einsum("tkd,tk->td", ys, jnp.where(held, w, 0.0).astype(ys.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    sh = p["shared"]
    y = y + L.swiglu(x, sh["w_gate"], sh["w_up"], sh["w_down"])
    return y, jnp.sum(held, axis=-1).astype(jnp.int32)
