#!/usr/bin/env python3
"""Smoke test of the serving system on a TPU, at qwen1.5-4b's published
widths (40 layers, d_model 2560, 20 heads of 128, d_ff 6912, vocab 151936)
with bf16 weights drawn from ``--seed``.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip path, and nothing else

One chip: the continuous-batching ``InferenceServer`` serves a few requests
with the Pallas kernels, first from contiguous KV slots, then from the paged
block pool; every request must complete.  The served tokens are compared
with one-shot ``make_generate`` (bit-identity is reported, not required),
and the logits the serving path computes for them are compared with the
plain float32 reference forward pass; that comparison decides the result.

Four chips: (i) the EngineCL runtime co-executes a data-parallel kernel
over every chip with HGuided, and its output must equal the same program on
one chip; (ii) a server with one member per chip, each holding its own copy
of the weights, serves the requests, checked as above.

Everything runs in this one process, which holds the chip(s).  Wall times
printed per phase are set-up times, compilation included, and not
measurements.  The last line of standard output is
``{"ok": true, "device": {...}}``, printed only when every phase passed;
any failure exits non-zero, and so does a machine without a TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen1.5-4b"
N_REQUESTS, PROMPT_LEN, GEN = 8, 128, 32
MAX_BATCH, SEG_LEN = 4, 4
BLOCK_LEN = 16  # paged KV block; the chip tiles KV blocks in 8-row units
# Served-path logits (bf16 weights and compute) against the float32
# reference, per scored position: ||s - r|| / ||r||.  bf16 keeps 8
# significant bits, and rounding through 40 residual layers measured about
# 2% on a cut-width qwen1.5-4b (40 layers, d_model 640) on the CPU; a
# format with 4 significant bits (fp8) would be some 16 times worse.
REL_TOL = 0.05
# A served token must be a near-argmax of the reference: its reference logit
# within MARGIN * max|s - r| of the row's maximum.  Two bf16 computations,
# each within that distance of the reference, can disagree on a near-tie by
# up to twice it; MARGIN = 4 covers the server's batch differing from the
# scoring batch (its rounding is another bf16 computation of the same size).
MARGIN = 4.0


def log(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def phase(name: str, device):
    """Print a phase's wall time (set-up incl. compile) and peak memory."""
    log(f"[{name}] start")
    t0 = time.perf_counter()
    try:
        yield
    except BaseException:
        log(f"[{name}] FAILED after {time.perf_counter() - t0:.1f}s")
        raise
    peak = (device.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"[{name}] passed: {time.perf_counter() - t0:.1f}s wall "
        f"(set-up incl. compile, not a measurement), "
        f"peak_bytes_in_use={peak}")


def smoke_config():
    from repro.configs import get_config

    # decode_block = BLOCK_LEN: the paged phase's one-shot reference tiles
    # its contiguous cache like the pool, and both servers share kernels.
    return dataclasses.replace(get_config(ARCH), kernel_impl="pallas",
                               decode_block=BLOCK_LEN)


def build_model(seed: int):
    import jax

    from repro.models import get_model
    from repro.models.params import materialize

    cfg = smoke_config()
    api = get_model(cfg)
    params = jax.block_until_ready(
        materialize(api.param_spec(cfg, 1), jax.random.PRNGKey(seed),
                    cfg.compute_dtype))
    leaves = jax.tree_util.tree_leaves(params)
    dtypes = sorted({str(x.dtype) for x in leaves})
    if dtypes != [cfg.compute_dtype]:
        raise RuntimeError(f"weights are {dtypes}, want {cfg.compute_dtype}")
    log(f"model {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}x{cfg.hd} kv_heads={cfg.n_kv_heads} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab} weights={dtypes[0]} "
        f"params={sum(x.size for x in leaves)} "
        f"bytes={sum(x.nbytes for x in leaves)} kernels={cfg.kernel_impl}")
    return cfg, api, params


def make_prompts(cfg, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed + 1)
    return rng.integers(0, cfg.vocab, (N_REQUESTS, PROMPT_LEN)).astype(np.int32)


def serve(cfg, api, params, prompts, groups, *, kernels=None, paged=None):
    """Serve every prompt through ``InferenceServer``; a request that is
    rejected or fails raises.  Returns the (N, GEN) served tokens."""
    from repro.core import HGuided, Static
    from repro.serve import InferenceServer

    multi = len(groups) > 1
    srv = InferenceServer(
        cfg, api, params, groups=groups, kernels=kernels,
        scheduler=HGuided() if multi else Static(), group_batches=multi,
        buckets=(PROMPT_LEN,), max_batch=MAX_BATCH, seg_len=SEG_LEN,
        max_new_cap=GEN, paged=paged)
    with srv:
        handles = [srv.submit(p, GEN) for p in prompts]
        out = np.stack([h.result(timeout=900) for h in handles])
    s = srv.stats()
    log(f"served {s['completed']}/{len(prompts)} requests: rejected="
        f"{s['rejected']} failed={s['failed']} segments={s['segments']} "
        f"occupancy_mean={s['occupancy_mean']:.2f} joins={s['joins']}")
    if multi:
        log(f"members: slots={s['placement']['member_slots']} "
            f"migrations={s['slot_migrations']}")
    mem = s.get("memory", {})
    if mem.get("mode") == "paged":
        log(f"paged KV: peak {mem['blocks_peak']}/{mem['blocks_total']} "
            f"blocks of {BLOCK_LEN} tokens")
    if (s["completed"], s["rejected"], s["failed"]) != (len(prompts), 0, 0):
        raise RuntimeError(f"not every request completed: {s}")
    if out.shape != (len(prompts), GEN):
        raise RuntimeError(f"served tokens have shape {out.shape}")
    return out


def one_shot(cfg, api, params, prompts) -> np.ndarray:
    """Per-request one-shot generate (batch of one) on the default chip."""
    import jax.numpy as jnp

    from repro.serve import make_generate

    gen = make_generate(cfg, api)
    return np.stack([np.asarray(gen(params, {"tokens": jnp.asarray(p[None])},
                                    GEN))[0] for p in prompts])


def report_identity(name: str, served, want) -> None:
    same = [bool(np.array_equal(a, b)) for a, b in zip(served, want)]
    log(f"{name}: served tokens bit-identical to one-shot generate for "
        f"{sum(same)}/{len(same)} requests")


def make_scorers(cfg, api):
    """Jitted (serving path, float32 reference) scorers of a continuation:
    both give (N, GEN, vocab) logits for prompts + served tokens."""
    import jax

    from repro.models.reference import forward
    from repro.serve import make_scored_continuation

    def reference(w, t):
        with jax.default_matmul_precision("highest"):
            return forward(w, t, cfg, last=GEN)

    return jax.jit(make_scored_continuation(cfg, api)), jax.jit(reference)


def check_reference(name: str, scorers, params, prompts, toks) -> None:
    """Score ``toks`` on the serving path (prefill, then decode through the
    cache, bf16) and with the float32 reference; raise unless every scored
    position is within REL_TOL and every served token is a near-argmax."""
    import jax.numpy as jnp

    served_path, reference = scorers
    cont = jnp.asarray(toks[:, :-1])
    p = jnp.asarray(prompts)
    s = np.asarray(served_path(params, p, cont))
    r = np.asarray(reference(params, jnp.concatenate([p, cont], 1)))
    d = s - r
    rel = np.sqrt((d * d).sum(-1) / (r * r).sum(-1))  # (N, GEN)
    delta = np.abs(d).max(-1)
    gap = r.max(-1) - np.take_along_axis(r, toks[..., None], -1)[..., 0]
    near = gap <= MARGIN * delta
    log(f"{name}: vs float32 reference over {rel.size} positions: rel err "
        f"max={rel.max():.5f} mean={rel.mean():.5f} (tol {REL_TOL}), "
        f"max|s-r|={delta.max():.4f}, served token is the reference argmax "
        f"at {int((gap == 0).sum())}/{gap.size}, near-argmax at "
        f"{int(near.sum())}/{gap.size}; first-token rel err "
        f"max={rel[:, 0].max():.5f}")
    if not (np.all(np.isfinite(s)) and rel.max() <= REL_TOL and near.all()):
        raise RuntimeError(f"{name}: served path disagrees with the reference")


def one_chip(args, device) -> None:
    from repro.core import DeviceGroup
    from repro.serve import PagedSpec
    from repro.serve.batcher import ModelKernels

    with phase("build", device):
        cfg, api, params = build_model(args.seed)
        prompts = make_prompts(cfg, args.seed)
    group = DeviceGroup("tpu:0", [device])
    kernels = ModelKernels(cfg, api, params)
    with phase("serve-contiguous", device):
        contiguous = serve(cfg, api, params, prompts, [group], kernels=kernels)
    with phase("serve-paged", device):
        paged = serve(cfg, api, params, prompts, [group], kernels=kernels,
                      paged=PagedSpec(block_len=BLOCK_LEN))
    with phase("one-shot", device):
        want = one_shot(cfg, api, params, prompts)
        report_identity("contiguous", contiguous, want)
        report_identity("paged", paged, want)
    with phase("reference", device):
        scorers = make_scorers(cfg, api)
        check_reference("contiguous", scorers, params, prompts, contiguous)
        check_reference("paged", scorers, params, prompts, paged)


def four_chips(args, devices) -> None:
    from benchmarks.kernels import make_mandelbrot, mandelbrot_kernel
    from repro.core import DeviceGroup, DeviceMask, EngineCL, HGuided, Program

    d0 = devices[0]
    with phase("coexec-kernel", d0):
        bench = make_mandelbrot(width=8192, height=4096)

        def run(engine):
            out = np.zeros_like(bench["outs"][0])
            prog = (Program().in_(bench["ins"][0]).out(out)
                    .kernel(mandelbrot_kernel, "mandelbrot")
                    .work_items(bench["gws"], bench["lws"]))
            engine.scheduler(HGuided()).program(prog).run()
            if engine.has_errors():
                raise RuntimeError("\n".join(engine.get_errors()))
            return out, engine.introspector.summary()

        out4, summary = run(EngineCL().use(DeviceMask.TPU))
        out1, _ = run(EngineCL().use(DeviceGroup("tpu:0", [d0])))
        share = summary["work_share"]
        log(f"mandelbrot {bench['gws']} pixels over {len(share)} chips: "
            f"work share={share} balance={summary['balance']:.3f}")
        if len(share) != len(devices) or min(share.values()) <= 0:
            raise RuntimeError(f"not every chip took work: {share}")
        if not np.array_equal(out4, out1):
            raise RuntimeError(f"{int((out4 != out1).sum())} pixels differ "
                               "from the one-chip run")
        log("co-executed output equal to the one-chip run")
    with phase("build", d0):
        cfg, api, params = build_model(args.seed)
        prompts = make_prompts(cfg, args.seed)
    with phase("serve-4-members", d0):
        groups = [DeviceGroup(f"tpu:{d.id}", [d]) for d in devices]
        served = serve(cfg, api, params, prompts, groups)
        for d in devices:
            log(f"chip {d.id}: peak_bytes_in_use="
                f"{(d.memory_stats() or {}).get('peak_bytes_in_use')}")
    with phase("one-shot", d0):
        report_identity("4-member", served, one_shot(cfg, api, params, prompts))
    with phase("reference", d0):
        check_reference("4-member", make_scorers(cfg, api), params, prompts,
                        served)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    d0 = devices[0]
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)}")
    if d0.platform != "tpu":
        log("no TPU: the smoke test runs only on the chip")
        return 1
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} chips, found "
            f"{len(devices)}")
        return 1
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.launch.compile_cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    if args.chips == 4:
        four_chips(args, devices[:4])
    else:
        one_chip(args, d0)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
