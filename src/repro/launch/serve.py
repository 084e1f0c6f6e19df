"""Serving launcher: one-shot generate, co-executed generate, and the
continuous-batching server.

Three modes over one shared generate path (``serve.make_generate`` — the
plain and co-executed variants previously re-implemented prefill+chain with
*different* cache materializations; now both build caches through
``serve.zeros_cache`` and are bit-identical, which ``--verify`` asserts):

    # one-shot batched generate
    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-4b \
        --requests 16 --prompt-len 32 --gen 8

    # co-executed across simulated-heterogeneous groups (paper's regime)
    ... --coexec --scheduler hguided --verify

    # continuous-batching server, Poisson arrival replay
    ... --server --requests 32 --rate 200 --verify
"""
from __future__ import annotations

import argparse
import threading
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.core import DeviceGroup, Dynamic, EngineCL, HGuided, Program, Static
from repro.core.trace import Tracer, set_tracer, tracer
from repro.launch.compile_cache import use_compile_cache
from repro.launch.specs import make_batch
from repro.models import get_model
from repro.models.params import materialize
from repro.serve import InferenceServer, make_generate
from repro.configs.base import ShapeCell


def _schedulers():
    return {"static": Static(), "dynamic": Dynamic(8), "hguided": HGuided()}


def _serve_groups(args):
    """Device groups for server mode: ``--groups N`` (first twice the power
    of the rest) or the legacy ``--coexec`` pair; one group otherwise.
    Member i runs on device i (modulo the devices there are), so on a
    four-chip host ``--groups 4`` puts one member on each chip."""
    n = max(args.groups, 2 if args.coexec else 1)
    if n == 1:
        return [DeviceGroup("serve:0")]
    devices = jax.devices()
    return [
        DeviceGroup(f"pod-{chr(ord('a') + i)}", [devices[i % len(devices)]],
                    power=(2.0 if i == 0 else 1.0), sim_time_per_wi=0.0)
        for i in range(n)
    ]


def run_oneshot(cfg, api, params, batch, gen: int):
    """Plain batched generate through the shared prefill+chain helper."""
    return make_generate(cfg, api)(params, batch, gen)


def run_coexec(cfg, api, params, batch, args) -> np.ndarray:
    """Split the request batch across device groups through the engine —
    the same ``make_generate`` path, embedded as the chunk kernel."""
    extra = {k: v for k, v in batch.items() if k != "tokens"}
    generate = make_generate(cfg, api, jit=False)

    def kern(offset, tokens, *rest):
        *extras, params = rest
        b = {"tokens": tokens, **dict(zip(extra.keys(), extras))}
        return generate(params, b, args.gen)

    out = np.zeros((args.requests, args.gen), np.int32)
    prog = (
        Program()
        .in_(np.asarray(batch["tokens"]))
        .out(out)
        .kernel(kern, "generate")
        .arg(params)
        .work_items(args.requests, 1)
    )
    for e in extra.values():
        prog.in_(np.asarray(e))
    eng = EngineCL().use(*_serve_groups(args)).scheduler(
        _schedulers()[args.scheduler]).program(prog)
    eng.run()
    if eng.has_errors():
        raise SystemExit("\n".join(eng.get_errors()))
    s = eng.introspector.summary()
    print(f"co-exec generated {out.shape} in {s['response_time']:.2f}s "
          f"balance={s['balance']:.3f} share={s['work_share']}")
    return out


def _make_draft(cfg, params, args):
    """Resolve ``--draft`` into a DraftSpec: ``self`` re-uses the target
    params (acceptance ≈ 1 — the co-execution plumbing benchmark),
    ``reduced`` materializes fresh params of the reduced same-arch config,
    and any other value names an arch whose reduced config drafts (reduced
    configs share vocab=256, so cross-arch drafting pairs up)."""
    from repro.serve import DraftSpec

    if not args.draft:
        return None
    if args.draft == "self":
        return DraftSpec(cfg, params, k=args.draft_k,
                         auto_bypass=args.spec_gate)
    import dataclasses

    name = args.arch if args.draft == "reduced" else args.draft
    dcfg = reduced(get_config(name))
    if args.kernel:
        dcfg = dataclasses.replace(dcfg, kernel_impl=args.kernel)
    dapi = get_model(dcfg)
    dparams = materialize(dapi.param_spec(dcfg, 1),
                          jax.random.PRNGKey(args.seed + 3), dcfg.compute_dtype)
    return DraftSpec(dcfg, dparams, k=args.draft_k,
                     auto_bypass=args.spec_gate)


def _metrics_pump(server, stop: threading.Event, every: float) -> None:
    """Periodic rolling-telemetry print (``--metrics-every``): completed /
    rejected counts plus windowed TTFT and inter-token-latency quantiles."""
    def ms(v):
        return "-" if v is None else f"{v * 1e3:.1f}ms"

    while not stop.wait(every):
        tel = server.telemetry
        print(f"[metrics] completed={int(tel.counter('requests_completed'))} "
              f"rejected={int(tel.counter('requests_rejected'))} "
              f"ttft_p50={ms(tel.quantile('ttft_s', 0.5))} "
              f"ttft_p99={ms(tel.quantile('ttft_s', 0.99))} "
              f"itl_p50={ms(tel.quantile('itl_s', 0.5))} "
              f"queue_p50={ms(tel.quantile('queue_wait_s', 0.5))}",
              flush=True)


def run_server(cfg, api, params, args) -> None:
    """Replay a seeded Poisson arrival trace through ``InferenceServer``."""
    from repro.core.obs import EngineObs
    from repro.serve import ObsHTTP, PagedSpec

    rng = np.random.default_rng(args.seed + 2)
    prompts = [
        rng.integers(0, cfg.vocab, args.prompt_len).astype(np.int32)
        for _ in range(args.requests)
    ]
    gaps = rng.exponential(1.0 / args.rate, args.requests)
    paged = PagedSpec(block_len=args.block_len) if args.paged else None
    groups = _serve_groups(args)
    obs = EngineObs(enabled=args.http_port >= 0 or tracer().enabled,
                    crash_dir=args.crash_dir)
    server = InferenceServer(
        cfg, api, params,
        groups=groups,
        scheduler=_schedulers()[args.scheduler],
        buckets=(args.prompt_len,),
        max_batch=args.max_batch,
        seg_len=args.seg_len,
        max_new_cap=max(args.gen, 1),
        max_wait_ms=args.max_wait_ms,
        paged=paged,
        draft=_make_draft(cfg, params, args),
        chunk_len=args.chunk_len,
        # --groups opts into per-group batches even for contiguous KV;
        # legacy --coexec keeps the slot-splitting regime (None = auto).
        group_batches=True if args.groups > 1 else None,
        obs=obs,
    )
    deadline = args.deadline_ms / 1e3 if args.deadline_ms else None
    http = None
    if args.http_port >= 0:
        http = ObsHTTP(server, port=args.http_port)
        print(f"[obs-http] serving /metrics /healthz /stats on "
              f"{http.url()}", flush=True)
    stop = threading.Event()
    pump = None
    if args.metrics_every > 0:
        pump = threading.Thread(
            target=_metrics_pump, args=(server, stop, args.metrics_every),
            name="metrics-pump", daemon=True)
        pump.start()
    t0 = time.perf_counter()
    drained = None
    try:
        with server:
            handles = []
            for i, (p, gap) in enumerate(zip(prompts, gaps)):
                time.sleep(gap)
                handles.append(server.submit(p, args.gen, deadline_s=deadline))
                if (args.drain_after and i + 1 == args.drain_after
                        and server.group_batches and len(groups) > 1):
                    drained = groups[-1].name
                    server.drain_group(drained)
            results = []
            for h in handles:
                # Wait for the *final* state before reading `rejected`: a
                # request may pass submit-time admission and still be
                # rejected later, at boarding time, once queue wait has
                # eaten its budget.  A failed request raises here.
                h.wait(timeout=600)
                results.append(None if h.rejected else h.result(timeout=600))
            wall = time.perf_counter() - t0
            if http is not None and args.http_hold_s > 0:
                # Keep the live server (and its endpoints) up so an
                # external scraper — the CI smoke's curl — can probe a
                # healthy engine, not a closed one.
                print(f"[obs-http] holding {args.http_hold_s:.0f}s for "
                      "scrapes", flush=True)
                time.sleep(args.http_hold_s)
    finally:
        if http is not None:
            http.close()
    if pump is not None:
        stop.set()
        pump.join(timeout=5)
        print(server.prometheus(), end="")
    lat = sorted(h.metrics["latency"] for h in handles if not h.rejected)
    s = server.stats()
    pct = (f"p50={lat[len(lat) // 2] * 1e3:.0f}ms "
           f"p99={lat[-1] * 1e3:.0f}ms " if lat else "")
    print(
        f"served {s['completed']}/{args.requests} requests in {wall:.2f}s "
        f"(rate {args.rate}/s, {s['rejected']} rejected) "
        f"{pct}occupancy={s['occupancy_mean']:.2f} "
        f"tokens/s={s['tokens_out'] / wall:.1f}"
    )
    if server.group_batches:
        print(f"multi-group: slots={s['placement']['member_slots']} "
              f"migrations={s['slot_migrations']}"
              + (f" drained={drained}" if drained else ""))
    if s["tokens_drafted"]:
        print(
            f"speculation k={args.draft_k}: {s['tokens_accepted']}/"
            f"{s['tokens_drafted']} draft tokens accepted "
            f"(acceptance={s['acceptance']:.2f})"
        )
    if "speculation" in s:
        g = s["speculation"]
        print(f"spec gate: {g['speculated_segments']} spec / "
              f"{g['bypassed_segments']} plain segments "
              f"({g['probes']} probes)")
    mem = s.get("memory", {})
    if mem.get("mode") == "paged":
        print(
            f"paged KV: peak {mem['blocks_peak']}/{mem['blocks_total']} "
            f"blocks ({mem['kv_bytes_allocated']} B allocated, "
            f"{mem['kv_bytes_touched']} B touched), "
            f"{mem['prefix_hits']} prefix hits, {mem['cow']} CoW, "
            f"{s['deferred']} boardings deferred"
        )
    if args.verify:
        if s["rejected"]:
            # A rejected request has no output to check: a run that served
            # nothing must not pass verification.
            raise SystemExit(f"verify: {s['rejected']} of {args.requests} "
                             "requests were rejected")
        generate = make_generate(cfg, api)
        for p, r in zip(prompts, results):
            want = np.asarray(generate(params, {"tokens": jnp.asarray(p[None])},
                                       args.gen))[0]
            if not np.array_equal(r, want):
                raise SystemExit(f"verify: served {r} != one-shot {want}")
        print(f"verify: {len(results)} results "
              "bit-identical to one-shot generate")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--coexec", action="store_true")
    ap.add_argument("--scheduler", default="hguided",
                    choices=["static", "dynamic", "hguided"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--server", action="store_true",
                    help="continuous-batching server, Poisson arrivals")
    ap.add_argument("--rate", type=float, default=100.0,
                    help="offered load, requests/s (server mode)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request latency budget (0 = none)")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--seg-len", type=int, default=2)
    ap.add_argument("--max-wait-ms", type=float, default=10.0)
    ap.add_argument("--paged", action="store_true",
                    help="serve from the paged KV block pool (block tables "
                         "+ prefix cache; with --groups N each group owns "
                         "its own pool and prefix-cache namespace)")
    ap.add_argument("--groups", type=int, default=1,
                    help="server mode: co-execute across N device groups, "
                         "group i on device i, one batch (and, under "
                         "--paged, one KV block pool) per group; wave "
                         "placement and slot migration follow --scheduler")
    ap.add_argument("--drain-after", type=int, default=0,
                    help="server mode with --groups >1: after this many "
                         "submissions, drain the last group — its decode "
                         "slots migrate to the surviving groups at segment "
                         "boundaries (elastic scale-down; --verify still "
                         "holds)")
    ap.add_argument("--block-len", type=int, default=16,
                    help="tokens per KV block in --paged mode (a multiple "
                         "of 8 under --kernel pallas: the chip tiles KV "
                         "blocks in 8-row units)")
    ap.add_argument("--chunk-len", type=int, default=0,
                    help="chunked prefill (server mode): advance each "
                         "prompt this many tokens per decode segment "
                         "inside the mixed-phase segment Program instead "
                         "of running a whole-prompt prefill Program "
                         "(0 = off, the legacy prefill/decode barrier). "
                         "Outputs stay bit-identical (--verify holds)")
    ap.add_argument("--draft", default="",
                    help="speculative decoding draft (server mode): 'self' "
                         "(target params; acceptance ~1), 'reduced' (fresh "
                         "reduced same-arch params), or an arch name whose "
                         "reduced config drafts.  Outputs stay bit-identical"
                         " to one-shot generate (--verify still holds)")
    ap.add_argument("--draft-k", type=int, default=2,
                    help="draft tokens proposed per verify step")
    ap.add_argument("--spec-gate", action="store_true",
                    help="auto-bypass speculation when the forecast "
                         "speedup drops below 1 (plain segments, periodic "
                         "re-probes; stats()['speculation'] shows the "
                         "per-bucket mode).  Without it a --draft server "
                         "drafts every segment")
    ap.add_argument("--verify", action="store_true",
                    help="assert outputs bit-identical to one-shot generate")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome trace-event JSON of the run "
                         "(load in Perfetto / chrome://tracing); covers "
                         "every mode — server, co-exec, one-shot")
    ap.add_argument("--http-port", type=int, default=-1,
                    help="server mode: serve live /metrics (Prometheus), "
                         "/healthz (liveness + per-group readiness), and "
                         "/stats (JSON) on 127.0.0.1:PORT for the run's "
                         "duration (0 = ephemeral port, -1 = off).  Also "
                         "enables continuous efficiency accounting and the "
                         "scheduler decision journal")
    ap.add_argument("--http-hold-s", type=float, default=0.0,
                    help="server mode with --http-port: keep the live "
                         "server and endpoints up this many seconds after "
                         "the replay drains, so external scrapers can probe "
                         "a healthy engine")
    ap.add_argument("--crash-dir", default="crashes",
                    help="directory for flight-recorder post-mortem "
                         "bundles (written on engine failure)")
    ap.add_argument("--metrics-every", type=float, default=0.0,
                    help="server mode: print rolling telemetry (completed, "
                         "TTFT/ITL quantiles) every N seconds, plus the "
                         "Prometheus exposition at exit (0 = off)")
    ap.add_argument("--kernel", default="",
                    choices=["", "reference", "pallas", "pallas_interpret"],
                    help="override cfg.kernel_impl (pallas_interpret runs "
                         "the Pallas kernels — flash-attention prefill and "
                         "ragged flash-decode — on CPU; --verify still "
                         "holds: the kernel path is bit-identical per row)")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = reduced(cfg)
    if args.kernel:
        import dataclasses

        cfg = dataclasses.replace(cfg, kernel_impl=args.kernel)
    if args.paged and args.kernel in ("pallas", "pallas_interpret"):
        import dataclasses

        # Tile the contiguous one-shot reference at the pool's block length
        # so --verify compares equal logical tile partitions (the paged
        # bit-identity contract on the Pallas path, DESIGN.md §10).
        cfg = dataclasses.replace(cfg, decode_block=args.block_len)
    api = get_model(cfg)
    params = materialize(api.param_spec(cfg, 1), jax.random.PRNGKey(args.seed),
                         cfg.compute_dtype)

    if args.trace_out:
        set_tracer(Tracer(capacity=1 << 17, enabled=True))
    try:
        if args.server:
            run_server(cfg, api, params, args)
            return
        cell = ShapeCell("serve", args.prompt_len, args.requests, "prefill")
        batch = make_batch(cfg, cell, jax.random.PRNGKey(args.seed + 1))
        t0 = time.time()
        if not args.coexec:
            toks = run_oneshot(cfg, api, params, batch, args.gen)
            print(f"generated {toks.shape} in {time.time() - t0:.2f}s")
            print(np.asarray(toks[: min(4, args.requests)]))
            return
        out = run_coexec(cfg, api, params, batch, args)
        print(out[: min(4, args.requests)])
        if args.verify:
            want = np.asarray(run_oneshot(cfg, api, params, batch, args.gen))
            assert np.array_equal(out, want), "co-exec != one-shot generate"
            print("verify: co-exec output bit-identical to one-shot generate")
    finally:
        if args.trace_out:
            doc = tracer().write(args.trace_out)
            print(f"trace: {len(doc['traceEvents'])} events -> "
                  f"{args.trace_out}")


if __name__ == "__main__":
    main()
