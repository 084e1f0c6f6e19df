"""Benchmark runner — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (scaffold contract):
  - table3_usability : derived = raw/engine token ratio
  - fig7_overhead    : us_per_call = engine time (us); derived = overhead %
  - fig9_balance     : derived = mean balance per scheduler
  - fig11_efficiency : derived = mean efficiency per scheduler
  - async_submit     : derived = concurrent/sequential speedup on the
                       persistent runtime (Future-based submit())
  - pipeline         : derived = waited-chain/pipelined speedup of a linked-
                       buffer run graph (plus transfer-count ratio)
  - serve            : derived = mean decode-batch occupancy / tokens per
                       second / rejection rate of the continuous-batching
                       server under an offered-load sweep
  - decode           : derived = ragged-vs-dense decode-attention speedup
                       per (cache depth, slot occupancy) cell
  - spec             : derived = speculative-vs-sequential decode speedup
                       per (draft depth k, acceptance rate alpha) cell
  - roofline         : derived = roofline fraction per (arch, shape) cell

Also writes ``BENCH_coexec.json`` (balance / efficiency / overhead),
``BENCH_pipeline.json`` (pipelined vs. waited-chain wall-clock + transfer
counts), ``BENCH_serve.json`` (serving latency/throughput under load) and
``BENCH_decode.json`` (ragged flash-decode vs dense cached attention) so
successive PRs have a perf trajectory to diff against.

Fast mode (default) uses reduced iteration counts so the full suite runs in
minutes on the CI container; ``--full`` reproduces the paper-scale settings.

``--baseline BENCH_x.json ...`` turns the run into a regression gate: the
named committed reports are snapshotted *before* the benchmarks overwrite
them, and the fresh output is compared against the committed values —
any ``tokens_per_s`` cell more than 20% slower, or any serving
``ttft_p99_s`` cell more than 30% higher, fails the run (exit 1).
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def table3_usability(rows: list[str]) -> None:
    from benchmarks import usability as U

    e = U.metrics(U.ENGINECL_VERSION)
    r = U.metrics(U.RAW_JAX_VERSION)
    ratios = [r[k] / e[k] for k in e if e[k]]
    rows.append(f"table3_usability_tok_ratio,0,{r['TOK'] / e['TOK']:.2f}")
    rows.append(f"table3_usability_mean_ratio,0,{np.mean(ratios):.2f}")


def fig7_overhead(rows: list[str], report: dict, iters: int) -> None:
    from benchmarks import overhead as O

    res = O.run(iters=iters)
    for rr in res:
        rows.append(
            f"fig7_overhead_{rr['benchmark']},{rr['enginecl_ms'] * 1e3:.0f},"
            f"{rr['overhead_pct']:.2f}"
        )
    mean = float(np.mean([rr["overhead_pct"] for rr in res]))
    rows.append(f"fig7_overhead_mean,0,{mean:.2f}")
    report["overhead"] = {
        "per_benchmark": {rr["benchmark"]: rr["overhead_pct"] for rr in res},
        "mean_pct": mean,
    }


def fig9_11_coexec(rows: list[str], report: dict, target_seconds: float) -> None:
    from benchmarks import coexec as C

    res = C.run(target_seconds=target_seconds)
    by_sched: dict = {}
    for rr in res:
        by_sched.setdefault(rr["scheduler"], []).append(rr)
    report["coexec"] = {}
    for s, items in by_sched.items():
        bal = float(np.mean([i["balance"] for i in items]))
        eff = float(np.mean([i["efficiency"] for i in items]))
        t = float(np.mean([i["coexec_s"] for i in items]))
        rows.append(f"fig9_balance_{s},{t * 1e6:.0f},{bal:.3f}")
        rows.append(f"fig11_efficiency_{s},{t * 1e6:.0f},{eff:.3f}")
        report["coexec"][s] = {
            "balance": bal,
            "efficiency": eff,
            "speedup": float(np.mean([i["speedup"] for i in items])),
            "coexec_s": t,
        }


def async_submit(rows: list[str], report: dict, n_programs: int = 4) -> None:
    """Future-based submit(): N independent Programs in flight on the
    persistent workers vs. the same Programs run() back-to-back."""
    from repro.core import DeviceGroup, Dynamic, EngineCL, Program

    n, lws = 1 << 15, 64

    def kern(offset, x):
        return np.float32(2.0) * x + 1.0

    def make_programs():
        progs = []
        for i in range(n_programs):
            x = np.arange(n, dtype=np.float32) * (i + 1)
            y = np.zeros(n, np.float32)
            progs.append(Program().in_(x).out(y).kernel(kern).work_items(n, lws))
        return progs

    eng = EngineCL().use(DeviceGroup("a"), DeviceGroup("b")).scheduler(Dynamic(8))
    for p in make_programs():  # warm compile + workers
        eng.program(p).run()

    t0 = time.perf_counter()
    for p in make_programs():
        eng.program(p).run()
    t_seq = time.perf_counter() - t0

    t0 = time.perf_counter()
    handles = [eng.submit(p) for p in make_programs()]
    for h in handles:
        h.result()
    t_async = time.perf_counter() - t0

    speedup = t_seq / t_async if t_async > 0 else 0.0
    rows.append(f"async_submit_speedup,{t_async * 1e6:.0f},{speedup:.2f}")
    report["async_submit"] = {
        "n_programs": n_programs,
        "sequential_s": t_seq,
        "concurrent_s": t_async,
        "speedup": speedup,
    }


def pipeline_bench(rows: list[str], n_stages: int = 6, n: int = 1 << 20,
                   reps: int = 3, json_path: str = "BENCH_pipeline.json") -> None:
    """Dataflow run graphs vs. the pre-dataflow waited chain.

    Both sides execute the same ``n_stages``-deep linked-buffer chain
    (stage k+1 reads what stage k wrote).  The *waited* baseline reproduces
    the old submission protocol: host-block after every stage and re-read
    each intermediate from host memory (its per-chunk re-versioning made
    every dependent stage a transfer-cache miss).  The *pipelined* side
    submits the whole chain as a run graph and waits once; intermediates
    hand off device-resident.  Emits ``BENCH_pipeline.json`` with wall-clock
    and host<->device transfer counts for both."""
    from repro.core import DeviceGroup, EngineCL, Program, Static

    lws = 64

    def kern(offset, a):
        return a * np.float32(1.0001) + np.float32(0.5)

    def make_chain():
        bufs = [np.linspace(0.0, 1.0, n).astype(np.float32)]
        progs = []
        for _ in range(n_stages):
            bufs.append(np.zeros(n, np.float32))
            progs.append(
                Program().in_(bufs[-2]).out(bufs[-1]).kernel(kern).work_items(n, lws)
            )
        return progs

    def run_waited(eng):
        for p in make_chain():
            eng.program(p).run()
            for b in p._outs:  # old protocol: per-chunk bump == downstream miss
                p.invalidate(b)

    def run_pipelined(eng):
        eng.run_pipeline(*make_chain())

    # One deterministic group per mode (handoff locality is exact, so the
    # transfer counts are a property of the protocol, not of thread timing).
    g_wait = DeviceGroup("waited")
    g_pipe = DeviceGroup("pipelined")
    eng_wait = EngineCL().use(g_wait).scheduler(Static())
    eng_pipe = EngineCL().use(g_pipe).scheduler(Static())
    run_waited(eng_wait)  # warm compile + workers (both engines share the
    run_pipelined(eng_pipe)  # jitted kernel shape)
    t_wait = min(_timed(run_waited, eng_wait) for _ in range(reps))
    t_pipe = min(_timed(run_pipelined, eng_pipe) for _ in range(reps))

    # Transfer count for ONE chain execution of each mode (fresh groups).
    g_wait2, g_pipe2 = DeviceGroup("w2"), DeviceGroup("p2")
    run_waited(EngineCL().use(g_wait2).scheduler(Static()))
    run_pipelined(EngineCL().use(g_pipe2).scheduler(Static()))

    speedup = t_wait / t_pipe if t_pipe > 0 else 0.0
    rows.append(f"pipeline_speedup,{t_pipe * 1e6:.0f},{speedup:.2f}")
    rows.append(
        f"pipeline_transfers,{g_pipe2.n_transfers},"
        f"{g_pipe2.n_transfers / max(1, g_wait2.n_transfers):.2f}"
    )
    out = {
        "n_stages": n_stages,
        "elements": n,
        "waited_s": t_wait,
        "pipelined_s": t_pipe,
        "speedup": speedup,
        "waited_transfers": g_wait2.n_transfers,
        "pipelined_transfers": g_pipe2.n_transfers,
        "pipelined_cache_hits": g_pipe2.n_cache_hits,
    }
    with open(json_path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)


def serve_bench(rows: list[str], full: bool,
                json_path: str = "BENCH_serve.json") -> None:
    """Continuous-batching server under offered load: p50/p99 latency,
    tokens/s, mean decode-batch occupancy, deadline rejection rate.
    Emits ``BENCH_serve.json``."""
    from benchmarks import serve_load as S

    out = S.run(n_requests=32 if full else 16,
                rates=(25.0, 100.0, 400.0) if full else (50.0, 400.0))
    for r in out["sweep"]:
        tag = f"{r['rate_rps']:g}rps" + ("_slo" if r["deadline_s"] else "")
        tag += "_paged" if r.get("kv_mode") == "paged" else ""
        rows.append(f"serve_p99_{tag},{r['p99_s'] * 1e6:.0f},"
                    f"{r['mean_batch_occupancy']:.2f}")
        rows.append(f"serve_tokens_{tag},{r['wall_s'] * 1e6:.0f},"
                    f"{r['tokens_per_s']:.1f}")
        if r["deadline_s"]:
            rows.append(f"serve_rejection_{tag},0,{r['rejection_rate']:.3f}")
    for r in out.get("mixed_sweep", []):
        tag = f"{r['rate_rps']:g}rps_mixed"
        tag += f"_c{r['chunk_len']}" if r.get("chunk_len") else ""
        rows.append(f"serve_ttft_p99_{tag},"
                    f"{r['ttft_p99_interactive_s'] * 1e6:.0f},"
                    f"{r['tokens_per_s']:.1f}")
    pv = out.get("paged_vs_contiguous")
    if pv:
        # derived = paged/contiguous peak KV allocation at equal load (< 1:
        # memory scales with recorded depth, not slot capacity).
        rows.append(f"serve_kv_alloc_ratio,{pv['paged_kv_bytes_allocated']},"
                    f"{pv['allocated_ratio']:.3f}")
    to = out.get("tracing_overhead")
    if to:
        # derived = tokens/s cost of leaving span tracing on (the <3%
        # observability contract; CI asserts it from the JSON report).
        rows.append(f"serve_tracing_overhead,0,{to['overhead_pct']:.2f}")
    cw = out.get("chunked_vs_whole")
    if cw:
        # derived = whole/chunked p99 TTFT at the top mixed-prompt rate
        # (> 1: dissolving prefill into decode segments cut the first-token
        # tail; tokens/s must hold — the baseline gate checks both).
        rows.append(
            f"serve_chunked_ttft_ratio,{cw['chunked_ttft_p99_s'] * 1e6:.0f},"
            f"{cw['ttft_p99_ratio']:.2f}")
    with open(json_path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)


def multigroup_bench(rows: list[str], full: bool,
                     json_path: str = "BENCH_serve.json") -> None:
    """Multi-group co-executed paged serving: 1-vs-2-group scaling at equal
    offered load and load-balance efficiency under a 3:1 rating skew
    (simulated device speeds, HGuided placement).  Merges under the
    ``multigroup_scaling`` key of ``BENCH_serve.json`` (run it after the
    ``serve`` table, which rewrites that file)."""
    from benchmarks import serve_load as S

    out = S.multigroup_scaling(n_requests=32 if full else 16)
    b, sk = out["balanced"], out["skewed"]
    rows.append(f"serve_multigroup_scaling,0,{b['scaling_x']:.2f}")
    rows.append(f"serve_multigroup_efficiency,0,{sk['efficiency']:.3f}")
    try:
        with open(json_path) as f:
            doc = json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        doc = {}
    doc["multigroup_scaling"] = out
    with open(json_path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)


def decode_bench(rows: list[str], full: bool,
                 json_path: str = "BENCH_decode.json") -> None:
    """Ragged flash-decode vs the dense decode-attention path across cache
    depths and slot occupancies (tokens/s + fraction of cache FLOPs/bytes
    actually touched).  Emits ``BENCH_decode.json``."""
    from benchmarks import decode as D

    out = D.run(full=full)
    for r in out["sweep"]:
        tag = f"{r['depth']}_{r['occupancy']}"
        rows.append(f"decode_ragged_{tag},{r['ragged_us']:.0f},"
                    f"{r['speedup']:.2f}")
        rows.append(f"decode_touched_{tag},{r['dense_us']:.0f},"
                    f"{r['flops_touched_frac']:.4f}")
    with open(json_path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)


def spec_bench(rows: list[str], full: bool,
               json_path: str = "BENCH_decode.json") -> None:
    """Speculative decoding on the multi-row verify path: tokens/s vs the
    plain one-token decode chain across (draft depth k, acceptance rate
    alpha) with a scripted-oracle draft, plus the real self-draft row.
    Merges under the ``spec`` key of ``BENCH_decode.json`` (so run it after
    the ``decode`` table, which rewrites that file)."""
    from benchmarks import spec as SP

    out = SP.run(full=full)
    for r in out["sweep"]:
        tag = f"k{r['k']}_a{r['alpha']:g}"
        rows.append(f"spec_{tag},{1e6 / r['tokens_per_s']:.1f},"
                    f"{r['speedup']:.2f}")
    sd = out["self_draft"]
    rows.append(f"spec_self_k{sd['k']},{1e6 / sd['tokens_per_s']:.1f},"
                f"{sd['speedup']:.2f}")
    try:
        with open(json_path) as f:
            doc = json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        doc = {}
    doc["spec"] = out
    with open(json_path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)


# Keys that identify a sweep cell (used to build stable baseline labels for
# list entries, so reordering a sweep cannot mispair cells).
_ID_KEYS = ("rate_rps", "deadline_s", "chunk_len", "kv_mode", "depth",
            "occupancy", "k", "alpha")


def _walk_metric(obj, match: str, prefix: str = "") -> dict[str, float]:
    """Flatten every numeric metric whose key contains ``match`` in a BENCH
    report to a stable ``path.key`` -> value map."""
    out: dict[str, float] = {}
    if isinstance(obj, dict):
        for key in sorted(obj):
            v = obj[key]
            # "ratio" keys are comparisons between cells, not metrics of a
            # cell — both of a ratio's legs are gated directly instead.
            if isinstance(v, (int, float)) and match in key \
                    and "ratio" not in key:
                out[f"{prefix}{key}"] = float(v)
            elif isinstance(v, (dict, list)):
                out.update(_walk_metric(v, match, f"{prefix}{key}."))
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            tag = str(i)
            if isinstance(v, dict):
                ids = [f"{kk}={v[kk]}" for kk in _ID_KEYS if kk in v]
                if ids:
                    tag = ",".join(ids)
            out.update(_walk_metric(v, match, f"{prefix}[{tag}]."))
    return out


def load_baselines(paths: list[str]) -> dict[str, dict[str, dict[str, float]]]:
    """Snapshot committed gated metrics before the run overwrites the
    report files in place: throughput (``tokens_per_s``, higher is better)
    and serving first-token tail latency (``ttft_p99_s``, lower is
    better)."""
    snaps = {}
    for p in paths:
        with open(p) as f:
            doc = json.load(f)
        snaps[p] = {"tokens_per_s": _walk_metric(doc, "tokens_per_s"),
                    "ttft_p99_s": _walk_metric(doc, "ttft_p99")}
    return snaps


def check_baselines(snaps: dict[str, dict[str, dict[str, float]]],
                    tol: float = 0.20, ttft_tol: float = 0.30) -> list[str]:
    """Compare freshly written reports against the committed snapshots:
    one failure line per tokens/s metric > ``tol`` below baseline and per
    p99-TTFT metric > ``ttft_tol`` above it (throughput regresses *down*,
    tail latency regresses *up*).  Cells present only on one side are
    skipped (sweeps may grow/shrink)."""
    fails = []
    for p, snap in snaps.items():
        try:
            with open(p) as f:
                doc = json.load(f)
        except (FileNotFoundError, json.JSONDecodeError):
            fails.append(f"{p}: not regenerated by this run")
            continue
        fresh_tok = _walk_metric(doc, "tokens_per_s")
        for key, want in sorted(snap["tokens_per_s"].items()):
            got = fresh_tok.get(key)
            if got is None or want <= 0:
                continue
            if got < (1.0 - tol) * want:
                fails.append(
                    f"{p}:{key}: {got:.1f} tokens/s is "
                    f"{100 * (1 - got / want):.0f}% below baseline "
                    f"{want:.1f} (tolerance {tol:.0%})"
                )
        fresh_ttft = _walk_metric(doc, "ttft_p99")
        for key, want in sorted(snap["ttft_p99_s"].items()):
            got = fresh_ttft.get(key)
            if got is None or want <= 0:
                continue
            if got > (1.0 + ttft_tol) * want:
                fails.append(
                    f"{p}:{key}: {got * 1e3:.0f}ms p99 TTFT is "
                    f"{100 * (got / want - 1):.0f}% above baseline "
                    f"{want * 1e3:.0f}ms (tolerance {ttft_tol:.0%})"
                )
    return fails


def _timed(fn, *args) -> float:
    t0 = time.perf_counter()
    fn(*args)
    return time.perf_counter() - t0


def roofline(rows: list[str]) -> None:
    from pathlib import Path

    from benchmarks.roofline import fraction

    d = Path("experiments/dryrun")
    if not d.exists():
        return
    for f in sorted(d.glob("*__pod16x16.json")):
        r = json.loads(f.read_text())
        if r.get("status") != "ok":
            continue
        dom_s = max(r["roofline"][k] for k in ("compute_s", "memory_s", "collective_s"))
        rows.append(f"roofline_{r['arch']}_{r['shape']},{dom_s * 1e6:.0f},{fraction(r):.4f}")


KNOWN_TABLES = ("usability", "overhead", "coexec", "async", "pipeline",
                "serve", "multigroup", "decode", "spec", "roofline")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument(
        "--tables", nargs="*", default=list(KNOWN_TABLES),
        help=f"subset of {', '.join(KNOWN_TABLES)}",
    )
    ap.add_argument("--json", default="BENCH_coexec.json",
                    help="machine-readable balance/efficiency/overhead report")
    ap.add_argument("--pipeline-json", default="BENCH_pipeline.json",
                    help="machine-readable pipelined-vs-waited chain report")
    ap.add_argument("--serve-json", default="BENCH_serve.json",
                    help="machine-readable serving load-sweep report")
    ap.add_argument("--decode-json", default="BENCH_decode.json",
                    help="machine-readable ragged-decode sweep report")
    ap.add_argument("--baseline", nargs="*", default=[],
                    help="committed BENCH_*.json files to gate against: "
                         "fail (exit 1) if any fresh tokens_per_s metric "
                         "regresses >20%%, or any serving ttft_p99_s "
                         "metric rises >30%%, vs its committed value")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    unknown = sorted(set(args.tables) - set(KNOWN_TABLES))
    if unknown:
        # A typo'd table name must fail loudly (nonzero exit), not emit an
        # empty CSV a CI step would happily wave through.
        ap.error(f"unknown table(s) {', '.join(unknown)}; "
                 f"known: {', '.join(KNOWN_TABLES)}")

    # Snapshot committed baselines BEFORE any table overwrites them in place.
    baselines = load_baselines(args.baseline)

    rows: list[str] = ["name,us_per_call,derived"]
    report: dict = {}
    if "usability" in args.tables:
        table3_usability(rows)
    if "overhead" in args.tables:
        fig7_overhead(rows, report, iters=5 if args.full else 2)
    if "coexec" in args.tables:
        fig9_11_coexec(rows, report, target_seconds=2.0 if args.full else 0.75)
    if "async" in args.tables:
        async_submit(rows, report)
    if "pipeline" in args.tables:
        pipeline_bench(rows, reps=5 if args.full else 3,
                       json_path=args.pipeline_json)
    if "serve" in args.tables:
        serve_bench(rows, args.full, json_path=args.serve_json)
    if "multigroup" in args.tables:
        multigroup_bench(rows, args.full, json_path=args.serve_json)
    if "decode" in args.tables:
        decode_bench(rows, args.full, json_path=args.decode_json)
    if "spec" in args.tables:
        spec_bench(rows, args.full, json_path=args.decode_json)
    if "roofline" in args.tables:
        roofline(rows)
    print("\n".join(rows))
    if report and args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"# wrote {args.json}")  # after the CSV block: stdout contract
    if baselines:
        fails = check_baselines(baselines)
        if fails:
            print("# BASELINE REGRESSION:")
            print("\n".join(f"#   {f}" for f in fails))
            raise SystemExit(1)
        n = sum(len(m) for v in baselines.values() for m in v.values())
        print(f"# baseline check passed ({n} metrics: tokens/s within "
              "20%, p99 TTFT within 30%)")


if __name__ == "__main__":
    main()
