#!/usr/bin/env python3
"""Find where an open-loop cell's backlog starts to grow.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 0.1,0.2,0.3 [--out <file.jsonl>]

One process builds the cell's model once, warms it up, and serves the cell's
traffic at each rate in turn (ramp, ``--seconds`` of window, drain).  For each
rate it prints one JSON line: the rate offered and completed in the window,
the backlog (sent but not finished) at the window's middle and end, and the
latency tails.  The knee is the highest rate whose backlog does not grow
between the two; the cell's own rate is set from it by hand, in its traffic
file.  Runs only on the chip.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def backlog(load, t: float) -> int:
    return sum(s.sent <= t and (s.done is None or s.done > t)
               for s in load.requests)


def ttft_quantile(ctx, q: float) -> float:
    """The q-quantile of first token - due time over the window's requests
    (unfinished ones at the drain's end), in ms."""
    from bench.stats import quantile

    return 1e3 * quantile([(r.first if r.status == "ok" else ctx.t_end)
                           - r.scheduled for r in ctx.requests
                           if r.in_window], q)


def summary(rate: float, load, ctx, read) -> dict:
    w0, w1 = load.window
    done = [s for s in load.requests
            if s.status == "ok" and w0 <= s.done < w1]
    return {
        "rate_rps": rate,
        "offered_rps": sum(s.in_window for s in load.requests) / (w1 - w0),
        "completed_rps": len(done) / (w1 - w0),
        "backlog_mid": backlog(load, (w0 + w1) / 2),
        "backlog_end": backlog(load, w1),
        "ttft_p50_ms": ttft_quantile(ctx, 0.5),
        "ttft_p90_ms": ttft_quantile(ctx, 0.9),
        "tpot_p90_ms": read("tpot_p90_ms")(ctx),
        "tokens_per_s": read("tokens_per_s")(ctx),
        "compiles_in_window": load.compiles,
        "failed": sum(s.status != "ok" for s in load.requests),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from bench import harness, loadgen
    from bench.run import chip, setup_jax

    cell = harness.resolve(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit("a sweep needs an open-loop cell")
    devices = chip(cell.chips)
    if devices is None:
        return 2
    setup_jax()
    compile_log = harness.CompileLog()
    sess = harness.Session(cell, args.seed, devices)
    sess.warm_up()
    harness.log(f"ready after {time.monotonic() - T_PROCESS:.1f} s")

    out = open(args.out, "w") if args.out else None
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            traffic = dict(cell.traffic, rate_rps=rate)
            plan = loadgen.make_plan(traffic, args.seed, args.seconds,
                                     cell.config["vocab_size"])
            sess.cell = dataclasses.replace(cell, traffic=traffic)
            load = harness.drive(sess, plan, args.seconds,
                                 compile_log=compile_log)
            ctx = harness.context(sess.cell, load, 0.0,
                                  devices[0].device_kind)
            row = json.dumps(summary(rate, load, ctx, harness.reader))
            print(row, flush=True)
            if out:
                out.write(row + "\n")
                out.flush()
    finally:
        if out:
            out.close()
        sess.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
