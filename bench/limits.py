#!/usr/bin/env python3
"""Readings that the check's limits are set from: sound runs and controls
of one cell over many seeds, in one process.

    python3 bench/limits.py --workload <cell> --seeds 1,2,3 --seconds <s> \\
        [--control program_fp8_cache|reference_fp8] \\
        [--fault no_exchange|altered] [--out <file.jsonl>]

Each seed is a whole run (weights, warm-up, ramp, window, drain, check) as
``run.py`` makes it; one JSON line per seed gives the numbers compared (the
widest and the mean gap of the served tokens below the reference's best)
and the check's log line, which with a control also has the float8
reference's gaps at the same positions.  ``program_fp8_cache`` serves with
the program's own float8 KV cache; ``reference_fp8`` compares the float8
reference's first choices in the program's place.  ``--fault`` plants one
of ``bench/faults.py``'s faults in the program for every run.  Runs only on
the chip.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default="",
                    choices=("", "reference_fp8", "program_fp8_cache"))
    ap.add_argument("--fault", default="", choices=("", "no_exchange",
                                                    "altered"))
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from bench import faults, harness
    from bench.run import chip, setup_jax

    cell = harness.resolve(args.workload)
    devices = chip(cell.chips)
    if devices is None:
        return 2
    setup_jax()
    if args.fault:
        faults.plant(args.fault)
    compile_log = harness.CompileLog()
    out = open(args.out, "a") if args.out else None
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            log = io.StringIO()
            with contextlib.redirect_stdout(log):
                line = harness.run(cell, seed, args.seconds, False, devices,
                                   time.monotonic(), compile_log=compile_log,
                                   control=args.control,
                                   checks_out=io.StringIO())
            found = {}
            for text in log.getvalue().splitlines():
                for key in ("check", "slot migrations", "members at window"):
                    if text.startswith(key):
                        found[key] = text
            row = json.dumps({"workload": cell.name, "seed": seed,
                              "control": args.control, "fault": args.fault,
                              "correct": line["correct"],
                              "failed": line["failed"],
                              "checks": line["checks"], **found})
            print(row, flush=True)
            if out:
                out.write(row + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
