#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's model on the chip from the seed, warms up every program
shape its traffic uses, runs the traffic's ramp and then ``--seconds`` of
measured window, drains, and checks the served tokens against the float32
reference.  With ``--trace 0`` the result reports the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, from the program's spans
and a profiler trace of a few steady seconds.  The last line of standard
output is the result as one JSON object; the numbers compared for
``correct`` and their limits are the last lines of standard error.  Without
an accelerator, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_jax() -> None:
    """The program's fixed compile-cache directory, every program in it."""
    import jax

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def chip(chips: int):
    """The first ``chips`` accelerators, or None (said on stderr) when JAX
    finds none or fewer than ``chips``."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        print("no accelerator: the benchmark runs only on the chip",
              file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"the cell needs {chips} chips, JAX finds {len(devices)}",
              file=sys.stderr)
        return None
    return devices[:chips]


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from bench import harness

    cell = harness.resolve(args.workload)
    devices = chip(cell.chips)
    if devices is None:
        return 2
    setup_jax()
    line = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                       devices, T_PROCESS, compile_log=harness.CompileLog())
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
