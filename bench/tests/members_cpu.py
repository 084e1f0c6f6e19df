"""Runs of the tiny four-member cell on four virtual CPU devices, for
``test_bench_members.py``; one JSON line per case on standard output.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python -m bench.tests.members_cpu <case> [<case> ...]

Cases: ``sound``; ``control`` (the float8 reference in the program's place);
and the faults of ``bench/faults.py``: ``no_exchange`` (a migrated slot's
cache rows are not sent to the other chip) and ``altered`` (each decoded
token replaced by the next id).  The check compares the cell's own sample
of requests.  The harness's look for a chip is skipped; the rest of a run is
driven as ``run.py`` drives it.
"""
from __future__ import annotations

import io
import json
import sys
import tempfile
import time

SEED = 2**31 + 211
LOGGED = {"window_compiles": "compilations inside the window",
          "members_open": "members at window open",
          "migrations": "slot migrations inside the window",
          "check": "check:"}


def main(cases) -> None:
    from bench.tests.util import tiny_root
    import repro.serve.server as server

    seen = {}
    close = server.InferenceServer.close

    def counting_close(self, *a, **k):
        seen["all_migrations"] = self.stats()["slot_migrations"]
        seen["member_slots"] = self.stats()["placement"]["member_slots"]
        return close(self, *a, **k)

    server.InferenceServer.close = counting_close
    with tempfile.TemporaryDirectory() as tmp:
        _run(cases, tiny_root(tmp), seen)


def _run(cases, root, seen) -> None:
    import jax

    from bench import faults, harness

    cell = harness.resolve("tiny.chat-x4", root=root)
    log = harness.CompileLog()
    for case in cases:
        undo = (faults.plant(case) if case in faults.CASES
                else (lambda: None))
        out = io.StringIO()
        with_log = io.StringIO()
        sys.stdout, real = with_log, sys.stdout
        try:
            line = harness.run(cell, SEED, 1.0, False, jax.devices()[:4],
                               time.monotonic(), compile_log=log,
                               control="reference_fp8" if case == "control"
                               else "", checks_out=out)
        finally:
            sys.stdout = real
            undo()
        logged = {key: [t for t in with_log.getvalue().splitlines()
                        if t.startswith(start)]
                  for key, start in LOGGED.items()}
        print(json.dumps({"case": case, "line": line, **logged, **seen}),
              flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
