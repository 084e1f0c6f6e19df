"""Records the two-chip trace that ``test_bench_trace_reduce.py`` reads: a
profiler trace, between the harness's two window anchors, of a chain of
matmuls on the first TPU chip and half as many on the second.

    python3 bench/tests/record_tpu_trace.py <out dir>

writes ``<out dir>/plugins/profile/run/tpu.xplane.pb``.  Runs only on a host
with two or more TPU chips.
"""
from __future__ import annotations

import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(out: str) -> int:
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax
    import jax.numpy as jnp

    from bench import harness
    from bench import trace_reduce as T

    devs = [d for d in jax.devices() if d.platform == "tpu"][:2]
    if len(devs) < 2:
        print("needs two TPU chips", file=sys.stderr)
        return 2
    step = jax.jit(lambda x: (x @ x) * (1.0 / 4096))
    xs = [jax.device_put(jnp.ones((4096, 4096), jnp.bfloat16), d)
          for d in devs]
    xs = [step(x).block_until_ready() for x in xs]  # compile on both chips
    prof = harness.Profiler(time.monotonic(), 0.2, "tpu")
    try:
        while T.START not in prof.anchors and prof.error is None:
            time.sleep(0.001)
        for i in range(40):
            xs[0] = step(xs[0])
            if i % 2 == 0:
                xs[1] = step(xs[1])
        jax.block_until_ready(xs)
        prof.thread.join()
        if prof.error is not None:
            raise prof.error
        dst = os.path.join(out, "plugins", "profile", "run")
        os.makedirs(dst, exist_ok=True)
        shutil.copy(T.find_trace(prof.dir), os.path.join(dst, "tpu.xplane.pb"))
        print(T.reduce_dir(prof.dir, prof.anchors, (), "tpu"))
    finally:
        prof.thread.join()
        shutil.rmtree(prof.dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
