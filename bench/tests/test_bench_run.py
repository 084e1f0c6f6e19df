"""``run.py`` refuses to run where JAX finds no accelerator."""
import os
import subprocess
import sys

from bench.tests.util import REPO


def test_exits_nonzero_without_accelerator():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench", "run.py"), "--workload",
         "qwen15-4b.chat", "--seed", str(2**31 + 5), "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "no accelerator" in p.stderr
