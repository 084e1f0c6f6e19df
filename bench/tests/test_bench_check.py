"""The comparison that decides ``correct``, driven end to end on the CPU at
a tiny size (the harness's look for a chip skipped): sound runs pass, the
control (the reference in float8, or the program's float8 KV cache) fails."""
import io
import time

import jax
import pytest

from bench import harness
from bench.tests.util import tiny_root

SEED = 2**31 + 101


def run(name, tmp_path, **kw):
    cell = harness.resolve(name, root=tiny_root(str(tmp_path)))
    return harness.run(cell, SEED, 1.0, False, jax.devices()[0],
                       time.monotonic(), checks_out=io.StringIO(), **kw)


@pytest.mark.parametrize("name", ["tiny.chat", "tiny.decode"])
def test_sound_run_is_correct(name, tmp_path):
    line = run(name, tmp_path)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert line["checks"]["max_gap"]["value"] <= \
        line["checks"]["max_gap"]["limit"]
    assert {"setup_s", "tpot_p90_ms"} <= set(line["metrics"])


@pytest.mark.parametrize("control", ["reference_fp8", "program_fp8_cache"])
def test_control_is_not_correct(control, tmp_path):
    line = run("tiny.chat", tmp_path, control=control)
    assert not line["correct"], line["checks"]
    assert line["checks"]["max_gap"]["value"] > \
        line["checks"]["max_gap"]["limit"]
