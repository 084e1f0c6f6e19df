"""One server member per chip.  A one-chip cell builds the one member on the
first device with the server's plain arguments; the tiny four-member cell,
run on four virtual CPU devices (``members_cpu.py``), is correct with every
member in use, compiles nothing inside its window, and comes out not correct
under its control and under each fault it can have."""
import json
import os
import re
import subprocess
import sys

import jax
import pytest

from bench import harness
from bench.tests.util import REPO, tiny_root

CASES = ("sound", "control", "no_exchange", "altered")


@pytest.fixture(scope="module")
def four_members():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(REPO, "src"), REPO]),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4"))
    p = subprocess.run([sys.executable, "-m", "bench.tests.members_cpu",
                        *CASES], cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return {d["case"]: d for d in map(json.loads, p.stdout.splitlines())}


def test_one_chip_cell_is_one_member_with_plain_arguments(tmp_path,
                                                           monkeypatch):
    import repro.serve

    made = []
    monkeypatch.setattr(repro.serve, "InferenceServer",
                        lambda *a, **kw: made.append(kw))
    cell = harness.resolve("tiny.chat", root=tiny_root(str(tmp_path)))
    dev = jax.devices()[0]
    sess = harness.Session(cell, 7, [dev])
    sess.server()
    (kw,) = made
    assert set(kw) == {"groups", "kernels", "buckets", "max_batch",
                       "max_new_cap"}
    (group,) = kw["groups"]
    assert group.name == "chip0" and group.devices == [dev]
    assert group.power == 1.0
    assert kw["max_batch"] == cell.traffic["server"]["slots_per_bucket"]
    assert harness.server_options(cell.traffic["server"]) == {}


def test_server_options_from_the_traffic_file(tmp_path):
    from repro.core import HGuided
    from repro.serve.multigroup import RateBalancer

    cell = harness.resolve("tiny.chat-x4", root=tiny_root(str(tmp_path)))
    assert cell.chips == 4
    opts = harness.server_options(cell.traffic["server"])
    assert isinstance(opts["scheduler"], HGuided)
    assert isinstance(opts["migration"], RateBalancer)
    assert opts["group_batches"] is True
    with pytest.raises(ValueError, match="hguided"):
        harness.server_options({"scheduler": "fastest"})


def _moved_in_check(got) -> int:
    (text,) = got["check"]
    return int(re.search(r"\((\d+) moved between chips\)", text).group(1))


def test_four_members_sound_run(four_members):
    got = four_members["sound"]
    line = got["line"]
    assert line["correct"], line["checks"]
    assert line["failed"] == 0
    assert got["member_slots"] == {f"chip{i}": 2 for i in range(4)}
    assert got["window_compiles"] == ["compilations inside the window: 0"]
    # The window's balancer starts from a rate observed on every member.
    (opened,) = got["members_open"]
    assert "None" not in opened
    assert sorted(re.findall(r"(chip\d) held", opened)) == [
        f"chip{i}" for i in range(4)]
    dev = line["device"]
    assert len(dev["memory_peak_bytes_per_device"]) == 4
    assert dev["memory_peak_bytes"] == max(dev["memory_peak_bytes_per_device"])


@pytest.mark.parametrize("case", CASES[1:])
def test_four_members_control_and_faults_not_correct(four_members, case):
    got = four_members[case]
    line = got["line"]
    assert not line["correct"], line["checks"]
    assert line["checks"]["failed_requests"]["value"] == 0
    assert line["checks"]["max_gap"]["value"] > \
        line["checks"]["max_gap"]["limit"]
    if case == "no_exchange":
        (text,) = got["migrations"]
        assert int(re.search(r"window: (\d+)", text).group(1)) > 0
        assert _moved_in_check(got) > 0


def test_repo_four_chip_cell_resolves():
    cell = harness.resolve("qwen15-4b.chat-x4")
    assert cell.chips == 4
    srv = cell.traffic["server"]
    assert (srv["scheduler"], srv["migration"], srv["group_batches"]) == (
        "hguided", "rate", True)
    names = {m["name"] for m in cell.end_to_end + cell.per_layer}
    assert {"tpot_p90_ms", "setup_s", "member_balance"} <= names
    for name in names:
        assert callable(harness.reader(name))



def test_sample_takes_moved_requests_and_is_unchanged_without():
    import numpy as np

    def picks(moved):
        reqs = [harness.Sent(np.zeros(4, np.int32), 8 + (i == 5), 16, 0.0,
                             0.0, True, status="ok", done=1.0,
                             migrated=i in moved) for i in range(40)]
        load = harness.Load(reqs, (0.0, 2.0), (0.0, 2.0), 3.0, {}, {}, [])
        chosen = harness.sample(load, 99, 8)
        return [next(i for i, r in enumerate(reqs) if r is s)
                for s in chosen]

    # No moved request: the longest, then the seed's draw, as before.
    rest = [i for i in range(40) if i != 5]
    drawn = np.random.default_rng([99, 3]).permutation(rest)[:7]
    assert picks(()) == [5] + [int(i) for i in drawn]
    many = picks(set(range(20, 40)))
    assert many[0] == 5 and len(set(many)) == 8
    assert sum(i >= 20 for i in many) >= 4
    assert 33 in picks({33})
