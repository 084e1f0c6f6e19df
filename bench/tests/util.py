"""A scratch checkout holding the benchmark and two tiny cells for tests."""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "tests", "data")
if os.path.join(REPO, "src") not in sys.path:  # the program under test
    sys.path.insert(0, os.path.join(REPO, "src"))


# The tiny cells and their chips: tiny.chat-x4 runs four members.
CELLS = {"tiny.chat": 1, "tiny.decode": 1, "tiny.chat-x4": 4}


def tiny_root(tmp: str) -> str:
    """``tmp`` made into a root: a copy of ``bench/`` with the tiny
    configuration and traffic files, and a BENCHMARK.json naming them."""
    bench = os.path.join(tmp, "bench")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    shutil.copy(os.path.join(DATA, "tiny.json"), os.path.join(bench, "configs"))
    for t in CELLS:
        shutil.copy(os.path.join(DATA, t + ".json"),
                    os.path.join(bench, "traffic"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"] = [{"name": "tiny", "source": "tests",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "tests"}]
    spec["workloads"] = [
        {"name": t, "config": "tiny", "traffic": t, "chips": chips,
         "why": "tests"} for t, chips in CELLS.items()]
    # Every metric in both tiny cells, but mfu: the CPU has no peak.
    spec["per_layer"] = [m for m in spec["per_layer"] if m["name"] != "mfu"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return tmp
