"""A configuration, traffic mix, metric reader or architecture module dropped
in as a file is found by its name, with no edit to the harness."""
import io
import json
import os

import pytest

from bench import harness
from bench.tests.util import BENCH, tiny_root


def test_new_cell_and_metric_found_by_name(tmp_path):
    root = tiny_root(str(tmp_path))
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", "tiny.json")) as f:
        conf = json.load(f)
    conf["num_hidden_layers"] = 3
    with open(os.path.join(bench, "configs", "tiny-3.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(bench, "traffic", "tiny.chat.json")) as f:
        traffic = json.load(f)
    traffic["rate_rps"] = 7.0
    with open(os.path.join(bench, "traffic", "tiny-3.burst.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench, "metrics", "requests_seen.py"), "w") as f:
        f.write("def read(ctx):\n    return len(ctx.requests)\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-3", "source": "tests",
                            "file": "bench/configs/tiny-3.json",
                            "reduced": [], "why": "tests"})
    spec["workloads"].append({"name": "tiny-3.burst", "config": "tiny-3",
                              "traffic": "tiny-3.burst", "chips": 1,
                              "why": "tests"})
    spec["per_layer"].append({"name": "requests_seen", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "server", "moves": "tpot_p90_ms",
                              "workloads": ["tiny-3.burst"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    cell = harness.resolve("tiny-3.burst", root=root)
    assert cell.config["num_hidden_layers"] == 3
    assert cell.traffic["rate_rps"] == 7.0
    assert "requests_seen" in [m["name"] for m in cell.per_layer]
    assert "requests_seen" not in [
        m["name"] for m in harness.resolve("tiny.chat", root=root).per_layer]
    got = harness.read_metrics(cell.per_layer[-1:],
                               harness.Context(requests=[1, 2]), bench)
    assert got == {"requests_seen": {"value": 2.0, "unit": "1"}}


def test_repo_cells_resolve():
    for name in ("qwen15-4b.chat", "internlm2-20b-s12.decode"):
        cell = harness.resolve(name)
        assert cell.chips == 1
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                        "tpot_p90_ms"}
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.reader(m["name"]))


TOY = '''"""A toy architecture for tests: the dense module's equations, each call
recorded, and operation counts of its own."""
from bench import archs

_dense = archs.load({"program": {"bench_arch": "dense"}})
CALLS = []


def program_config(c, cache_dtype=""):
    CALLS.append("program_config")
    return _dense.program_config(c, cache_dtype)


def layout(c):
    CALLS.append("layout")
    return _dense.layout(c)


def score(c, seed, tokens, targets, control=False):
    CALLS.append("score")
    return _dense.score(c, seed, tokens, targets, control)


def prefill_flops(c, prompt_len):
    return 1000 * prompt_len


def decode_flops(c, context):
    return 10**6
'''
SHARED = ("harness.py", "weights.py", "reference.py", "flops.py",
          os.path.join("metrics", "mfu.py"))


def add_arch_cell(root, arch):
    """A configuration ``tiny-<arch>`` naming ``arch`` and its cell
    ``tiny-<arch>.chat`` in ``root``."""
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", "tiny.json")) as f:
        conf = json.load(f)
    conf["program"]["bench_arch"] = arch
    with open(os.path.join(bench, "configs", f"tiny-{arch}.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": f"tiny-{arch}", "source": "tests",
                            "file": f"bench/configs/tiny-{arch}.json",
                            "reduced": [], "why": "tests"})
    spec["workloads"].append({"name": f"tiny-{arch}.chat",
                              "config": f"tiny-{arch}", "traffic": "tiny.chat",
                              "chips": 1, "why": "tests"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return f"tiny-{arch}.chat"


def test_new_architecture_found_by_name(tmp_path):
    import time

    import jax

    from bench.tests.test_bench_stats import rec

    root = tiny_root(str(tmp_path))
    with open(os.path.join(root, "bench", "archs", "toy.py"), "w") as f:
        f.write(TOY)
    cell = harness.resolve(add_arch_cell(root, "toy"), root=root)
    assert cell.arch.CALLS == []
    line = harness.run(cell, 2**31 + 7, 1.0, False, jax.devices()[0],
                       time.monotonic(), checks_out=io.StringIO())
    assert line["correct"], line["checks"]
    assert {"program_config", "layout", "score"} <= set(cell.arch.CALLS)
    # mfu counts the toy's operations: a 3-token prompt prefilled in the
    # window, then 4 tokens decoded in it.
    ctx = harness.Context(cell=cell, requests=[rec(0.0, first=1.0, done=5.0)],
                          window=(0.0, 10.0), device_kind="TPU v5 lite")
    want = 100.0 * (1000 * 3 + 4 * 10**6) / 10.0 / 197e12
    got = harness.reader("mfu", os.path.join(root, "bench"))(ctx)
    assert abs(got - want) <= 1e-12 * want
    for name in SHARED:  # found with none of the shared files edited
        with open(os.path.join(BENCH, name)) as a, \
                open(os.path.join(root, "bench", name)) as b:
            assert a.read() == b.read(), name


def test_unknown_architecture_names_the_known_ones(tmp_path):
    root = tiny_root(str(tmp_path))
    name = add_arch_cell(root, "nope")
    with pytest.raises(KeyError, match=r"nope.*\['dense'\]"):
        harness.resolve(name, root=root)
