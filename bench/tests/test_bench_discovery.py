"""A configuration, traffic mix or metric reader dropped in as a file is
found by its name, with no edit to the harness."""
import json
import os

from bench import harness
from bench.tests.util import tiny_root


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
