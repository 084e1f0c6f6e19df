"""The trace reduction, on a small trace recorded on the CPU (four runs of a
jitted matmul between the window's two anchor annotations) and on
hand-made intervals."""
import os

import pytest

from bench import trace_reduce as T
from bench.tests.util import DATA

TRACE = os.path.join(DATA, "cpu_trace")


def test_recorded_trace():
    r = T.reduce_dir(TRACE, {T.START: 0.0}, (), "cpu")
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    names = [n for n, _ in r["device_ops"]]
    assert any(n.startswith("dot") for n in names)
    assert len(r["device_ops"]) <= T.TOP and len(r["idle_gaps"]) <= T.TOP
    secs = [s for _, s in r["idle_gaps"]]
    assert secs == sorted(secs, reverse=True) and secs[0] > 0.01  # the sleeps


def test_a_missing_device_plane_is_an_error():
    pd = T.load(T.find_trace(TRACE))
    with pytest.raises(ValueError):
        T.reduce(pd, 0, 1e9)  # a CPU trace has no TPU plane


def test_union_gaps_and_naming():
    ops = [(0, 10, "a"), (5, 20, "b"), (30, 40, "a"), (100, 5000, "c")]
    busy = T.union(ops, 0, 4000)
    assert busy == [(0, 20), (30, 40), (100, 4000)]
    assert T.gaps(busy, 0, 4000) == []  # every stretch under a microsecond
    assert T.gaps([(0, 20), (3000, 4000)], 0, 6000) == [(20, 3000),
                                                        (4000, 6000)]
    spans = [(0, 10_000, "segment"), (500, 2_000, "write_back")]
    assert T.name_gap((20, 3000), spans) == "write_back"
    assert T.name_gap((4000, 6000), spans) == "segment"
    assert T.name_gap((20_000, 30_000), spans) == "no span"
    assert T.op_name("%fusion.12 = bf16[4] fusion(%x)") == "%fusion.12"


def test_host_spans_pair_begin_and_end():
    ev = [(0, 1.0, None, "B", "dep_wait", "g", None, None),
          (1, 1.5, 2.0, "X", "write_back", "g", None, None),
          (2, 3.0, None, "E", "dep_wait", "g", None, None)]
    got = T.host_spans(ev, lambda t: t * 10)
    assert sorted(got) == [(10.0, 30.0, "dep_wait"), (15.0, 20.0, "write_back")]
