"""The trace reduction, on a small trace recorded on the CPU (four runs of a
jitted matmul between the window's two anchor annotations), on one recorded
on a four-chip TPU host (``record_tpu_trace.py``: matmuls on two of the
chips), and on hand-made intervals."""
import os

import pytest

from bench import trace_reduce as T
from bench.tests.util import DATA

TRACE = os.path.join(DATA, "cpu_trace")
TPU2 = os.path.join(DATA, "tpu2_trace")


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


def two_plane_trace():
    """Two TPU planes between the window's anchors at 1 and 11 us: chip 0
    busy 2 + 1 us, chip 1 busy 6 us (two overlapping ops); a host plane
    holds the anchors."""
    def ev(mid, start_ns, dur_ns):
        return (f"events {{ metadata_id: {mid} offset_ps: {start_ns * 1000} "
                f"duration_ps: {dur_ns * 1000} }}")

    def plane(pid, name, line, events, names):
        meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}' for i, n in enumerate(names, 1))
        return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
                f'name: "{line}" timestamp_ns: 0 {" ".join(events)} }} '
                f'{meta} }}')

    text = " ".join([
        plane(1, "/device:TPU:0", T.OPS_LINE,
              [ev(1, 2000, 2000), ev(2, 6000, 1000)], ["%fusion.1", "%dot.2"]),
        plane(2, "/device:TPU:1", T.OPS_LINE,
              [ev(1, 1000, 4000), ev(2, 3000, 4000)],
              ["%fusion.1 = bf16[8] fusion(%a)", "%while.3"]),
        plane(3, "/host:CPU", "python",
              [ev(1, 1000, 1), ev(2, 11000, 1)], [T.START, T.END]),
    ])
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(text)


def test_two_planes_keep_each_planes_busy_seconds():
    pd = two_plane_trace()
    lo, hi = T.annotation(pd, T.START), T.annotation(pd, T.END)
    r = T.reduce(pd, lo, hi)
    assert r["busy_s_per_plane"] == pytest.approx(
        {"/device:TPU:0": 3e-6, "/device:TPU:1": 6e-6}, abs=1e-15)
    assert r["window_s"] == pytest.approx(10e-6, abs=1e-15)
    assert r["busy_s"] == pytest.approx(4.5e-6, abs=1e-15)  # the mean
    assert r["idle_share"] == 1.0 - r["busy_s"] / r["window_s"]
    assert r["device_ops"] == [["%fusion.1", pytest.approx(6e-6)],
                               ["%while.3", pytest.approx(4e-6)],
                               ["%dot.2", pytest.approx(1e-6)]]
    # chip 0 waits 1, 2 and 4 us, chip 1 the last 4 us
    assert [s for _, s in r["idle_gaps"]] == pytest.approx(
        [4e-6, 4e-6, 2e-6, 1e-6])


def test_one_plane_reads_as_before():
    """The recorded CPU trace's numbers as the reduction gave them before
    it kept each plane's busy seconds."""
    r = T.reduce_dir(TRACE, {T.START: 0.0}, (), "cpu")
    assert r["busy_s_per_plane"] == {"/host:CPU": r["busy_s"]}
    assert (r["busy_s"], r["window_s"], r["idle_share"]) == (
        0.006994716, 0.088608666, 0.9210605879113449)
    assert r["device_ops"][0] == ["dot_general.1", 0.006058253]
    assert [s for _, s in r["idle_gaps"][:4]] == [
        0.020478298, 0.020396488, 0.020368711, 0.020277543]


def test_two_planes_and_an_idle_chip():
    pd = two_plane_trace()
    lo, hi = T.annotation(pd, T.START), T.annotation(pd, T.END)
    names = ["/device:TPU:0", "/device:TPU:1", "/device:TPU:2"]
    r = T.reduce(pd, lo, hi, names=names)
    assert r["busy_s_per_plane"] == pytest.approx(
        dict(zip(names, (3e-6, 6e-6, 0.0))), abs=1e-15)
    assert r["busy_s"] == pytest.approx(3e-6, abs=1e-15)  # the mean of three
    assert r["idle_gaps"][0][1] == pytest.approx(10e-6)  # chip 2: the window
    with pytest.raises(ValueError):
        T.reduce(pd, lo, hi, names=["/device:TPU:5"])


def test_recorded_two_chip_trace():
    """Chip 0 ran 40 matmuls and chip 1 20 in the recorded window; chips 2
    and 3 ran nothing and have no device plane.  The numbers are those the
    recorder printed on the chip."""
    r = T.reduce_dir(TPU2, {T.START: 0.0}, (), "tpu")
    assert r["busy_s_per_plane"] == {"/device:TPU:0": 0.030091992,
                                     "/device:TPU:1": 0.015043969}
    assert (r["busy_s"], r["window_s"], r["idle_share"]) == (
        0.0225679805, 0.201127084, 0.8877924342601218)
    assert r["device_ops"][0] == ["%convolution_multiply_fusion", 0.042449602]
    four = T.reduce_dir(TPU2, {T.START: 0.0}, (), "tpu", [0, 1, 2, 3])
    assert four["busy_s_per_plane"]["/device:TPU:2"] == 0.0
    assert four["busy_s"] == pytest.approx(r["busy_s"] / 2)
    assert four["idle_share"] == 1.0 - four["busy_s"] / four["window_s"]
    assert [s for _, s in four["idle_gaps"][:2]] == [r["window_s"]] * 2
