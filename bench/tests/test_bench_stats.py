"""Tails over every request, failed ones counted as missing, and the other
readers' window arithmetic, on hand-made records."""
import pytest

from bench import harness
from bench.stats import quantile
from bench.tests.util import BENCH


def rec(scheduled, first=None, done=None, gen=5, status="ok", in_window=True):
    s = harness.Sent(prompt=[1, 2, 3], gen=gen, bucket=16,
                     scheduled=scheduled, sent=scheduled, in_window=in_window)
    s.first, s.done, s.status = first, done, status
    return s


def ctx(requests, **kw):
    base = dict(requests=requests, window=(0.0, 10.0),
                window_perf=(100.0, 110.0), t_end=20.0, loop="open")
    base.update(kw)
    return harness.Context(**base)


def read(name, c):
    return harness.reader(name, BENCH)(c)


def test_quantile_interpolates_like_numpy():
    assert quantile([1.0, 2.0, 3.0, 4.0], 0.5) == 2.5
    assert quantile(list(range(11)), 0.9) == 9.0
    assert quantile([], 0.9) is None


def test_tpot_open_loop_counts_missing_and_closed_loop_completed():
    rs = [rec(0.0, first=1.0, done=1.0 + 0.4 * (i + 1), gen=5)
          for i in range(10)]  # 0.1 .. 1.0 s per token
    assert read("tpot_p90_ms", ctx(rs)) == pytest.approx(910.0)
    rs2 = rs + [rec(0.0, first=1.0, status="failed", gen=5)] * 2
    assert read("tpot_p90_ms", ctx(rs2)) > 1000.0
    # Closed loop: only requests completed inside the window count.
    late = rec(0.0, first=1.0, done=50.0, gen=5, in_window=False)
    assert read("tpot_p90_ms", ctx(rs + [late], loop="closed")) == \
        pytest.approx(910.0)


def test_tokens_in_window_spreads_each_request():
    # 11 tokens: first at t=5, the other 10 evenly until t=15; the window
    # [0, 10) holds the first and half of the rest.
    r = rec(0.0, first=5.0, done=15.0, gen=11)
    assert read("tokens_per_s", ctx([r])) == pytest.approx(6 / 10)
    # A request wholly before the window adds nothing.
    old = rec(-9.0, first=-8.0, done=-1.0, gen=11, in_window=False)
    assert read("tokens_per_s", ctx([r, old])) == pytest.approx(6 / 10)


def test_span_readers():
    ev = [(0, 101.0, 101.5, "X", "segment", "batcher", None, None),
          (1, 102.0, 103.0, "X", "segment", "batcher", None, None),
          (2, 99.0, 100.5, "X", "write_back", "group/x", None, None),
          (3, 108.0, 112.0, "X", "write_back", "group/x", None, None),
          (4, 95.0, 96.0, "X", "segment", "batcher", None, None)]
    c = ctx([], spans=ev)
    assert read("segment_ms", c) == pytest.approx(750.0)
    assert read("write_back_share", c) == pytest.approx(25.0)
    assert read("prefill_wave_ms", c) is None


def test_occupancy_and_device_idle():
    c = ctx([], stats0={"segments": 10, "occupancy_mean": 2.0},
            stats1={"segments": 20, "occupancy_mean": 3.0}, slots=4)
    assert read("occupancy", c) == pytest.approx(100.0)  # (60-20)/10/4
    assert read("device_idle_share", c) is None
    c.device = {"idle_share": 0.875}
    assert read("device_idle_share", c) == pytest.approx(87.5)


def test_member_balance():
    c = ctx([])
    assert read("member_balance", c) is None  # no trace
    c.device = {"busy_s_per_plane": {"/device:TPU:0": 2.0}}
    assert read("member_balance", c) is None  # one chip: nothing to balance
    c.device = {"busy_s_per_plane": {"/device:TPU:0": 1.5,
                                     "/device:TPU:1": 1.5}}
    assert read("member_balance", c) == pytest.approx(100.0)
    c.device = {"busy_s_per_plane": {f"/device:TPU:{i}": b
                                     for i, b in enumerate((1.0, 2.0, 3.0,
                                                            2.0))}}
    assert read("member_balance", c) == pytest.approx(100.0 * 2.0 / 3.0)
