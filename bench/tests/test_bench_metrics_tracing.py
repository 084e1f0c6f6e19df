"""The readers of the program's transfer and batcher spans, on hand-made
spans and requests: the value computed by hand, and None where the program
put no such span (or no such arg) in the window."""
import pytest

from bench.tests.test_bench_stats import ctx, read, rec

NAMES = ("d2h_bytes_per_token", "h2d_bytes_per_token", "segment_wait_ms",
         "merge_ms")


def X(seq, t0, t1, name, track="group/x", **args):
    return (seq, t0, t1, "X", name, track, None, args or None)


# 11 tokens: the first at t=5, the other 10 evenly until t=15; the window
# [0, 10) (perf_counter [100, 110)) holds 6 of them.
REQ = rec(0.0, first=5.0, done=15.0, gen=11)
SPANS = [
    X(0, 101.0, 101.5, "write_back", bytes=3_000_000),
    X(1, 105.0, 106.0, "write_back", bytes=9_000_000),
    X(2, 99.0, 100.5, "write_back", bytes=10**9),  # starts before
    X(3, 101.0, 101.1, "upload", bytes=0, resident_bytes=10**8),
    X(4, 107.0, 107.2, "upload", bytes=6_000_000, resident_bytes=0),
    X(5, 111.0, 111.2, "upload", bytes=10**9, resident_bytes=0),  # after
    X(6, 102.0, 103.0, "segment", "batcher", queued_s=0.01),
    X(7, 104.0, 105.0, "segment", "batcher", queued_s=0.03),
    X(8, 98.0, 99.0, "segment", "batcher", queued_s=5.0),  # before
    X(9, 103.0, 103.01, "merge", "batcher"),
    X(10, 104.0, 104.02, "merge", "batcher"),
    X(11, 106.0, 106.05, "merge", "batcher"),
    X(12, 112.0, 113.0, "merge", "batcher"),  # after
]


def test_hand_computed_values():
    c = ctx([REQ], spans=SPANS)
    assert read("d2h_bytes_per_token", c) == pytest.approx(12 / 6)
    assert read("h2d_bytes_per_token", c) == pytest.approx(6 / 6)
    assert read("segment_wait_ms", c) == pytest.approx(20.0)
    assert read("merge_ms", c) == pytest.approx(20.0)


def test_no_upload_between_joins_reads_zero():
    c = ctx([REQ], spans=[X(0, 101.0, 101.1, "upload", bytes=0,
                            resident_bytes=10**8)])
    assert read("h2d_bytes_per_token", c) == 0.0


@pytest.mark.parametrize("name", NAMES)
def test_none_without_the_spans(name):
    assert read(name, ctx([REQ], spans=[])) is None
    # The spans of a program that puts no bytes or queued_s on them.
    bare = [(i, 101.0 + i, 101.5 + i, "X", n, "t", None, None)
            for i, n in enumerate(("write_back", "upload", "segment"))]
    if name != "merge_ms":
        assert read(name, ctx([REQ], spans=bare)) is None


@pytest.mark.parametrize("name", NAMES[:2])
def test_bytes_per_token_none_without_tokens(name):
    assert read(name, ctx([], spans=SPANS)) is None
