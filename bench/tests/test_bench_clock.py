"""One clock for the program's spans and the profiler trace: a span the
Tracer measures in place shows in a live CPU profiler trace as a
``TraceAnnotation``, and the harness's anchor mapping of the Tracer's own
record of it (``trace_reduce.host_spans``) lands within 1 ms of it."""
import shutil
import time

from bench import harness
from bench import trace_reduce as T

NAME = "clock_probe"


def host_event(pd, name):
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == name:
                    return e.start_ns, e.start_ns + e.duration_ns
    return None


def test_annotation_and_anchor_mapping_agree_within_1ms():
    from repro.core.trace import Tracer

    tr = Tracer(enabled=True)
    prof = harness.Profiler(time.monotonic(), 0.5, "cpu")
    try:
        deadline = time.monotonic() + 60
        while T.START not in prof.anchors and prof.error is None:
            assert time.monotonic() < deadline, "the profiler never started"
            time.sleep(0.001)
        with tr.span(NAME, track="test"):
            time.sleep(0.05)
        prof.thread.join()
        assert prof.error is None, prof.error
        pd = T.load(T.find_trace(prof.dir))
        lo = T.annotation(pd, T.START)

        def to_ns(t):
            return lo + (t - prof.anchors[T.START]) * 1e9

        (s, e, _), = [x for x in T.host_spans(tr.events(), to_ns)
                      if x[2] == NAME]
        got = host_event(pd, NAME)
        assert got is not None, "no TraceAnnotation in the trace"
        assert abs(s - got[0]) < 1e6 and abs(e - got[1]) < 1e6, (
            (s - got[0]) / 1e6, (e - got[1]) / 1e6)
    finally:
        prof.thread.join()
        shutil.rmtree(prof.dir, ignore_errors=True)
