"""Median duration of the batcher's ``segment`` spans that start in the
window: one decode segment Program (seg_len steps), from the run's start to
its end, so service time (the wait before the start is ``segment_wait_ms``).
The write-back that ends the run copies the tokens and positions to host;
the KV cache stays on the device."""
from bench.window import span_durations
from bench.stats import quantile


def read(ctx):
    q = quantile(span_durations(ctx, "segment"), 0.5)
    return None if q is None else q * 1e3
