"""Median duration of the batcher's ``segment`` spans that start in the
window: one decode segment Program (seg_len steps), submit to completion,
write-back to host included."""
from bench.window import span_durations
from bench.stats import quantile


def read(ctx):
    q = quantile(span_durations(ctx, "segment"), 0.5)
    return None if q is None else q * 1e3
