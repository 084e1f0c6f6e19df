"""Median duration of the batcher's ``merge`` spans that start in the
window: boarding one finished prefill wave into the host mirrors and
invalidating the cache, under the server's lock."""
from bench.window import span_durations
from bench.stats import quantile


def read(ctx):
    q = quantile(span_durations(ctx, "merge"), 0.5)
    return None if q is None else q * 1e3
