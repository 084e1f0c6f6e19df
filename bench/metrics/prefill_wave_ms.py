"""Median duration of the batcher's ``prefill_wave`` spans that start in
the window: one whole-prompt prefill Program, from the run's start to its
end (service time; the wait before the start is not in it)."""
from bench.window import span_durations
from bench.stats import quantile


def read(ctx):
    q = quantile(span_durations(ctx, "prefill_wave"), 0.5)
    return None if q is None else q * 1e3
