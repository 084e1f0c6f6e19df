"""Share of the window that the runtime's device workers spent in
``write_back`` spans: copying every output of a Program to its host buffer."""
from bench.window import span_time


def read(ctx):
    t0, t1 = ctx.window_perf
    if not ctx.spans:
        return None
    return 100.0 * span_time(ctx, "write_back") / (t1 - t0)
