"""90th percentile of the time per output token after the first,
(done - first token) / (tokens - 1).  Open loop: over every request sent in
the window, drained after it.  Closed loop: over every request completed in
the window.  A request that failed or never finished counts as missing: it
takes the time from its first token (or due time) to the end of the drain."""
from bench.stats import quantile


def read(ctx):
    t0, t1 = ctx.window
    vals = []
    for r in ctx.requests:
        if r.gen < 2:
            continue
        if ctx.loop == "open":
            if not r.in_window:
                continue
        elif not (r.status == "ok" and t0 <= r.done < t1):
            continue
        if r.status == "ok":
            vals.append((r.done - r.first) / (r.gen - 1))
        else:
            start = r.first if r.first is not None else r.scheduled
            vals.append((ctx.t_end - start) / (r.gen - 1))
    q = quantile(vals, 0.9)
    return None if q is None else q * 1e3
