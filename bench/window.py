"""Window arithmetic the metric readers share."""
from __future__ import annotations


def tokens_in_window(ctx):
    """Per request that made tokens in the window: (request, tokens made in
    the window, j0, j1), where tokens j0..j1 of the ones after the first
    fell in it (spread evenly between first token and completion); the
    first token counts if it came in the window."""
    t0, t1 = ctx.window
    out = []
    for r in ctx.requests:
        if r.first is None:
            continue
        n = 1.0 if t0 <= r.first < t1 else 0.0
        j0 = j1 = 0.0
        if r.gen > 1 and r.done is not None and r.done > r.first:
            span = r.done - r.first
            j0 = (r.gen - 1) * min(max(t0 - r.first, 0.0), span) / span
            j1 = (r.gen - 1) * min(max(t1 - r.first, 0.0), span) / span
            n += j1 - j0
        if n > 0:
            out.append((r, n, j0, j1))
    return out


def span_durations(ctx, name: str):
    """Durations (s) of the complete spans ``name`` starting in the window."""
    t0, t1 = ctx.window_perf
    return [e[2] - e[1] for e in ctx.spans
            if e[3] == "X" and e[4] == name and t0 <= e[1] < t1]


def span_time(ctx, name: str) -> float:
    """Seconds of the window covered by complete spans ``name``."""
    t0, t1 = ctx.window_perf
    return sum(max(0.0, min(e[2], t1) - max(e[1], t0)) for e in ctx.spans
               if e[3] == "X" and e[4] == name)
