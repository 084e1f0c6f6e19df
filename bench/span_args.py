"""The args the program puts on its spans, as the tracing metrics read them."""
from __future__ import annotations

from bench.window import tokens_in_window


def arg_values(ctx, name: str, key: str):
    """Arg ``key`` of each complete span ``name`` that starts in the window
    and carries it (a program that lacks the arg gives an empty list)."""
    t0, t1 = ctx.window_perf
    return [e[7][key] for e in ctx.spans
            if e[3] == "X" and e[4] == name and t0 <= e[1] < t1
            and e[7] and key in e[7]]


def mb_per_token(ctx, name: str):
    """The ``bytes`` of the spans ``name`` that start in the window, in MB,
    over the tokens made in the window; None without such spans or
    tokens."""
    nbytes = arg_values(ctx, name, "bytes")
    tokens = sum(n for _r, n, _j0, _j1 in tokens_in_window(ctx))
    if not nbytes or tokens <= 0:
        return None
    return sum(nbytes) / tokens / 1e6
