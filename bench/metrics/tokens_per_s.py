"""Output tokens generated inside the window over the window's length.

A request's first token counts where it came (the end of its prefill); its
other tokens are spread evenly between its first token and its completion,
and the part of that stretch inside the window counts.  So every token made
in the window counts once, whether its request finished in the window or
after it."""
from bench.window import tokens_in_window


def read(ctx):
    t0, t1 = ctx.window
    return sum(n for _r, n, _j0, _j1 in tokens_in_window(ctx)) / (t1 - t0)
