"""Share of the decode segments that ran ahead: 100 × the ``segment`` spans
starting in the window whose ``ahead`` arg is 1 — the runtime dispatched the
segment before the one it chains on had completed, so the device had it
queued — over the window's ``segment`` spans that carry the arg.  None for a
program that puts no ``ahead`` on its spans."""
from bench.span_args import arg_values


def read(ctx):
    ahead = arg_values(ctx, "segment", "ahead")
    return 100.0 * sum(1 for a in ahead if a) / len(ahead) if ahead else None
