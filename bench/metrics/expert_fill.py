"""Share of the held experts' matmul rows that carry a routed token:
100 x the (token, held expert) pairs routed over the rows the expert
matmuls were laid out for (``expert_routed`` and ``expert_rows`` on the
``segment`` spans that start in the window, summed over their steps and
layers).  A program whose segments count no expert rows gives nothing."""
from bench.span_args import arg_values


def read(ctx):
    rows = sum(arg_values(ctx, "segment", "expert_rows"))
    if rows <= 0:
        return None
    return 100.0 * sum(arg_values(ctx, "segment", "expert_routed")) / rows
