"""Mean time a decode segment waited before it ran: the ``queued_s`` of the
batcher's ``segment`` spans that start in the window (submit to the run's
start: the device worker's queue and the previous segment).  The mean, not
the median: a median hides segments serialized behind another group."""
from bench.span_args import arg_values


def read(ctx):
    waits = arg_values(ctx, "segment", "queued_s")
    return 1e3 * sum(waits) / len(waits) if waits else None
