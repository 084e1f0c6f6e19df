"""Device-to-host bytes per token made: the ``bytes`` of the runtime's
``write_back`` spans that start in the window (every output of a package,
bucket padding included, copied to its host buffer) over the tokens made in
the window, in MB."""
from bench.span_args import mb_per_token


def read(ctx):
    return mb_per_token(ctx, "write_back")
