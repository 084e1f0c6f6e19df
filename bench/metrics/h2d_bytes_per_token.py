"""Host-to-device bytes per token made: the ``bytes`` of the runtime's
``upload`` spans that start in the window (kernel inputs put on the device,
bucket padding included; inputs served from the transfer cache are not
counted) over the tokens made in the window, in MB."""
from bench.span_args import mb_per_token


def read(ctx):
    return mb_per_token(ctx, "upload")
