"""Roofline share of the MLA decode kernel: the latent rows its segments
read at real lengths (``latent_bytes`` on the ``segment`` spans that start
in the window), at the chip's HBM bandwidth, over the kernel's time.

The kernel's time is the sum of its entries (``%mla_decode...``) in the
profiled window's ``device_ops``, scaled from the profiled seconds to the
window's (a steady backlog: the profiled seconds stand for the window).
The kernel is memory-bound (about 121 operations per byte read against the
chip's 240), so bytes set its floor.  Nothing to read without the kernel
among the breakdown's operations or without the spans' bytes."""
from bench import flops
from bench.span_args import arg_values

KERNEL = "%mla_decode"


def read(ctx):
    if ctx.device is None:
        return None
    kernel_s = sum(t for name, t in ctx.device["device_ops"]
                   if name.startswith(KERNEL))
    nbytes = sum(arg_values(ctx, "segment", "latent_bytes"))
    if kernel_s <= 0 or nbytes <= 0:
        return None
    t0, t1 = ctx.window_perf
    kernel_s *= (t1 - t0) / ctx.device["window_s"]
    bw = flops.peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * nbytes / bw / kernel_s
