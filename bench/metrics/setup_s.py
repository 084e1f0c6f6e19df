"""Set-up time: process start to the window's start (weights drawn on the
device, every program loaded or compiled and run once, the traffic's ramp)."""


def read(ctx):
    return ctx.setup_s
