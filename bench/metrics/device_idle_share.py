"""Share of the profiled window in which no operation ran on the device:
1 - (union of the trace's device operations) / window."""


def read(ctx):
    if ctx.device is None:
        return None
    return 100.0 * ctx.device["idle_share"]
