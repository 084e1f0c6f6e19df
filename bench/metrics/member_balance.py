"""Balance of the server's members over the profiled window: 100 x the mean
over the max of each chip's busy seconds (union of its device operations),
the serving analogue of the EngineCL paper's balance efficiency.  Nothing to
read with fewer than two chips."""


def read(ctx):
    if ctx.device is None:
        return None
    busy = list(ctx.device["busy_s_per_plane"].values())
    if len(busy) < 2 or max(busy) <= 0:
        return None
    return 100.0 * (sum(busy) / len(busy)) / max(busy)
