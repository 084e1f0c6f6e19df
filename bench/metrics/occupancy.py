"""Mean share of the decode slots that held a request, over the segments
harvested in the window (``stats()`` segments and occupancy_mean)."""


def read(ctx):
    s0, s1 = ctx.stats0, ctx.stats1
    segs = s1["segments"] - s0["segments"]
    if segs <= 0:
        return None
    occ = (s1["occupancy_mean"] * s1["segments"]
           - s0["occupancy_mean"] * s0["segments"])
    return 100.0 * occ / segs / ctx.slots
