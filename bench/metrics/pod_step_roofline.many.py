"""ThreeSieves needed work / fused kernel device time, % of roofline (the
many-tenant cell: a grid of one cell per session, 4096 of them)."""
from bench import readings


def read(ctx):
    return readings.roofline(ctx)
