"""ThreeSieves needed work / fused kernel device time, % of roofline."""
from bench import readings


def read(ctx):
    return readings.roofline(ctx)
