"""SieveStreaming++ needed work / ingest_routed device time, % of roofline."""
from bench import readings


def read(ctx):
    return readings.roofline(ctx)
