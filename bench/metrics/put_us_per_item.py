"""Host us in TaggedBuffer.put per item (closed-loop cells)."""
from bench import readings


def read(ctx):
    return readings.put_us_per_item(ctx)
