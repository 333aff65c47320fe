"""Host us in TaggedBuffer.put per item (the many-tenant cell)."""
from bench import readings


def read(ctx):
    return readings.put_us_per_item(ctx)
