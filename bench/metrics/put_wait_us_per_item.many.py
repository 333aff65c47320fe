"""Producers' wait in TaggedBuffer.put per item (the many-tenant cell)."""
from bench import spans


def read(ctx):
    return spans.put_wait_us_per_item(ctx)
