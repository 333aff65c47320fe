"""TaggedBuffer.get's own work per device batch (the many-tenant cell)."""
from bench import spans


def read(ctx):
    return spans.get_ms_per_batch(ctx)
