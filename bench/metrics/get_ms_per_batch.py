"""TaggedBuffer.get's own work per device batch (closed-loop cells)."""
from bench import spans


def read(ctx):
    return spans.get_ms_per_batch(ctx)
