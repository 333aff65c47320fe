"""The pipeline's wait in TaggedBuffer.get per batch (the many-tenant cell)."""
from bench import spans


def read(ctx):
    return spans.get_wait_ms_per_batch(ctx)
