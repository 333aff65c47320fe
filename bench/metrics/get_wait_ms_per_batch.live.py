"""The pipeline's wait in TaggedBuffer.get per batch (the open-loop cell)."""
from bench import spans


def read(ctx):
    return spans.get_wait_ms_per_batch(ctx)
