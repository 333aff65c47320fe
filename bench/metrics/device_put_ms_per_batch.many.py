"""The device_put of a routed batch, per batch (the many-tenant cell)."""
from bench import spans


def read(ctx):
    return spans.device_put_ms_per_batch(ctx)
