"""Host ms of SummarizerPod.serve per device batch (the many-tenant cell)."""
from bench import readings


def read(ctx):
    return readings.serve_ms_per_batch(ctx)
