"""host_route per device batch (closed-loop cells)."""
from bench import spans


def read(ctx):
    return spans.route_ms_per_batch(ctx)
