"""host_route per device batch (the open-loop cell)."""
from bench import spans


def read(ctx):
    return spans.route_ms_per_batch(ctx)
