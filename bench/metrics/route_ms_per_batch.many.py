"""host_route per device batch (the many-tenant cell)."""
from bench import spans


def read(ctx):
    return spans.route_ms_per_batch(ctx)
