"""host_route's scatter per device batch: the fresh (S, C, d) zeros, the
items written into them, and the counts.

None where the program records no ``ingest_scatter`` stage (a program
whose route is one stage): a missing stage is not a zero."""
from bench import spans


def read(ctx):
    runs = spans.window_runs(ctx)
    if not runs or not all("ingest_scatter_s" in e["attrs"] for e in runs):
        return None
    return spans.stage_ms_per_batch(ctx, ("ingest_scatter",))
