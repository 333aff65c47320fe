"""host_route's slot lookup per device batch: each item's id to its slot
by a binary search of the live ids, and its FIFO position there.

None where the program records no ``ingest_slot_lookup`` stage (a program
whose route is one stage): a missing stage is not a zero."""
from bench import spans


def read(ctx):
    runs = spans.window_runs(ctx)
    if not runs or not all("ingest_slot_lookup_s" in e["attrs"] for e in runs):
        return None
    return spans.stage_ms_per_batch(ctx, ("ingest_slot_lookup",))
