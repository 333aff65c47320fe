"""The program's own spans, read back for the per-layer metrics of the
served pipeline and the front door.

``IngestPipeline.run`` leaves one ``ingest_run`` event per call in the
program's span recorder (``repro.obs``), with the seconds of each of its
stages (``<stage>_s``: ``ingest_get``, ``ingest_route``,
``ingest_device_put``, ..., and the buffer's ``buffer_get_wait_lock`` /
``buffer_get_wait_items`` inside ``ingest_get``) and the producers' wait
in the buffer since the previous run (``buffer_put_wait_s``, over
``buffer_put_items`` admitted).  The
harness makes one pipeline run per serve round, so the last
``len(bounds)`` events of the cell's pod are its rounds in order: the
warm-up, the window's rounds, the drain.  A reader returns None where
the program records no such event, or where the events do not line up
with the rounds.
"""
from __future__ import annotations

GET_WAITS = ("buffer_get_wait_lock", "buffer_get_wait_items")


def window_runs(ctx):
    """The ``ingest_run`` events of the window's rounds, or None."""
    from repro import obs

    pod = str(ctx["cell"].name)
    runs = [e for e in obs.get_recorder().find("ingest_run")
            if e["attrs"].get("pod") == pod]
    bounds = ctx["bounds"]
    if len(runs) < len(bounds):
        return None
    window = slice(1, ctx["n_window"] + 1)
    runs = runs[-len(bounds):][window]
    if [e["attrs"].get("batches") for e in runs] != \
            [b.batches for b in bounds[window]]:
        return None
    return runs


def stage_ms_per_batch(ctx, stages, less=()):
    """Seconds of ``stages`` less those of ``less`` (stages inside them),
    summed over the window's runs, in ms per device batch."""
    runs = window_runs(ctx)
    if not runs or not all("ingest_get_s" in e["attrs"] for e in runs):
        return None
    batches = sum(e["attrs"]["batches"] for e in runs)
    if not batches:
        return None
    total = sum(e["attrs"].get(f"{s}_s", 0.0) for e in runs for s in stages)
    total -= sum(e["attrs"].get(f"{s}_s", 0.0) for e in runs for s in less)
    return 1e3 * total / batches


def get_wait_ms_per_batch(ctx):
    """Time the pipeline waited in ``TaggedBuffer.get``, for the lock or
    for a full batch."""
    return stage_ms_per_batch(ctx, GET_WAITS)


def get_ms_per_batch(ctx):
    """``TaggedBuffer.get``'s own work: dequeue, stack, pad."""
    return stage_ms_per_batch(ctx, ("ingest_get",), less=GET_WAITS)


def route_ms_per_batch(ctx):
    return stage_ms_per_batch(ctx, ("ingest_route",))


def device_put_ms_per_batch(ctx):
    return stage_ms_per_batch(ctx, ("ingest_device_put",))


def put_wait_us_per_item(ctx):
    """Time producers waited in ``TaggedBuffer.put``, for the lock or for
    room, per item admitted.  The window's first run is left out: a put
    adds its wait when it returns, so that run's share holds the time a
    producer was blocked before the window opened (the warm-up round's
    drift check and readout, the profiler's start)."""
    runs = (window_runs(ctx) or [])[1:]
    if not runs or not all("buffer_put_items" in e["attrs"] for e in runs):
        return None
    items = sum(e["attrs"]["buffer_put_items"] for e in runs)
    if not items:
        return None
    return 1e6 * sum(e["attrs"]["buffer_put_wait_s"] for e in runs) / items
