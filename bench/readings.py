"""Shared arithmetic of the per-layer readers in ``bench/metrics/``.

Each reader gets the run's context (``harness.run`` builds it): the
round boundaries with the host-clock spans the harness took around its
own calls, the producer's put calls, the reduced trace, the roofline
module of the configuration and the table of peaks.  A reader returns
None where it finds nothing to read, and the metric is left out.
"""
from __future__ import annotations

import sys

import numpy as np


def window_rounds(ctx) -> list:
    return ctx["bounds"][1:ctx["n_window"] + 1]


def put_us_per_item(ctx):
    """Host time inside ``TaggedBuffer.put`` per item put in the window."""
    calls = [c for c in ctx["producer"].calls
             if c[2] >= ctx["t0"] and c[3] <= ctx["t_last"]]
    items = sum(hi - lo for lo, hi, *_ in calls)
    if not items:
        return None
    return 1e6 * sum(t1 - t0 for _, _, t0, t1, _ in calls) / items


def serve_ms_per_batch(ctx):
    """Host time of ``SummarizerPod.serve`` (the pipeline's get, repack,
    route, device_put and dispatch, ending in block_until_ready) per
    device batch."""
    rounds = window_rounds(ctx)
    batches = sum(b.batches for b in rounds)
    if not batches:
        return None
    return 1e3 * sum(b.serve_s for b in rounds) / batches


def readout_ms(ctx):
    """Host time of one readout and its copy to the host."""
    rounds = window_rounds(ctx)
    return 1e3 * float(np.mean([b.readout_s for b in rounds])) \
        if rounds else None


def device_idle(ctx):
    """Share of the traced window in which no operation ran on the chip."""
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def roofline(ctx):
    """The pod step's needed work over its device time, as a share of
    the chip's roofline; None where the step's events are not in the
    trace."""
    tr = ctx["trace"]
    t = tr and tr["kernel_s"].get("roofline")
    if not t:
        return None
    peaks = ctx["peaks"]["devices"][ctx["device_kind"]]  # unknown: error
    rounds = window_rounds(ctx)
    plan_K = np.asarray([p[0] for p in ctx["plan_of"]])
    w = ctx["roofline"].work(ctx["config"], plan_K,
                             ctx["bounds"][0].counters,
                             rounds[-1].counters,
                             sum(b.batches for b in rounds))
    t_flops = w["flops"] / peaks["flops_per_s"]
    t_bytes = w["bytes"] / peaks["hbm_bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    print(f"roofline: {w['flops']:.6e} FLOPs, {w['bytes']:.6e} bytes, "
          f"device time {t:.6f} s, bound by {bound}", file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / t
