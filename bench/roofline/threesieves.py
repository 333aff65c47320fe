"""Work a ThreeSieves pod step needs, whatever implements it.

Each routed item is priced once against the summary it meets: a kernel
row against the n summary rows (2 n d FLOPs) and one triangular matvec
(2 n^2).  Each accept appends once: the same row and matvec, plus the
new inverse-factor row (another 2 n^2).  Bytes: each item read once,
and each session's unpadded state (K d + 2 K^2 floats for its plan's K)
read and written once per device step.  n comes from the counters read
at the window's edges (their mean), never from the kernel's padding,
grid or number of gain passes.
"""
from __future__ import annotations

import numpy as np

# device events whose time this work is divided by (a substring of the
# trace event's name); see bench/trace.py
EVENTS = ("pod_step_pallas",)


def counters(state):
    """Per-session counters the count needs (traced; jitted by the caller)."""
    return {"items": state.items, "accepts": state.accepts,
            "n": state.algo.ld.n[:, None],
            "alive": state.active[:, None]}


def work(cfg: dict, plan_K: np.ndarray, start: dict, end: dict,
         steps: int) -> dict:
    """FLOPs and bytes between two counter readings over ``steps`` device
    steps; ``plan_K`` (S,) is each session's budget."""
    d, item = int(cfg["d"]), 4
    items = (end["items"] - start["items"]).astype(np.float64)
    accepts = (end["accepts"] - start["accepts"]).astype(np.float64)
    w = (start["alive"].astype(np.float64) + end["alive"]) / 2.0  # (S, R)
    n = (start["n"].astype(np.float64) + end["n"]) / 2.0  # (S, R)
    price = (w * (2 * n * d + 2 * n * n)).sum(axis=1)  # per item
    live = np.maximum(w.sum(axis=1), 1e-9)
    n_acc = (w * n).sum(axis=1) / live
    flops = float((items * price).sum()
                  + (accepts * (2 * n_acc * d + 4 * n_acc ** 2)).sum())
    K = plan_K.astype(np.float64)[:, None]
    state = (w * (K * d + 2 * K * K)).sum()
    nbytes = float(item * (items.sum() * d + 2 * steps * state))
    return {"flops": flops, "bytes": nbytes}
