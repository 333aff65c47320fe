"""The comparison that decides ``correct``.

Numbers compared, each against the limit in the configuration's file
(``limits``); all are taken after the window has closed, from what the
timed path produced:

* ``routing_gap``: the largest difference, over all sessions, between the
  items the producer sent a session and the items the pod routed to it
  (limit 0: routing is exact);
* ``drops``: items lost anywhere on the path: overflow and unknown-session
  drops in the pod's ledgers, clipped, shed or throttled items in the
  buffer (limit 0);
* ``rows_missing``: for sessions sampled from the seed (every plan among
  them), the reference's plain per-session run over exactly the items the
  session received since its last re-arm; the rows by which the summary
  the pod read back and the reference's summary differ, as sets, summed
  over the sample (limit 0: the decisions are exact);
* ``fval_gap``: on the same sessions, the gap between the f(S) the pod's
  readout reports and the reference's, relative to the latter (or to
  m = f({e}) where that is larger); the median over the sample.  A
  session's gap is a few float32 ulps of reordered sums in a sound run
  and grows with every session's rounding under a lower matmul
  precision; the median keeps the one and sees the other (PERF.md).
"""
from __future__ import annotations

import math

import numpy as np

from . import reference, traffic

CHECKS = ("routing_gap", "drops", "rows_missing", "fval_gap")


def since_rearm(stream_tags, bounds, s: int) -> np.ndarray:
    """Stream indices of session s's items since its last re-arm."""
    resets = np.asarray([b.resets[s] for b in bounds])
    items = np.asarray([b.items[s] for b in bounds])
    start = 0
    if resets[-1] > 0:
        k = int(np.argmax(resets == resets[-1]))  # the re-arming round
        start = int(items[k])
    return np.flatnonzero(stream_tags == s)[start:]


def compare(cfg: dict, got, want) -> dict:
    """Numbers for summaries ``got`` (the system's: feats, n, fval) against
    ``want`` (the reference's), sessions on the leading axis."""
    m = 0.5 * math.log1p(float(cfg["a"]))
    f_got = np.asarray(got[2], np.float64)
    f_want = np.asarray(want[2], np.float64)
    gap = np.abs(f_got - f_want) / np.maximum(np.abs(f_want), m)
    gap = np.where(np.isfinite(gap), gap, math.inf)
    missing = []
    for i in range(len(got[1])):
        mine = {r.tobytes() for r in np.asarray(got[0][i])[:int(got[1][i])]}
        theirs = {r.tobytes() for r in np.asarray(want[0][i])
                  [:int(want[1][i])]}
        missing.append(len(theirs - mine) + len(mine - theirs))
    return {"rows_missing": int(sum(missing)),
            "fval_gap": float(np.median(gap)),
            "fval_gap_max": float(gap.max()),
            "per_session": {"gap": gap.tolist(), "missing": missing,
                            "fval": f_want.tolist()}}


def decide(cell, *, seed, tags, X, stream_tags, bounds, plan_of, final_ro,
           buffer_losses, control=()) -> dict:
    cfg = cell.config
    S = int(cfg["sessions"])
    sent = np.bincount(stream_tags, minlength=S)
    routed = np.asarray(bounds[-1].items)
    values = {
        "routing_gap": int(np.abs(routed - sent).max()),
        "drops": int(np.asarray(final_ro.drops["overflow"]).sum())
        + int(np.asarray(final_ro.drops["unknown"])) + int(buffer_losses),
    }
    sample = traffic.sample_sessions(seed, plan_of,
                                     int(cfg["sample_sessions"]))
    P = len(tags)
    rows = [since_rearm(stream_tags, bounds, s) % P for s in sample]
    plans = [plan_of[s] for s in sample]
    want = reference.replay(cfg["algorithm"], cfg, X, rows, plans,
                            mode=cfg["matmul_precision"])
    got = (np.asarray(final_ro.feats)[sample], np.asarray(final_ro.n)[sample],
           np.asarray(final_ro.fval)[sample])
    numbers = compare(cfg, got, want)
    values.update({k: numbers[k] for k in CHECKS if k in numbers})
    limits = cfg["limits"]
    checks = {k: {"value": values[k], "limit": limits[k]} for k in CHECKS}
    out = {
        "correct": all(values[k] <= limits[k] for k in CHECKS),
        "checks": checks,
        "sample": {"sessions": sample,
                   "items": [len(r) for r in rows],
                   "fval_gap_max": numbers["fval_gap_max"],
                   "per_session": numbers["per_session"]},
    }
    if control:  # the reference at a lower precision in the pod's place
        out["control"] = {
            mode: compare(cfg, reference.replay(cfg["algorithm"], cfg, X, rows,
                                                plans, mode=mode), want)
            for mode in control}
    return out
