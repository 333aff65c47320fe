"""Reduce a profiler trace to device busy time, kernel times and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain structure (``read`` takes it back from gzipped JSON, which is how
a small recorded trace sits beside the tests):

    {"device": {plane: {line: [[name, start_ns, end_ns], ...]}},
     "host": {thread: [[name, start_ns, end_ns], ...]}}

``reduce`` takes the window from the harness's own host annotations
(``bench.*``), the first start to the last end, and gives

* ``busy_s``: the union of the intervals in which an operation ran on a
  device, clipped to the window, averaged over the devices;
* ``kernel_s``: the summed device time of the events whose names
  contain each of the given substrings (ops and programs alike);
* ``device_ops``: the ten operations that took most device time, by the
  name of their HLO instruction;
* ``idle_gaps``: device idle time summed by what the host was doing at
  each gap's middle: the ``bench.*`` annotations open then, and the
  innermost event on the serving thread.
"""
from __future__ import annotations

import glob
import gzip
import json
import os

import numpy as np

OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
SERVE = "bench.serve_round"


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"device": {}, "host": {}}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                evs = [[e.name, int(e.start_ns), int(e.start_ns
                                                      + e.duration_ns)]
                       for e in line.events]
                if evs:
                    lines[line.name] = evs
            if lines:
                out["device"][plane.name] = lines
        elif plane.name.startswith("/host:CPU"):
            for i, line in enumerate(plane.lines):
                evs = [[e.name, int(e.start_ns), int(e.start_ns
                                                     + e.duration_ns)]
                       for e in line.events]
                if evs:
                    out["host"][f"{line.name}#{i}"] = evs
    return out


def read(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(iv):
    """Merge [start, end] intervals; sorted, disjoint."""
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(iv, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in iv if e > lo and s < hi]


def _lines(planes: dict, names) -> list:
    return [ev for lines in planes.values() for ln, evs in lines.items()
            if ln in names for ev in evs]


def window(tr: dict):
    """(start_ns, end_ns) of the harness's annotations in the trace."""
    evs = [ev for evs in tr["host"].values() for ev in evs
           if ev[0].startswith("bench.") and ev[0] != "bench.put"]
    if not evs:
        raise ValueError("the trace holds no bench.* annotation")
    return min(e[1] for e in evs), max(e[2] for e in evs)


def reduce(tr: dict, kernels: dict | None = None, top: int = 10) -> dict:
    lo, hi = window(tr)
    planes = tr["device"]
    busy_by_dev, gaps = [], []
    for lines in planes.values():
        ops = [ev for ln, evs in lines.items() if ln in OPS_LINES
               for ev in evs]
        iv = _clip(_union([[s, e] for _, s, e in ops]), lo, hi)
        busy_by_dev.append(sum(e - s for s, e in iv))
        edges = [lo] + [x for s, e in iv for x in (s, e)] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = max(len(planes), 1)
    ops = _lines(planes, OPS_LINES)
    by_op = {}
    for name, s, e in ops:
        name = name.split(" = ")[0]  # "%fusion.3 = f32[...]..." -> "%fusion.3"
        by_op[name] = by_op.get(name, 0) + (min(e, hi) - max(s, lo)) \
            * (e > lo and s < hi)
    kernel_s = {}
    every = ops + _lines(planes, MODULE_LINES)
    for key, subs in (kernels or {}).items():
        hits = [(s, e) for name, s, e in every
                if any(sub in name for sub in subs)]
        # an op inside a matched program is not counted twice
        kernel_s[key] = (sum(e - s for s, e in
                             _clip(_union([list(h) for h in hits]), lo, hi))
                         / n_dev / 1e9 if hits else None)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_by_dev) / n_dev / 1e9,
        "devices": len(planes),
        "kernel_s": kernel_s,
        "device_ops": [[k, v / n_dev / 1e9] for k, v in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:top] if v > 0],
        "idle_gaps": _label_gaps(tr["host"], gaps, n_dev, top),
    }


def _covering(evs, arr, mid):
    """Events of ``evs`` (with ``arr`` = their (start, end) array) open at
    ``mid``."""
    if not len(evs):
        return []
    hit = np.flatnonzero((arr[:, 0] <= mid) & (arr[:, 1] > mid))
    return [evs[i] for i in hit]


def _label_gaps(host: dict, gaps: list, n_dev: int, top: int,
                labelled: int = 256) -> list:
    serving = [evs for evs in host.values()
               if any(ev[0] == SERVE for ev in evs)]
    serving = [ev for ev in (serving[0] if serving else [])
               if not ev[0].startswith("bench.")]
    bench = [ev for evs in host.values() for ev in evs
             if ev[0].startswith("bench.")]
    s_arr = np.asarray([ev[1:] for ev in serving], np.float64).reshape(-1, 2)
    b_arr = np.asarray([ev[1:] for ev in bench], np.float64).reshape(-1, 2)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])  # longest first
    by_label = {}
    for i, (s, e) in enumerate(gaps):
        if i >= labelled:  # the many short ones, together
            label = "shorter gaps"
        else:
            mid = (s + e) / 2
            open_ = sorted({ev[0] for ev in _covering(bench, b_arr, mid)})
            inner = _covering(serving, s_arr, mid)
            label = "+".join(open_) or "no bench span"
            if inner:
                label += ":" + min(inner, key=lambda ev: ev[2] - ev[1])[0]
        by_label[label] = by_label.get(label, 0) + (e - s)
    return [[k, v / n_dev / 1e9] for k, v in sorted(
        by_label.items(), key=lambda kv: -kv[1])[:top]]
