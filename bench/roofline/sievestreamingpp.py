"""Work a SieveStreaming++ pod step needs, whatever implements it.

The same count as ThreeSieves' (see ``threesieves.py``), once per live
rung instance: an item is priced against every live rung's summary, an
accept appends to each rung that takes it, and every live rung's state
is read and written once per device step.  A rung counts by the mean of
its liveness at the window's two edges.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_roofline_threesieves", Path(__file__).with_name("threesieves.py"))
_ts = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ts)

# the unfused step is one XLA program: vmap(run_batched) with the gain
# kernel inside; its device time is the module's
EVENTS = ("ingest_routed",)


def counters(state):
    return {"items": state.items, "accepts": state.accepts,
            "n": state.algo.lds.n, "alive": state.algo.alive}


work = _ts.work
