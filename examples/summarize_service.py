"""A miniature multi-tenant summarization service on a SummarizerPod.

Eight tenants stream embeddings through one tagged queue; the pod hosts
every session as one stacked device-resident state and advances them all
in a single jitted program.  Tenants buy DIFFERENT budgets: half are on
the pod-default plan, the rest bring their own ``SessionSpec``
(K/T/eps + kernel hyperparameters) — a "small" plan (K=4, coarse
ladder, the batch-calibrated RBF lengthscale 1/(2 sqrt d)) and a "pro"
plan (K=16, fine ladder, the stream-calibrated 1/sqrt d) — all sharing
the same compiled program via per-slot traced hyperparams (DESIGN.md
§9; the lengthscale/kernel-kind rows ride the same mechanism and feed
the fused pod-step kernel, §11).  The driver exercises the full session
lifecycle: admit (mixed specs), stream, drift-triggered reset (which
keeps each tenant's budget), periodic readout incl. the per-slot spec
rows, evict + slot reuse, and checkpoint/restore mid-stream.

    PYTHONPATH=src python examples/summarize_service.py
"""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import CheckpointStore
from repro.compat import use_compile_cache
from repro.core import (SessionSpec, make, rbf_lengthscale_batch,
                        rbf_lengthscale_stream)
from repro.data import MixtureSpec, session_stream
from repro.serve import SummarizerPod

use_compile_cache()

S, K_MAX, D, CHUNK = 8, 16, 32, 64
ROUNDS = 30

# the pod is sized for its biggest plan: K_MAX buffer rows, finest ladder
pod_spec = SessionSpec(algo="threesieves", K=K_MAX, d=D, T=200, eps=1e-2,
                       lengthscale=2.0)
algo = make(pod_spec)
pod = SummarizerPod(algo=algo, sessions=S, chunk=CHUNK)
state = pod.init()

# plans differ in kernel hyperparameters too: "small" tenants summarize
# finite uploads (batch-calibrated lengthscale 1/(2 sqrt d)), "pro"
# tenants summarize open-ended streams (1/sqrt d).  Per-slot rows, one
# compiled program — no recompile between admissions.
PLANS = {
    "default": None,  # pod spec: K=16, T=200, eps=1e-2, lengthscale=2.0
    "small": pod_spec.replace(K=4, T=100, eps=5e-2,
                              lengthscale=rbf_lengthscale_batch(D)),
    "pro": pod_spec.replace(K=16, T=400, eps=1e-2,
                            lengthscale=rbf_lengthscale_stream(D)),
}

ingest = jax.jit(pod.ingest)
drift = jax.jit(lambda s: pod.drift_check(s, min_items=500, min_rate=0.02))

print(f"pod: {S} slots, K_max={K_MAX}, d={D}; admitting tenants "
      f"100..{100 + S - 1} on mixed plans")
plan_of = {}
for i, sid in enumerate(range(100, 100 + S)):
    plan = list(PLANS)[i % len(PLANS)]
    plan_of[sid] = plan
    state, slot, ok = pod.admit(state, jnp.int32(sid), spec=PLANS[plan])
    assert bool(ok)
    print(f"  tenant {sid}: plan={plan:8s} -> slot {int(slot)}")

stream = session_stream(0, MixtureSpec(n_components=6, d=D, spread=5.0),
                        S, batch=S * CHUNK // 2,
                        session_ids=np.arange(100, 100 + S),
                        drift_per_batch=0.02)

store = CheckpointStore(tempfile.mkdtemp(prefix="pod_ckpt_"))
for rnd in range(ROUNDS):
    sids, X = next(stream)
    state, stats = ingest(state, sids, X)
    if rnd % 10 == 9:
        state, reset = drift(state)
        ro = pod.readout(state)
        n_reset = int(jnp.sum(reset))
        print(f"round {rnd + 1:3d}: items/session="
              f"{np.asarray(state.items).mean():7.1f}  mean f(S)="
              f"{float(jnp.mean(jnp.where(ro.active, ro.fval, 0.0))):6.3f}  "
              f"drift-resets={n_reset}")
        pod.save(store, rnd + 1, state, {"round": rnd + 1})

# evict one tenant, admit a new "small"-plan one into the recycled slot
state = pod.evict(state, jnp.int32(100))
state, slot, ok = pod.admit(state, jnp.int32(999), spec=PLANS["small"])
plan_of[999] = "small"
print(f"evicted tenant 100; tenant 999 (small plan) admitted into "
      f"recycled slot {int(slot)} (ok={bool(ok)})")

# restore the pod mid-stream (e.g. on a new host) and keep going — the
# per-slot budgets are state and travel with the checkpoint
restored, extra = pod.restore(store)
print(f"restored checkpoint of round {extra['round']}; continuing")
sids, X = next(stream)
restored, _ = ingest(restored, sids, X)

ro = pod.readout(restored)
print(f"final per-session summaries (restored pod); dropped: "
      f"unknown={int(ro.drops['unknown'])} "
      f"overflow={int(jnp.sum(ro.drops['overflow']))}")
for s in range(S):
    sid = int(restored.sid[s])
    print(f"  slot {s}: sid={sid:4d} plan={plan_of.get(sid, '?'):8s} "
          f"K={int(ro.specs.k_cap[s]):3d} T={int(ro.specs.T[s]):4d} "
          f"eps={float(ro.specs.eps[s]):.3f} "
          f"ls={float(ro.specs.lengthscale[s]):.3f}  "
          f"selected={int(ro.n[s]):3d}  f(S)={float(ro.fval[s]):6.3f}  "
          f"resets={int(restored.resets[s])}")
