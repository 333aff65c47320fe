"""Bring-up smoke: the served summarizer path, end to end, on a TPU.

    python chip_smoke.py             # one chip: the served ThreeSieves pod,
                                     # then a SieveStreaming++ pod
    python chip_smoke.py --chips 4   # the sharded pod on four chips vs four
                                     # one-device pods on device 0
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--chips 4]
                                     # tiny shapes, Pallas in interpret mode

Phase 1 drives a multi-tenant ``SummarizerPod`` the way a deployment
does: ``DriftSource`` -> ``TaggedBuffer`` (block policy) ->
``IngestPipeline`` (``feed_from``, each lease copied straight into
double-buffered chunks, the donated device step) ->
``SummarizerPod.serve(drift_every=...)`` -> ``readout()`` ->
``drain_metrics()``.  The pod is ThreeSieves at the
``paper-summarizer__pod256`` shape (S=256 sessions, K_max=100, d=256,
chunk C=1024, f32), with tenants on mixed plans K in {10, 50, 100}, fed
device batches of S*C/2 items.  Phase 2 runs a SieveStreaming++ pod
(S=64), which takes the unfused path: the Pallas gain kernel under vmap.

Each phase is checked against the plain per-session ``run`` scan in f32
(jnp oracle, highest matmul precision) on the exact item sequence each
sampled session received: every sampled f(S) must be within 1%.

Only a TPU run counts: without one the script exits non-zero before any
work.  Times printed are smoke timings, not benchmarks.  The last line
of standard output is one JSON object, ``{"ok": true, "device": ...}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))


@dataclasses.dataclass(frozen=True)
class Size:
    S: int  # sessions of the served pod
    K: int  # K_max, the pod's summary capacity
    d: int
    C: int  # per-session chunk of one device batch
    plans: tuple  # (K, T, eps) per plan
    batches: int
    drift_every: int
    sample: int  # sessions checked against the reference
    S_pp: int  # sessions of the SieveStreaming++ pod
    batches_pp: int
    sample_pp: int
    S_shard: int  # sessions per device in the four-chip phase
    batches_shard: int


FULL = Size(S=256, K=100, d=256, C=1024,
            plans=((10, 100, 0.05), (50, 200, 0.1), (100, 400, 0.1)),
            batches=4, drift_every=2, sample=8,
            S_pp=64, batches_pp=2, sample_pp=4,
            S_shard=256, batches_shard=2)
REHEARSAL = Size(S=8, K=8, d=16, C=32,
                 plans=((2, 10, 0.2), (4, 10, 0.2), (8, 20, 0.2)),
                 batches=4, drift_every=2, sample=8,
                 S_pp=4, batches_pp=2, sample_pp=4,
                 S_shard=4, batches_shard=2)
SEED = 0
TOL = 0.01  # relative f(S) tolerance against the reference


def log(msg: str) -> None:
    print(msg, flush=True)


def metric_total(name: str) -> float:
    """A counter's total, or a histogram's sum, over all its series."""
    from repro import obs

    for fam in obs.get_registry().snapshot().families:
        if fam["name"] == name:
            return sum(s.get("value", s.get("sum", 0.0))
                       for s in fam["series"])
    return 0.0


class Phase:
    """Times a phase; its compile seconds come from the XLA compile
    events the obs bridge counts."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = metric_total("xla_compile_seconds")
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            wall = time.perf_counter() - self.t0
            comp = metric_total("xla_compile_seconds") - self.c0
            log(f"[{self.name}] smoke timing, not a benchmark: "
                f"wall {wall:.3f} s, compile {comp:.3f} s")


def spec_of(algo: str, size: Size, backend: str):
    from repro.core import SessionSpec

    # lengthscale sqrt(d)/2: items of one mixture component are similar
    # (k ~ e^-1), items of different components near-orthogonal
    return SessionSpec(algo=algo, K=size.K, d=size.d, T=size.plans[-1][1],
                       eps=size.plans[-1][2], lengthscale=size.d ** 0.5 / 2,
                       backend=backend)


def plan_spec(spec, plan):
    K, T, eps = plan
    return spec.replace(K=K, T=T, eps=eps)


def source(size: Size, S: int, n_batches: int, seed: int):
    from repro.ingest import DriftSource

    return DriftSource(seed=seed, n_sessions=S, batch=S * size.C // 2,
                       d=size.d, drift_per_batch=0.05, n_batches=n_batches)


def session_items(src, sids):
    """Each session's exact item sequence, in stream order."""
    import numpy as np

    got = {int(s): [] for s in sids}
    for tags, X in src:
        for s in got:
            got[s].append(X[tags == s])
    return {s: np.concatenate(v) for s, v in got.items()}


def check_against_reference(name, spec, plans, ro, slots, items):
    """Replay each sampled session through plain per-session ``run`` in
    f32 and compare f(S).  Returns the share with an identical set."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import make

    ref = make(spec.replace(backend="jnp"))
    L = max(len(x) for x in items.values())
    L += (-L) % 256  # one padded length: one compiled scan
    identical = 0
    with jax.default_matmul_precision("highest"):
        run = jax.jit(ref.run)
        evaluate = jax.jit(ref.f.evaluate)
        for slot in slots:
            sid = int(slot)
            K, T, eps = plans[slot]
            X = items[sid]
            Xp = np.zeros((L, spec.d), np.float32)
            Xp[:len(X)] = X
            st = ref.init(ref.hyper(K=K, T=T, eps=eps))
            st = run(st, jnp.asarray(Xp), jnp.int32(len(X)))
            rf, rn, rval = ref.summary(st)
            pf, pn = ro.feats[slot], ro.n[slot]
            f_ref = float(evaluate(rf, rn))
            f_pod = float(evaluate(jnp.asarray(pf), jnp.asarray(pn)))
            f_track = float(ro.fval[slot])
            rel = abs(f_pod - f_ref) / max(abs(f_ref), 1e-12)
            rel_track = abs(f_track - f_pod) / max(abs(f_pod), 1e-12)
            rows_pod = {r.tobytes() for r in np.asarray(pf)[:int(pn)]}
            rows_ref = {r.tobytes() for r in np.asarray(rf)[:int(rn)]}
            same = rows_pod == rows_ref
            identical += same
            log(f"[{name}] session {sid} K={K}: items {len(X)}, "
                f"n pod {int(pn)} ref {int(rn)}, f(S) pod {f_pod:.6f} "
                f"ref {f_ref:.6f} rel {rel:.2e} (tracked {f_track:.6f}), "
                f"identical set {same}")
            if not (rel <= TOL and rel_track <= TOL and np.isfinite(f_pod)):
                raise AssertionError(
                    f"{name}: session {sid} f(S) {f_pod} vs reference "
                    f"{f_ref} (tracked {f_track}) is outside {TOL:.0%}")
    share = identical / len(slots)
    log(f"[{name}] sampled sessions within {TOL:.0%} of the reference: "
        f"{len(slots)}/{len(slots)}; identical selected set: {share:.3f}")
    return share


def make_pod(name, algo_name, size, S, rehearse):
    """A pod on the platform's kernels: Pallas on the chip, the Pallas
    interpreter in a rehearsal.  Fails unless they resolved so."""
    from repro.core import make
    from repro.kernels.pod_step import ops
    from repro.serve import SummarizerPod

    backend = "pallas-interpret" if rehearse else None
    spec = spec_of(algo_name, size, backend or "auto")
    algo = make(spec)
    fused = ops.fusable(algo)
    # an unfusable algorithm takes the jnp pod step by design (auto)
    backend_step = backend if fused else None
    pod = SummarizerPod(algo=algo, sessions=S, chunk=size.C,
                        podstep_backend=backend_step)
    podstep = ops.resolve(backend_step, algo)
    oracle = algo.f.oracle.resolved
    want = "pallas-interpret" if rehearse else "pallas"
    log(f"[{name}] pod_step backend {podstep}, oracle backend {oracle}, "
        f"interpret {'interpret' in podstep or 'interpret' in oracle}")
    if oracle != want or podstep != (want if fused else "jnp"):
        raise AssertionError(f"{name}: backends resolved to pod_step="
                             f"{podstep} oracle={oracle}, expected {want}")
    return pod, spec


def served_phase(name, algo_name, size, S, n_batches, drift_every, n_sample,
                 rehearse):
    """One pod through the served path, then the reference check."""
    import jax
    import numpy as np

    from repro import obs
    from repro.ingest import IngestPipeline, TaggedBuffer

    pod, spec = make_pod(name, algo_name, size, S, rehearse)

    state = pod.init()
    plans = {}
    for slot in range(S):  # sids 0..S-1 land in slots 0..S-1
        plan = size.plans[slot % len(size.plans)]
        state, got, ok = pod.admit(state, np.int32(slot),
                                   spec=plan_spec(spec, plan))
        if not (bool(ok) and int(got) == slot):
            raise AssertionError(f"{name}: admit of session {slot} failed")
        plans[slot] = plan

    B = S * size.C // 2
    src = source(size, S, n_batches, SEED)
    buf = TaggedBuffer(capacity=2 * B, policy="block")
    pipe = IngestPipeline(pod=pod, buffer=buf, batch=B, min_fill=B,
                          pod_id=name)
    pipe.feed_from(src)
    checks0 = len(obs.get_recorder().find("drift_check"))
    # the drift check runs on the chip; its window minimum is above what
    # any session receives, so no session resets and the per-session
    # reference below replays each session's stream exactly
    state, stats = pod.serve(state, pipe, max_batches=n_batches,
                             drift_every=drift_every,
                             min_items=S * size.C * n_batches,
                             min_rate=0.5)
    checks = len(obs.get_recorder().find("drift_check")) - checks0
    ro = pod.readout(state)
    pod.drain_metrics(state, pod=name)
    ro = jax.tree_util.tree_map(np.asarray, ro)
    items_per_session = np.asarray(state.items)
    log(f"[{name}] sessions {S}, device batches {stats['batches']} of {B} "
        f"items, items {stats['items']}, padded {stats['padded']}, "
        f"drift checks {checks}, "
        f"drift resets {int(np.asarray(state.resets).sum())}, "
        f"drops overflow {int(ro.drops['overflow'].sum())} "
        f"unknown {int(ro.drops['unknown'])}")
    if stats["batches"] != n_batches or stats["items"] != n_batches * B:
        raise AssertionError(f"{name}: served {stats}")
    if ro.drops["overflow"].sum() or ro.drops["unknown"] or buf.total_drops():
        raise AssertionError(f"{name}: items were dropped")
    if drift_every and not checks:
        raise AssertionError(f"{name}: no drift check ran")
    if np.asarray(state.resets).sum():
        raise AssertionError(f"{name}: a drift check reset a session")
    if not (np.all(np.isfinite(ro.fval)) and ro.feats.shape == (S, size.K,
                                                                size.d)):
        raise AssertionError(f"{name}: readout is not finite or misshapen")

    # evenly spread, each nudged forward onto plan i % P (slot s has plan
    # s % P), so the sample covers every plan
    P = len(size.plans)
    spread = np.linspace(0, S - P, min(n_sample, S)).astype(int)
    slots = np.unique([s + (i - s) % P for i, s in enumerate(spread)])
    if {plans[s] for s in slots} != set(size.plans):
        raise AssertionError(f"{name}: the sample misses a plan")
    items = session_items(source(size, S, n_batches, SEED), slots)
    for s in slots:
        if len(items[s]) != items_per_session[s]:
            raise AssertionError(f"{name}: session {s} routed "
                                 f"{items_per_session[s]} of {len(items[s])}")
    check_against_reference(name, spec, plans, ro, slots, items)


def sharded_phase(size, rehearse):
    """4 x S_shard sessions on a ('data',) mesh of four devices vs the
    same sessions as four one-device pods on device 0: bit-equal."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.ingest import PAD_SID
    from repro.launch.mesh import auto_mesh

    n_dev, S = 4, size.S_shard
    if len(jax.devices()) < n_dev:
        raise SystemExit(f"--chips 4 needs 4 devices, found "
                         f"{len(jax.devices())}")
    pod, spec = make_pod("sharded", "threesieves", size, S, rehearse)
    mesh = auto_mesh((n_dev,), ("data",), devices=jax.devices()[:n_dev])
    sharded = NamedSharding(mesh, P("data"))
    dev0 = jax.devices()[0]

    locals_ = []
    for p in range(n_dev):  # shard p hosts sessions p*S .. p*S+S-1
        st = pod.init()
        for slot in range(S):
            plan = size.plans[slot % len(size.plans)]
            st, _, ok = pod.admit(st, np.int32(p * S + slot),
                                  spec=plan_spec(spec, plan))
            assert bool(ok)
        locals_.append(jax.device_put(st, dev0))
    glob = jax.device_put(
        jax.tree_util.tree_map(lambda *xs: np.concatenate(xs), *locals_),
        sharded)
    step = jax.jit(pod.make_sharded_update(mesh, pre_routed=True),
                   in_shardings=sharded, out_shardings=sharded)
    local_step = jax.jit(pod.ingest_routed)
    route = jax.jit(pod.route)

    with Phase("sharded pod, 4 devices vs 4 one-device pods"):
        src = source(size, n_dev * S, size.batches_shard, SEED + 1)
        items = 0
        for tags, X in src:
            routed = []
            X0 = jax.device_put(X, dev0)
            for p, st in enumerate(locals_):
                # another shard's items are padding here: one shape
                mine = np.where((tags // S) == p, tags, np.int32(PAD_SID))
                r = route(st, jax.device_put(mine, dev0), X0)
                routed.append(r)
                locals_[p], _ = local_step(st, *r)
            chunks, counts, unknown, overflow = (
                np.concatenate([np.atleast_1d(np.asarray(r[i]))
                                for r in routed])
                for i in range(4))
            glob, _ = step(glob, *(jax.device_put(a, sharded) for a in
                                   (chunks, counts, unknown, overflow)))
            items += int(counts.sum())
        jax.block_until_ready(glob)
    want = jax.tree_util.tree_map(lambda *xs: np.concatenate(xs),
                                  *jax.device_get(locals_))
    got = jax.device_get(glob)
    equal = all(np.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)))
    ro = pod.readout(got)
    log(f"[sharded] sessions {n_dev}x{S}, batches {size.batches_shard}, "
        f"items {items}, selected {int(np.asarray(ro.n).sum())}, "
        f"state bit-equal to four one-device pods: {equal}")
    if not equal:
        raise AssertionError("sharded pod differs from one-device pods")
    if not np.all(np.isfinite(np.asarray(ro.fval))):
        raise AssertionError("sharded pod readout is not finite")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-pod phase and its "
                         "comparison, on four devices")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes and Pallas interpret mode, on any "
                         "platform (never a chip result)")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU: JAX found platform {dev.platform!r} "
              f"({dev.device_kind}); nothing was run", file=sys.stderr)
        return 2

    from repro.compat import use_compile_cache

    cache = use_compile_cache()
    size = REHEARSAL if args.rehearse else FULL
    log(f"device: platform {dev.platform}, kind {dev.device_kind}, "
        f"count {len(jax.devices())}; rehearsal {args.rehearse}; "
        f"compile cache {cache}")

    if args.chips == 4:
        sharded_phase(size, args.rehearse)
    else:
        with Phase("threesieves pod"):
            served_phase("threesieves", "threesieves", size, size.S,
                         size.batches, size.drift_every, size.sample,
                         args.rehearse)
        with Phase("sievestreaming++ pod"):
            served_phase("sievestreaming++", "sievestreaming++", size,
                         size.S_pp, size.batches_pp, 0, size.sample_pp,
                         args.rehearse)

    compiles = metric_total("xla_compile_total")
    fallbacks = metric_total("backend_fallback_total")
    log(f"xla_compile_total {compiles:g}, backend_fallback_total "
        f"{fallbacks:g}")
    if fallbacks:
        raise AssertionError("a kernel backend fell back")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
