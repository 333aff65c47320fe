"""PodAutoscaler: live two-pod handoff, victim policies, refusal edges.

The load-bearing claim (the acceptance bar of the autoscaler): under a
live ``IngestPipeline`` fleet, migrating a session between two pods
yields summaries *bit-equal* to the run that never migrated, over the
same per-session item order, with zero items dropped during the quiesce
window.  Everything else here guards the edges an autoscaler hits by
design: victims that raced an eviction (no-op, counted), a target pod
without room (atomic refusal), a handoff landing mid-drift-reset.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import make
from repro.ingest import IngestPipeline, PodRouter, TaggedBuffer
from repro.serve import (HandoffReport, PodAutoscaler, ScalePolicy,
                         SummarizerPod)

D = 5


def _pod(S=4, C=16, K=4, **kw):
    algo = make("threesieves", K=K, d=D, lengthscale=1.5, eps=0.1,
                T=kw.pop("T", 11), **kw)
    return SummarizerPod(algo=algo, sessions=S, chunk=C)


def _admit_all(pod, state, sids):
    for sid in sids:
        state, _, ok = pod.admit(state, jnp.int32(sid))
        assert bool(ok)
    return state


def _tagged(rng, n, sessions):
    sids = rng.choice(np.asarray(sessions, np.int32), n)
    X = rng.randn(n, D).astype(np.float32)
    X[:, 0] = np.arange(n, dtype=np.float32)  # per-item fingerprint
    return sids.astype(np.int32), X


def _per_session(batches):
    per = {}
    for sids, X in batches:
        for sid, x in zip(sids.tolist(), X):
            per.setdefault(int(sid), []).append(x)
    return per


def _assert_summary_equals_standalone(pod, state, sid, items, label=""):
    """The migrated tenant's summary must be bit-equal to the run that
    never moved: standalone run_batched over the same item order."""
    slot = pod.routing_table(state)[sid]
    ro = pod.readout(state)
    algo = pod.algo
    ref = jax.jit(algo.run_batched)(algo.init(), jnp.asarray(np.stack(items)))
    rf, rn, rfv = algo.summary(ref)
    assert int(ro.n[slot]) == int(rn), f"{label} session {sid}"
    np.testing.assert_array_equal(
        np.asarray(ro.feats[slot]), np.asarray(rf),
        err_msg=f"{label} session {sid} summary diverged")
    np.testing.assert_array_equal(
        np.asarray(ro.fval[slot]), np.asarray(rfv),
        err_msg=f"{label} session {sid} f-value diverged")


def _tree_equal(a, b, msg=""):
    for (pa, la), lb in zip(jax.tree_util.tree_leaves_with_path(a),
                            jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb),
            err_msg=f"{msg} leaf {jax.tree_util.keystr(pa)} differs")


def _fleet(pods, batch=16, capacity=2048):
    pipes = {i: IngestPipeline(p, buffer=TaggedBuffer(capacity), batch=batch,
                               get_timeout=30.0)
             for i, p in enumerate(pods)}
    return PodRouter(pipelines=pipes), pipes


# ------------------------------------------------------------- end-to-end
def test_live_handoff_bit_equal_zero_drops():
    """The acceptance bar: a mid-stream two-pod migration under a live
    pipeline fleet is invisible in the summaries — every session
    (migrated or resident) ends bit-equal to its unmigrated reference,
    and not one item is lost anywhere in the handoff."""
    podA, podB = _pod(S=4), _pod(S=4)
    sids_all = [100, 101, 102, 103]
    rng = np.random.RandomState(7)
    feed = [_tagged(rng, n, sids_all)
            for n in (24, 17, 31, 24, 9, 28, 24, 15, 24, 20, 24, 16)]
    per = _per_session(feed)
    n_total = sum(len(s) for s, _ in feed)

    router, pipes = _fleet([podA, podB])
    states = {0: _admit_all(podA, podA.init(), sids_all), 1: podB.init()}
    router.assign(sids_all, 0)
    asc = PodAutoscaler(router=router, pods={0: podA, 1: podB},
                        policy=ScalePolicy(max_occupancy=0.5, victims=2))

    # the producer pauses mid-stream so the handoff is provably live:
    # half the feed lands before the migration, half after
    from repro.ingest import Source

    gate = threading.Event()

    class Gated(Source):
        def batches(self):
            for i, b in enumerate(feed):
                if i == 6:
                    gate.wait(timeout=30.0)
                yield b

    feeder = router.feed_from(Gated())
    # phase 1: everything on pod A
    states[0], s1 = pipes[0].run(states[0], max_batches=3)
    # live handoff of two victims while the producer is mid-stream
    states, rep = asc.handoff(states, 0, 1, [100, 102])
    assert rep.ok and rep.moved == [100, 102] and not rep.skipped
    gate.set()  # the second half now streams straight to the new owner
    # phase 2: drain both pods to end-of-stream
    states[0], s2 = pipes[0].run(states[0])
    states[1], s3 = pipes[1].run(states[1])
    feeder.join(timeout=30.0)
    assert pipes[0].exhausted and pipes[1].exhausted

    # zero drops, every item accounted for exactly once
    for st in (s1, s2, s3):
        assert st["dropped_unknown"] == 0 and st["dropped_overflow"] == 0
    assert not router.drops_unrouted
    for pipe in pipes.values():
        assert not pipe.buffer.drop_counts()
        assert pipe.buffer.size == 0
    fed = s1["items"] + s2["items"] + s3["items"]
    assert fed == n_total
    routedA = {s: int(states[0].items[i])
               for s, i in podA.routing_table(states[0]).items()}
    routedB = {s: int(states[1].items[i])
               for s, i in podB.routing_table(states[1]).items()}
    assert sorted(routedA) == [101, 103] and sorted(routedB) == [100, 102]
    for sid, cnt in {**routedA, **routedB}.items():
        assert cnt == len(per[sid]), f"session {sid} lost items"

    # bit-equality against the never-migrated reference, every session
    for sid in (100, 102):
        _assert_summary_equals_standalone(podB, states[1], sid, per[sid],
                                          "migrated")
    for sid in (101, 103):
        _assert_summary_equals_standalone(podA, states[0], sid, per[sid],
                                          "resident")


def test_handoff_quiesce_preserves_fifo_backlog():
    """Items parked during quiesce come out at the target pod *before*
    post-flip arrivals — per-session FIFO across the migration."""
    podA, podB = _pod(S=2, C=32), _pod(S=2, C=32)
    router, pipes = _fleet([podA, podB], batch=32)
    states = {0: _admit_all(podA, podA.init(), [5]), 1: podB.init()}
    router.assign([5], 0)
    asc = PodAutoscaler(router=router, pods={0: podA, 1: podB})

    rng = np.random.RandomState(1)
    pre = rng.randn(8, D).astype(np.float32)
    router.put(np.full(8, 5, np.int32), pre)
    states[0], _ = pipes[0].run(states[0], max_batches=1)

    backlog = rng.randn(6, D).astype(np.float32)
    router.quiesce([5])
    router.put(np.full(6, 5, np.int32), backlog)  # parks in A's buffer
    assert pipes[0].buffer.depths() == {5: 6}
    states, rep = asc.handoff(states, 0, 1, [5])
    assert rep.ok and rep.backlog_items == 6
    post = rng.randn(4, D).astype(np.float32)
    router.put(np.full(4, 5, np.int32), post)  # lands at B, behind backlog
    states[1], stats = pipes[1].run(states[1], max_batches=1)
    assert stats["items"] == 10
    _assert_summary_equals_standalone(
        podB, states[1], 5, list(pre) + list(backlog) + list(post))


@pytest.mark.parametrize("parked", [False, True])
def test_handoff_there_and_back_bit_equal(parked):
    """A session moved to the other pod and back, with items ingested
    before, between and after, ends bit-equal to the run that never
    moved, as do the sessions that stayed.  ``parked``: the return trip
    happens while the first target still holds the session's items
    undrained, so they travel back as its backlog, FIFO."""
    podA, podB = _pod(S=4), _pod(S=4)
    sids_all = [70, 71, 72]
    router, pipes = _fleet([podA, podB])
    states = {0: _admit_all(podA, podA.init(), sids_all), 1: podB.init()}
    router.assign(sids_all, 0)
    asc = PodAutoscaler(router=router, pods={0: podA, 1: podB})
    rng = np.random.RandomState(21)
    feed = []

    def put():
        feed.append(_tagged(rng, 40, sids_all))
        router.put(*feed[-1])

    def drain(pods=(0, 1)):
        for i in pods:
            while pipes[i].buffer.size:
                states[i], st = pipes[i].run(states[i], max_batches=1)
                assert st["dropped_unknown"] == 0
                assert st["dropped_overflow"] == 0

    put()
    drain()
    states, there = asc.handoff(states, 0, 1, [71])
    put()
    drain((0,) if parked else (0, 1))
    states, back = asc.handoff(states, 1, 0, [71])
    assert there.ok and back.ok and back.moved == [71]
    assert (back.backlog_items > 0) == parked
    put()
    drain()
    assert not router.drops_unrouted
    assert sorted(podA.routing_table(states[0])) == sids_all
    assert int(np.asarray(states[1].active).sum()) == 0
    per = _per_session(feed)
    for sid in sids_all:
        _assert_summary_equals_standalone(podA, states[0], sid, per[sid],
                                          "there and back")


def test_handoff_after_stream_close_still_delivers_backlog():
    """Regression: a handoff landing after end-of-stream (the producer
    closed the buffers, the target pipeline already drained to
    exhaustion) must not strand the relocated backlog — a later run()
    on the target re-opens the drain and ingests it."""
    podA, podB = _pod(S=2, C=32), _pod(S=2, C=32)
    router, pipes = _fleet([podA, podB], batch=32)
    states = {0: _admit_all(podA, podA.init(), [5]), 1: podB.init()}
    router.assign([5], 0)
    asc = PodAutoscaler(router=router, pods={0: podA, 1: podB})

    rng = np.random.RandomState(2)
    items = rng.randn(12, D).astype(np.float32)
    router.put(np.full(6, 5, np.int32), items[:6])
    states[0], _ = pipes[0].run(states[0], max_batches=1)
    router.quiesce([5])
    router.put(np.full(6, 5, np.int32), items[6:])  # parks at A
    for pipe in pipes.values():  # the producer hangs up
        pipe.buffer.close()
    states[1], st = pipes[1].run(states[1])  # B drains to exhaustion
    assert pipes[1].exhausted and st["items"] == 0

    states, rep = asc.handoff(states, 0, 1, [5])
    assert rep.ok and rep.backlog_items == 6
    states[1], st2 = pipes[1].run(states[1])  # re-opens the drain
    assert st2["items"] == 6
    _assert_summary_equals_standalone(podB, states[1], 5, list(items))


def test_handoff_mid_drift_reset():
    """A victim whose summary was just drift-reset migrates with the
    reset applied: the re-selection continues on the target pod exactly
    as it would have on the source."""
    podA, podB = _pod(S=2, T=5), _pod(S=2, T=5)
    router, pipes = _fleet([podA, podB])
    states = {0: _admit_all(podA, podA.init(), [40, 41]), 1: podB.init()}
    router.assign([40, 41], 0)
    asc = PodAutoscaler(router=router, pods={0: podA, 1: podB})

    rng = np.random.RandomState(3)
    ing = jax.jit(podA.ingest)
    pre = _tagged(rng, 48, [40, 41])
    states[0], _ = ing(states[0], jnp.asarray(pre[0]), jnp.asarray(pre[1]))
    # drift fires on session 40's slot: its summary re-arms mid-stream
    slot40 = podA.routing_table(states[0])[40]
    mask = np.zeros(2, bool)
    mask[slot40] = True
    states[0] = podA.reset_slots(states[0], jnp.asarray(mask))
    resets_before = int(states[0].resets[slot40])
    assert resets_before == 1

    states, rep = asc.handoff(states, 0, 1, [40])
    assert rep.ok and rep.moved == [40]
    slotB = podB.routing_table(states[1])[40]
    # the reset ledger travels with the row
    assert int(states[1].resets[slotB]) == resets_before

    post = _tagged(rng, 24, [40])
    ingB = jax.jit(podB.ingest)
    states[1], _ = ingB(states[1], jnp.asarray(post[0]),
                        jnp.asarray(post[1]))
    # reference: only the post-reset items feed the re-armed summary
    post_items = [x for s, x in zip(post[0].tolist(), post[1]) if s == 40]
    _assert_summary_equals_standalone(podB, states[1], 40, post_items,
                                      "mid-drift-reset")


def test_second_handoff_same_victim_count_is_retrace_free(retrace_guard):
    """A warmed-up migration path must stay compiled: the snapshot /
    restore programs key on the victim *count* (the per-leaf gather is
    ``leaf[slots]`` with ``len(moving)`` rows), so a second handoff
    moving the same number of sessions — different sids, different
    slots, opposite direction — is served entirely from cache.  At
    fleet scale the quiesce window must not pay XLA compile latency."""
    podA, podB = _pod(S=4), _pod(S=4)
    router, pipes = _fleet([podA, podB])
    states = {0: _admit_all(podA, podA.init(), [60, 61, 62]), 1: podB.init()}
    router.assign([60, 61, 62], 0)
    asc = PodAutoscaler(router=router, pods={0: podA, 1: podB})
    rng = np.random.RandomState(13)
    sids, X = _tagged(rng, 24, [60, 61, 62])
    ing = jax.jit(podA.ingest)
    states[0], _ = ing(states[0], jnp.asarray(sids), jnp.asarray(X))

    states, rep = asc.handoff(states, 0, 1, [60])  # warmup compile
    assert rep.ok and rep.moved == [60]
    # same victim count, fresh sid, the reverse direction — zero compiles
    with retrace_guard.budget(0):
        states, rep2 = asc.handoff(states, 0, 1, [61])
        states, rep3 = asc.handoff(states, 1, 0, [60])
    assert retrace_guard.compiles == 0
    assert rep2.ok and rep2.moved == [61]
    assert rep3.ok and rep3.moved == [60]
    assert sorted(podA.routing_table(states[0])) == [60, 62]
    assert sorted(podB.routing_table(states[1])) == [61]


# ---------------------------------------------------------------- refusals
def test_handoff_unknown_or_evicted_sid_is_counted_noop():
    podA, podB = _pod(S=3), _pod(S=3)
    router, pipes = _fleet([podA, podB])
    stA = _admit_all(podA, podA.init(), [1, 2])
    stA = podA.evict(stA, jnp.int32(2))  # raced eviction
    states = {0: stA, 1: podB.init()}
    router.assign([1], 0)
    asc = PodAutoscaler(router=router, pods={0: podA, 1: podB})

    states, rep = asc.handoff(states, 0, 1, [1, 2, 777])
    assert rep.ok and rep.moved == [1]
    assert rep.skipped == [2, 777]
    assert asc.skipped_unknown == 2
    # an all-unknown victim set is a clean no-op, states untouched
    before = {k: v for k, v in states.items()}
    states, rep2 = asc.handoff(states, 0, 1, [888, 999])
    assert rep2.ok and not rep2.moved and rep2.skipped == [888, 999]
    assert asc.skipped_unknown == 4
    for k in before:
        _tree_equal(before[k], states[k], f"pod {k}")


def test_handoff_capacity_refusal_is_atomic():
    """A target pod without room refuses BEFORE quiescing: source pod,
    routing table and buffers are untouched, and the victims' stream
    keeps flowing to the source afterwards — nothing lost."""
    podA, podB = _pod(S=3), _pod(S=2)
    router, pipes = _fleet([podA, podB])
    stB = _admit_all(podB, podB.init(), [900])  # 1 free slot on B
    states = {0: _admit_all(podA, podA.init(), [10, 11, 12]), 1: stB}
    router.assign([10, 11, 12], 0)
    router.assign([900], 1)
    asc = PodAutoscaler(router=router, pods={0: podA, 1: podB})
    before0, before1 = states[0], states[1]
    table_before = router.table()

    states, rep = asc.handoff(states, 0, 1, [10, 11])
    assert not rep.ok and "free slots" in rep.reason
    _tree_equal(before0, states[0], "source pod")
    _tree_equal(before1, states[1], "target pod")
    assert router.table() == table_before
    assert not pipes[0].buffer.quiesced()  # refusal never quiesced

    # exactly-fitting victim sets still go through
    statesc, repc = asc.handoff(states, 0, 1, [10])
    assert repc.ok  # one victim fits the one free slot

    # clash case: craft a sid live on BOTH ends via direct admit
    stX = _admit_all(podA, podA.init(), [77])
    stY = _admit_all(podB, podB.init(), [77])
    st3 = {0: stX, 1: stY}
    st3b, rep3 = asc.handoff(st3, 0, 1, [77])
    assert not rep3.ok and "already live" in rep3.reason
    _tree_equal(stX, st3b[0], "clash source")
    _tree_equal(stY, st3b[1], "clash target")

    # the refused victims keep streaming to the source, zero loss
    rng = np.random.RandomState(5)
    X = rng.randn(8, D).astype(np.float32)
    router.put(np.full(8, 11, np.int32), X)
    states[0], stats = pipes[0].run(states[0], max_batches=1)
    assert stats["items"] == 8 and stats["dropped_unknown"] == 0


def test_handoff_src_equals_dst_refused():
    podA, podB = _pod(S=2), _pod(S=2)
    router, _ = _fleet([podA, podB])
    states = {0: _admit_all(podA, podA.init(), [1]), 1: podB.init()}
    router.assign([1], 0)
    asc = PodAutoscaler(router=router, pods={0: podA, 1: podB})
    _, rep = asc.handoff(states, 0, 0, [1])
    assert not rep.ok and rep.reason == "src == dst"


# ----------------------------------------------------------------- policy
def test_victim_policies_rank_as_documented():
    podA, podB = _pod(S=4), _pod(S=4)
    router, pipes = _fleet([podA, podB])
    stA = _admit_all(podA, podA.init(), [30, 31, 32, 33])
    rng = np.random.RandomState(9)
    ing = jax.jit(podA.ingest)
    # session 31 sees far more (accept-prone) traffic than the rest
    sids = np.asarray([31] * 24 + [30] * 4 + [32] * 2 + [33] * 2, np.int32)
    X = (rng.randn(32, D) * 3).astype(np.float32)
    stA, _ = ing(stA, jnp.asarray(sids), jnp.asarray(X))
    router.assign([30, 31, 32, 33], 0)

    def asc_with(policy):
        return PodAutoscaler(router=router, pods={0: podA, 1: podB},
                             policy=ScalePolicy(victim_policy=policy,
                                                victims=2))

    accepts = {s: int(stA.accepts[podA.routing_table(stA)[s]])
               for s in (30, 31, 32, 33)}
    want = sorted(accepts, key=lambda s: (accepts[s], s))[:2]
    assert asc_with("fewest-insertions").pick_victims(0, stA, 2) == want

    pipes[0].buffer.put([32] * 5 + [30] * 2,
                        np.zeros((7, D), np.float32))
    assert asc_with("largest-queue").pick_victims(0, stA, 2) == [32, 30]

    rr = asc_with("round-robin")
    assert rr.pick_victims(0, stA, 2) == [30, 31]
    assert rr.pick_victims(0, stA, 2) == [32, 33]
    assert rr.pick_victims(0, stA, 2) == [30, 31]

    with pytest.raises(ValueError, match="victim policy"):
        ScalePolicy(victim_policy="loudest")


def test_signals_and_maybe_rebalance():
    """Occupancy trips the policy; maybe_rebalance moves victims from
    the hot pod to the pod with the most free slots; the overflow delta
    baseline advances between checks."""
    podA, podB = _pod(S=2, C=4), _pod(S=4, C=4)
    router, pipes = _fleet([podA, podB], batch=8)
    states = {0: _admit_all(podA, podA.init(), [50, 51]), 1: podB.init()}
    router.assign([50, 51], 0)
    asc = PodAutoscaler(router=router, pods={0: podA, 1: podB},
                        policy=ScalePolicy(max_occupancy=0.6,
                                           max_overflow_delta=4))
    # overflow 6 items past chunk=4 for session 50 (one ingest of 10)
    rng = np.random.RandomState(11)
    ing = jax.jit(podA.ingest)
    states[0], _ = ing(states[0], jnp.full((10,), 50, jnp.int32),
                       jnp.asarray(rng.randn(10, D), jnp.float32))
    sig = asc.signals(0, states[0])
    assert sig.occupancy == 1.0 and sig.overflow_delta == {50: 6}
    hot, reason = asc.hot(sig)
    assert hot and "occupancy" in reason
    # the baseline advanced: a quiet second check reports no new drops
    assert asc.signals(0, states[0]).overflow_delta == {}

    states, rep = asc.maybe_rebalance(states)
    assert isinstance(rep, HandoffReport) and rep.ok
    assert rep.src == 0 and rep.dst == 1 and len(rep.moved) == 1
    assert "hot" in rep.reason
    # fleet is balanced now (1 session each): nothing trips
    states, rep2 = asc.maybe_rebalance(states)
    assert rep2 is None


def test_scale_policy_validation():
    with pytest.raises(ValueError, match="victims"):
        ScalePolicy(victims=0)
    with pytest.raises(ValueError, match="max_occupancy"):
        ScalePolicy(max_occupancy=1.5)


# ----------------------------------------------------------------- router
def test_router_counts_unrouted_and_feeds_by_table():
    podA, podB = _pod(S=2), _pod(S=2)
    router, pipes = _fleet([podA, podB])
    router.assign([1], 0)
    router.assign([2], 1)
    X = np.zeros((4, D), np.float32)
    router.put(np.asarray([1, 2, 9, 9], np.int32), X)
    assert pipes[0].buffer.depths() == {1: 1}
    assert pipes[1].buffer.depths() == {2: 1}
    assert router.drops_unrouted == {9: 2}
    router.unassign([2])
    router.put(np.asarray([2], np.int32), X[:1])
    assert router.drops_unrouted == {9: 2, 2: 1}
    with pytest.raises(KeyError):
        router.assign([3], 7)


def test_router_feeder_failure_surfaces_in_both_pods():
    from repro.ingest import Source

    class Boom(Source):
        def batches(self):
            yield (np.asarray([1], np.int32), np.zeros((1, D), np.float32))
            raise ConnectionError("wire cut")

    podA, podB = _pod(S=2), _pod(S=2)
    router, pipes = _fleet([podA, podB])
    states = {0: _admit_all(podA, podA.init(), [1]), 1: podB.init()}
    router.assign([1], 0)
    t = router.feed_from(Boom())
    t.join(timeout=30.0)
    with pytest.raises(RuntimeError, match="producer failed"):
        pipes[0].run(states[0])
