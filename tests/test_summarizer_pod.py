"""SummarizerPod session engine: routing scatter, lifecycle, drift reset,
checkpoint/restore (incl. elastic mesh change), shard_map execution, and
the headline semantics claim — every session bit-equal to its standalone
``run_batched``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointStore
from repro.core.api import make
from repro.serve import SummarizerPod


def _pod(S=8, C=16, K=5, d=6, **kw):
    algo = make("threesieves", K=K, d=d, lengthscale=1.5, eps=0.1,
                T=kw.pop("T", 11), **kw)
    return SummarizerPod(algo=algo, sessions=S, chunk=C)


def _admit_all(pod, state, sids):
    for sid in sids:
        state, _, ok = pod.admit(state, jnp.int32(sid))
        assert bool(ok)
    return state


def _tree_equal(a, b):
    for (pa, la), lb in zip(jax.tree_util.tree_leaves_with_path(a),
                            jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb),
            err_msg=f"leaf {jax.tree_util.keystr(pa)} differs")


# ------------------------------------------------------------------- routing
def test_route_scatter_fixed_shape():
    """Items land compacted at the front of their session's buffer, in
    stream order; unknown/padding sids and per-session overflow drop."""
    pod = _pod(S=3, C=4, d=2)
    state = _admit_all(pod, pod.init(), [10, 11, 12])
    #            s=10 s=11 s=10 pad  s=99 s=10 s=11 s=10 s=10(overflow? no:4+1)
    sids = jnp.asarray([10, 11, 10, -1, 99, 10, 11, 10, 10], jnp.int32)
    X = jnp.arange(9, dtype=jnp.float32)[:, None] * jnp.ones((1, 2))
    chunks, counts, unknown, overflow = pod.route(state, sids, X)
    assert chunks.shape == (3, 4, 2)
    # session 10 (slot 0) got items 0, 2, 5, 7 — item 8 overflows C=4
    np.testing.assert_array_equal(np.asarray(chunks[0, :, 0]),
                                  [0.0, 2.0, 5.0, 7.0])
    np.testing.assert_array_equal(np.asarray(chunks[1, :2, 0]), [1.0, 6.0])
    np.testing.assert_array_equal(np.asarray(counts), [4, 2, 0])
    # the two drop causes are counted apart: the unknown sid 99 (a
    # routing error) vs the overflow item 8 (backpressure, charged to
    # session 10's slot); queue padding (-1) is neither
    assert int(unknown) == 1
    np.testing.assert_array_equal(np.asarray(overflow), [1, 0, 0])


def test_route_ignores_stale_sid_on_freed_slot():
    pod = _pod(S=2, C=4, d=2)
    state = _admit_all(pod, pod.init(), [7, 8])
    state = pod.evict(state, jnp.int32(7))
    sids = jnp.asarray([7, 8], jnp.int32)
    X = jnp.ones((2, 2), jnp.float32)
    _, counts, unknown, overflow = pod.route(state, sids, X)
    np.testing.assert_array_equal(np.asarray(counts), [0, 1])
    assert int(unknown) == 1 and int(jnp.sum(overflow)) == 0


# ----------------------------------------------------------------- lifecycle
def test_admit_evict_slot_reuse():
    pod = _pod(S=2)
    st = pod.init()
    st, s0, ok0 = pod.admit(st, jnp.int32(100))
    st, s1, ok1 = pod.admit(st, jnp.int32(101))
    assert bool(ok0) and bool(ok1) and int(s0) != int(s1)
    st, _, ok_full = pod.admit(st, jnp.int32(102))
    assert not bool(ok_full)  # pod full, state unchanged
    np.testing.assert_array_equal(np.asarray(st.sid), [100, 101])
    st = pod.evict(st, jnp.int32(100))
    st, s2, ok2 = pod.admit(st, jnp.int32(102))
    assert bool(ok2) and int(s2) == int(s0)  # recycled slot
    assert int(st.sid[int(s2)]) == 102 and int(st.items[int(s2)]) == 0


def test_admit_is_idempotent_for_live_session():
    """A retried admit (lost ack / racing front-ends) must return the
    existing slot untouched, not occupy a phantom second slot that
    ``evict`` would later free together with the real one."""
    pod = _pod(S=3, C=8, K=4, d=6)
    st = _admit_all(pod, pod.init(), [7])
    sids = jnp.asarray([7, 7, 7, 7], jnp.int32)
    X = jnp.asarray(np.random.RandomState(0).randn(4, 6).astype(np.float32))
    st, _ = jax.jit(pod.ingest)(st, sids, X)
    before = st
    st, slot, ok = pod.admit(st, jnp.int32(7))  # re-admit the live session
    assert bool(ok) and int(slot) == 0
    _tree_equal(before, st)  # no reset, no second slot
    assert int(jnp.sum(st.active)) == 1
    st = pod.evict(st, jnp.int32(7))
    assert int(jnp.sum(st.active)) == 0


def test_drift_check_resets_collapsed_sessions():
    pod = _pod(S=4, C=8, K=3, T=5)
    rng = np.random.RandomState(0)
    st = _admit_all(pod, pod.init(), [0, 1, 2, 3])
    ing = jax.jit(pod.ingest)
    for _ in range(6):
        sids = jnp.asarray(rng.randint(0, 4, 24).astype(np.int32))
        X = jnp.asarray(rng.randn(24, 6).astype(np.float32) * 2)
        st, _ = ing(st, sids, X)
    # summaries are full by now -> windowed accept rate has collapsed
    st2, mask = pod.drift_check(st, min_items=10, min_rate=0.2)
    assert bool(jnp.all(mask == st.active))
    np.testing.assert_array_equal(np.asarray(st2.resets),
                                  np.asarray(mask, np.int32))
    n = pod.readout(st2).n
    assert int(jnp.sum(n)) == 0  # re-armed summaries are empty
    # lifetime counters survive the reset, the window does not
    np.testing.assert_array_equal(np.asarray(st2.items), np.asarray(st.items))
    assert int(jnp.sum(st2.win_items)) == 0


# ------------------------------------- the acceptance-criteria lifecycle test
def test_pod64_lifecycle_bit_equal_to_standalone():
    """S=64 sessions: admit -> stream 12 chunks -> drift-triggered reset ->
    checkpoint -> restore -> continue -> summary; every session's summary
    is bit-equal to running its algorithm standalone via ``run_batched``
    on the items routed to it (post-reset for the reset subset)."""
    S, C, K, D, ROUNDS, RESET_AT = 64, 24, 6, 8, 12, 6
    pod = _pod(S=S, C=C, K=K, d=D, T=15)
    algo = pod.algo
    st = _admit_all(pod, pod.init(), range(S))
    ing = jax.jit(pod.ingest)
    drift = jax.jit(lambda s: pod.drift_check(s, min_items=40, min_rate=0.09))

    rng = np.random.RandomState(7)
    per_round = {s: {} for s in range(S)}
    reset_mask = np.zeros(S, bool)
    for rnd in range(ROUNDS):
        N = 12 * S
        sids = rng.randint(0, S, N).astype(np.int32)
        X = (rng.randn(N, D) * 2.0).astype(np.float32)
        for s in range(S):
            per_round[s][rnd] = X[sids == s]
        st, stats = ing(st, jnp.asarray(sids), jnp.asarray(X))
        assert int(stats["dropped_unknown"][0]) == 0
        assert int(jnp.sum(stats["dropped_overflow"])) == 0
        if rnd == RESET_AT - 1:
            # summaries saturate fast here, so the windowed accept rate
            # has collapsed for most sessions — the monitor re-arms them
            st, mask = drift(st)
            reset_mask = np.asarray(mask)
            assert reset_mask.any()
        if rnd == 7:  # checkpoint mid-stream, restore, continue
            store = CheckpointStore(_tmp_dir())
            pod.save(store, rnd, st, {"round": rnd})
            st, extra = pod.restore(store)
            assert extra["round"] == rnd

    ro = pod.readout(st)
    feats, n, fval, active, drops = ro.feats, ro.n, ro.fval, ro.active, ro.drops
    assert bool(jnp.all(active))
    assert int(drops["unknown"]) == 0
    assert int(jnp.sum(drops["overflow"])) == 0

    # one fixed-shape jitted reference for all sessions: pad each
    # session's (post-reset) stream to a common length, mask via n_valid
    streams = {}
    for s in range(S):
        start = RESET_AT if reset_mask[s] else 0
        streams[s] = np.concatenate(
            [per_round[s][r] for r in range(start, ROUNDS)])
    L = max(len(v) for v in streams.values())
    runb = jax.jit(algo.run_batched)
    for s in range(S):
        pad = np.zeros((L - len(streams[s]), D), np.float32)
        Xs = jnp.asarray(np.concatenate([streams[s], pad]))
        ref = runb(algo.init(), Xs, jnp.int32(len(streams[s])))
        rf, rn, rfv = algo.summary(ref)
        assert int(n[s]) == int(rn), f"session {s}"
        np.testing.assert_array_equal(np.asarray(feats[s]), np.asarray(rf),
                                      err_msg=f"session {s} feats")
        np.testing.assert_array_equal(np.asarray(fval[s]), np.asarray(rfv),
                                      err_msg=f"session {s} fval")
    # the drift monitor's resets are recorded on the slots
    np.testing.assert_array_equal(np.asarray(st.resets),
                                  reset_mask.astype(np.int32))


def _tmp_dir():
    import tempfile

    return tempfile.mkdtemp(prefix="pod_test_ckpt_")


# -------------------------------------------------------------- checkpointing
def test_ckpt_restore_continue_equals_uninterrupted():
    """pod checkpoint -> restore -> continue == uninterrupted streaming
    (bit-equal state), including restoring onto a *different* mesh shape
    (elastic restart)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    pod = _pod(S=4, C=8, K=4, d=5)
    rng = np.random.RandomState(3)
    feed = []
    for _ in range(8):
        sids = jnp.asarray(rng.randint(0, 4, 16).astype(np.int32))
        X = jnp.asarray(rng.randn(16, 5).astype(np.float32) * 2)
        feed.append((sids, X))

    ing = jax.jit(pod.ingest)
    st_a = _admit_all(pod, pod.init(), range(4))
    for sids, X in feed:
        st_a, _ = ing(st_a, sids, X)

    st_b = _admit_all(pod, pod.init(), range(4))
    for sids, X in feed[:4]:
        st_b, _ = ing(st_b, sids, X)
    store = CheckpointStore(_tmp_dir())
    pod.save(store, 4, st_b)

    # elastic: restore onto a mesh with a different shape/axis layout
    mesh = jax.make_mesh((1,), ("data",))
    shardings = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), pod.abstract_state())
    st_c, _ = pod.restore(store, shardings=shardings)
    for sids, X in feed[4:]:
        st_c, _ = ing(st_c, sids, X)
    _tree_equal(st_a, st_c)


# ------------------------------------------------------------------ scale-out
def test_sharded_update_matches_local():
    """The shard-mapped pod program (1x1 host mesh) is bit-equal to the
    plain jitted ingest."""
    pod = _pod(S=4, C=8, K=4, d=5)
    rng = np.random.RandomState(5)
    st = _admit_all(pod, pod.init(), range(4))
    sids = jnp.asarray(rng.randint(0, 4, 20).astype(np.int32))
    X = jnp.asarray(rng.randn(20, 5).astype(np.float32) * 2)
    st_local, stats_local = jax.jit(pod.ingest)(st, sids, X)

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    upd = pod.make_sharded_update(mesh)
    with mesh:
        st_shard, stats_shard = jax.jit(upd)(st, sids, X)
    _tree_equal(st_local, st_shard)
    np.testing.assert_array_equal(np.asarray(stats_local["counts"]),
                                  np.asarray(stats_shard["counts"]))

    # the pre-routed variant (the ingest pipeline's device program):
    # routed chunks in, identical state out
    chunks, counts, unknown, overflow = jax.jit(pod.route)(st, sids, X)
    upd_pre = pod.make_sharded_update(mesh, pre_routed=True)
    with mesh:
        st_pre, stats_pre = jax.jit(upd_pre)(
            st, jnp.asarray(chunks), jnp.asarray(counts),
            jnp.asarray(unknown)[None], jnp.asarray(overflow))
    _tree_equal(st_local, st_pre)
    np.testing.assert_array_equal(np.asarray(stats_local["counts"]),
                                  np.asarray(stats_pre["counts"]))


# --------------------------------------------------- other family members fit
@pytest.mark.parametrize("name", ["sievestreaming++", "salsa"])
def test_pod_hosts_stacked_sieves(name):
    """Any sieve-family algorithm plugs into the pod unchanged."""
    algo = make(name, K=4, d=5, lengthscale=1.5, eps=0.2)
    pod = SummarizerPod(algo=algo, sessions=3, chunk=8)
    rng = np.random.RandomState(11)
    st = _admit_all(pod, pod.init(), [5, 6, 7])
    ing = jax.jit(pod.ingest)
    per = {s: [] for s in (5, 6, 7)}
    for _ in range(4):
        sids = rng.choice([5, 6, 7], 12).astype(np.int32)
        X = (rng.randn(12, 5) * 2).astype(np.float32)
        for sid, x in zip(sids, X):
            per[int(sid)].append(x)
        st, _ = ing(st, jnp.asarray(sids), jnp.asarray(X))
    ro = pod.readout(st)
    feats, n, fval = ro.feats, ro.n, ro.fval
    for i, sid in enumerate((5, 6, 7)):
        ref = jax.jit(algo.run_batched)(algo.init(),
                                        jnp.asarray(np.stack(per[sid])))
        rf, rn, rfv = algo.summary(ref)
        assert int(n[i]) == int(rn)
        np.testing.assert_array_equal(np.asarray(fval[i]), np.asarray(rfv))


def test_accept_counters_monotone_for_stacked_sieves():
    """Regression: accepts were counted as the delta of ``summary()[1]``
    (the winning rung's size) — for multi-rung algorithms the winner can
    switch to a *smaller* summary, driving the counter negative and
    firing spurious drift resets.  Counters must track monotone
    insertions instead."""
    algo = make("sievestreaming", K=8, d=32, lengthscale=3.0, eps=0.1)
    pod = SummarizerPod(algo=algo, sessions=1, chunk=32)
    st = _admit_all(pod, pod.init(), [0])
    ing = jax.jit(pod.ingest)
    rng = np.random.RandomState(0)
    base = rng.randn(1, 32).astype(np.float32)
    # 100 highly correlated items, then one orthogonal high-gain item
    # (historically flipped the winning rung to a smaller summary)
    corr = base + 0.01 * rng.randn(96, 32).astype(np.float32)
    ortho = 10.0 * rng.randn(1, 32).astype(np.float32)
    prev = 0
    for X in (corr[:32], corr[32:64], corr[64:], ortho):
        sids = jnp.zeros((len(X),), jnp.int32)
        st, _ = ing(st, sids, jnp.asarray(X))
        now = int(st.accepts[0])
        assert now >= prev, (now, prev)
        prev = now
    assert int(st.win_accepts[0]) == int(st.accepts[0]) >= 0
    # matches the algorithm's own monotone insertion count
    ref = algo.init()
    for X in (corr, ortho):
        ref = jax.jit(algo.run_batched)(ref, jnp.asarray(X))
    assert int(st.accepts[0]) == int(algo.insertions(ref))


def test_pod_hosts_quickstream_tenants():
    """The ring-buffer baseline joins the pod through the ragged-chunk
    contract (``run_batched(state, X, n_valid)`` + monotone
    ``insertions``): every session bit-equal to standalone."""
    algo = make("quickstream", K=4, d=5, lengthscale=1.5)
    pod = SummarizerPod(algo=algo, sessions=3, chunk=16)
    rng = np.random.RandomState(11)
    st = _admit_all(pod, pod.init(), [5, 6, 7])
    ing = jax.jit(pod.ingest)
    per = {s: [] for s in (5, 6, 7)}
    for _ in range(5):
        sids = rng.choice([5, 6, 7], 12).astype(np.int32)
        X = (rng.randn(12, 5) * 2).astype(np.float32)
        for sid, x in zip(sids, X):
            per[int(sid)].append(x)
        st, _ = ing(st, jnp.asarray(sids), jnp.asarray(X))
    ro = pod.readout(st)
    feats, n, fval = ro.feats, ro.n, ro.fval
    assert bool(jnp.all(st.accepts >= 0))
    for i, sid in enumerate((5, 6, 7)):
        ref = jax.jit(algo.run_batched)(algo.init(),
                                        jnp.asarray(np.stack(per[sid])))
        rf, rn, rfv = algo.summary(ref)
        assert int(n[i]) == int(rn)
        np.testing.assert_array_equal(np.asarray(feats[i]), np.asarray(rf))
        np.testing.assert_array_equal(np.asarray(fval[i]), np.asarray(rfv))
        assert int(st.accepts[i]) == int(algo.insertions(ref))


def test_drop_ledgers_accumulate_and_reset_on_admit():
    """ingest() returns what route() counts (regression: the counters
    were computed then discarded) and the PodState ledgers accumulate;
    readout surfaces them; a recycled slot starts with a clean
    per-session overflow ledger while the pod-scoped unknown ledger
    survives."""
    pod = _pod(S=2, C=2, d=6)
    st = _admit_all(pod, pod.init(), [1, 2])
    ing = jax.jit(pod.ingest)
    rng = np.random.RandomState(0)
    #                 s1 s1 s1(over) s1(over) s2  99(unknown)
    sids = jnp.asarray([1, 1, 1, 1, 2, 99], jnp.int32)
    X = jnp.asarray(rng.randn(6, 6).astype(np.float32))
    st, stats = ing(st, sids, X)
    np.testing.assert_array_equal(np.asarray(stats["dropped_overflow"]),
                                  [2, 0])
    assert int(stats["dropped_unknown"][0]) == 1
    st, stats = ing(st, sids, X)
    drops = pod.readout(st).drops
    np.testing.assert_array_equal(np.asarray(drops["overflow"]), [4, 0])
    assert int(drops["unknown"]) == 2
    # recycle slot 0: session ledger resets, pod ledger survives
    st = pod.evict(st, jnp.int32(1))
    st, slot, ok = pod.admit(st, jnp.int32(3))
    assert bool(ok) and int(slot) == 0
    drops = pod.readout(st).drops
    np.testing.assert_array_equal(np.asarray(drops["overflow"]), [0, 0])
    assert int(drops["unknown"]) == 2


def test_restore_slot_subset_into_live_pod():
    """Pod-autoscaling prerequisite: restore a *subset* of a saved pod's
    session rows into the free slots of a live pod, bit-equal, without
    touching the resident tenants — then both continue correctly."""
    pod = _pod(S=4, C=8, K=4, d=5)
    algo = pod.algo
    rng = np.random.RandomState(3)
    stA = _admit_all(pod, pod.init(), [100, 101, 102, 103])
    ing = jax.jit(pod.ingest)
    per = {s: [] for s in (100, 101, 102, 103)}
    for _ in range(6):
        sids = rng.randint(100, 104, 16).astype(np.int32)
        X = (rng.randn(16, 5) * 2).astype(np.float32)
        for sid, x in zip(sids, X):
            per[int(sid)].append(x)
        stA, _ = ing(stA, jnp.asarray(sids), jnp.asarray(X))
    store = CheckpointStore(_tmp_dir())
    pod.save(store, 1, stA, {"pod": "A"})

    # pod B is wider, hosts one resident tenant of its own
    podB = dataclasses.replace(pod, sessions=6)
    stB = _admit_all(podB, podB.init(), [500])
    ingB = jax.jit(podB.ingest)
    resB = []
    for _ in range(2):
        X = (rng.randn(4, 5) * 2).astype(np.float32)
        resB.append(X)
        stB, _ = ingB(stB, jnp.asarray([500] * 4, dtype=jnp.int32),
                      jnp.asarray(X))
    before_resident = jax.tree_util.tree_map(
        lambda l: np.asarray(l)[0], stB)

    merged, extra = podB.restore(store, slots=np.asarray([1, 3]), into=stB,
                                 saved_sessions=4)
    assert extra == {"pod": "A"}
    np.testing.assert_array_equal(np.asarray(merged.sid),
                                  [500, 101, 103, -1, -1, -1])
    # migrated rows are bit-equal to the saved pod's rows
    for (pa, la), lb in zip(jax.tree_util.tree_leaves_with_path(stA),
                            jax.tree_util.tree_leaves(merged)):
        np.testing.assert_array_equal(
            np.asarray(la)[[1, 3]], np.asarray(lb)[[1, 2]],
            err_msg=f"leaf {jax.tree_util.keystr(pa)} differs")
    # the resident tenant's row is untouched
    for (pa, la), lb in zip(
            jax.tree_util.tree_leaves_with_path(before_resident),
            jax.tree_util.tree_leaves(merged)):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb)[0],
            err_msg=f"leaf {jax.tree_util.keystr(pa)} differs")

    # migrated sessions continue bit-equal to standalone run_batched
    extra_items = {101: [], 103: []}
    for _ in range(3):
        sids = np.asarray([101, 103] * 4, np.int32)
        X = (rng.randn(8, 5) * 2).astype(np.float32)
        for sid, x in zip(sids, X):
            extra_items[int(sid)].append(x)
        merged, _ = ingB(merged, jnp.asarray(sids), jnp.asarray(X))
    ro = podB.readout(merged)
    feats, n, fval, active = ro.feats, ro.n, ro.fval, ro.active
    for sid, slot in ((101, 1), (103, 2)):
        Xs = jnp.asarray(np.stack(per[sid] + extra_items[sid]))
        ref = jax.jit(algo.run_batched)(algo.init(), Xs)
        rf, rn, rfv = algo.summary(ref)
        assert int(n[slot]) == int(rn), f"session {sid}"
        np.testing.assert_array_equal(np.asarray(feats[slot]),
                                      np.asarray(rf))

    # a duplicated slot index must not double-host the session
    st_dup = _admit_all(podB, podB.init(), [500])
    dup, _ = podB.restore(store, slots=np.asarray([2, 2, 2]), into=st_dup,
                          saved_sessions=4)
    assert int(jnp.sum(dup.sid == 102)) == 1
    assert int(jnp.sum(dup.active)) == 2

    # a clashing restore (101 already live) must refuse
    with pytest.raises(ValueError, match="already live"):
        podB.restore(store, slots=np.asarray([1]), into=merged,
                     saved_sessions=4)
    # bool-mask selection + free-slot shortage must refuse
    with pytest.raises(ValueError, match="free slots"):
        full = _admit_all(pod, pod.init(), [900, 901, 902, 903])
        pod.restore(store, slots=np.ones(4, bool), into=full)


def test_admit_rejects_negative_session_id():
    """-1 is the free-slot / queue-padding sentinel: admitting it would
    route every padding item of every ragged batch into that session."""
    pod = _pod(S=2)
    st = pod.init()
    st, _, ok = pod.admit(st, jnp.int32(-1))
    assert not bool(ok)
    assert int(jnp.sum(st.active)) == 0
