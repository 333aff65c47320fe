"""Many small tenants on one pod: slot lookup and admission that scale
with the items, not the tenants, and a served path that drains a backlog
left in few sessions without overflowing them.

Two references are kept here as the code stood before: the plain (N, S)
id match the routing used, and the admit that wrote every slot through a
masked select over an (S,)-stacked fresh copy.  ``share_slots``,
``SummarizerPod.route`` and ``SummarizerPod.admit`` are pinned bit-equal
to them on seeded cases.
"""
import dataclasses
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import make
from repro.core.sieve_family import stack_states, tree_select
from repro.ingest import PAD_SID, IngestPipeline, ReplaySource, TaggedBuffer
from repro.ingest.pipeline import fill_chunks, live_table, share_slots
from repro.serve import SummarizerPod


# ----------------------------------------------------------------- references
def _match_route(sid_table, active, sids, X, chunk):
    """The routing as it was: an (N, S) match of ids against the table."""
    S, C = len(sid_table), chunk
    N = len(sids)
    sids = np.asarray(sids, np.int32)
    match = (sids[:, None] == sid_table[None, :]) & active[None, :]
    found = match.any(axis=1)
    slot = np.where(found, match.argmax(axis=1), S)
    order = np.argsort(slot, kind="stable")
    seg_start = np.searchsorted(slot[order], slot[order], side="left")
    pos = np.empty((N,), np.int64)
    pos[order] = np.arange(N, dtype=np.int64) - seg_start
    keep = found & (pos < C)
    chunks = np.zeros((S, C) + X.shape[1:], X.dtype)
    chunks[slot[keep], pos[keep]] = X[keep]
    counts = np.bincount(slot[keep], minlength=S).astype(np.int32)
    unknown = np.int32((~found & (sids >= 0)).sum())
    over = found & (pos >= C)
    overflow = np.bincount(slot[over], minlength=S).astype(np.int32)
    return (chunks, counts, unknown, overflow), (slot, pos, found)


def _admit_masked(pod, state, session_id, spec=None):
    """The admit as it was: every slot selected against a fresh copy."""
    hyper = pod._hyper_of(spec)
    sess = jnp.asarray(session_id, jnp.int32)
    existing = state.active & (state.sid == sess)
    present = jnp.any(existing)
    free = ~state.active
    slot = jnp.where(present, jnp.argmax(existing), jnp.argmax(free))
    if hyper is None:
        spec_ok = jnp.bool_(True)
    else:
        row = jax.tree_util.tree_map(lambda l: l[slot], state.algo.hp)
        eq = [jnp.all(a == b) for a, b in zip(
            jax.tree_util.tree_leaves(row), jax.tree_util.tree_leaves(hyper))]
        spec_ok = jnp.where(present, jnp.all(jnp.stack(eq)), True)
    ok = (sess >= 0) & jnp.where(present, spec_ok, jnp.any(free))
    hot = (jnp.arange(pod.sessions) == slot) & ok & ~present
    one = pod.algo.init() if hyper is None else pod.algo.init(hyper)
    z = jnp.zeros((pod.sessions,), jnp.int32)
    state = dataclasses.replace(
        state,
        algo=tree_select(hot, stack_states(one, pod.sessions), state.algo),
        sid=jnp.where(hot, sess, state.sid),
        active=state.active | hot,
        items=jnp.where(hot, z, state.items),
        accepts=jnp.where(hot, z, state.accepts),
        win_items=jnp.where(hot, z, state.win_items),
        win_accepts=jnp.where(hot, z, state.win_accepts),
        resets=jnp.where(hot, z, state.resets),
        drops_overflow=jnp.where(hot, z, state.drops_overflow),
    )
    return state, slot, ok


def _tree_equal(a, b, msg=""):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a), lb):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"{msg} leaf {jax.tree_util.keystr(path)}")


# -------------------------------------------------------------------- lookup
def _slot_table(rng, S):
    """Live slots, free slots (id -1) and freed slots that kept a stale
    id; from S >= 4 on, one live id is held by two live slots."""
    ids = (rng.permutation(8 * S + 8)[:S] + 1).astype(np.int32)
    active = rng.rand(S) < 0.7
    active[rng.randint(S)] = True
    sid = np.where(active, ids, -1).astype(np.int32)
    stale = ~active & (rng.rand(S) < 0.5)
    sid[stale] = ids[stale]
    live = np.flatnonzero(active)
    if len(live) >= 2 and S >= 4:
        sid[live[-1]] = sid[live[0]]
    return sid, active


def _tagged(rng, sid, active, N, C, d=3):
    """Items for live, stale and unknown ids and padding, with one live
    session sent more than its chunk of C."""
    live = sid[active]
    stale = sid[~active & (sid >= 0)]
    pool = [live, live, live, np.asarray([PAD_SID, 10 ** 6, -7], np.int32)]
    if len(stale):
        pool.append(stale)
    pool = np.concatenate(pool)
    sids = rng.choice(pool, N).astype(np.int32)
    sids[rng.choice(N, min(C + 3, N), replace=False)] = live[0]
    X = rng.randn(N, d).astype(np.float32)
    X[:, 0] = np.arange(N)
    return sids, X


def _device_route(sid_table, active, sids, X, chunk):
    """``jax.jit(SummarizerPod.route)`` over this slot table, on numpy."""
    algo = make("threesieves", K=2, d=X.shape[1], lengthscale=1.0)
    pod = SummarizerPod(algo=algo, sessions=len(sid_table), chunk=chunk)
    state = dataclasses.replace(pod.init(), sid=jnp.asarray(sid_table),
                                active=jnp.asarray(active))
    return [np.asarray(a) for a in jax.jit(pod.route)(
        state, jnp.asarray(sids), jnp.asarray(X))]


@pytest.mark.parametrize("S", [1, 2, 5, 64, 512, 4096])
@pytest.mark.parametrize("seed", [0, 1])
def test_lookup_bit_equals_the_plain_match(S, seed):
    """``share_slots`` finds every item's slot as the (N, S) match did,
    and ``SummarizerPod.route`` gives what it gave — chunks, counts,
    unknown and overflow — with inactive slots, stale ids on freed
    slots, a live id on two slots, unknown ids, PAD_SID and overflow."""
    rng = np.random.RandomState(1000 * seed + S)
    C = 4
    sid, active = _slot_table(rng, S)
    N = min(4 * S + 16, 4096)
    sids, X = _tagged(rng, sid, active, N, C)
    want, (w_slot, _, _) = _match_route(sid, active, sids, X, C)
    assert want[3].sum() > 0  # the cases hold an overflow
    if S >= 2:
        assert int(want[2]) > 0  # ... and unknown ids

    np.testing.assert_array_equal(
        share_slots(live_table(sid, active), sids, S), w_slot)
    for g, w in zip(_device_route(sid, active, sids, X, C), want):
        assert g.dtype == np.asarray(w).dtype
        np.testing.assert_array_equal(g, w)


def test_lookup_with_no_live_session():
    """An empty pod: every item is unknown or padding, nothing routed."""
    sid = np.asarray([-1, 5, -1], np.int32)  # slot 1 freed, id stale
    active = np.zeros(3, bool)
    sids = np.asarray([5, PAD_SID, 9, 5], np.int32)
    X = np.ones((4, 2), np.float32)
    want, _ = _match_route(sid, active, sids, X, 2)
    for g, w in zip(_device_route(sid, active, sids, X, 2), want):
        np.testing.assert_array_equal(g, w)
    assert int(want[2]) == 3 and want[1].sum() == 0


# ------------------------------------------------------------------- admit
def _scan_admits(fn, state, sids, rows):
    @jax.jit
    def go(state, sids, rows):
        def body(st, xs):
            st, slot, ok = fn(st, xs[0], xs[1])
            return st, (slot, ok)

        return jax.lax.scan(body, state, (sids, rows))

    return go(state, sids, rows)


def _plans(pod):
    return [pod.algo.hyper(K=2, T=3, eps=0.2, lengthscale=1.0),
            pod.algo.hyper(K=3, T=5, eps=0.1, lengthscale=0.7),
            pod.algo.hyper(K=4, T=7, eps=0.3, lengthscale=1.5)]


def _rows(plans, which):
    return jax.tree_util.tree_map(
        lambda *v: jnp.stack(v), *[plans[i] for i in which])


def test_admit_writes_one_slot_like_the_masked_select():
    """A jitted scan of ``admit`` over mixed specs, re-admits (same spec:
    idempotent; another spec: refused), a negative id and a full pod
    gives the masked-select admit's state leaf for leaf, and its slots
    and oks; so does a second scan into slots freed by an eviction."""
    S, d = 8, 5
    pod = SummarizerPod(algo=make("threesieves", K=4, d=d, lengthscale=1.0,
                                  eps=0.1, T=5), sessions=S, chunk=8)
    plans = _plans(pod)
    new = lambda st, s, r: pod.admit(st, s, spec=r)  # noqa: E731
    old = lambda st, s, r: _admit_masked(pod, st, s, spec=r)  # noqa: E731
    sids1 = [10, 11, 12, 13, 14, 11, 12, -3, 15, 16, 17, 18, 10]
    plan1 = [0, 1, 2, 0, 1, 1, 0, 2, 2, 0, 1, 2, 0]
    want_ok = [1, 1, 1, 1, 1, 1, 0, 0, 1, 1, 1, 0, 1]
    args = (jnp.asarray(sids1, jnp.int32), _rows(plans, plan1))
    st_new, (slot_new, ok_new) = _scan_admits(new, pod.init(), *args)
    st_old, (slot_old, ok_old) = _scan_admits(old, pod.init(), *args)
    np.testing.assert_array_equal(np.asarray(ok_new), want_ok)
    np.testing.assert_array_equal(np.asarray(ok_new), np.asarray(ok_old))
    np.testing.assert_array_equal(np.asarray(slot_new),
                                  np.asarray(slot_old))
    _tree_equal(st_new, st_old, "first scan")

    # items and drops on the live slots, then two evictions: the second
    # scan recycles slots whose rows are not fresh
    rng = np.random.RandomState(3)
    sids = rng.choice(np.asarray(sids1[:5] + [15, 16, 17], np.int32), 40)
    X = jnp.asarray(rng.randn(40, d).astype(np.float32))
    st, _ = jax.jit(pod.ingest)(st_new, jnp.asarray(sids, jnp.int32), X)
    st = pod.evict_sids(st, jnp.asarray([11, 14], jnp.int32))
    sids2, plan2 = [20, 21, 22, 13, 20], [2, 1, 0, 0, 0]
    args = (jnp.asarray(sids2, jnp.int32), _rows(plans, plan2))
    st_new, (slot_new, ok_new) = _scan_admits(new, st, *args)
    st_old, (slot_old, ok_old) = _scan_admits(old, st, *args)
    # 20, 21 take the freed slots 1 and 4; 22 finds the pod full; 13,
    # which holds items, is re-admitted on its own plan (its state kept)
    # and 20 on another (refused)
    np.testing.assert_array_equal(np.asarray(ok_new), [1, 1, 0, 1, 0])
    assert int(st_new.items[3]) > 0
    np.testing.assert_array_equal(np.asarray(slot_new)[:2], [1, 4])
    np.testing.assert_array_equal(np.asarray(ok_new), np.asarray(ok_old))
    np.testing.assert_array_equal(np.asarray(slot_new),
                                  np.asarray(slot_old))
    _tree_equal(st_new, st_old, "second scan")


def test_admit_without_spec_like_the_masked_select():
    """The pod-default spec: a scan over new ids, a re-admit, padding and
    a full pod, against the masked select."""
    pod = SummarizerPod(algo=make("threesieves", K=3, d=4, lengthscale=1.0),
                        sessions=3, chunk=4)

    def scan(fn):
        @jax.jit
        def go(sids):
            def body(st, s):
                st, slot, ok = fn(st, s)
                return st, (slot, ok)

            return jax.lax.scan(body, pod.init(), sids)

        return go(jnp.asarray([5, 6, 5, -1, 7, 8], jnp.int32))

    st_new, (slot_new, ok_new) = scan(pod.admit)
    st_old, (slot_old, ok_old) = scan(
        lambda st, s: _admit_masked(pod, st, s))
    np.testing.assert_array_equal(np.asarray(ok_new), [1, 1, 1, 0, 1, 0])
    np.testing.assert_array_equal(np.asarray(ok_new), np.asarray(ok_old))
    np.testing.assert_array_equal(np.asarray(slot_new),
                                  np.asarray(slot_old))
    _tree_equal(st_new, st_old)


# ------------------------------------------- the buffer's per-session cap
def test_get_caps_each_session_and_keeps_fifo():
    """``per_session`` ends a queue's turns at the cap: no session gives
    more, each gives its oldest items, and a cap no queue reaches leaves
    the batch as it was."""
    rng = np.random.RandomState(5)
    sids = np.concatenate([np.full(30, 7), rng.choice([1, 2, 3], 12),
                           np.full(9, 7)]).astype(np.int32)
    X = np.arange(len(sids), dtype=np.float32)[:, None].repeat(2, 1)

    def filled():
        buf = TaggedBuffer(capacity=100, policy="block")
        buf.put(sids, X)
        return buf

    s, x = filled().get(64, per_session=5)
    assert np.bincount(s).max() <= 5 and len(s) < 64
    for sid in np.unique(s):
        np.testing.assert_array_equal(x[s == sid, 0],
                                      X[sids == sid, 0][:(s == sid).sum()])
    a, b = filled(), filled()
    for _ in range(3):  # until drained: the cap never binds
        ga, gb = a.get(20), b.get(20, per_session=39)
        np.testing.assert_array_equal(ga[0], gb[0])
        np.testing.assert_array_equal(ga[1], gb[1])


def test_pipeline_drains_a_concentrated_backlog_without_overflow():
    """A stream whose tail sits in one session: the buffer's last batches
    hold more of it than the pod's chunk, and the pipeline still routes
    every item, in order, over more batches."""
    S, C, d = 4, 8, 3
    pod = SummarizerPod(algo=make("threesieves", K=3, d=d, lengthscale=1.0,
                                  T=4), sessions=S, chunk=C)
    st = pod.init()
    for s in range(S):
        st, _, _ = pod.admit(st, jnp.int32(s))
    rng = np.random.RandomState(8)
    sids = np.concatenate([rng.randint(0, S, 40),
                           np.full(3 * C, 2)]).astype(np.int32)
    X = rng.randn(len(sids), d).astype(np.float32)
    buf = TaggedBuffer(capacity=len(sids), policy="block")
    buf.put(sids, X)
    buf.close()
    pipe = IngestPipeline(pod, buffer=buf, batch=2 * C)
    st, stats = pipe.run(st)
    assert stats["dropped_overflow"] == 0 and stats["dropped_unknown"] == 0
    np.testing.assert_array_equal(np.asarray(st.items),
                                  np.bincount(sids, minlength=S))
    assert stats["batches"] > len(sids) // (2 * C)


# ---------------------------------------- one copy from the buffer's store
DIRECT_PODS = {"ts256": (16, 32), "many": (256, 4)}  # name -> (S, C)
DIRECT_CASES = ("quiesced", "unknown", "capped", "uncapped", "closed")


@pytest.mark.parametrize("case", DIRECT_CASES)
@pytest.mark.parametrize("pod", sorted(DIRECT_PODS))
def test_direct_fill_bit_equals_host_route_of_get(pod, case):
    """Two buffers fed alike: the shares ``lease`` takes from one,
    copied by ``fill_chunks`` into one reused chunk array, give batch
    after batch what ``jit(SummarizerPod.route)`` gives for ``get``'s
    padded batch of the other — chunks, counts, unknown and overflow,
    dtypes included — while the shares shrink, so rows held last time
    must be zeroed, and both buffers free the same slots.  ``quiesced``
    parks a session, ``unknown`` sends an evicted id (freed slot, stale
    id), an id never admitted and a negative id, ``capped`` /
    ``uncapped`` a backlog of three chunks in one session with and
    without the per-session cap (overflow), ``closed`` drains to a
    partial last batch."""
    S, C = DIRECT_PODS[pod]
    B = S * C // 2
    cap = None if case == "uncapped" else C
    rng = np.random.RandomState(len(pod) * 100 + DIRECT_CASES.index(case))
    sid_table = (np.arange(S) * 3 + 100).astype(np.int32)
    active = np.ones(S, bool)
    sessions = list(sid_table)
    if case == "unknown":
        active[1] = False  # evicted: the freed slot keeps a stale id
        sessions += [7, -7]
    bufs = [TaggedBuffer(capacity=8 * B, policy="block") for _ in range(2)]
    algo = make("threesieves", K=2, d=3, lengthscale=1.0)
    dev = SummarizerPod(algo=algo, sessions=S, chunk=C)
    state = dataclasses.replace(dev.init(), sid=jnp.asarray(sid_table),
                                active=jnp.asarray(active))
    route = jax.jit(dev.route)
    chunks = np.zeros((S, C, 3), np.float32)
    held = np.zeros((S,), np.int64)
    zeroed, unknown, overflow = [], 0, 0
    puts = [2 * B, B // 2, B // 8, B // 32]
    for step in range(len(puts) + (8 if case == "closed" else 0)):
        if step < len(puts):
            sids = rng.choice(np.asarray(sessions, np.int32), puts[step])
            if step == 0 and case in ("capped", "uncapped"):
                sids = np.concatenate([np.full(3 * C, sid_table[2]), sids])
            X = rng.randn(len(sids), 3).astype(np.float32)
            X[:, 0] = step * 10 ** 5 + np.arange(len(sids))
            for buf in bufs:
                buf.put(sids.astype(np.int32), X)
                if case == "quiesced" and step == 0:
                    buf.quiesce([sid_table[3]])
                if case == "closed" and step == len(puts) - 1:
                    buf.close()
        got = bufs[0].get(B, pad_to=B, per_session=cap)
        lease = bufs[1].lease(B, per_session=cap)
        if got is None:
            assert case == "closed" and lease is None
            break
        want = [np.asarray(a) for a in route(state, *got)]
        with lease:
            slot = share_slots(live_table(sid_table, active), lease.sids, S)
            *have, z = fill_chunks(lease, slot, chunks, held)
        zeroed.append(z)
        last = lease.items
        unknown += int(want[2])
        overflow += int(want[3].sum())
        for h, w in zip([chunks] + have, want):
            assert np.asarray(h).dtype == np.asarray(w).dtype
            np.testing.assert_array_equal(h, w, err_msg=f"batch {step}")
        np.testing.assert_array_equal(held, want[1])
        a, b = bufs
        assert a.size == b.size and a.depths() == b.depths()
        np.testing.assert_array_equal(a._free[:a._nfree], b._free[:b._nfree])
    assert len(zeroed) >= 4 and max(zeroed) > 0
    assert (unknown > 0) == (case == "unknown")
    assert (overflow > 0) == (case == "uncapped")
    if case == "closed":
        assert got is None and 0 < last < B  # then the end of the stream
    if case == "quiesced":
        assert bufs[1].depths()[int(sid_table[3])] > 0


# ------------------------------------------------- many small sessions served
def test_many_small_sessions_served_match_their_own_run():
    """512 sessions of K <= 4 at d=16, chunk 8, batches of S*C/2 through
    TaggedBuffer -> IngestPipeline on the fused pod step (Pallas
    interpreter): every session's summary is, bit for bit, its own
    ``run_batched`` over the items it was sent."""
    S, C, d = 512, 8, 16
    B = S * C // 2
    algo = make("threesieves", K=4, d=d, lengthscale=1.0, eps=0.1, T=6,
                backend="pallas-interpret")
    pod = SummarizerPod(algo=algo, sessions=S, chunk=C,
                        podstep_backend="pallas-interpret")
    plans = _plans(pod)
    which = np.arange(S) % len(plans)
    st, (_, ok) = _scan_admits(
        lambda st, s, r: pod.admit(st, s, spec=r), pod.init(),
        jnp.arange(S, dtype=jnp.int32), _rows(plans, which))
    assert bool(np.asarray(ok).all())

    rng = np.random.RandomState(17)
    N = 3 * B + 300
    sids = rng.randint(0, S, N).astype(np.int32)
    X = (rng.randn(N, d) / 4).astype(np.float32)
    buf = TaggedBuffer(capacity=2 * B, policy="block")
    pipe = IngestPipeline(pod, buffer=buf, batch=B, min_fill=B)
    pipe.feed_from(ReplaySource(sids=sids, X=X, batch=1024))
    st, stats = pipe.run(st)
    assert pipe.exhausted
    assert stats["items"] == N and stats["dropped_overflow"] == 0
    ro = pod.readout(st)

    # each session alone over its items, one at a time (no vmap)
    per = [X[sids == s] for s in range(S)]
    L = max(len(p) for p in per)
    Xs = np.zeros((S, L, d), np.float32)
    for s, p in enumerate(per):
        Xs[s, :len(p)] = p
    n_valid = np.asarray([len(p) for p in per], np.int32)
    init = jax.vmap(algo.init)(_rows(plans, which))
    solo = jax.jit(lambda st, x, n: jax.lax.map(
        lambda a: algo.summary(algo.run_batched(*a)), (st, x, n)))(
        init, jnp.asarray(Xs), jnp.asarray(n_valid))
    feats, n, fval = (np.asarray(v) for v in solo)
    np.testing.assert_array_equal(np.asarray(ro.n), n)
    np.testing.assert_array_equal(np.asarray(ro.fval), fval)
    np.testing.assert_array_equal(np.asarray(ro.feats), feats)
    assert n.min() >= 1 and (n == 4).any()


# ------------------------------------------- the many-tenant cell, tiny
ROOT = Path(__file__).resolve().parents[1]
SPLIT = ("slot_lookup_ms_per_batch", "scatter_ms_per_batch")


def _harness():
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench import harness

    return harness


def _reader(name):
    return _harness().load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                                  "test_metric_" + name)


@pytest.fixture
def recorder():
    from repro import obs

    rec = obs.get_recorder()
    rec.clear()
    yield rec
    rec.clear()


def _ctx(batches):
    bounds = [types.SimpleNamespace(batches=b) for b in batches]
    return {"cell": types.SimpleNamespace(name="cell-a"), "bounds": bounds,
            "n_window": len(bounds) - 2}


def test_route_split_readers_by_hand(recorder):
    """The window's runs (warm-up and drain left out), per batch; None
    for a program whose route is one stage, not a zero."""
    def runs(split):
        for batches, lookup, scatter in [(1, 9.0, 9.0), (2, 0.01, 0.3),
                                         (2, 0.03, 0.1), (1, 9.0, 9.0)]:
            with recorder.span("ingest_run", pod="cell-a") as sp:
                sp.set(batches=batches, ingest_get_s=0.1,
                       ingest_route_s=lookup + scatter)
                if split:
                    sp.set(ingest_slot_lookup_s=lookup,
                           ingest_scatter_s=scatter)

    ctx = _ctx([1, 2, 2, 1])
    runs(split=False)
    assert [_reader(n).read(ctx) for n in SPLIT] == [None, None]
    recorder.clear()
    runs(split=True)
    got = [_reader(n).read(ctx) for n in SPLIT]
    assert got == pytest.approx([10.0, 100.0])


def test_tiny_many_tenant_cell_is_correct(monkeypatch):
    """The ts4096-many cell's own traffic, batch fill and buffer at 64
    sessions of chunk 4 (Pallas interpreter): every item is routed and
    summarized, the drain overflows no session, the summaries match the
    plain reference, and the traced run reports the route's split."""
    harness = _harness()
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    cell = harness.load_cell("ts4096-many")
    assert cell.config["batch_fill"] == 0.5
    cell.config = dict(cell.config, sessions=64, chunk=4, K_max=4, d=16,
                       lengthscale=4.0, plans=[[2, 5, 0.2], [3, 5, 0.2],
                                               [4, 10, 0.2]],
                       default_plan=[4, 10, 0.2], sample_sessions=16)
    cell.traffic = dict(cell.traffic, pool_items=4096, put_items=64,
                        batches_per_round=2)
    out = harness.run(cell, 2 ** 33 + 5, 1.5, interpret=True, trace=True)
    checks = out["checks"]
    assert out["correct"], checks
    assert checks["drops"]["value"] == 0
    assert checks["routing_gap"]["value"] == 0
    assert out["failed"] == 0 and out["info"]["compiles_in_window"] == 0
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(SPLIT) <= set(m) and all(m[k] >= 0 for k in SPLIT)
