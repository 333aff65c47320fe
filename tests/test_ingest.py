"""repro.ingest: sources, buffer backpressure, host routing, pipeline.

The two load-bearing claims, pinned property-style:

  * ordering — per-session FIFO survives everything between a producer
    and the pod: ragged batches, repacking across chunk boundaries,
    buffer fairness rotation, and the drop policies (survivors stay in
    order; only *which* items survive changes);
  * equivalence — the device ``route`` is bit-equal to the plain
    first-live-slot match, and the double-buffered pipeline is
    bit-equal to the synchronous ingest loop on the same stream.

Socket tests carry a ``timeout`` mark (enforced by pytest-timeout when
installed) *and* socket-level timeouts inside ``SocketSource`` itself,
so a dead socket fails fast rather than hanging CI either way.
"""
import collections
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.api import make
from repro.ingest import (PAD_SID, DriftSource, IngestPipeline, RateLimit,
                          ReplaySource, ShedPolicy, SocketSource,
                          SubsampleSource, TaggedBuffer, TokenBucket,
                          connect_producer, send_frame)
from repro.serve import SummarizerPod
from test_many_tenants import _match_route

D = 5


def _pod(S=4, C=8, K=4, **kw):
    algo = make("threesieves", K=K, d=D, lengthscale=1.5, eps=0.1,
                T=kw.pop("T", 11), **kw)
    return SummarizerPod(algo=algo, sessions=S, chunk=C)


def _admit_all(pod, state, sids):
    for sid in sids:
        state, _, ok = pod.admit(state, jnp.int32(sid))
        assert bool(ok)
    return state


def _tagged(rng, n, sessions, d=D):
    sids = rng.choice(np.asarray(sessions, np.int32), n)
    X = rng.randn(n, d).astype(np.float32)
    # a distinct per-item fingerprint so order checks are unambiguous
    X[:, 0] = np.arange(n, dtype=np.float32)
    return sids.astype(np.int32), X


def _per_session(sids, X):
    return {int(s): X[sids == s] for s in np.unique(sids)}


def _closed_buffer(feed):
    """A buffer holding ``feed``'s tagged batches, put in order, then
    closed: a replay whose device batches are fixed before any run."""
    buf = TaggedBuffer(capacity=max(1, sum(len(s) for s, _ in feed)),
                       policy="block")
    for sids, X in feed:
        buf.put(sids, X)
    buf.close()
    return buf


# -------------------------------------------------------------------- sources
def test_replay_source_slices_and_concatenates(tmp_path):
    rng = np.random.RandomState(0)
    sids, X = _tagged(rng, 23, [1, 2, 3])
    src = ReplaySource(sids=sids, X=X, batch=10)
    got = list(src)
    assert [len(s) for s, _ in got] == [10, 10, 3]
    np.testing.assert_array_equal(np.concatenate([s for s, _ in got]), sids)
    np.testing.assert_array_equal(np.concatenate([x for _, x in got]), X)
    # .npy paths load identically
    np.save(tmp_path / "s.npy", sids)
    np.save(tmp_path / "x.npy", X)
    src2 = ReplaySource(sids=tmp_path / "s.npy", X=tmp_path / "x.npy",
                        batch=10)
    for (a, b), (c, d) in zip(src, src2):
        np.testing.assert_array_equal(a, c)
        np.testing.assert_array_equal(b, d)
    # from_batches round-trips a ragged feed
    src3 = ReplaySource.from_batches(got)
    np.testing.assert_array_equal(
        np.concatenate([s for s, _ in src3]), sids)


def test_drift_source_is_deterministic_and_bounded():
    a = list(DriftSource(seed=7, n_sessions=3, batch=12, d=D, n_batches=4))
    b = list(DriftSource(seed=7, n_sessions=3, batch=12, d=D, n_batches=4))
    assert len(a) == 4
    for (sa, xa), (sb, xb) in zip(a, b):
        np.testing.assert_array_equal(sa, sb)
        np.testing.assert_array_equal(xa, xb)
    # and it really is the session_stream generator underneath
    from repro.data.streams import MixtureSpec, session_stream

    gen = session_stream(7, MixtureSpec(n_components=8, d=D, spread=4.0,
                                        noise=0.5), 3, 12, as_numpy=True)
    sg, xg = next(gen)
    np.testing.assert_array_equal(a[0][0], sg)
    np.testing.assert_array_equal(a[0][1], xg)


def test_subsample_source_thins_in_order():
    rng = np.random.RandomState(1)
    sids, X = _tagged(rng, 60, [1, 2])
    inner = ReplaySource(sids=sids, X=X, batch=16)
    # rate=1 is the identity
    full = list(SubsampleSource(inner=inner, rate=1.0, seed=3))
    np.testing.assert_array_equal(np.concatenate([s for s, _ in full]), sids)
    # thinned: a deterministic, order-preserving per-session subsequence
    t1 = list(SubsampleSource(inner=inner, rate=0.4, seed=3))
    t2 = list(SubsampleSource(inner=inner, rate=0.4, seed=3))
    s1 = np.concatenate([s for s, _ in t1])
    x1 = np.concatenate([x for _, x in t1])
    np.testing.assert_array_equal(s1, np.concatenate([s for s, _ in t2]))
    assert 0 < len(s1) < len(sids)
    whole = _per_session(sids, X)
    for s, xs in _per_session(s1, x1).items():
        fingerprints = xs[:, 0]
        ref = whole[s][:, 0]
        # subsequence: fingerprints appear in ref in the same order
        idx = np.searchsorted(ref, fingerprints)
        np.testing.assert_array_equal(ref[idx], fingerprints)
        assert np.all(np.diff(idx) > 0)


# --------------------------------------------------------------------- buffer
@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 7))
def test_buffer_fifo_per_session_across_chunks(seed, get_size):
    """Lossless regime: whatever the put chunking and get sizing, each
    session's items come out exactly in the order they went in."""
    rng = np.random.RandomState(seed)
    sids, X = _tagged(rng, 50, [3, 4, 5])
    buf = TaggedBuffer(capacity=128, policy="block")
    for lo in range(0, 50, 13):  # ragged put chunks
        buf.put(sids[lo:lo + 13], X[lo:lo + 13])
    buf.close()
    out_s, out_x = [], []
    while True:
        got = buf.get(get_size)
        if got is None:
            break
        out_s.append(got[0])
        out_x.append(got[1])
    out_s = np.concatenate(out_s)
    out_x = np.concatenate(out_x)
    assert len(out_s) == 50 and not buf.drop_counts()
    want = _per_session(sids, X)
    for s, xs in _per_session(out_s, out_x).items():
        np.testing.assert_array_equal(xs, want[s])


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from(["drop-oldest",
                                                "drop-newest"]))
def test_buffer_drop_policies_preserve_order_and_count(seed, policy):
    """Clipped regime: survivors of either drop policy are an ordered
    subsequence per session, and every clipped item is counted against
    the right session (Stream Clipper's accounting)."""
    rng = np.random.RandomState(seed)
    sids, X = _tagged(rng, 60, [1, 2, 3])
    buf = TaggedBuffer(capacity=16, policy=policy)
    dropped = 0
    for lo in range(0, 60, 10):
        dropped += buf.put(sids[lo:lo + 10], X[lo:lo + 10])
    buf.close()
    out_s, out_x = [], []
    while True:
        got = buf.get(8)
        if got is None:
            break
        out_s.append(got[0])
        out_x.append(got[1])
    out_s = np.concatenate(out_s)
    out_x = np.concatenate(out_x)
    drops = buf.drop_counts()
    assert dropped == sum(drops.values()) == 60 - len(out_s) > 0
    whole = _per_session(sids, X)
    for s, xs in _per_session(out_s, out_x).items():
        ref = whole[s][:, 0]
        fp = xs[:, 0]
        idx = np.searchsorted(ref, fp)
        np.testing.assert_array_equal(ref[idx], fp)  # ordered subsequence
        assert np.all(np.diff(idx) > 0)
        lost = len(whole[s]) - len(xs)
        assert drops.get(s, 0) == lost
        if policy == "drop-newest" and lost:
            # survivors are exactly the earliest accepted items
            assert fp[0] == ref[0]


def test_buffer_drop_oldest_clips_the_longest_queue():
    buf = TaggedBuffer(capacity=4, policy="drop-oldest")
    buf.put([7, 7, 7, 8], np.arange(4, dtype=np.float32)[:, None])
    buf.put([8], np.asarray([[9.0]], np.float32))  # clips 7's head
    assert buf.drop_counts() == {7: 1}
    s, x = buf.get(8)
    np.testing.assert_array_equal(sorted(s.tolist()), [7, 7, 8, 8])
    sev = x[s == 7][:, 0]
    np.testing.assert_array_equal(sev, [1.0, 2.0])  # head (0.0) clipped


def test_token_bucket_refills_against_injected_clock():
    b = TokenBucket(RateLimit(rate=2.0, burst=2.0), now=0.0)
    assert b.allow(0.0) and b.allow(0.0)  # burst spent
    assert not b.allow(0.0)
    assert not b.allow(0.4)  # 0.8 tokens — still short
    assert b.allow(0.5)  # 1.0 token refilled
    assert b.allow(10.0) and b.allow(10.0)  # refill caps at burst
    assert not b.allow(10.0)


def test_buffer_rate_limit_throttles_and_counts_separately():
    clock = [0.0]
    buf = TaggedBuffer(capacity=64, rate_limit=RateLimit(rate=1.0, burst=2.0),
                       clock=lambda: clock[0])
    sids = [1] * 5 + [2]
    rejected = buf.put(sids, np.zeros((6, 2), np.float32))
    assert rejected == 3  # session 1 over its burst of 2; session 2 fine
    assert buf.throttled_counts() == {1: 3}
    assert buf.total_throttled() == 3
    assert buf.total_drops() == 0  # throttles are NOT overflow drops
    assert buf.size == 3
    clock[0] = 3.0  # three tokens refilled
    assert buf.put([1, 1, 1], np.zeros((3, 2), np.float32)) == 1
    assert buf.throttled_counts() == {1: 4}


def test_buffer_per_session_rate_override():
    clock = [0.0]
    buf = TaggedBuffer(capacity=64, rate_limit=RateLimit(rate=1.0, burst=1.0),
                       clock=lambda: clock[0])
    buf.set_rate_limit(7, RateLimit(rate=100.0, burst=10.0))
    buf.set_rate_limit(8, None)  # exempt entirely
    rejected = buf.put([6, 6, 7, 7, 7, 8, 8, 8],
                       np.zeros((8, 2), np.float32))
    assert rejected == 1
    assert buf.throttled_counts() == {6: 1}


def test_shed_policy_ladder_rungs_and_fair_share():
    p = ShedPolicy(lo=0.5, hi=0.8, seed=0)
    assert p.rung(0, 100) == "admit"
    assert p.rung(49, 100) == "admit"
    assert p.rung(50, 100) == "subsample"
    assert p.rung(80, 100) == "clip"
    assert p.fair_share(100, 4) == pytest.approx(12.5)
    assert p.fair_share(100, 0) == pytest.approx(50.0)  # empty: lo * cap
    # under fair share every rung admits, deterministically
    for size in (50, 90):
        ok, rung = p.decide(size=size, capacity=100, depth=3, n_live=4)
        assert ok and rung == ("subsample" if size < 80 else "clip")


def test_buffer_shed_ladder_spares_under_share_sessions():
    buf = TaggedBuffer(capacity=16, policy="drop-newest",
                       shed=ShedPolicy(lo=0.25, hi=0.6, p_floor=0.01,
                                       clip_mult=1.0, seed=3))
    # hot session 0 floods; quiet session 1 trickles
    buf.put([0] * 40, np.zeros((40, 2), np.float32))
    buf.put([1], np.ones((1, 2), np.float32))
    assert buf.shed_counts().get(1, 0) == 0  # quiet under share: lossless
    assert buf.shed_counts()[0] > 0
    assert buf.total_drops() == 0  # ladder absorbed it before capacity
    by_policy = buf.shed_policy_counts()
    assert set(by_policy) <= {"subsample", "clip"}
    assert sum(by_policy.values()) == buf.total_sheds()
    assert buf.shed_rung() in ("subsample", "clip")
    assert buf.shed_rung_changes() >= 1


def test_buffer_shed_counts_survive_get_and_stay_lifetime():
    buf = TaggedBuffer(capacity=8, policy="drop-newest",
                       shed=ShedPolicy(lo=0.25, hi=0.5, p_floor=0.01,
                                       clip_mult=1.0, seed=0))
    buf.put([0] * 20, np.zeros((20, 2), np.float32))
    sheds = buf.total_sheds()
    assert sheds > 0
    buf.get(8)
    assert buf.total_sheds() == sheds  # lifetime ledger, not depth


def test_buffer_block_policy_backpressure():
    buf = TaggedBuffer(capacity=4, policy="block")
    rng = np.random.RandomState(0)
    sids, X = _tagged(rng, 12, [1, 2])
    done = []

    def producer():
        buf.put(sids, X)  # must block until the consumer drains
        buf.close()
        done.append(True)

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    out = []
    while True:
        got = buf.get(3, timeout=10.0)
        if got is None:
            break
        out.append(got)
    t.join(timeout=10.0)
    assert done and sum(len(s) for s, _ in out) == 12
    assert not buf.drop_counts()  # block never clips
    # a full buffer with no consumer times out rather than deadlocking
    buf2 = TaggedBuffer(capacity=2, policy="block")
    with pytest.raises(TimeoutError):
        buf2.put(sids, X, timeout=0.05)
    # an open-but-empty buffer times out on get as well
    with pytest.raises(TimeoutError):
        TaggedBuffer(capacity=2).get(1, timeout=0.05)


@pytest.mark.parametrize("admission", [
    {}, {"rate_limit": RateLimit(rate=1e-6, burst=12.0)}])
def test_buffer_block_stall_resumes_where_it_stopped(admission):
    """A ``block`` put that stalls on a full buffer goes on with the item
    it stopped at once room frees up, by either admission path: every
    item once, in order, and (with a rate limit) each item's token
    spent once — a burst of exactly 12 admits all 12."""
    buf = TaggedBuffer(capacity=4, policy="block", clock=lambda: 0.0,
                       **admission)
    sids, X = _tagged(np.random.RandomState(1), 12, [1])
    t = threading.Thread(target=lambda: (buf.put(sids, X), buf.close()),
                         daemon=True)
    t.start()
    out = []
    while (got := buf.get(3, timeout=10.0)) is not None:
        out.append(got)
    t.join(timeout=10.0)
    out_s = np.concatenate([s for s, _ in out])
    out_x = np.concatenate([x for _, x in out])
    assert len(out_s) == 12 and not buf.throttled_counts()
    want = _per_session(sids, X)
    for s, xs in _per_session(out_s, out_x).items():
        np.testing.assert_array_equal(xs, want[s])


def test_buffer_get_min_items_waits_for_fill():
    """A trickling producer must not hand the consumer near-empty
    batches when a fill threshold is set; close still drains the tail."""
    buf = TaggedBuffer(capacity=16)

    def producer():
        for i in range(5):
            buf.put([1], np.asarray([[float(i)]], np.float32))
        buf.close()

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    s, x = buf.get(4, min_items=4, timeout=10.0)
    assert len(s) == 4
    tail = buf.get(4, min_items=4, timeout=10.0)  # closed: drains 1 < 4
    assert tail is not None and len(tail[0]) == 1
    assert buf.get(4, min_items=4, timeout=10.0) is None
    t.join(timeout=10.0)


def test_buffer_quiesce_parks_then_releases_fifo():
    """A quiesced session keeps receiving but stops draining — nothing
    dropped — and its backlog comes out in order on release; extract
    hands the backlog over (for migration) in FIFO order too."""
    buf = TaggedBuffer(capacity=32)
    buf.put([1, 2, 1], np.asarray([[0.], [10.], [1.]], np.float32))
    buf.quiesce([1])
    buf.put([1, 2], np.asarray([[2.], [11.]], np.float32))  # still fed
    s, x = buf.get(8)  # only session 2 drains
    np.testing.assert_array_equal(s, [2, 2])
    np.testing.assert_array_equal(x[:, 0], [10.0, 11.0])
    assert buf.depths() == {1: 3} and buf.quiesced() == {1}
    assert not buf.drop_counts()
    buf.release([1])
    s, x = buf.get(8)
    np.testing.assert_array_equal(s, [1, 1, 1])
    np.testing.assert_array_equal(x[:, 0], [0.0, 1.0, 2.0])  # FIFO intact
    # extract: the migration path removes the backlog atomically
    buf.put([3, 3, 4], np.asarray([[5.], [6.], [7.]], np.float32))
    buf.quiesce([3])
    es, ex = buf.extract([3])
    np.testing.assert_array_equal(es, [3, 3])
    np.testing.assert_array_equal(np.stack(ex)[:, 0], [5.0, 6.0])
    assert buf.size == 1 and buf.quiesced() == set()
    # inject bypasses closed/capacity: relocation is not production
    buf.close()
    buf.inject(es, ex)
    s, x = buf.get(8)
    np.testing.assert_array_equal(sorted(s.tolist()), [3, 3, 4])


def test_buffer_quiesce_interacts_with_min_items_and_drop_oldest():
    """Quiesced backlog neither satisfies ``min_items`` nor gets clipped
    by drop-oldest while any other queue can pay instead."""
    buf = TaggedBuffer(capacity=16)
    buf.put([5] * 3, np.zeros((3, 1), np.float32))
    buf.quiesce([5])
    with pytest.raises(TimeoutError):  # 3 parked items don't count
        buf.get(4, min_items=2, timeout=0.05)
    buf.put([6], np.ones((1, 1), np.float32))
    s, _ = buf.get(4, min_items=1, timeout=5.0)
    np.testing.assert_array_equal(s, [6])
    # drop-oldest spares the quiesced queue: session 8 (longest live)
    # pays even though 7's parked queue is longer
    buf2 = TaggedBuffer(capacity=6, policy="drop-oldest")
    buf2.put([7] * 4 + [8] * 2, np.arange(6, dtype=np.float32)[:, None])
    buf2.quiesce([7])
    buf2.put([8], np.asarray([[9.0]], np.float32))
    assert buf2.drop_counts() == {8: 1}
    assert buf2.depths()[7] == 4  # the migrating session lost nothing
    # ...unless only quiesced queues remain to clip
    buf3 = TaggedBuffer(capacity=2, policy="drop-oldest")
    buf3.put([9, 9], np.zeros((2, 1), np.float32))
    buf3.quiesce([9])
    buf3.put([10], np.ones((1, 1), np.float32))
    assert buf3.drop_counts() == {9: 1}


def test_buffer_get_pads_to_fixed_shape():
    buf = TaggedBuffer(capacity=8)
    buf.put([5, 5], np.ones((2, 3), np.float32))
    s, x = buf.get(6, pad_to=6)
    assert s.shape == (6,) and x.shape == (6, 3)
    np.testing.assert_array_equal(s[2:], [PAD_SID] * 4)
    np.testing.assert_array_equal(x[2:], 0.0)


class _ItemBuffer:
    """The plain reference for ``TaggedBuffer``: the same contract, one
    item at a time — a FIFO of single rows per session, a Python turn
    per item in ``get``, ``np.stack`` of the rows.  Single-threaded
    (every wait in the sequences below has a zero timeout)."""

    def __init__(self, capacity, policy, *, rate_limit=None, shed=None,
                 clock=None):
        self.capacity, self.policy = capacity, policy
        self.rate_limit, self.shed, self._clock = rate_limit, shed, clock
        self._q = collections.OrderedDict()
        self._size = 0
        self._quiesced = set()
        self._closed = False
        self.drops, self.sheds, self.throttled = {}, {}, {}
        self._shed_by_policy = {}
        self._rung = "admit"
        self._buckets = {}

    @property
    def size(self):
        return self._size

    def depths(self):
        return {sid: len(dq) for sid, dq in self._q.items()}

    def _avail(self):
        return self._size - sum(
            len(self._q[s]) for s in self._quiesced if s in self._q)

    def quiesce(self, sids):
        self._quiesced.update(int(s) for s in np.asarray(sids).ravel())

    def release(self, sids):
        self._quiesced.difference_update(
            int(s) for s in np.asarray(sids).ravel())

    def close(self):
        self._closed = True

    def inject(self, sids, rows):
        for sid, row in zip((int(s) for s in np.asarray(sids).ravel()),
                            rows):
            self._q.setdefault(sid, collections.deque()).append(
                np.asarray(row, np.float32))
            self._size += 1

    def extract(self, sids):
        out_s, out_x = [], []
        for sid in (int(s) for s in np.asarray(sids).ravel()):
            self._quiesced.discard(sid)
            dq = self._q.pop(sid, None)
            if dq:
                out_s.extend([sid] * len(dq))
                out_x.extend(dq)
                self._size -= len(dq)
        return np.asarray(out_s, np.int32), out_x

    def _admit_rate(self, sid, now):
        if self.rate_limit is None:
            return True
        bucket = self._buckets.get(sid)
        if bucket is None:
            bucket = self._buckets[sid] = TokenBucket(self.rate_limit, now)
        return bucket.allow(now)

    def _admit_shed(self, sid):
        ok, rung = self.shed.decide(
            size=self._size, capacity=self.capacity,
            depth=len(self._q[sid]) if sid in self._q else 0,
            n_live=len(self._q))
        self._rung = rung
        if not ok:
            self.sheds[sid] = self.sheds.get(sid, 0) + 1
            self._shed_by_policy[rung] = \
                self._shed_by_policy.get(rung, 0) + 1
        return ok

    def put(self, sids, X, timeout=None):
        sids = np.asarray(sids, np.int32).ravel()
        X = np.asarray(X, np.float32)
        dropped = 0
        now = self._clock() if self.rate_limit else 0.0
        for sid, row in zip(sids.tolist(), X):
            if self._closed:
                raise ValueError("put() on a closed buffer")
            if not self._admit_rate(sid, now):
                self.throttled[sid] = self.throttled.get(sid, 0) + 1
                dropped += 1
                continue
            if self.shed is not None and not self._admit_shed(sid):
                dropped += 1
                continue
            if self._size >= self.capacity:
                if self.policy == "block":  # nothing drains: timed out
                    raise TimeoutError("buffer full")
                if self.policy == "drop-newest":
                    self.drops[sid] = self.drops.get(sid, 0) + 1
                    dropped += 1
                    continue
                pool = [s for s in self._q if s not in
                        self._quiesced] or list(self._q)
                victim = max(pool, key=lambda s: len(self._q[s]))
                self._q[victim].popleft()
                if not self._q[victim]:
                    del self._q[victim]
                self._size -= 1
                self.drops[victim] = self.drops.get(victim, 0) + 1
                dropped += 1
            self._q.setdefault(sid, collections.deque()).append(row)
            self._size += 1
        return dropped

    def get(self, max_items, *, pad_to=None, d=None, min_items=1,
            timeout=None, per_session=None):
        need = max(1, min(min_items, max_items))
        if not (self._avail() >= need or self._closed):
            raise TimeoutError("buffer below min_items")
        if self._avail() == 0:
            return None
        out_s, out_x = [], []
        gave = collections.Counter()  # a session's turns this batch
        while len(out_s) < max_items and self._q:
            took = 0
            for sid in list(self._q):
                if len(out_s) >= max_items:
                    break
                if sid in self._quiesced or gave[sid] == per_session:
                    continue
                dq = self._q[sid]
                out_s.append(sid)
                out_x.append(dq.popleft())
                gave[sid] += 1
                took += 1
                if not dq:
                    del self._q[sid]
            if not took:
                break
        self._size -= len(out_s)
        sids = np.asarray(out_s, np.int32)
        X = np.stack(out_x).astype(np.float32)
        if pad_to is not None and len(sids) < pad_to:
            n_pad = pad_to - len(sids)
            sids = np.concatenate(
                [sids, np.full((n_pad,), PAD_SID, np.int32)])
            X = np.concatenate([X, np.zeros((n_pad, X.shape[1]),
                                            np.float32)])
        return sids, X


def _outcome(fn, *args, **kw):
    """A call's result, or the type of what it raised."""
    try:
        return fn(*args, **kw)
    except (TimeoutError, ValueError) as e:
        return type(e)


def _same_batch(got, want):
    if want is None or isinstance(want, type):
        assert got is want
        return
    (gs, gx), (ws, wx) = got, want
    assert gs.dtype == ws.dtype and gx.dtype == wx.dtype
    assert gs.shape == ws.shape and gx.shape == wx.shape
    assert gs.tobytes() == ws.tobytes() and gx.tobytes() == wx.tobytes()


def _queued(buf):
    """Each session's queued items read from the buffer's log: sid -> the
    log positions of its live entries, oldest first.  A session's live
    entries hold the sequence numbers from its head to its tail, in that
    order."""
    lo, hi = buf._lo, buf._hi
    sess, seq = buf._sess[lo:hi], buf._seq[lo:hi]
    live = seq >= buf._head[sess]
    out = {}
    for s in np.unique(sess[live]).tolist():
        at = lo + np.flatnonzero(live & (sess == s))
        np.testing.assert_array_equal(
            buf._seq[at], np.arange(buf._head[s], buf._tail[s]))
        out[int(buf._sid[s])] = at
    return out


def _same_state(buf, ref):
    queued = _queued(buf)
    assert set(queued) == set(buf.depths()) and buf._nlive == len(queued)
    # every slot of the store is held by one queued item or free, once
    if buf._rows is not None:
        held = [buf._free[:buf._nfree]] + [buf._slot[at]
                                           for at in queued.values()]
        np.testing.assert_array_equal(np.sort(np.concatenate(held)),
                                      np.arange(len(buf._rows)))
    # the dicts in order: the order is the round-robin's
    assert list(buf.depths().items()) == list(ref.depths().items())
    assert buf.size == ref.size
    assert buf.quiesced() == ref._quiesced
    assert buf.drop_counts() == ref.drops
    assert buf.shed_counts() == ref.sheds
    assert buf.throttled_counts() == ref.throttled
    assert buf.shed_policy_counts() == ref._shed_by_policy
    assert buf.shed_rung() == ref._rung


ADMISSION = {  # name -> (policy, constructor keywords)
    "block": ("block", {}),
    "drop-newest": ("drop-newest", {}),
    "drop-oldest": ("drop-oldest", {}),
    "rate-limit": ("block", {"rate_limit": RateLimit(rate=100.0,
                                                     burst=20.0)}),
    "shed": ("drop-newest", {"shed": (0.3, 0.7)}),
}


@pytest.mark.parametrize("admission", sorted(ADMISSION))
@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_buffer_blocks_bit_equal_to_item_reference(admission, seed):
    """Block storage and the vectorised round-robin change the cost,
    not the result: random puts (1–4096 items, repeated tags), gets
    (random ``max_items``/``pad_to``/``min_items``), quiesce, release,
    extract → inject and close give the item-at-a-time reference's
    batches bit for bit, its return values and its ledgers."""
    rng = np.random.RandomState(seed)
    policy, kw = ADMISSION[admission]
    now = [0.0]
    capacity = int(rng.choice([64, 1500, 6000]))
    bufs = []
    for cls in (TaggedBuffer, _ItemBuffer):
        ckw = dict(kw)
        if "shed" in ckw:  # one ladder each, same seed: same draws
            lo, hi = ckw["shed"]
            ckw["shed"] = ShedPolicy(lo=lo, hi=hi, seed=seed)
        bufs.append(cls(capacity, policy, clock=lambda: now[0], **ckw))
    buf, ref = bufs
    tenants = rng.choice(100, size=rng.randint(1, 40), replace=False)
    d = 3
    pool = np.empty((40 * 4096, d), np.float32)
    for step in range(40):
        op = rng.choice(["put", "put", "put", "get", "get", "quiesce",
                         "release", "move", "tick"])
        if op == "put":
            n = int(rng.choice([rng.randint(1, 64), rng.randint(1, 4097)]))
            s = rng.choice(tenants, n).astype(np.int32)
            # a fresh array, a row slice of one shared array, or views
            # that are not row slices of their base
            X = [lambda: np.empty((n, d), np.float32),
                 lambda: pool[step * 4096:step * 4096 + n],
                 lambda: np.empty((2 * n, d), np.float32)[::2],
                 lambda: np.empty((n, d + 2), np.float32)[:, 1:-1],
                 lambda: np.empty((n + 1, d), np.float32).reshape(-1)[
                     1:1 + n * d].reshape(n, d),  # rows off by an item
                 ][rng.randint(5)]()
            X[:] = rng.randn(n, d)
            X[:, 0] = step * 10_000 + np.arange(n)  # a fingerprint
            got, want = (_outcome(b.put, s, X, timeout=0.0)
                         for b in bufs)
            assert got == want
        elif op == "get":
            m = int(rng.randint(1, 5000))
            pad = [None, m, m + int(rng.randint(0, 300))][rng.randint(3)]
            k = int(rng.choice([1, rng.randint(1, m + 1)]))
            _same_batch(*(_outcome(b.get, m, pad_to=pad, d=d, min_items=k,
                                   timeout=0.0) for b in bufs))
        elif op in ("quiesce", "release"):
            sids = rng.choice(tenants, rng.randint(1, 4))
            for b in bufs:
                getattr(b, op)(sids)
        elif op == "move":  # a handoff: extract, then inject back
            sids = rng.choice(tenants, rng.randint(1, 4))
            (gs, gx), (ws, wx) = (b.extract(sids) for b in bufs)
            assert gs.tobytes() == ws.tobytes() and len(gx) == len(wx)
            assert all(g.tobytes() == w.tobytes() for g, w in zip(gx, wx))
            buf.inject(gs, gx)
            ref.inject(ws, wx)
        else:
            now[0] += float(rng.uniform(0.0, 0.2))
        _same_state(buf, ref)
    for b in bufs:
        b.close()
    assert _outcome(buf.put, [1], np.zeros((1, d))) is ValueError
    while True:
        m = int(rng.randint(1, 3000))
        got, want = (b.get(m, pad_to=m) for b in bufs)
        _same_batch(got, want)
        _same_state(buf, ref)
        if want is None:
            break


def _sparse_ids(rng, n):
    """``n`` distinct session ids that are not 0..n-1: spread over int32,
    unsorted, and a few of them negative (none the padding id)."""
    ids = np.unique(rng.randint(0, 2**31 - 1, 2 * n)).astype(np.int32)
    ids = ids[rng.permutation(len(ids))[:n]]
    ids[:16] = -np.arange(2, 18)
    return ids


MANY = {  # case -> (policy, capacity, the gets' per_session caps, handoffs)
    "rejoin": ("block", 1 << 15, [None], False),
    "capped": ("block", 1 << 15, [1, 3, 7, None], False),
    "handoff": ("block", 1 << 15, [None, 4], True),
    "drop-oldest": ("drop-oldest", 4500, [None, 5], False),
    "drop-newest": ("drop-newest", 4500, [None, 5], False),
    "drop-oldest-handoff": ("drop-oldest", 4500, [None], True),
}


@pytest.mark.parametrize("case", sorted(MANY))
def test_buffer_many_sessions_bit_equal_to_item_reference(case):
    """At 4096 sessions with 4096-item puts, over ids that are not
    0..S-1 (large, unsorted, some negative), the queues give the
    item-at-a-time reference's batches, depths (in round-robin order)
    and ledgers bit for bit: queues that empty and join the round-robin
    again, ``per_session`` caps, quiesce, release and extract -> inject
    mid-stream, and overflow under either drop policy."""
    policy, capacity, caps, handoff = MANY[case]
    rng = np.random.RandomState(sorted(MANY).index(case))
    S = n = 4096
    d = 2
    ids = _sparse_ids(rng, S)
    hot = rng.choice(ids, 64, replace=False)
    bufs = [TaggedBuffer(capacity, policy), _ItemBuffer(capacity, policy)]
    buf, ref = bufs
    parked = np.empty(0, np.int32)
    for step in range(12 if handoff else 8):
        # every session, a quarter of the items on 64 hot ones
        s = np.where(rng.rand(n) < 0.25, rng.choice(hot, n),
                     rng.choice(ids, n)).astype(np.int32)
        X = rng.randn(n, d).astype(np.float32)
        X[:, 0] = step * 10_000 + np.arange(n)  # a fingerprint
        got, want = (_outcome(b.put, s, X, timeout=0.0) for b in bufs)
        assert got == want
        _same_state(buf, ref)
        if handoff and step % 3 == 0:
            parked = np.unique(rng.choice(s, 48))
            for b in bufs:
                b.quiesce(parked)
        elif handoff and step % 3 == 2:  # release half, move the rest
            for b in bufs:
                b.release(parked[::2])
            (gs, gx), (ws, wx) = (b.extract(parked[1::2]) for b in bufs)
            assert gs.tobytes() == ws.tobytes() and len(gx) == len(wx) > 0
            assert all(g.tobytes() == w.tobytes() for g, w in zip(gx, wx))
            buf.inject(gs, gx)
            ref.inject(ws, wx)
            _same_state(buf, ref)
        if policy == "block":
            m = int(rng.choice([n // 2, n, 2 * n]))
        else:  # a few hundred items over capacity a put
            m = n - int(rng.randint(0, 400))
        cap = caps[step % len(caps)]
        _same_batch(*(_outcome(b.get, m, pad_to=m, per_session=cap,
                               timeout=0.0) for b in bufs))
        _same_state(buf, ref)
    assert buf._next_turn > len(np.unique(buf._sid[:buf._nsess]))  # rejoins
    if policy != "block":
        assert sum(ref.drops.values()) > 0
    for b in bufs:
        b.release(parked)
        b.close()
    while True:
        got, want = (b.get(3000, pad_to=3000) for b in bufs)
        _same_batch(got, want)
        _same_state(buf, ref)
        if want is None:
            break


ENGAGE = {  # name -> (policy, constructor keywords, whole puts by array)
    "block": ("block", {}, True),
    "rate-limit": ("block", {"rate_limit": RateLimit(rate=1e9)}, False),
    "shed": ("block", {"shed": ShedPolicy(lo=0.9, hi=0.95)}, False),
    "drop-oldest": ("drop-oldest", {}, False),
}


@pytest.mark.parametrize("admission", sorted(ENGAGE))
def test_buffer_put_over_many_sessions_counts_the_array_path(admission):
    """A block put over 4096 distinct sessions is queued by array steps
    and counts every item in ``put_block_items``; a rate limit, a shed
    ladder or ``drop-oldest`` decides item by item and counts none
    there.  Either way each session gives one item, in order of first
    appearance."""
    policy, kw, whole = ENGAGE[admission]
    rng = np.random.RandomState(0)
    ids = _sparse_ids(rng, 4096)
    buf = TaggedBuffer(capacity=8192, policy=policy, **kw)
    buf.put(ids, rng.randn(4096, 3).astype(np.float32))
    assert buf.admitted() == 4096
    assert buf.block_counts() == {"put_block_items": 4096 if whole else 0,
                                  "get_blocks": 0}
    sids, _ = buf.get(8192)
    np.testing.assert_array_equal(sids, ids)
    assert buf.block_counts()["get_blocks"] == 4096


def test_buffer_queue_memory_follows_capacity_and_sessions():
    """A hot session's backlog, capped per batch, leaves dead entries
    between live ones in the log; the log still stays within twice the
    items queued plus a put, and the per-session arrays within the
    sessions seen, however many items pass through."""
    buf = TaggedBuffer(capacity=8192, policy="drop-newest")
    rng = np.random.RandomState(0)
    ids = _sparse_ids(rng, 300)
    for _ in range(200):
        s = np.where(rng.rand(4096) < 0.5, ids[0],
                     rng.choice(ids, 4096)).astype(np.int32)
        buf.put(s, np.zeros((4096, 2), np.float32))
        buf.get(4096, per_session=32)
        assert buf._hi - buf._lo <= len(buf._slot) <= 2 * (8192 + 4096)
    assert buf._nsess == 300 and len(buf._sid) <= 2 * 300
    assert buf.total_drops() > 0 and buf.depths()[int(ids[0])] > 4096


def _drain_concurrent_producers(take):
    """More producer threads than cores put ragged batches into a small
    ``block`` buffer while one consumer drains it with ``take(buf,
    max_items, sessions) -> (sids, rows)``, with the interpreter
    switching threads often: every item arrives once, each session's in
    order, and every item went through the block path."""
    import os
    import sys
    workers = (os.cpu_count() or 2) + 2
    per, sessions = 600, 3  # items per producer; sessions per producer
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        buf = TaggedBuffer(capacity=64, policy="block")

        def producer(p):
            rng = np.random.RandomState(p)
            sids = (p * sessions + rng.randint(0, sessions, per)).astype(
                np.int32)
            X = np.stack([np.full(per, p, np.float32),
                          np.arange(per, dtype=np.float32)], axis=1)
            lo = 0
            while lo < per:
                hi = min(per, lo + int(rng.randint(1, 100)))
                buf.put(sids[lo:hi], X[lo:hi], timeout=30.0)
                lo = hi

        threads = [threading.Thread(target=producer, args=(p,), daemon=True)
                   for p in range(workers)]
        for t in threads:
            t.start()
        out_s, out_x = [], []
        rng = np.random.RandomState(0)
        while sum(len(s) for s in out_s) < workers * per:
            s, x = take(buf, int(rng.randint(1, 200)), workers * sessions)
            out_s.append(s)
            out_x.append(x)
        for t in threads:
            t.join(timeout=30.0)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    out_s, out_x = np.concatenate(out_s), np.concatenate(out_x)
    assert buf.size == 0 and len(out_s) == workers * per
    assert buf.block_counts()["put_block_items"] == workers * per
    for p in range(workers):  # each producer's items once, in order
        mine = out_x[out_x[:, 0] == p, 1]
        np.testing.assert_array_equal(np.sort(mine), np.arange(per))
        for s in range(p * sessions, (p + 1) * sessions):
            seq = out_x[out_s == s, 1]
            assert np.all(np.diff(seq) > 0)


def test_buffer_blocks_survive_concurrent_producers():
    """``get`` drains a buffer that concurrent producers fill
    (``_drain_concurrent_producers``)."""
    _drain_concurrent_producers(
        lambda buf, m, S: buf.get(m, timeout=30.0))


def test_leases_survive_concurrent_producers():
    """The pipeline's consumer drains a buffer that concurrent producers
    fill and grow (``_drain_concurrent_producers``): each batch is
    leased and copied by ``fill_chunks`` into one reused chunk array
    while the puts go on, and is read back from the chunks."""
    from repro.ingest.pipeline import fill_chunks, live_table, share_slots
    arrays = {}

    def take(buf, m, S):
        if not arrays:
            arrays.update(chunks=np.zeros((S, 200, 2), np.float32),
                          held=np.zeros((S,), np.int64),
                          table=live_table(np.arange(S, dtype=np.int32),
                                           np.ones(S, bool)))
        chunks = arrays["chunks"]
        with buf.lease(m, timeout=30.0) as lease:
            slot = share_slots(arrays["table"], lease.sids, S)
            counts, unknown, overflow, _ = fill_chunks(
                lease, slot, chunks, arrays["held"])
        assert int(unknown) == 0 and overflow.sum() == 0
        np.testing.assert_array_equal(counts[slot], lease.counts)
        return (lease.sids.repeat(lease.counts),
                np.concatenate([chunks[i, :counts[i]] for i in slot]))

    _drain_concurrent_producers(take)


def _round_robin_puts(buf, puts, sliced=False, sessions=256, n=4096, d=2):
    """``puts`` puts of ``n`` items dealt to the sessions in turn, as the
    served benchmark's producer deals them: each put a fresh array, or
    (``sliced``) the next row slice of one array."""
    t = np.arange(puts * n)
    pool = np.zeros((puts * n, d), np.float32)
    for p in range(puts):
        X = pool[p * n:(p + 1) * n] if sliced else np.zeros((n, d),
                                                            np.float32)
        buf.put((t[p * n:(p + 1) * n] % sessions).astype(np.int32), X)


@pytest.mark.parametrize("sliced", [False, True])
def test_buffer_block_path_counts_blocks_not_items(sliced):
    """4096-item puts under ``block`` are queued by array steps, and
    a batch of 131072 takes one share of slots per session, whether each
    put is a fresh array or a row slice of one array."""
    buf = TaggedBuffer(capacity=2 * 131072, policy="block")
    _round_robin_puts(buf, 32, sliced)
    assert buf.block_counts() == {"put_block_items": 131072,
                                  "get_blocks": 0}
    sids, X = buf.get(131072)
    assert len(sids) == 131072 and buf.size == 0
    assert buf.block_counts()["get_blocks"] == 256
    # the round-robin: every turn deals the 256 sessions in order
    np.testing.assert_array_equal(sids.reshape(512, 256),
                                  np.tile(np.arange(256), (512, 1)))


@pytest.mark.parametrize("admission", [
    {"rate_limit": RateLimit(rate=1e9)}, {"shed": ShedPolicy(lo=0.9, hi=0.95)}])
def test_buffer_per_item_admission_skips_the_block_path(admission):
    """A rate limit or a shed ladder decides item by item: nothing goes
    through the block path, though a session's items still leave as one
    share."""
    buf = TaggedBuffer(capacity=8192, policy="block", **admission)
    _round_robin_puts(buf, 1)
    assert buf.block_counts() == {"put_block_items": 0, "get_blocks": 0}
    assert buf.admitted() == 4096
    buf.get(4096)
    assert buf.block_counts()["get_blocks"] == 256


def test_buffer_store_reuses_freed_slots():
    """The rows a ``get`` has copied out free their slots for the next
    puts, so the store stays at the size the buffer holds at its fullest
    plus a batch in flight, however many items pass through."""
    buf = TaggedBuffer(capacity=4096, policy="block")
    rng = np.random.RandomState(0)
    for _ in range(50):
        s = rng.randint(0, 37, 4096).astype(np.int32)
        X = rng.randn(4096, 4).astype(np.float32)
        buf.put(s, X)
        got_s, got_x = buf.get(4096)
        assert len(got_s) == 4096 and buf.size == 0
    assert len(buf._rows) <= 2 * 4096 and buf._nfree == len(buf._rows)


def test_buffer_get_refuses_a_slot_outside_the_store():
    """A queue that names a slot the store does not have makes ``get``
    raise, where the gather would otherwise clamp it to the last row."""
    buf = TaggedBuffer(capacity=64, policy="block")
    buf.put(np.int32([5, 5, 6]), np.ones((3, 2), np.float32))
    buf._slot[_queued(buf)[5][0]] = len(buf._rows) + 3
    with pytest.raises(RuntimeError, match="outside the store"):
        buf.get(3)


def test_pipeline_run_span_carries_the_block_counters():
    """A pipeline run leaves the block counters on its
    ``ingest_run`` span, as deltas since the previous run."""
    from repro import obs
    rec = obs.get_recorder()
    rec.clear()
    pod = _pod(S=3, C=32, T=9)
    st = _admit_all(pod, pod.init(), [1, 2, 3])
    buf = TaggedBuffer(capacity=256, policy="block")
    pipe = IngestPipeline(pod, buffer=buf, batch=32, min_fill=32)
    for _ in range(2):
        buf.put(np.tile(np.int32([1, 2, 3]), 20),
                np.ones((60, D), np.float32))
        st, _ = pipe.run(st, max_batches=1)
    runs = [e["attrs"] for e in rec.events if e["name"] == "ingest_run"]
    rec.clear()
    assert [(a["buffer_put_items"], a["buffer_put_block_items"])
            for a in runs] == [(60, 60), (60, 60)]
    # 11/11/10 items of 20/20/20, then of 29/29/30: a share per session
    assert [a["buffer_get_blocks"] for a in runs] == [3, 3]


# ------------------------------------------------------------------- routing
@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000))
def test_host_route_bit_equals_device_route(seed):
    """``SummarizerPod.route`` gives what the plain (N, S) match of ids
    against the table and per-slot FIFO positions give — chunks, counts
    and both drop counters — including unknown sids, padding and
    per-session overflow."""
    rng = np.random.RandomState(seed)
    pod = _pod(S=4, C=3)
    state = _admit_all(pod, pod.init(), [10, 11, 12, 13])
    sids = rng.choice(np.asarray([10, 11, 12, 13, 99, PAD_SID], np.int32),
                      26).astype(np.int32)
    X = rng.randn(26, D).astype(np.float32)
    cj, nj, uj, oj = pod.route(state, jnp.asarray(sids), jnp.asarray(X))
    (ch, nh, uh, oh), _ = _match_route(np.asarray(state.sid),
                                       np.asarray(state.active), sids, X,
                                       pod.chunk)
    np.testing.assert_array_equal(np.asarray(cj), ch)
    np.testing.assert_array_equal(np.asarray(nj), nh)
    assert int(uj) == int(uh)
    np.testing.assert_array_equal(np.asarray(oj), oh)


# ------------------------------------------------------------------ pipeline
def _assert_sessions_match_standalone(pod, state, per):
    ro = pod.readout(state)
    feats, n, fval = ro.feats, ro.n, ro.fval
    algo = pod.algo
    runb = jax.jit(algo.run_batched)
    slot_of = {int(s): i for i, s in enumerate(np.asarray(state.sid))}
    for sid, rows in per.items():
        i = slot_of[int(sid)]
        ref = runb(algo.init(), jnp.asarray(np.stack(rows)))
        rf, rn, rfv = algo.summary(ref)
        assert int(n[i]) == int(rn), f"session {sid}"
        np.testing.assert_array_equal(np.asarray(feats[i]), np.asarray(rf),
                                      err_msg=f"session {sid}")


@pytest.mark.parametrize("S", [4, 1, 16, 64])
def test_pipeline_bit_equal_to_sync_ingest_loop(S):
    """Same stream, two execution strategies: the double-buffered
    pipeline's final pod state equals the synchronous per-batch
    ``jit(pod.ingest)`` loop over ``get``'s batches bit for bit, from
    one session to more sessions than a batch has items."""
    pod = _pod(S=S, C=16)
    rng = np.random.RandomState(2)
    sessions = list(range(10, 10 + S))
    feed = [_tagged(rng, 32, sessions) for _ in range(6)]
    st0 = _admit_all(pod, pod.init(), sessions)

    ing = jax.jit(pod.ingest)
    st_sync, ref, n_sync = st0, _closed_buffer(feed), 0
    while (got := ref.get(32, pad_to=32, per_session=pod.chunk)) is not None:
        st_sync, _ = ing(st_sync, jnp.asarray(got[0]), jnp.asarray(got[1]))
        n_sync += 1

    pipe = IngestPipeline(pod, buffer=_closed_buffer(feed), batch=32)
    st_pipe, stats = pipe.run(st0)
    assert stats["batches"] == n_sync >= 6 and stats["items"] == 192
    for (pa, la), lb in zip(jax.tree_util.tree_leaves_with_path(st_sync),
                            jax.tree_util.tree_leaves(st_pipe)):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb),
            err_msg=f"leaf {jax.tree_util.keystr(pa)} differs")


def test_pipeline_buffer_mode_copies_once_bit_equal_to_sync_loop():
    """The pipeline builds each batch by one copy from the buffer's store
    into two reused chunk arrays.  Over two runs of at least six batches
    whose shares shrink (each array refilled, rows it held zeroed), each
    step is sent what ``SummarizerPod.route`` makes of ``get``'s batch,
    and the
    pod state equals the synchronous ``jit(pod.ingest)`` loop over
    ``get``'s batches of the same stream, bit for bit.  The
    ``ingest_run`` spans count the rows zeroed and every stage of the
    route."""
    from repro import obs
    rec = obs.get_recorder()
    rec.clear()
    pod = _pod(S=4, C=8)
    B = 16
    rng = np.random.RandomState(6)
    sids = rng.permutation(np.repeat(np.int32([10, 11, 12, 13]),
                                     [40, 20, 8, 4])).astype(np.int32)
    X = rng.randn(len(sids), D).astype(np.float32)
    st0 = _admit_all(pod, pod.init(), [10, 11, 12, 13])
    buf, ref = (TaggedBuffer(capacity=128, policy="block") for _ in "ab")
    for b in (buf, ref):
        b.put(sids, X)
        b.close()

    ing, route = jax.jit(pod.ingest), jax.jit(pod.route)
    st_sync, want = st0, []
    while (got := ref.get(B, pad_to=B, per_session=pod.chunk)) is not None:
        st_sync, _ = ing(st_sync, jnp.asarray(got[0]), jnp.asarray(got[1]))
        want.append([np.asarray(a) for a in route(st0, *got)])

    pipe = IngestPipeline(pod, buffer=buf, batch=B)
    step, sent = pipe._advance_fn(), []

    def advance(state, *args):  # what each step is sent, as it is sent
        sent.append([np.array(a) for a in args])
        return step(state, *args)

    pipe._advance = advance
    st_pipe, s1 = pipe.run(st0, max_batches=3)
    st_pipe, s2 = pipe.run(st_pipe)
    assert s1["batches"] + s2["batches"] == len(want) >= 6
    for i, (got, w) in enumerate(zip(sent, want)):
        for g, x in zip(got, w):
            assert g.dtype == np.asarray(x).dtype
            np.testing.assert_array_equal(g, x, err_msg=f"batch {i}")
    assert s1["items"] + s2["items"] == len(sids)
    for (pa, la), lb in zip(jax.tree_util.tree_leaves_with_path(st_sync),
                            jax.tree_util.tree_leaves(st_pipe)):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb),
            err_msg=f"leaf {jax.tree_util.keystr(pa)} differs")
    runs = [e["attrs"] for e in rec.events if e["name"] == "ingest_run"]
    rec.clear()
    assert sum(a["items"] for a in runs) == len(sids)
    assert sum(a["zeroed_rows"] for a in runs) > 0
    for stage in ("ingest_get", "ingest_route", "ingest_slot_lookup",
                  "ingest_scatter", "ingest_device_put"):
        assert all(f"{stage}_s" in a for a in runs), stage


def test_pipeline_repacks_ragged_batches_fifo():
    """Ragged puts drain per-session FIFO through the pipeline: device
    batches cross the puts' boundaries, and each session ends bit-equal
    to its standalone run on the original item order."""
    pod = _pod(S=3, C=16)
    rng = np.random.RandomState(4)
    sids, X = _tagged(rng, 70, [20, 21, 22])
    ragged, lo = [], 0
    for n in (7, 19, 3, 11, 17, 13):  # deliberately unaligned
        ragged.append((sids[lo:lo + n], X[lo:lo + n]))
        lo += n
    st = _admit_all(pod, pod.init(), [20, 21, 22])
    pipe = IngestPipeline(pod, buffer=_closed_buffer(ragged), batch=16)
    st, stats = pipe.run(st)
    assert stats["items"] == 70
    assert stats["padded"] == (16 - 70 % 16) % 16
    assert int(jnp.sum(st.items)) == 70
    _assert_sessions_match_standalone(pod, st, _per_session(sids, X))


def test_pipeline_buffer_mode_with_feeder_thread():
    """Producer thread -> TaggedBuffer -> pipeline: the decoupled path
    delivers every item, per-session FIFO intact (global interleaving
    legitimately changes under the fairness rotation)."""
    pod = _pod(S=3, C=32, T=9)
    rng = np.random.RandomState(5)
    sids, X = _tagged(rng, 90, [1, 2, 3])
    st = _admit_all(pod, pod.init(), [1, 2, 3])
    buf = TaggedBuffer(capacity=64, policy="block")
    pipe = IngestPipeline(pod, buffer=buf, batch=32, get_timeout=30.0)
    pipe.feed_from(ReplaySource(sids=sids, X=X, batch=17))
    st, stats = pipe.run(st)
    assert stats["items"] == 90
    _assert_sessions_match_standalone(pod, st, _per_session(sids, X))


def test_pod_serve_drift_loop():
    """pod.serve(pipeline) drives ingest and interleaves drift checks."""
    pod = _pod(S=2, C=32, T=5)
    src = DriftSource(seed=3, n_sessions=2, batch=32, d=D, n_batches=12,
                      drift_per_batch=0.5)
    st = _admit_all(pod, pod.init(), [0, 1])
    pipe = IngestPipeline(pod, buffer=_closed_buffer(list(src)), batch=32)
    st, stats = pod.serve(st, pipe, drift_every=3, min_items=30,
                          min_rate=0.9)
    assert pipe.exhausted
    assert stats["batches"] == 12 and stats["items"] == 12 * 32
    # the aggressive min_rate forces re-arms through the serve loop
    assert int(jnp.sum(st.resets)) > 0
    assert int(jnp.sum(st.items)) == 12 * 32


def test_pipeline_resume_is_retrace_free(retrace_guard):
    """Resuming a budgeted pipeline must not recompile anything: run()
    pads every device batch to the fixed (batch, d) shape, so the
    resumed drain — including the ragged tail — is served entirely from
    the warmup compile (the double-buffered advance, donation included)."""
    pod = _pod(S=2, C=32)
    rng = np.random.RandomState(12)
    sids, X = _tagged(rng, 90, [1, 2])  # ragged tail: 90 = 32 + 32 + 26
    st = _admit_all(pod, pod.init(), [1, 2])
    pipe = IngestPipeline(pod, buffer=_closed_buffer([(sids, X)]), batch=32)
    st, s1 = pipe.run(st, max_batches=1)  # warmup: compiles the step
    assert s1["batches"] == 1
    with retrace_guard.budget(0):
        st, s2 = pipe.run(st)  # resume to exhaustion
    assert retrace_guard.compiles == 0
    assert pipe.exhausted and s1["items"] + s2["items"] == 90
    assert s2["padded"] == 6
    _assert_sessions_match_standalone(pod, st, _per_session(sids, X))


def test_pipeline_surfaces_producer_failure():
    """A producer that dies mid-stream must raise from run(), not pose
    as a clean end-of-stream with fewer items."""
    from repro.ingest import Source

    rng = np.random.RandomState(9)
    sids, X = _tagged(rng, 8, [1, 2])

    class Boom(Source):
        def batches(self):
            yield sids, X
            raise ConnectionError("wire cut")

    pod = _pod(S=2, C=8)
    st = _admit_all(pod, pod.init(), [1, 2])
    buf = TaggedBuffer(capacity=32, policy="block")
    pipe = IngestPipeline(pod, buffer=buf, batch=8, get_timeout=10.0)
    pipe.feed_from(Boom())
    with pytest.raises(RuntimeError, match="producer failed"):
        pipe.run(st)
    # drop counters ride along in stats on the healthy path
    pipe2 = IngestPipeline(pod, buffer=_closed_buffer([(sids, X)]))
    _, stats = pipe2.run(st)
    assert stats["dropped_unknown"] == 0 and stats["dropped_overflow"] == 0


def test_pod_serve_respects_max_batches_with_drift():
    """Regression: with drift_every > max_batches the serve loop ran a
    full drift window before ever checking the budget."""
    pod = _pod(S=2, C=32, T=5)
    src = DriftSource(seed=3, n_sessions=2, batch=32, d=D, n_batches=12)
    st = _admit_all(pod, pod.init(), [0, 1])
    pipe = IngestPipeline(pod, buffer=_closed_buffer(list(src)), batch=32)
    st, stats = pod.serve(st, pipe, max_batches=4, drift_every=64,
                          min_items=10**6, min_rate=0.0)
    assert stats["batches"] == 4
    assert int(jnp.sum(st.items)) == 4 * 32
    # the feed is resumable: a later serve continues where it stopped
    st, stats = pod.serve(st, pipe, max_batches=None)
    assert stats["batches"] == 8 and pipe.exhausted


# ---------------------------------------------------------- shed ladder
def _overload_run(mult, buffer, rounds=12, batch=16, d=8):
    """One hot tenant (0) offering ``mult * batch - 3`` items a round and
    three quiet ones (1-3) one each, against a pod that drains one
    device batch a round; the stream is the same for every buffer.
    -> ({sid: f(S)}, the buffer's largest depth)."""
    algo = make("threesieves", d=d, K=4, T=64, eps=0.5)
    pod = SummarizerPod(algo, sessions=4, chunk=batch)
    st = _admit_all(pod, pod.init(), range(4))
    pipe = IngestPipeline(pod, buffer=buffer, batch=batch, get_timeout=60.0)
    rng = np.random.default_rng(5)
    max_depth = 0
    for _ in range(rounds):
        sids = np.int32([0] * (mult * batch - 3) + [1, 2, 3])
        buffer.put(sids, rng.normal(size=(len(sids), d)).astype(np.float32))
        max_depth = max(max_depth, buffer.size)
        st, _ = pipe.run(st, max_batches=1)
    buffer.close()
    st, _ = pipe.run(st)
    fval = np.asarray(pod.readout(st).fval)
    return ({int(s): float(fval[i])
             for i, s in enumerate(np.asarray(st.sid)) if s >= 0}, max_depth)


@pytest.mark.parametrize("mult", [2, 4, 10])
def test_shed_ladder_under_sustained_overload(mult):
    """At 2x, 4x and 10x the drain rate the watermark ladder keeps the
    buffer within its capacity and never reaches its capacity wall; the
    quiet tenants are shed nothing and end bit-equal to the run with no
    shedding, and up to 4x the summed f stays within 5% of it."""
    f_base, _ = _overload_run(mult, TaggedBuffer(1 << 20))
    buf = TaggedBuffer(64, policy="drop-newest",
                       shed=ShedPolicy(lo=0.25, hi=0.6, p_floor=0.1,
                                       clip_mult=2.0, seed=1))
    f_shed, max_depth = _overload_run(mult, buf)
    assert max_depth <= buf.capacity
    assert buf.total_drops() == 0 and buf.total_sheds() > 0
    sheds = buf.shed_counts()
    for q in (1, 2, 3):
        assert sheds.get(q, 0) == 0, q
        assert f_shed[q] == f_base[q], q
    if mult <= 4:
        assert sum(f_shed.values()) >= 0.95 * sum(f_base.values())


# -------------------------------------------------------------------- socket
@pytest.mark.timeout(60)
def test_socket_source_roundtrip_localhost():
    rng = np.random.RandomState(6)
    frames = [_tagged(rng, n, [1, 2]) for n in (5, 1, 9)]
    with SocketSource(port=0, timeout=20.0) as src:

        def producer():
            sock = connect_producer(src.host, src.port, timeout=20.0)
            for sids, X in frames:
                send_frame(sock, sids, X)
            sock.close()

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        got = list(src)
        t.join(timeout=20.0)
    assert len(got) == 3
    for (sa, xa), (sb, xb) in zip(frames, got):
        np.testing.assert_array_equal(sa, sb)
        np.testing.assert_array_equal(xa, xb)


@pytest.mark.timeout(60)
def test_socket_source_rejects_oversize_frame():
    """A corrupt/desynced header announcing a huge payload must be a
    protocol error, not a multi-GB allocation."""
    rng = np.random.RandomState(7)
    sids, X = _tagged(rng, 8, [1], d=16)
    with SocketSource(port=0, timeout=20.0, max_frame_bytes=256) as src:

        def producer():
            sock = connect_producer(src.host, src.port, timeout=20.0)
            try:
                send_frame(sock, sids, X)  # 8*4 + 8*16*4 bytes > 256
            finally:
                sock.close()

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        with pytest.raises(ValueError, match="corrupt or desynced"):
            next(iter(src))
        t.join(timeout=20.0)


@pytest.mark.timeout(30)
def test_socket_source_dead_socket_times_out():
    """CI must never hang on a dead socket: a producer that never
    connects surfaces as a timeout error, fast."""
    with SocketSource(port=0, timeout=0.3) as src:
        with pytest.raises(OSError):  # socket.timeout is a TimeoutError
            next(iter(src))


@pytest.mark.timeout(120)
def test_socket_to_pod_end_to_end():
    """The full wire: external producer -> SocketSource -> TaggedBuffer
    -> IngestPipeline -> pod; summaries bit-equal to standalone."""
    pod = _pod(S=2, C=32, T=9)
    rng = np.random.RandomState(8)
    sids, X = _tagged(rng, 64, [40, 41])
    st = _admit_all(pod, pod.init(), [40, 41])
    with SocketSource(port=0, timeout=30.0) as src:

        def producer():
            sock = connect_producer(src.host, src.port, timeout=30.0)
            for lo in range(0, 64, 16):
                send_frame(sock, sids[lo:lo + 16], X[lo:lo + 16])
            sock.close()

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        buf = TaggedBuffer(capacity=256, policy="block")
        pipe = IngestPipeline(pod, buffer=buf, batch=32, get_timeout=30.0)
        pipe.feed_from(src)
        st, stats = pod.serve(st, pipe)
        t.join(timeout=30.0)
    assert stats["items"] == 64
    _assert_sessions_match_standalone(pod, st, _per_session(sids, X))
