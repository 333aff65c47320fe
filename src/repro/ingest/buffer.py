"""Bounded tagged buffer between producers and the pod's ingest loop.

The decoupling point of the ingest subsystem: producer threads (a socket
reader, a generator feeder) ``put`` tagged items in, the pipeline
``get``s fixed-size device batches out.  Because the stream is
unbounded and the device rate is finite, the buffer must answer the
only question that matters under overload — *who loses data, and is it
counted?* — which is Stream Clipper's (Zhou, 1606.00389) drop/defer
framing:

  * ``block``        defer: the producer waits for room (lossless; the
                     right policy when the producer can be paused —
                     e.g. a local generator);
  * ``drop-newest``  clip the arriving item (the classic admission
                     bound: what is in the buffer is older and already
                     paid for);
  * ``drop-oldest``  clip from the *longest* session queue's head (the
                     freshest view wins; heavy tenants lose first, so
                     one noisy stream cannot starve the quiet ones).

Drops are counted **per session** — under summarization, losing items
is semantically fine (the algorithms subsample by design) but losing
them *silently and unevenly* is not.

Ahead of the capacity wall sit two admission policies (``repro.ingest.
shedding``): an optional per-session token-bucket ``rate_limit``
(items a hot producer sends beyond its budget are *throttled*) and an
optional ``shed`` watermark ladder that escalates admit-all ->
Bernoulli subsampling (1802.07098) -> Stream Clipper-style
two-threshold clipping (1606.00389) as fill crosses watermarks.  Their
ledgers (``throttled``, ``sheds``, per-policy shed counts) are kept
strictly separate from the overflow ``drops`` ledger: a shed is a
*policy* outcome with a stated guarantee, an overflow drop is the
accident the policies exist to prevent — ``drops_total{layer,reason}``
stays truthful because the two never mix (``total_drops()`` counts
overflow only; ``total_sheds()``/``total_throttled()`` the rest).

Fairness: items live in per-session FIFO queues; ``get`` drains them
round-robin, one item per live session per turn.  Per-session order is
therefore preserved end-to-end (the pod's routing contract); global
interleaving is deliberately NOT preserved — that is the fairness.

Storage: ``put`` copies the rows it admits into one store, an array of
rows with a free list of its slots, and a session's queue holds the
slots of its items in one int64 array.  So the Python work of both
sides scales with sessions and puts, not items, whatever arrays the
producers put.  The store doubles when no slot is free and keeps its
size: the buffer at its fullest plus the batch a ``get`` is copying
out.  ``put`` groups its batch by session with one stable argsort and
appends the slots of each session present in one step — whenever
admission is a prefix decision: the ``block`` policy (the prefix that
fits, then a wait for room, then the rest) and ``drop-newest`` (the
prefix that fits; the rest is clipped).  Where admission decides item by
item — a rate limit or a shed ladder installed, or ``drop-oldest``
(each overflow clips another queue's head) — ``put`` keeps its per-item
loop, and copies the rows it admitted in one step at its end.  ``get``
computes the round-robin per session, not per item: ``r`` full turns,
found from the sorted depths, give each drainable session ``min(depth,
r)`` items, the partial turn one more to the first sessions in order
that still hold items.  ``lease`` takes each session's share of slots
under the lock and leaves the rows in the store; ``get`` then, outside
the lock, puts them in turn order (a stable sort by turn) into the batch
with one ``np.take`` and gives the slots back.  The result is the
item-at-a-time loop's, bit for bit.  A consumer that lays the rows out
itself takes the ``Lease`` instead: the pipeline copies each share
straight into its session's chunk of the device batch
(``repro.ingest.pipeline``), and releases the slots after the copy.

Quiesce (the autoscaler's handoff primitive, DESIGN.md §10): a session
marked ``quiesce``d keeps *receiving* items but ``get`` stops draining
it — its backlog parks in the buffer, uncounted as dropped, until
``release`` (resume draining here) or ``extract`` (hand the backlog to
another pod's buffer, FIFO intact).  The drop-oldest policy spares
quiesced queues while any other queue can pay instead: clipping a
session mid-migration would silently violate the handoff's
zero-drop contract.

Waits are timed where they happen: a contended lock
(``buffer_put_wait_lock`` / ``buffer_get_wait_lock``), a ``put`` on a
full ``block`` buffer (``buffer_put_wait_room``) and a ``get`` below
``min_items`` (``buffer_get_wait_items``) are ``repro.obs`` stages,
opened only when the code really waits, and their seconds add up per
side in ``wait_seconds()`` — the ``buffer_wait_seconds_total`` drain
source, which says whether the pod or its producers set the pace.
Nothing is emitted to the span recorder or the registry while the lock
is held.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from repro import obs
from repro.concurrency import make_lock

from .shedding import RateLimit, ShedPolicy, TokenBucket

POLICIES = ("block", "drop-newest", "drop-oldest")
PAD_SID = -1  # the pod's queue-padding sentinel


class _Fifo:
    """One session's queue: the store slots of its items, oldest first,
    in one int64 array (``idx[head:tail]``)."""

    __slots__ = ("idx", "head", "tail")

    def __init__(self):
        self.idx = np.empty(16, np.int64)
        self.head = self.tail = 0

    def __len__(self) -> int:
        return self.tail - self.head

    def append(self, slots: np.ndarray) -> None:
        m = len(slots)
        if self.tail + m > len(self.idx):
            n = self.tail - self.head
            idx = np.empty(2 * (n + m), np.int64)
            idx[:n] = self.idx[self.head:self.tail]
            self.idx, self.head, self.tail = idx, 0, n
        self.idx[self.tail:self.tail + m] = slots
        self.tail += m

    def push(self, slot) -> None:
        if self.tail == len(self.idx):
            self.append(np.array([slot]))
        else:
            self.idx[self.tail] = slot
            self.tail += 1

    def take(self, k: int) -> np.ndarray:
        """The first ``k`` slots, off the queue (a view, valid until the
        next ``append``)."""
        self.head += k
        return self.idx[self.head - k:self.head]

    def slots(self) -> np.ndarray:
        return self.idx[self.head:self.tail]


def _round_robin(depth: list, max_items: int) -> list:
    """Items each queue gives to a batch of at most ``max_items`` taken
    one per queue per turn, queues in order: ``r`` full turns, then one
    more item from each of the first queues still holding one."""
    left = max_items
    for i, c in enumerate(sorted(depth)):
        if c * (len(depth) - i) > left:  # ``r`` turns end inside queue i
            r = left // (len(depth) - i)
            break
        left -= c  # queue i empties within the ``r`` turns
    else:
        return depth
    take = [min(c, r) for c in depth]
    rest = max_items - sum(take)
    for i, c in enumerate(depth):
        if not rest:
            break
        if c > r:
            take[i] += 1
            rest -= 1
    return take


class TaggedBuffer:
    """Bounded, thread-safe, per-session-fair tagged item buffer.

    ``rate_limit`` installs a default per-session token bucket
    (override per sid via :meth:`set_rate_limit`); ``shed`` installs
    the watermark shedding ladder; ``clock`` injects time for the
    buckets (tests pin it — production uses ``time.monotonic``).
    """

    def __init__(self, capacity: int, policy: str = "block", *,
                 rate_limit: Optional[RateLimit] = None,
                 shed: Optional[ShedPolicy] = None,
                 clock: Callable[[], float] = time.monotonic):
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        if policy not in POLICIES:
            raise ValueError(f"unknown policy {policy!r}; one of {POLICIES}")
        self.capacity = capacity
        self.policy = policy
        self.rate_limit = rate_limit
        self.shed = shed
        self._clock = clock
        self._q: "collections.OrderedDict[int, _Fifo]" = \
            collections.OrderedDict()  # sid -> FIFO of store slots
        # the store: every buffered row, and the rows a ``get`` still
        # copies out, in one array; ``_free[:_nfree]`` the other slots
        self._rows: Optional[np.ndarray] = None
        self._free = np.empty(0, np.int64)
        self._nfree = 0
        self._size = 0
        self._quiesced: set = set()  # sids parked: fed, never drained
        self._closed = False
        self._lock = make_lock("TaggedBuffer._lock")
        self._not_full = threading.Condition(self._lock)
        self._not_empty = threading.Condition(self._lock)
        self.drops: Dict[int, int] = {}  # sid -> items clipped (overflow)
        # the admission-policy ledgers — deliberate, per-policy losses,
        # NEVER mixed into ``drops`` (see module docstring)
        self.sheds: Dict[int, int] = {}  # sid -> items shed by the ladder
        self.throttled: Dict[int, int] = {}  # sid -> items rate-limited
        self._shed_by_policy: Dict[str, int] = {}  # rung -> items shed
        self._rung = "admit"
        self._rung_changes = 0
        self._buckets: Dict[int, TokenBucket] = {}
        self._rate_overrides: Dict[int, RateLimit] = {}
        self._wait_s = {"put": 0.0, "get": 0.0}  # side -> seconds waited
        self._admitted = 0  # items put admitted, lifetime
        self._block_items = 0  # of them, admitted session by session
        self._get_blocks = 0  # sessions' shares get took, lifetime

    # ------------------------------------------------------------- properties
    @property
    def size(self) -> int:
        with self._lock:
            return self._size

    @property
    def closed(self) -> bool:
        with self._lock:
            return self._closed

    def drop_counts(self) -> Dict[int, int]:
        with self._lock:
            return dict(self.drops)

    def total_drops(self) -> int:
        """Lifetime items clipped by the *overflow* policy, all
        sessions — monotone by construction (``drops`` only ever
        grows), so the telemetry drain
        (``repro.obs.drain.drain_buffer``) can snapshot it as a counter
        without per-call bookkeeping.  Deliberate losses (shed-ladder
        sheds, rate-limit throttles) are NOT included — they have their
        own ledgers (``total_sheds``/``total_throttled``) and their own
        metric families, so ``drops_total{layer="buffer",
        reason="clipped"}`` keeps meaning what it always meant."""
        with self._lock:
            return sum(self.drops.values())

    def shed_counts(self) -> Dict[int, int]:
        with self._lock:
            return dict(self.sheds)

    def total_sheds(self) -> int:
        """Lifetime items shed by the watermark ladder (all rungs)."""
        with self._lock:
            return sum(self.sheds.values())

    def shed_policy_counts(self) -> Dict[str, int]:
        """Lifetime sheds by ladder rung (``subsample`` / ``clip``) —
        the ``shed_total{policy,...}`` drain source."""
        with self._lock:
            return dict(self._shed_by_policy)

    def throttled_counts(self) -> Dict[int, int]:
        with self._lock:
            return dict(self.throttled)

    def total_throttled(self) -> int:
        """Lifetime items refused by per-session token buckets."""
        with self._lock:
            return sum(self.throttled.values())

    def shed_rung(self) -> str:
        """The ladder rung the last admission decision ran under
        (``admit`` when no shed policy is installed)."""
        with self._lock:
            return self._rung

    def shed_rung_changes(self) -> int:
        """Lifetime rung transitions — escalations are control-plane
        events worth a counter, not one span per item."""
        with self._lock:
            return self._rung_changes

    def set_rate_limit(self, sid: int, limit: Optional[RateLimit]) -> None:
        """Override the default ``rate_limit`` for one session
        (``None`` = unlimited for that session, whatever the default)."""
        with self._lock:
            self._rate_overrides[int(sid)] = limit
            self._buckets.pop(int(sid), None)  # re-built at next put

    def wait_seconds(self) -> Dict[str, float]:
        """Lifetime seconds spent waiting in ``put`` (for the lock or for
        room) and in ``get`` (for the lock or for ``min_items``)."""
        with self._lock:
            return dict(self._wait_s)

    def admitted(self) -> int:
        """Lifetime items ``put`` admitted (``inject`` not included)."""
        with self._lock:
            return self._admitted

    def block_counts(self) -> Dict[str, int]:
        """Lifetime items ``put`` admitted through the block path
        (``put_block_items``) and the sessions' shares ``get`` took
        into batches, one slice of store slots each (``get_blocks``):
        the storage format at work."""
        with self._lock:
            return {"put_block_items": self._block_items,
                    "get_blocks": self._get_blocks}

    def _lock_wait(self, side: str) -> Optional[obs.Stage]:
        """An open ``buffer_<side>_wait_lock`` stage when another thread
        holds the lock, else None; ``_lock_waited`` closes it once the
        lock is held.  A probe of ``locked()``, not a trylock, so the
        ``with self._lock`` that follows stays a blocking acquire that
        lockdep checks every time."""
        if not self._lock.locked():
            return None
        return obs.stage(f"buffer_{side}_wait_lock").__enter__()

    def _lock_waited(self, side: str, st: Optional[obs.Stage]) -> None:
        if st is not None:
            st.__exit__(None, None, None)
            self._wait_s[side] += st.seconds

    def depths(self) -> Dict[int, int]:
        """Per-session queue depth — the autoscaler's load signal (and
        the ``largest-queue`` victim policy's ranking key)."""
        with self._lock:
            return {sid: len(dq) for sid, dq in self._q.items()}

    def quiesced(self) -> set:
        with self._lock:
            return set(self._quiesced)

    def _avail(self) -> int:
        """Drainable items (excludes quiesced sessions' backlogs)."""
        return self._size - sum(
            len(self._q[s]) for s in self._quiesced if s in self._q)

    # ---------------------------------------------------------------- quiesce
    def quiesce(self, sids) -> None:
        """Park ``sids``: ``put`` keeps feeding their queues, ``get``
        stops draining them.  Step 1 of a pod handoff — the victims'
        items buffer here, none dropped, while their summary rows move."""
        with self._lock:
            self._quiesced.update(int(s) for s in np.asarray(sids).ravel())

    def release(self, sids) -> None:
        """Un-park ``sids``; their backlog drains again from here."""
        with self._lock:
            self._quiesced.difference_update(
                int(s) for s in np.asarray(sids).ravel())
            self._not_empty.notify_all()

    def inject(self, sids, rows) -> None:
        """Enqueue relocated items, bypassing capacity and closed checks.

        The migration counterpart of ``extract``: a handoff's parked
        backlog was already admitted (and counted against a buffer's
        capacity) at the source pod — re-admitting it at the target
        must neither block, drop, nor fail because the stream happened
        to close mid-handoff.  Not for producers; ``put`` is."""
        tags = np.asarray(sids).ravel()
        n = min(len(tags), len(rows))
        X = np.asarray(rows[:n], np.float32)
        with self._lock:
            if n:
                self._append_blocks(tags[:n], X)
                self._size += n
            self._not_empty.notify_all()

    def extract(self, sids) -> Tuple[np.ndarray, list]:
        """Atomically remove and return every buffered item of ``sids``
        (per-session FIFO order) — the backlog-migration half of
        ``release``: the caller forwards it to the target pod's buffer.
        Also un-parks the sids here.  -> (sids (M,), [rows])."""
        out_s: list = []
        out_x: list = []
        with self._lock:
            for sid in (int(s) for s in np.asarray(sids).ravel()):
                self._quiesced.discard(sid)
                q = self._q.pop(sid, None)
                if q:
                    out_s.extend([sid] * len(q))
                    out_x.extend(self._rows[q.slots()])
                    self._release(q.slots())
                    self._size -= len(q)
            if out_s:
                self._not_full.notify_all()
        return np.asarray(out_s, np.int32), out_x

    def _fit(self, X: np.ndarray) -> None:
        """Make the store hold rows shaped as ``X``'s (under the lock)."""
        if self._rows is None or (X.shape[1:] != self._rows.shape[1:]
                                  and self._nfree == len(self._rows)):
            self._rows = np.empty((0, *X.shape[1:]), np.float32)
            self._nfree = 0
        elif X.shape[1:] != self._rows.shape[1:]:
            raise ValueError(f"rows of shape {X.shape[1:]} in a buffer of "
                             f"rows of shape {self._rows.shape[1:]}")

    def _grow(self, m: int) -> None:
        """Make ``m`` slots free, doubling the store (under the lock):
        the slots a ``get`` still copies out stay valid in the old
        array, and the rows are in the new one."""
        if self._nfree >= m:
            return
        old = len(self._rows)
        new = max(2 * old, old + m - self._nfree, 1024)
        rows = np.empty((new, *self._rows.shape[1:]), np.float32)
        rows[:old] = self._rows
        free = np.empty(new, np.int64)
        free[:self._nfree] = self._free[:self._nfree]
        free[self._nfree:self._nfree + new - old] = np.arange(old, new)
        self._rows, self._free = rows, free
        self._nfree += new - old

    def _store(self, X: np.ndarray) -> np.ndarray:
        """Copy rows ``X`` into free slots of the store -> the slots
        (under the lock)."""
        m = len(X)
        self._fit(X)
        self._grow(m)
        self._nfree -= m
        slots = self._free[self._nfree:self._nfree + m].copy()
        self._rows[slots] = X
        return slots

    def _release(self, slots: np.ndarray) -> None:
        """Return store slots to the free list (under the lock): the
        next puts take them from the end, in that order, so slots given
        back in order make their copies run in order."""
        k = len(slots)
        self._free[self._nfree:self._nfree + k] = slots
        self._nfree += k

    def _append_blocks(self, sids: np.ndarray, X: np.ndarray) -> None:
        """Queue rows ``X`` as their sessions' next items, session by
        session; sessions new to the buffer join the round-robin in
        order of first appearance, as item-by-item appends would have
        them (under the lock; the caller counts them)."""
        order = np.argsort(sids, kind="stable")
        tags = sids[order]
        starts = np.flatnonzero(np.r_[True, tags[1:] != tags[:-1]])
        ends = np.r_[starts[1:], len(tags)]
        slots = self._store(X)[order]
        first = np.argsort(order[starts])
        for sid, a, b in zip(tags[starts[first]].tolist(),
                             starts[first].tolist(), ends[first].tolist()):
            self._queue(sid).append(slots[a:b])

    def _queue(self, sid: int) -> _Fifo:
        """``sid``'s queue, made (last in the round-robin) if new."""
        q = self._q.get(sid)
        if q is None:
            q = self._q[sid] = _Fifo()
        return q

    # --------------------------------------------------------------- producer
    def _admit_rate(self, sid: int, now: float) -> bool:
        """Token-bucket check for one arriving item (under the lock)."""
        limit = self._rate_overrides.get(sid, self.rate_limit)
        if limit is None:
            return True
        bucket = self._buckets.get(sid)
        if bucket is None:
            bucket = self._buckets[sid] = TokenBucket(limit, now)
        return bucket.allow(now)

    def _admit_shed(self, sid: int) -> bool:
        """Watermark-ladder check for one arriving item (under the
        lock); counts the shed and the rung transition if any."""
        ok, rung = self.shed.decide(
            size=self._size, capacity=self.capacity,
            depth=len(self._q[sid]) if sid in self._q else 0,
            n_live=len(self._q))
        if rung != self._rung:
            self._rung = rung
            self._rung_changes += 1
        if not ok:
            self.sheds[sid] = self.sheds.get(sid, 0) + 1
            self._shed_by_policy[rung] = \
                self._shed_by_policy.get(rung, 0) + 1
        return ok

    def _admit_items(self, tags: list, X: np.ndarray, lo: int,
                     now: float) -> Tuple[int, int, bool]:
        """Per-item admission from item ``lo`` on (under the lock): each
        item (row ``X[j]``) meets the token bucket, the shed ladder and
        the capacity in turn and, admitted, takes a slot and its place
        in its queue at once; the rows are copied in one step on the
        way out.  -> (the item it stopped at, items not admitted,
        whether that item passed its checks and waits for room under
        ``block``)."""
        dropped = 0
        self._fit(X)
        # slot -> item; a slot clipped and taken again holds its last item
        held: Dict[int, int] = {}
        try:
            for j in range(lo, len(tags)):
                sid = tags[j]
                if not self._admit_rate(sid, now):
                    self.throttled[sid] = self.throttled.get(sid, 0) + 1
                    dropped += 1
                    continue
                if self.shed is not None and not self._admit_shed(sid):
                    dropped += 1
                    continue
                if self._size >= self.capacity:
                    if self.policy == "block":
                        return j, dropped, True
                    if self.policy == "drop-newest":
                        self.drops[sid] = self.drops.get(sid, 0) + 1
                        dropped += 1
                        continue
                    # drop-oldest: clip the longest queue's head; quiesced
                    # sessions are mid-migration: clipping them breaks the
                    # handoff's zero-drop contract, so they only pay when
                    # no one else can
                    pool = [s for s in self._q if s not in
                            self._quiesced] or list(self._q)
                    victim = max(pool, key=lambda s: len(self._q[s]))
                    self._release(self._q[victim].take(1))
                    if not self._q[victim]:
                        del self._q[victim]
                    self._size -= 1
                    self.drops[victim] = self.drops.get(victim, 0) + 1
                    dropped += 1
                if not self._nfree:
                    self._grow(1)
                self._nfree -= 1
                slot = int(self._free[self._nfree])
                held[slot] = j
                q = self._q.get(sid)
                (self._queue(sid) if q is None else q).push(slot)
                self._size += 1
                self._admitted += 1
            return len(tags), dropped, False
        finally:
            if held:
                self._rows[np.fromiter(held, np.int64, len(held))] = X.take(
                    np.fromiter(held.values(), np.int64, len(held)), axis=0)

    def _admit_prefix(self, sids: np.ndarray, X: np.ndarray,
                      lo: int) -> Tuple[int, int, bool]:
        """Block admission from item ``lo`` on (under the lock): the
        prefix that fits joins the queues session by session; the rest
        waits for room under ``block`` or is clipped under
        ``drop-newest``.  -> as ``_admit_items``."""
        n = len(sids)
        room = self.capacity - self._size
        if room > 0:
            hi = min(n, lo + room)
            self._append_blocks(sids[lo:hi], X[lo:hi])
            self._size += hi - lo
            self._admitted += hi - lo
            self._block_items += hi - lo
            return hi, 0, False
        if self.policy == "block":
            return lo, 0, True
        # drop-newest: nothing drains while the lock is held, so the
        # rest of the call finds the buffer full
        for sid, m in zip(*(a.tolist() for a in np.unique(
                sids[lo:], return_counts=True))):
            self.drops[sid] = self.drops.get(sid, 0) + m
        return n, n - lo, False

    def put(self, sids, X, timeout: Optional[float] = None) -> int:
        """Enqueue a tagged batch; returns the number of items *not*
        admitted (rate-limit throttles + ladder sheds + overflow drops
        — each counted in its own ledger).

        Admission order per item: token bucket (throttle), shed ladder
        (policy shed), then capacity.  ``block`` waits for room
        (``timeout`` seconds per stall, None = forever) and raises
        ``TimeoutError`` on expiry, keeping what it admitted before;
        the drop policies never wait.  Raises ``ValueError`` after
        ``close()``.  Without a rate limit or a shed ladder, and under
        ``block`` or ``drop-newest``, admission is a prefix decision
        and the batch is queued session by session (module docstring).
        """
        sids = np.asarray(sids, np.int32).ravel()
        X = np.asarray(X, np.float32)
        n = min(len(sids), len(X))
        sids = sids[:n]
        dropped = 0
        now = self._clock() if self.rate_limit or self._rate_overrides \
            else 0.0
        waiting = self._lock_wait("put")
        with self._lock:
            self._lock_waited("put", waiting)
            per_item = (self.shed is not None
                        or self.policy == "drop-oldest"
                        or self.rate_limit is not None
                        or any(v is not None
                               for v in self._rate_overrides.values()))
            tags = sids.tolist() if per_item else None
            admitted = self._admitted
            lo = 0
            while lo < n:
                if self._closed:
                    raise ValueError("put() on a closed TaggedBuffer")
                if per_item:
                    lo, lost, full = self._admit_items(tags, X, lo, now)
                else:
                    lo, lost, full = self._admit_prefix(sids, X, lo)
                dropped += lost
                if not full:
                    continue
                self._not_empty.notify_all()  # the getter makes room
                with obs.stage("buffer_put_wait_room") as st:
                    room = self._not_full.wait_for(
                        lambda: self._size < self.capacity
                        or self._closed, timeout)
                self._wait_s["put"] += st.seconds
                if not room:
                    raise TimeoutError(
                        f"TaggedBuffer full ({self.capacity}) for "
                        f"{timeout}s")
                if self._closed:
                    raise ValueError("put() on a closed TaggedBuffer")
                if per_item:  # the stalled item passed its checks
                    self._append_blocks(sids[lo:lo + 1], X[lo:lo + 1])
                    self._size += 1
                    self._admitted += 1
                    lo += 1
            if self._admitted > admitted:
                self._not_empty.notify_all()  # waiters may need min_items
        return dropped

    def close(self) -> None:
        """End-of-stream: wake every waiter; ``get`` drains what is left."""
        with self._lock:
            self._closed = True
            self._not_empty.notify_all()
            self._not_full.notify_all()

    # --------------------------------------------------------------- consumer
    def lease(self, max_items: int, *, timeout: Optional[float] = None,
              min_items: int = 1, per_session: Optional[int] = None
              ) -> Optional["Lease"]:
        """Take up to ``max_items`` items off the queues, round-robin across
        sessions, and leave their rows in the store: -> a ``Lease`` of the
        sessions' shares, or ``None`` once the buffer is closed and
        drained.  The consumer step under ``get`` (which see for
        ``timeout``, ``min_items`` and ``per_session``); a caller that
        copies the rows itself releases the lease when done."""
        need = max(1, min(min_items, max_items))
        waiting = self._lock_wait("get")
        with self._lock:
            self._lock_waited("get", waiting)
            # quiesced backlogs are invisible here: they neither satisfy
            # the fill threshold nor drain (they belong to a migrating
            # session and leave via extract/release)
            if not (self._avail() >= need or self._closed):
                with obs.stage("buffer_get_wait_items") as st:
                    filled = self._not_empty.wait_for(
                        lambda: self._avail() >= need or self._closed,
                        timeout)
                self._wait_s["get"] += st.seconds
                if not filled:
                    raise TimeoutError(
                        f"TaggedBuffer below {need} items for {timeout}s")
            if self._avail() == 0:  # closed and drained (of drainables)
                return None
            live = [(sid, q) for sid, q in self._q.items()
                    if sid not in self._quiesced]
            who, ks, parts = [], [], []  # each session's share, in order
            depth = [len(q) for _, q in live]
            if per_session is not None:
                depth = [min(c, per_session) for c in depth]
            for (sid, q), k in zip(live, _round_robin(depth, max_items)):
                if k:
                    who.append(sid)
                    ks.append(k)
                    parts.append(q.take(k))
                    if not q:
                        del self._q[sid]
            at = np.concatenate(parts)  # their slots, session by session
            # a put that grows the store leaves these rows in this array
            lease = Lease(self, np.array(who, np.int32),
                          np.array(ks, np.int64), at, self._rows)
            self._size -= len(at)
            self._get_blocks += len(parts)
            self._not_full.notify_all()
        # as unsigned, a negative slot is out of range too
        if at.view(np.uint64).max() >= len(lease.store):
            lease.release()
            raise RuntimeError("TaggedBuffer: a queued slot lies outside "
                               "the store")
        return lease

    def _give_back(self, slots: np.ndarray) -> None:
        """Free a lease's slots, in store order (``_release``)."""
        # nearly sorted already: the puts' order
        slots = np.sort(slots, kind="stable")
        with self._lock:
            self._release(slots)

    def get(self, max_items: int, *, pad_to: Optional[int] = None,
            timeout: Optional[float] = None, d: Optional[int] = None,
            min_items: int = 1, per_session: Optional[int] = None
            ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Dequeue up to ``max_items`` items, round-robin across sessions.

        Blocks until at least ``min_items`` are available (or the buffer
        is closed — then drains what is left, however little, and
        finally returns ``None``, the end-of-stream sentinel).  A
        ``min_items`` near the device batch size keeps a fast consumer
        from burning full jitted steps on near-all-padding batches when
        the producer trickles; the default of 1 favors latency.
        ``timeout`` raises ``TimeoutError`` on an open-but-underfilled
        buffer.  ``pad_to`` right-pads the batch with (PAD_SID,
        zero-row) entries to a fixed length — the shape contract of the
        jitted pod program.  ``d`` is ignored: the buffered rows give
        the width, since a batch holds at least one item (it stays for
        the callers that pass it).  ``per_session`` caps the items one
        session gives a batch (the round-robin ends a queue's turns
        there): the pod's per-session ``chunk``, so that a backlog left
        in few sessions, as at the end of a stream, never overflows
        them.  The batch then holds fewer than ``max_items`` items.
        """
        lease = self.lease(max_items, timeout=timeout, min_items=min_items,
                           per_session=per_session)
        if lease is None:
            return None
        with lease:  # the slots are free once their rows are copied out
            # a session's k-th item of this batch goes out in turn k: a
            # stable sort by turn gives the round-robin order
            take, n = lease.counts, lease.items
            turn = np.arange(n) - (take.cumsum() - take).repeat(take)
            order = turn.argsort(kind="stable")
            rows = max(n, pad_to or 0)
            X = (np.zeros if rows > n else np.empty)(
                (rows, *lease.store.shape[1:]), np.float32)
            lease.store.take(lease.slots[order], axis=0, out=X[:n],
                             mode="clip")
        sids = np.empty(rows, np.int32)
        sids[:n] = lease.sids.repeat(take)[order]
        sids[n:] = PAD_SID
        return sids, X


class Lease:
    """One batch's shares, off their queues, their rows still in the store.

    ``sids`` (m,) int32 the sessions in round-robin order, ``counts`` (m,)
    the items each gives, ``slots`` (n,) their store slots, session by
    session and each session's oldest first, ``store`` the array the
    slots index (a put that doubles the store meanwhile leaves it
    valid).  ``release`` (or leaving a ``with`` block) frees the slots
    once their rows are copied out; until then no put reuses them.
    """

    __slots__ = ("sids", "counts", "slots", "store", "_buffer")

    def __init__(self, buffer: TaggedBuffer, sids: np.ndarray,
                 counts: np.ndarray, slots: np.ndarray, store: np.ndarray):
        self.sids, self.counts, self.slots = sids, counts, slots
        self.store = store
        self._buffer: Optional[TaggedBuffer] = buffer

    @property
    def items(self) -> int:
        return len(self.slots)

    def release(self) -> None:
        """Give the slots back to the buffer; a second call does nothing."""
        buffer, self._buffer = self._buffer, None
        if buffer is not None:
            buffer._give_back(self.slots)

    def __enter__(self) -> "Lease":
        return self

    def __exit__(self, *exc) -> None:
        self.release()
