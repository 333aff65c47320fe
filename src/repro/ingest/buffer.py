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
rows with a free list of its slots.  The queues are arrays, not one
object per session.  Each session has a dense index (the known ids are
kept sorted, and ``searchsorted`` finds an id's index) and, per index,
the sequence numbers of its oldest queued item (head) and of its next
(tail), and a round-robin key, set when its queue goes from empty to
non-empty, so that a new or emptied session joins the round-robin last.
Every queued item has an entry (store slot, session index, sequence
number) in one log, appended a put at a time; an entry whose sequence
number lies below its session's head is dead, and the log drops dead
entries when it needs room.  So the work of both sides is a fixed number
of array steps a call, whatever the number of sessions a put touches
and whatever arrays the producers put; memory is the store, the log
(at most twice the items queued plus a put) and a few numbers per
session.  The store doubles when no slot is free and keeps its size: the
buffer at its fullest plus the batch a ``get`` is copying out.  ``put``
groups its batch by session with one stable argsort and appends it to
the log in one step — whenever admission is a prefix decision: the
``block`` policy (the prefix that fits, then a wait for room, then the
rest) and ``drop-newest`` (the prefix that fits; the rest is clipped).
Where admission decides item by item — a rate limit or a shed ladder
installed, or ``drop-oldest`` (each overflow clips another queue's
head) — ``put`` keeps its per-item decisions, writes each admitted item
into the same arrays, and copies the rows it admitted in one step at its
end.  ``get`` computes the round-robin per session, not per item: ``r``
full turns, found from the sorted depths, give each drainable session
``min(depth, r)`` items, the partial turn one more to the first sessions
in order that still hold items.  ``lease`` takes each session's share of
slots under the lock and leaves the rows in the store; ``get`` then,
outside the lock, puts them in turn order (a stable sort by turn) into
the batch with one ``np.take`` and gives the slots back.  The result is
the item-at-a-time loop's, bit for bit.  A consumer that lays the rows
out itself takes the ``Lease`` instead: the pipeline copies each share
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

import threading
import time
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from repro import obs
from repro.concurrency import make_lock

from .shedding import RateLimit, ShedPolicy, TokenBucket

POLICIES = ("block", "drop-newest", "drop-oldest")
PAD_SID = -1  # the pod's queue-padding sentinel
_SCAN = 1 << 14  # log entries a lease or a clip reads a step, until done


def _round_robin(depth: np.ndarray, max_items: int) -> np.ndarray:
    """Items each queue gives to a batch of at most ``max_items`` taken
    one per queue per turn, queues in order: ``r`` full turns, then one
    more item from each of the first queues still holding one."""
    if depth.sum() <= max_items:
        return depth
    srt = np.sort(depth)
    # at sorted queue i: the items left once the shallower queues are
    # empty, and the queues left to share them
    left = max_items - (np.cumsum(srt) - srt)
    queues = np.arange(len(srt), 0, -1)
    i = np.argmax(srt * queues > left)  # ``r`` turns end inside queue i
    r = left[i] // queues[i]
    take = np.minimum(depth, r)
    take[np.flatnonzero(depth > r)[:max_items - take.sum()]] += 1
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
        # sessions, by a dense index given as ids are first seen: ``_ids``
        # the known ids sorted, ``_at`` the index of each; per index the
        # id, the sequence numbers of the oldest queued item (``_head``)
        # and of the next (``_tail``), and the round-robin key (``_turn``)
        self._ids = np.empty(0, np.int32)
        self._at = np.empty(0, np.int64)
        self._sid = np.empty(0, np.int32)
        self._head = np.empty(0, np.int64)
        self._tail = np.empty(0, np.int64)
        self._turn = np.empty(0, np.int64)
        self._nsess = 0
        self._nlive = 0  # sessions with queued items
        self._next_turn = 0
        # the log: (store slot, session index, sequence number) of every
        # queued item in ``[_lo, _hi)``, a put after another, each grouped
        # by session, so a session's entries lie in sequence order; dead
        # entries (sequence number below their session's head) among them
        self._slot = np.empty(0, np.int64)
        self._sess = np.empty(0, np.int64)
        self._seq = np.empty(0, np.int64)
        self._lo = self._hi = 0
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
        self._block_items = 0  # of them, admitted as a prefix
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
        """Lifetime items ``put`` admitted as a prefix, by array steps
        (``put_block_items``), and the sessions' shares ``get`` took
        into batches (``get_blocks``): the storage format at work."""
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
            live = self._live()
            return dict(zip(self._sid[live].tolist(),
                            (self._tail[live] - self._head[live]).tolist()))

    def quiesced(self) -> set:
        with self._lock:
            return set(self._quiesced)

    def _avail(self) -> int:
        """Drainable items (excludes quiesced sessions' backlogs)."""
        if not self._quiesced:
            return self._size
        parked = self._parked()
        return self._size - int((self._tail[parked]
                                 - self._head[parked]).sum())

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
        tags = np.asarray(sids, np.int32).ravel()
        n = min(len(tags), len(rows))
        X = np.asarray(rows[:n], np.float32)
        with self._lock:
            if n:
                self._enqueue(tags[:n], X)
                self._size += n
            self._not_empty.notify_all()

    def extract(self, sids) -> Tuple[np.ndarray, list]:
        """Atomically remove and return every buffered item of ``sids``
        (per-session FIFO order) — the backlog-migration half of
        ``release``: the caller forwards it to the target pod's buffer.
        Also un-parks the sids here.  -> (sids (M,), [rows])."""
        tags = np.asarray(sids).ravel()
        with self._lock:
            self._quiesced.difference_update(int(s) for s in tags)
            who = self._find(tags)  # a session's first mention, if queued
            who = who[np.sort(np.unique(who, return_index=True)[1])]
            who = who[who >= 0]
            who = who[self._tail[who] > self._head[who]]
            if not len(who):
                return np.empty(0, np.int32), []
            k = self._tail[who] - self._head[who]
            slots = self._take(who, k, self._tail[who])
            out_x = list(self._rows[slots])
            self._release(slots)
            self._size -= len(slots)
            self._not_full.notify_all()
        return self._sid[who].repeat(k), out_x

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

    # ---------------------------------------------------------------- queues
    def _find(self, sids: np.ndarray) -> np.ndarray:
        """The index of each of ``sids``, -1 where the buffer has not
        seen the id (under the lock)."""
        if not len(self._ids):
            return np.full(len(sids), -1, np.int64)
        pos = np.searchsorted(self._ids, sids)
        np.minimum(pos, len(self._ids) - 1, out=pos)
        return np.where(self._ids[pos] == sids, self._at[pos], -1)

    def _index(self, sids: np.ndarray) -> np.ndarray:
        """The index of each of ``sids``, new ids given the next ones
        (under the lock)."""
        idx = self._find(sids)
        if (idx >= 0).all():
            return idx
        new = np.unique(sids[idx < 0]).astype(np.int32)
        a, b = self._nsess, self._nsess + len(new)
        if b > len(self._sid):
            size = max(2 * len(self._sid), b, 64)
            for name in ("_sid", "_head", "_tail", "_turn"):
                old = getattr(self, name)
                arr = np.zeros(size, old.dtype)
                arr[:a] = old[:a]
                setattr(self, name, arr)
        self._sid[a:b] = new
        self._head[a:b] = self._tail[a:b] = 0
        at = np.searchsorted(self._ids, new)
        self._ids = np.insert(self._ids, at, new)
        self._at = np.insert(self._at, at, np.arange(a, b))
        self._nsess = b
        return self._find(sids)

    def _live(self) -> np.ndarray:
        """The indices of the sessions with queued items, in round-robin
        order (under the lock)."""
        n = self._nsess
        live = np.flatnonzero(self._tail[:n] > self._head[:n])
        return live[np.argsort(self._turn[live])]

    def _parked(self) -> np.ndarray:
        """The indices of the quiesced sessions the buffer has seen
        (under the lock)."""
        idx = self._find(np.fromiter(self._quiesced, np.int64,
                                     len(self._quiesced)))
        return idx[idx >= 0]

    def _room(self, m: int) -> None:
        """Make room for ``m`` more log entries (under the lock): the
        live entries move to the front, in the log's order, into an
        array of twice what they and the ``m`` need, if the one there is
        smaller."""
        if self._hi + m <= len(self._slot):
            return
        lo, hi = self._lo, self._hi
        keep = lo + np.flatnonzero(
            self._seq[lo:hi] >= self._head[self._sess[lo:hi]])
        n = len(keep)
        size = max(2 * (n + m), 1024)
        for name in ("_slot", "_sess", "_seq"):
            old = getattr(self, name)
            arr = np.empty(size, old.dtype) if size > len(old) else old
            arr[:n] = old[keep]
            setattr(self, name, arr)
        self._lo, self._hi = 0, n

    def _enqueue(self, sids: np.ndarray, X: np.ndarray) -> None:
        """Queue rows ``X`` as their sessions' next items: a fixed number
        of array steps, however many sessions the batch touches.  The
        batch goes into the log grouped by session (one stable argsort),
        an item's sequence number its session's tail plus its rank in
        its group; sessions whose queue was empty join the round-robin in
        order of first appearance, as item-by-item appends would have
        them (under the lock; the caller counts the items)."""
        n = len(sids)
        slots = self._store(X)
        sess = self._index(sids)
        order = np.argsort(sess, kind="stable")
        sess = sess[order]
        starts = np.flatnonzero(np.r_[True, sess[1:] != sess[:-1]])
        who = sess[starts]
        k = np.diff(np.r_[starts, n])
        tail = self._tail[who]
        joins = tail == self._head[who]
        if joins.any():
            first = order[starts[joins]]  # each joiner's first item
            j = who[joins][np.argsort(first)]
            self._turn[j] = np.arange(self._next_turn,
                                      self._next_turn + len(j))
            self._next_turn += len(j)
            self._nlive += len(j)
        self._tail[who] = tail + k
        self._room(n)
        lo, hi = self._hi, self._hi + n
        self._slot[lo:hi] = slots[order]
        self._sess[lo:hi] = sess
        self._seq[lo:hi] = (tail - starts).repeat(k) + np.arange(n)
        self._hi = hi

    def _push(self, s: int, slot: int) -> None:
        """Queue store slot ``slot`` as session index ``s``'s next item
        (under the lock; the caller counts it)."""
        self._room(1)
        t = int(self._tail[s])
        if t == self._head[s]:  # joins the round-robin, last
            self._turn[s] = self._next_turn
            self._next_turn += 1
            self._nlive += 1
        self._slot[self._hi] = slot
        self._sess[self._hi] = s
        self._seq[self._hi] = t
        self._hi += 1
        self._tail[s] = t + 1

    def _take(self, who: np.ndarray, k: np.ndarray,
              head: np.ndarray) -> np.ndarray:
        """Take the first ``k`` queued items of each of the sessions
        ``who`` off their queues, their heads set to ``head`` -> their
        store slots, session by session in ``who``'s order, each oldest
        first (under the lock).  The log is read from its front a step
        at a time, until every item is found: a session's items lie in
        the log in sequence order, so the wanted ones are the first live
        entries of their sessions, and the entries before the first
        entry still live afterwards are all dead and trimmed off."""
        n = int(k.sum())
        want = np.zeros(self._nsess, np.int64)
        want[who] = k
        off = np.zeros(self._nsess, np.int64)
        off[who] = k.cumsum() - k - self._head[who]
        out = np.empty(n, np.int64)
        got, i, trim = 0, self._lo, None
        while got < n and i < self._hi:
            j = min(i + _SCAN, self._hi)
            sess, seq = self._sess[i:j], self._seq[i:j]
            rank = seq - self._head[sess]  # negative: dead
            w = want[sess]
            hit = rank.view(np.uint64) < w.view(np.uint64)
            at = off[sess] + seq
            m = np.count_nonzero(hit)
            if m == j - i:
                out[at] = self._slot[i:j]
            else:
                out[at[hit]] = self._slot[i:j][hit]
                if trim is None:
                    alive = rank >= w
                    if alive.any():
                        trim = i + int(alive.argmax())
            got += m
            i = j
        if got != n:
            raise RuntimeError("TaggedBuffer: the log lost a queued item")
        self._lo = i if trim is None else trim
        self._nlive -= int((head == self._tail[who]).sum())
        self._head[who] = head
        return out

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

    def _admit_shed(self, sid: int, s: int) -> bool:
        """Watermark-ladder check for one arriving item of session index
        ``s`` (under the lock); counts the shed and the rung transition
        if any."""
        ok, rung = self.shed.decide(
            size=self._size, capacity=self.capacity,
            depth=int(self._tail[s] - self._head[s]), n_live=self._nlive)
        if rung != self._rung:
            self._rung = rung
            self._rung_changes += 1
        if not ok:
            self.sheds[sid] = self.sheds.get(sid, 0) + 1
            self._shed_by_policy[rung] = \
                self._shed_by_policy.get(rung, 0) + 1
        return ok

    def _clip_oldest(self) -> None:
        """Drop the head of the longest queue, the first in round-robin
        order among equals (under the lock).  Quiesced sessions are
        mid-migration: clipping them breaks the handoff's zero-drop
        contract, so they only pay when no one else can."""
        n = self._nsess
        depth = self._tail[:n] - self._head[:n]
        pool = depth > 0
        if self._quiesced:
            spared = pool.copy()
            spared[self._parked()] = False
            if spared.any():
                pool = spared
        longest = np.flatnonzero(pool & (depth == depth[pool].max()))
        v = int(longest[np.argmin(self._turn[longest])])
        i = self._lo  # its head entry, read from the log's front a step
        while i < self._hi:  # at a time: the oldest items lie there
            j = min(i + _SCAN, self._hi)
            at = np.flatnonzero((self._sess[i:j] == v)
                                & (self._seq[i:j] == self._head[v]))
            if len(at):
                i += int(at[0])
                break
            i = j
        else:
            raise RuntimeError("TaggedBuffer: the log lost a queued item")
        self._release(self._slot[i:i + 1])
        self._head[v] += 1
        if self._head[v] == self._tail[v]:
            self._nlive -= 1
        self._size -= 1
        sid = int(self._sid[v])
        self.drops[sid] = self.drops.get(sid, 0) + 1

    def _admit_items(self, tags: list, sess: list, X: np.ndarray, lo: int,
                     now: float) -> Tuple[int, int, bool]:
        """Per-item admission from item ``lo`` on (under the lock): each
        item (row ``X[j]``, session index ``sess[j]``) meets the token
        bucket, the shed ladder and the capacity in turn and, admitted,
        takes a slot and its place in its queue at once; the rows are
        copied in one step on the way out.  -> (the item it stopped at,
        items not admitted, whether that item passed its checks and
        waits for room under ``block``)."""
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
                if self.shed is not None and \
                        not self._admit_shed(sid, sess[j]):
                    dropped += 1
                    continue
                if self._size >= self.capacity:
                    if self.policy == "block":
                        return j, dropped, True
                    if self.policy == "drop-newest":
                        self.drops[sid] = self.drops.get(sid, 0) + 1
                        dropped += 1
                        continue
                    self._clip_oldest()
                    dropped += 1
                if not self._nfree:
                    self._grow(1)
                self._nfree -= 1
                slot = int(self._free[self._nfree])
                held[slot] = j
                self._push(sess[j], slot)
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
        prefix that fits joins the queues in one step; the rest
        waits for room under ``block`` or is clipped under
        ``drop-newest``.  -> as ``_admit_items``."""
        n = len(sids)
        room = self.capacity - self._size
        if room > 0:
            hi = min(n, lo + room)
            self._enqueue(sids[lo:hi], X[lo:hi])
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
        and the batch is queued in one step (module docstring).
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
            if per_item:
                tags, sess = sids.tolist(), self._index(sids).tolist()
            admitted = self._admitted
            lo = 0
            while lo < n:
                if self._closed:
                    raise ValueError("put() on a closed TaggedBuffer")
                if per_item:
                    lo, lost, full = self._admit_items(tags, sess, X, lo,
                                                       now)
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
                    self._enqueue(sids[lo:lo + 1], X[lo:lo + 1])
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
        copies the rows itself releases the lease when done.

        The shares come from ``_round_robin`` over the depths (tail less
        head) of the drainable sessions in round-robin order.  Then one
        pass over the front of the log, with no sort: an entry is taken
        when its rank in its queue (sequence number less head) is below
        its session's share, and lands in the lease at its session's
        offset plus that rank.  The pass stops once every share is
        found, and the dead entries it leaves at the log's front are
        trimmed off (``_take``)."""
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
            live = self._live()
            if self._quiesced:
                live = live[~np.isin(live, self._parked())]
            depth = self._tail[live] - self._head[live]
            if per_session is not None:
                np.minimum(depth, per_session, out=depth)
            k = _round_robin(depth, max_items)
            who, k = live[k > 0], k[k > 0]  # each session's share, in order
            at = self._take(who, k, self._head[who] + k)
            # a put that grows the store leaves these rows in this array
            lease = Lease(self, self._sid[who], k, at, self._rows)
            self._size -= len(at)
            self._get_blocks += len(who)
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
