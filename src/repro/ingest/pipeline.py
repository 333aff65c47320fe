"""Double-buffered ingest: host routing overlapped with the device step.

The synchronous feed (``jit(pod.ingest)`` per batch) serializes three
stages that have no business being serial: building the tagged batch on
host, the routing scatter, and the vmapped ``run_batched`` program.
``IngestPipeline`` splits them:

    device |  advance(i-1)  |   advance(i)    |  advance(i+1)  |
    host   | route(i) put(i)| route(i+1) put  | route(i+2) ...  |

  * routing moves to host, so the device program is
    ``ingest_routed``: run_batched + counters only, no slot lookup or
    scatter on its critical path.  The sorted table of live ids is
    built once per run.  A batch is one copy: ``TaggedBuffer.lease``
    hands over each session's share (its store slots, FIFO),
    ``share_slots`` finds the share's slot once, and ``fill_chunks``
    copies the rows from the store straight into the next of two reused
    (S, C, d) host arrays (``_ChunkRing``), zeroing only the rows that
    array held last time and not now; an array is refilled only once
    the step that read it, two batches back, has ended, which on the
    chip it long has.  The chunks are the ones ``SummarizerPod.route``
    makes of ``TaggedBuffer.get``'s padded batch, bit for bit (pinned
    by tests);
  * JAX's async dispatch provides the overlap: ``advance(i)`` returns
    as soon as the program is enqueued, and the host spends the device
    step's wall time leasing and copying batch i+1, then
    ``jax.device_put``-ing it;
  * the pod state is donated to the jitted step (off-CPU), so the
    stacked session pytree is updated in place — no per-step state
    round-trips.

Routing on host is legal precisely because the slot table (sid, active)
only changes through lifecycle calls (admit/evict), never through
``ingest`` itself — ``run()`` snapshots it once at entry, and lifecycle
ops between ``run()`` calls are picked up by the next snapshot
(drift resets keep slots, so ``serve``'s periodic ``drift_check`` needs
no re-snapshot).

Every batch comes from a ``TaggedBuffer`` that producers fill (sockets,
generators, a router).  ``feed_from(source)`` spawns a producer thread
for a tagged ``Source``; a replay ``put``s its batches and ``close``s
the buffer before ``run``.

``PodRouter`` is the fleet front-end above all of that: one ingress
point fanning a tagged stream out to N pods' buffers through a host
routing table (sid -> pod id), with the table flip + backlog migration
primitive the ``serve.autoscale.PodAutoscaler`` drives (DESIGN.md §10).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from repro import obs
from repro.compat import hashable_lru
from repro.concurrency import make_lock

from .buffer import TaggedBuffer
from .sources import Source


def live_table(sid_table: np.ndarray, active: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray]:
    """The live sessions' ids, sorted, and the slot that holds each.

    (sid_table (S,), active (S,)) -> (ids (L,), slots (L,)).  The sort
    is stable, so a live id held by several slots lists its first slot
    first.  Built once per ``IngestPipeline.run`` with the slot-table
    snapshot: the table only changes through lifecycle calls.
    """
    live = np.flatnonzero(np.asarray(active, bool))
    ids = np.asarray(sid_table, np.int32)[live]
    order = np.argsort(ids, kind="stable")
    return ids[order], live[order]


def share_slots(table: Tuple[np.ndarray, np.ndarray], sids: np.ndarray,
                sessions: int) -> np.ndarray:
    """The slot of each id: (``live_table`` output, sids (N,), S) -> (N,).

    A binary search of the sorted live ids finds an id in O(log S); it is
    found when the id at the found position equals it, and its slot is
    then the first live slot holding it.  An id with no live session
    (unknown, ``PAD_SID``, stale on a freed slot) gets the trash row S.
    """
    ids, slots = table
    sids = np.asarray(sids, np.int32)
    if not len(ids):
        return np.full((len(sids),), sessions, np.int64)
    at = np.minimum(np.searchsorted(ids, sids), len(ids) - 1)
    return np.where(ids[at] == sids, slots[at], sessions)


# the copy stages its rows through a block of this size, which stays in
# cache between the gather and the scatter
_BLOCK_BYTES = 1 << 19


def _ranges(starts: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """The ranges ``[starts[i], starts[i] + lengths[i])``, concatenated."""
    ends = lengths.cumsum()
    n = int(ends[-1]) if len(ends) else 0
    return np.repeat(starts - (ends - lengths), lengths) + np.arange(n)


def fill_chunks(lease, slot: np.ndarray, chunks: np.ndarray,
                held: np.ndarray
                ) -> Tuple[np.ndarray, np.int32, np.ndarray, int]:
    """Copy a ``TaggedBuffer.lease``'s shares straight into their slots'
    chunks: ``SummarizerPod.route`` of the same batch, with no packed
    batch.

    (lease, slot (m,) of each share from ``share_slots``, chunks
    (S, C, d) as its last fill left it, held (S,) the items each slot
    held then) -> (counts (S,), unknown (), overflow (S,), rows zeroed).
    A share's j-th item goes to row j of its slot's chunk, for j < C;
    the rest count in ``overflow``, and a share with no live slot counts
    in ``unknown`` if its id is not negative.  The rows the last fill
    held and this one does not are zeroed first, and ``held`` becomes
    ``counts``: so ``chunks`` ends bit-equal to ``route``'s, at a cost
    in items, not in S·C.  Each kept row is copied once, from the
    store to its chunk, through a cache-sized block.
    """
    S, C = chunks.shape[:2]
    rows = chunks.reshape((S * C,) + chunks.shape[2:])
    k = lease.counts
    found = slot < S
    kept = np.where(found, np.minimum(k, C), 0)
    counts = np.zeros((S,), np.int32)
    counts[slot[found]] = kept[found]  # one share per session, so per slot
    overflow = np.zeros((S,), np.int32)
    overflow[slot[found]] = (k - kept)[found]
    unknown = np.int32(k[~found & (lease.sids >= 0)].sum())

    stale = np.maximum(held - counts, 0)
    zeroed = int(stale.sum())
    if zeroed:
        rows[_ranges(np.arange(S) * C + counts, stale)] = 0
    held[:] = counts  # a copy cut short leaves rows below counts only

    src = lease.slots
    if (kept < k).any():
        src = src[_ranges(k.cumsum() - k, kept)]
    dst = _ranges(slot * C, kept)
    step = max(1, _BLOCK_BYTES // max(1, rows[:1].nbytes))
    block = np.empty((min(step, len(src)),) + rows.shape[1:], rows.dtype)
    for lo in range(0, len(src), step):
        part = block[:len(src[lo:lo + step])]
        lease.store.take(src[lo:lo + step], axis=0, out=part, mode="clip")
        rows[dst[lo:lo + step]] = part
    return counts, unknown, overflow, zeroed


class _ChunkRing:
    """The host chunk arrays a pipeline fills in turn.

    Two, zeroed once: the host fills one while the device step reads
    the other.  Each keeps the items its slots held at its last fill
    (``fill_chunks``), and an output of the step that read it, which
    ``next`` waits on before the array is filled again: a transfer may
    still read the host array (and on the CPU the device array may be
    it) until that step has run.
    """

    def __init__(self, shape: tuple, dtype):
        self.shape, self.dtype = shape, dtype
        self.chunks = [np.zeros(shape, dtype) for _ in range(2)]
        self.held = [np.zeros(shape[:1], np.int64) for _ in range(2)]
        self.reader = [None, None]
        self.turn = 0

    def next(self) -> Tuple[np.ndarray, np.ndarray]:
        """The array to fill next and its held counts, once free."""
        i = self.turn
        if self.reader[i] is not None:
            jax.block_until_ready(self.reader[i])
            self.reader[i] = None
        return self.chunks[i], self.held[i]

    def read_by(self, out) -> None:
        """Record the step that reads the array ``next`` gave; the next
        call gives the other one."""
        self.reader[self.turn] = out
        self.turn ^= 1


@hashable_lru(maxsize=32)
def _advance_for(pod, donate):
    return jax.jit(pod.ingest_routed, donate_argnums=donate)


@dataclasses.dataclass
class IngestPipeline:
    """Drive a SummarizerPod from a ``TaggedBuffer``, double-buffered.

    ``batch`` is the fixed device batch size: every device batch holds
    at most ``batch`` items (the last ones fewer, counted as padding), so
    the jitted step compiles exactly once.  A batch takes at most the
    pod's per-session routing capacity ``chunk`` from one session
    (``TaggedBuffer.lease``'s ``per_session``), so nothing overflows.
    """

    pod: "object"  # SummarizerPod (kept loose to avoid an import cycle)
    buffer: TaggedBuffer
    batch: int = 256
    get_timeout: Optional[float] = None  # None = wait forever
    min_fill: int = 1  # items to wait for per device batch
    # (raise toward ``batch`` when a trickling producer must not burn a
    # full jitted step per item; 1 favors latency)
    pod_id: "object" = 0  # telemetry label; PodRouter stamps its key here
    metrics: "object" = None  # None = process default registry; obs.NULL off
    # host callback fired at run()'s sync boundary (after
    # block_until_ready, state fully materialized); a returned dict is
    # merged into run()'s stats.  The pubsub front-end hooks its offset
    # commit here (PubSubFrontEnd.attach) — the boundary is what makes
    # "committed" mean "in the pod state".
    on_sync: "object" = None

    def __post_init__(self):
        self._advance = None
        self._ring: Optional[_ChunkRing] = None
        self._feeders = []
        self._feed_exc: Optional[BaseException] = None
        self._seen: dict = {}  # the buffer's counters at the last run
        self.exhausted = False

    # ------------------------------------------------------------------ feed
    def feed_from(self, source: Source, *, close: bool = True,
                  put_timeout: Optional[float] = None) -> threading.Thread:
        """Spawn a daemon thread that puts ``source`` into the buffer
        (and closes it on exhaustion) — the producer half of the feed.
        Backpressure is the buffer's policy: ``block`` pauses the
        feeder, the drop policies clip per session."""

        def _run():
            try:
                for sids, X in source:
                    self.buffer.put(sids, X, timeout=put_timeout)
            except BaseException as e:
                # surfaced by run(): a wire failure must not masquerade
                # as a clean end-of-stream with fewer items
                self._feed_exc = e
            finally:
                if close:
                    self.buffer.close()

        t = threading.Thread(target=_run, daemon=True)
        t.start()
        self._feeders.append(t)
        return t

    # ------------------------------------------------------------------- run
    def _advance_fn(self):
        if self._advance is None:
            # donating the stacked state needs real accelerator buffers;
            # on CPU it only produces a warning per call.  The program is
            # shared across pipelines on the same pod (hashable_lru).
            donate = (0,) if jax.default_backend() != "cpu" else ()
            self._advance = _advance_for(self.pod, donate)
        return self._advance

    def _next_batch(self, table, S):
        """Lease the next batch's shares and copy them straight from the
        buffer's store into the next ring array.
        -> (routed, items, padded, rows zeroed) or None."""
        with obs.stage("ingest_get"):
            lease = self.buffer.lease(self.batch, timeout=self.get_timeout,
                                      min_items=self.min_fill,
                                      per_session=self.pod.chunk)
        if lease is None:
            return None
        with lease, obs.stage("ingest_route"):
            with obs.stage("ingest_slot_lookup"):
                slot = share_slots(table, lease.sids, S)
            with obs.stage("ingest_scatter"):
                shape = (S, self.pod.chunk) + lease.store.shape[1:]
                ring = self._ring
                if ring is None or (ring.shape, ring.dtype) != (
                        shape, lease.store.dtype):
                    ring = self._ring = _ChunkRing(shape, lease.store.dtype)
                chunks, held = ring.next()
                counts, unknown, overflow, zeroed = fill_chunks(
                    lease, slot, chunks, held)
                lease.release()
        n = lease.items
        return (chunks, counts, unknown, overflow), n, self.batch - n, zeroed

    def run(self, state, *, max_batches: Optional[int] = None):
        """Ingest up to ``max_batches`` device batches (None = until the
        buffer is closed and drained); resumable — a later call drains
        what the buffer holds then.  Returns ``(state, stats)``.

        ``stats`` carries the drop counters the host routing observed
        (``dropped_unknown`` / ``dropped_overflow``) — items lost to
        dead session ids are loud here, not just in the device-side
        ledgers.  A producer failure recorded by a ``feed_from`` thread
        re-raises from here: a broken wire must never look like a clean
        end-of-stream.

        A batch is one copy: ``TaggedBuffer.lease`` takes the sessions'
        shares, each share's slot is looked up once (``share_slots``),
        and ``fill_chunks`` copies the rows from the buffer's store into
        the next of two reused chunk arrays.

        Each call leaves one ``ingest_run`` span (batches, items,
        padded, bytes sent to the device, ``zeroed_rows`` (the rows a
        reused chunk array held and had zeroed), the items producers put
        and the seconds they waited in ``put`` since the last run:
        ``buffer_put_items``, ``buffer_put_wait_s``; of those items, the
        ones admitted as a prefix, ``buffer_put_block_items``;
        the sessions' shares taken, ``buffer_get_blocks``) split into
        the stages ``ingest_slot_table``, ``ingest_get`` (the lease,
        with the buffer's ``buffer_get_wait_*`` inside it),
        ``ingest_route`` (split into ``ingest_slot_lookup`` and
        ``ingest_scatter``), ``ingest_device_put``, ``ingest_dispatch``
        and ``ingest_sync``, each an ``<stage>_s`` attribute and a
        profiler TraceMe.
        """
        advance = self._advance_fn()
        batches = items = padded = sent = zeroed = 0
        drop_unknown = drop_overflow = 0
        t0 = time.perf_counter()
        # one span per run; the stages split it without an event per
        # batch, and none of them syncs the device
        with obs.span("ingest_run", pod=str(self.pod_id)) as sp:
            with obs.stage("ingest_slot_table"):  # waits for the last step
                sid = np.asarray(state.sid)
                table = live_table(sid, np.asarray(state.active))
            S = len(sid)
            while max_batches is None or batches < max_batches:
                got = self._next_batch(table, S)
                if got is None:
                    # a later run() re-checks the buffer — a pod handoff
                    # may inject relocated backlog AFTER the stream
                    # closed, and it must still drain
                    self.exhausted = True
                    break
                routed, n, n_pad, n_zeroed = got
                with obs.stage("ingest_device_put"):
                    args = [jax.device_put(a) for a in routed]
                with obs.stage("ingest_dispatch"):
                    state, out = advance(state, *args)
                self._ring.read_by(out)
                # while the device runs this step, the loop's next
                # iteration leases + copies the following batch on
                # host — the overlap
                _, _, unknown, overflow = routed
                batches += 1
                items += n
                padded += n_pad
                zeroed += n_zeroed
                sent += sum(a.nbytes for a in routed)
                drop_unknown += int(unknown)
                drop_overflow += int(overflow.sum())
            with obs.stage("ingest_sync"):
                jax.block_until_ready(state.items)
            # the buffer's side since the last run: items admitted (and
            # of them, through the block path), seconds waited in put,
            # shares taken
            seen = {"buffer_put_items": self.buffer.admitted(),
                    "buffer_put_wait_s": self.buffer.wait_seconds()["put"],
                    **{f"buffer_{k}": v for k, v in
                       self.buffer.block_counts().items()}}
            sp.set(batches=batches, items=items, padded=padded,
                   bytes=sent, zeroed_rows=zeroed,
                   **{k: v - self._seen.get(k, 0) for k, v in seen.items()})
            self._seen = seen
        wall = time.perf_counter() - t0
        # telemetry happens HERE and only here: block_until_ready above is
        # the run's host-sync boundary, so draining the device ledgers now
        # costs a few already-materialized (S,) transfers and zero hot-path
        # work (DESIGN.md §13 "record at sync boundaries only")
        self._record_run(state, batches, items, padded)
        stats = {"batches": batches, "items": items,
                 "padded": padded, "wall_s": wall,
                 "dropped_unknown": drop_unknown,
                 "dropped_overflow": drop_overflow}
        if self.on_sync is not None:
            # same sync boundary as the drain: everything this run
            # routed is in the pod state, so offset commits made here
            # are exact (a crash before this point only re-delivers)
            stats.update(self.on_sync(state) or {})
        if self._feed_exc is not None:
            exc, self._feed_exc = self._feed_exc, None
            raise RuntimeError(
                "ingest producer failed mid-stream (items already routed "
                "are in the pod state)") from exc
        return state, stats

    def _record_run(self, state, batches, items, padded) -> None:
        """Flush one run()'s host-local tallies + the device ledgers into
        the metrics registry.  Host-only, post-sync; never traced."""
        reg = obs.get_registry(self.metrics)
        if not reg.enabled:
            return
        pod = str(self.pod_id)
        reg.counter("ingest_batches_total", "device batches dispatched",
                    ("pod",)).labels(pod=pod).inc(batches)
        reg.counter("ingest_items_total", "real (non-padding) items fed",
                    ("pod",)).labels(pod=pod).inc(items)
        reg.counter("ingest_padding_total",
                    "PAD_SID filler rows burned in partial batches",
                    ("pod",)).labels(pod=pod).inc(padded)
        obs.drain.drain_pod(state, pod=pod, registry=reg)
        obs.drain.drain_buffer(self.buffer, pod=pod, registry=reg)


@dataclasses.dataclass
class PodRouter:
    """Fleet front-end: one tagged ingress, N pods, a host routing table.

    Each pod runs its own ``IngestPipeline``; the router owns
    the sid -> pod-id table and fans ``put`` batches out to the right
    pod's ``TaggedBuffer`` (per-session FIFO is preserved — a session's
    items all flow through one buffer at a time).  Items for sids with
    no table entry are counted in ``drops_unrouted`` per sid — a
    front-end routing error must be loud, exactly like the pod-side
    ``drops_unknown`` ledger.

    The autoscaler's handoff protocol uses the two migration primitives:

      * ``quiesce(sids)`` — park the victims in their *current* pod's
        buffer (arrivals keep landing there, nothing drains, nothing is
        dropped) so the pod can finish in-flight work and its summary
        rows can be snapshotted at a stable point;
      * ``migrate(sids, dst)`` — atomically flip the table and move the
        parked backlog into the target pod's buffer.  The router lock
        serializes this against ``put``, so a racing producer cannot
        slip a newer item in front of the backlog: per-session FIFO
        survives the handoff.
    """

    pipelines: Dict[int, IngestPipeline]

    def __post_init__(self):
        for pid, pipe in self.pipelines.items():
            pipe.pod_id = pid  # every pipe's metrics carry its fleet id
        self._table: Dict[int, int] = {}
        self._lock = make_lock("PodRouter._lock")
        self._feeders = []
        self.drops_unrouted: Dict[int, int] = {}

    # ------------------------------------------------------------- the table
    def assign(self, sids, pod_id: int) -> None:
        """Route ``sids`` to ``pod_id`` from now on (admission time)."""
        if pod_id not in self.pipelines:
            raise KeyError(f"unknown pod id {pod_id}")
        sids = np.asarray(sids).ravel()
        with obs.span("admit", layer="router", pod=str(pod_id),
                      sessions=len(sids)):
            with self._lock:
                for sid in sids:
                    self._table[int(sid)] = pod_id

    def unassign(self, sids) -> None:
        """Drop table entries (eviction time); later items count as
        unrouted."""
        sids = np.asarray(sids).ravel()
        with obs.span("evict", layer="router", sessions=len(sids)):
            with self._lock:
                for sid in sids:
                    self._table.pop(int(sid), None)

    def owner(self, sid: int) -> Optional[int]:
        with self._lock:
            return self._table.get(int(sid))

    def table(self) -> Dict[int, int]:
        with self._lock:
            return dict(self._table)

    # ------------------------------------------------------------------ feed
    def put(self, sids, X, timeout: Optional[float] = None) -> None:
        """Fan one tagged batch out to the pods' buffers by table.

        The buffer writes happen OUTSIDE the router lock: a ``block``
        policy buffer may wait indefinitely for space, and the thing
        that frees space mid-handoff is ``migrate`` extracting the
        parked backlog — which needs this lock.  Holding it across a
        blocking ``put`` would deadlock producer, handoff and all
        routing.  The price is a put/flip race, repaired after the
        fact: any rows that landed in a pod the table no longer points
        to are relocated to the new owner — they are newer than the
        migrated backlog (same producer), so appending them behind it
        preserves per-session FIFO.
        """
        sids = np.asarray(sids, np.int32).ravel()
        X = np.asarray(X, np.float32)
        with self._lock:
            dest = np.empty(len(sids), np.int64)
            for i, sid in enumerate(sids.tolist()):
                pid = self._table.get(sid, -1)
                dest[i] = pid
                if pid < 0:
                    self.drops_unrouted[sid] = \
                        self.drops_unrouted.get(sid, 0) + 1
        for pid in self.pipelines:
            m = dest == pid
            if not m.any():
                continue
            self.pipelines[pid].buffer.put(sids[m], X[m], timeout=timeout)
            with self._lock:  # repair: did a flip race the enqueue?
                stale = {sid for sid in set(sids[m].tolist())
                         if self._table.get(sid, pid) != pid}
                for sid in stale:
                    bs, bx = self.pipelines[pid].buffer.extract([sid])
                    if len(bs):
                        owner = self._table[sid]
                        self.pipelines[owner].buffer.inject(bs, bx)

    def feed_from(self, source: Source, *, close: bool = True,
                  put_timeout: Optional[float] = None) -> threading.Thread:
        """Producer thread: route ``source`` through the table; on
        exhaustion close every pod's buffer (end-of-stream fans out)."""

        def _run():
            try:
                for sids, X in source:
                    self.put(sids, X, timeout=put_timeout)
            except BaseException as e:
                for pipe in self.pipelines.values():
                    pipe._feed_exc = e  # surfaced by each pipe's run()
            finally:
                if close:
                    for pipe in self.pipelines.values():
                        pipe.buffer.close()

        t = threading.Thread(target=_run, daemon=True)
        t.start()
        self._feeders.append(t)
        return t

    # ------------------------------------------------------------- migration
    def quiesce(self, sids) -> None:
        """Park ``sids`` in their current pods' buffers (handoff step 1)."""
        with self._lock:
            by_pod: Dict[int, list] = {}
            for sid in np.asarray(sids).ravel():
                pid = self._table.get(int(sid))
                if pid is not None:
                    by_pod.setdefault(pid, []).append(int(sid))
            for pid, group in by_pod.items():
                self.pipelines[pid].buffer.quiesce(group)

    def release(self, sids) -> None:
        """Un-park ``sids`` in place (handoff aborted): their backlog
        resumes draining to the pod that already owns them."""
        with self._lock:
            by_pod: Dict[int, list] = {}
            for sid in np.asarray(sids).ravel():
                pid = self._table.get(int(sid))
                if pid is not None:
                    by_pod.setdefault(pid, []).append(int(sid))
            for pid, group in by_pod.items():
                self.pipelines[pid].buffer.release(group)

    def migrate(self, sids, dst: int) -> int:
        """Flip the table for ``sids`` and move their parked backlog to
        pod ``dst``'s buffer, atomically w.r.t. ``put``.  Returns the
        number of backlog items moved (zero dropped, by construction)."""
        if dst not in self.pipelines:
            raise KeyError(f"unknown pod id {dst}")
        moved = 0
        with self._lock:
            by_pod: Dict[int, list] = {}
            for sid in np.asarray(sids).ravel():
                pid = self._table.get(int(sid))
                if pid is not None and pid != dst:
                    by_pod.setdefault(pid, []).append(int(sid))
                self._table[int(sid)] = dst
            dst_buf = self.pipelines[dst].buffer
            for pid, group in by_pod.items():
                bs, bx = self.pipelines[pid].buffer.extract(group)
                if len(bs):
                    # inject, not put: the backlog was already admitted
                    # at the source — relocation must not block on the
                    # target's capacity or fail on a racing close
                    dst_buf.inject(bs, bx)
                    moved += len(bs)
        return moved
