"""The load generator: one thread that feeds the pod's front door.

It walks the stream (``traffic.py``: the pool, lap after lap) in order and
calls ``TaggedBuffer.put``, the front door of the served path, from
outside the system under test.

* closed loop: puts ``put_items`` at a time as fast as the buffer admits
  them (the ``block`` policy makes it wait for room); an item's creation
  time is the start of its put call;
* open loop: item k of the window is due at ``t0 + k / rate``; the thread
  wakes every ``put_interval_s``, puts every item that is due and stamps
  each with its due time, so a stall on either side shows in freshness.

Before the window it puts ``warm_items`` as fast as it can, for the
warm-up round.  It closes the buffer when it stops, so the pipeline
drains what is left and ends.
"""
from __future__ import annotations

import threading
import time

import jax
import numpy as np

from .traffic import stream_tags


class Producer(threading.Thread):
    def __init__(self, buf, tags, X, *, sessions: int, loop: str,
                 warm_items: int, put_items: int, rate: float = 0.0,
                 put_interval_s: float = 0.002):
        super().__init__(name="bench-producer", daemon=True)
        if loop not in ("closed", "open"):
            raise ValueError(f"loop must be closed or open, got {loop!r}")
        if loop == "open" and rate <= 0:
            raise ValueError("an open loop needs a positive rate")
        self.buf, self.tags, self.X = buf, tags, X
        self.sessions = int(sessions)
        self.loop, self.rate = loop, float(rate)
        self.warm_items, self.put_items = int(warm_items), int(put_items)
        self.put_interval_s = float(put_interval_s)
        self.next = 0  # stream index of the next item to put
        self.calls = []  # (first index, end index, t_start, t_end, t_due)
        self.t0 = self.t_end = None
        self._go = threading.Event()
        self._halt = threading.Event()
        self.error = None

    # ------------------------------------------------------------ control
    def go(self, t0: float, t_end: float) -> None:
        """Open the window: the open-loop schedule starts at t0."""
        self.t0, self.t_end = t0, t_end
        self._go.set()

    def stop(self) -> None:
        self._halt.set()
        self._go.set()

    # --------------------------------------------------------------- body
    def _put(self, n: int, due: float) -> None:
        P = len(self.tags)
        lo, hi = self.next, self.next + n
        a = lo % P
        tags = stream_tags(self.tags, lo, hi, self.sessions)
        if a + n <= P:  # a view into the pool: the buffer keeps row views
            X = self.X[a:a + n]
        else:
            X = self.X[np.arange(lo, hi) % P]
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.put"):
            self.buf.put(tags, X)
        self.calls.append((lo, hi, t, time.perf_counter(), due))
        self.next = hi

    def run(self) -> None:
        try:
            while self.next < self.warm_items and not self._halt.is_set():
                n = min(self.put_items, self.warm_items - self.next)
                self._put(n, time.perf_counter())
            if self.loop == "closed":
                while not self._halt.is_set():
                    self._put(self.put_items, time.perf_counter())
                return
            self._go.wait()
            total = int((self.t_end - self.t0) * self.rate)
            done = 0
            while done < total and not self._halt.is_set():
                now = time.perf_counter()
                due = min(int((now - self.t0) * self.rate) + 1, total)
                while done < due:
                    n = min(due - done, self.put_items)
                    self._put(n, self.t0 + done / self.rate)
                    done += n
                wake = self.t0 + done / self.rate
                time.sleep(max(self.put_interval_s,
                               wake - time.perf_counter()))
        except BaseException as e:  # surfaced by the harness
            self.error = e
        finally:
            self.buf.close()

    # ------------------------------------------------------------ readings
    def created(self, n: int) -> np.ndarray:
        """Creation time of stream items 0..n-1 (float64)."""
        out = np.full(n, np.nan)
        for lo, hi, t, _, due in self.calls:
            if self.loop == "open" and self.t0 is not None \
                    and lo >= self.warm_items:
                k = np.arange(lo, hi) - self.warm_items
                out[lo:hi] = self.t0 + k / self.rate
            else:
                out[lo:hi] = t
        return out

    def lateness(self) -> np.ndarray:
        """Seconds each window put call started after its first item was
        due (open loop; empty in a closed loop)."""
        if self.loop != "open":
            return np.zeros(0)
        return np.asarray([t - due for lo, _, t, _, due in self.calls
                           if lo >= self.warm_items])
