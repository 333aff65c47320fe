"""Structured spans and stages for host operations of the serving stack.

The serving stack's control plane — admission at the fleet front-end,
``evict_sids``, the quiesce -> snapshot -> restore -> flip phases of a
pod handoff, checkpoint save/restore, drift checks — and each
``IngestPipeline.run`` are host code that runs at human-auditable
cadence.  Each operation is wrapped in a ``span``: a context manager
that records name, wall duration, nesting (parent span id, depth), an
*outcome* and free-form attributes, and emits one JSON line per
completed span.

Two sinks, one API: every span also opens a
``jax.profiler.TraceAnnotation`` of its name, so under a profiler trace
it sits on the host plane, on the device trace's clock.  Inside a span,
per-batch work is timed by a lighter ``stage``: it opens the same kind
of TraceMe and adds its seconds to the attribute ``<stage>_s`` of the
enclosing span on its thread, and is never emitted to the recorder or
the registry by itself.  A stage with no enclosing span (a producer
thread's ``put``) shows on the profiler timeline only.  So the event
ring holds one ``ingest_run`` per pipeline run, whatever the batch and
put rates, and still carries the run's split into stages.

Outcome contract: ``ok`` by default; an exception escaping the body
records ``outcome="error"`` (with the exception type) and re-raises —
a failed handoff must leave a span saying so, never a hole in the
timeline.  Domain refusals set their own outcome explicitly
(``sp.set_outcome("refused")``): a refusal is not an error, but it is
an event.

Durations are *dispatch* durations: spans and stages never call
``block_until_ready`` — instrumenting must not add device syncs
(DESIGN.md §13).  Wrap a span around code that already syncs (a
handoff's host gather, ``pipeline.run``'s final block) and the
duration is honest; wrap it around a bare jitted call and it measures
enqueue time, which is what the control plane actually waits for.

Spans and stages are host-only by construction: entering one inside a
JAX trace is a no-op (the static gate is podlint PL006; this is the runtime
backstop — a span recorded at trace time would fire once per compile
with a meaningless duration, then never again).

Thread-safety: the span stack is thread-local (producer threads,
checkpoint writers and the serve loop each get their own nesting) and
event emission takes the recorder lock only to append/write.
"""
from __future__ import annotations

import contextlib
import json
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

from jax.core import trace_ctx as _trace_ctx  # the "inside a trace?" probe
from jax.profiler import TraceAnnotation

from repro.concurrency import make_lock

from .registry import get_registry

MAX_BUFFERED_EVENTS = 10_000  # ring bound: telemetry must not be a leak


class Span:
    """Mutable handle the ``with`` body can annotate."""

    __slots__ = ("name", "span_id", "parent_id", "depth", "attrs", "outcome",
                 "_t0")

    def __init__(self, name: str, span_id: int, parent_id: Optional[int],
                 depth: int, attrs: Dict[str, object]):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.depth = depth
        self.attrs = attrs
        self.outcome = "ok"
        self._t0 = time.perf_counter()

    def set_outcome(self, outcome: str) -> None:
        self.outcome = str(outcome)

    def set(self, **attrs: object) -> None:
        self.attrs.update(attrs)


class SpanRecorder:
    """Collects span events; optionally streams them as JSONL.

    ``path=None`` buffers in memory only (``events`` keeps the most
    recent :data:`MAX_BUFFERED_EVENTS`); ``dump_jsonl(path)`` writes
    the buffer out later — the CI artifact path.
    """

    def __init__(self, path: Optional[str] = None, registry=None):
        self.events: List[dict] = []
        self._path = Path(path) if path else None
        self._fh = None
        self._lock = make_lock("SpanRecorder._lock")
        self._local = threading.local()
        self._next_id = 0
        self._registry = registry

    # ------------------------------------------------------------- plumbing
    def _stack(self) -> List[Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def configure(self, path: Optional[str] = None, registry=None) -> None:
        with self._lock:
            if path is not None:
                if self._fh is not None:
                    self._fh.close()
                    self._fh = None
                self._path = Path(path)
            if registry is not None:
                self._registry = registry

    def _emit(self, event: dict) -> None:
        reg = get_registry(self._registry)
        reg.counter("spans_total", "completed control-plane spans",
                    ("name", "outcome")).labels(
            name=event["name"], outcome=event["outcome"]).inc()
        reg.histogram("span_seconds", "span wall durations",
                      ("name",)).labels(name=event["name"]).observe(
            event["dur_s"])
        with self._lock:
            self.events.append(event)
            if len(self.events) > MAX_BUFFERED_EVENTS:
                del self.events[: len(self.events) - MAX_BUFFERED_EVENTS]
            if self._path is not None:
                if self._fh is None:
                    self._path.parent.mkdir(parents=True, exist_ok=True)
                    self._fh = self._path.open("a")
                self._fh.write(json.dumps(event, sort_keys=True,
                                          default=str) + "\n")
                self._fh.flush()

    # ----------------------------------------------------------------- span
    @contextlib.contextmanager
    def span(self, name: str, **attrs: object):
        if not _trace_ctx.is_top_level():  # inside a jit/vmap trace: no-op
            yield Span(name, -1, None, -1, dict(attrs))
            return
        stack = self._stack()
        with self._lock:
            span_id = self._next_id
            self._next_id += 1
        sp = Span(name, span_id, stack[-1].span_id if stack else None,
                  len(stack), dict(attrs))
        stack.append(sp)
        t_wall = time.time()
        tm = TraceAnnotation(name)
        tm.__enter__()
        try:
            yield sp
        except BaseException as e:
            sp.outcome = "error"
            sp.attrs.setdefault("error", type(e).__name__)
            raise
        finally:
            tm.__exit__(None, None, None)
            stack.pop()
            self._emit({
                "name": sp.name,
                "span_id": sp.span_id,
                "parent_id": sp.parent_id,
                "depth": sp.depth,
                "outcome": sp.outcome,
                "t_wall": round(t_wall, 6),
                "dur_s": round(time.perf_counter() - sp._t0, 9),
                "thread": threading.current_thread().name,
                "attrs": sp.attrs,
            })

    # ------------------------------------------------------------ inspection
    def find(self, name: Optional[str] = None,
             outcome: Optional[str] = None) -> List[dict]:
        with self._lock:
            return [e for e in self.events
                    if (name is None or e["name"] == name)
                    and (outcome is None or e["outcome"] == outcome)]

    def clear(self) -> None:
        with self._lock:
            self.events.clear()

    def dump_jsonl(self, path: str) -> Path:
        """Write every buffered event to ``path`` (the CI artifact)."""
        p = Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        with self._lock:
            p.write_text("".join(
                json.dumps(e, sort_keys=True, default=str) + "\n"
                for e in self.events))
        return p

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None


class Stage:
    """One timed interval inside the enclosing span (module docstring).

    ``seconds`` holds the interval's length after exit, for callers
    that keep their own totals (``TaggedBuffer``'s wait seconds)."""

    __slots__ = ("name", "seconds", "_recorder", "_tm", "_t0")

    def __init__(self, recorder: SpanRecorder, name: str):
        self.name = name
        self.seconds = 0.0
        self._recorder = recorder
        self._tm = None

    def __enter__(self) -> "Stage":
        if _trace_ctx.is_top_level():  # inside a trace: time nothing
            self._tm = TraceAnnotation(self.name)
            self._tm.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        if self._tm is None:
            return
        self._tm.__exit__(None, None, None)
        stack = self._recorder._stack()
        if stack:
            attrs = stack[-1].attrs
            key = self.name + "_s"
            attrs[key] = attrs.get(key, 0.0) + self.seconds


_RECORDER = SpanRecorder()


def get_recorder() -> SpanRecorder:
    return _RECORDER


def span(name: str, **attrs: object):
    """``with obs.span("handoff", src=0, dst=1) as sp:`` on the default
    recorder — the one the instrumented serving modules use."""
    return _RECORDER.span(name, **attrs)


def stage(name: str) -> Stage:
    """``with obs.stage("ingest_route"):`` on the default recorder: adds
    the interval's seconds to ``ingest_route_s`` of the enclosing
    span."""
    return Stage(_RECORDER, name)
