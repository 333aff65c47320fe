"""repro.obs — the fleet telemetry layer (DESIGN.md §13).

Four pieces, one rule:

  * :mod:`~repro.obs.registry` — counters / gauges / histograms with
    labels; lock-free snapshot reads; JSON snapshot + Prometheus text
    exposition; ``NULL`` (a no-op registry) switches a component off;
  * :mod:`~repro.obs.spans` — structured spans for host operations
    (admission, eviction, handoff phases, checkpoint save/restore,
    drift checks, ingest runs), emitted as JSONL with durations,
    nesting and outcomes (``ok`` / ``error`` / domain outcomes like
    ``refused``), and stages that split a span into timed parts; both
    also sit on the profiler's host timeline;
  * :mod:`~repro.obs.jaxbridge` — always-on retrace accounting: XLA
    compile events from ``jax.monitoring`` become ``xla_compile_total``
    / ``xla_compile_seconds`` (installed once, below, at import);
  * :mod:`~repro.obs.drain` — the device-counter drain: PodState's
    on-device accept/drop ledgers are harvested into host metrics at
    existing host-sync boundaries ONLY.

The rule: **telemetry never touches the hot path.**  No ``.item()``, no
``np.asarray``, no metric recording inside traced code — podlint PL004
and PL006 gate it statically, a test pins a bare run and an
instrumented one to bit-equal pod state, and the span API no-ops under
a trace as the runtime backstop.
"""
from . import drain
from .jaxbridge import install as install_jax_bridge
from .registry import (DEFAULT_BUCKETS, MetricFamily, MetricsRegistry,
                       MetricsSnapshot, NULL, NullRegistry, get_registry,
                       reset_default_registry)
from .spans import Span, SpanRecorder, Stage, get_recorder, span, stage

__all__ = [
    "DEFAULT_BUCKETS", "MetricFamily", "MetricsRegistry", "MetricsSnapshot",
    "NULL", "NullRegistry", "get_registry", "reset_default_registry",
    "Span", "SpanRecorder", "Stage", "get_recorder", "span", "stage",
    "drain", "install_jax_bridge", "record_backend_fallback",
]

# always-on retrace accounting: one listener pair, installed exactly once
install_jax_bridge()


def record_backend_fallback(kernel: str, requested: str, resolved: str,
                            *, registry=None) -> None:
    """One backend degrade (e.g. ``pallas`` -> ``jnp`` off-TPU) as a
    counter — the warn-once message tells a human once; the counter
    tells the CI artifact which path actually ran, every time.

    Called from backend *resolvers* (host code that runs at trace time,
    before any compiled program exists) — never from inside a step.
    """
    get_registry(registry).counter(
        "backend_fallback_total",
        "kernel-backend requests degraded to another backend",
        ("kernel", "from", "to"),
    ).labels(kernel=kernel, **{"from": requested, "to": resolved}).inc()
