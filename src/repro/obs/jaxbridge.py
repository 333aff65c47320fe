"""jax.monitoring -> metrics registry bridge.

XLA compile events are the serving stack's most expensive "silent"
cost: a retrace mid-serve stalls every tenant on the pod for the whole
compile.  The ``retrace_guard`` test fixture (tests/conftest.py) counts
``/jax/core/compile/backend_compile_duration`` events inside scoped
budgets; this bridge generalizes that counter into *always-on retrace
accounting* — every fresh compile increments ``xla_compile_total`` and
lands its duration in ``xla_compile_seconds``, so a CI bench artifact
(or a production scrape) shows exactly how many programs a run built
and how long they took.  Other monitored events are not recorded.

``jax.monitoring`` has no unregister API, so exactly ONE module-level
listener is installed, the first time :func:`install`
runs (``repro.obs`` calls it at import); repeat calls are no-ops.  The
listener resolves the *current* default registry at event time (late
binding), so ``reset_default_registry()`` — the test/bench isolation
hook — takes effect without re-subscription.  This is the same
single-listener discipline the retrace_guard uses; the two coexist as
independent subscribers counting the same event stream (pinned in
tests/test_obs.py).
"""
from __future__ import annotations

from repro.concurrency import make_lock

from .registry import get_registry

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_install_lock = make_lock("jaxbridge._install_lock")
_installed = False
_registrations = 0  # how many times listeners were REGISTERED (tests: == 1)


def _metric_on_duration(event: str, duration: float, **kwargs) -> None:
    if event != COMPILE_EVENT:
        return
    reg = get_registry()
    if not reg.enabled:
        return
    reg.counter("xla_compile_total",
                "fresh XLA compiles (cache hits do not count)").inc()
    reg.histogram("xla_compile_seconds",
                  "backend_compile durations").observe(duration)


def install() -> bool:
    """Subscribe the bridge listener exactly once; returns True when
    this call performed the subscription (False: already installed)."""
    global _installed, _registrations
    with _install_lock:
        if _installed:
            return False
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_metric_on_duration)
        _registrations += 1
        _installed = True
        return True


def installed() -> bool:
    return _installed


def registrations() -> int:
    return _registrations
