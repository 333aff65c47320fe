"""PL006 fixture: telemetry inside traced code.  The counter ``.inc``
and the ``obs.span`` both execute at *trace* time — once per compile,
never per step — so the metric silently undercounts and the span times
the tracer."""
import jax
import jax.numpy as jnp

from repro import obs

ITEMS = obs.get_registry().counter("fixture_items_total", "items seen")


def step(state, x):
    ITEMS.inc()  # BAD: runs once per compile, not once per step
    with obs.span("step", n=x.shape[0]):  # BAD: span under the trace
        with obs.stage("gain"):  # BAD: so is a stage
            gain = jnp.dot(state, x)
    return state + jnp.where(gain > 0, x, 0.0)


def run(state, X):
    stepped = jax.jit(step)
    for x in X:
        state = stepped(state, x)
    return state
