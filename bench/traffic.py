"""The one traffic generator: a drifting Gaussian mixture per tenant.

A mix (``bench/traffic/<mix>.json``) gives the parameters; the generator
draws a pool of tagged items from ``--seed`` in one jitted call on the
device and hands it to the host in bulk.  The model is the one of the
program's ``data.streams.session_stream``: tenant s draws from its own
mixture of ``components`` means (scale ``spread``), with isotropic noise
``noise``, and every mean random-walks by ``drift_per_batch`` per device
batch's worth of items.  The three scales are in units of the
configuration's RBF lengthscale, so a mix means the same kernel geometry
under any lengthscale (noise 0.0625 at d=256: two items of one component
are e^-1 alike).  Tenants are ``uniform``, the one kind there is: every
item's tenant is drawn uniformly at random.

The stream has no end.  Item i is pool row ``i % P`` in lap ``i // P``,
and in lap L a row tagged t goes to session ``(t + L) % S``: every lap
deals the pool's mixtures to other tenants, so no session is sent the
same row twice within S laps, and a program fast enough to run past the
pool's end meets traffic of the same kind, not repeats.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def key_of(seed: int):
    """A PRNG key from any whole seed (the high bits are folded in)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnames=(
    "epochs", "sessions", "d", "components", "spread", "drift"))
def _means(key, *, epochs, sessions, d, components, spread, drift):
    """(epochs, sessions, components, d): every tenant's means, per device
    batch's worth of items."""
    km, kd = jax.random.split(key)
    means = spread * jax.random.normal(km, (sessions, components, d))
    steps = drift * jax.random.normal(kd, (epochs - 1, sessions,
                                           components, d))
    return jnp.concatenate(
        [means[None], means[None] + jnp.cumsum(steps, axis=0)])


@functools.partial(jax.jit, static_argnames=(
    "items", "sessions", "components", "batch", "noise"))
def _items(key, means, first, *, items, sessions, components, batch, noise):
    """Stream items [first, first + items) of the pool."""
    kt, kc, kn = jax.random.split(key, 3)
    d = means.shape[-1]
    tags = jax.random.randint(kt, (items,), 0, sessions, jnp.int32)
    comp = jax.random.randint(kc, (items,), 0, components, jnp.int32)
    epoch = (first + jnp.arange(items, dtype=jnp.int32)) // batch
    X = means[epoch, tags, comp] + noise * jax.random.normal(kn, (items, d))
    return tags, X.astype(jnp.float32)


TENANTS = ("uniform",)


def make_pool(seed: int, *, items: int, sessions: int, d: int, batch: int,
              mix: dict, lengthscale: float = 1.0, tenants: str = "uniform",
              block: int = 1 << 18):
    """(tags (items,) int32, X (items, d) float32) on the host, drawn on
    the device ``block`` items at a time, each block copied out while
    the next is drawn: the pool never holds more than two blocks of
    device memory.  The mixture's scales are multiples of
    ``lengthscale``."""
    if tenants not in TENANTS:
        raise ValueError(f"tenants {tenants!r}: the generator draws only "
                         f"{TENANTS}")
    key = key_of(seed)
    comps = int(mix["components"])
    ls = float(lengthscale)
    means = _means(jax.random.fold_in(key, 0), epochs=-(-int(items) // batch),
                   sessions=int(sessions), d=int(d), components=comps,
                   spread=ls * float(mix["spread"]),
                   drift=ls * float(mix["drift_per_batch"]))
    tags = np.empty((int(items),), np.int32)
    X = np.empty((int(items), int(d)), np.float32)
    pending = []  # the next block draws while this one is copied out
    for first in range(0, int(items), block):
        n = min(block, int(items) - first)
        t, x = _items(jax.random.fold_in(key, 1 + first // block), means,
                      np.int32(first), items=n, sessions=int(sessions),
                      components=comps, batch=int(batch),
                      noise=ls * float(mix["noise"]))
        t.copy_to_host_async()
        x.copy_to_host_async()
        pending.append((first, n, t, x))
        if len(pending) == 2:
            _store(tags, X, *pending.pop(0))
    for p in pending:
        _store(tags, X, *p)
    return tags, X


def stream_tags(tags, lo: int, hi: int, sessions: int) -> np.ndarray:
    """The sessions of stream items [lo, hi): pool row i % P's tag,
    dealt on by the item's lap i // P."""
    i = np.arange(int(lo), int(hi), dtype=np.int64)
    P = len(tags)
    return ((tags[i % P] + i // P) % int(sessions)).astype(np.int32)


def _store(tags, X, first, n, t, x):
    tags[first:first + n] = np.asarray(t)
    X[first:first + n] = np.asarray(x)


def sample_sessions(seed: int, plans_of_slot: list, count: int) -> list:
    """``count`` session ids drawn from the seed, every plan among them."""
    rng = np.random.default_rng(int(seed))
    S = len(plans_of_slot)
    by_plan = {}
    for s in rng.permutation(S):
        by_plan.setdefault(tuple(plans_of_slot[s]), []).append(int(s))
    pick = [v[0] for v in by_plan.values()]
    rest = [s for s in rng.permutation(S).tolist() if s not in pick]
    return sorted(pick + rest[:max(count - len(pick), 0)])
