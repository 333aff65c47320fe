"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state.  The dry-run entrypoint (dryrun.py) sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import; everything else (tests, benches, examples) sees the 1 real device.

Mesh axes:
  * ``pod``   — inter-pod data parallelism (DCN boundary; gradients cross it
                once per step, activations never do),
  * ``data``  — intra-pod data parallelism + FSDP parameter sharding,
  * ``model`` — tensor parallelism (heads / ffn / vocab / experts) and
                sequence sharding for decode KV caches.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def auto_mesh(shape, axes, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules here
    constrain with ``with_sharding_constraint`` and let GSPMD propagate,
    which ``make_mesh``'s default Explicit axes refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Degenerate 1x1 mesh over the real local device (smoke tests, examples)."""
    return auto_mesh((1, 1), ("data", "model"))
