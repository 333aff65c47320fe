"""Traced-kernel math shared by the core objective and the Pallas kernels.

``KernelParams`` is the traced counterpart of ``core.functions.KernelConfig``:
the RBF constant ``inv2l2`` ( = 1/(2 l^2), derived ONCE on host in float64 by
``core.spec.HyperParams.build`` and rounded to f32) and the kernel-kind id,
both as () array leaves.  Carried inside ``HyperParams`` so a SummarizerPod
slot stamps its tenant's kernel at ``admit()`` without retracing — the same
masked-state trick as K/T/eps (DESIGN.md §9/§11).

This module is deliberately importable from BOTH ``repro.core`` and
``repro.kernels`` (it depends only on ``repro.constants``): the jnp oracle
backend and the Pallas kernel bodies call the SAME ``pairwise_traced`` /
``traced_gain_rows`` functions, so the fused/unfused f32 bit-equality pins
rest on a single op sequence rather than two copies kept in sync by hand.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.constants import GAIN_EPS, NORM_EPS

Array = jax.Array

# Stable integer ids for the kernel kinds — ``KernelParams.kind_id`` carries
# one of these as a traced () int32 so per-session kernels need no retrace.
KERNEL_KIND_IDS = {"rbf": 0, "linear_norm": 1}


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class KernelParams:
    """Per-session kernel hyperparameters as traced () array leaves."""

    inv2l2: Array  # () float32 — 1 / (2 * lengthscale^2)
    kind_id: Array  # () int32 — KERNEL_KIND_IDS[kind]

    @classmethod
    def of(cls, config) -> "KernelParams":
        """Host-side conversion from a static ``KernelConfig``."""
        return cls(
            inv2l2=jnp.float32(1.0 / (2.0 * float(config.lengthscale) ** 2)),
            kind_id=jnp.int32(KERNEL_KIND_IDS[config.kind]),
        )


def matmul(x: Array, y: Array) -> Array:
    """``x @ y`` at full precision, accumulated in float32, returned in
    ``x``'s dtype.

    The TPU compiler takes only 32-bit matmul accumulators, so a bf16
    objective's matmuls accumulate in f32 and round once.  ``HIGHEST``
    keeps f32 operands f32 on the TPU, where XLA's default multiplies
    them in one bf16 pass (on a v5e at d=256, ``x @ y.T`` came out 0.15
    off where ``|x.y|`` reaches 71), an error the expanded-square
    distance ``|x|^2 + |y|^2 - 2 x.y`` carries straight into the kernel
    values.  bf16 operands are exact in that one pass (and
    the kernel compiler refuses ``HIGHEST`` for them); the CPU ignores
    the setting.  Every matmul of the shared gain/append math goes
    through here, so the kernels and the jnp path keep one op sequence.
    """
    precision = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                 else None)
    return jnp.matmul(x, y, precision=precision,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def in_f32(fn, *xs):
    """``fn(*xs)`` evaluated in float32 and returned in the operands' dtype.

    For the transcendental and divide ops: the TPU's unit for them has no
    bf16, and XLA evaluates a bf16 ``exp``/``log``/``sqrt``/``/`` this
    way anyway, so the result is the same; for f32 the casts vanish.
    """
    dt = jnp.result_type(*xs)
    return fn(*(x.astype(jnp.float32) if hasattr(x, "astype") else x
                for x in xs)).astype(dt)


def pairwise_traced(x: Array, y: Array, kern: KernelParams) -> Array:
    """k(x_i, y_j) for x (N, d), y (M, d) -> (N, M), kernel from arrays.

    One Gram matmul feeds both kinds; the selection is branch-free so it
    vmaps over a pod's session axis and lowers inside a Pallas kernel.
    The rbf uses the multiply form ``exp(-inv2l2 * d2)`` (inv2l2 is the
    host-rounded constant), the normalized-linear kernel normalizes the
    Gram entries *after* the matmul — both read the one matmul.
    """
    g = matmul(x, y.T)  # (N, M)
    xn2 = jnp.sum(x * x, axis=-1, keepdims=True)  # (N, 1)
    yn2 = jnp.sum(y * y, axis=-1, keepdims=True).T  # (1, M)
    d2 = jnp.maximum(xn2 + yn2 - 2.0 * g, 0.0)
    # the () constant is widened before the cast: a Pallas TPU kernel
    # has no bf16 scalar arithmetic
    inv2l2 = jnp.full((1, 1), kern.inv2l2, jnp.float32).astype(x.dtype)
    rbf = in_f32(jnp.exp, -inv2l2 * d2)
    nx = jnp.maximum(in_f32(jnp.sqrt, xn2), NORM_EPS)
    ny = jnp.maximum(in_f32(jnp.sqrt, yn2), NORM_EPS)
    lin = 0.5 * (in_f32(jnp.divide, g, nx * ny) + 1.0)
    return jnp.where(kern.kind_id == 0, rbf, lin)


def traced_gain_rows(x: Array, feats: Array, linv: Array, mask: Array, *,
                     a: float, kern: KernelParams) -> Array:
    """Marginal gains of candidate rows x (B, d) -> (B, 1).

    The row-major form of the oracle query under traced kernel params:

        Km   = a * k(x, feats) * mask          (B, K)
        C    = Km @ Linv^T                     (B, K)
        gain = 1/2 log((1+a) - |C_row|^2)      (B, 1)

    ``mask`` broadcasts over rows ((K,) or (1, K)).  Shared verbatim by
    the jnp oracle backend and the Pallas pod-step kernel body — the
    f32 bit-equality pin between them rests on this single definition.
    """
    km = a * pairwise_traced(x, feats, kern) * mask  # (B, K)
    c = matmul(km, linv.T)  # (B, K)
    cn2 = jnp.sum(c * c, axis=-1, keepdims=True)  # (B, 1)
    return 0.5 * in_f32(jnp.log, jnp.maximum((1.0 + a) - cn2, GAIN_EPS))
