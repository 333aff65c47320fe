"""The batched marginal-gain oracle behind every algorithm in the family.

The paper's cost model reduces to one hot operation — the oracle query
Delta_f(x | S) — so the whole repo funnels it through a single pluggable
backend (DESIGN.md §5):

    jnp               XLA-compiled dense path (CPU/GPU/TPU; the default
                      off-TPU) — one (K,K)x(K,B) matmul per batch.
    pallas            the fused Pallas TPU kernel (kernels/rbf_gain): kernel
                      block + whitening matmul + log fused in VMEM.
    pallas-interpret  the same kernel under the Pallas interpreter — slow,
                      portable, used to verify the TPU path in CI.
    auto              resolve at trace time: ``pallas`` on TPU, else ``jnp``.

``LogDet.gains``/``gain1`` route through ``GainOracle`` so every algorithm
(ThreeSieves, SieveStreaming(++), Salsa, the baselines, Greedy, the
distributed merge) inherits the fused path with zero call-site changes.

Select a backend per-objective (``make_objective(..., backend=...)``) or
process-wide via the ``REPRO_ORACLE_BACKEND`` environment variable.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp

from repro.constants import GAIN_EPS
from repro.kernels.rbf_gain import DEFAULT_BLOCK_B, fused_gains

from .functions import KernelConfig, KernelParams, traced_gain_rows

Array = jax.Array

BACKENDS = ("auto", "jnp", "pallas", "pallas-interpret")

_ENV_VAR = "REPRO_ORACLE_BACKEND"

def default_backend() -> str:
    """Process-wide default: ``REPRO_ORACLE_BACKEND`` env var, else auto."""
    backend = os.environ.get(_ENV_VAR, "auto")
    if backend not in BACKENDS:
        raise ValueError(
            f"{_ENV_VAR}={backend!r} invalid; choose from {BACKENDS}")
    return backend


def resolve_backend(backend: str) -> str:
    """Map a requested backend to the one that will actually run.

    ``auto`` picks the fused Pallas kernel on TPU and the jnp path
    elsewhere.  An explicit ``pallas`` request off the TPU raises: the
    compiled kernel needs the chip, and quietly running jnp instead would
    hide a missing device (``pallas-interpret`` runs the kernel logic
    anywhere).
    """
    if backend not in BACKENDS:
        raise ValueError(f"backend {backend!r} invalid; choose from {BACKENDS}")
    platform = jax.default_backend()
    if backend == "auto":
        return "pallas" if platform == "tpu" else "jnp"
    if backend == "pallas" and platform != "tpu":
        raise RuntimeError(
            "repro.core.oracle: backend 'pallas' needs a TPU, but "
            f"jax.default_backend() is {platform!r}; use 'auto', 'jnp' or "
            "'pallas-interpret'.")
    return backend


@dataclasses.dataclass(frozen=True)
class GainOracle:
    """Batched marginal gains for f(S) = 1/2 logdet(I + a Sigma_S).

    Stateless and hashable — it is carried as a static field of ``LogDet``
    and therefore baked into jitted programs.  All backends compute the
    same quantity:

        C    = Linv @ (a * k(S, X) * mask)       (K, B)
        gain = 1/2 * log((1 + a) - |C_col|^2)    (B,)
    """

    kernel: KernelConfig = KernelConfig()
    a: float = 1.0
    backend: str = "auto"
    block_b: int = DEFAULT_BLOCK_B
    dtype: jnp.dtype = jnp.float32

    @property
    def resolved(self) -> str:
        return resolve_backend(self.backend)

    @property
    def inv2l2(self) -> float:
        return 1.0 / (2.0 * self.kernel.lengthscale**2)

    # ------------------------------------------------------------------ query
    def gains(self, feats: Array, linv: Array, n: Array, X: Array,
              kern: KernelParams | None = None) -> Array:
        """feats (K, d), linv (K, K), n () live rows, X (B, d) -> (B,).

        ``kern`` switches the kernel hyperparameters to traced arrays
        (``KernelParams``): the row-major ``traced_gain_rows`` block on the
        jnp path, the scalar-operand Pallas kernel otherwise.  Without it
        the static ``KernelConfig`` arithmetic is kept bit-frozen.
        """
        backend = self.resolved
        if backend == "jnp":
            X = X.astype(self.dtype)
            mask = (jnp.arange(feats.shape[0]) < n).astype(self.dtype)
            if kern is not None:
                return traced_gain_rows(X, feats, linv, mask[None, :],
                                        a=self.a, kern=kern)[:, 0]
            KX = self.kernel.pairwise(feats, X) * mask[:, None]  # (K, B)
            C = linv @ (self.a * KX)  # (K, B)
            cn2 = jnp.sum(C * C, axis=0)  # (B,)
            dd2 = jnp.maximum((1.0 + self.a) - cn2, GAIN_EPS)
            return 0.5 * jnp.log(dd2)
        if kern is not None:
            from repro.kernels.rbf_gain import fused_gains_traced

            return fused_gains_traced(
                X, feats, linv, n, kern, a=self.a,
                use_pallas=(backend == "pallas"),
                interpret=(backend == "pallas-interpret"),
                block_b=self.block_b,
            ).astype(self.dtype)
        return fused_gains(
            X, feats, linv, n, a=self.a, inv2l2=self.inv2l2,
            kind=self.kernel.kind, use_pallas=(backend == "pallas"),
            interpret=(backend == "pallas-interpret"), block_b=self.block_b,
        ).astype(self.dtype)

    def gain1(self, feats: Array, linv: Array, n: Array, x: Array,
              kern: KernelParams | None = None) -> Array:
        """Single-item query (d,) -> () — a B=1 batch."""
        return self.gains(feats, linv, n, x[None, :], kern=kern)[0]


def make(kernel: KernelConfig, a: float = 1.0, *,
         backend: str | None = None, block_b: int = DEFAULT_BLOCK_B,
         dtype: jnp.dtype = jnp.float32) -> GainOracle:
    """Build a ``GainOracle``; ``backend=None`` reads the process default."""
    return GainOracle(kernel=kernel, a=a,
                      backend=backend or default_backend(),
                      block_b=block_b, dtype=dtype)
