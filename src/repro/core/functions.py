"""Submodular objective functions as incremental, jittable JAX state machines.

The paper's workhorse objective is the Informative Vector Machine (IVM)
log-determinant

    f(S) = 1/2 * log det(I + a * Sigma_S),   Sigma_S[i, j] = k(e_i, e_j)

which is non-negative, monotone and submodular for any PSD kernel k
(Seeger 2004).  For a *normalized* kernel (k(e, e) = 1) the maximum singleton
value is known analytically:  m = f({e}) = 1/2 * log(1 + a).

TPU-native formulation (see DESIGN.md §3)
-----------------------------------------
We maintain, incrementally and in fixed-shape (K, ...) zero-padded buffers:

  * ``feats``  (K, d)   the selected items,
  * ``L``      (K, K)   Cholesky factor of  M = I + a * Sigma_S,
  * ``Linv``   (K, K)   its explicit inverse,
  * ``n``               number of live rows,
  * ``fval``            current objective value  ( = sum(log diag L) ).

Appending an element e:

    c   = Linv @ (a * k_S(e))            # O(K^2) matmul row
    dd  = sqrt((1 + a) - ||c||^2)
    df  = log dd                         # the marginal gain
    L   <- [[L, 0], [c^T, dd]]
    Linv<- [[Linv, 0], [-(c^T Linv)/dd, 1/dd]]

Because ``Linv`` is explicit, the marginal gain of a *batch* of B candidates
is a dense (K,K)x(K,B) matmul + column norms + log — pure MXU work, no
sequential triangular solves.  This is the hardware adaptation of the paper's
"one oracle query per element".
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.constants import GAIN_EPS, NORM_EPS

Array = jax.Array

# ---------------------------------------------------------------------------
# Kernel functions
# ---------------------------------------------------------------------------

# Traced-kernel math lives in the cycle-free ``repro.kernelmath`` (shared
# with the Pallas kernel bodies); re-exported here as the core API.
from repro.kernelmath import (  # noqa: E402  (re-export)
    KERNEL_KIND_IDS, KernelParams, matmul, pairwise_traced, traced_gain_rows)

__all__ = [
    "KERNEL_KIND_IDS", "KernelConfig", "KernelParams", "LogDet",
    "LogDetState", "naive_logdet", "pairwise_traced",
    "rbf_lengthscale_batch", "rbf_lengthscale_stream", "traced_gain_rows",
]


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Positive-definite kernel. ``rbf`` is the paper's choice.

    lengthscale convention follows the paper: l = 1/(2 sqrt(d)) for the batch
    experiments, l = 1/sqrt(d) for the streaming experiments.
    """

    kind: str = "rbf"  # "rbf" | "linear_norm"
    lengthscale: float = 1.0

    def pairwise(self, x: Array, y: Array) -> Array:
        """k(x_i, y_j) for x (N, d), y (M, d) -> (N, M)."""
        if self.kind == "rbf":
            # squared distances via the expanded form (MXU friendly).
            xn = jnp.sum(x * x, axis=-1, keepdims=True)  # (N, 1)
            yn = jnp.sum(y * y, axis=-1, keepdims=True).T  # (1, M)
            d2 = jnp.maximum(xn + yn - 2.0 * (x @ y.T), 0.0)
            return jnp.exp(-d2 / (2.0 * self.lengthscale**2))
        if self.kind == "linear_norm":
            # normalized linear kernel: <x, y> / (|x||y|)  in [-1, 1] -> [0,1]
            xs = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True),
                                 NORM_EPS)
            ys = y / jnp.maximum(jnp.linalg.norm(y, axis=-1, keepdims=True),
                                 NORM_EPS)
            return 0.5 * (xs @ ys.T + 1.0)
        raise ValueError(f"unknown kernel {self.kind}")


def rbf_lengthscale_batch(d: int) -> float:
    """Paper's batch-experiment lengthscale l = 1/(2 sqrt(d))."""
    return 1.0 / (2.0 * (d**0.5))


def rbf_lengthscale_stream(d: int) -> float:
    """Paper's streaming-experiment lengthscale l = 1/sqrt(d)."""
    return 1.0 / (d**0.5)


# ---------------------------------------------------------------------------
# Incremental log-det state
# ---------------------------------------------------------------------------


def _set_row(a: Array, at: Array, row: Array) -> Array:
    """``a`` with row ``row`` where the (K,) mask ``at`` is set."""
    return jnp.where(at[:, None], row[None, :], a)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LogDetState:
    """Fixed-shape summary state for f(S) = 1/2 log det(I + a Sigma_S)."""

    feats: Array  # (K, d) zero padded
    L: Array  # (K, K) lower triangular, identity on padded rows
    Linv: Array  # (K, K)
    n: Array  # () int32 — number of live rows
    fval: Array  # () float32 — current f(S)
    n_queries: Array  # () int32 — oracle queries issued (metrics only)

    @property
    def K(self) -> int:
        return self.feats.shape[0]


@dataclasses.dataclass(frozen=True)
class LogDet:
    """The IVM objective bound to a kernel and scale ``a``.

    All methods are pure and jittable; ``self`` is a static argument.

    ``backend`` selects the marginal-gain oracle implementation
    (``jnp`` | ``pallas`` | ``pallas-interpret`` | ``auto``); ``None``
    defers to the process default (``REPRO_ORACLE_BACKEND`` env var, else
    ``auto``).  See ``repro.core.oracle`` / DESIGN.md §5.
    """

    K: int
    d: int
    kernel: KernelConfig = KernelConfig()
    a: float = 1.0
    dtype: jnp.dtype = jnp.float32
    backend: str | None = None

    @property
    def oracle(self):
        """The batched gain oracle every query below routes through."""
        from . import oracle

        return oracle.make(self.kernel, self.a, backend=self.backend,
                           dtype=self.dtype)

    # -- constants -----------------------------------------------------------
    @property
    def singleton_value(self) -> float:
        """m = f({e}) for normalized kernels — known analytically (paper §4)."""
        import math

        return 0.5 * math.log(1.0 + self.a)

    # -- state ---------------------------------------------------------------
    def init(self) -> LogDetState:
        K = self.K
        eye = jnp.eye(K, dtype=self.dtype)
        return LogDetState(
            feats=jnp.zeros((K, self.d), self.dtype),
            L=eye,
            Linv=eye,
            n=jnp.zeros((), jnp.int32),
            fval=jnp.zeros((), self.dtype),
            n_queries=jnp.zeros((), jnp.int32),
        )

    def _mask(self, state: LogDetState) -> Array:
        return (jnp.arange(self.K) < state.n).astype(self.dtype)

    # -- queries --------------------------------------------------------------
    def gains(self, state: LogDetState, X: Array,
              kern: KernelParams | None = None) -> Array:
        """Marginal gains Delta_f(x | S) for a batch X (B, d) -> (B,).

        One fused batch query — (K,B) kernel block, one (K,K)x(K,B) matmul —
        dispatched through the pluggable ``GainOracle`` backend.  ``kern``
        (optional ``KernelParams``) switches the kernel hyperparameters
        from trace constants to traced arrays — the sieve family passes
        ``state.hp.kern`` so per-session kernels share one program.
        """
        return self.oracle.gains(state.feats, state.Linv, state.n, X,
                                 kern=kern)

    def gain1(self, state: LogDetState, x: Array,
              kern: KernelParams | None = None) -> Array:
        """Single-item marginal gain (d,) -> ()."""
        return self.oracle.gain1(state.feats, state.Linv, state.n, x,
                                 kern=kern)

    # -- update ---------------------------------------------------------------
    def append(self, state: LogDetState, x: Array,
               kern: KernelParams | None = None) -> LogDetState:
        """Add x to the summary (caller guarantees state.n < K).

        With ``kern`` the kernel row and the whitening matvec use the
        traced-kernel row form (the exact op sequence the fused pod-step
        kernel replays); without it the static ``KernelConfig`` path is
        bit-frozen for the baselines.
        """
        x = x.astype(self.dtype)
        mask = self._mask(state)
        if kern is None:
            kx = self.kernel.pairwise(state.feats, x[None, :])[:, 0] * mask
            c = state.Linv @ (self.a * kx)  # (K,)
        else:
            kx = pairwise_traced(x[None, :], state.feats, kern)[0] * mask
            # multiply-reduce form of Linv @ (a * kx): unlike the (1, K)
            # matvec, this lowering is bit-stable under vmap — the fused
            # pod-step kernel (unbatched per grid cell) must match the
            # vmapped session axis bit for bit
            c = jnp.sum(state.Linv * (self.a * kx)[None, :], axis=-1)  # (K,)
        dd2 = jnp.maximum((1.0 + self.a) - jnp.sum(c * c), GAIN_EPS)
        dd = jnp.sqrt(dd2)
        gain = 0.5 * jnp.log(dd2)

        # Row n is written by a select on the row index, not a scatter:
        # under the pod's vmap over sessions and the sieves' vmap over
        # instances, XLA:TPU (jaxlib 0.9.0) dropped the scatters, and
        # summaries kept zero rows on the chip.  The values are the same.
        at = jnp.arange(state.K) == state.n  # (K,) one-hot of row n
        # L row n := [c, dd] ; padded diag was 1 -> overwrite.
        L = _set_row(state.L, at, jnp.where(at, dd, c))
        # Linv row n := [-(c @ Linv)/dd, 1/dd]
        r = -matmul(c, state.Linv) / dd
        Linv = _set_row(state.Linv, at, jnp.where(at, 1.0 / dd, r))
        feats = _set_row(state.feats, at, x)
        return LogDetState(
            feats=feats,
            L=L,
            Linv=Linv,
            n=state.n + 1,
            fval=state.fval + gain,
            n_queries=state.n_queries,
        )

    def maybe_append(self, state: LogDetState, x: Array, take: Array,
                     kern: KernelParams | None = None) -> LogDetState:
        """Conditionally append (vmap/select friendly)."""
        appended = self.append(state, x, kern)
        return jax.tree_util.tree_map(
            lambda a, b: jnp.where(take, a, b), appended, state
        )

    # -- batch (re)evaluation ---------------------------------------------------
    def refactor(self, feats: Array, n: Array) -> LogDetState:
        """Full O(K^3) factorization of a given summary buffer.

        Used by replacement-based baselines (ISI, Preemption) and by the
        final evaluation of Random.  Padded rows/cols are identity, so they
        contribute 0 to the log-determinant.  Works for any buffer length
        (QuickStream evaluates rings larger than K).
        """
        K = feats.shape[0]
        live = jnp.arange(K) < n
        m2 = live[:, None] & live[None, :]
        Kmat = self.kernel.pairwise(feats, feats)
        M = jnp.where(m2, jnp.eye(K, dtype=self.dtype) + self.a * Kmat,
                      jnp.eye(K, dtype=self.dtype))
        L = jnp.linalg.cholesky(M)
        Linv = jax.scipy.linalg.solve_triangular(
            L, jnp.eye(K, dtype=self.dtype), lower=True
        )
        fval = jnp.sum(jnp.where(live, jnp.log(jnp.diagonal(L)), 0.0))
        return LogDetState(
            feats=jnp.where(live[:, None], feats, 0.0).astype(self.dtype),
            L=L,
            Linv=Linv,
            n=n.astype(jnp.int32),
            fval=fval.astype(self.dtype),
            n_queries=jnp.zeros((), jnp.int32),
        )

    def evaluate(self, feats: Array, n: Array) -> Array:
        """f(S) for an explicit summary buffer — the naive oracle."""
        return self.refactor(feats, n).fval


def naive_logdet(feats: Array, kernel: KernelConfig, a: float) -> Array:
    """Pure-numpy-style oracle: f(S) = 1/2 logdet(I + a K_SS) on live rows only.

    Reference for tests; feats has no padding here.
    """
    Kmat = kernel.pairwise(feats, feats)
    M = jnp.eye(feats.shape[0], dtype=Kmat.dtype) + a * Kmat
    sign, ld = jnp.linalg.slogdet(M)
    return 0.5 * ld
