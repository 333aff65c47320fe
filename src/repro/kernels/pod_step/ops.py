"""Public pod-step entry: backend resolution, table assembly, padding.

``pod_step(algo, state, chunks, counts)`` advances every session in a
SummarizerPod by one ingest chunk.  Backends (mirroring the oracle's
``REPRO_ORACLE_BACKEND`` scheme, selected via ``REPRO_PODSTEP_BACKEND``
or an explicit argument):

    jnp               vmap(run_batched) over the session axis — the
                      reference semantics (``ref.pod_step_ref``).
    pallas            the fused kernel: ONE grid launch per chunk, grid
                      (S,), whole sessions resident in VMEM.  TPU only.
    pallas-interpret  the same kernel under the Pallas interpreter —
                      slow, portable, bit-pinned in CI against
                      run_batched run one session at a time.
    auto              pallas on TPU when the algorithm is fusable,
                      else jnp.

Only ``ThreeSieves`` is fusable today (the stacked sieves carry a
rung-instance axis the (S,)-grid kernel does not model); non-fusable
algorithms fall back to jnp — with one ``RuntimeWarning`` per process
if the fused path was requested explicitly.  An explicit ``pallas``
request off the TPU raises.

Bit-safety contract: the interpret path runs UNPADDED — hardware padding
(lanes to 128, sublanes to 8) is applied only when the compiled TPU
kernel will consume it, so CI's bit-equality pin covers the exact op
sequence ``run_batched`` runs.
"""
from __future__ import annotations

import functools
import os
import warnings

import jax
import jax.numpy as jnp

from repro.core.functions import LogDetState
from repro.core.threesieves import ThreeSieves, TSState
from repro.obs import record_backend_fallback

from .kernel import pod_step_pallas
from .ref import pod_step_ref

Array = jax.Array

BACKENDS = ("auto", "jnp", "pallas", "pallas-interpret")

_ENV_VAR = "REPRO_PODSTEP_BACKEND"

_warned: set = set()


def _warn_once(key: str, msg: str) -> None:
    if key in _warned:
        return
    _warned.add(key)
    warnings.warn(msg, RuntimeWarning, stacklevel=4)


def default_backend() -> str:
    """Process-wide default: ``REPRO_PODSTEP_BACKEND`` env var, else auto."""
    backend = os.environ.get(_ENV_VAR, "auto")
    if backend not in BACKENDS:
        raise ValueError(
            f"{_ENV_VAR}={backend!r} invalid; choose from {BACKENDS}")
    return backend


def fusable(algo) -> bool:
    """Whether ``algo`` has a fused pod-step kernel."""
    return isinstance(algo, ThreeSieves)


def resolve(backend: str | None, algo) -> str:
    """Map a requested backend to the one that will actually run.

    An explicit fused request for an algorithm without a fused kernel
    degrades to ``jnp`` with one ``RuntimeWarning`` per process, counted
    in ``backend_fallback_total`` — an algorithm choice, not a device
    one.  An explicit ``pallas`` request off the TPU raises, like
    ``oracle.resolve_backend``.
    """
    backend = default_backend() if backend is None else backend
    if backend not in BACKENDS:
        raise ValueError(
            f"backend {backend!r} invalid; choose from {BACKENDS}")
    platform = jax.default_backend()
    if backend == "auto":
        return "pallas" if (platform == "tpu" and fusable(algo)) else "jnp"
    if backend in ("pallas", "pallas-interpret") and not fusable(algo):
        # warn once per process, but COUNT every degrade: the CI metrics
        # artifact shows which path actually ran, run after run
        record_backend_fallback("pod_step", backend, "jnp")
        _warn_once(
            f"fusable:{type(algo).__name__}",
            f"repro.kernels.pod_step: backend {backend!r} requested but "
            f"{type(algo).__name__} has no fused pod-step kernel (only "
            "ThreeSieves does) — falling back to the 'jnp' "
            "vmap(run_batched) path.")
        return "jnp"
    if backend == "pallas" and platform != "tpu":
        raise RuntimeError(
            "repro.kernels.pod_step: backend 'pallas' needs a TPU, but "
            f"jax.default_backend() is {platform!r}; use 'auto', 'jnp' or "
            "'pallas-interpret'.")
    return backend


def _pad_axis(x: Array, m: int, axis: int) -> Array:
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit,
                   static_argnames=("algo", "use_pallas", "interpret"))
def _pod_step_fused(algo, state: TSState, chunks: Array, counts: Array, *,
                    use_pallas: bool, interpret: bool) -> TSState:
    """Assemble SMEM tables from the stacked state, launch, reassemble."""
    f = algo.f
    S, C, _ = chunks.shape
    K = f.K
    ld, hp = state.ld, state.hp
    nv = jnp.clip(jnp.asarray(counts, jnp.int32), 0, C)  # run_batched's clip
    ints = jnp.stack([
        ld.n, state.j, state.t, state.n_fused, ld.n_queries, nv,
        hp.k_cap, hp.T, hp.ihi, hp.num_rungs, hp.kernel_kind,
    ], axis=-1).astype(jnp.int32)  # (S, NI)
    flts = jnp.stack([
        ld.fval.astype(jnp.float32),  # bf16 -> f32 transport is exact
        hp.base, hp.inv2l2,
    ], axis=-1).astype(jnp.float32)  # (S, NF)

    feats, L, Linv = ld.feats, ld.L, ld.Linv
    if use_pallas:
        # hardware alignment only on the compiled path — the interpret
        # path stays unpadded so the CI bit-pin covers run_batched's ops
        chunks = _pad_axis(_pad_axis(chunks, 128, 2), 8, 1)
        feats = _pad_axis(_pad_axis(feats, 128, 2), 128, 1)
        L = _pad_axis(_pad_axis(L, 128, 2), 128, 1)
        Linv = _pad_axis(_pad_axis(Linv, 128, 2), 128, 1)

    feats2, L2, Linv2, iouts, fvals = pod_step_pallas(
        chunks, feats, L, Linv, ints, flts,
        a=f.a, dtype=f.dtype, interpret=interpret)
    if use_pallas:
        feats2 = feats2[:, :K, :f.d]
        L2 = L2[:, :K, :K]
        Linv2 = Linv2[:, :K, :K]

    ld2 = LogDetState(
        feats=feats2, L=L2, Linv=Linv2,
        n=iouts[:, 0],
        fval=fvals[:, 0].astype(f.dtype),
        n_queries=iouts[:, 4],
    )
    return TSState(ld=ld2, j=iouts[:, 1], t=iouts[:, 2],
                   n_fused=iouts[:, 3], hp=hp)


def pod_step(algo, state, chunks: Array, counts: Array, *,
             backend: str | None = None):
    """Advance every pod session by one chunk via the resolved backend.

    algo: the pod's (static) sieve algorithm; state: stacked per-slot
    algorithm state; chunks (S, C, d); counts (S,) valid prefixes;
    backend: one of ``BACKENDS`` or None for the process default.
    Returns the stepped stacked state — identical pytree structure and,
    for f32, the same accept decisions across backends.
    """
    resolved = resolve(backend, algo)
    # C = 1 chunks hit XLA's GEMV path, whose reduction order differs from
    # the kernel's GEMM — and a one-item launch fuses nothing anyway
    if resolved == "jnp" or chunks.shape[1] < 2:
        return pod_step_ref(algo, state, chunks, counts)
    return _pod_step_fused(algo, state, chunks, counts,
                           use_pallas=(resolved == "pallas"),
                           interpret=(resolved == "pallas-interpret"))


def _reset_warnings() -> None:  # test hook
    _warned.clear()


__all__ = ["BACKENDS", "default_backend", "fusable", "pod_step",
           "pod_step_ref", "resolve"]
