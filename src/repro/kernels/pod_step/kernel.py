"""Pallas TPU kernel: one fused pod step — a whole ingest chunk for a
whole ThreeSieves session, one grid cell per session.

The unfused pod step (``serve.summarize.ingest_routed``) runs
``vmap(ThreeSieves.run_batched)`` as a chain of XLA ops per loop
iteration — gains matmul, TracedLadder thresholds, accept argmax,
Cholesky row append — each round-tripping the stacked (S, ...) state
through HBM.  This kernel replays the SAME loop entirely in VMEM: grid
(S,), one cell per session, with the session's summary (feats, L, Linv),
its chunk, and its scalar state resident for the cell's whole lifetime.

Per cell the loop body is the verbatim op sequence of
``ThreeSieves.run_batched`` under traced hyperparams:

    gains  = kernelmath.traced_gain_rows(chunk, feats, Linv, mask)  (C, 1)
    thr_p  = (rung_value(j_p)/2 - f(S)) / (K - |S|)   closed-form rungs
    accept = first p with gains[p] >= thr_p           (min-index reduce)
    append = kernel row + whitening matvec + Cholesky row write at n

Scalars (n, j, t, counters, per-session K/T/ladder/kernel hyperparams)
travel as int32/f32 SMEM tables; matrices as VMEM blocks.  Every accept
decision reads per-session hyperparameter SCALARS, so heterogeneous
(K, T, eps, lengthscale, kind) tenants share this one kernel.

Why the Cholesky append is safe to fuse (DESIGN.md §11): the append
touches exactly three rows (feats[n], L[n], Linv[n]) and reads only
state that is already resident in the cell's VMEM; rows above n are
never read again within the chunk, so in-place row writes between loop
iterations are exactly the functional ``LogDetState`` update.

In interpret mode the kernel is pinned BIT-EQUAL (f32) to
``run_batched`` run one session at a time, and to the decisions of
``vmap(run_batched)`` (tests/test_pod_step_kernel.py); bf16 is
tolerance-pinned.  The compiled kernel is checked on the chip by
``chip_smoke.py`` against the per-session ``run`` reference.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.constants import GAIN_EPS
from repro.core.thresholds import rung_value
from repro.kernelmath import (KernelParams, in_f32, matmul, pairwise_traced,
                              traced_gain_rows)

Array = jax.Array

# SMEM scalar-table layout (one row per session).
INT_COLS = ("n", "j", "t", "n_fused", "n_queries", "nv", "k_cap", "T",
            "ihi", "num_rungs", "kind_id")
FLT_COLS = ("fval", "base", "inv2l2")
NI = len(INT_COLS)
NF = len(FLT_COLS)
# outputs: the mutable prefix of the int table + fval
INT_OUT = 5  # n, j, t, n_fused, n_queries


def _write_row(ref, n, row, tile: int):
    """ref[0, n, :] = row as a read-modify-write of the aligned ``tile``
    rows holding row n: the chip stores only whole sublane tiles at a
    dynamic offset (8 rows in f32, 16 in bf16)."""
    base = pl.multiple_of((n // tile) * tile, tile)
    blk = ref[0, pl.ds(base, tile), :]
    hit = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0) == n - base
    ref[0, pl.ds(base, tile), :] = _select(hit, row, blk)


def _select(mask, x, y):
    """``jnp.where`` taken in f32 and cast back — exact, and the chip
    cannot lay out a bf16-shaped boolean mask."""
    f32 = jnp.float32
    return jnp.where(mask, x.astype(f32), y.astype(f32)).astype(y.dtype)


def _vec(x):
    """A () scalar as a (1, 1) f32 vector.  Arithmetic in the objective
    dtype runs on vectors: the chip's scalar unit has no bf16."""
    return jnp.full((1, 1), x, jnp.float32)


def _pod_step_kernel(chunk_ref, feats_in, l_in, linv_in, ints_in, flts_in,
                     feats_out, l_out, linv_out, ints_out, flts_out, *,
                     a: float, dtype, cap_k: int, cap_c: int,
                     row_tile: int):
    # carry the summary through; the loop below mutates the out-refs rows
    feats_out[...] = feats_in[...]
    l_out[...] = l_in[...]
    linv_out[...] = linv_in[...]

    n0, j0, t0 = ints_in[0, 0, 0], ints_in[0, 0, 1], ints_in[0, 0, 2]
    n_fused0, n_queries0 = ints_in[0, 0, 3], ints_in[0, 0, 4]
    nv, k_cap, T = ints_in[0, 0, 5], ints_in[0, 0, 6], ints_in[0, 0, 7]
    ihi, nr, kind_id = ints_in[0, 0, 8], ints_in[0, 0, 9], ints_in[0, 0, 10]
    fval0, base, inv2l2 = flts_in[0, 0, 0], flts_in[0, 0, 1], flts_in[0, 0, 2]
    kern = KernelParams(inv2l2=inv2l2, kind_id=kind_id)

    x_all = chunk_ref[0].astype(dtype)  # (C, d) — oracle casts X likewise
    ridx = jax.lax.broadcasted_iota(jnp.int32, (cap_c, 1), 0)  # (C, 1)
    kidx = jax.lax.broadcasted_iota(jnp.int32, (1, cap_k), 1)  # (1, K)

    def consume_all(j, t, steps):
        lowered = (t + steps) // T
        return jnp.minimum(j + lowered, nr - 1), (t + steps) % T

    def cond(carry):
        return carry[0] < nv

    def body(carry):
        cursor, n, j, t, fval32, n_fused = carry
        feats = feats_out[0]  # (K, d) — re-read: appends mutate these
        linv = linv_out[0]  # (K, K)
        mask = (kidx < n).astype(jnp.float32).astype(dtype)  # (1, K)
        fval = fval32.astype(dtype)  # (1, 1)

        # every iteration follows a state change (or is the first): one
        # fused gains pass, exactly as in ThreeSieves.run_batched
        gains = traced_gain_rows(x_all, feats, linv, mask,
                                 a=a, kern=kern)  # (C, 1)

        # closed-form rung seen by item p given no earlier accept
        r = ridx - cursor  # (C, 1)
        j_p = jnp.minimum(j + (t + r) // T, nr - 1)
        v_p = rung_value(base, ihi, nr, j_p, dtype)
        denom = _vec(jnp.maximum(k_cap - n, 1)).astype(dtype)
        thr_p = in_f32(jnp.divide, in_f32(jnp.divide, v_p, 2.0) - fval,
                       denom)  # residual_threshold
        # compared in f32 (exact for bf16 operands): the chip lays out
        # a bf16 compare's (C, 1) mask in a form it cannot reduce
        acc = ((gains.astype(jnp.float32) >= thr_p.astype(jnp.float32))
               & (ridx >= cursor) & (ridx < nv))
        exists = jnp.any(acc)
        # first accepting item: min-index reduce (2D-friendly argmax)
        istar = jnp.min(jnp.where(acc, ridx, jnp.int32(cap_c)))

        full = n >= k_cap
        take = (~full) & exists

        # --- append arithmetic (verbatim LogDet.append, traced-kern path);
        # computed unconditionally, written under pl.when(take) ------------
        # the accepted row, read from the ref (clamped: istar == C when
        # nothing accepts, and then the row is never written)
        row = jnp.minimum(istar, cap_c - 1)
        xs = chunk_ref[0, pl.ds(row, 1), :].astype(dtype)  # (1, d)
        kxr = pairwise_traced(xs, feats, kern) * mask  # (1, K)
        # multiply-reduce form of Linv @ (a * kx) — bit-matches the vmapped
        # LogDet.append (the (1,K) matvec lowers differently; see append)
        c_col = jnp.sum(linv * (a * kxr), axis=-1, keepdims=True)  # (K, 1)
        cr = c_col.reshape(1, -1)  # (1, K) — pure relayout, bit-exact
        dd2 = jnp.maximum(
            (1.0 + a) - jnp.sum(c_col * c_col, keepdims=True), GAIN_EPS)
        dd = in_f32(jnp.sqrt, dd2)
        gain = 0.5 * in_f32(jnp.log, dd2)
        at_n = kidx == n
        l_row = _select(at_n, dd, cr)  # (1, K)
        rr = in_f32(jnp.divide, -matmul(cr, linv), dd)
        linv_row = _select(at_n, in_f32(jnp.divide, 1.0, dd), rr)

        @pl.when(take)
        def _():
            _write_row(feats_out, n, xs, row_tile)
            _write_row(l_out, n, l_row, row_tile)
            _write_row(linv_out, n, linv_row, row_tile)

        # --- scalar carries: accept vs consume-the-rest -------------------
        rstar = istar - cursor
        j_acc = jnp.minimum(j + (t + rstar) // T, nr - 1)
        j_rej, t_rej = consume_all(j, t, nv - cursor)
        cursor2 = jnp.where(take, istar + 1, nv)
        j2 = jnp.where(take, j_acc, j_rej)
        t2 = jnp.where(take, jnp.int32(0), t_rej)
        n2 = jnp.where(take, n + 1, n)
        fval2 = jnp.where(take, fval + gain, fval).astype(jnp.float32)
        return cursor2, n2, j2, t2, fval2, n_fused + 1

    _, n, j, t, fval32, n_fused = jax.lax.while_loop(
        cond, body, (jnp.int32(0), n0, j0, t0, _vec(fval0), n_fused0))

    ints_out[0, 0, 0] = n
    ints_out[0, 0, 1] = j
    ints_out[0, 0, 2] = t
    ints_out[0, 0, 3] = n_fused
    ints_out[0, 0, 4] = n_queries0 + nv
    flts_out[0, 0, 0] = fval32[0, 0]


@functools.partial(jax.jit,
                   static_argnames=("a", "dtype", "interpret"))
def pod_step_pallas(chunks, feats, L, Linv, ints, flts, *, a: float,
                    dtype, interpret: bool = False):
    """One fused pod step over the stacked session axis.

    chunks (S, C, d) stream items (any float dtype — cast in-kernel),
    feats (S, K, d), L/Linv (S, K, K) in the objective dtype, ints
    (S, NI) int32 and flts (S, NF) f32 scalar tables (see
    ``INT_COLS``/``FLT_COLS``) -> (feats, L, Linv, ints_out (S, INT_OUT),
    fval (S, 1) f32).

    Grid is (S,): session s's whole working set lives in one grid cell's
    VMEM.  The ``ops.pod_step`` wrapper assembles the tables from a
    stacked ``TSState`` and handles hardware padding.
    """
    S, C, d = chunks.shape
    K = feats.shape[1]
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)

    # rows per sublane tile of the summary dtype; a divisor of K, so the
    # unpadded interpret path stays in bounds
    row_tile = math.gcd(K, 32 // jnp.dtype(feats.dtype).itemsize)
    kernel = functools.partial(_pod_step_kernel, a=a, dtype=dtype,
                               cap_k=K, cap_c=C, row_tile=row_tile)
    feats2, L2, Linv2, ints2, fval = pl.pallas_call(
        kernel,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, C, d), lambda s: (s, 0, 0)),  # chunk
            pl.BlockSpec((1, K, d), lambda s: (s, 0, 0)),  # feats
            pl.BlockSpec((1, K, K), lambda s: (s, 0, 0)),  # L
            pl.BlockSpec((1, K, K), lambda s: (s, 0, 0)),  # Linv
            smem((1, 1, NI), lambda s: (s, 0, 0)),  # int scalars
            smem((1, 1, NF), lambda s: (s, 0, 0)),  # float scalars
        ],
        out_specs=[
            pl.BlockSpec((1, K, d), lambda s: (s, 0, 0)),
            pl.BlockSpec((1, K, K), lambda s: (s, 0, 0)),
            pl.BlockSpec((1, K, K), lambda s: (s, 0, 0)),
            smem((1, 1, INT_OUT), lambda s: (s, 0, 0)),
            smem((1, 1, 1), lambda s: (s, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(feats.shape, feats.dtype),
            jax.ShapeDtypeStruct(L.shape, L.dtype),
            jax.ShapeDtypeStruct(Linv.shape, Linv.dtype),
            jax.ShapeDtypeStruct((S, 1, INT_OUT), jnp.int32),
            jax.ShapeDtypeStruct((S, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(chunks, feats, L, Linv, ints[:, None, :], flts[:, None, :])
    return feats2, L2, Linv2, ints2[:, 0, :], fval[:, 0, :]
