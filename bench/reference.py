"""Plain per-session reference of the served summarizer, written from the
paper and independent of the program under test.

Each session runs its algorithm one item at a time over exactly the items
it was sent since its last re-arm:

* the objective is the IVM log-determinant f(S) = 1/2 log det(I + a K_S)
  with the RBF kernel k(x, y) = exp(-|x - y|^2 / (2 l^2)), kept as the
  inverse Cholesky factor of I + a K_S, so a marginal gain is one kernel
  row, one triangular matvec and a log;
* ThreeSieves (arXiv:2010.10059, Algorithm 1): accept x when its gain
  reaches (v_j / 2 - f(S)) / (K - |S|), v_j the j-th rung of the ladder
  {(1 + eps)^i : m <= (1 + eps)^i <= K m}, m = f({e}) = 1/2 log(1 + a);
  after T rejections in a row move one rung down;
* SieveStreaming++ (Kazemi et al. 2019): one summary per rung, each with
  the same accept rule, and a rung is dropped once the best f(S) seen
  exceeds its guess v.  The answer is the best live rung's summary.

Everything is float32.  Every matrix product goes through ``dot``, whose
``mode`` fixes its precision: ``highest`` (f32 products, what the
configuration states), ``high`` (the three-pass bf16 split, emulated
explicitly so it means the same on any backend) or ``bf16`` (one bf16
pass).  The two lower modes exist for the control in ``control.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

GAIN_FLOOR = 1e-12  # the residual 1 + a - |c|^2 is clamped here before log
_HI = jax.lax.Precision.HIGHEST


def _bf16(x):
    """x rounded to bfloat16, kept in float32.  ``reduce_precision`` and
    not a cast pair: XLA may drop a float32 -> bfloat16 -> float32 round
    trip as excess precision, and on the TPU it does."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def dot(x, y, mode: str):
    """``x @ y`` in float32 at the precision ``mode`` names."""
    if mode == "highest":
        return jnp.matmul(x, y, precision=_HI)
    xh, yh = _bf16(x), _bf16(y)
    if mode == "bf16":
        return jnp.matmul(xh, yh, precision=_HI)
    if mode == "high":  # bf16_3x: hi*hi + hi*lo + lo*hi, lo*lo dropped
        xl, yl = _bf16(x - xh), _bf16(y - yh)
        return (jnp.matmul(xh, yh, precision=_HI)
                + jnp.matmul(xh, yl, precision=_HI)
                + jnp.matmul(xl, yh, precision=_HI))
    raise ValueError(f"unknown precision mode {mode!r}")


def ladder(eps: float, K: int, a: float) -> tuple:
    """(ihi, number of rungs) of {(1+eps)^i : m <= (1+eps)^i <= K m}."""
    m = 0.5 * math.log1p(a)
    ilo = math.ceil(math.log(m) / math.log1p(eps) - 1e-9)
    ihi = math.floor(math.log(K * m) / math.log1p(eps) + 1e-9)
    return ihi, max(ihi - ilo + 1, 1)


def kernel_row(x, feats, inv2l2, mode):
    """k(x, feats_i) for one item x (d,) against feats (K, d) -> (K,)."""
    g = dot(feats, x[:, None], mode)[:, 0]
    d2 = jnp.maximum(jnp.sum(x * x) + jnp.sum(feats * feats, axis=1)
                     - 2.0 * g, 0.0)
    return jnp.exp(-inv2l2 * d2)


def whiten(x, feats, linv, n, inv2l2, a, mode):
    """c = Linv (a k_S(x)) on the n live rows, and the clamped residual
    1 + a - |c|^2; the gain is half its log."""
    K = feats.shape[0]
    live = (jnp.arange(K) < n).astype(jnp.float32)
    k = a * kernel_row(x, feats, inv2l2, mode) * live
    c = dot(linv, k[:, None], mode)[:, 0]
    return c, jnp.maximum((1.0 + a) - jnp.sum(c * c), GAIN_FLOOR)


def append(feats, linv, n, fval, x, c, r2, mode):
    """Add x as row n: Linv gains the row [-(c^T Linv)/dd, 1/dd]."""
    K = feats.shape[0]
    dd = jnp.sqrt(r2)
    at = jnp.arange(K) == n
    row = jnp.where(at, 1.0 / dd, -dot(c[None, :], linv, mode)[0] / dd)
    return (jnp.where(at[:, None], x[None, :], feats),
            jnp.where(at[:, None], row[None, :], linv),
            n + 1, fval + 0.5 * jnp.log(r2))


# ----------------------------------------------------------- ThreeSieves
def ts_init(K, d):
    return dict(feats=jnp.zeros((K, d), jnp.float32),
                linv=jnp.eye(K, dtype=jnp.float32),
                n=jnp.int32(0), fval=jnp.float32(0.0),
                j=jnp.int32(0), t=jnp.int32(0))


def ts_step(st, x, plan, *, inv2l2, a, mode):
    """One item of ThreeSieves; ``plan`` = (Kp, T, eps_base, ihi, rungs)
    as () arrays."""
    Kp, T, base, ihi, rungs = plan
    c, r2 = whiten(x, st["feats"], st["linv"], st["n"], inv2l2, a, mode)
    gain = 0.5 * jnp.log(r2)
    v = jnp.power(base, (ihi - jnp.clip(st["j"], 0, rungs - 1))
                  .astype(jnp.float32))
    thr = (v / 2.0 - st["fval"]) / jnp.maximum(Kp - st["n"], 1)
    take = (gain >= thr) & (st["n"] < Kp)
    feats, linv, n, fval = append(st["feats"], st["linv"], st["n"],
                                  st["fval"], x, c, r2, mode)
    t_rej = st["t"] + 1
    down = t_rej >= T
    new = dict(feats=feats, linv=linv, n=n, fval=fval, j=st["j"],
               t=jnp.int32(0))
    old = dict(st, j=jnp.where(down, jnp.minimum(st["j"] + 1, rungs - 1),
                               st["j"]),
               t=jnp.where(down, 0, t_rej))
    return jax.tree_util.tree_map(lambda p, q: jnp.where(take, p, q),
                                  new, old)


def ts_answer(st):
    return st["feats"], st["n"], st["fval"]


# ------------------------------------------------------ SieveStreaming++
def ss_init(K, d, cap):
    one = ts_init(K, d)
    rungs = {k: jnp.broadcast_to(v, (cap,) + v.shape)
             for k, v in one.items() if k in ("feats", "linv", "n", "fval")}
    return dict(rungs, alive=jnp.zeros((cap,), bool), lb=jnp.float32(0.0))


def ss_arm(st, rungs):
    """Mark the plan's live rungs (a fresh or re-armed session)."""
    cap = st["alive"].shape[0]
    return dict(st, alive=jnp.arange(cap) < rungs)


def ss_step(st, x, plan, *, inv2l2, a, mode):
    Kp, _, base, ihi, rungs = plan
    cap = st["alive"].shape[0]
    v = jnp.power(base, (ihi - jnp.arange(cap)).astype(jnp.float32))

    def one(feats, linv, n, fval):
        c, r2 = whiten(x, feats, linv, n, inv2l2, a, mode)
        return c, r2

    c, r2 = jax.vmap(one)(st["feats"], st["linv"], st["n"], st["fval"])
    gain = 0.5 * jnp.log(r2)
    thr = (v / 2.0 - st["fval"]) / jnp.maximum(Kp - st["n"], 1)
    take = (gain >= thr) & st["alive"] & (st["n"] < Kp)
    f2, l2, n2, v2 = jax.vmap(
        lambda f, l, n, fv, cc, rr: append(f, l, n, fv, x, cc, rr, mode))(
        st["feats"], st["linv"], st["n"], st["fval"], c, r2)
    pick = lambda p, q: jnp.where(
        take.reshape(take.shape + (1,) * (p.ndim - 1)), p, q)
    out = dict(feats=pick(f2, st["feats"]), linv=pick(l2, st["linv"]),
               n=pick(n2, st["n"]), fval=pick(v2, st["fval"]))
    lb = jnp.maximum(st["lb"], jnp.max(out["fval"]))
    return dict(out, alive=st["alive"] & (v > lb), lb=lb)


def ss_answer(st):
    i = jnp.argmax(jnp.where(st["alive"], st["fval"], -jnp.inf))
    return st["feats"][i], st["n"][i], st["fval"][i]


# ------------------------------------------------------------- replay
ALGOS = {
    "threesieves": (ts_step, ts_answer),
    "sievestreaming++": (ss_step, ss_answer),
}


@functools.partial(jax.jit, static_argnames=("algo", "inv2l2", "a", "mode"))
def _block(states, pool, rows, valid, plans, *, algo, inv2l2, a, mode):
    """Sessions (leading axis) each run over their block of pool rows
    ``rows`` (P, L); items past ``valid`` (P,) leave a session untouched."""
    step = ALGOS[algo][0]

    def session(st, idx, nv, plan):
        def body(s, i):
            s2 = step(s, pool[idx[i]], plan, inv2l2=inv2l2, a=a, mode=mode)
            return jax.tree_util.tree_map(
                lambda p, q: jnp.where(i < nv, p, q), s2, s), None

        return jax.lax.scan(body, st, jnp.arange(idx.shape[0]))[0]

    return jax.vmap(session)(states, rows, valid, plans)


def replay(algo: str, cfg: dict, pool, rows: list, plans: list, *,
           mode: str = "highest", block: int = 1024):
    """Run each session over its items, pool rows ``rows[i]`` in stream
    order, on its plan ``plans[i]`` = (K, T, eps); return the answers as
    host arrays (feats (P, K_max, d), n (P,), fval (P,)).

    The items go through in blocks of ``block`` so that one compiled
    program serves any stream length."""
    K, d, a = cfg["K_max"], cfg["d"], float(cfg["a"])
    inv2l2 = float(np.float32(1.0 / (2.0 * float(cfg["lengthscale"]) ** 2)))
    P = len(rows)
    table = []
    for Kp, T, eps in plans:
        ihi, rungs = ladder(float(eps), int(Kp), a)
        table.append((Kp, T, np.float32(1.0 + float(eps)), ihi, rungs))
    plan_arr = tuple(np.asarray([r[i] for r in table],
                                np.float32 if i == 2 else np.int32)
                     for i in range(5))
    if algo == "threesieves":
        st = ts_init(K, d)
        states = jax.tree_util.tree_map(
            lambda v: jnp.broadcast_to(v, (P,) + v.shape), st)
    elif algo == "sievestreaming++":
        cap = ladder(float(cfg["default_plan"][2]), K, a)[1]
        st = ss_init(K, d, cap)
        states = jax.vmap(ss_arm)(jax.tree_util.tree_map(
            lambda v: jnp.broadcast_to(v, (P,) + v.shape), st),
            jnp.asarray(plan_arr[4]))
    else:
        raise ValueError(f"no reference for {algo!r}")
    pool = jax.device_put(np.asarray(pool, np.float32))
    lens = np.asarray([len(r) for r in rows])
    for lo in range(0, max(int(lens.max(initial=0)), 1), block):
        idx = np.zeros((P, block), np.int32)
        for i, r in enumerate(rows):
            part = r[lo:lo + block]
            idx[i, :len(part)] = part
        valid = np.clip(lens - lo, 0, block).astype(np.int32)
        states = _block(states, pool, idx, valid, plan_arr, algo=algo,
                        inv2l2=inv2l2, a=a, mode=mode)
    answer = jax.vmap(ALGOS[algo][1])(states)
    return tuple(np.asarray(v) for v in answer)
