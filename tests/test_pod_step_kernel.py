"""Pins for the fused Pallas pod-step kernel (kernels/pod_step).

The contract: the fused kernel (exercised via the Pallas interpreter on
CPU) is BIT-EQUAL in f32 to ``ThreeSieves.run_batched`` run one session
at a time — the op sequence each grid cell replays — under heterogeneous
per-session hyperparameters (K, T, eps, lengthscale, kernel kind),
ragged chunk tails, multiple ingest rounds, and through the
SummarizerPod.  Against the vmapped jnp path (``pod_step_ref``) every
accept decision and counter is equal and the factor rows agree to f32
rounding: XLA:CPU orders the small reductions of the batched program
differently from the unbatched one.  bf16 is tolerance-pinned (the
carry stays bf16).
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api
from repro.core.functions import KernelConfig, LogDet
from repro.core.spec import SessionSpec
from repro.core.threesieves import ThreeSieves
from repro.kernels.pod_step import ops as ps
from repro.kernels.pod_step import pod_step, pod_step_ref
from repro.serve.summarize import SummarizerPod


def _algo(dtype=jnp.float32, backend="jnp", K=8, d=5):
    f = LogDet(K=K, d=d, kernel=KernelConfig("rbf", 1.5), a=1.0,
               dtype=dtype, backend=backend)
    return ThreeSieves(f, eps=0.2, T=10)


def _mixed_stack(algo):
    """Stacked states with heterogeneous (K, T, eps, lengthscale, kind)."""
    hps = [
        algo.hyper(K=6, T=10, eps=0.2, lengthscale=1.5),
        algo.hyper(K=4, T=3, eps=0.5, lengthscale=0.7),
        algo.hyper(K=8, T=20, eps=0.1, lengthscale=2.0,
                   kernel_kind="linear_norm"),
        algo.hyper(K=3, T=5, eps=0.3, lengthscale=1.0),
    ]
    states = [algo.init(h) for h in hps]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def _assert_tree_equal(a, b, msg=""):
    for (pa, la), lb in zip(jax.tree_util.tree_leaves_with_path(a),
                            jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb),
            err_msg=f"{msg} leaf {jax.tree_util.keystr(pa)}")


def _assert_same_decisions(a, b, msg=""):
    """Integer and bool leaves (n, rung, counters) equal; float leaves
    equal to f32 rounding."""
    for (pa, la), lb in zip(jax.tree_util.tree_leaves_with_path(a),
                            jax.tree_util.tree_leaves(b)):
        la, lb = np.asarray(la), np.asarray(lb)
        err = f"{msg} leaf {jax.tree_util.keystr(pa)}"
        if la.dtype.kind == "f":
            np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-6,
                                       err_msg=err)
        else:
            np.testing.assert_array_equal(la, lb, err_msg=err)


def _per_session_ref(algo, st, chunks, counts):
    """``run_batched`` one session at a time (``lax.map``, no vmap)."""
    step = jax.jit(lambda st, c, n: jax.lax.map(
        lambda args: algo.run_batched(*args), (st, c, n)))
    return step(st, chunks, counts)


def test_fused_bit_equal_heterogeneous_multi_round():
    """fused(pallas-interpret) == per-session run_batched, bit for bit,
    over mixed per-session hyperparams and ragged counts, across rounds;
    the vmapped path makes the same decisions."""
    algo = _algo()
    ref = _mixed_stack(algo)
    fused = ref
    S, C, d = 4, 12, 5
    for rnd in range(4):
        chunks = jax.random.normal(jax.random.PRNGKey(rnd), (S, C, d))
        counts = jax.random.randint(jax.random.PRNGKey(100 + rnd),
                                    (S,), 0, C + 1)
        solo = _per_session_ref(algo, fused, chunks, counts)
        ref = pod_step(algo, ref, chunks, counts, backend="jnp")
        fused = pod_step(algo, fused, chunks, counts,
                         backend="pallas-interpret")
        _assert_tree_equal(solo, fused, msg=f"round {rnd}")
        _assert_same_decisions(ref, fused, msg=f"round {rnd}")
    assert int(jnp.sum(ref.ld.n)) > 0  # the rounds actually accepted


def test_fused_bit_equal_ragged_edges():
    """Edge counts: empty chunk, single item, exactly-full chunk, and a
    count beyond C (clipped like run_batched's n_valid)."""
    algo = _algo()
    st = _mixed_stack(algo)
    S, C, d = 4, 8, 5
    chunks = jax.random.normal(jax.random.PRNGKey(7), (S, C, d))
    for counts in ([0, 0, 0, 0], [1, 0, C, 3], [C, C, C, C],
                   [C + 5, 2, 0, 1]):
        counts = jnp.asarray(counts, jnp.int32)
        ref = pod_step(algo, st, chunks, counts, backend="jnp")
        fused = pod_step(algo, st, chunks, counts,
                         backend="pallas-interpret")
        _assert_tree_equal(_per_session_ref(algo, st, chunks, counts),
                           fused, msg=f"counts {counts}")
        _assert_same_decisions(ref, fused, msg=f"counts {counts}")


def test_fused_matches_when_summaries_saturate():
    """Once every slot hits its K cap the loop takes the full-summary
    branch — counters (rung, t, n_queries, n_fused) must still agree."""
    algo = _algo()
    ref = _mixed_stack(algo)
    fused = ref
    S, C, d = 4, 16, 5
    for rnd in range(6):
        chunks = 0.05 * jax.random.normal(
            jax.random.PRNGKey(50 + rnd), (S, C, d))
        counts = jnp.full((S,), C, jnp.int32)
        solo = _per_session_ref(algo, fused, chunks, counts)
        ref = pod_step(algo, ref, chunks, counts, backend="jnp")
        fused = pod_step(algo, fused, chunks, counts,
                         backend="pallas-interpret")
        _assert_tree_equal(solo, fused, msg=f"saturated round {rnd}")
    _assert_same_decisions(ref, fused, msg="saturated")
    # at least one session actually saturated its per-slot cap
    assert bool(jnp.any(ref.ld.n == ref.hp.k_cap))


def test_fused_bf16_tolerance_and_carry_dtype():
    """bf16 objective: fused tracks unfused within bf16 resolution and
    the state dtypes survive the f32 scalar transport."""
    algo = _algo(dtype=jnp.bfloat16)
    ref = _mixed_stack(algo)
    fused = ref
    S, C, d = 4, 12, 5
    for rnd in range(3):
        chunks = jax.random.normal(jax.random.PRNGKey(rnd), (S, C, d))
        counts = jnp.full((S,), C, jnp.int32)
        ref = pod_step(algo, ref, chunks, counts, backend="jnp")
        fused = pod_step(algo, fused, chunks, counts,
                         backend="pallas-interpret")
    assert fused.ld.fval.dtype == jnp.bfloat16
    assert fused.ld.Linv.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(ref.ld.n),
                                  np.asarray(fused.ld.n))
    np.testing.assert_allclose(
        np.asarray(ref.ld.fval, np.float32),
        np.asarray(fused.ld.fval, np.float32), rtol=0.05, atol=0.05)


def test_single_item_chunks_fall_back_bit_equal():
    """C = 1 hits XLA's GEMV path (different reduction order than the
    kernel's GEMM) — pod_step must route it to the reference."""
    algo = _algo()
    st = _mixed_stack(algo)
    chunks = jax.random.normal(jax.random.PRNGKey(3), (4, 1, 5))
    counts = jnp.asarray([1, 1, 0, 1], jnp.int32)
    ref = pod_step_ref(algo, st, chunks, counts)
    out = pod_step(algo, st, chunks, counts, backend="pallas-interpret")
    _assert_tree_equal(ref, out, msg="C=1")


# ---------------------------------------------------------------- dispatch


def test_resolve_backends():
    algo = _algo()
    assert ps.resolve("jnp", algo) == "jnp"
    assert ps.resolve("pallas-interpret", algo) == "pallas-interpret"
    on_tpu = jax.default_backend() == "tpu"
    assert ps.resolve(None, algo) == ("pallas" if on_tpu else "jnp")
    with pytest.raises(ValueError, match="invalid"):
        ps.resolve("mlir", algo)


def test_explicit_pallas_off_tpu_warns_once_then_falls_back():
    """An explicit 'pallas' request off the TPU no longer falls back: it
    raises, every time, naming the platform it found."""
    if jax.default_backend() == "tpu":
        pytest.skip("the request is honored on a TPU")
    algo = _algo()
    st = _mixed_stack(algo)
    chunks = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 5))
    counts = jnp.full((4,), 8, jnp.int32)
    for _ in range(2):
        with pytest.raises(RuntimeError, match=jax.default_backend()):
            pod_step(algo, st, chunks, counts, backend="pallas")


def test_unfusable_algorithm_falls_back_with_warning():
    """Stacked sieves have no fused kernel: explicit fused requests warn
    once and run the (trivially bit-equal) vmapped reference."""
    algo = api.make(SessionSpec(algo="sievestreaming", K=6, d=5,
                                eps=0.2, lengthscale=1.5, backend="jnp"))
    assert not ps.fusable(algo)
    ps._reset_warnings()
    states = [algo.init(algo.hyper(K=k)) for k in (4, 6)]
    st = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)
    chunks = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 5))
    counts = jnp.full((2,), 8, jnp.int32)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out = pod_step(algo, st, chunks, counts,
                       backend="pallas-interpret")
    assert any("no fused pod-step kernel" in str(x.message) for x in w)
    _assert_tree_equal(pod_step_ref(algo, st, chunks, counts), out,
                       msg="unfusable fallback")


def test_env_var_selects_default(monkeypatch):
    monkeypatch.setenv(ps._ENV_VAR, "pallas-interpret")
    assert ps.default_backend() == "pallas-interpret"
    assert ps.resolve(None, _algo()) == "pallas-interpret"
    monkeypatch.setenv(ps._ENV_VAR, "nope")
    with pytest.raises(ValueError, match="REPRO_PODSTEP_BACKEND"):
        ps.default_backend()


# ------------------------------------------------------------------- pod


def test_pod_fused_backend_bit_equal_mixed_kernels():
    """End-to-end through SummarizerPod: per-slot lengthscale/kind plans;
    each fused pod step is bit-identical to per-session run_batched, and
    fused vs unfused pods make the same decisions across rounds."""
    algo = api.make(SessionSpec(algo="threesieves", K=8, T=10, eps=0.2,
                                d=5, lengthscale=1.5, backend="jnp"))
    pod = SummarizerPod(algo=algo, sessions=4, chunk=16,
                        podstep_backend="jnp")
    podf = dataclasses.replace(pod, podstep_backend="pallas-interpret")
    specs = [
        SessionSpec(algo="threesieves", K=6, T=10, eps=0.2,
                    lengthscale=1.5),
        SessionSpec(algo="threesieves", K=4, T=3, eps=0.5,
                    lengthscale=0.7),
        SessionSpec(algo="threesieves", K=8, T=20, eps=0.1,
                    lengthscale=2.0, kernel_kind="linear_norm"),
    ]
    st = pod.init()
    for i, sp in enumerate(specs):
        st, _, ok = pod.admit(st, jnp.int32(i), spec=sp)
        assert bool(ok)
    stf = st
    for rnd in range(3):
        sids = jax.random.randint(jax.random.PRNGKey(10 + rnd),
                                  (24,), 0, 3)
        X = jax.random.normal(jax.random.PRNGKey(20 + rnd), (24, 5))
        chunks, counts, _, _ = podf.route(stf, sids, X)
        solo = _per_session_ref(algo, stf.algo, chunks, counts)
        st, _ = pod.ingest(st, sids, X)
        stf, _ = podf.ingest(stf, sids, X)
        _assert_tree_equal(solo, stf.algo, msg=f"pod round {rnd}")
        _assert_same_decisions(st, stf, msg=f"pod round {rnd}")
    ro = pod.readout(st)
    np.testing.assert_array_equal(np.asarray(ro.specs.kernel_kind)[:3],
                                  [0, 0, 1])
    assert int(jnp.sum(ro.n)) > 0


def test_kernel_rows_roundtrip_checkpoint(tmp_path):
    """Per-slot lengthscale/kind rows survive admit -> save -> restore."""
    from repro.ckpt import CheckpointStore

    algo = api.make(SessionSpec(algo="threesieves", K=8, d=5, eps=0.2,
                                lengthscale=1.5, backend="jnp"))
    pod = SummarizerPod(algo=algo, sessions=3, chunk=8)
    st = pod.init()
    st, _, ok = pod.admit(
        st, jnp.int32(0),
        spec=SessionSpec(algo="threesieves", K=4, lengthscale=0.7))
    assert bool(ok)
    st, _, ok = pod.admit(
        st, jnp.int32(1),
        spec=SessionSpec(algo="threesieves", K=6, lengthscale=2.0,
                         kernel_kind="linear_norm"))
    assert bool(ok)
    store = CheckpointStore(tmp_path)
    pod.save(store, 1, st)
    st2, _ = pod.restore(store, 1)
    _assert_tree_equal(st, st2, msg="ckpt roundtrip")
    hp = pod.readout(st2).specs
    np.testing.assert_allclose(np.asarray(hp.lengthscale)[:2], [0.7, 2.0])
    np.testing.assert_array_equal(np.asarray(hp.kernel_kind)[:2], [0, 1])


def test_admit_mixed_kernel_plans_no_recompile():
    """Admitting tenants whose plans differ only in hyperparameters —
    including lengthscale and kernel kind — must reuse one trace."""
    algo = api.make(SessionSpec(algo="threesieves", K=8, d=5, eps=0.2,
                                lengthscale=1.5, backend="jnp"))
    pod = SummarizerPod(algo=algo, sessions=4, chunk=8)
    traces = 0

    def admit(st, sid, hp):
        nonlocal traces
        traces += 1
        return pod.admit(st, sid, spec=hp)

    jadmit = jax.jit(admit)
    st = pod.init()
    plans = [
        algo.hyper(K=3, lengthscale=1.5),
        algo.hyper(K=8, lengthscale=0.25),
        algo.hyper(K=5, lengthscale=2.0, kernel_kind="linear_norm"),
    ]
    for sid, hp in enumerate(plans):
        st, _, ok = jadmit(st, jnp.int32(sid), hp)
        assert bool(ok)
    assert traces == 1
    hp = pod.readout(st).specs
    np.testing.assert_allclose(np.asarray(hp.lengthscale)[:3],
                               [1.5, 0.25, 2.0])
    np.testing.assert_array_equal(np.asarray(hp.kernel_kind)[:3],
                                  [0, 0, 1])
