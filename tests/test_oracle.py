"""GainOracle backend abstraction: jnp vs pallas-interpret parity across
shapes (aligned and ragged), backend resolution, and the LogDet routing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GainOracle, KernelConfig, LogDet, make_objective
from repro.core.oracle import default_backend, resolve_backend


def _filled_state(f, n_fill, seed=0):
    rng = np.random.RandomState(seed)
    st = f.init()
    for x in rng.randn(n_fill, f.d).astype(np.float32):
        st = f.append(st, jnp.asarray(x))
    return st


# ----------------------------------------------------------- backend parity
@pytest.mark.parametrize("kind", ["rbf", "linear_norm"])
@pytest.mark.parametrize("B,K,d", [
    (32, 8, 4),       # tiny, nothing aligned
    (256, 16, 32),    # aligned B
    (300, 100, 300),  # ragged everywhere
    (128, 128, 128),  # fully aligned
    (1, 5, 7),        # single candidate
    (5, 3, 2),        # short tail — exercises the small-block padding path
])
def test_jnp_vs_pallas_interpret(kind, B, K, d):
    rng = np.random.RandomState(B + K + d)
    f = LogDet(K=K, d=d, kernel=KernelConfig(kind, 0.9), a=1.3)
    st = _filled_state(f, min(K, 6), seed=B)
    X = jnp.asarray(rng.randn(B, d).astype(np.float32))

    o_jnp = GainOracle(kernel=f.kernel, a=f.a, backend="jnp")
    o_int = GainOracle(kernel=f.kernel, a=f.a, backend="pallas-interpret")
    got = o_int.gains(st.feats, st.Linv, st.n, X)
    want = o_jnp.gains(st.feats, st.Linv, st.n, X)
    assert got.shape == (B,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-6)


@pytest.mark.parametrize("kind", ["rbf", "linear_norm"])
def test_small_block_b_honored(kind):
    """Requesting block_b < 128 must not pad short tails to 128 (and must
    still be numerically correct)."""
    f = LogDet(K=9, d=5, kernel=KernelConfig(kind, 1.1), a=0.8)
    st = _filled_state(f, 4)
    X = jnp.asarray(np.random.RandomState(0).randn(11, 5).astype(np.float32))
    o_big = GainOracle(kernel=f.kernel, a=f.a, backend="pallas-interpret")
    o_small = GainOracle(kernel=f.kernel, a=f.a, backend="pallas-interpret",
                         block_b=16)
    want = GainOracle(kernel=f.kernel, a=f.a, backend="jnp").gains(
        st.feats, st.Linv, st.n, X)
    for o in (o_big, o_small):
        got = o.gains(st.feats, st.Linv, st.n, X)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-5, atol=3e-6)


def test_gain1_matches_gains():
    f = make_objective(8, 6)
    st = _filled_state(f, 5)
    x = jnp.asarray(np.random.RandomState(1).randn(6).astype(np.float32))
    o = f.oracle
    np.testing.assert_allclose(
        float(o.gain1(st.feats, st.Linv, st.n, x)),
        float(o.gains(st.feats, st.Linv, st.n, x[None, :])[0]))


# ------------------------------------------------------- backend resolution
def test_resolution_rules():
    on_tpu = jax.default_backend() == "tpu"
    assert resolve_backend("jnp") == "jnp"
    assert resolve_backend("pallas-interpret") == "pallas-interpret"
    assert resolve_backend("auto") == ("pallas" if on_tpu else "jnp")
    # an explicit pallas request off the TPU raises, naming the platform
    if on_tpu:
        assert resolve_backend("pallas") == "pallas"
    else:
        with pytest.raises(RuntimeError, match=jax.default_backend()):
            resolve_backend("pallas")
    with pytest.raises(ValueError):
        resolve_backend("cuda")


def test_env_var_default(monkeypatch):
    monkeypatch.delenv("REPRO_ORACLE_BACKEND", raising=False)
    assert default_backend() == "auto"
    monkeypatch.setenv("REPRO_ORACLE_BACKEND", "jnp")
    assert default_backend() == "jnp"
    assert make_objective(4, 2).oracle.backend == "jnp"
    monkeypatch.setenv("REPRO_ORACLE_BACKEND", "nope")
    with pytest.raises(ValueError):
        default_backend()


# ------------------------------------------------------------ LogDet routing
def test_logdet_gains_dispatch_through_oracle():
    """LogDet.gains must route through GainOracle — identical results on the
    explicit backend and on the default, for both paper kernels."""
    for kind in ("rbf", "linear_norm"):
        f = LogDet(K=10, d=8, kernel=KernelConfig(kind, 0.7), a=2.0)
        assert isinstance(f.oracle, GainOracle)
        st = _filled_state(f, 7)
        X = jnp.asarray(
            np.random.RandomState(2).randn(33, 8).astype(np.float32))
        via_logdet = f.gains(st, X)
        via_oracle = f.oracle.gains(st.feats, st.Linv, st.n, X)
        np.testing.assert_array_equal(np.asarray(via_logdet),
                                      np.asarray(via_oracle))

        f_int = LogDet(K=10, d=8, kernel=KernelConfig(kind, 0.7), a=2.0,
                       backend="pallas-interpret")
        np.testing.assert_allclose(np.asarray(f_int.gains(st, X)),
                                   np.asarray(via_logdet),
                                   rtol=3e-5, atol=3e-6)


def test_threesieves_under_interpret_backend():
    """A whole algorithm runs end-to-end on the Pallas-interpret oracle and
    selects the same summary as the jnp backend."""
    from repro.core import make

    rng = np.random.RandomState(3)
    X = jnp.asarray(rng.randn(40, 4).astype(np.float32) * 2.0)
    a = make("threesieves", K=4, d=4, T=10, eps=0.2)
    b = make("threesieves", K=4, d=4, T=10, eps=0.2,
             backend="pallas-interpret")
    sa = a.run(a.init(), X)
    sb = b.run(b.init(), X)
    assert int(sa.ld.n) == int(sb.ld.n)
    np.testing.assert_allclose(np.asarray(sa.ld.feats),
                               np.asarray(sb.ld.feats), atol=1e-6)


# ------------------------------------------------- epsilon centralization
def test_saturated_gains_bit_equal_across_backends():
    """Every gain path clamps ``dd2 = (1+a) - |c|^2`` at the same
    ``GAIN_EPS`` (the jnp oracle, ``LogDet.append`` and the Pallas kernel
    used to carry their own epsilon literals).  In exact arithmetic
    monotonicity keeps dd2 >= 1, so the clamp is precisely the guard
    against fp saturation — where backends disagreeing on the epsilon
    would price the same item differently and flip accept decisions.
    Drive the clamp through the oracle's function contract (a synthetic
    ill-conditioned Linv) and assert bit-equal gains and accepts."""
    from repro.constants import GAIN_EPS

    rng = np.random.RandomState(4)
    K, d, a = 4, 5, 1.0
    for kind in ("rbf", "linear_norm"):
        kernel = KernelConfig(kind, 1.3)
        feats = jnp.asarray(np.tile(2.0 * rng.randn(1, d), (K, 1))
                            .astype(np.float32))
        linv = jnp.asarray(50.0 * np.eye(K, dtype=np.float32))
        n = jnp.int32(K)
        # row 0 duplicates the summary (|c|^2 >> 1+a -> clamp engages);
        # row 1 is antipodal: k = 0 for both kernels (exp(-large) ~ 0 for
        # rbf, cos = -1 for linear_norm) -> regular, un-clamped gain
        X = jnp.concatenate([feats[:1], -feats[:1]])

        o_jnp = GainOracle(kernel=kernel, a=a, backend="jnp")
        o_int = GainOracle(kernel=kernel, a=a, backend="pallas-interpret")
        g_jnp = np.asarray(o_jnp.gains(feats, linv, n, X))
        g_int = np.asarray(o_int.gains(feats, linv, n, X))
        clamped = np.float32(0.5 * np.log(np.float32(GAIN_EPS)))
        assert g_jnp[0] == clamped, kind
        assert g_jnp[1] > clamped, kind
        np.testing.assert_array_equal(g_jnp, g_int, err_msg=kind)
        # accept decisions against any threshold are therefore bit-equal
        thr = np.linspace(-15.0, 1.0, 9, dtype=np.float32)[:, None]
        np.testing.assert_array_equal(g_jnp[None, :] >= thr,
                                      g_int[None, :] >= thr,
                                      err_msg=kind)


def test_append_gain_uses_same_clamp():
    """``LogDet.append`` prices its accepted item with the identical
    clamp the batched oracle uses (one constant, one decision)."""
    f = LogDet(K=5, d=3, kernel=KernelConfig("rbf", 1.2), a=1.0)
    st = _filled_state(f, 4, seed=2)
    x = jnp.asarray(np.random.RandomState(5).randn(3).astype(np.float32))
    batched = float(f.gains(st, x[None, :])[0])
    appended = f.append(st, x)
    np.testing.assert_allclose(float(appended.fval - st.fval), batched,
                               rtol=1e-5, atol=1e-7)


def test_gain_eps_is_single_sourced():
    """The clamp constant has exactly one definition site."""
    from repro import constants
    from repro.kernels.rbf_gain import kernel as kmod, ref as rmod

    import inspect

    assert constants.GAIN_EPS == 1e-12
    for mod in (kmod, rmod):
        src = inspect.getsource(mod)
        assert "GAIN_EPS" in src and "1e-12" not in src.replace(
            "NORM_EPS", "")
