"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached.

Interpret mode runs the kernels' logic anywhere but checks none of what
the chip's compiler (Mosaic) enforces: block tiling, SMEM/VMEM budgets,
which ops lower at all.  These tests hand the real compiler the served
shapes (S=256 sessions, chunk C=1024, K padded to 128, d=256) and a
pod that fills the chip (S=4096), in f32 and bf16, and check that the
kernel is in the program and the program fits the chip's 16 GiB.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every xdist worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import SessionSpec, make
from repro.core.sieve_family import stack_states
from repro.kernels.pod_step.kernel import NF, NI, pod_step_pallas
from repro.kernels.pod_step.ops import _pod_step_fused
from repro.kernels.rbf_gain import DEFAULT_BLOCK_B
from repro.kernels.rbf_gain.kernel import gain_pallas_traced

HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used <= HBM_BYTES, used


def _pod_step_args(sh, S, C, K, d, dtype):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    return (sds((S, C, d), jnp.float32), sds((S, K, d), dtype),
            sds((S, K, K), dtype), sds((S, K, K), dtype),
            sds((S, NI), jnp.int32), sds((S, NF), jnp.float32))


@pytest.mark.parametrize("S,dtype", [(256, jnp.float32), (256, jnp.bfloat16),
                                     (4096, jnp.float32)],
                         ids=["S256-f32", "S256-bf16", "S4096-f32"])
def test_pod_step_kernel_compiles(one_chip, S, dtype):
    args = _pod_step_args(one_chip, S, 1024, 128, 256, dtype)
    step = jax.jit(lambda *a: pod_step_pallas(*a, a=1.0, dtype=dtype))
    _check(step.lower(*args).compile())


def test_padded_pod_step_wrapper_compiles(one_chip):
    """The ops-layer wrapper: state tables assembled from a stacked
    ThreeSieves state at K=100, padded to the chip's tiles, launched."""
    algo = make(SessionSpec(algo="threesieves", K=100, d=256, T=500,
                            eps=0.1, lengthscale=8.0, backend="jnp"))
    S, C = 256, 1024
    state = jax.eval_shape(lambda: stack_states(algo.init(), S))
    state = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip),
        state)
    chunks = jax.ShapeDtypeStruct((S, C, 256), jnp.float32, sharding=one_chip)
    counts = jax.ShapeDtypeStruct((S,), jnp.int32, sharding=one_chip)
    lowered = _pod_step_fused.lower(algo, state, chunks, counts,
                                    use_pallas=True, interpret=False)
    _check(lowered.compile())


def _gain_args(sh, B, K, d, batch=()):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(batch + shape, dt, sharding=sh)
    return (sds((B, d), jnp.float32), sds((K, d), jnp.float32),
            sds((K, K), jnp.float32), sds((1, K), jnp.float32),
            sds((1, 1), jnp.float32), sds((1, 1), jnp.int32))


def test_gain_kernel_compiles(one_chip):
    gains = jax.jit(lambda *a: gain_pallas_traced(
        *a, a=1.0, block_b=DEFAULT_BLOCK_B))
    _check(gains.lower(*_gain_args(one_chip, 1024, 128, 256)).compile())


def test_gain_kernel_compiles_under_vmap(one_chip):
    """The unfused pod path: one gain kernel vmapped over 64 sessions."""
    gains = jax.jit(jax.vmap(lambda *a: gain_pallas_traced(
        *a, a=1.0, block_b=DEFAULT_BLOCK_B)))
    args = _gain_args(one_chip, 1024, 128, 256, batch=(64,))
    _check(gains.lower(*args).compile())


def test_many_tenant_programs_compile(one_chip):
    """The ts4096 pod (S=4096 sessions of chunk 64, K=100, d=256): the
    fused step's wrapper, the device route of a 131072-item batch (the
    sorted slot table and its binary search) and the one-slot admit,
    scanned over every session as the benchmark admits them."""
    from repro.serve import SummarizerPod

    algo = make(SessionSpec(algo="threesieves", K=100, d=256, T=2500,
                            eps=0.01, lengthscale=0.0625, backend="jnp"))
    S, C, N = 4096, 64, 131072
    pod = SummarizerPod(algo=algo, sessions=S, chunk=C)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=one_chip), tree)

    state = on_chip(pod.abstract_state())
    chunks = jax.ShapeDtypeStruct((S, C, 256), jnp.float32, sharding=one_chip)
    counts = jax.ShapeDtypeStruct((S,), jnp.int32, sharding=one_chip)
    _check(_pod_step_fused.lower(algo, state.algo, chunks, counts,
                                 use_pallas=True, interpret=False).compile())

    def fits(compiled):
        mem = compiled.memory_analysis()
        assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes
                ) <= HBM_BYTES

    sids = jax.ShapeDtypeStruct((N,), jnp.int32, sharding=one_chip)
    X = jax.ShapeDtypeStruct((N, 256), jnp.float32, sharding=one_chip)
    fits(jax.jit(pod.route).lower(state, sids, X).compile())

    def admit_all(state, sids, rows):
        def body(st, xs):
            st, slot, ok = pod.admit(st, xs[0], spec=xs[1])
            return st, (slot, ok)

        return jax.lax.scan(body, state, (sids, rows))

    hp = algo.hyper(K=100, T=2500, eps=0.01, lengthscale=0.0625)
    rows = on_chip(jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda l: jnp.broadcast_to(l, (S,) + jnp.shape(l)), hp)))
    fits(jax.jit(admit_all).lower(state, jax.ShapeDtypeStruct(
        (S,), jnp.int32, sharding=one_chip), rows).compile())
