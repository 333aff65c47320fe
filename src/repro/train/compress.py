"""Int8 error-feedback gradient compression for the cross-pod reduction.

At multi-pod scale the 'pod' axis crosses DCN (slow links); gradients are the
only traffic on it.  We compress per-leaf to int8 with a per-leaf fp32 scale
before the cross-pod psum and keep the quantization residual locally
(error feedback, Seide et al. / 1-bit Adam lineage) so the bias cancels over
steps: e_{t+1} = g_t + e_t - Q^{-1}(Q(g_t + e_t)).

Inside a jitted step this is expressed with ``shard_map`` over the 'pod'
axis: intra-pod reduction stays fp32 (fast ICI psum over 'data'/'model'
derived by GSPMD as usual); only the pod-axis reduction runs on the
quantized representation.  4x less DCN traffic than fp32, 2x less than bf16.

The compressor is a no-op (identity) when the mesh has no 'pod' axis, so the
same train_step works single-pod.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

Array = jax.Array


def _quantize(x: Array) -> Tuple[Array, Array]:
    """fp -> (int8, scale).  Symmetric per-tensor scaling."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q: Array, scale: Array) -> Array:
    return q.astype(jnp.float32) * scale


@dataclasses.dataclass(frozen=True)
class Compressor:
    """Int8 error-feedback psum over ``axis`` ('pod')."""

    mesh: Mesh
    axis: str = "pod"

    @property
    def active(self) -> bool:
        return self.axis in self.mesh.axis_names

    def init_ef(self, grads_like) -> Any:
        """Zero error-feedback residuals, mirroring the grad tree."""
        return jax.tree_util.tree_map(
            lambda g: jnp.zeros(g.shape, jnp.float32), grads_like)

    # -- single-leaf kernel (runs inside shard_map, per pod shard) -----------
    def _leaf(self, g: Array, e: Array) -> Tuple[Array, Array]:
        v = g.astype(jnp.float32) + e
        q, scale = _quantize(v)
        # int8 payloads sum in int32 (max 2 pods * 127 fits easily);
        # scales travel alongside as one fp32 scalar per leaf.
        qsum = jax.lax.psum(q.astype(jnp.int32), self.axis)
        ssum = jax.lax.psum(scale, self.axis)  # == sum of per-pod scales
        npods = jax.lax.psum(jnp.ones((), jnp.float32), self.axis)
        # decode: every pod used its own scale; with per-tensor symmetric
        # quantization the unbiased decode uses the mean scale (pods see
        # near-identical grad magnitude distributions).
        mean_scale = ssum / npods
        reduced = qsum.astype(jnp.float32) * mean_scale / npods
        new_e = v - _dequantize(q, scale)  # local residual
        return reduced.astype(g.dtype), new_e

    def compress_reduce(self, grads, ef_state
                        ) -> Tuple[Any, Any, Dict[str, Array]]:
        """grads are *already* psum'd over data/model by autodiff sharding;
        this adds the pod-mean with int8 payload + error feedback."""
        if not self.active:
            return grads, ef_state, {"compress_ratio": jnp.float32(1.0)}

        flat_g, treedef = jax.tree_util.tree_flatten(grads)
        flat_e = treedef.flatten_up_to(ef_state)

        specs_in = (P(), P())  # grads replicated within pod at this point
        fn = shard_map(
            lambda g, e: self._leaf(g, e), mesh=self.mesh,
            in_specs=specs_in, out_specs=(P(), P()), check_vma=False)

        new_g, new_e = [], []
        for g, e in zip(flat_g, flat_e):
            rg, re = fn(g, e)
            new_g.append(rg)
            new_e.append(re)
        grads2 = jax.tree_util.tree_unflatten(treedef, new_g)
        ef2 = jax.tree_util.tree_unflatten(treedef, new_e)
        # int8 payload + fp32 scale vs fp32 payload
        metrics = {"compress_ratio": jnp.float32(4.0)}
        return grads2, ef2, metrics


def reference_reduce(grads_per_pod):
    """Oracle for tests: exact fp32 mean over pods (list of grad trees)."""
    n = len(grads_per_pod)
    return jax.tree_util.tree_map(
        lambda *gs: sum(g.astype(jnp.float32) for g in gs) / n,
        *grads_per_pod)
