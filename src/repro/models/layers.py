"""Parameter-spec system + basic layers (norms, RoPE, MLPs, embeddings).

Parameters live in plain nested dicts.  Every leaf is declared as a
``ParamDef(shape, axes, init)`` where ``axes`` are *logical* sharding axes
('fsdp', 'heads', 'ffn', 'vocab', ...) resolved to mesh axes by
``repro.launch.sharding.build_rules`` — the flax-partitioning pattern without
the flax dependency.  The spec tree supports:

  * ``init_tree``      — materialize real parameters (smoke tests, training)
  * ``abstract_tree``  — ShapeDtypeStructs (dry-run: no allocation)
  * ``spec_tree_pspecs`` — PartitionSpecs from the logical axes
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "lecun"  # "lecun" | "normal:<std>" | "zeros" | "ones"
    dtype: str = "float32"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def _leaf_init(d: ParamDef, key) -> Array:
    dt = jnp.dtype(d.dtype)
    if d.init == "zeros":
        return jnp.zeros(d.shape, dt)
    if d.init == "ones":
        return jnp.ones(d.shape, dt)
    if d.init.startswith("normal:"):
        std = float(d.init.split(":")[1])
        return std * jax.random.normal(key, d.shape, dt)
    if d.init == "lecun":
        import math

        fan_in = d.shape[0] if len(d.shape) == 1 else math.prod(d.shape[:-1])
        std = max(fan_in, 1) ** -0.5
        return std * jax.random.normal(key, d.shape, dt)
    raise ValueError(d.init)


def init_tree(spec: Dict[str, Any], key) -> Dict[str, Any]:
    leaves, treedef = jax.tree_util.tree_flatten(spec, is_leaf=is_def)
    keys = jax.random.split(key, len(leaves))
    vals = [_leaf_init(l, k) for l, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def abstract_tree(spec: Dict[str, Any]) -> Dict[str, Any]:
    return jax.tree_util.tree_map(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype)),
        spec, is_leaf=is_def)


def spec_tree_pspecs(spec: Dict[str, Any], rules: Dict[Optional[str], Any]):
    """Logical axes -> PartitionSpec tree under the given rules."""

    def one(d: ParamDef) -> P:
        return P(*[rules.get(a, None) for a in d.axes])

    return jax.tree_util.tree_map(one, spec, is_leaf=is_def)


def stack_spec(spec: Dict[str, Any], n: int) -> Dict[str, Any]:
    """Add a leading scanned-layers dimension to every leaf."""
    return jax.tree_util.tree_map(
        lambda d: ParamDef((n,) + d.shape, (None,) + d.axes, d.init, d.dtype),
        spec, is_leaf=is_def)


# ---------------------------------------------------------------------------
# Logical activation sharding (the MaxText practice): GSPMD propagation can
# lose the batch/tp sharding across head-count-indivisible einsums, reshape
# chains, and remat boundaries — every device then redundantly computes the
# GLOBAL op.  ``shard_act`` re-anchors activations to the mesh at layer
# boundaries.  No-op outside a mesh context (plain single-device tests).
# ---------------------------------------------------------------------------


def _ambient_mesh():
    """The mesh set by ``jax.sharding.set_mesh``, or None outside one."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def shard_act(x: Array, *axes: Optional[str]) -> Array:
    """Constrain activation ``x`` along logical axes.

    axes entries: 'batch' (-> ('pod','data') as present), 'tp' (-> 'model'
    when the dim divides), or None.  Trailing dims default to None.
    """
    m = _ambient_mesh()
    if m is None:
        return x
    names = set(m.axis_names)
    dp = tuple(a for a in ("pod", "data") if a in names)
    tp = m.shape.get("model", 1)

    import math

    dp_size = math.prod(m.shape[d] for d in dp) if dp else 1

    def resolve(a, dim):
        if a == "batch" and dp and dim % dp_size == 0:
            return dp
        if a == "tp" and "model" in names and dim % tp == 0:
            return "model"
        return None

    padded = list(axes) + [None] * (x.ndim - len(axes))
    spec = P(*[resolve(a, d) for a, d in zip(padded, x.shape)])
    return jax.lax.with_sharding_constraint(x, spec)


def param_bytes(spec: Dict[str, Any]) -> int:
    leaves = jax.tree_util.tree_leaves(spec, is_leaf=is_def)
    return sum(int(jnp.prod(jnp.asarray(d.shape))) *
               jnp.dtype(d.dtype).itemsize for d in leaves)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def norm_spec(d: int, kind: str) -> Dict[str, ParamDef]:
    s = {"scale": ParamDef((d,), (None,), "ones")}
    if kind == "layernorm":
        s["bias"] = ParamDef((d,), (None,), "zeros")
    return s


def apply_norm(p, x: Array, kind: str, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (rotate-half convention; "partial" rotates only rope_dim dims)
# ---------------------------------------------------------------------------


def rope_cos_sin(pos: Array, rope_dim: int, theta: float):
    """pos (...,) int -> cos/sin (..., rope_dim/2)."""
    half = rope_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * freqs  # (..., half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: Array, pos: Array, *, frac: float = 1.0,
               theta: float = 10_000.0) -> Array:
    """x (B, S, H, hd), pos (B, S) or (S,) -> rotated x."""
    hd = x.shape[-1]
    rope_dim = int(hd * frac)
    rope_dim -= rope_dim % 2
    if rope_dim == 0:
        return x
    cos, sin = rope_cos_sin(pos, rope_dim, theta)  # (B,S,half) or (S,half)
    if cos.ndim == 2:  # (S, half) -> broadcast batch
        cos, sin = cos[None], sin[None]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]  # (B,S,1,half)
    xr, xp = x[..., :rope_dim], x[..., rope_dim:]
    x1, x2 = jnp.split(xr.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return jnp.concatenate([out.astype(x.dtype), xp], -1)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_spec(d: int, f: int, kind: str) -> Dict[str, ParamDef]:
    if kind == "swiglu":
        return {
            "w_gate": ParamDef((d, f), ("fsdp", "ffn")),
            "w_up": ParamDef((d, f), ("fsdp", "ffn")),
            "w_down": ParamDef((f, d), ("ffn", "fsdp")),
        }
    return {
        "w_in": ParamDef((d, f), ("fsdp", "ffn")),
        "w_out": ParamDef((f, d), ("ffn", "fsdp")),
    }


def apply_mlp(p, x: Array, kind: str) -> Array:
    dt = x.dtype
    if kind == "swiglu":
        g = shard_act(x @ p["w_gate"].astype(dt), "batch", None, "tp")
        u = shard_act(x @ p["w_up"].astype(dt), "batch", None, "tp")
        return (jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u) \
            @ p["w_down"].astype(dt)
    h = shard_act(x @ p["w_in"].astype(dt), "batch", None, "tp")
    return jax.nn.gelu(h.astype(jnp.float32)).astype(dt) @ p["w_out"].astype(dt)


# ---------------------------------------------------------------------------
# Embeddings / head
# ---------------------------------------------------------------------------


def embed_spec(vocab: int, d: int) -> ParamDef:
    return ParamDef((vocab, d), ("vocab", "fsdp"), "normal:0.02")


def embed_lookup(table: Array, ids: Array, dtype) -> Array:
    return jnp.take(table, ids, axis=0).astype(dtype)
