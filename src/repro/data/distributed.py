"""Distributed streaming summarization: per-shard local sieves + periodic
hierarchical merge.

The paper remarks that ThreeSieves instances can run in parallel; at
production scale the stream is data-parallel (each DP shard sees 1/P of the
items), so we run one local sieve-family algorithm per shard inside
``shard_map`` and periodically merge:

    merge: all_gather the P local summaries (P*K candidate items, tiny —
    K vectors each) then re-run a sieve pass over the gathered candidates
    to select the global K.  Submodularity makes this sound: greedy-style
    re-selection over the union of per-shard summaries is the standard
    two-round (tree-reduce) protocol for distributed submodular cover
    (Mirzasoleiman et al., RandGreeDi lineage) — each local summary is a
    (1-eps)(1-1/e) summary of its shard w.h.p., and the merge pass loses at
    most another constant factor.

Any algorithm exposing the uniform sieve-family protocol
(``init/run_batched/summary`` plus the bound objective ``f``) plugs in:
ThreeSieves, SieveStreaming(++), Salsa, or the baselines — the local phase
calls ``run_batched`` and the merge consumes ``vmap(summary)``.

Communication cost: P*K*d floats per merge — for P=32 shards, K=100, d=256
that is 3.2 MB, once every ``merge_every`` chunks.  Compare against
centralizing the raw stream: chunk*P*d floats *per chunk*.

All-device execution: the local phase is embarrassingly parallel (vmap'd
state under shard_map over the 'data' axis of the mesh) and jits to one
SPMD program; the merge is one all_gather + a scan — no host round trips.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from repro.core.functions import LogDetState

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MergedSummary:
    """Result of a global merge: one LogDet summary over the pooled pools."""

    ld: LogDetState


@dataclasses.dataclass(frozen=True)
class DistributedSummarizer:
    """P parallel sieve instances over the 'data' axis of ``mesh`` + merge.

    ``algo`` is any sieve-family algorithm from ``repro.core.api.make``
    (uniform ``init/run_batched/summary`` protocol, objective bound as
    ``algo.f``).
    """

    algo: Any
    mesh: Mesh
    axis: str = "data"

    @property
    def n_shards(self) -> int:
        return self.mesh.shape[self.axis]

    # ----------------------------------------------------------------- local
    def init(self):
        """Stacked per-shard states, sharded over the data axis."""
        P_ = self.n_shards
        one = self.algo.init()
        stacked = jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l, (P_,) + l.shape), one)
        spec = P(self.axis)
        return jax.device_put(
            stacked, NamedSharding(self.mesh, spec))

    def update(self, states, X: Array):
        """X (P*B, d) global batch, sharded over 'data'.  Each shard's local
        sieve consumes its (B, d) slice — one SPMD program, no host sync."""

        def local(st, x):
            st = jax.tree_util.tree_map(lambda l: l[0], st)
            out = self.algo.run_batched(st, x)
            return jax.tree_util.tree_map(lambda l: l[None], out)

        fn = shard_map(
            local, mesh=self.mesh,
            in_specs=(P(self.axis), P(self.axis)),
            out_specs=P(self.axis), check_vma=False)
        return fn(states, X)

    # ----------------------------------------------------------------- merge
    def merge(self, states) -> MergedSummary:
        """Gather all local summaries and re-sieve into one global summary.

        Returns a replicated ``MergedSummary`` holding the merged selection.
        Uses a *greedy threshold-free* pass over the pooled candidates:
        each round accepts the highest positive marginal gain — equivalent
        to one ThreeSieves pass with T=inf over a finite pool.  The local
        summaries are read through the uniform ``summary`` protocol
        (vmapped over the shard axis), so any sieve-family algorithm's
        states merge the same way.
        """
        f = self.algo.f
        K = f.K
        feats_s, n_s, _ = jax.vmap(self.algo.summary)(states)  # (P,K,d),(P,)
        feats_all = feats_s.reshape(-1, f.d)  # (P*K, d)
        live = (jnp.arange(K)[None, :] < n_s[:, None]).reshape(-1)

        def round_(carry, _):
            ld, used = carry
            gains = f.gains(ld, feats_all)  # one fused (K,K)x(K,PK) pass
            gains = jnp.where(live & ~used, gains, -jnp.inf)
            i = jnp.argmax(gains)
            take = (gains[i] > 0) & (ld.n < K)
            ld = f.maybe_append(ld, feats_all[i], take)
            used = used.at[i].set(True)
            return (ld, used), None

        (ld, _), _ = jax.lax.scan(
            round_, (f.init(), jnp.zeros((feats_all.shape[0],), bool)),
            None, length=K)
        return MergedSummary(ld=ld)

    def global_summary(self, states) -> Tuple[Array, Array, Array]:
        merged = self.merge(states)
        return merged.ld.feats, merged.ld.n, merged.ld.fval
