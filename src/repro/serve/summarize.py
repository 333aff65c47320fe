"""SummarizerPod: a multi-tenant streaming-summarization session engine.

The paper summarizes one stream on a fixed memory budget; the service
scenario is *many small tenants* — S independent summarizer sessions
(one per user/document/conversation), each tiny, none worth its own
dispatch.  The pod hosts all of them as ONE stacked, device-resident
state pytree and advances every session inside a single jitted SPMD
program:

  * state     — ``stack_states(algo.init(), S)`` plus per-slot metadata
                (session id, liveness, item/accept counters, drift
                window), every leaf with a leading (S,) session axis;
  * ingest    — a tagged queue ``(session_id, x)`` is routed to
                fixed-shape per-session chunk buffers with one scatter
                (stable-sort + searchsorted positions, no host loop),
                then ONE pod step advances all sessions at once: the
                fused Pallas pod-step kernel (one grid launch per chunk
                over the session axis, ``kernels/pod_step``) on TPU, or
                its bit-equal ``vmap(algo.run_batched)`` reference
                elsewhere — selected by ``podstep_backend`` /
                ``REPRO_PODSTEP_BACKEND`` (DESIGN.md §11);
  * lifecycle — admit writes one free slot's rows at a dynamic index;
                evict and drift-triggered reset reuse slots via masked
                row-selects (``tree_select``), so the compiled program
                never sees a shape change and nothing retraces;
  * scale-out — ``make_sharded_update`` shard_maps the same program
                over the mesh 'data' axis: P shards x S slots = P*S
                sessions per pod, still one SPMD program (the dry-run
                cells ``paper-summarizer__pod*`` lower exactly this);
  * fault tol — the whole pod state is one pytree, so
                ``ckpt.CheckpointStore`` checkpoints it mid-stream and
                restores it elastically onto any mesh shape.

Semantics: each session is bit-equal to running its algorithm standalone
via ``run_batched`` on the items routed to it (tested in
tests/test_summarizer_pod.py) — the pod is purely an execution strategy.

Per-session hyperparameters (DESIGN.md §9): sieve-family algorithms carry
(K, T, eps) — and, since the fused pod step, the kernel hyperparameters
(lengthscale, kernel kind) — as traced state (``state.hp``), so
``admit(state, sid, spec=SessionSpec(...))`` stamps a tenant's own budget
AND kernel into its slot's (S,) hyperparam rows — one compiled program,
mixed plans, no retrace.  The default (``spec=None``) is the pod's own
construction-time spec; ``readout().specs`` surfaces the live rows, and
checkpoints round-trip them like any other state leaf.

``algo`` must be a sieve-family algorithm (uniform
``init/run_batched(state, X, n_valid)/summary/insertions`` protocol,
objective bound as ``algo.f``): ThreeSieves (default and cheapest — one
summary per session), SieveStreaming(++), or Salsa.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.compat import hashable_lru
from repro.core.sieve_family import SieveAlgorithm, stack_states, tree_select
from repro.core.spec import HyperParams, SessionSpec
from repro.kernels.pod_step import pod_step

Array = jax.Array


class PodReadout(NamedTuple):
    """Periodic per-session readout of a pod (one fixed-shape pytree).

    ``drops`` surfaces the lifetime drop ledgers ``route``/``ingest``
    accumulate — per-session ``overflow`` (S,) and the pod-total
    ``unknown`` () — silently losing tenant data is the one failure mode
    a summarization service must never hide.  ``specs`` is the per-slot
    ``HyperParams`` rows ((S,) leaves: the K/T/eps each tenant bought),
    or ``None`` for algorithms without traced hyperparams.
    """

    feats: Array  # (S, K, d)
    n: Array  # (S,)
    fval: Array  # (S,)
    active: Array  # (S,) bool
    drops: Dict[str, Array]
    specs: Optional[HyperParams]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PodState:
    """Stacked state of S summarizer sessions; every leaf is (S, ...)."""

    algo: Any  # stacked algorithm state (leading session axis)
    sid: Array  # (S,) int32 — session id occupying the slot, -1 when free
    active: Array  # (S,) bool — slot hosts a live session
    items: Array  # (S,) int32 — items routed since admission
    accepts: Array  # (S,) int32 — summary insertions since admission
    win_items: Array  # (S,) int32 — items since the last drift check/reset
    win_accepts: Array  # (S,) int32 — accepts since the last check/reset
    resets: Array  # (S,) int32 — drift resets performed on the slot
    drops_overflow: Array  # (S,) int32 — items dropped past the slot's C
    drops_unknown: Array  # (S,) int32 — unknown-sid drop ledger; the count
    # lands on the shard's first slot (a scalar leaf could not shard over
    # the session axis), so ``jnp.sum`` gives the pod total

    @property
    def S(self) -> int:
        return self.sid.shape[0]


@hashable_lru(maxsize=64)
def _drift_for(pod, min_items: int, min_rate: float):
    def drift_check(state):  # named: traces show ``jit_drift_check``
        return pod.drift_check(state, min_items=min_items, min_rate=min_rate)

    return jax.jit(drift_check)


@dataclasses.dataclass(frozen=True)
class SummarizerPod:
    """S summarizer sessions as one stacked state + one jitted program.

    ``chunk`` is the per-session routing capacity of a single ingest
    call: an ingest batch may carry at most ``chunk`` items per session
    (the tail is counted as dropped — size the ingest batches so this
    never triggers, exactly like a serving queue's admission bound).

    ``podstep_backend`` selects how the pod advances per chunk
    (``kernels.pod_step.BACKENDS``): ``None`` defers to the
    ``REPRO_PODSTEP_BACKEND`` env var (default ``auto`` — the fused
    Pallas kernel on TPU for fusable algorithms, else the vmapped
    reference).  All backends are bit-equal in f32.
    """

    algo: Any
    sessions: int
    chunk: int
    podstep_backend: Optional[str] = None

    # ------------------------------------------------------------------ state
    def init(self) -> PodState:
        S = self.sessions
        zi = jnp.zeros((S,), jnp.int32)
        return PodState(
            algo=stack_states(self.algo.init(), S),
            sid=jnp.full((S,), -1, jnp.int32),
            active=jnp.zeros((S,), bool),
            items=zi, accepts=zi, win_items=zi, win_accepts=zi, resets=zi,
            drops_overflow=zi, drops_unknown=zi,
        )

    def abstract_state(self) -> PodState:
        """ShapeDtypeStruct pytree — the ``like`` donor for restore."""
        return jax.eval_shape(self.init)

    def _insertions(self, state: PodState) -> Array:
        """(S,) per-session summary insertions — monotone accept metric.

        Deliberately NOT ``summary()[1]``: for multi-rung algorithms the
        winning rung can switch to a smaller summary, and a shrinking
        counter would corrupt the lifetime accepts and fire spurious
        drift resets.
        """
        return jax.vmap(self.algo.insertions)(state.algo)

    # -------------------------------------------------------------- lifecycle
    def _hyper_of(self, spec) -> Optional[HyperParams]:
        """Resolve an admission ``spec`` to traced hyperparam scalars.

        ``None`` -> pod default; ``HyperParams`` passes through untouched
        (the jit-friendly, pre-validated form — pass these as arguments
        when jitting ``admit`` so a new tenant budget never retraces);
        ``SessionSpec`` is validated host-side against the pod's compiled
        program (algorithm, objective geometry, and shape capacities).
        """
        if spec is None:
            return None
        if isinstance(spec, HyperParams):
            return spec
        if not isinstance(spec, SessionSpec):
            raise TypeError("spec must be a SessionSpec, HyperParams or "
                            f"None, got {type(spec).__name__}")
        if not isinstance(self.algo, SieveAlgorithm):
            raise ValueError(
                "per-session specs need a sieve-family algorithm (traced "
                f"hyperparam state); this pod hosts "
                f"{type(self.algo).__name__}")
        from repro.core.api import _ALIASES, algo_name

        want = _ALIASES.get(spec.algo.lower(), spec.algo.lower())
        have = algo_name(self.algo)
        if want != have:
            raise ValueError(
                f"spec.algo={spec.algo!r} does not match this pod's "
                f"compiled program ({have}); only K/T/eps vary per slot")
        f = self.algo.f
        if spec.d is not None and int(spec.d) != f.d:
            raise ValueError(f"spec.d={spec.d} != pod objective d={f.d}")
        if float(spec.a) != f.a:
            raise ValueError(f"spec.a={spec.a} != pod a={f.a}")
        # the kernel hyperparameters are per-slot traced state (hp rows),
        # not pod-wide constants: tenants with different lengthscales or
        # kernel kinds share the compiled program
        return self.algo.hyper(K=spec.K, T=spec.T, eps=spec.eps,
                               lengthscale=spec.lengthscale,
                               kernel_kind=spec.kernel_kind)

    def admit(self, state: PodState, session_id: Array, spec=None
              ) -> Tuple[PodState, Array, Array]:
        """Admit a session into the first free slot.

        -> (state, slot, ok).  ``ok`` False when the pod is full (state
        unchanged).  Idempotent: re-admitting a live session id (a retry
        after a lost ack, a racing front-end) returns its existing slot
        untouched instead of occupying a phantom second slot that
        ``route`` would never feed and ``evict`` would free together
        with the real one.  Otherwise the slot's algorithm state is
        re-initialized, so a recycled slot starts fresh — no recompile:
        the chosen slot's rows are written at a dynamic index, O(one
        session) whatever S.

        ``spec`` selects the tenant's hyperparameters (``SessionSpec`` or
        pre-built ``HyperParams``; default = the pod's own spec): the
        slot's (S,) hyperparam rows are stamped with the tenant's
        (K, T, eps) while the compiled program stays untouched — the
        budgets are traced state, not trace constants (DESIGN.md §9).
        Re-admitting a live session with an explicit spec that DIFFERS
        from the slot's current hyperparams returns ``ok=False`` (state
        unchanged) — a mid-stream budget change cannot be a silent no-op;
        evict and re-admit to change plans.  A spec-less retry, or one
        repeating the live spec, stays the idempotent success above.
        """
        hyper = self._hyper_of(spec)
        sess = jnp.asarray(session_id, jnp.int32)
        existing = state.active & (state.sid == sess)
        present = jnp.any(existing)
        free = ~state.active
        slot = jnp.where(present, jnp.argmax(existing), jnp.argmax(free))
        if hyper is None:
            spec_ok = jnp.bool_(True)
        else:  # live slot's hp row must equal the requested one
            row = jax.tree_util.tree_map(lambda l: l[slot], state.algo.hp)
            eq = [jnp.all(a == b) for a, b in zip(
                jax.tree_util.tree_leaves(row),
                jax.tree_util.tree_leaves(hyper))]
            spec_ok = jnp.where(present, jnp.all(jnp.stack(eq)), True)
        # negative ids are reserved (-1 marks free slots and queue
        # padding); admitting one would route every padding item into it
        ok = (sess >= 0) & jnp.where(present, spec_ok, jnp.any(free))
        write = ok & ~present
        fresh = self.algo.init() if hyper is None else self.algo.init(hyper)

        def put(rows, row):  # the chosen slot's row, kept when not written
            return rows.at[slot].set(jnp.where(write, row, rows[slot]))

        z = jnp.int32(0)
        state = dataclasses.replace(
            state,
            algo=jax.tree_util.tree_map(put, state.algo, fresh),
            sid=put(state.sid, sess),
            active=put(state.active, True),
            items=put(state.items, z),
            accepts=put(state.accepts, z),
            win_items=put(state.win_items, z),
            win_accepts=put(state.win_accepts, z),
            resets=put(state.resets, z),
            # session-scoped: a recycled slot starts with a clean overflow
            # ledger; drops_unknown is pod-scoped and survives admits
            drops_overflow=put(state.drops_overflow, z),
        )
        return state, slot, ok

    def evict(self, state: PodState, session_id: Array) -> PodState:
        """Free the slot hosting ``session_id`` (no-op when absent)."""
        return self.evict_sids(
            state, jnp.asarray(session_id, jnp.int32).reshape(1))

    def evict_sids(self, state: PodState, session_ids: Array) -> PodState:
        """Free every slot hosting one of ``session_ids`` ((M,) int32;
        absentees are no-ops) in a single masked select — the
        evict-after-handoff step of a pod migration frees all victim
        slots at once, not one jitted call per victim."""
        sids = jnp.asarray(session_ids, jnp.int32).reshape(-1)
        gone = state.active & jnp.any(
            state.sid[:, None] == sids[None, :], axis=1)
        return dataclasses.replace(
            state,
            active=state.active & ~gone,
            sid=jnp.where(gone, -1, state.sid),
        )

    def routing_table(self, state: PodState) -> Dict[int, int]:
        """Host export of the live slot table: {session_id: slot}.

        The fleet front-end (``ingest.PodRouter``) and the autoscaler
        read this to know which sessions a pod hosts and where — the
        device-side truth the host routing tables are rebuilt from
        after admits, evictions and handoffs."""
        sid = np.asarray(state.sid)
        active = np.asarray(state.active)
        return {int(s): i for i, s in enumerate(sid) if active[i]}

    def reset_slots(self, state: PodState, mask: Array) -> PodState:
        """Drift reset: re-arm the masked sessions' summaries in place.

        The session keeps its slot, id, lifetime counters AND its
        hyperparams; only the algorithm state and the drift window
        restart (the paper's §3 re-selection policy, per tenant).  The
        fresh rows are re-initialized per slot from the slot's own
        ``hp`` row — a drift reset must not silently downgrade a tenant
        to the pod default budget.
        """
        mask = mask & state.active
        hp = getattr(state.algo, "hp", None)
        fresh = (stack_states(self.algo.init(), self.sessions) if hp is None
                 else jax.vmap(self.algo.init)(hp))
        z = jnp.zeros((self.sessions,), jnp.int32)
        return dataclasses.replace(
            state,
            algo=tree_select(mask, fresh, state.algo),
            win_items=jnp.where(mask, z, state.win_items),
            win_accepts=jnp.where(mask, z, state.win_accepts),
            resets=state.resets + mask.astype(jnp.int32),
        )

    def drift_check(self, state: PodState, *, min_items: int,
                    min_rate: float) -> Tuple[PodState, Array]:
        """Reset sessions whose windowed accept rate collapsed.

        A session that has routed >= ``min_items`` since its last window
        and accepted at a rate < ``min_rate`` is assumed drifted (its
        summary saturated on a stale distribution) and is re-armed.
        -> (state, reset_mask).
        """
        rate = (state.win_accepts.astype(jnp.float32)
                / jnp.maximum(state.win_items, 1).astype(jnp.float32))
        mask = state.active & (state.win_items >= min_items) \
            & (rate < min_rate)
        return self.reset_slots(state, mask), mask

    # ---------------------------------------------------------------- routing
    def route(self, state: PodState, sids: Array, X: Array
              ) -> Tuple[Array, Array, Array, Array]:
        """Scatter a tagged ingest batch to per-session chunk buffers.

        sids (N,) int32 session ids (-1 = queue padding), X (N, d)
        -> (chunks (S, C, d), counts (S,), unknown (), overflow (S,)).

        Fixed-shape throughout: each item resolves to its slot by a
        search of the slot table sorted by (id, free, slot), O((N + S)
        log(N + S)), which finds the first live slot holding the id (the
        slot the plain (N, S) match's argmax picks; items with no live
        session fall into a trash row), takes the next
        position in that slot's buffer (stable sort + searchsorted — no
        O(N^2) pairwise ranks), and one scatter writes all of them.
        The two drop causes are counted separately: ``unknown`` (no live
        session — a front-end routing error, lost tenant data) vs
        ``overflow`` (beyond a slot's C capacity — benign backpressure,
        counted per session so the noisy tenant is identifiable).
        Folding them together would hide the first behind the second.

        This is the routing ``ingest``, the synchronous reference loop
        and ``make_sharded_update`` run.  The double-buffered pipeline
        routes chunk i+1 on the host while the device runs step i, by
        copying a ``TaggedBuffer`` lease's shares straight into their
        chunks (``ingest.pipeline.fill_chunks``); the chunks equal this
        route's of ``TaggedBuffer.get``'s padded batch, bit for bit
        (tests/test_many_tenants.py pins the equivalence).
        """
        S, C = self.sessions, self.chunk
        N = sids.shape[0]
        ids, free, slots = jax.lax.sort(
            (state.sid, (~state.active).astype(jnp.int32),
             jnp.arange(S, dtype=jnp.int32)), num_keys=3)
        live = jnp.where(free == 0, slots, S)  # S = trash: a stale id
        # method="sort": one sort of the N + S keys; on a TPU v5e the
        # default scan's log S rounds of N-wide gathers cost ~3x as much
        at = jnp.minimum(jnp.searchsorted(ids, sids, method="sort"), S - 1)
        slot = jnp.where(ids[at] == sids, live[at], S)
        found = slot < S

        order = jnp.argsort(slot)  # stable: preserves stream order per slot
        sorted_slot = slot[order]
        seg_start = jnp.searchsorted(sorted_slot, sorted_slot, side="left")
        pos_sorted = (jnp.arange(N, dtype=jnp.int32)
                      - seg_start.astype(jnp.int32))
        pos = jnp.zeros((N,), jnp.int32).at[order].set(pos_sorted)

        keep = found & (pos < C)
        slot_f = jnp.where(keep, slot, S)
        pos_f = jnp.minimum(pos, C - 1)
        chunks = jnp.zeros((S + 1, C) + X.shape[1:], X.dtype)
        chunks = chunks.at[slot_f, pos_f].set(X)[:S]
        counts = jnp.bincount(slot_f, length=S).astype(jnp.int32)
        # (bincount drops the out-of-range trash index S)
        unknown = jnp.sum(~found & (sids >= 0)).astype(jnp.int32)
        over_slot = jnp.where(found & (pos >= C), slot, S)
        overflow = jnp.bincount(over_slot, length=S).astype(jnp.int32)
        return chunks, counts, unknown, overflow

    # ----------------------------------------------------------------- ingest
    def ingest(self, state: PodState, sids: Array, X: Array
               ) -> Tuple[PodState, Dict[str, Array]]:
        """Route one tagged batch and advance every session — the hot path.

        One routing scatter + one pod step over the session axis (the
        fused Pallas kernel or its vmapped ``run_batched`` reference —
        see ``podstep_backend``): a single fused program for the whole
        pod, whatever mix of sessions the batch addresses.
        """
        chunks, counts, unknown, overflow = self.route(state, sids, X)
        return self.ingest_routed(state, chunks, counts, unknown, overflow)

    def ingest_routed(self, state: PodState, chunks: Array, counts: Array,
                      unknown: Array, overflow: Array
                      ) -> Tuple[PodState, Dict[str, Array]]:
        """Advance every session from *pre-routed* chunk buffers.

        The double-buffered ingest pipeline computes the routing scatter
        on host for batch i+1 while this (jitted, state-donated) program
        runs batch i on device — so the device program is run_batched +
        counters only, no (N, S) id-match or scatter on its critical
        path.  ``ingest`` is exactly ``route`` + this.

        ``unknown`` may be () or (1,) — the sharded pre-routed program
        hands each shard its slice of a (P,) global drop vector.
        """
        n_before = self._insertions(state)
        algo2 = pod_step(self.algo, state.algo, chunks, counts,
                         backend=self.podstep_backend)
        state2 = dataclasses.replace(state, algo=algo2)
        acc = self._insertions(state2) - n_before  # (S,) this batch
        unk = jnp.sum(jnp.asarray(unknown, jnp.int32))
        state2 = dataclasses.replace(
            state2,
            items=state.items + counts,
            accepts=state.accepts + acc,
            win_items=state.win_items + counts,
            win_accepts=state.win_accepts + acc,
            drops_overflow=state.drops_overflow + overflow,
            drops_unknown=state.drops_unknown.at[0].add(unk),
        )
        return state2, {"counts": counts,
                        "dropped_unknown": unk[None],
                        "dropped_overflow": overflow}

    # ---------------------------------------------------------------- readout
    def readout(self, state: PodState) -> PodReadout:
        """Periodic per-session summaries as a ``PodReadout`` (named
        fields — the positional 5-tuple era is over): feats (S, K, d),
        n (S,), fval (S,), active (S,), the lifetime ``drops`` ledgers,
        and ``specs`` — the per-slot hyperparam rows each tenant was
        admitted with (``None`` for algorithms without traced
        hyperparams)."""
        feats, n, fval = jax.vmap(self.algo.summary)(state.algo)
        drops = {"overflow": state.drops_overflow,
                 "unknown": jnp.sum(state.drops_unknown)}
        return PodReadout(feats=feats, n=n, fval=fval, active=state.active,
                          drops=drops, specs=getattr(state.algo, "hp", None))

    def drain_metrics(self, state: PodState, *, pod: str = "0",
                      registry=None) -> None:
        """Harvest this pod's device ledgers into host metrics.

        Host-only, and ONLY at a host-sync boundary (a readout, a
        handoff edge, the end of a pipeline run) — the delegation target
        ``repro.obs.drain.drain_pod`` documents the rule.  Never jit or
        trace this (podlint PL004/PL006 enforce it statically; the pod's
        own traced methods — admit, evict, ingest — stay telemetry-free
        precisely so callers can keep jitting them).
        """
        obs.drain.drain_pod(state, pod=pod, registry=registry)

    # -------------------------------------------------------------- scale-out
    def make_sharded_update(self, mesh, axis="data", *,
                            pre_routed: bool = False):
        """The P*S-session pod program: ``ingest`` shard_mapped over
        ``axis`` (an axis name or a tuple of names — pass
        ``("pod", "data")`` on a multi-pod mesh so the session axis
        splits over BOTH, not replicated over 'pod').

        Global state/queue leaves carry a leading P*S (respectively P*N)
        axis sharded over ``axis``; each shard routes its N items to its
        own S slots (the cluster front-end routes session_id -> shard,
        e.g. ``sid % P``).  Returns a function
        ``(state, sids, X) -> (state, stats)`` to be jitted with the
        caller's shardings — one SPMD program for the whole pod.

        ``pre_routed=True`` returns the ``ingest_routed`` program
        instead — ``(state, chunks, counts, unknown, overflow) ->
        (state, stats)`` with chunks (P*S, C, d), counts/overflow (P*S,)
        and unknown (P,) (one host-routed count per shard): the device
        side of the double-buffered ingest pipeline, with the routing
        scatter gone from the SPMD program entirely.
        """
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        spec = P(axis)
        stats_spec = {"counts": spec, "dropped_unknown": spec,
                      "dropped_overflow": spec}
        if pre_routed:
            return shard_map(
                self.ingest_routed, mesh=mesh,
                in_specs=(spec, spec, spec, spec, spec),
                out_specs=(spec, stats_spec),
                check_vma=False)
        return shard_map(
            self.ingest, mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=(spec, stats_spec),
            check_vma=False)

    # ------------------------------------------------------------------ serve
    def serve(self, state: PodState, pipeline, *, max_batches=None,
              drift_every: int = 0, min_items: int = 0,
              min_rate: float = 0.0):
        """Drive the pod from an ``ingest.IngestPipeline`` — the
        streaming front-end loop.

        The pipeline owns the hot loop (double-buffered host routing +
        donated device steps); this wrapper interleaves the pod-level
        control plane: every ``drift_every`` device batches it pauses
        the pipeline at a safe point and runs ``drift_check`` (resets do
        not move slots, so the pipeline's host slot table stays valid).
        Returns ``(state, stats)`` with the pipeline's throughput/drop
        stats; with a pub/sub front-end attached to the pipeline
        (``PubSubFrontEnd.attach``), stats also carries
        ``pubsub_committed`` — the partition -> offset map committed at
        the last sync boundary, i.e. exactly where a restarted serve
        loop resumes (``PubSubFrontEnd(start=...)``).
        """
        if drift_every and drift_every > 0:
            # serve() is resumable — don't retrace drift per call
            drift = _drift_for(self, min_items, min_rate)
            total = {}
            remaining = max_batches
            while True:
                n = (drift_every if remaining is None
                     else min(drift_every, remaining))
                state, stats = pipeline.run(state, max_batches=n)
                for k, v in stats.items():
                    if isinstance(v, dict):
                        # non-additive stats (e.g. pubsub_committed —
                        # the offset map from the pipeline's on_sync
                        # commit): latest wins, offsets are monotone
                        total[k] = v
                    else:
                        total[k] = total.get(k, 0) + v
                # host-side control plane between pipeline runs — safe to
                # span here (the drift program itself stays untouched)
                with obs.span("drift_check", pod=str(pipeline.pod_id),
                              every=drift_every):
                    state, _ = drift(state)
                if remaining is not None:
                    remaining -= stats["batches"]
                    if remaining <= 0:
                        return state, total
                if stats["batches"] < n or pipeline.exhausted:
                    return state, total
        return pipeline.run(state, max_batches=max_batches)

    # ------------------------------------------------------------- checkpoint
    def save(self, store, step: int, state: PodState,
             extra: Optional[Dict] = None):
        """Checkpoint the whole pod (host-gathered, mesh-agnostic)."""
        return store.save(step, state, extra or {})

    def restore(self, store, step: Optional[int] = None, shardings=None,
                *, slots=None, into: Optional[PodState] = None,
                saved_sessions: Optional[int] = None
                ) -> Tuple[PodState, Dict]:
        """Restore a pod mid-stream; ``shardings`` (a PodState of
        NamedShardings) reshards onto the *current* mesh — the saved
        mesh shape is irrelevant (elastic restart).

        ``slots`` selects a *subset* of the saved session rows — a bool
        mask or an index array over the saved pod's slots — and places
        them into the free slots of the live pod state ``into`` (the
        session-migration half of pod autoscaling: drain on pod A,
        restore rows into pod B without touching B's resident tenants).
        ``saved_sessions`` sizes the saved pod when it differs from this
        pod's ``sessions`` (migrating between pods of different width).
        Inactive saved rows among the selection are skipped; a selected
        session id already live in ``into`` is a conflict (the session
        would be hosted twice) and raises.  ``into``'s pod-scoped
        ``drops_unknown`` ledger is kept as-is — it is not session
        state.  Per-slot hyperparams migrate with their rows (they are
        ordinary ``state.algo.hp`` leaves), so a K=10 tenant restored
        into a K_max=100 pod keeps its K=10 budget.
        """
        if step is None:
            step = store.latest_step()
        if step is None:
            raise FileNotFoundError(f"no committed checkpoint in {store.root}")
        if slots is None:
            return store.load(step, self.abstract_state(), shardings=shardings)

        if into is None:
            raise ValueError("slot-subset restore needs the live pod state: "
                             "restore(..., slots=..., into=state)")
        donor = (self if saved_sessions is None
                 else dataclasses.replace(self, sessions=saved_sessions))
        saved, extra = store.load(step, donor.abstract_state())
        S_saved = donor.sessions
        slots = np.asarray(slots)
        sel = (np.flatnonzero(slots) if slots.dtype == bool
               else slots.astype(np.int64).ravel())
        if sel.size and (sel.min() < 0 or sel.max() >= S_saved):
            raise IndexError(f"slot index out of range for saved pod of "
                             f"{S_saved} sessions: {sel}")
        # dedupe (first occurrence wins): a repeated index would place the
        # same session into two slots — the double-hosted state admit()'s
        # idempotency guard exists to prevent
        sel = sel[np.sort(np.unique(sel, return_index=True)[1])]
        saved_active = np.asarray(saved.active)
        sel = sel[saved_active[sel]]  # skip dead saved rows
        live_sids = np.asarray(into.sid)[np.asarray(into.active)]
        moving = np.asarray(saved.sid)[sel]
        clash = np.intersect1d(moving, live_sids)
        if clash.size:
            raise ValueError(f"session ids {clash.tolist()} are already live "
                             "in the target pod")
        free = np.flatnonzero(~np.asarray(into.active))
        if sel.size > free.size:
            raise ValueError(f"target pod has {free.size} free slots for "
                             f"{sel.size} restored sessions")
        dst = free[: sel.size]

        def place(saved_leaf, live_leaf, sh=None):
            out = np.array(live_leaf)
            out[dst] = np.asarray(saved_leaf)[sel]
            return jnp.asarray(out) if sh is None else jax.device_put(out, sh)

        if shardings is None:
            merged = jax.tree_util.tree_map(place, saved, into)
        else:  # honor the live pod's target shardings leaf-for-leaf
            merged = jax.tree_util.tree_map(place, saved, into, shardings)
        merged = dataclasses.replace(merged, drops_unknown=into.drops_unknown)
        return merged, extra
