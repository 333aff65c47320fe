import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: the dry-run needs 512 placeholder host
# devices so jax.make_mesh can build the production meshes.  Tests/benches
# never import this module (they must see 1 device).

"""Multi-pod dry-run: prove the distribution config is coherent without
hardware.

For every (architecture x input-shape) cell and mesh the entrypoint

    jax.jit(step, in_shardings=..., out_shardings=...)
        .lower(**input_specs(arch)).compile()

must succeed; we record ``memory_analysis()`` (fits HBM?),
``cost_analysis()`` (FLOPs/bytes for §Roofline) and the collective traffic
parsed from the optimized HLO (§Roofline third term) into one JSON per cell
under experiments/dryrun/.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b \
        --shape train_4k --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import all_archs, get_config
from repro.launch import sharding as shd
from repro.launch.hlo_stats import collective_stats
from repro.launch.inputs import SHAPES, cell_applicable, input_specs
from repro.launch.mesh import make_production_mesh
from repro.models import Model
from repro.serve.engine import make_decode_step, make_prefill_step
from repro.train.optim import AdamWConfig, OptState
from repro.train.step import TrainStepConfig, make_train_step

# TPU v5e constants (§Roofline)
PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # bytes/s / chip
LINK_BW = 50e9  # bytes/s / link (ICI)


def _abstract_opt_state(abs_params, opt_cfg: AdamWConfig) -> OptState:
    dt = jnp.dtype(opt_cfg.state_dtype)
    mom = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, dt), abs_params)
    return OptState(m=mom, v=jax.tree_util.tree_map(lambda x: x, mom),
                    step=jax.ShapeDtypeStruct((), jnp.int32))


def _named(mesh, pspec_tree):
    return jax.tree_util.tree_map(
        lambda p: NamedSharding(mesh, p), pspec_tree,
        is_leaf=lambda x: isinstance(x, P))


def _batch_shardings(specs, mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, shd.batch_pspec(s.shape, mesh)), specs)


def build_cell(arch: str, shape: str, mesh, *, moe_impl: str | None = None,
               remat: bool | None = None, microbatches: int = 1,
               n_layers: int | None = None, cost_faithful: bool = False,
               seq_shard: bool = False, remat_policy: str | None = None):
    """-> (jitted_fn, lower_args tuple, meta dict).

    ``cost_faithful`` lowers a flop-identical variant whose XLA cost
    analysis is honest: layers unrolled (while-loop bodies are counted once
    by XLA) and attention un-chunked (the q-chunk lax.map body likewise).
    Used by the finite-difference roofline pass; the production (scanned)
    variant is what the compile-success deliverable uses.
    """
    overrides = {}
    if moe_impl is not None:
        cfg0 = get_config(arch)
        if cfg0.moe is not None:
            overrides["moe"] = dataclasses.replace(cfg0.moe, impl=moe_impl)
    if remat is not None:
        overrides["remat"] = remat
    if n_layers is not None:
        overrides["n_layers"] = n_layers
    if cost_faithful:
        overrides["scan_layers"] = False
        overrides["attn_chunk"] = 1 << 20  # single-block attention path
    if seq_shard:
        overrides["attn_seq_shard"] = True
    if remat_policy is not None:
        overrides["remat_policy"] = remat_policy
    cfg = get_config(arch, **overrides)

    model = Model(cfg)
    spec = model.spec()
    kind0 = SHAPES[shape]["kind"]
    rules = shd.build_rules(
        cfg, mesh, mode="train" if kind0 == "train" else "serve")
    param_sh = shd.shardings(spec, rules, mesh)
    abs_params = model.abstract_params()
    kind, specs = input_specs(cfg, shape)
    n_params = cfg.param_count()
    meta = {
        "arch": arch, "shape": shape, "kind": kind,
        "params": n_params, "active_params": cfg.active_param_count(),
        "mesh": dict(mesh.shape),
    }

    if kind == "train":
        # bf16 moments above 50B params: the ZeRO memory knob (DESIGN.md)
        opt_cfg = AdamWConfig(
            state_dtype="bfloat16" if n_params > 50e9 else "float32")
        step_cfg = TrainStepConfig(num_microbatches=microbatches)
        train_step = make_train_step(model, opt_cfg, step_cfg)
        abs_opt = _abstract_opt_state(abs_params, opt_cfg)
        opt_sh = OptState(m=param_sh, v=param_sh,
                          step=NamedSharding(mesh, P()))
        batch_sh = _batch_shardings(specs, mesh)
        fn = jax.jit(
            train_step,
            in_shardings=(param_sh, opt_sh, batch_sh),
            out_shardings=(param_sh, opt_sh, None),
        )
        meta["opt_state_dtype"] = opt_cfg.state_dtype
        meta["microbatches"] = microbatches
        return fn, (abs_params, abs_opt, specs), meta

    if kind == "prefill":
        prefill = make_prefill_step(model)
        seq = SHAPES[shape]["seq"]
        from repro.launch.inputs import cache_specs

        # the stub-frontend prefix tokens occupy cache slots too
        caches = cache_specs(cfg, SHAPES[shape]["batch"],
                             seq + (cfg.n_prefix or 0))
        cache_sh = _named(mesh, shd.cache_pspecs(caches, mesh))
        batch_sh = _batch_shardings(specs, mesh)
        fn = jax.jit(
            prefill,
            in_shardings=(param_sh, batch_sh, cache_sh),
            out_shardings=(None, cache_sh, None),
        )
        return fn, (abs_params, specs, caches), meta

    # decode
    decode = make_decode_step(model)
    caches = specs["caches"]
    cache_sh = _named(mesh, shd.cache_pspecs(caches, mesh))
    tok_sh = NamedSharding(mesh, shd.batch_pspec(specs["token"].shape, mesh))
    pos_sh = NamedSharding(mesh, P())
    args = [abs_params, specs["token"], caches, specs["pos"]]
    in_sh = [param_sh, tok_sh, cache_sh, pos_sh]
    if "enc_out" in specs:
        args.append(specs["enc_out"])
        in_sh.append(NamedSharding(
            mesh, shd.batch_pspec(specs["enc_out"].shape, mesh)))
    fn = jax.jit(
        decode,
        in_shardings=tuple(in_sh),
        out_shardings=(None, None, cache_sh),
    )
    return fn, tuple(args), meta


def _mem_dict(compiled):
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def _cost_dict(compiled):
    ca = compiled.cost_analysis()
    if ca is None:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return {k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float)) and not k.startswith("utilization")}


def roofline_terms(cost: dict, coll_bytes: int, n_chips: int,
                   meta: dict, shape: str) -> dict:
    """Three-term roofline (seconds) per §Roofline.

    cost_analysis flops/bytes are per-shard (the SPMD program); so is
    coll_bytes.  Dividing per-shard work by per-chip peak gives the
    per-chip time directly.
    """
    flops = cost.get("flops", 0.0)
    bytes_accessed = cost.get("bytes accessed", 0.0)
    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_accessed / HBM_BW
    t_collective = coll_bytes / LINK_BW
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_collective}
    dominant = max(terms, key=terms.get)
    # MODEL_FLOPS: 6*N*D for train, 2*N_active*D for a forward-only step
    s = SHAPES[shape]
    tokens = s["batch"] * (s["seq"] if meta["kind"] == "train"
                           else (s["seq"] if meta["kind"] == "prefill" else 1))
    n_active = meta["active_params"]
    mult = 6 if meta["kind"] == "train" else 2
    model_flops_global = mult * n_active * tokens
    model_flops_per_chip = model_flops_global / n_chips
    return {
        **terms,
        "dominant": dominant,
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": bytes_accessed,
        "collective_bytes_per_chip": coll_bytes,
        "model_flops_global": model_flops_global,
        "model_flops_per_chip": model_flops_per_chip,
        "useful_flops_ratio": (model_flops_per_chip / flops) if flops else 0.0,
        "roofline_bound_s": max(terms.values()),
        "roofline_fraction": (
            (model_flops_per_chip / PEAK_FLOPS) / max(terms.values())
            if max(terms.values()) > 0 else 0.0),
    }


def _measure(arch, shape, mesh, n_chips, *, n_layers=None,
             cost_faithful=False, **kw):  # kw: moe_impl/remat/seq_shard
    """lower+compile one variant; -> (meta, mem, cost, coll_bytes, times)."""
    t0 = time.time()
    fn, args, meta = build_cell(arch, shape, mesh, n_layers=n_layers,
                                cost_faithful=cost_faithful, **kw)
    with jax.sharding.set_mesh(mesh):
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = _mem_dict(compiled)
        cost = _cost_dict(compiled)
        try:
            hlo = compiled.as_text()
        except Exception:
            hlo = lowered.as_text()
        coll = collective_stats(hlo)
    return meta, mem, cost, coll, (round(t_lower, 2), round(t_compile, 2))


def run_cell_fd(arch: str, shape: str, multi_pod: bool, out_dir: Path,
                *, moe_impl=None, remat=None, seq_shard=False,
                remat_policy=None, tag="fd") -> dict:
    """Finite-difference roofline: compile cost-faithful variants with 1 and
    2 layer-blocks (unrolled) and extrapolate linearly to the full depth —
    exact for per-block-homogeneous stacks, and immune to XLA's count-the-
    while-body-once cost analysis.  Memory/compile-success numbers come from
    the production (scanned) run_cell pass, not from here."""
    mesh_name = "pod512" if multi_pod else "pod256"
    cell_id = f"{arch}__{shape}__{mesh_name}__{tag}"
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    cfg_full = get_config(arch)
    bs, fkd = cfg_full.block_size, cfg_full.first_k_dense
    n_blocks = cfg_full.n_blocks
    n1, n2 = fkd + bs, fkd + 2 * bs
    kw = dict(moe_impl=moe_impl, remat=remat, seq_shard=seq_shard,
              remat_policy=remat_policy)
    try:
        meta1, _, c1, coll1, t1 = _measure(arch, shape, mesh, n_chips,
                                           n_layers=n1, cost_faithful=True,
                                           **kw)
        meta2, _, c2, coll2, t2 = _measure(arch, shape, mesh, n_chips,
                                           n_layers=n2, cost_faithful=True,
                                           **kw)

        def extrap(a, b):
            return a + (n_blocks - 1) * (b - a)

        cost = {k: extrap(c1.get(k, 0.0), c2.get(k, 0.0))
                for k in ("flops", "bytes accessed")}
        coll_bytes = int(extrap(coll1.total_bytes, coll2.total_bytes))
        coll_count = int(extrap(coll1.total_count, coll2.total_count))
        meta = dict(meta1)
        meta.update(arch=arch, params=cfg_full.param_count(),
                    active_params=cfg_full.active_param_count())
        result = {
            "cell": cell_id, "ok": True, **meta,
            "method": f"finite-difference unrolled (n1={n1}, n2={n2}, "
                      f"blocks={n_blocks})",
            "compile_s": [t1, t2],
            "cost_analysis": cost,
            "collectives": {"total_bytes": coll_bytes,
                            "total_count": coll_count,
                            "per_block_bytes": coll2.total_bytes
                            - coll1.total_bytes,
                            "kinds_at_n2": coll2.as_dict()},
            "roofline": roofline_terms(cost, coll_bytes, n_chips, meta,
                                       shape),
        }
    except Exception as e:
        result = {"cell": cell_id, "ok": False, "arch": arch, "shape": shape,
                  "mesh": mesh_name, "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-3000:]}
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{cell_id}.json").write_text(json.dumps(result, indent=1))
    status = "OK " if result["ok"] else "FAIL"
    print(f"[{status}] {cell_id}  "
          + (f"dominant={result.get('roofline', {}).get('dominant')} "
             f"roofline_frac="
             f"{result.get('roofline', {}).get('roofline_fraction', 0):.3f}"
             if result["ok"] else result["error"]))
    return result


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: Path,
             *, moe_impl=None, remat=None, microbatches=1, seq_shard=False,
             remat_policy=None, tag="") -> dict:
    mesh_name = "pod512" if multi_pod else "pod256"
    cell_id = f"{arch}__{shape}__{mesh_name}" + (f"__{tag}" if tag else "")
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    try:
        fn, args, meta = build_cell(arch, shape, mesh, moe_impl=moe_impl,
                                    remat=remat, microbatches=microbatches,
                                    seq_shard=seq_shard,
                                    remat_policy=remat_policy)
        with jax.sharding.set_mesh(mesh):
            lowered = fn.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = _mem_dict(compiled)
            cost = _cost_dict(compiled)
            try:
                hlo = compiled.as_text()
            except Exception:
                hlo = lowered.as_text()
            coll = collective_stats(hlo)
        result = {
            "cell": cell_id, "ok": True, **meta,
            "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
            "memory_analysis": mem,
            "cost_analysis": {k: cost[k] for k in
                              ("flops", "bytes accessed")
                              if k in cost},
            "collectives": coll.as_dict(),
            "roofline": roofline_terms(cost, coll.total_bytes, n_chips,
                                       meta, shape),
        }
    except Exception as e:  # a failure here is a bug in our system
        result = {"cell": cell_id, "ok": False, "arch": arch, "shape": shape,
                  "mesh": mesh_name, "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-3000:]}
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{cell_id}.json").write_text(json.dumps(result, indent=1))
    status = "OK " if result["ok"] else "FAIL"
    print(f"[{status}] {cell_id}  "
          + (f"lower={result.get('lower_s')}s compile={result.get('compile_s')}s "
             f"dominant={result.get('roofline', {}).get('dominant')}"
             if result["ok"] else result["error"]))
    return result


def run_summarizer_pod_cell(multi_pod: bool, out_dir: Path, *,
                            sessions_per_shard: int = 16, chunk: int = 1024,
                            K: int = 100, d: int = 256,
                            podstep_backend: str | None = None) -> dict:
    """The ``paper-summarizer__pod*`` cell: the SummarizerPod's real
    lowered program on the production mesh.

    One SPMD program hosts P x S summarizer sessions (P = 'data'-axis
    shards, S slots each): the shard-mapped ``ingest`` routes a global
    tagged queue to per-session chunk buffers and advances every session
    via the vmapped fused ``run_batched``.  We record compile success,
    cost/memory analysis and collective traffic for the hot path
    (ingest) and the periodic per-session ``readout``, plus the
    two-round distributed merge (``DistributedSummarizer``) that pools
    session summaries into one global summary.

    Also lowered: the *pre-routed* hot path (``ingest_routed``) — the
    device half of the double-buffered ``repro.ingest`` pipeline, where
    the routing scatter lives on host and the SPMD program is
    run_batched + counters only.  Its flops/bytes delta against the
    full ``ingest`` program is exactly what double-buffering takes off
    the device's critical path.

    Since the SessionSpec redesign the lowered state carries per-slot
    hyperparam rows (``state.algo.hp``, (P*S,) leaves), so the programs
    compiled here ARE the heterogeneous-budget programs: tenants with
    different (K, T, eps) share them without retracing.  The
    ``admit_spec`` entry lowers the spec-stamping admission itself —
    ``admit(state, sid, spec=HyperParams)`` with the hyperparams as
    *arguments* — proving a new tenant budget costs one masked
    row-select, not a compile.

    ``podstep_backend`` selects the pod's chunk-advance implementation
    (``kernels.pod_step.BACKENDS``; None = ``REPRO_PODSTEP_BACKEND`` /
    auto): on a TPU mesh the auto default lowers the fused single-launch
    pod-step kernel into the hot path; elsewhere the vmapped reference.
    The resolved choice is recorded in the cell result.
    """
    from repro.core.api import make
    from repro.data import DistributedSummarizer
    from repro.kernels.pod_step import resolve as resolve_podstep
    from repro.serve.summarize import SummarizerPod

    mesh_name = "pod512" if multi_pod else "pod256"
    cell_id = f"paper-summarizer__{mesh_name}"
    mesh = make_production_mesh(multi_pod=multi_pod)
    # sessions shard over every data-parallel axis — on the multi-pod mesh
    # that is ('pod', 'data'), doubling the tenant count, not replicating
    # the same 256 sessions per pod
    axes = ("pod", "data") if multi_pod else ("data",)
    P_shards = 1
    for ax in axes:
        P_shards *= mesh.shape[ax]
    S_tot = P_shards * sessions_per_shard
    N_tot = S_tot * chunk  # every session can fill its routing capacity

    algo = make("threesieves", K=K, d=d, T=5000, eps=1e-3)
    pod = SummarizerPod(algo=algo, sessions=sessions_per_shard, chunk=chunk,
                        podstep_backend=podstep_backend)
    pod_global = dataclasses.replace(pod, sessions=S_tot)

    state = jax.eval_shape(pod_global.init)
    sids = jax.ShapeDtypeStruct((N_tot,), jnp.int32)
    X = jax.ShapeDtypeStruct((N_tot, d), jnp.float32)
    data_sh = NamedSharding(mesh, P(axes))
    st_sh = jax.tree_util.tree_map(lambda _: data_sh, state)
    stats_sh = {"counts": data_sh, "dropped_unknown": data_sh,
                "dropped_overflow": data_sh}

    try:
        with jax.sharding.set_mesh(mesh):
            upd = jax.jit(pod.make_sharded_update(mesh, axis=axes),
                          in_shardings=(st_sh, data_sh, data_sh),
                          out_shardings=(st_sh, stats_sh))
            t0 = time.time()
            lowered = upd.lower(state, sids, X)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            cost = _cost_dict(compiled)
            coll = collective_stats(compiled.as_text())
            res_u = {
                "flops": cost.get("flops", 0.0),
                "bytes": cost.get("bytes accessed", 0.0),
                "collective_bytes": coll.total_bytes,
                "mem": _mem_dict(compiled),
                "lower_s": round(t_lower, 2),
                "compile_s": round(t_compile, 2),
            }

            # the pre-routed device program (double-buffered pipeline):
            # chunks arrive host-routed, one (P,) unknown count per shard
            upd_pre = jax.jit(
                pod.make_sharded_update(mesh, axis=axes, pre_routed=True),
                in_shardings=(st_sh, data_sh, data_sh, data_sh, data_sh),
                out_shardings=(st_sh, stats_sh))
            chunks_abs = jax.ShapeDtypeStruct((S_tot, chunk, d), jnp.float32)
            counts_abs = jax.ShapeDtypeStruct((S_tot,), jnp.int32)
            unk_abs = jax.ShapeDtypeStruct((P_shards,), jnp.int32)
            ov_abs = jax.ShapeDtypeStruct((S_tot,), jnp.int32)
            t0 = time.time()
            c_pre = upd_pre.lower(state, chunks_abs, counts_abs, unk_abs,
                                  ov_abs).compile()
            cost_pre = _cost_dict(c_pre)
            res_pre = {
                "flops": cost_pre.get("flops", 0.0),
                "bytes": cost_pre.get("bytes accessed", 0.0),
                "collective_bytes":
                    collective_stats(c_pre.as_text()).total_bytes,
                "compile_s": round(time.time() - t0, 2),
            }

            ro = jax.jit(pod_global.readout, in_shardings=(st_sh,))
            c_ro = ro.lower(state).compile()
            cost_ro = _cost_dict(c_ro)
            res_r = {"flops": cost_ro.get("flops", 0.0),
                     "bytes": cost_ro.get("bytes accessed", 0.0),
                     "collective_bytes":
                         collective_stats(c_ro.as_text()).total_bytes}

            # spec-stamping admission: hyperparams enter as () array
            # arguments, so one compile serves every tenant budget
            hp_abs = jax.eval_shape(
                lambda: pod_global.algo.hyper(K=K // 2, T=100, eps=2e-3))
            adm = jax.jit(
                lambda st, sid, hp: pod_global.admit(st, sid, spec=hp),
                in_shardings=(st_sh, None, None))
            t0 = time.time()
            c_adm = adm.lower(state, jax.ShapeDtypeStruct((), jnp.int32),
                              hp_abs).compile()
            res_adm = {
                "flops": _cost_dict(c_adm).get("flops", 0.0),
                "compile_s": round(time.time() - t0, 2),
                "hyperparam_args": sorted(
                    f.name for f in dataclasses.fields(hp_abs)),
            }

            # periodic two-round merge over pooled local summaries (the
            # DistributedSummarizer runs over the 'data' axis only)
            dist = DistributedSummarizer(algo=algo, mesh=mesh)
            dstates = jax.eval_shape(dist.init)
            d_sh = jax.tree_util.tree_map(
                lambda _: NamedSharding(mesh, P("data")), dstates)
            c_m = jax.jit(dist.merge, in_shardings=(d_sh,)).lower(
                dstates).compile()
            cost_m = _cost_dict(c_m)
            res_m = {"flops": cost_m.get("flops", 0.0),
                     "bytes": cost_m.get("bytes accessed", 0.0),
                     "collective_bytes":
                         collective_stats(c_m.as_text()).total_bytes}
        result = {
            "cell": cell_id, "ok": True,
            "K": K, "d": d, "sessions_per_shard": sessions_per_shard,
            "shards": P_shards, "total_sessions": S_tot,
            "chunk_per_session": chunk, "items_per_ingest": N_tot,
            "mesh": dict(mesh.shape),
            "heterogeneous_specs": True,  # per-slot rows incl. kernel hp
            "podstep_backend": resolve_podstep(podstep_backend, algo),
            "pod_ingest": res_u, "pod_ingest_prerouted": res_pre,
            "readout": res_r, "admit_spec": res_adm, "merge": res_m,
        }
    except Exception as e:
        result = {"cell": cell_id, "ok": False,
                  "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-3000:]}
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{cell_id}.json").write_text(json.dumps(result, indent=1))
    status = "OK " if result["ok"] else "FAIL"
    print(f"[{status}] {cell_id}  "
          + (f"{S_tot} sessions, ingest flops/shard="
             f"{result['pod_ingest']['flops']:.2e} "
             f"coll={result['pod_ingest']['collective_bytes']:.2e}"
             if result["ok"] else result["error"]))
    return result


def run_handoff_cell(multi_pod: bool, out_dir: Path, *,
                     sessions_per_shard: int = 16, chunk: int = 1024,
                     K: int = 100, d: int = 256, victims: int = 8) -> dict:
    """The ``paper-summarizer__handoff__*`` cell: the device-side
    programs of a pod->pod session migration, lowered on the production
    mesh.

    A live handoff (serve.autoscale) is mostly host work — quiesce,
    snapshot, table flip — but two programs do run on device and must
    compile against the sharded P*S-session state: the victim eviction
    (``evict_sids``, one masked row-select over the whole victim set)
    and the target pod's post-restore ingest (identical to the pod
    cell's hot path — recorded here as the program the migrated tenants
    land in).  The cell also records the migration payload: the exact
    bytes per session row the checkpoint path moves (the fixed-memory
    summary the paper promises — THE reason sessions are cheap to
    move), and the payload of a ``victims``-session handoff.
    """
    from repro.core.api import make
    from repro.serve.summarize import SummarizerPod

    mesh_name = "pod512" if multi_pod else "pod256"
    cell_id = f"paper-summarizer__handoff__{mesh_name}"
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = ("pod", "data") if multi_pod else ("data",)
    P_shards = 1
    for ax in axes:
        P_shards *= mesh.shape[ax]
    S_tot = P_shards * sessions_per_shard

    algo = make("threesieves", K=K, d=d, T=5000, eps=1e-3)
    pod = SummarizerPod(algo=algo, sessions=sessions_per_shard, chunk=chunk)
    pod_global = dataclasses.replace(pod, sessions=S_tot)

    state = jax.eval_shape(pod_global.init)
    data_sh = NamedSharding(mesh, P(axes))
    st_sh = jax.tree_util.tree_map(lambda _: data_sh, state)

    # per-session migration payload from the abstract state: every leaf
    # contributes its per-slot row (shape[1:]) at its dtype
    row_bytes = sum(
        int(np.prod(leaf.shape[1:])) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(state))

    try:
        with jax.sharding.set_mesh(mesh):
            ev = jax.jit(pod_global.evict_sids,
                         in_shardings=(st_sh, None), out_shardings=st_sh)
            vict_abs = jax.ShapeDtypeStruct((victims,), jnp.int32)
            t0 = time.time()
            c_ev = ev.lower(state, vict_abs).compile()
            t_ev = time.time() - t0
            cost_ev = _cost_dict(c_ev)
            res_ev = {
                "flops": cost_ev.get("flops", 0.0),
                "bytes": cost_ev.get("bytes accessed", 0.0),
                "collective_bytes":
                    collective_stats(c_ev.as_text()).total_bytes,
                "compile_s": round(t_ev, 2),
            }
            # the program the migrated tenants land in: the target pod's
            # pre-routed ingest (the double-buffered pipeline's device
            # half), same shapes as the pod cell's hot path
            upd_pre = jax.jit(
                pod.make_sharded_update(mesh, axis=axes, pre_routed=True),
                in_shardings=(st_sh, data_sh, data_sh, data_sh, data_sh),
                out_shardings=(st_sh, {"counts": data_sh,
                                       "dropped_unknown": data_sh,
                                       "dropped_overflow": data_sh}))
            t0 = time.time()
            c_in = upd_pre.lower(
                state,
                jax.ShapeDtypeStruct((S_tot, chunk, d), jnp.float32),
                jax.ShapeDtypeStruct((S_tot,), jnp.int32),
                jax.ShapeDtypeStruct((P_shards,), jnp.int32),
                jax.ShapeDtypeStruct((S_tot,), jnp.int32)).compile()
            cost_in = _cost_dict(c_in)
            res_in = {
                "flops": cost_in.get("flops", 0.0),
                "bytes": cost_in.get("bytes accessed", 0.0),
                "compile_s": round(time.time() - t0, 2),
            }
        result = {
            "cell": cell_id, "ok": True,
            "K": K, "d": d, "sessions_per_shard": sessions_per_shard,
            "shards": P_shards, "total_sessions": S_tot,
            "victims": victims, "mesh": dict(mesh.shape),
            "session_row_bytes": row_bytes,
            "handoff_payload_bytes": row_bytes * victims,
            "evict_sids": res_ev,
            "target_ingest_prerouted": res_in,
        }
    except Exception as e:
        result = {"cell": cell_id, "ok": False,
                  "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-3000:]}
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{cell_id}.json").write_text(json.dumps(result, indent=1))
    status = "OK " if result["ok"] else "FAIL"
    print(f"[{status}] {cell_id}  "
          + (f"{result['total_sessions']} sessions, row="
             f"{result['session_row_bytes']:,} B, "
             f"{victims}-victim payload="
             f"{result['handoff_payload_bytes']:,} B, evict compile="
             f"{result['evict_sids']['compile_s']}s"
             if result["ok"] else result["error"]))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--moe-impl", default=None, choices=[None, "dense", "dispatch"])
    ap.add_argument("--remat", default=None, choices=[None, "on", "off"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--tag", default="")
    ap.add_argument("--seq-shard", action="store_true",
                    help="context-parallel attention for indivisible heads")
    ap.add_argument("--remat-policy", default=None, choices=[None, "full", "dots"])
    ap.add_argument("--cost-mode", default="production",
                    choices=["production", "fd"],
                    help="fd = finite-difference unrolled roofline pass")
    args = ap.parse_args()

    if args.arch in ("paper-summarizer", "paper-handoff"):
        # the SummarizerPod session-engine / pod-handoff cells (no model
        # arch involved)
        out_dir = Path(args.out)
        meshes = {"single": [False], "multi": [True],
                  "both": [False, True]}[args.mesh]
        cell = (run_handoff_cell if args.arch == "paper-handoff"
                else run_summarizer_pod_cell)
        n_fail = sum(0 if cell(mp, out_dir)["ok"] else 1 for mp in meshes)
        print(f"done; {n_fail} failures")
        raise SystemExit(1 if n_fail else 0)

    archs = all_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    remat = None if args.remat is None else (args.remat == "on")

    out_dir = Path(args.out)
    n_fail = 0
    for arch in archs:
        cfg = get_config(arch)
        for shape in shapes:
            ok, why = cell_applicable(cfg, shape)
            if not ok:
                print(f"[SKIP] {arch}__{shape}: {why}")
                continue
            for mp in meshes:
                if args.cost_mode == "fd":
                    r = run_cell_fd(arch, shape, mp, out_dir,
                                    moe_impl=args.moe_impl, remat=remat,
                                    seq_shard=args.seq_shard,
                                    remat_policy=args.remat_policy,
                                    tag=args.tag or "fd")
                else:
                    r = run_cell(arch, shape, mp, out_dir,
                                 moe_impl=args.moe_impl, remat=remat,
                                 microbatches=args.microbatches,
                                 seq_shard=args.seq_shard,
                                 remat_policy=args.remat_policy,
                                 tag=args.tag)
                n_fail += 0 if r["ok"] else 1
    print(f"done; {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
