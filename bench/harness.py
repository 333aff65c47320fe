"""One run of one cell: set-up, the measured window, the drain, the check.

The served path it drives, from the producer's side:

    Producer thread --put--> TaggedBuffer (block, capacity 2B)
      --> IngestPipeline (min_fill = B, host_route, donated device step)
      under SummarizerPod.serve(drift_every = batches per round)
      --> SummarizerPod.readout, read back to the host, every round.

An item counts as summarized when the first readout after its routing
returns.  The buffer hands items out round-robin across sessions and in
order within one, so the per-session ``state.items`` counters read at
every round's end say exactly which items each round took.

Everything the cell needs comes from its files: ``BENCHMARK.json`` names
the configuration and the traffic mix, ``bench/configs/<config>.json``
and ``bench/traffic/<mix>.json`` give their numbers,
``bench/roofline/<kind>.py`` counts the pod step's work and
``bench/metrics/<metric>.py`` reads each per-layer metric.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT / "src"))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


# ------------------------------------------------------------------ cells
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # the BENCHMARK.json metric entries this cell reports
    per_layer: list


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(root / conf["file"]),
        traffic=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if reports(m, name)])


def compile_total() -> float:
    """Fresh XLA compiles so far (the program's obs bridge counts them;
    persistent-cache hits do not count)."""
    from repro import obs

    for fam in obs.get_registry().snapshot().families:
        if fam["name"] == "xla_compile_total":
            return sum(s.get("value", 0.0) for s in fam["series"])
    return 0.0


def enable_cache() -> None:
    """The program's persistent compilation cache, for every program: a
    run after a cell's first in a checkout compiles nothing."""
    import jax

    from repro.compat import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


# ------------------------------------------------------------- the system
def honoured(cfg: dict) -> None:
    """Refuse a configuration that states what the harness cannot run:
    the pod is built in float32 and the program's float32 products run
    at ``highest`` (the reference follows ``matmul_precision``)."""
    if cfg["dtype"] != "float32" or cfg["matmul_precision"] != "highest":
        raise ValueError(
            f"the harness builds float32 pods at highest precision; the "
            f"configuration states {cfg['dtype']} at "
            f"{cfg['matmul_precision']}")


def build_pod(cfg: dict, interpret: bool):
    """The cell's pod on the platform's kernels (Pallas on the chip, the
    Pallas interpreter in a CPU test)."""
    from repro.core import SessionSpec, make
    from repro.kernels.pod_step import ops
    from repro.serve import SummarizerPod

    K, T, eps = cfg["default_plan"]
    spec = SessionSpec(algo=cfg["algorithm"], K=int(K), T=int(T),
                       eps=float(eps), d=int(cfg["d"]), a=float(cfg["a"]),
                       lengthscale=float(cfg["lengthscale"]),
                       backend="pallas-interpret" if interpret else "auto")
    algo = make(spec)
    step = ("pallas-interpret" if interpret else None) \
        if ops.fusable(algo) else None
    pod = SummarizerPod(algo=algo, sessions=int(cfg["sessions"]),
                        chunk=int(cfg["chunk"]), podstep_backend=step)
    paths = {"pod_step": ops.resolve(step, algo),
             "oracle": algo.f.oracle.resolved}
    return pod, paths


def admit_all(pod, cfg: dict):
    """Every slot s admits session s on plan s mod P, in one jitted call."""
    import jax
    import jax.numpy as jnp

    S = int(cfg["sessions"])
    plans = [tuple(p) for p in cfg["plans"]]
    hyp = [pod.algo.hyper(K=int(k), T=int(t), eps=float(e),
                          lengthscale=float(cfg["lengthscale"]))
           for k, t, e in plans]
    which = np.arange(S) % len(plans)
    rows = jax.tree_util.tree_map(
        lambda *v: np.stack([np.asarray(x) for x in v])[which], *hyp)

    @jax.jit
    def admit(sids, rows):
        def body(st, xs):
            st, slot, ok = pod.admit(st, xs[0], spec=xs[1])
            return st, (slot, ok)

        return jax.lax.scan(body, pod.init(), (sids, rows))

    state, (slots, ok) = admit(jnp.arange(S, dtype=jnp.int32), rows)
    slots, ok = np.asarray(slots), np.asarray(ok)
    if not (ok.all() and (slots == np.arange(S)).all()):
        raise RuntimeError("admission failed: a session did not land in "
                           "its slot")
    return state, [plans[i] for i in which]


# ----------------------------------------------------------------- a run
@dataclasses.dataclass
class Boundary:
    t_ret: float  # when the round's readout was back on the host
    items: np.ndarray  # (S,) items routed per session since admission
    resets: np.ndarray  # (S,) drift re-arms per session
    counters: dict  # the roofline's counters
    batches: int  # device batches in the round
    serve_s: float  # host clock around pod.serve
    readout_s: float  # host clock around readout + read back


def run(cell: Cell, seed: int, seconds: float, *, trace: bool = False,
        interpret: bool = False, t_start: float | None = None,
        control: tuple = ()) -> dict:
    """Run one cell and return its result (see ``run.py`` for the line).

    ``interpret`` runs the Pallas kernels in interpret mode (the CPU
    tests); ``control`` names lower precisions at which the reference
    also stands in for the pod (``control.py``)."""
    import jax

    from repro.ingest import IngestPipeline, TaggedBuffer

    from . import check
    from . import traffic as gen
    from .producer import Producer

    t_start = time.perf_counter() if t_start is None else t_start
    cfg, mix = cell.config, cell.traffic
    honoured(cfg)
    enable_cache()

    S, C, d = int(cfg["sessions"]), int(cfg["chunk"]), int(cfg["d"])
    B = int(S * C * float(cfg["batch_fill"]))
    R = int(mix["batches_per_round"])
    pod, paths = build_pod(cfg, interpret)
    log(f"[{cell.name}] pod_step {paths['pod_step']}, oracle "
        f"{paths['oracle']}; S {S}, C {C}, d {d}, B {B}, "
        f"{R} batch(es) per round")
    want = "pallas-interpret" if interpret else "pallas"
    if paths["oracle"] != want or paths["pod_step"] not in (want, "jnp"):
        raise RuntimeError(f"kernels resolved to {paths}, not {want}")
    roof = load_module(HERE / "roofline" / f"{cfg['roofline']}.py",
                       f"bench_roofline_{cfg['roofline']}")
    state, plan_of = admit_all(pod, cfg)
    log(f"[{cell.name}] set-up: pod built and {S} sessions admitted at "
        f"{time.perf_counter() - t_start:.3f} s")

    tags, X = gen.make_pool(seed, items=int(mix["pool_items"]), sessions=S,
                            d=d, batch=B, mix=mix["mixture"],
                            lengthscale=float(cfg["lengthscale"]),
                            tenants=mix["tenants"])
    log(f"[{cell.name}] set-up: pool of {len(tags)} items drawn at "
        f"{time.perf_counter() - t_start:.3f} s")
    buf = TaggedBuffer(capacity=int(cfg["buffer_batches"]) * B,
                       policy="block")
    pipe = IngestPipeline(pod=pod, buffer=buf, batch=B, min_fill=B,
                          pod_id=cell.name)
    prod = Producer(buf, tags, X, sessions=S, loop=mix["loop"], warm_items=B,
                    put_items=int(mix["put_items"]),
                    rate=float(mix.get("rate_items_per_s", 0.0)),
                    put_interval_s=float(mix.get("put_interval_s", 0.002)))
    readout = jax.jit(pod.readout)
    counters = jax.jit(roof.counters)
    drift = dict(drift_every=R, min_items=int(mix["drift_min_items"]),
                 min_rate=float(mix["drift_min_rate"]))
    last = {}

    def serve_round(state, batches=R):
        with jax.profiler.TraceAnnotation("bench.serve_round"):
            t = time.perf_counter()
            state, stats = pod.serve(state, pipe, max_batches=batches,
                                     **drift)
            t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.readout"):
            ro = readout(state)
            host = jax.device_get((ro, state.items, state.resets,
                                   counters(state)))
            t2 = time.perf_counter()
        if prod.error is not None:
            raise RuntimeError("the producer failed") from prod.error
        last["ro"] = host[0]
        return state, Boundary(t_ret=t2, items=host[1], resets=host[2],
                               counters=host[3], batches=stats["batches"],
                               serve_s=t1 - t, readout_s=t2 - t1)

    prod.start()
    try:
        # warm-up: one batch compiles every program a round runs (the
        # device step, the drift check, the readout)
        state, b0 = serve_round(state, 1)
        bounds = [b0]
        compiles0 = compile_total()
        trace_dir = None
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        log(f"[{cell.name}] set-up: warm-up round done at {setup_s:.3f} s "
            f"({compiles0:g} fresh compiles)")
        t_end = t0 + float(seconds)
        prod.go(t0, t_end)
        while time.perf_counter() < t_end:
            state, b = serve_round(state)
            bounds.append(b)
        n_window = len(bounds) - 1
        t_last = bounds[-1].t_ret
        if trace:
            jax.profiler.stop_trace()
        compiles_in_window = compile_total() - compiles0
        prod.stop()
        while not pipe.exhausted:  # drain what was created in the window
            state, b = serve_round(state)
            bounds.append(b)
        prod.join(timeout=60)
        if prod.is_alive():
            raise RuntimeError("the producer did not stop")
        if prod.error is not None:
            raise RuntimeError("the producer failed") from prod.error
    finally:
        prod.stop()
        buf.close()

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    final_ro = last["ro"]
    buffer_losses = buf.total_drops() + buf.total_sheds() \
        + buf.total_throttled()
    del state, pipe, pod, readout, counters, last
    gc.collect()

    # ------------------------------------------------ what the window saw
    N = prod.next  # stream items put, warm-up and drain included
    created = prod.created(N)
    stream_tags = gen.stream_tags(tags, 0, N, S)
    cum = np.stack([b.items for b in bounds])  # (rounds, S)
    t_ret = np.asarray([b.t_ret for b in bounds])
    done_at = _summarized_at(stream_tags, cum, t_ret, S)
    in_window = (created >= t0) & (created <= t_last)
    if mix["loop"] == "open":
        in_window &= np.arange(N) >= prod.warm_items
    fresh = done_at[in_window] - created[in_window]
    window_items = int((cum[n_window] - cum[0]).sum())
    window_s = t_last - t0
    late = prod.lateness()
    log(f"[{cell.name}] window {window_s:.3f} s, {n_window} rounds, "
        f"{window_items} items read back in it; items put {N}; "
        f"{int(in_window.sum())} created in the window; "
        f"compiles in the window {compiles_in_window:g}")
    if len(late):
        log(f"[{cell.name}] producer lateness: p50 "
            f"{np.percentile(late, 50) * 1e3:.3f} ms, p99 "
            f"{np.percentile(late, 99) * 1e3:.3f} ms, max "
            f"{late.max() * 1e3:.3f} ms over {len(late)} put calls")

    # -------------------------------------------------------- the check
    t_check = time.perf_counter()
    result = check.decide(cell, seed=seed, tags=tags, X=X,
                          stream_tags=stream_tags, bounds=bounds,
                          plan_of=plan_of, final_ro=final_ro,
                          buffer_losses=buffer_losses, control=control)
    sample = result["sample"]
    log(f"[{cell.name}] checked {len(sample['sessions'])} sessions "
        f"({min(sample['items'])}-{max(sample['items'])} items each since "
        f"their last re-arm); the check took "
        f"{time.perf_counter() - t_check:.3f} s")
    unsummarized = int(np.isnan(fresh).sum())

    # ---------------------------------------------------------- metrics
    e2e = {
        "items_per_s": (window_items / window_s, "items/s"),
        "setup_s": (setup_s, "s"),
    }
    if np.isfinite(fresh).any():
        f = fresh[np.isfinite(fresh)]
        e2e["fresh_p50_s"] = (float(np.percentile(f, 50)), "s")
        e2e["fresh_p95_s"] = (float(np.percentile(f, 95)), "s")
    out = {"correct": result["correct"],
           "attempted": int(in_window.sum()),
           "failed": unsummarized,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": memory_peak},
           "checks": result["checks"]}
    ctx = dict(cell=cell, config=cfg, traffic=mix, roofline=roof,
               bounds=bounds, n_window=n_window, window_s=window_s,
               producer=prod, t0=t0, t_last=t_last, plan_of=plan_of,
               peaks=load_json(HERE / "peaks.json"),
               device_kind=dev.device_kind, trace=None)
    if trace:
        from . import trace as tr

        ctx["trace"] = tr.reduce(tr.load(trace_dir),
                                 {"roofline": roof.EVENTS})
        shutil.rmtree(trace_dir, ignore_errors=True)
        red = ctx["trace"]
        out["device"]["busy_s"] = red["busy_s"]
        out["device"]["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
        metrics = {}
        for m in cell.per_layer:
            reader = load_module(HERE / "metrics" / f"{m['name']}.py",
                                 "bench_metric_" + m["name"].replace(".", "_"))
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": float(e2e[m["name"]][0]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}
    out["metrics"] = metrics
    out["info"] = {"window_items": window_items, "rounds": n_window,
                   "compiles_in_window": compiles_in_window,
                   "sessions_checked": len(sample["sessions"]),
                   "fval_gap_max": sample["fval_gap_max"],
                   "host_e2e": {k: v[0] for k, v in e2e.items()}}
    if control:
        out["control"] = result["control"]
        out["sample"] = sample
    return out


def _summarized_at(stream_tags, cum, t_ret, S) -> np.ndarray:
    """Per stream item, when the readout that first covered it returned
    (NaN for an item no round took)."""
    N = len(stream_tags)
    order = np.argsort(stream_tags, kind="stable")
    counts = np.bincount(stream_tags, minlength=S)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    rank = np.empty(N, np.int64)
    rank[order] = np.arange(N) - np.repeat(starts, counts)
    out = np.full(N, np.nan)
    t_pad = np.concatenate([t_ret, [np.nan]])
    for s in range(S):
        idx = order[starts[s]:starts[s] + counts[s]]
        k = np.searchsorted(cum[:, s], rank[idx], side="right")
        out[idx] = t_pad[k]
    return out
