"""Tier-1 tests for the fleet telemetry layer (repro.obs, DESIGN.md §13).

Covers the four pieces and the one rule:

  * registry — counters/gauges/histograms with labels, signature
    conflicts, JSON snapshot round-trip, Prometheus exposition, the
    ``NULL`` off-switch;
  * spans — nesting, outcomes (ok/refused/error), the trace-time no-op
    backstop, JSONL dump;
  * jax bridge — exactly one subscription ever, and its compile counter
    agrees with the retrace_guard fixture counting the same events;
  * drain — cumulative device/host ledgers become monotone counters
    (including the slot-recycle counter-reset rule), and the three drop
    ledgers unify under ``drops_total{layer,reason}``;
  * instrumented stack — an ingest run records at its sync boundary
    with ZERO fresh compiles (telemetry must not retrace the pod), a
    refused handoff leaves a ``refused`` span with no phase children, a
    successful one leaves the full phase tree, checkpoint save/restore
    leave spans, and backend degrades are counted per event.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs import jaxbridge
from repro.obs.registry import MetricsSnapshot
from test_ingest import _closed_buffer

# --------------------------------------------------------------------------
# isolation: every test gets a fresh default registry + a cleared recorder
# --------------------------------------------------------------------------


@pytest.fixture
def fresh_obs():
    reg = obs.reset_default_registry()
    rec = obs.get_recorder()
    rec.clear()
    yield reg, rec
    obs.reset_default_registry()
    rec.clear()


# ------------------------------------------------------------------ registry
def test_counter_gauge_histogram_basics(fresh_obs):
    reg, _ = fresh_obs
    c = reg.counter("reqs_total", "requests", ("pod",))
    c.labels(pod="0").inc()
    c.labels(pod="0").inc(2)
    c.labels(pod="1").inc(5)
    g = reg.gauge("depth", "queue depth")
    g.set(7)
    g.dec(2)
    h = reg.histogram("lat_seconds", "latency")
    h.observe(0.004)
    h.observe(99.0)  # lands in the +inf bucket
    snap = reg.snapshot()
    assert snap.get("reqs_total", pod="0") == 3
    assert snap.get("reqs_total", pod="1") == 5
    assert snap.get("depth") == 5
    fam = [f for f in snap.families if f["name"] == "lat_seconds"][0]
    assert fam["series"][0]["count"] == 2
    assert fam["series"][0]["counts"][-1] == 1  # the 99s observation


def test_label_and_signature_contracts(fresh_obs):
    reg, _ = fresh_obs
    fam = reg.counter("x_total", "x", ("pod",))
    with pytest.raises(ValueError, match="label"):
        fam.labels(shard="0")  # wrong label name
    with pytest.raises(ValueError, match="cannot decrease"):
        fam.labels(pod="0").inc(-1)
    with pytest.raises(ValueError, match="cannot set"):
        reg.counter("y_total").set(3)
    # idempotent re-registration with the same signature is fine...
    assert reg.counter("x_total", "x", ("pod",)) is fam
    # ...a conflicting one is how dashboards lie — it raises
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total", "x", ("pod",))
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("x_total", "x", ("shard",))


def test_snapshot_json_round_trip_and_prometheus(fresh_obs):
    reg, _ = fresh_obs
    reg.counter("a_total", "help text", ("k",)).labels(k="v").inc(3)
    reg.histogram("h_seconds", "hist").observe(0.2)
    snap = reg.snapshot()
    back = MetricsSnapshot.from_json(snap.to_json())
    assert back.families == snap.families
    json.loads(snap.to_json())  # strict JSON (no Infinity literals)
    prom = snap.to_prometheus()
    assert '# TYPE a_total counter' in prom
    assert 'a_total{k="v"} 3' in prom
    assert 'le="+Inf"' in prom  # the 1e308 sentinel renders as +Inf
    assert prom.endswith("\n")


def test_null_registry_is_inert(fresh_obs):
    n = obs.NULL
    assert not n.enabled
    n.counter("x_total").labels(pod="0").inc()
    n.gauge("g").set(4)
    n.histogram("h").observe(1.0)
    assert n.snapshot().families == []
    assert n.to_prometheus() == ""
    assert obs.get_registry(n) is n
    assert obs.get_registry(None) is not n


# --------------------------------------------------------------------- spans
def test_spans_nest_and_record_outcomes(fresh_obs):
    reg, rec = fresh_obs
    with rec.span("outer", src="0"):
        with rec.span("inner") as sp:
            sp.set(items=3)
        with rec.span("refusal") as sp:
            sp.set_outcome("refused")
    inner, refusal, outer = rec.events
    assert outer["name"] == "outer" and outer["depth"] == 0
    assert inner["parent_id"] == outer["span_id"] and inner["depth"] == 1
    assert inner["attrs"]["items"] == 3
    assert refusal["outcome"] == "refused"
    assert outer["dur_s"] >= inner["dur_s"] >= 0
    snap = reg.snapshot()
    assert snap.get("spans_total", name="inner", outcome="ok") == 1
    assert snap.get("spans_total", name="refusal", outcome="refused") == 1


def test_span_records_error_and_reraises(fresh_obs):
    _, rec = fresh_obs
    with pytest.raises(RuntimeError, match="boom"):
        with rec.span("failing"):
            raise RuntimeError("boom")
    (ev,) = rec.find("failing")
    assert ev["outcome"] == "error"
    assert ev["attrs"]["error"] == "RuntimeError"


def test_span_is_noop_under_trace(fresh_obs):
    """The runtime backstop of podlint PL006: entering a span inside a
    jit trace records nothing (and crashes nothing)."""
    _, rec = fresh_obs

    @jax.jit
    def f(x):
        # the deliberate violation that pins the runtime backstop
        with obs.span("traced-span"):  # podlint: ignore[PL006] -- see above
            return x * 2

    np.testing.assert_array_equal(np.asarray(f(jnp.arange(3))), [0, 2, 4])
    assert rec.find("traced-span") == []


def test_span_jsonl_dump(fresh_obs, tmp_path):
    _, rec = fresh_obs
    with rec.span("one", pod="3"):
        pass
    p = rec.dump_jsonl(tmp_path / "spans.jsonl")
    lines = [json.loads(line) for line in p.read_text().splitlines()]
    assert [e["name"] for e in lines] == ["one"]
    assert lines[0]["attrs"]["pod"] == "3"


# ---------------------------------------------------------------- jax bridge
def test_bridge_installs_exactly_once(fresh_obs):
    """repro.obs installed the bridge at import; every later install()
    is a no-op — jax.monitoring has no unregister, so a second
    subscription would double-count forever."""
    assert jaxbridge.installed()
    assert obs.install_jax_bridge() is False
    assert obs.install_jax_bridge() is False
    assert jaxbridge.registrations() == 1


def test_bridge_and_retrace_guard_count_the_same_compiles(
        fresh_obs, retrace_guard):
    """Two independent subscribers, one event stream: the bridge's
    always-on xla_compile_total must agree with the retrace_guard
    fixture over a scope that definitely compiles."""
    reg, _ = fresh_obs
    with retrace_guard.budget(10):
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(11))  # fresh shape+fn
    fresh = retrace_guard.compiles
    assert fresh >= 1
    assert reg.snapshot().get("xla_compile_total") == fresh


# --------------------------------------------------------------------- drain
def test_observe_total_is_monotone_with_reset_rule(fresh_obs):
    reg, _ = fresh_obs
    assert obs.drain.observe_total("led_total", {"pod": "0"}, 10) == 10
    assert obs.drain.observe_total("led_total", {"pod": "0"}, 10) == 0
    assert obs.drain.observe_total("led_total", {"pod": "0"}, 15) == 5
    # the ledger shrank: a recycled slot restarted it — post-reset total
    # counts as new growth, the counter never goes down
    assert obs.drain.observe_total("led_total", {"pod": "0"}, 3) == 3
    assert reg.snapshot().get("led_total", pod="0") == 18
    # fresh registry => fresh baselines (no cross-test bleed)
    reg2 = obs.reset_default_registry()
    assert obs.drain.observe_total("led_total", {"pod": "0"}, 15) == 15
    assert reg2.snapshot().get("led_total", pod="0") == 15


def test_drain_pod_unifies_device_ledgers(fresh_obs):
    import types
    reg, _ = fresh_obs
    state = types.SimpleNamespace(
        drops_overflow=np.array([2, 0, 1], np.int32),
        drops_unknown=np.array([4, 0, 0], np.int32),
        items=np.array([10, 20, 0], np.int32),
        accepts=np.array([3, 5, 0], np.int32),
        resets=np.array([1, 0, 0], np.int32),
        active=np.array([True, True, False]),
    )
    obs.drain.drain_pod(state, pod="7")
    snap = reg.snapshot()
    assert snap.get("drops_total", layer="pod", reason="overflow",
                    pod="7") == 3
    assert snap.get("drops_total", layer="pod", reason="unknown",
                    pod="7") == 4
    assert snap.get("pod_items_total", pod="7") == 30
    assert snap.get("pod_accepts_total", pod="7") == 8
    assert snap.get("pod_drift_resets_total", pod="7") == 1
    assert snap.get("pod_active_sessions", pod="7") == 2
    assert snap.get("pod_occupancy", pod="7") == pytest.approx(2 / 3)
    # second drain with no growth adds nothing
    obs.drain.drain_pod(state, pod="7")
    assert reg.snapshot().get("drops_total", layer="pod",
                              reason="overflow", pod="7") == 3


def test_drop_ledgers_unify_across_all_three_layers(fresh_obs):
    """The satellite: pod, buffer and router drops all land in ONE
    ``drops_total{layer,reason}`` family, each as a monotone counter."""
    from repro.ingest import IngestPipeline, TaggedBuffer
    from repro.ingest.pipeline import PodRouter
    reg, _ = fresh_obs
    buf = TaggedBuffer(capacity=2, policy="drop-newest")
    buf.put(np.array([5, 5, 5], np.int32), np.zeros((3, 2), np.float32))
    obs.drain.drain_buffer(buf, pod="1")

    class _Pod:  # pipeline shell; never run
        class algo:
            class f:
                d = 2
        chunk = 4
    router = PodRouter({0: IngestPipeline(
        pod=_Pod(), buffer=TaggedBuffer(capacity=8), batch=4)})
    router.put(np.array([99], np.int32), np.zeros((1, 2), np.float32))
    obs.drain.drain_router(router)

    snap = reg.snapshot()
    assert snap.get("drops_total", layer="buffer", reason="clipped",
                    pod="1") == 1
    assert snap.get("drops_total", layer="router", reason="unrouted",
                    pod="-") == 1
    fam = [f for f in snap.families if f["name"] == "drops_total"][0]
    assert fam["labelnames"] == ["layer", "pod", "reason"]


def test_sheds_and_throttles_stay_out_of_drops_total(fresh_obs):
    """Regression for the PR 8 unification: the admission policies'
    deliberate losses (watermark sheds, rate-limit throttles) must NOT
    leak into ``drops_total{layer=buffer,reason=clipped}`` — that family
    counts capacity overflow only, so it stays an accident signal.
    Sheds land in ``shed_total{policy,pod}``, throttles in
    ``ratelimit_throttled_total{pod}``."""
    from repro.ingest import RateLimit, ShedPolicy, TaggedBuffer
    reg, _ = fresh_obs
    clock = [0.0]
    buf = TaggedBuffer(capacity=16, policy="drop-newest",
                       rate_limit=RateLimit(rate=1000.0, burst=8.0),
                       shed=ShedPolicy(lo=0.25, hi=0.5, p_floor=0.01,
                                       clip_mult=1.0, seed=0),
                       clock=lambda: clock[0])
    # session 1 floods: first throttled past its burst, admitted items
    # then walk the buffer up the ladder until the clip rung sheds
    buf.put(np.array([1] * 30, np.int32), np.zeros((30, 2), np.float32))
    assert buf.total_throttled() > 0
    clock[0] = 1.0  # bucket refills; now the ladder does the refusing
    buf.put(np.array([1] * 30, np.int32), np.zeros((30, 2), np.float32))
    assert buf.total_sheds() > 0
    assert buf.total_drops() == 0  # neither ledger bled into overflow

    obs.drain.drain_buffer(buf, pod="3")
    snap = reg.snapshot()
    assert snap.get("drops_total", layer="buffer", reason="clipped",
                    pod="3") == 0
    shed_sum = sum(snap.get("shed_total", policy=p, pod="3")
                   for p in obs.drain.SHED_POLICIES)
    assert shed_sum == buf.total_sheds()
    assert snap.get("ratelimit_throttled_total",
                    pod="3") == buf.total_throttled()
    assert snap.get("buffer_shed_rung", pod="3") == \
        obs.drain.SHED_RUNG_INDEX[buf.shed_rung()]
    # per-session ledgers agree with the totals
    assert sum(buf.shed_counts().values()) == buf.total_sheds()
    assert sum(buf.throttled_counts().values()) == buf.total_throttled()
    # a genuine overflow still lands in drops_total: drown a shed-free
    # buffer (no ladder) past capacity
    buf2 = TaggedBuffer(capacity=2, policy="drop-newest")
    buf2.put(np.array([7, 7, 7], np.int32), np.zeros((3, 2), np.float32))
    obs.drain.drain_buffer(buf2, pod="4")
    snap2 = reg.snapshot()
    assert snap2.get("drops_total", layer="buffer", reason="clipped",
                     pod="4") == 1
    assert snap2.get("shed_total", policy="subsample", pod="4") == 0


def test_backend_fallback_counted_per_degrade_warned_once(fresh_obs):
    from repro.kernels.pod_step import ops
    reg, _ = fresh_obs
    ops._reset_warnings()
    with pytest.warns(RuntimeWarning, match="no fused pod-step kernel"):
        assert ops.resolve("pallas-interpret", object()) == "jnp"
    import warnings as _w
    with _w.catch_warnings():  # second degrade: no warning, still counted
        _w.simplefilter("error")
        assert ops.resolve("pallas-interpret", object()) == "jnp"
    assert reg.snapshot().get(
        "backend_fallback_total", kernel="pod_step",
        **{"from": "pallas-interpret", "to": "jnp"}) == 2
    ops._reset_warnings()


# ------------------------------------------------------- instrumented stack
def _fleet(S=8, d=4, batch=16, n_pods=2):
    from repro.core.api import make
    from repro.ingest import IngestPipeline, TaggedBuffer
    from repro.ingest.pipeline import PodRouter
    from repro.serve.summarize import SummarizerPod
    algo = make("threesieves", d=d, K=4, T=16, eps=0.5)
    pods = {i: SummarizerPod(algo, sessions=S, chunk=batch)
            for i in range(n_pods)}
    pipes = {i: IngestPipeline(pod=p, buffer=TaggedBuffer(4096), batch=batch)
             for i, p in pods.items()}
    router = PodRouter(pipes)
    states = {i: p.init() for i, p in pods.items()}
    return pods, pipes, router, states


def test_pipeline_records_at_sync_boundary_without_retracing(
        fresh_obs, retrace_guard):
    """The tentpole contract: an instrumented ingest run records its
    boundary metrics + device-ledger drain with ZERO fresh compiles
    beyond warmup — telemetry never touches the compiled program."""
    from repro.core.api import make
    from repro.ingest import IngestPipeline
    from repro.serve.summarize import SummarizerPod
    reg, _ = fresh_obs
    algo = make("threesieves", d=4, K=4, T=16, eps=0.5)
    pod = SummarizerPod(algo, sessions=4, chunk=16)
    state = pod.init()
    admit = jax.jit(pod.admit)
    for sid in range(3):
        state, _, _ = admit(state, sid)
    rng = np.random.default_rng(0)

    def batches(n):
        return [(rng.integers(0, 3, 16).astype(np.int32),
                 rng.normal(size=(16, 4)).astype(np.float32))
                for _ in range(n)]

    warm = IngestPipeline(pod=pod, buffer=_closed_buffer(batches(1)),
                          batch=16)
    state, _ = warm.run(state)
    with retrace_guard.budget(0):
        pipe = IngestPipeline(pod=pod, buffer=_closed_buffer(batches(5)),
                              batch=16, pod_id="9")
        state, stats = pipe.run(state)
    assert stats["items"] == 80
    snap = reg.snapshot()
    assert snap.get("ingest_items_total", pod="9") == 80
    assert snap.get("ingest_batches_total", pod="9") == 5
    assert snap.get("pod_items_total", pod="9") == float(
        np.asarray(state.items).sum())
    assert snap.get("drops_total", layer="pod", reason="overflow",
                    pod="9") == 0.0
    assert snap.get("pod_active_sessions", pod="9") == 3


def test_pipeline_metrics_null_disables(fresh_obs):
    from repro.core.api import make
    from repro.ingest import IngestPipeline
    from repro.serve.summarize import SummarizerPod
    reg, _ = fresh_obs
    algo = make("threesieves", d=4, K=4, T=16, eps=0.5)
    pod = SummarizerPod(algo, sessions=4, chunk=16)
    state = pod.init()
    state, _, _ = pod.admit(state, 0)
    sids = np.zeros((16,), np.int32)
    X = np.ones((16, 4), np.float32)
    pipe = IngestPipeline(pod=pod, buffer=_closed_buffer([(sids, X)]),
                          batch=16, metrics=obs.NULL)
    state, stats = pipe.run(state)
    assert stats["items"] == 16
    assert reg.snapshot().get("ingest_items_total", pod="0") is None


def test_bare_and_instrumented_runs_give_bit_equal_state(fresh_obs):
    """Recording never touches what is computed: one feed through a
    pipeline with ``metrics=obs.NULL`` and through one that records into
    the default registry ends in bit-equal pod state, and only the
    second leaves counters."""
    from repro.core.api import make
    from repro.ingest import IngestPipeline
    from repro.serve.summarize import SummarizerPod
    reg, _ = fresh_obs
    algo = make("threesieves", d=4, K=4, T=16, eps=0.5)
    pod = SummarizerPod(algo, sessions=8, chunk=16)
    state0 = pod.init()
    for sid in range(8):
        state0, _, _ = pod.admit(state0, sid)
    rng = np.random.default_rng(3)
    feed = [(rng.integers(0, 9, 64).astype(np.int32),
             rng.normal(size=(64, 4)).astype(np.float32)) for _ in range(4)]
    out = {}
    for pod_id, metrics in (("bare", obs.NULL), ("instrumented", None)):
        pipe = IngestPipeline(pod=pod, buffer=_closed_buffer(feed),
                              batch=32, pod_id=pod_id, metrics=metrics)
        out[pod_id], stats = pipe.run(state0)
        assert stats["batches"] >= 8 and stats["dropped_unknown"] > 0
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(out["bare"]),
            jax.tree_util.tree_leaves(out["instrumented"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))
    snap = reg.snapshot()
    assert snap.get("ingest_items_total", pod="bare") is None
    assert snap.get("ingest_items_total", pod="instrumented") == 256


def test_handoff_refusal_leaves_refused_span_with_no_phases(fresh_obs):
    from repro.serve.autoscale import PodAutoscaler
    reg, rec = fresh_obs
    pods, pipes, router, states = _fleet()
    scaler = PodAutoscaler(router, pods)
    states, rep = scaler.handoff(states, 0, 0, [1])
    assert not rep.ok and rep.reason == "src == dst"
    assert [e["name"] for e in rec.events] == ["handoff"]
    (ev,) = rec.find("handoff")
    assert ev["outcome"] == "refused"
    assert ev["attrs"]["reason"] == "src == dst"
    assert reg.snapshot().get("handoffs_total", outcome="refused") == 1
    assert rec.find("quiesce") == []


def test_handoff_success_leaves_the_full_phase_tree(fresh_obs):
    from repro.serve.autoscale import PodAutoscaler
    reg, rec = fresh_obs
    pods, pipes, router, states = _fleet()
    admit = jax.jit(pods[0].admit)
    for sid in range(4):
        states[0], _, _ = admit(states[0], sid)
    router.assign([0, 1, 2, 3], 0)
    rec.clear()
    scaler = PodAutoscaler(router, pods)
    states, rep = scaler.handoff(states, 0, 1, [1, 2])
    assert rep.ok and rep.moved == [1, 2]
    (parent,) = rec.find("handoff")
    assert parent["outcome"] == "ok"
    phases = [e for e in rec.events if e["parent_id"] == parent["span_id"]]
    assert [e["name"] for e in phases] == [
        "quiesce", "snapshot", "restore", "evict", "flip"]
    assert all(e["depth"] == 1 and e["outcome"] == "ok" for e in phases)
    snap = reg.snapshot()
    assert snap.get("handoffs_total", outcome="ok") == 1
    assert snap.get("sessions_migrated_total") == 2
    # the handoff edge drained both pods' ledgers
    assert snap.get("pod_active_sessions", pod="0") == 2
    assert snap.get("pod_active_sessions", pod="1") == 2


def test_ckpt_save_restore_spans_and_counters(fresh_obs, tmp_path):
    from repro.ckpt import CheckpointStore
    reg, rec = fresh_obs
    store = CheckpointStore(tmp_path, keep=2)
    tree = {"a": jnp.arange(8), "b": jnp.ones((2, 3))}
    store.save(3, tree, {"note": "x"})
    store.save_async(4, tree)
    store.wait()
    like = jax.eval_shape(lambda: tree)
    back, extra = store.load(3, like)
    np.testing.assert_array_equal(np.asarray(back["a"]), np.arange(8))
    assert [e["outcome"] for e in rec.find("ckpt_save")] == ["ok", "ok"]
    assert rec.find("ckpt_write")  # the async bg write span
    assert rec.find("ckpt_restore")
    snap = reg.snapshot()
    assert snap.get("ckpt_saves_total", mode="sync") == 1
    assert snap.get("ckpt_saves_total", mode="async") == 1
    assert snap.get("ckpt_saved_bytes_total") > 0


def test_drift_reset_span_in_serve(fresh_obs):
    from repro.core.api import make
    from repro.ingest import IngestPipeline
    from repro.serve.summarize import SummarizerPod
    _, rec = fresh_obs
    algo = make("threesieves", d=4, K=4, T=16, eps=0.5)
    pod = SummarizerPod(algo, sessions=4, chunk=16)
    state = pod.init()
    state, _, _ = pod.admit(state, 0)
    sids = np.zeros((16,), np.int32)
    X = np.ones((16, 4), np.float32)
    pipe = IngestPipeline(pod=pod, buffer=_closed_buffer([(sids, X)] * 4),
                          batch=16)
    state, stats = pod.serve(state, pipe, drift_every=2)
    assert stats["batches"] == 4
    # the span is named for what it measures: every drift check, whether
    # or not a session re-arms
    assert len(rec.find("drift_check")) >= 2
    assert rec.find("drift_reset") == []


def test_pod_drain_metrics_delegates(fresh_obs):
    from repro.core.api import make
    from repro.serve.summarize import SummarizerPod
    reg, _ = fresh_obs
    algo = make("threesieves", d=4, K=4, T=16, eps=0.5)
    pod = SummarizerPod(algo, sessions=4, chunk=16)
    state = pod.init()
    state, _, _ = pod.admit(state, 42)
    pod.drain_metrics(state, pod="2")
    assert reg.snapshot().get("pod_active_sessions", pod="2") == 1


# ------------------------------------------------- stages on the served path
STAGES = ("ingest_slot_table", "ingest_get", "ingest_route",
          "ingest_device_put", "ingest_dispatch", "ingest_sync",
          "ingest_slot_lookup", "ingest_scatter")
ROUTE_SPLIT = ("ingest_slot_lookup", "ingest_scatter")  # inside the route


def _replay_run(n_batches=4, pod_id="5"):
    from repro.core.api import make
    from repro.ingest import IngestPipeline
    from repro.serve.summarize import SummarizerPod
    algo = make("threesieves", d=4, K=4, T=16, eps=0.5)
    pod = SummarizerPod(algo, sessions=4, chunk=16)
    state = pod.init()
    state, _, _ = pod.admit(state, 0)
    rng = np.random.default_rng(1)
    batches = [(np.zeros((16,), np.int32),
                rng.normal(size=(16, 4)).astype(np.float32))
               for _ in range(n_batches)]
    pipe = IngestPipeline(pod=pod, buffer=_closed_buffer(batches),
                          batch=16, pod_id=pod_id)
    return pipe.run(state)


def test_stage_adds_to_the_enclosing_span_only(fresh_obs):
    reg, rec = fresh_obs
    with obs.stage("alone") as st:  # no span: nothing recorded
        pass
    assert st.seconds >= 0
    assert rec.events == [] and reg.snapshot().families == []
    with rec.span("outer"):
        with rec.span("inner"):
            with obs.stage("work"):
                pass
        for _ in range(3):
            with obs.stage("work"):
                pass
    inner, outer = rec.events
    assert set(inner["attrs"]) == {"work_s"}
    assert set(outer["attrs"]) == {"work_s"}
    assert 0 <= outer["attrs"]["work_s"] <= outer["dur_s"]
    assert reg.snapshot().get("spans_total", name="outer", outcome="ok") == 1
    assert [f["name"] for f in reg.snapshot().families] == [
        "span_seconds", "spans_total"]


def test_stage_is_noop_under_trace(fresh_obs):
    _, rec = fresh_obs

    @jax.jit
    def f(x):
        with obs.stage("traced-stage"):  # podlint: ignore[PL006] -- pinned
            return x + 1

    with rec.span("host"):
        f(jnp.arange(3)).block_until_ready()
    (ev,) = rec.events
    assert "traced-stage_s" not in ev["attrs"]


def test_ingest_run_span_holds_its_stages(fresh_obs):
    """One ``ingest_run`` event per run, whatever the batch count; its
    stage attributes are ≥ 0, the outer ones sum to at most its duration
    and the route's split to at most the route."""
    _, rec = fresh_obs
    _, stats = _replay_run(n_batches=4)
    (ev,) = rec.events
    assert ev["name"] == "ingest_run" and ev["outcome"] == "ok"
    a = ev["attrs"]
    assert (a["pod"], a["batches"], a["items"], a["padded"]) == (
        "5", 4, stats["items"], 0)
    # per batch: chunks (S, C, d) f32, counts (S,), unknown (), overflow
    # (S,) int32
    assert a["bytes"] == 4 * 4 * (4 * 16 * 4 + 4 + 1 + 4)
    # the producers' wait in put since the last run: a count the span
    # carries, not one of its stages
    assert a["buffer_put_wait_s"] == 0
    stages = {k[:-2]: v for k, v in a.items()
              if k.endswith("_s") and k != "buffer_put_wait_s"}
    assert set(stages) == set(STAGES)
    assert all(v >= 0 for v in stages.values())
    assert sum(v for k, v in stages.items()
               if k not in ROUTE_SPLIT) <= ev["dur_s"]
    assert sum(stages[k] for k in ROUTE_SPLIT) <= stages["ingest_route"]


def test_stages_are_tracemes_inside_ingest_run(fresh_obs, tmp_path):
    """Under a profiler trace the span and its stages sit on the host
    plane, on one thread, each stage inside ``ingest_run``."""
    from jax.profiler import ProfileData
    _replay_run(n_batches=1)  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        _replay_run(n_batches=2)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events]
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:CPU") for line in plane.lines]
    (line,) = [evs for evs in lines
               if any(n == "ingest_run" for n, _, _ in evs)]
    (run,) = [ev for ev in line if ev[0] == "ingest_run"]
    inside = [n for n, s, e in line if s >= run[1] and e <= run[2]]
    assert set(STAGES) <= set(inside)
    assert inside.count("ingest_route") == 2
    routes = [(s, e) for n, s, e in line if n == "ingest_route"]
    for name in ROUTE_SPLIT:
        spans = [(s, e) for n, s, e in line if n == name]
        assert len(spans) == 2 and all(
            any(rs <= s and e <= re for rs, re in routes) for s, e in spans)


def test_buffer_waits_reach_the_drain(fresh_obs):
    """A put blocked on a full buffer and a get below ``min_items`` each
    show in ``buffer_wait_seconds_total`` after a drain — and neither
    writes to the recorder or the registry by itself."""
    import threading
    import time

    from repro.ingest import TaggedBuffer
    reg, rec = fresh_obs
    buf = TaggedBuffer(capacity=2, policy="block")
    row = np.zeros((1, 2), np.float32)
    buf.put([1, 1], np.zeros((2, 2), np.float32))
    t = threading.Thread(target=buf.put, args=([1], row))
    t.start()  # blocks: the buffer is full
    time.sleep(0.2)
    assert buf.get(2) is not None  # makes room
    t.join(5)
    assert not t.is_alive()
    got = []
    t = threading.Thread(target=lambda: got.append(buf.get(4, min_items=2)))
    t.start()  # waits: one item buffered, two asked for
    time.sleep(0.2)
    buf.put([2], row)
    t.join(5)
    assert len(got[0][0]) == 2
    assert rec.events == [] and reg.snapshot().families == []
    waits = buf.wait_seconds()
    assert waits["put"] >= 0.1 and waits["get"] >= 0.1
    obs.drain.drain_buffer(buf, pod="6")
    snap = reg.snapshot()
    for side in ("put", "get"):
        assert snap.get("buffer_wait_seconds_total", side=side,
                        pod="6") == waits[side]


def test_the_bridge_counts_compiles_only(fresh_obs):
    reg, _ = fresh_obs
    jax.jit(lambda x: x - 7)(jnp.arange(13)).block_until_ready()
    names = {f["name"] for f in reg.snapshot().families}
    assert "xla_compile_total" in names
    assert not names & {"jax_events_total", "jax_event_duration_count"}
