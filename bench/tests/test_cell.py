"""A cell's whole run at tiny sizes on the CPU, Pallas in interpret mode,
through the harness's own functions (the command itself refuses a CPU).

The sizes are cut (8 sessions, K_max 16, d 64, chunk 32); the traffic,
the served path, the drain and the check are the cell's own.
"""
import dataclasses
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
TINY = dict(sessions=8, K_max=16, d=64, chunk=32, lengthscale=4.0,
            plans=[[4, 10, 0.2], [8, 10, 0.2], [16, 20, 0.2]],
            default_plan=[16, 20, 0.2], sample_sessions=8,
            batch_fill=0.125)  # B = C: no session can overflow a chunk


def tiny(workload: str) -> harness.Cell:
    cell = harness.load_cell(workload)
    cell.config = dict(cell.config, **TINY)
    mix = dict(cell.traffic, pool_items=4096, put_items=64,
               batches_per_round=min(cell.traffic["batches_per_round"], 2))
    if mix["loop"] == "open":
        mix["rate_items_per_s"] = 400.0
    if mix["drift_min_items"] < 2 ** 20:
        mix["drift_min_items"] = 64  # re-arms within a short run
    cell.traffic = mix
    return cell


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """The tests share their process with others: leave JAX's
    persistent compilation cache as it was."""
    monkeypatch.setattr(harness, "enable_cache", lambda: None)


def run(workload, seed=2 ** 31 + 11, seconds=1.5, **kw):
    return harness.run(tiny(workload), seed, seconds, interpret=True, **kw)


@pytest.mark.parametrize("workload", ["ts256-rearm", "ts256-live",
                                      "sspp256-steady"])
def test_tiny_cell_is_correct(workload):
    out = run(workload)
    checks = out["checks"]
    assert out["correct"], checks
    assert checks["routing_gap"]["value"] == 0
    assert checks["drops"]["value"] == 0
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["items_per_s"] > 0 and m["setup_s"] > 0
    assert ("fresh_p95_s" in m) == (workload == "ts256-live")
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["info"]["compiles_in_window"] == 0
    assert out["device"]["platform"] == "cpu"  # never a chip number
    assert out["info"]["sessions_checked"] == 8


def test_traced_tiny_cell_reports_its_layers():
    out = run("ts256-steady", trace=True)
    names = set(out["metrics"])
    assert {"put_us_per_item", "serve_ms_per_batch",
            "device_idle"} <= names
    # no TPU plane in a CPU trace: the roofline reader finds nothing and
    # the metric is left out, never reported as 0
    assert "pod_step_roofline" not in names
    assert out["device"]["window_s"] > 0


def _faulty_pod(kind):
    from repro.serve import SummarizerPod

    @dataclasses.dataclass(frozen=True)
    class Pod(SummarizerPod):
        def ingest_routed(self, state, chunks, counts, unknown, overflow):
            if kind == "half_batch":  # the step sees half of each chunk
                state2, st = SummarizerPod.ingest_routed(
                    self, state, chunks, counts // 2, unknown, overflow)
                return dataclasses.replace(state2,
                                           items=state.items + counts), st
            state2, st = SummarizerPod.ingest_routed(
                self, state, chunks, counts, unknown, overflow)
            if kind == "stale_step":  # the summaries never move
                return dataclasses.replace(state2, algo=state.algo), st
            return state2, st

        def readout(self, state):
            ro = SummarizerPod.readout(self, state)
            if kind == "bad_readout":  # one answer altered where made
                ro = ro._replace(feats=ro.feats.at[0, 0, 0].add(1.0))
            return ro

    return Pod


@pytest.mark.parametrize("kind", ["stale_step", "half_batch", "bad_readout"])
def test_a_broken_timed_path_is_not_correct(kind, monkeypatch):
    build = harness.build_pod

    def broken(cfg, interpret):
        pod, paths = build(cfg, interpret)
        return _faulty_pod(kind)(algo=pod.algo, sessions=pod.sessions,
                                 chunk=pod.chunk,
                                 podstep_backend=pod.podstep_backend), paths

    monkeypatch.setattr(harness, "build_pod", broken)
    out = run("ts256-steady")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_control_is_not_correct(seed):
    """The reference at the next precision below the configuration's
    (three bf16 passes for float32 at highest), put in the pod's place,
    fails the check the program passes."""
    out = run("ts256-steady", seed=seed, control=("high",))
    assert out["correct"], out["checks"]
    limits = tiny("ts256-steady").config["limits"]
    ctl = out["control"]["high"]
    assert any(ctl[k] > limits[k] for k in ("rows_missing", "fval_gap"))


@pytest.mark.parametrize("key,value", [("dtype", "bfloat16"),
                                       ("matmul_precision", "high")])
def test_a_configuration_the_harness_cannot_honour_is_refused(key, value):
    cell = tiny("ts256-steady")
    cell.config = dict(cell.config, **{key: value})
    with pytest.raises(ValueError, match="float32 pods at highest"):
        harness.run(cell, 1, 0.5, interpret=True)


def test_the_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "ts256-steady", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 2 and p.stdout == ""
    assert "nothing was run" in p.stderr


def test_the_command_needs_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "ts256-steady", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
