"""The reduction from a profiler trace to busy time, kernel time, idle gaps."""
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).parent / "data"


def _trace():
    # host: the serving thread holds two rounds, the producer one put;
    # device: ops at [10, 30), [25, 40) and [70, 90) ns, one program
    return {
        "device": {"/device:TPU:0": {
            "XLA Ops": [["%pod_step_pallas.1 = f32[]", 10, 30],
                        ["%copy.1 = f32[]", 25, 40],
                        ["%fusion.2 = f32[]", 70, 90],
                        ["%fusion.3 = f32[]", 150, 160]],
            "XLA Modules": [["jit_ingest_routed(1)", 10, 40]]}},
        "host": {
            "main#0": [["bench.serve_round", 0, 50],
                       ["get", 40, 50],
                       ["bench.readout", 50, 60],
                       ["bench.serve_round", 60, 100]],
            "producer#1": [["bench.put", 0, 100]]},
    }


def test_window_busy_and_kernels_by_hand():
    r = tr.reduce(_trace(), {"step": ("pod_step_pallas",),
                             "program": ("ingest_routed",),
                             "absent": ("no_such_kernel",)})
    assert r["window_s"] == pytest.approx(100e-9)  # bench.* but put
    # [10, 40) merged + [70, 90); the op at 150 lies outside the window
    assert r["busy_s"] == pytest.approx(50e-9)
    assert r["kernel_s"] == pytest.approx({"step": 20e-9, "program": 30e-9,
                                           "absent": None})
    ops = dict(r["device_ops"])
    assert ops["%pod_step_pallas.1"] == pytest.approx(20e-9)
    assert "%fusion.3" not in ops


def test_idle_gaps_are_labelled_by_host_activity():
    gaps = dict(tr.reduce(_trace())["idle_gaps"])
    # idle: [0, 10) and [90, 100) in a round, [40, 70) labelled by its
    # middle, 55, in the readout
    assert sum(gaps.values()) == pytest.approx(50e-9)
    assert gaps["bench.put+bench.serve_round"] == pytest.approx(20e-9)
    assert gaps["bench.put+bench.readout"] == pytest.approx(30e-9)


def test_recorded_chip_trace():
    """A trace of ts256-steady on a TPU v5e, cut to the device plane and
    the threads that hold the harness's annotations."""
    raw = tr.read(DATA / "ts256-steady.trace.json.gz")
    r = tr.reduce(raw, {"step": ("pod_step_pallas",),
                        "program": ("ingest_routed",)})
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(14.028662624)
    assert r["busy_s"] == pytest.approx(0.043838365)
    assert r["kernel_s"]["step"] == pytest.approx(0.040842226)
    assert r["kernel_s"]["program"] == pytest.approx(0.043171238)
    assert r["device_ops"][0][0] == "%pod_step_pallas.1"
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) == 10
    idle = r["window_s"] - r["busy_s"]
    assert sum(v for _, v in r["idle_gaps"]) <= idle + 1e-9
    assert r["idle_gaps"][0][0] == "bench.put+bench.serve_round"


def test_a_trace_without_annotations_is_an_error():
    t = _trace()
    t["host"] = {"main#0": [["get", 0, 10]]}
    with pytest.raises(ValueError):
        tr.reduce(t)
