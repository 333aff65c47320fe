"""The readers of the program's own spans: the served pipeline's stages
and the buffer's waits, per batch and per item."""
import types

import pytest

from bench import spans
from bench import trace as tr
from bench.tests.test_cell import run

CLOSED = ("put_wait_us_per_item", "get_wait_ms_per_batch",
          "get_ms_per_batch", "route_ms_per_batch", "device_put_ms_per_batch")
LIVE = ("get_wait_ms_per_batch.live", "get_ms_per_batch.live",
        "route_ms_per_batch.live")


@pytest.fixture
def recorder():
    from repro import obs

    rec = obs.get_recorder()
    rec.clear()
    yield rec
    rec.clear()


def _ctx(batches):
    bounds = [types.SimpleNamespace(batches=b) for b in batches]
    return {"cell": types.SimpleNamespace(name="cell-a"), "bounds": bounds,
            "n_window": len(bounds) - 2}


def _runs(rec, rows, pod="cell-a"):
    for batches, stages in rows:
        with rec.span("ingest_run", pod=pod) as sp:
            sp.set(batches=batches, **stages)


def test_readers_take_the_window_rounds_by_hand(recorder):
    _runs(recorder, [(9, {"ingest_route_s": 100.0})], pod="other-cell")
    # warm-up, two window rounds, one drain round
    put = {"buffer_put_items": 10, "buffer_put_wait_s": 50.0}
    _runs(recorder, [
        (1, dict(put, ingest_get_s=50.0, ingest_route_s=50.0)),
        (2, {"ingest_get_s": 0.5, "buffer_get_wait_items_s": 0.25,
             "buffer_get_wait_lock_s": 0.05, "ingest_route_s": 0.4,
             "ingest_device_put_s": 0.02, **put}),
        (2, {"ingest_get_s": 0.3, "ingest_route_s": 0.4,
             "ingest_device_put_s": 0.02, "buffer_put_items": 40,
             "buffer_put_wait_s": 2.0}),
        (1, dict(put, ingest_get_s=50.0))])
    ctx = _ctx([1, 2, 2, 1])
    assert spans.get_wait_ms_per_batch(ctx) == pytest.approx(75.0)
    assert spans.get_ms_per_batch(ctx) == pytest.approx(125.0)
    assert spans.route_ms_per_batch(ctx) == pytest.approx(200.0)
    assert spans.device_put_ms_per_batch(ctx) == pytest.approx(10.0)
    # the window's first run holds the wait from before the window
    assert spans.put_wait_us_per_item(ctx) == pytest.approx(5e4)


def test_readers_find_nothing_in_a_program_without_the_spans(recorder):
    ctx = _ctx([1, 2, 2, 1])
    for read in (spans.get_wait_ms_per_batch, spans.get_ms_per_batch,
                 spans.route_ms_per_batch, spans.device_put_ms_per_batch,
                 spans.put_wait_us_per_item):
        assert read(ctx) is None
    # events that do not line up with the rounds are not read either
    _runs(recorder, [(1, {"ingest_get_s": 1.0})] * 4)
    assert spans.route_ms_per_batch(ctx) is None


def test_idle_gaps_are_labelled_by_the_open_stage():
    """The program's TraceMes on the serving thread name each gap by the
    innermost stage open at its middle."""
    t = {"device": {"/device:TPU:0": {"XLA Ops": [["%op.1 = f32[]", 40, 50]]}},
         "host": {
             "main#0": [["bench.serve_round", 0, 100],
                        ["ingest_run", 0, 100],
                        ["ingest_get", 0, 40],
                        ["buffer_get_wait_items", 0, 30],
                        ["ingest_route", 50, 90]],
             "producer#1": [["bench.put", 0, 100],
                            ["buffer_put_wait_room", 0, 100]]}}
    gaps = dict(tr.reduce(t)["idle_gaps"])
    assert gaps == pytest.approx({
        "bench.put+bench.serve_round:buffer_get_wait_items": 40e-9,
        "bench.put+bench.serve_round:ingest_route": 50e-9})


def test_traced_tiny_cell_reports_the_pipeline_stages():
    out = run("ts256-steady", trace=True)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(CLOSED) <= set(m)
    assert not set(LIVE) & set(m)
    assert all(m[k] >= 0 for k in CLOSED)
    parts = sum(m[k] for k in CLOSED[1:])
    assert parts <= m["serve_ms_per_batch"]
    assert m["put_wait_us_per_item"] <= m["put_us_per_item"]


def test_traced_tiny_live_cell_reports_the_pipeline_stages():
    out = run("ts256-live", trace=True)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(LIVE) <= set(m)
    assert not set(CLOSED) & set(m)
    assert all(m[k] >= 0 for k in LIVE)
    # the open loop fills each batch at the offered rate: the pipeline
    # waits for items far longer than it works on them
    assert m["get_wait_ms_per_batch.live"] > m["get_ms_per_batch.live"]
