"""The needed-work counts of both pod steps, on cases worked by hand."""
import importlib.util
from pathlib import Path

import numpy as np
import pytest

ROOF = Path(__file__).resolve().parents[1] / "roofline"


def _load(name):
    spec = importlib.util.spec_from_file_location(f"roof_{name}",
                                                  ROOF / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _edge(items, accepts, n, alive):
    return {"items": np.asarray(items), "accepts": np.asarray(accepts),
            "n": np.asarray(n), "alive": np.asarray(alive)}


def test_threesieves_by_hand():
    ts = _load("threesieves")
    start = _edge([0, 0], [0, 0], [[1], [2]], [[True], [True]])
    end = _edge([10, 20], [1, 0], [[3], [2]], [[True], [True]])
    w = ts.work({"d": 4}, np.asarray([2, 3]), start, end, steps=2)
    # mean n = 2 for both: an item costs 2*2*4 + 2*2^2 = 24, an accept
    # 2*2*4 + 4*2^2 = 32: 10*24 + 32 + 20*24
    assert w["flops"] == pytest.approx(752)
    # floats: 30 items of 4, and per step every session's K d + 2 K^2
    # (K = 2: 16, K = 3: 30) read and written: 120 + 2*2*46
    assert w["bytes"] == pytest.approx(4 * (120 + 184))


def test_sievestreaming_pp_by_hand():
    ss = _load("sievestreamingpp")
    start = _edge([0], [0], [[1, 0, 0]], [[True, True, True]])
    end = _edge([10], [3], [[3, 1, 0]], [[True, True, False]])
    w = ss.work({"d": 4}, np.asarray([2]), start, end, steps=1)
    # rung weights 1, 1, 0.5 and mean n 2, 0.5, 0: an item costs
    # 24 + 4.5 + 0 = 28.5; an accept at the weighted mean n = 1 costs
    # 2*1*4 + 4*1 = 12
    assert w["flops"] == pytest.approx(10 * 28.5 + 3 * 12)
    # 10 items of 4 floats; K = 2 state (16 floats) on 2.5 rungs, in and
    # out once
    assert w["bytes"] == pytest.approx(4 * (40 + 2 * 40))


def test_the_count_ignores_padding_and_passes():
    """Only the counters enter: K_max, C and the kernel's gain passes
    appear nowhere, so a kernel that does less redundant work reads a
    higher share of the same count."""
    ts = _load("threesieves")
    a = _edge([0], [0], [[5]], [[True]])
    b = _edge([100], [0], [[5]], [[True]])
    one = ts.work({"d": 8, "K_max": 100, "chunk": 1024}, np.asarray([5]),
                  a, b, steps=1)
    two = ts.work({"d": 8, "K_max": 8, "chunk": 8}, np.asarray([5]),
                  a, b, steps=1)
    assert one == two
