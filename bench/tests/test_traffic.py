"""The traffic pool is a function of the seed, and every session's item
sequence can be rebuilt from it."""
import types

import numpy as np
import pytest

from bench import check, traffic

MIX = {"components": 3, "spread": 0.5, "noise": 0.0625,
       "drift_per_batch": 0.00625}


def _pool(seed, items=1000, **kw):
    return traffic.make_pool(seed, items=items, sessions=5, d=8, batch=64,
                             mix=MIX, block=256, **kw)


def test_same_seed_same_pool_other_seed_other_pool():
    big = 2 ** 31 + 12345  # seeds past 32 signed bits are fine
    t1, x1 = _pool(big)
    t2, x2 = _pool(big)
    assert np.array_equal(t1, t2) and np.array_equal(x1, x2)
    t3, x3 = _pool(big + 1)
    assert not np.array_equal(x1, x3)
    assert _pool(2 ** 33 + 5)[1].shape == (1000, 8)
    assert t1.dtype == np.int32 and x1.dtype == np.float32
    assert t1.min() >= 0 and t1.max() < 5


def test_a_session_stream_is_rebuilt_across_the_pool_wrap():
    """Stream item i is pool row i % P, dealt in lap L to session
    (tag + L) % S; a session's items since its last re-arm are its stream
    items in order, taken from the pool."""
    tags = np.asarray([0, 1, 0, 2, 1], np.int32)
    stream = traffic.stream_tags(tags, 0, 12, 3)  # two and a half pools
    assert stream.tolist() == [0, 1, 0, 2, 1, 1, 2, 1, 0, 2, 2, 0]
    b = types.SimpleNamespace
    bounds = [b(items=np.asarray([4, 4, 4]), resets=np.asarray([0, 0, 0]))]
    assert (check.since_rearm(stream, bounds, 0) % 5).tolist() == \
        [0, 2, 3, 1]
    assert (check.since_rearm(stream, bounds, 1) % 5).tolist() == \
        [1, 4, 0, 2]


def test_no_session_is_sent_a_row_twice_within_its_laps():
    S, P = 7, 200
    tags = np.random.default_rng(0).integers(0, S, P).astype(np.int32)
    stream = traffic.stream_tags(tags, 0, S * P, S)
    for s in range(S):
        rows = np.flatnonzero(stream == s) % P
        assert len(np.unique(rows)) == len(rows)
    # any slice of the stream is the same slice of the whole
    assert np.array_equal(traffic.stream_tags(tags, 333, 777, S),
                          stream[333:777])


def test_the_mixture_is_drawn_in_lengthscales():
    t1, x1 = _pool(5, lengthscale=1.0)
    t2, x2 = _pool(5, lengthscale=0.0625)
    assert np.array_equal(t1, t2)
    np.testing.assert_allclose(x2, 0.0625 * x1, rtol=1e-5, atol=1e-7)


def test_the_generator_refuses_tenants_it_cannot_draw():
    with pytest.raises(ValueError, match="tenants"):
        _pool(1, tenants="zipf")


def test_since_rearm_replays_from_the_last_rearm():
    stream = np.asarray([0, 1, 0, 0, 1, 0, 0, 1, 0], np.int32)
    b = types.SimpleNamespace
    bounds = [b(items=np.asarray([2, 1]), resets=np.asarray([0, 0])),
              b(items=np.asarray([4, 2]), resets=np.asarray([1, 0])),
              b(items=np.asarray([6, 3]), resets=np.asarray([1, 0]))]
    # session 0 re-armed at the end of the second round, after 4 items
    assert check.since_rearm(stream, bounds, 0).tolist() == [6, 8]
    assert check.since_rearm(stream, bounds, 1).tolist() == [1, 4, 7]


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 3])
def test_sample_covers_every_plan(seed):
    plans = [(10,), (50,), (100,)] * 4
    s = traffic.sample_sessions(seed, plans, 4)
    assert s == traffic.sample_sessions(seed, plans, 4)
    assert len(s) == 4 and {plans[i] for i in s} == set(plans)
