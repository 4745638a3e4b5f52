"""The traffic generator: one seed gives one schedule; another seed
gives the same work (lengths, arrivals) in another order."""

import collections
import itertools

import numpy as np
import pytest

from bench import traffic


def _mix(**over):
    mix = {
        "arrival": {"kind": "poisson", "rate_per_s": 12.0},
        "warm_s": 3.0, "drain_s": 20.0,
        "src_len": {"median": 32, "sigma": 0.6, "min": 4, "max": 256},
        "out_len": {"ratio_low": 0.8, "ratio_high": 1.3, "min": 4,
                    "max": 256},
        "vocab": {"low": 4, "high": 256001},
        "langs": {"low": 256001, "high": 256203},
        "pool_seed": 200,
    }
    mix.update(over)
    return mix


def _window(items):
    return [it for it in items if it.in_window]


def test_same_seed_same_schedule():
    a = traffic.schedule(_mix(), 2 ** 31 + 5, 10.0)
    b = traffic.schedule(_mix(), 2 ** 31 + 5, 10.0)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert (x.due_s, x.src_len, x.new_tokens, x.lang) == \
            (y.due_s, y.src_len, y.new_tokens, y.lang)
        assert np.array_equal(x.src, y.src)


def test_other_seed_same_work_other_order():
    a = _window(traffic.schedule(_mix(), 1, 10.0))
    b = _window(traffic.schedule(_mix(), 2, 10.0))
    assert len(a) == len(b) == 120            # rate x seconds
    key = lambda it: (it.src_len, it.new_tokens)          # noqa: E731
    assert collections.Counter(map(key, a)) == \
        collections.Counter(map(key, b))
    assert [key(it) for it in a] != [key(it) for it in b]
    assert [it.due_s for it in a] != [it.due_s for it in b]
    gaps_a = sorted(np.diff([it.due_s for it in a]))
    gaps_b = sorted(np.diff([it.due_s for it in b]))
    # the same gaps, less the one each order leaves at the window's end
    assert len(set(np.round(gaps_a, 9)) & set(np.round(gaps_b, 9))) \
        >= len(gaps_a) - 1


def test_window_and_warm_up():
    items = traffic.schedule(_mix(), 3, 10.0)
    due = [it.due_s for it in items]
    assert due == sorted(due) and due[0] >= 0.0
    win = _window(items)
    assert win[0].due_s == pytest.approx(3.0)
    assert all(3.0 <= it.due_s < 13.0 for it in win)
    before = [it for it in items if it.due_s < 3.0]
    assert before and not any(it.in_window for it in before)
    assert due[-1] >= 13.0 + 20.0 - 10.0      # arrivals go on past the close


def test_lengths():
    lengths = set(traffic.src_lengths(_mix(), 10.0))
    for it in traffic.schedule(_mix(), 4, 10.0):
        assert 4 <= it.src_len <= 256 and it.src_len in lengths
        assert it.src.shape == (it.src_len,) and it.src.dtype == np.int32
        assert 4 <= it.src.min() and it.src.max() < 256001
        assert 256001 <= it.lang < 256203
        assert 4 <= it.new_tokens <= 256
        assert it.new_tokens <= round(it.src_len * 1.3) + 1
        assert it.new_tokens >= min(round(it.src_len * 0.8) - 1, 4)
    # sent as drawn: far more lengths than any set of buckets
    assert len(lengths) > 20


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 11])
@pytest.mark.parametrize("kind", ["poisson", "backlog"])
def test_no_two_neighbours_share_a_length(seed, kind):
    arr = ({"kind": "poisson", "rate_per_s": 30.0} if kind == "poisson"
           else {"kind": "backlog", "pool_size": 64, "queued_min": 8})
    items = traffic.schedule(_mix(arrival=arr), seed, 10.0)
    lens = [it.src_len for it in itertools.islice(items, 300)]
    assert len(lens) == 300
    assert all(a != b for a, b in zip(lens, lens[1:]))


def test_backlog():
    mix = _mix(arrival={"kind": "backlog", "pool_size": 50,
                        "queued_min": 8})
    items = list(itertools.islice(traffic.schedule(mix, 5, 10.0), 200))
    assert all(it.due_s == 0.0 and not it.in_window for it in items)
    # epochs of the one pool, each in another order, without end
    key = lambda it: (it.src_len, it.new_tokens)          # noqa: E731
    epochs = [items[a:a + 50] for a in range(0, 200, 50)]
    assert all(collections.Counter(map(key, e))
               == collections.Counter(map(key, epochs[0])) for e in epochs)
    assert [key(it) for it in epochs[0]] != [key(it) for it in epochs[1]]
    assert traffic.src_lengths(mix, 10.0) == \
        sorted({it.src_len for it in items})
