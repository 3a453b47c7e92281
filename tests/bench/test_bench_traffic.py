"""The benchmark's traffic generator: deterministic in the seed, the
same work for every seed, lengths and gaps on the mix's distribution."""
import itertools
import json
import statistics

import numpy as np
import pytest

from benchkit import REPO
from bench.lib.traffic import Traffic, gaps, lengths


def _mix(name):
    return json.loads((REPO / "bench/traffic" / f"{name}.json").read_text())


def _take(tr, n):
    return list(itertools.islice(iter(tr), n))


def _window(tr, seconds):
    out = []
    for s in tr:
        if s.due >= seconds:
            return out
        out.append(s)


@pytest.mark.parametrize("mix,rate", [("chat-long", 2.0),
                                      ("offline-short", 0.0)])
def test_equal_seeds_give_equal_schedules(mix, rate):
    def draw(seed):
        return _take(Traffic(_mix(mix), seed=seed, vocab=151936, seconds=30,
                             rate=rate, warm=20.0), 300)
    a, b, c = draw(2**40 + 7), draw(2**40 + 7), draw(2**40 + 8)
    key = [(s.rid, s.due, s.max_new, s.prompt.tobytes()) for s in a]
    assert key == [(s.rid, s.due, s.max_new, s.prompt.tobytes()) for s in b]
    assert key != [(s.rid, s.due, s.max_new, s.prompt.tobytes()) for s in c]
    assert all(0 <= s.prompt.min() and s.prompt.max() < 151936 for s in a)


def test_every_seed_puts_the_same_work_in_the_window():
    mix, T, rate = _mix("chat-long"), 30.0, 2.4
    wins = [_window(Traffic(mix, seed=s, vocab=1000, seconds=T, rate=rate), T)
            for s in (1, 2**33 + 5, 987654321012)]
    n = round(rate * T)
    for w in wins:
        assert len(w) == n
        assert w[0].due == 0.0
        assert all(a.due <= b.due for a, b in zip(w, w[1:]))
    ref = wins[0]
    for w in wins[1:]:
        assert sorted(len(s.prompt) for s in w) == \
            sorted(len(s.prompt) for s in ref)
        assert sorted(s.max_new for s in w) == sorted(s.max_new for s in ref)
        g = np.diff([s.due for s in w] + [T])
        g0 = np.diff([s.due for s in ref] + [T])
        np.testing.assert_allclose(np.sort(g), np.sort(g0), rtol=1e-9)
        assert [len(s.prompt) for s in w] != [len(s.prompt) for s in ref]


@pytest.mark.parametrize("key", ["prompt", "output"])
def test_lengths_follow_the_mix(key):
    d = _mix("chat-long")[key]
    x = lengths(d, 2000)
    assert x.min() >= d["min"] and x.max() <= d["max"]
    assert abs(np.median(x) - d["median"]) <= 1
    # the quartiles of a lognormal: median * exp(sigma * z_q)
    z = statistics.NormalDist().inv_cdf(0.75)
    for q, zq in ((25, -z), (75, z)):
        want = min(max(d["median"] * np.exp(d["sigma"] * zq), d["min"]),
                   d["max"])
        assert abs(np.percentile(x, q) - want) / want < 0.01


def test_the_ramp_comes_before_the_window_and_leaves_it_alone():
    mix, T, rate, warm = _mix("chat-long"), 30.0, 2.4, 75.0
    tr = Traffic(mix, seed=2**35 + 1, vocab=1000, seconds=T, rate=rate,
                 warm=warm)
    specs = _take(tr, 400)
    ramp = [s for s in specs if s.due < 0]
    assert len(ramp) == round(rate * warm)
    assert ramp[0].due == -warm and specs[:len(ramp)] == ramp
    win = [s for s in specs if 0 <= s.due < T]
    bare = _window(Traffic(mix, seed=2**35 + 1, vocab=1000, seconds=T,
                           rate=rate), T)
    assert len(win) == len(bare) == round(rate * T)
    assert [(s.due, s.max_new, len(s.prompt)) for s in win] == \
        [(s.due, s.max_new, len(s.prompt)) for s in bare]
    assert len({s.rid for s in specs}) == len(specs)


def test_other_arrival_processes_are_refused():
    with pytest.raises(ValueError, match="arrival process"):
        Traffic({"arrivals": {"process": "gamma"}}, seed=1, vocab=10,
                seconds=1, rate=1.0)


def test_poisson_gaps_fill_the_window_at_the_rate():
    g = gaps(2.0, 1000, 500.0)
    assert g.sum() == pytest.approx(500.0)
    assert np.mean(g) == pytest.approx(0.5)
    # exponential: the coefficient of variation is about 1
    assert np.std(g) / np.mean(g) == pytest.approx(1.0, abs=0.05)


def test_offline_stream_comes_in_blocks_of_the_same_lengths():
    mix = _mix("offline-short")
    tr = Traffic(mix, seed=3, vocab=1000, seconds=30)
    specs = _take(tr, 2 * mix["block"])
    assert all(s.due is None for s in specs)
    b0, b1 = specs[:mix["block"]], specs[mix["block"]:]
    assert sorted(len(s.prompt) for s in b0) == \
        sorted(len(s.prompt) for s in b1)
    assert len({s.rid for s in specs}) == len(specs)
