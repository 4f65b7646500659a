"""The traffic generator's schedules and samplers, and the key generators."""

import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench import generator as gen
from bench.harness import ROOT, load_module


def test_poisson_due_same_arrivals_every_seed():
    a = gen.poisson_due(1000.0, 2.0, np.random.default_rng(1))
    b = gen.poisson_due(1000.0, 2.0, np.random.default_rng(2))
    assert a.size == b.size == 2000
    for d in (a, b):
        assert d[0] > 0.0 and np.all(np.diff(d) >= 0) and d[-1] < 2.0
    # the same multiset of gaps, in another order
    ga, gb = np.diff(a, prepend=0.0), np.diff(b, prepend=0.0)
    np.testing.assert_allclose(np.sort(ga), np.sort(gb), rtol=1e-9)
    assert not np.allclose(ga, gb)


def test_poisson_due_gaps_are_exponential():
    d = gen.poisson_due(5000.0, 4.0, np.random.default_rng(3))
    gaps = np.diff(d, prepend=0.0)
    assert gaps.mean() == pytest.approx(1 / 5000.0, rel=0.01)
    # exponential: the standard deviation equals the mean
    assert gaps.std() == pytest.approx(gaps.mean(), rel=0.05)


def test_zipfian_matches_its_pmf():
    n, theta = 1000, 0.99
    z = gen.Zipfian(n, theta)
    r = z.ranks(np.random.default_rng(4), 400_000)
    assert r.min() >= 0 and r.max() < n
    pmf = 1.0 / np.arange(1, n + 1) ** theta
    pmf /= pmf.sum()
    freq = np.bincount(r, minlength=n) / r.size
    # the two head ranks are exact in Gray et al.'s sampler
    np.testing.assert_allclose(freq[:2], pmf[:2], rtol=0.03)
    assert freq[:10].sum() == pytest.approx(pmf[:10].sum(), rel=0.05)
    assert z.zetan == pytest.approx(np.sum(1.0 / np.arange(1, n + 1)
                                           ** theta))


class _Queue:
    def __init__(self):
        self.keys = []
        self.stats = {"coalesced_lookups": 0}

    def submit_lookup(self, k):
        self.keys.append(float(k[0]))
        return len(self.keys) - 1


def test_latest_reads_only_acknowledged_keys():
    order = np.arange(100, dtype=np.float64) + 1000.0
    loader = gen.Loader(order[60:], np.arange(40), 10, order, 60)
    ranks = gen.Zipfian(60, 0.99).ranks(np.random.default_rng(5), 500)
    reader = gen.OpenReader(np.zeros(500),
                            lambda i: order[loader.n_acked - 1 - ranks[i]])
    q = _Queue()
    reader.run(q, 0.0, 0)
    assert reader.n_submitted == 500
    assert set(q.keys) <= set(order[:60])
    # most reads go to the newest acknowledged keys
    assert np.mean(np.array(q.keys) >= order[50]) > 0.5
    loader.n_acked = 80
    reader = gen.OpenReader(np.zeros(500),
                            lambda i: order[loader.n_acked - 1 - ranks[i]])
    q = _Queue()
    reader.run(q, 0.0, 0)
    assert max(q.keys) == order[79]


class _AnsweringQueue:
    """Answers every lookup at once; notes how many tickets each thread
    holds unanswered at a time."""

    def __init__(self, epoch=7):
        self.lock = threading.Lock()
        self.epoch = epoch
        self.keys = {}
        self.held = {}
        self.most_held = 0

    def submit_lookup(self, k):
        with self.lock:
            t = len(self.keys)
            self.keys[t] = float(k[0])
            me = threading.get_ident()
            self.held[me] = self.held.get(me, 0) + 1
            self.most_held = max(self.most_held, self.held[me])
            return t

    def result(self, t):
        with self.lock:
            self.held[threading.get_ident()] -= 1
            k = self.keys[t]
        return SimpleNamespace(payloads=[int(k)], found=[True],
                               epoch=self.epoch)


def test_closed_readers_wait_for_each_answer_and_read_acknowledged_keys():
    order = np.arange(100, dtype=np.float64) + 1000.0
    loader = gen.Loader(order[60:], np.arange(40), 10, order, 60)
    ranks = gen.Zipfian(60, 0.99).ranks(np.random.default_rng(6), 3 * 64)
    readers = gen.ClosedReaders(
        3, lambda c, j: order[loader.n_acked - 1 - ranks[c * 64 + j % 64]])
    q = _AnsweringQueue()
    t0 = time.perf_counter()
    readers.run(q, t0, t0 + 0.05)
    recs = readers.records()
    assert all(len(r) > 0 for r in readers.reads)
    assert len(recs) == len(q.keys) == sum(len(r) for r in readers.reads)
    assert q.most_held == 1          # one read in flight per client
    sent = [r[1] for r in recs]
    assert sent == sorted(sent) and sent[0] >= t0
    assert all(r[1] <= r[2] for r in recs)
    assert {r[0] for r in recs} <= set(order[:60])
    assert all(r[3][0][0] == int(r[0]) and r[3][2] == 7 for r in recs)


def test_draw_gives_sorted_stored_keys_and_disjoint_extras():
    s = load_module(ROOT / "bench" / "keys" / "uniform.py").sample
    stored, extra = gen.draw(lambda r, n: s(r, n, bits=13), 3000, 1000,
                             np.random.default_rng(6))
    assert stored.size == 3000 and np.all(np.diff(stored) > 0)
    assert extra.size == 1000 and np.unique(extra).size == 1000
    assert not np.intersect1d(stored, extra).size
    assert max(stored.max(), extra.max()) < 8192
    assert not np.all(np.diff(extra) > 0)   # extras come in random order


@pytest.mark.parametrize("n", [1 << 12, 3000, 4099])
def test_ordinals_are_a_bijection(n):
    o = gen.Ordinals(n, np.random.default_rng(n))
    pos = o.position(np.arange(n))
    assert np.array_equal(np.sort(pos), np.arange(n))
    assert np.array_equal(o.ordinal(pos), np.arange(n))


def test_lognormal_keys_distinct_and_below_2_48():
    s = load_module(ROOT / "bench" / "keys" / "lognormal.py").sample
    k, extra = gen.draw(lambda r, n: s(r, n, mu=0.0, sigma=2.0, scale=1e9),
                        1 << 20, 1 << 18, np.random.default_rng(7))
    assert k.size == 1 << 20 and np.unique(k).size == k.size
    assert not np.intersect1d(k, extra).size
    assert k.max() < 2.0 ** 48 and k.min() >= 0
    assert np.all(k == np.floor(k))
    # median of floor(1e9 X), X ~ lognormal(0, 2), is about 1e9
    assert 0.9e9 < np.median(k) < 1.1e9
