"""The one traffic generator.

A traffic mix is a data file, ``bench/traffic/<name>.json``, with up to
four sections, each run by its own client threads against the queue:

* ``open``: single-key lookups on an open loop.  Arrivals are Poisson at
  ``rate_per_s``; keys follow ``keys``: ``scrambled_zipfian`` (YCSB's
  request distribution over the stored records, constant ``theta``) or
  ``latest`` (YCSB's: Zipfian over recency among acknowledged keys).
* ``closed``: ``clients`` closed-loop readers of single keys, each
  waiting for its answer before it sends the next; keys as in ``open``.
* ``bulk``: one closed-loop client sending batches of ``batch_keys``
  keys, a ``present_share`` of them stored, the rest absent.
* ``load``: one closed-loop client inserting batches of ``batch_keys``
  keys from the configuration's insert pool, each acknowledged (ingest
  and publish) before the next is sent.

Everything random is drawn in set-up from the seed.  The open loop's
arrivals are the same multiset of gaps for every seed, in another order.
"""

from __future__ import annotations

import math
import threading
import time

import numpy as np


# ---------------------------------------------------------------------------
# keys


def draw(sample, n: int, n_extra: int, rng: np.random.Generator) -> tuple:
    """``n + n_extra`` distinct keys from ``sample(rng, size)``: the ``n``
    stored ones sorted, and ``n_extra`` more in random order."""
    keys = np.unique(sample(rng, n + n_extra + (n + n_extra) // 64 + 64))
    while keys.size < n + n_extra:
        keys = np.union1d(keys, sample(rng, n + n_extra - keys.size + 64))
    # drop the surplus and pick the extras in one draw without replacement
    idx = rng.choice(keys.size, keys.size - n, replace=False)
    stored = np.ones(keys.size, bool)
    stored[idx] = False
    return keys[stored], keys[idx[keys.size - n - n_extra:]]


class Ordinals:
    """Record ordinal r <-> sorted position (a r + b) mod n, an affine
    bijection drawn from the seed: the insertion order of the stored
    records, scattered over the key space as YCSB's hashed insert order
    scatters it."""

    def __init__(self, n: int, rng: np.random.Generator):
        self.n = int(n)
        a = int(rng.integers(1, max(2, self.n))) | 1
        while math.gcd(a, self.n) != 1:
            a += 2
        self.a, self.b = a, int(rng.integers(0, self.n))
        self.a_inv = pow(a, -1, self.n)

    def position(self, r):
        """Sorted position of ordinal ``r``."""
        return (np.asarray(r, np.int64) * self.a + self.b) % self.n

    def ordinal(self, j):
        """Ordinal of the record at sorted position ``j``."""
        return ((np.asarray(j, np.int64) - self.b) % self.n
                * self.a_inv) % self.n


class Zipfian:
    """YCSB's ZipfianGenerator (Gray et al., SIGMOD 1994): ranks in
    [0, n) with P(rank i) proportional to 1/(i+1)^theta."""

    def __init__(self, n: int, theta: float):
        self.n, self.theta = int(n), float(theta)
        zetan, step = 0.0, 1 << 22
        for a in range(1, self.n + 1, step):
            i = np.arange(a, min(a + step, self.n + 1), dtype=np.float64)
            zetan += float(np.sum(i ** -self.theta))
        self.zetan = zetan
        self.half_pow = 0.5 ** self.theta
        zeta2 = 1.0 + self.half_pow
        self.alpha = 1.0 / (1.0 - self.theta)
        self.eta = ((1.0 - (2.0 / self.n) ** (1.0 - self.theta))
                    / (1.0 - zeta2 / zetan))

    def ranks(self, rng: np.random.Generator, size: int) -> np.ndarray:
        u = rng.random(size)
        uz = u * self.zetan
        r = np.floor(self.n * (self.eta * u - self.eta + 1.0) ** self.alpha)
        r = np.where(uz < 1.0 + self.half_pow, 1.0, r)
        r = np.where(uz < 1.0, 0.0, r)
        return np.clip(r, 0, self.n - 1).astype(np.int64)


def poisson_due(rate: float, seconds: float, rng: np.random.Generator
                ) -> np.ndarray:
    """Due times in (0, seconds) of round(rate * seconds) requests: the
    gaps from 0 are the exponential distribution's quantiles at
    (i + 0.5)/n, in an order drawn from ``rng``, scaled to span the
    window."""
    n = max(1, int(round(rate * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u))
    return np.cumsum(gaps) * (seconds * (1.0 - 0.5 / n) / gaps.sum())


# ---------------------------------------------------------------------------
# clients


class OpenReader:
    """Single-key lookups on an open loop (see module doc).  ``key_of``
    maps request ``i`` to its key at submit time.  The reader takes the
    answers that have resolved as its clients would: up to two after each
    submit, and all of them while ahead of its schedule; ``drain`` takes
    the rest after the window."""

    def __init__(self, due: np.ndarray, key_of):
        self.due = due
        self.key_of = key_of
        n = due.size
        self.keys = np.zeros(n, np.float64)
        self.submit = np.zeros(n, np.float64)
        self.tickets = np.zeros(n, np.int64)
        self.n_submitted = 0
        self.payloads = np.full(n, -1, np.int64)
        self.found = np.zeros(n, bool)
        self.epoch = np.full(n, -1, np.int64)
        self.answered = np.zeros(n, bool)
        self.n_collected = 0

    def run(self, queue, t0: float, base: int) -> None:
        due = self.due + t0
        buf = self.keys
        for i in range(due.size):
            if due[i] - time.perf_counter() > 1e-3:
                self.drain(queue, base, until=due[i] - 5e-4)
            wait = due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            buf[i] = self.key_of(i)
            self.submit[i] = time.perf_counter()
            self.tickets[i] = queue.submit_lookup(buf[i:i + 1])
            self.n_submitted = i + 1
            self.drain(queue, base, most=2)

    def drain(self, queue, base: int, until: float = math.inf,
              most: int | None = None) -> None:
        """Take the answers of the requests the queue has served (request
        ``i`` is served once its coalesced lookup count passes ``base +
        i``; taking a resolved ticket never flushes): at most ``most`` of
        them, and none more once past ``until``."""
        done = min(self.n_submitted, queue.stats["coalesced_lookups"] - base)
        i = self.n_collected
        if most is not None:
            done = min(done, i + most)
        while i < done:
            a = _answer(queue.result(int(self.tickets[i])))
            if a is not None:
                self.payloads[i], self.found[i] = a[0][0], a[1][0]
                self.epoch[i], self.answered[i] = a[2], True
            i += 1
            if i % 64 == 0 and time.perf_counter() > until:
                break
        self.n_collected = i


class ClosedReaders:
    """``clients`` closed-loop readers, as YCSB's client threads are: each
    sends one single-key lookup, waits for its answer (``result``, which
    flushes the queue if the ticket is still pending), then sends the
    next.  ``key_of(c, j)`` maps client ``c``'s ``j``-th read to its key
    at send time.  Each read records ``(key, sent, answered, answer)``."""

    def __init__(self, clients: int, key_of):
        self.clients = int(clients)
        self.key_of = key_of
        self.reads: list = [[] for _ in range(self.clients)]

    def run(self, queue, t0: float, t1: float) -> None:
        threads = [start(self._client, c, queue, t0, t1)
                   for c in range(self.clients)]
        for th in threads:
            th.join()

    def _client(self, c: int, queue, t0: float, t1: float) -> None:
        out = self.reads[c]
        _wait_until(t0)
        while time.perf_counter() < t1:
            key = float(self.key_of(c, len(out)))
            ts = time.perf_counter()
            res = queue.result(queue.submit_lookup(np.array([key])))
            out.append((key, ts, time.perf_counter(), _answer(res)))

    def records(self) -> list:
        """Every read of every client, in the order they were sent."""
        return sorted((r for rs in self.reads for r in rs),
                      key=lambda r: r[1])


class BulkClient:
    """One closed-loop client cycling through a pool of lookup batches."""

    def __init__(self, pool: list):
        self.pool = pool
        self.sent: list = []      # (pool index, submit time, done time)
        self.answers: list = []   # (payloads, found, epoch)

    def run(self, queue, t0: float, t1: float) -> None:
        _wait_until(t0)
        j = 0
        while time.perf_counter() < t1:
            q = self.pool[j % len(self.pool)]
            ts = time.perf_counter()
            res = queue.result(queue.submit_lookup(q))
            self.sent.append((j % len(self.pool), ts, time.perf_counter()))
            self.answers.append(_answer(res))
            j += 1


class Loader:
    """One closed-loop client inserting the pool batch by batch; each
    acknowledged batch extends ``order`` (all keys in insertion order)
    by moving ``n_acked``."""

    def __init__(self, pool_keys, pool_pays, batch: int, order, n_acked: int):
        self.pool_keys, self.pool_pays = pool_keys, pool_pays
        self.batch = int(batch)
        self.order = order
        self.n_acked = n_acked
        self.next = 0             # pool offset of the next batch
        self.batches: list = []   # (offset, n, submit, ack, report)

    def send(self, queue) -> bool:
        """Insert one batch and wait for its acknowledgement; False when
        the pool holds no whole batch more."""
        a = self.next
        if a + self.batch > self.pool_keys.size:
            return False
        ks = self.pool_keys[a:a + self.batch]
        ps = self.pool_pays[a:a + self.batch]
        ts = time.perf_counter()
        rep = queue.result(queue.submit_ingest(ks, ps))
        ta = time.perf_counter()
        self.next = a + self.batch
        self.batches.append((a, self.batch, ts, ta, rep))
        self.n_acked += self.batch
        return True

    def run(self, queue, t0: float, t1: float) -> None:
        _wait_until(t0)
        while time.perf_counter() < t1 and self.send(queue):
            pass


def _wait_until(t: float) -> None:
    wait = t - time.perf_counter()
    if wait > 0:
        time.sleep(wait)


def _answer(res) -> tuple:
    if not res:  # an Overloaded marker: shed, no answer
        return None
    return (np.asarray(res.payloads, np.int64), np.asarray(res.found, bool),
            int(res.epoch))


def start(target, *args) -> threading.Thread:
    th = threading.Thread(target=target, args=args, daemon=True)
    th.start()
    return th
