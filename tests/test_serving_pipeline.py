"""Snapshot-isolated epoch pipelining (ISSUE 8 tentpole): pinned
snapshots bit-identical to the quiesced index under concurrent ingest,
COW correctness, epoch tagging, refcounts, admission control, and the
fused-ingest split commit."""

import threading
import time

import numpy as np
import pytest

from repro.core import Index, Overloaded
from repro.robustness import (FaultInjector, InjectedCrash, InjectedFault,
                              InvariantAuditor)
from repro.serving import EpochPipeline, MicroBatchQueue, pin_index
from repro.serving.engine import ServingEngine  # noqa: F401 (import path)


def _mk_index(n=20_000, seed=0, wide=False, **kw):
    rng = np.random.default_rng(seed)
    # wide: beyond f32 exactness (2^24) but inside the device pair-exact
    # range (integer keys < 2^48 after the *2 even-grid scaling)
    hi = 2 ** 46 if wide else 2 ** 21
    keys = np.unique(rng.choice(hi, n, replace=False)).astype(np.float64)
    keys *= 2.0  # even grid: every midpoint is a representable fresh key
    kw.setdefault("method", "pgm")
    kw.setdefault("eps", 64)
    kw.setdefault("gap_rho", 0.2)
    return Index.build(keys, **kw), keys


def _fresh(keys, n):
    mids = np.setdiff1d(keys[:-1] + np.rint(np.diff(keys) * 0.5), keys)
    assert mids.size >= n
    return mids[:n]


# ---------------------------------------------------------------------------
# snapshot isolation: bit-identity to the quiesced index


@pytest.mark.parametrize("wide", [False, True])
def test_snapshot_bit_identical_under_ingest(wide):
    """A pinned snapshot's answers NEVER move while the live index
    ingests / deletes / updates — and equal the quiesced lookup at the
    pinned epoch bit-for-bit (payloads, slots, found)."""
    idx, keys = _mk_index(wide=wide)
    rng = np.random.default_rng(1)
    q = np.concatenate([rng.choice(keys, 1_500),
                        rng.choice(keys, 300) + 1.0,
                        [keys[0] - 4.0, keys[-1] + 4.0]])
    quiesced = idx.lookup(q)
    pipe = EpochPipeline(idx)
    epoch0 = pipe.epoch

    batches = np.array_split(_fresh(keys, 3_000), 4)
    for i, b in enumerate(batches):
        pipe.ingest(b, (50_000 + np.arange(b.size) + i).astype(np.int64))
        got = pipe.lookup(q)
        assert got.epoch == epoch0
        assert got.backend == "snapshot"
        np.testing.assert_array_equal(got.payloads, quiesced.payloads)
        np.testing.assert_array_equal(got.found, quiesced.found)
        # miss-row slots are backend-advisory (host oracle clamps to 0
        # where the device reports -1 — pre-existing convention); hit
        # rows must agree exactly
        hit = np.asarray(quiesced.found)
        np.testing.assert_array_equal(np.asarray(got.slots)[hit],
                                      np.asarray(quiesced.slots)[hit])
    # delete + update on the live side: still invisible at epoch 0
    idx.delete(float(keys[10]))
    idx.update(float(keys[11]), 999_999)
    got = pipe.lookup(q)
    np.testing.assert_array_equal(got.payloads, quiesced.payloads)

    # publish: the new epoch serves every applied write, quiesced path
    pipe.publish()
    assert pipe.epoch == pipe.live_epoch > epoch0
    allb = np.concatenate(batches)
    res = pipe.lookup(allb)
    assert res.found.all()
    assert not pipe.lookup(np.array([float(keys[10])])).found.any()
    pipe.close()


def test_snapshot_equals_quiesced_after_forced_refreeze():
    """Epoch-N pin survives a full device refreeze of the live index
    (the heaviest mutation path: arrays wholly rebuilt)."""
    idx, keys = _mk_index(n=8_000)
    pipe = EpochPipeline(idx)
    q = keys[::7]
    want = pipe.lookup(q)
    big = _fresh(keys, 4_000)
    pipe.ingest(big, np.arange(big.size, dtype=np.int64))
    idx._sync_device(prefer_delta=False)  # force refreeze under the pin
    got = pipe.lookup(q)
    np.testing.assert_array_equal(got.payloads, want.payloads)
    np.testing.assert_array_equal(got.found, want.found)
    pipe.close()


def test_sharded_snapshot_isolation_and_forced_split():
    """ShardedIndex snapshots pin the router topology too: answers stay
    bit-identical across concurrent ingest AND a forced shard split
    (which rewrites boundaries and slot bases live)."""
    idx, keys = _mk_index(n=24_000, shards=3)
    rng = np.random.default_rng(2)
    q = np.concatenate([rng.choice(keys, 2_000),
                        rng.choice(keys, 400) + 1.0])
    quiesced = idx.lookup(q)
    pipe = EpochPipeline(idx)
    epoch0 = pipe.epoch

    b = _fresh(keys, 2_000)
    pipe.ingest(b, (70_000 + np.arange(b.size)).astype(np.int64))
    idx.maybe_rebalance(force_shard=1)  # topology change under the pin
    got = pipe.lookup(q)
    assert got.epoch == epoch0 and got.backend == "snapshot"
    np.testing.assert_array_equal(got.payloads, quiesced.payloads)
    np.testing.assert_array_equal(got.found, quiesced.found)
    hit = np.asarray(quiesced.found)
    np.testing.assert_array_equal(np.asarray(got.slots)[hit],
                                  np.asarray(quiesced.slots)[hit])

    pipe.publish()
    res = pipe.lookup(b)
    assert res.found.all()
    np.testing.assert_array_equal(
        res.payloads, 70_000 + np.arange(b.size))
    pipe.close()


def test_concurrent_reader_thread_sees_one_epoch_per_call():
    """Hammer lookups from a reader thread while the main thread ingests
    and publishes: every result is internally consistent with the epoch
    it reports (fresh keys of epoch E are all-found iff served epoch >=
    E's publish)."""
    idx, keys = _mk_index(n=10_000)
    pipe = EpochPipeline(idx)
    b = _fresh(keys, 1_024)
    errors = []
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            res = pipe.lookup(b)
            nf = int(res.found.sum())
            # all-or-nothing: the batch publishes atomically, so a
            # partial found-count means a torn epoch was observed
            if nf not in (0, b.size):
                errors.append(f"torn epoch: {nf}/{b.size} found at "
                              f"epoch {res.epoch}")

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    time.sleep(0.02)
    pipe.ingest(b, np.arange(b.size, dtype=np.int64))
    time.sleep(0.02)
    pipe.publish()
    time.sleep(0.02)
    stop.set()
    t.join(timeout=5)
    assert not errors, errors
    pipe.close()


# ---------------------------------------------------------------------------
# pin refcounts + COW mechanics at the GappedArray level


def test_pin_refcount_and_cow_detach():
    idx, keys = _mk_index(n=4_000)
    ga = idx.gapped
    s1 = ga.pin_snapshot()
    s2 = ga.pin_snapshot()
    assert s1.pinned and s2.pinned
    base = s1.lookup_batch(keys[:64])
    # first post-pin mutation pays the COW once and detaches the cell
    idx.insert(float(keys[0] + 1.0), 1)
    assert ga._pins is None
    np.testing.assert_array_equal(s1.lookup_batch(keys[:64]), base)
    np.testing.assert_array_equal(s2.lookup_batch(keys[:64]), base)
    s1.release()
    assert not s1.pinned and s2.pinned  # shared cell: s2 still live
    s2.release()
    assert not s2.pinned
    # releasing twice is a no-op, not an underflow
    s2.release()
    aud = InvariantAuditor()
    aud.assert_ok(idx)


def test_pipeline_publish_releases_old_pin():
    idx, keys = _mk_index(n=4_000)
    pipe = EpochPipeline(idx)
    old = pipe._snapshot
    b = _fresh(keys, 128)
    pipe.ingest(b, np.arange(128, dtype=np.int64))
    pipe.publish()
    assert not old._snap.pinned  # old epoch's pin released on swap
    assert pipe._snapshot._snap.pinned
    pipe.close()
    assert not pipe._snapshot._snap.pinned


def test_static_index_refuses_snapshot():
    rng = np.random.default_rng(0)
    keys = np.unique(rng.choice(2 ** 20, 2_000, replace=False)
                     ).astype(np.float64)
    idx = Index.build(keys, method="pgm", eps=64, gap_rho=0.0)
    with pytest.raises(ValueError, match="gapped"):
        pin_index(idx)


def test_auditor_catches_planted_corruption():
    idx, keys = _mk_index(n=4_000)
    aud = InvariantAuditor()
    aud.assert_ok(idx)
    idx.gapped.occupied[np.flatnonzero(idx.gapped.occupied)[0]] = False
    with pytest.raises(AssertionError, match="slot"):
        aud.assert_ok(idx)


# ---------------------------------------------------------------------------
# admission control (MicroBatchQueue, ISSUE 8 satellite)


def test_deadline_flush_fires_without_explicit_flush():
    idx, keys = _mk_index(n=4_000)
    q = MicroBatchQueue(idx, max_wait_ms=20)
    t = q.submit_lookup(keys[:4])
    deadline = time.monotonic() + 5.0
    while q.stats["deadline_flushes"] == 0:
        assert time.monotonic() < deadline, "deadline timer never fired"
        time.sleep(0.005)
    res = q.result(t)
    assert res.found.all()
    assert q.stats["deadline_flushes"] >= 1
    assert q.stats["flushes"] >= 1
    q.close()


def test_bounded_depth_sheds_with_typed_overloaded():
    idx, keys = _mk_index(n=4_000)
    q = MicroBatchQueue(idx, max_depth=2)
    t1 = q.submit_lookup(keys[:2])
    t2 = q.submit_ingest(_fresh(keys, 2), np.array([1, 2]))
    t3 = q.submit_lookup(keys[4:6])  # over the bound: shed
    shed = q.result(t3)
    assert isinstance(shed, Overloaded)
    assert not shed  # falsy: `if result:` skips shed tickets
    assert shed.kind == "lookup" and shed.depth == 2 == shed.max_depth
    assert q.stats["shed"] == 1
    # shed tickets resolve exactly once, like real ones
    with pytest.raises(KeyError, match="exactly once"):
        q.result(t3)
    q.flush()
    assert q.result(t1).found.all()
    assert q.result(t2).n == 2
    q.close()


def test_ingest_retry_absorbs_transient_abort():
    idx, keys = _mk_index(n=4_000)
    inj = FaultInjector({("ingest", 0): "abort"})
    q = MicroBatchQueue(idx, faults=inj, ingest_retries=2,
                        retry_backoff_ms=0.1)
    b = _fresh(keys, 8)
    t = q.submit_ingest(b, np.arange(8, dtype=np.int64))
    rep = q.result(t)
    assert rep.n == 8
    assert q.stats["ingest_retries"] == 1
    assert q.stats["host_fallbacks"] == 0
    assert inj.fired == [("ingest", 0, "abort")]
    assert idx.lookup(b).found.all()
    q.close()


def test_ingest_final_retry_falls_back_to_host_path():
    idx, keys = _mk_index(n=4_000)
    inj = FaultInjector({("ingest", 0): "abort", ("ingest", 1): "abort"})
    q = MicroBatchQueue(idx, faults=inj, ingest_retries=2,
                        retry_backoff_ms=0.1)
    prev = idx.fused_ingest_enabled
    b = _fresh(keys, 8)
    rep = q.result(q.submit_ingest(b, np.arange(8, dtype=np.int64)))
    assert rep.n == 8
    assert q.stats["ingest_retries"] == 2
    assert q.stats["host_fallbacks"] == 1
    assert idx.fused_ingest_enabled == prev  # restored after fallback
    q.close()


@pytest.mark.parametrize("error", ["RuntimeError", "JaxRuntimeError"])
def test_device_error_propagates_without_retry(error):
    """Only the harness's transient ``InjectedFault`` is retried: any
    other ``RuntimeError`` from the index (e.g. a ``JaxRuntimeError``
    from compiling or running on the device) propagates on its first
    attempt, never rerouted onto the host path."""
    import jax

    exc = {"RuntimeError": RuntimeError,
           "JaxRuntimeError": jax.errors.JaxRuntimeError}[error]
    assert issubclass(exc, RuntimeError)
    idx, keys = _mk_index(n=4_000)
    calls = []

    def failing_ingest(k, p):
        calls.append(int(k.shape[0]))
        raise exc("device program failed to compile")

    idx.ingest = failing_ingest
    q = MicroBatchQueue(idx, ingest_retries=2, retry_backoff_ms=0.1)
    t = q.submit_ingest(_fresh(keys, 8), np.arange(8, dtype=np.int64))
    with pytest.raises(exc, match="failed to compile"):
        q.result(t)
    assert calls == [8]
    assert q.stats["ingest_retries"] == 0
    assert q.stats["host_fallbacks"] == 0
    assert idx.fused_ingest_enabled is None  # never forced to the host
    q.close()

    # the transient harness fault is still retried, and then succeeds
    idx2, keys2 = _mk_index(n=4_000)
    inj = FaultInjector({("ingest", 0): "abort"})
    q2 = MicroBatchQueue(idx2, faults=inj, ingest_retries=2,
                         retry_backoff_ms=0.1)
    b = _fresh(keys2, 8)
    assert q2.result(q2.submit_ingest(b, np.arange(8, dtype=np.int64))).n == 8
    assert q2.stats["ingest_retries"] == 1
    assert q2.stats["host_fallbacks"] == 0
    q2.close()


def test_injected_crash_propagates_through_retry():
    idx, keys = _mk_index(n=4_000)
    inj = FaultInjector({("ingest", 0): "crash"})
    q = MicroBatchQueue(idx, faults=inj, ingest_retries=5)
    t = q.submit_ingest(_fresh(keys, 4), np.arange(4, dtype=np.int64))
    with pytest.raises(InjectedCrash):
        q.result(t)
    assert q.stats["ingest_retries"] == 0  # crash is not retried
    q.close()


def test_exhausted_retries_raise_last_error():
    idx, keys = _mk_index(n=4_000)
    inj = FaultInjector({("ingest", i): "abort" for i in range(4)})
    q = MicroBatchQueue(idx, faults=inj, ingest_retries=2,
                        retry_backoff_ms=0.1)
    t = q.submit_ingest(_fresh(keys, 4), np.arange(4, dtype=np.int64))
    with pytest.raises(InjectedFault, match="injected abort"):
        q.result(t)
    q.close()


def test_deadline_timer_error_surfaces_on_next_call():
    """An exception inside the timer-thread flush must not vanish into
    the daemon thread — it re-raises on the next queue call."""
    idx, keys = _mk_index(n=4_000)
    inj = FaultInjector({("flush", 0): "abort"})
    q = MicroBatchQueue(idx, max_wait_ms=10, faults=inj)
    q.submit_lookup(keys[:4])
    deadline = time.monotonic() + 5.0
    while q._async_error is None:
        assert time.monotonic() < deadline, "timer error never captured"
        time.sleep(0.005)
    with pytest.raises(InjectedFault, match="injected abort"):
        q.submit_lookup(keys[4:8])
    q.close()


def test_queue_over_pipeline_composes():
    """MicroBatchQueue aggregates over an EpochPipeline unchanged —
    coalesced ingest goes through the WAL-less pipeline, coalesced
    lookups serve the pinned epoch."""
    idx, keys = _mk_index(n=6_000)
    pipe = EpochPipeline(idx, publish_every=1)
    q = MicroBatchQueue(pipe)
    b = _fresh(keys, 16)
    t1 = q.submit_ingest(b[:8], np.arange(8, dtype=np.int64))
    t2 = q.submit_ingest(b[8:], 8 + np.arange(8, dtype=np.int64))
    t3 = q.submit_lookup(b)
    assert q.result(t3).found.all()  # ingests flush first, then publish
    assert q.result(t1).n == 16 and q.result(t2).n == 16  # shared report
    assert pipe.stats["publishes"] == 1
    pipe.close()


# ---------------------------------------------------------------------------
# fused-ingest split commit (ISSUE 8 satellite)


def test_split_commit_prefix_on_device_bit_identical():
    """A localized abort (one in-batch collision pair late in the batch)
    commits the clean prefix through a second fused dispatch and routes
    only the remainder through the host — final state bit-identical to
    sequential insert()."""
    import copy

    rng = np.random.default_rng(7)
    keys = np.unique(rng.choice(2 ** 21, 30_000, replace=False)
                     ).astype(np.float64) * 2.0
    idx = Index.build(keys, method="pgm", eps=64, gap_rho=0.2)
    idx.fused_ingest_enabled = True
    idx.sync_device()

    mids = np.setdiff1d(keys[:-1] + np.rint(np.diff(keys) * 0.5), keys)
    # spaced midpoints (one per gap region) so the batch carries NO
    # natural collision pair — the crafted late one below is the only
    # abort cause, keeping the clean prefix long
    batch = mids[:: max(1, mids.size // 1_024)][:1_024]
    prims = idx.gapped.placement_primitives(batch)
    free = np.asarray(prims["free"]) & np.asarray(prims["bracket"])
    late_free = np.flatnonzero(free)
    late_free = late_free[late_free >= 600]
    assert late_free.size, "need a late free placement to craft the abort"
    j = int(late_free[0])
    # a second key in slot j's gap run -> in-graph collision_group abort
    cand = batch[j] + 2.0
    assert cand < keys[np.searchsorted(keys, batch[j])]
    assert cand not in keys and cand not in batch
    batch = np.sort(np.append(batch, cand))
    pays = (90_000 + np.arange(batch.size)).astype(np.int64)

    ref = copy.deepcopy(idx)
    rep = idx.ingest(batch, pays)
    assert rep.placement == "device-split"
    assert rep.split_commits >= 1
    assert rep.device in ("fused+delta", "fused+refreeze", "fused+none")
    assert rep.n == batch.size

    for k, p in zip(batch, pays):
        ref.insert(float(k), int(p))
    ga, gb = idx.gapped, ref.gapped
    np.testing.assert_array_equal(ga.slot_key, gb.slot_key)
    np.testing.assert_array_equal(ga.occupied, gb.occupied)
    np.testing.assert_array_equal(ga.payload[ga.occupied],
                                  gb.payload[gb.occupied])
    np.testing.assert_array_equal(ga.lookup_batch(batch),
                                  gb.lookup_batch(batch))
    res = idx.lookup(batch)
    np.testing.assert_array_equal(res.payloads, pays)


def test_split_commit_disabled_falls_back_whole_batch():
    rng = np.random.default_rng(7)
    keys = np.unique(rng.choice(2 ** 21, 30_000, replace=False)
                     ).astype(np.float64) * 2.0
    idx = Index.build(keys, method="pgm", eps=64, gap_rho=0.2)
    idx.fused_ingest_enabled = True
    idx.fused_split_commit = False
    idx.sync_device()
    mids = np.setdiff1d(keys[:-1] + np.rint(np.diff(keys) * 0.5), keys)
    batch = mids[:: max(1, mids.size // 1_024)][:1_024]
    prims = idx.gapped.placement_primitives(batch)
    free = np.flatnonzero(np.asarray(prims["free"])
                          & np.asarray(prims["bracket"]))
    free = free[free >= 600]
    batch = np.sort(np.append(batch, batch[int(free[0])] + 2.0))
    rep = idx.ingest(batch, np.arange(batch.size, dtype=np.int64))
    assert rep.placement != "device-split"
    assert rep.split_commits == 0
    assert idx.lookup(batch).found.all()


# ---------------------------------------------------------------------------
# StepWatchdog close/join (ISSUE 8 satellite)


def test_step_watchdog_exception_exit_cancels_and_joins():
    from repro.train.fault import StepWatchdog

    fired = []
    with pytest.raises(RuntimeError, match="boom"):
        with StepWatchdog(0.05, on_timeout=lambda s, e: fired.append(s)) \
                as wd:
            wd.arm(3)
            raise RuntimeError("boom")
    assert wd._timer is None
    time.sleep(0.12)
    assert fired == []  # cancelled timer never fires after teardown

    wd2 = StepWatchdog(0.01, on_timeout=lambda s, e: fired.append(s))
    wd2.arm(5)
    time.sleep(0.05)
    wd2.close()
    assert fired == [5] and wd2.events[0]["step"] == 5
    # close() after the timer already fired joins cleanly (no hang)
    wd2.close()


# ---------------------------------------------------------------------------
# ISSUE 10 satellites: regressions for the concurrency fixes repro-lint /
# locksan surfaced, and the MDL-drift retrain daemon


def test_snapshot_refcount_survives_concurrent_publish():
    """Regression: ``publish()`` dropping the pipeline's reference to
    the old snapshot must NOT unpin it under a reader that retained it
    — the pin (and its copy-on-write protection) drops only when the
    last reference goes."""
    idx, keys = _mk_index(n=6_000)
    pipe = EpochPipeline(idx)
    snap = pipe._snapshot
    snap.retain()                      # in-flight reader
    pre = snap.lookup(keys[:64])
    try:
        pipe.ingest(_fresh(keys, 64), np.arange(64, dtype=np.int64))
        pipe.publish()                 # pipeline drops its old-pin ref
        assert snap._snap.pinned       # reader's retain keeps it alive
        mid = snap.lookup(keys[:64])   # still the frozen epoch, exact
        np.testing.assert_array_equal(np.asarray(pre.payloads),
                                      np.asarray(mid.payloads))
        assert mid.epoch == pre.epoch
    finally:
        snap.release()
    assert not snap._snap.pinned       # last ref gone -> unpinned
    with pytest.raises(RuntimeError):
        snap.retain()                  # a released snapshot stays dead
    pipe.close()


def test_pipeline_stats_consistent_under_concurrent_readers():
    """Regression: ``stats`` / ``lag`` reads raced ingest before the
    pipeline lock — counters now reconcile exactly against the calls
    issued, with reader threads hammering lookup()+lag the whole
    time."""
    idx, keys = _mk_index(n=6_000)
    pipe = EpochPipeline(idx, publish_every=3)
    fresh = _fresh(keys, 256)
    errors, counts = [], []
    stop = threading.Event()

    def reader():
        n = 0
        try:
            while not stop.is_set():
                res = pipe.lookup(keys[:16])
                assert res.epoch <= pipe.live_epoch
                assert pipe.lag >= 0
                n += 1
        except Exception as e:      # noqa: BLE001 - surfaced below
            errors.append(e)
        counts.append(n)

    threads = [threading.Thread(target=reader) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for i in range(8):
            pipe.ingest(fresh[i * 32: (i + 1) * 32],
                        (50_000 + np.arange(32) + i * 32).astype(np.int64))
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not errors, errors
    s = pipe.stats
    assert s["snapshot_lookups"] + s["live_lookups"] == sum(counts)
    assert s["ingests"] == 8 and s["publishes"] == 8 // 3
    assert pipe.lag == 8 % 3           # un-published tail, exact
    pipe.close()


def test_mdl_drift_retrain_trigger_fires_and_resets_baseline():
    """The PR-9-residual closer: out-of-domain tail appends grow
    ``Index.mdl()`` (keys chain past the trained domain); the pipeline
    daemon sees the growth at publish, retrains, and resets its
    baseline so a quiesced workload never re-fires."""
    rng = np.random.default_rng(3)
    keys = np.unique(rng.choice(2 ** 21, 20_000, replace=False)
                     ).astype(np.float64) * 2.0
    idx = Index.build(keys, method="pgm", eps=64, gap_rho=0.15)
    pipe = EpochPipeline(idx, retrain_mdl_drift=0.02)
    base0 = pipe._mdl_baseline
    assert base0 is not None
    step = float(np.mean(np.diff(keys)))
    tail = keys[-1] + step * 10.0 * (1.0 + np.arange(800))
    tail = np.rint(tail) * 2.0         # stay on the even grid
    fired_at = None
    for i in range(4):
        pipe.ingest(tail[i * 200: (i + 1) * 200],
                    (1_000_000 + np.arange(200) + i * 200).astype(np.int64))
        pipe.publish()
        if pipe.stats["mdl_retrains"]:
            fired_at = i
            break
    assert fired_at is not None, "drift never crossed the threshold"
    assert pipe.stats["mdl_checks"] == fired_at + 1
    assert pipe.stats["retrains"] >= 1          # the real retrain ran
    # baseline reset to the post-retrain score: quiesced -> no re-fire
    assert pipe._mdl_baseline == pytest.approx(pipe._mdl_score())
    pipe.publish()                              # serve the retrained epoch
    got = pipe.lookup(np.concatenate([keys[:200],
                                      tail[:(fired_at + 1) * 200]]))
    assert got.found.all()
    n_fired = pipe.stats["mdl_retrains"]
    pipe.ingest(_fresh(keys, 32),
                (77_000 + np.arange(32)).astype(np.int64))
    pipe.publish()
    assert pipe.stats["mdl_retrains"] == n_fired
    pipe.close()


def test_mdl_drift_check_cadence():
    """``retrain_check_every=N`` scores every N-th publish only (the
    score walks the live set — the knob bounds that cost), and a slack
    threshold never fires."""
    idx, keys = _mk_index(n=6_000)
    pipe = EpochPipeline(idx, retrain_mdl_drift=10.0,
                         retrain_check_every=2)
    fresh = _fresh(keys, 128)
    for i in range(4):
        pipe.ingest(fresh[i * 32: (i + 1) * 32],
                    (np.arange(32) + i * 32).astype(np.int64))
        pipe.publish()
    assert pipe.stats["mdl_checks"] == 2
    assert pipe.stats["mdl_retrains"] == 0
    pipe.close()
