"""Fused single-dispatch device-resident ingest (the tentpole contract).

* an accepted batch costs exactly ONE device dispatch — counted by
  monkeypatching the jitted graph entry (``ops_gap._fused_ingest_xla``)
  — with ZERO host-oracle placement calls and no delta/refreeze
  dispatches; the committed state is bit-identical to sequential
  ``insert()`` AND to the host ``insert_batch`` partition, chain-append
  (CSR-merge) arm included, and the adopted device buffers answer the
  new keys with no re-sync;
* crowded / headroom-overflow batches ABORT in-graph and fall back to
  the two-dispatch place+delta path REUSING the dispatch's placement
  primitives (no second placement dispatch, no wasted work) — state
  still bit-identical to sequential;
* ``MicroBatchQueue`` demultiplexes one aggregated flush back into
  per-ticket typed slices in submission order (ingests flushed first).

Hypothesis property versions are importorskip-guarded like the other
property suites.
"""

import copy

import numpy as np
import pytest

from repro.core import Index
from repro.kernels import ops_gap


def _state_equal(g1, g2):
    return (np.array_equal(g1.slot_key, g2.slot_key)
            and np.array_equal(g1.occupied, g2.occupied)
            and np.array_equal(g1.payload, g2.payload)
            and g1.n_keys == g2.n_keys
            and dict(g1.links) == dict(g2.links))


def _mids(keys):
    return np.setdiff1d(keys[:-1] + np.rint(np.diff(keys) * 0.5), keys)


def _spread(keys, n):
    mids = _mids(keys)
    return mids[:: max(1, len(mids) // n)][:n]


def _build(width=2 ** 22, n=25_000, seed=0, method="pgm"):
    rng = np.random.default_rng(seed)
    keys = np.unique(rng.choice(width, n, replace=False)
                     ).astype(np.float64)
    idx = Index.build(keys, method=method, eps=64, gap_rho=0.2)
    idx.fused_ingest_enabled = True   # force the arm under test (the
    idx.sync_device()                 # CPU auto default is two-dispatch)
    return idx, keys, rng


def _count_dispatches(monkeypatch, gapped_cls):
    """Spy on the one-dispatch symbol and the host placement oracle."""
    calls = {"fused": 0, "oracle": 0}
    real_fused = ops_gap._fused_ingest_xla

    def counting_fused(*a, **kw):
        calls["fused"] += 1
        return real_fused(*a, **kw)

    real_pp = gapped_cls.placement_primitives

    def counting_pp(self, *a, **kw):
        calls["oracle"] += 1
        return real_pp(self, *a, **kw)

    monkeypatch.setattr(ops_gap, "_fused_ingest_xla", counting_fused)
    monkeypatch.setattr(gapped_cls, "placement_primitives", counting_pp)
    return calls


# ---------------------------------------------------------------------------
# accepted batch: one dispatch, state bit-identical, buffers adopted
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("width", [2 ** 22, 2 ** 40])
def test_fused_single_dispatch_state_identical(width, monkeypatch):
    idx, keys, _ = _build(width=width)
    batch = _spread(keys, 3_000)           # well-spread: closure-trivial
    pays = 1_000_000 + np.arange(batch.size)
    seq = copy.deepcopy(idx)
    hostp = copy.deepcopy(idx)

    calls = _count_dispatches(monkeypatch, type(idx.gapped))
    deltas0 = idx.stats["delta_updates"]
    refreezes0 = idx.stats["refreezes"]
    rep = idx.ingest(batch, pays)

    assert rep.device == "fused" and rep.placement == "device"
    assert rep.contested == 0 and rep.slot + rep.chain == rep.n
    assert rep.chain > 0                   # the CSR-merge arm really ran
    assert calls == {"fused": 1, "oracle": 0}
    assert idx.stats["delta_updates"] == deltas0   # nothing re-synced
    assert idx.stats["refreezes"] == refreezes0

    for i, k in enumerate(batch):
        seq.insert(float(k), int(pays[i]))
    hostp.gapped.insert_batch(batch, pays)
    assert _state_equal(idx.gapped, seq.gapped)
    assert _state_equal(idx.gapped, hostp.gapped)

    # the ADOPTED device buffers (no delta, no refreeze) answer slot and
    # chain keys exactly — batch is ascending, so pays align
    res = idx.lookup(batch, backend="fused", queries_sorted=True)
    assert np.array_equal(np.asarray(res.payloads), pays)
    assert bool(np.all(np.asarray(res.found)))
    assert idx.stats["delta_updates"] == deltas0
    assert idx.stats["refreezes"] == refreezes0


def test_fused_then_scalar_then_delta_roundtrip():
    """A fused commit leaves the mirror source-advanced/image-dirty; the
    next host-side mutation must still delta-sync correctly (the lazy
    image rebuild) and keep lookups exact."""
    idx, keys, rng = _build(n=20_000, seed=3)
    batch = _spread(keys, 1_000)
    rep = idx.ingest(batch, 2_000_000 + np.arange(batch.size))
    assert rep.device == "fused"
    deltas0 = idx.stats["delta_updates"]
    # scalar inserts -> stale device -> delta on the next device lookup
    extra = _mids(np.sort(np.concatenate([keys, batch])))[:40]
    for i, k in enumerate(extra):
        idx.insert(float(k), 9_000_000 + i)
    probe = np.sort(np.concatenate(
        [rng.choice(keys, 1_500), batch[:500], extra]))
    res = idx.lookup(probe, backend="fused", queries_sorted=True)
    assert idx.stats["delta_updates"] == deltas0 + 1
    assert np.array_equal(np.asarray(res.payloads),
                          idx.gapped.lookup_batch(probe))


def test_fused_ingest_rank_rows_match_host_refresh():
    """On skewed (SOSD lognormal, wide) keys the rank-router rows the
    fused ingest graph rewrites in-graph equal the rows the host
    ``refresh_rank_rows`` computes for the same batch, and the jnp and
    numpy key -> row helpers agree bit for bit."""
    import jax
    import jax.numpy as jnp

    from conftest import make_keys
    from repro.kernels import ops

    keys = make_keys("lognormal", 25_000, seed=4)
    idx = Index.build(keys, method="pgm", eps=64, gap_rho=0.2)
    idx.fused_ingest_enabled = True
    idx.sync_device()
    eng = idx._engine
    rt = eng._router
    assert eng.arrays.key_wide and rt.split > 0
    before = rt.ranks.copy()
    batch = _spread(keys, 1_000)
    rep = idx.ingest(batch, 3_000_000 + np.arange(batch.size))
    assert rep.device == "fused"
    device_rows = np.asarray(eng._rank_table)   # written in stage 7
    assert eng._router is rt
    assert not np.array_equal(device_rows, before)
    assert np.array_equal(device_rows, rt.ranks)  # host refresh, same rows

    rng = np.random.default_rng(4)
    probe = np.concatenate([
        rng.choice(keys, 2_000), batch, keys[[0, -1]],
        [keys[0] - 7.0, keys[-1] * 2.0, np.inf]])
    qh, ql = ops.split_key_pair(probe)
    host = ops.rank_row_np(qh, ql, rt.l1, rt.scale, True)
    dev = jax.jit(ops.rank_row, static_argnums=4)(
        jnp.asarray(qh), jnp.asarray(ql), jnp.asarray(rt.l1),
        jnp.asarray(rt.scale), True)
    assert np.array_equal(np.asarray(dev), host)
    assert host.min() >= 0 and host.max() < rt.n_rows
    res = idx.lookup(probe[:-1])
    assert np.array_equal(np.asarray(res.payloads),
                          idx.gapped.lookup_batch(probe[:-1]))


# ---------------------------------------------------------------------------
# aborted batch: in-graph refusal, primitives reused, state identical
# ---------------------------------------------------------------------------


def test_fused_abort_falls_back_reusing_primitives(monkeypatch):
    """Contiguous runs crammed with new keys hit the in-graph closure
    check (collision groups / chain overflow) — the graph refuses,
    the handle replays the SAME primitives on the host-partition path,
    and the end state matches sequential insert()."""
    init = np.arange(0, 1_000_000, 100, dtype=np.float64)
    idx = Index.build(init, method="pgm", eps=32, gap_rho=0.2)
    idx.fused_ingest_enabled = True
    idx.sync_device()
    batch = np.setdiff1d(np.arange(50_001, 50_001 + 620,
                                   dtype=np.float64), init)[:512]  # crowded
    pays = 3_000_000 + np.arange(batch.size)
    seq = copy.deepcopy(idx)

    calls = _count_dispatches(monkeypatch, type(idx.gapped))
    rep = idx.ingest(batch, pays)
    assert calls["fused"] == 1             # the dispatch was not wasted:
    assert calls["oracle"] == 0            # ...its primitives were reused
    assert rep.device != "fused"
    assert idx.stats["fused_aborts"]       # the per-bit reasons recorded
    assert rep.slot + rep.chain == rep.n

    monkeypatch.undo()
    for i, k in enumerate(batch):
        seq.insert(float(k), int(pays[i]))
    assert _state_equal(idx.gapped, seq.gapped)


def test_fused_abort_on_link_headroom_overflow(monkeypatch):
    """A batch whose chain arm outgrows the frozen link capacity must
    abort in-graph (link_overflow), not scribble past the buffer."""
    keys = np.arange(0, 24_000, 2, dtype=np.float64)
    # linear keys + near-zero gap budget: no chains at freeze time, so
    # the link capacity freezes at its floor — and the odd midpoints are
    # chain-bound (no bracketed gap slot), one per run (no collisions,
    # no per-run overflow): the ONLY obstacle is total link capacity
    idx = Index.build(keys, method="pgm", eps=64, gap_rho=0.01)
    idx.fused_ingest_enabled = True
    idx.sync_device()
    cap = int(idx._engine.arrays.link_keys.shape[0])
    assert cap <= 128
    batch = _spread(keys, 1_024)           # chain demand far beyond cap
    pays = 4_000_000 + np.arange(batch.size)
    seq = copy.deepcopy(idx)

    calls = _count_dispatches(monkeypatch, type(idx.gapped))
    rep = idx.ingest(batch, pays)
    assert rep.device != "fused"
    assert calls["fused"] == 1 and calls["oracle"] == 0
    assert any(b in idx.stats["fused_aborts"]
               for b in ("link_overflow", "chain_overflow"))
    monkeypatch.undo()
    for i, k in enumerate(batch):
        seq.insert(float(k), int(pays[i]))
    assert _state_equal(idx.gapped, seq.gapped)


# ---------------------------------------------------------------------------
# aggregation queue: typed demux in submission order
# ---------------------------------------------------------------------------


def test_microbatch_queue_demux_order():
    from repro.serving.engine import MicroBatchQueue

    idx, keys, rng = _build(n=20_000, seed=7)
    q = MicroBatchQueue(idx, min_bucket=64)
    parts = [rng.choice(keys, sz) for sz in (5, 17, 1, 33)]
    parts.append(np.array([keys[0] - 3.0, keys[5]]))  # one miss row
    tickets = [q.submit_lookup(p) for p in parts]
    ing = _spread(keys, 700)
    t_ing = q.submit_ingest(ing, 5_000_000 + np.arange(ing.size))
    q.flush()
    assert q.stats["lookup_dispatches"] == 1   # ONE coalesced dispatch
    assert q.stats["ingest_dispatches"] == 1
    assert q.stats["coalesced_lookups"] == len(parts)
    for t, p in zip(tickets, parts):
        res = q.result(t)
        assert res.payloads.shape[0] == p.shape[0]
        assert np.array_equal(np.asarray(res.payloads),
                              idx.gapped.lookup_batch(p))
    rep = q.result(t_ing)
    assert rep.n == ing.size
    # an unresolved ticket auto-flushes on result()
    t2 = q.submit_lookup(ing[:9])
    res2 = q.result(t2)
    assert np.array_equal(np.asarray(res2.payloads),
                          5_000_000 + np.arange(9))


# the hypothesis property versions (fused-or-abort vs sequential, queue
# demux under arbitrary submission patterns) live in
# tests/test_fused_ingest_props.py, importorskip-guarded so this
# deterministic module always runs
