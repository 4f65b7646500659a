"""Fused single-dispatch lookup path: bit-identity against the numpy
oracle (narrow + >2^24 hi/lo pair keys, CSR chain epilogue at max
chain), engine scheduling (the fused path owns the small/medium-batch
regime), and the incremental window-bound / rank-row refresh."""

import copy

import numpy as np
import pytest

from conftest import make_keys
from repro.core import BACKENDS, Index, LearnedIndex
from repro.kernels import QueryEngine, batched_lookup, from_learned_index
from repro.kernels import ops as ops_mod


def _mixed_queries(rng, keys, extra=(), n_hit=1500, n_miss=400):
    lo, hi = keys[0], keys[-1]
    miss = np.setdiff1d(
        np.round(rng.uniform(lo, hi, 4 * n_miss)), keys)[:n_miss]
    parts = [rng.choice(keys, n_hit), miss,
             [keys[0] - 10.0, keys[-1] + 10.0]]
    parts += [np.asarray(e, np.float64) for e in extra]
    return np.concatenate(parts)


@pytest.mark.parametrize("seed,wide", [(0, False), (1, False),
                                       (2, True), (3, True)])
def test_fused_backends_bit_identical_to_oracle(seed, wide):
    """Property: both fused implementations (XLA graph; Pallas kernel in
    interpret mode) agree bit-exactly with the device oracle AND the
    host oracle on payloads, slots, and found — including >2^24 keys
    riding the f32 hi/lo pair and chain hits at the frozen max chain."""
    rng = np.random.default_rng(seed)
    span = 2 ** 40 if wide else 2 ** 22
    keys = np.unique(rng.choice(span, 25_000, replace=False)
                     ).astype(np.float64)
    idx = LearnedIndex.build(keys, method="pgm", eps=64, gap_rho=0.15)
    # force chains (and exercise the CSR epilogue at max_chain)
    mids = np.setdiff1d(keys[:-1] + np.rint(np.diff(keys) * 0.5),
                        keys)[:3000]
    idx.gapped.insert_batch(mids, 7_000_000 + np.arange(len(mids)))
    arrs = from_learned_index(idx)
    assert arrs.key_wide == wide
    assert arrs.max_chain > 0
    plm = idx.mech.plm
    q = _mixed_queries(rng, keys, extra=[mids[:800], mids[:50] + 1.0])
    out_o, slot_o, found_o, _ = batched_lookup(arrs, plm.err_lo, q,
                                               backend="oracle")
    assert np.array_equal(np.asarray(out_o), idx.gapped.lookup_batch(q))
    for be in ("fused", "fused-pallas"):
        out, slot, found, fb = batched_lookup(
            arrs, plm.err_lo, q, backend=be, err_hi_by_seg=plm.err_hi,
            interpret=True)
        assert np.array_equal(np.asarray(out), np.asarray(out_o)), be
        assert np.array_equal(np.asarray(slot), np.asarray(slot_o)), be
        assert np.array_equal(np.asarray(found), np.asarray(found_o)), be
    # sorted fast path on the fused kernel (skips the lexsort/argsort)
    qs = np.sort(q)
    out_s, *_ = batched_lookup(arrs, plm.err_lo, qs,
                               backend="fused-pallas",
                               err_hi_by_seg=plm.err_hi, interpret=True,
                               queries_sorted=True)
    assert np.array_equal(np.asarray(out_s), idx.gapped.lookup_batch(qs))


def test_fused_wide_payloads_roundtrip():
    """int64 payloads ride the i32 hi/lo pair through both fused
    epilogues (in-kernel and XLA) and the host escape patch."""
    keys = make_keys("uniform_int", 12_000, seed=5)
    idx = LearnedIndex.build(keys, method="pgm", eps=64, gap_rho=0.2)
    ga = idx.gapped
    big = np.int64(3) << 40
    ga.payload[ga.occupied] = big + ga.payload[ga.occupied]
    ga.links.chain_payloads[:] = big + ga.links.chain_payloads
    assert ga.links.total > 0
    ga._invalidate()
    arrs = from_learned_index(idx)
    assert arrs.wide
    rng = np.random.default_rng(6)
    q = _mixed_queries(rng, keys, n_hit=1000, n_miss=200)
    truth = ga.lookup_batch(q)
    assert truth.max() > np.iinfo(np.int32).max
    plm = idx.mech.plm
    for be in ("fused", "fused-pallas"):
        out, *_ = batched_lookup(arrs, plm.err_lo, q, backend=be,
                                 err_hi_by_seg=plm.err_hi, interpret=True)
        assert np.asarray(out).dtype == np.int64
        assert np.array_equal(np.asarray(out), truth), be


def test_fused_escape_patch_is_exact():
    """A poisoned level-2 rank table (every row above the middle one
    empty) flags nearly every query; the O(#escapes) host patch must
    still produce oracle-exact results — the fused path's stale-table
    soundness."""
    keys = make_keys("uniform_int", 10_000, seed=7)
    idx = LearnedIndex.build(keys, method="pgm", eps=64, gap_rho=0.2)
    eng = QueryEngine.from_index(idx)
    rng = np.random.default_rng(7)
    q = _mixed_queries(rng, keys, n_hit=2000, n_miss=300)
    truth = idx.gapped.lookup_batch(q)
    import jax.numpy as jnp
    ranks = eng._router.ranks
    poisoned = np.minimum(ranks, ranks[eng._router.n_rows // 2])
    eng._rank_table = jnp.asarray(np.sort(poisoned))
    out, slot, found, fb = eng.lookup(q)
    assert fb > len(q) // 4          # the storm actually happened
    assert np.array_equal(np.asarray(out), truth)


def test_engine_schedules_fused_below_the_crossover():
    """The fused path owns the small/medium-batch regime: default
    engine resolution picks it at every bucket at and below the old
    ~8k crossover (the legacy xla stage used to be downgraded to the
    device oracle there)."""
    keys = make_keys("uniform_int", 20_000, seed=8)
    idx = LearnedIndex.build(keys, method="pgm", eps=64, gap_rho=0.15)
    eng = QueryEngine.from_index(idx)
    assert eng.backend == "fused"
    rng = np.random.default_rng(8)
    for n_q in (512, 1024, 4096):
        q = rng.choice(keys, n_q)
        out, *_ = eng.lookup(q)
        assert eng.last_stage == "fused", n_q
        assert np.array_equal(np.asarray(out), idx.gapped.lookup_batch(q))
    # legacy reference stages remain explicitly requestable
    eng.lookup(rng.choice(keys, 512), backend="xla", force_backend=True)
    assert eng.last_stage == "xla"
    # ...and the non-forced legacy xla request still downgrades
    eng.lookup(rng.choice(keys, 512), backend="xla")
    assert eng.last_stage == "oracle"


def test_handle_resolves_fused_and_serves_wide_keys():
    x = make_keys("uniform_int", 9_000, seed=9)
    wide_keys = np.unique(x + 2.0 ** 30)
    idx = Index.build(wide_keys, method="pgm", eps=64, gap_rho=0.1)
    assert idx.resolve_backend(4096).name == "fused"
    assert BACKENDS["fused"].wide_keys
    res = idx.lookup(wide_keys[:2048])
    assert res.backend == "fused"
    assert np.array_equal(res.payloads,
                          np.searchsorted(wide_keys, wide_keys[:2048]))


def test_incremental_bounds_match_full_recompute():
    """Property: the subset recompute (segments= + base=) reproduces the
    full query_window_bounds rows for the touched segments exactly."""
    rng = np.random.default_rng(11)
    keys = np.unique(rng.choice(2 ** 22, 15_000, replace=False)
                     ).astype(np.float64)
    idx = Index.build(keys, method="pgm", eps=32, gap_rho=0.2)
    lo0, hi0 = ops_mod.query_window_bounds(idx)
    # mutate a clustered slice, then recompute both ways
    mids = np.setdiff1d(keys[:-1] + np.rint(np.diff(keys) * 0.5), keys)
    batch = mids[len(mids) // 3: len(mids) // 3 + 800]
    idx.gapped.insert_batch(batch, np.arange(800))
    full_lo, full_hi = ops_mod.query_window_bounds(idx)
    plm = idx.mech.plm
    segs = np.unique(plm.segment_of(batch))
    segs = np.unique(np.clip(np.concatenate([segs - 1, segs, segs + 1]),
                             0, plm.n_segments - 1))
    inc_lo, inc_hi = ops_mod.query_window_bounds(
        idx, segments=segs, base=(lo0, hi0))
    assert np.allclose(inc_lo[segs], full_lo[segs])
    assert np.allclose(inc_hi[segs], full_hi[segs])
    # untouched rows keep the base values
    other = np.setdiff1d(np.arange(plm.n_segments), segs)
    assert np.array_equal(inc_lo[other], np.asarray(lo0)[other])
    assert np.array_equal(inc_hi[other], np.asarray(hi0)[other])


def test_delta_refresh_tracks_refreeze_fallback_rate():
    """Acceptance: after clustered delta updates, the refreshed engine's
    fused fallback count equals the freshly refrozen engine's (ratio 1
    — well within the 2x bar), while results stay bit-identical."""
    rng = np.random.default_rng(12)
    keys = np.unique(rng.choice(2 ** 22, 20_000, replace=False)
                     ).astype(np.float64)
    idx = Index.build(keys, method="pgm", eps=64, gap_rho=0.15)
    idx.refreeze_contested_frac = 1.1
    idx.refreeze_link_growth = 10.0
    idx.fused_ingest_enabled = False  # this test measures the DELTA arm
    idx.sync_device()
    mids = np.setdiff1d(keys[:-1] + np.rint(np.diff(keys) * 0.5), keys)
    lo = len(mids) // 4
    for r in range(2):
        batch = mids[lo + r * 600: lo + (r + 1) * 600]
        rep = idx.ingest(batch, 5_000_000 + np.arange(600) + r)
        assert rep.device == "delta"
    assert idx.stats["bound_refreshes"] >= 1
    fresh = copy.deepcopy(idx)
    fresh.refreeze()
    probe = np.concatenate([rng.choice(keys, 3000),
                            mids[lo: lo + 1200],
                            mids[lo: lo + 200] + 1.0])
    res_d = idx.lookup(probe, backend="fused")
    res_f = fresh.lookup(probe, backend="fused")
    assert np.array_equal(res_d.payloads, res_f.payloads)
    assert np.array_equal(res_d.found, res_f.found)
    assert res_d.fallbacks <= 2 * max(res_f.fallbacks, 1)


def test_tpu_defaults_serve_the_compiled_xla_graph(monkeypatch):
    """On a TPU the default device path is the fused XLA graph, no
    kernel entry point defaults to interpret mode, and the auto rule
    leaves the fused ingest graph off.  The platform is steered by
    patching ``jax.default_backend``; the graph itself runs on the CPU."""
    import inspect

    import jax

    from repro.kernels import gap_place, ops_gap
    from repro.kernels.lookup import resolve_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    assert resolve_interpret(True) is True  # an explicit request wins
    for fn in (batched_lookup, ops_gap.gap_positions_device,
               ops_gap.ingest_place, ops_gap.fused_ingest,
               gap_place.fused_ingest_body):
        assert inspect.signature(fn).parameters["interpret"].default is None

    rng = np.random.default_rng(7)
    keys = np.unique(rng.choice(2 ** 40, 20_000, replace=False)
                     ).astype(np.float64)
    idx = Index.build(keys, method="pgm", gap_rho=0.15)
    eng = idx.sync_device()
    assert (eng.fused_impl, eng.interpret, eng.backend) == ("xla", False,
                                                          "fused")
    q = _mixed_queries(rng, keys)
    res = idx.lookup(q)
    assert res.backend == "fused" and eng.last_stage == "fused"
    assert np.array_equal(res.payloads, idx.gapped.lookup_batch(q))
    mids = np.setdiff1d(keys[:-1] + np.rint(np.diff(keys) * 0.5),
                        keys)[:1024]
    rep = idx.ingest(mids, 10 ** 6 + np.arange(mids.size))
    assert rep.device != "fused"  # the fused write graph stayed off
    res = idx.lookup(mids)
    assert res.backend == "fused" and res.found.all()


def _single_level_trips(slot_key, slot_key_lo, r_bits=16, pct=99.0):
    """The bisect budget of a one-level router: 2^r_bits equal-width
    buckets, trips from the p99 occupancy over buckets."""
    sk = np.asarray(slot_key, np.float64)
    if slot_key_lo is not None and np.asarray(slot_key_lo).size:
        sk = sk + np.asarray(slot_key_lo, np.float64)
    fin = sk[np.isfinite(sk)]
    r = 1 << r_bits
    scale = r / max(fin[-1] - fin[0], 1e-9)
    table = np.searchsorted(sk, fin[0] + np.arange(r + 1) / scale)
    table[-1] = fin.size
    p = float(np.percentile(np.diff(table), pct))
    trips = int(max(1, np.ceil(np.log2(p + 3.0)) + 1))
    return min(trips, int(np.ceil(np.log2(sk.shape[0]))) + 1)


@pytest.mark.parametrize("kind", ["uniform_int", "lognormal"])
def test_rank_router_follows_the_key_cdf(kind):
    """The two-level rank router keeps skewed (SOSD lognormal) keys on
    the device: a half-present batch is answered exactly with under 1%
    escapes, and on uniform keys the router costs no more bisect trips
    than one level of equal-width buckets did."""
    keys = make_keys(kind, 1 << 18, seed=11)
    idx = Index.build(keys, method="pgm", gap_rho=0.15, sample_rate=0.01)
    idx.sync_device()
    eng = idx._engine
    rng = np.random.default_rng(11)
    absent = np.setdiff1d(rng.choice(keys, 6000) + 1.0, keys)[:4096]
    q = rng.permutation(np.concatenate([rng.choice(keys, 4096), absent]))
    res = idx.lookup(q)
    assert eng.last_stage == "fused"
    assert np.array_equal(np.asarray(res.payloads),
                          idx.gapped.lookup_batch(q))
    assert res.fallbacks < 0.01 * q.size, res.fallbacks
    st = eng.stats
    assert st["rank_trips"] == eng._router.trips
    assert st["rank_rows"] == eng._router.n_rows >= 1 << 16
    a = eng.arrays
    if kind == "uniform_int":
        assert st["rank_trips"] <= _single_level_trips(
            a.slot_key, a.slot_key_lo if a.key_wide else None)
    else:
        assert a.key_wide and st["rank_split"] > 0


@pytest.mark.parametrize("key_wide", [False, True], ids=["narrow", "wide"])
def test_rank_rows_count_keys_in_device_representation(key_wide):
    """The host rank refresh counts full-precision keys by their frozen
    device representation (f32, or the f32 hi/lo pair) without rounding
    the whole array: equal to searchsorted over the rounded keys, on
    continuous keys (f32 rounding moves narrow ones across boundaries)."""
    rng = np.random.default_rng(12)
    k = rng.uniform(0.0, 1e12 if key_wide else 1e3, 40_000)
    k = np.sort(np.concatenate([k, k[:4_000] * (1 + 1e-9),
                                np.full(64, np.inf)]))
    hi, lo = ops_mod.split_key_pair(k)
    rounded = (hi.astype(np.float64)
               + (lo.astype(np.float64) if key_wide else 0.0))
    b = np.concatenate([rng.choice(k[:-64], 2_000),
                        rng.uniform(0.0, k[-65], 2_000), [np.inf]])
    bh, bl = ops_mod.split_key_pair(b)
    want = np.searchsorted(rounded, bh.astype(np.float64) + bl)
    got = ops_mod._ranks_at(k, bh, bl, key_wide)
    assert np.array_equal(got, want)
    if not key_wide:  # f32 rounding moved keys across some boundary
        assert not np.array_equal(np.searchsorted(k, b), want)
