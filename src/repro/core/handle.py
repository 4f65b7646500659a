"""Unified ``Index`` handle: one epoch-versioned object owning both the
mutable host state and the frozen device state.

The paper's pitch is *pluggability* — sampling (§4) and gap insertion
(§5) as knobs over any base mechanism.  ``Index`` is the one public
surface those knobs hang off:

* ``Index.build(keys, method=..., sample_rate=..., gap_rho=...)``
* reads:  ``index.lookup(queries) -> LookupResult`` (typed: payloads,
  slots, found mask, fallback/escape stats) on a backend chosen from the
  capability registry below;
* writes: ``index.ingest(keys, payloads) -> IngestReport`` /
  ``index.remove(keys)`` — §5.3 dynamic ops, no retraining.

Epoch protocol
--------------
Every host mutation bumps ``index.epoch`` (delegated to the gapped
array's version counter, so scalar ``insert``/``delete``/``update``
through any path count too).  The frozen device state records the epoch
it was built against; when it is AT the host epoch, ``ingest`` computes
the batch's placement primitives on the device first (the kernels
ingest-place backend — see ``repro.kernels`` "Ingest backend contract";
host-oracle fallback whenever exactness cannot be guaranteed), then a
device-backend lookup first brings the device forward:

* **delta update** (the common case): scatter only the changed
  slot_key/payload entries and CSR-link tail regions into the resident
  device buffers — no re-jit, no window-bound recompute, no full
  transfer;
* **full refreeze**: taken only when the contested-remainder fraction of
  an ingest or the link-chain growth since the last freeze crosses a
  threshold (stale windows / long chains degrade the single-pass rate),
  or when a shape/dtype static changed (link capacity, max-chain
  headroom, payload or key width).

Backend capability registry
---------------------------
=============  ======  ==========  =========  =====================
name           device  wide keys   min batch  notes
=============  ======  ==========  =========  =====================
fused          yes     yes (hi/lo  512        single-dispatch path:
                       f32 pair)              the minimal-op fused
                                              XLA graph on every
                                              platform (the fused
                                              Pallas kernel only via
                                              an explicit engine
                                              ``fused_impl``)
pallas         yes     no          512        LEGACY multi-op Pallas
                                              kernel (debug/ref;
                                              interpreted off-TPU)
xla-windowed   yes     yes (hi/lo  512        legacy multi-op
                       f32 pair)              windowed bisect/rank
                                              (debug/reference)
numpy-oracle   no      yes (f64)   0          host reference; exact
=============  ======  ==========  =========  =====================

``lookup(backend=None)`` resolves: small batches go to ``numpy-oracle``;
everything else to ``fused`` — the single-dispatch path serves narrow
AND wide (hi/lo pair) keys on every platform, so it owns the whole
device regime including the small/medium batches the legacy multi-op
paths used to lose to the oracle.  ``pallas`` / ``xla-windowed`` remain
explicitly requestable as debug/reference stages; neither is compiled
for the TPU by any default path.  Explicitly
requesting a backend that cannot serve the index (e.g. the legacy
``pallas`` with >2^24 composite keys) raises with the capability that
failed; keys aliasing beyond pair exactness (~2^48) refuse every device
backend.
"""

from __future__ import annotations

import copy as _copy
import dataclasses
import time
from typing import Dict, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from . import gaps as _gaps
from . import mdl as _mdl
from . import sampling as _sampling
from .mechanisms import MECHANISMS
from .results import IngestReport, LookupResult, host_lookup_result

__all__ = ["Index", "BackendSpec", "BACKENDS"]


def _mechanism_factory(method: str, **kwargs):
    cls = MECHANISMS[method]
    return lambda: cls(**kwargs)


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """Capability record for one lookup backend."""

    name: str
    device: bool            # runs on the frozen device arrays
    wide_keys: bool         # exact beyond f32 (2^24) key magnitudes
    min_batch: int          # below this the backend loses to the host
    engine_backend: Optional[str]  # kernels.QueryEngine backend name


BACKENDS: Dict[str, BackendSpec] = {
    "fused": BackendSpec("fused", device=True, wide_keys=True,
                         min_batch=512, engine_backend="fused"),
    "pallas": BackendSpec("pallas", device=True, wide_keys=False,
                          min_batch=512, engine_backend="pallas"),
    "xla-windowed": BackendSpec("xla-windowed", device=True, wide_keys=True,
                                min_batch=512, engine_backend="xla"),
    "numpy-oracle": BackendSpec("numpy-oracle", device=False, wide_keys=True,
                                min_batch=0, engine_backend=None),
}


@dataclasses.dataclass
class Index:
    """A built learned index over sorted unique f64 keys (see module doc).

    Host state: ``keys`` / ``mech`` / ``gapped``; device state: a lazily
    frozen ``kernels.QueryEngine`` plus the host mirror its delta updates
    diff against.  ``epoch`` versions the pair.
    """

    keys: np.ndarray
    mech: object
    method: str
    gapped: Optional[_gaps.GappedArray] = None
    sample_rate: float = 1.0
    gap_rho: float = 0.0
    build_seconds: float = 0.0
    # mechanism-learning share of build_seconds (base fit + Eq.3 +
    # step-3 refit — O(n_s) under sampling; placement excluded)
    learn_seconds: float = 0.0
    # mechanism kwargs the build used — retrain() replays them
    mech_kwargs: dict = dataclasses.field(default_factory=dict)
    # the auto-tuner's TunedChoice when built with method="auto"
    tuned: object = dataclasses.field(default=None, repr=False,
                                      compare=False)
    # --- device-sync policy knobs -------------------------------------
    refreeze_contested_frac: float = 0.25
    refreeze_link_growth: float = 0.10
    min_device_batch: int = 512
    # single-dispatch device-resident ingest (fused place + slot scatter
    # + CSR merge + rank/bound refresh in ONE dispatch, device buffers
    # adopted from the graph's outputs).  None = AUTO: on only for
    # engines built with the explicit fused_impl="pallas"; off for the
    # default fused-XLA engine on every platform — on XLA-CPU the
    # graph's fixed O(state) cost (full-array carried-key repair scan,
    # functional whole-buffer updates) loses to the sparse host delta
    # (BENCH_ingest fused_dispatch rows), and on the TPU it has not
    # been measured.  True/False force the arm either way; the
    # staleness benchmarks pin False to keep exercising the delta
    # machinery in isolation.
    fused_ingest_enabled: Optional[bool] = None
    # split commit: when the fused abort gate vetoes a batch, retry the
    # longest locally-clean PREFIX in-graph and replay only the
    # contested remainder on the host path (ROADMAP residual closed in
    # PR 8) — False restores whole-batch abort-to-host
    fused_split_commit: bool = True
    # delta updates refresh window bounds for touched segments only;
    # past this fraction of all segments the refresh is skipped (stale
    # bounds are sound — the refreeze policy catches sustained growth)
    refresh_segments_frac: float = 0.25
    # --- device state (rebuilt lazily; dropped on deepcopy) -----------
    _engine: object = dataclasses.field(default=None, repr=False,
                                        compare=False)
    _mirror: object = dataclasses.field(default=None, repr=False,
                                        compare=False)
    _device_epoch: int = dataclasses.field(default=-1, repr=False,
                                           compare=False)
    _keycap_cache: object = dataclasses.field(default=None, repr=False,
                                              compare=False)
    # mutated key values since the last device sync — feeds the
    # incremental window-bound refresh (chain inserts never show up in
    # the device slot diff, so the handle logs them itself)
    _pending_touch: list = dataclasses.field(default_factory=list,
                                             repr=False, compare=False)
    stats: dict = dataclasses.field(default_factory=lambda: {
        "refreezes": 0, "delta_updates": 0, "delta_elems": 0,
        "lookups": 0, "ingests": 0, "bound_refreshes": 0,
        "retrains": 0, "search_probes": 0})

    # ------------------------------------------------------------------
    @classmethod
    def build(
        cls,
        keys: np.ndarray,
        method: str = "pgm",
        sample_rate: float = 1.0,
        gap_rho: float = 0.0,
        rng: Optional[np.random.Generator] = None,
        payloads: Optional[np.ndarray] = None,
        shards: Optional[int] = None,
        **mech_kwargs,
    ):
        """Build an index.  ``payloads`` overrides the stored payload
        per key (default: the key's position, ``arange(n)``) — gapped
        builds only.  ``shards=`` is the escape hatch into the
        range-partitioned ``repro.dist.ShardedIndex`` (same call
        surface, per-shard gap-inserted builds + learned router).

        ``method="auto"`` runs the §3 MDL auto-tuner
        (``core.tuning.autotune``) over a (mechanism, eps, sample-size)
        grid on a sample of the keys and builds the winner; the choice
        is recorded on ``index.tuned``.  The defaults ``sample_rate=1.0``
        mean "let the tuner pick" under auto; pass an explicit rate to
        pin it."""
        if shards is not None:
            from ..dist.sharded import ShardedIndex
            return ShardedIndex.build(
                keys, shards=int(shards), method=method,
                sample_rate=sample_rate, gap_rho=gap_rho, rng=rng,
                payloads=payloads, **mech_kwargs)
        keys = np.asarray(keys, np.float64)
        if keys.ndim != 1 or keys.shape[0] < 2:
            raise ValueError("need a 1-D array of at least two keys")
        if not bool(np.all(np.diff(keys) > 0)):
            raise ValueError("keys must be sorted, strictly increasing (unique)")
        if payloads is not None:
            payloads = np.asarray(payloads, np.int64)
            if payloads.shape != keys.shape:
                raise ValueError("payloads must match keys 1:1")
            if gap_rho <= 0.0:
                raise ValueError("explicit payloads need a gapped build "
                                 "(gap_rho > 0); static builds store "
                                 "positions")
        tuned = None
        if method == "auto":
            from . import tuning as _tuning
            tuned = _tuning.autotune(
                keys, queries=mech_kwargs.pop("queries", None),
                dynamic=gap_rho > 0.0, rng=rng,
                **{k: mech_kwargs.pop(k) for k in
                   ("alpha", "size_budget_bytes", "max_err_budget")
                   if k in mech_kwargs})
            method = tuned.method
            mech_kwargs = dict(tuned.mech_kwargs, **mech_kwargs)
            if sample_rate >= 1.0:  # default sentinel: tuner's pick
                sample_rate = tuned.sample_rate
        factory = _mechanism_factory(method, **mech_kwargs)
        t0 = time.perf_counter()
        if gap_rho > 0.0:
            refit_factory = None
            if method in ("pgm", "fiting") and "eps" in mech_kwargs:
                # D_g is near-linear: tighter refit eps => precise
                # placement, short linking arrays (beyond-paper knob)
                rkw = dict(mech_kwargs)
                rkw["eps"] = max(4.0, float(mech_kwargs["eps"]) / 16.0)
                refit_factory = _mechanism_factory(method, **rkw)
            ga = _gaps.build_gapped(
                factory, keys, payloads=payloads, rho=gap_rho,
                sample_rate=sample_rate, rng=rng,
                refit_factory=refit_factory,
            )
            mech = ga.mech
            gapped = ga
        else:
            gapped = None
            if sample_rate < 1.0:
                mech = _sampling.fit_sampled(factory, keys, rate=sample_rate,
                                             rng=rng)
            else:
                mech = factory()
                mech.fit(keys, np.arange(keys.shape[0], dtype=np.float64))
        dt = time.perf_counter() - t0
        timings = getattr(gapped, "build_timings", None) or {}
        return cls(
            keys=keys,
            mech=mech,
            method=method,
            gapped=gapped,
            sample_rate=sample_rate,
            gap_rho=gap_rho,
            build_seconds=dt,
            learn_seconds=float(timings.get("learn_seconds", dt)),
            mech_kwargs=dict(mech_kwargs),
            tuned=tuned,
        )

    # ------------------------------------------------------------------
    # epoch protocol
    # ------------------------------------------------------------------
    @property
    def epoch(self) -> int:
        """Monotone host-state version (0 for an untouched build)."""
        return self.gapped.version if self.gapped is not None else 0

    @property
    def device_epoch(self) -> int:
        """Epoch the frozen device state reflects (-1: not materialized)."""
        return self._device_epoch

    def __deepcopy__(self, memo):
        # device state is a cache keyed by epoch — rebuild it lazily in
        # the copy instead of deep-copying jax buffers
        new = Index(
            keys=_copy.deepcopy(self.keys, memo),
            mech=_copy.deepcopy(self.mech, memo),
            method=self.method,
            gapped=_copy.deepcopy(self.gapped, memo),
            sample_rate=self.sample_rate,
            gap_rho=self.gap_rho,
            build_seconds=self.build_seconds,
            learn_seconds=self.learn_seconds,
            mech_kwargs=dict(self.mech_kwargs),
            tuned=self.tuned,
            refreeze_contested_frac=self.refreeze_contested_frac,
            refreeze_link_growth=self.refreeze_link_growth,
            min_device_batch=self.min_device_batch,
            fused_ingest_enabled=self.fused_ingest_enabled,
            fused_split_commit=self.fused_split_commit,
            refresh_segments_frac=self.refresh_segments_frac,
            stats=dict(self.stats),
        )
        new.__class__ = self.__class__
        memo[id(self)] = new
        return new

    # ------------------------------------------------------------------
    # backend resolution
    # ------------------------------------------------------------------
    def _key_caps(self):
        """(wide, device_exact) of the LIVE key set, cached per epoch.

        ``wide``: keys exceed f32 exactness (2^24) and ride the hi/lo
        pair on device.  ``device_exact``: the device pair search cannot
        conflate stored keys — either every key is individually
        pair-exact (integers < 2^48; the common composite/hash case) or
        the pair mapping is alias-free over the stored set (continuous
        f64 keys whose spacing exceeds pair resolution).  ``ingest``
        maintains the cache incrementally for all-exact batches, so the
        hot path stays O(batch)."""
        cached = self._keycap_cache
        if cached is not None and cached[0] == self.epoch:
            return cached[1], cached[2]
        from ..kernels import ops as _ops
        if self.gapped is not None:
            arrs = (self.gapped.slot_key, self.gapped.links.chain_keys)
        else:
            arrs = (self.keys,)
        wide = any(_ops.keys_need_pair(a) for a in arrs)
        indiv = all(_ops.keys_pair_exact(a) for a in arrs)
        exact = indiv
        if wide and not indiv:
            merged = (np.sort(np.concatenate(arrs)) if len(arrs) > 1
                      and arrs[1].size else arrs[0])
            exact = _ops.pair_alias_free(merged)
        self._keycap_cache = (self.epoch, wide, exact, indiv)
        return wide, exact

    def _key_caps_after_batch(self, batch: np.ndarray) -> None:
        """Incremental cap maintenance after an ingest, O(batch log n):

        * all-exact set + per-key pair-exact batch: exact pairs
          reconstruct their key, so no aliasing can appear — roll the
          cache forward directly;
        * alias-free continuous set: a NEW alias must pair a new key
          with one of its key-order neighbors, so checking the batch
          against its bracketing stored keys (slot keys + the bracketing
          slots' chains) suffices — no O(n log n) global re-sort;
        * anything else leaves the cache stale for a full recompute.
        """
        cached = self._keycap_cache
        if cached is None or not cached[2]:
            return  # no cache, or already inexact (stays inexact)
        from ..kernels import ops as _ops
        batch = np.asarray(batch, np.float64)
        wide = cached[1] or _ops.keys_need_pair(batch)
        if cached[3] and _ops.keys_pair_exact(batch):
            self._keycap_cache = (self.epoch, wide, True, True)
            return
        ga = self.gapped
        if ga is None:
            return
        # continuous case: verify alias-freeness of the new keys against
        # their key-order neighbors in the (already updated) structure.
        # By the carried-key construction, a value's predecessor lives
        # on the PREV occupied slot (left-searchsorted - 1) or its
        # chain, and its bracketing chain hangs off the occupied upper
        # bound (right-searchsorted - 1); the successor value is that
        # slot's right neighbor's (carried) key.
        bs = np.unique(batch)
        m = ga.n_slots
        jr = np.searchsorted(ga.slot_key, bs, side="right") - 1
        jl = np.searchsorted(ga.slot_key, bs, side="left") - 1
        s_chain = np.unique(np.clip(np.concatenate([jl, jr]), 0, m - 1))
        s_vals = np.unique(np.clip(np.concatenate([jl, jr, jr + 1]),
                                   0, m - 1))
        nb = ga.slot_key[s_vals]
        off, ck, _ = ga.links.csr()
        starts, ends = off[s_chain], off[s_chain + 1]
        lens = ends - starts
        if int(lens.sum()):
            base = np.repeat(starts, lens)
            step = np.arange(int(lens.sum())) - np.repeat(
                np.cumsum(lens) - lens, lens)
            chain_nb = ck[base + step]
        else:
            chain_nb = np.zeros(0, np.float64)
        cand = np.concatenate([bs, nb[np.isfinite(nb)], chain_nb])
        exact = _ops.pair_alias_free(np.sort(np.unique(cand)))
        self._keycap_cache = (self.epoch, wide, bool(exact), False)

    def _keys_wide(self) -> bool:
        return self._key_caps()[0]

    def resolve_backend(self, n_queries: int,
                        requested: Optional[str] = None) -> BackendSpec:
        """Pick a backend from the capability registry (see module doc)."""
        has_plm = getattr(self.mech, "plm", None) is not None
        if requested is not None:
            try:
                spec = BACKENDS[requested]
            except KeyError:
                raise ValueError(
                    f"unknown backend {requested!r}; registered: "
                    f"{sorted(BACKENDS)}") from None
            if spec.device:
                if not has_plm:
                    raise ValueError(
                        f"backend {requested!r} cannot serve this index: "
                        f"mechanism {self.method!r} does not export a "
                        "piecewise linear model — use 'numpy-oracle'")
                wide, exact = self._key_caps()
                if wide and not spec.wide_keys:
                    raise ValueError(
                        f"backend {requested!r} cannot serve this index: "
                        "keys exceed f32 exactness (2^24) and the backend "
                        "lacks hi/lo wide-key support — use 'xla-windowed' "
                        "or 'numpy-oracle'")
                if wide and not exact:
                    raise ValueError(
                        f"backend {requested!r} cannot serve this index: "
                        "distinct keys alias in the f32 hi/lo pair "
                        "representation (exact only up to ~2^48) — only "
                        "'numpy-oracle' can distinguish them")
            return spec
        if n_queries < self.min_device_batch or not has_plm:
            return BACKENDS["numpy-oracle"]
        wide, exact = self._key_caps()
        if wide and not exact:  # beyond 2^48: only the host is exact
            return BACKENDS["numpy-oracle"]
        # the fused single-dispatch path serves narrow and wide (hi/lo
        # pair) keys on every platform (engine.fused_impl says which
        # implementation: the fused XLA graph unless built otherwise)
        return BACKENDS["fused"]

    # ------------------------------------------------------------------
    # device state lifecycle
    # ------------------------------------------------------------------
    def refreeze(self):
        """Full rebuild of the frozen device state (arrays + query-safe
        window bounds + host mirror) at the current epoch."""
        from ..kernels import ops as _ops
        with TraceAnnotation("repro.ops.freeze"):
            self._engine, self._mirror = _ops.freeze_state(self)
        self._device_epoch = self.epoch
        self._pending_touch = []  # fresh bounds cover everything logged
        self.stats["refreezes"] += 1
        return self._engine

    def _log_touch(self, keys) -> None:
        """Record mutated key values for the next delta's incremental
        window-bound refresh (cleared by any device sync)."""
        keys = np.atleast_1d(np.asarray(keys, np.float64))
        if keys.size:
            self._pending_touch.append(keys)
            if len(self._pending_touch) > 32:  # bound the log
                self._pending_touch = [
                    np.unique(np.concatenate(self._pending_touch))]

    def sync_device(self):
        """Bring the frozen device state to the current epoch NOW (delta
        scatter when possible, refreeze otherwise) instead of lazily on
        the next device lookup.  Returns the engine."""
        return self._sync_device()

    def _sync_device(self, prefer_delta: bool = True):
        """Bring the device state to the current epoch (delta if allowed
        and possible, else refreeze).  Only a sync with work opens the
        ``repro.index.sync`` span."""
        if self._engine is not None and self._device_epoch == self.epoch:
            return self._engine
        with TraceAnnotation("repro.index.sync"):
            if self._engine is None:
                return self.refreeze()
            from ..kernels import ops as _ops
            if prefer_delta:
                with TraceAnnotation("repro.ops.delta_update"):
                    new_arrays, n_elems, touched_keys = _ops.delta_update(
                        self._engine.arrays, self._mirror, self)
                if new_arrays is not None:
                    self._engine.swap_arrays(new_arrays)
                    self._device_epoch = self.epoch
                    self.stats["delta_updates"] += 1
                    self.stats["delta_elems"] += n_elems
                    pending = ([np.asarray(touched_keys, np.float64)]
                               if touched_keys is not None else [])
                    pending += [np.asarray(a, np.float64)
                                for a in self._pending_touch]
                    self._pending_touch = []
                    with TraceAnnotation("repro.index.bound_refresh"):
                        self._refresh_window_bounds(
                            np.concatenate(pending) if pending
                            else np.zeros(0, np.float64))
                    return self._engine
            return self.refreeze()

    def _refresh_window_bounds(self, touched_keys) -> None:
        """Incremental per-segment window-bound refresh after a delta
        update: only segments whose keys moved (plus their key-order
        neighbors) recompute, so the compacted-fallback rate stays flat
        under chain growth instead of climbing until the policy
        refreeze.  Near-global churn (more than
        ``refresh_segments_frac`` of the segments touched) skips the
        refresh — stale bounds are SOUND (they only cost fallbacks) and
        the refreeze policy catches sustained growth.
        """
        eng = self._engine
        if (eng is None or touched_keys is None
                or np.asarray(touched_keys).size == 0
                or self.refresh_segments_frac <= 0):  # refresh disabled
            return
        plm = getattr(self.mech, "plm", None)
        if plm is None:
            return
        from ..kernels import ops as _ops
        # fused-path rank router: refresh only the touched rows
        eng.refresh_rank_rows(touched_keys, self.gapped.slot_key)
        segs = np.unique(plm.segment_of(np.asarray(touched_keys,
                                                   np.float64)))
        # boundary terms reach into the neighboring segments' key spans
        segs = np.unique(np.clip(
            np.concatenate([segs - 1, segs, segs + 1]),
            0, plm.n_segments - 1))
        # the frac rule caps the refresh cost on big indexes; the floor
        # keeps small-K indexes (where a refresh is trivially cheap)
        # from reading every clustered burst as global churn
        if segs.size > max(8.0, self.refresh_segments_frac
                           * plm.n_segments):
            return
        err_hi_prev = (eng.err_hi if eng.err_hi is not None
                       else np.zeros_like(eng.err_lo))
        lo, hi = _ops.query_window_bounds(
            self, segments=segs, base=(eng.err_lo, err_hi_prev))
        eng.refresh_bounds(lo, hi)
        self.stats["bound_refreshes"] = (
            self.stats.get("bound_refreshes", 0) + 1)

    def _link_growth_fraction(self) -> float:
        """Chained keys added since the last freeze, relative to the
        index size AT that freeze (a stable denominator)."""
        if self.gapped is None or self._mirror is None:
            return 0.0
        grown = self.gapped.links.total - self._mirror.links_at_freeze
        return grown / max(self._mirror.n_keys_at_freeze, 1)

    # ------------------------------------------------------------------
    # reads
    # ------------------------------------------------------------------
    def predict(self, qs: np.ndarray) -> np.ndarray:
        return self.mech.predict(np.asarray(qs, np.float64))

    def lookup(self, queries, *, backend: Optional[str] = None,
               queries_sorted: bool = False) -> LookupResult:
        """Batched exact-match lookup -> ``LookupResult``.

        ``backend`` picks a registry entry explicitly; default resolves
        by batch size / platform / key width.  ``queries_sorted=True``
        skips the sort round trip on the Pallas path.
        """
        with TraceAnnotation("repro.index.lookup"):
            queries = np.asarray(queries, np.float64)
            spec = self.resolve_backend(queries.shape[0], backend)
            self.stats["lookups"] += 1
            if not spec.device:
                if self.gapped is not None:
                    pay, slots, found = self.gapped.lookup_batch(queries,
                                                                 full=True)
                    return host_lookup_result(pay, slots, found, spec.name,
                                              self.epoch)
                pos, probes = _sampling.exponential_search(
                    self.keys, queries, self.predict(queries))
                self.stats["search_probes"] += probes
                found = self.keys[pos] == queries
                pay = np.where(found, pos, -1)
                return host_lookup_result(pay, pos, found, spec.name,
                                          self.epoch)
            engine = self._sync_device()
            esc0 = engine.stats["oracle_escapes"]
            out, slot, found, fb = engine.lookup(
                queries, queries_sorted=queries_sorted,
                backend=spec.engine_backend, force_backend=backend is not None)
            # label the search stage that ACTUALLY ran: the engine's
            # size-aware scheduler may run the device oracle for small
            # default-resolved legacy-xla buckets (explicit requests are
            # forced), and overflow escapes land on the device oracle
            stage = {"fused": "fused", "pallas": "pallas",
                     "xla": "xla-windowed",
                     "oracle": "device-oracle"}[engine.last_stage]
            return LookupResult(
                payloads=np.asarray(out).astype(np.int64),
                slots=np.asarray(slot).astype(np.int64),
                found=np.asarray(found, bool),
                backend=stage,
                epoch=self.epoch,
                fallbacks=int(fb),
                oracle_escapes=engine.stats["oracle_escapes"] - esc0,
            )

    # ------------------------------------------------------------------
    # writes (§5.3 dynamic ops — need a gapped build)
    # ------------------------------------------------------------------
    def _need_gapped(self):
        if self.gapped is None:
            raise NotImplementedError(
                "dynamic ops need gap insertion (build with gap_rho > 0)"
            )

    def _device_placements(self, keys) -> Optional[dict]:
        """Compute the batch's placement primitives on the frozen device
        arrays (the kernels ingest-place backend), escape rows patched
        from the host oracle in O(#escapes).  Returns None whenever the
        device cannot serve the batch EXACTLY — device state behind the
        host epoch, non-PLM ``predict`` (rmi routes through its root
        model, btree has no slots), keys beyond per-key pair exactness,
        or slot counts past f32/i32 indexing — and the host partition
        runs as before.  Bit-identity with the host oracle is the
        contract (see kernels.__init__ "Ingest backend contract")."""
        if (self._engine is None or self._device_epoch != self.epoch
                or self.method not in ("pgm", "fiting")
                or self.gapped is None
                or keys.shape[0] < self.min_device_batch
                # past one partition chunk, insert_batch recomputes the
                # later chunks against mutated state anyway — computing
                # (and escape-patching) device primitives for rows that
                # would be discarded is pure waste, and the report's
                # placement label would lie
                or keys.shape[0] > self.gapped.batch_chunk()
                or self.gapped.n_slots >= (1 << 24)):
            return None
        from ..kernels import ops as _ops
        verify = False
        if self._engine.arrays.key_wide:
            # wide freeze: the stored set must be per-key pair-exact
            # (not merely alias-free — a pair-ROUNDED stored key could
            # land on the other side of a batch key) and so must the
            # batch, so device pair compares equal host f64 compares.
            # A merely ALIAS-FREE wide set no longer refuses outright:
            # its device primitives are certified row-by-row on the
            # host (exact f64 bracketing checks, see
            # GappedArray.verify_placements) with failing rows
            # recomputed per-key — reported as "device-verified"
            self._key_caps()  # refresh the cache to this epoch
            cached = self._keycap_cache
            if not (cached is not None and cached[0] == self.epoch
                    and cached[3] and _ops.keys_pair_exact(keys)):
                if not (cached is not None and cached[0] == self.epoch
                        and cached[2]):
                    return None  # aliasing set: only the host is exact
                verify = True
        elif _ops.keys_need_pair(keys):
            return None  # wide batch against a narrow (plain-f32) freeze
        prims, esc = self._engine.ingest_place(keys)
        n_esc = int(np.count_nonzero(esc))
        if n_esc:
            sub = self.gapped.placement_primitives(keys[esc])
            for f, v in prims.items():
                v[esc] = sub[f]
        self.stats["ingest_place_escapes"] = (
            self.stats.get("ingest_place_escapes", 0) + n_esc)
        if verify:
            bad = self.gapped.verify_placements(keys, prims)
            n_bad = int(np.count_nonzero(bad))
            if n_bad:
                sub = self.gapped.placement_primitives(keys[bad])
                for f, v in prims.items():
                    v[bad] = sub[f]
            self.stats["ingest_place_verify_patched"] = (
                self.stats.get("ingest_place_verify_patched", 0) + n_bad)
        self._placement_mode = "device-verified" if verify else "device"
        return prims

    def _fused_eligible(self, keys, payloads) -> bool:
        """Gates for the single-dispatch fused ingest: the device-
        placement gates (epoch, PLM mechanism, one-chunk batch, per-key
        pair exactness — verified mode is NOT eligible: its host
        certification would defeat the zero-host-intermediate point)
        PLUS the fused graph's own statics: i32 sort/index range, a
        nonzero frozen link image for the CSR merge, and payloads
        within the frozen narrow width."""
        ga = self.gapped
        if (self._engine is None or self._device_epoch != self.epoch
                or self.method not in ("pgm", "fiting") or ga is None
                or keys.shape[0] < self.min_device_batch
                or keys.shape[0] > ga.batch_chunk()
                or ga.n_slots >= (1 << 22)
                or ga.n_keys == 0):
            return False
        arrays = self._engine.arrays
        if int(arrays.link_keys.shape[0]) == 0:
            return False
        from ..kernels import ops as _ops
        if not arrays.wide and payloads.size and (
                int(payloads.min()) < _ops._I32_MIN
                or int(payloads.max()) > _ops._I32_MAX):
            return False
        if arrays.key_wide:
            self._key_caps()
            cached = self._keycap_cache
            if not (cached is not None and cached[0] == self.epoch
                    and cached[3] and _ops.keys_pair_exact(keys)):
                return False
        elif _ops.keys_need_pair(keys):
            return False
        return True

    def _fused_dispatch(self, keys, payloads):
        """Issue the ONE fused device dispatch; returns ``(prims, ok,
        state)``.  On an in-graph abort (``ok`` False) the primitives
        are escape-patched and handed to the host partition — exactly
        the two-dispatch path's inputs, from the dispatch already paid
        for, so an abort never wastes the round trip."""
        prims, esc, ok, reasons, state = self._engine.fused_ingest(
            keys, payloads)
        self._placement_mode = "device"
        if ok:
            return prims, True, state
        from ..kernels.ops_gap import FUSED_ABORT_BITS
        ab = self.stats.setdefault("fused_aborts", {})
        names = [name for i, name in enumerate(FUSED_ABORT_BITS)
                 if reasons >> i & 1]
        for name in names:
            ab[name] = ab.get(name, 0) + 1
        # per-batch reason + engine-lifetime counter ride the
        # IngestReport (the abort telemetry the split-commit question
        # in ROADMAP needs answered from BENCH_ingest.json)
        self._last_abort_reasons = tuple(names)
        self.stats["fused_abort_total"] = (
            self.stats.get("fused_abort_total", 0) + 1)
        # the split-commit arm needs the raw escape rows to pick a prefix
        self._last_escape_mask = np.asarray(esc, bool)
        n_esc = int(np.count_nonzero(esc))
        if n_esc:
            sub = self.gapped.placement_primitives(keys[esc])
            for f, v in prims.items():
                v[esc] = sub[f]
        self.stats["ingest_place_escapes"] = (
            self.stats.get("ingest_place_escapes", 0) + n_esc)
        return prims, False, None

    def _commit_fused(self, keys, payloads, prims, state, t0):
        """Commit an accepted fused dispatch.  Host state advances
        through the normal partition fed the SAME dispatch's primitives
        (the host stays authoritative and bit-identical to sequential
        ``insert()``); device state advances by ADOPTING the dispatch's
        output buffers — nothing is diffed, rebuilt, or re-uploaded.
        The mirror is marked source-advanced/image-dirty, so a later
        HOST-side delta lazily rebuilds its padded images first."""
        from ..kernels import ops as _ops
        eng = self._engine
        cand = np.asarray(prims["free"], bool) & np.asarray(
            prims["bracket"], bool)
        with TraceAnnotation("repro.index.insert"):
            counts = self.gapped.insert_batch(keys, payloads,
                                              placements=prims)
        self._key_caps_after_batch(keys)
        self.stats["ingests"] += 1
        if (counts["contested"] != 0 or counts["slot"] != state["n_slot"]
                or counts["chain"] != state["n_chain"]):
            # unreachable by the closure-trivial acceptance argument
            # (the graph aborts on every shape the partition could
            # demote) — if it ever fires, distrust the graph image and
            # refreeze instead of adopting it
            self._log_touch(keys)
            self.refreeze()
            return IngestReport(
                n=int(keys.shape[0]), slot=counts["slot"],
                chain=counts["chain"], contested=counts["contested"],
                epoch=self.epoch, device="refreeze",
                seconds=time.perf_counter() - t0, placement="device")
        # adopt the in-graph refreshed state + catch the host mirrors up
        err_lo = eng.err_lo
        err_hi = (eng.err_hi if eng.err_hi is not None
                  else np.zeros_like(err_lo))
        seg = state["seg"][cand]
        dlt = state["dlt"][cand].astype(np.float32)
        np.minimum.at(err_lo, seg, dlt - np.float32(1.0))
        np.maximum.at(err_hi, seg, dlt + np.float32(1.0))
        eng.adopt_fused_state(state, err_lo, err_hi)
        eng.refresh_rank_rows(keys, self.gapped.slot_key, upload=False)
        self._device_epoch = self.epoch
        self._pending_touch = []
        self._mirror.sources = _ops._snapshot_sources(self)
        self._mirror.images = None  # lazily rebuilt by the next delta
        self.stats["fused_ingests"] = (
            self.stats.get("fused_ingests", 0) + 1)
        device = "fused"
        if self._link_growth_fraction() > self.refreeze_link_growth:
            self.refreeze()  # capacity-growth policy still applies
            device = "refreeze"
        return IngestReport(
            n=int(keys.shape[0]), slot=counts["slot"],
            chain=counts["chain"], contested=0, epoch=self.epoch,
            device=device, device_elems=0,
            seconds=time.perf_counter() - t0, placement="device",
            fused_aborts=self.stats.get("fused_abort_total", 0),
            split_commits=self.stats.get("split_commits", 0))

    def _split_prefix(self, keys, prims) -> int:
        """Longest batch prefix with no locally-suspect row — the
        split-commit candidate.  A row is suspect when it carries the
        escape bit, duplicates another batch key, is a free candidate
        without a bracket, or shares a gap run (``pv``/``ub``) with any
        other batch row (collision groups, d1/d4 demotions, and chain
        duplicates all require two rows in one run).  Heuristic, not a
        proof: the second fused dispatch re-runs the full abort gate on
        the prefix, so a miss costs one dispatch, never correctness."""
        n = int(keys.shape[0])
        free = np.asarray(prims["free"], bool)
        bracket = np.asarray(prims["bracket"], bool)
        suspect = free & ~bracket
        esc = getattr(self, "_last_escape_mask", None)
        if esc is not None and esc.shape == suspect.shape:
            suspect |= esc
        order = np.argsort(keys, kind="stable")
        ks = keys[order]
        dup = np.r_[False, ks[1:] == ks[:-1]]
        dup |= np.r_[dup[1:], False]
        suspect[order[dup]] = True
        rid = np.where(free, np.asarray(prims["pv"], np.int64),
                       np.asarray(prims["ub"], np.int64))
        uniq, inv, cnt = np.unique(rid, return_inverse=True,
                                   return_counts=True)
        # shared runs are only collision-suspect when a FREE placement
        # is involved (two free rows fighting for one slot run, or a
        # free row racing a chain attach on the same slot); several
        # chain rows merging into one chain is the graph's normal case
        free_in_run = np.zeros(uniq.size, bool)
        np.logical_or.at(free_in_run, inv, free)
        suspect |= (cnt[inv] > 1) & free_in_run[inv]
        bad = np.flatnonzero(suspect)
        # row-level veto but no locally-attributable suspect (heuristic
        # miss): halve and hope the offending rows sit in the back half
        return int(bad[0]) if bad.size else n // 2

    def _try_split_commit(self, keys, payloads, prims, t0):
        """Split commit (ROADMAP residual): the abort gate vetoed the
        whole batch, but the veto is typically caused by a handful of
        rows.  Salvage the longest locally-clean prefix with a second
        fused dispatch (committed in-graph, device buffers adopted) and
        replay only the remainder through the host partition + delta
        sync.  Returns the merged ``IngestReport`` for the FULL batch,
        or None when the prefix is too small to be worth a dispatch or
        its dispatch also aborts — the caller then falls back to the
        single host partition on the primitives already paid for."""
        n = int(keys.shape[0])
        k = self._split_prefix(keys, prims)
        if k < max(self.min_device_batch, n // 8) or k >= n:
            return None
        pk, pp = keys[:k], payloads[:k]
        if not self._fused_eligible(pk, pp):
            return None
        with TraceAnnotation("repro.index.place"):
            prims2, esc2, ok2, reasons2, state2 = (
                self._engine.fused_ingest(pk, pp))
        if not ok2:
            self.stats["split_commit_misses"] = (
                self.stats.get("split_commit_misses", 0) + 1)
            return None
        rep1 = self._commit_fused(pk, pp, prims2, state2, t0)
        self.stats["split_commits"] = (
            self.stats.get("split_commits", 0) + 1)
        # remainder replays against the post-commit state (fresh
        # placements — the prefix moved slots under it)
        rk, rp = keys[k:], payloads[k:]
        with TraceAnnotation("repro.index.place"):
            rprims = self._device_placements(rk)
        with TraceAnnotation("repro.index.insert"):
            counts = self.gapped.insert_batch(rk, rp, placements=rprims)
        self._key_caps_after_batch(rk)
        self._log_touch(rk)
        device = rep1.device
        elems = rep1.device_elems
        if self._engine is not None:
            wide, exact = self._key_caps()
            if wide and not exact:
                self._engine = None
                self._mirror = None
                self._device_epoch = -1
                device = "none"
            else:
                contested_frac = counts["contested"] / max(rk.shape[0], 1)
                want_refreeze = (
                    contested_frac > self.refreeze_contested_frac
                    or self._link_growth_fraction()
                    > self.refreeze_link_growth)
                before = (self.stats["delta_updates"],
                          self.stats["refreezes"],
                          self.stats["delta_elems"])
                self._sync_device(prefer_delta=not want_refreeze)
                if self.stats["delta_updates"] > before[0]:
                    device = "fused+delta"
                    elems += self.stats["delta_elems"] - before[2]
                elif self.stats["refreezes"] > before[1]:
                    device = "refreeze"
        return IngestReport(
            n=n, slot=rep1.slot + counts["slot"],
            chain=rep1.chain + counts["chain"],
            contested=counts["contested"], epoch=self.epoch,
            device=device, device_elems=elems,
            seconds=time.perf_counter() - t0, placement="device-split",
            abort_reasons=getattr(self, "_last_abort_reasons", ()),
            fused_aborts=self.stats.get("fused_abort_total", 0),
            split_commits=self.stats.get("split_commits", 0))

    def ingest(self, keys, payloads) -> IngestReport:
        """Batched insert; placements computed on the frozen device
        arrays when the engine is at the host epoch (the ingest-place
        backend; host-oracle fallback otherwise), then the device state
        is delta-updated in place (full refreeze only past the policy
        thresholds — see module doc).

        On an eligible device-resident engine the ENTIRE ingest is one
        fused dispatch: placement, slot scatter + carried repair, the
        chain arm's CSR merge, and the rank-row/window-bound refresh
        run in a single graph whose outputs the engine adopts directly
        (``device == "fused"``).  The graph self-vetoes on any shape
        the host partition could demote (collision groups, contested
        rows, capacity overflows, duplicates) — those batches fall back
        to the host partition REUSING the same dispatch's primitives.
        """
        with TraceAnnotation("repro.index.ingest"):
            self._need_gapped()
            t0 = time.perf_counter()
            keys = np.atleast_1d(np.asarray(keys, np.float64))
            payloads = np.atleast_1d(np.asarray(payloads, np.int64))
            prims = None
            placement = "host"
            self._last_abort_reasons = ()
            enabled = self.fused_ingest_enabled
            if enabled is None:  # auto: only explicit Pallas engines (see
                enabled = (      # the field doc)
                    getattr(self._engine, "fused_impl", "xla") == "pallas")
            if enabled and self._fused_eligible(keys, payloads):
                with TraceAnnotation("repro.index.place"):
                    prims, ok, state = self._fused_dispatch(keys, payloads)
                placement = "device"
                if ok:
                    return self._commit_fused(keys, payloads, prims, state, t0)
                # split commit only helps when the veto is attributable to
                # specific rows; a purely capacity-based veto (static chain/
                # link headroom) vetoes any same-shaped prefix too, so those
                # keep the one-dispatch abort contract
                cap_only = set(self._last_abort_reasons) <= {
                    "chain_overflow", "link_overflow"}
                if self.fused_split_commit and not cap_only:
                    rep = self._try_split_commit(keys, payloads, prims, t0)
                    if rep is not None:
                        return rep
            if prims is None:
                with TraceAnnotation("repro.index.place"):
                    prims = self._device_placements(keys)
                placement = ("host" if prims is None
                             else getattr(self, "_placement_mode", "device"))
            with TraceAnnotation("repro.index.insert"):
                counts = self.gapped.insert_batch(keys, payloads,
                                                  placements=prims)
            self._key_caps_after_batch(keys)
            self._log_touch(keys)
            self.stats["ingests"] += 1
            device = "none"
            elems = 0
            if self._engine is not None:
                wide, exact = self._key_caps()
                if wide and not exact:
                    # ingested keys outgrew the hi/lo pair's exactness: the
                    # device can no longer answer exactly — drop the frozen
                    # state; the registry now routes every lookup host-side
                    self._engine = None
                    self._mirror = None
                    self._device_epoch = -1
                else:
                    contested_frac = (counts["contested"]
                                      / max(keys.shape[0], 1))
                    want_refreeze = (
                        contested_frac > self.refreeze_contested_frac
                        or self._link_growth_fraction()
                        > self.refreeze_link_growth)
                    before = (self.stats["delta_updates"],
                              self.stats["refreezes"],
                              self.stats["delta_elems"])
                    self._sync_device(prefer_delta=not want_refreeze)
                    if self.stats["delta_updates"] > before[0]:
                        device = "delta"
                        elems = self.stats["delta_elems"] - before[2]
                    elif self.stats["refreezes"] > before[1]:
                        device = "refreeze"
            return IngestReport(
                n=int(keys.shape[0]), slot=counts["slot"],
                chain=counts["chain"], contested=counts["contested"],
                epoch=self.epoch, device=device,
                device_elems=elems, seconds=time.perf_counter() - t0,
                placement=placement,
                abort_reasons=getattr(self, "_last_abort_reasons", ()),
                fused_aborts=self.stats.get("fused_abort_total", 0),
                split_commits=self.stats.get("split_commits", 0))

    def _roll_caps(self) -> None:
        """Advance the keycap cache to the current epoch UNCHANGED —
        for mutations that cannot worsen key capabilities (payload
        updates; deletes, which can only remove aliasing: stale wide
        or inexact flags err conservative)."""
        cached = self._keycap_cache
        if cached is not None:
            self._keycap_cache = (self.epoch,) + cached[1:]

    def remove(self, keys) -> int:
        """Batched delete; device state follows lazily (next device
        lookup delta-updates or refreezes as needed)."""
        self._need_gapped()
        keys = np.atleast_1d(np.asarray(keys, np.float64))
        n = self.gapped.delete_batch(keys)
        self._roll_caps()
        self._log_touch(keys)
        return n

    # scalar host ops (thin delegates; epoch bumps via gapped.version)
    def insert(self, key: float, payload: int) -> str:
        self._need_gapped()
        path = self.gapped.insert(key, payload)
        self._key_caps_after_batch(np.array([key], np.float64))
        self._log_touch(np.array([key], np.float64))
        return path

    def insert_batch(self, keys: np.ndarray, payloads: np.ndarray) -> dict:
        """Raw batched insert returning §5.3 path counts (host only; use
        ``ingest`` for the typed report + eager device sync)."""
        self._need_gapped()
        counts = self.gapped.insert_batch(keys, payloads)
        self._log_touch(keys)
        return counts

    def delete(self, key: float) -> bool:
        self._need_gapped()
        out = self.gapped.delete(key)
        self._roll_caps()
        self._log_touch(np.array([key], np.float64))
        return out

    def delete_batch(self, keys: np.ndarray) -> int:
        self._need_gapped()
        out = self.gapped.delete_batch(keys)
        self._roll_caps()
        self._log_touch(np.asarray(keys, np.float64))
        return out

    def update(self, key: float, payload: int) -> bool:
        self._need_gapped()
        out = self.gapped.update(key, payload)
        self._roll_caps()  # payload-only: key capabilities unchanged
        return out

    def update_batch(self, keys: np.ndarray, payloads: np.ndarray) -> int:
        """Batched payload update (ONE epoch bump; payload-only, so the
        next device sync is a pure payload-scatter delta)."""
        self._need_gapped()
        out = self.gapped.update_batch(np.asarray(keys, np.float64),
                                       np.asarray(payloads, np.int64))
        self._roll_caps()
        return out

    # ------------------------------------------------------------------
    # durability (serving/wal.py crash recovery rides on these)
    # ------------------------------------------------------------------
    def save_snapshot(self, directory, *, step: Optional[int] = None,
                      keep: int = 3, wal_lsn: int = 0,
                      extra: Optional[dict] = None) -> str:
        """Write a restorable checkpoint of the full HOST state through
        ``train.checkpoint.CheckpointManager`` — the same array format
        as trainer checkpoints (one fsynced ``.npy`` per array +
        manifest, atomic tmp→rename publish), not a second serializer.
        Device state is never serialized: it is an epoch-keyed cache
        that refreezes lazily after ``restore``.  ``wal_lsn`` records
        the ingest-WAL byte offset this snapshot is consistent with
        (crash recovery replays only records past it — serving/wal.py).
        Returns the published checkpoint directory."""
        import pickle
        from ..train.checkpoint import CheckpointManager
        state = {
            "keys": np.asarray(self.keys, np.float64),
            "mech_pickle": np.frombuffer(
                pickle.dumps(self.mech), np.uint8).copy(),
        }
        meta = {
            "kind": "index",
            "method": self.method,
            "sample_rate": float(self.sample_rate),
            "gap_rho": float(self.gap_rho),
            "gapped": self.gapped is not None,
            "epoch": int(self.epoch),
            "wal_lsn": int(wal_lsn),
        }
        ga = self.gapped
        if ga is not None:
            offsets, lkeys, lpays = ga.export_csr_links()
            state.update(
                slot_key=np.asarray(ga.slot_key, np.float64),
                occupied=np.asarray(ga.occupied, bool),
                payload=np.asarray(ga.payload, np.int64),
                offsets=np.asarray(offsets, np.int64),
                chain_keys=np.asarray(lkeys, np.float64),
                chain_payloads=np.asarray(lpays, np.int64),
            )
            meta["n_keys"] = int(ga.n_keys)
            meta["rho"] = float(ga.rho)
        if extra:
            meta.update(extra)
        s = int(step if step is not None else self.epoch)
        meta["step"] = s
        return CheckpointManager(directory, keep=keep).save(
            s, state, extra=meta)

    @classmethod
    def restore(cls, directory, step: Optional[int] = None):
        """Load a ``save_snapshot`` checkpoint -> ``(index, extra)``.

        Host state is restored bit-identically (arrays verbatim, the
        mechanism via its pickle); ``extra`` is the manifest's metadata
        dict (includes ``wal_lsn``).  Newest step when ``step`` is
        None."""
        import json as _json
        import os as _os
        import pickle
        from ..train.checkpoint import CheckpointManager
        from .links import CSRLinks
        mgr = CheckpointManager(str(directory))
        s = int(step) if step is not None else mgr.latest_step()
        if s is None:
            raise FileNotFoundError(f"no checkpoints in {directory}")
        with open(_os.path.join(str(directory), f"step_{s:08d}",
                                "manifest.json")) as f:
            names = _json.load(f)["leaves"]
        # flat dict of arrays: a same-keyed template sidesteps the
        # treedef-proto deserialization path entirely
        state, meta = mgr.restore(step=s,
                                  template={n: 0 for n in names})
        mech = pickle.loads(
            np.asarray(state["mech_pickle"], np.uint8).tobytes())
        gapped = None
        if meta.get("gapped"):
            slot_key = np.asarray(state["slot_key"], np.float64)
            gapped = _gaps.GappedArray(
                slot_key=slot_key,
                occupied=np.asarray(state["occupied"], bool),
                payload=np.asarray(state["payload"], np.int64),
                links=CSRLinks(
                    int(slot_key.shape[0]),
                    np.asarray(state["offsets"], np.int64),
                    np.asarray(state["chain_keys"], np.float64),
                    np.asarray(state["chain_payloads"], np.int64)),
                mech=mech,
                n_keys=int(meta["n_keys"]),
                rho=float(meta["rho"]),
                version=int(meta["epoch"]))
        idx = cls(keys=np.asarray(state["keys"], np.float64), mech=mech,
                  method=meta["method"], gapped=gapped,
                  sample_rate=float(meta["sample_rate"]),
                  gap_rho=float(meta["gap_rho"]))
        return idx, meta

    # ------------------------------------------------------------------
    # self-tuning: online retrain (the ROADMAP-4 dial)
    # ------------------------------------------------------------------
    def retrain(self, sample_rate: Optional[float] = None, *,
                gap_rho: Optional[float] = None, rng=None,
                method: Optional[str] = None, **mech_kwargs) -> dict:
        """Sampled refit of the LIVE gapped state — the paper's §4
        construction cost applied online.

        Extracts the live (key, payload) set (occupied slots + CSR
        chain keys via ``GappedArray.live_items``), rebuilds the gapped
        array through ``build_gapped`` with mechanism learning on a
        sample (O(n_s)), and swaps it in with the epoch bumped past the
        old one.  The OLD arrays are replaced, never mutated, so any
        outstanding ``GapSnapshot`` pin (``serving.EpochPipeline``)
        keeps serving its epoch bit-identically throughout; the device
        cache is dropped and refreezes lazily at the new epoch.
        Defaults replay the build's settings (``method`` / mech kwargs /
        ``gap_rho``); ``sample_rate`` defaults to the build's rate.
        Returns a record dict (n / seconds / learn_seconds / epoch /
        chains before-after)."""
        self._need_gapped()
        t0 = time.perf_counter()
        old_epoch = self.epoch
        chains_before = self.gapped.link_stats()
        keys, payloads = self.gapped.live_items()
        method = method or self.method
        rate = self.sample_rate if sample_rate is None else float(sample_rate)
        rho = self.gap_rho if gap_rho is None else float(gap_rho)
        kwargs = dict(self.mech_kwargs, **mech_kwargs) if method == \
            self.method else dict(mech_kwargs)
        new = Index.build(keys, method=method, sample_rate=rate,
                          gap_rho=rho, rng=rng, payloads=payloads,
                          **kwargs)
        # swap host state wholesale; epoch stays strictly monotone
        new.gapped.version = old_epoch + 1
        self.keys = new.keys
        self.mech = new.mech
        self.method = new.method
        self.gapped = new.gapped
        self.sample_rate = rate
        self.gap_rho = rho
        self.mech_kwargs = new.mech_kwargs
        self.tuned = new.tuned if new.tuned is not None else self.tuned
        # device state is an epoch-keyed cache of the REPLACED arrays
        self._engine = None
        self._mirror = None
        self._device_epoch = -1
        self._keycap_cache = None
        self._pending_touch = []
        self.stats["retrains"] += 1
        return {
            "n": int(keys.shape[0]),
            "seconds": time.perf_counter() - t0,
            "learn_seconds": float(new.learn_seconds),
            "sample_rate": rate,
            "epoch": int(self.epoch),
            "chains_before": chains_before,
            "chains_after": self.gapped.link_stats(),
        }

    # ------------------------------------------------------------------
    def mdl(self, alpha: float = 1.0) -> _mdl.MDLReport:
        """Evaluate under the §3 MDL framework (positions = logical y).

        Gapped builds are scored on the LIVE key set — occupied slot
        keys plus CSR chain keys from ``GappedArray.live_items()`` —
        against their physical slots, so keys added by ``ingest`` enter
        ``L(D|M)`` / ``max_abs_err`` and the report tracks drift (the
        retrain trigger's input).  A chained key's position is its chain
        owner's slot: exactly where the search lands before the chain
        bisect, i.e. the true correction distance."""
        if self.gapped is not None:
            keys, _ = self.gapped.live_items()
            y = (np.searchsorted(self.gapped.slot_key, keys,
                                 side="right") - 1).astype(np.float64)
        else:
            keys = self.keys
            y = np.arange(keys.shape[0], dtype=np.float64)
        return _mdl.mdl_report(self.method, self.mech, keys, y,
                               alpha=alpha)
