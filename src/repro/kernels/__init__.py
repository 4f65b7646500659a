"""Device kernels for the paper's compute hot-spot: fused batched
learned-index lookup (predict + bounded rank-search over VMEM tiles).

Modules
-------
lookup.py: the FUSED single-dispatch kernel (radix routing + bounded
           search + CSR chain epilogue + payload gather + in-kernel
           fallback flag/compaction, f32 hi/lo pair aware) and the
           legacy multi-op window kernel, both pl.pallas_call +
           BlockSpec (+scalar-prefetch dynamic windows)
ops.py:    the fused XLA pipeline, the legacy multi-op pipeline,
           ``QueryEngine``, and the epoch-versioned freeze/delta-update
           + incremental bound/rank refresh entry points
ref.py:    pure-jnp oracle the kernels are validated against + the
           shared ``chain_hit_index`` fori_loop CSR scan (pair aware)
shard_fanout.py: the multi-device fan-out — stacked per-shard frozen
           images mesh-placed via ``repro.dist.partitioning``, one
           ``shard_map`` graph chaining route -> all-to-all exchange ->
           the per-shard fused search -> inverse-permutation gather
           (see "Shard fan-out contract" below).

The ``Index`` handle contract (who calls what)
----------------------------------------------
``repro.core.Index`` owns this layer.  It freezes host state ONCE
(``freeze_state`` -> ``QueryEngine`` + ``HostMirror``), then keeps the
resident device buffers current across host mutations by **epoch**:

* every host mutation bumps ``index.epoch``; the engine remembers the
  epoch it was frozen at;
* a stale device lookup first calls ``delta_update`` — it re-derives the
  padded numpy images (cheap), diffs them against the host mirror, and
  scatters ONLY changed elements into the device buffers.  Shapes and
  jit statics are frozen with headroom, so compiled executables survive;
* after a delta the handle INCREMENTALLY refreshes the derived read
  tables for just the touched key ranges: the fused path's key->rank
  router rows (``QueryEngine.refresh_rank_rows``) and the per-segment window
  bounds (``query_window_bounds(segments=...)`` ->
  ``QueryEngine.refresh_bounds``) — so the compacted-fallback rate
  stays flat under churn instead of climbing until the policy refreeze.
  Skipped refreshes are SOUND: stale tables only raise fallbacks,
  never wrong results;
* ``delta_update`` declines — and the handle takes a full refreeze —
  when a capacity/static no longer holds (link storage, max-chain
  headroom, payload i32 width, key f32 width) or the diff would touch
  most of the buffers.

Backend decision table (mirrored by ``repro.core.BACKENDS``)
------------------------------------------------------------
=============  ==============  =====  ====================================
engine name    handle name     wide   search stage
=============  ==============  =====  ====================================
``fused``      fused           yes    THE default device path, one lean
                                      dispatch at every batch size, on
                                      every platform: the fused XLA
                                      graph — a two-level key->slot-
                                      rank router that follows the
                                      key CDF collapses route+predict+
                                      window into three gathers + a
                                      ~log2(p99 occupancy) bisect;
                                      escapes return as a MASK and are
                                      patched in O(#escapes) host numpy.
                                      The fused Pallas kernel (in-kernel
                                      radix routing, windowed search
                                      over VMEM tiles, CSR chain
                                      epilogue, per-tile fallback
                                      compaction) serves only engines
                                      built with ``fused_impl="pallas"``
                                      — the TPU compiler refuses it
                                      today (1-D in-kernel gathers).
``pallas``     pallas          no     LEGACY multi-op kernel (debug/ref;
                                      interpreted off-TPU)
``xla``        xla-windowed    yes    legacy multi-op windowed bisect /
                                      flat rank count (debug/reference;
                                      non-forced requests below
                                      ``xla_min_bucket`` downgrade to the
                                      device oracle)
``oracle``     (device oracle) yes    full-array searchsorted/pair bisect
(host numpy)   numpy-oracle    yes    GappedArray.lookup_batch (default
                                      below ``min_device_batch``)
=============  ==============  =====  ====================================

Wide keys: beyond f32 exactness (2^24) keys ride an f32 hi/lo pair
(``split_key_pair``) — lexicographic pair order == numeric order, exact
for integer keys < 2^48.  BOTH fused implementations compare pairs end
to end, so wide keys (e.g. paged-KV composite keys) finally have a
device kernel path; only the legacy kernel is narrow-only.

Ingest backend contract (device-side §5.3, single dispatch)
-----------------------------------------------------------
Writes can be a single fused dispatch, like reads.  On an eligible
device-resident engine with the fused write graph enabled
(``Index.fused_ingest_enabled`` — auto: ON only for engines built with
the explicit ``fused_impl="pallas"``; OFF for the default fused-XLA
engine on every platform: on XLA-CPU the graph's fixed O(state) cost —
full-array carried-key repair scan, functional whole-buffer updates —
loses to the sparse host delta, measured in BENCH_ingest's
``fused_dispatch`` rows, and on the TPU it has not been measured),
``Index.ingest`` issues ONE device invocation (``ops_gap.fused_ingest``,
surfaced as ``QueryEngine.fused_ingest``) whose graph chains four
stages with no host round trip between them:

1. **placement** — the shared per-key body
   (``gap_place.ingest_place_body``; inlined in the fused-XLA graph, or
   composed from the Pallas kernel on explicit Pallas engines) computes
   predicted slot, occupancy, run boundaries (``pv``/``ub``), bracket,
   escape;
2. **slot arm** — scatter the bracketed-free keys/payloads into their
   slots and repair the carried keys with one reverse pair-min scan
   (the associative-scan twin of ``GappedArray._repair_carried``);
3. **chain arm** — a device CSR merge: one pair bisect positions the
   sorted chain keys, a prefix-sum shift relocates every old entry, and
   the offsets advance by a cumsum — the in-graph twin of the host
   ``CSRLinks._merge`` single-allocation merge (no ``np.insert``);
4. **read-table refresh** — the touched key->rank router rows recompute
   against the NEW slot keys and the touched segments' window bounds
   widen in-graph, so the committed engine needs no separate
   ``refresh_rank_rows``/``refresh_bounds`` upload.

The graph is **closure-trivial or abort**: it detects, in-graph, every
shape the host partition's demotion closure could act on — collision
groups, contested rows, D1/D4 demotions, duplicates (in-batch, slot,
or chain), chain/link capacity overflows, placement escapes — and on
any hit returns ``ok=False`` with the buffers UNTOUCHED.  Accepted
batches provably partition as ``slot = free & bracket``/``chain =
rest`` at the target ``ub``, which is exactly what the graph committed;
the handle then advances the authoritative host state through the
normal partition fed the same dispatch's primitives, adopts the device
output buffers (``QueryEngine.adopt_fused_state`` — nothing diffed or
re-uploaded; the mirror goes source-advanced/image-dirty and rebuilds
its padded images lazily on the next host-side delta), and reports
``device="fused"``.  Aborted batches reuse those primitives on the
host-partition + delta path — an abort never wastes the dispatch.

The two-dispatch path (place, then delta sync) remains for everything
the fused gates refuse: ``ops_gap.ingest_place`` / ``QueryEngine
.ingest_place`` computes the primitives alone, with the same contract:

* ``GappedArray.placement_primitives`` is the ORACLE — the device
  result, after the escape patch, must equal it bit-for-bit (property-
  tested in tests/test_ingest_place.py); the host partition then
  consumes either transparently (``insert_batch(..., placements=)``).
* Exactness is gated, not assumed: placement routes to the device when
  the stored AND batch keys are per-key pair-exact (integer keys <
  2^48 — every compare equals the host f64 compare), the mechanism's
  ``predict`` is its exported PLM (pgm/fiting), the device state is at
  the host epoch, and the slot count fits i32/f32 indexing.  A merely
  ALIAS-FREE wide stored set (continuous keys, pairwise distinguishable
  but not per-key reconstructible) no longer refuses outright: the
  device primitives are certified row-by-row on the host with exact
  f64 bracketing checks (``GappedArray.verify_placements``) and failing
  rows recomputed per-key — reported as ``placement="device-verified"``
  (this mode is NOT fused-eligible: certification is host work).
* Slot prediction runs in double-f32 (pair slopes/intercepts carried in
  ``IndexArrays.seg_slope_lo``/``seg_icept_lo``); keys whose prediction
  lands within a padded error band of a .5 rounding boundary return an
  escape MASK and are re-derived host-side in O(#escapes) — the same
  stale-safe escape philosophy as the fused lookup, applied to writes.
* The contested remainder (class C) still replays on the host: scalar
  §5.3 inserts are pointer-chasing by nature; the device's job is the
  O(batch x log) predict/search/classify stage, the host's the few
  order-dependent keys the per-key commutativity analysis cannot clear.

Shard fan-out contract (multi-device read path)
-----------------------------------------------
``repro.dist.ShardedIndex`` extends the decision table one level up:
``backend="fanout"`` (the default for batches >= ``min_device_batch``
when available) runs ONE ``shard_map`` dispatch over the mesh from
``launch.mesh`` — per-shard images stacked on the ``data`` axis by
``shard_fanout.stack_shard_images`` (consensus wide/key_wide statics,
padded to the max shard's shapes), routed by the learned two-segment
router with an in-graph exact bisect backstop (``_route_block``),
exchanged via counting-sort send buffers + ``lax.all_to_all``, searched
by the SAME ``_fused_search``/``_epilogue`` body as the single-device
fused path, and unsorted back by inverse permutation.

* **Exactness**: routing and search are exact in the ROUNDED key
  representation (f32 round-trip narrow, hi/lo pair sum wide); the
  learned router only prices the backstop.  Per-query escape flags ride
  the exchange home, and escaped/dropped rows are re-resolved through
  each owning shard's host views in O(#escapes) — the same stale-safe
  philosophy as the fused lookup, across shards.
* **Availability is gated, not assumed** (``ShardFanout.build`` raises
  ``FanoutUnavailable``): PLM-mechanism shards only, pair-exact wide
  key sets, strictly ordered rounded shard boundaries, and freezable
  capacities.  The handle then falls back to the exact grouped host
  route; only an explicit ``backend="fanout"`` request surfaces the
  refusal as an error.
* **Capacity, not correctness**: exchange buffers are sized by an
  occupancy heuristic with a sticky per-bucket boost; overflow drops
  are counted, flagged, and host-patched — skew costs escapes, never
  wrong answers.
* The fan-out serves a FROZEN shard set: any shard mutation (ingest,
  split) retags the epochs and the next large lookup rebuilds the
  stacked images (incremental per-shard delta into the stacked images
  is deferred — see ROADMAP).

Fused-path contract
-------------------
``engine.lookup(queries, queries_sorted=..., backend=...)`` returns
``(payloads, slot, found, fb_count)`` — ``found`` covers first-level AND
linking-chain hits (the ``LookupResult.found`` mask).

1. **Single dispatch**: the whole route -> search -> chain epilogue ->
   payload pipeline runs in one device invocation.  Escaped queries
   (rank-row staleness, p99-truncated bisect, tile-window misses) are
   flagged by a bracket validation that makes results exact INDEPENDENT
   of the routing tables, and re-resolved in O(#escapes): host numpy on
   the fused XLA path, a compacted fixed-capacity device buffer behind
   a ``lax.cond`` on the fused Pallas path.
2. **Small-batch regime**: the fused path is never downgraded — it owns
   every bucket size (the recorded crossover vs the device oracle in
   ``BENCH_kernel.json`` is the gate).
3. **Sort-aware scheduling**: the Pallas paths need ascending queries;
   callers that already issue sorted batches pass ``queries_sorted=True``
   and skip the lexsort/argsort round trip.  The fused XLA and oracle
   backends are permutation-free.
4. **Shape buckets**: query batches are padded (+inf tail — sorted stays
   sorted) up to power-of-two buckets so each bucket compiles once.
5. **Wide payloads**: int64 payloads are carried as an i32 hi/lo pair
   and reconstructed after the epilogue (``IndexArrays.wide``).

Serving & durability contract (how this layer is consumed live)
---------------------------------------------------------------
``repro.serving.EpochPipeline`` double-buffers the handle for
concurrent serving: lookups run against a pinned immutable snapshot
(the frozen first-level arrays + CSR image — ``GappedArray
.pin_snapshot``, O(1) pin, copy-on-write on the live side) while
ingest mutates the live index through the contracts above.  Two
consequences for THIS layer:

* the kernels never see snapshot state — snapshots serve via the host
  oracle path, which the backend decision table already requires to be
  bit-identical to every device backend, so snapshot isolation comes
  for free from the existing exactness contract;
* fused-ingest aborts stay cheap under serving: an aborted dispatch's
  primitives are reused host-side (never wasted), and when the abort
  reason is *localized* the handle commits the clean PREFIX of the
  batch through a second fused dispatch and routes only the remainder
  through the host path (``placement="device-split"``,
  ``IngestReport.split_commits``) — so one contested key no longer
  demotes a whole large batch off the device.

Durability (``repro.serving.wal``: CRC-framed write-ahead log +
``Index.save_snapshot`` checkpoints) is layered strictly ABOVE the
engine: recovery replays acked batches through the normal ``ingest``
entry point, so a recovered index re-derives device state through the
same freeze/delta/fused machinery — nothing in this layer needs to be
crash-aware.

Machine-checked invariants (``repro.analysis``)
-----------------------------------------------
Two contracts in this package are enforced by the repo's static
analyzer (``scripts/lint.sh`` -> ``python -m repro.analysis``, part of
tier-1), not just by convention:

* **trace-safety** (rules ``trace-host-sync``, ``trace-py-branch``,
  ``trace-dyn-shape``, ``trace-self-capture``, ``trace-np-call``):
  inside jit-compiled functions and ``fori_loop``/``scan``/``cond``
  bodies, no host syncs (``.block_until_ready()``, ``float()``/
  ``int()``/``bool()`` on tracers), no Python ``if``/``while`` on
  traced values (identity tests like ``x is None`` are exempt — they
  never concretize), no data-dependent ``.reshape``/``np.*`` on traced
  operands, and no ``self`` capture in traced closures (it pins host
  state into the compiled graph).  The checker threads taint
  interprocedurally, so the package's static-flag idiom (``key_wide``,
  ``n_slots``... passed from ``static_argnames`` roots through
  helpers) is understood, not suppressed.
* **pair-exactness** (rules ``pair-f64-const``, ``pair-raw-fma``): in
  ``gap_place.py`` / ``lookup.py`` / ``ops_gap.py``, no float64
  intermediates (TPU demotes them silently) and no raw ``a * b + c``
  where the hi/lo pair contract requires ``two_sum``/``two_prod``
  error-free transforms.  Deliberately-approximate sites carry an
  inline ``# repro-lint: disable=... -- why`` justification.

Migration notes
---------------
``QueryEngine.from_index(idx)`` + manual refreeze-after-mutation is the
legacy pattern; prefer holding a ``repro.core.Index`` and calling
``index.lookup`` / ``index.ingest`` — the handle schedules freezes,
delta updates, and incremental refreshes for you and returns typed
results.  ``from_learned_index`` remains the raw freeze (no headroom,
no mirror) for kernel tests and benchmarks.
"""

from .ops import (HostMirror, IndexArrays, QueryEngine, batched_lookup,
                  build_radix_router, build_rank_router, delta_update,
                  freeze_state, from_learned_index, keys_need_pair,
                  keys_pair_exact, pair_alias_free, split_key_pair)
from .ops_gap import (fused_ingest, gap_positions_device,
                      gap_positions_oracle, ingest_place)
from .ref import chain_hit_index, lookup_ref, predict_ref, resolve_chains
from .shard_fanout import (FanoutUnavailable, ShardFanout,
                           stack_shard_images)

__all__ = [
    "FanoutUnavailable",
    "HostMirror",
    "IndexArrays",
    "QueryEngine",
    "ShardFanout",
    "batched_lookup",
    "build_radix_router",
    "build_rank_router",
    "chain_hit_index",
    "delta_update",
    "freeze_state",
    "from_learned_index",
    "fused_ingest",
    "gap_positions_device",
    "gap_positions_oracle",
    "ingest_place",
    "keys_need_pair",
    "keys_pair_exact",
    "lookup_ref",
    "pair_alias_free",
    "predict_ref",
    "resolve_chains",
    "split_key_pair",
    "stack_shard_images",
]
