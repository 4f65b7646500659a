"""Fused single-dispatch query engine — the device half of
``repro.core.Index``.

``IndexArrays`` freezes the host state of an index into f32/i32 device
arrays; ``batched_lookup`` / ``QueryEngine`` run the lookup.  The
DEFAULT path, on every platform, is the fused single dispatch (backend
``"fused"``) served by the fused XLA graph (``_fused_pipeline``): a
precomputed bucket->slot-rank table collapses route+predict+window into
two gathers plus a ~log2(p99 bucket occupancy) fixed-trip bisect, the
epilogue is fused behind it, and the escape MASK rides home with the
outputs for an O(#escapes) host-numpy patch.

The fused Pallas kernel (lookup.py — radix routing, bounded window
search, CSR chain epilogue, payload gather and per-tile fallback
compaction in ONE ``pallas_call``) runs only when a caller asks for it
(``QueryEngine(fused_impl="pallas")``): the TPU compiler refuses it
today (1-D in-kernel gathers, a rank-1 count block that is not a
multiple of 128, whole-table VMEM blocks).

A trailing bracket validation (``slot_key[r] <= q < slot_key[r+1]``)
makes the fused result exact INDEPENDENT of the routing tables: a stale
rank row or truncated bisect surfaces as a fallback flag, never a wrong
slot.  The legacy multi-op stages survive as debug/reference backends:

    [sort]* -> windowed search (legacy Pallas kernel / XLA fixed-trip
    windowed bisect) -> COMPACTED device fallback re-resolution ->
    fused payload + CSR epilogue -> [unsort]*

(* only on Pallas paths with unsorted queries — the XLA backends are
permutation-free, and ``queries_sorted=True`` skips the sort round
trip for callers that already issue sorted batches.)

On every backend the full-array oracle is NEVER evaluated over the
whole batch: escapes resolve in O(#escapes) (host patch on the fused
XLA path; fixed-capacity compacted buffers elsewhere, whose overflow —
legacy paths only — re-dispatches to the oracle backend, counted in
``QueryEngine.stats`` and asserted in tests/test_query_engine.py).

Epoch-versioned device state (``repro.core.Index``)
---------------------------------------------------
``freeze_state`` builds an engine plus a **host mirror** of the padded
device buffers; after host mutations, ``delta_update`` re-derives the
padded arrays (cheap numpy), diffs them against the mirror, and
scatters ONLY the changed elements into the resident device buffers —
slot_key/payload entries for slot placements, CSR link-table tail
regions for chain appends.  Shape/dtype statics (link capacity,
max-chain headroom, payload width, key width) are frozen with headroom;
when exceeded — or when the diff would touch most of the arrays —
``delta_update`` declines and the handle takes a full refreeze.

Wide keys (f32 hi/lo pairs)
---------------------------
Keys that exceed f32 exactness (>2^24 integer magnitudes, e.g. paged-KV
composite keys) are carried as an (hi, lo) f32 pair with
``lo = key - f64(hi)``; lexicographic pair order equals numeric order
and the representation is exact for integer keys below 2^48.  The XLA
windowed and oracle backends compare pairs end to end (search, window
edges, compacted fallback, CSR chain bisect); the legacy Pallas kernel
is narrow-key only — the capability registry (repro.core.handle)
refuses it for wide-key indexes.

Everything is shape-static and jit-friendly; ``QueryEngine`` buckets
query shapes so the serving path stops re-tracing per batch.
``interpret`` runs a Pallas kernel body in Python; every entry point
defaults it from the platform (``lookup.resolve_interpret``: on off the
TPU, off on it), so no kernel runs interpreted on a TPU unless a caller
asks.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import ref as _ref
from .lookup import fused_lookup_call, lookup_kernel_call, resolve_interpret

__all__ = ["IndexArrays", "QueryEngine", "batched_lookup",
           "build_radix_router", "from_learned_index", "freeze_state",
           "delta_update", "HostMirror", "keys_need_pair",
           "keys_pair_exact", "split_key_pair"]

_I32_MIN = np.iinfo(np.int32).min
_I32_MAX = np.iinfo(np.int32).max
FB_FRAC = 0.02  # compaction buffer sizing: ~2% of the batch


def _pad_pow(a: np.ndarray, multiple: int, fill) -> np.ndarray:
    n = a.shape[0]
    m = ((n + multiple - 1) // multiple) * multiple
    if m == n:
        return a
    return np.concatenate([a, np.full(m - n, fill, a.dtype)])


def keys_need_pair(keys) -> bool:
    """True when the keys exceed f32 exactness (need the hi/lo pair)."""
    k = np.asarray(keys, np.float64)
    f = k[np.isfinite(k)]
    if f.size == 0:
        return False
    return not bool(np.all(f.astype(np.float32).astype(np.float64) == f))


def keys_pair_exact(keys) -> bool:
    """True when every key is represented EXACTLY by its f32 hi/lo pair
    (hi + lo == key in f64 — holds e.g. for all integer keys < 2^48).
    An all-exact key set maps injectively to pairs, so the device search
    is exact by construction."""
    k = np.asarray(keys, np.float64)
    f = k[np.isfinite(k)]
    if f.size == 0:
        return True
    hi, lo = split_key_pair(f)
    return bool(np.all(hi.astype(np.float64) + lo.astype(np.float64) == f))


def pair_alias_free(sorted_keys) -> bool:
    """True when no two DISTINCT keys of this sorted array share an f32
    hi/lo pair.  The weaker (and sufficient) device-search requirement
    for key sets that are not per-key pair-exact (continuous f64 keys):
    the pair compare then never conflates two stored keys — the residual
    hazard is only an absent query within pair resolution (~2^-48
    relative) of a stored key, the same hazard class the plain-f32 path
    always had at 2^-24."""
    k = np.asarray(sorted_keys, np.float64)
    f = k[np.isfinite(k)]
    if f.size < 2:
        return True
    hi, lo = split_key_pair(f)
    same_pair = (hi[1:] == hi[:-1]) & (lo[1:] == lo[:-1])
    distinct = f[1:] != f[:-1]
    return not bool(np.any(same_pair & distinct))


def split_key_pair(keys):
    """(hi, lo) f32 pair with ``lo = key - f64(hi)``.

    Lexicographic (hi, lo) order equals numeric order (f32 rounding is
    monotone); exact for integer keys < 2^48 (hi is then a multiple of a
    power of two and the residual fits 24 mantissa bits) — the ROADMAP
    "f64 device keys" item.  Non-finite keys get lo = 0.
    """
    k = np.asarray(keys, np.float64)
    hi = k.astype(np.float32)
    with np.errstate(invalid="ignore"):
        lo = k - hi.astype(np.float64)
    lo = np.where(np.isfinite(k), lo, 0.0)
    return hi, lo.astype(np.float32)


@dataclasses.dataclass(frozen=True)
class IndexArrays:
    """Frozen device-side index state (all f32/i32, shape-static).

    64-bit payloads are carried as a hi/lo i32 pair (``wide=True``);
    keys beyond f32 exactness as an f32 hi/lo pair (``key_wide=True``).
    Narrow builds keep the corresponding ``*_lo`` / ``*_hi`` arrays
    zero-length, so they cost nothing.
    """

    seg_first_key: jax.Array     # (Kpad,) f32, +inf padded
    seg_first_key_lo: jax.Array  # (Kpad,) f32 when key_wide else (0,)
    seg_slope: jax.Array         # (Kpad,) f32
    seg_icept: jax.Array         # (Kpad,) f32
    # f32 residuals of the f64 slopes/intercepts (double-f32 pairs) —
    # the ingest-place backend predicts insert slots on device to the
    # host's rounding behavior (ops_gap.ingest_place); lookup paths
    # never read them (window search absorbs prediction error)
    seg_slope_lo: jax.Array      # (Kpad,) f32
    seg_icept_lo: jax.Array      # (Kpad,) f32
    slot_key: jax.Array          # (Mpad,) f32, +inf padded
    slot_key_lo: jax.Array       # (Mpad,) f32 when key_wide else (0,)
    payload: jax.Array           # (Mpad,) i32 — low 32 payload bits
    payload_hi: jax.Array        # (Mpad,) i32 when wide else (0,)
    link_offsets: jax.Array      # (Mpad + w_tile,) i32 (tail = total)
    link_keys: jax.Array         # (Lpad,) f32
    link_keys_lo: jax.Array      # (Lpad,) f32 when key_wide else (0,)
    link_payloads: jax.Array     # (Lpad,) i32 — low 32 payload bits
    link_payload_hi: jax.Array   # (Lpad,) i32 when wide else (0,)
    n_slots: int                 # true (unpadded) slot count
    max_chain: int
    wide: bool                   # payloads need the hi/lo i64 reconstruction
    key_wide: bool               # keys carried as an f32 hi/lo pair


def _split_i64(a: np.ndarray):
    """(lo32, hi32) two's-complement split of an int64 array."""
    a = np.asarray(a, np.int64)
    return a.astype(np.int32), (a >> 32).astype(np.int32)


class _CapacityError(Exception):
    """Frozen capacity/static exceeded — delta declined, refreeze."""


_NP_FIELDS = ("seg_first_key", "seg_first_key_lo", "seg_slope",
              "seg_icept", "seg_slope_lo", "seg_icept_lo",
              "slot_key", "slot_key_lo", "payload", "payload_hi",
              "link_offsets", "link_keys", "link_keys_lo", "link_payloads",
              "link_payload_hi")

# fields a host mutation can change (mech/seg tables never move)
_DELTA_FIELDS = ("slot_key", "slot_key_lo", "payload", "payload_hi",
                 "link_offsets", "link_keys", "link_keys_lo",
                 "link_payloads", "link_payload_hi")


def _freeze_numpy(index, *, w_tile: int = 2048, seg_chunk: int = 512,
                  max_chain: Optional[int] = None,
                  link_cap: Optional[int] = None,
                  force_wide: Optional[bool] = None,
                  force_key_wide: Optional[bool] = None):
    """Derive the padded numpy device images from host state.

    Raises ``_CapacityError`` when a forced static (chain bound, link
    capacity, payload/key width) cannot hold the current state.
    Returns ``(arrays: dict[str, np.ndarray], statics: dict)``.
    """
    plm = getattr(index.mech, "plm", None)
    if plm is None:
        raise ValueError("mechanism does not export a piecewise linear model")
    if index.gapped is not None:
        ga = index.gapped
        slot_key = np.asarray(ga.slot_key, np.float64)
        payload = np.asarray(ga.payload, np.int64)
        offsets, lkeys, lpay = ga.export_csr_links()
        chain = ga.links.max_chain
        live = payload[np.asarray(ga.occupied, bool)]
    else:
        slot_key = np.asarray(index.keys, np.float64)
        payload = np.arange(slot_key.shape[0], dtype=np.int64)
        offsets = np.zeros(slot_key.shape[0] + 1, np.int64)
        lkeys = np.zeros(0, np.float64)
        lpay = np.zeros(0, np.int64)
        chain = 0
        live = payload
    if max_chain is None:
        max_chain = int(chain)
    elif chain > max_chain:
        raise _CapacityError(f"max_chain {chain} > frozen {max_chain}")

    wide = bool(
        (live.size and (live.min() < _I32_MIN or live.max() > _I32_MAX))
        or (lpay.size and (lpay.min() < _I32_MIN or lpay.max() > _I32_MAX))
    )
    if force_wide is not None:
        if wide and not force_wide:
            raise _CapacityError("payloads outgrew the narrow i32 freeze")
        wide = force_wide
    key_wide = keys_need_pair(slot_key) or keys_need_pair(lkeys)
    if force_key_wide is not None:
        if key_wide and not force_key_wide:
            raise _CapacityError("keys outgrew the narrow f32 freeze")
        key_wide = force_key_wide

    n_slots = slot_key.shape[0]
    sk_hi, sk_lo = split_key_pair(slot_key)
    skp = _pad_pow(sk_hi, w_tile, np.float32(np.inf))
    # one extra +inf block so index_map's (b, b+1) pair is always valid
    skp = np.concatenate([skp, np.full(w_tile, np.inf, np.float32)])
    sklp = np.concatenate(
        [_pad_pow(sk_lo, w_tile, np.float32(0)),
         np.zeros(w_tile, np.float32)])
    pay_lo, pay_hi = _split_i64(payload)
    m_extra = skp.shape[0] - pay_lo.shape[0]
    pay_lo = np.concatenate([pay_lo, np.full(m_extra, -1, np.int32)])
    pay_hi = np.concatenate([pay_hi, np.full(m_extra, -1, np.int32)])

    if link_cap is None:
        link_cap = int(lkeys.shape[0])
    elif lkeys.shape[0] > link_cap:
        raise _CapacityError(
            f"links {lkeys.shape[0]} > frozen capacity {link_cap}")
    lk_hi, lk_lo = split_key_pair(lkeys)
    l_extra = link_cap - lkeys.shape[0]
    lk_hi = np.concatenate([lk_hi, np.full(l_extra, np.inf, np.float32)])
    lk_lo = np.concatenate([lk_lo, np.zeros(l_extra, np.float32)])
    lpay_lo, lpay_hi = _split_i64(lpay)
    lpay_lo = np.concatenate([lpay_lo, np.full(l_extra, -1, np.int32)])
    lpay_hi = np.concatenate([lpay_hi, np.full(l_extra, -1, np.int32)])
    # offsets padded past the slot blocks so the fused kernel's THREE
    # offset window blocks (b, b+1, b+2 — slot+1 can land one element
    # past the 2*w_tile window) are always in range
    offp = np.concatenate(
        [offsets, np.full(skp.shape[0] + w_tile - offsets.shape[0],
                          offsets[-1])]
    ).astype(np.int32)
    none32f = np.zeros(0, np.float32)
    none32i = np.zeros(0, np.int32)

    sfk = np.asarray(plm.seg_first_key, np.float64)
    sfk_hi, sfk_lo = split_key_pair(sfk)
    arrays = {
        "seg_first_key": _pad_pow(sfk_hi, seg_chunk, np.float32(np.inf)),
        "seg_first_key_lo": (
            np.concatenate([sfk_lo,
                            np.zeros(_pad_pow(sfk_hi, seg_chunk,
                                              np.float32(np.inf)).shape[0]
                                     - sfk_lo.shape[0], np.float32)])
            if key_wide else none32f),
        "seg_slope": _pad_pow(np.asarray(plm.slope, np.float32), seg_chunk,
                              np.float32(0)),
        "seg_icept": _pad_pow(np.asarray(plm.icept, np.float32), seg_chunk,
                              np.float32(n_slots - 1)),
        # double-f32 residuals (slope - f32(slope), icept - f32(icept))
        # for the ingest-place backend's on-device slot prediction
        "seg_slope_lo": _pad_pow(
            (np.asarray(plm.slope, np.float64)
             - np.asarray(plm.slope, np.float32).astype(np.float64)
             ).astype(np.float32), seg_chunk, np.float32(0)),
        "seg_icept_lo": _pad_pow(
            (np.asarray(plm.icept, np.float64)
             - np.asarray(plm.icept, np.float32).astype(np.float64)
             ).astype(np.float32), seg_chunk, np.float32(0)),
        "slot_key": skp,
        "slot_key_lo": sklp if key_wide else none32f,
        "payload": pay_lo,
        "payload_hi": pay_hi if wide else none32i,
        "link_offsets": offp,
        "link_keys": lk_hi,
        "link_keys_lo": lk_lo if key_wide else none32f,
        "link_payloads": lpay_lo,
        "link_payload_hi": lpay_hi if wide else none32i,
    }
    statics = {"n_slots": n_slots, "max_chain": int(max_chain),
               "wide": wide, "key_wide": key_wide, "w_tile": w_tile,
               "seg_chunk": seg_chunk, "link_cap": int(link_cap)}
    return arrays, statics


def _to_device(arrays: dict, statics: dict) -> IndexArrays:
    return IndexArrays(
        **{f: jnp.asarray(arrays[f]) for f in _NP_FIELDS},
        n_slots=statics["n_slots"], max_chain=statics["max_chain"],
        wide=statics["wide"], key_wide=statics["key_wide"],
    )


def from_learned_index(index, *, w_tile: int = 2048, seg_chunk: int = 512,
                       max_chain: Optional[int] = None) -> IndexArrays:
    """Freeze an index (``repro.core.Index`` or the legacy
    ``LearnedIndex`` shim) for the device query path.

    Payloads wider than int32 are carried as a hi/lo i32 pair and
    reconstructed to i64 in the epilogue (live payloads only — the
    unoccupied-slot marker is never read because carried keys route
    equal-key runs to their occupied tail slot).  Keys beyond f32
    exactness are carried as an f32 hi/lo pair (``key_wide``).
    """
    arrays, statics = _freeze_numpy(index, w_tile=w_tile,
                                    seg_chunk=seg_chunk, max_chain=max_chain)
    return _to_device(arrays, statics)


# ---------------------------------------------------------------------------
# pair-comparison helpers (wide keys)
# ---------------------------------------------------------------------------


def _ple(kh, kl, qh, ql):
    """Lexicographic (hi, lo) <=, elementwise."""
    return (kh < qh) | ((kh == qh) & (kl <= ql))


def _peq(kh, kl, qh, ql):
    return (kh == qh) & (kl == ql)


def _pair_bisect(kh, kl, qh, ql, lo0, hi0, trips):
    """Rightmost index in [lo0, hi0] with pair(key) <= pair(q); branchless
    fixed-trip bisect (lo0 may start at -1)."""
    m_max = kh.shape[0] - 1

    def body(_, carry):
        lo, hi = carry
        upd = lo < hi
        mid = (lo + hi + 1) >> 1
        midc = jnp.clip(mid, 0, m_max)
        go = _ple(jnp.take(kh, midc), jnp.take(kl, midc), qh, ql)
        lo = jnp.where(upd & go, mid, lo)
        hi = jnp.where(upd, jnp.where(go, hi, mid - 1), hi)
        return lo, hi

    lo, _ = jax.lax.fori_loop(0, trips, body, (lo0, hi0))
    return lo


def _pair_oracle(qh, ql, slot_key, slot_key_lo):
    """Full-array pair search (the wide-key oracle): slot + found."""
    m_pad = slot_key.shape[0]
    trips = int(np.ceil(np.log2(max(m_pad, 2)))) + 1
    lo0 = jnp.full(qh.shape, -1, jnp.int32)
    hi0 = jnp.full(qh.shape, m_pad - 1, jnp.int32)
    slot = _pair_bisect(slot_key, slot_key_lo, qh, ql, lo0, hi0, trips)
    safe = jnp.maximum(slot, 0)
    found = (slot >= 0) & _peq(jnp.take(slot_key, safe),
                               jnp.take(slot_key_lo, safe), qh, ql)
    return slot.astype(jnp.int32), found


# ---------------------------------------------------------------------------
# pipeline stages (all shape-static, called under one jit)
# ---------------------------------------------------------------------------


def _epilogue(queries, queries_lo, slot, found, payload, payload_hi,
              link_offsets, link_keys, link_keys_lo, link_payloads,
              link_payload_hi, max_chain, wide, key_wide):
    """Fused slot->payload gather + CSR chain scan (hi/lo aware).

    Returns ``(lo32, hi32, resolved)``; ``hi32`` is zero-length when
    narrow, ``resolved`` marks keys present in the first level OR a
    chain (the typed-result found mask).  The i64 reconstruction happens
    on the host (x64 may be disabled in jax).
    """
    safe_slot = jnp.clip(slot, 0, payload.shape[0] - 1)
    hit = _ref.chain_hit_index(
        queries, slot, found, link_offsets, link_keys, max_chain,
        queries_lo=queries_lo if key_wide else None,
        link_keys_lo=link_keys_lo if key_wide else None)
    has_links = link_keys.shape[0] > 0 and max_chain > 0
    out = jnp.where(found, jnp.take(payload, safe_slot), jnp.int32(-1))
    resolved = found
    if has_links:
        out = jnp.where(hit >= 0,
                        jnp.take(link_payloads, jnp.maximum(hit, 0)), out)
        resolved = found | (hit >= 0)
    if not wide:
        return out, jnp.zeros((0,), jnp.int32), resolved
    out_hi = jnp.where(found, jnp.take(payload_hi, safe_slot), jnp.int32(-1))
    if has_links:
        out_hi = jnp.where(
            hit >= 0, jnp.take(link_payload_hi, jnp.maximum(hit, 0)), out_hi)
    return out, out_hi, resolved


def _xla_window_lookup(queries, queries_lo, seg_first_key, seg_first_key_lo,
                       seg_slope, seg_icept, err_lo_by_seg, err_hi_by_seg,
                       slot_key, slot_key_lo, n_slots, trips, flat_w,
                       key_wide, radix_table=None, radix_scale=None):
    """XLA analog of the Pallas kernel: per-query bounded window search.

    The mechanism's error bounds give each query a slot window.  Narrow
    typical windows (``flat_w > 0``) use a loop-free rank count — one
    (Q, W) gather + compare + sum, mirroring the kernel's masked-count
    search.  Wide-window indexes (``flat_w == 0``) use a fixed-trip
    branchless bisect instead.  Queries whose true bracket escapes the
    window raise the same fallback flag as the kernel — no oracle pass
    here.  Cost: O(W) clustered reads or O(trips) clustered gathers vs
    the oracle's O(log Mpad) full-array probes.

    ``radix_table``/``radix_scale`` (engine-built) replace the exact
    segment-routing searchsorted with one multiply + one table gather.
    The routing may be off by a segment near bucket boundaries — that is
    SOUND: a mid-window rank is globally correct whatever the window
    placement (slot_key is totally ordered), and edge ranks raise the
    fallback flag.  With ``key_wide`` every key compare is an f32 hi/lo
    pair compare, and predictions subtract the segment anchor in pair
    arithmetic so large-magnitude keys keep their relative precision.
    """
    m_pad = slot_key.shape[0]
    # fold the error bounds into per-segment intercepts (K-sized ops are
    # free; saves two full-batch gathers)
    icept_lo = seg_icept + err_lo_by_seg - 1.0
    icept_hi = seg_icept + err_hi_by_seg + 1.0
    seg = _route_segment(queries, queries_lo, seg_first_key,
                         seg_first_key_lo, key_wide,
                         radix_table=radix_table, radix_scale=radix_scale)
    if key_wide:
        # pair-anchored delta: (qh - fkh) is (near-)exact by Sterbenz for
        # same-segment magnitudes; ql - fkl restores the f64 residual
        dx = ((queries - jnp.take(seg_first_key, seg))
              + (queries_lo - jnp.take(seg_first_key_lo, seg)))
    else:
        dx = queries - jnp.take(seg_first_key, seg)
    sl = jnp.take(seg_slope, seg)
    lo0 = jnp.clip(jnp.floor(sl * dx + jnp.take(icept_lo, seg)),
                   0.0, float(n_slots - 1)).astype(jnp.int32)
    hi0 = jnp.clip(jnp.ceil(sl * dx + jnp.take(icept_hi, seg)),
                   0.0, float(n_slots - 1)).astype(jnp.int32)
    hi0 = jnp.maximum(hi0, lo0)

    if flat_w:
        # flat masked rank count (loop-free).  ``flat_w`` covers the p95
        # segment window, NOT the widest: a query whose bracket escapes
        # [lo0, lo0+W) hits the rank==0/rank==W edge flags below and is
        # re-resolved by the compacted fallback — still single-pass.
        width = flat_w
        offs = jnp.arange(width, dtype=jnp.int32)
        idx = jnp.minimum(lo0[:, None] + offs[None, :], m_pad - 1)
        ks = jnp.take(slot_key, idx)
        if key_wide:
            ksl = jnp.take(slot_key_lo, idx)
            le = _ple(ks, ksl, queries[:, None], queries_lo[:, None])
            eq = _peq(ks, ksl, queries[:, None], queries_lo[:, None])
        else:
            le = ks <= queries[:, None]
            eq = ks == queries[:, None]
        rank = jnp.sum(le.astype(jnp.int32), axis=1)
        slot = lo0 - 1 + rank
        found = (slot >= 0) & jnp.any(eq, axis=1)
        fb_lo = (rank == 0) & (lo0 > 0)
        edge = jnp.minimum(lo0 + width, m_pad - 1)
        if key_wide:
            fb_hi = (rank == width) & _ple(
                jnp.take(slot_key, edge), jnp.take(slot_key_lo, edge),
                queries, queries_lo)
        else:
            fb_hi = (rank == width) & (jnp.take(slot_key, edge) <= queries)
        fb = (fb_lo | fb_hi) & jnp.isfinite(queries)
        return slot, found, fb

    if key_wide:
        slot = _pair_bisect(slot_key, slot_key_lo, queries, queries_lo,
                            lo0 - 1, hi0, trips)
        safe = jnp.clip(slot, 0, m_pad - 1)
        found = (slot >= 0) & _peq(jnp.take(slot_key, safe),
                                   jnp.take(slot_key_lo, safe),
                                   queries, queries_lo)
        edge = jnp.minimum(hi0 + 1, m_pad - 1)
        fb_hi = (slot == hi0) & _ple(jnp.take(slot_key, edge),
                                     jnp.take(slot_key_lo, edge),
                                     queries, queries_lo)
    else:
        def body(_, carry):
            lo, hi = carry
            upd = lo < hi
            mid = (lo + hi + 1) >> 1
            go = jnp.take(slot_key, jnp.clip(mid, 0, m_pad - 1)) <= queries
            lo = jnp.where(upd & go, mid, lo)
            hi = jnp.where(upd, jnp.where(go, hi, mid - 1), hi)
            return lo, hi

        slot, _ = jax.lax.fori_loop(0, trips, body, (lo0 - 1, hi0))
        safe = jnp.clip(slot, 0, m_pad - 1)
        found = (slot >= 0) & (jnp.take(slot_key, safe) == queries)
        fb_hi = (slot == hi0) & (
            jnp.take(slot_key, jnp.minimum(hi0 + 1, m_pad - 1)) <= queries
        )
    fb_lo = (slot == lo0 - 1) & (lo0 > 0)
    fb = (fb_lo | fb_hi) & jnp.isfinite(queries)
    return slot, found, fb


@functools.partial(
    jax.jit,
    static_argnames=("trips", "max_chain", "wide", "key_wide"),
)
def _fused_pipeline(
    queries, queries_lo, slot_key, slot_key_lo, payload, payload_hi,
    link_offsets, link_keys, link_keys_lo, link_payloads, link_payload_hi,
    rank_table, rank_scale,
    *, trips, max_chain, wide, key_wide,
):
    """The fused-XLA single dispatch: rank-routed bounded search + fused
    epilogue, in a DEDICATED lean jit (a dozen operands — the shared
    multi-backend ``_pipeline`` carries ~23, and per-argument dispatch
    overhead is real money at small batch).

    No device-side compaction: XLA-CPU lowers cumsum/scatter to scalar
    loops that cost more than the whole search, so the escape MASK
    rides home with the outputs and the caller patches the (rare)
    flagged queries in O(#escapes) host numpy — there is no
    overflow/oracle-escape concept on this path.
    """
    slot, found, fb = _fused_search(
        queries, queries_lo, slot_key, slot_key_lo,
        rank_table, rank_scale, trips, key_wide,
    )
    out, out_hi, resolved = _epilogue(
        queries, queries_lo, slot, found, payload, payload_hi,
        link_offsets, link_keys, link_keys_lo, link_payloads,
        link_payload_hi, max_chain, wide, key_wide)
    return out, out_hi, slot, resolved, fb


def _compact_fallback(queries, queries_lo, slot, found, fb, slot_key,
                      slot_key_lo, fb_cap, key_wide):
    """Re-resolve ONLY the fb-flagged queries via a fixed-capacity buffer.

    Gathers the flagged queries into a (fb_cap,)-shaped compacted batch
    (one cumsum + one scatter), binary-searches just those, and scatters
    the corrections back (out-of-range fill indices are dropped).  The
    whole stage sits behind a ``lax.cond`` so the hit-heavy common case
    (zero flags) pays one reduction and nothing else.  Returns the
    overflow flag the host uses for the full-oracle escape hatch.
    """
    n_q = queries.shape[0]
    fb_count = jnp.sum(fb.astype(jnp.int32))
    overflow = fb_count > fb_cap

    def compact(args):
        slot, found = args
        # the compaction cumsum lives INSIDE the cond: the hit-heavy
        # common case (zero flags) pays one reduction and nothing else
        pos = jnp.cumsum(fb.astype(jnp.int32)) - 1
        dst = jnp.where(fb & (pos < fb_cap), pos, fb_cap)
        idx = jnp.full((fb_cap + 1,), n_q, jnp.int32).at[dst].set(
            jnp.arange(n_q, dtype=jnp.int32))[:fb_cap]
        q_fb = jnp.take(queries, idx, mode="clip")
        if key_wide:
            ql_fb = jnp.take(queries_lo, idx, mode="clip")
            slot_fb, found_fb = _pair_oracle(q_fb, ql_fb, slot_key,
                                             slot_key_lo)
        else:
            slot_fb = jnp.searchsorted(slot_key, q_fb,
                                       side="right").astype(jnp.int32) - 1
            found_fb = (slot_fb >= 0) & (
                jnp.take(slot_key, jnp.maximum(slot_fb, 0)) == q_fb)
        return (slot.at[idx].set(slot_fb, mode="drop"),
                found.at[idx].set(found_fb, mode="drop"))

    slot, found = jax.lax.cond(fb_count > 0, compact, lambda a: a,
                               (slot, found))
    return slot, found, fb_count, overflow


def _route_segment(queries, queries_lo, seg_first_key, seg_first_key_lo,
                   key_wide, radix_table=None, radix_scale=None):
    """Approximate radix segment routing (one multiply + one table
    gather) with an exact searchsorted/pair-bisect fallback when no
    radix table was built.  Mis-routes near bucket boundaries are SOUND
    (see ``_xla_window_lookup``)."""
    if radix_table is not None:
        r = radix_table.shape[0]
        if key_wide:
            x = (queries - radix_scale[0]) + (queries_lo - radix_scale[1])
        else:
            x = queries - radix_scale[0]
        b = jnp.clip(x * radix_scale[2], 0.0, float(r - 1)).astype(jnp.int32)
        return jnp.take(radix_table, b, mode="clip")
    if key_wide:
        k_pad = seg_first_key.shape[0]
        seg_trips = int(np.ceil(np.log2(max(k_pad, 2)))) + 1
        seg = _pair_bisect(
            seg_first_key, seg_first_key_lo, queries, queries_lo,
            jnp.zeros(queries.shape, jnp.int32),
            jnp.full(queries.shape, k_pad - 1, jnp.int32), seg_trips)
        return jnp.clip(seg, 0, k_pad - 1)
    return jnp.clip(
        jnp.searchsorted(seg_first_key, queries, side="right") - 1,
        0, seg_first_key.shape[0] - 1)


def _fused_search(queries, queries_lo, slot_key, slot_key_lo,
                  rank_table, rank_scale, trips, key_wide):
    """Minimal-gather fused search: the XLA half of the fused
    single-dispatch backend (the Pallas fused kernel is the TPU half).

    On CPU/GPU XLA the lookup is GATHER-bound (gathers lower to scalar
    loops), so the whole route -> predict -> window chain is collapsed
    into one precomputed **bucket -> slot-rank table** (the device image
    of the mechanism's prediction, materialized at freeze time by
    ``build_rank_router``): per query that is TWO table gathers (window
    lower/upper rank — adjacent table rows) plus a ~log2(p99 bucket
    occupancy) fixed-trip bisect, versus the oracle's log2(Mpad) probes
    and the reference path's 4-gather segment routing + err-window
    bisect.

    The trailing **bracket validation** (``slot_key[r] <= q <
    slot_key[r+1]``, one of whose gathers doubles as the ``found``
    probe) makes the result exact INDEPENDENT of the table and trip
    budget: a stale table row (delta updates move key values under it)
    or a p99-truncated bisect surfaces as a fallback flag, never a
    wrong slot — escaped queries re-resolve through the compacted
    buffer like every other backend.
    """
    m_pad = slot_key.shape[0]
    r = rank_table.shape[0] - 1
    if key_wide:
        x = (queries - rank_scale[0]) + (queries_lo - rank_scale[1])
    else:
        x = queries - rank_scale[0]
    b = jnp.clip(x * rank_scale[2], 0.0, float(r - 1)).astype(jnp.int32)
    lo0 = jnp.take(rank_table, b) - 1
    hi0 = jnp.maximum(jnp.take(rank_table, b + 1) - 1, lo0)
    if key_wide:
        slot = _pair_bisect(slot_key, slot_key_lo, queries, queries_lo,
                            lo0, hi0, trips)
    else:
        def body(_, carry):
            lo, hi = carry
            upd = lo < hi
            mid = (lo + hi + 1) >> 1
            go = jnp.take(slot_key, jnp.clip(mid, 0, m_pad - 1)) <= queries
            lo = jnp.where(upd & go, mid, lo)
            hi = jnp.where(upd, jnp.where(go, hi, mid - 1), hi)
            return lo, hi

        slot, _ = jax.lax.fori_loop(0, trips, body, (lo0, hi0))
    safe = jnp.clip(slot, 0, m_pad - 1)
    nxt_i = jnp.clip(slot + 1, 0, m_pad - 1)
    kr = jnp.take(slot_key, safe)
    nxt = jnp.take(slot_key, nxt_i)
    if key_wide:
        krl = jnp.take(slot_key_lo, safe)
        nxtl = jnp.take(slot_key_lo, nxt_i)
        found = (slot >= 0) & _peq(kr, krl, queries, queries_lo)
        ok_lo = (slot < 0) | _ple(kr, krl, queries, queries_lo)
        ok_hi = ~_ple(nxt, nxtl, queries, queries_lo) | (slot + 1 >= m_pad)
    else:
        found = (slot >= 0) & (kr == queries)
        ok_lo = (slot < 0) | (kr <= queries)
        ok_hi = (nxt > queries) | (slot + 1 >= m_pad)
    fb = ~(ok_lo & ok_hi) & jnp.isfinite(queries)
    return slot, found, fb


def build_radix_router(arrays: "IndexArrays", r_size: int = 1 << 14):
    """Approximate radix segment router: ``(table, scale)`` numpy pair.

    One multiply + one table gather replaces the exact segment-routing
    searchsorted (mis-routes near bucket boundaries are sound — see
    ``_xla_window_lookup``).  ``scale`` carries kmin as an f32 hi/lo
    pair so wide-key subtraction keeps its relative precision.
    """
    segk = np.asarray(arrays.seg_first_key, np.float64)
    if arrays.key_wide:
        segk = segk + np.asarray(arrays.seg_first_key_lo, np.float64)
    finite = segk[np.isfinite(segk)]
    sk = np.asarray(arrays.slot_key, np.float64)
    if arrays.key_wide:
        sk = sk + np.asarray(arrays.slot_key_lo, np.float64)
    sk_fin = sk[np.isfinite(sk)]
    kmin = float(finite[0]) if finite.size else 0.0
    kmax = float(sk_fin[-1]) if sk_fin.size else kmin + 1.0
    scale = (r_size - 1) / max(kmax - kmin, 1e-9)
    buckets = kmin + np.arange(r_size, dtype=np.float64) / scale
    table = np.clip(
        np.searchsorted(segk, buckets, side="right") - 1,
        0, segk.shape[0] - 1,
    ).astype(np.int32)
    kmin_hi, kmin_lo = split_key_pair(np.array([kmin]))
    return table, np.array([kmin_hi[0], kmin_lo[0], scale], np.float32)


def build_rank_router(slot_key, slot_key_lo=None, r_bits: int = 16,
                      trips_pct: float = 99.0):
    """Bucket -> slot-rank table for the fused XLA search.

    ``table[b]`` is the rank (searchsorted-left) of bucket b's lower key
    boundary in the frozen slot-key array, so a query hashing to bucket
    b has its predecessor slot in ``[table[b] - 1, table[b+1] - 1]`` —
    the whole route/predict/window chain becomes two gathers into one
    (r_size + 1)-entry table.  Returns ``(table, scale, trips, meta)``:
    ``scale`` is the f32 [kmin_hi, kmin_lo, scale] device triple,
    ``trips`` a bisect budget covering the ``trips_pct`` percentile
    bucket occupancy (denser buckets escape through the bracket
    validation in ``_fused_search`` — sound, fallback-only), and
    ``meta`` the f64 (kmin, scale, r_size) used for incremental row
    refreshes (``QueryEngine.refresh_rank_rows``).
    """
    sk = np.asarray(slot_key, np.float64)
    if slot_key_lo is not None and np.asarray(slot_key_lo).size:
        sk = sk + np.asarray(slot_key_lo, np.float64)
    fin = sk[np.isfinite(sk)]
    kmin = float(fin[0]) if fin.size else 0.0
    kmax = float(fin[-1]) if fin.size else kmin + 1.0
    r_size = 1 << r_bits
    scale = r_size / max(kmax - kmin, 1e-9)
    bounds = kmin + np.arange(r_size + 1, dtype=np.float64) / scale
    table = np.searchsorted(sk, bounds, side="left").astype(np.int32)
    # top boundary: include every slot <= kmax (duplicated max keys)
    table[-1] = np.searchsorted(sk, kmax, side="right")
    occ = (table[1:] - table[:-1]).astype(np.float64)
    p = float(np.percentile(occ, trips_pct)) if occ.size else 1.0
    trips = int(max(1, np.ceil(np.log2(p + 3.0)) + 1))
    trips = min(trips, int(np.ceil(np.log2(max(sk.shape[0], 2)))) + 1)
    kmin_hi, kmin_lo = split_key_pair(np.array([kmin]))
    return (table, np.array([kmin_hi[0], kmin_lo[0], scale], np.float32),
            trips, (kmin, scale, r_size))


def _cached_rank_router(arrays: "IndexArrays"):
    """Per-``IndexArrays`` cache of the fused rank router for the
    ``batched_lookup`` entry point (``QueryEngine`` keeps its own,
    refreshable copy).  ``IndexArrays`` is frozen, so a cached instance
    can never drift; the cache rides the instance itself — a delta
    update produces a NEW instance and therefore a fresh build."""
    cached = getattr(arrays, "_rank_router_cache", None)
    if cached is None:
        table, scale, trips, _meta = build_rank_router(
            np.asarray(arrays.slot_key),
            np.asarray(arrays.slot_key_lo) if arrays.key_wide else None)
        cached = (jnp.asarray(table), jnp.asarray(scale), trips)
        object.__setattr__(arrays, "_rank_router_cache", cached)
    return cached


def _fused_fixup(qs, qls, slot, resolved, out, out_hi, fb_loc, fb_cnt,
                 slot_key, slot_key_lo, payload, payload_hi, link_offsets,
                 link_keys, link_keys_lo, link_payloads, link_payload_hi,
                 q_tile, fb_cap, max_chain, wide, key_wide):
    """Post-kernel correction for the fused Pallas path.

    The kernel already compacted each tile's escaped queries (per-tile
    local index lists + counts), so this stage only stitches the tile
    lists into one fixed-capacity global buffer, re-searches THOSE
    queries against the full array, reruns the epilogue on the
    (fb_cap,)-shaped buffer, and scatters the corrections back.  The
    whole thing sits behind a ``lax.cond`` keyed on the total escape
    count — the common case pays one (num_tiles,) reduction.
    """
    n_q = qs.shape[0]
    fb_count = jnp.sum(fb_cnt)
    overflow = fb_count > fb_cap

    def fix(args):
        slot, resolved, out, out_hi = args
        t = fb_cnt.shape[0]
        base = jnp.cumsum(fb_cnt) - fb_cnt                      # (T,)
        jj = jnp.arange(q_tile, dtype=jnp.int32)[None, :]
        loc = fb_loc.reshape(t, q_tile)
        valid = jj < fb_cnt[:, None]
        dst = jnp.where(valid, base[:, None] + jj, fb_cap)
        qid = jnp.where(
            valid,
            jnp.arange(t, dtype=jnp.int32)[:, None] * q_tile + loc,
            n_q)
        idx = jnp.full((fb_cap + 1,), n_q, jnp.int32).at[
            jnp.minimum(dst, fb_cap).reshape(-1)
        ].set(qid.reshape(-1), mode="drop")[:fb_cap]
        q_fb = jnp.take(qs, idx, mode="clip")
        ql_fb = jnp.take(qls, idx, mode="clip") if key_wide else qls
        if key_wide:
            slot_f, found_f = _pair_oracle(q_fb, ql_fb, slot_key,
                                           slot_key_lo)
        else:
            slot_f = jnp.searchsorted(slot_key, q_fb,
                                      side="right").astype(jnp.int32) - 1
            found_f = (slot_f >= 0) & (
                jnp.take(slot_key, jnp.maximum(slot_f, 0)) == q_fb)
        out_f, out_hi_f, res_f = _epilogue(
            q_fb, ql_fb, slot_f, found_f, payload, payload_hi,
            link_offsets, link_keys, link_keys_lo, link_payloads,
            link_payload_hi, max_chain, wide, key_wide)
        slot = slot.at[idx].set(slot_f, mode="drop")
        resolved = resolved.at[idx].set(res_f, mode="drop")
        out = out.at[idx].set(out_f, mode="drop")
        if wide:
            out_hi = out_hi.at[idx].set(out_hi_f, mode="drop")
        return slot, resolved, out, out_hi

    slot, resolved, out, out_hi = jax.lax.cond(
        fb_count > 0, fix, lambda a: a, (slot, resolved, out, out_hi))
    return slot, resolved, out, out_hi, fb_count, overflow


@functools.partial(
    jax.jit,
    static_argnames=("q_tile", "w_tile", "seg_chunk", "win_chunk",
                     "max_chain", "n_slots", "interpret", "backend",
                     "assume_sorted", "fb_cap", "trips", "flat_w",
                     "radix", "wide", "key_wide"),
)
def _pipeline(
    queries, queries_lo,
    seg_first_key, seg_first_key_lo, seg_slope, seg_icept,
    err_lo_by_seg, err_hi_by_seg,
    slot_key, slot_key_lo, payload, payload_hi,
    link_offsets, link_keys, link_keys_lo, link_payloads, link_payload_hi,
    radix_table, radix_scale,
    *,
    q_tile, w_tile, seg_chunk, win_chunk, max_chain, n_slots,
    interpret, backend, assume_sorted, fb_cap, trips, flat_w, radix, wide,
    key_wide,
):
    n_q = queries.shape[0]
    m_pad = slot_key.shape[0]

    def epi(qs, qls, slot, found):
        return _epilogue(qs, qls, slot, found, payload, payload_hi,
                         link_offsets, link_keys, link_keys_lo,
                         link_payloads, link_payload_hi, max_chain, wide,
                         key_wide)

    if backend == "oracle":
        # permutation-free: searchsorted needs no sorted queries
        if key_wide:
            slot, found = _pair_oracle(queries, queries_lo, slot_key,
                                       slot_key_lo)
        else:
            slot, found = _ref.lookup_ref(
                queries, seg_first_key, seg_slope, seg_icept, slot_key
            )
        out, out_hi, resolved = epi(queries, queries_lo, slot, found)
        zero = jnp.int32(0)
        return out, out_hi, slot, resolved, zero, zero > 0

    if backend == "xla":
        # permutation-free single pass: windowed bisect + compaction
        slot, found, fb = _xla_window_lookup(
            queries, queries_lo, seg_first_key, seg_first_key_lo,
            seg_slope, seg_icept, err_lo_by_seg, err_hi_by_seg,
            slot_key, slot_key_lo, n_slots, trips, flat_w, key_wide,
            radix_table=radix_table if radix else None,
            radix_scale=radix_scale if radix else None,
        )
        slot, found, fb_count, overflow = _compact_fallback(
            queries, queries_lo, slot, found, fb, slot_key, slot_key_lo,
            fb_cap, key_wide
        )
        out, out_hi, resolved = epi(queries, queries_lo, slot, found)
        return out, out_hi, slot, resolved, fb_count, overflow

    if backend == "fused-pallas":
        # fused single-dispatch kernel: routing + bounded search + CSR
        # chain epilogue + payload gather + fallback flag/compaction all
        # in one pallas_call over VMEM-resident tiles (pair-aware, so
        # wide keys stay on device).  Outside the kernel: the sort (if
        # needed), the scalar-prefetch tile schedule, and the rare
        # compacted escape correction behind a lax.cond.
        if assume_sorted:
            qs, qls = queries, queries_lo
        else:
            if key_wide:
                order = jnp.lexsort((queries_lo, queries))
                qls = jnp.take(queries_lo, order)
            else:
                order = jnp.argsort(queries)
                qls = queries_lo
            qs = jnp.take(queries, order)
        icept_fold = seg_icept + err_lo_by_seg - 1.0
        seg = _route_segment(qs, qls, seg_first_key, seg_first_key_lo,
                             key_wide, radix_table=radix_table,
                             radix_scale=radix_scale)
        if key_wide:
            dx = ((qs - jnp.take(seg_first_key, seg))
                  + (qls - jnp.take(seg_first_key_lo, seg)))
        else:
            dx = qs - jnp.take(seg_first_key, seg)
        lo = jnp.clip(jnp.take(seg_slope, seg) * dx
                      + jnp.take(icept_fold, seg),
                      0.0, float(n_slots - 1))
        tile_lo = jnp.min(lo.reshape(-1, q_tile), axis=1)
        tile_block = jnp.clip(
            (tile_lo // w_tile).astype(jnp.int32), 0, m_pad // w_tile - 2
        )
        slot_s, res_s, out_s, out_hi_s, _fb, fb_loc, fb_cnt = \
            fused_lookup_call(
                qs, qls, tile_block, radix_table, radix_scale,
                seg_first_key, seg_first_key_lo, seg_slope, icept_fold,
                slot_key, slot_key_lo, payload, payload_hi,
                link_offsets, link_keys, link_keys_lo, link_payloads,
                link_payload_hi,
                q_tile=q_tile, w_tile=w_tile, win_chunk=win_chunk,
                flat_w=flat_w, max_chain=max_chain, n_slots=n_slots,
                key_wide=key_wide, wide=wide, interpret=interpret)
        res_s = res_s.astype(bool)
        slot_s, res_s, out_s, out_hi_s, fb_count, overflow = _fused_fixup(
            qs, qls, slot_s, res_s, out_s, out_hi_s, fb_loc, fb_cnt,
            slot_key, slot_key_lo, payload, payload_hi, link_offsets,
            link_keys, link_keys_lo, link_payloads, link_payload_hi,
            q_tile, fb_cap, max_chain, wide, key_wide)
        if assume_sorted:
            return out_s, out_hi_s, slot_s, res_s, fb_count, overflow
        inv = jnp.argsort(order)
        out_hi = jnp.take(out_hi_s, inv) if wide else out_hi_s
        return (jnp.take(out_s, inv), out_hi, jnp.take(slot_s, inv),
                jnp.take(res_s, inv), fb_count, overflow)

    # --- Pallas backend (narrow keys only; the capability registry in
    # repro.core.handle routes wide-key indexes to the XLA backend) -----
    if key_wide:
        raise ValueError("the pallas backend does not support wide "
                         "(f32 hi/lo pair) keys; use 'xla'")
    if assume_sorted:
        qs = queries
    else:
        order = jnp.argsort(queries)
        qs = jnp.take(queries, order)

    # tile window scheduling (host-side XLA, cheap)
    y_hat, seg = _ref.predict_ref(qs, seg_first_key, seg_slope, seg_icept)
    lo = y_hat + jnp.take(err_lo_by_seg, seg) - 1.0
    lo = jnp.clip(lo, 0.0, float(n_slots - 1))
    tile_lo = jnp.min(lo.reshape(-1, q_tile), axis=1)
    tile_block = jnp.clip(
        (tile_lo // w_tile).astype(jnp.int32), 0, m_pad // w_tile - 2
    )
    slot_s, found_s, fb_s, _pred = lookup_kernel_call(
        qs, tile_block, seg_first_key, seg_slope, seg_icept, slot_key,
        q_tile=q_tile, w_tile=w_tile, seg_chunk=seg_chunk,
        win_chunk=win_chunk, interpret=interpret,
    )
    # compacted fallback: ONLY flagged queries are re-searched (padding
    # +inf queries flag the window edge — mask them out, they are sliced
    # away by the caller)
    fb_s = fb_s & jnp.isfinite(qs)
    slot_s, found_s, fb_count, overflow = _compact_fallback(
        qs, queries_lo, slot_s, found_s, fb_s, slot_key, slot_key_lo,
        fb_cap, key_wide
    )
    # fused epilogue in the sorted domain, then ONE unsort gather per out
    out_s, out_hi_s, res_s = epi(qs, queries_lo, slot_s, found_s)
    if assume_sorted:
        return out_s, out_hi_s, slot_s, res_s, fb_count, overflow
    inv = jnp.argsort(order)
    out_hi = jnp.take(out_hi_s, inv) if wide else out_hi_s
    return (jnp.take(out_s, inv), out_hi, jnp.take(slot_s, inv),
            jnp.take(res_s, inv), fb_count, overflow)


def query_window_bounds(index, max_widen: float = 32.0, segments=None,
                        base=None):
    """Per-segment error bounds valid for ABSENT queries too.

    The plm's finalized (err_lo, err_hi) only bound present keys; a query
    q between keys can fall outside [y_hat(q)+err_lo, y_hat(q)+err_hi]
    because its predecessor's slot was bounded against a *different*
    y_hat.  For monotone segment lines the exact correction is:

      * pairs (x_i, x_{i+1}) in segment s: q in (x_i, x_{i+1}) has
        pred slot_i and y_hat(q) < y_hat(x_{i+1}), so the lower bound
        needs min(slot_i - y_hat(x_{i+1}));
      * queries in s below its first key (pred = last key of the
        previous segment, slot_p): lower term slot_p - y_hat_s(first
        key), upper term slot_p - y_hat_s(segment start boundary);
      * queries in s above its last key: lower term
        slot_last - y_hat_s(next segment boundary);
      * empty segments: both boundary terms with pred slot_p.

    Windows stay CORRECT without this (escaped queries fall back), just
    larger: this tightens the miss-heavy case.  Segments with negative
    slope (non-monotone line) keep a widened conservative bound.
    ``max_widen`` clamps the per-segment widening: queries landing in
    extreme key gaps (which would force huge static windows) are left to
    the compacted fallback instead — rare by construction, and the clamp
    keeps the common-case window narrow enough for the loop-free flat
    search.  Returns (err_lo_q, err_hi_q) float64 (K,).

    Incremental mode (``segments`` + ``base``): recompute ONLY the given
    segment rows, starting from the plm's finalized bounds for those
    rows and the ``base`` (err_lo, err_hi) arrays for everything else —
    the per-segment terms depend only on that segment's keys and its
    immediate key-order neighbors, so a delta update that touched a few
    segments refreshes in O(touched keys) instead of O(n + K)
    (the ROADMAP "stale-window refresh" item; driven by
    ``Index._refresh_window_bounds``).
    """
    plm = index.mech.plm
    K = int(plm.n_segments)
    first_key = np.asarray(plm.seg_first_key, np.float64)
    slope = np.asarray(plm.slope, np.float64)
    icept = np.asarray(plm.icept, np.float64)
    x = np.asarray(index.keys, np.float64)
    n = x.shape[0]
    if segments is None:
        seg_list = np.arange(K)
        err_lo = np.array(plm.err_lo, np.float64).copy()
        err_hi = np.array(plm.err_hi, np.float64).copy()
    else:
        seg_list = np.unique(np.clip(np.asarray(segments, np.int64),
                                     0, K - 1))
        if base is None:
            raise ValueError("incremental refresh needs the base bounds")
        err_lo = np.asarray(base[0], np.float64).copy()
        err_hi = np.asarray(base[1], np.float64).copy()
        # touched rows restart from the plm's finalized bounds (exactly
        # what the full recompute would start them from)
        err_lo[seg_list] = np.asarray(plm.err_lo, np.float64)[seg_list]
        err_hi[seg_list] = np.asarray(plm.err_hi, np.float64)[seg_list]

    # key span per segment via key-boundary bisection (keys below the
    # first boundary clip into segment 0, matching plm.segment_of)
    b_lo_arr = first_key[seg_list]
    b_hi_arr = np.where(seg_list + 1 < K,
                        first_key[np.minimum(seg_list + 1, K - 1)], np.inf)
    i0_arr = np.where(seg_list == 0, 0,
                      np.searchsorted(x, b_lo_arr, side="left"))
    i1_arr = np.searchsorted(x, b_hi_arr, side="left") - 1

    # slots + predictions only for the involved keys (each segment's
    # span plus its predecessor key)
    if segments is None:
        inv = np.arange(n)
    else:
        spans = [np.arange(max(int(i0_arr[j]) - 1, 0), int(i1_arr[j]) + 1)
                 for j in range(seg_list.shape[0])]
        inv = (np.unique(np.concatenate(spans)) if spans
               else np.zeros(0, np.int64))
    slot_g = np.zeros(n, np.float64)
    y_g = np.zeros(n, np.float64)
    if inv.size:
        if index.gapped is not None:
            slot_g[inv] = (np.searchsorted(index.gapped.slot_key, x[inv],
                                           side="right") - 1)
        else:
            slot_g[inv] = inv
        y_g[inv] = np.asarray(index.mech.predict(x[inv]), np.float64)

    def yhat_at(s, v):  # segment s's line evaluated at key value v
        return slope[s] * (v - first_key[s]) + icept[s]

    for j, s in enumerate(seg_list):
        i0, i1 = int(i0_arr[j]), int(i1_arr[j])
        has_keys = i0 <= i1 and i0 < n
        p = i0 - 1  # last key strictly before segment s
        b_lo, b_hi = b_lo_arr[j], b_hi_arr[j]
        if slope[s] < 0:  # non-monotone line: conservative widening
            span = abs(slope[s]) * (
                (b_hi - b_lo) if np.isfinite(b_hi) else 0.0)
            err_lo[s] -= span
            err_hi[s] += span
            continue
        if has_keys:
            if i1 > i0:  # consecutive-pair terms within the segment
                err_lo[s] = min(err_lo[s],
                                float(np.min(slot_g[i0:i1]
                                             - y_g[i0 + 1:i1 + 1])))
            if p >= 0:
                err_lo[s] = min(err_lo[s], slot_g[p] - y_g[i0])
                err_hi[s] = max(err_hi[s], slot_g[p] - yhat_at(s, b_lo))
            if np.isfinite(b_hi):
                err_lo[s] = min(err_lo[s], slot_g[i1] - yhat_at(s, b_hi))
        elif p >= 0:
            if np.isfinite(b_hi):
                err_lo[s] = min(err_lo[s], slot_g[p] - yhat_at(s, b_hi))
            err_hi[s] = max(err_hi[s], slot_g[p] - yhat_at(s, b_lo))
    if max_widen is not None:
        err_lo[seg_list] = np.maximum(
            err_lo[seg_list],
            np.asarray(plm.err_lo, np.float64)[seg_list] - max_widen)
        err_hi[seg_list] = np.minimum(
            err_hi[seg_list],
            np.asarray(plm.err_hi, np.float64)[seg_list] + max_widen)
    return err_lo, err_hi


def auto_q_tile(n_q: int, n_slots: int, w_tile: int) -> int:
    """Pick q_tile so a sorted-query tile's slot span ~fits the 2*w_tile
    window: span ~= n_slots * q_tile / n_q.  Clamped to [32, 512]."""
    t = max(32, min(512, int(n_q * w_tile / max(n_slots, 1))))
    return 1 << (t.bit_length() - 1)  # floor to a power of two


def _bisect_trips(err_lo: np.ndarray, err_hi: np.ndarray) -> int:
    """Static trip count covering the widest per-segment search window."""
    lo = np.asarray(err_lo, np.float64)
    hi = np.asarray(err_hi, np.float64)
    w = hi - lo
    w = w[np.isfinite(w)]
    widest = float(np.max(w)) if w.size else 0.0
    return int(min(32, max(1, np.ceil(np.log2(widest + 4.0)) + 1)))


def _flat_width(err_lo: np.ndarray, err_hi: np.ndarray) -> int:
    """Power-of-two flat-search width covering the p95 segment window,
    or 0 when typical windows are too wide for the loop-free mode."""
    w = np.asarray(err_hi, np.float64) - np.asarray(err_lo, np.float64)
    w = w[np.isfinite(w)]
    if w.size == 0:
        return 16
    p95 = float(np.percentile(w, 95))
    fw = 1 << max(3, int(np.ceil(np.log2(p95 + 6.0))))
    return fw if fw <= 32 else 0


def _fused_flat_width(err_lo: np.ndarray, err_hi: np.ndarray,
                      cap: int = 256) -> int:
    """Flat-window width for the fused backend (p95 window, pow2).

    The fused path tolerates much wider flat windows than the legacy
    multi-op one (cap 256 vs 32): its window is ONE parallel gather
    whose latency hides behind prefetch, whereas the bisect it replaces
    is a chain of serially-dependent probes — at small/medium batch the
    dependent-load latency, not the compare count, is the bottleneck.
    Beyond ``cap`` (p95 windows wider than the compare budget) returns 0
    and the fused path delegates to the fixed-trip bisect.
    """
    w = np.asarray(err_hi, np.float64) - np.asarray(err_lo, np.float64)
    w = w[np.isfinite(w)]
    if w.size == 0:
        return 16
    p95 = float(np.percentile(w, 95))
    fw = 1 << max(3, int(np.ceil(np.log2(p95 + 6.0))))
    return fw if fw <= cap else 0


class _EscapeCounter:
    count = 0


_ESCAPES = _EscapeCounter()


_NO_F32 = np.zeros(0, np.float32)
_NO_RADIX_TABLE = np.zeros(1, np.int32)
_NO_RADIX_SCALE = np.zeros(3, np.float32)
_NO_RANK_TABLE = np.zeros(2, np.int32)


def host_fallback_views(arrays: IndexArrays) -> dict:
    """Host (numpy, f64/i64) copies of the frozen index for the fused
    path's O(#escapes) fallback patch.  Built lazily and cached per
    ``IndexArrays`` instance by the engine — a delta update swaps in a
    new instance, which simply invalidates the cache."""
    sk = np.asarray(arrays.slot_key, np.float64)
    if arrays.key_wide:
        sk = sk + np.asarray(arrays.slot_key_lo, np.float64)
    pay = np.asarray(arrays.payload).astype(np.int64)
    if arrays.wide:
        pay = (pay & 0xFFFFFFFF) | (
            np.asarray(arrays.payload_hi).astype(np.int64) << 32)
    lk = np.asarray(arrays.link_keys, np.float64)
    if arrays.key_wide:
        lk = lk + np.asarray(arrays.link_keys_lo, np.float64)
    lp = np.asarray(arrays.link_payloads).astype(np.int64)
    if arrays.wide:
        lp = (lp & 0xFFFFFFFF) | (
            np.asarray(arrays.link_payload_hi).astype(np.int64) << 32)
    return {"slot_key": sk, "payload": pay,
            "offsets": np.asarray(arrays.link_offsets),
            "link_keys": lk, "link_payloads": lp,
            "max_chain": arrays.max_chain, "key_wide": arrays.key_wide}


def resolve_escapes_host(host: dict, q64: np.ndarray):
    """Exact host resolution of the fused path's escaped queries
    (f64 searchsorted + per-slot chain probe).  O(#escapes x log) —
    the fused contract's replacement for the compacted device
    fallback, sized for escape rates in the fractions of a percent.
    Returns ``(slot, resolved, payload_i64)``.

    Queries are first rounded into the FROZEN key representation (f32
    hi/lo pair sum when wide, plain f32 when narrow) so the host
    compare agrees bit-for-bit with the device compare — for
    continuous key sets the stored values are the rounded ones, and an
    alias-free freeze guarantees the rounding never conflates two
    stored keys."""
    if host["key_wide"]:
        q_hi, q_lo = split_key_pair(q64)
        q64 = q_hi.astype(np.float64) + q_lo.astype(np.float64)
    else:
        q64 = np.asarray(q64, np.float64).astype(
            np.float32).astype(np.float64)
    sk = host["slot_key"]
    r = np.searchsorted(sk, q64, side="right").astype(np.int64) - 1
    safe = np.maximum(r, 0)
    found = (r >= 0) & (sk[safe] == q64)
    pay = np.where(found, host["payload"][safe], np.int64(-1))
    resolved = found.copy()
    if host["max_chain"] > 0 and host["link_keys"].size:
        off = host["offsets"]
        for j in np.flatnonzero((r >= 0) & ~found):
            s, e = int(off[r[j]]), int(off[r[j] + 1])
            if e > s:
                seg = host["link_keys"][s:e]
                p = int(np.searchsorted(seg, q64[j], side="right")) - 1
                if p >= 0 and seg[p] == q64[j]:
                    pay[j] = host["link_payloads"][s + p]
                    resolved[j] = True
    return r, resolved, pay


def _finish_fused_host(out, out_hi, slot, found, fb, n_q, wide, queries,
                       host_views):
    """Host finish for the fused path: zero-copy views of the padded
    device outputs (CPU backend shares the buffers; no per-output slice
    dispatch) in the common zero-escape case, materialized copies plus
    the O(#escapes) patch only when the mask is non-empty.
    ``host_views`` is a zero-arg callable so the (lazily cached) host
    copies are only built when an escape actually occurs."""
    fb_np = np.asarray(fb)[:n_q]
    idx = np.flatnonzero(fb_np)
    out_np = np.asarray(out)[:n_q]
    if wide:
        out_np = ((np.asarray(out_hi)[:n_q].astype(np.int64) << 32)
                  | (out_np.astype(np.int64) & 0xFFFFFFFF))
    slot_np = np.asarray(slot)[:n_q]
    found_np = np.asarray(found)[:n_q]
    if idx.size:
        out_np = np.array(out_np)
        slot_np = np.array(slot_np)
        found_np = np.array(found_np)
        r, res, pay = resolve_escapes_host(
            host_views(), np.asarray(queries, np.float64)[idx])
        out_np[idx] = pay
        slot_np[idx] = r
        found_np[idx] = res
    return out_np, slot_np, found_np, int(idx.size)


def _recombine_i64(out, out_hi, n_q, wide):
    """hi/lo pair -> i64 payloads on host (x64 may be disabled in jax)."""
    if not wide:
        return out[:n_q]
    lo = np.asarray(out[:n_q]).astype(np.int64) & 0xFFFFFFFF
    hi = np.asarray(out_hi[:n_q]).astype(np.int64)
    return (hi << 32) | lo


def _split_queries(queries, key_wide: bool):
    """Host-side query split matching the frozen key representation."""
    q64 = np.asarray(queries, np.float64)
    if key_wide:
        return split_key_pair(q64)
    return q64.astype(np.float32), _NO_F32


def _oracle_escape(arrays, err_lo_by_seg, queries, **kwargs):
    """Full-oracle widening — ONLY reached when the compaction buffer
    overflows (module-level so tests can count invocations)."""
    _ESCAPES.count += 1
    kwargs.pop("backend", None)
    kwargs.pop("use_kernel", None)
    return batched_lookup(arrays, err_lo_by_seg, queries,
                          backend="oracle", **kwargs)


def batched_lookup(
    arrays: IndexArrays,
    err_lo_by_seg,
    queries,
    *,
    q_tile: int = 0,
    w_tile: int = 2048,
    seg_chunk: int = 512,
    win_chunk: int = 512,
    interpret: Optional[bool] = None,
    use_kernel: bool = True,
    backend: Optional[str] = None,
    err_hi_by_seg=None,
    queries_sorted: bool = False,
    fb_frac: float = FB_FRAC,
):
    """Full device lookup: payloads (-1 = miss), slots, found, #fallbacks.

    ``backend`` selects the search stage: "pallas" (legacy kernel;
    ``interpret`` defaults from the platform), "xla" (windowed bisect,
    permutation-free)
    or "oracle" (full searchsorted).  Default: "pallas" when
    ``use_kernel`` else "oracle"; wide-key (``arrays.key_wide``) batches
    requesting "pallas" route to "xla".  ``err_lo_by_seg`` /
    ``err_hi_by_seg`` are the (K,) per-segment error bounds (finalized
    on the full data — see sampling.refinalize_bounds); err_hi defaults
    to zeros, which only costs extra (compacted) fallbacks.
    ``queries_sorted=True`` skips the argsort/inverse round trip on the
    Pallas path.  ``found`` marks present keys (first-level OR chain).
    """
    backend = backend or ("pallas" if use_kernel else "oracle")
    interpret = resolve_interpret(interpret)
    if backend not in ("pallas", "xla", "oracle", "fused", "fused-pallas"):
        raise ValueError(f"unknown backend {backend!r}")
    if backend == "pallas" and arrays.key_wide:
        backend = "xla"  # capability fallback (the LEGACY kernel is
        # narrow-only; the fused kernel takes wide keys natively)
    qh, ql = _split_queries(queries, arrays.key_wide)
    n_q = qh.shape[0]
    if q_tile <= 0:  # density-aware default (fallbacks stay rare)
        q_tile = auto_q_tile(n_q, arrays.n_slots, w_tile)
    if backend in ("pallas", "fused-pallas"):  # tile-granular grids
        qp = _pad_pow(qh, q_tile, np.float32(np.inf))
        qlp = (_pad_pow(ql, q_tile, np.float32(0))
               if arrays.key_wide else ql)
    else:
        qp, qlp = qh, ql
    if backend == "fused":
        # lean single dispatch + O(#escapes) host patch (see
        # _fused_pipeline); early return — none of the legacy statics
        # below apply
        rank_table, rank_scale, rk_trips = _cached_rank_router(arrays)
        out, out_hi, slot, found, fbm = _fused_pipeline(
            jnp.asarray(qp), jnp.asarray(qlp),
            arrays.slot_key, arrays.slot_key_lo,
            arrays.payload, arrays.payload_hi,
            arrays.link_offsets, arrays.link_keys, arrays.link_keys_lo,
            arrays.link_payloads, arrays.link_payload_hi,
            rank_table, rank_scale,
            trips=rk_trips, max_chain=arrays.max_chain,
            wide=arrays.wide, key_wide=arrays.key_wide)
        return _finish_fused_host(out, out_hi, slot, found, fbm, n_q,
                                  arrays.wide, queries,
                                  lambda: host_fallback_views(arrays))
    k_pad = int(arrays.seg_first_key.shape[0])
    err_lo_np = np.asarray(err_lo_by_seg, np.float32)
    err_hi_np = (np.zeros_like(err_lo_np) if err_hi_by_seg is None
                 else np.asarray(err_hi_by_seg, np.float32))
    trips = _bisect_trips(err_lo_np, err_hi_np)
    if backend in ("fused", "fused-pallas"):
        flat_w = _fused_flat_width(err_lo_np, err_hi_np)
    else:
        flat_w = _flat_width(err_lo_np, err_hi_np)
    err_lo_p = _pad_pow(err_lo_np, k_pad, np.float32(0))[:k_pad]
    err_hi_p = _pad_pow(err_hi_np, k_pad, np.float32(0))[:k_pad]
    radix = backend == "fused-pallas"  # the kernel routes via the table
    if radix:
        radix_table, radix_scale = build_radix_router(arrays)
    else:
        radix_table, radix_scale = _NO_RADIX_TABLE, _NO_RADIX_SCALE
    fb_cap = int(min(
        qp.shape[0],
        max(q_tile if backend in ("pallas", "fused-pallas") else 64,
            int(np.ceil(fb_frac * qp.shape[0]))),
    ))
    out, out_hi, slot, found, fb, overflow = _pipeline(
        jnp.asarray(qp), jnp.asarray(qlp),
        arrays.seg_first_key, arrays.seg_first_key_lo,
        arrays.seg_slope, arrays.seg_icept,
        jnp.asarray(err_lo_p), jnp.asarray(err_hi_p),
        arrays.slot_key, arrays.slot_key_lo,
        arrays.payload, arrays.payload_hi,
        arrays.link_offsets, arrays.link_keys, arrays.link_keys_lo,
        arrays.link_payloads, arrays.link_payload_hi,
        jnp.asarray(radix_table), jnp.asarray(radix_scale),
        q_tile=q_tile, w_tile=w_tile, seg_chunk=seg_chunk,
        win_chunk=win_chunk, max_chain=arrays.max_chain,
        n_slots=arrays.n_slots, interpret=interpret, backend=backend,
        assume_sorted=bool(queries_sorted), fb_cap=fb_cap, trips=trips,
        flat_w=flat_w, radix=radix, wide=arrays.wide,
        key_wide=arrays.key_wide,
    )
    if backend != "oracle" and bool(overflow):
        return _oracle_escape(
            arrays, err_lo_by_seg, queries,
            q_tile=q_tile, w_tile=w_tile, seg_chunk=seg_chunk,
            win_chunk=win_chunk, interpret=interpret,
            err_hi_by_seg=err_hi_by_seg, queries_sorted=queries_sorted,
            fb_frac=fb_frac,
        )
    out = _recombine_i64(out, out_hi, n_q, arrays.wide)
    return out, slot[:n_q], found[:n_q], fb


# ---------------------------------------------------------------------------
# epoch-versioned device state: freeze + delta update (host-mirror diff)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class HostMirror:
    """Host-side state at the device's epoch — what ``delta_update``
    diffs against and patches forward.

    ``sources``: f64/i64 copies of the unpadded index arrays (the diff
    is a handful of vectorized compares; f32/i32 splits are computed
    only for changed elements).  ``images``: the padded device-dtype
    buffers, patched in place so a dense diff uploads an already-built
    image instead of rebuilding it.  ``statics``: the frozen jit
    statics/capacities.  ``links_at_freeze``/``n_keys_at_freeze``: the
    refreeze policy's growth baseline (see Index._link_growth_fraction).
    """

    sources: dict
    images: dict
    statics: dict
    links_at_freeze: int
    n_keys_at_freeze: int


def _round_pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


@jax.jit
def _scatter_set(buf, idx, vals):
    return buf.at[idx].set(vals)


# fixed scatter capacity => ONE compiled scatter per (buffer, dtype)
# shape, however the diff size varies call to call
_SCATTER_CAP = 8192


def _scatter_into(dev, idx: np.ndarray, vals: np.ndarray):
    """Element-scatter a sparse diff (<= ``_SCATTER_CAP``) into a device
    buffer through a fixed-capacity bucket (padded by duplicating the
    last element — idempotent), so the jitted scatter compiles once per
    buffer shape."""
    n = idx.shape[0]
    if n < _SCATTER_CAP:
        idx = np.concatenate(
            [idx, np.full(_SCATTER_CAP - n, idx[-1], idx.dtype)])
        vals = np.concatenate(
            [vals, np.full(_SCATTER_CAP - n, vals[-1], vals.dtype)])
    return _scatter_set(dev, jnp.asarray(idx.astype(np.int32)),
                        jnp.asarray(vals))


def freeze_state(index, *, w_tile: int = 2048, seg_chunk: int = 512,
                 chain_headroom: int = 2, link_headroom: float = 2.0,
                 **engine_kwargs):
    """Freeze an index into a ``QueryEngine`` + ``HostMirror`` pair.

    Unlike the bare ``from_learned_index``, capacities are frozen WITH
    HEADROOM (max-chain x``chain_headroom``, link storage
    x``link_headroom``, power-of-two) so subsequent ``delta_update``
    calls keep shapes — and therefore compiled executables — stable.
    """
    ga = getattr(index, "gapped", None)
    chain = ga.links.max_chain if ga is not None else 0
    total = ga.links.total if ga is not None else 0
    max_chain = max(4, chain_headroom * max(chain, 1))
    link_cap = _round_pow2(max(64, int(link_headroom * max(total, 1))))
    np_arrays, statics = _freeze_numpy(
        index, w_tile=w_tile, seg_chunk=seg_chunk, max_chain=max_chain,
        link_cap=link_cap)
    arrays = _to_device(np_arrays, statics)
    err_lo, err_hi = query_window_bounds(index)
    engine = QueryEngine(arrays, err_lo, err_hi, w_tile=w_tile,
                         seg_chunk=seg_chunk, **engine_kwargs)
    n_keys = ga.n_keys if ga is not None else int(index.keys.shape[0])
    images = {f: np_arrays[f].copy() for f in _DELTA_FIELDS
              if np_arrays[f].size}
    mirror = HostMirror(sources=_snapshot_sources(index), images=images,
                        statics=statics, links_at_freeze=total,
                        n_keys_at_freeze=n_keys)
    return engine, mirror


def _snapshot_sources(index) -> dict:
    ga = getattr(index, "gapped", None)
    if ga is None:
        return {}
    offsets, lkeys, lpay = ga.export_csr_links()
    return {"slot_key": np.array(ga.slot_key, np.float64),
            "payload": np.array(ga.payload, np.int64),
            "offsets": np.array(offsets, np.int64),
            "link_keys": np.array(lkeys, np.float64),
            "link_payloads": np.array(lpay, np.int64)}


def _images_from_sources(sources: dict, statics: dict) -> dict:
    """Rebuild the padded device-dtype delta images from a host source
    snapshot — the lazy companion to the eager copy ``freeze_state``
    makes.  A fused on-device ingest commit advances ``mirror.sources``
    and marks ``images = None`` (the authoritative padded state lives
    in the engine's device buffers, written by the dispatch itself);
    the first HOST-side delta after such a commit lands here and pays
    the padding cost then — never on the fused hot path.
    """
    w_tile = statics["w_tile"]
    sk_hi, sk_lo = split_key_pair(sources["slot_key"])
    skp = _pad_pow(sk_hi, w_tile, np.float32(np.inf))
    skp = np.concatenate([skp, np.full(w_tile, np.inf, np.float32)])
    sklp = np.concatenate([_pad_pow(sk_lo, w_tile, np.float32(0)),
                           np.zeros(w_tile, np.float32)])
    pay_lo, pay_hi = _split_i64(sources["payload"])
    m_extra = skp.shape[0] - pay_lo.shape[0]
    pay_lo = np.concatenate([pay_lo, np.full(m_extra, -1, np.int32)])
    pay_hi = np.concatenate([pay_hi, np.full(m_extra, -1, np.int32)])
    offsets = sources["offsets"]
    offp = np.concatenate(
        [offsets, np.full(skp.shape[0] + w_tile - offsets.shape[0],
                          offsets[-1])]).astype(np.int32)
    link_cap = statics["link_cap"]
    lk_hi, lk_lo = split_key_pair(sources["link_keys"])
    l_extra = link_cap - lk_hi.shape[0]
    lk_hi = np.concatenate([lk_hi, np.full(l_extra, np.inf, np.float32)])
    lk_lo = np.concatenate([lk_lo, np.zeros(l_extra, np.float32)])
    lpay_lo, lpay_hi = _split_i64(sources["link_payloads"])
    lpay_lo = np.concatenate([lpay_lo, np.full(l_extra, -1, np.int32)])
    lpay_hi = np.concatenate([lpay_hi, np.full(l_extra, -1, np.int32)])
    none32f = np.zeros(0, np.float32)
    none32i = np.zeros(0, np.int32)
    images = {
        "slot_key": skp,
        "slot_key_lo": sklp if statics["key_wide"] else none32f,
        "payload": pay_lo,
        "payload_hi": pay_hi if statics["wide"] else none32i,
        "link_offsets": offp,
        "link_keys": lk_hi,
        "link_keys_lo": lk_lo if statics["key_wide"] else none32f,
        "link_payloads": lpay_lo,
        "link_payload_hi": lpay_hi if statics["wide"] else none32i,
    }
    return {f: img for f, img in images.items() if img.size}


def _diff_grown(old: np.ndarray, new: np.ndarray) -> np.ndarray:
    """Changed indices between two source arrays that may differ in
    length; positions past the new length are unread on device (the
    offsets bound every chain scan), so only [0, len(new)) matters."""
    n0, n1 = old.shape[0], new.shape[0]
    lo = min(n0, n1)
    d = np.flatnonzero(old[:lo] != new[:lo])
    if n1 > lo:
        d = np.concatenate([d, np.arange(lo, n1)])
    return d


def delta_update(arrays: IndexArrays, mirror: HostMirror, index,
                 max_diff_frac: float = 0.5):
    """Bring frozen device buffers to the index's current host state by
    scattering ONLY changed elements (slot_key/payload entries for slot
    placements, CSR link-table tails + shifted offsets for chain
    appends; dense diffs swap the single affected buffer).

    The diff runs on the SOURCE arrays (a few vectorized f64/i64
    compares) and the device-dtype splits are computed only for changed
    elements — no padded-image rebuild, no executable retrace.

    Returns ``(new_arrays, n_changed, touched_keys)`` — ``touched_keys``
    holds the finite key values whose placement changed (old + new slot
    keys, changed/appended chain keys), which is exactly what the
    caller needs to refresh window bounds for ONLY the touched segments
    (``Index._refresh_window_bounds``).  Declines with ``(None, 0,
    None)`` when a frozen static/capacity no longer holds or the diff
    would touch more than ``max_diff_frac`` of the slot buffers (a
    refreeze is then cheaper).  On success the mirror is advanced to
    the new host snapshot.
    """
    ga = getattr(index, "gapped", None)
    if ga is None or not mirror.sources:
        return None, 0, None
    st = mirror.statics
    if ga.n_slots != st["n_slots"]:
        return None, 0, None
    offsets, lkeys, lpay = ga.export_csr_links()
    if ga.links.max_chain > st["max_chain"]:
        return None, 0, None
    if lkeys.shape[0] > st["link_cap"]:
        return None, 0, None
    src = mirror.sources
    d_slot = np.flatnonzero(src["slot_key"] != np.asarray(ga.slot_key))
    d_pay = np.flatnonzero(src["payload"] != np.asarray(ga.payload))
    d_off = np.flatnonzero(src["offsets"] != offsets)
    d_lk = _diff_grown(src["link_keys"], lkeys)
    d_lp = _diff_grown(src["link_payloads"], lpay)
    changed = int(d_slot.size + d_pay.size + d_off.size + d_lk.size
                  + d_lp.size)
    if changed == 0:  # epoch moved without visible writes
        return arrays, 0, np.zeros(0, np.float64)
    if (d_slot.size + d_pay.size) > max_diff_frac * ga.n_slots:
        return None, 0, None
    # slot keys whose VALUE moved (old values too — a delete shifts its
    # old neighborhood).  Deliberately excludes the link-key diffs: a
    # CSR mid-insert positionally shifts the whole tail, which would
    # read as global churn; chain-INSERTED keys are instead reported by
    # the handle's own mutation log (Index._pending_touch).  Payload-
    # only diffs move nothing.
    touched_keys = np.concatenate([
        np.asarray(ga.slot_key)[d_slot], src["slot_key"][d_slot]])
    touched_keys = touched_keys[np.isfinite(touched_keys)]
    # width statics: only the CHANGED values can violate them
    new_pay = np.asarray(ga.payload)[d_pay]
    new_lpay = lpay[d_lp]
    if not st["wide"] and (
            (new_pay.size and (new_pay.min() < _I32_MIN
                               or new_pay.max() > _I32_MAX))
            or (new_lpay.size and (new_lpay.min() < _I32_MIN
                                   or new_lpay.max() > _I32_MAX))):
        return None, 0, None
    new_sk = np.asarray(ga.slot_key)[d_slot]
    if not st["key_wide"] and (keys_need_pair(new_sk)
                               or keys_need_pair(lkeys[d_lk])):
        return None, 0, None
    # NOTE: pair-ALIASING of distinct keys (beyond ~2^48) is the
    # caller's gate — repro.core.Index checks it per epoch (_key_caps)
    # and drops the device state instead of syncing; a full check here
    # would cost an O(n log n) merge per delta.

    if mirror.images is None:
        # a fused on-device ingest commit advanced the sources without
        # touching host images (device buffers were written in-dispatch)
        # — rebuild them lazily, only now that a host delta needs them
        mirror.images = _images_from_sources(src, st)

    updates = {}

    def upd(field, d, vals):
        """Sparse patch: fix the padded host image in place, then
        element-scatter (tiny diffs) or upload the patched image."""
        img = mirror.images[field]
        img[d] = vals
        if d.size <= _SCATTER_CAP:
            updates[field] = _scatter_into(getattr(arrays, field), d, vals)
        else:
            updates[field] = jnp.asarray(img)

    def upd_dense(field, prefix):
        """Dense patch (e.g. a chain append mid-array shifts every
        downstream CSR offset): one contiguous prefix write into the
        image (cheaper than an O(n) fancy-index scatter), one upload."""
        img = mirror.images[field]
        img[: prefix.shape[0]] = prefix
        updates[field] = jnp.asarray(img)

    def pair_group(fields, d, full64, split):
        dense = d.size > max(full64.shape[0] // 2, _SCATTER_CAP)
        parts = split(full64 if dense else full64[d])
        for f, part in zip(fields, parts):
            if f is None:
                continue
            (upd_dense(f, part) if dense else upd(f, d, part))

    if d_slot.size:
        pair_group(("slot_key", "slot_key_lo" if st["key_wide"] else None),
                   d_slot, np.asarray(ga.slot_key), split_key_pair)
        src["slot_key"][d_slot] = new_sk
    if d_pay.size:
        pair_group(("payload", "payload_hi" if st["wide"] else None),
                   d_pay, np.asarray(ga.payload), _split_i64)
        src["payload"][d_pay] = new_pay
    if d_off.size:
        pair_group(("link_offsets", None), d_off, offsets,
                   lambda a: (a.astype(np.int32),))
        src["offsets"] = np.array(offsets, np.int64)
    if d_lk.size:
        pair_group(("link_keys", "link_keys_lo" if st["key_wide"] else None),
                   d_lk, lkeys, split_key_pair)
        src["link_keys"] = np.array(lkeys, np.float64)
    if d_lp.size:
        pair_group(("link_payloads",
                    "link_payload_hi" if st["wide"] else None),
                   d_lp, lpay, _split_i64)
        src["link_payloads"] = np.array(lpay, np.int64)
    new_arrays = dataclasses.replace(arrays, **updates)
    return new_arrays, changed, touched_keys


# ---------------------------------------------------------------------------
# persistent engine: shape buckets + cached executables + sorted fast path
# ---------------------------------------------------------------------------


class QueryEngine:
    """Persistent single-pass query engine over a frozen ``IndexArrays``.

    Pads query batches up to power-of-two shape buckets so XLA compiles
    one executable per bucket instead of re-tracing every batch size, and
    keeps the padded error-bound arrays resident on device.  Serving
    callers that issue sorted batches pass ``queries_sorted=True`` to
    skip the argsort/inverse-permutation round trip on the Pallas path.

    ``swap_arrays`` accepts delta-updated buffers of identical shapes —
    the compiled executables and window bounds stay valid (stale bounds
    only raise the compacted-fallback rate, never wrong results).

    ``stats`` tracks calls, per-call fallback totals, and how often the
    compaction buffer overflowed into the full-oracle escape hatch.
    """

    def __init__(self, arrays: IndexArrays, err_lo_by_seg,
                 err_hi_by_seg=None, *, backend: Optional[str] = None,
                 fused_impl: Optional[str] = None,
                 interpret: Optional[bool] = None, q_tile: int = 0,
                 w_tile: int = 2048, seg_chunk: int = 512,
                 win_chunk: int = 512, fb_frac: float = FB_FRAC,
                 min_bucket: int = 256, xla_min_bucket: int = 8192,
                 fused_flat_max_bucket: int = 8192):
        self.arrays = arrays
        # the fused single-dispatch path is the default everywhere; the
        # multi-op "xla"/"pallas" stages stay as debug/reference backends
        self.backend = backend or "fused"
        # which fused implementation serves: the fused XLA graph on every
        # platform; the fused Pallas kernel only on explicit request (the
        # TPU compiler refuses it — see the module doc)
        self.fused_impl = fused_impl or "xla"
        self.interpret = resolve_interpret(interpret)
        self.q_tile = q_tile
        self.w_tile = w_tile
        self.seg_chunk = seg_chunk
        self.win_chunk = win_chunk
        self.fb_frac = fb_frac
        self.min_bucket = max(32, int(min_bucket))
        # below this bucket the LEGACY windowed path's extra ops cost
        # more than the full searchsorted they avoid; applies only to
        # non-forced "xla" requests (the fused path owns the
        # small/medium regime and is never downgraded)
        self.xla_min_bucket = int(xla_min_bucket)
        # above this bucket the fused path trades its wide flat window
        # for the bisect (compare count starts to matter at throughput
        # scale; below it the dependent-load latency chain does)
        self.fused_flat_max_bucket = int(fused_flat_max_bucket)
        self.err_lo = np.asarray(err_lo_by_seg, np.float32)
        self.err_hi = (None if err_hi_by_seg is None
                       else np.asarray(err_hi_by_seg, np.float32))
        # device-resident padded error bounds + static trip count, so the
        # hot path does zero host-side array prep per call
        err_hi_np = (np.zeros_like(self.err_lo) if self.err_hi is None
                     else self.err_hi)
        self._upload_bounds(self.err_lo, err_hi_np)
        self._trips = _bisect_trips(self.err_lo, err_hi_np)
        self._flat_w = _flat_width(self.err_lo, err_hi_np)
        self._fused_flat_w = _fused_flat_width(self.err_lo, err_hi_np)
        # approximate radix router: one multiply + one 64 KiB table
        # gather instead of the exact segment-routing searchsorted
        table, scale = build_radix_router(arrays)
        self._radix_table = jnp.asarray(table)
        self._radix_scale = jnp.asarray(scale)
        # bucket -> slot-rank table for the fused XLA search (the
        # host-side numpy copy feeds incremental row refreshes)
        self._rank_np, rk_scale, self._rank_trips, self._rank_meta = \
            build_rank_router(
                np.asarray(arrays.slot_key),
                np.asarray(arrays.slot_key_lo) if arrays.key_wide
                else None)
        self._rank_table = jnp.asarray(self._rank_np)
        self._rank_scale = jnp.asarray(rk_scale)
        # sticky per-bucket fallback-capacity boost: a workload that once
        # overflowed gets a larger compaction buffer next time instead of
        # paying the oracle escape on every call
        self._cap_boost: dict = {}
        # lazy host copies for the fused path's escape patch (invalidated
        # whenever swap_arrays installs delta-updated buffers)
        self._host_cache = None
        self.last_stage: Optional[str] = None  # search stage of last call
        self.stats = {"calls": 0, "fallbacks": 0, "oracle_escapes": 0,
                      "buckets": set()}

    @classmethod
    def from_index(cls, index, *, w_tile: int = 2048, seg_chunk: int = 512,
                   max_chain: Optional[int] = None, **kwargs):
        """Freeze an index with query-safe window bounds.

        Deprecated entry point: prefer the epoch-versioned
        ``repro.core.Index`` handle, which owns the engine, keeps it
        fresh across mutations via delta updates, and returns typed
        ``LookupResult``s.  This classmethod remains as a thin shim for
        code that manages freezing manually.
        """
        arrays = from_learned_index(index, w_tile=w_tile,
                                    seg_chunk=seg_chunk, max_chain=max_chain)
        err_lo, err_hi = query_window_bounds(index)
        return cls(arrays, err_lo, err_hi, w_tile=w_tile,
                   seg_chunk=seg_chunk, **kwargs)

    def swap_arrays(self, arrays: IndexArrays) -> None:
        """Adopt delta-updated buffers (same shapes/statics — compiled
        executables stay valid)."""
        self.arrays = arrays

    def _upload_bounds(self, err_lo: np.ndarray, err_hi: np.ndarray):
        k_pad = int(self.arrays.seg_first_key.shape[0])
        self._elo = jnp.asarray(
            _pad_pow(err_lo, k_pad, np.float32(0))[:k_pad])
        self._ehi = jnp.asarray(
            _pad_pow(err_hi, k_pad, np.float32(0))[:k_pad])

    def refresh_bounds(self, err_lo, err_hi) -> None:
        """Adopt incrementally refreshed per-segment window bounds after
        a delta update (same K — array shapes stay fixed, so the
        resident buffers are simply re-uploaded).

        The width-derived jit statics (bisect trip count, flat widths)
        are re-derived too: they only change when a refreshed window
        crosses its pow2/log2 sizing threshold, which costs ONE extra
        executable compile for the new static combination — without it,
        windows that outgrow the frozen trip budget would escape to the
        compacted fallback on every call (sound, but exactly the
        fallback-rate climb this refresh exists to prevent).
        """
        err_lo = np.asarray(err_lo, np.float32)
        err_hi = np.asarray(err_hi, np.float32)
        self.err_lo = err_lo
        self.err_hi = err_hi
        self._upload_bounds(err_lo, err_hi)
        self._trips = _bisect_trips(err_lo, err_hi)
        self._flat_w = _flat_width(err_lo, err_hi)
        self._fused_flat_w = _fused_flat_width(err_lo, err_hi)

    def _host_views(self) -> dict:
        cached = self._host_cache
        if cached is None or cached[0] is not self.arrays:
            cached = (self.arrays, host_fallback_views(self.arrays))
            self._host_cache = cached
        return cached[1]

    def refresh_rank_rows(self, touched_keys, slot_key, slot_key_lo=None,
                          upload=True):
        """Incrementally refresh the fused path's rank table after a
        delta update: only the buckets covering the touched key values
        recompute their boundary ranks against the CURRENT (host) slot
        keys.  A skipped/stale row is sound — the fused search's bracket
        validation turns it into compacted fallbacks, never wrong
        results — so this is purely a fallback-rate knob.

        ``upload=False`` refreshes only the host copy (``_rank_np``):
        the fused single-dispatch ingest already wrote the refreshed
        rows into the device table in-graph, so the commit path only
        needs the host mirror caught up for FUTURE incremental calls.
        """
        touched = np.asarray(touched_keys, np.float64)
        kmin, scale, r_size = self._rank_meta
        if touched.size == 0 or touched.size > r_size // 4:
            # empty, or near-global churn: a row-by-row refresh would
            # cost more than the fallbacks it saves — stale rows stay
            # sound (bracket validation), and the refreeze policy
            # catches sustained growth
            return
        touched = touched[np.isfinite(touched)]
        if touched.size == 0:
            return
        b = np.clip((touched - kmin) * scale, 0, r_size - 1).astype(np.int64)
        # one row of margin each side: the representation rounding below
        # can move a key across a bucket boundary
        rows = np.unique(np.clip(np.concatenate([b - 1, b, b + 1]),
                                 0, r_size))
        sk = np.asarray(slot_key, np.float64)
        if slot_key_lo is not None and np.asarray(slot_key_lo).size:
            sk = sk + np.asarray(slot_key_lo, np.float64)
        # round into the FROZEN device key representation (f32 hi/lo
        # pair sum when wide, plain f32 when narrow) so the refreshed
        # ranks agree with the device bracket validation bit-for-bit —
        # the table was built from the device values, and callers pass
        # the full-precision host keys
        if self.arrays.key_wide:
            sk_hi, sk_lo = split_key_pair(sk)
            sk = sk_hi.astype(np.float64) + sk_lo.astype(np.float64)
        else:
            sk = sk.astype(np.float32).astype(np.float64)
        bounds = kmin + rows.astype(np.float64) / scale
        vals = np.searchsorted(sk, bounds, side="left").astype(np.int32)
        top = rows == r_size
        if np.any(top):  # top boundary includes duplicated max keys
            fin = sk[np.isfinite(sk)]
            kmax = float(fin[-1]) if fin.size else kmin
            vals[top] = np.searchsorted(sk, kmax, side="right")
        self._rank_np[rows] = vals
        if upload:
            self._rank_table = jnp.asarray(self._rank_np)

    def ingest_place(self, keys):
        """Device §5.3 ingest placement against the frozen arrays: the
        per-key primitives ``GappedArray.insert_batch`` consumes, plus
        the escape mask for the O(#escapes) host patch (see
        ``ops_gap.ingest_place``).  Served by the fused-XLA graph, or by
        the Pallas kernel when the engine was built with
        ``fused_impl="pallas"``, like ``fused`` lookups."""
        from .ops_gap import ingest_place as _place
        return _place(self.arrays, keys,
                      impl=("pallas" if self.fused_impl == "pallas"
                            else "xla"),
                      interpret=self.interpret)

    def _rank_bounds(self):
        """Device-resident f32-pair bucket-boundary keys for the fused
        ingest graph's in-dispatch rank-row refresh.  Lazy (~2x(r+1)
        f32, built once per engine): lookups never touch it, and rebuild
        is only needed on refreeze — which makes a new engine anyway."""
        cached = getattr(self, "_rank_bounds_pair", None)
        if cached is None:
            kmin, scale, r_size = self._rank_meta
            bounds = kmin + np.arange(int(r_size) + 1,
                                      dtype=np.float64) / scale
            bh, bl = split_key_pair(bounds)
            cached = (jnp.asarray(bh), jnp.asarray(bl))
            self._rank_bounds_pair = cached
        return cached

    def fused_ingest(self, keys, payloads):
        """Single-dispatch §5.3 ingest against the frozen arrays: ONE
        jitted graph computes placement primitives, the slot-arm
        scatter + carried-key repair, the device CSR merge for the
        chain arm, and the rank-row/window-bound refresh (see
        ``ops_gap.fused_ingest``).  Returns ``(prims, escape, ok,
        reasons, state)`` — on ``ok`` the caller commits ``state`` via
        ``adopt_fused_state``; on abort the primitives are still valid
        for the host-partition fallback, so the dispatch is never
        wasted."""
        from .ops_gap import fused_ingest as _fused
        bh, bl = self._rank_bounds()
        return _fused(
            self.arrays, keys, payloads, rank_table=self._rank_table,
            rank_bounds_hi=bh, rank_bounds_lo=bl,
            rank_scale=self._rank_scale, elo=self._elo, ehi=self._ehi,
            max_chain=self.arrays.max_chain, impl=self.fused_impl,
            interpret=self.interpret, min_bucket=self.min_bucket)

    def adopt_fused_state(self, state: dict, err_lo=None,
                          err_hi=None) -> None:
        """Install the fused dispatch's output buffers (same shapes and
        statics — compiled executables stay valid), including the
        in-graph refreshed rank table and window bounds.  Fields whose
        frozen image is zero-length (narrow key/payload lo/hi splits)
        are skipped: the graph computes them from zeros and they must
        stay zero-length in ``IndexArrays``.  ``err_lo``/``err_hi`` are
        the caller-updated HOST bound mirrors; the width-derived jit
        statics are re-derived from them exactly as ``refresh_bounds``
        does (no re-upload — the device copies were written in-graph).
        """
        updates = {f: state[f] for f in _DELTA_FIELDS
                   if int(getattr(self.arrays, f).shape[0])}
        self.arrays = dataclasses.replace(self.arrays, **updates)
        self._rank_table = state["rank_table"]
        self._elo = state["elo"]
        self._ehi = state["ehi"]
        if err_lo is not None:
            err_lo = np.asarray(err_lo, np.float32)
            err_hi = np.asarray(err_hi, np.float32)
            self.err_lo = err_lo
            self.err_hi = err_hi
            self._trips = _bisect_trips(err_lo, err_hi)
            self._flat_w = _flat_width(err_lo, err_hi)
            self._fused_flat_w = _fused_flat_width(err_lo, err_hi)

    def bucket(self, n: int) -> int:
        b = self.min_bucket
        while b < n:
            b <<= 1
        return b

    def _fused_width_for(self, b: int) -> int:
        """Flat width for the fused path at bucket size ``b`` — the wide
        latency-optimal window below ``fused_flat_max_bucket``, the
        compare-lean legacy width (or the bisect, 0) above it."""
        return (self._fused_flat_w if b <= self.fused_flat_max_bucket
                else self._flat_w)

    def _dispatch(self, qj, qlj, backend, q_tile, fb_cap, queries_sorted,
                  flat_w=None):
        a = self.arrays
        return _pipeline(
            qj, qlj, a.seg_first_key, a.seg_first_key_lo,
            a.seg_slope, a.seg_icept, self._elo, self._ehi,
            a.slot_key, a.slot_key_lo, a.payload, a.payload_hi,
            a.link_offsets, a.link_keys, a.link_keys_lo,
            a.link_payloads, a.link_payload_hi,
            self._radix_table, self._radix_scale,
            q_tile=q_tile, w_tile=self.w_tile, seg_chunk=self.seg_chunk,
            win_chunk=self.win_chunk, max_chain=a.max_chain,
            n_slots=a.n_slots, interpret=self.interpret, backend=backend,
            assume_sorted=queries_sorted, fb_cap=fb_cap,
            trips=self._trips,
            flat_w=self._flat_w if flat_w is None else flat_w,
            radix=(backend in ("xla", "fused-pallas")),
            wide=a.wide, key_wide=a.key_wide,
        )

    def lookup(self, queries, *, queries_sorted: bool = False,
               backend: Optional[str] = None, force_backend: bool = False):
        """Returns (payloads, slot, found, fb_count) sliced to len(queries).

        ``backend`` overrides the engine default for this call ("fused"
        / "pallas" / "xla" / "oracle"); wide-key indexes route the
        legacy narrow-only "pallas" kernel to "xla" (a capability,
        always applied — the fused path serves wide keys natively).
        The fused path owns every bucket size; the size-aware
        xla->oracle downgrade only applies to non-forced requests for
        the legacy "xla" reference stage.  ``self.last_stage`` records
        the stage that actually ran ("fused" covers both the Pallas
        kernel and the fused XLA graph — see ``self.fused_impl``).
        """
        key_wide = self.arrays.key_wide
        qh, ql = _split_queries(queries, key_wide)
        n_q = qh.shape[0]
        b = self.bucket(n_q)
        if b == n_q:
            qp, qlp = qh, ql
        else:
            qp = np.full(b, np.inf, np.float32)
            qp[:n_q] = qh  # +inf tail keeps sorted batches sorted
            if key_wide:
                qlp = np.zeros(b, np.float32)
                qlp[:n_q] = ql
            else:
                qlp = ql
        q_tile = min(b, self.q_tile or auto_q_tile(b, self.arrays.n_slots,
                                                   self.w_tile))
        backend = backend or self.backend
        if backend == "pallas" and key_wide:
            backend = "xla"  # capability fallback (legacy kernel)
        if (backend == "xla" and b < self.xla_min_bucket
                and not force_backend):
            backend = "oracle"  # size-aware scheduling (see __init__)
        stage = backend
        flat_w = None
        if backend == "fused":
            stage = ("fused-pallas" if self.fused_impl == "pallas"
                     else "fused")
            flat_w = self._fused_width_for(b)
        self.last_stage = backend
        tile_granular = stage in ("pallas", "fused-pallas")
        boost = self._cap_boost.get(b, 1)
        fb_cap = int(min(b, boost * max(
            q_tile if tile_granular else 64,
            int(np.ceil(self.fb_frac * b)))))
        qj = jnp.asarray(qp)
        qlj = jnp.asarray(qlp)
        if stage == "fused":
            # fused-XLA contract: ONE lean dispatch returning the escape
            # MASK; the (rare) flagged queries are patched in
            # O(#escapes) host numpy — no device compaction, no
            # overflow/oracle escape
            a = self.arrays
            out, out_hi, slot, found, fb = _fused_pipeline(
                qj, qlj, a.slot_key, a.slot_key_lo, a.payload,
                a.payload_hi, a.link_offsets, a.link_keys,
                a.link_keys_lo, a.link_payloads, a.link_payload_hi,
                self._rank_table, self._rank_scale,
                trips=self._rank_trips, max_chain=a.max_chain,
                wide=a.wide, key_wide=a.key_wide)
            out, slot_h, found_h, n_fb = _finish_fused_host(
                out, out_hi, slot, found, fb, n_q, a.wide, queries,
                self._host_views)
            self.stats["calls"] += 1
            self.stats["fallbacks"] += n_fb
            self.stats["buckets"].add(b)
            return out, slot_h, found_h, n_fb
        out, out_hi, slot, found, fb, overflow = self._dispatch(
            qj, qlj, stage, q_tile, fb_cap, bool(queries_sorted), flat_w)
        if backend != "oracle" and fb_cap < b and bool(overflow):
            self.stats["oracle_escapes"] += 1
            self._cap_boost[b] = min(boost * 4, 64)  # sticky escalation
            self.last_stage = "oracle"  # the stage that actually served
            out, out_hi, slot, found, fb, _ = self._dispatch(
                qj, qlj, "oracle", q_tile, fb_cap, bool(queries_sorted))
        self.stats["calls"] += 1
        self.stats["fallbacks"] += int(fb)
        self.stats["buckets"].add(b)
        out = _recombine_i64(out, out_hi, n_q, self.arrays.wide)
        return out, slot[:n_q], found[:n_q], fb
