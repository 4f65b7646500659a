"""Host prep + jit wrappers + jnp oracles for the gap-insertion device
kernels (Eq. 3 gap placement AND the §5.3 dynamic-ingest placement
stage — see ``ingest_place`` for the latter's contract)."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .gap_place import (fused_ingest_body, gap_place_call,
                        ingest_place_body, ingest_place_call)
from .lookup import resolve_interpret
from .ops import _pad_pow


def prepare_gap_tables(x: np.ndarray, y: np.ndarray, plm, rho: float,
                       seg_chunk: int = 512):
    """Fold Eq. 3 into per-segment (first_key, base, x0, scale) tables.

    Mirrors core.gaps.gap_positions' segment anchoring (first/last present
    key per segment), done once host-side in O(n).
    """
    seg = plm.segment_of(x)
    K = plm.n_segments
    n = x.shape[0]
    idx = np.arange(n, dtype=np.int64)
    first = np.full(K, n, np.int64)
    last = np.full(K, -1, np.int64)
    np.minimum.at(first, seg, idx)
    np.maximum.at(last, seg, idx)
    present = first < n
    f = np.minimum(first, n - 1)
    l = np.clip(last, 0, n - 1)
    y_first = np.where(present, y[f], 0.0)
    y_last = np.where(present, y[l], 0.0)
    x_first = np.where(present, x[f], 0.0)
    x_last = np.where(present, x[l], 1.0)
    U = np.where(present, rho * (y_last - y_first), 0.0)
    S = np.concatenate([[0.0], np.cumsum(U)[:-1]])
    dx = np.where(x_last > x_first, x_last - x_first, 1.0)
    scale = (y_last - y_first) * (1.0 + rho) / dx
    base = y_first + S

    pad = lambda a, fill: _pad_pow(np.asarray(a, np.float32), seg_chunk,
                                   np.float32(fill))
    return (pad(plm.seg_first_key, np.inf), pad(base, 0.0),
            pad(x_first, 0.0), pad(scale, 0.0))


def gap_positions_device(x: np.ndarray, plm, rho: float, *,
                         key_tile: int = 1024, seg_chunk: int = 512,
                         interpret: Optional[bool] = None) -> np.ndarray:
    """Device Eq. 3: returns monotone target positions for all keys."""
    x = np.asarray(x, np.float64)
    y = np.arange(x.shape[0], dtype=np.float64)
    segk, base, x0, scale = prepare_gap_tables(x, y, plm, rho, seg_chunk)
    xp = _pad_pow(x.astype(np.float32), key_tile, np.float32(np.inf))
    out = gap_place_call(
        jnp.asarray(xp), jnp.asarray(segk), jnp.asarray(base),
        jnp.asarray(x0), jnp.asarray(scale),
        key_tile=key_tile, seg_chunk=seg_chunk,
        interpret=resolve_interpret(interpret),
    )
    yg = np.asarray(out)[: x.shape[0]].astype(np.float64)
    return np.maximum.accumulate(yg)  # same boundary-tie guard as core


def gap_positions_oracle(x: np.ndarray, plm, rho: float) -> np.ndarray:
    """Pure-jnp/numpy oracle — delegates to the core implementation."""
    from ..core.gaps import gap_positions

    x = np.asarray(x, np.float64)
    return gap_positions(x, np.arange(x.shape[0], dtype=np.float64), plm,
                         rho)


# ---------------------------------------------------------------------------
# §5.3 dynamic-ingest placement backend (device primitives for
# GappedArray.insert_batch — registered in the kernels backend table)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_slots",))
def _ingest_place_xla(x_hi, x_lo, segk_hi, segk_lo, slope_hi, slope_lo,
                      icept_hi, icept_lo, slot_hi, slot_lo, link_offsets,
                      link_hi, link_lo, *, n_slots):
    """Fused-XLA variant: the SAME per-key body the Pallas kernel runs,
    over the whole batch in one lean dispatch (the default ingest-place
    backend on every platform, mirroring the fused lookup)."""
    return ingest_place_body(
        x_hi, x_lo, segk_hi, segk_lo, slope_hi, slope_lo, icept_hi,
        icept_lo, slot_hi, slot_lo, link_offsets, link_hi, link_lo,
        n_slots=n_slots)


def ingest_place(arrays, keys, *, impl: str = "xla",
                 interpret: Optional[bool] = None, key_tile: int = 512):
    """Device §5.3 ingest placement: per-key placement primitives for an
    insert batch, computed against the FROZEN device arrays.

    Returns ``(primitives, escape)`` where ``primitives`` is the numpy
    dict ``GappedArray.insert_batch`` consumes (``p``/``free``/``pv``/
    ``ub``/``bracket`` — the same contract as the host oracle
    ``GappedArray.placement_primitives``) and ``escape`` flags keys
    whose double-f32 prediction landed inside the rounding-band guard;
    the caller (``Index.ingest``) re-derives THOSE rows host-side in
    O(#escapes) and the patched primitives are bit-identical to the
    host oracle.

    Exactness contract (gated by the Index handle): every stored and
    batch key must be pair-exact (reconstructed exactly by its f32
    hi/lo split — all integer keys < 2^48), so every pair compare below
    equals the host's f64 compare; narrow (f32-exact) indexes run with
    zero lo arrays.  ``impl`` picks the fused-XLA graph ("xla", the
    default on every platform) or the Pallas kernel ("pallas", explicit
    request only — the TPU compiler refuses it today); both run ONE
    shared per-key body, so they are bit-identical by construction.
    """
    from .ops import split_key_pair

    keys = np.asarray(keys, np.float64)
    x_hi, x_lo = split_key_pair(keys)
    key_wide = bool(arrays.key_wide)
    segk_hi = arrays.seg_first_key
    segk_lo = (arrays.seg_first_key_lo if key_wide
               else jnp.zeros_like(segk_hi))
    slot_hi = arrays.slot_key
    slot_lo = (arrays.slot_key_lo if key_wide
               else jnp.zeros_like(slot_hi))
    link_hi = arrays.link_keys
    link_lo = (arrays.link_keys_lo if key_wide
               else jnp.zeros_like(link_hi))
    if int(link_hi.shape[0]) == 0:  # tileable non-empty chain tables
        link_hi = jnp.full((1,), jnp.inf, jnp.float32)
        link_lo = jnp.zeros((1,), jnp.float32)
    n_b = keys.shape[0]
    if impl == "pallas":
        pad = (-n_b) % key_tile
        xh = jnp.asarray(np.concatenate(
            [x_hi, np.full(pad, np.inf, np.float32)]))
        xl = jnp.asarray(np.concatenate([x_lo, np.zeros(pad, np.float32)]))
        p, pv, ub, flags = ingest_place_call(
            xh, xl, segk_hi, segk_lo, arrays.seg_slope,
            arrays.seg_slope_lo, arrays.seg_icept, arrays.seg_icept_lo,
            slot_hi, slot_lo, arrays.link_offsets, link_hi, link_lo,
            key_tile=key_tile, n_slots=arrays.n_slots,
            interpret=resolve_interpret(interpret))
        flags = np.asarray(flags)[:n_b]
        free = (flags & 1).astype(bool)
        bracket = (flags & 2).astype(bool)
        escape = (flags & 4).astype(bool)
    else:
        p, pv, ub, free, bracket, escape = _ingest_place_xla(
            jnp.asarray(x_hi), jnp.asarray(x_lo), segk_hi, segk_lo,
            arrays.seg_slope, arrays.seg_slope_lo, arrays.seg_icept,
            arrays.seg_icept_lo, slot_hi, slot_lo, arrays.link_offsets,
            link_hi, link_lo, n_slots=arrays.n_slots)
        free = np.asarray(free)[:n_b]
        bracket = np.asarray(bracket)[:n_b]
        escape = np.asarray(escape)[:n_b]
    prims = {  # writable copies: the caller patches escape rows in place
        "p": np.asarray(p)[:n_b].astype(np.int64),
        "free": np.array(free, dtype=bool),
        "pv": np.asarray(pv)[:n_b].astype(np.int64),
        "ub": np.asarray(ub)[:n_b].astype(np.int64),
        "bracket": np.array(bracket, dtype=bool),
    }
    return prims, np.array(escape, dtype=bool)


# ---------------------------------------------------------------------------
# single-dispatch fused ingest (placement + partition + slot scatter +
# device CSR merge + rank/bound refresh — ONE graph, see
# gap_place.fused_ingest_body for the correctness contract)
# ---------------------------------------------------------------------------

# abort-reason bit names (the graph's ``reasons`` bitmask), for stats
FUSED_ABORT_BITS = (
    "escape", "dup_batch", "collision_group", "slot_dup", "contested",
    "d1_demote", "d4_demote", "chain_overflow", "link_overflow",
    "chain_dup",
)


@functools.partial(jax.jit, static_argnames=(
    "n_slots", "max_chain", "key_wide", "use_pallas", "interpret",
    "key_tile"))
def _fused_ingest_xla(
        x_hi, x_lo, pay_lo, pay_hi, segk_hi, segk_lo, slope_hi, slope_lo,
        icept_hi, icept_lo, slot_hi, slot_lo, spay_lo, spay_hi,
        link_offsets, link_hi, link_lo, lpay_lo, lpay_hi, rank_l1,
        rank_table, rank_bounds_hi, rank_bounds_lo, rank_scale, elo, ehi,
        *, n_slots, max_chain, key_wide, use_pallas, interpret, key_tile):
    """The one device dispatch ``Index.ingest`` issues on the fused
    path (the dispatch-counting shim in tests/test_fused_ingest.py
    monkeypatches exactly this symbol)."""
    return fused_ingest_body(
        x_hi, x_lo, pay_lo, pay_hi, segk_hi, segk_lo, slope_hi, slope_lo,
        icept_hi, icept_lo, slot_hi, slot_lo, spay_lo, spay_hi,
        link_offsets, link_hi, link_lo, lpay_lo, lpay_hi, rank_l1,
        rank_table, rank_bounds_hi, rank_bounds_lo, rank_scale, elo, ehi,
        n_slots=n_slots, max_chain=max_chain, key_wide=key_wide,
        use_pallas=use_pallas, interpret=interpret, key_tile=key_tile)


def fused_ingest(arrays, keys, payloads, *, rank_l1, rank_table,
                 rank_bounds_hi, rank_bounds_lo, rank_scale, elo, ehi,
                 max_chain,
                 impl: str = "xla", interpret: Optional[bool] = None,
                 min_bucket: int = 256, key_tile: int = 512):
    """Single-dispatch device-resident ingest.

    Pads the batch to a power-of-two bucket (+inf keys / -1 payloads —
    each bucket compiles once, like the fused lookup), runs the fused
    graph, and returns ``(prims, escape, ok, reasons, state)``:

    * ``prims``/``escape`` — the usual ``ingest_place`` contract (valid
      whether or not the graph committed, so an aborted batch reuses
      them on the host partition path at no extra dispatch);
    * ``ok`` — True iff the graph produced the post-batch device
      images; ``reasons`` is the abort bitmask (``FUSED_ABORT_BITS``);
    * ``state`` — dict of NEW device arrays (slot/payload/link images,
      rank table, window bounds) plus the downloaded ``seg``/``dlt``
      residuals the caller mirrors into its host bound copies.  All
      entries are live device buffers — nothing round-trips through
      host numpy on the ok path.
    """
    from .ops import _split_i64, split_key_pair

    keys = np.asarray(keys, np.float64)
    payloads = np.asarray(payloads, np.int64)
    n_b = keys.shape[0]
    bucket = max(min_bucket, 1 << max(n_b - 1, 1).bit_length())
    pad = bucket - n_b
    x_hi, x_lo = split_key_pair(keys)
    x_hi = np.concatenate([x_hi, np.full(pad, np.inf, np.float32)])
    x_lo = np.concatenate([x_lo, np.zeros(pad, np.float32)])
    p_lo, p_hi = _split_i64(payloads)
    p_lo = np.concatenate([p_lo, np.full(pad, -1, np.int32)])
    p_hi = np.concatenate([p_hi, np.full(pad, -1, np.int32)])

    key_wide = bool(arrays.key_wide)
    wide = bool(arrays.wide)
    zeros_f = lambda a: jnp.zeros_like(a)  # noqa: E731
    segk_lo = arrays.seg_first_key_lo if key_wide \
        else zeros_f(arrays.seg_first_key)
    slot_lo = arrays.slot_key_lo if key_wide else zeros_f(arrays.slot_key)
    link_lo = arrays.link_keys_lo if key_wide \
        else zeros_f(arrays.link_keys)
    spay_hi = arrays.payload_hi if wide else zeros_f(arrays.payload)
    lpay_hi = arrays.link_payload_hi if wide \
        else zeros_f(arrays.link_payloads)

    outs = _fused_ingest_xla(
        jnp.asarray(x_hi), jnp.asarray(x_lo), jnp.asarray(p_lo),
        jnp.asarray(p_hi), arrays.seg_first_key, segk_lo,
        arrays.seg_slope, arrays.seg_slope_lo, arrays.seg_icept,
        arrays.seg_icept_lo, arrays.slot_key, slot_lo, arrays.payload,
        spay_hi, arrays.link_offsets, arrays.link_keys, link_lo,
        arrays.link_payloads, lpay_hi, rank_l1, rank_table,
        rank_bounds_hi, rank_bounds_lo, rank_scale, elo, ehi,
        n_slots=arrays.n_slots, max_chain=int(max_chain),
        key_wide=key_wide, use_pallas=(impl == "pallas"),
        interpret=resolve_interpret(interpret), key_tile=key_tile)
    (p, pv, ub, free, bracket, escape, ok, reasons, n_slot, n_chain,
     seg, dlt) = outs[:12]
    (slot_key, slot_key_lo, payload, payload_hi, link_offsets, link_keys,
     link_keys_lo, link_payloads, link_payload_hi, new_rank, new_elo,
     new_ehi) = outs[12:]
    prims = {  # writable copies: escape rows are patched in place
        "p": np.asarray(p)[:n_b].astype(np.int64),
        "free": np.array(np.asarray(free)[:n_b], dtype=bool),
        "pv": np.asarray(pv)[:n_b].astype(np.int64),
        "ub": np.asarray(ub)[:n_b].astype(np.int64),
        "bracket": np.array(np.asarray(bracket)[:n_b], dtype=bool),
    }
    state = {
        "slot_key": slot_key, "slot_key_lo": slot_key_lo,
        "payload": payload, "payload_hi": payload_hi,
        "link_offsets": link_offsets, "link_keys": link_keys,
        "link_keys_lo": link_keys_lo, "link_payloads": link_payloads,
        "link_payload_hi": link_payload_hi, "rank_table": new_rank,
        "elo": new_elo, "ehi": new_ehi,
        "n_slot": int(n_slot), "n_chain": int(n_chain),
        "seg": np.asarray(seg)[:n_b], "dlt": np.asarray(dlt)[:n_b],
    }
    return (prims, np.array(np.asarray(escape)[:n_b], dtype=bool),
            bool(ok), int(reasons), state)
