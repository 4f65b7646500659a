"""Gap-insertion device kernels — Pallas TPU.

Two kernels live here:

1. ``gap_place_call`` — Eq. 3 gap-position manipulation: the
   result-driven target position for every key,

    y^g_i = base[seg(x_i)] + (x_i - x0[seg(x_i)]) * scale[seg(x_i)]

   where per-segment constants fold the paper's Eq. 3 terms
   (``base = y_k1 + S_k``, ``scale = (y_km - y_k1)(1+rho)/(x_km-x_k1)``,
   ``x0 = x_k1``; host-side prep in ``ops_gap.prepare_gap_tables``).
   Structure mirrors the lookup kernel's routing stage: keys tiled over
   the grid, segment tables VMEM-resident, branchless rank-routing via
   chunked masked counts, one fused multiply-add — O(n) with n/key_tile
   grid steps.  This makes the §5.4 combined pipeline (sample -> fit ->
   *place all n keys*) device-resident for billion-key stores.

2. ``ingest_place_call`` — the §5.3 dynamic-ingest placement stage:
   for a batch of insert keys, compute the per-key placement primitives
   (predicted slot, slot occupancy, run boundaries, order-check
   bracket) directly against the FROZEN device arrays, so
   ``Index.ingest`` ships placements back for the CSR merge instead of
   re-deriving everything in host numpy.  The per-key body
   (``ingest_place_body``) is shared verbatim with the fused-XLA
   variant in ``ops_gap`` — one numerics contract, two dispatch
   strategies (see ``ops_gap.ingest_place`` for the exactness story:
   f32 hi/lo pair compares end to end, double-f32 prediction with a
   rounding-band escape patched on host in O(#escapes)).

Double-f32 ("pair") arithmetic: slopes/intercepts and wide keys are
carried as f32 (hi, lo) pairs; ``_dd_mul``/``_dd_add2`` below implement
the classic Dekker/Knuth error-free transforms WITHOUT an fma (XLA-CPU
has no guaranteed fused multiply-add), giving ~2^-45-relative products
— far inside the host-patch escape band.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .lookup import resolve_interpret


# ---------------------------------------------------------------------------
# double-f32 (pair) arithmetic — error-free transforms, no fma needed
# ---------------------------------------------------------------------------

_SPLITTER = 4097.0  # 2^12 + 1 (Veltkamp split for f32; python scalar so
#                     Pallas kernels don't capture a traced constant)


def _two_sum(a, b):
    """Knuth two-sum: s + e == a + b exactly."""
    s = a + b
    bb = s - a
    e = (a - (s - bb)) + (b - bb)
    return s, e


def _two_prod(a, b):
    """Dekker two-product via Veltkamp splitting: p + e == a * b."""
    p = a * b
    ca = _SPLITTER * a
    ah = ca - (ca - a)
    al = a - ah
    cb = _SPLITTER * b
    bh = cb - (cb - b)
    bl = b - bh
    e = ((ah * bh - p) + ah * bl + al * bh) + al * bl
    return p, e


def _dd_add2(ah, al, bh, bl):
    """(ah, al) + (bh, bl), renormalized."""
    s, e = _two_sum(ah, bh)
    e = e + (al + bl)
    return _two_sum(s, e)


def _dd_sub2(ah, al, bh, bl):
    return _dd_add2(ah, al, -bh, -bl)


def _dd_mul(ah, al, bh, bl):
    """(ah, al) * (bh, bl), renormalized (drops the al*bl term)."""
    p, e = _two_prod(ah, bh)
    e = e + (ah * bl + al * bh)
    return _two_sum(p, e)


# ---------------------------------------------------------------------------
# pair compares + fixed-trip bisects (lexicographic (hi, lo) order ==
# numeric f64 order for pair-split keys — kernels.ops.split_key_pair)
# ---------------------------------------------------------------------------


def _p_le(kh, kl, qh, ql):
    return (kh < qh) | ((kh == qh) & (kl <= ql))


def _p_lt(kh, kl, qh, ql):
    return (kh < qh) | ((kh == qh) & (kl < ql))


def _p_eq(kh, kl, qh, ql):
    return (kh == qh) & (kl == ql)


def _bisect_pair(kh, kl, qh, ql, trips, strict):
    """Rightmost index with key {<,<=} query over the whole array
    (-1 when none) — branchless fixed-trip bisect, pair-aware."""
    n = kh.shape[0]
    cmp = _p_lt if strict else _p_le
    lo0 = jnp.full(qh.shape, -1, jnp.int32)
    hi0 = jnp.full(qh.shape, n - 1, jnp.int32)

    def body(_, carry):
        lo, hi = carry
        upd = lo < hi
        mid = (lo + hi + 1) >> 1
        midc = jnp.clip(mid, 0, n - 1)
        go = cmp(jnp.take(kh, midc), jnp.take(kl, midc), qh, ql)
        lo = jnp.where(upd & go, mid, lo)
        hi = jnp.where(upd, jnp.where(go, hi, mid - 1), hi)
        return lo, hi

    lo, _ = jax.lax.fori_loop(0, trips, body, (lo0, hi0))
    return lo


def ingest_place_body(
    x_hi, x_lo,                       # (B,) f32 pair of batch keys
    segk_hi, segk_lo,                 # (Kpad,) f32 segment first keys
    slope_hi, slope_lo,               # (Kpad,) f32 pair of slopes
    icept_hi, icept_lo,               # (Kpad,) f32 pair of intercepts
    slot_hi, slot_lo,                 # (Mpad,) f32 pair, +inf padded
    link_offsets,                     # (>= Mpad+1,) i32 CSR offsets
    link_hi, link_lo,                 # (Lpad,) f32 pair of chain keys
    *,
    n_slots: int,
):
    """Per-key §5.3 placement primitives against frozen device arrays.

    Returns ``(p, pv, ub, free, bracket, escape)`` — the device image of
    ``GappedArray.placement_primitives`` (the host oracle):

    * predicted slot ``p = clip(rint(slope*(x - seg_key) + icept))`` in
      double-f32, with ``escape`` flagging keys whose prediction lands
      within the pair-arithmetic error band of a rounding boundary (the
      host re-derives those few exactly);
    * ``free`` from the carried-key construction: a slot is occupied iff
      its key strictly precedes its right neighbor's;
    * ``ub``/``pv`` — key-run and slot-run left boundaries via pair
      bisects (exact: the Index handle gates this path on pair-exact
      key sets);
    * ``bracket`` — boundary-key order checks incl. the left boundary's
      chain max, gathered from the CSR link tables.

    Pure jnp on purpose: the Pallas kernel calls it per key tile over
    VMEM-resident tables, the fused-XLA variant over the whole batch —
    bit-identical by construction.
    """
    k_pad = segk_hi.shape[0]
    m_pad = slot_hi.shape[0]
    seg_trips = int(max(k_pad, 2) - 1).bit_length() + 1
    slot_trips = int(max(m_pad, 2) - 1).bit_length() + 1

    # --- segment routing (searchsorted-right - 1, clipped like host) ---
    seg = _bisect_pair(segk_hi, segk_lo, x_hi, x_lo, seg_trips,
                       strict=False)
    seg = jnp.clip(seg, 0, k_pad - 1)

    # --- double-f32 prediction + rint with escape band -----------------
    fk_h = jnp.take(segk_hi, seg)
    fk_l = jnp.take(segk_lo, seg)
    dx_h, dx_l = _dd_sub2(x_hi, x_lo, fk_h, fk_l)
    sl_h = jnp.take(slope_hi, seg)
    sl_l = jnp.take(slope_lo, seg)
    ic_h = jnp.take(icept_hi, seg)
    ic_l = jnp.take(icept_lo, seg)
    m_h, m_l = _dd_mul(sl_h, sl_l, dx_h, dx_l)
    y_h, y_l = _dd_add2(m_h, m_l, ic_h, ic_l)
    rh = jnp.round(y_h)
    d = (y_h - rh) + y_l  # |y_h - rh| <= 0.5 -> Sterbenz-exact
    step = jnp.where(d > 0.5, 1, jnp.where(d < -0.5, -1, 0)).astype(
        jnp.int32)
    rh_c = jnp.clip(rh, -1.0, float(n_slots))  # i32-safe (host clips too)
    p = jnp.clip(rh_c.astype(jnp.int32) + step, 0, n_slots - 1)
    # escape band: double-f32 carries ~2^-45 relative error; flag any
    # prediction within a (hugely padded) 2^-30-relative band of the
    # .5 rounding boundary and let the host recompute it in f64
    tol = (jnp.abs(sl_h * dx_h) + jnp.abs(ic_h) + 4.0) * jnp.float32(2e-9)
    escape = jnp.abs(jnp.abs(d) - 0.5) < tol
    escape |= ~jnp.isfinite(y_h)  # f32 range overflow: host re-derives
    # clip edges: rint(f64) could land on the far side of the clip
    escape |= (rh <= 0.0) & (jnp.abs(d) > 0.4)
    escape |= (rh >= n_slots - 1) & (jnp.abs(d) > 0.4)

    # --- occupancy from the carried-key construction -------------------
    nx_h = jnp.take(slot_hi, p)
    nx_l = jnp.take(slot_lo, p)
    # right neighbor; a table frozen by _freeze_numpy always has an
    # +inf tail block past n_slots, but do not RELY on it — an exactly
    # m-sized table would otherwise self-compare the last slot and
    # misread an occupied last slot as free
    r_valid = p + 1 < m_pad
    r_i = jnp.minimum(p + 1, m_pad - 1)
    r_h = jnp.where(r_valid, jnp.take(slot_hi, r_i), jnp.inf)
    r_l = jnp.where(r_valid, jnp.take(slot_lo, r_i), 0.0)
    free = _p_eq(nx_h, nx_l, r_h, r_l)

    # --- run boundaries: key-run ub, slot-run pv -----------------------
    ub = _bisect_pair(slot_hi, slot_lo, x_hi, x_lo, slot_trips,
                      strict=False)
    pv = _bisect_pair(slot_hi, slot_lo, nx_h, nx_l, slot_trips,
                      strict=True)

    # --- bracket: prev boundary key (incl. chain max) < key < next -----
    pv_safe = jnp.maximum(pv, 0)
    pm_h = jnp.take(slot_hi, pv_safe)
    pm_l = jnp.take(slot_lo, pv_safe)
    s0 = jnp.take(link_offsets, pv_safe)
    e0 = jnp.take(link_offsets, pv_safe + 1)
    has_chain = e0 > s0
    if link_hi.shape[0]:
        ci = jnp.clip(e0 - 1, 0, link_hi.shape[0] - 1)
        cm_h = jnp.take(link_hi, ci)
        cm_l = jnp.take(link_lo, ci)
        bigger = has_chain & _p_lt(pm_h, pm_l, cm_h, cm_l)
        pm_h = jnp.where(bigger, cm_h, pm_h)
        pm_l = jnp.where(bigger, cm_l, pm_l)
    prev_ok = (pv < 0) | _p_lt(pm_h, pm_l, x_hi, x_lo)
    bracket = free & prev_ok & _p_lt(x_hi, x_lo, nx_h, nx_l)
    return p, pv, ub, free, bracket, escape


def _gap_place_kernel(
    x_ref,       # (key_tile,) f32 keys (sorted, padded +inf)
    segk_ref,    # (Kpad,) f32 segment first keys (+inf padded)
    base_ref,    # (Kpad,) f32
    x0_ref,      # (Kpad,) f32
    scale_ref,   # (Kpad,) f32
    out_ref,     # (key_tile,) f32 target positions
    *,
    seg_chunk: int,
):
    x = x_ref[:]
    kt = x.shape[0]
    k_pad = segk_ref.shape[0]

    def seg_count(c, acc):
        ks = segk_ref[pl.ds(c * seg_chunk, seg_chunk)]
        return acc + jnp.sum((ks[None, :] <= x[:, None]).astype(jnp.int32),
                             axis=1)

    n_chunks = k_pad // seg_chunk
    cnt = jax.lax.fori_loop(0, n_chunks, seg_count,
                            jnp.zeros((kt,), jnp.int32))
    seg = jnp.clip(cnt - 1, 0, k_pad - 1)
    base = jnp.take(base_ref[:], seg)
    x0 = jnp.take(x0_ref[:], seg)
    scale = jnp.take(scale_ref[:], seg)
    out_ref[:] = base + (x - x0) * scale


@functools.partial(
    jax.jit, static_argnames=("key_tile", "seg_chunk", "interpret"))
def gap_place_call(
    keys_padded,   # (Npad,) f32, padded with +inf
    seg_first_key, # (Kpad,) f32
    base,          # (Kpad,) f32
    x0,            # (Kpad,) f32
    scale,         # (Kpad,) f32
    *,
    key_tile: int = 1024,
    seg_chunk: int = 512,
    interpret: bool = False,
):
    n = keys_padded.shape[0]
    assert n % key_tile == 0 and seg_first_key.shape[0] % seg_chunk == 0
    grid = (n // key_tile,)
    kernel = functools.partial(_gap_place_kernel, seg_chunk=seg_chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((key_tile,), lambda i: (i,)),
            pl.BlockSpec(seg_first_key.shape, lambda i: (0,)),
            pl.BlockSpec(base.shape, lambda i: (0,)),
            pl.BlockSpec(x0.shape, lambda i: (0,)),
            pl.BlockSpec(scale.shape, lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((key_tile,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.float32),
        interpret=interpret,
    )(keys_padded, seg_first_key, base, x0, scale)


# ---------------------------------------------------------------------------
# §5.3 dynamic-ingest placement kernel
# ---------------------------------------------------------------------------


def _ingest_place_kernel(
    x_hi_ref, x_lo_ref,               # (key_tile,) f32 batch-key pair
    segk_hi_ref, segk_lo_ref,         # (Kpad,) segment tables
    slope_hi_ref, slope_lo_ref,
    icept_hi_ref, icept_lo_ref,
    slot_hi_ref, slot_lo_ref,         # (Mpad,) frozen slot keys
    off_ref,                          # (Opad,) i32 CSR offsets
    link_hi_ref, link_lo_ref,         # (Lpad,) chain keys
    p_ref, pv_ref, ub_ref,            # out (key_tile,) i32
    flags_ref,                        # out (key_tile,) i32 bitmask
    *,
    n_slots: int,
):
    """One key tile of ``ingest_place_body`` over VMEM-resident tables.

    The frozen tables ride whole-array BlockSpecs (slot keys at f32 are
    4 B/slot — ~4 MiB/M slots, VMEM-resident like the lookup kernel's
    segment tables), so the kernel only fits tables that fit VMEM;
    nothing routes around that — engines serve the fused-XLA variant
    unless this kernel is requested explicitly.  Flags pack free(1) |
    bracket(2) | escape(4).
    """
    p, pv, ub, free, bracket, escape = ingest_place_body(
        x_hi_ref[:], x_lo_ref[:],
        segk_hi_ref[:], segk_lo_ref[:],
        slope_hi_ref[:], slope_lo_ref[:],
        icept_hi_ref[:], icept_lo_ref[:],
        slot_hi_ref[:], slot_lo_ref[:],
        off_ref[:], link_hi_ref[:], link_lo_ref[:],
        n_slots=n_slots,
    )
    p_ref[:] = p
    pv_ref[:] = pv.astype(jnp.int32)
    ub_ref[:] = ub.astype(jnp.int32)
    flags_ref[:] = (free.astype(jnp.int32)
                    + 2 * bracket.astype(jnp.int32)
                    + 4 * escape.astype(jnp.int32))


_I32MAX = 2 ** 31 - 1  # sort sentinel: above any slot/link index


def _p_min(a, b):
    """Pair lexicographic min (associative_scan combine fn)."""
    ah, al = a
    bh, bl = b
    take = _p_le(ah, al, bh, bl)
    return jnp.where(take, ah, bh), jnp.where(take, al, bl)


def fused_ingest_body(
    x_hi, x_lo,                       # (B,) f32 pair, +inf padded
    pay_lo, pay_hi,                   # (B,) i32 payload pair (-1 padded)
    segk_hi, segk_lo,                 # (Kpad,) segment tables
    slope_hi, slope_lo,
    icept_hi, icept_lo,
    slot_hi, slot_lo,                 # (Mpad,) frozen slot keys
    spay_lo, spay_hi,                 # (Mpad,) i32 slot payload pair
    link_offsets,                     # (O,) i32 CSR offsets (tail=total)
    link_hi, link_lo,                 # (Lpad,) chain keys (+inf padded)
    lpay_lo, lpay_hi,                 # (Lpad,) i32 chain payload pair
    rank_table,                       # (R+1,) i32 fused-lookup rank rows
    rank_bounds_hi, rank_bounds_lo,   # (R+1,) f32 pair of bucket bounds
    rank_scale,                       # (3,) f32 (kmin_hi, kmin_lo, scale)
    elo, ehi,                         # (k_pad,) f32 per-seg window bounds
    *,
    n_slots: int,
    max_chain: int,
    key_wide: bool,
    use_pallas: bool = False,
    interpret: Optional[bool] = None,
    key_tile: int = 512,
):
    """The single-dispatch §5.3 ingest graph: placement -> partition ->
    slot scatter + carried repair -> CSR-merge scatter -> rank-row /
    window-bound refresh, all in ONE jitted XLA graph (with
    ``use_pallas`` the Pallas placement kernel composes inside it —
    still one dispatch).

    The graph serves exactly the batches whose host demotion closure is
    TRIVIAL — no collision groups (no two batch keys predict the same
    slot when either is free), no demotion rule fires on the first
    round, no contested remainder — which it detects in-graph and
    reports via ``reasons``; everything else returns the placement
    primitives untouched with ``ok=False`` so ``Index.ingest`` replays
    the batch through the host partition + delta path (the primitives
    are NOT wasted: they are the same ``ingest_place`` output the
    two-dispatch path would have computed).  On the accepted batches the
    split is provably the host's fixed point (``cand = free & bracket``,
    every other key chains at its pre-batch ``ub``), so the produced
    device images are bit-identical to freezing the post-batch host
    state:

    * slot arm — masked scatter of the key pair + payload pair at
      ``p[cand]``, then the carried-key repair as a reverse pair-min
      ``associative_scan`` (== ``_repair_carried``: pair lex order is
      numeric order for pair-exact splits);
    * chain arm — device CSR merge: chain entries are key-sorted
      (target order == key order by the global CSR key invariant), a
      strict pair bisect gives each its ``np.insert`` position, old
      elements shift by ``searchsorted(pos, i, 'right')``, offsets gain
      a prefix-sum of per-slot counts — single-allocation, no host
      ``np.insert``;
    * refresh arm — touched bucket rows of the fused lookup's rank
      table are re-bisected against the NEW slot keys in-graph, and the
      per-segment window bounds are widened by a scatter-min/max of the
      inserted keys' (slot - predict) residuals.  Both tables are
      stale-SOUND, so the f32 bound rounding here only moves the
      fallback rate, never correctness.

    Every state output is gated on ``ok`` (aborted graphs return the
    old arrays untouched).  Duplicate keys — in-batch, vs a slot key,
    or vs a chain key — abort, and the host replay raises the same
    ``KeyError`` the sequential path would.
    """
    B = x_hi.shape[0]
    m_pad = slot_hi.shape[0]
    O = link_offsets.shape[0]
    l_pad = link_hi.shape[0]
    k_pad = segk_hi.shape[0]
    iota = jnp.arange(B, dtype=jnp.int32)

    # ---- stage 1: placement primitives (shared per-key body) ----------
    if use_pallas:
        p, pv, ub, flags = ingest_place_call(
            x_hi, x_lo, segk_hi, segk_lo, slope_hi, slope_lo,
            icept_hi, icept_lo, slot_hi, slot_lo, link_offsets,
            link_hi, link_lo, key_tile=min(key_tile, B),
            n_slots=n_slots, interpret=resolve_interpret(interpret))
        free = (flags & 1) != 0
        bracket = (flags & 2) != 0
        escape = (flags & 4) != 0
    else:
        p, pv, ub, free, bracket, escape = ingest_place_body(
            x_hi, x_lo, segk_hi, segk_lo, slope_hi, slope_lo,
            icept_hi, icept_lo, slot_hi, slot_lo, link_offsets,
            link_hi, link_lo, n_slots=n_slots)
    p = p.astype(jnp.int32)
    pv = pv.astype(jnp.int32)
    ub = ub.astype(jnp.int32)
    valid = jnp.isfinite(x_hi)
    free &= valid
    bracket &= valid
    escape &= valid

    # ---- stage 2: batch key ranks + in-batch duplicate detection ------
    # all later key compares among batch keys become i32 rank compares
    # (exact for the distinct keys dup detection guarantees)
    xs_hi, xs_lo, perm = jax.lax.sort((x_hi, x_lo, iota), num_keys=2,
                                      is_stable=True)
    both_fin = jnp.isfinite(xs_hi[1:]) & jnp.isfinite(xs_hi[:-1])
    dup_batch = jnp.any(both_fin & _p_eq(xs_hi[:-1], xs_lo[:-1],
                                         xs_hi[1:], xs_lo[1:]))
    rank = jnp.zeros(B, jnp.int32).at[perm].set(iota)

    # ---- stage 3: closure-trivial partition + abort detection ---------
    # collision groups: any free key sharing a predicted slot with any
    # other batch key aborts (the host winner/loser machinery owns it)
    pa = jnp.where(valid, p, _I32MAX)
    ps_a, free_a = jax.lax.sort((pa, free.astype(jnp.int32)),
                                num_keys=1, is_stable=True)
    eq = (ps_a[1:] == ps_a[:-1]) & (ps_a[1:] != _I32MAX)
    isdup_s = jnp.concatenate([eq, jnp.zeros(1, bool)]) \
        | jnp.concatenate([jnp.zeros(1, bool), eq])
    grp_abort = jnp.any(isdup_s & (free_a > 0))

    cand = free & bracket
    hard = valid & ~cand

    # batch key == stored slot key -> the host raises KeyError
    ubc = jnp.clip(ub, 0, m_pad - 1)
    bdup_any = jnp.any(valid & (ub >= 0) & _p_eq(
        jnp.take(slot_hi, ubc), jnp.take(slot_lo, ubc), x_hi, x_lo))

    # leading-run displacement / contested (host rule D3 + class C)
    c_abort = jnp.any(hard & (ub < 0))

    # D1 (chain capture): a hard key chaining into a candidate's run
    # with a LARGER key would demote the candidate on the host
    runmax = jnp.full(n_slots + 1, -1, jnp.int32)
    runmax = runmax.at[jnp.where(hard, ub + 1, 0)].max(
        jnp.where(hard, rank, -1))
    d1_any = jnp.any(cand & (rank < jnp.take(
        runmax, jnp.clip(pv + 1, 0, n_slots))))

    # D4 (co-monotonicity): adjacent candidates of one run whose slot
    # order disagrees with key order demote on the host
    pc = jnp.where(cand, p, _I32MAX)
    ps_c, rk_c, pv_c = jax.lax.sort(
        (pc, rank, jnp.where(cand, pv, -2)), num_keys=1, is_stable=True)
    d4_any = jnp.any((ps_c[1:] != _I32MAX) & (ps_c[:-1] != _I32MAX)
                     & (pv_c[1:] == pv_c[:-1]) & (rk_c[1:] <= rk_c[:-1]))
    # (D2 cannot fire here: its occupier set is hard & free & bracket,
    # empty once collision groups are excluded — cand == free & bracket)

    # ---- stage 4: chain-arm counts + capacity checks ------------------
    cnt = jnp.zeros(O, jnp.int32).at[jnp.where(hard, ub + 1, 0)].add(
        jnp.where(hard, 1, 0))
    n_chain = jnp.sum(hard.astype(jnp.int32))
    n_slot = jnp.sum(cand.astype(jnp.int32))
    L_old = link_offsets[n_slots]
    ub1 = jnp.clip(ub + 1, 0, O - 1)
    old_len = jnp.take(link_offsets, ub1) \
        - jnp.take(link_offsets, jnp.clip(ub, 0, O - 1))
    chain_over = jnp.any(hard & (old_len + jnp.take(cnt, ub1) > max_chain))
    link_over = L_old + n_chain > l_pad

    # ---- stage 5: device CSR merge (the np.insert replacement) --------
    # chain entries sorted by key == sorted by (target, key): per-slot
    # chain key ranges ascend with the slot (global CSR invariant)
    ch_hi = jnp.where(hard, x_hi, jnp.inf)
    ch_lo = jnp.where(hard, x_lo, 0.0)
    sh, sl_, spl, sph, jflag = jax.lax.sort(
        (ch_hi, ch_lo, pay_lo, pay_hi, hard.astype(jnp.int32)),
        num_keys=2, is_stable=True)
    jmask = jflag > 0
    link_trips = int(max(l_pad, 2) - 1).bit_length() + 1
    pos = _bisect_pair(link_hi, link_lo, sh, sl_, link_trips,
                       strict=True) + 1
    posc = jnp.clip(pos, 0, l_pad - 1)
    edup_any = jnp.any(jmask & (pos < L_old) & _p_eq(
        jnp.take(link_hi, posc), jnp.take(link_lo, posc), sh, sl_))
    cj = jnp.cumsum(jmask.astype(jnp.int32)) - 1
    dst_new = jnp.where(jmask, pos + cj, l_pad)
    pos_eff = jnp.where(jmask, pos, l_pad + 1)  # sorted: jmask is a prefix
    old_i = jnp.arange(l_pad, dtype=jnp.int32)
    dst_old = old_i + jnp.searchsorted(pos_eff, old_i,
                                       side="right").astype(jnp.int32)
    new_lhi = jnp.full(l_pad, jnp.inf, jnp.float32) \
        .at[dst_old].set(link_hi, mode="drop") \
        .at[dst_new].set(sh, mode="drop")
    new_llo = jnp.zeros(l_pad, jnp.float32) \
        .at[dst_old].set(link_lo, mode="drop") \
        .at[dst_new].set(sl_, mode="drop")
    new_lpl = jnp.full(l_pad, -1, jnp.int32) \
        .at[dst_old].set(lpay_lo, mode="drop") \
        .at[dst_new].set(spl, mode="drop")
    new_lph = jnp.full(l_pad, -1, jnp.int32) \
        .at[dst_old].set(lpay_hi, mode="drop") \
        .at[dst_new].set(sph, mode="drop")
    new_off = link_offsets + jnp.cumsum(cnt)

    # ---- stage 6: slot arm — scatter + carried-key repair -------------
    nb_hi = jnp.concatenate([slot_hi[1:], jnp.full(1, jnp.inf,
                                                   jnp.float32)])
    nb_lo = jnp.concatenate([slot_lo[1:], jnp.zeros(1, jnp.float32)])
    occ_old = _p_lt(slot_hi, slot_lo, nb_hi, nb_lo)
    idx_c = jnp.where(cand, p, m_pad)
    occ_new = occ_old.at[idx_c].set(True, mode="drop")
    sc_hi = slot_hi.at[idx_c].set(x_hi, mode="drop")
    sc_lo = slot_lo.at[idx_c].set(x_lo, mode="drop")
    new_shi, new_slo = jax.lax.associative_scan(
        _p_min,
        (jnp.where(occ_new, sc_hi, jnp.inf),
         jnp.where(occ_new, sc_lo, 0.0)),
        reverse=True)
    new_pl = spay_lo.at[idx_c].set(pay_lo, mode="drop")
    new_ph = spay_hi.at[idx_c].set(pay_hi, mode="drop")

    # ---- stage 7: rank-row refresh against the NEW slot keys ----------
    r_size = rank_table.shape[0] - 1
    if key_wide:
        xb = (x_hi - rank_scale[0]) + (x_lo - rank_scale[1])
    else:
        xb = x_hi - rank_scale[0]
    b = jnp.clip(xb * rank_scale[2], 0.0,
                 float(r_size - 1)).astype(jnp.int32)
    rows = jnp.clip(jnp.concatenate([b - 1, b, b + 1]), 0, r_size)
    rows_ok = jnp.concatenate([valid] * 3) & (rows < r_size)
    slot_trips = int(max(m_pad, 2) - 1).bit_length() + 1
    vals = _bisect_pair(new_shi, new_slo,
                        jnp.take(rank_bounds_hi, rows),
                        jnp.take(rank_bounds_lo, rows),
                        slot_trips, strict=True) + 1
    new_rank = rank_table.at[jnp.where(rows_ok, rows, r_size + 1)].set(
        vals, mode="drop")

    # ---- stage 8: window-bound widening for the inserted keys ---------
    seg_trips = int(max(k_pad, 2) - 1).bit_length() + 1
    seg = jnp.clip(_bisect_pair(segk_hi, segk_lo, x_hi, x_lo, seg_trips,
                                strict=False), 0, k_pad - 1)
    y1 = jnp.take(slope_hi, seg) * (x_hi - jnp.take(segk_hi, seg)) \
        + jnp.take(icept_hi, seg)
    dlt = p.astype(jnp.float32) - y1
    segc = jnp.where(cand, seg, k_pad)
    new_elo = elo.at[segc].min(dlt - 1.0, mode="drop")
    new_ehi = ehi.at[segc].max(dlt + 1.0, mode="drop")

    # ---- abort gating -------------------------------------------------
    reasons = (jnp.any(escape).astype(jnp.int32)
               + 2 * dup_batch.astype(jnp.int32)
               + 4 * grp_abort.astype(jnp.int32)
               + 8 * bdup_any.astype(jnp.int32)
               + 16 * c_abort.astype(jnp.int32)
               + 32 * d1_any.astype(jnp.int32)
               + 64 * d4_any.astype(jnp.int32)
               + 128 * chain_over.astype(jnp.int32)
               + 256 * link_over.astype(jnp.int32)
               + 512 * edup_any.astype(jnp.int32))
    ok = reasons == 0
    gate = lambda new, old: jnp.where(ok, new, old)  # noqa: E731
    return (p, pv, ub, free, bracket, escape, ok, reasons,
            n_slot, n_chain, seg, dlt,
            gate(new_shi, slot_hi), gate(new_slo, slot_lo),
            gate(new_pl, spay_lo), gate(new_ph, spay_hi),
            gate(new_off, link_offsets),
            gate(new_lhi, link_hi), gate(new_llo, link_lo),
            gate(new_lpl, lpay_lo), gate(new_lph, lpay_hi),
            gate(new_rank, rank_table),
            gate(new_elo, elo), gate(new_ehi, ehi))


@functools.partial(
    jax.jit, static_argnames=("key_tile", "n_slots", "interpret"))
def ingest_place_call(
    x_hi, x_lo,            # (Bpad,) f32 pair, Bpad % key_tile == 0
    segk_hi, segk_lo,
    slope_hi, slope_lo,
    icept_hi, icept_lo,
    slot_hi, slot_lo,
    link_offsets,          # i32
    link_hi, link_lo,
    *,
    key_tile: int = 512,
    n_slots: int,
    interpret: bool = False,
):
    n = x_hi.shape[0]
    assert n % key_tile == 0
    grid = (n // key_tile,)
    kernel = functools.partial(_ingest_place_kernel, n_slots=n_slots)
    whole = lambda a: pl.BlockSpec(a.shape, lambda i: (0,))  # noqa: E731
    out32 = jax.ShapeDtypeStruct((n,), jnp.int32)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((key_tile,), lambda i: (i,)),
            pl.BlockSpec((key_tile,), lambda i: (i,)),
            whole(segk_hi), whole(segk_lo),
            whole(slope_hi), whole(slope_lo),
            whole(icept_hi), whole(icept_lo),
            whole(slot_hi), whole(slot_lo),
            whole(link_offsets), whole(link_hi), whole(link_lo),
        ],
        out_specs=[pl.BlockSpec((key_tile,), lambda i: (i,))] * 4,
        out_shape=[out32, out32, out32, out32],
        interpret=interpret,
    )(x_hi, x_lo, segk_hi, segk_lo, slope_hi, slope_lo, icept_hi,
      icept_lo, slot_hi, slot_lo, link_offsets, link_hi, link_lo)
