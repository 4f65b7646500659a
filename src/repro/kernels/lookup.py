"""Fused learned-index lookup — Pallas TPU kernel.

The paper's query hot path is ``predict(q) -> bounded search around the
prediction``.  On GPU/CPU that is a pointer-chasing binary search; the
TPU-native re-think (DESIGN.md §2) recasts it as:

  1. **Tile scheduling (host/XLA)**: queries are sorted; for each tile of
     ``q_tile`` queries the mechanism's prediction + error bound gives a
     slot window; the tile's window start (quantized to ``w_tile`` blocks)
     is passed via *scalar prefetch* so the BlockSpec index_map DMAs
     exactly the two adjacent ``w_tile`` blocks of the slot-key array that
     cover the tile's window from HBM into VMEM.
  2. **In-kernel (VMEM, branchless)**: segment routing and the linear
     prediction are recomputed fused (segment tables live in VMEM), and
     the bounded "search" is a *rank computation*: counting
     ``slot_key <= q`` over the 2·w_tile VMEM window with chunked masked
     reductions — no per-lane gather, pure VPU compare+reduce.
  3. Queries whose true bracket falls outside the tile window raise a
     fallback flag and are re-resolved by the jnp oracle path outside
     (rare by construction; measured in tests/benchmarks).

Memory/roofline: the kernel reads each needed slot-key block exactly once
per tile (2·w_tile·4 B), the segment tables once per tile (VMEM-resident),
and is memory-bound by design — arithmetic intensity ≈ (comparisons per
byte) — matching the §Roofline treatment of index lookup as a memory-term
workload.

VMEM budget per grid step (defaults q_tile=256, w_tile=2048, K<=8192):
  window 2*2048*4 = 16 KiB, segments 4*8192*4 = 128 KiB,
  queries/outputs < 8 KiB, compare chunk 256*512*4 = 512 KiB  << 16 MiB.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Pallas interpret mode for a kernel entry point: an explicit
    request wins; otherwise interpret only off the TPU, so no kernel
    runs in Python on a TPU unless a caller asks for it."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)

__all__ = ["lookup_kernel_call", "fused_lookup_call"]


def _lookup_kernel(
    tile_block_ref,  # scalar-prefetch: (num_tiles,) int32 block index
    q_ref,           # (q_tile,) f32 queries (sorted, padded with +inf)
    segk_ref,        # (K,) f32 segment first keys (padded with +inf)
    slope_ref,       # (K,) f32
    icept_ref,       # (K,) f32
    win_a_ref,       # (w_tile,) f32 slot keys, block b
    win_b_ref,       # (w_tile,) f32 slot keys, block b+1
    slot_ref,        # out (q_tile,) i32 absolute predecessor slot
    found_ref,       # out (q_tile,) i32 1 if slot_key[slot] == q
    fb_ref,          # out (q_tile,) i32 1 if fallback needed
    pred_ref,        # out (q_tile,) f32 fused in-kernel prediction y_hat
    *,
    w_tile: int,
    seg_chunk: int,
    win_chunk: int,
):
    i = pl.program_id(0)
    q = q_ref[:]
    q_tile = q.shape[0]
    k_pad = segk_ref.shape[0]

    # ---- segment routing: rank of q among segment first keys ----------
    # chunked masked count, no gather:  seg = sum(segk <= q) - 1
    def seg_count(c, acc):
        ks = segk_ref[pl.ds(c * seg_chunk, seg_chunk)]
        return acc + jnp.sum(
            (ks[None, :] <= q[:, None]).astype(jnp.int32), axis=1
        )

    n_seg_chunks = k_pad // seg_chunk
    seg_cnt = jax.lax.fori_loop(
        0, n_seg_chunks, seg_count, jnp.zeros((q_tile,), jnp.int32)
    )
    seg = jnp.clip(seg_cnt - 1, 0, k_pad - 1)

    # per-query segment parameters (small VMEM gathers over the K tables)
    fk = jnp.take(segk_ref[:], seg)
    sl = jnp.take(slope_ref[:], seg)
    ic = jnp.take(icept_ref[:], seg)
    y_hat = sl * (q - fk) + ic  # fused in-kernel prediction

    # ---- bounded search: rank of q within the 2*w_tile VMEM window ----
    base = tile_block_ref[i] * w_tile  # absolute element offset of win_a

    def win_count(c, acc):
        off = c * win_chunk
        in_a = off < w_tile
        # static: win_chunk divides w_tile, so a chunk never straddles
        ks = jax.lax.cond(
            in_a,
            lambda: win_a_ref[pl.ds(off % w_tile, win_chunk)],
            lambda: win_b_ref[pl.ds(off % w_tile, win_chunk)],
        )
        le = acc[0] + jnp.sum((ks[None, :] <= q[:, None]).astype(jnp.int32), axis=1)
        eq = acc[1] + jnp.sum((ks[None, :] == q[:, None]).astype(jnp.int32), axis=1)
        return (le, eq)

    n_win_chunks = (2 * w_tile) // win_chunk
    zero = jnp.zeros((q_tile,), jnp.int32)
    rank, eq_cnt = jax.lax.fori_loop(0, n_win_chunks, win_count, (zero, zero))

    slot_ref[:] = base + rank - 1
    found_ref[:] = (eq_cnt > 0).astype(jnp.int32)
    # fallback: true bracket may lie outside the window
    fb_lo = (rank == 0) & (base > 0)
    fb_hi = rank == 2 * w_tile
    fb_ref[:] = (fb_lo | fb_hi).astype(jnp.int32)
    pred_ref[:] = y_hat


@functools.partial(
    jax.jit,
    static_argnames=("q_tile", "w_tile", "seg_chunk", "win_chunk", "interpret"),
)
def lookup_kernel_call(
    queries_sorted,   # (Qpad,) f32, sorted ascending, padded with +inf
    tile_block,       # (Qpad // q_tile,) i32 — window block index per tile
    seg_first_key,    # (Kpad,) f32, padded with +inf
    seg_slope,        # (Kpad,) f32
    seg_icept,        # (Kpad,) f32
    slot_key_padded,  # (Mpad,) f32, padded with +inf, Mpad % w_tile == 0
    *,
    q_tile: int = 256,
    w_tile: int = 2048,
    seg_chunk: int = 512,
    win_chunk: int = 512,
    interpret: bool = False,
):
    """Invoke the fused lookup kernel.  See ops.py for the full pipeline."""
    n_q = queries_sorted.shape[0]
    assert n_q % q_tile == 0, "pad queries to a multiple of q_tile"
    assert slot_key_padded.shape[0] % w_tile == 0
    assert w_tile % win_chunk == 0 and (2 * w_tile) % win_chunk == 0
    assert seg_first_key.shape[0] % seg_chunk == 0
    num_tiles = n_q // q_tile

    kernel = functools.partial(
        _lookup_kernel, w_tile=w_tile, seg_chunk=seg_chunk, win_chunk=win_chunk
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((q_tile,), lambda i, tb: (i,)),
            pl.BlockSpec(seg_first_key.shape, lambda i, tb: (0,)),
            pl.BlockSpec(seg_slope.shape, lambda i, tb: (0,)),
            pl.BlockSpec(seg_icept.shape, lambda i, tb: (0,)),
            pl.BlockSpec((w_tile,), lambda i, tb: (tb[i],)),
            pl.BlockSpec((w_tile,), lambda i, tb: (tb[i] + 1,)),
        ],
        out_specs=[
            pl.BlockSpec((q_tile,), lambda i, tb: (i,)),
            pl.BlockSpec((q_tile,), lambda i, tb: (i,)),
            pl.BlockSpec((q_tile,), lambda i, tb: (i,)),
            pl.BlockSpec((q_tile,), lambda i, tb: (i,)),
        ],
    )
    slot, found, fb, pred = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_q,), jnp.int32),
            jax.ShapeDtypeStruct((n_q,), jnp.int32),
            jax.ShapeDtypeStruct((n_q,), jnp.int32),
            jax.ShapeDtypeStruct((n_q,), jnp.float32),
        ],
        interpret=interpret,
    )(tile_block, queries_sorted, seg_first_key, seg_slope, seg_icept,
      slot_key_padded, slot_key_padded)
    return slot, found.astype(bool), fb.astype(bool), pred


# ---------------------------------------------------------------------------
# fused single-dispatch kernel
# ---------------------------------------------------------------------------
#
# One pallas_call per batch that carries a query from raw key to payload:
#
#   1. approximate radix segment routing — ONE multiply + one gather into
#      a VMEM-resident 2^14 bucket table (replacing the legacy kernel's
#      chunked K-table count loop); mis-routes near bucket boundaries
#      only shift the predicted window, which the escape flags catch;
#   2. window-bounded search over the two scalar-prefetch-scheduled
#      w_tile VMEM blocks: a per-query flat gather of ``flat_w + 1`` keys
#      around the prediction (the upper escape probe rides the same
#      gather), or — for wide-window indexes (flat_w == 0) — the chunked
#      masked rank count over the full 2*w_tile window;
#   3. fused epilogue in the same kernel: slot->payload gather from the
#      payload window blocks plus the ceil(log2(max_chain + 1))-trip CSR
#      chain bisect over the VMEM-resident link tables (link tables ride
#      whole, so this kernel only fits indexes whose link tables fit the
#      VMEM budget — nothing routes around that; the engine serves the
#      fused XLA graph unless this kernel is requested explicitly);
#   4. in-kernel fallback flagging AND per-tile compaction: escaped
#      queries are compacted into a per-tile local index list + count via
#      branchless (q_tile, q_tile) prefix-count/one-hot matrices (VPU/MXU
#      friendly — no cumsum, no scatter), so the host-side correction
#      only stitches tile lists into one fixed-capacity buffer.
#
# Every key compare has an f32 hi/lo pair variant (``key_wide``) —
# lexicographic pair order == numeric order — which is what finally gives
# >2^24 keys a device path on this kernel (the legacy kernel above is
# narrow-only).  64-bit payloads ride an i32 hi/lo pair (``wide``).
#
# TPU caveat: the flat mode leans on per-lane VMEM gathers (jnp.take on
# VMEM-resident arrays, the same idiom the legacy kernel uses for its
# segment tables); if a target's Mosaic lowering handles them poorly,
# schedule with flat_w=0 — the rank-count mode is pure compare+reduce.


def _fused_kernel(tile_block_ref, *args, w_tile, win_chunk, flat_w,
                  max_chain, n_slots, key_wide, wide, has_links):
    n_out = 7 if wide else 6
    ins, outs = args[:-n_out], args[-n_out:]
    it = iter(ins)
    q_ref = next(it)
    ql_ref = next(it) if key_wide else None
    if flat_w:
        rt_ref = next(it)
        rv_ref = next(it)
        segk_ref = next(it)
        segkl_ref = next(it) if key_wide else None
        slope_ref = next(it)
        iclo_ref = next(it)
    win_a = next(it)
    win_b = next(it)
    if key_wide:
        wlo_a = next(it)
        wlo_b = next(it)
    pay_a = next(it)
    pay_b = next(it)
    if wide:
        ph_a = next(it)
        ph_b = next(it)
    if has_links:
        off_a = next(it)
        off_b = next(it)
        off_c = next(it)
        lk_ref = next(it)
        lkl_ref = next(it) if key_wide else None
        lp_ref = next(it)
        lph_ref = next(it) if wide else None
    if wide:
        (slot_ref, res_ref, out_ref, outhi_ref, fb_ref, fbloc_ref,
         fbcnt_ref) = outs
    else:
        slot_ref, res_ref, out_ref, fb_ref, fbloc_ref, fbcnt_ref = outs
        outhi_ref = None

    i = pl.program_id(0)
    q = q_ref[:]
    qt = q.shape[0]
    ql = ql_ref[:] if key_wide else None
    base = tile_block_ref[i] * w_tile
    two_w = 2 * w_tile
    finite = jnp.isfinite(q)

    def wgather(a_ref, b_ref, idx):
        """Gather from the two adjacent VMEM window blocks (local idx,
        pre-clipped to [0, 2*w_tile))."""
        ia = jnp.clip(idx, 0, w_tile - 1)
        ib = jnp.clip(idx - w_tile, 0, w_tile - 1)
        return jnp.where(idx < w_tile, jnp.take(a_ref[:], ia),
                         jnp.take(b_ref[:], ib))

    # ---- search: per-query flat window or full-window rank count ------
    if flat_w:
        rv = rv_ref[:]
        r_size = rt_ref.shape[0]
        x = q - rv[0]
        if key_wide:
            x = x + (ql - rv[1])
        bkt = jnp.clip(x * rv[2], 0.0, float(r_size - 1)).astype(jnp.int32)
        seg = jnp.take(rt_ref[:], bkt)
        dx = q - jnp.take(segk_ref[:], seg)
        if key_wide:
            dx = dx + (ql - jnp.take(segkl_ref[:], seg))
        # approximate window BASE by design: fma contraction only shifts
        # lo0 by <=1 slot and the rank==0/rank==W escape flags re-resolve
        # any window miss, so exactness never depends on this product
        lo0 = jnp.clip(
            # repro-lint: disable=pair-raw-fma -- window base is approximate by contract; escapes re-resolve
            jnp.floor(jnp.take(slope_ref[:], seg) * dx
                      + jnp.take(iclo_ref[:], seg)),
            0.0, float(n_slots - 1)).astype(jnp.int32)
        loc0 = lo0 - base
        offs = jax.lax.broadcasted_iota(jnp.int32, (qt, flat_w + 1), 1)
        idxl = loc0[:, None] + offs
        inb = (idxl >= 0) & (idxl < two_w)
        idxc = jnp.clip(idxl, 0, two_w - 1)
        ks = wgather(win_a, win_b, idxc)
        if key_wide:
            ksl = wgather(wlo_a, wlo_b, idxc)
            le = ((ks < q[:, None])
                  | ((ks == q[:, None]) & (ksl <= ql[:, None]))) & inb
        else:
            le = (ks <= q[:, None]) & inb
        rank = jnp.sum(le.astype(jnp.int32), axis=1)
        slot = lo0 - 1 + jnp.minimum(rank, flat_w)
        window_ok = (loc0 >= 0) & (loc0 + flat_w + 1 <= two_w)
        fb = (((rank == 0) & (lo0 > 0)) | (rank == flat_w + 1)
              | ~window_ok)
    else:
        def win_count(c, acc):
            off = c * win_chunk
            in_a = off < w_tile
            ks = jax.lax.cond(
                in_a,
                lambda: win_a[pl.ds(off % w_tile, win_chunk)],
                lambda: win_b[pl.ds(off % w_tile, win_chunk)],
            )
            if key_wide:
                ksl = jax.lax.cond(
                    in_a,
                    lambda: wlo_a[pl.ds(off % w_tile, win_chunk)],
                    lambda: wlo_b[pl.ds(off % w_tile, win_chunk)],
                )
                le = ((ks[None, :] < q[:, None])
                      | ((ks[None, :] == q[:, None])
                         & (ksl[None, :] <= ql[:, None])))
            else:
                le = ks[None, :] <= q[:, None]
            return acc + jnp.sum(le.astype(jnp.int32), axis=1)

        rank = jax.lax.fori_loop(0, two_w // win_chunk, win_count,
                                 jnp.zeros((qt,), jnp.int32))
        slot = base + rank - 1
        fb = ((rank == 0) & (base > 0)) | (rank == two_w)
    fb = fb & finite

    # ---- fused epilogue: found + payload + CSR chain bisect -----------
    sloc = slot - base
    okx = (slot >= 0) & (sloc >= 0) & (sloc < two_w)
    slc = jnp.clip(sloc, 0, two_w - 1)
    found = okx & (wgather(win_a, win_b, slc) == q)
    if key_wide:
        found = found & (wgather(wlo_a, wlo_b, slc) == ql)
    out = jnp.where(found, wgather(pay_a, pay_b, slc), jnp.int32(-1))
    if wide:
        out_hi = jnp.where(found, wgather(ph_a, ph_b, slc), jnp.int32(-1))
    resolved = found
    if has_links:
        def ogather(idx):
            """CSR offsets live one element past the window (slot + 1
            can be base + 2*w_tile) — three offset blocks cover it."""
            ia = jnp.clip(idx, 0, w_tile - 1)
            ib = jnp.clip(idx - w_tile, 0, w_tile - 1)
            ic = jnp.clip(idx - two_w, 0, w_tile - 1)
            return jnp.where(
                idx < w_tile, jnp.take(off_a[:], ia),
                jnp.where(idx < two_w, jnp.take(off_b[:], ib),
                          jnp.take(off_c[:], ic)))

        start = ogather(slc)
        end = ogather(slc + 1)
        scan = okx & ~found & (end > start)
        lk = lk_ref[:]
        lkl = lkl_ref[:] if key_wide else None
        l_max = lk.shape[0] - 1
        trips = int(max_chain).bit_length()

        def chain_body(_, carry):
            lo, hi = carry
            upd = lo < hi
            mid = (lo + hi + 1) >> 1
            midc = jnp.clip(mid, 0, l_max)
            kh = jnp.take(lk, midc)
            if key_wide:
                go = (kh < q) | ((kh == q) & (jnp.take(lkl, midc) <= ql))
            else:
                go = kh <= q
            lo = jnp.where(upd & go, mid, lo)
            hi = jnp.where(upd, jnp.where(go, hi, mid - 1), hi)
            return lo, hi

        lo_c, _ = jax.lax.fori_loop(0, trips, chain_body,
                                    (start - 1, end - 1))
        locc = jnp.clip(lo_c, 0, l_max)
        eq = jnp.take(lk, locc) == q
        if key_wide:
            eq = eq & (jnp.take(lkl, locc) == ql)
        hit = scan & (lo_c >= start) & eq
        out = jnp.where(hit, jnp.take(lp_ref[:], locc), out)
        if wide:
            out_hi = jnp.where(hit, jnp.take(lph_ref[:], locc), out_hi)
        resolved = resolved | hit

    # ---- in-kernel per-tile fallback compaction -----------------------
    # branchless prefix-count + one-hot place: pos[i] = rank of query i
    # among the tile's flagged queries; fbloc[d] = local index of the
    # d-th flagged query (q_tile when d >= count)
    ii = jax.lax.broadcasted_iota(jnp.int32, (qt, qt), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (qt, qt), 1)
    fbm = fb[None, :]
    pos = jnp.sum(((jj <= ii) & fbm).astype(jnp.int32), axis=1) - 1
    oh = (pos[None, :] == ii) & fbm
    any_d = jnp.sum(oh.astype(jnp.int32), axis=1) > 0
    fbloc = (jnp.sum(jnp.where(oh, jj, 0), axis=1)
             + jnp.where(any_d, 0, qt))

    slot_ref[:] = slot
    res_ref[:] = resolved.astype(jnp.int32)
    out_ref[:] = out
    if wide:
        outhi_ref[:] = out_hi
    fb_ref[:] = fb.astype(jnp.int32)
    fbloc_ref[:] = fbloc
    fbcnt_ref[0] = jnp.sum(fb.astype(jnp.int32))


def fused_lookup_call(
    queries_sorted,    # (Qpad,) f32 hi, sorted ascending, +inf padded
    queries_lo,        # (Qpad,) f32 lo when key_wide else (0,)
    tile_block,        # (Qpad // q_tile,) i32 window block per tile
    radix_table,       # (R,) i32 bucket -> segment (flat mode)
    radix_scale,       # (3,) f32 [kmin_hi, kmin_lo, scale]
    seg_first_key,     # (Kpad,) f32, +inf padded
    seg_first_key_lo,  # (Kpad,) f32 when key_wide else (0,)
    seg_slope,         # (Kpad,) f32
    icept_lo_fold,     # (Kpad,) f32 — icept + err_lo - 1 pre-folded
    slot_key_padded,   # (Mpad,) f32, Mpad % w_tile == 0
    slot_key_lo,       # (Mpad,) f32 when key_wide else (0,)
    payload,           # (Mpad,) i32
    payload_hi,        # (Mpad,) i32 when wide else (0,)
    link_offsets,      # (Mpad + w_tile,) i32
    link_keys,         # (Lpad,) f32
    link_keys_lo,      # (Lpad,) f32 when key_wide else (0,)
    link_payloads,     # (Lpad,) i32
    link_payload_hi,   # (Lpad,) i32 when wide else (0,)
    *,
    q_tile: int,
    w_tile: int,
    win_chunk: int,
    flat_w: int,
    max_chain: int,
    n_slots: int,
    key_wide: bool,
    wide: bool,
    interpret: bool = False,
):
    """Invoke the fused single-dispatch kernel (see ops.py for the full
    pipeline; the sort, tile schedule, and escape correction live there).

    Returns ``(slot, resolved_i32, out_lo, out_hi, fb_bool, fb_loc,
    fb_cnt)`` — ``out_hi`` is zero-length when ``wide`` is False;
    ``fb_loc``/``fb_cnt`` are the per-tile compacted escape lists.
    """
    n_q = queries_sorted.shape[0]
    assert n_q % q_tile == 0, "pad queries to a multiple of q_tile"
    m_pad = slot_key_padded.shape[0]
    assert m_pad % w_tile == 0
    assert w_tile % win_chunk == 0
    num_tiles = n_q // q_tile
    has_links = int(link_keys.shape[0]) > 0 and max_chain > 0

    def tile_spec():
        return pl.BlockSpec((q_tile,), lambda i, tb: (i,))

    def full_spec(shape):
        return pl.BlockSpec(shape, lambda i, tb: (0,))

    def win_spec(off):
        return pl.BlockSpec((w_tile,),
                            lambda i, tb, _o=off: (tb[i] + _o,))

    in_specs = [tile_spec()]
    operands = [queries_sorted]
    if key_wide:
        in_specs.append(tile_spec())
        operands.append(queries_lo)
    if flat_w:
        in_specs += [full_spec(radix_table.shape),
                     full_spec(radix_scale.shape),
                     full_spec(seg_first_key.shape)]
        operands += [radix_table, radix_scale, seg_first_key]
        if key_wide:
            in_specs.append(full_spec(seg_first_key_lo.shape))
            operands.append(seg_first_key_lo)
        in_specs += [full_spec(seg_slope.shape),
                     full_spec(icept_lo_fold.shape)]
        operands += [seg_slope, icept_lo_fold]
    in_specs += [win_spec(0), win_spec(1)]
    operands += [slot_key_padded, slot_key_padded]
    if key_wide:
        in_specs += [win_spec(0), win_spec(1)]
        operands += [slot_key_lo, slot_key_lo]
    in_specs += [win_spec(0), win_spec(1)]
    operands += [payload, payload]
    if wide:
        in_specs += [win_spec(0), win_spec(1)]
        operands += [payload_hi, payload_hi]
    if has_links:
        in_specs += [win_spec(0), win_spec(1), win_spec(2)]
        operands += [link_offsets, link_offsets, link_offsets]
        in_specs.append(full_spec(link_keys.shape))
        operands.append(link_keys)
        if key_wide:
            in_specs.append(full_spec(link_keys_lo.shape))
            operands.append(link_keys_lo)
        in_specs.append(full_spec(link_payloads.shape))
        operands.append(link_payloads)
        if wide:
            in_specs.append(full_spec(link_payload_hi.shape))
            operands.append(link_payload_hi)

    n_vec_out = 6 if wide else 5  # slot, res, out, [out_hi], fb, fb_loc
    out_specs = [tile_spec() for _ in range(n_vec_out)]
    out_specs.append(pl.BlockSpec((1,), lambda i, tb: (i,)))
    out_shape = [jax.ShapeDtypeStruct((n_q,), jnp.int32)
                 for _ in range(n_vec_out)]
    out_shape.append(jax.ShapeDtypeStruct((num_tiles,), jnp.int32))

    kernel = functools.partial(
        _fused_kernel, w_tile=w_tile, win_chunk=win_chunk, flat_w=flat_w,
        max_chain=max_chain, n_slots=n_slots, key_wide=key_wide,
        wide=wide, has_links=has_links)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_tiles,),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    outs = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret,
    )(tile_block, *operands)
    if wide:
        slot, res, out, out_hi, fb, fb_loc, fb_cnt = outs
    else:
        slot, res, out, fb, fb_loc, fb_cnt = outs
        out_hi = jnp.zeros((0,), jnp.int32)
    return slot, res, out, out_hi, fb.astype(bool), fb_loc, fb_cnt
