"""The round megakernel: ONE HBM sweep of the bin matrix per boosting round.

The fused windowed round (ops/treegrow_windowed.py::_round_fused) is one
*dispatch* but — before this kernel — still three XLA/Pallas passes over
the window's bins inside it: the window gather reads W columns of the
(F, N) bin matrix, materializes a (W, F) copy in HBM, and the histogram
pass re-reads that copy; the Pallas partition streams the segment rows a
third time.  PERF_NOTES' roofline says histogram build is MEMORY-bound —
HBM traffic on the bin matrix, not FLOPs, bounds round time at any N —
so those are three full window-sweeps where one suffices (ROADMAP "round
megakernel"; docs/PERF_NOTES.md round 16).

This module fuses them into a single Pallas kernel with an HBM-resident
grid (``pl.ANY`` refs throughout — the jaxlint R11 discipline; nothing
row- or bin-proportional is ever staged whole in VMEM):

* **partition phase** — the round-12 ``make_async_copy`` chunk-DMA move
  sweep of ops/partition_pallas.py, minus the count sweep (the fused
  round already computed per-segment left counts for its window
  verification, so they arrive as scalar-prefetch operands) and with the
  round-12 queued follow-up applied: interior chunks skip the READ half
  of the read-modify-write destination pair (their fixed-size write tail
  lands inside the run and is overwritten by the next chunk's window;
  only boundary chunks can clobber a neighbour and keep the RMW).
  Partition movements are written to the output order on the way out.
* **histogram phase** — per feature block, the small-child windows of the
  freshly written order are streamed through double-buffered VMEM
  buffers: each window row's bin COLUMN is DMA'd from the HBM-resident
  matrix exactly once (copy-in row i+1 while accumulating row i) and
  folded into a per-leaf VMEM accumulator carry.  No (W, F) copy ever
  exists in HBM: the bin matrix is read once, in place.
* **split-gain phase** (single-device) — while a feature block's child
  histograms are still VMEM-resident, the candidate gain planes are
  evaluated and reduced PER FEATURE on-core via the shared machinery in
  ops/split.py (gain_plane + reduce_plane_per_feature — the same code
  the XLA path runs, so parity is structural); only the O(tile x F)
  per-feature bests leave the kernel, and the O(F) cross-feature argmax
  (select_from_feature_best) finishes outside.  Under SPMD the kernel
  stops after the histogram phase: the leaf-histogram merge must stay
  the round's single in-dispatch collective (psum / psum_scatter,
  UNCHANGED), so sibling subtraction and split search run post-merge in
  XLA exactly as before.

Bitwise contract: the kernel's histogram accumulator is the SCATTER
formulation — per window chunk, a seeded ``.at[].add`` fold continued on
the same accumulator, which preserves the per-bucket addition chain of
the XLA round's full-window scatter (the round-12 OOC rule: chunked
accumulation must seed-and-continue the SAME chain, never tree-reduce).
tests/test_megakernel.py pins the megakernel round bitwise-equal to the
three-pass round across the equivalence matrix (float / int8-quantized /
categorical, interpret mode on CPU).

Validation status: the kernel is validated through Mosaic INTERPRET mode,
like partition_pallas v2 was.  ON THE CHIP IT DOES NOT COMPILE (PR 21, TPU
v5e, jax 0.9.0 / libtpu 0.0.34, 100k x 28, 31 leaves, 256 bins, leaf tile
8): Pallas's Mosaic lowering raises ``ValueError: Cannot store scalars to
VMEM`` at the partition phase's compaction store
(partition_pallas.emit_move_sweep, ``dbuf[0, 0, k] = obuf[slot, 0, i]``)
and stops there, so the scatter accumulate and the on-core gain reduction
(argsort in the categorical scan), which were the two pieces expected to
be refused, were not reached.  ``megakernel_mode``'s ``auto`` therefore
selects nothing (ops/treegrow_windowed.py); ``1`` still forces the kernel,
and on the chip that raises: this refusal's text names neither Mosaic nor
Pallas, so utils/degrade.py does not take it for a kernel failure.
ROADMAP.md Design item 1 decides between a redesign and deletion.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .hist_pallas import VMEM_ACC_BUDGET
from .partition_pallas import _CHUNK, emit_move_sweep
from .split import (FeatureBests, SplitParams, gain_plane,
                    reduce_plane_per_feature)


def megakernel_feature_block(num_bins: int, leaf_tile: int) -> int:
    """Feature-block width for the megakernel's VMEM carries, budgeted by
    the SAME constant the histogram kernels' leaf-tile policy uses
    (hist_pallas.VMEM_ACC_BUDGET — one policy, no duplicated numbers).
    Two (tile, 3, FB, B) f32 carries live at once (fresh accumulator +
    parent/staging block), so FB is sized for 2x."""
    bpad = max(num_bins, 8)
    per_f = 2 * leaf_tile * 3 * bpad * 4  # bytes per feature column
    fb = max(VMEM_ACC_BUDGET // max(per_f, 1), 8)
    return int(min(128, (fb // 8) * 8))


class _MKStatics(NamedTuple):
    """Trace-time geometry shared between the kernel body and the host
    wrapper (everything here is a Python int/bool at trace time)."""

    tile: int
    f: int
    num_bins: int
    fb: int  # feature-block width (megakernel_feature_block)
    fuse_tail: bool
    has_cat: bool
    has_contri: bool


def _mk_kernel(seg_start, seg_len, n_left, win_start, win_cnt, small_left,
               # ---- tensor operands (HBM unless noted) ----
               bins_hbm, order_hbm, go_hbm, pay_hbm, *rest,
               st: _MKStatics, params: SplitParams):
    """Single sequential grid step; phases ordered by data dependency
    (partition writes the order the histogram phase streams)."""
    T, F, B, FB = st.tile, st.f, st.num_bins, st.fb

    if st.fuse_tail:
        (parent_hbm, ptab, ftab_i, fcontri,
         out_order, left_out, right_out,
         fb_gain, fb_thr, fb_left, fb_var, fb_lg, fb_lh, fb_lc,
         obuf, gbuf, dbuf, wbuf, cbuf, pbuf, acc, pscr, sems) = rest
    else:
        (out_order, fresh_out,
         obuf, gbuf, dbuf, wbuf, cbuf, pbuf, acc, pscr, sems) = rest

    # ================= phase 1: segment partition (move sweep) =========
    # THE shared move sweep (partition_pallas.emit_move_sweep — one copy
    # of the cursor/boundary-RMW logic for both kernels), with the count
    # sweep replaced by the prefetched per-segment left counts.
    for s in range(T):
        emit_move_sweep(order_hbm, go_hbm, out_order, obuf, gbuf, dbuf,
                        sems, seg_start[s], seg_len[s], n_left[s])

    # ============ phase 2 (+3): window histograms, feature-block major ==
    # each window row's bin column is DMA'd from the HBM matrix ONCE;
    # the per-leaf accumulator is a VMEM carry across the whole window
    # sweep of one feature block.  Accumulation is the seeded scatter
    # fold (module docstring: bitwise contract with the XLA round).
    fb_blocks = [(lo, min(FB, F - lo)) for lo in range(0, F, FB)]
    for fb_lo, fbw in fb_blocks:
        acc[...] = jnp.zeros_like(acc)

        def bins_copy(row, i, fb_lo=fb_lo, fbw=fbw):
            return pltpu.make_async_copy(
                bins_hbm.at[pl.ds(fb_lo, fbw), pl.ds(row, 1)],
                cbuf.at[pl.ds(0, fbw), pl.ds(i, 1)], sems.at[jax.lax.rem(i, 2)])

        def pay_copy(row, i):
            return pltpu.make_async_copy(
                pay_hbm.at[:, pl.ds(row, 1)],
                pbuf.at[:, pl.ds(i, 1)], sems.at[2 + jax.lax.rem(i, 2)])

        for s in range(T):
            wst = win_start[s]
            wcnt = win_cnt[s]
            nc = pl.cdiv(wcnt, _CHUNK)

            def win_body(j, _, s=s, wst=wst, wcnt=wcnt, fb_lo=fb_lo,
                         fbw=fbw):
                # the window run is CONTIGUOUS in the partitioned order —
                # one chunk DMA; the fixed-size over-read past the window
                # tail is masked below (order_hbm-sized padding covers it)
                wc = pltpu.make_async_copy(
                    out_order.at[:, pl.ds(wst + j * _CHUNK, _CHUNK)],
                    wbuf, sems.at[4])
                wc.start()
                wc.wait()
                m = jnp.minimum(wcnt - j * _CHUNK, _CHUNK)
                pbuf[...] = jnp.zeros_like(pbuf)  # stale tails add exact 0

                # per-row column gather, double-buffered: start row i+1's
                # two DMAs while waiting on row i's (paged-attention
                # pattern: many small column DMAs, two in flight)
                @pl.when(m > 0)
                def _warm_row():
                    r0 = wbuf[0, 0]
                    bins_copy(r0, 0).start()
                    pay_copy(r0, 0).start()

                def row_body(i, _):
                    @pl.when(i + 1 < m)
                    def _prefetch():
                        rn = wbuf[0, i + 1]
                        bins_copy(rn, i + 1).start()
                        pay_copy(rn, i + 1).start()

                    ri = wbuf[0, i]
                    bins_copy(ri, i).wait()
                    pay_copy(ri, i).wait()
                    return 0

                jax.lax.fori_loop(0, m, row_body, 0)

                # seeded scatter fold of this chunk onto the carry —
                # identical per-bucket addition chain to the XLA round's
                # full-window scatter (histogram_scatter), restricted to
                # this slot's rows (zero-payload adds are exact no-ops)
                binv = jnp.clip(
                    cbuf[:, :].astype(jnp.int32).T[:, :fbw], 0, B - 1)
                g, h, mk = pbuf[0], pbuf[1], pbuf[2]
                payload = jnp.stack([g * mk, h * mk, mk])  # (3, _CHUNK)
                idx = binv + (jnp.arange(fbw, dtype=jnp.int32) * B)[None, :]
                a3 = acc[s].reshape(3, FB * B)[:, : fbw * B]
                a3 = a3.at[:, idx].add(payload[:, :, None])
                acc[s, :, : fbw, :] = a3.reshape(3, fbw, B)
                return 0

            jax.lax.fori_loop(0, nc, win_body, 0)

        if not st.fuse_tail:
            wr = pltpu.make_async_copy(
                acc.at[:, :, pl.ds(0, fbw), :],
                fresh_out.at[:, :, pl.ds(fb_lo, fbw), :], sems.at[5])
            wr.start()
            wr.wait()
            continue

        # ---- phase 3: sibling subtraction + on-core gain reduction ----
        # parent slot histograms for THIS feature block come in by DMA,
        # children are written back out, and the split-gain planes are
        # evaluated + reduced per feature while everything is VMEM-
        # resident (ops/split.py shared machinery; module docstring)
        prd = pltpu.make_async_copy(
            parent_hbm.at[:, :, pl.ds(fb_lo, fbw), :],
            pscr.at[:, :, pl.ds(0, fbw), :], sems.at[5])
        prd.start()
        prd.wait()
        fresh = acc[:, :, :fbw, :]
        parent = pscr[:, :, :fbw, :]
        big = parent - fresh
        sml = (small_left_vec(small_left, T) > 0)[:, None, None, None]
        left_h = jnp.where(sml, fresh, big)
        right_h = jnp.where(sml, big, fresh)
        acc[:, :, : fbw, :] = left_h
        wr = pltpu.make_async_copy(
            acc.at[:, :, pl.ds(0, fbw), :],
            left_out.at[:, :, pl.ds(fb_lo, fbw), :], sems.at[5])
        wr.start()
        wr.wait()
        acc[:, :, : fbw, :] = right_h
        wr = pltpu.make_async_copy(
            acc.at[:, :, pl.ds(0, fbw), :],
            right_out.at[:, :, pl.ds(fb_lo, fbw), :], sems.at[5])
        wr.start()
        wr.wait()

        cand = jnp.concatenate([left_h, right_h], axis=0)  # (2T, 3, fbw, B)
        nbpf_fb = ftab_i[0, fb_lo:fb_lo + fbw]
        mbpf_fb = ftab_i[1, fb_lo:fb_lo + fbw]
        fmask_fb = ftab_i[2, fb_lo:fb_lo + fbw] > 0
        cmask_fb = (ftab_i[3, fb_lo:fb_lo + fbw] > 0) if st.has_cat else None
        fc_fb = fcontri[0, fb_lo:fb_lo + fbw] if st.has_contri else None

        def cand_bests(hist_c, pg, ph, pc, dep, pout):
            gain, ctx = gain_plane(
                hist_c, pg, ph, pc, nbpf_fb, mbpf_fb, params,
                feature_mask=fmask_fb, categorical_mask=cmask_fb,
                depth=dep, parent_output=pout, feature_contri=fc_fb)
            return reduce_plane_per_feature(gain, ctx)

        out = jax.vmap(cand_bests)(
            cand, ptab[0], ptab[1], ptab[2], ptab[3], ptab[4])
        fb_gain[:, fb_lo:fb_lo + fbw] = out.gain
        fb_thr[:, fb_lo:fb_lo + fbw] = out.threshold_bin
        fb_left[:, fb_lo:fb_lo + fbw] = out.use_left.astype(jnp.int32)
        fb_var[:, fb_lo:fb_lo + fbw] = out.variant
        fb_lg[:, fb_lo:fb_lo + fbw] = out.left_g
        fb_lh[:, fb_lo:fb_lo + fbw] = out.left_h
        fb_lc[:, fb_lo:fb_lo + fbw] = out.left_c


def small_left_vec(small_left, tile: int):
    """Scalar-prefetch operands are SMEM scalars; rebuild the (T,) vector
    the tail's broadcast select needs."""
    return jnp.asarray([small_left[i] for i in range(tile)], jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("num_bins", "leaf_tile", "params", "fuse_tail",
                     "has_cat", "interpret"),
)
def round_megakernel(
    bins_t: jnp.ndarray,  # (F, N) int16 — HBM-resident, read ONCE
    order: jnp.ndarray,  # (N,) i32 — pre-round physical row order
    go_left: jnp.ndarray,  # (N,) bool per POSITION
    grad: jnp.ndarray,  # (N,) f32 by ROW id (dequantized under quant)
    hess: jnp.ndarray,
    row_mask: jnp.ndarray,  # (N,) bool by ROW id
    seg_start: jnp.ndarray,  # (T,) i32 split-segment geometry
    seg_len: jnp.ndarray,
    n_left: jnp.ndarray,  # (T,) i32 — per-segment left counts (precomputed)
    win_start: jnp.ndarray,  # (T,) i32 — small-child window geometry
    win_cnt: jnp.ndarray,
    small_left: jnp.ndarray,  # (T,) i32 — 1 when the left child is windowed
    parent_hists: Optional[jnp.ndarray] = None,  # (T, 3, F, B) fuse_tail
    cand_tab: Optional[jnp.ndarray] = None,  # (5, 2T) f32 fuse_tail
    num_bins_pf: Optional[jnp.ndarray] = None,
    missing_bin_pf: Optional[jnp.ndarray] = None,
    feature_mask: Optional[jnp.ndarray] = None,
    categorical_mask: Optional[jnp.ndarray] = None,
    feature_contri: Optional[jnp.ndarray] = None,
    *,
    num_bins: int,
    leaf_tile: int,
    params: SplitParams = SplitParams(),
    fuse_tail: bool = False,
    has_cat: bool = False,
    interpret: bool = False,
):
    """One round's partition + window histograms (+ on-core split-gain
    reduction when ``fuse_tail``) in a single Pallas call.

    Returns ``(raw_order, fresh_hists)`` without the tail (the caller
    merges raw_order over untouched positions and runs merge/subtraction/
    search as before — the sharded path), or ``(raw_order, left_hists,
    right_hists, FeatureBests)`` with it (the caller finishes with
    select_from_feature_best).  ``raw_order`` is defined INSIDE segments
    only, same contract as partition_pallas."""
    f, n = bins_t.shape
    T = leaf_tile
    FB = min(megakernel_feature_block(num_bins, leaf_tile), f)
    B = num_bins
    n_pad = (pl.cdiv(n, _CHUNK) + 1) * _CHUNK
    order_p = jnp.pad(order, (0, n_pad - n))[None]
    go_p = jnp.pad(go_left.astype(jnp.int32), (0, n_pad - n))[None]
    pay = jnp.stack([grad.astype(jnp.float32), hess.astype(jnp.float32),
                     row_mask.astype(jnp.float32)])  # (3, N)
    st = _MKStatics(tile=T, f=f, num_bins=B, fb=FB, fuse_tail=fuse_tail,
                    has_cat=has_cat, has_contri=feature_contri is not None)

    tensor_in = [bins_t, order_p, go_p, pay]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 4
    out_shape = [jax.ShapeDtypeStruct((1, n_pad), jnp.int32)]
    out_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    if fuse_tail:
        ftab_i = jnp.stack([
            jnp.asarray(num_bins_pf, jnp.int32),
            jnp.asarray(missing_bin_pf, jnp.int32),
            jnp.asarray(feature_mask, jnp.int32),
            (jnp.asarray(categorical_mask, jnp.int32) if has_cat
             else jnp.zeros((f,), jnp.int32)),
        ])  # (4, F)
        fc = (jnp.asarray(feature_contri, jnp.float32)[None]
              if feature_contri is not None
              else jnp.zeros((1, f), jnp.float32))
        tensor_in += [parent_hists, cand_tab, ftab_i, fc]
        in_specs += [
            pl.BlockSpec(memory_space=pl.ANY),  # parent hists: HBM, DMA
            # jaxlint: disable=R11 (O(tile) candidate scalars — a few hundred bytes, not row-proportional)
            pl.BlockSpec((5, 2 * T), lambda i, *_: (0, 0),
                         memory_space=pltpu.VMEM),
            # jaxlint: disable=R11 (O(F) per-feature int tables for the on-core gain scan — KBs, not row-proportional)
            pl.BlockSpec((4, f), lambda i, *_: (0, 0),
                         memory_space=pltpu.VMEM),
            # jaxlint: disable=R11 (O(F) feature_contri row — same table class as above)
            pl.BlockSpec((1, f), lambda i, *_: (0, 0),
                         memory_space=pltpu.VMEM),
        ]
        out_shape += [
            jax.ShapeDtypeStruct((T, 3, f, B), jnp.float32),  # left hists
            jax.ShapeDtypeStruct((T, 3, f, B), jnp.float32),  # right hists
            jax.ShapeDtypeStruct((2 * T, f), jnp.float32),  # per-F gain
            jax.ShapeDtypeStruct((2 * T, f), jnp.int32),  # threshold
            jax.ShapeDtypeStruct((2 * T, f), jnp.int32),  # use_left
            jax.ShapeDtypeStruct((2 * T, f), jnp.int32),  # variant
            jax.ShapeDtypeStruct((2 * T, f), jnp.float32),  # left_g
            jax.ShapeDtypeStruct((2 * T, f), jnp.float32),  # left_h
            jax.ShapeDtypeStruct((2 * T, f), jnp.float32),  # left_c
        ]
        out_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2 + [
            # jaxlint: disable=R11 (O(tile x F) REDUCED per-feature bests — the point of the on-core reduction; not row- or bin-proportional)
            pl.BlockSpec((2 * T, f), lambda i, *_: (0, 0),
                         memory_space=pltpu.VMEM)] * 7
    else:
        out_shape += [jax.ShapeDtypeStruct((T, 3, f, B), jnp.float32)]
        out_specs += [pl.BlockSpec(memory_space=pl.ANY)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(1,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, 1, _CHUNK), jnp.int32),  # order chunks (dbl-buf)
            pltpu.VMEM((2, 1, _CHUNK), jnp.int32),  # go chunks (dbl-buf)
            pltpu.VMEM((2, 1, _CHUNK), jnp.int32),  # left/right RMW windows
            pltpu.VMEM((1, _CHUNK), jnp.int32),  # window order values
            pltpu.VMEM((FB, _CHUNK), bins_t.dtype),  # gathered bin columns
            pltpu.VMEM((3, _CHUNK), jnp.float32),  # gathered payload columns
            # the two (tile, 3, FB, B) carries are the budgeted exception:
            # FB is sized from VMEM_ACC_BUDGET so together they stay under
            # the shared accumulator headroom, independent of N
            pltpu.VMEM((T, 3, FB, B), jnp.float32),  # fresh-hist carry
            pltpu.VMEM((T, 3, FB, B), jnp.float32),  # parent/staging block
            pltpu.SemaphoreType.DMA((6,)),
        ],
    )
    outs = pl.pallas_call(
        functools.partial(_mk_kernel, st=st, params=params),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(seg_start.astype(jnp.int32), seg_len.astype(jnp.int32),
      n_left.astype(jnp.int32), win_start.astype(jnp.int32),
      win_cnt.astype(jnp.int32), small_left.astype(jnp.int32),
      *tensor_in)

    raw_order = outs[0][0, :n]
    if not fuse_tail:
        return raw_order, outs[1]
    left_hists, right_hists = outs[1], outs[2]
    bests = FeatureBests(
        gain=outs[3], threshold_bin=outs[4], use_left=outs[5] > 0,
        variant=outs[6], left_g=outs[7], left_h=outs[8], left_c=outs[9])
    return raw_order, left_hists, right_hists, bests
