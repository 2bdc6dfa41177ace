"""Pallas TPU kernel for the leaf-ordered row partition — v2, HBM-resident.

The XLA implementation (ops/partition.py::stable_partition_ranges) is
exact but pays O(N) regardless of how few rows a round actually splits:
two full-N cumsums plus a full-N permutation scatter measured ~41 ms per
1M-row round on a v5e — pure fixed cost from the windowed grower's admit
phase (docs/NEXT.md round-6 lever 1).  A round only *moves* the rows
inside its split segments (the parents of this round's splits, at most
2x the round's window), so the data movement should be window-
proportional, like the reference's in-place ``DataPartition::Split``
(src/treelearner/data_partition.hpp) which touches only the split leaf's
``[start, count)`` index range.

v1 (rounds 7-11) was that in-place split but staged ``order``/``go``/
``out`` as whole-array VMEM blocks (~12 B/row across the three buffers):
compute was segment-proportional, STAGING was O(N), and the scoped-VMEM
budget capped the kernel at ``_MAX_VMEM_ROWS = 650_000`` rows with a
silent XLA fallback above — exactly the regime the Higgs-11M target
lives in (ROADMAP "Uncap N").  v2 removes the cap:

* ``order``/``go_left``/``out`` live in HBM (``pl.ANY`` refs — no
  BlockSpec staging at all); the kernel streams fixed-size chunks
  through a small double-buffered VMEM scratch via
  ``pltpu.make_async_copy`` DMA, starting chunk c+1's copy-in while
  chunk c is being placed.  VMEM residency is O(_CHUNK), independent
  of N — the jaxlint R11 ``whole-array-vmem-staging`` fix pattern.
* grid ``(S,)`` — one sequential grid step per segment.  Per segment:
  a COUNT sweep (vector masked sums of streamed ``go`` chunks ->
  ``n_left``), then a MOVE sweep placing each input chunk's rows into
  the segment's left run ``[start, start+n_left)`` and right run
  ``[start+n_left, start+len)``.
* the move sweep compacts each chunk's left/right rows into VMEM
  staging buffers (scalar stores — the same SREG-bound ceiling as v1's
  move loop) and writes each run back with a read-modify-write DMA
  pair: the destination window is copied in, overlaid from its cursor,
  and copied back, so the fixed-size DMA's tail can never clobber
  neighbouring data (runs are cursor-contiguous; RMW makes the
  overhang idempotent).  Round 16: INTERIOR chunks — whose fixed-size
  destination window provably stays inside the final run — skip the
  read half (their transient write tail is rewritten by the next
  chunk's window before any read); only boundary chunks, which can
  reach a neighbouring run/segment, keep the pair.  HBM traffic on the
  bulk of a big segment drops to ~2 reads + 2 writes per chunk —
  segment-proportional, never O(N).
* positions outside every segment are untouched in the raw output —
  the caller merges them back with the ``seg_id`` mask it already has
  (ops/partition.py does), same contract as v1.

With staging gone the dispatcher no longer needs a row cap:
``partition_rows`` takes this kernel at ANY N (the 650k fallback is
deleted).

Validation status: equivalence vs ``stable_partition_ranges`` is pinned in
``tests/test_partition.py`` through Mosaic INTERPRET mode, including a
slow-marked >650k-row case that v1 could not reach.  ON THE CHIP THE
KERNEL DOES NOT COMPILE (PR 21, TPU v5e, jax 0.9.0 / libtpu 0.0.34, 1M
rows x 8 segments): Pallas's Mosaic lowering raises ``ValueError: Cannot
store scalars to VMEM`` at ``lc_ref[0, s] = n_left`` and, in the move
sweep, at the compaction stores ``dbuf[0, 0, k] = obuf[slot, 0, i]``.  The
growers therefore no longer select it (``treegrow_windowed.
PALLAS_PARTITION``); ROADMAP.md Design item 1 decides between a repair
(compaction through SMEM, or a vector formulation) and deletion.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_CHUNK = 512  # rows per DMA chunk; VPU-wide for the count phase, and the
# move phase's compaction loop stays short enough per chunk


def emit_move_sweep(order_hbm, go_hbm, out_hbm, obuf, gbuf, dbuf, sems,
                    start, seg_len, n_left):
    """One segment's MOVE sweep: stream order+go chunks (double-buffered),
    compact into the left/right runs, write back with boundary-RMW.

    THE shared routine between :func:`_partition_kernel` (which computes
    ``n_left`` with its count sweep first) and the round megakernel's
    partition phase (ops/round_pallas.py, where ``n_left`` arrives as a
    prefetched scalar) — one copy of the cursor/RMW logic, so a boundary
    fix or DMA tuning can never drift between the two kernels.  Expects
    the partition semaphore layout: ``sems[0:2]`` order chunks,
    ``sems[2:4]`` go chunks, ``sems[4]`` left run, ``sems[5]`` right run.
    """
    nc = pl.cdiv(seg_len, _CHUNK)

    def go_copy(c, slot):
        return pltpu.make_async_copy(
            go_hbm.at[:, pl.ds(start + c * _CHUNK, _CHUNK)],
            gbuf.at[slot], sems.at[2 + slot])

    def order_copy(c, slot):
        return pltpu.make_async_copy(
            order_hbm.at[:, pl.ds(start + c * _CHUNK, _CHUNK)],
            obuf.at[slot], sems.at[slot])

    @pl.when(nc > 0)
    def _warm_move():
        order_copy(0, 0).start()
        go_copy(0, 0).start()

    def move_body(c, cur):
        lcur, rcur = cur
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < nc)
        def _prefetch():
            order_copy(c + 1, 1 - slot).start()
            go_copy(c + 1, 1 - slot).start()

        order_copy(c, slot).wait()
        go_copy(c, slot).wait()
        m = jnp.minimum(seg_len - c * _CHUNK, _CHUNK)

        # left run RMW: read the destination window, overlay this chunk's
        # left rows from the cursor, write back (the tail past the overlay
        # is restored bit-for-bit, so the fixed-size DMA cannot clobber
        # the right run or a neighbouring segment).  INTERIOR chunks —
        # whose whole fixed-size window stays inside the final left run —
        # skip the read half (the round-12 queued follow-up): their write
        # tail is transient garbage that the NEXT chunk's window (which
        # starts exactly at this chunk's cursor frontier) fully rewrites
        # before anything reads it; only a window that can escape the run
        # (the boundary chunk) keeps the RMW pair.  Halves the serialized
        # DMA chain on the bulk of a big segment's chunks.
        @pl.when(lcur + _CHUNK > n_left)
        def _left_rd():
            left_rd = pltpu.make_async_copy(
                out_hbm.at[:, pl.ds(start + lcur, _CHUNK)], dbuf.at[0],
                sems.at[4])
            left_rd.start()
            left_rd.wait()

        def place_left(i, k):
            g = gbuf[slot, 0, i]

            @pl.when(g > 0)
            def _():
                dbuf[0, 0, k] = obuf[slot, 0, i]

            return k + g

        m_left = jax.lax.fori_loop(0, m, place_left, jnp.int32(0))
        left_wr = pltpu.make_async_copy(
            dbuf.at[0], out_hbm.at[:, pl.ds(start + lcur, _CHUNK)],
            sems.at[4])
        left_wr.start()
        left_wr.wait()

        # right run RMW (reads AFTER the left write retired: where the two
        # fixed-size windows overlap, the read sees the left run's final
        # bytes and the overlay/tail preserves them).  Same interior-chunk
        # skip, relative to the segment end: only the right window that
        # can reach past the segment (into a neighbour or untouched
        # positions) pays the read.
        @pl.when(n_left + rcur + _CHUNK > seg_len)
        def _right_rd():
            right_rd = pltpu.make_async_copy(
                out_hbm.at[:, pl.ds(start + n_left + rcur, _CHUNK)],
                dbuf.at[1], sems.at[5])
            right_rd.start()
            right_rd.wait()

        def place_right(i, k):
            g = gbuf[slot, 0, i]

            @pl.when(g == 0)
            def _():
                dbuf[1, 0, k] = obuf[slot, 0, i]

            return k + 1 - g

        m_right = jax.lax.fori_loop(0, m, place_right, jnp.int32(0))
        right_wr = pltpu.make_async_copy(
            dbuf.at[1], out_hbm.at[:, pl.ds(start + n_left + rcur, _CHUNK)],
            sems.at[5])
        right_wr.start()
        right_wr.wait()
        return (lcur + m_left, rcur + m_right)

    jax.lax.fori_loop(0, nc, move_body, (jnp.int32(0), jnp.int32(0)))


def _partition_kernel(seg_start_ref, seg_len_ref, order_hbm, go_hbm,
                      out_hbm, lc_ref, obuf, gbuf, dbuf, sems):
    """Grid (S,): one sequential step per segment.

    Scratch: ``obuf``/``gbuf`` (2, 1, _CHUNK) double-buffered input
    chunks (order / go_left), ``dbuf`` (2, 1, _CHUNK) destination RMW
    windows (left / right run), ``sems`` 6 DMA semaphores (order x2,
    go x2, left dst, right dst)."""
    s = pl.program_id(0)
    start = seg_start_ref[s]
    seg_len = seg_len_ref[s]
    nc = pl.cdiv(seg_len, _CHUNK)

    def go_copy(c, slot):
        return pltpu.make_async_copy(
            go_hbm.at[:, pl.ds(start + c * _CHUNK, _CHUNK)],
            gbuf.at[slot], sems.at[2 + slot])

    # ---- COUNT: stream go chunks (double-buffered), masked vector sum ----
    @pl.when(nc > 0)
    def _warm_count():
        go_copy(0, 0).start()

    def count_body(c, acc):
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < nc)
        def _prefetch():  # copy-in chunk c+1 while summing chunk c
            go_copy(c + 1, 1 - slot).start()

        go_copy(c, slot).wait()
        m = jnp.minimum(seg_len - c * _CHUNK, _CHUNK)
        iota = jax.lax.broadcasted_iota(jnp.int32, (1, _CHUNK), 1)
        return acc + jnp.sum(jnp.where(iota < m, gbuf[slot], 0))

    n_left = jax.lax.fori_loop(0, nc, count_body, jnp.int32(0))
    lc_ref[0, s] = n_left

    # ---- MOVE: the shared sweep (emit_move_sweep) ----
    emit_move_sweep(order_hbm, go_hbm, out_hbm, obuf, gbuf, dbuf, sems,
                    start, seg_len, n_left)


@functools.partial(jax.jit, static_argnames=("interpret",))
def partition_pallas_segments(
    order: jnp.ndarray,  # (N,) i32 — row ids, physically grouped by leaf
    seg_start: jnp.ndarray,  # (S,) i32 — start POSITION of each segment
    seg_len: jnp.ndarray,  # (S,) i32 — length (0 = inactive slot)
    go_left: jnp.ndarray,  # (N,) bool per POSITION
    *,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Stably partition every segment of ``order`` by ``go_left``.

    Returns ``(raw_order, left_counts)`` where ``raw_order`` holds the
    partitioned row ids INSIDE segments and the kernel's own untouched
    output elsewhere — merge with ``jnp.where(seg_id >= 0, raw_order,
    order)`` (the dispatcher in ops/partition.py does).  Segments must be
    disjoint.  No row cap: inputs stay HBM-resident (module docstring).
    """
    n = order.shape[0]
    S = seg_start.shape[0]
    # pad so every fixed-size chunk DMA is in range: a segment's last
    # chunk may reach up to CHUNK-1 past its end (<= n + CHUNK - 1), and
    # the RMW windows reach the same bound — out-of-range dynamic slices
    # CLAMP silently on TPU (docs/NEXT.md infra notes), so over-allocate
    # instead of relying on clamping
    n_pad = (pl.cdiv(n, _CHUNK) + 1) * _CHUNK
    order_p = jnp.pad(order, (0, n_pad - n))
    go_p = jnp.pad(go_left.astype(jnp.int32), (0, n_pad - n))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # order: HBM, DMA-chunked
            pl.BlockSpec(memory_space=pl.ANY),  # go_left: HBM
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # out: HBM, run-wise DMA
            # jaxlint: disable=R11 (left counts are O(S) segments — a few KB — not row-proportional; staging whole is the point)
            pl.BlockSpec((1, S), lambda s, *_: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, 1, _CHUNK), jnp.int32),  # order chunks (dbl-buf)
            pltpu.VMEM((2, 1, _CHUNK), jnp.int32),  # go chunks (dbl-buf)
            pltpu.VMEM((2, 1, _CHUNK), jnp.int32),  # left/right RMW windows
            pltpu.SemaphoreType.DMA((6,)),
        ],
    )
    raw, lc = pl.pallas_call(
        _partition_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, n_pad), order.dtype),
            jax.ShapeDtypeStruct((1, S), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(seg_start.astype(jnp.int32), seg_len.astype(jnp.int32),
      order_p[None], go_p[None])
    return raw[0, :n], lc[0]
